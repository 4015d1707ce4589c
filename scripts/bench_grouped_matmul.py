"""The routed layer's grouped products at the routed cells' buffer shapes: the
Pallas kernels (``ops/pallas/grouped_matmul.py``) against ``jax.lax.ragged_dot``.

    python scripts/bench_grouped_matmul.py              # on a TPU: agreement and times
    JAX_PLATFORMS=cpu python scripts/bench_grouped_matmul.py --describe
                                                        # compile only, for a described v5e

One layer's three products (``grouped_swiglu``) over a buffer of the cell's
rows, 8 held experts, group sizes as the cell's router leaves them (padded
cells: even groups; Kimi-Linear early in training: none held; Trinity: all
on one expert). Each is timed forward and forward with both gradients, 20
calls in a ``fori_loop`` whose carry feeds the next call, best of three; the
row tile and weight tile are swept on the four padded buffers. Agreement is
printed beside each time (bfloat16; and float32 at "highest" once), a line
of JSON a buffer on stdout.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dotaclient_tpu.ops.pallas import grouped_matmul as gm


def even(rows):
    return [rows // 8 + (i < rows % 8) for i in range(8)]


# name: (buffer rows, hidden, expert width, group sizes); a rollout pass's buffer and the learner's
CASES = {
    "lfm2_roll": (640, 2048, 1536, even(640)),
    "lfm2_learn": (5440, 2048, 1536, even(5440)),
    "sdar_roll6": (1440, 2048, 768, even(1440)),
    "sdar_roll5": (1200, 2048, 768, even(1200)),
    "sdar_learn": (41040, 2048, 768, even(41040)),
    "kimi_roll": (640, 2304, 1024, [0] * 8),
    "kimi_learn": (5440, 2304, 1024, [0] * 8),
    "trin_roll": (640, 2048, 1024, [80] + [0] * 7),
    "trin_learn": (5440, 2048, 1024, [680] + [0] * 7),
}
CALLS = 20


def operands(rows, H, F, sizes, dtype=jnp.bfloat16):
    k = jax.random.split(jax.random.PRNGKey(rows + H + F), 4)
    xs = jax.random.normal(k[0], (rows, H), jnp.float32).at[sum(sizes):].set(0).astype(dtype)
    wg = (jax.random.normal(k[1], (8, H, F), jnp.float32) / np.sqrt(H)).astype(dtype)
    wu = (jax.random.normal(k[2], (8, H, F), jnp.float32) / np.sqrt(H)).astype(dtype)
    wd = (jax.random.normal(k[3], (8, F, H), jnp.float32) / np.sqrt(F)).astype(dtype)
    return xs, wg, wu, wd, jnp.asarray(sizes, jnp.int32)


def ragged(xs, wg, wu, wd, load):
    mid = jax.nn.silu(jax.lax.ragged_dot(xs, wg, load)) * jax.lax.ragged_dot(xs, wu, load)
    return jax.lax.ragged_dot(mid, wd, load)


def kernel(xs, wg, wu, wd, load):
    return gm.grouped_swiglu(xs, wg, wu, wd, load, interpret=False)


def forward_loop(f):
    def run(xs, wg, wu, wd, load):
        inside = (jnp.arange(xs.shape[0]) < load.sum())[:, None]
        body = lambda i, x: (x + 1e-3 * jnp.where(inside, f(x, wg, wu, wd, load), 0)).astype(x.dtype)
        return jax.lax.fori_loop(0, CALLS, body, xs)
    return jax.jit(run)


def backward_loop(f):
    def run(xs, wg, wu, wd, load):
        inside = (jnp.arange(xs.shape[0]) < load.sum())[:, None]

        def loss(x, a, b, c):
            return jnp.sum(jnp.where(inside, f(jnp.where(inside, x, 0), a, b, c, load), 0).astype(jnp.float32))

        def body(i, carry):
            x, acc = carry
            dx, *dw = jax.grad(loss, argnums=(0, 1, 2, 3))(x, wg, wu, wd)
            acc = acc + sum(d[:, :8, :128].astype(jnp.float32).sum() for d in dw)
            return (x + 1e-3 * jnp.where(inside, dx, 0)).astype(x.dtype), acc
        return jax.lax.fori_loop(0, CALLS, body, (xs, jnp.float32(0)))
    return jax.jit(run)


def microseconds_a_call(fn, args):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t)
    return best / CALLS * 1e6


def agreement(rows, args):
    """Worst difference of the output and of each gradient, the rows inside the groups."""
    n = int(args[4].sum())
    inside = (jnp.arange(rows) < n)[:, None]

    def grads(f):
        def loss(x, a, b, c):
            y = jnp.where(inside, f(x, a, b, c, args[4]), 0).astype(jnp.float32)
            return jnp.sum(y * jnp.cos(jnp.arange(y.size, dtype=jnp.float32).reshape(y.shape)))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(*args[:4])

    worst = lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()) if a.size else 0.0
    out = {"forward": worst(jax.jit(kernel)(*args)[:n], jax.jit(ragged)(*args)[:n])}
    got, want = grads(kernel), grads(ragged)
    out["gradients"] = [worst(got[0][:n], want[0][:n])] + [worst(a, b) for a, b in zip(got[1:], want[1:])]
    out["finite"] = all(bool(jnp.isfinite(g).all()) for g in (got[0][:n], *got[1:]))
    return out


def describe():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    for name, (rows, H, F, sizes) in CASES.items():
        for dtype, precision in ((jnp.bfloat16, "default"), (jnp.float32, "highest")):
            shapes = jax.eval_shape(lambda: operands(rows, H, F, sizes, dtype))
            shapes = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip) for s in shapes]
            with jax.default_matmul_precision(precision):
                backward_loop(kernel).lower(*shapes).compile()
                forward_loop(kernel).lower(*shapes).compile()
            print(name, jnp.dtype(dtype).name, "compiled", flush=True)


def main():
    if "--describe" in sys.argv:
        return describe()
    print(jax.devices(), flush=True)
    for name, (rows, H, F, sizes) in CASES.items():
        args = operands(rows, H, F, sizes)
        r = {"agreement": agreement(rows, args)}
        for side, f in (("ragged", ragged), ("kernel", kernel)):
            r[f"{side}_forward_us"] = microseconds_a_call(forward_loop(f), args)
            r[f"{side}_backward_us"] = microseconds_a_call(backward_loop(f), args)
        print(name, json.dumps(r), flush=True)
    tiles = gm.ROW_TILES, gm._WEIGHT_TILE_BYTES
    for name in ("lfm2_roll", "sdar_roll6", "lfm2_learn", "sdar_learn"):
        rows, H, F, sizes = CASES[name]
        args = operands(rows, H, F, sizes)
        for tm in (128, 256, 512):
            for weight_bytes in (2 << 20, 4 << 20):
                gm.ROW_TILES, gm._WEIGHT_TILE_BYTES = (tm, tm), weight_bytes
                loop = forward_loop if "roll" in name else backward_loop
                print(name, "row tile", tm, "weight tile bytes", weight_bytes, microseconds_a_call(loop(kernel), args), flush=True)
    gm.ROW_TILES, gm._WEIGHT_TILE_BYTES = tiles
    with jax.default_matmul_precision("highest"):
        rows, H, F, sizes = CASES["lfm2_roll"]
        args = operands(rows, H, F, sizes, jnp.float32)
        print("lfm2_roll float32 at highest", float(jnp.abs(jax.jit(kernel)(*args) - jax.jit(ragged)(*args)).max()), flush=True)


if __name__ == "__main__":
    main()

"""A rollout pass's attention against its lanes' rings at the SDAR cell's
widths: the ring kernel (``ops/pallas/ring_attend.py``) against the path it
replaces (``models/sdar.py _attend_heads``: ``afmoe._attend`` a KV head at a
time over the whole ring).

    python scripts/bench_ring_attend.py              # on a TPU: agreement and times
    JAX_PLATFORMS=cpu python scripts/bench_ring_attend.py --describe
                                                     # compile only, for a described v5e

One lane set of the cell (15 lanes, 4 KV heads of 8 query heads, 128 wide,
rings of 18,432 rows, bfloat16) and a pass of 6 rows (the first pass) and of
5 (the others). The lanes stand at 5%, 50% and 95% of a game of 3,000 steps
(a pass sees six rows a step), or spread evenly over it; each lane's cursor
runs from a seeded offset, so ranges wrap. Each form is timed over 20 calls
in a ``fori_loop`` whose carry feeds the next call's queries, best of three;
the kernel's block rows are swept at each position. A line of JSON a case on
stdout, then the cell's dispatch as those times predict it.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dotaclient_tpu.models import sdar
from dotaclient_tpu.ops.pallas import ring_attend as ra

LANES, KV, G, D, R = 15, 4, 8, 128, 18432
GAME = 3000                      # game steps; a pass sees six ring rows a step
CALLS = 20
PLACES = {"at_5pct": 0.05, "at_50pct": 0.5, "at_95pct": 0.95, "spread": None}
PASSES = {6: sdar.FIRST_PASS, 5: sdar.SLOT_PASS}
BLOCKS = (256, 512, 1024)
# the cell (ledger, the parent's line): a dispatch of 16 rollout steps, 4 layers x 4 passes x 2 lane sets a step
CELL_DISPATCH_S, CELL_STEPS, CELL_CALLS_A_STEP = 0.986, 16, 4 * 4 * 2


def counters(place, seed=0):
    rng = np.random.default_rng(seed)
    share = PLACES[place]
    steps = (np.arange(LANES) + 0.5) / LANES * GAME if share is None else np.full(LANES, share * GAME)
    pos = (steps.astype(np.int64) * 6).astype(np.int32)
    cursor = ((rng.integers(0, R, LANES) + pos) % R).astype(np.int32)
    return jnp.asarray(pos), jnp.asarray(cursor)


def operands(N, dtype=jnp.bfloat16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = (jax.random.normal(k[0], (LANES, N, KV, G, D), jnp.float32) / np.sqrt(D)).astype(dtype)
    own = [jax.random.normal(k[i], (LANES, N, KV, D), jnp.float32).astype(dtype) for i in (1, 2)]
    rings = [
        tuple(jax.random.normal(jax.random.fold_in(k[3 + i], h), (LANES, R, D), jnp.float32).astype(dtype) for h in range(KV))
        for i in (0, 1)
    ]
    return q, own[0], own[1], tuple(rings)


def attend(N):
    """The path the kernel replaces, masks and all, as ``BlockAttention`` runs it."""
    own = jnp.asarray(PASSES[N])

    def f(q, k, v, ring, pos, cursor):
        age = (cursor[:, None] - 1 - jnp.arange(R, dtype=jnp.int32)[None, :]) % R
        see_ring = jnp.broadcast_to((age < pos[:, None])[:, None, :], (LANES, N, R))
        return sdar._attend_heads(q, k, v, ring, see_ring, jnp.broadcast_to(own[None], (LANES, N, N)))
    return f


def kernel(N, block_rows):
    return lambda q, k, v, ring, pos, cursor: ra.ring_attend(
        q, k, v, ring[0], ring[1], pos, cursor, PASSES[N], interpret=False, block_rows=block_rows
    )


def loop(f):
    def run(q, k, v, ring, pos, cursor):
        body = lambda i, q: (0.09 * f(q, k, v, ring, pos, cursor)).astype(q.dtype)
        return jax.lax.fori_loop(0, CALLS, body, q)
    return jax.jit(run)


def microseconds_a_call(fn, args):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t)
    return best / CALLS * 1e6


def describe():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    for N in PASSES:
        shapes = jax.eval_shape(lambda: (*operands(N), *counters("spread")))
        shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), shapes)
        loop(attend(N)).lower(*shapes).compile()
        for block_rows in BLOCKS:
            loop(kernel(N, block_rows)).lower(*shapes).compile()
        print(N, "rows compiled", flush=True)


def main():
    if "--describe" in sys.argv:
        return describe()
    print(jax.devices(), flush=True)
    best = {}
    for N in PASSES:
        args = operands(N)
        for place in PLACES:
            pos, cursor = counters(place)
            got = jax.jit(kernel(N, ra.BLOCK_ROWS))(*args, pos, cursor)
            want = jax.jit(attend(N))(*args, pos, cursor)
            r = {
                "rows": N, "place": place, "max_abs_diff": float(jnp.abs(got - want).max()),
                "max_abs": float(jnp.abs(want).max()),
                "read_share": float(jnp.sum(ra.visited_blocks(pos, cursor, R)[1]) * ra.BLOCK_ROWS / (LANES * R)),
                "attend_us": microseconds_a_call(loop(attend(N)), (*args, pos, cursor)),
            }
            for block_rows in BLOCKS:
                r[f"kernel_{block_rows}_us"] = microseconds_a_call(loop(kernel(N, block_rows)), (*args, pos, cursor))
            best[N, place] = r
            print(json.dumps(r), flush=True)
    # float32 once, against the reference at the highest precision
    args = operands(6, jnp.float32)
    pos, cursor = counters("spread")
    with jax.default_matmul_precision("highest"):
        diff = jnp.abs(jax.jit(kernel(6, ra.BLOCK_ROWS))(*args, pos, cursor) - jax.jit(attend(6))(*args, pos, cursor)).max()
    print("float32 spread max_abs_diff", float(diff), flush=True)
    # the cell's dispatch as these times predict it: one first pass and three of five rows a step
    for place in PLACES:
        saved = sum(
            (best[N, place]["attend_us"] - best[N, place][f"kernel_{ra.BLOCK_ROWS}_us"]) * n
            for N, n in ((6, 1), (5, 3))
        ) * CELL_CALLS_A_STEP / 4 * CELL_STEPS * 1e-6
        print(json.dumps({"place": place, "dispatch_s_saved": saved, "dispatch_s": CELL_DISPATCH_S - saved,
                          "frames_per_s_gain": CELL_DISPATCH_S / (CELL_DISPATCH_S - saved) - 1}), flush=True)


if __name__ == "__main__":
    main()

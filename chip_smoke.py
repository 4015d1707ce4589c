"""Chip smoke: drive the trainer and the server once on the real TPU.

    python chip_smoke.py                   # on a machine with a TPU
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --rehearse-cpu    # control-flow rehearsal

One process, the only one that touches the device, using every chip it can
see (``MeshConfig.data_parallel=-1``), so the same file passes on one chip
and on a four-chip host. It fails at once, before any phase, unless
``jax.devices()[0].platform == "tpu"``. Each phase goes through the module's
own ``main(argv)`` at the full default ``ModelConfig`` width on benchmark
config 1's shape (128 envs, 1v1 vs scripted_easy, T=16), is fatal on
failure, and prints its compile seconds and steady seconds apart:

  a. fused trainer      ``train.learner.main --actor fused``
  b. buffered trainer   ``train.learner.main --actor device``, which writes
                        the checkpoint phase c serves (on this device, in
                        this run: a CPU-made checkpoint is not an input)
  c. server             ``serve.__main__.main`` on that checkpoint, with a
                        fleet of ``ServeClient`` sessions (threads of this
                        process) over the real socket lane
  d. transformer core   fused, ``--core transformer``, two dispatches
  e. the Pallas kernel  ``lstm_sequence_pallas(interpret=False)`` against
                        ``lstm_sequence_reference`` at H=128 and H=512
  f. the KDA step kernel ``kda_step_pallas(interpret=False)`` through
                        ``kimilinear.delta_rule_step`` against
                        ``kimilinear.delta_rule_chunk`` at one step, at the
                        Kimi-Linear cell's shapes (40 lanes, 32 heads of
                        128 x 128), the state donated

What it claims: the programs compile for the device, values are finite,
counters add up, every request is answered with an action legal under its
masks, lanes and batches are spread over every chip (read off the arrays),
the kernel run twice returns the same bits and matches its reference. It
does NOT claim bitwise equality between two different compiled programs,
and it claims no speed: its times are start-up times.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
``--rehearse-cpu`` runs the same phases at a tiny size on the CPU (Pallas in
interpret mode), labels every line a rehearsal, and never prints that line.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import signal
import socket
import sys
import threading
import time
import traceback
from importlib import metadata
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# sizes: the chip run is benchmark config 1 (scripts/bench_configs.py) at the
# full default model width; the rehearsal shrinks lanes, T and B only
FULL = dict(
    n_envs=128, ppo=None, buffer="capacity_rollouts=512,min_fill=128",
    rollout_len=16, batch_rollouts=32, games=8, ticks=20,
    kernel_b=32, kernel_t=16, kda_lanes=40, kda_heads=32,
)
REHEARSAL = dict(
    n_envs=12, ppo="rollout_len=4,batch_rollouts=4",
    buffer="capacity_rollouts=32,min_fill=8",
    rollout_len=4, batch_rollouts=4, games=4, ticks=5,
    kernel_b=8, kernel_t=4, kda_lanes=3, kda_heads=2,
)
# One log boundary (log_every=10), so the record holds a loss. Both sizes
# collect n_envs rollouts at a time and n_envs / batch_rollouts does not
# divide 10: the buffered run ends with a train batch still in the ring.
TRAIN_STEPS = 10


def device_summary(devices: Sequence[Any]) -> Dict[str, Any]:
    """The device as JAX reports it (the pass line's ``device`` object)."""
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_tpu(devices: Sequence[Any]) -> None:
    """Exit non-zero, saying what was found, unless the devices are TPUs."""
    found = device_summary(devices)
    if found["platform"] != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU, found platform={found['platform']!r} "
            f"kind={found['kind']!r} count={found['count']} — no phase run, "
            f"no result printed (a CPU rehearsal is --rehearse-cpu)"
        )


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or loading from
    the persistent cache), and its cache hits and misses — summed over every
    thread of the process from ``jax.monitoring`` events."""

    _TIMED = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_: Any) -> None:
        if event in self._TIMED:
            with self._lock:
                self.seconds += duration

    def _on_event(self, event: str, **_: Any) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def read(self) -> Tuple[float, int, int]:
        with self._lock:
            return self.seconds, self.hits, self.misses


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# -- phases a, b, d: the trainer ---------------------------------------------


def run_learner(argv: List[str]) -> Tuple[Dict[str, float], Any]:
    """``train.learner.main(argv)``, keeping hold of the ``Learner`` it
    builds so the caller can read shardings off its arrays afterwards."""
    from dotaclient_tpu.train import learner as learner_mod

    built: List[Any] = []

    class KeptLearner(learner_mod.Learner):
        def __init__(self, *args: Any, **kwargs: Any) -> None:
            super().__init__(*args, **kwargs)
            built.append(self)

    original = learner_mod.Learner
    learner_mod.Learner = KeptLearner
    try:
        stats = learner_mod.main(argv)
    finally:
        learner_mod.Learner = original
    return stats, built[0]


def check_training_record(
    stats: Dict[str, float], jsonl: str, frames_expected: int
) -> Dict[str, float]:
    from dotaclient_tpu.utils import telemetry

    check(
        stats["frames_trained"] == frames_expected,
        f"frames_trained {stats['frames_trained']} != {frames_expected}",
    )
    records = [json.loads(ln) for ln in telemetry.load_jsonl(jsonl)]
    logged = [r["scalars"] for r in records if "loss" in r.get("scalars", {})]
    check(bool(logged), f"no record with a loss in {jsonl}")
    last = logged[-1]
    for key in ("loss", "grad_norm"):
        value = last.get(key)
        check(
            value is not None and math.isfinite(value),
            f"{key} not finite in {jsonl}: {value}",
        )
    check(last.get("health_ok") == 1.0, f"health_ok {last.get('health_ok')}")
    return {"loss": last["loss"], "grad_norm": last["grad_norm"]}


def check_spread(leaves: Sequence[Any], n_rows: int, what: str) -> None:
    """From the arrays, not from config: every leaf (leading axis
    ``n_rows``) holds a DISTINCT row block on every device — ``parallel/
    mesh.row_sharding`` silently replicates what does not divide."""
    devices = jax.devices()
    for x in leaves:
        check(x.shape[0] == n_rows, f"{what}: leaf {x.shape} rows != {n_rows}")
        check(
            x.sharding.device_set == set(devices),
            f"{what}: leaf {x.shape} lives on {len(x.sharding.device_set)} "
            f"of {len(devices)} devices",
        )
        rows = {s.index[0].indices(n_rows)[:2] for s in x.addressable_shards}
        check(
            len(rows) == len(devices),
            f"{what}: leaf {x.shape} has {len(rows)} distinct row blocks "
            f"over {len(devices)} devices (replicated?)",
        )


def check_device_memory() -> None:
    for dev in jax.local_devices():
        stats = dev.memory_stats()   # a dict on TPU, None on CPU
        if stats is not None:
            check(stats["bytes_in_use"] > 0, f"{dev}: bytes_in_use == 0")


def trainer_argv(size: Dict[str, Any], out: str, name: str) -> List[str]:
    argv = [
        "--n-envs", str(size["n_envs"]), "--opponent", "scripted_easy",
        "--team-size", "1", "--steps", str(TRAIN_STEPS),
        "--metrics-jsonl", os.path.join(out, f"{name}.jsonl"),
    ]
    if size["ppo"]:
        argv += ["--ppo", size["ppo"]]
    return argv


def phase_fused(
    size: Dict[str, Any], out: str, name: str = "a_fused",
    extra: Sequence[str] = (),
) -> Dict[str, Any]:
    argv = trainer_argv(size, out, name) + ["--actor", "fused", *extra]
    stats, learner = run_learner(argv)
    lanes = size["n_envs"]   # 1v1 vs a scripted bot: one learner lane a game
    got = check_training_record(
        stats, os.path.join(out, f"{name}.jsonl"),
        lanes * size["rollout_len"] * TRAIN_STEPS,
    )
    actor = learner.device_actor
    check(actor.n_lanes == lanes, f"lane count {actor.n_lanes} != {lanes}")
    # per-lane carries and returns, per-game keys (1v1: a game is a lane)
    check_spread(
        jax.tree.leaves(
            (actor.state.carry, actor.state.ep_return, actor.state.key)
        ),
        lanes, "fused actor state",
    )
    check_device_memory()
    return got


def phase_buffered(size: Dict[str, Any], out: str) -> Dict[str, Any]:
    name = "b_buffered"
    argv = trainer_argv(size, out, name) + [
        "--actor", "device", "--buffer", size["buffer"],
        "--checkpoint-dir", os.path.join(out, "ckpt"),
    ]
    stats, learner = run_learner(argv)
    got = check_training_record(
        stats, os.path.join(out, f"{name}.jsonl"),
        size["batch_rollouts"] * size["rollout_len"] * TRAIN_STEPS,
    )
    # the ring still holds unconsumed rollouts: gather one more train batch
    # the way the loop does and read its layout
    batch = learner.buffer.take()
    check(batch is not None, "ring underfilled after the run")
    check_spread(
        jax.tree.leaves(batch), size["batch_rollouts"], "buffered train batch"
    )
    check_device_memory()
    check(
        learner.ckpt.latest_step() == TRAIN_STEPS,
        f"checkpoint at step {learner.ckpt.latest_step()}",
    )
    return got


# -- phase c: the server -----------------------------------------------------


def masked_obs(config: Any, rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """``serve_loadgen.synthetic_obs`` with random legality masks (at least
    one legal entry each), so "legal under its mask" is a real claim."""
    from serve_loadgen import synthetic_obs

    obs = synthetic_obs(config, rng)
    for key in ("mask_action_type", "mask_target_unit", "mask_cast_target",
                "mask_ability"):
        mask = rng.random(obs[key].shape) < 0.5
        mask[rng.integers(mask.size)] = True
        obs[key] = mask
    return obs


def action_is_legal(obs: Dict[str, np.ndarray], act: Dict[str, int]) -> bool:
    from dotaclient_tpu.models import distributions as D

    kind = act["action_type"]
    if not obs["mask_action_type"][kind]:
        return False
    if kind == D.A_ATTACK:
        return bool(obs["mask_target_unit"][act["target_unit"]])
    if kind == D.A_CAST:
        return bool(
            obs["mask_cast_target"][act["target_unit"]]
            and obs["mask_ability"][act["ability"]]
        )
    return True


def play_games(
    port: int, config: Any, games: int, ticks: int,
    stop: threading.Event,
) -> List[str]:
    """``games`` concurrent ``ServeClient`` sessions of ``ticks`` requests;
    returns the failures (empty = every request answered, legally)."""
    from dotaclient_tpu.serve.client import ServeClient

    failures: List[str] = []

    def game(gi: int) -> None:
        rng = np.random.default_rng(1000 + gi)
        client = None
        t_give_up = time.monotonic() + 600.0
        while client is None:   # the server is still restoring / binding
            try:
                client = ServeClient(
                    "127.0.0.1", port, config, should_abort=stop.is_set
                )
            except (ConnectionError, OSError) as e:
                if stop.is_set() or time.monotonic() > t_give_up:
                    failures.append(f"game {gi}: never attached ({e})")
                    return
                time.sleep(0.2)
        try:
            for t in range(ticks):
                obs = masked_obs(config, rng)
                act = client.step(obs, reset=(t == 0))
                if not action_is_legal(obs, act):
                    failures.append(f"game {gi} tick {t}: illegal {act}")
                # a log-probability: <= 0 up to f32 rounding of the
                # five heads' log-softmax sums (the chip showed +4e-6)
                if not (math.isfinite(client.last_logp)
                        and client.last_logp <= 1e-4):
                    failures.append(
                        f"game {gi} tick {t}: logp {client.last_logp}"
                    )
        except Exception as e:  # noqa: BLE001 - ServeDeadlineError included
            failures.append(f"game {gi}: {type(e).__name__}: {e}")
        finally:
            client.close()

    threads = [
        threading.Thread(target=game, args=(g,), name=f"game-{g}")
        for g in range(games)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=900.0)
        if th.is_alive():
            failures.append(f"{th.name}: did not finish")
    return failures


def phase_serve(size: Dict[str, Any], out: str) -> Dict[str, Any]:
    from dotaclient_tpu.serve.__main__ import main as serve_main
    from dotaclient_tpu.utils import telemetry
    from dotaclient_tpu.utils.checkpoint import CheckpointManager

    ckpt = os.path.join(out, "ckpt")
    mgr = CheckpointManager(ckpt)
    try:
        config = mgr.restore_config()
    finally:
        mgr.close()
    with socket.socket() as s:   # a free port the clients can know up front
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    stop = threading.Event()
    handoff = threading.Lock()
    failures: List[str] = []
    main_thread = threading.main_thread().ident

    def fleet() -> None:
        try:
            failures.extend(
                play_games(port, config, size["games"], size["ticks"], stop)
            )
        except BaseException as e:  # noqa: BLE001 - reported below
            failures.append(f"fleet: {type(e).__name__}: {e}")
        finally:
            # the server's own clean-stop path is KeyboardInterrupt
            with handoff:
                if not stop.is_set():
                    signal.pthread_kill(main_thread, signal.SIGINT)

    fleet_thread = threading.Thread(target=fleet, name="serve-fleet")
    before = telemetry.get_registry().snapshot()
    fleet_thread.start()
    try:
        rc = serve_main([
            "--checkpoint", ckpt, "--serve-listen", f"127.0.0.1:{port}",
            "--duration", "900",
            "--serve-metrics-jsonl", os.path.join(out, "c_serve.jsonl"),
        ])
    finally:
        with handoff:
            stop.set()
        fleet_thread.join(timeout=60.0)
    check(rc == 0, f"serve main returned {rc}")
    check(not fleet_thread.is_alive(), "client fleet still running")
    check(not failures, "; ".join(failures[:5]))
    after = telemetry.get_registry().snapshot()
    asked = size["games"] * size["ticks"]

    def grew(key: str) -> float:
        return after.get(key, 0.0) - before.get(key, 0.0)

    check(grew("serve/replies_total") == asked,
          f"replies {grew('serve/replies_total')} != {asked}")
    check(grew("serve/dispatches_total") > 0, "no dispatch")
    check(grew("serve/dispatch_errors_total") == 0, "dispatch errors")
    check(grew("serve/reply_errors_total") == 0, "reply errors")
    return {
        "requests": asked,
        "dispatches": int(grew("serve/dispatches_total")),
        "p99_ms": round(after.get("serve/p99_latency_ms", 0.0), 3),
    }


# -- phase e: the Pallas kernel ----------------------------------------------


def phase_kernel(size: Dict[str, Any], interpret: bool) -> Dict[str, Any]:
    from dotaclient_tpu.ops.pallas import (
        lstm_sequence_pallas,
        lstm_sequence_reference,
    )

    B, T = size["kernel_b"], size["kernel_t"]
    got: Dict[str, Any] = {}
    for H in (128, 512):
        rng = np.random.default_rng(H)

        def f(*shape: int, scale: float = 1.0) -> jnp.ndarray:
            return jnp.asarray(
                rng.normal(size=shape).astype(np.float32) * scale
            )

        args = (
            f(B, T, H), f(B, H), f(B, H),
            f(H, 4 * H, scale=H ** -0.5), f(H, 4 * H, scale=H ** -0.5),
            f(4 * H, scale=0.1),
            jnp.asarray((rng.random((B, T)) < 0.2).astype(np.float32)),
        )
        kernel = jax.jit(
            lambda *a: lstm_sequence_pallas(*a, interpret)
        )
        reference = jax.jit(lstm_sequence_reference)
        # Two programs per width. "default" is what a caller gets: on the
        # TPU neither side contracts f32 at full precision (bf16 MXU
        # passes, eps 2^-8), so they agree to bf16 rounding, not f32's.
        # "highest" asks both for full-f32 contractions (the kernel's dots
        # read the same default at trace time) and pins the math itself.
        # Tolerances from those dtypes, |h| <= 1; the v5e measured 3e-4
        # and 5e-6 (PR 21).
        for precision, tol in (("default", 1e-2), ("highest", 1e-4)):
            with jax.default_matmul_precision(precision):
                out_k = jax.block_until_ready(kernel(*args))
                out_k2 = jax.block_until_ready(kernel(*args))
                out_r = jax.block_until_ready(reference(*args))
            err = 0.0
            for a, a2, r in zip(
                jax.tree.leaves(out_k), jax.tree.leaves(out_k2),
                jax.tree.leaves(out_r),
            ):
                a, a2, r = np.asarray(a), np.asarray(a2), np.asarray(r)
                what = f"H={H} {precision}"
                check(a.shape == r.shape, f"{what}: shape {a.shape}")
                check(bool(np.isfinite(a).all()), f"{what}: not finite")
                check(bool((a == a2).all()), f"{what}: not deterministic")
                err = max(err, float(np.abs(a - r).max()))
            check(err <= tol, f"H={H} {precision}: max |kernel - reference| "
                  f"= {err:.3e} > {tol}")
            got[f"H{H}_{precision}_max_abs_err"] = float(f"{err:.3e}")
    return got


# -- phase f: the KDA step kernel ----------------------------------------------


def phase_kda_step(size: Dict[str, Any], interpret: bool) -> Dict[str, Any]:
    """One step of the delta rule through the kernel against the closed form
    at ``Precision.HIGHEST`` (whose few-rows branch multiplies and reduces in
    float32 whatever the precision: the kernel has no product to round), a
    quarter of the lanes void with a poisoned state; the donated state's
    buffer is the new state's."""
    from dotaclient_tpu.models.kimilinear import delta_rule_chunk, delta_rule_step

    B, h, d = size["kda_lanes"], size["kda_heads"], 128
    rng = np.random.default_rng(36)

    def f(*shape: int) -> np.ndarray:
        return rng.standard_normal(shape).astype(np.float32)

    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    carried = rng.random(B) < 0.75
    S0 = np.where(carried[:, None, None, None], f(B, h, d, d) * 0.5, np.float32(1e30))
    rows = tuple(jnp.asarray(x) for x in (
        unit(f(B, 1, h, d)) / math.sqrt(d), unit(f(B, 1, h, d)), f(B, 1, h, d),
        -np.abs(f(B, 1, h, d)) * 0.3, 1.0 / (1.0 + np.exp(-f(B, 1, h))),
    ))
    seg, carried = jnp.zeros((B, 1), jnp.int32), jnp.asarray(carried)
    with jax.default_matmul_precision("highest"):
        o_r, S_r = jax.block_until_ready(jax.jit(delta_rule_chunk)(*rows, jnp.asarray(S0), seg, carried))
    step = jax.jit(
        lambda S, *r: delta_rule_step(interpret, *r, S, seg, carried), donate_argnums=(0,)
    ).lower(jnp.asarray(S0), *rows).compile()
    got = []
    for _ in range(2):
        state = jnp.asarray(S0)
        held = state.unsafe_buffer_pointer()
        o, S = jax.block_until_ready(step(state, *rows))
        check(state.is_deleted(), "the donated state was not taken")
        got.append((np.asarray(o), np.asarray(S), S.unsafe_buffer_pointer() == held))
    (o, S, in_place), (o2, S2, _) = got
    check(bool(np.isfinite(o).all() and np.isfinite(S).all()), "kda step: not finite")
    check(bool((o == o2).all() and (S == S2).all()), "kda step: not deterministic")
    err_o, err_S = float(np.abs(o - np.asarray(o_r)).max()), float(np.abs(S - np.asarray(S_r)).max())
    check(err_o <= 1e-5 and err_S <= 1e-5, f"kda step: max |kernel - closed form| = {err_o:.3e} (o), {err_S:.3e} (S) > 1e-5")
    # compiled for a TPU the program names its alias; the interpreter's rehearsal has no custom call to name
    aliased = "output_to_operand_aliasing" in step.as_text()
    check(interpret or (aliased and in_place), f"kda step: state not updated in place (aliased={aliased}, same buffer={in_place})")
    return {
        "lanes": B, "heads": h, "o_max_abs_err": float(f"{err_o:.3e}"), "S_max_abs_err": float(f"{err_S:.3e}"),
        "output_aliases_input": aliased, "same_buffer": bool(in_place),
    }


# -- driver ------------------------------------------------------------------


def main(argv: Any = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--rehearse-cpu", action="store_true",
        help="run the phases at a tiny size on the CPU (Pallas interpreted); "
        "every line is labelled a rehearsal and no pass line is printed",
    )
    p.add_argument(
        "--out", default=os.path.join(REPO, "chiprun_out", "chip_smoke"),
        help="directory for the checkpoint and the metrics records",
    )
    args = p.parse_args(argv)
    tag = "REHEARSAL(cpu) " if args.rehearse_cpu else ""

    def say(text: str) -> None:
        print(f"{tag}chip_smoke: {text}", flush=True)

    devices = jax.devices()
    if args.rehearse_cpu:
        if devices[0].platform != "cpu":
            sys.exit("chip_smoke: --rehearse-cpu needs JAX_PLATFORMS=cpu")
    else:
        require_tpu(devices)

    sys.path.insert(0, os.path.join(REPO, "scripts"))   # serve_loadgen
    from dotaclient_tpu.native import load_library
    from dotaclient_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    found = device_summary(devices)
    say(
        f"platform={found['platform']} device_kind={found['kind']!r} "
        f"count={found['count']} jax={jax.__version__} "
        f"jaxlib={metadata.version('jaxlib')} "
        f"libtpu={metadata.version('libtpu')} compile_cache={cache_dir} "
        f"rollout_codec={'native' if load_library() is not None else 'python'}"
    )

    # an earlier run's records: the sinks append, and the learner would
    # decline to save over a checkpoint at the same step
    os.makedirs(args.out, exist_ok=True)
    shutil.rmtree(os.path.join(args.out, "ckpt"), ignore_errors=True)
    for stale in glob.glob(os.path.join(args.out, "*.jsonl")):
        os.unlink(stale)

    size = REHEARSAL if args.rehearse_cpu else FULL
    runners: Dict[str, Tuple[str, Callable[[], Dict[str, Any]]]] = {
        "a": ("fused trainer", lambda: phase_fused(size, args.out)),
        "b": ("buffered trainer", lambda: phase_buffered(size, args.out)),
        "c": ("server", lambda: phase_serve(size, args.out)),
        "d": ("transformer core, fused", lambda: phase_fused(
            size, args.out, "d_transformer",
            ("--core", "transformer", "--steps-per-dispatch", "5"),
        )),
        "e": ("pallas lstm kernel", lambda: phase_kernel(
            size, interpret=args.rehearse_cpu
        )),
        "f": ("pallas kda step kernel", lambda: phase_kda_step(
            size, interpret=args.rehearse_cpu
        )),
    }
    clock = CompileClock()
    summary: Dict[str, Any] = {}
    try:
        for key, (title, run) in runners.items():
            say(f"phase {key} ({title}) ...")
            c0, h0, m0 = clock.read()
            t0 = time.perf_counter()
            try:
                detail = run()
            except BaseException:  # noqa: BLE001 - every failure is fatal
                traceback.print_exc()
                say(f"phase {key} ({title}) FAIL")
                return 1
            wall = time.perf_counter() - t0
            c1, h1, m1 = clock.read()
            summary[key] = {
                "compile_s": round(c1 - c0, 2),
                "steady_s": round(wall - (c1 - c0), 2),
                "cache_hits": h1 - h0, "cache_misses": m1 - m0, **detail,
            }
            say(f"phase {key} ({title}) PASS {json.dumps(summary[key])}")
    finally:
        # the full-pipeline checkpoint (ring + actor state, tens of MB) was
        # only phase c's input; the metrics records stay
        shutil.rmtree(os.path.join(args.out, "ckpt"), ignore_errors=True)

    say(f"phases {json.dumps(summary, sort_keys=True)}")
    if args.rehearse_cpu:
        say("rehearsal complete: control flow only, nothing here is a "
            "device result")
        return 0
    print(json.dumps({"ok": True, "device": found}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Synthetic load generator for the policy-serving plane (ISSUE 11).

Drives N concurrent synthetic clients — each one attached game sending
sequential step requests, exactly the serve protocol's cadence — against a
``PolicyServer`` and reports the headline serving curve: actions/sec and
request-latency percentiles (closed loop; the curve is not measured on
chip: ROADMAP.md S6/R6). Run standalone against a live ``python -m dotaclient_tpu.serve`` server:

    python scripts/serve_loadgen.py --addr 127.0.0.1:7788 \
        --clients 32 --requests 100
    python scripts/serve_loadgen.py --addr 127.0.0.1:7788 \
        --serve request_wire_dtype=bfloat16     # narrow request payloads

Prints one JSON line: actions/sec, p50/p99 latency ms, reply versions seen.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time
from typing import Dict, List

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:  # direct `python scripts/...` invocation
    sys.path.insert(0, _REPO)


def synthetic_obs(config, rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """One plausible random observation (unbatched leaves, template
    dtypes/shapes; integer leaves respect the config's declared bounds so
    the bf16 request wire's exact int casts hold)."""
    obs_spec, act = config.obs, config.actions
    U = obs_spec.max_units
    return {
        "units": rng.normal(size=(U, obs_spec.unit_features)).astype(np.float32),
        "unit_mask": np.ones((U,), bool),
        "unit_handles": rng.integers(0, U, size=(U,)).astype(np.int32),
        "globals": rng.normal(size=(obs_spec.global_features,)).astype(np.float32),
        "hero_id": np.asarray(
            rng.integers(0, config.model.n_hero_ids), np.int32
        ),
        "mask_action_type": np.ones((act.n_action_types,), bool),
        "mask_target_unit": np.ones((act.max_units,), bool),
        "mask_cast_target": np.ones((act.max_units,), bool),
        "mask_ability": np.ones((act.max_abilities,), bool),
    }


def run_loadgen(
    host: str,
    port: int,
    config,
    n_clients: int = 16,
    requests_per_client: int = 50,
    seed: int = 0,
    router: bool = False,
    max_reconnects: int = 6,
    should_abort=None,
    collect_samples: bool = False,
    think_s: float = 0.0,
) -> Dict[str, float]:
    """N threads × R sequential requests each; returns the serving curve
    numbers. The wall clock covers first-send→last-reply across the whole
    fleet, so actions/sec reflects the server's real coalescing, not a
    single connection's round-trip ceiling.

    ``router=True`` points ``--addr`` at a ``SessionRouter`` instead of a
    backend: clients attach through it and ride its redirects when a
    backend dies mid-run (ISSUE 19) — the summary then also reports how
    many sessions re-homed and how many requests missed their deadline.
    ``collect_samples`` additionally returns per-reply ``(t_end, latency,
    client)`` tuples (monotonic clock) so callers can split the latency
    curve around a failover event. ``think_s``
    sleeps between a client's requests — a game's frame cadence, which
    stretches the run so a chaos plan can land faults mid-game."""
    from dotaclient_tpu.serve.client import ServeClient, ServeDeadlineError

    latencies: List[List[float]] = [[] for _ in range(n_clients)]
    samples: List[tuple] = []
    samples_lock = threading.Lock()
    versions: set = set()
    errors: List[str] = []
    deadline_errors = [0]
    rehomed = [0]
    barrier = threading.Barrier(n_clients + 1)

    def worker(ci: int) -> None:
        rng = np.random.default_rng(seed + ci)
        try:
            client = ServeClient(
                host, port, config, router=router,
                max_reconnects=max_reconnects, should_abort=should_abort,
            )
        except Exception as e:  # attach failed (slots exhausted?)
            errors.append(f"attach: {type(e).__name__}: {e}")
            barrier.wait()
            return
        try:
            barrier.wait()   # fleet starts together: real contention
            for r in range(requests_per_client):
                if should_abort is not None and should_abort():
                    errors.append("abort: stop requested")
                    return
                if think_s > 0 and r:
                    time.sleep(think_s)
                try:
                    client.step(synthetic_obs(config, rng), reset=(r == 0))
                except ServeDeadlineError as e:
                    # the typed bounded failure: counted, run continues —
                    # a fleet with spare capacity should absorb it
                    with samples_lock:
                        deadline_errors[0] += 1
                    errors.append(f"deadline: {e}")
                    continue
                latencies[ci].append(client.last_latency_s)
                versions.add(client.last_version)
                if collect_samples:
                    with samples_lock:
                        samples.append(
                            (time.monotonic(), client.last_latency_s, ci)
                        )
        except Exception as e:
            errors.append(f"step: {type(e).__name__}: {e}")
        finally:
            with samples_lock:
                rehomed[0] += client.rehomed_count
            client.close()

    threads = [
        threading.Thread(target=worker, args=(i,), daemon=True)
        for i in range(n_clients)
    ]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    flat = sorted(s for per in latencies for s in per)
    n = len(flat)
    out = {
        "clients": n_clients,
        "requests_per_client": requests_per_client,
        "replies": n,
        "errors": len(errors),
        "error_sample": errors[:3],
        "deadline_errors": deadline_errors[0],
        "sessions_rehomed": rehomed[0],
        "actions_per_sec": round(n / wall, 1) if wall > 0 else 0.0,
        "p50_ms": round(flat[n // 2] * 1e3, 3) if n else 0.0,
        "p99_ms": round(flat[min(n - 1, int(n * 0.99))] * 1e3, 3) if n else 0.0,
        "versions_seen": sorted(versions),
    }
    if collect_samples:
        out["samples"] = samples
    return out


def _wait_until(pred, timeout=30.0, poll=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(poll)
    return pred()


def run_rehome_parity(
    seed: int = 0,
    n_pre: int = 5,
    n_post: int = 5,
    metrics_jsonl=None,
) -> Dict[str, object]:
    """The re-home parity digest (ISSUE 19 acceptance): prove that a
    session yanked off a SIGKILL'd backend and re-homed onto a promoted
    hot spare resumes BIT-EXACT under carry-shadow.

    In-process fixture: two live backends + one spare behind a
    ``SessionRouter`` (all sharing one param tree and serve seed), one
    client per live backend, ``max_batch=1`` / zero window so every
    request is its own dispatch. After ``n_pre`` steps each, the first
    client's backend dies abruptly (listener + conns torn down — the
    in-process equivalent of SIGKILL for the wire); its next step rides
    the router redirect to the promoted spare and resends the shadowed
    carry row. Every reply of BOTH games — the re-homed one and the
    uninterrupted control — is then replayed through
    ``ServeEngine.reference_step`` threading reference carry stores, with
    the boundary modelled as a host copy of the carry row between stores.
    ``parity == "bitwise"`` requires zero mismatches AND the teeth check:
    replaying the first post-kill step from a ZEROED carry must disagree,
    so a carry the model ignores cannot fake a pass.

    Returns the digest dict; the chaos ``serve_failover`` scenario,
    ci_gate.sh, and the tier-2 router tests all gate on it."""
    import jax
    import jax.numpy as jnp

    from dotaclient_tpu.config import ModelConfig, RunConfig
    from dotaclient_tpu.models.policy import init_params
    from dotaclient_tpu.serve import (
        PolicyServer,
        ServeClient,
        ServeEngine,
        SessionRouter,
        make_inference_policy,
    )
    from dotaclient_tpu.utils import telemetry

    cfg = RunConfig()
    cfg = dataclasses.replace(
        cfg,
        model=ModelConfig(unit_embed_dim=8, hidden_dim=8, hero_embed_dim=4),
        serve=dataclasses.replace(
            cfg.serve,
            # one request per dispatch: the recorded dispatch_idx stream
            # is exactly the replay schedule
            max_batch=1, batch_window_ms=0.0, max_slots=4,
            carry_shadow=True, request_wire_dtype="float32",
            request_deadline_s=20.0, request_retries=16,
            router_probe_s=0.1, router_dead_after_s=0.4,
            seed=seed,
        ),
    )
    policy = make_inference_policy(cfg)
    params = init_params(policy, jax.random.PRNGKey(seed))
    regs = [telemetry.Registry() for _ in range(3)]
    engines = [ServeEngine(cfg, policy, params, registry=r) for r in regs]
    servers = [
        PolicyServer(e, cfg, registry=r) for e, r in zip(engines, regs)
    ]
    addrs = [tuple(s.address) for s in servers]
    rreg = telemetry.Registry()
    router = SessionRouter(
        cfg, list(addrs[:2]), spares=[addrs[2]], registry=rreg,
    )

    def rgauges() -> Dict[str, float]:
        counters, gauges = rreg.counters_and_gauges()
        return {**counters, **gauges}

    clients: List[ServeClient] = []
    records: List[List[dict]] = [[], []]
    try:
        assert _wait_until(
            lambda: rgauges().get("router/backends_live", 0) >= 2
            and rgauges().get("router/spares_available", 0) >= 1,
            timeout=15.0,
        ), "router probes never confirmed the fleet live"
        rh, rp = router.address[0], int(router.address[1])
        clients = [ServeClient(rh, rp, cfg, router=True) for _ in range(2)]
        vic = next(
            i for i, c in enumerate(clients)
            if tuple(c.backend_addr) == addrs[0]
        )
        rngs = [np.random.default_rng(seed + 100 + i) for i in range(2)]

        def step_and_record(ci: int, reset: bool) -> None:
            obs = synthetic_obs(cfg, rngs[ci])
            t0 = time.monotonic()
            clients[ci].step(obs, reset=reset)
            c = clients[ci]
            records[ci].append(dict(
                addr=tuple(c.backend_addr), slot=c.slot,
                didx=c.last_dispatch_idx, obs=obs, reset=reset,
                packed=np.array(c.last_packed, copy=True),
                logp=c.last_logp, wall_s=time.monotonic() - t0,
            ))

        for r in range(n_pre):
            step_and_record(0, r == 0)
            step_and_record(1, r == 0)
        # abrupt death of the victim's backend: listener and live conns
        # torn down at once — what the wire sees from a SIGKILL
        servers[0].close()
        engines[0].stop()
        for r in range(n_post):
            step_and_record(vic, False)
            step_and_record(1 - vic, False)
        rehomed_count = clients[vic].rehomed_count
        rehomed_to = tuple(clients[vic].backend_addr)
    finally:
        for c in clients:
            try:
                c.close()
            except (OSError, ConnectionError):
                pass
        router.close()
        for s in servers[1:]:
            s.close()
        for e in engines[1:]:
            e.stop()

    # ---- reference replay: one carry store per backend, the boundary is
    # a host row copy between stores (exactly what the shadow wire does)
    ref = engines[1]   # same compiled program, params, and serve seed
    S = cfg.serve.max_slots

    def fresh_store():
        return jax.tree.map(jnp.asarray, policy.initial_state(S + 1))

    stores: Dict[tuple, object] = {}
    mismatches = 0
    boundary_rec = None
    for ci in (0, 1):
        prev = None
        for rec in records[ci]:
            addr = rec["addr"]
            if addr not in stores:
                stores[addr] = fresh_store()
            if prev is not None and prev["addr"] != addr:
                boundary_rec = rec
                row = jax.tree.map(
                    lambda c: np.asarray(c)[prev["slot"]],
                    stores[prev["addr"]],
                )
                stores[addr] = jax.tree.map(
                    lambda c, r: c.at[rec["slot"]].set(
                        jnp.asarray(r).astype(c.dtype)
                    ),
                    stores[addr], row,
                )
            packed, logp, stores[addr] = ref.reference_step(
                [rec["obs"]], [rec["slot"]],
                [1.0 if rec["reset"] else 0.0],
                stores[addr], rec["didx"],
            )
            if not (
                np.array_equal(packed[0], rec["packed"])
                and float(logp[0]) == rec["logp"]
            ):
                mismatches += 1
            prev = rec

    # teeth: the same post-kill step from a ZEROED carry must disagree,
    # or the parity above proves nothing about the carry transfer
    teeth = False
    if boundary_rec is not None:
        _p, zlogp, _ = ref.reference_step(
            [boundary_rec["obs"]], [boundary_rec["slot"]], [0.0],
            fresh_store(), boundary_rec["didx"],
        )
        teeth = float(zlogp[0]) != boundary_rec["logp"]

    snap = rgauges()
    if metrics_jsonl:
        # one router-registry snapshot line: ci_gate validates the
        # --require-router schema tier against this
        sink = telemetry.JsonlSink(metrics_jsonl)
        sink.emit(1, snap)
        sink.close()
    if boundary_rec is None:
        parity = "FAIL: the victim session never re-homed"
    elif mismatches:
        parity = f"FAIL: {mismatches} step(s) diverged from the reference"
    elif not teeth:
        parity = "FAIL: teeth check (zero-carry replay matched too)"
    else:
        parity = "bitwise"
    post = records[vic][n_pre:]
    return {
        "parity": parity,
        "steps": sum(len(r) for r in records),
        "mismatches": mismatches,
        "teeth": teeth,
        "rehomed_sessions": int(rehomed_count > 0),
        "rehomed_to_spare": rehomed_to == addrs[2],
        "blackout_s": round(max((r["wall_s"] for r in post), default=0.0), 3),
        "router_sessions_rehomed": int(
            snap.get("router/sessions_rehomed_total", 0)
        ),
        "router_spares_promoted": int(
            snap.get("router/spares_promoted_total", 0)
        ),
        "router_backend_deaths": int(
            snap.get("router/backend_deaths_total", 0)
        ),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--addr", type=str, default=None, help="host:port of a "
                   "running serve server (or, with --router, a session "
                   "router)")
    p.add_argument("--router", action="store_true",
                   help="--addr names a SessionRouter: clients attach "
                   "through it and follow its redirects when a backend "
                   "dies mid-run (ISSUE 19)")
    p.add_argument("--clients", type=int, default=16,
                   help="concurrent synthetic games")
    p.add_argument("--requests", type=int, default=50,
                   help="sequential step requests per client")
    p.add_argument("--max-reconnects", type=int, default=6,
                   help="bounded backoff attempts per (re)connect — the "
                   "actor contract's connect_with_backoff schedule")
    p.add_argument("--think-ms", type=float, default=0.0,
                   help="sleep between a client's requests (a game's frame "
                   "cadence; 0 = saturate)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--serve", type=str, default=None, metavar="K=V,...",
        help="ServeConfig overrides for the CLIENT side (request encoding "
        "and failover budget — e.g. 'request_wire_dtype=bfloat16' or "
        "'request_deadline_s=5'; must match the server where it matters)",
    )
    p.add_argument("--rehome-parity", action="store_true",
                   help="ignore --addr: run the in-process re-home parity "
                   "digest (2 backends + hot spare + router, carry-shadow "
                   "on) and print it — exit 0 iff parity is bitwise")
    p.add_argument("--metrics-jsonl", type=str, default=None, metavar="PATH",
                   help="with --rehome-parity: also dump one router "
                   "telemetry snapshot line to PATH "
                   "(check_telemetry_schema.py --require-router)")
    args = p.parse_args(argv)

    if args.rehome_parity:
        out = run_rehome_parity(
            seed=args.seed, metrics_jsonl=args.metrics_jsonl
        )
        print(json.dumps(out))
        return 0 if out["parity"] == "bitwise" else 1
    if not args.addr:
        p.error("--addr is required (unless --rehome-parity)")

    from dotaclient_tpu.config import ServeConfig, default_config
    from dotaclient_tpu.utils.overrides import parse_dataclass_overrides

    config = default_config()
    if args.serve:
        try:
            over = parse_dataclass_overrides(ServeConfig, args.serve, "--serve")
        except ValueError as e:
            p.error(str(e))
        config = dataclasses.replace(
            config, serve=dataclasses.replace(config.serve, **over)
        )

    # SIGTERM flips the abort flag every client's backoff/retry loop
    # polls: a terminated loadgen abandons its schedules within one
    # segment instead of riding retries to their deadline
    import signal

    stop = threading.Event()
    try:
        signal.signal(signal.SIGTERM, lambda *_: stop.set())
    except ValueError:
        pass   # not the main thread (embedded use): callers manage signals

    host, port = args.addr.rsplit(":", 1)
    out = run_loadgen(
        host, int(port), config,
        n_clients=args.clients, requests_per_client=args.requests,
        seed=args.seed, router=args.router,
        max_reconnects=args.max_reconnects, should_abort=stop.is_set,
        think_s=args.think_ms / 1e3,
    )
    print(json.dumps(out))
    return 0 if not out["errors"] else 1


if __name__ == "__main__":
    sys.exit(main())

"""Standalone policy-serving server.

    python -m dotaclient_tpu.serve --checkpoint runs/ckpt
    python -m dotaclient_tpu.serve --checkpoint runs/ckpt \
        --serve batch_window_ms=4,max_batch=128 --serve-listen 0.0.0.0:7788
    python -m dotaclient_tpu.serve --checkpoint runs/ckpt \
        --subscribe 10.0.0.5:7777          # hot weight refresh from a learner
    python -m dotaclient_tpu.serve --checkpoint runs/ckpt \
        --subscribe shm://tpu-dota-1234    # same-host shm weights slab

Loads a training checkpoint into the inference-only tree (no value head, no
optimizer state), serves actions over the continuous-batching socket lane,
and optionally subscribes to a learner's weights fanout so refreshes are
hot-swapped between dispatches. Clients are ``serve.ServeClient`` (one per
game); ``scripts/serve_loadgen.py`` drives synthetic fleets.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--checkpoint", type=str, required=True,
                   help="training checkpoint directory (orbax run dir); "
                   "its stored config governs the model tree")
    p.add_argument("--serve-listen", type=str, default="127.0.0.1:0",
                   help="host:port for the serve request/reply lane "
                   "(port 0 = ephemeral, printed at startup)")
    p.add_argument(
        "--serve", type=str, default=None, metavar="K=V,...",
        help="comma-separated ServeConfig overrides, e.g. "
        "'batch_window_ms=4,max_batch=128' (knob table in "
        "docs/OPERATIONS.md)",
    )
    p.add_argument(
        "--subscribe", type=str, default=None, metavar="ADDR",
        help="weights fanout to subscribe to: 'host:port' (a learner's "
        "--transport socket lane) or 'shm://NAME' (its same-host shm "
        "slab); new versions hot-swap between dispatches",
    )
    p.add_argument(
        "--serve-metrics-jsonl", type=str, default=None, metavar="PATH",
        help="append a serve-telemetry snapshot (one {ts, step, scalars} "
        "object per interval; step = dispatch count) to PATH — validate "
        "with scripts/check_telemetry_schema.py --path PATH --require-serve",
    )
    p.add_argument("--trace-jsonl", type=str, default=None, metavar="PATH",
                   help="pipeline tracing (ISSUE 12): append sampled "
                   "lifecycle events (request/reply trace records, "
                   "per-compile cost analysis) as JSON lines to PATH; "
                   "merge with a learner/actor run's logs via "
                   "scripts/trace_report.py")
    p.add_argument("--trace-sample", type=int, default=None, metavar="N",
                   help="with --trace-jsonl: trace every Nth request "
                   "(default telemetry.trace_sample_n = 16)")
    p.add_argument("--fleet-interval", type=float, default=None, metavar="S",
                   help="fleet health plane (ISSUE 13): with --subscribe, "
                   "push one compact metric snapshot (serve counters + "
                   "gauges) back to the learner every S seconds over the "
                   "subscription lane (default telemetry.fleet_interval_s "
                   "= 5; 0 disables) — the fleet console then shows serve "
                   "p99 next to the actors")
    p.add_argument("--duration", type=float, default=0.0,
                   help="serve for this many seconds then exit (0 = forever)")
    args = p.parse_args(argv)

    from dotaclient_tpu.serve import (
        PolicyServer,
        ServeEngine,
        load_inference_params,
        make_inference_policy,
    )
    from dotaclient_tpu.utils import compile_cache, telemetry
    from dotaclient_tpu.utils.overrides import parse_dataclass_overrides

    compile_cache.enable()
    config, params, version = load_inference_params(args.checkpoint)
    if args.serve:
        from dotaclient_tpu.config import ServeConfig

        try:
            over = parse_dataclass_overrides(ServeConfig, args.serve, "--serve")
        except ValueError as e:
            p.error(str(e))
        config = dataclasses.replace(
            config, serve=dataclasses.replace(config.serve, **over)
        )

    if args.trace_jsonl:
        from dotaclient_tpu.utils import tracing

        # before the engine/server exist — they capture tracing.get()
        tracing.configure(args.trace_jsonl, sample_n=args.trace_sample)

    policy = make_inference_policy(config)
    engine = ServeEngine(config, policy, params, version=version)
    host, port = args.serve_listen.rsplit(":", 1)
    server = PolicyServer(engine, config, host=host, port=int(port))
    # the device is read off the committed arrays: one server is one
    # single-device replica, however many chips the host holds
    print(
        f"serve: listening on {server.address} "
        f"(window {config.serve.batch_window_ms} ms, "
        f"max_batch {config.serve.max_batch}, "
        f"{config.serve.max_slots} carry slots, weights v{version}, "
        f"params on {', '.join(sorted(map(str, engine.param_devices)))})",
        flush=True,
    )
    # machine-readable address line: the chaos harness and fleet tooling
    # spawn ephemeral-port backends and parse this (ISSUE 19)
    print(
        "SERVE_LISTENING "
        + json.dumps({
            "host": server.address[0], "port": int(server.address[1]),
        }),
        flush=True,
    )

    if args.subscribe:
        if args.subscribe.startswith("shm://"):
            from dotaclient_tpu.transport.shm_transport import ShmTransport

            source = ShmTransport(args.subscribe[len("shm://"):])
        else:
            from dotaclient_tpu.transport.socket_transport import (
                SocketTransport,
            )

            sub_host, sub_port = args.subscribe.rsplit(":", 1)
            source = SocketTransport(sub_host, int(sub_port))
        server.attach_weights_source(source)
        print(f"serve: subscribed to weights fanout {args.subscribe}", flush=True)

    publisher = None
    if args.subscribe:
        # fleet health plane (ISSUE 13): the weights-subscription lane is
        # the serve process's channel back to the learner — ride metric
        # snapshots on it so the fleet console shows this server's p99
        from dotaclient_tpu.utils.fleet import FleetPublisher

        interval = (
            telemetry.fleet_interval_s
            if args.fleet_interval is None
            else args.fleet_interval
        )
        if interval > 0:
            # peer id = the bound listen port, NOT the pid: a restarted
            # serve process must reuse its fleet row so the
            # fleet_peer_stale page resolves on its first fresh snapshot
            # (a pid-keyed row would stay stale — and paging — until the
            # aggregator's forget window). Ephemeral-port servers
            # (--serve-listen :0) get a fresh row per boot by nature.
            publisher = FleetPublisher(
                peer_id=int(server.address[1]) & 0xFFFF, kind="serve",
                interval_s=interval,
            )

    sink = None
    if args.serve_metrics_jsonl:
        sink = telemetry.JsonlSink(args.serve_metrics_jsonl)
    tel = telemetry.get_registry()
    t_end = time.time() + args.duration if args.duration else None
    # the wake interval follows the fleet cadence so snapshots publish on
    # time, but the JSONL sink keeps its OWN historical 5 s cadence —
    # --fleet-interval must not silently multiply the metrics log volume
    wake = min(5.0, publisher.interval_s) if publisher is not None else 5.0
    sink_every = 5.0
    last_sink = time.monotonic()
    try:
        while t_end is None or time.time() < t_end:
            time.sleep(min(wake, t_end - time.time()) if t_end else wake)
            if publisher is not None:
                try:
                    publisher.maybe_publish(source)
                except (ConnectionError, OSError):
                    pass   # learner gone: serving continues on last weights
            if (
                sink is not None
                and time.monotonic() - last_sink >= sink_every
            ):
                last_sink = time.monotonic()
                snap = tel.snapshot()
                sink.emit(int(snap.get("serve/dispatches_total", 0)), snap)
    except KeyboardInterrupt:
        pass
    finally:
        if sink is not None:
            snap = tel.snapshot()
            sink.emit(int(snap.get("serve/dispatches_total", 0)), snap)
            sink.close()
        server.close()
        engine.stop()
        if args.trace_jsonl:
            from dotaclient_tpu.utils import tracing

            tracing.shutdown()
        snap = tel.snapshot()
        print(json.dumps({
            "serve_requests_total": snap.get("serve/requests_total", 0.0),
            "serve_dispatches_total": snap.get("serve/dispatches_total", 0.0),
            "serve_p99_latency_ms": snap.get("serve/p99_latency_ms", 0.0),
            "serve_weights_version": snap.get("serve/weights_version", 0.0),
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

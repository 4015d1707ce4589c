"""Session-affine router for a serve-backend fleet (ISSUE 19).

PR 11's single ``PolicyServer`` is three orders of magnitude short of the
ROADMAP's "millions of concurrent games"; the missing robustness half is
horizontal scale-out. The :class:`SessionRouter` is the control plane of
that scale-out: it maps each game (a *session*) to one of N backends and
keeps the map honest under failure. Data traffic never touches the router —
clients talk to their assigned backend directly over the PR 11 serve lane;
the router only answers the cheap control questions ("where do I attach?",
"where is my session now?") over two new JSON-payload frame kinds on the
shared CRC wire (``KIND_ROUTE_REQUEST``/``KIND_ROUTE_REPLY``).

Liveness is the existing heartbeat/idle discipline turned outward: the
router holds ONE persistent probe connection per backend (it occupies one
carry slot — budget ``serve.max_slots`` accordingly) and ships heartbeat
frames (kind 2, which the backend reader ignores by design) at
``serve.router_probe_s``. A SIGKILL'd backend surfaces as EOF/RST on that
connection within one probe turn; the probe then tries to reconnect for
``serve.router_dead_after_s`` before the backend is declared DEAD — a
transient blip inside the grace window is not a death.

On death the router **re-homes**: a hot spare (a normal backend process
subscribed to the same weights fanout, registered with ``--spares``) is
promoted — a routing change only, never a weight load — and every session
of the dead backend is reassigned to the least-loaded live backend, its
assignment epoch bumped so the client's next ``where`` sees the redirect.
The state contract is the client's (serve/client.py): default mode resumes
on a fresh zeroed carry slot (the reset_recurrent discipline, counted);
carry-shadow mode resends the stashed carry row so the session resumes
bit-exact (the re-home parity digest pins it:
tests/test_router.py::test_rehome_parity_digest_is_bitwise).

Telemetry (all ``router/*`` keys eager-created at construction;
``check_telemetry_schema.py --require-router``): session and re-home
counters, live/dead/spare gauges, per-backend session counts
(``router/backend/<i>/sessions``). The router process runs the PR 13 alert
engine over its own registry, so ``serve_peer_dead`` pages (and
``sessions_rehomed_burst`` warns) from the router's metrics JSONL with the
same ``ALERT`` event durability the learner has.
"""

from __future__ import annotations

import json
import socket
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from dotaclient_tpu.transport.socket_transport import (
    FrameCorrupt,
    FramingLost,
    _recv_frame,
    _send_frame,
)
from dotaclient_tpu.utils import telemetry

# Route control frames extend the shared wire kind space (0-2 training
# transport, 3-4 serve request/reply, 5 fleet metrics). Payloads are JSON —
# control traffic is tiny and schema-fluid; the CRC trailer still applies.
KIND_ROUTE_REQUEST = 6
KIND_ROUTE_REPLY = 7

_KIND_HEARTBEAT = 2   # probe frames; the backend reader skips kind != 3


def route_call(
    sock: socket.socket, request: Dict[str, Any], timeout: float = 5.0
) -> Dict[str, Any]:
    """One control round-trip on an open router connection: send a JSON
    route request, block for the JSON reply (skipping any other kind to
    stay in sync — the client discipline of the serve lane)."""
    sock.settimeout(timeout)
    _send_frame(sock, KIND_ROUTE_REQUEST, json.dumps(request).encode())
    while True:
        frame = _recv_frame(sock)
        if frame is None:
            raise ConnectionError("router closed the connection")
        kind, payload = frame
        if kind != KIND_ROUTE_REPLY:
            continue
        return json.loads(bytes(payload).decode())


class _Backend:
    """One registered backend: address, liveness, and its session set.
    All mutable fields are guarded by the router's one lock except the
    probe thread's private socket."""

    __slots__ = (
        "index", "addr", "spare", "live", "sessions", "probe_sock",
        "last_ok",
    )

    def __init__(self, index: int, addr: Tuple[str, int], spare: bool):
        self.index = index
        self.addr = addr
        self.spare = spare          # not in the assignment pool until promoted
        self.live = False           # probe-confirmed reachability
        self.sessions: set = set()  # session ids homed here
        self.probe_sock: Optional[socket.socket] = None
        self.last_ok = 0.0


class SessionRouter:
    """Session→backend affinity map + liveness probes + re-homing."""

    def __init__(
        self,
        config: Any,
        backends: List[Tuple[str, int]],
        spares: Optional[List[Tuple[str, int]]] = None,
        host: str = "127.0.0.1",
        port: int = 0,
        registry: Optional[telemetry.Registry] = None,
    ) -> None:
        if not backends:
            raise ValueError("a router needs at least one active backend")
        scfg = config.serve
        self._probe_s = max(0.05, scfg.router_probe_s)
        self._dead_after_s = max(self._probe_s, scfg.router_dead_after_s)
        self._tel = (
            registry if registry is not None else telemetry.get_registry()
        )
        self._lock = threading.Lock()
        self._backends: List[_Backend] = [
            _Backend(i, addr, spare=False)
            for i, addr in enumerate(backends)
        ]
        for addr in spares or []:
            self._backends.append(
                _Backend(len(self._backends), addr, spare=True)
            )
        # session id → (backend index, assignment epoch, rehomed flag).
        # Epochs are per-session and bump on every reassignment, so a
        # client holding a stale addr learns of the redirect from one
        # integer compare.
        self._sessions: Dict[int, Dict[str, Any]] = {}
        self._next_session = 1
        self._closed = threading.Event()
        # eager-create the full router key family: a router that never
        # loses a backend still reports zeros
        # (check_telemetry_schema.py --require-router)
        for name in (
            "router/sessions_attached_total",
            "router/sessions_detached_total",
            "router/sessions_rehomed_total",
            "router/carry_resets_total",
            "router/spares_promoted_total",
            "router/backend_deaths_total",
            "router/probe_reconnects_total",
            "router/route_requests_total",
            "router/route_errors_total",
        ):
            self._tel.counter(name)
        for name in (
            "router/backends_live",
            "router/backends_dead",
            "router/spares_available",
            "router/sessions_active",
        ):
            self._tel.gauge(name)
        for b in self._backends:
            self._tel.gauge(f"router/backend/{b.index}/sessions")
        self._listener = socket.create_server((host, port))
        self.address = self._listener.getsockname()
        self._probe_threads = [
            threading.Thread(
                target=self._probe_loop, args=(b,),
                name=f"router-probe-{b.index}", daemon=True,
            )
            for b in self._backends
        ]
        for t in self._probe_threads:
            t.start()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="router-accept", daemon=True
        )
        self._accept_thread.start()

    # -- liveness probes (one thread per backend) ---------------------------

    def _probe_connect(self, b: _Backend) -> Optional[socket.socket]:
        try:
            sock = socket.create_connection(b.addr, timeout=self._probe_s)
        except OSError:
            return None
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        sock.settimeout(self._probe_s)
        try:
            # drain the attach frame the backend sends every joiner (the
            # probe holds the slot for the router's lifetime)
            frame = _recv_frame(sock)
        except (OSError, FrameCorrupt, FramingLost):
            frame = None
        if frame is None:
            try:
                sock.close()
            except OSError:
                pass
            return None
        return sock

    def _probe_loop(self, b: _Backend) -> None:
        """Own b's probe socket; flip b.live and trigger re-homing. A lost
        connection gets ``router_dead_after_s`` of reconnect attempts
        before the death is declared; a dead backend that answers again
        rejoins the pool (empty — its sessions already moved on)."""
        while not self._closed.is_set():
            sock = self._probe_connect(b)
            if sock is None:
                self._tel.counter("router/probe_reconnects_total").inc()
                if b.live and (
                    time.monotonic() - b.last_ok >= self._dead_after_s
                ):
                    self._declare_dead(b)
                elif not b.live:
                    # never (or not currently) attached: keep last_ok
                    # fresh-from-zero semantics — first success arms it
                    pass
                if self._closed.wait(min(0.2, self._probe_s)):
                    return
                continue
            b.probe_sock = sock
            b.last_ok = time.monotonic()
            self._set_live(b, True)
            try:
                while not self._closed.is_set():
                    _send_frame(sock, _KIND_HEARTBEAT, b"")
                    try:
                        frame = _recv_frame(sock)
                    except socket.timeout:
                        frame = True  # no reply traffic is the steady state
                    except (FrameCorrupt, FramingLost):
                        frame = True  # probe lane carries no payloads we parse
                    if frame is None:
                        break  # EOF: the backend is gone
                    b.last_ok = time.monotonic()
            except OSError:
                pass  # send failed: the backend is gone
            finally:
                b.probe_sock = None
                try:
                    sock.close()
                except OSError:
                    pass
            # connection lost: grace loop — reconnect attempts until the
            # dead window elapses, then declare
            lost_at = time.monotonic()
            while (
                not self._closed.is_set()
                and time.monotonic() - lost_at < self._dead_after_s
            ):
                sock = self._probe_connect(b)
                if sock is not None:
                    b.probe_sock = sock
                    b.last_ok = time.monotonic()
                    self._tel.counter("router/probe_reconnects_total").inc()
                    break
                self._closed.wait(min(0.2, self._probe_s))
            else:
                if not self._closed.is_set():
                    self._declare_dead(b)
                continue
            # reconnected inside the grace window: resume the heartbeat
            # loop on the fresh socket next turn (close this one first)
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass

    def _set_live(self, b: _Backend, live: bool) -> None:
        with self._lock:
            b.live = live
            self._publish_gauges_locked()

    def _declare_dead(self, b: _Backend) -> None:
        """The failover moment: promote a spare if one is live, re-home
        every session of the dead backend, bump epochs. One lock hold —
        route requests racing this see either the old or the new world,
        never a half-moved session."""
        with self._lock:
            if not b.live and not b.sessions:
                return  # already processed (or never attached)
            b.live = False
            self._tel.counter("router/backend_deaths_total").inc()
            # promotion is a routing change: the spare already subscribes
            # to the weights fanout, so it enters the pool as-is
            for s in self._backends:
                if s.spare and s.live:
                    s.spare = False
                    self._tel.counter("router/spares_promoted_total").inc()
                    break
            moved = self._rehome_locked(b)
            self._publish_gauges_locked()
        if moved:
            self._tel.counter("router/sessions_rehomed_total").inc(moved)

    def _rehome_locked(self, dead: _Backend) -> int:
        """Reassign every session homed on ``dead`` to the least-loaded
        live non-spare backend. Sessions with no live home stay parked on
        the dead backend (epoch unchanged) — the next death/recovery or
        ``where`` retry picks them up; the client's deadline budget bounds
        how long it waits for that."""
        moved = 0
        for sid in sorted(dead.sessions):
            target = self._pick_backend_locked()
            if target is None or target is dead:
                break
            dead.sessions.discard(sid)
            target.sessions.add(sid)
            sess = self._sessions[sid]
            sess["backend"] = target.index
            sess["epoch"] += 1
            sess["rehomed"] = True
            moved += 1
        return moved

    def _pick_backend_locked(self) -> Optional[_Backend]:
        pool = [b for b in self._backends if b.live and not b.spare]
        if not pool:
            return None
        return min(pool, key=lambda b: (len(b.sessions), b.index))

    def _publish_gauges_locked(self) -> None:
        live = sum(1 for b in self._backends if b.live and not b.spare)
        dead = sum(1 for b in self._backends if not b.live and not b.spare)
        spares = sum(1 for b in self._backends if b.spare and b.live)
        self._tel.gauge("router/backends_live").set(float(live))
        self._tel.gauge("router/backends_dead").set(float(dead))
        self._tel.gauge("router/spares_available").set(float(spares))
        self._tel.gauge("router/sessions_active").set(
            float(len(self._sessions))
        )
        for b in self._backends:
            self._tel.gauge(f"router/backend/{b.index}/sessions").set(
                float(len(b.sessions))
            )

    # -- route control plane -------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._closed.is_set():
            try:
                sock, _addr = self._listener.accept()
            except OSError:
                return  # listener closed
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(
                target=self._conn_loop, args=(sock,),
                name="router-conn", daemon=True,
            ).start()

    def _conn_loop(self, sock: socket.socket) -> None:
        try:
            while not self._closed.is_set():
                try:
                    frame = _recv_frame(sock)
                except (FrameCorrupt, FramingLost):
                    self._tel.counter("router/route_errors_total").inc()
                    return  # control lane: no resync, the client redials
                if frame is None:
                    return  # clean disconnect
                kind, payload = frame
                if kind != KIND_ROUTE_REQUEST:
                    continue
                self._tel.counter("router/route_requests_total").inc()
                try:
                    request = json.loads(bytes(payload).decode())
                    reply = self._handle(request)
                except Exception:  # noqa: BLE001 - control plane stays up
                    self._tel.counter("router/route_errors_total").inc()
                    reply = {"error": "malformed route request"}
                _send_frame(
                    sock, KIND_ROUTE_REPLY, json.dumps(reply).encode()
                )
        except OSError:
            pass  # disposable control connection
        finally:
            try:
                sock.close()
            except OSError:
                pass

    def _handle(self, request: Dict[str, Any]) -> Dict[str, Any]:
        op = request.get("op")
        if op == "attach":
            return self.attach()
        if op == "where":
            return self.where(int(request["session"]))
        if op == "detach":
            return self.detach(int(request["session"]))
        if op == "status":
            return self.status()
        self._tel.counter("router/route_errors_total").inc()
        return {"error": f"unknown op {op!r}"}

    def attach(self) -> Dict[str, Any]:
        with self._lock:
            target = self._pick_backend_locked()
            if target is None:
                self._tel.counter("router/route_errors_total").inc()
                return {"error": "no live backend"}
            sid = self._next_session
            self._next_session += 1
            target.sessions.add(sid)
            self._sessions[sid] = {
                "backend": target.index, "epoch": 0, "rehomed": False,
            }
            self._tel.counter("router/sessions_attached_total").inc()
            self._publish_gauges_locked()
            return {
                "session": sid,
                "addr": list(target.addr),
                "epoch": 0,
            }

    def where(self, sid: int) -> Dict[str, Any]:
        """Current home of a session. A session parked on a dead backend
        re-homes HERE if a live backend has appeared since — the lazy
        half of re-homing that covers sessions stranded while no backend
        was live."""
        with self._lock:
            sess = self._sessions.get(sid)
            if sess is None:
                return {"error": f"unknown session {sid}"}
            b = self._backends[sess["backend"]]
            if not b.live:
                target = self._pick_backend_locked()
                if target is None:
                    return {"error": "no live backend"}
                b.sessions.discard(sid)
                target.sessions.add(sid)
                sess["backend"] = target.index
                sess["epoch"] += 1
                sess["rehomed"] = True
                b = target
                self._tel.counter("router/sessions_rehomed_total").inc()
                self._publish_gauges_locked()
            return {
                "session": sid,
                "addr": list(b.addr),
                "epoch": sess["epoch"],
                "rehomed": bool(sess["rehomed"]),
            }

    def detach(self, sid: int) -> Dict[str, Any]:
        with self._lock:
            sess = self._sessions.pop(sid, None)
            if sess is not None:
                self._backends[sess["backend"]].sessions.discard(sid)
                self._tel.counter("router/sessions_detached_total").inc()
                self._publish_gauges_locked()
        return {"session": sid, "detached": sess is not None}

    def status(self) -> Dict[str, Any]:
        from dotaclient_tpu.utils.fleet import peer_label

        with self._lock:
            return {
                "backends": [
                    {
                        "index": b.index,
                        "addr": list(b.addr),
                        # the PR 13 fleet row this backend publishes under
                        # (serve peers key on their listen port): the
                        # operator joins router liveness against
                        # fleet/<peer>/serve/p99_latency_ms by this name
                        "fleet_peer": peer_label(
                            "serve", b.addr[1] & 0xFFFF
                        ),
                        "live": b.live,
                        "spare": b.spare,
                        "sessions": len(b.sessions),
                    }
                    for b in self._backends
                ],
                "sessions": len(self._sessions),
            }

    def note_carry_reset(self) -> None:
        """Client-reported default-mode re-home (the carry went to zeros;
        the reset_recurrent discipline). Counted here so the honest state
        contract is observable fleet-wide, not per-client."""
        self._tel.counter("router/carry_resets_total").inc()

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        self._closed.set()
        try:
            self._listener.close()
        except OSError:
            pass
        for b in self._backends:
            sock = b.probe_sock
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass
        for t in self._probe_threads:
            t.join(timeout=2)


def main(argv=None) -> int:
    """Standalone router:

        python -m dotaclient_tpu.serve.router \\
            --listen 127.0.0.1:7799 \\
            --backends 127.0.0.1:7788,127.0.0.1:7789 \\
            --spares 127.0.0.1:7790 --metrics-jsonl router.jsonl

    Runs the session router plus the PR 13 alert engine over its own
    registry; ``ALERT`` events (``serve_peer_dead``,
    ``sessions_rehomed_burst``) and periodic ``router/*`` snapshots ride
    the metrics JSONL with the learner's flush-per-emit durability.
    """
    import argparse
    import dataclasses

    p = argparse.ArgumentParser(description=main.__doc__)
    p.add_argument("--listen", type=str, default="127.0.0.1:0",
                   help="host:port of the route control lane (0 = "
                   "ephemeral, printed at startup)")
    p.add_argument("--backends", type=str, required=True,
                   help="comma-separated host:port of active backends")
    p.add_argument("--spares", type=str, default=None,
                   help="comma-separated host:port of hot spares "
                   "(subscribed to the same weights fanout; promotion is "
                   "a routing change)")
    p.add_argument("--serve", type=str, default=None, metavar="K=V,...",
                   help="ServeConfig overrides (router_probe_s, "
                   "router_dead_after_s, ...)")
    p.add_argument("--metrics-jsonl", type=str, default=None, metavar="PATH",
                   help="append router telemetry snapshots + ALERT events "
                   "to PATH — validate with check_telemetry_schema.py "
                   "--path PATH --require-router")
    p.add_argument("--interval", type=float, default=1.0,
                   help="snapshot/alert evaluation cadence in seconds")
    p.add_argument("--duration", type=float, default=0.0,
                   help="route for this many seconds then exit (0 = forever)")
    args = p.parse_args(argv)

    from dotaclient_tpu.config import ServeConfig, default_config
    from dotaclient_tpu.utils.alerts import AlertEngine
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

    def parse_addrs(spec: Optional[str]) -> List[Tuple[str, int]]:
        if not spec:
            return []
        out = []
        for part in spec.split(","):
            host, port = part.strip().rsplit(":", 1)
            out.append((host, int(port)))
        return out

    host, port = args.listen.rsplit(":", 1)
    tel = telemetry.get_registry()
    router = SessionRouter(
        config,
        parse_addrs(args.backends),
        spares=parse_addrs(args.spares),
        host=host,
        port=int(port),
        registry=tel,
    )
    sink = (
        telemetry.JsonlSink(args.metrics_jsonl)
        if args.metrics_jsonl
        else None
    )
    engine = AlertEngine(
        registry=tel,
        emit=(sink.emit_event if sink is not None else None),
    )
    print(
        "ROUTER_LISTENING "
        + json.dumps({
            "host": router.address[0], "port": int(router.address[1]),
        }),
        flush=True,
    )
    ticks = 0
    t_end = time.time() + args.duration if args.duration else None
    try:
        while t_end is None or time.time() < t_end:
            time.sleep(args.interval)
            ticks += 1
            counters, gauges = tel.counters_and_gauges()
            snapshot = {**counters, **gauges}
            engine.evaluate(snapshot)
            if sink is not None:
                sink.emit(ticks, snapshot)
    except KeyboardInterrupt:
        pass
    finally:
        router.close()
        if sink is not None:
            counters, gauges = tel.counters_and_gauges()
            sink.emit(ticks + 1, {**counters, **gauges})
            sink.close()
        counters, gauges = tel.counters_and_gauges()
        print(json.dumps({
            "router_sessions_attached": counters.get(
                "router/sessions_attached_total", 0.0
            ),
            "router_sessions_rehomed": counters.get(
                "router/sessions_rehomed_total", 0.0
            ),
            "router_backend_deaths": counters.get(
                "router/backend_deaths_total", 0.0
            ),
        }))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())

"""Serve-plane client: one game, one session, bounded failure (ISSUE 19).

The protocol is intentionally dumb — a game wants exactly one action per
observation, so the client is synchronous: ``step(obs)`` ships one request
frame and blocks until the echoing reply arrives. Recurrent state stays
server-resident by default: the backend keeps this game's carry in the slot
it assigned at attach, and ``reset=True`` on the first step of each episode
zeroes that slot before the core — the same episode-boundary discipline the
actors apply.

Failure is BOUNDED, never a hang: every ``step()`` spends from a per-request
deadline budget (``serve.request_deadline_s``) across bounded resend
attempts (``serve.request_retries``), and the connect path rides
``connect_with_backoff`` — the PR 4/6 actor discipline, so a SIGTERM'd
client abandons a reconnect schedule within one backoff segment
(``should_abort``). A request that cannot be served inside its budget
raises the typed :class:`ServeDeadlineError`; whoever owns the game decides
what a missed action means.

Fleet mode (``router=True``): ``(host, port)`` names a
:class:`~dotaclient_tpu.serve.router.SessionRouter` instead of a backend.
The client attaches through the router (session-affine assignment), talks
to its backend directly, and on ANY backend failure re-asks the router
``where`` its session lives now — following the redirect to a re-homed
backend or a promoted hot spare. The re-home state contract is honest:
default mode resumes on a fresh zeroed carry (counted via
``carry_resets``/the router's ``router/carry_resets_total``); with
``serve.carry_shadow`` on, the client stashes the carry row each reply
ships back and resends it on the first post-re-home request, so the
session resumes bit-exact (the re-home parity digest pins it:
tests/test_router.py::test_rehome_parity_digest_is_bitwise).

Request payloads ride the rollout codec, so
``serve.request_wire_dtype="bfloat16"`` narrows observation (and shadow
carry) leaves through the ISSUE 7 cast-plan machinery; CRC trailers and
the quarantine discipline come with the shared framing.
"""

from __future__ import annotations

import socket
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from dotaclient_tpu.config import RunConfig
from dotaclient_tpu.models.distributions import HEADS
from dotaclient_tpu.serve.router import route_call
from dotaclient_tpu.serve.server import (
    ATTACH_REQUEST_ID,
    KIND_SERVE_REPLY,
    KIND_SERVE_REQUEST,
)
from dotaclient_tpu.transport.socket_transport import (
    _recv_frame,
    _send_frame,
)
from dotaclient_tpu.transport.serialize import (
    decode_rollout_bytes,
    encode_rollout_bytes,
    rollout_int_bounds,
)
from dotaclient_tpu.utils import tracing


class ServeDeadlineError(ConnectionError):
    """A request's deadline budget elapsed (retries, reconnects, and
    router redirects included). The typed bounded-failure every caller can
    rely on: a ``step()`` either returns an action or raises this within
    ``serve.request_deadline_s`` — never a hang."""


def serve_request_wire_kwargs(config: RunConfig) -> Dict[str, Any]:
    """Encode kwargs for the request wire — ``{}`` for full width, the
    rollout cast plan (bf16 floats, exact bounded ints) otherwise. The one
    derivation every request encoder shares (client, loadgen, tests)."""
    if config.serve.request_wire_dtype == "float32":
        return {}
    return dict(
        wire_dtype=config.serve.request_wire_dtype,
        int_bounds=rollout_int_bounds(config),
    )


class ServeClient:
    """Blocking request/reply client for one game (direct or fleet mode)."""

    def __init__(
        self,
        host: str,
        port: int,
        config: RunConfig,
        timeout_s: float = 30.0,
        router: bool = False,
        max_reconnects: int = 6,
        should_abort: Optional[Callable[[], bool]] = None,
    ) -> None:
        scfg = config.serve
        self._timeout_s = timeout_s
        self._deadline_s = max(0.05, scfg.request_deadline_s)
        self._retries = max(0, int(scfg.request_retries))
        self._shadow = bool(scfg.carry_shadow)
        self._max_reconnects = max(1, int(max_reconnects))
        self._should_abort = should_abort
        self._wire_kwargs = serve_request_wire_kwargs(config)
        self._next_id = 1   # 0 is the attach frame's id
        self.last_version = 0
        self.last_logp = 0.0
        self.last_latency_s = 0.0
        self.last_dispatch_idx = -1
        self._last_packed = np.zeros((len(HEADS),), np.int32)
        # fleet-mode state
        self._router = bool(router)
        self._router_addr: Optional[Tuple[str, int]] = None
        self._route_sock: Optional[socket.socket] = None
        self.session: Optional[int] = None
        self._epoch = -1
        # failover bookkeeping (the honest state contract, observable)
        self.rehomed_count = 0
        self.last_rehomed = False
        self.carry_resets = 0
        self.retries_total = 0
        self._carry_stash: Optional[Dict[str, np.ndarray]] = None
        self._pending_restore = False
        self._sock: Optional[socket.socket] = None
        self.backend_addr: Tuple[str, int] = (host, port)

        deadline = time.monotonic() + self._deadline_s
        if self._router:
            self._router_addr = (host, port)
            info = self._route({"op": "attach"}, deadline)
            if "error" in info:
                raise ConnectionError(f"router attach failed: {info['error']}")
            self.session = int(info["session"])
            self._epoch = int(info["epoch"])
            self.backend_addr = (info["addr"][0], int(info["addr"][1]))
        try:
            self._connect_backend(deadline)
        except BaseException:
            self.close()
            raise

    # -- connection plumbing -------------------------------------------------

    def _abort_by(self, deadline: float) -> Callable[[], bool]:
        """The backoff/retry stop predicate: the caller's SIGTERM hook OR
        the request deadline — whichever trips first ends the schedule
        within one segment."""
        def abort() -> bool:
            if self._should_abort is not None and self._should_abort():
                return True
            return time.monotonic() >= deadline
        return abort

    def _connect_backend(self, deadline: float) -> None:
        """(Re)connect to ``backend_addr`` and read the attach frame, with
        the actor contract's bounded backoff. A RE-connect lands on a
        fresh slot — state discontinuity — so it arms the restore path
        (shadow resend or an explicit counted reset)."""
        from dotaclient_tpu.actor.__main__ import connect_with_backoff

        reconnecting = self._sock is not None
        self._close_backend()

        def factory() -> socket.socket:
            sock = socket.create_connection(
                self.backend_addr, timeout=self._timeout_s
            )
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sock.settimeout(self._timeout_s)
            try:
                # attach: the first frame names this connection's carry
                # slot and the server's current weights version. A shed
                # joiner (every slot taken → the server closes without an
                # attach frame) must not leak the fd.
                meta = self._recv_reply_on(
                    sock, ATTACH_REQUEST_ID, deadline
                )[0]
            except BaseException:
                try:
                    sock.close()
                except OSError:
                    pass
                raise
            self.slot = meta["env_id"]
            self.last_version = meta["model_version"]
            return sock

        self._sock = connect_with_backoff(
            factory,
            max_attempts=self._max_reconnects,
            base_delay=0.1,
            max_delay=1.0,
            should_abort=self._abort_by(deadline),
        )
        if reconnecting:
            self._pending_restore = True

    def _close_backend(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def _close_route(self) -> None:
        if self._route_sock is not None:
            try:
                self._route_sock.close()
            except OSError:
                pass
            self._route_sock = None

    def _route(self, request: Dict[str, Any], deadline: float) -> Dict[str, Any]:
        """One router round-trip, redialing the control connection once if
        it went stale (bounded by the deadline either way)."""
        from dotaclient_tpu.actor.__main__ import connect_with_backoff

        assert self._router_addr is not None
        for attempt in (0, 1):
            if self._route_sock is None:
                def factory() -> socket.socket:
                    s = socket.create_connection(
                        self._router_addr, timeout=self._timeout_s
                    )
                    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    return s

                self._route_sock = connect_with_backoff(
                    factory,
                    max_attempts=self._max_reconnects,
                    base_delay=0.1,
                    max_delay=1.0,
                    should_abort=self._abort_by(deadline),
                )
            try:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise ServeDeadlineError(
                        "route round-trip would exceed the request deadline"
                    )
                return route_call(
                    self._route_sock, request,
                    timeout=min(self._timeout_s, remaining),
                )
            except ServeDeadlineError:
                raise
            except (OSError, ConnectionError, ValueError):
                self._close_route()
                if attempt:
                    raise
        raise ConnectionError("unreachable")  # pragma: no cover

    def _recover(self, deadline: float) -> None:
        """After a failed attempt: find the session's current home (fleet
        mode re-asks the router and follows the redirect — a re-homed
        session lands on a live backend or a promoted spare) and
        reconnect. Loops until connected or the deadline budget is
        spent."""
        self._close_backend()
        # recovery IS a state discontinuity (the old connection's slot is
        # gone) — arm the restore path here, not on the reconnect check:
        # _close_backend above already nulled the socket it keys on
        self._pending_restore = True
        while True:
            if self._should_abort is not None and self._should_abort():
                raise ConnectionError(
                    "serve client stopping: stop requested"
                )
            if time.monotonic() >= deadline:
                raise ServeDeadlineError(
                    "recovery exceeded the request deadline budget"
                )
            try:
                if self._router:
                    info = self._route(
                        {"op": "where", "session": self.session}, deadline
                    )
                    if "error" in info:
                        # no live backend YET: the router may be mid
                        # spare-promotion — poll inside the budget
                        time.sleep(0.05)
                        continue
                    addr = (info["addr"][0], int(info["addr"][1]))
                    epoch = int(info["epoch"])
                    if epoch != self._epoch:
                        # the redirect: the session re-homed
                        self._epoch = epoch
                        self.backend_addr = addr
                        self.rehomed_count += 1
                        self.last_rehomed = True
                self._connect_backend(deadline)
                return
            except ServeDeadlineError:
                raise
            except (OSError, ConnectionError):
                # backend refused / mid-restart: go around (deadline- and
                # abort-bounded above)
                time.sleep(0.05)

    # -- request/reply -------------------------------------------------------

    def _recv_reply_on(
        self, sock: socket.socket, request_id: int, deadline: float
    ) -> Tuple[Dict[str, Any], Any]:
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                # surfaced as a retryable timeout; step() converts to the
                # typed deadline error once the budget is truly spent
                raise socket.timeout("request deadline elapsed mid-wait")
            sock.settimeout(min(self._timeout_s, remaining))
            frame = _recv_frame(sock)
            if frame is None:
                raise ConnectionError("serve server closed the connection")
            kind, payload = frame
            if kind != KIND_SERVE_REPLY:
                continue   # future control kinds: skip, stay in sync
            meta, arrays = decode_rollout_bytes(payload, upcast=True)
            if meta["rollout_id"] == request_id:
                return meta, arrays
            # an out-of-order echo (attach duplicates): keep draining

    def _step_once(
        self,
        obs: Dict[str, np.ndarray],
        reset: bool,
        deadline: float,
    ) -> Dict[str, int]:
        request_id = self._next_id
        self._next_id += 1
        send_reset = reset
        send_carry = None
        if self._pending_restore:
            if self._shadow and self._carry_stash is not None:
                # bit-exact resume: the stashed row rides this request
                # and the backend installs it before dispatching
                send_carry = self._carry_stash
            else:
                # honest default: the fresh slot's carry is zeros — make
                # the reset explicit and COUNT the discontinuity
                send_reset = True
        trace_blob = None
        tracer = tracing.get()
        if tracer is not None and tracer.should_sample():
            # request-side trace record (ISSUE 12): the server stamps
            # recv/reply and echoes it; `done` below closes the RTT
            rec = tracing.new_record(
                tracer.next_tid(self.slot), self.slot, self.last_version
            )
            tracing.append_hop(rec, "encode")
            trace_blob = tracing.record_to_blob(rec, pad=False)
        arrays: Dict[str, Any] = {
            "obs": obs,
            "reset": np.asarray(1.0 if send_reset else 0.0, np.float32),
        }
        if send_carry is not None:
            arrays["carry"] = send_carry
        payload = encode_rollout_bytes(
            arrays,
            model_version=self.last_version,
            env_id=self.slot,
            rollout_id=request_id,
            length=1,
            total_reward=0.0,
            **self._wire_kwargs,
            trace=trace_blob,
        )
        t0 = time.perf_counter()
        _send_frame(self._sock, KIND_SERVE_REQUEST, payload)
        meta, reply = self._recv_reply_on(self._sock, request_id, deadline)
        self.last_latency_s = time.perf_counter() - t0
        if tracer is not None and "trace_blob" in meta:
            rec = tracing.parse_blob(meta["trace_blob"])
            if rec is not None:
                tracing.append_hop(rec, "done")
                tracer.emit_chunk(rec)
        if self._pending_restore:
            self._pending_restore = False
            if send_carry is None:
                self.carry_resets += 1
        self.last_version = meta["model_version"]
        self._last_packed = np.asarray(reply["actions"]).astype(np.int32)
        self.last_logp = float(np.asarray(reply["logp"]).reshape(-1)[0])
        if "dispatch_idx" in reply:
            self.last_dispatch_idx = int(
                np.asarray(reply["dispatch_idx"]).reshape(-1)[0]
            )
        if self._shadow:
            stash = reply.get("carry")
            if stash is not None:
                self._carry_stash = stash
        return {h: int(self._last_packed[j]) for j, h in enumerate(HEADS)}

    def step(
        self,
        obs: Dict[str, np.ndarray],
        reset: bool = False,
    ) -> Dict[str, int]:
        """One action for one observation (unbatched leaves). Returns the
        per-head action indices; the joint log-prob, serving weights
        version, raw packed row, and measured round-trip latency land on
        ``last_logp`` / ``last_version`` / ``last_packed`` /
        ``last_latency_s``.

        Resolves within ``serve.request_deadline_s``: transient failures
        (dead backend, dropped connection, slow window) are retried up to
        ``serve.request_retries`` times — fleet mode re-asks the router
        between attempts and follows its redirect — and budget exhaustion
        raises the typed :class:`ServeDeadlineError`, never hangs."""
        deadline = time.monotonic() + self._deadline_s
        attempts = 0
        last_err: Optional[BaseException] = None
        while True:
            if self._should_abort is not None and self._should_abort():
                raise ConnectionError(
                    "serve client stopping: stop requested"
                )
            try:
                return self._step_once(obs, reset, deadline)
            except ServeDeadlineError:
                raise
            except (OSError, ConnectionError, ValueError) as e:
                # socket.timeout is an OSError; FrameCorrupt a ValueError:
                # every transport-shaped failure rides one retry path
                last_err = e
            attempts += 1
            self.retries_total += 1
            if (
                time.monotonic() >= deadline
                or attempts > self._retries
            ):
                raise ServeDeadlineError(
                    f"serve request failed after {attempts} attempt(s) "
                    f"inside the {self._deadline_s:.1f}s budget "
                    f"({type(last_err).__name__}: {last_err})"
                ) from last_err
            self._recover(deadline)

    @property
    def last_packed(self) -> np.ndarray:
        """The raw packed ``[5]`` int32 action row of the last reply (the
        parity digest compares these bitwise)."""
        return self._last_packed

    @property
    def last_carry(self) -> Optional[Dict[str, np.ndarray]]:
        """The carry-shadow stash (opaque wire dict) from the last reply —
        ``None`` unless ``serve.carry_shadow`` is on server-side."""
        return self._carry_stash

    def close(self) -> None:
        if self._router and self.session is not None:
            try:
                self._route(
                    {"op": "detach", "session": self.session},
                    time.monotonic() + 1.0,
                )
            except (OSError, ConnectionError, ValueError):
                pass   # router gone: the probe plane will reap the session
        self._close_backend()
        self._close_route()

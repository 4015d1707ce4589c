"""Shared-memory same-host transport lane tests (ISSUE 3).

Covers the SPSC ring (FIFO, wraparound, drop-newest-when-full, the
deferred-release zero-copy contract), the seqlock'd weights slab
(latest-wins, torn-read retry surface), slot claim/release, and the
Transport-protocol parity the learner relies on (consume_decoded feeding
the buffer's staging lanes). Everything runs in-process — attach works
within one process; the cross-process path has no test and no cell yet
(ROADMAP.md S4/R1: 8 actor processes over this lane)."""

import os

import numpy as np
import pytest

from dotaclient_tpu.transport import (
    ShmTransport,
    ShmTransportServer,
    encode_rollout,
    encode_rollout_bytes,
    encode_weights,
)


def lane_name(tag: str) -> str:
    return f"t-shm-{os.getpid()}-{tag}"


def make_lane(tag, slots=2, ring_bytes=1 << 16, weights_bytes=1 << 20):
    server = ShmTransportServer(
        name=lane_name(tag), slots=slots, ring_bytes=ring_bytes,
        weights_bytes=weights_bytes,
    )
    actor = ShmTransport(lane_name(tag), slots=slots)
    return server, actor


def tiny_rollout(rid=0, n=16):
    return encode_rollout(
        {"rewards": np.arange(n, dtype=np.float32) + rid},
        model_version=0, env_id=0, rollout_id=rid, length=n,
        total_reward=0.0,
    )


class TestRolloutRing:
    def test_fifo_exactly_once(self):
        server, actor = make_lane("fifo")
        try:
            for i in range(7):
                actor.publish_rollout(tiny_rollout(i))
            got = server.consume_rollouts(64, timeout=1.0)
            assert [r.rollout_id for r in got] == list(range(7))
            assert server.consume_rollouts(64, timeout=0.01) == []
        finally:
            actor.close()
            server.close()

    def test_wraparound_many_laps(self):
        """Frames must survive the ring edge: ship several ring-sizes worth
        of data through a small ring, draining between bursts."""
        server, actor = make_lane("wrap", ring_bytes=1 << 14)  # 16 KiB ring
        try:
            sent = 0
            received = []
            for wave in range(40):
                for _ in range(3):
                    msg = tiny_rollout(sent, n=200)   # ~800B+ frames
                    assert actor.publish_rollout_bytes(
                        msg.SerializeToString()
                    )
                    sent += 1
                received.extend(server.consume_rollouts(16, timeout=1.0))
            received.extend(server.consume_rollouts(16, timeout=0.2))
            assert [r.rollout_id for r in received] == list(range(sent))
        finally:
            actor.close()
            server.close()

    def test_drop_newest_when_full_is_counted(self):
        server, actor = make_lane("full", ring_bytes=1 << 12)  # 4 KiB ring
        try:
            wire = tiny_rollout(0, n=200).SerializeToString()   # ~860B
            sent = sum(
                1 for _ in range(20)
                if actor.publish_rollout_bytes(wire)
            )
            assert 0 < sent < 20          # ring filled, surplus dropped
            # producer-side drop counter is in the ring header
            assert server.pending_rollouts == sent
            got = server.consume_rollouts(64, timeout=1.0)
            assert len(got) == sent
            # after draining+release, publishing works again
            server.consume_rollouts(1, timeout=0.01)   # releases prior batch
            assert actor.publish_rollout_bytes(wire)
        finally:
            actor.close()
            server.close()

    def test_deferred_release_protects_inflight_views(self):
        """The zero-copy contract: frames handed out by a drain must stay
        intact while the producer keeps writing — their ring space is only
        released at the NEXT drain."""
        server, actor = make_lane("views", ring_bytes=1 << 14)
        try:
            wire = bytes(tiny_rollout(1, n=500).SerializeToString())
            n_fit = 0
            while actor.publish_rollout_bytes(wire):
                n_fit += 1
            views = server._drain(n_fit, timeout=1.0)
            assert len(views) == n_fit
            # ring is logically empty but unreleased: the producer must
            # still see it as full and drop, not overwrite the views
            # (items are (recv_ts, view) pairs since ISSUE 12)
            assert not actor.publish_rollout_bytes(wire)
            assert all(bytes(v) == wire for _ts, v in views)
        finally:
            actor.close()
            server.close()

    def test_consume_decoded_roundtrip(self):
        """The learner-ingest path: zero-copy drain → native decoder views
        → values bit-identical to what the actor shipped."""
        server, actor = make_lane("dec", ring_bytes=1 << 20)
        try:
            tree = {
                "obs": {"units": np.random.default_rng(0)
                        .normal(size=(9, 8, 4)).astype(np.float32)},
                "rewards": np.arange(8, dtype=np.float32),
            }
            actor.publish_rollout_bytes(
                encode_rollout_bytes(tree, 5, 0, 77, 8, 1.25)
            )
            out = server.consume_decoded(8, timeout=1.0)
            assert len(out) == 1
            meta, arrays = out[0]
            assert meta["model_version"] == 5
            assert meta["rollout_id"] == 77
            np.testing.assert_array_equal(
                arrays["obs"]["units"], tree["obs"]["units"]
            )
            np.testing.assert_array_equal(arrays["rewards"], tree["rewards"])
        finally:
            actor.close()
            server.close()


class TestWeightsSlab:
    def test_latest_wins_and_cache(self):
        server, actor = make_lane("w")
        try:
            assert actor.latest_weights() is None
            for v in (1, 2, 3):
                server.publish_weights(
                    encode_weights({"w": np.full(4, float(v), np.float32)}, v)
                )
            msg = actor.latest_weights()
            assert msg.version == 3
            # unchanged slab: the cached parse is returned, not re-read
            assert actor.latest_weights() is msg
        finally:
            actor.close()
            server.close()

    def test_bf16_wire_through_slab(self):
        server, actor = make_lane("wb")
        try:
            from dotaclient_tpu.transport import decode_weights

            params = {"k": np.linspace(0, 1, 9, dtype=np.float32)}
            server.publish_weights(
                encode_weights(params, 4, wire_dtype="bfloat16")
            )
            version, tree = decode_weights(actor.latest_weights())
            assert version == 4
            assert tree["k"].dtype == np.float32    # upcast on apply
        finally:
            actor.close()
            server.close()

    def test_oversized_weights_rejected(self):
        server, actor = make_lane("wo", weights_bytes=1 << 10)
        try:
            with pytest.raises(ValueError, match="shm_weights_bytes"):
                server.publish_weights(
                    encode_weights(
                        {"w": np.zeros(4096, np.float32)}, 1
                    )
                )
        finally:
            actor.close()
            server.close()


class TestSlotClaim:
    def test_two_actors_distinct_slots_and_release(self):
        server = ShmTransportServer(
            name=lane_name("claim"), slots=2, ring_bytes=1 << 14
        )
        try:
            a1 = ShmTransport(lane_name("claim"), slots=2)
            a2 = ShmTransport(lane_name("claim"), slots=2)
            assert {a1.slot, a2.slot} == {0, 1}
            assert server.n_connected == 2
            with pytest.raises(ConnectionError, match="no free shm"):
                ShmTransport(lane_name("claim"), slots=2)
            a1.close()
            assert server.n_connected == 1
            a3 = ShmTransport(lane_name("claim"), slots=2)  # reuses slot 0
            assert a3.slot == a1.slot
            a2.close()
            a3.close()
        finally:
            server.close()

    def test_actor_detects_dead_learner(self):
        """shm has no connection to break: the actor must notice a dead
        learner via the slab's pid beacon and raise ConnectionError so the
        reconnect/exit-for-supervisor machinery engages (review finding)."""
        import struct

        from dotaclient_tpu.transport import shm_transport as st

        server, actor = make_lane("alive")
        try:
            dead_pid = 2 ** 22 + 54321
            assert not st._pid_alive(dead_pid)
            struct.pack_into(
                "<Q", server._weights.buf, st._OFF_SERVER_PID, dead_pid
            )
            actor._last_liveness = -1e9   # force the time-gated probe
            with pytest.raises(ConnectionError, match="learner process"):
                actor.latest_weights()
            actor._last_liveness = -1e9
            with pytest.raises(ConnectionError, match="learner process"):
                actor.publish_rollout_bytes(b"x" * 64)
        finally:
            actor.close()
            server.close()

    def test_attach_to_dead_lane_raises(self):
        """Attaching to a crashed learner's leftover segments must fail
        like a refused connect — otherwise the reconnect loop 'succeeds'
        against a corpse forever (review finding)."""
        import struct

        from dotaclient_tpu.transport import shm_transport as st

        server = ShmTransportServer(
            name=lane_name("dead"), slots=1, ring_bytes=1 << 14
        )
        try:
            dead_pid = 2 ** 22 + 99991
            assert not st._pid_alive(dead_pid)
            struct.pack_into(
                "<Q", server._weights.buf, st._OFF_SERVER_PID, dead_pid
            )
            with pytest.raises(ConnectionError, match="learner process"):
                ShmTransport(lane_name("dead"), slots=1)
        finally:
            server.close()

    def test_server_restart_reclaims_stale_lane(self):
        """A fixed --shm-name must survive a SIGKILL'd predecessor: the new
        server reclaims segments whose pid beacon is dead instead of
        crash-looping on FileExistsError (review finding)."""
        import struct

        from dotaclient_tpu.transport import shm_transport as st

        name = lane_name("restart")
        old = ShmTransportServer(name=name, slots=1, ring_bytes=1 << 14)
        dead_pid = 2 ** 22 + 77777
        struct.pack_into("<Q", old._weights.buf, st._OFF_SERVER_PID, dead_pid)
        # simulate the crash: the segments persist, close() never runs
        st._OWNED_BY_THIS_PROCESS.discard(f"{name}-w")
        st._OWNED_BY_THIS_PROCESS.discard(f"{name}-r0")
        new = ShmTransportServer(name=name, slots=1, ring_bytes=1 << 14)
        try:
            actor = ShmTransport(name, slots=1)   # fresh lane works
            actor.publish_rollout(tiny_rollout(5))
            got = new.consume_rollouts(4, timeout=1.0)
            assert [r.rollout_id for r in got] == [5]
            actor.close()
        finally:
            new.close()
        # a LIVE owner is never stolen from
        live = ShmTransportServer(name=name, slots=1, ring_bytes=1 << 14)
        try:
            with pytest.raises(FileExistsError, match="live learner"):
                ShmTransportServer(name=name, slots=1, ring_bytes=1 << 14)
        finally:
            live.close()

    def test_crashed_actor_slot_is_reaped(self):
        """A SIGKILL'd actor never runs close(): the server must reap its
        slot (dead-pid claim word + leftover lockfile) so a restarted
        actor can connect instead of exhausting slots."""
        import struct

        from dotaclient_tpu.transport import shm_transport as st

        server = ShmTransportServer(
            name=lane_name("reap"), slots=1, ring_bytes=1 << 14
        )
        try:
            actor = ShmTransport(lane_name("reap"), slots=1)
            # simulate the crash: the claim word + lockfile survive, the
            # process behind the pid does not (use a free pid)
            dead_pid = 2 ** 22 + 12345
            assert not st._pid_alive(dead_pid)
            struct.pack_into(
                "<Q", server._rings[0].buf, st._OFF_CLAIM, dead_pid
            )
            actor._ring = None   # the "crashed" actor must not unlock
            with pytest.raises(ConnectionError):
                ShmTransport(lane_name("reap"), slots=1)   # slot still held
            server._publish_ring_telemetry()               # reap pass
            assert server.n_connected == 0
            revived = ShmTransport(lane_name("reap"), slots=1)
            assert revived.slot == 0
            revived.close()
            actor._weights_shm.close()
        finally:
            server.close()

    def test_both_claimed_rings_are_drained(self):
        server = ShmTransportServer(
            name=lane_name("multi"), slots=2, ring_bytes=1 << 16
        )
        try:
            a1 = ShmTransport(lane_name("multi"), slots=2)
            a2 = ShmTransport(lane_name("multi"), slots=2)
            for i in range(4):
                a1.publish_rollout(tiny_rollout(i))
                a2.publish_rollout(tiny_rollout(100 + i))
            got = server.consume_rollouts(64, timeout=1.0)
            assert sorted(r.rollout_id for r in got) == sorted(
                list(range(4)) + list(range(100, 104))
            )
            a1.close()
            a2.close()
        finally:
            server.close()

"""Child producer for bench.py's transport stage.

Runs as a separate OS process (the real split-topology shape: an actor
process feeding the learner's transport) and ships ``--frames`` rollout
frames of ``--bytes`` wire bytes each through the requested lane. Does no
device work (bench.py starts it with ``JAX_PLATFORMS=cpu``: its parent
holds the chip), and the parent's timing window starts at first frame
arrival, so it measures transport, not interpreter startup.

Usage (spawned by bench.py, but runnable by hand):
    python scripts/bench_transport_producer.py --lane socket \
        --addr 127.0.0.1:7777 --frames 2000 --bytes 65536
    python scripts/bench_transport_producer.py --lane shm \
        --addr tpu-dota-12345 --frames 2000 --bytes 65536
"""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--lane", choices=("socket", "shm"), required=True)
    p.add_argument("--addr", required=True,
                   help="host:port (socket) or lane name (shm)")
    p.add_argument("--frames", type=int, default=2000)
    p.add_argument("--bytes", type=int, default=65536)
    p.add_argument("--payload-hex", default=None,
                   help="explicit payload bytes (hex); default zeros")
    args = p.parse_args(argv)

    payload = (
        bytes.fromhex(args.payload_hex)
        if args.payload_hex
        else b"\x00" * args.bytes
    )
    if args.lane == "socket":
        from dotaclient_tpu.transport.socket_transport import SocketTransport

        host, port = args.addr.rsplit(":", 1)
        t = SocketTransport(host, int(port))
        for _ in range(args.frames):
            # TCP applies its own backpressure (sendall blocks when the
            # consumer falls behind)
            t.publish_rollout_bytes(payload)
    else:
        from dotaclient_tpu.transport.shm_transport import ShmTransport

        t = ShmTransport(args.addr)
        stuck_since = None
        for _ in range(args.frames):
            # ring-full means the consumer owes a drain: spin-yield (the
            # production actor drops instead — a bench must deliver all
            # frames to measure sustained throughput). Bounded: a consumer
            # that stopped draining must not leave a 100%-CPU orphan.
            while not t.publish_rollout_bytes(payload):
                now = time.monotonic()
                if stuck_since is None:
                    stuck_since = now
                elif now - stuck_since > 60.0:
                    print("producer: ring full for 60s; consumer gone",
                          file=sys.stderr)
                    t.close()
                    return 1
                time.sleep(0)
            stuck_since = None
    t.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

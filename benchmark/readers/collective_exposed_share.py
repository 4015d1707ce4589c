"""``collective_exposed_share``: share of the traced window with a
collective in flight and no other operation running, on the chip where
that is largest."""

from benchmark.harness import result, trace


def read(record):
    tw = result.traced_window(record)
    if tw is None or record["chips"] < 2:
        return None
    tr, lo, hi = tw
    return 100.0 * max(trace.collective_seconds(p, lo, hi)[1] for p in tr.devices) / ((hi - lo) * 1e-9)

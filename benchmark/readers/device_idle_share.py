"""``device_idle_share``: 1 - busy union over the traced window, on the
chip that idles most."""

from benchmark.harness import result, trace


def read(record):
    tw = result.traced_window(record)
    if tw is None:
        return None
    tr, lo, hi = tw
    window = (hi - lo) * 1e-9
    return 100.0 * max(1.0 - trace.busy_seconds(p, lo, hi) / window for p in tr.devices)

"""``hbm_peak_gb``: the allocator's peak on the fullest chip, 1e9 bytes."""

from benchmark.harness import device


def read(record):
    peak = device.memory_peak_bytes(record["devices"])
    return peak / 1e9 if peak else None

"""``gqa_ring_device_share``: share of device busy time in operations written under
``core_attn_full`` or ``core_cache_write`` (``models/afmoe.py`` ``Attention`` as the LFM2 core runs
it: 32 query heads over 8 KV heads of 64, one ring pair a lane), forward and transposed, mean over
chips; 0 where a program has no such scopes."""

from benchmark.readers import _scopes

SCOPES = ("core_attn_full", "core_cache_write")


def read(record):
    return _scopes.share(record, lambda op: any(_scopes.under(op, s) for s in SCOPES))

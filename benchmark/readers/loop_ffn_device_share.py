"""``loop_ffn_device_share``: share of device busy time in operations written under
``core_loop`` and ``core_dense_ffn`` (the tied SwiGLU of ``models/looplm.py``'s stack),
forward and transposed, mean over chips; 0 where a program has no such scopes."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "core_loop", "core_dense_ffn"))

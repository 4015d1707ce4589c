"""``lfm2_dense_ffn_device_share``: share of device busy time in operations written under
``core_dense_ffn`` (the leading layer's SwiGLU of width 11,776, 72 M of the core's 452 M
parameters), forward and transposed, mean over chips; 0 where a program has no such scope."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "core_dense_ffn"))

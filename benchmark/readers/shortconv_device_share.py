"""``shortconv_device_share``: share of device busy time in operations written under ``core_conv``
(``models/lfm2moe.py``: the gated short-convolution mixers whole), forward and transposed, mean over
chips; 0 where a program has no such scope."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "core_conv"))

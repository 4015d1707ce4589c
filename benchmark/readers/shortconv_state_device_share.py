"""``shortconv_state_device_share``: share of device busy time in operations written under
``core_conv_state`` (``models/lfm2moe.py``, inside ``core_conv``: the history read, the taps and the
history write; no projection), forward and transposed, mean over chips. A handful of element-wise
operations: what the compiler fuses into a projection's fusion carries that fusion's name and is
not in it, so no roofline is built on this scope."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "core_conv_state"))

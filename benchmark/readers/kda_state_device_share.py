"""``kda_state_device_share``: share of device busy time in operations written under ``core_kda_state``
(``models/kimilinear.py``: the recurrence and its readout alone), forward and transposed, mean over chips; 0 where a
program has no such scope."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "core_kda_state"))

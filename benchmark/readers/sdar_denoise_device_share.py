"""``sdar_denoise_device_share``: share of device busy time in operations written under
``core_denoise`` (``models/sdar.py decode``: the S denoising passes of a rollout step whole, the
layers' products against weights and rings), mean over chips; 0 where a program has no such scope."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "core_denoise"))

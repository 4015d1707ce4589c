"""``attn_latent_device_share``: share of device busy time in operations written under ``core_attn_latent``
(``models/kimilinear.py``: the MLA layer's projections and its absorbed products against the latent ring), forward and transposed, mean over chips; 0 where a
program has no such scope."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "core_attn_latent"))

"""``lfm2_moe_device_share``: share of device busy time in operations written under ``core_router``
or ``core_experts_routed`` (``models/afmoe.py`` ``RoutedExperts`` at 64 outputs with no shared
expert: ``core_expert_shared`` holds nothing here), forward and transposed, mean over chips; 0 where
a program has no such scopes."""

from benchmark.readers import _scopes

SCOPES = ("core_router", "core_experts_routed")


def read(record):
    return _scopes.share(record, lambda op: any(_scopes.under(op, s) for s in SCOPES))

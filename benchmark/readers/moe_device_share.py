"""``moe_device_share``: share of device busy time in operations written under
``core_router``, ``core_experts_routed`` or ``core_expert_shared`` (``models/afmoe.py``
``RoutedExperts``, whichever core runs it), forward and transposed, mean over chips; 0 where a
program has no such scopes."""

from benchmark.readers import _scopes

SCOPES = ("core_router", "core_experts_routed", "core_expert_shared")


def read(record):
    return _scopes.share(record, lambda op: any(_scopes.under(op, s) for s in SCOPES))

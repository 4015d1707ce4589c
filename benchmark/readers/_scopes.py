"""What the seven ``*_device_share`` metrics of PR 24 share: which of the
program's named scopes an operation was written under.

The program scopes its fused step with ``jax.named_scope`` (``train/fused.py``
``phase_rollout`` and ``phase_update``; ``actor/device_rollout.py``
``rollout_*``; ``train/ppo.py`` ``update_*``), and XLA keeps the path in
each operation's scoped name (``harness/trace.py``). A backward operation
carries ``jvp(...)`` and ``transpose(...)`` around parts of that path, and
where they sit is JAX's choice. On the v5e (the cells' traces, PR 24) a scope
entered OUTSIDE the differentiated function stays outside them and one
entered inside it is wrapped on its own: ``jit(one_iter)/phase_update/
update_loss/transpose(jvp(Policy.sequence))/policy_core_scan/while/body/...``,
``.../update_loss/jvp(update_gae)/while/body/...``; XLA:CPU's module also has
``.../update_loss/transpose(phase_update)/update_loss/jvp(...)``. So the path
is split at ``/`` and every segment is unwrapped, and a scope is matched as a
WHOLE segment: ``update_loss`` is not found inside ``dynamic_update_slice``,
and a backward operation counts under the scope its forward was written in.

An operation the compiler made itself (``copy-done``, ``slice-done``, a
``while``, an all-reduce) has no scoped name at all and counts under no
scope: at the small cell these are 7% of device time, all of it inside the
rollout's loop (``PERF.md`` section 5).

A trace of a program without these scopes (the parent of PR 24, the traces
recorded in PR 22) has no such segment: every reader here then reads 0, and
``unscoped_device_share`` 100.
"""

from benchmark.harness import result, trace

PHASES = ("phase_rollout", "phase_update")
_WRAPPERS = ("jvp(", "transpose(")


def segments(scope):
    """The path segments of a scoped name, ``jvp(`` and ``transpose(``
    wrappers and their closing brackets taken off each."""
    out = []
    for seg in scope.rstrip(":").split("/"):
        while seg.startswith(_WRAPPERS) and seg.endswith(")"):
            seg = seg[seg.index("(") + 1:-1]
        out.append(seg)
    return tuple(out)


def under(op, *names):
    """Whether ``op`` was written under every one of the scopes ``names``."""
    segs = segments(op.scope)
    return all(name in segs for name in names)


def in_policy(op):
    """Under one of the policy's own scopes, as ``policy_core_share`` and
    ``nonpolicy_device_share`` decide it."""
    return trace.scope_of(op).startswith("policy_")


def share(record, keep):
    """Percent of device busy self time in the traced window spent in the
    operations ``keep`` holds for, mean over chips; ``None`` untraced."""
    tw = result.traced_window(record)
    if tw is None:
        return None
    return trace.mean_share_where(*tw, keep)

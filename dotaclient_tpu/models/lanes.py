"""Several lane sets' carries through ONE step of the policy.

A rollout whose two teams play the same parameters steps both teams in one
call of ``Policy.step``: every product against a weight sees all rows, so each
weight is read once. The carries are not joined: a ring or a state is
megabytes a lane, donated and updated in place, so each set's large leaves
stay the set's own arrays (``LaneBlocks``) and whatever touches one runs a
block at a time (``by_lane_block``). This module is all that knows the format:
the rollout builds a ``LaneBlocks`` (``actor/device_rollout.py``), the policy
joins and splits it around its core (``models/policy.py``), and a core calls
``by_lane_block`` where it touches a carry leaf (``models/afmoe.py``,
``models/kimilinear.py``, ``models/lfm2moe.py``). A plain carry is one block everywhere.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Carry = Any


class LaneBlocks(tuple):
    """Several lane sets' carries handed to ONE ``Policy.step`` (a rollout
    whose two teams play the same parameters): the step's rows are the sets'
    lanes in order, and it hands back a ``LaneBlocks`` of the new carries. A
    leaf to ``jax.tree``, so it never crosses a ``jit`` or ``scan`` boundary."""


def _is_blocks(x) -> bool:
    return isinstance(x, LaneBlocks)


def join_lanes(carry: Carry, stays: bool) -> Carry:
    """A ``LaneBlocks`` of carries as one carry over all their lanes. Rows are
    concatenated (the LSTM's, the window's: kilobytes a lane). Where the carry
    ``stays`` on the chip only the per-lane counters (``[B]``) are: a ring or a
    state is megabytes a lane, updated in place, and becomes a ``LaneBlocks``
    of the sets' arrays as they lie, which the core reads and writes a block
    at a time (``by_lane_block``). Any other carry is returned as it is."""
    if not _is_blocks(carry):
        return carry
    return jax.tree.map(
        lambda *leaf: LaneBlocks(leaf) if stays and leaf[0].ndim > 1 else jnp.concatenate(leaf), *carry
    )


def split_lanes(joined: Carry, like: Carry) -> Carry:
    """``join_lanes`` undone: the joined carry as ``like``'s lane sets."""
    if not _is_blocks(like):
        return joined
    sets, at = [], 0
    for i, c in enumerate(like):
        n = jax.tree.leaves(c)[0].shape[0]
        sets.append(jax.tree.map(
            lambda leaf: leaf[i] if _is_blocks(leaf) else leaf[at:at + n], joined, is_leaf=_is_blocks
        ))
        at += n
    return LaneBlocks(sets)


def by_lane_block(fn, leaves, *rows):
    """``fn(leaves, *rows) -> (out, leaves)`` where a step serves several lane
    sets in one pass: ``leaves`` is a carry leaf (a ring, a state) or a tuple
    of them, ``rows`` are ``[B, ...]`` arrays over all lanes, ``out`` is arrays
    of ``[B, ...]`` rows (or None). A leaf of megabytes a lane is never
    concatenated with another set's: ``fn`` runs once a block, on the block's
    arrays as they lie and its rows of ``rows``; the ``out``s are concatenated
    (activations: kilobytes) and the new leaves stay a block each. Plain
    leaves are one block: ``fn`` is called as it is."""
    first = jax.tree.leaves(leaves, is_leaf=_is_blocks)[0]
    if not _is_blocks(first):
        return fn(leaves, *rows)
    outs, news, at = [], [], 0
    for i, block in enumerate(first):
        n = block.shape[0]
        out, new = fn(
            jax.tree.map(lambda l: l[i], leaves, is_leaf=_is_blocks), *(r[at:at + n] for r in rows)
        )
        outs.append(out)
        news.append(new)
        at += n
    return (
        jax.tree.map(lambda *o: jnp.concatenate(o), *outs),
        jax.tree.map(lambda *l: LaneBlocks(l), *news),
    )

"""The rollout's loop body as the TPU's compiler leaves it (ISSUE 33), for a
described v5e (no chip needed): where both teams play the same parameters a
step multiplies by every weight ONCE and copies no ring, no latent ring and no
KDA state; the frozen opponent's loop is the loop it was. Beside
``tests/test_shared_pass.py``, which holds the mathematics; one file, so that
one worker loads the TPU's library."""

import collections
import dataclasses
import re

import jax
import numpy as np
import pytest

from dotaclient_tpu.models.policy import init_params, make_policy
from tests.test_kimilinear import one_chip  # noqa: F401  (the described, unattached v5e: a fixture)

CELLS = {
    "afmoe": "trinity-mini-5v5-ep16.fused-selfplay-anycore",
    "kimilinear": "kimi-linear-5v5-ep32.fused-selfplay-anycore",
    "looplm": "ouro-2.6b-5v5-ut4.fused-selfplay-anycore",
}
# Instructions in the FROZEN opponent's loop body and everything it calls, at
# the parent of PR 33 (commit 8915058) and since: its two passes are the same
# lines as before. (jax 0.9.0, libtpu 0.0.34; a new compiler may move them.)
# Kimi-Linear's count fell from 21,664 at PR 36: a KDA layer's step is one Pallas call
# a lane set there (``ops/pallas/kda_step.py``), where the closed form's T = 1 case was
# some 184 instructions; the other two cores run no line that PR touched.
# Since a routed layer's three grouped products became the grouped-matmul kernels
# (``ops/pallas/grouped_matmul.py``: one call on the scalar unit for the layer's tables,
# one a product), where XLA's ``ragged_dot`` lowering was its own metadata call and three
# products: Trinity's count fell from 21,809 and Kimi-Linear's rose from 20,192. The
# looped core holds no routed layer.
FROZEN_BODY_INSTRUCTIONS = {"afmoe": 21512, "kimilinear": 20200, "looplm": 29017}


def computations(text):
    """{name: its instruction lines} of an HLO module's text."""
    comps, lines = {}, None
    for ln in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", ln)
        if head:
            lines = comps.setdefault(head.group(1), [])
        elif ln.startswith("}"):
            lines = None
        elif lines is not None and " = " in ln:
            lines.append(ln)
    return comps


def loop_body(text):
    """(the rollout scan's body: its own instructions, each of which makes a
    buffer; those and the instructions of every computation the body calls:
    fusions, reducers, inner loops)."""
    comps = computations(text)
    whiles = [ln for lines in comps.values() for ln in lines if re.search(r" while\(", ln)]
    # the rollout's scan is the loop that carries the game's state: the longest body
    body = max((re.search(r"body=%?([\w.\-]+)", ln).group(1) for ln in whiles), key=lambda c: len(comps[c]))
    seen, todo = [], [body]
    while todo:
        c = todo.pop()
        if c not in seen and c in comps:
            seen.append(c)
            for ln in comps[c]:
                todo += re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", ln)
    return comps[body], [ln for c in seen for ln in comps[c]]


def weight_products(lines):
    """{scoped name of a Dense or einsum against a parameter: how many
    ``dot``/``convolution`` instructions carry it, and of the held experts'
    grouped products: how many calls of the grouped-matmul kernel, one weight
    operand each}; the products against rings and states (inside
    ``jax.checkpoint``) apart."""
    weights, held = collections.Counter(), collections.Counter()
    for ln in lines:
        if re.search(r" (?:dot|convolution)\(", ln) or re.search(r' custom-call\(.*op_name="[^"]*/grouped_matmul/', ln):
            name = re.search(r'op_name="([^"]*)"', ln).group(1)
            name = name[name.index("policy_"):] if "policy_" in name else name
            (held if "checkpoint" in name else weights)[name] += 1
    return weights, held


def carry_sized_detours(body, leaves):
    """The body's own instructions that make a NEW buffer of the size of one
    team's ring, latent ring or KDA state (a leaf of a megabyte or more) by
    ``copy``, ``concatenate`` or ``convert``, and those that make one of the
    size of both teams' together in any way."""
    large = {x.size for x in leaves if x.size * x.dtype.itemsize >= 2**20}
    assert large
    found = []
    for ln in body:
        m = re.search(r"= \(?\w+\[([\d,]+)\]\S* ([\w\-]+)\(", ln)
        size = int(np.prod([int(n) for n in m.group(1).split(",")])) if m else 0
        if (size in large and m.group(2) in ("copy", "concatenate", "convert")) or size // 2 in large and size % 2 == 0:
            found.append(ln.strip()[:160])
    return found


def rollout_text(cell_name, one_chip, live):
    """The optimised HLO of ``_rollout_impl`` at the cell's published widths,
    one game (5 lanes a team), the opponent the learner's own parameters
    (``live``) or a frozen set."""
    from benchmark.harness import cells, program
    from dotaclient_tpu.actor.device_rollout import DeviceActor

    cfg = program.build_run_config(cells.load_cell(cell_name), seed=0, rehearsal=False)
    cfg = dataclasses.replace(cfg, env=dataclasses.replace(cfg.env, n_envs=1))
    if cfg.model.loop_steps > 1:
        # a ring for every layer and loop step is 403 MB a lane at Ouro's 3,072 positions, and a ring's
        # length is no width: 256 positions and games of 40 s keep ten lanes' carry a third of a gigabyte
        cfg = dataclasses.replace(
            cfg, env=dataclasses.replace(cfg.env, max_dota_time=40.0),
            model=dataclasses.replace(cfg.model, full_context=256),
        )
    policy = make_policy(cfg.model, cfg.obs, cfg.actions)
    actor = DeviceActor(cfg, policy, seed=0)
    assert actor.one_pass_when_live
    shapes = jax.eval_shape(lambda: (init_params(policy, jax.random.PRNGKey(0)), actor.state))
    params, state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    if live:
        lowered = jax.jit(lambda p, s: actor._rollout_impl(p, s, None), donate_argnums=(1,)).lower(params, state)
    else:
        lowered = jax.jit(actor._rollout_impl, donate_argnums=(1,)).lower(params, state, params)
    return lowered.compile().as_text(), jax.tree.leaves(state.carry)


@pytest.mark.parametrize("core", sorted(CELLS))
def test_the_live_loop_reads_each_weight_once_and_copies_no_carry(one_chip, core):
    live, leaves = rollout_text(CELLS[core], one_chip, live=True)
    frozen, _ = rollout_text(CELLS[core], one_chip, live=False)
    (live_body, live_all), (frozen_body, frozen_all) = loop_body(live), loop_body(frozen)
    w_live, held_live = weight_products(live_all)
    w_frozen, held_frozen = weight_products(frozen_all)
    # (a) every product against a weight once where the frozen loop has it twice
    assert w_live and set(w_live) == set(w_frozen)
    assert {name: 2 * n for name, n in w_live.items()} == dict(w_frozen)
    # ... and the products against rings and states a team each, as they were
    assert held_live == held_frozen and sum(held_live.values()) >= 2
    # (b) no ring, latent ring or KDA state is copied, joined or rounded in the loop
    assert carry_sized_detours(live_body, leaves) == []
    assert carry_sized_detours(frozen_body, leaves) == []
    # the frozen opponent's loop is the loop it was before the live one changed
    assert len(frozen_all) == FROZEN_BODY_INSTRUCTIONS[core], len(frozen_all)
    assert len(live_all) < len(frozen_all)

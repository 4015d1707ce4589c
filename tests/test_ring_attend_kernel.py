"""The ring kernel (``ops/pallas/ring_attend.py``) in interpreter mode on the
CPU, as ``tests/test_kda_step_kernel.py`` holds the KDA step kernel: a
rollout pass's attention against ``afmoe._attend``, its specification, at
rings of a few blocks, lanes at different positions (a reset lane, a range
that wraps past the ring's end, a lane past a whole ring), passes of 6 and 5
rows, bfloat16 and float32; through ``sdar.BlockAttention`` over a plain
carry and over a ``LaneBlocks`` of two lane sets; a tiny model's decode over
twelve steps through the kernel against the decode through ``_attend``; the
visits the grid makes; which passes take the kernel and what the learner
counts. (Compiled by Mosaic: a rollout step at the cell's widths in
``tests/test_sdar.py``, for a described v5e.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import obs as obs_mod
from dotaclient_tpu.config import default_config
from dotaclient_tpu.models import distributions as D, init_params, sdar
from dotaclient_tpu.models.afmoe import _attend
from dotaclient_tpu.models.lanes import LaneBlocks
from dotaclient_tpu.models.policy import Policy
from dotaclient_tpu.ops.pallas import ring_attend as ra
from dotaclient_tpu.train import learner
from tests.test_afmoe import perturbed, run_config
from tests.test_sdar import tiny_model

HD, BLOCK, R = 128, 128, 512               # heads of whole lanes, rings of four blocks
# a lane each: a reset lane, a range across a block boundary, a range wrapping past R, a whole ring, past a whole ring
POS = [0, 100, 300, 512, 2000]
CURSOR = [7, 300, 100, 77, 17]
CLOSE = {"float32": dict(rtol=1e-5, atol=1e-6), "bfloat16": dict(rtol=1e-2, atol=1e-2)}


def masks(pos, cursor, own, R):
    """``BlockAttention``'s masks of a rollout pass: a row sees the ring rows
    its lane's episode wrote, and the pass's rows as ``own`` says."""
    B, N = pos.shape[0], own.shape[0]
    age = (cursor[:, None] - 1 - jnp.arange(R)[None, :]) % R
    return (
        jnp.broadcast_to((age < pos[:, None])[:, None, :], (B, N, R)),
        jnp.broadcast_to(jnp.asarray(own)[None], (B, N, N)),
    )


def operands(N, dtype, kv=2, G=4, seed=0):
    """A pass's queries, keys and values and the rings; every ring row a lane
    cannot see holds 1e4, so a row seen by mistake shows."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    B = len(POS)
    pos, cursor = jnp.asarray(POS, jnp.int32), jnp.asarray(CURSOR, jnp.int32)
    seen = masks(pos, cursor, np.ones((N, N), bool), R)[0][:, 0, :, None]
    ring = lambda: jnp.where(seen, f(B, R, HD), 1e4).astype(dtype)
    q = (f(B, N, kv, G, HD) / np.sqrt(HD)).astype(dtype)
    return q, f(B, N, kv, HD).astype(dtype), f(B, N, kv, HD).astype(dtype), [ring() for _ in range(kv)], [ring() for _ in range(kv)], pos, cursor


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("own", ["first_pass_6_rows", "slot_pass_5_rows"])
def test_the_kernel_is_attend_over_the_rows_a_lane_sees(dtype, own):
    own = sdar.FIRST_PASS if own.startswith("first") else sdar.SLOT_PASS
    q, k, v, ring_k, ring_v, pos, cursor = operands(own.shape[0], jnp.dtype(dtype))
    see_ring, see_own = masks(pos, cursor, own, R)
    want = _attend(q, k, v, jnp.stack(ring_k, 2), jnp.stack(ring_v, 2), see_ring, see_own)
    got = jax.jit(
        lambda q, k, v, rk, rv, p, c: ra.ring_attend(q, k, v, rk, rv, p, c, own, interpret=True, block_rows=BLOCK)
    )(q, k, v, ring_k, ring_v, pos, cursor)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all() and float(jnp.abs(want).max()) < 10
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **CLOSE[dtype])


def test_the_grid_visits_each_block_a_lane_sees_once_and_no_other():
    """The visits (``_schedule``) over many counters: lane by lane, a lane's
    blocks distinct and in ring order from the block of its first row, every
    row the lane sees inside them, and one visit for a lane that sees none."""
    rng = np.random.default_rng(1)
    B, nblk = 7, R // BLOCK
    for _ in range(50):
        pos = rng.choice([0, 1, 5, 127, 128, 129, 300, 511, 512, 513, 4000], B).astype(np.int32)
        cursor = rng.integers(0, R, B).astype(np.int32)
        visits, length = (np.asarray(x) for x in ra._schedule(jnp.asarray(pos), jnp.asarray(cursor), R, BLOCK))
        assert visits.shape == (B * nblk + 1,) and (visits[int(length):] == B * nblk).all()
        lane, block = visits[:int(length)] // nblk, visits[:int(length)] % nblk
        assert (np.diff(lane) >= 0).all() and set(lane) == set(range(B))
        _, count = ra.visited_blocks(jnp.asarray(pos), jnp.asarray(cursor), R, BLOCK)
        for b in range(B):
            mine = block[lane == b]
            seen = {int(r) // BLOCK for r in range(R) if (cursor[b] - 1 - r) % R < min(pos[b], R)}
            assert len(mine) == max(int(count[b]), 1) and len(set(mine)) == len(mine)
            assert seen == (set(mine) if pos[b] else set())
            assert (np.diff(mine) % nblk == 1).all()


def _kernel_everywhere(cfg, rows, platform):
    """``pass_takes_kernel`` with the platform and the widths taken out: what
    the tests patch in to run the kernel's path (interpreted) on the CPU."""
    return rows <= sdar.ROWS


@pytest.mark.parametrize("blocks", ["one_plain_block", "two_unequal_lane_blocks"])
def test_a_block_attention_through_the_kernel_is_the_layer_through_attend(monkeypatch, blocks):
    model = tiny_model(full_context=48)
    layer = sdar.BlockAttention(model)
    B, N, Rt, kv, hd = 5, sdar.ROWS, model.full_context, model.n_kv_heads, model.head_dim
    rng = np.random.default_rng(3)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    a = f(B, N, model.hidden_dim)
    ring = tuple(tuple(f(B, Rt, hd) for _ in range(kv)) for _ in range(2))
    pos0, cursor0 = jnp.asarray([0, 5, 47, 48, 100], jnp.int32), jnp.asarray([3, 5, 20, 0, 37], jnp.int32)
    p, seg = pos0[:, None] + jnp.arange(N, dtype=jnp.int32), jnp.zeros((B, N), jnp.int32)
    params = layer.init(jax.random.PRNGKey(0), a, ring, p, seg, pos0, cursor0, sdar.FIRST_PASS, (0, 1))

    def run(ring):
        out, new = layer.apply(params, a, ring, p, seg, pos0, cursor0, sdar.FIRST_PASS, (0, 1))
        return out, jax.tree.map(lambda x: jnp.concatenate(x) if isinstance(x, LaneBlocks) else x, new,
                                 is_leaf=lambda x: isinstance(x, LaneBlocks))

    want = run(ring)
    monkeypatch.setattr(sdar, "pass_takes_kernel", _kernel_everywhere)
    monkeypatch.setattr(ra, "BLOCK_ROWS", 16)
    if blocks == "two_unequal_lane_blocks":
        ring = jax.tree.map(lambda r: LaneBlocks((r[:2], r[2:])), ring)
    got = run(ring)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-5, atol=1e-6)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_a_tiny_decode_through_the_kernel_is_the_decode_through_attend(monkeypatch):
    """Twelve rollout steps of rings of 48 rows (eight steps): lanes pass a
    whole ring, an episode ends on one lane early and on another late."""
    cfg = default_config()
    model = tiny_model(full_context=48)
    policy = Policy(model, cfg.obs, cfg.actions)
    params = perturbed(jax.jit(lambda k: init_params(policy, k))(jax.random.PRNGKey(0)))
    B, steps = 4, 12
    obs = obs_mod.batch_of(run_config(model), np.random.default_rng(0), B, steps)
    dones = np.zeros((B, steps), np.float32)
    dones[0, 3] = dones[2, 9] = 1

    def rollout():
        step = jax.jit(lambda p, o, c, k: policy.apply(p, o, c, k, method=sdar.decode))
        carry, outs = policy.initial_state(B), []
        for t in range(steps):
            if t:
                carry = policy.reset_carry(carry, 1.0 - jnp.asarray(dones[:, t - 1]))
            out, carry = step(params, {k: v[:, t] for k, v in obs.items()}, carry, jax.random.split(jax.random.PRNGKey(t), 2))
            outs.append(out)
        return outs, carry

    want, want_carry = rollout()
    monkeypatch.setattr(sdar, "pass_takes_kernel", _kernel_everywhere)
    monkeypatch.setattr(ra, "BLOCK_ROWS", 16)
    got, got_carry = rollout()
    assert (np.asarray(got_carry["pos"]) > model.full_context).any()
    for g, w in zip(got, want):
        for h in D.HEADS:
            np.testing.assert_array_equal(np.asarray(g["actions"][h]), np.asarray(w["actions"][h]))
            np.testing.assert_allclose(np.asarray(g["logits"][h]), np.asarray(w["logits"][h]), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(g["value"]), np.asarray(w["value"]), rtol=1e-5, atol=1e-5)
    for g, w in zip(jax.tree.leaves(got_carry), jax.tree.leaves(want_carry)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5)


def _cell_config():
    from benchmark.harness import cells, program

    return program.build_run_config(cells.load_cell("sdar-30b-a3b-5v5-ep16.fused-selfplay-anycore"), seed=0, rehearsal=False)


@pytest.mark.parametrize("head_dim,dtype,rows,platform,kernel", [
    (128, "bfloat16", 6, "tpu", True),
    (128, "float32", 5, "tpu", True),
    (256, "bfloat16", 6, "tpu", True),
    (128, "bfloat16", 6, "cpu", False),
    (128, "bfloat16", 7, "tpu", False),
    (128, "bfloat16", 342, "tpu", False),
    (64, "bfloat16", 6, "tpu", False),
    (8, "float32", 6, "tpu", False),
])
def test_which_passes_take_the_kernel_is_told_by_platform_rows_and_width(head_dim, dtype, rows, platform, kernel):
    model = tiny_model(head_dim=head_dim, dtype=dtype, full_context=6 * 1024)
    assert sdar.pass_takes_kernel(model, rows, platform) == kernel
    # a ring that is no whole number of blocks keeps ``_attend``
    assert not sdar.pass_takes_kernel(dataclasses.replace(model, full_context=6 * 1024 + 6), rows, platform)


def test_the_learner_never_takes_the_kernel_and_a_rollout_pass_does(monkeypatch):
    """Traced with the platform taken out of the predicate: the learner's pass
    (hundreds of rows a lane) holds no kernel, a rollout step one a layer and
    pass under ``platform_dependent``."""
    monkeypatch.setattr(sdar, "pass_takes_kernel", _kernel_everywhere)
    monkeypatch.setattr(ra, "BLOCK_ROWS", 16)
    cfg = default_config()
    model = tiny_model(full_context=48)
    policy = Policy(model, cfg.obs, cfg.actions)
    params = jax.eval_shape(lambda: init_params(policy, jax.random.PRNGKey(0)))
    B, T = 2, model.rollout_chunk
    obs = obs_mod.batch_of(run_config(model), np.random.default_rng(0), B, T + 1)
    carry = policy.initial_state(B)
    acts = {h: jnp.zeros((B, T), jnp.int32) for h in D.HEADS}
    learner_text = str(jax.make_jaxpr(lambda p: policy.apply(
        p, obs, carry, jnp.zeros((B, T)), acts, jnp.ones((B, T, sdar.SLOTS), jnp.int8), method=sdar.sequence,
    ))(params))
    assert "pallas_call" not in learner_text and "platform_index" not in learner_text
    step_text = str(jax.make_jaxpr(lambda p: policy.apply(
        p, {k: v[:, 0] for k, v in obs.items()}, carry, jax.random.split(jax.random.PRNGKey(0), 1), method=sdar.decode,
    ))(params))
    assert step_text.count("platform_index") == model.n_layers * sdar.rollout_passes(model)


class _Mesh:
    """What ``_ring_kernel_calls`` reads of a mesh: its first device's platform."""

    def __init__(self, platform):
        self.devices = np.asarray([type("Device", (), {"platform": platform})()])


def test_the_learner_counts_the_cells_kernel_calls_on_a_tpu_alone():
    """The SDAR cell on a TPU: 4 layers x 4 passes less the commit's dead last
    layer, for both teams' lane sets, 16 steps a dispatch; a CPU, toy widths
    and another core count none; the gauge's share is the share of the
    blocks the kernel visits."""
    cfg = _cell_config()
    selfplay, alone = type("Actor", (), {"opponent_players": [5, 6]})(), type("Actor", (), {"opponent_players": []})()
    assert learner._ring_kernel_calls(cfg, _Mesh("tpu"), selfplay) == 15 * 2 * 16 == 480
    assert learner._ring_kernel_calls(cfg, _Mesh("tpu"), alone) == 240
    assert learner._ring_kernel_calls(cfg, _Mesh("cpu"), selfplay) == 0
    assert learner._ring_kernel_calls(dataclasses.replace(cfg, model=tiny_model()), _Mesh("tpu"), selfplay) == 0
    lfm2 = dataclasses.replace(cfg, model=dataclasses.replace(cfg.model, core="lfm2moe", diffusion_steps=0))
    assert learner._ring_kernel_calls(lfm2, _Mesh("tpu"), selfplay) == 0
    R = cfg.model.full_context
    pos, cursor = jnp.asarray([0, 1153, R + 5]), jnp.asarray([0, 1153, 700])
    # nothing; rows 0..1152 from block 0 on; the whole ring
    blocks = 0 + -(-1153 // ra.BLOCK_ROWS) + R // ra.BLOCK_ROWS
    assert float(sdar.ring_rows_read_share(cfg.model, pos, cursor)) == pytest.approx(blocks * ra.BLOCK_ROWS / (3 * R), rel=1e-6)

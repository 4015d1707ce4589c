"""The SDAR core (``models/sdar.py``) against its plain reference
(``benchmark/reference/sdar_ref.py``) at tiny widths on the CPU: hidden 32,
two layers of 4 query heads over 2 KV heads of 8, 16 experts 2 a token with
4 held and none shared (softmax router), three denoising passes and a
commit, rings of 144 positions (six a step), float32, seeded weights.

The program decodes a lane's history step by step (``decode``: S passes with
draws between them and a commit to the ring), and its learner reads the last
chunk in SDAR's layout (``sequence``: clean rows and noisy copies); the
reference takes each lane's whole history as one explicit sequence of rows
and an explicit mask, teacher-forced on the program's draws and orders. The
reference made wrong in one way at a time must DISAGREE.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import obs as obs_mod
from benchmark.reference import policy_ref, sdar_ref
from dotaclient_tpu.config import default_config
from dotaclient_tpu.models import afmoe, distributions as D, init_params, sdar
from dotaclient_tpu.models.lanes import LaneBlocks
from dotaclient_tpu.models.policy import Policy
from dotaclient_tpu.train.ppo import ppo_loss
from tests.test_afmoe import perturbed, run_config

B, T, GAMES = 4, 4, 2          # lanes, chunk steps, games (lanes are game-major: two a game)
HIST = 3 * T                    # steps decoded; the learner's chunk is the last T and a bootstrap
AGREE, DIFFER = 1e-4, 1e-2

SIZES = dict(
    core="sdar", hidden_dim=32, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=8, full_context=6 * 24,
    rollout_chunk=T, global_attn_every=1, global_attn_offset=0, n_dense_layers=0, expert_ffn_dim=16,
    moe_experts=16, experts_per_token=2, held_experts=4, expert_offset=0, n_shared_experts=0, route_scale=1.0,
    route_score="softmax", rope_theta=1e6, rms_norm_eps=1e-6, mup_enabled=False, attn_qk_norm=True,
    attn_out_gate=False, rope_full_layers=True, diffusion_steps=3, dtype="float32",
)
QUICK = {"xla_backend_optimization_level": 0, "xla_llvm_disable_expensive_passes": True}


def tiny_model(**over):
    return dataclasses.replace(default_config().model, **{**SIZES, **over})


def quickly(fn, *args):
    return jax.jit(fn).lower(*args).compile(compiler_options=QUICK)(*args)


def rollout(policy, params, obs, dones, seed=0):
    """The program's decode over a history, step by step, an episode's end
    resetting its lane: (per-step outputs, the carry before each step)."""
    step = jax.jit(lambda p, o, c, k: policy.apply(p, o, c, k, method=sdar.decode))
    carry, outs, carries = policy.initial_state(B), [], []
    for t in range(HIST):
        if t:
            carry = policy.reset_carry(carry, 1.0 - jnp.asarray(dones[:, t - 1]))
        carries.append(carry)
        out, carry = step(params, {k: v[:, t] for k, v in obs.items()}, carry,
                          jax.random.split(jax.random.PRNGKey(seed * 1000 + t), GAMES))
        outs.append(out)
    carries.append(carry)
    return outs, carries


def stacked(outs, key, sub=None, axis=1):
    xs = [o[key] if sub is None else o[key][sub] for o in outs]
    return jnp.stack(xs, axis=axis)


@pytest.fixture(scope="module")
def world():
    cfg = default_config()
    model = tiny_model()
    policy = Policy(model, cfg.obs, cfg.actions)
    params = perturbed(jax.jit(lambda k: init_params(policy, k))(jax.random.PRNGKey(0)))
    rc = run_config(model)
    rng = np.random.default_rng(0)
    obs = obs_mod.batch_of(rc, rng, B, HIST + 1)
    dones = np.zeros((B, HIST + 1), np.float32)
    dones[0, [2, 9]] = 1                      # an episode ends inside the compared chunk (steps 8-11)
    dones[1, [7]] = 1                         # on the step before the chunk: the chunk starts an episode
    dones[2, [3, 4]] = 1                      # two in a row
    outs, carries = rollout(policy, params, obs, dones)
    return {"policy": policy, "params": params, "rc": rc, "obs": obs, "dones": dones, "outs": outs, "carries": carries}


def history_actions(world):
    acts = {h: stacked(world["outs"], "actions", h) for h in D.HEADS}
    stage = stacked(world["outs"], "act_stage")
    pad = lambda x: jnp.concatenate([x, jnp.zeros_like(x[:, :1])], axis=1)     # the bootstrap step's block: never read
    return {h: pad(a) for h, a in acts.items()}, pad(stage)


def reference(world, fault=None):
    acts, stage = history_actions(world)
    return quickly(
        lambda p, o, d, a, s: sdar_ref.forward(
            p, o, d, a, s, world["rc"]["model"], world["rc"]["actions"], noisy_first=HIST - T, noisy_steps=T, fault=fault,
        )[:2],
        world["params"], world["obs"], world["dones"], acts, stage,
    )


@pytest.fixture(scope="module")
def reference_out(world):
    return reference(world)


def learner(world):
    """The learner's pass over the last chunk from the carry the fused
    program hands it (the start's counters beside the END's rings)."""
    policy, outs, carries = world["policy"], world["outs"], world["carries"]
    carry0 = policy.chunk_start_carry(carries[HIST - T], carries[HIST])
    chunk = {k: v[:, HIST - T:] for k, v in world["obs"].items()}
    acts = {h: stacked(outs[HIST - T:], "actions", h) for h in D.HEADS}
    stage = stacked(outs[HIST - T:], "act_stage")
    return quickly(
        lambda p: policy.apply(p, chunk, carry0, jnp.asarray(world["dones"][:, HIST - T:HIST]), acts, stage, method=sdar.sequence),
        world["params"],
    )


def worst(got_logits, got_values, want_logits, want_values):
    want = {"l": want_logits, "v": want_values}
    scale = max(1.0, policy_ref.max_abs_diff(want, jax.tree.map(jnp.zeros_like, want)))
    return policy_ref.max_abs_diff({"l": got_logits, "v": got_values}, want) / scale


def rollout_passes_of_chunk(world):
    outs = world["outs"][HIST - T:]
    logits = {h: jnp.stack([o["logits"][h] for o in outs], axis=2) for h in D.HEADS}     # [S, B, T, K]
    return logits, stacked(outs, "value")


# -- the program agrees with the reference -----------------------------------------


def test_every_pass_and_value_agree_with_the_reference(world, reference_out):
    """Each of the three passes' logits and the values of the last chunk,
    from the rollout's own decode (rings written by pass 1 and the commit),
    against the reference's explicit sequence teacher-forced on the draws."""
    logits, values = rollout_passes_of_chunk(world)
    want_logits, want_values = reference_out
    assert worst(logits, values, want_logits, want_values[:, HIST - T:HIST]) < AGREE


def test_the_learner_copies_agree_with_the_reference_and_the_rollout(world, reference_out):
    stage_logits, values = learner(world)
    want_logits, want_values = reference_out
    assert worst(stage_logits, values, want_logits, want_values[:, HIST - T:]) < AGREE
    roll_logits, roll_values = rollout_passes_of_chunk(world)
    assert worst(stage_logits, values[:, :T], roll_logits, roll_values) < 1e-5


def test_the_learner_log_probability_is_the_rollouts(world):
    """At the rollout's parameters the ratio PPO takes is 1: each head from
    the copy of the pass that committed it; and the path-wise KL of the
    learner's heads against the rollout's is 0, against other heads above it."""
    stage_logits, _ = learner(world)
    outs = world["outs"][HIST - T:]
    obs_t = {k: v[:, HIST - T:HIST] for k, v in world["obs"].items()}
    acts, stage = {h: stacked(outs, "actions", h) for h in D.HEADS}, stacked(outs, "act_stage")
    logp = D.staged_log_prob(stage_logits, obs_t, acts, stage)
    np.testing.assert_allclose(np.asarray(logp), np.asarray(stacked(outs, "logp")), atol=2e-5)
    rollout, _ = rollout_passes_of_chunk(world)
    assert float(jnp.abs(D.staged_kl(stage_logits, rollout, obs_t, acts, stage)).max()) < 1e-5
    other = jax.tree.map(lambda x: 1.5 * x, rollout)
    kl = np.asarray(D.staged_kl(stage_logits, other, obs_t, acts, stage))
    assert (kl > 0).all()


@pytest.mark.parametrize("fault", sdar_ref.FAULTS)
def test_a_wrong_reference_disagrees(world, reference_out, fault):
    """A comparison that passed with the observation seeing its own block, a
    noisy copy seeing the clean block, a causal block, no rotation, no head
    norm or a sigmoid router would pin nothing."""
    logits, values = rollout_passes_of_chunk(world)
    wrong_logits, wrong_values = reference(world, fault=fault)
    assert worst(logits, values, wrong_logits, wrong_values[:, HIST - T:HIST]) > DIFFER


def _reference_read_twice(world):
    """The reference as the comparison calls it: the history once with its own
    experts, then again with those experts handed back and the chunk read a
    second time beside it."""
    acts, stage = history_actions(world)
    model, actions_cfg = world["rc"]["model"], world["rc"]["actions"]
    first, clean = HIST - T, 6 * (HIST + 1)

    def both(p, o, d, a, s):
        once = sdar_ref.forward(p, o, d, a, s, model, actions_cfg, noisy_first=first, noisy_steps=T)
        routes = [r["chosen"] for r in once[2]]
        again = [jnp.concatenate([r[:, 6 * first:6 * (first + T)], r[:, clean:]], axis=1) for r in routes]
        twice = sdar_ref.forward(p, o, d, a, s, model, actions_cfg, noisy_first=first, noisy_steps=T,
                                 routes=routes, again_routes=again)
        return once[:2], twice[:2], twice[3]

    return quickly(both, world["params"], world["obs"], world["dones"], acts, stage)


def test_the_chunk_read_again_beside_the_history_is_the_chunk_read_once(world):
    """``sdar_ref.forward``'s second reading of the chunk (the comparison's one
    reference pass a lane for both the rollout and the learner): the first
    reading's outputs do not move, and under the same experts the second
    returns what the first returns for those steps."""
    once, twice, again = _reference_read_twice(world)
    assert worst(twice[0], twice[1], once[0], once[1]) < 1e-6
    assert worst(again[0], again[1], once[0], once[1][:, HIST - T:HIST]) < 1e-6


def test_the_reference_s_clean_blocks_scored_against_rows_up_to_their_step_alone(world, monkeypatch):
    """Query blocks of the clean rows are scored against the rows up to their
    last step (``sdar_ref.attention``'s ``clean_rows``): blocks of 7 rows, cut
    across steps, give what one block over the whole history gives."""
    whole = _reference_read_twice(world)
    monkeypatch.setattr(sdar_ref, "QUERY_BLOCK", 7)
    cut = _reference_read_twice(world)
    for w, c in zip(whole, cut):
        assert worst(c[0], c[1], w[0], w[1]) < 1e-6


def test_gradients_agree_with_the_reference(world):
    """The staged loss through the program's learner pass, against the
    reference's PPO loss over the whole history with what lies before the
    chunk as data (the auxiliary loss off: it is a mean over the program's
    own rows)."""
    policy, params, outs, carries = world["policy"], world["params"], world["outs"], world["carries"]
    cfg = dataclasses.replace(default_config().ppo, moe_aux_coef=0.0)
    rng = np.random.default_rng(3)
    acts = {h: stacked(outs[HIST - T:], "actions", h) for h in D.HEADS}
    stage = stacked(outs[HIST - T:], "act_stage")
    batch = {
        "obs": {k: v[:, HIST - T:] for k, v in world["obs"].items()},
        "actions": acts, "act_stage": stage,
        "behavior_logp": stacked(outs[HIST - T:], "logp") + jnp.asarray(0.1 * rng.standard_normal((B, T)), jnp.float32),
        "rewards": jnp.asarray(rng.standard_normal((B, T)), jnp.float32),
        "dones": jnp.asarray(world["dones"][:, HIST - T:HIST]),
        "valid": jnp.ones((B, T), jnp.float32),
        "carry0": policy.chunk_start_carry(carries[HIST - T], carries[HIST]),
    }
    got = quickly(jax.grad(lambda p: ppo_loss(policy, p, batch, cfg)[0]), params)
    hist_acts, hist_stage = history_actions(world)
    ref_batch = {**batch, "actions": hist_acts, "act_stage": hist_stage}
    ppo = {k: getattr(cfg, k) for k in ("gamma", "gae_lambda", "clip_eps", "entropy_coef", "value_coef")}
    want = quickly(
        jax.grad(lambda p: sdar_ref.ppo_loss(p, world["obs"], world["dones"], ref_batch, world["rc"]["model"],
                                             world["rc"]["actions"], ppo)),
        params,
    )
    scale = max(float(jnp.abs(x).max()) for x in jax.tree.leaves(want))
    assert policy_ref.max_abs_diff(got, want) / scale < 1e-4
    assert float(jnp.abs(got["params"]["core"]["tokens"]).max()) > 0      # the table learns


def test_reference_imports_nothing_from_the_program():
    import pathlib

    text = pathlib.Path(sdar_ref.__file__).read_text()
    assert "import dotaclient_tpu" not in text and "from dotaclient_tpu" not in text


# -- the block, the order and the mask --------------------------------------------


def test_every_emitted_action_is_legal_and_none_exactly_where_the_type_leaves_a_slot_out(world):
    for t, out in enumerate(world["outs"]):
        obs = {k: np.asarray(v[:, t]) for k, v in world["obs"].items()}
        a = {h: np.asarray(x) for h, x in out["actions"].items()}
        stage = np.asarray(out["act_stage"])
        assert obs["mask_action_type"][np.arange(B), a["action_type"]].all()
        rel = np.asarray(D.relevant(jnp.asarray(a["action_type"])))
        np.testing.assert_array_equal(stage > 0, rel)
        assert (stage[:, 0] == 1).all() and ((stage[:, 1:] == 0) | (stage[:, 1:] >= 2)).all()
        for b in range(B):
            n = rel[b, 1:].sum()
            assert sorted(stage[b, 1:][rel[b, 1:]]) == [2, 3][:n]      # n // 2 a pass, the remainder first
            if a["action_type"][b] == D.A_ATTACK:
                assert obs["mask_target_unit"][b, a["target_unit"][b]] or not obs["mask_target_unit"][b].any()
            if a["action_type"][b] == D.A_CAST:
                assert obs["mask_cast_target"][b, a["target_unit"][b]] or not obs["mask_cast_target"][b].any()
                assert obs["mask_ability"][b, a["ability"][b]] or not obs["mask_ability"][b].any()


def test_the_order_is_a_function_of_the_key_alone(world):
    """Other parameters, same keys: wherever the type drawn is the same, so is
    every slot's pass (LLaDA's random remasking)."""
    other = jax.tree.map(lambda x: x * 1.5, world["params"])
    outs, _ = rollout(world["policy"], other, world["obs"], world["dones"])
    same = 0
    for a, b in zip(world["outs"], outs):
        typ = np.asarray(a["actions"]["action_type"]) == np.asarray(b["actions"]["action_type"])
        np.testing.assert_array_equal(np.asarray(a["act_stage"])[typ], np.asarray(b["act_stage"])[typ])
        same += typ.sum()
    assert same > 0
    t = jnp.asarray([3, 1, 3, 1])
    for seed in range(3):
        k = jax.random.PRNGKey(seed)
        np.testing.assert_array_equal(D.commit_stages(k, t, 3), D.commit_stages(k, t, 3))


def test_the_learner_layout_is_block_causal():
    """o_t blind to its own slots; a noisy copy blind to its step's clean
    slots and to every other copy; later steps see the whole committed
    block; nothing sees a noisy row but its own copy."""
    step, slot, copy, sees = sdar.learner_rows(T, 3)
    assert sees.shape == ((T + 1) * 6 + T * 15,) * 2
    for i in range(len(step)):
        for j in range(len(step)):
            ti, tj, ki, kj, ci, cj = step[i], step[j], slot[i], slot[j], copy[i], copy[j]
            if cj > 0:
                want = ci == cj and ti == tj
            elif tj < ti:
                want = True
            elif tj > ti:
                want = False
            else:
                want = kj == 0 or (ci == 0 and ki > 0)
            assert sees[i, j] == want, (i, j)


def test_a_block_is_seen_by_later_steps_and_not_by_its_own_observation(world):
    """Through the program: changing step t's committed block moves step t +
    1's outputs and leaves o_t's value and step t's noisy copies alone."""
    policy, outs, carries = world["policy"], world["outs"], world["carries"]
    lane = 3                                       # no episode end in the chunk
    t = 1
    carry0 = policy.chunk_start_carry(carries[HIST - T], carries[HIST])
    chunk = {k: v[:, HIST - T:] for k, v in world["obs"].items()}
    acts = {h: stacked(outs[HIST - T:], "actions", h) for h in D.HEADS}
    stage = stacked(outs[HIST - T:], "act_stage")
    d = jnp.zeros((B, T))
    run = jax.jit(lambda a, s: policy.apply(world["params"], chunk, carry0, d, a, s, method=sdar.sequence))
    base = run(acts, stage)
    # a different committed type at step t (and so other NONE slots)
    acts2 = {**acts, "action_type": acts["action_type"].at[lane, t].set((acts["action_type"][lane, t] + 1) % 4)}
    stage2 = stage.at[lane, t].set(D.commit_stages(jax.random.PRNGKey(9), acts2["action_type"][lane, t], 3))
    moved = run(acts2, stage2)
    dv = np.abs(np.asarray(moved[1] - base[1]))[lane]
    assert dv[t] == 0 and dv[t + 1] > 1e-6
    dl = np.abs(np.asarray(moved[0]["move_x"] - base[0]["move_x"]))[:, lane]        # [S, T, K]
    assert dl[0, t].max() == 0 and dl[:, t + 1].max() > 1e-6                          # copy 1 of t holds only [MASK]


def test_an_episode_start_inside_the_chunk_hides_the_old_episode(world):
    """Lane 0's episode ends at step 9, inside the learner's chunk: its copies
    and values after it are those of a history that STARTS at step 10 (the
    reference given nothing before), so nothing of the old episode is read,
    from the ring or from the chunk."""
    stage_logits, values = learner(world)
    acts, stage = history_actions(world)
    cut = 10
    fresh = quickly(
        lambda p, o, d, a, s: sdar_ref.forward(
            p, o, d, a, s, world["rc"]["model"], world["rc"]["actions"], noisy_first=0, noisy_steps=HIST - cut,
        )[:2],
        world["params"], {k: v[:1, cut:] for k, v in world["obs"].items()}, world["dones"][:1, cut:],
        {h: a[:1, cut:] for h, a in acts.items()}, stage[:1, cut:],
    )
    got = ({h: v[:, :1, cut - (HIST - T):] for h, v in stage_logits.items()}, values[:1, cut - (HIST - T):])
    assert worst(got[0], got[1], fresh[0], fresh[1]) < AGREE


def test_the_shared_pass_is_two_passes(world):
    """Both teams' rows through ONE decode (a ``LaneBlocks`` of their
    carries: each team's rings written where they lie) equal a decode a team."""
    policy, params = world["policy"], world["params"]
    o = {k: v[:, 0] for k, v in world["obs"].items()}
    c = policy.initial_state(B)
    k1, k2 = jax.random.split(jax.random.PRNGKey(5), GAMES), jax.random.split(jax.random.PRNGKey(6), GAMES)
    one = jax.jit(lambda p, o, c, k: policy.apply(p, o, c, k, method=sdar.decode))
    a1, c1 = one(params, o, c, k1)
    a2, c2 = one(params, o, c, k2)
    def both(p, o, c1, c2, ks):
        out, carries = policy.apply(
            p, jax.tree.map(lambda x: jnp.concatenate([x, x]), o), LaneBlocks((c1, c2)), ks, method=sdar.decode
        )
        assert isinstance(carries, LaneBlocks)
        return out, tuple(carries)

    ab, cb = jax.jit(both)(params, o, c, c, (k1, k2))
    for h in D.HEADS:
        np.testing.assert_array_equal(np.asarray(ab["actions"][h]), np.concatenate([a1["actions"][h], a2["actions"][h]]))
    np.testing.assert_allclose(np.asarray(ab["logp"]), np.concatenate([a1["logp"], a2["logp"]]), atol=1e-5)
    for got, want in zip(jax.tree.leaves(cb), jax.tree.leaves((c1, c2))):
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=1e-5)


def test_lane_groups_and_row_blocks_are_one_product(world, monkeypatch):
    """The learner's attention a lane, or a block of one lane's rows, at a
    time (what the published widths take) is the product of all at once."""
    want = learner(world)
    for budget in (20000, 3000):
        monkeypatch.setattr(sdar, "SCORE_BYTES", budget)
        got = learner(world)
        assert worst(got[0], got[1], want[0], want[1]) < 1e-5


# -- the router's shares -----------------------------------------------------------


def test_the_softmax_router_s_held_shares_sum_to_the_uncut_layer():
    """16 chips holding 8 experts each of 128 (8 a token, softmax,
    renormalised over the chosen): the program's layer at every chip's share
    adds up to the reference's whole layer."""
    E, k, held = 128, 8, 8
    model = tiny_model(moe_experts=E, experts_per_token=k, held_experts=held)
    whole = {**dataclasses.asdict(model), "held_experts": E}
    rng = np.random.default_rng(4)
    m = jnp.asarray(rng.standard_normal((2, 5, 32)), jnp.float32)
    full = afmoe.RoutedExperts(dataclasses.replace(model, held_experts=E))
    params = full.init(jax.random.PRNGKey(2), m)["params"]
    total = 0
    for offset in range(0, E, held):
        share = afmoe.RoutedExperts(dataclasses.replace(model, expert_offset=offset))
        p = {**params, **{w: params[w][offset:offset + held] for w in ("expert_gate", "expert_up", "expert_down")}}
        total = total + share.apply({"params": p}, m, mutable=["losses", "routing"])[0]
    with jax.default_matmul_precision("highest"):
        want, _ = sdar_ref.experts(params, m, whole)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)
    assert float(jnp.abs(want).max()) > 1e-3


def test_the_router_scores_by_sigmoid_unless_asked():
    model = tiny_model(route_score="sigmoid", core="afmoe", diffusion_steps=0)
    m = jnp.ones((1, 1, 32))
    layer = afmoe.RoutedExperts(model)
    params = layer.init(jax.random.PRNGKey(0), m)
    _, sown = layer.apply(params, m, mutable=["losses"])
    probs = sown["losses"]["moe_probs"][0]
    s = jax.nn.sigmoid(m[0] @ params["params"]["router"])
    np.testing.assert_allclose(np.asarray(probs[0]), np.asarray(s / s.sum(-1, keepdims=True)), rtol=1e-5)


def test_the_episode_has_to_fit_six_positions_a_step():
    model = tiny_model()
    sdar.require_episode_fits(model, 20, T)
    with pytest.raises(ValueError, match="six|positions a step"):
        sdar.require_episode_fits(model, 21, T)
    with pytest.raises(ValueError, match="core 'sdar'"):
        sdar.check_config(tiny_model(route_score="sigmoid"))


# -- the rollout step at the cell's widths, compiled for the chip (no chip needed) ------


def test_a_rollout_step_at_the_cells_widths_multiplies_every_ring_as_it_lies():
    """``decode`` at SDAR's published widths, one game (5 lanes), compiled
    for a described v5e: each pass's attention against the rings is the ring
    kernel (``ops/pallas/ring_attend.py``, chosen by
    ``sdar.pass_takes_kernel``), which reads each ring's blocks where they
    lie: no product against a whole ring is left under ``core_block_attend``,
    no ring is copied, transposed or widened to float32, and the four passes
    make one kernel call a layer, all KV heads' keys and values in it, named
    under ``core_block_attend``, but for the commit's last layer, whose
    output nothing reads: 15 calls, the form this test pins."""
    import os
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import cells, program
    from dotaclient_tpu.models.policy import dummy_obs_batch, make_policy

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cfg = program.build_run_config(cells.load_cell("sdar-30b-a3b-5v5-ep16.fused-selfplay-anycore"), seed=0, rehearsal=False)
    assert sdar.pass_takes_kernel(cfg.model, sdar.ROWS, "tpu")
    policy, lanes = make_policy(cfg.model, cfg.obs, cfg.actions), 5
    shapes = jax.eval_shape(lambda: (
        init_params(policy, jax.random.PRNGKey(0)), dummy_obs_batch(lanes, cfg.obs, cfg.actions),
        policy.initial_state(lanes), jax.random.split(jax.random.PRNGKey(0), 1),
    ))
    args = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), shapes)
    text = jax.jit(
        lambda p, o, c, k: policy.apply(p, o, c, k, method=sdar.decode), donate_argnums=(2,)
    ).lower(*args).compile().as_text()
    ring = rf"\[{lanes},{cfg.model.full_context},{cfg.model.head_dim}\]"
    assert not re.findall(rf"= bf16{ring}{{[^}}]*}} (copy|transpose)\(", text)
    assert not re.findall(rf"= f32{ring}", text)
    products = [l for l in text.splitlines() if re.search(r"= [^ ]+ convolution\(", l) and "core_block_attend" in l]
    # the einsums of ``afmoe._attend`` whose second operand is a ring, or any product with a ring-long axis
    assert not [l for l in products if "brkd" in l or f",{cfg.model.full_context}," in l]
    calls = [l for l in text.splitlines() if "custom-call(" in l and "ring_attend" in l]
    L = cfg.model.n_layers
    # four passes, a call each layer; the commit's last layer is keys and values alone (its output is dead)
    assert len(calls) == 4 * L - 1 == 15
    assert all("core_block_attend" in l for l in calls)

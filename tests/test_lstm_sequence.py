"""``models/lstm.py lstm_sequence`` against what it replaced (ISSUE 29).

The reference is built here, from the SAME parameter tree: ``nn.scan`` over
``nn.OptimizedLSTMCell`` with the reset applied before the step, as
``Policy.sequence`` ran the LSTM until PR 29. Autodiff of that scan is the
gradient the hand-written backward is held to.
"""

import collections
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.models.lstm import lstm_sequence
from dotaclient_tpu.models.policy import mask_carry

H = 8


class ScannedCell(nn.Module):
    """The learner's LSTM as it was: the cell under ``nn.scan``."""

    dtype: str

    @nn.compact
    def __call__(self, carry, x, resets):
        def scan_step(cell, c, inp):
            xt, reset_t = inp
            return cell(mask_carry(c, 1.0 - reset_t), xt)

        scan = nn.scan(
            scan_step, variable_broadcast="params",
            split_rngs={"params": False}, in_axes=1, out_axes=1,
        )
        cell = nn.OptimizedLSTMCell(
            H, dtype=jnp.dtype(self.dtype), param_dtype=jnp.float32, name="core"
        )
        return scan(cell, carry, (x, resets))


def _scalar(out, weights):
    (c, h), ys = out
    w_c, w_h, w_y = weights
    return (c * w_c).sum() + (h * w_h).sum() + (ys * w_y).sum(), out


@jax.jit
def _new(core_params, carry, x, resets, weights):
    def loss(core_params, carry, x):
        return _scalar(lstm_sequence(core_params, carry, x, resets, x.dtype), weights)

    return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(core_params, carry, x)


@jax.jit
def _old(core_params, carry, x, resets, weights):
    def loss(core_params, carry, x):
        out = ScannedCell(str(x.dtype)).apply(
            {"params": {"core": core_params}}, carry, x, resets
        )
        return _scalar(out, weights)

    return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(core_params, carry, x)


RESETS = {
    "absent": lambda r, B, T: np.zeros((B, T)),
    "random": lambda r, B, T: r.random((B, T)) < 0.4,
    "at_step_1": lambda r, B, T: np.arange(T)[None, :].repeat(B, 0) == min(1, T - 1),
    "at_last_step": lambda r, B, T: np.arange(T)[None, :].repeat(B, 0) == T - 1,
    "every_step": lambda r, B, T: np.ones((B, T)),
}


def _case(dtype, resets, carry0, T, B, seed=0):
    r = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    cell = nn.OptimizedLSTMCell(H, param_dtype=jnp.float32)
    zeros = jnp.zeros((1, H))
    core_params = cell.init(keys[0], (zeros, zeros), zeros)["params"]
    # the biases start at zero: move every leaf, so none is compared as 0
    core_params = jax.tree.map(
        lambda p: p + 0.3 * jnp.asarray(r.normal(size=p.shape), p.dtype), core_params
    )

    def normal(*shape):
        return jnp.asarray(r.normal(size=shape), jnp.float32)

    # a learner's carry0 is float32 whatever the compute type
    # (Policy.chunk_start_carry)
    carry = (normal(B, H), normal(B, H)) if carry0 == "nonzero" else (
        jnp.zeros((B, H)), jnp.zeros((B, H))
    )
    x = normal(B, T, H).astype(dtype)
    return (
        core_params, carry, x,
        jnp.asarray(RESETS[resets](r, B, T), dtype),
        (normal(B, H), normal(B, H), normal(B, T, H)),
    )


def _close(got, want, rtol, atol):
    flat_got, tree = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree == tree_want
    for a, b in zip(flat_got, flat_want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a, np.float64), np.asarray(b, np.float64), rtol=rtol, atol=atol
        )


@pytest.mark.parametrize("B", [1, 6])
@pytest.mark.parametrize("T", [1, 2, 17])
@pytest.mark.parametrize("carry0", ["zero", "nonzero"])
@pytest.mark.parametrize("resets", sorted(RESETS))
def test_float32_matches_the_scanned_cell(resets, carry0, T, B):
    """Loss, outputs, final carry, and the gradient with respect to every
    core parameter, ``x`` and ``carry0``."""
    args = _case(jnp.float32, resets, carry0, T, B)
    _close(_new(*args), _old(*args), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("resets", ["absent", "random", "every_step"])
def test_bfloat16_forward_is_the_cells_and_the_gradient_no_further_from_float32(resets):
    """In the stated compute type the forward is the cell's own expressions
    in the cell's own order (the tolerance is
    ``test_scan_equals_repeated_steps``'s), and the hand-written gradient,
    which rounds once where autodiff rounds every factor, stands no
    further from the float32 gradient than autodiff's does."""
    args = _case(jnp.bfloat16, resets, "nonzero", 17, 6)
    (new_loss, new_out), new_grads = _new(*args)
    (old_loss, old_out), old_grads = _old(*args)
    _close((new_loss, new_out), (old_loss, old_out), rtol=2e-5, atol=2e-5)

    exact = list(args)
    exact[2] = args[2].astype(jnp.float32)
    exact[3] = args[3].astype(jnp.float32)
    _, exact_grads = _old(*exact)

    def distance(grads):
        return [
            # a reset at every step leaves carry0 no gradient at all
            float(jnp.linalg.norm((g.astype(jnp.float32) - e).ravel()) / max(jnp.linalg.norm(e.ravel()), 1e-30))
            for g, e in zip(jax.tree.leaves(grads), jax.tree.leaves(exact_grads))
        ]

    for new, old in zip(distance(new_grads), distance(old_grads)):
        assert new < 0.05 and new < 1.5 * old + 1e-3, (new, old)


def test_not_differentiated_it_saves_nothing_and_agrees():
    """Called outside ``jax.grad`` (the advantage pass, an evaluation) the
    primal runs, which stacks the outputs and nothing for a backward."""
    core_params, carry, x, resets, weights = _case(jnp.float32, "random", "nonzero", 5, 3)
    fn = jax.jit(lambda *a: lstm_sequence(*a, jnp.float32))
    (_, want), _ = _old(core_params, carry, x, resets, weights)
    _close(fn(core_params, carry, x, resets), want, rtol=1e-6, atol=1e-6)
    stacked = re.findall(r"f32\[5,3,(\d+)\]", fn.lower(core_params, carry, x, resets).compile().as_text())
    assert set(stacked) <= {str(H), str(4 * H)}                  # ys, and X @ Wx


# -- the parameter tree --------------------------------------------------------


def test_lstm_policy_parameter_tree_is_pinned():
    """Checkpoints, published weight frames, ``serve.policy_path
    .slice_train_params`` and ``benchmark/reference/policy_ref.py`` read
    these names: a renamed or reshaped leaf fails here, before a checkpoint
    does."""
    from dotaclient_tpu.config import default_config
    from dotaclient_tpu.models import init_params, make_policy

    cfg = default_config()
    policy = make_policy(cfg.model, cfg.obs, cfg.actions)
    params = jax.eval_shape(lambda: init_params(policy, jax.random.PRNGKey(0)))
    hidden = cfg.model.hidden_dim
    core = {
        "/".join(k.key for k in path): (leaf.shape, str(leaf.dtype))
        for path, leaf in jax.tree_util.tree_flatten_with_path(params["params"]["core"])[0]
    }
    kernel, bias = ((hidden, hidden), "float32"), ((hidden,), "float32")
    assert core == {
        "ii/kernel": kernel, "if/kernel": kernel, "ig/kernel": kernel, "io/kernel": kernel,
        "hi/kernel": kernel, "hf/kernel": kernel, "hg/kernel": kernel, "ho/kernel": kernel,
        "hi/bias": bias, "hf/bias": bias, "hg/bias": bias, "ho/bias": bias,
    }
    assert sorted(params["params"]) == [
        "core", "globals_proj", "head_ability", "head_action_type", "head_move_x",
        "head_move_y", "head_value", "hero_embed", "target_query", "trunk_proj",
        "unit_encoder",
    ]


# -- the guard: no weight-gradient accumulator in the backward loop ------------


def _computations(hlo):
    """Optimised HLO text -> {computation name: its lines}."""
    out, name = collections.defaultdict(list), None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", line)
        if m:
            name = m.group(1)
        elif name is not None:
            out[name].append(line)
    return out


def test_policy_gradient_holds_no_weight_gradient_inside_a_loop():
    """The wide LSTM lost 14% of its step to two ``[H, 4H]`` float32
    accumulators that the backward loop read and wrote every step (ISSUE 29;
    PERF.md section 6). In the optimised HLO of the gradient of
    ``Policy.sequence``, nothing a ``while`` runs may add or multiply into a
    kernel's shape, and exactly two ``[H, 4H]`` float32 products stand
    outside the loops."""
    import dataclasses

    from dotaclient_tpu.config import default_config
    from dotaclient_tpu.models import init_params, make_policy
    from dotaclient_tpu.models.policy import dummy_obs_batch

    cfg = default_config()
    hidden = 24                                  # no other width of the policy
    model = dataclasses.replace(cfg.model, hidden_dim=hidden, dtype="float32")
    policy = make_policy(model, cfg.obs, cfg.actions)
    B, T = 3, 5
    params = jax.eval_shape(lambda: init_params(policy, jax.random.PRNGKey(0)))
    obs = jax.eval_shape(lambda: dummy_obs_batch(B, cfg.obs, cfg.actions, time=T))
    carry = jax.eval_shape(lambda: policy.initial_state(B))
    dones = jax.ShapeDtypeStruct((B, T), jnp.float32)

    def loss(params, obs, carry, dones):
        logits, value, (c, h) = policy.apply(params, obs, carry, dones, method="sequence")
        return value.sum() + c.sum() + h.sum() + sum(v.sum() for v in logits.values())

    hlo = jax.jit(jax.grad(loss)).lower(params, obs, carry, dones).compile().as_text()
    comps = _computations(hlo)

    # every computation a while loop runs, directly or through calls and fusions
    called = {
        name: set(re.findall(r"(?:calls|to_apply|body|condition|branch_computations)=\{?(%[\w.\-]+)", "\n".join(lines)))
        for name, lines in comps.items()
    }
    in_loop = set()
    frontier = [
        c for lines in comps.values() for line in lines if " while(" in line
        for c in re.findall(r"(?:body|condition)=(%[\w.\-]+)", line)
    ]
    assert frontier                                   # the scans ARE loops
    while frontier:
        name = frontier.pop()
        if name not in in_loop:
            in_loop.add(name)
            frontier.extend(called.get(name, ()))

    kernel_shapes = (f"f32[{hidden},{4 * hidden}]", f"f32[{hidden},{hidden}]")
    row = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = (\S+?)\{?[\d,]*\}? (add|dot|convolution)\(")
    inside, products_outside = [], []
    for name, lines in comps.items():
        for line in lines:
            m = row.match(line)
            if m and m.group(1).startswith(kernel_shapes):
                if name in in_loop:
                    inside.append(line.strip()[:160])
                elif m.group(2) != "add" and m.group(1).startswith(kernel_shapes[0]):
                    products_outside.append(line.strip()[:160])
    assert not inside, inside
    assert len(products_outside) == 2, products_outside

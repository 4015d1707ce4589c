"""PPO learner: loss, optimizer, and the single pjit'd train step.

Parity target is the reference learner loop — collect N rollouts, re-run the
policy over sequences teacher-forced from stored initial LSTM states, GAE,
clipped-surrogate PPO loss with entropy bonus and value loss, grad-clip, Adam
(SURVEY.md §3.2, BASELINE.json:5; reconstructed — the reference checkout was
an empty mount).

TPU-first shape (SURVEY.md §7 step 4): the whole loop body — sequence
forward, loss, gradient, ``psum`` over the data axis, Adam update — is
ONE jitted function with donated train-state buffers, compiled once against a
``(data, model)`` mesh. The gradient all-reduce is emitted by XLA from the
sharding annotations (batch sharded over ``data``, params replicated); there
is no hand-written collective.

Advantage estimation is its own pipeline stage (the one-pass advantage
plane, ``train/advantage.py``): a batch arriving with precomputed
``advantages``/``returns`` leaves trains all ``epochs_per_batch ×
minibatches`` updates on them over a T-step forward. Batches without the
leaves (fused mode, vtrace, ``one_pass_advantage=false``, and every direct
caller of :func:`make_train_step`) keep the in-step estimator over the
full T+1 chunk — the historical behavior, bitwise.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dotaclient_tpu.config import (
    ADV_NORM_MODES, ADVANTAGE_MODES, PPOConfig, RunConfig,
)
from dotaclient_tpu.models import distributions as D
from dotaclient_tpu.models.policy import Policy
from dotaclient_tpu.train.gae import gae, vtrace


@flax.struct.dataclass
class TrainState:
    """Learner state. ``version`` is the model-version counter the actors tag
    rollouts with (staleness filtering, SURVEY.md §3.4)."""

    step: jnp.ndarray          # i32 []
    version: jnp.ndarray       # i32 []
    params: Any
    opt_state: Any


# A training batch of rollout chunks. Time layout (SURVEY.md §5.7):
#   obs arrays            [B, T+1, ...]  — includes the bootstrap observation
#   actions/logp/...      [B, T]
#   carry0                ([B, H], [B, H]) — stored rollout-initial LSTM state
#   valid                 [B, T] — False on padding after an episode's end
Batch = Dict[str, Any]


def make_optimizer(cfg: PPOConfig) -> optax.GradientTransformation:
    if cfg.kl_target > 0:
        # inject_hyperparams materializes the learning rate as an array in
        # the optimizer state so the KL-adaptive controller in _train_step
        # can rescale it in-graph (state layout gains one scalar leaf).
        return optax.chain(
            optax.clip_by_global_norm(cfg.max_grad_norm),
            optax.inject_hyperparams(optax.adam)(
                learning_rate=cfg.learning_rate
            ),
        )
    return optax.chain(
        optax.clip_by_global_norm(cfg.max_grad_norm),
        optax.adam(cfg.learning_rate),
    )


def init_train_state(policy_params: Any, cfg: PPOConfig) -> TrainState:
    """Build a fresh TrainState.

    The params are copied: the train step donates the whole state (its
    buffers die on every step), while callers — the actor's inference path in
    particular — keep using their own copy.
    """
    opt = make_optimizer(cfg)
    params = jax.tree.map(jnp.copy, policy_params)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        version=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=opt.init(params),
    )


def _moe_aux_loss(losses_col: Any, valid: jnp.ndarray) -> jnp.ndarray:
    """Switch load-balancing loss from the routing stats an MoE core sows.

    Leaves arrive as ``[T+1, B, E]`` (the learner scan stacks one ``[B, E]``
    sow per step on axis 0); padded steps and the trailing bootstrap slot
    are masked out of the means exactly like every other loss term. Zero
    for dense cores (empty collection).
    """
    if not losses_col:
        return jnp.zeros(())
    B, T = valid.shape
    keystr = jax.tree_util.keystr
    flat, _ = jax.tree_util.tree_flatten_with_path(losses_col)
    probs = {keystr(p[:-2]): l for p, l in flat if "moe_probs" in keystr(p)}
    fracs = {keystr(p[:-2]): l for p, l in flat if "moe_frac" in keystr(p)}
    w = valid.T[..., None]                       # [T, B, 1]
    denom = jnp.maximum(valid.sum(), 1.0)
    aux = jnp.zeros(())
    for key, pr in probs.items():
        fr = fracs[key]
        E = pr.shape[-1]
        mean_p = (pr[:T] * w).sum((0, 1)) / denom   # [E] masked importance
        mean_f = (fr[:T] * w).sum((0, 1)) / denom   # [E] masked load
        aux = aux + E * jnp.sum(mean_p * mean_f)
    return aux


def _moe_counters(losses_col: Any) -> Dict[str, jnp.ndarray]:
    """What a core that holds part of a routed layer counted in this pass
    (``models/afmoe.py``), summed over its expert layers: token-expert pairs
    computed here, pairs its weights left out (always 0), the busiest held
    expert's load over the mean, and where the layer's grouped products are
    the kernel's (``afmoe.grouped_takes_kernel``) the share of the buffers'
    rows inside the row tiles it visits, the layers' mean. Empty for every
    other core."""
    flat, _ = jax.tree_util.tree_flatten_with_path(losses_col)

    def leaves(name):
        return [l for p, l in flat if name in jax.tree_util.keystr(p)]

    loads = leaves("moe_load")
    if not loads:
        return {}
    load = jnp.stack(loads)                                   # [layers, held]
    return {
        "moe_local_assignments": sum(leaves("moe_local")),
        "moe_dropped_assignments": sum(leaves("moe_dropped")),
        "moe_max_over_mean_load": (
            load.max(axis=1) / jnp.maximum(load.mean(axis=1), 1e-9)
        ).max(),
        **({"moe_kernel_rows_share": sum(shares) / len(shares)} if (shares := leaves("moe_kernel_rows_share")) else {}),
    }


def _select_bias_errors(losses_col: Any) -> Dict[Tuple[str, ...], jnp.ndarray]:
    """{module path: [E] tokens an expert took minus the mean} for every
    routed layer that balances its load by a selection bias
    (``models/afmoe.py``). Empty for every other core."""
    flat, _ = jax.tree_util.tree_flatten_with_path(losses_col)
    return {
        tuple(k.key for k in p[:-2]): l
        for p, l in flat if getattr(p[-2], "key", None) == "select_bias_err"
    }


def _balance_select_bias(params: Any, errors: Dict[Tuple[str, ...], jnp.ndarray], rate: float) -> Any:
    """The load-balancing update of a routed layer's selection bias, which no
    gradient reaches: an expert that took more tokens than the mean in this
    batch is chosen a little less readily in the next, ``bias += rate *
    centred(sign(mean - tokens))``."""

    def at(tree, path, fn):
        if not path:
            return fn(tree)
        return {**tree, path[0]: at(tree[path[0]], path[1:], fn)}

    for path, err in errors.items():
        step = -jnp.sign(err)
        step = rate * (step - step.mean())
        params = at(
            params, ("params",) + path + ("select_bias",),
            lambda b: b + step.astype(b.dtype),
        )
    return params


def ppo_loss(
    policy: Policy,
    params: Any,
    batch: Batch,
    cfg: PPOConfig,
    step: Any = None,
    anchor_params: Any = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Clipped-surrogate PPO loss over a batch of rollout chunks.

    ``step`` (the optimizer-step counter) enables the critic-only warmup
    window: while ``step < cfg.value_warmup_steps`` the policy surrogate,
    entropy bonus, and MoE aux terms are switched off so only the value
    loss trains (see PPOConfig.value_warmup_steps; the matching gradient
    mask in ``_train_step`` keeps the rest of the network bitwise frozen).

    ``anchor_params`` (with ``cfg.anchor_kl_coef > 0``) adds the anchor-KL
    regularizer: one extra frozen-policy forward over the batch, exact
    conditional KL(π_θ ‖ π_anchor) per frame (PPOConfig.anchor_kl_coef).

    Precomputed ``advantages``/``returns`` (``train/advantage.py``) skip the estimator and the bootstrap slot. A looped or a staged core's loss is its own (below).
    """
    if policy.model.diffusion_steps:
        return staged_loss(policy, params, batch, cfg, step, anchor_params)
    if policy.model.loop_steps > 1:
        return exit_weighted_loss(policy, params, batch, cfg, step, anchor_params)
    obs = batch["obs"]
    T = batch["rewards"].shape[1]
    valid = batch["valid"].astype(jnp.float32)
    n_valid = jnp.maximum(valid.sum(), 1.0)
    precomputed = "advantages" in batch
    if precomputed:
        obs = {k: v[:, :T] for k, v in obs.items()}

    (logits, values, _), mutated = policy.apply(
        params, obs, batch["carry0"], batch["dones"], method="sequence",
        mutable=["losses"],
    )
    moe_aux = _moe_aux_loss(mutated.get("losses", {}), valid)
    moe_counters = {**_moe_counters(mutated.get("losses", {})), **_kda_gauges(mutated.get("losses", {}), valid), **_shortconv_gauges(mutated.get("losses", {}), valid)}
    bias_errors = _select_bias_errors(mutated.get("losses", {}))
    # Trailing slot is the bootstrap step: value used, policy outputs unused.
    logits_t = {k: v[:, :T] for k, v in logits.items()}
    obs_t = {k: v[:, :T] for k, v in obs.items()}
    values_t = values[:, :T]

    logp = D.log_prob(logits_t, obs_t, batch["actions"])

    if precomputed:
        # Consume-time advantages (train/advantage.py): upcast from the
        # bf16 staging dtype; both are constants to the optimizer (the
        # pass ran on stop-gradient values), exactly like the in-step
        # estimator's outputs below.
        adv = batch["advantages"].astype(jnp.float32)
        returns = batch["returns"].astype(jnp.float32)
    elif cfg.advantage == "gae":
        with jax.named_scope("update_gae"):
            adv, returns = gae(
                batch["rewards"],
                jax.lax.stop_gradient(values),
                batch["dones"],
                cfg.gamma,
                cfg.gae_lambda,
            )
    elif cfg.advantage == "vtrace":
        # Importance weights are constants to the optimizer (stop-grad on
        # the target logp): the surrogate's gradient flows through the
        # ratio below, not through the advantage estimate.
        with jax.named_scope("update_gae"):
            adv, returns = vtrace(
                batch["rewards"],
                jax.lax.stop_gradient(values),
                batch["dones"],
                batch["behavior_logp"],
                jax.lax.stop_gradient(logp),
                cfg.gamma,
                cfg.vtrace_rho_clip,
                cfg.vtrace_c_clip,
            )
    else:
        raise ValueError(
            f"unknown advantage {cfg.advantage!r} (one of {ADVANTAGE_MODES})"
        )
    # Advantage normalization over the (valid) batch. Always centered;
    # rescaled per cfg.adv_norm — the floor keeps near-zero advantage
    # batches from being blown up to unit scale (cfg comment, BASELINE.md
    # 5v5 fine-tune measurement).
    adv_mean = (adv * valid).sum() / n_valid
    adv = adv - adv_mean
    if cfg.adv_norm == "batch":
        adv_var = (jnp.square(adv) * valid).sum() / n_valid
        adv_std = jnp.sqrt(adv_var + 1e-8)
        adv = adv / jnp.maximum(adv_std, cfg.adv_norm_floor)
    elif cfg.adv_norm not in ADV_NORM_MODES:
        raise ValueError(
            f"unknown adv_norm {cfg.adv_norm!r} (one of {ADV_NORM_MODES})"
        )
    ratio = jnp.exp(logp - batch["behavior_logp"])
    clipped = jnp.clip(ratio, 1.0 - cfg.clip_eps, 1.0 + cfg.clip_eps)
    policy_loss = -(jnp.minimum(ratio * adv, clipped * adv) * valid).sum() / n_valid

    value_loss = 0.5 * (jnp.square(values_t - returns) * valid).sum() / n_valid
    ent = (D.entropy(logits_t, obs_t) * valid).sum() / n_valid

    anchor_kl = jnp.zeros(())
    if cfg.anchor_kl_coef > 0 and anchor_params is not None:
        # Frozen-anchor forward (no gradient: anchor_params is not the
        # differentiated argument). Same states, same masks — the exact
        # conditional KL is well-defined per frame.
        def _anchor_kl(_):
            (anchor_logits, _, _), _ = policy.apply(
                anchor_params, obs, batch["carry0"], batch["dones"],
                method="sequence", mutable=["losses"],
            )
            a_t = {k: v[:, :T] for k, v in anchor_logits.items()}
            return (D.kl(logits_t, a_t, obs_t) * valid).sum() / n_valid

        if cfg.value_warmup_steps and step is not None:
            # The warmup window zeroes the whole policy group, so the
            # anchor forward would be dead compute (~a full extra policy
            # pass per step) — skip it until the policy trains.
            anchor_kl = jax.lax.cond(
                step >= cfg.value_warmup_steps,
                _anchor_kl,
                lambda _: jnp.zeros(()),
                None,
            )
        else:
            anchor_kl = _anchor_kl(None)

    if cfg.value_warmup_steps and step is not None:
        policy_on = (step >= cfg.value_warmup_steps).astype(jnp.float32)
    else:
        policy_on = 1.0
    loss = (
        policy_on
        * (
            policy_loss
            - cfg.entropy_coef * ent
            + cfg.moe_aux_coef * moe_aux
            + cfg.anchor_kl_coef * anchor_kl
        )
        + cfg.value_coef * value_loss
    )
    metrics = {
        "loss": loss,
        "moe_aux": moe_aux,
        **moe_counters,
        **(
            {"anchor_kl": anchor_kl}
            if cfg.anchor_kl_coef > 0 and anchor_params is not None
            else {}
        ),
        # Stashed for _train_step's post-update KL measurement (popped
        # there — never reaches the logger). Only when the KL-adaptive lr
        # is on, to avoid carrying a [B, T] array through aux otherwise.
        **({"_logp": logp} if cfg.kl_target > 0 else {}),
        # Likewise popped by _train_step: the load errors its balancing
        # update of the selection biases reads (a routed afmoe layer only).
        **({"_select_bias_err": bias_errors} if bias_errors else {}),
        "policy_loss": policy_loss,
        "value_loss": value_loss,
        "entropy": ent,
        "approx_kl": ((batch["behavior_logp"] - logp) * valid).sum() / n_valid,
        "clip_frac": (
            (jnp.abs(ratio - 1.0) > cfg.clip_eps).astype(jnp.float32) * valid
        ).sum() / n_valid,
        "value_mean": (values_t * valid).sum() / n_valid,
        "reward_mean": (batch["rewards"] * valid).sum() / n_valid,
    }
    return loss, metrics


def _train_step(
    policy: Policy,
    cfg: PPOConfig,
    state: TrainState,
    batch: Batch,
    anchor_params: Any = None,
    probe: bool = True,
) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
    grad_fn = jax.value_and_grad(
        lambda p: ppo_loss(
            policy, p, batch, cfg, step=state.step,
            anchor_params=anchor_params,
        ),
        has_aux=True,
    )
    # The step's stages carry a scope each (metadata only; a backward
    # operation keeps the scope its forward was written in), so a profiler
    # trace times loss, optimizer and probes by name.
    with jax.named_scope("update_loss"):
        (_, metrics), grads = grad_fn(state.params)
    bias_errors = metrics.pop("_select_bias_err", {})
    with jax.named_scope("update_optimizer"):
        if cfg.value_warmup_steps:
            # Critic-only warmup: zero every gradient outside the value head so
            # the behavior policy is EXACTLY frozen (value-loss gradients still
            # flow through the shared trunk otherwise). The head itself keeps
            # its full gradient and recalibrates to this config's returns.
            policy_on = (state.step >= cfg.value_warmup_steps).astype(jnp.float32)

            def _mask(path, g):
                in_value_head = any(
                    getattr(k, "key", None) == "head_value" for k in path
                )
                # astype(g.dtype): a float32 scalar would silently promote
                # bfloat16 grads (and with them Adam's moments) to float32,
                # retracing the donated step and skewing checkpoint templates.
                return g if in_value_head else g * policy_on.astype(g.dtype)

            grads = jax.tree_util.tree_map_with_path(_mask, grads)
        opt = make_optimizer(cfg)
        opt_state_in = state.opt_state
        if cfg.value_warmup_steps:
            # At the warmup boundary, re-init the optimizer state: the frozen
            # params sat out the warmup with zero moments while Adam's shared
            # step count advanced, so their bias correction is desynchronized —
            # the first post-warmup update would be ~(1-b1)/sqrt(1-b2) ≈ 3×
            # oversized across every policy param at once, exactly the
            # destroy-the-transferred-policy kick this feature exists to
            # prevent. A fresh opt_state makes the first live step behave like
            # a fresh optimizer's first step. (The value head's moments reset
            # too — harmless, it has converged toward this config's returns by
            # then.) jnp.where keeps the opt_state structure unchanged, so
            # checkpoints stay layout-compatible.
            at_boundary = state.step == cfg.value_warmup_steps
            fresh = opt.init(state.params)
            opt_state_in = jax.tree.map(
                lambda f, cur: jnp.where(at_boundary, f, cur),
                fresh, opt_state_in,
            )
        updates, opt_state = opt.update(grads, opt_state_in, state.params)
        params = optax.apply_updates(state.params, updates)
        if cfg.select_bias_rate > 0:
            params = _balance_select_bias(params, bias_errors, cfg.select_bias_rate)
    with jax.named_scope("update_probe"):
        if cfg.kl_target > 0:
            # KL-adaptive lr: measure the POST-update policy shift on this
            # batch's taken actions (k3 estimator, E_old[r − 1 − log r] ≥ 0)
            # and rescale the lr carried in the optimizer state for the NEXT
            # step. All in-graph: no host sync, fused-mode compatible.
            logp_pre = metrics.pop("_logp")

            def _measure_kl(operand):
                params_new, lp_pre = operand
                T = batch["rewards"].shape[1]
                obs = batch["obs"]
                if "advantages" in batch:
                    # one-pass batches train on a T-step forward (the
                    # bootstrap slot only fed the estimator) — measure the
                    # post-update KL over the same window
                    obs = {k: v[:, :T] for k, v in obs.items()}
                (logits_post, _, _), _ = policy.apply(
                    params_new, obs, batch["carry0"], batch["dones"],
                    method="sequence", mutable=["losses"],
                )
                logits_t = {k: v[:, :T] for k, v in logits_post.items()}
                obs_t = {k: v[:, :T] for k, v in obs.items()}
                logp_post = D.log_prob(logits_t, obs_t, batch["actions"])
                d = logp_post - lp_pre
                valid = batch["valid"].astype(jnp.float32)
                n_valid = jnp.maximum(valid.sum(), 1.0)
                return (((jnp.exp(d) - 1.0) - d) * valid).sum() / n_valid

            if cfg.value_warmup_steps:
                # The frozen-policy window has post-KL ≡ 0 by construction;
                # skip the measurement forward (~a full policy pass) there.
                post_kl = jax.lax.cond(
                    state.step >= cfg.value_warmup_steps,
                    _measure_kl,
                    lambda _: jnp.zeros(()),
                    (params, logp_pre),
                )
            else:
                post_kl = _measure_kl((params, logp_pre))

            inj = opt_state[1]
            lr = inj.hyperparams["learning_rate"]
            t = cfg.kl_target
            factor = jnp.where(
                post_kl > 2.0 * t,
                cfg.kl_lr_down,
                jnp.where(post_kl < 0.5 * t, cfg.kl_lr_up, 1.0),
            )
            if cfg.value_warmup_steps:
                # The frozen-policy window measures KL ≡ 0; don't let the
                # controller ratchet the lr up against a flat signal (the
                # boundary reset would restore it anyway, but the value head
                # trains through the warmup at whatever lr this leaves).
                factor = jnp.where(
                    state.step < cfg.value_warmup_steps, 1.0, factor
                )
            new_lr = jnp.clip(
                lr * factor,
                cfg.learning_rate * cfg.kl_lr_min_scale,
                cfg.learning_rate * cfg.kl_lr_max_scale,
            )
            hp = dict(inj.hyperparams)
            hp["learning_rate"] = new_lr
            opt_state = (opt_state[0], inj._replace(hyperparams=hp))
            metrics["post_kl"] = post_kl
            metrics["lr"] = lr
        metrics["grad_norm"] = optax.global_norm(grads)
        if probe:
            # Training-health probe (ISSUE 6, train/health.py): one scalar AND
            # over the two values every step already computes. loss covers
            # NaN/Inf anywhere in the forward/returns path (non-finite params
            # from a previous step included); the PRE-clip gradient global
            # norm covers a backward pass that NaN'd after a finite loss.
            # Scanned multi-update programs AND-fold this flag
            # (fold_scan_metrics), so one poisoned update taints the whole
            # program's verdict.
            metrics["health_ok"] = (
                jnp.isfinite(metrics["loss"]) & jnp.isfinite(metrics["grad_norm"])
            ).astype(jnp.float32)
    new_state = dataclasses.replace(
        state,
        step=state.step + 1,
        version=state.version + 1,
        params=params,
        opt_state=opt_state,
    )
    return new_state, metrics


def fold_scan_metrics(metric_seq: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Reduce a ``lax.scan``'s stacked per-update metrics to one report:
    the LAST update's values (the state reflects it — the historical
    contract of every scanned train path), except ``health_ok``, which
    AND-folds (min) across the scan — a single poisoned update inside a
    fused multi-update program must taint the program's verdict even when
    later updates happen to report finite values again."""
    out = jax.tree.map(lambda m: m[-1], metric_seq)
    if "health_ok" in metric_seq:
        out["health_ok"] = metric_seq["health_ok"].min()
    return out


def train_state_sharding(policy: Policy, config: RunConfig, mesh: Mesh):
    """The TrainState sharding tree (TP partition rules applied to params
    and the Adam mirrors, scalars replicated) — the single source of truth
    shared by ``make_train_step`` and the fused step."""
    from dotaclient_tpu.parallel.sharding import state_shardings

    return state_shardings(train_state_shape(policy, config), mesh, config.mesh)


def train_state_shape(policy: Policy, config: RunConfig) -> TrainState:
    """The TrainState's shapes and dtypes, with nothing allocated."""
    from dotaclient_tpu.models import init_params

    return jax.eval_shape(
        lambda: init_train_state(
            init_params(policy, jax.random.PRNGKey(0)), config.ppo
        )
    )


def make_train_step(
    policy: Policy,
    config: RunConfig,
    mesh: Mesh,
    debug_checkify: bool = False,
    anchor_params: Any = None,
):
    """Compile the train step against ``mesh``.

    Batch arrays are sharded over the data axis (leading/batch dim); the
    train state follows the tensor-parallel rules of
    ``parallel.sharding.state_shardings`` — replicated when
    ``model_parallel == 1``, last-axis-sharded kernels over the model axis
    otherwise. XLA inserts the gradient all-reduce (data axis) and the TP
    collectives (model axis) over ICI. The train state is donated —
    params/opt-state update in place in HBM.

    ``anchor_params`` (required iff ``ppo.anchor_kl_coef > 0``) is
    closure-captured: the anchor is fixed for the compiled step's lifetime,
    so it rides along as a jit constant (replicated; at policy scale the
    memory is noise).
    """
    if (config.ppo.anchor_kl_coef > 0) != (anchor_params is not None):
        raise ValueError(
            "anchor_params must be passed exactly when ppo.anchor_kl_coef > 0"
        )
    from dotaclient_tpu.parallel.mesh import data_sharding as _data_sharding

    # (dcn, data) when the mesh is multi-slice, else just (data,): the
    # gradient all-reduce then lowers hierarchically — ICI inside each
    # slice, one slice-level all-reduce over DCN
    data_sharding = _data_sharding(mesh, config.mesh)
    repl = NamedSharding(mesh, P())
    # a bare sharding broadcasts over the whole batch pytree, so the
    # compiled contract is structure-agnostic: a batch may carry the
    # optional precomputed-advantage leaves (train/advantage.py) or not
    batch_shardings = data_sharding
    state_sharding = train_state_sharding(policy, config, mesh)
    metrics_repl = repl
    if debug_checkify:
        # Debug numerics mode (SURVEY.md §5.2): checkify float checks guard
        # every op and RAISE on the first NaN/Inf instead of letting it
        # propagate into the params. No donation, no sharding constraints —
        # this is the hunt-the-NaN path, not the production path.
        from jax.experimental import checkify

        inner = checkify.checkify(
            lambda state, batch: _train_step(
                policy, config.ppo, state, batch, anchor_params=anchor_params,
                probe=config.health.enabled,
            ),
            errors=checkify.float_checks,
        )
        jitted = jax.jit(inner)

        def checked_step(state, batch):
            err, out = jitted(state, batch)
            checkify.check_error(err)
            return out

        return checked_step
    step_fn = jax.jit(
        lambda state, batch: _train_step(
            policy, config.ppo, state, batch, anchor_params=anchor_params,
            probe=config.health.enabled,
        ),
        in_shardings=(state_sharding, batch_shardings),
        out_shardings=(state_sharding, metrics_repl),
        donate_argnums=(0,),
    )
    return step_fn


def make_epoch_step(
    policy: Policy,
    config: RunConfig,
    mesh: Mesh,
    anchor_params: Any = None,
):
    """Compile the fused epoch step: ``(state, batch, perms) → (state',
    last_metrics)`` — all ``epochs_per_batch × minibatches`` optimizer
    updates over one consumed batch inside ONE donated XLA program.

    The staged loop in ``Learner._optimize`` pays a jitted-gather dispatch
    plus a train-step dispatch per minibatch (2·E·M host→device round trips
    per batch); here a ``lax.scan`` walks minibatch slices of the epoch
    permutations in-program, so one batch costs one dispatch regardless of
    the epoch/minibatch configuration (the OPPO/Podracer observation —
    PAPERS.md — that PPO's inner loop belongs inside the compiled program).

    ``perms`` is ``[E, B] int32`` — one shuffled row order per epoch, drawn
    host-side from the SAME seeded stream as the staged fallback. Taking
    the permutations as an input (rather than folding a PRNG key in-graph)
    is deliberate: on identical seeds the two paths run the same updates
    on the same data (agreement to float-ulp XLA-fusion rounding — tested)
    and the checkpointed ``mb_draws`` counter reconstructs the stream
    exactly on resume, for either path. The array is E·B int32 — its
    transfer rides the dispatch and is noise next to the batch itself.
    With ``minibatches == 1`` the scan trains on the whole batch per epoch
    and ``perms`` is ignored (matching the staged path, which never
    shuffles an unsplit batch).

    The train state is donated and updates in place in HBM; each minibatch
    slice is re-constrained to the batch sharding so the update runs
    exactly as it would on a staged minibatch. Metrics are the last
    update's (device-resident), like the staged loop's.
    """
    if (config.ppo.anchor_kl_coef > 0) != (anchor_params is not None):
        raise ValueError(
            "anchor_params must be passed exactly when ppo.anchor_kl_coef > 0"
        )
    from dotaclient_tpu.parallel.mesh import data_sharding as _data_sharding

    cfg = config.ppo
    E = cfg.epochs_per_batch
    M = max(1, cfg.minibatches)
    B = cfg.batch_rollouts
    if B % M:
        raise ValueError(
            f"batch_rollouts {B} not divisible by minibatches {M}"
        )
    mb = B // M
    ds = _data_sharding(mesh, config.mesh)
    repl = NamedSharding(mesh, P())
    # bare sharding = structure-agnostic contract (see make_train_step):
    # one-pass batches add advantages/returns leaves, sliced per
    # minibatch by the same in-program jnp.take as every other leaf
    batch_shardings = ds
    state_sharding = train_state_sharding(policy, config, mesh)

    def epoch_step(state, batch, perms):
        def body(st, idx_mb):
            if M == 1:
                sub = batch
            else:
                sub = jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(
                        jnp.take(x, idx_mb, axis=0), ds
                    ),
                    batch,
                )
            return _train_step(
                policy, cfg, st, sub, anchor_params=anchor_params,
                probe=config.health.enabled,
            )

        # [E, B] → [E·M, mb]: scan one optimizer step per slice; epoch e's
        # minibatches are rows e·M..(e+1)·M of the reshape, exactly the
        # slices the staged loop gathers. health_ok AND-folds across the
        # scan (fold_scan_metrics) so one poisoned update taints the batch.
        idx = perms.reshape(E * M, mb)
        state, metric_seq = jax.lax.scan(body, state, idx)
        return state, fold_scan_metrics(metric_seq)

    return jax.jit(
        epoch_step,
        in_shardings=(state_sharding, batch_shardings, repl),
        out_shardings=(state_sharding, repl),
        donate_argnums=(0,),
    )


def example_batch(config: RunConfig, batch: int, as_struct: bool = False) -> Batch:
    """A correctly-shaped zero batch (compile warm-up, tests, AOT)."""
    from dotaclient_tpu.models.policy import dummy_obs_batch, make_policy

    T = config.ppo.rollout_len
    obs = dummy_obs_batch(batch, config.obs, config.actions, time=T + 1)
    # carry0 layout comes from the policy's own core (LSTM (h, c) or a
    # transformer KV cache); the wire/buffer representation is always f32
    carry0 = jax.tree.map(
        lambda t: jnp.zeros(t.shape, jnp.float32),
        make_policy(config.model, config.obs, config.actions).initial_state(batch),
    )
    out: Batch = {
        "obs": obs,
        "actions": {
            h: jnp.zeros((batch, T), jnp.int32)
            for h in config.actions.head_sizes
        },
        "behavior_logp": jnp.zeros((batch, T), jnp.float32),
        "rewards": jnp.zeros((batch, T), jnp.float32),
        "dones": jnp.zeros((batch, T), jnp.float32),
        "valid": jnp.ones((batch, T), jnp.float32),
        "carry0": carry0, **_staged_batch(config, batch),
    }
    if as_struct:
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), out
        )
    return out


def exit_weighted_loss(
    policy: Policy,
    params: Any,
    batch: Batch,
    cfg: PPOConfig,
    step: Any = None,
    anchor_params: Any = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``ppo_loss`` for a core that runs its stack R times and may leave
    after any of them (``models/looplm.py``): ``Policy.sequence`` hands back
    logits and values of EVERY loop step (a leading axis R) and the core
    sows its exit gates' logits. Per valid frame, with ``l_r`` every term
    of ``ppo_loss`` that reads the policy's outputs (clipped surrogate,
    entropy bonus, value loss, with their coefficients) evaluated from loop
    step r's heads against the SAME behaviour log-probability, advantage and
    return, and ``p`` the exit distribution (``looplm.exit_distribution``):

      L = mean over valid frames of [ sum_r p_r l_r - exit_entropy_coef H(p) ]

    Advantages and returns come from the LAST loop step's values, as the
    rollout's action, log-probability and value do. A policy whose core is
    not looped is one exit of mass 1, and the loss is ``ppo_loss``'s bit for
    bit (a test holds that). The anchor KL, the KL-adaptive learning rate
    and precomputed advantages read one set of head outputs a frame and are
    refused here; a looped core trains in fused mode only, which uses none.
    """
    from dotaclient_tpu.models.looplm import exit_distribution

    if cfg.anchor_kl_coef > 0 or cfg.kl_target > 0 or "advantages" in batch:
        raise ValueError(
            f"core {policy.model.core!r} hands the loss {policy.model.loop_steps} sets of "
            "head outputs a frame: ppo.anchor_kl_coef, ppo.kl_target and precomputed "
            "advantages (the one-pass advantage plane) are not written for that"
        )
    obs = batch["obs"]
    T = batch["rewards"].shape[1]
    valid = batch["valid"].astype(jnp.float32)
    n_valid = jnp.maximum(valid.sum(), 1.0)

    (logits, values, _), mutated = policy.apply(
        params, obs, batch["carry0"], batch["dones"], method="sequence",
        mutable=["losses"],
    )
    sown = mutated.get("losses", {}).get("core", {})
    if values.ndim == 2:
        logits, values = jax.tree.map(lambda x: x[None], (logits, values))
    R = values.shape[0]
    # Trailing slot is the bootstrap step: value used, policy outputs unused.
    logits_t = {k: v[:, :, :T] for k, v in logits.items()}
    obs_t = {k: v[:, :T] for k, v in obs.items()}
    values_t = values[:, :, :T]
    logp = jax.vmap(lambda lg: D.log_prob(lg, obs_t, batch["actions"]))(logits_t)   # [R, B, T]

    with jax.named_scope("update_gae"):
        last_values = jax.lax.stop_gradient(values[-1])
        if cfg.advantage == "gae":
            adv, returns = gae(
                batch["rewards"], last_values, batch["dones"], cfg.gamma, cfg.gae_lambda,
            )
        elif cfg.advantage == "vtrace":
            adv, returns = vtrace(
                batch["rewards"], last_values, batch["dones"], batch["behavior_logp"],
                jax.lax.stop_gradient(logp[-1]), cfg.gamma, cfg.vtrace_rho_clip, cfg.vtrace_c_clip,
            )
        else:
            raise ValueError(f"unknown advantage {cfg.advantage!r} (one of {ADVANTAGE_MODES})")
    adv_mean = (adv * valid).sum() / n_valid
    adv = adv - adv_mean
    if cfg.adv_norm == "batch":
        adv_var = (jnp.square(adv) * valid).sum() / n_valid
        adv_std = jnp.sqrt(adv_var + 1e-8)
        adv = adv / jnp.maximum(adv_std, cfg.adv_norm_floor)
    elif cfg.adv_norm not in ADV_NORM_MODES:
        raise ValueError(f"unknown adv_norm {cfg.adv_norm!r} (one of {ADV_NORM_MODES})")
    ratio = jnp.exp(logp - batch["behavior_logp"])
    clipped = jnp.clip(ratio, 1.0 - cfg.clip_eps, 1.0 + cfg.clip_eps)
    surrogate = jnp.minimum(ratio * adv, clipped * adv)                 # [R, B, T]
    value_sq = jnp.square(values_t - returns)
    ent = jax.vmap(lambda lg: D.entropy(lg, obs_t))(logits_t)

    with jax.named_scope("update_exit_mix"):
        if "exit_logits" in sown:
            p = jnp.moveaxis(exit_distribution(sown["exit_logits"][0][:, :T]), -1, 0)
        else:
            p = jnp.ones((1,) + valid.shape, jnp.float32)

        def mix(x):
            """``[R, B, T]`` -> the exit-weighted sum over loop steps, masked: ``[B, T]``."""
            return (p * x).sum(axis=0) * valid

        exit_ent = mix(-jnp.log(jnp.maximum(p, 1e-30))).sum() / n_valid
        # each term in ``ppo_loss``'s own form, so that one exit of mass 1 is that loss
        policy_loss = -mix(surrogate).sum() / n_valid
        value_loss = 0.5 * mix(value_sq).sum() / n_valid
        ent = mix(ent).sum() / n_valid

    if cfg.value_warmup_steps and step is not None:
        policy_on = (step >= cfg.value_warmup_steps).astype(jnp.float32)
    else:
        policy_on = 1.0
    loss = (
        policy_on
        * (policy_loss - cfg.entropy_coef * ent - cfg.exit_entropy_coef * exit_ent)
        + cfg.value_coef * value_loss
    )
    metrics = {
        "loss": loss,
        "moe_aux": jnp.zeros(()),
        "policy_loss": policy_loss,
        "value_loss": value_loss,
        "entropy": ent,
        # against the behaviour policy, which acted from the last loop step
        "approx_kl": ((batch["behavior_logp"] - logp[-1]) * valid).sum() / n_valid,
        "clip_frac": (
            (jnp.abs(ratio[-1] - 1.0) > cfg.clip_eps).astype(jnp.float32) * valid
        ).sum() / n_valid,
        "value_mean": (values_t[-1] * valid).sum() / n_valid,
        "reward_mean": (batch["rewards"] * valid).sum() / n_valid,
    }
    if "exit_logits" in sown:
        # what the learner folds into the registry at the log cadence
        # (``looplm/*``): the mean exit mass of each loop step, the mean loop
        # step of exit counted from 1, the exit entropy, and the passes the
        # core made over its stack in this forward (R where none is skipped)
        mass = (p * valid).sum(axis=(1, 2)) / n_valid
        metrics.update({f"looplm_exit_mass_{r}": mass[r] for r in range(R)})
        metrics["looplm_expected_exit_step"] = (mass * jnp.arange(1, R + 1)).sum()
        metrics["looplm_exit_entropy"] = exit_ent
        metrics["looplm_loop_passes"] = sown["loop_passes"][0]
    return loss, metrics


def _kda_gauges(losses_col: Any, valid: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """What a core with delta-rule linear-attention layers sowed in this pass
    (``models/kimilinear.py``), over its KDA layers: the mean decay (what a
    step keeps of a state's channel), the mean write strength, the end
    states' root mean square, and the lane-layer reads of a void state (a
    step at position 0 of its episode, the bootstrap step left to the next
    chunk). Empty for every other core."""
    leaves = _sown(losses_col)
    if not leaves("kda_decay"):
        return {}
    T = valid.shape[1]
    return {
        "kda_decay_mean": jnp.stack(leaves("kda_decay")).mean(),
        "kda_beta_mean": jnp.stack(leaves("kda_beta")).mean(),
        "kda_state_rms": jnp.sqrt(jnp.stack(leaves("kda_state_sq")).mean()),
        "kda_void_reads": (leaves("kda_void_reads")[0][:, :T] * valid).sum(),
    }


def _shortconv_gauges(losses_col: Any, valid: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """What a core with gated short-convolution layers sowed in this pass
    (``models/lfm2moe.py``), over its convolution layers: the root mean
    square of the rows the histories carry, the mean magnitude of the output
    gate, and the lane-layer reads of a void history (a step at position 0
    of its episode, the bootstrap step left to the next chunk). Empty for
    every other core."""
    leaves = _sown(losses_col)
    if not leaves("shortconv_gate"):
        return {}
    T = valid.shape[1]
    return {
        "shortconv_history_rms": jnp.sqrt(jnp.stack(leaves("shortconv_history_sq")).mean()),
        "shortconv_gate_mean": jnp.stack(leaves("shortconv_gate")).mean(),
        "shortconv_void_reads": (leaves("shortconv_void_reads")[0][:, :T] * valid).sum(),
    }


def _sown(losses_col: Any):
    """``name -> the leaves sown under it`` in a ``losses`` collection, in layer order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(losses_col)
    return lambda name: [leaf for path, leaf in flat if getattr(path[-2], "key", None) == name]


# -- the loss of a core that decodes an action over several passes (models/sdar.py) ---


def _staged_batch(config: RunConfig, batch: int) -> Batch:
    """What a chunk of a staged core carries besides: the pass that
    committed each slot (``act_stage [B, T, 5]`` int8). Empty otherwise."""
    if not config.model.diffusion_steps:
        return {}
    return {"act_stage": jnp.ones((batch, config.ppo.rollout_len, len(D.HEADS)), jnp.int8)}


def staged_loss(
    policy: Policy,
    params: Any,
    batch: Batch,
    cfg: PPOConfig,
    step: Any = None,
    anchor_params: Any = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``ppo_loss`` for a core that decodes an action as a block over S
    passes (``models/sdar.py``): the learner's pass is the core's own
    ``sequence`` (SDAR's layout: clean rows and S noisy copies of every
    step's block), which hands back each head's logits from every copy; the
    log-probability, the entropy bonus and the ratio read each head from the
    copy of the pass that committed it (``batch["act_stage"]``:
    ``distributions.staged_log_prob``, ``staged_entropy``), so at the
    rollout's parameters the ratio is 1. Every other term is ``ppo_loss``'s.
    The auxiliary load-balancing loss is over every row of the pass. The
    anchor KL, the KL-adaptive learning rate and precomputed advantages are
    refused: the core trains in fused mode only, which uses none."""
    from dotaclient_tpu.models.policy import resident_core

    if cfg.anchor_kl_coef > 0 or cfg.kl_target > 0 or "advantages" in batch:
        raise ValueError(
            f"core {policy.model.core!r} decodes an action over {policy.model.diffusion_steps} passes: "
            "ppo.anchor_kl_coef, ppo.kl_target and precomputed advantages (the one-pass advantage "
            "plane) are not written for that"
        )
    obs, actions, act_stage = batch["obs"], batch["actions"], batch["act_stage"]
    T = batch["rewards"].shape[1]
    valid = batch["valid"].astype(jnp.float32)
    n_valid = jnp.maximum(valid.sum(), 1.0)
    (stage_logits, values), mutated = policy.apply(
        params, obs, batch["carry0"], batch["dones"], actions, act_stage,
        method=resident_core(policy.model).sequence, mutable=["losses"],
    )
    losses = mutated.get("losses", {})
    probs = _sown(losses)("moe_probs")          # [rows, B, E] a routed layer: every row of the pass weighs alike
    moe_aux = _moe_aux_loss(losses, jnp.ones((valid.shape[0], probs[0].shape[0] if probs else 1), jnp.float32))
    bias_errors = _select_bias_errors(losses)
    obs_t = {k: v[:, :T] for k, v in obs.items()}
    values_t = values[:, :T]
    logp = D.staged_log_prob(stage_logits, obs_t, actions, act_stage)
    with jax.named_scope("update_gae"):
        if cfg.advantage == "gae":
            adv, returns = gae(
                batch["rewards"], jax.lax.stop_gradient(values), batch["dones"], cfg.gamma, cfg.gae_lambda,
            )
        elif cfg.advantage == "vtrace":
            adv, returns = vtrace(
                batch["rewards"], jax.lax.stop_gradient(values), batch["dones"], batch["behavior_logp"],
                jax.lax.stop_gradient(logp), cfg.gamma, cfg.vtrace_rho_clip, cfg.vtrace_c_clip,
            )
        else:
            raise ValueError(f"unknown advantage {cfg.advantage!r} (one of {ADVANTAGE_MODES})")
    adv = adv - (adv * valid).sum() / n_valid
    if cfg.adv_norm == "batch":
        adv_std = jnp.sqrt((jnp.square(adv) * valid).sum() / n_valid + 1e-8)
        adv = adv / jnp.maximum(adv_std, cfg.adv_norm_floor)
    elif cfg.adv_norm not in ADV_NORM_MODES:
        raise ValueError(f"unknown adv_norm {cfg.adv_norm!r} (one of {ADV_NORM_MODES})")
    ratio = jnp.exp(logp - batch["behavior_logp"])
    clipped = jnp.clip(ratio, 1.0 - cfg.clip_eps, 1.0 + cfg.clip_eps)
    policy_loss = -(jnp.minimum(ratio * adv, clipped * adv) * valid).sum() / n_valid
    value_loss = 0.5 * (jnp.square(values_t - returns) * valid).sum() / n_valid
    ent = (D.staged_entropy(stage_logits, obs_t, actions, act_stage) * valid).sum() / n_valid
    if cfg.value_warmup_steps and step is not None:
        policy_on = (step >= cfg.value_warmup_steps).astype(jnp.float32)
    else:
        policy_on = 1.0
    loss = (
        policy_on * (policy_loss - cfg.entropy_coef * ent + cfg.moe_aux_coef * moe_aux)
        + cfg.value_coef * value_loss
    )
    metrics = {
        "loss": loss,
        "moe_aux": moe_aux,
        **_moe_counters(losses),
        **_diffusion_gauges(stage_logits, obs_t, actions, act_stage, valid),
        **_ring_read_share(policy.model, batch["carry0"]),
        **({"_select_bias_err": bias_errors} if bias_errors else {}),
        "policy_loss": policy_loss,
        "value_loss": value_loss,
        "entropy": ent,
        "approx_kl": ((batch["behavior_logp"] - logp) * valid).sum() / n_valid,
        "clip_frac": (
            (jnp.abs(ratio - 1.0) > cfg.clip_eps).astype(jnp.float32) * valid
        ).sum() / n_valid,
        "value_mean": (values_t * valid).sum() / n_valid,
        "reward_mean": (batch["rewards"] * valid).sum() / n_valid,
    }
    return loss, metrics


def _ring_read_share(model, carry0) -> Dict[str, jnp.ndarray]:
    """Where a rollout pass of this configuration attends through the ring
    kernel on a TPU (``sdar.pass_takes_kernel``): the share of the chunk's
    rings that the first pass at its start read, ring rows of the blocks the
    kernel visits over lanes x ``full_context`` (``sdar/ring_rows_read_share``
    at the log cadence). Empty where the widths leave the kernel out."""
    from dotaclient_tpu.models import sdar

    if not sdar.pass_takes_kernel(model, sdar.ROWS, "tpu"):
        return {}
    return {"sdar_ring_rows_read_share": sdar.ring_rows_read_share(model, carry0["pos"], carry0["cursor"])}


def _diffusion_gauges(stage_logits, obs_t, actions, act_stage, valid) -> Dict[str, jnp.ndarray]:
    """What the learner folds into ``diffusion/*`` at the log cadence: the
    chunk's tokens committed (slots a pass filled) and NONE slots over valid
    steps, and for each pass s the mean entropy of the heads it committed
    (each from its own copy's logits, the target under its type's mask)."""
    stage = act_stage.astype(jnp.int32)
    w = valid[..., None]
    lp = D._head_logps(D.pick_stage_logits(stage_logits, act_stage), obs_t)
    is_cast = (actions["action_type"] == D.A_CAST)[..., None]
    lp["target_unit"] = jnp.where(is_cast, lp["target_cast"], lp["target_attack"])
    entropy = jnp.stack([-jnp.sum(jnp.exp(lp[h]) * lp[h], axis=-1) for h in D.HEADS], axis=-1)   # [B, T, 5]
    out = {
        "diffusion_tokens_committed": ((stage > 0) * w).sum(),
        "diffusion_none_slots": ((stage == 0) * w).sum(),
    }
    for s in range(1, stage_logits[D.HEADS[0]].shape[0] + 1):
        here = (stage == s) * w
        out[f"diffusion_stage_entropy_{s}"] = (here * entropy).sum() / jnp.maximum(here.sum(), 1.0)
    return out

"""Flax policy: unit encoders → masked reduce → recurrent core → action heads.

Parity target is the reference ``Policy(nn.Module)``: per-unit-type input
encoders, concat (+ hero embedding for multi-hero pools), an LSTM(128) core,
and heads for action-type / move-x / move-y / target-unit (dot-product
attention over unit embeddings) / ability, with invalid-action masking before
softmax (SURVEY.md §3.3, BASELINE.json:5,7,9,10; reconstructed — the reference
checkout was an empty mount).

TPU-first design decisions (SURVEY.md §7 step 3):

* One module serves both the actor's batch-step mode (``method="step"``) and
  the learner's teacher-forced sequence mode (``method="sequence"``), sharing
  parameters — sequence mode runs the LSTM through ``models/lstm.py`` (one
  ``lax.scan``, the weight gradient one product AFTER the backward loop) and
  the windowed transformer under ``nn.scan``, and hands a core whose carry stays on the chip
  (``models/afmoe.py``, ``looplm.py``, ``kimilinear.py``, ``lfm2moe.py``) the chunk in ONE pass (its step: T = 1).
* The carry, its reset and the chunk-start carry a learner is handed are
  the core's own: ``initial_state``, ``reset_carry`` and
  ``chunk_start_carry``. The LSTM's ``(h, c)`` and the transformer's window
  are rows that a reset zeroes (``mask_carry``) and a chunk start copies in
  float32; where ``ModelConfig.carry_stays_on_chip`` the carry is megabytes a lane
  (attention caches: 22 MiB at Trinity-Mini's widths, 403 MB at Ouro's; matrix states and a
  latent ring: 12 MB at Kimi-Linear's; one ring and rows: 6 MiB at LFM2's), which a reset never touches (a position counter returns
  to 0) and a chunk start never widens: the core's own module answers (``resident_core``).
* ``step`` takes one lane set's carry or several sets' (``LaneBlocks``: a rollout
  whose two teams play the same parameters): one pass, every weight read once, each
  set's caches and states touched where they lie (``models/lanes.py``).
* The trunk and heads are written shape-polymorphically (Dense/einsum on the
  last axis) so the same code handles ``[B, ...]`` and ``[B, T, ...]``.
* Compute dtype is configurable bfloat16 with float32 params; logits are cast
  to float32 before masking/softmax for numerical stability.
* Fixed shapes everywhere: the unit axis is always ``ObsSpec.max_units``;
  validity arrives as masks (never shape changes ⇒ never recompiles).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dotaclient_tpu.config import RESIDENT_CORES, ROUTED_FFN_CORES, ActionSpec, ModelConfig, ObsSpec
from dotaclient_tpu.models.lanes import join_lanes, split_lanes

# Recurrent carry: (h, c) for the LSTM core; (valid, KV caches) for the
# transformer core; {"pos", "cursor", "kv"} for a ring-cache core. Always a
# pytree whose leaves have leading batch axis — reset it with
# Policy.reset_carry, never by unpacking it.
Carry = Any


# Collections a core sows per call; never part of the parameters.
TRANSIENT_COLLECTIONS = ("losses", "routing")


def mask_carry(carry: Carry, keep: jnp.ndarray) -> Carry:
    """Multiply every carry leaf by ``keep`` ([B], 0 ⇒ reset that row) —
    core-agnostic episode-boundary reset."""
    def m(t):
        k = keep.reshape((-1,) + (1,) * (t.ndim - 1)).astype(t.dtype)
        return t * k
    return jax.tree.map(m, carry)


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


class UnitEncoder(nn.Module):
    """Per-unit MLP shared across unit slots (the per-unit-type information is
    one-hot in the feature vector, so a single shared encoder replaces the
    reference's per-type encoder stack without losing expressivity)."""

    config: ModelConfig

    @nn.compact
    def __call__(self, units: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        x = nn.Dense(cfg.unit_embed_dim, dtype=dtype, param_dtype=pdtype)(units)
        x = nn.relu(x)
        x = nn.Dense(cfg.unit_embed_dim, dtype=dtype, param_dtype=pdtype)(x)
        return nn.relu(x)


class Policy(nn.Module):
    """Actor-critic policy with a recurrent core.

    ``value_head=False`` is the inference-only path (ISSUE 11, the serving
    plane): the SAME trunk/core/head modules — so logits are bit-identical
    by construction — but no value head is ever created, and the param tree
    is exactly the training tree minus ``head_value``
    (``serve.policy_path.slice_train_params`` produces it from a training
    checkpoint or a published weights frame). The step/sequence signatures
    are unchanged; the value output is a constant-zero placeholder so every
    actor-side consumer (which discards it) works with either variant."""

    model: ModelConfig
    obs_spec: ObsSpec
    action_spec: ActionSpec
    value_head: bool = True

    def setup(self):
        cfg = self.model
        self.unit_encoder = UnitEncoder(cfg)
        self.hero_embed = nn.Embed(
            cfg.n_hero_ids, cfg.hero_embed_dim, param_dtype=_dtype(cfg.param_dtype)
        )
        self.globals_proj = nn.Dense(
            cfg.unit_embed_dim, dtype=_dtype(cfg.dtype),
            param_dtype=_dtype(cfg.param_dtype),
        )
        self.trunk_proj = nn.Dense(
            cfg.hidden_dim, dtype=_dtype(cfg.dtype),
            param_dtype=_dtype(cfg.param_dtype),
        )
        if cfg.core == "lstm":
            self.core = nn.OptimizedLSTMCell(
                cfg.hidden_dim, dtype=_dtype(cfg.dtype),
                param_dtype=_dtype(cfg.param_dtype),
            )
        elif cfg.core == "transformer":
            from dotaclient_tpu.models.transformer import WindowedTransformerCore

            self.core = WindowedTransformerCore(cfg)
        elif cfg.carry_stays_on_chip:
            # the constructor's lookup: the core's own module, found by the
            # core's name (config.RESIDENT_CORES)
            self.core = resident_core(cfg).Core(cfg)
        else:
            raise ValueError(f"unknown core {cfg.core!r}")
        hs = self.action_spec.head_sizes
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        self.head_action_type = nn.Dense(hs["action_type"], dtype=dtype, param_dtype=pdtype)
        self.head_move_x = nn.Dense(hs["move_x"], dtype=dtype, param_dtype=pdtype)
        self.head_move_y = nn.Dense(hs["move_y"], dtype=dtype, param_dtype=pdtype)
        self.head_ability = nn.Dense(hs["ability"], dtype=dtype, param_dtype=pdtype)
        # Target-unit head: dot-product attention query over unit embeddings.
        self.target_query = nn.Dense(self.model.unit_embed_dim, dtype=dtype, param_dtype=pdtype)
        if self.value_head:
            self.head_value = nn.Dense(1, dtype=jnp.float32, param_dtype=pdtype)

    # -- shared trunk ------------------------------------------------------

    def _trunk(self, obs: Mapping[str, jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """obs arrays with any leading axes → (core input [..., H],
        unit embeddings [..., U, E] for the target-attention head)."""
        dtype = _dtype(self.model.dtype)
        units = obs["units"].astype(dtype)
        unit_mask = obs["unit_mask"][..., None].astype(dtype)   # [..., U, 1]
        unit_emb = self.unit_encoder(units) * unit_mask          # zero padding
        # Masked mean + max pool over the unit axis (padding never leaks).
        n_units = unit_mask.sum(axis=-2)                         # [..., 1]
        mean_pool = unit_emb.sum(axis=-2) / jnp.maximum(n_units, 1.0)
        max_pool = jnp.where(
            unit_mask > 0, unit_emb, jnp.asarray(-1e9, dtype)
        ).max(axis=-2)
        max_pool = jnp.where(n_units > 0, max_pool, 0.0)  # all-padding row
        g = nn.relu(self.globals_proj(obs["globals"].astype(dtype)))
        hero = self.hero_embed(obs["hero_id"].astype(jnp.int32)).astype(dtype)
        x = jnp.concatenate([mean_pool, max_pool, g, hero], axis=-1)
        x = nn.relu(self.trunk_proj(x))
        return x, unit_emb

    def _heads(
        self, y: jnp.ndarray, unit_emb: jnp.ndarray
    ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
        """Core output [..., H] → per-head float32 logits + value [...]."""
        q = self.target_query(y)                                  # [..., E]
        scale = jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
        target_logits = (
            jnp.einsum("...e,...ue->...u", q, unit_emb).astype(jnp.float32) / scale
        )
        logits = {
            "action_type": self.head_action_type(y).astype(jnp.float32),
            "move_x": self.head_move_x(y).astype(jnp.float32),
            "move_y": self.head_move_y(y).astype(jnp.float32),
            "target_unit": target_logits,
            "ability": self.head_ability(y).astype(jnp.float32),
        }
        if self.value_head:
            value = self.head_value(y.astype(jnp.float32))[..., 0]
        else:
            value = jnp.zeros(y.shape[:-1], jnp.float32)
        return logits, value

    # -- public modes ------------------------------------------------------

    def initial_state(self, batch_size: int) -> Carry:
        if self.model.carry_stays_on_chip:
            # counters, rings and states as the core's module lays them out

            return resident_core(self.model).initial_state(self.model, batch_size)
        if self.model.core == "transformer":
            from dotaclient_tpu.models.transformer import (
                transformer_initial_state,
            )

            return transformer_initial_state(self.model, batch_size)
        shape = (batch_size, self.model.hidden_dim)
        dtype = _dtype(self.model.dtype)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    def reset_carry(self, carry: Carry, keep: jnp.ndarray) -> Carry:
        """Episode-boundary reset of the rows where ``keep`` ([B]) is 0, as
        the core defines it: the LSTM and the windowed transformer zero the
        row (``mask_carry``); a core whose carry stays on the chip returns
        the row's position to 0 and touches no cache and no state."""
        if self.model.carry_stays_on_chip:
            # (a layer that keeps a state reads it as void at position 0)

            return resident_core(self.model).reset(carry, keep)
        return mask_carry(carry, keep)

    def chunk_start_carry(self, start: Carry, end: Carry) -> Carry:
        """What a learner is handed as a chunk's ``carry0``, given the carry
        before the chunk's first step and after its last: the start in
        float32 for the LSTM and the windowed transformer, and for a core
        whose carry stays on the chip its own ``chunk_start_view``: the start's
        counters (and states) beside the END's rings, no cache copied or widened."""
        if self.model.carry_stays_on_chip:
            # the start's buffers and the end's, never a new one

            return resident_core(self.model).chunk_start_view(start, end)
        return jax.tree.map(lambda t: t.astype(jnp.float32), start)

    def step(
        self, obs: Mapping[str, jnp.ndarray], carry: Carry
    ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray, Carry]:
        """Single batched step (actor path): obs arrays ``[B, ...]``; ``carry``
        is B lanes' carry, or a ``LaneBlocks`` of the carries of lane sets that
        make up the B rows in order (every weight is then read once for all)."""
        with jax.named_scope("policy_trunk"):
            x, unit_emb = self._trunk(obs)
        with jax.named_scope("policy_core"):
            sets, carry = carry, join_lanes(carry, self.model.carry_stays_on_chip)
            if self.model.carry_stays_on_chip:
                # the chunk function at T = 1 (of a looped core's [R, B, 1, H] the last loop step)
                carry, y = self.core(carry, x[:, None])
                y = y[:, 0] if y.ndim == 3 else y[-1, :, 0]
            else:
                carry, y = self.core(carry, x)
            carry = split_lanes(carry, sets)
        with jax.named_scope("policy_heads"):
            logits, value = self._heads(y, unit_emb)
        return logits, value, carry

    def sequence(
        self,
        obs: Mapping[str, jnp.ndarray],
        carry: Carry,
        dones: jnp.ndarray | None = None,
    ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray, Carry]:
        """Teacher-forced sequence mode (learner path): obs arrays
        ``[B, T, ...]``, ``carry`` is the stored rollout-initial LSTM state.
        Truncated-BPTT parity with the reference (SURVEY.md §5.7).

        ``dones`` (``[B, T']`` with T' ≤ T, f32/bool, episode ended AT step t)
        enables chunks that *span* episodes (the on-device rollout regime):
        the recurrent state is zeroed before step t+1 whenever step t ended an
        episode — exactly matching the actor-side reset — so step t+1 starts
        its new episode from a fresh carry. Without ``dones`` the behavior is
        unchanged (scalar-pool chunks never span episodes)."""
        with jax.named_scope("policy_trunk"):
            x, unit_emb = self._trunk(obs)                        # [B, T, H]
        T = x.shape[1]
        if dones is None:
            resets = jnp.zeros((x.shape[0], T), x.dtype)
        else:
            # step 0 is reset by carry0 itself; step t>0 resets if t-1 done
            resets = jnp.concatenate(
                [
                    jnp.zeros((x.shape[0], 1), x.dtype),
                    dones.astype(x.dtype)[:, : T - 1],
                ],
                axis=1,
            )

        if self.model.carry_stays_on_chip:
            # one pass over the chunk: T queries against the carried keys and the chunk's own, the resets a segment
            # mask. A looped core hands back every loop step, so logits and value lead with [R] (its gates are sown)
            with jax.named_scope("policy_core"):
                carry, ys = self.core(carry, x, resets)
            with jax.named_scope("policy_heads"):
                logits, value = self._heads(ys, unit_emb)
            return logits, value, carry

        if self.model.core == "lstm":
            # the cell's mathematics (step mode calls the cell), backward by hand
            from dotaclient_tpu.models.lstm import lstm_sequence

            with jax.named_scope("policy_core_scan"):
                core_params, dtype = self.core.variables["params"], _dtype(self.model.dtype)
                carry, ys = lstm_sequence(core_params, carry, x, resets, dtype)
        else:
            def scan_step(cell, c, inp):
                xt, reset_t = inp
                return cell(mask_carry(c, 1.0 - reset_t), xt)

            # what a core sows (the MoE load-balancing loss, a scalar per
            # step) stacks along a leading time axis
            scan = nn.scan(scan_step, variable_broadcast="params", variable_axes={"losses": 0},
                           split_rngs={"params": False}, in_axes=1, out_axes=1)
            with jax.named_scope("policy_core_scan"):
                carry, ys = scan(self.core, carry, (x, resets))   # ys [B, T, H]
        with jax.named_scope("policy_heads"):
            logits, value = self._heads(ys, unit_emb)
        return logits, value, carry

    def __call__(self, obs: Mapping[str, jnp.ndarray], carry: Carry):
        """Default = step mode (used for parameter init)."""
        return self.step(obs, carry)


def require_carry_stays(model: ModelConfig, where: str) -> None:
    """Raise where ``where`` would ship a carry with every chunk or reply and
    the core's carry is caches or matrix states: the LSTM's and the windowed
    transformer's rows travel, such a core's megabytes a lane stay on
    the chip (the fused trainer, the serve engine's resident carries)."""
    if model.carry_stays_on_chip:
        carry_bytes_per_lane = resident_core(model).carry_bytes_per_lane

        raise ValueError(
            f"core {model.core!r} carries {carry_bytes_per_lane(model):,} bytes of "
            f"caches and states a lane: it trains in actor mode 'fused' and "
            f"serves from the engine's resident carries, not in {where}, "
            f"which would copy that carry with every chunk or reply"
        )


def require_episode_fits(model: ModelConfig, episode_steps: int, rollout_len: int) -> None:
    """Raise where the core's carry cannot hold an episode of
    ``episode_steps`` observations rolled out ``rollout_len`` at a time (the
    full-attention rings of a core whose carry stays on the chip)."""
    if model.carry_stays_on_chip:
        # the core's own module knows which of its leaves an episode must fit

        resident_core(model).require_episode_fits(model, episode_steps, rollout_len)


def make_policy(model: ModelConfig, obs_spec: ObsSpec, action_spec: ActionSpec) -> Policy:
    # Only some cores route an MoE FFN, and the rest refuse ``moe_experts``:
    # ``require_routed_ffn``, at the end of this file, which the serving
    # plane's policy (``serve/policy_path.py``) asks too. (This function keeps
    # its ten lines: the line of ``init_params``' ``policy.init`` below is in
    # the traced frames of operations first traced during initialisation, and
    # so in the fused programs' compile-cache keys: PERF.md section 6, PR 30.)
    require_routed_ffn(model)

    return Policy(model=model, obs_spec=obs_spec, action_spec=action_spec)


def init_params(policy: Policy, rng: jax.Array):
    """Initialize parameters from a dummy batch-1 observation (shapes come
    from the policy's own specs).

    The ``losses`` collection (sown per-call intermediates like the MoE
    load-balancing loss) and ``routing`` (the experts a routed layer took,
    for a comparison that asks) are transient output, not state — they are
    stripped so they never ride inside the param tree (where the learner's
    scan would mistake them for scannable variables)."""
    dummy = dummy_obs_batch(1, policy.obs_spec, policy.action_spec)
    carry = policy.initial_state(1)
    variables = policy.init(rng, dummy, carry)
    return {k: v for k, v in variables.items() if k not in TRANSIENT_COLLECTIONS}


def dummy_obs_batch(
    batch: int, obs_spec: ObsSpec, action_spec: ActionSpec, time: int | None = None
) -> Dict[str, jnp.ndarray]:
    """Zero observation arrays of the right static shapes (init / AOT tracing)."""
    lead = (batch,) if time is None else (batch, time)
    return {
        "units": jnp.zeros(lead + (obs_spec.max_units, obs_spec.unit_features), jnp.float32),
        "unit_mask": jnp.zeros(lead + (obs_spec.max_units,), bool),
        "unit_handles": jnp.zeros(lead + (obs_spec.max_units,), jnp.int32),
        "globals": jnp.zeros(lead + (obs_spec.global_features,), jnp.float32),
        "hero_id": jnp.zeros(lead, jnp.int32),
        "mask_action_type": jnp.ones(lead + (action_spec.n_action_types,), bool),
        "mask_target_unit": jnp.ones(lead + (action_spec.max_units,), bool),
        "mask_cast_target": jnp.ones(lead + (action_spec.max_units,), bool),
        "mask_ability": jnp.ones(lead + (action_spec.max_abilities,), bool),
    }


def require_routed_ffn(model: ModelConfig) -> None:
    """Raise where ``moe_experts`` asks for a routed FFN of a core that has
    none: silently training a dense core under an "8-expert" label would
    mislabel every result."""
    if model.moe_experts > 0 and model.core not in ROUTED_FFN_CORES:
        raise ValueError(
            f"moe_experts={model.moe_experts} requires a core of {ROUTED_FFN_CORES} "
            f"(got core={model.core!r}), the cores that route an FFN"
        )


def resident_core(model: ModelConfig):
    """The module of a core whose carry stays on the chip
    (``ModelConfig.carry_stays_on_chip``), found by the core's name: it holds
    ``Core`` and answers for the carry (``initial_state``, ``reset``,
    ``chunk_start_view``, ``carry_bytes_per_lane``, ``require_episode_fits``),
    so that nothing in this file compares such a core's name."""
    import importlib

    return importlib.import_module(f"dotaclient_tpu.models.{RESIDENT_CORES[model.core]}")


def require_one_pass_decode(model: ModelConfig, where: str) -> None:
    """Raise where ``where`` steps the policy ONE pass an action and the core
    decodes an action as a block over several (``ModelConfig.diffusion_steps``:
    ``models/sdar.py decode``, which only the fused trainer's rollout runs)."""
    if model.diffusion_steps:
        raise ValueError(
            f"core {model.core!r} decodes an action as a block over {model.diffusion_steps} denoising "
            f"passes and a commit: it trains in actor mode 'fused', and {where} steps one pass an action"
        )

"""SDAR's block (``sdar_moe``: the Qwen3-MoE layer, generation by diffusion
over blocks) as a policy core that decodes an action as a block of tokens.

``ModelConfig.core = "sdar"``. A stack of pre-norm layers on a float32 stream
``h`` of width ``hidden_dim``, two residual adds a layer, no post-norm, no
input scale, no bias:

  a = RMSNorm(h)
  q = RMSNorm_q(a Wq), k = RMSNorm_k(a Wk) per head of head_dim, v = a Wv
  q, k rotated by RoPE(theta) at each row's position (rotate-half)
  h = h + softmax(q k^T / sqrt(head_dim) + M) v Wo    n_heads / n_kv_heads a KV head
  m = RMSNorm(h)
  h = h + sum over the chosen and held e of w_e SwiGLU_e(m)
      r = softmax(m Wr) over moe_experts (``route_score`` "softmax"), the
      experts_per_token largest, w = r_e / sum of the chosen (``route_norm``);
      no shared expert (``afmoe.RoutedExperts``)
  after the last layer y = RMSNorm(h)

**A game step is a block.** Step t of a lane's episode holds ``ROWS`` = 6
positions: the observation o_t at 6t (its row is the trunk's output, given,
like a prompt), then the five action slots at 6t + 1..5, in
``distributions.HEADS`` order, whose rows are token embeddings: one table of
every head's ids, ``[MASK]`` and NONE (``token_ids``). M is the block-causal
mask: o_t sees its episode's positions before 6t and itself; a slot sees the
same, o_t and all five slots of its own pass. The ring of each layer holds
``full_context`` positions, six a step; ``pos`` counts positions.

**A rollout step is S + 1 passes** (``decode``; S = ``diffusion_steps``):

1. rows [o_t, 5 x MASK]; o_t's key and value go to the ring; the action type
   is drawn from slot 1's row, the value read from o_t's row. The order in
   which the arguments the type makes relevant are committed is drawn from
   the rollout's key alone (``distributions.commit_stages``); the others
   become NONE.
2. passes 2..S: rows the five slots, the type and what earlier passes
   committed clean, the rest MASK; each pass draws the arguments it commits.
3. the commit pass: the five clean slots, whose keys and values go to the
   ring at 6t + 1..5: the next step's passes see the whole block.

Each head is taken from the pass that committed it (``act_stage``), and the
log-probability is ``distributions.staged_log_prob``.

**The learner's pass** (``sequence``, SDAR's training layout): a chunk's clean
rows (six a step, and the bootstrap step's) and S noisy copies of each step's
five slots, copy s holding what was committed before pass s. A clean row sees
the ring's episode, the chunk's earlier clean blocks and its own block as
above; noisy copy s of step t sees the ring, the clean blocks before t, o_t
and its own five rows, never t's clean slots or another copy. So copy s is
the rollout's pass s at the same parameters, and the learner's
log-probability is the rollout's. The scores of a chunk's rows against a ring
are too large for every lane at once: ``_attend_lanes`` runs them a few lanes
at a time (a ``lax.map`` over lane groups), rematerialised in the backward
pass. A rollout pass's rows (at most ``ROWS`` a lane, all of the lane's
current episode) see only the ring rows their lane's episode wrote: on a TPU
at heads of whole lanes the pass attends through the ring kernel
(``ops/pallas/ring_attend.py``, chosen by ``pass_takes_kernel`` under
``jax.lax.platform_dependent``), whose grid visits only the ring blocks that
hold them; the learner's pass, a CPU and toy widths keep ``_attend_lanes``.

Scopes inside ``policy_core``: ``core_denoise`` around each of the S
denoising passes, ``core_commit`` around the commit pass (the learner's pass
carries neither), and inside the layers ``core_attn_full`` with
``core_block_attend`` in it (a pass's rows against the ring and the block: no
weight is read under it), ``core_cache_write``, ``core_router``,
``core_experts_routed``. The rollout's draws between passes are under
``rollout_sample`` / ``rollout_stage_sample``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Mapping, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from dotaclient_tpu.config import ActionSpec, ModelConfig
from dotaclient_tpu.models import afmoe, distributions as D
from dotaclient_tpu.models.afmoe import (
    RMSNorm, RoutedExperts, SwiGLU, _attend, _dense, _dtype, chunk_positions, layer_is_dense, rope, write_rows,
)
from dotaclient_tpu.models.lanes import LaneBlocks, by_lane_block, join_lanes, split_lanes
from dotaclient_tpu.ops.pallas import ring_attend

SLOTS = len(D.HEADS)     # the tokens of an action's block
ROWS = 1 + SLOTS         # positions a game step: the observation and its block
# a pass's scores against the rings are computed this many bytes at a time at most
SCORE_BYTES = 1 << 27


# -- the static plan of a configuration --------------------------------------


def check_config(cfg: ModelConfig) -> None:
    afmoe.check_config(cfg)
    if (
        cfg.diffusion_steps < 2 or cfg.mup_enabled or cfg.loop_steps != 1 or cfg.attn_out_gate
        or not cfg.rope_full_layers or cfg.route_score != "softmax"
    ):
        raise ValueError(
            "core 'sdar' decodes the action type and at least one pass of arguments, with no input scale, one "
            "pass a position, no output gate, RoPE on every layer and a softmax router: set diffusion_steps >= 2, "
            "mup_enabled false, loop_steps 1, attn_out_gate false, rope_full_layers true, route_score 'softmax' "
            f"(got {cfg.diffusion_steps}, {cfg.mup_enabled}, {cfg.loop_steps}, {cfg.attn_out_gate}, "
            f"{cfg.rope_full_layers}, {cfg.route_score!r})"
        )


def carry_bytes_per_lane(cfg: ModelConfig) -> int:
    item = _dtype(cfg.dtype).dtype.itemsize
    return 8 + cfg.n_layers * cfg.full_context * 2 * cfg.n_kv_heads * cfg.head_dim * item


def require_episode_fits(cfg: ModelConfig, episode_steps: int, rollout_len: int) -> None:
    """An episode of ``episode_steps`` blocks, and the chunk a learner is
    handed the start of, have to fit the rings: six positions a step."""
    if rollout_len > cfg.rollout_chunk:
        raise ValueError(
            f"core 'sdar': ppo.rollout_len {rollout_len} exceeds model.rollout_chunk {cfg.rollout_chunk}"
        )
    if (episode_steps + cfg.rollout_chunk) * ROWS > cfg.full_context:
        raise ValueError(
            f"core 'sdar': an episode of {episode_steps} steps and a chunk of {cfg.rollout_chunk}, "
            f"{ROWS} positions a step, do not fit model.full_context {cfg.full_context}"
        )


def rollout_passes(cfg: ModelConfig) -> int:
    """Passes of the core a rollout step: S denoising passes and the commit."""
    return cfg.diffusion_steps + 1


def token_ids(spec: ActionSpec) -> Tuple[Dict[str, int], int, int, int]:
    """(the first id of each head's values, ``[MASK]``, NONE, table size):
    ``[MASK]`` and NONE are the table's last two rows."""
    offsets, at = {}, 0
    for h in D.HEADS:
        offsets[h] = at
        at += spec.head_sizes[h]
    return offsets, at, at + 1, at + 2


# -- the carry ------------------------------------------------------------------


def initial_state(cfg: ModelConfig, batch_size: int) -> Dict[str, Any]:
    dtype = _dtype(cfg.dtype)
    # a ring a KV head: a position is one contiguous row of head_dim (a pass's
    # write is a scatter of rows), and a head's keys are one [R, D] matrix that
    # a product of many rows (the learner's) takes as it lies
    heads = lambda: tuple(
        jnp.zeros((batch_size, cfg.full_context, cfg.head_dim), dtype) for _ in range(cfg.n_kv_heads)
    )
    return {
        "pos": jnp.zeros((batch_size,), jnp.int32),
        "cursor": jnp.zeros((batch_size,), jnp.int32),
        "kv": tuple((heads(), heads()) for _ in range(cfg.n_layers)),
    }


# position 0 hides what the ring holds; the learner reads the START's counters
# beside the rings as the chunk left them (``require_episode_fits``)
reset = afmoe.reset
chunk_start_view = afmoe.chunk_start_view


# -- which rows a pass's rows see --------------------------------------------------

# pass 1: o_t sees itself; a slot sees o_t and the five slots
FIRST_PASS = np.ones((ROWS, ROWS), bool)
FIRST_PASS[0, 1:] = False
# passes 2..S and the commit: the five slots see each other (o_t is in the ring by then)
SLOT_PASS = np.ones((SLOTS, SLOTS), bool)


def learner_rows(T: int, S: int):
    """The learner's row layout for a chunk of T steps and its bootstrap:
    ``(step [N], slot [N], copy [N], sees [N, N])``. Rows are the clean
    blocks of steps 0..T (slot 0 the observation; copy 0), then for each step
    0..T-1 and copy s = 1..S its five noisy slots. ``sees`` is what a row may
    see among the chunk's rows, before episodes cut it."""
    clean = [(t, k, 0) for t in range(T + 1) for k in range(ROWS)]
    noisy = [(t, k, s) for t in range(T) for s in range(1, S + 1) for k in range(1, ROWS)]
    step, slot, copy = (np.asarray(x) for x in zip(*(clean + noisy)))
    ti, tj = step[:, None], step[None, :]
    ki, kj = slot[:, None], slot[None, :]
    ci, cj = copy[:, None], copy[None, :]
    sees_clean = (tj < ti) | ((tj == ti) & ((kj == 0) | ((ci == 0) & (ki > 0))))
    sees_noisy = (ci == cj) & (ti == tj)
    return step, slot, copy, np.where(cj == 0, sees_clean, sees_noisy)


# -- the layer --------------------------------------------------------------------


def _groups(B: int, N: int, per_row: int) -> Tuple[int, int]:
    """(lanes a group, rows a block) whose scores take at most
    ``SCORE_BYTES``: every lane at once where they fit (a rollout pass), else
    whole lanes a few at a time, else one lane's rows a block at a time (the
    learner's pass at the published widths)."""
    if B * N * per_row <= SCORE_BYTES:
        return B, N
    if N * per_row <= SCORE_BYTES:
        return max(g for g in range(1, B + 1) if B % g == 0 and g * N * per_row <= SCORE_BYTES), N
    return 1, max([n for n in range(1, N + 1) if N % n == 0 and n * per_row <= SCORE_BYTES] or [1])


def _attend_lanes(q, k, v, ring_k, ring_v, see_ring, see_own):
    """``_attend`` a group of lanes, or a block of one lane's rows, at a time:
    the scores of a learner's chunk (342 rows a lane) against a ring are
    ``kv G R`` float32 a row, 0.8 GB a lane at the published widths, too
    much for every lane at once. Each group reads its lanes' rings where they
    lie (a slice along the lanes), and a block of rows its lane's keys and
    values whole: the softmax of a row is over all of them."""
    B, N, kv, G, _ = q.shape
    g, n = _groups(B, N, kv * G * ring_k.shape[1] * 4)
    if (g, n) == (B, N):
        return _attend(q, k, v, ring_k, ring_v, see_ring, see_own)
    blocks = N // n

    # the whole iteration rematerialised, its slices with it: the backward
    # pass keeps nothing an iteration but its index (saved, the slices of
    # every iteration's rings would be the rings again)
    @jax.checkpoint
    def one(i):
        lane, row = (i // blocks) * g, (i % blocks) * n
        lanes = lambda x: jax.lax.dynamic_slice_in_dim(x, lane, g)
        rows = lambda x: jax.lax.dynamic_slice_in_dim(lanes(x), row, n, axis=1)
        return _attend(rows(q), lanes(k), lanes(v), lanes(ring_k), lanes(ring_v), rows(see_ring), rows(see_own))

    out = jax.lax.map(one, jnp.arange(B // g * blocks))              # [groups, g, n, kv, G, D]
    return out.reshape(B // g, blocks, g, n, kv, G, -1).swapaxes(1, 2).reshape(q.shape[:-1] + (-1,))


def _attend_heads(q, k, v, ring, see_ring, see_own, *counters):
    """``_attend_lanes`` a KV head at a time: its G query heads against its
    own ring ``ring[0][h]``, ``ring[1][h]`` (``counters`` are the kernel's)."""
    return jnp.concatenate([
        _attend_lanes(q[:, :, h:h + 1], k[:, :, h:h + 1], v[:, :, h:h + 1], ring[0][h][:, :, None],
                      ring[1][h][:, :, None], see_ring, see_own)
        for h in range(q.shape[2])
    ], axis=2)


# -- a rollout pass's attention as the ring kernel ----------------------------------


def pass_takes_kernel(cfg: ModelConfig, rows: int, platform: str) -> bool:
    """Whether a pass of ``rows`` rows a lane attends through the ring kernel
    (``ops/pallas/ring_attend.py``) in a program lowered for ``platform``: the
    ONE predicate, the model's and the learner's for
    ``sdar/ring_kernel_calls_total``. A pass of at most ``ROWS`` rows is a
    rollout pass (``decode``; the learner's is hundreds), whose rows are all
    of their lane's current episode. Mosaic compiles for the TPU alone, and
    the kernel takes heads of whole lanes in bfloat16 or float32 and a ring of
    whole blocks (the published widths, not the toy widths of ``tests/``)."""
    return platform == "tpu" and rows <= ROWS and ring_attend.takes(cfg.head_dim, _dtype(cfg.dtype), cfg.full_context)


def _ring_pass(interpret, own, block_rows, q, k, v, ring, see_ring, see_own, pos0, cursor0):
    """``_attend_heads`` of a rollout pass through the kernel: ``see_ring`` and
    ``see_own`` are what the kernel reads from the counters and ``own``."""
    return ring_attend.ring_attend(
        q, k, v, ring[0], ring[1], pos0, cursor0, np.asarray(own), interpret=interpret, block_rows=block_rows
    )


# ``platform_dependent`` traces BOTH paths at every call (a pass has one a layer and lane set, and a start
# traces the rollout several times): under ``jit`` a path is traced once a shape and lowered once a program
_heads_traced_once = jax.jit(_attend_heads)
_ring_traced_once = jax.jit(_ring_pass, static_argnums=(0, 1, 2))


@functools.lru_cache(maxsize=None)
def _ring_branch(interpret: bool, own: Tuple[Tuple[bool, ...], ...], block_rows: int):
    """The kernel's branch for a pass whose rows see each other as ``own``
    (interpreted anywhere but on a TPU: a test's case), made once: a branch
    made anew at each call is traced anew."""
    return functools.partial(_ring_traced_once, interpret, own, block_rows)


def ring_rows_read_share(cfg: ModelConfig, pos, cursor) -> jnp.ndarray:
    """The share of the lanes' rings that a rollout pass at these counters
    reads through the kernel: rows of the blocks its grid visits over lanes x
    ``full_context`` (``sdar/ring_rows_read_share``)."""
    _, blocks = ring_attend.visited_blocks(pos, cursor, cfg.full_context)
    return jnp.sum(blocks) * ring_attend.BLOCK_ROWS / (pos.shape[0] * cfg.full_context)


class BlockAttention(nn.Module):
    """Grouped-query attention of a pass's rows ``a [B, N, H]`` over a ring
    and the pass's own rows: the afmoe core's parameters and products
    (``attn_qk_norm``, RoPE, no gate), with the block mask of the caller.
    ``p [B, N]`` positions, ``seg [B, N]`` each row's episode in the chunk
    (0 is the carry's: only those see the ring), ``pos0``/``cursor0`` the
    ring's counters, ``sees [N, N]`` what a row may see of the others in one
    episode, ``write`` ``(first, n)``: rows first..first + n - 1 go to the
    ring at ``cursor0``, or None."""

    config: ModelConfig

    @nn.compact
    def __call__(self, a, ring, p, seg, pos0, cursor0, sees, write):
        cfg = self.config
        dtype = _dtype(cfg.dtype)
        B, N, _ = a.shape
        nh, kv, D_ = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        G = nh // kv
        with jax.named_scope("core_attn_full"):
            q = _dense(cfg, nh * D_, "wq")(a).reshape(B, N, kv, G, D_)
            k = _dense(cfg, kv * D_, "wk")(a).reshape(B, N, kv, D_)
            v = _dense(cfg, kv * D_, "wv")(a).reshape(B, N, kv, D_)
            q = RMSNorm(cfg, name="q_norm")(q) if cfg.attn_qk_norm else q.astype(jnp.float32)
            k = RMSNorm(cfg, name="k_norm")(k) if cfg.attn_qk_norm else k.astype(jnp.float32)
            q, k = rope(q, p, cfg.rope_theta), rope(k, p, cfg.rope_theta)
            q, k, v = (q / math.sqrt(D_)).astype(dtype), k.astype(dtype), v.astype(dtype)
            own = jnp.asarray(sees)

            def attend(ring, q, k, v, seg, pos0, cursor0):
                R = ring[0][0].shape[1]
                age = (cursor0[:, None] - 1 - jnp.arange(R, dtype=jnp.int32)[None, :]) % R
                see_ring = (seg == 0)[:, :, None] & (age < pos0[:, None])[:, None, :]
                see_own = own[None] & (seg[:, :, None] == seg[:, None, :])
                with jax.named_scope("core_block_attend"):
                    platforms = [p for p in ("tpu", "cpu") if pass_takes_kernel(cfg, N, p)]
                    if not platforms:
                        return _attend_heads(q, k, v, ring, see_ring, see_own), None
                    own_key = tuple(map(tuple, np.asarray(sees, bool).tolist()))
                    kernel = {p: _ring_branch(p != "tpu", own_key, ring_attend.BLOCK_ROWS) for p in platforms}
                    return jax.lax.platform_dependent(
                        q, k, v, ring, see_ring, see_own, pos0, cursor0, default=_heads_traced_once, **kernel
                    ), None

            out, _ = by_lane_block(attend, ring, q, k, v, seg, pos0, cursor0)
            attn = _dense(cfg, cfg.hidden_dim, "wo")(out.reshape(B, N, nh * D_).astype(dtype))
        if write is not None:
            first, n = write
            with jax.named_scope("core_cache_write"):
                _, ring = by_lane_block(
                    lambda ring, k, v, c: (None, tuple(
                        tuple(write_rows(r, c, new[:, :, h]) for h, r in enumerate(heads)) for heads, new in zip(ring, (k, v))
                    )),
                    ring, k[:, first:first + n], v[:, first:first + n], cursor0,
                )
        return attn, ring


class Block(nn.Module):
    config: ModelConfig
    layer: int

    @nn.compact
    def __call__(self, h, ring, p, seg, pos0, cursor0, sees, write):
        cfg = self.config
        dtype = _dtype(cfg.dtype)
        a = RMSNorm(cfg, name="attn_norm")(h).astype(dtype)
        mix, ring = BlockAttention(cfg, name="attn")(a, ring, p, seg, pos0, cursor0, sees, write)
        h = h + mix.astype(jnp.float32)
        m = RMSNorm(cfg, name="ffn_norm")(h).astype(dtype)
        if layer_is_dense(cfg, self.layer):
            with jax.named_scope("core_dense_ffn"):
                f = SwiGLU(cfg, cfg.dense_ffn_dim, name="ffn")(m)
        else:
            # rematerialised in a backward pass: the learner's routed buffer is
            # 8 rows a token of its 342 a lane, gigabytes kept a layer otherwise
            f = nn.remat(RoutedExperts)(cfg, name="moe")(m)
        return h + f.astype(jnp.float32), ring


class SdarCore(nn.Module):
    """The layers, the final norm and the token table; ``run`` is one pass.
    Called as ``(carry, x [B, 1, H])`` (what ``init_params`` traces through
    ``Policy.step``) it is pass 1 of a step and hands back the observation's
    row; a game step of the policy is ``decode``."""

    config: ModelConfig

    def setup(self):
        cfg = self.config
        check_config(cfg)
        for l in range(cfg.n_layers):
            setattr(self, f"layer_{l}", Block(cfg, l))
        self.out_norm = RMSNorm(cfg)
        # the block's vocabulary is the policy's action space (this core is its policy's child)
        self.vocab = token_ids(getattr(self.parent, "action_spec", ActionSpec()))[3]
        self.tokens = self.param(
            "tokens", nn.initializers.normal(0.02), (self.vocab, cfg.hidden_dim), _dtype(cfg.param_dtype)
        )

    def embed(self, ids: jnp.ndarray) -> jnp.ndarray:
        """Token rows ``[..., H]`` in the compute type: a product with the ids'
        one-hot rows, never a gather (one term an output, so the selection is
        exact in any precision the table is held in; its transpose is a
        product too)."""
        dtype = _dtype(self.config.dtype)
        onehot = jax.nn.one_hot(ids, self.vocab, dtype=dtype)
        return jnp.dot(onehot, self.tokens.astype(dtype), preferred_element_type=jnp.float32).astype(dtype)

    def run(self, kv, rows, p, seg, pos0, cursor0, sees, write=None):
        """One pass of ``rows [B, N, H]`` through the stack -> (rings, y)."""
        h = rows.astype(jnp.float32)                # the residual stream stays float32
        rings = []
        for l in range(self.config.n_layers):
            h, ring = getattr(self, f"layer_{l}")(h, kv[l], p, seg, pos0, cursor0, sees, write)
            rings.append(ring)
        return tuple(rings), self.out_norm(h).astype(_dtype(self.config.dtype))

    def __call__(self, carry, x, resets=None):
        if x.shape[1] != 1:
            raise ValueError(
                "core 'sdar' has no one-pass chunk: a step is models/sdar.py decode, a learner's chunk sdar.sequence"
            )
        B, R = x.shape[0], self.config.full_context
        pos0, cursor0 = carry["pos"], carry["cursor"]
        rows = jnp.concatenate([x[:, :1].astype(_dtype(self.config.dtype)), self.embed(jnp.full((B, SLOTS), self.vocab - 2))], 1)
        p = pos0[:, None] + jnp.arange(ROWS, dtype=jnp.int32)
        kv, y = self.run(carry["kv"], rows, p, jnp.zeros_like(p), pos0, cursor0, FIRST_PASS, (0, 1))
        return {"pos": pos0 + 1, "cursor": (cursor0 + 1) % R, "kv": kv}, y[:, :1]


Core = SdarCore      # what ``models/policy.py resident_core`` constructs


# -- a rollout step: S denoising passes and a commit --------------------------------


def _per_game(fn, key: jax.Array, *trees):
    """``fn(key, *trees)`` vmapped over a lane set's games: lanes are
    game-major, each game's lanes draw from that game's key (as
    ``actor/device_rollout.sample_per_game``)."""
    games = key.shape[0]
    split = lambda t: t.reshape((games, t.shape[0] // games) + t.shape[1:])
    out = jax.vmap(fn)(key, *(jax.tree.map(split, t) for t in trees))
    return jax.tree.map(lambda t: t.reshape((-1,) + t.shape[2:]), out)


def _by_set(fn, sizes, keys, *trees):
    """``_per_game`` a lane set at a time over the rows of all sets."""
    outs, at = [], 0
    for n, key in zip(sizes, keys):
        outs.append(_per_game(fn, key, *(jax.tree.map(lambda t: t[at:at + n], t) for t in trees)))
        at += n
    return jax.tree.map(lambda *o: jnp.concatenate(o), *outs)


def _slot_logits(policy, y_slots, unit_emb):
    """Head j's logits from slot j's row ``y_slots [B, 5, H]``."""
    logits, _ = policy._heads(y_slots, unit_emb[:, None])
    return {h: logits[h][:, j] for j, h in enumerate(D.HEADS)}


def _tokens(ids, actions, stage, before: int):
    """``[B, 5]`` the block's tokens as pass ``before`` reads them: a slot
    committed by an earlier pass clean, a slot the type leaves out NONE once
    the type is known, the rest MASK."""
    offsets, mask, none, _ = ids
    clean = jnp.stack([offsets[h] + actions[h].astype(jnp.int32) for h in D.HEADS], axis=-1)
    st = stage.astype(jnp.int32)
    out = jnp.where((st > 0) & (st < before), clean, mask)
    return jnp.where((st == 0) & (before > 1), none, out)


def decode(policy, obs: Mapping[str, jnp.ndarray], carry, keys, forced: Optional[Tuple[Any, Any]] = None):
    """A rollout step of a policy whose core decodes a block (a method of
    ``Policy``: ``policy.apply(params, obs, carry, keys, method=decode)``).
    ``carry`` is one lane set's or a ``LaneBlocks`` of several (one pass over
    all rows, each set's rings where they lie); ``keys`` are ``[games, 2]``
    per set (a tuple where the carry is a ``LaneBlocks``). ``forced``
    ``(actions, act_stage)`` takes those instead of drawing (teacher
    forcing: the comparison). Returns ``({"actions", "logp", "act_stage",
    "logits": each head [S, B, K], "value"}, carry)``."""
    cfg, core = policy.model, policy.core
    S, R, dtype = cfg.diffusion_steps, cfg.full_context, _dtype(cfg.dtype)
    ids = token_ids(policy.action_spec)
    with jax.named_scope("policy_trunk"):
        x, unit_emb = policy._trunk(obs)
    B = x.shape[0]
    sets = carry
    sizes = [jax.tree.leaves(c)[0].shape[0] for c in sets] if isinstance(sets, LaneBlocks) else [B]
    keys = tuple(keys) if isinstance(sets, LaneBlocks) else (keys,)
    with jax.named_scope("policy_core"):
        carry = join_lanes(carry, True)
        pos0, cursor0, kv = carry["pos"], carry["cursor"], carry["kv"]
        zeros = jnp.zeros((B, SLOTS), jnp.int32)
        with jax.named_scope("core_denoise"):
            rows = jnp.concatenate([x[:, None].astype(dtype), core.embed(zeros + ids[1])], axis=1)
            p = pos0[:, None] + jnp.arange(ROWS, dtype=jnp.int32)
            kv, y = core.run(kv, rows, p, jnp.zeros_like(p), pos0, cursor0, FIRST_PASS, (0, 1))
    # passes 2.. see o_t in the ring: the counters as they stand after its write
    pos1, cursor1 = pos0 + 1, cursor0 + 1
    p_slots = pos1[:, None] + jnp.arange(SLOTS, dtype=jnp.int32)
    with jax.named_scope("policy_heads"):
        _, value = policy._heads(y[:, 0], unit_emb)
        passes = [_slot_logits(policy, y[:, 1:], unit_emb)]
    with jax.named_scope("rollout_sample"), jax.named_scope("rollout_stage_sample"):
        if forced is None:
            def draw(i, fn, *trees):
                """``fn`` a game at a time with key i of the game's S + 1:
                0 the type, 1 the order, s the arguments of pass s."""
                return _by_set(lambda k, *t: fn(jax.random.split(k, S + 1)[i], *t), sizes, keys, *trees)

            a_type = draw(0, D.type_sample, passes[0]["action_type"], obs)
            stage = draw(1, lambda k, t: D.commit_stages(k, t, S), a_type)
        else:
            actions_in, stage = forced
            a_type = actions_in["action_type"]
        actions = {h: jnp.zeros_like(a_type) for h in D.HEADS}
        actions["action_type"] = a_type
    for s in range(2, S + 1):
        with jax.named_scope("policy_core"), jax.named_scope("core_denoise"):
            rows = core.embed(_tokens(ids, actions, stage, s))
            _, y = core.run(kv, rows, p_slots, zeros, pos1, cursor1, SLOT_PASS)
        with jax.named_scope("policy_heads"):
            passes.append(_slot_logits(policy, y, unit_emb))
        with jax.named_scope("rollout_sample"), jax.named_scope("rollout_stage_sample"):
            if forced is None:
                drawn = draw(s, D.stage_sample, passes[-1], obs, a_type)
            else:
                drawn = forced[0]
            st = stage.astype(jnp.int32)
            for j, h in enumerate(D.HEADS[1:], start=1):
                actions[h] = jnp.where(st[:, j] == s, drawn[h], actions[h])
    with jax.named_scope("policy_core"):
        with jax.named_scope("core_commit"):
            rows = core.embed(_tokens(ids, actions, stage, S + 1))
            kv, _ = core.run(kv, rows, p_slots, zeros, pos1, cursor1, SLOT_PASS, (0, SLOTS))
        carry = split_lanes({"pos": pos0 + ROWS, "cursor": (cursor0 + ROWS) % R, "kv": kv}, sets)
    stage_logits = {h: jnp.stack([lg[h] for lg in passes]) for h in D.HEADS}
    with jax.named_scope("rollout_sample"), jax.named_scope("rollout_stage_sample"):
        logp = D.staged_log_prob(stage_logits, obs, actions, stage)
    out = {"actions": actions, "logp": logp, "act_stage": stage.astype(jnp.int8), "logits": stage_logits, "value": value}
    return out, carry


# -- the learner's pass ---------------------------------------------------------------


def sequence(policy, obs: Mapping[str, jnp.ndarray], carry, dones, actions, act_stage):
    """The learner's pass over a chunk in SDAR's training layout (a method of
    ``Policy``): obs ``[B, T + 1, ...]`` (the bootstrap step last), the
    chunk-start carry, ``dones [B, T]``, the rollout's ``actions`` and
    ``act_stage [B, T, 5]``. Returns (stage logits: each head ``[S, B, T,
    K]``, copy s's slot rows; values ``[B, T + 1]`` from the clean
    observation rows). No ring is written: the rollout has."""
    cfg, core = policy.model, policy.core
    S, dtype = cfg.diffusion_steps, _dtype(cfg.dtype)
    ids = token_ids(policy.action_spec)
    with jax.named_scope("policy_trunk"):
        x, unit_emb = policy._trunk(obs)                     # [B, T + 1, H]
    B, T1 = x.shape[:2]
    T = T1 - 1
    resets = jnp.concatenate([jnp.zeros((B, 1), jnp.float32), dones.astype(jnp.float32)[:, :T]], axis=1)
    step, slot, copy, sees = learner_rows(T, S)
    with jax.named_scope("policy_core"):
        pos0, cursor0 = carry["pos"], carry["cursor"]
        seg, pstep = chunk_positions(pos0 // ROWS, resets, T1)           # a step's segment and place in its episode
        p = ROWS * pstep[:, step] + slot[None]
        row_seg = seg[:, step]
        # clean blocks: the rollout's tokens (the bootstrap step's slots MASK: nothing reads them)
        st = jnp.pad(act_stage.astype(jnp.int32), ((0, 0), (0, 1), (0, 0)))
        acts = {h: jnp.pad(actions[h], ((0, 0), (0, 1))) for h in D.HEADS}
        clean = _tokens(ids, acts, st, S + 1)
        clean = jnp.where(jnp.arange(T1)[None, :, None] == T, ids[1], clean)
        noisy = jnp.stack([_tokens(ids, actions, act_stage, s) for s in range(1, S + 1)], axis=2)   # [B, T, S, 5]
        rows = jnp.concatenate([
            jnp.concatenate([x[:, :, None].astype(dtype), core.embed(clean)], axis=2).reshape(B, T1 * ROWS, -1),
            core.embed(noisy).reshape(B, T * S * SLOTS, -1),
        ], axis=1)
        _, y = core.run(carry["kv"], rows, p, row_seg, pos0, cursor0, sees)
    with jax.named_scope("policy_heads"):
        _, values = policy._heads(y[:, :T1 * ROWS:ROWS], unit_emb)
        y_noisy = y[:, T1 * ROWS:].reshape(B, T, S, SLOTS, -1)
        logits, _ = policy._heads(y_noisy, unit_emb[:, :T, None, None])
    stage_logits = {h: jnp.moveaxis(logits[h][:, :, :, j], 2, 0) for j, h in enumerate(D.HEADS)}
    return stage_logits, values

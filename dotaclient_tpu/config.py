"""Frozen dataclass configuration tree.

The reference spreads configuration across per-entrypoint ``argparse`` flags
plus the ``GameConfig`` proto (SURVEY.md §5.6). Here the whole system is
configured by one immutable tree that is serialized into checkpoints; the
``GameConfig`` proto survives only at the environment boundary.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Mapping, Tuple


@dataclasses.dataclass(frozen=True)
class ObsSpec:
    """Fixed-shape observation layout (TPU-critical: no shape depends on the
    live unit count — SURVEY.md §7 step 2)."""

    max_units: int = 32          # padded unit slots per observation
    unit_features: int = 22      # per-unit feature vector length
    global_features: int = 8     # game-time, team, gold/xp diffs, ...
    max_abilities: int = 4       # ability slots exposed per hero


@dataclasses.dataclass(frozen=True)
class ActionSpec:
    """Discrete multi-head action space (reference head set, SURVEY.md §3.3)."""

    n_action_types: int = 4      # noop / move / attack-unit / cast
    move_bins: int = 9           # discretized move offsets per axis
    max_units: int = 32          # target-unit head size == padded unit slots
    max_abilities: int = 4

    @property
    def head_sizes(self) -> Mapping[str, int]:
        return {
            "action_type": self.n_action_types,
            "move_x": self.move_bins,
            "move_y": self.move_bins,
            "target_unit": self.max_units,
            "ability": self.max_abilities,
        }


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Flax policy hyper-parameters (LSTM(128) core per BASELINE.json:7)."""

    unit_embed_dim: int = 64
    hidden_dim: int = 128        # LSTM hidden size — parity with reference
    n_hero_ids: int = 32         # hero-embedding vocabulary (multi-hero pools)
    hero_embed_dim: int = 16
    core: str = "lstm"           # "lstm" | "transformer" | RESIDENT_CORES ("afmoe", "looplm", "kimilinear", "lfm2moe", "sdar")
    # Transformer-core options (scale-out path, SURVEY.md §7 step 8).
    n_layers: int = 2
    n_heads: int = 4
    context_window: int = 16     # rolling KV-cache length (recurrent carry)
    # Mixture-of-experts FFN (expert parallelism; 0 = dense MLP).
    moe_experts: int = 0         # experts per MoE layer, sharded over `model`
    moe_capacity_factor: float = 2.0
    dtype: str = "bfloat16"      # compute dtype; params stay float32
    param_dtype: str = "float32"
    # "afmoe" core (models/afmoe.py): window and full grouped-query
    # attention over per-lane ring caches, sigmoid top-k routing with a
    # shared expert. It reads hidden_dim (stream width), n_layers, n_heads,
    # context_window (the sliding window) and moe_experts (the router's
    # width) from the fields above, and these:
    n_kv_heads: int = 4
    head_dim: int = 128
    global_attn_every: int = 4   # expert layer l attends fully iff (l+1+offset) % this == 0
    global_attn_offset: int = 0  # published index of layer l minus l (a cut that keeps a later period)
    full_context: int = 3072     # ring of a full layer: >= episode steps + rollout_chunk
    rollout_chunk: int = 16      # a window ring holds context_window + this many steps
    n_dense_layers: int = 1      # leading layers with a dense FFN
    dense_ffn_dim: int = 6144
    expert_ffn_dim: int = 1024
    experts_per_token: int = 8
    n_shared_experts: int = 1
    held_experts: int = 0        # routed experts this chip holds (0 = all)
    expert_offset: int = 0       # index of the first held expert
    route_norm: bool = True
    route_scale: float = 2.826
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True     # stream input scaled by sqrt(hidden_dim)
    # What an afmoe layer does besides, each switched off by a core that
    # lacks it ("looplm", models/looplm.py: a stack of these layers with
    # moe_experts 0, so every FFN is dense, and global_attn_every 1, so
    # every layer attends fully):
    attn_qk_norm: bool = True    # RMSNorm of each head's query and key
    attn_out_gate: bool = True   # attention output times sigmoid(a Wgate)
    rope_full_layers: bool = False  # RoPE on full-attention layers too
    # "looplm" core: the stack of n_layers runs loop_steps times over a
    # position with ONE set of weights; every (loop step, layer) pair keeps
    # its own ring of full_context rows, and a gate after each loop step
    # gives the probability of stopping there (train/ppo.exit_weighted_loss)
    loop_steps: int = 1
    # "kimilinear" core (models/kimilinear.py): delta-rule linear-attention
    # layers (KDA: a float32 matrix state a head and a short convolution's
    # history) beside latent-attention layers (MLA: one ring of kv_lora_rank +
    # qk_rope_head_dim numbers a position). Which layer is which comes from
    # global_attn_every / global_attn_offset / n_dense_layers ("full" = MLA);
    # n_heads serves both kinds; the FFNs are the afmoe core's. Widths no
    # field above states:
    kv_lora_rank: int = 512      # MLA: the normalised latent of keys and values
    qk_nope_head_dim: int = 128  # MLA: a head's query/key width from the latent
    qk_rope_head_dim: int = 64   # MLA: the key part shared by all heads
    v_head_dim: int = 128        # MLA: a head's value width
    kda_head_dim: int = 128      # KDA: d_k = d_v of a head's state
    kda_conv_kernel: int = 4     # KDA: taps of the causal depthwise convolution
    # "lfm2moe" core (models/lfm2moe.py): gated short-convolution layers (a
    # carry of shortconv_taps - 1 rows of hidden_dim a lane and layer) beside
    # grouped-query attention layers over rings of full_context rows (the
    # afmoe core's Attention with attn_qk_norm and rope_full_layers on,
    # attn_out_gate off); "full" = attention, the rest as the kimilinear
    # core reads the fields above; n_shared_experts 0. The one width no
    # field above states:
    shortconv_taps: int = 3      # taps of the mixer's causal depthwise convolution (conv_L_cache)
    # A routed layer's buffer of tokens x experts_per_token rows multiplied
    # WHOLE (afmoe.RoutedExperts, whichever core builds it): the rows of pairs
    # whose expert is not held, zeros, padded evenly into the held experts'
    # groups, so that a step's time does not follow the router's draw. Same
    # outputs; more work wherever fewer pairs land here than the buffer holds.
    pad_expert_groups: bool = False
    # What a routed layer's router scores its experts by (afmoe.RoutedExperts):
    # "sigmoid" each expert on its own (Trinity, Kimi-Linear, LFM2), "softmax"
    # over all of them (the Qwen3-MoE layer of the "sdar" core)
    route_score: str = "sigmoid"
    # "sdar" core (models/sdar.py): an action is a block of five tokens (the
    # heads in distributions.HEADS order) decoded by this many denoising
    # passes over the block and a pass that commits it to the ring; a game
    # step is six positions, the observation and its block. 0: every other
    # core, whose step yields its action from one pass
    diffusion_steps: int = 0

    @property
    def carry_stays_on_chip(self) -> bool:
        """The core's carry is megabytes a lane (ring caches, a linear-
        attention layer's matrix states) that stay on the chip, and the core
        owns its reset and its chunk start: a reset moves a counter and
        rewrites no leaf, a chunk start widens and copies nothing the core
        can read back, and only the fused trainer and the serve engine's
        resident carries run it (``models/policy.py require_carry_stays``).
        The core's module (``RESIDENT_CORES``) answers for the carry."""
        return self.core in RESIDENT_CORES

    # the property's name before PR 32, when every such carry was rings:
    # benchmark/tests/test_looplm_cell.py asks under it, and files under
    # benchmark/ are a `benchmark` PR's to edit
    carry_is_rings = carry_stays_on_chip


# Cores whose carry stays on the chip (ModelConfig.carry_stays_on_chip) and
# the module under dotaclient_tpu/models/ that holds each one's ``Core``,
# ``initial_state``, ``reset``, ``chunk_start_view``, ``carry_bytes_per_lane``
# and ``require_episode_fits``: ring caches (afmoe, looplm), matrix states
# beside a latent ring (kimilinear), convolution histories of kilobytes
# beside one ring (lfm2moe), a ring of six positions a step (sdar).
RESIDENT_CORES = {"afmoe": "afmoe", "looplm": "looplm", "kimilinear": "kimilinear", "lfm2moe": "lfm2moe", "sdar": "sdar"}

# Cores whose FFN slot can be a routed mixture (``moe_experts`` > 0).
ROUTED_FFN_CORES = ("transformer", "afmoe", "kimilinear", "lfm2moe", "sdar")


# Valid PPOConfig.adv_norm values — the single source of truth for the
# runtime check in train.ppo and any CLI-level validation.
ADV_NORM_MODES = ("batch", "none")

# Valid PPOConfig.advantage estimators.
ADVANTAGE_MODES = ("gae", "vtrace")

# Valid PPOConfig.advantage_dtype storage widths for the one-pass
# advantage plane's staged advantages/returns (train/advantage.py).
ADVANTAGE_STORE_DTYPES = ("bfloat16", "float32")


@dataclasses.dataclass(frozen=True)
class PPOConfig:
    gamma: float = 0.99
    gae_lambda: float = 0.95
    clip_eps: float = 0.2
    value_coef: float = 0.5
    entropy_coef: float = 0.01
    learning_rate: float = 3e-4
    max_grad_norm: float = 0.5
    rollout_len: int = 16        # truncated-BPTT chunk length T
    batch_rollouts: int = 32     # rollouts per optimizer step (B)
    epochs_per_batch: int = 1
    minibatches: int = 1         # shuffled minibatch splits per epoch
    max_staleness: int = 4       # drop rollouts older than this many BATCHES
    moe_aux_coef: float = 0.01   # Switch load-balancing loss weight (MoE core)
    # afmoe core: step of the selection bias's balancing update per optimizer
    # step (train/ppo._balance_select_bias); 0 leaves the bias where it is
    select_bias_rate: float = 0.001
    # looped core (model.loop_steps > 1): weight of the exit distribution's
    # entropy bonus in the exit-weighted loss (train/ppo.exit_weighted_loss)
    exit_entropy_coef: float = 0.05
    # Advantage normalization. "batch" (the standard per-batch whitening) is
    # right for training from scratch, but it amplifies GAE noise to unit
    # scale when the true advantage signal is ~zero — measured to destroy a
    # near-optimal transferred policy within ~1k steps (BASELINE.md, 5v5
    # curriculum). adv_norm_floor puts a lower bound on the divisor so small
    # advantages stay small: floor 0.0 reproduces the standard behavior,
    # floor 1.0 means "whiten only when the batch std exceeds unit scale".
    # adv_norm="none" centers but never rescales.
    adv_norm: str = "batch"      # one of ADV_NORM_MODES
    adv_norm_floor: float = 0.0
    # Advantage estimator. "gae" (reference parity) assumes on-policy
    # batches; "vtrace" (IMPALA) reweights every step by the clipped
    # importance ratio min(ρ̄, π/μ) so STALE rollouts from async actors
    # contribute bias-corrected targets instead of being merely tolerated
    # by the PPO clip — the estimator for the external/overlap topology
    # at high staleness. gae_lambda is unused under vtrace (its trace
    # cutting comes from the c̄-clipped ratios).
    advantage: str = "gae"       # one of ADVANTAGE_MODES
    vtrace_rho_clip: float = 1.0
    vtrace_c_clip: float = 1.0
    # Critic-only warmup: for the first N optimizer steps, train ONLY the
    # value head (policy surrogate + entropy off; all non-value-head grads
    # masked to zero, so the behavior policy is bitwise frozen). The
    # curriculum-transfer lever: after --init-from the transferred critic is
    # calibrated to the SOURCE config's returns (team size, reward weights,
    # gamma), so early advantages are systematically wrong and can destroy a
    # near-optimal policy before the critic adapts (BASELINE.md 5v5
    # fine-tune measurements). 0 disables.
    value_warmup_steps: int = 0
    # KL-adaptive learning rate (trust-region-style auto-stabilizer).
    # When kl_target > 0, every train step measures the POST-update KL
    # (k3 estimator over the batch's taken actions) inside the compiled
    # step and adapts the Adam learning rate carried in the optimizer
    # state: ×kl_lr_down when KL > 2·target, ×kl_lr_up when KL <
    # target/2, clipped to [learning_rate·kl_lr_min_scale,
    # learning_rate·kl_lr_max_scale]. Fully in-graph — no host sync — so
    # it works in fused mode. Motivating measurement: 5v5 fine-tune
    # collapses at lr 3e-4 but ascends at 1e-5 (BASELINE.md); this makes
    # step size self-tuning instead of a per-run guess. 0 disables
    # (plain constant-lr Adam; optimizer-state layout unchanged).
    kl_target: float = 0.0
    # Anchor-KL regularizer (AlphaStar's KL-to-supervised-anchor, adapted):
    # adds anchor_kl_coef · KL(π_θ ‖ π_anchor) to the loss, where π_anchor
    # is the policy AT LEARNER CONSTRUCTION (after --restore/--init-from —
    # i.e. the transferred policy in a curriculum run; a mid-run resume
    # re-anchors at the resumed params). Motivation (BASELINE.md, 5v5
    # fine-tune): the shaped reward's true optimum is a farming attractor,
    # and rate limiters (low lr, KL-adaptive lr) only slow the slide into
    # it — a persistent gradient integrates to the same place. The anchor
    # term changes the optimum instead: drift from the known-good policy
    # now costs loss, so improvement must pay for its distance. 0 disables.
    anchor_kl_coef: float = 0.0
    kl_lr_down: float = 0.7
    kl_lr_up: float = 1.02
    kl_lr_min_scale: float = 0.01
    kl_lr_max_scale: float = 10.0
    # Fused epoch step (train/ppo.make_epoch_step): when a consumed batch
    # needs more than one optimizer step (epochs_per_batch × minibatches >
    # 1), run ALL of them inside one donated XLA program — a lax.scan over
    # minibatch slices of the epoch permutations — instead of the staged
    # host loop's gather+step dispatch pair per minibatch. Same updates on
    # the same data (the permutations come from the same seeded stream as
    # the staged fallback; agreement to XLA-fusion float rounding); the
    # staged path remains for --checkify and as the explicit opt-out.
    # False forces the staged loop.
    fused_epoch: bool = True
    # One-pass advantage plane (train/advantage.py): compute the value
    # forward + GAE scan ONCE per consumed batch — a jitted, mesh-sharded
    # pass at the buffer gather boundary — and train all epochs_per_batch
    # × minibatches optimizer steps on the precomputed advantages/returns
    # instead of re-running the estimator inside every step (HEPPO-GAE's
    # pipeline-stage observation, PAPERS.md). This is the standard PPO
    # regime (advantages fixed for the batch, from the params the batch's
    # first update trains from); the in-step recompute remains for
    # advantage="vtrace" (its importance ratios need the CURRENT policy's
    # logp, which changes every optimizer step), for fused mode (the
    # rollout+update program is strictly on-policy with E×M per-chunk
    # updates of its own), and at steps_per_batch == 1 (the in-step
    # estimator already runs once per batch there — a separate pass would
    # add a forward, not remove one). False forces the per-step recompute
    # everywhere.
    one_pass_advantage: bool = True
    # Storage width for the staged advantages/returns between the pass and
    # the epoch step (the narrow-ring discipline of ISSUE 7 extended to
    # the advantage plane): "bfloat16" halves the staged bytes and the
    # loss upcasts at consume; "float32" opts out (bit-exact staging).
    # The estimator's INPUTS (rewards, behavior_logp, dones, values) keep
    # their pinned-f32 precision either way — only the derived outputs
    # narrow.
    advantage_dtype: str = "bfloat16"

    @property
    def steps_per_batch(self) -> int:
        """Optimizer steps (= version ticks) per consumed batch — the unit
        ``max_staleness`` is denominated in. Shared by the learner's
        counters and the buffer's staleness window so they cannot drift."""
        return self.epochs_per_batch * max(1, self.minibatches)


@dataclasses.dataclass(frozen=True)
class EnvConfig:
    n_envs: int = 8
    ticks_per_observation: int = 6
    max_dota_time: float = 600.0
    hero_pool: Tuple[int, ...] = (1,)   # hero ids agents may draft from
    team_size: int = 1                  # 1 => 1v1, 2 => 2v2, 5 => 5v5
    opponent: str = "scripted_easy"     # scripted_easy | scripted_hard | league
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class RewardConfig:
    """Shaped-reward component weights.

    The reference hardcoded its shaping inside ``agent.py`` (SURVEY.md §2.1);
    here the table is part of the config tree (checkpointed, overridable per
    run). Defaults reproduce ``features/reward.py``'s historical weights.
    The 5v5 pure-self-play experiments in BASELINE.md show why this must be
    tunable: dense farm shaping can dominate the sparse win/tower terms and
    converge to a farming equilibrium that loses the timeout adjudication.
    """

    xp: float = 0.002
    gold: float = 0.006
    hp: float = 2.0            # own-hero hp *fraction* delta
    enemy_hp: float = 1.0      # symmetric harass term
    last_hits: float = 0.16
    denies: float = 0.12
    kills: float = 1.0
    deaths: float = -1.0
    tower_damage: float = 2.0  # enemy tower hp-fraction lost
    own_tower: float = 2.0     # OWN tower hp-fraction lost (defense term)
    win: float = 5.0

    def as_dict(self) -> Mapping[str, float]:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device mesh layout. Axes: dcn (multi-slice), data (batch/grad psum),
    model (TP). With ``dcn_slices == 1`` the mesh is 2-D (data, model)."""

    data_axis: str = "data"
    model_axis: str = "model"
    dcn_axis: str = "dcn"
    data_parallel: int = -1      # -1 => all remaining devices
    model_parallel: int = 1
    dcn_slices: int = 1          # ICI-connected slices bridged over DCN


@dataclasses.dataclass(frozen=True)
class BufferConfig:
    capacity_rollouts: int = 256   # ring-buffer slots (sharded over data axis)
    min_fill: int = 32             # rollouts required before first train step
    # Host staging lanes for ingest: decoded rollout rows are copied into
    # one of this many REUSED preallocated numpy buffers (rotating) before
    # the device scatter, instead of a fresh np.stack allocation per ingest.
    # 2 = double buffering: the scatter for ingest N can still be in flight
    # (async dispatch holds the host rows) while ingest N+1 assembles into
    # the other lane. 1 disables the overlap margin but keeps the reuse.
    staging_slots: int = 2
    # Transport-consume poll timeout (seconds) for the learner's ingest
    # drain — how long an empty poll blocks before the loop moves on. A
    # batch already assembled in the prefetch lane is consumed without
    # reaching the drain at all (train/learner.py `_next_batch`).
    consume_poll_timeout_s: float = 0.001
    # Admission control (ISSUE 6): semantic integrity at the buffer door,
    # extending the wire-integrity discipline (CRC + poison-peer
    # quarantine, ISSUE 4) to payload CONTENT.
    #
    # max_weight_staleness: absolute version-delta bound for admission —
    # a frame whose producer version is more than this many optimizer
    # versions behind is rejected and counted
    # (buffer/stale_rejected_total). -1 (default) derives the bound from
    # ppo.max_staleness × steps_per_batch, the historical behavior; >= 0
    # overrides it with a raw version delta (the knob thousand-actor
    # fleets tune directly — IMPACT's soundness argument needs staleness
    # BOUNDED at ingest, not merely observed).
    max_weight_staleness: int = -1
    # reject_nonfinite: scan every float leaf of a host-ingested payload
    # (observations, rewards, behavior logp, carries) and reject frames
    # carrying NaN/Inf (buffer/nonfinite_rejected_total) — one actor with
    # corrupted state must not poison the learner's numerics. Device-path
    # ingest (add_device) skips the scan: those chunks are produced
    # in-process by construction and divergence there is the train-step
    # probe's job (train/health.py).
    reject_nonfinite: bool = True


@dataclasses.dataclass(frozen=True)
class TransportConfig:
    """Cross-process transport tuning (socket/shm lanes; ISSUE 3).

    The weights fanout is non-blocking: ``publish_weights`` is an O(1)
    enqueue per connection and per-connection writer threads do the actual
    sends, coalescing to the latest version (stale intermediate weights are
    worthless — IMPACT licenses bounded staleness, PAPERS.md)."""

    # Wire dtype for the weights fanout: "float32" (bit-exact) or
    # "bfloat16" — float32 params are cast at encode, halving the fanout
    # bytes per publish; the actor upcasts on apply (lossless: every bf16
    # value is exactly representable in f32).
    wire_dtype: str = "float32"
    # Wire dtype for ROLLOUT payloads (ISSUE 7) — the dominant byte stream
    # at scale: "float32" (bit-exact, the default) or "bfloat16". With
    # bfloat16, actors narrow f32 observation/feature leaves to bf16 (and
    # config-bounded integer leaves — action indices, hero ids — to
    # int8/int16, exactly) at encode, the ``__wire_cast__`` marker names
    # what was narrowed, the learner's trajectory buffer STORES the narrow
    # dtypes (≈half the resident HBM ring bytes and per-scatter H2D
    # traffic), and the upcast to f32 happens on-device inside the already
    # jitted consume gather — the train step sees f32 inputs bit-identical
    # to decoding the wire. Precision-critical leaves (behavior_logp,
    # rewards, dones, values, LSTM initial carries) are pinned f32 by
    # serialize.rollout_leaf_pinned and cross the wire byte-identical, so
    # PPO ratios and GAE are untouched. Keep actor and learner values
    # aligned (the buffer tolerates either width at the door, but mixed
    # fleets forfeit the bandwidth win on the f32 side).
    rollout_wire_dtype: str = "float32"
    # A connection whose writer thread is still stuck sending when this
    # many NEWER publishes have been enqueued is declared over-budget and
    # dropped (counted in transport/fanout_conns_dropped) — a stalled actor
    # must never delay the learner or its peers.
    fanout_max_lag: int = 8
    # Shared-memory same-host lane (--transport shm): per-actor SPSC
    # rollout ring size and the seqlock'd weights slab size. The slab must
    # hold one encoded ModelWeights payload; rings drop-newest (counted)
    # when the learner falls behind.
    shm_slots: int = 16
    shm_ring_bytes: int = 8 * 1024 * 1024
    shm_weights_bytes: int = 32 * 1024 * 1024
    # Fault tolerance (ISSUE 4). Every wire frame carries a CRC32 trailer;
    # a peer that ships this many CONSECUTIVE corrupt frames is quarantined
    # (socket: connection cut; shm: slot never drained again until reaped)
    # instead of crashing a reader thread — one bit-flipping actor must not
    # take the learner down, and one flaky NIC must not poison the buffer.
    poison_frame_limit: int = 8
    # TCP-lane liveness, both directions: the learner's per-connection
    # writer interleaves heartbeat frames with the weights fanout at this
    # cadence (actors echo them), and either side drops/declares-dead a
    # connection with no inbound traffic for idle_timeout_s — a half-open
    # TCP connection (peer host died, NAT entry expired) can never wedge
    # the fleet. 0 disables the respective check. Keep idle_timeout_s
    # comfortably above BOTH heartbeat_interval_s and the actor's fixed
    # ~1s echo rate limit (actors echo liveness on inbound frames at most
    # once per second), or healthy peers get dropped as half-open.
    heartbeat_interval_s: float = 5.0
    idle_timeout_s: float = 30.0


@dataclasses.dataclass(frozen=True)
class LearnerConfig:
    """Learner-side execution knobs (ISSUE 5).

    ``async_snapshots`` routes every train-loop side effect that fetches
    device state — the weights publish, the periodic checkpoint, and the
    log-boundary metrics fetch — through the snapshot engine
    (train/snapshot.py): the train thread runs one cheap jitted on-device
    copy and dispatches the next step immediately; a background thread does
    the device→host transfer, the wire cast + encode, the fanout enqueue,
    and the orbax write. Published versions stay monotonic (latest-wins
    coalescing when the thread falls behind), the graceful-stop/forced
    checkpoint drains pending snapshots and lands at the exact stop step
    via the sync path, and async write failures surface through the
    ``checkpoint/save_failures_total`` degrade policy. Disable for
    debugging (``--sync-snapshots``): every side effect runs inline on the
    train thread, stalling it — the pre-ISSUE-5 behavior."""

    async_snapshots: bool = True
    # Upper bound on how long a graceful stop waits for the snapshot
    # thread to finish in-flight work before proceeding with the forced
    # sync checkpoint anyway (a wedged disk must not turn a drain into a
    # hang; the sync save then surfaces the real error loudly).
    snapshot_drain_timeout_s: float = 60.0
    # Compute-stage pipeline overlap (ISSUE 14, the OPPO observation):
    # with the one-pass advantage plane on, run batch N+1's advantage
    # pass on the prefetch lane — dispatch-only work enqueued behind
    # batch N's in-flight donated epoch step — instead of at consume
    # time. advantage/overlap_fraction measures how much of the pass's
    # host time actually hid behind a dispatch. False defers every pass
    # to consume time (the serial one-pass path; both give the same
    # advantages, tests/test_advantage.py). Which is faster: not measured
    # on chip (ROADMAP.md S1/D5).
    overlap_advantage: bool = True


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """Training health guardian (ISSUE 6): detect → contain → recover.

    Detection is a cheap in-graph probe fused into every train-step
    variant (``train/ppo.py`` adds a ``health_ok`` finiteness flag over
    loss and grad-norm to the step metrics; scanned multi-update programs
    AND-fold it), surfaced WITHOUT blocking the train thread: the
    ``HealthMonitor`` (train/health.py) accumulates the per-batch verdict
    scalars host-side and the snapshot engine fetches them in one batched
    transfer per boundary — ordered BEFORE the publish job, so a poisoned
    version can never reach the weights fanout. Containment: unhealthy
    state blocks weight publishes and periodic checkpoints (actors keep
    serving the last good version). Recovery: divergence rolls the
    TrainState back to the ``last_good`` checkpoint slot
    (utils/checkpoint.py) with a distinct minibatch-RNG stream, bounded by
    ``max_rollbacks`` before a loud exit."""

    enabled: bool = True
    # Host-side EMA of the (pre-clip) gradient global norm, updated on
    # healthy verdicts only; a verdict whose grad_norm exceeds
    # explosion_band × the EMA latches divergence even when every value is
    # still finite — the "loss exploded but has not NaN'd yet" band. The
    # EMA arms after warmup_steps healthy verdicts (early training swings
    # legitimately). Band is deliberately wide by default: the finiteness
    # probe is the primary tripwire; the band exists to catch runaway
    # growth before it saturates to inf.
    ema_alpha: float = 0.02
    explosion_band: float = 100.0
    warmup_steps: int = 50
    # Divergence rollbacks attempted (each restores last_good and resumes
    # with a DISTINCT minibatch-shuffle RNG stream) before the guardian
    # declares the run unrecoverable and exits non-zero with the runbook
    # message (docs/OPERATIONS.md "Failure modes").
    max_rollbacks: int = 3


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Low-latency policy-serving plane (ISSUE 11; dotaclient_tpu/serve).

    The serving workload is training inverted: many concurrent games each
    wanting ONE action at tight latency. The continuous-batching engine
    collects per-game step requests into preallocated staging lanes until
    ``batch_window_ms`` elapses or ``max_batch`` requests are staged
    (whichever first), runs ONE jitted dispatch over the padded batch with
    server-resident recurrent carries, and scatters sampled actions back
    per requester. These knobs trade latency (smaller window) against
    throughput (fuller batches); the curve is not measured on chip
    (ROADMAP.md S6/R6)."""

    # Batch-collection deadline in milliseconds. 0 dispatches whatever is
    # pending immediately (minimum latency, worst batching).
    batch_window_ms: float = 2.0
    # Requests per dispatch (the padded batch's static shape — changing it
    # recompiles the serve step). A window closes early when it fills.
    max_batch: int = 64
    # Server-resident carry slots = concurrently attached games. A slot is
    # allocated at client attach, zeroed on release, and reused; clients
    # never ship recurrent state.
    max_slots: int = 256
    # Request wire dtype ("float32" | "bfloat16"): bf16 narrows the
    # request's observation leaves via the rollout cast-plan machinery
    # (ISSUE 7) — the same ``__wire_cast__`` marker discipline, roughly
    # half the request bytes. Replies (a few ints + one float) stay f32.
    request_wire_dtype: str = "float32"
    # Weight-swap subscription cadence: the serve server's weights thread
    # polls its fanout subscription (socket or shm lane) this often; a new
    # version hot-swaps BETWEEN dispatches, never within one.
    weights_poll_s: float = 0.5
    # Base seed of the serve-side sampling RNG stream: dispatch i samples
    # with fold_in(key(seed), i) — the stream the parity digest replays.
    seed: int = 0
    # -- serve-fleet failover (ISSUE 19) ---------------------------------
    # Per-request deadline budget in seconds: every ServeClient.step()
    # resolves to an action or a typed ServeDeadlineError within this
    # budget — reconnects, router redirects, and retries all spend from
    # it. A dead backend is a bounded deadline miss, never a hang.
    request_deadline_s: float = 10.0
    # Bounded resend attempts per request inside the deadline budget (the
    # actor-contract retry discipline: backoff between attempts, SIGTERM
    # honored within one segment via should_abort).
    request_retries: int = 4
    # Router→backend liveness probe cadence: one persistent probe
    # connection per backend (it holds one carry slot), heartbeat frames
    # at this interval — a SIGKILL'd backend surfaces as EOF within one
    # probe turn.
    router_probe_s: float = 1.0
    # Grace window before a probe-lost backend is declared DEAD and its
    # sessions re-home (a transient reconnect inside the window is not a
    # death). Keep > one probe turn to ride out GC/compile pauses.
    router_dead_after_s: float = 3.0
    # Opt-in carry-shadow mode: replies carry the updated recurrent carry
    # row back to the client (narrowed by request_wire_dtype like every
    # other leaf — bit-exact at the default f32 wire), and a re-homed
    # session resends its stashed row so it resumes bit-exact on the new
    # backend. Off: a re-home resets the carry to zeros (the
    # reset_recurrent discipline) and is counted.
    carry_shadow: bool = False


@dataclasses.dataclass(frozen=True)
class LeagueConfig:
    enabled: bool = False
    pool_size: int = 8
    snapshot_every: int = 200      # learner steps between opponent snapshots
    selfplay_prob: float = 0.5     # chance of facing the latest policy
    # Snapshot matchmaking: "uniform" | "pfsp" (prioritized fictitious
    # self-play — weight (1-winrate)^pfsp_power, replay hard opponents).
    matchmaking: str = "pfsp"
    pfsp_power: float = 2.0
    # Optimizer steps a drawn opponent is held before redrawing: episodes
    # span many rollout chunks, so holding keeps most of an episode against
    # ONE opponent — the per-chunk outcome attribution PFSP feeds on stays
    # meaningful, and lanes stop seeing mid-episode opponent swaps.
    opponent_hold: int = 64
    # Scripted-anchor games (AlphaStar-style league exploiters, simplified):
    # this fraction of the device actor's games pins the opponent side to a
    # scripted bot instead of a pool snapshot. Pure self-play pools can
    # converge to metas where nobody pressures towers (BASELINE.md "5v5
    # farming equilibrium"); anchors keep fight/push behavior in the
    # training distribution. Anchor outcomes are excluded from PFSP stats.
    anchor_prob: float = 0.0
    # "scripted_easy" | "scripted_hard" | "mixed". Measured (BASELINE.md 30k
    # league run): anchoring only vs hard improved the hard-bot eval but
    # collapsed the easy-bot eval — the meta only covers strategies in the
    # anchor distribution.
    anchor_opponent: str = "scripted_hard"
    # "mixed" only: fraction of anchor games played vs scripted_easy (the
    # rest vs scripted_hard), easy rounding up. The 10k mixed-anchor run
    # (BASELINE.md) showed 12.5% easy games does not fully offset the shaped
    # reward's farming pull on the easy-bot eval — this is the knob to raise.
    anchor_easy_share: float = 0.5


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Top-level config tree."""

    obs: ObsSpec = ObsSpec()
    actions: ActionSpec = ActionSpec()
    model: ModelConfig = ModelConfig()
    ppo: PPOConfig = PPOConfig()
    env: EnvConfig = EnvConfig()
    reward: RewardConfig = RewardConfig()
    mesh: MeshConfig = MeshConfig()
    buffer: BufferConfig = BufferConfig()
    transport: TransportConfig = TransportConfig()
    learner: LearnerConfig = LearnerConfig()
    health: HealthConfig = HealthConfig()
    serve: ServeConfig = ServeConfig()
    league: LeagueConfig = LeagueConfig()
    checkpoint_dir: str = "checkpoints"
    checkpoint_every: int = 100
    # Best-model tracking: whenever the windowed win-rate at a log boundary
    # beats the best seen so far (and the window holds at least this many
    # episodes — the noise guard), a weights-only checkpoint is saved to
    # `<checkpoint_dir>/best` (its own max_to_keep=1 rotation). Motivated by
    # the measured 5v5 fine-tune trajectory that peaked at 0.714 mid-run and
    # ended at 0.16 — the peak policy otherwise rotates out of the periodic
    # checkpoints (BASELINE.md). 0 disables.
    checkpoint_best_min_episodes: int = 50
    # Fused-mode dispatch batching: lax.scan this many rollout+update
    # iterations inside the ONE jitted fused program, so each host dispatch
    # advances K optimizer steps. The per-dispatch host round trip is the
    # fused path's floor; K>1 amortizes it. Trade-offs: the league opponent
    # draw and all host-side cadences (logging, eval, snapshots, best-model
    # capture) coarsen to K-step granularity. Fused mode only; other actors
    # reject K>1.
    steps_per_dispatch: int = 1
    log_every: int = 10
    seed: int = 0

    def replace(self, **kwargs: Any) -> "RunConfig":
        return dataclasses.replace(self, **kwargs)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "RunConfig":
        raw = json.loads(text)
        return cls(
            obs=ObsSpec(**raw["obs"]),
            actions=ActionSpec(**raw["actions"]),
            model=ModelConfig(**raw["model"]),
            ppo=PPOConfig(**raw["ppo"]),
            env=EnvConfig(**{**raw["env"], "hero_pool": tuple(raw["env"]["hero_pool"])}),
            # absent in checkpoints written before RewardConfig existed
            reward=RewardConfig(**raw.get("reward", {})),
            mesh=MeshConfig(**raw["mesh"]),
            buffer=BufferConfig(**raw["buffer"]),
            # .get: absent in checkpoints written before TransportConfig
            transport=TransportConfig(**raw.get("transport", {})),
            # .get: absent in checkpoints written before LearnerConfig
            learner=LearnerConfig(**raw.get("learner", {})),
            # .get: absent in checkpoints written before HealthConfig
            health=HealthConfig(**raw.get("health", {})),
            # .get: absent in checkpoints written before ServeConfig
            serve=ServeConfig(**raw.get("serve", {})),
            league=LeagueConfig(**raw["league"]),
            # .get: absent in checkpoints written before the field existed
            checkpoint_best_min_episodes=raw.get(
                "checkpoint_best_min_episodes",
                cls.checkpoint_best_min_episodes,
            ),
            steps_per_dispatch=raw.get(
                "steps_per_dispatch", cls.steps_per_dispatch
            ),
            **{k: raw[k] for k in ("checkpoint_dir", "checkpoint_every", "log_every", "seed")},
        )


def default_config() -> RunConfig:
    return RunConfig()

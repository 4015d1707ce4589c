"""Inference-only policy path: the slim param tree the serving plane runs.

Training carries state serving never needs: the value head (PPO's critic),
the optimizer moments, the step/version counters. The serve plane runs
``models.policy.Policy`` with ``value_head=False`` — the SAME trunk, core,
and action-head modules, so logits are bit-identical to the training policy
by construction — over a param tree that is exactly the training tree minus
``head_value``.

Two sources restore into that slim tree, and must agree bit-for-bit
(pinned by tests/test_serve.py's round-trip test):

* a **training checkpoint** (``load_inference_params``): the orbax
  weights-only restore (integrity-manifest verified, walk-back on
  corruption — utils/checkpoint.py) followed by the slice;
* a **published weights frame** (``weights_frame_to_params``): the
  ``ModelWeights`` proto the snapshot engine fans out to actors, decoded
  (bf16 wire leaves upcast exactly) and sliced — the path a live serve
  server's weight-swap subscription takes on every refresh.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from dotaclient_tpu.config import RunConfig
from dotaclient_tpu.models.policy import Policy, require_routed_ffn

# Top-level param-tree entries that exist only for training. The slice is
# name-based (not shape-based) so a future training-only head lands here
# once instead of silently riding into every serve tree.
TRAIN_ONLY_PARAM_KEYS = ("head_value",)


def make_inference_policy(config: RunConfig) -> Policy:
    """The serving-plane policy module: identical architecture, no value
    head (``value_head=False``), so it applies the sliced tree directly."""
    require_routed_ffn(config.model)
    return Policy(
        model=config.model,
        obs_spec=config.obs,
        action_spec=config.actions,
        value_head=False,
    )


def slice_train_params(params: Any) -> Dict[str, Any]:
    """Training param tree → inference-only tree (drop the value head).

    Accepts the variables dict (``{"params": {...}}``) or a bare params
    level and returns the same nesting it was given; unknown layouts fail
    loudly rather than serving a tree the slim module would reject."""
    if not isinstance(params, dict):
        raise TypeError(
            f"expected a param dict, got {type(params).__name__}"
        )
    if "params" in params:
        out = dict(params)
        out["params"] = slice_train_params(params["params"])
        return out
    return {
        k: v for k, v in params.items() if k not in TRAIN_ONLY_PARAM_KEYS
    }


def load_inference_params(checkpoint_dir: str) -> Tuple[RunConfig, Dict[str, Any], int]:
    """Restore a training checkpoint into the slim tree.

    Returns ``(config, sliced params, step)`` — the checkpoint's OWN config
    is authoritative for the model tree (guessing one risks a template
    mismatch), and the step doubles as the serve plane's starting weights
    version (the snapshot engine publishes version=step-aligned counters,
    so a later fanout frame with a higher version supersedes it)."""
    from dotaclient_tpu.utils.checkpoint import CheckpointManager

    mgr = CheckpointManager(checkpoint_dir)
    try:
        config = mgr.restore_config()
        params, step = mgr.restore_weights()
    finally:
        mgr.close()
    return config, slice_train_params(params), int(step)


def weights_frame_to_params(msg: Any) -> Tuple[int, Dict[str, Any]]:
    """A published ``ModelWeights`` frame → ``(version, sliced params)``.

    ``decode_weights`` upcasts bf16 wire leaves to f32 exactly (the
    lossless inverse of the fanout's ``wire_dtype`` cast), so the result is
    bit-identical to slicing the learner-side host params the frame was
    encoded from."""
    from dotaclient_tpu.transport.serialize import decode_weights

    version, tree = decode_weights(msg)
    return version, slice_train_params(tree)

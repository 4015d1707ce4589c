"""Pallas (Mosaic) TPU kernels.

``kda_step``: one step of a delta-rule linear-attention layer, a head's
state read once and written once; called by ``models/kimilinear.py`` for a
rollout step on a TPU. ``grouped_matmul``: a routed layer's grouped
products and their gradients, a grid that follows the group sizes; called by
``models/afmoe.py RoutedExperts`` on a TPU. ``ring_attend``: a rollout pass's
attention against its lanes' rings, a grid of the ring blocks each lane
sees; called by ``models/sdar.py BlockAttention`` on a TPU. ``lstm``: the LSTM over a whole sequence in one call;
called by nothing in the package (``models/lstm.py`` is the LSTM's path),
kept with its tests and ``chip_smoke.py`` phase e as the alternative for
wider cores. Nothing here looks at the backend: every entry point takes
``interpret=`` from its caller.
"""

from dotaclient_tpu.ops.pallas.kda_step import kda_step_pallas
from dotaclient_tpu.ops.pallas.lstm import (
    lstm_sequence_pallas,
    lstm_sequence_reference,
)

__all__ = [
    "kda_step_pallas",
    "lstm_sequence_pallas",
    "lstm_sequence_reference",
]

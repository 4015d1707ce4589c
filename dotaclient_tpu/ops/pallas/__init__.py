"""Pallas (Mosaic) TPU kernels."""

from dotaclient_tpu.ops.pallas.lstm import (
    lstm_sequence_pallas,
    lstm_sequence_reference,
)

__all__ = [
    "lstm_sequence_pallas",
    "lstm_sequence_reference",
]

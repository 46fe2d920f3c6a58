"""Work counts from the model's semantics, not from what today's code
moves: an existing synapse is one u8 register and one multiply-add (2
FLOP) per tick; a neuron's state is its membrane (read and written, 4
bytes each way) per tick.  Padding, the f32 copy of ``W``, the ``C``
operand and silent slots do not count, so a program that stores weights
as u8, skips padding or goes event-driven raises its share and cannot
push it past 100%.
"""
from __future__ import annotations

from typing import Dict

SYNAPSE_BYTES = 1          # one u8 weight register
NEURON_BYTES = 8           # membrane read + membrane write, f32
FLOP_PER_SYNAPSE = 2       # multiply + add


def tick_flops(synapses: int) -> float:
    return float(FLOP_PER_SYNAPSE * synapses)


def tick_bytes(synapses: int, neurons: int) -> float:
    return float(SYNAPSE_BYTES * synapses + NEURON_BYTES * neurons)


def least_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak HBM bandwidth."""
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])


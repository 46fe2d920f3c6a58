"""Work counts of the Potjans-Diesmann microcircuit, from the model's
semantics: a synaptic event (a spiking source's real fan-out entry) is
one multiply-add (2 FLOP), one read of its entry at the byte sizes the
configuration states (``entry_bytes``: target, weight, delay) and one
read-modify-write of a ring cell (4 bytes each way); a neuron's state
(``V``, ``I``, refractory counter, 4 bytes each) is read and written
once per tick, with 6 FLOP of ``iaf_psc_exp`` update (``P22 V + P21 I
+ P20 I_e`` and ``P11 I + x``).  Padding entries and empty read slots
do not count.
"""
from __future__ import annotations

from typing import Dict

FLOP_PER_EVENT = 2
RING_RMW_BYTES = 8
NEURON_FLOP = 6
NEURON_STATE_BYTES = 24


def event_bytes(entry_bytes: Dict[str, int]) -> int:
    return int(sum(entry_bytes.values())) + RING_RMW_BYTES


def delivery_flops(events: float) -> float:
    return float(FLOP_PER_EVENT * events)


def delivery_bytes(events: float, entry_bytes: Dict[str, int]) -> float:
    return float(events * event_bytes(entry_bytes))


def step_flops(events: float, neuron_ticks: float) -> float:
    """The model's operations: delivery plus the neuron update."""
    return delivery_flops(events) + float(NEURON_FLOP * neuron_ticks)


def step_bytes(events: float, neuron_ticks: float,
               entry_bytes: Dict[str, int]) -> float:
    """The model's memory traffic: delivery plus each neuron's state."""
    return delivery_bytes(events, entry_bytes) + float(
        NEURON_STATE_BYTES * neuron_ticks)

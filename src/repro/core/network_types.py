"""Parameter/state pytrees shared by the tick engine and its wrappers.

Split out of :mod:`repro.core.network` so that :mod:`repro.core.engine`
(which *implements* the tick) and :mod:`repro.core.network` (which
exposes the user-facing rollout wrappers) can both import them without a
cycle. Everything here is re-exported from ``repro.core.network`` --
existing callers never see the split.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.lif import LIFParams, LIFState


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SNNParams:
    """Network parameters (all runtime inputs -- never compiled constants).

    Attributes:
      w: synaptic weights, shape ``(n, n)``; ``w[pre, post]``.
      c: connection list, shape ``(n, n)`` bool/0-1; ``c[pre, post]``.
        ``None`` means the implicit all-to-all (every mux closed): the
        effective matrix is ``w`` itself and no second ``(n, n)`` buffer
        exists -- the 64k-fabric memory escape hatch (jnp/event backends
        only; the Pallas kernels stream ``c`` explicitly).
      w_in: input weights, shape ``(n_in, n)`` mapping external channels
        onto neurons (identity for the paper's networks where inputs drive
        input-layer neurons directly).
      lif: per-neuron :class:`LIFParams`.
      drive: optional :class:`PoissonDrive`, the on-device background
        drive of the event backend's ``fan_out`` strategy; None (the
        leaf vanishes) everywhere else.  A fan-out fabric has no dense
        ``w``: it passes ``w=None, c=None``.
    """

    w: Optional[jax.Array]
    c: Optional[jax.Array]
    w_in: jax.Array
    lif: LIFParams
    drive: Optional["PoissonDrive"] = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PoissonDrive:
    """Independent Poisson background input, drawn inside the tick.

    Tick ``t`` adds ``poisson(fold_in(key, t), lam) * weight`` to every
    neuron's arriving input -- the elementwise form of the diagonal
    drive (``event_ext_diag``), with the counts made on the device from
    the key and the absolute tick, so the host sends nothing per tick and
    anyone holding the key can draw the same counts.

    Attributes:
      key: raw ``uint32[2]`` PRNG key.
      lam: ``(n,)`` float32 mean events per neuron per tick.
      weight: ``(n,)`` float32 input per event.
    """

    key: jax.Array
    lam: jax.Array
    weight: jax.Array

    def counts(self, tick: jax.Array) -> jax.Array:
        """This tick's ``(n,)`` int32 event counts."""
        return jax.random.poisson(jax.random.fold_in(self.key, tick),
                                  self.lam, dtype=jnp.int32)

    def input(self, tick: jax.Array) -> jax.Array:
        """This tick's ``(n,)`` drive, ``counts * weight``."""
        return self.counts(tick).astype(self.weight.dtype) * self.weight


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SNNState:
    """Rollout state: LIF state + circular delay line.

    ``delay_buf`` has shape ``(..., max_delay, n)``; slot ``(k % max_delay)``
    holds the spikes scheduled to arrive at tick ``k``. ``max_delay == 1``
    (the hardware default) degenerates to plain previous-tick delivery.

    Under the event backend's ``fan_out`` strategy the same array is the
    *postsynaptic* (dendritic) ring: row ``k % max_delay`` holds the
    summed weights arriving at each neuron at tick ``k``.  Tick ``t``
    reads and clears row ``t``, and each spiking source adds its fan-out
    weights at rows ``(t + d) % max_delay`` -- per-synapse delays
    ``1 <= d <= max_delay`` at a cost of spikes x fan-out.
    """

    lif: LIFState
    delay_buf: jax.Array
    tick: jax.Array

    @staticmethod
    def zeros(batch_shape, n: int, max_delay: int = 1, dtype=jnp.float32,
              current: bool = False) -> "SNNState":
        """``current=True`` adds the ``psc_exp`` synaptic current."""
        return SNNState(
            lif=LIFState.zeros(batch_shape, n, dtype=dtype, current=current),
            delay_buf=jnp.zeros(tuple(batch_shape) + (max_delay, n), dtype=dtype),
            tick=jnp.zeros((), dtype=jnp.int32),
        )


def synaptic_input(
    spikes: jax.Array, params: SNNParams, ext: Optional[jax.Array]
) -> jax.Array:
    """``sum_pre s[pre] * W[pre,post] * C[pre,post] (+ ext @ W_in)``.

    The masked matmul *is* the mux fabric: C routes a zero exactly where the
    hardware's multiplexer would (``c=None``: every mux closed, ``wc = w``).
    """
    wc = (params.w if params.c is None
          else params.w * params.c.astype(params.w.dtype))
    syn = spikes @ wc
    if ext is not None:
        syn = syn + ext @ params.w_in
    return syn

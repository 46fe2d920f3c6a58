"""On-device tick telemetry: carry-resident reductions inside the scan.

The paper pitches the processor as a research platform with runtime
visibility into the live fabric (spike activity, membrane state over the
UART link). :class:`TickTelemetry` is that visibility for the TPU
restatement: a small pytree of per-rollout accumulators that rides the
:class:`~repro.core.engine.TickCarry` when the engine's static
``telemetry=True`` flag is set.

Design constraints (all pinned in tests/test_obs.py):

* **Zero cost when off.** Telemetry is gated by a *static* engine flag
  and an optional carry slot (``None`` leaves vanish from the pytree),
  so ``telemetry=False`` programs lower to HLO byte-identical to the
  pre-observability engine.

* **Reductions only, no host syncs.** Every update is a per-tick
  reduction over the neuron axis into batch-shaped accumulators; the
  scan never materializes a per-tick series and never leaves the device.

* **vmap-transparent.** Accumulators keep the state's batch shape, so
  the multi-tenant server's slot vmap yields per-slot (= per-tenant)
  telemetry with no extra code.

The numbers come off-device exactly once, at :meth:`TickTelemetry.summary`.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TickTelemetry:
    """Per-rollout accumulators; every leaf is batch-shaped ``(...,)``.

    Attributes:
      ticks: ticks accumulated so far (i32).
      spikes: total spikes emitted (``sum_t sum_n y``) -- equals
        ``raster.sum()`` of the same rollout, pinned in tests.
      v_sum: sum over ticks of the mean membrane potential (divide by
        ``ticks`` for the time-averaged mean).
      v_max: running max membrane potential observed after any tick.
      ref_sum: sum over ticks of the refractory-occupancy fraction
        (``mean_n 1{r > 0}``); divide by ``ticks`` for mean occupancy.
      overflow: event-backend overflow ticks -- ticks whose spike count
        exceeded ``k_active`` and took the dense fallback (always 0 for
        dense backends and the event fan-in gather path).  These are
        *correctness* fallbacks: without them spikes would be dropped.
      policy_dense: event-backend *policy* ticks -- ticks the adaptive
        knee routed to the dense arm purely for speed (spike count above
        the knee but within ``k_active``; the event arm would have been
        exact too).  Disjoint from ``overflow`` by construction.
      dw_l1: accumulated ``sum |dw|`` from the plasticity hook (0 when
        frozen) -- the L1 norm of the whole weight-update stream.
      dw_sq: accumulated ``sum dw^2``; ``sqrt`` of it is the L2 norm of
        the update stream.
      syn_events: event backend ``fan_out`` strategy only (None
        elsewhere, so the leaf vanishes): fan-out entries delivered into
        the delay ring, padding excluded.
      spill_blocks: ``fan_out`` only: blocks of row reads run beyond the
        first of a tick (a tick whose spiking sources need more reads than
        the static budget runs further blocks; no spike is ever dropped).
      pop_spikes: ``fan_out`` only: ``(..., n_pops)`` spikes emitted per
        population.
    """

    ticks: jax.Array
    spikes: jax.Array
    v_sum: jax.Array
    v_max: jax.Array
    ref_sum: jax.Array
    overflow: jax.Array
    policy_dense: jax.Array
    dw_l1: jax.Array
    dw_sq: jax.Array
    syn_events: Optional[jax.Array] = None
    spill_blocks: Optional[jax.Array] = None
    pop_spikes: Optional[jax.Array] = None

    @staticmethod
    def zeros(batch_shape=(), n_pops: Optional[int] = None,
              ) -> "TickTelemetry":
        """``n_pops`` adds the ``fan_out`` counters."""
        shape = tuple(batch_shape)
        f = lambda: jnp.zeros(shape, jnp.float32)
        i = lambda: jnp.zeros(shape, jnp.int32)
        fan = n_pops is not None
        return TickTelemetry(
            ticks=jnp.zeros(shape, jnp.int32), spikes=f(), v_sum=f(),
            v_max=f(), ref_sum=f(), overflow=i(), policy_dense=i(),
            dw_l1=f(), dw_sq=f(),
            syn_events=f() if fan else None,
            spill_blocks=i() if fan else None,
            pop_spikes=jnp.zeros(shape + (n_pops,), jnp.float32)
            if fan else None)

    def accumulate(
        self,
        lif_state,
        *,
        overflow_inc: Optional[jax.Array] = None,
        policy_inc: Optional[jax.Array] = None,
        fan_out_inc: Optional[tuple] = None,
    ) -> "TickTelemetry":
        """Fold one tick's outputs in (pure reductions over the neuron axis).

        Args:
          lif_state: the post-tick :class:`~repro.core.lif.LIFState`.
          overflow_inc: optional batch-shaped i32 increment (event backend:
            1 on ticks that overflowed ``k_active`` into the dense fallback).
          policy_inc: optional batch-shaped i32 increment (event backend:
            1 on ticks the adaptive knee routed to the dense arm for speed
            -- counted separately from ``overflow_inc``).
          fan_out_inc: optional ``(syn_events, spill_blocks, pop_spikes)``
            increments of the ``fan_out`` counters.

        The plasticity hook's weight delta folds in separately, through
        :meth:`fold_dw`, on the ticks where the hook runs.
        """
        y, v, r = lif_state.y, lif_state.v, lif_state.r
        n = y.shape[-1]
        # One variadic reduce for all four neuron-axis statistics: a
        # single kernel per tick instead of four (the scan body's per-op
        # dispatch is the telemetry overhead the bench gate watches, not
        # the arithmetic).
        zero = jnp.zeros((), jnp.float32)
        ninf = jnp.asarray(-jnp.inf, jnp.float32)
        s_y, s_v, m_v, s_r = jax.lax.reduce(
            (y.astype(jnp.float32), v.astype(jnp.float32),
             v.astype(jnp.float32), (r > 0).astype(jnp.float32)),
            (zero, zero, ninf, zero),
            lambda a, b: (a[0] + b[0], a[1] + b[1],
                          jnp.maximum(a[2], b[2]), a[3] + b[3]),
            (y.ndim - 1,))
        overflow = self.overflow
        if overflow_inc is not None:
            overflow = overflow + overflow_inc
        policy_dense = self.policy_dense
        if policy_inc is not None:
            policy_dense = policy_dense + policy_inc
        out = TickTelemetry(
            ticks=self.ticks + 1,
            spikes=self.spikes + s_y,
            v_sum=self.v_sum + s_v / n,
            v_max=jnp.maximum(self.v_max, m_v),
            ref_sum=self.ref_sum + s_r / n,
            overflow=overflow,
            policy_dense=policy_dense,
            dw_l1=self.dw_l1,
            dw_sq=self.dw_sq,
            syn_events=self.syn_events,
            spill_blocks=self.spill_blocks,
            pop_spikes=self.pop_spikes)
        if fan_out_inc is not None:
            syn, spill, pops = fan_out_inc
            out = dataclasses.replace(
                out, syn_events=self.syn_events + syn,
                spill_blocks=self.spill_blocks + spill,
                pop_spikes=self.pop_spikes + pops)
        return out

    def fold_dw(self, dw: jax.Array) -> "TickTelemetry":
        """Fold one plasticity tick's committed weight delta
        ``w_new - w_old`` in (any shape; reduced to scalars)."""
        return dataclasses.replace(
            self,
            dw_l1=self.dw_l1 + jnp.abs(dw).sum(),
            dw_sq=self.dw_sq + jnp.square(dw).sum())

    # -- host-side readout -------------------------------------------------

    def summary(self, n: int) -> Dict[str, float]:
        """Reduce to host floats (the one device->host hop).

        Args:
          n: live neuron count, for the spike-rate normalization
            (``spikes / (ticks * n)`` -- mean spikes per neuron per tick).
        """
        import numpy as np

        leaf = lambda a: np.asarray(a)
        ticks = float(leaf(self.ticks).max()) if leaf(self.ticks).size else 0.0
        spikes = float(leaf(self.spikes).sum())
        batch = max(1, int(leaf(self.spikes).size))
        denom = max(1.0, ticks * n * batch)
        out = {
            "ticks": ticks,
            "spikes": spikes,
            "spike_rate": spikes / denom,
            "v_mean": float(leaf(self.v_sum).mean()) / max(1.0, ticks),
            "v_max": float(leaf(self.v_max).max()),
            "refractory_occupancy":
                float(leaf(self.ref_sum).mean()) / max(1.0, ticks),
            "overflow_ticks": float(leaf(self.overflow).sum()),
            "policy_dense_ticks": float(leaf(self.policy_dense).sum()),
            "dw_l1": float(leaf(self.dw_l1).sum()),
            "dw_l2": float(np.sqrt(leaf(self.dw_sq).sum())),
        }
        if self.syn_events is not None:
            out["syn_events"] = float(leaf(self.syn_events).sum())
            out["spill_blocks"] = float(leaf(self.spill_blocks).sum())
            out["pop_spikes"] = leaf(self.pop_spikes).reshape(
                -1, self.pop_spikes.shape[-1]).sum(axis=0).tolist()
        return out

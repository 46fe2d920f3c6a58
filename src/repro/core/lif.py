"""Discrete-time leaky integrate-and-fire (LIF) neuron dynamics.

Implements the paper's two formulations exactly:

* **Euler model** (paper Eq. 1-4): membrane decays by a factor
  ``(1 - dt/tau_m)`` each tick and integrates ``dt/C_m * (w.s + I_bias)``.

* **Fixed-leak hardware realization** (paper Eq. 5): the leak is a constant
  decrement ``lambda`` applied only while the membrane is non-zero,
  ``v' = v + sum_j w_j s_j - lambda * 1{v != 0}``,
  followed by the same threshold / reset / refractory logic.

Both are pure functions over a :class:`LIFState`, vectorised over arbitrary
leading (batch) dimensions, and differentiable through the surrogate spike
function (:mod:`repro.core.surrogate`).

The integer mode mirrors the FPGA datapath: u8 weights (0-255), i32
accumulation, integer thresholds -- bit-exact with the register-bank
contents (:mod:`repro.core.registers`).

The ``psc_exp`` mode is NEST's ``iaf_psc_exp`` (current-based LIF with an
exponentially decaying synaptic current as a second state variable),
integrated exactly with propagators computed once in float64 on the host
(:meth:`LIFParams.psc_exp`); it is what the Potjans-Diesmann cortical
microcircuit runs on.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.surrogate import spike_surrogate


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LIFParams:
    """Neuron parameters, one entry per neuron (shape ``(n,)`` or scalar).

    Attributes:
      v_th: firing threshold ``V_th``.
      leak: Euler mode: ``dt/tau_m`` (decay fraction per tick).
            Fixed-leak mode: the per-tick decrement ``lambda``.
      r_ref: refractory length ``R_ref`` in ticks.
      gain: Euler mode input gain ``dt/C_m``; unused (1.0) in fixed-leak mode.
      i_bias: tonic bias current ``I_bias``.
      v_reset: reset potential (paper resets to 0).
      syn_decay: ``psc_exp`` mode only: the synaptic current's decay per
        tick ``P11 = exp(-h/tau_syn)``; None for every other mode (the
        leaf then vanishes, so their pytrees are unchanged).

    In ``psc_exp`` mode the membrane is held relative to ``E_L`` and
    ``leak`` is the membrane propagator ``P22 = exp(-h/tau_m)``, ``gain``
    the current-to-membrane propagator ``P21`` and ``i_bias`` the
    per-tick constant drive ``P20 * I_e`` (see :meth:`psc_exp`).
    """

    v_th: jax.Array
    leak: jax.Array
    r_ref: jax.Array
    gain: jax.Array
    i_bias: jax.Array
    v_reset: jax.Array
    syn_decay: Optional[jax.Array] = None

    @staticmethod
    def make(
        n: int,
        *,
        v_th: float = 1.0,
        leak: float = 0.0,
        r_ref: int = 0,
        gain: float = 1.0,
        i_bias: float = 0.0,
        v_reset: float = 0.0,
        dtype=jnp.float32,
    ) -> "LIFParams":
        full = lambda v: jnp.full((n,), v, dtype=dtype)
        return LIFParams(
            v_th=full(v_th),
            leak=full(leak),
            r_ref=jnp.full((n,), r_ref, dtype=jnp.int32),
            gain=full(gain),
            i_bias=full(i_bias),
            v_reset=full(v_reset),
        )

    @staticmethod
    def psc_exp(
        n: int,
        *,
        c_m: float,
        tau_m: float,
        tau_syn: float,
        t_ref: float,
        e_l: float,
        v_th: float,
        v_reset: float,
        dt: float,
        i_e: float = 0.0,
    ) -> "LIFParams":
        """NEST ``iaf_psc_exp`` parameters (pF, ms, mV, pA) as exact-
        integration propagators over one tick of ``dt`` ms.

        The propagators are computed in float64 and held as float32;
        voltages are relative to ``e_l`` (threshold ``v_th - e_l``, reset
        ``v_reset - e_l``).  ``t_ref`` becomes ``round(t_ref / dt)``
        ticks, NEST's refractory count."""
        p11, p22, p21, p20 = psc_exp_propagators(
            c_m=c_m, tau_m=tau_m, tau_syn=tau_syn, dt=dt)
        full = lambda v: jnp.full((n,), v, dtype=jnp.float32)
        return LIFParams(
            v_th=full(v_th - e_l),
            leak=full(p22),
            r_ref=jnp.full((n,), round(t_ref / dt), dtype=jnp.int32),
            gain=full(p21),
            i_bias=full(p20 * i_e),
            v_reset=full(v_reset - e_l),
            syn_decay=full(p11),
        )


def psc_exp_propagators(*, c_m: float, tau_m: float, tau_syn: float,
                        dt: float):
    """``(P11, P22, P21, P20)`` of NEST's ``iaf_psc_exp`` in float64.

    ``P21`` is NEST's ``propagator_32`` for ``tau_syn != tau_m``:
    ``tau_m tau_syn / (C_m (tau_m - tau_syn)) * (exp(-h/tau_m) -
    exp(-h/tau_syn))``."""
    if tau_syn == tau_m:
        raise ValueError("psc_exp needs tau_syn != tau_m")
    p11 = math.exp(-dt / tau_syn)
    p22 = math.exp(-dt / tau_m)
    p21 = (tau_m * tau_syn / (c_m * (tau_m - tau_syn))
           * (math.expm1(-dt / tau_m) - math.expm1(-dt / tau_syn)))
    p20 = tau_m / c_m * -math.expm1(-dt / tau_m)
    return p11, p22, p21, p20


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LIFState:
    """Dynamic neuron state with arbitrary leading batch dims.

    Attributes:
      v: membrane potential ``v[k]``, shape ``(..., n)``.
      r: refractory counter ``r[k]`` (ticks remaining), shape ``(..., n)``.
      y: output spikes from the previous tick, shape ``(..., n)``.
      i: ``psc_exp`` mode only: the synaptic current, shape ``(..., n)``;
        None for every other mode.
    """

    v: jax.Array
    r: jax.Array
    y: jax.Array
    i: Optional[jax.Array] = None

    @staticmethod
    def zeros(batch_shape, n: int, dtype=jnp.float32,
              current: bool = False) -> "LIFState":
        """``current=True`` adds the ``psc_exp`` synaptic current."""
        shape = tuple(batch_shape) + (n,)
        return LIFState(
            v=jnp.zeros(shape, dtype=dtype),
            r=jnp.zeros(shape, dtype=jnp.int32),
            y=jnp.zeros(shape, dtype=dtype),
            i=jnp.zeros(shape, dtype=dtype) if current else None,
        )


def _threshold_reset_refractory(
    v_tilde: jax.Array,
    state: LIFState,
    params: LIFParams,
    *,
    surrogate: bool,
    reset: str = "zero",
) -> LIFState:
    """Paper Eq. 2-4: spike, reset, refractory-counter update (shared).

    ``reset``: "zero" (paper Eq. 3: v -> v_reset) or "subtract"
    (v -> v - V_th on spike; the standard rate-coding-exact hardware
    variant -- one line of HDL -- used by the classifier readout; see
    EXPERIMENTS.md §Iris for the deviation note).
    """
    not_refractory = (state.r == 0)
    if surrogate:
        y_soft = spike_surrogate(v_tilde - params.v_th)
        y = y_soft * not_refractory.astype(v_tilde.dtype)
    else:
        y = ((v_tilde >= params.v_th) & not_refractory).astype(v_tilde.dtype)
    spiked = y > 0
    if reset == "subtract":
        v_after = v_tilde - params.v_th.astype(v_tilde.dtype)
        v_new = jnp.where(spiked, v_after, v_tilde)
        v_new = jnp.where(state.r > 0, params.v_reset.astype(v_tilde.dtype), v_new)
    else:
        # Eq. 3: v resets if the neuron spiked OR it is still refractory.
        hold = spiked | (state.r > 0)
        v_new = jnp.where(hold, params.v_reset.astype(v_tilde.dtype), v_tilde)
    # Eq. 4: reload the counter on spike, else count down to zero.
    r_new = jnp.where(spiked, params.r_ref, jnp.maximum(state.r - 1, 0))
    return LIFState(v=v_new, r=r_new, y=y)


def lif_step_euler(
    state: LIFState,
    syn_input: jax.Array,
    params: LIFParams,
    *,
    surrogate: bool = False,
    reset: str = "zero",
) -> LIFState:
    """One tick of the Euler LIF model (paper Eq. 1-4).

    Args:
      state: current :class:`LIFState`.
      syn_input: summed weighted synaptic drive ``sum_j w_j s_j[k]`` of shape
        ``(..., n)`` (the synaptic matmul happens outside, or fused in the
        Pallas kernel).
      params: :class:`LIFParams`.
      surrogate: use the differentiable surrogate spike (training).
    """
    decay = (1.0 - params.leak).astype(state.v.dtype)
    v_tilde = decay * state.v + params.gain * (syn_input + params.i_bias)
    return _threshold_reset_refractory(v_tilde, state, params,
                                       surrogate=surrogate, reset=reset)


def lif_step_fixed_leak(
    state: LIFState,
    syn_input: jax.Array,
    params: LIFParams,
    *,
    surrogate: bool = False,
    reset: str = "zero",
) -> LIFState:
    """One tick of the fixed-leak hardware model (paper Eq. 5).

    ``v' = v + sum_j w_j s_j - lambda * 1{v != 0}`` -- the leak is a constant
    decrement applied only to active (non-zero) membranes, exactly as the
    FPGA implements it. The decrement never drives ``v`` through zero from
    the leak alone (the hardware clamps at rest); we clamp the *leak
    contribution* the same way.
    """
    active = (state.v != 0).astype(state.v.dtype)
    leak_step = params.leak * active
    # Clamp: leak alone must not overshoot past the resting potential.
    leak_step = jnp.minimum(leak_step, jnp.abs(state.v))
    v_tilde = state.v + syn_input + params.i_bias - jnp.sign(state.v) * leak_step
    return _threshold_reset_refractory(v_tilde, state, params,
                                       surrogate=surrogate, reset=reset)


def lif_step_int(
    state: LIFState,
    syn_input: jax.Array,
    params: LIFParams,
    *,
    reset: str = "zero",
) -> LIFState:
    """Bit-faithful integer datapath (u8 weights, i32 accumulate).

    Mirrors the FPGA: all quantities are integers, the leak is the fixed
    decrement, and there is no surrogate (inference only).
    """
    v = state.v.astype(jnp.int32)
    syn = syn_input.astype(jnp.int32) + params.i_bias.astype(jnp.int32)
    leak = params.leak.astype(jnp.int32)
    active = (v != 0).astype(jnp.int32)
    leak_step = jnp.minimum(leak * active, jnp.abs(v))
    v_tilde = v + syn - jnp.sign(v) * leak_step
    not_refractory = state.r == 0
    th = params.v_th.astype(jnp.int32)
    spiked = (v_tilde >= th) & not_refractory
    y = spiked.astype(jnp.int32)
    if reset == "subtract":
        v_new = jnp.where(spiked, v_tilde - th, v_tilde)
        v_new = jnp.where(state.r > 0, params.v_reset.astype(jnp.int32), v_new)
    else:
        hold = spiked | (state.r > 0)
        v_new = jnp.where(hold, params.v_reset.astype(jnp.int32), v_tilde)
    r_new = jnp.where(spiked, params.r_ref, jnp.maximum(state.r - 1, 0))
    return LIFState(v=v_new, r=r_new, y=y)


def lif_step_psc_exp(
    state: LIFState,
    syn_input: jax.Array,
    params: LIFParams,
    *,
    surrogate: bool = False,
    reset: str = "zero",
) -> LIFState:
    """One tick of NEST's ``iaf_psc_exp``, in NEST's update order.

    The membrane integrates the current *before* this tick's arrivals;
    then the current decays and takes them (arrivals "at T+1 have an
    immediate effect on the state of the neuron")::

        v~ = P22 v + P21 i + P20 I_e        (held at reset while refractory)
        i' = P11 i + syn_input
    """
    if state.i is None or params.syn_decay is None:
        raise ValueError(
            "psc_exp needs the synaptic current in the state "
            "(LIFState.zeros(..., current=True)) and its decay in the "
            "parameters (LIFParams.psc_exp)")
    v_tilde = params.leak * state.v + params.gain * state.i + params.i_bias
    i_new = params.syn_decay * state.i + syn_input
    out = _threshold_reset_refractory(v_tilde, state, params,
                                      surrogate=surrogate, reset=reset)
    return dataclasses.replace(out, i=i_new)


def lif_step(
    state: LIFState,
    syn_input: jax.Array,
    params: LIFParams,
    *,
    mode: str = "fixed_leak",
    surrogate: bool = False,
    reset: str = "zero",
) -> LIFState:
    """Dispatch on the paper's two formulations (+ integer datapath and
    NEST's ``iaf_psc_exp``)."""
    if mode == "euler":
        return lif_step_euler(state, syn_input, params, surrogate=surrogate, reset=reset)
    if mode == "fixed_leak":
        return lif_step_fixed_leak(state, syn_input, params, surrogate=surrogate, reset=reset)
    if mode == "int":
        return lif_step_int(state, syn_input, params, reset=reset)
    if mode == "psc_exp":
        return lif_step_psc_exp(state, syn_input, params, surrogate=surrogate,
                                reset=reset)
    raise ValueError(f"unknown LIF mode: {mode!r}")

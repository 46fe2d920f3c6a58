"""Jit'd public wrappers for the Pallas kernels.

Handles: block padding/unpadding, backend selection (real TPU Pallas vs
interpret mode on CPU -- correctness-identical), dtype plumbing, and the
bridge to :mod:`repro.core` state dataclasses.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.lif import LIFState
from repro.kernels import lif_step as _lif_kernel
from repro.kernels import spike_matmul as _sm_kernel
from repro.kernels import stdp_update as _stdp_kernel
from repro.kernels import tick_fused as _tick_kernel
from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _pick_block(n: int, target: int, align: int) -> int:
    """Largest block <= target that keeps padded overhead small."""
    if n >= target:
        return target
    # round n up to alignment
    return max(align, -(-n // align) * align)


def spike_matmul(
    s: jax.Array,
    w: jax.Array,
    c: jax.Array,
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Padded, backend-selected ``s @ (w*c)``; returns (B, N) f32."""
    if interpret is None:
        interpret = not _on_tpu()
    B, K = s.shape
    N = w.shape[1]
    bb = _pick_block(B, _sm_kernel.DEFAULT_BLOCK_B, 8)
    bn = _pick_block(N, _sm_kernel.DEFAULT_BLOCK_N, 128)
    bk = _pick_block(K, _sm_kernel.DEFAULT_BLOCK_K, 128)
    s_p = _pad_to(_pad_to(s, 0, bb), 1, bk)
    w_p = _pad_to(_pad_to(w, 0, bk), 1, bn)
    c_p = _pad_to(_pad_to(c, 0, bk), 1, bn)
    out = _sm_kernel.spike_matmul(
        s_p, w_p, c_p, block_b=bb, block_n=bn, block_k=bk, interpret=interpret
    )
    return out[:B, :N]


def fused_lif_step_arrays(
    s: jax.Array,
    w: jax.Array,
    c: jax.Array,
    v: jax.Array,
    r: jax.Array,
    drive: Optional[jax.Array],
    v_th: jax.Array,
    leak: jax.Array,
    r_ref: jax.Array,
    gain: jax.Array,
    i_bias: jax.Array,
    v_reset: jax.Array,
    *,
    mode: str = "fixed_leak",
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Array-level fused tick with padding; see kernel docstring."""
    if interpret is None:
        interpret = not _on_tpu()
    B, K = s.shape
    N = w.shape[1]
    bb = _pick_block(B, _lif_kernel.DEFAULT_BLOCK_B, 8)
    bn = _pick_block(N, _lif_kernel.DEFAULT_BLOCK_N, 128)
    bk = _pick_block(K, _lif_kernel.DEFAULT_BLOCK_K, 128)

    s_p = _pad_to(_pad_to(s, 0, bb), 1, bk)
    w_p = _pad_to(_pad_to(w, 0, bk), 1, bn)
    c_p = _pad_to(_pad_to(c, 0, bk), 1, bn)
    v_p = _pad_to(_pad_to(v, 0, bb), 1, bn)
    # Padded neurons must never spike: give them refractory lock + huge th.
    r_p = _pad_to(_pad_to(r, 0, bb), 1, bn, value=1)
    drive_p = None if drive is None else _pad_to(_pad_to(drive, 0, bb), 1, bn)
    big = jnp.finfo(jnp.float32).max / 2
    vth_p = _pad_to(v_th, 0, bn, value=big)
    leak_p = _pad_to(leak, 0, bn)
    rref_p = _pad_to(r_ref, 0, bn)
    gain_p = _pad_to(gain, 0, bn)
    ibias_p = _pad_to(i_bias, 0, bn)
    vreset_p = _pad_to(v_reset, 0, bn)

    v_new, r_new, y = _lif_kernel.fused_lif_step(
        s_p, w_p, c_p, v_p, r_p, drive_p,
        vth_p, leak_p, rref_p, gain_p, ibias_p, vreset_p,
        mode=mode, block_b=bb, block_n=bn, block_k=bk, interpret=interpret,
    )
    return v_new[:B, :N], r_new[:B, :N], y[:B, :N]


def fused_lif_step(
    lif_state: LIFState,
    spikes: jax.Array,
    params,  # SNNParams (avoids circular import in annotations)
    ext: Optional[jax.Array],
    *,
    mode: str = "fixed_leak",
    surrogate: bool = False,
    interpret: Optional[bool] = None,
) -> LIFState:
    """State-level bridge used by ``repro.core.network.step(backend="pallas")``.

    The fused kernel is the inference datapath; surrogate-gradient training
    uses the jnp path (the kernel has no custom VJP -- by design, matching
    the inference-only FPGA).
    """
    if surrogate:
        raise ValueError("pallas backend is inference-only; use backend='jnp' to train")
    batch_shape = lif_state.v.shape[:-1]
    n = lif_state.v.shape[-1]
    flat = lambda a: a.reshape((-1, a.shape[-1]))
    drive = None
    if ext is not None:
        drive = flat(ext) @ params.w_in
    v, r, y = fused_lif_step_arrays(
        flat(spikes), params.w, params.c, flat(lif_state.v), flat(lif_state.r), drive,
        params.lif.v_th, params.lif.leak, params.lif.r_ref,
        params.lif.gain, params.lif.i_bias, params.lif.v_reset,
        mode=mode, interpret=interpret,
    )
    unflat = lambda a: a.reshape(batch_shape + (n,))
    return LIFState(v=unflat(v), r=unflat(r), y=unflat(y))


def fused_tick(
    state,  # SNNState (avoids circular import in annotations)
    params,  # SNNParams
    ext: Optional[jax.Array],
    *,
    wc: Optional[jax.Array] = None,
    delays: Optional[jax.Array] = None,
    mode: str = "fixed_leak",
    surrogate: bool = False,
    interpret: Optional[bool] = None,
) -> Tuple[LIFState, jax.Array]:
    """Whole-tick bridge used by ``TickEngine`` (``backend="pallas_fused"``).

    One kernel launch executes the complete tick circuit -- delay-line
    slot read, masked synaptic accumulation, LIF update, delay-line slot
    write -- replacing the 4-op chain of the split backends (see
    :mod:`repro.kernels.tick_fused`). The circular read/write pointers
    ``tick % D`` / ``(tick+1) % D`` ride in as scalar-prefetch operands,
    so advancing the tick never retraces. Under ``vmap`` over slots (the
    serving path) the kernel runs once for all slots, on its slot grid,
    and reads each slot's operands in place (see :func:`_slot_batchable`).

    Args:
      wc: pre-masked ``W*C`` (frozen path, hoisted by the caller as a
        scan constant); None streams ``w`` and ``c`` separately and masks
        per tile in VMEM (learning path -- ``params.w`` is this tick's
        mutable matrix).
      delays: optional per-synapse delay matrix ``(n, n)`` i32 in
        ``[1, max_delay]``.

    Returns:
      ``(lif_state', delay_buf')`` -- the delay buffer is returned
      unchanged when ``max_delay == 1`` (the tick never writes it, same
      as the reference path).
    """
    if surrogate:
        raise ValueError(
            "pallas_fused backend is inference-only; use backend='jnp' to train")
    if interpret is None:
        interpret = not _on_tpu()
    st = state
    batch_shape = st.lif.v.shape[:-1]
    n = st.lif.v.shape[-1]
    max_delay = st.delay_buf.shape[-2]
    flat = lambda a: a.reshape((-1, a.shape[-1]))
    v = flat(st.lif.v)
    r = flat(st.lif.r)
    B = v.shape[0]
    drive = None
    if ext is not None:
        drive = flat(ext) @ params.w_in

    slots = jnp.stack(
        [jnp.mod(st.tick, max_delay), jnp.mod(st.tick + 1, max_delay)]
    ).astype(jnp.int32)

    if delays is None and max_delay == 1:
        # Degenerate ring: arriving == previous-tick emissions, no write.
        read = flat(st.lif.y)[:, None, :]
    elif delays is None:
        # Uniform ring: only the arriving slot enters the contraction, so
        # the kernel reads one (B, 1, n) row per tick, not the D-deep ring.
        read = jax.lax.dynamic_slice_in_dim(
            st.delay_buf.reshape((-1, max_delay, n)), slots[0], 1, axis=1)
    else:
        read = st.delay_buf.reshape((-1, max_delay, n))
    write = max_delay > 1

    w_op = params.w if wc is None else wc
    c_op = params.c if wc is None else None

    bb = _pick_block(B, _tick_kernel.DEFAULT_BLOCK_B, 8)
    bn = _pick_block(n, _tick_kernel.DEFAULT_BLOCK_N, 128)
    bk = _pick_block(n, _tick_kernel.DEFAULT_BLOCK_K, 128)

    pad_b_last = lambda a, m: _pad_to(_pad_to(a, 0, bb), a.ndim - 1, m)
    read_p = pad_b_last(read, bk)
    w_p = _pad_to(_pad_to(w_op, 0, bk), 1, bn)
    c_p = None if c_op is None else _pad_to(_pad_to(c_op, 0, bk), 1, bn)
    delays_p = None
    if delays is not None:
        delays_p = _pad_to(
            _pad_to(delays.astype(jnp.int32), 0, bk, value=1), 1, bn, value=1)
    v_p = pad_b_last(v, bn)
    # Padded neurons must never spike: give them refractory lock + huge th.
    r_p = _pad_to(_pad_to(r, 0, bb), 1, bn, value=1)
    drive_p = None if drive is None else pad_b_last(drive, bn)
    dly_full_p = pad_b_last(st.delay_buf.reshape((-1, max_delay, n)),
                            bn) if write else None
    big = jnp.finfo(jnp.float32).max / 2
    vth_p = _pad_to(params.lif.v_th, 0, bn, value=big)
    leak_p = _pad_to(params.lif.leak, 0, bn)
    rref_p = _pad_to(params.lif.r_ref, 0, bn)
    gain_p = _pad_to(params.lif.gain, 0, bn)
    ibias_p = _pad_to(params.lif.i_bias, 0, bn)
    vreset_p = _pad_to(params.lif.v_reset, 0, bn)

    v_new, r_new, y, dly_new = _slot_batchable(functools.partial(
        _tick_kernel.fused_tick, mode=mode, block_b=bb, block_n=bn,
        block_k=bk, interpret=interpret))(
        slots, read_p, w_p, c_p, delays_p, v_p, r_p, drive_p, dly_full_p,
        vth_p, leak_p, rref_p, gain_p, ibias_p, vreset_p)
    unflat = lambda a: a[:B, :n].reshape(batch_shape + (n,))
    lif = LIFState(v=unflat(v_new), r=unflat(r_new), y=unflat(y))
    if not write:
        return lif, st.delay_buf
    delay_buf = dly_new[:B, :, :n].reshape(batch_shape + (max_delay, n))
    return lif, delay_buf


def _slot_batchable(kernel):
    """``kernel`` (the whole-tick kernel with its static arguments bound)
    as a function whose ``vmap`` is the kernel's slot grid.

    Under the serving path's slot ``vmap`` each slot has its own tick, so
    the pointer table is batched; JAX batches such a ``pallas_call`` as a
    loop over the slots that slices each slot's whole ``W`` and ``C`` out
    of the stack and writes the outputs back. Here the batched call is
    one launch instead: the pointers go in as an ``(S, 2)`` table, the
    stacked operands stay stacked and are read in place, and the
    unbatched ones are shared by every slot. Unbatched calls run the
    kernel as it is."""

    @jax.custom_batching.custom_vmap
    def call(slots, *args):
        return kernel(slots, *args)

    @call.def_vmap
    def _slot_grid(n_slots, in_batched, slots, *args):
        if not in_batched[0]:
            slots = jnp.broadcast_to(slots, (n_slots,) + slots.shape)
        out = kernel(slots, *args)
        return out, jax.tree.map(lambda _: True, out)

    return call


def fused_stdp_step(
    s_pre: jax.Array,
    x_pre: jax.Array,
    s_post: jax.Array,
    x_post: jax.Array,
    w: jax.Array,
    c: jax.Array,
    elig: jax.Array,
    reward: jax.Array,
    *,
    rule: str,
    a_plus: float,
    a_minus: float,
    decay_pre: float,
    decay_post: float,
    decay_elig: float,
    lr_reward: float,
    w_min: float,
    w_max: float,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Padded, backend-selected fused learning tick; see kernel docstring.

    The state<->array bridge (batch flattening, PlasticityState rebuild)
    lives in ``repro.plasticity.rules.plasticity_step`` -- this is the
    array-level entry point it and the tests share.  Zero-padding is
    exact here: padded batch rows contribute zero to both outer products,
    padded synapses carry C == 0 (so dw == 0 there), and every padded
    region is sliced away before returning.
    """
    if interpret is None:
        interpret = not _on_tpu()
    B, K = s_pre.shape
    N = s_post.shape[1]
    bb = _pick_block(B, _stdp_kernel.DEFAULT_BLOCK_B, 8)
    bk = _pick_block(K, _stdp_kernel.DEFAULT_BLOCK_K, 128)
    bn = _pick_block(N, _stdp_kernel.DEFAULT_BLOCK_N, 128)

    pad_bk = lambda a: _pad_to(_pad_to(a, 0, bb), 1, bk)
    pad_bn = lambda a: _pad_to(_pad_to(a, 0, bb), 1, bn)
    pad_kn = lambda a: _pad_to(_pad_to(a, 0, bk), 1, bn)

    w_new, elig_new, x_pre_new, x_post_new = _stdp_kernel.fused_stdp_step(
        pad_bk(s_pre), pad_bk(x_pre), pad_bn(s_post), pad_bn(x_post),
        pad_kn(w), pad_kn(c), pad_kn(elig),
        jnp.asarray(reward, jnp.float32),
        rule=rule, a_plus=a_plus, a_minus=a_minus,
        decay_pre=decay_pre, decay_post=decay_post, decay_elig=decay_elig,
        lr_reward=lr_reward, w_min=w_min, w_max=w_max,
        block_b=bb, block_k=bk, block_n=bn, interpret=interpret,
    )
    return (
        w_new[:K, :N], elig_new[:K, :N],
        x_pre_new[:B, :K], x_post_new[:B, :N],
    )


def fused_lif_step_slots(
    lif_state: LIFState,
    spikes: jax.Array,
    params,  # SNNParams with a leading slot axis on every leaf
    ext: Optional[jax.Array],
    *,
    mode: str = "fixed_leak",
    surrogate: bool = False,
    interpret: Optional[bool] = None,
) -> LIFState:
    """Slot-batched fused tick: S resident networks, one program.

    Every leaf of ``lif_state`` / ``params`` (and ``spikes`` / ``ext``)
    carries a leading *slot* axis of length S -- S independent register
    images time-sharing one compiled datapath, the serving restatement of
    the paper's one-fabric-many-networks claim.  Implemented as ``vmap``
    over :func:`fused_lif_step`, which the Pallas batching rule lowers to
    an extra grid dimension (interpret mode on CPU is identical).

    ``launch.serve.SNNServer`` reaches the same lowering by vmapping the
    whole engine rollout over the slot axis (so one vmap covers the
    plasticity hook too); this array-level entry point is for callers
    that drive single ticks of many resident networks directly --
    equivalence against the per-slot loop is pinned in
    tests/test_serve_snn.py.
    """
    f = functools.partial(fused_lif_step, mode=mode, surrogate=surrogate,
                          interpret=interpret)
    if ext is None:
        return jax.vmap(lambda st, sp, p: f(st, sp, p, None))(
            lif_state, spikes, params)
    return jax.vmap(f)(lif_state, spikes, params, ext)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EventFanIn:
    """Device-side padded fan-in lists (the event backend's gather layout).

    ``idx[m, j]`` is the j-th presynaptic source of postsynaptic neuron
    ``m`` (ascending, 0-padded); ``mask`` gates padding to 0.  Built once
    per topology from :func:`repro.core.connectivity.padded_fan_in` via
    :meth:`from_padded` -- like the connection list itself it is runtime
    *data*, so swapping topologies of equal cap never retraces.
    """

    idx: jax.Array           # (n, cap) int32
    mask: jax.Array          # (n, cap) float32

    @classmethod
    def from_padded(cls, nbrs) -> "EventFanIn":
        if nbrs.axis != "in":
            raise ValueError(
                f"EventFanIn needs fan-in lists (axis='in'), got {nbrs.axis!r}")
        return cls(idx=jnp.asarray(nbrs.idx, jnp.int32),
                   mask=jnp.asarray(nbrs.mask, jnp.float32))

    @classmethod
    def from_dense(cls, c, cap: Optional[int] = None) -> "EventFanIn":
        from repro.core import connectivity
        import numpy as np

        return cls.from_padded(
            connectivity.padded_fan_in(np.asarray(c) > 0, cap))


def default_k_active(n: int) -> int:
    """Default spike-slot budget for the top-k event path: n/8, floored at 8
    (matches the bench cost model's ``2*rate*n`` at rate ~0.06).

    Thin alias over :func:`repro.core.dispatch_policy.resolve_k_active`
    (with ``k_active=None``) -- the single source of the trigger that the
    engine's telemetry mirror and the kernel bridge also use.
    """
    from repro.core.dispatch_policy import resolve_k_active

    return resolve_k_active(n, None)


def event_synaptic_input(
    s: jax.Array,
    wc: jax.Array,
    *,
    k_active: Optional[int] = None,
    fan_in: Optional[EventFanIn] = None,
    overflow: str = "fallback",
) -> jax.Array:
    """Event-driven synaptic input: the pure-jnp reference the ``"event"``
    backend and the Pallas dispatch kernel both answer to.

    Two dispatch strategies, both exploiting what the paper's mux fabric
    exploits (an open mux routes nothing; a silent neuron costs nothing):

    * **top-k spike gather** (default): select the (at most ``k_active``)
      spiking presynaptic rows per batch element, gather their fan-out
      slices of ``wc`` and reduce -- ``B*k_active*N`` FLOPs instead of
      ``B*K*N``.  ``jax.lax.top_k`` is tie-stable, so the gathered rows
      come out in ascending presynaptic order and the reduction sums the
      same nonzero terms as the dense product, in the order XLA's dot
      picks.
    * **fan-in gather** (``fan_in`` given): for every postsynaptic neuron
      read exactly its padded in-edge list -- ``B*N*cap`` FLOPs, no
      data-dependent control flow at all (safe under ``vmap``, which is
      how the multi-tenant server runs it).

    Args:
      s: ``(..., K)`` presynaptic spikes in {0, 1}.
      wc: ``(K, N)`` pre-masked effective matrix ``W*C``.
      k_active: spike-slot budget for the top-k path (None -> ``K//8``,
        floored at 8).  Ignored when ``fan_in`` is given.
      fan_in: optional :class:`EventFanIn` switching to the gather path.
      overflow: what the top-k path does when some batch row spikes more
        than ``k_active`` times (where truncation would silently drop real
        spikes -- the bug this argument exists to kill):

        * ``"fallback"`` (default): detect ``s.sum(-1) > k_active`` and
          compute the dense product instead -- exact at any rate, and the
          scalar ``lax.cond`` only pays for the dense branch on ticks
          that overflow (outside ``vmap``).
        * ``"strict"``: fail under :mod:`jax.experimental.checkify`
          instead of falling back (run the caller through
          ``checkify.checkify`` to surface the error).
        * ``"unchecked"``: no detection -- caller guarantees the rate.
    """
    K = s.shape[-1]
    if fan_in is not None:
        # Gather path: s[..., idx] is (..., N, cap); the per-edge weights
        # wc[idx[m, j], m] come straight off the dense matrix, so the same
        # call serves frozen (hoisted wc) and learning (per-tick wc) paths.
        n = wc.shape[1]
        w_edges = wc[fan_in.idx, jnp.arange(n)[:, None]] * fan_in.mask
        gathered = s[..., fan_in.idx]                       # (..., n, cap)
        return jnp.einsum("...nc,nc->...n", gathered.astype(jnp.float32),
                          w_edges.astype(jnp.float32))

    from repro.core.dispatch_policy import resolve_k_active

    k_active = resolve_k_active(K, k_active)

    def dense(sv):
        return sv.astype(jnp.float32) @ wc.astype(jnp.float32)

    def event(sv):
        # Top-k by spike value (1.0 beats 0.0); ties broken by lower index,
        # so spiking rows arrive in ascending presynaptic order.
        vals, idx = jax.lax.top_k(sv, k_active)             # (..., k)
        rows = jnp.take(wc, idx, axis=0)                    # (..., k, N)
        return jnp.einsum("...k,...kn->...n", vals.astype(jnp.float32),
                          rows.astype(jnp.float32))

    if overflow == "unchecked":
        return event(s)
    n_spiking = jnp.sum(s > 0, axis=-1)
    over = jnp.any(n_spiking > k_active)
    if overflow == "strict":
        from jax.experimental import checkify

        checkify.check(
            jnp.logical_not(over),
            "event dispatch overflow: {m} spiking rows > k_active={k}",
            m=jnp.max(n_spiking), k=jnp.asarray(k_active))
        return event(s)
    if overflow != "fallback":
        raise ValueError(f"unknown overflow mode {overflow!r}")
    return jax.lax.cond(over, dense, event, s)


def event_spike_matmul(
    s: jax.Array, w: jax.Array, c: jax.Array, *, k_active: int,
    overflow: str = "fallback",
) -> jax.Array:
    """Beyond-paper event-driven dispatch (pure JAX, MXU-friendly).

    Instead of the dense (B,K)x(K,N) product, gather the fan-out rows of at
    most ``k_active`` spiking presynaptic neurons per batch row and reduce:
    FLOPs drop from ``B*K*N`` to ``B*k_active*N`` -- the TPU analogue of the
    paper's mux fabric *not even routing* silent neurons.

    Exact at *any* rate: rows with more than ``k_active`` spikes used to be
    silently truncated by the top-k (dropping real spikes and returning a
    wrong synaptic input); the overflow is now detected and falls back to
    the dense product (or raises -- ``overflow="strict"`` under checkify).
    See :func:`event_synaptic_input` for the modes.
    """
    wc = w * c.astype(w.dtype)
    return event_synaptic_input(s, wc, k_active=k_active, overflow=overflow)


def event_lif_step(
    lif_state: LIFState,
    spikes: jax.Array,
    params,  # SNNParams (avoids circular import in annotations)
    ext: Optional[jax.Array],
    wc: jax.Array,
    *,
    k_active: Optional[int] = None,
    fan_in: Optional[EventFanIn] = None,
    overflow: str = "fallback",
    mode: str = "fixed_leak",
    surrogate: bool = False,
    ext_diag: bool = False,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> LIFState:
    """State-level bridge for ``TickEngine(backend="event")``.

    On TPU the top-k path lowers to the Pallas event-dispatch kernel
    (:mod:`repro.kernels.event_dispatch`): spike lists ride in as scalar
    prefetch, and a double-buffered loop gathers only the spiking rows'
    fan-out slices out of HBM, prefetching row k+1's slice while
    accumulating row k.  On CPU
    (and for the fan-in gather / surrogate paths) the pure-jnp reference
    above *is* the implementation -- XLA already executes the gathers
    natively, so interpret-mode emulation would only add overhead.

    ``ext_diag=True`` computes the external drive as the elementwise
    ``ext * diag(w_in)`` instead of the full ``ext @ w_in`` GEMM --
    bit-identical when ``w_in`` is diagonal (the caller's contract;
    :func:`repro.core.dispatch_policy.is_diagonal` checks it).
    """
    if use_kernel is None:
        use_kernel = _on_tpu() and fan_in is None and not surrogate

    def _drive_of(e):
        if e is None:
            return None
        if ext_diag:
            return e * jnp.diagonal(params.w_in)
        return e @ params.w_in

    if use_kernel:
        from repro.kernels import event_dispatch as _ev_kernel

        if surrogate:
            raise ValueError(
                "event kernel path is inference-only; use the jnp path to train")
        from repro.core.dispatch_policy import resolve_k_active

        batch_shape = lif_state.v.shape[:-1]
        n = lif_state.v.shape[-1]
        flat = lambda a: a.reshape((-1, a.shape[-1]))
        s = flat(spikes)
        k = resolve_k_active(s.shape[-1], k_active)
        drive = _drive_of(None if ext is None else flat(ext))
        vals, idx = jax.lax.top_k(s, k)
        # Per-row live-slot count: top_k packs the 1.0s first, so the
        # first counts[b] slots are the real spiking rows (ascending) and
        # the kernel never reads the tail.
        counts = jnp.sum(vals > 0, axis=-1).astype(jnp.int32)
        bn = _pick_block(n, _ev_kernel.DEFAULT_BLOCK_N, 128)
        pad_n = lambda a, v=0: _pad_to(a, a.ndim - 1, bn, value=v)
        big = jnp.finfo(jnp.float32).max / 2
        lp = params.lif

        def event(_):
            v_new, r_new, y = _ev_kernel.event_lif_dispatch_db(
                idx, pad_n(wc), pad_n(flat(lif_state.v)),
                pad_n(flat(lif_state.r), 1),    # padded neurons: refractory
                None if drive is None else pad_n(drive),
                _pad_to(lp.v_th, 0, bn, value=big), _pad_to(lp.leak, 0, bn),
                _pad_to(lp.r_ref, 0, bn), _pad_to(lp.gain, 0, bn),
                _pad_to(lp.i_bias, 0, bn), _pad_to(lp.v_reset, 0, bn),
                counts=counts, mode=mode, block_n=bn,
                interpret=not _on_tpu() if interpret is None else interpret,
            )
            return v_new[:, :n], r_new[:, :n], y[:, :n]

        n_spiking = jnp.sum(s > 0, axis=-1)
        if overflow == "fallback":
            # The kernel's k slots truncate past k_active; overflow ticks
            # take the dense fused kernel instead (exact at any rate).
            def dense(_):
                return fused_lif_step_arrays(
                    s, wc, jnp.ones_like(wc), flat(lif_state.v),
                    flat(lif_state.r), drive, lp.v_th, lp.leak, lp.r_ref,
                    lp.gain, lp.i_bias, lp.v_reset,
                    mode=mode, interpret=interpret)

            v_new, r_new, y = jax.lax.cond(
                jnp.any(n_spiking > k), dense, event, 0)
        else:
            if overflow == "strict":
                from jax.experimental import checkify

                checkify.check(
                    jnp.logical_not(jnp.any(n_spiking > k)),
                    "event dispatch overflow: {m} spiking rows > k_active={k}",
                    m=jnp.max(n_spiking), k=jnp.asarray(k))
            v_new, r_new, y = event(0)
        unflat = lambda a: a.reshape(batch_shape + (n,))
        return LIFState(v=unflat(v_new), r=unflat(r_new), y=unflat(y))

    from repro.core.lif import lif_step

    syn = event_synaptic_input(spikes, wc, k_active=k_active, fan_in=fan_in,
                               overflow=overflow)
    if ext is not None:
        syn = syn + _drive_of(ext)
    return lif_step(lif_state, syn, params.lif, mode=mode, surrogate=surrogate)


# Re-export oracles for test convenience.
ref = _ref

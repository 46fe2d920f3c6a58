"""Whole-tick fused kernel: delay read -> masked matmul -> LIF -> delay write.

The paper's datapath is ONE resident circuit that completes the entire
tick -- delay-line slot read, all-to-all masked synaptic accumulation,
LIF update, delay-line slot write -- before the next tick starts; that
single-circuit property is why the FPGA hits its latency numbers.
:mod:`repro.kernels.lif_step` fused the *middle* of that tick (matmul +
LIF) but still left the delay-line read and write as separate XLA ops,
i.e. two extra HBM round-trips per tick on the raster and the delay
buffer. This kernel closes the loop: one ``pallas_call`` per tick is the
whole circuit.

Structure (grid ``(B/bB, N/bN, K/bK)``, K the presynaptic contraction
axis, K-steps accumulating into a VMEM f32 scratch):

* **Delay-line read without a retrace.** The circular pointers
  ``tick % D`` / ``(tick+1) % D`` are *runtime scalars* riding in as
  scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``), so
  changing ``tick`` never recompiles.  With a uniform delay the caller
  dynamic-slices the arriving slot out of the ring and passes a
  ``(B, 1, K)`` operand: the read costs one spike row per tick, like a
  plain spike-vector load.  (A ``(bB, 1, bK)`` block steered at the
  slot of the whole ``(B, D, K)`` ring would be one row out of D, not
  aligned to the TPU's (8, 128) tiling, and does not compile.)
* **Masked accumulation.** Same as :mod:`lif_step`: ``w*c`` fused per
  tile in VMEM (the mux that routes a zero, at zero bandwidth), double-
  buffered by the Pallas pipeline across K steps. The frozen path passes
  a pre-masked ``W*C`` scan constant instead (no ``c`` operand at all --
  half the weight-side traffic); the learning path streams ``w`` and
  ``c`` separately because ``w`` changes every tick.
* **Per-synapse delays.** With a delay matrix, synapse ``(pre, post)``
  with delay ``d`` reads history slot ``(slot - (d-1)) % D``. The kernel
  loads the full ``(bB, D, bK)`` history tile, builds the d-major
  flattened ``(bB, D*bK) @ (D*bK, bN)`` product with per-delay masked
  weight planes -- the same contraction, in the same d-major order, as
  the reference einsum in ``TickEngine.tick_body``.
* **LIF epilogue + delay-line write.** On the last K step the shared
  :func:`repro.kernels.lif_step._lif_epilogue` runs in VREGs and the
  fresh spikes are stored into write slot ``slots_ref[1] = (tick+1) % D``
  of the output delay buffer (the other ``D-1`` slots stream through
  unchanged from the input tile).
* **Slot grid.** Serving runs S resident networks, each at its own
  tick. Given an ``(S, 2)`` table of pointers, the kernel takes a leading
  slot grid axis (grid ``(S, B/bB, N/bN, K/bK)``): an operand with a
  leading slot axis is read tile by tile from slot ``s`` of its stacked
  array, one without it is shared by every slot, and slot ``s`` reads
  its pointers from row ``s`` of the table. JAX's own batching of a
  kernel whose scalar prefetch is batched is a loop of calls that
  copies each slot's whole operands out of the stack first.

All shapes must be pre-padded to block multiples by the caller
(:func:`repro.kernels.ops.fused_tick` handles padding, slot scalars,
and the state-dataclass bridge).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Collection, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

from repro.kernels.launch_spec import KernelLaunch, Operand, Scratch
from repro.kernels.lif_step import _lif_epilogue

DEFAULT_BLOCK_B = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 512


def _tick_kernel(
    slots_ref,          # (2,) i32 in SMEM: [read_slot, write_slot]; (S, 2)
    *refs,              # on the slot grid, one row per slot
    mode: str,
    n_delay: int,
    has_c: bool,
    has_delays: bool,
    has_drive: bool,
    write_delay: bool,
    slot_grid: bool,
):
    """One grid step of the whole-tick circuit.

    ``refs`` carries, in order: the variable-presence inputs
    (``dly_read, w, [c], [delays], v, r, [drive], [dly_full]``), the six
    per-neuron parameter rows, the outputs (``v', r', y', [dly']``), and
    the f32 accumulator scratch. On the slot grid (``slot_grid``) the
    slot axis is squeezed out of every block, so the refs are a slot's
    tiles.
    """
    it = iter(refs)
    dly_read_ref = next(it)
    w_ref = next(it)
    c_ref = next(it) if has_c else None
    delays_ref = next(it) if has_delays else None
    v_ref = next(it)
    r_in_ref = next(it)
    drive_ref = next(it) if has_drive else None
    dly_full_ref = next(it) if write_delay else None
    vth_ref, leak_ref, rref_ref, gain_ref, ibias_ref, vreset_ref = (
        next(it), next(it), next(it), next(it), next(it), next(it))
    v_out_ref, r_out_ref, y_out_ref = next(it), next(it), next(it)
    dly_out_ref = next(it) if write_delay else None
    acc_ref = next(it)

    if slot_grid:
        slot_id = pl.program_id(0)
        pointer = lambda j: slots_ref[slot_id, j]
    else:
        pointer = lambda j: slots_ref[j]
    k = pl.program_id(3 if slot_grid else 2)
    nk = pl.num_programs(3 if slot_grid else 2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Masked MXU tile: the mux fabric. On the frozen path w IS W*C already.
    wc = w_ref[...].astype(jnp.float32)
    if has_c:
        wc = wc * c_ref[...].astype(jnp.float32)

    if not has_delays:
        # Uniform delay: the caller passed only the arriving slot; the
        # tile is (bB, 1, bK).
        s = dly_read_ref[:, 0, :].astype(jnp.float32)
        acc_ref[...] += jnp.dot(s, wc, preferred_element_type=jnp.float32)
    else:
        # Per-synapse delays: synapse with delay d reads history slot
        # (slot - (d-1)) % D. Build the d-major flattened contraction so the
        # summation order matches the reference einsum exactly.
        slot = pointer(0)
        hist = [
            dly_read_ref[:, pl.ds(jax.lax.rem(slot - d + n_delay, n_delay), 1), :][:, 0, :]
            for d in range(n_delay)
        ]
        hist_flat = jnp.concatenate(hist, axis=1).astype(jnp.float32)  # (bB, D*bK)
        d_ids = delays_ref[...]
        w_planes = [wc * (d_ids == d + 1).astype(jnp.float32) for d in range(n_delay)]
        w_flat = jnp.concatenate(w_planes, axis=0)                     # (D*bK, bN)
        acc_ref[...] += jnp.dot(hist_flat, w_flat,
                                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        v = v_ref[...].astype(jnp.float32)
        r = r_in_ref[...]
        drive = drive_ref[...].astype(jnp.float32) if has_drive else None
        v_new, r_new, spiked = _lif_epilogue(
            acc_ref[...], v, r, drive,
            vth_ref[...].astype(jnp.float32),
            leak_ref[...].astype(jnp.float32),
            rref_ref[...],
            gain_ref[...].astype(jnp.float32),
            ibias_ref[...].astype(jnp.float32),
            vreset_ref[...].astype(jnp.float32),
            mode,
        )
        y = spiked.astype(y_out_ref.dtype)
        v_out_ref[...] = v_new.astype(v_out_ref.dtype)
        r_out_ref[...] = r_new.astype(r_out_ref.dtype)
        y_out_ref[...] = y
        if write_delay:
            # Delay-line write: fresh spikes land at slot (tick+1) % D; the
            # other D-1 slots stream through from the input tile unchanged.
            buf = dly_full_ref[...]
            dly_out_ref[...] = buf
            dly_out_ref[:, pl.ds(pointer(1), 1), :] = (
                y[:, None, :].astype(dly_out_ref.dtype))


def tick_launch(
    *,
    B: int,
    K: int,
    N: int,
    n_read: int,
    dtypes: dict,
    has_c: bool,
    has_delays: bool,
    has_drive: bool,
    write_delay: bool,
    n_full: int = 0,
    n_slots: int = 0,
    batched: Collection[str] = (),
    block_b: int = DEFAULT_BLOCK_B,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
) -> KernelLaunch:
    """The whole-tick kernel's launch descriptor.

    This is the single source of truth for the grid, the BlockSpecs, the
    operand order (which must match ``_tick_kernel``'s ``refs``
    iteration), and the VMEM scratch -- :func:`fused_tick` materializes a
    ``pallas_call`` from it and :mod:`repro.analysis.pallas_rules` lints
    it.  ``dtypes`` maps operand names (``dly_read, w, c, delays, v, r,
    drive, dly_full, param``) to dtypes; ``n_full`` is the full delay
    depth D when ``write_delay``.

    ``n_slots > 0`` gives the slot grid: a leading grid axis of that
    length, an ``(n_slots, 2)`` pointer table, and a leading slot axis on
    the outputs and on each input named in ``batched`` (the parameter
    rows by their own names); the other inputs are shared by every slot.
    """
    grid = (B // block_b, N // block_n, K // block_k)
    bn = (block_b, block_n)
    kn = (block_k, block_n)
    map_bn = lambda i, j, k, s: (i, j)
    map_kn = lambda i, j, k, s: (k, j)
    map_param = lambda i, j, k, s: (0, j)

    # Per-synapse delays contract every slot of the (bB, D, bK) history
    # tile; a uniform ring arrives as its one slot (n_read == 1).
    read = Operand("dly_read", (B, n_read, K), dtypes["dly_read"],
                   (block_b, n_read, block_k),
                   lambda i, j, k, s: (i, 0, k))

    inputs = [read,
              Operand("w", (K, N), dtypes["w"], kn, map_kn)]
    if has_c:
        inputs.append(Operand("c", (K, N), dtypes["c"], kn, map_kn))
    if has_delays:
        inputs.append(Operand("delays", (K, N), dtypes["delays"],
                              kn, map_kn))
    inputs += [Operand("v", (B, N), dtypes["v"], bn, map_bn),
               Operand("r", (B, N), dtypes["r"], bn, map_bn)]
    if has_drive:
        inputs.append(Operand("drive", (B, N), dtypes["drive"],
                              bn, map_bn))
    if write_delay:
        dly_bn = ((block_b, n_full, block_n),
                  lambda i, j, k, s: (i, 0, j))
        inputs.append(Operand("dly_full", (B, n_full, N),
                              dtypes["dly_full"], *dly_bn))
    param = (1, block_n)
    for pname in ("v_th", "leak", "r_ref", "gain", "i_bias", "v_reset"):
        inputs.append(Operand(pname, (1, N),
                              dtypes.get(pname, dtypes["param"]),
                              param, map_param))

    outputs = [Operand("v_out", (B, N), dtypes["v"], bn, map_bn),
               Operand("r_out", (B, N), dtypes["r"], bn, map_bn),
               Operand("y_out", (B, N), dtypes["dly_read"], bn, map_bn)]
    if write_delay:
        outputs.append(Operand("dly_out", (B, n_full, N),
                               dtypes["dly_full"], *dly_bn))

    # Worst-case prefetch example for the lint: read slot at the deepest
    # history index, write slot at the deepest buffer index.
    slots_ex = np.array(
        [n_read - 1, (n_full - 1) if write_delay else 0], np.int32)
    if n_slots:
        grid = (n_slots,) + grid
        inputs = [_on_slot_grid(op, n_slots, op.name in batched)
                  for op in inputs]
        outputs = [_on_slot_grid(op, n_slots, True) for op in outputs]
        slots_ex = np.broadcast_to(slots_ex, (n_slots, 2))
    return KernelLaunch(
        name="tick_fused",
        grid=grid,
        inputs=tuple(inputs),
        outputs=tuple(outputs),
        scratch=(Scratch("vmem", (block_b, block_n), jnp.float32),),
        num_scalar_prefetch=1,
        prefetch_example=(slots_ex,),
    )


def _on_slot_grid(op: Operand, n_slots: int, stacked: bool) -> Operand:
    """``op`` under a leading slot grid axis ``s``: a stacked operand
    gains a leading slot axis whose block is squeezed and indexed by
    ``s``; a shared one keeps its blocks and ignores ``s``."""
    index_map = op.index_map
    if not stacked:
        return dataclasses.replace(
            op, index_map=lambda s, *idx: index_map(*idx))
    return dataclasses.replace(
        op, shape=(n_slots,) + op.shape,
        block_shape=(None,) + op.block_shape,
        index_map=lambda s, *idx: (s,) + index_map(*idx))


@functools.partial(
    jax.jit,
    static_argnames=("mode", "block_b", "block_n", "block_k", "interpret"),
)
def fused_tick(
    slots: jax.Array,
    dly_read: jax.Array,
    w: jax.Array,
    c: Optional[jax.Array],
    delays: Optional[jax.Array],
    v: jax.Array,
    r: jax.Array,
    drive: Optional[jax.Array],
    dly_full: Optional[jax.Array],
    v_th: jax.Array,
    leak: jax.Array,
    r_ref: jax.Array,
    gain: jax.Array,
    i_bias: jax.Array,
    v_reset: jax.Array,
    *,
    mode: str = "fixed_leak",
    block_b: int = DEFAULT_BLOCK_B,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, Optional[jax.Array]]:
    """One whole network tick as a single ``pallas_call``.

    Shapes (pre-padded to block multiples):

    * ``slots``: (2,) i32 -- ``[tick % D, (tick+1) % D]`` (scalar prefetch).
    * ``dly_read``: (B, Dr, K) spike history: the arriving slot alone
      (``Dr == 1``) for a uniform delay; all ``Dr`` slots for
      per-synapse delays.
    * ``w``: (K, N) weights -- pre-masked ``W*C`` when ``c`` is None.
    * ``c``: (K, N) connection mask or None (frozen pre-masked path).
    * ``delays``: (K, N) i32 in ``[1, Dr]`` or None (uniform 1-tick delay).
    * ``v``/``drive``: (B, N) f32; ``r``: (B, N) i32.
    * ``dly_full``: (B, D, N) delay buffer to write through, or None when
      the tick does not write the delay line (``max_delay == 1``).
    * per-neuron params: (N,), reshaped to (1, N) rows.

    Slot grid: with ``slots`` an ``(S, 2)`` table, one launch runs S
    ticks, slot ``s`` at pointers ``slots[s]``. Any other operand may then
    carry a leading slot axis of length S (one array per slot) or not
    (shared by every slot), and every output carries one.

    Returns ``(v', r', y', dly')`` with ``dly'`` None iff ``dly_full`` is.
    """
    B, n_read, K = dly_read.shape[-3:]
    N = w.shape[-1]
    if B % block_b or N % block_n or K % block_k:
        raise ValueError(
            f"shapes must be block-aligned: B={B}%{block_b}, "
            f"N={N}%{block_n}, K={K}%{block_k}")
    if mode not in ("fixed_leak", "euler"):
        raise ValueError(f"fused tick supports fixed_leak|euler, got {mode!r}")
    has_c = c is not None
    has_delays = delays is not None
    has_drive = drive is not None
    write_delay = dly_full is not None
    n_delay = n_read
    slot_grid = slots.ndim == 2

    params = {"v_th": v_th, "leak": leak, "r_ref": r_ref, "gain": gain,
              "i_bias": i_bias, "v_reset": v_reset}
    arrays = {"dly_read": dly_read, "w": w, "c": c, "delays": delays,
              "v": v, "r": r, "drive": drive, "dly_full": dly_full}
    # An operand stacked over slots has one axis more than its own rank.
    rank = {"dly_read": 3, "dly_full": 3, **{n: 1 for n in params}}
    batched = [n for n, a in {**arrays, **params}.items()
               if a is not None and a.ndim > rank.get(n, 2)]
    arrays.update({n: a.reshape(a.shape[:-1] + (1, N))
                   for n, a in params.items()})
    launch = tick_launch(
        B=B, K=K, N=N, n_read=n_read,
        dtypes={"dly_read": dly_read.dtype, "w": w.dtype,
                "c": c.dtype if has_c else None,
                "delays": delays.dtype if has_delays else None,
                "v": v.dtype, "r": r.dtype,
                "drive": drive.dtype if has_drive else None,
                "dly_full": dly_full.dtype if write_delay else None,
                "param": v_th.dtype},
        has_c=has_c, has_delays=has_delays, has_drive=has_drive,
        write_delay=write_delay,
        n_full=dly_full.shape[-2] if write_delay else 0,
        n_slots=slots.shape[0] if slot_grid else 0, batched=batched,
        block_b=block_b, block_n=block_n, block_k=block_k)

    kernel = functools.partial(
        _tick_kernel, mode=mode, n_delay=n_delay, has_c=has_c,
        has_delays=has_delays, has_drive=has_drive, write_delay=write_delay,
        slot_grid=slot_grid)
    out = pl.pallas_call(
        kernel,
        grid_spec=launch.grid_spec(),
        out_shape=launch.out_shapes(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(launch.grid) - 1)
            + ("arbitrary",),
        ),
        interpret=interpret,
    )(slots.astype(jnp.int32), *launch.gather(arrays))
    if write_delay:
        v_new, r_new, y, dly_new = out
        return v_new, r_new, y, dly_new
    v_new, r_new, y = out
    return v_new, r_new, y, None

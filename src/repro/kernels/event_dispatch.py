"""Event-driven dispatch kernel: gather spiking fan-outs, accumulate, LIF.

The paper's mux fabric routes *only* closed connections, and a silent
neuron costs nothing -- its muxes simply never fire.  The dense kernels
(:mod:`lif_step`, :mod:`tick_fused`) pay the full ``B*K*N`` masked
matmul per tick regardless of activity; at the sparse operating point
the ROADMAP cares about (large n, density <= 0.05, rate <= 0.05) almost
all of that work multiplies zeros.  This kernel is the TPU restatement
of event dispatch: per batch row, only the (at most ``k_active``)
*spiking* presynaptic neurons' fan-out slices are ever gathered out of
HBM, and they are accumulated into the synaptic-input tile before the
shared LIF epilogue runs in VREGs.

Structure (grid ``(B/8, N/bN)``; :func:`event_lif_dispatch_db`):

* **Spike lists ride in as scalar prefetch.**  The caller
  (:func:`repro.kernels.ops.event_lif_step`) extracts the spiking row
  ids with a tie-stable ``top_k`` -- ascending presynaptic order -- plus
  a per-row live count.  Both are *runtime data* in SMEM.  Each row's
  synaptic input is thus the f32 sum of its spiking weight rows one at
  a time, in ascending order, from zero (tests hold it to that sum bit
  for bit).  The jnp reference's dot sums in an order XLA picks, so the
  two agree bit for bit where every order is exact, e.g. on weights of
  a dyadic grid.
* **Double-buffered row gather.**  Each grid step owns an ``(8, bN)``
  tile: 8 batch rows, the f32 sublane count, so every VMEM block is
  aligned to the TPU's (8, 128) tiling.  For each of its rows a
  ``fori_loop`` walks just the ``counts[b]`` live slots, issuing the
  weight-row DMA for spike k+1 into the alternate VMEM buffer while
  accumulating spike k (copy start -> wait -> accumulate).  Padding
  slots are never touched -- a quiet batch row costs zero DMAs -- and
  the weight matrix stays in HBM (``memory_space=ANY``).
* **Row-addressable weights.**  The matrix is viewed as ``(K, 1, N)``
  so a spike's row is an index on an untiled leading axis: the DMA
  source is a whole ``(1, bN)`` tile, never a one-row slice of an
  (8, 128)-tiled array (which Mosaic refuses).
* **Shared LIF epilogue.**  :func:`repro.kernels.lif_step._lif_epilogue`
  -- the identical threshold/leak/reset/refractory math every other
  backend uses -- runs once per tile on the 8 accumulated rows.

Overflow (a batch row spiking more than ``k_active`` times) is handled
by the caller, not here: the bridge detects it and falls back to the
dense fused kernel (or raises under checkify), so truncation can never
silently drop spikes.  N must be pre-padded to a ``block_n`` multiple
by the caller; the batch is padded to 8 rows here.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

from repro.kernels.launch_spec import KernelLaunch, Operand, Scratch
from repro.kernels.lif_step import _lif_epilogue

DEFAULT_BLOCK_N = 128
BLOCK_B = 8     # batch rows per tile: the f32 sublane count


def db_dma_schedule(nb: int):
    """The double-buffered DMA protocol of ``_event_db_kernel``, as a
    concrete op list for ``nb`` live spikes.

    This is the kernel's manual-DMA twin: the kernel's control flow is
    traced (``pl.when`` + ``fori_loop``), so the analyzer cannot walk it
    -- instead this function restates the exact same protocol in plain
    Python (warmup start, prefetch-next start, wait, accumulate), and the
    semaphore-pairing lint simulates it for every ``nb``.  If the kernel
    protocol changes, change THIS function in the same commit -- the
    parity comment in ``_event_db_kernel.body`` points back here.

    Ops: ``("start", slot, k)`` begins spike ``k``'s copy into buffer
    ``slot`` (signals semaphore ``slot``); ``("wait", slot, k)`` blocks
    on semaphore ``slot``; ``("use", slot, k)`` reads buffer ``slot``
    expecting spike ``k``'s data.
    """
    ops = []
    if nb > 0:
        ops.append(("start", 0, 0))          # warmup: spike 0 -> buffer 0
    for k in range(nb):
        slot = k % 2
        if k + 1 < nb:
            # Start spike k+1's DMA into the other buffer BEFORE waiting
            # on spike k: the gather overlaps the accumulate.
            ops.append(("start", 1 - slot, k + 1))
        ops.append(("wait", slot, k))
        ops.append(("use", slot, k))
    return ops


def event_db_launch(*, B: int, K: int, N: int, k_active: int, dtypes: dict,
                    has_drive: bool,
                    block_n: int = DEFAULT_BLOCK_N) -> KernelLaunch:
    """Launch descriptor for :func:`event_lif_dispatch_db` (``B`` already
    padded to a :data:`BLOCK_B` multiple).  The ``(K, 1, N)`` weight view
    stays in HBM (``memory_space=ANY``); its gathers are manual DMAs
    described by :func:`db_dma_schedule`."""
    tile = (BLOCK_B, block_n)
    map_b = lambda b, j, i, c: (b, j)
    map_p = lambda b, j, i, c: (0, j)
    w_op = Operand("w", (K, 1, N), dtypes["w"], memory_space="any")
    state = [Operand("v", (B, N), dtypes["v"], tile, map_b),
             Operand("r", (B, N), dtypes["r"], tile, map_b)]
    if has_drive:
        state.append(Operand("drive", (B, N), dtypes["drive"], tile, map_b))
    params = [Operand(pname, (1, N), dtypes.get(pname, dtypes["param"]),
                      (1, block_n), map_p)
              for pname in ("v_th", "leak", "r_ref", "gain", "i_bias",
                            "v_reset")]
    outputs = [Operand("v_out", (B, N), dtypes["v"], tile, map_b),
               Operand("r_out", (B, N), dtypes["r"], tile, map_b),
               Operand("y_out", (B, N), dtypes["v"], tile, map_b)]
    idx_ex = np.full((B, k_active), K - 1, np.int32)
    counts_ex = np.full((B,), k_active, np.int32)
    return KernelLaunch(
        name="event_dispatch_db",
        grid=(B // BLOCK_B, N // block_n),
        inputs=tuple([w_op] + state + params),
        outputs=tuple(outputs),
        scratch=(Scratch("vmem", (2, 1, block_n), dtypes["w"]),
                 Scratch("sem_dma", (2,))),
        num_scalar_prefetch=2,
        prefetch_example=(idx_ex, counts_ex),
        dma_schedule=db_dma_schedule,
    )


def _event_db_kernel(
    idx_ref,            # (B, k) i32 in SMEM: spiking row ids, live prefix
    counts_ref,         # (B,) i32 in SMEM: live slots per row
    *refs,
    mode: str,
    has_drive: bool,
    block_n: int,
):
    """One (8-row, bN-column) tile: a double-buffered walk of each row's
    compact spike list, then the LIF epilogue on all 8 rows."""
    it = iter(refs)
    w_hbm_ref = next(it)    # (K, 1, N) weights, memory_space=ANY (HBM)
    v_ref = next(it)
    r_in_ref = next(it)
    drive_ref = next(it) if has_drive else None
    vth_ref, leak_ref, rref_ref, gain_ref, ibias_ref, vreset_ref = (
        next(it), next(it), next(it), next(it), next(it), next(it))
    v_out_ref, r_out_ref, y_out_ref = next(it), next(it), next(it)
    w_buf_ref = next(it)    # (2, 1, block_n) VMEM: the double buffer
    sem_ref = next(it)      # (2,) DMA semaphores, one per buffer slot

    col = pl.multiple_of(pl.program_id(1) * block_n, block_n)

    def gather_row(b):
        nb = counts_ref[b]

        def copy_k(slot, k):
            # The gather: spike k's fan-out slice for this column tile,
            # HBM -> VMEM buffer `slot`.
            return pltpu.make_async_copy(
                w_hbm_ref.at[idx_ref[b, k], :, pl.ds(col, block_n)],
                w_buf_ref.at[slot],
                sem_ref.at[slot],
            )

        @pl.when(nb > 0)
        def _warmup():
            copy_k(0, 0).start()

        # DMA protocol twin: db_dma_schedule() restates this exact
        # start/wait/use order in plain Python for the semaphore-pairing
        # lint -- change both together.
        def body(k, acc):
            slot = jax.lax.rem(k, 2)

            @pl.when(k + 1 < nb)
            def _prefetch():
                # Start spike k+1's DMA into the other buffer BEFORE
                # waiting on spike k: the gather overlaps the accumulate.
                copy_k(1 - slot, k + 1).start()

            copy_k(slot, k).wait()
            return acc + w_buf_ref[slot].astype(jnp.float32)

        # Only the live slots: the loop bound IS the compact-list length,
        # so padding costs no DMA, no add -- a quiet row costs nothing.
        return jax.lax.fori_loop(0, nb, body,
                                 jnp.zeros((1, block_n), jnp.float32))

    b0 = pl.program_id(0) * BLOCK_B
    rows = [gather_row(b0 + i) for i in range(BLOCK_B)]
    # Assemble the (8, bN) synaptic-input tile row by row (a select per
    # row: exact, and no unaligned sublane store).
    row_id = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_B, block_n), 0)
    acc = jnp.zeros((BLOCK_B, block_n), jnp.float32)
    for i, row in enumerate(rows):
        acc = jnp.where(row_id == i, row, acc)

    v = v_ref[...].astype(jnp.float32)
    r = r_in_ref[...]
    drive = drive_ref[...].astype(jnp.float32) if has_drive else None
    v_new, r_new, spiked = _lif_epilogue(
        acc, v, r, drive,
        vth_ref[...].astype(jnp.float32),
        leak_ref[...].astype(jnp.float32),
        rref_ref[...],
        gain_ref[...].astype(jnp.float32),
        ibias_ref[...].astype(jnp.float32),
        vreset_ref[...].astype(jnp.float32),
        mode,
    )
    v_out_ref[...] = v_new.astype(v_out_ref.dtype)
    r_out_ref[...] = r_new.astype(r_out_ref.dtype)
    y_out_ref[...] = spiked.astype(y_out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("mode", "block_n", "interpret"),
)
def event_lif_dispatch_db(
    idx: jax.Array,
    w: jax.Array,
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
    counts: jax.Array,
    mode: str = "fixed_leak",
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Double-buffered compact-spike-list event tick (one ``pallas_call``).

    Shapes (N pre-padded to ``block_n`` multiples; any B):

    * ``idx``: (B, k_active) i32 -- spiking presynaptic row ids, the
      ``counts[b]`` live ones first and ascending (the tie-stable top-k
      packs real spikes first); the rest are never read.
    * ``counts``: (B,) i32 live slots per row.
    * ``w``: (K, N) effective weights ``W*C``.
    * ``v``/``drive``: (B, N) f32; ``r``: (B, N) i32; params: (N,).

    Returns ``(v', r', y')`` each (B, N).
    """
    B, k_active = idx.shape
    K, N = w.shape
    if N % block_n:
        raise ValueError(f"N={N} must be a multiple of block_n={block_n}")
    if mode not in ("fixed_leak", "euler"):
        raise ValueError(f"event dispatch supports fixed_leak|euler, got {mode!r}")
    if counts.shape != (B,):
        raise ValueError(f"counts must be shape ({B},), got {counts.shape}")
    has_drive = drive is not None

    # Pad the batch to whole 8-row tiles: padded rows have no live spikes
    # (zero DMAs) and are sliced away below.
    Bp = -(-B // BLOCK_B) * BLOCK_B
    pad_b = lambda a, value=0: jnp.pad(
        a, ((0, Bp - B),) + ((0, 0),) * (a.ndim - 1), constant_values=value)

    launch = event_db_launch(
        B=Bp, K=K, N=N, k_active=k_active,
        dtypes={"w": w.dtype, "v": v.dtype, "r": r.dtype,
                "drive": drive.dtype if has_drive else None,
                "param": v_th.dtype},
        has_drive=has_drive, block_n=block_n)
    row = lambda a: a.reshape(1, N)
    arrays = {"w": w.reshape(K, 1, N), "v": pad_b(v), "r": pad_b(r, 1),
              "drive": pad_b(drive) if has_drive else None,
              "v_th": row(v_th), "leak": row(leak), "r_ref": row(r_ref),
              "gain": row(gain), "i_bias": row(i_bias),
              "v_reset": row(v_reset)}

    kernel = functools.partial(_event_db_kernel, mode=mode,
                               has_drive=has_drive, block_n=block_n)
    outs = pl.pallas_call(
        kernel,
        grid_spec=launch.grid_spec(),
        out_shape=launch.out_shapes(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pad_b(idx.astype(jnp.int32)), pad_b(counts.astype(jnp.int32)),
      *launch.gather(arrays))
    return tuple(o[:B] for o in outs)

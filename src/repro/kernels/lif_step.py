"""Fused LIF tick kernel: masked synaptic matmul + neuron state update.

This is the paper's per-neuron datapath (charge accumulation -> leak ->
threshold -> reset -> refractory) restated for the TPU memory hierarchy:

* The FPGA instantiates N parallel neuron state machines, each muxing N
  single-bit inputs. The TPU equivalent streams (bB x bK) spike tiles and
  (bK x bN) weight/connection tiles HBM->VMEM, feeds the MXU with the
  masked product, and applies the LIF nonlinearity in VREGs before the
  (bB x bN) state tiles leave VMEM -- one HBM round-trip per tick instead
  of three (matmul out, mask product, state update).
* The connection-list mask is fused into the matmul operand (``w * c``
  per tile in VMEM) so the gated synapse matrix is never materialized in
  HBM -- the mux-"routes-a-zero" semantics at zero bandwidth cost.

Grid: ``(B/bB, N/bN, K/bK)`` with K the presynaptic (contraction) axis;
K-steps accumulate into a VMEM f32 scratch; the LIF epilogue fires on the
last K step. Blocks default to MXU-aligned (128, 128, 512).

All shapes must be pre-padded to block multiples by the caller
(:mod:`repro.kernels.ops` handles padding + unpadding).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.launch_spec import KernelLaunch, Operand, Scratch

DEFAULT_BLOCK_B = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 512


def lif_launch(*, B: int, K: int, N: int, dtypes: dict,
               block_b: int = DEFAULT_BLOCK_B,
               block_n: int = DEFAULT_BLOCK_N,
               block_k: int = DEFAULT_BLOCK_K) -> KernelLaunch:
    """Launch descriptor for :func:`fused_lif_step` (see
    :mod:`repro.kernels.launch_spec`).  ``dtypes`` maps ``s, w, c, v, r,
    drive, param`` to dtypes (``drive`` always present: the entry point
    substitutes a zeros placeholder when the caller passes None)."""
    bn = ((block_b, block_n), lambda i, j, k: (i, j))
    param = ((1, block_n), lambda i, j, k: (0, j))
    kn = ((block_k, block_n), lambda i, j, k: (k, j))
    inputs = [
        Operand("s", (B, K), dtypes["s"], (block_b, block_k),
                lambda i, j, k: (i, k)),
        Operand("w", (K, N), dtypes["w"], *kn),
        Operand("c", (K, N), dtypes["c"], *kn),
        Operand("v", (B, N), dtypes["v"], *bn),
        Operand("r", (B, N), dtypes["r"], *bn),
        Operand("drive", (B, N), dtypes["drive"], *bn),
    ]
    inputs += [Operand(pname, (1, N), dtypes.get(pname, dtypes["param"]),
                       *param)
               for pname in ("v_th", "leak", "r_ref", "gain", "i_bias",
                             "v_reset")]
    outputs = (Operand("v_out", (B, N), dtypes["v"], *bn),
               Operand("r_out", (B, N), dtypes["r"], *bn),
               Operand("y_out", (B, N), dtypes["s"], *bn))
    return KernelLaunch(
        name="lif_step",
        grid=(B // block_b, N // block_n, K // block_k),
        inputs=tuple(inputs),
        outputs=outputs,
        scratch=(Scratch("vmem", (block_b, block_n), jnp.float32),),
    )


def _lif_epilogue(acc, v, r, drive, v_th, leak, r_ref, gain, i_bias, v_reset, mode):
    """Shared epilogue math (f32 in VREGs)."""
    syn = acc if drive is None else acc + drive
    if mode == "euler":
        v_tilde = (1.0 - leak) * v + gain * (syn + i_bias)
    else:  # fixed_leak
        active = (v != 0).astype(jnp.float32)
        leak_step = jnp.minimum(leak * active, jnp.abs(v))
        v_tilde = v + syn + i_bias - jnp.sign(v) * leak_step
    not_ref = r == 0
    spiked = (v_tilde >= v_th) & not_ref
    hold = spiked | (r > 0)
    v_new = jnp.where(hold, v_reset, v_tilde)
    r_new = jnp.where(spiked, r_ref, jnp.maximum(r - 1, 0))
    return v_new, r_new, spiked


def _fused_kernel(
    # inputs
    s_ref, w_ref, c_ref, v_ref, r_ref_in, drive_ref,
    vth_ref, leak_ref, rref_ref, gain_ref, ibias_ref, vreset_ref,
    # outputs
    v_out_ref, r_out_ref, y_out_ref,
    # scratch
    acc_ref,
    *, mode: str, has_drive: bool,
):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Masked MXU tile: the mux fabric. w*c fused in VMEM, never in HBM.
    wc = (w_ref[...] * c_ref[...].astype(w_ref.dtype)).astype(jnp.float32)
    acc_ref[...] += jnp.dot(
        s_ref[...].astype(jnp.float32), wc, preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        v = v_ref[...].astype(jnp.float32)
        r = r_ref_in[...]
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
        v_out_ref[...] = v_new.astype(v_out_ref.dtype)
        r_out_ref[...] = r_new.astype(r_out_ref.dtype)
        y_out_ref[...] = spiked.astype(y_out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("mode", "block_b", "block_n", "block_k", "interpret"),
)
def fused_lif_step(
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
    block_b: int = DEFAULT_BLOCK_B,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One fused network tick. Shapes (pre-padded to block multiples):

    ``s``: (B, K) previous-tick spikes; ``w, c``: (K, N); ``v, drive``: (B, N);
    ``r``: (B, N) i32; per-neuron params: (N,) (reshaped to (1, N) blocks).
    Returns ``(v', r', y')`` each (B, N).
    """
    B, K = s.shape
    N = w.shape[1]
    if B % block_b or N % block_n or K % block_k:
        raise ValueError(
            f"shapes must be block-aligned: B={B}%{block_b}, N={N}%{block_n}, K={K}%{block_k}"
        )
    has_drive = drive is not None
    if drive is None:
        drive = jnp.zeros((B, N), v.dtype)  # placeholder operand (unread)

    row = lambda a: a.reshape(1, N)
    launch = lif_launch(
        B=B, K=K, N=N,
        dtypes={"s": s.dtype, "w": w.dtype, "c": c.dtype, "v": v.dtype,
                "r": r.dtype, "drive": drive.dtype, "param": v_th.dtype},
        block_b=block_b, block_n=block_n, block_k=block_k)

    kernel = functools.partial(_fused_kernel, mode=mode, has_drive=has_drive)
    v_new, r_new, y = pl.pallas_call(
        kernel,
        grid_spec=launch.grid_spec(),
        out_shape=launch.out_shapes(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*launch.gather(
        {"s": s, "w": w, "c": c, "v": v, "r": r, "drive": drive,
         "v_th": row(v_th), "leak": row(leak), "r_ref": row(r_ref),
         "gain": row(gain), "i_bias": row(i_bias),
         "v_reset": row(v_reset)}))
    return v_new, r_new, y

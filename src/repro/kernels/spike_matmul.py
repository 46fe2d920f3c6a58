"""Standalone masked spike matmul kernel: ``out = s @ (w * c)``.

The building block of the fused tick kernel, exposed separately because the
scaled framework also uses it for (a) input projection through large
``w_in`` matrices and (b) the event-driven sparse-dispatch comparison
(benchmarks). Same tiling story as :mod:`repro.kernels.lif_step`: the
connection mask is applied tile-by-tile in VMEM so the gated matrix never
exists in HBM, halving weight-side HBM traffic vs a separate mask kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_B = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 512


def _kernel(s_ref, w_ref, c_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    wc = (w_ref[...] * c_ref[...].astype(w_ref.dtype)).astype(jnp.float32)
    acc_ref[...] += jnp.dot(
        s_ref[...].astype(jnp.float32), wc, preferred_element_type=jnp.float32
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n", "block_k", "interpret", "out_dtype")
)
def spike_matmul(
    s: jax.Array,
    w: jax.Array,
    c: jax.Array,
    *,
    block_b: int = DEFAULT_BLOCK_B,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """``(B,K) @ ((K,N) * (K,N)) -> (B,N)``, f32 MXU accumulation."""
    B, K = s.shape
    K2, N = w.shape
    if K != K2 or w.shape != c.shape:
        raise ValueError(f"shape mismatch: s{s.shape} w{w.shape} c{c.shape}")
    if B % block_b or N % block_n or K % block_k:
        raise ValueError(
            f"shapes must be block-aligned: B={B}%{block_b}, N={N}%{block_n}, K={K}%{block_k}"
        )
    grid = (B // block_b, N // block_n, K // block_k)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_b, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(s, w, c)

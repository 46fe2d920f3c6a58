"""Fused STDP tick kernel: trace decay + outer-product weight update.

The learning-tick datapath restated for the TPU memory hierarchy
(companion to :mod:`repro.kernels.lif_step`, which owns the inference
half of the tick):

* NeuroCoreX co-locates a trace register and a multiply-accumulate with
  every synapse cell, so learning costs zero extra memory traffic.  The
  TPU restatement: the batched pair-STDP update is two MXU matmuls
  contracted over the batch axis,

      dw = a_plus * x_pre'^T @ s_post  -  a_minus * s_pre^T @ x_post',

  computed tile-by-tile in VMEM while the weight tile is already resident
  for the update -- weights, eligibility, and traces make exactly one HBM
  round-trip per learning tick instead of four (trace decay out,
  LTP matmul out, LTD matmul out, clip/update out).
* The trace decays (``x' = decay * x + s``, one FMA in VREGs) are fused
  at the head of the same pass; the updated traces are both an output and
  the operand of the LTP/LTD products, so they never exist in HBM in
  their pre-decay form.
* The connection-list mask ``C`` gates ``dw`` in VMEM (a mux that routes
  a zero cannot learn), and the epilogue clips to the register bank's u8
  domain ``[w_min, w_max]`` so the weights stay serializable at every
  tick.

Grid: ``(K/bk, N/bn, B/bB)`` with the batch axis B innermost (the
contraction axis of both outer products); per-(i,j) partial products
accumulate into a VMEM f32 scratch and the weight/eligibility epilogue
fires on the last B step.  Trace outputs are recomputed and rewritten on
every visit of their block (their buffers are re-fetched undefined when
the grid axis their index map ignores advances).

All shapes must be pre-padded to block multiples by the caller
(:mod:`repro.kernels.ops` handles padding + unpadding; zero-padding is
exact: padded batch rows contribute 0 to both products, padded synapses
have C == 0).

Hyper-parameters enter as compile-time constants (like the LIF ``mode``)
-- they are synthesis-time learning-engine configuration; only the
*reward* is a runtime scalar (SMEM), because R-STDP's dopamine signal
changes every tick.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.launch_spec import KernelLaunch, Operand, Scratch

DEFAULT_BLOCK_B = 128
DEFAULT_BLOCK_K = 128
DEFAULT_BLOCK_N = 128


def stdp_launch(*, B: int, K: int, N: int, dtypes: dict,
                block_b: int = DEFAULT_BLOCK_B,
                block_k: int = DEFAULT_BLOCK_K,
                block_n: int = DEFAULT_BLOCK_N) -> KernelLaunch:
    """Launch descriptor for :func:`fused_stdp_step` (see
    :mod:`repro.kernels.launch_spec`): grid ``(K/bk, N/bn, B/bB)``, batch
    innermost as the contraction axis of both outer products.  ``dtypes``
    maps ``s_pre, x_pre, s_post, x_post, w, c, elig, reward`` to dtypes.
    """
    bk = ((block_b, block_k), lambda i, j, b: (b, i))
    bn = ((block_b, block_n), lambda i, j, b: (b, j))
    kn = ((block_k, block_n), lambda i, j, b: (i, j))
    inputs = (
        Operand("s_pre", (B, K), dtypes["s_pre"], *bk),
        Operand("x_pre", (B, K), dtypes["x_pre"], *bk),
        Operand("s_post", (B, N), dtypes["s_post"], *bn),
        Operand("x_post", (B, N), dtypes["x_post"], *bn),
        Operand("w", (K, N), dtypes["w"], *kn),
        Operand("c", (K, N), dtypes["c"], *kn),
        Operand("elig", (K, N), dtypes["elig"], *kn),
        # R-STDP's dopamine scalar is runtime data: SMEM, not a constant.
        Operand("reward", (1, 1), dtypes["reward"], (1, 1),
                lambda i, j, b: (0, 0), memory_space="smem"),
    )
    outputs = (
        Operand("w_out", (K, N), dtypes["w"], *kn),
        Operand("elig_out", (K, N), dtypes["elig"], *kn),
        Operand("x_pre_out", (B, K), dtypes["x_pre"], *bk),
        Operand("x_post_out", (B, N), dtypes["x_post"], *bn),
    )
    return KernelLaunch(
        name="stdp_update",
        grid=(K // block_k, N // block_n, B // block_b),
        inputs=inputs,
        outputs=outputs,
        scratch=(Scratch("vmem", (block_k, block_n), jnp.float32),),
    )


def _stdp_kernel(
    # inputs
    spre_ref, xpre_ref, spost_ref, xpost_ref, w_ref, c_ref, elig_ref,
    reward_ref,
    # outputs
    w_out_ref, elig_out_ref, xpre_out_ref, xpost_out_ref,
    # scratch
    acc_ref,
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
):
    b = pl.program_id(2)
    nb = pl.num_programs(2)
    f32 = jnp.float32

    @pl.when(b == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Fused trace decay (one FMA; the traces never round-trip pre-decay).
    x_pre_new = decay_pre * xpre_ref[...].astype(f32) + spre_ref[...].astype(f32)
    x_post_new = (
        decay_post * xpost_ref[...].astype(f32) + spost_ref[...].astype(f32))

    # Batched pair STDP == two MXU products contracted over the batch axis.
    contract_b = (((0,), (0,)), ((), ()))
    ltp = jax.lax.dot_general(
        x_pre_new, spost_ref[...].astype(f32), contract_b,
        preferred_element_type=f32)
    ltd = jax.lax.dot_general(
        spre_ref[...].astype(f32), x_post_new, contract_b,
        preferred_element_type=f32)
    acc_ref[...] += a_plus * ltp - a_minus * ltd

    # Trace outputs are revisited across the grid axis their index map
    # ignores (j for x_pre, i for x_post), and a revisited output buffer is
    # re-fetched undefined -- so write on *every* visit (the value is
    # identical each time; the FMA is already in registers).
    xpre_out_ref[...] = x_pre_new.astype(xpre_out_ref.dtype)
    xpost_out_ref[...] = x_post_new.astype(xpost_out_ref.dtype)

    @pl.when(b == nb - 1)
    def _epilogue():
        cf = c_ref[...].astype(f32)
        dw = acc_ref[...] * cf                      # the mux gates learning
        w = w_ref[...].astype(f32)
        if rule == "rstdp":
            elig_new = decay_elig * elig_ref[...].astype(f32) + dw
            upd = lr_reward * reward_ref[0, 0].astype(f32) * elig_new
        else:
            elig_new = elig_ref[...].astype(f32)
            upd = dw
        # Non-plastic synapses (c == 0) pass through bit-identical (not
        # even clipped): a frozen inhibitory block may share the matrix.
        w_new = jnp.where(cf > 0, jnp.clip(w + upd, w_min, w_max), w)
        w_out_ref[...] = w_new.astype(w_out_ref.dtype)
        elig_out_ref[...] = elig_new.astype(elig_out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "rule", "a_plus", "a_minus", "decay_pre", "decay_post", "decay_elig",
        "lr_reward", "w_min", "w_max", "block_b", "block_k", "block_n",
        "interpret",
    ),
)
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
    block_b: int = DEFAULT_BLOCK_B,
    block_k: int = DEFAULT_BLOCK_K,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused learning tick. Shapes (pre-padded to block multiples):

    ``s_pre, x_pre``: (B, K); ``s_post, x_post``: (B, N);
    ``w, c, elig``: (K, N); ``reward``: (1, 1) runtime scalar.
    Returns ``(w', elig', x_pre', x_post')`` -- semantics of
    :func:`repro.kernels.ref.fused_stdp_step_ref`.
    """
    B, K = s_pre.shape
    N = s_post.shape[1]
    if B % block_b or K % block_k or N % block_n:
        raise ValueError(
            f"shapes must be block-aligned: B={B}%{block_b}, "
            f"K={K}%{block_k}, N={N}%{block_n}")
    launch = stdp_launch(
        B=B, K=K, N=N,
        dtypes={"s_pre": s_pre.dtype, "x_pre": x_pre.dtype,
                "s_post": s_post.dtype, "x_post": x_post.dtype,
                "w": w.dtype, "c": c.dtype, "elig": elig.dtype,
                "reward": reward.dtype},
        block_b=block_b, block_k=block_k, block_n=block_n)

    kernel = functools.partial(
        _stdp_kernel,
        rule=rule, a_plus=a_plus, a_minus=a_minus,
        decay_pre=decay_pre, decay_post=decay_post, decay_elig=decay_elig,
        lr_reward=lr_reward, w_min=w_min, w_max=w_max,
    )
    w_new, elig_new, x_pre_new, x_post_new = pl.pallas_call(
        kernel,
        grid_spec=launch.grid_spec(),
        out_shape=launch.out_shapes(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*launch.gather(
        {"s_pre": s_pre, "x_pre": x_pre, "s_post": s_post,
         "x_post": x_post, "w": w, "c": c, "elig": elig,
         "reward": reward.reshape(1, 1)}))
    return w_new, elig_new, x_pre_new, x_post_new

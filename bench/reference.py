"""The plain reference: the paper's fixed-leak LIF fabric (arXiv:2512.10180
Eq. 5) with pair STDP, written out in ``jax.numpy`` with no kernels, no
slots, no padding and no chunking.  It imports nothing of the program.

One tick, for every neuron at once::

    syn   = y_prev @ (W * C) + ext_t @ W_in
    v~    = v + syn - sign(v) * min(leak * [v != 0], |v|)
    spike = v~ >= v_th  and  r == 0
    v'    = 0 if spike or r > 0 else v~
    r'    = r_ref if spike else max(r - 1, 0)

and, for a plastic fabric, after the neuron update (pre = y_prev,
post = the new spikes; only while the tick lies inside the budget)::

    x_pre'  = d_pre * x_pre + pre          x_post' = d_post * x_post + post
    dW      = (a_plus * x_pre' (x) post - a_minus * pre (x) x_post') * C
    W'      = clip(W + dW, w_min, w_max) where C, else W

``dtype`` is the precision the whole computation runs in: float32 at
``highest`` matmul precision for the reference, bfloat16 for the control
that must fail the comparison.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, dtype):
    return jnp.matmul(a.astype(dtype), b.astype(dtype), precision=HIGHEST,
                      preferred_element_type=dtype)


def lif(v, r, syn, v_th, leak, r_ref):
    zero = jnp.zeros((), v.dtype)
    active = (v != 0).astype(v.dtype)
    leak_step = jnp.minimum(leak * active, jnp.abs(v))
    vt = v + syn - jnp.sign(v) * leak_step
    spiked = (vt >= v_th) & (r == 0)
    v2 = jnp.where(spiked | (r > 0), zero, vt)
    r2 = jnp.where(spiked, r_ref, jnp.maximum(r - 1, 0))
    return v2, r2, spiked.astype(v.dtype)


@functools.partial(jax.jit, static_argnames=("dtype",))
def frozen_counts(t: Dict, ext, budget, *, dtype=jnp.float32):
    """Spike counts of every neuron over each row's budget.

    ``ext``: (B, T, n) input per tick; ``budget``: (B,) ticks that count.
    Returns (B, n) f32 counts."""
    n = t["w"].shape[0]
    B, T = ext.shape[:2]
    w = t["w"] * t["c"]
    v_th, leak = t["v_th"].astype(dtype), t["leak"].astype(dtype)

    def tick(carry, xs):
        v, r, y, counts = carry
        e, k = xs
        syn = _dot(y, w, dtype) + _dot(e, t["w_in"], dtype)
        v, r, y2 = lif(v, r, syn, v_th, leak, t["r_ref"])
        counts = counts + y2.astype(jnp.float32) * (k < budget)[:, None]
        return (v, r, y2, counts), None

    z = jnp.zeros((B, n), dtype)
    init = (z, jnp.zeros((B, n), jnp.int32), z, jnp.zeros((B, n), jnp.float32))
    (_, _, _, counts), _ = jax.lax.scan(
        tick, init, (jnp.swapaxes(ext, 0, 1), jnp.arange(T)))
    return counts


@functools.partial(jax.jit, static_argnames=("rule", "dtype"))
def plastic_request(t: Dict, w, ext, budget, rule: tuple, *,
                    dtype=jnp.float32):
    """One request on a plastic fabric from weights ``w``; returns
    (learned weights, counts).  ``ext``: (T, n); ``rule``: (a_plus,
    a_minus, d_pre, d_post, w_min, w_max)."""
    a_plus, a_minus, d_pre, d_post, w_min, w_max = rule
    n = w.shape[0]
    c = t["c"].astype(dtype)
    v_th, leak = t["v_th"].astype(dtype), t["leak"].astype(dtype)
    cast = lambda x: jnp.asarray(x, dtype)

    def tick(carry, xs):
        v, r, y, xp, xq, w, counts = carry
        e, k = xs
        syn = _dot(y[None], w * c, dtype)[0] + _dot(e[None], t["w_in"],
                                                    dtype)[0]
        v, r, y2 = lif(v, r, syn, v_th, leak, t["r_ref"])
        xp2 = cast(d_pre) * xp + y
        xq2 = cast(d_post) * xq + y2
        dw = (cast(a_plus) * jnp.outer(xp2, y2)
              - cast(a_minus) * jnp.outer(y, xq2)) * c
        w2 = jnp.where(c > 0, jnp.clip(w + dw, cast(w_min), cast(w_max)), w)
        on = k < budget
        counts = counts + y2.astype(jnp.float32) * on
        return (v, r, y2, jnp.where(on, xp2, xp), jnp.where(on, xq2, xq),
                jnp.where(on, w2, w), counts), None

    z = jnp.zeros((n,), dtype)
    init = (z, jnp.zeros((n,), jnp.int32), z, z, z, w.astype(dtype),
            jnp.zeros((n,), jnp.float32))
    out, _ = jax.lax.scan(tick, init, (ext.astype(dtype),
                                       jnp.arange(ext.shape[0])))
    return out[5], out[6]


def run_frozen(t: Dict, exts, budgets, T: int, *, dtype=jnp.float32,
               block: int = 128) -> np.ndarray:
    """Counts for a list of requests on one frozen tenant, ``block`` rows
    at a time.  ``exts``: list of (ticks, n_in) arrays."""
    n = t["w"].shape[0]
    out = []
    for lo in range(0, len(exts), block):
        part = exts[lo:lo + block]
        ext = np.zeros((block, T, n), np.float32)
        bud = np.zeros((block,), np.int32)
        for i, e in enumerate(part):
            k = min(e.shape[0], T)
            ext[i, :k, :e.shape[1]] = e[:k]
            bud[i] = min(budgets[lo + i], T)
        counts = frozen_counts(t, jnp.asarray(ext), jnp.asarray(bud),
                               dtype=dtype)
        out.append(np.asarray(counts)[:len(part)])
    return np.concatenate(out) if out else np.zeros((0, n), np.float32)


def run_plastic(t: Dict, exts, budgets, T: int, rule: tuple, *,
                dtype=jnp.float32, w0: Optional[jax.Array] = None):
    """Chain the requests in the order given, each from the weights the
    previous one learned; returns (final weights, list of counts)."""
    n = t["w"].shape[0]
    w = t["w"] if w0 is None else w0
    counts = []
    for e, b in zip(exts, budgets):
        ext = np.zeros((T, n), np.float32)
        k = min(e.shape[0], T)
        ext[:k, :e.shape[1]] = e[:k]
        w, cnt = plastic_request(t, w, jnp.asarray(ext),
                                 jnp.asarray(min(b, T), jnp.int32), rule,
                                 dtype=dtype)
        counts.append(cnt)
    return w, [np.asarray(c) for c in counts]


# -- the sharded all-to-all stream -------------------------------------------

@functools.partial(jax.jit, static_argnames=("dtype",))
def stream_ticks(state, w_lv, win_lv, lifp, scales, ext, *,
                 dtype=None):
    """Ticks of the implicit all-to-all fabric (C = 1 everywhere).

    ``dtype=None`` is the exact reference: int8 levels times int8 spikes
    summed in int32 (every level and every sum is an integer, so this is
    the real-number result), then scaled by powers of two in f32.  A
    float dtype runs the whole tick in that dtype (the control).
    ``ext``: (T, n_in) inputs; returns (state, (T, n) spikes)."""
    s_w, s_in = scales

    def tick(st, e):
        v, r, y = st
        if dtype is None:
            syn = (jax.lax.dot(y.astype(jnp.int8)[None], w_lv,
                               preferred_element_type=jnp.int32)[0]
                   .astype(jnp.float32) * s_w
                   + jax.lax.dot(e.astype(jnp.int8)[None], win_lv,
                                 preferred_element_type=jnp.int32)[0]
                   .astype(jnp.float32) * s_in)
            v_th, leak = lifp["v_th"], lifp["leak"]
        else:
            syn = (_dot(y[None], w_lv.astype(dtype) * jnp.asarray(s_w, dtype),
                        dtype)[0]
                   + _dot(e[None], win_lv.astype(dtype)
                          * jnp.asarray(s_in, dtype), dtype)[0])
            v_th, leak = lifp["v_th"].astype(dtype), lifp["leak"].astype(dtype)
        v2, r2, y2 = lif(v, r, syn.astype(v.dtype), v_th.astype(v.dtype),
                         leak.astype(v.dtype), lifp["r_ref"])
        return (v2, r2, y2), y2

    return jax.lax.scan(tick, state, ext.astype(jnp.float32))


def stream_state(n: int, dtype=jnp.float32, sharding=None):
    z = jnp.zeros((n,), dtype)
    st = (z, jnp.zeros((n,), jnp.int32), z)
    return st if sharding is None else jax.device_put(st, sharding)


@functools.partial(jax.jit, static_argnames=("T", "n_out", "dtype"))
def stream_counts(state, w_lv, win_lv, lifp, scales, ext, T, n_out, *,
                  dtype=None):
    """:func:`stream_ticks` over consecutive requests of ``T`` ticks each;
    returns (state, (requests, n_out) counts of the last ``n_out``
    neurons)."""
    state, y = stream_ticks(state, w_lv, win_lv, lifp, scales, ext,
                            dtype=dtype)
    n = y.shape[-1]
    return state, y[:, n - n_out:].astype(jnp.float32).reshape(
        -1, T, n_out).sum(axis=1)

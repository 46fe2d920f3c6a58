"""Event-driven dispatch: overflow contract, fan-in gather, Pallas kernel.

No hypothesis dependency (unlike test_kernels.py) so these always run:
they pin the two correctness contracts the event backend lives by --
overflow can never silently drop spikes, and both dispatch strategies
plus the Pallas kernel (interpret mode -- the same body the TPU runs)
are bit-compatible with the dense reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import connectivity
from repro.core.lif import LIFParams, LIFState
from repro.core.network import SNNParams
from repro.kernels import ops
from repro.kernels.ops import EventFanIn
from repro.kernels.ref import fused_lif_step_ref, spike_matmul_ref

jax.config.update("jax_platform_name", "cpu")


class TestOverflowContract:
    def test_exact_past_k_active_via_dense_fallback(self):
        """Regression: rows spiking MORE than k_active used to be silently
        truncated by the top_k (a wrong synaptic input); the overflow now
        falls back to the dense product and stays exact at any rate."""
        rng = np.random.default_rng(0)
        b, n, k_active = 6, 64, 4
        w = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
        c = jnp.asarray((rng.random((n, n)) < 0.5).astype(np.float32))
        s = np.zeros((b, n), np.float32)
        s[0, : k_active + 3] = 1.0                   # one overflowing row
        s[1:] = (rng.random((b - 1, n)) < 0.8)      # high-rate rows
        got = ops.event_spike_matmul(jnp.asarray(s), w, c, k_active=k_active)
        want = spike_matmul_ref(jnp.asarray(s), w, c)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_unchecked_mode_documents_the_old_bug(self):
        """overflow="unchecked" reproduces the truncation (that is its
        contract); the default must NOT."""
        n, k_active = 32, 2
        w = jnp.ones((n, n))
        c = jnp.ones((n, n))
        s = jnp.ones((1, n))
        want = spike_matmul_ref(s, w, c)
        trunc = ops.event_synaptic_input(s, w * c, k_active=k_active,
                                         overflow="unchecked")
        assert float(trunc[0, 0]) == k_active        # dropped n-k real spikes
        safe = ops.event_synaptic_input(s, w * c, k_active=k_active)
        np.testing.assert_array_equal(np.asarray(safe), np.asarray(want))

    def test_strict_mode_raises_under_checkify(self):
        from jax.experimental import checkify

        rng = np.random.default_rng(0)
        n, k_active = 32, 4
        w = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
        c = jnp.asarray((rng.random((n, n)) < 0.5).astype(np.float32))
        fn = checkify.checkify(
            lambda s: ops.event_spike_matmul(s, w, c, k_active=k_active,
                                             overflow="strict"))
        ok = jnp.zeros((2, n)).at[:, :k_active].set(1.0)
        err, _ = fn(ok)
        err.throw()                                  # no error at low rate
        err, _ = fn(jnp.ones((2, n)))
        with pytest.raises(Exception, match="event dispatch overflow"):
            err.throw()

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="overflow"):
            ops.event_synaptic_input(jnp.ones((1, 8)), jnp.ones((8, 8)),
                                     overflow="typo")


class TestFanInGather:
    def test_matches_dense(self):
        rng = np.random.default_rng(7)
        n = 48
        c_np = np.asarray(connectivity.sparse_random(n, 0.15, seed=7))
        s = jnp.asarray((rng.random((5, n)) < 0.3).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
        wc = w * jnp.asarray(c_np, jnp.float32)
        got = ops.event_synaptic_input(s, wc,
                                       fan_in=EventFanIn.from_dense(c_np))
        want = spike_matmul_ref(s, w, jnp.asarray(c_np, jnp.float32))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_rate_independent_no_overflow(self):
        """The gather path reads topology, not activity: saturating input
        needs no fallback and stays exact."""
        n = 24
        c_np = np.asarray(connectivity.sparse_random(n, 0.2, seed=9))
        rng = np.random.default_rng(9)
        w = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
        wc = w * jnp.asarray(c_np, jnp.float32)
        s = jnp.ones((3, n))
        got = ops.event_synaptic_input(s, wc,
                                       fan_in=EventFanIn.from_dense(c_np))
        want = spike_matmul_ref(s, w, jnp.asarray(c_np, jnp.float32))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _case(b, n, *, density=0.3, seed=None, weights="dyadic"):
    """A random fabric and batch-``b`` state.

    ``weights="dyadic"`` (the default): the u8 grid (k/256), on which
    every f32 summation order is exact, so a kernel-vs-jnp comparison is
    bitwise whatever order XLA's dot sums in -- and XLA:CPU picks that
    order per shape and per host (the top-k einsum of
    :func:`ops.event_synaptic_input` is not an ascending sum at b=16,
    n=256 on some hosts).  ``"uniform"``: f32 draws, on which a change
    of summation order shows in the low bits; compared against an
    explicit ascending sum (:func:`_ascending_syn`)."""
    rng = np.random.default_rng(n + b if seed is None else seed)
    c = connectivity.sparse_random(n, density, seed=n)
    w = (rng.uniform(0, 1, (n, n)) if weights == "uniform"
         else rng.integers(0, 256, (n, n)) / 256)
    params = SNNParams(
        w=jnp.asarray(w, jnp.float32),
        c=jnp.asarray(c, jnp.float32),
        w_in=jnp.eye(n, dtype=jnp.float32),
        lif=LIFParams.make(n, v_th=0.8, leak=0.2, r_ref=1))
    lif0 = LIFState(
        v=jnp.asarray(rng.normal(size=(b, n)), jnp.float32),
        r=jnp.asarray(rng.integers(0, 2, (b, n)), jnp.int32),
        y=jnp.zeros((b, n), jnp.float32))
    return rng, params, params.w * params.c, lif0


def _ascending_syn(s, wc) -> np.ndarray:
    """Synaptic input as the event kernel defines it: for each batch row,
    the f32 sum of the spiking rows of ``wc`` in ascending presynaptic
    order, one row at a time, from 0."""
    s, wc = np.asarray(s), np.asarray(wc, np.float32)
    out = np.zeros((s.shape[0], wc.shape[1]), np.float32)
    for b in range(s.shape[0]):
        for k in np.flatnonzero(s[b]):
            out[b] = out[b] + wc[k]
    return out


class TestEventKernel:
    @pytest.mark.parametrize("mode", ["fixed_leak", "euler"])
    @pytest.mark.parametrize("b,n,with_ext", [(4, 74, True), (3, 139, False),
                                              (8, 256, True)])
    def test_kernel_matches_jnp_path(self, mode, b, n, with_ext):
        """The Pallas event kernel (interpret mode -- the same body the TPU
        runs) is bit-exact vs the pure-jnp event reference, ragged N incl."""
        rng, params, wc, lif0 = _case(b, n)
        s = jnp.asarray((rng.random((b, n)) < 0.1).astype(np.float32))
        ext = jnp.asarray((rng.random((b, n)) < 0.2).astype(np.float32)) \
            if with_ext else None
        # Both sides jitted: XLA's FMA contraction decisions must match
        # for a bitwise comparison (eager-vs-jit differs in the last ulp
        # of the euler multiply-add chain).
        # k_active=n: every row fits its spike list, so the event arm
        # runs on this tick, never the dense overflow fallback.
        want = jax.jit(lambda l, sp, e: ops.event_lif_step(
            l, sp, params, e, wc, k_active=n, mode=mode, use_kernel=False))(lif0, s, ext)
        got = jax.jit(lambda l, sp, e: ops.event_lif_step(
            l, sp, params, e, wc, k_active=n, mode=mode, use_kernel=True,
            interpret=True))(lif0, s, ext)
        for name in ("v", "r", "y"):
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          np.asarray(getattr(want, name)),
                                          err_msg=name)

    def test_kernel_overflow_falls_back_dense(self):
        """Kernel path at saturating rate: the cond takes the dense fused
        kernel, so no spike is ever dropped."""
        b, n = 4, 64
        _, params, wc, _ = _case(b, n, density=0.5)
        lif0 = LIFState(v=jnp.zeros((b, n)), r=jnp.zeros((b, n), jnp.int32),
                        y=jnp.zeros((b, n)))
        s = jnp.ones((b, n))                 # every presynaptic neuron fires
        got = ops.event_lif_step(lif0, s, params, None, wc, k_active=4,
                                 use_kernel=True, interpret=True)
        want = fused_lif_step_ref(
            s, params.w, params.c, lif0.v, lif0.r, None,
            params.lif.v_th, params.lif.leak, params.lif.r_ref,
            params.lif.gain, params.lif.i_bias, params.lif.v_reset)
        np.testing.assert_allclose(np.asarray(got.v), np.asarray(want.v),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got.y), np.asarray(want.y))

    def test_kernel_path_is_inference_only(self):
        b, n = 2, 16
        _, params, wc, lif0 = _case(b, n)
        with pytest.raises(ValueError, match="inference-only"):
            ops.event_lif_step(lif0, jnp.zeros((b, n)), params, None, wc,
                               surrogate=True, use_kernel=True,
                               interpret=True)


class TestDoubleBufferedKernel:
    """The compact-spike-list kernel: per-row counts bound the DMA loop,
    a two-slot VMEM buffer overlaps row k+1's copy with row k's
    accumulate -- and none of that may change a single bit vs the jnp
    reference."""

    @pytest.mark.parametrize("mode", ["fixed_leak", "euler"])
    @pytest.mark.parametrize("b,n,with_ext", [(1, 74, True), (9, 139, False),
                                              (16, 256, True)])
    def test_db_matches_jnp_path(self, mode, b, n, with_ext):
        """Batch sizes around the kernel's 8-row tiles: one padded tile,
        a full tile plus one row, two full tiles."""
        rng, params, wc, lif0 = _case(b, n)
        s = jnp.asarray((rng.random((b, n)) < 0.1).astype(np.float32))
        ext = jnp.asarray((rng.random((b, n)) < 0.2).astype(np.float32)) \
            if with_ext else None
        # k_active=n: every row fits its spike list, so the event arm
        # runs on this tick, never the dense overflow fallback.
        want = jax.jit(lambda l, sp, e: ops.event_lif_step(
            l, sp, params, e, wc, k_active=n, mode=mode, use_kernel=False))(lif0, s, ext)
        got = jax.jit(lambda l, sp, e: ops.event_lif_step(
            l, sp, params, e, wc, k_active=n, mode=mode, use_kernel=True,
            interpret=True))(lif0, s, ext)
        for name in ("v", "r", "y"):
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          np.asarray(getattr(want, name)),
                                          err_msg=name)

    @pytest.mark.parametrize("mode", ["fixed_leak", "euler"])
    @pytest.mark.parametrize("b,n,with_ext", [(1, 74, True), (9, 139, False),
                                              (16, 256, True)])
    def test_db_sums_in_ascending_order(self, mode, b, n, with_ext):
        """Uniform f32 weights, where the order of a sum shows in its low
        bits: the kernel accumulates each row's spikes one at a time in
        ascending presynaptic order -- bit for bit, so a changed
        accumulation order or a mis-ordered spike list fails here."""
        from repro.core.lif import lif_step

        rng, params, wc, lif0 = _case(b, n, weights="uniform")
        s = jnp.asarray((rng.random((b, n)) < 0.1).astype(np.float32))
        ext = jnp.asarray((rng.random((b, n)) < 0.2).astype(np.float32)) \
            if with_ext else None
        syn = jnp.asarray(_ascending_syn(s, wc))
        # k_active=n: the event arm, never the dense overflow fallback.
        want = jax.jit(lambda l, sy, e: lif_step(
            l, sy if e is None else sy + e @ params.w_in, params.lif,
            mode=mode))(lif0, syn, ext)
        got = jax.jit(lambda l, sp, e: ops.event_lif_step(
            l, sp, params, e, wc, k_active=n, mode=mode, use_kernel=True,
            interpret=True))(lif0, s, ext)
        for name in ("v", "r", "y"):
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          np.asarray(getattr(want, name)),
                                          err_msg=name)

    def test_db_zero_spike_rows(self):
        """Rows with count==0 must skip the DMA loop entirely and still
        run the LIF epilogue (leak/refractory continue on silent input)."""
        b, n = 4, 64
        rng, params, wc, lif0 = _case(b, n)
        s = np.zeros((b, n), np.float32)
        s[1, 3] = 1.0                        # rows 0, 2, 3 fully silent
        got = ops.event_lif_step(lif0, jnp.asarray(s), params, None, wc,
                                 use_kernel=True, interpret=True)
        want = ops.event_lif_step(lif0, jnp.asarray(s), params, None, wc,
                                  use_kernel=False)
        for name in ("v", "r", "y"):
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          np.asarray(getattr(want, name)),
                                          err_msg=name)

    def test_db_ragged_counts(self):
        """Every row a different live count (0..k_active), padding tail
        untouched: the per-row bound is data, not shape."""
        b, n, k = 6, 80, 8
        rng, params, wc, lif0 = _case(b, n, seed=3)
        s = np.zeros((b, n), np.float32)
        for row in range(b):
            cols = rng.choice(n, size=row, replace=False)
            s[row, cols] = 1.0               # row r spikes exactly r rows
        got = ops.event_lif_step(lif0, jnp.asarray(s), params, None, wc,
                                 k_active=k, use_kernel=True, interpret=True)
        want = ops.event_lif_step(lif0, jnp.asarray(s), params, None, wc,
                                  k_active=k, use_kernel=False)
        for name in ("v", "r", "y"):
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          np.asarray(getattr(want, name)),
                                          err_msg=name)

    def test_db_overflow_falls_back_dense(self):
        b, n = 3, 64
        _, params, wc, _ = _case(b, n, density=0.5)
        lif0 = LIFState(v=jnp.zeros((b, n)), r=jnp.zeros((b, n), jnp.int32),
                        y=jnp.zeros((b, n)))
        s = jnp.ones((b, n))
        got = ops.event_lif_step(lif0, s, params, None, wc, k_active=4,
                                 use_kernel=True, interpret=True)
        want = fused_lif_step_ref(
            s, params.w, params.c, lif0.v, lif0.r, None,
            params.lif.v_th, params.lif.leak, params.lif.r_ref,
            params.lif.gain, params.lif.i_bias, params.lif.v_reset)
        np.testing.assert_allclose(np.asarray(got.v), np.asarray(want.v),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got.y), np.asarray(want.y))

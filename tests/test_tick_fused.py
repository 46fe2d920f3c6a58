"""Whole-tick megakernel (`kernels/tick_fused.py`): parity + recompile pins.

Five pins:

* **Parity vs the jnp reference** on the hard cases: per-synapse delays
  > 1, refractory counters live mid-rollout, learning on/off -- all
  bit-exact (spikes, membrane, refractory counters AND the delay ring).

* **Premasked == per-tile masked**: the frozen path's hoisted ``W*C``
  operand and the learning path's in-VMEM ``w*c`` produce identical
  results.

* **One trace across tick counts**: the circular delay pointer is a
  scalar-prefetch *runtime value*; stepping the same jitted tick through
  an entire ring cycle (every slot value) must never retrace.

* **Padding is exact**: ragged n exercises every pad path (weights,
  delay ring, per-neuron params) and still matches the reference.

* **The slot grid is the per-slot kernel**: ``vmap`` over slots at
  different ticks equals one unbatched call per slot, bit for bit.

Kernels run in interpret mode on CPU -- the same kernel body the TPU
executes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import connectivity
from repro.core.engine import EngineOptions, TickCarry, TickEngine
from repro.core.lif import LIFParams
from repro.core.network import (
    SNNParams, SNNState, learning_rollout, rollout,
)
from repro.kernels import ops
from repro.plasticity import PlasticityParams, PlasticityState

jax.config.update("jax_platform_name", "cpu")


def _params(n, c, *, seed=0, v_th=1.0, leak=0.2, r_ref=0, w_scale=2.0):
    rng = np.random.default_rng(seed)
    return SNNParams(
        w=jnp.asarray(rng.uniform(0, w_scale, (n, n)), jnp.float32),
        c=jnp.asarray(c, jnp.float32),
        w_in=jnp.eye(n, dtype=jnp.float32) * 2.0,
        lif=LIFParams.make(n, v_th=v_th, leak=leak, r_ref=r_ref))


def _ext(n, ticks, batch_shape=(), p=0.35, seed=1):
    rng = np.random.default_rng(seed)
    shape = (ticks,) + tuple(batch_shape) + (n,)
    return jnp.asarray((rng.random(shape) < p) * 1.0, jnp.float32)


def _assert_trees_bitexact(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestFusedParity:
    @pytest.mark.parametrize("max_delay", [2, 3, 4])
    def test_uniform_delay_ring(self, max_delay):
        """Delay-line read AND write inside the kernel, whole ring cycled."""
        n, ticks = 11, 3 * max_delay + 2
        p = _params(n, connectivity.sparse_random(n, 0.5, seed=max_delay),
                    v_th=0.8)
        st0 = SNNState.zeros((), n, max_delay=max_delay)
        ext = _ext(n, ticks, seed=max_delay)
        fin_j, ras_j = rollout(p, st0, ext, ticks, backend="jnp")
        fin_f, ras_f = rollout(p, st0, ext, ticks, backend="pallas_fused")
        np.testing.assert_array_equal(np.asarray(ras_j), np.asarray(ras_f))
        _assert_trees_bitexact(fin_j, fin_f)

    @pytest.mark.parametrize("max_delay", [2, 4])
    def test_per_synapse_delays(self, max_delay):
        """The d-major flattened contraction matches the reference einsum."""
        n, ticks = 7, 4 * max_delay
        rng = np.random.default_rng(max_delay)
        p = _params(n, connectivity.sparse_random(n, 0.6, seed=5), v_th=0.8)
        delays = jnp.asarray(rng.integers(1, max_delay + 1, (n, n)), jnp.int32)
        st0 = SNNState.zeros((), n, max_delay=max_delay)
        ext = _ext(n, ticks, p=0.3, seed=6)
        fin_j, ras_j = rollout(p, st0, ext, ticks, delays=delays, backend="jnp")
        fin_f, ras_f = rollout(p, st0, ext, ticks, delays=delays,
                               backend="pallas_fused")
        np.testing.assert_array_equal(np.asarray(ras_j), np.asarray(ras_f))
        _assert_trees_bitexact(fin_j, fin_f)

    def test_refractory_active(self):
        """r_ref > 0 with dense firing: the epilogue's refractory mask must
        hold spikes AND count down identically to the reference."""
        n, ticks = 10, 16
        p = _params(n, connectivity.sparse_random(n, 0.8, seed=2),
                    v_th=0.6, r_ref=3, w_scale=3.0)
        st0 = SNNState.zeros((2,), n, max_delay=2)
        ext = _ext(n, ticks, (2,), p=0.6, seed=3)
        fin_j, ras_j = rollout(p, st0, ext, ticks, backend="jnp")
        fin_f, ras_f = rollout(p, st0, ext, ticks, backend="pallas_fused")
        assert float(np.asarray(fin_j.lif.r).max()) > 0, "refractory never engaged"
        np.testing.assert_array_equal(np.asarray(ras_j), np.asarray(ras_f))
        _assert_trees_bitexact(fin_j, fin_f)

    @pytest.mark.parametrize("learn", [False, True])
    def test_learning_on_off(self, learn):
        """Same network, learning on vs off: fused matches jnp either way,
        and learning actually changes the weights (the hook really ran)."""
        n, ticks, b = 8, 12, 2
        c = connectivity.sparse_random(n, 0.6, seed=7)
        p = _params(n, c, v_th=0.9, w_scale=3.0)
        ext = _ext(n, ticks, (b,), p=0.5, seed=8)
        if not learn:
            st0 = SNNState.zeros((b,), n)
            fin_j, ras_j = rollout(p, st0, ext, ticks, backend="jnp")
            fin_f, ras_f = rollout(p, st0, ext, ticks, backend="pallas_fused")
            np.testing.assert_array_equal(np.asarray(ras_j), np.asarray(ras_f))
            _assert_trees_bitexact(fin_j, fin_f)
            return
        pp = PlasticityParams.make("stdp", a_plus=0.4, a_minus=0.2, w_max=16.0)
        st0 = SNNState.zeros((b,), n)
        pst0 = PlasticityState.zeros((b,), n)
        (f1, p1, w1), r1 = learning_rollout(
            p, st0, pst0, ext, ticks, plasticity=pp, backend="jnp")
        (f2, p2, w2), r2 = learning_rollout(
            p, st0, pst0, ext, ticks, plasticity=pp,
            backend="pallas_fused", plasticity_backend="jnp")
        assert not np.array_equal(np.asarray(w1), np.asarray(p.w)), \
            "plasticity hook never changed the weights"
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
        _assert_trees_bitexact((f1, p1), (f2, p2))

    def test_premasked_equals_per_tile_mask(self):
        """Frozen path (hoisted W*C operand) == learning-style (w, c) path."""
        n = 9
        p = _params(n, connectivity.sparse_random(n, 0.5, seed=1), v_th=0.7)
        st = SNNState.zeros((), n, max_delay=3)
        st = dataclasses.replace(
            st, delay_buf=st.delay_buf.at[0].set(1.0), tick=jnp.int32(0))
        ext = jnp.ones((n,))
        wc = p.w * p.c
        lif_a, dly_a = ops.fused_tick(st, p, ext, wc=wc)
        lif_b, dly_b = ops.fused_tick(st, p, ext, wc=None)
        _assert_trees_bitexact(lif_a, lif_b)
        np.testing.assert_array_equal(np.asarray(dly_a), np.asarray(dly_b))

    def test_ragged_padding_exact(self):
        """n not a multiple of any block: padded neurons must stay silent."""
        n, ticks = 139, 9
        p = _params(n, connectivity.sparse_random(n, 0.3, seed=9), v_th=0.8)
        st0 = SNNState.zeros((5,), n, max_delay=2)
        ext = _ext(n, ticks, (5,), p=0.2, seed=10)
        fin_j, ras_j = rollout(p, st0, ext, ticks, backend="jnp")
        fin_f, ras_f = rollout(p, st0, ext, ticks, backend="pallas_fused")
        np.testing.assert_array_equal(np.asarray(ras_j), np.asarray(ras_f))
        _assert_trees_bitexact(fin_j, fin_f)

    def test_surrogate_rejected(self):
        n = 4
        p = _params(n, connectivity.ring(n))
        eng = TickEngine(EngineOptions(backend="pallas_fused", surrogate=True))
        with pytest.raises(ValueError, match="inference-only"):
            eng.tick(SNNState.zeros((), n), p, None)


def _slot_states(n_slots, b, n, max_delay, *, seed):
    """Per-slot states, each at its own tick, mid-rollout: live membranes,
    refractory counters, spikes and a filled ring."""
    rng = np.random.default_rng(seed)
    shape = (n_slots, b, n)
    lif = dataclasses.replace(
        SNNState.zeros((n_slots, b), n).lif,
        v=jnp.asarray(rng.uniform(0, 1, shape), jnp.float32),
        r=jnp.asarray(rng.integers(0, 3, shape), jnp.int32),
        y=jnp.asarray(rng.random(shape) < 0.3, jnp.float32))
    ring = rng.random((n_slots, b, max_delay, n)) < 0.3
    return SNNState(lif=lif, delay_buf=jnp.asarray(ring, jnp.float32),
                    tick=jnp.asarray([0, 1, 5, 2][:n_slots], jnp.int32))


# (max_delay, per-synapse delays, weight operands): "wc" a pre-masked W*C
# per slot (frozen), "w,c" a W per slot and a shared mask C (learning),
# "stacked" every register per slot.
_SLOT_GRID_CASES = {
    "max_delay_1": (1, False, "stacked"),
    "uniform_ring": (3, False, "stacked"),
    "per_synapse_delays": (3, True, "stacked"),
    "frozen_wc": (2, False, "wc"),
    "learning_w_c": (1, False, "w,c"),
}


class TestSlotGrid:
    @pytest.mark.parametrize("case", sorted(_SLOT_GRID_CASES))
    def test_vmap_equals_per_slot_calls(self, case):
        """``vmap`` over 4 slots at different ticks -- the kernel's slot
        grid -- equals one unbatched call per slot, bit for bit."""
        max_delay, with_delays, form = _SLOT_GRID_CASES[case]
        S, b, n = 4, 2, 139
        stacked = [_params(n, connectivity.sparse_random(n, 0.5, seed=i),
                           seed=i, v_th=0.8, r_ref=2) for i in range(S)]
        params = jax.tree.map(lambda *a: jnp.stack(a), *stacked)
        st = _slot_states(S, b, n, max_delay, seed=len(case))
        ext = _ext(n, S, (b,), seed=2)
        rng = np.random.default_rng(3)
        delays = (jnp.asarray(rng.integers(1, max_delay + 1, (S, n, n)),
                              jnp.int32) if with_delays else None)
        shared = stacked[0]
        wcs = params.w * params.c

        if form == "wc":
            def tick(s, p, e, wc, d):
                return ops.fused_tick(s, p, e, wc=wc, delays=d)
        elif form == "w,c":
            def tick(s, p, e, wc, d):
                return ops.fused_tick(
                    s, dataclasses.replace(shared, w=p.w), e, delays=d)
        else:
            def tick(s, p, e, wc, d):
                return ops.fused_tick(s, p, e, delays=d)

        at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
        got = jax.jit(jax.vmap(tick))(st, params, ext, wcs, delays)
        for i in range(S):
            want = jax.jit(tick)(at(st, i), at(params, i), ext[i], wcs[i],
                                 None if delays is None else delays[i])
            _assert_trees_bitexact(at(got, i), want)
        assert float(np.asarray(got[0].y).sum()) > 0, "no slot spiked"


class TestFusedRecompilePin:
    def test_one_trace_across_tick_counts(self):
        """Advancing the circular delay pointer through a full ring cycle --
        every (read, write) slot pair -- reuses ONE trace: the pointer is a
        runtime scalar (scalar prefetch), never a compiled constant."""
        n, max_delay = 8, 3
        p = _params(n, connectivity.sparse_random(n, 0.5, seed=4), v_th=0.7)
        eng = TickEngine(EngineOptions(backend="pallas_fused"))
        traces = {"n": 0}

        def tick(state, params, ext):
            traces["n"] += 1
            carry, _ = eng.tick_body(TickCarry(state=state), (ext, None),
                                     params=params)
            return carry.state

        jtick = jax.jit(tick)
        st = SNNState.zeros((), n, max_delay=max_delay)
        ext = jnp.ones((n,))
        for k in range(2 * max_delay + 1):  # tick = 0..2D: every slot, twice
            st = jtick(st, p, ext)
        assert int(st.tick) == 2 * max_delay + 1
        assert traces["n"] == 1, f"tick advance retraced {traces['n'] - 1}x"

    def test_one_trace_across_rollout_lengths_same_shape(self):
        """Rollouts launched from different tick offsets (same shapes) share
        the compiled program -- the scan body never bakes in the tick."""
        n, ticks, max_delay = 6, 5, 4
        p = _params(n, connectivity.sparse_random(n, 0.6, seed=3), v_th=0.7)
        traces = {"n": 0}

        def run(params, state, ext):
            traces["n"] += 1
            return rollout(params, state, ext, ticks, backend="pallas_fused")

        jrun = jax.jit(run)
        st = SNNState.zeros((), n, max_delay=max_delay)
        ext = _ext(n, ticks, seed=11)
        fin, _ = jrun(p, st, ext)
        for _ in range(3):  # restart from advanced (offset) states
            fin, _ = jrun(p, fin, ext)
        assert traces["n"] == 1, f"offset restart retraced {traces['n'] - 1}x"

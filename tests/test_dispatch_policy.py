"""Adaptive event dispatch: the crossover policy and the per-tick knee.

Three layers of pins:

* **Policy module** (:mod:`repro.core.dispatch_policy`) -- the single
  spike-budget trigger (:func:`resolve_k_active`), the cost-model
  strategy selection (fan_in below the gather knee, dense above,
  vmap_safe excluding topk), diagonal-``w_in`` detection, and the
  concrete-topology contract (tracers are rejected).

* **The knee itself** -- both arms of the adaptive ``lax.cond`` are
  bit-exact (the branch is pure speed policy, never semantics), the
  hysteresis band holds the dense arm until activity falls below
  ``hysteresis * knee`` (checked in both directions with engineered
  spike-count sequences), overflow ticks and policy ticks are counted
  in *separate* telemetry fields, and varying activity never retraces.

* **End-to-end** -- ``network.rollout(dispatch="auto")`` plans from the
  concrete topology and stays bit-compatible with the jnp reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import connectivity, dispatch_policy
from repro.core.dispatch_policy import (
    DispatchPlan, is_diagonal, knee_spikes, plan, resolve_k_active,
)
from repro.core.engine import EngineOptions, TickEngine
from repro.core.lif import LIFParams
from repro.core.network import SNNParams, SNNState, rollout

jax.config.update("jax_platform_name", "cpu")


def _params(n, c, *, seed=0, v_th=0.5, leak=0.25, r_ref=0, w_scale=0.0):
    """w_scale=0 kills the recurrent path so spike counts are purely
    ext-driven -- the hysteresis tests script them tick by tick."""
    rng = np.random.default_rng(seed)
    return SNNParams(
        w=jnp.asarray(rng.uniform(0, 1, (n, n)) * w_scale, jnp.float32),
        c=jnp.asarray(c, jnp.float32),
        w_in=jnp.eye(n, dtype=jnp.float32),
        lif=LIFParams.make(n, v_th=v_th, leak=leak, r_ref=r_ref))


def _scripted_ext(n, ranges):
    """One tick per (start, count): `count` disjoint neurons driven at 1.0
    (disjoint across consecutive ticks, so refractory never interferes and
    the arriving spike count at tick t+1 is exactly counts[t])."""
    ticks = []
    for start, count in ranges:
        e = np.zeros((n,), np.float32)
        e[start:start + count] = 1.0
        ticks.append(e)
    return jnp.asarray(np.stack(ticks))


def _ring(n, fan=4):
    """Circulant topology with exactly `fan` in-edges per neuron -- a cap
    the cost model can price deterministically."""
    c = np.zeros((n, n), np.float32)
    for j in range(1, fan + 1):
        c[np.arange(n), (np.arange(n) + j) % n] = 1.0
    return c


class TestResolveKActive:
    def test_default_budget(self):
        assert resolve_k_active(1024) == 128          # n // 8
        assert resolve_k_active(32) == 8              # floor 8
        assert resolve_k_active(4) == 4               # but never past n

    def test_explicit_clamped_to_n(self):
        assert resolve_k_active(64, 16) == 16
        assert resolve_k_active(64, 999) == 64

    def test_is_the_single_trigger(self):
        """ops.default_k_active must delegate here, not re-derive."""
        from repro.kernels import ops

        for n in (8, 64, 1024, 5000):
            assert ops.default_k_active(n) == resolve_k_active(n)


class TestKneeModel:
    def test_platform_penalties(self):
        assert knee_spikes(1024, platform="cpu") == 51    # n / 20
        assert knee_spikes(1024, platform="tpu") == 512   # n / 2
        assert knee_spikes(8, platform="cpu") == 1        # floored

    def test_uncalibrated_platform_raises(self):
        # A platform without a calibration must not plan with the CPU's.
        with pytest.raises(ValueError, match="no gather penalty"):
            knee_spikes(1024, platform="neuron")

    def test_is_diagonal(self):
        assert is_diagonal(np.eye(8))
        assert is_diagonal(np.diag(np.arange(1.0, 9.0)))
        assert not is_diagonal(np.ones((8, 8)))
        assert not is_diagonal(None)
        assert not is_diagonal(np.ones((4, 8)))


class TestPlan:
    def test_fan_in_below_gather_knee(self):
        """A 4-in-edge ring on CPU: 4 gathered elements cost ~80 dense
        MACs, far under the n=256 dense row -- fan_in wins."""
        p = plan(_ring(256, fan=4), platform="cpu")
        assert p.strategy == "fan_in"
        assert p.cap == 4
        assert p.neighbors is not None
        assert p.neighbors.idx.shape == (256, 4)
        assert p.knee is None                        # knee is topk-only

    def test_dense_above_gather_knee(self):
        """density 0.5 random on CPU: every event formulation pays more
        than the masked GEMM -- the plan says so."""
        c = np.asarray(connectivity.sparse_random(128, 0.5, seed=0))
        p = plan(c, platform="cpu")
        assert p.strategy == "dense"
        assert p.neighbors is None
        assert p.costs["dense"] < p.costs["fan_in"]
        assert p.costs["dense"] < p.costs["topk"]

    def test_topk_wins_on_tpu_and_arms_the_knee(self):
        """On TPU (gather penalty ~2) a tight spike budget beats both the
        dense product and a wide fan-in gather; the adaptive knee arms."""
        c = np.asarray(connectivity.sparse_random(128, 0.3, seed=1))
        p = plan(c, rate=0.05, platform="tpu")
        assert p.strategy == "topk"
        assert p.k_active == max(8, int(2 * 0.05 * 128))
        assert p.knee == min(knee_spikes(128, platform="tpu"), p.k_active)
        assert p.hysteresis == dispatch_policy.DEFAULT_HYSTERESIS

    def test_adaptive_false_disarms_knee(self):
        c = np.asarray(connectivity.sparse_random(128, 0.3, seed=1))
        p = plan(c, rate=0.05, platform="tpu", adaptive=False)
        assert p.strategy == "topk" and p.knee is None

    def test_vmap_safe_excludes_topk(self):
        """The server's contract: under vmap the knee cond lowers to a
        both-arms select, so topk must never be chosen."""
        c = np.asarray(connectivity.sparse_random(128, 0.3, seed=1))
        p = plan(c, rate=0.05, platform="tpu", vmap_safe=True)
        assert p.strategy != "topk"

    def test_forced_cap_too_small_disables_fan_in(self):
        """Never truncate: a fabric whose fan-in exceeds the forced cap
        simply cannot take the fan_in strategy."""
        p = plan(_ring(256, fan=4), cap=2, platform="cpu")
        assert p.cap is None
        assert p.strategy != "fan_in"
        assert "fan_in" not in p.costs

    def test_prefer_density_overrides_cost_model(self):
        """The operator knob: at/below the preferred density a fabric
        whose fan-in fits takes fan_in regardless of modeled cost."""
        c = np.asarray(connectivity.sparse_random(128, 0.5, seed=0))
        assert plan(c, platform="cpu").strategy == "dense"
        p = plan(c, platform="cpu", prefer_density=1.0)
        assert p.strategy == "fan_in"

    def test_diag_w_in_detected(self):
        c = _ring(64)
        assert plan(c, w_in=np.eye(64)).ext_diag
        assert not plan(c, w_in=np.ones((64, 64))).ext_diag
        assert not plan(c).ext_diag

    def test_tracer_rejected(self):
        """plan() is host-side by contract: topology statistics cannot be
        read off a tracer, and the error says to plan outside jit."""
        c = jnp.asarray(_ring(32))
        with pytest.raises(TypeError, match="concrete"):
            jax.jit(lambda a: plan(a))(c)

    def test_engine_kwargs_build_an_engine(self):
        p = plan(_ring(64, fan=4), w_in=np.eye(64))
        eng = TickEngine(EngineOptions(**p.engine_kwargs()))
        assert eng.backend == "event"
        assert eng.event_dispatch == p.strategy
        assert isinstance(p, DispatchPlan)


# -- the per-tick knee ------------------------------------------------------

# Scripted arrival counts (w=0, w_in=I, disjoint driven sets): arriving
# spike count at tick t+1 is exactly the tick-t ext count, tick 0 is 0.
#   knee hi = min(event_knee=40, k=60) = 40; lo = 0.75*40 = 30.
#   m per tick:    [0,   50,     35,      10,  35]
#   dense_mode:    [F,   T,      T(hyst), F,   F]   -> policy_dense == 2
#   with hysteresis=1.0 (lo=40), tick 2 releases:   -> policy_dense == 1
_RANGES = [(0, 50), (60, 35), (100, 10), (110, 35), (0, 0)]
_N = 160


def _knee_engine(**kw):
    base = dict(backend="event", event_dispatch="topk", event_k_active=60,
                event_knee=40, telemetry=True)
    base.update(kw)
    return TickEngine(EngineOptions(**base))


class TestAdaptiveKnee:
    def test_hysteresis_holds_dense_through_the_band(self):
        p = _params(_N, _ring(_N))
        ext = _scripted_ext(_N, _RANGES)
        st = SNNState.zeros((), _N)
        _, _, tel = _knee_engine().rollout(p, st, ext, len(_RANGES))
        assert int(tel.policy_dense) == 2            # ticks 1 and 2
        assert int(tel.overflow) == 0                # never past k=60

    def test_hysteresis_one_releases_at_the_knee(self):
        """Same activity, release threshold at the knee itself: the tick-2
        count (35 < 40) drops straight back to the spike-list arm."""
        p = _params(_N, _ring(_N))
        ext = _scripted_ext(_N, _RANGES)
        st = SNNState.zeros((), _N)
        eng = _knee_engine(event_hysteresis=1.0)
        _, _, tel = eng.rollout(p, st, ext, len(_RANGES))
        assert int(tel.policy_dense) == 1            # tick 1 only

    def test_overflow_counted_separately_from_policy(self):
        """k=12: the 50-spike tick is an *overflow* fallback (bits), the
        10-spike tick inside the hysteresis band a *policy* fallback
        (speed) -- disjoint fields, one tick each."""
        p = _params(_N, _ring(_N))
        ext = _scripted_ext(_N, [(0, 50), (60, 10), (100, 0), (0, 0)])
        st = SNNState.zeros((), _N)
        eng = _knee_engine(event_k_active=12)        # hi=min(40,12)=12, lo=9
        _, _, tel = eng.rollout(p, st, ext, 4)
        assert int(tel.overflow) == 1                # tick 1: m=50 > 12
        assert int(tel.policy_dense) == 1            # tick 2: 9 < m=10 <= 12

    def test_knee_requires_fallback_overflow(self):
        with pytest.raises(ValueError, match="event_knee requires"):
            EngineOptions(backend="event", event_dispatch="topk",
                          event_knee=4, event_overflow="strict")


class TestKneeParity:
    """Both arms are bit-exact: the cond is pure policy, never semantics."""

    def _case(self, n=96, density=0.3, seed=5):
        rng = np.random.default_rng(seed)
        c = connectivity.sparse_random(n, density, seed=seed)
        p = SNNParams(
            w=jnp.asarray(rng.uniform(0, 1, (n, n)), jnp.float32),
            c=jnp.asarray(c, jnp.float32),
            w_in=jnp.eye(n, dtype=jnp.float32),
            lif=LIFParams.make(n, v_th=0.8, leak=0.2, r_ref=1))
        return rng, p

    def test_dense_arm_bitexact_vs_jnp_backend(self):
        """Saturating drive keeps every tick above the knee: the whole
        rollout runs the dense arm, bit-identical to the jnp backend."""
        rng, p = self._case()
        n, ticks = p.w.shape[0], 6
        ext = jnp.asarray((rng.random((ticks, n)) < 0.9), jnp.float32)
        st = SNNState.zeros((), n)
        eng = TickEngine(EngineOptions(backend="event", event_dispatch="topk",
                         event_k_active=64, event_knee=8))
        _, got = eng.rollout(p, st, ext, ticks)
        _, want = rollout(p, SNNState.zeros((), n), ext, ticks, backend="jnp")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_event_arm_bitexact_vs_plain_event(self):
        """Low rate keeps every tick below the release threshold: the whole
        rollout runs the spike-list arm, bit-identical to the same engine
        without a knee (overflow fallback only)."""
        rng, p = self._case(seed=6)
        n, ticks = p.w.shape[0], 6
        ext = jnp.asarray((rng.random((ticks, n)) < 0.02), jnp.float32)
        st = SNNState.zeros((), n)
        eng = TickEngine(EngineOptions(backend="event", event_dispatch="topk",
                         event_k_active=64, event_knee=48))
        _, got = eng.rollout(p, st, ext, ticks)
        plain = TickEngine(EngineOptions(backend="event", event_dispatch="topk",
                           event_k_active=64))
        _, want = plain.rollout(p, SNNState.zeros((), n), ext, ticks)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_mixed_rates_match_jnp_backend(self):
        """Activity crossing the knee mid-rollout (both switch directions)
        stays exact vs the dense reference."""
        rng, p = self._case(seed=7)
        n, ticks = p.w.shape[0], 10
        rates = np.asarray([0.9, 0.9, 0.02, 0.02, 0.5,
                            0.02, 0.9, 0.02, 0.5, 0.02])
        ext = jnp.asarray(
            (rng.random((ticks, n)) < rates[:, None]), jnp.float32)
        st = SNNState.zeros((), n)
        eng = TickEngine(EngineOptions(backend="event", event_dispatch="topk",
                         event_k_active=64, event_knee=16, telemetry=True))
        _, got, tel = eng.rollout(p, st, ext, ticks)
        _, want = rollout(p, SNNState.zeros((), n), ext, ticks, backend="jnp")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # The sequence really exercised both arms.
        assert 0 < int(tel.policy_dense) + int(tel.overflow) < ticks

    def test_ext_diag_bitexact_with_diagonal_w_in(self):
        """ext * diag(w_in) vs ext @ w_in: adding exact zeros is an f32
        no-op, so the eliminated GEMM changes no bits."""
        rng, p = self._case(seed=8)
        n, ticks = p.w.shape[0], 6
        ext = jnp.asarray((rng.random((ticks, n)) < 0.3), jnp.float32)
        out = {}
        for ed in (False, True):
            eng = TickEngine(EngineOptions(backend="event", event_dispatch="topk",
                             event_k_active=64, event_knee=16,
                             event_ext_diag=ed))
            _, out[ed] = eng.rollout(p, SNNState.zeros((), n), ext, ticks)
        np.testing.assert_array_equal(np.asarray(out[True]),
                                      np.asarray(out[False]))


class TestKneeRecompilePin:
    def test_one_trace_across_activity_levels(self):
        """The knee branches on a *runtime* spike count: rollouts at
        wildly different rates (both arms, overflow included) share one
        compiled program."""
        rng, p = TestKneeParity()._case(seed=9)
        n, ticks = p.w.shape[0], 5
        eng = TickEngine(EngineOptions(backend="event", event_dispatch="topk",
                         event_k_active=16, event_knee=8))
        traces = {"n": 0}

        def run(params, state, ext):
            traces["n"] += 1
            return eng.rollout(params, state, ext, ticks)

        jrun = jax.jit(run)
        st = SNNState.zeros((), n)
        for rate in (0.01, 0.3, 0.95):               # event / policy / overflow
            ext = jnp.asarray((rng.random((ticks, n)) < rate), jnp.float32)
            jrun(p, st, ext)
        assert traces["n"] == 1, f"activity level retraced {traces['n'] - 1}x"


class TestAutoDispatchEndToEnd:
    def test_rollout_auto_matches_jnp(self):
        """network.rollout(dispatch="auto"): plan from the concrete
        topology, run the event backend, match the dense reference."""
        rng = np.random.default_rng(11)
        n, ticks = 96, 6
        c = connectivity.sparse_random(n, 0.05, seed=11)
        p = SNNParams(
            w=jnp.asarray(rng.uniform(0, 1, (n, n)), jnp.float32),
            c=jnp.asarray(c, jnp.float32),
            w_in=jnp.eye(n, dtype=jnp.float32),
            lif=LIFParams.make(n, v_th=0.8, leak=0.2, r_ref=1))
        ext = jnp.asarray((rng.random((ticks, 2, n)) < 0.1), jnp.float32)
        st = SNNState.zeros((2,), n)
        _, got = rollout(p, st, ext, ticks, backend="event", dispatch="auto")
        _, want = rollout(p, SNNState.zeros((2,), n), ext, ticks,
                          backend="jnp")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_rollout_accepts_prebuilt_plan(self):
        rng = np.random.default_rng(12)
        n, ticks = 128, 4                            # 4*20 gathered < n dense
        c = _ring(n, fan=4)
        p = SNNParams(
            w=jnp.asarray(rng.uniform(0, 1, (n, n)), jnp.float32),
            c=jnp.asarray(c, jnp.float32),
            w_in=jnp.eye(n, dtype=jnp.float32),
            lif=LIFParams.make(n, v_th=0.8, leak=0.2, r_ref=1))
        dp = plan(np.asarray(c), w_in=np.eye(n))
        assert dp.strategy == "fan_in" and dp.ext_diag
        ext = jnp.asarray((rng.random((ticks, n)) < 0.2), jnp.float32)
        _, got = rollout(p, SNNState.zeros((), n), ext, ticks, dispatch=dp)
        _, want = rollout(p, SNNState.zeros((), n), ext, ticks, backend="jnp")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_plan_under_jit_raises_with_pointer(self):
        """dispatch="auto" inside jit cannot read the topology -- the
        error tells the caller to plan outside and pass the plan in."""
        n = 32
        p = _params(n, _ring(n))
        st = SNNState.zeros((), n)
        with pytest.raises(TypeError, match="outside jit"):
            jax.jit(lambda pp, ss: rollout(
                pp, ss, None, 2, backend="event", dispatch="auto"))(p, st)

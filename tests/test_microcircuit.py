"""The Potjans-Diesmann microcircuit on the event backend's ``fan_out``
strategy: the resident fan-out, the dendritic delay ring with per-synapse
delays, NEST's ``iaf_psc_exp`` and the on-device Poisson drive.

Small sizes on the CPU: the microcircuit at ``scale`` 0.02 (1,543
neurons, every in-degree as published) against the bench's plain
reference, and a 256-neuron fabric against the dense per-synapse-delay
path, both bit for bit (weights on a dyadic grid make every sum exact).
"""
import dataclasses
import hashlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.pd_microcircuit import (DT, NEURON, Microcircuit,
                                           WEIGHT_QUANTUM)
from repro.core import connectivity, dispatch_policy
from repro.core.engine import EngineOptions, TickCarry, TickEngine
from repro.core.lif import LIFParams, LIFState, lif_step, psc_exp_propagators
from repro.core.network_types import PoissonDrive, SNNParams, SNNState
from repro.obs import metrics
from repro.obs.telemetry import TickTelemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_cfg(scale):
    with open(os.path.join(ROOT, "bench", "configs",
                           "pd14_microcircuit.json")) as fh:
        cfg = json.load(fh)
    return dict(cfg, scale=scale, synapse_block=1 << 16)


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.fixture(scope="module")
def small_circuit():
    """The microcircuit at scale 0.02: the bench's synapse list, the
    program's fan-out built from it, params and a first state."""
    from bench import reference_microcircuit as ref

    cfg = _bench_cfg(0.02)
    seed = 4000000011
    net = ref.network(cfg)
    mc = Microcircuit(scale=0.02)
    deg = ref.out_degrees(net, seed)
    blocks = ((s, t, lv.astype(jnp.float32) * WEIGHT_QUANTUM, d)
              for s, t, lv, d in ref.synapse_blocks(
                  net, seed, deg, cfg["synapse_block"]))
    fo = mc.fan_out(deg.sum(axis=0), blocks, window=64)
    return dict(cfg=cfg, seed=seed, net=net, mc=mc, fo=fo, ref=ref,
                params=mc.params(ref.poisson_key(seed)),
                state=mc.initial_state(ref.v0_key(seed)))


class TestConfig:
    def test_full_scale_sizes(self):
        mc = Microcircuit()
        assert mc.n == 77169
        assert int(mc.synapse_counts().sum()) == 298_880_968
        assert mc.pop_starts[-1] == mc.n and len(mc.pop_starts) == 9

    def test_bench_network_agrees_with_the_program(self):
        from bench import reference_microcircuit as ref

        for scale in (1.0, 0.02):
            mc, net = Microcircuit(scale=scale), ref.network(_bench_cfg(scale))
            assert net["n"] == mc.n
            assert list(net["starts"]) == list(mc.pop_starts)
            np.testing.assert_array_equal(net["k"], mc.synapse_counts())
            np.testing.assert_array_equal(net["lam"], mc.lam())
            assert net["w_ext_level"] * WEIGHT_QUANTUM == mc.external_weight()
            lp = mc.lif_params()
            for key, got in (("p11", lp.syn_decay), ("p22", lp.leak),
                             ("p21", lp.gain)):
                assert np.float32(net[key]) == np.asarray(got)[0], key

    def test_mean_weight_is_the_published_psc(self):
        w = Microcircuit().weight_means()
        assert abs(w[0, 0] - 87.81) < 0.01
        assert w[0, 1] == -4 * w[0, 0] and w[0, 2] == 2 * w[0, 0]


class TestPscExp:
    def test_propagators_are_nests(self):
        """NEST's ``propagator_32`` form of P21 and its P11, P22, P20."""
        c_m, tau_m, tau_s, h = (NEURON["c_m"], NEURON["tau_m"],
                                NEURON["tau_syn"], DT)
        p11, p22, p21, p20 = psc_exp_propagators(c_m=c_m, tau_m=tau_m,
                                                 tau_syn=tau_s, dt=h)
        nest_p32 = (-tau_m / (c_m * (1.0 - tau_m / tau_s))
                    * math.exp(-h / tau_s)
                    * math.expm1(h * (1.0 / tau_s - 1.0 / tau_m)))
        assert p21 == pytest.approx(nest_p32, rel=1e-13)
        assert p11 == math.exp(-h / tau_s) and p22 == math.exp(-h / tau_m)
        assert p20 == pytest.approx(tau_m / c_m * (1 - p22), rel=1e-13)

    def test_single_neuron_psp_is_the_closed_form(self):
        """One input of w pA at tick 0: the current decays as
        ``w exp(-t/tau_s)`` and the membrane follows the alpha-difference
        ``w tau_m tau_s / (C (tau_m - tau_s)) (e^-t/tau_m - e^-t/tau_s)``."""
        lp = LIFParams.psc_exp(1, dt=DT, **NEURON)
        lp = dataclasses.replace(lp, v_th=jnp.full((1,), 1e9))
        st = LIFState.zeros((), 1, current=True)
        w = 87.8125
        vs, cur = [], []
        for k in range(200):
            st = lif_step(st, jnp.full((1,), w if k == 0 else 0.0), lp,
                          mode="psc_exp")
            vs.append(float(st.v[0]))
            cur.append(float(st.i[0]))
        c_m, tau_m, tau_s = NEURON["c_m"], NEURON["tau_m"], NEURON["tau_syn"]
        t = DT * np.arange(1, 201)
        v_exact = w * tau_m * tau_s / (c_m * (tau_m - tau_s)) * (
            np.exp(-(t - DT) / tau_m) - np.exp(-(t - DT) / tau_s))
        i_exact = w * np.exp(-(t - DT) / tau_s)
        np.testing.assert_allclose(vs, v_exact, rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(cur, i_exact, rtol=2e-5, atol=1e-9)
        assert max(vs) == pytest.approx(0.15, rel=1e-3)   # the 0.15 mV PSP

    def test_refractory_hold_and_reset(self):
        lp = LIFParams.psc_exp(1, dt=DT, **NEURON)
        st = dataclasses.replace(LIFState.zeros((), 1, current=True),
                                 v=jnp.full((1,), 20.0))
        st = lif_step(st, jnp.zeros((1,)), lp, mode="psc_exp")
        assert float(st.y[0]) == 1.0 and float(st.v[0]) == 0.0
        assert int(st.r[0]) == 20
        for _ in range(20):
            st = lif_step(st, jnp.full((1,), 1e4), lp, mode="psc_exp")
            assert float(st.v[0]) == 0.0 and float(st.y[0]) == 0.0
        assert int(st.r[0]) == 0

    @pytest.mark.parametrize("backend", ["pallas", "pallas_fused"])
    def test_rejected_on_kernel_backends(self, backend):
        with pytest.raises(ValueError, match="psc_exp"):
            EngineOptions(mode="psc_exp", backend=backend)

    def test_rejected_on_the_event_topk_kernel(self):
        with pytest.raises(ValueError, match="psc_exp"):
            EngineOptions(mode="psc_exp", backend="event",
                          event_dispatch="topk")


def _random_fabric(n, depth, seed=0, density=0.05):
    """Unique (pre, post) synapses with dyadic weights and delays
    1..depth, as a list and as dense ``W`` / delay matrices."""
    rng = np.random.default_rng(seed)
    c = rng.random((n, n)) < density
    np.fill_diagonal(c, False)
    src, tgt = np.nonzero(c)
    lev = rng.integers(-1500, 2500, src.size)
    w = (lev * WEIGHT_QUANTUM).astype(np.float32)
    d = rng.integers(1, depth + 1, src.size)
    w_dense = np.zeros((n, n), np.float32)
    d_dense = np.ones((n, n), np.int32)
    w_dense[src, tgt] = w
    d_dense[src, tgt] = d
    return src, tgt, w, d, w_dense, d_dense


class TestFanOut:
    def test_layout_and_stats(self):
        src = np.array([0, 0, 2, 1, 0, 3])
        tgt = np.array([1, 2, 3, 0, 3, 0])
        w = np.arange(6, dtype=np.float32)
        d = np.array([1, 2, 3, 4, 5, 6])
        fo = connectivity.fan_out_from_synapses(src, tgt, w, d, (0, 2, 4),
                                                window=2)
        assert fo.window == 2
        # population 0 sources get 2 rows of 2 (cap 3), population 1 one
        np.testing.assert_array_equal(np.asarray(fo.offset), [0, 2, 4, 5])
        np.testing.assert_array_equal(np.asarray(fo.count), [3, 1, 1, 1])
        np.testing.assert_array_equal(np.asarray(fo.targets)[:2],
                                      [[1, 2], [3, 0]])
        np.testing.assert_array_equal(np.asarray(fo.weights)[0], [0, 1])
        np.testing.assert_array_equal(np.asarray(fo.weights)[1, 0], 4)
        np.testing.assert_array_equal(np.asarray(fo.delays)[4:, 0], [3, 6])
        entries, padded, frac = fo.stats()
        assert (entries, padded) == (6, 12) and frac == 0.5
        reg = metrics.MetricsRegistry()
        metrics.record_fan_out(reg, entries=entries, padding_fraction=frac,
                               syn_events=10)
        metrics.record_fan_out(reg, syn_events=25)
        assert reg.get("snn_fanout_entries").value() == 6
        assert reg.get("snn_fanout_padding_fraction").value() == 0.5
        assert reg.get("snn_synaptic_events_total").value() == 25

    def test_blocks_in_any_order_build_the_same_rows(self):
        src, tgt, w, d, _, _ = _random_fabric(64, 8, seed=3)
        whole = connectivity.fan_out_from_synapses(src, tgt, w, d, (0, 40, 64))
        perm = np.random.default_rng(1).permutation(src.size)
        size = 50
        blocks = []
        for lo in range(0, src.size, size):
            idx = perm[lo:lo + size]
            pad = size - idx.size
            blocks.append(tuple(jnp.asarray(np.concatenate([a[idx], fill]))
                                for a, fill in (
                                    (src, np.full(pad, 64)), (tgt, np.zeros(pad, int)),
                                    (w, np.zeros(pad, np.float32)),
                                    (d, np.ones(pad, int)))))
        count = np.bincount(src, minlength=64)
        fo = connectivity.build_fan_out(count, (0, 40, 64), blocks, window=3)
        for s in range(64):
            c = int(fo.count[s])
            rows = lambda f, a: np.asarray(a)[int(f.offset[s]):].reshape(
                -1)[:c].tolist()
            got = sorted(zip(rows(fo, fo.targets), rows(fo, fo.weights),
                             rows(fo, fo.delays)))
            want = sorted(zip(rows(whole, whole.targets),
                              rows(whole, whole.weights),
                              rows(whole, whole.delays)))
            assert got == want

    def test_short_list_is_refused(self):
        src, tgt, w, d, _, _ = _random_fabric(32, 4, seed=4)
        count = np.bincount(src, minlength=32)
        count[5] += 1
        with pytest.raises(ValueError, match="short or overfull"):
            connectivity.build_fan_out(count, (0, 32), [tuple(
                jnp.asarray(a) for a in (src, tgt, w, d))])


def _psc_fabric(n, depth, *, seed=0, i_e=600.0):
    lp = LIFParams.psc_exp(n, dt=DT, i_e=i_e, **NEURON)
    rng = np.random.default_rng(seed + 100)
    st = SNNState.zeros((), n, max_delay=depth, current=True)
    v0 = jnp.asarray(rng.uniform(0, 15, n), jnp.float32)
    return lp, dataclasses.replace(st, lif=dataclasses.replace(st.lif, v=v0))


class TestFanOutDelivery:
    N, D = 256, 8

    def test_matches_the_dense_per_synapse_delay_path(self):
        """The fan-out ring and the dense ``(n, n)`` delay-plane einsum
        deliver the same spikes at the same ticks: bit for bit."""
        n, depth = self.N, self.D
        src, tgt, w, d, w_dense, d_dense = _random_fabric(n, depth)
        lp, st0 = _psc_fabric(n, depth)
        dense = TickEngine(EngineOptions(mode="psc_exp", backend="jnp"))
        p_dense = SNNParams(w=jnp.asarray(w_dense), c=None,
                            w_in=jnp.zeros((0, n)), lif=lp)
        fs, raster_d = jax.jit(lambda p, s, dl: dense.rollout(
            p, s, None, 300, delays=dl))(p_dense, st0, jnp.asarray(d_dense))
        fo = connectivity.fan_out_from_synapses(src, tgt, w, d, (0, n))
        event = TickEngine(EngineOptions(mode="psc_exp", backend="event",
                                         event_dispatch="fan_out",
                                         event_k_active=16))
        p_event = SNNParams(w=None, c=None, w_in=jnp.zeros((0, n)), lif=lp)
        fe, raster_e = jax.jit(lambda p, s, f: event.rollout(
            p, s, None, 300, neighbors=f))(p_event, st0, fo)
        assert float(raster_d.sum()) > 300          # the fabric is active
        np.testing.assert_array_equal(np.asarray(raster_d),
                                      np.asarray(raster_e))
        np.testing.assert_array_equal(_bits(fs.lif.v), _bits(fe.lif.v))
        np.testing.assert_array_equal(_bits(fs.lif.i), _bits(fe.lif.i))

    @pytest.mark.parametrize("window", [None, 2])
    def test_a_tick_past_the_budget_delivers_every_spike(self, window):
        """Every neuron starts above threshold, so tick 0 spikes n
        sources against a block of 3 reads: the spill blocks deliver them
        all, exactly as one block of every read would, and the telemetry
        counts them (one read per source, or rows read 2 entries at a
        time)."""
        n, depth = 64, 4
        src, tgt, w, d, _, _ = _random_fabric(n, depth, seed=5, density=0.2)
        lp, st0 = _psc_fabric(n, depth, i_e=0.0)
        st0 = dataclasses.replace(st0, lif=dataclasses.replace(
            st0.lif, v=jnp.full((n,), 16.0)))
        fo = connectivity.fan_out_from_synapses(src, tgt, w, d, (0, 20, n),
                                                window)
        p = SNNParams(w=None, c=None, w_in=jnp.zeros((0, n)), lif=lp)
        count = np.bincount(src, minlength=n)
        reads = int(np.sum(-(-count // fo.window)))

        def run(k):
            eng = TickEngine(EngineOptions(
                mode="psc_exp", backend="event", event_dispatch="fan_out",
                event_k_active=k, telemetry=True))
            return eng.rollout(p, st0, None, 3, neighbors=fo)

        small, raster, tel = run(3)
        whole, _, tel_whole = run(reads)
        assert float(raster[0].sum()) == n
        np.testing.assert_array_equal(np.asarray(small.delay_buf),
                                      np.asarray(whole.delay_buf))
        assert float(np.abs(np.asarray(small.delay_buf)).sum()) > 0
        assert int(tel.spill_blocks) == math.ceil(reads / 3) - 1
        assert int(tel_whole.spill_blocks) == 0
        assert float(tel.syn_events) == float(src.size)
        np.testing.assert_array_equal(np.asarray(tel.pop_spikes), [20, 44])
        summary = tel.summary(n)
        assert summary["syn_events"] == src.size
        assert summary["pop_spikes"] == [20.0, 44.0]

    def test_unbatched_frozen_only(self):
        n = 16
        src, tgt, w, d, _, _ = _random_fabric(n, 2, seed=6, density=0.3)
        fo = connectivity.fan_out_from_synapses(src, tgt, w, d, (0, n))
        lp, _ = _psc_fabric(n, 2)
        eng = TickEngine(EngineOptions(mode="psc_exp", backend="event",
                                       event_dispatch="fan_out"))
        p = SNNParams(w=None, c=None, w_in=jnp.zeros((0, n)), lif=lp)
        with pytest.raises(ValueError, match="unbatched"):
            eng.rollout(p, SNNState.zeros((2,), n, 2, current=True), None,
                        2, neighbors=fo)
        with pytest.raises(ValueError, match="fan-out lists"):
            eng.rollout(p, SNNState.zeros((), n, 2, current=True), None, 2)


class TestPoissonDrive:
    def test_counts_are_a_function_of_key_and_tick(self):
        lam = jnp.asarray(np.repeat([1.28, 2.32], 500), jnp.float32)
        drive = PoissonDrive(key=jax.random.PRNGKey(7), lam=lam,
                             weight=jnp.full((1000,), 87.8125))
        a = np.asarray(jax.jit(drive.counts)(jnp.int32(12345)))
        b = np.asarray(drive.counts(jnp.int32(12345)))
        c = np.asarray(drive.counts(jnp.int32(12346)))
        want = jax.random.poisson(jax.random.fold_in(jax.random.PRNGKey(7),
                                                     12345), lam,
                                  dtype=jnp.int32)
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, np.asarray(want))
        assert not np.array_equal(a, c)
        assert abs(a[:500].mean() - 1.28) < 0.2
        assert abs(a[500:].mean() - 2.32) < 0.25
        np.testing.assert_array_equal(np.asarray(drive.input(12345)),
                                      a * np.float32(87.8125))

    def test_only_the_fan_out_strategy_takes_it(self):
        n = 8
        lp, st = _psc_fabric(n, 1)
        p = SNNParams(w=jnp.zeros((n, n)), c=None, w_in=jnp.zeros((0, n)),
                      lif=lp, drive=PoissonDrive(
                          key=jax.random.PRNGKey(0), lam=jnp.ones((n,)),
                          weight=jnp.ones((n,))))
        eng = TickEngine(EngineOptions(mode="psc_exp", backend="jnp"))
        with pytest.raises(ValueError, match="Poisson"):
            eng.rollout(p, st, None, 2)


def test_plan_picks_fan_out_only_with_fan_out_lists():
    n = 64
    src, tgt, w, d, w_dense, _ = _random_fabric(n, 4, seed=8)
    fo = connectivity.fan_out_from_synapses(src, tgt, w, d, (0, n))
    plan = dispatch_policy.plan(fan_out=fo, k_active=32, platform="cpu")
    assert plan.strategy == "fan_out" and plan.neighbors is fo
    assert plan.k_active == 32 and plan.cap == fo.window
    opts = plan.engine_options(mode="psc_exp")
    assert opts.event_dispatch == "fan_out"
    c = w_dense != 0
    for kw in (dict(), dict(vmap_safe=True), dict(prefer_density=1.0, cap=n),
               dict(rate=0.01)):
        assert dispatch_policy.plan(c, platform="cpu", **kw).strategy \
            != "fan_out"
    with pytest.raises(ValueError, match="fan-out lists"):
        dispatch_policy.plan(platform="cpu")


def test_engine_matches_the_reference_bit_for_bit(small_circuit):
    """500 ticks of the microcircuit at scale 0.02 through
    ``TickEngine.chunk`` (five 100-tick requests, state carried) against
    the bench's plain reference over the same list: the same population
    counts and, bit for bit, the same membrane, current and ring."""
    sc = small_circuit
    mc, ref, net = sc["mc"], sc["ref"], sc["net"]
    engine = TickEngine(mc.engine_options(k=8))
    carry = TickCarry(state=sc["state"],
                      telem=TickTelemetry.zeros((), n_pops=8))
    step = jax.jit(lambda p, c, f: engine.chunk(p, c, None, 100,
                                                neighbors=f))
    spikes = np.zeros(8)
    for _ in range(5):
        carry, raster = step(sc["params"], carry, sc["fo"])
        r = np.asarray(raster).sum(axis=0)
        spikes += np.add.reduceat(r, np.asarray(mc.pop_starts[:-1]))
    st0 = sc["state"]
    syn = ref.Synapses(net, sc["seed"], sc["cfg"]["synapse_block"])
    final, pops = ref.replay(
        net, syn, sc["seed"], dict(v=st0.lif.v, i=st0.lif.i, r=st0.lif.r,
                                   ring=st0.delay_buf, tick=0), 500)
    st = carry.state
    assert spikes.sum() > 500
    np.testing.assert_array_equal(np.asarray(pops), spikes)
    np.testing.assert_array_equal(np.asarray(carry.telem.pop_spikes), spikes)
    np.testing.assert_array_equal(_bits(final["v"]), _bits(st.lif.v))
    np.testing.assert_array_equal(_bits(final["i"]), _bits(st.lif.i))
    np.testing.assert_array_equal(np.asarray(final["ring"]),
                                  np.asarray(st.delay_buf))
    assert int(final["tick"]) == int(st.tick) == 500


def test_reference_controls_differ(small_circuit):
    """The controls that must fail the comparison do differ from the
    reference: bfloat16 neurons, and every delay set to one tick."""
    sc = small_circuit
    ref, net = sc["ref"], sc["net"]
    st0 = sc["state"]
    start = dict(v=st0.lif.v, i=st0.lif.i, r=st0.lif.r, ring=st0.delay_buf,
                 tick=0)
    syn = ref.Synapses(net, sc["seed"], sc["cfg"]["synapse_block"])
    base, pops = ref.replay(net, syn, sc["seed"], start, 100)
    low, _ = ref.replay(net, syn, sc["seed"], start, 100, dtype=jnp.bfloat16)
    one = ref.Synapses(net, sc["seed"], sc["cfg"]["synapse_block"],
                       delay_one=True)
    fast, _ = ref.replay(net, one, sc["seed"], start, 100)
    assert not np.array_equal(_bits(base["i"]), _bits(low["i"]))
    assert not np.array_equal(np.asarray(base["ring"]),
                              np.asarray(fast["ring"]))


# -- the existing cells' chunk programs ----------------------------------------------

def _hlo_digest(compiled) -> str:
    """The optimized HLO with source metadata and stack frames removed."""
    t = compiled.as_text()
    t = re.sub(r',? ?metadata=\{[^}]*\}', '', t)
    t = "\n".join(ln for ln in t.splitlines()
                  if not re.match(r'^\d+ ("|\{)', ln))
    t = re.sub(r',? ?stack_frame_id=\d+', '', t)
    return hashlib.sha256(t.encode()).hexdigest()[:16]


def test_existing_chunk_programs_compile_to_the_same_hlo():
    """The existing cells' chunk programs at small size -- the engine
    chunk the stream cell runs (jnp, c=None) and its event twin, with
    telemetry off and on, and the dense cell's continuous-serving chunk
    program (pallas_fused over slots, telemetry and STDP) -- compile to
    the same optimized HLO as before ``psc_exp``, ``fan_out`` and the
    Poisson drive existed (digests recorded from that tree on this CPU
    build of jax; the dense chunk program's since the whole-tick kernel
    runs on its slot grid, a deliberate change of that program)."""
    import functools

    from repro.launch.serve import SNNServer
    from repro.plasticity import PlasticityState

    n = 64
    pp = SNNParams(w=jnp.zeros((n, n)), c=None, w_in=jnp.zeros((16, n)),
                   lif=LIFParams.make(n))
    got = {}
    for backend in ("jnp", "event"):
        for tel in (False, True):
            eng = TickEngine(EngineOptions(backend=backend, telemetry=tel))
            carry = TickCarry(state=SNNState.zeros((), n),
                              telem=TickTelemetry.zeros(()) if tel else None)
            c = jax.jit(lambda p, s, e: eng.chunk(p, s, e, 8)).lower(
                pp, carry, jnp.zeros((8, 16))).compile()
            got[f"{backend}/tel{int(tel)}"] = _hlo_digest(c)
    n, slots, chunk = 128, 2, 4
    server = SNNServer(n_max=n, slots=slots, max_ticks=32,
                       backend="pallas_fused", chunk_ticks=chunk)
    params = jax.eval_shape(lambda: SNNParams(
        w=jnp.zeros((n, n)), c=jnp.zeros((n, n)), w_in=jnp.zeros((n, n)),
        lif=LIFParams.make(n)))
    carry = jax.eval_shape(lambda: TickCarry(
        state=SNNState.zeros((), n), plast=PlasticityState.zeros((), n),
        w=jnp.zeros((n, n)), telem=TickTelemetry.zeros(())))
    sp = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((slots,) + a.shape, a.dtype), tree)
    arr = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        (slots,) + shape, dt)
    fn = functools.partial(server._chunk_fn, "pallas_fused", chunk)
    c = jax.jit(fn).lower(
        sp(params), sp(carry), arr((chunk, n)), arr((n, n)), arr((chunk,)),
        arr((), jnp.int32), arr((), jnp.int32), arr((), jnp.bool_),
        arr((n,))).compile()
    got["server_chunk/pallas_fused"] = _hlo_digest(c)
    assert got == HLO_PINS, got


HLO_PINS = {"jnp/tel0": "8176f75791b60a46", "jnp/tel1": "8e95f110f1f7e302",
            "event/tel0": "e9512b91ee44f375",
            "event/tel1": "35f7d6765dadaf45",
            "server_chunk/pallas_fused": "c3e7a39ea40c4ea3"}

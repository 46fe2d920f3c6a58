"""Observability layer: telemetry parity, HLO identity, metrics, serving.

Four pins (DESIGN.md §11):

* **Zero cost when off** -- the telemetry-off frozen jnp rollout lowers
  to HLO *byte-identical* to a pre-observability oracle scan written out
  verbatim in this file (module name normalized, nothing else), with a
  teeth check proving telemetry-on does perturb the lowered text.

* **Bit-exactness when on** -- for every backend (jnp / pallas /
  pallas_fused / event), telemetry-on rasters and final states equal
  telemetry-off bit-for-bit, and the accumulated spike count equals
  ``raster.sum()`` of the same rollout.

* **vmap transparency** -- per-row telemetry from a vmapped rollout
  equals the batched rollout's telemetry leaf-for-leaf (what the
  multi-tenant server's slot vmap relies on).

* **Host-side instruments** -- the dependency-free registry renders a
  valid Prometheus 0.0.4 text exposition and JSON dump; the SNN server
  reports requests/chunks/TTFT/tenant activity through it, and its
  empty-queue / all-rejected paths return well-formed zero reports.
"""
import io
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import EngineOptions, TickEngine
from repro.core.lif import LIFParams, lif_step
from repro.core.network import (
    SNNParams, SNNState, learning_rollout, rollout,
)
from repro.obs import (
    EventLog, MetricsRegistry, TickTelemetry, profile, span, trace_scope,
)
from repro.plasticity import PlasticityParams, PlasticityState

jax.config.update("jax_platform_name", "cpu")

BACKENDS = ("jnp", "pallas", "pallas_fused", "event")


def _params(n, *, density=0.5, seed=0, v_th=1.5, leak=0.25, r_ref=1):
    rng = np.random.default_rng(seed)
    c = (rng.random((n, n)) < density).astype(np.float32)
    np.fill_diagonal(c, 0.0)
    return SNNParams(
        w=jnp.asarray(rng.uniform(0, 2.0, (n, n)), jnp.float32),
        c=jnp.asarray(c, jnp.float32),
        w_in=jnp.eye(n, dtype=jnp.float32) * 2.0,
        lif=LIFParams.make(n, v_th=v_th, leak=leak, r_ref=r_ref))


def _ext(n, ticks, batch_shape=(), p=0.35, seed=1, mag=1.0):
    rng = np.random.default_rng(seed)
    shape = (ticks,) + tuple(batch_shape) + (n,)
    return jnp.asarray((rng.random(shape) < p) * mag, jnp.float32)


# ---------------------------------------------------------------------------
# Tier A: on-device telemetry
# ---------------------------------------------------------------------------

class TestTelemetryParity:
    """Telemetry on == telemetry off, bit for bit, on every backend."""

    N, T, D = 24, 12, 4

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_on_off_bit_exact_and_spikes_match_raster(self, backend):
        p = _params(self.N)
        st0 = SNNState.zeros((), self.N, max_delay=self.D)
        ext = _ext(self.N, self.T, seed=3)
        fs_off, r_off = rollout(p, st0, ext, self.T, backend=backend)
        fs_on, r_on, tel = rollout(p, st0, ext, self.T, backend=backend,
                                   telemetry=True)
        np.testing.assert_array_equal(np.asarray(r_off), np.asarray(r_on))
        for a, b in zip(jax.tree.leaves(fs_off), jax.tree.leaves(fs_on)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(r_on).sum() > 0, "dead network proves nothing"
        assert float(tel.ticks) == self.T
        assert float(tel.spikes) == float(np.asarray(r_on).sum())
        assert float(tel.overflow) == 0.0 or backend == "event"
        assert float(tel.dw_l1) == 0.0, "frozen rollout must report no dw"

    def test_summary_fields(self):
        p = _params(self.N)
        st0 = SNNState.zeros((), self.N, max_delay=self.D)
        ext = _ext(self.N, self.T, seed=3)
        _, raster, tel = rollout(p, st0, ext, self.T, telemetry=True)
        s = tel.summary(self.N)
        r = np.asarray(raster)
        assert s["ticks"] == self.T
        assert s["spikes"] == float(r.sum())
        assert s["spike_rate"] == pytest.approx(r.mean())
        assert 0.0 <= s["refractory_occupancy"] <= 1.0
        assert np.isfinite(s["v_max"]) and np.isfinite(s["v_mean"])
        assert s["dw_l1"] == 0.0 and s["dw_l2"] == 0.0

    def test_vmap_transparent(self):
        """Per-row vmapped telemetry == batched telemetry, leaf for leaf."""
        B = 3
        p = _params(self.N)
        ext_b = _ext(self.N, self.T, batch_shape=(B,), seed=5)

        def per_row(ext_row):
            st = SNNState.zeros((), self.N, max_delay=self.D)
            return rollout(p, st, ext_row, self.T, telemetry=True)[2]

        tel_v = jax.vmap(per_row, in_axes=1)(ext_b)
        st_b = SNNState.zeros((B,), self.N, max_delay=self.D)
        _, raster_b, tel_b = rollout(p, st_b, ext_b, self.T, telemetry=True)
        for a, b in zip(jax.tree.leaves(tel_v), jax.tree.leaves(tel_b)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)
        # and the per-row spike counts really are per-row
        per_row_spikes = np.asarray(raster_b).sum(axis=(0, 2))
        np.testing.assert_allclose(np.asarray(tel_b.spikes), per_row_spikes)

    def test_no_retrace_between_calls(self):
        """The static flag keys the jit cache; repeated calls don't trace."""
        p = _params(self.N)
        st0 = SNNState.zeros((), self.N, max_delay=self.D)
        traces = {"n": 0}

        @jax.jit
        def run(p, st, ext):
            traces["n"] += 1
            return rollout(p, st, ext, self.T, telemetry=True)

        run(p, st0, _ext(self.N, self.T, seed=1))
        run(p, st0, _ext(self.N, self.T, seed=2))
        assert traces["n"] == 1


class TestLearningTelemetry:
    N, T = 20, 16

    def _setup(self, seed=0):
        p = _params(self.N, v_th=1.0, seed=seed)
        st0 = SNNState.zeros((), self.N)  # STDP needs max_delay == 1
        pst0 = PlasticityState.zeros((), self.N)
        pp = PlasticityParams.make("stdp", a_plus=0.2, a_minus=0.1)
        ext = _ext(self.N, self.T, p=0.5, seed=seed + 1)
        return p, st0, pst0, pp, ext

    def test_dw_accumulates_and_stays_bit_exact(self):
        p, st0, pst0, pp, ext = self._setup()
        (fs_off, _, w_off), r_off = learning_rollout(
            p, st0, pst0, ext, self.T, plasticity=pp)
        (fs_on, _, w_on), r_on, tel = learning_rollout(
            p, st0, pst0, ext, self.T, plasticity=pp, telemetry=True)
        np.testing.assert_array_equal(np.asarray(r_off), np.asarray(r_on))
        np.testing.assert_array_equal(np.asarray(w_off), np.asarray(w_on))
        assert float(jnp.abs(w_on - p.w).sum()) > 0, "weights never moved"
        assert float(tel.dw_l1) > 0.0
        assert float(tel.dw_sq) > 0.0
        s = tel.summary(self.N)
        assert s["dw_l1"] > 0.0 and s["dw_l2"] > 0.0
        # L1 of the update stream >= L1 of the net displacement
        assert s["dw_l1"] >= float(jnp.abs(w_on - p.w).sum()) - 1e-4


class TestEventOverflowTelemetry:
    N, T = 24, 12

    def test_overflow_ticks_counted_and_exact(self):
        """k_active=2 + hot drive: nearly every tick overflows into the
        dense fallback; telemetry counts them and the raster stays exact."""
        p = _params(self.N, v_th=0.8)
        st0 = SNNState.zeros((), self.N)
        ext = _ext(self.N, self.T, p=0.8, seed=9, mag=2.0)
        _, r_ref = rollout(p, st0, ext, self.T, backend="jnp")
        eng = TickEngine(EngineOptions(backend="event", event_k_active=2,
                                       telemetry=True))
        _, r_ev, tel = eng.rollout(p, st0, ext, self.T)
        np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_ev))
        assert np.asarray(r_ref).sum() > 2 * self.T, "drive too cold"
        assert float(tel.overflow) > 0
        assert float(tel.overflow) <= self.T

    def test_fan_in_gather_path_never_overflows(self):
        from repro.kernels.ops import EventFanIn

        p = _params(self.N, density=0.2, v_th=0.8)
        st0 = SNNState.zeros((), self.N)
        ext = _ext(self.N, self.T, p=0.8, seed=9, mag=2.0)
        fan_in = EventFanIn.from_dense(np.asarray(p.c))
        eng = TickEngine(EngineOptions(backend="event", event_k_active=2,
                                       telemetry=True))
        _, r_ev, tel = eng.rollout(p, st0, ext, self.T, neighbors=fan_in)
        _, r_ref = rollout(p, st0, ext, self.T, backend="jnp")
        np.testing.assert_allclose(np.asarray(r_ref), np.asarray(r_ev))
        assert float(tel.overflow) == 0.0, "gather path is exact by design"


class TestHLOIdentity:
    """telemetry=False ships the pre-observability program.

    Primary pin (structural, via :mod:`repro.analysis`): hoisted W*C,
    pure hot loop, no 64-bit types, no host calls -- the invariants the
    old byte-identity assertion was standing in for, asserted directly so
    the pin survives harmless lowering churn across jax versions.  ONE
    byte-compare against the inlined oracle remains as a canary; if it
    fails while the structural pin stays green, the lowering drifted
    cosmetically -- re-derive the oracle, don't add more byte pins.
    """

    N, T, D = 16, 8, 4

    def _args(self):
        p = _params(self.N)
        st0 = SNNState.zeros((), self.N, max_delay=self.D)
        ext = _ext(self.N, self.T, seed=7)
        return p, st0, ext

    @staticmethod
    def _oracle(params, state, ext_seq):
        """The frozen jnp rollout as it existed before the obs layer:
        hoisted W*C, delay read, matmul, LIF step, delay write -- no
        TickCarry, no telemetry slot, no named scopes."""
        wc = params.w * params.c.astype(params.w.dtype)
        max_delay = state.delay_buf.shape[-2]

        def body(st, ext):
            slot = jnp.mod(st.tick, max_delay)
            arriving = jax.lax.dynamic_index_in_dim(
                st.delay_buf, slot, axis=-2, keepdims=False
            ) if max_delay > 1 else st.lif.y
            syn = arriving @ wc
            if ext is not None:
                syn = syn + ext @ params.w_in
            lif_state = lif_step(st.lif, syn, params.lif)
            if max_delay > 1:
                write_slot = jnp.mod(st.tick + 1, max_delay)
                delay_buf = jax.lax.dynamic_update_index_in_dim(
                    st.delay_buf, lif_state.y, write_slot, axis=-2)
            else:
                delay_buf = st.delay_buf
            st2 = SNNState(lif=lif_state, delay_buf=delay_buf,
                           tick=st.tick + 1)
            return st2, lif_state.y

        return jax.lax.scan(body, state, ext_seq)

    @staticmethod
    def _lowered(fn, *args):
        txt = jax.jit(fn).lower(*args).as_text()
        return re.sub(r"module @\S+", "module @m", txt)

    def _engine_off(self, p, st, ext):
        return rollout(p, st, ext, self.T, backend="jnp")

    def _engine_on(self, p, st, ext):
        return rollout(p, st, ext, self.T, backend="jnp", telemetry=True)

    def _assert_structurally_clean(self, fn, p, st0, ext, tag):
        from repro.analysis import hlo_rules, jaxpr_rules

        cj = jaxpr_rules.closed_jaxpr_of(fn, p, st0, ext)
        assert jaxpr_rules.check_hot_loop_purity(cj, tag) == []
        assert jaxpr_rules.check_dtype_discipline(cj, tag) == []
        assert jaxpr_rules.check_hoist(
            cj, tag, n=self.N, expect=jaxpr_rules.HOIST_HOISTED) == []
        text = hlo_rules.lowered_text(fn, p, st0, ext)
        assert hlo_rules.check_no_f64_text(text, tag) == []
        assert hlo_rules.check_no_host_calls_text(text, tag) == []
        # Region-aware HLO count agrees with the jaxpr-level contract:
        # exactly one hoisted W*C product, zero per-tick ones.
        assert hlo_rules.wc_multiplies(text, self.N) == (0, 1)

    def test_telemetry_off_structural_pin(self):
        p, st0, ext = self._args()
        self._assert_structurally_clean(
            self._engine_off, p, st0, ext, "obs/telemetry-off")

    def test_telemetry_on_passes_the_same_structural_pin(self):
        """Telemetry adds carry leaves and reductions -- not impurity, not
        a hoist regression (false-positive resistance for the analyzer)."""
        p, st0, ext = self._args()
        self._assert_structurally_clean(
            self._engine_on, p, st0, ext, "obs/telemetry-on")

    def test_canary_telemetry_off_is_byte_identical_to_oracle(self):
        # The one remaining byte-compare (see class docstring).
        p, st0, ext = self._args()
        assert self._lowered(self._engine_off, p, st0, ext) \
            == self._lowered(self._oracle, p, st0, ext)

    def test_teeth_telemetry_on_perturbs_the_lowering(self):
        """Proves the byte-compare can fail: the telemetry-on program
        lowers differently (extra carry leaves + reductions)."""
        p, st0, ext = self._args()

        def engine_on(p, st, ext):
            return rollout(p, st, ext, self.T, backend="jnp",
                           telemetry=True)

        def engine_off(p, st, ext):
            return rollout(p, st, ext, self.T, backend="jnp")

        assert self._lowered(engine_on, p, st0, ext) \
            != self._lowered(engine_off, p, st0, ext)

    def test_oracle_matches_numerically_too(self):
        p, st0, ext = self._args()
        fs_o, r_o = self._oracle(p, st0, ext)
        fs_e, r_e = rollout(p, st0, ext, self.T, backend="jnp")
        np.testing.assert_array_equal(np.asarray(r_o), np.asarray(r_e))
        for a, b in zip(jax.tree.leaves(fs_o), jax.tree.leaves(fs_e)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Tier B: host-side instruments
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs_total", "requests")
        c.inc()
        c.inc(2.5)
        assert c.value() == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_counter_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("waves_total", labelnames=("backend",))
        c.inc(backend="jnp")
        c.inc(2, backend="event")
        assert c.value(backend="jnp") == 1
        assert c.value(backend="event") == 2
        assert c.value(backend="pallas") == 0
        with pytest.raises(ValueError):
            c.inc(nope="x")

    def test_gauge(self):
        reg = MetricsRegistry()
        g = reg.gauge("queue_depth")
        g.set(7)
        assert g.value() == 7
        g.set(3)
        assert g.value() == 3
        g.inc()
        assert g.value() == 4

    def test_histogram_buckets_cumulative(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        assert h.count() == 5
        assert h.sum() == pytest.approx(56.05)
        text = "\n".join(h.expose())
        assert 'lat_bucket{le="0.1"} 1' in text
        assert 'lat_bucket{le="1"} 3' in text
        assert 'lat_bucket{le="10"} 4' in text
        assert 'lat_bucket{le="+Inf"} 5' in text
        assert "lat_count 5" in text

    def test_idempotent_registration_and_kind_mismatch(self):
        reg = MetricsRegistry()
        a = reg.counter("x_total")
        b = reg.counter("x_total")
        assert a is b
        with pytest.raises(ValueError):
            reg.gauge("x_total")

    def test_prometheus_exposition_format(self):
        reg = MetricsRegistry()
        reg.counter("a_total", "things").inc(2)
        reg.gauge("b_depth").set(1)
        reg.histogram("c_seconds", "latency", buckets=(1.0,)).observe(0.5)
        text = reg.to_prometheus()
        assert "# HELP a_total things" in text
        assert "# TYPE a_total counter" in text
        assert "# TYPE b_depth gauge" in text
        assert "# TYPE c_seconds histogram" in text
        assert "a_total 2" in text
        assert 'c_seconds_bucket{le="+Inf"} 1' in text
        assert text.endswith("\n")

    def test_json_dump_round_trips(self):
        reg = MetricsRegistry()
        reg.counter("a_total", labelnames=("k",)).inc(3, k="v")
        reg.histogram("h_s", buckets=(1.0,)).observe(2.0)
        d = json.loads(json.dumps(reg.to_dict()))
        assert d["a_total"]["type"] == "counter"
        assert d["a_total"]["values"] == {'{k="v"}': 3.0}
        assert d["h_s"]["values"][""]["count"] == 1


class TestEventLog:
    def test_emit_filter_and_ring(self):
        log = EventLog(max_records=4)
        for i in range(6):
            log.emit("tick", i=i)
        log.emit("other")
        recs = log.events()
        assert len(recs) == 4  # ring capped
        assert [r["i"] for r in recs if r["event"] == "tick"] == [3, 4, 5]
        assert len(log.events("other")) == 1
        log.clear()
        assert log.events() == []

    def test_stream_mirror_is_json_lines(self):
        buf = io.StringIO()
        log = EventLog(stream=buf)
        log.emit("wave", backend="jnp", n=3)
        line = buf.getvalue().strip()
        rec = json.loads(line)
        assert rec["event"] == "wave" and rec["n"] == 3
        assert "ts" in rec


class TestTracing:
    def test_profile_none_is_noop(self):
        with profile(None):
            x = jnp.ones(3).sum()
        assert float(x) == 3.0

    def test_profile_bad_dir_degrades_to_logged_event(self, tmp_path):
        # Even if the profiler backend objects, serving must not crash.
        with profile(str(tmp_path / "trace")):
            jnp.ones(3).sum()

    def test_span_observes_into_histogram(self):
        reg = MetricsRegistry()
        h = reg.histogram("wave_seconds", labelnames=("backend",))
        with span("test/wave", histogram=h, backend="jnp"):
            pass
        assert h.count(backend="jnp") == 1
        assert h.sum(backend="jnp") >= 0.0

    def test_trace_scope_in_traced_code(self):
        @jax.jit
        def f(x):
            with trace_scope("test/scope"):
                return x * 2

        assert float(f(jnp.float32(3))) == 6.0


# ---------------------------------------------------------------------------
# Serving integration
# ---------------------------------------------------------------------------

class TestServeObservability:
    def _server(self, **kw):
        from repro.launch.serve import SNNServer

        kw.setdefault("n_max", 16)
        kw.setdefault("slots", 4)
        kw.setdefault("max_ticks", 8)
        return SNNServer(**kw)

    def test_registry_metrics_after_serve(self):
        from repro.launch.serve import make_demo_requests, make_demo_tenants

        server = self._server()
        names = make_demo_tenants(server, 4, seed=2)
        reqs = make_demo_requests(server, names, 8, seed=3)
        stats = server.serve_continuous(reqs)
        reg = server.registry
        assert reg.get("snn_requests_total").value() == stats["requests_served"] == 8
        assert reg.get("snn_ttft_seconds").count() == 8
        assert reg.get("snn_queue_depth").value() == 0.0
        assert reg.get("snn_slot_ticks_total").value() == \
            stats["chunks"] * server.slots * server.chunk_ticks
        text = reg.to_prometheus()
        assert "# TYPE snn_requests_total counter" in text
        assert "# TYPE snn_ttft_seconds histogram" in text
        assert "snn_chunks_total{backend=" in text

    def test_tenant_report(self):
        from repro.launch.serve import make_demo_requests, make_demo_tenants

        server = self._server()
        names = make_demo_tenants(server, 4, seed=2)
        stats = server.serve_continuous(
            make_demo_requests(server, names, 8, seed=3))
        report = server.tenant_report()
        assert set(report) == set(names)
        assert sum(r["requests"] for r in report.values()) \
            == stats["requests_served"]
        for r in report.values():
            assert 0.0 <= r["spike_rate"] <= 1.0
            assert 0.0 <= r["refractory_occupancy"] <= 1.0
        plastic = [n for n, r in report.items() if r["plastic"]]
        assert plastic, "demo tenants include one plastic network"
        assert report[plastic[0]]["dw_l1"] > 0, "plastic tenant never learned"
        frozen = [n for n in names if n not in plastic]
        assert all(report[n]["dw_l1"] == 0 for n in frozen)

    def test_empty_queue_zero_report(self):
        server = self._server()
        stats = server.serve_continuous([])
        assert stats["n_requests"] == 0
        assert stats["requests_served"] == 0
        assert stats["requests_rejected"] == 0
        assert stats["chunks"] == 0
        assert stats["mean_ttft_s"] == 0.0

    def test_unknown_tenant_rejected_not_keyerror(self):
        from repro.launch.serve import ServeRequest

        server = self._server()
        bad = ServeRequest(rid=0, tenant="ghost",
                         ext=np.zeros((4, 4), np.float32), n_ticks=4)
        stats = server.serve_continuous([bad])
        assert stats["requests_served"] == 0
        assert stats["requests_rejected"] == 1
        assert server.registry.get("snn_requests_rejected_total").value() == 1

    def test_telemetry_off_server_still_serves(self):
        from repro.launch.serve import make_demo_requests, make_demo_tenants

        server = self._server(telemetry=False)
        names = make_demo_tenants(server, 4, seed=2)
        stats = server.serve_continuous(
            make_demo_requests(server, names, 4, seed=3))
        assert stats["requests_served"] == 4
        assert server.tenant_report() == {}

    def test_lm_serve_empty_queue(self):
        from repro.launch.serve import serve

        stats = serve(None, None, [])
        assert stats["n_requests"] == 0
        assert stats["requests_served"] == 0
        assert stats["mean_ttft_s"] == 0.0


class TestTickTelemetryUnit:
    def test_zeros_shapes(self):
        t = TickTelemetry.zeros((3,))
        assert t.spikes.shape == (3,)
        assert t.ticks.dtype == jnp.int32
        s = TickTelemetry.zeros(()).summary(8)
        assert s["ticks"] == 0.0 and s["spikes"] == 0.0

    def test_accumulate_matches_hand_reductions(self):
        from repro.core.lif import LIFState

        rng = np.random.default_rng(0)
        n = 8
        st = LIFState(
            v=jnp.asarray(rng.normal(size=(n,)), jnp.float32),
            y=jnp.asarray((rng.random(n) < 0.5).astype(np.float32)),
            r=jnp.asarray(rng.integers(0, 3, n), jnp.float32))
        t = TickTelemetry.zeros(()).accumulate(st)
        assert float(t.ticks) == 1
        assert float(t.spikes) == float(np.asarray(st.y).sum())
        assert float(t.v_sum) == pytest.approx(float(np.asarray(st.v).mean()))
        assert float(t.v_max) == pytest.approx(float(np.asarray(st.v).max()))
        assert float(t.ref_sum) == pytest.approx(
            float((np.asarray(st.r) > 0).mean()))

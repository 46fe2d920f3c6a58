"""Continuous admission: per-slot refill equals the wave path exactly.

Pins the tentpole contract: chunked per-slot scheduling returns the
same per-request counts, predictions, and learned weights as wave
admission, bit for bit -- while never retracing across slot refills,
mixing dense and event tenants, and handling the admission edges
(zero-tick budgets, unknown tenants, feeder-streamed late arrivals).
"""
from collections import deque

import jax
import numpy as np
import pytest

from repro.launch.serve import (
    ServeRequest, ServeResult, SNNServer, make_demo_requests,
    make_demo_tenants,
)

jax.config.update("jax_platform_name", "cpu")


def _twin_servers(**kw):
    """Two identically-built servers (tenants, seeds, everything) so the
    wave and continuous paths start from the same learned state."""
    kw.setdefault("n_max", 24)
    kw.setdefault("slots", 4)
    kw.setdefault("max_ticks", 12)
    kw.setdefault("event_density", 0.2)
    a, b = SNNServer(**kw), SNNServer(**kw)
    names = make_demo_tenants(a, 8, seed=0)
    assert make_demo_tenants(b, 8, seed=0) == names
    return a, b, names


class TestWaveOracle:
    def test_counts_preds_weights_bit_exact_vs_wave(self):
        sw, sc, names = _twin_servers()
        reqs_w = make_demo_requests(sw, names, 16, seed=1)
        reqs_c = make_demo_requests(sc, names, 16, seed=1)
        sw.serve(reqs_w)
        sc.serve_continuous(reqs_c)
        for a, b in zip(reqs_w, reqs_c):
            assert a.pred == b.pred
            np.testing.assert_array_equal(a.counts, b.counts)
        # Plastic write-back: the learned registers match too.
        for n in names:
            np.testing.assert_array_equal(
                np.asarray(sw.tenants[n].params.w),
                np.asarray(sc.tenants[n].params.w))

    def test_exact_across_chunk_sizes(self):
        sw, _, names = _twin_servers()
        reqs_w = make_demo_requests(sw, names, 8, seed=3)
        sw.serve(reqs_w)
        for chunk in (1, 5, 12):
            sc = SNNServer(n_max=24, slots=4, max_ticks=12,
                           event_density=0.2)
            make_demo_tenants(sc, 8, seed=0)
            reqs_c = make_demo_requests(sc, names, 8, seed=3)
            sc.serve_continuous(reqs_c, chunk_ticks=chunk)
            for a, b in zip(reqs_w, reqs_c):
                assert a.pred == b.pred, f"chunk_ticks={chunk}"
                np.testing.assert_array_equal(a.counts, b.counts)

    def test_mixed_dense_and_event_tenants(self):
        _, sc, names = _twin_servers()
        backends = {sc.tenants[n].backend for n in names}
        assert backends == {"jnp", "event"}
        reqs = make_demo_requests(sc, names, 12, seed=2)
        stats = sc.serve_continuous(reqs)
        assert stats["requests_served"] == 12
        assert set(stats["backends"]) == {"jnp", "event"}


class TestZeroRecompile:
    def test_slot_refills_never_retrace(self):
        _, sc, names = _twin_servers()
        sc.serve_continuous(make_demo_requests(sc, names, 4, seed=9))
        warm = sc.compiles
        stats = sc.serve_continuous(make_demo_requests(sc, names, 20, seed=1))
        assert sc.compiles == warm, "slot refill retraced the chunk program"
        assert stats["recompiles_after_warmup"] == 0

    def test_second_batch_reuses_programs(self):
        _, sc, names = _twin_servers()
        sc.serve_continuous(make_demo_requests(sc, names, 8, seed=1))
        warm = sc.compiles
        sc.serve_continuous(make_demo_requests(sc, names, 8, seed=2))
        assert sc.compiles == warm

    def test_chunk_program_text_is_the_served_program(self):
        """The text comes from the program serving ran, at the shapes it
        ran with: asking for it traces nothing new."""
        _, sc, names = _twin_servers()
        sc.serve_continuous(make_demo_requests(sc, names, 8, seed=1))
        warm = sc.compiles
        for backend in ("jnp", "event"):
            assert "HloModule" in sc.chunk_program_text(backend)
        assert sc.compiles == warm
        with pytest.raises(KeyError):
            sc.chunk_program_text("pallas_fused")


class TestAdmissionEdges:
    def test_zero_tick_budget_completes_without_running(self):
        _, sc, names = _twin_servers()
        t = sc.tenants[names[0]]
        r = ServeRequest(rid=0, tenant=names[0],
                         ext=np.zeros((1, t.n_in), np.float32), n_ticks=0)
        stats = sc.serve_continuous([r])
        assert stats["requests_served"] == 1
        assert r.t_done is not None
        np.testing.assert_array_equal(r.counts, np.zeros_like(r.counts))

    def test_unknown_tenant_rejected_and_counted(self):
        _, sc, names = _twin_servers()
        bad = ServeRequest(rid=0, tenant="ghost",
                           ext=np.zeros((2, 4), np.float32), n_ticks=2)
        ok = make_demo_requests(sc, names, 2, seed=1)
        stats = sc.serve_continuous([bad] + ok)
        assert stats["requests_rejected"] == 1
        assert stats["requests_served"] == 2
        assert sc.registry.get("snn_admission_rejections_total").value(
            reason="unknown_tenant") == 1

    def test_feeder_streams_late_arrivals(self):
        _, sc, names = _twin_servers()
        late = deque(make_demo_requests(sc, names, 6, seed=4))
        completed = []
        stats = sc.serve_continuous(
            make_demo_requests(sc, names, 2, seed=5),
            feeder=lambda: late.popleft() if late else None,
            on_complete=completed.append)
        assert stats["requests_served"] == 8
        assert len(completed) == 8
        assert not late

    def test_chunk_ticks_validated(self):
        _, sc, _ = _twin_servers()
        with pytest.raises(ValueError, match="chunk_ticks"):
            sc.serve_continuous([], chunk_ticks=0)
        with pytest.raises(ValueError, match="chunk_ticks"):
            sc.serve_continuous([], chunk_ticks=sc.max_ticks + 1)


class TestStatsSchema:
    def test_same_keys_wave_continuous_and_empty(self):
        sw, sc, names = _twin_servers()
        wave = sw.serve(make_demo_requests(sw, names, 4, seed=1))
        cont = sc.serve_continuous(make_demo_requests(sc, names, 4, seed=1))
        empty = sc.serve_continuous([])
        assert set(wave) == set(cont) == set(empty)
        assert wave["mode"] == "wave"
        assert cont["mode"] == "continuous"
        assert empty["requests_served"] == 0
        assert empty["p99_ttft_s"] == 0.0

    def test_ttft_measured_from_enqueue_not_wave_start(self):
        _, sc, names = _twin_servers()
        reqs = make_demo_requests(sc, names, 2, seed=1)
        t_early = 1.0   # an epoch stamp far in the past
        for r in reqs:
            r.t_submit = t_early
        stats = sc.serve_continuous(reqs)
        # If TTFT were re-stamped at wave/chunk start these would be
        # sub-second; from the caller's enqueue they are epoch-sized.
        assert stats["mean_ttft_s"] > 1e6

    def test_results_are_serve_results(self):
        _, sc, names = _twin_servers()
        stats = sc.serve_continuous(make_demo_requests(sc, names, 3, seed=1))
        assert len(stats["results"]) == 3
        for res in stats["results"]:
            assert isinstance(res, ServeResult)
            assert not res.rejected
            assert res.ttft_s >= 0.0


class TestDeprecatedShims:
    def test_snn_request_shim_warns_and_serves(self):
        from repro.launch.serve import SNNRequest

        _, sc, names = _twin_servers()
        t = sc.tenants[names[0]]
        with pytest.warns(DeprecationWarning, match="SNNRequest"):
            r = SNNRequest(rid=0, tenant=names[0],
                           ext=np.zeros((2, t.n_in), np.float32), n_ticks=2)
        stats = sc.serve_continuous([r])
        assert stats["requests_served"] == 1

    def test_lm_request_shim_warns(self):
        from repro.launch.serve import Request

        with pytest.warns(DeprecationWarning, match="Request"):
            Request(rid=0, prompt=np.zeros((4,), np.int32), max_new=2)

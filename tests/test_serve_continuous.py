"""Continuous admission: per-slot refill equals each request run alone.

Pins the serving contract: chunked per-slot scheduling returns the
same per-request counts, predictions, and learned weights as the
per-request reference (``serve_reference``: each request alone on the
unbatched core engine, in submission order), bit for bit -- while
never retracing across slot refills, mixing dense and event tenants,
and handling the admission edges (zero-tick budgets, unknown tenants,
feeder-streamed late arrivals).
"""
import functools
import re
import warnings
from collections import deque

import jax
import numpy as np
import pytest

from repro.launch.serve import (
    ServeRequest, ServeResult, SNNServer, make_demo_requests,
    make_demo_tenants,
)
from serve_reference import serve_reference

jax.config.update("jax_platform_name", "cpu")


def _twin_servers(**kw):
    """Two identically-built servers (tenants, seeds, everything) so the
    reference and the continuous path start from the same learned
    state."""
    kw.setdefault("n_max", 24)
    kw.setdefault("slots", 4)
    kw.setdefault("max_ticks", 12)
    kw.setdefault("event_density", 0.2)
    a, b = SNNServer(**kw), SNNServer(**kw)
    names = make_demo_tenants(a, 8, seed=0)
    assert make_demo_tenants(b, 8, seed=0) == names
    return a, b, names


def _assert_same(ref_server, reqs_ref, server, reqs, msg=""):
    """Bitwise: every request's counts and prediction, every tenant's
    weights (the learned ones included)."""
    for a, b in zip(reqs_ref, reqs):
        assert a.pred == b.pred, msg
        np.testing.assert_array_equal(a.counts, b.counts, err_msg=msg)
    for n in ref_server.tenants:
        np.testing.assert_array_equal(
            np.asarray(ref_server.tenants[n].params.w),
            np.asarray(server.tenants[n].params.w), err_msg=msg)


class TestWaveOracle:
    """The per-request reference is the oracle of the served path."""

    def test_counts_preds_weights_bit_exact_vs_wave(self):
        sr, sc, names = _twin_servers()
        reqs_r = make_demo_requests(sr, names, 16, seed=1)
        reqs_c = make_demo_requests(sc, names, 16, seed=1)
        serve_reference(sr, reqs_r)
        sc.serve_continuous(reqs_c)
        # Plastic write-back: the learned registers match too.
        _assert_same(sr, reqs_r, sc, reqs_c)

    def test_exact_across_chunk_sizes(self):
        sr, _, names = _twin_servers()
        reqs_r = make_demo_requests(sr, names, 8, seed=3)
        serve_reference(sr, reqs_r)
        for chunk in (1, 5, 12):
            sc = SNNServer(n_max=24, slots=4, max_ticks=12,
                           event_density=0.2)
            make_demo_tenants(sc, 8, seed=0)
            reqs_c = make_demo_requests(sc, names, 8, seed=3)
            sc.serve_continuous(reqs_c, chunk_ticks=chunk)
            _assert_same(sr, reqs_r, sc, reqs_c, f"chunk_ticks={chunk}")

    def test_mixed_dense_and_event_tenants(self):
        sr, sc, names = _twin_servers()
        backends = {sc.tenants[n].backend for n in names}
        assert backends == {"jnp", "event"}
        reqs_r = make_demo_requests(sr, names, 12, seed=2)
        reqs_c = make_demo_requests(sc, names, 12, seed=2)
        serve_reference(sr, reqs_r)
        stats = sc.serve_continuous(reqs_c)
        assert stats["requests_served"] == 12
        assert set(stats["backends"]) == {"jnp", "event"}
        _assert_same(sr, reqs_r, sc, reqs_c)


def _requests(server, spec, *, seed):
    """Requests as ``(tenant, budget)`` pairs, with random impulse drive
    for all ``max_ticks`` ticks: the fabric keeps spiking past a budget,
    so a tick past it would learn if the budget did not stop the hook."""
    rng = np.random.default_rng(seed)
    reqs = []
    T = server.max_ticks
    for i, (name, ticks) in enumerate(spec):
        t = server.tenants[name]
        ext = ((rng.random((T, t.n_in)) < 0.3)
               * rng.integers(80, 255, (T, t.n_in))).astype(np.float32)
        reqs.append(ServeRequest(rid=i, tenant=name, ext=ext, n_ticks=ticks))
    return reqs


# Which tenants learn, against the chunk program's per-slot learning cond:
# the demo's one plastic tenant is "dense-7"; "sparse-*" ride the event
# program (frozen); the rest are frozen dense tenants.
_LEARNING_CASES = {
    "plastic_and_frozen_mixed": [
        ("dense-7", 9), ("layered-0", 12), ("dense-3", 5), ("dense-7", 12),
        ("ring-1", 7), ("layered-4", 4), ("dense-7", 6), ("ring-5", 10)],
    "all_frozen": [
        ("dense-3", 9), ("layered-0", 12), ("ring-1", 5), ("layered-4", 7),
        ("ring-5", 3)],
    "plastic_budget_ends_mid_chunk": [
        ("dense-7", 3), ("dense-3", 8), ("dense-7", 11), ("layered-0", 4),
        ("dense-7", 3)],
    "event_frozen_slots": [
        ("sparse-2", 9), ("sparse-6", 12), ("sparse-2", 5), ("sparse-6", 3),
        ("sparse-2", 11)],
}


def _assert_same_dw(dw_ref, server):
    """The served weight-delta telemetry equals the reference's, per
    tenant, as summed and as reported."""
    rep = server.tenant_report()
    for n in rep:
        assert server._tenant_obs[n]["dw_l1"] == dw_ref[n]
        assert rep[n]["dw_l1"] == round(dw_ref[n], 3)


class TestLearningOnlyWhereItLearns:
    """The chunk program runs the plasticity hook only on slot-ticks that
    learn; the reference runs each request alone through
    ``learning_rollout`` with ``learn_until=budget``, and is the
    oracle."""

    @pytest.mark.parametrize("case,backend", [
        *(pytest.param(c, "jnp", id=c) for c in sorted(_LEARNING_CASES)),
        # The dense tenants on the whole-tick kernel's slot grid.
        pytest.param("plastic_and_frozen_mixed", "pallas_fused",
                     id="plastic_and_frozen_mixed-pallas_fused")])
    def test_bit_exact_vs_wave(self, case, backend):
        sr, sc, _ = _twin_servers(backend=backend)
        spec = _LEARNING_CASES[case]
        reqs_r = _requests(sr, spec, seed=7)
        reqs_c = _requests(sc, spec, seed=7)
        dw_ref = serve_reference(sr, reqs_r)
        sc.serve_continuous(reqs_c, chunk_ticks=4)
        _assert_same(sr, reqs_r, sc, reqs_c)
        rep_c = sc.tenant_report()
        assert set(rep_c) == set(dw_ref) == {n for n, _ in spec}
        _assert_same_dw(dw_ref, sc)
        plastic_dw = sum(rep_c[n]["dw_l1"] for n in rep_c
                         if sc.tenants[n].plastic)
        assert (plastic_dw > 0) == any(n == "dense-7" for n, _ in spec)

    @pytest.mark.parametrize("case", sorted(_LEARNING_CASES))
    def test_learning_slot_ticks_counted(self, case):
        _, sc, _ = _twin_servers()
        spec = _LEARNING_CASES[case]
        sc.serve_continuous(_requests(sc, spec, seed=7), chunk_ticks=4)
        want = sum(min(ticks, sc.max_ticks) for name, ticks in spec
                   if sc.tenants[name].plastic)
        reg = sc.registry
        assert reg.get("snn_learning_slot_ticks_total").value() == want
        assert 0 < reg.get("snn_slot_ticks_total").value()
        if case in ("all_frozen", "event_frozen_slots"):
            assert want == 0

    def test_hook_only_inside_a_per_slot_cond(self):
        """In the chunk program's jaxpr the plasticity step sits only in a
        ``cond`` branch, and no select over a stacked ``(S, N, N)`` operand
        (a vmapped ``cond`` or ``learn_until`` gate) is left outside."""
        _, sc, names = _twin_servers()
        sc.serve_continuous(make_demo_requests(sc, names, 8, seed=1))
        key = ("jnp", sc.chunk_ticks)
        jaxpr = jax.make_jaxpr(
            functools.partial(sc._chunk_fn, *key))(*sc._chunk_arg_specs[key])
        S, N = sc.slots, sc.n_max
        found = []

        def walk(jx, in_cond):
            for eqn in jx.eqns:
                found.append((eqn, in_cond))
                for v in eqn.params.values():
                    for sub in v if isinstance(v, (tuple, list)) else (v,):
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            walk(sub, in_cond or eqn.primitive.name == "cond")

        walk(jaxpr.jaxpr, False)
        hook = [c for e, c in found
                if "tick/plasticity" in str(e.source_info.name_stack)]
        assert hook and all(hook)
        stacked = [e for e, c in found if not c and e.primitive.name ==
                   "select_n" and any(getattr(v.aval, "shape", ()) ==
                                      (S, N, N) for v in e.invars)]
        assert not stacked


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


def _entry_param_copies(text: str, shape: str):
    """(parameters of ``shape``, those of them the entry computation of a
    compiled module copies whole)."""
    entry = text[text.index("\nENTRY"):]
    params = re.findall(
        r"%(\S+) = " + re.escape(shape) + r"\{[\d,]*\} parameter\(", entry)
    return params, [p for p in params if f"copy(%{p})" in entry]


def _serve_erroring_on_warnings(server, reqs, **kw):
    """Serve with every warning raised as an error: a donated buffer the
    program cannot reuse warns ("donated buffers were not usable")."""
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        return server.serve_continuous(reqs, **kw)


class TestInPlace:
    """The refill and chunk programs donate the stacked operands they
    update, so neither copies a whole ``(S, N, N)`` stack per call."""

    @pytest.fixture(scope="class")
    def served(self):
        _, sc, names = _twin_servers()
        reqs = make_demo_requests(sc, names, 16, seed=1)
        _serve_erroring_on_warnings(sc, reqs)
        return sc, reqs

    @pytest.mark.parametrize("backend", ["jnp", "event"])
    def test_fill_updates_every_stack_in_place(self, served, backend):
        sc, _ = served
        S, N = sc.slots, sc.n_max
        stack = S * N * N * 4
        # params.w, .c, .w_in, carry.w, carry.plast.elig, plastic_c
        assert sc.program_alias_bytes(backend, "fill") >= 6 * stack
        text = sc._compiled(("fill", backend)).as_text()
        params, copied = _entry_param_copies(text, f"f32[{S},{N},{N}]")
        assert len(params) == 6
        assert not copied

    @pytest.mark.parametrize("backend", ["jnp", "event"])
    def test_chunk_enters_carry_in_place(self, served, backend):
        sc, _ = served
        S, N = sc.slots, sc.n_max
        # carry.w and carry.plast.elig, plus the (S, N) running counts
        want = 2 * S * N * N * 4 + S * N * 4
        assert sc.program_alias_bytes(backend, "chunk") >= want
        params, copied = _entry_param_copies(
            sc.chunk_program_text(backend), f"f32[{S},{N},{N}]")
        carry = [p for p in params if p.startswith("carry")]
        assert len(carry) == 2
        assert not [p for p in copied if p.startswith("carry")]

    def test_asking_traces_nothing_and_rejects_unknown_programs(
            self, served):
        sc, _ = served
        warm = sc.compiles
        sc.program_alias_bytes("jnp", "fill")
        sc.program_alias_bytes("jnp", "chunk")
        assert sc.compiles == warm
        with pytest.raises(ValueError, match="program"):
            sc.program_alias_bytes("jnp", "wave")

    def test_refills_counted_per_program(self, served):
        """Each backend's group builds its stack with the first fill and
        refills in place for every later request."""
        sc, reqs = served
        refills = sc.registry.get("snn_slot_refills_total")
        for backend in ("jnp", "event"):
            n = sum(sc.tenants[r.tenant].backend == backend for r in reqs)
            assert n > 1
            assert refills.value(backend=backend) == n - 1


class TestDonationSafe:
    """Host reads that straddle a donating call: a plastic write-back read
    from a carry the next refill donates, a zero-budget retire straight
    after a refill, and a second call on the same server."""

    BATCHES = (
        [("dense-7", 4), ("dense-3", 0), ("dense-7", 4), ("layered-0", 0),
         ("dense-7", 0), ("dense-7", 8), ("ring-1", 6)],
        [("dense-7", 5), ("sparse-2", 3), ("dense-7", 0), ("dense-3", 7),
         ("sparse-6", 0), ("sparse-2", 9), ("dense-7", 9)],
    )

    @pytest.mark.parametrize("chunk", [1, 4])
    def test_bit_exact_vs_wave_across_refills_and_calls(self, chunk):
        sr, sc, _ = _twin_servers()
        dw_ref = {}
        for seed, spec in enumerate(self.BATCHES):
            reqs_r = _requests(sr, spec, seed=seed)
            reqs_c = _requests(sc, spec, seed=seed)
            for n, dw in serve_reference(sr, reqs_r).items():
                dw_ref[n] = dw_ref.get(n, 0.0) + dw
            _serve_erroring_on_warnings(sc, reqs_c, chunk_ticks=chunk)
            _assert_same(sr, reqs_r, sc, reqs_c)
            # The served path observes only slots that ran a tick, so a
            # tenant seen only at budget 0 is in the reference's alone.
            _assert_same_dw(dw_ref, sc)
        assert sc.tenant_report()["dense-7"]["dw_l1"] > 0


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
        """A served call, an empty queue and a fully rejected one report
        the same keys."""
        _, sc, names = _twin_servers()
        cont = sc.serve_continuous(make_demo_requests(sc, names, 4, seed=1))
        empty = sc.serve_continuous([])
        ghost = ServeRequest(rid=0, tenant="ghost",
                             ext=np.zeros((2, 4), np.float32), n_ticks=2)
        rejected = sc.serve_continuous([ghost])
        assert set(cont) == set(empty) == set(rejected)
        assert cont["requests_served"] == 4 and cont["chunks"] > 0
        assert empty["requests_served"] == 0
        assert empty["p99_ttft_s"] == 0.0
        assert rejected["requests_rejected"] == 1

    def test_ttft_measured_from_enqueue_not_wave_start(self):
        _, sc, names = _twin_servers()
        reqs = make_demo_requests(sc, names, 2, seed=1)
        t_early = 1.0   # an epoch stamp far in the past
        for r in reqs:
            r.t_submit = t_early
        stats = sc.serve_continuous(reqs)
        # If TTFT were re-stamped at slot-fill start these would be
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

"""Multi-tenant SNN serving: one compiled chunk program, many networks.

Pins the acceptance criteria: >= 8 heterogeneous tenants (different C
topologies and LIF registers, incl. a plastic one) through ONE jitted
chunk program (plus its slot-refill program) per backend, with zero
recompiles across tenant swaps; frozen tenants come back bit-identical
from the shared learning datapath; the served datapath equals the core
engine run tenant-by-tenant; per-request tick budgets mask, never
retrace.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import connectivity
from repro.core.lif import LIFParams
from repro.core.network import SNNParams, SNNState, rollout
from repro.core.registers import RegisterBank, WeightLayout
from repro.launch.serve import (
    ServeRequest, SNNServer, make_demo_requests, make_demo_tenants,
)

jax.config.update("jax_platform_name", "cpu")

N_MAX = 16


def _server(**kw):
    kw.setdefault("n_max", N_MAX)
    kw.setdefault("slots", 4)
    kw.setdefault("max_ticks", 10)
    return SNNServer(**kw)


def _layered_bank(n_in, n_out, *, w=120, th=80, seed=0):
    n = n_in + n_out
    bank = RegisterBank(n, weight_layout=WeightLayout.PER_SYNAPSE)
    c = connectivity.layered([n_in, n_out])
    bank.set_connection_list(c)
    rng = np.random.default_rng(seed)
    bank.set_weights((rng.integers(w // 2, w, (n, n)) * c).astype(np.uint8))
    bank.set_thresholds(np.full((n,), th, np.uint8))
    return bank


# One backend's resident programs: the chunk step and the slot refill.
PROGRAMS_PER_BACKEND = 2


def _drive(t, n_in, *, mag=200.0, p=0.5, seed=0):
    rng = np.random.default_rng(seed)
    return ((rng.random((t, n_in)) < p) * mag).astype(np.float32)


class TestOneProgramManyTenants:
    def test_eight_heterogeneous_tenants_zero_recompiles(self):
        server = _server(slots=4)
        names = make_demo_tenants(server, 8, seed=1)
        assert len(names) == 8
        # heterogeneous: multiple topologies and register settings
        cs = [np.asarray(server.tenants[n].params.c) for n in names]
        assert len({c.tobytes() for c in cs}) == 8
        reqs = make_demo_requests(server, names, 16, seed=2)
        stats = server.serve_continuous(reqs)
        assert stats["n_requests"] == 16
        assert stats["n_tenants"] == 8
        assert stats["compiles"] == PROGRAMS_PER_BACKEND, \
            "slot/tenant churn must not retrace"
        assert stats["recompiles_after_warmup"] == 0
        # serving again (new tenants swapped through the same slots) stays warm
        stats2 = server.serve_continuous(
            make_demo_requests(server, names, 8, seed=3))
        assert stats2["compiles"] == PROGRAMS_PER_BACKEND
        assert stats2["recompiles_after_warmup"] == 0

    def test_served_wave_matches_core_engine_per_tenant(self):
        """The slot axis is transparent: serving == rollout, tenant by tenant."""
        server = _server(slots=4, max_ticks=8)
        names = make_demo_tenants(server, 4, seed=5)
        reqs = make_demo_requests(server, names, 4, seed=6)
        stats = server.serve_continuous(reqs)
        for r in reqs:
            t = server.tenants[r.tenant]
            ext = np.zeros((server.max_ticks, server.n_max), np.float32)
            ext[: r.ext.shape[0], : r.ext.shape[1]] = r.ext
            st0 = SNNState.zeros((), server.n_max)
            _, raster = rollout(t.params, st0, jnp.asarray(ext),
                                server.max_ticks)
            counts = np.asarray(raster)[: r.n_ticks].sum(0)
            expect = counts[t.n - t.n_out : t.n]
            np.testing.assert_array_equal(r.counts, expect)
        assert stats["recompiles_after_warmup"] == 0

    def test_rate_decoded_argmax(self):
        """A tenant wired so output neuron 1 dominates decodes to pred=1."""
        server = _server(slots=2, max_ticks=8)
        n_in, n_out = 3, 3
        bank = _layered_bank(n_in, n_out, seed=0)
        w = np.zeros((n_in + n_out, n_in + n_out), np.uint8)
        w[:n_in, n_in + 1] = 250          # all inputs drive output neuron 1
        bank.set_weights(w)
        bank.set_thresholds(np.full((n_in + n_out,), 50, np.uint8))
        server.add_tenant("biased", bank, n_in=n_in, n_out=n_out)
        req = ServeRequest(rid=0, tenant="biased",
                         ext=_drive(8, n_in, seed=1), n_ticks=8)
        server.serve_continuous([req])
        assert req.pred == 1
        assert req.counts[1] > 0

    def test_tick_budget_masks_not_retraces(self):
        server = _server(slots=2, max_ticks=10)
        bank = _layered_bank(4, 2, seed=3)
        server.add_tenant("t", bank, n_in=4, n_out=2)
        ext = _drive(10, 4, seed=4)
        full = ServeRequest(rid=0, tenant="t", ext=ext, n_ticks=10)
        short = ServeRequest(rid=1, tenant="t", ext=ext, n_ticks=3)
        server.serve_continuous([full, short])
        assert server.compiles == PROGRAMS_PER_BACKEND
        assert short.counts.sum() <= full.counts.sum()
        # budget-3 counts == the first 3 ticks of the full raster
        t = server.tenants["t"]
        pad = np.zeros((10, server.n_max), np.float32)
        pad[:, :4] = ext
        _, raster = rollout(t.params, SNNState.zeros((), server.n_max),
                            jnp.asarray(pad), 10)
        expect = np.asarray(raster)[:3].sum(0)[t.n - t.n_out : t.n]
        np.testing.assert_array_equal(short.counts, expect)


class TestEventTenancy:
    """Sparse tenants pick the event program per slot (DESIGN.md §10)."""

    def _sparse_bank(self, n, *, seed):
        rng = np.random.default_rng(seed)
        c = connectivity.sparse_random(n, 0.1, seed=seed)
        bank = RegisterBank(n, weight_layout=WeightLayout.PER_SYNAPSE)
        bank.set_connection_list(c)
        bank.set_weights((rng.integers(60, 200, (n, n)) * c).astype(np.uint8))
        bank.set_thresholds(np.full((n,), 70, np.uint8))
        return bank

    def test_sparse_tenant_routes_to_event_backend(self):
        server = _server(event_density=0.2)
        server.add_tenant("sparse", self._sparse_bank(N_MAX, seed=20),
                          n_in=N_MAX, n_out=N_MAX)
        server.add_tenant("dense", _layered_bank(8, 8, seed=21), n_in=8,
                          n_out=8)
        assert server.tenants["sparse"].backend == "event"
        assert server.tenants["sparse"].fan_idx.shape == (
            N_MAX, server.event_cap)
        assert server.tenants["dense"].backend == "jnp"
        assert server.tenants["dense"].fan_idx is None

    def test_event_disabled_by_default(self):
        server = _server()
        server.add_tenant("sparse", self._sparse_bank(N_MAX, seed=22),
                          n_in=N_MAX, n_out=N_MAX)
        assert server.tenants["sparse"].backend == "jnp"

    def test_mixed_waves_one_compile_per_backend_zero_recompiles(self):
        server = _server(slots=2, event_density=0.2)
        server.add_tenant("s0", self._sparse_bank(N_MAX, seed=23),
                          n_in=N_MAX, n_out=N_MAX)
        server.add_tenant("s1", self._sparse_bank(N_MAX, seed=24),
                          n_in=N_MAX, n_out=N_MAX)
        server.add_tenant("d0", _layered_bank(8, 8, seed=25), n_in=8, n_out=8)
        reqs = []
        for i, name in enumerate(["s0", "d0", "s1", "d0", "s0"]):
            t = server.tenants[name]
            reqs.append(ServeRequest(rid=i, tenant=name,
                                   ext=_drive(6, t.n_in, seed=30 + i),
                                   n_ticks=6))
        stats = server.serve_continuous(reqs)
        assert stats["n_requests"] == 5
        assert stats["backends"] == {"event": 3, "jnp": 2}
        assert stats["compiles"] == 2 * PROGRAMS_PER_BACKEND
        assert stats["recompiles_after_warmup"] == 0
        # a second mixed queue stays warm on both backends' programs
        stats2 = server.serve_continuous([ServeRequest(
            rid=9, tenant=name, ext=_drive(5, server.tenants[name].n_in,
                                           seed=40), n_ticks=5)
            for name in ("s1", "d0")])
        assert stats2["compiles"] == 2 * PROGRAMS_PER_BACKEND
        assert stats2["recompiles_after_warmup"] == 0

    def test_event_wave_matches_core_engine_rollout(self):
        """The event program's served raster equals the plain jnp rollout
        tenant-by-tenant (bit-exact at fabric size)."""
        server = _server(slots=2, max_ticks=8, event_density=0.2)
        server.add_tenant("s", self._sparse_bank(N_MAX, seed=26),
                          n_in=N_MAX, n_out=N_MAX)
        req = ServeRequest(rid=0, tenant="s", ext=_drive(8, N_MAX, seed=27),
                         n_ticks=8)
        server.serve_continuous([req])
        t = server.tenants["s"]
        ext = np.zeros((8, N_MAX), np.float32)
        ext[: req.ext.shape[0]] = req.ext
        _, raster = rollout(t.params, SNNState.zeros((), N_MAX),
                            jnp.asarray(ext), 8)
        np.testing.assert_array_equal(
            req.counts, np.asarray(raster).sum(0)[t.n - t.n_out : t.n])

    def test_hub_tenant_exceeding_cap_stays_dense(self):
        """A sparse-by-density tenant with one hub neuron above the fan-in
        cap must NOT ride the event program (the cap never truncates)."""
        n = N_MAX
        c = np.zeros((n, n), np.bool_)
        c[:, 0] = True            # hub in-degree n > default cap n//4
        c[0, 0] = False
        bank = RegisterBank(n, weight_layout=WeightLayout.PER_SYNAPSE)
        bank.set_connection_list(c)
        bank.set_weights((np.full((n, n), 90) * c).astype(np.uint8))
        bank.set_thresholds(np.full((n,), 70, np.uint8))
        server = _server(event_density=0.2)
        server.add_tenant("hub", bank, n_in=n, n_out=n)
        assert server.tenants["hub"].density <= 0.2
        assert server.tenants["hub"].backend == "jnp"


class TestPlasticTenancy:
    def test_frozen_tenants_bit_identical_plastic_learns(self):
        server = _server(slots=4, max_ticks=10)
        frozen_bank = _layered_bank(4, 4, seed=7)
        plastic_bank = _layered_bank(4, 4, seed=7)   # same image, one learns
        server.add_tenant("frozen", frozen_bank, n_in=4, n_out=4)
        server.add_tenant("plastic", plastic_bank, n_in=4, n_out=4,
                          plastic=True)
        w_frozen0 = np.asarray(server.tenants["frozen"].params.w).copy()
        w_plastic0 = np.asarray(server.tenants["plastic"].params.w).copy()
        np.testing.assert_array_equal(w_frozen0, w_plastic0)

        ext = _drive(10, 4, p=0.7, seed=8)
        for _ in range(3):
            server.serve_continuous([
                ServeRequest(rid=0, tenant="frozen", ext=ext, n_ticks=10),
                ServeRequest(rid=1, tenant="plastic", ext=ext, n_ticks=10),
            ])
        w_frozen1 = np.asarray(server.tenants["frozen"].params.w)
        w_plastic1 = np.asarray(server.tenants["plastic"].params.w)
        # shared learning datapath, exact no-op for the frozen tenant
        np.testing.assert_array_equal(w_frozen0, w_frozen1)
        # the plastic tenant's registers moved (write-back across requests)
        assert not np.array_equal(w_plastic0, w_plastic1)
        # and stayed in the u8 register domain (serializable to the bank)
        assert w_plastic1.min() >= 0.0 and w_plastic1.max() <= 255.0
        assert server.compiles == PROGRAMS_PER_BACKEND

    def test_same_plastic_tenant_twice_equals_sequential(self):
        """Two requests for one plastic tenant must not race on write-back:
        admission defers the duplicate, so the result equals serving them
        strictly one after the other."""
        def build():
            server = _server(slots=2, max_ticks=8)
            server.add_tenant("p", _layered_bank(4, 4, seed=12), n_in=4,
                              n_out=4, plastic=True)
            return server

        e1, e2 = _drive(8, 4, p=0.7, seed=13), _drive(8, 4, p=0.7, seed=14)
        together = build()
        together.serve_continuous([
            ServeRequest(rid=0, tenant="p", ext=e1, n_ticks=8),
            ServeRequest(rid=1, tenant="p", ext=e2, n_ticks=8)])
        sequential = build()
        sequential.serve_continuous(
            [ServeRequest(rid=0, tenant="p", ext=e1, n_ticks=8)])
        sequential.serve_continuous(
            [ServeRequest(rid=1, tenant="p", ext=e2, n_ticks=8)])
        np.testing.assert_array_equal(
            np.asarray(together.tenants["p"].params.w),
            np.asarray(sequential.tenants["p"].params.w))

    def test_budget_gates_learning_not_just_decode(self):
        """A request's persisted weights must not depend on the server's
        max_ticks ceiling: learning stops at the request's own budget."""
        ext = _drive(6, 4, p=0.8, seed=15)

        def learned_w(max_ticks):
            server = _server(slots=2, max_ticks=max_ticks)
            server.add_tenant("p", _layered_bank(4, 4, seed=16), n_in=4,
                              n_out=4, plastic=True)
            server.serve_continuous(
                [ServeRequest(rid=0, tenant="p", ext=ext, n_ticks=6)])
            return np.asarray(server.tenants["p"].params.w)

        np.testing.assert_array_equal(learned_w(6), learned_w(12))

    def test_serve_empty_queue(self):
        server = _server()
        stats = server.serve_continuous([])
        assert stats["n_requests"] == 0 and stats["chunks"] == 0
        assert stats["requests_served"] == 0
        assert stats["mean_ttft_s"] == 0.0  # never np.mean([])

    def test_serve_fully_rejected_queue(self):
        """Every request names an unknown tenant: zero report, counted
        rejections, no KeyError mid-serve."""
        server = _server()
        bad = [ServeRequest(rid=i, tenant=f"ghost-{i}",
                          ext=np.zeros((4, 4), np.float32), n_ticks=4)
               for i in range(3)]
        stats = server.serve_continuous(bad)
        assert stats["requests_served"] == 0
        assert stats["requests_rejected"] == 3
        assert stats["chunks"] == 0 and stats["mean_ttft_s"] == 0.0

    def test_rectangular_w_in_pads(self):
        import dataclasses as dc
        from repro.launch.serve import pad_tenant_params
        from repro.core.network import params_from_registers

        bank = _layered_bank(3, 3, seed=17)
        p = params_from_registers(bank)
        p = dc.replace(p, w_in=p.w_in[:3])        # (n_in, n) input map
        padded = pad_tenant_params(p, N_MAX)
        assert padded.w_in.shape == (N_MAX, N_MAX)
        np.testing.assert_array_equal(np.asarray(padded.w_in[:3, :6]),
                                      np.asarray(p.w_in))

    def test_plastic_writeback_only_touches_routed_synapses(self):
        server = _server(slots=2, max_ticks=10)
        bank = _layered_bank(4, 4, seed=9)
        t = server.add_tenant("p", bank, n_in=4, n_out=4, plastic=True)
        w0 = np.asarray(t.params.w).copy()
        c = np.asarray(t.params.c)
        ext = _drive(10, 4, p=0.8, seed=10)
        server.serve_continuous(
            [ServeRequest(rid=0, tenant="p", ext=ext, n_ticks=10)])
        w1 = np.asarray(server.tenants["p"].params.w)
        np.testing.assert_array_equal(w0[c == 0], w1[c == 0])


class TestPadding:
    def test_padded_neurons_never_spike(self):
        server = _server(slots=2, max_ticks=8)
        bank = _layered_bank(3, 2, seed=11)
        t = server.add_tenant("small", bank, n_in=3, n_out=2)
        ext = np.full((8, 3), 255.0, np.float32)
        st0 = SNNState.zeros((), server.n_max)
        _, raster = rollout(t.params, st0,
                            jnp.asarray(np.pad(ext, ((0, 0), (0, server.n_max - 3)))),
                            8)
        assert float(np.asarray(raster)[:, t.n:].sum()) == 0.0

    def test_oversized_tenant_rejected(self):
        server = _server()
        bank = _layered_bank(N_MAX, 2)
        with pytest.raises(ValueError, match="fabric"):
            server.add_tenant("big", bank, n_in=N_MAX, n_out=2)


class TestSlotBatchedOps:
    def test_fused_lif_step_slots_matches_per_slot_loop(self):
        from repro.kernels import ops
        from repro.core.lif import LIFState

        rng = np.random.default_rng(0)
        S, B, n = 3, 2, 12
        params = []
        for s in range(S):
            c = connectivity.sparse_random(n, 0.5, seed=s).astype(np.float32)
            params.append(SNNParams(
                w=jnp.asarray(rng.uniform(0, 2, (n, n)), jnp.float32),
                c=jnp.asarray(c),
                w_in=jnp.eye(n, dtype=jnp.float32),
                lif=LIFParams.make(n, v_th=0.5 + s, leak=0.1 * s, r_ref=s % 2)))
        slotted = jax.tree.map(lambda *xs: jnp.stack(xs), *params)
        spikes = jnp.asarray((rng.random((S, B, n)) < 0.4), jnp.float32)
        ext = jnp.asarray(rng.uniform(0, 1, (S, B, n)), jnp.float32)
        state = LIFState(
            v=jnp.asarray(rng.uniform(0, 1, (S, B, n)), jnp.float32),
            r=jnp.zeros((S, B, n), jnp.int32),
            y=spikes)

        out = ops.fused_lif_step_slots(state, spikes, slotted, ext,
                                       mode="fixed_leak", interpret=True)
        for s in range(S):
            st_s = LIFState(v=state.v[s], r=state.r[s], y=state.y[s])
            ref = ops.fused_lif_step(st_s, spikes[s], params[s], ext[s],
                                     mode="fixed_leak", interpret=True)
            np.testing.assert_allclose(np.asarray(out.v[s]), np.asarray(ref.v),
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_array_equal(np.asarray(out.y[s]),
                                          np.asarray(ref.y))

"""Mesh-partitioned tick engine: sharded == single-device, bit for bit.

The contract under test (DESIGN.md §15): setting ``EngineOptions.mesh``
partitions the fabric by destination columns and changes NOTHING else.

* **Frozen parity** -- every backend (jnp / pallas / pallas_fused /
  event), at mesh sizes 1 and 8, produces the bit-identical raster and
  final state tree of the unsharded engine; checked at n=128 and (dense
  jnp + event) n=4096, with uniform delay rings and batch dims riding
  along.

* **Learning parity** -- sharded STDP at D=8 is bitwise the unsharded
  run for jnp/event/pallas.  ``pallas_fused`` is REMAPPED to the
  row-kernel "pallas" arm when sharded (the megakernel's fused update
  order differs at the ulp level), so its D>1 contract is: bitwise vs
  unsharded *pallas*, allclose vs the unsharded megakernel.  A 1-device
  mesh skips the remap, so D=1 is bitwise for all four.

* **Chunked serving** -- K sharded chunks == one K*T-tick sharded
  rollout bitwise, from ONE compiled program (zero recompiles after the
  first trace), with the delta-combined telemetry accumulator matching
  the unsharded totals instead of inflating D-fold per chunk.

* **Fail-fast validation** -- the documented unsupported combinations
  raise instead of silently partitioning wrong.

Weights come from :func:`snn_sharding.make_sharded_dyadic_weights`: u8
levels x a power-of-two scale, the grid on which every f32 summation
order is exact -- that is what licenses ``assert_array_equal`` (not
allclose) across a partition that reorders nothing per-column but could.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import connectivity
from repro.core.engine import EngineOptions, TickCarry, TickEngine
from repro.core.lif import LIFParams
from repro.core.network_types import SNNParams, SNNState
from repro.kernels.ops import EventFanIn
from repro.launch.mesh import make_snn_mesh
from repro.obs.telemetry import TickTelemetry
from repro.parallel import snn_sharding
from repro.plasticity import PlasticityParams, PlasticityState

jax.config.update("jax_platform_name", "cpu")

BACKENDS = ("jnp", "pallas", "pallas_fused", "event")

# tests/conftest.py simulates 8 host devices on any CPU box; this only
# skips on a real-accelerator host with fewer than 8 physical devices
# (where the CPU simulation flag does not apply).
needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs an 8-way mesh: 8 physical accelerators (CPU hosts get "
           "8 simulated devices from tests/conftest.py)")


def _params(n, *, density=0.25, seed=0, v_th=1.0, leak=0.25, r_ref=1,
            max_delay=1):
    del max_delay  # state-side; kept in the signature for call-site clarity
    w = snn_sharding.make_sharded_dyadic_weights(n, seed=seed)
    c = jnp.asarray(connectivity.sparse_random(n, density, seed=seed + 1),
                    jnp.float32)
    return SNNParams(
        w=w, c=c,
        w_in=jnp.eye(n, dtype=jnp.float32) * 2.0,
        lif=LIFParams.make(n, v_th=v_th, leak=leak, r_ref=r_ref))


def _ext(n, ticks, batch_shape=(), p=0.3, seed=1):
    rng = np.random.default_rng(seed)
    shape = (ticks,) + tuple(batch_shape) + (n,)
    return jnp.asarray(rng.random(shape) < p, jnp.float32)


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Frozen-weight parity
# ---------------------------------------------------------------------------

@needs8
class TestFrozenParity:
    @pytest.mark.parametrize("n_dev", (1, 8))
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_bitwise_all_backends(self, backend, n_dev):
        n, ticks = 128, 10
        params = _params(n)
        ext = _ext(n, ticks)
        st0 = SNNState.zeros((), n)
        st_ref, ras_ref = TickEngine(EngineOptions(backend=backend)).rollout(
            params, st0, ext, ticks)
        st_sh, ras_sh = TickEngine(EngineOptions(
            backend=backend, mesh=make_snn_mesh(n_dev))).rollout(
            params, st0, ext, ticks)
        np.testing.assert_array_equal(np.asarray(ras_sh), np.asarray(ras_ref))
        _assert_tree_equal(st_sh, st_ref)

    @pytest.mark.parametrize("telemetry", (False, True))
    @pytest.mark.parametrize("backend", ("jnp", "event"))
    def test_bitwise_n4096(self, backend, telemetry):
        """Big enough that a reduction reorder would surface (the bench's
        parity point), small enough for tier-1.  The pallas arms run
        interpret-mode on CPU (minutes per tick at this n); their parity
        is pinned at n=128 above and at n=16384 on the bench's mesh."""
        n, ticks = 4096, 4
        params = _params(n, density=0.05)
        ext = _ext(n, ticks, p=0.1)
        st0 = SNNState.zeros((), n)
        ref = TickEngine(EngineOptions(
            backend=backend, telemetry=telemetry)).rollout(
            params, st0, ext, ticks)
        sh = TickEngine(EngineOptions(
            backend=backend, telemetry=telemetry,
            mesh=make_snn_mesh(8))).rollout(params, st0, ext, ticks)
        np.testing.assert_array_equal(np.asarray(sh[1]), np.asarray(ref[1]))
        if telemetry:
            np.testing.assert_array_equal(np.asarray(sh[2].spikes),
                                          np.asarray(ref[2].spikes))
            np.testing.assert_array_equal(np.asarray(sh[2].v_max),
                                          np.asarray(ref[2].v_max))

    @pytest.mark.parametrize("backend", ("jnp", "event"))
    def test_learning_bitwise_n4096(self, backend):
        n, ticks = 4096, 3
        params = _params(n, density=0.05, v_th=0.8)
        ext = _ext(n, ticks, p=0.2)
        opts = dict(backend=backend, plasticity=PlasticityParams.make(
            "stdp", a_plus=0.05, a_minus=0.05))
        (_, _, w_r), ras_r = TickEngine(EngineOptions(
            **opts)).learning_rollout(
            params, SNNState.zeros((), n),
            PlasticityState.zeros((), n), ext, ticks)
        (_, _, w_s), ras_s = TickEngine(EngineOptions(
            **opts, mesh=make_snn_mesh(8))).learning_rollout(
            params, SNNState.zeros((), n),
            PlasticityState.zeros((), n), ext, ticks)
        np.testing.assert_array_equal(np.asarray(ras_s), np.asarray(ras_r))
        np.testing.assert_array_equal(np.asarray(w_s), np.asarray(w_r))

    def test_batched_rollout(self):
        n, ticks, B = 128, 8, 3
        params = _params(n)
        ext = _ext(n, ticks, (B,))
        st0 = SNNState.zeros((B,), n)
        st_ref, ras_ref = TickEngine(EngineOptions()).rollout(
            params, st0, ext, ticks)
        st_sh, ras_sh = TickEngine(EngineOptions(
            mesh=make_snn_mesh(8))).rollout(params, st0, ext, ticks)
        assert ras_sh.shape == (ticks, B, n)
        np.testing.assert_array_equal(np.asarray(ras_sh), np.asarray(ras_ref))
        _assert_tree_equal(st_sh, st_ref)

    def test_uniform_delay_ring(self):
        """max_delay=4: each shard's ring holds only its own columns; the
        arriving plane still gathers to full width before the dot."""
        n, ticks = 128, 12
        params = _params(n)
        ext = _ext(n, ticks)
        st0 = SNNState.zeros((), n, max_delay=4)
        st_ref, ras_ref = TickEngine(EngineOptions()).rollout(
            params, st0, ext, ticks)
        st_sh, ras_sh = TickEngine(EngineOptions(
            mesh=make_snn_mesh(8))).rollout(params, st0, ext, ticks)
        np.testing.assert_array_equal(np.asarray(ras_sh), np.asarray(ras_ref))
        _assert_tree_equal(st_sh, st_ref)

    def test_event_fan_in_neighbors(self):
        """Fan-in lists shard by destination row, ids stay global."""
        n, ticks = 128, 10
        params = _params(n, density=0.1)
        nbrs = EventFanIn.from_dense(np.asarray(params.c))
        ext = _ext(n, ticks)
        st0 = SNNState.zeros((), n)
        _, ras_ref = TickEngine(EngineOptions(
            backend="event", event_dispatch="fan_in")).rollout(
            params, st0, ext, ticks, neighbors=nbrs)
        _, ras_sh = TickEngine(EngineOptions(
            backend="event", event_dispatch="fan_in",
            mesh=make_snn_mesh(8))).rollout(
            params, st0, ext, ticks, neighbors=nbrs)
        np.testing.assert_array_equal(np.asarray(ras_sh), np.asarray(ras_ref))

    def test_implicit_all_to_all(self):
        """c=None (every mux closed) on the sharded jnp arm: the local
        slab IS the local w columns, no second (n, n) buffer."""
        n, ticks = 128, 8
        p = _params(n)
        params = dataclasses.replace(p, c=None)
        ext = _ext(n, ticks)
        st0 = SNNState.zeros((), n)
        _, ras_ref = TickEngine(EngineOptions()).rollout(
            params, st0, ext, ticks)
        _, ras_sh = TickEngine(EngineOptions(
            mesh=make_snn_mesh(8))).rollout(params, st0, ext, ticks)
        np.testing.assert_array_equal(np.asarray(ras_sh), np.asarray(ras_ref))


# ---------------------------------------------------------------------------
# Telemetry parity (the delta combine)
# ---------------------------------------------------------------------------

@needs8
class TestTelemetryParity:
    def test_totals_match_unsharded(self):
        n, ticks, B = 128, 16, 2
        params = _params(n)
        ext = _ext(n, ticks, (B,))
        st0 = SNNState.zeros((B,), n)
        _, ras_ref, tel_ref = TickEngine(EngineOptions(
            telemetry=True)).rollout(params, st0, ext, ticks)
        _, ras_sh, tel_sh = TickEngine(EngineOptions(
            telemetry=True, mesh=make_snn_mesh(8))).rollout(
            params, st0, ext, ticks)
        np.testing.assert_array_equal(np.asarray(ras_sh), np.asarray(ras_ref))
        # Counting sums (0/1 events, well under 2**24) and max are exact
        # across any partition; the mean-based accumulators reduce in a
        # different order (per-shard sum then psum), so allclose.
        np.testing.assert_array_equal(np.asarray(tel_sh.spikes),
                                      np.asarray(tel_ref.spikes))
        np.testing.assert_array_equal(np.asarray(tel_sh.v_max),
                                      np.asarray(tel_ref.v_max))
        np.testing.assert_allclose(np.asarray(tel_sh.v_sum),
                                   np.asarray(tel_ref.v_sum), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(tel_sh.ref_sum),
                                   np.asarray(tel_ref.ref_sum), rtol=1e-5)

    def test_d1_mesh_is_bitwise_identity(self):
        """A 1-device mesh must skip the combine entirely: f32
        ``(out - in) + in`` is not an identity, bitwise."""
        n, ticks = 128, 12
        params = _params(n)
        ext = _ext(n, ticks)
        st0 = SNNState.zeros((), n)
        _, _, tel_ref = TickEngine(EngineOptions(
            telemetry=True)).rollout(params, st0, ext, ticks)
        _, _, tel_sh = TickEngine(EngineOptions(
            telemetry=True, mesh=make_snn_mesh(1))).rollout(
            params, st0, ext, ticks)
        _assert_tree_equal(tel_sh, tel_ref)


# ---------------------------------------------------------------------------
# Learning parity
# ---------------------------------------------------------------------------

_PP = PlasticityParams.make("stdp", a_plus=0.05, a_minus=0.05)


@needs8
class TestLearningParity:
    def _run(self, backend, mesh, n, ticks):
        params = _params(n, v_th=0.8)
        ext = _ext(n, ticks, p=0.4)
        opts = EngineOptions(backend=backend, plasticity=_PP, mesh=mesh)
        return TickEngine(opts).learning_rollout(
            params, SNNState.zeros((), n),
            PlasticityState.zeros((), n), ext, ticks)

    @pytest.mark.parametrize("backend", ("jnp", "event", "pallas"))
    def test_d8_bitwise(self, backend):
        n, ticks = 64, 10
        (st_r, _, w_r), ras_r = self._run(backend, None, n, ticks)
        (st_s, _, w_s), ras_s = self._run(backend, make_snn_mesh(8), n, ticks)
        np.testing.assert_array_equal(np.asarray(ras_s), np.asarray(ras_r))
        np.testing.assert_array_equal(np.asarray(w_s), np.asarray(w_r))
        _assert_tree_equal(st_s, st_r)
        # learning actually happened (this is not a frozen no-op pin)
        assert float(jnp.abs(w_r - _params(n, v_th=0.8).w).sum()) > 0

    def test_d8_pallas_fused_remap_contract(self):
        """Sharded megakernel learning runs the row-kernel arm: bitwise
        vs unsharded "pallas", allclose vs the unsharded megakernel."""
        n, ticks = 64, 10
        (_, _, w_row), ras_row = self._run("pallas", None, n, ticks)
        (_, _, w_fus), ras_fus = self._run("pallas_fused", None, n, ticks)
        (_, _, w_s), ras_s = self._run(
            "pallas_fused", make_snn_mesh(8), n, ticks)
        np.testing.assert_array_equal(np.asarray(ras_s), np.asarray(ras_row))
        np.testing.assert_array_equal(np.asarray(w_s), np.asarray(w_row))
        np.testing.assert_array_equal(np.asarray(ras_s), np.asarray(ras_fus))
        np.testing.assert_allclose(np.asarray(w_s), np.asarray(w_fus),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_d1_bitwise_identity(self, backend):
        """1-device mesh skips the remap: every backend, megakernel
        included, is the single-device program bit for bit."""
        n, ticks = 64, 8
        (st_r, _, w_r), ras_r = self._run(backend, None, n, ticks)
        (st_s, _, w_s), ras_s = self._run(backend, make_snn_mesh(1), n, ticks)
        np.testing.assert_array_equal(np.asarray(ras_s), np.asarray(ras_r))
        np.testing.assert_array_equal(np.asarray(w_s), np.asarray(w_r))
        _assert_tree_equal(st_s, st_r)


# ---------------------------------------------------------------------------
# Chunked serving: carry hand-off + one compiled program
# ---------------------------------------------------------------------------

@needs8
class TestShardedChunks:
    def test_chunks_match_rollout_zero_recompiles(self):
        n, T, K = 128, 6, 4
        params = _params(n)
        ext = _ext(n, K * T)
        mesh = make_snn_mesh(8)
        eng = TickEngine(EngineOptions(telemetry=True, mesh=mesh))
        _, ras_ref, tel_ref = eng.rollout(
            params, SNNState.zeros((), n), ext, K * T)
        _, _, tel_1dev = TickEngine(EngineOptions(telemetry=True)).rollout(
            params, SNNState.zeros((), n), ext, K * T)

        traces = 0

        @jax.jit
        def chunk_fn(params, carry, ext):
            nonlocal traces
            traces += 1
            return eng.chunk(params, carry, ext, T)

        # Seed the telemetry slot up front: the carry's pytree STRUCTURE
        # must be identical on every chunk or the second call retraces.
        carry = TickCarry(state=SNNState.zeros((), n),
                          telem=TickTelemetry.zeros(()))
        # ... and committed to the mesh as the chunk's own output is: an
        # array's mesh is part of its abstract type, so an uncommitted
        # seed would trace once more on the first hand-off.
        carry = snn_sharding.place(
            carry, snn_sharding.carry_specs(snn_sharding.snn_rules(mesh),
                                            carry), mesh)
        rasters = []
        for k in range(K):
            carry, ras = chunk_fn(params, carry, ext[k * T:(k + 1) * T])
            rasters.append(np.asarray(ras))
        assert traces == 1, "sharded chunk retraced after the first call"
        np.testing.assert_array_equal(
            np.concatenate(rasters, axis=0), np.asarray(ras_ref))
        # Delta combine across K boundaries: totals equal the one-shot
        # sharded scan AND the unsharded engine (no D-fold inflation).
        for tel in (tel_ref, tel_1dev):
            np.testing.assert_array_equal(np.asarray(carry.telem.spikes),
                                          np.asarray(tel.spikes))
            np.testing.assert_array_equal(np.asarray(carry.telem.v_max),
                                          np.asarray(tel.v_max))
            np.testing.assert_allclose(np.asarray(carry.telem.v_sum),
                                       np.asarray(tel.v_sum), rtol=1e-5)


# ---------------------------------------------------------------------------
# Fail-fast validation
# ---------------------------------------------------------------------------

@needs8
class TestValidation:
    def test_n_not_divisible(self):
        n = 100                                   # 100 % 8 != 0
        p = SNNParams(
            w=jnp.zeros((n, n)), c=jnp.zeros((n, n)),
            w_in=jnp.eye(n), lif=LIFParams.make(n))
        eng = TickEngine(EngineOptions(mesh=make_snn_mesh(8)))
        with pytest.raises(ValueError, match="split evenly"):
            eng.rollout(p, SNNState.zeros((), n), _ext(n, 2), 2)

    def test_tick_refuses_mesh(self):
        n = 16
        eng = TickEngine(EngineOptions(mesh=make_snn_mesh(8)))
        with pytest.raises(ValueError, match="single-device"):
            eng.tick(SNNState.zeros((), n), _params(n))

    def test_delay_matrix_refused(self):
        n = 16
        p = _params(n)
        delays = jnp.ones((n, n), jnp.int32)
        eng = TickEngine(EngineOptions(mesh=make_snn_mesh(8)))
        with pytest.raises(ValueError, match="delay"):
            eng.rollout(p, SNNState.zeros((), n, max_delay=2),
                        _ext(n, 2), 2, delays=delays)

    def test_event_ext_diag_refused_at_construction(self):
        with pytest.raises(ValueError, match="event_ext_diag"):
            EngineOptions(backend="event", event_ext_diag=True,
                          mesh=make_snn_mesh(8))

    def test_sharded_learning_needs_delay1(self):
        n = 16
        p = _params(n)
        eng = TickEngine(EngineOptions(plasticity=_PP, mesh=make_snn_mesh(8)))
        with pytest.raises(ValueError, match="max_delay == 1"):
            eng.learning_rollout(
                p, SNNState.zeros((), n, max_delay=4),
                PlasticityState.zeros((), n), _ext(n, 2), 2)

    def test_implicit_c_refuses_pallas(self):
        n = 16
        p = dataclasses.replace(_params(n), c=None)
        eng = TickEngine(EngineOptions(backend="pallas",
                                       mesh=make_snn_mesh(8)))
        with pytest.raises(ValueError):
            eng.rollout(p, SNNState.zeros((), n), _ext(n, 2), 2)

    def test_learning_implicit_c_needs_plastic_mask(self):
        n = 16
        p = dataclasses.replace(_params(n), c=None)
        eng = TickEngine(EngineOptions(plasticity=_PP, mesh=make_snn_mesh(8)))
        with pytest.raises(ValueError, match="plastic_c"):
            eng.learning_rollout(p, SNNState.zeros((), n),
                                 PlasticityState.zeros((), n), _ext(n, 2), 2)


# ---------------------------------------------------------------------------
# Host-side builders: weights and fan-in shards
# ---------------------------------------------------------------------------

class TestBuilders:
    def test_sharded_weights_mesh_independent(self):
        """Same (n, seed) -> the identical global matrix at any mesh size
        (column-block seeding): the substrate of every parity test."""
        n = 256
        w_global = np.asarray(snn_sharding.make_sharded_dyadic_weights(n))
        w_mesh = snn_sharding.make_sharded_dyadic_weights(
            n, make_snn_mesh(min(8, len(jax.devices()))))
        np.testing.assert_array_equal(np.asarray(w_mesh), w_global)

    def test_sharded_weights_on_dyadic_grid(self):
        n, levels = 128, 8
        w = np.asarray(snn_sharding.make_sharded_dyadic_weights(
            n, levels=levels))
        scale = 2.0 ** round(math.log2(2.0 / math.sqrt(n)))
        lv = w / np.float32(scale)
        np.testing.assert_array_equal(lv, np.round(lv))
        assert lv.min() >= 0 and lv.max() <= levels - 1
        assert math.log2(scale) == round(math.log2(scale))

    def test_shard_fan_in_slices_global_lists(self):
        c = connectivity.sparse_random(64, 0.2, seed=3)
        full = connectivity.padded_fan_in(c)
        shards = connectivity.shard_fan_in(c, 4)
        assert len(shards) == 4
        assert all(s.cap == full.cap for s in shards)       # uniform shapes
        assert all(s.axis == "in" for s in shards)
        np.testing.assert_array_equal(
            np.concatenate([s.idx for s in shards]), full.idx)
        np.testing.assert_array_equal(
            np.concatenate([s.mask for s in shards]), full.mask)
        assert sum(s.n_edges for s in shards) == full.n_edges

    def test_shard_fan_in_rejects_ragged(self):
        c = connectivity.sparse_random(64, 0.2, seed=3)
        with pytest.raises(ValueError, match="split evenly"):
            connectivity.shard_fan_in(c, 5)

    def test_shard_stats_and_imbalance(self):
        c = connectivity.sparse_random(64, 0.3, seed=4)
        stats = connectivity.shard_stats(c, 4)
        assert sum(s.n_edges_in for s in stats) == int(c.sum())
        assert sum(s.n_edges_out for s in stats) == int(c.sum())
        assert all(s.n_post == 16 for s in stats)
        assert connectivity.shard_imbalance(stats) >= 1.0

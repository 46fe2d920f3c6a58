"""repro.analysis: teeth + false-positive resistance.

Every rule class must FIRE on a deliberately broken fixture (a gate that
cannot fail is not a gate) and must PASS the sanctioned look-alikes
(register-boundary u8 decode, telemetry-on programs, the event knee's
``lax.cond`` arms) -- a gate that cries wolf gets disabled.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import check as check_mod
from repro.analysis import jaxpr_rules, pallas_rules, programs, static_rules
from repro.kernels.launch_spec import KernelLaunch, Operand

F32 = jnp.float32


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Rule class 1: hot-loop purity
# ---------------------------------------------------------------------------

class TestPurityTeeth:
    def test_callback_inside_scan_body_fires(self):
        def prog(x):
            def body(c, _):
                jax.debug.print("tick {c}", c=c)
                return c + 1.0, c
            return jax.lax.scan(body, x, None, length=3)

        cj = jaxpr_rules.closed_jaxpr_of(prog, jnp.zeros(()))
        assert "purity.callback_in_loop" in _rules(
            jaxpr_rules.check_hot_loop_purity(cj, "fixture"))

    def test_pure_callback_outside_loop_fires(self):
        def prog(x):
            return jax.pure_callback(
                lambda a: np.asarray(a),
                jax.ShapeDtypeStruct((), F32), x)

        cj = jaxpr_rules.closed_jaxpr_of(prog, jnp.zeros((), F32))
        assert "purity.callback" in _rules(
            jaxpr_rules.check_hot_loop_purity(cj, "fixture"))

    def test_clean_scan_passes(self):
        def prog(x):
            def body(c, _):
                return c * 0.5 + 1.0, c
            return jax.lax.scan(body, x, None, length=3)

        cj = jaxpr_rules.closed_jaxpr_of(prog, jnp.zeros(()))
        assert jaxpr_rules.check_hot_loop_purity(cj, "fixture") == []


# ---------------------------------------------------------------------------
# Rule class 2: dtype discipline
# ---------------------------------------------------------------------------

class TestDtypeTeeth:
    def test_u8_upcast_outside_sanctioned_scope_fires(self):
        def prog(b):
            return b.astype(F32) * 2.0

        cj = jaxpr_rules.closed_jaxpr_of(prog, jnp.zeros((4,), jnp.uint8))
        assert "dtype.u8_upcast" in _rules(
            jaxpr_rules.check_dtype_discipline(cj, "fixture"))

    def test_u8_upcast_under_decode_scope_passes(self):
        """The register-decode boundary is exactly where u8 widens."""
        def prog(b):
            with jax.named_scope("decode_u8"):
                w = b.astype(F32)
            return w * 2.0

        cj = jaxpr_rules.closed_jaxpr_of(prog, jnp.zeros((4,), jnp.uint8))
        assert jaxpr_rules.check_dtype_discipline(cj, "fixture") == []

    def test_f64_fires_when_x64_enabled(self):
        with jax.enable_x64(True):
            cj = jaxpr_rules.closed_jaxpr_of(
                lambda x: x + 1.0, jnp.zeros((2,), jnp.float64))
        rules = _rules(jaxpr_rules.check_dtype_discipline(cj, "fixture"))
        assert rules & {"dtype.x64_input", "dtype.x64"}


# ---------------------------------------------------------------------------
# Rule class 3: hoist contract (both directions)
# ---------------------------------------------------------------------------

_N = 6


def _unhoisted(w, c, x):
    def body(carry, _):
        wc = w * c                       # (n, n) product per tick: the bug
        return carry @ wc, None
    return jax.lax.scan(body, x, None, length=3)


def _hoisted(w, c, x):
    wc = w * c                           # once per rollout
    def body(carry, _):
        return carry @ wc, None
    return jax.lax.scan(body, x, None, length=3)


class TestHoistTeeth:
    def _args(self):
        rng = np.random.default_rng(0)
        return (jnp.asarray(rng.random((_N, _N)), F32),
                jnp.asarray(rng.random((_N, _N)), F32),
                jnp.zeros((_N,), F32))

    def test_frozen_expectation_catches_in_loop_recompute(self):
        cj = jaxpr_rules.closed_jaxpr_of(_unhoisted, *self._args())
        rules = _rules(jaxpr_rules.check_hoist(
            cj, "fixture", n=_N, expect=jaxpr_rules.HOIST_HOISTED))
        assert "hoist.wc_in_loop" in rules
        assert "hoist.wc_missing" in rules   # nothing hoisted either

    def test_learning_expectation_catches_stale_hoist(self):
        cj = jaxpr_rules.closed_jaxpr_of(_hoisted, *self._args())
        assert "hoist.wc_not_in_loop" in _rules(jaxpr_rules.check_hoist(
            cj, "fixture", n=_N, expect=jaxpr_rules.HOIST_IN_LOOP))

    def test_matching_expectations_pass(self):
        args = self._args()
        cj_h = jaxpr_rules.closed_jaxpr_of(_hoisted, *args)
        cj_u = jaxpr_rules.closed_jaxpr_of(_unhoisted, *args)
        assert jaxpr_rules.check_hoist(
            cj_h, "fixture", n=_N, expect=jaxpr_rules.HOIST_HOISTED) == []
        assert jaxpr_rules.check_hoist(
            cj_u, "fixture", n=_N, expect=jaxpr_rules.HOIST_IN_LOOP) == []


# ---------------------------------------------------------------------------
# Rule class 4: recompile hazards (statics)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _MutableStatic:
    # Hashes (by identity) yet is freely mutable -- the sneaky case a
    # plain hash() probe cannot catch.
    knobs: object


class _UnstableHash:
    def __eq__(self, other):
        return isinstance(other, _UnstableHash)

    def __hash__(self):                  # id-derived: new instance, new hash
        return id(self)


@dataclasses.dataclass(frozen=True)
class _HashablePlanFixture:
    """A DispatchPlan look-alike that (wrongly) hashes."""
    strategy: str = "fan_in"

    def engine_kwargs(self):
        return {"backend": "event", "event_dispatch": self.strategy}


class TestStaticTeeth:
    def test_unhashable_static_fires(self):
        assert "static.unhashable" in _rules(
            static_rules.check_hashable_static(
                {"k": 1}, "fixture", name="opts"))

    def test_mutable_field_in_frozen_static_fires(self):
        class _Knobs:   # hashable by identity, mutable in every other way
            pass

        assert "static.mutable_field" in _rules(
            static_rules.check_hashable_static(
                _MutableStatic(knobs=_Knobs()), "fixture", name="opts"))

    def test_plainly_unhashable_static_fires(self):
        assert "static.unhashable" in _rules(
            static_rules.check_hashable_static(
                _MutableStatic(knobs=[1, 2]), "fixture", name="opts"))

    def test_unstable_hash_across_instances_fires(self):
        assert "static.unstable_hash" in _rules(
            static_rules.check_hash_stability(
                _UnstableHash, "fixture", name="opts"))

    def test_unknown_static_argname_fires(self):
        def fn(a, *, mode="x"):
            return a

        assert "static.unknown_argname" in _rules(
            static_rules.check_static_argnames(
                fn, ("mode", "nonexistent"), "fixture", name="fn"))

    def test_hashable_dispatch_plan_fires(self):
        """The plan carries arrays; a hashable plan would silently become
        a jit cache key and retrace per instance."""
        assert "static.plan_hashable" in _rules(
            static_rules.check_dispatch_plan(
                _HashablePlanFixture(), "fixture"))

    def test_engine_options_pass(self):
        from repro.core.engine import EngineOptions

        make = lambda: EngineOptions(backend="event", event_k_active=4)
        assert static_rules.check_hashable_static(
            make(), "fixture", name="EngineOptions") == []
        assert static_rules.check_hash_stability(
            make, "fixture", name="EngineOptions") == []

    def test_real_dispatch_plan_passes(self):
        assert static_rules.check_dispatch_plan(
            programs.demo_dispatch_plan(), "fixture") == []


# ---------------------------------------------------------------------------
# Rule class 5: Pallas kernel lint
# ---------------------------------------------------------------------------

def _tiny_launch(**overrides):
    base = dict(
        name="fixture",
        grid=(2,),
        inputs=(Operand("x", (256, 128), F32, (128, 128),
                        lambda i: (i, 0)),),
        outputs=(Operand("y", (256, 128), F32, (128, 128),
                         lambda i: (i, 0)),),
    )
    base.update(overrides)
    return KernelLaunch(**base)


class TestPallasTeeth:
    def test_oob_index_map_fires(self):
        # Block row i+1 walks one block past the operand's 256 rows.
        launch = _tiny_launch(inputs=(
            Operand("x", (256, 128), F32, (128, 128),
                    lambda i: (i + 1, 0)),))
        assert "pallas.oob" in _rules(
            pallas_rules.check_index_maps(launch, "fixture"))

    def test_sentinel_row_prefetch_is_in_bounds(self):
        """A prefetch-steered row gather at its worst case -- every
        index the operand's last row -- must lint clean."""
        launch = _tiny_launch(
            inputs=(Operand("w", (9, 128), F32, (1, 128),
                            lambda i, s: (s[i], 0)),),
            outputs=(Operand("y", (256, 128), F32, (128, 128),
                             lambda i, s: (i, 0)),),
            prefetch_example=(np.full((2,), 8, np.int32),),
            num_scalar_prefetch=1)
        assert pallas_rules.check_index_maps(launch, "fixture") == []

    def test_vmem_budget_fires(self):
        launch = _tiny_launch(inputs=(
            Operand("x", (8192, 8192), F32, (4096, 4096),
                    lambda i: (0, 0)),))
        assert "pallas.vmem" in _rules(
            pallas_rules.check_vmem(launch, "fixture"))

    def test_alias_shape_mismatch_fires(self):
        launch = _tiny_launch(
            inputs=(Operand("x", (256, 128), F32, (128, 128),
                            lambda i: (i, 0)),
                    Operand("z", (64, 64), F32, (64, 64),
                            lambda i: (0, 0))),
            input_output_aliases={1: 0})
        assert "pallas.alias" in _rules(
            pallas_rules.check_aliasing(launch, "fixture"))

    @pytest.mark.parametrize("shape,block", [
        ((8, 256), (1, 128)),      # one row of an 8-row batch operand
        ((64, 200), (8, 100)),     # lane block not a multiple of 128
    ])
    def test_misaligned_block_fires(self, shape, block):
        launch = _tiny_launch(inputs=(
            Operand("x", shape, F32, block, lambda i: (0, 0)),))
        assert "pallas.align" in _rules(
            pallas_rules.check_alignment(launch, "fixture"))

    @pytest.mark.parametrize("shape,block", [
        ((16, 256), (8, 128)),     # whole (8, 128) tiles
        ((1, 256), (1, 128)),      # a (1, N) parameter row: full extent
        ((3, 74), (3, 74)),        # the whole operand
    ])
    def test_aligned_block_passes(self, shape, block):
        launch = _tiny_launch(inputs=(
            Operand("x", shape, F32, block, lambda i: (0, 0)),))
        assert pallas_rules.check_alignment(launch, "fixture") == []

    @pytest.mark.parametrize("ops,rule", [
        ([("start", 0, 0), ("use", 0, 0)], "pallas.dma.use_before_wait"),
        ([("wait", 0, 0)], "pallas.dma.wait_without_start"),
        ([("start", 0, 0), ("start", 0, 1)], "pallas.dma.start_busy"),
        ([("start", 0, 0)], "pallas.dma.dangling"),
    ])
    def test_dma_protocol_violations_fire(self, ops, rule):
        bad, _ = pallas_rules.simulate_dma_schedule(ops)
        assert rule in {r for r, _ in bad}

    def test_dropped_spike_fires(self):
        def schedule(nb):   # waits on every copy but never uses spike 1
            ops = []
            for k in range(nb):
                ops += [("start", k % 2, k), ("wait", k % 2, k)]
                if k != 1:
                    ops.append(("use", k % 2, k))
            return ops

        launch = _tiny_launch(dma_schedule=schedule)
        assert "pallas.dma.missing_spike" in _rules(
            pallas_rules.check_dma_schedule(launch, "fixture"))

    def test_quiet_row_dma_fires(self):
        def schedule(nb):   # unconditional warmup: DMA on silent rows
            ops = [("start", 0, 0), ("wait", 0, 0)]
            for k in range(nb):
                ops.append(("use", 0, k) if k == 0
                           else ("start", k % 2, k))
                if k > 0:
                    ops += [("wait", k % 2, k), ("use", k % 2, k)]
            return ops

        launch = _tiny_launch(dma_schedule=schedule)
        assert "pallas.dma.quiet_row" in _rules(
            pallas_rules.check_dma_schedule(launch, "fixture"))

    def test_shipped_db_schedule_passes(self):
        from repro.kernels.event_dispatch import db_dma_schedule

        launch = _tiny_launch(dma_schedule=db_dma_schedule)
        assert pallas_rules.check_dma_schedule(launch, "fixture") == []


# ---------------------------------------------------------------------------
# Rule class 6: sharding (no fabric-sized collective in the hot loop)
# ---------------------------------------------------------------------------

class TestShardingTeeth:
    """The sharded engine's per-tick collective moves spikes ((B, n)); a
    program that all-gathers the WEIGHT operand per tick must fire."""

    N = 8

    def _mesh(self):
        from repro.launch.mesh import make_snn_mesh

        return make_snn_mesh(1)

    def test_w_gather_in_loop_fires(self):
        from repro.analysis import sharding_rules
        from repro.parallel.snn_sharding import shard_map_fn

        n, mesh = self.N, self._mesh()
        from jax.sharding import PartitionSpec as P

        def body(w_local, s):
            def tick(c, _):
                # THE regression: replicate the whole weight matrix
                # every iteration instead of exchanging spikes.
                w_full = jax.lax.all_gather(
                    w_local, "model", axis=1, tiled=True)
                return c + s @ w_full, None
            out, _ = jax.lax.scan(tick, jnp.zeros((n,), F32), None, length=3)
            return out

        fn = shard_map_fn(body, mesh, (P(None, "model"), P()), P())
        cj = jaxpr_rules.closed_jaxpr_of(
            fn, jnp.zeros((n, n), F32), jnp.zeros((n,), F32))
        assert "sharding.w_gather_in_loop" in _rules(
            sharding_rules.check_no_w_gather_in_loop(cj, "fixture", n=n))

    def test_spike_gather_in_loop_passes(self):
        from repro.analysis import sharding_rules
        from repro.parallel.snn_sharding import shard_map_fn

        n, mesh = self.N, self._mesh()
        from jax.sharding import PartitionSpec as P

        def body(w_local, s_local):
            def tick(c, _):
                # The sanctioned exchange: (n,) spikes, n-fold smaller.
                s_full = jax.lax.all_gather(
                    s_local, "model", axis=0, tiled=True)
                return c + s_full @ w_local, None
            out, _ = jax.lax.scan(
                tick, jnp.zeros((w_local.shape[1],), F32), None, length=3)
            return out

        fn = shard_map_fn(body, mesh, (P(None, "model"), P("model")),
                          P("model"))
        cj = jaxpr_rules.closed_jaxpr_of(
            fn, jnp.zeros((n, n), F32), jnp.zeros((n,), F32))
        assert sharding_rules.check_no_w_gather_in_loop(
            cj, "fixture", n=n) == []

    def test_hoisted_w_gather_outside_loop_passes(self):
        from repro.analysis import sharding_rules
        from repro.parallel.snn_sharding import shard_map_fn

        n, mesh = self.N, self._mesh()
        from jax.sharding import PartitionSpec as P

        def body(w_local, s):
            # Once per rollout (e.g. a placement/premask step), not per
            # tick: outside every loop body, so it passes.
            w_full = jax.lax.all_gather(w_local, "model", axis=1, tiled=True)

            def tick(c, _):
                return c + s @ w_full, None
            out, _ = jax.lax.scan(tick, jnp.zeros((n,), F32), None, length=3)
            return out

        fn = shard_map_fn(body, mesh, (P(None, "model"), P()), P())
        cj = jaxpr_rules.closed_jaxpr_of(
            fn, jnp.zeros((n, n), F32), jnp.zeros((n,), F32))
        assert sharding_rules.check_no_w_gather_in_loop(
            cj, "fixture", n=n) == []

    def test_mesh_carrying_options_pass_static_rules(self):
        from repro.core.engine import EngineOptions

        make = lambda: EngineOptions(mesh=self._mesh())
        assert static_rules.check_hashable_static(make(), "fixture") == []
        assert static_rules.check_hash_stability(make, "fixture") == []


# ---------------------------------------------------------------------------
# False-positive resistance on the shipped registry + CLI plumbing
# ---------------------------------------------------------------------------

class TestShippedPrograms:
    def _check(self, name):
        report = check_mod.run([name], include_static=False)
        assert report.ok(), report.table()

    def test_event_knee_cond_arms_pass_clean(self):
        # tick/event/frozen/* carries event_knee: both lax.cond arms (the
        # dense fallback included) are part of the analyzed program.
        self._check("tick/event/frozen/notelem")

    def test_telemetry_on_program_passes(self):
        self._check("tick/jnp/frozen/telem")

    def test_learning_program_passes(self):
        self._check("tick/jnp/learning/notelem")

    def test_sharded_programs_pass(self):
        self._check("tick/sharded/frozen/notelem")
        self._check("tick/sharded/learning/telem")

    def test_kernel_lints_pass(self):
        for reg, _ in programs.kernel_launches():
            self._check(f"kernel/{reg}")

    def test_static_surface_passes(self):
        from repro.analysis.findings import Report

        report = Report()
        check_mod.check_static_surface(report)
        assert report.ok(), report.table()

    def test_cli_list_and_single_program(self, capsys):
        assert check_mod.main(["--list"]) == 0
        listed = capsys.readouterr().out.splitlines()
        assert "tick/jnp/frozen/notelem" in listed
        assert check_mod.main(["--program", "kernel/lif_step"]) == 0
        out = capsys.readouterr().out
        assert "PASS" in out

    def test_cli_rejects_unknown_program(self):
        with pytest.raises(SystemExit):
            check_mod.main(["--program", "no/such/program"])

"""The trip-count-aware HLO cost parser vs known ground truth.

Also documents the motivating fact: XLA's cost_analysis counts a while
body ONCE, so scanned programs need the corrected parse.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_cost

jax.config.update("jax_platform_name", "cpu")

D = 128


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


class TestKnownCounts:
    def test_single_matmul(self):
        x = jax.ShapeDtypeStruct((D, D), jnp.float32)
        c = _compile(lambda a, b: a @ b, x, x)
        s = hlo_cost.analyze(c.as_text())
        assert s.flops == pytest.approx(2 * D**3, rel=1e-6)

    def test_scan_multiplies_by_trip_count(self):
        n = 8
        x = jax.ShapeDtypeStruct((D, D), jnp.float32)
        ws = jax.ShapeDtypeStruct((n, D, D), jnp.float32)

        def scanned(x, ws):
            return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]

        c = _compile(scanned, x, ws)
        raw = c.cost_analysis().get("flops")
        s = hlo_cost.analyze(c.as_text())
        assert s.flops == pytest.approx(n * 2 * D**3, rel=1e-6)
        # the motivating discrepancy: raw counts the body once
        assert raw < s.flops / 2

    def test_nested_scan(self):
        x = jax.ShapeDtypeStruct((D, D), jnp.float32)
        ws = jax.ShapeDtypeStruct((8, D, D), jnp.float32)

        def nested(x, ws):
            def outer(c, w3):
                return jax.lax.scan(lambda cc, w: (cc @ w, None), c, w3)[0], None
            return jax.lax.scan(outer, x, ws.reshape(2, 4, D, D))[0]

        c = _compile(nested, x, ws)
        s = hlo_cost.analyze(c.as_text())
        assert s.flops == pytest.approx(8 * 2 * D**3, rel=1e-6)

    def test_matches_unrolled(self):
        x = jax.ShapeDtypeStruct((D, D), jnp.float32)
        ws = jax.ShapeDtypeStruct((4, D, D), jnp.float32)

        def unrolled(x, ws):
            for i in range(4):
                x = x @ ws[i]
            return x

        def scanned(x, ws):
            return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]

        su = hlo_cost.analyze(_compile(unrolled, x, ws).as_text())
        ss = hlo_cost.analyze(_compile(scanned, x, ws).as_text())
        assert su.flops == pytest.approx(ss.flops, rel=1e-6)

    def test_grad_flops_about_3x(self):
        """Backward of y = sum(x @ w) costs ~2 extra matmuls."""
        x = jax.ShapeDtypeStruct((D, D), jnp.float32)

        def fwd(a, b):
            return jnp.sum(a @ b)

        sf = hlo_cost.analyze(_compile(fwd, x, x).as_text())
        sg = hlo_cost.analyze(_compile(jax.grad(fwd, argnums=(0, 1)), x, x).as_text())
        assert 1.9 <= sg.flops / sf.flops <= 3.1


class TestCollectives:
    def test_allgather_bytes_counted_with_trips(self):
        """The sharded tick engine's one collective per tick, scanned:
        the corrected parse must charge the gather once PER TRIP (raw
        cost_analysis counts the while body once -- the same bug the
        flops tests pin, on the bytes axis the roofline sums)."""
        if len(jax.devices()) < 8:
            pytest.skip("needs an 8-way mesh: 8 physical accelerators "
                        "(CPU hosts get 8 simulated devices from "
                        "tests/conftest.py)")
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_snn_mesh
        from repro.parallel.snn_sharding import shard_map_fn

        mesh = make_snn_mesh(8)
        width, n_trips = 1024, 16
        x = jax.ShapeDtypeStruct((width,), jnp.float32)

        def once(v):
            return v + jnp.sum(jax.lax.all_gather(v, "model", tiled=True))

        def looped(v):
            # The gather reads the CARRY, so it is loop-variant -- XLA
            # cannot hoist it out of the while body the way the tick
            # engine's hoisted W*C leaves the loop.
            def body(c, _):
                return c + jnp.sum(
                    jax.lax.all_gather(c, "model", tiled=True)), None
            return jax.lax.scan(body, v, None, length=n_trips)[0]

        specs = ((P("model"),), P("model"))
        s1 = hlo_cost.analyze(
            _compile(shard_map_fn(once, mesh, *specs), x).as_text())
        sn = hlo_cost.analyze(
            _compile(shard_map_fn(looped, mesh, *specs), x).as_text())
        per_gather = s1.collective_bytes.get("all-gather", 0.0)
        # operand accounting: each gather reads one per-shard f32 slice
        assert per_gather >= (width // 8) * 4
        assert sn.collective_bytes.get("all-gather", 0.0) == pytest.approx(
            n_trips * per_gather, rel=1e-6)
        assert sn.total_collective_bytes == pytest.approx(
            n_trips * s1.total_collective_bytes, rel=1e-6)

    def test_dot_bytes_positive(self):
        x = jax.ShapeDtypeStruct((D, D), jnp.float32)
        c = _compile(lambda a, b: a @ b, x, x)
        s = hlo_cost.analyze(c.as_text())
        assert s.dot_bytes == pytest.approx(3 * D * D * 4, rel=1e-6)


class TestMosaicKernels:
    def test_named_by_innermost_jit_scope(self):
        """A vmapped kernel's custom call is named after a wrapper
        (``closed_call``); the jit scope in its op_name names the kernel."""
        hlo = "\n".join([
            '  %closed_call.11 = (f32[8,128]{1,0}) custom-call(%p.1), '
            'custom_call_target="tpu_custom_call", metadata={op_name='
            '"jit(run)/vmap()/while/body/jit(fused_tick)/while/body/'
            'closed_call/pallas_call" stack_frame_id=41}',
            '  ROOT %event_lif_dispatch_db.1 = (f32[8,128]{1,0}) '
            'custom-call(%p.2), custom_call_target="tpu_custom_call"',
            '  %cc.3 = f32[8]{0} custom-call(%p.3), '
            'custom_call_target="Sharding", metadata={op_name="jit(f)"}',
        ])
        assert hlo_cost.mosaic_kernels(hlo) == {"fused_tick": 1,
                                                "event_lif_dispatch_db": 1}

    def test_cpu_program_has_none(self):
        text = jax.jit(lambda x: x * 2).lower(jnp.ones(4)).compile().as_text()
        assert hlo_cost.mosaic_kernels(text) == {}


class TestDynamicSlices:
    def test_whole_matrices_by_trailing_dims(self):
        """Slices are matched by their result's trailing dimensions and
        carry their op_name; other instructions are not slices."""
        hlo = "\n".join([
            '  %ds.1 = f32[1,64,64]{2,1,0:T(8,128)} dynamic-slice(%p.1, %i, '
            '%z, %z), dynamic_slice_sizes={1,64,64}, metadata={op_name='
            '"jit(f)/while/body/jit(fused_tick)/while/body/dynamic_slice"}',
            '  %ds.2 = s32[64,64]{1,0} dynamic-slice(%p.2, %z, %z), '
            'dynamic_slice_sizes={64,64}',
            '  %ds.3 = f32[1,64,32]{2,1,0} dynamic-slice(%p.1, %i, %z, %z), '
            'dynamic_slice_sizes={1,64,32}',
            '  %dus.4 = f32[4,64,64]{2,1,0} dynamic-update-slice(%p.1, '
            '%ds.1, %i, %z, %z)',
        ])
        assert hlo_cost.dynamic_slices(hlo, (64, 64)) == [
            (64 * 64 * 4,
             "jit(f)/while/body/jit(fused_tick)/while/body/dynamic_slice"),
            (64 * 64 * 4, "")]
        assert hlo_cost.dynamic_slices(hlo, (64, 32)) == [(64 * 32 * 4, "")]

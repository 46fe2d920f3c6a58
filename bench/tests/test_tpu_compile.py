"""The sharded stream's request step, compiled at its full size for a
described four-chip v5e host (nothing runs): what the chip's compiler
would refuse fails here, and each chip's share of the fabric is read
from the compiled program."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import harness


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_stream_step_compiles_for_four_chips(topo):
    from repro.core.engine import EngineOptions, TickCarry, TickEngine
    from repro.core.lif import LIFParams
    from repro.core.network_types import SNNParams, SNNState
    from repro.obs.telemetry import TickTelemetry
    from repro.parallel import snn_sharding

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = harness.Cell("fabric64k-stream").config
    n, n_in, n_out, T = cfg["n"], cfg["n_in"], cfg["n_out"], 8
    mesh = Mesh(topo.devices[:4], ("model",))
    rules = snn_sharding.snn_rules(mesh)
    f32 = jnp.float32
    vec = lambda dt=f32: jnp.zeros((n,), dt)
    params = SNNParams(
        w=jax.ShapeDtypeStruct((n, n), f32), c=None,
        w_in=jax.ShapeDtypeStruct((n_in, n), f32),
        lif=LIFParams(v_th=vec(), leak=vec(), r_ref=vec(jnp.int32),
                      gain=vec(), i_bias=vec(), v_reset=vec()))
    carry = TickCarry(state=jax.eval_shape(lambda: SNNState.zeros((), n)),
                      telem=jax.eval_shape(lambda: TickTelemetry.zeros(())))
    shard = lambda tree, specs: jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree, specs, is_leaf=lambda x: isinstance(x, P))
    p_specs = snn_sharding.params_specs(rules, params)
    params = shard(jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype), params), p_specs)
    carry = shard(carry, snn_sharding.carry_specs(rules, carry))
    ext = jax.ShapeDtypeStruct((T, n_in), f32,
                               sharding=NamedSharding(mesh, P()))
    engine = TickEngine(EngineOptions(mode=cfg["mode"], backend=cfg["backend"],
                                      telemetry=True, mesh=mesh))

    def step(params, carry, ext):
        carry, raster = engine.chunk(params, carry, ext, T)
        return carry, raster[:, n - n_out:].sum(axis=0)

    compiled = jax.jit(step).lower(params, carry, ext).compile()
    text = compiled.as_text()
    assert "all-gather" in text
    mem = compiled.memory_analysis()
    # each chip holds a quarter of W: 65,536 x 16,384 f32 = 4 GiB
    assert mem.argument_size_in_bytes >= n * n * 4 // 4
    assert mem.argument_size_in_bytes < n * n * 4 // 2

"""The main path's Pallas kernels and programs compile for a TPU v5e.

Interpret mode runs a kernel body on the CPU but enforces none of the
TPU's rules (tile alignment, VMEM, DMA slicing); the chip's compiler
does.  These tests call that compiler for a *described* ``v5e:2x2``
topology -- no chip attached -- at the widths the serving path uses,
and check that each program carries its Mosaic kernel
(``tpu_custom_call``) rather than an interpreted body.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU compiler library,
and every test worker imports every test file.  Nothing here runs:
arguments are ``ShapeDtypeStruct`` s placed on the described devices.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.core.engine import EngineOptions, TickCarry, TickEngine
from repro.core.lif import LIFParams
from repro.core.network_types import SNNParams, SNNState
from repro.kernels import ops
from repro.kernels.event_dispatch import event_lif_dispatch_db
from repro.launch import hlo_cost
from repro.launch.hlo_cost import mosaic_kernels
from repro.obs.telemetry import TickTelemetry
from repro.parallel import snn_sharding
from repro.plasticity import PlasticityState

KERNEL = "tpu_custom_call"
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    """A described 2x2 v5e host, with the persistent compilation cache
    off: an entry compiled for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(tree, sharding, lead=()):
    """Shapes of ``tree`` (arrays or shape structs) on ``sharding``,
    with optional leading axes."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(lead + tuple(a.shape), a.dtype,
                                       sharding=sharding), tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _fabric(n, b, *, max_delay=1):
    """Shapes of an n-neuron fabric and a batch-b state."""
    params = jax.eval_shape(lambda: SNNParams(
        w=jnp.zeros((n, n)), c=jnp.zeros((n, n)), w_in=jnp.zeros((n, n)),
        lif=LIFParams.make(n)))
    state = jax.eval_shape(lambda: SNNState.zeros((b,), n, max_delay))
    return params, state


class TestKernels:
    N = 4096
    B = 8

    @pytest.mark.parametrize("form,depth", [("frozen", 1), ("masked", 1),
                                            ("frozen", 4)])
    def test_tick_fused(self, one_chip, form, depth):
        """Depth 4: a uniform delay ring; the bridge hands the kernel the
        arriving slot alone, a (B, 1, n) operand."""
        params, state = _fabric(self.N, self.B, max_delay=depth)
        wc = params.w if form == "frozen" else None

        def tick(st, p, wc):
            return ops.fused_tick(st, p, None, wc=wc, interpret=False)

        c = _compile(tick, _spec(state, one_chip), _spec(params, one_chip),
                     None if wc is None else _spec(wc, one_chip))
        text = c.as_text()
        assert mosaic_kernels(text) == {"fused_tick": 1}
        call = next(line for line in text.splitlines()
                    if f'custom_call_target="{KERNEL}"' in line)
        # The spike operand is one row per batch element, whatever D is.
        assert f"f32[{self.B},1,{self.N}]" in call

    def test_tick_fused_delays(self, one_chip):
        n, depth = 1024, 4
        params, state = _fabric(n, self.B, max_delay=depth)
        delays = jax.ShapeDtypeStruct((n, n), I32, sharding=one_chip)

        def tick(st, p, d):
            return ops.fused_tick(st, p, None, wc=p.w, delays=d,
                                  interpret=False)

        c = _compile(tick, _spec(state, one_chip), _spec(params, one_chip),
                     delays)
        assert mosaic_kernels(c.as_text()) == {"fused_tick": 1}

    def test_lif_step(self, one_chip):
        n, b = self.N, self.B
        mat = jax.ShapeDtypeStruct((n, n), F32, sharding=one_chip)
        row = lambda dt=F32: jax.ShapeDtypeStruct((b, n), dt, sharding=one_chip)
        vec = lambda dt=F32: jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
        c = _compile(
            functools.partial(ops.fused_lif_step_arrays, interpret=False),
            row(), mat, mat, row(), row(I32), row(),
            vec(), vec(), vec(I32), vec(), vec(), vec())
        assert mosaic_kernels(c.as_text()) == {"fused_lif_step": 1}

    @pytest.mark.parametrize("rule", ["stdp", "rstdp"])
    def test_stdp_update(self, one_chip, rule):
        n, b = self.N, self.B
        mat = jax.ShapeDtypeStruct((n, n), F32, sharding=one_chip)
        row = jax.ShapeDtypeStruct((b, n), F32, sharding=one_chip)
        reward = jax.ShapeDtypeStruct((), F32, sharding=one_chip)
        step = functools.partial(
            ops.fused_stdp_step, rule=rule, a_plus=0.5, a_minus=0.25,
            decay_pre=0.5, decay_post=0.5, decay_elig=0.5, lr_reward=0.1,
            w_min=0.0, w_max=255.0, interpret=False)
        c = _compile(step, row, row, row, row, mat, mat, mat, reward)
        assert mosaic_kernels(c.as_text()) == {"fused_stdp_step": 1}

    @pytest.mark.parametrize("b", [1, 8])
    def test_event_dispatch_db(self, one_chip, b):
        n, k = self.N, self.N // 8
        arr = lambda shape, dt=F32: jax.ShapeDtypeStruct(
            shape, dt, sharding=one_chip)

        def tick(idx, w, v, r, drive, counts, p, r_ref):
            return event_lif_dispatch_db(idx, w, v, r, drive, p, p, r_ref,
                                         p, p, p, counts=counts)

        c = _compile(tick, arr((b, k), I32), arr((n, n)), arr((b, n)),
                     arr((b, n), I32), arr((b, n)), arr((b,), I32),
                     arr((n,)), arr((n,), I32))
        assert mosaic_kernels(c.as_text()) == {"event_lif_dispatch_db": 1}

    def test_event_rollout_topk(self, one_chip, monkeypatch):
        """``rollout(backend="event", dispatch="topk")`` as the engine
        builds it: the event kernel on ticks within ``k_active``, the
        dense LIF kernel on the overflow arm of the same ``lax.cond``."""
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)
        params, state = _fabric(self.N, self.B)
        ext = jax.ShapeDtypeStruct((4, self.B, self.N), F32,
                                   sharding=one_chip)
        eng = TickEngine(EngineOptions(backend="event",
                                       event_dispatch="topk"))
        c = _compile(lambda p, s, e: eng.rollout(p, s, e, 4),
                     _spec(params, one_chip), _spec(state, one_chip), ext)
        assert mosaic_kernels(c.as_text()) == {"event_lif_dispatch_db": 1,
                                               "fused_lif_step": 1}


@pytest.fixture(scope="module")
def fused_chunk(one_chip):
    """The continuous-serving chunk program of a ``pallas_fused`` server
    over 8 slots, exactly as :class:`SNNServer` builds it, compiled."""
    from repro.launch.serve import SNNServer

    n, slots, chunk = 4096, 8, 8
    server = SNNServer(n_max=n, slots=slots, max_ticks=32,
                       backend="pallas_fused", chunk_ticks=chunk)
    params, _ = _fabric(n, 1)
    carry = jax.eval_shape(lambda: TickCarry(
        state=SNNState.zeros((), n), plast=PlasticityState.zeros((), n),
        w=jnp.zeros((n, n)), telem=TickTelemetry.zeros(())))
    lead = (slots,)
    arr = lambda shape, dt=F32: jax.ShapeDtypeStruct(
        lead + shape, dt, sharding=one_chip)
    fn = functools.partial(server._chunk_fn, "pallas_fused", chunk)
    # The kernel bridges pick interpret mode off-TPU; this process only
    # describes the chip, so tell them the program is for one.
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)
        compiled = _compile(
            fn, _spec(params, one_chip, lead), _spec(carry, one_chip, lead),
            arr((chunk, n)), arr((n, n)), arr((chunk,)), arr((), I32),
            arr((), I32), arr((), jnp.bool_), arr((n,)))
    return n, compiled


def test_server_chunk_vmapped_over_slots(fused_chunk):
    """The whole-tick kernel survives the slot vmap, and the STDP kernel
    has one call site, in the per-slot learning ``cond`` the slot loop
    runs."""
    _, c = fused_chunk
    # The whole-tick kernel and the STDP pass: both Mosaic kernels.
    assert mosaic_kernels(c.as_text()) == {"fused_tick": 1,
                                           "fused_stdp_step": 1}
    assert c.memory_analysis().argument_size_in_bytes < 16 * 2 ** 30


def test_server_chunk_reads_slot_stacks_in_place(fused_chunk):
    """The whole-tick kernel reads each slot's ``W`` and ``C`` tile by
    tile from the stacked ``(S, n, n)`` operands, on its slot grid: no
    whole ``(n, n)`` matrix is sliced out of a stack for it. Only the
    learning ``cond`` slices one out: the learning slot's, for the STDP
    pass."""
    n, c = fused_chunk
    slices = hlo_cost.dynamic_slices(c.as_text(), (n, n))
    assert not [s for s in slices if "jit(fused_tick)" in s[1]]
    assert slices and all("/cond/" in op_name for _, op_name in slices)


def test_sharded_tick_on_four_chips(topo):
    """n=16384 frozen fabric, destination-sharded over the host's 4
    chips: each holds a quarter of the 1 GiB f32 W, and the tick loop
    moves spikes with exactly one all-gather."""
    n, ticks, d = 16384, 8, 4
    mesh = jax.sharding.Mesh(
        np.asarray(topo.devices[:d]), ("model",),
        axis_types=(jax.sharding.AxisType.Auto,))
    eng = TickEngine(EngineOptions(backend="jnp", mesh=mesh))
    rules = snn_sharding.snn_rules(mesh)
    params = jax.eval_shape(lambda: SNNParams(
        w=jnp.zeros((n, n)), c=None, w_in=jnp.zeros((256, n)),
        lif=LIFParams.make(n)))
    state = jax.eval_shape(lambda: SNNState.zeros((), n))
    p_specs = snn_sharding.params_specs(rules, params)
    s_specs = snn_sharding.state_specs(rules, state)
    place = lambda tree, specs: jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree, specs)
    ext = jax.ShapeDtypeStruct((ticks, 256), F32,
                               sharding=NamedSharding(mesh, jax.P()))
    c = _compile(lambda p, s, e: eng.rollout(p, s, e, ticks),
                 place(params, p_specs), place(state, s_specs), ext)
    w_bytes = n * n * 4
    per_device = c.memory_analysis().argument_size_in_bytes
    assert w_bytes // d <= per_device < w_bytes // d + 2 ** 24
    assert c.as_text().count("all-gather(") == 1


def test_microcircuit_chunk_at_full_size(one_chip):
    """The Potjans-Diesmann microcircuit's 100-tick request program at
    its published size (77,169 psc_exp neurons, a resident fan-out of
    about 3.3e8 entries, a 64-deep ring, the Poisson drive) compiles for
    one v5e chip and fits it, the ring delivery a scatter-add (no
    kernel)."""
    from repro.configs.pd_microcircuit import FANOUT_WINDOW, Microcircuit
    from repro.core.connectivity import FanOut

    mc = Microcircuit()
    n, rows, window = mc.n, 660_000, FANOUT_WINDOW
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fo = FanOut(targets=arr((rows, window), I32),
                weights=arr((rows, window), F32),
                delays=arr((rows, window), jnp.uint8), offset=arr((n,), I32),
                count=arr((n,), I32), pop_starts=arr((9,), I32))
    key = jnp.zeros((2,), jnp.uint32)
    params = jax.eval_shape(lambda: mc.params(key))
    carry = jax.eval_shape(lambda: TickCarry(
        state=mc.initial_state(key), telem=TickTelemetry.zeros((), n_pops=8)))
    engine = TickEngine(mc.engine_options())
    c = _compile(lambda p, s, f: engine.chunk(p, s, None, 100, neighbors=f),
                 _spec(params, one_chip), _spec(carry, one_chip), fo)
    mem = c.memory_analysis()
    assert 2.9e9 < mem.argument_size_in_bytes < 3.2e9
    assert mem.temp_size_in_bytes < 2 ** 30
    text = c.as_text()
    assert "scatter(" in text and KERNEL not in text

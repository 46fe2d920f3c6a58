"""The program registry: every compiled program we ship, as an
analyzable spec.

A :class:`Program` bundles what the rules need: a traceable ``fn`` +
example args (for the jaxpr/HLO rules), the W*C hoist expectation, the
:class:`~repro.core.engine.EngineOptions` factory (for the
recompile-hazard rules), and the Pallas launch descriptors the program's
kernels would use at a representative operating point (for the kernel
lint).  :func:`iter_programs` yields the full shipped matrix:

* tick programs -- 4 backends x frozen/learning x telemetry on/off
  (16 programs), the event knee variant riding on the frozen event
  programs so the adaptive ``lax.cond`` arms are linted as shipped,
  and the event backend's ``fan_out`` chunk (``psc_exp`` neurons,
  per-synapse delays into the ring, on-device Poisson drive) with
  telemetry on and off;
* serve programs -- the continuous chunked step (dense + event) and
  the slot-refill register-download program;
* kernel launches -- each Pallas kernel's descriptor at a
  representative padded shape (what :mod:`repro.kernels.ops` would
  launch on TPU; CPU runs interpret mode, but the descriptor is
  identical).

Everything is built lazily and small (n <= 24, a handful of ticks):
the analyzer traces and lowers, it never executes a tick.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Iterator, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.analysis import jaxpr_rules
from repro.kernels.launch_spec import KernelLaunch

# Small but non-degenerate: n is the fabric width the hoist rule greps
# for, chosen to collide with nothing else (ticks, delay depth, batch).
_N = 24
_TICKS = 5


@dataclasses.dataclass
class Program:
    """One analyzable program (see module docstring)."""

    name: str
    fn: Optional[Callable] = None
    args: Tuple[Any, ...] = ()
    n: int = _N
    hoist: str = jaxpr_rules.HOIST_SKIP
    upcast_allowlist: Sequence[str] = jaxpr_rules.DEFAULT_UPCAST_ALLOWLIST
    check_hlo: bool = True
    options_factory: Optional[Callable[[], Any]] = None
    launches: Tuple[KernelLaunch, ...] = ()


# ---------------------------------------------------------------------------
# Tick programs
# ---------------------------------------------------------------------------

def _snn_params(n: int):
    from repro.core import connectivity
    from repro.core.lif import LIFParams
    from repro.core.network import SNNParams

    rng = np.random.default_rng(0)
    c = connectivity.sparse_random(n, 0.3, seed=0)
    return SNNParams(
        w=jnp.asarray(rng.uniform(0, 2.0, (n, n)), jnp.float32),
        c=jnp.asarray(c, jnp.float32),
        w_in=jnp.eye(n, dtype=jnp.float32),
        lif=LIFParams.make(n, v_th=1.0, leak=0.25, r_ref=1))


def _ext_seq(n: int, ticks: int):
    rng = np.random.default_rng(1)
    return jnp.asarray((rng.random((ticks, n)) < 0.3), jnp.float32)


def _tick_options(backend: str, learning: bool, telemetry: bool):
    from repro.core.engine import EngineOptions
    from repro.plasticity import PlasticityParams

    kw: dict = dict(backend=backend, telemetry=telemetry)
    if learning:
        kw["plasticity"] = PlasticityParams.make(
            "stdp", a_plus=0.05, a_minus=0.05)
    elif backend == "event":
        # The frozen event programs ship with the adaptive knee on, so
        # the per-tick lax.cond (both arms) is part of the linted program.
        kw["event_knee"] = 4
    return EngineOptions(**kw)


def _tick_hoist(backend: str, learning: bool) -> str:
    if backend == "pallas":
        # w and c stream into the kernel separately; the mask multiply
        # happens per tile in VMEM (judged by the kernel lint), so the
        # jaxpr-level contract is only "no dense W*C leaked into the loop".
        return jaxpr_rules.HOIST_KERNEL
    if learning:
        return (jaxpr_rules.HOIST_IN_LOOP
                if backend in ("jnp", "event")
                else jaxpr_rules.HOIST_KERNEL)
    return jaxpr_rules.HOIST_HOISTED


def _tick_program(backend: str, learning: bool, telemetry: bool) -> Program:
    from repro.core.engine import TickEngine
    from repro.core.network import SNNState

    opts = _tick_options(backend, learning, telemetry)
    engine = TickEngine(opts)
    params = _snn_params(_N)
    state = SNNState.zeros((), _N)
    ext = _ext_seq(_N, _TICKS)
    if learning:
        from repro.plasticity import PlasticityState

        pst = PlasticityState.zeros((), _N)
        fn = functools.partial(engine.learning_rollout, n_ticks=_TICKS)
        args = (params, state, pst, ext)
    else:
        fn = functools.partial(engine.rollout, n_ticks=_TICKS)
        args = (params, state, ext)
    tag = "learning" if learning else "frozen"
    tel = "telem" if telemetry else "notelem"
    return Program(
        name=f"tick/{backend}/{tag}/{tel}",
        fn=fn, args=args, n=_N,
        hoist=_tick_hoist(backend, learning),
        options_factory=functools.partial(
            _tick_options, backend, learning, telemetry),
    )


def _sharded_options(learning: bool, telemetry: bool):
    """EngineOptions with a 1-device ``("model",)`` mesh: the analysis
    sweep runs wherever CI lands (usually one visible device), and the
    sharded program structure -- shard_map around the scan, spec trees,
    the spike collective plumbing -- is identical at any axis size; only
    the gather width changes.  Meshes compare by device assignment, so
    the factory stays hash-stable across calls (rule d)."""
    from repro.core.engine import EngineOptions
    from repro.launch.mesh import make_snn_mesh
    from repro.plasticity import PlasticityParams

    kw: dict = dict(backend="jnp", telemetry=telemetry,
                    mesh=make_snn_mesh(1))
    if learning:
        kw["plasticity"] = PlasticityParams.make(
            "stdp", a_plus=0.05, a_minus=0.05)
    return EngineOptions(**kw)


def _tick_sharded_program(learning: bool, telemetry: bool) -> Program:
    from repro.core.engine import TickEngine
    from repro.core.network import SNNState

    engine = TickEngine(_sharded_options(learning, telemetry))
    params = _snn_params(_N)
    state = SNNState.zeros((), _N)
    ext = _ext_seq(_N, _TICKS)
    if learning:
        from repro.plasticity import PlasticityState

        pst = PlasticityState.zeros((), _N)
        fn = functools.partial(engine.learning_rollout, n_ticks=_TICKS)
        args = (params, state, pst, ext)
    else:
        fn = functools.partial(engine.rollout, n_ticks=_TICKS)
        args = (params, state, ext)
    tag = "learning" if learning else "frozen"
    tel = "telem" if telemetry else "notelem"
    # shard_map is not a loop primitive: the frozen premask hoists to
    # just inside the partition, which the hoist walk still sees as
    # outside every scan body -- HOIST_HOISTED holds sharded too.
    return Program(
        name=f"tick/sharded/{tag}/{tel}",
        fn=fn, args=args, n=_N,
        hoist=(jaxpr_rules.HOIST_IN_LOOP if learning
               else jaxpr_rules.HOIST_HOISTED),
        options_factory=functools.partial(
            _sharded_options, learning, telemetry),
    )


def _fan_out_options(telemetry: bool):
    from repro.core.engine import EngineOptions

    return EngineOptions(mode="psc_exp", backend="event",
                         event_dispatch="fan_out", event_k_active=4,
                         telemetry=telemetry)


def _fan_out_program(telemetry: bool) -> Program:
    """The microcircuit's chunk program in miniature: a resident
    fan-out with per-synapse weights and delays, psc_exp neurons and the
    Poisson drive (``configs/pd_microcircuit.py`` at full size)."""
    import jax

    from repro.core import connectivity
    from repro.core.engine import TickCarry, TickEngine
    from repro.core.lif import LIFParams
    from repro.core.network_types import PoissonDrive, SNNParams, SNNState

    n, depth = _N, 4
    rng = np.random.default_rng(4)
    src, tgt = np.nonzero(connectivity.sparse_random(n, 0.3, seed=4))
    fo = connectivity.fan_out_from_synapses(
        src, tgt, rng.integers(-64, 64, src.size) * 2.0 ** -4,
        rng.integers(1, depth + 1, src.size), (0, n // 2, n))
    params = SNNParams(
        w=None, c=None, w_in=jnp.zeros((0, n), jnp.float32),
        lif=LIFParams.psc_exp(n, c_m=250.0, tau_m=10.0, tau_syn=0.5,
                              t_ref=2.0, e_l=-65.0, v_th=-50.0,
                              v_reset=-65.0, dt=0.1),
        drive=PoissonDrive(key=jax.random.PRNGKey(0),
                           lam=jnp.full((n,), 1.5, jnp.float32),
                           weight=jnp.full((n,), 87.8125, jnp.float32)))
    engine = TickEngine(_fan_out_options(telemetry))
    carry = TickCarry(state=SNNState.zeros((), n, depth, current=True))

    def fn(params, carry, fo):
        return engine.chunk(params, carry, None, _TICKS, neighbors=fo)

    tel = "telem" if telemetry else "notelem"
    return Program(
        name=f"tick/fan_out/psc_exp/{tel}", fn=fn, args=(params, carry, fo),
        n=n, hoist=jaxpr_rules.HOIST_SKIP,
        options_factory=functools.partial(_fan_out_options, telemetry))


# ---------------------------------------------------------------------------
# Serve programs (chunk / refill)
# ---------------------------------------------------------------------------

def _demo_server(event: bool):
    """A tiny 2-slot server with one resident demo tenant (dense or
    sparse-enough-to-ride-the-event-program)."""
    from repro.core import connectivity
    from repro.core.lif import LIFParams
    from repro.core.network import SNNParams
    from repro.launch.serve import SNNServer

    n_max, n = 16, 12
    server = SNNServer(n_max=n_max, slots=2, max_ticks=4, backend="jnp",
                       event_density=0.2 if event else None, chunk_ticks=2)
    rng = np.random.default_rng(2)
    c = (connectivity.sparse_random(n, 0.08, seed=3) if event
         else connectivity.all_to_all(n))
    params = SNNParams(
        w=jnp.asarray(rng.uniform(0, 2.0, (n, n)), jnp.float32),
        c=jnp.asarray(c, jnp.float32),
        w_in=jnp.eye(n, dtype=jnp.float32),
        lif=LIFParams.make(n, v_th=1.0, leak=0.25, r_ref=1))
    t = server.add_tenant_params("demo", params, n_in=n, n_out=n,
                                 plastic=False)
    if event and t.backend != "event":
        raise RuntimeError(
            "demo tenant did not route to the event program; the serve "
            "registry is mis-built")
    return server, t


def _serve_chunk_program(event: bool) -> Program:
    import jax

    server, t = _demo_server(event)
    backend = t.backend
    S, N, chunk = server.slots, server.n_max, 2
    fresh = server._fresh_slot_carry(t)
    bcast = lambda x: jax.tree.map(
        lambda a: jnp.broadcast_to(a, (S,) + a.shape), x)
    args = (bcast(t.params), bcast(fresh),
            jnp.zeros((S, chunk, N), jnp.float32),
            jnp.broadcast_to(t.plastic_c, (S,) + t.plastic_c.shape),
            jnp.zeros((S, chunk), jnp.float32),
            jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), jnp.bool_),
            jnp.zeros((S, N), jnp.float32),
            bcast(t.fan_idx) if event else None,
            bcast(t.fan_mask) if event else None)
    # _chunk_run_for registers the backend engine and returns the jitted
    # chunk program -- the same object serving runs (make_jaxpr recurses
    # into the pjit eqn, so the analysis sees the whole body).
    fn = server._chunk_run_for(backend, chunk)
    # The step vmaps the tick over slots, so every W*C product carries a
    # leading slot axis -- the rank-2 hoist grep does not apply (the
    # tick programs above pin the hoist contract for each backend).
    return Program(name=f"serve/chunk/{backend}", fn=fn, args=args,
                   n=N, hoist=jaxpr_rules.HOIST_SKIP)


def _serve_refill_program() -> Program:
    import jax

    server, t = _demo_server(False)
    S, N = server.slots, server.n_max
    fresh = server._fresh_slot_carry(t)
    bcast = lambda x: jax.tree.map(
        lambda a: jnp.broadcast_to(a, (S,) + a.shape), x)
    zero_row = jnp.zeros((N,), jnp.float32)
    stacked = (bcast(t.params), bcast(fresh),
               jnp.broadcast_to(t.plastic_c, (S,) + t.plastic_c.shape),
               jnp.zeros((S, N), jnp.float32), None, None)
    image = (t.params, fresh, t.plastic_c, zero_row, None, None)
    fill = server._fill_run_for("jnp")
    return Program(name="serve/refill/jnp", fn=fill,
                   args=(stacked, image, jnp.asarray(0, jnp.int32)),
                   n=N, hoist=jaxpr_rules.HOIST_SKIP)


# ---------------------------------------------------------------------------
# Kernel launches (representative padded operating point)
# ---------------------------------------------------------------------------

def kernel_launches() -> Tuple[Tuple[str, KernelLaunch], ...]:
    """``(registry name, launch)`` for each Pallas kernel at a
    representative shape (MXU-aligned, the sizes
    :mod:`repro.kernels.ops` would pick for a mid-size fabric).  The
    registry name disambiguates variants of the same kernel (the frozen
    and learning tick launches share ``KernelLaunch.name``)."""
    from repro.kernels.event_dispatch import event_db_launch
    from repro.kernels.lif_step import lif_launch
    from repro.kernels.stdp_update import stdp_launch
    from repro.kernels.tick_fused import tick_launch

    f32, i32 = jnp.float32, jnp.int32
    lif_dt = {"s": f32, "w": f32, "c": f32, "v": f32, "r": i32,
              "drive": f32, "param": f32}
    tick_dt = {"dly_read": f32, "w": f32, "c": f32, "delays": i32,
               "v": f32, "r": i32, "drive": f32, "dly_full": f32,
               "param": f32}
    ev_dt = {"w": f32, "v": f32, "r": i32, "drive": f32, "param": f32}
    stdp_dt = {"s_pre": f32, "x_pre": f32, "s_post": f32, "x_post": f32,
               "w": f32, "c": f32, "elig": f32, "reward": f32}
    return (
        ("lif_step", lif_launch(B=128, K=512, N=256, dtypes=lif_dt)),
        # Frozen pre-masked uniform-delay tick (no c operand), delay
        # depth 4: the bridge passes the arriving slot alone (n_read=1).
        ("tick_fused/frozen",
         tick_launch(B=128, K=512, N=256, n_read=1, dtypes=tick_dt,
                     has_c=False, has_delays=False, has_drive=True,
                     write_delay=True, n_full=4)),
        # Learning per-synapse-delay tick: w and c stream separately.
        ("tick_fused/learning",
         tick_launch(B=128, K=512, N=256, n_read=4, dtypes=tick_dt,
                     has_c=True, has_delays=True, has_drive=True,
                     write_delay=True, n_full=4)),
        # The serving path's slot grid: 4 slots, each at its own pointers;
        # every operand stacked per slot but the mask, which the slots
        # share.
        ("tick_fused/slots",
         tick_launch(B=8, K=512, N=256, n_read=1, dtypes=tick_dt,
                     has_c=True, has_delays=False, has_drive=True,
                     write_delay=False, n_slots=4,
                     batched=("dly_read", "w", "v", "r", "drive", "v_th",
                              "leak", "r_ref", "gain", "i_bias",
                              "v_reset"))),
        ("event_dispatch_db",
         event_db_launch(B=8, K=1024, N=256, k_active=128, dtypes=ev_dt,
                         has_drive=True)),
        ("stdp_update", stdp_launch(B=128, K=128, N=128, dtypes=stdp_dt)),
    )


# ---------------------------------------------------------------------------
# Static-argnames registry (rule d)
# ---------------------------------------------------------------------------

def jit_static_registry():
    """(jitted fn, declared static_argnames) for every kernel entry point
    -- the analyzer validates each name against the unwrapped signature.
    """
    from repro.kernels import event_dispatch, lif_step, stdp_update, tick_fused

    dims = ("block_b", "block_n", "block_k")
    return (
        (tick_fused.fused_tick, ("mode",) + dims + ("interpret",)),
        (lif_step.fused_lif_step, ("mode",) + dims + ("interpret",)),
        (event_dispatch.event_lif_dispatch_db,
         ("mode", "block_n", "interpret")),
        (stdp_update.fused_stdp_step,
         ("rule", "a_plus", "a_minus", "decay_pre", "decay_post",
          "decay_elig", "lr_reward", "w_min", "w_max") + dims
         + ("interpret",)),
    )


def demo_dispatch_plan():
    """A representative admission-time dispatch plan (sparse topology at
    the serve cap) for the DispatchPlan static rules."""
    from repro.core import connectivity, dispatch_policy

    c = np.asarray(connectivity.sparse_random(_N, 0.08, seed=5)) > 0
    return dispatch_policy.plan(
        c, w_in=np.eye(_N, dtype=np.float32), cap=8, vmap_safe=True,
        prefer_density=0.2)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

BACKENDS = ("jnp", "pallas", "pallas_fused", "event")


def program_names() -> Tuple[str, ...]:
    names = [f"tick/{b}/{t}/{tel}"
             for b in BACKENDS
             for t in ("frozen", "learning")
             for tel in ("notelem", "telem")]
    names += ["tick/sharded/frozen/notelem", "tick/sharded/learning/telem"]
    names += ["tick/fan_out/psc_exp/notelem", "tick/fan_out/psc_exp/telem"]
    names += ["serve/chunk/jnp", "serve/chunk/event", "serve/refill/jnp"]
    names += [f"kernel/{reg}" for reg, _ in kernel_launches()]
    return tuple(names)


def build_program(name: str) -> Program:
    """Build one program by name (lazy -- nothing traces until a rule
    asks for the jaxpr)."""
    parts = name.split("/")
    if name.startswith("tick/fan_out/psc_exp/"):
        return _fan_out_program(parts[-1] == "telem")
    if parts[0] == "tick":
        _, backend, tag, tel = parts
        if backend == "sharded":
            return _tick_sharded_program(tag == "learning", tel == "telem")
        return _tick_program(backend, tag == "learning", tel == "telem")
    if name in ("serve/chunk/jnp", "serve/chunk/event"):
        return _serve_chunk_program(parts[-1] == "event")
    if name == "serve/refill/jnp":
        return _serve_refill_program()
    if parts[0] == "kernel":
        reg_name = "/".join(parts[1:])
        for reg, launch in kernel_launches():
            if reg == reg_name:
                return Program(name=name, launches=(launch,))
        raise KeyError(f"unknown kernel launch {reg_name!r}")
    raise KeyError(f"unknown program {name!r}")


def iter_programs(names: Optional[Sequence[str]] = None) -> Iterator[Program]:
    for name in (names or program_names()):
        yield build_program(name)

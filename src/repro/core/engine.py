"""TickEngine: ONE scan body behind every rollout flavor.

The paper's datapath is a single resident circuit -- delay-line read,
masked synaptic accumulation (the mux fabric), LIF update, delay-line
write -- and everything else (frozen inference, on-device learning,
layered feed-forward sweeps, multi-tenant serving) is just a different
*carry* threaded through that same circuit. Before this module the repo
had three near-duplicate ``lax.scan`` bodies re-deriving the tick;
now :meth:`TickEngine.tick_body` is the only place the tick exists, and
``repro.core.network.rollout`` / ``learning_rollout`` /
``forward_layered`` are thin wrappers over :meth:`TickEngine.scan`.

Two structural invariants the engine owns:

* **One backend dispatch point.** ``backend="jnp"`` (reference) vs
  ``backend="pallas"`` (fused synaptic-matmul+LIF kernel) vs
  ``backend="pallas_fused"`` (the whole-tick megakernel: delay read,
  masked accumulation, LIF update, delay write in ONE ``pallas_call``,
  circular delay pointer scalar-prefetched -- see
  :mod:`repro.kernels.tick_fused`) vs ``backend="event"`` (event-driven
  sparse dispatch: only spiking neurons' fan-outs are gathered, the mux
  fabric's silent-neurons-cost-nothing property -- see
  :func:`repro.kernels.ops.event_lif_step`) is decided in exactly one
  branch inside the tick body -- no caller ever re-implements it, and
  delay rings, refractory state and the plasticity hook compose with
  every backend unchanged.

* **Loop-invariant mask hoisting.** For the frozen-weight path the
  masked matrix ``W*C`` is materialized once per rollout, *outside* the
  scan, and closed over as a scan constant (tests/test_engine.py pins
  this on the optimized HLO: no (n,n) multiply inside the while body).
  The learning path recomputes ``W*C`` per tick because ``W`` lives in
  the carry and changes every tick -- that recompute is the datapath,
  not waste.

Carry spec: :class:`TickCarry` has four slots -- ``state`` (always),
``plast`` + ``w`` (learning only) and ``telem`` (telemetry only;
``None`` leaves vanish from the pytree, so the frozen/untelemetered
carry is exactly the seed's ``SNNState`` carry and rasters stay
bit-identical).

Observability (DESIGN.md §11): ``telemetry=True`` (a *static* flag, like
``backend``) threads a :class:`~repro.obs.telemetry.TickTelemetry`
accumulator through the carry -- per-tick spike counts, membrane
mean/max, refractory occupancy, event-overflow ticks and plasticity
weight-delta norms, all carry-resident reductions with no host syncs
inside the scan. ``telemetry=False`` compiles to HLO byte-identical to
the pre-observability engine (pinned in tests/test_obs.py), and the
``jax.named_scope`` labels on the backend arms are pure metadata under
the same pin.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.lif import lif_step
from repro.deprecation import warn_deprecated
from repro.core.network_types import SNNParams, SNNState  # noqa: F401 (re-export surface)

_BACKENDS = ("jnp", "pallas", "pallas_fused", "event")
_MODES = ("fixed_leak", "euler", "int", "psc_exp")
_OVERFLOW = ("fallback", "strict", "unchecked")
_DISPATCH = ("auto", "fan_in", "topk", "dense", "fan_out")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TickCarry:
    """What one tick hands the next.

    Attributes:
      state: the network state (LIF + delay line + tick counter).
      plast: plasticity traces/eligibility, or None on the frozen path.
      w: the *mutable* weight matrix, or None on the frozen path (frozen
        weights are scan constants, so they live outside the carry and
        the hoisted ``W*C`` stays valid for the whole rollout).
      telem: :class:`~repro.obs.telemetry.TickTelemetry` accumulators, or
        None when the engine's ``telemetry`` flag is off (the leaf then
        vanishes from the pytree -- zero carry growth, identical HLO).
      policy: adaptive-dispatch hysteresis bit (scalar bool), or None
        when the engine has no per-tick knee armed (``event_knee``).
        True means the previous tick ran the dense arm for speed; the
        knee's release threshold then drops to ``hysteresis * knee`` so
        activity hovering at the knee doesn't flip the branch per tick.
    """

    state: SNNState
    plast: Optional[Any] = None
    w: Optional[jax.Array] = None
    telem: Optional[Any] = None
    policy: Optional[jax.Array] = None


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """ALL of the engine's static (trace-time) configuration, in one
    frozen, *validated* dataclass.

    This is the one home for what used to be :class:`TickEngine`'s
    sprawl of per-call statics (``backend``, ``telemetry``,
    ``event_k_active``, ``event_overflow``, ``event_dispatch``,
    ``event_knee``, ``event_hysteresis``, ``event_ext_diag``, ...).
    Invalid values and invalid *combinations* (e.g. ``event_knee``
    without ``event_overflow="fallback"``) fail here, at construction,
    with a clear message -- not deep inside the scan.

    Hashable non-pytree, like the LIF ``mode`` string it generalizes:
    jit-safe to close over, cheap to ``dataclasses.replace``. Build one
    and pass it to :class:`TickEngine`,
    :func:`repro.core.network.rollout` /
    :func:`~repro.core.network.learning_rollout`, or
    :class:`repro.launch.serve.SNNServer` -- the per-call static kwargs
    those accept remain as a deprecation shim for one release.

    Attributes:
      mode: LIF formulation ("fixed_leak" | "euler" | "int" | "psc_exp";
        "psc_exp", NEST's current-based ``iaf_psc_exp``, runs on the jnp
        and event backends only, and not on the event top-k kernel).
      surrogate: differentiable surrogate spike (training; jnp/event only).
      backend: "jnp" (reference), "pallas" (fused matmul+LIF kernel),
        "pallas_fused" (whole-tick megakernel, one launch per tick) or
        "event" (event-driven sparse dispatch: gather only spiking
        neurons' fan-outs -- the large-sparse-fabric backend).
      plasticity: optional :class:`~repro.plasticity.stdp.PlasticityParams`;
        when set *and* the carry holds weights, the plasticity hook runs
        after the delay-line write each tick.
      plasticity_backend: backend for the plasticity hook; defaults to
        following ``backend``.
      event_k_active: spike-slot budget for the event backend's top-k
        dispatch (None -> ``n // 8``, floored at 8, via
        :func:`repro.core.dispatch_policy.resolve_k_active`); rows
        spiking past it fall back to the dense product per
        ``event_overflow``.  For "fan_out" it is the block of row reads
        (``window`` fan-out entries each): a tick with more reads runs
        further blocks (never drops a spike).
      event_overflow: "fallback" (dense product on overflow ticks,
        exact at any rate), "strict" (checkify error) or "unchecked".
      event_dispatch: the event backend's synaptic-input formulation --
        "auto" (fan-in gather when ``neighbors`` is provided, else the
        top-k spike list; the :mod:`~repro.core.dispatch_policy` plan
        picks smarter), "fan_in" (requires ``neighbors``), "topk"
        (spike-list gather) or "dense" (masked product; still the event
        backend: it keeps the diagonal-drive elimination and telemetry,
        it just computes the synaptic product densely because the
        topology is past the gather knee on this platform) or "fan_out"
        (requires a :class:`~repro.core.connectivity.FanOut` as
        ``neighbors``: spiking sources push their per-synapse weights
        into the state's delay ring at their per-synapse delays, see
        :meth:`TickEngine._fan_out_tick`; unbatched, unsharded, frozen).
      event_knee: per-tick adaptive switch for the "topk" strategy:
        ticks whose max batch-row spike count exceeds this run the
        dense product instead of the spike-list gather (both arms
        bit-exact -- the knee is pure speed policy). None disables
        in-scan switching. See :func:`repro.core.dispatch_policy.
        knee_spikes` for the calibrated default.
      event_hysteresis: release fraction for the knee: after a dense
        tick, activity must fall below ``hysteresis * knee`` before the
        engine switches back to the spike-list arm.
      event_ext_diag: the external drive ``ext @ w_in`` is computed as
        the elementwise ``ext * diag(w_in)`` -- set (by the dispatch
        plan) only when ``w_in`` is diagonal, where it is bit-identical
        and saves a full ``n x n`` GEMM per tick.
      telemetry: static flag; when True the carry gains a
        :class:`~repro.obs.telemetry.TickTelemetry` slot and every tick
        folds its reductions in (see the module docstring). When False
        (default) the lowered HLO is byte-identical to the
        pre-observability engine.
      mesh: optional :class:`jax.sharding.Mesh`; when set, ``scan()``
        (and everything funneling through it: rollout, learning_rollout,
        chunk) runs under ``shard_map`` with the fabric partitioned by
        destination columns across ``shard_axis`` -- see
        :mod:`repro.parallel.snn_sharding` and DESIGN.md §15.  Hashable
        (meshes compare by device assignment), so the options stay a
        jit-safe static.
      shard_axis: mesh axis name to shard over (None -> the mesh's first
        axis).  Set *without* ``mesh`` it marks the engine as running
        INSIDE a ``shard_map`` body (the tick body then all-gathers the
        arriving spikes along this axis) -- that is how
        ``snn_sharding.sharded_scan`` builds its inner engine; user code
        sets ``mesh`` and leaves the inner form alone.
    """

    mode: str = "fixed_leak"
    surrogate: bool = False
    backend: str = "jnp"
    plasticity: Optional[Any] = None
    plasticity_backend: Optional[str] = None
    event_k_active: Optional[int] = None
    event_overflow: str = "fallback"
    event_dispatch: str = "auto"
    event_knee: Optional[int] = None
    event_hysteresis: float = 0.75
    event_ext_diag: bool = False
    telemetry: bool = False
    mesh: Optional[Any] = None
    shard_axis: Optional[str] = None

    def __post_init__(self):
        self.validate()

    def validate(self) -> None:
        """Fail fast on invalid values or combinations (construction-time)."""
        if self.backend not in _BACKENDS:
            raise ValueError(
                f"backend must be one of {_BACKENDS}, got {self.backend!r}")
        if self.mode not in _MODES:
            raise ValueError(
                f"mode must be one of {_MODES}, got {self.mode!r}")
        if self.mode == "psc_exp" and (
                self.backend not in ("jnp", "event")
                or (self.backend == "event" and self.event_dispatch == "topk")):
            raise ValueError(
                "mode='psc_exp' runs on the jnp and event backends only "
                "(and not on the event top-k kernel): the Pallas kernels "
                f"carry no synaptic current, got backend={self.backend!r}, "
                f"event_dispatch={self.event_dispatch!r}")
        if self.plasticity_backend not in (None,) + _BACKENDS:
            raise ValueError(
                f"plasticity_backend must be None or one of {_BACKENDS}, "
                f"got {self.plasticity_backend!r}")
        if self.event_overflow not in _OVERFLOW:
            raise ValueError(
                f"event_overflow must be one of {_OVERFLOW}, "
                f"got {self.event_overflow!r}")
        if self.event_dispatch not in _DISPATCH:
            raise ValueError(
                f"event_dispatch must be one of {_DISPATCH}, "
                f"got {self.event_dispatch!r}")
        if self.event_k_active is not None and int(self.event_k_active) < 1:
            raise ValueError(
                f"event_k_active must be >= 1 (or None for the n//8 "
                f"default), got {self.event_k_active}")
        if self.event_knee is not None:
            if int(self.event_knee) < 1:
                raise ValueError(
                    f"event_knee must be >= 1 ticks' spikes (or None to "
                    f"disable the adaptive knee), got {self.event_knee}")
            if self.event_overflow != "fallback":
                raise ValueError(
                    "event_knee requires event_overflow='fallback' (the "
                    "knee routes overflow ticks to the dense arm silently, "
                    "which contradicts strict/unchecked semantics)")
        if not (0.0 < float(self.event_hysteresis) <= 1.0):
            raise ValueError(
                "event_hysteresis is a release *fraction* of the knee and "
                f"must lie in (0, 1], got {self.event_hysteresis}")
        if self.mesh is not None:
            from jax.sharding import Mesh

            if not isinstance(self.mesh, Mesh):
                raise ValueError(
                    f"mesh must be a jax.sharding.Mesh, got {type(self.mesh)}")
            names = tuple(self.mesh.axis_names)
            axis = self.shard_axis if self.shard_axis is not None else names[0]
            if axis not in names:
                raise ValueError(
                    f"shard_axis {axis!r} is not a mesh axis (axes: {names})")
        if self.sharded and self.event_dispatch == "fan_out":
            raise ValueError(
                "event_dispatch='fan_out' is single-device: its delay ring "
                "and fan-out lists are not sharded")
        if self.sharded and self.event_ext_diag:
            raise ValueError(
                "event_ext_diag is unavailable on the sharded path: each "
                "shard holds a rectangular (n_in, n/D) slice of w_in whose "
                "jnp.diagonal is NOT the diagonal drive; the full "
                "ext @ w_in product is rectangular-safe, use that")

    @property
    def sharded(self) -> bool:
        """True when this engine partitions (or runs inside a partition
        of) the fabric -- outer ``mesh`` or inner ``shard_axis`` form."""
        return self.mesh is not None or self.shard_axis is not None

    def resolved_shard_axis(self) -> Optional[str]:
        """The mesh axis the fabric shards over (None when unsharded)."""
        if self.shard_axis is not None:
            return self.shard_axis
        if self.mesh is not None:
            return tuple(self.mesh.axis_names)[0]
        return None

    def effective_backend(self) -> str:
        """The backend the tick body actually dispatches to.

        Sharded ``"pallas_fused"`` remaps to ``"pallas"``: the whole-tick
        megakernel couples the delay-ring width to the state width inside
        one ``pallas_call`` and so cannot span the per-tick spike
        all-gather; the unfused pallas arm (fused synaptic-matmul+LIF,
        ring managed outside) composes with the collective unchanged.

        Exactness of the remap: on the frozen path weights live on the
        dyadic u8-grid, every f32 reduction order is exact, and the two
        arms are bitwise identical (pinned in tests/test_snn_sharding).
        Learning pushes weights off the grid, so the remapped arm agrees
        with single-device ``"pallas"`` learning bitwise and with the
        megakernel only to the ulp -- the documented contract for
        sharded ``pallas_fused`` learning.  (A 1-device mesh skips the
        remap entirely and stays bitwise with the megakernel: see
        :func:`repro.parallel.snn_sharding.sharded_scan`.)"""
        if self.sharded and self.backend == "pallas_fused":
            return "pallas"
        return self.backend

    def _fan_out(self, neighbors: Optional[Any]) -> bool:
        """Whether the event backend runs its ``fan_out`` strategy (the
        pairing of option and lists checked by :meth:`_event_strategy`)."""
        from repro.core.connectivity import FanOut

        if self.event_dispatch != "fan_out" and not isinstance(
                neighbors, FanOut):
            return False
        return self._event_strategy(neighbors) == "fan_out"

    def _event_strategy(self, neighbors: Optional[Any]) -> str:
        """Resolve ``event_dispatch`` against what the call provided."""
        from repro.core.connectivity import FanOut

        strategy = self.event_dispatch
        fan_out = isinstance(neighbors, FanOut)
        if strategy == "auto":
            strategy = ("fan_out" if fan_out else
                        "fan_in" if neighbors is not None else "topk")
        if strategy not in ("fan_in", "topk", "dense", "fan_out"):
            raise ValueError(
                f"event_dispatch must be auto|fan_in|topk|dense|fan_out, got "
                f"{self.event_dispatch!r}")
        if (strategy == "fan_out") != fan_out:
            raise ValueError(
                "event_dispatch='fan_out' takes (and only it takes) "
                "fan-out lists: pass neighbors=connectivity.FanOut")
        if self.mode == "psc_exp" and strategy == "topk":
            raise ValueError(
                "mode='psc_exp' has no event top-k kernel: pass fan-out or "
                "fan-in lists, or event_dispatch='dense'")
        if strategy == "fan_in" and neighbors is None:
            raise ValueError(
                "event_dispatch='fan_in' needs fan-in neighbor lists: pass "
                "neighbors=EventFanIn.from_dense(wc, c) (or let "
                "dispatch_policy.plan build them)")
        return strategy


class TickEngine(EngineOptions):
    """The resident tick datapath, configured by :class:`EngineOptions`.

    Preferred construction::

        eng = TickEngine(EngineOptions(backend="event", telemetry=True))

    The old per-call static kwargs (``TickEngine(backend=..., mode=...,
    event_k_active=..., ...)``) remain accepted as a deprecation shim for
    one release; they emit a :class:`DeprecationWarning` and keep the old
    *lazy* validation semantics (invalid combinations fail where they
    always did, inside the scan) so existing callers see no behavior
    change. New code should build an :class:`EngineOptions`, which
    validates eagerly at construction.

    Hashable, frozen, and field-compatible with :class:`EngineOptions`
    (it *is* one), so it stays jit-safe to close over.
    """

    def __init__(self, options: Optional[EngineOptions] = None, **legacy):
        if options is not None:
            if legacy:
                raise TypeError(
                    "pass ONE of EngineOptions or legacy static kwargs, "
                    f"not both (got options= and {sorted(legacy)})")
            if not isinstance(options, EngineOptions):
                raise TypeError(
                    f"options must be an EngineOptions, got {type(options)}")
            EngineOptions.__init__(
                self, **{f.name: getattr(options, f.name)
                         for f in dataclasses.fields(EngineOptions)})
            return
        names = {f.name for f in dataclasses.fields(EngineOptions)}
        unknown = set(legacy) - names
        if unknown:
            raise TypeError(
                f"unknown engine option(s) {sorted(unknown)}; valid names: "
                f"{sorted(names)}")
        if legacy:
            warn_deprecated(
                "TickEngine(**per-call statics) is deprecated; build a "
                "validated EngineOptions and pass TickEngine(options) "
                "(the kwargs shim remains for one release)")
        # Legacy shim: set fields WITHOUT the eager cross-field validation
        # (old callers relied on e.g. the event_knee/event_overflow clash
        # raising inside rollout, not at construction).
        for f in dataclasses.fields(EngineOptions):
            object.__setattr__(self, f.name, legacy.get(f.name, f.default))

    @property
    def options(self) -> EngineOptions:
        """This engine's configuration as a plain :class:`EngineOptions`."""
        return EngineOptions(**{f.name: getattr(self, f.name)
                                for f in dataclasses.fields(EngineOptions)})

    # -- the single tick body ---------------------------------------------

    def masked_weights(self, params: SNNParams, w: Optional[jax.Array] = None) -> jax.Array:
        """``W*C``: the mux fabric's effective matrix.

        ``c=None`` means the implicit all-to-all (every mux closed): the
        effective matrix IS ``w``, and no second ``(n, n)`` buffer is ever
        materialized -- the memory-math escape hatch for the 64k fabric
        (DESIGN.md §15)."""
        w = params.w if w is None else w
        if params.c is None:
            return w
        return w * params.c.astype(w.dtype)

    def tick_body(
        self,
        carry: TickCarry,
        xs: Tuple[Optional[jax.Array], Optional[jax.Array]],
        *,
        params: SNNParams,
        wc: Optional[jax.Array] = None,
        delays: Optional[jax.Array] = None,
        plastic_c: Optional[jax.Array] = None,
        learn_until: Optional[jax.Array] = None,
        neighbors: Optional[Any] = None,
    ) -> Tuple[TickCarry, jax.Array]:
        """One synchronous network tick:

        delay-line read -> synaptic input -> LIF step -> delay-line write
        [-> plasticity hook].

        Args:
          xs: ``(ext, reward)`` -- this tick's external drive (impulse
            registers) and dopamine scalar; either may be None.
          wc: pre-masked ``W*C`` (frozen path; loop-invariant, hoisted by
            the caller). None means derive it from the carry weights.
          delays: optional per-synapse delay matrix, shape ``(n, n)`` int
            in ``[1, max_delay]``.
          plastic_c: learnable-synapse mask for the plasticity hook.
          learn_until: optional scalar tick bound (runtime value): the
            plasticity hook only commits weight/trace updates while
            ``tick < learn_until``. Serving uses this to stop learning at
            a request's tick budget without changing program shape.
          neighbors: optional :class:`repro.kernels.ops.EventFanIn`
            switching the ``"event"`` backend to its padded fan-in gather
            path (no data-dependent control flow -- safe under ``vmap``,
            which is how the multi-tenant server runs sparse tenants).
            Ignored by the dense backends.
        """
        ext, reward = xs
        st = carry.state
        learning = carry.w is not None
        w = carry.w if learning else params.w
        backend = self.effective_backend()
        # Inner-shard form (set by snn_sharding.sharded_scan): this tick
        # body runs inside shard_map on (n, n/D) operands and must gather
        # the arriving spikes before the fan-in product.
        shard_axis = self.shard_axis if self.mesh is None else None
        if params.c is None and backend in ("pallas", "pallas_fused"):
            raise ValueError(
                "c=None (implicit all-to-all) needs the jnp or event "
                "backend: the Pallas kernels stream c as an explicit "
                "operand and mask per tile")

        max_delay = st.delay_buf.shape[-2]

        if backend == "event" and self._fan_out(neighbors):
            return self._fan_out_tick(carry, ext, reward, params, neighbors)
        if params.drive is not None:
            raise ValueError(
                "the Poisson drive (SNNParams.drive) runs on the event "
                "backend's fan_out strategy only")

        if backend == "pallas_fused":
            # -- whole-tick megakernel: delay read, masked accumulation, LIF
            #    update and delay write in ONE pallas_call; the circular
            #    pointers ride in as scalar prefetch (no retrace per tick).
            #    ``wc`` (pre-masked, hoisted) serves the frozen path; the
            #    learning path streams w (this tick's matrix) + c and masks
            #    per tile in VMEM.
            from repro.kernels import ops  # local import; CPU tests use jnp

            with jax.named_scope("tick/pallas_fused"):
                p = dataclasses.replace(params, w=w) if learning else params
                lif_state, delay_buf = ops.fused_tick(
                    st, p, ext, wc=wc, delays=delays,
                    mode=self.mode, surrogate=self.surrogate)
            state2 = SNNState(lif=lif_state, delay_buf=delay_buf,
                              tick=st.tick + 1)
            return self._tick_tail(carry, st, state2, w, reward,
                                   params, plastic_c, learn_until)

        if wc is None and (delays is not None or backend != "pallas"):
            # Every remaining path consumes the premasked matrix -- except
            # the unfused "pallas" uniform-delay tick, whose kernel masks
            # per tile in VMEM; forming wc there would be a dead (n, n)
            # multiply traced into every tick.
            wc = w if params.c is None else w * params.c.astype(w.dtype)

        slot = jnp.mod(st.tick, max_delay)
        overflow_inc = None
        policy_inc = None
        policy_out = None

        if delays is None:
            # -- delay-line read: spikes scheduled to arrive this tick.
            arriving = jax.lax.dynamic_index_in_dim(
                st.delay_buf, slot, axis=-2, keepdims=False
            ) if max_delay > 1 else st.lif.y
            if shard_axis is not None:
                # -- cross-shard spike exchange: THE one collective per
                #    tick. Gathering the (B, n/D) local arriving spikes
                #    into the full (B, n) presynaptic vector lets every
                #    shard reduce its output columns over the complete
                #    fan-in locally, in the single-device order -- which
                #    is what keeps the sharded rollout bit-exact (a psum
                #    of partial fan-ins would re-associate the f32 sum).
                #    tiled=True concatenates shard blocks in axis order,
                #    exactly the global column layout.  The gather sits
                #    BEFORE the event knee's lax.cond, so both arms (and
                #    every shard's branch decision) see identical data
                #    and no collective ever hides inside a branch.
                with jax.named_scope("tick/spike_all_gather"):
                    arriving = jax.lax.all_gather(
                        arriving, shard_axis,
                        axis=arriving.ndim - 1, tiled=True)
            # -- synaptic input + LIF step: THE backend dispatch point.
            if backend == "pallas":
                from repro.kernels import ops  # local import; CPU tests use jnp

                with jax.named_scope("tick/pallas"):
                    p = dataclasses.replace(params, w=w) if learning else params
                    lif_state = ops.fused_lif_step(
                        st.lif, arriving, p, ext,
                        mode=self.mode, surrogate=self.surrogate)
            elif backend == "event":
                # -- event-driven dispatch: only spiking neurons' fan-outs
                #    are gathered (the mux fabric routes nothing for silent
                #    neurons). ``wc`` is the hoisted matrix on the frozen
                #    path and this tick's carry-derived matrix when learning.
                #    The formulation ("fan_in" gather | "topk" spike list |
                #    "dense" product) is the trace-time strategy; the "topk"
                #    strategy additionally arbitrates per tick at the knee.
                from repro.core import dispatch_policy
                from repro.kernels import ops  # local import; CPU path is jnp

                strategy = self._event_strategy(neighbors)
                n = arriving.shape[-1]
                k = dispatch_policy.resolve_k_active(n, self.event_k_active)
                telemetry = self.telemetry and carry.telem is not None

                def _dense_step():
                    # The dense arm of the event backend: the masked product
                    # plus the (possibly diagonal-eliminated) drive. With
                    # event_ext_diag=False this is bit-identical to the
                    # "jnp" backend's tick; with it, identical anyway when
                    # w_in is diagonal (adding exact zeros is a f32 no-op).
                    syn = arriving @ wc
                    if ext is not None:
                        syn = syn + (
                            ext * jnp.diagonal(params.w_in)
                            if self.event_ext_diag else ext @ params.w_in)
                    return lif_step(st.lif, syn, params.lif, mode=self.mode,
                                    surrogate=self.surrogate)

                with jax.named_scope(f"tick/event/{strategy}"):
                    if strategy == "dense":
                        lif_state = _dense_step()
                    elif strategy == "fan_in":
                        # Exact by construction (no overflow: every in-edge
                        # is always read), safe under vmap.
                        lif_state = ops.event_lif_step(
                            st.lif, arriving, params, ext, wc,
                            k_active=self.event_k_active, fan_in=neighbors,
                            overflow=self.event_overflow,
                            mode=self.mode, surrogate=self.surrogate,
                            ext_diag=self.event_ext_diag)
                    elif self.event_knee is None:
                        lif_state = ops.event_lif_step(
                            st.lif, arriving, params, ext, wc,
                            k_active=self.event_k_active, fan_in=None,
                            overflow=self.event_overflow,
                            mode=self.mode, surrogate=self.surrogate,
                            ext_diag=self.event_ext_diag)
                        if telemetry:
                            # Mirror ops.event_synaptic_input's fallback
                            # trigger: ANY batch row spiking past k_active
                            # flips the whole tick to the dense product.
                            over = jnp.any(
                                jnp.sum(arriving > 0, axis=-1) > k)
                            overflow_inc = jnp.broadcast_to(
                                over.astype(jnp.int32),
                                carry.telem.overflow.shape)
                    else:
                        # -- adaptive knee: the spike-list gather's cost is
                        #    ~spikes * gather_penalty dense-row-equivalents,
                        #    so past the knee the dense product is simply
                        #    the faster exact arm. Generalizes the overflow
                        #    fallback from safety valve to speed policy:
                        #    overflow (m > k) *must* go dense for bits;
                        #    the knee band (knee < m <= k) goes dense for
                        #    ticks/s. Hysteresis: once dense, stay dense
                        #    until m falls below hysteresis * knee.
                        if self.event_overflow != "fallback":
                            raise ValueError(
                                "event_knee requires event_overflow="
                                "'fallback' (the knee routes overflow "
                                "ticks to the dense arm silently, which "
                                "contradicts strict/unchecked semantics)")
                        m = jnp.max(jnp.sum(arriving > 0, axis=-1))
                        over_k = m > k
                        hi = min(int(self.event_knee), k)
                        lo = int(hi * self.event_hysteresis)
                        prev = (carry.policy if carry.policy is not None
                                else jnp.zeros((), jnp.bool_))
                        dense_mode = (m > hi) | (prev & (m > lo))
                        take_dense = over_k | dense_mode
                        # Inside the event arm m <= min(knee, k): every
                        # spiking row fits the k top-k slots, so the
                        # unchecked gather is exact (the guard IS the
                        # overflow check -- no second cond inside).
                        lif_state = jax.lax.cond(
                            take_dense,
                            _dense_step,
                            lambda: ops.event_lif_step(
                                st.lif, arriving, params, ext, wc,
                                k_active=k, fan_in=None,
                                overflow="unchecked",
                                mode=self.mode, surrogate=self.surrogate,
                                ext_diag=self.event_ext_diag))
                        if carry.policy is not None:
                            policy_out = dense_mode
                        if telemetry:
                            overflow_inc = jnp.broadcast_to(
                                over_k.astype(jnp.int32),
                                carry.telem.overflow.shape)
                            policy_inc = jnp.broadcast_to(
                                (take_dense & ~over_k).astype(jnp.int32),
                                carry.telem.policy_dense.shape)
            else:
                with jax.named_scope("tick/jnp"):
                    syn = arriving @ wc
                    if ext is not None:
                        syn = syn + ext @ params.w_in
                    lif_state = lif_step(st.lif, syn, params.lif,
                                         mode=self.mode,
                                         surrogate=self.surrogate)
        else:
            # -- per-synapse delays: synapse (pre,post) reads slot (tick - delay).
            #    Like "pallas", the "event" backend composes with the matrix-
            #    delay path through this reference einsum (per-delay history
            #    planes defeat a single spike-list gather).
            def gather_delay(d):
                idx = jnp.mod(slot - d, max_delay)
                return jax.lax.dynamic_index_in_dim(
                    st.delay_buf, idx, axis=-2, keepdims=False)

            hist = jnp.stack([gather_delay(d) for d in range(max_delay)], axis=0)
            onehot = jax.nn.one_hot(delays - 1, max_delay, axis=0, dtype=wc.dtype)
            syn = jnp.einsum("d...p,dpq,pq->...q", hist, onehot, wc)
            if ext is not None:
                syn = syn + ext @ params.w_in
            lif_state = lif_step(st.lif, syn, params.lif,
                                 mode=self.mode, surrogate=self.surrogate)

        # -- delay-line write: freshly emitted spikes land at tick+1 (1-cycle min).
        if max_delay > 1:
            write_slot = jnp.mod(st.tick + 1, max_delay)
            delay_buf = jax.lax.dynamic_update_index_in_dim(
                st.delay_buf, lif_state.y, write_slot, axis=-2)
        else:
            delay_buf = st.delay_buf
        state2 = SNNState(lif=lif_state, delay_buf=delay_buf, tick=st.tick + 1)
        # Sharded learning: the presynaptic events are the GATHERED full-
        # width arriving spikes (with max_delay == 1 they are exactly the
        # gathered previous-tick emissions), so the plasticity hook sees
        # the same (.., n) x (.., n/D) operands on every shard and its
        # x_pre trace stays replicated by construction.
        s_pre = arriving if (shard_axis is not None and delays is None) else None
        return self._tick_tail(carry, st, state2, w, reward,
                               params, plastic_c, learn_until,
                               overflow_inc=overflow_inc,
                               policy=policy_out, policy_inc=policy_inc,
                               s_pre=s_pre)

    def _fan_out_tick(
        self, carry: TickCarry, ext, reward, params: SNNParams, fan_out,
    ) -> Tuple[TickCarry, jax.Array]:
        """One tick of the event backend's ``fan_out`` strategy.

        The state's ``delay_buf`` is the postsynaptic ring (see
        :class:`~repro.core.network_types.SNNState`)::

            x      = ring[t % D] (+ the Poisson drive)
            ring[t % D] = 0
            LIF(x)  ->  spikes
            ring[(t + d) % D, target] += w    for each spiking source's
                                              fan-out entry (target, w, d)

        The cost scales with spikes x fan-out, not with ``D x n^2``.
        Spiking sources' rows are read ``fan_out.window`` entries at a
        time, in blocks of ``event_k_active`` reads; a tick with more
        reads runs further blocks, counted as ``spill_blocks`` in the
        telemetry.  With weights on a dyadic grid every ring sum
        is exact, in any order.
        """
        from repro.core import dispatch_policy

        st = carry.state
        if carry.w is not None:
            raise ValueError("event_dispatch='fan_out' is frozen-weight "
                             "only (no plasticity on the fan-out lists)")
        if st.lif.v.ndim != 1:
            raise ValueError(
                "event_dispatch='fan_out' runs one unbatched fabric; vmap "
                f"it for a batch (state shape {st.lif.v.shape})")
        if ext is not None:
            raise ValueError(
                "event_dispatch='fan_out' takes no external input: its "
                "drive is SNNParams.drive, drawn on the device")
        ring = st.delay_buf
        slot = jnp.mod(st.tick, ring.shape[-2])
        with jax.named_scope("tick/event/fan_out/drive"):
            syn = jax.lax.dynamic_index_in_dim(ring, slot, axis=-2,
                                               keepdims=False)
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, jnp.zeros_like(syn), slot, axis=-2)
            if params.drive is not None:
                syn = syn + params.drive.input(st.tick)
        lif_state = lif_step(st.lif, syn, params.lif, mode=self.mode,
                             surrogate=self.surrogate)
        # a block of reads, not of sources: not capped at n
        k = (int(self.event_k_active) if self.event_k_active is not None
             else dispatch_policy.resolve_k_active(fan_out.n))
        with jax.named_scope("tick/event/fan_out/deliver"):
            ring, blocks = fan_out_deliver(ring, lif_state.y, st.tick,
                                           fan_out, k)
        state2 = SNNState(lif=lif_state, delay_buf=ring, tick=st.tick + 1)
        inc = None
        if self.telemetry and carry.telem is not None:
            fired = lif_state.y > 0
            inc = (jnp.sum(jnp.where(fired, fan_out.count, 0)).astype(
                       jnp.float32),
                   jnp.maximum(blocks - 1, 0),
                   pop_counts(lif_state.y, fan_out.pop_starts))
        return self._tick_tail(carry, st, state2, None, reward, params,
                               None, None, fan_out_inc=inc)

    def _tick_tail(
        self, carry, st, state2, w, reward, params, plastic_c, learn_until,
        overflow_inc=None, policy=None, policy_inc=None, s_pre=None,
        fan_out_inc=None,
    ) -> Tuple[TickCarry, jax.Array]:
        """Shared tick tail: fold telemetry, optionally run the plasticity
        hook (:meth:`plasticity_hook`), and rebuild the carry.

        The default presynaptic events are ``st.lif.y`` (the previous
        tick's emissions; exact for ``max_delay == 1``, which learning
        requires); the sharded tick body overrides ``s_pre`` with the
        gathered full-width arriving spikes so plasticity sees the whole
        presynaptic axis against its local postsynaptic columns.
        """
        lif_state = state2.lif
        telemetry = self.telemetry and carry.telem is not None
        # Hysteresis slot: updated only by the adaptive knee; every other
        # path passes the carried bit (usually None) through unchanged so
        # the carry pytree stays scan-invariant.
        policy2 = policy if policy is not None else carry.policy
        telem2 = carry.telem.accumulate(
            lif_state, overflow_inc=overflow_inc,
            policy_inc=policy_inc,
            fan_out_inc=fan_out_inc) if telemetry else carry.telem
        plast2, w2 = carry.plast, carry.w
        if carry.w is not None and self.plasticity is not None:
            plast2, w2 = self.plasticity_hook(
                carry.plast, w, st.lif.y if s_pre is None else s_pre,
                lif_state.y, params.c if plastic_c is None else plastic_c,
                reward,
                gate=None if learn_until is None else st.tick < learn_until)
            if telemetry:
                telem2 = telem2.fold_dw(w2 - w)  # the committed delta
        return TickCarry(state=state2, plast=plast2, w=w2,
                         telem=telem2, policy=policy2), lif_state.y

    def plasticity_hook(
        self, plast, w, s_pre, s_post, plastic_c, reward, *, gate=None,
    ):
        """One learning tick on one carry's weights; returns
        ``(plast', w')``.

        ``s_pre`` is what arrived (previous emissions), ``s_post`` what was
        just emitted -- the NeuroCoreX shared datapath. The hook always runs
        *outside* the tick kernel (including for ``backend="pallas_fused"``):
        learning is its own fused pass over ``(w, elig, traces)``, a disjoint
        working set from the tick's ``(v, r, delay line)``.

        ``gate`` (runtime bool, the ``learn_until`` test) commits the
        update only where it holds; None commits it unconditionally -- for
        a caller that decides whether to run the hook at all, as the
        continuous server's chunk program does per slot.  With telemetry
        on, the caller folds the committed delta ``w' - w`` in
        (:meth:`~repro.obs.telemetry.TickTelemetry.fold_dw`).
        """
        from repro.plasticity import rules as plasticity_rules

        pb = self.plasticity_backend or self.backend
        if pb == "pallas_fused":
            pb = "pallas"  # the plasticity pass has no whole-tick variant
        elif pb == "event":
            pb = "jnp"     # STDP outer products are dense; no event pass
        with jax.named_scope("tick/plasticity"):
            plast2, w2 = plasticity_rules.plasticity_step(
                plast, s_pre, s_post, w, plastic_c, self.plasticity, reward,
                backend=pb)
        if gate is not None:
            w2 = jnp.where(gate, w2, w)
            plast2 = jax.tree.map(
                lambda new, old: jnp.where(gate, new, old), plast2, plast)
        return plast2, w2

    # -- scan driver -------------------------------------------------------

    def _seed_carry(self, carry0: TickCarry, neighbors: Optional[Any]) -> TickCarry:
        """Seed the optional carry slots (telemetry accumulator, knee
        hysteresis bit) the engine's statics call for.  Shared by the
        single-device scan and the sharded wrapper (which seeds on the
        GLOBAL side so its spec trees see the final carry structure)."""
        if self.telemetry and carry0.telem is None:
            from repro.obs.telemetry import TickTelemetry

            fan_out = self.backend == "event" and self._fan_out(neighbors)
            carry0 = dataclasses.replace(
                carry0,
                telem=TickTelemetry.zeros(
                    carry0.state.lif.v.shape[:-1],
                    n_pops=neighbors.n_pops if fan_out else None))
        if (self.backend == "event" and self.event_knee is not None
                and carry0.policy is None
                and self._event_strategy(neighbors) == "topk"):
            # Seed the hysteresis bit (start in the spike-list arm).
            carry0 = dataclasses.replace(
                carry0, policy=jnp.zeros((), jnp.bool_))
        return carry0

    def scan(
        self,
        params: SNNParams,
        carry0: TickCarry,
        ext_seq: Optional[jax.Array],
        n_ticks: int,
        *,
        rewards: Optional[jax.Array] = None,
        delays: Optional[jax.Array] = None,
        plastic_c: Optional[jax.Array] = None,
        learn_until: Optional[jax.Array] = None,
        neighbors: Optional[Any] = None,
    ) -> Tuple[TickCarry, jax.Array]:
        """Scan ``n_ticks`` ticks of :meth:`tick_body`; returns
        ``(final_carry, raster)``.

        Frozen carries (``carry0.w is None``) get the hoisted ``W*C``;
        learning carries re-derive it per tick from the carried weights.
        With ``telemetry=True`` a zeroed accumulator is seeded into the
        carry when the caller didn't provide one.

        With ``mesh`` set this whole method runs under ``shard_map``
        instead (:func:`repro.parallel.snn_sharding.sharded_scan`): one
        compiled program, the hoist and the scan INSIDE the partition,
        so the frozen path still materializes its (local) ``W*C`` slab
        exactly once per rollout.
        """
        if self.mesh is not None:
            from repro.parallel import snn_sharding

            return snn_sharding.sharded_scan(
                self, params, carry0, ext_seq, n_ticks, rewards=rewards,
                delays=delays, plastic_c=plastic_c,
                learn_until=learn_until, neighbors=neighbors)
        carry0 = self._seed_carry(carry0, neighbors)
        learning = carry0.w is not None
        wc = None
        if not learning and self.effective_backend() != "pallas":
            # Loop-invariant: materialized ONCE per rollout, a scan constant.
            # For "pallas_fused" this pre-masked matrix is the kernel's single
            # weight operand (no per-tile mask multiply, no c traffic).
            wc = self.masked_weights(params)

        def body(carry, xs):
            return self.tick_body(carry, xs, params=params, wc=wc,
                                  delays=delays, plastic_c=plastic_c,
                                  learn_until=learn_until, neighbors=neighbors)

        if ext_seq is None and rewards is None:
            return jax.lax.scan(
                lambda c, _: body(c, (None, None)), carry0, None, length=n_ticks)
        if ext_seq is None:
            return jax.lax.scan(
                lambda c, r: body(c, (None, r)), carry0, rewards, length=n_ticks)
        if rewards is None:
            return jax.lax.scan(
                lambda c, e: body(c, (e, None)), carry0, ext_seq)
        return jax.lax.scan(body, carry0, (ext_seq, rewards))

    # -- convenience entry points (what the network wrappers call) --------

    def tick(
        self,
        state: SNNState,
        params: SNNParams,
        ext: Optional[jax.Array] = None,
        *,
        delays: Optional[jax.Array] = None,
        neighbors: Optional[Any] = None,
    ) -> SNNState:
        """One frozen-weight tick (the public ``network.step`` semantics)."""
        if self.mesh is not None:
            raise ValueError(
                "tick() is single-device; the sharded engine runs through "
                "scan()/rollout()/chunk() (shard_map wraps the whole scan, "
                "so a 1-tick chunk() is the sharded single tick)")
        carry, _ = self.tick_body(TickCarry(state=state), (ext, None),
                                  params=params, delays=delays,
                                  neighbors=neighbors)
        return carry.state

    def rollout(
        self,
        params: SNNParams,
        state: SNNState,
        ext_seq: Optional[jax.Array],
        n_ticks: int,
        *,
        delays: Optional[jax.Array] = None,
        neighbors: Optional[Any] = None,
    ):
        """Frozen-weight rollout; returns ``(final_state, raster)`` -- or
        ``(final_state, raster, telemetry)`` when the engine's static
        ``telemetry`` flag is set (the extra element is compile-time
        constant arity, so no retraces)."""
        final, raster = self.scan(params, TickCarry(state=state), ext_seq,
                                  n_ticks, delays=delays, neighbors=neighbors)
        if self.telemetry:
            return final.state, raster, final.telem
        return final.state, raster

    def learning_rollout(
        self,
        params: SNNParams,
        state: SNNState,
        plast_state: Any,
        ext_seq: Optional[jax.Array],
        n_ticks: int,
        *,
        rewards: Optional[jax.Array] = None,
        plastic_c: Optional[jax.Array] = None,
        learn_until: Optional[jax.Array] = None,
        neighbors: Optional[Any] = None,
    ):
        """Learning rollout: the carry holds mutable weights; returns
        ``((final_state, final_plast_state, final_w), raster)`` -- plus a
        trailing ``telemetry`` element when the engine's static
        ``telemetry`` flag is set.

        ``learn_until`` (optional runtime scalar) freezes the plasticity
        hook from that tick on -- see :meth:`tick_body`."""
        if self.plasticity is None:
            raise ValueError("learning_rollout needs a TickEngine with plasticity set")
        if state.delay_buf.shape[-2] != 1:
            raise ValueError(
                "learning_rollout requires max_delay == 1 (pair STDP reads the "
                "previous tick's spikes as the presynaptic events)")
        if rewards is None:
            rewards = jnp.zeros((n_ticks,), jnp.float32)
        if plastic_c is None:
            if params.c is None:
                raise ValueError(
                    "learning with c=None (implicit all-to-all) needs an "
                    "explicit plastic_c mask (pass jnp.ones((n, n)) to "
                    "learn every synapse)")
            plastic_c = params.c
        carry0 = TickCarry(state=state, plast=plast_state, w=params.w)
        final, raster = self.scan(params, carry0, ext_seq, n_ticks,
                                  rewards=rewards, plastic_c=plastic_c,
                                  learn_until=learn_until, neighbors=neighbors)
        if self.telemetry:
            return (final.state, final.plast, final.w), raster, final.telem
        return (final.state, final.plast, final.w), raster

    def init_learning_carry(
        self,
        params: SNNParams,
        state: SNNState,
        plast_state: Any,
    ) -> TickCarry:
        """Build the chunk-resumable carry for a fresh learning request.

        Pairs with :meth:`chunk` -- the continuous-serving path builds
        one of these when a slot is (re)filled, then hands it across
        chunk boundaries instead of re-entering :meth:`learning_rollout`
        from scratch every wave."""
        return TickCarry(state=state, plast=plast_state, w=params.w)

    def chunk(
        self,
        params: SNNParams,
        carry: TickCarry,
        ext_seq: Optional[jax.Array],
        n_ticks: int,
        *,
        rewards: Optional[jax.Array] = None,
        plastic_c: Optional[jax.Array] = None,
        learn_until: Optional[jax.Array] = None,
        neighbors: Optional[Any] = None,
    ) -> Tuple[TickCarry, jax.Array]:
        """Run ``n_ticks`` more ticks from an *existing* carry; returns
        ``(next_carry, raster)``.

        This is the continuous-admission hand-off: a serving loop that
        admits per slot (not per wave) runs the fabric in small chunks
        and threads the full :class:`TickCarry` -- state, plasticity
        traces, mutable weights, telemetry, hysteresis bit -- across
        chunk boundaries, so ``K`` chunks of ``T`` ticks are bit-exact
        with one ``K*T``-tick rollout (pinned in
        tests/test_engine_options.py). ``n_ticks`` stays static per
        chunk size, so one compiled chunk program serves every request
        length; the carry is the only thing that moves.

        ``rewards`` defaults to zeros on learning carries (``carry.w``
        present) -- mid-stream R-STDP feedback passes real rewards."""
        if rewards is None and carry.w is not None:
            rewards = jnp.zeros((n_ticks,), jnp.float32)
        if plastic_c is None and carry.w is not None:
            if params.c is None:
                raise ValueError(
                    "learning chunk with c=None needs an explicit "
                    "plastic_c mask (see learning_rollout)")
            plastic_c = params.c
        return self.scan(params, carry, ext_seq, n_ticks,
                         rewards=rewards, plastic_c=plastic_c,
                         learn_until=learn_until, neighbors=neighbors)


def pop_counts(y: jax.Array, pop_starts: jax.Array) -> jax.Array:
    """Spikes of ``y`` per contiguous population."""
    c = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                         jnp.cumsum((y > 0).astype(jnp.int32))])
    return (c[pop_starts[1:]] - c[pop_starts[:-1]]).astype(jnp.float32)


def read_block(csum: jax.Array, reads: jax.Array, block: jax.Array,
               k: int):
    """Block ``block`` of a tick's row reads: reads number ``block * k + 1
    .. (block + 1) * k`` in source order, as ``(src, part, live)`` --
    each read's source, which ``window``-wide part of the source's row it
    is, and which of the ``k`` slots are real.  ``reads`` is each
    neuron's read count this tick (0 if silent) and ``csum`` its cumsum;
    each slot is a binary search of ``csum``, so nothing is truncated."""
    rank = block * k + jnp.arange(1, k + 1, dtype=jnp.int32)
    live = rank <= csum[-1]
    src = jnp.where(live, jnp.searchsorted(csum, rank, side="left"),
                    0).astype(jnp.int32)
    part = jnp.where(live, rank - 1 - (csum[src] - reads[src]), 0)
    return src, part, live


def fan_out_deliver(ring: jax.Array, y: jax.Array, tick: jax.Array,
                    fan_out, k: int) -> Tuple[jax.Array, jax.Array]:
    """Push every spiking source's fan-out into the ``(D, n)`` ring:
    ``ring[(tick + d) % D, target] += w``; returns ``(ring, blocks)``.

    A spiking source's fan-out is read as whole rows of ``window``
    entries (its ``count`` rounded up), and the tick's row reads are
    taken ``k`` at a time: as many blocks as the tick needs (``blocks``,
    a runtime count), so a block gathers ``k`` rows and scatters ``k *
    window`` entries whatever the sources' degrees.  Entries past a
    source's ``count``, and empty slots, are dropped by an out-of-range
    ring row."""
    depth, width = ring.shape[-2], fan_out.window
    reads = jnp.where(y > 0, (fan_out.count + (width - 1)) // width, 0)
    csum = jnp.cumsum(reads)
    blocks = (csum[-1] + (k - 1)) // k
    lane = jnp.arange(width, dtype=jnp.int32)

    def block(b, ring):
        src, part, live = read_block(csum, reads, b, k)
        rows = fan_out.offset[src] + part
        real = live[:, None] & (part[:, None] * width + lane[None, :]
                                < fan_out.count[src][:, None])
        tgt = fan_out.targets[rows]
        w = fan_out.weights[rows]
        d = fan_out.delays[rows].astype(jnp.int32)
        row = jnp.where(real, jnp.mod(tick + d, depth), depth)
        return ring.at[row, tgt].add(w, mode="drop")

    return jax.lax.fori_loop(0, blocks, block, ring), blocks

"""Mesh partitioning of the SNN tick fabric (DESIGN.md §15).

The fabric shards by **destination** (fan-in / column sharding): mesh
shard ``i`` owns postsynaptic columns ``[i*n/D, (i+1)*n/D)`` of the
synapse matrix ``W`` (and ``C``), the matching slices of ``w_in``, the
per-neuron LIF parameters/state, and -- crucially -- the delay rings of
its own neurons.  Each tick, every shard

1. reads the spikes arriving at its local neurons from its local ring,
2. ``all_gather``\\ s them along the mesh axis into the full presynaptic
   spike vector (the ONE collective per tick; ``B*n`` floats, ~n x
   smaller than any weight movement),
3. computes the *complete* fan-in dot ``s_full @ (W*C)[:, local]`` for
   its columns, and
4. steps LIF + writes its local ring.

Because every output column is still reduced over the full presynaptic
axis **on one device, in the same order** as the single-device engine,
the sharded rollout is bit-exact -- unlike row (source) sharding, whose
per-tick ``psum`` would re-associate the f32 fan-in sum.  The scheme is
also exactly what the repo's backends already are: the jnp/event arms
consume a pre-masked ``(n, n_local)`` slab, the event top-k/fan-in
gathers index *rows* of that slab with global presynaptic ids (rows stay
whole under column sharding), and the Pallas fused-LIF kernel is
rectangular in ``(K, N)`` already.

Implementation: :func:`sharded_scan` wraps the UNCHANGED
:meth:`repro.core.engine.TickEngine.scan` in ``shard_map`` -- one
compiled program, the whole tick loop inside, so chunked serving crosses
no host boundary and recompiles exactly as often as the single-device
engine (never, after warmup).  Specs come from the same
:class:`repro.parallel.sharding.AxisRules` machinery the transformer
stack uses, with the SNN logical axes mapped so that only
``neurons_post`` shards.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.engine import TickCarry, TickEngine
from repro.core.network_types import SNNParams, SNNState
from repro.parallel.sharding import AxisRules, BASE_RULES


def shard_map_fn(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` without varying-manual-axes checking.

    The tick scan's carry mixes replicated leaves (telemetry, the tick
    counter) with per-shard ones, and the collectives that keep the
    replicated leaves identical on every shard (the spike all-gather,
    :func:`combine_telemetry`) are ones the checker cannot see through:
    with ``check_vma`` on, the scan rejects a carry whose input is
    replicated and whose output is typed as varying."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def snn_rules(mesh: Optional[Mesh] = None, axis: str = "model") -> AxisRules:
    """The SNN logical->mesh table: destination columns shard, everything
    presynaptic/batch/time replicates.  Built on BASE_RULES so per-run
    overrides compose the same way they do for the transformer cells."""
    mapping = dict(BASE_RULES)
    mapping.update({
        "batch": None,          # one fabric, batch rides along replicated
        "time": None,
        "delay": None,
        "inputs": None,
        "neurons_pre": None,    # full presynaptic axis on every shard
        "neurons_post": axis,   # the ONE sharded dimension
    })
    return AxisRules(mapping, mesh=mesh)


def _vec(rules: AxisRules, a: jax.Array) -> P:
    """(..., n) -> shard the trailing neuron axis, replicate the rest."""
    return rules.spec((None,) * (a.ndim - 1) + ("neurons_post",))


def _mat(rules: AxisRules) -> P:
    return rules.spec(("neurons_pre", "neurons_post"))


def _rep(tree) -> Any:
    return jax.tree.map(lambda _: P(), tree)


def params_specs(rules: AxisRules, params: SNNParams) -> SNNParams:
    """PartitionSpec tree for :class:`SNNParams` (c=None passes through)."""
    return SNNParams(
        w=_mat(rules),
        c=None if params.c is None else _mat(rules),
        w_in=rules.spec(("inputs", "neurons_post")),
        lif=jax.tree.map(lambda a: _vec(rules, a), params.lif),
    )


def state_specs(rules: AxisRules, state: SNNState) -> SNNState:
    return SNNState(
        lif=jax.tree.map(lambda a: _vec(rules, a), state.lif),
        delay_buf=_vec(rules, state.delay_buf),
        tick=P(),
    )


def carry_specs(rules: AxisRules, carry: TickCarry) -> TickCarry:
    """Spec tree for a (seeded) :class:`TickCarry`.

    ``plast.x_pre`` replicates: presynaptic traces are a function of the
    *gathered* full-width spike vector, so every shard computes the
    identical trace array -- no collective needed for plasticity beyond
    the tick's own spike exchange.  Telemetry replicates (local partials
    are combined once per scan by :func:`combine_telemetry`)."""
    plast = None
    if carry.plast is not None:
        plast = dataclasses.replace(
            jax.tree.map(lambda _: P(), carry.plast),
            x_pre=P(),
            x_post=_vec(rules, carry.plast.x_post),
            elig=_mat(rules),
        )
    return TickCarry(
        state=state_specs(rules, carry.state),
        plast=plast,
        w=None if carry.w is None else _mat(rules),
        telem=None if carry.telem is None else _rep(carry.telem),
        policy=None if carry.policy is None else P(),
    )


def neighbors_specs(rules: AxisRules, neighbors: Any) -> Any:
    """Fan-in lists slice by destination ROW (idx entries stay global
    presynaptic ids -- rows of the local ``wc`` slab are the full
    presynaptic axis, so no index translation)."""
    spec = rules.spec(("neurons_post", None))
    return jax.tree.map(lambda _: spec, neighbors)


def combine_telemetry(telem_in, telem_out, axis: str):
    """Fold per-shard telemetry partials into fabric-wide totals (one
    collective bundle per SCAN, not per tick).

    Only the DELTA this scan accumulated is combined: the incoming
    accumulator ``telem_in`` is replicated (it is either the zero seed or
    the already-combined output of the previous chunk), so summing
    ``telem_out`` wholesale would re-``psum`` prior chunks' totals D-fold
    every chunk.  Sums (spikes, dw norms) ``psum`` their delta; the
    mean-based accumulators additionally divide by the axis size because
    each shard normalized by its local ``n/D``; ``v_max`` is a plain
    ``pmax`` (max is idempotent over the replicated prior).
    ``ticks``/``overflow``/``policy_dense`` are computed from replicated
    inputs (tick counter, gathered spikes) and are already identical on
    every shard."""
    d = jax.lax.psum(1, axis)
    dsum = lambda i, o: i + jax.lax.psum(o - i, axis)
    dmean = lambda i, o: i + jax.lax.psum(o - i, axis) / d
    return dataclasses.replace(
        telem_out,
        spikes=dsum(telem_in.spikes, telem_out.spikes),
        v_sum=dmean(telem_in.v_sum, telem_out.v_sum),
        v_max=jax.lax.pmax(telem_out.v_max, axis),
        ref_sum=dmean(telem_in.ref_sum, telem_out.ref_sum),
        dw_l1=dsum(telem_in.dw_l1, telem_out.dw_l1),
        dw_sq=dsum(telem_in.dw_sq, telem_out.dw_sq),
    )


def named_shardings(mesh: Mesh, specs):
    """Spec tree (from the builders above) -> NamedSharding tree.

    ``P`` is a tuple subclass, i.e. itself a pytree -- the ``is_leaf``
    stops the map from descending into it."""
    from jax.sharding import NamedSharding

    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def place(tree, specs, mesh: Mesh):
    """Commit a global pytree onto the mesh per its spec tree.

    Placement OUTSIDE compiled programs (plain ``jax.device_put`` -- the
    analysis gate's purity rule forbids transfers inside the hot loop);
    once the carry is committed, every subsequent ``chunk()`` finds its
    operands already resident and moves nothing."""
    return jax.device_put(tree, named_shardings(mesh, specs))


def make_sharded_dyadic_weights(
    n: int,
    mesh: Optional[Mesh] = None,
    axis: str = "model",
    *,
    seed: int = 0,
    n_blocks: int = 8,
    levels: int = 8,
) -> jax.Array:
    """Dyadic-grid weights materialized shard-local (the 64k-safe path).

    Weights are ``uint8 levels x 2^round(log2(2/sqrt(n)))`` -- the grid on
    which every f32 reduction order is exact (the repo's bitwise-parity
    substrate).  Generation is seeded per COLUMN BLOCK (``n_blocks``
    fixed blocks, independent of the mesh), so the same ``(n, seed)``
    yields the identical global matrix on any mesh size -- D=1 vs D=8
    parity checks compare the same fabric.  With ``mesh`` given, each
    device shard is assembled directly from its covering blocks via
    ``jax.make_array_from_callback``: the full ``(n, n)`` f32 matrix (16
    GiB at 64k) never exists as one host allocation.
    """
    import math

    import numpy as np

    if n % n_blocks:
        raise ValueError(f"n={n} must divide into {n_blocks} gen blocks")
    scale = 2.0 ** round(math.log2(2.0 / math.sqrt(n)))
    bw = n // n_blocks

    def block(b: int) -> np.ndarray:
        rng = np.random.default_rng((seed, b))
        u8 = rng.integers(0, levels, size=(n, bw), dtype=np.uint8)
        return u8.astype(np.float32) * np.float32(scale)

    if mesh is None:
        return jnp.concatenate([block(b) for b in range(n_blocks)], axis=1)
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, P(None, axis))

    def cb(index) -> np.ndarray:
        lo = index[1].start or 0
        hi = index[1].stop if index[1].stop is not None else n
        parts = []
        for b in range(n_blocks):
            blo, bhi = b * bw, (b + 1) * bw
            if bhi <= lo or blo >= hi:
                continue
            parts.append(block(b)[:, max(lo, blo) - blo:min(hi, bhi) - blo])
        return np.concatenate(parts, axis=1)

    return jax.make_array_from_callback((n, n), sharding, cb)


def sharded_scan(
    engine: TickEngine,
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
    """Run :meth:`TickEngine.scan` under ``shard_map`` on ``engine.mesh``.

    The inner engine is the same options with ``mesh=None`` and the
    resolved ``shard_axis`` set -- its tick body all-gathers the arriving
    spikes and otherwise runs unchanged on ``(n, n/D)`` operands, so all
    four backends, plasticity, telemetry and the chunk contract compose
    exactly as on one device (and D=1 is bitwise the single-device
    program)."""
    mesh = engine.mesh
    if mesh is None:
        raise ValueError("sharded_scan needs EngineOptions.mesh set")
    axis = engine.resolved_shard_axis()
    n_dev = mesh.shape[axis]
    n = carry0.state.lif.v.shape[-1]
    if n % n_dev:
        raise ValueError(
            f"n={n} neurons do not split evenly over mesh axis "
            f"{axis!r} of size {n_dev} (pad the fabric or resize the mesh)")
    if delays is not None:
        raise ValueError(
            "per-synapse delay matrices don't compose with the sharded arm "
            "(the delay-plane einsum needs full-width spike history); use "
            "uniform rings (max_delay) or run single-device")
    learning = carry0.w is not None
    if learning and carry0.state.delay_buf.shape[-2] != 1:
        raise ValueError(
            "sharded learning requires max_delay == 1 (pair STDP reads the "
            "previous tick's spikes as the presynaptic events)")

    # Seed telemetry/policy slots on the GLOBAL side so the spec trees
    # below see the final carry structure; the inner scan's own seeding
    # then no-ops.
    carry0 = engine._seed_carry(carry0, neighbors)
    # A 1-device mesh partitions nothing: run the PLAIN engine inside
    # the (trivial) shard_map -- no gather, no pallas_fused remap -- so
    # "sharded at D=1" is the single-device program bit-for-bit, for
    # every backend including the learning megakernel.
    inner = TickEngine(dataclasses.replace(
        engine.options, mesh=None,
        shard_axis=axis if n_dev > 1 else None))

    rules = snn_rules(mesh, axis)
    args: Dict[str, Any] = {
        "params": params, "carry": carry0, "ext": ext_seq,
        "rewards": rewards, "plastic_c": plastic_c,
        "learn_until": learn_until, "neighbors": neighbors,
    }
    in_specs = {
        "params": params_specs(rules, params),
        "carry": carry_specs(rules, carry0),
        "ext": _rep(ext_seq),
        "rewards": _rep(rewards),
        "plastic_c": None if plastic_c is None else _mat(rules),
        "learn_until": _rep(learn_until),
        "neighbors": (None if neighbors is None
                      else neighbors_specs(rules, neighbors)),
    }
    # Raster is (T, *batch, n): shard only the trailing neuron axis.
    raster_spec = P(*([None] * carry0.state.lif.y.ndim), axis)
    out_specs = (carry_specs(rules, carry0), raster_spec)

    def body(a):
        carry, raster = inner.scan(
            a["params"], a["carry"], a["ext"], n_ticks,
            rewards=a["rewards"], plastic_c=a["plastic_c"],
            learn_until=a["learn_until"], neighbors=a["neighbors"])
        # D=1 partitions nothing -- leave the accumulator untouched so
        # the 1-device-mesh program stays bitwise the plain engine.
        if carry.telem is not None and n_dev > 1:
            carry = dataclasses.replace(
                carry,
                telem=combine_telemetry(a["carry"].telem, carry.telem, axis))
        return carry, raster

    return shard_map_fn(body, mesh, (in_specs,), out_specs)(args)

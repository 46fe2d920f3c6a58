#!/usr/bin/env python3
"""Bring-up check: the SNN serving path, run once on a TPU through the
entry points a user calls, and compared with a plain reference.

One process, phases in order, one JSON line each on stdout:

* **A -- the paper's fabric.**  ``--arch snn`` (n_max=74, 8 resident
  tenants of mixed topology, one of them plastic, 4 slots) served by
  :meth:`SNNServer.serve_continuous`, built as the serve CLI builds it.
  The line gives the bytes each continuous program (slot refill, chunk)
  updates in place, ``alias_bytes``; both must be non-zero.  It also gives
  ``slot_slice_bytes``, the bytes of whole ``(n, n)`` matrices the chunk
  program slices out of its slot stacks outside the learning ``cond``;
  on a TPU it must be 0.
* **B -- the full-width dense fabric.**  ``--arch snn-fused`` (n_max=4096,
  ``pallas_fused``) served the same way; sparse tenants ride the second,
  event, program.  ``slot_slice_bytes`` 0 here shows the whole-tick
  kernel reading each slot's ``W`` and ``C`` in place, on its slot grid.
* **C -- the event kernel.**  The ``snn-event`` fabric (n=4096, density
  0.05, input rate 0.05, batch 8) through
  :func:`repro.core.network.rollout` with ``backend="event"``: once with
  ``dispatch="auto"`` (the cost model's pick for this fabric) and once
  with ``dispatch="topk"``, the spike-list strategy the Pallas event
  kernel serves.  Its weights are signed, so the fabric fires near the
  input rate instead of saturating; telemetry counts the ticks whose
  spike lists overflowed ``k_active`` into the dense fallback, and the
  phase fails unless the event kernel served nearly every tick.

Every result is compared with a per-request ``rollout`` on the ``jnp``
backend under ``jax.default_matmul_precision("highest")``.  Frozen
tenants and phase C must agree bit for bit (counts, predictions,
rasters, and phase C's final membrane state): their weights sit on a u8
or dyadic grid, on which every f32 summation order is exact.  The
plastic tenant is held to a tolerance, :data:`PLASTIC_TOL`: its learned
weights leave that grid, so the
server's matmuls (the chip's default precision) and the reference's
(``highest``) round differently, and a rounding that moves a neuron
across threshold shifts a spike, which STDP then feeds back into the
weights.

With ``--four-chip`` the script runs only the sharded fabric on a 4-chip
host: n=16384 destination-sharded over 4 chips vs the single-device
engine on one of them (``jnp`` and ``event``, bit for bit), then
``--arch snn-64k`` for a few chunks, and a check that each chip holds a
quarter of W.

The last stdout line is ``{"ok": true, "device": {...}}`` and appears
only when every phase passed on a TPU; any failure, or a platform other
than TPU, exits non-zero before it.  ``--smoke`` runs the same phases at
the configs' smoke sizes, for a rehearsal on the CPU
(``JAX_PLATFORMS=cpu``); it still prints no result off a TPU.

Usage::

    python chip_smoke.py                 # phases A-C on one chip
    python chip_smoke.py --four-chip     # the sharded fabric, 4 chips
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --smoke [--four-chip]
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_bundle  # noqa: E402
from repro.core import connectivity, dispatch_policy  # noqa: E402
from repro.core.engine import EngineOptions, TickEngine  # noqa: E402
from repro.core.lif import LIFParams  # noqa: E402
from repro.core.network import rollout  # noqa: E402
from repro.core.network_types import SNNParams, SNNState  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.hlo_cost import dynamic_slices, mosaic_kernels  # noqa: E402
from repro.plasticity import PlasticityState  # noqa: E402
from repro.util.env import enable_compilation_cache  # noqa: E402

SEED = 0
# Plastic tenant vs reference (see the module docstring for why a
# tolerance).  Read on a TPU v5 lite at SEED: phase A 0.0 relative L2,
# spike totals equal (297); phase B 5.43e-05 relative L2 (max |dw|
# 0.010), spike totals equal (51,046).  Limits: learned weights within
# 1e-3 relative L2 of the reference (18x B's reading), total output
# spikes within 0.1% + 2 (about 53 spikes in B; 0 read).
PLASTIC_TOL = {"w_rel_l2": 1e-3, "spikes_rel": 1e-3, "spikes_abs": 2}
# Phase C: the event kernel must serve at least this share of the ticks
# (the rest overflow k_active into the dense fallback).
EVENT_ARM_MIN_SHARE = 0.9


class PhaseFailed(AssertionError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


class CompileClock:
    """Sums the backend compile time jax reports while it is armed."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def take(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return round(s, 3)


def _peak_bytes(device=None):
    stats = (device or jax.devices()[0]).memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _emit(line: dict) -> None:
    print(json.dumps(line, default=str), flush=True)


# -- reference: a per-request rollout on the jnp backend ----------------------

@functools.lru_cache(maxsize=None)
def _reference_fn(mode: str, n: int, ticks: int, plasticity=None):
    """Jitted reference for one fabric size: frozen rollout, or (given
    ``plasticity``) learning rollout with the server's STDP rule."""
    eng = TickEngine(EngineOptions(mode=mode, backend="jnp",
                                   plasticity=plasticity))
    if plasticity is None:
        def run(params, ext):
            _, raster = eng.rollout(params, SNNState.zeros((), n), ext, ticks)
            return raster
    else:
        def run(params, ext, plastic_c, until):
            (_, _, w2), raster = eng.learning_rollout(
                params, SNNState.zeros((), n), PlasticityState.zeros((), n),
                ext, ticks, plastic_c=plastic_c, learn_until=until)
            return raster, w2
    return jax.jit(run)


def _request_ext(r, ticks: int, n: int) -> np.ndarray:
    ext = np.zeros((ticks, n), np.float32)
    t = min(r.ext.shape[0], ticks)
    ext[:t, :r.ext.shape[1]] = r.ext[:t]
    return ext


def _served_parity(server, requests, w0_plastic) -> dict:
    """Each served request against its reference; returns the parity
    record (raises on a frozen mismatch or a plastic-tolerance breach)."""
    T, N = server.max_ticks, server.n_max
    mode = server.engine.mode
    frozen_ok, n_frozen = True, 0
    plastic = {}
    by_tenant = {}
    for r in requests:
        by_tenant.setdefault(r.tenant, []).append(r)
    with jax.default_matmul_precision("highest"):
        for name, reqs in by_tenant.items():
            t = server.tenants[name]
            if not t.plastic:
                run = _reference_fn(mode, N, T)
                for r in reqs:
                    budget = min(int(r.n_ticks), T)
                    raster = np.asarray(run(t.params,
                                            _request_ext(r, T, N)))
                    ref = raster[:budget].sum(axis=0)[t.n - t.n_out:t.n]
                    same = (np.array_equal(ref, r.counts)
                            and int(ref.argmax()) == r.pred)
                    frozen_ok &= same
                    n_frozen += 1
                continue
            # Plastic: requests ran one at a time, each from the weights
            # the previous one learned -- chain the reference the same way.
            run = _reference_fn(mode, N, T, server.engine.plasticity)
            params = dataclasses.replace(t.params, w=w0_plastic[name])
            served_spikes = ref_spikes = 0.0
            for r in sorted(reqs, key=lambda q: q.t_done):
                budget = min(int(r.n_ticks), T)
                raster, w2 = run(params, _request_ext(r, T, N), t.plastic_c,
                                 jnp.asarray(budget, jnp.int32))
                ref = np.asarray(raster)[:budget].sum(axis=0)[
                    t.n - t.n_out:t.n]
                ref_spikes += float(ref.sum())
                served_spikes += float(np.asarray(r.counts).sum())
                params = dataclasses.replace(params, w=w2)
            w_ref = np.asarray(params.w)
            w_srv = np.asarray(t.params.w)
            rel = float(np.linalg.norm(w_srv - w_ref)
                        / max(1e-30, np.linalg.norm(w_ref)))
            plastic[name] = {
                "requests": len(reqs),
                "w_rel_l2": rel,
                "w_max_abs_diff": float(np.abs(w_srv - w_ref).max()),
                "weights_moved": bool(not np.array_equal(
                    w_ref, np.asarray(w0_plastic[name]))),
                "spikes_served": served_spikes,
                "spikes_reference": ref_spikes,
                "within_tolerance": bool(
                    rel <= PLASTIC_TOL["w_rel_l2"]
                    and abs(served_spikes - ref_spikes)
                    <= PLASTIC_TOL["spikes_rel"] * ref_spikes
                    + PLASTIC_TOL["spikes_abs"]),
            }
    out = {"frozen_requests": n_frozen, "frozen_bitwise": bool(frozen_ok),
           "plastic": plastic, "plastic_tolerance": PLASTIC_TOL}
    _check(frozen_ok, "a frozen tenant's counts or prediction differ "
                      "from the reference")
    for name, rec in plastic.items():
        _check(rec["within_tolerance"],
               f"plastic tenant {name!r} outside tolerance: {rec}")
    return out


# -- phases A and B: continuous serving ----------------------------------------

def phase_serve(tag: str, arch: str, smoke: bool, clock: CompileClock,
                on_tpu: bool, default_backend: str,
                kernel: Optional[str]) -> dict:
    """Serve ``2 x tenants`` demo requests; ``kernel`` names the Mosaic
    kernel the default program must call on a TPU."""
    bundle = get_bundle(arch)
    cfg = bundle.smoke if smoke else bundle.model
    slots = 4
    t0 = time.perf_counter()
    server, names = serve.make_snn_server(cfg, slots)
    _check(server.backend == default_backend,
           f"{cfg.name} serves on {server.backend!r}, not "
           f"{default_backend!r}")
    reqs = serve.make_demo_requests(server, names, 2 * len(names),
                                    seed=SEED)
    build_s = time.perf_counter() - t0
    w0 = {t.name: t.params.w for t in server.tenants.values() if t.plastic}
    t0 = time.perf_counter()
    stats = server.serve_continuous(reqs)
    serve_s = time.perf_counter() - t0
    compile_s = clock.take()
    backends = dict(stats["backends"])
    _check(stats["requests_served"] == len(reqs),
           f"served {stats['requests_served']} of {len(reqs)} requests")
    _check(stats["recompiles_after_warmup"] == 0,
           f"recompiles_after_warmup={stats['recompiles_after_warmup']}")
    line = {
        "phase": tag, "config": cfg.name, "n_max": server.n_max,
        "slots": slots, "tenants": len(names),
        "default_backend": server.backend, "backends": backends,
        "requests_served": stats["requests_served"],
        "chunks": stats["chunks"], "chunk_ticks": server.chunk_ticks,
        "build_s": round(build_s, 3), "serve_wall_s": round(serve_s, 3),
        "compile_s": compile_s,
        "recompiles_after_warmup": stats["recompiles_after_warmup"],
    }
    line["alias_bytes"] = {
        p: server.program_alias_bytes(default_backend, p)
        for p in ("fill", "chunk")}
    _check(all(line["alias_bytes"].values()),
           f"a continuous program copies its stacked inputs: "
           f"{line['alias_bytes']}")
    # Whole (n, n) matrices sliced out of the slot stacks outside the
    # learning cond: 0 when every kernel reads its slot's operands in place
    # (interpreted kernels slice their blocks, so off the TPU it is not).
    text = server.chunk_program_text(default_backend)
    n = server.n_max
    line["slot_slice_bytes"] = sum(
        b for b, op_name in dynamic_slices(text, (n, n))
        if "/cond/" not in op_name)
    _check(line["slot_slice_bytes"] == 0 or not on_tpu,
           f"the chunk program slices {line['slot_slice_bytes']} bytes of "
           f"whole slot matrices outside the learning cond")
    line["parity"] = _served_parity(server, reqs, w0)
    _check(backends.get(default_backend, 0) > 0,
           f"no request rode the {default_backend!r} program")
    if kernel:
        line["kernels"] = mosaic_kernels(text)
        _check(kernel in line["kernels"] or not on_tpu,
               f"{default_backend} chunk program does not call {kernel}: "
               f"{line['kernels']}")
    line["peak_bytes_in_use"] = _peak_bytes()
    return line


# -- phase C: the event backend through network.rollout ------------------------

def _signed_dyadic_weights(rng, n: int) -> np.ndarray:
    """Signed u8 levels (-129..126, a hair inhibitory) x a power-of-two
    scale near 16/sqrt(n).  At n=4096, density 0.05 and input rate 0.05
    the fabric then fires near the input rate (0.052 at SEED on the
    ``jnp`` backend) instead of saturating, so every spike list fits
    ``k_active``.  Every f32 sum of up to n of them is exact, whatever
    the order."""
    scale = 2.0 ** round(np.log2(16.0 / np.sqrt(n)))
    return (rng.integers(-129, 127, (n, n)) * (2.0 ** -7) * scale).astype(
        np.float32)


def phase_event(smoke: bool, clock: CompileClock, on_tpu: bool) -> dict:
    bundle = get_bundle("snn-event")
    cfg = bundle.smoke if smoke else bundle.model
    n, T, B = cfg.n_neurons, cfg.n_ticks, 8
    rng = np.random.default_rng(SEED)
    c = connectivity.sparse_random(n, cfg.snn_density, seed=SEED)
    params = SNNParams(
        w=jnp.asarray(_signed_dyadic_weights(rng, n)),
        c=jnp.asarray(c, jnp.float32),
        w_in=jnp.eye(n, dtype=jnp.float32),
        lif=LIFParams.make(n, v_th=1.0, leak=0.1, r_ref=1))
    state = SNNState.zeros((B,), n)
    ext = jnp.asarray(rng.random((T, B, n)) < cfg.snn_rate, jnp.float32)

    with jax.default_matmul_precision("highest"):
        ref_final, ref = jax.jit(lambda p, s, e: rollout(
            p, s, e, T, mode=cfg.snn_mode, backend="jnp"))(params, state, ext)
        ref = np.asarray(ref)
    clock.take()
    k_active = dispatch_policy.resolve_k_active(n, None)

    runs = {}
    plan = dispatch_policy.plan(c, w_in=np.eye(n, dtype=np.float32), batch=B)
    for dispatch in ("auto", "topk"):
        t0 = time.perf_counter()
        rec = {"strategy": plan.strategy if dispatch == "auto" else "topk"}
        if dispatch == "auto":
            # The policy plans from the concrete topology, outside jit --
            # exactly what rollout(dispatch="auto") does when called eagerly.
            final, raster = rollout(params, state, ext, T, mode=cfg.snn_mode,
                                    backend="event", dispatch="auto")
        else:
            # Telemetry counts the ticks whose spike lists overflowed
            # k_active and took the dense fallback instead of the kernel.
            fn = jax.jit(lambda p, s, e: rollout(
                p, s, e, T, mode=cfg.snn_mode, backend="event",
                dispatch="topk", telemetry=True))
            compiled = fn.lower(params, state, ext).compile()
            final, raster, tel = compiled(params, state, ext)
            overflow = int(np.asarray(tel.overflow).max())
            rec.update(k_active=k_active, overflow_ticks=overflow,
                       event_arm_ticks=T - overflow,
                       kernels=mosaic_kernels(compiled.as_text()))
        raster = np.asarray(raster)
        rec.update(
            wall_s=round(time.perf_counter() - t0, 3),
            compile_s=clock.take(),
            spikes=float(raster.sum()),
            spike_rate=float(raster.mean()),
            max_spikes_per_row_tick=float(raster.sum(-1).max()),
            raster_bitwise=bool(np.array_equal(raster, ref)),
            counts_bitwise=bool(np.array_equal(raster.sum(0), ref.sum(0))),
            final_v_bitwise=bool(np.array_equal(
                np.asarray(final.lif.v), np.asarray(ref_final.lif.v))))
        if dispatch == "topk":
            _check("event_lif_dispatch_db" in rec["kernels"] or not on_tpu,
                   f"event topk program does not call the event kernel: "
                   f"{rec['kernels']}")
            _check(rec["event_arm_ticks"] >= EVENT_ARM_MIN_SHARE * T,
                   f"the event arm served {rec['event_arm_ticks']} of {T} "
                   f"ticks (k_active={k_active})")
        _check(rec["raster_bitwise"] and rec["final_v_bitwise"],
               f"event dispatch={dispatch} differs from reference: {rec}")
        runs[dispatch] = rec
    return {
        "phase": "C", "config": cfg.name, "n": n, "batch": B, "ticks": T,
        "density": cfg.snn_density, "rate": cfg.snn_rate,
        "reference_spikes": float(ref.sum()), "dispatch": runs,
        "peak_bytes_in_use": _peak_bytes(),
    }


# -- four chips: the sharded fabric --------------------------------------------

def phase_sharded_parity(n: int, d: int, clock: CompileClock) -> dict:
    from repro.launch.mesh import make_snn_mesh
    from repro.parallel import snn_sharding

    ticks, n_in = 8, 256
    mesh = make_snn_mesh(d)
    rng = np.random.default_rng(SEED)
    w = snn_sharding.make_sharded_dyadic_weights(n, mesh)
    w_in = jnp.asarray(rng.integers(0, 8, (n_in, n)).astype(np.float32) * 0.25)
    params = SNNParams(w=w, c=None, w_in=w_in,
                       lif=LIFParams.make(n, v_th=1.0, leak=0.25, r_ref=1))
    rules = snn_sharding.snn_rules(mesh)
    sharded_params = snn_sharding.place(
        params, snn_sharding.params_specs(rules, params), mesh)
    ext = jnp.asarray(rng.random((ticks, n_in)) < 0.05, jnp.float32)
    state = SNNState.zeros((), n)
    dev0 = jax.devices()[0]
    single_params = jax.device_put(params, dev0)

    # Each device holds its quarter of W: shapes from the shards, bytes
    # from each device's allocator.
    w_bytes = n * n * 4
    shards = sorted(((s.device.id, s.data.shape)
                     for s in sharded_params.w.addressable_shards))
    quarter_ok = (len({dev for dev, _ in shards}) == d
                  and all(shape == (n, n // d) for _, shape in shards))
    in_use = {str(dev.id): (dev.memory_stats() or {}).get("bytes_in_use")
              for dev in mesh.devices.flat}
    held_ok = all(b is None or b >= w_bytes // d for b in in_use.values())
    _check(quarter_ok and held_ok,
           f"W is not split in quarters: shards={shards} in_use={in_use}")

    out = {}
    for backend in ("jnp", "event"):
        outs = {}
        for where, p in (("sharded", sharded_params), ("single", single_params)):
            eng = TickEngine(EngineOptions(
                backend=backend, mesh=mesh if where == "sharded" else None))
            t0 = time.perf_counter()
            final, raster = jax.jit(
                lambda p, s, e, eng=eng: eng.rollout(p, s, e, ticks))(
                    p, state, ext)
            raster = np.asarray(raster)
            outs[where] = (final, raster, round(time.perf_counter() - t0, 3),
                           clock.take())
        same = (np.array_equal(outs["sharded"][1], outs["single"][1])
                and all(np.array_equal(np.asarray(a), np.asarray(b))
                        for a, b in zip(jax.tree.leaves(outs["sharded"][0]),
                                        jax.tree.leaves(outs["single"][0]))))
        out[backend] = {"bitwise": bool(same),
                        "spikes": float(outs["single"][1].sum()),
                        "wall_s": {k: v[2] for k, v in outs.items()},
                        "compile_s": {k: v[3] for k, v in outs.items()}}
        _check(same, f"sharded {backend} rollout differs from one device")
    return {"phase": "four_chip/parity", "n": n, "devices": d,
            "w_bytes": w_bytes, "w_shards": [list(s) for _, s in shards],
            "bytes_in_use": in_use, "backends": out,
            "peak_bytes_in_use": {str(dev.id): _peak_bytes(dev)
                                  for dev in mesh.devices.flat}}


def phase_64k(smoke: bool, d: int, clock: CompileClock) -> dict:
    bundle = get_bundle("snn-64k")
    cfg = bundle.smoke if smoke else bundle.model
    cfg = dataclasses.replace(cfg, snn_mesh=d)
    stats = serve.serve_sharded_main(
        cfg, argparse.Namespace(requests=3, metrics_out=None))
    tel = stats["telemetry"]
    _check(stats["recompiles_after_warmup"] == 0, "64k chunk loop recompiled")
    _check(tel["spikes"] == stats["spikes_out"],
           f"telemetry spikes {tel['spikes']} != raster sum "
           f"{stats['spikes_out']}")
    return {"phase": "four_chip/snn-64k", "config": cfg.name,
            "n": stats["n_neurons"], "devices": stats["n_devices"],
            "ticks": stats["ticks"],
            "recompiles_after_warmup": stats["recompiles_after_warmup"],
            "telemetry_spikes": tel["spikes"],
            "raster_spikes": stats["spikes_out"],
            "ticks_per_s": stats["ticks_per_s"], "compile_s": clock.take(),
            "peak_bytes_in_use": {str(dev.id): _peak_bytes(dev)
                                  for dev in jax.devices()[:d]}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded fabric on 4 chips")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke sizes, for a CPU rehearsal")
    args = ap.parse_args(argv)

    enable_compilation_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.smoke:
        print(f"no TPU: jax runs on {dev.platform}", file=sys.stderr)
        return 2
    clock = CompileClock()
    try:
        if args.four_chip:
            d = 4
            _check(len(jax.devices()) >= d,
                   f"--four-chip needs {d} devices, jax sees "
                   f"{len(jax.devices())}")
            _emit(phase_sharded_parity(1024 if args.smoke else 16384, d,
                                       clock))
            _emit(phase_64k(args.smoke, d, clock))
        else:
            _emit(phase_serve("A", "snn", args.smoke, clock, on_tpu,
                              "jnp", kernel=None))
            _emit(phase_serve("B", "snn-fused", args.smoke, clock, on_tpu,
                              "pallas_fused", kernel="fused_tick"))
            _emit(phase_event(args.smoke, clock, on_tpu))
    except PhaseFailed as e:
        print(f"phase failed: {e}", file=sys.stderr)
        return 1
    if not on_tpu:
        print(f"rehearsal passed on {dev.platform}; no TPU, no result",
              file=sys.stderr)
        return 2
    _emit({"ok": True, "device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Runs the Potjans-Diesmann microcircuit cell: one resident fabric,
served as a stream of fixed-length requests.

Set-up draws the synapse list from the seed (the bench's generator,
:mod:`bench.reference_microcircuit`), has the program build its resident
fan-out from it block by block, and runs the start transient
(``warm_requests`` requests) through the same compiled request step.
One client then sends requests of ``ticks`` ticks, driven only by the
on-device Poisson background, and reads back each one's 8 population
spike counts before sending the next; the state is carried throughout.

Before the window the run draws ``compared_requests`` request indices
from the seed, and keeps a copy of the state before and after each.
After the window, with the program's arrays freed, the plain reference
replays each of them from its copied state: the population counts must
agree, and so must the final current, ring and membrane, bit for bit.
"""
from __future__ import annotations

import gc
import glob
import os
import time
from typing import Dict, List, Optional

import numpy as np

from bench import reference_microcircuit as ref
from bench import traffic, work_microcircuit as work
from bench.fabric import host_rng
from bench.harness import check

DELIVERY_SCOPE = "fan_out/deliver"


def _program(cfg: Dict, seed: int, ticks: int):
    """The program's fabric, its first carry and the jitted request step."""
    import jax
    import jax.numpy as jnp

    from repro.configs.pd_microcircuit import Microcircuit
    from repro.core.engine import TickCarry, TickEngine
    from repro.obs import metrics
    from repro.obs.telemetry import TickTelemetry

    mc = Microcircuit(scale=float(cfg.get("scale", 1.0)))
    net = ref.network(cfg)
    if mc.n != net["n"] or list(mc.pop_starts) != list(net["starts"]):
        raise RuntimeError("the program's microcircuit and the config differ")
    t0 = time.time()
    deg = ref.out_degrees(net, seed)
    q = jnp.float32(net["q"])
    blocks = ((s, t, lv.astype(jnp.float32) * q, d) for s, t, lv, d in
              ref.synapse_blocks(net, seed, deg, int(cfg["synapse_block"])))
    fo = mc.fan_out(deg.sum(axis=0), blocks, int(cfg["fanout_window"]))
    jax.block_until_ready(fo)
    build_s = time.time() - t0
    entries, padded, pad_frac = fo.stats()
    registry = metrics.MetricsRegistry()
    metrics.record_fan_out(registry, entries=entries,
                           padding_fraction=pad_frac)
    params = mc.params(ref.poisson_key(seed))
    engine = TickEngine(mc.engine_options(k=int(cfg["read_block"])))
    carry = TickCarry(state=mc.initial_state(ref.v0_key(seed)),
                      telem=TickTelemetry.zeros((), n_pops=fo.n_pops))
    starts = jnp.asarray(mc.pop_starts, jnp.int32)

    @jax.jit
    def step(params, carry, fo):
        carry, raster = engine.chunk(params, carry, None, ticks,
                                     neighbors=fo)
        c = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(
            raster.sum(axis=0).astype(jnp.int32))])
        return carry, c[starts[1:]] - c[starts[:-1]]

    info = {"build_s": build_s, "fanout_entries": entries,
            "fanout_padded_entries": padded,
            "fanout_padding_fraction": pad_frac, "fanout_window": fo.window,
            "fanout_bytes": int(fo.targets.nbytes + fo.weights.nbytes
                                + fo.delays.nbytes)}
    return params, fo, carry, step, registry, info


def _snapshot(carry) -> Dict[str, np.ndarray]:
    st = carry.state
    return {"v": np.asarray(st.lif.v), "i": np.asarray(st.lif.i),
            "r": np.asarray(st.lif.r), "ring": np.asarray(st.delay_buf),
            "tick": int(st.tick)}


def _telem(carry) -> Dict:
    t = carry.telem
    return {"syn_events": float(t.syn_events),
            "spill_blocks": int(t.spill_blocks),
            "pop_spikes": np.asarray(t.pop_spikes, np.float64),
            "ticks": int(t.ticks)}


def run(ctx) -> Dict:
    import jax

    from repro.obs import metrics

    cfg, tr, seed = ctx.config, ctx.traffic, ctx.seed
    T = int(tr["ticks"])
    params, fo, carry, step, registry, info = _program(cfg, seed, T)
    n = fo.n

    def serve(carry):
        with ctx.spans("bench/issue"):
            carry, cnt = step(params, carry, fo)
            # the counts travel as soon as the request ends, not when the
            # host gets round to asking: the host's share of each round
            # trip (and its noise) stays small
            cnt.copy_to_host_async()
        with ctx.spans("bench/readback"):
            return carry, np.asarray(cnt)

    t_warm = []
    for _ in range(int(tr["warm_requests"])):
        t = time.time()
        carry, _ = serve(carry)
        t_warm.append(time.time() - t)
    tel0 = _telem(jax.block_until_ready(carry))

    # The requests compared with the reference: drawn before the window,
    # over the part of it the warm-up's pace says it will reach.
    reach = max(int(cfg["compared_requests"]),
                int(0.8 * ctx.seconds / max(1e-6, float(np.median(
                    t_warm[-3:])))))
    picks = set(host_rng(seed, 5).choice(
        reach, int(cfg["compared_requests"]), replace=False).tolist())

    # no collector pauses inside the window: what is alive now is kept
    # out of every collection, and the window's garbage waits for its end
    gc.collect()
    gc.freeze()
    gc.disable()
    compiles0 = ctx.clock.count
    t0 = time.time()
    setup_s = t0 - ctx.t_start
    t_end = t0 + ctx.seconds
    lead = 0.3 * ctx.seconds
    trace_at = (t0 + lead, t0 + lead + min(4.0, 0.4 * ctx.seconds))
    trace_ctx, traced = None, None
    stamps: List[tuple] = []
    counts: List[np.ndarray] = []
    snaps: Dict[int, tuple] = {}
    before, i = None, 0
    while True:
        now = time.time()
        if ctx.trace and trace_ctx is None and traced is None and \
                now >= trace_at[0]:
            from bench import trace

            traced = {"start": _telem(carry), "i0": i}
            trace_ctx = trace.capture(ctx.trace_dir, ctx.spans)
            trace_ctx.__enter__()
            # the profiler takes seconds to start: time the span from here
            trace_at = (trace_at[0], time.time() + trace_at[1] - trace_at[0])
        if trace_ctx is not None and now >= trace_at[1]:
            trace_ctx.__exit__(None, None, None)
            trace_ctx = None
            traced.update(end=_telem(carry), i1=i)
        if now >= t_end:
            break
        before = carry          # arrays are immutable: keeping it is a copy
        carry, cnt = serve(carry)
        stamps.append((now, time.time()))
        counts.append(cnt)
        if i in picks:
            snaps[i] = (before, carry)
        i += 1
    if trace_ctx is not None:
        trace_ctx.__exit__(None, None, None)
        traced.update(end=_telem(carry), i1=i)
    gc.enable()
    gc.unfreeze()
    compiles_in_window = ctx.clock.count - compiles0
    peak = ctx.peak_bytes()
    tel1 = _telem(carry)
    metrics.record_fan_out(registry, syn_events=tel1["syn_events"])

    in_window = [k for k, (_, d) in enumerate(stamps) if d <= t_end]
    lat_ms = [(d - s) * 1e3 for s, d in stamps]
    ticks_done = T * len(in_window)
    win_ticks = tel1["ticks"] - tel0["ticks"]
    win_events = tel1["syn_events"] - tel0["syn_events"]
    dt_s = float(cfg["dt_ms"]) * 1e-3
    sizes = ref.network(cfg)["sizes"]
    rates = ((tel1["pop_spikes"] - tel0["pop_spikes"])
             / (sizes * max(1, win_ticks) * dt_s))
    out = {
        "attempted": len(stamps),
        "failed": 0,
        "end_to_end": {"goodput": ticks_done / ctx.seconds,
                       "latency_p95_ms": traffic.percentile(lat_ms, 95),
                       "setup_s": setup_s},
        "memory_peak_bytes": peak,
        "info": dict(info, completed_in_window=len(in_window),
                     latency_p50_ms=traffic.percentile(lat_ms, 50),
                     latency_mean_ms=float(np.mean(lat_ms)) if lat_ms
                     else None,
                     latency_max_ms=max(lat_ms, default=None),
                     compiles_in_window=compiles_in_window,
                     syn_events=tel1["syn_events"],
                     spill_blocks=tel1["spill_blocks"],
                     window_syn_events=win_events,
                     window_spill_blocks=tel1["spill_blocks"]
                     - tel0["spill_blocks"],
                     window_ticks=win_ticks,
                     pop_rates_hz=[float(r) for r in rates],
                     pop_rates_over_published=[
                         float(r / p) for r, p in zip(
                             rates, cfg["published_rates_hz"])],
                     warm_request_s=[float(t) for t in t_warm],
                     metrics=registry.to_dict()),
        "layer": {"window_s": ctx.seconds, "chips": 1,
                  "model_flops": work.step_flops(win_events, n * win_ticks),
                  "entry_bytes": cfg["entry_bytes"]},
    }
    if traced and "end" in traced:
        found = delivery_trace(ctx.trace_dir) or {}
        out["layer"].update(
            traced_ticks=traced["end"]["ticks"] - traced["start"]["ticks"],
            traced_events=traced["end"]["syn_events"]
            - traced["start"]["syn_events"],
            delivery_s=found.get("seconds"),
            delivery_ticks=found.get("ticks"))

    snaps = {k: (_snapshot(a), _snapshot(b)) for k, (a, b) in snaps.items()}
    del params, carry, before, step, fo
    gc.collect()
    t_ref = time.time()
    out["replay"] = {"snaps": snaps, "counts": {k: counts[k] for k in snaps}}
    out["checks"] = compare(cfg, seed, T, out["replay"])
    out["info"]["reference_s"] = time.time() - t_ref
    out["info"]["compared"] = sorted(snaps)
    return out


def compare(cfg: Dict, seed: int, ticks: int, replay: Dict, *,
            dtype=None, delay_one: bool = False) -> Dict:
    """Each compared request replayed by the plain reference from the
    program's state before it:

    * ``count_mismatch``: requests whose 8 population spike counts differ;
    * ``state_mismatch``: requests whose final synaptic current or delay
      ring differ in any bit (exact: every ring sum is a sum of grid
      weights, and the current takes the same float32 operations);
    * ``v_mismatch``: requests whose final membrane differs in any bit.
    """
    import jax.numpy as jnp

    net = ref.network(cfg)
    syn = ref.Synapses(net, seed, int(cfg["synapse_block"]),
                       delay_one=delay_one)
    bad = {"count_mismatch": 0, "state_mismatch": 0, "v_mismatch": 0}
    for k, (before, after) in sorted(replay["snaps"].items()):
        final, pops = ref.replay(net, syn, seed, before, ticks,
                                 dtype=dtype or jnp.float32)
        f = {key: np.asarray(v) for key, v in final.items()}
        bad["count_mismatch"] += int(not np.array_equal(
            np.asarray(pops), replay["counts"][k]))
        same_i = _bits_equal(f["i"], after["i"])
        bad["state_mismatch"] += int(
            not (same_i and np.array_equal(f["ring"], after["ring"])))
        bad["v_mismatch"] += int(not _bits_equal(f["v"], after["v"]))
    del syn
    gc.collect()
    limits = cfg["limits"]
    return {name: check(v, limits[name]) for name, v in bad.items()}


def _bits_equal(a, b) -> bool:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return a.shape == b.shape and bool(np.all(a.view(np.uint32)
                                              == b.view(np.uint32)))


def control_checks(ctx, res, variant: str = "bfloat16") -> Dict:
    """The comparison with a control in the program's place, over the
    same requests: the plain reference in bfloat16 (the step below the
    configuration's float32), or with every delay set to one tick."""
    import jax.numpy as jnp

    T = int(ctx.traffic["ticks"])
    if variant == "bfloat16":
        ctl = compare(ctx.config, ctx.seed, T, res["replay"],
                      dtype=jnp.bfloat16)
    elif variant == "delay_one":
        ctl = compare(ctx.config, ctx.seed, T, res["replay"], delay_one=True)
    else:
        raise ValueError(f"unknown control {variant!r}")
    return ctl


# -- the delivery's device time, from the raw trace ---------------------------------

def delivery_trace(logdir: str) -> Optional[Dict[str, float]]:
    """:func:`delivery_loops` of the first chip's ops in the trace under
    ``logdir``, on the chip's own timeline."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        return None
    pd = ProfileData.from_file(paths[-1])
    planes = sorted(p.name for p in pd.planes
                    if p.name.startswith("/device:TPU:")
                    and "NON_CORE" not in p.name)
    ops = [(e.name, e.start_ns, e.duration_ns)
           for p in pd.planes if planes and p.name == planes[0]
           for line in p.lines if line.name == "XLA Ops"
           for e in line.events]
    return delivery_loops(ops)


def delivery_loops(ops) -> Optional[Dict[str, float]]:
    """Device seconds of the fan-out delivery, and the ticks they cover,
    from a device's op events ``(HLO text, start_ns, duration_ns)``.

    The delivery runs as one ``while`` per tick (its blocks of reads),
    one instruction of the request program.  Where the events name the
    ``tick/event/fan_out/deliver`` scope it is the longest-running
    ``while`` that does; where they carry no scopes, it is the ``while``
    that most of the program's custom fusions (the ring's scatter-add:
    the program has no kernel) sit directly inside."""
    import bisect
    import collections

    from bench import trace

    loops, custom = [], []
    for text, start, dur in ops:
        rec = trace.op_record(text, start, dur)
        if rec[0].startswith("while"):
            loops.append((start, start + dur, rec[0], DELIVERY_SCOPE in text))
        elif rec[3] == "mosaic":
            custom.append((start, start + dur))
    loops.sort()
    weight = collections.Counter()
    if any(w[3] for w in loops):
        for a, b, name, scoped in loops:
            weight[name] += (b - a) if scoped else 0
    else:
        starts = [w[0] for w in loops]
        for a, b in custom:
            j = bisect.bisect_right(starts, a) - 1
            while j >= 0 and not (loops[j][0] <= a and b <= loops[j][1]):
                j -= 1
            if j >= 0:
                weight[loops[j][2]] += b - a
    if not weight:
        return None
    name = weight.most_common(1)[0][0]
    mine = [(a, b) for a, b, nm, _ in loops if nm == name]
    return {"seconds": sum(b - a for a, b in mine) * 1e-9,
            "ticks": len(mine)}

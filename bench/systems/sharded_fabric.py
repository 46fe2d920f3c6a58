"""Runs a cell of one large fabric sharded over a mesh, served as a stream.

The fabric's ``(n, n)`` weights are split by destination column over the
cell's chips (``EngineOptions.mesh``); one session's state is carried from
request to request through the jitted ``TickEngine.chunk``, with the
per-tick spike all-gather inside.  One client sends a request (``ticks``
ticks of input), reads back its output counts, and sends the next.

Once the window has closed and the program's arrays are gone, the plain
reference replays the session (:func:`bench.reference.stream_ticks`,
integer levels, column-sharded by the compiler rather than by the
program) and every request's counts must match bit for bit.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from bench import fabric, traffic
from bench.harness import check

BATCH = 32          # requests per reference call
WARM = 1 << 30      # input index of the first warm-up request


def _mesh(cfg):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_snn_mesh

    mesh = make_snn_mesh(cfg["chips"])
    return mesh, NamedSharding(mesh, P(None, "model"))


def build(cfg: Dict, seed: int):
    """The program's sharded fabric and its jitted request step."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import EngineOptions, TickCarry, TickEngine
    from repro.core.lif import LIFParams
    from repro.core.network_types import SNNParams, SNNState
    from repro.obs.telemetry import TickTelemetry
    from repro.parallel import snn_sharding

    n, n_in, n_out, T = cfg["n"], cfg["n_in"], cfg["n_out"], cfg["ticks"]
    mesh, cols = _mesh(cfg)
    key = fabric.seed_key(seed)
    s_w, s_in = fabric.stream_scales(n, n_in)
    w_lv, win_lv = fabric.stream_levels(key, n, n_in, cols)
    scale = jax.jit(lambda lv, s: lv.astype(jnp.float32) * s,
                    out_shardings=cols)
    w, w_in = scale(w_lv, s_w), scale(win_lv, s_in)
    del w_lv, win_lv
    lp = fabric.stream_lif(key, n)
    ones = jnp.ones((n,), jnp.float32)
    params = SNNParams(w=w, c=None, w_in=w_in, lif=LIFParams(
        v_th=lp["v_th"], leak=lp["leak"], r_ref=lp["r_ref"], gain=ones,
        i_bias=0 * ones, v_reset=0 * ones))
    rules = snn_sharding.snn_rules(mesh)
    params = snn_sharding.place(
        params, snn_sharding.params_specs(rules, params), mesh)
    engine = TickEngine(EngineOptions(
        mode=cfg["mode"], backend=cfg["backend"], telemetry=True, mesh=mesh))

    def fresh():
        carry = TickCarry(state=SNNState.zeros((), n),
                          telem=TickTelemetry.zeros(()))
        return snn_sharding.place(
            carry, snn_sharding.carry_specs(rules, carry), mesh)

    @jax.jit
    def step(params, carry, ext):
        carry, raster = engine.chunk(params, carry, ext, T)
        return carry, raster[:, n - n_out:].sum(axis=0)

    return params, fresh, step, mesh


def run(ctx) -> Dict:
    import jax
    import jax.numpy as jnp

    cfg = dict(ctx.config, ticks=ctx.traffic["ticks"])
    tr, seed = ctx.traffic, ctx.seed
    n, n_in, T = cfg["n"], cfg["n_in"], cfg["ticks"]
    params, fresh, step, mesh = build(cfg, seed)
    stamps: List[tuple] = []          # (t_issue, t_done) per request
    counts: List[np.ndarray] = []

    def serve(carry, i):
        with ctx.spans("bench/issue"):
            ext = jnp.asarray(traffic.stream_input(tr, seed, i, n_in))
            carry, cnt = step(params, carry, ext)
        with ctx.spans("bench/readback"):
            return carry, np.asarray(cnt)

    carry = fresh()
    for i in range(tr["warm_requests"]):
        carry, _ = serve(carry, WARM + i)
    jax.block_until_ready(carry)

    compiles0 = ctx.clock.count
    t0 = time.time()
    setup_s = t0 - ctx.t_start
    t_end = t0 + ctx.seconds
    lead = 0.3 * ctx.seconds
    trace_at = (t0 + lead, t0 + lead + min(4.0, 0.4 * ctx.seconds))
    trace_ctx, traced = None, None
    carry = fresh()
    i = 0
    while True:
        now = time.time()
        if ctx.trace and trace_ctx is None and traced is None and \
                now >= trace_at[0]:
            from bench import trace

            trace_ctx = trace.capture(ctx.trace_dir, ctx.spans)
            trace_ctx.__enter__()
            traced = [now, None, i]
        if trace_ctx is not None and now >= trace_at[1]:
            trace_ctx.__exit__(None, None, None)
            trace_ctx, traced[1] = None, i
        if now >= t_end:
            break
        carry, cnt = serve(carry, i)
        stamps.append((now, time.time()))
        counts.append(cnt)
        i += 1
    if trace_ctx is not None:
        trace_ctx.__exit__(None, None, None)
        traced[1] = i
    compiles_in_window = ctx.clock.count - compiles0
    peak = ctx.peak_bytes()

    in_window = [k for k, (_, d) in enumerate(stamps) if d <= t_end]
    lat_ms = [(d - s) * 1e3 for s, d in stamps]
    ticks_done = T * len(in_window)
    out = {
        "attempted": len(stamps),
        "failed": 0,
        "end_to_end": {"goodput": ticks_done / ctx.seconds,
                       "latency_p95_ms": traffic.percentile(lat_ms, 95),
                       "setup_s": setup_s},
        "memory_peak_bytes": peak,
        "info": {"completed_in_window": len(in_window),
                 "latency_p50_ms": traffic.percentile(lat_ms, 50),
                 "compiles_in_window": compiles_in_window,
                 "spike_rate_out": float(np.mean(counts)) / T
                 if counts else None,
                 "telemetry": carry.telem.summary(n)},
        "layer": {"window_s": ctx.seconds, "synops": ticks_done * n * n,
                  "chunk_ticks": T},
    }
    if traced and traced[1] is not None:
        out["layer"]["traced_ticks"] = T * (traced[1] - traced[2])

    del params, carry, step
    gc.collect()
    t_ref = time.time()
    ref = reference_counts(cfg, seed, tr, len(counts))
    out["checks"] = compare(cfg["limits"], counts, ref)
    out["info"]["reference_s"] = time.time() - t_ref
    out["info"]["compared"] = len(counts)
    out["replay"] = counts
    return out


def reference_counts(cfg, seed, tr, n_req, dtype=None) -> np.ndarray:
    """The session replayed by the plain reference: (n_req, n_out)."""
    import jax.numpy as jnp

    from bench import reference

    n, n_in, n_out, T = cfg["n"], cfg["n_in"], cfg["n_out"], cfg["ticks"]
    _, cols = _mesh(cfg)
    from jax.sharding import NamedSharding, PartitionSpec as P

    vec = NamedSharding(cols.mesh, P("model"))
    key = fabric.seed_key(seed)
    w_lv, win_lv = fabric.stream_levels(key, n, n_in, cols)
    lifp = fabric.stream_lif(key, n)
    scales = fabric.stream_scales(n, n_in)
    state = reference.stream_state(n, dtype or jnp.float32, vec)
    out = []
    for lo in range(0, n_req, BATCH):
        ext = np.zeros((BATCH * T, n_in), np.float32)
        for k in range(min(BATCH, n_req - lo)):
            ext[k * T:(k + 1) * T] = traffic.stream_input(tr, seed, lo + k,
                                                          n_in)
        state, cnt = reference.stream_counts(
            state, w_lv, win_lv, lifp, scales, jnp.asarray(ext), T, n_out,
            dtype=dtype)
        out.append(np.asarray(cnt)[:min(BATCH, n_req - lo)])
    return np.concatenate(out) if out else np.zeros((0, n_out), np.float32)


def compare(limits, counts: List[np.ndarray], ref: np.ndarray) -> Dict:
    """``count_mismatch``: requests whose output counts differ from the
    reference's (exact: every weight is a power of two times an integer
    level, so the session has one right answer)."""
    bad = sum(1 for got, want in zip(counts, ref)
              if not np.array_equal(got, want))
    return {"count_mismatch": check(bad, limits["count_mismatch"])}


def control_checks(ctx, res) -> Dict:
    """The comparison with the control in the program's place: the plain
    reference computed in bfloat16 (the step below the configuration's
    float32), over the same requests as the run."""
    import jax.numpy as jnp

    cfg = dict(ctx.config, ticks=ctx.traffic["ticks"])
    n_req = len(res["replay"])
    ref = reference_counts(cfg, ctx.seed, ctx.traffic, n_req)
    ctl = reference_counts(cfg, ctx.seed, ctx.traffic, n_req,
                           dtype=jnp.bfloat16)
    return compare(cfg["limits"], list(ctl), ref)

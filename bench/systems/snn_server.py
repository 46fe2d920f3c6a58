"""Runs a cell of a multi-tenant fabric served by ``SNNServer.serve_continuous``.

The program under test is driven through the hooks its async front-end
uses: a ``feeder`` polled once per chunk for arriving requests and an
``on_complete`` callback per finished request.  The benchmark owns the
clients (closed loop) or the arrival schedule (open loop), the tenants'
registers (made on the device from the seed) and the clock.

Once the window has closed and the server is gone, every request due in
the window on a frozen tenant is replayed by :mod:`bench.reference` and
must match bit for bit, and the plastic tenant's whole chain of requests
(warm-up and drain included, in the order they ran) must end within the
configured distance of the reference's learned weights.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from bench import fabric, traffic, work
from bench.harness import check


@dataclasses.dataclass
class Rec:
    """One request the benchmark sent, and what came back."""

    rid: int
    tenant: str
    budget: int
    ext: np.ndarray
    due: float
    phase: str                      # warm | window
    done: Optional[float] = None
    counts: Optional[np.ndarray] = None
    pred: Optional[int] = None


def plasticity_rule(cfg: Dict) -> tuple:
    """(a_plus, a_minus, d_pre, d_post, w_min, w_max) in weight units: the
    configuration states the rule in u8 levels of the plastic tenant."""
    p = cfg["plasticity"]
    plastic = [t for t in cfg["tenants"] if t.get("plastic")]
    if len(plastic) != 1:
        raise ValueError("the fabric holds exactly one plastic tenant")
    unit = fabric.LEVEL * fabric.weight_scale(plastic[0])
    return (p["a_plus_levels"] * unit, p["a_minus_levels"] * unit,
            math.exp(-1.0 / p["tau_pre"]), math.exp(-1.0 / p["tau_post"]),
            p["w_min_levels"] * unit, p["w_max_levels"] * unit)


def build_server(cfg: Dict, seed: int):
    import jax.numpy as jnp

    from repro.core.lif import LIFParams
    from repro.core.network_types import SNNParams
    from repro.launch.serve import SNNServer
    from repro.plasticity import PlasticityParams

    a_plus, a_minus, d_pre, d_post, w_min, w_max = plasticity_rule(cfg)
    server = SNNServer(
        n_max=cfg["n_max"], slots=cfg["slots"], max_ticks=cfg["max_ticks"],
        mode=cfg["mode"], backend=cfg["backend"],
        event_density=cfg["event_density"], event_cap=cfg["event_cap"],
        chunk_ticks=cfg["chunk_ticks"],
        plasticity=PlasticityParams(
            rule=cfg["plasticity"]["rule"], a_plus=a_plus, a_minus=a_minus,
            decay_pre=d_pre, decay_post=d_post, w_min=w_min, w_max=w_max))
    regs = fabric.build_tenants(seed, cfg["tenants"])
    for spec in cfg["tenants"]:
        r = regs.pop(spec["name"])
        n = spec["n"]
        lif = LIFParams(v_th=r["v_th"], leak=r["leak"], r_ref=r["r_ref"],
                        gain=jnp.ones((n,), jnp.float32),
                        i_bias=jnp.zeros((n,), jnp.float32),
                        v_reset=jnp.zeros((n,), jnp.float32))
        n_in, n_out = fabric.tenant_io(spec)
        server.add_tenant_params(
            spec["name"], SNNParams(w=r["w"], c=r["c"], w_in=r["w_in"],
                                    lif=lif),
            n_in=n_in, n_out=n_out, plastic=bool(spec.get("plastic")))
    return server


class Loop:
    """The clients and the window around one server."""

    def __init__(self, ctx, server, draws):
        from repro.launch.serve import ServeRequest

        self.ctx, self.server, self.draws = ctx, server, draws
        self.ServeRequest = ServeRequest
        self.recs: Dict[int, Rec] = {}
        self.order: List[int] = []          # completion order
        self.queue: deque = deque()
        self.next_draw = 0
        self.phase = "warm"
        self.t_end = math.inf
        self.snaps: Dict[str, Dict] = {}
        self.trace_ctx = None
        self.trace_at: Optional[tuple] = None

    # -- requests -----------------------------------------------------------
    def make(self, due: float):
        d = self.draws[self.next_draw % len(self.draws)]
        self.next_draw += 1
        rid = len(self.recs)
        self.recs[rid] = Rec(rid, d.tenant, d.budget, d.ext, due, self.phase)
        r = self.ServeRequest(rid=rid, tenant=d.tenant, ext=d.ext,
                              n_ticks=d.budget)
        r.t_submit = due
        return r

    def on_complete(self, r):
        with self.ctx.spans("bench/on_complete"):
            rec = self.recs[r.rid]
            rec.done, rec.counts, rec.pred = r.t_done, r.counts, r.pred
            self.order.append(r.rid)
            self.tick()
            if self.closed and r.t_done < self.t_end:
                self.queue.append(self.make(time.time()))

    def feeder(self):
        with self.ctx.spans("bench/feeder"):
            self.tick()
            if self.queue:
                return self.queue.popleft()
            if self.schedule is not None and self.sched_i < len(
                    self.schedule) and self.schedule[self.sched_i] <= \
                    time.time():
                due = self.schedule[self.sched_i]
                self.sched_i += 1
                return self.make(due)
            return None

    # -- counters, trace and the end of the window ------------------------------
    def counters(self) -> Dict:
        reg = self.server.registry
        chunks = reg.get("snn_chunks_total")
        return {
            "t": time.time(),
            "useful": reg.get("snn_useful_slot_ticks_total").value(),
            "slot_ticks": reg.get("snn_slot_ticks_total").value(),
            "chunks": {b: chunks.value(backend=b)
                       for b in ("pallas_fused", "jnp", "pallas", "event")},
        }

    def snapshot(self, key: str) -> None:
        self.snaps[key] = self.counters()

    def tick(self) -> None:
        """Start and stop the trace, and mark the end of the window.  The
        trace stops once its time is up and at least two chunks were
        dispatched inside it, so a slow program still shows whole ones."""
        now = time.time()
        if self.trace_at and self.trace_ctx is None and \
                "trace_stop" not in self.snaps and now >= self.trace_at[0]:
            from bench import trace

            self.trace_ctx = trace.capture(self.ctx.trace_dir, self.ctx.spans)
            self.trace_ctx.__enter__()
            self.snapshot("trace_start")
        elif self.trace_ctx is not None and now >= self.trace_at[1] and \
                sum(self.counters()["chunks"].values()) - sum(
                    self.snaps["trace_start"]["chunks"].values()) >= 2:
            self.snapshot("trace_stop")
            self.trace_ctx.__exit__(None, None, None)
            self.trace_ctx = None
        if now >= self.t_end and "end" not in self.snaps:
            self.snapshot("end")

    # -- phases ----------------------------------------------------------------
    def warm(self, n: int) -> None:
        """Serve ``n`` requests of the traffic (every program, tenant and
        slot the window will touch) before the clock starts."""
        self.closed, self.schedule = False, None
        reqs = [self.make(time.time()) for _ in range(n)]
        self.server.serve_continuous(reqs, on_complete=self.on_complete)

    def window(self, tr: Dict, seconds: float, t0: float) -> None:
        self.phase, self.t_end = "window", t0 + seconds
        self.snapshot("start")
        if self.ctx.trace:
            lead = 0.3 * seconds
            self.trace_at = (t0 + lead, t0 + lead + min(4.0, 0.4 * seconds))
        self.closed = tr["loop"] == "closed"
        self.schedule, self.sched_i = None, 0
        if self.closed:
            for _ in range(tr["clients"]):
                self.queue.append(self.make(t0))
            with self.ctx.spans("bench/serve_continuous"):
                self.server.serve_continuous(
                    feeder=self.feeder, on_complete=self.on_complete)
        else:
            self.schedule = list(t0 + traffic.arrivals(tr, self.ctx.seed,
                                                       seconds))
            self.late = []
            while self.sched_i < len(self.schedule):
                wait = self.schedule[self.sched_i] - time.time()
                if wait > 0:
                    with self.ctx.spans("bench/idle_wait"):
                        time.sleep(wait)
                self.late.append(time.time() - self.schedule[self.sched_i])
                with self.ctx.spans("bench/serve_continuous"):
                    self.server.serve_continuous(
                        feeder=self.feeder, on_complete=self.on_complete)
        while time.time() < self.t_end:     # a window that ended early
            self.tick()
            time.sleep(0.01)
        self.tick()
        if self.trace_ctx is not None:
            self.snapshot("trace_stop")
            self.trace_ctx.__exit__(None, None, None)
            self.trace_ctx = None


def _delta(a: Dict, b: Dict) -> Dict:
    return {"seconds": b["t"] - a["t"],
            "useful": b["useful"] - a["useful"],
            "slot_ticks": b["slot_ticks"] - a["slot_ticks"],
            "chunks": {k: b["chunks"][k] - a["chunks"][k]
                       for k in a["chunks"]}}


def run(ctx) -> Dict:
    """One run of a cell; returns what :mod:`bench.run` prints."""
    import jax

    cfg, tr = ctx.config, ctx.traffic
    specs = {t["name"]: t for t in cfg["tenants"]}
    phases = {"start": time.time() - ctx.t_start}
    server = build_server(cfg, ctx.seed)
    backend_of = {n: t.backend for n, t in server.tenants.items()}
    phases["tenants"] = time.time() - ctx.t_start
    draws = traffic.pool(tr, ctx.seed,
                         {n: fabric.tenant_io(s)[0] for n, s in specs.items()})
    phases["pool"] = time.time() - ctx.t_start
    loop = Loop(ctx, server, draws)
    loop.warm(tr["warm_requests"])
    jax.effects_barrier()
    phases["warm"] = time.time() - ctx.t_start
    phases["compile_s"] = ctx.clock.seconds

    compiles0 = ctx.clock.count
    t0 = time.time()
    setup_s = t0 - ctx.t_start
    loop.window(tr, ctx.seconds, t0)
    compiles_in_window = ctx.clock.count - compiles0
    t_end = t0 + ctx.seconds
    peak = ctx.peak_bytes()

    recs = list(loop.recs.values())
    due = [r for r in recs if r.phase == "window"]
    done = [r for r in due if r.done is not None]
    in_window = [r for r in done if r.done <= t_end]
    lat_ms = [(r.done - r.due) * 1e3 for r in done]
    cell_backends = {backend_of[r.tenant] for r in due}
    cnt = _delta(loop.snaps["start"], loop.snaps["end"])
    synops = sum(r.budget * fabric.synapses(specs[r.tenant])
                 for r in in_window)
    out = {
        "attempted": len(due),
        "failed": len(due) - len(done),
        "end_to_end": {
            "goodput": sum(r.budget for r in in_window) / ctx.seconds,
            "latency_p95_ms": traffic.percentile(lat_ms, 95),
            "setup_s": setup_s,
        },
        "memory_peak_bytes": peak,
        "info": {
            "completed_in_window": len(in_window),
            "latency_p50_ms": traffic.percentile(lat_ms, 50),
            "compiles_in_window": compiles_in_window,
            "backends": sorted(cell_backends),
            "warm_requests": len(recs) - len(due),
            "setup_phases_s": phases,
        },
        "layer": {
            "window_s": ctx.seconds,
            "counters": cnt,
            "synops": synops,
            "chunk_ticks": server.chunk_ticks,
        },
    }
    if getattr(loop, "late", None):
        out["info"]["generator_late_ms_p95"] = traffic.percentile(
            [x * 1e3 for x in loop.late], 95)
    if "trace_stop" in loop.snaps:
        a, b = loop.snaps["trace_start"], loop.snaps["trace_stop"]
        traced = [r for r in done if a["t"] <= r.done <= b["t"]]
        out["layer"]["traced"] = _delta(a, b)
        out["layer"]["traced_work"] = _traced_work(traced, specs, backend_of)
    out["info"]["tenant_report"] = {
        k: {kk: v[kk] for kk in ("requests", "spike_rate", "backend")}
        for k, v in server.tenant_report().items()}

    # -- the program's state goes before the reference runs -------------------
    plastic = [n for n, s in specs.items() if s.get("plastic")]
    served_w = {}
    for name in plastic:
        n = specs[name]["n"]
        served_w[name] = np.asarray(server.tenants[name].params.w[:n, :n])
    del server, loop.server
    gc.collect()
    t_ref = time.time()
    ref_counts, ref_w, chained = reference_outputs(cfg, specs, recs, done,
                                                   loop.order, ctx.seed)
    out["checks"] = compare(cfg["limits"], recs, served_w, ref_counts, ref_w)
    out["info"]["plastic_count_mismatch"] = sum(
        not np.array_equal(c, recs[rid].counts) for rid, c in chained.items())
    out["info"]["reference_s"] = time.time() - t_ref
    out["info"]["compared"] = {"frozen_requests": len(ref_counts),
                               "plastic_chain": sum(
                                   recs[i].tenant in ref_w
                                   for i in loop.order)}
    out["replay"] = (recs, done, loop.order, served_w)
    return out


def _traced_work(traced: List[Rec], specs, backend_of) -> Dict:
    per = {}
    for r in traced:
        b = per.setdefault(backend_of[r.tenant],
                           {"flops": 0.0, "bytes": 0.0, "slot_ticks": 0})
        s = specs[r.tenant]
        b["flops"] += r.budget * work.tick_flops(fabric.synapses(s))
        b["bytes"] += r.budget * work.tick_bytes(fabric.synapses(s), s["n"])
        b["slot_ticks"] += r.budget
    return per


def reference_outputs(cfg, specs, recs, done_window, order, seed,
                      dtype=None):
    """What the plain reference says: output counts of every request due
    in the window on a frozen tenant, each plastic tenant's weights after
    its whole chain of requests in the order they ran, and the output
    counts of every request of those chains."""
    import jax.numpy as jnp

    from bench import reference

    dtype = dtype or jnp.float32
    regs = fabric.build_tenants(seed, cfg["tenants"])
    T = cfg["max_ticks"]
    counts: Dict[int, np.ndarray] = {}
    chained: Dict[int, np.ndarray] = {}
    by_tenant: Dict[str, List[Rec]] = {}
    for r in done_window:
        if not specs[r.tenant].get("plastic"):
            by_tenant.setdefault(r.tenant, []).append(r)
    for name, rs in by_tenant.items():
        n = specs[name]["n"]
        n_out = fabric.tenant_io(specs[name])[1]
        ref = reference.run_frozen(regs[name], [r.ext for r in rs],
                                   [r.budget for r in rs], T, dtype=dtype)
        for r, row in zip(rs, ref):
            counts[r.rid] = row[n - n_out:n]
    weights = {}
    rule = plasticity_rule(cfg)
    for name, s in specs.items():
        chain = [recs[i] for i in order if recs[i].tenant == name]
        if s.get("plastic") and chain:
            w, chain_counts = reference.run_plastic(
                regs[name], [r.ext for r in chain],
                [r.budget for r in chain], T, rule, dtype=dtype)
            weights[name] = np.asarray(w, np.float32)
            n, n_out = s["n"], fabric.tenant_io(s)[1]
            chained.update((r.rid, c[n - n_out:n])
                           for r, c in zip(chain, chain_counts))
    return counts, weights, chained


def compare(limits, recs, served_w, ref_counts, ref_w) -> Dict:
    """The numbers that decide ``correct``, each beside its limit.

    ``frozen_mismatch``: requests due in the window on a frozen tenant
    whose output counts or prediction differ from the reference (exact:
    the fabric's weights sit on a dyadic grid).  ``plastic_w_rel_l2``:
    relative L2 distance of the plastic tenant's learned weights from the
    reference's, after its whole chain of requests."""
    mismatch = 0
    for rid, want in ref_counts.items():
        r = recs[rid]
        if not (np.array_equal(want, r.counts)
                and int(np.argmax(want)) == r.pred):
            mismatch += 1
    checks = {"frozen_mismatch": check(mismatch, limits["frozen_mismatch"])}
    for name, w_ref in ref_w.items():
        rel = float(np.linalg.norm(served_w[name] - w_ref)
                    / max(1e-30, float(np.linalg.norm(w_ref))))
        checks["plastic_w_rel_l2"] = check(rel, limits["plastic_w_rel_l2"])
    return checks


def sweep(ctx, rates) -> List[Dict]:
    """Offered rate against what the server sustains: one server, warmed
    once, then one open-loop window of ``ctx.seconds`` per rate."""
    cfg, tr = ctx.config, ctx.traffic
    specs = {t["name"]: t for t in cfg["tenants"]}
    server = build_server(cfg, ctx.seed)
    draws = traffic.pool(tr, ctx.seed,
                         {n: fabric.tenant_io(s)[0] for n, s in specs.items()})
    Loop(ctx, server, draws).warm(tr["warm_requests"])
    out = []
    for rate in rates:
        loop = Loop(ctx, server, draws)
        t0 = time.time()
        loop.window(dict(tr, rate_per_s=rate), ctx.seconds, t0)
        due = [r for r in loop.recs.values()]
        done = [r for r in due if r.done is not None]
        lat = [(r.done - r.due) * 1e3 for r in done]
        out.append({
            "rate_per_s": rate, "arrived": len(due),
            "completed_in_window": sum(r.done <= t0 + ctx.seconds
                                       for r in done),
            "drain_s": max(r.done for r in done) - (t0 + ctx.seconds),
            "latency_p50_ms": traffic.percentile(lat, 50),
            "latency_p95_ms": traffic.percentile(lat, 95),
            "goodput": sum(r.budget for r in done
                           if r.done <= t0 + ctx.seconds) / ctx.seconds})
        print(json.dumps(out[-1]), flush=True)
    return out


def control_checks(ctx, res) -> Dict:
    """The comparison with the control in the program's place: the plain
    reference computed in bfloat16 (the step below the configuration's
    float32), over the same requests as the run."""
    import jax.numpy as jnp

    cfg = ctx.config
    specs = {t["name"]: t for t in cfg["tenants"]}
    recs, done, order, _ = res["replay"]
    ref_counts, ref_w, _ = reference_outputs(cfg, specs, recs, done, order,
                                             ctx.seed)
    ctl_counts, ctl_w, _ = reference_outputs(cfg, specs, recs, done, order,
                                             ctx.seed, dtype=jnp.bfloat16)
    ctl = [dataclasses.replace(r) for r in recs]
    for rid, row in ctl_counts.items():
        ctl[rid].counts, ctl[rid].pred = row, int(np.argmax(row))
    return compare(cfg["limits"], ctl, ctl_w, ref_counts, ref_w)

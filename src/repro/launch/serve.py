"""Batched serving driver (the paper's kind: an inference platform).

Two server flavors:

* :class:`WaveServer` -- the LM model zoo: requests are grouped into
  waves of ``slots``; each wave left-pads prompts to a common length,
  prefills the whole wave in one batched program, then decodes all slots
  in lock-step (one jitted decode program).

* :class:`SNNServer` -- the SNN processor itself, multi-tenant: S
  independent *networks* (each its own ``W/C/thresholds/leak`` register
  image, loaded via :func:`repro.core.network.params_from_registers`)
  ride one compiled chunk program, vmapped over a slot axis, and are
  admitted continuously: a slot whose request spent its tick budget is
  refilled from the queue between chunks. The slot axis is the TPU
  restatement of time-sharing the mux fabric (DESIGN.md §8): swapping
  a tenant in = rewriting a slot's registers, never recompiling. Each
  request is answered as if it ran alone on the core engine (the
  per-request reference in tests/serve_reference.py).

Both mirror how the FPGA serves: one resident "fabric" (compiled
program), per-request state swapped in registers -- and like the FPGA,
switching requests never recompiles anything.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --requests 6 --max-new 12
  PYTHONPATH=src python -m repro.launch.serve --arch snn --smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_bundle
from repro.core.engine import EngineOptions
from repro.deprecation import warn_deprecated
from repro.models import model as M
from repro.obs import MetricsRegistry, log_event, profile, span
from repro.util.env import enable_compilation_cache


@dataclasses.dataclass
class ServeRequest:
    """ONE request type for both servers (the unified serve surface).

    The LM :class:`WaveServer` reads ``prompt``/``max_new``; the
    :class:`SNNServer` reads ``ext``/``n_ticks``/``rewards``. ``t_submit``
    is the *enqueue* time: callers that queue requests (the async
    front-end) stamp it at admission so TTFT includes queue wait; the
    servers only stamp it (lazily, when still ``0.0``) for requests
    handed to them directly. ``t_admit`` is when continuous admission
    put the request in a slot (queue wait = ``t_admit - t_submit``).

    Result fields (``out``/``counts``/``pred``/timestamps) are filled in
    place as the request completes -- :meth:`ServeResult.of` snapshots
    them into the immutable result record the stats dicts carry.
    """

    rid: int
    # -- LM fields
    prompt: Optional[np.ndarray] = None   # (S,) or (S, K) int32
    max_new: int = 0
    # -- SNN fields
    tenant: str = ""
    ext: Optional[np.ndarray] = None      # (T_req, n_in) input spike train
    n_ticks: int = 0                      # tick budget for this request
    rewards: Optional[np.ndarray] = None  # (T_req,) dopamine (R-STDP)
    # -- result fields (filled by the servers)
    out: List = dataclasses.field(default_factory=list)
    counts: Optional[np.ndarray] = None   # (n_out,) rate-decoded counts
    pred: Optional[int] = None            # argmax over output neurons
    t_submit: float = 0.0
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class ServeResult:
    """Immutable completion record, one per served request.

    ``ttft_s`` is measured from *enqueue* (``t_submit``), not from
    slot-fill start -- under continuous admission a queued request's
    wait is real latency its caller observed.
    """

    rid: int
    tenant: str = ""
    out: tuple = ()                       # LM: generated token ids
    counts: Optional[np.ndarray] = None   # SNN: rate-decoded counts
    pred: Optional[int] = None
    rejected: bool = False
    reason: str = ""                      # admission-rejection reason
    t_submit: float = 0.0
    t_first: Optional[float] = None
    t_done: Optional[float] = None

    @property
    def ttft_s(self) -> float:
        if self.t_first is None:
            return 0.0
        return max(0.0, self.t_first - self.t_submit)

    @classmethod
    def of(cls, r: "ServeRequest") -> "ServeResult":
        return cls(rid=r.rid, tenant=r.tenant, out=tuple(r.out),
                   counts=r.counts, pred=r.pred, t_submit=r.t_submit,
                   t_first=r.t_first, t_done=r.t_done)

    @classmethod
    def rejection(cls, r: "ServeRequest", reason: str) -> "ServeResult":
        now = time.time()
        return cls(rid=r.rid, tenant=r.tenant, rejected=True, reason=reason,
                   t_submit=r.t_submit or now, t_first=None, t_done=now)


class Request(ServeRequest):
    """Deprecated LM request shim -- use :class:`ServeRequest`."""

    def __init__(self, rid, prompt=None, max_new=0, out=None,
                 t_submit=0.0, t_first=None, t_done=None):
        warn_deprecated(
            "launch.serve.Request is deprecated; use ServeRequest "
            "(same fields, shared with SNNServer)")
        super().__init__(rid=rid, prompt=prompt, max_new=max_new,
                         t_submit=t_submit, t_first=t_first, t_done=t_done)
        if out is not None:
            self.out = out


class SNNRequest(ServeRequest):
    """Deprecated SNN request shim -- use :class:`ServeRequest`."""

    def __init__(self, rid, tenant="", ext=None, n_ticks=0, rewards=None,
                 counts=None, pred=None, t_submit=0.0, t_first=None,
                 t_done=None):
        warn_deprecated(
            "launch.serve.SNNRequest is deprecated; use ServeRequest "
            "(same fields, shared with the LM WaveServer)")
        super().__init__(rid=rid, tenant=tenant, ext=ext, n_ticks=n_ticks,
                         rewards=rewards, counts=counts, pred=pred,
                         t_submit=t_submit, t_first=t_first, t_done=t_done)


class WaveServer:
    """One compiled prefill + one compiled decode program, reused forever."""

    def __init__(self, cfg, params, *, slots: int, max_len: int):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self._decode = jax.jit(lambda p, b, c: M.decode_fn(p, cfg, b, c))
        self._prefill = jax.jit(lambda p, b, c: M.prefill_fn(p, cfg, b, c))

    def _pad_prompts(self, reqs: List[ServeRequest]) -> np.ndarray:
        plen = max(len(r.prompt) for r in reqs)
        shape = (self.slots, plen) + (
            (self.cfg.n_codebooks,) if self.cfg.family == "audio" else ())
        toks = np.zeros(shape, np.int32)
        for i, r in enumerate(reqs):
            toks[i, plen - len(r.prompt):] = r.prompt  # left-pad with 0
        return toks

    def run_wave(self, reqs: List[ServeRequest]) -> int:
        """Prefill + decode one wave to completion; returns decode steps."""
        cfg = self.cfg
        toks = self._pad_prompts(reqs)
        plen = toks.shape[1]
        caches = M.init_cache(cfg, self.slots, self.max_len)
        last, caches = self._prefill(self.params, {"inputs": jnp.asarray(toks)},
                                     caches)
        last_np = np.asarray(last, np.float32)        # (slots, V) or (slots,K,V)
        now = time.time()
        cur = last_np.argmax(-1).astype(np.int32)     # (slots,) or (slots, K)
        for r_i, r in enumerate(reqs):
            r.t_first = now
            r.out.append(int(np.atleast_1d(cur[r_i]).flat[0]))

        steps = 0
        pos = plen
        active = {i for i, r in enumerate(reqs) if len(r.out) < r.max_new}
        for r_i, r in enumerate(reqs):
            if r_i not in active:
                r.t_done = now
        max_new = max(r.max_new for r in reqs)
        while active and pos < self.max_len - 1 and steps < max_new:
            tok_in = cur[:, None] if cfg.family != "audio" else cur[:, None, :]
            batch = {"token": jnp.asarray(tok_in),
                     "pos": jnp.asarray(pos, jnp.int32)}
            logits, caches = self._decode(self.params, batch, caches)
            cur = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            steps += 1
            pos += 1
            now = time.time()
            for r_i in list(active):
                r = reqs[r_i]
                r.out.append(int(np.atleast_1d(cur[r_i]).flat[0]))
                if len(r.out) >= r.max_new:
                    r.t_done = now
                    active.discard(r_i)
        now = time.time()
        for r in reqs:
            if r.t_done is None:
                r.t_done = now
        return steps


def serve(cfg, params, requests: List[ServeRequest], *, slots: int = 4,
          max_len: int = 64) -> Dict:
    if not requests:
        # Empty queue: a well-formed zero report, never np.mean([]).
        return {"n_requests": 0, "requests_served": 0, "decode_steps": 0,
                "new_tokens": 0, "wall_s": 0.0, "tokens_per_s": 0.0,
                "mean_ttft_s": 0.0, "p99_ttft_s": 0.0, "outputs": {},
                "results": []}
    server = WaveServer(cfg, params, slots=slots, max_len=max_len)
    now = time.time()
    for r in requests:
        # TTFT counts from *enqueue*: keep a caller-stamped submit time
        # (the async front-end stamps at admission), stamp only if unset.
        if not r.t_submit:
            r.t_submit = now
    done: List[ServeRequest] = []
    steps = 0
    queue = list(requests)
    while queue:
        wave = queue[:slots]
        queue = queue[slots:]
        # pad the wave with a dummy clone so the batch shape is static
        while len(wave) < slots:
            wave.append(ServeRequest(rid=-1, prompt=wave[0].prompt, max_new=1))
        steps += server.run_wave(wave)
        done.extend(r for r in wave if r.rid >= 0)

    total_new = sum(len(r.out) for r in done)
    t0 = min(r.t_submit for r in done)
    t1 = max(r.t_done for r in done)
    ttfts = [r.t_first - r.t_submit for r in done]
    return {
        "n_requests": len(done),
        "requests_served": len(done),
        "decode_steps": steps,
        "new_tokens": total_new,
        "wall_s": round(t1 - t0, 3),
        "tokens_per_s": round(total_new / max(1e-9, t1 - t0), 2),
        "mean_ttft_s": round(float(np.mean(ttfts)), 3) if done else 0.0,
        "p99_ttft_s": round(float(np.percentile(ttfts, 99)), 4) if done else 0.0,
        "outputs": {r.rid: r.out[:8] for r in done},
        "results": [ServeResult.of(r) for r in done],
    }


# ---------------------------------------------------------------------------
# Multi-tenant SNN serving: many resident networks, one compiled tick program
# ---------------------------------------------------------------------------

_PAD_VTH = 1e30  # padded neurons can never reach threshold


@dataclasses.dataclass
class Tenant:
    """One resident network: a register image padded onto the fabric.

    ``params`` leaves are fabric-shaped ``(n_max, ...)``; neurons past
    ``n`` carry an unreachable threshold (silent forever) and a zeroed
    connection/plastic mask (can never learn). ``plastic_c`` gates the
    learning hook per synapse: all-zero for frozen tenants, so their
    weights come back *bit-identical* from every request.

    ``backend`` is the tick program this tenant rides: the server's
    default, or ``"event"`` when the tenant's topology is sparse enough
    to clear the server's ``event_density`` threshold (then ``fan_idx``
    / ``fan_mask`` hold its padded fan-in lists, fabric-shaped
    ``(n_max, event_cap)`` so every event slot stacks to one static
    shape).
    """

    name: str
    n: int
    n_in: int
    n_out: int
    plastic: bool
    params: "object"            # repro.core.network.SNNParams, padded
    plastic_c: jax.Array        # (n_max, n_max)
    density: float = 1.0
    backend: str = "jnp"
    fan_idx: Optional[jax.Array] = None   # (n_max, event_cap) i32
    fan_mask: Optional[jax.Array] = None  # (n_max, event_cap) f32
    plan: Optional["object"] = None       # dispatch_policy.DispatchPlan


def pad_tenant_params(params, n_max: int):
    """Zero-pad an ``(n, n)`` register image onto the ``n_max`` fabric."""
    from repro.core.lif import LIFParams
    from repro.core.network import SNNParams

    n = params.w.shape[0]
    if n > n_max:
        raise ValueError(f"tenant has {n} neurons; fabric holds {n_max}")
    p2 = lambda a: jnp.pad(
        a, ((0, n_max - a.shape[0]), (0, n_max - a.shape[1])))
    p1 = lambda a, v=0: jnp.pad(a, (0, n_max - n), constant_values=v)
    lif = LIFParams(
        v_th=p1(params.lif.v_th, _PAD_VTH),
        leak=p1(params.lif.leak),
        r_ref=p1(params.lif.r_ref),
        gain=p1(params.lif.gain, 1.0),
        i_bias=p1(params.lif.i_bias),
        v_reset=p1(params.lif.v_reset),
    )
    # w_in may be rectangular (n_in, n): pad each axis to the fabric size.
    return SNNParams(w=p2(params.w), c=p2(params.c), w_in=p2(params.w_in), lif=lif)


def _arg_specs(args):
    """Shape-only stand-ins for a program's array arguments, to lower it
    again later: they hold no reference to arrays a donating call
    deletes."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding,
                                       weak_type=a.weak_type), args)


class SNNServer:
    """Slot-batched multi-tenant SNN serving on one compiled chunk program.

    S slots x one :class:`~repro.core.engine.TickEngine`, vmapped over the
    slot axis: every chunk runs S independent networks -- heterogeneous
    ``C`` topologies, thresholds, leaks, even a mix of frozen and plastic
    tenants -- through ONE jitted program of static shape
    ``(slots, chunk_ticks, n_max)`` per backend in use, plus one slot
    refill program per backend. Admission is continuous
    (:meth:`serve_continuous`); per-request tick budgets, offsets and
    tenant registers are runtime data, so neither budgets nor tenant
    swaps ever retrace (``recompiles_after_warmup`` stays at 0).

    One datapath for inference and learning, as NeuroCoreX does in
    silicon: the chunk program runs the tick body on every slot and the
    plasticity hook only on the slot-ticks that learn. Each request's
    counts, and each plastic tenant's learned weights, equal those of
    the request run alone on the core engine, bit for bit.
    """

    def __init__(self, *, n_max: int, slots: int = 8, max_ticks: int = 32,
                 mode: str = "fixed_leak", backend: str = "jnp",
                 plasticity=None, event_density: Optional[float] = None,
                 event_cap: Optional[int] = None, telemetry: bool = True,
                 registry: Optional[MetricsRegistry] = None,
                 options: Optional[EngineOptions] = None,
                 chunk_ticks: Optional[int] = None):
        """Args (beyond the obvious):

        backend: the default tick backend every tenant rides.
        event_density: when set, tenants whose topology density is at or
          below it (and whose max in-degree fits ``event_cap``) are served
          through a second resident program with ``backend="event"`` --
          the sparse tenants pick event dispatch per slot, dense tenants
          keep the default program.  None disables the event program.
        event_cap: fan-in cap (static shape) of the event program's padded
          neighbor lists; defaults to ``n_max // 4``.  One cap for the
          whole server keeps the event program's shapes static, so tenant
          swaps never retrace (a tenant whose in-degree exceeds the cap
          simply stays on the dense program -- never truncated).
        telemetry: thread :class:`~repro.obs.telemetry.TickTelemetry`
          through every slot's carry (static flag -- the resident
          programs are traced with it once, never retraced). Feeds
          :meth:`tenant_report` and the spike/overflow/weight-delta
          metrics; False serves the exact telemetry-free programs.
        registry: a :class:`~repro.obs.metrics.MetricsRegistry` to report
          into; defaults to a fresh private one (``server.registry``).
        options: a validated :class:`~repro.core.engine.EngineOptions`
          superseding the per-call engine statics (``mode`` / ``backend``
          / ``plasticity`` / ``telemetry``) -- the preferred spelling;
          the individual kwargs remain as a compatibility shim.
        chunk_ticks: tick-chunk size for :meth:`serve_continuous`
          (default ``max(1, min(8, max_ticks // 4))``): smaller chunks
          retire/refill slots sooner (lower TTFT, higher goodput under
          mixed budgets) at more per-chunk host dispatch overhead.
        """
        from repro.core.engine import TickEngine
        from repro.plasticity import PlasticityParams

        if options is not None:
            mode = options.mode
            backend = options.backend
            telemetry = options.telemetry
            if options.plasticity is not None:
                plasticity = options.plasticity
        self.n_max = int(n_max)
        self.slots = int(slots)
        self.max_ticks = int(max_ticks)
        self.backend = backend
        self.event_density = event_density
        self.event_cap = int(event_cap or max(1, n_max // 4))
        self.telemetry = bool(telemetry)
        self.chunk_ticks = int(
            max(1, min(8, self.max_ticks // 4))
            if chunk_ticks is None else chunk_ticks)
        if not (1 <= self.chunk_ticks <= self.max_ticks):
            raise ValueError(
                f"chunk_ticks must lie in [1, max_ticks={self.max_ticks}], "
                f"got {self.chunk_ticks}")
        if plasticity is None:
            plasticity = PlasticityParams.make(
                "stdp", a_plus=0.5, a_minus=0.25, w_min=0.0, w_max=255.0)
        self._mk_engine = lambda b: TickEngine(EngineOptions(
            mode=mode, backend=b, plasticity=plasticity,
            telemetry=self.telemetry))
        self.engine = self._mk_engine(backend)
        self._engines = {backend: self.engine}
        self.tenants: Dict[str, Tenant] = {}
        self._compiles: Dict[str, int] = {}   # per-program, TRACE time only
        self._chunk_runs: Dict[tuple, object] = {}
        # (backend, chunk) or ("fill", backend) -> shapes of the first
        # dispatched argument list
        self._chunk_arg_specs: Dict[tuple, tuple] = {}
        self._fresh_zeros = None
        self._tenant_obs: Dict[str, Dict] = {}  # accumulated telemetry
        self.registry = registry if registry is not None else MetricsRegistry()
        r = self.registry
        self._c_requests = r.counter(
            "snn_requests_total", "requests served to completion")
        self._c_rejected = r.counter(
            "snn_requests_rejected_total", "requests refused at admission")
        self._c_rej_reason = r.counter(
            "snn_admission_rejections_total",
            "admission rejections, by reason", ("reason",))
        self._c_chunks = r.counter(
            "snn_chunks_total",
            "continuous-admission chunks run, by resident program",
            ("backend",))
        self._c_refills = r.counter(
            "snn_slot_refills_total",
            "slot refills written in place into the stacked inputs of a "
            "continuous group's programs, by resident program", ("backend",))
        self._c_spikes = r.counter(
            "snn_spikes_out_total", "rate-decoded output spikes")
        self._c_slot_ticks = r.counter(
            "snn_slot_ticks_total", "slot-ticks executed (slots x ticks)")
        self._c_useful_ticks = r.counter(
            "snn_useful_slot_ticks_total",
            "slot-ticks inside a live request's budget (goodput numerator)")
        self._c_learning_ticks = r.counter(
            "snn_learning_slot_ticks_total",
            "slot-ticks on which the continuous chunk program ran the "
            "plasticity hook (a plastic tenant inside its budget)")
        self._c_overflow = r.counter(
            "snn_event_overflow_ticks_total",
            "event-backend ticks that overflowed k_active to dense fallback")
        self._c_policy = r.counter(
            "snn_event_policy_dense_ticks_total",
            "event-backend ticks the adaptive knee routed dense for speed")
        self._c_dw = r.counter(
            "snn_weight_delta_l1_total", "summed |dw| applied by plasticity")
        self._g_queue = r.gauge("snn_queue_depth", "requests awaiting a slot")
        self._g_busy = r.gauge(
            "snn_slots_busy", "slots holding a live request right now")
        self._g_goodput = r.gauge(
            "snn_slot_ticks_per_s", "raw slot-tick rate of the last serve call")
        self._g_useful_goodput = r.gauge(
            "snn_goodput_slot_ticks_per_s",
            "useful (in-budget) slot-ticks per second of the last serve call")
        self._h_ttft = r.histogram(
            "snn_ttft_seconds", "enqueue-to-first-output latency")
        self._h_queue_wait = r.histogram(
            "snn_queue_wait_seconds",
            "enqueue-to-slot-fill wait under continuous admission")

    @property
    def compiles(self) -> int:
        """Total trace count across the server's resident programs (a
        chunk program per (backend, chunk size) in use and a refill
        program per backend; tenant/slot churn must never add to it)."""
        return sum(self._compiles.values())

    def _chunk_run_for(self, backend: str, chunk: int):
        """The jitted chunked step -- one resident program per
        (backend, chunk size), traced once; slot refills only rewrite
        its array arguments.

        ``carry`` and ``counts_acc`` are donated: the step updates the
        stacked carry (its ``(S, N, N)`` weights and eligibility traces
        included) and the running counts in place instead of copying
        them at entry, so the caller must not touch the arrays it passed
        once the call is made.  ``params`` and ``plastic_c`` are read
        only, and the next chunk reads them again."""
        key = (backend, int(chunk))
        if key not in self._chunk_runs:
            self._engines.setdefault(backend, self._mk_engine(backend))
            self._chunk_runs[key] = jax.jit(
                functools.partial(self._chunk_fn, backend, int(chunk)),
                donate_argnames=("carry", "counts_acc"))
        return self._chunk_runs[key]

    def _compiled(self, key: tuple):
        """A resident program compiled for the argument shapes
        :meth:`serve_continuous` first dispatched it with (a ``KeyError``
        if it never ran).  Compiles, so keep it out of timed code."""
        return self._chunk_runs[key].lower(
            *self._chunk_arg_specs[key]).compile()

    def chunk_program_text(self, backend: str,
                           chunk: Optional[int] = None) -> str:
        """Compiled text of the resident chunk program for ``backend``.
        For checks of what the program holds, e.g. which Pallas kernels
        it calls."""
        key = (backend, int(self.chunk_ticks if chunk is None else chunk))
        return self._compiled(key).as_text()

    def program_alias_bytes(self, backend: str, program: str) -> int:
        """Bytes of input that the compiled ``program`` (``"fill"``, the
        slot refill, or ``"chunk"``, at the server's chunk size) for
        ``backend`` updates in place: its donated inputs that alias an
        output.  0 means every call writes whole new copies of them."""
        if program == "fill":
            key = ("fill", backend)
        elif program == "chunk":
            key = (backend, self.chunk_ticks)
        else:
            raise ValueError(f"program must be 'fill' or 'chunk', "
                             f"got {program!r}")
        return int(self._compiled(key).memory_analysis().alias_size_in_bytes)

    # -- tenant registry ---------------------------------------------------

    def add_tenant(self, name: str, bank, *, n_in: int, n_out: int,
                   plastic: bool = False) -> Tenant:
        """Register a tenant from its :class:`RegisterBank` image.

        The bank is the wire format (the paper's UART-fed registers);
        loading it is a parameter download -- shapes never change, so the
        resident program is never re-traced.
        """
        from repro.core.network import params_from_registers

        params = params_from_registers(bank)
        return self.add_tenant_params(name, params, n_in=n_in, n_out=n_out,
                                      plastic=plastic)

    def add_tenant_params(self, name: str, params, *, n_in: int, n_out: int,
                          plastic: bool = False) -> Tenant:
        n = params.w.shape[0]
        if not (0 < n_in <= n and 0 < n_out <= n):
            raise ValueError(
                f"tenant {name!r}: n_in={n_in}, n_out={n_out} must lie in "
                f"[1, {n}] (the tenant's live neuron count)")
        padded = pad_tenant_params(params, self.n_max)
        plastic_c = padded.c if plastic else jnp.zeros_like(padded.c)
        density = float(np.asarray(params.c).sum()) / max(1, n * n)
        backend, fan_idx, fan_mask, plan = self.backend, None, None, None
        if self.event_density is not None and density <= self.event_density:
            from repro.core import dispatch_policy

            # Admission-time dispatch plan (host side, concrete topology):
            # vmap_safe because the chunk program vmaps the tick over slots
            # (the topk path's lax.cond would lower to a both-arms select);
            # prefer_density is the operator contract -- at or below the
            # server's threshold a fabric whose fan-in fits the shared cap
            # rides the event program regardless of the modeled cost.
            plan = dispatch_policy.plan(
                np.asarray(padded.c) > 0, w_in=np.asarray(padded.w_in),
                cap=self.event_cap, vmap_safe=True,
                prefer_density=self.event_density)
            if plan.strategy == "fan_in":
                # Sparse tenant: ride the event program. Fan-in lists are
                # built at the shared cap so every event slot stacks to
                # one static shape (no retrace on tenant swap).
                backend = "event"
                fan_idx = plan.neighbors.idx
                fan_mask = plan.neighbors.mask
        t = Tenant(name=name, n=n, n_in=n_in, n_out=n_out, plastic=plastic,
                   params=padded, plastic_c=plastic_c, density=density,
                   backend=backend, fan_idx=fan_idx, fan_mask=fan_mask,
                   plan=plan)
        self.tenants[name] = t
        return t

    # -- the compiled chunk program ----------------------------------------

    def _chunk_fn(self, backend, chunk, params, carry, ext, plastic_c,
                  rewards, offset, budget, learns, counts_acc,
                  fan_idx=None, fan_mask=None):
        """The continuous-admission step: run every resident slot for
        ``chunk`` ticks from its carried state.

        ``(slot-batched params, slot-batched TickCarry, (S,chunk,N) ext,
        (S,N,N) mask, (S,chunk) rewards, (S,) tick offsets, (S,)
        budgets, (S,) learns, (S,N) running counts[, fan-in lists]) ->
        (next carry, (S,N) updated running counts)``.

        Counts accumulate *on device* -- the host only reads a slot's
        row back when its request retires, so consecutive chunks
        dispatch without a host round-trip between them.

        Everything per-request is *runtime data* -- offsets, budgets,
        ``learns`` (the slot's tenant is plastic), the carry, even which
        tenant owns a slot (its registers are just array values) -- so
        one trace serves every refill; only the chunk size and backend
        are static. The count mask compares the absolute tick index
        (``offset + arange``) against the budget, so partial counts
        summed across chunks equal the request's one-shot masked sum
        exactly (small integers in f32 -- order-free).

        Each tick runs the tick body vmapped over the slots without the
        plasticity hook, then the hook slot by slot under a
        ``lax.cond``: only on slots that learn, and only while the carry's
        own tick counter (which persists across chunks) is below the
        budget -- a request run alone through
        :meth:`~repro.core.engine.TickEngine.learning_rollout` with
        ``learn_until=budget``, the per-request reference. Under ``vmap``
        a ``cond`` would lower to a select that runs both arms; out of
        it, a frozen slot or a tick past the budget costs the device no
        STDP pass, no ``learn_until`` select and no weight-delta norms.
        On those ticks the reference commits ``W`` unchanged and adds
        exactly 0 to the norms, so counts, learned weights and the
        weight-delta telemetry stay bit-identical to it."""
        from repro.core.engine import TickEngine
        from repro.kernels.ops import EventFanIn

        key = f"chunk/{backend}"
        self._compiles[key] = self._compiles.get(key, 0) + 1
        engine = self._engines[backend]
        # The tick body alone: with no plasticity set, the engine leaves
        # the hook out though the carry holds W.
        ticker = TickEngine(dataclasses.replace(engine.options,
                                                plasticity=None))

        def slot_tick(p, c, e, fi, fm):
            nbrs = None if fi is None else EventFanIn(idx=fi, mask=fm)
            c2, y = ticker.chunk(p, c, e[None], 1, neighbors=nbrs)
            return c2, y[0]

        def tick(c, xs):
            e, rew = xs                                     # (S,N), (S,)
            go = learns & (c.state.tick < budget)
            y_pre = c.state.lif.y
            c, y = jax.vmap(slot_tick)(params, c, e, fan_idx, fan_mask)

            def learn(s, lc):
                # Plain dynamic slices and in-place updates (``.at[s]``
                # would add a bounds select that rereads the slot's W).
                plast, w, telem = lc
                at = lambda a: jax.lax.dynamic_index_in_dim(a, s, 0, False)
                put = lambda a, v: jax.lax.dynamic_update_index_in_dim(
                    a, v, s, 0)
                w_old = at(w)
                plast2, w2 = engine.plasticity_hook(
                    jax.tree.map(at, plast), w_old, at(y_pre), at(y),
                    at(plastic_c), at(rew))
                if telem is not None:
                    # The committed delta's norms, in a cond of their own
                    # (``go[s]`` holds here): a cond's operands are
                    # materialized, so the sum reads w2 as stored. XLA may
                    # otherwise fuse a copy of w2's arithmetic into the
                    # reduction, which rounds apart from the reference's
                    # norms; an optimization barrier does not last until
                    # the CPU backend's fusion.
                    tel = jax.lax.cond(
                        go[s], lambda t, a, b: t.fold_dw(a - b),
                        lambda t, a, b: t,
                        jax.tree.map(at, telem), w2, w_old)
                    telem = jax.tree.map(put, telem, tel)
                return jax.tree.map(put, plast, plast2), put(w, w2), telem

            plast, w, telem = jax.lax.fori_loop(
                0, go.shape[0],
                lambda s, lc: jax.lax.cond(go[s], functools.partial(learn, s),
                                           lambda lc: lc, lc),
                (c.plast, c.w, c.telem))
            return dataclasses.replace(c, plast=plast, w=w, telem=telem), y

        carry2, raster = jax.lax.scan(
            tick, carry, (jnp.swapaxes(ext, 0, 1), rewards.T))
        t_abs = jnp.arange(chunk)[:, None] + offset[None, :]     # (chunk, S)
        tmask = (t_abs < budget[None, :]).astype(raster.dtype)
        counts = (raster * tmask[:, :, None]).sum(axis=0)        # (S, N)
        return carry2, counts_acc + counts

    def _observe_slot(self, t: Tenant, tel, i: int) -> None:
        """Fold slot ``i`` of a chunk's telemetry into the tenant ledger."""
        o = self._tenant_obs.setdefault(t.name, {
            "requests": 0, "ticks": 0, "spikes": 0.0, "v_max": 0.0,
            "ref_sum": 0.0, "overflow_ticks": 0, "policy_dense_ticks": 0,
            "dw_l1": 0.0})
        o["requests"] += 1
        o["ticks"] += int(tel.ticks[i])
        o["spikes"] += float(tel.spikes[i])
        o["v_max"] = max(o["v_max"], float(tel.v_max[i]))
        o["ref_sum"] += float(tel.ref_sum[i])
        o["overflow_ticks"] += int(tel.overflow[i])
        o["policy_dense_ticks"] += int(tel.policy_dense[i])
        o["dw_l1"] += float(tel.dw_l1[i])

    def tenant_report(self) -> Dict[str, Dict]:
        """Per-tenant activity from accumulated slot telemetry.

        ``spike_rate`` is spikes per live-neuron-tick (padded fabric
        neurons carry an unreachable threshold, so every spike belongs
        to one of the tenant's ``n`` live neurons); the refractory
        occupancy is rescaled from the fabric axis to live neurons the
        same way. Empty when the server was built with
        ``telemetry=False`` or has served nothing yet.
        """
        rep: Dict[str, Dict] = {}
        for name in sorted(self._tenant_obs):
            o, t = self._tenant_obs[name], self.tenants[name]
            ticks = o["ticks"]
            rescale = self.n_max / max(1, t.n)
            rep[name] = {
                "requests": o["requests"],
                "ticks": ticks,
                "spikes": o["spikes"],
                "spike_rate": round(o["spikes"] / max(1, ticks * t.n), 4),
                "v_max": round(o["v_max"], 4),
                "refractory_occupancy": round(
                    o["ref_sum"] / max(1, ticks) * rescale, 4),
                "overflow_ticks": o["overflow_ticks"],
                "policy_dense_ticks": o["policy_dense_ticks"],
                "dw_l1": round(o["dw_l1"], 3),
                "plastic": t.plastic,
                "backend": t.backend,
                "dispatch": t.plan.strategy if t.plan is not None else None,
            }
        return rep

    def _stats(self, *, done: List[ServeRequest], n_rejected: int,
               chunks: int = 0, ticks: int = 0, slot_ticks: int = 0,
               wall_s: float = 0.0) -> Dict:
        """ONE stats schema for a served call and the empty report --
        identical key sets, no drift (pinned in
        tests/test_serve_continuous.py).

        ``slot_ticks_per_s`` is the raw rate (every tick the fabric ran,
        padding and post-budget ticks included); the goodput rate counts
        only ticks inside a live request's budget -- the quantity
        continuous admission exists to improve.
        """
        wall = max(1e-9, wall_s)
        ttfts = [r.t_first - r.t_submit for r in done]
        useful = sum(min(int(r.n_ticks), self.max_ticks) for r in done)
        total_spikes = float(sum(r.counts.sum() for r in done)) if done else 0.0
        return {
            "n_requests": len(done),
            "requests_served": len(done),
            "requests_rejected": n_rejected,
            "n_tenants": len({r.tenant for r in done}),
            "chunks": chunks,
            "ticks": ticks,
            "useful_slot_ticks": useful,
            "spikes_out": total_spikes,
            "wall_s": round(wall_s, 3),
            "spikes_per_s": round(total_spikes / wall, 1) if done else 0.0,
            "slot_ticks_per_s": round(slot_ticks / wall, 1) if done else 0.0,
            "goodput_slot_ticks_per_s":
                round(useful / wall, 1) if done else 0.0,
            "mean_ttft_s":
                round(float(np.mean(ttfts)), 4) if done else 0.0,
            "p99_ttft_s":
                round(float(np.percentile(ttfts, 99)), 4) if done else 0.0,
            "compiles": self.compiles,
            # One trace per resident program (the chunk step per
            # (backend, chunk), the refill per backend) is warmup;
            # anything past that is a retrace regression.
            "recompiles_after_warmup": sum(
                max(0, c - 1) for c in self._compiles.values()),
            "backends": {
                b: sum(1 for r in done
                       if self.tenants[r.tenant].backend == b)
                for b in sorted({self.tenants[r.tenant].backend
                                 for r in done})},
            "preds": {r.rid: r.pred for r in done},
            "results": [ServeResult.of(r) for r in done],
        }

    def _reject_unknown(self, requests: List[ServeRequest]):
        """Split off requests naming an unregistered tenant (counted,
        logged, never a KeyError mid-serve)."""
        rejected = [r for r in requests if r.tenant not in self.tenants]
        if rejected:
            self._c_rejected.inc(len(rejected))
            self._c_rej_reason.inc(len(rejected), reason="unknown_tenant")
            log_event("snn_requests_rejected", n=len(rejected),
                      tenants=sorted({r.tenant for r in rejected}))
        return [r for r in requests if r.tenant in self.tenants], rejected

    # -- continuous admission (per-slot refill) ----------------------------

    def _fresh_slot_carry(self, tenant: Tenant):
        """A fresh single-slot :class:`~repro.core.engine.TickCarry` for
        a just-admitted request: zeroed state/traces/telemetry, the
        tenant's current (possibly learned) weights.

        The zero leaves are tenant-independent (every tenant rides the
        same padded fabric), so they are built once and shared -- a
        refill must not pay a dozen eager zero-array dispatches."""
        from repro.core.engine import TickCarry
        from repro.core.network import SNNState
        from repro.plasticity import PlasticityState

        if self._fresh_zeros is None:
            telem = None
            if self.telemetry:
                from repro.obs.telemetry import TickTelemetry

                telem = TickTelemetry.zeros(())
            self._fresh_zeros = (SNNState.zeros((), self.n_max),
                                 PlasticityState.zeros((), self.n_max),
                                 telem)
        state, plast, telem = self._fresh_zeros
        return TickCarry(state=state, plast=plast,
                         w=tenant.params.w, telem=telem)

    def _fill_run_for(self, backend: str):
        """The jitted slot-refill program for ``backend``: writes one
        tenant image into slot ``i`` of the stacked program inputs in a
        single compiled call (one trace per backend; an eager
        ``.at[i].set`` per leaf costs ~1 ms each, which would dominate
        the chunk loop).

        The stacked inputs are donated, so the refill writes the slot's
        slices in place rather than copying every ``(S, N, N)`` stack;
        the image (tenant registers, the shared fresh-carry zeros) is
        only read."""
        key = ("fill", backend)
        if key not in self._chunk_runs:
            def _fill(stacked, image, i):
                k = f"fill/{backend}"
                self._compiles[k] = self._compiles.get(k, 0) + 1
                return jax.tree.map(lambda a, b: a.at[i].set(b),
                                    stacked, image)

            self._chunk_runs[key] = jax.jit(_fill, donate_argnums=0)
        return self._chunk_runs[key]

    @staticmethod
    def _next_admittable(pending: deque, busy_plastic: set,
                         tenants: Dict[str, Tenant]):
        """Pop the first FIFO request whose tenant isn't a currently
        resident *plastic* tenant (two slots learning from the same
        pre-admission registers would race the write-back; deferred,
        the request starts from the weights its predecessor learned)."""
        for idx, r in enumerate(pending):
            t = tenants[r.tenant]
            if t.plastic and r.tenant in busy_plastic:
                continue
            del pending[idx]
            return r
        return None

    def _route(self, r: ServeRequest, pending_map: Dict[str, deque],
               rejected: List[ServeRequest]) -> None:
        """Admit one (feeder-supplied) request into the right backend
        queue, stamping its enqueue time if the caller didn't."""
        if not r.t_submit:
            r.t_submit = time.time()
        if r.tenant not in self.tenants:
            self._c_rejected.inc()
            self._c_rej_reason.inc(reason="unknown_tenant")
            log_event("snn_requests_rejected", n=1, tenants=[r.tenant])
            rejected.append(r)
            return
        b = self.tenants[r.tenant].backend
        pending_map.setdefault(b, deque()).append(r)

    def serve_continuous(
        self,
        requests: Optional[List[ServeRequest]] = None,
        *,
        chunk_ticks: Optional[int] = None,
        feeder: Optional[Callable[[], Optional[ServeRequest]]] = None,
        on_complete: Optional[Callable[[ServeRequest], None]] = None,
    ) -> Dict:
        """Serve a request queue by per-slot continuous admission.

        The fabric runs in chunks of ``chunk_ticks`` ticks; after each chunk,
        slots whose request exhausted its tick budget *retire* (decode,
        write back learned weights, complete) and are *refilled* from
        the queue -- without recompiling: the chunked step is one jitted
        program per (backend, chunk size), and a refill only rewrites
        its array arguments (registers, carry slices, budgets). Short
        requests no longer pay for long ones; a request's latency is its
        own budget plus at most ``chunk_ticks - 1`` overshoot ticks.

        Args:
          requests: the initial queue (any mix of tenants/backends).
          chunk_ticks: override the server's default chunk size.
          feeder: optional non-blocking callable polled once per chunk
            for late-arriving requests (``None`` = none right now); this
            is how the async front-end streams admissions into a running
            loop. The call returns when every queue is drained and the
            feeder (if any) has nothing more to give.
          on_complete: optional callback invoked (from this thread) with
            each request as it completes -- the async front-end resolves
            per-request futures here, long before the batch returns.

        Requests naming an unregistered tenant are rejected (counted,
        logged); each backend's queue runs on its own resident program.
        Returns the per-call stats (:meth:`_stats`); ``server.registry``
        accumulates the same quantities across calls. Each request's
        counts and prediction, and each plastic tenant's learned
        weights, are bit-exact with the request run alone on the core
        engine (the per-request reference in tests/serve_reference.py).
        """
        chunk = int(self.chunk_ticks if chunk_ticks is None else chunk_ticks)
        if not (1 <= chunk <= self.max_ticks):
            raise ValueError(
                f"chunk_ticks must lie in [1, max_ticks={self.max_ticks}], "
                f"got {chunk}")
        t_start = time.time()
        requests, rejected = self._reject_unknown(list(requests or []))
        for r in requests:
            if not r.t_submit:
                r.t_submit = t_start
        pending_map: Dict[str, deque] = {}
        for r in requests:
            pending_map.setdefault(
                self.tenants[r.tenant].backend, deque()).append(r)
        done: List[ServeRequest] = []
        chunks = 0
        fed_dry = feeder is None
        while True:
            live = [b for b, q in pending_map.items() if q]
            if not live:
                if fed_dry:
                    break
                # One more feeder poll before giving up: a request may
                # have arrived between the last chunk and now.
                n_before = len(rejected)
                got = False
                with span("snn/admit"):
                    while feeder is not None:
                        r = feeder()
                        if r is None:
                            break
                        self._route(r, pending_map, rejected)
                        got = True
                if not got and len(rejected) == n_before:
                    break
                continue
            # FIFO across backends: run the program whose queue holds
            # the oldest waiting request.
            backend = min(live, key=lambda b: pending_map[b][0].t_submit)
            with span(f"snn/group/{backend}"):
                chunks += self._continuous_group(
                    backend, pending_map, rejected, chunk, feeder,
                    on_complete, done)
        self._g_queue.set(0)
        self._g_busy.set(0)
        if not done:
            return self._stats(done=[], n_rejected=len(rejected))
        t0 = min(r.t_submit for r in done)
        t1 = max(r.t_done for r in done)
        stats = self._stats(
            done=done, n_rejected=len(rejected), chunks=chunks,
            ticks=chunks * chunk, slot_ticks=chunks * chunk * self.slots,
            wall_s=t1 - t0)
        self._c_spikes.inc(stats["spikes_out"])
        self._g_goodput.set(stats["slot_ticks_per_s"])
        self._g_useful_goodput.set(stats["goodput_slot_ticks_per_s"])
        return stats

    def _continuous_group(self, backend: str, pending_map: Dict[str, deque],
                          rejected: List[ServeRequest], chunk: int,
                          feeder, on_complete,
                          done: List[ServeRequest]) -> int:
        """Run one backend's resident chunked program until its queue
        drains; returns the number of chunks run.

        Slot state (which request, tick offset, accumulated counts)
        lives host-side; the compiled step sees only arrays. Refill
        writes one slot's registers/carry via ``.at[i].set`` -- values,
        not shapes, so the program never retraces (pinned:
        ``recompiles_after_warmup == 0`` across refills).

        Both programs donate the stacked arrays they update (the refill
        all of them, the chunk the carry and the counts), so the stacked
        arrays live only in this function's locals, rebound to each
        call's outputs. A read of one slot (``counts_acc[i]``,
        ``carry_s.w[i]``) is a new array and outlives the next donation;
        ``_chunk_arg_specs`` keeps shapes only."""
        S, N = self.slots, self.n_max
        pending = pending_map.setdefault(backend, deque())
        run = self._chunk_run_for(backend, chunk)
        fill_run = self._fill_run_for(backend)
        slot_req: List[Optional[ServeRequest]] = [None] * S
        slot_tenant: List[Optional[Tenant]] = [None] * S
        busy_plastic: set = set()
        params_s = carry_s = plastic_c_s = counts_acc = None
        fan_idx_s = fan_mask_s = None
        zero_row = jnp.zeros((N,), jnp.float32)   # refill counts reset
        offset = np.zeros((S,), np.int64)   # absolute ticks already run
        budget = np.zeros((S,), np.int32)
        learns = np.zeros((S,), bool)       # the slot's tenant is plastic
        chunks = 0

        def fill(i: int, r: ServeRequest) -> None:
            nonlocal params_s, carry_s, plastic_c_s, counts_acc
            nonlocal fan_idx_s, fan_mask_s
            with span(f"snn/fill/{backend}", rid=r.rid, slot=i):
                t = self.tenants[r.tenant]
                r.t_admit = time.time()
                self._h_queue_wait.observe(max(0.0, r.t_admit - r.t_submit))
                slot_req[i], slot_tenant[i] = r, t
                offset[i] = 0
                budget[i] = min(int(r.n_ticks), self.max_ticks)
                learns[i] = t.plastic
                if t.plastic:
                    busy_plastic.add(t.name)
                fresh = self._fresh_slot_carry(t)
                if params_s is None:
                    # First fill seeds EVERY slot with this tenant's image;
                    # idle slots ride along at budget 0 (masked to nothing,
                    # never learning).
                    bcast = lambda x: jax.tree.map(
                        lambda a: jnp.broadcast_to(a, (S,) + a.shape), x)
                    params_s = bcast(t.params)
                    carry_s = bcast(fresh)
                    counts_acc = jnp.zeros((S, N), jnp.float32)
                    plastic_c_s = jnp.broadcast_to(
                        t.plastic_c, (S,) + t.plastic_c.shape)
                    if backend == "event":
                        fan_idx_s = jnp.broadcast_to(
                            t.fan_idx, (S,) + t.fan_idx.shape)
                        fan_mask_s = jnp.broadcast_to(
                            t.fan_mask, (S,) + t.fan_mask.shape)
                    return
                ev = backend == "event"
                image = (t.params, fresh, t.plastic_c, zero_row,
                         t.fan_idx if ev else None, t.fan_mask if ev else None)
                stacked = (params_s, carry_s, plastic_c_s, counts_acc,
                           fan_idx_s, fan_mask_s)
                if ("fill", backend) not in self._chunk_arg_specs:
                    self._chunk_arg_specs[("fill", backend)] = (
                        *_arg_specs((stacked, image)), i)
                (params_s, carry_s, plastic_c_s, counts_acc,
                 fan_idx_s, fan_mask_s) = fill_run(stacked, image, i)
                self._c_refills.inc(backend=backend)

        def retire(i: int, now: float, row: Optional[np.ndarray] = None,
                   tel=None) -> None:
            r, t = slot_req[i], slot_tenant[i]
            with span("snn/retire", rid=r.rid, slot=i):
                if row is None:   # the retire-time sync point
                    with span("snn/readback"):
                        row = np.asarray(counts_acc[i])
                out = row[t.n - t.n_out: t.n]
                r.counts = out
                r.pred = int(out.argmax())
                r.t_first = r.t_done = now
                if self.telemetry and carry_s is not None and offset[i] > 0:
                    if tel is None:
                        with span("snn/telemetry"):
                            tel = jax.tree.map(np.asarray, carry_s.telem)
                    self._observe_slot(t, tel, i)
                    self._c_overflow.inc(float(tel.overflow[i]))
                    self._c_policy.inc(float(tel.policy_dense[i]))
                    self._c_dw.inc(float(tel.dw_l1[i]))
                if t.plastic:
                    # Register write-back: the tenant's next request
                    # starts from what this one learned.
                    t.params = dataclasses.replace(t.params, w=carry_s.w[i])
                    busy_plastic.discard(t.name)
                slot_req[i] = slot_tenant[i] = None
                done.append(r)
                self._c_requests.inc()
                self._c_useful_ticks.inc(int(budget[i]))
                self._h_ttft.observe(r.t_done - r.t_submit)
                if on_complete is not None:
                    on_complete(r)

        while True:
            # Stream in late arrivals (the async front-end's feeder).
            if feeder is not None:
                with span("snn/admit"):
                    while True:
                        r = feeder()
                        if r is None:
                            break
                        self._route(r, pending_map, rejected)
            # Refill free slots FIFO; zero-budget requests complete
            # without running a tick (counts all-zero, nothing learned).
            for i in range(S):
                if slot_req[i] is None and pending:
                    r = self._next_admittable(pending, busy_plastic,
                                              self.tenants)
                    if r is not None:
                        fill(i, r)
                if slot_req[i] is not None and budget[i] <= offset[i]:
                    retire(i, time.time())
            busy = [i for i in range(S) if slot_req[i] is not None]
            self._g_queue.set(sum(len(q) for q in pending_map.values()))
            self._g_busy.set(len(busy))
            if not busy:
                if pending:
                    continue   # freed a plastic tenant; re-admit
                break
            with span("snn/assemble"):
                ext = np.zeros((S, chunk, N), np.float32)
                rew = np.zeros((S, chunk), np.float32)
                for i in busy:
                    r = slot_req[i]
                    o = int(offset[i])
                    if r.ext is not None and o < r.ext.shape[0]:
                        seg = np.asarray(r.ext[o:o + chunk], np.float32)
                        ext[i, :seg.shape[0], :seg.shape[1]] = seg
                    if r.rewards is not None and o < len(r.rewards):
                        seg = np.asarray(r.rewards[o:o + chunk], np.float32)
                        rew[i, :seg.shape[0]] = seg
                args = (params_s, carry_s, jnp.asarray(ext), plastic_c_s,
                        jnp.asarray(rew), jnp.asarray(offset, jnp.int32),
                        jnp.asarray(budget), jnp.asarray(learns), counts_acc)
                if backend == "event":
                    args += (fan_idx_s, fan_mask_s)
                if (backend, chunk) not in self._chunk_arg_specs:
                    self._chunk_arg_specs[(backend, chunk)] = _arg_specs(args)
            # The dispatch only: counts stay on device, so this span does
            # NOT wait for the chunk to execute -- consecutive chunks
            # pipeline, and the device queue only drains at a retire
            # round's ``snn/readback``.
            learning = [int(min(chunk, budget[i] - offset[i]))
                        for i in busy if learns[i] and offset[i] < budget[i]]
            with span(f"snn/chunk/{backend}", learn=len(learning)):
                carry_s, counts_acc = run(*args)
            chunks += 1
            self._c_chunks.inc(backend=backend)
            self._c_slot_ticks.inc(S * chunk)
            self._c_learning_ticks.inc(sum(learning))
            for i in busy:
                offset[i] += chunk
            due = [i for i in busy if offset[i] >= budget[i]]
            if due:
                # One (S, N) read-back (and one telemetry pull) serves
                # every retire this round.
                with span("snn/readback"):
                    rows = np.asarray(counts_acc)
                tel = None
                if self.telemetry:
                    with span("snn/telemetry"):
                        tel = jax.tree.map(np.asarray, carry_s.telem)
                now = time.time()
                for i in due:
                    retire(i, now, rows[i], tel)
        return chunks


def make_demo_tenants(server: SNNServer, n_tenants: int = 8, *,
                      seed: int = 0) -> List[str]:
    """Register ``n_tenants`` heterogeneous networks on the fabric.

    Mixed topologies (layered / ring / sparse-random / all-to-all),
    per-tenant thresholds and leaks, and one plastic (STDP) tenant --
    all loaded through the byte-exact :class:`RegisterBank` wire format.
    """
    from repro.core import connectivity
    from repro.core.registers import RegisterBank, WeightLayout

    rng = np.random.default_rng(seed)
    names: List[str] = []
    n_max = server.n_max
    for i in range(n_tenants):
        kind = ("layered", "ring", "sparse", "dense")[i % 4]
        n = int(rng.integers(max(6, n_max // 3), n_max + 1))
        if kind == "layered":
            n_in = max(2, n // 3)
            n_out = max(2, n // 4)
            hidden = n - n_in - n_out
            sizes = [n_in, hidden, n_out] if hidden > 0 else [n_in, n_out]
            c = connectivity.layered(sizes)
        elif kind == "ring":
            c = connectivity.ring(n, k=1 + i % 2)
            n_in, n_out = n, n
        elif kind == "sparse":
            # Sparse enough to clear the default event_density threshold:
            # these tenants ride the event program when it's enabled.
            c = connectivity.sparse_random(n, 0.1, seed=seed + i)
            n_in, n_out = n, n
        else:
            c = connectivity.all_to_all(n)
            n_in, n_out = n, n
        bank = RegisterBank(n, weight_layout=WeightLayout.PER_SYNAPSE)
        bank.set_connection_list(c)
        bank.set_weights(
            (rng.integers(40, 200, (n, n)) * c).astype(np.uint8))
        bank.set_thresholds(rng.integers(60, 160, (n,)).astype(np.uint8))
        bank.set_leak(int(rng.integers(0, 8)))
        bank.set_refractory(int(rng.integers(0, 3)))
        name = f"{kind}-{i}"
        server.add_tenant(name, bank, n_in=n_in, n_out=n_out,
                          plastic=(i == n_tenants - 1))
        names.append(name)
    return names


def make_demo_requests(server: SNNServer, names: List[str], n_requests: int,
                       *, seed: int = 0) -> List[ServeRequest]:
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        t = server.tenants[names[i % len(names)]]
        ticks = int(rng.integers(4, server.max_ticks + 1))
        # Impulse-register drive: spikes carry u8 magnitudes (paper Fig. 5),
        # sized so a spike can actually cross the tenants' u8 thresholds.
        ext = ((rng.random((ticks, t.n_in)) < 0.3)
               * rng.integers(80, 255, (ticks, t.n_in))).astype(np.float32)
        reqs.append(ServeRequest(rid=i, tenant=t.name, ext=ext, n_ticks=ticks))
    return reqs


def make_snn_server(cfg, slots: int) -> Tuple[SNNServer, List[str]]:
    """The serve CLI's fabric for an SNN config: the server plus its
    resident demo tenants (at least 8, one of them plastic).

    Dense default program + event program for sparse tenants: tenants at
    or below 20% density pick event dispatch per slot (DESIGN.md §10)."""
    backend = "jnp" if cfg.snn_backend == "event" else cfg.snn_backend
    server = SNNServer(n_max=cfg.n_neurons, slots=slots,
                       max_ticks=cfg.n_ticks, mode=cfg.snn_mode,
                       backend=backend, event_density=0.2,
                       chunk_ticks=max(
                           1, min(cfg.snn_chunk_ticks, cfg.n_ticks)))
    return server, make_demo_tenants(server, max(8, slots))


def serve_snn_main(cfg, args) -> Dict:
    server, names = make_snn_server(cfg, args.slots)
    print(f"serving SNN fabric n_max={server.n_max}: {len(names)} resident "
          f"tenants, {args.slots} slots, {args.requests} requests")
    reqs = make_demo_requests(server, names, max(args.requests, len(names)))
    with profile(getattr(args, "profile", None)):
        stats = server.serve_continuous(reqs)
    for k, v in stats.items():
        if k == "results":
            continue
        print(f"{k}: {v}")
    report = server.tenant_report()
    if report:
        print("\nper-tenant activity (slot telemetry):")
        for name, row in report.items():
            print(f"  {name}: " + ", ".join(
                f"{k}={v}" for k, v in row.items()))
    print("\nmetrics exposition:")
    print(server.registry.to_prometheus())
    out = getattr(args, "metrics_out", None)
    if out:
        import json

        with open(out, "w") as fh:
            json.dump(server.registry.to_dict(), fh, indent=1, sort_keys=True)
        print(f"wrote metrics JSON to {out}")
    assert stats["recompiles_after_warmup"] == 0, "tenant swap recompiled!"
    return stats


def serve_sharded_main(cfg, args) -> Dict:
    """Serve a mesh-sharded fabric: ONE tenant occupying every device.

    The slotted :class:`SNNServer` time-shares one small fabric between
    many tenants; this is the other end of the scale axis (DESIGN.md
    §15): a single network too large for one device, its ``(n, n)``
    weight matrix partitioned by destination columns over the
    ``("model",)`` mesh from ``cfg.snn_mesh``.  The serving loop is the
    continuous-admission chunk contract reused verbatim -- jitted
    ``engine.chunk`` calls threading the (mesh-resident) carry, zero
    recompiles after warmup -- just with D devices under each chunk.

    At >=16384 neurons the topology is the implicit all-to-all
    (``c=None``): ``W*C`` is ``W`` itself and the second 16 GiB buffer
    never exists (the 64k memory escape hatch).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import connectivity
    from repro.core.engine import TickCarry, TickEngine
    from repro.core.lif import LIFParams
    from repro.core.network_types import SNNParams, SNNState
    from repro.launch.mesh import make_snn_mesh
    from repro.parallel import snn_sharding
    from repro.util.env import ensure_host_device_count

    n, n_dev = cfg.n_neurons, cfg.snn_mesh
    have = ensure_host_device_count(n_dev)
    if have < n_dev:
        platform = jax.default_backend()
        if platform != "cpu":
            raise SystemExit(
                f"config {cfg.name!r} shards its fabric over {n_dev} "
                f"{platform} chips; this host has {have}")
        raise SystemExit(
            f"config {cfg.name!r} wants a {n_dev}-device mesh but jax sees "
            f"{have} CPU device(s) and its backend is already initialized; "
            f"to simulate the mesh on the host, re-run with "
            f"JAX_PLATFORMS=cpu XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_dev}")
    mesh = make_snn_mesh(n_dev)

    backend = cfg.snn_backend
    use_implicit = n > 4096          # c=None: no (n, n) mask at scale
    if use_implicit and backend in ("pallas", "pallas_fused"):
        print(f"backend {backend!r} needs an explicit c; the implicit "
              f"all-to-all fabric at n={n} serves on 'jnp'")
        backend = "jnp"
    engine = TickEngine(EngineOptions(
        mode=cfg.snn_mode, backend=backend, telemetry=True, mesh=mesh))

    # -- build the fabric, shard-local where it is large ------------------
    w = snn_sharding.make_sharded_dyadic_weights(n, mesh)
    if use_implicit:
        c = None
    else:
        c_np = connectivity.sparse_random(n, cfg.snn_density, seed=0)
        sstats = connectivity.shard_stats(c_np, n_dev)
        print(f"topology: density={cfg.snn_density}, edge imbalance "
              f"across {n_dev} shards = "
              f"{connectivity.shard_imbalance(sstats):.3f}")
        c = jax.device_put(
            jnp.asarray(c_np, jnp.float32),
            NamedSharding(mesh, PartitionSpec(None, "model")))
    n_in = min(n, 256)
    rng = np.random.default_rng(7)
    w_in = jnp.asarray(
        rng.integers(0, 8, (n_in, n)).astype(np.float32) * 0.25)
    params = SNNParams(w=w, c=c, w_in=w_in,
                       lif=LIFParams.make(n, v_th=1.0, leak=0.25, r_ref=1))
    rules = snn_sharding.snn_rules(mesh)
    params = snn_sharding.place(
        params, snn_sharding.params_specs(rules, params), mesh)
    # Seed the telemetry slot up front: a carry whose pytree STRUCTURE
    # changes between warmup and steady state would retrace once.
    from repro.obs.telemetry import TickTelemetry

    carry = TickCarry(state=SNNState.zeros((), n),
                      telem=TickTelemetry.zeros(()))
    # ... and commit it to the mesh as each chunk's output is: an
    # array's mesh is part of its abstract type, so an uncommitted seed
    # would trace the chunk program a second time on the first hand-off.
    carry = snn_sharding.place(
        carry, snn_sharding.carry_specs(rules, carry), mesh)

    chunk_ticks = max(1, cfg.snn_chunk_ticks)
    n_chunks = max(2, args.requests)
    traces = 0

    @jax.jit
    def chunk_fn(params, carry, ext):
        nonlocal traces
        traces += 1
        return engine.chunk(params, carry, ext, chunk_ticks)

    def _ext():
        spikes = rng.random((chunk_ticks, n_in)) < cfg.snn_rate
        return jnp.asarray(spikes, jnp.float32)

    print(f"serving sharded SNN fabric n={n} on a {n_dev}-device mesh "
          f"({backend} backend, {chunk_ticks}-tick chunks, "
          f"{n_chunks} chunks)")
    carry, raster = chunk_fn(params, carry, _ext())      # warmup / compile
    jax.block_until_ready(raster)
    warm_traces = traces
    # Emitted spikes, summed on device over every chunk the telemetry
    # accumulator also saw (the warmup chunk included).
    spikes_out = raster.sum()
    t0 = time.perf_counter()
    for _ in range(n_chunks):
        carry, raster = chunk_fn(params, carry, _ext())
        spikes_out = spikes_out + raster.sum()
    jax.block_until_ready(raster)
    dt = time.perf_counter() - t0

    ticks = n_chunks * chunk_ticks
    tel = carry.telem.summary(n)
    stats = {
        "mode": "sharded",
        "n_neurons": n,
        "n_devices": n_dev,
        "ticks": ticks,
        "ticks_per_s": ticks / dt,
        "synops_per_s": ticks / dt * float(n) * float(n),
        "recompiles_after_warmup": traces - warm_traces,
        "spikes_out": float(spikes_out),
    }
    for k, v in stats.items():
        print(f"{k}: {v}")
    print("telemetry: " + ", ".join(f"{k}={v:.4g}" for k, v in tel.items()))
    out = getattr(args, "metrics_out", None)
    if out:
        import json

        with open(out, "w") as fh:
            json.dump({**stats, "telemetry": tel}, fh, indent=1,
                      sort_keys=True)
        print(f"wrote metrics JSON to {out}")
    assert stats["recompiles_after_warmup"] == 0, "chunk loop recompiled!"
    return {**stats, "telemetry": tel}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture a jax.profiler trace of the serve run "
                         "into DIR (view with TensorBoard/Perfetto)")
    ap.add_argument("--metrics-out", metavar="PATH", default=None,
                    help="dump the metrics registry as JSON to PATH "
                         "(SNN server only)")
    args = ap.parse_args(argv)
    enable_compilation_cache()

    bundle = get_bundle(args.arch)
    cfg = bundle.smoke if args.smoke else bundle.model
    if cfg.family == "snn":
        if cfg.snn_mesh:
            return serve_sharded_main(cfg, args)
        return serve_snn_main(cfg, args)
    print(f"serving {cfg.name}: {M.n_params(cfg):,} params, "
          f"{args.slots} slots, {args.requests} requests")
    params = M.init(cfg, jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(4, 12))
        if cfg.family == "audio":
            prompt = rng.integers(0, cfg.vocab_size, (plen, cfg.n_codebooks))
        else:
            prompt = rng.integers(0, cfg.vocab_size, (plen,))
        reqs.append(ServeRequest(rid=i, prompt=prompt.astype(np.int32),
                                 max_new=args.max_new))
    with profile(args.profile):
        stats = serve(cfg, params, reqs, slots=args.slots,
                      max_len=args.max_len)
    for k, v in stats.items():
        if k == "results":
            continue
        print(f"{k}: {v}")
    return stats


if __name__ == "__main__":
    main()

"""Profiler trace -> the numbers the per-layer metrics read.

:func:`capture` records a ``jax.profiler`` trace of a short steady part of
the window, inside a host span named :data:`WINDOW`.  :func:`load` reduces
the ``.xplane.pb`` to a small dict (the committed test trace has the same
form)::

    {"window": [t0_ns, t1_ns],
     "devices": {"/device:TPU:0": [[name, start_ns, dur_ns, kind], ...]},
     "host": [[name, start_ns, dur_ns], ...]}

``name`` is the HLO instruction's name (``fused_stdp_step.12``,
``all-gather-start.3``); ``kind`` is ``"mosaic"`` for a Pallas kernel
(a ``tpu_custom_call``, or the custom fusion a ``vmap`` wraps one in) and
``""`` otherwise.  On a TPU the ops line nests a loop's body inside the
loop's own event; busy time takes the union, so nothing counts twice.
The rest of the module computes from that dict only.
"""
from __future__ import annotations

import contextlib
import glob
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

WINDOW = "bench/traced"
HOST_PREFIXES = ("bench/", "snn/")
CONTAINERS = ("while", "conditional", "call")


@contextlib.contextmanager
def capture(logdir: str, spans):
    import jax

    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        with spans(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def op_record(text: str, start: float, dur: float) -> list:
    """[name, start, dur, kind] of one op event, from the HLO text the
    TPU's ops line carries as the event's name."""
    name = text.split(" = ", 1)[0].strip().lstrip("%")
    mosaic = ('custom_call_target="tpu_custom_call"' in text
              or "kind=kCustom" in text)
    return [name, start, dur, "mosaic" if mosaic else ""]


def load(logdir: str) -> Dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    devices: Dict[str, List] = {}
    host: List = []
    cpu_ops: List = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "NON_CORE" not in \
                plane.name:
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append(op_record(e.name, e.start_ns, e.duration_ns))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                cpu_line = line.name.startswith("tf_XLAPjRtCpuClient")
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append([e.name, e.start_ns, e.duration_ns])
                    elif cpu_line and "hlo_op" in dict(e.stats):
                        cpu_ops.append(op_record(e.name, e.start_ns,
                                                 e.duration_ns))
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops       # CPU rehearsal only
    win = [h for h in host if h[0] == WINDOW]
    window = ([win[0][1], win[0][1] + win[0][2]] if win else
              [min(h[1] for h in host), max(h[1] + h[2] for h in host)])
    for ops in devices.values():
        ops.sort(key=lambda o: o[1])
    host.sort(key=lambda h: h[1])
    return {"window": window, "devices": devices, "host": host}


# -- interval arithmetic ---------------------------------------------------------

def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def subtract(a_set, b_set) -> List[Tuple[float, float]]:
    """``a_set`` minus ``b_set``; both are merged, sorted interval lists."""
    out, j = [], 0
    for a, b in a_set:
        cur = a
        while j < len(b_set) and b_set[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_set) and b_set[k][0] < b:
            if b_set[k][0] > cur:
                out.append((cur, b_set[k][0]))
            cur = max(cur, b_set[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# -- reductions ------------------------------------------------------------------

Match = Callable[[list], bool]


def window_s(tr: Dict) -> float:
    return (tr["window"][1] - tr["window"][0]) * 1e-9


def _ops(tr: Dict, dev: str, match: Optional[Match] = None):
    lo, hi = tr["window"]
    return clip([(o[1], o[1] + o[2]) for o in tr["devices"][dev]
                 if match is None or match(o)], lo, hi)


def busy_s(tr: Dict) -> Dict[str, float]:
    """Per device, seconds in which some op ran (union of intervals)."""
    return {d: length(union(_ops(tr, d))) * 1e-9 for d in tr["devices"]}


def op_seconds(tr: Dict, match: Match) -> Dict[str, float]:
    """Per device, summed duration of the matching ops in the window."""
    return {d: length(_ops(tr, d, match)) * 1e-9 for d in tr["devices"]}


def exposed_seconds(tr: Dict, match: Match) -> Dict[str, float]:
    """Per device, seconds in which a matching op ran and no other op did
    (e.g. a collective with no compute beside it)."""
    out = {}
    for d in tr["devices"]:
        mine = union(_ops(tr, d, match))
        rest = union(_ops(tr, d, lambda o: not match(o)
                          and not is_container(o)))
        out[d] = length(subtract(mine, rest)) * 1e-9
    return out


def idle_gaps(tr: Dict, dev: str) -> List[Tuple[str, float]]:
    """Idle intervals of ``dev`` inside the window, each labelled with the
    innermost benchmark or program host span that covers its midpoint."""
    lo, hi = tr["window"]
    busy = union(_ops(tr, dev))
    gaps = subtract([(lo, hi)], busy)
    hosts = [h for h in tr["host"] if h[0] != WINDOW]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        cover = [h for h in hosts if h[1] <= mid <= h[1] + h[2]]
        label = max(cover, key=lambda h: h[1])[0] if cover else \
            "program host code (no span)"
        out.append((label, (b - a) * 1e-9))
    return out


def op_label(o: list) -> str:
    """An op's name without its instance number, marked when it is a
    Pallas kernel: ``fused_stdp_step [mosaic]``, ``multiply_reduce_fusion``."""
    base = o[0].rsplit(".", 1)[0] if o[0].rsplit(".", 1)[-1].isdigit() \
        else o[0]
    return base + (" [mosaic]" if o[3] == "mosaic" else "")


def is_container(o: list) -> bool:
    return o[0].split(".", 1)[0] in CONTAINERS


def breakdown(tr: Dict, top: int = 10) -> Dict:
    """The device ops that took most time (mean over devices) and the
    longest idle gaps of the first device, by host span."""
    totals: Dict[str, float] = {}
    devs = sorted(tr["devices"])
    for d in devs:
        for o in tr["devices"][d]:
            if is_container(o):
                continue
            lab = op_label(o)
            totals[lab] = totals.get(lab, 0.0) + o[2] * 1e-9 / len(devs)
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(tr, devs[0]), key=lambda g: -g[1])[:top] \
        if devs else []
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def name_matcher(*prefixes: str, mosaic: Optional[bool] = None) -> Match:
    """Ops whose instruction name starts with one of ``prefixes`` (all ops
    when none are given), optionally only Pallas kernels or only others."""
    def match(o):
        if mosaic is not None and (o[3] == "mosaic") != mosaic:
            return False
        return not prefixes or o[0].startswith(prefixes)
    return match

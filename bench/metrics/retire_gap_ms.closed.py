"""Device idle milliseconds per retire round of the continuous serving
loop: from the end of each ``snn/readback`` span (the host holds the
round's counts, the device has drained) to the end of the next
``snn/chunk/*`` dispatch, averaged over the rounds whose readback ends
inside the traced window (mean over the cell's chips).  The stall the
host loop (retire, admit, refill, input assembly) imposes once a round.
A program without these spans reads nothing."""
from bench import trace


def read(run):
    tr = run.get("trace")
    if tr is None or not tr["devices"]:
        return None
    lo, hi = tr["window"]
    chunks = [(s, s + d) for n, s, d in tr["host"]
              if n.startswith("snn/chunk/")]
    busy = {dev: trace.union(trace.clip([(o[1], o[1] + o[2]) for o in ops],
                                        lo, hi))
            for dev, ops in tr["devices"].items()}
    stalls = []
    for name, start, dur in tr["host"]:
        end = start + dur
        if name != "snn/readback" or not lo <= end <= hi:
            continue
        nxt = min((c for c in chunks if c[0] >= end), default=None)
        if nxt is None or nxt[0] >= hi:
            continue                    # the trace stopped inside the round
        round_ = trace.clip([(end, nxt[1])], lo, hi)
        stalls.append(sum(trace.length(trace.subtract(round_, b))
                          for b in busy.values()) / len(busy))
    if not stalls:
        return None
    return 1e-6 * sum(stalls) / len(stalls)

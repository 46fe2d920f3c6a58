"""Share of the device's idle time in the traced window that no step of
the serving loop explains: idle gaps whose innermost covering host span
(``trace.idle_gaps``) is none, a whole ``snn/group/*`` or the
benchmark's ``bench/serve_continuous``, over all idle time (every chip of
the cell).  Host work added without a span of its own raises it."""
from bench import trace

UNATTRIBUTED = ("program host code (no span)", "bench/serve_continuous")


def unattributed(label: str) -> bool:
    return label in UNATTRIBUTED or label.startswith("snn/group/")


def read(run):
    tr = run.get("trace")
    if tr is None or not tr["devices"]:
        return None
    gaps = [g for dev in tr["devices"] for g in trace.idle_gaps(tr, dev)]
    total = sum(s for _, s in gaps)
    if total <= 0:
        return None
    return 100.0 * sum(s for lab, s in gaps if unattributed(lab)) / total

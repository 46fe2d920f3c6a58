"""Per tick, the all-gather time on a device with no other op running
beside it (mean over devices), in the traced part of the window: the
spike exchange of ``parallel/snn_sharding.py`` that compute does not
hide."""
from bench import trace

COLLECTIVE = trace.name_matcher("all-gather")


def read(run):
    tr, ticks = run.get("trace"), run.get("traced_ticks")
    if tr is None or not ticks:
        return None
    exposed = trace.exposed_seconds(tr, COLLECTIVE)
    if not exposed:
        return None
    return 1e3 * sum(exposed.values()) / len(exposed) / ticks

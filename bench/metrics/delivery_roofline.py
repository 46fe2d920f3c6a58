"""Share of its roofline the microcircuit's fan-out delivery reached in
the traced part of the window: the least time for the synaptic events it
delivered (``bench/work_microcircuit.py``: 2 FLOP, the fan-out entry's
bytes and a ring read-modify-write per event; events per tick from the
telemetry over the trace) over the delivery's device time."""
from bench import work, work_microcircuit as micro


def read(run):
    spent, ticks = run.get("delivery_s"), run.get("delivery_ticks")
    events, host_ticks = run.get("traced_events"), run.get("traced_ticks")
    if not spent or not ticks or not events or not host_ticks:
        return None
    events = events * ticks / host_ticks
    least = work.least_seconds(
        micro.delivery_flops(events),
        micro.delivery_bytes(events, run["entry_bytes"]), run["peak"])
    return 100.0 * least / spent

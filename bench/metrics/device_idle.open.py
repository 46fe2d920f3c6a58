"""Share of the traced window in which no op ran on the device (mean over
the cell's chips): one minus the union of the op intervals over the
window."""
from bench import trace


def read(run):
    tr = run.get("trace")
    if tr is None or not tr["devices"]:
        return None
    busy = trace.busy_s(tr)
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / trace.window_s(tr))

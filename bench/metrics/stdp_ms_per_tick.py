"""Device time of the ``fused_stdp_step`` kernel per tick the dense chunk
program executed, in the traced part of the window."""
from bench import trace

KERNEL = trace.name_matcher("fused_stdp_step")


def read(run):
    tr, traced = run.get("trace"), run.get("traced")
    if tr is None or not traced:
        return None
    ticks = traced["chunks"]["pallas_fused"] * run["chunk_ticks"]
    spent = sum(trace.op_seconds(tr, KERNEL).values())
    if ticks <= 0 or spent <= 0:
        return None
    return 1e3 * spent / ticks

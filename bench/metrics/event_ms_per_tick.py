"""Device time of the event chunk program (the fan-in gather path of
``core/engine.py`` and ``kernels/ops.py``) per tick it executed, in the
traced part of the window."""
from bench import trace

PROGRAM = trace.name_matcher("tick/event")


def read(run):
    tr, traced = run.get("trace"), run.get("traced")
    if tr is None or not traced:
        return None
    ticks = traced["chunks"]["event"] * run["chunk_ticks"]
    spent = sum(trace.op_seconds(tr, PROGRAM).values())
    if ticks <= 0 or spent <= 0:
        return None
    return 1e3 * spent / ticks

"""Share of its roofline the ``fused_tick`` kernel reached in the traced
part of the window: the least time for the useful work it served (2 FLOP
and one u8 register per existing synapse, plus the membrane, per useful
slot-tick, see ``bench/work.py``) over the summed device time of its
events.

Under the server's slot ``vmap`` the kernel runs as an unnamed custom
fusion (``closed_call.N``); it is the dense chunk program's one Pallas
kernel besides ``fused_stdp_step``, and is found as such."""
from bench import trace, work

STDP = trace.name_matcher("fused_stdp_step")


def KERNEL(o):
    return o[3] == "mosaic" and not STDP(o)


def read(run):
    tr, done = run.get("trace"), run.get("traced_work", {}).get("pallas_fused")
    if tr is None or not done or not done["slot_ticks"]:
        return None
    spent = sum(trace.op_seconds(tr, KERNEL).values())
    if spent <= 0:
        return None
    least = work.least_seconds(done["flops"], done["bytes"], run["peak"])
    return 100.0 * least / spent

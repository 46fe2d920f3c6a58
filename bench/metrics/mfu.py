"""Model FLOP/s utilisation over the window: 2 FLOP per existing synapse
per useful slot-tick (``bench/work.py``), over the window, over the
chips' summed bf16 peak."""
from bench import work


def read(run):
    if not run.get("synops"):
        return None
    flops = work.tick_flops(run["synops"])
    return 100.0 * flops / run["window_s"] / (
        run["chips"] * run["peak"]["flops_bf16"])

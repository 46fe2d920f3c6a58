"""Model FLOP/s utilisation of the microcircuit over the window: the
model's operations (2 FLOP per delivered synaptic event, from the
telemetry's ``syn_events``, and the neuron update per neuron per tick,
``bench/work_microcircuit.py``) over the window, over the chip's bf16
peak.  The whole step's share."""


def read(run):
    flops = run.get("model_flops")
    if not flops:
        return None
    return 100.0 * flops / run["window_s"] / (
        run["chips"] * run["peak"]["flops_bf16"])

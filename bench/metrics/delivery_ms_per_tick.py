"""Device time of the microcircuit's fan-out delivery per tick, in the
traced part of the window: the delivery loop's events (one per tick, the
``tick/event/fan_out/deliver`` scope) on the chip's own timeline, over
the ticks they cover."""


def read(run):
    spent, ticks = run.get("delivery_s"), run.get("delivery_ticks")
    if not spent or not ticks:
        return None
    return 1e3 * spent / ticks

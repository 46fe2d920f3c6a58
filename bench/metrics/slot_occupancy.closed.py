"""Useful slot-ticks (inside a live request's budget) over the slot-ticks
the server executed in the window, from the server's own counters
(``snn_useful_slot_ticks_total`` / ``snn_slot_ticks_total``)."""


def read(run):
    c = run["counters"]
    if c["slot_ticks"] <= 0:
        return None
    return 100.0 * c["useful"] / c["slot_ticks"]

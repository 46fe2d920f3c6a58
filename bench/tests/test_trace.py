"""The trace-to-metrics reduction, checked on a recorded TPU trace: two
chunks of the dense serving program on a TPU v5 lite (``data/``), and on
hand-made intervals."""
import json
import os

import numpy as np
import pytest

from bench import harness, trace, work

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tpu_v5e_dense_chunk.json")


@pytest.fixture(scope="module")
def tr():
    with open(DATA) as fh:
        return json.load(fh)


def _mask(intervals, lo, hi):
    """Busy microseconds by brute force: one boolean per microsecond."""
    m = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            m[int((a - lo) // 1000):int((b - lo) // 1000)] = True
    return m


def test_busy_is_the_union_of_op_intervals(tr):
    lo, hi = tr["window"]
    ops = tr["devices"]["/device:TPU:0"]
    want = _mask([(o[1], o[1] + o[2]) for o in ops], lo, hi).sum() * 1e-6
    got = trace.busy_s(tr)["/device:TPU:0"]
    assert got == pytest.approx(want, abs=2e-4 + 1e-6 * len(ops))
    assert got < trace.window_s(tr)
    # nested loop events do not count twice: busy is far below the sum
    assert got < 0.6 * sum(o[2] for o in ops) * 1e-9


def test_idle_gaps_fill_the_rest_of_the_window(tr):
    gaps = trace.idle_gaps(tr, "/device:TPU:0")
    busy = trace.busy_s(tr)["/device:TPU:0"]
    assert sum(g for _, g in gaps) == pytest.approx(
        trace.window_s(tr) - busy, rel=1e-9)
    labels = {lab for lab, _ in gaps}
    assert labels <= {"bench/feeder", "bench/on_complete",
                      "snn/chunk/pallas_fused", "program host code (no span)"}


def test_kernels_are_found_by_name(tr):
    stdp = trace.op_seconds(tr, trace.name_matcher("fused_stdp_step"))
    tick = trace.op_seconds(tr, trace.name_matcher(mosaic=True))
    dev = "/device:TPU:0"
    hand_stdp = sum(o[2] for o in tr["devices"][dev]
                    if o[0].startswith("fused_stdp_step")
                    and o[1] >= tr["window"][0]
                    and o[1] + o[2] <= tr["window"][1]) * 1e-9
    assert stdp[dev] == pytest.approx(hand_stdp, rel=0.02)
    # about two chunks of 8 ticks: one learning pass per tick and one
    # vmapped tick kernel per slot (4) per tick, cut at the window's edges
    n_stdp = sum(o[0].startswith("fused_stdp_step")
                 for o in tr["devices"][dev])
    n_tick = sum(o[3] == "mosaic" and not o[0].startswith("fused_stdp")
                 for o in tr["devices"][dev])
    assert n_stdp >= 12 and abs(n_tick - 4 * n_stdp) <= 4
    assert tick[dev] > stdp[dev] > 0


def test_readers_on_the_recorded_trace(tr):
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    # the work two chunks of four slots could at most have served
    syn = 4096 * 4095
    done = {"flops": 64 * work.tick_flops(syn),
            "bytes": 64 * work.tick_bytes(syn, 4096), "slot_ticks": 64}
    run = {"trace": tr, "peak": peak, "chips": 1, "chunk_ticks": 8,
           "traced": {"chunks": {"pallas_fused": 2, "event": 0}},
           "traced_work": {"pallas_fused": done}}
    roof = harness.Cell.reader("fused_tick_roofline").read(run)
    assert 0 < roof <= 100
    stdp = harness.Cell.reader("stdp_ms_per_tick").read(run)
    assert stdp == pytest.approx(1e3 * sum(trace.op_seconds(
        tr, trace.name_matcher("fused_stdp_step")).values()) / 16)
    idle = harness.Cell.reader("device_idle.closed").read(run)
    assert 0 < idle < 100
    assert harness.Cell.reader("event_ms_per_tick").read(run) is None
    b = trace.breakdown(tr)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert not any(name.startswith("while") for name, _ in b["device_ops"])


def test_interval_arithmetic_by_hand():
    assert trace.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    tr = {"window": [0, 100], "host": [["bench/readback", 40, 30]],
          "devices": {"d": [["fusion.1", 0, 20, ""],
                            ["all-gather.2", 10, 20, ""],
                            ["while.3", 0, 100, ""],
                            ["fusion.4", 80, 10, ""]]}}
    ag = trace.name_matcher("all-gather")
    # the all-gather runs 10..30; compute covers 10..20 of it; the loop
    # around everything is not compute
    assert trace.exposed_seconds(tr, ag)["d"] == pytest.approx(10e-9)
    gaps = trace.idle_gaps({**tr, "devices": {"d": [
        o for o in tr["devices"]["d"] if not o[0].startswith("while")]}}, "d")
    assert gaps == [("bench/readback", pytest.approx(50e-9)),
                    ("program host code (no span)", pytest.approx(10e-9))]

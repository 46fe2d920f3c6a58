"""Host spans of the continuous serving loop, and the benchmark readers
that turn them into per-layer metrics.

One span per step of ``SNNServer.serve_continuous`` (admit, fill,
assemble, chunk, readback, telemetry, retire, inside one group per
resident program) lands in the profiler's host plane on the device
clock, so every idle gap of the device has the name of the step the host
was in.  ``bench.trace.load`` keeps them; ``retire_gap_ms.closed`` and
``idle_unattributed.closed`` read them.
"""
import glob
import os

import jax
import pytest

from bench import harness, trace
from repro.launch.serve import SNNServer, make_demo_requests, make_demo_tenants

jax.config.update("jax_platform_name", "cpu")

STEPS = ("snn/group/jnp", "snn/admit", "snn/fill/jnp", "snn/assemble",
         "snn/chunk/jnp", "snn/readback", "snn/telemetry", "snn/retire")


def _tiny_server():
    server = SNNServer(n_max=16, slots=2, max_ticks=8, chunk_ticks=2)
    names = make_demo_tenants(server, 2, seed=0)
    assert server.tenants[names[-1]].plastic
    return server, names


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Six requests of 4-8 ticks through 2 slots in chunks of 2 (several
    retire rounds), two queued and four fed one per poll, under a
    profiler capture."""
    server, names = _tiny_server()
    reqs = make_demo_requests(server, names, 6, seed=4)
    late = list(reqs[2:])
    completed = []
    logdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(logdir)
    try:
        server.serve_continuous(
            reqs[:2], feeder=lambda: late.pop(0) if late else None,
            on_complete=completed.append)
    finally:
        jax.profiler.stop_trace()
    return server, reqs, completed, logdir, trace.load(logdir)


def _named(tr, prefix):
    return [h for h in tr["host"] if h[0].startswith(prefix)]


def _inside(h, outer):
    return any(o[1] <= h[1] and h[1] + h[2] <= o[1] + o[2] for o in outer)


def test_every_step_has_a_span(served):
    *_, tr = served
    names = {h[0] for h in tr["host"]}
    assert set(STEPS) <= names, set(STEPS) - names


def test_slot_steps_lie_inside_a_group(served):
    *_, tr = served
    groups = _named(tr, "snn/group/")
    steps = (_named(tr, "snn/fill/") + _named(tr, "snn/readback")
             + _named(tr, "snn/retire"))
    assert groups and steps
    assert all(_inside(h, groups) for h in steps)


def test_one_retire_per_request_and_one_readback_per_round(served):
    _, reqs, completed, _, tr = served
    assert len(completed) == len(reqs)
    assert len(_named(tr, "snn/retire")) == len(reqs)
    # every retire of a round shares the round's completion stamp
    rounds = {r.t_done for r in completed}
    assert len(rounds) > 1
    assert len(_named(tr, "snn/readback")) == len(rounds)


def test_fill_and_retire_carry_the_request_id(served):
    from jax.profiler import ProfileData

    _, reqs, _, logdir, _ = served
    path = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    rids = {"snn/fill/jnp": [], "snn/retire": []}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in rids:
                    rids[e.name].append(dict(e.stats)["rid"])
    want = sorted(r.rid for r in reqs)
    assert sorted(rids["snn/fill/jnp"]) == want
    assert sorted(rids["snn/retire"]) == want


def test_queue_wait_is_observed_once_per_request():
    server, names = _tiny_server()
    reqs = make_demo_requests(server, names, 6, seed=5)
    stats = server.serve_continuous(reqs)
    h = server.registry.get("snn_queue_wait_seconds")
    assert h.count() == stats["requests_served"] == len(reqs)
    waits = [r.t_admit - r.t_submit for r in reqs]
    for r, w in zip(reqs, waits):
        assert 0.0 <= w <= r.t_done - r.t_submit
    assert h.sum() == pytest.approx(sum(waits))
    assert not hasattr(stats["results"][0], "t_admit")


def test_chunk_seconds_histogram_is_gone():
    server, _ = _tiny_server()
    assert "snn_chunk_seconds" not in server.registry.to_prometheus()


# -- the readers, on hand-made traces ---------------------------------------

MS = 1_000_000      # ns


def _trace(busy_ms, host_ms):
    """A reduced trace of a 50 ms window: one device's busy intervals and
    the host spans, in ms."""
    ops = [[f"op.{k}", a * MS, (b - a) * MS, ""]
           for k, (a, b) in enumerate(busy_ms)]
    return {"window": [0, 50 * MS], "devices": {"/device:TPU:0": ops},
            "host": [[n, a * MS, (b - a) * MS] for n, a, b in host_ms]}


# device busy 0-10, 13-30, 35-50: idle 10-13 and 30-35
BUSY = [(0, 10), (13, 30), (35, 50)]
TWO_ROUNDS = _trace(BUSY, [
    ("snn/readback", 8, 10), ("snn/retire", 10, 11.5),
    ("snn/assemble", 11.5, 12), ("snn/chunk/pallas_fused", 12, 13),
    ("snn/readback", 28, 30), ("snn/retire", 30, 32.5),
    ("snn/fill/pallas_fused", 32.5, 34), ("snn/chunk/pallas_fused", 34, 35)])
NO_READBACK = _trace(BUSY, [("snn/chunk/pallas_fused", 12, 13),
                            ("snn/chunk/pallas_fused", 34, 35)])
UNDER_STEPS = _trace(BUSY, [("snn/group/pallas_fused", 0, 50),
                            ("snn/retire", 9, 14), ("bench/feeder", 29, 36)])
UNLABELLED = _trace(BUSY, [("snn/group/pallas_fused", 0, 50),
                           ("bench/serve_continuous", 29, 36)])
# 3 ms under a step, 5 ms under the group alone
HALF_LABELLED = _trace(BUSY, [("snn/group/pallas_fused", 0, 50),
                              ("snn/assemble", 10, 13)])


@pytest.mark.parametrize("metric,tr,want", [
    ("retire_gap_ms.closed", TWO_ROUNDS, 4.0),
    ("retire_gap_ms.closed", NO_READBACK, None),
    ("idle_unattributed.closed", UNDER_STEPS, 0.0),
    ("idle_unattributed.closed", UNLABELLED, 100.0),
    ("idle_unattributed.closed", HALF_LABELLED, 62.5),
], ids=["two_rounds", "no_readback", "under_steps", "unlabelled",
        "part_labelled"])
def test_reader(metric, tr, want):
    got = harness.Cell.reader(metric).read({"trace": tr})
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, abs=1e-9)
    assert harness.Cell.reader(metric).read({"trace": None}) is None


def test_readers_are_listed_for_the_dense_cell_only():
    new = {"retire_gap_ms.closed", "idle_unattributed.closed"}
    dense = {m["name"] for m in
             harness.Cell("fused4k-dense-closed").per_layer()}
    stream = {m["name"] for m in harness.Cell("fabric64k-stream").per_layer()}
    assert new <= dense and not new & stream

"""The microcircuit cell at its smoke size on the CPU: a sound run (with
spill blocks: the smoke block is 2 reads of 64 fan-out entries) comes
out correct, and each fault planted in the program's fan-out path comes
out not correct -- delays collapsed to one tick, the fan-out of one
spiking source dropped each tick, every spill block skipped, weights
held in bfloat16 -- as do both controls in the program's place."""
import dataclasses
import json

import jax.numpy as jnp
import pytest

from bench import harness, run
from repro.core import connectivity
from repro.core import engine as engine_mod

CELL = "microcircuit-stream"


def _run(capsys, seconds="3", trace="0"):
    rc = run.main(["--workload", CELL, "--seed", "4000000013",
                   "--seconds", seconds, "--trace", trace, "--smoke"],
                  require_tpu=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _wrap_fan_out(monkeypatch, edit):
    orig = connectivity.build_fan_out

    def broken(*a, **kw):
        return edit(orig(*a, **kw))

    monkeypatch.setattr(connectivity, "build_fan_out", broken)


def _wrap_read_block(monkeypatch, edit):
    orig = engine_mod.read_block

    def broken(csum, reads, block, k):
        src, part, live = orig(csum, reads, block, k)
        return src, part, edit(live, src, csum, block)

    monkeypatch.setattr(engine_mod, "read_block", broken)


def test_sound_run_is_correct_and_spills(capsys):
    line = _run(capsys)
    assert line["correct"] is True
    assert line["attempted"] > 0
    assert set(line["checks"]) == {"count_mismatch", "state_mismatch",
                                   "v_mismatch"}


def test_sound_traced_run_reports_the_whole_step_share(capsys):
    line = _run(capsys, seconds="4", trace="1")
    assert line["correct"] is True
    assert line["metrics"]["mfu.microcircuit"]["value"] > 0


def test_delays_collapsed_to_one_tick_are_caught(capsys, monkeypatch):
    _wrap_fan_out(monkeypatch, lambda fo: dataclasses.replace(
        fo, delays=jnp.ones_like(fo.delays)))
    assert _run(capsys)["correct"] is False


def test_a_dropped_fan_out_is_caught(capsys, monkeypatch):
    # every read of the tick's first spiking source
    _wrap_read_block(monkeypatch, lambda live, src, csum, b: live & (
        src != jnp.searchsorted(csum, 1)))
    assert _run(capsys)["correct"] is False


def test_a_skipped_spill_block_is_caught(capsys, monkeypatch):
    _wrap_read_block(monkeypatch, lambda live, src, csum, b: live & (b == 0))
    assert _run(capsys)["correct"] is False


def test_bfloat16_weights_are_caught(capsys, monkeypatch):
    _wrap_fan_out(monkeypatch, lambda fo: dataclasses.replace(
        fo, weights=fo.weights.astype(jnp.bfloat16).astype(jnp.float32)))
    assert _run(capsys)["correct"] is False


@pytest.mark.parametrize("variant", ["bfloat16", "delay_one"])
def test_controls_in_the_programs_place_fail(capsys, variant):
    """The program's readings pass every limit; each control in its
    place fails at least one."""
    import argparse

    import jax

    cell = harness.Cell(CELL)
    harness.prepare_env()
    a = argparse.Namespace(seed=4000000017, seconds=3, trace=0, smoke=True)
    ctx = run.Ctx(cell, a, harness.CompileClock(), harness.Spans(),
                  jax.devices()[:1])
    system = cell.system()
    res = system.run(ctx)
    limits = ctx.config["limits"]
    assert all(c["value"] <= limits[k] for k, c in res["checks"].items())
    ctl = system.control_checks(ctx, res, variant)
    assert any(c["value"] > limits[k] for k, c in ctl.items())


def test_delivery_is_found_in_a_trace_with_or_without_scopes():
    """Two ticks of a request program: the outer scan ``while``, one
    delivery ``while`` per tick holding the scatter's custom fusion,
    and a Poisson ``while`` beside it."""
    from bench.systems import microcircuit as mc

    meta = ', metadata={op_name="jit(step)/while/body/tick/event/fan_out/' \
        'deliver/while"}'
    scatter = "%fusion.9 = f32[8] fusion(%a, %b), kind=kCustom"

    def ops(scope):
        tag = meta if scope else ""
        out = [("%while.1 = (s32[]) while(%t)", 0, 1000)]
        for t0 in (100, 500):
            out += [("%while.2 = (s32[]) while(%p)", t0, 50),
                    ("%while.3 = (s32[]) while(%r)" + tag, t0 + 60, 200),
                    ("%while.4 = (s32[]) while(%g)" + tag, t0 + 70, 20),
                    (scatter, t0 + 100, 100)]
        # a relayout loop around a small custom fusion, once
        return out + [("%while.5 = (s32[]) while(%x)", 300, 30),
                      ("%fusion.8 = f32[8] fusion(%c), kind=kCustom", 305, 5)]

    for scope in (True, False):
        got = mc.delivery_loops(ops(scope))
        assert got["ticks"] == 2
        assert got["seconds"] == pytest.approx(400e-9)
    assert mc.delivery_loops([("%fusion.1 = f32[] fusion()", 0, 5)]) is None


def test_readers():
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"delivery_s": 0.2, "delivery_ticks": 100, "traced_events": 2e7,
           "traced_ticks": 200, "entry_bytes": {"target": 4, "weight": 4,
                                                "delay": 1},
           "peak": peak, "window_s": 51.0, "chips": 1, "model_flops": 1e12}
    assert harness.Cell.reader("delivery_ms_per_tick").read(run) == 2.0
    roof = harness.Cell.reader("delivery_roofline").read(run)
    # 1e7 events x 17 bytes at 819 GB/s over 0.2 s
    assert roof == pytest.approx(100 * 1.7e8 / 819e9 / 0.2)
    mfu = harness.Cell.reader("mfu.microcircuit").read(run)
    assert mfu == pytest.approx(100 * 1e12 / 51.0 / 197e12)
    for name in ("delivery_ms_per_tick", "delivery_roofline",
                 "mfu.microcircuit"):
        assert harness.Cell.reader(name).read({"peak": peak}) is None


def test_work_counts():
    from bench import work_microcircuit as work

    entry = {"target": 4, "weight": 4, "delay": 1}
    assert work.event_bytes(entry) == 17
    assert work.delivery_flops(10) == 20
    assert work.step_flops(10, 3) == 20 + 18
    assert work.step_bytes(10, 3, entry) == 170 + 72

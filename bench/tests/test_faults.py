"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole run of a cell at its smoke size on the CPU (the
harness's look for a chip skipped), with one fault planted in the
program: a step that returns its state unchanged, half of the slots
left out, the spike exchange between chips left out, or an answer
altered where it is produced.  The control test puts the plain
reference, computed in bfloat16, in the program's place.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import control, run
from repro.core import engine as engine_mod
from repro.launch import serve

SERVING = "fused4k-dense-closed"
STREAM = "fabric64k-stream"


def _run(capsys, workload, seconds="3"):
    rc = run.main(["--workload", workload, "--seed", "4000000007",
                   "--seconds", seconds, "--trace", "0", "--smoke"],
                  require_tpu=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _unchanged(self, params, carry, ext_seq, n_ticks, **kw):
    n = carry.state.lif.y.shape[-1]
    return carry, jnp.zeros((n_ticks, n), jnp.float32)


def _wrap_chunk_fn(monkeypatch, edit):
    orig = serve.SNNServer._chunk_fn

    def broken(self, *a, **kw):
        carry, counts = orig(self, *a, **kw)
        return edit(carry, counts)

    monkeypatch.setattr(serve.SNNServer, "_chunk_fn", broken)


def test_sound_traced_run_is_correct(capsys, tmp_path):
    rc = run.main(["--workload", SERVING, "--seed", "4000000007",
                   "--seconds", "4", "--trace", "1", "--smoke",
                   "--dump", str(tmp_path)], require_tpu=False)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert "slot_occupancy.closed" in line["metrics"]
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert len(list(tmp_path.glob("*.trace.json"))) == 1


def test_state_unchanged_is_caught(capsys, monkeypatch):
    monkeypatch.setattr(engine_mod.TickEngine, "chunk", _unchanged)
    assert _run(capsys, SERVING)["correct"] is False


def test_half_the_slots_left_out_is_caught(capsys, monkeypatch):
    _wrap_chunk_fn(monkeypatch, lambda c, k: (c, k.at[k.shape[0] // 2:]
                                              .set(0.0)))
    assert _run(capsys, SERVING)["correct"] is False


def test_altered_answer_is_caught(capsys, monkeypatch):
    _wrap_chunk_fn(monkeypatch, lambda c, k: (c, k.at[0, :].add(1.0)))
    assert _run(capsys, SERVING)["correct"] is False


def test_sound_stream_is_correct(capsys):
    assert _run(capsys, STREAM)["correct"] is True


def test_stream_state_unchanged_is_caught(capsys, monkeypatch):
    monkeypatch.setattr(engine_mod.TickEngine, "chunk", _unchanged)
    assert _run(capsys, STREAM)["correct"] is False


def test_stream_exchange_left_out_is_caught(capsys, monkeypatch):
    def local_only(x, axis_name, *, axis=0, tiled=False, **kw):
        # each shard sees only its own columns' spikes, repeated
        return jnp.concatenate([x] * 4, axis=axis)

    monkeypatch.setattr(jax.lax, "all_gather", local_only)
    assert _run(capsys, STREAM)["correct"] is False


def test_stream_altered_answer_is_caught(capsys, monkeypatch):
    orig = engine_mod.TickEngine.chunk

    def flipped(self, *a, **kw):
        carry, raster = orig(self, *a, **kw)
        return carry, raster.at[0, -1].set(1.0 - raster[0, -1])

    monkeypatch.setattr(engine_mod.TickEngine, "chunk", flipped)
    assert _run(capsys, STREAM)["correct"] is False


@pytest.mark.parametrize("workload", [SERVING, STREAM])
def test_control_in_the_programs_place_fails(capsys, workload):
    """Lower readings (the program) pass every limit; the bfloat16
    reference in its place fails at least one."""
    from bench import harness

    assert control.main(["--workload", workload, "--seconds", "3",
                         "--seeds", "11", "--smoke"], require_tpu=False) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    limits = harness.Cell(workload).config["limits"]
    assert all(v <= limits[k] for k, v in last["lower_readings"].items())
    assert any(v > limits[k] for k, v in last["upper_readings"].items())

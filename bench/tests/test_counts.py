"""Work counts against hand counts, the peaks table, and the device
check."""
import numpy as np
import pytest

from bench import fabric, harness, peaks, traffic, work


@pytest.mark.parametrize("spec, hand", [
    ({"kind": "dense", "n": 4}, 4 * 3),
    # layers 4 -> 5 -> 3 of 12 neurons
    ({"kind": "layered", "n": 12}, 4 * 5 + 5 * 3),
    ({"kind": "ring", "n": 5, "k": 2}, 10),
])
def test_synapses_match_hand_counts_and_topology(spec, hand):
    spec = dict(spec, name="t")
    assert fabric.synapses(spec) == hand
    t = fabric.build_tenants(0, [spec])["t"]
    assert int(np.asarray(t["c"]).sum()) == hand


def test_sparse_synapses_are_the_expected_count():
    spec = {"name": "s", "kind": "sparse", "n": 200, "density": 0.1}
    assert fabric.synapses(spec) == round(0.1 * 200 * 199)
    got = int(np.asarray(fabric.build_tenants(1, [spec])["s"]["c"]).sum())
    assert abs(got - fabric.synapses(spec)) < 5 * np.sqrt(3980)


def test_flops_and_bytes_per_tick():
    # 12 synapses, 4 neurons: 2 FLOP and one u8 register per synapse,
    # 8 bytes of membrane per neuron.
    assert work.tick_flops(12) == 24
    assert work.tick_bytes(12, 4) == 12 + 32
    peak = peaks.peaks("TPU v5 lite")
    assert work.least_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    assert work.least_seconds(1.0, 819e9, peak) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v99")
    assert peaks.SOURCE


def test_cpu_platform_is_refused():
    with pytest.raises(harness.NoDevice):
        harness.require_chips(1, "tpu")
    with pytest.raises(harness.NoDevice):
        harness.require_chips(64, "cpu")


def test_run_prints_no_result_off_a_tpu(capsys):
    from bench import run

    assert run.main(["--workload", "fused4k-dense-closed", "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_mix_is_the_same_multiset_for_every_seed():
    tr = {"tenants": ["a", "b", "c"], "zipf_s": 1.0, "pool": 200, "block": 100,
          "budgets": [[0.75, 2, 4], [0.25, 32, 32]],
          "input": {"rate": 0.05, "levels": [80, 255]}}
    one = traffic.pool(tr, 1, {"a": 8, "b": 8, "c": 8})
    two = traffic.pool(tr, 2 ** 33 + 5, {"a": 8, "b": 8, "c": 8})
    key = lambda p: sorted((d.tenant, d.budget) for d in p)
    assert key(one) == key(two)
    assert [d.tenant for d in one] != [d.tenant for d in two]
    shares = [sum(d.tenant == t for d in one[:100]) for t in "abc"]
    assert shares == [55, 27, 18]       # 1 : 1/2 : 1/3 of each block of 100
    assert key(one[:100]) == key(one[100:])
    due = traffic.arrivals({"rate_per_s": 10.0}, 3, 5.0)
    assert len(due) == 50 and due[0] == 0.0 and due[-1] < 5.0
    assert np.all(np.diff(due) > 0)

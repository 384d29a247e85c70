"""The reducer on traces with known answers: one written by hand (every
interval chosen, data/synthetic.xspace.txt) and one recorded on a v5e
(data/v5e_small.xplane.pb, see its .json for how it was made)."""

import json
import os

import jax.profiler
import pytest

from benchmarks.harness import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture()
def synthetic(tmp_path):
    with open(os.path.join(DATA, "synthetic.xspace.txt")) as fh:
        text = "".join(line for line in fh if not line.startswith("#"))
    folder = tmp_path / "plugins" / "profile" / "2026_01_01"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_union_merges_overlaps():
    total, merged = trace_reduce.union_ns(
        [(5, 7), (0, 4), (2, 6), (10, 12), (12, 13)])
    assert total == 7 + 3 and merged == [[0, 7], [10, 13]]


def test_synthetic_trace_has_the_chosen_answers(synthetic):
    out = trace_reduce.reduce(trace_reduce.find_xplane(synthetic))
    d0, d1 = out["devices"][0], out["devices"][1]
    assert d0["busy_s"] == pytest.approx(9000e-9)      # overlap counted once
    assert d1["busy_s"] == pytest.approx(5000e-9)
    assert out["busy_s"] == pytest.approx(7000e-9)     # mean over devices
    assert out["span_s"] == pytest.approx(21000e-9)
    assert d0["collective_s"] == pytest.approx(4000e-9)
    assert out["collective_s"] == pytest.approx(3000e-9)
    assert out["events"] == 7
    ops = dict(map(tuple, out["device_ops"]))
    assert list(ops)[:2] == ["module jit_step", "fusion.1"]
    assert ops["module jit_step"] == pytest.approx(21000e-9)
    assert "while.7" not in ops                        # a container
    assert ops["fusion.1"] == pytest.approx(9000e-9)
    assert ops["all-reduce.3"] == pytest.approx(6000e-9)
    assert ops["copy.2"] == pytest.approx(1000e-9)
    # gaps on device 0, longest first, named by the tightest host event
    assert out["idle_gaps"][0] == ["python: TransferFromDevice",
                                   pytest.approx(8000e-9)]
    assert out["idle_gaps"][1] == ["python: encode_png",
                                   pytest.approx(4000e-9)]
    # idle share of a 21 us slice: 1 - 7/21
    assert 1 - out["busy_s"] / 21000e-9 == pytest.approx(2 / 3)


def test_recorded_v5e_trace():
    """Answers summed by hand from the 24 events (data/v5e_small.json)."""
    with open(os.path.join(DATA, "v5e_small.json")) as fh:
        want = json.load(fh)
    path = os.path.join(DATA, "v5e_small.xplane.pb")
    out = trace_reduce.reduce(path)
    assert sorted(out["devices"]) == want["devices"]
    assert out["events"] == want["events"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["collective_s"] == 0.0
    assert out["device_ops"][0][0] == want["top_op"]
    assert out["device_ops"][1] == [want["top_leaf"][0],
                                    pytest.approx(want["top_leaf"][1])]
    assert "while" not in dict(map(tuple, out["device_ops"]))
    assert out["idle_gaps"][0][1] == pytest.approx(want["longest_gap_s"])
    # the while's own time is busy time no leaf op accounts for
    leaves = sum(seconds for _, seconds in trace_reduce.reduce(
        path, top=10 ** 6, modules=0)["device_ops"])
    assert leaves == pytest.approx(want["leaf_ops_s"], rel=1e-9)
    assert leaves < out["busy_s"] < out["span_s"]

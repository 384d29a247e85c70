"""Every cell's file, end to end on the CPU through run.py."""

import os

import pytest

from benchmarks.tests import rehearsal

MANIFEST = rehearsal.manifest_with_prepared()
CELLS = [(w["name"], w["chips"]) for w in MANIFEST["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.make_root(str(tmp_path_factory.mktemp("bench")))


def metric_names(kind, cell):
    return {m["name"] for m in MANIFEST[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell,chips", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contract_line(root, cell, chips, trace):
    rc, result, output = rehearsal.drive(root, cell, trace, chips)
    assert rc == 0 and result is not None, output[-3000:]
    want = set(rehearsal.RESULT_KEYS) | ({"breakdown"} if trace else set())
    assert set(result) == want
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    assert result["device"]["count"] == chips
    assert result["device"]["platform"] == "cpu"   # a rehearsal, no speed
    kind = "per_layer" if trace else "end_to_end"
    reported = set(result["metrics"])
    # the CPU reports no memory, so peak_hbm_gib's reader returns nothing
    assert reported <= metric_names(kind, cell)
    assert metric_names(kind, cell) - reported <= {"peak_hbm_gib"}
    for value in result["metrics"].values():
        assert set(value) == {"value", "unit"}
        assert isinstance(value["value"], float)
    if trace:
        assert result["device"]["busy_s"] > 0
        assert result["device"]["window_s"] > result["device"]["busy_s"]
        assert 1 <= len(result["breakdown"]["device_ops"]) <= 10
        assert len(result["breakdown"]["idle_gaps"]) <= 10
    assert "nothing compiled inside the window" in output


def test_refuses_a_machine_without_the_chip(root):
    """No switch in the child: the CPU is refused, no result is printed."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, os.path.join(rehearsal.REPO, "benchmarks", "run.py"),
         "--workload", CELLS[0][0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=rehearsal.REPO, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout

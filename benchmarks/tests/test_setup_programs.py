"""The three set-up metrics that read ``serving.programs`` of
/internal/status (how each stage's program came to be: loaded from the
store beside the compile cache, or traced), on canned status blocks."""

import json
import os

import pytest

from benchmarks.harness import files

BENCH = files.Bench(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

#: a warm start of an SDXL cell, the first run of an empty cache, and a
#: program that has no such block (the parent of the PR that added it)
WARM = {"serving": {"programs": {"loaded": 3, "traced": 0,
                                 "load_s": 4.437}}}
COLD = {"serving": {"programs": {"loaded": 0, "traced": 3, "load_s": 0.0}}}
PARENT = {"serving": {"compiles": {"chunk": 1}, "xla": {"trace_s": 30.5}}}


@pytest.mark.parametrize("name, warm, cold", [
    ("setup_programs_loaded", 3.0, 0.0),
    ("setup_programs_traced", 0.0, 3.0),
    ("setup_program_load_s", 4.437, 0.0),
])
def test_setup_program_metrics_read_the_programs_block(name, warm, cold):
    spec = BENCH.layer_metric(name)
    assert spec["moves"] == "setup_s" and spec["layer"] == "engine"
    read = BENCH.load("readers", spec["reader"]).read
    assert read({"status_before": WARM}, **spec["args"]) == warm
    assert read({"status_before": COLD}, **spec["args"]) == cold
    # where the program has no such counter the metric is left out
    assert read({"status_before": PARENT}, **spec["args"]) is None
    assert read({"records": [], "spans": {}}, **spec["args"]) is None
    entry = [m for m in json.load(open(os.path.join(
        BENCH.root, "BENCHMARK.json")))["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and "workloads" not in entry[0]

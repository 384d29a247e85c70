"""``expand_ahead_ms``: the ``expand.ahead`` span of an expanded request
(the host work that reads nothing of the expanded text, done under the
expander's first decode chunk), on canned span tables."""

import json
import os
from types import SimpleNamespace

from benchmarks.harness import files

BENCH = files.Bench(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def context(spans: dict, traced=()) -> dict:
    return {"records": [SimpleNamespace(request_id=rid, traced=rid in traced)
                        for rid in spans],
            "spans": spans}


def test_expand_ahead_ms_reads_the_span_where_there_is_one():
    spec = BENCH.layer_metric("expand_ahead_ms")
    assert spec["layer"] == "engine" and spec["moves"] == "request_p50_s"
    assert spec["source"] == "program_span" and spec["better"] == "higher"
    read = BENCH.load("readers", spec["reader"]).read
    # a window of expanded requests: the median of each one's span
    drew = context({
        "a": {"expand": [0.5], "expand.ahead": [0.009]},
        "b": {"expand": [0.5], "expand.ahead": [0.011]},
        "c": {"expand": [0.5], "expand.ahead": [0.010]},
        "t": {"expand": [0.6], "expand.ahead": [0.030]}}, traced=("t",))
    assert abs(read(drew, **spec["args"]) - 10.0) < 1e-9
    # a request that enqueued no decode chunk drew nothing and is left out
    some = context({"a": {"expand": [0.5], "expand.ahead": [0.008]},
                    "b": {"expand": [0.01]}})
    assert abs(read(some, **spec["args"]) - 8.0) < 1e-9
    # the parent of the PR that added the span, and a cell with no
    # expander: nothing to read, nothing reported
    parent = context({"a": {"expand": [0.5], "noise": [0.002]},
                      "b": {"expand": [0.5], "noise": [0.002]}})
    assert read(parent, **spec["args"]) is None
    assert read(context({"a": {"prepare": [0.005]}}), **spec["args"]) is None
    assert read({"records": [], "spans": {}}, **spec["args"]) is None


def test_expand_ahead_ms_lists_the_expander_cells():
    manifest = json.load(open(os.path.join(BENCH.root, "BENCHMARK.json")))
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "expand_ahead_ms"]
    expanders = [w["name"] for w in manifest["workloads"]
                 if "expand" in w["config"]]
    # every expander cell, each appended as it came (PR 56's, the eighth)
    assert entry["workloads"] == expanders and len(expanders) >= 8
    assert not any(w.startswith("sdxl") for w in entry["workloads"])

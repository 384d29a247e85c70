"""span_sum and status_value on hand-made contexts: what they add up, and
that a program without the span or the block gives None, never an error
(the driver lays these files over the parent's checkout too)."""

import dataclasses
import json
import os

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

BENCH = files.Bench(rehearsal.REPO)


@dataclasses.dataclass
class Rec:
    request_id: str
    traced: bool = False


def context(spans, traced=()):
    return {"records": [Rec(rid, rid in traced) for rid in spans],
            "spans": spans}


def test_span_sum_adds_the_named_spans_per_request_then_takes_the_median():
    read = BENCH.load("readers", "span_sum").read
    spans = {
        "a": {"http.read_parse": [0.001], "http.respond": [0.002, 0.003]},
        "b": {"http.read_parse": [0.002], "http.respond": [0.002]},
        "c": {"http.read_parse": [0.010], "http.respond": [0.020]},
    }
    got = read(context(spans), ["http.read_parse", "http.respond"])
    assert got == pytest.approx(6.0)            # ms: 6, 4, 30 -> median 6


def test_span_sum_leaves_traced_requests_out_when_others_exist():
    read = BENCH.load("readers", "span_sum").read
    spans = {"slow": {"x": [1.0]}, "w-0": {"x": [0.002]}}
    assert read(context(spans, traced=("slow",)), ["x"]) \
        == pytest.approx(2.0)
    assert read(context({"slow": {"x": [1.0]}}, traced=("slow",)), ["x"]) \
        == pytest.approx(1000.0)


def test_span_sum_counts_a_span_the_request_lacks_as_nothing():
    read = BENCH.load("readers", "span_sum").read
    spans = {"a": {"vae_decode_fetch": [0.004]},            # no chunk ran
             "b": {"vae_decode_fetch": [0.004], "chunk.fence_wait": [0.1]},
             "c": {"queue_wait": [0.05]}}                   # left out
    got = read(context(spans), ["chunk.fence_wait", "vae_decode_fetch"])
    assert got == pytest.approx(54.0)           # ms: median of 4 and 104


@pytest.mark.parametrize("spans", [
    {"a": {}},                                      # no tree at all
    {"a": {"queue_wait": [0.05]}},                  # the parent's spans
])
def test_span_sum_missing_spans_is_none(spans):
    read = BENCH.load("readers", "span_sum").read
    assert read(context(spans),
                ["http.read_parse", "http.respond"]) is None


def test_status_value_sums_keys_of_a_block():
    read = BENCH.load("readers", "status_value").read
    ctx = {"status_before": {"serving": {"xla": {
        "trace_s": 30.5, "lower_s": 9.5, "backend_s": 41.0,
        "cache_misses": 1}}}}
    args = {"status": "status_before", "path": ["serving", "xla"]}
    assert read(ctx, keys=["trace_s", "lower_s"], **args) == 40.0
    assert read(ctx, keys=["backend_s"], **args) == 41.0
    assert read(ctx, keys=["cache_misses"], **args) == 1.0


@pytest.mark.parametrize("status", [
    None,                                           # no status read
    {"serving": None},                              # no dispatcher
    {"serving": {"compiles": {"chunk": 1}}},        # the parent: no block
    {"serving": {"xla": {"trace_s": 1.0}}},         # a key short
    {"serving": {"xla": {"trace_s": 1.0, "lower_s": "n/a"}}},
])
def test_status_value_missing_block_or_key_is_none(status):
    read = BENCH.load("readers", "status_value").read
    ctx = {} if status is None else {"status_before": status}
    assert read(ctx, "status_before", ["serving", "xla"],
                ["trace_s", "lower_s"]) is None


@pytest.mark.parametrize("name", [
    "http_io_ms", "png_encode_ms", "engine_prepare_ms", "device_wait_ms",
    "setup_trace_lower_s", "setup_xla_load_s", "setup_cache_misses"])
def test_new_metric_files_call_their_readers_with_their_args(name):
    """Each file's args fit its reader's signature, on an empty context."""
    with open(os.path.join(BENCH.dir, "layer_metrics", name + ".json")) as fh:
        spec = json.load(fh)
    reader = BENCH.load("readers", spec["reader"])
    assert reader.read({"records": [], "spans": {}}, **spec["args"]) is None

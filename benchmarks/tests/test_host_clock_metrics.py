"""The three metrics of what no request's tree covered (PR 54), each read a
request from the spans the program adds to its trees: ``http.accept``,
``http.between`` beside the root, and the host clock's ``host.stall``; on
canned records and span tables."""

import json
import os
import types

import pytest

from benchmarks.harness import files

BENCH = files.Bench(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

#: w-0 was sent while the profiler ran; w-1 follows the slice, so its gap
#: holds the profiler's stop and the reduction; w-4 came on a kept
#: connection while w-3 was in flight
RECORDS = [types.SimpleNamespace(request_id=f"w-{i}", traced=i == 0,
                                 start=100.0 + 3 * i) for i in range(5)]
SPANS = {
    "w-0": {"http.accept": [0.5], "http.read_parse": [0.001],
            "http.between": [0.002], "host.stall": [0.3]},
    "w-1": {"http.accept": [0.0008], "http.read_parse": [0.001],
            "http.between": [1.9]},
    "w-2": {"http.accept": [0.0012], "http.between": [0.0022],
            "host.stall": [0.021, 0.039]},
    "w-3": {"http.accept": [0.001], "http.between": [0.0026]},
    "w-4": {"http.read_parse": [0.001], "host.stall": [0.5]},
}
#: the parent of the PR that added the spans
PARENT = {rid: {"http.read_parse": [0.001]} for rid in SPANS}


#: where the gap has something to read: one client, so every accept finds
#: the server empty, and a traced run that has requests after its slice
#: (sd15_ouro_expand_b4's and sd15_xing4_expand_solo's have none: the
#: profiler's stop takes what the window had left); the other two are read
#: in every cell. A cell that came later is appended behind these
GAP_CELLS = {"between_requests_ms": [
    "sdxl_solo", "sd15_expand_solo", "sd15_qwen3next_expand_solo",
    "sd15_lfm2_expand_solo", "sd15_mellum2_expand_b4",
    "sd15_kanana2_expand_b4", "sd15_gigachat35_expand_b4"]}


@pytest.mark.parametrize("name, reader, moves, value", [
    ("http_accept_ms", "span_sum", "request_p50_s", 1.0),
    # the request that follows the traced slice is left out: w-2 and w-3
    ("between_requests_ms", "span_gap", "images_per_s", 2.4),
    # the mean over w-1, w-2, w-3: a request without a stall counts 0, one
    # without the witness (the program's proof it records these) not at all
    ("host_stall_ms", "span_mean", "images_per_s", 60.0 / 3),
])
def test_a_metric_reads_its_span(name, reader, moves, value):
    spec = BENCH.layer_metric(name)
    assert spec["moves"] == moves and spec["unit"] == "ms"
    assert spec["layer"] == "HTTP surface and host tail"
    assert spec["reader"] == reader and spec["source"] == "program_span"
    read = BENCH.load("readers", spec["reader"]).read
    assert read({"records": RECORDS, "spans": SPANS},
                **spec["args"]) == pytest.approx(value)
    # the parent records no such span: the metric is left out of the line
    for lacking in (PARENT, {}):
        assert read({"records": RECORDS, "spans": lacking},
                    **spec["args"]) is None
    entry, = [m for m in json.load(open(os.path.join(
        BENCH.root, "BENCHMARK.json")))["per_layer"] if m["name"] == name]
    first = GAP_CELLS.get(name)
    assert (entry.get("workloads") if first is None
            else entry["workloads"][:len(first)]) == first
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key]


def test_overlapping_clients_leave_the_gap_to_the_accepts_that_met_none():
    """Two clients (sdxl_pair): after the slice only the first request
    finds the server empty, and its gap is the harness's pause."""
    spec = BENCH.layer_metric("between_requests_ms")
    read = BENCH.load("readers", spec["reader"]).read
    pair = {rid: {k: v for k, v in have.items() if k != "http.between"}
            for rid, have in SPANS.items()}
    pair["w-1"]["http.between"] = [5.16]
    context = {"records": RECORDS[::-1], "spans": pair}     # in any order
    assert read(context, **spec["args"]) is None
    pair["w-3"]["http.between"] = [0.0007]
    assert read(context, **spec["args"]) == pytest.approx(0.7)
    # a traced run whose window ended with its slice, or one request on
    # (sd15_ouro_expand_b4, sd15_xing4_expand_solo), has no gap of the program's to read
    for n in (1, 2):
        assert read({"records": RECORDS[:n], "spans": SPANS},
                    **spec["args"]) is None
    # an untraced run leaves no request out
    untraced = [types.SimpleNamespace(request_id=r.request_id, traced=False,
                                      start=r.start) for r in RECORDS[1:]]
    assert read({"records": untraced, "spans": pair},
                **spec["args"]) == pytest.approx((0.7 + 5160) / 2)


def test_a_window_without_a_stall_reads_nought():
    spec = BENCH.layer_metric("host_stall_ms")
    read = BENCH.load("readers", spec["reader"]).read
    calm = {rid: {k: v for k, v in have.items() if k != "host.stall"}
            for rid, have in SPANS.items()}
    assert read({"records": RECORDS, "spans": calm}, **spec["args"]) == 0.0
    # only traced requests: they are read, as by every span reader
    assert read({"records": RECORDS[:1], "spans": SPANS},
                **spec["args"]) == pytest.approx(300.0)

"""The five metrics of PR 71: what the device did in EVERY request of a
window, read from the program's own ``device.run`` spans and the
``serving.device`` counters (no profiler), and the coalesce factor from the
attr ``dispatch.device`` carries. The two new readers on hand-made trees."""

import json
import os
import types

import pytest

from benchmarks.harness import files
from stable_diffusion_webui_distributed_tpu.obs import spans

BENCH = files.Bench(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
MANIFEST = json.load(open(os.path.join(BENCH.root, "BENCHMARK.json")))

#: name -> (reader, layer, source, unit, cells; None: every cell)
METRICS = {
    "device_busy_ms": ("span_uncovered", "engine", "program_span", "ms",
                       None),
    "device_idle_program_ms": ("span_uncovered", "engine", "program_span",
                               "ms", None),
    "dry_enqueues_per_request": ("status_ratio", "engine",
                                 "program_counter", "enqueues/request",
                                 None),
    "late_fence_share": ("status_ratio", "engine", "program_counter", "%",
                         None),
    "coalesce_factor": ("span_attr_mean", "dispatcher", "program_span",
                        "req/dispatch", ["sdxl_solo", "sdxl_pair"]),
}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_entry_and_its_file_agree(name):
    reader, layer, source, unit, cells = METRICS[name]
    spec = BENCH.layer_metric(name)
    assert (spec["reader"], spec["layer"], spec["source"], spec["unit"]) \
        == (reader, layer, source, unit)
    assert spec["moves"] == "request_p50_s"
    assert os.path.exists(os.path.join(BENCH.root, "benchmarks", "readers",
                                       reader + ".py"))
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry.get("workloads") == cells
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key]
    # appended: what the benchmark had stands where it stood
    assert MANIFEST["per_layer"].index(entry) >= 78


def record(rid, traced=False):
    return types.SimpleNamespace(request_id=rid, traced=traced)


@pytest.fixture()
def store():
    """Four hand-made trees in the program's store, times in seconds from
    one base: ``apart`` has three runs that lie apart, ``nested`` one run
    inside another and two that touch, one of them past the section's end,
    ``follower`` no ``dispatch.device`` at all, ``traced`` was sent while
    the profiler ran."""
    tracer = spans.TRACER
    tracer.clear()
    base = 1000.0

    def tree(rid, section, runs, requests=1):
        req = spans.RequestTrace(rid, "txt2img", {})
        if section is not None:
            spans.add_span(req, "dispatch.device", base + section[0],
                           section[1] - section[0],
                           {"requests": requests, "precision": "bf16"})
        for start, end in runs:
            sp = spans.add_span(req, "device.run", base + start,
                                end - start, {"kind": "run_chunk"})
            sp.tid = spans.DEVICE_TID
        tracer.close(req)

    tree("apart", (0.0, 1.0), [(0.1, 0.3), (0.4, 0.5), (0.7, 0.95)])
    tree("nested", (0.0, 1.0), [(0.2, 0.8), (0.3, 0.5), (0.8, 0.9),
                                (0.9, 1.2)], requests=2)
    tree("follower", None, [])
    tree("traced", (0.0, 2.0), [(0.0, 0.5)], requests=3)
    yield [record("apart"), record("nested"), record("follower"),
           record("traced", traced=True)]
    tracer.clear()


def test_span_uncovered_takes_the_union_inside_the_section(store):
    read = BENCH.load("readers", "span_uncovered").read
    args = BENCH.layer_metric("device_idle_program_ms")["args"]
    busy = BENCH.layer_metric("device_busy_ms")["args"]
    assert args == dict(busy, part="uncovered")
    # apart: 1000 - (200 + 100 + 250) = 450; nested: the union is 0.2 to
    # the section's end: 200 left; the follower has no section and the
    # traced one is left out: the median of two
    assert read({"records": store}, **args) == pytest.approx(325.0)
    assert read({"records": store}, **busy) == pytest.approx(675.0)
    assert read({"records": store[:1]}, **args) == pytest.approx(450.0)
    assert read({"records": store[1:2]}, **busy) == pytest.approx(800.0)
    # only traced requests: they are what there is
    assert read({"records": store[3:]}, **args) == pytest.approx(1500.0)
    # a follower alone, or records the store does not know
    assert read({"records": store[2:3]}, **args) is None
    assert read({"records": [record("elsewhere")]}, **args) is None
    with pytest.raises(ValueError):
        read({"records": store}, **dict(args, part="both"))


def test_span_uncovered_reads_nothing_of_a_program_without_the_span(store):
    """The parent of PR 71: sections, and no ``device.run``."""
    read = BENCH.load("readers", "span_uncovered").read
    args = BENCH.layer_metric("device_idle_program_ms")["args"]
    for req in spans.TRACER.finished():
        req.spans[:] = [sp for sp in req.spans if sp.name != "device.run"]
    assert read({"records": store}, **args) is None
    spans.TRACER.clear()
    assert read({"records": store}, **args) is None


@pytest.mark.parametrize("intervals, inside", [
    ([], 0.0), ([(2.0, 3.0)], 1.0), ([(-1.0, 11.0)], 10.0),
    ([(1.0, 2.0), (2.0, 3.0)], 2.0), ([(1.0, 5.0), (2.0, 3.0)], 4.0),
    ([(8.0, 12.0), (1.0, 2.0), (1.5, 2.5)], 3.5), ([(11.0, 12.0)], 0.0),
])
def test_covered_us(intervals, inside):
    reader = BENCH.load("readers", "span_uncovered")
    assert reader.covered_us([(0.0, 10.0)], intervals) \
        == pytest.approx(inside)


def test_span_attr_mean_is_the_coalesce_factor(store):
    read = BENCH.load("readers", "span_attr_mean").read
    args = BENCH.layer_metric("coalesce_factor")["args"]
    assert args == {"span": "dispatch.device", "attr": "requests"}
    assert read({"records": store}, **args) == pytest.approx(1.5)
    assert read({"records": store[1:2]}, **args) == pytest.approx(2.0)
    assert read({"records": store[3:]}, **args) == pytest.approx(3.0)
    assert read({"records": store[2:3]}, **args) is None
    assert read({"records": store}, span="dispatch.device",
                attr="precision") is None       # not a number
    assert read({"records": store}, span="no.such", attr="requests") is None


@pytest.mark.parametrize("name, value", [
    ("dry_enqueues_per_request", 26 / 6), ("late_fence_share", 12.5)])
def test_the_counters_are_read_over_the_window(name, value):
    spec = BENCH.layer_metric(name)
    read = BENCH.load("readers", spec["reader"]).read
    before = {"serving": {"device": {
        "requests": 2, "dry_enqueues": 10, "fences": 40, "late_fences": 4}}}
    after = {"serving": {"device": {
        "requests": 8, "dry_enqueues": 36, "fences": 200,
        "late_fences": 24}}}
    context = {"status_before": before, "status_after": after}
    assert read(context, **spec["args"]) == pytest.approx(value)
    # the parent's status has no such block
    assert read({"status_before": {"serving": {}},
                 "status_after": {"serving": {}}}, **spec["args"]) is None

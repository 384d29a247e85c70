"""The reducer on traces with known answers: one written by hand (every
interval chosen, data/synthetic.xspace.txt) and one recorded on a v5e
(data/v5e_small.xplane.pb, see its .json for how it was made)."""

import dataclasses
import json
import os

import jax.profiler
import pytest

from benchmarks.harness import files, trace_reduce, xplane_proto
from benchmarks.tests import rehearsal

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


BENCH = files.Bench(rehearsal.REPO)


def written(tmp_path, name):
    with open(os.path.join(DATA, name)) as fh:
        text = "".join(line for line in fh if not line.startswith("#"))
    folder = tmp_path / "plugins" / "profile" / "2026_01_01"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


@pytest.fixture()
def synthetic(tmp_path):
    return written(tmp_path, "synthetic.xspace.txt")


@pytest.fixture()
def with_metadata(tmp_path):
    """The reducer's summary of data/synthetic_meta.xspace.txt (its header
    says what is in it)."""
    return trace_reduce.reduce(trace_reduce.find_xplane(
        written(tmp_path, "synthetic_meta.xspace.txt")))


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
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert out["collective_s"] == 0.0
    assert out["device_ops"][0][0] == want["top_op"]
    assert out["device_ops"][1] == [want["top_leaf"][0],
                                    pytest.approx(want["top_leaf"][1])]
    assert "while" not in dict(map(tuple, out["device_ops"]))
    assert out["idle_gaps"][0][1] == pytest.approx(want["longest_gap_s"])
    # the while's own time is busy time no leaf op accounts for
    leaves = sum(seconds for _, seconds in trace_reduce.reduce(
        path, top=10 ** 6, modules=0)["device_ops"])
    assert leaves == pytest.approx(want["leaf_ops_s"], rel=1e-6)
    assert leaves < out["busy_s"] < out["span_s"]
    # what the op IS comes from its event metadata, and the executable it
    # belongs to from program_id against the module's name
    top = out["op_table"][0]
    assert {k: top[k] for k in want["top_row"]} == want["top_row"]
    assert top["seconds"] == pytest.approx(want["top_leaf"][1], rel=1e-6)
    assert out["modules"] == {"jit_step": pytest.approx(4.13e-06)}
    assert out["module_calls"] == {"jit_step": 2}      # "then 2 calls"
    assert out["head_s"] == out["tail_s"] == 0.0       # no marks in it


def test_the_proto_reader_sees_what_jax_sees():
    """Planes, lines, event counts and names, against ProfileData."""
    path = os.path.join(DATA, "v5e_small.xplane.pb")
    ours = xplane_proto.read_xspace(path)
    theirs = jax.profiler.ProfileData.from_file(path)
    seen = 0
    for mine, plane in zip(ours.planes, theirs.planes):
        assert mine.name == plane.name
        names = {e.key: e.value.name for e in mine.event_metadata}
        for my_line, line in zip(mine.lines, plane.lines):
            assert my_line.name == line.name
            events = list(line.events)
            assert len(my_line.events) == len(events)
            for my_event, event in zip(my_line.events, events):
                assert names[my_event.metadata_id] == event.name
                assert int(my_event.duration_ps / 1e3) \
                    == int(event.duration_ns)
                seen += 1
    assert seen > 24


def test_table_scopes_modules_head_and_tail(with_metadata):
    out = with_metadata
    assert out["modules"] == {"jit_run_chunk": pytest.approx(12000e-9),
                              "jit_decode": pytest.approx(1000e-9)}
    # the launches those seconds are summed over
    assert out["module_calls"] == {"jit_run_chunk": 1, "jit_decode": 1}
    assert out["busy_s"] == pytest.approx(13000e-9)
    rows = {r["name"]: r for r in out["op_table"]}
    assert "while.2" not in rows and len(rows) == 7
    conv = rows["convolution_add_fusion.5"]
    assert conv["scope"].endswith("UNet/up_0_res_0/conv1/"
                                  "conv_general_dilated")
    assert (conv["category"], conv["module"], conv["calls"]) \
        == ("convolution fusion", "jit_run_chunk", 2)   # a ref_value stat
    assert conv["seconds"] == pytest.approx(4000e-9)
    assert (conv["flops"], conv["bytes"]) == (1e6, 2e4)
    assert rows["_tiled.3"]["shape"].startswith("bf16[2,1024,128]")
    assert rows["copy.4"]["scope"] == "" \
        and rows["copy.4"]["module"] == "jit_run_chunk"
    assert rows["convolution_fusion.1"]["module"] == "jit_decode"
    # the breakdown names modules, not fusion numbers
    ops = dict(map(tuple, out["device_ops"]))
    assert list(ops)[:2] == ["module jit_run_chunk", "module jit_decode"]
    assert ops["up_0_attn_0/block_0/attn1 _tiled.3"] \
        == pytest.approx(2000e-9)
    assert "block_0/attn1/qkv fusion.9" in ops and "copy.4" in ops
    # head and tail are gaps like the others, with owners; the load
    # generator's own marks are never the owner
    assert out["head_s"] == pytest.approx(500e-9)
    assert out["tail_s"] == pytest.approx(1000e-9)
    gaps = dict(map(tuple, out["idle_gaps"]))
    assert gaps == {
        "tail: python3: sdtpu:png_encode": pytest.approx(1000e-9),
        "python3: sdtpu:vae_decode_dispatch": pytest.approx(1000e-9),
        "head: python3: sdtpu:txt2img": pytest.approx(500e-9)}
    # busy + the gaps = the marked slice
    assert out["busy_s"] + sum(gaps.values()) == pytest.approx(15500e-9)


def test_scope_tail_drops_plumbing_and_the_primitive():
    tail = trace_reduce.scope_tail
    assert tail("jit(run_chunk)/while/body/closed_call/UNet/up_1_attn_1/"
                "block_0/attn1/dot_general") == "up_1_attn_1/block_0/attn1"
    assert tail("jit(step)/while/body/closed_call/dot_general") == ""
    assert tail("") == ""


@dataclasses.dataclass
class Rec:
    traced: bool = True


def test_the_five_classes_partition_the_modules_ops(with_metadata):
    context = {"trace": with_metadata, "bench": BENCH,
               "records": [Rec(), Rec(), Rec(traced=False)]}
    reader = BENCH.load("readers", "op_class_ms")
    seconds = reader.by_class(context, "unet")
    assert seconds == {
        "conv": pytest.approx(4000e-9), "linear": pytest.approx(1000e-9),
        "self_attn": pytest.approx(2000e-9),
        "cross_attn": pytest.approx(500e-9), "other": pytest.approx(750e-9)}
    in_module = sum(r["seconds"] for r in with_metadata["op_table"]
                    if r["module"] == "jit_run_chunk")
    assert sum(seconds.values()) == pytest.approx(in_module)
    # the metric files: ms a traced request (two were traced)
    total = 0.0
    for name in ("conv", "linear", "self_attn", "cross_attn", "other"):
        spec = BENCH.layer_metric(name + "_device_ms")
        value = BENCH.load("readers", spec["reader"]).read(
            context, **spec["args"])
        assert value == pytest.approx(1e3 * seconds[name] / 2)
        total += value
    assert total == pytest.approx(1e3 * in_module / 2)
    # nothing of that executable in the slice, or no table: nothing to read
    other = dict(with_metadata, op_table=[
        r for r in with_metadata["op_table"] if r["module"] == "jit_decode"])
    assert reader.read(dict(context, trace=other), "unet", "conv") is None
    assert reader.read(dict(context, trace=None), "unet", "conv") is None


def test_roofline_share_counts_needs_from_the_shape(with_metadata):
    needs = BENCH.load("harness", "kernel_needs")
    assert needs.parse_shape("bf16[2,4096,640]{2,1,0:T(8,128)(2,1)}") \
        == ((2, 4096, 640), 2)
    assert needs.parse_shape("(f32[8,128]{1,0}, s32[])") == ((8, 128), 4)
    assert needs.parse_shape("token[]") is None
    # SDXL's T = 4096 site at batch 2: 4 x B x H x T^2 x D
    ops, moved = needs.self_attention((2, 4096, 640), 2)
    assert ops == 4 * 2 * 10 * 4096 ** 2 * 64 == 4 * 20 * 4096 ** 2 * 64
    assert needs.self_attention((20, 4096, 64), 2) == (ops, moved)
    assert moved == 4 * 2 * 4096 * 640 * 2
    spec = BENCH.layer_metric("self_attn_roofline")
    read = BENCH.load("readers", spec["reader"]).read
    context = {"trace": with_metadata, "bench": BENCH,
               "peak": {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 2e12}}
    # one call of bf16[2,1024,128] in 2000 ns: 4 x 262144 x 1024 operations
    # need 1073.7 ns at that peak, its 2 MiB of traffic 1048.6 ns
    assert read(context, **spec["args"]) == pytest.approx(
        100 * (4 * 262144 * 1024 / 1e15) / 2000e-9)
    slow = dict(context, peak={"bf16_flops_per_s": 1e16,
                               "hbm_bytes_per_s": 2e12})
    assert read(slow, **spec["args"]) == pytest.approx(
        100 * (4 * 262144 * 2 / 2e12) / 2000e-9)        # memory-bound
    no_kernel = dict(with_metadata, op_table=[
        r for r in with_metadata["op_table"] if r["name"] != "_tiled.3"])
    assert read(dict(context, trace=no_kernel), **spec["args"]) is None
    assert read(dict(context, trace=None), **spec["args"]) is None

"""idle_by_owner on a hand-made xplane (two threads, nested spans, a head, a
tail, a stretch under nothing) and span_self on a hand-made tree: every
interval chosen, so the answers are sums done by hand."""

import dataclasses

import pytest

from benchmarks.harness import files, xplane_proto
from benchmarks.tests import rehearsal

BENCH = files.Bench(rehearsal.REPO)

#: the device's ops (ns): idle is [0, 200) the head, [400, 500) a gap and
#: [700, 1100) the tail of a slice marked [0, 1100)
OPS = [(200, 400), (500, 700)]
SLICE = (0, 1100)
#: the request's thread, spans nested as the program nests them
LEADER = [
    ("http.read_parse", 0, 20), ("txt2img", 20, 900),
    ("queue_wait", 30, 180), ("coalesce.window", 40, 150),
    ("engine.wait", 150, 180), ("dispatch.device", 180, 850),
    ("prepare", 185, 210), ("tokenize", 190, 200),
    ("chunk.fence_wait", 390, 480), ("png_encode", 750, 850),
    ("http.respond", 900, 1000), ("respond.write", 950, 1000),
]
#: a follower's thread: alive from inside the leader's window until after
#: the leader has answered; then nothing is alive at all. While it waits its
#: thread puts up the wait (its innermost span), not its root, and the wait
#: yields to whatever the leader's thread puts up
FOLLOWER = [("txt2img", 90, 1060), ("coalesced.wait", 100, 1050)]
#: who owns the 700 idle ns, by hand
BY_NAME = {
    "http.read_parse": 20, "txt2img": 10 + 10 + 40 + 10 + 10,
    "queue_wait": 10, "coalesce.window": 100, "engine.wait": 30,
    "dispatch.device": 5 + 20 + 50, "prepare": 5, "tokenize": 10,
    "chunk.fence_wait": 80, "png_encode": 100, "http.respond": 50,
    "respond.write": 50, "coalesced.wait": 50, "(no span)": 40,
}
BY_CLASS = {"admission": 20 + 10 + 100 + 30 + 50, "fence": 80,
            "tail": 100 + 50 + 50, "host": 10,
            "unowned": 80 + 75 + 5 + 40}


@dataclasses.dataclass
class Rec:
    request_id: str
    traced: bool = False


def xplane(tmp_path, threads, ops=OPS, mark=SLICE) -> str:
    space = xplane_proto._xspace_class()()
    device = space.planes.add(id=1, name="/device:TPU:0")
    device.event_metadata.add(key=1).value.name = "%fusion.1 = f32[] fusion()"
    line = device.lines.add(id=1, name="XLA Ops", timestamp_ns=0)
    for start, end in ops:
        line.events.add(metadata_id=1, offset_ps=start * 1000,
                        duration_ps=(end - start) * 1000)
    host = space.planes.add(id=2, name="/host:CPU")
    ids: dict = {}

    def event(line, name, start, end):
        if name not in ids:
            ids[name] = len(ids) + 1
            host.event_metadata.add(key=ids[name]).value.name = name
        line.events.add(metadata_id=ids[name], offset_ps=start * 1000,
                        duration_ps=(end - start) * 1000)

    client = host.lines.add(id=1, name="client/1", timestamp_ns=0)
    event(client, "bench:request", *mark)
    for i, spans in enumerate(threads):
        line = host.lines.add(id=2 + i, name=f"handler/{2 + i}",
                              timestamp_ns=0)
        for name, start, end in spans:
            event(line, "sdtpu:" + name, start, end)
        event(line, "TransferFromDevice", 700, 720)     # not a span
    path = tmp_path / "slice.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return str(path)


def context(path, traced=1):
    return {"records": [Rec(f"w-{i}", True) for i in range(traced)]
            + [Rec("w-9")], "bench": BENCH, "xplane": path}


def test_every_idle_instant_goes_to_the_latest_started_span(tmp_path,
                                                            capsys):
    reader = BENCH.load("readers", "idle_by_owner")
    ctx = context(xplane(tmp_path, [LEADER, FOLLOWER]))
    by_name, by_class = reader.tables(ctx, "request")
    assert {k: round(v * 1e6) for k, v in by_name.items()} == BY_NAME
    assert {k: round(v * 1e6) for k, v in by_class.items()} == BY_CLASS
    # the classes partition the idle time: the slice less the busy time
    assert sum(by_class.values()) * 1e6 == pytest.approx(1100 - 400)
    for cls, ns in BY_CLASS.items():
        assert reader.read(ctx, "request", cls) * 1e6 == pytest.approx(ns)
    # the table by name is printed once, most first
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("idle by owner: ")]
    assert len(lines) == 1
    assert lines[0].index("coalesce.window") < lines[0].index("tokenize")


def test_idle_time_is_per_traced_request(tmp_path):
    reader = BENCH.load("readers", "idle_by_owner")
    ctx = context(xplane(tmp_path, [LEADER, FOLLOWER]), traced=2)
    assert reader.read(ctx, "request", "fence") * 1e6 \
        == pytest.approx(80 / 2)


def test_a_span_no_rule_names_is_host(tmp_path):
    reader = BENCH.load("readers", "idle_by_owner")
    ctx = context(xplane(tmp_path, [[("a_later_prs.span", 0, 1100)]]))
    assert reader.read(ctx, "request", "host") * 1e6 == pytest.approx(700)
    assert reader.read(ctx, "request", "unowned") == 0.0


@pytest.mark.parametrize("case", ["no trace", "no marks", "no spans",
                                  "no traced request"])
def test_nothing_to_read_is_none_not_an_error(tmp_path, case):
    reader = BENCH.load("readers", "idle_by_owner")
    if case == "no trace":
        ctx = context(None)
    elif case == "no marks":
        ctx = context(xplane(tmp_path, [LEADER], mark=(0, 0)))
        # a mark of no length is still a mark: take it away
        space = xplane_proto.read_xspace(ctx["xplane"])
        del space.planes[1].lines[0]
        with open(ctx["xplane"], "wb") as fh:
            fh.write(space.SerializeToString())
    elif case == "no spans":
        ctx = context(xplane(tmp_path, []))
    else:
        ctx = context(xplane(tmp_path, [LEADER]), traced=0)
    assert reader.read(ctx, "request", "tail") is None


def event(name, rid, span_id, ts, dur, parent=None):
    args = {"request_id": rid, "span_id": span_id}
    if parent is not None:
        args["parent_id"] = parent
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "args": args}


def test_span_self_is_top_level_time_less_the_leaves_union():
    reader = BENCH.load("readers", "span_self")
    events = [
        event("http.read_parse", "a", 1, 0, 100),           # a leaf, top
        event("txt2img", "a", 2, 100, 1000),                # the root
        event("queue_wait", "a", 3, 110, 200, parent=2),
        event("coalesce.window", "a", 4, 120, 150, parent=3),   # leaf
        event("dispatch.device", "a", 5, 320, 700, parent=2),
        event("chunk.enqueue", "a", 6, 400, 100, parent=5),     # leaf
        event("chunk.fence_wait", "a", 7, 450, 250, parent=5),  # overlaps
        event("http.respond", "a", 8, 1100, 50),
        event("respond.write", "a", 9, 1120, 30, parent=8),     # leaf
    ]
    # top: 100 + 1000 + 50; leaves: 100 + 150 + [400, 700) + 30
    assert reader.unspanned_us(events) == pytest.approx(1150 - 580)


def test_span_self_reads_the_programs_store_and_takes_the_median():
    from stable_diffusion_webui_distributed_tpu.obs import spans

    reader = BENCH.load("readers", "span_self")
    spans.TRACER.clear()
    assert reader.read({"records": [Rec("self-0")]}) is None
    for i, gap in enumerate((0.002, 0.004, 0.03)):
        with spans.request(f"self-{i}", name="txt2img"):
            with spans.span("dispatch.device"):
                with spans.span("prepare"):
                    pass
                end = spans.time.perf_counter() + gap
                while spans.time.perf_counter() < end:
                    pass
    records = [Rec("self-0", traced=True), Rec("self-1"), Rec("self-2"),
               Rec("never-sent")]
    got = reader.read({"records": records})         # ms, median of 4 and 30
    assert 17.0 <= got <= 17.0 + 5.0
    spans.TRACER.clear()

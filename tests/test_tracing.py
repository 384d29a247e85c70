"""The request's host timeline and the set-up's compile accounting, inside
the program (ISSUE 24): spans where the host's work is, the same tree on
the profiler's clock, and ``serving.xla`` from ``jax.monitoring``.

CPU-only, tiny model: counts and shapes of the tree, never a time.
"""

import glob
import http.client
import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from stable_diffusion_webui_distributed_tpu.models.configs import TINY
from stable_diffusion_webui_distributed_tpu.obs import spans as obs_spans
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.serving import metrics
from test_pipeline import init_params

#: table B of ISSUE 24: span -> the span it sits under (None: beside the
#: root). ``text_encode`` and the others that existed keep their names.
TXT2IMG_SPANS = {
    "http.accept": None, "http.read_parse": None, "http.respond": None,
    "queue_wait": "txt2img", "coalesce.window": "queue_wait",
    "engine.wait": "queue_wait", "dispatch.device": "txt2img",
    "prepare": "dispatch.device", "request.plan": "prepare",
    "tokenize": "prepare", "text_encode": "prepare", "noise": "prepare",
    "batch.assemble": "prepare",
    "denoise_range": "dispatch.device", "denoise_chunk": "denoise_range",
    "denoise.inputs": "denoise_range", "denoise.plan": "denoise_range",
    "chunk.enqueue": "denoise_chunk", "chunk.fence_wait": None,
    "vae_decode_dispatch": "dispatch.device",
    "vae_decode_fetch": "dispatch.device", "png_encode": None,
    "decode.wait": "vae_decode_fetch", "fetch.copy": "vae_decode_fetch",
    "respond.serialize": "http.respond", "respond.write": "http.respond",
    "xla.compile": None,
}
IMG2IMG_SPANS = {
    "http.accept": None, "http.read_parse": None, "http.respond": None,
    "generate_range": "dispatch.device", "prepare": "generate_range",
    "denoise.inputs": "denoise_range", "denoise.plan": "denoise_range",
    "init_image": "prepare", "png_decode": "init_image",
    "upload": "init_image", "vae_encode": "prepare", "noise": "prepare",
    "chunk.enqueue": "denoise_chunk", "png_encode": "generate_range",
}
#: intervals found after the fact (add_span, add_child): in the store
#: only, never an annotation with the store's span id. ISSUE 54's two are
#: in a tree only where a stall overlapped the request (``host.stall``) or
#: the request found the server empty (``http.between``)
AFTER_THE_FACT = ("xla.compile", "host.stall", "http.between")


def post(server, route, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}{route}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as r:
        return json.loads(r.read())


def get(server, route):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}{route}", timeout=60) as r:
        return json.loads(r.read())


def events_of(server, rid, ph="X"):
    """The request's spans on the host's threads; ``ph`` "b": the ones
    that ran on the device (``device.run``, an async pair each)."""
    doc = get(server, "/internal/trace.json")
    return [e for e in doc["traceEvents"]
            if e["args"]["request_id"] == rid and e["ph"] == ph]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A fresh engine (so its executables are made inside the requests)
    behind ApiServer; one txt2img, one img2img, then a txt2img under a
    profiler capture."""
    from stable_diffusion_webui_distributed_tpu.server.api import ApiServer

    mp = pytest.MonkeyPatch()
    mp.setenv("SDTPU_BUCKET_LADDER", "32x32")
    mp.setenv("SDTPU_BATCH_LADDER", "1")
    engine = Engine(TINY, init_params(TINY), chunk_size=2,
                    state=GenerationState())
    server = ApiServer(engine, state=engine.state, host="127.0.0.1",
                       port=0).start()
    body = {"prompt": "a traced cow", "steps": 4, "width": 32, "height": 32,
            "seed": 11, "sampler_name": "Euler a"}
    try:
        first = post(server, "/sdapi/v1/txt2img",
                     dict(body, request_id="trace-t2i"))
        post(server, "/sdapi/v1/img2img",
             dict(body, request_id="trace-i2i", init_images=first["images"],
                  denoising_strength=0.5))
        tdir = str(tmp_path_factory.mktemp("xplane"))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=options)
        try:
            post(server, "/sdapi/v1/txt2img",
                 dict(body, request_id="trace-prof", seed=12))
            # the client has its answer before the server's handler thread
            # has closed http.respond: stop the capture only once the
            # exchange has ended on the server too, or a loaded machine
            # cuts that span's annotation off
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and not any(
                    e["name"] == "http.respond"
                    for e in events_of(server, "trace-prof")):
                time.sleep(0.01)
        finally:
            jax.profiler.stop_trace()
        xplane = glob.glob(tdir + "/**/*.xplane.pb", recursive=True)[0]
        yield {"server": server,
               "txt2img": events_of(server, "trace-t2i"),
               "img2img": events_of(server, "trace-i2i"),
               "profiled": events_of(server, "trace-prof"),
               "device": {which: events_of(server, rid, "b")
                          for which, rid in (("txt2img", "trace-t2i"),
                                             ("img2img", "trace-i2i"),
                                             ("profiled", "trace-prof"))},
               "status": get(server, "/internal/status"),
               "threads": sorted(t.name for t in threading.enumerate()),
               "xplane": xplane}
    finally:
        server.stop()
        mp.undo()


def by_id(events):
    return {e["args"]["span_id"]: e for e in events}


class TestSpanTree:
    @pytest.mark.parametrize("name", sorted(TXT2IMG_SPANS))
    def test_txt2img_has_span(self, served, name):
        events = served["txt2img"]
        found = [e for e in events if e["name"] == name]
        assert found, sorted({e["name"] for e in events})
        want = TXT2IMG_SPANS[name]
        if want is not None:
            ids = by_id(events)
            assert {ids[e["args"]["parent_id"]]["name"] for e in found} \
                == {want}

    @pytest.mark.parametrize("name", sorted(IMG2IMG_SPANS))
    def test_img2img_has_span(self, served, name):
        events = served["img2img"]
        found = [e for e in events if e["name"] == name]
        assert found, sorted({e["name"] for e in events})
        want = IMG2IMG_SPANS[name]
        if want is not None:
            ids = by_id(events)
            assert want in {ids[e["args"]["parent_id"]]["name"]
                            for e in found}

    @pytest.mark.parametrize("which", ["txt2img", "img2img", "profiled"])
    def test_parents_resolve_and_hold_their_children(self, served, which):
        events = served[which]
        ids = by_id(events)
        assert len(ids) == len(events)          # span ids are unique
        tops = [e for e in events if "parent_id" not in e["args"]
                and e["name"] != "http.between"]
        # the root, and the three parts of the HTTP exchange beside it
        assert sorted(e["name"] for e in tops) == sorted(
            ["http.accept", "http.read_parse", "http.respond",
             which.replace("profiled", "txt2img")])
        slack = 50.0        # us: a parent's clock is read outside its child's
        for e in events:
            parent = e["args"].get("parent_id")
            if parent is None:
                continue
            assert parent in ids, (e["name"], parent)
            p = ids[parent]
            assert e["ts"] >= p["ts"] - slack, (e["name"], p["name"])
            assert e["ts"] + e["dur"] <= p["ts"] + p["dur"] + slack, \
                (e["name"], p["name"])

    def test_exchange_ends_meet_the_root(self, served):
        events = {e["name"]: e for e in served["txt2img"]
                  if "parent_id" not in e["args"]}
        root = events["txt2img"]
        read, respond = events["http.read_parse"], events["http.respond"]
        assert read["ts"] + read["dur"] == pytest.approx(root["ts"], abs=5)
        assert respond["ts"] >= root["ts"] + root["dur"] - 5
        assert read["args"]["bytes"] > 0
        assert respond["args"]["bytes"] > 0 \
            and respond["args"]["status"] == 200

    @pytest.mark.parametrize("which", ["txt2img", "img2img", "profiled"])
    def test_the_accept_meets_the_read(self, served, which):
        """``http.accept`` starts at the accept thread's stamp, at or
        before the handler thread's first instruction, and ends where
        ``http.read_parse`` starts, which reads what it read without it."""
        events = {e["name"]: e for e in served[which]
                  if "parent_id" not in e["args"]}
        accept, read = events["http.accept"], events["http.read_parse"]
        assert 0 <= accept["args"]["thread_start_ms"] * 1e3 <= accept["dur"]
        assert accept["ts"] + accept["dur"] \
            == pytest.approx(read["ts"], abs=1000)
        assert set(accept["args"]) == {"request_id", "span_id",
                                       "thread_start_ms"}
        assert read["tid"] == accept["tid"]
        assert "reused" not in read["args"] and read["args"]["bytes"] > 0

    def test_the_gap_before_a_request_is_beside_its_root(self, served):
        """A request that found the server empty has ``http.between``: from
        the last exchange's end to its accept, what ``serving.host`` adds
        to ``between_ms``; one sent while another is in flight has none."""
        server = served["server"]
        body = {"prompt": "a cow apart", "steps": 4, "width": 32,
                "height": 32, "sampler_name": "Euler a"}
        post(server, "/sdapi/v1/txt2img", dict(body, request_id="gap-0"))
        before = get(server, "/internal/status")["serving"]["host"]
        time.sleep(0.1)
        post(server, "/sdapi/v1/txt2img", dict(body, request_id="gap-1"))
        # the server ends an exchange after it has answered: under six
        # loaded workers the next accept can come first, and then has no
        # gap to count (once in three whole runs, PR 61)
        time.sleep(0.1)
        after = get(server, "/internal/status")["serving"]["host"]
        tops = {e["name"]: e for e in events_of(server, "gap-1")
                if "parent_id" not in e["args"]}
        gap, accept = tops["http.between"], tops["http.accept"]
        assert 100e3 <= gap["dur"] < 2000e3
        assert gap["ts"] + gap["dur"] == pytest.approx(accept["ts"], abs=5)
        # the two status reads bracket two gaps: this one and the read's
        assert after["betweens"] == before["betweens"] + 2
        assert after["between_ms"] - before["between_ms"] \
            >= gap["dur"] / 1e3 - 0.01

    def test_a_kept_connection_accepts_once(self, served):
        """A connection's later request has no accept of its own and says
        ``reused``; an exchange that mints no request records nothing."""
        server = served["server"]
        body = json.dumps({"prompt": "a kept cow", "steps": 4, "width": 32,
                           "height": 32, "seed": 13,
                           "sampler_name": "Euler a"})
        before = get(server, "/internal/status")["serving"]["host"]
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=600)
        try:
            for rid in ("kept-0", "kept-1"):
                conn.request("POST", "/sdapi/v1/txt2img",
                             body[:-1] + f', "request_id": "{rid}"}}',
                             {"Content-Type": "application/json"})
                assert conn.getresponse().read()
        finally:
            conn.close()
        after = get(server, "/internal/status")["serving"]["host"]
        # the connection's two requests and this status read
        assert after["exchanges"] == before["exchanges"] + 3
        tops = [{e["name"]: e for e in events_of(server, rid)
                 if "parent_id" not in e["args"]}
                for rid in ("kept-0", "kept-1")]
        assert "http.accept" in tops[0] and "http.accept" not in tops[1]
        assert "reused" not in tops[0]["http.read_parse"]["args"]
        assert tops[1]["http.read_parse"]["args"]["reused"] is True
        assert not events_of(server, "host") or all(
            e["name"] == "host.stall" for e in events_of(server, "host"))

    def test_counts_ride_in_attrs(self, served):
        named = {}
        for e in served["txt2img"]:
            named.setdefault(e["name"], []).append(e)
        assert named["png_encode"][0]["args"]["bytes"] > 0
        assert sum(e["args"]["steps"] for e in named["chunk.enqueue"]) == 4
        assert sum(e["args"]["steps"]
                   for e in named["chunk.fence_wait"]) == 4
        assert {e["args"]["fun_name"] for e in named["xla.compile"]} \
            >= {"run_chunk", "decode_u8"}

    @pytest.mark.parametrize("which", ["txt2img", "profiled"])
    def test_png_encode_says_its_strips(self, served, which):
        """Every encode names the strips its image was deflated as (the
        encoder's own count: 1 for a small image or under PIL) beside the
        file's bytes."""
        encodes = [e for e in served[which] if e["name"] == "png_encode"]
        assert encodes
        for e in encodes:
            assert e["args"]["strips"] >= 1
            assert e["args"]["bytes"] > 0

    @pytest.mark.parametrize("which", ["txt2img", "img2img", "profiled"])
    def test_the_response_copies_the_engines_images(self, served, which):
        """Through the dispatcher and the server the image is still the
        encoder's own base64, so ``respond.serialize`` copies it into the
        body and ``json.dumps`` does not read it (api.json_body)."""
        part, = [e for e in served[which]
                 if e["name"] == "respond.serialize"]
        assert part["args"]["images_copied"] == 1
        assert part["args"]["bytes"] > 0

    def test_warm_request_compiles_nothing(self, served):
        assert not [e for e in served["profiled"]
                    if e["name"] == "xla.compile"]


#: kind of a ``device.run`` -> (the request that has it, the span that
#: enqueued it, how many of them that request's tree holds: 4 steps in
#: chunks of 2, img2img at strength 0.5 runs one chunk; an image)
DEVICE_RUNS = {
    "encode": ("txt2img", "text_encode", 2),
    "run_chunk": ("txt2img", "chunk.enqueue", 2),
    "decode_u8": ("txt2img", "vae_decode_dispatch", 1),
    "vae_encode": ("img2img", "vae_encode", 1),
}


class TestDeviceRun:
    @pytest.mark.parametrize("kind", sorted(DEVICE_RUNS))
    def test_one_a_dispatch_under_the_span_that_enqueued_it(self, served,
                                                            kind):
        which, parent, count = DEVICE_RUNS[kind]
        runs = [e for e in served["device"][which]
                if e["args"]["kind"] == kind]
        assert [e["name"] for e in runs] == ["device.run"] * count
        ids = by_id(served[which])
        parents = [ids[e["args"]["parent_id"]] for e in runs]
        assert {p["name"] for p in parents} == {parent}
        if kind != "encode":    # one span encodes both prompts
            assert len({p["args"]["span_id"] for p in parents}) == count
        assert all("dry" in p["args"] for p in parents)
        for e in runs:
            assert ("exact" in e["args"]) != ("bound" in e["args"])
        if kind == "run_chunk":
            assert [e["args"]["steps"] for e in runs] == [2, 2]

    @pytest.mark.parametrize("which", ["txt2img", "img2img", "profiled"])
    def test_they_follow_one_another_inside_the_device_section(self, served,
                                                               which):
        runs = sorted(served["device"][which], key=lambda e: e["ts"])
        enqueues = [e for e in served[which] if "dry" in e["args"]]
        assert len(runs) >= len(enqueues) >= 3
        for a, b in zip(runs, runs[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1.0      # us
        section, = (e for e in served[which]
                    if e["name"] == "dispatch.device")
        assert runs[0]["ts"] >= section["ts"]
        assert runs[-1]["ts"] + runs[-1]["dur"] \
            <= section["ts"] + section["dur"] + 1.0
        # every fence says whether the device was done before the host
        fences = [e for e in served[which]
                  if e["name"] in ("chunk.fence_wait", "decode.wait")]
        assert fences and all("late" in e["args"] for e in fences)

    def test_a_capture_arms_the_watcher_and_nothing_else_does(self, served):
        """While the profiler ran the watcher was handed the dispatches
        (its thread exists from then on); it made no annotation: a
        ``device.run`` is found after the fact."""
        block = served["status"]["serving"]["device"]
        assert block["watcher"]["armed"] is False
        assert block["watcher"]["alive"] is True
        assert "device-watcher" in served["threads"]
        data = jax.profiler.ProfileData.from_file(served["xplane"])
        names = {event.name for plane in data.planes
                 for line in plane.lines for event in line.events}
        assert "sdtpu:chunk.enqueue" in names
        assert "sdtpu:device.run" not in names

    def test_status_counts_the_three_requests(self, served):
        block = served["status"]["serving"]["device"]
        assert block["requests"] == 3
        assert block["dispatches"]["run_chunk"] == 5
        assert block["dispatches"]["decode_u8"] == 3
        assert block["dispatches"]["vae_encode"] == 1
        assert block["dispatches_total"] == sum(block["dispatches"].values())
        assert set(block["busy_s"]) == set(block["dispatches"])
        assert block["fences"] >= 8 and block["late_fences"] >= 0
        assert 3 <= block["dry_enqueues"] <= block["dispatches_total"]
        assert block["idle_s"] > 0 and block["busy_s_total"] > 0


class TestProfilerClock:
    @pytest.fixture(scope="class")
    def host_events(self, served):
        data = jax.profiler.ProfileData.from_file(served["xplane"])
        out = []
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for event in line.events:
                    if event.name.startswith("sdtpu:"):
                        out.append((event.name[len("sdtpu:"):],
                                    dict(event.stats), event.start_ns,
                                    event.duration_ns))
        return out

    @pytest.mark.parametrize("name", ["dispatch.device", "png_encode"])
    def test_capture_holds_the_span(self, host_events, name):
        mine = [e for e in host_events if e[0] == name]
        assert mine, sorted({e[0] for e in host_events})
        assert {e[1]["request_id"] for e in mine} == {"trace-prof"}

    def test_every_live_span_is_annotated(self, served, host_events):
        """All but the after-the-fact interval (add_child: xla.compile)
        are on the profiler's host plane, with the store's own span ids:
        the wait before the device too, since ISSUE 37."""
        annotated = {e[1]["span_id"]: e[0] for e in host_events
                     if e[1].get("request_id") == "trace-prof"}
        assert "queue_wait" in annotated.values()
        # no request id exists while http.accept is open: by span id alone
        accepts = {e[1]["span_id"] for e in host_events
                   if e[0] == "http.accept"}
        for e in served["profiled"]:
            if e["name"] in AFTER_THE_FACT:
                assert e["args"]["span_id"] not in annotated
            elif e["name"] == "http.accept":
                assert e["args"]["span_id"] in accepts
            else:
                assert annotated.get(e["args"]["span_id"]) == e["name"]

    def test_durations_agree_across_the_two_clocks(self, served,
                                                   host_events):
        spans = {e["args"]["span_id"]: e for e in served["profiled"]}
        for name, stats, _start, dur_ns in host_events:
            if name == "dispatch.device" \
                    and stats["request_id"] == "trace-prof":
                assert dur_ns / 1e3 == pytest.approx(
                    spans[stats["span_id"]]["dur"], rel=0.05, abs=500)

    def test_no_capture_no_annotation(self):
        assert obs_spans._annotate("idle", request_id="x") is None


class TestXlaAccounting:
    def test_fresh_jit_in_a_request_is_one_executable_and_one_span(self):
        metrics.install_xla_listener()

        def fresh_for_test_tracing(x):
            return x * 3 + 1

        fn = jax.jit(fresh_for_test_tracing)
        name = "fresh_for_test_tracing"
        before = metrics.XLA.executables(name)
        total = metrics.METRICS.summary()["xla"]["executables"]
        with obs_spans.request("rid-xla", name="unit") as req:
            with obs_spans.span("outer") as outer:
                fn(jnp.ones(3)).block_until_ready()
            made = [s for s in req.spans if s.name == "xla.compile"
                    and s.attrs["fun_name"] == name]
            assert len(made) == 1
            assert made[0].parent_id == outer.span_id
            assert made[0].attrs["stage"] == "backend_compile"
            assert metrics.XLA.executables(name) == before + 1
            fn(jnp.ones(3)).block_until_ready()     # warm: neither moves
            assert metrics.XLA.executables(name) == before + 1
            assert len([s for s in req.spans
                        if s.name == "xla.compile"]) == 1
        block = metrics.METRICS.summary()["xla"]
        assert block["executables"] == total + 1
        row = metrics.XLA.functions[name]
        assert row["traces"] == 1 and row["trace_s"] > 0
        assert row["lower_s"] > 0 and row["backend_s"] > 0

    def test_registering_twice_counts_once(self):
        metrics.install_xla_listener()
        metrics.install_xla_listener()

        def twice_for_test_tracing(x):
            return x - 2

        jax.jit(twice_for_test_tracing)(jnp.ones(2)).block_until_ready()
        assert metrics.XLA.executables("twice_for_test_tracing") == 1

    def test_outside_a_request_counts_but_leaves_no_span(self):
        metrics.install_xla_listener()

        def outside_for_test_tracing(x):
            return x + 5

        before = len(obs_spans.TRACER.finished())
        jax.jit(outside_for_test_tracing)(jnp.ones(2)).block_until_ready()
        assert metrics.XLA.executables("outside_for_test_tracing") == 1
        assert len(obs_spans.TRACER.finished()) == before

    def test_nested_traces_do_not_add_seconds_twice(self):
        stats = metrics.XlaCompileStats()
        trace = metrics._XLA_TRACE
        stats.on_scalar(trace, 0.0, fun_name="outer")
        stats.on_scalar(trace, 0.1, fun_name="inner")
        stats.on_duration(trace, 0.5, fun_name="inner")
        stats.on_duration(trace, 2.0, fun_name="outer")
        out = stats.summary()
        assert out["traces"] == 2 and out["trace_s"] == 2.0
        assert {r["fun_name"]: r["trace_s"] for r in out["top"]} \
            == {"outer": 2.0, "inner": 0.0}

    def test_cache_events_and_names_fold(self):
        stats = metrics.XlaCompileStats()
        stats.on_event("/jax/compilation_cache/cache_hits")
        stats.on_event("/jax/compilation_cache/cache_misses")
        stats.on_event("/jax/compilation_cache/cache_misses")
        stats.on_duration(metrics._XLA_CACHE_RETRIEVAL, 0.25)
        for name in ("jit(run_chunk)", "jit_run_chunk"):
            stats.on_duration(
                "/jax/core/compile/jaxpr_to_mlir_module_duration", 1.0,
                fun_name=name)
        out = stats.summary()
        assert (out["cache_hits"], out["cache_misses"],
                out["cache_retrieval_s"]) == (1, 2, 0.25)
        assert [(r["fun_name"], r["lower_s"]) for r in out["top"]] \
            == [("run_chunk", 2.0)]

    def test_cache_misses_name_their_function(self):
        stats = metrics.XlaCompileStats()
        backend = metrics._XLA_BACKEND
        for name, event in (("jit(fill)", "cache_misses"),
                            ("jit(run_chunk)", "cache_hits")):
            stats.on_scalar(backend, 0.0, fun_name=name)
            stats.on_event("/jax/compilation_cache/" + event)
            stats.on_duration(backend, 1.5, fun_name=name)
        stats.on_event("/jax/compilation_cache/cache_misses")  # unowned
        out = stats.summary()
        assert out["missed"] == ["fill"] and out["cache_misses"] == 2
        rows = {r["fun_name"]: r for r in out["top"]}
        assert rows["fill"]["cache_misses"] == 1
        assert rows["run_chunk"]["cache_hits"] == 1

    def test_status_carries_the_block(self, served):
        xla = get(served["server"], "/internal/status")["serving"]["xla"]
        assert xla["executables"] >= 3 and len(xla["top"]) <= 10
        assert {"trace_s", "lower_s", "backend_s", "cache_hits",
                "cache_misses", "cache_retrieval_s", "missed"} <= set(xla)
        assert "run_chunk" in {r["fun_name"] for r in xla["top"]}

"""Observability layer: span trees, Prometheus exposition, flight recorder.

The acceptance scenario: a 4-request coalesced run through the serving
dispatcher must export valid Chrome trace-event JSON whose per-request span
trees account for the measured e2e latency, and ``/internal/metrics`` must
serve parseable Prometheus text with the four latency histograms. Spans are
default-on, so the overhead test pins that recording stays negligible.
"""

import gc
import json
import re
import socket
import sys
import threading
import time
import urllib.request

import pytest

from stable_diffusion_webui_distributed_tpu.models.configs import TINY
from stable_diffusion_webui_distributed_tpu.obs import (
    flightrec, prometheus, watchdog,
)
from stable_diffusion_webui_distributed_tpu.obs import spans as obs_spans
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
)
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.runtime.logging import (
    get_logger, lines_for_request,
)
from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
    ShapeBucketer,
)
from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
    ServingDispatcher,
)
from stable_diffusion_webui_distributed_tpu.serving import (
    metrics as metrics_mod,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    METRICS, HostStats,
)
from test_pipeline import init_params


def payload(**kw):
    defaults = dict(prompt="a cow", steps=4, width=32, height=32,
                    seed=7, sampler_name="Euler a")
    defaults.update(kw)
    return GenerationPayload(**defaults)


def assert_chrome_event(e):
    """One Chrome trace-event "X" record with the sdtpu arg contract; a
    span that ran on the device is an async pair, whose "b" half carries
    the same keys and whose "e" half closes it by ``id``."""
    if e["ph"] == "e":
        assert e["cat"] == "sdtpu.device" and e["id"] and e["ts"] >= 0
        assert "request_id" in e["args"]
        return
    assert e["ph"] == ("b" if e["cat"] == "sdtpu.device" else "X")
    for key in ("name", "cat", "pid", "tid", "ts", "dur", "args"):
        assert key in e, f"missing {key}: {e}"
    assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
    assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    assert "request_id" in e["args"] and "span_id" in e["args"]


@pytest.fixture(scope="module")
def engine():
    return Engine(TINY, init_params(TINY), chunk_size=4,
                  state=GenerationState())


@pytest.fixture(scope="module")
def bucketer():
    return ShapeBucketer(shapes=[(32, 32), (48, 48)], batches=[4])


# -- span lifecycle ----------------------------------------------------------

class TestSpanLifecycle:
    def test_request_records_root_and_children(self):
        obs_spans.TRACER.clear()
        with obs_spans.request("rid-1", name="unit", route="/x") as req:
            assert obs_spans.current() is req
            assert obs_spans.current_request_id() == "rid-1"
            with obs_spans.span("outer", k=1) as outer:
                with obs_spans.span("inner"):
                    pass
        assert obs_spans.current() is None
        done = {t.request_id: t for t in obs_spans.TRACER.finished()}
        tr = done["rid-1"]
        assert tr.status == "ok" and tr.dur > 0
        by_name = {s.name: s for s in tr.spans}
        assert set(by_name) == {"unit", "outer", "inner"}
        root, out, inner = by_name["unit"], by_name["outer"], by_name["inner"]
        assert root.parent_id is None and root.span_id == tr.root_id
        assert out.parent_id == tr.root_id
        assert inner.parent_id == out.span_id
        assert root.attrs["status"] == "ok" and root.attrs["route"] == "/x"
        assert out.attrs == {"k": 1} and out is outer

    def test_error_status_and_detail(self):
        flightrec.RECORDER.clear()
        with pytest.raises(ValueError):
            with obs_spans.request("rid-err", name="unit"):
                raise ValueError("kaboom")
        tr = {t.request_id: t for t in
              obs_spans.TRACER.finished()}["rid-err"]
        assert tr.status == "error"
        assert "ValueError" in tr.detail and "kaboom" in tr.detail
        assert len(flightrec.RECORDER) == 1

    def test_interrupt_mark_sticks(self):
        with obs_spans.request("rid-int", name="unit") as req:
            obs_spans.mark(req, "interrupted", "cancelled by client")
        tr = {t.request_id: t for t in
              obs_spans.TRACER.finished()}["rid-int"]
        assert tr.status == "interrupted"
        assert tr.detail == "cancelled by client"

    def test_slow_threshold(self, monkeypatch):
        monkeypatch.setattr(obs_spans.TRACER, "slow_s", 0.01)
        with obs_spans.request("rid-slow", name="unit"):
            time.sleep(0.03)
        tr = {t.request_id: t for t in
              obs_spans.TRACER.finished()}["rid-slow"]
        assert tr.status == "slow" and "threshold" in tr.detail

    def test_disabled_tracer_is_noop(self, monkeypatch):
        monkeypatch.setattr(obs_spans.TRACER, "enabled", False)
        before = len(obs_spans.TRACER.finished())
        with obs_spans.request("rid-off", name="unit") as req:
            assert req is None
            with obs_spans.span("child") as sp:
                assert sp is None
        assert len(obs_spans.TRACER.finished()) == before

    def test_span_outside_request_is_noop(self):
        with obs_spans.span("orphan") as sp:
            assert sp is None

    def test_store_retention_bounded(self):
        tr = obs_spans.SpanTracer(enabled=True, max_requests=2)
        for i in range(3):
            req = obs_spans.RequestTrace(f"r{i}", "unit", {})
            tr.open(req)
            tr.close(req)
        kept = [t.request_id for t in tr.finished()]
        assert kept == ["r1", "r2"]  # oldest evicted
        assert tr.summary()["capacity"] == 2

    def test_maybe_request_joins_active_context(self):
        with obs_spans.request("rid-outer", name="unit") as outer:
            with obs_spans.maybe_request("rid-ignored") as joined:
                assert joined is outer  # no double-rooting
        done = {t.request_id for t in obs_spans.TRACER.finished()}
        assert "rid-ignored" not in done

    def test_bind_current_crosses_threads(self):
        seen = {}

        def probe():
            seen["rid"] = obs_spans.current_request_id()

        with obs_spans.request("rid-thread", name="unit"):
            t = threading.Thread(target=obs_spans.bind_current(probe))
            t.start()
            t.join()
        assert seen["rid"] == "rid-thread"


# -- the acceptance scenario: 4-request coalesced run ------------------------

class TestCoalescedRunTracing:
    RIDS = ("req-obs-0", "req-obs-1", "req-obs-2", "req-obs-3")

    @pytest.fixture(scope="class")
    def run(self, engine, bucketer):
        """4 concurrent requests (2 shapes -> 2 buckets) through a
        coalescing dispatcher, with per-request wall clocks. The requests
        compile on the CPU (20-35 s beside five other test workers), so
        the tracer's 30 s "slow" mark is off while they run: the statuses
        below are about errors, not about this machine's speed."""
        slow_s, obs_spans.TRACER.slow_s = obs_spans.TRACER.slow_s, 0.0
        try:
            yield self._run(engine, bucketer)
        finally:
            obs_spans.TRACER.slow_s = slow_s

    def _run(self, engine, bucketer):
        obs_spans.TRACER.clear()
        flightrec.RECORDER.clear()
        METRICS.clear()
        metrics_mod.DEVICE.clear()
        prometheus.clear_histograms()
        disp = ServingDispatcher(engine, bucketer=bucketer, window=0.6)
        shapes = [(32, 32), (24, 32), (48, 48), (40, 40)]
        walls, errors = {}, []

        def submit(i):
            w, h = shapes[i]
            p = payload(width=w, height=h, seed=300 + i,
                        prompt=f"obs cow {i}", request_id=self.RIDS[i])
            t0 = time.perf_counter()
            try:
                disp.submit(p)
            except Exception as e:  # noqa: BLE001 — surfaced by assert
                errors.append(e)
            walls[self.RIDS[i]] = time.perf_counter() - t0

        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(4)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total_wall = time.perf_counter() - t0
        assert not errors, errors
        traces = {t.request_id: t for t in obs_spans.TRACER.finished()
                  if t.request_id in self.RIDS}
        return {"traces": traces, "walls": walls, "total_wall": total_wall}

    def test_every_request_has_a_trace(self, run):
        assert set(run["traces"]) == set(self.RIDS)
        for tr in run["traces"].values():
            assert tr.status == "ok"
            assert tr.name == "serve.txt2img"

    def test_span_tree_shape(self, run):
        for rid, tr in run["traces"].items():
            names = {s.name for s in tr.spans}
            assert "serve.txt2img" in names  # root
            assert "bucket" in names         # bucketer span joins the ctx
            assert "queue_wait" in names     # live (leader) or recorded
            # the device time is visible either as this request's own
            # dispatch span or as the follower's wait on its leader's
            assert ("dispatch.device" in names
                    or "coalesced.wait" in names), (rid, names)

    def test_coalesce_links_leader_and_followers(self, run):
        waits = [s for tr in run["traces"].values() for s in tr.spans
                 if s.name == "coalesced.wait"]
        if not all("dispatch.device" in {s.name for s in tr.spans}
                   for tr in run["traces"].values()):
            assert waits, "followers must carry their wait on the leader"
        for sp in waits:
            leader = run["traces"][sp.attrs["leader_request_id"]]
            device = [s for s in leader.spans
                      if s.span_id == sp.attrs["leader_span_id"]]
            assert [s.name for s in device] == ["dispatch.device"]

    def test_the_device_s_side_is_in_the_leader_s_tree(self, run):
        """A coalesced dispatch's ``device.run`` spans belong to the tree
        that holds its ``dispatch.device`` (ISSUE 71); a follower, whose
        wait carries the leader's ids, has none."""
        for tr in run["traces"].values():
            names = {s.name for s in tr.spans}
            runs = [s for s in tr.spans if s.name == "device.run"]
            if "dispatch.device" not in names:
                assert not runs and not tr.works
                continue
            section, = [s for s in tr.spans if s.name == "dispatch.device"]
            assert {"run_chunk", "decode_u8"} \
                <= {s.attrs["kind"] for s in runs}
            for s in runs:
                assert s.t0 >= section.t0
                assert s.t0 + s.dur <= section.t0 + section.dur + 1e-6
        leaders = [tr for tr in run["traces"].values() if tr.works]
        block = METRICS.summary()["device"]
        assert block["requests"] == len(leaders)
        assert block["dispatches_total"] \
            == sum(len(tr.works) for tr in leaders)

    def test_root_duration_matches_measured_e2e(self, run):
        # acceptance: the span tree accounts for the measured latency
        for rid, tr in run["traces"].items():
            wall = run["walls"][rid]
            assert abs(tr.dur - wall) < 0.35, (rid, tr.dur, wall)
            # direct children cover the bulk of the request: queue wait +
            # device dispatch dominate e2e by construction
            children = [s for s in tr.spans
                        if s.parent_id == tr.root_id
                        and s.name != "serve.txt2img"]
            covered = sum(s.dur for s in children)
            assert covered >= 0.5 * tr.dur, (rid, covered, tr.dur)
            for s in tr.spans:
                assert s.t0 >= tr.t0 - 0.05
                assert s.t0 + s.dur <= tr.t0 + tr.dur + 0.05

    def test_chrome_export_is_schema_valid(self, run):
        doc = obs_spans.TRACER.export_chrome()
        assert doc["displayTimeUnit"] == "ms"
        events = doc["traceEvents"]
        assert len(events) >= 4
        for e in events:
            assert_chrome_event(e)
        # round-trips through strict JSON
        assert json.loads(json.dumps(doc)) == doc
        # the events of this run span ~the measured total wall clock
        ours = [e for e in events
                if e["args"]["request_id"] in self.RIDS]
        lo = min(e["ts"] for e in ours)
        hi = max(e["ts"] + e["dur"] for e in ours)
        assert abs((hi - lo) / 1e6 - run["total_wall"]) < 0.5

    def test_histograms_observed_per_request(self, run):
        for key, minimum in (("e2e", 4), ("queue_wait", 4),
                             ("device_dispatch", 1), ("decode", 1)):
            _counts, _sum, n = prometheus.HISTOGRAMS[key].snapshot()
            assert n >= minimum, (key, n)
        # e2e sum is the sum of the four root durations
        _c, total, n = prometheus.HISTOGRAMS["e2e"].snapshot()
        assert n == 4
        want = sum(t.dur for t in run["traces"].values())
        assert total == pytest.approx(want, rel=0.01)


# -- the wait before the device, live (ISSUE 37) -----------------------------

class FakeEngine:
    """What the dispatcher's queueing touches of an engine; no model."""

    expander = None
    policy = None
    preempt_hook = None
    model_name = "fake"

    def __init__(self):
        import types

        self.family = types.SimpleNamespace(inpaint=False)
        self.state = GenerationState()
        self.device_started = []    # perf_counter at each device section

    def _parse_controlnet_units(self, p):
        return []

    def generate_range(self, run, start, count, job):
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            GenerationResult,
        )

        self.device_started.append(time.perf_counter())
        return GenerationResult(parameters=run.model_dump())


class FakeExecDispatcher(ServingDispatcher):
    """The real queueing (groups, window, locks, spans) around a group
    execution that runs no model: a short device section and one decoded
    batch fetched the engine's way."""

    def _execute_group(self, g):
        import jax.numpy as jnp

        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            GenerationResult,
        )

        self.engine.device_started.append(time.perf_counter())
        with obs_spans.span("vae_decode_fetch"):
            Engine._fetch_decoded(jnp.zeros((1, 8, 8, 3), jnp.uint8))
        time.sleep(0.02)
        for t in g.tickets:
            t.result = GenerationResult(parameters=t.payload.model_dump())


def tree_of(rid):
    tr = {t.request_id: t for t in obs_spans.TRACER.finished()}[rid]
    return tr, {s.name: s for s in tr.spans}


def children_of(tr, span):
    return sorted(s.name for s in tr.spans if s.parent_id == span.span_id)


class TestLiveWaitSpans:
    @pytest.fixture()
    def quiet(self):
        """No slow marks from this machine's speed; clean stores."""
        slow_s, obs_spans.TRACER.slow_s = obs_spans.TRACER.slow_s, 0.0
        obs_spans.TRACER.clear()
        METRICS.clear()
        yield
        obs_spans.TRACER.slow_s = slow_s

    @pytest.mark.parametrize("path", ["solo", "grouped"])
    def test_one_request_waits_live(self, quiet, bucketer, path):
        engine = FakeEngine()
        disp = FakeExecDispatcher(engine, bucketer=bucketer, window=0.05)
        extra = {"enable_hr": True} if path == "solo" else {}
        disp.submit(payload(request_id=f"live-{path}", **extra))
        tr, by = tree_of(f"live-{path}")
        wait = by["queue_wait"]
        assert wait.parent_id == tr.root_id
        assert children_of(tr, wait) == (
            ["engine.wait"] if path == "solo"
            else ["coalesce.window", "engine.wait"])
        assert by["engine.wait"].attrs == {"queued": 0}
        if path == "grouped":
            # one image under a ladder whose top rung is 4: not full
            assert by["coalesce.window"].attrs == {"window_s": 0.05,
                                                   "ended_by": "timer"}
            assert by["coalesce.window"].dur >= 0.05
        # the interval the after-the-fact record had: ticket creation to
        # the start of the device section, which the histogram's sample
        # (taken beside it) and the device section's own clock both say
        assert wait.t0 >= tr.t0
        assert wait.dur == pytest.approx(METRICS.avg_queue_wait(), abs=1e-3)
        end = wait.t0 + wait.dur
        assert end <= engine.device_started[0]
        assert by["engine.wait"].t0 + by["engine.wait"].dur \
            == pytest.approx(end, abs=1e-3)
        assert by["dispatch.device"].parent_id == tr.root_id
        assert by["dispatch.device"].t0 >= end

    def test_coalesce_window_counter_has_both_label_values(self, quiet):
        """``sdtpu_coalesce_window_total{ended_by}``: one increment a
        leader, ``full`` under a ladder a request fills alone, ``timer``
        under one it does not; a solo request counts nowhere."""
        prometheus.COALESCE_WINDOW_COUNTER.clear()
        for batches, extra in (([1], {}), ([4], {}), ([4], {}),
                               ([1], {"enable_hr": True})):
            FakeExecDispatcher(
                FakeEngine(), window=0.05,
                bucketer=ShapeBucketer(shapes=[(32, 32)], batches=batches),
            ).submit(payload(**extra))
        assert prometheus.COALESCE_WINDOW_COUNTER.snapshot() == {
            ("full",): 1.0, ("timer",): 2.0}
        text = prometheus.render()
        assert "# TYPE sdtpu_coalesce_window_total counter" in text
        assert 'sdtpu_coalesce_window_total{ended_by="full"} 1' in text
        assert 'sdtpu_coalesce_window_total{ended_by="timer"} 2' in text
        prometheus.clear_histograms()
        assert prometheus.COALESCE_WINDOW_COUNTER.total() == 0

    def test_a_follower_waits_on_its_leader(self, quiet, bucketer):
        engine = FakeEngine()
        disp = FakeExecDispatcher(engine, bucketer=bucketer, window=0.3)
        threads = [threading.Thread(
            target=disp.submit,
            args=(payload(request_id=f"pair-{i}", seed=40 + i),))
            for i in range(2)]
        threads[0].start()
        time.sleep(0.05)        # inside the leader's window
        threads[1].start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
        (lead, by_lead), (tr, by) = tree_of("pair-0"), tree_of("pair-1")
        assert by_lead["dispatch.device"].attrs["requests"] == 2
        wait = by["coalesced.wait"]
        assert wait.parent_id == tr.root_id
        assert wait.attrs == {
            "leader_request_id": "pair-0",
            "leader_span_id": by_lead["dispatch.device"].span_id}
        assert "dispatch.device" not in by and "engine.wait" not in by
        # its queue_wait is the leader's record: it ends where the
        # leader's own ends, and has no children
        assert children_of(tr, by["queue_wait"]) == []
        assert by["queue_wait"].t0 + by["queue_wait"].dur == pytest.approx(
            by_lead["queue_wait"].t0 + by_lead["queue_wait"].dur, abs=1e-3)
        assert children_of(lead, by_lead["queue_wait"]) \
            == ["coalesce.window", "engine.wait"]
        # the wait covers the dispatch that carried it
        device = by_lead["dispatch.device"]
        assert wait.t0 <= device.t0
        assert wait.t0 + wait.dur >= device.t0 + device.dur

    def test_a_cancelled_wait_still_closes(self, quiet, bucketer):
        """Cancelled before dispatch: the hand-opened spans close on the
        way out and the thread's context is the root's again."""
        engine = FakeEngine()
        disp = FakeExecDispatcher(engine, bucketer=bucketer, window=0.0)
        seen = {}

        def begin_request():
            disp.cancel("live-cancel")

        engine.state.begin_request = begin_request
        with obs_spans.request("live-cancel", name="unit") as req:
            disp.submit(payload(request_id="live-cancel", enable_hr=True))
            seen["ctx"] = obs_spans._CURRENT.get()
        assert seen["ctx"] == (req, req.root_id)
        names = [s.name for s in req.spans]
        assert names.count("queue_wait") == 1
        assert names.count("engine.wait") == 1
        assert "dispatch.device" not in names

    def test_the_capture_holds_the_waits(self, quiet, bucketer, tmp_path):
        import jax

        engine = FakeEngine()
        disp = FakeExecDispatcher(engine, bucketer=bucketer, window=0.02)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            disp.submit(payload(request_id="live-prof"))
        finally:
            jax.profiler.stop_trace()
        tr, by = tree_of("live-prof")
        import glob

        data = jax.profiler.ProfileData.from_file(glob.glob(
            str(tmp_path) + "/**/*.xplane.pb", recursive=True)[0])
        found = {}
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for event in line.events:
                    if event.name.startswith("sdtpu:"):
                        found[event.name[6:]] = dict(event.stats)
        for name in ("queue_wait", "coalesce.window", "engine.wait",
                     "decode.wait", "fetch.copy"):
            assert found[name]["request_id"] == "live-prof", name
            assert found[name]["span_id"] == by[name].span_id, name


class TestSlowAgainstTheMedian:
    @staticmethod
    def finished(name, dur, **attrs):
        req = obs_spans.RequestTrace("r", name, attrs)
        req.dur = dur
        return req

    def test_synthetic_durations(self):
        tr = obs_spans.SpanTracer(enabled=True, slow_s=30.0)
        sdxl = dict(width=1024, height=1024, steps=30)
        # fewer than SLOW_MIN_SAMPLES ok requests of the class: never
        for _ in range(obs_spans.SLOW_MIN_SAMPLES):
            assert tr.slow_detail(self.finished("txt2img", 9.0, **sdxl)) \
                is None
        tr.clear()
        for i in range(obs_spans.SLOW_MIN_SAMPLES):
            assert tr.slow_detail(
                self.finished("txt2img", 3.2 + 0.01 * i, **sdxl)) is None
        assert tr.slow_detail(self.finished("txt2img", 4.7, **sdxl)) is None
        detail = tr.slow_detail(self.finished("txt2img", 6.0, **sdxl))
        assert "median" in detail and "1024x1024" in detail
        # a slow one does not join the window: the next is judged alike
        assert tr.slow_detail(self.finished("txt2img", 6.0, **sdxl))
        # another class (steps, or root name) has its own window
        assert tr.slow_detail(self.finished(
            "txt2img", 6.0, width=1024, height=1024, steps=60)) is None
        assert tr.slow_detail(self.finished("img2img", 6.0, **sdxl)) is None
        # a root without the three attrs is only held to the absolute rule
        for dur in [0.01] * 10 + [5.0]:
            assert tr.slow_detail(self.finished("unit", dur)) is None
        assert "threshold" in tr.slow_detail(self.finished("unit", 31.0))
        # the window is the last SLOW_WINDOW: a class that got slower for
        # good stops being flagged
        for _ in range(obs_spans.SLOW_WINDOW):
            tr.slow_detail(self.finished("txt2img", 4.7, **sdxl))
        assert tr.slow_detail(self.finished("txt2img", 6.0, **sdxl)) is None
        # SDTPU_OBS_SLOW_S=0 turns slow capture off, this rule too
        off = obs_spans.SpanTracer(enabled=True, slow_s=0.0)
        for dur in [1.0] * 10 + [50.0]:
            assert off.slow_detail(self.finished("txt2img", dur, **sdxl)) \
                is None

    def test_a_slow_first_request_does_not_blind_the_class(self):
        """SLOW_MIN_SAMPLES is 3: the warm-up's first request holds the
        program loads and took five times the rest; the median of the
        three is still the steady request."""
        tr = obs_spans.SpanTracer(enabled=True, slow_s=30.0)
        tiny = dict(width=512, height=512, steps=20)
        for dur in (5.0, 1.0, 1.01):
            assert tr.slow_detail(self.finished("txt2img", dur, **tiny)) \
                is None
        assert tr.slow_detail(self.finished("txt2img", 1.02, **tiny)) is None
        assert "median 1.0" in tr.slow_detail(
            self.finished("txt2img", 1.6, **tiny))
        assert tr.slow_detail(self.finished("txt2img", 1.0, **tiny)) is None

    def test_a_slow_request_is_kept_with_its_tree(self, monkeypatch):
        obs_spans.TRACER.clear()
        flightrec.RECORDER.clear()
        monkeypatch.setattr(obs_spans.TRACER, "slow_s", 30.0)
        tiny = dict(width=32, height=32, steps=2)
        for i in range(obs_spans.SLOW_MIN_SAMPLES + 1):
            with obs_spans.request(f"med-{i}", name="txt2img", **tiny):
                with obs_spans.span("dispatch.device"):
                    time.sleep(0.06 if i == obs_spans.SLOW_MIN_SAMPLES
                               else 0.005)
        entry = flightrec.RECORDER.dump()["entries"][-1]
        assert entry["request_id"] == f"med-{obs_spans.SLOW_MIN_SAMPLES}"
        assert entry["reason"] == "slow" and "median" in entry["detail"]
        assert {e["name"] for e in entry["spans"]} \
            == {"txt2img", "dispatch.device"}
        assert len(flightrec.RECORDER) == 1


# -- the host clock: what no request's tree covers (ISSUE 54) ----------------

def dawdle_in_a_named_function(clock, ticks):
    for _ in range(ticks):
        clock.tick()
        time.sleep(0.01)


class TestHostClock:
    @pytest.fixture()
    def clean(self, monkeypatch):
        monkeypatch.setattr(obs_spans.TRACER, "slow_s", 30.0)
        obs_spans.TRACER.clear()
        flightrec.RECORDER.clear()
        prometheus.clear_histograms()

    @pytest.mark.parametrize("lag_ms, stalls", [(5, 0), (30, 1), (200, 1)])
    def test_a_late_wake_up_is_a_stall(self, clean, lag_ms, stalls):
        """``tick()`` inline on an injected clock: the lag is the stall, in
        the block, the ring and the tree of the request active then."""
        with obs_spans.request("stalled", name="unit"):
            with obs_spans.span("inner"):
                now = [time.perf_counter()]
                clock = watchdog.HostClock(clock=lambda: now[0])
                clock.tick()        # the first wake-up is due from here
                now[0] += watchdog.TICK_S + lag_ms / 1e3
                clock.tick()
        host = clock.stats.summary()
        assert host["ticks"] == 2 and host["stalls"] == stalls
        assert host["stall_ms"] == pytest.approx(lag_ms * stalls)
        assert host["stall_ms_max"] == pytest.approx(lag_ms * stalls)
        assert [(round(sp.dur * 1e3), sp.attrs["requests"],
                 sp.attrs["spans"]) for sp in clock.ring] \
            == [(lag_ms, ["stalled"], ["inner"])] * stalls
        tr, spans = tree_of("stalled")
        assert ("host.stall" in spans) == bool(stalls)
        if stalls:
            assert spans["host.stall"].parent_id == tr.root_id
            assert spans["host.stall"].dur == pytest.approx(lag_ms / 1e3)
        events = clock.events()
        assert [e["name"] for e in events] == ["host.stall"] * stalls
        for e in events:
            assert_chrome_event(e)
            assert e["dur"] == pytest.approx(lag_ms * 1e3)
        assert prometheus.HOST_STALL_COUNTER.total() \
            == pytest.approx(lag_ms / 1e3 * stalls)
        assert ("sdtpu_host_stall_seconds_total{} " in prometheus.render()) \
            == bool(stalls)

    def test_the_thread_counts_a_call_that_keeps_the_gil(self, clean):
        """The real thread: with the switch interval over it, 0.2 s of
        bytecode on this thread is 0.2 s no other thread ran."""
        clock = watchdog.HostClock().start()
        interval = sys.getswitchinterval()
        try:
            time.sleep(0.03)
            sys.setswitchinterval(0.5)
            until = time.perf_counter() + 0.2
            while time.perf_counter() < until:
                pass
        finally:
            sys.setswitchinterval(interval)
            time.sleep(0.03)
            clock.stop()
        host = clock.stats.summary()
        assert host["stalls"] >= 1 and host["stall_ms_max"] >= 150
        assert host["ticks"] >= 3

    def test_a_request_is_sampled_once_while_it_is_slow(self, clean,
                                                        monkeypatch):
        dumps = []
        dump_stacks = watchdog.dump_stacks
        monkeypatch.setattr(watchdog, "dump_stacks",
                            lambda: dumps.append(1) or dump_stacks())
        tiny = dict(width=32, height=32, steps=2)
        clock = watchdog.HostClock()
        for i in range(obs_spans.SLOW_MIN_SAMPLES + 1):
            with obs_spans.request(f"quick-{i}", name="txt2img", **tiny):
                dawdle_in_a_named_function(clock, 1)
        assert not dumps and not len(flightrec.RECORDER)
        assert all(t.live is None for t in obs_spans.TRACER.finished())
        with obs_spans.request("dawdler", name="txt2img", **tiny):
            with obs_spans.span("dispatch.device"):
                with obs_spans.span("chunk.fence_wait"):
                    dawdle_in_a_named_function(clock, 8)
        assert len(dumps) == 1
        entry, = flightrec.RECORDER.dump()["entries"]
        assert entry["request_id"] == "dawdler" and entry["reason"] == "slow"
        live = entry["live"]
        assert "dawdle_in_a_named_function" in live["stacks"]
        assert [sp["name"] for sp in live["open"]] \
            == ["chunk.fence_wait", "dispatch.device"]
        assert live["open"][0]["thread"] == threading.get_ident()
        assert 0 < live["open"][0]["age_ms"] <= live["age_ms"]
        assert live["stalls"] == clock.events()
        json.dumps(entry)       # the recorder's entries stay plain JSON

    def test_slow_capture_off_takes_no_sample(self, clean, monkeypatch):
        monkeypatch.setattr(obs_spans.TRACER, "slow_s", 0.0)
        clock = watchdog.HostClock()
        tiny = dict(width=32, height=32, steps=2)
        for i in range(5):
            with obs_spans.request(f"off-{i}", name="txt2img", **tiny):
                dawdle_in_a_named_function(clock, 6 if i == 4 else 1)
        assert all(t.live is None for t in obs_spans.TRACER.finished())

    def test_a_stall_is_cut_to_where_the_request_began(self, clean):
        """A request that began inside the stall owns what it overlapped."""
        now = [time.perf_counter()]
        clock = watchdog.HostClock(clock=lambda: now[0])
        clock.tick()
        time.sleep(2 * watchdog.TICK_S)     # past where the clock was due
        with obs_spans.request("latecomer", name="unit") as req:
            now[0] = req.t0 + 0.1
            clock.tick()
        _, spans = tree_of("latecomer")
        assert spans["host.stall"].t0 == req.t0
        assert spans["host.stall"].dur == pytest.approx(0.1)
        assert clock.ring[0].dur > 0.1

    def test_a_tick_takes_no_median(self, clean, monkeypatch):
        """The rule's median is taken where a duration joins its class: a
        tick compares a float a request, however many are active."""
        tiny = dict(width=32, height=32, steps=2)
        clock = watchdog.HostClock()
        for i in range(obs_spans.SLOW_MIN_SAMPLES):
            with obs_spans.request(f"seed-{i}", name="txt2img", **tiny):
                time.sleep(0.002)
        monkeypatch.setattr(obs_spans.statistics, "median",
                            lambda values: pytest.fail("a median a tick"))
        with obs_spans.request("slow-one", name="txt2img", **tiny) as req:
            time.sleep(0.02)
            clock.tick()
            assert req.live is not None and not req.live["open"]
        with obs_spans.request("other-class", name="txt2img", width=64,
                               height=64, steps=2) as other:
            time.sleep(0.02)
            clock.tick()
            assert other.live is None

    def test_a_collection_is_counted(self, clean):
        before = list(gc.callbacks)
        clock = watchdog.HostClock().start()
        try:
            assert len(gc.callbacks) == len(before) + 1
            with obs_spans.request("collected", name="unit"):
                gc.collect()
                time.sleep(0.05)    # a few ticks: the deque is drained
            # a collection that begins with the tracer's lock held (inside
            # SpanTracer.record) must end: the callback takes no lock
            done = threading.Event()

            def collect_under_the_lock():
                with obs_spans.TRACER._lock:
                    gc.collect()
                done.set()

            t = threading.Thread(target=collect_under_the_lock, daemon=True)
            t.start()
            t.join(timeout=10)
            assert done.is_set()
            time.sleep(0.05)
        finally:
            clock.stop()
        assert gc.callbacks == before
        assert not any(t.name == "host-clock" for t in threading.enumerate())
        host = clock.stats.summary()
        assert host["gc_collections"]["2"] >= 2
        assert 0 < host["gc_pause_ms_max"] <= host["gc_pause_ms"]
        assert 'sdtpu_gc_pause_seconds_total{generation="2"}' \
            in prometheus.render()

    def test_each_clock_counts_for_itself(self, clean):
        """Two servers in one process: a block holds its own clock's."""
        now = [time.perf_counter()]
        one, other = (watchdog.HostClock(clock=lambda: now[0])
                      for _ in range(2))
        one.tick()
        now[0] += 0.1
        one.tick()
        other.tick()
        assert one.stats.summary()["stalls"] == 1 and len(one.ring) == 1
        assert other.stats.summary()["stalls"] == 0 and not other.ring
        assert other.stats.summary()["ticks"] == 1

    def test_between_two_exchanges(self):
        host = HostStats()
        assert host.exchange_began(10.0) is None    # nothing before it
        assert host.exchange_began(10.5) is None    # the first is in flight
        host.exchange_ended(11.0)
        host.exchange_ended(12.0)           # the last in flight: the stamp
        assert host.exchange_began(12.1) == pytest.approx(0.1)
        host.exchange_ended(12.2)
        assert host.exchange_began(12.225) == pytest.approx(0.025)
        host.exchange_ended(12.4)
        # accepted (the stamp) before the last one ended, taken up after
        assert host.exchange_began(12.3) is None
        out = host.summary()
        assert out["exchanges"] == 5 and out["betweens"] == 2
        assert out["between_ms"] == pytest.approx(125.0)
        assert out["between_ms_max"] == pytest.approx(100.0)


# -- the device's side: device.run, the watcher -----------------------------

class Output:
    """Stands for a device array: ready when told."""

    def __init__(self, ready=False):
        self.event = threading.Event()
        self.waits = 0
        if ready:
            self.event.set()

    def is_ready(self):
        return self.event.is_set()

    def block_until_ready(self):
        self.waits += 1
        assert self.event.wait(30)
        return self


def device_runs(req):
    return [s for s in req.spans if s.name == "device.run"]


class TestDeviceWork:
    @pytest.fixture()
    def clean(self, monkeypatch):
        monkeypatch.setattr(obs_spans.TRACER, "slow_s", 30.0)
        obs_spans.TRACER.clear()
        flightrec.RECORDER.clear()
        prometheus.clear_histograms()
        metrics_mod.DEVICE.clear()

    @pytest.mark.parametrize("ready_first", [False, True])
    def test_a_fence_stamps_or_finds_it_done(self, clean, ready_first):
        """``late`` from what ``is_ready()`` says before the wait: a wait
        that blocked ends at the ready time (exact), one that found the
        device done knows a bound."""
        out = Output(ready=ready_first)
        with obs_spans.request("fenced", name="unit") as req:
            with obs_spans.span("chunk.enqueue", steps=3) as enq:
                work = obs_spans.device_work("run_chunk")
                work.queued(out)
            assert enq.attrs["dry"] is True      # nothing was queued
            with obs_spans.span("chunk.fence_wait") as wait, \
                    obs_spans.fence(out):
                out.event.set()
            assert wait.attrs["late"] is ready_first
            run, = device_runs(req)
        assert run.parent_id == enq.span_id
        assert run.attrs["kind"] == "run_chunk" and run.attrs["steps"] == 3
        assert ("bound" in run.attrs) is ready_first
        assert ("exact" in run.attrs) is not ready_first
        assert run.tid == obs_spans.DEVICE_TID
        assert run.t0 >= enq.t0 and run.dur >= 0
        assert req.fences == [1, int(ready_first)]

    def test_dry_says_whether_the_device_had_anything_left(self, clean):
        first, second, third = Output(), Output(), Output()
        with obs_spans.request("queue", name="unit") as req:
            seen = []
            for out in (first, second):
                with obs_spans.span("chunk.enqueue") as enq:
                    obs_spans.device_work("run_chunk").queued(out)
                seen.append(enq.attrs["dry"])
            first.event.set()
            second.event.set()      # both done before the host is back
            with obs_spans.span("chunk.enqueue") as enq:
                obs_spans.device_work("run_chunk").queued(third)
            seen.append(enq.attrs["dry"])
            third.event.set()
        assert seen == [True, False, True]
        runs = device_runs(req)
        assert len(runs) == 3 and all("bound" in r.attrs for r in runs)
        # in the order they were enqueued, one after the other
        for a, b in zip(runs, runs[1:]):
            assert a.t0 + a.dur <= b.t0 + 1e-9
        block = metrics_mod.DEVICE.summary()
        assert block["dry_enqueues"] == 2 and block["requests"] == 1
        assert block["dispatches"] == {"run_chunk": 3}
        assert block["dispatches_total"] == 3 and block["fences"] == 0
        assert block["busy_s"]["run_chunk"] == pytest.approx(
            sum(r.dur for r in runs))

    def test_a_span_that_enqueues_several_counts_each(self, clean):
        """``text_encode`` encodes a request's prompts one after the
        other: ``dry`` is each dispatch's own (its ``device.run`` says
        it), the span's attr says whether any was."""
        outs = [Output(), Output(), Output()]
        with obs_spans.request("prompts", name="unit") as req:
            with obs_spans.span("text_encode") as enc:
                obs_spans.device_work("encode").queued(outs[0])
                obs_spans.device_work("encode").queued(outs[1])
                outs[0].event.set()
                outs[1].event.set()
                obs_spans.device_work("encode").queued(outs[2])
                outs[2].event.set()
        assert enc.attrs["dry"] is True
        assert [r.attrs["dry"] for r in device_runs(req)] \
            == [True, False, True]
        assert {r.parent_id for r in device_runs(req)} == {enc.span_id}
        assert metrics_mod.DEVICE.summary()["dry_enqueues"] == 2

    def test_a_donated_output_is_refused(self, clean):
        import jax
        import jax.numpy as jnp

        bump = jax.jit(lambda x: x + 1, donate_argnums=(0,))
        carry = jnp.zeros((8, 128))
        bump(carry).block_until_ready()
        assert carry.is_deleted()
        with obs_spans.request("donor", name="unit") as req:
            with obs_spans.span("chunk.enqueue"):
                work = obs_spans.device_work("run_chunk")
                with pytest.raises(ValueError, match="no call donates"):
                    work.queued(carry)
        assert not req.works and not device_runs(req)

    def test_outside_a_request_nothing_is_kept(self, clean):
        out = Output(ready=True)
        obs_spans.device_work("run_chunk").queued(out)
        with obs_spans.fence(out):
            pass
        assert obs_spans.TRACER.settle(time.perf_counter()) \
            and out.waits == 0

    def test_what_a_request_never_fenced_does_not_hold_the_queue(self,
                                                                clean):
        """A request that ends with an output still unready (an interrupt)
        lets the array go; the next touch closes its ``device.run``."""
        left = Output()
        with obs_spans.request("cut-short", name="unit") as req:
            with obs_spans.span("chunk.enqueue"):
                obs_spans.device_work("run_chunk").queued(left)
        assert not device_runs(req) and req.works[0].output is None
        with obs_spans.request("next", name="unit"):
            with obs_spans.span("chunk.enqueue") as enq:
                obs_spans.device_work("run_chunk").queued(Output(ready=True))
        assert enq.attrs["dry"] is True
        run, = device_runs(req)
        assert "bound" in run.attrs and left.waits == 0

    def test_idle_is_the_section_less_its_runs(self, clean):
        """``serving.device`` ``idle_s``: ``dispatch.device`` less the
        union of the ``device.run`` inside, and the two counters."""
        out = Output()
        with obs_spans.request("idle", name="unit") as req:
            with obs_spans.span("dispatch.device") as section:
                time.sleep(0.02)            # the device has nothing
                with obs_spans.span("chunk.enqueue"):
                    obs_spans.device_work("run_chunk").queued(out)
                with obs_spans.span("chunk.fence_wait"), \
                        obs_spans.fence(out):
                    time.sleep(0.02)
                    out.event.set()
                time.sleep(0.01)            # nor has it here
        run, = device_runs(req)
        block = metrics_mod.DEVICE.summary()
        assert block["idle_s"] == pytest.approx(section.dur - run.dur)
        assert block["idle_s"] >= 0.03 and run.dur >= 0.02
        assert block["busy_s_total"] == pytest.approx(run.dur)
        text = prometheus.render()
        assert 'sdtpu_device_busy_seconds_total{kind="run_chunk"}' in text
        assert "sdtpu_device_dry_enqueues_total{} 1" in text
        assert all(work.output is None for work in req.works)

    def test_the_export_keeps_it_off_the_host_threads(self, clean):
        """A ``device.run`` is an async pair: no "X" event, so a reader
        that takes a request's "X" events for host time still does."""
        out = Output()
        with obs_spans.request("pair", name="unit"):
            with obs_spans.span("chunk.enqueue"):
                obs_spans.device_work("run_chunk").queued(out)
            with obs_spans.fence(out):
                out.event.set()
        events = obs_spans.TRACER.export_chrome()["traceEvents"]
        for e in events:
            assert_chrome_event(e)
        begin, end = (e for e in events if e["name"] == "device.run")
        assert (begin["ph"], end["ph"]) == ("b", "e")
        assert begin["id"] == end["id"] == begin["args"]["span_id"]
        assert end["ts"] == pytest.approx(begin["ts"] + begin["dur"])
        assert begin["args"]["kind"] == "run_chunk"
        enqueue, = (e for e in events if e["name"] == "chunk.enqueue")
        assert begin["args"]["parent_id"] == enqueue["args"]["span_id"]


class TestDeviceWatcher:
    @pytest.fixture()
    def clean(self, monkeypatch):
        monkeypatch.setattr(obs_spans.TRACER, "slow_s", 30.0)
        monkeypatch.setattr(obs_spans.TRACER, "armed", False)
        obs_spans.TRACER.clear()
        flightrec.RECORDER.clear()
        metrics_mod.DEVICE.clear()

    @staticmethod
    def watchers():
        return [t for t in threading.enumerate()
                if t.name == "device-watcher"]

    def test_off_no_thread_is_started(self, clean):
        """The census: a running clock, dispatches and fences, and no
        watcher thread."""
        clock = watchdog.HostClock().start()
        try:
            assert obs_spans.TRACER.watcher is clock.watcher
            out = Output()
            with obs_spans.request("unwatched", name="unit") as req:
                with obs_spans.span("chunk.enqueue"):
                    obs_spans.device_work("run_chunk").queued(out)
                with obs_spans.fence(out):
                    out.event.set()
            assert not self.watchers() and not clock.watcher.alive()
            assert out.waits == 0 and clock.watcher.stamped == 0
            assert "exact" in device_runs(req)[0].attrs
        finally:
            clock.stop()
        assert obs_spans.TRACER.watcher is None

    def test_armed_it_stamps_what_no_fence_waits_for(self, clean):
        """Armed (``/internal/trace.json?device=1``): an output nobody
        fences gets the moment it was ready, not the next enqueue's."""
        clock = watchdog.HostClock().start()
        obs_spans.TRACER.armed = True
        try:
            out = Output()
            with obs_spans.request("armed", name="unit") as req:
                with obs_spans.span("text_encode"):
                    obs_spans.device_work("encode").queued(out)
                assert self.watchers()
                time.sleep(0.02)
                out.event.set()
                deadline = time.monotonic() + 10
                while not device_runs(req) and time.monotonic() < deadline:
                    time.sleep(0.005)
                run, = device_runs(req)
            assert "exact" in run.attrs and run.dur >= 0.02
            assert clock.watcher.stamped == 1 and out.waits == 1
        finally:
            clock.stop()
        assert not self.watchers()      # it stops with the clock

    def test_an_inline_fenced_output_is_not_handed_over(self, clean):
        clock = watchdog.HostClock().start()
        obs_spans.TRACER.armed = True
        try:
            out = Output()
            with obs_spans.request("forked", name="unit"):
                with obs_spans.span("expand.fork"):
                    obs_spans.device_work("expand_fork").queued(
                        out, watch=False)
                    with obs_spans.fence(out):
                        out.event.set()
            assert out.waits == 0 and not self.watchers()
        finally:
            clock.stop()

    def test_the_slow_rule_starts_it_and_the_sample_carries_device(
            self, clean):
        """A request alive past the rule: ONE sample, with every dispatch
        it registered, ready or not; the watcher follows what is left and
        the entry says when each came."""
        tiny = dict(width=32, height=32, steps=2)
        clock = watchdog.HostClock().start()
        try:
            for i in range(obs_spans.SLOW_MIN_SAMPLES + 1):
                with obs_spans.request(f"quick-{i}", name="txt2img", **tiny):
                    time.sleep(0.01)
            assert not self.watchers()
            done, held = Output(ready=True), Output()
            with obs_spans.request("held-back", name="txt2img",
                                   **tiny) as req:
                with obs_spans.span("dispatch.device"):
                    for out in (done, held):
                        with obs_spans.span("chunk.enqueue"):
                            obs_spans.device_work("run_chunk").queued(out)
                    deadline = time.monotonic() + 10
                    while req.live is None and time.monotonic() < deadline:
                        time.sleep(0.005)
                    assert req.live is not None and req.watched
                    assert [(row["kind"], row["ready"])
                            for row in req.live["device"]] \
                        == [("run_chunk", True), ("run_chunk", False)]
                    assert self.watchers()
                    time.sleep(0.03)
                    held.event.set()        # the device lets go at last
                    with obs_spans.span("chunk.fence_wait"), \
                            obs_spans.fence(held):
                        held.block_until_ready()
        finally:
            clock.stop()
        entry, = flightrec.RECORDER.dump()["entries"]
        assert entry["request_id"] == "held-back"
        first, second = entry["live"]["device"]
        assert first["ready_at_sample"] and not second["ready_at_sample"]
        assert second["ready"] and second["ready_ms"] > second["enqueued_ms"]
        assert second["ready_ms"] - first["ready_ms"] >= 30
        json.dumps(entry)

    def test_many_threads_and_the_watcher_lose_no_dispatch(self, clean):
        """More request threads than cores, the watcher armed, the switch
        interval shortened: every dispatch gets exactly one ``device.run``
        and the counters add up."""
        threads, each = 12, 40
        clock = watchdog.HostClock().start()
        obs_spans.TRACER.armed = True
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        traces = []

        def one(i):
            with obs_spans.request(f"stress-{i}", name="unit") as req:
                traces.append(req)
                for j in range(each):
                    out = Output(ready=j % 3 == 0)
                    with obs_spans.span("chunk.enqueue"):
                        obs_spans.device_work("run_chunk").queued(out)
                    if j % 2:
                        with obs_spans.span("chunk.fence_wait"), \
                                obs_spans.fence(out):
                            out.event.set()
                    else:
                        out.event.set()
        try:
            workers = [threading.Thread(target=one, args=(i,), daemon=True)
                       for i in range(threads)]
            for t in workers:
                t.start()
            for t in workers:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in workers)
        finally:
            sys.setswitchinterval(interval)
            clock.stop()
        obs_spans.TRACER.settle(time.perf_counter())
        for req in traces:
            runs = device_runs(req)
            assert len(runs) == each == len(req.works)
            assert len({r.span_id for r in runs}) == each
            assert sorted(r.parent_id for r in runs) \
                == sorted(w.span.span_id for w in req.works)
            assert all(r.dur >= 0 for r in runs)
        block = metrics_mod.DEVICE.summary()
        assert block["requests"] == threads
        assert block["dispatches_total"] == threads * each
        assert block["fences"] == threads * (each // 2)

    def test_a_stall_with_no_request_alive_says_so(self, clean):
        """``alive``: 0 on a stall no request's tree can hold (none was
        alive when the clock woke: a gap between two, or one that ended
        while the clock was stopped)."""
        now = [time.perf_counter()]
        clock = watchdog.HostClock(clock=lambda: now[0])
        clock.tick()
        with obs_spans.request("gone-by-then", name="unit"):
            pass
        now[0] += watchdog.TICK_S + 0.05
        clock.tick()
        with obs_spans.request("there", name="unit"):
            now[0] += watchdog.TICK_S + 0.04
            clock.tick()
        assert [(sp.attrs["alive"], sp.attrs["requests"])
                for sp in clock.ring] == [(0, []), (1, ["there"])]
        assert [e["args"]["alive"] for e in clock.events()] == [0, 1]


# -- histogram mechanics -----------------------------------------------------

class TestHistogram:
    def test_bucket_counts_and_cumulative_render(self):
        h = prometheus.Histogram("test_seconds", "test",
                                 buckets=(0.01, 0.1, 1.0))
        for v in (0.003, 0.05, 0.05, 0.5, 7.0):
            h.observe(v)
        counts, total, n = h.snapshot()
        assert counts == [1, 2, 1, 1]  # le=0.01, 0.1, 1.0, +Inf
        assert n == 5 and total == pytest.approx(7.603)
        lines = h.render()
        assert lines[0] == "# HELP test_seconds test"
        assert lines[1] == "# TYPE test_seconds histogram"
        assert 'test_seconds_bucket{le="0.01"} 1' in lines
        assert 'test_seconds_bucket{le="0.1"} 3' in lines  # cumulative
        assert 'test_seconds_bucket{le="1.0"} 4' in lines
        assert 'test_seconds_bucket{le="+Inf"} 5' in lines
        assert "test_seconds_count 5" in lines

    def test_boundary_is_inclusive(self):
        h = prometheus.Histogram("b_seconds", "t", buckets=(0.1, 1.0))
        h.observe(0.1)  # le="0.1" must include exactly 0.1
        counts, _total, _n = h.snapshot()
        assert counts == [1, 0, 0]

    def test_quantile_estimate(self):
        h = prometheus.Histogram("q_seconds", "t", buckets=(0.01, 0.1, 1.0))
        for _ in range(90):
            h.observe(0.005)
        for _ in range(10):
            h.observe(0.5)
        assert h.quantile(0.5) == 0.01
        assert h.quantile(0.99) == 1.0
        assert prometheus.Histogram("e", "t").quantile(0.5) == 0.0

    def test_clear(self):
        h = prometheus.Histogram("c_seconds", "t")
        h.observe(1.0)
        h.clear()
        assert h.snapshot() == ([0] * (len(h.bounds) + 1), 0.0, 0)


# -- prometheus exposition over HTTP -----------------------------------------

SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (NaN|[-+]?[0-9.eE+-]+)$")


class TestInternalEndpoints:
    @pytest.fixture()
    def server(self, engine, monkeypatch):
        from stable_diffusion_webui_distributed_tpu.server.api import (
            ApiServer,
        )

        # tiny-model ladder: the default 512x512 ladder would pad a 32x32
        # request 256x
        monkeypatch.setenv("SDTPU_BUCKET_LADDER", "32x32")
        monkeypatch.setenv("SDTPU_BATCH_LADDER", "1,2")
        srv = ApiServer(engine, state=engine.state,
                        host="127.0.0.1", port=0).start()
        yield srv
        srv.stop()

    @staticmethod
    def _get(server, route):
        url = f"http://127.0.0.1:{server.port}{route}"
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.read().decode(), r.headers.get("Content-Type", "")

    @staticmethod
    def _post(server, route, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}{route}",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    def test_metrics_exposition_parses(self, server):
        out = self._post(server, "/sdapi/v1/txt2img",
                         {"prompt": "metric cow", "steps": 2, "width": 32,
                          "height": 32, "seed": 5})
        assert len(out["images"]) == 1
        body, ctype = self._get(server, "/internal/metrics")
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        names = set()
        for line in body.strip().splitlines():
            if line.startswith("# HELP "):
                names.add(line.split()[2])
                continue
            if line.startswith("# TYPE "):
                parts = line.split()
                assert parts[3] in ("counter", "gauge", "histogram")
                continue
            assert SAMPLE_RE.match(line), f"unparseable sample: {line!r}"
        for want in ("sdtpu_request_e2e_seconds", "sdtpu_queue_wait_seconds",
                     "sdtpu_device_dispatch_seconds", "sdtpu_decode_seconds",
                     "sdtpu_serving_requests_total", "sdtpu_eta_mpe_percent",
                     "sdtpu_stage_seconds"):
            assert want in names, f"missing metric family {want}"
        # the request above landed in the e2e histogram
        assert re.search(
            r"^sdtpu_request_e2e_seconds_count [1-9]\d*$", body, re.M)

    def test_trace_json_served(self, server):
        self._post(server, "/sdapi/v1/txt2img",
                   {"prompt": "trace cow", "steps": 2, "width": 32,
                    "height": 32, "seed": 6, "request_id": "http-rid-1"})
        body, _ctype = self._get(server, "/internal/trace.json")
        doc = json.loads(body)
        assert doc["displayTimeUnit"] == "ms"
        events = doc["traceEvents"]
        for e in events:
            assert_chrome_event(e)
        mine = [e for e in events
                if e["args"]["request_id"] == "http-rid-1"]
        assert any(e["name"] == "txt2img" for e in mine)  # ingress root

    def test_flightrec_route_and_status_summary(self, server):
        body, _ = self._get(server, "/internal/flightrec")
        doc = json.loads(body)
        assert set(doc) == {"entries", "capacity", "count"}
        status, _ = self._get(server, "/internal/status")
        obs = json.loads(status)["obs"]
        assert obs["enabled"] is True
        assert "retained" in obs and "flightrec_entries" in obs


    @staticmethod
    def _host(server):
        status, _ = TestInternalEndpoints._get(server, "/internal/status")
        return json.loads(status)["serving"]["host"]

    def test_status_carries_the_host_block(self, server):
        first = self._host(server)
        assert set(first) == {
            "ticks", "stalls", "stall_ms", "stall_ms_max", "gc_pause_ms",
            "gc_pause_ms_max", "gc_collections", "exchanges", "betweens",
            "between_ms", "between_ms_max"}
        assert set(first["gc_collections"]) == {"0", "1", "2"}
        time.sleep(0.1)
        second = self._host(server)
        assert second["exchanges"] == first["exchanges"] + 1
        assert second["betweens"] == first["betweens"] + 1
        assert 95 <= second["between_ms"] - first["between_ms"] < 2000
        assert second["ticks"] > first["ticks"]
        body, _ = self._get(server, "/internal/metrics")
        assert "# TYPE sdtpu_host_stall_seconds_total counter" in body
        assert "# TYPE sdtpu_gc_pause_seconds_total counter" in body

    def test_a_refused_connection_leaves_nothing(self, server):
        """Stamped on the accept thread, taken up by no handler: the stamp
        goes with the socket, and no exchange began that could not end."""
        httpd = server._httpd
        before = self._host(server)
        httpd.verify_request = lambda request, address: False
        try:
            with socket.create_connection(("127.0.0.1", server.port),
                                          timeout=10) as conn:
                assert conn.recv(1) == b""      # the server closed it
        finally:
            del httpd.verify_request
        after = self._host(server)
        assert not httpd.accepted
        # the second status read alone, and it found the server empty
        assert after["exchanges"] == before["exchanges"] + 1
        assert after["betweens"] == before["betweens"] + 1

    def test_one_clock_however_many_requests(self, server):
        def clocks():
            return [t for t in threading.enumerate()
                    if t.name == "host-clock"]

        seen = []
        posts = [threading.Thread(target=self._post, args=(
            server, "/sdapi/v1/txt2img",
            {"prompt": "cow", "steps": 2, "width": 32, "height": 32,
             "seed": 20 + i})) for i in range(4)]
        for t in posts:
            t.start()
        while any(t.is_alive() for t in posts):
            seen.append(len(clocks()))
            time.sleep(0.005)
        for t in posts:
            t.join(timeout=60)
        assert set(seen) == {1}
        callbacks = list(gc.callbacks)
        server.stop()
        assert not clocks() and len(gc.callbacks) == len(callbacks) - 1

    def test_spans_off_nothing_runs(self, engine, monkeypatch):
        from stable_diffusion_webui_distributed_tpu.server.api import (
            ApiServer,
        )

        monkeypatch.setattr(obs_spans.TRACER, "enabled", False)
        callbacks = list(gc.callbacks)
        threads = {t.name for t in threading.enumerate()}
        srv = ApiServer(engine, state=engine.state,
                        host="127.0.0.1", port=0).start()
        try:
            status, _ = self._get(srv, "/internal/status")
            assert "host" not in json.loads(status)["serving"]
            assert gc.callbacks == callbacks and srv.host_clock is None
            assert srv._httpd.accepted is None
            assert {t.name for t in threading.enumerate()} - threads \
                <= {"sdapi-server"} | {t.name for t in threading.enumerate()
                                       if "process_request" in t.name}
        finally:
            srv.stop()


# -- flight recorder ---------------------------------------------------------

class TestFlightRecorder:
    def test_retention_and_eviction(self):
        rec = flightrec.FlightRecorder(capacity=2)
        for i in range(3):
            rec.record(f"r{i}", "error", f"d{i}", events=[], duration_s=i)
        dump = rec.dump()
        assert dump["capacity"] == 2 and dump["count"] == 2
        assert [e["request_id"] for e in dump["entries"]] == ["r1", "r2"]
        rec.clear()
        assert len(rec) == 0

    def test_dump_to_file_is_trace_report_readable(self, tmp_path):
        rec = flightrec.FlightRecorder(capacity=4)
        rec.record("rf", "slow", "over threshold", duration_s=1.5, events=[
            {"ph": "X", "name": "root", "pid": 1, "tid": 1, "ts": 0,
             "dur": 1.5e6, "args": {"request_id": "rf", "span_id": 1}}])
        path = rec.dump_to_file(str(tmp_path / "rec.json"))
        doc = json.loads(open(path).read())
        assert doc["entries"][0]["reason"] == "slow"
        import sys
        sys.path.insert(0, "tools")
        import trace_report
        assert len(trace_report.load_events(doc)) == 1

    def test_failed_request_correlates_logs(self):
        flightrec.RECORDER.clear()
        logger = get_logger()
        rid = "rid-logged-failure"
        with pytest.raises(RuntimeError):
            with obs_spans.request(rid, name="unit"):
                logger.info("marker line for %s", rid)
                raise RuntimeError("dies after logging")
        entry = flightrec.RECORDER.dump()["entries"][-1]
        assert entry["request_id"] == rid and entry["reason"] == "error"
        assert any(rid in line for line in entry["logs"])
        assert entry["spans"][0]["args"]["request_id"] == rid
        assert lines_for_request(rid) == entry["logs"]

    def test_no_request_no_log_correlation(self):
        get_logger().info("uncorrelated line")
        assert lines_for_request("") == []


# -- ETA calibration gauge ---------------------------------------------------

class TestEtaGauge:
    def test_record_eta_error_feeds_gauge(self):
        from stable_diffusion_webui_distributed_tpu.scheduler.eta import (
            EtaCalibration, record_eta_error,
        )

        prometheus.ETA_GAUGE.clear()
        cal = EtaCalibration(avg_ipm=6.0)
        record_eta_error(cal, predicted=10.0, actual=8.0)
        s = prometheus.ETA_GAUGE.summary()
        assert s["samples"] == 1
        assert s["mpe_percent"] == pytest.approx(25.0)
        assert s["last_predicted_s"] == 10.0 and s["last_actual_s"] == 8.0
        assert cal.eta_percent_error == [pytest.approx(25.0)]
        # the gauge value reaches the exposition
        assert "sdtpu_eta_mpe_percent 25" in prometheus.render()

    def test_outlier_rejected_like_the_paper_window(self):
        from stable_diffusion_webui_distributed_tpu.scheduler.eta import (
            EtaCalibration, record_eta_error,
        )

        prometheus.ETA_GAUGE.clear()
        cal = EtaCalibration(avg_ipm=6.0)
        record_eta_error(cal, predicted=100.0, actual=1.0)  # +9900%
        assert prometheus.ETA_GAUGE.summary()["samples"] == 0
        assert cal.eta_percent_error == []
        prometheus.ETA_GAUGE.record(0.0, 5.0)  # non-positive: ignored
        assert prometheus.ETA_GAUGE.summary()["samples"] == 0

    def test_window_matches_scheduler_constant(self):
        from stable_diffusion_webui_distributed_tpu.scheduler.eta import (
            MPE_WINDOW,
        )

        prometheus.ETA_GAUGE.clear()
        for i in range(MPE_WINDOW + 3):
            prometheus.ETA_GAUGE.record(10.0 + i, 10.0)
        s = prometheus.ETA_GAUGE.summary()
        assert s["samples"] == MPE_WINDOW + 3  # total accepted
        # but the MPE itself averages only the window's most-recent errors:
        # sample i has error (10+i-10)/10*100 = 10*i percent
        want = sum(10.0 * i
                   for i in range(3, MPE_WINDOW + 3)) / MPE_WINDOW
        assert s["mpe_percent"] == pytest.approx(want)


# -- overhead ----------------------------------------------------------------

class TestOverhead:
    def test_span_recording_is_cheap(self):
        n = 2000
        with obs_spans.request("rid-overhead", name="unit"):
            t0 = time.perf_counter()
            for _ in range(n):
                with obs_spans.span("tick"):
                    pass
            cost = time.perf_counter() - t0
        # ~5-20 µs/span typical; 1 ms/span is already catastrophic.
        # Generous CI bound: the point is "negligible", not a benchmark.
        assert cost / n < 1e-3, f"{cost / n * 1e6:.1f} µs per span"
        obs_spans.TRACER.clear()

    def test_noop_span_outside_request_is_cheaper(self):
        n = 5000
        t0 = time.perf_counter()
        for _ in range(n):
            with obs_spans.span("tick"):
                pass
        cost = time.perf_counter() - t0
        assert cost / n < 5e-4, f"{cost / n * 1e6:.1f} µs per no-op span"

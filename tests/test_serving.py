"""Serving layer: shape bucketer, dispatch metrics, continuous batching.

The acceptance scenario from the serving design: 8 concurrent requests
across 4 raw shapes must land on 2 bucket executables (<= 2 chunk
compiles), merge into coalesced device batches, and return seeds /
infotext / image bytes identical to serial execution of the same
payloads.  All assertions are host-side counts — no wall-clock.
"""

import threading
import time

import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.models.configs import TINY
from stable_diffusion_webui_distributed_tpu.obs import (
    prometheus as obs_prom, spans as obs_spans,
)
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload, b64png_to_array,
)
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.scheduler.eta import (
    EtaCalibration, predict_eta,
)
from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
    DEFAULT_BATCH_LADDER, DEFAULT_SHAPE_LADDER, ShapeBucketer,
)
from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
    ServingDispatcher,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    METRICS, DispatchMetrics,
)
from test_pipeline import init_params


def payload(**kw):
    defaults = dict(prompt="a cow", steps=4, width=32, height=32,
                    seed=7, sampler_name="Euler a")
    defaults.update(kw)
    return GenerationPayload(**defaults)


class TestBucketer:
    def test_smallest_fitting_bucket(self):
        b = ShapeBucketer(shapes=DEFAULT_SHAPE_LADDER,
                          batches=DEFAULT_BATCH_LADDER)
        assert b.bucket_shape(500, 500) == (512, 512)
        assert b.bucket_shape(512, 512) == (512, 512)
        assert b.bucket_shape(513, 512) == (640, 640)
        assert b.bucket_shape(1025, 64) is None  # nothing fits -> raw

    def test_batch_ladder(self):
        b = ShapeBucketer(shapes=[(64, 64)], batches=[1, 2, 4, 8])
        assert b.bucket_batch(1) == 1
        assert b.bucket_batch(3) == 4
        assert b.bucket_batch(8) == 8
        assert b.bucket_batch(9) == 9  # ladder tops out: run raw

    def test_padding_ratio(self):
        b = ShapeBucketer(shapes=[(512, 512)], batches=[1])
        assert b.padding_ratio(512, 512) == pytest.approx(1.0)
        assert b.padding_ratio(256, 256) == pytest.approx(4.0)
        assert b.padding_ratio(4096, 4096) == pytest.approx(1.0)  # no fit

    def test_payload_pad_and_crop_round_trip(self):
        b = ShapeBucketer(shapes=[(32, 32)], batches=[4])
        p = payload(width=24, height=20)
        run, bucketed = b.bucket_payload(p)
        assert bucketed and (run.width, run.height) == (32, 32)
        assert run.group_size == 4
        assert (p.width, p.height) == (24, 20)  # original untouched
        img = np.arange(32 * 32 * 3, dtype=np.uint8).reshape(32, 32, 3)
        back = ShapeBucketer.crop(img, p.width, p.height)
        assert back.shape == (20, 24, 3)
        # center crop: offsets (32-20)//2 = 6 rows, (32-24)//2 = 4 cols
        np.testing.assert_array_equal(back, img[6:26, 4:28])
        assert ShapeBucketer.crop(img, 32, 32) is img  # exact hit: no-op

    def test_exact_hit_not_bucketed(self):
        b = ShapeBucketer(shapes=[(32, 32)], batches=[1])
        run, bucketed = b.bucket_payload(payload(width=32, height=32))
        assert not bucketed and (run.width, run.height) == (32, 32)

    def test_env_ladder_parse(self, monkeypatch):
        monkeypatch.setenv("SDTPU_BUCKET_LADDER", "64x64, 128x96")
        monkeypatch.setenv("SDTPU_BATCH_LADDER", "2, 4")
        b = ShapeBucketer()
        assert b.shapes == [(64, 64), (128, 96)]
        assert b.batches == [2, 4]

    def test_env_ladder_warn_and_default(self, monkeypatch):
        monkeypatch.setenv("SDTPU_BUCKET_LADDER", "not-a-ladder")
        monkeypatch.setenv("SDTPU_BATCH_LADDER", "4,-1")
        with pytest.warns(UserWarning, match="SDTPU_BUCKET_LADDER"):
            b = ShapeBucketer()
        assert set(b.shapes) == set(DEFAULT_SHAPE_LADDER)
        assert set(b.batches) == set(DEFAULT_BATCH_LADDER)

    def test_from_config(self, monkeypatch):
        monkeypatch.delenv("SDTPU_BUCKET_LADDER", raising=False)
        monkeypatch.delenv("SDTPU_BATCH_LADDER", raising=False)

        class Cfg:
            bucket_ladder = "96x96"
            batch_ladder = "1,2"

        b = ShapeBucketer.from_config(Cfg())
        assert b.shapes == [(96, 96)] and b.batches == [1, 2]
        # env wins over config fields
        monkeypatch.setenv("SDTPU_BUCKET_LADDER", "48x48")
        assert ShapeBucketer.from_config(Cfg()).shapes == [(48, 48)]


class TestMetrics:
    def test_counters_and_summary(self):
        m = DispatchMetrics()
        m.record_compile("chunk")
        m.record_compile("chunk")
        m.record_cache_hit("chunk")
        m.record_request(bucketed=True, padding_ratio=2.0)
        m.record_request(bucketed=False, padding_ratio=1.0)
        m.record_request(bucketed=False, bypassed=True)
        m.record_dispatch(4)
        m.record_dispatch(1)
        m.record_queue_wait(0.2)
        m.record_queue_wait(0.4)
        s = m.summary()
        assert m.compile_count("chunk") == 2
        assert s["cache_hits"] == {"chunk": 1}
        assert s["requests"] == 3 and s["bucket_bypasses"] == 1
        assert s["bucket_hit_rate"] == pytest.approx(0.5)
        assert s["dispatches"] == 2 and s["coalesced_dispatches"] == 1
        assert m.coalesce_factor() == pytest.approx(2.5)
        assert m.avg_queue_wait() == pytest.approx(0.3)
        assert m.avg_padding_ratio() == pytest.approx(1.5)
        m.clear()
        assert m.summary()["requests"] == 0
        assert m.coalesce_factor() == 0.0


    def test_decode_block_counts_kept_rows_a_dispatch(self):
        m = DispatchMetrics()
        assert m.summary()["decode"] == {"dispatches": 0, "rows": 0}
        m.record_decoded(rows=4, dispatches=4)
        m.record_decoded(rows=1, dispatches=1)
        assert m.summary()["decode"] == {"dispatches": 5, "rows": 5}
        assert m.summary()["unet_images"] == 5
        m.clear()
        assert m.summary()["decode"] == {"dispatches": 0, "rows": 0}


class TestEtaOverheads:
    def test_padding_scales_and_wait_adds(self):
        cal = EtaCalibration(avg_ipm=6.0)
        p = payload(batch_size=2, steps=20, width=512, height=512)
        base = predict_eta(cal, p)  # 20 s at the benchmark point
        assert predict_eta(cal, p, padding_overhead=2.0) == \
            pytest.approx(2.0 * base)
        assert predict_eta(cal, p, queue_wait=5.0) == \
            pytest.approx(base + 5.0)
        # wait is latency, not compute: a sub-1 padding factor never
        # shrinks the estimate and negative wait never subtracts
        assert predict_eta(cal, p, padding_overhead=0.5,
                           queue_wait=-3.0) == pytest.approx(base)

    def test_dispatcher_eta_overhead(self):
        METRICS.clear()
        disp = ServingDispatcher(
            None, bucketer=ShapeBucketer(shapes=[(64, 64)], batches=[1]),
            window=0.2)
        over = disp.eta_overhead(payload(width=32, height=32))
        assert over["padding_overhead"] == pytest.approx(4.0)
        # no observed waits yet: floor at half the coalesce window
        assert over["queue_wait"] == pytest.approx(0.1)


@pytest.fixture(scope="module")
def engine():
    return Engine(TINY, init_params(TINY), chunk_size=4,
                  state=GenerationState())


@pytest.fixture(scope="module")
def bucketer():
    # batches=[4]: every group partition pads to the same compiled batch,
    # so the compile count is deterministic under thread scheduling
    return ShapeBucketer(shapes=[(32, 32), (48, 48)], batches=[4])


class TestContinuousBatching:
    # 8 requests over 4 raw shapes that map onto 2 buckets; prompts vary
    # per shape so merged conditioning really is per-request
    SHAPES = [(32, 32), (24, 32), (48, 48), (40, 40)]

    def _payloads(self):
        out = []
        for i, (w, h) in enumerate(self.SHAPES):
            for k in range(2):
                out.append(payload(width=w, height=h, seed=100 + i * 10 + k,
                                   prompt=f"cow {i}"))
        return out

    @staticmethod
    def _submit_at_once(dispatcher, payloads):
        """Every payload from a thread of its own; the results in order."""
        results = [None] * len(payloads)
        errors = []

        def run(i, p):
            try:
                results[i] = dispatcher.submit(p)
            except Exception as e:  # noqa: BLE001 — surfaced by assert
                errors.append(e)

        threads = [threading.Thread(target=run, args=(i, p))
                   for i, p in enumerate(payloads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        return results

    def test_acceptance_coalesce_and_byte_exactness(self, engine, bucketer):
        serial = ServingDispatcher(engine, bucketer=bucketer, window=0.0)
        coalesced = ServingDispatcher(engine, bucketer=bucketer, window=0.6)

        METRICS.clear()
        baseline = [serial.submit(p) for p in self._payloads()]
        assert METRICS.compile_count("chunk") <= 2  # one per shape bucket
        assert METRICS.summary()["dispatches"] == 8

        METRICS.clear()
        results = self._submit_at_once(coalesced, self._payloads())

        s = METRICS.summary()
        # the whole point: 4 raw shapes -> 2 executables, and the serial
        # phase already built both, so the concurrent phase compiles NOTHING
        assert s["compiles"].get("chunk", 0) == 0
        assert s["coalesced_dispatches"] >= 1
        assert s["coalesce_factor"] >= 2.0
        assert s["requests"] == 8 and s["bucket_bypasses"] == 0

        for got, want in zip(results, baseline):
            assert got.seeds == want.seeds
            assert got.infotexts == want.infotexts
            assert got.images == want.images  # pixel bytes, not just shape

    def test_groups_of_two_match_solo(self, engine):
        """Four requests of two prompts on a ladder of batch 2: several
        coalesced groups, each ticket with the bytes of its solo run."""
        bucketer = ShapeBucketer(shapes=[(32, 32)], batches=[2])
        payloads = [payload(prompt=f"stage cow {i % 2}", seed=200 + i)
                    for i in range(4)]
        serial = ServingDispatcher(engine, bucketer=bucketer, window=0.0)
        baseline = [serial.submit(p) for p in payloads]
        coalesced = ServingDispatcher(engine, bucketer=bucketer, window=0.6)
        results = self._submit_at_once(coalesced, payloads)
        for got, want in zip(results, baseline):
            assert got.seeds == want.seeds
            assert got.infotexts == want.infotexts
            assert got.images == want.images

    def test_infotext_reports_requested_size(self, engine, bucketer):
        disp = ServingDispatcher(engine, bucketer=bucketer, window=0.0)
        r = disp.submit(payload(width=24, height=32, seed=5))
        assert len(r.images) == 1
        assert b64png_to_array(r.images[0]).shape == (32, 24, 3)
        assert "Size: 24x32" in r.infotexts[0]
        assert r.seeds == [5]

    def test_cancel_drops_only_one_requester(self, engine, bucketer):
        disp = ServingDispatcher(engine, bucketer=bucketer, window=0.6)
        solo = ServingDispatcher(engine, bucketer=bucketer, window=0.0)
        keep = payload(width=32, height=32, seed=11,
                       request_id="req-keep")
        drop = payload(width=32, height=32, seed=12,
                       request_id="req-drop")
        results = {}

        def run(name, p):
            results[name] = disp.submit(p)

        threads = [threading.Thread(target=run, args=("keep", keep)),
                   threading.Thread(target=run, args=("drop", drop))]
        for t in threads:
            t.start()
        time.sleep(0.15)  # inside the coalesce window
        assert disp.cancel("req-drop")
        assert not disp.cancel("no-such-request")
        for t in threads:
            t.join()

        cancelled = results["drop"]
        assert cancelled.images == []
        assert cancelled.parameters.get("cancelled") is True
        # the co-batched survivor is byte-identical to running alone
        alone = solo.submit(payload(width=32, height=32, seed=11))
        assert results["keep"].seeds == alone.seeds
        assert results["keep"].images == alone.images
        assert results["keep"].infotexts == alone.infotexts

    def test_group_cancelled_whole_makes_no_device_call(self, engine,
                                                        bucketer,
                                                        monkeypatch):
        """Every ticket of a group cancelled inside the window: the leader
        still takes the device and finishes the group (each ``done`` set,
        each requester answered empty), and nothing is encoded, denoised
        or decoded."""
        disp = ServingDispatcher(engine, bucketer=bucketer, window=0.6)
        calls = []
        for name in ("encode_prompts", "_denoise_range", "_queue_decoded"):
            monkeypatch.setattr(
                engine, name,
                lambda *a, _name=name, **kw: calls.append(_name))
        results = {}

        def run(rid):
            results[rid] = disp.submit(
                payload(width=32, height=32, seed=13, request_id=rid))

        threads = [threading.Thread(target=run, args=(rid,))
                   for rid in ("req-a", "req-b")]
        before = METRICS.summary()["dispatches"]
        for t in threads:
            t.start()
        time.sleep(0.15)  # inside the coalesce window
        assert disp.cancel("req-a") and disp.cancel("req-b")
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert calls == []
        assert METRICS.summary()["dispatches"] == before
        for r in results.values():
            assert r.images == []
            assert r.parameters.get("cancelled") is True

    def test_solo_bucketed_run_restored(self, engine):
        # batch above the ladder top -> not coalescable -> solo path,
        # still shape-bucketed and cropped + infotext-rebuilt afterwards
        disp = ServingDispatcher(
            engine, bucketer=ShapeBucketer(shapes=[(32, 32)], batches=[1]),
            window=0.0)
        r = disp.submit(payload(width=24, height=32, seed=21, batch_size=2))
        assert len(r.images) == 2
        for b64 in r.images:
            assert b64png_to_array(b64).shape == (32, 24, 3)
        assert all("Size: 24x32" in t for t in r.infotexts)
        assert r.seeds == [21, 22]

    def test_warmup_prebuilds_ladder(self, engine, tmp_path, monkeypatch):
        from stable_diffusion_webui_distributed_tpu.runtime import mesh
        from stable_diffusion_webui_distributed_tpu.serving.warmup import (
            warmup_engine,
        )

        # the sweep places the compile cache, and the programs it keeps go
        # beside it: in the test's own directory, not the checkout's
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(mesh, "DEFAULT_COMPILE_CACHE", str(tmp_path))
        b = ShapeBucketer(shapes=[(32, 32)], batches=[1])
        report = warmup_engine(engine, b, steps=4, sampler="Euler a")
        assert report["skipped"] is False
        assert report["buckets"] == [(32, 32, 1)]
        assert report["steps"] == 4 and report["sampler"] == "Euler a"
        assert isinstance(report["stage_builds"], dict)
        assert report["programs"]["dir"].startswith(str(tmp_path))
        # a second sweep over the same ladder builds nothing new
        again = warmup_engine(engine, b, steps=4, sampler="Euler a")
        assert again["stage_builds"] == {}

    def test_warmup_env_disable(self, monkeypatch):
        from stable_diffusion_webui_distributed_tpu.serving.warmup import (
            warmup_engine,
        )

        monkeypatch.setenv("SDTPU_WARMUP", "0")
        report = warmup_engine(None)  # engine untouched when disabled
        assert report["skipped"] is True


class TestFullGroupEndsTheWindow:
    """The leader's wait ends the moment its group holds ``max_batch``
    images (no joiner fits any more) and lasts the whole window otherwise.
    Window 0.6 s, so a wait the timer ended cannot pass for one the group
    ended."""

    WINDOW = 0.6
    #: case -> batch ladder; the arrivals as (seconds after the one before,
    #: images); what ends the first leader's window; the requests each
    #: dispatch carries, in order; the arrival cancelled while the device
    #: is held against its (full) group, if any; the ladder under which
    #: the same arrivals share a group that is NOT full, if compared
    CASES = {
        "ladder1_one_request": dict(
            ladder=[1], arrivals=[(0.0, 1)], ended_by="full",
            dispatches=[1]),
        "ladder2_follower_fills_it": dict(
            ladder=[2], arrivals=[(0.0, 1), (0.1, 1)], ended_by="full",
            dispatches=[2], not_full_ladder=[4]),
        "ladder4_one_request": dict(
            ladder=[4], arrivals=[(0.0, 1)], ended_by="timer",
            dispatches=[1]),
        "ladder2_two_images_full_on_arrival": dict(
            ladder=[2], arrivals=[(0.0, 2)], ended_by="full",
            dispatches=[1]),
        "ladder2_cancelled_follower_keeps_it_full": dict(
            ladder=[2], arrivals=[(0.0, 1), (0.1, 1), (0.0, 1)],
            ended_by="full", dispatches=[2, 1], cancel=1),
    }

    @staticmethod
    def _serve(engine, ladder, arrivals, tag, window, cancel=None):
        """The arrivals through one dispatcher, each from a thread of its
        own; with ``cancel``, the device is held until every arrival has
        queued, and that one is cancelled once it has joined its group,
        before the next arrives."""
        disp = ServingDispatcher(
            engine, bucketer=ShapeBucketer(shapes=[(32, 32)],
                                           batches=ladder), window=window)
        payloads = [payload(prompt=f"full cow {i}", seed=300 + 10 * i,
                            batch_size=n, request_id=f"{tag}-{i}")
                    for i, (_delay, n) in enumerate(arrivals)]
        results = [None] * len(payloads)

        def run(i):
            results[i] = disp.submit(payloads[i])

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(payloads))]
        if cancel is not None:
            disp._exec_lock.acquire()
        try:
            for i, (delay, _n) in enumerate(arrivals):
                time.sleep(delay)
                threads[i].start()
                if i == cancel:
                    time.sleep(0.1)     # it has joined its group
                    assert disp.cancel(payloads[i].request_id)
        finally:
            if cancel is not None:
                disp._exec_lock.release()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        traces = {tr.request_id: tr for tr in obs_spans.TRACER.finished()}
        spans = [{sp.name: sp for sp in traces[p.request_id].spans}
                 for p in payloads]
        return results, spans

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_window_ends_when_the_group_is_full(self, engine, case):
        spec = self.CASES[case]
        counted = {k: obs_prom.COALESCE_WINDOW_COUNTER.value(ended_by=k)
                   for k in ("full", "timer")}
        results, spans = self._serve(
            engine, spec["ladder"], spec["arrivals"], case, self.WINDOW,
            cancel=spec.get("cancel"))

        # one dispatch a leader, each with the requests of its group
        leaders = [by for by in spans if "dispatch.device" in by]
        assert [by["dispatch.device"].attrs["requests"]
                for by in leaders] == spec["dispatches"]
        # every leader has a window span, however short, and is counted
        window = spans[0]["coalesce.window"]
        assert window.attrs == {"window_s": self.WINDOW,
                                "ended_by": spec["ended_by"]}
        ends = [by["coalesce.window"].attrs["ended_by"] for by in leaders]
        for k in counted:
            assert obs_prom.COALESCE_WINDOW_COUNTER.value(ended_by=k) \
                == counted[k] + ends.count(k)

        if spec["ended_by"] == "timer":
            assert window.dur >= self.WINDOW
        else:
            # within 0.1 s of the arrival that filled the group: the
            # leader's own, or its last follower's join
            filled = max([window.t0] + [by["coalesced.wait"].t0
                                        for by in spans
                                        if "coalesced.wait" in by])
            assert abs(window.t0 + window.dur - filled) < 0.1

        if spec.get("cancel") is not None:
            # the cancelled follower kept its rows: the group stayed full
            # and the later arrival led a group of its own
            dropped = results[spec["cancel"]]
            assert dropped.images == []
            assert dropped.parameters.get("cancelled") is True
            assert "dispatch.device" in spans[-1]
            assert spans[-1]["coalesce.window"].attrs["ended_by"] == "timer"
            assert len(results[0].images) == len(results[-1].images) == 1

        if spec.get("not_full_ladder"):
            # the same requests in a group the timer ends: same answers
            want, by = self._serve(
                engine, spec["not_full_ladder"], spec["arrivals"],
                f"{case}-open", self.WINDOW)
            assert by[0]["coalesce.window"].attrs["ended_by"] == "timer"
            assert by[0]["dispatch.device"].attrs["requests"] \
                == len(spec["arrivals"])
            for got, ref in zip(results, want):
                assert got.seeds == ref.seeds
                assert got.infotexts == ref.infotexts
                assert got.images == ref.images


class TestDecodeDispatch:
    def test_engine_batch_goes_one_image_a_dispatch(self, engine):
        """The engine's own path (``_flush_decoded``): a batch of four is
        four decode dispatches of one image through ONE executable key,
        with the images, seeds and order of each image generated alone
        (tests/test_pipeline.py, a slow module, holds the same batch
        against the one-dispatch decode)."""
        p = payload(batch_size=4, seed=77)
        before, had = METRICS.summary()["decode"], set(engine._cache)
        got = engine.txt2img(p)
        after = METRICS.summary()["decode"]
        assert {k: after[k] - before[k] for k in after} == {
            "dispatches": 4, "rows": 4}
        assert all(k[3] == 1 for k in set(engine._cache) - had
                   if k[0] == "decode-u8")
        assert ("decode-u8", 32, 32, 1, TINY.name) in engine._cache
        alone = [engine.generate_range(p, i, 1) for i in range(4)]
        assert got.seeds == [77, 78, 79, 80]
        assert got.images == [r.images[0] for r in alone]
        assert got.infotexts == [r.infotexts[0] for r in alone]


class TestMergeStage:
    """The dispatcher's merge stage walks the decode dispatches one image
    at a time: image i is copied down, cropped and encoded into the
    ticket that owns it before image i+1 is awaited
    (dispatcher._group_merge)."""

    @staticmethod
    def _pair(engine, bucketer, tag, cancel=False):
        from stable_diffusion_webui_distributed_tpu.obs import (
            journal as obs_journal, spans as obs_spans,
        )

        disp = ServingDispatcher(engine, bucketer=bucketer, window=0.6)
        lead = payload(seed=21, batch_size=2, request_id=f"{tag}-lead")
        follow = payload(width=24, height=32, seed=31, batch_size=2,
                         prompt="a crop", request_id=f"{tag}-follow")
        results = {}
        if cancel:
            # once the batch is on the device: its rows are all decoded
            decode = disp._group_decode

            def cancel_then_decode(*args):
                assert disp.cancel(follow.request_id)
                return decode(*args)

            disp._group_decode = cancel_then_decode

        def run(name, p):
            results[name] = disp.submit(p)

        threads = [threading.Thread(target=run, args=("lead", lead)),
                   threading.Thread(target=run, args=("follow", follow))]
        before = METRICS.summary()["decode"]
        threads[0].start()
        time.sleep(0.1)      # the first to arrive leads
        threads[1].start()
        for t in threads:
            t.join()
        after = METRICS.summary()["decode"]
        traces = {tr.request_id: tr for tr in obs_spans.TRACER.finished()}
        spans = sorted(traces[lead.request_id].spans, key=lambda sp: sp.t0)
        return {
            "results": results, "lead": lead, "follow": follow,
            "spans": spans,
            "decode": {k: after[k] - before[k] for k in after},
            "journal": {
                name: [e["event"] for e in obs_journal.JOURNAL.events_for(
                    p.request_id)]
                for name, p in (("lead", lead), ("follow", follow))},
        }

    @pytest.fixture(scope="class")
    def pair(self, engine, bucketer):
        from stable_diffusion_webui_distributed_tpu.obs import (
            journal as obs_journal,
        )

        mp = pytest.MonkeyPatch()
        mp.setenv("SDTPU_JOURNAL", "1")
        obs_journal.JOURNAL.clear()
        try:
            yield self._pair(engine, bucketer, "merge")
        finally:
            obs_journal.JOURNAL.clear()
            mp.undo()

    def test_coalesced_as_one_dispatch(self, pair):
        device = [sp for sp in pair["spans"] if sp.name == "dispatch.device"]
        assert [sp.attrs["requests"] for sp in device] == [2]

    def test_image_i_is_encoded_before_image_i_plus_1_is_awaited(self, pair):
        tail = [sp for sp in pair["spans"]
                if sp.name in ("decode.wait", "png_encode")]
        assert [sp.name for sp in tail] == ["decode.wait", "png_encode"] * 4
        assert all(sp.attrs["rows"] == 1 for sp in tail
                   if sp.name == "decode.wait")
        assert not [sp for sp in pair["spans"] if sp.name == "fetch.join"]

    def test_decode_counters_grow_by_rows_and_dispatches(self, pair):
        assert pair["decode"] == {"dispatches": 4, "rows": 4}

    @pytest.mark.parametrize("who", ["lead", "follow"])
    def test_results_match_the_request_served_alone(self, pair, engine,
                                                    bucketer, who):
        solo = ServingDispatcher(engine, bucketer=bucketer, window=0.0)
        alone = solo.submit(pair[who].model_copy(
            update={"request_id": None}))
        got = pair["results"][who]
        assert got.seeds == alone.seeds == [pair[who].seed + i
                                            for i in range(2)]
        assert got.infotexts == alone.infotexts
        assert got.images == alone.images

    def test_journal_order(self, pair):
        lead, follow = pair["journal"]["lead"], pair["journal"]["follow"]
        assert lead[-3:] == ["decoded", "merged", "completed"]
        assert follow[-2:] == ["merged", "completed"]
        assert "decoded" not in follow

    def test_cancelled_ticket_is_fetched_and_dropped(self, engine, bucketer):
        got = self._pair(engine, bucketer, "merge-cancel", cancel=True)
        dropped = got["results"]["follow"]
        assert dropped.images == []
        assert dropped.parameters.get("cancelled") is True
        assert got["decode"] == {"dispatches": 4, "rows": 4}
        names = [sp.name for sp in got["spans"]
                 if sp.name in ("decode.wait", "png_encode")]
        assert names == ["decode.wait", "png_encode"] * 2 \
            + ["decode.wait"] * 2
        kept = got["results"]["lead"]
        assert kept.seeds == [21, 22] and len(kept.images) == 2


class TestPrecisionDispatch:
    """Per-request serving precision (pipeline/precision.py) as a dispatch
    group-key axis: mixed bf16/int8 traffic on ONE shape bucket must hold
    the compile budget (one chunk executable per precision actually used —
    never coalesce across precisions, never an unbounded key)."""

    # the (48, 48) bucket at batch 2 is disjoint from every other class's
    # chunk keys on the shared module engine, so compile counts are exact
    # (steps stay at 4: one chunk-scan length, one executable per precision)
    def _bucketer(self):
        return ShapeBucketer(shapes=[(48, 48)], batches=[2])

    def test_mixed_precision_compile_budget(self, engine):
        disp = ServingDispatcher(engine, bucketer=self._bucketer(),
                                 window=0.0)

        METRICS.clear()
        bf16 = [disp.submit(payload(seed=31)),
                disp.submit(payload(seed=32))]
        # one bucket, one precision -> exactly one chunk executable
        assert METRICS.compile_count("chunk") == 1

        int8 = [disp.submit(payload(
                    seed=31, override_settings={"precision": "int8"})),
                disp.submit(payload(seed=32, precision="int8"))]
        s = METRICS.summary()
        # the int8 rung adds exactly ONE more executable for the same
        # bucket (<= 3 precisions x <= 2 step-cache variants per bucket),
        # shared by both the override_settings and the field spelling
        assert s["compiles"].get("chunk", 0) == 2
        assert s["precision"]["bf16"]["requests"] == 2
        assert s["precision"]["int8"]["requests"] == 2

        # engagement: the quantized executable really ran (same seeds,
        # different pixels); the two int8 spellings agree byte-for-byte
        assert int8[0].images != bf16[0].images
        assert int8[0].seeds == bf16[0].seeds
        assert int8[1].images != bf16[1].images

    def test_unknown_precision_buckets_to_default(self, engine):
        # off-ladder names never mint a fourth executable: they resolve to
        # the policy default and ride the existing bf16 group
        disp = ServingDispatcher(engine, bucketer=self._bucketer(),
                                 window=0.0)
        base = disp.submit(payload(seed=33))
        METRICS.clear()
        odd = disp.submit(payload(
            seed=33, override_settings={"precision": "fp4-turbo"}))
        assert METRICS.compile_count("chunk") == 0
        assert odd.images == base.images

"""A request of several dispatch groups through the one executor.

``Engine._run_txt2img`` and ``_run_img2img`` run a request group by group
(``n_iter`` groups of ``batch_size``) with a depth-1 decode pipeline:
``_queue_decoded`` dispatches a group's images to the decoder one by one,
``_flush_decoded`` fetches and encodes every image but the newest, and the
newest stays in flight under the next group's denoise. These tests hold that
loop to the request's groups run one by one at their seeds: the golden pin,
ControlNet units, an interrupt and a preemption between two groups, the
order of decodes and fetches, and the padded rows of a remainder group.

``TestExpandedRequestsOfOneGroup`` holds the OTHER kind of group, the
dispatcher's, to the same rule where its requests are expanded ones
(serving/dispatcher.py, pipeline/expand.py:expand_group): two clients'
requests behind one instruction share one decode scan and one UNet
dispatch, and each gets what it gets alone.
"""

import threading
import time

import pytest

from stable_diffusion_webui_distributed_tpu.models import configs

from stable_diffusion_webui_distributed_tpu.models.configs import TINY
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
)
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
    ShapeBucketer,
)
from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
    EXPANSION_AT, ServingDispatcher,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    EXPANDER, METRICS,
)
from tests import expander_contract as contract
from test_fleet import OneShotHook
from test_goldens import _check, _controlnet_params, _hint_b64
from test_pipeline import init_params


@pytest.fixture(scope="module")
def engine():
    return Engine(TINY, init_params(TINY), chunk_size=4,
                  state=GenerationState(),
                  controlnet_provider=lambda name: _controlnet_params())


class TestGroupsOfARequest:
    """A request of several dispatch groups (``n_iter``) against its
    groups run one by one at their seeds: the group loop, its decode
    pipeline, and an interrupt or a preemption between two groups."""

    @staticmethod
    def _payload(job="txt2img", **kw):
        fields = dict(prompt="a stage cow", steps=4, width=32, height=32,
                      seed=7, sampler_name="Euler a")
        if job == "img2img":
            fields.update(init_images=[_hint_b64()], denoising_strength=0.5)
        fields.update(kw)
        return GenerationPayload(**fields)

    @staticmethod
    def _run(engine, p):
        return engine.img2img(p) if p.init_images else engine.txt2img(p)

    def test_two_groups_golden_pin(self, engine):
        """The hash-pinned bytes of a two-group request."""
        p = self._payload(prompt="stage graph pin", seed=77, n_iter=2)
        _check("path/txt2img-two-groups", engine.txt2img(p))

    def test_three_groups_equal_the_groups_at_their_seeds(self, engine):
        p = self._payload(seed=81, n_iter=3)
        whole = engine.txt2img(p)
        groups = [engine.generate_range(p, i, 1) for i in range(3)]
        assert whole.images == [g.images[0] for g in groups]
        assert whole.seeds == [81, 82, 83]
        assert whole.infotexts == [g.infotexts[0] for g in groups]

    @pytest.mark.parametrize("sampler", ["Heun", "Euler a"])
    def test_controlnet_units_over_two_groups(self, engine, sampler):
        """A full-window unit and a windowed one (steps 6, chunks of 4:
        live in the first chunk, dropped from the second) under a sampler
        of two UNet evaluations a step and under one of one."""
        units = [
            {"enabled": True, "image": _hint_b64(), "module": "canny",
             "model": "gold-cn", "weight": 1.0},
            {"enabled": True, "image": _hint_b64(), "module": "none",
             "model": "gold-cn", "weight": 0.7,
             "guidance_start": 0.0, "guidance_end": 0.3},
        ]
        p = self._payload(
            prompt="staged control", steps=6, seed=46, n_iter=2,
            sampler_name=sampler,
            alwayson_scripts={"controlnet": {"args": units}})
        whole = engine.txt2img(p)
        groups = [engine.generate_range(p, i, 1) for i in range(2)]
        assert whole.images == [g.images[0] for g in groups]
        plain = engine.txt2img(p.model_copy(update={"alwayson_scripts": {}}))
        assert whole.images != plain.images

    @pytest.mark.parametrize("job", ["txt2img", "img2img"])
    def test_preemption_between_groups_resumes_to_the_same_bytes(
            self, engine, job):
        """The device is yielded at the second group's first chunk, with
        the first group's decode in flight; the interloper's images and
        the resumed request's are those of undisturbed runs."""
        batch_p = self._payload(job, seed=70, n_iter=3)
        inter_p = self._payload(seed=71)
        baseline = self._run(engine, batch_p)
        inter_base = engine.txt2img(inter_p)
        hook = OneShotHook(engine, inter_p)
        engine.preempt_hook = hook
        try:
            resumed = self._run(engine, batch_p)
        finally:
            engine.preempt_hook = None
        assert hook.fired == 1
        assert resumed.images == baseline.images
        assert resumed.seeds == baseline.seeds
        assert hook.result.images == inter_base.images

    @pytest.mark.parametrize("after_flush", [1, 2])
    @pytest.mark.parametrize("job", ["txt2img", "img2img"])
    def test_interrupt_leaves_a_byte_exact_prefix(self, engine, monkeypatch,
                                                  job, after_flush):
        """The latch rises as the k-th flush returns: no further group
        starts, the decode in flight is still fetched, and the gallery is
        the first k + 1 images in global index order."""
        p = self._payload(job, seed=90, n_iter=4)
        baseline = self._run(engine, p)
        assert len(baseline.images) == 4
        flushes = []
        flush = engine._flush_decoded

        def flush_and_interrupt(out, payload, entries):
            flush(out, payload, entries)
            flushes.append(len(entries))
            if len(flushes) == after_flush:
                engine.state.flag.interrupt()

        monkeypatch.setattr(engine, "_flush_decoded", flush_and_interrupt)
        try:
            got = self._run(engine, p)
        finally:
            engine.state.flag.clear()
        assert flushes == [1] * (after_flush + 1)
        assert got.images == baseline.images[:after_flush + 1]
        assert got.seeds == baseline.seeds[:after_flush + 1]
        assert got.infotexts == baseline.infotexts[:after_flush + 1]

    @pytest.mark.parametrize("batch", [1, 2])
    @pytest.mark.parametrize("job", ["txt2img", "img2img"])
    def test_decode_pipeline_order(self, engine, monkeypatch, job, batch):
        """The depth-1 decode pipeline, read from a log: a group's images
        are dispatched to the decoder one by one after its denoise, every
        image but the newest is then fetched and encoded, and the newest
        stays in flight under the next group's denoise. Images are
        appended in global index order."""
        groups = 3
        p = self._payload(job, seed=30, batch_size=batch, n_iter=groups)
        log = []

        def logged(name, token):
            fn = getattr(engine, name)

            def run(*args, **kw):
                log.append(token(*args))
                return fn(*args, **kw)

            monkeypatch.setattr(engine, name, run)

        decode_fn = engine._decode_u8_fn

        def decode_u8_fn(*key):
            fn = decode_fn(*key)

            def decode(*args):
                log.append("decode")
                return fn(*args)

            return decode

        monkeypatch.setattr(engine, "_decode_u8_fn", decode_u8_fn)
        logged("_denoise_range", lambda *a: "denoise")
        logged("_fetch_decoded", lambda *a: "fetch")
        logged("_append_image", lambda out, payload, img, i, *a: i)
        result = self._run(engine, p)
        want, shown = [], 0
        for g in range(groups):
            want += ["denoise"] + ["decode"] * batch
            newest = (g + 1) * batch - 1
            for i in range(shown, newest):
                want += ["fetch", i]
            shown = newest
        want += ["fetch", shown]
        assert log == want
        assert result.seeds == [30 + i for i in range(groups * batch)]

    def test_padded_rows_of_a_later_group_are_never_decoded(
            self, engine, monkeypatch):
        """Three images at ``batch_size`` 2: the second group denoises a
        padded row on the first group's executable, and ``_queue_decoded``
        dispatches and keeps only the row that was asked for, in order."""
        p = GenerationPayload(prompt="pad rows", steps=3, width=32,
                              height=32, batch_size=2, n_iter=2, seed=64)
        full = engine.txt2img(p)
        seen = []
        queue = engine._queue_decoded

        def watch(latents, pos, n, *size):
            entries = queue(latents, pos, n, *size)
            seen.append((latents.shape[0], pos, n, [e[1] for e in entries]))
            return entries

        monkeypatch.setattr(engine, "_queue_decoded", watch)
        before = METRICS.summary()["decode"]
        three = engine.generate_range(p, 0, 3)
        after = METRICS.summary()["decode"]
        assert seen == [(2, 0, 2, [0, 1]), (2, 2, 1, [2])]
        assert after["rows"] - before["rows"] == 3
        assert after["dispatches"] - before["dispatches"] == 3
        assert three.images == full.images[:3]
        assert three.seeds == [64, 65, 66]


# -- the dispatcher's groups of expanded requests ------------------------------

#: the tiny preset of the configuration the two-client cell runs: window
#: and full attention, a dense layer and expert layers
EXPANDED = contract.Case(configs.TINY_EXPAND, None, word="rule")
LONG = ("an old lighthouse on a cliff above a stormy sea at dusk in the "
        "first snow of the winter")


@pytest.fixture(scope="module")
def expanding():
    return EXPANDED.engine()


def _dispatcher(engine, ladder, window=0.6):
    return ServingDispatcher(
        engine, bucketer=ShapeBucketer(shapes=[(32, 32)], batches=ladder),
        window=window)


def _at_once(dispatcher, payloads, cancel=None):
    """Every payload from a thread of its own; the results in order."""
    results, errors = [None] * len(payloads), []

    def run(i, p):
        try:
            results[i] = dispatcher.submit(p)
        except Exception as e:  # noqa: BLE001 - surfaced by the assert
            errors.append(e)

    threads = [threading.Thread(target=run, args=(i, p))
               for i, p in enumerate(payloads)]
    for t in threads:
        t.start()
    if cancel:
        time.sleep(0.15)        # inside the coalesce window
        assert dispatcher.cancel(cancel)
    for t in threads:
        t.join()
    assert not errors, errors
    return results


class TestExpandedRequestsOfOneGroup:
    @staticmethod
    def _pair(**second):
        return [EXPANDED.payload(seed=11, request_id="first"),
                EXPANDED.payload(**dict(dict(prompt=LONG, seed=12,
                                             request_id="second"),
                                        **second))]

    @pytest.fixture(scope="class")
    def alone(self, expanding):
        """Each of the pair through ``_run_solo``: a ladder of one rung
        has none for a second request."""
        solo = _dispatcher(expanding, [1], window=0.0)
        assert not solo._coalescable(self._pair()[0])
        return [solo.submit(p) for p in self._pair()]

    def test_two_requests_share_one_scan_and_get_what_they_get_alone(
            self, expanding, alone):
        pair = _dispatcher(expanding, [2])
        assert pair._coalescable(self._pair()[0])
        # a lone request pads: UNet rows to the rung, the scan to its
        # bucket with one live sequence, and compiles what a pair runs
        lone = pair.submit(self._pair()[0])
        assert lone.prompts == alone[0].prompts
        assert lone.images == alone[0].images
        EXPANDER.clear()
        METRICS.clear()
        got = _at_once(pair, self._pair())
        stats, served = EXPANDER.summary(), METRICS.summary()
        assert not served["compiles"]       # nothing on the pair
        assert served["coalesce_factor"] == 2.0
        assert stats["requests"] == stats["scans_joined"] == 1
        assert stats["requests_joined"] == stats["prompts_joined"] == 2
        assert stats["tokens_decoded"] == 2 * 40
        assert stats["decode_steps"] == 2 * contract.STEPS
        for mine, solo in zip(got, alone):
            assert mine.prompts == solo.prompts != [LONG]
            assert mine.seeds == solo.seeds
            assert mine.infotexts == solo.infotexts
            assert mine.images == solo.images
            assert mine.parameters["prompt"] == solo.parameters["prompt"]
        assert got[0].prompts != got[1].prompts

    def test_a_request_of_two_images_is_two_sequences_of_the_scan(
            self, expanding):
        solo = _dispatcher(expanding, [2], window=0.0)
        p = EXPANDED.payload(prompt=LONG, seed=31, batch_size=2)
        assert not solo._coalescable(p)     # no rung for a second of two
        want = solo.submit(p)
        group = _dispatcher(expanding, [4], window=0.0)
        assert group._coalescable(p)
        EXPANDER.clear()
        got = group.submit(p)
        assert EXPANDER.summary()["sequences"] == 2
        assert got.prompts == want.prompts and len(set(got.prompts)) == 2
        assert got.infotexts == want.infotexts

    @pytest.mark.parametrize("other", [
        {"instruction": "another rule"}, {"max_new_tokens": 33},
        {"temperature": 0.5}, {"ignore_eos": False}, {"context_chunks": 2},
        None])
    def test_other_script_arguments_or_none_do_not_join(self, expanding,
                                                        other):
        pair = _dispatcher(expanding, [2])
        base = EXPANDED.payload()
        scripts = {} if other is None else EXPANDED.script(**other)
        key = pair._group_key(base)
        assert key == pair._group_key(EXPANDED.payload(prompt=LONG, seed=5))
        assert key != pair._group_key(
            EXPANDED.payload(alwayson_scripts=scripts))
        # the places consumers read stay where they were
        assert key[-1] == "bf16" or isinstance(key[-1], str)
        assert key[-3:-1] == (0, 0) and key[8] == 1
        assert key[EXPANSION_AT][0].startswith("rule0 rule1")
        plain = pair._group_key(EXPANDED.payload(alwayson_scripts={}))
        assert plain[EXPANSION_AT] is None
        # a worker without an expander reads the script as a plain request
        assert ServingDispatcher._group_key(None, base)[EXPANSION_AT] is None

    def test_a_different_budget_runs_in_a_group_of_its_own(self, expanding,
                                                           alone):
        pair = _dispatcher(expanding, [2], window=0.2)
        METRICS.clear()
        got = _at_once(pair, [
            self._pair()[0],
            EXPANDED.payload(prompt=LONG, seed=12,
                             alwayson_scripts=EXPANDED.script(
                                 max_new_tokens=33))])
        assert METRICS.summary()["coalesce_factor"] == 1.0
        assert got[0].prompts == alone[0].prompts
        assert got[1].prompts != alone[1].prompts

    @pytest.mark.parametrize("ladder,images,joins", [
        ([1], 1, False), ([4], 4, False), ([2], 1, True), ([4], 2, True),
        ([1, 2, 4], 3, False)])
    def test_the_ladder_decides(self, expanding, ladder, images, joins):
        """Ladder "1" at one image and ladder "4" at four (every expander
        cell the benchmark had) leave the request solo."""
        d = _dispatcher(expanding, ladder)
        assert d._coalescable(
            EXPANDED.payload(batch_size=images)) is joins
        assert d._coalescable(EXPANDED.payload(
            batch_size=images, alwayson_scripts={}))

    def test_a_cancelled_members_sequence_is_dropped(self, expanding, alone):
        # (a rung of four: two requests do not fill the group, so the
        # window is still open when the cancel lands)
        pair = _dispatcher(expanding, [4])
        EXPANDER.clear()
        got = _at_once(pair, self._pair(), cancel="second")
        assert got[1].images == [] and got[1].parameters["cancelled"]
        assert got[0].prompts == alone[0].prompts
        assert got[0].images == alone[0].images
        stats = EXPANDER.summary()      # the scan carried the one left
        assert stats["sequences"] == 1 and stats["requests_joined"] == 1

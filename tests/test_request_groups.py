"""A request of several dispatch groups through the one executor.

``Engine._run_txt2img`` and ``_run_img2img`` run a request group by group
(``n_iter`` groups of ``batch_size``) with a depth-1 decode pipeline:
``_queue_decoded`` dispatches a group's images to the decoder one by one,
``_flush_decoded`` fetches and encodes every image but the newest, and the
newest stays in flight under the next group's denoise. These tests hold that
loop to the request's groups run one by one at their seeds: the golden pin,
ControlNet units, an interrupt and a preemption between two groups, the
order of decodes and fetches, and the padded rows of a remainder group.
"""

import pytest

from stable_diffusion_webui_distributed_tpu.models.configs import TINY
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
)
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import METRICS
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

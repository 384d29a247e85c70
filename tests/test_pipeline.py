"""End-to-end pipeline tests on the tiny families (CPU, random weights).

Covers the minimum end-to-end slice of SURVEY.md §7 plus the seed-exact
range-split contract that replaces the reference's per-worker seed offsets
(/root/reference/scripts/distributed.py:297-305)."""

import base64
import io

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.models.configs import TINY, TINY_XL
from stable_diffusion_webui_distributed_tpu.models.clip import CLIPTextModel
from stable_diffusion_webui_distributed_tpu.models.unet import UNet
from stable_diffusion_webui_distributed_tpu.models.vae import VAE
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
    b64png_to_array,
)
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import METRICS


def init_params(family):
    k = jax.random.key(0)
    ids = jnp.zeros((1, 77), jnp.int32)
    te = CLIPTextModel(family.text_encoder).init(k, ids)["params"]
    te2 = (CLIPTextModel(family.text_encoder_2).init(k, ids)["params"]
           if family.text_encoder_2 else None)
    ctx_dim = family.unet.cross_attention_dim
    args = [jnp.zeros((2, 8, 8, family.unet.in_channels)), jnp.ones((2,)),
            jnp.zeros((2, 77, ctx_dim))]
    if family.unet.addition_embed_dim:
        args.append(jnp.zeros((2, family.unet.projection_input_dim)))
    un = UNet(family.unet).init(k, *args)["params"]
    vae = VAE(family.vae).init(k, jnp.zeros((1, 16, 16, 3)),
                               jax.random.key(1))["params"]
    return {"text_encoder": te, "text_encoder_2": te2,
            "unet": un, "vae": vae}


@pytest.fixture(scope="module")
def engine():
    return Engine(TINY, init_params(TINY), chunk_size=4,
                  state=GenerationState())


@pytest.fixture(scope="module")
def engine_xl():
    return Engine(TINY_XL, init_params(TINY_XL), chunk_size=4,
                  state=GenerationState())


def decode(b64):
    return b64png_to_array(b64)


class TestTxt2Img:
    def test_shapes_seeds_infotext(self, engine):
        p = GenerationPayload(prompt="a cow", steps=6, width=64, height=64,
                              batch_size=2, seed=42)
        r = engine.txt2img(p)
        assert len(r.images) == 2
        assert r.seeds == [42, 43]
        img = decode(r.images[0])
        assert img.shape == (64, 64, 3)
        assert "Seed: 42" in r.infotexts[0]
        assert "Sampler: Euler a" in r.infotexts[0]

    def test_deterministic(self, engine):
        p = GenerationPayload(prompt="x", steps=4, width=32, height=32, seed=9)
        a = engine.txt2img(p).images[0]
        b = engine.txt2img(p).images[0]
        assert a == b

    def test_range_split_seed_exact(self, engine):
        """Sub-ranges == same images of the full batch: the DP contract."""
        p = GenerationPayload(prompt="a cow", steps=4, width=32, height=32,
                              batch_size=3, seed=100)
        full = engine.txt2img(p)
        part0 = engine.generate_range(p, 0, 1)
        part12 = engine.generate_range(p, 1, 2)
        assert part0.images[0] == full.images[0]
        assert part12.images == full.images[1:]
        assert part12.seeds == full.seeds[1:]

    def test_cond_cache_reused_across_requests(self, engine, monkeypatch):
        """Second request with the same prompt skips text encoding entirely
        (webui's cached_c/uc); a LoRA change invalidates the cache."""
        p = GenerationPayload(prompt="cache me", steps=2, width=32,
                              height=32, seed=3)
        first = engine.txt2img(p)
        enc = engine._encode_fn()
        calls = []

        def counting(*args, **kw):
            calls.append(1)
            return enc(*args, **kw)

        monkeypatch.setattr(engine, "_encode_fn", lambda *sig: counting)
        again = engine.txt2img(p)
        assert again.images == first.images
        assert calls == []  # both cond and uncond came from the cache
        engine._cond_epoch += 1  # what set_loras does on a merge
        engine.txt2img(p)
        assert calls  # stale epoch -> re-encoded

    @pytest.mark.parametrize("size,batch", [(32, 4), (64, 4), (32, 3)])
    def test_decode_one_image_a_dispatch(self, engine, monkeypatch, size,
                                         batch):
        """A batch decodes one image a dispatch at every size (PERF.md
        section 5, "the decode by batch"), through ONE executable key,
        and yields the images, seeds and order of the one-dispatch decode
        (engine._queue_decoded); serving.decode counts rows and
        dispatches."""
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            array_to_b64png,
        )
        p = GenerationPayload(prompt="mb", steps=3, width=size, height=size,
                              batch_size=batch, seed=77)
        seen = []
        queue = engine._queue_decoded

        def watch(latents, *args):
            entries = queue(jnp.array(latents), *args)  # rows are donated
            seen.append((latents, entries))
            return entries

        monkeypatch.setattr(engine, "_queue_decoded", watch)
        before = METRICS.summary()["decode"]
        had = set(engine._cache)
        sliced = engine.txt2img(p)
        made = [k for k in set(engine._cache) - had if k[0] == "decode-u8"]
        after = METRICS.summary()["decode"]
        assert after["rows"] - before["rows"] == batch
        assert after["dispatches"] - before["dispatches"] == batch
        (latents, entries), = seen
        assert [(e[0].shape, e[1]) for e in entries] == [
            ((1, size, size, 3), i) for i in range(batch)]
        assert all(k[1:4] == (size, size, 1) for k in made), made
        assert ("decode-u8", size, size, 1, TINY.name) in engine._cache
        whole = np.asarray(engine._decode_u8_fn(size, size, batch)(
            engine.params["vae"], latents))
        assert sliced.images == [array_to_b64png(img) for img in whole]
        assert sliced.seeds == [77 + i for i in range(batch)]

    def test_remainder_group_pad_and_drop(self, engine):
        """7 images at batch_size 2: the final odd group reuses the
        compiled 2-batch executable (pad-and-drop) and must produce the
        same images as a clean run."""
        p = GenerationPayload(prompt="pad", steps=3, width=32, height=32,
                              batch_size=2, n_iter=4, seed=60)
        full = engine.txt2img(p)  # 8 images, seeds 60..67
        p7 = p.model_copy()
        r7 = engine.generate_range(p7, 0, 7)
        assert len(r7.images) == 7
        assert r7.images == full.images[:7]
        assert r7.seeds == full.seeds[:7]

    def test_flash_attention_engine_end_to_end(self):
        """The engine with the Pallas flash-attention policy must reproduce
        the XLA-attention engine's output (interpret mode on CPU)."""
        from stable_diffusion_webui_distributed_tpu.runtime import dtypes

        params = init_params(TINY)
        p = GenerationPayload(prompt="f", steps=3, width=32, height=32,
                              seed=13)
        xla_eng = Engine(TINY, params, chunk_size=4, state=GenerationState())
        flash_eng = Engine(
            TINY, params, chunk_size=4, state=GenerationState(),
            policy=dtypes.Policy(compute_dtype=np.float32,
                                 attention_impl="flash"))
        a = xla_eng.txt2img(p)
        b = flash_eng.txt2img(p)
        ia = decode(a.images[0]).astype(np.int32)
        ib = decode(b.images[0]).astype(np.int32)
        assert np.abs(ia - ib).max() <= 1

    def test_n_iter(self, engine):
        p = GenerationPayload(prompt="y", steps=4, width=32, height=32,
                              batch_size=2, n_iter=2, seed=5)
        r = engine.txt2img(p)
        assert len(r.images) == 4
        assert r.seeds == [5, 6, 7, 8]

    def test_variation_seed_images_differ_but_share_base(self, engine):
        p0 = GenerationPayload(prompt="v", steps=4, width=32, height=32,
                               batch_size=2, seed=11, subseed=99,
                               subseed_strength=0.4)
        r = engine.txt2img(p0)
        assert r.images[0] != r.images[1]  # subseed advances per image
        assert r.seeds == [11, 11]         # base seed does not
        assert r.subseeds == [99, 100]


class TestImg2Img:
    def test_roundtrip(self, engine):
        src = GenerationPayload(prompt="s", steps=4, width=32, height=32,
                                seed=1)
        base = engine.txt2img(src).images[0]
        p = GenerationPayload(prompt="s", steps=6, width=32, height=32,
                              seed=2, init_images=[base],
                              denoising_strength=0.5)
        r = engine.img2img(p)
        assert decode(r.images[0]).shape == (32, 32, 3)

    def test_strength_zero_steps(self, engine):
        # strength ~0 -> almost no denoise steps; must not crash
        src = GenerationPayload(prompt="s", steps=4, width=32, height=32,
                                seed=1)
        base = engine.txt2img(src).images[0]
        p = GenerationPayload(prompt="s", steps=4, width=32, height=32,
                              seed=2, init_images=[base],
                              denoising_strength=0.1)
        r = engine.img2img(p)
        assert len(r.images) == 1

    def test_inpaint_mask(self, engine):
        src = GenerationPayload(prompt="s", steps=4, width=32, height=32,
                                seed=1)
        base = engine.txt2img(src).images[0]
        # mask: repaint left half only
        from PIL import Image

        m = np.zeros((32, 32, 3), np.uint8)
        m[:, :16] = 255
        buf = io.BytesIO()
        Image.fromarray(m).save(buf, format="PNG")
        mask_b64 = base64.b64encode(buf.getvalue()).decode()
        p = GenerationPayload(prompt="s", steps=6, width=32, height=32,
                              seed=3, init_images=[base], mask=mask_b64,
                              denoising_strength=0.9)
        r = engine.img2img(p)
        out = decode(r.images[0]).astype(np.int32)
        orig = decode(base).astype(np.int32)
        # unmasked right half stays close to the original
        right_diff = np.abs(out[:, 16:] - orig[:, 16:]).mean()
        left_diff = np.abs(out[:, :16] - orig[:, :16]).mean()
        assert right_diff < left_diff

    def test_hires_fix_output_size(self, engine):
        p = GenerationPayload(prompt="h", steps=4, width=32, height=32,
                              seed=4, enable_hr=True, hr_scale=2.0,
                              denoising_strength=0.7)
        r = engine.txt2img(p)
        assert decode(r.images[0]).shape == (64, 64, 3)

    def test_inpaint_fill_modes(self, engine):
        """webui inpainting_fill enum: original/latent-noise/latent-nothing/
        fill all produce valid, distinct repaints; the unmasked region stays
        pinned in every mode."""
        src = GenerationPayload(prompt="s", steps=4, width=32, height=32,
                                seed=1)
        base_img = engine.txt2img(src).images[0]
        from PIL import Image

        m = np.zeros((32, 32, 3), np.uint8)
        m[:, :16] = 255
        buf = io.BytesIO()
        Image.fromarray(m).save(buf, format="PNG")
        mask_b64 = base64.b64encode(buf.getvalue()).decode()

        outs = {}
        for fill in (1, 2, 3, 0):
            p = GenerationPayload(prompt="s", steps=6, width=32, height=32,
                                  seed=3, init_images=[base_img],
                                  mask=mask_b64, mask_blur=0,
                                  denoising_strength=0.9,
                                  inpainting_fill=fill)
            r = engine.img2img(p)
            outs[fill] = decode(r.images[0]).astype(np.int32)
            orig = decode(base_img).astype(np.int32)
            # pinned (right) side must move less than the repainted left
            right_diff = np.abs(outs[fill][:, 20:] - orig[:, 20:]).mean()
            left_diff = np.abs(outs[fill][:, :12] - orig[:, :12]).mean()
            assert right_diff < left_diff, (fill, right_diff, left_diff)
        assert not np.array_equal(outs[1], outs[3])  # nothing != original
        assert not np.array_equal(outs[1], outs[2])  # noise != original

    def test_infotext_round_trip(self):
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            build_infotext, parse_infotext,
        )

        p = GenerationPayload(
            prompt="a (red:1.3) cow <lora:style:0.8>\nSteps: 3 of the "
                   "ritual\nsecond line",
            negative_prompt="ugly, blurry\nlowres second line",
            steps=25, width=640, height=512, seed=1234,
            sampler_name="DPM++ 2M Karras", cfg_scale=5.5,
            subseed=99, subseed_strength=0.4)
        text = build_infotext(p, p.seed, p.subseed, "model-x")
        back = parse_infotext(text)
        assert back.prompt == p.prompt
        assert back.negative_prompt == p.negative_prompt
        assert (back.steps, back.width, back.height) == (25, 640, 512)
        assert back.sampler_name == "DPM++ 2M Karras"
        assert back.cfg_scale == 5.5
        assert (back.seed, back.subseed) == (1234, 99)
        assert back.subseed_strength == 0.4

    def test_infotext_round_trip_seed_resize_and_ensd(self):
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            build_infotext, parse_infotext,
        )

        p = GenerationPayload(
            prompt="cow", steps=10, seed=5,
            seed_resize_from_w=1024, seed_resize_from_h=768,
            override_settings={"eta_noise_seed_delta": 31337})
        back = parse_infotext(build_infotext(p, 5, 0, "m"))
        assert (back.seed_resize_from_w, back.seed_resize_from_h) == \
            (1024, 768)
        assert back.override_settings["eta_noise_seed_delta"] == 31337

    def test_seed_resize_and_ensd_change_output_deterministically(
            self, engine):
        base = dict(prompt="s", steps=3, width=32, height=32, seed=11)
        plain = engine.txt2img(GenerationPayload(**base))
        resized = engine.txt2img(GenerationPayload(
            **base, seed_resize_from_w=16, seed_resize_from_h=16))
        assert resized.images[0] != plain.images[0]
        again = engine.txt2img(GenerationPayload(
            **base, seed_resize_from_w=16, seed_resize_from_h=16))
        assert again.images[0] == resized.images[0]
        # ENSD shifts the ancestral sampler noise (Euler a default)
        shifted = engine.txt2img(GenerationPayload(
            **base, override_settings={"eta_noise_seed_delta": 31337}))
        assert shifted.images[0] != plain.images[0]

    def test_prompts_from_file_script(self, engine):
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            apply_scripts,
        )

        p = GenerationPayload(
            prompt="ignored", steps=3, width=32, height=32, seed=40,
            script_name="Prompts from file or textbox",
            script_args=[True, False, "# comment\na cow\n\na dog\n"])
        expanded = apply_scripts(p)
        assert expanded.all_prompts == ["a cow", "a dog"]
        assert expanded.batch_size == 2 and expanded.group_size == 1
        assert not expanded.same_seed  # checkbox_iterate ON advances seeds
        r = engine.txt2img(p)
        assert len(r.images) == 2
        assert r.prompts == ["a cow", "a dog"]
        assert r.seeds == [40, 41]
        # line i reproduces a plain generation of that prompt at seed+i
        plain = engine.txt2img(GenerationPayload(
            prompt="a dog", steps=3, width=32, height=32, seed=41))
        assert r.images[1] == plain.images[0]

        # default (checkbox_iterate off): webui runs every line at the
        # request seed
        p2 = GenerationPayload(
            prompt="x", steps=3, width=32, height=32, seed=40,
            script_name="Prompts from file or textbox",
            script_args=[False, False, "a cow\na dog"])
        r2 = engine.txt2img(p2)
        assert r2.seeds == [40, 40]

    def test_prompt_matrix_expansion_order(self):
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            expand_prompt_matrix,
        )

        got = expand_prompt_matrix("a cow|red|blue")
        # binary-counter order: bit j of index i selects option j (webui
        # scripts/prompt_matrix.py semantics)
        assert got == ["a cow", "a cow, red", "a cow, blue",
                       "a cow, red, blue"]

    def test_prompt_matrix_end_to_end(self, engine):
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            apply_scripts,
        )

        p = GenerationPayload(prompt="a cow|red", steps=3, width=32,
                              height=32, seed=21,
                              script_name="Prompt Matrix")
        expanded = apply_scripts(p)
        assert expanded.batch_size == 2 and expanded.same_seed
        # the user's original batch_size caps the compiled dispatch group
        assert expanded.group_size == 1
        r = engine.txt2img(p)
        assert len(r.images) == 2
        assert r.prompts == ["a cow", "a cow, red"]
        assert r.seeds == [21, 21]  # fixed seed across the matrix
        assert r.images[0] != r.images[1]  # prompts actually condition
        assert "a cow, red" in r.infotexts[1]
        # matrix cell 0 == a plain single generation of the base prompt at
        # the same seed (same index-0 noise, same conditioning)
        plain = engine.txt2img(GenerationPayload(
            prompt="a cow", steps=3, width=32, height=32, seed=21))
        assert r.images[0] == plain.images[0]

    def test_all_prompts_range_contract(self, engine):
        # per-image prompts must survive the fan-out split: generating
        # [1, 3) standalone reproduces those rows of the full batch
        p = GenerationPayload(prompt="base", steps=3, width=32, height=32,
                              seed=9,
                              all_prompts=["base", "base b", "base c"],
                              batch_size=3)
        full = engine.txt2img(p)
        part = engine.generate_range(p, 1, 2)
        assert part.images == full.images[1:3]
        assert part.prompts == ["base b", "base c"]

    def test_context_padding_independent_of_slice(self, engine):
        # a short prompt grouped with a >1-chunk prompt gets a 2-chunk
        # context; the same image produced alone on another worker must
        # match bitwise, so the request-wide context length travels as
        # payload.context_chunks (engine.request_context_chunks)
        long_prompt = "a " + " ".join(f"word{i}" for i in range(90))
        p = GenerationPayload(prompt="base", steps=3, width=32, height=32,
                              seed=9, all_prompts=["short one", long_prompt],
                              batch_size=2, group_size=2)
        n = engine.request_context_chunks(p)
        assert n > 1  # the long prompt really spans multiple 77-token chunks
        full = engine.txt2img(p)

        # simulate the HTTP fan-out: the remote gets only ITS slice plus
        # the master's context_chunks (scheduler/worker.py slice logic)
        p_slice = p.model_copy()
        p_slice.all_prompts = ["short one"]
        p_slice.batch_size = 1
        p_slice.context_chunks = n
        part = engine.generate_range(p_slice, 0, 1)
        assert part.images[0] == full.images[0]

        # without the pin the slice pads to its own (shorter) context —
        # the bug this guards against would silently diverge
        p_bare = p_slice.model_copy()
        p_bare.context_chunks = None
        bare = engine.generate_range(p_bare, 0, 1)
        assert bare.images[0] != full.images[0]

    def test_hires_upscaler_variants(self, engine):
        base = dict(prompt="h", steps=3, width=32, height=32, seed=4,
                    enable_hr=True, hr_scale=2.0, denoising_strength=0.7)
        bilinear = engine.txt2img(GenerationPayload(**base))
        nearest = engine.txt2img(GenerationPayload(
            **base, hr_upscaler="Latent (nearest)"))
        assert nearest.images[0] != bilinear.images[0]
        # unknown model-based upscaler falls back to latent bilinear
        fallback = engine.txt2img(GenerationPayload(
            **base, hr_upscaler="R-ESRGAN 4x+"))
        assert fallback.images[0] == bilinear.images[0]


class TestXL:
    def test_txt2img(self, engine_xl):
        p = GenerationPayload(prompt="xl", steps=4, width=32, height=32,
                              seed=6)
        r = engine_xl.txt2img(p)
        assert decode(r.images[0]).shape == (32, 32, 3)


class TestVPrediction:
    def test_v_pred_runs_and_differs_from_epsilon(self):
        """Same weights under v-prediction vs epsilon parameterization must
        both generate, and differently (SD2.x 768-v support)."""
        from stable_diffusion_webui_distributed_tpu.models.configs import (
            TINY_V,
        )

        params = init_params(TINY)
        p = GenerationPayload(prompt="v", steps=4, width=32, height=32,
                              seed=3)
        eps_engine = Engine(TINY, params, chunk_size=4,
                            state=GenerationState())
        v_engine = Engine(TINY_V, params, chunk_size=4,
                          state=GenerationState())
        a = eps_engine.txt2img(p)
        b = v_engine.txt2img(p)
        assert a.images[0] != b.images[0]
        assert decode(b.images[0]).shape == (32, 32, 3)


class TestMeshEngine:
    def test_sharded_engine_matches_unsharded(self, engine, mesh8):
        """Engine on a dp=4,tp=2 mesh must reproduce the meshless images
        exactly — sharding is a placement decision, never a numerics one."""
        sharded = Engine(TINY, init_params(TINY), chunk_size=4,
                         state=GenerationState(), mesh=mesh8)
        p = GenerationPayload(prompt="mesh cow", steps=4, width=32,
                              height=32, batch_size=4, seed=21)
        a = engine.txt2img(p)
        before = METRICS.summary()["decode"]
        b = sharded.txt2img(p)
        after = METRICS.summary()["decode"]
        # a batch split over dp decodes as ONE partitioned dispatch, every
        # chip its own rows (engine._queue_decoded)
        assert after["rows"] - before["rows"] == 4
        assert after["dispatches"] - before["dispatches"] == 1
        ia = np.stack([decode(x) for x in a.images]).astype(np.int32)
        ib = np.stack([decode(x) for x in b.images]).astype(np.int32)
        # identical placement-independent math; allow 1 LSB for reduction
        # order differences across device boundaries
        assert np.abs(ia - ib).max() <= 1

    @pytest.mark.parametrize("spec,want", [
        (None, "auto"), ("dp=1", "auto"), ("dp=4", "xla"),
        ("dp=2,tp=2", "xla"), ("sp=4", "ring")])
    def test_attention_impl_follows_the_mesh(self, spec, want):
        """pjit does not partition a pallas_call: an engine under a dp or
        tp mesh keeps XLA's attention, one chip lets ops/attention.py
        choose per site, an sp axis rides the ring."""
        from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
            build_mesh,
        )

        eng = Engine(TINY, init_params(TINY), state=GenerationState(),
                     mesh=None if spec is None else build_mesh(spec))
        assert eng.unet.attention_impl == want
        assert eng.controlnet_module.attention_impl == want

    def test_sp_mesh_ring_attention_matches(self, engine):
        """Engine on an sp=4 mesh routes latent self-attention through the
        ring — output must match the meshless run (sequence parallelism is
        a placement decision, not a numerics one)."""
        from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
            build_mesh,
        )

        sharded = Engine(TINY, init_params(TINY), chunk_size=4,
                         state=GenerationState(), mesh=build_mesh("sp=4"))
        assert sharded.unet.attention_impl == "ring"
        p = GenerationPayload(prompt="ring cow", steps=3, width=32,
                              height=32, batch_size=2, seed=31)
        a = engine.txt2img(p)
        b = sharded.txt2img(p)
        ia = np.stack([decode(x) for x in a.images]).astype(np.int32)
        ib = np.stack([decode(x) for x in b.images]).astype(np.int32)
        assert np.abs(ia - ib).max() <= 1

    def test_sharded_engine_odd_batch_falls_back(self, engine, mesh8):
        sharded = Engine(TINY, init_params(TINY), chunk_size=4,
                         state=GenerationState(), mesh=mesh8)
        p = GenerationPayload(prompt="odd", steps=4, width=32, height=32,
                              batch_size=3, seed=22)
        before = METRICS.summary()["decode"]
        r = sharded.txt2img(p)
        after = METRICS.summary()["decode"]
        assert len(r.images) == 3
        # not split over dp: one image a dispatch, as on one chip
        assert after["dispatches"] - before["dispatches"] == 3


class TestRefiner:
    """SDXL base+refiner handoff (BASELINE config #2's two-model pass)."""

    @pytest.fixture(scope="class")
    def engines(self):
        from stable_diffusion_webui_distributed_tpu.models.configs import (
            TINY_REFINER, TINY_XL,
        )

        refiner = Engine(TINY_REFINER, init_params(TINY_REFINER),
                         chunk_size=4, state=GenerationState(),
                         model_name="tiny-ref")
        provider = lambda name: refiner if name == "tiny-ref" else None
        base = Engine(TINY_XL, init_params(TINY_XL), chunk_size=4,
                      state=GenerationState(), engine_provider=provider)
        return base, refiner

    def test_refiner_changes_output(self, engines):
        base_engine, _ = engines
        plain = base_engine.txt2img(GenerationPayload(
            prompt="c", steps=6, width=32, height=32, seed=9))
        refined = base_engine.txt2img(GenerationPayload(
            prompt="c", steps=6, width=32, height=32, seed=9,
            refiner_checkpoint="tiny-ref", refiner_switch_at=0.5))
        assert refined.images[0] != plain.images[0]

    def test_switch_at_one_is_base_only(self, engines):
        base_engine, _ = engines
        plain = base_engine.txt2img(GenerationPayload(
            prompt="c", steps=6, width=32, height=32, seed=9))
        same = base_engine.txt2img(GenerationPayload(
            prompt="c", steps=6, width=32, height=32, seed=9,
            refiner_checkpoint="tiny-ref", refiner_switch_at=1.0))
        assert same.images[0] == plain.images[0]

    def test_unknown_refiner_falls_back(self, engines):
        base_engine, _ = engines
        r = base_engine.txt2img(GenerationPayload(
            prompt="c", steps=4, width=32, height=32, seed=9,
            refiner_checkpoint="missing", refiner_switch_at=0.5))
        assert len(r.images) == 1


class TestDpmAdaptiveEngine:
    """DPM adaptive end-to-end: the engine routes it through the host-side
    PID loop (engine._denoise_adaptive), not the fixed-grid scan."""

    def test_txt2img_runs_and_is_deterministic(self, engine):
        p = GenerationPayload(prompt="adaptive cow", steps=8, width=32,
                              height=32, seed=21,
                              sampler_name="DPM adaptive")
        a = engine.txt2img(p)
        assert len(a.images) == 1
        assert "Sampler: DPM adaptive" in a.infotexts[0]
        b = engine.txt2img(p)
        assert a.images == b.images  # PID trajectory is deterministic
        # and it is genuinely a different algorithm than the fixed grid
        e = engine.txt2img(p.model_copy(update={"sampler_name": "Euler"}))
        assert e.images != a.images

    def test_img2img_runs(self, engine):
        base = GenerationPayload(prompt="seed image", steps=4, width=32,
                                 height=32, seed=5)
        init = engine.txt2img(base).images[0]
        p = GenerationPayload(prompt="adapted", steps=8, width=32, height=32,
                              seed=6, sampler_name="DPM adaptive",
                              init_images=[init], denoising_strength=0.6)
        r = engine.img2img(p)
        assert len(r.images) == 1

    def test_interrupt_between_attempts(self):
        st = GenerationState()
        eng = Engine(TINY, init_params(TINY), state=st)
        st.add_listener(lambda prog: st.flag.interrupt())
        p = GenerationPayload(prompt="i", steps=20, width=32, height=32,
                              seed=8, sampler_name="DPM adaptive")
        r = eng.txt2img(p)
        assert len(r.images) == 1  # partial result still decoded


def _host_mem_available_gb() -> float:
    """MemAvailable from /proc/meminfo in GiB; inf when unreadable (non-Linux
    hosts just run the test)."""
    try:
        with open("/proc/meminfo", "r", encoding="ascii") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) / (1024 * 1024)
    except (OSError, ValueError, IndexError):
        pass
    return float("inf")


class TestMixedFleetBitStability:
    """The same engine driven through a LocalBackend and through a real
    HTTP round-trip (this framework's server + HTTPBackend) must produce
    byte-identical images for EVERY sampler family — including DPM
    adaptive, whose host-side controller runs wherever the engine runs.
    (Divergence remains only vs legacy torch sdwui remotes; PARITY.md.)"""

    @pytest.mark.parametrize("sampler", ["Euler a", "DPM++ 2M Karras",
                                         "DPM adaptive"])
    def test_local_equals_http(self, engine, sampler, monkeypatch):
        from stable_diffusion_webui_distributed_tpu.scheduler.worker import (
            HTTPBackend, LocalBackend,
        )
        from stable_diffusion_webui_distributed_tpu.server.api import (
            ApiServer,
        )

        if _host_mem_available_gb() < 8.0:
            pytest.skip("needs ~8 GiB host RAM for the HTTP round-trip")
        # ApiServer fronts a bare Engine with a ServingDispatcher whose
        # DEFAULT bucket ladder starts at 512x512 — padding this 32x32 tiny
        # request up 256x would allocate hundreds of GB on CPU. Pin a ladder
        # that matches the test shapes before the server is built.
        monkeypatch.setenv("SDTPU_BUCKET_LADDER", "32x32,64x64")
        monkeypatch.setenv("SDTPU_BATCH_LADDER", "1,2")

        p = GenerationPayload(prompt="fleet parity", steps=6, width=32,
                              height=32, batch_size=2, seed=77,
                              sampler_name=sampler)
        local = LocalBackend(engine).generate(p, 0, 2)
        srv = ApiServer(engine, state=engine.state,
                        host="127.0.0.1", port=0).start()
        try:
            remote = HTTPBackend("127.0.0.1", srv.port).generate(p, 0, 2)
        finally:
            srv.stop()
        assert remote.images == local.images
        assert remote.seeds == local.seeds


class TestInterrupt:
    def test_interrupt_stops_early(self):
        st = GenerationState()
        eng = Engine(TINY, init_params(TINY), chunk_size=1, state=st)
        # interrupt as soon as the first chunk reports progress
        st.add_listener(lambda prog: st.flag.interrupt())
        p = GenerationPayload(prompt="i", steps=12, width=32, height=32,
                              seed=8)
        r = eng.txt2img(p)
        # partial result is still decoded and returned (reference keeps
        # whatever images came back, distributed.py:158-169)
        assert len(r.images) == 1
        assert st.progress.sampling_step < 12


class TestDpmAdaptiveEdgeCases:
    def test_steps_1_denoises_full_range(self, engine):
        """steps=1 makes the ladder [sigma_max, 0]; the adaptive range must
        fall back to the schedule's own sigma_min (advisor r4) — webui's
        DPM adaptive ignores the slider, so steps=1 and steps=8 integrate
        the SAME [sigma_max, sigma_min] range and must match byte-exactly."""
        base = dict(prompt="one step", width=32, height=32, seed=31,
                    sampler_name="DPM adaptive")
        one = engine.txt2img(GenerationPayload(steps=1, **base))
        eight = engine.txt2img(GenerationPayload(steps=8, **base))
        assert one.images[0] == eight.images[0]

    def test_incomplete_trajectory_marked(self, engine, monkeypatch):
        """A run that hits the attempt backstop before sigma_min must be
        visible: warning + infotext marker (VERDICT r4 item 5)."""
        from stable_diffusion_webui_distributed_tpu.pipeline import (
            engine as engine_mod,
        )

        orig = engine_mod.kd.sample_dpm_adaptive

        def strangled(attempt_fn, x, sigma_max, sigma_min, **kw):
            # rtol so tight every step is rejected; tiny backstop
            kw.update(rtol=1e-12, atol=1e-14, max_attempts=3)
            return orig(attempt_fn, x, sigma_max, sigma_min, **kw)

        monkeypatch.setattr(engine_mod.kd, "sample_dpm_adaptive", strangled)
        r = engine.txt2img(GenerationPayload(
            prompt="stuck", steps=8, width=32, height=32, seed=32,
            sampler_name="DPM adaptive"))
        assert "DPM adaptive: incomplete" in r.infotexts[0]
        # and a normal run right after is NOT marked (per-request latch)
        monkeypatch.setattr(engine_mod.kd, "sample_dpm_adaptive", orig)
        ok = engine.txt2img(GenerationPayload(
            prompt="fine", steps=8, width=32, height=32, seed=33,
            sampler_name="DPM adaptive"))
        assert "incomplete" not in ok.infotexts[0]

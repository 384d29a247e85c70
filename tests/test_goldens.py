"""Golden-output regression fixtures: frozen image hashes per sampler and
per generation path (VERDICT r3 #4).

Every case renders on the TINY families with deterministically initialized
weights (jax.random.key(0) via test_pipeline.init_params) and fixed seeds,
then hashes the returned PNG bytes. PNGs are lossless, so the hash is
element-level: ANY numeric change anywhere in the tokenizer → CLIP → UNet →
sampler → VAE → encoder chain flips it. While no trained checkpoints exist
in this environment, these fixtures are the only available proxy for the
user-facing acceptance bar — seed-exact images across refactors (SURVEY §7
hard part #1).

A hash mismatch means the framework's numerics CHANGED. If the change is
intentional (e.g. a sampler bug fix), regenerate with

    SDTPU_UPDATE_GOLDENS=1 python -m pytest tests/test_goldens.py -q

and commit the goldens.json diff explaining why. Goldens are tied to the
environment's jax/XLA build: a toolchain upgrade that shifts float results
legitimately regenerates them (one commit, stated as such).
"""

import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.models.configs import (
    TINY, TINY_REFINER, TINY_XL,
)
from stable_diffusion_webui_distributed_tpu.models.controlnet import ControlNet
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
    array_to_b64png,
)
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)

from test_pipeline import init_params

GOLDENS_PATH = os.path.join(os.path.dirname(__file__), "goldens.json")
UPDATE = os.environ.get("SDTPU_UPDATE_GOLDENS", "") not in ("", "0")

#: every sampler family exercised at the txt2img surface (the reference's
#: speed-table rows, /root/reference/scripts/spartan/worker.py:75-94)
SAMPLERS = [
    "Euler a", "Euler", "Heun", "DDIM", "LMS", "PLMS",
    "DPM2", "DPM2 a", "DPM++ 2M", "DPM++ 2M Karras", "DPM++ 2S a",
    "DPM++ SDE", "DPM fast", "DPM adaptive",
]


def _lora_sd():
    """Deterministic synthetic kohya adapter (local RNG: goldens must not
    depend on other modules' random-stream positions)."""
    rng = np.random.default_rng(0x7E8)  # the seed the goldens froze with
    sd = {}
    for module, d in [
        ("lora_unet_input_blocks_1_1_transformer_blocks_0_attn1_to_q", 32),
        ("lora_te_text_model_encoder_layers_0_self_attn_q_proj", 32),
    ]:
        sd[f"{module}.lora_down.weight"] = (
            rng.standard_normal((4, d)).astype(np.float32))
        sd[f"{module}.lora_up.weight"] = (
            rng.standard_normal((d, 4)).astype(np.float32))
        sd[f"{module}.alpha"] = np.float32(4)
    return sd


def _controlnet_params():
    """Deterministic NON-zero ControlNet weights: plain .init() leaves the
    zero-convolutions at exactly zero (the architecture's identity
    property), which would make every unit a no-op and the golden
    meaningless — so every leaf is refilled from a fixed PRNG stream."""
    cfg = TINY.unet
    shapes = ControlNet(cfg).init(
        jax.random.key(11),
        jnp.zeros((1, 4, 4, cfg.in_channels)), jnp.ones((1,)),
        jnp.zeros((1, 77, cfg.cross_attention_dim)),
        jnp.zeros((1, 32, 32, 3)))["params"]  # hint/8 == latent dims
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    key = jax.random.key(99)
    filled = [jax.random.normal(jax.random.fold_in(key, i), l.shape,
                                l.dtype) * 0.05
              for i, l in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, filled)


def _hint_b64():
    y, x = np.mgrid[0:32, 0:32]
    img = np.stack([x * 8, y * 8, (x + y) * 4], axis=-1).astype(np.uint8)
    return array_to_b64png(img)


@pytest.fixture(scope="module")
def engine():
    return Engine(TINY, init_params(TINY), chunk_size=4,
                  state=GenerationState(),
                  lora_provider={"gold": _lora_sd()}.get,
                  controlnet_provider=lambda name: _controlnet_params())


@pytest.fixture(scope="module")
def engine_xl():
    engines = {}
    eng = Engine(TINY_XL, init_params(TINY_XL), chunk_size=4,
                 state=GenerationState(),
                 engine_provider=engines.get)
    engines["refiner"] = Engine(TINY_REFINER, init_params(TINY_REFINER),
                                chunk_size=4, state=eng.state)
    return eng


def _load_goldens():
    if not os.path.exists(GOLDENS_PATH):
        return {}
    with open(GOLDENS_PATH) as f:
        return json.load(f)


def _check(case: str, result) -> None:
    got = [hashlib.sha256(img.encode()).hexdigest()[:32]
           for img in result.images]
    goldens = _load_goldens()
    if UPDATE:
        goldens[case] = got
        with open(GOLDENS_PATH, "w") as f:
            json.dump(goldens, f, indent=1, sort_keys=True)
        return
    assert case in goldens, (
        f"no golden recorded for '{case}' — run with SDTPU_UPDATE_GOLDENS=1 "
        "to freeze one")
    assert got == goldens[case], (
        f"golden mismatch for '{case}': the generation numerics changed. "
        "If intentional, regenerate via SDTPU_UPDATE_GOLDENS=1 and commit "
        "goldens.json with justification.")


class TestSamplerGoldens:
    @pytest.mark.parametrize("sampler", SAMPLERS)
    def test_txt2img(self, engine, sampler):
        p = GenerationPayload(prompt="a golden cow", steps=4, width=32,
                              height=32, seed=1234, sampler_name=sampler)
        _check(f"txt2img/{sampler}", engine.txt2img(p))


class TestPathGoldens:
    def test_txt2img_batch_seed_walk(self, engine):
        p = GenerationPayload(prompt="golden herd", steps=4, width=32,
                              height=32, seed=500, batch_size=3)
        _check("path/txt2img-batch3", engine.txt2img(p))

    def test_subseed_variation(self, engine):
        p = GenerationPayload(prompt="golden herd", steps=4, width=32,
                              height=32, seed=500, subseed=77,
                              subseed_strength=0.4)
        _check("path/subseed-variation", engine.txt2img(p))

    def test_img2img(self, engine):
        p = GenerationPayload(prompt="golden repaint", steps=6, width=32,
                              height=32, seed=42, init_images=[_hint_b64()],
                              denoising_strength=0.7)
        _check("path/img2img", engine.img2img(p))

    def test_inpaint_mask(self, engine):
        mask = np.zeros((32, 32, 3), np.uint8)
        mask[8:24, 8:24] = 255
        p = GenerationPayload(prompt="golden patch", steps=6, width=32,
                              height=32, seed=43, init_images=[_hint_b64()],
                              mask=array_to_b64png(mask),
                              denoising_strength=0.8)
        _check("path/inpaint", engine.img2img(p))

    def test_hires_fix(self, engine):
        p = GenerationPayload(prompt="golden zoom", steps=4, width=32,
                              height=32, seed=44, enable_hr=True,
                              hr_scale=2.0, hr_upscaler="Latent",
                              denoising_strength=0.6)
        _check("path/hires-latent-2x", engine.txt2img(p))

    def test_lora(self, engine):
        p = GenerationPayload(prompt="golden style <lora:gold:0.8>",
                              steps=4, width=32, height=32, seed=45)
        _check("path/lora", engine.txt2img(p))

    def test_controlnet(self, engine):
        unit = {"enabled": True, "image": _hint_b64(), "module": "canny",
                "model": "gold-cn", "weight": 1.0}
        p = GenerationPayload(
            prompt="golden control", steps=4, width=32, height=32, seed=46,
            alwayson_scripts={"controlnet": {"args": [unit]}})
        _check("path/controlnet-canny", engine.txt2img(p))

    def test_controlnet_adaptive(self, engine):
        """ControlNet under DPM adaptive with a WINDOWED unit (guidance
        gated host-side per attempt from log-sigma progress —
        engine._denoise_adaptive controls_at; VERDICT r4 item 4). The
        window excludes 0.5, the frozen fraction the in-graph gate sees:
        the unit must still fire early, then switch off — so the output
        differs BOTH from no-unit and from a full-window unit."""
        unit = {"enabled": True, "image": _hint_b64(), "module": "none",
                "model": "gold-cn", "weight": 1.0,
                "guidance_start": 0.0, "guidance_end": 0.3}
        p = GenerationPayload(
            prompt="golden control", steps=4, width=32, height=32, seed=48,
            sampler_name="DPM adaptive",
            alwayson_scripts={"controlnet": {"args": [unit]}})
        with_cn = engine.txt2img(p)
        plain = engine.txt2img(p.model_copy(
            update={"alwayson_scripts": {}}))
        assert with_cn.images != plain.images  # unit fired at all
        full = engine.txt2img(p.model_copy(update={"alwayson_scripts": {
            "controlnet": {"args": [{**unit, "guidance_end": 1.0}]}}}))
        assert with_cn.images != full.images   # window actually gates
        _check("path/controlnet-adaptive", with_cn)

    def test_xl_refiner(self, engine_xl):
        p = GenerationPayload(prompt="golden xl", steps=5, width=32,
                              height=32, seed=47,
                              refiner_checkpoint="refiner",
                              refiner_switch_at=0.6)
        _check("path/xl-base-refiner", engine_xl.txt2img(p))

"""Benchmark: the five BASELINE.md configs on the local TPU chip.

Protocol is the reference's own self-benchmark
(/root/reference/scripts/spartan/worker.py:506-575, shared.py:63-77):
2 warmup + 3 recorded samples, metric images-per-minute
(ipm = batch / (seconds/60), worker.py:522-533). Config #1 is the
reference's fixed "herd of cows" calibration payload; configs #2-#5 extend
the same protocol to BASELINE.md's target workloads:

  1  SD 1.5 txt2img 512x512, 20 steps Euler a, batch 1        (default)
  2  SDXL base+refiner txt2img 1024x1024, 30 steps, batch 8
  3  SD 1.5 img2img + ControlNet canny, 512x512, batch 4
  4  SDXL txt2img with 3 stacked LoRA adapters, batch 4
  5  SDXL hires-fix two-pass (1024 -> latent 2x -> img2img), batch 1

The default command (``python bench.py``, same as ``--config 1``) runs in
ONE process: it imports JAX once, requires ``jax.devices()[0].platform ==
"tpu"`` and exits non-zero before building any model when it finds no TPU
- it never measures a CPU. The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``
(runtime/mesh.py). ``chip_smoke.py`` is the quicker proof that the serving
path starts on the chip; this file is the protocol above.

Weights are zero-initialized architectures: throughput is
weight-value-independent (same graphs, same FLOPs), and the image has no
network egress to fetch trained checkpoints.

Prints exactly ONE JSON line on stdout. ``vs_baseline`` compares ipm
against a nominal 30 ipm for config #1 — the ballpark a single CUDA sdwui
worker of the reference's era sustains on that payload (the reference
publishes no numbers at all, BASELINE.md) — scaled for the other configs
by their step/pixel cost relative to config #1 using the reference's own
ETA arithmetic (worker.py:230-286). Extra keys: per-image p50 latency,
images/sec/chip, and a UNet-FLOPs MFU estimate against the chip's peak.

``SDTPU_BENCH_TINY=1`` is a CPU logic check (tiny families, tiny payloads,
same code path): its rows are prefixed ``tiny_`` and never carry a device
metric's name. The count-only modes (``--serving``, ``--ledger``, ...)
report compiles, hit rates and padding, and fall to that tiny form on a
CPU; the wall-clock fields they carry there are CPU timings, not speed.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import threading
import time

NOMINAL_SINGLE_GPU_IPM = 30.0


def tiny_env() -> bool:
    """One shared parse of SDTPU_BENCH_TINY (bench, sweep): tiny mode is a
    CPU logic-check, never a perf claim."""
    return os.environ.get("SDTPU_BENCH_TINY", "") not in ("", "0")

def _peak_for(device_kind: str):
    """bf16 peak FLOPs/s for a device kind — one table, owned by the perf
    ledger (obs/perf.py) so bench MFU and live /internal/perf MFU agree."""
    from stable_diffusion_webui_distributed_tpu.obs import perf as obs_perf

    return obs_perf.peak_flops_for(device_kind)


def _init_on_device(mod, *args, dtype=None, seed=None):
    """Params of ``mod`` created on the device in ONE jitted call: zeros,
    or flax's own initializers under ``jax.random.key(seed)`` when a seed
    is given (XLA drops the traced forward pass; only the initializers
    run)."""
    import jax
    import jax.numpy as jnp

    def use(s):
        return dtype if (dtype is not None
                         and jnp.issubdtype(s.dtype, jnp.floating)) else s.dtype

    # one jitted call: per-leaf creation would be ~1000 separate device
    # allocations and dispatches. Floating leaves are created directly in
    # the policy's storage dtype — materializing SDXL f32 (10.4 GB) and
    # casting after would transiently need ~15.6 GB, an OOM on a 16 GB v5e
    # (seen: round-3 sweep c2/c4/c5).
    if seed is None:
        shapes = jax.eval_shape(lambda: mod.init(jax.random.key(0), *args))
        build = lambda: jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, use(s)), shapes)
    else:
        build = lambda: jax.tree_util.tree_map(
            lambda x: x.astype(use(x)),
            mod.init(jax.random.key(seed), *args))
    return jax.jit(build)()["params"]


def family_params(family, dtype=None, seed=None):
    """The full component dict for one model family, made on the device:
    zero-initialized, or seeded random (``seed``) where the VALUES matter —
    with zeros every activation is zero and no wrong result can show."""
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.models.clip import CLIPTextModel
    from stable_diffusion_webui_distributed_tpu.models.unet import UNet
    from stable_diffusion_webui_distributed_tpu.models.vae import VAE

    def make(mod, *args):
        return _init_on_device(mod, *args, dtype=dtype, seed=seed)

    ids = jnp.zeros((1, 77), jnp.int32)
    ucfg = family.unet
    uargs = [jnp.zeros((2, 16, 16, ucfg.in_channels)), jnp.ones((2,)),
             jnp.zeros((2, 77, ucfg.cross_attention_dim))]
    if ucfg.addition_embed_dim:
        from stable_diffusion_webui_distributed_tpu.models.unet import (
            make_added_cond,
        )

        # 6 time ids for the base model, 5 for the refiner (aesthetic
        # score replaces target size) — derive from the projection width
        n_ids = ((ucfg.projection_input_dim - ucfg.addition_embed_dim)
                 // ucfg.addition_time_embed_dim)
        uargs.append(make_added_cond(
            jnp.zeros((2, ucfg.addition_embed_dim)),
            jnp.zeros((2, n_ids)), ucfg.addition_time_embed_dim))
    return {
        "text_encoder": make(CLIPTextModel(family.text_encoder), ids),
        "text_encoder_2": (make(CLIPTextModel(family.text_encoder_2), ids)
                           if family.text_encoder_2 else None),
        "unet": make(UNet(ucfg), *uargs),
        "vae": make(VAE(family.vae),
                    jnp.zeros((1, 64, 64, 3)), jax.random.key(1)),
    }


def _make_engine(family, refiner_family=None, lora_names=(),
                 controlnet=False):
    import jax
    import numpy as np

    from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
    from stable_diffusion_webui_distributed_tpu.runtime import dtypes

    # f32 only ever serves the tiny CPU logic checks: run_config refuses a
    # non-tiny run that finds no TPU before it gets here
    policy = dtypes.TPU if jax.devices()[0].platform != "cpu" else dtypes.F32

    t0 = time.time()
    params = family_params(family, dtype=policy.param_dtype)
    print(f"bench: zero-init {family.name} params in {time.time()-t0:.1f}s",
          file=sys.stderr)

    lora_provider = None
    if lora_names:
        loras = {n: _stack_lora(family, params, seed=i)
                 for i, n in enumerate(lora_names)}
        lora_provider = loras.get

    controlnet_provider = None
    if controlnet:
        from stable_diffusion_webui_distributed_tpu.models.controlnet import (
            ControlNet,
        )
        import jax.numpy as jnp

        ucfg = family.unet
        cargs = [jnp.zeros((1, 8, 8, ucfg.in_channels)), jnp.ones((1,)),
                 jnp.zeros((1, 77, ucfg.cross_attention_dim)),
                 jnp.zeros((1, 64, 64, 3))]
        cn_params = _init_on_device(ControlNet(ucfg), *cargs,
                                    dtype=policy.param_dtype)
        controlnet_provider = lambda name: cn_params

    engines = {}

    def engine_provider(name):
        return engines.get(name)

    chunk = int(os.environ.get("SDTPU_CHUNK", "10"))  # sweep-measured best
    engine = Engine(family, params, policy=policy,
                    model_name=f"{family.name}-bench", chunk_size=chunk,
                    lora_provider=lora_provider,
                    controlnet_provider=controlnet_provider,
                    engine_provider=engine_provider)
    if refiner_family is not None:
        engines["refiner"] = Engine(
            refiner_family,
            family_params(refiner_family, dtype=policy.param_dtype),
            policy=policy,
            model_name=f"{refiner_family.name}-bench")
    return engine


def _stack_lora(family, params, rank=8, seed=0):
    """Synthetic kohya-format adapter hitting every resolvable attention
    q projection of this family's UNet (valid keys found by probing the
    real key resolver, so this works for SD1.5, SDXL, and TINY alike)."""
    import numpy as np

    from stable_diffusion_webui_distributed_tpu.models import lora as lora_mod

    rng = np.random.default_rng(seed)
    sd = {}
    for i in range(12):
        for attn in ("attn1", "attn2"):
            mod = (f"lora_unet_input_blocks_{i}_1_transformer_blocks_0_"
                   f"{attn}_to_q")
            hit = lora_mod._resolve_unet_key(mod, family.unet)
            if hit is None:
                continue
            path, _ = hit
            leaf = params["unet"]
            for p in path:
                leaf = leaf[p]
            d = int(leaf["kernel"].shape[0])
            sd[f"{mod}.lora_down.weight"] = (
                rng.standard_normal((rank, d)).astype("float32") * 0.01)
            sd[f"{mod}.lora_up.weight"] = (
                rng.standard_normal((d, rank)).astype("float32") * 0.01)
            sd[f"{mod}.alpha"] = np.float32(rank)
    return sd


def _synth_b64_image(width, height):
    import numpy as np

    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        array_to_b64png,
    )

    y, x = np.mgrid[0:height, 0:width]
    img = np.stack([x % 256, y % 256, (x + y) % 256], axis=-1)
    return array_to_b64png(img.astype(np.uint8))


def _controlnet_scripts(image_b64):
    return {"controlnet": {"args": [{
        "enabled": True, "image": image_b64, "module": "canny",
        "model": "canny-bench", "weight": 1.0,
    }]}}


def _build_config(n, tiny):
    """-> (metric_name, engine, payload, flop_segments, rel_cost).

    ``flop_segments``: [(engine_for_unet, batch, width, height, steps)] used
    for the UNet cost-analysis MFU estimate. ``rel_cost`` scales the nominal
    config-#1 baseline ipm by the reference's ETA arithmetic
    (steps/20 * pixels/512^2, worker.py:230-286) for vs_baseline.
    """
    from stable_diffusion_webui_distributed_tpu.models import configs as C
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        BenchmarkPayload,
    )

    sd, xl, rf = ((C.TINY, C.TINY_XL, C.TINY_REFINER) if tiny
                  else (C.SD15, C.SDXL_BASE, C.SDXL_REFINER))
    size_sd = 64 if tiny else 512
    size_xl = 64 if tiny else 1024
    steps_sd = 4 if tiny else 20
    steps_xl = 4 if tiny else 30
    prefix = "tiny_" if tiny else ""

    bp = BenchmarkPayload()
    if n == 1:
        engine = _make_engine(sd)
        payload = GenerationPayload(
            prompt=bp.prompt, negative_prompt=bp.negative_prompt,
            steps=steps_sd, width=size_sd, height=size_sd,
            batch_size=1, sampler_name=bp.sampler_name, seed=1)
        name = ("tiny_logiccheck_ipm" if tiny
                else "sd15_512x512_20step_euler_a_ipm")
        return (name, engine, payload,
                [(engine, 1, size_sd, size_sd, steps_sd)], 1.0)
    if n == 2:
        batch = 2 if tiny else 8
        engine = _make_engine(xl, refiner_family=rf)
        payload = GenerationPayload(
            prompt=bp.prompt, steps=steps_xl, width=size_xl, height=size_xl,
            batch_size=batch, sampler_name=bp.sampler_name, seed=1,
            refiner_checkpoint="refiner", refiner_switch_at=0.8)
        switch = int(steps_xl * 0.8)
        segs = [(engine, batch, size_xl, size_xl, switch),
                (engine.engine_provider("refiner"), batch, size_xl, size_xl,
                 steps_xl - switch)]
        rel = (steps_xl / 20.0) * (size_xl / 512.0) ** 2
        return prefix + "sdxl_base_refiner_1024_b8_ipm", engine, payload, \
            segs, rel
    if n == 3:
        batch = 2 if tiny else 4
        engine = _make_engine(sd, controlnet=True)
        init = _synth_b64_image(size_sd, size_sd)
        payload = GenerationPayload(
            prompt=bp.prompt, steps=steps_sd, width=size_sd, height=size_sd,
            batch_size=batch, sampler_name=bp.sampler_name, seed=1,
            init_images=[init], denoising_strength=0.75,
            alwayson_scripts=_controlnet_scripts(init))
        # img2img runs ~denoising_strength * steps real steps
        eff_steps = max(1, int(steps_sd * 0.75))
        return prefix + "sd15_img2img_controlnet_b4_ipm", engine, payload, \
            [(engine, batch, size_sd, size_sd, eff_steps)], eff_steps / 20.0
    if n == 4:
        batch = 2 if tiny else 4
        names = ("bench0", "bench1", "bench2")
        engine = _make_engine(xl, lora_names=names)
        tags = " ".join(f"<lora:{t}:0.8>" for t in names)
        payload = GenerationPayload(
            prompt=f"{bp.prompt} {tags}", steps=steps_xl,
            width=size_xl, height=size_xl, batch_size=batch,
            sampler_name=bp.sampler_name, seed=1)
        rel = (steps_xl / 20.0) * (size_xl / 512.0) ** 2
        return prefix + "sdxl_lora_stack_b4_ipm", engine, payload, \
            [(engine, batch, size_xl, size_xl, steps_xl)], rel
    if n == 5:
        engine = _make_engine(xl)
        payload = GenerationPayload(
            prompt=bp.prompt, steps=steps_xl, width=size_xl, height=size_xl,
            batch_size=1, sampler_name=bp.sampler_name, seed=1,
            enable_hr=True, hr_scale=2.0, hr_upscaler="Latent",
            denoising_strength=0.7)
        hr = size_xl * 2
        hr_steps = max(1, int(steps_xl * 0.7))
        segs = [(engine, 1, size_xl, size_xl, steps_xl),
                (engine, 1, hr, hr, hr_steps)]
        rel = (steps_xl / 20.0) * (size_xl / 512.0) ** 2 \
            + (hr_steps / 20.0) * (hr / 512.0) ** 2
        return prefix + "sdxl_hires_2pass_ipm", engine, payload, segs, rel
    raise SystemExit(f"unknown config {n} (valid: 1-5)")


def _unet_flops_per_image(segments):
    """Analytic-by-compiler FLOPs: XLA cost analysis of one CFG UNet call
    per segment, x steps, / batch. Text encoder + VAE excluded (noted in
    stderr; the UNet dominates). None when cost analysis is unavailable."""
    import jax
    import jax.numpy as jnp

    total = 0.0
    for engine, batch, width, height, steps in segments:
        ucfg = engine.family.unet
        f = engine.family.vae_scale_factor
        lh, lw = height // f, width // f
        lat = jnp.zeros((2 * batch, lh, lw, ucfg.in_channels),
                        engine.policy.compute_dtype)
        t = jnp.ones((2 * batch,), jnp.float32)
        ctx = jnp.zeros((2 * batch, 77, ucfg.cross_attention_dim),
                        jnp.float32)
        args = [lat, t, ctx]
        if ucfg.addition_embed_dim:
            from stable_diffusion_webui_distributed_tpu.models.unet import (
                make_added_cond,
            )

            n_ids = ((ucfg.projection_input_dim - ucfg.addition_embed_dim)
                     // ucfg.addition_time_embed_dim)
            args.append(make_added_cond(
                jnp.zeros((2 * batch, ucfg.addition_embed_dim)),
                jnp.zeros((2 * batch, n_ids)), ucfg.addition_time_embed_dim))
        params = {"params": engine.params["unet"]}

        def call(p, *a):
            return engine.unet.apply(p, *a)

        cost = jax.jit(call).lower(params, *args).compile().cost_analysis()
        flops = float((cost or {}).get("flops", 0.0))
        if flops <= 0:
            return None
        total += flops * steps / batch
    return total


def run_config(n, tiny):
    import jax

    dev = jax.devices()[0]
    print(f"bench: device={dev.device_kind} platform={dev.platform} "
          f"config={n} tiny={tiny}", file=sys.stderr)
    if not tiny and dev.platform != "tpu":
        # images per minute is a device metric: never measure a CPU
        raise SystemExit(
            f"bench: FATAL: config {n} needs a TPU, found platform "
            f"{dev.platform!r} ({dev.device_kind}); SDTPU_BENCH_TINY=1 is "
            "the CPU logic check")

    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        RECORDED_SAMPLES,
        WARMUP_SAMPLES,
    )

    metric, engine, payload, segments, rel_cost = _build_config(n, tiny)
    run = engine.img2img if payload.init_images else engine.txt2img

    samples = []
    for i in range(WARMUP_SAMPLES + RECORDED_SAMPLES):
        t0 = time.time()
        result = run(payload)
        elapsed = time.time() - t0
        assert len(result.images) == payload.batch_size, \
            f"expected {payload.batch_size} images, got {len(result.images)}"
        kind = "warmup" if i < WARMUP_SAMPLES else "sample"
        print(f"bench: {kind} {i}: {elapsed:.2f}s "
              f"({elapsed / payload.batch_size:.2f}s/image)", file=sys.stderr)
        if i >= WARMUP_SAMPLES:
            samples.append(elapsed)

    avg = sum(samples) / len(samples)
    ipm = payload.batch_size / (avg / 60.0)
    # per-IMAGE p50: median request wall-time / batch (BASELINE.md metric)
    p50_image = sorted(samples)[(len(samples) - 1) // 2] / payload.batch_size

    out = {
        "metric": metric,
        "value": round(ipm, 2),
        "unit": "images/min",
        "vs_baseline": round(ipm / (NOMINAL_SINGLE_GPU_IPM / rel_cost), 3),
        "p50_image_latency_s": round(p50_image, 3),
        "images_per_sec_chip": round(ipm / 60.0, 4),
        "config": n,
        "device": dev.device_kind,
    }
    flops_per_img = _unet_flops_per_image(segments)
    peak = _peak_for(dev.device_kind)
    if flops_per_img and peak:
        from stable_diffusion_webui_distributed_tpu.runtime import dtypes

        # int8 cells: the MXU's int8 rate is 2x bf16 on these chips,
        # so MFU against the bf16 peak would read >100%. State the
        # basis explicitly and scale the denominator.
        basis = "bf16"
        lin = getattr(dtypes.TPU, "unet_int8", False)
        cnv = getattr(dtypes.TPU, "unet_int8_conv", False)
        if lin and cnv:
            peak, basis = peak * 2, "int8"
        elif lin or cnv:
            # partial quantization: conv/linear FLOPs still run at the
            # bf16 rate, so the bf16 peak stays the denominator (the
            # number is comparable to bf16 controls; the label warns
            # it can exceed 1 on the quantized fraction)
            basis = "bf16-partial-int8"
        out["unet_mfu"] = round(
            flops_per_img * (ipm / 60.0) / peak, 4)
        out["mfu_peak_basis"] = basis
        print(f"bench: unet flops/image={flops_per_img:.3e}, "
              f"peak={peak:.0e} FLOPs/s [{basis}] (text encoder + VAE "
              "excluded from MFU)", file=sys.stderr)
    return out


def _psnr_b64(imgs_a, imgs_b):
    """Mean PSNR (dB) across paired base64-PNG image lists."""
    import numpy as np

    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        b64png_to_array,
    )

    vals = []
    for a64, b64 in zip(imgs_a, imgs_b):
        a = b64png_to_array(a64).astype(np.float64)
        b = b64png_to_array(b64).astype(np.float64)
        mse = float(np.mean((a - b) ** 2))
        vals.append(99.0 if mse == 0 else 10.0 * np.log10(255.0**2 / mse))
    return sum(vals) / max(1, len(vals))


def _ssim_b64(imgs_a, imgs_b, window=7):
    """Mean SSIM across paired base64-PNG image lists (luma, uniform
    window — same metric as tests/quality.py)."""
    import numpy as np

    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        b64png_to_array,
    )

    def gray(img):
        img = np.asarray(img, dtype=np.float64)
        return img @ np.array([0.299, 0.587, 0.114]) if img.ndim == 3 else img

    vals = []
    for a64, b64 in zip(imgs_a, imgs_b):
        ga, gb = gray(b64png_to_array(a64)), gray(b64png_to_array(b64))
        wa = np.lib.stride_tricks.sliding_window_view(ga, (window, window))
        wb = np.lib.stride_tricks.sliding_window_view(gb, (window, window))
        mu_a, mu_b = wa.mean(axis=(-1, -2)), wb.mean(axis=(-1, -2))
        var_a, var_b = wa.var(axis=(-1, -2)), wb.var(axis=(-1, -2))
        cov = (wa * wb).mean(axis=(-1, -2)) - mu_a * mu_b
        c1, c2 = (0.01 * 255.0) ** 2, (0.03 * 255.0) ** 2
        s = ((2 * mu_a * mu_b + c1) * (2 * cov + c2)) / (
            (mu_a**2 + mu_b**2 + c1) * (var_a + var_b + c2))
        vals.append(float(s.mean()))
    return sum(vals) / max(1, len(vals))


def run_int8(tiny):
    """Int8 x step-cache grid (ISSUE 7): ONE random-weights tiny engine
    serves every cell through the per-request ``precision`` override
    (pipeline/precision.py) — the same engine/variant-module path
    production dispatch uses. Each int8 cell reports chunk compile
    counts, and PSNR/SSIM against the bf16 cell at the SAME cadence, so
    quantization error is isolated from step-cache error. Quality is the
    platform-independent part; the 2x MXU rate is stated as peak basis,
    not measured on CPU. Writes BENCH_int8.json."""
    import jax

    from stable_diffusion_webui_distributed_tpu.models import configs as C
    from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
        GenerationState,
    )
    from stable_diffusion_webui_distributed_tpu.samplers import (
        kdiffusion as kd,
    )
    from stable_diffusion_webui_distributed_tpu.serving.metrics import METRICS

    dev = jax.devices()[0]
    engine = Engine(C.TINY, family_params(C.TINY, seed=0), chunk_size=4,
                    state=GenerationState())
    p = GenerationPayload(prompt="a herd of cows", steps=8, width=32,
                          height=32, batch_size=2, seed=42)
    spec = kd.resolve_sampler(p.sampler_name)
    cutoff = float(kd.build_sigmas(spec, engine.schedule,
                                   p.steps)[p.steps // 2])

    def cell(precision, cadence):
        q = p.model_copy()
        q.precision = precision
        if cadence > 1:
            q.override_settings = {"deepcache": cadence,
                                   "cfg_cutoff": cutoff}
        METRICS.clear()
        r = engine.txt2img(q)
        s = METRICS.summary()
        return r, {
            "cell": f"c{cadence}-{precision or 'bf16'}",
            "precision": precision or "bf16",
            "cadence": cadence,
            "chunk_executables": s["compiles"].get("chunk", 0),
        }

    cells = []
    bf16_by_cadence = {}
    for cadence in (1, 3):
        base_r, base_c = cell("", cadence)  # bf16 control
        bf16_by_cadence[cadence] = base_r
        cells.append(base_c)
        for precision in ("int8", "int8+conv"):
            r, c = cell(precision, cadence)
            c["psnr_db_vs_bf16"] = round(
                _psnr_b64(r.images, base_r.images), 2)
            c["ssim_vs_bf16"] = round(
                _ssim_b64(r.images, base_r.images), 4)
            cells.append(c)
            print(f"bench: int8 {c['cell']}: "
                  f"psnr {c['psnr_db_vs_bf16']} dB, "
                  f"ssim {c['ssim_vs_bf16']}", file=sys.stderr)

    quantized = [c for c in cells if c["precision"] != "bf16"]
    min_psnr = min(c["psnr_db_vs_bf16"] for c in quantized)
    min_ssim = min(c["ssim_vs_bf16"] for c in quantized)
    out = {
        "metric": ("tiny_" if tiny or dev.platform == "cpu" else "")
        + "int8_min_psnr_db",
        "value": min_psnr,
        "unit": "db_vs_bf16_same_cadence",
        "vs_baseline": None,
        # the tier-1 floors (tests/test_quality_int8.py); the grid must
        # clear them at every step-cache rung or the fleet's int8 degrade
        # rung is trading SLO misses for broken images
        "psnr_floor_db": 20.0,
        "ssim_floor": 0.6,
        "min_ssim": min_ssim,
        "pass": bool(min_psnr >= 20.0 and min_ssim >= 0.6),
        # why int8 at all: the MXU int8 rate is 2x bf16 on v5e/v4 — the
        # FLOPs/image above run against the doubled peak (bench --config
        # MFU cells state the same basis)
        "mxu_peak_ratio_int8_vs_bf16": 2.0,
        "steps": p.steps,
        "cfg_cutoff_sigma": round(cutoff, 4),
        "family": C.TINY.name,
        "cells": cells,
        "device": dev.device_kind,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_int8.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    return out


def run_serving(tiny):
    """Serving-layer microbench: 8 concurrent mixed-shape requests through
    the continuous-batching dispatcher. The headline value is the coalesce
    factor (requests per device dispatch); chunk-compile count and bucket
    hit rate ride along. Counts, not wall-clock — meaningful on CPU."""
    import jax

    from stable_diffusion_webui_distributed_tpu.models import configs as C
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
        ShapeBucketer,
    )
    from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
        ServingDispatcher,
    )
    from stable_diffusion_webui_distributed_tpu.serving.metrics import METRICS

    dev = jax.devices()[0]
    if tiny or dev.platform == "cpu":
        ladder, steps = [(64, 64), (96, 96)], 4
        shapes = [(64, 64), (48, 64), (96, 96), (80, 80)]
        family = C.TINY
    else:
        ladder, steps = [(512, 512), (768, 768)], 20
        shapes = [(512, 512), (448, 512), (768, 768), (640, 640)]
        family = C.SD15
    engine = _make_engine(family)
    # one batch bucket: any partition of the 8 requests into groups pads
    # to the same compiled batch, so compile count == shape-ladder size
    bucketer = ShapeBucketer(shapes=ladder, batches=[4])
    dispatcher = ServingDispatcher(engine, bucketer=bucketer, window=0.5)

    METRICS.clear()
    results, errs = [], []

    def submit(i, w, h):
        p = GenerationPayload(prompt=f"bench cow {i % 4}", steps=steps,
                              width=w, height=h, seed=100 + i,
                              sampler_name="Euler a")
        try:
            results.append(dispatcher.submit(p))
        except Exception as e:  # noqa: BLE001 — reported in the JSON line
            errs.append(repr(e))

    t0 = time.time()
    threads = [threading.Thread(target=submit, args=(i, *shapes[i % 4]))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.time() - t0
    if errs:
        _dump_flightrec("serving")
    s = METRICS.summary()
    images = sum(len(r.images) for r in results)
    return {
        "metric": ("tiny_" if tiny or dev.platform == "cpu" else "")
        + "serving_coalesce_factor",
        "value": round(s["coalesce_factor"] or 0.0, 3),
        "unit": "requests/dispatch",
        "vs_baseline": None,
        "chunk_compiles": s["compiles"].get("chunk", 0),
        "bucket_hit_rate": s["bucket_hit_rate"],
        "dispatches": s["dispatches"],
        "coalesced_dispatches": s["coalesced_dispatches"],
        "avg_queue_wait_s": round(s["avg_queue_wait_s"] or 0.0, 4),
        "avg_padding_ratio": round(s["avg_padding_ratio"] or 1.0, 4),
        "requests": 8,
        "raw_shapes": len(set(shapes)),
        "bucket_ladder": [f"{w}x{h}" for w, h in bucketer.shapes],
        "images": images,
        "errors": errs,
        "wall_s": round(wall, 2),
        "device": dev.device_kind,
    }


def run_ragged(tiny):
    """--ragged: ragged-dispatch microbench (SDTPU_RAGGED). Three phases
    over one mixed-HEIGHT workload (8 requests, 4 heights, one width):

    - fine_ladder: classic dispatch, one ladder entry per height — zero
      padding bought with one chunk compile PER height;
    - coarse_classic: classic dispatch, one coarse bucket — one compile,
      every short request pays the full ladder-padding tax;
    - ragged: the same coarse bucket under SDTPU_RAGGED — one compile AND
      ~no compute padding (true row counts ride as traced data, the
      attention kernel masks the tail).

    Counts and ratios, not wall-clock — meaningful on CPU. Writes
    BENCH_ragged.json and appends a "ragged" row to BENCH_LEDGER.jsonl
    (tools/bench_compare.py gates avg_padding_ratio, token_padding_ratio,
    chunk_compiles and the census alarm)."""
    import jax

    from stable_diffusion_webui_distributed_tpu.models import configs as C
    from stable_diffusion_webui_distributed_tpu.obs import perf as obs_perf
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
        ShapeBucketer,
    )
    from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
        ServingDispatcher,
    )
    from stable_diffusion_webui_distributed_tpu.serving.metrics import METRICS

    dev = jax.devices()[0]
    if tiny or dev.platform == "cpu":
        bucket_w, heights, steps = 64, [64, 48, 32, 16], 4
        family = C.TINY
    else:
        bucket_w, heights, steps = 512, [512, 384, 256, 128], 20
        family = C.SD15
    fine = [(bucket_w, hh) for hh in heights]
    coarse = [(bucket_w, max(heights))]

    def phase(ladder, ragged):
        engine = _make_engine(family)
        bucketer = ShapeBucketer(shapes=ladder, batches=[1])
        dispatcher = ServingDispatcher(engine, bucketer=bucketer,
                                       window=0.0)
        METRICS.clear()
        obs_perf.LEDGER.clear()
        errs = []
        with _EnvPatch(SDTPU_PERF="1",
                       SDTPU_RAGGED="1" if ragged else None):
            for i in range(8):
                hh = heights[i % len(heights)]
                p = GenerationPayload(
                    prompt="bench ragged cow " + "moo " * (i % 4),
                    steps=steps, width=bucket_w, height=hh, seed=300 + i,
                    sampler_name="Euler a")
                try:
                    dispatcher.submit(p)
                except Exception as e:  # noqa: BLE001 — in the JSON line
                    errs.append(repr(e))
            census = obs_perf.executables_census(engine)
        s = METRICS.summary()
        groups = obs_perf.LEDGER.summary()["groups"]
        tok = [g["token_padding_ratio"] for g in groups
               if g.get("token_padding_ratio")]
        return {
            "chunk_compiles": s["compiles"].get("chunk", 0),
            "avg_padding_ratio": round(s["avg_padding_ratio"] or 1.0, 4),
            "dispatches": s["dispatches"],
            "token_padding_ratio": round(sum(tok) / len(tok), 4)
            if tok else None,
            "census_alarm": bool(census["alarm"]),
            "errors": errs,
        }

    t0 = time.time()
    fine_classic = phase(fine, ragged=False)
    coarse_classic = phase(coarse, ragged=False)
    ragged = phase(coarse, ragged=True)
    wall = time.time() - t0
    if ragged["errors"] or fine_classic["errors"] \
            or coarse_classic["errors"]:
        _dump_flightrec("ragged")
    out = {
        "metric": ("tiny_" if tiny or dev.platform == "cpu" else "")
        + "ragged_padding_ratio",
        "value": ragged["avg_padding_ratio"],
        "unit": "padded_px/true_px",
        "vs_baseline": coarse_classic["avg_padding_ratio"],
        "chunk_compiles": ragged["chunk_compiles"],
        "chunk_compiles_fine_ladder": fine_classic["chunk_compiles"],
        "chunk_compiles_coarse_classic": coarse_classic["chunk_compiles"],
        "avg_padding_ratio": ragged["avg_padding_ratio"],
        "classic_coarse_padding_ratio":
            coarse_classic["avg_padding_ratio"],
        "token_padding_ratio": ragged["token_padding_ratio"],
        "census_alarm": int(ragged["census_alarm"]),
        "phases": {"fine_ladder": fine_classic,
                   "coarse_classic": coarse_classic, "ragged": ragged},
        "requests": 8,
        "bucket": f"{bucket_w}x{max(heights)}",
        "heights": heights,
        "wall_s": round(wall, 2),
        "device": dev.device_kind,
        "errors": (fine_classic["errors"] + coarse_classic["errors"]
                   + ragged["errors"]),
    }
    base = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(base, "BENCH_ragged.json"), "w",
              encoding="utf-8") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    row = _ledger_row("ragged", {
        "chunk_compiles": ragged["chunk_compiles"],
        "chunk_compiles_fine_ladder": fine_classic["chunk_compiles"],
        "avg_padding_ratio": ragged["avg_padding_ratio"],
        "classic_coarse_padding_ratio":
            coarse_classic["avg_padding_ratio"],
        "token_padding_ratio": ragged["token_padding_ratio"],
        "census_alarm": int(ragged["census_alarm"]),
    }, dev.device_kind, tiny, time.time())
    with open(os.path.join(base, "BENCH_LEDGER.jsonl"), "a",
              encoding="utf-8") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")
    return out


def _percentile(samples, q):
    """Nearest-rank percentile over a list of seconds (0.0 when empty)."""
    if not samples:
        return 0.0
    ordered = sorted(samples)
    idx = max(0, min(len(ordered) - 1,
                     int(math.ceil(q * len(ordered))) - 1))
    return ordered[idx]


class _EnvPatch:
    """Set env knobs for one bench phase and restore them exactly."""

    def __init__(self, **kv):
        self.kv = kv
        self.saved = {}

    def __enter__(self):
        for k, v in self.kv.items():
            self.saved[k] = os.environ.get(k)
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = str(v)
        return self

    def __exit__(self, *exc):
        for k, old in self.saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old
        return False


def run_cache(tiny):
    """--cache: caching-tier microbench over a redundant request mix
    (SDTPU_CACHE=1). Four phases through the serving dispatcher: a cold
    set of distinct prompts sharing one negative (embed dedupe), byte-
    exact repeats (result dedupe at admission — zero new dispatches), a
    concurrent identical burst (single-flight collapse), and prefix
    pairs that diverge only in a post-prefix field (mid-denoise resume
    from the chunk-boundary carry). Reports per-layer hit rates, the
    FLOPs/image delta between a full and a resumed denoise, and e2e
    latency percentiles. Counts and FLOP ratios are structural —
    meaningful on CPU. Writes BENCH_cache.json and appends a "cache"
    row to BENCH_LEDGER.jsonl."""
    import jax

    from stable_diffusion_webui_distributed_tpu import cache
    from stable_diffusion_webui_distributed_tpu.models import configs as C
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
        ShapeBucketer,
    )
    from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
        ServingDispatcher,
    )
    from stable_diffusion_webui_distributed_tpu.serving.metrics import METRICS

    dev = jax.devices()[0]
    if tiny or dev.platform == "cpu":
        family, size, steps = C.TINY, 64, 8
    else:
        family, size, steps = C.SD15, 512, 16

    # chunk 4 puts a capture boundary at the resume step (steps/2) and
    # keeps the resumed run's chunk partition identical to a continuous
    # run from that boundary — the byte-identity invariant.
    with _EnvPatch(SDTPU_CACHE="1", SDTPU_CHUNK="4"):
        engine = _make_engine(family)
        bucketer = ShapeBucketer(shapes=[(size, size)], batches=[1])
        dispatcher = ServingDispatcher(engine, bucketer=bucketer, window=0.0)
        cache.clear_all()
        METRICS.clear()

        lat, lat_lock, errs = [], threading.Lock(), []

        def go(p):
            t0 = time.time()
            try:
                dispatcher.submit(p)
            except Exception as e:  # noqa: BLE001 — reported in the JSON
                errs.append(repr(e))
                return
            with lat_lock:
                lat.append(time.time() - t0)

        def payload(tag, seed, **kw):
            return GenerationPayload(
                prompt=f"bench cache cow {tag}",
                negative_prompt="blurry, low quality, jpeg artifacts",
                steps=steps, width=size, height=size, seed=seed,
                sampler_name="Euler a", **kw)

        # phase 1 — cold: distinct prompts, one shared negative. The
        # negative half hits from the second request on.
        distinct = [payload(i, 200 + i) for i in range(6)]
        for p in distinct:
            go(p.model_copy(deep=True))

        # phase 2 — byte-exact repeats: served from the result cache at
        # admission; no new dispatch, no encode, no denoise.
        for p in distinct:
            go(p.model_copy(deep=True))

        # phase 3 — concurrent identical burst: single-flight elects one
        # leader, the rest block on its flight and share the result.
        burst = payload("burst", 999)
        threads = [threading.Thread(target=go,
                                    args=(burst.model_copy(deep=True),))
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        # phase 4 — prefix pairs: denoising_strength is inert for plain
        # txt2img but splits the result key, so the second request of
        # each pair misses result dedupe and instead resumes mid-denoise
        # from the carry its twin captured at the chunk boundary.
        for j in range(3):
            first = payload(f"prefix{j}", 500 + j, denoising_strength=0.4)
            second = payload(f"prefix{j}", 500 + j, denoising_strength=0.7)
            go(first.model_copy(deep=True))
            go(second.model_copy(deep=True))

        summ = cache.summary()
        cache.clear_all()
    if errs:
        _dump_flightrec("cache")

    embed = summ["embed"]
    pos, neg = embed["positive"], embed["negative"]
    e_hits = pos["hits"] + neg["hits"]
    e_total = e_hits + pos["misses"] + neg["misses"]
    res = summ["result"]
    out = {
        "metric": ("tiny_" if tiny or dev.platform == "cpu" else "")
        + "cache_embed_hit_rate",
        "value": round((e_hits / e_total) if e_total else 0.0, 3),
        "unit": "fraction",
        "vs_baseline": None,
        "embed_cache_hit_rate": round((e_hits / e_total) if e_total
                                      else 0.0, 3),
        "embed_positive": pos,
        "embed_negative": neg,
        "result_dedupe_hit_rate": round(res["hit_rate"], 3),
        "result_dedupe_hits": res["hits"],
        "single_flight": res["single_flight"],
        "prefix_captured": summ["prefix"]["captured"],
        "prefix_resumed": summ["prefix"]["resumed"],
        "e2e_p50_s": round(_percentile(lat, 0.50), 4),
        "e2e_p95_s": round(_percentile(lat, 0.95), 4),
        "requests": len(lat),
        "errors": errs,
        "device": dev.device_kind,
    }
    base = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(base, "BENCH_cache.json"), "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    row = _ledger_row("cache", {
        "embed_cache_hit_rate": out["embed_cache_hit_rate"],
        "result_dedupe_hit_rate": out["result_dedupe_hit_rate"],
        "prefix_resumed": out["prefix_resumed"],
        "single_flight_joined": res["single_flight"].get("joined", 0),
    }, dev.device_kind, tiny, time.time())
    with open(os.path.join(base, "BENCH_LEDGER.jsonl"), "a",
              encoding="utf-8") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")
    return out


def run_lora(tiny):
    """--lora: adapter-churn microbench (BENCH_lora.json + a "lora"
    ledger row). Two arms cycle the same four synthetic adapters through
    the serving dispatcher: the merged baseline (host merge + epoch bump
    per switch) and the traced arm (SDTPU_LORA_TRACED=1 — factors ride
    as jit arguments on the rank/slot ladder). The numbers are
    structural, so CPU runs are meaningful: the traced churn phase must
    mint ZERO new chunk executables and perform ZERO host merges while
    the merged arm pays >= 1 merge per switch; the executables census
    must stay silent; and the embed cache must survive every switch
    (unet-only adapters leave conditioning untouched)."""
    import jax

    from stable_diffusion_webui_distributed_tpu import cache
    from stable_diffusion_webui_distributed_tpu.models import configs as C
    from stable_diffusion_webui_distributed_tpu.obs import perf as obs_perf
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
        ShapeBucketer,
    )
    from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
        ServingDispatcher,
    )
    from stable_diffusion_webui_distributed_tpu.serving.metrics import METRICS

    dev = jax.devices()[0]
    if tiny or dev.platform == "cpu":
        family, size, steps = C.TINY, 64, 8
    else:
        family, size, steps = C.SD15, 512, 16
    names = ("la", "lb", "lc", "ld")

    def chunk_compiles():
        return METRICS.summary()["compiles"].get("chunk", 0)

    def arm(traced):
        with _EnvPatch(SDTPU_LORA_TRACED="1" if traced else None,
                       SDTPU_CACHE="1", SDTPU_CHUNK="4"):
            engine = _make_engine(family, lora_names=names)
            bucketer = ShapeBucketer(shapes=[(size, size)], batches=[1])
            dispatcher = ServingDispatcher(engine, bucketer=bucketer,
                                           window=0.0)
            cache.clear_all()
            METRICS.clear()
            errs, lat = [], []

            def go(p):
                t0 = time.time()
                try:
                    dispatcher.submit(p.model_copy(deep=True))
                except Exception as e:  # noqa: BLE001 — reported in JSON
                    errs.append(repr(e))
                    return
                lat.append(time.time() - t0)

            def payload(seed, adapter=None):
                tag = f" <lora:{adapter}:0.8>" if adapter else ""
                return GenerationPayload(
                    prompt=f"bench lora llama{tag}",
                    negative_prompt="blurry", steps=steps, width=size,
                    height=size, seed=seed, sampler_name="Euler a")

            # phase 1 — adapterless baseline: mints the plain bucket
            base = payload(100)
            go(base)
            compiles_base = chunk_compiles()
            # phase 2 — first adapter: the traced arm mints the ladder
            # cell's executables exactly once; the merged arm reuses the
            # plain ones (merge mutates params, not the compile key)
            go(payload(101, names[0]))
            compiles_warm = chunk_compiles()
            merges_warm = engine._lora_merge_total
            # phase 3 — churn: two full cycles over all four adapters.
            # THE claim under test: switches are compile-free and (on
            # the traced arm) merge-free.
            switches = 0
            for cyc in range(2):
                for i, n in enumerate(names[1:] + names[:1]):
                    go(payload(110 + 10 * cyc + i, n))
                    switches += 1
            compiles_churn = chunk_compiles() - compiles_warm
            merges_churn = engine._lora_merge_total - merges_warm
            # phase 4 — cache survival: the pre-churn baseline request,
            # byte-exact, must still hit result dedupe (no epoch bump
            # invalidated it), and every churn request after the first
            # re-used its embed entry (adapters here are unet-only)
            res_before = cache.summary()["result"]["hits"]
            go(base)
            result_survived = cache.summary()["result"]["hits"] > res_before
            emb = cache.summary()["embed"]
            e_hits = emb["positive"]["hits"] + emb["negative"]["hits"]
            e_total = e_hits + emb["positive"]["misses"] + \
                emb["negative"]["misses"]
            census = obs_perf.census_from_keys(engine.executable_keys())
            cache.clear_all()
        return {
            "chunk_compiles_baseline": compiles_base,
            "chunk_compiles_first_adapter": compiles_warm - compiles_base,
            "chunk_compiles_churn": compiles_churn,
            "merges_churn": merges_churn,
            "merges_total": engine._lora_merge_total,
            "switches": switches,
            "embed_hit_rate": round((e_hits / e_total) if e_total
                                    else 0.0, 3),
            "result_cache_survived_churn": bool(result_survived),
            "census_alarm": int(bool(census["alarm"])),
            "e2e_p50_s": round(_percentile(lat, 0.50), 4),
            "errors": errs,
        }

    merged = arm(traced=False)
    traced = arm(traced=True)
    out = {
        "metric": ("tiny_" if tiny or dev.platform == "cpu" else "")
        + "lora_traced_chunk_compiles",
        "value": traced["chunk_compiles_churn"],
        "unit": "count",
        "vs_baseline": merged["merges_churn"],
        "merged": merged,
        "traced": traced,
        "device": dev.device_kind,
    }
    if merged["errors"] or traced["errors"]:
        _dump_flightrec("lora")
    base = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(base, "BENCH_lora.json"), "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    row = _ledger_row("lora", {
        "lora_traced_chunk_compiles": traced["chunk_compiles_churn"],
        "lora_traced_merges": traced["merges_churn"],
        "lora_merged_merges_per_switch": round(
            merged["merges_churn"] / merged["switches"], 3)
        if merged["switches"] else 0.0,
        "lora_embed_hit_rate": traced["embed_hit_rate"],
        "census_alarm": traced["census_alarm"],
    }, dev.device_kind, tiny, time.time())
    with open(os.path.join(base, "BENCH_LEDGER.jsonl"), "a",
              encoding="utf-8") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")
    return out


def _fleet_workload(tiny, dev):
    """The mixed-tenant open-loop arrival plan: (delay_s, tenant, class,
    payload-kwargs) per request. Interactive traffic is Poisson (seeded —
    the fleet and FIFO phases replay identical arrivals), batch is an
    immediate backlog, best-effort is an immediate flood."""
    import random

    if tiny or dev.platform == "cpu":
        size, i_steps, b_steps = 64, 4, 8
    else:
        size, i_steps, b_steps = 512, 20, 40
    rng = random.Random(7)
    plan = []
    t = 0.0
    for i in range(6):  # interactive: Poisson arrivals, ~80ms mean gap
        t += rng.expovariate(1.0 / 0.08)
        plan.append((t, "alice", "interactive",
                     dict(steps=i_steps, seed=500 + i)))
    for i in range(3):  # batch: backlog waiting at t=0
        plan.append((0.0, "batch-corp", "batch",
                     dict(steps=b_steps, batch_size=2, seed=600 + i)))
    for i in range(10):  # best-effort: flood at t=0 (quota fodder)
        plan.append((0.0, "scraper", "best_effort",
                     dict(steps=i_steps, seed=700 + i)))
    return size, plan


def _fleet_phase(dispatcher, plan, size):
    """Replay the arrival plan open-loop (threads fire at their arrival
    times regardless of completions) and collect per-request outcomes."""
    from stable_diffusion_webui_distributed_tpu.fleet.admission import (
        FleetRejected,
    )
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )

    records, errs = [], []
    lock = threading.Lock()
    start = time.time()

    def fire(delay, tenant, cls, kw):
        time.sleep(max(0.0, delay))
        p = GenerationPayload(prompt=f"fleet {cls}", width=size, height=size,
                              sampler_name="Euler a", tenant=tenant,
                              priority_class=cls, **kw)
        t0 = time.time()
        status = "ok"
        try:
            dispatcher.submit(p)
        except FleetRejected as e:
            status = e.reason  # "quota" | "slo"
        except Exception as e:  # noqa: BLE001 — reported in the JSON line
            status = "error"
            with lock:
                errs.append(repr(e))
        with lock:
            records.append({"class": cls, "tenant": tenant,
                            "status": status,
                            "latency_s": time.time() - t0})

    threads = [threading.Thread(target=fire, args=req) for req in plan]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records, errs, time.time() - start


def _fleet_class_stats(records, slo_s):
    out = {}
    for cls in ("interactive", "batch", "best_effort"):
        rows = [r for r in records if r["class"] == cls]
        done = [r["latency_s"] for r in rows if r["status"] == "ok"]
        stats = {
            "requests": len(rows),
            "completed": len(done),
            "throttled": sum(1 for r in rows if r["status"] == "quota"),
            "rejected": sum(1 for r in rows if r["status"] == "slo"),
            "p50_s": round(_percentile(done, 0.50), 4),
            "p95_s": round(_percentile(done, 0.95), 4),
        }
        if cls == "interactive":
            stats["slo_s"] = slo_s
            stats["slo_attainment"] = round(
                sum(1 for s in done if s <= slo_s) / len(done), 4) \
                if done else None
        out[cls] = stats
    return out


def run_fleet(tiny):
    """Fleet-scheduler microbench: one mixed-tenant open-loop workload
    (Poisson interactive + batch backlog + best-effort flood) replayed
    twice — FIFO baseline, then the weighted-fair fleet gate with quotas
    and chunk-boundary preemption. Reports per-class p50/p95 latency,
    interactive SLO attainment, preemption count and the quota-throttle
    rate; writes the full comparison to BENCH_fleet.json."""
    import jax

    from stable_diffusion_webui_distributed_tpu.models import configs as C
    from stable_diffusion_webui_distributed_tpu.obs import (
        prometheus as obs_prom,
    )
    from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
        ShapeBucketer,
    )
    from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
        ServingDispatcher,
    )
    from stable_diffusion_webui_distributed_tpu.serving.metrics import METRICS

    dev = jax.devices()[0]
    cpu = tiny or dev.platform == "cpu"
    family = C.TINY if cpu else C.SD15
    slo_s = 10.0 if cpu else 30.0
    size, plan = _fleet_workload(tiny, dev)

    # short chunks give the preemptible batch jobs several yield points
    with _EnvPatch(SDTPU_CHUNK="2" if cpu else "5"):
        engine = _make_engine(family)
    bucketer = ShapeBucketer(shapes=[(size, size)], batches=[4])

    # warm every executable the workload touches so neither phase pays
    # compile time (the FIFO phase runs first and would otherwise eat it)
    with _EnvPatch(SDTPU_FLEET="0"):
        warm = ServingDispatcher(engine, bucketer=bucketer, window=0.0)
        warm_plan = [(0.0, t, c, kw) for (_d, t, c, kw) in
                     {(r[2]): r for r in plan}.values()]
        _fleet_phase(warm, warm_plan, size)

    # phase 1: FIFO baseline — the pre-fleet dispatcher, same arrivals
    with _EnvPatch(SDTPU_FLEET="0"):
        fifo = ServingDispatcher(engine, bucketer=bucketer, window=0.05)
        METRICS.clear()
        fifo_records, fifo_errs, fifo_wall = _fleet_phase(fifo, plan, size)

    # phase 2: the fleet gate — WFQ + quotas + zero-quantum preemption
    with _EnvPatch(SDTPU_FLEET="1", SDTPU_FLEET_QUANTUM_S="0",
                   SDTPU_QUOTA_IPM="240", SDTPU_QUOTA_BURST="8"):
        obs_prom.clear_histograms()
        fleet = ServingDispatcher(engine, bucketer=bucketer, window=0.05)
        METRICS.clear()
        records, errs, wall = _fleet_phase(fleet, plan, size)

    if errs or fifo_errs:
        _dump_flightrec("fleet")
    stats = _fleet_class_stats(records, slo_s)
    fifo_stats = _fleet_class_stats(fifo_records, slo_s)
    throttled = sum(s["throttled"] for s in stats.values())
    fleet_state = fleet.fleet_summary() or {}
    out = {
        "metric": ("tiny_" if cpu else "") + "fleet_interactive_p95_s",
        "value": stats["interactive"]["p95_s"],
        "unit": "s",
        "vs_baseline": fifo_stats["interactive"]["p95_s"],
        "slo_attainment": stats["interactive"]["slo_attainment"],
        "preemptions": fleet_state.get("preemptions", 0),
        "quota_throttle_rate": round(throttled / len(records), 4)
        if records else 0.0,
        "classes": stats,
        "baseline_fifo": fifo_stats,
        "queue_wait_p95_s": round(obs_prom.fleet_queue_wait_p95(), 4),
        "requests": len(plan),
        "errors": errs + fifo_errs,
        "wall_s": round(wall, 2),
        "fifo_wall_s": round(fifo_wall, 2),
        "device": dev.device_kind,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_fleet.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=2)
    print(f"bench: fleet comparison written to {path} "
          f"(summarize with tools/fleet_report.py)", file=sys.stderr)
    return out


def run_watchdog(tiny):
    """--watchdog: structural hang-watchdog/requeue microbench — stub
    workers only, no device. One worker is benchmarked fast but actually
    ~20x slower than its ETA; with a tight SDTPU_WATCHDOG_FACTOR the hang
    watchdog must latch the stall, the scheduler must requeue the stalled
    range onto the healthy survivor, and the request must still deliver
    every image. All reported numbers are structural (counts/ratios) so
    tools/bench_compare.py can diff them across machines."""
    from stable_diffusion_webui_distributed_tpu.obs import (
        prometheus as obs_prom,
    )
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        ConfigModel,
    )
    from stable_diffusion_webui_distributed_tpu.scheduler.worker import (
        StubBackend, StubBehavior, WorkerNode,
    )
    from stable_diffusion_webui_distributed_tpu.scheduler.world import World

    with _EnvPatch(SDTPU_WATCHDOG_FACTOR="2.0"):
        w = World(ConfigModel())
        w.add_worker(WorkerNode(
            "survivor", StubBackend(StubBehavior(seconds_per_image=0.001)),
            avg_ipm=2400.0))
        # claims 2400 ipm (ETA 0.025 s/image) but delivers 0.5 s/image:
        # its share blows through factor x ETA and must be requeued
        w.add_worker(WorkerNode(
            "staller", StubBackend(StubBehavior(seconds_per_image=0.5)),
            avg_ipm=2400.0))
        stalls0 = obs_prom.watchdog_stalls_total()
        p = GenerationPayload(prompt="p", steps=20, width=512, height=512,
                              batch_size=4, seed=10)
        t0 = time.perf_counter()
        result = w.execute(p)
        wall = time.perf_counter() - t0
        stalls = obs_prom.watchdog_stalls_total() - stalls0
        health = w.health_summary()
    requeued = sum(s.get("requeued_images", 0) for s in health.values())
    delivered = len(result.images)
    out = {
        "metric": "watchdog_requeue_recovery_rate",
        "value": round(delivered / p.total_images, 4),
        "unit": "ratio",
        "watchdog_stalls": stalls,
        "requeued_images": requeued,
        "delivered_images": delivered,
        "total_images": p.total_images,
        "wall_s": round(wall, 3),
        "worker_health": {
            label: {"failures": s.get("failures", 0),
                    "consecutive_failures": s.get("consecutive_failures", 0),
                    "requeued_images": s.get("requeued_images", 0),
                    "state": s.get("state", "")}
            for label, s in health.items()},
        "device": "stub",
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_watchdog.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=2)
    print(f"bench: watchdog microbench written to {path}", file=sys.stderr)
    return out


def _scenario_mix(dispatcher, size, steps, n=4):
    """Record the scenario base mix: ``n`` distinct requests through the
    dispatcher with the journal on. Returns the journaled (payload,
    arrival) mix every scenario replays scaled — and warms the engine's
    executable so scenario latencies exclude compile time."""
    from stable_diffusion_webui_distributed_tpu.obs import (
        journal as obs_journal,
    )
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.sim import (
        workload as sim_workload,
    )

    obs_journal.JOURNAL.clear()
    for i in range(n):
        dispatcher.submit(GenerationPayload(
            prompt=f"scenario base mix {i}",
            negative_prompt="blurry, low quality",
            steps=steps, width=size, height=size, seed=400 + i,
            sampler_name="Euler a", request_id=f"record-{i:03d}"))
    snapshot = obs_journal.JOURNAL.snapshot()
    mix = sim_workload.base_mix(snapshot["events"])
    obs_journal.JOURNAL.clear()
    return mix


def _scenario_steady(engine, bucketer, mix, seed, slo_s):
    """Steady-state: the recorded mix resampled to 3x its size at a
    steady scaled rate through a fresh dispatcher."""
    from stable_diffusion_webui_distributed_tpu.obs import (
        journal as obs_journal, perf as obs_perf,
    )
    from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
        ServingDispatcher,
    )
    from stable_diffusion_webui_distributed_tpu.sim import (
        score as sim_score, workload as sim_workload,
    )

    spec = sim_workload.WorkloadSpec(seed=seed, count=3 * len(mix),
                                     rate_scale=4.0)
    plan = sim_workload.generate_plan(mix, spec)
    obs_perf.LEDGER.clear()
    dispatcher = ServingDispatcher(engine, bucketer=bucketer, window=0.02)
    records = sim_workload.emit_open_loop(plan, dispatcher.submit)
    events = obs_journal.JOURNAL.snapshot()["events"]
    score = sim_score.score_run(
        records, events=events, ledger=obs_perf.LEDGER.summary(),
        slo_s_by_class={"interactive": slo_s})
    score["plan_fingerprint"] = sim_workload.plan_fingerprint(plan)
    obs_journal.JOURNAL.clear()
    return score


def _scenario_burst(engine, bucketer, mix, seed, slo_s):
    """Flash burst under the fleet gate: diverse tenants/classes with a
    simultaneous-arrival burst at mid-run; per-(tenant, class) SLO
    attainment/burn comes from the real perf ledger."""
    from stable_diffusion_webui_distributed_tpu.obs import (
        journal as obs_journal, perf as obs_perf,
    )
    from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
        ServingDispatcher,
    )
    from stable_diffusion_webui_distributed_tpu.sim import (
        score as sim_score, workload as sim_workload,
    )

    spec = sim_workload.WorkloadSpec(
        seed=seed + 1, count=2 * len(mix), rate_scale=2.0,
        burst_size=4, burst_at=0.5,
        tenants=["alice", "batch-corp"],
        classes=["interactive", "batch"])
    plan = sim_workload.generate_plan(mix, spec)
    obs_perf.LEDGER.clear()
    with _EnvPatch(SDTPU_FLEET="1", SDTPU_FLEET_QUANTUM_S="0",
                   SDTPU_QUOTA_IPM="240", SDTPU_QUOTA_BURST="8",
                   SDTPU_SLO_INTERACTIVE_S=str(slo_s)):
        dispatcher = ServingDispatcher(engine, bucketer=bucketer,
                                       window=0.02)
        records = sim_workload.emit_open_loop(plan, dispatcher.submit)
    events = obs_journal.JOURNAL.snapshot()["events"]
    score = sim_score.score_run(
        records, events=events, ledger=obs_perf.LEDGER.summary(),
        slo_s_by_class={"interactive": slo_s, "batch": 4 * slo_s})
    score["plan_fingerprint"] = sim_workload.plan_fingerprint(plan)
    obs_journal.JOURNAL.clear()
    return score


def _scenario_chaos(seed):
    """Chaos kill: stub two-worker World, a scripted kill on one worker
    at request 1. The kill lands in the existing failure path, the
    scheduler requeues the dead range onto the survivor, and the scorer
    audits full recovery with zero double-merged images from the
    journal + delivered result."""
    from stable_diffusion_webui_distributed_tpu.obs import (
        journal as obs_journal,
    )
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        ConfigModel,
    )
    from stable_diffusion_webui_distributed_tpu.scheduler.worker import (
        StubBackend, StubBehavior, WorkerNode,
    )
    from stable_diffusion_webui_distributed_tpu.scheduler.world import World
    from stable_diffusion_webui_distributed_tpu.sim import (
        chaos as sim_chaos, score as sim_score,
    )

    obs_journal.JOURNAL.clear()
    w = World(ConfigModel())
    w.add_worker(WorkerNode(
        "survivor", StubBackend(StubBehavior(seconds_per_image=0.001)),
        avg_ipm=2400.0))
    w.add_worker(WorkerNode(
        "victim", StubBackend(StubBehavior(seconds_per_image=0.001)),
        avg_ipm=2400.0))
    plan = sim_chaos.ChaosPlan(
        [sim_chaos.Fault(kind="kill", worker="victim", at_request=1)],
        seed=seed)
    sim_chaos.arm(plan)
    try:
        p = GenerationPayload(prompt="chaos kill", steps=8, width=512,
                              height=512, batch_size=4, seed=77,
                              request_id="chaos-kill-000")
        t0 = time.perf_counter()
        result = w.execute(p)
        latency = time.perf_counter() - t0
    finally:
        sim_chaos.disarm()
    records = [{"request_id": "chaos-kill-000", "class": "interactive",
                "tenant": "default", "status": "completed",
                "expected": p.total_images,
                "images": len(result.images), "latency_s": latency}]
    events = obs_journal.JOURNAL.snapshot()["events"]
    score = sim_score.score_run(records, events=events)
    score["chaos_plan"] = plan.status()
    obs_journal.JOURNAL.clear()
    return score


def _scenario_chaos_stall(seed):
    """Chaos stall: stub two-worker World with a tight watchdog factor;
    the victim sleeps 1.2s before generating (ETA at 2400 ipm is
    0.025 s/image) so the hang watchdog latches and the range requeues
    onto the survivor — the same recipe as tests/test_sim.py's stall
    scenario, scored for full recovery."""
    from stable_diffusion_webui_distributed_tpu.obs import (
        journal as obs_journal,
    )
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        ConfigModel,
    )
    from stable_diffusion_webui_distributed_tpu.scheduler.worker import (
        StubBackend, StubBehavior, WorkerNode,
    )
    from stable_diffusion_webui_distributed_tpu.scheduler.world import World
    from stable_diffusion_webui_distributed_tpu.sim import (
        chaos as sim_chaos, score as sim_score,
    )

    obs_journal.JOURNAL.clear()
    with _EnvPatch(SDTPU_WATCHDOG_FACTOR="2.0"):
        w = World(ConfigModel())
        w.add_worker(WorkerNode(
            "survivor", StubBackend(StubBehavior(seconds_per_image=0.001)),
            avg_ipm=2400.0))
        w.add_worker(WorkerNode(
            "victim", StubBackend(StubBehavior(seconds_per_image=0.001)),
            avg_ipm=2400.0))
        plan = sim_chaos.ChaosPlan(
            [sim_chaos.Fault(kind="stall", worker="victim", at_request=1,
                             duration_s=1.2)],
            seed=seed + 1)
        sim_chaos.arm(plan)
        try:
            p = GenerationPayload(prompt="chaos stall", steps=8, width=512,
                                  height=512, batch_size=4, seed=88,
                                  request_id="chaos-stall-000")
            t0 = time.perf_counter()
            result = w.execute(p)
            latency = time.perf_counter() - t0
        finally:
            sim_chaos.disarm()
    records = [{"request_id": "chaos-stall-000", "class": "interactive",
                "tenant": "default", "status": "completed",
                "expected": p.total_images,
                "images": len(result.images), "latency_s": latency}]
    events = obs_journal.JOURNAL.snapshot()["events"]
    score = sim_score.score_run(records, events=events)
    score["chaos_plan"] = plan.status()
    obs_journal.JOURNAL.clear()
    return score


def _scenario_sweep(engine, mix, seed, size, slo_s):
    """Capacity sweep: the same replayed mix under three candidate
    configs (coalesce cadence x batch ladder); ranked by worst-class SLO
    attainment, then p95, then compiles."""
    from stable_diffusion_webui_distributed_tpu.obs import (
        perf as obs_perf,
    )
    from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
        ShapeBucketer,
    )
    from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
        ServingDispatcher,
    )
    from stable_diffusion_webui_distributed_tpu.sim import (
        score as sim_score, sweep as sim_sweep, workload as sim_workload,
    )

    spec = sim_workload.WorkloadSpec(seed=seed + 2, count=2 * len(mix),
                                     rate_scale=4.0)
    plan = sim_workload.generate_plan(mix, spec)
    configs = {
        "solo_b1": {"window": 0.0, "batches": [1]},
        "coalesce_b2": {"window": 0.02, "batches": [2]},
        "coalesce_b4": {"window": 0.05, "batches": [4]},
    }

    def runner(name, cfg):
        obs_perf.LEDGER.clear()
        bucketer = ShapeBucketer(shapes=[(size, size)],
                                 batches=list(cfg["batches"]))
        dispatcher = ServingDispatcher(engine, bucketer=bucketer,
                                       window=float(cfg["window"]))
        records = sim_workload.emit_open_loop(plan, dispatcher.submit)
        return sim_score.score_run(
            records, ledger=obs_perf.LEDGER.summary(),
            slo_s_by_class={"interactive": slo_s})

    out = sim_sweep.run_sweep(configs, runner)
    out["plan_fingerprint"] = sim_workload.plan_fingerprint(plan)
    return out


def run_scenarios(tiny):
    """--scenarios: the scenario-matrix regression suite (sim/). Records
    a small journal mix through the real dispatcher, then replays it
    through three scenarios — steady state, flash burst under the fleet
    gate, and a chaos worker-kill on the scheduler tier — scoring each
    from the journal + perf ledger, and finishes with a capacity sweep
    (coalesce cadence x batch ladder) over the same mix. Writes
    BENCH_scenarios.json and appends one ledger row per scenario
    (kinds scenario_steady / scenario_burst / scenario_chaos), all
    gated by tools/bench_compare.py. Deterministic from SDTPU_SIM_SEED;
    CPU-safe."""
    import jax

    from stable_diffusion_webui_distributed_tpu import sim
    from stable_diffusion_webui_distributed_tpu.models import configs as C
    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        env_int,
    )
    from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
        ShapeBucketer,
    )
    from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
        ServingDispatcher,
    )

    dev = jax.devices()[0]
    cpu = tiny or dev.platform == "cpu"
    family = C.TINY if cpu else C.SD15
    size, steps = (64, 4) if cpu else (512, 20)
    slo_s = 10.0 if cpu else 30.0
    seed = env_int("SDTPU_SIM_SEED", 0)

    with _EnvPatch(SDTPU_SIM="1", SDTPU_JOURNAL="1", SDTPU_PERF="1",
                   SDTPU_CHUNK="2" if cpu else "5"):
        engine = _make_engine(family)
        bucketer = ShapeBucketer(shapes=[(size, size)], batches=[2])
        recorder = ServingDispatcher(engine, bucketer=bucketer, window=0.0)
        mix = _scenario_mix(recorder, size, steps)
        if not mix:
            raise RuntimeError("journal recorded no replayable mix")

        scenarios = {
            "steady": _scenario_steady(engine, bucketer, mix, seed, slo_s),
            "flash_burst": _scenario_burst(engine, bucketer, mix, seed,
                                           slo_s),
            "chaos_kill": _scenario_chaos(seed),
        }
        sweep = _scenario_sweep(engine, mix, seed, size, slo_s)
        for name, score in scenarios.items():
            sim.record_last_run(name, score)

    out = {
        "seed": seed,
        "recorded_mix": len(mix),
        "scenarios": scenarios,
        "sweep": sweep,
        "device": dev.device_kind,
        "tiny": bool(tiny),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_scenarios.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    print(f"bench: scenario matrix written to {path} "
          f"(gate with tools/bench_compare.py)", file=sys.stderr)

    from stable_diffusion_webui_distributed_tpu.sim import (
        score as sim_score,
    )

    recorded_at = time.time()
    rows = [
        _ledger_row(f"scenario_{kind}",
                    sim_score.ledger_metrics(scenarios[name]),
                    dev.device_kind if name != "chaos_kill" else "stub",
                    tiny, recorded_at)
        for name, kind in (("steady", "steady"),
                           ("flash_burst", "burst"),
                           ("chaos_kill", "chaos"))
    ]
    lpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "BENCH_LEDGER.jsonl")
    with open(lpath, "a", encoding="utf-8") as f:
        for row in rows:
            f.write(json.dumps(row, sort_keys=True) + "\n")
    print(f"bench: {len(rows)} scenario ledger rows appended to {lpath}",
          file=sys.stderr)
    return out


def _alert_firings(history, start):
    """Distinct rules that transitioned to firing in history[start:]."""
    return sorted({e["rule"] for e in history[start:]
                   if e.get("to") == "firing"})


def run_alerts(tiny):
    """--alerts: alert-engine validation against labeled ground truth.
    Replays the scenario mix as a steady phase with the TSDB daemon +
    alert engine live (every firing there is a false positive), then the
    chaos kill and chaos stall scenarios bracketed by explicit TSDB
    ticks (every injected fault window must raise a matching alert —
    recall 1.0). Windows are compressed with SDTPU_ALERT_TIMESCALE so
    the 5m/1h SRE pairs evaluate over seconds. Writes BENCH_alerts.json
    (read by tools/alert_report.py) + an ``alerts`` ledger row with
    alert_false_positives / alert_recall, both zero-movement gated by
    tools/bench_compare.py. CPU-safe."""
    import jax

    from stable_diffusion_webui_distributed_tpu.models import configs as C
    from stable_diffusion_webui_distributed_tpu.obs import (
        alerts as obs_alerts, journal as obs_journal,
        prometheus as obs_prom, tsdb as obs_tsdb,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        env_int,
    )
    from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
        ShapeBucketer,
    )
    from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
        ServingDispatcher,
    )
    from stable_diffusion_webui_distributed_tpu.sim import (
        score as sim_score,
    )

    dev = jax.devices()[0]
    cpu = tiny or dev.platform == "cpu"
    family = C.TINY if cpu else C.SD15
    size, steps = (64, 4) if cpu else (512, 20)
    slo_s = 10.0 if cpu else 30.0
    seed = env_int("SDTPU_SIM_SEED", 0)

    with _EnvPatch(SDTPU_SIM="1", SDTPU_JOURNAL="1", SDTPU_PERF="1",
                   SDTPU_CHUNK="2" if cpu else "5",
                   SDTPU_TSDB="1", SDTPU_ALERTS="1",
                   SDTPU_TSDB_INTERVAL_S="0.05",
                   SDTPU_ALERT_TIMESCALE="0.01"):
        obs_prom.clear_histograms()
        obs_tsdb.reset()
        obs_alerts.reset()
        engine = _make_engine(family)
        bucketer = ShapeBucketer(shapes=[(size, size)], batches=[2])
        recorder = ServingDispatcher(engine, bucketer=bucketer, window=0.0)
        mix = _scenario_mix(recorder, size, steps)
        if not mix:
            raise RuntimeError("journal recorded no replayable mix")

        def ticks(n, sleep_s=0.02):
            # explicit cadence: back-to-back ticks would land at ~the
            # same t_mono and rate() needs time separation
            for _ in range(n):
                obs_tsdb.tick()
                time.sleep(sleep_s)

        # phase 1 — steady traffic, daemon live: zero tolerated firings.
        # The daemon also warms every anomaly rule's EWMA baseline past
        # its warmup, which is what makes the fault phases detectable.
        mark = len(obs_alerts.ENGINE.history())
        obs_tsdb.start_daemon()
        try:
            steady = _scenario_steady(engine, bucketer, mix, seed, slo_s)
        finally:
            obs_tsdb.stop_daemon()
        ticks(4)
        history = obs_alerts.ENGINE.history()
        fired_steady = _alert_firings(history, mark)

        # phase 2 — chaos kill: the ConnectionError lands in the worker
        # failure path, so the flat worker_failures_total rate jumps.
        mark = len(history)
        ticks(4)
        chaos_kill = _scenario_chaos(seed)
        ticks(4)
        history = obs_alerts.ENGINE.history()
        fired_kill = _alert_firings(history, mark)

        # phase 3 — chaos stall: the hang watchdog latches, and any
        # watchdog_stalls_total increase inside the fast window fires.
        mark = len(history)
        ticks(2)
        chaos_stall = _scenario_chaos_stall(seed)
        ticks(4)
        history = obs_alerts.ENGINE.history()
        fired_stall = _alert_firings(history, mark)

        validation = sim_score.alert_validation([
            {"name": "steady", "expected": [], "fired": fired_steady},
            {"name": "chaos_kill",
             "expected": ["error_rate_anomaly", "worker_flap"],
             "fired": fired_kill},
            {"name": "chaos_stall", "expected": ["watchdog_stall"],
             "fired": fired_stall},
        ])
        alert_events = [
            e for e in obs_journal.JOURNAL.snapshot()["events"]
            if e.get("event", "").startswith("alert_")]
        tsdb_stats = obs_tsdb.STORE.stats()
        alert_state = obs_alerts.ENGINE.state()
        obs_journal.JOURNAL.clear()
        obs_tsdb.reset()
        obs_alerts.reset()

    out = {
        "seed": seed,
        "recorded_mix": len(mix),
        "validation": validation,
        "history": history,
        "alert_journal_events": alert_events,
        "alert_state": {n: r["state"]
                        for n, r in alert_state["rules"].items()},
        "steady": steady,
        "chaos_kill": chaos_kill,
        "chaos_stall": chaos_stall,
        "tsdb": tsdb_stats,
        "device": dev.device_kind,
        "tiny": bool(tiny),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_alerts.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    print(f"bench: alert validation written to {path} "
          f"(inspect with tools/alert_report.py)", file=sys.stderr)

    recorded_at = time.time()
    row = _ledger_row("alerts", {
        "alert_false_positives": validation["alert_false_positives"],
        "alert_recall": validation["alert_recall"],
        "faults_injected": validation["faults"],
    }, "stub", tiny, recorded_at)
    lpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "BENCH_LEDGER.jsonl")
    with open(lpath, "a", encoding="utf-8") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")
    print(f"bench: alerts ledger row appended to {lpath}", file=sys.stderr)
    return out


def run_federation(tiny):
    """--federation: fleet-federation + paging validation. Two stub
    workers are fronted by in-process API servers; the federation prober
    scrapes both over real HTTP on explicit ticks (steady phase: zero
    stale verdicts, zero fleet-scope firings = zero false positives),
    then one worker is chaos-killed and its API server shut down
    mid-run — the staleness gauge must cross the freshness deadline,
    trip the fleet-scope alerts (worker_metrics_stale +
    fleet_error_rate), and land the transitions on a local webhook
    capture server. Writes BENCH_federation.json + a ``federation``
    ledger row; tools/bench_compare.py zero-movement-gates
    notify_delivery_rate and federation_staleness_fp. CPU-safe."""
    import http.server

    from stable_diffusion_webui_distributed_tpu.obs import (
        alerts as obs_alerts, federation as obs_federation,
        journal as obs_journal, notify as obs_notify,
        prometheus as obs_prom, tsdb as obs_tsdb,
    )
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        ConfigModel, env_int,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
        GenerationState,
    )
    from stable_diffusion_webui_distributed_tpu.scheduler.worker import (
        StubBackend, StubBehavior, WorkerNode,
    )
    from stable_diffusion_webui_distributed_tpu.scheduler.world import World
    from stable_diffusion_webui_distributed_tpu.server.api import ApiServer
    from stable_diffusion_webui_distributed_tpu.sim import (
        chaos as sim_chaos,
    )

    seed = env_int("SDTPU_SIM_SEED", 0)

    # local webhook capture server: every delivered page lands here
    received = []

    class _Hook(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length") or 0)
            try:
                received.append(json.loads(self.rfile.read(n)))
            except ValueError:
                received.append({"malformed": True})
            self.send_response(200)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"ok")

        def log_message(self, *args):  # keep bench stderr clean
            pass

    hook = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Hook)
    threading.Thread(target=hook.serve_forever, daemon=True).start()
    hook_url = f"http://127.0.0.1:{hook.server_address[1]}/hook"

    try:
        with _EnvPatch(SDTPU_SIM="1", SDTPU_JOURNAL="1",
                       SDTPU_TSDB="1", SDTPU_ALERTS="1",
                       SDTPU_FEDERATION="1",
                       SDTPU_TSDB_INTERVAL_S="0.05",
                       SDTPU_ALERT_TIMESCALE="0.01",
                       SDTPU_OBS_HTTP_TIMEOUT_S="2.0",
                       SDTPU_NOTIFY_URL=hook_url):
            obs_prom.clear_histograms()
            obs_tsdb.reset()
            obs_alerts.reset()
            obs_federation.reset()
            obs_notify.reset()
            obs_journal.JOURNAL.clear()

            w = World(ConfigModel())  # registers itself as prober source
            w.add_worker(WorkerNode(
                "alpha",
                StubBackend(StubBehavior(seconds_per_image=0.001)),
                avg_ipm=2400.0))
            w.add_worker(WorkerNode(
                "victim",
                StubBackend(StubBehavior(seconds_per_image=0.001)),
                avg_ipm=2400.0))
            servers = {}
            for node in w.workers:
                srv = ApiServer(w, state=GenerationState(),
                                host="127.0.0.1", port=0).start()
                node.backend.address = "127.0.0.1"
                node.backend.port = srv.port
                servers[node.label] = srv

            def cycle(n, sleep_s=0.05):
                # explicit cadence, like run_alerts: the federation poll
                # and the TSDB sample share one deterministic clock
                for _ in range(n):
                    obs_federation.tick()
                    obs_tsdb.tick()
                    time.sleep(sleep_s)

            # phase 1 — steady: both workers polled over real HTTP; any
            # stale verdict or fleet-scope firing is a false positive.
            mark = len(obs_alerts.ENGINE.history())
            cycle(6)
            steady_summary = obs_federation.summary()
            history = obs_alerts.ENGINE.history()
            fired_steady = _alert_firings(history, mark)
            steady_stale = sorted(
                label for label, st in steady_summary["workers"].items()
                if st["stale"])

            # phase 2 — kill: the chaos fault lands in the victim's
            # generate path (journaled, requeued onto alpha) and its API
            # server goes down, so federation polls fail and the
            # staleness gauge crosses the freshness deadline.
            mark = len(history)
            plan = sim_chaos.ChaosPlan(
                [sim_chaos.Fault(kind="kill", worker="victim",
                                 at_request=1)],
                seed=seed)
            sim_chaos.arm(plan)
            try:
                p = GenerationPayload(prompt="federation kill", steps=8,
                                      width=512, height=512, batch_size=4,
                                      seed=99, request_id="fed-kill-000")
                result = w.execute(p)
            finally:
                sim_chaos.disarm()
            servers["victim"].stop()
            time.sleep(max(0.3, obs_federation.stale_after_s()))
            cycle(6)
            history = obs_alerts.ENGINE.history()
            fired_kill = _alert_firings(history, mark)
            kill_summary = obs_federation.summary()

            flushed = obs_notify.flush(10.0)
            notify_counts = obs_notify.NOTIFIER.counts()
            fed_journal = [
                e for e in obs_journal.JOURNAL.snapshot()["events"]
                if e.get("event") in ("notify_sent", "notify_failed",
                                      "federation_poll_failed")]
            servers["alpha"].stop()
            obs_journal.JOURNAL.clear()
            obs_notify.reset()
            obs_federation.reset()
            obs_tsdb.reset()
            obs_alerts.reset()
    finally:
        hook.shutdown()
        hook.server_close()

    sent = notify_counts.get("sent", 0)
    failed = notify_counts.get("failed", 0)
    delivery_rate = sent / (sent + failed) if (sent + failed) else None
    staleness_recall = 1.0 if "worker_metrics_stale" in fired_kill else 0.0
    staleness_fp = len(steady_stale) + sum(
        1 for r in fired_steady
        if r in ("worker_metrics_stale", "fleet_error_rate"))
    if not flushed:
        raise RuntimeError("notify queue did not drain within 10s")
    if staleness_recall < 1.0:
        raise RuntimeError(
            f"killed worker raised no worker_metrics_stale alert "
            f"(kill-phase firings: {fired_kill})")
    if sent == 0 or not received:
        raise RuntimeError(
            f"no webhook reached the capture server "
            f"(counts: {notify_counts})")

    out = {
        "seed": seed,
        "steady": {"fired": fired_steady, "stale_workers": steady_stale,
                   "summary": steady_summary},
        "kill": {"fired": fired_kill, "summary": kill_summary,
                 "chaos_plan": plan.status(),
                 "recovered_images": len(result.images)},
        "webhooks_received": received,
        "notify_counts": notify_counts,
        "federation_journal_events": fed_journal,
        "notify_delivery_rate": delivery_rate,
        "federation_staleness_recall": staleness_recall,
        "federation_staleness_fp": staleness_fp,
        "tiny": bool(tiny),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_federation.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    print(f"bench: federation validation written to {path} "
          f"(inspect with tools/fed_report.py)", file=sys.stderr)

    recorded_at = time.time()
    row = _ledger_row("federation", {
        "notify_delivery_rate": delivery_rate,
        "federation_staleness_fp": staleness_fp,
        "federation_staleness_recall": staleness_recall,
        "webhooks_delivered": sent,
    }, "stub", tiny, recorded_at)
    lpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "BENCH_LEDGER.jsonl")
    with open(lpath, "a", encoding="utf-8") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")
    print(f"bench: federation ledger row appended to {lpath}",
          file=sys.stderr)
    return out


def run_obsplane(tiny):
    """--obsplane: push-vs-poll control plane validation. Two stub
    workers are fronted by in-process API servers; phase 1 drives the
    *poll* prober at its natural cadence and samples per-worker
    staleness on a fast sidecar clock, phase 2 runs the *push* plane's
    subscriber daemons (long-poll /internal/deltas) and samples the
    same way — push staleness p95 must not exceed the poll baseline.
    Mid-push a worker is chaos-killed and its API server shut down:
    the stale alert must fire and land on the page-severity webhook
    only, a synthetic warn probe must land on the warn webhook only
    (the severity routing matrix), the delta streams must report zero
    event loss, and the fleet-merged timeline must be causally clean
    with the victim's lane present. Writes BENCH_obsplane.json + an
    ``obsplane`` ledger row; tools/bench_compare.py zero-gates
    push_event_loss and notify_misrouted and trend-gates
    push_staleness_p95_s. CPU-safe."""
    import http.server

    from stable_diffusion_webui_distributed_tpu.obs import (
        alerts as obs_alerts, federation as obs_federation,
        fleetlog as obs_fleetlog, journal as obs_journal,
        notify as obs_notify, prometheus as obs_prom,
        push as obs_push, tsdb as obs_tsdb,
    )
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        ConfigModel, env_int,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
        GenerationState,
    )
    from stable_diffusion_webui_distributed_tpu.scheduler.worker import (
        StubBackend, StubBehavior, WorkerNode,
    )
    from stable_diffusion_webui_distributed_tpu.scheduler.world import World
    from stable_diffusion_webui_distributed_tpu.server.api import ApiServer
    from stable_diffusion_webui_distributed_tpu.sim import (
        chaos as sim_chaos,
    )

    seed = env_int("SDTPU_SIM_SEED", 0)

    def hook_server(bucket):
        class _Hook(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                try:
                    bucket.append(json.loads(self.rfile.read(n)))
                except ValueError:
                    bucket.append({"malformed": True})
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"ok")

            def log_message(self, *args):  # keep bench stderr clean
                pass

        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Hook)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, f"http://127.0.0.1:{srv.server_address[1]}/hook"

    page_hits, warn_hits = [], []
    page_srv, page_url = hook_server(page_hits)
    warn_srv, warn_url = hook_server(warn_hits)

    poll_cadence_s = 0.25    # a realistic scrape interval
    sample_s = 0.02          # the staleness sidecar sampling clock
    phase_s = 2.0

    def sample_staleness(workers_fn, seconds):
        out = []
        t_end = time.monotonic() + seconds
        while time.monotonic() < t_end:
            obs_tsdb.tick()
            for st in workers_fn().values():
                out.append(float(st["staleness_s"]))
            time.sleep(sample_s)
        return out

    try:
        with _EnvPatch(SDTPU_SIM="1", SDTPU_JOURNAL="1",
                       SDTPU_TSDB="1", SDTPU_ALERTS="1",
                       SDTPU_TSDB_INTERVAL_S="0.05",
                       SDTPU_ALERT_TIMESCALE="0.01",
                       SDTPU_OBS_HTTP_TIMEOUT_S="2.0",
                       SDTPU_PUSH_WAIT_S="0.05",
                       SDTPU_NOTIFY_ROUTES=(f"page={page_url},"
                                            f"warn={warn_url}")):
            obs_prom.clear_histograms()
            obs_tsdb.reset()
            obs_alerts.reset()
            obs_federation.reset()
            obs_notify.reset()
            obs_push.reset()
            obs_fleetlog.reset()
            obs_journal.JOURNAL.clear()

            w = World(ConfigModel())
            w.add_worker(WorkerNode(
                "alpha",
                StubBackend(StubBehavior(seconds_per_image=0.001)),
                avg_ipm=2400.0))
            w.add_worker(WorkerNode(
                "victim",
                StubBackend(StubBehavior(seconds_per_image=0.001)),
                avg_ipm=2400.0))
            servers = {}
            for node in w.workers:
                srv = ApiServer(w, state=GenerationState(),
                                host="127.0.0.1", port=0).start()
                node.backend.address = "127.0.0.1"
                node.backend.port = srv.port
                servers[node.label] = srv

            # a little real traffic so both planes have counters to ship
            w.execute(GenerationPayload(
                prompt="obsplane steady", steps=8, width=512, height=512,
                batch_size=4, seed=99, request_id="obsplane-000"))

            # phase 1 — the poll baseline: the prober scrapes both
            # workers over real HTTP on its cadence; staleness ramps to
            # the cadence between scrapes, so its p95 ~= the cadence.
            poll_samples = []
            with _EnvPatch(SDTPU_FEDERATION="1"):
                obs_federation.set_source(w)
                t_end = time.monotonic() + phase_s
                while time.monotonic() < t_end:
                    obs_federation.tick()
                    poll_samples.extend(sample_staleness(
                        lambda: obs_federation.summary()["workers"],
                        poll_cadence_s))
                obs_federation.reset()

            # phase 2 — push: subscriber daemons long-poll the delta
            # endpoints; the anchor refreshes continuously, so the same
            # sidecar sampler must see a lower p95.
            push_samples = []
            with _EnvPatch(SDTPU_PUSH="1"):
                obs_push.set_source(w)
                if not obs_push.start_daemons():
                    raise RuntimeError("push daemons refused to start")
                push_samples = sample_staleness(
                    lambda: obs_push.summary()["workers"], phase_s)
                steady_push = obs_push.summary()

                # the chaos: kill the victim mid-request (requeued onto
                # alpha), then its API server dies — the subscriber's
                # long-polls fail, staleness crosses the deadline, and
                # the page-severity stale alert must route to url1 only.
                mark = len(obs_alerts.ENGINE.history())
                plan = sim_chaos.ChaosPlan(
                    [sim_chaos.Fault(kind="kill", worker="victim",
                                     at_request=1)],
                    seed=seed)
                sim_chaos.arm(plan)
                try:
                    result = w.execute(GenerationPayload(
                        prompt="obsplane kill", steps=8, width=512,
                        height=512, batch_size=4, seed=99,
                        request_id="obsplane-kill-001"))
                finally:
                    sim_chaos.disarm()
                servers["victim"].stop()
                time.sleep(max(0.3, obs_federation.stale_after_s()))
                sample_staleness(
                    lambda: obs_push.summary()["workers"], 1.0)
                fired_kill = _alert_firings(
                    obs_alerts.ENGINE.history(), mark)
                # the warn lane of the routing matrix: a synthetic
                # warn-severity transition must land on url2 only
                obs_notify.notify_transition(
                    "obsplane_warn_probe", "firing", 1.0,
                    "severity routing probe", severity="warn")
                flushed = obs_notify.flush(10.0)
                push_summary = obs_push.summary()
                timeline = obs_fleetlog.timeline()
                by_channel = obs_notify.NOTIFIER.counts_by_channel()
                obs_push.stop_daemons()

            servers["alpha"].stop()
            obs_journal.JOURNAL.clear()
            obs_notify.reset()
            obs_push.reset()
            obs_fleetlog.reset()
            obs_tsdb.reset()
            obs_alerts.reset()
    finally:
        for srv in (page_srv, warn_srv):
            srv.shutdown()
            srv.server_close()

    poll_p95 = _percentile(poll_samples, 0.95)
    push_p95 = _percentile(push_samples, 0.95)
    event_loss = push_summary["event_loss"]
    # severity routing matrix: every page-hook body must carry a
    # page-severity rule, every warn-hook body a warn one
    page_rules = {"worker_metrics_stale", "fleet_error_rate",
                  "watchdog_stall", "slo_burn_fast"}
    misrouted = sum(1 for b in page_hits
                    if b.get("rule") not in page_rules)
    misrouted += sum(1 for b in warn_hits
                     if b.get("rule") in page_rules)

    if not flushed:
        raise RuntimeError("notify queue did not drain within 10s")
    if "worker_metrics_stale" not in fired_kill:
        raise RuntimeError(
            f"killed worker raised no worker_metrics_stale alert "
            f"(kill-phase firings: {fired_kill})")
    if not any(b.get("rule") == "worker_metrics_stale"
               for b in page_hits):
        raise RuntimeError(
            f"stale page never reached the page webhook "
            f"(page={page_hits}, warn={warn_hits})")
    if not any(b.get("rule") == "obsplane_warn_probe"
               for b in warn_hits):
        raise RuntimeError(
            f"warn probe never reached the warn webhook "
            f"(warn={warn_hits})")
    if misrouted:
        raise RuntimeError(
            f"severity routing crossed channels: {misrouted} misrouted "
            f"(page={page_hits}, warn={warn_hits})")
    if event_loss:
        raise RuntimeError(
            f"delta streams lost {event_loss} entries "
            f"(workers: {push_summary['workers']})")
    if push_p95 is not None and poll_p95 is not None \
            and push_p95 > poll_p95:
        raise RuntimeError(
            f"push staleness p95 {push_p95:.3f}s worse than the poll "
            f"baseline {poll_p95:.3f}s")
    if timeline["violations"]:
        raise RuntimeError(
            f"fleet timeline has {timeline['violations']} causal-order "
            f"violation(s)")
    if not any(e["node"] == "victim" for e in timeline["events"]):
        raise RuntimeError("victim's lane missing from the timeline")

    out = {
        "seed": seed,
        "poll": {"staleness_p95_s": poll_p95,
                 "samples": len(poll_samples),
                 "cadence_s": poll_cadence_s},
        "push": {"staleness_p95_s": push_p95,
                 "samples": len(push_samples),
                 "steady_summary": steady_push,
                 "kill_summary": push_summary,
                 "fired": fired_kill,
                 "recovered_images": len(result.images)},
        "routing": {"page_received": page_hits,
                    "warn_received": warn_hits,
                    "by_channel": by_channel,
                    "misrouted": misrouted},
        "timeline": {"count": timeline["count"],
                     "violations": timeline["violations"],
                     "nodes": timeline["nodes"]},
        "tiny": bool(tiny),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_obsplane.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    print(f"bench: obsplane validation written to {path} "
          f"(inspect the timeline with tools/fed_report.py --timeline)",
          file=sys.stderr)

    recorded_at = time.time()
    row = _ledger_row("obsplane", {
        "push_event_loss": event_loss,
        "push_duplicates": push_summary["duplicates"],
        "notify_misrouted": misrouted,
        "push_staleness_p95_s": push_p95,
        "poll_staleness_p95_s": poll_p95,
    }, "stub", tiny, recorded_at)
    lpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "BENCH_LEDGER.jsonl")
    with open(lpath, "a", encoding="utf-8") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")
    print(f"bench: obsplane ledger row appended to {lpath}",
          file=sys.stderr)
    return out


def _ledger_row(kind, metrics, device, tiny, recorded_at):
    """One append-only BENCH_LEDGER.jsonl row. ``schema`` versions the row
    shape; ``metrics`` holds only platform-independent structural numbers
    (compile counts, ratios, attainment) that tools/bench_compare.py can
    diff across machines."""
    return {"schema": 1, "kind": kind, "recorded_at": recorded_at,
            "device": device, "tiny": bool(tiny), "metrics": metrics}


def _run_lint_metrics():
    """Full-package sdtpu-lint run for the ledger: wall time (trajectory
    only) and finding count (zero-movement gated by bench_compare — the
    repo gate is clean, so any nonzero count is a regression). The
    concurrency tier rides in the same row: ``lock_cycles`` counts LK005
    entry-reachable deadlock cycles (zero-tolerance in bench_compare),
    and ``schedule_explorer_seeds`` is the number of clean seeded
    interleavings across the sim/harnesses.py subsystem harnesses."""
    from stable_diffusion_webui_distributed_tpu.analysis import run_analysis
    from stable_diffusion_webui_distributed_tpu.runtime import locksan
    from stable_diffusion_webui_distributed_tpu.runtime.config import env_int
    from stable_diffusion_webui_distributed_tpu.sim import harnesses
    root = os.path.dirname(os.path.abspath(__file__))
    result = run_analysis(root, use_cache=False)
    lock_cycles = sum(1 for f in result.findings
                      if f.rule == "LK005" and "potential deadlock"
                      in f.message)
    seeds = max(1, env_int("SDTPU_SCHED_SEEDS", 64))
    was = locksan.installed()
    if not was:
        locksan.install()
    try:
        clean_seeds = 0
        for name in sorted(harnesses.HARNESSES):
            clean_seeds += sum(
                1 for r in harnesses.run_harness(name, range(seeds))
                if r.ok)
    finally:
        if not was:
            locksan.uninstall()
    return {
        "lint_wall_time_s": round(result.wall_time_s, 3),
        "lint_finding_count": len(result.findings),
        "lint_modules": result.modules,
        "lock_cycles": lock_cycles,
        "schedule_explorer_seeds": clean_seeds,
    }


def run_ledger(tiny):
    """--ledger: run the serving and fleet microbenches with the perf
    ledger on (SDTPU_PERF=1) and append one structural row per run to
    BENCH_LEDGER.jsonl. The ledger is append-only: every row is a point on
    the repo's perf trajectory, and tools/bench_compare.py diffs any two
    rows (or a row vs a BENCH_*.json) against regression thresholds."""
    with _EnvPatch(SDTPU_PERF="1"):
        serving = run_serving(tiny)
        fleet = run_fleet(tiny)
        watchdog = run_watchdog(tiny)
    recorded_at = time.time()
    rows = [
        _ledger_row("serving", {
            "chunk_compiles": serving.get("chunk_compiles"),
            "coalesce_factor": serving.get("value"),
            "bucket_hit_rate": serving.get("bucket_hit_rate"),
            "avg_padding_ratio": serving.get("avg_padding_ratio"),
            "dispatches": serving.get("dispatches"),
            "coalesced_dispatches": serving.get("coalesced_dispatches"),
        }, serving.get("device", ""), tiny, recorded_at),
        _ledger_row("fleet", {
            "slo_attainment": fleet.get("slo_attainment"),
            "preemptions": fleet.get("preemptions"),
            "quota_throttle_rate": fleet.get("quota_throttle_rate"),
            "queue_wait_p95_s": fleet.get("queue_wait_p95_s"),
            "interactive_p95_s": fleet.get("value"),
            "fifo_interactive_p95_s": fleet.get("vs_baseline"),
        }, fleet.get("device", ""), tiny, recorded_at),
        _ledger_row("watchdog", {
            "watchdog_stalls": watchdog.get("watchdog_stalls"),
            "requeued_images": watchdog.get("requeued_images"),
            "requeue_recovery_rate": watchdog.get("value"),
        }, watchdog.get("device", ""), tiny, recorded_at),
        _ledger_row("lint", _run_lint_metrics(), "cpu", tiny, recorded_at),
    ]
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_LEDGER.jsonl")
    with open(path, "a", encoding="utf-8") as f:
        for row in rows:
            f.write(json.dumps(row, sort_keys=True) + "\n")
    print(f"bench: {len(rows)} ledger rows appended to {path} "
          f"(diff with tools/bench_compare.py)", file=sys.stderr)
    return {"ledger_path": path, "rows": rows}


def _dump_flightrec(tag):
    """Persist the obs flight recorder (failed/interrupted/slow requests'
    span trees + correlated log lines) next to the bench outputs so a dead
    chip-window run leaves a triage artifact behind."""
    try:
        from stable_diffusion_webui_distributed_tpu.obs import flightrec

        if not len(flightrec.RECORDER):
            return None
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            f"BENCH_flightrec_{tag}.json")
        flightrec.RECORDER.dump_to_file(path)
        print(f"bench: flight recorder dumped to {path} "
              f"(inspect with tools/trace_report.py)", file=sys.stderr)
        return path
    except Exception:  # noqa: BLE001 — triage artifact must never mask rc
        return None


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", type=int, default=1, choices=range(1, 6),
                    help="BASELINE.md config number (default 1): images per "
                         "minute on the local TPU, one process; exits "
                         "non-zero without a TPU unless SDTPU_BENCH_TINY=1 "
                         "asks for the CPU logic check (tiny_* rows)")
    ap.add_argument("--serving", action="store_true",
                    help="serving-layer microbench: coalesce factor + "
                         "compile counts (CPU-safe)")
    ap.add_argument("--fleet", action="store_true",
                    help="fleet-scheduler comparison: mixed-tenant "
                         "open-loop workload, FIFO vs WFQ gate; writes "
                         "BENCH_fleet.json (CPU-safe)")
    ap.add_argument("--int8", action="store_true",
                    help="int8 x step-cache grid: FLOPs/image, compile "
                         "counts, PSNR/SSIM vs bf16 per cell; writes "
                         "BENCH_int8.json (CPU-safe)")
    ap.add_argument("--cache", action="store_true",
                    help="caching-tier microbench: redundant request mix "
                         "through the dispatcher with SDTPU_CACHE=1 — "
                         "per-layer hit rates, FLOPs/image delta for a "
                         "prefix-resumed denoise, e2e p50/p95; writes "
                         "BENCH_cache.json + a ledger row (CPU-safe)")
    ap.add_argument("--lora", action="store_true",
                    help="adapter-churn microbench: four adapters "
                         "cycling through the dispatcher, merged vs "
                         "SDTPU_LORA_TRACED arms — chunk-compile and "
                         "host-merge counts per switch, embed-cache "
                         "survival, census silence; writes "
                         "BENCH_lora.json + a ledger row (CPU-safe)")
    ap.add_argument("--ragged", action="store_true",
                    help="ragged-dispatch microbench: mixed-height "
                         "workload under a fine ladder, a coarse classic "
                         "bucket, and SDTPU_RAGGED — compile counts + "
                         "padding ratios; writes BENCH_ragged.json + a "
                         "ledger row (CPU-safe)")
    ap.add_argument("--watchdog", action="store_true",
                    help="hang-watchdog/requeue structural microbench "
                         "(stub workers, no device); writes "
                         "BENCH_watchdog.json (CPU-safe)")
    ap.add_argument("--scenarios", action="store_true",
                    help="scenario-matrix regression suite (sim/): "
                         "record a journal mix, replay it through "
                         "steady / flash-burst / chaos-kill scenarios "
                         "and a capacity sweep; writes "
                         "BENCH_scenarios.json + per-scenario ledger "
                         "rows (CPU-safe)")
    ap.add_argument("--alerts", action="store_true",
                    help="alert-engine validation: steady scenario with "
                         "the TSDB daemon + alert engine live (zero "
                         "false-positive firings), then the chaos "
                         "kill/stall scenarios (every fault window must "
                         "raise a matching alert); writes "
                         "BENCH_alerts.json + a ledger row (CPU-safe)")
    ap.add_argument("--federation", action="store_true",
                    help="fleet-federation + paging validation: two "
                         "API-fronted stub workers polled over real "
                         "HTTP, one chaos-killed mid-run — staleness "
                         "alert recall, steady false positives and "
                         "webhook delivery to a local capture server; "
                         "writes BENCH_federation.json + a ledger row "
                         "(CPU-safe)")
    ap.add_argument("--obsplane", action="store_true",
                    help="push-vs-poll control plane validation: two "
                         "API-fronted stub workers, poll-baseline then "
                         "push-daemon staleness p95, chaos kill with "
                         "severity-routed paging over two capture "
                         "webhooks, zero delta-stream loss and a "
                         "causally clean fleet timeline; writes "
                         "BENCH_obsplane.json + a ledger row (CPU-safe)")
    ap.add_argument("--ledger", action="store_true",
                    help="run the serving, fleet and watchdog microbenches "
                         "with the perf ledger on and append structural "
                         "rows to BENCH_LEDGER.jsonl (CPU-safe)")
    args = ap.parse_args()

    # SDTPU_BENCH_TINY=1: logic-validation mode for CPU-only environments
    # (same protocol and code path, tiny models + payloads; NOT a perf claim).
    tiny = tiny_env()

    # one process per chip: JAX is imported here, in the process that
    # measures. The compile cache sits where JAX_COMPILATION_CACHE_DIR says,
    # else at the fixed in-checkout path (a tuning sweep re-runs the same
    # configs; first SDXL compile is minutes).
    from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
        enable_compilation_cache,
    )

    enable_compilation_cache()

    try:
        if args.ledger:
            print(json.dumps(run_ledger(tiny)))
        elif args.serving:
            print(json.dumps(run_serving(tiny)))
        elif args.fleet:
            print(json.dumps(run_fleet(tiny)))
        elif args.watchdog:
            print(json.dumps(run_watchdog(tiny)))
        elif args.scenarios:
            print(json.dumps(run_scenarios(tiny)))
        elif args.alerts:
            print(json.dumps(run_alerts(tiny)))
        elif args.federation:
            print(json.dumps(run_federation(tiny)))
        elif args.obsplane:
            print(json.dumps(run_obsplane(tiny)))
        elif args.cache:
            print(json.dumps(run_cache(tiny)))
        elif args.lora:
            print(json.dumps(run_lora(tiny)))
        elif args.ragged:
            print(json.dumps(run_ragged(tiny)))
        elif args.int8:
            print(json.dumps(run_int8(tiny)))
        else:
            print(json.dumps(run_config(args.config, tiny)))
    except BaseException:
        _dump_flightrec("error")
        raise


if __name__ == "__main__":
    main()

"""chip_smoke.py: the quickest proof that the serving path starts on the chip.

One process, one chip, no arguments (``python chip_smoke.py``):

1. the compile cache is placed (``JAX_COMPILATION_CACHE_DIR``, else
   ``<checkout>/.jax_cache``) and its directory and entry count printed;
2. both Pallas attention kernels are compiled through their public entry
   points at every UNet self-attention shape of SD1.5 512² and SDXL 1024²
   (the tiled one also at the cross-attention shapes over the crossover,
   77 and 231 keys), the compiled text is searched for the Mosaic call, and
   each runs once on seeded q/k/v against its reference on the same device;
   the tiled kernel and XLA's attention are then timed alone at each shape
   and both times printed beside what the default path takes there, so the
   rule of ops/attention.py can be read again on any chip;
3. SD1.5 is built at its published width with seeded random weights in the
   serving policy's dtype, wrapped in ``ApiServer(engine, port=0)`` and asked
   over real HTTP for the reference's calibration image, the same image
   again, a batch of two, and the second seed alone — the normal route
   api -> dispatcher -> engine -> VAE decode -> fetch -> native PNG.

``--chips 4`` runs none of that: it runs the same engine at batch 4 on one
chip, then over ``dp=4`` and ``dp=2,tp=2`` meshes of four chips, and compares.

Every check prints ``check ok`` or ``check FAIL``; any FAIL, any exception,
or a first device that is not a TPU makes the exit code 1 and the last
stdout line ``{"ok": false, ...}``. The last line is always that one JSON
object with the device as JAX reports it; everything else is on earlier
lines. A CPU never runs this command: tests drive the phase functions with
the tiny family instead (tests/test_chip_smoke.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
import urllib.request

#: the repo's own bound for a bf16 kernel against its reference
#: (tests/test_ops.py::test_bf16_inputs), used for rtol and atol alike
KERNEL_TOL = 3e-2

#: PSNR floor (dB) between images whose arithmetic differs only in ORDER:
#: another batch shape on one chip, a dp split (another program, even at
#: the same per-shard shape), tp partial sums. ``__graft_entry__`` holds
#: the tp pair to rtol=atol=2e-4 on f32 outputs under the f32 policy; the
#: serving policy computes in bf16 (unit roundoff 2**-8) and returns uint8,
#: where 2e-4 is below one level.
#: The repo's floor for a LOSSY path (int8, step cache: tests/quality.py)
#: is 20 dB; a reorder-only path is held 10 dB above it.
REORDER_PSNR_FLOOR_DB = 30.0

#: (batch, heads, tokens, head_dim) of every UNet self-attention at CFG
#: batch 2: SD1.5 at 512², then SDXL at 1024²; then SD1.5's two tiled
#: shapes at CFG batch 8 (four images a request) — the (B*H, T, D) cases
#: of tests/test_chip_compile.py
KERNEL_CASES = [
    (2, 8, 4096, 40), (2, 8, 1024, 80), (2, 8, 256, 160), (2, 8, 64, 160),
    (2, 10, 4096, 64), (2, 20, 1024, 64),
    (8, 8, 4096, 40), (8, 8, 1024, 80),
]

#: (batch, heads, tokens, head_dim, keys) of the cross-attention sites at or
#: over the crossover: SD1.5 over an expanded context of three 77-token
#: chunks at CFG batch 2 and 8, then SDXL over 77 tokens at CFG batch 2 and
#: 4 (sdxl_pair), then SD1.5's 64x64 site at CFG batch 4 (two images), the
#: first whose scores XLA cannot keep on chip; the key count is off the
#: tiling, so the kernel pads and masks it
CROSS_CASES = [
    (2, 8, 4096, 40, 231), (8, 8, 4096, 40, 231),
    (2, 8, 1024, 80, 231), (8, 8, 1024, 80, 231),
    (2, 10, 4096, 64, 77), (2, 20, 1024, 64, 77),
    (4, 10, 4096, 64, 77), (4, 20, 1024, 64, 77),
    (4, 8, 4096, 40, 231),
]


class Report:
    """Prints facts and checks as they happen and remembers what failed."""

    def __init__(self) -> None:
        self.facts: dict[str, str] = {}
        self.failed: list[str] = []

    def fact(self, name: str, value) -> None:
        self.facts[name] = str(value)
        print(f"{name}: {value}", flush=True)

    def check(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"check {'ok  ' if ok else 'FAIL'} {name}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)


class CompileCounter:
    """Counts what XLA really did, from JAX's own monitoring events:
    executables made (compiled, or loaded from the persistent cache), and
    the persistent cache's hits and misses."""

    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }
    #: wraps compile_or_get_cached: one per executable, hit or miss
    _EXECUTABLE = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.counts = {"executables": 0, "cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        key = self._EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == self._EXECUTABLE:
            self.counts["executables"] += 1


def device_record() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# -- phase: compile cache ---------------------------------------------------

def cache_entries(cache_dir: str) -> str:
    names = os.listdir(cache_dir) if os.path.isdir(cache_dir) else []
    size = sum(os.path.getsize(os.path.join(cache_dir, n)) for n in names)
    return f"{len(names)} ({size / 2**20:.1f} MiB)"


def phase_cache(report: Report) -> str:
    from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
        DEFAULT_COMPILE_CACHE, enable_compilation_cache,
    )

    cache_dir = enable_compilation_cache()
    report.fact("compile cache directory", cache_dir
                + (" (the default inside the checkout)"
                   if cache_dir == DEFAULT_COMPILE_CACHE
                   else " (from JAX_COMPILATION_CACHE_DIR)"))
    report.fact("compile cache entries before", cache_entries(cache_dir))
    return cache_dir


# -- phase: the Pallas kernels against their references ----------------------

#: attention calls in one device-side loop, and loops timed (the median is
#: reported) after one that warms up
TIMING_CALLS = 20
TIMING_LOOPS = 5


def phase_kernels(report: Report, cases, seed: int, cross=()) -> None:
    """flash_attention / ragged_attention through their public entry points:
    is the Mosaic kernel in the compiled text, and does one run on seeded
    bf16 q/k/v agree with the reference on the same device. Then the tiled
    kernel and XLA's attention alone, in milliseconds a call. ``cross``
    cases carry a key count of their own and take the tiled kernel only."""
    import statistics

    import jax
    import jax.numpy as jnp
    import numpy as np

    from stable_diffusion_webui_distributed_tpu.ops.attention import choose
    from stable_diffusion_webui_distributed_tpu.ops.flash_attention import (
        flash_attention,
    )
    from stable_diffusion_webui_distributed_tpu.ops.ragged_attention import (
        ragged_attention, ragged_attention_reference,
    )

    def close(name, got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        ok = bool(np.isfinite(got).all()) and bool(
            np.allclose(got, want, rtol=KERNEL_TOL, atol=KERNEL_TOL))
        report.check(f"{name} agrees with its reference", ok,
                     f"max abs err {err:.3e}, tol {KERNEL_TOL}")

    def alone_ms(attention, q, k, v) -> float:
        """One call's milliseconds on the device: the output feeds the next
        call's q inside one executable, so no dispatch is timed."""
        loop = jax.jit(lambda q, k, v: jax.lax.fori_loop(
            0, TIMING_CALLS, lambda _, x: attention(x, k, v), q))
        seconds = []
        for _ in range(TIMING_LOOPS + 1):
            t0 = time.perf_counter()
            loop(q, k, v).block_until_ready()
            seconds.append(time.perf_counter() - t0)
        return statistics.median(seconds[1:]) / TIMING_CALLS * 1e3

    def tiled_against_xla(b, h, t, d, s) -> tuple:
        shape = f"B{b} H{h} T{t} D{d}" + (f" S{s}" if s != t else "")
        q, k, v = (jax.random.normal(key, (b, n, h, d), jnp.bfloat16)
                   for key, n in zip(jax.random.split(jax.random.key(seed), 3),
                                     (t, s, s)))
        flash = jax.jit(flash_attention).lower(q, k, v).compile()
        report.check(f"flash kernel in compiled text [{shape}]",
                     "tpu_custom_call" in flash.as_text())
        close(f"flash [{shape}]", flash(q, k, v),
              jax.jit(jax.nn.dot_product_attention)(q, k, v))
        report.fact(
            f"attention alone [{shape}]",
            f"tiled {alone_ms(flash_attention, q, k, v):.4f} ms, "
            f"XLA {alone_ms(jax.nn.dot_product_attention, q, k, v):.4f} ms "
            f"a call; the default path takes "
            f"{choose(jax.default_backend(), t, s, q.dtype, batch_heads=b * h)}")
        return shape, q, k, v

    for b, h, t, d, s in cross:
        tiled_against_xla(b, h, t, d, s)
    for b, h, t, d in cases:
        shape, q, k, v = tiled_against_xla(b, h, t, d, t)
        # one full row and one cut inside a tile, so the in-tile mask and
        # the skipped tail tiles both run
        true_len = jnp.asarray(
            [t] + [max(1, (5 * t) // 8 + 3)] * (b - 1), jnp.int32)

        ragged = jax.jit(ragged_attention).lower(q, k, v, true_len).compile()
        report.check(f"ragged kernel in compiled text [{shape}]",
                     "tpu_custom_call" in ragged.as_text())
        close(f"ragged [{shape}]", ragged(q, k, v, true_len),
              jax.jit(ragged_attention_reference)(
                  q, k, v, true_len, q_true_len=true_len))


# -- shared: weights, images --------------------------------------------------

def build_params(report: Report, family, policy, seed: int):
    """Seeded random weights in the policy's storage dtype, made on the
    device (bench.family_params: one jitted call per component)."""
    import jax

    import bench

    t0 = time.perf_counter()
    params = bench.family_params(family, dtype=policy.param_dtype, seed=seed)
    jax.block_until_ready(params)
    leaves = jax.tree_util.tree_leaves(params)
    report.fact("weights", f"{family.name} seed {seed}, "
                f"{sum(x.size for x in leaves) / 1e6:.1f} M params, "
                f"{sum(x.nbytes for x in leaves) / 2**30:.2f} GiB as "
                f"{policy.param_dtype.name}, made in "
                f"{time.perf_counter() - t0:.1f} s")
    return params


def decode_images(b64_images):
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        b64png_to_array,
    )

    return [b64png_to_array(s) for s in b64_images]


def check_images(report: Report, name: str, images, width: int,
                 height: int) -> None:
    """Decoded PNGs: expected shape, and pixels that a broken pipeline does
    not produce — a NaN latent decodes to one flat value."""
    import numpy as np

    for i, img in enumerate(images):
        flat = img.astype(np.float64)
        saturated = float(np.mean((img == 0) | (img == 255)))
        report.check(
            f"{name} image {i} is {height}x{width}x3 uint8, not constant",
            img.shape == (height, width, 3) and img.dtype == np.uint8
            and float(flat.std()) > 1.0 and saturated < 0.5,
            f"mean {flat.mean():.1f} std {flat.std():.1f} "
            f"saturated {saturated:.3f}")


def image_distance(a, b) -> tuple[int, float]:
    """(largest pixel difference in levels, PSNR in dB; 99 when identical)."""
    import numpy as np

    diff = a.astype(np.float64) - b.astype(np.float64)
    mse = float(np.mean(diff ** 2))
    psnr = 99.0 if mse == 0 else float(10.0 * np.log10(255.0 ** 2 / mse))
    return int(np.max(np.abs(diff))), psnr


def check_reordered(report: Report, name: str, got, want) -> None:
    worst, psnr = image_distance(got, want)
    report.check(name, psnr >= REORDER_PSNR_FLOOR_DB,
                 f"largest pixel difference {worst} levels, PSNR "
                 f"{psnr:.1f} dB, floor {REORDER_PSNR_FLOOR_DB} dB")


# -- phase: the main path over HTTP -------------------------------------------

def _http_json(url: str, body=None):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=1100) as resp:
        return resp.status, json.loads(resp.read())


def phase_serve(report: Report, counter: CompileCounter, family, policy,
                width: int, height: int, steps: int, seed: int) -> None:
    """api -> dispatcher -> engine -> decode -> fetch -> PNG, over HTTP with
    the default bucket and batch ladders."""
    import jax

    from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
    from stable_diffusion_webui_distributed_tpu.runtime import native
    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        BenchmarkPayload,
    )
    from stable_diffusion_webui_distributed_tpu.server.api import ApiServer

    engine = Engine(family, build_params(report, family, policy, seed),
                    policy=policy, model_name=f"{family.name}-smoke")
    server = ApiServer(engine, port=0).start()
    base = f"http://127.0.0.1:{server.port}"
    calibration = BenchmarkPayload(width=width, height=height, steps=steps)

    def stage_builds() -> int:
        _, status = _http_json(f"{base}/internal/status")
        return sum(status["serving"]["compiles"].values())

    def txt2img(name: str, batch: int, first_seed: int):
        builds0 = stage_builds()
        xla0 = counter.counts["executables"]
        body = dict(calibration.model_dump(), batch_size=batch,
                    seed=first_seed)
        t0 = time.perf_counter()
        status, out = _http_json(f"{base}/sdapi/v1/txt2img", body)
        seconds = time.perf_counter() - t0   # ends in the HTTP response
        builds = stage_builds() - builds0
        xla = counter.counts["executables"] - xla0
        report.fact(f"request {name}",
                    f"HTTP {status}, batch {batch}, seed {first_seed}, "
                    f"{seconds:.2f} s, {builds} stage builds, "
                    f"{xla} XLA executables made")
        report.check(f"request {name} answered 200 with {batch} image(s)",
                     status == 200 and len(out["images"]) == batch)
        seeds = json.loads(out["info"])["all_seeds"]
        report.check(f"request {name} used consecutive seeds",
                     seeds == list(range(first_seed, first_seed + batch)),
                     str(seeds))
        return out["images"], seconds, builds, xla

    try:
        cold, cold_s, _, _ = txt2img("cold", 1, seed)
        warm, warm_s, builds, xla = txt2img("repeat", 1, seed)
        pair, _, _, _ = txt2img("batch-2", 2, seed)
        second, _, _, _ = txt2img("second-seed", 1, seed + 1)
    finally:
        server.stop()

    report.fact("cold request seconds (with compile)", f"{cold_s:.2f}")
    report.fact("warm request seconds", f"{warm_s:.2f}")
    report.check("repeat is byte-identical", warm == cold)
    report.check("repeat compiled nothing", builds == 0 and xla == 0,
                 f"{builds} stage builds, {xla} XLA executables made")

    singles = decode_images(cold + second)
    pair_images = decode_images(pair)
    check_images(report, "batch-1", singles, width, height)
    check_images(report, "batch-2", pair_images, width, height)
    report.check("different seeds give different images",
                 image_distance(singles[0], singles[1])[0] > 0)
    worst = max(image_distance(p, s)[0]
                for p, s in zip(pair_images, singles))
    report.fact("seed-exact contract, largest pixel difference between "
                "batch-2 rows and the matching batch-1 images",
                f"{worst} levels")
    for i, (p, s) in enumerate(zip(pair_images, singles)):
        check_reordered(report, f"batch-2 row {i} matches its batch-1 image",
                        p, s)

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    report.check("device reports peak memory", bool(peak),
                 f"peak_bytes_in_use {peak}"
                 + (f" = {peak / 2**30:.2f} GiB" if peak else ""))
    encoder = native.active_encoder()
    report.check("PNGs encoded by native/png_encoder.cpp",
                 encoder == "native", f"encoder {encoder}")


# -- phase: four chips ---------------------------------------------------------

def phase_mesh(report: Report, counter: CompileCounter, family, policy,
               width: int, height: int, steps: int, seed: int,
               mesh_specs=("dp=4", "dp=2,tp=2")) -> None:
    """The same engine at one batch on one chip and over each mesh, direct
    ``Engine.txt2img``: agreement, bytes on every device, output sharding."""
    import jax

    from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        BenchmarkPayload,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.mesh import build_mesh

    devices = jax.devices()
    batch = len(devices)
    params = build_params(report, family, policy, seed)
    calibration = BenchmarkPayload(width=width, height=height, steps=steps)

    def generate(name: str, engine, n: int, first_seed: int):
        payload = GenerationPayload(**dict(
            calibration.model_dump(), batch_size=n, seed=first_seed))
        # the decode executable's output is the last thing that can say
        # where the output lived; Engine.txt2img returns PNG strings
        shardings = []
        decode_fn = engine._decode_u8_fn

        def watch(*shape):
            decode = decode_fn(*shape)

            def decode_and_note(*args):
                imgs = decode(*args)
                shardings.append(imgs.sharding)
                return imgs

            return decode_and_note

        engine._decode_u8_fn = watch
        xla0 = counter.counts["executables"]
        t0 = time.perf_counter()
        result = engine.txt2img(payload)   # returns fetched PNGs
        seconds = time.perf_counter() - t0
        del engine._decode_u8_fn
        report.fact(f"run {name}", f"batch {n}, seed {first_seed}, "
                    f"{seconds:.2f} s, "
                    f"{counter.counts['executables'] - xla0} XLA "
                    "executables made")
        images = decode_images(result.images)
        report.check(f"run {name} returned {n} image(s)", len(images) == n)
        check_images(report, name, images, width, height)
        return images, shardings

    one = Engine(family, params, policy=policy,
                 model_name=f"{family.name}-smoke")
    base, _ = generate("one chip", one, batch, seed)
    # a dp split with one image per device has the per-shard shape of a
    # batch-1 run: the pair __graft_entry__ holds bit-exact on CPU devices
    solo = [generate(f"one chip, image {i} alone", one, 1, seed + i)[0][0]
            for i in range(batch)]

    for spec in mesh_specs:
        mesh = build_mesh(spec)
        engine = Engine(family, params, policy=policy, mesh=mesh,
                        model_name=f"{family.name}-smoke-{spec}")
        images, shardings = generate(f"mesh {spec}", engine, batch, seed)
        spans = [len(s.device_set) for s in shardings]
        report.check(
            f"mesh {spec}: decoded output is sharded over {batch} devices",
            bool(spans) and all(n == batch for n in spans)
            and not any(s.is_fully_replicated for s in shardings),
            f"device counts {spans}, "
            f"specs {[str(getattr(s, 'spec', s)) for s in shardings]}")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        report.check(f"mesh {spec}: every device holds bytes",
                     all(in_use), "bytes_in_use " + ", ".join(
                         f"{d.id}:{b}" for d, b in zip(devices, in_use)))
        for i, img in enumerate(images):
            check_reordered(
                report, f"mesh {spec} image {i} matches the one-chip batch",
                img, base[i])
        if mesh.shape["dp"] == batch:
            # bit-exact on CPU devices; the chip's partitioned program is
            # another program than the batch-1 one and rounds differently
            # (8 levels, PR 21), so the pair is held to the reorder floor
            # and the exact difference is a fact, not a check
            worst = max(image_distance(a, b)[0]
                        for a, b in zip(images, solo))
            report.fact(f"mesh {spec} against each image computed alone, "
                        "largest pixel difference", f"{worst} levels")
            for i, img in enumerate(images):
                check_reordered(
                    report,
                    f"mesh {spec} image {i} matches that image alone",
                    img, solo[i])


# -- entry ----------------------------------------------------------------------

def run(args, device: dict) -> bool:
    import jax

    print(f"device: {json.dumps(device)}", flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device['platform']!r}",
              file=sys.stderr, flush=True)
        return False
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{device['count']} device(s)", file=sys.stderr, flush=True)
        return False

    from stable_diffusion_webui_distributed_tpu.models.configs import FAMILIES
    from stable_diffusion_webui_distributed_tpu.runtime import dtypes

    report = Report()
    counter = CompileCounter()
    t0 = time.perf_counter()
    cache_dir = phase_cache(report)
    family, size, steps = FAMILIES["sd15"], 512, 20
    if args.chips == 1:
        phase_kernels(report, KERNEL_CASES, args.seed, CROSS_CASES)
        phase_serve(report, counter, family, dtypes.TPU, size, size, steps,
                    args.seed)
    else:
        phase_mesh(report, counter, family, dtypes.TPU, size, size, steps,
                   args.seed)
    report.fact("compile cache entries after", cache_entries(cache_dir))
    report.fact("XLA", ", ".join(f"{v} {k}" for k, v in
                                 counter.counts.items()))
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        report.fact(f"device {dev.id} memory",
                    f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}, "
                    f"bytes_in_use {stats.get('bytes_in_use')}")
    report.fact("total seconds", f"{time.perf_counter() - t0:.1f}")
    if report.failed:
        print("failed checks: " + "; ".join(report.failed), flush=True)
    return not report.failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1 (default): kernels + the serving path over "
                         "HTTP. 4: only the dp=4 and dp=2,tp=2 meshes "
                         "against one chip")
    ap.add_argument("--seed", type=int, default=1,
                    help="seed of the weights, the q/k/v and the first "
                         "image (default 1)")
    args = ap.parse_args(argv)

    ok, device = False, None
    try:
        device = device_record()
        ok = run(args, device)
    except Exception:  # reported, never passed over: the exit code is 1
        traceback.print_exc()
        sys.stderr.flush()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""AOT warmup: pre-build the bucket ladder's executables at server start.

The chunk executable is keyed on exact ``(sampler, steps, width, height,
batch)`` — so with shape bucketing in front, the full set of executables
a server will ever dispatch is known AT STARTUP: the bucket ladder times
the batch ladder at the configured serving defaults.  Warmup runs one
tiny generation per bucket so every stage (text encode, chunk loop, VAE
decode) is built — and, with the persistent XLA cache enabled
(``runtime/mesh.py:enable_compilation_cache``), compiled artifacts land
on disk, so even a RESTARTED server re-serves its first request at
dispatch cost rather than compile cost.

Knobs: ``SDTPU_WARMUP`` (0 disables, default on when invoked),
``SDTPU_WARMUP_STEPS`` / ``SDTPU_WARMUP_SAMPLER`` pick the (steps,
sampler) point to pre-build — warmup only pays off for the step counts
traffic actually uses, since steps are part of the compile key.
``SDTPU_WARMUP_PRECISIONS`` (comma-separated, default "" = policy
default only) adds serving-precision rungs to the sweep — e.g.
``bf16,int8`` pre-builds the int8 ladder too, so the first fleet-degraded
or user-requested int8 request dispatches instead of compiling
(pipeline/precision.py; precision is a static compile-key axis).
``SDTPU_WARMUP_LORA`` (comma-separated ``rXsY`` cells, default "" =
none) adds traced-LoRA ladder cells — e.g. ``r16s1,r32s2`` pre-builds
the executables every adapter bucketed into those cells will share
(models/lora.py ladder; under SDTPU_LORA_TRACED adapter CONTENT is a
jit argument, so one all-zero stand-in set per cell covers all of them).

The sweep places the compile cache, so its stages keep their programs
beside it (serving/aot.py): on a restart every program the store holds is
deserialized instead of traced and compiled (seconds, not minutes), only
the missing ones pay a trace, and each of those back-fills the store. The
report's ``programs`` block says how many came each way and where they
are kept.
"""

from __future__ import annotations

import re
import time
from typing import Dict, List, Optional

from stable_diffusion_webui_distributed_tpu.runtime.config import (
    env_int, env_str,
)
from stable_diffusion_webui_distributed_tpu.serving import aot as aot_mod
from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
    ShapeBucketer,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import METRICS


def _warmup_precisions() -> List[str]:
    """Precision rungs to sweep (bucketed onto the PRECISIONS ladder;
    "" = the engine policy's default). Default is the single empty entry,
    so warmup cost is unchanged unless the operator opts in."""
    from stable_diffusion_webui_distributed_tpu.pipeline import (
        precision as precision_mod,
    )

    raw = env_str("SDTPU_WARMUP_PRECISIONS", "")
    if not raw.strip():
        return [""]
    out: List[str] = []
    for part in raw.split(","):
        name = precision_mod.bucket_precision(part, "")
        entry = name if part.strip() else ""
        if entry not in out:
            out.append(entry)
    return out or [""]


def _warmup_lora_cells() -> List[Optional[tuple]]:
    """Traced-LoRA ladder cells to sweep, parsed from SDTPU_WARMUP_LORA
    ("r16s1,r32s2" → [(16, 1), (32, 2)]); None = the adapterless point.
    Cells are bucketed onto the configured ladders, so "r10s3" warms the
    (16, 4) executables a rank-10, 3-adapter request would dispatch to.
    Ignored (adapterless only) unless SDTPU_LORA_TRACED is on — the
    merged path shares the adapterless executables."""
    from stable_diffusion_webui_distributed_tpu.models import lora as lora_mod

    raw = env_str("SDTPU_WARMUP_LORA", "")
    if not raw.strip() or not lora_mod.traced_enabled():
        return [None]
    out: List[Optional[tuple]] = [None]
    for part in raw.split(","):
        part = part.strip().lower()
        if not part:
            continue
        m = re.fullmatch(r"r(\d+)s(\d+)", part)
        if m is None:
            continue
        rb = lora_mod.bucket_rank(int(m.group(1)))
        sc = lora_mod.bucket_slots(int(m.group(2)))
        if rb is None or sc is None:
            continue
        cell = (rb, sc)
        if cell not in out:
            out.append(cell)
    return out


def warmup_engine(engine, bucketer: Optional[ShapeBucketer] = None,
                  steps: Optional[int] = None,
                  sampler: Optional[str] = None) -> Dict:
    """Pre-lower every (shape, batch[, precision]) bucket's pipeline;
    returns a report of how many stage builds the sweep triggered and its
    wall time."""
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
        enable_compilation_cache,
    )

    if env_str("SDTPU_WARMUP") == "0":
        return {"skipped": True, "reason": "SDTPU_WARMUP=0"}

    active_cache = enable_compilation_cache()
    bucketer = bucketer or ShapeBucketer()
    steps = steps if steps is not None else env_int("SDTPU_WARMUP_STEPS", 20)
    sampler = sampler or env_str("SDTPU_WARMUP_SAMPLER", "Euler a")

    precisions = _warmup_precisions()
    lora_cells = _warmup_lora_cells()
    summary0 = METRICS.summary()
    before = dict(summary0["compiles"])
    programs_before = summary0["programs"]
    t0 = time.monotonic()
    warmed = []
    try:
        for bw, bh in bucketer.shapes:
            for nb in bucketer.batches:
                for prec in precisions:
                    for cell in lora_cells:
                        engine._warmup_lora = cell
                        payload = GenerationPayload(
                            prompt="", steps=steps, width=bw, height=bh,
                            batch_size=nb, sampler_name=sampler, seed=0,
                            precision=prec)
                        engine.state.begin_request()
                        engine.generate_range(payload, 0, None, "warmup")
                        point = [bw, bh, nb]
                        if prec != "":
                            point.append(prec)
                        if cell is not None:
                            point.append("r%ds%d" % cell)
                        warmed.append(tuple(point))
    finally:
        engine._warmup_lora = None
        engine._traced_lora = None
    summary1 = METRICS.summary()
    after = summary1["compiles"]
    built = {k: after.get(k, 0) - before.get(k, 0)
             for k in after if after.get(k, 0) != before.get(k, 0)}
    programs = {k: summary1["programs"][k] - programs_before[k]
                for k in ("loaded", "traced", "load_s")}
    report = {
        "skipped": False,
        "buckets": warmed,
        "steps": steps,
        "sampler": sampler,
        "precisions": precisions,
        "lora_cells": ["r%ds%d" % c for c in lora_cells if c is not None],
        "stage_builds": built,
        "xla_cache_dir": active_cache,
        # how the sweep's programs came to be (loaded from the store
        # beside the compile cache, or traced and kept there)
        "programs": dict(programs, dir=aot_mod.store_dir()),
        "wall_s": round(time.monotonic() - t0, 2),
    }
    return report

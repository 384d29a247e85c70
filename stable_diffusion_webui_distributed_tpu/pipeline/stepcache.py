"""Step-cache policy: DeepCache-style deep-feature reuse + CFG truncation.

PERF.md's round-5 roofline put the SDXL north-star ABOVE the bf16
roofline for the as-specified workload — the remaining gap is FLOPs per
image, not MFU. This module holds the host-side policy for the two
step-level FLOP levers the engine implements:

- **Deep-feature reuse** (``SDTPU_DEEPCACHE``, refresh cadence N): deep
  UNet features (everything below ``models/unet.py:CACHE_SPLIT`` plus the
  mid block) vary slowly across adjacent denoise steps; on non-refresh
  steps only the shallow down blocks + up path run, starting from the
  cached deep feature (SwiftDiffusion / DeepCache observation).
- **CFG truncation** (``SDTPU_CFG_CUTOFF``, a sigma threshold): below the
  threshold the classifier-free-guidance uncond branch stops mattering;
  the engine drops the uncond half of the batched cond/uncond UNet call,
  halving those steps' FLOPs ("Speed Is All You Need" trick).

Recompile discipline: the only *static* compile-key bit the levers add is
"step cache on/off" — the cadence value itself and the cutoff step index
travel as traced data inside the chunk executable (``lax.cond`` selects
refresh-vs-reuse / full-vs-truncated per step). Requested cadences are
quantized onto :data:`CADENCE_LADDER` (:func:`bucket_cadence`, the RC001
bucket-ladder rule) so serving-side coalescing groups on a bounded key
set; together that mints at most 2 chunk executables per shape bucket
(plain + step-cache).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from stable_diffusion_webui_distributed_tpu.runtime.config import (
    env_float,
    env_int,
)

#: Sanctioned refresh cadences. Request/env values are rounded DOWN onto
#: the ladder (never reuse a staler feature than asked for); values above
#: the top rung clamp to it. 1 = cache off.
CADENCE_LADDER = (1, 2, 3, 4, 6, 8)


def bucket_cadence(cadence) -> int:
    """Quantize a requested refresh cadence onto :data:`CADENCE_LADDER`.

    This is the RC001 bucket-ladder quantization for the step-cache
    compile key: every distinct static value mints an XLA executable, so
    the env/request-derived cadence must pass through here before it can
    influence one."""
    try:
        c = int(cadence)
    except (TypeError, ValueError):
        return 1
    c = max(1, c)
    best = 1
    for rung in CADENCE_LADDER:
        if rung <= c:
            best = rung
    return best


@dataclasses.dataclass(frozen=True)
class StepCacheSpec:
    """Resolved step-cache policy for one request."""

    cadence: int = 1          # bucketed refresh cadence; 1 = cache off
    cutoff_sigma: float = 0.0  # CFG truncation threshold; 0 = off

    @property
    def active(self) -> bool:
        return self.cadence > 1 or self.cutoff_sigma > 0.0


def resolve(payload=None) -> StepCacheSpec:
    """Env defaults (``SDTPU_DEEPCACHE`` / ``SDTPU_CFG_CUTOFF``) with
    per-request ``override_settings`` keys ``deepcache`` / ``cfg_cutoff``
    on top (the same channel webui options ride in)."""
    cad = env_int("SDTPU_DEEPCACHE", 1)
    cut = env_float("SDTPU_CFG_CUTOFF", 0.0)
    ov = getattr(payload, "override_settings", None) or {}
    if "deepcache" in ov:
        cad = ov.get("deepcache")
    if "cfg_cutoff" in ov:
        try:
            cut = float(ov.get("cfg_cutoff"))
        except (TypeError, ValueError):
            pass
    return StepCacheSpec(cadence=bucket_cadence(cad),
                         cutoff_sigma=max(0.0, float(cut or 0.0)))


def cutoff_step(sigmas: Sequence[float], cutoff_sigma: float) -> int:
    """Map a sigma threshold onto the built (descending) sigma ladder:
    the smallest step index whose sigma is BELOW the threshold — steps at
    or past it run cond-only. Disabled (<= 0) or never-reached thresholds
    return ``len(sigmas) - 1`` (one past the last step index, i.e. the
    in-graph ``i >= cutoff`` predicate never fires). Same searchsorted
    mapping the adaptive path uses for CN guidance windows."""
    n = len(sigmas) - 1
    if cutoff_sigma <= 0.0:
        return n
    asc = np.asarray(sigmas, dtype=np.float64)[::-1].copy()
    j = int(np.searchsorted(asc, cutoff_sigma, side="left"))
    return min(max(n - j + 1, 0), n)


def prefix_boundary(pos: int, cadence: int, cfg_stop: int,
                    min_steps: int) -> bool:
    """Is chunk boundary ``pos`` a legal denoise-prefix split point
    (cache/prefix.py)?

    Three byte-identity constraints, all derived from how the chunk loop
    stitches state across dispatches:

    - ``pos >= min_steps`` — a capture shallower than the configured
      floor saves too little to pay its host sync;
    - ``pos % cadence == 0`` — a resumed range re-enters with an INVALID
      deep-feature cache, so its first step refreshes; a continuous run
      refreshes at ``pos`` only when the cadence lands there. Off-cadence
      splits would make the resumed tail diverge from the continuous one.
    - ``pos <= cfg_stop`` — the shared prefix must have run full CFG:
      past the cutoff the trajectory already depends on the divergent
      truncation parameter the prefix key deliberately excludes.
    """
    if pos < max(1, int(min_steps)):
        return False
    if int(cadence) > 1 and pos % int(cadence) != 0:
        return False
    return pos <= int(cfg_stop)

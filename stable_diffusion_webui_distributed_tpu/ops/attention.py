"""Which attention a UNet site takes: the tiled Pallas kernel or XLA.

One place decides, from what the call itself shows: the platform, whether
it is self-attention, the shapes and the dtype. No environment variable and
no option: a shape where the kernel has not measured faster stays on
``jax.nn.dot_product_attention``.

The readings that set :data:`TILED_MIN_TOKENS` (one v5e, bf16, CFG batch 2,
each alone in a device-side loop of 20 calls, median of 5; my chip runs,
PR 25: ``chip_smoke.py`` prints the first six on every run, the rest are
from the tile sweep; PERF.md section 6 has the table):

    (B*H, T, D)        XLA ms    tiled ms
    (20, 4096, 64)     3.713     1.106     SDXL 64x64
    (40, 1024, 64)     0.532     0.191     SDXL 32x32
    (16, 4096, 40)     2.974     0.928     SD1.5 64x64
    (16, 1024, 80)     0.097     0.087     SD1.5 32x32
    (16,  256, 160)    0.035     0.043     SD1.5 16x16
    (16,   64, 160)    0.028     0.036     SD1.5 8x8
    (24, 4096, 64)     4.454     1.369     SDXL refiner
    (48, 1024, 64)     0.629     0.229     SDXL refiner
    (40,  256, 64)     0.044     0.056     SD2.1 16x16
    (40,   64, 64)     0.036     0.045     SD2.1 8x8

At 1024 tokens and above the kernel wins at every head size measured; at
256 and under XLA's score matrix is 5 MB or less and XLA wins.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.ops.flash_attention import (
    blocks, flash_attention,
)

#: fewest tokens at which the tiled kernel measured faster than XLA (above)
TILED_MIN_TOKENS = 1024

TILED = "tiled"
XLA = "xla"


def choose(platform: str, t: int, s: int, dtype, *,
           self_attention: bool) -> str:
    """``"tiled"`` or ``"xla"`` for one site, from what the site shows.

    Tiled wants a TPU, self-attention (cross-attention's 77-token context
    is small and does not tile), the serving policy's bf16 (the only dtype
    timed) and a sequence at or over the crossover that tiles evenly."""
    if (platform == "tpu" and self_attention
            and jnp.dtype(dtype) == jnp.bfloat16
            and t >= TILED_MIN_TOKENS and blocks(t, s) is not None):
        return TILED
    return XLA


def attend(q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float,
           impl: str = "auto", self_attention: bool):
    """(output, path taken) for ``(B, T, H, D)`` q and ``(B, S, H, D)`` k, v.

    ``impl`` "auto" asks :func:`choose`; "flash" forces the kernel on
    self-attention wherever the sequence tiles (tests, chip_smoke.py);
    anything else is XLA."""
    t, s = q.shape[1], k.shape[1]
    if impl == "auto":
        path = choose(jax.default_backend(), t, s, q.dtype,
                      self_attention=self_attention)
    elif impl == "flash" and self_attention and blocks(t, s) is not None:
        path = TILED
    else:
        path = XLA
    if path == TILED:
        return flash_attention(q, k, v, scale=scale), path
    return jax.nn.dot_product_attention(q, k, v, scale=scale), path

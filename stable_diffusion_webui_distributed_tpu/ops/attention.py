"""Which attention a site takes: the tiled Pallas kernel or XLA.

One place decides, from what the call itself shows: the platform, the
shapes (queries, keys, batch x heads) and the dtype, whether it is masked
and whether query heads share KV heads. No environment variable and no option:
a shape where the kernel has not measured faster stays on XLA
(``jax.nn.dot_product_attention`` for a UNet's sites; a decoder LM's
causal, windowed, grouped-query sites over a cache, which the kernel cannot
take at all, go to :func:`attend_positions`, and to :func:`attend_two_ranges`
where the keys are two buffers: a decode step of several sequences forked
from one prefill, models/lm.py ``Attention`` with ``sequences``, whose
queries all attend the prefill's rows, read once for all of them, and each
its own rows behind them. A prefill's chunk, a one-sequence decode step and
a latent layer take :func:`attend_positions`).

The readings that set :data:`TILED_MIN_TOKENS` (one v5e, bf16, each alone
in a device-side loop of 20 calls, median of 5; ``chip_smoke.py`` prints
the first eight on every run: my chip run, PR 57, with every head size's
heads side by side in the lanes; the last four are PR 25's, from the tile
sweep; PERF.md section 6 has the tables):

    (B*H, T, D)        XLA ms    tiled ms
    (20, 4096, 64)     3.727     1.117     SDXL 64x64
    (40, 1024, 64)     0.530     0.187     SDXL 32x32
    (16, 4096, 40)     2.975     0.818     SD1.5 64x64
    (16, 1024, 80)     0.098     0.096     SD1.5 32x32
    (16,  256, 160)    0.036     0.046     SD1.5 16x16
    (16,   64, 160)    0.038     0.040     SD1.5 8x8
    (64, 4096, 40)    11.756     3.214     SD1.5 64x64, four images
    (64, 1024, 80)     0.823     0.287     SD1.5 32x32, four images
    (24, 4096, 64)     4.454     1.369     SDXL refiner
    (48, 1024, 64)     0.629     0.229     SDXL refiner
    (40,  256, 64)     0.044     0.056     SD2.1 16x16
    (40,   64, 64)     0.036     0.045     SD2.1 8x8

``chip_smoke.py`` hands the kernel ``(B, T, H, D)`` arrays, whose
flattening to ``(B, T, H*D)`` is a copy there and none in a UNet, where
the qkv projection leaves that shape. On ``(B, T, H*D)`` operands, with
whatever copies a layout needs counted (my chip run, PR 57): 0.794 ms at
``(16, 4096, 40)``, 0.080 at ``(16, 1024, 80)``, 3.092 at ``(64, 4096,
40)`` and 0.252 at ``(64, 1024, 80)``, where one head a block through
``(B*H, T, D)`` copies read 0.973, 0.106, 4.027 and 0.339.

At 1024 tokens and above the kernel wins at every head size measured; at
256 and under XLA's score matrix is 5 MB or less and XLA wins.

Cross-attention over a context of ``n * 77`` keys, which the kernel pads to
128 or 256 rows and masks (``ops/flash_attention.py:_tiled_keys``; my chip
runs, PR 60: alone in ``chip_smoke.py``'s loop as above, and the same site
inside the cells' ``jit_run_chunk`` by ``benchmarks/op_table.py``, where
nothing is flattened and XLA fuses around the call; PERF.md sections 5, 6):

                              alone              in the cell
    (B, T, S, H, D)           XLA ms   tiled ms   XLA ms   tiled ms
    (2, 4096, 231, 8, 40)     0.083    0.076      0.040    0.034   SD1.5, 3 chunks
    (2, 1024, 231, 8, 80)     0.046    0.051      0.012    0.011
    (8, 4096, 231, 8, 40)     0.739    0.211      0.695    0.136   four images
    (8, 1024, 231, 8, 80)     0.089    0.109      0.048    0.044
    (4, 4096, 231, 8, 40)     0.396    0.123                       two images
    (2, 4096, 77, 10, 64)     0.055    0.102      0.020    0.036   SDXL
    (2, 1024, 77, 20, 64)     0.045    0.066      0.010    0.022
    (4, 4096, 77, 10, 64)     0.076    0.162               0.072   sdxl_pair
    (4, 1024, 77, 20, 64)     0.056    0.105               0.044

XLA's path is three ops with the float32 scores and the bf16 probabilities
between them, six bytes a score. Where those fit the core's 128 MiB of VMEM
(91 MB at SD1.5's batch 2 and at its 32x32 sites of batch 8, 38 and 76 MB
in SDXL) the compiled cell keeps them there (memory space ``S(1)`` on the
arrays in the solo cell's optimised HLO): SDXL's sites are twice as fast on
XLA, and SD1.5's 91 MB sites within 0.6 ms a request of the kernel (ahead
by 8 to 15 % of a call), which no cell resolves. Where they do not fit (363
MB at ``(8, 4096, 231, 8, 40)``, 182 at batch 4) both arrays go through HBM
and the kernel is three to five times faster. :data:`SCORE_BYTES` and
:data:`ON_CHIP_BYTES` are that line.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.ops.flash_attention import (
    blocks, flash_attention,
)

#: fewest tokens at which the tiled kernel measured faster than XLA (above)
TILED_MIN_TOKENS = 1024
#: what XLA's attention holds for one score between its three ops: the
#: float32 score and the bf16 probability
SCORE_BYTES = 6
#: a v5e core's VMEM: scores over this go through HBM on XLA's path
ON_CHIP_BYTES = 128 * 2 ** 20

TILED = "tiled"
XLA = "xla"


def choose(platform: str, t: int, s: int, dtype, *, batch_heads: int = 1,
           masked: bool = False, kv_groups: int = 1) -> str:
    """``"tiled"`` or ``"xla"`` for one site, from what the site shows.

    Tiled wants a TPU, the serving policy's bf16 (the only dtype timed) and
    ``t`` queries at or over the crossover that tile evenly. Over ``s`` keys
    at or over the crossover too (self-attention) that is all. Over a short
    context (cross-attention's ``n * 77`` keys) XLA is ahead as long as it
    keeps the scores on chip, so such a site goes to the kernel only where
    ``batch_heads * t * s`` scores at :data:`SCORE_BYTES` each are more than
    :data:`ON_CHIP_BYTES`. The kernel takes no mask of a caller's and one KV
    head a query head, so a causal or windowed site (``masked``) and a
    grouped-query one (``kv_groups`` query heads a KV head) stay on XLA;
    neither has been timed on the kernel."""
    if masked or kv_groups != 1:
        return XLA
    if not (platform == "tpu" and jnp.dtype(dtype) == jnp.bfloat16
            and t >= TILED_MIN_TOKENS and blocks(t, s) is not None):
        return XLA
    if s < TILED_MIN_TOKENS and (batch_heads * t * s * SCORE_BYTES
                                 <= ON_CHIP_BYTES):
        return XLA
    return TILED


def attend(q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float,
           impl: str = "auto"):
    """(output, path taken) for ``(B, T, H, D)`` q and ``(B, S, H, D)`` k, v.

    ``impl`` "auto" asks :func:`choose`; "flash" forces the kernel wherever
    the sequences tile (tests, chip_smoke.py); anything else is XLA."""
    b, t, h, _ = q.shape
    s = k.shape[1]
    if impl == "auto":
        path = choose(jax.default_backend(), t, s, q.dtype, batch_heads=b * h)
    elif impl == "flash" and blocks(t, s) is not None:
        path = TILED
    else:
        path = XLA
    if path == TILED:
        return flash_attention(q, k, v, scale=scale), path
    return jax.nn.dot_product_attention(q, k, v, scale=scale), path


def _weights(scores: jax.Array, seen: jax.Array) -> jax.Array:
    """The softmax of float32 ``scores`` over the keys ``seen`` (last
    axis); a query that sees none gets zeros."""
    scores = jnp.where(seen, scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    weights = jnp.exp(scores - jnp.where(jnp.isfinite(top), top, 0.0))
    total = jnp.sum(weights, axis=-1, keepdims=True)
    return weights / jnp.where(total > 0, total, 1.0)


def _seen(q_pos: jax.Array, k_pos: jax.Array, window: int) -> jax.Array:
    """``(T, S)``: whether query ``i`` sees key ``j``. ``k_pos`` ``(T, S)``:
    the keys' positions as query ``i`` counts them (each query a sequence
    with rows of its own: :func:`attend_two_ranges`)."""
    if k_pos.ndim == 2:
        delta = q_pos[:, None] - k_pos
        seen = (delta >= 0) & (k_pos >= 0)
    else:
        delta = q_pos[:, None] - k_pos[None, :]
        seen = (delta >= 0) & (k_pos[None, :] >= 0)
    if window:
        seen &= delta < window
    return seen


def attend_positions(q: jax.Array, k: jax.Array, v: jax.Array,
                     q_pos: jax.Array, k_pos: jax.Array, *, scale: float,
                     window: int = 0):
    """(output, path taken) for causal, optionally windowed, grouped-query
    attention over keys that carry their own positions: a decoder LM's site
    (models/lm.py), where the keys are a cache.

    ``q`` is ``(T, H, D)``, ``k`` ``(S, KV, D)`` and ``v`` ``(S, KV, Dv)``
    (a value need not be as wide as a key; the output is ``(T, H, Dv)``)
    with ``H`` a multiple of ``KV``: query head ``j`` attends KV head
    ``j // (H / KV)``.
    ``q_pos`` ``(T,)`` and ``k_pos`` ``(S,)`` are token positions; a key
    with a negative position is an empty slot. Query ``i`` sees key ``j``
    when ``0 <= i - j`` and, with ``window`` over 0, ``i - j < window``.
    Scores and softmax are float32; the output has ``q``'s dtype. A query
    that sees no key (a padded row) gets zeros."""
    t, heads, dim = q.shape
    s, kv, _ = k.shape
    groups = heads // kv
    path = choose(jax.default_backend(), t, s, q.dtype, masked=True,
                  kv_groups=groups)
    seen = _seen(q_pos, k_pos, window)
    scores = jnp.einsum("tkgd,skd->kgts", q.reshape(t, kv, groups, dim), k,
                        preferred_element_type=jnp.float32) * scale
    weights = _weights(scores, seen[None, None])
    out = jnp.einsum("kgts,skd->tkgd", weights.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(t, heads, v.shape[-1]).astype(q.dtype), path


def attend_two_ranges(q: jax.Array, k_shared: jax.Array, v_shared: jax.Array,
                      k_own: jax.Array, v_own: jax.Array, q_pos: jax.Array,
                      shared_pos: jax.Array, own_pos: jax.Array, *,
                      scale: float, window: int = 0):
    """:func:`attend_positions` for ``B`` sequences of one query each whose
    keys are two ranges under ONE softmax: ``k_shared`` ``(S, KV, D)`` and
    ``v_shared``, which every sequence attends, and ``k_own`` ``(B, T, KV,
    D)`` and ``v_own``, sequence ``b``'s own rows. ``q`` is ``(B, H, D)``,
    ``q_pos`` ``(B,)``, ``shared_pos`` ``(S,)`` and ``own_pos`` ``(T,)``
    (slot ``j`` of every sequence's own rows holds the same position) or
    ``(B, T)`` (a position a sequence a slot: sequences that stand at
    different positions, models/lm.py:own_positions).
    Sequence ``b`` gets what ``attend_positions`` gives its query over the
    shared keys followed by its own.

    The sequences are the shared product's query rows: a KV head's shared
    keys are read once for all ``B`` of them, not once a sequence. The same
    masking rules, float32 scores and softmax, output in ``q``'s dtype."""
    b, heads, dim = q.shape
    s, kv, _ = k_shared.shape
    groups = heads // kv
    path = choose(jax.default_backend(), 1, s + k_own.shape[1], q.dtype,
                  masked=True, kv_groups=groups)
    q = q.reshape(b, kv, groups, dim)
    scores = jnp.concatenate(
        [jnp.einsum("bkgd,skd->kgbs", q, k_shared,
                    preferred_element_type=jnp.float32),
         jnp.einsum("bkgd,bskd->kgbs", q, k_own,
                    preferred_element_type=jnp.float32)], axis=-1) * scale
    seen = jnp.concatenate([_seen(q_pos, shared_pos, window),
                            _seen(q_pos, own_pos, window)], axis=-1)
    to_shared, to_own = jnp.split(
        _weights(scores, seen[None, None]).astype(v_shared.dtype), [s],
        axis=-1)
    out = jnp.einsum("kgbs,skd->bkgd", to_shared, v_shared,
                     preferred_element_type=jnp.float32) \
        + jnp.einsum("kgbs,bskd->bkgd", to_own, v_own,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, heads, v_shared.shape[-1]).astype(q.dtype), path

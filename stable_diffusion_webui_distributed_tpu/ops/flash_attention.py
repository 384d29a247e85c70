"""Tiled online-softmax attention (Pallas) for the UNet's attention sites.

``jax.nn.dot_product_attention`` writes the whole (T x S) score matrix to
HBM and reads it back: 1.34 GB a layer at SDXL 1024² (T = 4096, 20 batch x
heads, f32), which pins the layer to the HBM roofline at 12 % of the MXU's
(PERF.md section 6, PR 24). Here the scores never leave VMEM. The grid is
``(batch, head group, T/block_q, S/block_k)``; up to S = 4096 one K/V block
holds the whole sequence (512 KB a head at head_dim 64) and a grid step is
one plain softmax over a q tile, beyond that (the hires second pass, up to
65 536 tokens) the running state (m, l, acc) lives in VMEM scratch across
the k steps of a q tile. q, k, v go into the MXU in their own dtype (bf16
under the serving policy) with float32 accumulation; the softmax statistics
and the accumulator are float32, and the probabilities are cast to v's
dtype for P·V exactly as XLA's path does.

Heads stay side by side in the lanes: q, k, v are ``(B, T, H*D)`` as the
qkv projection leaves them and nothing is transposed in HBM
(:func:`heads_per_block`). Where ``128 % D == 0`` one block is the 128 lanes
of ``128 // D`` neighbouring heads (one SDXL self-attention sublayer on a
v5e: 1.27 ms against 1.47 ms through ``(B*H, T, D)`` copies; PERF.md
section 6, PR 25). Any other head size (SD1.5's 40, 80, 160) takes all ``H``
heads in one block, which is legal because it is the array's whole last
dimension: 320 lanes at T = 4096, 640 at T = 1024. A ``(B, T, 8, 40)`` copy
for the other layout holds 40 of every 128 lanes in HBM, and a site needed
four of them (PERF.md section 6, PR 57). Only a width whose K and V blocks
would not fit the kernel's VMEM goes through ``(B*H, T, D)``.

A key count off the sublane tiling is a cross-attention context (``n * 77``
tokens: 77, 231) and takes a kernel of its own, :func:`_keys_kernel`, under
its own jitted name :func:`_tiled_keys` (a trace's reader prices a
``_tiled`` call as ``T x T`` work from its result's shape, and must not find
this one). k and v are padded with zero rows to the next multiple of 128 and
the kernel is told the true key count: the scores at or past it are set to
``-inf`` before the maximum, so they weigh ``exp(-inf) = 0``. With so few
keys the scores lie TRANSPOSED in that kernel, keys on the sublanes and
queries on the lanes: a row's maximum over 256 lanes is seven rotations of
the cross-lane unit for every two vregs of scores, and the kernel above took
0.62 ms at SD1.5's ``(8, 4096, 231, 8, 40)`` where XLA, writing the float32
scores to HBM, takes 0.74; over the sublanes a maximum is one elementwise
pass, the sum rides the MXU as a row of ones under ``v^T``, and the call
takes 0.21 ms (PERF.md section 6, PR 60). A query sequence off the tiling
falls back to ``jax.nn.dot_product_attention``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from stable_diffusion_webui_distributed_tpu.serving.metrics import ATTENTION

_NT = (((1,), (1,)), ((), ()))   # q @ k.T without a transpose in the kernel

#: score-tile budget, in elements: block_q * block_k float32 scores are
#: 4 MiB of VMEM, their exponentials as much again. On a v5e q tiles of 256,
#: 512 and 1024 against K/V of 4096 all ran the SDXL layer in 1.12-1.13 ms,
#: and Mosaic's compile time grows with the tile (1.7, 3.3, 6.3 s).
_SCORE_TILE = 1 << 20
#: longest K/V block; longer sequences take the online-softmax steps
_MAX_BLOCK_K = 4096
#: scoped VMEM the kernel may use: about three times what the largest tile
#: needs (double-buffered K and V blocks, scores, exponentials), and under
#: half of a v5e core's 128 MiB. The compiler's default of 16 MiB is short.
_VMEM_LIMIT = 48 * 2 ** 20


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *scratch, heads: int,
                 head_dim: int, k_steps: int, scale: float):
    """One (batch, head group, q-tile, k-block) step.

    Refs are ``(1, block, heads*head_dim)``: ``heads`` heads side by side
    in the lanes. With one K/V block for the whole sequence there is no
    running state and no scratch."""
    j = pl.program_id(3)
    if k_steps > 1:
        m_ref, l_ref, acc_ref = scratch

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    # bf16 products are exact in the float32 accumulator, and Mosaic refuses
    # bf16 operands at a higher precision: a caller's
    # jax.default_matmul_precision("highest") must not reach these dots
    precision = (jax.lax.Precision.DEFAULT
                 if q_ref.dtype == jnp.bfloat16 else None)
    for g in range(heads):
        lanes = slice(g * head_dim, (g + 1) * head_dim)
        q = q_ref[0, :, lanes] * scale                       # (block_q, D)
        k = k_ref[0, :, lanes]                               # (block_k, D)
        v = v_ref[0, :, lanes]
        s = jax.lax.dot_general(q, k, _NT, precision=precision,
                                preferred_element_type=jnp.float32)
        if k_steps == 1:
            p = jnp.exp(s - s.max(axis=-1, keepdims=True))
            l = p.sum(axis=-1, keepdims=True)
            o = jnp.dot(p.astype(v.dtype), v, precision=precision,
                        preferred_element_type=jnp.float32)
            o_ref[0, :, lanes] = (o / l).astype(o_ref.dtype)
            continue
        m_prev = m_ref[g]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[g] = m_new
        l_ref[g] = l_ref[g] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
            p.astype(v.dtype), v, precision=precision,
            preferred_element_type=jnp.float32)

    if k_steps > 1:
        @pl.when(j == k_steps - 1)
        def _finalize():
            for g in range(heads):
                lanes = slice(g * head_dim, (g + 1) * head_dim)
                o_ref[0, :, lanes] = (acc_ref[g] / l_ref[g]).astype(
                    o_ref.dtype)


def _keys_kernel(q_ref, k_ref, vt_ref, o_ref, *, heads: int, head_dim: int,
                 scale: float, keys: int):
    """One (batch, head group, q-tile) step over a short key sequence whose
    first ``keys`` rows are keys and the rest padding.

    ``q_ref`` and ``o_ref`` are ``(1, block_q, heads*head_dim)``, ``k_ref``
    ``(1, S, heads*head_dim)``, ``vt_ref`` ``(1, heads, rows, S)``: a head's
    values transposed, and at row :func:`_ones_row` a row of ones over the
    keys, so that ``v^T p`` carries the softmax's denominator. The scores are
    ``(S, block_q)``: the maximum and the mask run over the sublanes."""
    block_q, s_len = q_ref.shape[1], k_ref.shape[1]
    precision = (jax.lax.Precision.DEFAULT
                 if q_ref.dtype == jnp.bfloat16 else None)
    padding = jax.lax.broadcasted_iota(jnp.int32, (s_len, block_q), 0) >= keys
    ones = _ones_row(head_dim)
    outs = []
    for g in range(heads):
        lanes = slice(g * head_dim, (g + 1) * head_dim)
        q = q_ref[0, :, lanes] * scale                       # (block_q, D)
        s = jax.lax.dot_general(k_ref[0, :, lanes], q, _NT,
                                precision=precision,
                                preferred_element_type=jnp.float32)
        s = jnp.where(padding, -jnp.inf, s)
        p = jnp.exp(s - s.max(axis=0, keepdims=True))        # (S, block_q)
        o = jnp.dot(vt_ref[0, g], p.astype(vt_ref.dtype), precision=precision,
                    preferred_element_type=jnp.float32)      # (rows, block_q)
        outs.append(o[:head_dim] / o[ones:ones + 1])
    width = heads * head_dim
    if width % 128:     # the transposition wants whole 128-lane tiles
        outs.append(jnp.zeros((-width % 128, block_q), jnp.float32))
    o_ref[0] = jnp.concatenate(outs, axis=0).T[:, :width].astype(o_ref.dtype)


def _ones_row(head_dim: int) -> int:
    """Where ``v^T`` carries its row of ones: the first sublane tile behind
    the values."""
    return -(-head_dim // 8) * 8


def _keys_call(q, k, vt, *, keys: int, heads: int, head_dim: int,
               block_q: int, scale: float, interpret: bool):
    """:func:`_keys_kernel` on ``(N, T, W)`` q, ``(N, S, W)`` k and ``(N,
    W / head_dim, rows, S)`` vt, the first ``keys`` of ``S`` keys."""
    from jax.experimental.pallas import tpu as pltpu

    n, t, w = q.shape
    s_len = k.shape[1]
    width = heads * head_dim
    all_heads = n * (w // head_dim)
    q_spec = pl.BlockSpec((1, block_q, width), lambda b, h, i: (b, i, h))
    return pl.pallas_call(
        functools.partial(_keys_kernel, heads=heads, head_dim=head_dim,
                          scale=scale, keys=keys),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(n, w // width, t // block_q),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, s_len, width), lambda b, h, i: (b, 0, h)),
            pl.BlockSpec((1, heads, vt.shape[2], s_len),
                         lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=q_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=4 * all_heads * t * keys * head_dim,
            transcendentals=all_heads * t * keys,
            bytes_accessed=q.dtype.itemsize * (2 * q.size + k.size + vt.size)),
        interpret=interpret,
    )(q, k, vt)


@functools.partial(jax.jit, static_argnames=(
    "heads", "head_dim", "block_q", "scale", "interpret"))
def _tiled_keys(q, k, v, *, heads: int, head_dim: int, block_q: int,
                scale: float, interpret: bool):
    """:func:`_tiled` for a key count off the tiling: ``(N, T, W) x (N, S,
    W) -> (N, T, W)`` through :func:`_keys_kernel`, k and v padded to
    :func:`padded` rows in one block. Under a name of its own: the call's
    work is ``T x S``, not what a ``_tiled`` result's shape says."""
    n, keys, w = k.shape
    rows = (0, padded(keys) - keys)
    ones = _ones_row(head_dim)
    vt = jnp.concatenate(
        [v.reshape(n, keys, w // head_dim, head_dim).transpose(0, 2, 3, 1),
         jnp.zeros((n, w // head_dim, ones - head_dim, keys), v.dtype),
         jnp.ones((n, w // head_dim, 8, keys), v.dtype)], axis=2)
    return _keys_call(q, jnp.pad(k, ((0, 0), rows, (0, 0))),
                      jnp.pad(vt, ((0, 0), (0, 0), (0, 0), rows)),
                      keys=keys, heads=heads, head_dim=head_dim,
                      block_q=block_q, scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "heads", "head_dim", "block_q", "block_k", "scale", "interpret"))
def _tiled(q, k, v, *, heads: int, head_dim: int, block_q: int,
           block_k: int, scale: float, interpret: bool):
    """``(N, T, W) x (N, S, W) -> (N, T, W)`` with ``W = groups * heads *
    head_dim``: ``heads`` heads to a block, side by side in the lanes.

    Jitted on its own so that the 70 attention sites of one UNet trace and
    lower the kernel once per shape, not once per site."""
    from jax.experimental.pallas import tpu as pltpu

    n, t, w = q.shape
    s_len = k.shape[1]
    width = heads * head_dim
    k_steps = s_len // block_k
    kernel = functools.partial(_attn_kernel, heads=heads, head_dim=head_dim,
                               k_steps=k_steps, scale=scale)
    all_heads = n * (w // head_dim)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(n, w // width, t // block_q, k_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, width), lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((1, block_k, width), lambda b, h, i, j: (b, j, h)),
            pl.BlockSpec((1, block_k, width), lambda b, h, i, j: (b, j, h)),
        ],
        out_specs=pl.BlockSpec((1, block_q, width),
                               lambda b, h, i, j: (b, i, h)),
        scratch_shapes=[] if k_steps == 1 else [
            pltpu.VMEM((heads, block_q, 1), jnp.float32),          # max m
            pltpu.VMEM((heads, block_q, 1), jnp.float32),          # denom l
            pltpu.VMEM((heads, block_q, head_dim), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        # XLA's cost analysis sees a custom call as free: say what it does
        cost_estimate=pl.CostEstimate(
            flops=4 * all_heads * t * s_len * head_dim,
            transcendentals=all_heads * t * s_len,
            bytes_accessed=q.dtype.itemsize * (2 * q.size + k.size + v.size)),
        interpret=interpret,
    )(q, k, v)


def _block(n: int, cap: int) -> int | None:
    """The whole of ``n`` when it fits under ``cap``, else its largest
    divisor up to ``cap`` that is a multiple of 128; None when there is
    none."""
    if n <= cap:
        return n
    return next((b for b in range(cap - cap % 128, 0, -128) if n % b == 0),
                None)


def padded(s: int) -> int:
    """The K/V rows the kernel is handed for ``s`` keys: ``s`` itself on
    the sublane tiling, else the next multiple of 128 (77 -> 128, 231 ->
    256), the rows past ``s`` masked (:func:`_tiled_keys`)."""
    return s if s % 8 == 0 else -(-s // 128) * 128


def blocks(t: int, s: int) -> tuple[int, int] | None:
    """(block_q, block_k) from the shape, or None when ``(t, s)`` does not
    tile: queries under 8 tokens or off the sublane tiling, a long sequence
    with no divisor that is a multiple of 128, or keys off the tiling that
    do not fit one block padded. Those take a q tile of up to 2048, which
    read 9 % faster than 1024 in their kernel (PERF.md section 6, PR 60)."""
    if t % 8:
        return None
    if s % 8:
        block_k, widest = padded(s), 2048
        if block_k > _MAX_BLOCK_K:
            return None
    else:
        block_k, widest = _block(s, _MAX_BLOCK_K), 1024
        if block_k is None:
            return None
    block_q = _block(t, max(128, min(widest, _SCORE_TILE // block_k)))
    return None if block_q is None else (block_q, block_k)


def heads_per_block(h: int, d: int, block_q: int, block_k: int,
                    itemsize: int) -> int:
    """How many heads ride side by side in the lanes of one block, from the
    shape alone; 0 when the kernel has to be handed ``(B*H, T, D)``.

    ``128 // d`` neighbours where they fill 128 lanes exactly; else all
    ``h``, when the double-buffered q, k, v and output blocks of the whole
    ``h * d`` width (padded to whole 128-lane tiles), one head's scores,
    exponentials and probabilities and every head's running state stay
    under :data:`_VMEM_LIMIT` (SD1.5 at T = 4096: 26 of the 48 MiB)."""
    if 128 % d == 0 and h % (128 // d) == 0:
        return 128 // d
    lanes = -(-h * d // 128) * 128
    blocks_bytes = 2 * itemsize * lanes * 2 * (block_q + block_k)
    scores_bytes = (4 + 4 + itemsize) * block_q * block_k
    state_bytes = 3 * 4 * h * block_q * max(128, d)      # m, l, acc
    return (h if blocks_bytes + scores_bytes + state_bytes <= _VMEM_LIMIT
            else 0)


def flash_attention(
    q: jax.Array,      # (B, T, H, D)
    k: jax.Array,      # (B, S, H, D)
    v: jax.Array,      # (B, S, H, D)
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Drop-in for ``jax.nn.dot_product_attention`` (no mask/bias path).

    Tile sizes come from the shape (:func:`blocks`); ``block_q`` and
    ``block_k`` override them for tests. A key count off the sublane tiling
    is padded and masked in one K/V block (:func:`_tiled_keys`); a sequence
    that does not tile takes the XLA path."""
    b, t, h, d = q.shape
    s = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if block_q is None and block_k is None:
        chosen = blocks(t, s)
    else:
        block_q = min(block_q or t, t)
        block_k = padded(s) if s % 8 else min(block_k or s, s)
        chosen = (None if t % block_q or padded(s) % block_k
                  else (block_q, block_k))
    if chosen is None:
        return jax.nn.dot_product_attention(q, k, v, scale=scale)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if s % 8:
        call = functools.partial(_tiled_keys, head_dim=d, block_q=chosen[0],
                                 scale=float(scale), interpret=interpret)
    else:
        call = functools.partial(_tiled, head_dim=d, block_q=chosen[0],
                                 block_k=chosen[1], scale=float(scale),
                                 interpret=interpret)

    heads = heads_per_block(h, d, *chosen, q.dtype.itemsize)
    ATTENTION.record_layout("lanes" if heads else "heads_major")
    if heads:
        def flat(x):
            return x.reshape(b, x.shape[1], h * d)

        return call(flat(q), flat(k), flat(v),
                    heads=heads).reshape(b, t, h, d)

    def to_bhtd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    out = call(to_bhtd(q), to_bhtd(k), to_bhtd(v), heads=1)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)

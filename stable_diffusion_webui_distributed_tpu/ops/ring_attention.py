"""Ring attention: sequence parallelism over the mesh's ``sp`` axis.

For resolutions whose latent-token count outgrows one chip (hires 2048²+ =
65k tokens), Q/K/V are sharded over tokens on the ``sp`` axis; each device
computes attention of its local query shard against K/V blocks that rotate
around the ring via ``lax.ppermute`` over ICI, accumulated with the online
softmax (permutation-invariant, so ring order never changes the result).
This is the blockwise/ring-attention recipe the task brief makes
first-class; the reference has no counterpart (its long-sequence axis is
pixels, handled by per-worker caps — SURVEY.md §5 long-context).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


_RING_CHUNK_DEFAULT = 1024


def _ring_chunk() -> int:
    """Upper bound on the key-block chunk folded per inner step
    (SDTPU_RING_CHUNK, default 1024): the per-device score buffer is
    (b, h, t_loc, chunk) instead of (b, h, t_loc, t_loc) — at the hires
    65k-token scale a full local score matrix would be GBs of HBM per
    ring step; chunked folding keeps it flat."""
    from stable_diffusion_webui_distributed_tpu.runtime.config import env_int

    return max(128, env_int("SDTPU_RING_CHUNK", _RING_CHUNK_DEFAULT))


def _ring_body(q, k, v, axis_name: str, scale: float, vary_axes=None):
    """Per-device computation: local Q against the rotating K/V ring.

    Each ring step folds its K/V block into the running online softmax in
    bounded key-chunks (an inner ``lax.scan``) — the same associative
    (m, l, acc) update at two granularities, so the result is identical
    to the dense fold up to float summation order."""
    n = lax.psum(1, axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    b, t_loc, h, d = q.shape
    qf = q.astype(jnp.float32) * scale

    # fresh accumulators must be marked device-varying over every mesh axis
    # the inputs vary over (the ring axis, plus dp on combined dp+sp
    # meshes) or the fori_loop carry types disagree under shard_map
    def varying(x):
        return lax.pcast(x, vary_axes or axis_name, to="varying")

    m0 = varying(jnp.full((b, h, t_loc, 1), -jnp.inf, jnp.float32))
    l0 = varying(jnp.zeros((b, h, t_loc, 1), jnp.float32))
    acc0 = varying(jnp.zeros((b, h, t_loc, d), jnp.float32))

    s_loc = k.shape[1]
    chunk = min(_ring_chunk(), s_loc)
    # non-divisor request: pad the local K/V block up to the next chunk
    # multiple and mask the tail (scores -> -inf, so exp -> 0 and the
    # padded keys contribute nothing to l or acc). This keeps the HBM
    # bound of the chunked fold at every resolution without degrading the
    # chunk size toward 1 when s_loc is near-prime.
    n_chunks = -(-s_loc // chunk)
    pad = n_chunks * chunk - s_loc
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # (n_chunks, chunk) validity mask for key positions; only the final
    # chunk can contain padding, but carrying it through the scan keeps
    # the fold uniform
    key_valid = (jnp.arange(n_chunks * chunk) < s_loc).reshape(
        n_chunks, chunk)

    def fold(carry, kv):
        m, l, acc = carry
        k_c, v_c, valid_c = kv                      # (b, chunk, h, d)
        s = jnp.einsum("bthd,bshd->bhts", qf, k_c.astype(jnp.float32))
        if pad:
            s = jnp.where(valid_c[None, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhts,bshd->bhtd", p, v_c.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    def step(_, carry):
        m, l, acc, k_blk, v_blk = carry
        if n_chunks == 1:
            (m, l, acc), _ = fold((m, l, acc), (k_blk, v_blk, key_valid[0]))
        else:
            kc = k_blk.reshape(b, n_chunks, chunk, h, d).transpose(
                1, 0, 2, 3, 4)
            vc = v_blk.reshape(b, n_chunks, chunk, h, d).transpose(
                1, 0, 2, 3, 4)
            (m, l, acc), _ = lax.scan(fold, (m, l, acc), (kc, vc, key_valid))
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return m, l, acc, k_next, v_next

    m, l, acc, _, _ = lax.fori_loop(0, n, step, (m0, l0, acc0, k, v))
    out = acc / l                                  # (b, h, t_loc, d)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention(
    q: jax.Array,      # (B, T, H, D), T sharded over `axis_name`
    k: jax.Array,
    v: jax.Array,
    mesh,
    axis_name: str = "sp",
    scale: Optional[float] = None,
) -> jax.Array:
    """Sequence-parallel attention over ``mesh``'s ``axis_name`` ring.

    Inputs/outputs are global arrays; sharding is applied here via
    ``shard_map`` (batch replicated or dp-sharded upstream; tokens split
    over the ring axis).
    """
    from jax.sharding import PartitionSpec as P

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    # carry the dp axis on the batch dim when the mesh has one — otherwise
    # shard_map would declare the batch replicated and XLA would all-gather
    # activations across dp at every layer
    dp = "dp" if mesh.shape.get("dp", 1) > 1 else None
    spec = P(dp, axis_name, None, None)
    vary_axes = (axis_name, dp) if dp else (axis_name,)

    def body(q_l, k_l, v_l):
        return _ring_body(q_l, k_l, v_l, axis_name, scale, vary_axes)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)

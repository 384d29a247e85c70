"""Denoising UNet (SD 1.x / SDXL families) in Flax, TPU-first.

The reference never touches this network — it lives inside every remote
sdwui process the reference fans requests out to
(/root/reference/scripts/spartan/worker.py:432-435). Here it is the hot loop.

TPU-first choices:
- NHWC everywhere (flax Conv default): feeds the MXU's native conv layout.
- bf16 matmuls/convs with f32 GroupNorm statistics and f32 residual adds at
  block boundaries — bit-growth control without banding artifacts.
- One fused QKV matmul for self-attention, fused KV for cross-attention.
- Static shapes: spatial dims are compile-time constants; the time step and
  conditioning are data, so one compilation serves every prompt/seed/step
  count at a given resolution bucket.
- ``remat`` on transformer blocks (optional) trades FLOPs for HBM at big
  batch sizes.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.models.configs import UNetConfig
from stable_diffusion_webui_distributed_tpu.models.lora import (
    apply_site as _lora_site,
)
from stable_diffusion_webui_distributed_tpu.ops.quant import (
    conv as _conv,
    linear as _linear,
)
from stable_diffusion_webui_distributed_tpu.ops.upsample import UpsampleConv
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, NORM,
)


def timestep_embedding(t: jax.Array, dim: int, max_period: float = 10000.0) -> jax.Array:
    """Sinusoidal embedding, (B,) -> (B, dim). f32: frequencies span 1e4."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


#: GroupNorm32 pins its input (below) from this many spatial positions a row
#: on: SDXL's 128x128 level. Measured on a v5e (PERF.md section 6, PR 29):
#: there the pin takes 306 ms off a request. Under it a pinned INPUT costs
#: more than the float32 copies it saves (those lie in on-chip memory there):
#: a convolution whose input comes from behind a barrier, and not from a
#: neighbour in the spatial-major form, runs batch-major at a third of the
#: speed (PERF.md section 6, PR 65: ``conv2`` of ten SD1.5 ResBlocks, the
#: module 334 -> 352 ms).
PIN_MIN_POSITIONS = 128 * 128

#: ...and under it, up to this many rows (one image beside its unconditional
#: twin), the norm's SUMS read a pinned copy and a ResBlock's 1x1 ``skip``
#: is a matrix product over the positions. There the TPU compiler tiles the
#: two rows alone on the sublanes (``T(2,128)``): every pass over the
#: activation fills a quarter of a register and the 1x1 convolution runs
#: batch-major. A pinned copy for the sums gives the activation whole tiles
#: and leaves the normalise, between two convolutions, in their form
#: (PERF.md section 6, PR 65, one v5e: SD1.5's module 334.3 -> 316.2 ms,
#: SDXL's 2 912 -> 2 900; at four rows SDXL's 5 816 -> 5 839, so not there).
TWO_ROW_TILE = 2


def two_row_tiles(rows: int, dtype) -> bool:
    """Whether a narrow activation of this many rows gets such tiles."""
    return dtype != jnp.float32 and rows <= TWO_ROW_TILE


def norm_form(rows: int, positions: int, dtype) -> str:
    """How a GroupNorm site of this static shape is traced: ``pinned`` (the
    whole norm behind an optimisation barrier), ``stats_pinned`` (only the
    sums) or ``plain``. A float32 activation (the VAE) has no cast to hold
    out of its neighbours: a barrier there forces float32 tensors XLA
    otherwise keeps in bf16."""
    if dtype == jnp.float32:
        return "plain"
    if positions >= PIN_MIN_POSITIONS:
        return "pinned"
    return "stats_pinned" if two_row_tiles(rows, dtype) else "plain"


class _ChannelAffine(nn.Module):
    """``scale`` and ``bias`` per channel, float32, ones and zeros."""

    @nn.compact
    def __call__(self, channels: int) -> Tuple[jax.Array, jax.Array]:
        scale = self.param("scale", nn.initializers.ones, (channels,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (channels,),
                          jnp.float32)
        return scale, bias


class GroupNorm32(nn.Module):
    """GroupNorm with f32 statistics regardless of activation dtype.

    Per-channel sums over the activation as it lies (the cast to float32
    lives inside the reduction), folded into groups on the ``(B, C)``
    vectors, then one elementwise pass ``x * a + b`` in float32 that writes
    the storage dtype; the activation is never viewed by groups.

    A narrower-than-float32 activation is pinned by an optimisation barrier
    where :func:`norm_form` says so, from its static shape. With
    ``PIN_MIN_POSITIONS`` or more positions a row the whole norm reads the
    pinned activation: without it the TPU compiler hoists the cast into the
    producing convolution's epilogue, in that convolution's spatial-major
    shape with the batch folded into a block index, and then moves float32
    copies of the activation, of its square and of the broadcast ``a`` and
    ``b`` through HBM (PERF.md section 6, PR 29: 2.9 GB of float32 copies an
    SDXL step). Under that size, at ``TWO_ROW_TILE`` rows or fewer, only the
    sums read a pinned copy: the normalise stays in its neighbours' layout
    and the activation leaves the two-row tiles (PR 65). Every site is
    counted at trace time by its form (serving/metrics.py ``NORM``).

    Parameters sit at ``gn/scale`` and ``gn/bias``, where
    ``flax.linen.GroupNorm`` under that name kept them.
    """

    num_groups: int = 32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        batch, channels = x.shape[0], x.shape[-1]
        groups = min(self.num_groups, channels)
        per_group = channels // groups
        positions = x.size // (batch * channels)
        scale, bias = _ChannelAffine(name="gn")(channels)
        form = norm_form(batch, positions, x.dtype)
        if not self.is_initializing():
            NORM.record(form)
        if form == "pinned":
            x = jax.lax.optimization_barrier(x)
        spatial = tuple(range(1, x.ndim - 1))
        x32 = x.astype(jnp.float32)
        summed = x32
        if form == "stats_pinned":
            summed = jax.lax.optimization_barrier(x).astype(jnp.float32)

        def group_mean(per_channel_sum: jax.Array) -> jax.Array:
            grouped = per_channel_sum.reshape(batch, groups, per_group).sum(-1)
            return jnp.repeat(grouped / (positions * per_group), per_group,
                              axis=-1)

        mean = group_mean(summed.sum(spatial))
        var = jnp.maximum(0.0, group_mean(jnp.square(summed).sum(spatial))
                          - jnp.square(mean))
        a = scale * jax.lax.rsqrt(var + 1e-6)  # flax GroupNorm's epsilon
        b = bias - mean * a
        a, b = (jnp.expand_dims(v, spatial) for v in (a, b))
        return (x32 * a + b).astype(x.dtype)


class PointwiseConv(nn.Module):
    """A 1x1 convolution to ``features`` channels as one matrix product
    over the flattened positions, under ``nn.Conv``'s parameter names."""

    features: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (1, 1, x.shape[-1], self.features))
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        batch, channels = x.shape[0], x.shape[-1]
        # the product in the activation's shape BEFORE the bias: with the
        # bias added over the flattened positions XLA emits the product in
        # float32 and copies it (PERF.md section 6, PR 66, one v5e, two
        # rows: SDXL's module 2 900.4 -> 2 883.4 ms, SD1.5's 316.2 -> 314.4)
        out = jnp.dot(x.astype(self.dtype).reshape(batch, -1, channels),
                      kernel[0, 0].astype(self.dtype))
        return (out.reshape(*x.shape[:-1], self.features)
                + bias.astype(self.dtype))


class ResBlock(nn.Module):
    out_channels: int
    dtype: jnp.dtype = jnp.float32
    quant_convs: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, temb: jax.Array) -> jax.Array:
        qc = self.quant_convs
        h = nn.silu(GroupNorm32(name="norm1")(x))
        h = _conv(qc, self.out_channels, padding=1, dtype=self.dtype,
                  name="conv1")(h)
        t = nn.Dense(self.out_channels, dtype=self.dtype, name="time_proj")(
            nn.silu(temb)
        )
        h = h + t[:, None, None]
        h = nn.silu(GroupNorm32(name="norm2")(h))
        h = _conv(qc, self.out_channels, padding=1, dtype=self.dtype,
                  name="conv2")(h)
        if x.shape[-1] != self.out_channels:
            # two rows on the sublanes: XLA runs the 1x1 convolution
            # batch-major, the product over the positions as a Dense
            if two_row_tiles(x.shape[0], self.dtype) and not qc:
                x = PointwiseConv(self.out_channels, dtype=self.dtype,
                                  name="skip")(x)
            else:
                x = _conv(qc, self.out_channels, (1, 1), padding=0,
                          dtype=self.dtype, name="skip")(x)
        return (x.astype(jnp.float32) + h.astype(jnp.float32)).astype(self.dtype)


class Attention(nn.Module):
    """Self- or cross-attention over flattened spatial tokens.

    ``impl``: "auto" (ops/attention.py chooses per site, from platform,
    shape and dtype, between the tiled Pallas kernel and XLA), "xla"
    (compiler-fused, always), "flash" (the tiled kernel wherever the
    sequence tiles), "ring" (sequence-parallel over the mesh's ``sp`` axis
    for token counts beyond one chip — requires ``mesh``), or "ragged"
    (per-row true-length masked kernel, ops/ragged_attention.py).
    Cross-attention follows "auto", "xla" and "flash" like self-attention
    (its context of ``n * 77`` tokens padded and masked in the kernel) and
    takes the XLA path under "ring" and "ragged", as does any shape the
    chosen impl can't tile. Every site is counted at trace time by the
    path it took (serving/metrics.py ``ATTENTION``).

    ``true_len`` (traced (B,) int32, optional) forces the ragged path
    regardless of ``impl``: for self-attention the row's valid spatial
    prefix, for cross-attention the row's valid context prefix — the
    ragged-dispatch contract where heterogeneous rows share one
    bucket-shaped executable.
    """

    num_heads: int
    dtype: jnp.dtype = jnp.float32
    impl: str = "auto"
    mesh: Optional[object] = None
    quant_linears: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, context: Optional[jax.Array] = None,
                 true_len: Optional[jax.Array] = None,
                 lora=None) -> jax.Array:
        B, T, C = x.shape
        head_dim = C // self.num_heads
        qz = self.quant_linears
        if context is None:
            qkv = _linear(qz, 3 * C, use_bias=False, dtype=self.dtype,
                          name="qkv")(x)
            qkv = _lora_site(qkv, x, lora, "qkv")
            q, k, v = jnp.split(qkv, 3, axis=-1)
            ctx_len = T
        else:
            q = _linear(qz, C, use_bias=False, dtype=self.dtype, name="q")(x)
            q = _lora_site(q, x, lora, "q")
            kv = _linear(qz, 2 * C, use_bias=False, dtype=self.dtype,
                         name="kv")(context)
            kv = _lora_site(kv, context, lora, "kv")
            k, v = jnp.split(kv, 2, axis=-1)
            ctx_len = context.shape[1]

        q = q.reshape(B, T, self.num_heads, head_dim)
        k = k.reshape(B, ctx_len, self.num_heads, head_dim)
        v = v.reshape(B, ctx_len, self.num_heads, head_dim)
        sp = (self.mesh.shape.get("sp", 1)
              if (self.impl == "ring" and self.mesh is not None) else 1)
        dp_ok = (self.mesh is None
                 or B % max(1, self.mesh.shape.get("dp", 1)) == 0)
        if context is None and (true_len is not None
                                or self.impl == "ragged"):
            from stable_diffusion_webui_distributed_tpu.ops.ragged_attention import (
                ragged_attention,
            )

            tl = (true_len if true_len is not None
                  else jnp.full((B,), T, jnp.int32))
            out = ragged_attention(q, k, v, tl, scale=1.0 / head_dim**0.5)
            path = "ragged"
        elif context is not None and true_len is not None:
            # ragged cross-attention: mask padded context rows; the 77·n
            # token context is small, so the dense masked form suffices
            from stable_diffusion_webui_distributed_tpu.ops.ragged_attention import (
                ragged_attention_reference,
            )

            out = ragged_attention_reference(q, k, v, true_len,
                                             scale=1.0 / head_dim**0.5)
            path = "ragged"
        elif self.impl == "ring" and context is None and sp > 1 \
                and T % sp == 0 and dp_ok:
            from stable_diffusion_webui_distributed_tpu.ops.ring_attention import (
                ring_attention,
            )

            out = ring_attention(q, k, v, self.mesh,
                                 scale=1.0 / head_dim**0.5)
            path = "ring"
        else:
            from stable_diffusion_webui_distributed_tpu.ops.attention import (
                attend,
            )

            out, path = attend(q, k, v, scale=1.0 / head_dim**0.5,
                               impl=self.impl)
        ATTENTION.record(path, T, ctx_len, head_dim)
        out = out.reshape(B, T, C)
        y = _linear(self.quant_linears, C, dtype=self.dtype,
                    name="out_proj")(out)
        return _lora_site(y, out, lora, "out_proj")


class GEGLU(nn.Module):
    dim_out: int
    dtype: jnp.dtype = jnp.float32
    quant_linears: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, lora=None) -> jax.Array:
        h = _linear(self.quant_linears, 2 * self.dim_out, dtype=self.dtype,
                    name="proj")(x)
        h = _lora_site(h, x, lora, "proj")
        a, g = jnp.split(h, 2, axis=-1)
        return a * nn.gelu(g)


class TransformerBlock(nn.Module):
    """self-attn -> cross-attn -> GEGLU MLP, each with pre-LN + residual."""

    num_heads: int
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "auto"
    mesh: Optional[object] = None
    quant_linears: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, context: jax.Array,
                 true_len: Optional[jax.Array] = None,
                 ctx_true: Optional[jax.Array] = None,
                 lora=None) -> jax.Array:
        C = x.shape[-1]
        qz = self.quant_linears

        def sub(key):
            return None if lora is None else lora.get(key)

        x = x + Attention(self.num_heads, dtype=self.dtype,
                          impl=self.attention_impl, mesh=self.mesh,
                          quant_linears=qz, name="attn1")(
            nn.LayerNorm(dtype=jnp.float32, name="ln1")(x),
            true_len=true_len, lora=sub("attn1"),
        )
        x = x + Attention(self.num_heads, dtype=self.dtype,
                          impl=self.attention_impl,
                          quant_linears=qz, name="attn2")(
            nn.LayerNorm(dtype=jnp.float32, name="ln2")(x), context,
            true_len=ctx_true, lora=sub("attn2"),
        )
        h = nn.LayerNorm(dtype=jnp.float32, name="ln3")(x)
        g = GEGLU(4 * C, dtype=self.dtype, quant_linears=qz,
                  name="geglu")(h, lora=sub("geglu"))
        h = _linear(qz, C, dtype=self.dtype, name="ff_out")(g)
        h = _lora_site(h, g, lora, "ff_out")
        return x + h


class SpatialTransformer(nn.Module):
    """GN -> linear proj-in -> depth x TransformerBlock -> proj-out + residual."""

    depth: int
    num_heads: int
    use_remat: bool = False
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "auto"
    mesh: Optional[object] = None
    quant_linears: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, context: jax.Array,
                 true_rows: Optional[jax.Array] = None,
                 ctx_true: Optional[jax.Array] = None,
                 lora=None) -> jax.Array:
        B, H, W, C = x.shape
        residual = x
        # row-major flatten: a valid spatial prefix of true_rows rows is a
        # valid token prefix of true_rows * W tokens
        true_len = (None if true_rows is None
                    else jnp.minimum(true_rows, H).astype(jnp.int32) * W)
        hn = GroupNorm32(name="norm")(x).reshape(B, H * W, C)
        h = _linear(self.quant_linears, C, dtype=self.dtype,
                    name="proj_in")(hn)
        h = _lora_site(h, hn, lora, "proj_in")
        block = TransformerBlock
        if self.use_remat:
            block = nn.remat(TransformerBlock, static_argnums=())
        for i in range(self.depth):
            h = block(self.num_heads, dtype=self.dtype,
                      attention_impl=self.attention_impl, mesh=self.mesh,
                      quant_linears=self.quant_linears,
                      name=f"block_{i}")(h, context, true_len, ctx_true,
                                         None if lora is None
                                         else lora.get(f"block_{i}"))
        ho = _linear(self.quant_linears, C, dtype=self.dtype,
                     name="proj_out")(h)
        ho = _lora_site(ho, h, lora, "proj_out")
        return residual + ho.reshape(B, H, W, C)


class Downsample(nn.Module):
    channels: int
    dtype: jnp.dtype = jnp.float32
    quant_convs: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        return _conv(self.quant_convs, self.channels, strides=(2, 2),
                     padding=1, dtype=self.dtype, name="conv")(x)


class Upsample(nn.Module):
    channels: int
    dtype: jnp.dtype = jnp.float32
    quant_convs: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        return UpsampleConv(self.channels, dtype=self.dtype,
                            quant=self.quant_convs, name="conv")(x)


#: Depth at which the step cache splits the UNet: levels < CACHE_SPLIT are
#: "shallow" (recomputed every step), levels >= CACHE_SPLIT plus the mid
#: block are "deep" (computed on refresh steps only, reused in between —
#: DeepCache's observation that deep features vary slowly across adjacent
#: denoise steps). Split 1 maximizes the skipped FLOPs: everything below
#: the top resolution level is cached.
CACHE_SPLIT = 1


def cache_supported(cfg: UNetConfig) -> bool:
    """Deep-feature caching needs at least one level below the split."""
    return len(cfg.block_out_channels) > CACHE_SPLIT


def deep_cache_shape(cfg: UNetConfig, batch: int, lat_h: int,
                     lat_w: int) -> Tuple[int, int, int, int]:
    """Shape of the cached deep feature: the up-path hidden state right
    after the split level's Upsample — i.e. the value the shallow up path
    starts from on reuse steps. Spatial dims follow the stride-2 conv
    arithmetic (ceil halving per Downsample, doubling at the Upsample)."""
    h, w = lat_h, lat_w
    for _ in range(CACHE_SPLIT):
        h, w = (h + 1) // 2, (w + 1) // 2
    return (batch, 2 * h, 2 * w, cfg.block_out_channels[CACHE_SPLIT])


class UNet(nn.Module):
    """The full conditional denoiser.

    ``__call__(latents, timesteps, context, *, added_cond)``:
      latents (B,H,W,Cin) NHWC; timesteps (B,) f32; context (B,T,Dctx);
      added_cond: SDXL (B, projection_input_dim) vector or None.
    Returns the predicted noise/v, (B,H,W,Cout).

    Step-cache modes (``cache_mode``, a static trace-time choice):
      - ``None``: the ordinary full forward (bit-identical to the
        pre-cache code path — the golden-hash contract).
      - ``"deep"``: run conv_in + full down path + mid + the deep up
        levels (>= CACHE_SPLIT) and return the hidden state right after
        the split level's Upsample — the deep feature the engine carries
        in its denoise scan.
      - ``"reuse"``: ``cache`` required; run only conv_in + the shallow
        down levels (< CACHE_SPLIT) for fresh skips, start the up path
        from ``cache``, finish with norm_out/conv_out. This is the small
        per-step branch on non-refresh steps.
    ControlNet residual injection is full-forward only — the engine
    bypasses the cache for chunks with active CN units.
    """

    cfg: UNetConfig
    dtype: jnp.dtype = jnp.float32
    use_remat: bool = False
    attention_impl: str = "auto"
    mesh: Optional[object] = None
    # experimental dynamic W8A8 for transformer linears (ops/quant.py;
    # SDTPU_UNET_INT8=1) — the int8-MXU lever from PERF.md's roofline
    quant_linears: bool = False
    # ...and for the ResBlock/Down/Up convs (SDTPU_UNET_INT8_CONV=1) —
    # the conv-dominated configs' (#1/#3) half of the same lever;
    # conv_in/conv_out and the time MLP stay in the policy dtype
    quant_convs: bool = False

    def heads_for(self, channels: int) -> int:
        if self.cfg.num_attention_heads is not None:
            return self.cfg.num_attention_heads
        return max(1, channels // 64)

    @nn.compact
    def __call__(
        self,
        latents: jax.Array,
        timesteps: jax.Array,
        context: jax.Array,
        added_cond: Optional[jax.Array] = None,
        control_residuals: Optional[Tuple[jax.Array, ...]] = None,
        cache: Optional[jax.Array] = None,
        cache_mode: Optional[str] = None,
        true_rows: Optional[jax.Array] = None,
        ctx_true: Optional[jax.Array] = None,
        lora=None,
    ) -> jax.Array:
        c = self.cfg
        assert cache_mode in (None, "deep", "reuse"), cache_mode
        if true_rows is not None or ctx_true is not None:
            # ragged dispatch rides the plain full forward only — the
            # engine disables the step cache for ragged chunks
            assert cache_mode is None, "ragged rows exclude the step cache"
        if cache_mode is not None:
            assert cache_supported(c), \
                "step cache needs a level below CACHE_SPLIT"
            assert control_residuals is None, \
                "ControlNet requires the full forward (engine bypasses)"
        if cache_mode == "reuse":
            assert cache is not None, "reuse mode needs the cached feature"
        split = CACHE_SPLIT
        ch0 = c.block_out_channels[0]
        time_dim = 4 * ch0

        # Timestep embedding MLP.
        temb = timestep_embedding(timesteps, ch0)
        temb = nn.Dense(time_dim, dtype=self.dtype, name="time_fc1")(
            temb.astype(self.dtype)
        )
        temb = nn.Dense(time_dim, dtype=self.dtype, name="time_fc2")(nn.silu(temb))

        # SDXL micro-conditioning: pooled text + fourier(time_ids) -> MLP.
        if c.addition_embed_dim:
            assert added_cond is not None, "SDXL family requires added_cond"
            a = nn.Dense(time_dim, dtype=self.dtype, name="add_fc1")(
                added_cond.astype(self.dtype)
            )
            a = nn.Dense(time_dim, dtype=self.dtype, name="add_fc2")(nn.silu(a))
            temb = temb + a

        context = context.astype(self.dtype)
        x = nn.Conv(ch0, (3, 3), padding=1, dtype=self.dtype, name="conv_in")(
            latents.astype(self.dtype)
        )

        # --- down path ---
        # "reuse" runs only the shallow levels (< split): a shallow level's
        # Downsample output feeds the split level's down blocks AND the
        # split level's up blocks (as a skip), both of which live in the
        # cached deep half — so the last shallow Downsample is skipped too.
        n_levels = len(c.block_out_channels)
        down_levels = split if cache_mode == "reuse" else n_levels
        last_ds = split - 1 if cache_mode == "reuse" else n_levels - 1
        # Per-level valid-row counts: each stride-2 Downsample follows the
        # ceil-halving arithmetic, so rows_lvl[level] is the valid spatial
        # prefix at that level's resolution (shared by down, mid, up).
        rows_lvl = None
        if true_rows is not None:
            rows_lvl = [true_rows.astype(jnp.int32)]
            for _ in range(n_levels - 1):
                rows_lvl.append((rows_lvl[-1] + 1) // 2)
        skips = [x]
        for level, (ch, depth) in enumerate(zip(
                c.block_out_channels[:down_levels],
                c.down_blocks[:down_levels])):
            for i in range(c.layers_per_block):
                x = ResBlock(ch, dtype=self.dtype,
                             quant_convs=self.quant_convs,
                             name=f"down_{level}_res_{i}")(x, temb)
                if depth is not None:
                    x = SpatialTransformer(
                        depth, self.heads_for(ch), self.use_remat, self.dtype,
                        self.attention_impl, self.mesh,
                        quant_linears=self.quant_linears,
                        name=f"down_{level}_attn_{i}")(
                        x, context,
                        None if rows_lvl is None else rows_lvl[level],
                        ctx_true,
                        None if lora is None
                        else lora.get(f"down_{level}_attn_{i}"))
                skips.append(x)
            if level < last_ds:
                x = Downsample(ch, dtype=self.dtype,
                               quant_convs=self.quant_convs,
                               name=f"down_{level}_ds")(x)
                skips.append(x)

        if cache_mode != "reuse":
            # --- mid ---
            mid_ch = c.block_out_channels[-1]
            x = ResBlock(mid_ch, dtype=self.dtype,
                         quant_convs=self.quant_convs,
                         name="mid_res_0")(x, temb)
            if c.mid_block_depth is not None:
                x = SpatialTransformer(
                    c.mid_block_depth, self.heads_for(mid_ch), self.use_remat,
                    self.dtype, self.attention_impl, self.mesh,
                    quant_linears=self.quant_linears,
                    name="mid_attn")(
                    x, context,
                    None if rows_lvl is None else rows_lvl[-1], ctx_true,
                    None if lora is None else lora.get("mid_attn"))
            x = ResBlock(mid_ch, dtype=self.dtype,
                         quant_convs=self.quant_convs,
                         name="mid_res_1")(x, temb)

        # ControlNet residual injection: one residual per skip + one for the
        # mid block output (the standard ControlNet contract; the reference
        # only serializes the conditioning payload, control_net.py:20-79 —
        # the math lives here).
        if control_residuals is not None:
            assert len(control_residuals) == len(skips) + 1, (
                f"expected {len(skips) + 1} control residuals, "
                f"got {len(control_residuals)}")
            x = x + control_residuals[-1].astype(x.dtype)
            skips = [s + r.astype(s.dtype)
                     for s, r in zip(skips, control_residuals[:-1])]

        # --- up path (mirror of down, one extra layer per block) ---
        # "deep" stops after the split level's Upsample and returns the
        # hidden state there; "reuse" starts from it.
        up_stop = split if cache_mode == "deep" else 0
        if cache_mode == "reuse":
            x = cache.astype(self.dtype)
        for level in reversed(range(up_stop,
                                    split if cache_mode == "reuse"
                                    else n_levels)):
            ch = c.block_out_channels[level]
            depth = c.down_blocks[level]
            for i in range(c.layers_per_block + 1):
                x = jnp.concatenate([x, skips.pop()], axis=-1)
                x = ResBlock(ch, dtype=self.dtype,
                             quant_convs=self.quant_convs,
                             name=f"up_{level}_res_{i}")(x, temb)
                if depth is not None:
                    x = SpatialTransformer(
                        depth, self.heads_for(ch), self.use_remat, self.dtype,
                        self.attention_impl, self.mesh,
                        quant_linears=self.quant_linears,
                        name=f"up_{level}_attn_{i}")(
                        x, context,
                        None if rows_lvl is None else rows_lvl[level],
                        ctx_true,
                        None if lora is None
                        else lora.get(f"up_{level}_attn_{i}"))
            if level > 0:
                x = Upsample(ch, dtype=self.dtype,
                             quant_convs=self.quant_convs,
                             name=f"up_{level}_us")(x)
        if cache_mode == "deep":
            # the shallow skips stay unconsumed by design; the engine's
            # reuse branch recomputes them fresh each step
            return x
        assert not skips, f"{len(skips)} unconsumed skip connections"

        x = nn.silu(GroupNorm32(name="norm_out")(x))
        x = nn.Conv(c.out_channels, (3, 3), padding=1, dtype=jnp.float32,
                    name="conv_out")(x)
        return x.astype(jnp.float32)


def make_added_cond(
    pooled_text: jax.Array,      # (B, addition_embed_dim)
    time_ids: jax.Array,         # (B, 6): orig_h, orig_w, crop_t, crop_l, tgt_h, tgt_w
    addition_time_embed_dim: int,
) -> jax.Array:
    """SDXL micro-conditioning vector: pooled text ++ fourier(time_ids)."""
    return join_added_cond(
        pooled_text, time_id_embedding(time_ids, addition_time_embed_dim))


def time_id_embedding(time_ids: jax.Array,
                      addition_time_embed_dim: int) -> jax.Array:
    """The Fourier half of :func:`make_added_cond`, (B, ids) -> (B, ids *
    dim) float32: a function of the ids alone, so the engine keeps it."""
    B = time_ids.shape[0]
    emb = timestep_embedding(time_ids.reshape(-1), addition_time_embed_dim)
    return emb.reshape(B, -1)


def join_added_cond(pooled_text: jax.Array, emb: jax.Array) -> jax.Array:
    """The request's half: its pooled text before the embedded ids."""
    return jnp.concatenate([pooled_text.astype(jnp.float32), emb], axis=-1)

"""AutoencoderKL (VAE) in Flax: image <-> latent codec.

In the reference deployment this runs inside each sdwui worker; the master
only ever sees finished PNGs come back over HTTP
(/root/reference/scripts/distributed.py:103-106 decodes base64). Here the
decode stage is on the critical path after every denoise, so it is built to
overlap with the next batch's UNet work (separate jit unit) and defaults to
f32 (bf16 decode shows visible banding).
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.models.configs import VAEConfig
from stable_diffusion_webui_distributed_tpu.models.unet import GroupNorm32
from stable_diffusion_webui_distributed_tpu.ops.upsample import UpsampleConv


class VAEResBlock(nn.Module):
    out_channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h = nn.silu(GroupNorm32(name="norm1")(x))
        h = nn.Conv(self.out_channels, (3, 3), padding=1, dtype=self.dtype,
                    name="conv1")(h)
        h = nn.silu(GroupNorm32(name="norm2")(h))
        h = nn.Conv(self.out_channels, (3, 3), padding=1, dtype=self.dtype,
                    name="conv2")(h)
        if x.shape[-1] != self.out_channels:
            x = nn.Conv(self.out_channels, (1, 1), dtype=self.dtype,
                        name="skip")(x)
        return x + h


class VAEAttention(nn.Module):
    """Single-head spatial self-attention (the mid-block attn)."""

    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        B, H, W, C = x.shape
        h = GroupNorm32(name="norm")(x).reshape(B, H * W, C)
        qkv = nn.Dense(3 * C, dtype=self.dtype, name="qkv")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q[:, :, None]  # single head
        k = k[:, :, None]
        v = v[:, :, None]
        out = jax.nn.dot_product_attention(q, k, v, scale=1.0 / C**0.5)
        out = nn.Dense(C, dtype=self.dtype, name="out_proj")(out[:, :, 0])
        return x + out.reshape(B, H, W, C)


class Encoder(nn.Module):
    cfg: VAEConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, images: jax.Array) -> jax.Array:
        c = self.cfg
        x = nn.Conv(c.block_out_channels[0], (3, 3), padding=1,
                    dtype=self.dtype, name="conv_in")(images.astype(self.dtype))
        for level, ch in enumerate(c.block_out_channels):
            for i in range(c.layers_per_block):
                x = VAEResBlock(ch, dtype=self.dtype,
                                name=f"down_{level}_res_{i}")(x)
            if level < len(c.block_out_channels) - 1:
                x = nn.Conv(ch, (3, 3), strides=(2, 2), padding=((0, 1), (0, 1)),
                            dtype=self.dtype, name=f"down_{level}_ds")(x)
        ch = c.block_out_channels[-1]
        x = VAEResBlock(ch, dtype=self.dtype, name="mid_res_0")(x)
        x = VAEAttention(dtype=self.dtype, name="mid_attn")(x)
        x = VAEResBlock(ch, dtype=self.dtype, name="mid_res_1")(x)
        x = nn.silu(GroupNorm32(name="norm_out")(x))
        # 2*latent moments (mean, logvar).
        x = nn.Conv(2 * c.latent_channels, (3, 3), padding=1,
                    dtype=self.dtype, name="conv_out")(x)
        return nn.Conv(2 * c.latent_channels, (1, 1), dtype=self.dtype,
                       name="quant_conv")(x)


class Decoder(nn.Module):
    cfg: VAEConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, latents: jax.Array) -> jax.Array:
        c = self.cfg
        x = nn.Conv(c.latent_channels, (1, 1), dtype=self.dtype,
                    name="post_quant_conv")(latents.astype(self.dtype))
        ch = c.block_out_channels[-1]
        x = nn.Conv(ch, (3, 3), padding=1, dtype=self.dtype, name="conv_in")(x)
        x = VAEResBlock(ch, dtype=self.dtype, name="mid_res_0")(x)
        x = VAEAttention(dtype=self.dtype, name="mid_attn")(x)
        x = VAEResBlock(ch, dtype=self.dtype, name="mid_res_1")(x)
        for idx, level in enumerate(reversed(range(len(c.block_out_channels)))):
            ch = c.block_out_channels[level]
            for i in range(c.layers_per_block + 1):
                x = VAEResBlock(ch, dtype=self.dtype,
                                name=f"up_{level}_res_{i}")(x)
            if idx < len(c.block_out_channels) - 1:
                x = UpsampleConv(ch, dtype=self.dtype,
                                 name=f"up_{level}_us")(x)
        x = nn.silu(GroupNorm32(name="norm_out")(x))
        x = nn.Conv(c.in_channels, (3, 3), padding=1, dtype=jnp.float32,
                    name="conv_out")(x)
        return x.astype(jnp.float32)


class VAE(nn.Module):
    """Full codec. ``encode`` returns latent *moments*; sampling + scaling are
    done by the pipeline (so the RNG discipline stays in one place)."""

    cfg: VAEConfig
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        dec_dtype = jnp.float32 if self.cfg.force_decoder_f32 else self.dtype
        self.encoder = Encoder(self.cfg, dtype=self.dtype)
        self.decoder = Decoder(self.cfg, dtype=dec_dtype)

    def encode(self, images: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """images (B,H,W,3) in [-1,1] -> (mean, logvar), each (B,h,w,C)."""
        moments = self.encoder(images)
        mean, logvar = jnp.split(moments, 2, axis=-1)
        return mean, jnp.clip(logvar, -30.0, 20.0)

    def decode(self, latents: jax.Array) -> jax.Array:
        """latents (B,h,w,C), already un-scaled -> images (B,H,W,3) in [-1,1]."""
        return self.decoder(latents)

    def __call__(self, images: jax.Array, key: jax.Array) -> jax.Array:
        mean, logvar = self.encode(images)
        z = mean + jnp.exp(0.5 * logvar) * jax.random.normal(
            key, mean.shape, mean.dtype
        )
        return self.decode(z)

"""The UNet's forward pass, plainly: ``jax.numpy`` and ``lax`` in float32
at the highest matmul precision, no flax module of the program, no kernel,
no cache, the same parameter tree. It follows the published Stable Diffusion
UNet (ldm ``UNetModel`` / diffusers ``UNet2DConditionModel``): sinusoidal
time embedding -> MLP, ResBlocks (GroupNorm32-SiLU-conv, time projection
added between), spatial transformers (GroupNorm, linear in, [self-attention,
cross-attention, GEGLU feed-forward] x depth with pre-LayerNorm residuals,
linear out), stride-2 conv downsampling, nearest 2x + conv upsampling, skip
concatenation, and for SDXL the added pooled-text/time-ids embedding.

Departures from the papers, shared with the program and forced by its
parameter tree: q/k/v are one fused matrix in self-attention and k/v one in
cross-attention (the same linear maps, stacked); proj_in/proj_out are
linear for every family (SD1.5's 1x1 convs are the same map).

Weights arrive as the configuration stores them (bfloat16) and are upcast
leaf by leaf where used: a float32 copy of SDXL's tree (10.3 GB) does not
fit beside the program's on a 16 GB chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
EPS = 1e-6          # flax's GroupNorm and LayerNorm default


def _w(leaf):
    return jnp.asarray(leaf, F32)


def dense(p, x):
    y = jnp.matmul(x, _w(p["kernel"]), precision="highest")
    return y + _w(p["bias"]) if "bias" in p else y


def conv(p, x, stride=1, pad=1):
    y = lax.conv_general_dilated(
        x, _w(p["kernel"]), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest")
    return y + _w(p["bias"])


def group_norm(p, x, groups=32):
    b, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(b, h * w, g, c // g)
    mean = xg.mean(axis=(1, 3), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + EPS)).reshape(b, h, w, c)
    return y * _w(p["gn"]["scale"]) + _w(p["gn"]["bias"])


def layer_norm(p, x):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * _w(p["scale"]) + _w(p["bias"])


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def timestep_embedding(t, dim, max_period=10000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=F32) / half)
    args = t.astype(F32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def attention(q, k, v, heads):
    b, t, c = q.shape
    d = c // heads
    q = q.reshape(b, t, heads, d)
    k = k.reshape(b, k.shape[1], heads, d)
    v = v.reshape(b, v.shape[1], heads, d)
    logits = jnp.einsum("bthd,bshd->bhts", q, k,
                        precision="highest") / math.sqrt(d)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", weights, v, precision="highest")
    return out.reshape(b, t, c)


def res_block(p, x, temb):
    h = conv(p["conv1"], silu(group_norm(p["norm1"], x)))
    h = h + dense(p["time_proj"], silu(temb))[:, None, None]
    h = conv(p["conv2"], silu(group_norm(p["norm2"], h)))
    if "skip" in p:
        x = conv(p["skip"], x, pad=0)
    return x + h


def transformer_block(p, x, context, heads):
    h = layer_norm(p["ln1"], x)
    q, k, v = jnp.split(dense(p["attn1"]["qkv"], h), 3, axis=-1)
    x = x + dense(p["attn1"]["out_proj"], attention(q, k, v, heads))
    h = layer_norm(p["ln2"], x)
    k, v = jnp.split(dense(p["attn2"]["kv"], context), 2, axis=-1)
    x = x + dense(p["attn2"]["out_proj"],
                  attention(dense(p["attn2"]["q"], h), k, v, heads))
    a, g = jnp.split(dense(p["geglu"]["proj"], layer_norm(p["ln3"], x)),
                     2, axis=-1)
    return x + dense(p["ff_out"], a * gelu_tanh(g))


def spatial_transformer(p, x, context, depth, heads):
    b, h, w, c = x.shape
    y = dense(p["proj_in"], group_norm(p["norm"], x).reshape(b, h * w, c))
    for i in range(depth):
        y = transformer_block(p[f"block_{i}"], y, context, heads)
    return x + dense(p["proj_out"], y).reshape(b, h, w, c)


def unet_forward(cfg, params, latents, timesteps, context, added_cond=None):
    """cfg: the family's UNetConfig (sizes only). Returns float32."""
    p = params
    chans = cfg.block_out_channels

    def heads(ch):
        return cfg.num_attention_heads or max(1, ch // 64)

    temb = timestep_embedding(timesteps, chans[0])
    temb = dense(p["time_fc2"], silu(dense(p["time_fc1"], temb)))
    if cfg.addition_embed_dim:
        a = dense(p["add_fc1"], added_cond.astype(F32))
        temb = temb + dense(p["add_fc2"], silu(a))
    context = context.astype(F32)
    x = conv(p["conv_in"], latents.astype(F32))
    skips = [x]
    for level, (ch, depth) in enumerate(zip(chans, cfg.down_blocks)):
        for i in range(cfg.layers_per_block):
            x = res_block(p[f"down_{level}_res_{i}"], x, temb)
            if depth is not None:
                x = spatial_transformer(p[f"down_{level}_attn_{i}"], x,
                                        context, depth, heads(ch))
            skips.append(x)
        if level < len(chans) - 1:
            x = conv(p[f"down_{level}_ds"]["conv"], x, stride=2)
            skips.append(x)
    x = res_block(p["mid_res_0"], x, temb)
    if cfg.mid_block_depth is not None:
        x = spatial_transformer(p["mid_attn"], x, context,
                                cfg.mid_block_depth, heads(chans[-1]))
    x = res_block(p["mid_res_1"], x, temb)
    for level in reversed(range(len(chans))):
        ch, depth = chans[level], cfg.down_blocks[level]
        for i in range(cfg.layers_per_block + 1):
            x = jnp.concatenate([x, skips.pop()], axis=-1)
            x = res_block(p[f"up_{level}_res_{i}"], x, temb)
            if depth is not None:
                x = spatial_transformer(p[f"up_{level}_attn_{i}"], x,
                                        context, depth, heads(ch))
        if level > 0:
            x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
            x = conv(p[f"up_{level}_us"]["conv"], x)
    return conv(p["conv_out"], silu(group_norm(p["norm_out"], x)))

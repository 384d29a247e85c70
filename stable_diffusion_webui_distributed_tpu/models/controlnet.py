"""ControlNet: conditioned residual injection for the UNet, in-graph.

The reference only *serializes* ControlNet conditioning for the remote API
(/root/reference/scripts/spartan/control_net.py:20-79: b64-encodes unit
images/masks, both Mikubill and Forge key conventions) — the network itself
runs inside each sdwui worker. Here the network is ours: a Flax copy of the
UNet's down+mid path with a conditioning-hint embedder and zero-convolution
taps, whose outputs are added to the UNet's skip connections
(models/unet.py ``control_residuals``). Params ride as jit arguments, so
enabling/disabling units or swapping ControlNet checkpoints never recompiles
(SURVEY.md §7 hard part #2).

Preprocessors ("modules") are numpy/JAX implementations — no OpenCV in this
image; ``canny`` is a Sobel-magnitude edge detector with double threshold,
close to (not bit-equal with) OpenCV's.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from stable_diffusion_webui_distributed_tpu.models.configs import UNetConfig
from stable_diffusion_webui_distributed_tpu.models.unet import (
    ResBlock,
    SpatialTransformer,
    Downsample,
    timestep_embedding,
)

#: Channel ladder of the conditioning-hint embedder (ldm input_hint_block).
HINT_CHANNELS = (16, 16, 32, 32, 96, 96, 256)


class HintEmbedder(nn.Module):
    """(B, H, W, 3) image-space hint -> (B, H/8, W/8, ch0) latent-space."""

    out_channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, hint: jax.Array) -> jax.Array:
        x = hint.astype(self.dtype)
        strides = {2: 2, 4: 2, 6: 2}  # downsample x8 total at convs 2/4/6
        for i, ch in enumerate(HINT_CHANNELS):
            s = strides.get(i, 1)
            x = nn.Conv(ch, (3, 3), strides=(s, s), padding=1,
                        dtype=self.dtype, name=f"conv_{i}")(x)
            x = nn.silu(x)
        # final zero-initialized projection (trained from zero in ControlNet)
        return nn.Conv(self.out_channels, (3, 3), padding=1,
                       kernel_init=nn.initializers.zeros,
                       dtype=self.dtype, name="conv_out")(x)


class ControlNet(nn.Module):
    """Down+mid copy of the UNet emitting one residual per skip + mid."""

    cfg: UNetConfig
    dtype: jnp.dtype = jnp.float32
    # same experimental W8A8 flags as the UNet (runtime/dtypes.py): the
    # CN forward is ~half a UNet, so leaving it bf16 would dilute the
    # int8 cells on ControlNet configs (#3)
    quant_linears: bool = False
    quant_convs: bool = False
    # mirror the UNet's attention configuration: on sp>1 meshes the CN's
    # self-attention must ride the same ring (token-sharded activations),
    # or it all-gathers and materializes the dense score matrix the ring
    # exists to avoid
    use_remat: bool = False
    attention_impl: str = "auto"
    mesh: object = None

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
        hint: jax.Array,
        added_cond: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, ...]:
        c = self.cfg
        ch0 = c.block_out_channels[0]
        time_dim = 4 * ch0

        temb = timestep_embedding(timesteps, ch0)
        temb = nn.Dense(time_dim, dtype=self.dtype, name="time_fc1")(
            temb.astype(self.dtype))
        temb = nn.Dense(time_dim, dtype=self.dtype, name="time_fc2")(
            nn.silu(temb))
        if c.addition_embed_dim:
            assert added_cond is not None
            a = nn.Dense(time_dim, dtype=self.dtype, name="add_fc1")(
                added_cond.astype(self.dtype))
            a = nn.Dense(time_dim, dtype=self.dtype, name="add_fc2")(
                nn.silu(a))
            temb = temb + a

        context = context.astype(self.dtype)
        x = nn.Conv(ch0, (3, 3), padding=1, dtype=self.dtype,
                    name="conv_in")(latents.astype(self.dtype))
        x = x + HintEmbedder(ch0, dtype=self.dtype, name="hint")(hint)

        def zero_conv(i, h):
            return nn.Conv(h.shape[-1], (1, 1),
                           kernel_init=nn.initializers.zeros,
                           dtype=self.dtype, name=f"zero_conv_{i}")(h)

        residuals: List[jax.Array] = [zero_conv(0, x)]
        n = 1
        for level, (ch, depth) in enumerate(
                zip(c.block_out_channels, c.down_blocks)):
            for i in range(c.layers_per_block):
                x = ResBlock(ch, dtype=self.dtype,
                             quant_convs=self.quant_convs,
                             name=f"down_{level}_res_{i}")(x, temb)
                if depth is not None:
                    x = SpatialTransformer(
                        depth, self.heads_for(ch), self.use_remat,
                        self.dtype, self.attention_impl, self.mesh,
                        quant_linears=self.quant_linears,
                        name=f"down_{level}_attn_{i}")(x, context)
                residuals.append(zero_conv(n, x))
                n += 1
            if level < len(c.block_out_channels) - 1:
                x = Downsample(ch, dtype=self.dtype,
                               quant_convs=self.quant_convs,
                               name=f"down_{level}_ds")(x)
                residuals.append(zero_conv(n, x))
                n += 1

        mid_ch = c.block_out_channels[-1]
        x = ResBlock(mid_ch, dtype=self.dtype,
                     quant_convs=self.quant_convs, name="mid_res_0")(x, temb)
        if c.mid_block_depth is not None:
            x = SpatialTransformer(
                c.mid_block_depth, self.heads_for(mid_ch), self.use_remat,
                self.dtype, self.attention_impl, self.mesh,
                quant_linears=self.quant_linears, name="mid_attn")(x, context)
        x = ResBlock(mid_ch, dtype=self.dtype,
                     quant_convs=self.quant_convs, name="mid_res_1")(x, temb)
        residuals.append(nn.Conv(mid_ch, (1, 1),
                                 kernel_init=nn.initializers.zeros,
                                 dtype=self.dtype, name="mid_out")(x))
        return tuple(residuals)


# --------------------------------------------------------------------------
# ldm checkpoint conversion (control_model.* layout)
# --------------------------------------------------------------------------

def convert_controlnet(sd: Dict[str, np.ndarray], cfg: UNetConfig,
                       prefix: str = "control_model") -> Dict:
    """ldm ControlNet checkpoint -> :class:`ControlNet` params."""
    from stable_diffusion_webui_distributed_tpu.models.convert import (
        _Puller, _conv, _linear, _res_block, _transformer,
    )

    p = _Puller(sd)
    out: Dict = {
        "time_fc1": _linear(p, f"{prefix}.time_embed.0"),
        "time_fc2": _linear(p, f"{prefix}.time_embed.2"),
        "conv_in": _conv(p, f"{prefix}.input_blocks.0.0"),
        "mid_out": _conv(p, f"{prefix}.middle_block_out.0"),
    }
    if cfg.addition_embed_dim:
        out["add_fc1"] = _linear(p, f"{prefix}.label_emb.0.0")
        out["add_fc2"] = _linear(p, f"{prefix}.label_emb.0.2")

    hint: Dict = {}
    for i in range(len(HINT_CHANNELS)):
        hint[f"conv_{i}"] = _conv(p, f"{prefix}.input_hint_block.{2 * i}")
    hint["conv_out"] = _conv(
        p, f"{prefix}.input_hint_block.{2 * len(HINT_CHANNELS)}")
    out["hint"] = hint

    levels = list(zip(cfg.block_out_channels, cfg.down_blocks))
    out["zero_conv_0"] = _conv(p, f"{prefix}.zero_convs.0.0")
    n = 1
    prev = cfg.block_out_channels[0]
    for level, (ch, depth) in enumerate(levels):
        for i in range(cfg.layers_per_block):
            key = f"{prefix}.input_blocks.{n}"
            out[f"down_{level}_res_{i}"] = _res_block(
                p, f"{key}.0", has_skip=prev != ch)
            if depth is not None:
                out[f"down_{level}_attn_{i}"] = _transformer(
                    p, f"{key}.1", depth)
            out[f"zero_conv_{n}"] = _conv(p, f"{prefix}.zero_convs.{n}.0")
            prev = ch
            n += 1
        if level < len(levels) - 1:
            out[f"down_{level}_ds"] = {
                "conv": _conv(p, f"{prefix}.input_blocks.{n}.0.op")}
            out[f"zero_conv_{n}"] = _conv(p, f"{prefix}.zero_convs.{n}.0")
            n += 1

    out["mid_res_0"] = _res_block(p, f"{prefix}.middle_block.0", False)
    idx = 1
    if cfg.mid_block_depth is not None:
        out["mid_attn"] = _transformer(p, f"{prefix}.middle_block.1",
                                       cfg.mid_block_depth)
        idx = 2
    out["mid_res_1"] = _res_block(p, f"{prefix}.middle_block.{idx}", False)
    p.finish("controlnet")
    return out


# --------------------------------------------------------------------------
# preprocessors ("modules" in the reference's unit payloads)
# --------------------------------------------------------------------------

def preprocess_none(img: np.ndarray) -> np.ndarray:
    """Pass-through: image already IS the control map (e.g. user-drawn)."""
    return img.astype(np.float32) / 255.0 if img.dtype == np.uint8 else img


def preprocess_canny(img: np.ndarray, low: float = 100.0,
                     high: float = 200.0) -> np.ndarray:
    """Sobel-magnitude edge map with double threshold (cv2-free canny
    approximation). Thresholds are on the 0-255 gradient scale like cv2."""
    gray = np.asarray(img, np.float32)
    if gray.ndim == 3:
        gray = gray @ np.array([0.299, 0.587, 0.114], np.float32)
    # 3x3 gaussian-ish blur
    k = np.array([1.0, 2.0, 1.0], np.float32) / 4.0
    gray = np.apply_along_axis(lambda r: np.convolve(r, k, "same"), 1, gray)
    gray = np.apply_along_axis(lambda c: np.convolve(c, k, "same"), 0, gray)
    gx = np.zeros_like(gray)
    gy = np.zeros_like(gray)
    gx[:, 1:-1] = gray[:, 2:] - gray[:, :-2]
    gy[1:-1, :] = gray[2:, :] - gray[:-2, :]
    # x2: central difference is half the Sobel response cv2's thresholds
    # are calibrated against (the [1,2,1] smoothing is already applied)
    mag = 2.0 * np.sqrt(gx**2 + gy**2)
    strong = mag >= high
    weak = (mag >= low) & ~strong
    # weak pixels survive if any 8-neighbour is strong (one-pass hysteresis)
    pad = np.pad(strong, 1)
    neighbour = np.zeros_like(strong)
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            if dy == 0 and dx == 0:
                continue
            neighbour |= pad[1 + dy: pad.shape[0] - 1 + dy,
                             1 + dx: pad.shape[1] - 1 + dx]
    edges = strong | (weak & neighbour)
    out = edges.astype(np.float32)
    return np.repeat(out[:, :, None], 3, axis=2)


def preprocess_inpaint(img: np.ndarray,
                       mask: Optional[np.ndarray] = None) -> np.ndarray:
    """ControlNet v1.1 inpaint convention: the hint is the image with
    masked pixels set to -1.0 (the unit payload's ``image.mask`` channel
    the reference forwards; white mask = repaint)."""
    out = preprocess_none(img).copy()
    if mask is not None:
        m = np.asarray(mask)
        if m.dtype == np.uint8 or m.max() > 1.0:
            m = m.astype(np.float32) / 255.0
        else:
            m = m.astype(np.float32)
        if m.ndim == 3:
            m = m[..., 0]
        out[m > 0.5] = -1.0
    return out


PREPROCESSORS = {
    "none": preprocess_none,
    "canny": preprocess_canny,
    "invert": lambda img: 1.0 - preprocess_none(img),
}


def run_preprocessor(module: str, img: np.ndarray,
                     mask: Optional[np.ndarray] = None) -> np.ndarray:
    """Resolve a webui module name; unknown modules fall back to pass-through
    (same spirit as the reference's sampler fallback, worker.py:457-467).
    ``mask`` feeds mask-aware modules (inpaint family)."""
    name = (module or "none").lower()
    if name.startswith("inpaint"):  # inpaint / inpaint_only / +lama alias
        return preprocess_inpaint(img, mask)
    fn = PREPROCESSORS.get(name)
    if fn is None:
        from stable_diffusion_webui_distributed_tpu.runtime.logging import (
            get_logger,
        )

        get_logger().warning(
            "controlnet preprocessor '%s' unavailable; passing image "
            "through unprocessed", module)
        fn = preprocess_none
    return fn(img)

"""Nearest-neighbour 2x upsample followed by a 3x3, padding-1 convolution,
as the UNet's ``Upsample`` and the VAE decoder's ``up_{level}_us`` run it.

A 3x3 convolution over a nearest-2x image reads every low-resolution pixel
through 36 taps (9 taps x 4 outputs) of which 16 carry information: output
row ``2i`` sees input rows ``i-1, i, i``, row ``2i+1`` sees ``i, i, i+1``,
so two of the three taps of a phase multiply the same pixel and their
weights can be added first. With ``K[u, v]`` the stored kernel and ``x``
zero-padded by one ring::

    rows, phase a=0: rows (i-1, i) with (K[0], K[1] + K[2])
          phase a=1: rows (i, i+1) with (K[0] + K[1], K[2])     (columns alike)
    out[2i+a, 2j+b] = sum_pq x[i-1+a+p, j-1+b+q] . F[a, b][p, q]

The zero padding of the high-resolution image is the zero ring of the
low-resolution one, so this is the same sum at every edge and for odd
sizes: four 2x2 phase convolutions of the low-resolution input,
interleaved. They are run as ONE convolution whose input is dilated by 2
(``lhs_dilation``: a zero between neighbours, which the convolution skips
and never stores) under the 4x4 kernel whose taps along an axis are ``K0,
K0 + K1, K1 + K2, K2``: phase ``(a, b)`` meets taps ``[a::2, b::2]`` and
zeros elsewhere, so the convolution itself interleaves the phases. Sixteen
multiplies a low-resolution pixel for 36, no gather (``jax.image.resize``
lowers to one) and no pass to interleave. On a v5e four separate
convolutions and a stack cost the VAE decoder more than the 3x3 did
(PERF.md section 6, PR 43).

:class:`UpsampleConv` declares what ``flax.linen.Conv`` declares (``kernel``
``(3, 3, Cin, Cout)``, ``bias``, same initialisers), so seeded weights,
checkpoints and LoRA merges see no change. The weight sums are taken in
float32 and cast to the module's dtype; the products accumulate as
``nn.Conv``'s do. Every site is counted by its form when a model is applied
(serving/metrics.py ``UPSAMPLE``).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.ops.quant import int8_conv
from stable_diffusion_webui_distributed_tpu.serving.metrics import UPSAMPLE

#: the stored taps that meet one pixel at each of the four folded taps of an
#: axis: ``K0, K0 + K1, K1 + K2, K2``
_TAPS = ((0,), (0, 1), (1, 2), (2,))


def nearest_2x(x: jax.Array) -> jax.Array:
    """(B, H, W, C) -> (B, 2H, 2W, C), every pixel four times: a broadcast
    and a reshape."""
    B, H, W, C = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, None, :], (B, H, 2, W, 2, C))
    return x.reshape(B, 2 * H, 2 * W, C)


def fold_kernel(kernel: jax.Array, dtype) -> jax.Array:
    """The stored ``(3, 3, Cin, Cout)`` kernel as ``(4, 4, Cin, Cout)``:
    phase ``(a, b)``'s 2x2 kernel is ``[a::2, b::2]``. Each of the sixteen
    is its one, two or four stored taps summed in float32 and cast to
    ``dtype``, written tap by tap so that XLA makes the sums in one pass
    over the stored kernel with no float32 copy of it: they run inside the
    denoise scan's body, once a step."""
    k = [[kernel[u, v].astype(jnp.float32) for v in range(3)]
         for u in range(3)]
    return jnp.stack([
        jnp.stack([sum(k[u][v] for u in _TAPS[s] for v in _TAPS[t])
                   .astype(dtype) for t in range(4)])
        for s in range(4)])


def folded_upsample_conv(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """``conv3x3(nearest_2x(x), kernel)`` at padding 1, without the bias:
    the four phases as one convolution of ``x`` dilated by 2."""
    return jax.lax.conv_general_dilated(
        x, fold_kernel(kernel, x.dtype), (1, 1), ((2, 2), (2, 2)),
        lhs_dilation=(2, 2), dimension_numbers=("NHWC", "HWIO", "NHWC"))


class UpsampleConv(nn.Module):
    """Nearest-2x then a 3x3, padding-1 convolution to ``features``
    channels, under ``nn.Conv``'s parameter names. ``quant`` keeps the
    int8 convolution (ops/quant.py) on the upsampled image."""

    features: int
    dtype: jnp.dtype = jnp.float32
    quant: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (3, 3, x.shape[-1], self.features))
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        if not self.is_initializing():
            UPSAMPLE.record("plain" if self.quant else "folded")
        if self.quant:
            out = int8_conv(nearest_2x(x), kernel, padding=((1, 1), (1, 1)))
            return (out + bias.astype(jnp.float32)).astype(self.dtype)
        out = folded_upsample_conv(x.astype(self.dtype), kernel)
        return out + bias.astype(self.dtype)

"""Nearest-2x + 3x3 as four folded 2x2 phases (ops/upsample.py): the fold
against ``jax.image.resize`` + ``nn.Conv`` on the same parameters, the
parameter trees it must leave alone, and the sites' counter."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict

from stable_diffusion_webui_distributed_tpu.models.configs import TINY
from stable_diffusion_webui_distributed_tpu.models.unet import UNet
from stable_diffusion_webui_distributed_tpu.models.vae import VAE
from stable_diffusion_webui_distributed_tpu.ops.quant import QuantConv
from stable_diffusion_webui_distributed_tpu.ops.upsample import (
    UpsampleConv, fold_kernel, nearest_2x,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    METRICS, UPSAMPLE,
)

#: three UNet levels (two upsamples) and four VAE levels (three), as SDXL
UNET3 = dataclasses.replace(TINY.unet, block_out_channels=(32, 32, 64),
                            down_blocks=(None, 1, 1))
VAE4 = dataclasses.replace(TINY.vae, block_out_channels=(32, 32, 32, 32))


def _resize(x):
    B, H, W, C = x.shape
    return jax.image.resize(x, (B, 2 * H, 2 * W, C), method="nearest")


def _site(seed, batch, height, width, cin, cout):
    kx, kk, kb = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(kx, (batch, height, width, cin), jnp.float32)
    params = {"kernel": jax.random.normal(kk, (3, 3, cin, cout)) / 3.0,
              "bias": jax.random.normal(kb, (cout,))}
    return x, {"params": params}


@pytest.mark.parametrize("batch,height,width,cin,cout", [
    (1, 5, 8, 6, 10), (2, 4, 7, 8, 4), (2, 6, 3, 3, 5), (1, 1, 1, 4, 2),
    (1, 2, 9, 5, 5), (2, 7, 7, 2, 6)])
def test_folded_equals_resize_then_conv(batch, height, width, cin, cout):
    """Odd and even H != W, Cin != Cout, batch 1 and 2, a bias: every
    edge row and column is the zero ring's."""
    x, variables = _site(height * 31 + width, batch, height, width, cin, cout)
    want = nn.Conv(cout, (3, 3), padding=1).apply(variables, _resize(x))
    got = UpsampleConv(cout).apply(variables, x)
    assert got.shape == want.shape == (batch, 2 * height, 2 * width, cout)
    assert got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_fold_sums_the_taps_that_meet_one_pixel():
    kernel = jnp.arange(9.0).reshape(3, 3, 1, 1)
    folded = np.asarray(fold_kernel(kernel, jnp.float32))[..., 0, 0]
    k = np.arange(9.0).reshape(3, 3)
    # phase a's two taps sum these stored rows (columns alike)
    rows = {0: [[0], [1, 2]], 1: [[0, 1], [2]]}
    for a in (0, 1):
        for b in (0, 1):
            phase = folded[a::2, b::2]
            for p in (0, 1):
                for q in (0, 1):
                    assert phase[p, q] == k[np.ix_(rows[a][p],
                                                   rows[b][q])].sum()


def test_bf16_sums_in_float32_and_rounds_once():
    x, variables = _site(7, 2, 6, 5, 16, 8)
    got = UpsampleConv(8, dtype=jnp.bfloat16).apply(variables, x)
    want = nn.Conv(8, (3, 3), padding=1, dtype=jnp.bfloat16).apply(
        variables, _resize(x))
    assert got.dtype == jnp.bfloat16
    # one bf16 rounding of a four-term weight sum and of the output
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=0,
                               atol=2.0 ** -6 * float(jnp.abs(want).max()))


def test_int8_keeps_the_3x3_on_a_broadcast_upsample():
    x, variables = _site(3, 2, 4, 5, 8, 6)
    assert np.array_equal(nearest_2x(x), _resize(x))
    want = QuantConv(6, (3, 3), padding=1).apply(variables, _resize(x))
    got = UpsampleConv(6, quant=True).apply(variables, x)
    np.testing.assert_array_equal(got, want)
    jaxpr = str(jax.make_jaxpr(
        lambda v, x: UpsampleConv(6, quant=True).apply(v, x))(variables, x))
    assert "gather" not in jaxpr


@pytest.mark.parametrize("quant", [False, True])
def test_declares_what_nn_conv_declares(quant):
    """Names, shapes, dtypes and the seeded values of ``nn.Conv`` under
    the same key: checkpoints, LoRA and the weight fill see no change."""
    x = jnp.zeros((1, 4, 4, 6))
    key = jax.random.key(5)
    want = nn.Conv(10, (3, 3), padding=1).init(key, _resize(x))
    got = UpsampleConv(10, quant=quant).init(key, x)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def _unet_args(cfg):
    return (jnp.zeros((2, 8, 8, cfg.in_channels)), jnp.ones((2,)),
            jnp.zeros((2, 77, cfg.cross_attention_dim)))


def test_upsample_parameters_sit_where_they_sat():
    """``up_{level}_us/conv/{kernel,bias}`` in the UNet and
    ``decoder/up_{level}_us/{kernel,bias}`` in the VAE, (3, 3, C, C), as
    the parent's ``nn.Conv`` sites kept them (tests/param_trees.json pins
    the whole trees of TINY_XL; this the deeper models' sites)."""
    unet = jax.eval_shape(UNet(UNET3).init, jax.random.key(0),
                          *_unet_args(UNET3))["params"]
    vae = jax.eval_shape(VAE(VAE4).init, jax.random.key(0),
                         jnp.zeros((1, 16, 16, 3)),
                         jax.random.key(1))["params"]
    flat = {"/".join(k): v.shape for k, v in flatten_dict(unet).items()}
    sites = {k: v for k, v in flat.items() if "_us/" in k}
    assert sites == {"up_1_us/conv/kernel": (3, 3, 32, 32),
                     "up_1_us/conv/bias": (32,),
                     "up_2_us/conv/kernel": (3, 3, 64, 64),
                     "up_2_us/conv/bias": (64,)}
    flat = {"/".join(k): v.shape for k, v in flatten_dict(vae).items()}
    sites = {k: v for k, v in flat.items() if "_us/" in k}
    assert sites == {f"decoder/up_{level}_us/{leaf}": shape
                     for level in (1, 2, 3)
                     for leaf, shape in (("kernel", (3, 3, 32, 32)),
                                         ("bias", (32,)))}


class TestSitesCounter:
    """``serving.upsample`` counts a site when a model is applied under a
    trace, by form; ``init`` (the weight fill's ``eval_shape``) counts
    nothing."""

    def setup_method(self):
        UPSAMPLE.clear()

    def test_unet_and_vae_count_folded_sites(self):
        args = _unet_args(UNET3)
        params = jax.eval_shape(UNet(UNET3).init, jax.random.key(0), *args)
        assert UPSAMPLE.summary() == {"folded": 0, "plain": 0}
        jax.eval_shape(UNet(UNET3).apply, params, *args)
        assert UPSAMPLE.summary() == {"folded": 2, "plain": 0}
        vae = VAE(VAE4)
        params = jax.eval_shape(vae.init, jax.random.key(0),
                                jnp.zeros((1, 16, 16, 3)), jax.random.key(1))
        jax.eval_shape(lambda p, z: vae.apply(p, z, method=VAE.decode),
                       params, jnp.zeros((1, 2, 2, VAE4.latent_channels)))
        assert UPSAMPLE.summary() == {"folded": 5, "plain": 0}
        assert METRICS.summary()["upsample"] == {"folded": 5, "plain": 0}

    def test_int8_convolutions_count_plain(self):
        args = _unet_args(UNET3)
        unet = UNet(UNET3, quant_convs=True)
        params = jax.eval_shape(unet.init, jax.random.key(0), *args)
        jax.eval_shape(unet.apply, params, *args)
        assert UPSAMPLE.summary() == {"folded": 0, "plain": 2}

    def test_deep_cache_mode_ends_after_the_split_levels_upsample(self):
        """``cache_mode="deep"`` returns the folded site's output: one
        module, one output, at the shape the engine's cache carries."""
        from stable_diffusion_webui_distributed_tpu.models.unet import (
            deep_cache_shape,
        )

        args = _unet_args(UNET3)
        params = jax.eval_shape(UNet(UNET3).init, jax.random.key(0), *args)
        out = jax.eval_shape(
            lambda p, *a: UNet(UNET3).apply(p, *a, cache_mode="deep"),
            params, *args)
        assert out.shape == deep_cache_shape(UNET3, 2, 8, 8)
        assert UPSAMPLE.summary() == {"folded": 2, "plain": 0}

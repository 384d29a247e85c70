"""flops.py against XLA's own count, and the plain reference against the
program, both at the tiny width on the CPU."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import flops, weights
from benchmarks.reference import unet_ref
from stable_diffusion_webui_distributed_tpu.models.configs import FAMILIES
from stable_diffusion_webui_distributed_tpu.models.unet import UNet

#: flops.py counts matmuls and convolutions only; XLA also counts norms,
#: activations, softmax and casts. At the tiny width (32-64 channels) those
#: are a far larger share than at published widths: SD1.5 at 64x64 is 11 %
#: under XLA's count, SDXL at 128x128 3.4 % (planning compiles, ISSUE 23).
TINY_MARGIN = (0.80, 1.05)


def _inputs(cfg, latent, batch=1):
    keys = jax.random.split(jax.random.key(0), 3)
    out = [jax.random.normal(keys[0], (batch, latent, latent,
                                       cfg.in_channels)),
           jnp.full((batch,), 500.0),
           jax.random.normal(keys[1], (batch, 77, cfg.cross_attention_dim))]
    if cfg.addition_embed_dim:
        out.append(jax.random.normal(keys[2],
                                     (batch, cfg.projection_input_dim)))
    return out


def _params(family):
    module, args = weights.component_inits(family)["unet"]
    return weights.fill(weights.param_shapes(module, args), jnp.float32, 3)


@pytest.mark.parametrize("name", ["tiny", "tiny-xl"])
def test_flops_against_cost_analysis(name):
    family = FAMILIES[name]
    cfg = family.unet
    params = _params(family)
    unet = UNet(cfg, dtype=jnp.float32)
    compiled = jax.jit(lambda p, *a: unet.apply({"params": p}, *a)).lower(
        params, *_inputs(cfg, 16)).compile()
    xla = compiled.cost_analysis()["flops"]
    ours = flops.unet_forward_flops(cfg, 16, 16)
    assert TINY_MARGIN[0] * xla <= ours <= TINY_MARGIN[1] * xla, (ours, xla)


def test_flops_at_published_widths_are_the_known_counts():
    # 0.78 TFLOPs a forward pass of SD1.5 at 64x64 (the literature's ~400
    # GMACs counts the padding taps: 0.803e12 with them), 6.72 of SDXL at
    # 128x128; XLA's cost analysis for a described v5e reads 0.905e12 and
    # 6.995e12 with norms, activations and softmax (ISSUE 21, 23)
    sd15 = flops.unet_forward_flops(FAMILIES["sd15"].unet, 64, 64)
    sdxl = flops.unet_forward_flops(FAMILIES["sdxl-base"].unet, 128, 128)
    assert sd15 == pytest.approx(0.7818e12, rel=1e-3)
    assert sdxl == pytest.approx(6.7248e12, rel=1e-3)
    assert 0.85 < sd15 / 0.905e12 < 1.0 and 0.95 < sdxl / 6.995e12 < 1.0
    payload = {"width": 512, "height": 512, "steps": 20,
               "sampler_name": "Euler a"}
    assert flops.unet_flops_per_image(FAMILIES["sd15"], payload) == \
        40 * flops.unet_forward_flops(FAMILIES["sd15"].unet, 64, 64)


@pytest.mark.parametrize("name", ["tiny", "tiny-xl"])
def test_reference_agrees_with_the_program_and_rejects_int8(name):
    """float32 program against the float32 reference: they differ only by
    the order of sums, so 1e-4 relative RMS; the same tree through the
    int8 linears must NOT pass (2.5e-2 measured here)."""
    import benchmarks.verify_reference as verify
    from stable_diffusion_webui_distributed_tpu.runtime import dtypes

    with jax.default_matmul_precision("highest"):
        out = verify.compare(FAMILIES[name], dtypes.F32, 23, 16)
    assert out["finite"]
    assert out["program_vs_reference_relative_rms"] < 1e-4
    assert out["int8_vs_reference_relative_rms"] > 5e-3


def test_weights_are_seeded_and_typed():
    family = FAMILIES["tiny"]
    a = weights.family_params(family, jnp.bfloat16, 7)
    b = weights.family_params(family, jnp.bfloat16, 7)
    c = weights.family_params(family, jnp.bfloat16, 8)
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all(x.dtype == jnp.bfloat16 for x in la)
    assert all(bool((x == y).all()) for x, y in zip(la, lb))
    assert any(bool((x != y).any()) for x, y in zip(la, lc))
    kernel = a["unet"]["conv_in"]["kernel"].astype(jnp.float32)
    fan_in = 3 * 3 * family.unet.in_channels
    assert float(kernel.std()) == pytest.approx(fan_in ** -0.5, rel=0.15)
    assert float(a["unet"]["norm_out"]["gn"]["scale"].min()) == 1.0
    assert float(abs(a["unet"]["conv_in"]["bias"]).max()) == 0.0

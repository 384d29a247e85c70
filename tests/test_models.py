"""Model zoo tests: forward shapes, clip-skip, tokenizer, ldm conversion.

The conversion tests build *synthetic* ldm-layout state dicts by replaying
the torch ldm module-construction rules independently of the converter; if
the converter's key numbering or any transpose is wrong, the converted tree
will not match the Flax-initialized tree and the forward pass fails.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.models.configs import (
    CLIPTextConfig, TINY, TINY_XL, UNetConfig,
)
from stable_diffusion_webui_distributed_tpu.models import convert
from stable_diffusion_webui_distributed_tpu.models.clip import CLIPTextModel
from stable_diffusion_webui_distributed_tpu.models.unet import (
    GroupNorm32, UNet, make_added_cond,
)
from stable_diffusion_webui_distributed_tpu.models.vae import VAE
from stable_diffusion_webui_distributed_tpu.models.tokenizer import (
    CLIPTokenizer, FallbackTokenizer,
)

RNG = np.random.default_rng(0)


# --------------------------------------------------------------------------
# synthetic ldm state-dict generators (torch tensor conventions)
# --------------------------------------------------------------------------

def _lin(sd, key, o, i, bias=True):
    sd[f"{key}.weight"] = RNG.standard_normal((o, i), np.float32) * 0.02
    if bias:
        sd[f"{key}.bias"] = np.zeros(o, np.float32)


def _conv(sd, key, o, i, k=3):
    sd[f"{key}.weight"] = RNG.standard_normal((o, i, k, k), np.float32) * 0.02
    sd[f"{key}.bias"] = np.zeros(o, np.float32)


def _norm(sd, key, c):
    sd[f"{key}.weight"] = np.ones(c, np.float32)
    sd[f"{key}.bias"] = np.zeros(c, np.float32)


def _ldm_res(sd, key, cin, cout, tdim):
    _norm(sd, f"{key}.in_layers.0", cin)
    _conv(sd, f"{key}.in_layers.2", cout, cin)
    _lin(sd, f"{key}.emb_layers.1", cout, tdim)
    _norm(sd, f"{key}.out_layers.0", cout)
    _conv(sd, f"{key}.out_layers.3", cout, cout)
    if cin != cout:
        _conv(sd, f"{key}.skip_connection", cout, cin, k=1)


def _ldm_xformer(sd, key, c, depth, ctx):
    _norm(sd, f"{key}.norm", c)
    _lin(sd, f"{key}.proj_in", c, c)
    _lin(sd, f"{key}.proj_out", c, c)
    for d in range(depth):
        bp = f"{key}.transformer_blocks.{d}"
        for nm in ("norm1", "norm2", "norm3"):
            _norm(sd, f"{bp}.{nm}", c)
        for nm in ("to_q", "to_k", "to_v"):
            _lin(sd, f"{bp}.attn1.{nm}", c, c, bias=False)
        _lin(sd, f"{bp}.attn1.to_out.0", c, c)
        _lin(sd, f"{bp}.attn2.to_q", c, c, bias=False)
        _lin(sd, f"{bp}.attn2.to_k", c, ctx, bias=False)
        _lin(sd, f"{bp}.attn2.to_v", c, ctx, bias=False)
        _lin(sd, f"{bp}.attn2.to_out.0", c, c)
        _lin(sd, f"{bp}.ff.net.0.proj", 8 * c, c)
        _lin(sd, f"{bp}.ff.net.2", c, 4 * c)


def make_ldm_unet(cfg, prefix="model.diffusion_model"):
    sd = {}
    ch0 = cfg.block_out_channels[0]
    tdim = 4 * ch0
    ctx = cfg.cross_attention_dim
    _lin(sd, f"{prefix}.time_embed.0", tdim, ch0)
    _lin(sd, f"{prefix}.time_embed.2", tdim, tdim)
    if cfg.addition_embed_dim:
        _lin(sd, f"{prefix}.label_emb.0.0", tdim, cfg.projection_input_dim)
        _lin(sd, f"{prefix}.label_emb.0.2", tdim, tdim)
    _conv(sd, f"{prefix}.input_blocks.0.0", ch0, cfg.in_channels)

    levels = list(zip(cfg.block_out_channels, cfg.down_blocks))
    skips = [ch0]
    prev = ch0
    n = 1
    for level, (ch, depth) in enumerate(levels):
        for _ in range(cfg.layers_per_block):
            _ldm_res(sd, f"{prefix}.input_blocks.{n}.0", prev, ch, tdim)
            if depth is not None:
                _ldm_xformer(sd, f"{prefix}.input_blocks.{n}.1", ch, depth, ctx)
            prev = ch
            skips.append(ch)
            n += 1
        if level < len(levels) - 1:
            _conv(sd, f"{prefix}.input_blocks.{n}.0.op", ch, ch)
            skips.append(ch)
            n += 1

    mid = cfg.block_out_channels[-1]
    _ldm_res(sd, f"{prefix}.middle_block.0", mid, mid, tdim)
    idx = 1
    if cfg.mid_block_depth is not None:
        _ldm_xformer(sd, f"{prefix}.middle_block.1", mid, cfg.mid_block_depth, ctx)
        idx = 2
    _ldm_res(sd, f"{prefix}.middle_block.{idx}", mid, mid, tdim)

    n = 0
    for level in reversed(range(len(levels))):
        ch, depth = levels[level]
        for i in range(cfg.layers_per_block + 1):
            _ldm_res(sd, f"{prefix}.output_blocks.{n}.0",
                     prev + skips.pop(), ch, tdim)
            sub = 1
            if depth is not None:
                _ldm_xformer(sd, f"{prefix}.output_blocks.{n}.1", ch, depth, ctx)
                sub = 2
            if i == cfg.layers_per_block and level > 0:
                _conv(sd, f"{prefix}.output_blocks.{n}.{sub}.conv", ch, ch)
            prev = ch
            n += 1

    _norm(sd, f"{prefix}.out.0", ch0)
    _conv(sd, f"{prefix}.out.2", cfg.out_channels, ch0)
    return sd


def make_ldm_clip_hf(cfg: CLIPTextConfig,
                     prefix="cond_stage_model.transformer.text_model"):
    sd = {}
    h = cfg.hidden_size
    sd[f"{prefix}.embeddings.token_embedding.weight"] = (
        RNG.standard_normal((cfg.vocab_size, h), np.float32) * 0.02
    )
    sd[f"{prefix}.embeddings.position_embedding.weight"] = (
        RNG.standard_normal((cfg.max_length, h), np.float32) * 0.01
    )
    for i in range(cfg.num_layers):
        lp = f"{prefix}.encoder.layers.{i}"
        for nm in ("q_proj", "k_proj", "v_proj", "out_proj"):
            _lin(sd, f"{lp}.self_attn.{nm}", h, h)
        _norm(sd, f"{lp}.layer_norm1", h)
        _norm(sd, f"{lp}.layer_norm2", h)
        _lin(sd, f"{lp}.mlp.fc1", cfg.intermediate_size, h)
        _lin(sd, f"{lp}.mlp.fc2", h, cfg.intermediate_size)
    _norm(sd, f"{prefix}.final_layer_norm", h)
    if cfg.projection_dim:
        parent = prefix.rsplit(".text_model", 1)[0]
        _lin(sd, f"{parent}.text_projection", cfg.projection_dim, h, bias=False)
    return sd


def make_ldm_clip_openai(cfg: CLIPTextConfig,
                         prefix="conditioner.embedders.1.model"):
    sd = {}
    h = cfg.hidden_size
    sd[f"{prefix}.token_embedding.weight"] = (
        RNG.standard_normal((cfg.vocab_size, h), np.float32) * 0.02
    )
    sd[f"{prefix}.positional_embedding"] = (
        RNG.standard_normal((cfg.max_length, h), np.float32) * 0.01
    )
    for i in range(cfg.num_layers):
        lp = f"{prefix}.transformer.resblocks.{i}"
        sd[f"{lp}.attn.in_proj_weight"] = (
            RNG.standard_normal((3 * h, h), np.float32) * 0.02
        )
        sd[f"{lp}.attn.in_proj_bias"] = np.zeros(3 * h, np.float32)
        _lin(sd, f"{lp}.attn.out_proj", h, h)
        _norm(sd, f"{lp}.ln_1", h)
        _norm(sd, f"{lp}.ln_2", h)
        _lin(sd, f"{lp}.mlp.c_fc", cfg.intermediate_size, h)
        _lin(sd, f"{lp}.mlp.c_proj", h, cfg.intermediate_size)
    _norm(sd, f"{prefix}.ln_final", h)
    if cfg.projection_dim:
        sd[f"{prefix}.text_projection"] = (
            RNG.standard_normal((h, cfg.projection_dim), np.float32) * 0.02
        )
    return sd


def _ldm_vae_res(sd, key, cin, cout):
    _norm(sd, f"{key}.norm1", cin)
    _conv(sd, f"{key}.conv1", cout, cin)
    _norm(sd, f"{key}.norm2", cout)
    _conv(sd, f"{key}.conv2", cout, cout)
    if cin != cout:
        _conv(sd, f"{key}.nin_shortcut", cout, cin, k=1)


def _ldm_vae_attn(sd, key, c):
    _norm(sd, f"{key}.norm", c)
    for nm in ("q", "k", "v", "proj_out"):
        _conv(sd, f"{key}.{nm}", c, c, k=1)


def make_ldm_vae(cfg, prefix="first_stage_model"):
    sd = {}
    chs = cfg.block_out_channels
    _conv(sd, f"{prefix}.encoder.conv_in", chs[0], cfg.in_channels)
    prev = chs[0]
    for level, ch in enumerate(chs):
        for i in range(cfg.layers_per_block):
            _ldm_vae_res(sd, f"{prefix}.encoder.down.{level}.block.{i}",
                         prev if i == 0 else ch, ch)
        prev = ch
        if level < len(chs) - 1:
            _conv(sd, f"{prefix}.encoder.down.{level}.downsample.conv", ch, ch)
    _ldm_vae_res(sd, f"{prefix}.encoder.mid.block_1", chs[-1], chs[-1])
    _ldm_vae_attn(sd, f"{prefix}.encoder.mid.attn_1", chs[-1])
    _ldm_vae_res(sd, f"{prefix}.encoder.mid.block_2", chs[-1], chs[-1])
    _norm(sd, f"{prefix}.encoder.norm_out", chs[-1])
    _conv(sd, f"{prefix}.encoder.conv_out", 2 * cfg.latent_channels, chs[-1])
    _conv(sd, f"{prefix}.quant_conv",
          2 * cfg.latent_channels, 2 * cfg.latent_channels, k=1)

    _conv(sd, f"{prefix}.post_quant_conv",
          cfg.latent_channels, cfg.latent_channels, k=1)
    _conv(sd, f"{prefix}.decoder.conv_in", chs[-1], cfg.latent_channels)
    _ldm_vae_res(sd, f"{prefix}.decoder.mid.block_1", chs[-1], chs[-1])
    _ldm_vae_attn(sd, f"{prefix}.decoder.mid.attn_1", chs[-1])
    _ldm_vae_res(sd, f"{prefix}.decoder.mid.block_2", chs[-1], chs[-1])
    prev = chs[-1]
    for level in reversed(range(len(chs))):
        ch = chs[level]
        for i in range(cfg.layers_per_block + 1):
            _ldm_vae_res(sd, f"{prefix}.decoder.up.{level}.block.{i}",
                         prev if i == 0 else ch, ch)
        prev = ch
        if level > 0:
            _conv(sd, f"{prefix}.decoder.up.{level}.upsample.conv", ch, ch)
    _norm(sd, f"{prefix}.decoder.norm_out", chs[0])
    _conv(sd, f"{prefix}.decoder.conv_out", cfg.in_channels, chs[0])
    return sd


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def tree_shapes(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k, simple=True, separator="/"): np.shape(v)
            for k, v in flat}


def assert_same_structure(converted, initialized, scope):
    a, b = tree_shapes(converted), tree_shapes(initialized)
    assert set(a) == set(b), (
        f"{scope}: key mismatch\n  only-converted: {sorted(set(a) - set(b))[:6]}"
        f"\n  only-init: {sorted(set(b) - set(a))[:6]}"
    )
    bad = {k: (a[k], b[k]) for k in a if a[k] != b[k]}
    assert not bad, f"{scope}: shape mismatches {dict(list(bad.items())[:6])}"


# --------------------------------------------------------------------------
# tests
# --------------------------------------------------------------------------

class TestCLIP:
    def test_forward_and_skip(self):
        cfg = TINY.text_encoder
        ids = jnp.asarray(FallbackTokenizer(cfg.vocab_size)(["a cow", ""]))
        model = CLIPTextModel(cfg)
        params = model.init(jax.random.key(0), ids)
        ctx0, pooled = model.apply(params, ids, skip=0)
        ctx1, _ = model.apply(params, ids, skip=1)
        assert ctx0.shape == (2, 77, cfg.hidden_size)
        assert pooled.shape == (2, cfg.hidden_size)
        assert not np.allclose(np.asarray(ctx0), np.asarray(ctx1))

    def test_conversion_hf(self):
        cfg = TINY.text_encoder
        sd = make_ldm_clip_hf(cfg)
        converted = convert.convert_clip_hf(
            sd, cfg, "cond_stage_model.transformer.text_model")
        ids = jnp.asarray(FallbackTokenizer(cfg.vocab_size)(["x"]))
        model = CLIPTextModel(cfg)
        init = model.init(jax.random.key(0), ids)["params"]
        assert_same_structure(converted, init, "clip-hf")
        ctx, _ = model.apply({"params": converted}, ids)
        assert np.isfinite(np.asarray(ctx)).all()

    def test_conversion_sd2_layout(self):
        # SD2.x single file: OpenCLIP under cond_stage_model.model
        cfg = TINY.text_encoder
        import dataclasses as dc

        cfg2 = dc.replace(cfg, hidden_act="gelu", default_skip=1)
        sd = make_ldm_clip_openai(cfg2, prefix="cond_stage_model.model")
        sd.update(make_ldm_unet(TINY.unet))
        sd.update(make_ldm_vae(TINY.vae))
        from stable_diffusion_webui_distributed_tpu.models.configs import (
            ModelFamily,
        )

        fam = ModelFamily(name="tiny-sd2", text_encoder=cfg2,
                          unet=TINY.unet, vae=TINY.vae,
                          prediction_type="v_prediction")
        assert convert.detect_family(sd) == "sd21"
        converted = convert.convert_ldm(sd, fam)
        assert converted["text_encoder_2"] is None
        ids = jnp.asarray(FallbackTokenizer(cfg2.vocab_size)(["x"]))
        model = CLIPTextModel(cfg2)
        ctx, _ = model.apply({"params": converted["text_encoder"]}, ids)
        assert np.isfinite(np.asarray(ctx)).all()

    def test_conversion_openclip(self):
        cfg = TINY_XL.text_encoder_2
        sd = make_ldm_clip_openai(cfg)
        converted = convert.convert_clip_openai(
            sd, cfg, "conditioner.embedders.1.model")
        ids = jnp.asarray(FallbackTokenizer(cfg.vocab_size)(["x"]))
        model = CLIPTextModel(cfg)
        init = model.init(jax.random.key(0), ids)["params"]
        assert_same_structure(converted, init, "openclip")
        _, pooled = model.apply({"params": converted}, ids)
        assert pooled.shape == (1, cfg.projection_dim)


class TestUNetConversion:
    @pytest.mark.parametrize("family", [TINY, TINY_XL], ids=["sd", "xl"])
    def test_conversion_matches_init(self, family):
        cfg = family.unet
        sd = make_ldm_unet(cfg)
        converted = convert.convert_unet(sd, cfg)
        lat = jnp.zeros((1, 8, 8, cfg.in_channels))
        ctx = jnp.zeros((1, 77, cfg.cross_attention_dim))
        t = jnp.ones((1,))
        model = UNet(cfg)
        if cfg.addition_embed_dim:
            ac = jnp.zeros((1, cfg.projection_input_dim))
            init = model.init(jax.random.key(0), lat, t, ctx, ac)["params"]
            assert_same_structure(converted, init, f"unet-{family.name}")
            out = model.apply({"params": converted}, lat, t, ctx, ac)
        else:
            init = model.init(jax.random.key(0), lat, t, ctx)["params"]
            assert_same_structure(converted, init, f"unet-{family.name}")
            out = model.apply({"params": converted}, lat, t, ctx)
        assert out.shape == (1, 8, 8, cfg.out_channels)
        assert np.isfinite(np.asarray(out)).all()


class TestVAEConversion:
    def test_conversion_matches_init(self):
        cfg = TINY.vae
        sd = make_ldm_vae(cfg)
        converted = convert.convert_vae(sd, cfg)
        img = jnp.zeros((1, 16, 16, 3))
        model = VAE(cfg)
        init = model.init(jax.random.key(0), img, jax.random.key(1))["params"]
        assert_same_structure(converted, init, "vae")
        mean, logvar = model.apply({"params": converted}, img,
                                   method=VAE.encode)
        dec = model.apply({"params": converted}, mean, method=VAE.decode)
        assert dec.shape == (1, 16, 16, 3)


class TestGroupNorm32:
    """models/unet.py:GroupNorm32 never views the activation by groups; it
    must still be flax's GroupNorm at float32, on the same parameters."""

    @pytest.mark.parametrize("batch", [1, 2])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("channels", [8, 320, 960, 1920])
    @pytest.mark.parametrize("mean_over_std", [0.0, 10.0])
    def test_matches_flax_group_norm(self, mean_over_std, channels, dtype,
                                     batch):
        """Against ``flax.linen.GroupNorm(dtype=float32)`` on the same
        parameters, both held to a float64 GroupNorm: flax's float32
        E[x^2] - E[x]^2 is itself 1.2e-5 from it on centred input and
        1e-4..2e-3 with the mean at 10 x std, so "agrees with flax" is
        1e-5 (3e-4 off-centre) plus flax's own distance, one bf16 ulp
        more in bf16, and this module may not be the farther of the two."""
        import flax.linen as nn

        rng = np.random.default_rng(channels + batch)
        x = jnp.asarray(1.3 * (rng.standard_normal((batch, 16, 16, channels))
                               + mean_over_std), dtype)
        scale = 1.0 + 0.3 * rng.standard_normal(channels)
        bias = rng.standard_normal(channels)
        gn = {"scale": jnp.asarray(scale, jnp.float32),
              "bias": jnp.asarray(bias, jnp.float32)}
        groups = min(32, channels)
        got = GroupNorm32().apply({"params": {"gn": gn}}, x)
        assert got.dtype == dtype and got.shape == x.shape
        flax = nn.GroupNorm(num_groups=groups, dtype=jnp.float32).apply(
            {"params": gn}, x.astype(jnp.float32))
        x64 = np.asarray(x, np.float64).reshape(batch, -1, groups,
                                                channels // groups)
        exact = (x64 - x64.mean((1, 3), keepdims=True)) / np.sqrt(
            x64.var((1, 3), keepdims=True) + 1e-6)
        exact = exact.reshape(x.shape) * scale + bias
        flax_off = np.abs(np.asarray(flax, np.float64) - exact).max()
        flax = np.asarray(flax.astype(dtype), np.float64)
        got = np.asarray(got, np.float64)
        limit = 3e-4 if mean_over_std else 1e-5
        if dtype == jnp.bfloat16:
            # one unit in the last of bf16's 8 significant bits
            ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(got), 1e-30)))
                          - 7)
            limit = ulp + limit
        else:
            assert np.abs(got - exact).max() <= 1.1 * flax_off + 1e-6
        assert (np.abs(got - exact) <= limit).all()
        assert (np.abs(got - flax) <= limit + flax_off).all()

    def test_parameter_trees_are_the_parents(self):
        """Paths, shapes, dtypes and order of UNet(TINY_XL) and VAE as
        written out at the commit before GroupNorm32 left flax's module
        (tests/param_trees.json): convert.py, the sharding rules, LoRA and
        the benchmark's weight fill read these paths."""
        from flax.traverse_util import flatten_dict

        from test_pipeline import init_params

        with open(os.path.join(os.path.dirname(__file__),
                               "param_trees.json")) as fh:
            want = json.load(fh)
        params = jax.eval_shape(lambda: init_params(TINY_XL))
        for name in ("unet", "vae"):
            got = [["/".join(path), list(leaf.shape), leaf.dtype.name]
                   for path, leaf in flatten_dict(params[name]).items()]
            assert got == want[name], name

    def test_no_group_shaped_view_of_the_activation(self):
        shape = (2, 16, 16, 960)
        x = jnp.zeros(shape, jnp.bfloat16)
        module = GroupNorm32()
        params = module.init(jax.random.key(0), x)
        closed = jax.make_jaxpr(module.apply)(params, x)

        def outvars(jaxpr):
            for eqn in jaxpr.eqns:
                yield from eqn.outvars
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from outvars(sub)

        sized = [v.aval.shape for v in outvars(closed.jaxpr)
                 if v.aval.size == x.size]
        assert sized and set(sized) == {shape}, set(sized)
        (out,) = closed.out_avals
        assert out.dtype == jnp.bfloat16 and out.shape == shape

    @pytest.mark.parametrize("rows,side,dtype,form", [
        (rows, side, dtype,
         "plain" if dtype == jnp.float32 else
         "pinned" if side == 128 else
         "stats_pinned" if rows == 2 else "plain")
        for rows in (2, 4, 8) for side in (64, 32, 128)
        for dtype in (jnp.bfloat16, jnp.float32)])
    def test_the_form_goes_with_shape_and_dtype_alone(self, rows, side,
                                                      dtype, form):
        """No knob: bf16 at 128 x 128 positions and over is pinned whole
        (the TPU compiler otherwise copies float32 activations through
        HBM); under that, two rows pin a copy for the sums alone (two rows
        lie alone on the sublanes, PERF.md section 6, PR 65) and four or
        eight rows nothing (eight rows trace the program they traced
        before); never a float32 activation (the VAE decoder). The site is
        counted by its form when applied, not when initialised."""
        from stable_diffusion_webui_distributed_tpu.models.unet import (
            norm_form,
        )
        from stable_diffusion_webui_distributed_tpu.serving.metrics import (
            NORM,
        )

        assert norm_form(rows, side * side, dtype) == form
        x = jnp.zeros((rows, side, side, 8), dtype)
        module = GroupNorm32()
        NORM.clear()
        params = jax.eval_shape(module.init, jax.random.key(0), x)
        assert not any(NORM.summary().values())
        closed = jax.make_jaxpr(module.apply)(params, x)
        assert NORM.summary() == {
            name: int(name == form) for name in NORM.FORMS}
        barriers = [eqn for eqn in closed.jaxpr.eqns
                    if eqn.primitive.name == "optimization_barrier"]
        assert len(barriers) == (form != "plain")
        if form == "stats_pinned":
            # the normalise reads the activation as it lies: the pinned
            # copy feeds the two sums and nothing else of its size
            (pinned,) = barriers[0].outvars
            readers = [eqn for eqn in closed.jaxpr.eqns
                       if pinned in eqn.invars]
            assert [eqn.primitive.name for eqn in readers] == [
                "convert_element_type"]
            final = closed.jaxpr.eqns[-1]
            assert final.primitive.name == "convert_element_type"

    @pytest.mark.parametrize("mean_over_std", [0.0, 10.0])
    @pytest.mark.parametrize("channels", [320, 960])
    def test_the_pinned_sums_are_the_plain_sums(self, channels,
                                                mean_over_std):
        """Two rows (sums from a pinned copy) against the same rows among
        eight (plain): bit for bit, and so at the same float64 distances."""
        rng = np.random.default_rng(channels)
        x = jnp.asarray(1.3 * (rng.standard_normal((8, 16, 16, channels))
                               + mean_over_std), jnp.bfloat16)
        gn = {"scale": jnp.asarray(1.0 + 0.3 * rng.standard_normal(channels),
                                   jnp.float32),
              "bias": jnp.asarray(rng.standard_normal(channels), jnp.float32)}
        apply = jax.jit(GroupNorm32().apply)
        plain = apply({"params": {"gn": gn}}, x)
        pinned = apply({"params": {"gn": gn}}, x[:2])
        assert (np.asarray(pinned, np.float32)
                == np.asarray(plain[:2], np.float32)).all()


class TestPointwiseSkip:
    """A ResBlock's 1x1 ``skip`` at two rows in a narrow dtype is a matrix
    product under ``nn.Conv``'s parameter names (models/unet.py
    ``PointwiseConv``): the convolution everywhere else."""

    @pytest.mark.parametrize("rows,dtype,product", [
        (2, jnp.bfloat16, True), (1, jnp.bfloat16, True),
        (4, jnp.bfloat16, False), (8, jnp.bfloat16, False),
        (2, jnp.float32, False)])
    def test_the_skip_goes_with_rows_and_dtype_alone(self, rows, dtype,
                                                     product):
        from stable_diffusion_webui_distributed_tpu.models.unet import (
            ResBlock,
        )

        x = jnp.zeros((rows, 8, 8, 64), dtype)
        temb = jnp.zeros((rows, 32), dtype)
        block = ResBlock(32, dtype=dtype)
        params = jax.eval_shape(block.init, jax.random.key(0), x, temb)
        skip = params["params"]["skip"]
        assert skip["kernel"].shape == (1, 1, 64, 32)
        assert skip["bias"].shape == (32,)
        closed = jax.make_jaxpr(block.apply)(params, x, temb)
        convs = [eqn for eqn in closed.jaxpr.eqns
                 if eqn.primitive.name == "conv_general_dilated"]
        assert len(convs) == (2 if product else 3)

    @pytest.mark.parametrize("quant", [False, True])
    def test_the_product_is_the_convolution(self, quant):
        """Same parameters, two rows against the same rows among four: the
        product's block is the convolution's to bf16's last bits, and an
        int8 ResBlock keeps its convolution."""
        from stable_diffusion_webui_distributed_tpu.models.unet import (
            ResBlock,
        )

        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.standard_normal((4, 8, 8, 64)), jnp.bfloat16)
        temb = jnp.asarray(rng.standard_normal((4, 32)), jnp.bfloat16)
        block = ResBlock(32, dtype=jnp.bfloat16, quant_convs=quant)
        params = block.init(jax.random.key(0), x, temb)
        four = np.asarray(block.apply(params, x, temb), np.float32)
        two = np.asarray(block.apply(params, x[:2], temb[:2]), np.float32)
        if quant:
            assert (two == four[:2]).all()
        else:
            assert np.abs(two - four[:2]).max() <= 2.0 ** -6 * np.abs(
                four).max()

    @pytest.mark.parametrize("shape", [(2, 8, 8, 64), (1, 4, 6, 96)])
    def test_the_bias_is_added_in_the_activations_shape(self, shape):
        """The product is reshaped to ``(B, H, W, features)`` BEFORE the
        bias: added over the flattened positions, the bias made the v5e's
        compiler emit the product in float32 and copy it (PERF.md section
        6, PR 66)."""
        from stable_diffusion_webui_distributed_tpu.models.unet import (
            PointwiseConv,
        )

        x = jnp.zeros(shape, jnp.bfloat16)
        conv = PointwiseConv(32, dtype=jnp.bfloat16)
        params = jax.eval_shape(conv.init, jax.random.key(0), x)
        eqns = jax.make_jaxpr(conv.apply)(params, x).jaxpr.eqns
        names = [eqn.primitive.name for eqn in eqns]
        product = names.index("dot_general")
        assert names[product + 1] == "reshape"
        assert eqns[product + 1].outvars[0].aval.shape == (*shape[:-1], 32)
        (add,) = [eqn for eqn in eqns if eqn.primitive.name == "add"]
        assert add.outvars[0].aval.shape == (*shape[:-1], 32)
        assert names.index("add") > product + 1

    #: sha256 of the lowered text of a small bf16 ``UNet`` by (rows, side).
    #: Eight rows of 64 x 64 (the batch-4 cells' SD1.5 program) and four of
    #: 128 x 128 (``sdxl_pair``'s) take no product and are the text of
    #: d6ed944 (PR 65); two rows (the solo cells' SD1.5 and SDXL programs)
    #: and one take it and are PR 66's text. A PR that means to change one
    #: of these programs replaces its hash and says so.
    LOWERED = {
        (8, 64): "6b016c159572ba8b04aad2cbf233acf2"
                 "24b6af3b238ceff4eb97e5b80d788bd0",
        (4, 128): "42751f6ee290274ccf870b1e2041ab7a"
                  "d0c7d520954f1278c7c81a4eabb79542",
        (2, 64): "3d583350e25a558c9c9cfb0e7353a7d2"
                 "c3dcb46acbb2f86c1ac37308ae314b28",
        (2, 128): "b7a8b5f75c5c098e464d8963cf3fd4a1"
                  "c24784b8232ed4bf5702cf0801a46e26",
        (1, 64): "24d8ae08aa8d4787b35d1e2f5ba52045"
                 "1d8f1b6a83ce5c885de6a6aaed2a13e3"}

    @pytest.mark.parametrize("rows,side", sorted(LOWERED))
    def test_the_lowered_text_is_the_recorded_one(self, rows, side):
        """So that the next drift of a cell's denoise program is seen:
        PR 66 changed the two-row programs (SD1.5's four solo cells too)
        and no others."""
        import hashlib

        config = UNetConfig(
            block_out_channels=(32, 64), down_blocks=(None, 1),
            layers_per_block=2, cross_attention_dim=32,
            num_attention_heads=4, mid_block_depth=1)
        model = UNet(config, dtype=jnp.bfloat16)
        args = (jnp.zeros((rows, side, side, 4), jnp.float32),
                jnp.zeros((rows,), jnp.float32),
                jnp.zeros((rows, 77, 32), jnp.float32))
        params = jax.eval_shape(model.init, jax.random.key(0), *args)
        text = jax.jit(model.apply).lower(params, *args).as_text()
        assert (hashlib.sha256(text.encode()).hexdigest()
                == self.LOWERED[rows, side])


class TestTokenizer:
    def test_real_bpe_roundtrip(self, tmp_path):
        # Minimal CLIP-style vocabulary exercising merges + end-of-word.
        import json as js

        chars = "abcdehilorsuwy "
        vocab = {}
        for ch in chars.strip():
            vocab[ch] = len(vocab)
            vocab[ch + "</w>"] = len(vocab)
        for tok in ["lo", "low</w>", "he", "hel", "hell", "hello</w>",
                    "wo", "wor", "worl", "world</w>"]:
            vocab[tok] = len(vocab)
        vocab["<|startoftext|>"] = len(vocab)
        vocab["<|endoftext|>"] = len(vocab)
        merges = [("l", "o"), ("lo", "w</w>"), ("h", "e"), ("he", "l"),
                  ("hel", "l"), ("hell", "o</w>"), ("w", "o"), ("wo", "r"),
                  ("wor", "l"), ("worl", "d</w>")]
        (tmp_path / "vocab.json").write_text(js.dumps(vocab))
        (tmp_path / "merges.txt").write_text(
            "#version\n" + "\n".join(f"{a} {b}" for a, b in merges))
        tok = CLIPTokenizer.load(str(tmp_path))
        ids = tok.encode("hello world")
        assert ids == [vocab["hello</w>"], vocab["world</w>"]]
        batch = tok(["hello world"])
        assert batch.shape == (1, 77)
        assert batch[0, 0] == tok.bos and batch[0, 3] == tok.eos

    def test_fallback_deterministic(self):
        tok = FallbackTokenizer(256)
        a, b = tok(["same prompt"]), tok(["same prompt"])
        np.testing.assert_array_equal(a, b)
        assert (tok(["other"]) != a).any()


@pytest.mark.slow
class TestDecodeDtypePolicy:
    """SDTPU_DECODE_DTYPE=bf16 (Policy.decode_in_bf16): decoder convs drop
    to bf16 while GroupNorm statistics and the final conv_out stay f32 —
    the HBM-scratch lever for the b8 1024² decode (round-3 OOM dump shows
    16 GB of f32 conv temps)."""

    def _decode_hlo(self, force_f32):
        import dataclasses
        import re

        cfg = dataclasses.replace(TINY.vae, force_decoder_f32=force_f32)
        vae = VAE(cfg, dtype=jnp.bfloat16)
        params = vae.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)),
                          jax.random.key(1))["params"]
        lat = jnp.zeros((1, 4, 4, 4), jnp.float32)
        hlo = jax.jit(
            lambda p, l: vae.apply({"params": p}, l, method=VAE.decode)
        ).lower(params, lat).as_text()
        return re.findall(r'stablehlo\.convolution.*-> tensor<[0-9x]+x'
                          r'(f32|bf16)>', hlo), params, vae, lat

    def test_bf16_decoder_convs(self):
        dtypes_found, params, vae, lat = self._decode_hlo(force_f32=False)
        assert dtypes_found, "no convolutions found in decode HLO"
        # all convs except the final conv_out (pinned f32) are bf16
        assert dtypes_found.count("f32") == 1, dtypes_found
        assert dtypes_found[-1] == "f32"  # conv_out stays f32
        out = jax.jit(lambda p, l: vae.apply({"params": p}, l,
                                             method=VAE.decode))(params, lat)
        assert out.dtype == jnp.float32  # image always comes back f32

    def test_f32_default_unchanged(self):
        dtypes_found, *_ = self._decode_hlo(force_f32=True)
        assert set(dtypes_found) == {"f32"}

    def test_engine_policy_wires_it(self):
        from stable_diffusion_webui_distributed_tpu.pipeline.engine import (
            Engine,
        )
        from stable_diffusion_webui_distributed_tpu.runtime import dtypes
        from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
            GenerationState,
        )

        pol = dtypes.Policy(decode_in_bf16=True)
        from test_pipeline import init_params

        eng = Engine(TINY, init_params(TINY), policy=pol,
                     state=GenerationState())
        assert eng.vae.cfg.force_decoder_f32 is False
        # default policy leaves the family config untouched
        eng2 = Engine(TINY, init_params(TINY), state=GenerationState())
        assert eng2.vae.cfg.force_decoder_f32 is True

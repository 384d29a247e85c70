"""Seeded random weights in seconds, without tracing a forward pass.

``bench.family_params`` runs flax's initialisers by tracing the whole model
(97-99 s for SD1.5 on an empty compile cache, PERF.md section 5). Here the
parameter tree comes from ``jax.eval_shape`` of each module's ``init`` (no
FLOPs, no device memory), and one jitted call per component fills it: leaves
of one shape and kind share a single uniform draw, sliced per leaf. Values
are made directly in the storage dtype, so SDXL never exists in float32.

The fill keeps flax's default variances (the ones PR 21's images passed
``check_images`` with): a kernel has variance 1/fan_in, an embedding table
1/features, ``scale`` is 1, ``bias`` is 0, any other leaf (CLIP's
``position_embedding``) is uniform with standard deviation 0.01.
"""

from __future__ import annotations

import math


def component_inits(family):
    """{component: (flax module, abstract example arguments)} for a
    ``ModelFamily``: the modules the engine builds, at the smallest input
    that fixes every parameter's shape."""
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.models.clip import (
        CLIPTextModel,
    )
    from stable_diffusion_webui_distributed_tpu.models.unet import UNet
    from stable_diffusion_webui_distributed_tpu.models.vae import VAE

    f32, i32 = jnp.float32, jnp.int32
    s = jax.ShapeDtypeStruct
    ucfg = family.unet
    unet_args = [s((2, 16, 16, ucfg.in_channels), f32), s((2,), f32),
                 s((2, 77, ucfg.cross_attention_dim), f32)]
    if ucfg.addition_embed_dim:
        unet_args.append(s((2, ucfg.projection_input_dim), f32))
    px = 8 * family.vae_scale_factor
    out = {
        "text_encoder": (CLIPTextModel(family.text_encoder),
                         [s((1, family.text_encoder.max_length), i32)]),
        "unet": (UNet(ucfg), unet_args),
        "vae": (VAE(family.vae),
                [s((1, px, px, family.vae.in_channels), f32),
                 jax.random.key(1)]),
    }
    if family.text_encoder_2:
        out["text_encoder_2"] = (
            CLIPTextModel(family.text_encoder_2),
            [s((1, family.text_encoder_2.max_length), i32)])
    return out


def param_shapes(module, args):
    """The ``params`` tree of ``module.init`` as ShapeDtypeStructs."""
    import jax

    return jax.eval_shape(
        lambda *a: module.init(jax.random.key(0), *a), *args)["params"]


def leaf_rule(path: str, shape) -> tuple[str, float]:
    """(kind, half-width of the uniform draw) for one leaf, by its name."""
    name = path.rsplit("/", 1)[-1]
    if name == "kernel":
        return "draw", math.sqrt(3.0 / max(1, math.prod(shape[:-1])))
    if name == "embedding":
        return "draw", math.sqrt(3.0 / shape[-1])
    if name == "scale":
        return "ones", 0.0
    if name == "bias":
        return "zeros", 0.0
    return "draw", 0.01 * math.sqrt(3.0)


def fill(shapes, dtype, seed: int):
    """One jitted call: the tree ``shapes`` filled from ``seed``. Floating
    leaves take ``dtype``; leaves of one (kind, shape) share one draw."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    groups: dict = {}
    for i, (path, leaf) in enumerate(flat):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        kind, width = leaf_rule(name, leaf.shape)
        groups.setdefault((kind, width, tuple(leaf.shape)), []).append(i)

    def build(seed_scalar):
        key = jax.random.key(seed_scalar, impl="rbg")
        out = [None] * len(flat)
        for g, ((kind, width, shape), members) in enumerate(groups.items()):
            if kind == "draw":
                stack = jax.random.uniform(
                    jax.random.fold_in(key, g), (len(members),) + shape,
                    dtype, -width, width)
                for j, i in enumerate(members):
                    out[i] = stack[j]
            else:
                value = (jnp.ones if kind == "ones" else jnp.zeros)(
                    shape, dtype)
                for i in members:
                    out[i] = value
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jnp.uint32(seed % (2 ** 32)))


def family_params(family, dtype, seed: int) -> dict:
    """The engine's component dict for ``family``, made on the device."""
    params = {"text_encoder_2": None}
    for i, (name, (module, args)) in enumerate(
            sorted(component_inits(family).items())):
        params[name] = fill(param_shapes(module, args), dtype, seed + i)
    return params


def describe(params) -> str:
    import jax

    leaves = jax.tree_util.tree_leaves(params)
    return (f"{sum(x.size for x in leaves) / 1e6:.1f} M parameters, "
            f"{sum(x.nbytes for x in leaves) / 2**30:.2f} GiB as "
            f"{leaves[0].dtype.name}")

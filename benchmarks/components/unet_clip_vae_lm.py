"""The components of a UNet + CLIP + VAE family that also holds a resident
language model (``ModelFamily.expander``, models/lm.py): what
``components/unet_clip_vae.py`` gives, plus ``expander``.

``leaf_rule`` answers for the leaves whose name and shape do not give the
fan-in: a stacked expert kernel ``(experts, in, out)`` takes its fan-in from
``in``, the router's weight ``(in, experts)`` from ``in`` (tables keep the
default 1/features). ``harness/weights.py:fill`` draws every leaf of one
(kind, half-width, shape) as ONE stacked array: the twelve equal-shaped
expert kernels of a Laguna share (128 x 3072 x 1024 each) would be a single
9.7 GB draw beside its slices. So an expert kernel's half-width differs from
its neighbours' in the last digits (a relative 1e-9 per leaf, far below
bfloat16's rounding): each then is a group of its own, drawn at its own
shape from its own key.
"""

import importlib.util
import math
import os
import zlib

_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _base():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "unet_clip_vae.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_components_unet_clip_vae", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def component_inits(family):
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.models import lm

    out = _base().component_inits(family)
    cfg = family.expander
    if cfg is not None:
        s = jax.ShapeDtypeStruct
        cache = {name: [s(shape, jnp.float32) for shape in rows]
                 for name, rows in lm.cache_shapes(cfg, 8).items()}
        out["expander"] = (lm.DecoderLM(cfg), [
            s((4,), jnp.int32), s((), jnp.int32), s((), jnp.int32), cache])
    return out


def leaf_rule(path: str, shape):
    parts = path.split("/")
    if parts[-1] in _EXPERT_LEAVES and len(shape) == 3:
        own = 1.0 + (zlib.crc32(path.encode()) % 1000003) * 1e-15
        return "draw", math.sqrt(3.0 / shape[1]) * own
    if parts[-1] == "router":
        return "draw", math.sqrt(3.0 / shape[0])
    return None

"""The components of a UNet + CLIP + VAE family whose resident language
model (``ModelFamily.expander``, models/lm.py) has latent-attention layers,
several residual streams and a router with a selection bias: what
``components/unet_clip_vae_lm.py`` gives (``lm.cache_shapes`` hands its
example arguments the latent buffers), with rules for the leaves of a
stream mixer and for the bias, whose name and shape do not say how to draw
them.

A mixer's ``phi`` ``(streams * hidden, n^2 + 2n)`` takes half of a kernel's
deviation from its fan-in and ``alpha`` is 1, so its three projections of
the normed state have deviation 1/2; ``b_res`` ``(n, n)`` is uniform on
[-1, 1]. ``exp`` of their sum then spreads over about two decades, twenty
Sinkhorn iterations bring every token's matrix to row and column sums
within 1e-3 of 1, and ``H_res`` comes out visibly neither the identity nor
uniform (row maxima from 0.25 to 0.9; the reference's run reports them).
``harness/weights.py``'s default for an unknown leaf (deviation 0.01) would
make every ``H_res`` uniform to two digits, and a mixer that was skipped or
run in a lower precision would pass the comparison with the reference
unseen. ``b_pre`` and ``b_post`` keep that default: ``H_pre`` is about 1/2
and ``H_post`` about 1, moved by the projections. The router's
``e_score_correction_bias`` is uniform with deviation 0.1, wide enough
beside sigmoid scores to change which experts are chosen in some (token,
layer) pairs (the reference's run reports the share).
"""

import functools
import importlib.util
import math
import os

B_RES_HALF_WIDTH = 1.0
SELECTION_BIAS_DEVIATION = 0.1


@functools.lru_cache(maxsize=None)
def _base():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "unet_clip_vae_lm.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_components_unet_clip_vae_lm", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def component_inits(family):
    return _base().component_inits(family)


def leaf_rule(path: str, shape):
    name = path.rsplit("/", 1)[-1]
    if name == "phi":
        return "draw", 0.5 * math.sqrt(3.0 / shape[0])
    if name == "alpha":
        return "ones", 0.0
    if name == "b_res":
        return "draw", B_RES_HALF_WIDTH
    if name == "e_score_correction_bias":
        return "draw", SELECTION_BIAS_DEVIATION * math.sqrt(3.0)
    return _base().leaf_rule(path, shape)

"""The components of a UNet + CLIP + VAE family whose resident language
model (``ModelFamily.expander``, models/lm.py) decodes several sequences a
step over gated-delta-rule layers AND latent-attention layers under sigmoid-
gated norms, with a router that has a selection bias: what
``components/unet_clip_vae_lm_table.py`` gives (the token table drawn at
variance 1, and why: at the harness's default every router sees nearly one
shared vector and the sequences of a step choose the same few experts),
with the siblings' rules for the leaves whose name and shape do not say
how to draw them, and one of its own.

``A_log`` is uniform on [-4, 4] and the convolution's taps take their
fan-in from the taps, as ``components/unet_clip_vae_lm_delta.py`` draws
Qwen3-Next's: ``exp(A_log)`` is then log-uniform from 0.018 to 55, some
heads forget inside a token and some remember hundreds, so a state that was
dropped, shared between sequences or kept in bfloat16 reads far from the
reference. ``e_score_correction_bias`` is uniform with deviation 0.1, as
``components/unet_clip_vae_lm_kanana2.py`` draws kanana-2's, so that a
router that left the bias out of the choice would show.

A norm's ``weight`` is uniform with deviation 0.5 (half-width 0.866). Every
norm of this model is ``x_hat * 2 sigmoid(weight)`` and the delta
read-out's ``x_hat * (1 + weight)``; at the harness's default for an
unknown leaf (deviation 0.01) both are 1 to two digits, and a program that
read one kind of norm as the other (``1 + w`` against ``2 sigmoid(w)``: 1.5
against 1.24 at ``w`` = 0.5) would pass the comparison with the reference
unseen. ``dt_bias`` keeps the default.
"""

import functools
import importlib.util
import math
import os

A_LOG_HALF_WIDTH = 4.0
SELECTION_BIAS_DEVIATION = 0.1
NORM_WEIGHT_DEVIATION = 0.5


@functools.lru_cache(maxsize=None)
def _base():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "unet_clip_vae_lm_table.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_components_unet_clip_vae_lm_table", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def component_inits(family):
    return _base().component_inits(family)


def leaf_rule(path: str, shape):
    name = path.rsplit("/", 1)[-1]
    if name == "A_log":
        return "draw", A_LOG_HALF_WIDTH
    if name == "conv_kernel":
        return "draw", math.sqrt(3.0 / shape[0])
    if name == "e_score_correction_bias":
        return "draw", SELECTION_BIAS_DEVIATION * math.sqrt(3.0)
    if name == "weight" and len(shape) == 1:    # no other component has one
        return "draw", NORM_WEIGHT_DEVIATION * math.sqrt(3.0)
    return _base().leaf_rule(path, shape)

"""The components of a UNet + CLIP + VAE family whose resident language
model (``ModelFamily.expander``, models/lm.py) has gated short-convolution
layers and a router with a selection bias: what
``components/unet_clip_vae_lm.py`` gives (``lm.cache_shapes`` hands its
example arguments the conv layers' kept rows too), with rules for the two
leaves whose name and shape do not say how to draw them.

A conv mixer's taps ``conv_kernel`` ``(taps, channels)`` take their fan-in
from the taps, every tap alike: the two taps that read the KEPT rows weigh
as much as the one that reads the current row, and the convolution's output
has the deviation of its input. ``harness/weights.py``'s default for an
unknown leaf (deviation 0.01) would make a conv mixer add a hundredth of
what an attention adds, and a cache that dropped its kept rows, or a layer
that skipped its mixer, would pass the comparison with the reference
unseen. The router's ``e_score_correction_bias`` is uniform with deviation
0.1, as the Xing4.0 share's is: wide enough beside sigmoid scores to change
which experts are chosen in some (token, layer) pairs (the reference's run
reports the share).
"""

import functools
import importlib.util
import math
import os

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
    if name == "conv_kernel":
        return "draw", math.sqrt(3.0 / shape[0])
    if name == "e_score_correction_bias":
        return "draw", SELECTION_BIAS_DEVIATION * math.sqrt(3.0)
    return _base().leaf_rule(path, shape)

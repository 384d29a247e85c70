"""The components of a UNet + CLIP + VAE family whose resident language
model (``ModelFamily.expander``, models/lm.py) has linear-attention layers:
what ``components/unet_clip_vae_lm.py`` gives (``lm.cache_shapes`` hands its
example arguments the recurrent state and the convolution's inputs too),
with rules for the leaves of a gated-delta-rule mixer whose name and shape
do not say how to draw them.

``A_log`` (a layer's decay rates, one a value head) is drawn uniformly from
[-4, 4]: ``exp(A_log)`` is then log-uniform from 0.018 to 55, so some heads
forget inside a token and some remember hundreds. ``harness/weights.py``'s
default for an unknown leaf (deviation 0.01) would make ``exp(A_log)``
about 1 in every head: all forget inside ten tokens, and a dropped or stale
recurrent state would pass the comparison with the reference unseen. The
convolution's taps ``(taps, channels)`` take their fan-in from the taps.
Zero-centred norm weights and ``dt_bias`` keep the default (deviation 0.01:
a norm multiplies by about 1, the decay's softplus sees its projection).
"""

import functools
import importlib.util
import math
import os

A_LOG_HALF_WIDTH = 4.0


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
    if name == "A_log":
        return "draw", A_LOG_HALF_WIDTH
    if name == "conv_kernel":
        return "draw", math.sqrt(3.0 / shape[0])
    return _base().leaf_rule(path, shape)

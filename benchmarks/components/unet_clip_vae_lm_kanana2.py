"""The components of a UNet + CLIP + VAE family whose resident language
model (``ModelFamily.expander``, models/lm.py) decodes several sequences a
step over latent-attention layers and a router with a selection bias: what
``components/unet_clip_vae_lm_table.py`` gives (the token table drawn at
variance 1, and why: at the harness's default every router sees nearly one
shared vector and the sequences of a step choose the same few experts,
which takes the distinct-expert reads of a forked step out of the cell that
measures them), with one rule more, for the bias.

The router's ``e_score_correction_bias`` is drawn uniform with deviation
0.1 (half-width 0.173), as ``components/unet_clip_vae_lm_latent.py`` draws
Xing4.0's: wide enough beside sigmoid scores to change which experts are
chosen in most (token, layer) pairs (``reference/kanana2_ref.py`` reports
the share), so that a router that left the bias out of the choice reads
far from the reference. The harness's default for an unknown leaf
(deviation 0.01) would change few choices and that fault would pass.
"""

import functools
import importlib.util
import math
import os

SELECTION_BIAS_DEVIATION = 0.1


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
    if path.rsplit("/", 1)[-1] == "e_score_correction_bias":
        return "draw", SELECTION_BIAS_DEVIATION * math.sqrt(3.0)
    return _base().leaf_rule(path, shape)

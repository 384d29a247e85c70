"""The components of a UNet + CLIP + VAE family whose resident language
model (``ModelFamily.expander``, models/lm.py) is looped and has an exit
gate: what ``components/unet_clip_vae_lm_table.py`` gives (the token table
drawn at variance 1, and why), with one rule more, for the gate.

``lambda_t = sigmoid(w_g . h_t + b_g)`` reads a pass's NORMED output, whose
norm is sqrt(features) under scales of 1. ``harness/weights.py`` would draw
``w_g`` at variance 1/features, so ``w_g . h_t`` has deviation 1; here it is
drawn at a quarter of that (half the half-width), deviation one half, and
the bias stays 0. ``|w_g . h + b_g|`` then stays under 10 by twenty
deviations, where a float32 sigmoid is still under ``1 - 4e-5``: one that
rounded to 1 would make ``S_1 >= 1`` and end a token at its first pass under
the published threshold of 1. The largest ``lambda`` a run saw is in
``reference/ouro_ref.py``'s readings and in the program's
``serving.expander.exit_lambda_max``.


The norms AFTER the sublayers (``input_norm_2``, ``post_attention_norm_2``)
get scales of deviation 0.1 (uniform, either sign: a fixed diagonal map),
not the default 1. At 1 every sublayer adds an output of the state's own
norm, 96 of them a pass swamp the state that came in, and every pass lands
on nearly the same state: on the chip, at the published widths and seed 49,
a model of three passes read 8.4e-3 from the reference of four, and every
pass attending the last pass's keys and values 1.7e-3, where the program
itself reads 1.6e-3 (my chip runs, PR 49, call 1): the mechanism the cell
exists for, a pass with keys and values of its OWN, could not be told from
its absence. At 0.1 the 96 outputs of a pass add up to about the energy of
the state that came in (96 x 0.01): a pass refines the state, as a trained
looped model's does, and what a pass attends matters.
"""

import functools
import importlib.util
import math
import os

GATE = "early_exit_gate/kernel"
AFTER = ("input_norm_2/scale", "post_attention_norm_2/scale")


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
    if path == GATE:
        return "draw", 0.5 * math.sqrt(3.0 / shape[0])
    if path.endswith(AFTER):
        return "draw", 0.1 * math.sqrt(3.0)
    return _base().leaf_rule(path, shape)

"""The components of a UNet + CLIP + VAE family whose resident language
model (``ModelFamily.expander``, models/lm.py) decodes several sequences a
step over shortcut-connected double layers of latent attention whose ONE
router is a softmax over experts that have kernels and zero-compute
identity experts, chosen under a selection bias: what
``components/unet_clip_vae_lm_table.py`` gives (the token table drawn at
variance 1, and why: at the harness's default every router sees nearly one
shared vector and the sequences of a step choose the same few experts; the
stacked expert kernels ``(held, in, out)`` each a draw of its own from its
fan-in, the router's weight from its first dimension), with one rule more,
for the bias.

The router's ``e_score_correction_bias`` is drawn uniform with half-width
``0.5 / outputs`` (6.5e-4 at the published 768 outputs). Its scores are a
softmax over ALL outputs, so they sum to one and a typical one is
``1 / outputs``; the twelve chosen lie a few times over that and the gap
between the twelfth and the thirteenth is a fraction of ``1 / outputs``. A
bias of half a typical score therefore changes the chosen set in a good
part of the (token, router) pairs and leaves it in the rest
(``reference/longcat_flash_ref.py`` reports the share), so that a router
that left the bias out of the choice, or weighed by it, would show. The
siblings' rule (deviation 0.1 beside sigmoid scores of order one) would be
130 times a typical score here: every token would choose the twelve
largest biases, all sequences the same experts. The harness's default for
an unknown leaf (deviation 0.01) would do the same.

``q_b_proj`` is drawn at ``1 / latent_q_scale`` times its fan-in's
deviation and ``kv_b_proj`` at ``1 / latent_kv_scale`` times (2.0 and
12^0.5 at the published widths), so that the scaled queries, un-rotated
keys and values are of order one, as a checkpoint trained under the two
scales has them. At variance 1/fan-in the scaled scores have deviation 5.7
(2 x 3.46 x 128^0.5 x 192^-0.5), every softmax over two thousand latents is
near an arg-max, each attention multiplies a relative perturbation of its
input by about eight and eight of them in a row make rounding in bfloat16
read 0.40 from the reference, the same with the held experts' part dropped
and with the router in bfloat16 (my chip run, PR 67, call 1): a comparison
that can tell nothing apart. Drawn so, a program that left a scale out
still shows (its scores are half, or its values 0.29 times, what they
should be). Every 2-D kernel's half-width differs from its neighbours' in
the last digits (a relative 1e-9 a leaf), as
``components/unet_clip_vae_lm_falcon_h1.py`` draws Falcon-H1's and for its
reason: ``harness/weights.py`` draws the leaves of one (kind, half-width,
shape) as ONE stacked array, and sixteen dense kernels of 6144 x 12288
would be a 2.4 GB draw beside its slices.
"""

import functools
import importlib.util
import math
import os
import zlib

#: the bias's half-width, in typical scores (``1 / outputs``)
SELECTION_BIAS_HALF_WIDTH_IN_SCORES = 0.5
_SCALES: dict = {}


@functools.lru_cache(maxsize=None)
def _base():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "unet_clip_vae_lm_table.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_components_unet_clip_vae_lm_table", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _scaled_by(cfg) -> dict:
    """By the module that holds a kernel, the scale its product is
    multiplied by."""
    return {"q_b_proj": cfg.latent_q_scale, "kv_b_proj": cfg.latent_kv_scale}


def component_inits(family):
    if family.expander is not None:
        _SCALES.clear()
        _SCALES.update(_scaled_by(family.expander))
    return _base().component_inits(family)


def _published() -> dict:
    from stable_diffusion_webui_distributed_tpu.models import configs

    return _scaled_by(configs.LONGCAT_FLASH_CHAT)


def leaf_rule(path: str, shape):
    parts = path.split("/")
    if parts[-1] == "e_score_correction_bias":
        return "draw", SELECTION_BIAS_HALF_WIDTH_IN_SCORES / shape[0]
    if parts[-1] == "kernel" and len(shape) == 2:
        own = 1.0 + (zlib.crc32(path.encode()) % 1000003) * 1e-15
        by = (_SCALES or _published()).get(parts[-2], 1.0)
        return "draw", math.sqrt(3.0 / shape[0]) / by * own
    return _base().leaf_rule(path, shape)

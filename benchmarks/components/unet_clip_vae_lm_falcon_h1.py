"""The components of a UNet + CLIP + VAE family whose resident language
model (``ModelFamily.expander``, models/lm.py) applies forward multipliers
and holds a selective state-space mixer beside attention in every layer:
what ``components/unet_clip_vae_lm.py`` gives, with rules for the leaves a
multiplier scales and for a state-space mixer's own.

**A leaf that a multiplier ``m`` scales is drawn at ``1 / m`` times the
deviation it would have had**, so that the stream after the table, each
mixer's contribution, the attention scores and the logits are of order 1,
as a trained checkpoint's are. At variance-1/fan-in weights keys scaled by
0.011 make every softmax flat (a wrong rotation would pass the comparison
with the reference unseen), logits scaled by 1/128 make every draw uniform,
and mixers scaled by 0.04-0.09 vanish beside a stream of 5.7 (a dropped
state, a shared state or a state kept in bfloat16 would pass unseen). The
multipliers are read from the family's own ``LMConfig``
(``component_inits`` keeps them for ``leaf_rule``, which is handed a path
and a shape alone; before any family was seen they are the published
model's): the token table at variance ``1 / m_e^2`` (rows of the scale of
the sublayers' outputs once scaled, as ``unet_clip_vae_lm_table.py`` draws
its table and for its reason), ``lm_head`` at ``1 / m_h``, ``k_proj`` at
``1 / m_k``, ``o_proj`` at ``1 / m_ao``, a state-space mixer's ``out_proj``
at ``1 / m_so``, its ``in_proj`` at ``1 / (m_si g)`` with ``g`` the
geometric mean of the five ``ssm_multipliers`` (a leaf has ONE deviation:
its five column ranges then come out at 0.6 to 1.7), ``gate_proj`` at ``1
/ m_g``, ``down_proj`` at ``1 / m_d``. ``q_proj``, ``v_proj`` and
``up_proj`` keep variance 1/fan-in.

``A_log`` is uniform on [-4, 4], as ``unet_clip_vae_lm_delta.py`` draws a
delta mixer's and for its reason: ``exp(A_log)`` is log-uniform from 0.018
to 55 and ``dt`` about 1, so some heads forget inside a token and some
remember hundreds. ``dt_bias`` (deviation 0.5), the convolution's bias
(0.5), the skip ``D`` (8: half of what the state's 256 columns add to a
head's output) and the read-out norm's weight (1) are off the values flax
would give them (1, 0, 1, 1): a dropped bias, a dropped skip, a norm before
the gate or over the wrong groups must show. The taps take their fan-in
from the taps.

Every large kernel's half-width differs from its neighbours' in the last
digits, as ``unet_clip_vae_lm.py`` does for the experts' and for its
reason: ``harness/weights.py`` draws the leaves of one (kind, half-width,
shape) as ONE stacked array, and nine layers' ``gate_proj`` would be a
1.98 GB draw beside its slices.
"""

import functools
import importlib.util
import math
import os
import zlib

A_LOG_HALF_WIDTH = 4.0
DEVIATIONS = {"dt_bias": 0.5, "conv_bias": 0.5, "D": 8.0}
_MULTIPLIERS: dict = {}


@functools.lru_cache(maxsize=None)
def _base():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "unet_clip_vae_lm.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_components_unet_clip_vae_lm", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _scaled_by(cfg) -> dict:
    """By the last two names of a leaf's path, the multiplier its product
    is scaled by."""
    spread = math.prod(cfg.ssm_multipliers) ** (1 / len(cfg.ssm_multipliers))
    before, after = cfg.mixer_multiplier("ssm")
    return {
        "embed_tokens/embedding": cfg.embedding_multiplier,
        "lm_head/kernel": cfg.logit_multiplier,
        "attn/k_proj": cfg.key_multiplier,
        "attn/o_proj": cfg.mixer_multiplier("full")[1],
        "ssm/in_proj": before * spread,
        "ssm/out_proj": after,
        "mlp/gate_proj": cfg.mlp_multipliers[0],
        "mlp/down_proj": cfg.mlp_multipliers[1]}


def component_inits(family):
    if family.expander is not None:
        _MULTIPLIERS.clear()
        _MULTIPLIERS.update(_scaled_by(family.expander))
    return _base().component_inits(family)


def _published() -> dict:
    from stable_diffusion_webui_distributed_tpu.models import configs

    return _scaled_by(configs.FALCON_H1_34B)


def leaf_rule(path: str, shape):
    parts = path.split("/")
    name = parts[-1]
    scaled = _MULTIPLIERS or _published()
    if path == "embed_tokens/embedding":    # variance 1 once scaled
        return "draw", math.sqrt(3.0) / scaled[path]
    if name == "kernel" and len(shape) == 2:
        own = 1.0 + (zlib.crc32(path.encode()) % 1000003) * 1e-15
        by = scaled.get(path if path == "lm_head/kernel"
                        else "/".join(parts[-3:-1]), 1.0)
        return "draw", math.sqrt(3.0 / shape[0]) / by * own
    if name == "A_log":
        return "draw", A_LOG_HALF_WIDTH
    if name == "conv_kernel":
        return "draw", math.sqrt(3.0 / shape[0])
    if name in DEVIATIONS:
        return "draw", DEVIATIONS[name] * math.sqrt(3.0)
    if path.endswith("ssm/norm/scale"):
        return "draw", math.sqrt(3.0)
    return _base().leaf_rule(path, shape)

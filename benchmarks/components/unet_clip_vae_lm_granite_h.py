"""The components of a UNet + CLIP + VAE family whose resident language
model (``ModelFamily.expander``, models/lm.py) is nine state-space layers
alone to one unrotated attention, every one in front of a router over
experts held by share beside a shared expert, under a residual multiplier,
a score scale that is not ``head_dim ** -0.5`` and a head that IS the token
table: what ``components/unet_clip_vae_lm.py`` gives (the stacked expert
kernels ``(held, in, out)`` each a draw of its own from its fan-in, the
router's weight from its first dimension), with rules for the leaves a
scalar of the forward pass scales, for a state-space mixer's own, and for
the table.

**A leaf that a scalar ``m`` of the forward pass scales is drawn at ``1 /
m`` times the deviation it would have had**, as
``components/unet_clip_vae_lm_falcon_h1.py`` draws Falcon-H1's and for its
reason: a checkpoint trained under the scalars has weights that make the
stream, each sublayer's contribution and the scores of order one, and a
comparison on weights that do not cannot tell the model's mechanisms
apart. The scalars are read from the family's own ``LMConfig``
(``component_inits`` keeps them for ``leaf_rule``, which is handed a path
and a shape alone; before any family was seen they are the published
model's):

- what a sublayer adds is times ``residual_multiplier`` (0.22): ``o_proj``,
  a state-space mixer's ``out_proj``, the shared expert's ``down_proj`` and
  every expert's ``w_down`` are drawn at ``1 / 0.22``, so a sublayer adds a
  vector of the stream's own scale and ten layers move it (at variance 1 /
  fan-in twenty sublayers add 0.22 each to a stream of 1: dropping the held
  experts, the shared expert or the attention would read a few percent);
- the scores are times ``attention_scale`` (1/128) where a model of this
  head width would have ``128 ** -0.5``: ``q_proj`` and ``k_proj`` are each
  drawn at ``(attention_scale * head_dim ** 0.5) ** -0.5`` (3.36), so the
  scaled scores have deviation 1. At variance 1 / fan-in they have 0.09:
  every softmax is flat, the ONE layer that sees positions averages them,
  and a rotation or the wrong scale would pass unseen;
- **the table** is times ``embedding_multiplier`` (12) on the way in, and
  is ALSO the head, over ``logits_scaling`` (16) on the way out: ONE leaf
  under both. It is drawn at variance ``1 / 12^2``: rows of deviation 1 once
  multiplied, the stream's scale, as ``unet_clip_vae_lm_table.py`` argues
  for its table. As the head that makes a row's logits a product of the
  unit-RMS normed state with rows of deviation 1/12 over 4096 channels,
  over 16: deviation ``64 / 12 / 16 = 0.33``, and the row's OWN input token,
  whose embedding is still a part of the stream after ten layers, a logit
  of about ``12 * 4096 / 144 / (16 * rms(h)) = 21 / rms(h)``, 4 to 5: at
  temperature 1.0 a draw is near uniform over the 50 176 held ids and
  repeats its input in under 1 % of steps (``reference/granite_h_ref.py``
  reports the share). At the siblings' variance 1 the same arithmetic gives
  the own token a logit of ``12 * 4096 / (16 * 12) = 256`` against a spread
  of 4: every draw repeats its input, all four sequences of a request make
  the prompt's last token for ever, and a step's four rows choose ONE
  row's experts. A tied head leaves no second leaf to draw apart.

``A_log`` is uniform on [-4, 4], ``dt_bias`` (deviation 0.5), the
convolution's bias (0.5), the skip ``D`` (8) and the read-out norm's weight
(1) are off the values flax would give them, all as Falcon-H1's are drawn
and for its reasons: some heads forget inside a token and some remember
hundreds; a dropped bias, a dropped skip or a norm before the gate must
show. The taps take their fan-in from the taps.

Every large kernel's half-width differs from its neighbours' in the last
digits, as ``unet_clip_vae_lm.py`` does for the experts' and for its
reason: ``harness/weights.py`` draws the leaves of one (kind, half-width,
shape) as ONE stacked array, and nine layers' ``in_proj`` would be a
1.2 GB draw beside its slices.
"""

import functools
import importlib.util
import math
import os
import zlib

TABLE = "embed_tokens/embedding"
A_LOG_HALF_WIDTH = 4.0
DEVIATIONS = {"dt_bias": 0.5, "conv_bias": 0.5, "D": 8.0}
_SCALED: dict = {}


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
    """By the last two names of a leaf's path (the table's whole path), the
    scalar its product is multiplied by."""
    scores = ((cfg.attention_scale or cfg.head_dim ** -0.5)
              * cfg.head_dim ** 0.5) ** 0.5
    added = cfg.residual_multiplier
    return {TABLE: cfg.embedding_multiplier,
            "attn/q_proj": scores, "attn/k_proj": scores,
            "attn/o_proj": added, "ssm/out_proj": added,
            "shared_expert/down_proj": added, "experts/w_down": added}


def component_inits(family):
    if family.expander is not None:
        _SCALED.clear()
        _SCALED.update(_scaled_by(family.expander))
    return _base().component_inits(family)


def _published() -> dict:
    from stable_diffusion_webui_distributed_tpu.models import configs

    return _scaled_by(configs.GRANITE_4_H_SMALL)


def leaf_rule(path: str, shape):
    parts = path.split("/")
    name = parts[-1]
    scaled = _SCALED or _published()
    if path == TABLE:       # deviation 1 once multiplied
        return "draw", math.sqrt(3.0) / scaled[path]
    if name == "kernel" and len(shape) == 2:
        own = 1.0 + (zlib.crc32(path.encode()) % 1000003) * 1e-15
        by = scaled.get("/".join(parts[-3:-1]), 1.0)
        return "draw", math.sqrt(3.0 / shape[0]) / by * own
    if name == "w_down" and len(shape) == 3:
        kind, width = _base().leaf_rule(path, shape)
        return kind, width / scaled["experts/w_down"]
    if name == "A_log":
        return "draw", A_LOG_HALF_WIDTH
    if name == "conv_kernel":
        return "draw", math.sqrt(3.0 / shape[0])
    if name in DEVIATIONS:
        return "draw", DEVIATIONS[name] * math.sqrt(3.0)
    if path.endswith("ssm/norm/scale"):
        return "draw", math.sqrt(3.0)
    return _base().leaf_rule(path, shape)

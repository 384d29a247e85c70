"""A decoder-only language model read from its config's lists: the family's
resident prompt expander (``ModelFamily.expander``, pipeline/engine.py's
``expand`` stage).

Nothing here names an architecture. ``LMConfig`` says, layer by layer, which
token mixer a layer has (``"full"``: attention over every earlier
position; ``"sliding"``: over the last ``sliding_window``; ``"linear"``: a
gated delta rule over a recurrent state, ops/delta_rule.py, behind a short
causal convolution; ``"latent"``: attention whose cache holds one low-rank
latent and one rotated key a position, shared by every head, in three forms
over that one cache, :class:`LatentAttention`; ``"conv"``: a gated causal
convolution over the hidden channels, :class:`ShortConv`; ``"ssm"``: a
selective state-space recurrence, ops/ssm.py, behind a short causal
convolution with a bias, :class:`SSMMixer`), or SEVERAL of them side by
side (``"full+ssm"``: the base kinds joined by ``+``, each reading the
layer's ONE normed input over buffers of its own, their sum entering the
residual once; ``configs.kind_parts`` is the one place that splits the
spelling), how many query
heads an attention layer has (they may differ by layer; the KV heads are shared by
groups of them), which rotary parameterisation goes with which kind,
whether queries and keys are normed per head or over the whole projection
(``qk_norm_extent``) and rotated at all (``rope_full`` None: no table),
on which side of a sublayer a layer's norm stands (``norm_placement``, by
layer), how strongly a linear layer may write (``linear_write_scale``),
and whether the MLP is dense
or a router over experts (ops/moe.py) with one shared expert, gated or
not, or with none. An attention output passes a sigmoid gate before
``o_proj``: one a head from ``g_proj``, one a channel from the second half
of ``q_proj``'s columns, or no gate (``LMConfig.attn_gate``; a latent
layer's gate is a ``g_proj`` of its own, one a head or one a value
channel). A norm is ``x_hat * scale``, zero-centred ``x_hat * (1 +
weight)``, or under a sigmoid ``x_hat * c sigmoid(weight)``
(``norm_sigmoid_scale``); a SwiGLU may be clamped (``swiglu_limit``); a
linear layer's read-out is gated by ``silu(z)`` or by ``c sigmoid(z)``
(``linear_sigmoid_gate_scale``). The router's scores are a softmax or sigmoids,
with or without a bias that chooses (ops/moe.py:route). With
Forward multipliers (``LMConfig.embedding_multiplier``,
``logit_multiplier``, ``key_multiplier``, ``mixer_multipliers``,
``ssm_multipliers``, ``mlp_multipliers``) scale the table's output, the
logits, the keys, each mixer's input and output, a state-space mixer's
projected ranges and a dense SwiGLU's gate and output where the forward
pass says; one that is 1.0 traces no op. ``residual_multiplier`` scales
what EACH sublayer adds to the residual, an expert layer's routed sum and
shared expert included, and ``attention_scale`` replaces ``head_dim **
-0.5`` as the scores' multiplier of a full or a sliding layer, in all three
of its forms. With ``tied_head`` the head is the token table itself: no
``lm_head`` leaf, the logits the normed state contracted with the held
slice as it lies (:meth:`DecoderLM.tied_logits`). A latent layer's queries
and its normed latent may each carry a scalar (``latent_q_scale``,
``latent_kv_scale``: the cache holds the latent scaled). A router's LAST
ids may be zero-compute experts that return their input
(``zero_experts``: no kernels, ``(the picks' weights on them) * n`` added
where the token lives), and an expert layer's routed sum may be carried out
of its layer and land after the NEXT layer's MLP sublayer
(``moe_shortcut``: two entries of the lists then spell one
shortcut-connected double layer, two token mixers, two dense MLPs and one
router whose sum arrives a token mixer and an MLP late). With
``residual_streams`` over 1 a token is ``(streams, hidden)`` between
sublayers and a :class:`StreamMixer` around each sublayer reads, writes
and mixes the streams; with 1 a layer is ``x + F(norm(x))``, ``x +
norm(F(x))`` or ``x + norm(F(norm(x)))`` by its placement.

One call, :meth:`DecoderLM.__call__`, runs a chunk of ``T`` tokens that
starts at position ``start`` against the cache and returns the cache with
the chunk written: a prefill is a long chunk, a decode step a chunk of one.
The cache (cache/kv.py) holds, per layer, the buffers of the layer's kind
(of each of its parts, where it has several: ``k`` and ``v`` beside
``ssm_state`` and ``ssm_conv``), six base kinds in all (a state-space part
is as a linear layer's: the recurrence's ``(heads, head width, state
width)`` float32 and the convolution's last ``taps - 1`` inputs, under
names of its own): a full layer's key and value buffers hold every
position up to their capacity; a sliding layer's are rings of
``sliding_window`` slots, slot ``p % window`` holding position ``p``; a
linear layer has no positions at all but the recurrent state ``(value
heads, key width, value width)`` in float32 and the convolution's last
``taps - 1`` inputs, neither growing with the sequence; a latent layer has
ONE buffer, ``capacity`` rows of the normed latent beside the rotated key;
a conv layer has one too, the ``taps - 1`` last inputs of its convolution,
and that is all of its state.
A chunk may be padded: only its first ``length`` tokens are real. A padded
row is never written to a ring and lands beyond ``end`` in a full or a
latent buffer, where no real query sees it; in a linear layer it neither
decays the state nor writes to it (its decay and write strength are
masked), and a convolution, a linear layer's or a conv layer's, keeps the
last REAL rows.

A prefill is one sequence: a prompt. A decode step may run several
(``sequences``: the images of one request, forked from one prefill): row
``b`` of the chunk is then sequence ``b``'s ONE token, every sequence at the
same position, and the cache is a FORKED one (cache/kv.py:fork): every
attention layer's ``k_shared`` and ``v_shared`` are the prefill's own
buffers, which all sequences read and none writes, and its ``k`` and ``v``
each sequence's own rows behind them, ``(sequences, [passes,] slots, kv
heads, head_dim)``, the token a sequence makes at position ``p`` in slot
``(p - forked_at) % slots``, in a full layer and a sliding one alike (a full
layer wants a slot for every position a sequence will decode, a sliding one
no more than its window). A latent layer goes the same way with its one
buffer: ``latent_shared`` is the prefill's ``(capacity, width)`` latents,
``latent`` each sequence's own ``(sequences, slots, width)`` behind them.
A linear layer has nothing to share: a fork COPIES its
``state`` and ``conv`` once a sequence, ``(sequences, value heads, key
width, value width)`` and ``(sequences, taps - 1, channels)`` under the
names they had, and row ``b`` of a step runs the recurrent step over
sequence ``b``'s own state and a one-row convolution over its own kept
rows; a row that does not count (a pad, a sequence that has ended) leaves
both where they were, as a padded row of a chunk does. A state-space part
goes the same way, so a layer of attention and such a part has buffers
some shared (its keys and values before the fork) and some copied.
Norms, projections, the router, the experts and
the head take the rows as they take a chunk's; the token mixers alone tell
the sequences apart, and read what they share once. That holds for
the ``full``, ``sliding``, ``latent``, ``linear`` and ``ssm`` kinds of one
stream
(:func:`shares_a_step`); a model with conv layers' kept rows or several
residual streams decodes one sequence a step. The sequences of a step may
also continue prompts of their OWN behind one shared range (the kept
instruction; a JOINED cache, cache/kv.py:joined_rows, which carries
:data:`OWN_FROM`): ``start`` is then the step's common position, which
says where every sequence writes, and sequence ``b`` stands ``own_from[b]``
short of it in everything that reads a position: its rotation, its
window's reach into the shared range, the positions of its own rows
(:func:`own_positions`) and the key of its draw. Which cache a step was
handed is read off the cache; the fork's trace has none of the join's ops.

A LOOPED model (``LMConfig.total_ut_steps`` over 1: full attention, dense
MLPs, one stream) passes every token through its whole stack that many
times over one set of weights. The stack is the body of ONE loop over the
passes (``jax.lax.scan`` in :class:`DecoderLM`), its layers are alike and
share one trace of a layer, the final norm closes every pass, and a
learned gate says after which pass the head reads. Pass ``t`` of a layer
attends the keys and values that pass ``t`` wrote for the earlier
positions, so a layer's key and value buffers carry a PASS AXIS in front
of their slots: ``(passes, capacity, kv heads, head_dim)``, and a
sequence's own rows ``(sequences, passes, slots, ...)`` under ``sequences``
(:func:`cache_shapes`):
the model has ``passes x layers`` cache slots a position. Every pass of
every chunk always runs (a later token attends all of them); the gate's
rule only picks the state that is read. With ``post_sublayer_norm`` a
layer norms each sublayer's output too. A model of one pass has no loop,
no gate, no such norm and no pass axis, as before these existed.
"""

from __future__ import annotations

import math
from typing import Dict, Mapping, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from stable_diffusion_webui_distributed_tpu.models.configs import (
    LMConfig, RopeConfig, kind_parts,
)
from stable_diffusion_webui_distributed_tpu.ops import (
    delta_rule, moe, ssm, stream_mixer,
)
from stable_diffusion_webui_distributed_tpu.ops.attention import (
    attend_positions, attend_two_ranges,
)
from stable_diffusion_webui_distributed_tpu.ops.quant import int8_dot
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER,
)

FULL, SLIDING, LINEAR, LATENT, CONV, SSM = (
    "full", "sliding", "linear", "latent", "conv", "ssm")
#: where a layer norms a sublayer (``LMConfig.sublayer_norms``): its input,
#: its output, or (``"both"``) both
PRE, POST = "pre", "post"
#: the cache's buffers of one layer, by the layer's kind
ATTENTION_BUFFERS = ("k", "v")
LINEAR_BUFFERS = ("state", "conv")
LATENT_BUFFERS = ("latent",)
CONV_BUFFERS = ("kept",)
#: a state-space part's recurrence and its convolution's kept inputs, under
#: names of their own: a model may hold both recurrences
SSM_BUFFERS = ("ssm_state", "ssm_conv")
#: the buffers that keep no positions: one size at every length
STATE_BUFFERS = LINEAR_BUFFERS + CONV_BUFFERS + SSM_BUFFERS
#: how a conv layer's mixer was traced: one token, or a chunk of several
CONV_STEP, CONV_CHUNK = "step", "chunk"
#: ops/attention.py records a latent layer's site under its form
LATENT_ABSORBED, LATENT_EXPANDED = "latent_absorbed", "latent_expanded"
LATENT_FORKED = "latent_forked"


#: under a fork (cache/kv.py:fork) an attention layer has two more: what
#: the prefill left, which every sequence reads and none writes
SHARED_BUFFERS = ("k_shared", "v_shared")
#: and a latent layer one
LATENT_SHARED = ("latent_shared",)
#: a buffer that keeps positions, by name: its shared twin under a fork
SHARED_OF = dict(zip(ATTENTION_BUFFERS + LATENT_BUFFERS,
                     SHARED_BUFFERS + LATENT_SHARED))
#: and the cache one entry that is no layer's: the position of the fork
FORKED_AT = "forked_at"
#: a JOINED cache (cache/kv.py:joined_rows) has one more: each sequence's
#: first real own slot, ``(sequences, 1)``. Its sequences continue prompts
#: of their own behind the one shared range (the kept instruction), each
#: prompt's rows right-aligned in front of its sequence's decode slots, so
#: that every sequence writes the same slot at a step; sequence ``b`` then
#: stands ``own_from[b]`` positions short of the step's common position
OWN_FROM = "own_from"


def buffers_of(kind: str, forked: bool = False) -> Tuple[str, ...]:
    """The cache's buffers of one layer of ``kind``: a base kind's own, or
    those of every part of a layer of several, part after part."""
    parts = kind_parts(kind)
    if len(parts) > 1:
        return sum((buffers_of(part, forked) for part in parts), ())
    if kind == LATENT:
        return LATENT_BUFFERS + (LATENT_SHARED if forked else ())
    return {LINEAR: LINEAR_BUFFERS, CONV: CONV_BUFFERS,
            SSM: SSM_BUFFERS}.get(
        kind, ATTENTION_BUFFERS + (SHARED_BUFFERS if forked else ()))


def slots_axis(name: str) -> int | None:
    """The axis of buffer ``name`` that counts positions: keys and values
    are ``(..., slots, kv heads, head_dim)``, latents ``(..., slots,
    width)``; None for a buffer that has no positions (a linear layer's
    state and kept inputs, a conv layer's kept rows, a state-space part's
    state and kept inputs: one size at every length, which a fork copies
    whole)."""
    if name in STATE_BUFFERS:
        return None
    return -2 if name in LATENT_BUFFERS + LATENT_SHARED else -3


def shares_a_step(cfg: LMConfig) -> bool:
    """Whether several sequences can be decoded in one step: every layer
    keeps a row a position (keys and values in buffers or rings, or
    latents) or a recurrent state with a sequence axis (:class:`DeltaMixer`
    and :class:`SSMMixer` under ``sequences``), in every part of it, and a
    token is one stream."""
    return (cfg.base_kinds <= {FULL, SLIDING, LATENT, LINEAR, SSM}
            and cfg.residual_streams == 1)


def site_attrs(cfg: LMConfig) -> dict:
    """Span attributes of a model whose layers depart from input norms,
    rotated attention, a write strength under 1, one mixer a layer, no
    forward multiplier, routed sums added in place and experts that all
    have kernels, as one trace of its stack counts them
    (``serving.expander`` ``sublayer_norms``, ``attention_unrotated``,
    ``write_strength_bound``, ``ssm_mixers``, ``joined_layers``,
    ``multipliers_applied``, ``moe_shortcuts``, ``tied_head``), a head of
    its own, sublayers added unscaled and scores scaled by ``head_dim **
    -0.5``; ``{}`` for one that departs in none."""
    attrs = {}
    if cfg.norm_placement:
        attrs["norms_pre"] = 2 * sum(p != POST for p in cfg.norm_placement)
        attrs["norms_post"] = 2 * sum(p != PRE for p in cfg.norm_placement)
    if cfg.rope_full is None and FULL in cfg.base_kinds:
        attrs["unrotated"] = len(cfg.layers_of(FULL))
    if cfg.linear_write_scale != 1.0 and LINEAR in cfg.base_kinds:
        attrs["write_strength_bound"] = cfg.linear_write_scale
    if SSM in cfg.base_kinds:
        attrs["ssm_layers"] = len(cfg.layers_of(SSM))
    joined = sum(len(kind_parts(kind)) > 1 for kind in cfg.layer_types)
    if joined:
        attrs["joined_layers"] = joined
    if cfg.multipliers_applied:
        attrs["multipliers"] = cfg.multipliers_applied
    if cfg.moe_shortcut:
        attrs["moe_shortcuts"] = len(cfg.expert_layers)
    if cfg.zero_experts:
        attrs["zero_experts"] = cfg.zero_experts
    if cfg.tied_head:
        attrs["tied_head"] = True
    if cfg.residual_multiplier != 1.0:
        attrs["residual_multiplier"] = cfg.residual_multiplier
    if cfg.attention_scale:
        attrs["attention_scale"] = cfg.attention_scale
    return attrs


def latent_form(tokens: int, sequences: bool = False) -> str:
    """The form a latent layer's chunk of ``tokens`` takes: a decode step
    attends the cache as it lies (``sequences``: the rows are one token
    each of as many sequences, over a forked cache's two ranges), a longer
    chunk rebuilds keys and values from it (:class:`LatentAttention`)."""
    if sequences:
        return LATENT_FORKED
    return LATENT_ABSORBED if tokens == 1 else LATENT_EXPANDED


def own_positions(own_pos: jax.Array, forked_at, own_from: jax.Array):
    """``(B, slots)``: the position each sequence of a joined cache holds
    in each of its own slots, from ``own_pos`` ``(slots,)``, the COMMON
    position that fell on the slot (the step's position is common to the
    sequences, and so is the slot a step writes). Sequence ``b`` stands
    ``own_from[b]`` short of it, and its slots in front of ``own_from[b]``
    (the pad in front of its right-aligned prompt) hold nothing:
    negative."""
    common = own_pos[None, :]
    return jnp.where(common - forked_at >= own_from[:, None],
                     common - own_from[:, None], -1)


# -- rotary embeddings -------------------------------------------------------

def rope_frequencies(rope: RopeConfig, head_dim: int) -> np.ndarray:
    """Inverse frequencies of the rotated pairs, ``(rotary_dim / 2,)``.
    With ``factor`` over 0 they are YaRN's: a pair that turns fewer than
    ``beta_slow`` times over the original context is interpolated by
    ``factor``, one that turns more than ``beta_fast`` times is kept, and
    those between blend linearly."""
    dim = int(head_dim * rope.partial_rotary_factor)
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    plain = 1.0 / rope.theta ** exponent
    if not rope.factor:
        return plain

    def pair_of(rotations: float) -> float:
        return (dim * math.log(rope.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    low = max(math.floor(pair_of(rope.beta_fast)), 0)
    high = min(math.ceil(pair_of(rope.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return plain / rope.factor * ramp + plain * (1 - ramp)


def rope_tables(rope: RopeConfig, head_dim: int, positions: jax.Array):
    """(cos, sin), each ``(T, rotary_dim / 2)`` float32."""
    angles = (positions.astype(jnp.float32)[:, None]
              * jnp.asarray(rope_frequencies(rope, head_dim), jnp.float32))
    return (jnp.cos(angles) * rope.attention_factor,
            jnp.sin(angles) * rope.attention_factor)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               interleaved: bool = False) -> jax.Array:
    """Rotates the first ``2 * cos.shape[-1]`` dims of ``(T, H, D)`` ``x``
    (pairs are (i, i + half), the ``rotate_half`` convention, or with
    ``interleaved`` the neighbours (2i, 2i + 1), each pair turned in
    place); the rest pass. Float32 result."""
    half = cos.shape[-1]
    x = x.astype(jnp.float32)
    if interleaved:
        pairs = x[..., :2 * half]
        a, b = pairs[..., 0::2], pairs[..., 1::2]
        c, s = cos[:, None, :], sin[:, None, :]
        turned = jnp.stack([a * c - b * s, b * c + a * s], axis=-1)
        return jnp.concatenate(
            [turned.reshape(pairs.shape), x[..., 2 * half:]], axis=-1)
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], axis=-1)


# -- modules -----------------------------------------------------------------

class Linear(nn.Module):
    """``x @ kernel`` without bias: operands in ``dtype``, float32
    accumulation and result. ``quant`` takes ops/quant.py's dynamic int8
    product instead (the lower-precision control)."""

    features: int
    dtype: jnp.dtype = jnp.float32
    quant: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features))
        if self.quant:
            return int8_dot(x, kernel)
        return jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype),
                       preferred_element_type=jnp.float32)


class RMSNorm(nn.Module):
    """``x_hat * scale`` over the last axis in float32;
    ``zero_centred``: ``x_hat * (1 + weight)``; ``sigmoid_scale`` ``c``
    over 0: ``x_hat * c sigmoid(weight)``, the weight under a gate that is
    ``c / 2`` where it is zero."""

    eps: float = 1e-6
    zero_centred: bool = False
    sigmoid_scale: float = 0.0

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if self.sigmoid_scale:
            scale = self.sigmoid_scale * jax.nn.sigmoid(self.param(
                "weight", nn.initializers.zeros,
                (x.shape[-1],)).astype(jnp.float32))
        elif self.zero_centred:
            scale = 1.0 + self.param(
                "weight", nn.initializers.zeros,
                (x.shape[-1],)).astype(jnp.float32)
        else:
            scale = self.param("scale", nn.initializers.ones,
                               (x.shape[-1],)).astype(jnp.float32)
        x = x.astype(jnp.float32)
        mean = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(mean + self.eps) * scale


def model_norm(cfg: LMConfig, **how) -> RMSNorm:
    """A norm of the model's kind (``LMConfig.zero_centred_norm``,
    ``norm_sigmoid_scale``)."""
    return RMSNorm(cfg.rms_norm_eps, cfg.zero_centred_norm,
                   cfg.norm_sigmoid_scale, **how)


class SwiGLU(nn.Module):
    """``W_d(silu(W_g n) * W_u n)``; ``limit`` over 0:
    ``silu(min(W_g n, limit)) * clip(W_u n, -limit, limit)``;
    ``multipliers`` ``(m_g, m_d)`` off 1: ``m_d W_d(silu(m_g W_g n) * W_u
    n)``."""

    width: int
    dtype: jnp.dtype = jnp.float32
    quant: bool = False
    limit: float = 0.0
    multipliers: Tuple[float, float] = (1.0, 1.0)

    @nn.compact
    def __call__(self, n: jax.Array) -> jax.Array:
        def lin(features, name):
            return Linear(features, self.dtype, self.quant, name=name)

        before, after = self.multipliers
        gate = lin(self.width, "gate_proj")(n)
        if before != 1.0:
            gate = gate * before
        if self.limit:
            gate = jnp.minimum(gate, self.limit)
        gate = jax.nn.silu(gate)
        up = lin(self.width, "up_proj")(n)
        if self.limit:
            up = jnp.clip(up, -self.limit, self.limit)
        out = lin(n.shape[-1], "down_proj")(gate * up)
        return out if after == 1.0 else out * after


class Experts(nn.Module):
    """The stacked kernels of the experts held here."""

    held: int
    width: int

    @nn.compact
    def __call__(self, hidden_size: int):
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=1, out_axis=2,
            batch_axis=0)
        return (self.param("w_gate", init,
                           (self.held, hidden_size, self.width)),
                self.param("w_up", init,
                           (self.held, hidden_size, self.width)),
                self.param("w_down", init,
                           (self.held, self.width, hidden_size)))


class MoE(nn.Module):
    """``(out, beside)`` of an expert layer's MLP sublayer over the normed
    rows ``n``: ``out`` is what the layer adds at its own residual, the
    routed sum and the shared expert; under ``LMConfig.moe_shortcut``
    ``(out, carried, beside)``: ``out`` is the shared expert alone and
    ``carried`` the routed sum, which the NEXT layer adds after its MLP
    sublayer. The routed sum is this chip's held experts' part
    (ops/moe.py) and, with ``zero_experts``, the identity experts' ``(sum
    of the picks' weights on them) * n``. ``beside`` is ``(experts chosen,
    load, tokens with no held expert)`` and, with ``zero_experts``, the
    picks that fell on identity experts. ``router_dtype`` under float32
    makes the router's product in that dtype: a control."""

    config: LMConfig
    dtype: jnp.dtype = jnp.float32
    quant: bool = False
    meshed: bool = False
    router_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, n: jax.Array, valid: jax.Array):
        cfg = self.config
        first, held = cfg.experts
        router = self.param("router", nn.initializers.lecun_normal(),
                            (n.shape[-1], cfg.num_experts))
        # the router sees the normed input in float32: a near-tie decided
        # by rounding the input would send a token to other experts
        if self.router_dtype == jnp.float32:
            logits = jnp.dot(n, router.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
        else:
            logits = jnp.dot(n.astype(self.router_dtype),
                             router.astype(self.router_dtype),
                             preferred_element_type=jnp.float32)
        bias = None
        if cfg.router_bias:
            bias = self.param("e_score_correction_bias",
                              nn.initializers.zeros, (cfg.num_experts,))
        router = dict(renormalise=cfg.norm_topk_prob,
                      scale=cfg.routed_scaling_factor,
                      scoring=cfg.router_scoring, eps=cfg.norm_topk_eps)
        # one predicate picks both kernels: a decode step that takes the
        # pipelined expert kernel takes its routing as one launch too
        path = moe.choose(jax.default_backend(), n.shape[0], self.dtype,
                          n.shape[-1], cfg.moe_intermediate_size,
                          meshed=self.meshed)
        if path != moe.KERNEL:  # XLA's chain, its ops in the order they had
            routing = moe.route(
                logits, cfg.num_experts_per_tok,
                bias=None if bias is None else bias.astype(jnp.float32),
                **router)
        kernels = Experts(held, cfg.moe_intermediate_size,
                          name="experts")(n.shape[-1])
        compute = [w.astype(self.dtype) for w in kernels]
        if path == moe.KERNEL:
            routed, step = moe.kernel_step(
                n.astype(self.dtype), logits, bias, valid, *compute,
                k=cfg.num_experts_per_tok, first=first,
                zero_experts=cfg.zero_experts, limit=cfg.swiglu_limit,
                **router)
            beside = (step.picks, step.load, step.none_held)
            if cfg.zero_experts:    # the identity experts: no kernel
                routed = routed + step.identity_weight * n.astype(
                    jnp.float32)
                beside += (step.identity_picks,)
        else:
            routed, path = moe.routed_experts(
                n.astype(self.dtype), routing, *compute, first=first,
                num_experts=cfg.num_experts, meshed=self.meshed,
                limit=cfg.swiglu_limit)
            load, none_held = moe.load_counts(routing, first, held, valid)
            beside = (routing.experts, load, none_held)
            if cfg.zero_experts:
                routed = routed + moe.identity_part(n, routing,
                                                    cfg.real_experts)
                beside += (moe.identity_picks(routing, cfg.real_experts,
                                              valid),)
        EXPANDER.record_product(path)
        EXPANDER.record_route("kernel" if path == moe.KERNEL else "xla")
        if not cfg.shared_expert_intermediate_size:     # no shared expert
            return routed, beside
        shared = SwiGLU(cfg.shared_expert_intermediate_size, self.dtype,
                        self.quant, cfg.swiglu_limit,
                        name="shared_expert")(n)
        if cfg.shared_expert_gate:
            shared = shared * jax.nn.sigmoid(Linear(
                1, self.dtype, self.quant, name="shared_expert_gate")(n))
        if cfg.moe_shortcut:
            return shared, routed, beside
        return routed + shared, beside


class Attention(nn.Module):
    config: LMConfig
    layer: int
    dtype: jnp.dtype = jnp.float32
    quant: bool = False
    #: ``"full"`` or ``"sliding"`` where the layer holds other mixers too
    #: (``""``: the layer's kind is the attention's)
    kind: str = ""

    @nn.compact
    def __call__(self, n, q_pos, start, end, k_cache, v_cache,
                 k_shared=None, v_shared=None, sequences: bool = False,
                 pass_index=None, forked_at=None, own_from=None):
        """``pass_index``: which pass of a looped stack this is; the
        buffers then carry the pass axis just before their slots' and the
        chunk writes and attends that pass's rows alone. ``sequences``:
        the buffers are a forked cache's, ``k_cache`` and ``v_cache`` each
        sequence's own rows from position ``forked_at`` on; the shared
        ones are returned behind them as they came. ``own_from`` ``(B,)``:
        the cache is a joined one (:data:`OWN_FROM`), ``start`` the step's
        common position and ``q_pos`` each sequence's own, ``start -
        own_from``; an own slot then holds another position a sequence and
        none in front of the sequence's first."""
        cfg = self.config
        kind = self.kind or cfg.layer_types[self.layer]
        heads = cfg.num_heads_per_layer[self.layer]
        kv, dim = cfg.num_kv_heads, cfg.head_dim
        tokens = n.shape[0]
        passes = 1 if pass_index is None else cfg.total_ut_steps
        # what the scores are multiplied by, in all three forms below
        scale = cfg.attention_scale or dim ** -0.5

        def lin(features, name):
            return Linear(features, self.dtype, self.quant, name=name)

        def put(cache, rows, at, axis):
            """``rows`` written from slot ``at`` of the slots' axis, in the
            pass's own rows where the buffer has a pass axis."""
            if pass_index is None:
                return jax.lax.dynamic_update_slice_in_dim(
                    cache, rows, at, axis)
            index = [0] * cache.ndim
            index[axis:axis + 2] = pass_index, at
            return jax.lax.dynamic_update_slice(
                cache, jnp.expand_dims(rows, axis), index)

        def of_pass(cache, axis):
            """The rows this chunk attends: the pass's own. (On the chip
            the slice is the asynchronous READ of those rows into on-chip
            memory, ``slice-done`` in a trace: the stream the products
            need, not a copy made before them. A step of four sequences
            over their own 512-slot copies moved 23.15 GB in 31.6 ms, 89 %
            of the HBM's peak with the rows read once: PERF.md section 6,
            PR 51. A branch a pass over a buffer a (layer, pass) slot
            costs 58 ms a step in the copies XLA makes for the branches:
            PR 49.)"""
            if pass_index is None:
                return cache
            return jax.lax.dynamic_index_in_dim(cache, pass_index, axis,
                                                keepdims=False)

        rope = cfg.rope_full if kind == FULL else cfg.rope_sliding
        if rope is not None:    # (made here: the order of a trace's ops
            cos, sin = rope_tables(rope, dim, q_pos)    # is part of its key)
        store = k_cache.dtype
        whole = cfg.qk_norm and cfg.qk_norm_extent == "projection"
        if whole and cfg.attn_gate == "element":
            raise ValueError("a norm over the whole query projection wants "
                             "no gate among q_proj's columns")

        def heads_of(x, count, name):
            """``x`` cut into its heads; under ``whole`` normed first,
            over all of them at once (one RMS, a weight a column)."""
            if whole:
                x = model_norm(cfg, name=name)(x)
            return x.reshape(tokens, count, -1)

        if cfg.attn_gate == "element":
            # every head's columns are its query, then its gate
            q, gate = jnp.split(lin(2 * heads * dim, "q_proj")(n).reshape(
                tokens, heads, 2 * dim), 2, axis=-1)
        else:
            q = heads_of(lin(heads * dim, "q_proj")(n), heads, "q_norm")
        k = lin(kv * dim, "k_proj")(n)
        if cfg.key_multiplier != 1.0:   # the cache holds the scaled keys
            k = k * cfg.key_multiplier
        k = heads_of(k, kv, "k_norm")
        if cfg.qk_norm and not whole:
            q = model_norm(cfg, name="q_norm")(q)
            k = model_norm(cfg, name="k_norm")(k)
        if rope is None:        # no table is built, nothing is rotated
            EXPANDER.record_unrotated(delta_rule.form(tokens, sequences))
            q, k = q.astype(self.dtype), k.astype(store)
        else:
            q = apply_rope(q, cos, sin, rope.interleaved).astype(self.dtype)
            k = apply_rope(k, cos, sin, rope.interleaved).astype(store)
        v = lin(kv * dim, "v_proj")(n).reshape(tokens, kv, dim).astype(store)
        real = q_pos < end
        if sequences:
            # row b is sequence b's one token at ``start``: it goes to its
            # sequence's own rows, what the prefill left is only read, and
            # both are the keys of one softmax
            shared, own = k_shared.shape[-3], k_cache.shape[-3]
            window = 0 if kind == FULL else shared
            behind = start - forked_at
            k_cache = put(k_cache, k[:, None], behind % own, 1)
            v_cache = put(v_cache, v[:, None], behind % own, 1)
            slots = jnp.arange(shared)
            # the position a shared slot held at the fork, a ring's the
            # newest that fell on it; negative: none (never written, or a
            # padded row of the prompt's chunk)
            shared_pos = (
                forked_at - 1 - (forked_at - 1 - slots) % window if window
                else jnp.where(slots < forked_at, slots, -1))
            # own slot j holds the newest position that fell on it
            own_pos = start - (behind - jnp.arange(own)) % own
            out, path = attend_two_ranges(
                q, of_pass(k_shared, 0), of_pass(v_shared, 0),
                of_pass(k_cache, 1), of_pass(v_cache, 1), q_pos, shared_pos,
                jnp.where(own_pos >= forked_at, own_pos, -1)
                if own_from is None else
                own_positions(own_pos, forked_at, own_from),
                scale=scale, window=window)
            ATTENTION.record(path, 1, shared + own, dim, passes)
        elif kind == FULL:
            # written first: a padded row lands beyond ``end``, where no
            # query looks until a real token has overwritten it
            k_cache = put(k_cache, k, start, 0)
            v_cache = put(v_cache, v, start, 0)
            keys, values = of_pass(k_cache, 0), of_pass(v_cache, 0)
            slots = jnp.arange(keys.shape[0])
            k_pos = jnp.where(slots < end, slots, -1)
            window = 0
        else:
            # the chunk's keys would overwrite slots its own first queries
            # still need, so it attends over ring + chunk and writes after
            window = k_cache.shape[0]
            slots = jnp.arange(window)
            ring_pos = start - 1 - ((start - 1 - slots) % window)
            keys = jnp.concatenate([k_cache, k])
            values = jnp.concatenate([v_cache, v])
            k_pos = jnp.concatenate([ring_pos, jnp.where(real, q_pos, -1)])
            into = jnp.where(real & (q_pos >= end - window),
                             q_pos % window, window)
            k_cache = k_cache.at[into].set(k, mode="drop")
            v_cache = v_cache.at[into].set(v, mode="drop")
        if not sequences:
            out, path = attend_positions(q, keys, values, q_pos, k_pos,
                                         scale=scale, window=window)
            ATTENTION.record(path, tokens, keys.shape[0], dim, passes)
        if cfg.attn_gate == "element":
            out = out.astype(jnp.float32) * jax.nn.sigmoid(gate)
        elif cfg.attn_gate == "head":
            gate = jax.nn.sigmoid(lin(heads, "g_proj")(n))
            out = out.astype(jnp.float32) * gate[:, :, None]
        return (lin(n.shape[-1], "o_proj")(out.reshape(tokens, heads * dim)),
                k_cache, v_cache) + ((k_shared, v_shared) if sequences else ())


class LatentUp(nn.Module):
    """``kv_b_proj``: the kernel that takes a cached latent to every
    head's un-rotated key and value, ``(rank, heads, key + value)``. Handed
    out as :class:`Experts` hands out its kernels, since a decode step
    never applies it to the cache: it folds the key half into the query
    and the value half into the output."""

    heads: int
    width: int

    @nn.compact
    def __call__(self, rank: int) -> jax.Array:
        return self.param(
            "kernel", nn.initializers.lecun_normal(),
            (rank, self.heads * self.width)).reshape(
                rank, self.heads, self.width)


class LatentAttention(nn.Module):
    """The token mixer of a ``"latent"`` layer. Queries go through a normed
    low-rank latent (``q_a_proj``, ``q_a_norm``, ``q_b_proj``) or, with
    ``q_lora_rank`` 0, through one ``q_proj``; a position's keys and values
    all derive from one
    normed latent ``c`` (``kv_lora_rank``) and one rotated key shared by
    every head (``qk_rope_head_dim``), and those two are all the cache
    holds: ``cache`` is ``(capacity, rank + rope)``.

    Three forms over that cache, by the chunk's length and whose rows they
    are (:func:`latent_form`). *Expanded* (a prefill): every cached position's
    per-head key ``[W_k c | k_rope]`` and value ``W_v c`` are rebuilt
    through ``kv_b_proj`` and attended as any keys and values. *Absorbed*
    (one token): ``q W_k^T`` is a query over the latent itself, so the step
    attends the cache as it lies, one KV head of width ``rank + rope``
    whose values are its first ``rank`` columns, and ``W_v`` is applied to
    each head's attended latent instead of to every position. The same
    scores and the same sum, in another order. *Forked* (``sequences``: row
    ``b`` is sequence ``b``'s one token): absorbed over two ranges under one
    softmax, ``shared`` the prefill's latents as they lie ``(capacity,
    width)``, which every sequence attends and none writes, and ``cache``
    each sequence's own rows ``(sequences, slots, width)`` from position
    ``forked_at`` on. One KV head serves every head, so the sequences' heads
    are all query rows of ONE product over the shared latents, read once;
    ``shared`` is returned behind the cache as it came.

    With ``attn_gate`` the heads' outputs pass a sigmoid before ``o_proj``,
    one a value channel (``"element"``) or one a head (``"head"``) from a
    ``g_proj`` of the layer's input: in every form on the per-head values,
    so in the absorbed and forked forms after ``W_v``."""

    config: LMConfig
    layer: int
    dtype: jnp.dtype = jnp.float32
    quant: bool = False

    @nn.compact
    def __call__(self, n, q_pos, start, end, cache, shared=None,
                 sequences: bool = False, forked_at=None, own_from=None):
        cfg = self.config
        heads = cfg.num_heads_per_layer[self.layer]
        rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        rope, v_dim = cfg.qk_rope_head_dim, cfg.v_head_dim
        tokens = n.shape[0]
        f32 = jnp.float32

        def lin(features, name):
            return Linear(features, self.dtype, self.quant, name=name)

        def norm(name):
            return model_norm(cfg, name=name)

        def dot(spec, a, b):
            return jnp.einsum(spec, a.astype(self.dtype), b,
                              preferred_element_type=f32)

        def turned(x):
            return apply_rope(x, cos, sin, cfg.rope_full.interleaved)

        cos, sin = rope_tables(cfg.rope_full, rope, q_pos)
        if cfg.q_lora_rank:
            q = lin(heads * (nope + rope), "q_b_proj")(
                norm("q_a_norm")(lin(cfg.q_lora_rank, "q_a_proj")(n))
            ).reshape(tokens, heads, nope + rope)
        else:       # no query latent: no norm on the query path either
            q = lin(heads * (nope + rope), "q_proj")(n).reshape(
                tokens, heads, nope + rope)
        if cfg.latent_q_scale != 1.0:   # both parts, before the rotation
            q = q * cfg.latent_q_scale
        q_nope, q_rope = q[..., :nope], turned(q[..., nope:])
        c, k_rope = jnp.split(lin(rank + rope, "kv_a_proj_with_mqa")(n),
                              [rank], axis=-1)
        c = norm("kv_a_norm")(c)
        if cfg.latent_kv_scale != 1.0:
            # the cache holds the latent scaled: what ``kv_b_proj`` takes
            # in the expanded form and both folds of the other two
            c = c * cfg.latent_kv_scale
        row = jnp.concatenate([c, turned(k_rope[:, None, :])[:, 0]],
                              axis=-1)
        form = latent_form(tokens, sequences)
        if cfg.latent_q_scale != 1.0 or cfg.latent_kv_scale != 1.0:
            EXPANDER.record_latent_scaled(form)
        if sequences:
            # row b is sequence b's one token at ``start``: it goes to its
            # sequence's own rows, what the prefill left is only read, and
            # both are the keys of one softmax (as ``Attention`` does)
            own = cache.shape[1]
            behind = start - forked_at
            cache = jax.lax.dynamic_update_slice_in_dim(
                cache, row[:, None].astype(cache.dtype), behind % own, 1)
            slots = jnp.arange(shared.shape[0])
            k_pos = jnp.where(slots < forked_at, slots, -1)
            own_pos = start - (behind - jnp.arange(own)) % own
            if own_from is None:
                own_pos = jnp.where(own_pos >= forked_at, own_pos, -1)
            else:       # a joined cache: a position a sequence a slot
                own_pos = own_positions(own_pos, forked_at, own_from)
        else:
            # written first: a padded row lands beyond ``end``
            cache = jax.lax.dynamic_update_slice_in_dim(
                cache, row.astype(cache.dtype), start, 0)
            slots = jnp.arange(cache.shape[0])
            k_pos = jnp.where(slots < end, slots, -1)
        up = LatentUp(heads, nope + v_dim, name="kv_b_proj")(rank).astype(
            self.dtype)
        w_k, w_v = up[..., :nope], up[..., nope:]
        if form != LATENT_EXPANDED:
            q = jnp.concatenate([dot("thd,rhd->thr", q_nope, w_k), q_rope],
                                axis=-1).astype(self.dtype)
        if form == LATENT_ABSORBED:
            keys = cache[:, None, :]
            values = keys[..., :rank]
        elif form == LATENT_EXPANDED:
            q = jnp.concatenate([q_nope, q_rope], axis=-1).astype(self.dtype)
            held = dot("sr,rhd->shd", cache[:, :rank], up)
            keys = jnp.concatenate(
                [held[..., :nope],
                 jnp.broadcast_to(cache[:, None, rank:].astype(f32),
                                  held.shape[:2] + (rope,))],
                axis=-1).astype(cache.dtype)
            values = held[..., nope:].astype(cache.dtype)
        if sequences:
            # keys the rows as they lie, values their first ``rank`` columns
            out, _ = attend_two_ranges(
                q, shared[:, None, :], shared[:, None, :rank],
                cache[:, :, None, :], cache[:, :, None, :rank], q_pos,
                k_pos, own_pos, scale=cfg.latent_softmax_scale)
            ATTENTION.record(form, tokens, shared.shape[0], cache.shape[-1],
                             own=own)
        else:
            out, _ = attend_positions(q, keys, values, q_pos, k_pos,
                                      scale=cfg.latent_softmax_scale)
            ATTENTION.record(form, tokens, cache.shape[0], cache.shape[1])
        if form != LATENT_EXPANDED:
            out = dot("thr,rhd->thd", out, w_v)
        if cfg.attn_gate == "element":
            out = out.astype(f32) * jax.nn.sigmoid(
                lin(heads * v_dim, "g_proj")(n)).reshape(
                    tokens, heads, v_dim)
        elif cfg.attn_gate == "head":
            out = out.astype(f32) * jax.nn.sigmoid(
                lin(heads, "g_proj")(n))[:, :, None]
        return (lin(n.shape[-1], "o_proj")(
            out.reshape(tokens, heads * v_dim)), cache) \
            + ((shared,) if sequences else ())


class Mixed(NamedTuple):
    """What a :class:`StreamMixer` gives before its sublayer. ``tile`` is
    the kernel's form of the three maps (ops/stream_mixer.py:mixer), which
    its write-back takes; None in the XLA form."""
    read: jax.Array
    h_pre: jax.Array
    h_post: jax.Array
    h_res: jax.Array
    tile: jax.Array | None = None


class StreamMixer(nn.Module):
    """One sublayer's hyper-connection, per token, from the token's
    ``(streams, hidden)`` state ``X``: a :class:`Mixed` of the sublayer's
    input read out of the streams and the three maps. ``H_pre`` ``(T, n)``
    reads ``read = H_pre X`` ``(T, hidden)``, ``H_post`` ``(T, n)`` writes
    the sublayer's output back into each stream (:func:`written`), and
    ``H_res`` ``(T, n, n)`` mixes the streams among themselves. All three
    are projections of the RMS-normed flattened state; ``H_res`` is the
    exponential of its projection made doubly stochastic by Sinkhorn's
    alternating column and row normalisations, so that mixing neither
    grows nor shrinks what the streams carry. Float32 throughout, the
    projection at the highest precision like the router's:
    ``sinkhorn_dtype`` is the lower-precision control.

    Two forms of the one computation, chosen by what the call shows
    (ops/stream_mixer.py:choose), as an expert layer's product is. A decode
    step on a TPU takes two Pallas kernels a mixer, one here and one in
    :func:`written`: in XLA a mixer at one token is about a hundred
    launches with nothing streaming, 25.3 us, forty times a token (PERF.md
    section 6, PR 35 and PR 36), and none of XLA's own forms is both few
    launches and cheap. Everything else (a prefill's rows, a CPU, a
    program a mesh partitions, the lower-precision control) keeps the XLA
    form, whose iterations are a ``fori_loop`` of static trip count:
    unrolled they tie on the chip (an iteration is four small fusions
    either way) and cost the share's executables 95 s more to compile, and
    the matrix as sixteen arrays of its entries fuses into one launch and
    a decode span of 5.1 s for 1.6 (PR 35)."""

    config: LMConfig
    sinkhorn_dtype: jnp.dtype = jnp.float32
    meshed: bool = False

    @nn.compact
    def __call__(self, streams: jax.Array):
        cfg = self.config
        n = cfg.residual_streams
        tokens = streams.shape[0]
        f32 = jnp.float32
        # projections of deviation 1/2: twenty iterations then bring every
        # token's matrix to sums within 1e-3 of one
        phi = self.param(
            "phi", nn.initializers.variance_scaling(0.25, "fan_in", "normal"),
            (n * cfg.hidden_size, n * n + 2 * n))
        alpha = self.param("alpha", nn.initializers.ones, (3,))
        b_pre = self.param("b_pre", nn.initializers.zeros, (n,))
        b_post = self.param("b_post", nn.initializers.zeros, (n,))
        b_res = self.param("b_res", nn.initializers.normal(0.5), (n, n))
        norm = RMSNorm(cfg.rms_norm_eps, name="norm")
        # an init runs the XLA form: the norm makes its weight when called
        path = stream_mixer.LOOP if self.is_initializing() else \
            stream_mixer.choose(
                jax.default_backend(), tokens, n, cfg.hidden_size,
                meshed=self.meshed, sinkhorn_dtype=self.sinkhorn_dtype)
        EXPANDER.record_mixer(path)
        if path == stream_mixer.KERNEL:
            # a decode scan hands in what mixer_operands made outside it
            packed = self.get_variable("mixers", "packed") \
                if self.has_variable("mixers", "packed") \
                else stream_mixer.pack(phi, alpha, b_pre, b_post, b_res)
            read, tile = stream_mixer.mixer(
                streams, norm.get_variable("params", "scale"), packed,
                eps=cfg.rms_norm_eps, hc_eps=cfg.hc_eps,
                clamp=cfg.hc_res_clamp, iters=cfg.sinkhorn_iters)
            return Mixed(read, *stream_mixer.maps_of(tile, n), tile)
        alpha, b_pre, b_post, b_res = (
            p.astype(f32) for p in (alpha, b_pre, b_post, b_res))
        flat = norm(streams.reshape(tokens, n * cfg.hidden_size))
        pre, post, res = jnp.split(
            jnp.dot(flat, phi.astype(f32),
                    precision=jax.lax.Precision.HIGHEST),
            [n, 2 * n], axis=-1)
        h_pre = jax.nn.sigmoid(alpha[0] * pre + b_pre)
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * post + b_post)
        m = jnp.exp(jnp.clip(alpha[2] * res.reshape(tokens, n, n) + b_res,
                             *cfg.hc_res_clamp)).astype(self.sinkhorn_dtype)

        def columns_then_rows(_, m):
            m = m / (jnp.sum(m, axis=-2, keepdims=True) + cfg.hc_eps)
            return m / (jnp.sum(m, axis=-1, keepdims=True) + cfg.hc_eps)

        h_res = jax.lax.fori_loop(0, cfg.sinkhorn_iters, columns_then_rows, m)
        # element-wise in float32: a product of (n,) with (n, hidden) has
        # nothing for the MXU, whose default precision would round the
        # streams to bfloat16
        read = sum(h_pre[:, j, None] * streams[:, j].astype(f32)
                   for j in range(n))
        return Mixed(read, h_pre, h_post, h_res.astype(f32))


def written(streams: jax.Array, mixed: Mixed, out: jax.Array) -> jax.Array:
    """``H_res X + H_post (outer) out``: the streams after a sublayer whose
    output is ``out``, in the dtype they came in; in the form ``mixed``
    was made in."""
    if mixed.tile is not None:
        return stream_mixer.write_back(streams, mixed.tile, out)
    # element-wise in float32, as the mixer's read is
    rows = [streams[:, j].astype(jnp.float32)
            for j in range(streams.shape[1])]
    return jnp.stack(
        [sum(mixed.h_res[:, i, j, None] * r for j, r in enumerate(rows))
         + mixed.h_post[:, i, None] * out for i in range(len(rows))],
        axis=1).astype(streams.dtype)


def mixer_operands(params) -> dict:
    """The ``mixers`` collection of a model's ``params``: by each
    :class:`StreamMixer`'s path, its parameters as the kernel reads them
    (ops/stream_mixer.py:pack). Made outside a decode scan they are made
    once a chunk: inside its body XLA hoists only the part that does not
    grow (``phi``'s transpose, not the gates' tiles). Empty for a model
    with one stream."""
    if "phi" in params:
        return {"packed": stream_mixer.pack(*(params[name] for name in (
            "phi", "alpha", "b_pre", "b_post", "b_res")))}
    found = {name: mixer_operands(sub) for name, sub in params.items()
             if isinstance(sub, Mapping)}
    return {name: sub for name, sub in found.items() if sub}


def causal_conv(kernel: jax.Array, kept: jax.Array, x: jax.Array, length,
                bias: jax.Array | None = None):
    """(convolved ``(T, channels)``, the rows to keep): the causal
    depth-wise convolution of a chunk ``x`` ``(T, channels)`` whose first
    ``length`` rows are real, by ``kernel`` ``(taps, channels)``, both
    float32 (a lower-precision control hands in both in its dtype).
    Tap ``j`` of row ``t`` reads input ``t - (taps - 1) + j``; the inputs
    before the chunk are ``kept``, the ``taps - 1`` last real inputs, and
    what is returned to keep are the last real ones after this chunk, in
    ``kept``'s dtype: a padded row is never kept, and a chunk shorter than
    ``taps - 1`` keeps older rows on. ``bias`` ``(channels,)`` is added to
    every row's sum. The taps' count and what follows the sum (an
    activation, a gate) are the caller's."""
    taps, tokens = kernel.shape[0], x.shape[0]
    inputs = jnp.concatenate([kept.astype(kernel.dtype), x])
    out = sum(kernel[j] * inputs[j:j + tokens] for j in range(taps))
    if bias is not None:
        out = out + bias
    return out, jax.lax.dynamic_slice_in_dim(
        inputs, length, taps - 1, 0).astype(kept.dtype)


def causal_conv_rows(kernel: jax.Array, kept: jax.Array, x: jax.Array,
                     real: jax.Array, bias: jax.Array | None = None):
    """:func:`causal_conv` of ONE row for each of ``B`` sequences: ``x``
    ``(B, channels)`` is sequence ``b``'s one input behind its own ``kept``
    ``(B, taps - 1, channels)``. A sequence whose row does not count
    (``real`` ``(B,)``) keeps the rows it had."""
    inputs = jnp.concatenate(
        [kept.astype(kernel.dtype), x[:, None, :]], axis=1)
    out = sum(kernel[j] * inputs[:, j] for j in range(kernel.shape[0]))
    if bias is not None:
        out = out + bias
    return out, jnp.where(real[:, None, None], inputs[:, 1:],
                          kept.astype(kernel.dtype)).astype(kept.dtype)


def _decay_init(key, shape, dtype=jnp.float32):
    """``A_log``: the log of a decay rate drawn uniformly from (0, 16)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


class DeltaMixer(nn.Module):
    """The token mixer of a ``"linear"`` layer: queries, keys and values
    through a causal depth-wise convolution and SiLU, queries and keys
    L2-normalised per head, then the gated delta rule over the layer's
    recurrent state at a write strength ``linear_write_scale sigmoid(b)``;
    the read-out is RMS-normed per head, gated by
    ``silu(z)`` (or, ``linear_sigmoid_gate_scale`` ``c`` over 0, normed
    ``x_hat * (1 + weight)`` and gated by ``c sigmoid(z)``) and projected.
    ``state`` is ``(value heads, key width,
    value width)`` float32; ``conv`` holds the convolution's last
    ``taps - 1`` real inputs. ``sequences``: row ``b`` is sequence ``b``'s
    one token, ``state`` and ``conv`` carry the sequences in front, each
    row steps its own, and a row that is not ``real`` leaves both, by the
    Pallas kernel or element-wise as ``delta_rule.step_form`` says of the
    state (``meshed``: a mesh partitions the program, so element-wise)."""

    config: LMConfig
    dtype: jnp.dtype = jnp.float32
    quant: bool = False
    meshed: bool = False

    @nn.compact
    def __call__(self, n, real, length, state, conv,
                 sequences: bool = False):
        cfg = self.config
        EXPANDER.record_delta(delta_rule.form(n.shape[0], sequences),
                              cfg.linear_write_scale)
        k_heads, v_heads = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        k_dim, v_dim = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        channels, taps = cfg.linear_conv_channels, cfg.linear_conv_kernel
        tokens = n.shape[0]
        f32 = jnp.float32

        def lin(features, name):
            return Linear(features, self.dtype, self.quant, name=name)

        # columns: [q | k | v | z] and [b | a]
        qkv, z = jnp.split(lin(channels + v_heads * v_dim, "qkvz_proj")(n),
                           [channels], axis=-1)
        b, a = jnp.split(lin(2 * v_heads, "ba_proj")(n), 2, axis=-1)
        kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (taps, channels)).astype(f32)
        a_log = self.param("A_log", _decay_init, (v_heads,)).astype(f32)
        dt_bias = self.param("dt_bias", nn.initializers.ones,
                             (v_heads,)).astype(f32)
        if sequences:
            qkv, conv = causal_conv_rows(kernel, conv, qkv, real)
        else:
            qkv, conv = causal_conv(kernel, conv, qkv, length)
        qkv = jax.nn.silu(qkv)
        q, k, v = jnp.split(
            qkv, [k_heads * k_dim, 2 * k_heads * k_dim], axis=-1)

        def unit(x):
            x = x.reshape(tokens, k_heads, k_dim)
            x = x * jax.lax.rsqrt(
                jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
            return jnp.repeat(x, v_heads // k_heads, axis=1)

        # a padded row neither decays the state nor writes to it
        g = jnp.where(real[:, None],
                      -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias), 0.0)
        # ``c sigmoid(b)``: at ``c`` 2 ``I - beta k k^T`` reflects too
        scale = cfg.linear_write_scale
        beta = jnp.where(
            real[:, None], jax.nn.sigmoid(b) if scale == 1.0
            else scale * jax.nn.sigmoid(b), 0.0)
        # computed in float32 whatever the buffer holds
        step = delta_rule.gated_delta_rule
        if sequences:
            path = delta_rule.step_form(jax.default_backend(), state.dtype,
                                        state.shape, meshed=self.meshed)
            EXPANDER.record_delta_step(path)
            step = delta_rule.FORKED_STEPS[path]
        out, after = step(
            state.astype(f32), unit(q) * k_dim ** -0.5, unit(k),
            v.reshape(tokens, v_heads, v_dim), g, beta)
        state = after.astype(state.dtype)
        # the read-out's norm first, then its gate (the order of a
        # trace's ops is part of its executable's key)
        gate_scale = cfg.linear_sigmoid_gate_scale
        out = RMSNorm(cfg.rms_norm_eps, bool(gate_scale), name="norm")(out)
        z = z.reshape(tokens, v_heads, v_dim)
        out = out * (gate_scale * jax.nn.sigmoid(z) if gate_scale
                     else jax.nn.silu(z))
        return (lin(n.shape[-1], "out_proj")(
            out.reshape(tokens, v_heads * v_dim)), state, conv)


class ShortConv(nn.Module):
    """The token mixer of a ``"conv"`` layer: ``[B | C | x] = n W_in``
    (three times the hidden width), ``u = B * x``, a causal depth-wise
    convolution of ``conv_taps`` taps over ``u`` with no activation after
    it, and ``(C * c) W_out``. ``kept`` holds the last ``taps - 1`` real
    rows of ``u`` in float32, the layer's whole state: nothing of it grows
    with the sequence. The gates and the taps are element-wise in float32,
    as the linear kind's convolution is: ``conv_dtype`` is the
    lower-precision control."""

    config: LMConfig
    dtype: jnp.dtype = jnp.float32
    quant: bool = False
    conv_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, n, length, kept):
        cfg = self.config
        EXPANDER.record_conv(CONV_STEP if n.shape[0] == 1 else CONV_CHUNK)
        b, c, x = jnp.split(
            Linear(3 * cfg.hidden_size, self.dtype, self.quant,
                   name="in_proj")(n).astype(self.conv_dtype), 3, axis=-1)
        kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (cfg.conv_taps, cfg.hidden_size))
        mixed, kept = causal_conv(kernel.astype(self.conv_dtype), kept,
                                  b * x, length)
        return Linear(n.shape[-1], self.dtype, self.quant,
                      name="out_proj")(c * mixed), kept


class GroupedRMSNorm(nn.Module):
    """``x_hat * scale`` with the RMS taken over each of ``groups`` equal
    runs of the last axis, one weight a channel, float32."""

    groups: int
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones,
                           (x.shape[-1],)).astype(jnp.float32)
        runs = x.astype(jnp.float32).reshape(
            x.shape[:-1] + (self.groups, -1))
        mean = jnp.mean(jnp.square(runs), axis=-1, keepdims=True)
        return (runs * jax.lax.rsqrt(mean + self.eps)).reshape(x.shape) \
            * scale


class SSMMixer(nn.Module):
    """The token mixer of an ``"ssm"`` part: a selective state-space
    recurrence (ops/ssm.py). ``in_proj`` gives ``[z | x | B | C | dt]``
    (the gate and the input ``ssm_num_heads * ssm_head_dim`` wide each,
    ``B`` and ``C`` ``ssm_state_size`` a group, ``dt`` one a head), each
    range scaled by its own of ``ssm_multipliers``; ``[x | B | C]`` pass a
    causal depth-wise convolution of ``ssm_conv_kernel`` taps (with a bias
    under ``ssm_conv_bias``) and SiLU; per head ``dt = softplus(dt +
    dt_bias)``, the decay ``exp(-exp(A_log) dt)``, ``S <- decay S + dt x
    B^T``, ``y = S C + D x`` with ``B`` and ``C`` those of the head's
    group; the read-out is ``y * silu(z)``, THEN an RMS norm over each of
    the groups' channels (``ssm_norm_before_gate``: the norm, then the
    gate), through ``out_proj``. ``state`` is ``(heads,
    head width, state width)`` float32; ``conv`` holds the convolution's
    last ``taps - 1`` real inputs. ``sequences``: row ``b`` is sequence
    ``b``'s one token, ``state`` and ``conv`` carry the sequences in
    front, each row steps its own, and a row that is not ``real`` leaves
    both (its ``dt`` is 0: no decay, no write)."""

    config: LMConfig
    dtype: jnp.dtype = jnp.float32
    quant: bool = False

    @nn.compact
    def __call__(self, n, real, length, state, conv,
                 sequences: bool = False):
        cfg = self.config
        EXPANDER.record_ssm(ssm.form(n.shape[0], sequences))
        heads, dim = cfg.ssm_num_heads, cfg.ssm_head_dim
        groups, width = cfg.ssm_num_groups, cfg.ssm_state_size
        inner, channels = cfg.ssm_inner, cfg.ssm_conv_channels
        tokens = n.shape[0]
        f32 = jnp.float32

        def lin(features, name):
            return Linear(features, self.dtype, self.quant, name=name)

        # columns: [z | x | B | C | dt]
        ranges = (inner, inner, groups * width, groups * width, heads)
        p = lin(inner + channels + heads, "in_proj")(n)
        if any(m != 1.0 for m in cfg.ssm_multipliers):
            p = p * jnp.asarray(np.repeat(cfg.ssm_multipliers, ranges), f32)
        z, xbc, dt = jnp.split(p, [inner, inner + channels], axis=-1)
        kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (cfg.ssm_conv_kernel, channels)).astype(f32)
        bias = self.param("conv_bias", nn.initializers.zeros,
                          (channels,)).astype(f32) \
            if cfg.ssm_conv_bias else None
        a_log = self.param("A_log", _decay_init, (heads,)).astype(f32)
        skip = self.param("D", nn.initializers.ones, (heads,)).astype(f32)
        dt_bias = self.param("dt_bias", nn.initializers.ones,
                             (heads,)).astype(f32)
        if sequences:
            xbc, conv = causal_conv_rows(kernel, conv, xbc, real, bias)
        else:
            xbc, conv = causal_conv(kernel, conv, xbc, length, bias)
        xbc = jax.nn.silu(xbc)
        x, b, c = jnp.split(xbc, [inner, inner + groups * width], axis=-1)
        x = x.reshape(tokens, heads, dim)
        b, c = (m.reshape(tokens, groups, width) for m in (b, c))
        # a row that does not count neither decays the state nor writes
        dt = jnp.where(real[:, None], jax.nn.softplus(dt + dt_bias), 0.0)
        log_decay = -jnp.exp(a_log) * dt
        # computed in float32 whatever the buffer holds
        if sequences:
            y, after = ssm.step_each(state.astype(f32), x, b, c, dt,
                                     log_decay)
        else:
            y, after = ssm.mix(state.astype(f32), x, b, c, dt, log_decay,
                               cfg.ssm_chunk)
        state = after.astype(state.dtype)
        y = y + skip[:, None] * x
        # the gate first, then the norm over each group's channels
        y, gate = y.reshape(tokens, inner), jax.nn.silu(z)
        norm = GroupedRMSNorm(groups, cfg.rms_norm_eps, name="norm")
        out = norm(y) * gate if cfg.ssm_norm_before_gate else norm(y * gate)
        return lin(n.shape[-1], "out_proj")(out), state, conv


class DecoderLayer(nn.Module):
    config: LMConfig
    layer: int
    dtype: jnp.dtype = jnp.float32
    quant: bool = False
    meshed: bool = False
    #: lower-precision controls of a model with several residual streams
    stream_dtype: jnp.dtype = jnp.float32
    sinkhorn_dtype: jnp.dtype = jnp.float32
    #: and of one with conv layers: their gates' and taps' products
    conv_dtype: jnp.dtype = jnp.float32
    #: and of one with expert layers: the router's product
    router_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, q_pos, start, end, buffers,
                 sequences: bool = False, real=None, pass_index=None,
                 forked_at=None, carried=None, own_from=None):
        """``(x, buffers, routed)``. ``buffers`` are the layer's own of
        the cache (:func:`buffers_of`
        its kind), returned as the chunk leaves them. ``x`` is ``(T,
        hidden)``, or ``(T, streams, hidden)`` with several streams.
        ``sequences``: the rows are one token each of as many sequences
        (:func:`shares_a_step`), the buffers a forked cache's, forked at
        position ``forked_at``, and ``real`` says which rows count;
        ``own_from``: a joined one's (:data:`OWN_FROM`).
        ``pass_index``: the pass of a looped stack, whose rows of the
        buffers the layer then takes. ``carried``: the routed sum the
        expert layer before this one left for it
        (``LMConfig.moe_shortcut``), added to the residual after this
        layer's MLP sublayer; under ``moe_shortcut`` what this layer
        leaves for the next is returned last (None: nothing crosses)."""
        cfg = self.config
        kind = cfg.layer_types[self.layer]

        def counted():
            """The rows that count: a chunk's before ``end``, or those the
            caller says (made where they are used, as they always were:
            the order of a trace's ops is part of its executable's key)."""
            return q_pos < end if real is None else real

        def one_mixer(kind, n, buffers):
            """(mixed, its buffers as the chunk leaves them) of the
            layer's token mixer of base kind ``kind``, its input and its
            output under the kind's multipliers."""
            before, behind = cfg.mixer_multiplier(kind)
            if before != 1.0:
                n = n * before
            if kind == LINEAR:
                mixed, *after = DeltaMixer(
                    cfg, self.dtype, self.quant, self.meshed,
                    name="delta")(
                        n, counted(), end - start, *buffers,
                        sequences=sequences)
            elif kind == SSM:
                mixed, *after = SSMMixer(
                    cfg, self.dtype, self.quant, name="ssm")(
                        n, counted(), end - start, *buffers,
                        sequences=sequences)
            elif kind == CONV:
                mixed, *after = ShortConv(
                    cfg, self.dtype, self.quant, self.conv_dtype,
                    name="short_conv")(
                        n, end - start, *buffers)
            elif kind == LATENT:
                mixed, *after = LatentAttention(
                    cfg, self.layer, self.dtype, self.quant, name="attn")(
                        n, q_pos, start, end, *buffers,
                        sequences=sequences, forked_at=forked_at,
                        own_from=own_from)
            else:
                mixed, *after = Attention(
                    cfg, self.layer, self.dtype, self.quant, kind,
                    name="attn")(
                        n, q_pos, start, end, *buffers,
                        sequences=sequences, pass_index=pass_index,
                        forked_at=forked_at, own_from=own_from)
            if behind != 1.0:
                mixed = mixed * behind
            return mixed, tuple(after)

        def token_mixer(n):
            """(mixed, the layer's buffers as the chunk leaves them): the
            layer's one mixer, or the sum of its several, which all read
            the one normed input, each over its own buffers."""
            parts = kind_parts(kind)
            if len(parts) == 1:
                return one_mixer(kind, n, buffers)
            EXPANDER.record_joined(form)
            mixed, after, at = None, (), 0
            for part in parts:
                own = len(buffers_of(part, sequences))
                out, kept = one_mixer(part, n, buffers[at:at + own])
                mixed = out if mixed is None else mixed + out
                after, at = after + kept, at + own
            return mixed, after

        leaves = []     # the routed sum a shortcut carries out of the layer

        def mlp(n):
            """(out, what an expert layer routed; None for a dense one)."""
            if self.layer in cfg.dense_layers:
                return SwiGLU(cfg.intermediate_size, self.dtype, self.quant,
                              cfg.swiglu_limit, cfg.mlp_multipliers,
                              name="mlp")(n), None
            out, *crosses, routed = MoE(
                cfg, self.dtype, self.quant, self.meshed, self.router_dtype,
                name="mlp")(n, counted())
            if crosses:
                EXPANDER.record_shortcut(form)
                leaves.extend(crosses)
            return out, routed

        streams = cfg.residual_streams
        placement = cfg.sublayer_norms[self.layer]
        form = delta_rule.form(x.shape[0], sequences)
        beside = []     # what each sublayer returns beside its output
        for sublayer, norm_name, hc in (
                (token_mixer, "input_norm", "attn_hc"),
                (mlp, "post_attention_norm", "mlp_hc")):

            def norm(x):
                """The stream as the sublayer reads it."""
                if placement == POST:
                    return x
                EXPANDER.record_norm(PRE, form)
                return model_norm(cfg, name=norm_name)(x)

            def normed_after(out):
                """The sublayer's output as the residual takes it."""
                if placement == PRE:
                    return out
                EXPANDER.record_norm(POST, form)
                return model_norm(cfg, name=norm_name + "_2")(out)

            if streams == 1:
                out, more = sublayer(norm(x))
                out = normed_after(out)
                if cfg.residual_multiplier != 1.0:
                    # a token mixer's, a dense MLP's, an expert layer's
                    # routed sum and shared expert: all under the one
                    out = out * cfg.residual_multiplier
                x = x + out
                if carried is not None and sublayer is mlp:
                    # the sum the layer before routed lands here, one
                    # token mixer and one MLP after its router
                    x = x + carried
                beside.append(more)
                continue
            mixed = StreamMixer(
                cfg, self.sinkhorn_dtype, self.meshed, name=hc)(x)
            out, more = sublayer(norm(mixed.read))
            out = normed_after(out)
            beside.append(more)
            with jax.named_scope(hc):
                x = written(x, mixed, out)
        buffers, routed = beside
        if not cfg.moe_shortcut:
            return x, buffers, routed
        return x, buffers, routed, (leaves[0] if leaves else None)


class DecoderLM(nn.Module):
    """``(logits, cache, routed)`` for one chunk. ``tokens`` ``(T,)`` are
    vocabulary ids; ``start`` is the first one's position and ``length``
    how many are real. ``logits`` are float32 over
    the held slice, for every row of the chunk or (``all_logits`` False)
    for the last real one alone. With ``sequences`` the ``(B,)`` tokens are
    one each of ``B`` sequences, all at position ``start``, of which the
    first ``length`` are real (the others pad ``B``); ``cache`` is a forked
    one (cache/kv.py:fork) and the logits are every sequence's. ``routed``
    has, stacked over the expert layers, the experts every token chose
    ``(layers, T, k)``, the tokens sent to each held expert ``(layers,
    held)``, the tokens none of whose experts is held ``(layers,)`` and,
    where the router has zero-compute experts, the picks of rows that
    count that fell on them ``(layers,)``."""

    config: LMConfig
    dtype: jnp.dtype = jnp.float32
    quant_linears: bool = False
    #: a mesh partitions the program this module is traced into: its expert
    #: layers keep the products ``pjit`` can split (ops/moe.py:choose)
    meshed: bool = False
    #: what the residual streams are kept in between sublayers, and what
    #: Sinkhorn's iterations run in: float32; lower is a control
    stream_dtype: jnp.dtype = jnp.float32
    sinkhorn_dtype: jnp.dtype = jnp.float32
    #: what a conv layer's gates and taps multiply in: float32; lower is a
    #: control
    conv_dtype: jnp.dtype = jnp.float32
    #: what an expert layer's router multiplies in: float32 at the highest
    #: precision; lower is a control
    router_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens, start, length, cache: Dict[str, jax.Array],
                 all_logits: bool = True, sequences: bool = False):
        cfg = self.config
        EXPANDER.record_multipliers(cfg.multipliers_applied)
        if sequences:
            if not shares_a_step(cfg):
                raise ValueError("a step of several sequences wants full, "
                                 "sliding, latent, linear or ssm mixers "
                                 "and one stream")
            cache = dict(cache)
            (lead,) = cache.pop(OWN_FROM, (None,))
            # a joined cache: every sequence its own position
            own_from = None if lead is None else lead[:, 0]
            q_pos = jnp.full(tokens.shape, start, jnp.int32) \
                if lead is None else start - own_from
            end, all_logits = start + 1, True
            real = jnp.arange(tokens.shape[0]) < length
            # a fork does not know where it stands: its first step does
            (stamp,) = cache.pop(FORKED_AT)
            forked_at = jnp.where(stamp[0, 0] < 0, start, stamp[0, 0])
        else:
            q_pos = start + jnp.arange(tokens.shape[0], dtype=jnp.int32)
            end = start + length
            real = None     # a layer's own: the rows before ``end``
            forked_at = own_from = None
        # the table is sharded over the vocabulary: an id another chip
        # holds gets nothing here (their parts are summed in a deployment)
        first, count = cfg.vocab
        local = tokens - first
        here = (local >= 0) & (local < count)
        table = nn.Embed(count, cfg.hidden_size, name="embed_tokens")
        x = table(jnp.clip(local, 0, count - 1)).astype(jnp.float32)
        x = x * here[:, None]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if cfg.residual_streams > 1:    # every stream starts as the token
            x = jnp.broadcast_to(
                x[:, None, :], (x.shape[0], cfg.residual_streams,
                                x.shape[1])).astype(self.stream_dtype)

        def layer_module(layer, **how):
            return DecoderLayer(
                cfg, layer, self.dtype, self.quant_linears, self.meshed,
                self.stream_dtype, self.sinkhorn_dtype, self.conv_dtype,
                self.router_dtype, **how)

        def named_layer(layer, x, buffers, pass_index, carried=None):
            """Layer ``layer`` as this module's own submodule."""
            return layer_module(layer, name=f"layers_{layer}")(
                x, q_pos, start, end, buffers, sequences=sequences,
                real=real, pass_index=pass_index, forked_at=forked_at,
                carried=carried, own_from=own_from)

        def stack(apply_layer, x, cache, pass_index=None):
            """(x, the cache, what the expert layers routed) after every
            layer once. A buffer list has one entry for each layer that has
            the buffer, in layer order. An expert layer's routed sum that
            a shortcut carries (``LMConfig.moe_shortcut``) goes from its
            layer to the next: the sum is a row's, so under ``sequences``
            it forks with the rows."""
            written = {name: [] for name in cache}
            routed = []
            carried = ()    # under a shortcut: what the layer before left
            for layer, kind in enumerate(cfg.layer_types):
                names = buffers_of(kind, sequences)
                x, buffers, r, *carried = apply_layer(
                    layer, x,
                    tuple(cache[name][len(written[name])] for name in names),
                    pass_index, *carried)
                for name, buffer in zip(names, buffers):
                    written[name].append(buffer)
                if r is not None:
                    routed.append(r)
            return x, written, routed

        def final_norm(**how):
            return model_norm(cfg, **how)

        if cfg.total_ut_steps == 1:
            x, cache, routed = stack(named_layer, x, cache)
            if cfg.residual_streams > 1:    # the streams' sum is the output
                x = jnp.sum(x.astype(jnp.float32), axis=1)
            if not all_logits:
                x = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, 0)
            n = final_norm(name="norm")(x)
        elif self.is_initializing():
            # one pass through the named submodules makes every parameter
            x, cache, routed = stack(named_layer, x, cache, jnp.int32(0))
            h = jnp.stack([final_norm(name="norm")(x)] * cfg.total_ut_steps)
            n = self.read_pass(h, None if all_logits else length)
        else:
            params = self.variables["params"]
            one = layer_module(0, parent=None)
            # every layer of a looped stack is alike, so ONE trace of a
            # layer serves all of them, each called on its own parameters:
            # 48 layers traced in turn cost a cold set-up more than the
            # whole of SD1.5's (PERF.md section 6, PR 49)
            @jax.jit
            def looped_layer(p, x, buffers, pass_index, *where):
                return one.apply({"params": p}, x, *where, buffers,
                                 sequences=sequences, real=real,
                                 pass_index=pass_index, forked_at=forked_at,
                                 own_from=own_from)

            def shared_layer(layer, x, buffers, pass_index):
                return looped_layer(params[f"layers_{layer}"], x, buffers,
                                    pass_index, q_pos, start, end)

            def one_pass(carry, pass_index):
                x, cache, _ = stack(shared_layer, *carry, pass_index)
                x = final_norm(parent=None).apply(
                    {"params": params["norm"]}, x)
                return (x, cache), x

            # ONE loop whose body is the stack, the final norm closing
            # every pass: the next pass, the gate and the head read the
            # normed state
            (_, cache), h = jax.lax.scan(one_pass, (x, cache),
                                         jnp.arange(cfg.total_ut_steps))
            routed = []
            n = self.read_pass(h, None if all_logits else length)
        if sequences:
            cache[FORKED_AT] = [jnp.full_like(stamp, forked_at)]
            if lead is not None:
                cache[OWN_FROM] = [lead]
        if cfg.tied_head:
            logits = self.tied_logits(table.embedding, n)
            EXPANDER.record_tied_head(
                delta_rule.form(tokens.shape[0], sequences))
        else:
            logits = Linear(cfg.vocab[1], self.dtype, self.quant_linears,
                            name="lm_head")(n)
        if cfg.logit_multiplier != 1.0:
            logits = logits * cfg.logit_multiplier
        if not routed:     # no expert layer: the three parts, empty
            none = jnp.zeros((0,), jnp.int32)
            return logits, cache, (
                none[:, None, None],
                jnp.zeros((0, cfg.experts[1]), jnp.int32), none)
        return logits, cache, tuple(jnp.stack(part) for part in zip(*routed))

    @nn.nowrap
    def tied_logits(self, table, n):
        """``n E^T`` over the held slice ``table`` ``(count, hidden)`` of
        ``embed_tokens``: the head a tied model has (``LMConfig.
        tied_head``). The slice is contracted over its SECOND axis as it
        lies, so no transpose of it is made and a step streams it once, as
        it would a head's kernel; operands in ``dtype``, float32
        accumulation and result, as :class:`Linear`. Under
        ``quant_linears`` the control's int8 product takes the slice
        transposed (ops/quant.py wants ``(in, out)``: the control makes
        the copy the program does not). Scoped ``lm_head``: the name a
        trace knows a head's product by."""
        with jax.named_scope("lm_head"):
            if self.quant_linears:
                return int8_dot(n, table.T)
            return jax.lax.dot_general(
                n.astype(self.dtype), table.astype(self.dtype),
                (((n.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

    @nn.nowrap
    def read_pass(self, h, last):
        """The state the head reads, of a looped model's ``h`` ``(passes,
        T, hidden)``: each pass's normed output. Every pass always runs (a
        later token attends each pass's keys and values of this one); what
        the learned gate decides is which pass's state the head reads, per
        row: ``lambda_t = sigmoid(w_g . h_t + b_g)``, ``S_t = sum_{i <= t}
        lambda_i prod_{j < i} (1 - lambda_j)`` with the last pass's ``S``
        taken as 1, and the first pass whose ``S_t`` reaches
        ``early_exit_threshold``. ``last``: only the row before it is read
        (None: every row). The gates ``(passes, rows)`` and the chosen
        passes ``(rows,)`` are sown under ``"passes"``
        (:func:`apply_counting`)."""
        cfg = self.config
        if last is not None:
            h = jax.lax.dynamic_slice_in_dim(h, last - 1, 1, 1)
        # the gate sees the state in float32, as a router does: a pass
        # chosen by rounding the input would read another state
        gate = nn.Dense(1, dtype=jnp.float32, param_dtype=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST,
                        name="early_exit_gate")
        lam = jax.nn.sigmoid(gate(h)[..., 0])
        stay = jnp.cumprod(1.0 - lam, axis=0)
        left = jnp.cumsum(
            lam * jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]]),
            axis=0).at[-1].set(1.0)
        chosen = jnp.argmax(left >= cfg.early_exit_threshold, axis=0)
        self.sow("passes", "gates", lam)
        self.sow("passes", "exit", chosen)
        return jnp.take_along_axis(h, chosen[None, :, None], axis=0)[0]


# -- the cache's shapes, and the executables the engine builds ----------------

def cache_shapes(cfg: LMConfig, capacity: int) -> Dict[str, list]:
    """By buffer name the shapes of the layers that have it, in layer
    order. An attention layer has ``k`` and ``v``: a full layer holds
    ``capacity`` positions, a sliding layer a ring of its window. A linear
    layer has ``state`` and ``conv``, whatever the capacity. A latent layer
    has ``latent``: ``capacity`` rows of ``latent_width`` (no pass axis: a
    looped stack is full attention). A conv layer has
    ``kept``: its convolution's ``conv_taps - 1`` last inputs, whatever the
    capacity. A state-space part has ``ssm_state`` and ``ssm_conv``,
    whatever the capacity, beside what the layer's other parts have. A
    model without layers of a kind has none of the kind's names. A looped
    model's ``k`` and ``v`` carry the pass axis in front: ``(passes,
    capacity, kv heads, head_dim)``."""
    passes = (cfg.total_ut_steps,) if cfg.total_ut_steps > 1 else ()
    rows = [passes + (capacity if part == FULL else cfg.sliding_window,
                      cfg.num_kv_heads, cfg.head_dim)
            for kind in cfg.layer_types for part in kind_parts(kind)
            if part in (FULL, SLIDING)]
    shapes = {"k": rows, "v": list(rows)} if rows else {}
    linear = len(cfg.layers_of(LINEAR))
    if linear:
        shapes["state"] = [(cfg.linear_num_value_heads,
                            cfg.linear_key_head_dim,
                            cfg.linear_value_head_dim)] * linear
        shapes["conv"] = [(cfg.linear_conv_kernel - 1,
                           cfg.linear_conv_channels)] * linear
    latent = len(cfg.layers_of(LATENT))
    if latent:
        shapes["latent"] = [(capacity, cfg.latent_width)] * latent
    conv = len(cfg.layers_of(CONV))
    if conv:
        shapes["kept"] = [(cfg.conv_taps - 1, cfg.hidden_size)] * conv
    space = len(cfg.layers_of(SSM))
    if space:
        shapes["ssm_state"] = [(cfg.ssm_num_heads, cfg.ssm_head_dim,
                                cfg.ssm_state_size)] * space
        shapes["ssm_conv"] = [(cfg.ssm_conv_kernel - 1,
                               cfg.ssm_conv_channels)] * space
    return shapes


def buffer_dtype(name: str, dtype):
    """What a cache buffer holds: keys, values and latents are in the
    cache's ``dtype``, a linear layer's or a state-space part's state and
    every kept convolution input in float32."""
    return jnp.dtype(jnp.float32 if name in STATE_BUFFERS else dtype)


def empty_cache(cfg: LMConfig, capacity: int, dtype) -> Dict[str, list]:
    """Every buffer zero in its :func:`buffer_dtype` (zero is a linear
    layer's state at position 0, and what a convolution reads before
    it)."""
    return {name: [jnp.zeros(shape, buffer_dtype(name, dtype))
                   for shape in rows]
            for name, rows in cache_shapes(cfg, capacity).items()}


def sample(logits: jax.Array, key: jax.Array, position, temperature,
           first: int = 0):
    """One vocabulary id from ``logits`` ``(V,)`` over the held slice that
    starts at id ``first``: the draw is keyed by the position of the token
    being made, so a sequence does not depend on how its decoding was cut
    into chunks. Temperature 0 is the arg-max."""
    key = jax.random.fold_in(key, position)
    drawn = jax.random.categorical(
        key, logits / jnp.maximum(temperature, 1e-6))
    return first + jnp.where(temperature > 0, drawn,
                             jnp.argmax(logits)).astype(jnp.int32)


def sample_each(logits: jax.Array, keys: jax.Array, position, temperature,
                first: int = 0):
    """:func:`sample` for each of ``keys`` ``(B,)``: from its own row of
    ``logits`` ``(B, V)``, or every key from the one row ``(V,)``. A draw is
    what :func:`sample` gives that key alone, whatever its place among the
    ``B``. ``position`` is one for all, or ``(B,)``: each sequence's own
    (the sequences of a joined cache stand at different positions)."""
    return jax.vmap(sample, in_axes=(0 if logits.ndim == 2 else None, 0,
                                     0 if jnp.ndim(position) else None,
                                     None, None))(
        logits, keys, position, temperature, first)


def apply_counting(module: DecoderLM, variables, *args, live=None,
                   **kwargs):
    """``module.apply`` as ``(logits, cache, routed, exits)``. ``exits`` is
    what an executable of a looped model returns beside the rest, ``()``
    for a model of one pass (whose executables return what they always
    did): one pair of the rows by the pass whose state the head read
    ``(passes,)`` and the largest exit probability any gate gave, over the
    first ``live`` rows whose logits were made (None: all of them)."""
    cfg = module.config
    if cfg.total_ut_steps == 1:
        return module.apply(variables, *args, **kwargs) + ((),)
    (logits, cache, routed), sown = module.apply(
        variables, *args, mutable=["passes"], **kwargs)
    (gates,), (chosen,) = sown["passes"]["gates"], sown["passes"]["exit"]
    real = jnp.arange(chosen.shape[0]) < (
        chosen.shape[0] if live is None else live)
    counts = jnp.sum(jax.nn.one_hot(chosen, cfg.total_ut_steps,
                                    dtype=jnp.int32) * real[:, None], axis=0)
    return logits, cache, routed, (
        (counts, jnp.max(jnp.where(real[None], gates, 0.0))),)


def no_exits(cfg: LMConfig):
    """What :func:`apply_counting`'s ``exits`` add up from."""
    if cfg.total_ut_steps == 1:
        return ()
    return ((jnp.zeros((cfg.total_ut_steps,), jnp.int32), jnp.float32(0)),)


def add_exits(so_far, exits):
    return tuple((counts + more, jnp.maximum(most, gate))
                 for (counts, most), (more, gate) in zip(so_far, exits))


def no_zero_picks(cfg: LMConfig):
    """What a decode executable's count of picks on zero-compute experts
    adds up from, by expert layer: ``()`` for a router that has none
    (whose executables return what they always did)."""
    if not cfg.zero_experts:
        return ()
    return (jnp.zeros((len(cfg.expert_layers),), jnp.int32),)


def prefill_fn(module: DecoderLM, sequences: bool = False):
    """``expand_prefill(params, cache, tokens, start, length, key,
    temperature) -> (cache, next token, routed load, none held)``: one
    chunk, and the token that follows its last real one. With
    ``sequences``, ``key`` is ``(B,)`` keys and the next token ``(B,)``:
    what each key draws from the chunk's one last row, the first tokens of
    ``B`` sequences that share the chunk. A looped model's returns
    :func:`apply_counting`'s ``exits`` of that row after these."""

    def expand_prefill(params, cache, tokens, start, length, key,
                       temperature):
        logits, cache, routed, exits = apply_counting(
            module, {"params": params}, tokens, start, length, cache,
            all_logits=False)
        draw = sample_each if sequences else sample
        token = draw(logits[0], key, start + length, temperature,
                     module.config.vocab[0])
        return (cache, token, routed[1], routed[2]) + exits

    return expand_prefill


def decode_chunk_fn(module: DecoderLM, steps: int):
    """``expand_decode_chunk(params, cache, token, position, key,
    temperature) -> (cache, token, position, the steps' tokens, routed
    load, none held)``: ``steps`` tokens, each fed back as the next input;
    ``token`` sits at ``position`` and is not yet in the cache. A looped
    model's returns the steps' ``exits`` after these, and one whose router
    has zero-compute experts the steps' picks on them ``(expert layers,)``
    last."""

    def expand_decode_chunk(params, cache, token, position, key,
                            temperature):
        variables = {"params": params, "mixers": mixer_operands(params)}
        cfg = module.config
        looped = len(no_exits(cfg))

        def step(carry, _):
            cache, token, position, load, none_held, *rest = carry
            exits, zero = rest[:looped], rest[looped:]
            logits, cache, routed, more = apply_counting(
                module, variables, token[None], position, 1, cache,
                all_logits=False)
            token = sample(logits[0], key, position + 1, temperature,
                           module.config.vocab[0])
            return (cache, token, position + 1, load + routed[1],
                    none_held + routed[2]) + add_exits(exits, more) \
                + tuple(z + r for z, r in zip(zero, routed[3:])), token

        layers = len(cfg.expert_layers)
        zero = (jnp.zeros((layers, cfg.experts[1]), jnp.int32),
                jnp.zeros((layers,), jnp.int32)) + no_exits(cfg) \
            + no_zero_picks(cfg)
        (cache, token, position, load, none_held, *rest), made = \
            jax.lax.scan(step, (cache, token, position) + zero, None,
                         length=steps)
        return (cache, token, position, made, load, none_held) + tuple(rest)

    return expand_decode_chunk


def decode_sequences_fn(module: DecoderLM, steps: int):
    """:func:`decode_chunk_fn` over ``B`` sequences a step:
    ``expand_decode_chunk(params, cache, tokens, position, keys,
    temperature, live) -> (cache, tokens, position, the steps' tokens
    (steps, B), routed load, none held, experts read)``. ``tokens`` and
    ``keys`` are ``(B,)``, ``cache`` a forked one (cache/kv.py:fork), and
    all sequences sit at the one ``position``; or a joined one
    (cache/kv.py:joined_rows; read off the cache: it has :data:`OWN_FROM`),
    whose sequences continue prompts of their own: ``position`` is then
    the step's common one and sequence ``b`` stands ``own_from[b]`` short
    of it, in its rotation, its window and the key of its draw, so that
    it draws what it draws alone. Only the first ``live`` count:
    the others pad ``B`` up to a size an executable exists for, repeat a
    live one (so they choose no expert of their own) and are left out of
    the load. ``experts read`` ``(expert
    layers,)`` sums over the steps the DISTINCT held experts a step's rows
    chose (ops/moe.py:experts_read): what a step streams, however many of
    its rows chose one. A model with expert layers returns ``calls
    unread`` ``(expert layers,)`` next: the steps in which the rows chose
    no held expert, so that the layer's routed sum read nothing (a step of
    one sequence counts them already, as ``none held``). A looped model's
    returns the live rows' ``exits``
    after these, and one whose router has zero-compute experts the live
    rows' picks on them ``(expert layers,)`` last."""

    def expand_decode_chunk(params, cache, tokens, position, keys,
                            temperature, live):
        variables = {"params": params}
        cfg = module.config
        looped = len(no_exits(cfg))
        layers = len(cfg.expert_layers)
        calls = int(bool(layers))       # whether ``calls unread`` is carried
        # a joined cache: how far each sequence stands short of the step
        short = cache[OWN_FROM][0][:, 0] if OWN_FROM in cache else None

        def step(carry, _):
            cache, tokens, position, load, none_held, read, *rest = carry
            unread, exits, zero = (rest[:calls], rest[calls:calls + looped],
                                   rest[calls + looped:])
            logits, cache, routed, more = apply_counting(
                module, variables, tokens, position, live, cache,
                sequences=True, live=live)
            tokens = sample_each(
                logits, keys,
                position + 1 if short is None else position + 1 - short,
                temperature, cfg.vocab[0])
            return (cache, tokens, position + 1, load + routed[1],
                    none_held + routed[2],
                    read + moe.experts_read(routed[1])) \
                + tuple(u + (moe.experts_read(routed[1]) == 0)
                        for u in unread) \
                + add_exits(exits, more) \
                + tuple(z + r for z, r in zip(zero, routed[3:])), tokens

        zero = (jnp.zeros((layers, cfg.experts[1]), jnp.int32),
                jnp.zeros((layers,), jnp.int32),
                jnp.zeros((layers,), jnp.int32)) \
            + (jnp.zeros((layers,), jnp.int32),) * calls + no_exits(cfg) \
            + no_zero_picks(cfg)
        (cache, tokens, position, load, none_held, read, *rest), made = \
            jax.lax.scan(step, (cache, tokens, position) + zero, None,
                         length=steps)
        return (cache, tokens, position, made, load, none_held,
                read) + tuple(rest)

    return expand_decode_chunk

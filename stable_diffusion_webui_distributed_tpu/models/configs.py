"""Model architecture configs: SD 1.5, SDXL base/refiner, and tiny test models.

Shapes follow the published Stable Diffusion architectures (the ones every
sdwui node in the reference deployment serves remotely). A ``TINY`` family is
provided so the full pipeline runs in seconds on CPU for tests — same code
path, ~100k params.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class CLIPTextConfig:
    """Text-encoder transformer config (CLIP / OpenCLIP family)."""

    vocab_size: int = 49408
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    max_length: int = 77
    # "quick_gelu" (OpenAI CLIP, SD1.5) or "gelu" (OpenCLIP bigG, SDXL).
    hidden_act: str = "quick_gelu"
    # Project pooled EOS embedding (OpenCLIP bigG); 0 disables.
    projection_dim: int = 0
    # Which hidden state feeds cross-attention: 0 = final layer norm output,
    # 1 = penultimate layer ("clip skip 2" — SDXL always uses penultimate).
    default_skip: int = 0
    # webui re-applies the final LayerNorm to clip-skipped hidden states for
    # SD1.x; SDXL (sgm) uses the raw penultimate states.
    layernorm_skipped: bool = True


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    """Denoising UNet config (SD family).

    ``down_blocks`` entries are transformer depths per block: ``None`` means a
    plain ResNet block (no attention); an int is the number of transformer
    layers in each attention block at that resolution.
    """

    in_channels: int = 4
    out_channels: int = 4
    block_out_channels: Tuple[int, ...] = (320, 640, 1280, 1280)
    down_blocks: Tuple[Optional[int], ...] = (1, 1, 1, None)
    layers_per_block: int = 2
    cross_attention_dim: int = 768
    # Per-block head count; None derives heads from head_dim=64 (SDXL rule).
    num_attention_heads: Optional[int] = 8
    mid_block_depth: Optional[int] = 1  # transformer depth in the mid block
    # SDXL micro-conditioning: pooled text (1280) + 6 fourier-embedded
    # time_ids (6*256) -> MLP -> added to the timestep embedding.
    addition_embed_dim: int = 0  # 0 = disabled (SD1.5)
    addition_time_embed_dim: int = 256
    projection_input_dim: int = 2816


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    """AutoencoderKL config."""

    in_channels: int = 3
    latent_channels: int = 4
    block_out_channels: Tuple[int, ...] = (128, 256, 512, 512)
    layers_per_block: int = 2
    scaling_factor: float = 0.18215
    # Decode in f32 even under bf16 policy (visible banding otherwise).
    force_decoder_f32: bool = True


@dataclasses.dataclass(frozen=True)
class RopeConfig:
    """One rotary parameterisation. ``factor`` 0 is plain RoPE; over 0 the
    frequencies are YaRN's (interpolated below ``beta_slow`` rotations of
    the original context, kept above ``beta_fast``) and cos/sin are
    multiplied by ``attention_factor``. ``interleaved`` says which dims
    make a rotated pair: ``(i, i + half)`` (False, the ``rotate_half``
    convention) or neighbours ``(2i, 2i + 1)``."""

    theta: float = 1e4
    partial_rotary_factor: float = 1.0   # share of head_dim that is rotated
    factor: float = 0.0
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    interleaved: bool = False


def kind_parts(kind: str) -> Tuple[str, ...]:
    """The base kinds of a layer spelled ``kind``: one kind of token mixer
    (models/lm.py), or several joined by ``+`` (mixers side by side under
    one norm, each kind once, one of them at most keeping keys and
    values)."""
    return tuple(kind.split("+"))


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """A decoder-only language model read from lists: the kind of every
    layer's token mixer (``"full"`` or ``"sliding"`` attention over cached
    keys and values, ``"linear"``: a gated delta rule over a recurrent
    state of fixed size behind a short causal convolution, ``"latent"``:
    attention over one cached low-rank latent and one rotated key a
    position, shared by every head, or ``"conv"``: a gated causal
    convolution of ``conv_taps`` taps over the hidden channels, whose whole
    state is its last ``conv_taps - 1`` inputs), its query
    heads (an entry of a linear or conv layer is not read), which layers
    have a dense MLP and which a router over experts. ``num_experts``
    is the router's width (all of the layer's experts); ``experts_held`` and
    ``vocab_held`` are the contiguous ranges ``(first, count)`` of experts
    and of vocabulary ids whose weights THIS chip holds (``None``: all).

    ``attn_gate`` says where an attention layer's output gate comes from:
    ``"head"`` is one sigmoid a query head from its own ``g_proj``,
    ``"element"`` one a channel, the second half of every head's
    ``q_proj`` columns, ``"none"`` no gate at all. A latent layer reads
    the same key: ``"element"`` is one sigmoid a value channel from a
    ``g_proj`` of its own (a latent layer's queries come through a latent,
    so no half of their columns can be the gate), applied to the heads'
    outputs before ``o_proj`` in all three forms, ``"head"`` one a head.
    ``qk_norm`` puts an RMS norm over each head's query
    and key before the rotation. ``zero_centred_norm`` makes every norm
    ``x_hat * (1 + weight)`` instead of ``x_hat * scale``;
    ``norm_sigmoid_scale`` over 0 makes every norm ``x_hat * scale *
    sigmoid(weight)`` (a gate that is 1 at ``weight`` 0 when the scale is
    2). ``linear_sigmoid_gate_scale`` over 0 makes a linear layer's
    read-out ``x_hat * (1 + weight) * scale * sigmoid(z)`` where 0 has
    ``x_hat * scale * silu(z)``. ``swiglu_limit`` over 0 clamps every
    SwiGLU, dense, shared and routed: ``silu(min(gate, limit)) * clip(up,
    -limit, limit)``.
    ``shared_expert_gate`` multiplies the shared expert by
    ``sigmoid(w_s^T n)``. The defaults of the last three are the ungated,
    un-normed forms. ``shared_expert_intermediate_size`` 0 is an expert
    layer with no shared expert: no such weights and no such product.

    ``router_scoring`` is ``"softmax"`` or ``"sigmoid"`` over the router's
    outputs; ``router_bias`` adds a learned per-expert bias to the scores
    when the experts are CHOSEN and not when they are weighed;
    ``norm_topk_eps`` is added to the chosen scores' sum before they are
    divided by it.
    ``residual_streams`` over 1 replaces ``x + F(norm(x))`` by that many
    streams a token, read, written and mixed per token by a mixer around
    every sublayer (models/lm.py:StreamMixer), whose mixing matrix is made
    doubly stochastic by ``sinkhorn_iters`` Sinkhorn iterations.

    ``total_ut_steps`` over 1 is a looped model: a token goes through the
    whole stack that many times over the SAME weights, the final norm
    closing every pass, and pass ``t`` of a layer writes and attends keys
    and values of its own (models/lm.py: the cache's pass axis). All
    passes always run. A learned gate ``lambda_t = sigmoid(w_g . h_t +
    b_g)`` reads each pass's normed output: the head
    reads the first pass whose cumulated exit probability ``S_t = sum_{i <=
    t} lambda_i prod_{j < i} (1 - lambda_j)`` reaches
    ``early_exit_threshold`` (``S`` of the last pass counts as 1, so at
    threshold 1 it is the last). ``post_sublayer_norm`` puts an RMS norm
    after each sublayer as well as before it: ``x + norm(F(norm(x)))``.
    At one pass and without such norms a model is what it was before these
    keys existed: no loop, no gate, no pass axis, no leaf more.

    ``norm_placement`` says it a layer: ``"pre"`` (``x + F(norm(x))``),
    ``"post"`` (``x + norm(F(x))``: the sublayers read the stream as it
    is) or ``"both"``; empty, every layer is ``"both"`` under
    ``post_sublayer_norm`` and ``"pre"`` without (:attr:`sublayer_norms`).
    ``rope_full`` None: a full layer builds no rotary table and rotates
    nothing (the order is then some other layer's to carry).
    ``qk_norm_extent`` ``"projection"`` norms queries and keys over ALL
    their heads' outputs at once, one RMS and one weight of ``heads *
    head_dim``, before the heads are cut; ``"head"`` each head alone.
    ``linear_write_scale`` ``c`` makes a linear layer's write strength
    ``beta = c sigmoid(b)``: at 2 the state's transition ``I - beta k k^T``
    has eigenvalues down to -1.

    A layer may hold SEVERAL token mixers side by side that read ONE norm
    and add into the residual once: its entry of ``layer_types`` then
    spells its base kinds joined by ``+`` (``"full+ssm"``), and
    :func:`kind_parts` is the one place that splits the spelling. A base
    kind alone is a layer as before. ``"ssm"`` is a selective state-space
    mixer (models/lm.py:SSMMixer, ops/ssm.py): ``ssm_num_heads`` heads of
    ``ssm_head_dim`` over states ``ssm_state_size`` wide, ``B`` and ``C``
    shared by the heads of one of ``ssm_num_groups`` groups, behind a
    causal convolution of ``ssm_conv_kernel`` taps (with a bias under
    ``ssm_conv_bias``) over ``[x | B | C]``, its read-out gated and THEN
    normed over each group's channels (``ssm_norm_before_gate``: normed,
    then gated), its prefill cut into chunks of ``ssm_chunk``.

    The forward multipliers are scalars the forward pass applies where it
    says, none folded into a weight; every default is 1.0 and applies
    nothing (no op is traced). ``embedding_multiplier`` scales the table's
    output, ``logit_multiplier`` the head's logits, ``key_multiplier`` the
    keys before their rotation (the cache holds them scaled),
    ``mixer_multipliers`` a token mixer's input and its output by base
    kind, as ``(kind, in, out)`` triples, ``ssm_multipliers`` the five
    column ranges ``[z | x | B | C | dt]`` of a state-space mixer's
    ``in_proj`` output, ``mlp_multipliers`` a dense SwiGLU's gate
    pre-activation and its output. ``latent_q_scale`` multiplies a latent
    layer's queries (both parts, after ``q_b_proj``), ``latent_kv_scale``
    its normed latent before ``kv_b_proj`` (the cache holds it scaled, so
    the un-rotated keys and the values carry it in every form and the
    rotated key does not).

    ``zero_experts`` says how many of the router's LAST ids are
    zero-compute experts, ``E_e(n) = n``: they have no kernels and are held
    wherever the token lives; the real experts are the first ``num_experts
    - zero_experts`` (:attr:`real_experts`), and ``experts_held`` is a range
    of those. ``moe_shortcut`` carries an expert layer's routed sum (real
    and identity experts) OUT of its layer: the layer adds only its shared
    expert at its own residual, and the sum lands after the NEXT layer's
    MLP sublayer, which must be a dense one: two layers of the list then
    spell one shortcut-connected double layer, ``x1 = x + A_0(N(x)); n =
    N(x1); s = M(n); x2 = x1 + F_0(n); x3 = x2 + A_1(N(x2)); y = x3 +
    F_1(N(x3)) + s``. Each default is the plain form and traces no op.

    ``tied_head``: the head IS the table. The logits are the normed state
    times the held slice of ``embed_tokens`` transposed (contracted over
    the hidden axis as the slice lies: one leaf, no ``lm_head``, no
    transpose made), so ``vocab_held`` slices both at once.
    ``residual_multiplier`` scales what EACH sublayer adds to the residual,
    a token mixer's and a dense or an expert MLP's alike, after the
    sublayer's post-norm where it has one: ``x + m * norm(F(norm(x)))``
    (refused with several streams, whose mixers weigh what is written, and
    with ``moe_shortcut``, whose carried sum is weighed elsewhere).
    ``attention_scale`` over 0 is what a ``full`` or ``sliding`` layer
    multiplies its scores by in all three of its forms (a chunk, a
    one-sequence step, a forked step) where 0 has ``head_dim ** -0.5``.
    Each default is the plain form and traces no op."""

    vocab_size: int = 100352
    hidden_size: int = 3072
    layer_types: Tuple[str, ...] = ("full",)
    num_heads_per_layer: Tuple[int, ...] = (48,)
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    rope_full: Optional[RopeConfig] = dataclasses.field(
        default_factory=RopeConfig)
    rope_sliding: RopeConfig = dataclasses.field(default_factory=RopeConfig)
    dense_layers: Tuple[int, ...] = (0,)
    intermediate_size: int = 12288
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[Tuple[int, int]] = None
    attn_gate: str = "head"
    qk_norm: bool = False
    zero_centred_norm: bool = False
    shared_expert_gate: bool = False
    # "linear" layers: key heads (each serves value_heads / key_heads value
    # heads), their widths, and the taps of the causal convolution
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 4
    # "conv" layers: the taps of the gated convolution (``conv_L_cache``)
    conv_taps: int = 3
    # "latent" layers: the ranks of the query's and the cache's low-rank
    # paths (``q_lora_rank`` 0: no query latent, one ``q_proj``), a head's
    # un-rotated and rotated key widths and its value
    # width; they rotate by ``rope_full`` over all of ``qk_rope_head_dim``.
    # YaRN's ``mscale_all_dim`` scales their softmax, not the tables.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_mscale_all_dim: float = 0.0
    router_scoring: str = "softmax"
    router_bias: bool = False
    norm_topk_eps: float = 0.0
    residual_streams: int = 1
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    total_ut_steps: int = 1
    early_exit_threshold: float = 1.0
    post_sublayer_norm: bool = False
    norm_sigmoid_scale: float = 0.0
    linear_sigmoid_gate_scale: float = 0.0
    swiglu_limit: float = 0.0
    norm_placement: Tuple[str, ...] = ()
    qk_norm_extent: str = "head"
    linear_write_scale: float = 1.0
    # "ssm" parts: heads, their width, the state's width, the groups that
    # share B and C, the convolution's taps and whether it has a bias, and
    # the chunk of the prefill's chunk-wise form
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_num_groups: int = 1
    ssm_conv_kernel: int = 4
    ssm_conv_bias: bool = False
    ssm_norm_before_gate: bool = False
    ssm_chunk: int = 128
    embedding_multiplier: float = 1.0
    logit_multiplier: float = 1.0
    key_multiplier: float = 1.0
    mixer_multipliers: Tuple[Tuple[str, float, float], ...] = ()
    ssm_multipliers: Tuple[float, float, float, float, float] = (1.0,) * 5
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    zero_experts: int = 0
    moe_shortcut: bool = False
    latent_q_scale: float = 1.0
    latent_kv_scale: float = 1.0
    tied_head: bool = False
    residual_multiplier: float = 1.0
    attention_scale: float = 0.0

    def __post_init__(self) -> None:
        if self.norm_placement and (
                len(self.norm_placement) != self.num_layers
                or not set(self.norm_placement) <= {"pre", "post", "both"}):
            raise ValueError("norm_placement wants 'pre', 'post' or 'both' "
                             "for every layer")
        if self.total_ut_steps > 1 and (
                set(self.layer_types) != {"full"}
                or len(set(self.num_heads_per_layer)) != 1
                or self.residual_streams != 1 or self.expert_layers):
            raise ValueError("a looped stack (total_ut_steps over 1) wants "
                             "full attention layers all alike, dense MLPs "
                             "and one stream")
        if self.moe_shortcut and (
                self.residual_streams != 1 or self.total_ut_steps != 1
                or not self.shared_expert_intermediate_size
                or any(i + 1 not in self.dense_layers
                       for i in self.expert_layers)):
            raise ValueError("moe_shortcut wants a dense layer after every "
                             "expert layer, a shared expert (the dense path "
                             "beside the router), one stream and one pass")
        if not 0 <= self.zero_experts <= self.num_experts:
            raise ValueError("zero_experts are some of the router's "
                             "num_experts outputs")
        if self.residual_multiplier != 1.0 and (
                self.residual_streams != 1 or self.moe_shortcut):
            raise ValueError("residual_multiplier wants one stream (a "
                             "stream mixer weighs what a sublayer writes) "
                             "and no moe_shortcut (a carried sum is weighed "
                             "where it was routed)")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def real_experts(self) -> int:
        """The router's outputs that are experts with kernels: the first
        ``num_experts - zero_experts`` ids."""
        return self.num_experts - self.zero_experts

    @property
    def experts(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.real_experts)

    @property
    def vocab(self) -> Tuple[int, int]:
        return self.vocab_held or (0, self.vocab_size)

    @property
    def sublayer_norms(self) -> Tuple[str, ...]:
        """Where each layer norms its sublayers: ``norm_placement``, or
        what ``post_sublayer_norm`` says of every layer."""
        return self.norm_placement or (
            ("both" if self.post_sublayer_norm else "pre"),
        ) * self.num_layers

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """The layers that have a token mixer of base kind ``kind``, alone
        or beside others."""
        return tuple(i for i, t in enumerate(self.layer_types)
                     if kind in kind_parts(t))

    @property
    def base_kinds(self) -> frozenset:
        """The base kinds any layer has a part of."""
        return frozenset(part for kind in self.layer_types
                         for part in kind_parts(kind))

    def mixer_multiplier(self, kind: str) -> Tuple[float, float]:
        """``(in, out)`` of a token mixer of base kind ``kind``."""
        for name, before, after in self.mixer_multipliers:
            if name == kind:
                return before, after
        return 1.0, 1.0

    @property
    def multipliers_applied(self) -> int:
        """How many of the forward multipliers are off 1 (the scores' scale
        off ``head_dim ** -0.5`` counts as one)."""
        scalars = (self.embedding_multiplier, self.logit_multiplier,
                   self.key_multiplier, *self.ssm_multipliers,
                   *self.mlp_multipliers, self.residual_multiplier,
                   *(m for _, *pair in self.mixer_multipliers for m in pair))
        return sum(m != 1.0 for m in scalars) + bool(self.attention_scale)

    @property
    def ssm_inner(self) -> int:
        """A state-space mixer's heads side by side."""
        return self.ssm_num_heads * self.ssm_head_dim

    @property
    def ssm_conv_channels(self) -> int:
        """Channels a state-space mixer's convolution runs over: ``[x | B
        | C]``, ``B`` and ``C`` one a group."""
        return self.ssm_inner + 2 * self.ssm_num_groups * self.ssm_state_size

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_layers)
                     if i not in self.dense_layers)

    @property
    def linear_conv_channels(self) -> int:
        """Channels the convolution of a linear layer runs over: its
        queries, keys and values side by side."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def latent_width(self) -> int:
        """Values a latent layer caches a position: the normed latent and
        the one rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_softmax_scale(self) -> float:
        """``(nope + rope) ** -0.5 * m ** 2``, ``m = 0.1 * mscale_all_dim *
        ln(factor) + 1`` where the rotary tables are YaRN's, else 1."""
        m = 1.0
        if self.rope_full.factor > 1 and self.rope_mscale_all_dim:
            m += 0.1 * self.rope_mscale_all_dim \
                * math.log(self.rope_full.factor)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    """A complete diffusion model family: text encoder(s) + UNet + VAE."""

    name: str = "sd15"
    text_encoder: CLIPTextConfig = dataclasses.field(default_factory=CLIPTextConfig)
    # SDXL's second (OpenCLIP bigG) encoder; None for SD1.5.
    text_encoder_2: Optional[CLIPTextConfig] = None
    unet: UNetConfig = dataclasses.field(default_factory=UNetConfig)
    vae: VAEConfig = dataclasses.field(default_factory=VAEConfig)
    # v-prediction (SD2.x-style) vs epsilon-prediction.
    prediction_type: str = "epsilon"
    # resident prompt expander (models/lm.py): the always-on "prompt
    # expansion" script's language model; None = the family has none and
    # the script is ignored
    expander: Optional[LMConfig] = None

    @property
    def vae_scale_factor(self) -> int:
        """Image->latent downsampling: one 2x per VAE level transition
        (8 for every real SD family; derived so tiny test VAEs agree)."""
        return 2 ** (len(self.vae.block_out_channels) - 1)

    @property
    def inpaint(self) -> bool:
        """Inpainting-specialized checkpoint (ldm "hybrid" conditioning):
        the UNet eats [latent, mask, masked-image latent] — latent + 1 +
        latent channels (sd-v1-5-inpainting and friends)."""
        return self.unet.in_channels == 2 * self.vae.latent_channels + 1

    @property
    def context_dim(self) -> int:
        return self.unet.cross_attention_dim


# Alias kept for readability at call sites that only care about dimensions.
SDModelConfig = ModelFamily


SD15 = ModelFamily(name="sd15")

# SD 2.x: OpenCLIP ViT-H text encoder (penultimate layer + final LN, the
# ldm FrozenOpenCLIPEmbedder convention), 1024-dim cross-attention,
# head_dim-64 attention. "sd21" is the 768-v v-prediction model; "sd21-base"
# the 512 epsilon model (same weights layout — select via the <ckpt>.json
# family sidecar, as webui selects via the .yaml).
SD2_TEXT = CLIPTextConfig(hidden_size=1024, intermediate_size=4096,
                          num_layers=24, num_heads=16, hidden_act="gelu",
                          default_skip=1, layernorm_skipped=True)
_SD2_UNET = UNetConfig(cross_attention_dim=1024, num_attention_heads=None)

SD21 = ModelFamily(name="sd21", text_encoder=SD2_TEXT, unet=_SD2_UNET,
                   prediction_type="v_prediction")
SD21_BASE = ModelFamily(name="sd21-base", text_encoder=SD2_TEXT,
                        unet=_SD2_UNET)

SDXL_TEXT_L = CLIPTextConfig(hidden_size=768, intermediate_size=3072,
                             num_layers=12, num_heads=12, default_skip=1,
                             layernorm_skipped=False)
SDXL_TEXT_G = CLIPTextConfig(hidden_size=1280, intermediate_size=5120,
                             num_layers=32, num_heads=20, hidden_act="gelu",
                             projection_dim=1280, default_skip=1,
                             layernorm_skipped=False)

SDXL_BASE = ModelFamily(
    name="sdxl-base",
    text_encoder=SDXL_TEXT_L,
    text_encoder_2=SDXL_TEXT_G,
    unet=UNetConfig(
        block_out_channels=(320, 640, 1280),
        down_blocks=(None, 2, 10),
        cross_attention_dim=2048,
        num_attention_heads=None,  # heads = channels // 64
        mid_block_depth=10,
        addition_embed_dim=1280,
    ),
    vae=VAEConfig(scaling_factor=0.13025),
)

# SDXL refiner: single 1280-wide text encoder (bigG), 4-level UNet with
# depth-4 transformers, aesthetic-score conditioning (2560 proj input).
SDXL_REFINER = ModelFamily(
    name="sdxl-refiner",
    text_encoder=SDXL_TEXT_G,
    text_encoder_2=None,
    unet=UNetConfig(
        block_out_channels=(384, 768, 1536, 1536),
        down_blocks=(None, 4, 4, None),
        cross_attention_dim=1280,
        num_attention_heads=None,
        mid_block_depth=4,
        addition_embed_dim=1280,
        projection_input_dim=2560,
    ),
    vae=VAEConfig(scaling_factor=0.13025),
)

# Tiny family for CPU tests: same code path, trivially small.
TINY = ModelFamily(
    name="tiny",
    text_encoder=CLIPTextConfig(
        vocab_size=1024, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=4, max_length=77,
    ),
    unet=UNetConfig(
        block_out_channels=(32, 64),
        down_blocks=(1, 1),
        layers_per_block=1,
        cross_attention_dim=32,
        num_attention_heads=4,
        mid_block_depth=1,
    ),
    vae=VAEConfig(block_out_channels=(32, 32), layers_per_block=1),
)

# Tiny SDXL-shaped family: exercises dual encoders + micro-conditioning.
TINY_XL = ModelFamily(
    name="tiny-xl",
    text_encoder=CLIPTextConfig(
        vocab_size=1024, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=4, default_skip=1, layernorm_skipped=False,
    ),
    text_encoder_2=CLIPTextConfig(
        vocab_size=1024, hidden_size=48, intermediate_size=96,
        num_layers=2, num_heads=4, hidden_act="gelu",
        projection_dim=48, default_skip=1, layernorm_skipped=False,
    ),
    unet=UNetConfig(
        block_out_channels=(32, 64),
        down_blocks=(None, 2),
        layers_per_block=1,
        cross_attention_dim=80,
        num_attention_heads=4,
        mid_block_depth=2,
        addition_embed_dim=48,
        addition_time_embed_dim=8,
        projection_input_dim=48 + 6 * 8,
    ),
    vae=VAEConfig(block_out_channels=(32, 32), layers_per_block=1,
                  scaling_factor=0.13025),
)

# Tiny refiner-shaped family: single projected text encoder + the refiner's
# 5-element micro-conditioning (aesthetic score instead of target size).
TINY_REFINER = ModelFamily(
    name="tiny-refiner",
    text_encoder=CLIPTextConfig(
        vocab_size=1024, hidden_size=48, intermediate_size=96,
        num_layers=2, num_heads=4, hidden_act="gelu",
        projection_dim=48, default_skip=1, layernorm_skipped=False,
    ),
    unet=UNetConfig(
        block_out_channels=(32, 64),
        down_blocks=(None, 2),
        layers_per_block=1,
        cross_attention_dim=48,
        num_attention_heads=4,
        mid_block_depth=2,
        addition_embed_dim=48,
        addition_time_embed_dim=8,
        projection_input_dim=48 + 5 * 8,
    ),
    vae=VAEConfig(block_out_channels=(32, 32), layers_per_block=1,
                  scaling_factor=0.13025),
)

# Tiny v-prediction family: exercises the v-pred denoiser branch on CPU.
TINY_V = dataclasses.replace(TINY, name="tiny-v",
                             prediction_type="v_prediction")

# Inpainting-specialized variants (ldm "hybrid" conditioning, 9-channel
# conv_in: latent + mask + masked-image latent — sd-v1-5-inpainting,
# stable-diffusion-2-inpainting, sd_xl_base inpainting ports; webui
# detects these via the .yaml, here via conv_in shape at load).
SD15_INPAINT = dataclasses.replace(
    SD15, name="sd15-inpaint",
    unet=dataclasses.replace(SD15.unet, in_channels=9))
SD2_INPAINT = dataclasses.replace(
    SD21_BASE, name="sd2-inpaint",
    unet=dataclasses.replace(SD21_BASE.unet, in_channels=9))
SDXL_INPAINT = dataclasses.replace(
    SDXL_BASE, name="sdxl-inpaint",
    unet=dataclasses.replace(SDXL_BASE.unet, in_channels=9))
TINY_INPAINT = dataclasses.replace(
    TINY, name="tiny-inpaint",
    unet=dataclasses.replace(TINY.unet, in_channels=9))

# Laguna-S-2.1 (poolside; huggingface.co/poolside/Laguna-S-2.1 config.json)
# at its published widths: 48 layers in the pattern full, sliding, sliding,
# sliding with 48 and 72 query heads, a dense first layer, 256 experts of
# which 10 a token plus one shared.
_LAGUNA_ROPE_FULL = RopeConfig(
    theta=5e5, partial_rotary_factor=0.5, factor=128.0,
    original_max_position=8192, beta_fast=32.0, beta_slow=1.0,
    attention_factor=1.4852030263919618)
LAGUNA_S_2_1 = LMConfig(
    layer_types=("full", "sliding", "sliding", "sliding") * 12,
    num_heads_per_layer=(48, 72, 72, 72) * 12,
    rope_full=_LAGUNA_ROPE_FULL, rope_sliding=RopeConfig(theta=1e4))


def lm_share(cfg: LMConfig, layers, chips: int, rank: int,
             vocab_chips: int = 0) -> LMConfig:
    """The share of ``cfg`` one chip of ``chips`` holds when they share each
    layer: the first ``layers`` layers (the others lie on further chips as
    pipeline stages), every attention head, the shared expert, and the
    ``rank``-th contiguous part of the experts (those that have kernels)
    and of the vocabulary.
    ``layers`` may name the published layers held instead of counting them
    (leading dense layers that repeat one kind and shape are held once):
    a dense layer held keeps its dense MLP wherever it comes to lie.
    ``vocab_chips``: the table and the head are sliced that many ways
    where it is not as many as the experts (0: as many)."""
    experts = cfg.real_experts // chips
    vocab = cfg.vocab_size // (vocab_chips or chips)
    if isinstance(layers, int):
        layers = range(layers)
    return dataclasses.replace(
        cfg, layer_types=tuple(cfg.layer_types[i] for i in layers),
        norm_placement=tuple(cfg.norm_placement[i] for i in layers)
        if cfg.norm_placement else (),
        num_heads_per_layer=tuple(cfg.num_heads_per_layer[i]
                                  for i in layers),
        dense_layers=tuple(at for at, i in enumerate(layers)
                           if i in cfg.dense_layers),
        experts_held=(rank * experts, experts),
        vocab_held=(rank * vocab, vocab))


def sd15_laguna_expander() -> ModelFamily:
    """SD1.5 with Laguna-S-2.1 as its resident prompt expander, cut to one
    chip of a pair: layers 0-4 (the dense layer and one whole period),
    experts 0-127 of every expert layer, vocabulary ids 0-50175."""
    return dataclasses.replace(
        SD15, name="sd15-laguna-expand",
        expander=lm_share(LAGUNA_S_2_1, layers=5, chips=2, rank=0))


# Qwen3-Next-80B-A3B-Instruct
# (huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct config.json) at its
# published widths: 48 layers in the pattern linear, linear, linear, full;
# a linear layer is a gated delta rule over 16 key and 32 value heads of
# width 128 behind a 4-tap convolution, a full layer 16 query heads of
# width 256 over 2 KV heads with q/k norms, a quarter of the dims rotated
# and an element-wise output gate out of q_proj; every layer a router over
# 512 experts of width 512, 10 a token, plus one gated shared expert.
QWEN3_NEXT_80B_A3B = LMConfig(
    vocab_size=151936, hidden_size=2048,
    layer_types=("linear", "linear", "linear", "full") * 12,
    num_heads_per_layer=(16,) * 48, num_kv_heads=2, head_dim=256,
    rope_full=RopeConfig(theta=1e7, partial_rotary_factor=0.25),
    dense_layers=(), intermediate_size=5120, num_experts=512,
    num_experts_per_tok=10, moe_intermediate_size=512,
    shared_expert_intermediate_size=512, routed_scaling_factor=1.0,
    norm_topk_prob=True, rms_norm_eps=1e-6, attn_gate="element",
    qk_norm=True, zero_centred_norm=True, shared_expert_gate=True,
    linear_num_key_heads=16, linear_num_value_heads=32,
    linear_key_head_dim=128, linear_value_head_dim=128,
    linear_conv_kernel=4)


def sd15_qwen3next_expander() -> ModelFamily:
    """SD1.5 with Qwen3-Next-80B-A3B-Instruct as its resident prompt
    expander, cut to one chip of a four-chip host: layers 0-11 (three whole
    periods), experts 0-127 of every layer, vocabulary ids 0-37983."""
    return dataclasses.replace(
        SD15, name="sd15-qwen3next-expand",
        expander=lm_share(QWEN3_NEXT_80B_A3B, layers=12, chips=4, rank=0))


# Xing4.0-29B-A4B (huggingface.co/XingChen-AGI/Xing4.0-29B-A4B config.json)
# at its published widths: 40 latent-attention layers of 32 heads (a 768-wide
# query latent, a cached 512-wide latent and one 64-wide rotated key, keys
# of 128 + 64 and values of 128 a head), YaRN by 64 over 4096 whose mscale
# goes into the softmax, four residual streams mixed by 20 Sinkhorn
# iterations around every sublayer, two dense layers of width 9216 then a
# sigmoid router with a selection bias over 64 experts of width 1024, 4 a
# token at scale 2, plus one shared.
XING4_0_29B_A4B = LMConfig(
    vocab_size=131072, hidden_size=3584, layer_types=("latent",) * 40,
    num_heads_per_layer=(32,) * 40,
    rope_full=RopeConfig(theta=1e4, factor=64.0, original_max_position=4096,
                         beta_fast=32.0, beta_slow=1.0, attention_factor=1.0),
    dense_layers=(0, 1), intermediate_size=9216, num_experts=64,
    num_experts_per_tok=4, moe_intermediate_size=1024,
    shared_expert_intermediate_size=1024, routed_scaling_factor=2.0,
    norm_topk_prob=True, rms_norm_eps=1e-6, q_lora_rank=768,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_mscale_all_dim=1.0, router_scoring="sigmoid",
    router_bias=True, residual_streams=4, sinkhorn_iters=20, hc_eps=1e-6,
    hc_res_clamp=(-30.0, 30.0), attn_gate="none")


def sd15_xing4_expander() -> ModelFamily:
    """SD1.5 with Xing4.0-29B-A4B as its resident prompt expander, cut to
    one chip of a four-chip host: layers 0-19 (the first pipeline stage:
    both dense layers and 18 expert layers), experts 0-15 of every expert
    layer, vocabulary ids 0-32767."""
    return dataclasses.replace(
        SD15, name="sd15-xing4-expand",
        expander=lm_share(XING4_0_29B_A4B, layers=20, chips=4, rank=0))


# Tiny expander that keeps every kind: two head counts, a window (8) shorter
# than any test context so the ring wraps, a dense first layer, 16 experts
# top-4 with a shared one, partial YaRN and full plain rotary.
TINY_LM = LMConfig(
    vocab_size=512, hidden_size=32,
    layer_types=("full", "sliding", "sliding", "full"),
    num_heads_per_layer=(4, 6, 6, 4), num_kv_heads=2, head_dim=16,
    sliding_window=8,
    rope_full=RopeConfig(theta=5e5, partial_rotary_factor=0.5, factor=4.0,
                         original_max_position=16, attention_factor=1.1386),
    rope_sliding=RopeConfig(theta=1e4),
    dense_layers=(0,), intermediate_size=64, num_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=16)
TINY_EXPAND = dataclasses.replace(
    TINY, name="tiny-expand", expander=lm_share(TINY_LM, 4, chips=2, rank=0))


def tiny_expander() -> ModelFamily:
    """Factory form of :data:`TINY_EXPAND` (benchmark rehearsals)."""
    return TINY_EXPAND


# Tiny expander with all three layer kinds: two linear layers (2 key heads
# serving 4 value heads of width 8, 4 taps) before a sliding and a full
# one, element-wise gate, q/k norms, zero-centred norms, a gated shared
# expert and no dense layer.
TINY_DELTA_LM = LMConfig(
    vocab_size=512, hidden_size=32,
    layer_types=("linear", "linear", "sliding", "full"),
    num_heads_per_layer=(4, 4, 4, 4), num_kv_heads=2, head_dim=16,
    sliding_window=8,
    rope_full=RopeConfig(theta=1e7, partial_rotary_factor=0.25),
    rope_sliding=RopeConfig(theta=1e4),
    dense_layers=(), intermediate_size=64, num_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, routed_scaling_factor=1.0,
    attn_gate="element", qk_norm=True, zero_centred_norm=True,
    shared_expert_gate=True, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, linear_conv_kernel=4)
TINY_DELTA_EXPAND = dataclasses.replace(
    TINY, name="tiny-delta-expand",
    expander=lm_share(TINY_DELTA_LM, 4, chips=4, rank=0))


def tiny_delta_expander() -> ModelFamily:
    """Factory form of :data:`TINY_DELTA_EXPAND` (benchmark rehearsals)."""
    return TINY_DELTA_EXPAND


# Tiny expander of latent-attention layers: a cached latent (16 + a rotated
# key of 8) narrower than the four heads' keys and values (4 x (8 + 8) and
# 4 x 8), a 24-wide query latent, YaRN whose mscale scales the softmax, four
# residual streams, two dense layers then expert layers, 16 experts top-4
# by sigmoid scores with a selection bias, one shared.
TINY_LATENT_LM = LMConfig(
    vocab_size=512, hidden_size=32, layer_types=("latent",) * 4,
    num_heads_per_layer=(4,) * 4,
    rope_full=RopeConfig(theta=1e4, factor=4.0, original_max_position=16,
                         attention_factor=1.0),
    dense_layers=(0, 1), intermediate_size=64, num_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, routed_scaling_factor=2.0,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, rope_mscale_all_dim=1.0,
    router_scoring="sigmoid", router_bias=True, residual_streams=4,
    attn_gate="none")
TINY_LATENT_EXPAND = dataclasses.replace(
    TINY, name="tiny-latent-expand",
    expander=lm_share(TINY_LATENT_LM, 4, chips=4, rank=0))


def tiny_xing4_expander() -> ModelFamily:
    """Factory form of :data:`TINY_LATENT_EXPAND` (benchmark rehearsals)."""
    return TINY_LATENT_EXPAND


# LFM2-24B-A2B (huggingface.co/LiquidAI/LFM2-24B-A2B config.json) at its
# published widths: 40 layers, two leading conv layers then the pattern
# full, conv, conv, conv; a conv layer is a gated 3-tap causal convolution
# over the 2048 hidden channels between two projections, a full layer 32
# query heads of width 64 over 8 KV heads with q/k norms, every dim rotated
# and no output gate; two dense layers of width 11776 then a sigmoid router
# with a selection bias over 64 experts of width 1536, 4 a token, weights
# over (their sum + 1e-6), and no shared expert.
_LFM2_PATTERN = ("conv", "conv") + ("full", "conv", "conv", "conv") * 9 \
    + ("full", "conv")
LFM2_24B_A2B = LMConfig(
    vocab_size=65536, hidden_size=2048, layer_types=_LFM2_PATTERN,
    num_heads_per_layer=(32,) * 40, num_kv_heads=8, head_dim=64,
    rope_full=RopeConfig(theta=1e6), dense_layers=(0, 1),
    intermediate_size=11776, num_experts=64, num_experts_per_tok=4,
    moe_intermediate_size=1536, shared_expert_intermediate_size=0,
    routed_scaling_factor=1.0, norm_topk_prob=True, norm_topk_eps=1e-6,
    rms_norm_eps=1e-5, attn_gate="none", qk_norm=True, conv_taps=3,
    router_scoring="sigmoid", router_bias=True)


def sd15_lfm2_expander() -> ModelFamily:
    """SD1.5 with LFM2-24B-A2B as its resident prompt expander, cut in
    depth alone: layers 0-9 (the first of five pipeline stages: both dense
    conv layers and two whole periods of expert layers), every layer whole
    (all 64 experts, all 65536 vocabulary ids)."""
    return dataclasses.replace(
        SD15, name="sd15-lfm2-expand",
        expander=lm_share(LFM2_24B_A2B, layers=10, chips=1, rank=0))


# Tiny expander of gated short-convolution layers: two dense conv layers,
# then full, conv, conv, conv with 4 ungated q/k-normed heads over 2 KV
# heads, 3 taps, 16 experts top-4 by biased sigmoid scores, all held, and
# no shared expert.
TINY_CONV_LM = LMConfig(
    vocab_size=512, hidden_size=32,
    layer_types=("conv", "conv", "full", "conv", "conv", "conv"),
    num_heads_per_layer=(4,) * 6, num_kv_heads=2, head_dim=8,
    rope_full=RopeConfig(theta=1e6), dense_layers=(0, 1),
    intermediate_size=64, num_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=16, shared_expert_intermediate_size=0,
    routed_scaling_factor=1.0, norm_topk_eps=1e-6, rms_norm_eps=1e-5,
    attn_gate="none", qk_norm=True, conv_taps=3, router_scoring="sigmoid",
    router_bias=True)
TINY_CONV_EXPAND = dataclasses.replace(
    TINY, name="tiny-conv-expand",
    expander=lm_share(TINY_CONV_LM, 6, chips=1, rank=0))


def tiny_lfm2_expander() -> ModelFamily:
    """Factory form of :data:`TINY_CONV_EXPAND` (benchmark rehearsals)."""
    return TINY_CONV_EXPAND


# Mellum2-12B-A2.5B-Instruct
# (huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct config.json) at its
# published widths: 28 layers in the pattern sliding, sliding, sliding,
# full (the full layer LAST in the period), 32 query heads of width 128
# over 4 KV heads in both kinds, no gate and no q/k norm; a window of 1024
# under plain RoPE, the full layers under YaRN (factor 16 over 8192), one
# theta and every dim rotated in both; every layer a softmax router over 64
# experts of width 896, 8 a token, renormalised, with no shared expert and
# no dense layer.
_MELLUM2_ROPE_FULL = RopeConfig(
    theta=5e5, factor=16.0, original_max_position=8192, beta_fast=32.0,
    beta_slow=1.0, attention_factor=1.2772588722239782)
MELLUM2_12B_A2_5B = LMConfig(
    vocab_size=98304, hidden_size=2304,
    layer_types=("sliding", "sliding", "sliding", "full") * 7,
    num_heads_per_layer=(32,) * 28, num_kv_heads=4, head_dim=128,
    sliding_window=1024, rope_full=_MELLUM2_ROPE_FULL,
    rope_sliding=RopeConfig(theta=5e5), dense_layers=(),
    intermediate_size=7168, num_experts=64, num_experts_per_tok=8,
    moe_intermediate_size=896, shared_expert_intermediate_size=0,
    routed_scaling_factor=1.0, norm_topk_prob=True, rms_norm_eps=1e-6,
    attn_gate="none")


def sd15_mellum2_expander() -> ModelFamily:
    """SD1.5 with Mellum2-12B-A2.5B-Instruct as its resident prompt
    expander, cut in depth alone: layers 0-7 (the first of four pipeline
    stages of 8, 8, 8 and 4: two whole periods, six window layers and two
    full ones), every layer whole (all 64 experts, all 98304 vocabulary
    ids). Three periods (10.9 GB) leave too little beside SD1.5 for the
    VAE decoder's scratch at a batch of four 512x512 images (3.8 GB)."""
    return dataclasses.replace(
        SD15, name="sd15-mellum2-expand",
        expander=lm_share(MELLUM2_12B_A2_5B, layers=8, chips=1, rank=0))


# Tiny expander of one such period: sliding, sliding, sliding, full with 4
# ungated heads over 2 KV heads, a window of 8 under plain RoPE and YaRN
# over an original length of 16 in the full layer (its ramp lies inside a
# test's positions), 8 experts top-2 by a renormalised softmax, all held,
# no shared expert, no dense layer.
TINY_WINDOW_LM = LMConfig(
    vocab_size=512, hidden_size=32,
    layer_types=("sliding", "sliding", "sliding", "full"),
    num_heads_per_layer=(4,) * 4, num_kv_heads=2, head_dim=8,
    sliding_window=8,
    rope_full=RopeConfig(theta=1e4, factor=4.0, original_max_position=16,
                         beta_fast=4.0, beta_slow=1.0,
                         attention_factor=1.1386294361119891),
    rope_sliding=RopeConfig(theta=1e4), dense_layers=(),
    intermediate_size=64, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=16, shared_expert_intermediate_size=0,
    routed_scaling_factor=1.0, norm_topk_prob=True, rms_norm_eps=1e-6,
    attn_gate="none")
TINY_WINDOW_EXPAND = dataclasses.replace(
    TINY, name="tiny-window-expand",
    expander=lm_share(TINY_WINDOW_LM, 4, chips=1, rank=0))


def tiny_mellum2_expander() -> ModelFamily:
    """Factory form of :data:`TINY_WINDOW_EXPAND` (benchmark rehearsals)."""
    return TINY_WINDOW_EXPAND


# Ouro-2.6B (huggingface.co/ByteDance/Ouro-2.6B config.json; the LoopLM
# family, arXiv 2510.25741) at its published widths, whole: 48 full-attention
# layers of 16 heads of width 128 over 16 KV heads (no grouping), RoPE theta
# 1e6 over every dim, a dense SwiGLU of width 5632 in every layer, an RMS norm
# before AND after each sublayer, vocabulary 49152, head untied; a token
# passes the whole stack FOUR times over the same weights, each pass with
# keys and values of its own, and a learned gate picks the pass the head
# reads (at the published threshold 1: the fourth).
OURO_2_6B = LMConfig(
    vocab_size=49152, hidden_size=2048, layer_types=("full",) * 48,
    num_heads_per_layer=(16,) * 48, num_kv_heads=16, head_dim=128,
    rope_full=RopeConfig(theta=1e6), dense_layers=tuple(range(48)),
    intermediate_size=5632, num_experts=0, num_experts_per_tok=0,
    moe_intermediate_size=0, shared_expert_intermediate_size=0,
    rms_norm_eps=1e-6, attn_gate="none", total_ut_steps=4,
    early_exit_threshold=1.0, post_sublayer_norm=True)


def sd15_ouro_expander() -> ModelFamily:
    """SD1.5 with Ouro-2.6B as its resident prompt expander, held whole:
    all 48 layers, all 16 heads, all 49152 vocabulary ids, all four
    passes."""
    return dataclasses.replace(SD15, name="sd15-ouro-expand",
                               expander=OURO_2_6B)


# Tiny looped expander: 4 full layers of 4 ungrouped heads passed 3 times,
# sandwich norms, the exit gate at the published threshold.
TINY_LOOP_LM = LMConfig(
    vocab_size=512, hidden_size=64, layer_types=("full",) * 4,
    num_heads_per_layer=(4,) * 4, num_kv_heads=4, head_dim=16,
    rope_full=RopeConfig(theta=1e6), dense_layers=(0, 1, 2, 3),
    intermediate_size=128, num_experts=0, num_experts_per_tok=0,
    moe_intermediate_size=0, shared_expert_intermediate_size=0,
    rms_norm_eps=1e-6, attn_gate="none", total_ut_steps=3,
    early_exit_threshold=1.0, post_sublayer_norm=True)
TINY_LOOP_EXPAND = dataclasses.replace(
    TINY, name="tiny-loop-expand", expander=TINY_LOOP_LM)


def tiny_ouro_expander() -> ModelFamily:
    """Factory form of :data:`TINY_LOOP_EXPAND` (benchmark rehearsals)."""
    return TINY_LOOP_EXPAND


# kanana-2-30b-a3b-instruct-2601
# (huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json,
# ``model_type: deepseek_v3``) at its published widths: 48 latent-attention
# layers of 32 heads with NO query latent (``q_lora_rank`` null: one
# ``q_proj``), a cached 512-wide latent and one 64-wide rotated key, keys of
# 128 + 64 and values of 128 a head, plain RoPE theta 1e6 over neighbouring
# pairs (``rope_interleave``), one stream; one dense layer of width 6144
# then a sigmoid router with a selection bias over 128 experts of width 768,
# 6 a token, renormalised over (their sum + 1e-20) at scale 2.448, plus two
# shared experts that are ONE SwiGLU of width 1536.
KANANA_2_30B_A3B = LMConfig(
    vocab_size=128256, hidden_size=2048, layer_types=("latent",) * 48,
    num_heads_per_layer=(32,) * 48,
    rope_full=RopeConfig(theta=1e6, interleaved=True), dense_layers=(0,),
    intermediate_size=6144, num_experts=128, num_experts_per_tok=6,
    moe_intermediate_size=768, shared_expert_intermediate_size=1536,
    routed_scaling_factor=2.448, norm_topk_prob=True, norm_topk_eps=1e-20,
    rms_norm_eps=1e-6, q_lora_rank=0, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    router_scoring="sigmoid", router_bias=True, attn_gate="none")


def sd15_kanana2_expander() -> ModelFamily:
    """SD1.5 with kanana-2-30b-a3b-instruct-2601 as its resident prompt
    expander, cut in depth alone: layers 0-7 (the first of seven pipeline
    stages of 8, 7, 7, 7, 7, 7 and 5: the dense layer and seven expert
    layers), every layer whole (all 128 experts, all 128256 vocabulary
    ids)."""
    return dataclasses.replace(
        SD15, name="sd15-kanana2-expand",
        expander=lm_share(KANANA_2_30B_A3B, layers=8, chips=1, rank=0))


# Tiny expander of that block: four latent layers of 4 heads with no query
# latent (a cached latent of 16 + a rotated key of 8, under keys of 8 + 8
# and values of 8 a head), neighbouring rotary pairs, one stream, one dense
# layer then 16 experts top-4 by biased sigmoid scores over (their sum +
# 1e-20), all held, and a shared expert of twice the routed width.
TINY_KANANA_LM = LMConfig(
    vocab_size=512, hidden_size=32, layer_types=("latent",) * 4,
    num_heads_per_layer=(4,) * 4,
    rope_full=RopeConfig(theta=1e6, interleaved=True), dense_layers=(0,),
    intermediate_size=64, num_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=16, shared_expert_intermediate_size=32,
    routed_scaling_factor=2.448, norm_topk_prob=True, norm_topk_eps=1e-20,
    rms_norm_eps=1e-6, q_lora_rank=0, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, router_scoring="sigmoid",
    router_bias=True, attn_gate="none")
TINY_KANANA_EXPAND = dataclasses.replace(
    TINY, name="tiny-kanana2-expand",
    expander=lm_share(TINY_KANANA_LM, 4, chips=1, rank=0))


def tiny_kanana2_expander() -> ModelFamily:
    """Factory form of :data:`TINY_KANANA_EXPAND` (benchmark rehearsals)."""
    return TINY_KANANA_EXPAND


# GigaChat3.5-432B-A28B
# (huggingface.co/ai-sage/GigaChat3.5-432B-A28B config.json, ``model_type:
# gigachat3_5``) at its published widths: 40 layers of hidden 7168 in the
# pattern linear, linear, linear, latent (``full_attention_layers`` 3, 7,
# ..., 39). A linear layer is a gated delta rule over 32 key and 64 value
# heads of width 128 behind a 4-tap convolution, its read-out normed ``(1 +
# w)`` and gated by ``2 sigmoid(z)``; a latent layer 64 heads through a
# 1536-wide query latent over a cached 512-wide latent and one 64-wide
# rotated key (neighbouring pairs, theta 1e5, YaRN by 8 over 32768 whose
# mscale goes into the softmax), its heads' outputs under one sigmoid a
# channel from a ``g_proj`` of its own. Every norm is ``x_hat * 2
# sigmoid(w)`` and stands before AND after each sublayer; every SwiGLU is
# clamped at 10. Three dense layers of width 18432, then a sigmoid router
# with a selection bias over 256 experts of width 2048, 8 a token over
# (their sum + 1e-20) at scale 2.5, plus one ungated shared expert.
GIGACHAT_3_5 = LMConfig(
    vocab_size=128256, hidden_size=7168,
    layer_types=("linear", "linear", "linear", "latent") * 10,
    num_heads_per_layer=(64,) * 40,
    rope_full=RopeConfig(theta=1e5, factor=8.0, original_max_position=32768,
                         beta_fast=32.0, beta_slow=1.0, attention_factor=1.0,
                         interleaved=True),
    dense_layers=(0, 1, 2), intermediate_size=18432, num_experts=256,
    num_experts_per_tok=8, moe_intermediate_size=2048,
    shared_expert_intermediate_size=2048, routed_scaling_factor=2.5,
    norm_topk_prob=True, norm_topk_eps=1e-20, rms_norm_eps=1e-6,
    attn_gate="element", linear_num_key_heads=32,
    linear_num_value_heads=64, linear_key_head_dim=128,
    linear_value_head_dim=128, linear_conv_kernel=4, q_lora_rank=1536,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_mscale_all_dim=1.0, router_scoring="sigmoid",
    router_bias=True, post_sublayer_norm=True, norm_sigmoid_scale=2.0,
    linear_sigmoid_gate_scale=2.0, swiglu_limit=10.0)


def sd15_gigachat35_expander() -> ModelFamily:
    """SD1.5 with GigaChat3.5-432B-A28B as its resident prompt expander,
    cut to one chip of sixteen that share each layer: published layers 0,
    3, 4, 5 and 6 (the leading dense layers counted once, then one whole
    period after them: latent, linear, linear, linear), experts 0-15 of
    the 256 in each of the four expert layers, vocabulary ids 0-16031 (the
    table and the head lie eight ways)."""
    return dataclasses.replace(
        SD15, name="sd15-gigachat35-expand",
        expander=lm_share(GIGACHAT_3_5, layers=(0, 3, 4, 5, 6), chips=16,
                          rank=0, vocab_chips=8))


# Tiny expander with every kind of that model: a dense linear layer, then
# latent, linear, linear, linear over experts; 2 key heads serving 4 value
# heads of width 8 behind 4 taps; 4 latent heads through a 24-wide query
# latent over a cached 16 + 8, neighbouring rotary pairs under YaRN whose
# ramp lies inside a test's positions; gated norms before and after each
# sublayer, the element-wise attention gate, the sigmoid read-out gate, a
# clamp, 16 experts top-4 by biased sigmoid scores of which 4 are held, one
# shared, a quarter of the vocabulary.
TINY_GIGACHAT35_LM = LMConfig(
    vocab_size=512, hidden_size=32,
    layer_types=("linear", "linear", "linear", "latent") * 2,
    num_heads_per_layer=(4,) * 8,
    rope_full=RopeConfig(theta=1e4, factor=4.0, original_max_position=16,
                         beta_fast=4.0, beta_slow=1.0, attention_factor=1.0,
                         interleaved=True),
    dense_layers=(0, 1, 2), intermediate_size=64, num_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, routed_scaling_factor=2.5,
    norm_topk_prob=True, norm_topk_eps=1e-20, rms_norm_eps=1e-6,
    attn_gate="element", linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8, linear_conv_kernel=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, rope_mscale_all_dim=1.0,
    router_scoring="sigmoid", router_bias=True, post_sublayer_norm=True,
    norm_sigmoid_scale=2.0, linear_sigmoid_gate_scale=2.0, swiglu_limit=10.0)
TINY_GIGACHAT35_EXPAND = dataclasses.replace(
    TINY, name="tiny-gigachat35-expand",
    expander=lm_share(TINY_GIGACHAT35_LM, (0, 3, 4, 5, 6), chips=4, rank=0))


def tiny_gigachat35_expander() -> ModelFamily:
    """Factory form of :data:`TINY_GIGACHAT35_EXPAND` (benchmark
    rehearsals)."""
    return TINY_GIGACHAT35_EXPAND


# Olmo-Hybrid-7B (huggingface.co/allenai/Olmo-Hybrid-7B config.json,
# ``model_type: olmo_hybrid``) at its published widths: 32 layers of hidden
# 3840 in the pattern linear, linear, linear, full. A linear layer norms its
# sublayers' INPUT and is a gated delta rule over 30 key and 30 value heads
# of widths 96 and 192 behind a 4-tap convolution, whose write strength is
# ``2 sigmoid(b)`` (``linear_allow_neg_eigval``). A full layer norms its
# sublayers' OUTPUT and attends with 30 heads of 128 over 30 KV heads (no
# grouping), no rotary table (``rope_theta`` null), no gate, queries and
# keys normed over the whole projection. A dense SwiGLU of 11008 in every
# layer, vocabulary 100352, head untied.
OLMO_HYBRID_7B = LMConfig(
    vocab_size=100352, hidden_size=3840,
    layer_types=("linear", "linear", "linear", "full") * 8,
    num_heads_per_layer=(30,) * 32, num_kv_heads=30, head_dim=128,
    rope_full=None, dense_layers=tuple(range(32)), intermediate_size=11008,
    num_experts=0, num_experts_per_tok=0, moe_intermediate_size=0,
    shared_expert_intermediate_size=0, rms_norm_eps=1e-6, attn_gate="none",
    qk_norm=True, qk_norm_extent="projection", linear_num_key_heads=30,
    linear_num_value_heads=30, linear_key_head_dim=96,
    linear_value_head_dim=192, linear_conv_kernel=4, linear_write_scale=2.0,
    norm_placement=("pre", "pre", "pre", "post") * 8)


def sd15_olmo_hybrid_expander() -> ModelFamily:
    """SD1.5 with Olmo-Hybrid-7B as its resident prompt expander, cut in
    depth alone: layers 0-15 (the first of two pipeline stages of 16, four
    whole periods), every layer whole, all 100352 vocabulary ids."""
    return dataclasses.replace(
        SD15, name="sd15-olmo-hybrid-expand",
        expander=lm_share(OLMO_HYBRID_7B, layers=16, chips=1, rank=0))


# Tiny expander of that stack: two periods of linear, linear, linear, full;
# 3 key heads serving 3 value heads of widths 6 and 10 (unequal, neither a
# power of two) behind 4 taps, written at up to 2; 3 ungrouped heads of 8
# that rotate nothing and norm queries and keys over the whole projection;
# linear layers normed before their sublayers, full layers after.
TINY_OLMO_HYBRID_LM = LMConfig(
    vocab_size=512, hidden_size=24,
    layer_types=("linear", "linear", "linear", "full") * 2,
    num_heads_per_layer=(3,) * 8, num_kv_heads=3, head_dim=8,
    rope_full=None, dense_layers=tuple(range(8)), intermediate_size=48,
    num_experts=0, num_experts_per_tok=0, moe_intermediate_size=0,
    shared_expert_intermediate_size=0, rms_norm_eps=1e-6, attn_gate="none",
    qk_norm=True, qk_norm_extent="projection", linear_num_key_heads=3,
    linear_num_value_heads=3, linear_key_head_dim=6,
    linear_value_head_dim=10, linear_conv_kernel=4, linear_write_scale=2.0,
    norm_placement=("pre", "pre", "pre", "post") * 2)
TINY_OLMO_HYBRID_EXPAND = dataclasses.replace(
    TINY, name="tiny-olmo-hybrid-expand", expander=TINY_OLMO_HYBRID_LM)


def tiny_olmo_hybrid_expander() -> ModelFamily:
    """Factory form of :data:`TINY_OLMO_HYBRID_EXPAND` (benchmark
    rehearsals)."""
    return TINY_OLMO_HYBRID_EXPAND


# Falcon-H1-34B-Instruct (huggingface.co/tiiuae/Falcon-H1-34B-Instruct
# config.json, ``model_type: falcon_h1``) at its published widths: 72 layers
# of hidden 5120, every one alike: attention of 20 heads over 4 KV heads of
# 128 (rotated over the whole head at theta 1e11, no gate, no query or key
# norm) AND a selective state-space mixer of 32 heads of 128 over states 256
# wide (B and C shared by groups of 16 heads, a 4-tap convolution with bias
# over the 5120 channels [x | B | C]) side by side under ONE input norm,
# then a dense SwiGLU of 21504; vocabulary 261120, head untied; fourteen
# forward multipliers (one of them, attention's input, is 1).
FALCON_H1_34B = LMConfig(
    vocab_size=261120, hidden_size=5120, layer_types=("full+ssm",) * 72,
    num_heads_per_layer=(20,) * 72, num_kv_heads=4, head_dim=128,
    rope_full=RopeConfig(theta=1e11), dense_layers=tuple(range(72)),
    intermediate_size=21504, num_experts=0, num_experts_per_tok=0,
    moe_intermediate_size=0, shared_expert_intermediate_size=0,
    rms_norm_eps=1e-5, attn_gate="none", ssm_num_heads=32,
    ssm_head_dim=128, ssm_state_size=256, ssm_num_groups=2,
    ssm_conv_kernel=4, ssm_conv_bias=True, ssm_chunk=128,
    embedding_multiplier=5.656854249492381, logit_multiplier=0.0078125,
    key_multiplier=0.011048543456039804,
    mixer_multipliers=(("full", 1.0, 0.0375),
                       ("ssm", 0.25, 0.08838834764831845)),
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284))


def sd15_falcon_h1_expander() -> ModelFamily:
    """SD1.5 with Falcon-H1-34B-Instruct as its resident prompt expander,
    cut in depth and in the vocabulary: layers 0-8 (the first of eight
    pipeline stages of nine), every layer whole, vocabulary ids 0-65279
    (the table and the head lie four ways)."""
    return dataclasses.replace(
        SD15, name="sd15-falcon-h1-expand",
        expander=lm_share(FALCON_H1_34B, layers=9, chips=1, rank=0,
                          vocab_chips=4))


# Tiny expander of that stack: three layers of attention (6 heads of 8 over
# 2 KV heads, a group of 3) beside a state-space mixer (6 heads of 5 over
# states 7 wide in 3 groups, a 4-tap convolution with bias over 72
# channels) under one norm; a chunk of 16, so a prefill runs several; every
# one of the fourteen multipliers off 1.
TINY_FALCON_H1_LM = LMConfig(
    vocab_size=512, hidden_size=24, layer_types=("full+ssm",) * 3,
    num_heads_per_layer=(6,) * 3, num_kv_heads=2, head_dim=8,
    rope_full=RopeConfig(theta=1e11), dense_layers=(0, 1, 2),
    intermediate_size=48, num_experts=0, num_experts_per_tok=0,
    moe_intermediate_size=0, shared_expert_intermediate_size=0,
    rms_norm_eps=1e-5, attn_gate="none", ssm_num_heads=6, ssm_head_dim=5,
    ssm_state_size=7, ssm_num_groups=3, ssm_conv_kernel=4,
    ssm_conv_bias=True, ssm_chunk=16, embedding_multiplier=2.5,
    logit_multiplier=0.5, key_multiplier=0.6,
    mixer_multipliers=(("full", 0.8, 0.7), ("ssm", 0.5, 1.3)),
    ssm_multipliers=(0.7, 1.4, 0.8, 1.2, 0.6), mlp_multipliers=(0.9, 0.75))
TINY_FALCON_H1_EXPAND = dataclasses.replace(
    TINY, name="tiny-falcon-h1-expand", expander=TINY_FALCON_H1_LM)


def tiny_falcon_h1_expander() -> ModelFamily:
    """Factory form of :data:`TINY_FALCON_H1_EXPAND` (benchmark
    rehearsals)."""
    return TINY_FALCON_H1_EXPAND


# LongCat-Flash-Chat (huggingface.co/meituan-longcat/LongCat-Flash-Chat
# config.json) at its published widths: 28 shortcut-connected double layers
# of hidden 6144, each TWO entries of these lists (``moe_shortcut``): an
# expert layer whose shared expert is the first dense SwiGLU of 12288 (it
# reads the router's input and is added at that residual) and a dense layer
# of 12288, both latent attention of 64 heads through a 1536-wide query
# latent over a cached 512-wide latent and one 64-wide rotated key (keys of
# 128 + 64, values of 128 a head, plain RoPE theta 1e7), queries scaled by
# (6144 / 1536)^0.5 and the normed latent by (6144 / 512)^0.5. ONE router a
# double layer: a softmax over 768 outputs, 512 experts of width 2048 and
# 256 zero-compute identity experts, 12 a token chosen under a selection
# bias, weighed 6 p without it and not renormalised; its sum lands after
# the second dense SwiGLU.
LONGCAT_FLASH_CHAT = LMConfig(
    vocab_size=131072, hidden_size=6144, layer_types=("latent",) * 56,
    num_heads_per_layer=(64,) * 56, rope_full=RopeConfig(theta=1e7),
    dense_layers=tuple(range(1, 56, 2)), intermediate_size=12288,
    num_experts=768, zero_experts=256, num_experts_per_tok=12,
    moe_intermediate_size=2048, shared_expert_intermediate_size=12288,
    routed_scaling_factor=6.0, norm_topk_prob=False, rms_norm_eps=1e-5,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, router_scoring="softmax",
    router_bias=True, attn_gate="none", moe_shortcut=True,
    latent_q_scale=(6144 / 1536) ** 0.5, latent_kv_scale=(6144 / 512) ** 0.5)


def sd15_longcat_flash_expander() -> ModelFamily:
    """SD1.5 with LongCat-Flash-Chat as its resident prompt expander, cut
    to one chip of 32 that share each layer: published double layers 0-3
    (eight entries; one of seven pipeline stages of four), experts 0-15 of
    the 512 that have kernels in each of the four routers (every identity
    expert is held wherever the token lives), vocabulary ids 0-16383 (the
    table and the head lie eight ways)."""
    return dataclasses.replace(
        SD15, name="sd15-longcat-flash-expand",
        expander=lm_share(LONGCAT_FLASH_CHAT, layers=8, chips=32, rank=0,
                          vocab_chips=8))


# Tiny expander of that topology: two double layers (four entries) of 4
# latent heads through a 24-wide query latent over a cached 16 + 8, both
# latent scales off 1, a router over 16 experts and 8 identity experts, 4 a
# token by biased softmax scores at scale 6 without renormalising, 4 of the
# 16 held, a shared expert and a dense MLP of one width, a quarter of the
# vocabulary.
TINY_LONGCAT_FLASH_LM = LMConfig(
    vocab_size=512, hidden_size=32, layer_types=("latent",) * 4,
    num_heads_per_layer=(4,) * 4, rope_full=RopeConfig(theta=1e7),
    dense_layers=(1, 3), intermediate_size=64, num_experts=24,
    zero_experts=8, num_experts_per_tok=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=64, routed_scaling_factor=6.0,
    norm_topk_prob=False, rms_norm_eps=1e-5, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    router_scoring="softmax", router_bias=True, attn_gate="none",
    moe_shortcut=True, latent_q_scale=(32 / 24) ** 0.5,
    latent_kv_scale=(32 / 16) ** 0.5)
TINY_LONGCAT_FLASH_EXPAND = dataclasses.replace(
    TINY, name="tiny-longcat-flash-expand",
    expander=lm_share(TINY_LONGCAT_FLASH_LM, 4, chips=4, rank=0))


def tiny_longcat_flash_expander() -> ModelFamily:
    """Factory form of :data:`TINY_LONGCAT_FLASH_EXPAND` (benchmark
    rehearsals)."""
    return TINY_LONGCAT_FLASH_EXPAND


# granite-4.0-h-small (huggingface.co/ibm-granite/granite-4.0-h-small
# config.json, ``model_type: granitemoehybrid``) at its published widths: 40
# layers of hidden 4096, nine selective state-space mixers ALONE to one
# attention (attention at 5, 15, 25, 35): a state-space layer is 128 heads
# of 64 over states 128 wide, B and C one group, a 4-tap convolution with
# bias over the 8448 channels [x | B | C], the read-out gated and then
# normed over all 8192 channels; an attention layer 32 heads over 8 KV
# heads of 128, NOT rotated (``nope``), its scores times 1/128. Every layer
# then a softmax router over 72 experts of width 768, ten a token
# renormalised, beside an ungated shared expert of 1536. The table's rows
# times 12, what each sublayer adds to the residual times 0.22, the logits
# (the table again: the head is tied) over 16; vocabulary 100352.
_GRANITE_PERIOD = ("ssm",) * 5 + ("full",) + ("ssm",) * 4
GRANITE_4_H_SMALL = LMConfig(
    vocab_size=100352, hidden_size=4096, layer_types=_GRANITE_PERIOD * 4,
    num_heads_per_layer=(32,) * 40, num_kv_heads=8, head_dim=128,
    rope_full=None, attention_scale=0.0078125, attn_gate="none",
    qk_norm=False, dense_layers=(), intermediate_size=768, num_experts=72,
    num_experts_per_tok=10, moe_intermediate_size=768,
    shared_expert_intermediate_size=1536, shared_expert_gate=False,
    router_scoring="softmax", router_bias=False, norm_topk_prob=True,
    routed_scaling_factor=1.0, rms_norm_eps=1e-5, ssm_num_heads=128,
    ssm_head_dim=64, ssm_state_size=128, ssm_num_groups=1,
    ssm_conv_kernel=4, ssm_conv_bias=True, ssm_norm_before_gate=False,
    ssm_chunk=256, embedding_multiplier=12.0, logit_multiplier=0.0625,
    residual_multiplier=0.22, tied_head=True)


def sd15_granite_h_expander() -> ModelFamily:
    """SD1.5 with granite-4.0-h-small as its resident prompt expander, cut
    to one chip of a pair that share each layer: layers 0-9 (one period of
    the pattern, five state-space layers, the attention, four more; one of
    four pipeline stages), experts 0-35 of every layer's 72, vocabulary
    ids 0-50175 (the first half of the table, which is also the head)."""
    return dataclasses.replace(
        SD15, name="sd15-granite-h-expand",
        expander=lm_share(GRANITE_4_H_SMALL, layers=10, chips=2, rank=0,
                          vocab_chips=2))


# Tiny expander of that stack: two state-space layers (6 heads of 5 over
# states 7 wide, one group, a 4-tap convolution with bias over 44 channels;
# a chunk of 16, so a prefill runs several), an unrotated attention of 4
# heads over 2 KV heads whose scores are NOT scaled by head_dim^-0.5, one
# more state-space layer; every layer a softmax router over 12 experts, 3 a
# token renormalised, beside a shared expert; the three multipliers and the
# tied head all on; 4 of the 12 experts and half the vocabulary held.
TINY_GRANITE_H_LM = LMConfig(
    vocab_size=512, hidden_size=32,
    layer_types=("ssm", "ssm", "full", "ssm"),
    num_heads_per_layer=(4,) * 4, num_kv_heads=2, head_dim=8,
    rope_full=None, attention_scale=0.2, attn_gate="none", dense_layers=(),
    intermediate_size=16, num_experts=12, num_experts_per_tok=3,
    moe_intermediate_size=16, shared_expert_intermediate_size=24,
    router_scoring="softmax", norm_topk_prob=True,
    routed_scaling_factor=1.0, rms_norm_eps=1e-5, ssm_num_heads=6,
    ssm_head_dim=5, ssm_state_size=7, ssm_num_groups=1, ssm_conv_kernel=4,
    ssm_conv_bias=True, ssm_chunk=16, embedding_multiplier=3.0,
    logit_multiplier=0.25, residual_multiplier=0.6, tied_head=True)
TINY_GRANITE_H_EXPAND = dataclasses.replace(
    TINY, name="tiny-granite-h-expand",
    expander=lm_share(TINY_GRANITE_H_LM, 4, chips=3, rank=0, vocab_chips=2))


def tiny_granite_h_expander() -> ModelFamily:
    """Factory form of :data:`TINY_GRANITE_H_EXPAND` (benchmark
    rehearsals)."""
    return TINY_GRANITE_H_EXPAND


FAMILIES = {f.name: f for f in (SD15, SD21, SD21_BASE, SDXL_BASE,
                                SDXL_REFINER, SD15_INPAINT, SD2_INPAINT,
                                SDXL_INPAINT, TINY, TINY_XL, TINY_REFINER,
                                TINY_V, TINY_INPAINT)}

"""Bytes a decode STEP of the prompt expander (models/lm.py, any
``LMConfig``) must move through HBM, from shapes alone. The configuration
is walked layer by layer, as the program's own ``cache/kv.py:state_bytes``
walks it, so a new configuration brings no file of its own: what a layer
needs follows from its kind (``layer_types``), its heads and whether its
MLP is dense or a router over experts. A step carries ``sequences``
sequences (the images of one request, one token each, all at one
position), forked from one prefill at ``forked_at``, and needs:

- every fixed weight it streams, ONCE a step however many sequences it
  carries (:func:`mixer_bytes`, :func:`mlp_bytes`): a full or sliding
  layer's ``q_proj`` (twice as wide where the gate is the second half of
  its columns), ``k_proj``, ``v_proj``, ``o_proj`` and a per-head gate's
  ``g_proj``; a latent layer's ``q_proj`` (or ``q_a_proj`` and
  ``q_b_proj``), ``kv_a_proj_with_mqa``, ``kv_b_proj``, ``o_proj`` and its
  gate's ``g_proj``; a linear layer's ``qkvz_proj``, ``ba_proj``,
  ``out_proj``, taps, ``A_log`` and ``dt_bias``; a conv layer's
  ``in_proj``, taps and ``out_proj``; the residual streams' mixers'
  ``phi``; a dense layer's SwiGLU; an expert layer's router, selection
  bias, shared expert and its gate;
- a looped stack's weights once a PASS (``total_ut_steps`` times a step:
  the stack cannot stay on the chip between passes), the held head once a
  step, a table row a sequence;
- the kernels of each DISTINCT held expert the step's rows chose, once:
  the program's ``experts_read`` over its ``decode_steps``
  (``serving.expander``, counted on the device beside the load), never the
  picks: an expert is streamed once however many rows chose it. At one
  sequence a step the two are the same number;
- the rows of a full, sliding or latent layer (:func:`rows_needed`): what
  lies BEFORE THE FORK once a step for all sequences (a fork copies
  nothing that has positions, cache/kv.py:fork, and the shared rows are
  the query rows' one operand), each sequence's OWN rows behind it once a
  sequence. A sliding layer needs no row that has left the window. One
  sequence is a fork of itself: ``position + 1`` rows, as ever. A looped
  model's position is a row of every pass;
- a linear or conv layer's state and kept rows READ AND WRITTEN once a
  sequence (float32; no positions, so a fork gave every sequence a copy
  and nothing of it can be read once for all).

Norm weights, activations, the key, value and latent rows written, the
router's scores and the sort of the rows by expert are left out: the count
may be under what the program moves, never over it, and the share a
reader makes of it is under 100 by construction. A program that read whole
buffers where a step needs ``forked_at + i + 1`` rows of them moves more
and reads LOWER here, not higher.

``tests/test_bytes_lm.py`` holds this walker to the eight per-architecture
modules it replaced (PR 58), byte for byte, and names every term in which
it differs from one.
"""

STATE_ITEMSIZE = 4      # recurrent states and kept inputs are float32
#: the layer kinds this walker can count. Another kind is counted by
#: nobody: a ``benchmark`` PR adds it here, to each of the three functions
#: below that branch on a kind, and a hand count of it to the tests
KINDS = ("full", "sliding", "latent", "linear", "conv")


def known(kind: str) -> str:
    """``kind``, or ValueError: an unknown kind counted as some other would
    make a plausible share of a roofline out of the wrong bytes."""
    if kind not in KINDS:
        raise ValueError(f"bytes_lm cannot count a layer of kind {kind!r}: "
                         f"it knows {', '.join(KINDS)}")
    return kind


def mixer_bytes(cfg, layer: int, itemsize: int = 2) -> int:
    """The weights of one layer's token mixer, and of the residual
    streams' mixers around both its sublayers."""
    d, kind = cfg.hidden_size, known(cfg.layer_types[layer])
    heads = cfg.num_heads_per_layer[layer]
    if kind == "linear":
        values = cfg.linear_num_value_heads * cfg.linear_value_head_dim
        channels = cfg.linear_conv_channels
        total = (d * (channels + values)                    # qkvz_proj
                 + d * 2 * cfg.linear_num_value_heads       # ba_proj
                 + values * d                               # out_proj
                 + cfg.linear_conv_kernel * channels        # the taps
                 + 2 * cfg.linear_num_value_heads)          # A_log, dt_bias
    elif kind == "conv":
        total = d * 3 * d + cfg.conv_taps * d + d * d
    elif kind == "latent":
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        query = (cfg.q_lora_rank * (d + heads * (nope + rope))
                 if cfg.q_lora_rank else d * heads * (nope + rope))
        gate = {"element": d * heads * cfg.v_head_dim,
                "head": d * heads}.get(cfg.attn_gate, 0)
        total = (query + d * (cfg.kv_lora_rank + rope)
                 + cfg.kv_lora_rank * heads * (nope + cfg.v_head_dim)
                 + heads * cfg.v_head_dim * d + gate)
    else:                                   # full, sliding
        dim, kv = cfg.head_dim, cfg.num_kv_heads
        wide = 2 if cfg.attn_gate == "element" else 1
        total = (d * heads * dim * wide + 2 * d * kv * dim + heads * dim * d
                 + (d * heads if cfg.attn_gate == "head" else 0))
    n = cfg.residual_streams
    if n > 1:
        total += 2 * n * d * (n * n + 2 * n)
    return total * itemsize


def mlp_bytes(cfg, layer: int, itemsize: int = 2) -> int:
    """What of one layer's MLP every step reads whatever its rows chose:
    a dense SwiGLU whole, of an expert layer the router, its selection
    bias, the shared expert and its gate."""
    d = cfg.hidden_size
    if layer in cfg.dense_layers:
        return 3 * d * cfg.intermediate_size * itemsize
    return (d * cfg.num_experts
            + (cfg.num_experts if cfg.router_bias else 0)
            + 3 * d * cfg.shared_expert_intermediate_size
            + (d if cfg.shared_expert_gate
               and cfg.shared_expert_intermediate_size else 0)) * itemsize


def stack_bytes(cfg, itemsize: int = 2) -> int:
    """Every layer's fixed weights once: what ONE pass streams."""
    return sum(mixer_bytes(cfg, layer, itemsize)
               + mlp_bytes(cfg, layer, itemsize)
               for layer in range(cfg.num_layers))


def head_bytes(cfg, itemsize: int = 2) -> int:
    return cfg.hidden_size * cfg.vocab[1] * itemsize


def fixed_bytes(cfg, sequences: float = 1.0, itemsize: int = 2) -> float:
    """Weights a step reads whatever its rows chose: the stack a pass, the
    head once, a table row a sequence."""
    return (cfg.total_ut_steps * stack_bytes(cfg, itemsize)
            + head_bytes(cfg, itemsize)
            + sequences * cfg.hidden_size * itemsize)


def expert_bytes(cfg, itemsize: int = 2) -> int:
    """One routed expert's three kernels."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize


def row_bytes(cfg, kind: str, itemsize: int = 2) -> int:
    """One position of one layer of ``kind``: keys and values of every
    pass, or a latent and its rotated key; a kind without positions: 0."""
    if known(kind) == "latent":
        return cfg.latent_width * itemsize
    if kind in ("full", "sliding"):
        return (cfg.total_ut_steps * 2 * cfg.num_kv_heads * cfg.head_dim
                * itemsize)
    return 0


def state_bytes(cfg, kind: str) -> int:
    """One sequence's state in one layer of ``kind`` that keeps no
    positions: a linear layer's recurrent state and kept convolution
    inputs, a conv layer's kept rows. What a step reads, and writes
    again."""
    if known(kind) == "linear":
        return STATE_ITEMSIZE * (
            cfg.linear_num_value_heads * cfg.linear_key_head_dim
            * cfg.linear_value_head_dim
            + (cfg.linear_conv_kernel - 1) * cfg.linear_conv_channels)
    if kind == "conv":
        return STATE_ITEMSIZE * (cfg.conv_taps - 1) * cfg.hidden_size
    return 0


def rows_needed(cfg, kind: str, forked_at: int, step: int):
    """``(shared, own)``: rows of one layer of ``kind`` that step ``step``
    (0 the first, at position ``forked_at``) needs of what lies before the
    fork, once for all sequences, and of a sequence's own rows, once a
    sequence. A sliding layer attends its last ``sliding_window``
    positions, its own first."""
    own = step + 1
    if kind == "sliding":
        own = min(own, cfg.sliding_window)
        return min(forked_at, cfg.sliding_window - own), own
    return forked_at, own


def step_bytes(cfg, forked_at: int, step: int, experts_read: float,
               sequences: float = 1.0, itemsize: int = 2) -> dict:
    """One decode step's bytes by term: ``weights``, ``experts``,
    ``rows_shared``, ``rows_own``, ``states``."""
    out = {"weights": fixed_bytes(cfg, sequences, itemsize),
           "experts": experts_read * expert_bytes(cfg, itemsize),
           "rows_shared": 0.0, "rows_own": 0.0, "states": 0.0}
    for kind in cfg.layer_types:
        shared, own = rows_needed(cfg, kind, forked_at, step)
        row = row_bytes(cfg, kind, itemsize)
        out["rows_shared"] += shared * row
        out["rows_own"] += sequences * own * row
        out["states"] += 2 * sequences * state_bytes(cfg, kind)
    return out


def decode_bytes(cfg, first_position: int, steps: int,
                 experts_read_per_step: float, sequences: float = 1.0,
                 itemsize: int = 2, first_step: int = 0) -> float:
    """Bytes ``steps`` decode steps of ``sequences`` sequences forked at
    ``first_position`` need, from step ``first_step`` on (0: the whole
    decode; a later launch of the decode executable starts further on);
    ``experts_read_per_step`` is how many distinct held experts a step's
    rows chose, summed over the expert layers (the program's
    ``experts_read`` over its ``decode_steps``)."""
    return sum(sum(step_bytes(cfg, first_position, step,
                              experts_read_per_step, sequences,
                              itemsize).values())
               for step in range(first_step, first_step + steps))

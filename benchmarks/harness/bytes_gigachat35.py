"""Bytes a decode STEP of the prompt expander (models/lm.py, a
GigaChat3.5-432B-A28B share: gated delta-rule layers beside gated latent
attention, one dense layer, then a router over a held share of the experts
plus a shared expert) must read and write in HBM when the step carries
several sequences FORKED from one prefill (the images of one request, one
token each, all at one position), from shapes alone:

- the weights every step needs, ONCE a step however many sequences it
  carries: a linear layer's ``qkvz_proj``, ``ba_proj`` and ``out_proj``; a
  latent layer's ``q_a_proj``, ``q_b_proj``, ``kv_a_proj_with_mqa``,
  ``kv_b_proj``, ``g_proj`` and ``o_proj``; the dense layer's SwiGLU; each
  expert layer's router and shared expert; the head (a table row a
  sequence is left out);
- the kernels of the DISTINCT held experts the step's rows chose: the
  kernel reads an expert once however many rows chose it, so the count is
  the program's ``experts_read`` (``serving.expander``; counted on the
  device beside the load), never the picks: four sequences make 32 picks a
  layer of which 2 fall on the 16 held here on average, 1.91 of them
  distinct under even routing;
- the latents the step attends: what lies before the fork (the shared
  range: ``forked_at`` rows of ``kv_lora_rank + qk_rope_head_dim`` a latent
  layer) ONCE a step for all sequences, and each sequence's own rows behind
  it (``position + 1 - forked_at``) once a sequence. NOT ``position + 1``
  rows a sequence: a fork copies no latent (cache/kv.py:fork) and the
  shared rows are the query rows' one operand (ops/attention.py:
  attend_two_ranges);
- each linear layer's recurrent state READ AND WRITTEN once a sequence
  (``2 x value heads x key width x value width x 4`` B: the state is
  float32, it has no positions, and a fork gave every sequence a copy of
  its own, so nothing of it can be read once for all) with its kept
  convolution rows read and written beside it.

Norm weights, the selection bias, the convolution's taps, ``A_log`` and
``dt_bias``, activations, the table's rows, the latent rows written and the
router's scores are left out: the count may be under what the program
moves, never over it. (PERF.md section 7 (i): two older counts, of
configurations whose steps carry ONE sequence, count ``position + 1`` rows
a sequence and every held pick; this one follows bytes_kanana2.py and
counts neither.)
"""


def linear_layer_bytes(cfg, itemsize: int = 2) -> int:
    """qkvz_proj, ba_proj, out_proj."""
    d = cfg.hidden_size
    values = cfg.linear_num_value_heads * cfg.linear_value_head_dim
    return (d * (cfg.linear_conv_channels + values)
            + d * 2 * cfg.linear_num_value_heads + values * d) * itemsize


def latent_layer_bytes(cfg, layer: int, itemsize: int = 2) -> int:
    """q_proj (or q_a_proj and q_b_proj), kv_a_proj_with_mqa, kv_b_proj,
    o_proj, and the output gate's g_proj where the layer has one."""
    d, heads = cfg.hidden_size, cfg.num_heads_per_layer[layer]
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    query = d * heads * (nope + rope) if not cfg.q_lora_rank else \
        cfg.q_lora_rank * (d + heads * (nope + rope))
    gate = {"element": d * heads * cfg.v_head_dim,
            "head": d * heads}.get(cfg.attn_gate, 0)
    return (query + d * (cfg.kv_lora_rank + rope)
            + cfg.kv_lora_rank * heads * (nope + cfg.v_head_dim)
            + heads * cfg.v_head_dim * d + gate) * itemsize


def fixed_bytes(cfg, itemsize: int = 2) -> int:
    """Weights a step reads whatever its rows chose: once a step."""
    d = cfg.hidden_size
    total = d * cfg.vocab[1] * itemsize                  # the head
    for layer, kind in enumerate(cfg.layer_types):
        total += linear_layer_bytes(cfg, itemsize) if kind == "linear" \
            else latent_layer_bytes(cfg, layer, itemsize)
        if layer in cfg.dense_layers:
            total += 3 * d * cfg.intermediate_size * itemsize
        else:     # router, shared expert
            total += (d * cfg.num_experts
                      + 3 * d * cfg.shared_expert_intermediate_size
                      ) * itemsize
    return total


def expert_bytes(cfg, itemsize: int = 2) -> int:
    """One routed expert's three kernels."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize


def row_bytes(cfg, itemsize: int = 2) -> int:
    """One position's latents over the latent layers."""
    return len(cfg.layers_of("latent")) * cfg.latent_width * itemsize


def state_bytes(cfg) -> int:
    """One sequence's recurrent states and kept rows over the linear
    layers, float32: what a step reads, and writes again."""
    return len(cfg.layers_of("linear")) * 4 * (
        cfg.linear_num_value_heads * cfg.linear_key_head_dim
        * cfg.linear_value_head_dim
        + (cfg.linear_conv_kernel - 1) * cfg.linear_conv_channels)


def decode_bytes(cfg, first_position: int, steps: int,
                 experts_read_per_step: float, sequences: float = 1.0,
                 itemsize: int = 2) -> float:
    """Bytes ``steps`` decode steps of ``sequences`` sequences forked at
    ``first_position`` need; ``experts_read_per_step`` is how many distinct
    held experts a step's rows chose, summed over the expert layers (the
    program's ``experts_read`` over its ``decode_steps``). Step ``i``
    attends ``first_position`` shared rows once and ``i + 1`` own rows a
    sequence, and reads and writes every sequence's states."""
    shared = steps * first_position
    own = sequences * steps * (steps + 1) / 2
    return (steps * (fixed_bytes(cfg, itemsize)
                     + experts_read_per_step * expert_bytes(cfg, itemsize)
                     + 2 * sequences * state_bytes(cfg))
            + (shared + own) * row_bytes(cfg, itemsize))

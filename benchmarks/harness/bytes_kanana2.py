"""Bytes a decode STEP of the prompt expander (models/lm.py, a
kanana-2-30b-a3b share: latent attention with no query latent, one dense
layer, then a router over experts that are all held, plus a shared expert)
must read from HBM when the step carries several sequences FORKED from one
prefill (the images of one request, one token each, all at one position),
from shapes alone:

- the weights every step needs, ONCE a step however many sequences it
  carries: each layer's four attention kernels (``q_proj``,
  ``kv_a_proj_with_mqa``, ``kv_b_proj``, ``o_proj``), the dense layer's
  SwiGLU, each expert layer's router and shared expert, the head (a table
  row a sequence is left out);
- the kernels of the DISTINCT experts the step's rows chose: the kernel
  reads an expert once however many rows chose it, so the count is the
  program's ``experts_read`` (``serving.expander``; counted on the device
  beside the load), never the picks: four sequences make 24 picks a layer
  and read about 22.4 of 128 experts under even routing;
- the latents the step attends: what lies before the fork (the shared
  range: ``forked_at`` rows of ``kv_lora_rank + qk_rope_head_dim`` a layer)
  ONCE a step for all sequences, and each sequence's own rows behind it
  (``position + 1 - forked_at``) once a sequence. NOT ``position + 1``
  rows a sequence: a fork copies nothing (cache/kv.py:fork) and the shared
  rows are the query rows' one operand (ops/attention.py:
  attend_two_ranges), so a count a sequence would read over 100 % of what
  the program can be made to move.

Norm scales, the selection bias, activations, the table's rows, the latent
rows written and the router's scores are left out: the count may be under
what the program moves, never over it.
"""


def latent_layer_bytes(cfg, layer: int, itemsize: int = 2) -> int:
    """q_proj (or q_a_proj and q_b_proj), kv_a_proj_with_mqa, kv_b_proj,
    o_proj."""
    d, heads = cfg.hidden_size, cfg.num_heads_per_layer[layer]
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    query = d * heads * (nope + rope) if not cfg.q_lora_rank else \
        cfg.q_lora_rank * (d + heads * (nope + rope))
    return (query + d * (cfg.kv_lora_rank + rope)
            + cfg.kv_lora_rank * heads * (nope + cfg.v_head_dim)
            + heads * cfg.v_head_dim * d) * itemsize


def fixed_bytes(cfg, itemsize: int = 2) -> int:
    """Weights a step reads whatever its rows chose: once a step."""
    d = cfg.hidden_size
    total = d * cfg.vocab[1] * itemsize                  # the head
    for layer in range(cfg.num_layers):
        total += latent_layer_bytes(cfg, layer, itemsize)
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
    """One position's latents over all layers."""
    return cfg.num_layers * cfg.latent_width * itemsize


def decode_bytes(cfg, first_position: int, steps: int,
                 experts_read_per_step: float, sequences: float = 1.0,
                 itemsize: int = 2) -> float:
    """Bytes ``steps`` decode steps of ``sequences`` sequences forked at
    ``first_position`` need; ``experts_read_per_step`` is how many distinct
    held experts a step's rows chose, summed over the expert layers (the
    program's ``experts_read`` over its ``decode_steps``). Step ``i``
    attends ``first_position`` shared rows once and ``i + 1`` own rows a
    sequence."""
    shared = steps * first_position
    own = sequences * steps * (steps + 1) / 2
    return (steps * (fixed_bytes(cfg, itemsize)
                     + experts_read_per_step * expert_bytes(cfg, itemsize))
            + (shared + own) * row_bytes(cfg, itemsize))

"""Bytes a decode STEP of the prompt expander (models/lm.py, a Mellum2
share) must read from HBM when the step carries several sequences (the
images of one request, one token each, all at one position), from shapes
alone:

- the weights every step needs, ONCE a step however many sequences it
  carries: each layer's four attention projections and its router, the
  head (a table row a sequence is left out);
- the kernels of the DISTINCT experts the step's rows chose: the grouped
  product reads an expert once however many rows chose it, so the count
  is the program's ``experts_read`` (``serving.expander``; counted on the
  device beside the load), never the picks: four sequences make 32 picks a
  layer and read about 26.5 of 64 experts under even routing, and picks x
  the expert's bytes would count a fifth too much;
- the keys and values every sequence attends: ``position + 1`` rows in a
  full layer, ``min(position + 1, sliding_window)`` in a window layer,
  times sequences (a fork copies the rows, it does not share them).

Norm scales, activations, the table's rows, the key and value rows
written, the router's scores and the sort of the rows by expert are left
out: the count may be under what the program moves, never over it.
"""


def attention_layer_bytes(cfg, layer: int, itemsize: int = 2) -> int:
    """q_proj, k_proj, v_proj, o_proj of an ungated attention."""
    d, heads = cfg.hidden_size, cfg.num_heads_per_layer[layer]
    kv, dim = cfg.num_kv_heads, cfg.head_dim
    return (2 * d * heads * dim + 2 * d * kv * dim) * itemsize


def layer_fixed_bytes(cfg, layer: int, itemsize: int = 2) -> int:
    """What of one layer every step reads: attention and the router."""
    return attention_layer_bytes(cfg, layer, itemsize) \
        + cfg.hidden_size * cfg.num_experts * itemsize


def fixed_bytes(cfg, itemsize: int = 2) -> int:
    """Weights a step reads whatever its rows chose: once a step."""
    head = cfg.hidden_size * cfg.vocab[1] * itemsize
    return head + sum(layer_fixed_bytes(cfg, layer, itemsize)
                      for layer in range(cfg.num_layers))


def expert_bytes(cfg, itemsize: int = 2) -> int:
    """One routed expert's three kernels."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize


def layer_bytes(cfg, layer: int, itemsize: int = 2) -> int:
    """A whole layer as it lies in HBM: attention, router, every expert."""
    return layer_fixed_bytes(cfg, layer, itemsize) \
        + cfg.experts[1] * expert_bytes(cfg, itemsize)


def cache_bytes(cfg, position: int, itemsize: int = 2) -> int:
    """Keys and values ONE sequence's token at ``position`` attends, over
    all layers."""
    row = 2 * cfg.num_kv_heads * cfg.head_dim * itemsize
    total = 0
    for kind in cfg.layer_types:
        seen = position + 1
        if kind == "sliding":
            seen = min(seen, cfg.sliding_window)
        total += seen * row
    return total


def decode_bytes(cfg, first_position: int, steps: int,
                 experts_read_per_step: float, sequences: float = 1.0,
                 itemsize: int = 2) -> float:
    """Bytes ``steps`` decode steps of ``sequences`` sequences need, the
    first at ``first_position``; ``experts_read_per_step`` is how many
    distinct held experts a step's rows chose, summed over the expert
    layers (the program's ``experts_read`` over its ``decode_steps``)."""
    cache = sum(cache_bytes(cfg, first_position + i, itemsize)
                for i in range(steps))
    return (steps * (fixed_bytes(cfg, itemsize)
                     + experts_read_per_step * expert_bytes(cfg, itemsize))
            + sequences * cache)

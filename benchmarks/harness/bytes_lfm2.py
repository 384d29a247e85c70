"""Bytes a decoded token of the prompt expander (models/lm.py, an LFM2
share) must move through HBM, from shapes alone: the weights every token
needs (a conv layer's two projections and its taps, an attention layer's
four projections, a dense layer's MLP, an expert layer's router and
selection bias, the head, one row of the table), the kernels of the experts
the token CHOSE among those held (here all of them are held), the keys and
values its query attends in the attention layers, and the conv layers' kept
rows, read and written in float32. Norm weights, activations, the key and
value row written and the router's scores are left out: the count may be
under what the program moves, never over it.
"""


def conv_layer_bytes(cfg, itemsize: int = 2) -> int:
    """in_proj (hidden -> 3 hidden), the taps, out_proj."""
    d = cfg.hidden_size
    return (d * 3 * d + cfg.conv_taps * d + d * d) * itemsize


def attention_layer_bytes(cfg, layer: int, itemsize: int = 2) -> int:
    """q_proj, k_proj, v_proj, o_proj of an ungated attention."""
    d, heads = cfg.hidden_size, cfg.num_heads_per_layer[layer]
    kv, dim = cfg.num_kv_heads, cfg.head_dim
    return (2 * d * heads * dim + 2 * d * kv * dim) * itemsize


def fixed_bytes(cfg, itemsize: int = 2) -> int:
    """Weights every decoded token reads, whatever it chose."""
    d = cfg.hidden_size
    total = (d + d * cfg.vocab[1]) * itemsize     # a table row, the head
    for layer, kind in enumerate(cfg.layer_types):
        total += conv_layer_bytes(cfg, itemsize) if kind == "conv" \
            else attention_layer_bytes(cfg, layer, itemsize)
        if layer in cfg.dense_layers:
            total += 3 * d * cfg.intermediate_size * itemsize
        else:     # router, selection bias; there is no shared expert
            total += (d * cfg.num_experts + cfg.num_experts) * itemsize
    return total


def expert_bytes(cfg, itemsize: int = 2) -> int:
    """One routed expert's three kernels."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize


def kept_rows_bytes(cfg) -> int:
    """The conv layers' kept rows, float32, read and written a step."""
    layers = sum(kind == "conv" for kind in cfg.layer_types)
    return 2 * layers * (cfg.conv_taps - 1) * cfg.hidden_size * 4


def cache_bytes(cfg, position: int, itemsize: int = 2) -> int:
    """Keys and values the token at ``position`` attends, over the
    attention layers (a conv layer attends none)."""
    layers = sum(kind == "full" for kind in cfg.layer_types)
    return (layers * (position + 1) * 2 * cfg.num_kv_heads * cfg.head_dim
            * itemsize)


def decode_bytes(cfg, first_position: int, tokens: int,
                 chosen_held_per_token: float, itemsize: int = 2) -> float:
    """Bytes ``tokens`` decode steps need, the first at ``first_position``;
    ``chosen_held_per_token`` is how many of a token's chosen experts are
    held here, summed over the expert layers (from the program's counter of
    tokens routed to each held expert)."""
    cache = sum(cache_bytes(cfg, first_position + i, itemsize)
                for i in range(tokens))
    return (tokens * (fixed_bytes(cfg, itemsize) + kept_rows_bytes(cfg)
                      + chosen_held_per_token * expert_bytes(cfg, itemsize))
            + cache)

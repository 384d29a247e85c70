"""Bytes a decoded token of the prompt expander (models/lm.py, a Laguna-S
share) must read from HBM, from shapes alone: the weights every token needs
(attention projections and gates, the dense layer's MLP, each expert layer's
router and shared expert, the head, one row of the table), the kernels of
the experts the token CHOSE among those held (never of all that are held),
and the cache positions it attends (every earlier position in a full layer,
at most the window in a sliding one). Norm scales, activations and what is
written are left out: the count may be under what the program moves, never
over it.
"""


def fixed_bytes(cfg, itemsize: int = 2) -> int:
    """Weights every decoded token reads, whatever it chose."""
    d, dim, kv = cfg.hidden_size, cfg.head_dim, cfg.num_kv_heads
    total = d + d * cfg.vocab[1]                 # a table row, the head
    for layer, heads in enumerate(cfg.num_heads_per_layer):
        total += 2 * d * heads * dim + 2 * d * kv * dim + d * heads
        if layer in cfg.dense_layers:
            total += 3 * d * cfg.intermediate_size
        else:
            total += (d * cfg.num_experts
                      + 3 * d * cfg.shared_expert_intermediate_size)
    return total * itemsize


def expert_bytes(cfg, itemsize: int = 2) -> int:
    """One routed expert's three kernels."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize


def cache_bytes(cfg, position: int, itemsize: int = 2) -> int:
    """Keys and values the token at ``position`` attends, all layers."""
    row = 2 * cfg.num_kv_heads * cfg.head_dim * itemsize
    total = 0
    for kind in cfg.layer_types:
        seen = position + 1
        if kind == "sliding":
            seen = min(seen, cfg.sliding_window)
        total += seen * row
    return total


def decode_bytes(cfg, first_position: int, tokens: int,
                 chosen_held_per_token: float, itemsize: int = 2) -> float:
    """Bytes ``tokens`` decode steps need, the first at ``first_position``;
    ``chosen_held_per_token`` is how many of a token's chosen experts are
    held here, summed over the expert layers (from the program's counter of
    tokens routed to each held expert)."""
    cache = sum(cache_bytes(cfg, first_position + i, itemsize)
                for i in range(tokens))
    return (tokens * (fixed_bytes(cfg, itemsize)
                      + chosen_held_per_token * expert_bytes(cfg, itemsize))
            + cache)

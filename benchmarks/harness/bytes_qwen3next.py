"""Bytes a decoded token of the prompt expander (models/lm.py, a Qwen3-Next
share) must move through HBM, from shapes alone: the weights every token
needs (a linear mixer's fused projections, taps and decay rates, a full
layer's attention projections, each layer's router, shared expert and its
gate, the head, one row of the table), the kernels of the experts the token
CHOSE among those held (never of all that are held), each linear layer's
recurrent state and kept convolution inputs READ AND WRITTEN (float32: the
step rewrites all of it), and the cache positions a full layer's query
attends. Norm weights, activations, the key and value rows written and the
router's scores are left out: the count may be under what the program
moves, never over it.
"""

STATE_ITEMSIZE = 4      # the recurrent state and kept inputs are float32


def linear_layer_bytes(cfg, itemsize: int = 2) -> int:
    """A linear mixer's weights: W_qkvz, W_ba, W_out, the taps, A_log and
    dt_bias."""
    d = cfg.hidden_size
    values = cfg.linear_num_value_heads * cfg.linear_value_head_dim
    channels = cfg.linear_conv_channels
    return (d * (channels + values) + d * 2 * cfg.linear_num_value_heads
            + values * d + cfg.linear_conv_kernel * channels
            + 2 * cfg.linear_num_value_heads) * itemsize


def full_layer_bytes(cfg, layer: int, itemsize: int = 2) -> int:
    """An attention layer's projections: q_proj with its gate's columns,
    k, v, o."""
    d, dim = cfg.hidden_size, cfg.head_dim
    heads, kv = cfg.num_heads_per_layer[layer], cfg.num_kv_heads
    gate = 2 if cfg.attn_gate == "element" else 1
    return (d * heads * dim * gate + 2 * d * kv * dim
            + heads * dim * d) * itemsize


def fixed_bytes(cfg, itemsize: int = 2) -> int:
    """Weights every decoded token reads, whatever it chose."""
    d = cfg.hidden_size
    total = (d + d * cfg.vocab[1]) * itemsize     # a table row, the head
    for layer, kind in enumerate(cfg.layer_types):
        total += (linear_layer_bytes(cfg, itemsize) if kind == "linear"
                  else full_layer_bytes(cfg, layer, itemsize))
        # router, shared expert, its gate
        total += (d * cfg.num_experts
                  + 3 * d * cfg.shared_expert_intermediate_size
                  + d) * itemsize
    return total


def expert_bytes(cfg, itemsize: int = 2) -> int:
    """One routed expert's three kernels."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize


def state_bytes(cfg) -> int:
    """Every linear layer's recurrent state and kept convolution inputs,
    read once and written once a token."""
    one = (cfg.linear_num_value_heads * cfg.linear_key_head_dim
           * cfg.linear_value_head_dim
           + (cfg.linear_conv_kernel - 1) * cfg.linear_conv_channels)
    linear = sum(kind == "linear" for kind in cfg.layer_types)
    return 2 * linear * one * STATE_ITEMSIZE


def cache_bytes(cfg, position: int, itemsize: int = 2) -> int:
    """Keys and values the token at ``position`` attends, over the
    attention layers (a linear layer has none)."""
    row = 2 * cfg.num_kv_heads * cfg.head_dim * itemsize
    total = 0
    for kind in cfg.layer_types:
        if kind == "linear":
            continue
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
    return (tokens * (fixed_bytes(cfg, itemsize) + state_bytes(cfg)
                      + chosen_held_per_token * expert_bytes(cfg, itemsize))
            + cache)

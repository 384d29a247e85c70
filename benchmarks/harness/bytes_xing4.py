"""Bytes a decoded token of the prompt expander (models/lm.py, a Xing4.0
share) must move through HBM, from shapes alone: the weights every token
needs (a latent-attention layer's five projections, its two stream mixers'
``phi``, a dense layer's MLP, an expert layer's router, selection bias and
shared expert, the head, one row of the table), the kernels of the experts
the token CHOSE among those held (never of all that are held), and the
latents its query attends: one row of ``kv_lora_rank + qk_rope_head_dim``
a position a layer, whatever the number of heads. Norm weights (the
mixers' ``streams * hidden`` wide ones too), the mixers' scalars and
biases, activations and the residual streams, the latent row written and
the router's scores are left out: the count may be under what the program
moves, never over it.
"""


def latent_layer_bytes(cfg, layer: int, itemsize: int = 2) -> int:
    """q_a_proj, q_b_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj."""
    d, heads = cfg.hidden_size, cfg.num_heads_per_layer[layer]
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    return (d * cfg.q_lora_rank + cfg.q_lora_rank * heads * (nope + rope)
            + d * (cfg.kv_lora_rank + rope)
            + cfg.kv_lora_rank * heads * (nope + cfg.v_head_dim)
            + heads * cfg.v_head_dim * d) * itemsize


def mixer_bytes(cfg, itemsize: int = 2) -> int:
    """Both sublayers' ``phi`` of one layer; none with one stream."""
    n = cfg.residual_streams
    if n == 1:
        return 0
    return 2 * n * cfg.hidden_size * (n * n + 2 * n) * itemsize


def fixed_bytes(cfg, itemsize: int = 2) -> int:
    """Weights every decoded token reads, whatever it chose."""
    d = cfg.hidden_size
    total = (d + d * cfg.vocab[1]) * itemsize     # a table row, the head
    for layer in range(cfg.num_layers):
        total += latent_layer_bytes(cfg, layer, itemsize) \
            + mixer_bytes(cfg, itemsize)
        if layer in cfg.dense_layers:
            total += 3 * d * cfg.intermediate_size * itemsize
        else:     # router, selection bias, shared expert
            total += (d * cfg.num_experts + cfg.num_experts
                      + 3 * d * cfg.shared_expert_intermediate_size
                      ) * itemsize
    return total


def expert_bytes(cfg, itemsize: int = 2) -> int:
    """One routed expert's three kernels."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize


def cache_bytes(cfg, position: int, itemsize: int = 2) -> int:
    """Latents the token at ``position`` attends, over the layers."""
    return cfg.num_layers * (position + 1) * cfg.latent_width * itemsize


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

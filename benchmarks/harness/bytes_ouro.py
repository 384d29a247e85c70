"""Bytes a decode STEP of the prompt expander (models/lm.py, a looped dense
model: Ouro-2.6B) must read from HBM when the step carries several
sequences (the images of one request, one token each, all at one position),
from shapes alone:

- the stack's weights ONCE A PASS, ``total_ut_steps`` times a step however
  many sequences it carries: each layer's four attention projections and
  its SwiGLU's three kernels (the same weights are streamed again in every
  pass: 4.93 GB cannot stay on the chip between passes);
- the head once a step (the head reads one pass's state);
- the keys and values every sequence attends: ``position + 1`` rows of
  EVERY (layer, pass) slot, times sequences (a fork copies the rows, it
  does not share them).

Norm scales, the gate, activations, the table's rows and the key and value
rows written are left out: the count may be under what the program moves,
never over it. A program that read a pass's rows out of a larger buffer
before attending them would move more and read LOWER here, not higher.
"""


def layer_bytes(cfg, layer: int, itemsize: int = 2) -> int:
    """One layer's kernels: q, k, v, o and the SwiGLU's gate, up, down."""
    d, heads = cfg.hidden_size, cfg.num_heads_per_layer[layer]
    kv, dim = cfg.num_kv_heads, cfg.head_dim
    attention = 2 * d * heads * dim + 2 * d * kv * dim
    return (attention + 3 * d * cfg.intermediate_size) * itemsize


def stack_bytes(cfg, itemsize: int = 2) -> int:
    """Every layer once: what ONE pass streams."""
    return sum(layer_bytes(cfg, layer, itemsize)
               for layer in range(cfg.num_layers))


def head_bytes(cfg, itemsize: int = 2) -> int:
    return cfg.hidden_size * cfg.vocab[1] * itemsize


def fixed_bytes(cfg, itemsize: int = 2) -> int:
    """Weights a step reads: the stack a pass, the head once."""
    return cfg.total_ut_steps * stack_bytes(cfg, itemsize) \
        + head_bytes(cfg, itemsize)


def cache_bytes(cfg, position: int, itemsize: int = 2) -> int:
    """Keys and values ONE sequence's token at ``position`` attends, over
    all (layer, pass) slots."""
    row = 2 * cfg.num_kv_heads * cfg.head_dim * itemsize
    return cfg.total_ut_steps * cfg.num_layers * (position + 1) * row


def decode_bytes(cfg, first_position: int, steps: int,
                 experts_read_per_step: float = 0.0, sequences: float = 1.0,
                 itemsize: int = 2) -> float:
    """Bytes ``steps`` decode steps of ``sequences`` sequences need, the
    first at ``first_position``. ``experts_read_per_step`` is what
    ``readers/bytes_util_steps.py`` hands every counter: a dense model
    reads none and it counts for nothing."""
    cache = sum(cache_bytes(cfg, first_position + i, itemsize)
                for i in range(steps))
    return steps * fixed_bytes(cfg, itemsize) + sequences * cache

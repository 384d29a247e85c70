"""The gated delta rule: the recurrence of a linear-attention layer
(models/lm.py, layer kind ``"linear"``) over a state of fixed size.

Per head, with the state ``S`` of shape ``(K, V)``, a key ``k`` and a query
``q`` of width ``K``, a value ``v`` of width ``V`` (the two widths need not
be equal), a log-decay ``g <= 0`` and a write strength ``beta`` in (0, 2)::

    S <- exp(g) S;   u = beta (v - S^T k);   S <- S + k u^T;   o = S^T q

(the transition ``I - beta k k^T`` has the eigenvalue ``1 - beta`` along a
unit ``k``: a caller whose ``beta`` is ``sigmoid(b)`` stays in (0, 1), one
whose is ``2 sigmoid(b)`` also reflects; no form below assumes either).

Three forms of the same mathematics, chosen by what the call shows (the
number of tokens and of sequences, the platform, the state's dtype and
shape, all static, and whether a mesh will partition the program), as
ops/moe.py chooses its product:

- ``T == 1`` (a decode step of one sequence): the recurrence as written
  (:func:`recurrent_step`), element-wise products and sums over the state,
  so float32 stays float32 on a TPU.
- several sequences a step (row ``b`` is sequence ``b``'s one token over
  its OWN state ``(B, H, K, V)``): the same step once a sequence, nothing
  shared between them: a state has no positions, so unlike keys, values or
  latents no part of it can be read once for all. :func:`step_form` picks
  how: on a TPU, with no mesh, over a float32 state whose value width is a
  multiple of the 128 lanes (and whose key width and heads tile the
  sublanes), ``"kernel"``: one Pallas kernel (ops/delta_kernel.py) that
  reads a block of heads' states into VMEM, runs the step's five lines
  there in float32 on the VPU and stores the block, one read and one write
  of the state where XLA's two fusions read it twice. Everywhere else (a
  CPU, a mesh: ``pjit`` does not partition a ``pallas_call``, a state kept
  in another dtype, a value width off the lanes such as Olmo-Hybrid's 192,
  which XLA keeps on chip between steps in eight layers of twelve: PERF.md
  section 7), ``"elementwise"``: :func:`recurrent_step_each`, the one-
  sequence step under ``vmap``.
- ``T > 1`` (prefill): the chunk-wise form. Tokens are cut into chunks of
  :data:`CHUNK`; inside a chunk the writes depend on each other through a
  unit lower-triangular system, solved row by row as the published
  implementation does; between chunks only the state is carried. Its
  products are float32 at the highest precision: a prefill happens once a
  prompt, and a state that later steps read for hundreds of tokens should
  not start from a bfloat16 product.

A masked row (``g = 0`` and ``beta = 0``) neither decays the state nor
writes to it: that is how a chunk padded to its bucket leaves the state
where its last real token put it. Everything is float32; the caller scales
``q`` and normalises ``q`` and ``k``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.ops import delta_kernel

#: tokens of one chunk of the chunk-wise form
CHUNK = 64

_HIGHEST = jax.lax.Precision.HIGHEST


def recurrent_step(state, q, k, v, g, beta):
    """One token. ``state`` ``(H, K, V)``; ``q``, ``k`` ``(H, K)``; ``v``
    ``(H, V)``; ``g``, ``beta`` ``(H,)``. Returns ``(o (H, V), state)``."""
    state = state * jnp.exp(g)[:, None, None]
    seen = jnp.sum(state * k[:, :, None], axis=1)             # S^T k
    u = beta[:, None] * (v - seen)
    state = state + k[:, :, None] * u[:, None, :]
    return jnp.sum(state * q[:, :, None], axis=1), state


#: :func:`recurrent_step` for each of ``B`` sequences: every operand with a
#: leading sequence axis, ``state`` ``(B, H, K, V)``. Returns ``(o (B, H,
#: V), state)``.
recurrent_step_each = jax.vmap(recurrent_step)


def recurrent(state, q, k, v, g, beta):
    """The recurrence token by token over ``T`` tokens (leading axis of
    every operand but ``state``): what the chunk-wise form must equal."""

    def step(state, row):
        out, state = recurrent_step(state, *row)
        return state, out

    state, out = jax.lax.scan(step, state, (q, k, v, g, beta))
    return out, state


def _solve_rows(lower):
    """``(I + L)^-1`` for strictly lower-triangular ``L`` ``(..., C, C)``,
    by forward substitution over the rows: row ``i`` of ``-L`` plus its
    product with the rows already solved, then the unit diagonal."""
    size = lower.shape[-1]

    def one_row(i, solved):
        row = jax.lax.dynamic_slice_in_dim(solved, i, 1, axis=-2)
        row = row + jnp.matmul(row, solved, precision=_HIGHEST)
        return jax.lax.dynamic_update_slice_in_dim(solved, row, i, axis=-2)

    return jax.lax.fori_loop(1, size, one_row, -lower) \
        + jnp.eye(size, dtype=lower.dtype)


def chunked(state, q, k, v, g, beta, chunk: int = CHUNK):
    """The chunk-wise form over ``T`` tokens: ``q``, ``k`` ``(T, H, K)``,
    ``v`` ``(T, H, V)``, ``g``, ``beta`` ``(T, H)``, ``state`` ``(H, K,
    V)``. ``T`` is padded up to whole chunks with masked rows. Returns
    ``(o (T, H, V), state)``."""
    tokens = q.shape[0]
    pad = -tokens % chunk

    def chunks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        x = x.reshape((-1, chunk) + x.shape[1:])
        return jnp.moveaxis(x, 2, 1)                # (N, H, C, ...)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    decay = jnp.cumsum(g, axis=-1)                  # (N, H, C), <= 0
    k_beta = k * beta[..., None]
    rows = jnp.arange(chunk)
    below = rows[:, None] > rows[None, :]
    upto = rows[:, None] >= rows[None, :]
    # exp(decay_i - decay_j) for j <= i; masked before the exponential, so
    # the positive differences above the diagonal never overflow
    between = jnp.exp(jnp.where(
        upto, decay[..., :, None] - decay[..., None, :], 0.0)) * upto

    def mm(a, b):
        return jnp.matmul(a, b, precision=_HIGHEST)

    k_t = jnp.swapaxes(k, -1, -2)
    solve = _solve_rows(mm(k_beta, k_t) * between * below)
    writes = mm(solve, v * beta[..., None])
    k_decayed = mm(solve, k_beta * jnp.exp(decay)[..., None])
    inside = mm(q, k_t) * between                   # j <= i
    to_end = jnp.exp(decay[..., -1:] - decay)       # (N, H, C)

    def one_chunk(state, parts):
        q_i, k_i, writes_i, k_decayed_i, inside_i, decay_i, to_end_i = parts
        new = writes_i - mm(k_decayed_i, state)
        out = mm(q_i * jnp.exp(decay_i)[..., None], state) + mm(inside_i, new)
        state = state * jnp.exp(decay_i[:, -1])[:, None, None] \
            + mm(jnp.swapaxes(k_i * to_end_i[..., None], -1, -2), new)
        return state, out

    state, out = jax.lax.scan(
        one_chunk, state, (q, k, writes, k_decayed, inside, decay, to_end))
    out = jnp.moveaxis(out, 1, 2).reshape((-1,) + out.shape[1:2]
                                          + out.shape[3:])
    return out[:tokens], state


def gated_delta_rule(state, q, k, v, g, beta):
    """``(o (T, H, V), state)`` for a chunk of ``T`` tokens: the recurrent
    step at one token, the chunk-wise form at more."""
    if q.shape[0] == 1:
        out, state = recurrent_step(state, q[0], k[0], v[0], g[0], beta[0])
        return out[None], state
    return chunked(state, q, k, v, g, beta)


RECURRENT, CHUNKED, FORKED = "recurrent", "chunked", "recurrent_forked"
KERNEL, ELEMENTWISE = "kernel", "elementwise"


def form(tokens: int, sequences: bool = False) -> str:
    """Which form a chunk of ``tokens`` takes (spans, counters);
    ``sequences``: the rows are one token each of as many sequences, each
    over a state of its own."""
    if sequences:
        return FORKED
    return RECURRENT if tokens == 1 else CHUNKED


def step_form(platform: str, dtype, shape, *, meshed: bool = False) -> str:
    """``"kernel"`` or ``"elementwise"`` for one forked step over a state
    of ``dtype`` and ``shape`` ``(B, H, K, V)``, from what the call shows.
    The kernel wants a TPU, a program no mesh partitions, several sequences
    a step, a float32 state and a shape that tiles
    (ops/delta_kernel.py:head_block: ``V`` a multiple of the 128 lanes,
    ``K`` and the heads of the 8 sublanes)."""
    sequences, heads, k_dim, v_dim = shape
    if (platform == "tpu" and not meshed and sequences > 1
            and jnp.dtype(dtype) == jnp.float32
            and delta_kernel.head_block(heads, k_dim, v_dim) is not None):
        return KERNEL
    return ELEMENTWISE


#: the forked step by the form :func:`step_form` names: the same operands,
#: ``(o (B, H, V), state)``
FORKED_STEPS = {KERNEL: delta_kernel.recurrent_step_each,
                ELEMENTWISE: recurrent_step_each}

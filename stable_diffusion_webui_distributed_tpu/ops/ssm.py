"""The selective state-space recurrence: the token mixer of a layer part of
kind ``"ssm"`` (models/lm.py:SSMMixer) over a state of fixed size.

Per head ``j`` of ``H``, with the state ``S_j`` of shape ``(P, N)`` (head
width by state width), an input ``x_j`` of width ``P``, a step ``dt_j > 0``,
a log-decay ``l_j = -exp(A_log_j) dt_j <= 0``, and an input map ``B`` and an
output map ``C`` of width ``N`` that a GROUP of consecutive heads shares
(``G`` groups, head ``j`` reads group ``j // (H / G)``)::

    S_j <- exp(l_j) S_j + dt_j x_j B^T;      y_j = S_j C

(the skip ``D_j x_j``, the gate and the read-out's norm are the caller's).
The decay is a scalar a head, so unlike the delta rule (ops/delta_rule.py:
``I - beta k k^T``) nothing a token writes depends on what the state holds:
the writes of a chunk are independent, and the chunk-wise form needs no
triangular solve, only the decays between two positions of a chunk.

Three forms of the same mathematics, chosen by what the call shows
(:func:`form`, the delta rule's own), as the delta rule's are:

- one token (:func:`step`): the recurrence as written, element-wise over
  the state, so float32 stays float32 on a TPU;
- several sequences a step (:func:`step_each`): row ``b`` is sequence
  ``b``'s one token over its OWN state ``(B, H, P, N)``, the same step once
  a sequence, nothing shared between them (a state has no positions);
- a chunk of ``T`` tokens (:func:`chunked`, a prefill): tokens are cut into
  chunks of ``chunk``; with ``L_t = sum_{s <= t} l_s`` inside a chunk,

      y_t = sum_{s <= t} exp(L_t - L_s) (C_t . B_s) dt_s x_s
            + exp(L_t) S_prev C_t
      S_next = exp(L_last) S_prev + sum_s exp(L_last - L_s) dt_s x_s B_s^T

  The differences of ``L`` are formed BEFORE the exponential (a segment
  sum, masked above the diagonal), never as a quotient of exponentials:
  ``exp(-L_s)`` overflows float32 where a head forgets inside a token.
  Between chunks only the state is carried. Its products are float32 at
  the highest precision, as the delta rule's prefill is and for its
  reason.

A masked row (``dt = 0``, so ``l = 0``) neither decays the state nor writes
to it: that is how a chunk padded to its bucket, a pad of a group of
sequences and a sequence that has ended leave the state where it was.
Everything is float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: which form a chunk takes (spans, counters): the delta rule's three
#: names, by its rule
from stable_diffusion_webui_distributed_tpu.ops.delta_rule import form  # noqa: F401

_HIGHEST = jax.lax.Precision.HIGHEST


def _grouped(x, groups: int):
    """``(H, ...)`` as ``(G, H / G, ...)``: the heads by the group whose
    ``B`` and ``C`` they read."""
    return x.reshape((groups, x.shape[0] // groups) + x.shape[1:])


def step(state, x, b, c, dt, log_decay):
    """One token. ``state`` ``(H, P, N)``; ``x`` ``(H, P)``; ``b``, ``c``
    ``(G, N)``; ``dt``, ``log_decay`` ``(H,)``. Returns ``(y (H, P),
    state)``."""
    groups = b.shape[0]
    s = _grouped(state, groups)                             # (G, h, P, N)
    written = _grouped(dt[:, None] * x, groups)             # (G, h, P)
    s = s * _grouped(jnp.exp(log_decay), groups)[..., None, None] \
        + written[..., None] * b[:, None, None, :]
    y = jnp.sum(s * c[:, None, None, :], axis=-1)
    return y.reshape(x.shape), s.reshape(state.shape)


#: :func:`step` for each of ``B`` sequences: every operand with a leading
#: sequence axis, ``state`` ``(B, H, P, N)``. Returns ``(y (B, H, P),
#: state)``.
step_each = jax.vmap(step)


def recurrent(state, x, b, c, dt, log_decay):
    """The recurrence token by token over ``T`` tokens (leading axis of
    every operand but ``state``): what the chunk-wise form must equal."""

    def one(state, row):
        y, state = step(state, *row)
        return state, y

    state, y = jax.lax.scan(one, state, (x, b, c, dt, log_decay))
    return y, state


def chunked(state, x, b, c, dt, log_decay, chunk: int):
    """The chunk-wise form over ``T`` tokens: ``x`` ``(T, H, P)``, ``b``,
    ``c`` ``(T, G, N)``, ``dt``, ``log_decay`` ``(T, H)``, ``state`` ``(H,
    P, N)``. ``T`` is padded up to whole chunks with masked rows. Returns
    ``(y (T, H, P), state)``."""
    tokens, heads = dt.shape
    groups = b.shape[1]
    pad = -tokens % chunk

    def chunks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, chunk) + a.shape[1:])         # (n, C, ...)

    x, b, c, dt, log_decay = map(chunks, (x, b, c, dt, log_decay))
    # L, the heads in front of a chunk's positions: (n, H, C)
    decay = jnp.swapaxes(jnp.cumsum(log_decay, axis=1), 1, 2)
    rows = jnp.arange(chunk)
    upto = rows[:, None] >= rows[None, :]                   # s <= t
    # exp(L_t - L_s) for s <= t; masked before the exponential, so the
    # positive differences above the diagonal never overflow
    between = jnp.exp(jnp.where(
        upto, decay[..., :, None] - decay[..., None, :], 0.0)) * upto
    written = dt[..., None] * x                             # (n, C, H, P)
    # C_t . B_s a group, then every head of the group under its own decay
    scores = jnp.einsum("ntgk,nsgk->ngts", c, b, precision=_HIGHEST)
    scores = jnp.repeat(scores, heads // groups, axis=1) * between
    inside = jnp.einsum("nhts,nshp->nthp", scores, written,
                        precision=_HIGHEST)
    # what a chunk adds to the state: every write decayed to the chunk's end
    to_end = jnp.swapaxes(jnp.exp(decay[..., -1:] - decay), 1, 2)
    by_group = (written * to_end[..., None]).reshape(
        written.shape[:2] + (groups, heads // groups, -1))  # (n, C, G, h, P)
    adds = jnp.einsum("nsghp,nsgk->nghpk", by_group, b,
                      precision=_HIGHEST).reshape((-1,) + state.shape)

    def one_chunk(state, parts):
        c_i, decay_i, adds_i, inside_i = parts
        # exp(L_t) S_prev C_t: the state as the chunk found it, read out
        read = jnp.einsum(
            "ghpk,tgk->tghp", _grouped(state, groups), c_i,
            precision=_HIGHEST).reshape(inside_i.shape)
        out = inside_i + read * jnp.exp(decay_i).T[..., None]
        state = state * jnp.exp(decay_i[:, -1])[:, None, None] + adds_i
        return state, out

    state, y = jax.lax.scan(one_chunk, state, (c, decay, adds, inside))
    return y.reshape((-1,) + y.shape[2:])[:tokens], state


def mix(state, x, b, c, dt, log_decay, chunk: int):
    """``(y (T, H, P), state)`` for a chunk of ``T`` tokens: the step at
    one token, the chunk-wise form at more."""
    if x.shape[0] == 1:
        y, state = step(state, x[0], b[0], c[0], dt[0], log_decay[0])
        return y[None], state
    return chunked(state, x, b, c, dt, log_decay, chunk)

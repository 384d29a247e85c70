"""Mixture-of-experts feed-forward over the experts THIS chip holds.

A deployment shards an expert layer over chips: every chip routes each
token over all of the layer's experts (the router's weight is whole
everywhere), computes the part of the sum that its own experts give, and an
all-reduce over the expert axis adds the parts. This module is one chip's
part: it is told which contiguous range of experts its stacked kernels are
(``first``, and their count from the kernels' leading dimension), computes
those, and adds nothing for the absent ones. No code stands in for the
other chips.

A router's LAST ids may be zero-compute experts (``LMConfig.zero_experts``,
``E_e(n) = n``): they have no kernels, so every product below passes them
by as it passes an absent expert, and what they add is ``(the sum of a
token's weights on them) * n`` (:func:`identity_part`), computed where the
token lives. A pick on one is neither held nor absent: the load, the
experts read and the tokens with no held expert stay counts of the experts
that have kernels, and :func:`identity_picks` counts these beside them.

Three products, chosen by what the call shows (:func:`choose`: the number
of rows, the platform, the operands' dtype and the kernels' shapes, all
static, and whether a mesh will partition the program). By row count:

- more rows than one row tile of 8 (every prefill: a chunk is never under
  64 rows), ``"grouped"``: rows are sorted by the expert they chose, each
  expert's rows padded up to a row tile, and one loop walks the tiles that
  hold rows, each a ``(tile, d) @ (d, f)`` product against the one expert
  the tile belongs to. An expert nobody chose is not read.
- 1 to 8 rows (a decode step: one sequence, or the 2, 4 or 8 sequences of
  ``cache/kv.py:SEQUENCE_BUCKETS``) on a TPU with bf16 operands and ``d``
  and ``f`` multiples of the lane width, no mesh, ``"kernel"``: one
  Pallas kernel (ops/moe_kernel.py) over the step's DISTINCT held experts
  that walks their blocks through a ring of reads of its own, the next
  block in flight while one multiplies, and reads nothing at all where
  none of the step's experts is held. One row's picks are distinct
  already (:func:`_chosen`);
  several rows' are made so and every expert takes the whole block of
  rows, a per-row weight deciding what it adds (:func:`_block`): under one
  row tile the grouped product would pad every expert's rows to 8 anyway,
  one unpipelined trip an expert. The same experts are read. The same
  answer takes the ROUTING as one kernel too (:func:`kernel_step`,
  ops/route_kernel.py): scores, top-k, the held-first order and the counts
  in one launch in front of the expert kernel, where :func:`route` and the
  chain behind it are about twenty.
- where the kernel is not taken (a CPU, float32 operands, widths off the
  lane tiling as the tests' tiny presets have, a mesh: ``pjit`` does not
  partition a ``pallas_call``): one row, ``"loop"``: a loop over the chosen
  experts held here (5 of 10 on average when half are held), each reading
  that expert's three kernels once; 2 to 8 rows, ``"grouped"``. The experts
  that were held but not chosen are not read: that is what bounds a decoded
  token's bytes.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.ops import (
    moe_kernel, route_kernel,
)

GROUPED = "grouped"
LOOP = "loop"
KERNEL = "kernel"


class Routing(NamedTuple):
    experts: jax.Array   # (T, k) int32, ids over ALL of the layer's experts
    weights: jax.Array   # (T, k) float32, renormalised and scaled


def route(logits: jax.Array, k: int, *, renormalise: bool, scale: float,
          scoring: str = "softmax", bias: jax.Array | None = None,
          eps: float = 0.0) -> Routing:
    """Scores are float32 over every expert: a softmax, or (``scoring``
    ``"sigmoid"``) each expert's own sigmoid. The ``k`` largest are taken,
    their scores optionally renormalised to sum to one (divided by their
    sum plus ``eps``), then scaled. With a
    ``bias`` ``(experts,)`` the ``k`` are those with the largest ``score +
    bias``; the bias chooses and does not weigh: the weights are the
    chosen experts' scores without it."""
    logits = logits.astype(jnp.float32)
    scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    if bias is None:
        top, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(scores + bias, k)
        top = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalise:
        total = jnp.sum(top, axis=-1, keepdims=True)
        top = top / (total + eps if eps else total)
    return Routing(experts.astype(jnp.int32), top * scale)


#: the grouped product's smallest row tile: a call of no more rows than
#: this pads every expert's rows to all of it, and takes the kernel instead
MIN_ROW_TILE = 8


def row_tile(tokens: int, k: int, num_experts: int) -> int:
    """Rows of one tile of the grouped product: the power of two at or over
    the rows an expert gets on average, within [8, 256]."""
    mean = max(1, -(-tokens * k // num_experts))
    return int(min(256, max(MIN_ROW_TILE, 1 << (mean - 1).bit_length())))


def _swiglu(x, w_gate, w_up, w_down, limit: float = 0.0):
    """``W_d(silu(W_g x) * W_u x)`` for one expert; float32 accumulation,
    operands in ``x``'s dtype, float32 result. ``limit`` over 0 clamps:
    ``silu(min(W_g x, limit)) * clip(W_u x, -limit, limit)``."""
    f32 = jnp.float32
    gate = jnp.dot(x, w_gate, preferred_element_type=f32)
    up = jnp.dot(x, w_up, preferred_element_type=f32)
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    return jnp.dot(hidden, w_down, preferred_element_type=f32)


def held_mask(experts: jax.Array, first: int, count: int):
    """(local ids, which of the chosen experts are held here)."""
    local = experts - first
    return local, (local >= 0) & (local < count)


def _grouped(x, routing: Routing, w_gate, w_up, w_down, first: int,
             num_experts: int, limit: float = 0.0):
    tokens, k = routing.experts.shape
    count = w_gate.shape[0]
    tile = row_tile(tokens, k, num_experts)
    local, held = held_mask(routing.experts, first, count)
    chosen = jnp.where(held, local, count).reshape(-1)   # absent sort last
    order = jnp.argsort(chosen, stable=True)
    sorted_e = chosen[order]
    token_of = order // k
    sizes = jnp.bincount(chosen, length=count + 1)[:count]
    tiles_of = (sizes + tile - 1) // tile
    tile_end = jnp.cumsum(tiles_of)
    row_start = jnp.cumsum(sizes) - sizes
    # every expert wastes less than one tile, so this many always suffice
    max_tiles = tokens * k // tile + count
    rows = max_tiles * tile
    e = jnp.minimum(sorted_e, count - 1)
    dest = ((tile_end[e] - tiles_of[e]) * tile
            + jnp.arange(tokens * k) - row_start[e])
    dest = jnp.where(sorted_e < count, dest, rows)       # absent: dropped
    xs = jnp.zeros((rows, x.shape[-1]), x.dtype).at[dest].set(
        x[token_of], mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(max_tiles), side="right"),
        count - 1)

    def one_tile(t, ys):
        expert = tile_expert[t]
        y = _swiglu(jax.lax.dynamic_slice_in_dim(xs, t * tile, tile),
                    w_gate[expert], w_up[expert], w_down[expert], limit)
        return jax.lax.dynamic_update_slice_in_dim(ys, y, t * tile, 0)

    ys = jax.lax.fori_loop(0, tile_end[-1], one_tile,
                           jnp.zeros((rows, x.shape[-1]), jnp.float32))
    weight = jnp.where(sorted_e < count,
                       routing.weights.reshape(-1)[order], 0.0)
    part = ys[jnp.minimum(dest, rows - 1)] * weight[:, None]
    return jnp.zeros((tokens, x.shape[-1]), jnp.float32).at[token_of].add(
        part)


def _chosen(x, routing: Routing, w_gate, w_up, w_down, first: int,
            kernel: bool = False, limit: float = 0.0):
    count = w_gate.shape[0]
    local, held = held_mask(routing.experts[0], first, count)
    order = jnp.argsort(~held, stable=True)              # held ones first
    experts = jnp.where(held, local, 0)[order]
    weights = jnp.where(held, routing.weights[0], 0.0)[order]
    if kernel:
        return moe_kernel.chosen_experts(x, experts, weights, jnp.sum(held),
                                         w_gate, w_up, w_down, limit=limit)

    def one_expert(j, acc):
        expert = experts[j]
        return acc + weights[j] * _swiglu(x, w_gate[expert], w_up[expert],
                                          w_down[expert], limit)

    return jax.lax.fori_loop(0, jnp.sum(held), one_expert,
                             jnp.zeros(x.shape, jnp.float32))


def _block(x, routing: Routing, w_gate, w_up, w_down, first: int,
           limit: float = 0.0):
    """A step of 2-8 rows through the kernel: the step's distinct held
    experts first (as :func:`_chosen` orders a token's held picks), each
    with a column of per-row weights, zero where the row did not choose
    it."""
    rows, k = routing.experts.shape
    count = w_gate.shape[0]
    slots = min(rows * k, count)
    local, held = held_mask(routing.experts, first, count)
    weights = jnp.zeros((count, rows), jnp.float32).at[
        jnp.where(held, local, count), jnp.arange(rows)[:, None]].add(
            routing.weights, mode="drop")                # absent: dropped
    chosen = jnp.any(weights != 0.0, axis=1)
    experts = jnp.argsort(~chosen, stable=True)[:slots]  # chosen ones first
    return moe_kernel.chosen_experts(x, experts, weights[experts],
                                     jnp.sum(chosen), w_gate, w_up, w_down,
                                     limit=limit)


def choose(platform: str, tokens: int, dtype, d: int, f: int, *,
           meshed: bool = False) -> str:
    """``"grouped"``, ``"loop"`` or ``"kernel"`` for one expert layer's
    call, from what the call shows. The kernel wants a decode step (no
    more rows than one row tile: a prefill chunk is never that short) on a
    TPU, the serving policy's bf16 (the only dtype run on the chip), widths
    that tile (ops/moe_kernel.py:f_tile) and a program no mesh partitions:
    ``pjit`` would run a ``pallas_call`` whole on every chip, against
    kernels it has split by expert."""
    if tokens > MIN_ROW_TILE:
        return GROUPED
    dtype = jnp.dtype(dtype)
    if (platform == "tpu" and not meshed and dtype == jnp.bfloat16
            and moe_kernel.f_tile(d, f, dtype.itemsize) is not None):
        return KERNEL
    return LOOP if tokens == 1 else GROUPED


def routed_experts(x: jax.Array, routing: Routing, w_gate: jax.Array,
                   w_up: jax.Array, w_down: jax.Array, *, first: int,
                   num_experts: int, meshed: bool = False,
                   limit: float = 0.0):
    """(this chip's part of ``sum_e w_e E_e(x)``, the product taken):
    ``x`` is ``(T, d)``, the kernels are stacked ``(held, d, f)``, ``(held,
    d, f)``, ``(held, f, d)`` and are experts ``first .. first + held - 1``
    of ``num_experts``; ``meshed`` says a mesh will partition the program;
    ``limit`` over 0 clamps every expert's SwiGLU (:func:`_swiglu`).
    Float32 ``(T, d)``."""
    path = choose(jax.default_backend(), x.shape[0], x.dtype,
                  *w_gate.shape[1:], meshed=meshed)
    if path == GROUPED:
        return _grouped(x, routing, w_gate, w_up, w_down, first,
                        num_experts, limit), path
    if x.shape[0] > 1:
        return _block(x, routing, w_gate, w_up, w_down, first, limit), path
    return _chosen(x, routing, w_gate, w_up, w_down, first,
                   kernel=path == KERNEL, limit=limit), path


def kernel_step(x: jax.Array, logits: jax.Array, bias: jax.Array | None,
                valid: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                w_down: jax.Array, *, k: int, renormalise: bool,
                scale: float, scoring: str, eps: float, first: int,
                zero_experts: int = 0, limit: float = 0.0):
    """A decode step :func:`choose` gave ``"kernel"``, in two launches
    behind the router's product: ``(this chip's part of sum_e w_e E_e(x),
    the ops/route_kernel.py:Step it was made from)``. The routing kernel
    turns the router's float32 ``logits`` ``(rows, experts)`` into the
    expert kernel's operands, as :func:`route` with :func:`_chosen` (one
    row) or :func:`_block` (2-8 rows) would, and the counts of
    :func:`load_counts`, :func:`identity_part` and :func:`identity_picks`
    beside them; the expert kernel takes those as they come. ``bias`` in
    the dtype it is stored in."""
    step = route_kernel.routing(
        logits, bias, valid, k=k, renormalise=renormalise, scale=scale,
        scoring=scoring, eps=eps, first=first, count=w_gate.shape[0],
        zero_experts=zero_experts)
    return moe_kernel.chosen_experts(x, step.experts, step.weights,
                                     step.held, w_gate, w_up, w_down,
                                     limit=limit), step


def identity_part(x: jax.Array, routing: Routing, first_zero: int):
    """``sum over a token's picks e >= first_zero of w_e E_e(x)`` with
    ``E_e(x) = x``: the zero-compute experts' part of the routed sum, ``(T,
    d)`` float32 from ``x`` in float32."""
    weight = jnp.sum(jnp.where(routing.experts >= first_zero,
                               routing.weights, 0.0), axis=-1)
    return weight[:, None] * x.astype(jnp.float32)


def identity_picks(routing: Routing, first_zero: int, valid=None):
    """Picks that fell on a zero-compute expert, over the rows that count
    (``valid`` masks padded rows); int32."""
    picks = routing.experts >= first_zero
    if valid is not None:
        picks = picks & valid[:, None]
    return jnp.sum(picks).astype(jnp.int32)


def load_counts(routing: Routing, first: int, count: int, valid=None):
    """(tokens routed to each held expert ``(count,)``, tokens none of whose
    chosen experts is held here), both int32. ``valid`` masks padded rows."""
    local, held = held_mask(routing.experts, first, count)
    if valid is not None:
        held = held & valid[:, None]
    per_expert = jnp.bincount(jnp.where(held, local, count).reshape(-1),
                              length=count + 1)[:count]
    rows = jnp.ones(held.shape[0], bool) if valid is None else valid
    none_held = jnp.sum(rows & ~jnp.any(held, axis=-1))
    return per_expert.astype(jnp.int32), none_held.astype(jnp.int32)


def experts_read(per_expert: jax.Array) -> jax.Array:
    """Distinct held experts one call's rows chose, from its
    :func:`load_counts` ``(..., count)``: the experts whose kernels the
    call streams. Every product reads an expert once however many rows
    chose it (the kernel walks a step's distinct experts, the grouped
    product an expert's tiles), so a step of several sequences reads this
    many, not its picks; a step of one token reads as many as it has
    picks."""
    return jnp.sum(per_expert > 0, axis=-1).astype(jnp.int32)

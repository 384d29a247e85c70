"""A decode step's expert ROUTING as one Pallas kernel: everything between
the router's logits and the expert kernel's scalar-prefetched operands, and
the counts that ride beside them.

In XLA that chain (``ops/moe.py:route``, ``_chosen`` / ``_block``,
``load_counts``, ``identity_part`` / ``identity_picks``) is about twenty
launches an expert layer a step on the TPU: the scoring, ``lax.top_k`` (a
full sort of every score for ten), a gather of the unbiased scores, the
renormalising sum and division, ``argsort(~held)`` and two gathers (one
row) or a scatter-add, ``any``, an ``argsort`` over all held experts and a
gather (several rows), a ``bincount`` and three more reductions; 11 us of a
Qwen3-Next layer's step with nothing streaming (PERF.md section 6, PR 68).
Here it is one launch in the form of ops/stream_mixer.py: every operand one
whole block in VMEM, no grid, every value between the steps in vector
registers.

The rows (1 to 8) lie down the sublanes and the experts across the lanes of
``(8, experts rounded up to the lane width)`` float32 registers, six for
768 experts:

1. the scores, float32 over every expert, by ``route``'s formulas;
2. top-k as ``k`` rounds of max / first index / mask over the lanes, every
   row at once: ties go to the lower id, as ``lax.top_k`` breaks them; the
   renormalising sum adds the picks in their order;
3. the expert kernel's operands, the held picks first. One row keeps its
   picks' order (``_chosen``'s stable sort): a held pick's slot is the count
   of held picks before it. Several rows' distinct held experts go by id
   (``_block``'s): a slot is a prefix count over the chosen lanes
   (log-step rotations), and every slot's column of per-row weights is one
   masked sum over the lanes, laid flat ``rows`` a slot;
4. the counts: the load of every held expert, the rows none of whose picks
   is held, and for a router with identity experts each row's weight on
   them and the picks that fell there.

Not bit equal to the XLA chain: the softmax's sum and the identity weight
add in another order (1-2 ulp). The picks are the same picks.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: a float32 vector register: a step's rows are one's sublanes at most
#: (ops/moe.py:choose sends no more than its row tile of 8 here)
LANES, SUBLANES = 128, 8
#: VMEM beside the operands (a few KB): what the compiler spills of the body
_VMEM_SLACK = 2 * 2 ** 20


class Step(NamedTuple):
    """What one expert layer's decode step consumes."""
    experts: jax.Array      # (slots,) int32 local ids, the held ones first
    weights: jax.Array      # (slots,) float32; (slots, rows) at several rows
    held: jax.Array         # (1,) int32: the slots that hold an expert
    picks: jax.Array        # (rows, k) int32: ``Routing.experts``
    load: jax.Array         # (count,) int32
    none_held: jax.Array    # () int32
    identity_weight: jax.Array | None   # (rows, 1) float32
    identity_picks: jax.Array | None    # () int32


def slots_of(rows: int, k: int, count: int) -> int:
    """Grid slots of the expert kernel: a token's picks, or the most
    distinct held experts several rows can choose."""
    return k if rows == 1 else min(rows * k, count)


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def _prefix_count(flags, lane):
    """Inclusive prefix sum along the lanes of ``flags`` (int32, the same
    in every sublane): log-step rotations."""
    from jax.experimental.pallas import tpu as pltpu

    total, shift = flags, 1
    while shift < flags.shape[1]:
        total = total + jnp.where(lane >= shift,
                                  pltpu.roll(total, shift, 1), 0)
        shift *= 2
    return total


def _kernel(*refs, rows: int, experts: int, k: int, scoring: str,
            renormalise: bool, eps: float, scale: float, first: int,
            count: int, zero_experts: int, biased: bool):
    from jax.experimental.pallas import tpu as pltpu

    refs = iter(refs)
    logits_ref = next(refs)
    bias_ref = next(refs) if biased else None
    valid_ref = next(refs)
    (experts_ref, weights_ref, held_ref, picks_ref, load_ref,
     none_ref) = (next(refs) for _ in range(6))
    if zero_experts:
        identity_ref, zero_picks_ref = next(refs), next(refs)
    padded_ref, rows_ref = refs         # the two scratch buffers
    f32, i32 = jnp.float32, jnp.int32
    width = padded_ref.shape[-1]
    slots = slots_of(rows, k, count)

    # the operands laid in whole registers: rows down the sublanes
    padded_ref[...] = jnp.zeros_like(padded_ref)
    padded_ref[0, 0:rows, 0:experts] = logits_ref[...]
    if biased:
        padded_ref[1, 0:1, 0:experts] = bias_ref[...].astype(f32)
    rows_ref[...] = jnp.zeros_like(rows_ref)
    rows_ref[0:1, 0:rows] = valid_ref[...]
    x = padded_ref[0]
    lane = jax.lax.broadcasted_iota(i32, (SUBLANES, width), 1)
    slot_lane = jax.lax.broadcasted_iota(i32, (SUBLANES, LANES), 1)
    sublane = jax.lax.broadcasted_iota(i32, (SUBLANES, 1), 0)
    real_lane = lane < experts
    real_row = sublane < rows
    # ``valid`` arrives along the lanes: row r's flag down to sublane r
    valid = jnp.max(jnp.where(slot_lane == sublane, rows_ref[0:1, :], 0),
                    axis=1, keepdims=True) != 0                 # (8, 1)

    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(x)
    else:       # jax.nn.softmax's own steps
        if width != experts:
            x = jnp.where(real_lane, x, -jnp.inf)
        unnormalised = jnp.exp(x - jnp.max(x, axis=1, keepdims=True))
        scores = unnormalised / jnp.sum(unnormalised, axis=1, keepdims=True)
    select = scores + padded_ref[1, 0:1, :] if biased else scores
    if width != experts:
        select = jnp.where(real_lane, select, -jnp.inf)

    # top-k: k rounds of max / first index / mask, every row at once
    taken = jnp.zeros((SUBLANES, width), jnp.bool_)
    total = jnp.zeros((SUBLANES, 1), f32)
    picks = jnp.zeros((SUBLANES, LANES), i32)
    # one row: its held picks in their order
    ids = jnp.zeros((SUBLANES, LANES), i32)
    raw = jnp.zeros((SUBLANES, LANES), f32)
    held_so_far = jnp.zeros((SUBLANES, 1), i32)
    for j in range(k):
        best = jnp.max(select, axis=1, keepdims=True)
        index = jnp.min(jnp.where(select == best, lane, width), axis=1,
                        keepdims=True)
        hit = lane == index
        top = jnp.sum(jnp.where(hit, scores, 0.0), axis=1,
                      keepdims=True) if biased else best
        total = total + top
        taken = taken | hit
        select = jnp.where(hit, -jnp.inf, select)
        picks = jnp.where(slot_lane == j, index, picks)
        if rows == 1:
            is_held = (index >= first) & (index < first + count)
            here = is_held & (slot_lane == held_so_far)
            ids = jnp.where(here, index - first, ids)
            raw = jnp.where(here, top, raw)
            held_so_far = held_so_far + is_held.astype(i32)
    picks_ref[...] = picks[0:rows, 0:k]

    def weigh(top):
        """``route``'s: renormalised by the picks' sum, then scaled."""
        if renormalise:
            top = top / (total + eps if eps else total)
        return top * scale

    taken = taken & real_row
    weights = jnp.where(taken, weigh(scores), 0.0)              # (8, width)
    held_lane = (lane >= first) & (lane < first + count)
    if rows == 1:
        experts_ref[...] = ids[0:1, 0:slots]
        weights_ref[...] = jnp.where(slot_lane < held_so_far, weigh(raw),
                                     0.0)[0:1, 0:slots]
        held_ref[...] = held_so_far[0:1, :]
    else:
        # the registers that hold the held range, from a lane boundary
        low = first // LANES * LANES
        high = min(_round_up(first + count, LANES), width)
        local = lane[:, low:high] - first
        mine = jnp.where(held_lane, weights, 0.0)[:, low:high]
        chosen = jnp.max((mine != 0.0).astype(i32), axis=0, keepdims=True)
        chosen = jnp.broadcast_to(chosen, mine.shape)
        before = _prefix_count(chosen, lane[:, low:high] - low) - chosen
        flat = _round_up(slots * rows, LANES)
        flat_lane = jax.lax.broadcasted_iota(i32, (SUBLANES, flat), 1)
        ids = jnp.zeros((SUBLANES, LANES), i32)
        columns = jnp.zeros((SUBLANES, flat), f32)
        for slot in range(slots):
            this = (chosen != 0) & (before == slot)
            ids = jnp.where(
                slot_lane == slot,
                jnp.sum(jnp.where(this, local, 0), axis=1, keepdims=True),
                ids)
            columns = jnp.where(
                flat_lane == slot * rows + sublane,
                jnp.sum(jnp.where(this, mine, 0.0), axis=1, keepdims=True),
                columns)
        experts_ref[...] = ids[0:1, 0:slots]
        weights_ref[...] = jnp.sum(columns, axis=0,
                                   keepdims=True)[:, 0:slots * rows]
        held_ref[...] = jnp.sum(chosen[0:1], axis=1, keepdims=True)

    counted = taken & valid
    load = jnp.sum((counted & held_lane).astype(i32), axis=0, keepdims=True)
    if first:
        load = pltpu.roll(jnp.broadcast_to(load, (SUBLANES, width)),
                          width - first, 1)[0:1]
    load_ref[...] = load[:, 0:count]
    any_held = jnp.max((taken & held_lane).astype(i32), axis=1,
                       keepdims=True)
    none_ref[...] = jnp.sum((valid & (any_held == 0)).astype(i32), axis=0,
                            keepdims=True)
    if zero_experts:
        identity = lane >= experts - zero_experts
        identity_ref[...] = jnp.sum(jnp.where(identity, weights, 0.0),
                                    axis=1, keepdims=True)[0:rows]
        zero_picks_ref[...] = jnp.sum(
            jnp.sum((counted & identity).astype(i32), axis=1, keepdims=True),
            axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=(
    "k", "scoring", "renormalise", "eps", "scale", "first", "count",
    "zero_experts", "interpret"))
def _route_call(logits, bias, valid, *, k: int, scoring: str,
                renormalise: bool, eps: float, scale: float, first: int,
                count: int, zero_experts: int, interpret: bool):
    """Jitted on its own so that the expert layers of one model trace and
    lower the kernel once, not once per layer, and under a name of its own:
    the optimised HLO calls the launch ``_route_call.<n>``. The cost
    estimate is the kernel's true one: XLA places the decode scan's
    asynchronous copies by what the ops between a copy's start and its end
    are said to cost (ops/moe_kernel.py:_call), and the true bytes did best
    for the kernels of this form before (ops/stream_mixer.py)."""
    from jax.experimental.pallas import tpu as pltpu

    rows, experts = logits.shape
    width = _round_up(experts, LANES)
    slots = slots_of(rows, k, count)
    f32, i32 = jnp.float32, jnp.int32
    out_shape = [jax.ShapeDtypeStruct((1, slots), i32),
                 jax.ShapeDtypeStruct((1, slots * rows), f32),
                 jax.ShapeDtypeStruct((1, 1), i32),
                 jax.ShapeDtypeStruct((rows, k), i32),
                 jax.ShapeDtypeStruct((1, count), i32),
                 jax.ShapeDtypeStruct((1, 1), i32)]
    if zero_experts:
        out_shape += [jax.ShapeDtypeStruct((rows, 1), f32),
                      jax.ShapeDtypeStruct((1, 1), i32)]
    operands = (logits,) + (() if bias is None else (bias,)) + (valid,)
    whole = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    moved = sum(a.size * a.dtype.itemsize for a in operands) + sum(
        4 * s.shape[0] * s.shape[1] for s in out_shape)
    return pl.pallas_call(
        functools.partial(
            _kernel, rows=rows, experts=experts, k=k, scoring=scoring,
            renormalise=renormalise, eps=eps, scale=scale, first=first,
            count=count, zero_experts=zero_experts, biased=bias is not None),
        out_shape=out_shape,
        in_specs=[whole() for _ in operands],
        out_specs=[whole() for _ in out_shape],
        scratch_shapes=[pltpu.VMEM((2, SUBLANES, width), f32),
                        pltpu.VMEM((SUBLANES, LANES), i32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_SLACK),
        cost_estimate=pl.CostEstimate(
            flops=SUBLANES * width * (8 * k + 4 * slots),
            transcendentals=rows * experts, bytes_accessed=moved),
        interpret=interpret,
    )(*operands)


def routing(logits: jax.Array, bias: jax.Array | None, valid: jax.Array, *,
            k: int, renormalise: bool, scale: float, scoring: str,
            eps: float, first: int, count: int, zero_experts: int = 0,
            interpret: bool | None = None) -> Step:
    """One launch from the router's float32 ``logits`` ``(rows, experts)``
    to a :class:`Step`: ``ops/moe.py:route``'s picks and weights (``bias``
    ``(experts,)`` in the dtype it is stored in chooses and does not
    weigh), laid out for ``ops/moe_kernel.py:chosen_experts`` as
    ``ops/moe.py:_chosen`` (one row) or ``_block`` (2-8 rows) lays them,
    with ``load_counts``' counts over the rows ``valid`` ``(rows,)`` marks
    and, with ``zero_experts`` (the router's LAST ids), ``identity_part``'s
    weight a row and ``identity_picks``. This chip holds experts ``first ..
    first + count - 1``. A slot behind the held ones reads local id 0 and
    weight 0 (``_block`` leaves the lowest ids nobody chose there; the
    expert kernel reads neither). ``interpret`` is for a run without the
    chip."""
    rows, experts = logits.shape
    if rows > SUBLANES:
        raise ValueError(f"{rows} rows are over one register's {SUBLANES}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out = _route_call(
        logits.astype(jnp.float32),
        None if bias is None else bias.reshape(1, experts),
        valid.astype(jnp.int32).reshape(1, rows), k=int(k),
        scoring=scoring, renormalise=bool(renormalise), eps=float(eps),
        scale=float(scale), first=int(first), count=int(count),
        zero_experts=int(zero_experts), interpret=interpret)
    slot_ids, weights, held, picks, load, none_held, *identity = out
    slots = slot_ids.shape[1]
    return Step(
        slot_ids.reshape(slots),
        weights.reshape(slots) if rows == 1 else weights.reshape(slots,
                                                                 rows),
        held.reshape(1), picks, load.reshape(count), none_held.reshape(()),
        identity[0] if identity else None,
        identity[1].reshape(()) if identity else None)

"""A decode step's sum over the chosen experts held here, as one pipelined
Pallas kernel: one row (a step of one sequence) or a block of 2-8 rows (a
step of several sequences, every distinct expert taking the whole block).

``ops/moe.py:_chosen`` walks the chosen experts in a ``fori_loop`` whose
trip count is dynamic. On the TPU such a loop runs its trips strictly one
after the other: the condition and the slice's offset on the scalar core,
then three dependent fusions, each starting its own read from HBM when the
one before has finished. The stream itself runs at the chip's bandwidth
(826 GB/s on the margin between two expert sizes) but 6-9 us of every trip
pass with nothing in flight (PERF.md section 6, PR 34).

Here the grid runs over the ``k`` chosen slots times the tiles of the
experts' width ``f``. The local ids of the chosen experts (held ones first,
as ``_chosen`` sorts them), their routing weights and the held count are
scalar-prefetched, and the three kernels' ``BlockSpec`` index maps read the
id, so the pipeline fetches the blocks of step ``i + 1`` while step ``i``
multiplies. The grid is static and the held count is not: the ``k - held``
steps with nothing to do come FIRST and repeat the index of the first block
a held slot reads. No read is issued for an unchanged index and their body
is skipped, so they pass while that first read, which the pipeline starts
with the call, is in flight. SwiGLU is separable over ``f`` and the down
product sums over the tiles, so a tile is ``silu(x Wg[:, tile]) * (x Wu[:,
tile])``, cast to the operand dtype, times ``Wd[tile, :]``; the float32 sum
of ``weight * that`` stays in VMEM (the output block, whose index never
changes) and is written once.

A step of several rows (``rows`` = 2-8: the sequences of one request, one
token each) runs the same grid over the step's DISTINCT held experts, at
most ``slots = min(rows * k, held)`` of them. Sorting rows by expert would
buy nothing under one row tile, so every expert multiplies the whole block
``(rows, d)`` (padded to the bf16 sublane tile) and a per-row weight says
what it adds: ``out[r] += W[slot, r] * E_slot(x)[r]``, with ``W[slot, r]``
zero where row ``r`` did not choose the expert. The weights lie flat in
SMEM, ``rows`` a slot; a row that did not choose an expert is SELECTED out,
not multiplied by zero, so an overflowed product of a row that never asked
for it cannot reach that row. One row is ``rows == 1``, ``slots == k``: a
token's picks are distinct already, and its one weight stays a scalar.

Only experts that are chosen and held are read, with one exception: a step
none of whose experts is held still costs the read of ONE tile of local
expert 0 (the pipeline fetches the first step's blocks before it can know),
which no product uses.

Operands in their own dtype into the MXU, float32 accumulation, as
``moe._swiglu``; the dots pin their precision, since Mosaic refuses bf16
operands at a caller's ``default_matmul_precision("highest")``. Not bit
equal to the loop: the float32 sum over ``f`` runs tile by tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: lane width: ``d`` and the ``f`` tile are multiples of it
LANES = 128
#: VMEM the three kernels' blocks may take, double-buffered. The ``f`` tile
#: is the widest that fits: 512 at the first three published shapes (3072 x
#: 1024, 2048 x 512 and 3584 x 1024; 18.9, 12.6 and 22.0 MB), all of 768 at
#: 2048 x 768, and 256 at 7168 x 2048 (22.0 MB), the first under 512.
_WEIGHT_VMEM = 24 * 2 ** 20
#: beside the blocks: x, the output, a tile's float32 intermediates (Mosaic
#: compiles both published shapes with 1 MiB; the decode span does not move
#: between 1, 2 and 4)
_VMEM_SLACK = 4 * 2 ** 20
#: experts' reads XLA is told one call costs, whatever its rows
#: (:func:`_call`)
_COST_EXPERTS = 3
#: rows of one block: the bf16 sublane tile, and the most a call takes
#: (ops/moe.py:choose sends at most one row tile of 8 here)
ROW_BLOCK = 16


def f_tile(d: int, f: int, itemsize: int) -> int | None:
    """The widest tile of ``f`` (all of it, else a divisor that is a
    multiple of the lane width) whose three blocks fit :data:`_WEIGHT_VMEM`
    twice over; None when ``d`` or ``f`` is off the lanes or none fits."""
    if d % LANES or f % LANES:
        return None
    for tiles in range(1, f // LANES + 1):
        tile = f // tiles
        if f % tiles == 0 and tile % LANES == 0 \
                and 2 * 3 * d * tile * itemsize <= _WEIGHT_VMEM:
            return tile
    return None


def _column(weights_ref, first, rows: int, block: int):
    """``(block, 1)`` float32: the ``rows`` SMEM scalars from ``first`` down
    the sublanes, zero under them."""
    row = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    column = jnp.zeros((block, 1), jnp.float32)
    for r in range(rows):
        column = jnp.where(row == r, weights_ref[first + r], column)
    return column


def _kernel(ids_ref, held_ref, weights_ref, x_ref, gate_ref, up_ref,
            down_ref, out_ref, *, limit: float = 0.0):
    step, tile = pl.program_id(0), pl.program_id(1)
    slot = step - (pl.num_programs(0) - held_ref[0])   # idle steps first
    rows = weights_ref.shape[0] // ids_ref.shape[0]    # static: 1, or 2-8

    @pl.when((step == 0) & (tile == 0))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(slot >= 0)
    def _one_tile():
        x = x_ref[...]
        # see flash_attention.py: bf16 products are exact in float32, and
        # Mosaic refuses bf16 operands at a higher precision
        dot = functools.partial(
            jnp.dot, preferred_element_type=jnp.float32,
            precision=(jax.lax.Precision.DEFAULT
                       if x.dtype == jnp.bfloat16 else None))
        if limit:   # the clamp is element-wise, so a tile's is the whole's
            hidden = (jax.nn.silu(jnp.minimum(dot(x, gate_ref[...]), limit))
                      * jnp.clip(dot(x, up_ref[...]), -limit, limit)
                      ).astype(x.dtype)
        else:
            hidden = (jax.nn.silu(dot(x, gate_ref[...]))
                      * dot(x, up_ref[...])).astype(x.dtype)
        if rows == 1:
            out_ref[...] += weights_ref[slot] * dot(hidden, down_ref[...])
        else:
            # a row that did not choose this expert gets nothing from it,
            # not ``0 * y``: its product may have overflowed
            weight = _column(weights_ref, slot * rows, rows, x.shape[0])
            out_ref[...] += jnp.where(
                weight != 0.0, weight * dot(hidden, down_ref[...]), 0.0)


@functools.partial(jax.jit, static_argnames=("tile", "interpret", "limit"))
def _call(ids, held, weights, x, w_gate, w_up, w_down, *, tile: int,
          interpret: bool, limit: float = 0.0):
    """Jitted on its own so that the expert layers of one model trace and
    lower the kernel once per shape, not once per layer.

    The cost estimate is a hint with consequences. XLA's cost analysis sees
    a custom call as free, and its scheduler places the asynchronous copies
    that stream the decode scan's Linear kernels into VMEM by what the ops
    between a copy's start and its end are said to cost. The call's own
    time hardly moves with the hint; where the copies' waits land does.
    ``expand.decode_chunk`` + ``expand.fence_wait`` of one traced request,
    ms, by the experts' reads the call claims (one v5e, my chip runs,
    PR 34; the loop: 689.9 and 845.3):

        claimed     none  0.5    1     2    2.5    3    3.5    4     5    10
        Qwen3-Next  683.2 694.5 704.0 698.9 673.0 661.1 660.6   -   701.4 693.8
        Laguna      831.8   -   829.7 823.8 824.2 828.5 832.5 839.2 835.2 846.6

    Three reads is inside the better stretch of both (the Qwen3-Next share
    holds 2.5 of a token's 10 experts on average and its call takes as long
    as 3.1 reads would; the Laguna share holds 5 and takes 5.6).

    A call of several rows claims the same three, though it reads many more
    (26.5 of Mellum2's 64 for 4 rows of 8 by ``held * (1 - (1 - k /
    experts) ** rows)``; 24.3 measured). The same two spans of a traced
    four-image request, by the reads claimed (one v5e, my chip runs, PR 46;
    the grouped loop: 1 401.3):

        claimed      2       3       4     13.2   26.5 (the shapes')   53
        Mellum2   1 144.7 1 155.0 1 162.5 1 187.2     1 190.2       1 186.2

    The call's own time is the same in every column (842-844 ms); the
    Linears' ``copy-done`` waits are 29 ms shorter at three than at the
    shapes' figure (and 15 shorter again at two, where Qwen3-Next's
    one-row call above does 38 ms worse). And at 13.2 and 26.5 XLA's own
    compiler dies (a null dereference in its memory assignment's
    ``BestFitRepacker``) on the executable benchmarks/verify_reference.py
    builds for that configuration, whole at 592 positions; it compiles at
    3 and at 53. So the rule stays a constant, not the shapes' figure."""
    from jax.experimental.pallas import tpu as pltpu

    slots = ids.shape[0]
    block_rows, d = x.shape
    f = w_gate.shape[2]
    tiles = f // tile

    def block(step, t, ids, held, weights):
        """(local expert, tile of f) grid step ``(step, t)`` reads. The
        ``slots - held`` idle steps come first and sit on the first block
        a held slot will read, which the pipeline fetches as the call
        starts: they pass while that read is in flight."""
        slot = step - (slots - held[0])
        return ids[jnp.maximum(slot, 0)], jnp.where(slot >= 0, t, 0)

    def columns(step, t, *prefetched):
        expert, t = block(step, t, *prefetched)
        return expert, 0, t

    def rows(step, t, *prefetched):
        expert, t = block(step, t, *prefetched)
        return expert, t, 0

    whole = pl.BlockSpec((block_rows, d), lambda step, t, *_: (0, 0))
    itemsize = w_gate.dtype.itemsize
    return pl.pallas_call(
        # the unclamped kernel is the function itself, as it always was
        functools.partial(_kernel, limit=limit) if limit else _kernel,
        out_shape=jax.ShapeDtypeStruct((block_rows, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, tiles),
            in_specs=[whole,
                      pl.BlockSpec((None, d, tile), columns),
                      pl.BlockSpec((None, d, tile), columns),
                      pl.BlockSpec((None, tile, d), rows)],
            out_specs=whole),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * 3 * d * tile * itemsize + _VMEM_SLACK),
        cost_estimate=pl.CostEstimate(
            flops=6 * _COST_EXPERTS * d * f,
            transcendentals=_COST_EXPERTS * f,
            bytes_accessed=3 * _COST_EXPERTS * d * f * itemsize),
        interpret=interpret,
    )(ids, held, weights, x, w_gate, w_up, w_down)


def chosen_experts(x: jax.Array, experts: jax.Array, weights: jax.Array,
                   held: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                   w_down: jax.Array, *,
                   interpret: bool | None = None,
                   limit: float = 0.0) -> jax.Array:
    """``sum_j weights[j] E_experts[j](x)`` over the first ``held`` of the
    slots: ``x`` ``(rows, d)`` with ``rows`` at most :data:`ROW_BLOCK`,
    ``experts`` ``(slots,)`` local ids with the held ones first, ``held``
    an int32 scalar, the kernels stacked as ``moe.routed_experts`` takes
    them. ``weights`` is float32 ``(slots,)`` for one row and ``(slots,
    rows)`` for several, zero where a row did not choose the slot's expert
    (such a row gets nothing from it). Float32 ``(rows, d)``.
    ``interpret`` is for a compile without the chip; ``limit`` over 0
    clamps each expert's SwiGLU as ``moe._swiglu`` does."""
    rows = x.shape[0]
    _, d, f = w_gate.shape
    tile = f_tile(d, f, w_gate.dtype.itemsize)
    if tile is None:
        raise ValueError(f"experts of {d} x {f} do not tile")
    if rows > ROW_BLOCK:
        raise ValueError(f"{rows} rows are over one block of {ROW_BLOCK}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    weights = weights.astype(jnp.float32)
    if rows > 1:
        x = jnp.pad(x, ((0, ROW_BLOCK - rows), (0, 0)))
        weights = weights.reshape(-1)
    out = _call(experts.astype(jnp.int32),
                jnp.reshape(held, (1,)).astype(jnp.int32), weights, x,
                w_gate, w_up, w_down, tile=tile, interpret=interpret,
                limit=limit)
    return out if rows == 1 else out[:rows]

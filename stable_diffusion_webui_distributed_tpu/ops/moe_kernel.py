"""A decode step's sum over the chosen experts held here, as one Pallas
kernel that schedules its own reads: one row (a step of one sequence) or a
block of 2-8 rows (a step of several sequences, every distinct expert
taking the whole block).

``ops/moe.py:_chosen`` walks the chosen experts in a ``fori_loop`` whose
trip count is dynamic. On the TPU such a loop runs its trips strictly one
after the other: the condition and the slice's offset on the scalar core,
then three dependent fusions, each starting its own read from HBM when the
one before has finished. The stream itself runs at the chip's bandwidth
(826 GB/s on the margin between two expert sizes) but 6-9 us of every trip
pass with nothing in flight (PERF.md section 6, PR 34).

Here the three stacked kernels stay in HBM and ONE grid step walks the
call's reads itself. The local ids of the chosen experts (held ones first,
as ``_chosen`` sorts them), their routing weights and the held count are
scalar-prefetched; an expert's width ``f`` is cut into blocks (:func:`ring`)
and a ``fori_loop`` whose trip count is ``held x blocks`` waits for a
block's three copies, multiplies, and before the wait has started the next
block's copies (``make_async_copy`` under DMA semaphores) into the slot of
the ring the block before multiplied from: no moment of a call has no read
outstanding. SwiGLU is separable over ``f`` and the down product sums over
the blocks, so a block is ``silu(x Wg[:, block]) * (x Wu[:, block])``, cast
to the operand dtype, times ``Wd[block, :]``; the float32 sum of ``weight *
that`` stays in VMEM (the output block) and is written once.

Only experts that are chosen and held are read, and a step none of whose
experts is held reads NOTHING: the trip count is the condition, no copy is
started and the zeroed output is the answer. (Until PR 70 a ``BlockSpec``
pipeline over a static grid of ``k`` slots fetched its first step's blocks
before it could know: one block of local expert 0, 11 MB at Xing4.0's
shape, in the 30.6 % of that share's calls that hold nothing.) A block is
as wide as the ring's two slots allow, since the same bytes in more copies
stream slower (:func:`ring`).

A step of several rows (``rows`` = 2-8: the sequences of one request, one
token each) walks the step's DISTINCT held experts, at most ``slots =
min(rows * k, held)`` of them. Sorting rows by expert would buy nothing
under one row tile, so every expert multiplies the whole block ``(rows,
d)`` (padded to the bf16 sublane tile) and a per-row weight says what it
adds: ``out[r] += W[slot, r] * E_slot(x)[r]``, with ``W[slot, r]`` zero
where row ``r`` did not choose the expert. The weights lie flat in SMEM,
``rows`` a slot; a row that did not choose an expert is SELECTED out, not
multiplied by zero, so an overflowed product of a row that never asked for
it cannot reach that row. One row is ``rows == 1``, ``slots == k``: a
token's picks are distinct already, and its one weight stays a scalar.

Operands in their own dtype into the MXU, float32 accumulation, as
``moe._swiglu``; the dots pin their precision, since Mosaic refuses bf16
operands at a caller's ``default_matmul_precision("highest")``. Not bit
equal to the loop: the float32 sum over ``f`` runs block by block.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: lane width: ``d`` and every block of ``f`` are multiples of it
LANES = 128
#: VMEM the ring of blocks may take: :data:`_RING_BUFFERS` slots of the
#: widest block's three slabs (what the ``BlockSpec`` pipeline's two
#: buffers a weight took before)
_WEIGHT_VMEM = 24 * 2 ** 20
#: beside the ring: x, the output, a block's float32 intermediates (Mosaic
#: compiles every published shape with 1 MiB; the decode span does not move
#: between 1, 2 and 4)
_VMEM_SLACK = 4 * 2 ** 20
#: slots of the ring. A block's read is started before the wait for the
#: block in front of it, so two reads are outstanding at every boundary; a
#: third and a fourth slot changed no shape's call by more than its noise
#: (PERF.md section 6, PR 70)
_RING_BUFFERS = 2
#: experts whose rows are longer than this (GigaChat3.5's 7168, LongCat's
#: 6144) are read a lane width a block: 3-4 % less a call than the 256
#: columns that fit. The shorter ones take the widest block that fits: the
#: same bytes in more, narrower copies stream up to 9 % slower (3072 and
#: 3584 rows; nothing to tell apart at 2048-2304 and 4096; my chip runs,
#: PR 70)
_LONG_ROWS = 4096
#: experts' reads XLA is told one call costs, whatever its rows
#: (:func:`_call`)
_COST_EXPERTS = 3
#: rows of one block: the bf16 sublane tile, and the most a call takes
#: (ops/moe.py:choose sends at most one row tile of 8 here)
ROW_BLOCK = 16


class Ring(NamedTuple):
    """How a call reads its experts' width ``f``: every expert in ``wides``
    blocks of ``wide`` columns, through ``buffers`` slots of VMEM."""
    buffers: int
    wide: int
    wides: int

    def vmem_bytes(self, d: int, itemsize: int) -> int:
        return self.buffers * 3 * d * self.wide * itemsize


def ring(d: int, f: int, itemsize: int) -> Ring | None:
    """The blocks of ``f`` a call walks, from what it sees; None when ``d``
    or ``f`` is off the lanes or no block fits. A block is the widest
    divisor of ``f`` on the lanes (all of it where that fits) whose three
    slabs fit :data:`_WEIGHT_VMEM` once a slot of the ring, one lane width
    where the rows are over :data:`_LONG_ROWS`."""
    if d % LANES or f % LANES:
        return None
    lanes = f // LANES
    slab = 3 * d * LANES * itemsize         # one lane width of an expert
    if _RING_BUFFERS * slab > _WEIGHT_VMEM:
        return None
    widest = 1 if d > _LONG_ROWS else _WEIGHT_VMEM // (_RING_BUFFERS * slab)
    wide = next(n for n in range(min(lanes, widest), 0, -1)
                if lanes % n == 0)
    return Ring(_RING_BUFFERS, wide * LANES, lanes // wide)


def f_tile(d: int, f: int, itemsize: int) -> int | None:
    """The widest block of ``f`` a call reads (:func:`ring`); None when
    these widths do not tile."""
    blocks = ring(d, f, itemsize)
    return None if blocks is None else blocks.wide


def _column(weights_ref, first, rows: int, block: int):
    """``(block, 1)`` float32: the ``rows`` SMEM scalars from ``first`` down
    the sublanes, zero under them."""
    row = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    column = jnp.zeros((block, 1), jnp.float32)
    for r in range(rows):
        column = jnp.where(row == r, weights_ref[first + r], column)
    return column


def _kernel(ids_ref, held_ref, weights_ref, x_ref, gate_hbm, up_hbm,
            down_hbm, out_ref, gate_ref, up_ref, down_ref, landed, *,
            blocks: Ring, limit: float = 0.0):
    from jax.experimental.pallas import tpu as pltpu

    rows = weights_ref.shape[0] // ids_ref.shape[0]    # static: 1, or 2-8
    reads = held_ref[0] * blocks.wides      # nothing held: nothing read
    out_ref[...] = jnp.zeros_like(out_ref)

    def copies(read):
        """The three copies of the call's ``read``: block ``read %
        wides`` of the expert in slot ``read // wides``, into the ring."""
        slot = jax.lax.div(read, blocks.wides)
        expert, buffer = ids_ref[slot], jax.lax.rem(read, blocks.buffers)
        columns = pl.ds(pl.multiple_of(
            (read - slot * blocks.wides) * blocks.wide, LANES), blocks.wide)
        return (pltpu.make_async_copy(gate_hbm.at[expert, :, columns],
                                      gate_ref.at[buffer],
                                      landed.at[0, buffer]),
                pltpu.make_async_copy(up_hbm.at[expert, :, columns],
                                      up_ref.at[buffer],
                                      landed.at[1, buffer]),
                pltpu.make_async_copy(down_hbm.at[expert, columns, :],
                                      down_ref.at[buffer],
                                      landed.at[2, buffer]))

    def start(read):
        for copy in copies(read):
            copy.start()

    for read in range(blocks.buffers - 1):
        pl.when(read < reads)(functools.partial(start, jnp.int32(read)))

    def one_block(out_ref, read, _):
        # started before the wait: the buffer it lands in is the one the
        # block before multiplied from, and no moment has no read in flight
        ahead = read + blocks.buffers - 1
        pl.when(ahead < reads)(lambda: start(ahead))
        for copy in copies(read):
            copy.wait()
        slot = jax.lax.div(read, blocks.wides)
        buffer = jax.lax.rem(read, blocks.buffers)
        x = x_ref[...]
        gate, up, down = gate_ref[buffer], up_ref[buffer], down_ref[buffer]
        # see flash_attention.py: bf16 products are exact in float32, and
        # Mosaic refuses bf16 operands at a higher precision
        dot = functools.partial(
            jnp.dot, preferred_element_type=jnp.float32,
            precision=(jax.lax.Precision.DEFAULT
                       if x.dtype == jnp.bfloat16 else None))
        if limit:   # the clamp is element-wise: a block's is the whole's
            hidden = (jax.nn.silu(jnp.minimum(dot(x, gate), limit))
                      * jnp.clip(dot(x, up), -limit, limit)
                      ).astype(x.dtype)
        else:
            hidden = (jax.nn.silu(dot(x, gate))
                      * dot(x, up)).astype(x.dtype)
        if rows == 1:
            out_ref[...] += weights_ref[slot] * dot(hidden, down)
        else:
            # a row that did not choose this expert gets nothing from it,
            # not ``0 * y``: its product may have overflowed
            weight = _column(weights_ref, slot * rows, rows, x.shape[0])
            out_ref[...] += jnp.where(
                weight != 0.0, weight * dot(hidden, down), 0.0)

    # the body is handed the block it accumulates into
    jax.lax.fori_loop(0, reads, functools.partial(one_block, out_ref), None)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret", "limit"))
def _call(ids, held, weights, x, w_gate, w_up, w_down, *, blocks: Ring,
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

    block_rows, d = x.shape
    f = w_gate.shape[2]
    dtype = w_gate.dtype
    whole = pl.BlockSpec((block_rows, d), lambda step, *_: (0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, blocks=blocks, limit=limit),
        out_shape=jax.ShapeDtypeStruct((block_rows, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole, in_hbm, in_hbm, in_hbm],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((blocks.buffers, d, blocks.wide), dtype),
                pltpu.VMEM((blocks.buffers, d, blocks.wide), dtype),
                pltpu.VMEM((blocks.buffers, blocks.wide, d), dtype),
                pltpu.SemaphoreType.DMA((3, blocks.buffers))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=blocks.vmem_bytes(d, dtype.itemsize)
            + _VMEM_SLACK),
        cost_estimate=pl.CostEstimate(
            flops=6 * _COST_EXPERTS * d * f,
            transcendentals=_COST_EXPERTS * f,
            bytes_accessed=3 * _COST_EXPERTS * d * f * dtype.itemsize),
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
    blocks = ring(d, f, w_gate.dtype.itemsize)
    if blocks is None:
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
                w_gate, w_up, w_down, blocks=blocks, interpret=interpret,
                limit=limit)
    return out if rows == 1 else out[:rows]

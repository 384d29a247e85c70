"""The forked recurrent step of the gated delta rule (ops/delta_rule.py:
``recurrent_step_each``) as one Pallas kernel that holds a head's state in
VMEM across its decay, its write and its read.

The step is five element-wise lines over a ``(B, H, K, V)`` float32 state,
and the sum over ``K`` that gives ``seen`` must end before the write can
start. XLA therefore makes two fusions of a layer, each taking the state as
a parameter: one decays it and reduces to ``seen``, the other reads it
AGAIN, decays it again, writes ``k u^T`` and reads out. Two reads and one
write of the state where the mathematics needs one of each: at ``(4, 64,
128, 128)`` 25.5 + 57.1 us a layer a step inside the GigaChat3.5 share's
decode scan (PERF.md section 6, PR 62).

Here the grid runs over (sequence, block of heads). A grid step's block of
``(heads, K, V)`` states is read into VMEM once, every head of it goes
through all five lines there, in float32 on the VPU exactly as
``recurrent_step`` writes them (no product goes to the MXU), and the block
is stored once. The state is aliased in to out, so a decode scan's donated
carry is updated in place. A masked row (``g = 0``, ``beta = 0``) leaves
its state bit-equal: ``S * 1 + k * 0``. The call takes 57.1 us there: what
33.5 MB take through the chip's DMA engines whichever way reads and writes
are ordered (54-56 us for a kernel that only copies; 590-620 GB/s, not the
750 the Linears' read-only streams reach), so the VPU work is hidden and
what is left of the step is its bytes.

How the small operands are laid, since a ``(K, V)`` tile takes a key from
the sublane axis and everything else from the lanes:

- ``k`` and ``q`` come as ``(B, H, K)`` in blocks of ``(heads, K)`` and are
  transposed inside the kernel, one ``(heads, K)`` to ``(K, heads)`` a grid
  step; column ``h`` then broadcasts down the lanes. Handed as ``(..., K,
  1)`` they would be padded 128-fold in HBM and in VMEM and cost as many
  bytes as the state.
- ``exp(g)`` and ``beta`` come as rows along ``V`` (``(B, H, V)``, 128 KB
  at the published shape): Mosaic does not broadcast a ``(1, 1)`` value over
  both sublanes and lanes, a row broadcasts down the sublanes.
- ``v``, ``u``, ``seen`` and ``o`` are rows along ``V`` already.

The sums over ``K`` run sublane tile by sublane tile; on the chip the state
and ``o`` came out bit-equal to the element-wise step's after 256 steps at
the published shape, in interpret mode on a CPU within 5e-7.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: lane width: ``V`` is a multiple of it
LANES = 128
#: sublane tile of float32: ``K`` and a block's heads are multiples of it
SUBLANES = 8
#: VMEM a grid step's state blocks may take, in and out, double-buffered
_STATE_VMEM = 8 * 2 ** 20
#: beside them: the rows and keys of a block, a head's temporaries
_VMEM_SLACK = 4 * 2 ** 20


def head_block(heads: int, k_dim: int, v_dim: int) -> int | None:
    """Heads of one grid step: the most (a multiple of the sublane tile that
    divides ``heads``: a block of ``k`` is ``(heads, K)``) whose states fit
    :data:`_STATE_VMEM` four times over; None when the shape does not tile
    (``V`` off the lanes, ``K`` or ``heads`` off the sublanes)."""
    if v_dim % LANES or k_dim % SUBLANES or heads % SUBLANES:
        return None
    fitting = [block for block in range(SUBLANES, heads + 1, SUBLANES)
               if heads % block == 0
               and 4 * block * k_dim * v_dim * 4 <= _STATE_VMEM]
    return max(fitting) if fitting else None


def _kernel(state_ref, q_ref, k_ref, v_ref, decay_ref, beta_ref,
            out_ref, after_ref):
    heads = state_ref.shape[0]
    keys = k_ref[...].T                        # (K, heads): a head a column
    queries = q_ref[...].T
    for h in range(heads):
        row = slice(h, h + 1)
        state = state_ref[h] * decay_ref[row, :]
        key = keys[:, row]                     # (K, 1), down the lanes
        seen = jnp.sum(state * key, axis=0, keepdims=True)      # S^T k
        u = beta_ref[row, :] * (v_ref[row, :] - seen)
        state = state + key * u
        after_ref[h] = state
        out_ref[row, :] = jnp.sum(state * queries[:, row], axis=0,
                                  keepdims=True)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _call(state, q, k, v, decay, beta, *, block: int, interpret: bool):
    """Jitted on its own so that the delta layers of one model trace and
    lower the kernel once per shape, not once per layer (as
    ops/moe_kernel.py:_call). The cost estimate is the call's true bytes,
    the state read and written: XLA sees a custom call as free and places
    the decode scan's asynchronous weight copies by what the ops between a
    copy's start and its end are said to cost."""
    from jax.experimental.pallas import tpu as pltpu

    sequences, heads, k_dim, v_dim = state.shape

    def rows(width):
        return pl.BlockSpec((None, block, width), lambda b, h: (b, h, 0))

    states = pl.BlockSpec((None, block, k_dim, v_dim),
                          lambda b, h: (b, h, 0, 0))
    return pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct((sequences, heads, v_dim),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)),
        grid=(sequences, heads // block),
        in_specs=[states, rows(k_dim), rows(k_dim), rows(v_dim), rows(v_dim),
                  rows(v_dim)],
        out_specs=(rows(v_dim), states),
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=4 * block * k_dim * v_dim * 4 + _VMEM_SLACK),
        cost_estimate=pl.CostEstimate(
            flops=7 * state.size, transcendentals=0,
            bytes_accessed=2 * state.size * 4),
        interpret=interpret,
    )(state, q, k, v, decay, beta)


def recurrent_step_each(state, q, k, v, g, beta, *,
                        interpret: bool | None = None):
    """``delta_rule.recurrent_step_each`` through the kernel: ``state``
    ``(B, H, K, V)`` float32; ``q``, ``k`` ``(B, H, K)``; ``v`` ``(B, H,
    V)``; ``g``, ``beta`` ``(B, H)``. Returns ``(o (B, H, V), state)``.
    ``interpret`` is for a compile without the chip."""
    _, heads, k_dim, v_dim = state.shape
    block = head_block(heads, k_dim, v_dim)
    if block is None or state.dtype != jnp.float32:
        raise ValueError(f"a {state.dtype} state of {state.shape} "
                         "does not tile")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f32 = jnp.float32

    def row(x):
        return jnp.broadcast_to(x.astype(f32)[..., None], v.shape)

    return _call(state, q.astype(f32), k.astype(f32), v.astype(f32),
                 row(jnp.exp(g)), row(beta), block=block,
                 interpret=interpret)

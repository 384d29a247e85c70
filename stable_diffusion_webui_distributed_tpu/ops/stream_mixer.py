"""A residual-stream mixer of a decode step as two Pallas kernels: what it
does before its sublayer, and the write-back after it.

``models/lm.py:StreamMixer`` in XLA is about a hundred launches a mixer on
the TPU (the norm, ``phi``'s product, the gates, four small fusions for each
of Sinkhorn's twenty iterations, the streams' read, eight more for the
write-back, which takes ``H_res`` apart into sixteen scalars), 25 us with
nothing streaming, forty times a decoded token. Here a mixer is two
launches, 3.1 us together in the decode scan of the 20-layer share (PERF.md
section 6, PR 36), and every value between the steps stays in VMEM or in
vector registers. Before the sublayer (:func:`mixer`):

1. one pass over the ``(streams, hidden)`` state gives the sum of its
   squares and, against ``phi`` laid with the state's axis in the lanes, the
   ``streams * (streams + 2)`` raw products; the norm's ``rsqrt`` is one
   number a token and multiplies the sums, not the state. ``phi`` is read in
   the dtype it is stored in and widened here (exact), multiplied on the VPU
   in float32 and summed in float32: nothing goes through the MXU, whose
   passes would round the operands;
2. the products are laid as one ``(8, 128)`` tile, row ``i`` holding
   ``H_res[i, :]``'s projections, then ``H_post[i]``'s and ``H_pre[i]``'s;
   ``alpha`` and the biases arrive as two tiles in that layout;
3. the sigmoids, the clamped ``exp``, and every one of Sinkhorn's
   iterations (columns, then rows, a true division, ``eps`` added to the sum)
   on that tile, zero outside the matrix;
4. the read ``sum_j H_pre[j] X[j]``.

After it (:func:`write_back`) ``H_res X + H_post (outer) out`` from the same
tile.

Every operand is a whole block in VMEM and neither call has a grid: XLA
brings ``phi`` (0.7 MB as bf16) on chip with its own asynchronous copy,
ahead of the call, as it does the scan's other small operands. Copies
started inside the kernel, all with the call and waited for chunk by chunk,
measured the same decode span and 0.2 us more a mixer, so they went; so did
a cost estimate of 3, 8 and 20 times the true bytes (1 589.7, 1 600.8,
1 604.3 ms for 1 587.0).

Not bit equal to the XLA form: the sums run in another order and the norm's
factor multiplies 24 sums, not 14 336 terms. Float32 throughout, as there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

KERNEL, LOOP = "kernel", "loop"
#: a float32 vector register: the mixer's maps of one token are one
LANES, SUBLANES = 128, 8
#: VMEM beside ``phi``: the state, the norm's weight, the gates' tiles, the
#: outputs (0.2 MB) and what the compiler spills of the body
_VMEM_SLACK = 2 * 2 ** 20


def choose(platform: str, tokens: int, streams: int, hidden: int, *,
           meshed: bool = False, sinkhorn_dtype=jnp.float32) -> str:
    """``"kernel"`` or ``"loop"`` for one mixer's call, from what the call
    shows. The kernel wants a decode step on a TPU, several streams whose
    maps fit one register tile, a hidden size on the lanes, a program no
    mesh partitions (``pjit`` would run the call whole on every chip) and
    Sinkhorn in float32: a lower ``sinkhorn_dtype`` is the references'
    control and keeps the form it is a control of."""
    if (platform == "tpu" and tokens == 1 and 1 < streams <= SUBLANES
            and hidden % LANES == 0 and not meshed
            and jnp.dtype(sinkhorn_dtype) == jnp.float32):
        return KERNEL
    return LOOP


def _interpreted(interpret: bool | None) -> bool:
    """Off the chip the kernels run in Pallas' interpreter."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _kernel(x_ref, scale_ref, gates_ref, phi_ref, read_ref, maps_ref, *,
            eps: float, hc_eps: float, clamp, iters: int):
    n, hidden = x_ref.shape
    rows = phi_ref.shape[0]
    f32 = jnp.float32

    # one pass over the state: its squares, and its products with phi
    squares = jnp.zeros((1, LANES), f32)
    products = jnp.zeros((rows, LANES), f32)
    for j in range(n):
        for tile in range(hidden // LANES):
            at = pl.ds(tile * LANES, LANES)
            x = x_ref[pl.ds(j, 1), at].astype(f32)
            squares += x * x
            products += (x * scale_ref[pl.ds(j, 1), at].astype(f32)) \
                * phi_ref[:, pl.ds(j * hidden + tile * LANES,
                                   LANES)].astype(f32)
    factor = jax.lax.rsqrt(
        jnp.sum(squares, axis=-1, keepdims=True) / (n * hidden) + eps)
    sums = jnp.sum(products, axis=-1, keepdims=True) * factor    # (rows, 1)

    # row i of the tile: H_res[i, :], then H_post[i], then H_pre[i]
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    sublane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    across = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    tile = jnp.zeros((SUBLANES, LANES), f32)
    for i in range(n):
        mine = (lane == row - i * (n + 2)) & (lane < n + 2)
        tile += jnp.where(
            sublane == i,
            jnp.sum(jnp.where(mine, sums, 0.0), axis=0, keepdims=True), 0.0)
    z = tile * gates_ref[0] + gates_ref[1]
    gate = jax.nn.sigmoid(z)
    rows_in = sublane < n
    inside = rows_in & (across < n)
    m = jnp.where(inside, jnp.exp(jnp.clip(z, *clamp)), 0.0)

    def columns_then_rows(_, m):
        # the tile's zeros are selected, not divided: a compiler that folds
        # a chain of divisions into one would divide them by eps ** 40 = 0
        m = jnp.where(inside, m / (jnp.sum(m, axis=0, keepdims=True)
                                   + hc_eps), 0.0)
        return jnp.where(inside, m / (jnp.sum(m, axis=1, keepdims=True)
                                      + hc_eps), 0.0)

    m = jax.lax.fori_loop(0, iters, columns_then_rows, m, unroll=True)
    maps_ref[...] = jnp.where(
        inside, m, jnp.where(rows_in & (across == n), 2.0 * gate,
                             jnp.where(rows_in & (across == n + 1), gate,
                                       0.0)))

    read = jnp.zeros((1, hidden), f32)
    for j in range(n):
        h_pre = jnp.sum(jnp.where((sublane == j) & (across == n + 1), gate,
                                  0.0), keepdims=True)
        read += h_pre * x_ref[pl.ds(j, 1), :].astype(f32)
    read_ref[...] = read


@functools.partial(jax.jit, static_argnames=(
    "eps", "hc_eps", "clamp", "iters", "interpret"))
def _mixer_call(x, scale, gates, phi_t, *, eps: float, hc_eps: float, clamp,
                iters: int, interpret: bool):
    """Jitted on its own so that the forty mixers of one model trace and
    lower the kernel once, not once a mixer."""
    from jax.experimental.pallas import tpu as pltpu

    n, hidden = x.shape
    rows = phi_t.shape[0]
    phi_bytes = phi_t.size * phi_t.dtype.itemsize
    whole = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps, hc_eps=hc_eps, clamp=clamp,
                          iters=iters),
        out_shape=(jax.ShapeDtypeStruct((1, hidden), jnp.float32),
                   jax.ShapeDtypeStruct((SUBLANES, LANES), jnp.float32)),
        in_specs=[whole(), whole(), whole(), whole()],
        out_specs=(whole(), whole()),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=phi_bytes + _VMEM_SLACK),
        cost_estimate=pl.CostEstimate(
            flops=2 * (rows + 2) * n * hidden + 40 * iters * n * n,
            transcendentals=3 * SUBLANES * LANES,
            bytes_accessed=phi_bytes + x.size * x.dtype.itemsize
            + scale.size * scale.dtype.itemsize + 4 * hidden
            + 3 * 4 * SUBLANES * LANES),
        interpret=interpret,
    )(x, scale, gates, phi_t)


def pack(phi: jax.Array, alpha: jax.Array, b_pre: jax.Array,
         b_post: jax.Array, b_res: jax.Array):
    """(``phi`` as the kernel reads it, the gates' two tiles): none of it
    depends on the token, so a decode scan makes it once a chunk, outside
    the loop (models/lm.py:mixer_operands). ``phi`` ``(streams * hidden, n*n + 2n)`` keeps
    its dtype and becomes ``(n * (n + 2), streams * hidden)``, the long axis
    in the lanes, its rows in the order of the tile: row ``i * (n + 2) + c``
    is ``H_res[i, c]``'s column for ``c < n``, then ``H_post[i]``'s and
    ``H_pre[i]``'s. The tiles ``(2, 8, 128)`` float32 hold, in that layout,
    what multiplies a projection (``alpha``) and what is added to it."""
    n = b_pre.shape[0]
    order = [2 * n + i * n + c if c < n else (n + i if c == n else i)
             for i in range(n) for c in range(n + 2)]
    f32 = jnp.float32
    alpha = alpha.astype(f32)
    scale = jnp.concatenate(
        [jnp.full((n, n), alpha[2]), jnp.full((n, 1), alpha[1]),
         jnp.full((n, 1), alpha[0])], axis=1)
    bias = jnp.concatenate(
        [b_res.astype(f32), b_post.astype(f32)[:, None],
         b_pre.astype(f32)[:, None]], axis=1)
    gates = jnp.zeros((2, SUBLANES, LANES), f32).at[:, :n, :n + 2].set(
        jnp.stack([scale, bias]))
    return phi.T[jnp.asarray(order)], gates


def mixer(streams: jax.Array, norm_scale: jax.Array, packed, *, eps: float,
          hc_eps: float, clamp, iters: int, interpret: bool | None = None):
    """``(read (1, hidden), the maps' tile (8, 128))`` of one token's
    ``streams`` ``(1, n, hidden)``, float32: ``sum_j H_pre[j] streams[j]``
    and, in the tile's first ``n`` rows, ``H_res`` in columns ``0 .. n - 1``,
    ``H_post`` in column ``n`` and ``H_pre`` in column ``n + 1``
    (:func:`maps_of` cuts them out), zero elsewhere. ``norm_scale`` ``(n *
    hidden,)`` is the norm's weight in its stored dtype, ``packed`` what
    :func:`pack` made of the mixer's parameters. ``interpret`` is for a
    compile without the chip."""
    _, n, hidden = streams.shape
    phi_t, gates = packed
    return _mixer_call(
        streams[0], norm_scale.reshape(n, hidden), gates, phi_t,
        eps=float(eps), hc_eps=float(hc_eps),
        clamp=(float(clamp[0]), float(clamp[1])), iters=int(iters),
        interpret=_interpreted(interpret))


def maps_of(tile: jax.Array, n: int):
    """``(h_pre (1, n), h_post (1, n), h_res (1, n, n))`` out of the
    kernel's tile."""
    return tile[None, :n, n + 1], tile[None, :n, n], tile[None, :n, :n]


def _write_kernel(x_ref, tile_ref, out_ref, new_ref):
    n = x_ref.shape[0]
    tile = tile_ref[...]
    across = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)

    def column(c):      # (8, 1): the tile's column c, zero under row n
        return jnp.sum(jnp.where(across == c, tile, 0.0), axis=1,
                       keepdims=True)

    new = column(n) * out_ref[...].astype(jnp.float32)
    for j in range(n):
        new += column(j) * x_ref[pl.ds(j, 1), :].astype(jnp.float32)
    new_ref[...] = new[:n].astype(new_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _write_call(x, tile, out, *, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    whole = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _write_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[whole(), whole(), whole()],
        out_specs=whole(),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_SLACK),
        cost_estimate=pl.CostEstimate(
            flops=2 * (x.shape[0] + 1) * x.size, transcendentals=0,
            bytes_accessed=2 * x.size * x.dtype.itemsize
            + out.size * out.dtype.itemsize + tile.size * 4),
        interpret=interpret,
    )(x, tile, out)


def write_back(streams: jax.Array, tile: jax.Array, out: jax.Array, *,
               interpret: bool | None = None) -> jax.Array:
    """``H_res X + H_post (outer) out`` after the sublayer, from the tile
    :func:`mixer` gave before it: ``streams`` ``(1, n, hidden)``, ``out``
    ``(1, hidden)``; the streams as they go on, in ``streams``' dtype.
    XLA makes eight launches of it (it takes the matrix apart into sixteen
    scalars first); here it is one."""
    return _write_call(streams[0], tile, out,
                       interpret=_interpreted(interpret))[None]

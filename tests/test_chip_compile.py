"""The Pallas kernels (both attention kernels, the chosen experts' sum of a
decode step, a residual-stream mixer's two, the forked step of the gated
delta rule) and one folded upsample site, compiled by the TPU's own
compiler.

Interpret mode (tests/test_ops.py, tests/test_ragged.py) checks the math;
it cannot see what Mosaic refuses: a slice off the tiling, too much VMEM.
The TPU compiler is installed here and compiles for a chip that is
described and not attached, so the kernels of the main path are lowered
with ``interpret=False`` at the real self-attention shapes of SD1.5 512²
and SDXL 1024² (and the cross-attention shapes over the crossover) for one
chip of a ``v5e:2x2`` host. Nothing runs: a pass
says the chip's compiler accepts the kernel, not that its result is right
(chip_smoke.py compares results on the chip).
"""

import math
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from stable_diffusion_webui_distributed_tpu.ops import (
    delta_kernel, moe_kernel, stream_mixer,
)
from stable_diffusion_webui_distributed_tpu.ops.flash_attention import (
    flash_attention,
)
from stable_diffusion_webui_distributed_tpu.ops.ragged_attention import (
    _ragged_bhtd,
)
from stable_diffusion_webui_distributed_tpu.models.unet import (
    GroupNorm32, ResBlock,
)
from stable_diffusion_webui_distributed_tpu.ops.upsample import UpsampleConv

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chip_smoke import CROSS_CASES, KERNEL_CASES  # noqa: E402  (repo root on path)

#: (batch*heads, tokens, head_dim) of every UNet self-attention at CFG
#: batch 2 — the cases chip_smoke.py runs on the chip: (16, 4096, 40),
#: (16, 1024, 80), (16, 256, 160), (16, 64, 160) for SD1.5 at 512²;
#: (20, 4096, 64), (40, 1024, 64) for SDXL at 1024²; (64, 4096, 40) and
#: (64, 1024, 80) for SD1.5's tiled sites at CFG batch 8
SHAPES = [(b * h, t, d) for b, h, t, d in KERNEL_CASES]


@pytest.fixture(scope="module")
def one_chip():
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next run warns and
    compiles again): keep the cache off around these."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


#: a gather as an HLO instruction (the text also names source frames)
_GATHER = re.compile(r" gather\(")


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("b,h,t,d", KERNEL_CASES)
def test_flash_kernel_compiles_for_v5e(one_chip, b, h, t, d):
    """Through the public entry point, with the tiles it takes from the
    shape: head_dim 64 rides the lanes two heads to a block; 40, 80 and
    160 all eight heads in one block of the whole width, whose slices at
    lane offsets 40, 80, 120 ... cross the 128-lane tiles."""
    qkv = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b,h,t,d,s", CROSS_CASES)
def test_flash_kernel_compiles_over_a_context_off_the_tiling(one_chip, b, h,
                                                             t, d, s):
    """Cross-attention over 231 and 77 keys (SD1.5's expanded context at
    CFG batch 2 and 8, SDXL's at 2 and 4): the keys padded and masked, under
    the jitted entry of its own name."""
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False), q, kv, kv)
    assert "tpu_custom_call" in text
    assert "_tiled_keys" in text and not re.search(r"%_tiled(\.\d+)? =", text)


#: a float32 array of batch x heads x 4096 queries: the score matrix
_SCORES = re.compile(r"f32\[8,8,4096,\d+\]")
#: q or the result copied through HBM laid out by head
_Q_BY_HEAD = re.compile(
    r"= \w+\[8,(?:4096,8|8,4096),40\]\S* (?:copy|transpose)\(")


def test_sd15_cross_site_holds_no_score_matrix(one_chip):
    """A 64x64 ``attn2`` site of SD1.5 in its context (the q and kv
    projections, the attention, ``out_proj`` and the residual) at CFG batch
    8 over 231 keys: through the kernel no ``f32[8,8,4096,231]`` array is
    written, through XLA's attention it is (243 MB, and the bf16
    probabilities behind it)."""
    b, h, t, d, s, c, ctx = 8, 8, 4096, 40, 231, 320, 768

    def on_chip(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def site(attention):
        def fn(x, context, w_q, w_kv, w_out):
            q = (x @ w_q).reshape(b, t, h, d)
            k, v = (a.reshape(b, s, h, d)
                    for a in jnp.split(context @ w_kv, 2, axis=-1))
            return attention(q, k, v).reshape(b, t, c) @ w_out + x
        return fn

    args = (on_chip(b, t, c), on_chip(b, s, ctx), on_chip(c, c),
            on_chip(ctx, 2 * c), on_chip(c, c))
    text = _compiled_text(
        site(lambda q, k, v: flash_attention(q, k, v, interpret=False)),
        *args)
    assert "tpu_custom_call" in text
    assert not _SCORES.search(text)
    assert not _Q_BY_HEAD.search(text)
    assert _SCORES.search(_compiled_text(
        site(jax.nn.dot_product_attention), *args))


#: a copy or a transpose that writes a four-dimensional array: q, k, v or
#: the output laid out by head, (B, T, H, D) or (B, H, T, D)
_BY_HEAD_COPY = re.compile(r"= \w+\[\d+,\d+,\d+,\d+\]\S* (?:copy|transpose)\(")


@pytest.mark.parametrize("b,h,t,d", [(2, 8, 4096, 40), (8, 8, 4096, 40),
                                     (2, 8, 1024, 80), (8, 8, 1024, 80)])
def test_sd15_site_holds_no_copy_by_head(one_chip, b, h, t, d):
    """One of SD1.5's tiled self-attention sites in its context (the qkv
    projection, the split, the kernel, the output projection and the
    residual) at CFG batch 2 and 8: the split's three slices feed the
    kernel and its result feeds ``out_proj``, with no ``[B,T,8,40]`` or
    ``[B,8,T,80]`` array copied through HBM on either side. Handed
    ``(B*H, T, D)`` the same site holds them."""
    c = h * d

    def on_chip(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def site(attention):
        def fn(x, w_qkv, w_out):
            q, k, v = (a.reshape(b, t, h, d)
                       for a in jnp.split(x @ w_qkv, 3, axis=-1))
            return attention(q, k, v).reshape(b, t, c) @ w_out + x
        return fn

    def heads_major(q, k, v):
        out = flash_attention(*(a.transpose(0, 2, 1, 3).reshape(b * h, t, 1, d)
                                for a in (q, k, v)), interpret=False)
        return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    args = on_chip(b, t, c), on_chip(c, 3 * c), on_chip(c, c)
    text = _compiled_text(
        site(lambda q, k, v: flash_attention(q, k, v, interpret=False)),
        *args)
    assert "tpu_custom_call" in text
    assert not _BY_HEAD_COPY.search(text)
    assert _BY_HEAD_COPY.search(_compiled_text(site(heads_major), *args))


@pytest.mark.parametrize("tokens,heads,head_dim", [
    (16384, 10, 64), (65536, 10, 64), (16384, 8, 40), (16384, 8, 80)])
def test_flash_kernel_compiles_at_hires_lengths(one_chip, tokens, heads,
                                                head_dim):
    """The hires second pass: K/V blocks of 4096 and the running softmax
    state in VMEM scratch across the k steps, two of SDXL's heads a block
    or all eight of SD1.5's (scratch ``(8, block_q, ...)``)."""
    qkv = jax.ShapeDtypeStruct((1, tokens, heads, head_dim), jnp.bfloat16,
                               sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bh,t,d", SHAPES)
def test_ragged_kernel_compiles_for_v5e(one_chip, bh, t, d):
    qkv = jax.ShapeDtypeStruct((bh, t, d), jnp.bfloat16, sharding=one_chip)
    tl = jax.ShapeDtypeStruct((bh,), jnp.int32, sharding=one_chip)
    block = min(128, t)
    text = _compiled_text(
        lambda n, q, k, v: _ragged_bhtd(q, k, v, n, d ** -0.5, block, block,
                                        False),
        tl, qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_flash_kernel_compiles_under_highest_matmul_precision(one_chip):
    """benchmarks/verify_reference.py runs the program under
    ``jax.default_matmul_precision("highest")``; Mosaic refuses bf16
    operands at that precision ("Bad lhs type"), so the kernel's dots pin
    their own."""
    qkv = jax.ShapeDtypeStruct((1, 1024, 10, 64), jnp.bfloat16,
                               sharding=one_chip)
    with jax.default_matmul_precision("highest"):
        text = _compiled_text(
            lambda q, k, v: flash_attention(q, k, v, interpret=False),
            qkv, qkv, qkv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch,side,channels,dtype", [
    (2, 64, 640, jnp.bfloat16), (1, 128, 512, jnp.float32)])
def test_folded_upsample_site_holds_no_gather(one_chip, batch, side, channels,
                                              dtype):
    """SDXL's ``up_1_us`` (64² -> 128², bf16) and the VAE decoder's first
    upsample at 1024² (float32), weights as the chip stores them: the
    v5e's optimised HLO of a folded site convolves and never gathers,
    where ``jax.image.resize`` ahead of the 3x3 left a gather of the
    upsampled activation's size."""
    def on_chip(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = on_chip((batch, side, side, channels), dtype)
    variables = {"params": {
        "kernel": on_chip((3, 3, channels, channels), jnp.bfloat16),
        "bias": on_chip((channels,), jnp.bfloat16)}}
    site = UpsampleConv(channels, dtype=dtype)
    text = _compiled_text(site.apply, variables, x)
    assert not _GATHER.search(text)
    assert "convolution(" in text

    def resized(variables, x):
        up = jax.image.resize(x, (batch, 2 * side, 2 * side, channels),
                              method="nearest")
        return nn.Conv(channels, (3, 3), padding=1, dtype=dtype).apply(
            variables, up)

    assert _GATHER.search(_compiled_text(resized, variables, x))


#: a convolution instruction of the optimised HLO: result, window, labels, scope
_CONVOLUTION = re.compile(
    r" = (\w+)\[[\d,]*\]\S* convolution\(.*window=\{size=(\w+).*"
    r'dim_labels=(\w+)_.*op_name="([^"]*)"')
_FLOAT32_COPY = re.compile(
    r" = f32\[([\d,]+)\]\S* copy\(.*op_name=\"([^\"]*)\"")


class _ResBlocks(nn.Module):
    """SD1.5's ``up_0``: a ResBlock over the concatenated skip (so a 1x1
    ``skip``) and two more, then the next site's norm."""

    @nn.compact
    def __call__(self, x, temb):
        for i in range(3):
            x = ResBlock(320, dtype=jnp.bfloat16, name=f"res_{i}")(x, temb)
        return GroupNorm32(name="norm")(x)


@pytest.mark.parametrize("rows", [2, 8])
def test_a_two_row_resblock_chain_keeps_its_convolutions_spatial_major(
        one_chip, rows):
    """SD1.5's 64 x 64 ResBlocks at CFG batch 2 with the sums of every
    norm read from a pinned copy: every 3x3 convolution of the v5e's
    optimised HLO still takes rows x column blocks as its batch
    (``0b1f``; a pin on the norm's INPUT turned ``conv2`` batch-major in
    the whole UNet, a third of the speed: PERF.md section 6, PR 65), no
    float32 copy of the activation's size feeds a norm's sums (the
    convolution's float32 result in the reduction's layout, and its
    square: two a norm before; the broadcast ``a`` and ``b`` stay), the
    1x1 ``skip`` is a product and not a convolution; at
    eight rows it is the convolution it was. A chain this short does not
    show the two-row tiles: ``tools/chunk_hlo.py`` reads the whole
    executable's (``two_row_tile_mb``)."""
    def on_chip(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    x, temb = on_chip((rows, 64, 64, 640)), on_chip((rows, 1280))
    module = _ResBlocks()
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), jnp.zeros(x.shape, x.dtype),
                            jnp.zeros(temb.shape, temb.dtype)))
    variables = jax.tree.map(lambda leaf: on_chip(leaf.shape), shapes)
    text = _compiled_text(module.apply, variables, x, temb)
    convolutions = _CONVOLUTION.findall(text)
    three = [labels for _, window, labels, scope in convolutions
             if window == "3x3" and "conv_general_dilated" in scope]
    assert len(three) == 6
    skips = [scope for *_, scope in convolutions if "/skip/" in scope]
    assert len(skips) == 1
    if rows == 8:
        assert "conv_general_dilated" in skips[0]
        return
    assert set(three) == {"0b1f"}, three
    assert "dot_general" in skips[0]
    activation = rows * 64 * 64 * 320
    copied = [scope for dims, scope in _FLOAT32_COPY.findall(text)
              if math.prod(map(int, dims.split(","))) >= activation
              and re.search(r"/norm[12]?/(convert_element_type|square)$",
                            scope)]
    assert not copied, copied


#: a fusion of the optimised HLO under a ``skip``'s product: result dtype, dims
_SKIP_PRODUCT = re.compile(
    r" = (\w+)\[([\d,]+)\]\S* fusion\(.*"
    r"op_name=\"[^\"]*/skip/dot_general\"")


@pytest.mark.parametrize("side,channels", [(64, 640), (128, 960)])
def test_a_two_row_skip_product_is_written_in_the_storage_dtype(
        one_chip, side, channels):
    """SD1.5's ``up_0`` and SDXL's ``up_0`` at CFG batch 2: the 1x1
    ``skip`` as a product reshaped to the activation's shape BEFORE its
    bias leaves its fusion in bf16 and in ``(B, H, W, C)``. With the bias
    added over the flattened positions the v5e's compiler wrote the
    product as ``f32[2, H*W, 320]`` and copied it into the residual add's
    layout (SDXL's module 2 900.4 -> 2 883.4 ms, SD1.5's 316.2 -> 314.4:
    PERF.md section 6, PR 66)."""
    def on_chip(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    x, temb = on_chip((2, side, side, channels)), on_chip((2, 1280))
    module = _ResBlocks()
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), jnp.zeros(x.shape, x.dtype),
                            jnp.zeros(temb.shape, temb.dtype)))
    variables = jax.tree.map(lambda leaf: on_chip(leaf.shape), shapes)
    text = _compiled_text(module.apply, variables, x, temb)
    products = _SKIP_PRODUCT.findall(text)
    assert products == [("bf16", f"2,{side},{side},320")], products


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("d,f", [(3072, 1024), (2048, 512), (3584, 1024),
                                 (2048, 1536), (2304, 896), (2048, 768)])
def test_chosen_experts_kernel_compiles_for_v5e(one_chip, d, f, precision):
    """The six published expert shapes (Laguna-S-2.1's, Qwen3-Next's,
    Xing4.0's, LFM2's, the widest: two blocks of 768 an expert,
    Mellum2's, whose blocks are the largest: one of 896, 24.8 MB in the
    ring's two slots, and kanana-2's, one of 768), 128 held, 10 chosen,
    bf16; also under benchmarks/verify_reference.py's
    ``default_matmul_precision("highest")``, which must not reach the
    kernel's dots."""
    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wide = on_chip((128, d, f), jnp.bfloat16)
    with jax.default_matmul_precision(precision):
        text = _compiled_text(
            lambda *a: moe_kernel.chosen_experts(*a, interpret=False),
            on_chip((1, d), jnp.bfloat16), on_chip((10,), jnp.int32),
            on_chip((10,), jnp.float32), on_chip((), jnp.int32), wide, wide,
            on_chip((128, f, d), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [1, 4])
def test_clamped_experts_kernel_compiles_at_the_seventh_shape(one_chip,
                                                              rows):
    """GigaChat3.5's 7168 x 2048 (the seventh published shape: rows over
    4 096, so sixteen blocks of one lane width an expert; 16 held, 8 a
    token), its SwiGLU clamped at 10 inside the block's body, at one row
    and at a block of four (16 slots: every held expert may be chosen)."""
    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    d, f, held = 7168, 2048, 16
    assert moe_kernel.f_tile(d, f, 2) == 128
    slots = min(rows * 8, held)
    wide = on_chip((held, d, f), jnp.bfloat16)
    weights = (slots,) if rows == 1 else (slots, rows)
    text = _compiled_text(
        lambda *a: moe_kernel.chosen_experts(*a, interpret=False,
                                             limit=10.0),
        on_chip((rows, d), jnp.bfloat16), on_chip((slots,), jnp.int32),
        on_chip(weights, jnp.float32), on_chip((), jnp.int32), wide, wide,
        on_chip((held, f, d), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("rows", [2, 4, 8])
def test_block_of_rows_kernel_compiles_for_v5e(one_chip, rows, precision):
    """A decode step of 2, 4 or 8 sequences at Mellum2's shape (2304 x 896,
    all 64 held, 8 a token): ``min(rows * 8, 64)`` slots of a whole
    expert, the rows padded to one bf16 sublane tile, a weight a slot and
    row in SMEM."""
    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    d, f, held = 2304, 896, 64
    slots = min(rows * 8, held)
    wide = on_chip((held, d, f), jnp.bfloat16)
    with jax.default_matmul_precision(precision):
        text = _compiled_text(
            lambda *a: moe_kernel.chosen_experts(*a, interpret=False),
            on_chip((rows, d), jnp.bfloat16), on_chip((slots,), jnp.int32),
            on_chip((slots, rows), jnp.float32), on_chip((), jnp.int32),
            wide, wide, on_chip((held, f, d), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("d,f,held,k,limit", [
    (3072, 1024, 128, 10, 0.0),     # Laguna-S-2.1
    (2048, 512, 128, 10, 0.0),      # Qwen3-Next
    (3584, 1024, 16, 4, 0.0),       # Xing4.0
    (2048, 1536, 64, 4, 0.0),       # LFM2
    (2304, 896, 64, 8, 0.0),        # Mellum2
    (2048, 768, 128, 6, 0.0),       # kanana-2
    (7168, 2048, 16, 8, 10.0),      # GigaChat3.5, clamped
    (6144, 2048, 16, 12, 0.0),      # LongCat-Flash
    (4096, 768, 36, 10, 0.0),       # granite-4.0-h-small
])
def test_the_ring_of_reads_compiles_at_the_nine_published_shapes(
        one_chip, d, f, held, k, limit, rows):
    """The nine cells' own calls (their experts held, their picks a token,
    one row and the block of four): the experts' kernels stay in HBM, the
    body copies their blocks into its ring of VMEM under DMA semaphores
    and walks them in a loop whose trip count is the held count's, and
    Mosaic takes the ring's bytes plus the slack as the call's VMEM."""
    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blocks = moe_kernel.ring(d, f, 2)
    assert blocks.vmem_bytes(d, 2) + moe_kernel._VMEM_SLACK <= 28 * 2 ** 20
    slots = k if rows == 1 else min(rows * k, held)
    wide = on_chip((held, d, f), jnp.bfloat16)
    text = _compiled_text(
        lambda *a: moe_kernel.chosen_experts(*a, interpret=False,
                                             limit=limit),
        on_chip((rows, d), jnp.bfloat16), on_chip((slots,), jnp.int32),
        on_chip((slots,) if rows == 1 else (slots, rows), jnp.float32),
        on_chip((), jnp.int32), wide, wide,
        on_chip((held, f, d), jnp.bfloat16))
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("stored", [jnp.bfloat16, jnp.float32])
def test_stream_mixer_kernels_compile_for_v5e(one_chip, stored):
    """The published shape (four streams of 3 584, ``phi`` 14 336 x 24,
    twenty iterations): the kernel before the sublayer, with ``phi`` as the
    policy stores it on the chip and in float32, and the write-back after
    it."""
    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, hidden = 4, 3584

    def both(streams, scale, phi, alpha, b_pre, b_post, b_res, out):
        read, tile = stream_mixer.mixer(
            streams, scale, stream_mixer.pack(phi, alpha, b_pre, b_post,
                                              b_res),
            eps=1e-6, hc_eps=1e-6, clamp=(-30.0, 30.0), iters=20,
            interpret=False)
        return read, stream_mixer.write_back(streams, tile, out,
                                             interpret=False)

    text = _compiled_text(
        both, on_chip((1, n, hidden), jnp.float32),
        on_chip((n * hidden,), stored),
        on_chip((n * hidden, n * n + 2 * n), stored), on_chip((3,), stored),
        on_chip((n,), stored), on_chip((n,), stored), on_chip((n, n), stored),
        on_chip((1, hidden), jnp.float32))
    assert text.count("tpu_custom_call") == 2


#: a recurrent state of the GigaChat3.5 share copied as an HLO instruction
_STATE_COPY = re.compile(r"= f32\[4,64,128,128\]\S* copy\(")


@pytest.mark.parametrize("shape", [(2, 64, 128, 128), (4, 64, 128, 128),
                                   (8, 64, 128, 128), (4, 16, 64, 256)])
def test_forked_delta_step_kernel_compiles_for_v5e(one_chip, shape):
    """The published 64 heads of 128 x 128 float32, 2, 4 or 8 sequences a
    step, and a state two lane tiles wide: the keys' and queries' ``(heads,
    K)`` blocks transposed in the kernel, a head's column broadcast down
    the lanes, the state aliased in to out (a donated state is stepped in
    place: no copy of it, nothing temporary of its size)."""
    def on_chip(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    b, h, k_dim, v_dim = shape
    compiled = jax.jit(
        lambda *a: delta_kernel.recurrent_step_each(*a, interpret=False),
        donate_argnums=(0,)).lower(
            on_chip(*shape), on_chip(b, h, k_dim), on_chip(b, h, k_dim),
            on_chip(b, h, v_dim), on_chip(b, h), on_chip(b, h)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert " copy(" not in text.replace("copy_bitcast", "")
    memory = compiled.memory_analysis()
    state = b * h * k_dim * v_dim * 4
    assert memory.alias_size_in_bytes == state
    assert memory.temp_size_in_bytes < state // 8


def _forked_on(chip, cache, sequences, own_slots):
    """``cache``'s shapes forked (cache/kv.py:fork), placed on ``chip``."""
    from stable_diffusion_webui_distributed_tpu.cache import kv

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(lambda c: kv.fork(c, sequences, own_slots), cache))


def _joined_on(chip, cache, sequences, region, own_slots):
    """``cache``'s shapes as those of ``sequences`` one-sequence caches
    joined behind one of them (cache/kv.py:joined_rows), placed on
    ``chip``."""
    from stable_diffusion_webui_distributed_tpu.cache import kv

    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(
            lambda c, lengths, at: kv.forked(c, kv.joined_rows(
                (c,) * sequences, lengths, at, region, own_slots)),
            cache, jax.ShapeDtypeStruct((sequences,), jnp.int32), scalar))


@pytest.mark.parametrize("preset", ["TINY_LOOP_EXPAND",
                                    "TINY_WINDOW_EXPAND"])
def test_a_forked_decode_chunk_compiles_for_v5e(one_chip, preset):
    """The decode chunk of four sequences forked from one prefill, at the
    tiny looped preset (a pass axis in the shared buffers and in a
    sequence's own rows) and at the tiny window preset (rings and a buffer
    shared alike): the chip's compiler takes the two ranges under one
    softmax, and the donated cache, shared buffers included, goes through
    to the result without a copy of its own."""
    from stable_diffusion_webui_distributed_tpu.models import configs, lm

    cfg = getattr(configs, preset).expander
    module = lm.DecoderLM(cfg, dtype=jnp.bfloat16)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    one = {name: [jax.ShapeDtypeStruct(shape, jnp.bfloat16)
                  for shape in rows]
           for name, rows in lm.cache_shapes(cfg, 256).items()}
    cache = _forked_on(one_chip, one, 4, 64)
    scalar = on_chip((), jnp.int32)
    shapes = jax.eval_shape(
        lambda *a: module.init(jax.random.key(0), *a),
        jax.ShapeDtypeStruct((4,), jnp.int32), scalar, scalar,
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
            one))["params"]
    params = jax.tree_util.tree_map(
        lambda x: on_chip(x.shape, jnp.bfloat16), shapes)
    compiled = jax.jit(lm.decode_sequences_fn(module, 32),
                       donate_argnums=(1,)).lower(
        params, cache, on_chip((4,), jnp.int32), scalar,
        on_chip((4,), jax.random.key(0).dtype), on_chip((), jnp.float32),
        scalar).compile()
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= held


#: (which executable, the share, its cache's slots, then the bounds: the
#: arguments' GB, the expert kernels, MB of temporaries, MB aliased)
EXPANDER_EXECUTABLES = [
    ("decode", "sd15_laguna_expander", 1024, 11.1, 4, 64, 14),
    # two requests' sequences behind the kept instruction (a JOINED cache:
    # each prompt's 64 slots and 384 decode slots a sequence, 33 MB donated
    # with the instruction's 14.7): the same four expert kernels
    ("decode2_joined", "sd15_laguna_expander", 1024, 11.1, 4, 64, 30),
    ("prefill", "sd15_laguna_expander", 1024, 11.1, 0, 64, 14),
    ("decode", "sd15_qwen3next_expander", 1024, 10.8, 12, 64, 14),
    # eighteen expert kernels and forty mixers of two; forty transposed
    # copies of phi (0.9 MB each in bf16 tiles) made before the scan and
    # eighteen float32 routers hoisted out of it: 124 MB
    ("decode", "sd15_xing4_expander", 1024, 8.75, 98, 128, 14),
    ("prefill", "sd15_xing4_expander", 1024, 8.75, 0, 128, 14),
    # eight expert kernels; the donated cache is two layers' keys and
    # values and eight layers' kept rows, 4.3 MB
    ("decode", "sd15_lfm2_expander", 1024, 10.8, 8, 64, 4),
    ("prefill", "sd15_lfm2_expander", 1024, 10.8, 0, 64, 4),
    # at a 2 560-slot cache (a 2 048-token instruction): eight expert
    # kernels at one sequence and at four sequences a step (the whole
    # block of rows an expert), which donate a forked cache: the one
    # sequence's 23 MB and 256 slots a layer for each of four, 40 MB;
    # the instruction's one chunk, twice the window, needs 0.9 GB of scores
    ("decode", "sd15_mellum2_expander", 2560, 7.6, 8, 64, 23),
    ("decode4", "sd15_mellum2_expander", 2560, 7.6, 8, 64, 39),
    ("prefill2048", "sd15_mellum2_expander", 2560, 7.6, 0, 1000, 23),
    # seven expert kernels (the sixth published shape, 2048 x 768, 24
    # slots a call of four rows) behind eight forked latent attentions:
    # four sequences donate the one sequence's 23.6 MB of latents (shared,
    # handed through) and 256 own slots a layer each, 33 MB in all; the
    # prompt's 64-token chunk in the expanded form over 2 560 latents
    ("decode4", "sd15_kanana2_expander", 2560, 10.15, 7, 160, 32),
    ("prefill", "sd15_kanana2_expander", 2560, 10.15, 0, 400, 23),
    # four clamped expert kernels (the seventh published shape, 7168 x
    # 2048, tile 256) behind one forked latent attention and four delta
    # mixers that step a state a sequence, each through the kernel that
    # holds a head's state in VMEM (ops/delta_kernel.py: eight kernels in
    # all, and no copy of a state in the scan): four sequences donate the one
    # sequence's 2.9 MB of latents (shared, handed through), 256 own slots
    # each and sixteen states with their kept rows, 70 MB; the prompt's
    # 64-token chunk chunk-wise over four states and expanded over 2 560
    # latents; the instruction's one chunk of 2 048
    # (1.66 GB of temporaries: 64 heads' scores over 2 560 latents)
    ("decode4", "sd15_gigachat35_expander", 2560, 9.46, 8, 64, 70),
    ("prefill", "sd15_gigachat35_expander", 2560, 9.46, 0, 400, 20),
    ("prefill2048", "sd15_gigachat35_expander", 2560, 9.46, 0, 2000, 20),
    # no kernel at all: twelve delta mixers that step a (30, 96, 192) state
    # a sequence at strength up to 2 (element-wise: a 192-wide state is off
    # the lanes, ops/delta_rule.py:step_form) beside four unrotated
    # attentions of 30
    # ungrouped heads; four sequences donate the one sequence's 157 MB of
    # keys and values (shared, handed through), 256 own slots a layer each
    # and forty-eight states with their kept rows, 333 MB; the prompt's
    # chunk chunk-wise over twelve states; the instruction's one chunk of
    # 2 048 (30 heads' scores over 2 560 positions)
    # (the arguments are the 8.20 GB of weights and the cache: a state's
    # 192-wide minor axis lies in tiles of 256, so forty-eight states take
    # 142 MB where their shapes say 106)
    ("decode4", "sd15_olmo_hybrid_expander", 2560, 8.50, 0, 128, 330),
    ("prefill", "sd15_olmo_hybrid_expander", 2560, 8.30, 0, 400, 180),
    ("prefill2048", "sd15_olmo_hybrid_expander", 2560, 8.30, 0, 2000, 180),
    # no kernel either: nine layers of attention (20 heads over 4 KV heads,
    # rotated at theta 1e11) AND a state-space mixer that steps a (32, 128,
    # 256) float32 state a sequence, element-wise, under thirteen
    # multipliers; four sequences donate the one sequence's 47 MB of keys
    # and values (shared, handed through), 256 own slots a layer each and
    # thirty-six states with their kept rows, 219 MB; the prompt's chunk
    # chunk-wise (one chunk of 128 padded from 64) over nine states; the
    # instruction's one chunk of 2 048 (sixteen chunks' segment sums and 20
    # heads' scores over 2 560 positions: 0.82 GB of temporaries)
    ("decode4", "sd15_falcon_h1_expander", 2560, 9.25, 0, 128, 215),
    ("prefill", "sd15_falcon_h1_expander", 2560, 9.10, 0, 64, 84),
    ("prefill2048", "sd15_falcon_h1_expander", 2560, 9.10, 0, 1000, 84),
    # four expert kernels (the eighth published shape, 6144 x 2048, tile
    # 256 by its own rule; a call of four rows of twelve picks walks at
    # most the 16 held experts) behind eight forked latent attentions of 64
    # heads, each router's sum carried past one attention and one dense MLP
    # of 12 288 before it lands: four sequences donate the one sequence's
    # 23.6 MB of latents (shared, handed through) and 256 own slots a
    # sublayer each, 33 MB; 0.2 GB of temporaries (the routers in float32
    # hoisted out of the scan, the 64 heads' folded queries). The prompt's
    # 64-token chunk in the expanded form over 2 560 latents; the
    # instruction's one chunk of 2 048: 1.63 GB of temporaries, 64 heads'
    # float32 scores over 2 560 latents (1.34 GB) beside the grouped
    # product's 2 048 x 12 picks sorted into tiles of 32 rows. This is
    # where the share's fit beside SD1.5's 2.13 GB shows first: 10.37 GB of
    # arguments + 1.63 is 12.0 of the chip's 16.9
    ("decode4", "sd15_longcat_flash_expander", 2560, 10.35, 4, 260, 32),
    ("prefill", "sd15_longcat_flash_expander", 2560, 10.35, 0, 400, 23),
    ("prefill2048", "sd15_longcat_flash_expander", 2560, 10.35, 0, 2000, 23),
    # ten expert kernels (the ninth published shape, 4096 x 768, two tiles
    # of 384 by the kernel's own rule; a call of four rows of ten picks
    # walks at most the 36 held experts: 36 grid slots) behind ten routing
    # kernels, nine state-space mixers that step a (128, 64, 128) float32
    # state a sequence, element-wise, ONE forked unrotated attention, and
    # the logits off the table (no lm_head among the 9.51 GB of
    # arguments): four sequences donate the one sequence's 21 MB of keys
    # and values of that ONE layer (shared, handed through), 256 own slots
    # each and thirty-six states with their kept rows, 169 MB; 38 MB of
    # temporaries. The prompt's 64-token chunk chunk-wise (one chunk of
    # 256 padded from 64) over nine states: 171 MB of temporaries; the
    # instruction's one chunk of 2 048: 1.38 GB, the same at ssm_chunk 128
    # and 256 (so the published 256 stays): 32 heads' float32 scores over
    # 2 560 positions (0.67 GB) and the grouped product's 2 048 x 10 picks
    # sorted into tiles beside their float32 results are the peak, the
    # chunk-wise form's (8, 128, 256, 256) float32 decay table and scores
    # (268 MB each) are not live with them
    ("decode4", "sd15_granite_h_expander", 2560, 9.6, 10, 64, 160),
    ("prefill", "sd15_granite_h_expander", 2560, 9.5, 0, 260, 45),
    ("prefill2048", "sd15_granite_h_expander", 2560, 9.5, 0, 2000, 45),
]


#: one launch of the routing kernel (the jitted function's own name)
_ROUTE_CALL = re.compile(r"%_route_call[\w.]* = [^\n]*tpu_custom_call")
#: a sort or a scatter (fused or not) traced under an expert layer's MLP
_CHAIN_OP = re.compile(
    r" (?:sort|scatter)\([^\n]*op_name=\"[^\"]*/layers_\d+/mlp/")


@pytest.mark.parametrize("rows,factory", [
    (1, "sd15_qwen3next_expander"), (4, "sd15_longcat_flash_expander"),
    (8, "sd15_mellum2_expander"), (2, "sd15_gigachat35_expander"),
    (4, "sd15_granite_h_expander")])
def test_routing_kernel_compiles_for_v5e(one_chip, rows, factory):
    """One row of Qwen3-Next's 512 / 10, four of LongCat's 768 / 12 with
    its bias (stored bf16, widened in the kernel) and 256 identity
    experts, eight of Mellum2's 64 / 8 (64 grid slots, under one
    register's lanes), two of GigaChat3.5's 256 of which 16 held and four
    of granite-4.0-h-small's 72 / 10 of which 36 held (a router under one
    lane width, ten rounds, more grid slots than picks held)."""
    from stable_diffusion_webui_distributed_tpu.models import configs
    from stable_diffusion_webui_distributed_tpu.ops import route_kernel

    cfg = getattr(configs, factory)().expander

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(logits, valid, *bias):
        return route_kernel.routing(
            logits, bias[0] if bias else None, valid,
            k=cfg.num_experts_per_tok, renormalise=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor, scoring=cfg.router_scoring,
            eps=cfg.norm_topk_eps, first=cfg.experts[0],
            count=cfg.experts[1], zero_experts=cfg.zero_experts,
            interpret=False)

    bias = [on_chip((cfg.num_experts,), jnp.bfloat16)] * cfg.router_bias
    text = _compiled_text(step, on_chip((rows, cfg.num_experts),
                                        jnp.float32),
                          on_chip((rows,), jnp.bool_), *bias)
    assert len(_ROUTE_CALL.findall(text)) == 1
    assert not re.search(r" (?:sort|scatter|gather)\(", text)


@pytest.mark.parametrize(
    "which,expander,capacity,argument_gb,kernels,temp_mb,alias_mb",
    EXPANDER_EXECUTABLES,
    # the ids these cases had before the capacity was a column
    ids=["-".join(map(str, row[:2] + row[3:]))
         for row in EXPANDER_EXECUTABLES])
def test_the_prompt_expanders_executables_compile_and_fit_one_v5e(
        one_chip, monkeypatch, which, expander, capacity, argument_gb,
        kernels, temp_mb, alias_mb):
    """A share's decode chunk (and two shares' 64-token prefill) at the
    published widths (5.57 B, 5.42 B and 4.39 B parameters as bfloat16
    shapes, a 1 024-slot cache; the last with twenty layers of latent
    attention and forty mixers in the scan's body): the chip's
    compiler accepts them, every expert layer of a decode step is the
    pipelined kernel and every mixer its two (ops/moe.py:choose and
    ops/stream_mixer.py:choose are told the platform it is compiled for)
    and a prefill has none, the
    weights are arguments and not copies (an expert's kernels are read
    block by block, never gathered whole), and everything fits beside
    SD1.5."""
    from stable_diffusion_webui_distributed_tpu.models import configs, lm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = getattr(configs, expander)().expander
    module = lm.DecoderLM(cfg, dtype=jnp.bfloat16)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = {name: [on_chip(shape, lm.buffer_dtype(name, jnp.bfloat16))
                    for shape in rows]
             for name, rows in lm.cache_shapes(cfg, capacity).items()}
    if which == "decode4":      # four sequences forked, 256 slots each
        cache = _forked_on(one_chip, cache, 4, 256)
    elif which == "decode2_joined":
        cache = _joined_on(one_chip, cache, 2, 64, 384)
    small = {name: [jax.ShapeDtypeStruct(shape, jnp.float32)
                    for shape in rows]
             for name, rows in lm.cache_shapes(cfg, 8).items()}
    scalar = on_chip((), jnp.int32)
    shapes = jax.eval_shape(
        lambda *a: module.init(jax.random.key(0), *a),
        jax.ShapeDtypeStruct((4,), jnp.int32), scalar, scalar,
        small)["params"]
    params = jax.tree_util.tree_map(
        lambda x: on_chip(x.shape, jnp.bfloat16), shapes)
    key = on_chip((), jax.random.key(0).dtype)
    heat = on_chip((), jnp.float32)
    if which == "decode":
        lowered = jax.jit(lm.decode_chunk_fn(module, 32),
                          donate_argnums=(1,)).lower(
            params, cache, scalar, scalar, key, heat)
    elif which in ("decode4", "decode2_joined"):
        rows = 4 if which == "decode4" else 2
        lowered = jax.jit(lm.decode_sequences_fn(module, 32),
                          donate_argnums=(1,)).lower(
            params, cache, on_chip((rows,), jnp.int32), scalar,
            on_chip((rows,), jax.random.key(0).dtype), heat, scalar)
    else:
        tokens = 2048 if which == "prefill2048" else 64
        lowered = jax.jit(lm.prefill_fn(module), donate_argnums=(1,)).lower(
            params, cache, on_chip((tokens,), jnp.int32), scalar, scalar,
            key, heat)
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = text.count("tpu_custom_call")
    assert calls >= kernels and bool(calls) == bool(kernels)
    # a decode step's routing is one launch an expert layer in front of
    # the expert kernel (ops/route_kernel.py), and XLA's chain is gone:
    # no sort (top_k, argsort) and no scatter under an expert layer
    routed = which != "prefill" and which != "prefill2048" and len(
        cfg.expert_layers)
    assert len(_ROUTE_CALL.findall(text)) == routed
    if routed:
        assert not _CHAIN_OP.search(text)
    assert not _STATE_COPY.search(text)
    memory = compiled.memory_analysis()
    assert argument_gb * 1e9 < memory.argument_size_in_bytes \
        < (argument_gb + 0.1) * 1e9
    assert memory.temp_size_in_bytes < temp_mb * 1e6
    assert memory.alias_size_in_bytes > alias_mb * 1e6  # the donated cache

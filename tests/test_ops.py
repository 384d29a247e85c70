"""Kernel tests: the tiled Pallas attention (interpret mode on CPU) and ring
attention over the 8-device virtual mesh, both checked against the XLA
reference attention; the chooser between the tiled kernel and XLA
(ops/attention.py), and the count of attention sites by path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.ops import attention as attention_ops
from stable_diffusion_webui_distributed_tpu.ops.flash_attention import (
    _keys_call,
    _tiled,
    _tiled_keys,
    blocks,
    flash_attention,
    heads_per_block,
    padded,
)
from stable_diffusion_webui_distributed_tpu.ops.ring_attention import (
    ring_attention,
)

RNG = np.random.default_rng(3)


def qkv(b, t, h, d, s=None):
    s = t if s is None else s
    q = jnp.asarray(RNG.standard_normal((b, t, h, d), np.float32))
    k = jnp.asarray(RNG.standard_normal((b, s, h, d), np.float32))
    v = jnp.asarray(RNG.standard_normal((b, s, h, d), np.float32))
    return q, k, v


def reference(q, k, v):
    return jax.nn.dot_product_attention(
        q, k, v, scale=1.0 / q.shape[-1] ** 0.5)


class TestFlashAttention:
    @pytest.mark.parametrize("t,block", [(256, 128), (128, 64), (64, 64)])
    def test_matches_xla(self, t, block):
        q, k, v = qkv(2, t, 4, 32)
        out = flash_attention(q, k, v, block_q=block, block_k=block,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(reference(q, k, v)),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("t,s", [(64, 77), (60, 77), (60, 64)])
    def test_non_tiling_is_still_correct(self, t, s):
        # a 77-token cross-attention context is padded and masked in the
        # kernel; queries off the tiling take the XLA fallback path
        q, k, v = qkv(1, t, 4, 32, s=s)
        out = flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(reference(q, k, v)),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16_inputs(self):
        q, k, v = qkv(1, 128, 2, 32)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        out = flash_attention(qb, kb, vb, block_q=64, block_k=64,
                              interpret=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(reference(q, k, v)),
            rtol=3e-2, atol=3e-2)

    def test_jittable(self):
        q, k, v = qkv(1, 128, 2, 32)
        f = jax.jit(lambda a, b, c: flash_attention(a, b, c, block_q=64,
                                                    block_k=64,
                                                    interpret=True))
        np.testing.assert_allclose(np.asarray(f(q, k, v)),
                                   np.asarray(reference(q, k, v)),
                                   rtol=2e-5, atol=2e-5)


class TestRingAttention:
    def test_matches_single_device(self):
        """Token-sharded ring attention over sp=8 must equal the dense
        single-device result — the long-context sequence-parallel path."""
        from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
            build_mesh,
        )

        mesh = build_mesh("sp=8")
        q, k, v = qkv(2, 8 * 16, 4, 32)  # 128 tokens over 8 ring stages
        out = ring_attention(q, k, v, mesh)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(reference(q, k, v)),
                                   rtol=2e-5, atol=2e-5)

    def test_combined_dp_sp_mesh(self):
        """dp x sp mesh: batch rides dp, tokens ride the sp ring — both
        dims sharded, result identical to dense."""
        import numpy as np
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()).reshape(2, 1, 4),
                    ("dp", "tp", "sp"))
        q, k, v = qkv(4, 64, 2, 16)
        out = ring_attention(q, k, v, mesh)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(reference(q, k, v)),
                                   rtol=2e-5, atol=2e-5)

    def test_under_jit_with_dp_and_sp(self):
        from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
            build_mesh,
        )

        mesh = build_mesh("sp=4")  # subset of the 8 virtual devices
        q, k, v = qkv(2, 64, 2, 16)
        f = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh))
        np.testing.assert_allclose(np.asarray(f(q, k, v)),
                                   np.asarray(reference(q, k, v)),
                                   rtol=2e-5, atol=2e-5)


class TestFlashAttentionStreaming:
    """The k-tile streaming form (grid innermost over S/block_k with VMEM
    scratch carry) must fold MANY tiles correctly — the shape class the
    hires 2048² pass hits (S >> block_k), where whole-K VMEM residency is
    impossible."""

    def test_many_k_tiles_asymmetric_blocks(self):
        q, k, v = qkv(2, 512, 2, 32)
        out = flash_attention(q, k, v, block_q=128, block_k=64,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(reference(q, k, v)),
                                   rtol=2e-5, atol=2e-5)

    def test_sd15_head_dim_40(self):
        # production head_dim for SD1.5 latent self-attention
        q, k, v = qkv(1, 256, 8, 40)
        out = flash_attention(q, k, v, block_q=64, block_k=64,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(reference(q, k, v)),
                                   rtol=2e-5, atol=2e-5)


def heads_major(q, k, v, block_q, block_k):
    """The kernel handed ``(B*H, T, D)``, one head a block: the layout
    every head size off 128's divisors took before PR 57. Keys off the
    tiling go through the entry that pads and masks them."""
    b, t, h, d = q.shape

    def to_bhtd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    static = dict(heads=1, head_dim=d, block_q=block_q, scale=d ** -0.5,
                  interpret=True)
    if padded(k.shape[1]) == k.shape[1]:
        out = _tiled(to_bhtd(q), to_bhtd(k), to_bhtd(v), block_k=block_k,
                     **static)
    else:
        out = _tiled_keys(to_bhtd(q), to_bhtd(k), to_bhtd(v), **static)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def traced_kernel(q, k, v):
    """(name of the jitted entry, its ``pallas_call`` equation) of one
    ``flash_attention`` site."""
    jaxpr = jax.make_jaxpr(
        lambda *a: flash_attention(*a, interpret=True))(q, k, v)
    (inner,) = [e for e in jaxpr.eqns if e.primitive.name in ("pjit", "jit")]
    (call,) = [e for e in inner.params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    return inner.params["name"], call


class TestTiledKernel:
    """The kernel as the default path calls it: tiles from the shape, heads
    side by side in the lanes (``128 // head_dim`` a block where that
    divides, else all of them in one block of the whole width), one plain
    softmax or the running state."""

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 3e-2)])
    @pytest.mark.parametrize("h,d", [(2, 40), (2, 64), (2, 80), (2, 160),
                                     (8, 40), (8, 80)])
    def test_matches_xla_at_unet_head_dims(self, h, d, dtype, tol):
        q, k, v = qkv(2, 128, h, d)
        got = flash_attention(*(x.astype(dtype) for x in (q, k, v)),
                              interpret=True)
        assert got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(reference(q, k, v)),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("h,d", [(2, 64), (4, 32), (3, 64), (2, 40),
                                     (8, 40), (8, 80)])
    def test_running_state_at_every_block_width(self, h, d):
        """Four k steps: (2, 64) and (4, 32) ride the lanes 128 a block;
        three heads of 64 and SD1.5's eight of 40 or 80 all in one block."""
        q, k, v = qkv(1, 256, h, d)
        got = flash_attention(q, k, v, block_q=128, block_k=64,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(reference(q, k, v)),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("t,d,block_q,block_k", [
        (128, 40, 128, 128), (128, 80, 128, 128),
        (256, 40, 128, 64), (256, 80, 64, 64)])    # k_steps 1, 1, 4, 4
    def test_lanes_layout_is_the_heads_major_result(self, t, d, block_q,
                                                    block_k, dtype):
        """SD1.5's eight heads of 40 and 80 in one block give what one head
        a block through ``(B*H, T, D)`` copies gives: the same dots, the
        same float32 softmax, another place in the lanes."""
        q, k, v = (x.astype(dtype) for x in qkv(2, t, 8, d))
        assert heads_per_block(8, d, block_q, block_k,
                               q.dtype.itemsize) == 8
        got = flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                              interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(heads_major(q, k, v, block_q, block_k), np.float32))

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 3e-2)])
    @pytest.mark.parametrize("h,d", [(8, 40), (2, 64), (8, 80)])
    @pytest.mark.parametrize("s", [77, 231, 154])
    def test_keys_off_the_tiling_are_padded_and_masked(self, s, h, d, dtype,
                                                       tol):
        """Cross-attention's context of one, two and three 77-token chunks:
        k and v grow zero rows to 128 or 256 and the kernel masks them, the
        heads side by side in the lanes."""
        q, k, v = qkv(2, 128, h, d, s=s)
        assert heads_per_block(h, d, *blocks(128, s), 4) == (8 if h == 8
                                                             else 2)
        got = flash_attention(*(x.astype(dtype) for x in (q, k, v)),
                              interpret=True)
        assert got.dtype == dtype and got.shape == q.shape
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(reference(q, k, v)),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("s,d", [(77, 40), (231, 64), (154, 80)])
    def test_masked_keys_by_either_layout(self, s, d, dtype):
        """``(B*H, T, D)`` operands, one head a block, give the lanes
        layout's result over padded keys too."""
        q, k, v = (x.astype(dtype) for x in qkv(2, 128, 8, d, s=s))
        block_q, block_k = blocks(128, s)
        got = flash_attention(q, k, v, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(heads_major(q, k, v, block_q, block_k), np.float32))

    @pytest.mark.parametrize("d", [40, 64])
    def test_the_mask_and_not_the_zeros_does_the_work(self, d):
        """K rows and V^T columns past the key count hold garbage (finite,
        large; the row of ones too): the result is the reference's over the
        keys alone; with every row counted as a key it is not."""
        q, k, v = qkv(2, 64, 2, d, s=231)

        def garbage(*shape):
            return 30.0 * jnp.asarray(RNG.standard_normal(shape, np.float32))

        vt = jnp.concatenate([v.transpose(0, 2, 3, 1),
                              jnp.ones((2, 2, 8, 231))], axis=2)
        operands = (q.reshape(2, 64, 2 * d),
                    jnp.concatenate([k.reshape(2, 231, 2 * d),
                                     garbage(2, 25, 2 * d)], axis=1),
                    jnp.concatenate([vt, garbage(2, 2, d + 8, 25)], axis=3))
        static = dict(heads=2, head_dim=d, block_q=64, scale=d ** -0.5,
                      interpret=True)
        want = np.asarray(reference(q, k, v)).reshape(2, 64, 2 * d)
        np.testing.assert_allclose(
            np.asarray(_keys_call(*operands, keys=231, **static)), want,
            rtol=2e-5, atol=2e-5)
        unmasked = np.asarray(_keys_call(*operands, keys=256, **static))
        assert np.isfinite(unmasked).all()
        assert np.abs(unmasked - want).max() > 0.5

    def test_a_tiling_key_count_traces_no_mask(self):
        """A self-attention site's kernel is what it was: no column index
        and no comparison in the kernel's jaxpr; 231 keys trace both."""
        def kernel_ops(s):
            name, call = traced_kernel(*qkv(1, 128, 2, 64, s=s))
            return name, {e.primitive.name
                          for e in call.params["jaxpr"].eqns}

        name, ops = kernel_ops(128)
        assert name == "_tiled" and not ops & {"iota", "ge"}
        name, ops = kernel_ops(231)
        assert name == "_tiled_keys" and {"iota", "ge"} <= ops

    @pytest.mark.parametrize("s,want", [(77, 128), (154, 256), (231, 256),
                                        (616, 616), (64, 64), (4096, 4096),
                                        (129, 256), (4097, 4224)])
    def test_padded_key_rows(self, s, want):
        assert padded(s) == want

    @pytest.mark.parametrize("h,d,block_q,block_k,want", [
        (10, 64, 256, 4096, 2),      # SDXL: two neighbours fill 128 lanes
        (20, 64, 1024, 1024, 2),
        (4, 32, 128, 128, 4),
        (1, 128, 128, 128, 1),
        (8, 40, 256, 4096, 8),       # SD1.5 64x64: 320 lanes, one block
        (8, 80, 1024, 1024, 8),      # SD1.5 32x32: 640 lanes
        (8, 160, 256, 256, 8),
        (3, 64, 128, 64, 3),         # an odd count of 64s: the whole width
        (20, 96, 256, 4096, 0),      # 1920 lanes of K and V do not fit
    ])
    def test_heads_a_block_come_from_the_shape(self, h, d, block_q, block_k,
                                               want):
        assert heads_per_block(h, d, block_q, block_k, 2) == want

    def test_a_width_that_does_not_fit_goes_heads_major(self, monkeypatch):
        """Nothing any model here traces; the branch still has to be right."""
        import sys

        # ops/__init__.py's function shadows the module of the same name
        monkeypatch.setattr(sys.modules[flash_attention.__module__],
                            "_VMEM_LIMIT", 1 << 16)
        q, k, v = qkv(1, 128, 3, 40)
        assert heads_per_block(3, 40, 128, 128, 4) == 0
        got = flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(reference(q, k, v)),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("block_q,block_k", [(48, 128), (128, 48)])
    def test_blocks_that_do_not_divide_fall_back(self, block_q, block_k):
        q, k, v = qkv(1, 128, 2, 64)
        got = flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                              interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(reference(q, k, v)))

    @pytest.mark.parametrize("t,s,want", [
        (4096, 4096, (256, 4096)),     # SDXL 64x64: 4 MiB of scores a tile
        (1024, 1024, (1024, 1024)),    # SDXL 32x32: the whole layer of a head
        (256, 256, (256, 256)),
        (64, 64, (64, 64)),
        (65536, 65536, (256, 4096)),   # hires: 16 k steps of 4096
        (9216, 9216, (256, 3072)),     # 96x96 latent: divisors of 128
        (64, 77, (64, 128)),           # cross-attention's context, padded
        (4096, 77, (2048, 128)),       # SDXL 64x64 over one chunk
        (4096, 231, (2048, 256)),      # SD1.5 64x64 over three chunks
        (1024, 231, (1024, 256)),
        (4096, 4090, (256, 4096)),     # the longest context of one block
        (4096, 4107, None),            # padded, more than one block
        (60, 77, None),                # queries off the sublane tiling
        (4104, 4104, None),            # over a block and no divisor of 128
    ])
    def test_blocks_come_from_the_shape(self, t, s, want):
        assert blocks(t, s) == want

    def test_inner_jit_traces_the_kernel_once_per_shape(self):
        """Three sites, two shapes: the sites of one shape share one traced
        kernel (the same jaxpr object), so a UNet's 70 sites trace and
        lower it two or three times."""
        a, b = qkv(1, 128, 2, 64), qkv(1, 64, 2, 64)

        def three_sites(a, b):
            once = flash_attention(*a, interpret=True)
            again = flash_attention(once, a[1], a[2], interpret=True)
            return again, flash_attention(*b, interpret=True)

        inner = [e.params["jaxpr"]
                 for e in jax.make_jaxpr(three_sites)(a, b).eqns
                 if e.primitive.name in ("pjit", "jit")
                 and e.params["name"] == _tiled.__name__]
        assert len(inner) == 3
        assert inner[0] is inner[1] and inner[0] is not inner[2]
        again, other = jax.jit(three_sites)(a, b)
        np.testing.assert_allclose(
            np.asarray(again),
            np.asarray(reference(reference(*a), a[1], a[2])),
            rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(other),
                                   np.asarray(reference(*b)),
                                   rtol=2e-5, atol=2e-5)

    def test_cost_estimate_counts_both_matmuls(self):
        """XLA's cost analysis prices a custom call at nothing: the kernel
        says what it does (FlopsAccountant, obs/perf.py read it)."""
        _, call = traced_kernel(*(x.astype(jnp.bfloat16)
                                  for x in qkv(2, 128, 2, 64)))
        cost = call.params["cost_estimate"]
        assert cost.flops == 4 * 2 * 2 * 128 * 128 * 64
        assert cost.transcendentals == 2 * 2 * 128 * 128
        assert cost.bytes_accessed == 2 * 4 * (2 * 128 * 2 * 64)

    def test_cost_estimate_counts_the_keys_and_not_the_padding(self):
        _, call = traced_kernel(*(x.astype(jnp.bfloat16)
                                  for x in qkv(2, 128, 2, 64, s=77)))
        cost = call.params["cost_estimate"]
        assert cost.flops == 4 * 2 * 2 * 128 * 77 * 64
        assert cost.transcendentals == 2 * 2 * 128 * 77
        # q read and the result written; k and v^T (with its ones) padded
        assert cost.bytes_accessed == 2 * 2 * 2 * 128 * (2 * 64 + 64 + 72)


class TestChooser:
    """ops/attention.py: the tiled kernel on a TPU, for bf16 sites of 1024
    queries and more, over 1024 keys and more or over a short context whose
    scores XLA cannot keep on chip; XLA everywhere else. No chip needed."""

    @pytest.mark.parametrize("t", [1024, 4096, 16384])
    def test_tpu_self_attention_over_the_crossover_is_tiled(self, t):
        assert attention_ops.choose("tpu", t, t, jnp.bfloat16) == "tiled"
        assert attention_ops.choose("tpu", t, t, jnp.bfloat16,
                                    batch_heads=16) == "tiled"

    @pytest.mark.parametrize("platform,t,s,dtype,batch_heads", [
        ("tpu", 4096, 231, jnp.bfloat16, 16),      # SD1.5 expanded, one image
        ("tpu", 1024, 231, jnp.bfloat16, 16),
        ("tpu", 1024, 231, jnp.bfloat16, 64),      # four images: 91 MB
        ("tpu", 4096, 77, jnp.bfloat16, 20),       # SDXL, one image
        ("tpu", 1024, 77, jnp.bfloat16, 40),
        ("tpu", 4096, 77, jnp.bfloat16, 40),       # sdxl_pair
        ("tpu", 1024, 77, jnp.bfloat16, 80),
        ("tpu", 4096, 77, jnp.bfloat16, 16),       # SD1.5, one chunk
        ("tpu", 256, 231, jnp.bfloat16, 64),       # under the crossover
        ("tpu", 256, 256, jnp.bfloat16, 16),
        ("tpu", 64, 64, jnp.bfloat16, 16),
        ("tpu", 4104, 4104, jnp.bfloat16, 16),     # does not tile
        ("tpu", 1023, 1023, jnp.bfloat16, 16),     # odd
        ("tpu", 4096, 4096, jnp.float32, 16),      # a dtype never timed
        ("tpu", 4096, 231, jnp.float32, 64),
        ("cpu", 4096, 4096, jnp.bfloat16, 16),
        ("cpu", 4096, 231, jnp.bfloat16, 64),
        ("gpu", 4096, 4096, jnp.bfloat16, 16),
    ])
    def test_everything_else_is_xla(self, platform, t, s, dtype,
                                    batch_heads):
        assert attention_ops.choose(platform, t, s, dtype,
                                    batch_heads=batch_heads) == "xla"

    @pytest.mark.parametrize("t,s,batch_heads", [
        (4096, 231, 64),       # SD1.5 expanded, four images: 363 MB of scores
        (4096, 231, 32),       # two images: 182 MB
        (4096, 154, 64),       # a context of two chunks
        (4096, 616, 16),       # eight chunks, one image
    ])
    def test_a_short_context_whose_scores_leave_the_chip_is_tiled(
            self, t, s, batch_heads):
        assert attention_ops.choose("tpu", t, s, jnp.bfloat16,
                                    batch_heads=batch_heads) == "tiled"
        for extra in ({"masked": True}, {"kv_groups": 2}):
            assert attention_ops.choose("tpu", t, s, jnp.bfloat16,
                                        batch_heads=batch_heads,
                                        **extra) == "xla"

    def test_the_line_is_what_xla_holds_a_score_against_the_vmem(self):
        at = attention_ops.ON_CHIP_BYTES // (4096 * 231 * 6)
        assert 16 < at < 32       # between the solo cells and two images
        assert attention_ops.choose("tpu", 4096, 231, jnp.bfloat16,
                                    batch_heads=at) == "xla"
        assert attention_ops.choose("tpu", 4096, 231, jnp.bfloat16,
                                    batch_heads=at + 1) == "tiled"

    def test_auto_on_this_cpu_is_xla_bit_for_bit(self):
        q, k, v = (x.astype(jnp.bfloat16) for x in qkv(1, 1024, 2, 64))
        out, path = attention_ops.attend(q, k, v, scale=0.125)
        assert path == "xla"
        np.testing.assert_array_equal(
            np.asarray(out, np.float32),
            np.asarray(jax.nn.dot_product_attention(q, k, v, scale=0.125),
                       np.float32))

    @pytest.mark.parametrize("impl,self_attention,s,want", [
        ("flash", True, 128, "tiled"),
        ("flash", False, 77, "tiled"),   # cross-attention: padded, masked
        ("flash", False, 231, "tiled"),
        ("flash", True, 76, "xla"),      # does not tile
        ("xla", True, 128, "xla"),
        ("ring", True, 128, "xla"),      # a ring site that fell through
    ])
    def test_explicit_impl_forces_a_side(self, impl, self_attention, s, want):
        q, k, v = qkv(1, s if self_attention else 64, 2, 64, s=s)
        out, path = attention_ops.attend(q, k, v, scale=0.125, impl=impl)
        assert path == want
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(reference(q, k, v)),
                                   rtol=2e-5, atol=2e-5)


class TestAttentionSites:
    """serving.attention of /internal/status: one UNet trace's sites by the
    path they took and by (T, S, head_dim)."""

    def _trace(self, impl, **changed):
        import dataclasses

        from stable_diffusion_webui_distributed_tpu.models.configs import (
            TINY_XL,
        )
        from stable_diffusion_webui_distributed_tpu.models.unet import UNet
        from stable_diffusion_webui_distributed_tpu.serving.metrics import (
            ATTENTION, METRICS,
        )

        cfg = dataclasses.replace(TINY_XL.unet, **changed)
        unet = UNet(cfg, attention_impl=impl)
        x = jnp.zeros((2, 16, 16, cfg.in_channels))
        args = (x, jnp.zeros((2,)), jnp.zeros((2, 77, cfg.cross_attention_dim)),
                jnp.zeros((2, cfg.projection_input_dim)))
        ATTENTION.clear()
        jax.eval_shape(
            lambda *a: unet.init_with_output(jax.random.key(0), *a)[0],
            *args)
        return METRICS.summary()["attention"]

    def test_default_counts_every_site_on_xla_off_the_tpu(self):
        got = self._trace("auto")
        # TINY_XL: depth 2 at the 8x8 level down and up (1 + 2 blocks of
        # layers_per_block + 1) and a mid block of depth 2, one self- and
        # one cross-attention each
        assert got["tiled"] == 0 and got["xla"] > 0
        assert got["xla"] % 2 == 0
        assert set(got["by_shape"]) == {"T64 S64 D16", "T64 S77 D16"}
        assert (got["by_shape"]["T64 S64 D16"]
                == got["by_shape"]["T64 S77 D16"]
                == {"xla": got["xla"] // 2})

    def test_forced_kernel_takes_every_site(self):
        """Self-attention and, its 77 keys padded and masked,
        cross-attention."""
        auto = self._trace("auto")
        got = self._trace("flash")
        assert got["tiled"] == auto["xla"] and got["xla"] == 0
        assert (got["by_shape"]["T64 S64 D16"]
                == got["by_shape"]["T64 S77 D16"]
                == {"tiled": got["tiled"] // 2})

    @pytest.mark.parametrize("channels,heads,d", [(160, 4, 40), (128, 2, 64)])
    def test_tiled_sites_by_the_layout_they_were_handed(self, channels,
                                                        heads, d):
        """SD1.5's head size and SDXL's both keep their heads in the lanes;
        ``tiled`` counts what it counted."""
        changed = dict(block_out_channels=(32, channels),
                       num_attention_heads=heads)
        auto = self._trace("auto", **changed)
        assert auto["tiled_layout"] == {"lanes": 0, "heads_major": 0}
        got = self._trace("flash", **changed)
        assert got["tiled"] == auto["xla"] > 0 and got["xla"] == 0
        assert (got["by_shape"][f"T64 S64 D{d}"]
                == got["by_shape"][f"T64 S77 D{d}"]
                == {"tiled": got["tiled"] // 2})
        assert got["tiled_layout"] == {"lanes": got["tiled"],
                                       "heads_major": 0}

    def test_a_loaded_program_counts_its_layouts_again(self):
        """serving/aot.py keeps a trace's counts with the program."""
        from stable_diffusion_webui_distributed_tpu.serving.metrics import (
            ATTENTION, capture_sites, replay_sites,
        )

        ATTENTION.clear()
        with capture_sites() as rows:
            flash_attention(*qkv(1, 64, 2, 40), interpret=True)
            flash_attention(*qkv(1, 64, 2, 64), interpret=True)
        traced = ATTENTION.summary()
        assert traced["tiled_layout"] == {"lanes": 2, "heads_major": 0}
        ATTENTION.clear()
        replay_sites(rows)
        assert ATTENTION.summary() == traced


@pytest.mark.slow
class TestInt8Quant:
    """Dynamic W8A8 linears (ops/quant.py): numerics vs f32 matmul, exact
    nn.Dense parameter compatibility, and the UNet flag wiring (the UNet
    case compiles two full TINY forwards — slow tier)."""

    def test_int8_dot_close_to_f32(self):
        from stable_diffusion_webui_distributed_tpu.ops.quant import int8_dot

        x = jnp.asarray(RNG.standard_normal((4, 64, 96), np.float32))
        w = jnp.asarray(RNG.standard_normal((96, 128), np.float32))
        got = np.asarray(int8_dot(x, w))
        want = np.asarray(x @ w)
        cos = (got * want).sum() / (np.linalg.norm(got)
                                    * np.linalg.norm(want))
        assert cos > 0.999, cos
        # 8-bit symmetric quantization error stays proportional to scale
        rel = np.abs(got - want).mean() / np.abs(want).mean()
        assert rel < 0.05, rel

    def test_quantdense_param_tree_matches_dense(self):
        import flax.linen as nn

        from stable_diffusion_webui_distributed_tpu.ops.quant import (
            QuantDense,
        )

        x = jnp.zeros((2, 16))
        dense = nn.Dense(24).init(jax.random.key(0), x)["params"]
        quant = QuantDense(24).init(jax.random.key(0), x)["params"]
        assert jax.tree_util.tree_structure(dense) == \
            jax.tree_util.tree_structure(quant)
        assert all(
            a.shape == b.shape
            for a, b in zip(jax.tree_util.tree_leaves(dense),
                            jax.tree_util.tree_leaves(quant)))
        # identical initializers => identical init values: a checkpoint
        # trained/converted for one loads into the other byte-for-byte
        for a, b in zip(jax.tree_util.tree_leaves(dense),
                        jax.tree_util.tree_leaves(quant)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_unet_quant_flag_same_params_close_output(self):
        from stable_diffusion_webui_distributed_tpu.models.configs import TINY
        from stable_diffusion_webui_distributed_tpu.models.unet import UNet

        cfg = TINY.unet
        lat = jnp.asarray(RNG.standard_normal((1, 8, 8, cfg.in_channels),
                                              np.float32))
        t = jnp.ones((1,))
        ctx = jnp.asarray(RNG.standard_normal(
            (1, 77, cfg.cross_attention_dim), np.float32)) * 0.1
        base = UNet(cfg)
        params = base.init(jax.random.key(0), lat, t, ctx)["params"]
        quant = UNet(cfg, quant_linears=True)
        # the SAME param tree drives both (checkpoint compatibility)
        out_f32 = base.apply({"params": params}, lat, t, ctx)
        out_q = quant.apply({"params": params}, lat, t, ctx)
        err = np.abs(np.asarray(out_q) - np.asarray(out_f32)).mean()
        ref = np.abs(np.asarray(out_f32)).mean()
        assert err / ref < 0.2, (err, ref)  # quantization noise, not garbage
        assert np.isfinite(np.asarray(out_q)).all()


@pytest.mark.slow
class TestInt8Conv:
    """Dynamic W8A8 convs (ops/quant.py QuantConv): numerics, exact
    nn.Conv parameter compatibility, and the quant_convs UNet flag."""

    def test_int8_conv_close_to_f32(self):
        from stable_diffusion_webui_distributed_tpu.ops.quant import (
            int8_conv,
        )

        x = jnp.asarray(RNG.standard_normal((2, 16, 16, 8), np.float32))
        w = jnp.asarray(RNG.standard_normal((3, 3, 8, 12), np.float32))
        got = np.asarray(int8_conv(x, w, padding=[(1, 1), (1, 1)]))
        want = np.asarray(jax.lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC")))
        cos = (got * want).sum() / (np.linalg.norm(got)
                                    * np.linalg.norm(want))
        assert cos > 0.999, cos

    def test_quantconv_param_tree_matches_conv(self):
        import flax.linen as nn

        from stable_diffusion_webui_distributed_tpu.ops.quant import (
            QuantConv,
        )

        x = jnp.zeros((1, 8, 8, 4))
        ref = nn.Conv(6, (3, 3), padding=1).init(jax.random.key(0), x)[
            "params"]
        qnt = QuantConv(6, (3, 3), padding=1).init(jax.random.key(0), x)[
            "params"]
        assert jax.tree_util.tree_structure(ref) == \
            jax.tree_util.tree_structure(qnt)
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(qnt)):
            assert a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_strided_matches_downsample_shape(self):
        from stable_diffusion_webui_distributed_tpu.ops.quant import (
            QuantConv,
        )

        x = jnp.asarray(RNG.standard_normal((1, 16, 16, 4), np.float32))
        mod = QuantConv(4, (3, 3), strides=(2, 2), padding=1)
        params = mod.init(jax.random.key(1), x)["params"]
        out = mod.apply({"params": params}, x)
        assert out.shape == (1, 8, 8, 4)

    def test_unet_quant_convs_same_params_close_output(self):
        from stable_diffusion_webui_distributed_tpu.models.configs import TINY
        from stable_diffusion_webui_distributed_tpu.models.unet import UNet

        cfg = TINY.unet
        lat = jnp.asarray(RNG.standard_normal((1, 8, 8, cfg.in_channels),
                                              np.float32))
        t = jnp.ones((1,))
        ctx = jnp.asarray(RNG.standard_normal(
            (1, 77, cfg.cross_attention_dim), np.float32)) * 0.1
        base = UNet(cfg)
        params = base.init(jax.random.key(0), lat, t, ctx)["params"]
        quant = UNet(cfg, quant_linears=True, quant_convs=True)
        out_f32 = base.apply({"params": params}, lat, t, ctx)
        out_q = quant.apply({"params": params}, lat, t, ctx)
        err = np.abs(np.asarray(out_q) - np.asarray(out_f32)).mean()
        ref = np.abs(np.asarray(out_f32)).mean()
        assert err / ref < 0.35, (err, ref)
        assert np.isfinite(np.asarray(out_q)).all()


@pytest.mark.slow
class TestInt8LoraInterop:
    """LoRA merges mutate the SAME kernel params QuantDense reads at call
    time (dynamic quantization has no stored scales), so a merged adapter
    must change the int8 path's output exactly like the f32 path's."""

    def test_merged_lora_affects_int8_forward(self):
        from stable_diffusion_webui_distributed_tpu.models import (
            lora as lora_mod,
        )
        from stable_diffusion_webui_distributed_tpu.models.configs import TINY
        from stable_diffusion_webui_distributed_tpu.models.unet import UNet
        from test_adapters import make_lora_sd

        cfg = TINY.unet
        lat = jnp.asarray(RNG.standard_normal((1, 8, 8, cfg.in_channels),
                                              np.float32))
        t = jnp.ones((1,))
        ctx = jnp.asarray(RNG.standard_normal(
            (1, 77, cfg.cross_attention_dim), np.float32)) * 0.1
        base = UNet(cfg)
        params = base.init(jax.random.key(0), lat, t, ctx)["params"]
        merged, applied, _ = lora_mod.merge_lora(
            {"unet": params, "text_encoder": {}}, make_lora_sd(), 1.0, TINY)
        assert applied > 0
        quant = UNet(cfg, quant_linears=True)
        out_base = quant.apply({"params": params}, lat, t, ctx)
        out_merged = quant.apply({"params": merged["unet"]}, lat, t, ctx)
        assert not np.allclose(np.asarray(out_base),
                               np.asarray(out_merged))


@pytest.mark.slow
class TestInt8UnderMesh:
    """int8_dot under GSPMD: per-token activation scales and per-channel
    weight scales must compose with dp/tp shardings (multi-chip int8 is
    how the roofline lever scales past one chip)."""

    def test_int8_dot_sharded_matches_single_device(self, mesh8):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from stable_diffusion_webui_distributed_tpu.ops.quant import int8_dot

        x = jnp.asarray(RNG.standard_normal((8, 32, 64), np.float32))
        w = jnp.asarray(RNG.standard_normal((64, 96), np.float32))
        want = np.asarray(int8_dot(x, w))
        xs = jax.device_put(x, NamedSharding(mesh8, P("dp", None, None)))
        ws = jax.device_put(w, NamedSharding(mesh8, P(None, "tp")))
        got = np.asarray(jax.jit(int8_dot)(xs, ws))
        # dp shards tokens (per-token scales are token-local: exact);
        # tp shards output channels (per-channel scales channel-local:
        # exact) — the sharded result must match bit-for-bit up to XLA
        # reduction-order noise in the int32->f32 rescale
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


class TestMeshSafeConcat:
    """Regression guards for the SPMD partitioner concat hazard: jax 0.4.x
    mis-partitioned a ``jnp.concatenate`` along a sharded dimension on a
    mesh with a second (operand-unused) axis, summing the replicas along
    that axis into the output (rows came out scaled by the axis size), and
    ``parallel/sharding.py`` carried stack+reshape and pad+add stand-ins for
    it. The installed jax partitions the plain concatenate correctly, so the
    engine and the UNet call it (the pad+add cost 30 ms an SDXL request,
    PERF.md section 6, PR 43); these pin that it stays correct on sharded
    operands, at the shapes the engine and the UNet concatenate."""

    def _dp_sharded(self, x, mesh8):
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(*(["dp"] + [None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh8, spec))

    def test_batch_concat_matches_concatenate_semantics(self):
        """The CFG halves as pipeline/denoise.py:cfg_rows joins them."""
        from stable_diffusion_webui_distributed_tpu.pipeline.denoise import (
            Inputs, cfg_rows,
        )

        x = jnp.asarray(RNG.standard_normal((2, 3, 3, 4), np.float32))
        u = jnp.asarray(RNG.standard_normal((1, 5, 6), np.float32))
        c = jnp.asarray(RNG.standard_normal((2, 5, 6), np.float32))
        inp = Inputs(ctx_u=u, ctx_c=c)
        latent, unet_in, tb, ctx, added = cfg_rows(x, 3.0, inp)
        np.testing.assert_array_equal(latent, np.concatenate([x, x]))
        np.testing.assert_array_equal(
            ctx, np.concatenate([np.broadcast_to(u, c.shape), c]))
        assert unet_in is latent and added is None and tb.shape == (4,)

    def test_batch_concat_dp_sharded_operand(self, mesh8):
        """The CFG [x; x] doubling with a dp-sharded latent — the exact
        shape of the TestMeshEngine dp=4,tp=2 corruption."""
        x = np.asarray(RNG.standard_normal((4, 8, 8, 4), np.float32))
        xs = self._dp_sharded(jnp.asarray(x), mesh8)
        want = np.concatenate([x, x], axis=0)
        np.testing.assert_array_equal(np.asarray(jnp.concatenate([xs, xs])),
                                      want)
        jitted = jax.jit(lambda v: jnp.concatenate([v, v]))
        np.testing.assert_array_equal(np.asarray(jitted(xs)), want)

    def test_channel_concat_matches_concatenate_semantics(self):
        """An inpainting model's [latent, mask, masked image] channels."""
        from stable_diffusion_webui_distributed_tpu.pipeline.denoise import (
            Inputs, cfg_rows,
        )

        x = jnp.asarray(RNG.standard_normal((2, 4, 4, 4), np.float32))
        cond = jnp.asarray(RNG.standard_normal((2, 4, 4, 5), np.float32))
        ctx = jnp.zeros((2, 3, 2), jnp.float32)
        inp = Inputs(ctx_u=ctx, ctx_c=ctx, inpaint_cond=cond)
        _, unet_in, _, _, _ = cfg_rows(x, 1.0, inp, inpaint=True)
        np.testing.assert_array_equal(
            unet_in, np.concatenate(
                [np.concatenate([x, x]), np.concatenate([cond, cond])],
                axis=-1))

    def test_channel_concat_tp_sharded_operands(self, mesh8):
        """The UNet decoder's skip concat with tp-sharded channels of
        unequal widths."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        a = np.asarray(RNG.standard_normal((2, 4), np.float32))
        b = np.asarray(RNG.standard_normal((2, 6), np.float32))
        sh = NamedSharding(mesh8, P(None, "tp"))
        as_, bs_ = jax.device_put(jnp.asarray(a), sh), \
            jax.device_put(jnp.asarray(b), sh)
        want = np.concatenate([a, b], axis=-1)
        np.testing.assert_array_equal(
            np.asarray(jnp.concatenate([as_, bs_], axis=-1)), want)
        jitted = jax.jit(lambda u, v: jnp.concatenate([u, v], axis=-1))
        np.testing.assert_array_equal(np.asarray(jitted(as_, bs_)), want)


@pytest.mark.slow
class TestInt8ControlNet:
    def test_controlnet_quant_same_params_close_output(self):
        """The CN copy of the UNet honors the same quant flags with the
        same param tree (c3-int8 would otherwise leave half the FLOPs in
        bf16)."""
        from stable_diffusion_webui_distributed_tpu.models.configs import TINY
        from stable_diffusion_webui_distributed_tpu.models.controlnet import (
            ControlNet,
        )

        cfg = TINY.unet
        lat = jnp.asarray(RNG.standard_normal((1, 8, 8, cfg.in_channels),
                                              np.float32))
        t = jnp.ones((1,))
        ctx = jnp.asarray(RNG.standard_normal(
            (1, 77, cfg.cross_attention_dim), np.float32)) * 0.1
        hint = jnp.asarray(RNG.random((1, 64, 64, 3)), jnp.float32)
        base = ControlNet(cfg)
        params = base.init(jax.random.key(0), lat, t, ctx, hint)["params"]
        # randomize the zero-initialized output convs, otherwise every
        # residual is exactly zero on both paths and the comparison below
        # would be vacuous
        params = jax.tree_util.tree_map(
            lambda x: jnp.asarray(
                RNG.standard_normal(x.shape).astype(np.float32) * 0.05)
            if x.ndim == 4 else x, params)
        quant = ControlNet(cfg, quant_linears=True, quant_convs=True)
        out_b = base.apply({"params": params}, lat, t, ctx, hint)
        out_q = quant.apply({"params": params}, lat, t, ctx, hint)
        assert len(out_b) == len(out_q)
        worst = 0.0
        for a, b in zip(out_b, out_q):
            a, b = np.asarray(a), np.asarray(b)
            assert np.isfinite(b).all()
            assert a.shape == b.shape
            denom = max(np.abs(a).mean(), 1e-6)
            worst = max(worst, float(np.abs(a - b).mean() / denom))
        assert worst < 0.5, worst   # quantization noise, not garbage
        # and the residuals are genuinely non-zero (comparison is real)
        assert max(float(np.abs(np.asarray(r)).max()) for r in out_b) > 0


class TestRingChunking:
    """The ring body folds each rotating K/V block in bounded key-chunks;
    the chunked fold must match the dense fold (same associative update,
    finer granularity)."""

    def test_chunked_matches_unchunked(self, monkeypatch):
        from stable_diffusion_webui_distributed_tpu.ops.ring_attention import (
            ring_attention,
        )
        from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
            build_mesh,
        )

        mesh = build_mesh("sp=4")
        q, k, v = qkv(1, 4 * 512, 2, 16)   # t_loc = 512 per device
        monkeypatch.setenv("SDTPU_RING_CHUNK", "1024")  # 1 chunk (dense)
        dense = np.asarray(ring_attention(q, k, v, mesh))
        monkeypatch.setenv("SDTPU_RING_CHUNK", "128")   # 4 chunks per block
        chunked = np.asarray(jax.jit(
            lambda a, b, c: ring_attention(a, b, c, mesh))(q, k, v))
        np.testing.assert_allclose(chunked, dense, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            dense, np.asarray(reference(q, k, v)), rtol=2e-4, atol=2e-4)

    def test_non_divisor_chunk_pads_masked_tail(self, monkeypatch):
        """A chunk size that does not divide the per-device block pads K/V
        with masked rows (scores -> -inf) instead of silently rounding the
        chunk down — the result must still match the dense fold."""
        from stable_diffusion_webui_distributed_tpu.ops.ring_attention import (
            ring_attention,
        )
        from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
            build_mesh,
        )

        mesh = build_mesh("sp=4")
        q, k, v = qkv(1, 4 * 128, 2, 16)   # t_loc = 128 per device
        monkeypatch.setenv("SDTPU_RING_CHUNK", "48")  # 3 chunks, 16 pad rows
        chunked = np.asarray(ring_attention(q, k, v, mesh))
        np.testing.assert_allclose(
            chunked, np.asarray(reference(q, k, v)), rtol=2e-4, atol=2e-4)

    def test_chunk_env_warn_and_default(self, monkeypatch):
        import importlib

        # the ops package re-exports the ring_attention FUNCTION under the
        # module's name, so fetch the module itself
        ra = importlib.import_module(
            "stable_diffusion_webui_distributed_tpu.ops.ring_attention")
        monkeypatch.setenv("SDTPU_RING_CHUNK", "not-an-int")
        with pytest.warns(UserWarning, match="SDTPU_RING_CHUNK"):
            assert ra._ring_chunk() == ra._RING_CHUNK_DEFAULT

"""The resident prompt expander: a config-driven decoder LM with window and
full attention mixed, head counts that differ by layer, a dense layer and
expert layers in one stack, on the txt2img path.

Everything runs the tiny preset that keeps every kind (models/configs.py
``TINY_LM``): two head counts, a window of 8 under every context here so the
ring wraps, a dense first layer, 16 experts top-4 with a shared one, partial
YaRN and full plain rotary. The plain reference is the benchmark's own
(benchmarks/reference/laguna_ref.py: float32, no cache, no chunks).
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.models.tokenizer import (
    FallbackLMTokenizer, load_lm_tokenizer,
)
from stable_diffusion_webui_distributed_tpu.ops import (
    attention, moe, moe_kernel,
)
from stable_diffusion_webui_distributed_tpu.pipeline import expand
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    prompt_expansion_args,
)
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    EXPANDER, METRICS, PLAN,
)
from tests import expander_contract as contract
from tests.expander_contract import empty, rel_rms, run, tiny_params

REF = contract.load_reference("laguna")
#: ``DecoderLM.init``'s tree as it is
CASE = contract.Case(configs.TINY_EXPAND, REF, word="rule", tolerance=1e-4)
FAMILY, CFG = CASE.family, CASE.cfg
params, engine = contract.fixtures(CASE)


class TestAgainstTheReference(contract.OneSequenceAgainstTheReference):
    """Prefix prefill, user-chunk prefill against it, then one token a
    step through both cache kinds; the ring (8 slots) wraps several times.
    Logits at every position against one plain forward."""
    CASE = CASE
    test_prefill_then_cached_decode_matches_the_full_forward = \
        contract.OneSequenceAgainstTheReference \
        .prefill_then_decode_matches_the_full_forward
    PARAMETERS = {
        "test_prefill_then_cached_decode_matches_the_full_forward": [
            ("size", [40, 60])]}

    def test_the_int8_control_is_further_from_the_reference(self, params):
        (ids,), want, _ = CASE.referred(40)
        control = CASE.program(control=True)(params, ids)
        assert rel_rms(control, want) > 1e-3

    def test_a_padded_chunk_gives_what_the_exact_chunk_gives(self, params):
        (ids,) = REF.inputs(FAMILY, 5, 24)
        exact, cache_a, _ = run(CFG, params, ids[:19], 0, 19, empty(CFG, 32),
                                all_logits=False)
        padded, cache_b, _ = run(CFG, params, ids, 0, 19, empty(CFG, 32),
                                 all_logits=False)
        np.testing.assert_allclose(exact, padded, rtol=1e-5, atol=1e-5)
        # decoding on from both caches agrees: the pad rows left no trace
        nxt = lambda c: run(CFG, params, ids[19:20], 19, 1, c,  # noqa: E731
                            all_logits=False)[0]
        np.testing.assert_allclose(nxt(cache_a), nxt(cache_b), rtol=1e-5,
                                   atol=1e-5)

    def test_reference_held_to_other_routing_differs(self, params):
        (ids,), want, own = CASE.referred(16)
        forced = jax.jit(lambda p, i, f: REF.forward(FAMILY, p, i, forced=f))
        np.testing.assert_allclose(forced(params, ids, own), want,
                                   rtol=1e-5, atol=1e-5)
        other = forced(params, ids, (own + 1) % CFG.num_experts)
        assert rel_rms(other, want) > 1e-3


class TestRope:
    def test_program_and_reference_frequencies_agree(self):
        for rope in (CFG.rope_full, CFG.rope_sliding,
                     configs.LAGUNA_S_2_1.rope_full,
                     configs.LAGUNA_S_2_1.rope_sliding):
            dim = 128 if rope.theta == 5e5 and rope.factor == 128 else 16
            np.testing.assert_allclose(lm.rope_frequencies(rope, dim),
                                       REF._inv_freq(rope, dim), rtol=1e-12)

    def test_yarn_interpolates_slow_pairs_and_keeps_fast_ones(self):
        rope = configs.LAGUNA_S_2_1.rope_full
        freq = lm.rope_frequencies(rope, 128)
        plain = 1.0 / rope.theta ** (np.arange(0, 64, 2) / 64)
        assert freq.shape == (32,)       # half of 128 dims are rotated
        np.testing.assert_allclose(freq[0], plain[0])
        np.testing.assert_allclose(freq[-1], plain[-1] / rope.factor)

    def test_partial_rotary_leaves_the_rest_alone(self):
        x = jax.random.normal(jax.random.key(0), (5, 3, 16))
        cos, sin = lm.rope_tables(CFG.rope_full, 16, jnp.arange(5))
        out = lm.apply_rope(x, cos, sin)
        assert cos.shape == (5, 4)
        np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
        assert not np.allclose(out[1:, :, :8], x[1:, :, :8])


class TestMasks:
    def test_causal_and_window(self):
        t, kv_heads, heads, dim = 12, 2, 6, 8
        key = jax.random.key(1)
        q = jax.random.normal(key, (t, heads, dim))
        k = jax.random.normal(jax.random.fold_in(key, 1), (t, kv_heads, dim))
        v = jax.random.normal(jax.random.fold_in(key, 2), (t, kv_heads, dim))
        pos = jnp.arange(t)
        for window in (0, 5):
            out, path = attention.attend_positions(
                q, k, v, pos, pos, scale=dim ** -0.5, window=window)
            assert path == attention.XLA
            of = np.arange(heads) * kv_heads // heads
            scores = np.einsum("ihd,jhd->hij", q, np.asarray(k)[:, of]) \
                * dim ** -0.5
            i, j = np.arange(t)[:, None], np.arange(t)[None, :]
            seen = (j <= i) & ((i - j < window) if window else True)
            scores = np.where(seen[None], scores, -np.inf)
            probs = np.exp(scores - scores.max(-1, keepdims=True))
            probs /= probs.sum(-1, keepdims=True)
            want = np.einsum("hij,jhd->ihd", probs, np.asarray(v)[:, of])
            np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)

    def test_empty_slots_and_unseeing_rows(self):
        q = jnp.ones((2, 2, 4))
        k = v = jnp.ones((3, 1, 4))
        out, _ = attention.attend_positions(
            q, k, v, jnp.array([0, 5]), jnp.array([-1, 3, 9]), scale=1.0)
        np.testing.assert_array_equal(out[0], 0.0)    # sees nothing
        np.testing.assert_allclose(out[1], 1.0)       # sees position 3 only

    @pytest.mark.parametrize("window", [0, 12])
    @pytest.mark.parametrize("filled", [1, 8])
    @pytest.mark.parametrize("forked_at", [20, 32])
    @pytest.mark.parametrize("groups", [1, 4])
    def test_two_ranges_are_one_softmax_over_both(self, groups, forked_at,
                                                  filled, window):
        """Four sequences' queries over 32 shared slots (the fork inside
        them or at their end: the slots behind it are empty) and 8 of
        their own (one filled, the first step; all filled), against each
        query alone over the shared keys followed by its own."""
        b, kv_heads, dim, s, t = 4, 2, 8, 32, 8
        key = jax.random.key(2)

        def normal(i, shape):
            return jax.random.normal(jax.random.fold_in(key, i), shape)

        q = normal(0, (b, kv_heads * groups, dim))
        k_shared, v_shared = normal(1, (s, kv_heads, dim)), \
            normal(2, (s, kv_heads, dim))
        k_own, v_own = normal(3, (b, t, kv_heads, dim)), \
            normal(4, (b, t, kv_heads, dim))
        q_pos = jnp.full((b,), forked_at + filled - 1)
        shared_pos = jnp.where(jnp.arange(s) < forked_at, jnp.arange(s), -1)
        own_pos = forked_at + jnp.arange(t)     # the unfilled lie ahead
        out, path = attention.attend_two_ranges(
            q, k_shared, v_shared, k_own, v_own, q_pos, shared_pos, own_pos,
            scale=dim ** -0.5, window=window)
        assert path == attention.XLA and out.shape == q.shape
        for i in range(b):
            want, _ = attention.attend_positions(
                q[i][None], jnp.concatenate([k_shared, k_own[i]]),
                jnp.concatenate([v_shared, v_own[i]]), q_pos[:1],
                jnp.concatenate([shared_pos, own_pos]), scale=dim ** -0.5,
                window=window)
            np.testing.assert_allclose(out[i], want[0], rtol=1e-5,
                                       atol=1e-6)
        assert not np.allclose(out[0], out[1])

    @pytest.mark.parametrize("window", [0, 12, 24])
    def test_two_ranges_whose_own_rows_hold_a_position_a_sequence(
            self, window):
        """A joined cache's site: the sequences stand at positions of
        their own (prompts of 2, 9 and 16 rows right-aligned in 16 slots
        behind 20 shared rows, then 3 decoded), so an own slot holds
        another position a sequence (``own_pos`` ``(B, T)``, none in front
        of a sequence's first) and a window reaches another depth into the
        shared range for each: against each query alone over the shared
        keys followed by its own real rows."""
        b, kv_heads, dim, s, region, made = 3, 2, 8, 32, 16, 3
        forked_at, users = 20, np.asarray([2, 9, 16])
        key = jax.random.key(5)

        def normal(i, shape):
            return jax.random.normal(jax.random.fold_in(key, i), shape)

        q = normal(0, (b, 4 * kv_heads, dim))
        k_shared, v_shared = normal(1, (s, kv_heads, dim)), \
            normal(2, (s, kv_heads, dim))
        k_own, v_own = normal(3, (b, region + 8, kv_heads, dim)), \
            normal(4, (b, region + 8, kv_heads, dim))
        own_from = jnp.asarray(region - users)
        start = forked_at + region + made - 1       # the step's, common
        q_pos = start - own_from
        shared_pos = jnp.where(jnp.arange(s) < forked_at, jnp.arange(s), -1)
        slots = jnp.arange(region + 8)
        common = jnp.where(slots < region + made, forked_at + slots, -1)
        own_pos = lm.own_positions(common, forked_at, own_from)
        assert own_pos.shape == (b, region + 8)
        for i, user in enumerate(users):    # its prompt, then what it made
            assert np.array_equal(
                own_pos[i][own_pos[i] >= 0],
                forked_at + np.arange(user + made))
        out, _ = attention.attend_two_ranges(
            q, k_shared, v_shared, k_own, v_own, q_pos, shared_pos, own_pos,
            scale=dim ** -0.5, window=window)
        reach = []
        for i in range(b):
            want, _ = attention.attend_positions(
                q[i][None], jnp.concatenate([k_shared, k_own[i]]),
                jnp.concatenate([v_shared, v_own[i]]), q_pos[i][None],
                jnp.concatenate([shared_pos, own_pos[i]]),
                scale=dim ** -0.5, window=window)
            np.testing.assert_allclose(out[i], want[0], rtol=1e-5,
                                       atol=1e-6)
            reach.append(int(np.sum(np.asarray(attention._seen(
                q_pos[i][None], shared_pos, window)))))
        # the shortest prompt's window reaches furthest into what is shared
        assert reach == ([forked_at] * 3 if not window
                         else sorted(reach, reverse=True))
        assert window != 12 or reach == [7, 0, 0]
        assert window != 24 or reach == [19, 12, 5]

    def test_two_ranges_and_a_query_that_sees_nothing(self):
        """Empty slots in both ranges; the second sequence's query stands
        before every key and gets zeros."""
        q = jnp.ones((2, 2, 4))
        k_shared = v_shared = jnp.ones((3, 1, 4))
        k_own = v_own = 2 * jnp.ones((2, 2, 1, 4))
        out, _ = attention.attend_two_ranges(
            q, k_shared, v_shared, k_own, v_own, jnp.array([6, 1]),
            jnp.array([-1, 3, 9]), jnp.array([-1, 8]), scale=1.0)
        np.testing.assert_allclose(out[0], 1.0)     # position 3 only
        np.testing.assert_array_equal(out[1], 0.0)
        out, _ = attention.attend_two_ranges(
            q, k_shared, v_shared, k_own, v_own, jnp.array([8, 8]),
            jnp.array([-1, -1, 9]), jnp.array([-1, 8]), scale=1.0)
        np.testing.assert_allclose(out, 2.0)        # its own row only

    @pytest.mark.parametrize("site", [
        ("tpu", 4096, 4096, jnp.bfloat16, 20),
        ("tpu", 1024, 1024, jnp.bfloat16, 40),
        ("tpu", 256, 256, jnp.bfloat16, 16),
        ("tpu", 4096, 77, jnp.bfloat16, 20),
        ("tpu", 4096, 4096, jnp.float32, 20),
        ("cpu", 4096, 4096, jnp.bfloat16, 20),
    ])
    def test_the_unet_sites_choose_as_before(self, site):
        platform, t, s, dtype, batch_heads = site
        want = (attention.TILED if platform == "tpu" and s == t
                and dtype == jnp.bfloat16 and t >= 1024 else attention.XLA)
        assert attention.choose(platform, t, s, dtype,
                                batch_heads=batch_heads) == want
        for extra in ({"masked": True}, {"kv_groups": 6}):
            assert attention.choose(platform, t, s, dtype,
                                    batch_heads=batch_heads,
                                    **extra) == attention.XLA


ROUTED_EXPERTS = jax.jit(lambda *a: moe.routed_experts(
    *a, first=0, num_experts=16)[0])


class TestExperts:
    def _layer(self, tokens, seed=0, experts=16, k=4, scale=2.5):
        key = jax.random.key(seed)
        d, f = 32, 16
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (tokens, d))
        logits = jax.random.normal(ks[1], (tokens, experts))
        wg = jax.random.normal(ks[2], (experts, d, f)) / d ** 0.5
        wu = jax.random.normal(ks[3], (experts, d, f)) / d ** 0.5
        wd = jax.random.normal(ks[4], (experts, f, d)) / f ** 0.5
        return x, moe.route(logits, k, renormalise=True, scale=scale), \
            wg, wu, wd

    def _dense(self, x, routing, wg, wu, wd):
        out = np.zeros(x.shape, np.float64)
        for t in range(x.shape[0]):
            for e, w in zip(np.asarray(routing.experts[t]),
                            np.asarray(routing.weights[t])):
                h = jax.nn.silu(x[t] @ wg[e]) * (x[t] @ wu[e])
                out[t] += w * np.asarray(h @ wd[e])
        return out

    def test_route_is_topk_of_a_softmax_renormalised_and_scaled(self):
        _, routing, *_ = self._layer(7)
        np.testing.assert_allclose(routing.weights.sum(-1), 2.5, rtol=1e-5)
        assert routing.experts.shape == (7, 4)
        assert np.all(np.diff(np.asarray(routing.weights), axis=-1) <= 0)

    @pytest.mark.parametrize("tokens", [1, 5, 40])
    def test_both_products_equal_the_dense_sum(self, tokens):
        x, routing, wg, wu, wd = self._layer(tokens)
        paths = []

        def layer(*a):
            out, path = moe.routed_experts(*a, first=0, num_experts=16)
            paths.append(path)
            return out

        jax.eval_shape(layer, x, routing, wg, wu, wd)   # chosen when traced
        got = ROUTED_EXPERTS(x, routing, wg, wu, wd)
        assert paths == [moe.LOOP if tokens == 1 else moe.GROUPED]
        np.testing.assert_allclose(got, self._dense(x, routing, wg, wu, wd),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("tokens,experts,k,scale", [
        (1, 16, 4, 2.5), (24, 16, 4, 2.5),
        # 64 experts, 8 a token, unscaled, no shared expert to count once;
        # four rows: a decode step of four sequences
        (4, 64, 8, 1.0)])
    def test_the_share(self, tokens, experts, k, scale):
        """The two halves' routed parts add up to the uncut layer's: a chip
        computes its own experts and adds nothing for the absent ones. A
        share that holds every expert gives the whole layer's result."""
        x, routing, wg, wu, wd = self._layer(tokens, seed=2, experts=experts,
                                             k=k, scale=scale)
        whole = self._dense(x, routing, wg, wu, wd)
        half = experts // 2
        parts = [moe.routed_experts(x, routing, wg[lo:lo + half],
                                    wu[lo:lo + half], wd[lo:lo + half],
                                    first=lo, num_experts=experts)[0]
                 for lo in (0, half)]
        assert not np.allclose(parts[0], whole, atol=1e-3)
        np.testing.assert_allclose(parts[0] + parts[1], whole, rtol=2e-4,
                                   atol=2e-5)
        held = moe.routed_experts(x, routing, wg, wu, wd, first=0,
                                  num_experts=experts)[0]
        np.testing.assert_allclose(held, whole, rtol=2e-4, atol=2e-5)

    def test_load_counts(self):
        _, routing, *_ = self._layer(24, seed=2)
        valid = jnp.arange(24) < 20
        load, none = moe.load_counts(routing, 8, 8, valid)
        chosen = np.asarray(routing.experts)[:20]
        assert list(load) == [int((chosen == e).sum()) for e in range(8, 16)]
        assert int(none) == int((~((chosen >= 8).any(-1))).sum())

    def test_row_tile_is_a_power_of_two_near_the_mean_rows(self):
        assert moe.row_tile(576, 10, 256) == 32
        assert moe.row_tile(64, 10, 256) == 8


#: (f, the widest block the ring's VMEM admits, the blocks of an expert:
#: ``wide`` x ``wides``) at d 128 in float32, where one lane width of an
#: expert's three slabs is 192 KiB
RINGS = {
    "one_block": (256, 256, (256, 1)),
    "two_blocks": (256, 128, (128, 2)),
    "eight_blocks": (1024, 128, (128, 8)),
    "three_blocks": (768, 256, (256, 3)),
}


def _ring(monkeypatch, name, d=128, itemsize=4):
    """The ring's VMEM set so that ``RINGS[name]`` is what a call of these
    widths walks; the width ``f``."""
    f, widest, blocks = RINGS[name]
    buffers = moe_kernel._RING_BUFFERS
    monkeypatch.setattr(moe_kernel, "_WEIGHT_VMEM",
                        buffers * 3 * d * widest * itemsize)
    assert moe_kernel.ring(d, f, itemsize) == moe_kernel.Ring(buffers,
                                                              *blocks)
    return f


@pytest.fixture(scope="class")
def programs_released():
    """The interpreter compiles a program a case, and jax keeps each for
    the life of the process: a dozen memory maps a program, 30 000 by the
    end of this file's kernel classes, of the 65 530 the kernel gives a
    process. A worker that had run this file beside others came to the
    limit and XLA's next compile died there (a segmentation fault in
    another file's test). The kernel classes let theirs go."""
    yield
    jax.clear_caches()


@pytest.fixture
def copies_started(monkeypatch):
    """The ``dma_start``s a kernel call EXECUTES in interpret mode, one
    entry each: the rule that discharges a start to a plain copy also says
    so to the host, from inside whatever ``cond`` or ``while`` holds it."""
    from jax._src.pallas.mosaic import primitives as mosaic
    from jax._src.state import discharge

    started = []
    rule = discharge._partial_discharge_rules[mosaic.dma_start_p]

    def counting(*args, **kwargs):
        jax.debug.callback(lambda: started.append(1))
        return rule(*args, **kwargs)

    monkeypatch.setitem(discharge._partial_discharge_rules,
                        mosaic.dma_start_p, counting)
    moe_kernel._call.clear_cache()      # nothing traced without the count
    yield started
    moe_kernel._call.clear_cache()


@pytest.mark.usefixtures("programs_released")
class TestTheChosenExpertsKernel:
    """ops/moe_kernel.py in interpret mode against ``moe._chosen``'s loop,
    at small widths on the lane tiling. The token chose experts 9, 2, 12
    and 5; which of them are held is the share's range."""

    D, F, EXPERTS = 128, 256, 16

    def _layer(self, dtype, seed=3, f=None):
        ks = jax.random.split(jax.random.key(seed), 4)
        d, f, e = self.D, f or self.F, self.EXPERTS
        x = jax.random.normal(ks[0], (1, d)).astype(dtype)
        wg = (jax.random.normal(ks[1], (e, d, f)) / d ** 0.5).astype(dtype)
        wu = (jax.random.normal(ks[2], (e, d, f)) / d ** 0.5).astype(dtype)
        wd = (jax.random.normal(ks[3], (e, f, d)) / f ** 0.5).astype(dtype)
        routing = moe.Routing(jnp.array([[9, 2, 12, 5]], jnp.int32),
                              jnp.array([[0.9, 0.7, 0.5, 0.4]], jnp.float32))
        return x, routing, wg, wu, wd

    @pytest.mark.parametrize("blocks", list(RINGS))
    @pytest.mark.parametrize("first,count,held", [
        (13, 3, 0), (8, 2, 1), (4, 8, 2), (2, 11, 4), (0, 16, 4)])
    def test_the_kernel_equals_the_loop(self, monkeypatch, first, count,
                                        held, blocks):
        """None, one, some and all of the chosen experts held, shares that
        start past expert 0; a whole expert a block, two, three and eight
        blocks an expert (an odd count: the ring's slots alternate across
        the experts' boundaries)."""
        f = _ring(monkeypatch, blocks)
        x, routing, wg, wu, wd = self._layer(jnp.float32, f=f)
        share = [w[first:first + count] for w in (wg, wu, wd)]
        assert int(moe.held_mask(routing.experts[0], first,
                                 count)[1].sum()) == held
        want = moe._chosen(x, routing, *share, first)
        got = moe._chosen(x, routing, *share, first, kernel=True)
        assert got.shape == (1, self.D) and got.dtype == jnp.float32
        if held == 0:
            assert not np.any(np.asarray(got))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("blocks", ["one_block", "three_blocks"])
    @pytest.mark.parametrize("rows", [1, 4])
    @pytest.mark.parametrize("held", [0, 1, 2, 3, 4])
    def test_a_call_reads_its_held_experts_blocks_and_nothing_else(
            self, monkeypatch, copies_started, held, rows, blocks):
        """``held x blocks`` reads of three copies each, whatever the slots: a
        call that holds nothing starts NO copy and returns exact zeros (the
        ``BlockSpec`` pipeline this replaced fetched its first step's
        blocks before it could know: ``max(held, 1) x blocks`` less
        repeats). The ids behind the held ones are never looked at: they
        point past the share."""
        f = _ring(monkeypatch, blocks)
        x, _, wg, wu, wd = self._layer(jnp.float32, f=f)
        x = jnp.tile(x, (rows, 1))
        ids = jnp.where(jnp.arange(4) < held, jnp.array([3, 0, 2, 1]), 99)
        weights = jnp.full((4,) if rows == 1 else (4, rows), 0.5)
        got = moe_kernel.chosen_experts(x, ids, weights, jnp.int32(held),
                                        wg[:4], wu[:4], wd[:4])
        jax.block_until_ready(got)
        jax.effects_barrier()
        blocks = moe_kernel.ring(self.D, f, 4)
        assert len(copies_started) == 3 * held * blocks.wides
        want = sum(0.5 * moe._swiglu(x, wg[e], wu[e], wd[e])
                   for e in [3, 0, 2, 1][:held]) if held else 0.0 * x
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        if not held:
            assert not np.any(np.asarray(got))

    @pytest.mark.parametrize("limit", [0.0, 0.25])
    def test_the_clamp_is_a_blocks_as_it_is_the_wholes(self, monkeypatch,
                                                       limit):
        f = _ring(monkeypatch, "three_blocks")
        x, routing, wg, wu, wd = self._layer(jnp.float32, f=f)
        want = moe._chosen(x, routing, wg, wu, wd, 0, limit=limit)
        got = moe._chosen(x, routing, wg, wu, wd, 0, kernel=True,
                          limit=limit)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        unclamped = moe._chosen(x, routing, wg, wu, wd, 0)
        assert bool(jnp.allclose(want, unclamped)) == (not limit)

    @pytest.mark.parametrize("precision", [None, "highest"])
    def test_bf16_operands_accumulate_in_float32(self, precision):
        """The serving policy's operands, and a caller's matmul precision
        that must not reach the kernel's dots."""
        x, routing, wg, wu, wd = self._layer(jnp.bfloat16)
        want = moe._chosen(x, routing, wg, wu, wd, 0)
        with jax.default_matmul_precision(precision or "default"):
            got = moe._chosen(x, routing, wg, wu, wd, 0, kernel=True)
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("d,f,itemsize,blocks", [
        (3072, 1024, 2, (512, 2)),  # Laguna-S-2.1's experts: two halves
        (2048, 512, 2, (512, 1)),   # Qwen3-Next's: a whole expert a block
        (128, 256, 4, (256, 1)),
        (32, 16, 4, None),        # the tiny presets: off the lanes
        (3072, 1000, 2, None),
        (65536, 128, 2, None),    # on the lanes, and one block is too much
    ])
    def test_the_blocks_come_from_the_shape(self, d, f, itemsize, blocks):
        got = moe_kernel.ring(d, f, itemsize)
        assert got == (blocks and moe_kernel.Ring(2, *blocks))
        assert moe_kernel.f_tile(d, f, itemsize) == (blocks and got.wide)

    @pytest.mark.parametrize("d,f,blocks", [
        (3072, 1024, (512, 2)),     # Laguna-S-2.1
        (2048, 512, (512, 1)),      # Qwen3-Next
        (3584, 1024, (512, 2)),     # Xing4.0
        (2048, 1536, (768, 2)),     # LFM2
        (2304, 896, (896, 1)),      # Mellum2
        (2048, 768, (768, 1)),      # kanana-2
        (7168, 2048, (128, 16)),    # GigaChat3.5: long rows
        (6144, 2048, (128, 16)),    # LongCat-Flash: long rows
        (4096, 768, (384, 2)),      # granite-4.0-h-small
    ])
    def test_the_ring_fits_the_vmem_the_call_had(self, d, f, blocks):
        """The nine published shapes in bf16: an expert's blocks cover
        ``f`` on the lanes, the widest that fit (one lane width over 4 096
        rows), and the ring's two slots stay under the 24 MiB the two
        buffers a weight had, 28 with the slack: what XLA keeps for the
        Linears' prefetched slices is untouched."""
        got = moe_kernel.ring(d, f, 2)
        assert got == moe_kernel.Ring(2, *blocks)
        assert got.wide * got.wides == f and not got.wide % 128
        assert got.vmem_bytes(d, 2) <= moe_kernel._WEIGHT_VMEM == 24 * 2 ** 20
        assert (moe_kernel._WEIGHT_VMEM + moe_kernel._VMEM_SLACK
                == 28 * 2 ** 20)
        assert moe_kernel._COST_EXPERTS == 3
        wider = next((w for w in range(got.wide + 128, f + 1, 128)
                      if f % w == 0), None)
        assert d > 4096 or wider is None or 2 * 3 * d * wider * 2 > 24 * 2 ** 20

    def test_a_width_that_does_not_tile_is_refused(self):
        x, routing, wg, wu, wd = self._layer(jnp.float32)
        with pytest.raises(ValueError, match="do not tile"):
            moe_kernel.chosen_experts(
                x[:, :96], routing.experts[0], routing.weights[0],
                jnp.int32(4), wg[:, :96], wu[:, :96], wd[:, :, :96])

    @pytest.mark.parametrize("platform,tokens,dtype,d,f,meshed,path", [
        ("tpu", 1, jnp.bfloat16, 3072, 1024, False, moe.KERNEL),
        ("tpu", 1, jnp.bfloat16, 2048, 512, False, moe.KERNEL),
        ("cpu", 1, jnp.bfloat16, 3072, 1024, False, moe.LOOP),
        ("gpu", 1, jnp.bfloat16, 3072, 1024, False, moe.LOOP),
        ("tpu", 1, jnp.float32, 3072, 1024, False, moe.LOOP),
        ("tpu", 1, jnp.bfloat16, 32, 16, False, moe.LOOP),
        ("tpu", 1, jnp.bfloat16, 3072, 1000, False, moe.LOOP),
        ("tpu", 1, jnp.bfloat16, 3000, 1024, False, moe.LOOP),
        ("tpu", 1, jnp.bfloat16, 3072, 1024, True, moe.LOOP),
        ("tpu", 64, jnp.bfloat16, 3072, 1024, False, moe.GROUPED),
        ("cpu", 2, jnp.float32, 32, 16, True, moe.GROUPED),
        # a decode step of 2, 4 or 8 sequences: no more than one row tile
        ("tpu", 2, jnp.bfloat16, 2304, 896, False, moe.KERNEL),
        ("tpu", 4, jnp.bfloat16, 2304, 896, False, moe.KERNEL),
        ("tpu", 8, jnp.bfloat16, 2304, 896, False, moe.KERNEL),
        ("tpu", 9, jnp.bfloat16, 2304, 896, False, moe.GROUPED),
        ("tpu", 64, jnp.bfloat16, 2304, 896, False, moe.GROUPED),
        ("tpu", 4, jnp.bfloat16, 2304, 896, True, moe.GROUPED),
        ("cpu", 4, jnp.bfloat16, 2304, 896, False, moe.GROUPED),
        ("tpu", 4, jnp.float32, 2304, 896, False, moe.GROUPED),
        ("tpu", 4, jnp.bfloat16, 32, 16, False, moe.GROUPED),
    ])
    def test_the_choice_is_made_from_what_the_call_shows(
            self, platform, tokens, dtype, d, f, meshed, path):
        assert moe.choose(platform, tokens, dtype, d, f,
                          meshed=meshed) == path

    @pytest.mark.parametrize("platform,meshed,tokens,path", [
        ("cpu", False, 1, "loop"), ("cpu", False, 6, "grouped"),
        ("tpu", False, 1, "kernel"), ("tpu", True, 1, "loop"),
        ("tpu", False, 6, "kernel"), ("tpu", True, 6, "grouped"),
        ("tpu", False, 9, "grouped")])
    def test_an_expert_layer_counts_the_product_it_was_traced_with(
            self, monkeypatch, platform, meshed, tokens, path):
        """``serving.expander`` ``expert_products``: one count a layer a
        trace (nothing compiles here: a described platform only steers the
        chooser)."""
        cfg = dataclasses.replace(
            configs.TINY_LM, hidden_size=self.D,
            moe_intermediate_size=self.F)
        n = jnp.zeros((tokens, self.D), jnp.bfloat16)
        layer = lm.MoE(cfg, jnp.bfloat16, meshed=meshed)
        valid = jnp.ones(tokens, bool)
        params = jax.eval_shape(
            lambda: layer.init(jax.random.key(0), n, valid))["params"]
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        EXPANDER.clear()
        out, _ = jax.eval_shape(
            lambda p: layer.apply({"params": p}, n, valid), params)
        assert out.shape == (tokens, self.D)
        want = {"kernel": 0, "loop": 0, "grouped": 0, path: 1}
        assert EXPANDER.summary()["expert_products"] == want

    def test_the_tiny_expanders_decode_chunk_counts_its_layers(self):
        """Three expert layers traced once inside the scan's body, on the
        loop: a CPU, float32, widths off the lanes."""
        module = lm.DecoderLM(CFG)
        params = contract.param_shapes(CFG)
        cache = lm.empty_cache(CFG, 16, jnp.float32)
        EXPANDER.clear()
        jax.eval_shape(lm.decode_chunk_fn(module, 4), params, cache,
                       jnp.int32(1), jnp.int32(3), jax.random.key(0),
                       jnp.float32(0.0))
        assert EXPANDER.summary()["expert_products"] == {
            "kernel": 0, "loop": 3, "grouped": 0}
        jax.eval_shape(lm.prefill_fn(module), params, cache,
                       jnp.zeros((8,), jnp.int32), jnp.int32(0),
                       jnp.int32(5), jax.random.key(0), jnp.float32(0.0))
        assert EXPANDER.summary()["expert_products"] == {
            "kernel": 0, "loop": 3, "grouped": 3}
        assert moe.row_tile(100000, 10, 256) == 256


@pytest.mark.usefixtures("programs_released")
class TestTheBlockOfRowsKernel:
    """ops/moe_kernel.py at 2-8 rows in interpret mode: a step's distinct
    held experts, each taking the whole block of rows under a per-row
    weight (``moe._block``), against the grouped product and a plain sum a
    row. The share holds experts 4-11 of 16, so some picks are absent."""

    D, F, EXPERTS, FIRST, HELD = 128, 256, 16, 4, 8

    def _kernels(self, dtype=jnp.float32, seed=5):
        ks = jax.random.split(jax.random.key(seed), 3)
        d, f, e = self.D, self.F, self.EXPERTS
        return ((jax.random.normal(ks[0], (e, d, f)) / d ** 0.5).astype(dtype),
                (jax.random.normal(ks[1], (e, d, f)) / d ** 0.5).astype(dtype),
                (jax.random.normal(ks[2], (e, f, d)) / f ** 0.5).astype(dtype))

    def _routing(self, rows, case):
        """(rows, k) picks, distinct within a row."""
        held = list(range(self.FIRST, self.FIRST + self.HELD))
        absent = [e for e in range(self.EXPERTS) if e not in held]
        if case == "one_expert":
            picks = [[9]] * rows
        elif case == "all_distinct":       # 2 rows: 4 picks ... 8 rows: 16
            picks = [[2 * r, 2 * r + 1] for r in range(rows)]
        elif case == "one_row_none_held":
            picks = [[held[(3 * r) % 8], held[(3 * r + 1) % 8]]
                     for r in range(rows)]
            picks[rows // 2] = absent[:2]
        else:                               # "none_held"
            picks = [[absent[r % 8], absent[(r + 3) % 8]]
                     for r in range(rows)]
        experts = jnp.array(picks, jnp.int32)
        weights = 0.2 + jax.random.uniform(jax.random.key(rows),
                                           experts.shape)
        return moe.Routing(experts, weights)

    def _share(self, kernels):
        return [w[self.FIRST:self.FIRST + self.HELD] for w in kernels]

    def _a_row_at_a_time(self, x, routing, share):
        out = np.zeros(x.shape, np.float64)
        for r in range(x.shape[0]):
            for e, w in zip(np.asarray(routing.experts[r]) - self.FIRST,
                            np.asarray(routing.weights[r])):
                if 0 <= e < self.HELD:
                    out[r] += w * np.asarray(moe._swiglu(
                        x[r:r + 1], *(k[e] for k in share)))[0]
        return out

    @pytest.mark.parametrize("blocks", ["one_block", "three_blocks"])
    @pytest.mark.parametrize("case", ["one_expert", "all_distinct",
                                      "one_row_none_held", "none_held"])
    @pytest.mark.parametrize("rows", [2, 4, 8])
    def test_the_block_equals_the_grouped_product_and_a_sum_a_row(
            self, monkeypatch, rows, case, blocks):
        """One expert a call and many (two rows' four picks to eight rows'
        eight held of sixteen), a whole expert a block and three blocks
        with a narrower last one."""
        self.F = _ring(monkeypatch, blocks)
        share = self._share(self._kernels())
        x = jax.random.normal(jax.random.key(rows + 10), (rows, self.D))
        routing = self._routing(rows, case)
        got = moe._block(x, routing, *share, self.FIRST)
        assert got.shape == (rows, self.D) and got.dtype == jnp.float32
        grouped = moe._grouped(x, routing, *share, self.FIRST, self.EXPERTS)
        np.testing.assert_allclose(got, grouped, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            got, self._a_row_at_a_time(x, routing, share), rtol=1e-4,
            atol=1e-5)
        _, held = moe.held_mask(routing.experts, self.FIRST, self.HELD)
        for r in range(rows):           # no held pick: exactly nothing
            assert bool(np.any(np.asarray(got[r]))) == bool(held[r].any())

    @pytest.mark.parametrize("precision", [None, "highest"])
    def test_bf16_operands_accumulate_in_float32(self, precision):
        share = self._share(self._kernels(jnp.bfloat16))
        x = jax.random.normal(jax.random.key(3), (4, self.D)).astype(
            jnp.bfloat16)
        routing = self._routing(4, "one_row_none_held")
        want = moe._grouped(x, routing, *share, self.FIRST, self.EXPERTS)
        with jax.default_matmul_precision(precision or "default"):
            got = moe._block(x, routing, *share, self.FIRST)
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("blocks", ["one_block", "three_blocks"])
    def test_a_row_gets_nothing_from_an_expert_it_did_not_choose(
            self, monkeypatch, blocks):
        """Expert 9's product overflows for every row of the block, in
        every block of its width; only the rows that chose it may see
        that: selected out, not ``0 * inf``."""
        self.F = _ring(monkeypatch, blocks)
        wg, wu, wd = self._kernels()
        wd = wd.at[9].set(jnp.inf)
        share = self._share((wg, wu, wd))
        x = jax.random.normal(jax.random.key(4), (4, self.D))
        routing = moe.Routing(
            jnp.array([[9, 5], [5, 6], [6, 9], [7, 1]], jnp.int32),
            jnp.full((4, 2), 0.5, jnp.float32))
        got = np.asarray(moe._block(x, routing, *share, self.FIRST))
        assert not np.isfinite(got[0]).any() and not np.isfinite(got[2]).any()
        assert np.isfinite(got[1]).all() and np.isfinite(got[3]).all()
        clean = moe._block(x, routing, *self._share((wg, wu, wd.at[9].set(
            0.0))), self.FIRST)
        np.testing.assert_array_equal(got[[1, 3]], np.asarray(clean)[[1, 3]])

    @pytest.mark.parametrize("rows,k,held,slots", [
        (2, 8, 64, 16), (4, 8, 64, 32), (8, 8, 64, 64), (8, 10, 64, 64),
        (4, 4, 8, 8)])
    def test_the_slots_are_the_most_distinct_experts_a_step_can_choose(
            self, rows, k, held, slots):
        """``min(rows * k, held)`` slots the ring may walk in its one grid
        step, the block padded to the bf16 sublane tile, and the one-row
        call's cost estimate (three reads: ``moe_kernel._call`` says why
        not the reads the shapes let expect)."""
        d, f = 128, 256
        s = jax.ShapeDtypeStruct
        wide = s((held, d, f), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(
            lambda *a: moe._block(a[0], moe.Routing(a[1], a[2]), *a[3:], 0))(
            s((rows, d), jnp.bfloat16), s((rows, k), jnp.int32),
            s((rows, k), jnp.float32), wide, wide,
            s((held, f, d), jnp.bfloat16))
        (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "jit"
                   and e.params["name"] == "_call"]
        ids, n, weights, x = (v.aval.shape for v in call.invars[:4])
        assert (ids, n, weights, x) == ((slots,), (1,), (slots * rows,),
                                        (moe_kernel.ROW_BLOCK, d))
        (kernel,) = [e for e in call.params["jaxpr"].eqns
                     if e.primitive.name == "pallas_call"]
        assert kernel.params["grid_mapping"].grid == (1,)
        cost = kernel.params["cost_estimate"]
        assert (cost.flops, cost.transcendentals, cost.bytes_accessed) == (
            18 * d * f, 3 * f, 18 * d * f)

    def test_one_row_keeps_the_kernel_it_had(self):
        """The body is one for every row count, and one row's trace must
        stay what it was: a scalar weight a slot, three reads claimed, no
        pad, and no select on a weight column; the experts' kernels stay
        in HBM and a ring of two slots of VMEM takes their blocks."""
        s = jax.ShapeDtypeStruct
        d, f, k = 128, 256, 4
        wide = s((16, d, f), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(lambda *a: moe_kernel.chosen_experts(*a))(
            s((1, d), jnp.bfloat16), s((k,), jnp.int32),
            s((k,), jnp.float32), s((), jnp.int32), wide, wide,
            s((16, f, d), jnp.bfloat16))
        (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "jit"]
        assert [v.aval.shape for v in call.invars[:4]] == [
            (k,), (1,), (k,), (1, d)]
        assert [e.primitive.name for e in jaxpr.jaxpr.eqns] == [
            "reshape", "jit"]               # no pad, no slice of the result
        (kernel,) = [e for e in call.params["jaxpr"].eqns
                     if e.primitive.name == "pallas_call"]
        mapping = kernel.params["grid_mapping"]
        assert mapping.grid == (1,)
        assert [str(b.block_aval.memory_space) for b in
                mapping.block_mappings[1:4]] == ["any"] * 3
        assert [tuple(a.shape) for a in mapping.scratch_avals] == [
            (2, d, f), (2, d, f), (2, f, d), (3, 2)]
        cost = kernel.params["cost_estimate"]
        assert (cost.flops, cost.transcendentals, cost.bytes_accessed) == (
            18 * d * f, 3 * f, 18 * d * f)

        def names(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn.primitive.name
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from names(sub)

        body = list(names(kernel.params["jaxpr"]))
        assert "select_n" not in body and "iota" not in body
        assert body.count("dot_general") == 3 and body.count("swap") == 2
        # the first read before the loop, the one that keeps the ring
        # full and the wait inside it: three copies each
        assert body.count("dma_start") == 6 and body.count("dma_wait") == 3
        assert body.count("while") == 1


@pytest.mark.usefixtures("programs_released")
class TestCallsThatReadNothing:
    """``serving.expander`` ``expert_calls`` / ``expert_calls_unread``:
    the expert kernel's calls of the decode steps, and those whose rows
    chose no held expert (ops/moe_kernel.py reads nothing for them)."""

    #: the tiny expander at widths the kernel tiles, holding experts 12-15
    #: of 16: four picks a row often miss all four
    CFG = dataclasses.replace(
        configs.TINY_EXPAND.expander, hidden_size=128,
        moe_intermediate_size=128, experts_held=(12, 4))
    STEPS = 6

    def _decode(self, steps, cache, tokens, position):
        fn = lm.decode_sequences_fn(lm.DecoderLM(self.CFG), steps)
        return fn(contract.lm_params(self.CFG), cache, tokens, position,
                  contract.keys([0, 1]), jnp.float32(1.0), jnp.int32(2))

    def test_a_step_of_several_sequences_counts_them_on_the_device(
            self, monkeypatch):
        """The executable returns, behind the experts read, the steps in
        which a layer's rows streamed none: what a step at a time counts
        from each step's own experts read, and the same through the chain
        and through the kernels (held to them here: both in the
        interpreter), whose call then started no copy."""
        layers = len(self.CFG.expert_layers)
        cache = kv.fork(lm.empty_cache(self.CFG, 32, jnp.float32), 2, 16)
        first = jnp.array([3, 4], jnp.int32)
        plain = self._decode(self.STEPS, cache, first, jnp.int32(0))
        monkeypatch.setattr(moe, "choose", lambda *a, **kw: moe.KERNEL)
        got = self._decode(self.STEPS, cache, first, jnp.int32(0))
        assert len(got) == len(plain) == 8
        for ours, theirs in zip(got[3:], plain[3:]):    # tokens, counts
            np.testing.assert_array_equal(ours, theirs)
        unread = np.asarray(got[7])
        assert unread.shape == (layers,) and unread.dtype == np.int32
        want, tokens, position = np.zeros(layers, int), first, jnp.int32(0)
        for _ in range(self.STEPS):
            cache, tokens, position, _, _, _, read, one = self._decode(
                1, cache, tokens, position)
            np.testing.assert_array_equal(np.asarray(one),
                                          np.asarray(read) == 0)
            want += np.asarray(one)
        np.testing.assert_array_equal(unread, want)
        assert 0 < unread.sum() < layers * self.STEPS

    def test_the_counters_and_the_layer_metric(self):
        """benchmarks/layer_metrics/expert_calls_unread_share.json through
        the harness's own loader and reader, its entry in BENCHMARK.json,
        and the Prometheus twins."""
        from benchmarks.harness import files
        from stable_diffusion_webui_distributed_tpu.obs import prometheus

        bench = files.Bench(contract.ROOT)
        spec = bench.layer_metric("expert_calls_unread_share")
        reader = bench.load("readers", spec["reader"])
        assert (spec["reader"], spec["args"]["scale"]) == ("status_ratio",
                                                           100)

        def one_request(**calls):
            EXPANDER.record(
                prefilled=0, from_prefix=0, sequences=1, decoded=384,
                decode_steps=384, experts_read=9000, load=[[1, 2]],
                none_held=2115, positions={}, state_bytes={},
                prefix_snapshots=0, padded_rows_masked=0,
                residual_streams=1, sinkhorn_iters=0, **calls)
            return {"serving": METRICS.summary()}

        EXPANDER.clear()
        before = {"serving": METRICS.summary()}
        assert (before["serving"]["expander"]["expert_calls"],
                before["serving"]["expander"]["expert_calls_unread"]) == (
                    0, 0)
        after = one_request(expert_calls=6912, expert_calls_unread=2115)
        status = {"status_before": before, "status_after": after}
        assert reader.read(status, **spec["args"]) == pytest.approx(
            100 * 2115 / 6912)
        text = prometheus.render()
        assert "sdtpu_expander_expert_calls_total 6912" in text
        assert "sdtpu_expander_expert_calls_unread_total 2115" in text
        # every expert held: the calls read, and the share is 0, not nothing
        held = one_request(expert_calls=3072)
        status = {"status_before": after, "status_after": held}
        assert reader.read(status, **spec["args"]) == 0.0
        # no decode step in the window, and the parent's block, which has
        # no such counters: nothing to read
        status = {"status_before": held, "status_after": one_request()}
        assert reader.read(status, **spec["args"]) is None
        for block in status.values():
            del block["serving"]["expander"]["expert_calls"]
        assert reader.read(status, **spec["args"]) is None
        EXPANDER.clear()
        entry = next(m for m in bench.manifest["per_layer"]
                     if m["name"] == "expert_calls_unread_share")
        assert {key: entry[key] for key in entry if key != "workloads"} == {
            key: spec[key] for key in (
                "name", "unit", "better", "source", "layer", "moves")}
        assert (entry["layer"], entry["moves"], entry["unit"]) == (
            "kernels", "request_p50_s", "%")
        kernel_cells = next(m for m in bench.manifest["per_layer"]
                            if m["name"] == "expert_kernel_sites")
        assert entry["workloads"] == kernel_cells["workloads"]
        # the nine cells whose expander has expert layers on the kernel,
        # and since PR 72 the two-client cell of the first of them
        assert len(entry["workloads"]) == 10
        assert entry["workloads"][-1] == "sd15_expand_pair"


class TestTheShareOfALayer:
    @pytest.mark.parametrize("shared", [True, False])
    def test_two_halves_and_the_shared_expert_once_equal_the_uncut_layer(
            self, shared):
        """The reference's uncut expert layer against the sum of the two
        chips' layers: each chip's routed part, the shared expert counted
        once (a layer with none has no such weights and adds nothing for
        one), the residual once."""
        whole = dataclasses.replace(
            CFG, experts_held=None, vocab_held=None,
            **({} if shared else {"shared_expert_intermediate_size": 0}))
        p = contract.lm_params(whole, 4)["layers_1"]
        assert ("shared_expert" in p["mlp"]) == shared
        x = jax.random.normal(jax.random.key(9), (20, whole.hidden_size))
        h = x + REF._attention(whole, 1, REF._rms(
            x, p["input_norm"]["scale"], whole.rms_norm_eps), p["attn"])
        n = REF._rms(h, p["post_attention_norm"]["scale"],
                     whole.rms_norm_eps)
        once = REF._swiglu(n, p["mlp"]["shared_expert"]) if shared else 0.0
        if shared:
            want, _ = REF.layer_forward(whole, 1, x, p)
        else:   # the reference's routed part over every expert, alone
            want = h + REF.routed_part(
                n, *REF.route(whole, n, p["mlp"]), p["mlp"]["experts"], 0)
        total = h + once
        for rank in (0, 1):
            share = configs.lm_share(whole, whole.num_layers, 2, rank)
            lo, count = share.experts
            mlp = dict(p["mlp"], experts={
                k: w[lo:lo + count] for k, w in p["mlp"]["experts"].items()})
            out, _ = lm.MoE(share, jnp.float32).apply(
                {"params": mlp}, n, jnp.ones(20, bool))
            total = total + out - once
        np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)

    def test_lm_share_cuts_layers_experts_and_vocabulary(self):
        share = configs.sd15_laguna_expander().expander
        assert share.num_layers == 5
        assert share.layer_types == ("full", "sliding", "sliding",
                                     "sliding", "full")
        assert share.num_heads_per_layer == (48, 72, 72, 72, 48)
        assert share.experts == (0, 128) and share.vocab == (0, 50176)
        assert share.num_experts == 256 and share.num_experts_per_tok == 10
        other = configs.lm_share(configs.LAGUNA_S_2_1, 5, 2, 1)
        assert other.experts == (128, 128) and other.vocab == (50176, 50176)


class TestSampling:
    def test_a_draw_is_keyed_by_seed_and_position(self):
        logits = jax.random.normal(jax.random.key(0), (256,))
        key = jax.random.key(7)
        a = lm.sample(logits, key, 5, jnp.float32(1.0), 256)
        assert int(a) == int(lm.sample(logits, key, 5, jnp.float32(1.0),
                                       256))
        draws = {int(lm.sample(logits, key, p, jnp.float32(1.0), 256))
                 for p in range(40)}
        assert len(draws) > 10 and min(draws) >= 256
        assert int(lm.sample(logits, key, 5, jnp.float32(0.0))) \
            == int(jnp.argmax(logits))

    def test_chunked_decode_equals_one_step_at_a_time(self, params):
        made = [contract.decoded(CFG, params, steps, calls, 64)[0]
                for steps, calls in ((12, 1), (4, 3), (1, 12))]
        assert made[0] == made[1] == made[2]


COPY_TREE = jax.jit(kv.copy_tree)
ZEROED_IN_PLACE = jax.jit(lambda tree: jax.tree_util.tree_map(
    lambda x: x * 0, tree), donate_argnums=(0,))


class TestTokenizerAndCache:
    def test_the_fallback_hashes_into_the_held_slice_and_back(self):
        tok = load_lm_tokenizer(None, 512, 256)
        assert isinstance(tok, FallbackLMTokenizer)
        ids = tok.encode("a herd of cows, grazing")
        assert ids == tok.encode("a herd of cows, grazing")
        assert all(514 <= i < 768 for i in ids)
        assert tok.decode([tok.bos] + ids + [tok.eos]) \
            == " ".join(f"w{i}" for i in ids)

    def test_vocabulary_files_are_used_when_a_directory_has_them(
            self, tmp_path):
        vocab = {}
        for ch in "abcdefghijklmnopqrstuvwxyz":
            vocab[ch] = len(vocab)
            vocab[ch + "</w>"] = len(vocab)
        for piece in ("co", "cow</w>", "re", "red</w>"):
            vocab[piece] = len(vocab)
        vocab["<|startoftext|>"] = len(vocab)
        vocab["<|endoftext|>"] = len(vocab)
        merges = [("c", "o"), ("co", "w</w>"), ("r", "e"), ("re", "d</w>")]
        (tmp_path / "vocab.json").write_text(json.dumps(vocab))
        (tmp_path / "merges.txt").write_text(
            "\n".join(f"{a} {b}" for a, b in merges))
        tok = load_lm_tokenizer(str(tmp_path), 0, len(vocab))
        ids = tok.encode("red cow")
        assert ids == [vocab["red</w>"], vocab["cow</w>"]]
        assert tok.decode([tok.bos] + ids + [tok.eos]) == "red cow"
        assert isinstance(load_lm_tokenizer(str(tmp_path / "none"), 0, 64),
                          FallbackLMTokenizer)

    def test_buckets(self):
        assert [kv.chunk_bucket(n) for n in (1, 16, 64, 65, 512)] \
            == [64, 64, 64, 128, 512]
        assert kv.capacity_for(960) == 1024 and kv.capacity_for(256) == 256

    @pytest.mark.parametrize("own_copier", [False, True])
    def test_a_kept_prefix_is_handed_out_as_a_copy(self, own_copier):
        """Each copy is ONE call of one executable (the manager's own, or
        the one its maker hands it by capacity, as the expander does
        through the engine's cache of stages), and what is held survives
        the copy's donation."""
        calls = []
        copy = COPY_TREE

        def copier(capacity):
            def counted(tree):
                calls.append(capacity)
                return copy(tree)
            return counted

        manager = kv.KVCacheManager(CFG, jnp.float32,
                                    copier=copier if own_copier else None)
        cache, held = manager.acquire([1, 2, 3], 256)
        assert held == 0 and [k.shape for k in cache["k"]] == [
            (256, 2, 16), (8, 2, 16), (8, 2, 16), (256, 2, 16)]
        manager.keep_prefix([1, 2, 3], 256,
                            jax.tree_util.tree_map(lambda x: x + 1, cache))
        again, held = manager.acquire([1, 2, 3], 256)
        assert held == 3 and float(again["v"][1][0, 0, 0]) == 1.0
        if own_copier:      # the snapshot's, then the hand-out's
            assert calls == [256, 256]
        # the copy goes the way of every cache, into a donating executable
        # (where the backend does not take a donation, by hand)
        spent = ZEROED_IN_PLACE(again)
        for leaf in jax.tree_util.tree_leaves(again):
            if not leaf.is_deleted():
                leaf.delete()
        third, held = manager.acquire([1, 2, 3], 256)
        assert held == 3
        assert all(float(jnp.min(x)) == float(jnp.max(x)) == 1.0
                   for x in jax.tree_util.tree_leaves(third))
        assert float(jnp.max(spent["k"][0])) == 0.0
        assert manager.acquire([1, 2], 256)[1] == 0
        assert (manager.prefix_hits, manager.prefix_misses) == (2, 2)
        assert manager.positions_in_use(40) == {"full": 80, "sliding": 16}


class TestEnginePath:
    def test_the_script_is_parsed_from_alwayson_scripts(self):
        args = prompt_expansion_args(CASE.payload())
        assert args.max_new_tokens == 40 and args.context_chunks == 1
        assert prompt_expansion_args(CASE.payload(alwayson_scripts={})) is None
        off = CASE.payload(alwayson_scripts=CASE.script(max_new_tokens=0))
        assert prompt_expansion_args(off) is None
        assert prompt_expansion_args(CASE.payload(alwayson_scripts={
            "Prompt Expansion": {"args": []}})).max_new_tokens == 64

    def test_expanded_txt2img_repeats_and_differs_from_plain(self, engine):
        EXPANDER.clear()
        a = engine.txt2img(CASE.payload())
        b = engine.txt2img(CASE.payload())
        plain = engine.txt2img(CASE.payload(alwayson_scripts={}))
        assert a.images == b.images and a.prompts == b.prompts
        assert a.images != plain.images
        assert plain.prompts == ["a cow in a valley"]
        words = a.prompts[0].split()
        assert len(words) == 45 and words[:5] == "a cow in a valley".split()
        assert all(w.startswith("w") for w in words[5:])
        assert a.prompts[0] in a.infotexts[0]
        stats = EXPANDER.summary()
        # the second request found the instruction's cache
        assert stats["requests"] == 2
        assert stats["tokens_prefilled"] == 31 + 5 + 5
        assert stats["tokens_from_prefix_cache"] == 31
        assert stats["tokens_decoded"] == 80
        assert stats["cache_positions"] == {"full": 2 * 76, "sliding": 16}
        routed = sum(map(sum, stats["expert_tokens"]))
        assert len(stats["expert_tokens"]) == 3
        assert 0 < routed <= 3 * 4 * (31 + 5 + 64 + 5 + 64)
        assert stats["expert_load_max_over_mean"] >= 1.0

    def test_one_sequence_decodes_with_the_plain_functions_lowered_text(
            self, engine):
        """A solo preset with expert layers: what one image runs is
        ``lm.decode_chunk_fn`` under its old key, lowered text and all;
        nothing of the several-sequence path is in its way."""
        EXPANDER.clear()
        engine.txt2img(CASE.payload())
        (key,) = [k for k in engine.executable_keys()
                  if k[0] == "expand_decode_chunk"]
        _, steps, capacity = key                # no sequence count
        assert EXPANDER.summary()["sequences"] == 1
        cache = lm.empty_cache(CFG, capacity, jnp.float32)
        args = (engine.params["expander"], cache, jnp.int32(0),
                jnp.int32(36), jax.random.key(0), jnp.float32(1.0))
        served = engine.expander._decode_fn(capacity).lower(*args).as_text()
        EXPANDER.clear()
        plain = jax.jit(lm.decode_chunk_fn(engine.expander.module, steps),
                        donate_argnums=(1,)).lower(*args).as_text()
        assert served == plain
        assert EXPANDER.summary()["expert_products"] == {
            "kernel": 0, "loop": 3, "grouped": 0}

    def test_another_seed_gets_another_expansion(self, engine):
        a = engine.txt2img(CASE.payload())
        b = engine.txt2img(CASE.payload(seed=99))
        assert a.prompts != b.prompts

    def test_a_batch_expands_each_image_by_its_own_seed(self, engine):
        both = engine.txt2img(CASE.payload(batch_size=2))
        second = engine.generate_range(CASE.payload(batch_size=2), 1, 1)
        assert both.prompts[0] != both.prompts[1]
        assert second.prompts == both.prompts[1:]
        assert second.images == both.images[1:]
        solo = engine.txt2img(CASE.payload(seed=1235))
        assert solo.prompts[0] == both.prompts[1]

    def test_context_chunks_keeps_the_tail(self, engine):
        long = engine.txt2img(CASE.payload(alwayson_scripts=CASE.script(
            max_new_tokens=100)))
        words = long.prompts[0].split()
        assert len(words) == 75 and "cow" not in words

    def test_eos_ends_the_expansion(self, engine, monkeypatch):
        full = engine.txt2img(CASE.payload()).prompts[0].split()[5:]
        eos = int(full[9][1:])
        monkeypatch.setattr(engine.expander.tokenizer, "eos", eos)
        cut = engine.txt2img(CASE.payload(alwayson_scripts=CASE.script(
            ignore_eos=False))).prompts[0].split()[5:]
        assert cut == full[:full.index(f"w{eos}")]

    def test_the_stage_builds_its_executables_through_the_engines_cache(
            self, engine):
        engine.txt2img(CASE.payload())
        kinds = {k[0] for k in engine.executable_keys()}
        assert {"expand_prefill", "expand_decode_chunk"} <= kinds
        before = dict(METRICS.summary()["compiles"])
        engine.txt2img(CASE.payload(prompt="another prompt of five"))
        assert METRICS.summary()["compiles"] == before

    def test_spans(self, engine):
        from stable_diffusion_webui_distributed_tpu.obs import spans

        engine.txt2img(CASE.payload())       # the instruction's snapshot is kept
        spans.TRACER.clear()
        with spans.request("rid-expand"):
            engine.txt2img(CASE.payload())
        events = [e for e in spans.TRACER.export_chrome()["traceEvents"]
                  if e.get("ph") == "X"]
        names = [e["name"] for e in events]
        for name in ("expand", "expand.tokenize", "expand.prefill",
                     "expand.decode_chunk", "expand.fence_wait",
                     "expand.detokenize", "expand.ahead", "expand.account",
                     "prepare"):
            assert name in names, name
        by_id = {e["args"]["span_id"]: e for e in events}

        def parent(e):
            return by_id[e["args"]["parent_id"]]["name"]

        for e in events:
            # the counters come down once the UNet is queued
            if e["name"].startswith("expand."):
                assert parent(e) == ("denoise_range" if e["name"]
                                     == "expand.account" else "expand")
        prefill = next(e for e in events if e["name"] == "expand.prefill")
        assert prefill["args"]["tokens"] == 5
        assert prefill["args"]["prefix_hit"] is True
        # what reads nothing of the text is drawn under the first decode
        # chunk: once, between its enqueue and the wait for its tokens
        first = {}
        for e in sorted(events, key=lambda e: e["ts"]):
            first.setdefault(e["name"], e)
        for name in ("request.plan", "noise", "batch.assemble",
                     "denoise.inputs", "denoise.plan"):
            assert parent(first[name]) == "expand.ahead", name
        assert [names.count(n) for n in ("expand.ahead", "request.plan",
                                         "noise", "batch.assemble")] \
            == [1, 1, 1, 1]
        ahead = first["expand.ahead"]
        assert first["expand.decode_chunk"]["ts"] < ahead["ts"] \
            < first["expand.fence_wait"]["ts"]
        # the rest of the two spans runs where it ran, with the text
        assert [parent(e) for e in events
                if e["name"] == "denoise.inputs"] \
            == ["expand.ahead", "denoise_range"]
        account, = [e for e in events if e["name"] == "expand.account"]
        assert first["chunk.enqueue"]["ts"] < account["ts"]
        assert account["ts"] < first["chunk.fence_wait"]["ts"]

    @pytest.mark.parametrize("batch, want", [
        (1, {"expand_prefill": "expand.prefill",
             "expand_decode_chunk": "expand.decode_chunk"}),
        (2, {"expand_prefill": "expand.prefill",
             "expand_fork": "expand.fork",
             "expand_decode_chunk": "expand.decode_chunk"})])
    def test_the_device_s_side_of_every_enqueue(self, engine, batch, want):
        """A ``device.run`` an executable under the span that enqueued it
        (ISSUE 71): the prefill's and the fork's fenced inside their own
        span, a decode chunk's by the fetch of its tokens."""
        from stable_diffusion_webui_distributed_tpu.obs import spans

        if batch > 1 and not engine.expander.shares_a_step:
            pytest.skip("one image after the other: no fork")
        engine.txt2img(CASE.payload(batch_size=batch))
        spans.TRACER.clear()
        with spans.request("rid-device") as req:
            engine.txt2img(CASE.payload(batch_size=batch))
        by_id = {sp.span_id: sp for sp in req.spans}
        runs = [sp for sp in req.spans if sp.name == "device.run"]
        found = {}
        for run in runs:
            found.setdefault(run.attrs["kind"], set()).add(
                by_id[run.parent_id].name)
        assert {k: found.get(k) for k in want} \
            == {k: {v} for k, v in want.items()}
        # one a span, and the chunk's tokens ride along
        chunks = [sp for sp in req.spans
                  if sp.name == "expand.decode_chunk"]
        mine = [r for r in runs if r.attrs["kind"] == "expand_decode_chunk"]
        assert sorted(r.parent_id for r in mine) \
            == sorted(sp.span_id for sp in chunks)
        assert {r.attrs["tokens"] for r in mine} == {32}
        fences = [sp for sp in req.spans if sp.name == "expand.fence_wait"]
        assert len(fences) == len(chunks)
        assert all("late" in sp.attrs for sp in fences)
        ordered = sorted(runs, key=lambda sp: sp.t0)
        for a, b in zip(ordered, ordered[1:]):
            assert a.t0 + a.dur <= b.t0 + 1e-6

    def test_a_family_without_an_expander_is_untouched(self):
        from stable_diffusion_webui_distributed_tpu.obs import spans

        plain = Engine(configs.TINY, dict(tiny_params()), chunk_size=4,
                       state=GenerationState())
        assert plain.expander is None
        before = PLAN.summary()["ahead"]
        without = plain.txt2img(CASE.payload(alwayson_scripts={}))
        spans.TRACER.clear()
        with spans.request("rid-plain"):
            with_script = plain.txt2img(CASE.payload())
        assert with_script.images == without.images
        assert with_script.prompts == ["a cow in a valley"]
        kinds = {k[0] for k in plain.executable_keys()}
        assert not any(k.startswith("expand") for k in kinds)
        # and its request is the tree it was before any request drew ahead
        assert PLAN.summary()["ahead"] == before
        events = sorted((e for e in spans.TRACER.export_chrome()[
            "traceEvents"] if e.get("ph") == "X"), key=lambda e: e["ts"])
        by_id = {e["args"]["span_id"]: e["name"] for e in events}
        tree = [(e["name"], by_id.get(e["args"].get("parent_id")))
                for e in events]
        assert tree == [
            ("request", None), ("generate_range", "request"),
            ("request.plan", "generate_range"),
            ("prepare", "generate_range"), ("tokenize", "prepare"),
            ("text_encode", "prepare"), ("prepare", "generate_range"),
            ("noise", "prepare"), ("batch.assemble", "prepare"),
            ("denoise_range", "generate_range"),
            ("denoise.inputs", "denoise_range"),
            ("denoise.plan", "denoise_range"),
            ("denoise_chunk", "denoise_range"),
            ("chunk.enqueue", "denoise_chunk"),
            ("chunk.fence_wait", "denoise_range"),
            ("vae_decode_dispatch", "generate_range"),
            ("vae_decode_fetch", "generate_range"),
            ("decode.wait", "vae_decode_fetch"),
            ("fetch.copy", "vae_decode_fetch"),
            ("png_encode", "generate_range")]

    def test_status_block(self, engine):
        engine.txt2img(CASE.payload())
        block = METRICS.summary()["expander"]
        assert set(block) == {
            "requests", "scans_joined", "requests_joined", "prompts_joined",
            "tokens_prefilled", "tokens_from_prefix_cache",
            "sequences", "tokens_decoded", "decode_steps", "experts_read",
            "rows_attended", "rows_read", "rows_read_shared",
            "tokens_no_held_expert", "expert_tokens",
            "expert_load_max_over_mean", "cache_positions", "state_bytes",
            "prefix_snapshots", "padded_rows_masked", "expert_products",
            "route_products", "mixer_products", "conv_mixers",
            "residual_streams",
            "sinkhorn_iters", "layer_passes", "exit_pass",
            "exit_lambda_max", "delta_mixers", "delta_steps",
            "state_bytes_stepped", "fork_bytes_copied", "sublayer_norms",
            "attention_unrotated", "write_strength_bound", "ssm_mixers",
            "joined_layers", "multipliers_applied", "moe_shortcuts",
            "latent_scaled", "zero_expert_picks", "tied_head",
            "expert_picks_held", "expert_calls", "expert_calls_unread"}
        # a routed sum a layer a step, and those with no held pick are the
        # one-sequence steps' tokens that had none
        assert block["expert_calls"] == block["decode_steps"] * len(
            CFG.expert_layers)
        assert 0 <= block["expert_calls_unread"] <= min(
            block["tokens_no_held_expert"], block["expert_calls"])
        # a model of one pass leaves the looped model's counters alone
        assert (block["layer_passes"], block["exit_pass"],
                block["exit_lambda_max"]) == (0, [], 0.0)
        assert set(block["expert_products"]) == {"kernel", "loop", "grouped"}
        # every expert layer traced on this CPU kept XLA's routing chain
        assert block["route_products"] == {
            "kernel": 0, "xla": sum(block["expert_products"].values())}
        assert set(block["mixer_products"]) == {"kernel", "loop"}
        assert block["conv_mixers"] == {"step": 0, "chunk": 0}
        # nor has it a recurrent state to step or to copy at a fork
        assert block["delta_mixers"] == {"recurrent": 0, "chunked": 0,
                                         "recurrent_forked": 0}
        assert block["delta_steps"] == {"kernel": 0, "elementwise": 0}
        assert (block["state_bytes_stepped"],
                block["fork_bytes_copied"]) == (0, 0)
        # every layer norms its sublayers' input alone and rotates, and
        # no delta-rule mixer was traced to bound a write strength
        assert set(block["sublayer_norms"]) == {"pre", "post"}
        assert not any(block["sublayer_norms"]["post"].values())
        assert not any(block["attention_unrotated"].values())
        assert block["write_strength_bound"] == 0.0
        # nor a state-space mixer, a layer of two mixers or a multiplier
        assert not any(block["ssm_mixers"].values())
        assert not any(block["joined_layers"].values())
        assert set(block["ssm_mixers"]) == set(block["delta_mixers"])
        assert block["multipliers_applied"] == 0
        # nor a routed sum that crosses a layer, a scaled latent or an
        # identity expert
        assert not any(block["moe_shortcuts"].values())
        assert set(block["moe_shortcuts"]) == set(block["delta_mixers"])
        assert not any(block["latent_scaled"].values())
        assert block["zero_expert_picks"] == 0
        # it has a head of its own, and a pick that fell on a held expert
        # is counted: an expert read serves one pick at least (exactly one
        # at one sequence a step)
        assert not any(block["tied_head"].values())
        assert set(block["tied_head"]) == set(block["delta_mixers"])
        assert block["expert_picks_held"] >= block["experts_read"] > 0
        json.dumps(block)

    @pytest.mark.parametrize("sequences,forked_at,steps", [
        (1, 300, 64), (4, 296, 64), (4, 2088, 256), (3, 40, 32)])
    def test_rows_attended_and_rows_read(self, sequences, forked_at, steps):
        """What the stage hands ``record`` (pipeline/expand.py), against
        the sums written out: a step at position ``p`` attends ``p + 1``
        rows a sequence and reads what lies before the fork once for all.
        One sequence counts the same in both; four forked at 296 for 64
        steps read a row for 3.1 queries."""
        from stable_diffusion_webui_distributed_tpu.serving.metrics import (
            ExpanderStats,
        )

        attended = sum(sequences * (p + 1)
                       for p in range(forked_at, forked_at + steps))
        read = sum(forked_at + sequences * (p + 1 - forked_at)
                   for p in range(forked_at, forked_at + steps))
        stats = ExpanderStats()
        for _ in range(2):      # counters add up over requests
            stats.record(
                prefilled=0, from_prefix=0, sequences=sequences, decoded=0,
                decode_steps=steps, experts_read=0, load=[], none_held=0,
                positions={}, state_bytes={}, prefix_snapshots=0,
                padded_rows_masked=0, residual_streams=1, sinkhorn_iters=0,
                **expand.rows_of(sequences, forked_at, steps))
        block = stats.summary()
        assert block["rows_attended"] == 2 * attended
        assert block["rows_read"] == 2 * read
        if sequences == 1:
            assert attended == read
        if (sequences, forked_at, steps) == (4, 296, 64):
            assert round(attended / read, 1) == 3.1
        if forked_at == 2088:
            assert round(attended / read, 1) == 3.4


class TestDrawnAhead:
    """What of a request reads nothing of the expanded text is made under
    the expander's first decode chunk, the keys and a snapshot's copy are
    one dispatch each, and the counters come down behind the UNet's first
    chunk: the same functions on the same arguments in another order."""

    @staticmethod
    def ahead_since(before):
        now = PLAN.summary()["ahead"]
        return [now[k] - before[k] for k in ("drawn", "taken", "dropped")]

    @pytest.mark.parametrize("images", [1, 4])
    def test_the_images_are_what_the_old_order_gives(self, engine,
                                                     monkeypatch, images):
        assert engine.expander.shares_a_step
        request = CASE.payload(batch_size=images, subseed=7)
        engine.txt2img(CASE.payload())       # the instruction's snapshot is kept
        before = PLAN.summary()["ahead"]
        EXPANDER.clear()
        ahead = engine.txt2img(request)
        assert self.ahead_since(before) == [1, 1, 0]
        def counters():     # but those a trace feeds
            return {k: v for k, v in EXPANDER.summary().items()
                    if not k.endswith(("_products", "_mixers", "_norms",
                                       "_unrotated", "_bound"))}

        counted = counters()
        monkeypatch.setattr(engine, "_draws_ahead", lambda p: False)
        EXPANDER.clear()
        plain = engine.txt2img(request)
        assert self.ahead_since(before) == [1, 1, 0]
        assert len(ahead.images) == images
        assert ahead.model_dump() == plain.model_dump()
        assert counted == counters()
        assert counted["requests"] == 1 and counted["sequences"] == images

    def test_an_expansion_without_a_decode_chunk_draws_nothing(
            self, engine, monkeypatch):
        """``max_new_tokens`` 1: the one token comes from the prefill."""
        request = CASE.payload(subseed=7,
                          alwayson_scripts=CASE.script(max_new_tokens=1))
        before = PLAN.summary()["ahead"]
        EXPANDER.clear()
        one = engine.txt2img(request)
        assert self.ahead_since(before) == [0, 0, 0]
        assert len(one.prompts[0].split()) == 6
        assert EXPANDER.summary()["requests"] == 1
        monkeypatch.setattr(engine, "_draws_ahead", lambda p: False)
        assert engine.txt2img(request).model_dump() == one.model_dump()

    def test_an_interrupt_during_the_expansion_drops_the_draw(
            self, engine, monkeypatch):
        whole = engine.txt2img(CASE.payload(subseed=7))
        real = engine.expander._decode_fn

        def then_interrupt(capacity, sequences=1):
            def decode(*args):
                engine.state.flag.interrupt()
                return real(capacity, sequences)(*args)
            return decode

        before = PLAN.summary()["ahead"]
        EXPANDER.clear()
        with monkeypatch.context() as patch:
            patch.setattr(engine.expander, "_decode_fn", then_interrupt)
            cut = engine.txt2img(CASE.payload(subseed=7))
        assert self.ahead_since(before) == [1, 0, 1]
        assert cut.images == []
        # the counters of the request that enqueued no chunk are there
        stats = EXPANDER.summary()
        assert stats["requests"] == 1
        assert stats["decode_steps"] == expand.DECODE_STEPS
        # and the next request is a whole one
        assert engine.txt2img(CASE.payload(subseed=7)).model_dump() \
            == whole.model_dump()
        assert self.ahead_since(before) == [2, 1, 1]

    @pytest.mark.parametrize("job", ["img2img", "hires", "adaptive"])
    def test_what_cannot_be_drawn_ahead_keeps_its_order(self, engine, job):
        import base64
        import io

        from PIL import Image

        buf = io.BytesIO()
        Image.new("RGB", (32, 32), (90, 120, 60)).save(buf, format="PNG")
        request = {
            "img2img": dict(init_images=[base64.b64encode(
                buf.getvalue()).decode()], denoising_strength=0.5),
            "hires": dict(enable_hr=True, hr_scale=2.0,
                          hr_second_pass_steps=2, denoising_strength=0.5),
            "adaptive": dict(sampler_name="DPM adaptive"),
        }[job]
        before = PLAN.summary()["ahead"]
        EXPANDER.clear()
        out = engine.generate_range(
            CASE.payload(**request), 0, None,
            "img2img" if job == "img2img" else "txt2img")
        assert self.ahead_since(before) == [0, 0, 0]
        assert len(out.images) == 1 and len(out.prompts[0].split()) == 45
        assert EXPANDER.summary()["requests"] == 1

    @pytest.mark.parametrize("images", [1, 2, 3, 4])
    def test_the_keys_of_one_dispatch_are_the_eager_keys(self, engine,
                                                         images):
        """Bit for bit, at a seed whose sum with the index wraps; three
        images are padded to four with the last."""
        from stable_diffusion_webui_distributed_tpu.runtime import rng

        seed = 2 ** 32 - 2
        batch = kv.sequence_bucket(images)
        indices = list(range(images)) + [images - 1] * (batch - images)
        got = engine.expander._keys_fn(batch)(
            np.uint32(seed),
            np.asarray(indices[0] if batch == 1 else indices, np.uint32),
            np.uint32(expand._KEY_DOMAIN))
        want = [jax.random.fold_in(rng.key_for_image(seed, i),
                                   expand._KEY_DOMAIN) for i in indices]
        assert got.shape == (() if batch == 1 else (batch,))
        np.testing.assert_array_equal(
            jax.random.key_data(got).reshape(batch, -1),
            np.stack([jax.random.key_data(k) for k in want]))
        assert ("expand_keys", batch) in engine.executable_keys()


class TestDispatcher:
    def test_an_expanded_request_never_shares_a_dispatch(self, engine):
        """... where the ladder has no rung for a second request of its
        size, or the expander decodes one sequence a step; with both it
        shares a dispatch as a plain request does (the rule since PR 72:
        tests/test_request_groups.py drives the groups)."""
        from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
            ServingDispatcher,
        )

        class Stub:
            max_batch = 1
            _expansion = ServingDispatcher._expansion

        stub = Stub()
        stub.engine = engine
        stub._traced_rowspec = lambda p: (0, 0)
        assert ServingDispatcher._coalescable(
            stub, CASE.payload(alwayson_scripts={}))
        assert not ServingDispatcher._coalescable(stub, CASE.payload())
        stub.max_batch = 4      # the batch-4 cells: four images a request
        assert not ServingDispatcher._coalescable(
            stub, CASE.payload(batch_size=4))
        assert ServingDispatcher._coalescable(stub, CASE.payload())
        assert ServingDispatcher._coalescable(
            stub, CASE.payload(batch_size=2))
        stub.engine = contract.engine_for(configs.TINY_CONV_EXPAND)
        assert not ServingDispatcher._coalescable(stub, CASE.payload())
        stub.engine = Engine(configs.TINY, dict(tiny_params()),
                             state=GenerationState())
        stub.max_batch = 1
        assert ServingDispatcher._coalescable(stub, CASE.payload())


class TestRequestsOfOneScan:
    """``expand_group``: the images of several requests as the sequences
    of one joined scan (the dispatcher's group stage)."""

    MEMBERS = [("a cow in a valley", 1234, [0]),
               ("an old lighthouse on a cliff above a stormy sea at dusk "
                "in the first snow of the winter", 77, [0, 1])]

    @pytest.mark.parametrize("users,batch", [((5, 16, 63), 4),
                                             ((7, 100), 2)])
    def test_a_joined_decode_is_each_sequence_alone(self, params, users,
                                                    batch):
        """The preset of the configuration the two-client cell runs."""
        contract.joined_against_alone(CASE, params, users, batch)

    def test_every_image_gets_the_text_it_gets_alone(self, engine):
        args = prompt_expansion_args(CASE.payload())
        alone = [[engine.expander.expand(prompt, args, seed, i)
                  for i in indices] for prompt, seed, indices in self.MEMBERS]
        EXPANDER.clear()
        assert engine.expander.expand_group(self.MEMBERS, args,
                                            rows=4) == alone
        stats = EXPANDER.summary()
        assert stats["requests"] == stats["scans_joined"] == 1
        assert stats["requests_joined"] == stats["prompts_joined"] == 2
        assert stats["sequences"] == 3 and stats["tokens_decoded"] == 120
        assert stats["decode_steps"] == 2 * contract.STEPS  # counted once
        # the 31 kept rows once a step, the prompts' 5 + 2 x 19 and what
        # each decodes once a sequence
        steps = 2 * contract.STEPS
        assert stats["rows_read_shared"] == steps * 31
        assert stats["rows_read"] == steps * 31 + steps * 43 \
            + 3 * steps * (steps + 1) // 2
        assert stats["rows_attended"] == 3 * steps * 31 + steps * 43 \
            + 3 * steps * (steps + 1) // 2

    def test_a_lone_request_pads_to_the_rung_and_one_row_takes_the_old_path(
            self, engine):
        args = prompt_expansion_args(CASE.payload())
        prompt, seed, _ = self.MEMBERS[0]
        alone = engine.expander.expand(prompt, args, seed, 0)
        before = set(engine.executable_keys())
        EXPANDER.clear()
        assert engine.expander.expand_group(
            [(prompt, seed, [0])], args, rows=1) == [[alone]]
        assert EXPANDER.summary()["scans_joined"] == 0
        assert set(engine.executable_keys()) == before
        assert engine.expander.expand_group(
            [(prompt, seed, [0])], args, rows=2) == [[alone]]
        assert EXPANDER.summary()["requests_joined"] == 1
        # (the keys of two, ``("expand_keys", 2)``, where no test before
        # this one ran a batch of two)
        made = set(engine.executable_keys()) - before - {("expand_keys", 2)}
        assert made == {
            ("expand_join", contract.CAPACITY, 2, 64, 2 * contract.STEPS),
            ("expand_decode_chunk", contract.STEPS, contract.CAPACITY, 2,
             "joined", 64)}
        # the pair that follows compiles nothing
        METRICS.clear()
        engine.expander.expand_group(self.MEMBERS[:1] + [
            (self.MEMBERS[1][0], 77, [0])], args, rows=2)
        assert not METRICS.summary()["compiles"]

    def test_spans_of_a_joined_scan(self, engine):
        from stable_diffusion_webui_distributed_tpu.obs import spans

        args = prompt_expansion_args(CASE.payload())
        spans.TRACER.clear()
        with spans.request("joined-spans"):
            engine.expander.expand_group(self.MEMBERS, args, rows=4)
        by_name = {}
        for e in contract.span_events():
            by_name.setdefault(e["name"], []).append(e["args"])
        assert [(a["sequences"], a["requests"])
                for a in by_name["expand"]] == [(3, 2)]
        assert {(a["sequences"], a["requests"])
                for a in by_name["expand.decode_chunk"]} == {(3, 2)}
        (fork,) = by_name["expand.fork"]
        assert fork["joined"] and fork["sequences"] == 4 \
            and fork["prompts"] == 2
        # the shared range's copy and one a sequence; a prompt a sequence
        assert len(by_name["expand.prefix_copy"]) == 4
        assert [a["tokens"] for a in by_name["expand.prefill"]] \
            == [5, 19, 19]
        kinds = [e["args"]["kind"] for e in
                 spans.TRACER.export_chrome()["traceEvents"]
                 if e.get("name") == "device.run" and e.get("ph") == "b"]
        assert kinds.count("expand_join") == 1
        assert kinds.count("expand_decode_chunk") == 2


class TestSharding:
    @pytest.mark.parametrize("preset", ["TINY_EXPAND",
                                        "TINY_WINDOW_EXPAND"])
    def test_expert_and_vocabulary_axes(self, preset):
        """``ep`` and ``vp`` partition the tree of a share with a dense
        layer, a shared expert and gates, and of one with none of them
        (every layer a router over held experts, ungated attention)."""
        from jax.sharding import PartitionSpec as P

        from stable_diffusion_webui_distributed_tpu.parallel.sharding import (
            shard_params, tp_spec_for,
        )

        assert tp_spec_for("layers_1/mlp/experts/w_gate", 3) \
            == P("ep", None, None)
        assert tp_spec_for("embed_tokens/embedding", 2) == P("vp", None)
        assert tp_spec_for("lm_head/kernel", 2) == P(None, "vp")
        devices = np.array(jax.devices()[:4]).reshape(2, 2)
        mesh = jax.sharding.Mesh(devices, ("ep", "vp"))
        placed = shard_params(
            contract.lm_params(getattr(configs, preset).expander), mesh)
        gate = placed["layers_1"]["mlp"]["experts"]["w_gate"]
        assert gate.sharding.spec == P("ep", None, None)
        assert placed["lm_head"]["kernel"].sharding.spec == P(None, "vp")
        assert placed["embed_tokens"]["embedding"].sharding.spec \
            == P("vp", None)
        # no tp axis on this mesh: a Megatron leaf stays whole
        assert placed["layers_0"]["attn"]["q_proj"]["kernel"].sharding.spec \
            == P()
        if preset == "TINY_WINDOW_EXPAND":
            # every layer's experts over ep, the router whole, no leaf
            # left without a rule that a Laguna share's has
            for layer in range(4):
                mlp = placed[f"layers_{layer}"]["mlp"]
                assert set(mlp) == {"router", "experts"}
                assert mlp["router"].sharding.spec == P()
                for leaf in mlp["experts"].values():
                    assert leaf.sharding.spec == P("ep", None, None)
                    assert leaf.addressable_shards[0].data.shape[0] == 4

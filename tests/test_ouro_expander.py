"""The resident prompt expander as a LOOPED model: a stack of full-attention
layers with dense MLPs and a norm before and after each sublayer, which a
token passes ``total_ut_steps`` times over one set of weights, every pass
with keys and values of its own (the cache's pass axis), the final norm
closing each pass and a learned gate choosing the pass the head reads.

Everything runs the tiny preset (models/configs.py ``TINY_LOOP_LM``: 4
layers, 3 passes, hidden 64). The plain reference is the benchmark's own
(benchmarks/reference/ouro_ref.py: float32, one sequence, no cache, no
pass axis). A model of ONE pass must be what it was before passes existed:
the last class holds every preset the other test files run to that.
"""

import dataclasses
import importlib.util
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.obs import prometheus
from stable_diffusion_webui_distributed_tpu.parallel import sharding
from stable_diffusion_webui_distributed_tpu.pipeline import expand
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
)
from stable_diffusion_webui_distributed_tpu.runtime import dtypes
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER, METRICS,
)
from tests.test_mellum2_expander import (
    assert_own_rows, forked_against_alone, forked_shapes,
)
from tests.test_pipeline import init_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _load(os.path.join(ROOT, "benchmarks", "reference", "ouro_ref.py"),
            "ouro_ref_for_tests")
FAMILY = configs.TINY_LOOP_EXPAND
CFG = FAMILY.expander
PASSES = CFG.total_ut_steps
STEPS = expand.DECODE_STEPS


def lm_params(cfg, seed=0):
    """``DecoderLM.init``'s tree with the norms off 1 and the gate's bias
    off 0, so that reading one norm as another, or no bias, would show."""
    params = lm.DecoderLM(cfg).init(
        jax.random.key(seed), jnp.zeros((4,), jnp.int32), jnp.int32(0),
        jnp.int32(4), lm.empty_cache(cfg, 8, jnp.float32))["params"]
    key = jax.random.key(seed + 100)

    def off(path, x):
        leaf = getattr(path[-1], "key", "")
        if leaf == "bias":
            return x + 0.25
        if leaf != "scale":
            return x
        return x + 0.2 * jax.random.normal(
            jax.random.fold_in(key, zlib.crc32(str(path).encode()) % 2 ** 31),
            x.shape)

    return jax.tree_util.tree_map_with_path(off, params)


@pytest.fixture(scope="module")
def params():
    return lm_params(CFG)


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def with_threshold(threshold):
    return dataclasses.replace(FAMILY, expander=dataclasses.replace(
        CFG, early_exit_threshold=threshold))


# -- (a) program against reference --------------------------------------------

class TestAgainstTheReference:
    @pytest.mark.parametrize("size", [24, 48])
    def test_chunks_fork_and_decode_match_four_full_forwards(self, params,
                                                             size):
        """The prefix's chunk, a copy, the prompt's chunk, a fork into
        four and one step over all four a position through the (pass,
        layer) cache, against a full forward of each whole sequence:
        logits and every pass's gate to 1e-5, the chosen pass the same."""
        prefix, user, decoded = REF.split(size)
        ids, continuations = REF.inputs(FAMILY, 3, size)
        got, gates, chose = jax.jit(REF.program(
            FAMILY, dtypes.F32, with_gates=True))(params, ids, continuations)
        want, lam, own = jax.jit(lambda p, i, c: REF.forward(
            FAMILY, p, i, c, with_gates=True))(params, ids, continuations)
        rows = prefix + user + REF.SEQUENCES * decoded
        assert got.shape == want.shape == (rows, CFG.vocab[1])
        assert gates.shape == lam.shape == (PASSES, rows)
        assert rel_rms(got, want) < 1e-5
        np.testing.assert_allclose(gates, lam, atol=1e-5)
        # the published threshold: the last pass for every token, though
        # no gate is anywhere near 1
        assert np.array_equal(chose, own)
        assert np.all(np.asarray(own) == PASSES - 1)
        assert 0.5 < float(lam.max()) < 0.9999
        tails = np.asarray(got[prefix + user:]).reshape(
            REF.SEQUENCES, decoded, -1)
        assert rel_rms(tails[1], tails[0]) > 0.1

    @pytest.mark.parametrize("control", [name for name, _ in REF.CONTROLS])
    def test_each_control_is_further_from_the_reference(self, params,
                                                        control):
        """Every pass attending the last pass's rows, a pass fewer, no
        norms after the sublayers, the final norm after the last pass
        alone and the int8 linears each read far from the reference where
        the program reads 1e-6."""
        ids, continuations = REF.inputs(FAMILY, 3, 48)
        want = jax.jit(lambda p, i, c: REF.forward(FAMILY, p, i, c))(
            params, ids, continuations)
        side, kwargs = dict(REF.CONTROLS)[control]
        if side == "program":
            lower = jax.jit(REF.program(FAMILY, dtypes.F32, **kwargs))(
                params, ids, continuations)
        else:
            lower = jax.jit(lambda p, i, c: REF.forward(
                FAMILY, p, i, c, **kwargs))(params, ids, continuations)
        assert rel_rms(lower, want) > 1e-2

    @pytest.mark.parametrize("threshold", [0.5, 0.9, 0.99])
    def test_a_threshold_below_one_moves_the_pass_read_in_both(
            self, params, threshold):
        """Program and reference leave at the same pass, row by row, and
        read the same logits; every pass still ran (the later rows'
        logits need each pass's keys of the earlier ones)."""
        family = with_threshold(threshold)
        ids, continuations = REF.inputs(family, 3, 48)
        got, gates, chose = jax.jit(REF.program(
            family, dtypes.F32, with_gates=True))(params, ids, continuations)
        want, lam, own = jax.jit(lambda p, i, c: REF.forward(
            family, p, i, c, with_gates=True))(params, ids, continuations)
        assert np.array_equal(chose, own)
        assert len(set(np.asarray(own).tolist())) > 1
        assert np.any(np.asarray(own) < PASSES - 1)
        assert rel_rms(got, want) < 1e-5
        np.testing.assert_allclose(gates, lam, atol=1e-5)
        # the gates do not depend on the threshold; what is read does
        at_one = jax.jit(REF.program(FAMILY, dtypes.F32))(
            params, ids, continuations)
        assert rel_rms(got, at_one) > 1e-2

    def test_the_exit_rule_by_hand(self):
        lam = jnp.asarray([[0.5, 0.1, 0.9], [0.5, 0.1, 0.9],
                           [0.5, 0.1, 0.9]])
        # S: 0.5, 0.75, 1 | 0.1, 0.19, 1 | 0.9, 0.99, 1
        assert REF.exit_rule(lam, 1.0).tolist() == [2, 2, 2]
        assert REF.exit_rule(lam, 0.7).tolist() == [1, 2, 0]
        assert REF.exit_rule(lam, 0.95).tolist() == [2, 2, 1]
        assert REF.exit_rule(lam, 0.05).tolist() == [0, 0, 0]


# -- (b) a step over B sequences ----------------------------------------------

def _prefilled(params, length=21, capacity=256):
    ids = jax.random.randint(jax.random.key(5), (length,), 0, 512)
    logits, cache, _ = lm.DecoderLM(CFG).apply(
        {"params": params}, ids, jnp.int32(0), jnp.int32(length),
        lm.empty_cache(CFG, capacity, jnp.float32), all_logits=False)
    return logits[0], cache, length


def _keys(indices, seed=77):
    from stable_diffusion_webui_distributed_tpu.runtime import rng

    return jnp.stack([rng.key_for_image(seed, i) for i in indices])


class TestSequencesOfOneStep:
    @pytest.mark.parametrize("user", [1, 16, 63, 64])
    @pytest.mark.parametrize("live,batch", [(2, 2), (4, 4), (3, 4)])
    def test_a_forked_decode_is_each_sequence_alone(self, params, user,
                                                    live, batch):
        """The looped stack at the chunk bucket's edges: every pass reads
        its own rows of the shared buffers and of a sequence's own."""
        forked_against_alone(CFG, params, user, live, batch)

    @pytest.mark.parametrize("live,batch", [(1, 1), (2, 2), (4, 4), (3, 4)])
    def test_each_sequence_gets_what_it_gets_alone(self, params, live,
                                                   batch):
        """A chunk of steps over ``batch`` forked sequences against the
        one-sequence chunk run once a key: the same tokens, the same rows
        of every pass in the cache, and the pad left out of the exits."""
        module = lm.DecoderLM(CFG)
        row, cache, length = _prefilled(params)
        keys = _keys(list(range(live)) + [live - 1] * (batch - live))
        first = lm.sample_each(row, keys, length, jnp.float32(1.0))
        alone = jax.jit(lm.decode_chunk_fn(module, STEPS))
        together = jax.jit(lm.decode_sequences_fn(module, STEPS))
        (forked, tokens, position, made, load, none_held, read,
         (counts, most)) = together(
            params, kv.fork(cache, batch), first, jnp.int32(length), keys,
            jnp.float32(1.0), jnp.int32(live))
        assert made.shape == (STEPS, batch)
        assert load.shape == (0, 0) and read.shape == (0,)
        assert counts.tolist() == [0] * (PASSES - 1) + [STEPS * live]
        largest = 0.0
        for b in range(live):
            own, last, _, steps, _, _, (own_counts, own_most) = alone(
                params, cache, first[b], jnp.int32(length), keys[b],
                jnp.float32(1.0))
            assert np.array_equal(steps, made[:, b])
            assert int(last) == int(tokens[b])
            assert own_counts.tolist() == [0] * (PASSES - 1) + [STEPS]
            largest = max(largest, float(own_most))
            assert all(x.shape == (PASSES, 256, 4, 16)
                       for x in own["k"] + own["v"])
            assert_own_rows(CFG, own, forked, b, length, STEPS)
        assert all(x.shape == (batch, PASSES, 256, 4, 16)
                   for x in forked["k"] + forked["v"])
        assert float(most) == pytest.approx(largest, rel=1e-5)
        assert len({tuple(np.asarray(made[:, b])) for b in range(live)}) \
            == live

    def test_every_pass_writes_rows_of_its_own(self, params):
        """After a prefill every pass's rows of the written positions are
        filled and differ from pass to pass; the rest stay zero."""
        _, cache, length = _prefilled(params)
        for rows in cache["k"] + cache["v"]:
            assert rows.shape == (PASSES, 256, 4, 16)
            assert np.all(np.asarray(rows[:, length:]) == 0)
            for t in range(PASSES):
                assert np.all(np.any(np.asarray(rows[t, :length]) != 0,
                                     axis=(1, 2)))
            assert rel_rms(rows[1, :length], rows[0, :length]) > 0.05

    def test_a_fork_copies_no_pass(self, params):
        """(c): the shared buffers ARE the prefill's, every pass of them;
        a sequence's own rows have the passes behind the sequences."""
        _, cache, _ = _prefilled(params)
        forked = kv.fork(cache, 4, 2 * STEPS)
        for name, shared in zip(("k", "v"), ("k_shared", "v_shared")):
            assert all(mine is theirs for mine, theirs
                       in zip(cache[name], forked[shared]))
            assert [x.shape for x in forked[shared]] == \
                [(PASSES, 256, 4, 16)] * 4
            assert [x.shape for x in forked[name]] == \
                [(4, PASSES, 64, 4, 16)] * 4
        (at,) = forked["forked_at"]
        assert at.shape == (4, 1) and np.all(np.asarray(at) == -1)
        made = jax.jit(lambda c: kv.own_rows(c, 4, 2 * STEPS))(cache)
        assert [x.shape for x in made["k"]] == [(4, PASSES, 64, 4, 16)] * 4
        assert not any(np.any(np.asarray(x)) for x in made["v"])
        assert set(made) == {"k", "v", "forked_at"}

    def test_a_looped_model_shares_a_step(self):
        assert lm.shares_a_step(CFG)
        assert lm.shares_a_step(configs.OURO_2_6B)

    def test_what_a_looped_stack_wants(self):
        for change in ({"layer_types": ("full", "sliding", "full", "full")},
                       {"residual_streams": 4}, {"dense_layers": (0,)}):
            with pytest.raises(ValueError):
                dataclasses.replace(CFG, **change)


# -- (c) the cache's manager over buffers with a pass axis ----------------------

class TestTheCacheCountsThePassAxis:
    def test_a_kept_prefix_restores_every_pass(self, params):
        manager = kv.KVCacheManager(CFG, jnp.float32)
        prefix = list(range(1, 22))
        empty, held = manager.acquire(prefix, 256)
        assert held == 0
        assert all(not np.any(np.asarray(x)) for x in empty["k"])
        _, cache, length = _prefilled(params)
        manager.keep_prefix(prefix, 256, cache)
        # the executables donate what they are given: the kept copy is
        # neither the cache it was made from nor the one handed out
        again, held = manager.acquire(prefix, 256)
        assert held == length == 21 and manager.snapshots == 1
        for name in ("k", "v"):
            for kept, made in zip(again[name], cache[name]):
                assert kept is not made
                assert kept.shape == (PASSES, 256, 4, 16)
                assert np.array_equal(np.asarray(kept), np.asarray(made))
        # a chunk continued from the copy is the chunk continued from the
        # original, in every pass
        module = lm.DecoderLM(CFG)
        more = jnp.arange(7, dtype=jnp.int32) + 30
        a, after_a, _ = module.apply({"params": params}, more,
                                     jnp.int32(21), jnp.int32(7), again)
        b, after_b, _ = module.apply({"params": params}, more,
                                     jnp.int32(21), jnp.int32(7), cache)
        assert np.array_equal(np.asarray(a), np.asarray(b))
        for x, y in zip(after_a["k"], after_b["k"]):
            assert np.array_equal(np.asarray(x), np.asarray(y))

    def test_bytes_and_positions(self):
        manager = kv.KVCacheManager(CFG, jnp.bfloat16)
        # a position occupies a row of every pass of every layer
        assert manager.positions_in_use(40) == {"full": 4 * 40 * PASSES,
                                                "sliding": 0}
        # four sequences forked at 30: what lies before it once, the 10
        # behind it once each, in every pass of every layer
        assert manager.positions_in_use(40, 4, 30) == {
            "full": 4 * (30 + 4 * 10) * PASSES, "sliding": 0}
        one = kv.state_bytes(CFG, 256, jnp.bfloat16)
        assert one == {"full": 4 * PASSES * 2 * 256 * 4 * 16 * 2,
                       "sliding": 0}
        # a forked group: every buffer once and 64 slots a sequence
        assert kv.state_bytes(CFG, 256, jnp.bfloat16, 4, 64) == {
            "full": 4 * PASSES * 2 * (256 + 4 * 64) * 4 * 16 * 2,
            "sliding": 0}
        assert lm.cache_shapes(CFG, 256) == {
            "k": [(PASSES, 256, 4, 16)] * 4, "v": [(PASSES, 256, 4, 16)] * 4}


# -- the engine's path ----------------------------------------------------------

INSTRUCTION = " ".join(f"word{i}" for i in range(30))


def script(**args):
    return {"prompt expansion": {"args": [dict(
        {"instruction": INSTRUCTION, "max_new_tokens": 40,
         "temperature": 1.0, "ignore_eos": True, "context_chunks": 1},
        **args)]}}


@pytest.fixture(scope="module")
def engine():
    params = init_params(configs.TINY)
    params["expander"] = lm_params(CFG, seed=1)
    return Engine(FAMILY, params, chunk_size=4, state=GenerationState())


def payload(**kw):
    base = dict(prompt="a cow in a valley", steps=4, width=32, height=32,
                seed=1234, alwayson_scripts=script())
    base.update(kw)
    return GenerationPayload(**base)


CAPACITY = kv.capacity_for(31 + 64 + 2 * STEPS)


class TestTheEnginePath:
    def test_every_image_its_own_expansion(self, engine):
        whole = engine.txt2img(payload(batch_size=4))
        assert len(set(whole.prompts)) == 4
        for i in (0, 3):
            solo = engine.txt2img(payload(seed=1234 + i))
            assert solo.prompts[0] == whole.prompts[i], i
        again = engine.txt2img(payload(batch_size=4))
        assert again.prompts == whole.prompts
        assert again.images == whole.images
        keys = {k for k in engine.executable_keys()
                if k[0].startswith("expand")}
        assert keys == {("expand_prefill", 64, CAPACITY),
                        ("expand_prefill", 64, CAPACITY, 4),
                        ("expand_fork", CAPACITY, 4, 2 * STEPS),
                        ("expand_decode_chunk", STEPS, CAPACITY),
                        ("expand_decode_chunk", STEPS, CAPACITY, 4),
                        # one dispatch each: the images' keys, a
                        # snapshot's copy
                        ("expand_keys", 1), ("expand_keys", 4),
                        ("expand_copy", CAPACITY)}

    def test_counters_and_spans_of_the_passes(self, engine):
        from stable_diffusion_webui_distributed_tpu.obs import spans

        engine.txt2img(payload(batch_size=4))       # the snapshot is kept
        EXPANDER.clear()
        spans.TRACER.clear()
        with spans.request("rid-ou"):
            engine.txt2img(payload(batch_size=4))
        stats = METRICS.summary()["expander"]
        assert stats["requests"] == 1 and stats["sequences"] == 4
        assert stats["tokens_from_prefix_cache"] == 31
        assert stats["tokens_decoded"] == 4 * 40
        assert stats["decode_steps"] == 2 * STEPS
        # every decode step ran every pass
        assert stats["layer_passes"] == PASSES * 2 * STEPS
        # the prompt's one row and every step's four, all at the last pass
        assert stats["exit_pass"] == [0] * (PASSES - 1) + [1 + 4 * 2 * STEPS]
        assert 0.5 < stats["exit_lambda_max"] < 0.9999
        assert stats["experts_read"] == 0 and stats["expert_tokens"] == []
        # forked at 31 + 5: those positions once, the 40 behind them once
        # a sequence
        assert stats["cache_positions"] == {
            "full": 4 * (36 + 4 * 40) * PASSES, "sliding": 0}
        steps = range(36, 36 + 2 * STEPS)
        assert stats["rows_attended"] == sum(4 * (p + 1) for p in steps)
        assert stats["rows_read"] == sum(36 + 4 * (p + 1 - 36)
                                         for p in steps)
        sizes = kv.state_bytes(CFG, CAPACITY, jnp.float32, 4, 2 * STEPS)
        assert stats["state_bytes"] == sizes
        row = PASSES * 2 * 4 * 16 * 4       # a layer's slot, float32
        assert sizes["full"] == 4 * (CAPACITY + 4 * 2 * STEPS) * row
        events = [e for e in spans.TRACER.export_chrome()["traceEvents"]
                  if e.get("ph") == "X"]
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e["args"])
        assert [a["passes"] for a in by_name["expand.prefill"]] == [PASSES]
        (fork,) = by_name["expand.fork"]
        assert fork["passes"] == PASSES and fork["sequences"] == 4
        # the bytes a fork makes: the sequences' own rows alone
        assert fork["bytes"] == 4 * 4 * 2 * STEPS * row
        assert [(a["passes"], a["sequences"])
                for a in by_name["expand.decode_chunk"]] == [(PASSES, 4)] * 2
        text = prometheus.render()
        assert f"sdtpu_expander_layer_passes_total {PASSES * 2 * STEPS}" \
            in text
        assert f'sdtpu_expander_exit_pass_total{{pass="{PASSES}"}} ' \
            f"{1 + 4 * 2 * STEPS}" in text
        assert "sdtpu_expander_exit_lambda_max 0." in text

    def test_a_site_carries_its_passes(self):
        """A looped stack's layers are alike and share ONE trace of a
        layer an executable: a fresh engine's four-image request records
        one site for each of its two prefill executables and one for the
        decode scan, marked with the passes."""
        params = init_params(configs.TINY)
        params["expander"] = lm_params(CFG, seed=1)
        fresh = Engine(FAMILY, params, chunk_size=4, state=GenerationState())
        ATTENTION.clear()
        fresh.txt2img(payload(batch_size=4))
        sites = ATTENTION.summary()["by_shape"]
        assert sites[f"T64 S{CAPACITY} D16 P{PASSES}"] == {"xla": 2}
        # a forked step's keys: the shared buffer and a sequence's own
        assert sites[f"T1 S{CAPACITY + 2 * STEPS} D16 P{PASSES}"] \
            == {"xla": 1}
        ATTENTION.clear()

    def test_a_threshold_below_one_counts_the_earlier_passes(self):
        family = with_threshold(0.9)
        params = init_params(configs.TINY)
        params["expander"] = lm_params(CFG, seed=1)
        early = Engine(family, params, chunk_size=4, state=GenerationState())
        EXPANDER.clear()
        early.txt2img(payload(batch_size=4))
        stats = EXPANDER.summary()
        assert sum(stats["exit_pass"]) == 1 + 4 * 2 * STEPS
        assert sum(stats["exit_pass"][:-1]) > 0
        assert stats["layer_passes"] == PASSES * 2 * STEPS   # all still run


class TestNewLeavesAreWholeOnEveryChip:
    @pytest.mark.parametrize("path,ndim", [
        ("expander/layers_3/input_norm_2/scale", 1),
        ("expander/layers_3/post_attention_norm_2/scale", 1),
        ("expander/early_exit_gate/kernel", 2),
        ("expander/early_exit_gate/bias", 1)])
    def test_rule(self, path, ndim):
        from jax.sharding import PartitionSpec as P

        assert sharding.tp_spec_for(path, ndim) == P()


# -- the published model, from shapes -----------------------------------------

def _count(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


class TestThePublishedModel:
    def test_parameters_and_bytes_from_shapes(self):
        cfg = configs.sd15_ouro_expander().expander
        assert cfg is configs.OURO_2_6B
        assert cfg.layer_types == ("full",) * 48 and cfg.total_ut_steps == 4
        assert cfg.vocab == (0, 49152) and cfg.expert_layers == ()
        shapes = jax.eval_shape(lambda: lm.DecoderLM(cfg).init(
            jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
            jnp.int32(4), lm.empty_cache(cfg, 8, jnp.float32)))["params"]
        layer = shapes["layers_0"]
        assert set(layer) == {"attn", "mlp", "input_norm", "input_norm_2",
                              "post_attention_norm",
                              "post_attention_norm_2"}
        assert set(layer["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
        assert _count(layer["attn"]) == 4 * 2048 * 2048 == 16_777_216
        assert _count(layer["mlp"]) == 3 * 2048 * 5632 == 34_603_008
        norms = 4 * 2048
        assert round((_count(layer) - norms) / 1e6, 1) == 51.4
        assert _count(shapes["embed_tokens"]) == _count(shapes["lm_head"]) \
            == 100_663_296
        assert _count(shapes["early_exit_gate"]) == 2049
        total = _count(shapes)
        assert total == 48 * (51_380_224 + norms) + 2 * 100_663_296 \
            + 2048 + 2049
        assert round(total / 1e6) == 2668
        assert round(48 * 51_380_224 * 2 / 1e9, 2) == 4.93   # the stack
        # the cache: 32 KiB a (pass, layer) slot, 1.5 MiB a position,
        # 0.75 GiB a sequence at the capacity bucket of 512
        sizes = kv.state_bytes(cfg, 512, jnp.bfloat16)
        assert sizes["full"] // 512 == 48 * 4 * 2 * 16 * 128 * 2 \
            == 3 * 2 ** 19
        assert sizes["full"] == 3 * 2 ** 28
        # four sequences forked: the 0.75 GiB once and 64 slots each
        assert kv.state_bytes(cfg, 512, jnp.bfloat16, 4, 64)["full"] \
            == 3 * 2 ** 28 + 4 * 64 * 3 * 2 ** 19 == 9 * 2 ** 27
        assert kv.capacity_for(256 + 64 + 2 * STEPS) == 512

    def test_one_step_of_four_sequences_is_48_layers_in_one_loop(self):
        """The decode step the cell runs, lowered without weights or
        FLOPs: ONE loop over the passes whose body holds 48 layers' ops,
        not 192, and every site marked with its four passes."""
        cfg = configs.OURO_2_6B
        module = lm.DecoderLM(cfg, dtype=jnp.bfloat16)
        cache = forked_shapes(cfg, 512, 4, 64)
        # a layer: four passes of 512 shared rows, and every sequence's
        # four passes of 64 of its own
        assert len(cache["k"]) == len(cache["v_shared"]) == 48
        assert cache["k_shared"][0].shape == (4, 512, 16, 128)
        assert cache["k"][0].shape == (4, 4, 64, 16, 128)
        shapes = jax.eval_shape(lambda: module.init(
            jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
            jnp.int32(4), lm.empty_cache(cfg, 8, jnp.float32)))
        ATTENTION.clear()
        text = jax.jit(lambda v, c: module.apply(
            v, jnp.zeros((4,), jnp.int32), jnp.int32(330), jnp.int32(4), c,
            sequences=True)).lower(shapes, cache).as_text()
        assert text.count("stablehlo.while") == 1
        assert text.count("stablehlo.case") == 0
        # the 48 layers are 48 calls of ONE traced layer: its 7 Linears
        # and attention's four products (scores and sums over the shared
        # rows and over a sequence's own); the gate and the head beside
        # them
        assert text.count("stablehlo.dot_general") == 7 + 4 + 2
        assert ATTENTION.summary()["by_shape"] == {
            "T1 S576 D128 P4": {"xla": 1}}
        assert text.count("call @looped_layer(") == 48
        ATTENTION.clear()


# -- a model of one pass is what it was -----------------------------------------

ONE_PASS_PRESETS = ["TINY_EXPAND", "TINY_DELTA_EXPAND", "TINY_LATENT_EXPAND",
                    "TINY_CONV_EXPAND", "TINY_WINDOW_EXPAND"]
#: the buffers a preset's cache has at a capacity of 64, as they were
#: before a pass axis existed
BUFFERS_TODAY = {
    "TINY_EXPAND": {"k": [(64, 2, 16), (8, 2, 16), (8, 2, 16), (64, 2, 16)]},
    "TINY_DELTA_EXPAND": {"k": [(8, 2, 16), (64, 2, 16)],
                          "state": [(4, 8, 8)] * 2, "conv": [(3, 64)] * 2},
    "TINY_LATENT_EXPAND": {"latent": [(64, 24)] * 4},
    "TINY_CONV_EXPAND": {"k": [(64, 2, 8)], "kept": [(2, 32)] * 5},
    "TINY_WINDOW_EXPAND": {"k": [(8, 2, 8)] * 3 + [(64, 2, 8)]},
}


def layers_one_after_the_other(cfg, params, tokens, start, length, cache):
    """What ``DecoderLM`` computed before passes existed, put together by
    hand from its parts: the table, each ``DecoderLayer`` once in order on
    its own buffers, the final norm, the head."""
    first, count = cfg.vocab
    local = tokens - first
    here = (local >= 0) & (local < count)
    x = params["embed_tokens"]["embedding"][
        jnp.clip(local, 0, count - 1)].astype(jnp.float32)
    x = x * here[:, None]
    if cfg.residual_streams > 1:
        x = jnp.broadcast_to(x[:, None, :],
                             (x.shape[0], cfg.residual_streams, x.shape[1]))
    q_pos = start + jnp.arange(tokens.shape[0], dtype=jnp.int32)
    end = start + length
    written = {name: [] for name in cache}
    for layer, kind in enumerate(cfg.layer_types):
        names = lm.buffers_of(kind)
        x, buffers, _ = lm.DecoderLayer(cfg, layer).apply(
            {"params": params[f"layers_{layer}"]}, x, q_pos, start, end,
            tuple(cache[name][len(written[name])] for name in names))
        for name, buffer in zip(names, buffers):
            written[name].append(buffer)
    if cfg.residual_streams > 1:
        x = jnp.sum(x.astype(jnp.float32), axis=1)
    n = lm.RMSNorm(cfg.rms_norm_eps, cfg.zero_centred_norm).apply(
        {"params": params["norm"]}, x)
    return lm.Linear(cfg.vocab[1]).apply(
        {"params": params["lm_head"]}, n), written


class TestAModelOfOnePassIsWhatItWas:
    @pytest.mark.parametrize("preset", ONE_PASS_PRESETS)
    def test_no_loop_no_pass_axis_and_the_same_bits(self, preset):
        cfg = getattr(configs, preset).expander
        assert cfg.total_ut_steps == 1 and not cfg.post_sublayer_norm
        # the buffers have the shapes they have today
        shapes = lm.cache_shapes(cfg, 64)
        today = dict(BUFFERS_TODAY[preset])
        if "k" in today:
            today["v"] = today["k"]
        assert shapes == today
        module = lm.DecoderLM(cfg)
        cache = lm.empty_cache(cfg, 64, jnp.float32)
        tokens = jax.random.randint(jax.random.key(2), (12,), 0, 256)
        params = module.init(jax.random.key(1), tokens, jnp.int32(0),
                             jnp.int32(12), cache)["params"]
        # no leaf more: no gate, no norm after a sublayer
        names = {str(getattr(k, "key", k)) for path, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]
                 for k in path}
        assert not names & {"early_exit_gate", "input_norm_2",
                            "post_attention_norm_2"}
        # prefill: the outputs are those of the layers one after the
        # other, bit for bit, and the lowered text holds no loop that
        # theirs does not hold
        args = (tokens, jnp.int32(0), jnp.int32(12), cache)
        served = jax.jit(lambda p, *a: module.apply({"params": p}, *a)[:2])
        by_hand = jax.jit(lambda p, *a: layers_one_after_the_other(
            cfg, p, *a))
        logits, after = served(params, *args)
        want, want_after = by_hand(params, *args)
        assert np.array_equal(np.asarray(logits), np.asarray(want))
        for name in after:
            for mine, theirs in zip(after[name], want_after[name]):
                assert mine.shape == theirs.shape
                assert np.array_equal(np.asarray(mine), np.asarray(theirs))
        loops = by_hand.lower(params, *args).as_text().count(
            "stablehlo.while")
        text = served.lower(params, *args).as_text()
        assert text.count("stablehlo.while") == loops
        # nothing is sown, so nothing can be read of passes
        _, sown = module.apply({"params": params}, *args,
                               mutable=["passes"])
        assert not sown
        # the executables return what they always did, and the decode
        # scan is the one loop around a step's own
        prefill = jax.jit(lm.prefill_fn(module))
        out = prefill(params, cache, tokens, jnp.int32(0), jnp.int32(12),
                      jax.random.key(0), jnp.float32(1.0))
        assert len(out) == 4
        step_args = (tokens[:1], jnp.int32(12), jnp.int32(1), out[0])

        def one_step(p, *a):
            logits, after, _ = module.apply(
                {"params": p, "mixers": lm.mixer_operands(p)}, *a,
                all_logits=False)
            return after, lm.sample(logits[0], jax.random.key(0), a[1] + 1,
                                    jnp.float32(1.0))

        step_loops = jax.jit(one_step).lower(
            params, *step_args).as_text().count("stablehlo.while")
        decode = jax.jit(lm.decode_chunk_fn(module, STEPS))
        decode_args = (params, out[0], out[1], jnp.int32(12),
                       jax.random.key(0), jnp.float32(1.0))
        decode_text = decode.lower(*decode_args).as_text()
        assert decode_text.count("stablehlo.while") == 1 + step_loops
        assert len(decode(*decode_args)) == 6
        if lm.shares_a_step(cfg):
            many = jax.jit(lm.decode_sequences_fn(module, STEPS))
            keys = jax.random.split(jax.random.key(3), 2)
            outs = many(params, kv.fork(out[0], 2),
                        jnp.stack([out[1]] * 2), jnp.int32(12), keys,
                        jnp.float32(1.0), jnp.int32(2))
            assert len(outs) == 7
            assert [x.ndim for x in outs[0]["k"]] == [4] * len(outs[0]["k"])

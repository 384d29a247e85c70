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
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.obs import prometheus
from stable_diffusion_webui_distributed_tpu.parallel import sharding
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER,
)
from tests import expander_contract as contract
from tests.expander_contract import CAPACITY, STEPS, count, rel_rms

REF = contract.load_reference("ouro")
#: the norms off 1 and the gate's bias off 0, so that reading one norm as
#: another, or no bias, would show
CASE = contract.Case(
    configs.TINY_LOOP_EXPAND, REF,
    how=(("spread", (("scale", 0.2),)), ("shift", (("bias", 0.25),))),
    extra="with_gates")
FAMILY, CFG = CASE.family, CASE.cfg
PASSES = CFG.total_ut_steps
params, engine = contract.fixtures(CASE)


@functools.lru_cache(maxsize=None)
def with_threshold(threshold):
    """The model leaving at the first pass whose gates add up to
    ``threshold``."""
    return dataclasses.replace(CASE, family=dataclasses.replace(
        FAMILY, expander=dataclasses.replace(
            CFG, early_exit_threshold=threshold)))


@functools.lru_cache(maxsize=None)
def reference_with(**how):
    return jax.jit(lambda p, i, c: REF.forward(FAMILY, p, i, c, **how))


def prefilled(params):
    """One chunk of 21 positions, none padded."""
    return contract.prefilled(CFG, params, 21, prefix=0, capacity=256,
                              bucket=21)


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
        inputs, want, lam, own = CASE.referred(size)
        got, gates, chose = CASE.program(with_gates=True)(params, *inputs)
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
        inputs, want, *_ = CASE.referred(48)
        side, kwargs = dict(REF.CONTROLS)[control]
        lower = (CASE.program if side == "program" else reference_with)(
            **kwargs)(params, *inputs)
        assert rel_rms(lower, want) > 1e-2

    @pytest.mark.parametrize("threshold", [0.5, 0.9, 0.99])
    def test_a_threshold_below_one_moves_the_pass_read_in_both(
            self, params, threshold):
        """Program and reference leave at the same pass, row by row, and
        read the same logits; every pass still ran (the later rows'
        logits need each pass's keys of the earlier ones)."""
        early = with_threshold(threshold)
        inputs, want, lam, own = early.referred(48)
        got, gates, chose = early.program(with_gates=True)(params, *inputs)
        assert np.array_equal(chose, own)
        assert len(set(np.asarray(own).tolist())) > 1
        assert np.any(np.asarray(own) < PASSES - 1)
        assert rel_rms(got, want) < 1e-5
        np.testing.assert_allclose(gates, lam, atol=1e-5)
        # the gates do not depend on the threshold; what is read does
        at_one = CASE.program()(params, *inputs)
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

class TestSequencesOfOneStep(contract.SequencesOfOneStep):
    """The looped stack at the chunk bucket's edges: every pass reads its
    own rows of the shared buffers and of a sequence's own."""
    CASE = CASE
    PARAMETERS = {"test_a_forked_decode_is_each_sequence_alone": [
        ("live,batch", [(2, 2), (4, 4), (3, 4)]),
        ("user", [1, 16, 63, 64])]}

    @pytest.mark.parametrize("live,batch", [(1, 1), (2, 2), (4, 4), (3, 4)])
    def test_each_sequence_gets_what_it_gets_alone(self, params, live,
                                                   batch):
        """From one chunk of 21 positions, none padded, and with a
        buffer's own count of slots a sequence: the same tokens, the same
        rows of every pass in the cache, and the pad left out of the
        exits."""
        ((counts, most),), own = contract.forked_against_alone(
            CASE, params, live, batch, own_slots=0, user=21, prefix=0,
            capacity=256, bucket=21)
        assert counts.tolist() == [0] * (PASSES - 1) + [STEPS * live]
        assert [c.tolist() for (c, _), in own] \
            == [[0] * (PASSES - 1) + [STEPS]] * live
        assert float(most) == pytest.approx(
            max(float(m) for (_, m), in own), rel=1e-5)

    def test_every_pass_writes_rows_of_its_own(self, params):
        """After a prefill every pass's rows of the written positions are
        filled and differ from pass to pass; the rest stay zero."""
        _, cache, length = prefilled(params)
        for rows in cache["k"] + cache["v"]:
            assert rows.shape == (PASSES, 256, 4, 16)
            assert np.all(np.asarray(rows[:, length:]) == 0)
            for t in range(PASSES):
                assert np.all(np.any(np.asarray(rows[t, :length]) != 0,
                                     axis=(1, 2)))
            assert rel_rms(rows[1, :length], rows[0, :length]) > 0.05

    test_a_fork_copies_no_pass = contract.SequencesOfOneStep \
        .a_fork_shares_what_has_positions_and_copies_the_rest

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
    CASE = CASE

    test_a_kept_prefix_restores_every_pass = \
        contract.StatesOfOneStep.a_snapshot_restores_every_buffer

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


class TestTheEnginePath(contract.ForkedEnginePath):
    CASE, KEYS = CASE, None

    def test_every_image_its_own_expansion(self, engine):
        whole = engine.txt2img(CASE.payload(batch_size=4))
        assert len(set(whole.prompts)) == 4
        for i in (0, 3):
            solo = engine.txt2img(CASE.payload(seed=1234 + i))
            assert solo.prompts[0] == whole.prompts[i], i
        again = engine.txt2img(CASE.payload(batch_size=4))
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

    test_counters_and_spans_of_the_passes = contract.ForkedEnginePath \
        .a_batch_prefills_once_forks_and_decodes_four_a_step

    def check_counted(self, stats, sizes, one):
        # every decode step ran every pass
        assert stats["layer_passes"] == PASSES * 2 * STEPS
        # the prompt's one row and every step's four, all at the last pass
        assert stats["exit_pass"] == [0] * (PASSES - 1) + [1 + 4 * 2 * STEPS]
        assert 0.5 < stats["exit_lambda_max"] < 0.9999
        assert stats["experts_read"] == 0 and stats["expert_tokens"] == []
        assert stats["cache_positions"] == {
            "full": 4 * (36 + 4 * 40) * PASSES, "sliding": 0}
        steps = range(36, 36 + 2 * STEPS)
        assert stats["rows_attended"] == sum(4 * (p + 1) for p in steps)
        assert stats["rows_read"] == sum(36 + 4 * (p + 1 - 36)
                                         for p in steps)
        row = PASSES * 2 * 4 * 16 * 4       # a layer's slot, float32
        assert sizes["full"] == 4 * (CAPACITY + 4 * 2 * STEPS) * row
        text = prometheus.render()
        assert f"sdtpu_expander_layer_passes_total {PASSES * 2 * STEPS}" \
            in text
        assert f'sdtpu_expander_exit_pass_total{{pass="{PASSES}"}} ' \
            f"{1 + 4 * 2 * STEPS}" in text
        assert "sdtpu_expander_exit_lambda_max 0." in text

    def check_spans(self, by_name, sizes, one):
        (prefill,) = by_name["expand.prefill"]
        (fork,) = by_name["expand.fork"]
        chunks = by_name["expand.decode_chunk"]
        assert [a["passes"] for a in [prefill, fork] + chunks] \
            == [PASSES] * 4
        # the bytes a fork makes: the sequences' own rows alone
        assert fork["bytes"] == 4 * 4 * 2 * STEPS * PASSES * 2 * 4 * 16 * 4

    def test_a_site_carries_its_passes(self):
        """A looped stack's layers are alike and share ONE trace of a
        layer an executable: a fresh engine's four-image request records
        one site for each of its two prefill executables and one for the
        decode scan, marked with the passes."""
        fresh = CASE.engine()
        ATTENTION.clear()
        fresh.txt2img(CASE.payload(batch_size=4))
        sites = ATTENTION.summary()["by_shape"]
        assert sites[f"T64 S{CAPACITY} D16 P{PASSES}"] == {"xla": 2}
        # a forked step's keys: the shared buffer and a sequence's own
        assert sites[f"T1 S{CAPACITY + 2 * STEPS} D16 P{PASSES}"] \
            == {"xla": 1}
        ATTENTION.clear()

    def test_a_threshold_below_one_counts_the_earlier_passes(self):
        early = CASE.engine(with_threshold(0.9).family)
        EXPANDER.clear()
        early.txt2img(CASE.payload(batch_size=4))
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

class TestThePublishedModel:
    def test_parameters_and_bytes_from_shapes(self):
        cfg = configs.sd15_ouro_expander().expander
        assert cfg is configs.OURO_2_6B
        assert cfg.layer_types == ("full",) * 48 and cfg.total_ut_steps == 4
        assert cfg.vocab == (0, 49152) and cfg.expert_layers == ()
        shapes = contract.param_shapes(cfg)
        layer = shapes["layers_0"]
        assert set(layer) == {"attn", "mlp", "input_norm", "input_norm_2",
                              "post_attention_norm",
                              "post_attention_norm_2"}
        assert set(layer["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
        assert count(layer["attn"]) == 4 * 2048 * 2048 == 16_777_216
        assert count(layer["mlp"]) == 3 * 2048 * 5632 == 34_603_008
        norms = 4 * 2048
        assert round((count(layer) - norms) / 1e6, 1) == 51.4
        assert count(shapes["embed_tokens"]) == count(shapes["lm_head"]) \
            == 100_663_296
        assert count(shapes["early_exit_gate"]) == 2049
        total = count(shapes)
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
        cache = contract.forked_structs(cfg, 512, 4, 64)
        # a layer: four passes of 512 shared rows, and every sequence's
        # four passes of 64 of its own
        assert len(cache["k"]) == len(cache["v_shared"]) == 48
        assert cache["k_shared"][0].shape == (4, 512, 16, 128)
        assert cache["k"][0].shape == (4, 4, 64, 16, 128)
        shapes = {"params": contract.param_shapes(cfg)}
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


class OnePass(NamedTuple):
    """What a preset of one pass runs, jitted once a config."""
    served: object
    by_hand: object
    prefill: object
    one_step: object
    decode: object
    many: object


@functools.lru_cache(maxsize=None)
def one_pass(cfg):
    module = lm.DecoderLM(cfg)

    def one_step(p, *a):
        logits, after, _ = module.apply(
            {"params": p, "mixers": lm.mixer_operands(p)}, *a,
            all_logits=False)
        return after, lm.sample(logits[0], jax.random.key(0), a[1] + 1,
                                jnp.float32(1.0))

    return OnePass(
        jax.jit(lambda p, *a: module.apply({"params": p}, *a)[:2]),
        jax.jit(lambda p, *a: layers_one_after_the_other(cfg, p, *a)),
        jax.jit(lm.prefill_fn(module)), jax.jit(one_step),
        jax.jit(lm.decode_chunk_fn(module, STEPS)),
        jax.jit(lm.decode_sequences_fn(module, STEPS)))


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
        fns = one_pass(cfg)
        cache = lm.empty_cache(cfg, 64, jnp.float32)
        tokens = jax.random.randint(jax.random.key(2), (12,), 0, 256)
        params = contract.lm_params(cfg, 1)
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
        logits, after = fns.served(params, *args)
        want, want_after = fns.by_hand(params, *args)
        assert np.array_equal(np.asarray(logits), np.asarray(want))
        for name in after:
            for mine, theirs in zip(after[name], want_after[name]):
                assert mine.shape == theirs.shape
                assert np.array_equal(np.asarray(mine), np.asarray(theirs))
        loops = fns.by_hand.lower(params, *args).as_text().count(
            "stablehlo.while")
        text = fns.served.lower(params, *args).as_text()
        assert text.count("stablehlo.while") == loops
        # nothing is sown, so nothing can be read of passes
        _, sown = jax.eval_shape(lambda p: lm.DecoderLM(cfg).apply(
            {"params": p}, *args, mutable=["passes"]), params)
        assert not sown
        # the executables return what they always did, and the decode
        # scan is the one loop around a step's own
        out = fns.prefill(params, cache, tokens, jnp.int32(0), jnp.int32(12),
                          jax.random.key(0), jnp.float32(1.0))
        assert len(out) == 4
        step_loops = fns.one_step.lower(
            params, tokens[:1], jnp.int32(12), jnp.int32(1),
            out[0]).as_text().count("stablehlo.while")
        decode_args = (params, out[0], out[1], jnp.int32(12),
                       jax.random.key(0), jnp.float32(1.0))
        decode_text = fns.decode.lower(*decode_args).as_text()
        assert decode_text.count("stablehlo.while") == 1 + step_loops
        assert len(fns.decode(*decode_args)) == 6
        if lm.shares_a_step(cfg):
            keys = jax.random.split(jax.random.key(3), 2)
            outs = fns.many(params, kv.fork(out[0], 2),
                            jnp.stack([out[1]] * 2), jnp.int32(12), keys,
                            jnp.float32(1.0), jnp.int32(2))
            # behind the experts read, the steps that streamed none
            assert len(outs) == 7 + bool(cfg.expert_layers)
            assert [x.ndim for x in outs[0]["k"]] == [4] * len(outs[0]["k"])

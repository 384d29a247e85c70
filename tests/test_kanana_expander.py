"""The resident prompt expander whose block is plain latent attention (no
query latent, interleaved rotary pairs, one stream) over experts that are
all held (a biased sigmoid router, a shared expert of twice the routed
width, one leading dense layer), at the shape that makes such a model
work: the images of one request decoded as sequences of ONE step over ONE
shared latent cache, forked from one prefill.

Everything runs the tiny preset (models/configs.py ``TINY_KANANA_LM``: a
cached latent of 16 + a rotated key of 8 under 4 heads, 16 experts top-4).
The plain reference is the benchmark's own (benchmarks/reference/
kanana2_ref.py: float32, one sequence, no cache, the expanded attention
only, transformers' de-interleave and ``rotate_half``).
"""

import functools
import hashlib
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
from tests.test_pipeline import init_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _load(os.path.join(ROOT, "benchmarks", "reference", "kanana2_ref.py"),
            "kanana2_ref_for_tests")
FAMILY = configs.TINY_KANANA_EXPAND
CFG = FAMILY.expander
STEPS = expand.DECODE_STEPS


def lm_params(cfg, seed=0):
    """``DecoderLM.init``'s tree with the norms off 1 and the selection
    bias off 0 (deviation 0.1, as the benchmark seeds it), so that reading
    one norm as another or leaving the bias out would show."""
    params = lm.DecoderLM(cfg).init(
        jax.random.key(seed), jnp.zeros((4,), jnp.int32), jnp.int32(0),
        jnp.int32(4), lm.empty_cache(cfg, 8, jnp.float32))["params"]
    key = jax.random.key(seed + 100)

    def off(path, x):
        name = getattr(path[-1], "key", "")
        if name not in ("scale", "e_score_correction_bias"):
            return x
        noise = jax.random.normal(
            jax.random.fold_in(key, zlib.crc32(str(path).encode()) % 2 ** 31),
            x.shape)
        return x + 0.2 * noise if name == "scale" else 0.1 * noise

    return jax.tree_util.tree_map_with_path(off, params)


@pytest.fixture(scope="module")
def params():
    return lm_params(CFG)


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


@functools.lru_cache(maxsize=None)
def _reference(size):
    """(ids, continuations, the reference's logits, its routing) at
    ``size`` positions, the tiny preset's seeded weights."""
    ids, continuations = REF.inputs(FAMILY, 3, size)
    want, own = jax.jit(lambda p, i, c: REF.forward(
        FAMILY, p, i, c, with_routing=True))(lm_params(CFG), ids,
                                             continuations)
    return ids, continuations, want, own


# -- (a) program against reference --------------------------------------------

class TestAgainstTheReference:
    @pytest.mark.parametrize("size", [37, 148])
    def test_prefill_fork_and_decode_match_four_full_forwards(self, params,
                                                              size):
        """The prefix as one chunk (expanded form), a copy, the prompt's
        chunk, a fork into four and one step over all four a position (the
        forked absorbed form), against a full forward of each whole
        sequence: logits to 1e-5, routing identical."""
        prefix, user, decoded = REF.split(size)
        ids, continuations, want, own = _reference(size)
        got, chose = jax.jit(REF.program(FAMILY, dtypes.F32,
                                         with_routing=True))(
            params, ids, continuations)
        rows = prefix + user + REF.SEQUENCES * decoded
        assert got.shape == want.shape == (rows, CFG.vocab[1])
        assert got.dtype == want.dtype == jnp.float32
        assert rel_rms(got, want) < 1e-5
        assert chose.shape == (len(CFG.expert_layers), rows,
                               CFG.num_experts_per_tok)
        assert np.array_equal(np.sort(chose, -1), np.sort(own, -1))
        # the four continuations part at their first row
        tails = np.asarray(got[prefix + user:]).reshape(
            REF.SEQUENCES, decoded, -1)
        assert rel_rms(tails[1], tails[0]) > 0.1

    def test_the_two_executables_give_what_the_one_gives(self, params):
        """The chip's readings may run the chunks with the fork and the
        decode steps as two executables, as the timed path does."""
        ids, continuations = REF.inputs(FAMILY, 3, 37)
        whole, chose = jax.jit(REF.program(FAMILY, dtypes.F32,
                                           with_routing=True))(
            params, ids, continuations)
        got, chose_staged = REF.staged(FAMILY, dtypes.F32, params, ids,
                                       continuations)
        np.testing.assert_allclose(got, whole, rtol=1e-6, atol=1e-6)
        assert np.array_equal(chose, chose_staged)

    @pytest.mark.parametrize("control", [name for name, _ in REF.CONTROLS])
    def test_each_control_is_further_from_the_reference(self, params,
                                                        control):
        """Int8 linears, ``rotate_half`` pairing in place of the
        interleaved one, the selection bias left out of the choice, a
        shared expert of the routed width, a sequence's own rows dropped,
        the shared range attended without the prompt's rows: each reads
        far from the reference where the program reads 1e-6."""
        ids, continuations, want, _ = _reference(74)
        lower = jax.jit(REF.program(
            FAMILY, dtypes.F32, **dict(REF.CONTROLS)[control]))(
                params, ids, continuations)
        assert rel_rms(lower, want) > 5e-2
        assert [name for name, _ in REF.CONTROLS] == [
            "control", "rotate_half", "no_selection_bias",
            "narrow_shared_expert", "own_rows_dropped",
            "shared_without_prompt"]

    def test_the_selection_bias_changes_most_choices(self, params):
        assert REF.bias_changes_share(CFG, params) > 0.3

    def test_interleaved_pairs_turn_in_place(self):
        """Dims (2i, 2i + 1) turn by ``pos * theta^(-2i/d)``: the program's
        rotation against complex multiplication, and its ``q . k`` against
        the reference's de-interleave followed by ``rotate_half``."""
        rope = CFG.rope_full
        assert rope.interleaved and not configs.RopeConfig().interleaved
        d = 8
        x = jax.random.normal(jax.random.key(0), (5, 3, d))
        y = jax.random.normal(jax.random.key(1), (5, 3, d))
        positions = jnp.asarray([0, 1, 7, 100, 3000])
        cos, sin = lm.rope_tables(rope, d, positions)
        got = np.asarray(lm.apply_rope(x, cos, sin, True))
        angle = np.asarray(positions, np.float64)[:, None] \
            * rope.theta ** (-np.arange(0, d, 2) / d)
        z = (np.asarray(x)[..., 0::2] + 1j * np.asarray(x)[..., 1::2]) \
            * np.exp(1j * angle)[:, None, :]
        np.testing.assert_allclose(got[..., 0::2], z.real, atol=2e-4)
        np.testing.assert_allclose(got[..., 1::2], z.imag, atol=2e-4)
        # the rotate_half pairing is another rotation
        assert rel_rms(lm.apply_rope(x, cos, sin), got) > 0.1
        # positions 0..4, where the reference's table starts
        cos, sin = lm.rope_tables(rope, d, jnp.arange(5))
        ours = jnp.einsum("thd,thd->th", lm.apply_rope(x, cos, sin, True),
                          lm.apply_rope(y, cos, sin, True))
        theirs = jnp.einsum("thd,thd->th", REF._rope(x, rope.theta),
                            REF._rope(y, rope.theta))
        np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-5)
        # a rest that is not rotated passes
        wide = jnp.concatenate([x, y], axis=-1)
        assert np.array_equal(
            np.asarray(lm.apply_rope(wide, cos, sin, True))[..., d:],
            np.asarray(y))


# -- (b) a step over B sequences ----------------------------------------------

def _keys(indices, seed=77):
    from stable_diffusion_webui_distributed_tpu.runtime import rng

    return jnp.stack([rng.key_for_image(seed, i) for i in indices])


@functools.lru_cache(maxsize=None)
def _executables(cfg):
    """(the one-sequence decode chunk, the several-sequences one, a step
    of each that returns its logits), jitted once a config."""
    module = lm.DecoderLM(cfg)

    def one_step(params, cache, token, position):
        return module.apply({"params": params}, token[None], position,
                            jnp.int32(1), cache)[:2]

    def forked_step(params, cache, tokens, position, live):
        return module.apply({"params": params}, tokens, position, live,
                            cache, sequences=True)[:2]

    return (jax.jit(lm.decode_chunk_fn(module, STEPS)),
            jax.jit(lm.decode_sequences_fn(module, STEPS)),
            jax.jit(one_step), jax.jit(forked_step))


def _prefilled(params, user, prefix=21, capacity=None):
    """(the prompt's last row of logits, the cache, its length) after a
    prefix's chunk and a prompt of ``user`` real tokens in its padded
    chunk: the bucket's other rows land behind the prompt in the latent
    buffer, where a forked step must not see them."""
    module = lm.DecoderLM(CFG)
    bucket = kv.chunk_bucket(user)
    capacity = capacity or kv.capacity_for(prefix + bucket + 2 * STEPS)
    ids = jax.random.randint(jax.random.key(user), (prefix + bucket,), 0,
                             512)
    _, cache, _ = module.apply(
        {"params": params}, ids[:prefix], jnp.int32(0), jnp.int32(prefix),
        lm.empty_cache(CFG, capacity, jnp.float32), all_logits=False)
    row, cache, _ = module.apply(
        {"params": params}, ids[prefix:], jnp.int32(prefix),
        jnp.int32(user), cache, all_logits=False)
    return row[0], cache, prefix + user


def assert_own_rows(alone, forked, b, first, steps):
    """Sequence ``b``'s own latent rows against the cache of that sequence
    decoded alone: position ``p`` lies in its own slot ``(p - first) %
    slots``, and alone in slot ``p``."""
    positions = np.arange(first, first + steps)
    for mine, theirs in zip(alone["latent"], forked["latent"]):
        np.testing.assert_allclose(
            np.asarray(mine)[positions],
            np.asarray(theirs[b])[(positions - first) % theirs.shape[1]],
            rtol=2e-5, atol=2e-5)


class TestSequencesOfOneStep:
    @pytest.mark.parametrize("user,live,batch", [
        (1, 4, 4), (64, 3, 4), (16, 2, 2)])
    def test_a_forked_decode_is_each_sequence_alone(self, params, user,
                                                    live, batch):
        """``batch`` sequences forked from one prefill against each of the
        ``live`` decoded alone from the same cache by the one-sequence
        executable: a chunk of steps token for token, the rows written,
        the load without the pad, and the logits of a few teacher-forced
        steps after it."""
        alone, together, one_step, forked_step = _executables(CFG)
        row, cache, length = _prefilled(params, user)
        keys = _keys(list(range(live)) + [live - 1] * (batch - live))
        first = lm.sample_each(row, keys, length, jnp.float32(1.0))
        forked, tokens, position, made, load, none_held, read = together(
            params, kv.fork(cache, batch, 2 * STEPS), first,
            jnp.int32(length), keys, jnp.float32(1.0), jnp.int32(live))
        assert int(position) == length + STEPS
        # the shared rows are the prefill's, untouched
        for mine, theirs in zip(cache["latent"], forked["latent_shared"]):
            assert np.array_equal(np.asarray(mine), np.asarray(theirs))
        assert int(forked[lm.FORKED_AT][0][0, 0]) == length
        own, total = [], 0
        for b in range(live):
            after, last, _, steps, own_load, _ = alone(
                params, cache, first[b], jnp.int32(length), keys[b],
                jnp.float32(1.0))
            assert np.array_equal(steps, made[:, b]), b
            assert int(last) == int(tokens[b])
            assert_own_rows(after, forked, b, length, STEPS)
            own.append(after)
            total = total + own_load
        assert np.array_equal(load, total)      # the pad is not counted
        assert int(none_held.sum()) == 0
        assert len({tuple(np.asarray(made[:, b])) for b in range(live)}) \
            == live
        # distinct experts a step: never over the picks, never under one
        # sequence's four a layer
        k = CFG.num_experts_per_tok
        assert np.all(read >= STEPS * k) and np.all(
            read <= STEPS * min(live * k, CFG.num_experts))
        forced = jax.random.randint(jax.random.key(8), (3, batch), 0, 512)
        for t, row in enumerate(forced):
            at = jnp.int32(length + STEPS + t)
            logits, forked = forked_step(params, forked, row, at,
                                         jnp.int32(live))
            for b in range(live):
                want, own[b] = one_step(params, own[b], row[b], at)
                np.testing.assert_allclose(logits[b], want[0], rtol=1e-5,
                                           atol=1e-5)

    def test_a_fork_copies_nothing(self, params):
        """The shared buffers ARE the prefill's; what is made is a few
        rows a sequence and the position, not yet known."""
        _, cache, _ = _prefilled(params, 5)
        forked = kv.fork(cache, 4, 2 * STEPS)
        assert set(forked) == {"latent", "latent_shared", "forked_at"}
        assert all(mine is theirs for mine, theirs
                   in zip(cache["latent"], forked["latent_shared"]))
        assert [x.shape for x in forked["latent_shared"]] == [(256, 24)] * 4
        assert [x.shape for x in forked["latent"]] == [(4, 64, 24)] * 4
        assert not any(np.any(np.asarray(x)) for x in forked["latent"])
        (at,) = forked["forked_at"]
        assert at.shape == (4, 1) and np.all(np.asarray(at) == -1)
        # what the engine's fork executable makes: the same, from shapes
        made = jax.jit(lambda c: kv.own_rows(c, 4, 2 * STEPS))(cache)
        again = kv.forked(cache, made)
        assert jax.tree_util.tree_structure(again) \
            == jax.tree_util.tree_structure(forked)
        assert all(mine is theirs for mine, theirs
                   in zip(cache["latent"], again["latent_shared"]))
        # without a count of slots a sequence gets a buffer's own
        assert [x.shape for x in kv.fork(cache, 2)["latent"]] \
            == [(2, 256, 24)] * 4
        assert lm.buffers_of(lm.LATENT) == ("latent",)
        assert lm.buffers_of(lm.LATENT, forked=True) == ("latent",
                                                         "latent_shared")
        assert lm.slots_axis("latent") == -2 and lm.slots_axis("k") == -3

    @pytest.mark.parametrize("preset,shares", [
        ("TINY_KANANA_EXPAND", True), ("TINY_WINDOW_EXPAND", True),
        ("TINY_LOOP_EXPAND", True), ("TINY_LATENT_EXPAND", False),
        ("TINY_DELTA_EXPAND", True), ("TINY_CONV_EXPAND", False)])
    def test_which_kinds_share_a_step(self, preset, shares):
        """Latent layers of one stream do, and since PR 56 a recurrent
        state with a sequence axis; four streams and a conv layer's kept
        rows still decode one sequence a step."""
        cfg = getattr(configs, preset).expander
        assert lm.shares_a_step(cfg) is shares
        if not shares:
            with pytest.raises(ValueError):
                jax.eval_shape(
                    lambda: lm.DecoderLM(cfg).init(
                        jax.random.key(0), jnp.zeros((2,), jnp.int32),
                        jnp.int32(0), jnp.int32(2),
                        lm.empty_cache(cfg, 8, jnp.float32),
                        sequences=True))
        assert lm.shares_a_step(configs.sd15_kanana2_expander().expander)
        assert not lm.shares_a_step(configs.sd15_xing4_expander().expander)

    def test_bytes_and_positions_of_a_forked_latent_cache(self):
        manager = kv.KVCacheManager(CFG, jnp.bfloat16)
        assert manager.positions_in_use(40) == {"full": 0, "sliding": 0,
                                                "latent": 4 * 40}
        # four sequences forked at 30: what lies before it once, the 10
        # behind it once each
        assert manager.positions_in_use(40, 4, 30) == {
            "full": 0, "sliding": 0, "latent": 4 * (30 + 4 * 10)}
        row = 24 * 2                        # a slot's latent, bfloat16
        assert kv.state_bytes(CFG, 256, jnp.bfloat16) == {
            "full": 0, "sliding": 0, "latent": 4 * 256 * row}
        # a forked group: every buffer once and 64 slots a sequence
        assert kv.state_bytes(CFG, 256, jnp.bfloat16, 4, 64) == {
            "full": 0, "sliding": 0, "latent": 4 * (256 + 4 * 64) * row}
        # the four-stream sibling's bytes are what they were
        other = configs.TINY_LATENT_EXPAND.expander
        assert kv.state_bytes(other, 256, jnp.float32)["latent"] \
            == 4 * 256 * 24 * 4

    def test_the_forms_by_rows_and_whose_they_are(self):
        assert lm.latent_form(64) == "latent_expanded"
        assert lm.latent_form(1) == "latent_absorbed"
        assert lm.latent_form(1, sequences=True) == "latent_forked"
        assert lm.latent_form(4, sequences=True) == "latent_forked"


# -- (c) the tree and its rules -----------------------------------------------

class TestTheTreeAndItsRules:
    def test_no_query_latent_makes_no_q_a_leaf(self, params):
        attn = params["layers_0"]["attn"]
        assert set(attn) == {"q_proj", "kv_a_proj_with_mqa", "kv_a_norm",
                             "kv_b_proj", "o_proj"}
        assert CFG.q_lora_rank == 0
        assert attn["q_proj"]["kernel"].shape == (32, 4 * 16)
        assert attn["kv_a_proj_with_mqa"]["kernel"].shape == (32, 16 + 8)
        assert attn["kv_b_proj"]["kernel"].shape == (16, 4 * 16)
        assert attn["o_proj"]["kernel"].shape == (4 * 8, 32)
        assert set(params["layers_0"]) == {"attn", "mlp", "input_norm",
                                           "post_attention_norm"}
        assert set(params["layers_0"]["mlp"]) == {"gate_proj", "up_proj",
                                                  "down_proj"}
        assert set(params["layers_1"]["mlp"]) == {
            "router", "e_score_correction_bias", "experts", "shared_expert"}
        shared = params["layers_1"]["mlp"]["shared_expert"]
        assert shared["gate_proj"]["kernel"].shape == (32, 2 * 16)
        # the sibling with a query latent keeps its three leaves
        other = configs.TINY_LATENT_EXPAND.expander
        shapes = jax.eval_shape(lambda: lm.DecoderLM(other).init(
            jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
            jnp.int32(4), lm.empty_cache(other, 8, jnp.float32)))["params"]
        assert {"q_a_proj", "q_a_norm", "q_b_proj"} \
            <= set(shapes["layers_0"]["attn"])
        assert "q_proj" not in shapes["layers_0"]["attn"]

    def test_sharding_rules(self, params):
        """``q_proj`` takes the rule every ``q_proj`` takes (its columns,
        whole heads, over ``tp``: the path does not say the layer's kind);
        what makes and reads the latent stays whole on every chip."""
        from jax.sharding import PartitionSpec as P

        from stable_diffusion_webui_distributed_tpu.parallel.sharding import (
            shard_params, tp_spec_for,
        )

        assert tp_spec_for("layers_0/attn/q_proj/kernel", 2) \
            == P(None, "tp")
        for path, ndim in (("layers_0/attn/kv_a_proj_with_mqa/kernel", 2),
                           ("layers_0/attn/kv_b_proj/kernel", 2),
                           ("layers_0/attn/kv_a_norm/scale", 1),
                           ("layers_1/mlp/e_score_correction_bias", 1)):
            assert tp_spec_for(path, ndim) == P(), path
        assert tp_spec_for("layers_1/mlp/experts/w_up", 3) \
            == P("ep", None, None)
        devices = np.array(jax.devices()[:4]).reshape(2, 2)
        mesh = jax.sharding.Mesh(devices, ("ep", "vp"))
        placed = shard_params(params, mesh)
        assert placed["layers_1"]["mlp"]["experts"]["w_gate"].sharding.spec \
            == P("ep", None, None)
        assert placed["layers_1"]["attn"]["q_proj"]["kernel"] \
            .sharding.spec == P()           # no tp axis on this mesh
        assert placed["layers_1"]["attn"]["kv_b_proj"]["kernel"] \
            .sharding.spec == P()
        assert placed["lm_head"]["kernel"].sharding.spec == P(None, "vp")


# -- (d) the engine's path ----------------------------------------------------

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
    return Engine(configs.tiny_kanana2_expander(), params, chunk_size=4,
                  state=GenerationState())


def payload(**kw):
    base = dict(prompt="a cow in a valley", steps=4, width=32, height=32,
                seed=1234, alwayson_scripts=script())
    base.update(kw)
    return GenerationPayload(**base)


CAPACITY = kv.capacity_for(31 + 64 + 2 * STEPS)


class TestEnginePath:
    def test_a_batch_prefills_once_forks_and_decodes_four_a_step(self,
                                                                 engine):
        """The spans, counters and Prometheus families of a four-image
        request from the kept snapshot; image ``i`` gets what a one-image
        request with seed ``s + i`` gets."""
        from stable_diffusion_webui_distributed_tpu.obs import spans

        assert engine.expander.shares_a_step
        ATTENTION.clear()
        whole = engine.txt2img(payload(batch_size=4))   # keeps the snapshot
        assert len(set(whole.prompts)) == 4
        keys = {k for k in engine.executable_keys()
                if k[0].startswith("expand")}
        assert keys == {("expand_prefill", 64, CAPACITY),
                        ("expand_prefill", 64, CAPACITY, 4),
                        ("expand_fork", CAPACITY, 4, 2 * STEPS),
                        ("expand_decode_chunk", STEPS, CAPACITY, 4),
                        # one dispatch each: the images' keys, a
                        # snapshot's copy
                        ("expand_keys", 4), ("expand_copy", CAPACITY)}
        sites = ATTENTION.summary()
        assert sites["latent_forked"] == 4 and sites["latent_expanded"] == 8
        assert "latent_absorbed" not in sites
        assert sites["by_shape"][f"T4 S{CAPACITY}+{2 * STEPS} D24"] \
            == {"latent_forked": 4}
        EXPANDER.clear()
        spans.TRACER.clear()
        with spans.request("rid-k2"):
            again = engine.txt2img(payload(batch_size=4))
        assert again.prompts == whole.prompts
        assert again.images == whole.images
        stats = METRICS.summary()["expander"]
        assert stats["requests"] == 1 and stats["sequences"] == 4
        assert stats["tokens_prefilled"] == 5       # the prompt, once
        assert stats["tokens_from_prefix_cache"] == 31
        assert stats["tokens_decoded"] == 4 * 40
        assert stats["decode_steps"] == 2 * STEPS
        assert stats["tokens_no_held_expert"] == 0
        picks = 2 * STEPS * 3 * 4       # steps x layers x k, one sequence
        assert picks <= stats["experts_read"] < 4 * picks
        assert stats["expert_products"]["kernel"] == 0      # a CPU
        # forked at 31 + 5: those positions once, the 40 behind them once
        # a sequence, in each of four layers
        assert stats["cache_positions"] == {
            "full": 0, "sliding": 0, "latent": 4 * (36 + 4 * 40)}
        steps = range(36, 36 + 2 * STEPS)
        assert stats["rows_attended"] == sum(4 * (p + 1) for p in steps)
        assert stats["rows_read"] == sum(36 + 4 * (p + 1 - 36)
                                         for p in steps)
        assert stats["rows_read_shared"] == 2 * STEPS * 36
        sizes = kv.state_bytes(CFG, CAPACITY, jnp.float32, 4, 2 * STEPS)
        assert stats["state_bytes"] == sizes
        events = [e for e in spans.TRACER.export_chrome()["traceEvents"]
                  if e.get("ph") == "X"]
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e["args"])
        assert [a["sequences"] for a in by_name["expand"]] == [4]
        (prefill,) = by_name["expand.prefill"]
        assert prefill["tokens"] == 5 and prefill["sequences"] == 4
        assert prefill["latent"] == "latent_expanded"
        (fork,) = by_name["expand.fork"]
        assert fork["sequences"] == 4 and fork["latent"] == "latent_forked"
        # the bytes a fork makes: four layers' own rows of 64 slots a
        # sequence, float32; the prefill's latents stay where they are
        assert fork["bytes"] == 4 * 4 * 2 * STEPS * 24 * 4 \
            == sum(sizes.values()) - sum(kv.state_bytes(
                CFG, CAPACITY, jnp.float32).values())
        assert [(a["sequences"], a["latent"])
                for a in by_name["expand.decode_chunk"]] \
            == [(4, "latent_forked")] * 2
        by_id = {e["args"]["span_id"]: e for e in events}
        for e in events:
            if e["name"].startswith("expand."):
                # the counters come down once the UNet is queued
                assert by_id[e["args"]["parent_id"]]["name"] == (
                    "denoise_range" if e["name"] == "expand.account"
                    else "expand")
        text = prometheus.render()
        assert "sdtpu_expander_rows_attended_total " \
            f"{stats['rows_attended']}" in text
        assert 'sdtpu_expander_rows_read_total{range="shared"} ' \
            f"{2 * STEPS * 36}" in text
        assert 'sdtpu_expander_rows_read_total{range="own"} ' \
            f"{stats['rows_read'] - 2 * STEPS * 36}" in text

    def test_every_image_its_own_expansion_and_one_image_the_old_path(
            self, engine):
        whole = engine.txt2img(payload(batch_size=4))
        ATTENTION.clear()
        EXPANDER.clear()
        for i in (0, 3):
            solo = engine.txt2img(payload(seed=1234 + i))
            assert solo.prompts[0] == whole.prompts[i], i
        part = engine.generate_range(payload(batch_size=4), 2, 2)
        assert part.prompts == whole.prompts[2:]
        # one image: the one-sequence executable, the absorbed form, a
        # decode span that names it
        assert ("expand_decode_chunk", STEPS, CAPACITY) \
            in set(engine.executable_keys())
        assert ATTENTION.summary()["latent_absorbed"] == 4
        stats = EXPANDER.summary()
        assert stats["rows_attended"] - stats["rows_read"] \
            == 2 * STEPS * 36       # the pair's shared rows, once saved
        ATTENTION.clear()


# -- (e) the published share, from shapes -------------------------------------

def _published_shapes(share):
    return jax.eval_shape(lambda: lm.DecoderLM(share).init(
        jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
        jnp.int32(4), lm.empty_cache(share, 8, jnp.float32)))["params"]


class TestThePublishedShare:
    def test_parameters_and_bytes_from_shapes(self):
        share = configs.sd15_kanana2_expander().expander
        whole = configs.KANANA_2_30B_A3B
        assert share.layer_types == ("latent",) * 8
        assert whole.num_layers == 48 and whole.dense_layers == (0,)
        assert share.experts == (0, 128) and share.vocab == (0, 128256)
        assert whole.shared_expert_intermediate_size \
            == 2 * whole.moe_intermediate_size == 1536
        assert whole.latent_softmax_scale == 192 ** -0.5    # no mscale
        assert whole.rope_full.interleaved and not whole.rope_full.factor
        assert (whole.routed_scaling_factor, whole.norm_topk_eps) \
            == (2.448, 1e-20)
        shapes = _published_shapes(share)

        def count(tree):
            return sum(int(np.prod(x.shape))
                       for x in jax.tree_util.tree_leaves(tree))

        layer = shapes["layers_1"]
        attn = {name: count(leaf) for name, leaf in layer["attn"].items()}
        assert attn == {"q_proj": 12_582_912, "kv_a_proj_with_mqa": 1_179_648,
                        "kv_a_norm": 512, "kv_b_proj": 4_194_304,
                        "o_proj": 8_388_608}
        assert round(sum(attn.values()) / 1e6, 2) == 26.35
        assert count(layer["mlp"]["experts"]) == 128 * 4_718_592
        assert round(128 * 4.718592, 1) == 604.0
        assert count(layer["mlp"]["shared_expert"]) == 9_437_184
        assert layer["mlp"]["router"].shape == (2048, 128)
        assert count(layer) == 640_029_312
        assert count(shapes["layers_0"]) == 64_098_816
        assert count(shapes["layers_0"]["mlp"]) == 3 * 2048 * 6144
        assert count(shapes["embed_tokens"]) == count(shapes["lm_head"]) \
            == 262_668_288
        total = count(shapes)
        assert total == 64_098_816 + 7 * 640_029_312 + 2 * 262_668_288 \
            + 2048
        assert round(total / 1e6) == 5070
        assert round(total * 2 / 1e9, 2) == 10.14
        # beside SD1.5's 1 066 M: 12.27 GB = 11.43 GiB
        assert round((total + 1066e6) * 2 / 1e9, 2) == 12.27
        assert round((total + 1066e6) * 2 / 2 ** 30, 2) == 11.43
        # ISSUE 52's fallback, layers 0-6: 4 430 M
        assert round((total - 640_029_312) / 1e6) == 4430
        # the whole model: 30.7 B, 3 B of them a token's
        assert round((64_098_816 + 47 * 640_029_312 + 2 * 262_668_288)
                     / 1e9, 1) == 30.7
        # the caches of four forked sequences at the cell's capacity
        capacity = kv.capacity_for(2048 + 64 + 8 * STEPS)
        assert capacity == 2560
        assert kv.state_bytes(share, capacity, jnp.bfloat16, 4, 8 * STEPS) \
            == {"full": 0, "sliding": 0,
                "latent": 8 * (2560 + 4 * 256) * 576 * 2}

    def test_on_the_chip_a_forked_step_takes_the_kernel(self, monkeypatch):
        """One decode step of the share the cell runs, traced without
        weights or FLOPs with the choosers told they are on a TPU (nothing
        compiles; tests/test_chip_compile.py compiles it for a described
        v5e): seven expert layers through the pipelined kernel, eight
        forked latent sites over 2 560 shared and 256 own rows of 576."""
        share = configs.sd15_kanana2_expander().expander
        module = lm.DecoderLM(share, dtype=jnp.bfloat16)
        s = jax.ShapeDtypeStruct
        one = {name: [s(shape, jnp.bfloat16) for shape in rows]
               for name, rows in lm.cache_shapes(share, 2560).items()}
        cache = jax.eval_shape(lambda c: kv.fork(c, 4, 256), one)
        assert [x.shape for x in cache["latent_shared"]] \
            == [(2560, 576)] * 8
        assert [x.shape for x in cache["latent"]] == [(4, 256, 576)] * 8
        shapes = {"params": _published_shapes(share)}
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ATTENTION.clear()
        EXPANDER.clear()
        logits, after, routed = jax.eval_shape(
            lambda v, c: module.apply(v, jnp.zeros((4,), jnp.int32),
                                      jnp.int32(2200), jnp.int32(4), c,
                                      sequences=True), shapes, cache)
        assert logits.shape == (4, 128256)
        assert jax.tree_util.tree_map(lambda x: x.shape, after) \
            == jax.tree_util.tree_map(lambda x: x.shape, cache)
        assert routed[0].shape == (7, 4, 6) and routed[1].shape == (7, 128)
        assert EXPANDER.summary()["expert_products"] == {
            "kernel": 7, "loop": 0, "grouped": 0}
        assert ATTENTION.summary()["by_shape"] == {
            "T4 S2560+256 D576": {"latent_forked": 8}}
        # a prefill chunk keeps the grouped product and the expanded form
        jax.eval_shape(
            lambda v, c: module.apply(v, jnp.zeros((64,), jnp.int32),
                                      jnp.int32(2048), jnp.int32(64), c),
            shapes, one)
        assert EXPANDER.summary()["expert_products"]["grouped"] == 7
        assert ATTENTION.summary()["latent_expanded"] == 8
        ATTENTION.clear()
        EXPANDER.clear()


# -- (f) the executables the benchmark already runs ---------------------------

#: sha256 (first 16 hex digits) of the lowered text of the tiny presets'
#: expander executables at commit eed35c3 (PR 51), by :func:`lowered_texts`
PARENT = {
    "TINY_EXPAND": {
        "prefill": "bc2e7bd7d29e1c3a", "decode": "21684674f1ee90da",
        "prefill4": "ff959a44160abdee", "fork": "326e63be1749961c",
        "decode4": "4764711654ed217f",
    },
    "TINY_DELTA_EXPAND": {
        "prefill": "da95d3f5fde87d89", "decode": "f874bd6fc3fd9312",
        # new at PR 56, when a recurrent state got a sequence axis and the
        # preset began to share a step: this PR's own, no parent's
        "prefill4": "a473ed63136570d9", "fork": "0cdae66e2a7352fe",
        "decode4": "17ee34382fce3ba0",
    },
    "TINY_LATENT_EXPAND": {
        "prefill": "a0a2d734b4c65d95", "decode": "e9c357475afd73d4",
    },
    "TINY_CONV_EXPAND": {
        "prefill": "c56a134d182e06b7", "decode": "71408dd293f37716",
    },
    "TINY_WINDOW_EXPAND": {
        "prefill": "6e701a11514cb72f", "decode": "7b5c4d4699af4ff2",
        "prefill4": "da63a5472b21b1d0", "fork": "fb52110920b84f15",
        "decode4": "f64bb28587c62d63",
    },
    "TINY_LOOP_EXPAND": {
        "prefill": "ca81e0aa2715817e", "decode": "543a1489c79eee98",
        "prefill4": "429d4c7d764a7f0c", "fork": "3e46abbd36be45ca",
        "decode4": "4c0eb8c4d9f46fb5",
    },
}


def lowered_texts(preset, capacity=256, sequences=4, own_slots=64):
    """The lowered text of every expander executable of a tiny preset, as
    pipeline/expand.py builds them: the one-sequence prefill and decode
    chunk and, where the preset shares a step, the prefill that draws
    ``sequences`` first tokens, the fork and the forked decode chunk."""
    cfg = getattr(configs, preset).expander
    module = lm.DecoderLM(cfg)
    s = jax.ShapeDtypeStruct
    cache = {name: [s(shape, lm.buffer_dtype(name, jnp.float32))
                    for shape in rows]
             for name, rows in lm.cache_shapes(cfg, capacity).items()}
    params = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
        jnp.int32(4), lm.empty_cache(cfg, 8, jnp.float32)))["params"]
    scalar, heat = s((), jnp.int32), s((), jnp.float32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    keys = jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), sequences))
    chunk = s((64,), jnp.int32)
    out = {
        "prefill": jax.jit(lm.prefill_fn(module), donate_argnums=(1,)).lower(
            params, cache, chunk, scalar, scalar, key, heat).as_text(),
        "decode": jax.jit(lm.decode_chunk_fn(module, STEPS),
                          donate_argnums=(1,)).lower(
            params, cache, scalar, scalar, key, heat).as_text(),
    }
    if lm.shares_a_step(cfg):
        out["prefill4"] = jax.jit(
            lm.prefill_fn(module, sequences=True), donate_argnums=(1,)).lower(
                params, cache, chunk, scalar, scalar, keys, heat).as_text()
        out["fork"] = jax.jit(functools.partial(
            kv.own_rows, sequences=sequences, slots=own_slots)).lower(
                cache).as_text()
        forked = jax.eval_shape(
            lambda c: kv.fork(c, sequences, own_slots), cache)
        out["decode4"] = jax.jit(
            lm.decode_sequences_fn(module, STEPS), donate_argnums=(1,)).lower(
                params, forked, s((sequences,), jnp.int32), scalar, keys,
                heat, scalar).as_text()
    return out


@pytest.mark.parametrize("preset", sorted(PARENT))
def test_the_existing_presets_lower_to_the_parents_text(preset):
    """The expanded and the one-sequence absorbed forms, the rotate_half
    pairing, ``own_rows`` and ``fork`` stay as they were for every
    configuration the benchmark already runs: the lowered text of each
    executable of each tiny preset is the parent's, byte for byte. (A PR
    that means to change one of them replaces its hash, and says so.)"""
    got = {name: hashlib.sha256(text.encode()).hexdigest()[:16]
           for name, text in lowered_texts(preset).items()}
    assert got == PARENT[preset]

"""The resident prompt expander with linear-attention layers: a gated delta
rule over a recurrent state beside attention over cached keys and values,
in one stack, one cache manager and one decode scan.

Everything runs the tiny preset that keeps all three layer kinds
(models/configs.py ``TINY_DELTA_LM``: linear, linear, sliding, full; an
element-wise gate out of ``q_proj``, q/k norms, zero-centred norms, a gated
shared expert, 16 experts top-4 of which a chip of four holds 4), with
``A_log`` set so that the heads' memories run from a few tokens to the
whole context. The plain reference is the benchmark's own
(benchmarks/reference/qwen3next_ref.py: float32, no cache, no chunks, the
recurrence token by token).
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
from stable_diffusion_webui_distributed_tpu.ops import delta_rule
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


REF = _load(os.path.join(ROOT, "benchmarks", "reference",
                         "qwen3next_ref.py"), "qwen3next_ref_for_tests")
FAMILY = configs.TINY_DELTA_EXPAND
CFG = FAMILY.expander
#: decay rates exp(A_log) from 0.002 (remembers every test context) to 7
A_LOG = (-6.0, -3.0, 0.0, 2.0)


def lm_params(cfg, seed=0):
    module = lm.DecoderLM(cfg)
    cache = lm.empty_cache(cfg, 8, jnp.float32)
    params = module.init(jax.random.key(seed), jnp.zeros((4,), jnp.int32),
                         jnp.int32(0), jnp.int32(4), cache)["params"]
    key = jax.random.key(seed + 100)
    for layer in cfg.layers_of(lm.LINEAR):
        delta = params[f"layers_{layer}"]["delta"]
        delta["A_log"] = jnp.asarray(A_LOG, jnp.float32)
        # norms and biases off their initial values, so that a weight read
        # as ``scale`` where it is ``1 + weight`` would show
        delta["dt_bias"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, layer), (len(A_LOG),))
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.2 * jax.random.normal(
            jax.random.fold_in(key, zlib.crc32(str(path).encode()) % 2 ** 31),
            x.shape)
        if getattr(path[-1], "key", "") in ("weight", "scale") else x,
        params)


@pytest.fixture(scope="module")
def params():
    return lm_params(CFG)


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def run(params, ids, start, length, cache, **kw):
    return lm.DecoderLM(CFG).apply(
        {"params": params}, ids, jnp.int32(start), jnp.int32(length), cache,
        **kw)


def close(a, b, tol=2e-5):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(x, y, rtol=tol, atol=tol)


# -- the rule's two forms -----------------------------------------------------

def _operands(tokens, heads=4, k_dim=8, v_dim=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (tokens, heads, k_dim))) * k_dim ** -0.5
    k = unit(jax.random.normal(ks[1], (tokens, heads, k_dim)))
    v = jax.random.normal(ks[2], (tokens, heads, v_dim))
    g = -jnp.exp(jnp.asarray(A_LOG)) * jax.nn.softplus(
        jax.random.normal(ks[3], (tokens, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (tokens, heads)))
    state = jax.random.normal(ks[5], (heads, k_dim, v_dim))
    return state, q, k, v, g, beta


class TestTheDeltaRule:
    @pytest.mark.parametrize("tokens", [2, 19, 64, 150])
    def test_the_chunk_wise_form_equals_the_recurrence(self, tokens):
        """From a state that is not zero, over less than a chunk, one whole
        chunk and several with a ragged tail."""
        operands = _operands(tokens)
        want, want_state = delta_rule.recurrent(*operands)
        got, got_state = jax.jit(delta_rule.gated_delta_rule)(*operands)
        assert got.shape == (tokens, 4, 8)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got_state, want_state, rtol=2e-5,
                                   atol=2e-5)

    def test_one_token_takes_the_recurrent_step(self):
        operands = _operands(1)
        want, want_state = delta_rule.recurrent(*operands)
        got, got_state = delta_rule.gated_delta_rule(*operands)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(got_state, want_state, rtol=1e-6,
                                   atol=1e-6)
        assert (delta_rule.form(1), delta_rule.form(64)) \
            == ("recurrent", "chunked")

    def test_the_recurrence_is_the_written_one(self):
        """S <- exp(g) S; u = beta (v - S^T k); S <- S + k u^T; o = S^T q,
        in numpy, one head."""
        state, q, k, v, g, beta = (np.asarray(x, np.float64)
                                   for x in _operands(5, heads=4))
        got, got_state = delta_rule.recurrent(*_operands(5, heads=4))
        for h in range(4):
            s = state[h].copy()
            for t in range(5):
                s = np.exp(g[t, h]) * s
                u = beta[t, h] * (v[t, h] - s.T @ k[t, h])
                s = s + np.outer(k[t, h], u)
                np.testing.assert_allclose(got[t, h], s.T @ q[t, h],
                                           rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(got_state[h], s, rtol=1e-4,
                                       atol=1e-5)

    @pytest.mark.parametrize("tokens", [1, 40])
    def test_a_masked_row_leaves_the_state_alone(self, tokens):
        state, q, k, v, g, beta = _operands(tokens)
        _, after = delta_rule.gated_delta_rule(
            state, q, k, v, jnp.zeros_like(g), jnp.zeros_like(beta))
        np.testing.assert_allclose(after, state, rtol=1e-6, atol=1e-6)

    def test_a_slow_head_still_holds_the_first_write(self):
        """exp(A_log) = 0.002 and 0.05: after 40 tokens the first token's
        write is still there (what later writes along the same keys have
        not replaced, in a key space of 8 dims); at 1 and 7 it is gone."""
        state, q, k, v, g, beta = _operands(40)
        zero = jnp.zeros_like(state)
        _, with_first = delta_rule.gated_delta_rule(zero, q, k, v, g, beta)
        _, without = delta_rule.gated_delta_rule(
            zero, q, k, v, g, beta.at[0].set(0.0))
        left = jnp.linalg.norm(with_first - without, axis=(1, 2))
        assert float(left[0]) > 1e-2 and float(left[1]) > 1e-2
        assert float(left[2]) < 1e-6 and float(left[3]) < 1e-6


# -- program against reference ------------------------------------------------

class TestAgainstTheReference:
    @pytest.mark.parametrize("size", [40, 200])
    def test_prefill_then_decode_through_state_matches_the_full_forward(
            self, params, size):
        """Prefix prefill (chunk-wise), the user chunk against a copy of
        the snapshot, then one token a step through the recurrent state,
        the ring and the full buffer. At 200 the prefix is two chunks and
        the slowest head's memory spans every position."""
        (ids,) = REF.inputs(FAMILY, 3, size)
        got, chose = jax.jit(REF.program(FAMILY, dtypes.F32,
                                         with_routing=True))(params, ids)
        want, own = jax.jit(lambda p, i: REF.forward(
            FAMILY, p, i, with_routing=True))(params, ids)
        assert got.shape == want.shape == (size, CFG.vocab[1])
        assert rel_rms(got, want) < 1e-4
        assert np.array_equal(np.sort(chose, -1), np.sort(own, -1))

    def test_the_int8_control_is_further_from_the_reference(self, params):
        (ids,) = REF.inputs(FAMILY, 3, 40)
        want = REF.forward(FAMILY, params, ids)
        control = jax.jit(REF.program(FAMILY, dtypes.F32, control=True))(
            params, ids)
        assert rel_rms(control, want) > 1e-3

    def test_a_state_that_is_dropped_shows(self, params):
        """The tolerance means something: decoding from a zeroed recurrent
        state is far from the reference."""
        (ids,) = REF.inputs(FAMILY, 3, 40)
        want = REF.forward(FAMILY, params, ids)
        _, cache, _ = run(params, ids[:30], 0, 30,
                          lm.empty_cache(CFG, 64, jnp.float32))
        kept, _, _ = run(params, ids[30:31], 30, 1, cache)
        cache["state"] = [jnp.zeros_like(s) for s in cache["state"]]
        dropped, _, _ = run(params, ids[30:31], 30, 1, cache)
        assert rel_rms(kept, want[30:31]) < 1e-4
        assert rel_rms(dropped, want[30:31]) > 1e-2


# -- padding, snapshots, chunked decode ---------------------------------------

class TestPaddingAndSnapshots:
    def test_a_padded_chunk_gives_what_the_exact_chunk_gives(self, params):
        """Logits, recurrent state and the convolution's kept inputs: the
        pad rows neither decayed the state nor wrote to it, and the kept
        inputs are the last three REAL rows."""
        (ids,) = REF.inputs(FAMILY, 5, 24)
        empty = lambda: lm.empty_cache(CFG, 32, jnp.float32)   # noqa: E731
        exact, cache_a, _ = run(params, ids[:19], 0, 19, empty(),
                                all_logits=False)
        padded, cache_b, _ = run(params, ids, 0, 19, empty(),
                                 all_logits=False)
        np.testing.assert_allclose(exact, padded, rtol=2e-5, atol=2e-5)
        close(cache_a["state"], cache_b["state"])
        close(cache_a["conv"], cache_b["conv"])
        nxt = lambda c: run(params, ids[19:20], 19, 1, c,      # noqa: E731
                            all_logits=False)[0]
        np.testing.assert_allclose(nxt(cache_a), nxt(cache_b), rtol=2e-5,
                                   atol=2e-5)

    def test_a_chunk_shorter_than_the_taps_keeps_older_inputs(self, params):
        """Two real rows: the kept inputs are the last one from before the
        chunk and the chunk's two."""
        (ids,) = REF.inputs(FAMILY, 6, 12)
        _, cache, _ = run(params, ids[:10], 0, 10,
                          lm.empty_cache(CFG, 32, jnp.float32))
        before = cache["conv"][0]
        _, whole, _ = run(params, ids, 0, 12,
                          lm.empty_cache(CFG, 32, jnp.float32))
        _, after, _ = run(params, jnp.pad(ids[10:], (0, 6)), 10, 2, cache)
        np.testing.assert_allclose(after["conv"][0][0], before[-1],
                                   rtol=1e-6)
        close(after["conv"], whole["conv"])
        close(after["state"], whole["state"])

    def test_snapshot_plus_prompt_equals_one_whole_prefill(self, params):
        (ids,) = REF.inputs(FAMILY, 7, 48)
        whole, cache_w, _ = run(params, ids, 0, 48,
                                lm.empty_cache(CFG, 64, jnp.float32))
        first, snapshot, _ = run(params, ids[:31], 0, 31,
                                 lm.empty_cache(CFG, 64, jnp.float32))
        copy = jax.tree_util.tree_map(jnp.copy, snapshot)
        rest, cache_s, _ = run(params, ids[31:], 31, 17, copy)
        np.testing.assert_allclose(jnp.concatenate([first, rest]), whole,
                                   rtol=5e-5, atol=5e-5)
        close(cache_s["state"], cache_w["state"], 5e-5)
        close(cache_s["conv"], cache_w["conv"])
        # the snapshot itself is as the prefix's last token left it
        again, _, _ = run(params, ids[31:], 31, 17, snapshot)
        np.testing.assert_array_equal(again, rest)

    def test_decoding_cut_into_chunks_equals_one_scan(self, params):
        module = lm.DecoderLM(CFG)
        key = jax.random.key(11)
        first = jnp.int32(CFG.vocab[0] + 3)

        def decode(steps, calls):
            fn = jax.jit(lm.decode_chunk_fn(module, steps),
                         donate_argnums=(1,))
            cache = lm.empty_cache(CFG, 128, jnp.float32)
            token, position, made = first, jnp.int32(0), []
            for _ in range(calls):
                cache, token, position, out, _, _ = fn(
                    params, cache, token, position, key, jnp.float32(1.0))
                made += np.asarray(out).tolist()
            return made, cache

        one, cache_one = decode(64, 1)
        cut, cache_cut = decode(32, 2)
        assert one == cut and len(set(one)) > 8
        close(cache_one["state"], cache_cut["state"])
        close(cache_one["conv"], cache_cut["conv"])


# -- the cache manager --------------------------------------------------------

class TestTheCacheManager:
    def test_three_kinds_in_one_cache(self):
        shapes = lm.cache_shapes(CFG, 256)
        assert shapes == {
            "k": [(8, 2, 16), (256, 2, 16)], "v": [(8, 2, 16), (256, 2, 16)],
            "state": [(4, 8, 8)] * 2, "conv": [(3, 64)] * 2}
        cache = lm.empty_cache(CFG, 256, jnp.bfloat16)
        assert {x.dtype for x in cache["k"] + cache["v"]} \
            == {jnp.dtype(jnp.bfloat16)}
        assert {x.dtype for x in cache["state"] + cache["conv"]} \
            == {jnp.dtype(jnp.float32)}

    def test_a_model_without_linear_layers_has_the_cache_it_had(self):
        old = configs.TINY_EXPAND.expander
        assert set(lm.cache_shapes(old, 64)) == {"k", "v"}
        assert len(lm.cache_shapes(old, 64)["k"]) == old.num_layers
        assert kv.KVCacheManager(old, jnp.float32).positions_in_use(40) \
            == {"full": 80, "sliding": 16}

    def test_a_linear_layer_uses_no_positions_and_bytes_come_from_shapes(
            self):
        manager = kv.KVCacheManager(CFG, jnp.bfloat16)
        assert manager.positions_in_use(40) == {"full": 40, "sliding": 8,
                                                "linear": 0}
        assert kv.state_bytes(CFG, 256, jnp.bfloat16) == {
            "full": 2 * 256 * 2 * 16 * 2, "sliding": 2 * 8 * 2 * 16 * 2,
            "linear": 2 * (4 * 8 * 8 + 3 * 64) * 4}
        share = configs.sd15_qwen3next_expander().expander
        sizes = kv.state_bytes(share, 1024, jnp.bfloat16)
        assert sizes == {"full": 3 * 2 * 1024 * 2 * 256 * 2, "sliding": 0,
                         "linear": 9 * (32 * 128 * 128 + 3 * 8192) * 4}
        assert round(sum(sizes.values()) / 1e6, 1) == 26.1

    def test_a_snapshot_is_handed_out_as_a_copy_of_every_kind(self):
        manager = kv.KVCacheManager(CFG, jnp.float32)
        cache, held = manager.acquire([1, 2, 3], 256)
        assert held == 0 and manager.snapshots == 0
        manager.keep_prefix([1, 2, 3], 256,
                            jax.tree_util.tree_map(lambda x: x + 1, cache))
        again, held = manager.acquire([1, 2, 3], 256)
        assert held == 3 and manager.snapshots == 1
        assert float(again["state"][1][0, 0, 0]) == 1.0
        assert float(again["conv"][0][2, 5]) == 1.0
        # a shorter prefix is another prefix: a state cannot be cut back
        assert manager.acquire([1, 2], 256)[1] == 0


# -- the share ----------------------------------------------------------------

class TestTheShareOfALayer:
    @pytest.mark.parametrize("layer", [0, 3])
    def test_four_shares_and_what_every_chip_computes_alike_once(
            self, layer):
        """The reference's uncut layer (a linear one and a full one) against
        the sum of four chips' expert layers: each chip's routed part, and
        the mixer, the gated shared expert and the residual counted once."""
        whole = dataclasses.replace(CFG, experts_held=None, vocab_held=None)
        p = lm_params(whole, seed=4)[f"layers_{layer}"]
        x = jax.random.normal(jax.random.key(9), (20, whole.hidden_size))
        want, _ = REF.layer_forward(whole, layer, x, p)
        n = REF._norm(x, p["input_norm"], whole.rms_norm_eps)
        h = x + (REF._delta_mixer(whole, n, p["delta"]) if layer == 0
                 else REF._attention(whole, layer, n, p["attn"]))
        n = REF._norm(h, p["post_attention_norm"], whole.rms_norm_eps)
        shared = REF.shared_part(n, p["mlp"])
        total = h + shared
        parts = []
        for rank in range(4):
            share = configs.lm_share(whole, whole.num_layers, 4, rank)
            lo, count = share.experts
            assert count == 4
            mlp = dict(p["mlp"], experts={
                k: w[lo:lo + count] for k, w in p["mlp"]["experts"].items()})
            out, _ = lm.MoE(share, jnp.float32).apply(
                {"params": mlp}, n, jnp.ones(20, bool))
            parts.append(out - shared)
        assert not np.allclose(parts[0], sum(parts), atol=1e-3)
        np.testing.assert_allclose(total + sum(parts), want, rtol=2e-4,
                                   atol=2e-5)

    def test_the_share_of_the_published_model(self):
        share = configs.sd15_qwen3next_expander().expander
        assert share.num_layers == 12
        assert share.layer_types == ("linear", "linear", "linear",
                                     "full") * 3
        assert share.experts == (0, 128) and share.vocab == (0, 37984)
        assert share.num_experts == 512 and share.num_experts_per_tok == 10
        assert share.linear_conv_channels == 8192
        assert not share.dense_layers
        last = configs.lm_share(configs.QWEN3_NEXT_80B_A3B, 12, 4, 3)
        assert last.experts == (384, 128) and last.vocab == (113952, 37984)

    def test_the_share_has_5423_million_parameters(self):
        """Shapes only: nine linear layers at 37.92 M outside their routed
        experts, three full at 31.46 M, 402.65 M of held experts a layer,
        the table and the head."""
        share = configs.sd15_qwen3next_expander().expander
        shapes = jax.eval_shape(
            lambda: lm.DecoderLM(share).init(
                jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
                jnp.int32(4), lm.empty_cache(share, 8, jnp.float32)))
        count = lambda tree: sum(    # noqa: E731
            x.size for x in jax.tree_util.tree_leaves(tree))
        layers = shapes["params"]
        held = count(layers["layers_0"]["mlp"]["experts"])
        assert held == 128 * 3 * 2048 * 512
        assert round((count(layers["layers_0"]) - held) / 1e6, 2) == 37.92
        assert round((count(layers["layers_3"]) - held) / 1e6, 2) == 31.46
        assert round(count(layers) / 1e6) == 5423


# -- the old model, the sharding rules ----------------------------------------

class TestWhatStaysAsItWas:
    def test_the_defaults_are_the_ungated_unnormed_forms(self):
        for cfg in (configs.LAGUNA_S_2_1, configs.TINY_LM):
            assert (cfg.attn_gate, cfg.qk_norm, cfg.zero_centred_norm,
                    cfg.shared_expert_gate) == ("head", False, False, False)
            assert lm.LINEAR not in cfg.layer_types

    def test_the_old_presets_parameter_tree(self):
        """TINY_LM builds the leaves it built: per-head ``g_proj``, norms
        named ``scale``, no gate on the shared expert, no q/k norm."""
        old = configs.TINY_EXPAND.expander
        tree = jax.eval_shape(lambda: lm.DecoderLM(old).init(
            jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
            jnp.int32(4), lm.empty_cache(old, 8, jnp.float32)))["params"]
        assert set(tree["layers_1"]["attn"]) == {
            "q_proj", "k_proj", "v_proj", "g_proj", "o_proj"}
        assert tree["layers_1"]["attn"]["q_proj"]["kernel"].shape \
            == (32, 6 * 16)
        assert set(tree["layers_1"]["mlp"]) == {"router", "experts",
                                                "shared_expert"}
        assert set(tree["layers_1"]["input_norm"]) == {"scale"}
        assert set(tree["norm"]) == {"scale"}

    def test_the_new_presets_parameter_tree(self, params):
        delta = params["layers_0"]["delta"]
        assert set(delta) == {"qkvz_proj", "ba_proj", "conv_kernel", "A_log",
                              "dt_bias", "norm", "out_proj"}
        assert delta["qkvz_proj"]["kernel"].shape == (32, 64 + 32)
        assert delta["conv_kernel"].shape == (4, 64)
        assert set(delta["norm"]) == {"scale"}          # the plain weight
        attn = params["layers_3"]["attn"]
        assert set(attn) == {"q_proj", "k_proj", "v_proj", "o_proj",
                             "q_norm", "k_norm"}
        assert attn["q_proj"]["kernel"].shape == (32, 4 * 2 * 16)
        assert set(attn["q_norm"]) == {"weight"}
        assert "shared_expert_gate" in params["layers_0"]["mlp"]

    def test_sharding_leaves_the_mixer_whole(self, params):
        from jax.sharding import PartitionSpec as P

        from stable_diffusion_webui_distributed_tpu.parallel.sharding import (
            shard_params, tp_spec_for,
        )

        for path, ndim in (("layers_0/delta/qkvz_proj/kernel", 2),
                           ("layers_0/delta/out_proj/kernel", 2),
                           ("layers_0/delta/conv_kernel", 2),
                           ("layers_0/delta/A_log", 1),
                           ("layers_0/mlp/shared_expert_gate/kernel", 2)):
            assert tp_spec_for(path, ndim) == P(), path
        assert tp_spec_for("layers_0/mlp/experts/w_up", 3) \
            == P("ep", None, None)
        devices = np.array(jax.devices()[:4]).reshape(2, 2)
        mesh = jax.sharding.Mesh(devices, ("ep", "vp"))
        placed = shard_params(params, mesh)
        assert placed["layers_0"]["mlp"]["experts"]["w_gate"].sharding.spec \
            == P("ep", None, None)
        assert placed["layers_1"]["delta"]["qkvz_proj"]["kernel"] \
            .sharding.spec == P()
        assert placed["lm_head"]["kernel"].sharding.spec == P(None, "vp")


# -- the engine path ----------------------------------------------------------

INSTRUCTION = " ".join(f"rule{i}" for i in range(30))


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


class TestEnginePath:
    def test_a_request_from_the_kept_snapshot_equals_the_first(self, engine):
        EXPANDER.clear()
        a = engine.txt2img(payload())       # prefills the instruction
        b = engine.txt2img(payload())       # starts from its snapshot
        plain = engine.txt2img(payload(alwayson_scripts={}))
        assert a.images == b.images and a.prompts == b.prompts
        assert a.images != plain.images
        words = a.prompts[0].split()
        assert len(words) == 45 and len(set(words[5:])) > 8
        stats = EXPANDER.summary()
        assert stats["requests"] == 2
        assert stats["tokens_prefilled"] == 31 + 5 + 5
        assert stats["tokens_from_prefix_cache"] == 31
        assert stats["cache_positions"] == {"full": 76, "sliding": 8,
                                            "linear": 0}
        assert stats["prefix_snapshots"] == 1
        # 31 -> 64 once, 5 -> 64 twice
        assert stats["padded_rows_masked"] == 33 + 2 * 59
        assert stats["state_bytes"] == kv.state_bytes(CFG, 256, jnp.float32)
        assert stats["state_bytes"]["linear"] == 2 * (256 + 192) * 4
        assert len(stats["expert_tokens"]) == 4

    def test_another_seed_gets_another_expansion(self, engine):
        assert engine.txt2img(payload()).prompts \
            != engine.txt2img(payload(seed=99)).prompts

    def test_spans_and_sites(self, engine):
        from stable_diffusion_webui_distributed_tpu.obs import spans

        spans.TRACER.clear()
        with spans.request("rid-delta"):
            engine.txt2img(payload())
        events = [e for e in spans.TRACER.export_chrome()["traceEvents"]
                  if e.get("ph") == "X"]
        names = [e["name"] for e in events]
        for name in ("expand", "expand.prefix_copy", "expand.prefill",
                     "expand.decode_chunk", "expand.fence_wait", "prepare"):
            assert name in names, name
        by_id = {e["args"]["span_id"]: e for e in events}
        for e in events:
            if e["name"].startswith("expand."):
                # the counters come down once the UNet is queued
                assert by_id[e["args"]["parent_id"]]["name"] == (
                    "denoise_range" if e["name"] == "expand.account"
                    else "expand")
        prefill = next(e for e in events if e["name"] == "expand.prefill")
        assert prefill["args"]["tokens"] == 5
        assert prefill["args"]["padded"] == 59
        assert prefill["args"]["form"] == "chunked"
        copy = next(e for e in events if e["name"] == "expand.prefix_copy")
        assert copy["args"]["hit"] is True
        assert copy["args"]["bytes"] == sum(
            kv.state_bytes(CFG, 256, jnp.float32).values())
        # the attention layers' sites: a decode step against the ring + 1
        # and against the full buffer, on XLA
        shapes = ATTENTION.summary()["by_shape"]
        assert shapes["T1 S256 D16"] == {"xla": shapes["T1 S256 D16"]["xla"]}
        assert "T1 S9 D16" in shapes

    def test_status_block(self, engine):
        engine.txt2img(payload())
        block = METRICS.summary()["expander"]
        assert {"state_bytes", "prefix_snapshots", "padded_rows_masked",
                "tokens_no_held_expert", "expert_tokens"} <= set(block)
        assert set(block["state_bytes"]) == {"full", "sliding", "linear"}

    def test_the_old_family_records_no_masked_rows(self):
        old = configs.TINY_EXPAND
        params = init_params(configs.TINY)
        module = lm.DecoderLM(old.expander)
        params["expander"] = module.init(
            jax.random.key(1), jnp.zeros((4,), jnp.int32), jnp.int32(0),
            jnp.int32(4), lm.empty_cache(old.expander, 8, jnp.float32))[
                "params"]
        engine = Engine(old, params, chunk_size=4, state=GenerationState())
        EXPANDER.clear()
        engine.txt2img(payload())
        stats = EXPANDER.summary()
        assert stats["padded_rows_masked"] == 0
        assert set(stats["state_bytes"]) == {"full", "sliding"}

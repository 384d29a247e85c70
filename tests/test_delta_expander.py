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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.ops import delta_rule
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER,
)
from tests import expander_contract as contract
from tests.expander_contract import close, empty, rel_rms, run

REF = contract.load_reference("qwen3next")
#: decay rates exp(A_log) from 0.002 (remembers every test context) to 7
A_LOG = (-6.0, -3.0, 0.0, 2.0)
#: norms and biases off their initial values, so that a weight read as
#: ``scale`` where it is ``1 + weight`` would show
CASE = contract.Case(
    configs.TINY_DELTA_EXPAND, REF,
    how=(("spread", (("weight", 0.2), ("scale", 0.2), ("dt_bias", 0.3))),
         ("a_log", A_LOG)),
    word="rule", tolerance=1e-4)
FAMILY, CFG = CASE.family, CASE.cfg
params, engine = contract.fixtures(CASE)


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


GATED_DELTA_RULE = jax.jit(delta_rule.gated_delta_rule)


class TestTheDeltaRule:
    @pytest.mark.parametrize("tokens", [2, 19, 64, 150])
    def test_the_chunk_wise_form_equals_the_recurrence(self, tokens):
        """From a state that is not zero, over less than a chunk, one whole
        chunk and several with a ragged tail."""
        operands = _operands(tokens)
        want, want_state = delta_rule.recurrent(*operands)
        got, got_state = GATED_DELTA_RULE(*operands)
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

class TestAgainstTheReference(contract.OneSequenceAgainstTheReference):
    """Prefix prefill (chunk-wise), the user chunk against a copy of the
    snapshot, then one token a step through the recurrent state, the ring
    and the full buffer. At 200 the prefix is two chunks and the slowest
    head's memory spans every position."""
    CASE = CASE
    DROPPED = "state"
    test_prefill_then_decode_through_state_matches_the_full_forward = \
        contract.OneSequenceAgainstTheReference \
        .prefill_then_decode_matches_the_full_forward
    test_a_state_that_is_dropped_shows = \
        contract.OneSequenceAgainstTheReference.a_buffer_that_is_dropped_shows
    PARAMETERS = {
        "test_prefill_then_decode_through_state_matches_the_full_forward": [
            ("size", [40, 200])]}

    def test_the_int8_control_is_further_from_the_reference(self, params):
        (ids,), want, _ = CASE.referred(40)
        control = CASE.program(control=True)(params, ids)
        assert rel_rms(control, want) > 1e-3


# -- padding, snapshots, chunked decode ---------------------------------------

class TestPaddingAndSnapshots(contract.PaddingAndSnapshots):
    CASE = CASE

    def test_a_padded_chunk_gives_what_the_exact_chunk_gives(self, params):
        """Logits, recurrent state and the convolution's kept inputs: the
        pad rows neither decayed the state nor wrote to it, and the kept
        inputs are the last three REAL rows."""
        (ids,) = REF.inputs(FAMILY, 5, 24)
        exact, cache_a, _ = run(CFG, params, ids[:19], 0, 19, empty(CFG, 32),
                                all_logits=False)
        padded, cache_b, _ = run(CFG, params, ids, 0, 19, empty(CFG, 32),
                                 all_logits=False)
        np.testing.assert_allclose(exact, padded, rtol=2e-5, atol=2e-5)
        close(cache_a["state"], cache_b["state"])
        close(cache_a["conv"], cache_b["conv"])
        nxt = lambda c: run(CFG, params, ids[19:20], 19, 1, c,  # noqa: E731
                            all_logits=False)[0]
        np.testing.assert_allclose(nxt(cache_a), nxt(cache_b), rtol=2e-5,
                                   atol=2e-5)

    def test_a_chunk_shorter_than_the_taps_keeps_older_inputs(self, params):
        """Two real rows: the kept inputs are the last one from before the
        chunk and the chunk's two."""
        (ids,) = REF.inputs(FAMILY, 6, 12)
        _, cache, _ = run(CFG, params, ids[:10], 0, 10, empty(CFG, 32))
        before = cache["conv"][0]
        _, whole, _ = run(CFG, params, ids, 0, 12, empty(CFG, 32))
        _, after, _ = run(CFG, params, jnp.pad(ids[10:], (0, 6)), 10, 2,
                          cache)
        np.testing.assert_allclose(after["conv"][0][0], before[-1],
                                   rtol=1e-6)
        close(after["conv"], whole["conv"])
        close(after["state"], whole["state"])


    @pytest.mark.parametrize("users,batch", [((5, 16, 63), 4),
                                             ((7, 100), 2)])
    def test_a_joined_decode_is_each_sequence_alone(self, params, users,
                                                    batch):
        """Sequences that continue prompts of their own behind one kept
        prefix: each one's recurrent state and kept inputs are those its
        own prompt's chunk left, its window and full layers' rows its
        own."""
        contract.joined_against_alone(CASE, params, users, batch)


# -- the cache manager --------------------------------------------------------

class TestTheCacheManager(contract.TheCacheManager):
    CASE = CASE
    test_a_snapshot_is_handed_out_as_a_copy_of_every_kind = \
        contract.TheCacheManager.a_snapshot_is_handed_out_as_a_copy

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


# -- the share ----------------------------------------------------------------

class TestTheShareOfALayer:
    @pytest.mark.parametrize("layer", [0, 3])
    def test_four_shares_and_what_every_chip_computes_alike_once(
            self, layer):
        """The reference's uncut layer (a linear one and a full one) against
        the sum of four chips' expert layers: each chip's routed part, and
        the mixer, the gated shared expert and the residual counted once."""
        whole = dataclasses.replace(CFG, experts_held=None, vocab_held=None)
        p = CASE.params(4, whole)[f"layers_{layer}"]
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
        layers, count = contract.param_shapes(share), contract.count
        held = count(layers["layers_0"]["mlp"]["experts"])
        assert held == 128 * 3 * 2048 * 512
        assert round((count(layers["layers_0"]) - held) / 1e6, 2) == 37.92
        assert round((count(layers["layers_3"]) - held) / 1e6, 2) == 31.46
        assert round(count(layers) / 1e6) == 5423


# -- the old model, the sharding rules ----------------------------------------

class TestWhatStaysAsItWas(contract.ShardingRules):
    def test_the_defaults_are_the_ungated_unnormed_forms(self):
        for cfg in (configs.LAGUNA_S_2_1, configs.TINY_LM):
            assert (cfg.attn_gate, cfg.qk_norm, cfg.zero_centred_norm,
                    cfg.shared_expert_gate) == ("head", False, False, False)
            assert lm.LINEAR not in cfg.layer_types

    def test_the_old_presets_parameter_tree(self):
        """TINY_LM builds the leaves it built: per-head ``g_proj``, norms
        named ``scale``, no gate on the shared expert, no q/k norm."""
        old = configs.TINY_EXPAND.expander
        tree = contract.param_shapes(old)
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

    WHOLE = (("layers_0/delta/qkvz_proj/kernel", 2),
             ("layers_0/delta/out_proj/kernel", 2),
             ("layers_0/delta/conv_kernel", 2),
             ("layers_0/delta/A_log", 1),
             ("layers_0/mlp/shared_expert_gate/kernel", 2))
    EXPERT_LAYER = 0
    PLACED_WHOLE = ("layers_1/delta/qkvz_proj/kernel",)
    test_sharding_leaves_the_mixer_whole = contract.ShardingRules.sharding_rules

# -- the engine path ----------------------------------------------------------

class TestEnginePath(contract.SoloEnginePath):
    CASE = CASE
    STATUS_KEYS = frozenset({
        "state_bytes", "prefix_snapshots", "padded_rows_masked",
        "tokens_no_held_expert", "expert_tokens"})
    test_spans_and_sites = contract.SoloEnginePath.spans_of_a_request

    def check_stats(self, stats):
        assert stats["cache_positions"] == {"full": 76, "sliding": 8,
                                            "linear": 0}
        # 31 -> 64 once, 5 -> 64 twice
        assert stats["padded_rows_masked"] == 33 + 2 * 59
        assert stats["state_bytes"]["linear"] == 2 * (256 + 192) * 4
        assert len(stats["expert_tokens"]) == 4

    def check_prefill_span(self, args):
        assert args["padded"] == 59 and args["form"] == "chunked"

    def check_events(self, events):
        # the attention layers' sites: a decode step against the ring + 1
        # and against the full buffer, on XLA
        shapes = ATTENTION.summary()["by_shape"]
        assert shapes["T1 S256 D16"] == {"xla": shapes["T1 S256 D16"]["xla"]}
        assert "T1 S9 D16" in shapes

    def test_the_old_family_records_no_masked_rows(self):
        engine = contract.engine_for(configs.TINY_EXPAND)
        EXPANDER.clear()
        engine.txt2img(CASE.payload())
        stats = EXPANDER.summary()
        assert stats["padded_rows_masked"] == 0
        assert set(stats["state_bytes"]) == {"full", "sliding"}

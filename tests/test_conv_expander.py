"""The resident prompt expander with gated short-convolution layers (a fifth
layer kind whose whole state is the convolution's last two inputs), ungated
q/k-normed grouped-query attention one layer in four, and a sigmoid router
with a selection bias over experts that are all held, with no shared
expert, in the one stack, cache manager and decode scan the other expanders
use.

Everything runs the tiny preset that keeps every new part
(models/configs.py ``TINY_CONV_LM``: two dense conv layers, then full,
conv, conv, conv; 3 taps over 32 channels; 4 heads of width 8 over 2 KV
heads; 16 experts top-4 by biased sigmoid scores over (their sum + 1e-6)).
The plain reference is the benchmark's own
(benchmarks/reference/lfm2_ref.py: float32, no cache, no chunks, the
convolution as three shifted products of the whole sequence).
"""
import dataclasses
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.ops import moe, moe_kernel
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER,
)
from tests import expander_contract as contract
from tests.expander_contract import close, empty, rel_rms, run

REF = contract.load_reference("lfm2")
#: the norms off 1 and the selection bias (which starts at zero) drawn
CASE = contract.Case(
    configs.TINY_CONV_EXPAND, REF,
    how=(("spread", (("scale", 0.2), ("e_score_correction_bias", 0.1))),),
    word="rule")
FAMILY, CFG = CASE.family, CASE.cfg
params, engine = contract.fixtures(CASE)


# -- program against reference ------------------------------------------------

class TestAgainstTheReference(contract.OneChunkAgainstTheReference,
                              contract.OneSequenceAgainstTheReference):
    """Under and over one capacity step of 256."""
    CASE = CASE
    DROPPED = "kept"
    test_prefill_then_decode_through_the_kept_rows = \
        contract.OneSequenceAgainstTheReference \
        .prefill_then_decode_matches_the_full_forward
    test_kept_rows_that_are_dropped_show = \
        contract.OneSequenceAgainstTheReference.a_buffer_that_is_dropped_shows
    PARAMETERS = {
        "test_one_chunk_matches_the_full_forward": [("size", [40, 300])],
        "test_prefill_then_decode_through_the_kept_rows": [
            ("size", [40, 300])]}

    @pytest.mark.parametrize("control", [name for name, _ in REF.CONTROLS])
    def test_each_control_is_further_from_the_reference(self, params,
                                                        control):
        """The int8 linears, the kept rows zeroed between calls, the
        gates' and taps' products in bfloat16."""
        (ids,), want, _ = CASE.referred(40)
        program = CASE.program()(params, ids)
        lower = CASE.program(**dict(REF.CONTROLS)[control])(params, ids)
        assert rel_rms(lower, want) > 1e-3 > 100 * rel_rms(program, want)


# -- the convolution ----------------------------------------------------------

def as_delta_mixer_had_it(kernel, conv, qkv, length):
    """The lines ``DeltaMixer`` had before the helper, word for word."""
    f32 = jnp.float32
    taps, tokens = kernel.shape[0], qkv.shape[0]
    inputs = jnp.concatenate([conv.astype(f32), qkv])
    qkv = jax.nn.silu(sum(kernel[j] * inputs[j:j + tokens]
                          for j in range(taps)))
    conv = jax.lax.dynamic_slice_in_dim(
        inputs, length, taps - 1, 0).astype(conv.dtype)
    return qkv, conv


class TestTheConvolution:
    @pytest.mark.parametrize("tokens,length", [(1, 1), (5, 2), (64, 19),
                                               (64, 64)])
    def test_the_helper_gives_the_linear_mixer_what_it_had(self, tokens,
                                                           length):
        """Bit for bit, four taps then SiLU, eagerly and jitted."""
        ks = jax.random.split(jax.random.key(tokens), 3)
        kernel = jax.random.normal(ks[0], (4, 24))
        conv = jax.random.normal(ks[1], (3, 24))
        qkv = jax.random.normal(ks[2], (tokens, 24))

        def through_the_helper(kernel, conv, qkv, length):
            out, kept = lm.causal_conv(kernel, conv, qkv, length)
            return jax.nn.silu(out), kept

        for wrap in (lambda f: f, jax.jit):
            got = wrap(through_the_helper)(kernel, conv, qkv,
                                           jnp.int32(length))
            want = wrap(as_delta_mixer_had_it)(kernel, conv, qkv,
                                               jnp.int32(length))
            for g, w in zip(got, want):
                assert g.dtype == w.dtype
                np.testing.assert_array_equal(g, w)

    def test_a_linear_mixer_calls_the_helper(self, monkeypatch):
        cfg = configs.TINY_DELTA_EXPAND.expander
        seen = []
        whole = lm.causal_conv
        monkeypatch.setattr(lm, "causal_conv", lambda kernel, *a: (
            seen.append(kernel.shape), whole(kernel, *a))[1])
        contract.param_shapes(cfg)
        assert seen == [(4, cfg.linear_conv_channels)] * 2

    def test_a_conv_mixer_is_the_written_one(self, params):
        """``(C * sum_j w_j * u_{t-2+j}) W_out`` with ``u = B * x`` and
        nothing after the taps, from a zero state."""
        p = params["layers_3"]["short_conv"]
        n = jax.random.normal(jax.random.key(2), (9, CFG.hidden_size))
        got, kept = lm.ShortConv(CFG).apply(
            {"params": p}, n, jnp.int32(9), jnp.zeros((2, CFG.hidden_size)))
        b, c, x = np.split(np.asarray(n @ p["in_proj"]["kernel"]), 3, -1)
        u = np.concatenate([np.zeros((2, CFG.hidden_size)), b * x])
        w = np.asarray(p["conv_kernel"])
        mixed = np.stack([sum(w[j] * u[t + j] for j in range(3))
                          for t in range(9)])
        np.testing.assert_allclose(
            got, (c * mixed) @ np.asarray(p["out_proj"]["kernel"]),
            rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(kept, u[-2:], rtol=1e-6)
        np.testing.assert_allclose(got, REF.short_conv(CFG, n, p),
                                   rtol=1e-5, atol=1e-6)


# -- padded chunks, snapshots, the decode scan --------------------------------

class TestPaddingAndSnapshots(contract.PaddingAndSnapshots):
    CASE = CASE

    def test_a_padded_chunk_gives_what_the_exact_chunk_gives(self, params):
        """Logits and kept rows: the kept rows are the last two REAL rows
        of ``u``, never a pad row's."""
        (ids,) = REF.inputs(FAMILY, 5, 24)
        exact, cache_a, _ = run(CFG, params, ids[:19], 0, 19, empty(CFG, 32),
                                all_logits=False)
        padded, cache_b, _ = run(CFG, params, ids, 0, 19, empty(CFG, 32),
                                 all_logits=False)
        np.testing.assert_allclose(exact, padded, rtol=2e-5, atol=2e-5)
        close(cache_a["kept"], cache_b["kept"])
        assert float(jnp.max(jnp.abs(cache_a["kept"][0]))) > 1e-3
        nxt = lambda c: run(CFG, params, ids[19:20], 19, 1, c,  # noqa: E731
                            all_logits=False)[0]
        np.testing.assert_allclose(nxt(cache_a), nxt(cache_b), rtol=2e-5,
                                   atol=2e-5)
        _, want, _ = CASE.referred_on(ids[:20])
        assert rel_rms(nxt(cache_b), want[19:20]) < 1e-5

    def test_a_chunk_of_one_real_row_keeps_an_older_one(self, params):
        """One real row in a padded chunk: the kept rows are the last one
        from before the chunk and the chunk's one."""
        (ids,) = REF.inputs(FAMILY, 6, 11)
        _, cache, _ = run(CFG, params, ids[:10], 0, 10, empty(CFG, 32))
        before = cache["kept"][0]
        _, whole, _ = run(CFG, params, ids, 0, 11, empty(CFG, 32))
        _, after, _ = run(CFG, params, jnp.pad(ids[10:], (0, 7)), 10, 1,
                          cache)
        np.testing.assert_allclose(after["kept"][0][0], before[-1],
                                   rtol=1e-6)
        close(after["kept"], whole["kept"])

    def test_a_resumed_snapshot_decodes_as_the_uninterrupted_run(self,
                                                                 params):
        """Through the manager: the prefix's cache kept, handed out as a
        copy, the prompt prefilled against it and eight tokens decoded,
        against the same from one prefill of prefix and prompt together."""
        (ids,) = REF.inputs(FAMILY, 8, 40)
        prefix = np.asarray(ids[:29]).tolist()
        manager = kv.KVCacheManager(CFG, jnp.float32)
        cache, held = manager.acquire(prefix, 64)
        assert held == 0
        _, cache, _ = run(CFG, params, ids[:29], 0, 29, cache)
        manager.keep_prefix(prefix, 64, cache)
        decode = contract.executables(CFG, 8).alone

        def finish(cache, start):
            _, cache, _ = run(CFG, params, ids[start:], start, 40 - start,
                              cache)
            out = decode(params, cache, ids[5], jnp.int32(40),
                         jax.random.key(3), jnp.float32(1.0))
            return out[0], np.asarray(out[3]).tolist()

        resumed, held = manager.acquire(prefix, 64)
        assert held == 29
        cache_r, made_r = finish(resumed, 29)
        cache_u, made_u = finish(empty(CFG), 0)
        assert made_r == made_u and len(set(made_u)) > 2
        close(cache_r["kept"], cache_u["kept"])


# -- the cache manager --------------------------------------------------------

class TestTheCacheManager(contract.TheCacheManager):
    CASE = CASE
    test_a_snapshot_is_handed_out_as_a_copy = \
        contract.TheCacheManager.a_snapshot_is_handed_out_as_a_copy

    def test_a_conv_layer_has_one_buffer_of_two_rows(self):
        assert lm.buffers_of(lm.CONV) == ("kept",)
        assert lm.cache_shapes(CFG, 256) == {
            "k": [(256, 2, 8)], "v": [(256, 2, 8)],
            "kept": [(2, 32)] * 5}
        cache = lm.empty_cache(CFG, 256, jnp.bfloat16)
        assert {x.dtype for x in cache["k"] + cache["v"]} \
            == {jnp.dtype(jnp.bfloat16)}
        assert {x.dtype for x in cache["kept"]} == {jnp.dtype(jnp.float32)}
        # and the same two rows at any capacity
        assert lm.cache_shapes(CFG, 1024)["kept"] == [(2, 32)] * 5

    @pytest.mark.parametrize("preset,names", [
        ("TINY_EXPAND", {"k", "v"}),
        ("TINY_DELTA_EXPAND", {"k", "v", "state", "conv"}),
        ("TINY_LATENT_EXPAND", {"latent"})])
    def test_the_other_models_have_the_cache_they_had(self, preset, names):
        old = getattr(configs, preset).expander
        assert set(lm.cache_shapes(old, 64)) == names
        assert lm.CONV not in kv.state_bytes(old, 64, jnp.bfloat16)
        assert lm.CONV not in kv.KVCacheManager(
            old, jnp.float32).positions_in_use(40)

    def test_bytes_and_positions_come_from_the_shapes(self):
        manager = kv.KVCacheManager(CFG, jnp.bfloat16)
        assert manager.positions_in_use(40) == {"full": 40, "sliding": 0,
                                                "conv": 0}
        assert manager.positions_in_use(4000)["conv"] == 0
        assert kv.state_bytes(CFG, 256, jnp.bfloat16) == {
            "full": 2 * 256 * 2 * 8 * 2, "sliding": 0,
            "conv": 5 * 2 * 32 * 4}
        share = configs.sd15_lfm2_expander().expander
        sizes = kv.state_bytes(share, 1024, jnp.bfloat16)
        # 4.2 MB of keys and values, 128 KiB of kept rows
        assert sizes == {"full": 2 * 2 * 1024 * 8 * 64 * 2, "sliding": 0,
                         "conv": 8 * 2 * 2048 * 4}
        assert sizes["full"] == 4194304 and sizes["conv"] == 131072
        assert kv.KVCacheManager(share, jnp.bfloat16).positions_in_use(
            960) == {"full": 1920, "sliding": 0, "conv": 0}


# -- ungated attention, no shared expert, the router's epsilon ----------------

class TestWhatAnLayerMayLack:
    def test_an_ungated_attention_has_no_gate_and_is_the_references(
            self, params):
        attn = params["layers_2"]["attn"]
        assert set(attn) == {"q_proj", "k_proj", "v_proj", "o_proj",
                             "q_norm", "k_norm"}
        assert attn["q_proj"]["kernel"].shape == (32, 4 * 8)
        n = jax.random.normal(jax.random.key(4), (12, CFG.hidden_size))
        pos = jnp.arange(12, dtype=jnp.int32)
        got, _, _ = lm.Attention(CFG, 2).apply(
            {"params": attn}, n, pos, jnp.int32(0), jnp.int32(12),
            jnp.zeros((16, 2, 8)), jnp.zeros((16, 2, 8)))
        np.testing.assert_allclose(got, REF.attention(CFG, 2, n, attn),
                                   rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize("gate,leaf", [("head", "g_proj"),
                                           ("element", None)])
    def test_the_gated_forms_are_what_they_were(self, gate, leaf):
        cfg = dataclasses.replace(CFG, attn_gate=gate)
        attn = contract.param_shapes(cfg)["layers_2"]["attn"]
        assert ("g_proj" in attn) == (leaf == "g_proj")
        width = 4 * 8 * (2 if gate == "element" else 1)
        assert attn["q_proj"]["kernel"].shape == (32, width)

    def test_a_layer_with_no_shared_expert_has_no_such_weights(self, params):
        mlp = params["layers_3"]["mlp"]
        assert set(mlp) == {"router", "e_score_correction_bias", "experts"}
        n = jax.random.normal(jax.random.key(5), (20, CFG.hidden_size))
        got, (chosen, load, none_held) = lm.MoE(CFG).apply(
            {"params": mlp}, n, jnp.ones(20, bool))
        want, (own, _) = REF._moe(CFG, n, mlp)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        assert np.array_equal(np.sort(chosen, -1), np.sort(own, -1))
        # every chosen expert is held: four a token, none missing
        assert int(load.sum()) == 20 * 4 and int(none_held) == 0

    @pytest.mark.parametrize("preset", ["TINY_EXPAND", "TINY_DELTA_EXPAND",
                                        "TINY_LATENT_EXPAND"])
    def test_the_other_presets_keep_their_shared_expert(self, preset):
        cfg = getattr(configs, preset).expander
        assert cfg.shared_expert_intermediate_size == 16
        assert cfg.norm_topk_eps == 0.0
        # a latent layer reads ``attn_gate`` since PR 56; until then the
        # latent preset carried the default it never read
        assert (cfg.attn_gate == "none") is (lm.LATENT in cfg.layer_types)
        shapes = contract.param_shapes(cfg)
        layer = shapes[f"layers_{cfg.expert_layers[0]}"]
        assert "shared_expert" in layer["mlp"]
        assert "short_conv" not in layer

    def test_the_weights_are_over_their_sum_plus_epsilon(self):
        logits = jax.random.normal(jax.random.key(1), (50, 16))
        bias = 0.3 * jax.random.normal(jax.random.key(2), (16,))
        plain = moe.route(logits, 4, renormalise=True, scale=1.0,
                          scoring="sigmoid", bias=bias)
        with_eps = moe.route(logits, 4, renormalise=True, scale=1.0,
                             scoring="sigmoid", bias=bias, eps=1e-2)
        np.testing.assert_array_equal(plain.experts, with_eps.experts)
        chosen = jnp.take_along_axis(jax.nn.sigmoid(logits), plain.experts,
                                     -1)
        np.testing.assert_allclose(
            with_eps.weights,
            chosen / (chosen.sum(-1, keepdims=True) + 1e-2), rtol=1e-6)
        # no epsilon is the division the other routers had, bit for bit
        np.testing.assert_array_equal(
            plain.weights, chosen / chosen.sum(-1, keepdims=True))


# -- the share ----------------------------------------------------------------

class TestTheShare:
    def test_the_share_of_the_published_model(self):
        whole = configs.LFM2_24B_A2B
        assert whole.num_layers == 40
        assert len(whole.layers_of(lm.CONV)) == 30
        assert whole.layers_of(lm.FULL) == tuple(range(2, 40, 4))
        share = configs.sd15_lfm2_expander().expander
        assert share.layer_types == (
            "conv", "conv", "full", "conv", "conv", "conv", "full", "conv",
            "conv", "conv")
        assert share.dense_layers == (0, 1)
        assert share.expert_layers == tuple(range(2, 10))
        # cut in depth alone: every expert and every id is held
        assert share.experts == (0, 64) and share.vocab == (0, 65536)
        assert share.num_experts_per_tok == 4 and share.conv_taps == 3
        assert share.head_dim * share.num_heads_per_layer[2] \
            == share.hidden_size
        # the fourth published shape the expert kernel tiles: two blocks
        # an expert
        assert moe_kernel.f_tile(2048, 1536, 2) == 768
        assert moe_kernel.ring(2048, 1536, 2) == moe_kernel.Ring(2, 768, 2)
        assert moe.choose("tpu", 1, jnp.bfloat16, 2048, 1536) == moe.KERNEL

    def test_the_share_has_5401_million_parameters(self):
        """Shapes only: a conv mixer 16.78 M, an attention mixer 10.49 M,
        an expert 9.437 M, a dense MLP 72.35 M, table and head 134.2 M
        each; the whole model by the same count 23.98 B."""
        count, shapes_of = contract.count, contract.param_shapes
        layers = shapes_of(configs.sd15_lfm2_expander().expander)
        assert round(count(layers["layers_0"]["short_conv"]) / 1e6, 2) \
            == 16.78
        assert round(count(layers["layers_2"]["attn"]) / 1e6, 2) == 10.49
        assert round(count(layers["layers_0"]["mlp"]) / 1e6, 2) == 72.35
        experts = layers["layers_5"]["mlp"]["experts"]
        assert round(count(experts) / 64 / 1e6, 3) == 9.437
        assert round(count(layers["layers_5"]["mlp"]) / 1e6, 1) == 604.1
        assert round(count(layers["embed_tokens"]) / 1e6, 1) == 134.2
        assert round(count(layers["lm_head"]) / 1e6, 1) == 134.2
        assert round(count(layers) / 1e6) == 5401
        assert round(count(layers) * 2 / 2 ** 30, 2) == 10.06
        assert round(count(shapes_of(configs.LFM2_24B_A2B)) / 1e9, 2) \
            == 23.98


# -- the tree and the sharding rules ------------------------------------------

class TestTheTreeAndItsRules(contract.ShardingRules):
    def test_the_presets_parameter_tree(self, params):
        assert set(params["layers_0"]) == {
            "input_norm", "post_attention_norm", "short_conv", "mlp"}
        assert set(params["layers_0"]["mlp"]) == {"gate_proj", "up_proj",
                                                  "down_proj"}
        mixer = params["layers_0"]["short_conv"]
        assert set(mixer) == {"in_proj", "conv_kernel", "out_proj"}
        assert mixer["in_proj"]["kernel"].shape == (32, 96)
        assert mixer["conv_kernel"].shape == (3, 32)
        assert mixer["out_proj"]["kernel"].shape == (32, 32)
        assert set(params["layers_2"]) == {
            "input_norm", "post_attention_norm", "attn", "mlp"}

    WHOLE = (("layers_0/short_conv/in_proj/kernel", 2),
             ("layers_0/short_conv/out_proj/kernel", 2),
             ("layers_0/short_conv/conv_kernel", 2),
             ("layers_3/mlp/e_score_correction_bias", 1))
    EXPERT_LAYER = 3
    PLACED_WHOLE = ("layers_1/short_conv/in_proj/kernel",)
    test_sharding_leaves_the_mixer_whole = contract.ShardingRules.sharding_rules

    def check_placed(self, placed, mesh):
        from jax.sharding import PartitionSpec as P

        from stable_diffusion_webui_distributed_tpu.parallel.sharding import (
            tp_spec_for,
        )

        # the UNet's convolutions keep their rule
        assert tp_spec_for("down_0/res_0/conv/kernel", 4) \
            == P(None, None, None, "tp")
        # and the meshed program still runs: the partitioned experts'
        # logits are the unpartitioned program's
        ids = jax.random.randint(jax.random.key(1), (8,), *CFG.vocab)
        module = lm.DecoderLM(CFG, meshed=True)
        with mesh:
            got, _, _ = jax.jit(lambda p, i: module.apply(
                {"params": p}, i, jnp.int32(0), jnp.int32(8),
                empty(CFG, 32)))(placed, ids)
        want, _, _ = run(CFG, CASE.params(), ids, 0, 8, empty(CFG, 32))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# -- the engine path ----------------------------------------------------------

class TestEnginePath(contract.SoloEnginePath):
    CASE = CASE
    STATUS_KEYS = frozenset({
        "state_bytes", "cache_positions", "conv_mixers", "prefix_snapshots",
        "padded_rows_masked", "tokens_no_held_expert", "expert_tokens"})
    test_spans = contract.SoloEnginePath.spans_of_a_request

    def check_stats(self, stats):
        assert stats["cache_positions"] == {"full": 76, "sliding": 0,
                                            "conv": 0}
        # 31 -> 64 once, 5 -> 64 twice
        assert stats["padded_rows_masked"] == 33 + 2 * 59
        assert stats["state_bytes"]["conv"] == 5 * 2 * 32 * 4
        assert len(stats["expert_tokens"]) == 4
        assert stats["tokens_no_held_expert"] == 0
        # one prefill executable (31 and 5 tokens both pad to 64) and the
        # decode scan's body, five conv mixers each: counted when traced,
        # not when run
        assert stats["conv_mixers"] == {"step": 5, "chunk": 5}
        assert stats["expert_products"]["grouped"] == 4
        assert sum(stats["expert_products"].values()) == 8

    def check_prefill_span(self, args):
        assert args["padded"] == 59
        assert "form" not in args and "latent" not in args  # no recurrence

    def check_status(self, block):
        assert block["cache_positions"]["conv"] == 0
        assert set(block["conv_mixers"]) == {"step", "chunk"}
        assert block["residual_streams"] == 1
        assert block["sinkhorn_iters"] == 0

    def test_the_published_share_traces_eight_conv_mixers_and_two_sites(
            self):
        """One decode step of the share the cell runs, traced without
        weights or FLOPs: eight conv mixers, and the two attention layers'
        sites at head width 64 over the cache's 1024 slots."""
        share = configs.sd15_lfm2_expander().expander
        cache = contract.cache_structs(share, 1024)
        shapes = contract.param_shapes(share)
        ATTENTION.clear()
        EXPANDER.clear()
        logits, after, _ = contract.sites_of(
            share, shapes, jnp.zeros((1,), jnp.int32), 600, 1,
            cache, jnp.bfloat16, all_logits=False)
        assert logits.shape == (1, 65536)
        assert [x.shape for x in after["kept"]] == [(2, 2048)] * 8
        assert EXPANDER.summary()["conv_mixers"] == {"step": 8, "chunk": 0}
        assert EXPANDER.summary()["expert_products"]["grouped"] == 0
        sites = ATTENTION.summary()
        assert sites["by_shape"] == {"T1 S1024 D64": {"xla": 2}}
        ATTENTION.clear()
        EXPANDER.clear()

    def test_an_expander_without_conv_layers_counts_none(self):
        engine = contract.engine_for(configs.TINY_EXPAND)
        EXPANDER.clear()
        engine.txt2img(CASE.payload())
        stats = EXPANDER.summary()
        assert stats["conv_mixers"] == {"step": 0, "chunk": 0}
        assert stats["padded_rows_masked"] == 0
        assert set(stats["state_bytes"]) == {"full", "sliding"}


class TestTheServedPath:
    def test_a_request_through_the_api_server_and_its_status(self):
        """``ApiServer`` -> dispatcher -> ``expand`` -> CLIP -> UNet -> VAE
        -> PNG, and what ``/internal/status`` then says of the expander
        and of the attention sites."""
        from stable_diffusion_webui_distributed_tpu.server.api import (
            ApiServer,
        )

        def call(server, route, body=None):
            data = None if body is None else json.dumps(body).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}{route}", data=data,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=600) as r:
                return json.loads(r.read())

        mp = pytest.MonkeyPatch()
        mp.setenv("SDTPU_BUCKET_LADDER", "32x32")
        mp.setenv("SDTPU_BATCH_LADDER", "1")
        engine = CASE.engine()
        server = ApiServer(engine, state=engine.state, host="127.0.0.1",
                           port=0).start()
        body = {"prompt": "a cow in a valley", "steps": 4, "width": 32,
                "height": 32, "seed": 77, "sampler_name": "Euler a",
                "alwayson_scripts": CASE.script()}
        try:
            EXPANDER.clear()
            ATTENTION.clear()
            first = call(server, "/sdapi/v1/txt2img", body)
            again = call(server, "/sdapi/v1/txt2img", body)
            plain = call(server, "/sdapi/v1/txt2img",
                         dict(body, alwayson_scripts={}))
            status = call(server, "/internal/status")
        finally:
            server.stop()
            mp.undo()
        assert first["images"] == again["images"] != plain["images"]
        prompts = json.loads(first["info"])["all_prompts"]
        assert len(prompts[0].split()) == 45
        block = status["serving"]["expander"]
        assert block["requests"] == 2
        assert block["tokens_from_prefix_cache"] == 31
        assert block["conv_mixers"] == {"step": 5, "chunk": 5}
        assert block["state_bytes"]["conv"] == 5 * 2 * 32 * 4
        assert block["cache_positions"] == {"full": 76, "sliding": 0,
                                            "conv": 0}
        # the one attention layer's decode site, at the head's width
        assert status["serving"]["attention"]["by_shape"]["T1 S256 D8"] \
            == {"xla": 1}

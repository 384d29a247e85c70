"""The resident prompt expander with latent attention (one cached latent and
one rotated key a position, attended in two forms), several residual
streams under Sinkhorn-projected mixers, and a sigmoid router with a
selection bias, in the one stack, cache manager and decode scan the other
expanders use.

Everything runs the tiny preset that keeps every new part
(models/configs.py ``TINY_LATENT_LM``: four latent layers of 4 heads over a
16 + 8 wide cache row, a 24-wide query latent, YaRN whose mscale scales the
softmax, four streams, two dense layers then expert layers, 16 experts
top-4 by biased sigmoid scores of which a chip of four holds 4). The plain
reference is the benchmark's own (benchmarks/reference/xing4_ref.py:
float32, no cache, no chunks, expanded attention only).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.ops import (
    moe, moe_kernel, stream_mixer,
)
from stable_diffusion_webui_distributed_tpu.ops.attention import (
    attend_positions,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER, METRICS,
)
from tests import expander_contract as contract
from tests.expander_contract import ROOT, empty, rel_rms, run

REF = contract.load_reference("xing4")
#: the norms off 1 and the leaves that start at zero (the selection bias,
#: the mixers' ``b_pre`` and ``b_post``) drawn
CASE = contract.Case(
    configs.TINY_LATENT_EXPAND, REF,
    how=(("spread", (("scale", 0.2), ("e_score_correction_bias", 0.1),
                     ("b_pre", 0.3), ("b_post", 0.3), ("alpha", 0.2))),),
    word="rule")
FAMILY, CFG = CASE.family, CASE.cfg
params, engine = contract.fixtures(CASE)


def lm_params_of_a_mixer(mixer, streams, stored, at_the_clamp=False):
    """One mixer's parameters in the dtype a policy stores them in."""
    p = mixer.init(jax.random.key(1), streams)["params"]
    if at_the_clamp:
        p["alpha"] = jnp.asarray([1.0, 1.0, 500.0])
    return jax.tree_util.tree_map(lambda leaf: leaf.astype(stored), p)


def stream_maps(cfg, p, streams):
    """(h_pre, h_post, h_res): the mixer's maps without its read."""
    return lm.StreamMixer(cfg).apply({"params": p}, streams)[1:4]


# -- program against reference ------------------------------------------------

class TestAgainstTheReference(contract.OneChunkAgainstTheReference,
                              contract.OneSequenceAgainstTheReference):
    """Prefix prefill (expanded), the user chunk against a copy of the
    snapshot, then one token a step (absorbed) through the cache."""
    CASE = CASE
    DROPPED = "latent"
    test_prefill_then_decode_through_the_latent_cache = \
        contract.OneSequenceAgainstTheReference \
        .prefill_then_decode_matches_the_full_forward
    test_a_cache_that_is_dropped_shows = \
        contract.OneSequenceAgainstTheReference.a_buffer_that_is_dropped_shows
    PARAMETERS = {
        "test_one_chunk_matches_the_full_forward": [("size", [40, 200])],
        "test_prefill_then_decode_through_the_latent_cache": [
            ("size", [40, 200])]}

    def test_the_int8_control_is_further_from_the_reference(self, params):
        (ids,), want, _ = CASE.referred(40)
        program = CASE.program()(params, ids)
        control = CASE.program(control=True)(params, ids)
        assert rel_rms(control, want) > 1e-3 > 100 * rel_rms(program, want)

    @pytest.mark.parametrize("lower", ["stream_dtype", "sinkhorn_dtype"])
    def test_streams_or_sinkhorn_in_bfloat16_show(self, params, lower):
        (ids,), want, _ = CASE.referred(40)
        got = CASE.program(**{lower: jnp.bfloat16})(params, ids)
        assert rel_rms(got, want) > 1e-3


# -- latent attention's two forms ---------------------------------------------

class TestTheTwoForms:
    def test_the_form_is_chosen_by_the_chunks_length(self):
        assert lm.latent_form(1) == lm.LATENT_ABSORBED == "latent_absorbed"
        assert lm.latent_form(2) == lm.latent_form(512) \
            == lm.LATENT_EXPANDED == "latent_expanded"

    def test_absorbed_equals_expanded_on_the_same_cache(self, params):
        """One real token at position 30: as a chunk of one (absorbed) and
        as the first row of a padded chunk of two (expanded)."""
        (ids,) = REF.inputs(FAMILY, 4, 32)
        _, cache, _ = run(CFG, params, ids[:30], 0, 30, empty(CFG))
        ATTENTION.clear()
        contract.sites_of(CFG, params, ids[30:31], 30, 1, cache)
        assert ATTENTION.summary()["latent_absorbed"] == CFG.num_layers
        ATTENTION.clear()
        contract.sites_of(CFG, params, ids[30:32], 30, 1, cache)
        assert ATTENTION.summary()["latent_expanded"] == CFG.num_layers
        assert "latent_absorbed" not in ATTENTION.summary()
        absorbed, cache_a, _ = run(CFG, params, ids[30:31], 30, 1, cache,
                                   all_logits=False)
        expanded, cache_e, _ = run(CFG, params, ids[30:32], 30, 1, cache,
                                   all_logits=False)
        np.testing.assert_allclose(absorbed, expanded, rtol=1e-5, atol=1e-5)
        for a, e in zip(cache_a["latent"], cache_e["latent"]):
            np.testing.assert_allclose(a[:31], e[:31], rtol=1e-5, atol=1e-5)

    def test_the_cache_row_is_the_normed_latent_and_one_rotated_key(
            self, params):
        """Against the reference's own arithmetic for layer 0, whose input
        is the embedding read through the first mixer."""
        (ids,) = REF.inputs(FAMILY, 4, 12)
        _, cache, _ = run(CFG, params, ids, 0, 12, empty(CFG))
        rows = cache["latent"][0]
        assert rows.shape == (64, CFG.kv_lora_rank + CFG.qk_rope_head_dim)
        assert not np.any(np.asarray(rows[12:]))
        p = params["layers_0"]
        x = params["embed_tokens"]["embedding"][ids]
        streams = jnp.repeat(x[:, None, :], CFG.residual_streams, axis=1)
        h_pre, _, _ = REF.stream_maps(CFG, streams, p["attn_hc"])
        n = REF._norm(jnp.einsum("tn,tnc->tc", h_pre, streams),
                      p["input_norm"], CFG.rms_norm_eps)
        kva = n @ p["attn"]["kv_a_proj_with_mqa"]["kernel"]
        latent = REF._norm(kva[:, :16], p["attn"]["kv_a_norm"],
                           CFG.rms_norm_eps)
        key = REF._rope(kva[:, None, 16:], CFG.rope_full)[:, 0]
        np.testing.assert_allclose(rows[:12, :16], latent, rtol=2e-5,
                                   atol=2e-6)
        np.testing.assert_allclose(rows[:12, 16:], key, rtol=2e-5, atol=2e-5)

    def test_values_narrower_than_keys(self):
        """``attend_positions`` with 24-wide keys over one KV head and
        16-wide values, against plain softmax attention."""
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (3, 4, 24))
        k = jax.random.normal(ks[1], (10, 1, 24))
        v = jax.random.normal(ks[2], (10, 1, 16))
        q_pos = jnp.asarray([5, 6, 7])
        k_pos = jnp.where(jnp.arange(10) < 8, jnp.arange(10), -1)
        out, _ = attend_positions(q, k, v, q_pos, k_pos, scale=0.3)
        assert out.shape == (3, 4, 16)
        scores = jnp.einsum("thd,sd->hts", q, k[:, 0]) * 0.3
        seen = (k_pos[None, :] >= 0) & (k_pos[None, :] <= q_pos[:, None])
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        np.testing.assert_allclose(
            out, jnp.einsum("hts,sd->thd", probs, v[:, 0]), rtol=1e-5,
            atol=1e-6)

    def test_the_softmax_carries_yarns_mscale_and_the_tables_do_not(self):
        assert CFG.rope_full.attention_factor == 1.0
        m = 0.1 * np.log(4.0) + 1.0
        assert CFG.latent_softmax_scale == pytest.approx(16 ** -0.5 * m * m)
        assert CFG.latent_softmax_scale == pytest.approx(
            REF.softmax_scale(CFG))
        plain = dataclasses.replace(CFG, rope_mscale_all_dim=0.0)
        assert plain.latent_softmax_scale == pytest.approx(0.25)
        published = configs.XING4_0_29B_A4B
        assert published.latent_softmax_scale == pytest.approx(
            192 ** -0.5 * 2.00474, rel=1e-5)
        np.testing.assert_allclose(
            lm.rope_frequencies(published.rope_full, 64),
            REF._inv_freq(published.rope_full, 64), rtol=1e-12)


# -- padding, chunks, snapshots -----------------------------------------------

class TestPaddingAndSnapshots(contract.PaddingAndSnapshots):
    CASE = CASE

    def test_a_chunked_prefill_gives_what_one_chunk_gives(self, params):
        (ids,) = REF.inputs(FAMILY, 5, 48)
        whole, cache_w, _ = run(CFG, params, ids, 0, 48, empty(CFG))
        first, cache, _ = run(CFG, params, ids[:20], 0, 20, empty(CFG))
        second, cache, _ = run(CFG, params, ids[20:36], 20, 16, cache)
        third, cache, _ = run(CFG, params, ids[36:], 36, 12, cache)
        np.testing.assert_allclose(
            jnp.concatenate([first, second, third]), whole, rtol=2e-5,
            atol=2e-5)
        for a, b in zip(cache["latent"], cache_w["latent"]):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

    def test_padded_rows_are_never_read(self, params):
        """A chunk of 19 real rows padded to 24: the same next token, and
        whatever the pad rows wrote beyond ``end`` is overwritten before
        any query can see it."""
        (ids,) = REF.inputs(FAMILY, 5, 24)
        exact, cache_a, _ = run(CFG, params, ids[:19], 0, 19, empty(CFG),
                                all_logits=False)
        garbage = ids.at[19:].set(CFG.vocab[0] + 1)
        padded, cache_b, _ = run(CFG, params, garbage, 0, 19, empty(CFG),
                                 all_logits=False)
        np.testing.assert_allclose(exact, padded, rtol=2e-5, atol=2e-5)
        assert np.any(np.asarray(cache_b["latent"][0][19:24]))
        for step in range(19, 22):
            a, cache_a, _ = run(CFG, params, ids[step:step + 1], step, 1,
                                cache_a)
            b, cache_b, _ = run(CFG, params, ids[step:step + 1], step, 1,
                                cache_b)
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


# -- the cache manager --------------------------------------------------------

class TestTheCacheManager(contract.TheCacheManager):
    CASE = CASE
    test_a_snapshot_is_handed_out_as_a_copy = \
        contract.TheCacheManager.a_snapshot_is_handed_out_as_a_copy

    def test_a_latent_layer_has_one_buffer(self):
        assert lm.buffers_of(lm.LATENT) == ("latent",)
        assert lm.cache_shapes(CFG, 256) == {"latent": [(256, 24)] * 4}
        cache = lm.empty_cache(CFG, 256, jnp.bfloat16)
        assert {x.dtype for x in cache["latent"]} \
            == {jnp.dtype(jnp.bfloat16)}

    @pytest.mark.parametrize("preset", ["TINY_EXPAND", "TINY_DELTA_EXPAND"])
    def test_the_other_models_have_the_cache_they_had(self, preset):
        old = getattr(configs, preset).expander
        shapes = lm.cache_shapes(old, 64)
        assert "latent" not in shapes and {"k", "v"} <= set(shapes)
        assert "latent" not in kv.state_bytes(old, 64, jnp.float32)
        assert "latent" not in kv.KVCacheManager(
            old, jnp.float32).positions_in_use(40)

    def test_bytes_and_positions_come_from_the_shapes(self):
        manager = kv.KVCacheManager(CFG, jnp.bfloat16)
        assert manager.positions_in_use(40) == {"full": 0, "sliding": 0,
                                                "latent": 160}
        assert kv.state_bytes(CFG, 256, jnp.bfloat16) == {
            "full": 0, "sliding": 0, "latent": 4 * 256 * 24 * 2}
        share = configs.sd15_xing4_expander().expander
        sizes = kv.state_bytes(share, 1024, jnp.bfloat16)
        assert sizes == {"full": 0, "sliding": 0,
                         "latent": 20 * 1024 * 576 * 2}
        assert round(sizes["latent"] / 1e6, 1) == 23.6
        # against every head's keys (192) and values (128): 14 times less
        assert 32 * (192 + 128) / 576 > 14


# -- the residual streams -----------------------------------------------------

class TestTheStreams:
    def test_h_res_is_doubly_stochastic_and_the_references(self, params):
        (ids,) = REF.inputs(FAMILY, 8, 60)
        x = params["embed_tokens"]["embedding"][ids]
        streams = jnp.repeat(x[:, None, :], 4, axis=1) \
            + 0.5 * jax.random.normal(jax.random.key(2), (60, 4, 32))
        p = params["layers_1"]["mlp_hc"]
        got = stream_maps(CFG, p, streams)
        want = REF.stream_maps(CFG, streams, p)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
        h_pre, h_post, h_res = got
        assert h_pre.shape == h_post.shape == (60, 4)
        assert h_res.shape == (60, 4, 4)
        assert np.all((h_pre > 0) & (h_pre < 1))
        assert np.all((h_post > 0) & (h_post < 2))
        np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=1e-3)
        np.testing.assert_allclose(h_res.sum(-2), 1.0, atol=1e-3)
        # neither the identity nor uniform, and not the same for two tokens
        row_max = np.asarray(h_res.max(-1))
        assert row_max.min() < 0.5 < row_max.max() < 0.999
        assert np.abs(np.asarray(h_res[0] - h_res[1])).max() > 1e-2

    def test_the_clamp_keeps_a_large_projection_finite(self):
        cfg = dataclasses.replace(CFG, hc_res_clamp=(-3.0, 3.0))
        streams = jax.random.normal(jax.random.key(0), (5, 4, 32))
        p = lm.StreamMixer(cfg).init(jax.random.key(1), streams)["params"]
        p["alpha"] = jnp.asarray([1.0, 1.0, 500.0])
        _, _, h_res = stream_maps(cfg, p, streams)
        want = REF.stream_maps(cfg, streams, p)[2]
        assert np.all(np.isfinite(h_res))
        np.testing.assert_allclose(h_res, want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=1e-5)

    def test_a_layer_is_the_written_mixing(self, params):
        """``X' = H_res X + H_post (outer) F(norm(H_pre X))`` twice, against
        the reference's layer; layer 3 routes."""
        streams = jax.random.normal(jax.random.key(3), (20, 4, 32))
        p = params["layers_3"]
        want, (chosen, _), _ = REF.layer_forward(CFG, 3, streams, p)
        q_pos = jnp.arange(20, dtype=jnp.int32)
        got, _, routed = lm.DecoderLayer(CFG, 3).apply(
            {"params": p}, streams, q_pos, jnp.int32(0), jnp.int32(20),
            (empty(CFG, 32)["latent"][3],))
        assert got.shape == (20, 4, 32) and got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        assert np.array_equal(np.sort(routed[0], -1), np.sort(chosen, -1))

    def test_one_stream_zero_bias_softmax_is_the_model_it_was(self):
        """``TINY_LM`` builds the tree it built (no mixer, no bias, a
        ``(T, hidden)`` residual) and, told to carry a selection bias of
        zeros, gives the same logits bit for bit."""
        old = configs.TINY_EXPAND.expander
        assert (old.residual_streams, old.router_scoring,
                old.router_bias) == (1, "softmax", False)
        p = contract.lm_params(old)
        assert set(p["layers_1"]) == {"attn", "input_norm", "mlp",
                                      "post_attention_norm"}
        assert set(p["layers_1"]["mlp"]) == {"router", "experts",
                                             "shared_expert"}
        biased = dataclasses.replace(old, router_bias=True)
        q = contract.lm_params(biased)
        assert float(jnp.abs(
            q["layers_1"]["mlp"]["e_score_correction_bias"]).max()) == 0.0
        ids = jax.random.randint(jax.random.key(1), (24,), *old.vocab)
        a, cache_a, routed_a = run(old, p, ids, 0, 24, empty(old, 32))
        b, cache_b, routed_b = run(biased, q, ids, 0, 24, empty(biased, 32))
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(routed_a[0], routed_b[0])
        for x, y in zip(jax.tree_util.tree_leaves(cache_a),
                        jax.tree_util.tree_leaves(cache_b)):
            np.testing.assert_array_equal(x, y)

    def test_one_stream_traces_the_plain_residual(self):
        """No stream axis, no mixer's op, no sigmoid in the router."""
        old = configs.TINY_EXPAND.expander
        ids = jnp.zeros((4,), jnp.int32)
        text = str(jax.make_jaxpr(lambda p: run(
            old, p, ids, 0, 4, empty(old, 8)))(contract.param_shapes(old)))
        assert "f32[4,4,32]" not in text and "logistic" in text  # SiLU only
        latent = str(jax.make_jaxpr(lambda p: run(
            CFG, p, ids, 0, 4, empty(CFG, 8)))(contract.param_shapes(CFG)))
        assert "f32[4,4,32]" in latent


# -- a decode step's mixer as two kernels --------------------------------------

def only_at_one_token(platform, tokens, *args, **kw):
    """ops/stream_mixer.py:choose for a test that forces the kernels where
    a decode step would take them on the chip."""
    return stream_mixer.KERNEL if tokens == 1 else stream_mixer.LOOP


class TestTheMixerKernels:
    """ops/stream_mixer.py in interpret mode against the XLA form of
    ``models/lm.py:StreamMixer`` and ``written``."""

    @staticmethod
    def close(got, want, what):
        """1e-5 of the largest entry: the two forms sum in another order."""
        want = np.asarray(want)
        np.testing.assert_allclose(
            got, want, rtol=1e-5, atol=1e-5 * np.abs(want).max(),
            err_msg=what)

    @pytest.mark.parametrize("at_the_clamp", [False, True])
    @pytest.mark.parametrize("stored", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("hidden", [256, 3584])
    @pytest.mark.parametrize("streams", [2, 4])
    def test_the_kernels_equal_the_xla_form(self, monkeypatch, streams,
                                            hidden, stored, at_the_clamp):
        """Both forms on the same stored parameters (bf16 is widened, never
        narrowed): the read, the three maps and the streams written back.
        A projection driven to the clamp takes the ``exp`` to its ends."""
        cfg = dataclasses.replace(CFG, residual_streams=streams,
                                  hidden_size=hidden,
                                  hc_res_clamp=(-3.0, 3.0))
        x = 1.3 * jax.random.normal(jax.random.key(0), (1, streams, hidden))
        mixer = lm.StreamMixer(cfg)
        p = lm_params_of_a_mixer(mixer, x, stored, at_the_clamp)
        want = mixer.apply({"params": p}, x)
        assert want.tile is None
        monkeypatch.setattr(stream_mixer, "choose", only_at_one_token)
        got = mixer.apply({"params": p}, x)
        assert got.tile.shape == (8, 128) and got.tile.dtype == jnp.float32
        for name in ("read", "h_pre", "h_post", "h_res"):
            g, w = getattr(got, name), getattr(want, name)
            assert g.shape == w.shape and g.dtype == jnp.float32, name
            self.close(g, w, name)
        np.testing.assert_allclose(got.h_res.sum(-1), 1.0, atol=1e-3)
        if not at_the_clamp:
            np.testing.assert_allclose(got.h_res.sum(-2), 1.0, atol=1e-3)
        # nothing of the tile but the maps
        rest = np.asarray(got.tile).copy()
        rest[:streams, :streams + 2] = 0.0
        assert not rest.any()
        out = jax.random.normal(jax.random.key(5), (1, hidden))
        after = lm.written(x, got, out)
        assert after.shape == x.shape and after.dtype == x.dtype
        self.close(after, lm.written(x, want, out), "written")

    @pytest.mark.parametrize(
        "platform,tokens,streams,hidden,meshed,sinkhorn_dtype,path", [
            ("tpu", 1, 4, 3584, False, jnp.float32, "kernel"),
            ("tpu", 1, 2, 256, False, jnp.float32, "kernel"),
            ("tpu", 1, 8, 128, False, jnp.float32, "kernel"),
            ("cpu", 1, 4, 3584, False, jnp.float32, "loop"),
            ("gpu", 1, 4, 3584, False, jnp.float32, "loop"),
            ("tpu", 64, 4, 3584, False, jnp.float32, "loop"),
            ("tpu", 2, 4, 3584, False, jnp.float32, "loop"),
            ("tpu", 1, 1, 3584, False, jnp.float32, "loop"),
            ("tpu", 1, 9, 3584, False, jnp.float32, "loop"),
            ("tpu", 1, 4, 32, False, jnp.float32, "loop"),
            ("tpu", 1, 4, 3500, False, jnp.float32, "loop"),
            ("tpu", 1, 4, 3584, True, jnp.float32, "loop"),
            ("tpu", 1, 4, 3584, False, jnp.bfloat16, "loop"),
        ])
    def test_the_choice_is_made_from_what_the_call_shows(
            self, platform, tokens, streams, hidden, meshed, sinkhorn_dtype,
            path):
        assert stream_mixer.choose(
            platform, tokens, streams, hidden, meshed=meshed,
            sinkhorn_dtype=sinkhorn_dtype) == path

    def test_a_decode_chunk_on_the_kernels_equals_the_xla_form(
            self, monkeypatch):
        """The tiny preset with its hidden size on the lanes: one token's
        logits and cache on both forms, then a chunk of the decode scan
        drawing at temperature 1.0, whose mixers read what
        ``mixer_operands`` made outside the scan."""
        cfg = dataclasses.replace(CFG, hidden_size=128)
        p = CASE.params(0, cfg)
        module = lm.DecoderLM(cfg)
        ids = jax.random.randint(jax.random.key(1), (12,), *cfg.vocab)
        _, cache, _ = run(cfg, p, ids, 0, 12, empty(cfg, 32))
        args = (p, cache, ids[3], jnp.int32(12), jax.random.key(7),
                jnp.float32(1.0))

        def both():
            """Traced anew: the chooser is read when a mixer is traced."""
            logits, after, _ = jax.jit(lambda p, c: module.apply(
                {"params": p}, ids[3:4], jnp.int32(12), jnp.int32(1), c))(
                    p, cache)
            EXPANDER.clear()
            made = jax.jit(lm.decode_chunk_fn(module, 6))(*args)
            return logits, after, made, EXPANDER.summary()["mixer_products"]

        want = both()
        monkeypatch.setattr(stream_mixer, "choose", only_at_one_token)
        got = both()
        assert want[3] == {"kernel": 0, "loop": 8}
        assert got[3] == {"kernel": 8, "loop": 0}
        np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-4)
        for g, w in zip(jax.tree_util.tree_leaves(got[1]),
                        jax.tree_util.tree_leaves(want[1])):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
        assert np.array_equal(got[2][3], want[2][3])    # the steps' tokens
        assert len(set(np.asarray(want[2][3]).tolist())) > 2
        packed = lm.mixer_operands(p)
        assert set(packed) == {f"layers_{i}" for i in range(4)}
        phi_t, gates = packed["layers_2"]["mlp_hc"]["packed"]
        assert phi_t.shape == (24, 4 * 128) and gates.shape == (2, 8, 128)
        assert phi_t.dtype == p["layers_2"]["mlp_hc"]["phi"].dtype
        # a model with one stream has nothing to pack
        assert lm.mixer_operands(
            {"layers_0": {"attn": {"q_proj": {"kernel": ids}}}}) == {}

    def test_the_layer_metric_reads_the_status_block(self):
        """benchmarks/layer_metrics/x4_mixer_kernel_sites.json through the
        harness's own loader and reader, and its entry in BENCHMARK.json."""
        from benchmarks.harness import files

        bench = files.Bench(ROOT)
        spec = bench.layer_metric("x4_mixer_kernel_sites")
        reader = bench.load("readers", spec["reader"])
        EXPANDER.clear()
        EXPANDER.record_mixer("loop")
        status = {"status_before": {"serving": METRICS.summary()}}
        assert reader.read(status, **spec["args"]) == 0.0
        for _ in range(40):
            EXPANDER.record_mixer("kernel")
        status = {"status_before": {"serving": METRICS.summary()}}
        assert reader.read(status, **spec["args"]) == 40.0
        # the parent's block has no such counter: nothing to read
        del status["status_before"]["serving"]["expander"]["mixer_products"]
        assert reader.read(status, **spec["args"]) is None
        EXPANDER.clear()
        entry = next(m for m in bench.manifest["per_layer"]
                     if m["name"] == "x4_mixer_kernel_sites")
        assert entry == {key: spec[key] for key in (
            "name", "unit", "better", "source", "layer", "moves")} | {
                "workloads": ["sd15_xing4_expand_solo"]}
        assert (entry["layer"], entry["moves"]) == ("kernels",
                                                    "request_p50_s")


# -- the router ---------------------------------------------------------------

class TestTheRouter:
    def test_softmax_without_a_bias_is_the_default_and_unchanged(self):
        logits = jax.random.normal(jax.random.key(0), (50, 16))
        a = moe.route(logits, 4, renormalise=True, scale=2.5)
        scores = jax.nn.softmax(logits, -1)
        top, experts = jax.lax.top_k(scores, 4)
        np.testing.assert_array_equal(a.experts, experts)
        np.testing.assert_array_equal(
            a.weights, top / top.sum(-1, keepdims=True) * 2.5)
        b = moe.route(logits, 4, renormalise=True, scale=2.5,
                      scoring="softmax", bias=jnp.zeros(16))
        np.testing.assert_array_equal(a.experts, b.experts)
        np.testing.assert_array_equal(a.weights, b.weights)

    def test_the_bias_chooses_and_never_weighs(self):
        logits = jax.random.normal(jax.random.key(1), (200, 16))
        bias = 0.3 * jax.random.normal(jax.random.key(2), (16,))
        plain = moe.route(logits, 4, renormalise=True, scale=2.0,
                          scoring="sigmoid")
        biased = moe.route(logits, 4, renormalise=True, scale=2.0,
                           scoring="sigmoid", bias=bias)
        moved = np.any(np.sort(plain.experts, -1)
                       != np.sort(biased.experts, -1), axis=-1)
        assert 0.2 < moved.mean() < 1.0
        scores = jax.nn.sigmoid(logits)
        _, want = jax.lax.top_k(scores + bias, 4)
        np.testing.assert_array_equal(biased.experts, want)
        chosen = jnp.take_along_axis(scores, biased.experts, -1)
        np.testing.assert_allclose(
            biased.weights, 2.0 * chosen / chosen.sum(-1, keepdims=True),
            rtol=1e-6)
        np.testing.assert_allclose(biased.weights.sum(-1), 2.0, rtol=1e-6)
        # a bias that is the same for every expert moves nothing at all
        flat = moe.route(logits, 4, renormalise=True, scale=2.0,
                         scoring="sigmoid", bias=jnp.full(16, 0.7))
        np.testing.assert_array_equal(flat.experts, plain.experts)
        np.testing.assert_array_equal(flat.weights, plain.weights)

    def test_the_reference_routes_alike(self, params):
        n = jax.random.normal(jax.random.key(3), (40, 32))
        p = params["layers_2"]["mlp"]
        chosen, weights, moved = REF.route(CFG, n, p)
        logits = jnp.dot(n, p["router"], precision="highest")
        got = moe.route(logits, 4, renormalise=True, scale=2.0,
                        scoring="sigmoid", bias=p["e_score_correction_bias"])
        np.testing.assert_array_equal(got.experts, chosen)
        np.testing.assert_allclose(got.weights, weights, rtol=1e-5)
        assert 0 < float(moved.mean()) < 1


# -- the share ----------------------------------------------------------------

class TestTheShareOfALayer:
    def test_four_expert_shares_and_the_replicated_parts_once(self):
        """The reference's uncut expert layer against the sum of four
        chips' routed parts, with attention, both mixers and the shared
        expert counted once."""
        whole = dataclasses.replace(CFG, experts_held=None, vocab_held=None)
        p = CASE.params(4, whole)["layers_2"]
        streams = jax.random.normal(jax.random.key(9), (20, 4, 32))
        want, _, _ = REF.layer_forward(whole, 2, streams, p)
        attention = lambda u: REF._latent_attention(     # noqa: E731
            whole, 2, REF._norm(u, p["input_norm"], whole.rms_norm_eps),
            p["attn"])
        after, _ = REF.hyper_connected(whole, streams, p["attn_hc"],
                                       attention)
        h_pre, h_post, h_res = REF.stream_maps(whole, after, p["mlp_hc"])
        n = REF._norm(jnp.einsum("tn,tnc->tc", h_pre, after),
                      p["post_attention_norm"], whole.rms_norm_eps)
        shared = REF.shared_part(n, p["mlp"])
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
        total = jnp.einsum("tij,tjc->tic", h_res, after) \
            + h_post[:, :, None] * (shared + sum(parts))[:, None, :]
        np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)

    def test_four_vocabulary_slices_add_up_to_the_uncut_logits(self):
        """The table's rows are summed over chips in a deployment: the four
        chips' lookups of any ids add up to the uncut table's. The head's
        columns lie side by side: on ids of its own slice a chip's logits
        are the uncut model's columns of that slice."""
        whole = dataclasses.replace(CFG, vocab_held=None)
        p = CASE.params(5, whole)
        family = dataclasses.replace(FAMILY, expander=whole)
        forward = jax.jit(lambda p, i: REF.forward(family, p, i))
        table, head = p["embed_tokens"]["embedding"], p["lm_head"]["kernel"]
        ids = jax.random.randint(jax.random.key(6), (16,), 0, 512)
        parts = 0.0
        for rank in range(4):
            share = configs.lm_share(whole, 4, 4, rank)
            lo, count = share.vocab
            assert (lo, count) == (128 * rank, 128)
            local = ids - lo
            here = (local >= 0) & (local < count)
            parts = parts + table[lo:lo + count][
                jnp.clip(local, 0, count - 1)] * here[:, None]
            own = jax.random.randint(jax.random.key(7 + rank), (8,), lo,
                                     lo + count)
            sliced = dict(p, embed_tokens={"embedding": table[lo:lo + count]},
                          lm_head={"kernel": head[:, lo:lo + count]})
            held = dataclasses.replace(share, experts_held=whole.experts_held)
            got, _, _ = run(held, sliced, own, 0, 8, empty(held, 32))
            want = forward(p, own)
            assert got.shape == (8, 128) and want.shape == (8, 512)
            np.testing.assert_allclose(got, want[:, lo:lo + count],
                                       rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(parts, table[ids], rtol=1e-6)

    def test_the_share_of_the_published_model(self):
        share = configs.sd15_xing4_expander().expander
        assert share.num_layers == 20
        assert share.layer_types == ("latent",) * 20
        assert share.dense_layers == (0, 1)
        assert share.expert_layers == tuple(range(2, 20))
        assert share.experts == (0, 16) and share.vocab == (0, 32768)
        assert share.num_experts == 64 and share.num_experts_per_tok == 4
        assert share.latent_width == 576 and share.residual_streams == 4
        last = configs.lm_share(configs.XING4_0_29B_A4B, 20, 4, 3)
        assert last.experts == (48, 16) and last.vocab == (98304, 32768)
        # the third published shape the expert kernel tiles: two blocks
        # an expert through the ring's two slots
        assert moe_kernel.f_tile(3584, 1024, 2) == 512
        assert moe_kernel.ring(3584, 1024, 2) == moe_kernel.Ring(2, 512, 2)
        assert moe.choose("tpu", 1, jnp.bfloat16, 3584, 1024) == moe.KERNEL

    def test_the_share_has_4389_million_parameters(self):
        """Shapes only: latent attention 28.41 M a layer, two mixers 0.72 M
        with their norms, a dense MLP 99.09 M, sixteen experts 176.16 M."""
        share = configs.sd15_xing4_expander().expander
        layers, count = contract.param_shapes(share), contract.count
        assert round(count(layers["layers_0"]["attn"]) / 1e6, 2) == 28.41
        assert round(count(layers["layers_0"]["mlp"]) / 1e6, 2) == 99.09
        assert count(layers["layers_5"]["mlp"]["experts"]) \
            == 16 * 3 * 3584 * 1024
        assert count(layers["layers_5"]["attn_hc"]) \
            == 14336 * 24 + 14336 + 3 + 4 + 4 + 16
        assert layers["layers_5"]["mlp"]["e_score_correction_bias"].shape \
            == (64,)
        assert round(count(layers) / 1e6) == 4389


# -- the parameter tree, the sharding rules -----------------------------------

class TestTheTreeAndItsRules(contract.ShardingRules):
    def test_the_presets_parameter_tree(self, params):
        attn = params["layers_0"]["attn"]
        assert set(attn) == {"q_a_proj", "q_a_norm", "q_b_proj",
                             "kv_a_proj_with_mqa", "kv_a_norm", "kv_b_proj",
                             "o_proj"}
        assert attn["q_a_proj"]["kernel"].shape == (32, 24)
        assert attn["q_b_proj"]["kernel"].shape == (24, 4 * 16)
        assert attn["kv_a_proj_with_mqa"]["kernel"].shape == (32, 16 + 8)
        assert attn["kv_b_proj"]["kernel"].shape == (16, 4 * 16)
        assert attn["o_proj"]["kernel"].shape == (4 * 8, 32)
        hc = params["layers_0"]["mlp_hc"]
        assert set(hc) == {"phi", "alpha", "b_pre", "b_post", "b_res",
                           "norm"}
        assert hc["phi"].shape == (4 * 32, 16 + 8)
        assert hc["norm"]["scale"].shape == (128,)
        assert set(params["layers_1"]["mlp"]) == {"gate_proj", "up_proj",
                                                  "down_proj"}
        assert set(params["layers_2"]["mlp"]) == {
            "router", "e_score_correction_bias", "experts", "shared_expert"}

    WHOLE = (("layers_0/attn/q_a_proj/kernel", 2),
             ("layers_0/attn/q_b_proj/kernel", 2),
             ("layers_0/attn/kv_a_proj_with_mqa/kernel", 2),
             ("layers_0/attn/kv_b_proj/kernel", 2),
             ("layers_0/attn_hc/phi", 2),
             ("layers_0/mlp_hc/b_res", 2),
             ("layers_0/mlp_hc/norm/scale", 1),
             ("layers_2/mlp/e_score_correction_bias", 1))
    EXPERT_LAYER = 2
    PLACED_WHOLE = ("layers_1/attn/kv_b_proj/kernel",
                    "layers_1/attn_hc/phi")
    test_sharding_leaves_the_new_leaves_whole = contract.ShardingRules.sharding_rules

    def check_placed(self, placed, mesh):
        from jax.sharding import PartitionSpec as P

        assert placed["embed_tokens"]["embedding"].sharding.spec \
            == P("vp", None)


# -- the engine path ----------------------------------------------------------

class TestEnginePath(contract.SoloEnginePath):
    CASE = CASE
    STATUS_KEYS = frozenset({
        "state_bytes", "cache_positions", "residual_streams",
        "sinkhorn_iters", "expert_products", "mixer_products"})
    test_spans = contract.SoloEnginePath.spans_of_a_request

    def check_stats(self, stats):
        assert stats["cache_positions"] == {"full": 0, "sliding": 0,
                                            "latent": 4 * 76}
        assert stats["state_bytes"]["latent"] == 4 * 256 * 24 * 4
        assert (stats["residual_streams"], stats["sinkhorn_iters"]) \
            == (4, 20)
        assert len(stats["expert_tokens"]) == 2        # two expert layers
        assert stats["padded_rows_masked"] == 0        # no recurrence

    def check_prefill_span(self, args):
        assert args["latent"] == "latent_expanded"
        assert "form" not in args       # no recurrence to name

    def check_status(self, block):
        assert set(block["mixer_products"]) == {"kernel", "loop"}
        # on a CPU, in float32, at these widths every expert layer loops
        assert block["expert_products"]["kernel"] == 0

    def test_sites_are_counted_by_form_when_the_model_is_traced(self):
        """A new engine's first request: the decode scan's body is traced
        once and both prefills share one executable (one bucket, 64)."""
        fresh = CASE.engine()
        ATTENTION.clear()
        EXPANDER.clear()
        fresh.txt2img(CASE.payload())
        fresh.txt2img(CASE.payload())
        # /internal/status: eight mixers a trace, two traces, none of them
        # the kernel on a CPU
        assert METRICS.summary()["expander"]["mixer_products"] == {
            "kernel": 0, "loop": 16}
        sites = ATTENTION.summary()
        assert sites["latent_absorbed"] == 4
        assert sites["latent_expanded"] == 4
        assert sites["by_shape"]["T1 S256 D24"] == {"latent_absorbed": 4}
        assert sites["by_shape"]["T64 S256 D24"] == {"latent_expanded": 4}

    def test_an_expander_with_one_stream_reports_no_sinkhorn(self):
        engine = contract.engine_for(configs.TINY_EXPAND)
        EXPANDER.clear()
        engine.txt2img(CASE.payload())
        stats = EXPANDER.summary()
        assert (stats["residual_streams"], stats["sinkhorn_iters"]) == (1, 0)
        assert set(stats["state_bytes"]) == {"full", "sliding"}

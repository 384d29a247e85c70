"""The resident prompt expander whose layer is a SHORTCUT-CONNECTED double
layer (two latent attentions, two dense SwiGLUs, ONE router whose sum lands
one attention and one MLP after it) over experts that have kernels AND
zero-compute identity experts, under two latent scales: spelled as two
entries of ``LMConfig``'s lists (``moe_shortcut``, ``zero_experts``,
``latent_q_scale``, ``latent_kv_scale``), decoded as the sequences of ONE
step over ONE shared latent cache, forked from one prefill.

Everything runs the tiny preset (models/configs.py
``TINY_LONGCAT_FLASH_LM``: two double layers of 4 heads through a 24-wide
query latent over a cached 16 + 8, 16 experts and 8 identity experts top-4
by biased softmax scores at scale 6, 4 of the 16 held). The plain reference
is the benchmark's own (benchmarks/reference/longcat_flash_ref.py: float32,
one sequence, no cache, the expanded attention only, a layer ONE function
of the five published equations).
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.obs import prometheus
from stable_diffusion_webui_distributed_tpu.ops import moe, moe_kernel
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER,
)
from tests import expander_contract as contract
from tests.expander_contract import STEPS, rel_rms, run

REF = contract.load_reference("longcat_flash")
FAMILY = configs.TINY_LONGCAT_FLASH_EXPAND
#: the norms off 1 and the selection bias off 0 on the scale of a softmax
#: score over 24 outputs (half-width about 0.5 / 24, as the benchmark
#: seeds it over 768)
CASE = contract.Case(
    FAMILY, REF,
    how=(("spread", (("scale", 0.2), ("e_score_correction_bias", 0.012))),),
    control_floor=1e-2, control_size=148,
    controls=("control", "no_identity_term", "no_held_experts",
              "no_shortcut", "no_q_scale", "no_kv_scale"))
CFG = CASE.cfg
params, engine = contract.fixtures(CASE)


def replaced(**how):
    return dataclasses.replace(CFG, **how)


# -- (a) program against reference --------------------------------------------

class TestAgainstTheReference(contract.ForkedAgainstTheReference,
                              contract.StagedAsTheTimedPathRunsIt):
    """Expanded form, a copy, a fork into four and the forked absorbed
    form, the routed sum forking with the rows: logits to 1e-5 and routing
    identical. The router's product in bfloat16, the identity term or the
    held experts' part dropped, the routed sum added in place, either
    latent scale left out: each reads far from the reference."""
    CASE = CASE
    test_prefill_fork_and_decode_match_four_full_forwards = \
        contract.ForkedAgainstTheReference.program_matches_four_full_forwards
    PARAMETERS = {
        "test_prefill_fork_and_decode_match_four_full_forwards": [
            ("size", [37, 148])],
        "test_each_control_is_further_from_the_reference": [
            ("control", [name for name, _ in REF.CONTROLS])]}

    def test_one_sequence_through_the_cache_matches_the_full_forward(
            self, params):
        """A prefill chunk (expanded form), then one token a step through
        the cache (absorbed form), the routed sum carried inside each
        step: the reference's one full forward of the one sequence."""
        ids, continuations = REF.inputs(FAMILY, 5, 37)
        one = continuations[:1]
        want, chosen = jax.jit(lambda p, i, c: REF.forward(
            FAMILY, p, i, c, with_routing=True))(params, ids, one)
        cache = contract.empty(CFG, 64)
        got, cache, routed = run(CFG, params, ids, 0, ids.shape[0], cache)
        rows, picks = [got], [routed[0]]
        for t, token in enumerate(np.asarray(one[0])):
            got, cache, routed = run(
                CFG, params, jnp.array([token], jnp.int32),
                ids.shape[0] + t, 1, cache)
            rows.append(got)
            picks.append(routed[0])
        assert rel_rms(jnp.concatenate(rows), want) < 1e-5
        assert np.array_equal(np.sort(jnp.concatenate(picks, axis=1), -1),
                              np.sort(chosen, -1))
        # four routed parts: the zero-compute picks of the rows that count
        assert len(routed) == 4 and routed[3].shape == (2,)

    def test_the_selection_bias_changes_some_choices_and_not_all(
            self, params):
        assert 0.05 < REF.bias_changes_share(CFG, params) < 0.95

    @pytest.mark.parametrize("fault,control", [
        ("in_place", "no_shortcut"), ("no_q_scale", "no_q_scale"),
        ("no_kv_scale", "no_kv_scale")])
    def test_a_wrong_program_is_the_reference_with_the_same_fault(
            self, params, fault, control):
        """The shortcut's sum lands after the NEXT layer's MLP: a program
        that adds it in place (``moe_shortcut`` off) is far from the
        reference and IS the reference that adds it in place; so each
        latent scale, through the expanded and the forked form."""
        inputs, want, _ = CASE.referred(74)
        wrong = CASE.program(**{control: True})(params, *inputs)
        assert rel_rms(wrong, want) > 0.1
        same = jax.jit(lambda p, i, c: REF.forward(
            FAMILY, p, i, c, fault=fault))(params, *inputs)
        assert rel_rms(wrong, same) < 1e-5
        assert rel_rms(same, want) > 0.1


# -- (b) a step over B sequences ----------------------------------------------

class TestSequencesOfOneStep(contract.SequencesOfOneStep,
                             contract.WhichKindsShareAStep):
    """Latent layers of one stream share a step, a routed sum that crosses
    a layer too: it is a row's, so it forks with the rows."""
    CASE = CASE
    SHARE = ("sd15_longcat_flash_expander", "sd15_kanana2_expander")
    ONE_A_STEP = ("sd15_xing4_expander",)
    PARAMETERS = {
        "test_a_forked_decode_is_each_sequence_alone": [
            ("user,live,batch", [(1, 4, 4), (64, 3, 4), (16, 2, 2)])],
        "test_which_kinds_share_a_step": [("preset,shares", [
            ("TINY_LONGCAT_FLASH_EXPAND", True),
            ("TINY_KANANA_EXPAND", True), ("TINY_LATENT_EXPAND", False)])]}

    test_a_fork_copies_nothing = contract.SequencesOfOneStep \
        .a_fork_shares_what_has_positions_and_copies_the_rest

    def check_fork(self, forked):
        assert set(forked) == {"latent", "latent_shared", lm.FORKED_AT}
        assert [x.shape for x in forked["latent"]] \
            == [(4, 2 * STEPS, 24)] * 4

    def test_the_identity_picks_of_a_forked_chunk_are_the_sequences_own(
            self, params):
        """What the chunk of four counts beside its load is what the four
        count alone, the pad left out."""
        rest, rests = contract.forked_against_alone(CASE, params, 3, 4,
                                                    user=7)
        (together,) = rest
        assert together.shape == (2,) and together.dtype == jnp.int32
        assert np.array_equal(together, sum(own[0] for own in rests))
        # 4 picks a row a router, a third of the outputs identity experts
        assert 0 < int(together.sum()) < 3 * STEPS * 2 * 4


# -- (c) the router's sum -----------------------------------------------------

def _moe(cfg, p, n, valid=None):
    valid = jnp.ones((n.shape[0],), bool) if valid is None else valid
    return jax.jit(lambda p, n, v, m=lm.MoE(cfg): m.apply(
        {"params": p}, n, v))(p, n, valid)


class TestTheRoutersSum:
    def test_the_shares_the_identity_term_and_the_dense_path_once(self):
        """The guide's share test: the held parts that every share of the
        experts gives for one layer, with the identity experts' term and
        the first dense SwiGLU counted ONCE, add up to the uncut
        reference's ``M(n) + F_0(n)``, through the program's own expert
        layer."""
        whole = configs.lm_share(configs.TINY_LONGCAT_FLASH_LM, 4, chips=1,
                                 rank=0)
        assert whole.experts == (0, 16) and whole.num_experts == 24
        uncut = CASE.params(3, whole)
        n = jax.random.normal(jax.random.key(9), (6, 32))
        p = uncut["layers_0"]["mlp"]
        chosen, weights = REF.route(whole, n, p)
        want = REF.routed_sum(whole, n, chosen, weights, p["experts"]) \
            + REF._swiglu(n, p["shared_expert"])
        identity = moe.identity_part(n, moe.Routing(chosen, weights), 16)
        assert float(jnp.abs(identity).max()) > 0
        chips, parts = 4, []
        for rank in range(chips):
            share = configs.lm_share(configs.TINY_LONGCAT_FLASH_LM, 4,
                                     chips=chips, rank=rank)
            assert share.experts == (4 * rank, 4)
            mine = {**p, "experts": {
                name: w[4 * rank:4 * rank + 4]
                for name, w in p["experts"].items()}}
            dense, routed, beside = _moe(share, mine, n)
            np.testing.assert_allclose(
                dense, REF._swiglu(n, p["shared_expert"]), rtol=2e-5,
                atol=2e-5)
            assert np.array_equal(np.sort(beside[0], -1),
                                  np.sort(chosen, -1))
            parts.append(routed - identity)     # every chip adds the term
        assert rel_rms(parts[0] + identity + dense, want) > 0.05
        np.testing.assert_allclose(sum(parts) + identity + dense, want,
                                   rtol=2e-4, atol=2e-5)
        # the uncut layer itself, through the program's expert layer
        dense, routed, _ = _moe(whole, p, n)
        np.testing.assert_allclose(routed + dense, want, rtol=2e-4,
                                   atol=2e-5)

    def test_a_token_all_of_whose_picks_are_identity_experts(self, params):
        """A bias that chooses (and does not weigh) sends every pick to
        ids 16-23: the routed sum is exactly ``(sum w) * n``, no expert is
        held or read, and the picks count as identity picks alone."""
        p = dict(params["layers_0"]["mlp"])
        p["e_score_correction_bias"] = jnp.where(jnp.arange(24) >= 16, 9.0,
                                                 0.0)
        n = jax.random.normal(jax.random.key(4), (5, 32))
        valid = jnp.array([True, True, True, True, False])
        # no expert's kernels are touched: were one, this would show
        p["experts"] = jax.tree_util.tree_map(
            lambda w: jnp.full_like(w, jnp.nan), p["experts"])
        _, routed, (chosen, load, none_held, zero) = _moe(CFG, p, n, valid)
        assert np.all(np.asarray(chosen) >= 16)
        scores = jax.nn.softmax(n @ p["router"], axis=-1)
        total = jnp.sum(6.0 * jnp.take_along_axis(scores, chosen, -1), -1)
        np.testing.assert_allclose(routed, total[:, None] * n, rtol=1e-6,
                                   atol=0)
        assert np.array_equal(
            np.asarray(routed), np.asarray(moe.identity_part(
                n, moe.Routing(chosen, 6.0 * jnp.take_along_axis(
                    scores, chosen, -1)), 16)))
        assert not np.any(np.asarray(load)) and int(none_held) == 4
        assert int(zero) == 4 * 4           # the padded row is left out
        # and the other way: every pick a real expert
        p["experts"] = params["layers_0"]["mlp"]["experts"]
        p["e_score_correction_bias"] = jnp.where(jnp.arange(24) < 16, 9.0,
                                                 0.0)
        _, routed, (chosen, load, _, zero) = _moe(CFG, p, n, valid)
        assert np.all(np.asarray(chosen) < 16) and int(zero) == 0
        assert int(load.sum()) == int(np.sum(np.asarray(chosen)[:4] < 4))

    def test_identity_picks_are_neither_held_nor_absent(self):
        """The load, the experts read and the tokens with no held expert
        are about the experts that have kernels, whatever the router's
        last ids are; the identity term is float32 ``(sum w) * n``."""
        routing = moe.Routing(
            jnp.array([[0, 17, 5, 20], [16, 23, 9, 8], [1, 2, 3, 22]],
                      jnp.int32),
            jnp.array([[.1, .2, .3, .4], [.5, .6, .7, .8],
                       [.9, 1., 1.1, 1.2]], jnp.float32))
        load, none_held = moe.load_counts(routing, 0, 4)
        assert load.tolist() == [1, 1, 1, 1] and int(none_held) == 1
        assert int(moe.experts_read(load)) == 4
        assert int(moe.identity_picks(routing, 16)) == 5
        assert int(moe.identity_picks(
            routing, 16, jnp.array([True, False, True]))) == 3
        x = jax.random.normal(jax.random.key(0), (3, 8)).astype(jnp.bfloat16)
        got = moe.identity_part(x, routing, 16)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(
            got, np.array([.6, 1.1, 1.2], np.float32)[:, None]
            * np.asarray(x, np.float32), rtol=1e-6)
        # every product passes an identity pick by as it passes an absent one
        ks = jax.random.split(jax.random.key(1), 4)
        share = [jax.random.normal(k, s) for k, s in zip(
            ks, ((4, 8, 16), (4, 8, 16), (4, 16, 8)))]
        xs = jax.random.normal(ks[3], (3, 8))
        grouped = moe._grouped(xs, routing, *share, 0, 24)
        real = moe.Routing(jnp.where(routing.experts >= 16, 15,
                                     routing.experts), routing.weights)
        np.testing.assert_allclose(
            grouped, moe._grouped(xs, real, *share, 0, 16), rtol=1e-6)

    def test_a_step_of_forty_eight_picks_through_the_kernel(self):
        """The published step in interpret mode at narrow widths: four
        rows of twelve picks over 768 outputs, 16 held, 256 identity: the
        block walks at most 16 distinct experts and equals the grouped
        product."""
        d, f, held = 128, 256, 16
        ks = jax.random.split(jax.random.key(2), 5)
        share = [jax.random.normal(ks[0], (held, d, f)) / d ** 0.5,
                 jax.random.normal(ks[1], (held, d, f)) / d ** 0.5,
                 jax.random.normal(ks[2], (held, f, d)) / f ** 0.5]
        x = jax.random.normal(ks[3], (4, d))
        # twelve distinct picks a row: four held, four identity, four absent
        picks = [[r, r + 4, (r + 8) % 16, 15 - r, 512 + r, 600, 700 + r,
                  767, 16 + r, 100, 200 + r, 511] for r in range(4)]
        routing = moe.Routing(
            jnp.array(picks, jnp.int32),
            0.05 + jax.random.uniform(ks[4], (4, 12)))
        got = moe._block(x, routing, *share, 0)
        want = moe._grouped(x, routing, *share, 0, 768)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        load, none_held = moe.load_counts(routing, 0, held)
        assert int(load.sum()) == 16 and int(none_held) == 0
        assert int(moe.identity_picks(routing, 512)) == 16
        # the eighth published expert shape: rows over 4 096 are read a
        # lane width a block, sixteen an expert (256 columns fit, 3-4 %
        # slower a call)
        assert moe_kernel.ring(6144, 2048, 2) == moe_kernel.Ring(2, 128, 16)
        assert moe_kernel.f_tile(6144, 2048, 2) == 128
        assert 2 * 3 * 6144 * 128 * 2 == 9_437_184
        assert moe.choose("tpu", 4, jnp.bfloat16, 6144, 2048) == "kernel"
        # a prefill's tile: 2 048 rows of 12 over the router's 768 outputs
        assert moe.row_tile(2048, 12, 768) == 32


# -- (d) the latent scales ----------------------------------------------------

class TestTheLatentScales:
    @pytest.mark.parametrize("scale", ["latent_q_scale", "latent_kv_scale"])
    def test_each_scale_is_told_apart_in_all_three_forms(self, params,
                                                         scale):
        """Expanded (a chunk), absorbed (one token) and forked (one token
        each of four sequences) read ONE cache and agree with each other
        under the scales; a program without one of them differs in every
        form, and its cache rows differ where the scale lands in them."""
        ids = jax.random.randint(jax.random.key(1), (24,), *np.cumsum(
            CFG.vocab))
        without = replaced(**{scale: 1.0})
        results = {}
        for name, cfg in (("with", CFG), ("without", without)):
            chunk, cache, _ = run(cfg, params, ids, 0, 24,
                                  contract.empty(cfg, 64))
            # the last token again as a step of one over the first 23
            _, before, _ = run(cfg, params, ids[:23], 0, 23,
                               contract.empty(cfg, 64))
            step, _, _ = run(cfg, params, ids[23:], 23, 1, before)
            forked, _, _ = run(
                cfg, params, jnp.tile(ids[23:], 4), 23, 4,
                kv.fork(before, 4, 8), sequences=True)
            results[name] = (chunk[-1], step[0], forked[0], forked[3],
                             cache["latent"][0])
        expanded, absorbed, forked, last, rows = results["with"]
        np.testing.assert_allclose(absorbed, expanded, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(forked, expanded, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(last, expanded, rtol=2e-5, atol=2e-5)
        for mine, theirs in zip(results["with"][:4],
                                results["without"][:4]):
            assert rel_rms(theirs, mine) > 0.05
        # the cache holds the latent scaled, the rotated key as it was
        bare = results["without"][4]
        if scale == "latent_kv_scale":
            np.testing.assert_allclose(
                rows[:24, :16], bare[:24, :16] * CFG.latent_kv_scale,
                rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_array_equal(rows, bare)
        np.testing.assert_array_equal(rows[:24, 16:], bare[:24, 16:])

    def test_the_sites_are_counted_by_form(self, params):
        shapes = contract.param_shapes(CFG)     # an init is a trace too
        EXPANDER.clear()
        contract.sites_of(CFG, shapes, jnp.zeros((64,), jnp.int32), 0, 64,
                          contract.cache_structs(CFG, 128, jnp.float32))
        contract.sites_of(CFG, shapes, jnp.zeros((1,), jnp.int32), 64, 1,
                          contract.cache_structs(CFG, 128, jnp.float32))
        contract.sites_of(
            CFG, shapes, jnp.zeros((4,), jnp.int32), 64, 4,
            contract.forked_structs(CFG, 128, 4, 32, jnp.float32),
            sequences=True)
        stats = EXPANDER.summary()
        assert stats["latent_scaled"] == {
            "latent_expanded": 4, "latent_absorbed": 4, "latent_forked": 4}
        assert stats["moe_shortcuts"] == {
            "chunked": 2, "recurrent": 2, "recurrent_forked": 2}
        text = prometheus.render()
        assert 'sdtpu_expander_moe_shortcuts_total{form="recurrent_forked"}' \
            " 2" in text
        assert 'sdtpu_expander_latent_scaled_total{form="latent_forked"} 4' \
            in text
        # a sibling with neither counts nothing
        other = configs.TINY_KANANA_EXPAND.expander
        shapes = contract.param_shapes(other)
        EXPANDER.clear()
        contract.sites_of(other, shapes,
                          jnp.zeros((64,), jnp.int32), 0, 64,
                          contract.cache_structs(other, 128, jnp.float32))
        stats = EXPANDER.summary()
        assert not any(stats["latent_scaled"].values())
        assert not any(stats["moe_shortcuts"].values())
        EXPANDER.clear()


# -- (e) the config, the tree and its rules -----------------------------------

class TestTheConfigAndTheTree(contract.ShardingRules):
    def test_a_shortcut_wants_a_dense_layer_behind_every_expert_layer(self):
        base = dict(layer_types=("latent",) * 4, num_heads_per_layer=(4,) * 4,
                    moe_shortcut=True)
        configs.LMConfig(dense_layers=(1, 3), **base)
        for dense in ((0, 2), (1,), (), (0, 1, 2)):
            with pytest.raises(ValueError, match="moe_shortcut"):
                configs.LMConfig(dense_layers=dense, **base)
        with pytest.raises(ValueError, match="moe_shortcut"):
            configs.LMConfig(dense_layers=(1, 3), residual_streams=4,
                             **base)
        with pytest.raises(ValueError, match="moe_shortcut"):
            configs.LMConfig(dense_layers=(1, 3),
                             shared_expert_intermediate_size=0, **base)
        with pytest.raises(ValueError):     # a looped stack is dense
            configs.LMConfig(layer_types=("full",) * 2,
                             num_heads_per_layer=(4, 4), dense_layers=(1,),
                             total_ut_steps=2, moe_shortcut=True)
        with pytest.raises(ValueError, match="zero_experts"):
            configs.LMConfig(num_experts=8, zero_experts=9)

    def test_the_defaults_are_the_old_model(self):
        cfg = configs.LMConfig()
        assert (cfg.zero_experts, cfg.moe_shortcut, cfg.latent_q_scale,
                cfg.latent_kv_scale) == (0, False, 1.0, 1.0)
        assert cfg.real_experts == cfg.num_experts
        assert cfg.experts == (0, cfg.num_experts)
        assert lm.site_attrs(configs.TINY_KANANA_EXPAND.expander) == {}
        assert lm.site_attrs(CFG) == {"moe_shortcuts": 2, "zero_experts": 8}
        # the experts held are a range of those that have kernels
        whole = configs.TINY_LONGCAT_FLASH_LM
        assert whole.real_experts == 16 and whole.experts == (0, 16)
        assert [configs.lm_share(whole, 4, chips=4, rank=r).experts
                for r in range(4)] == [(0, 4), (4, 4), (8, 4), (12, 4)]
        assert lm.no_zero_picks(configs.TINY_KANANA_EXPAND.expander) == ()
        (zero,) = lm.no_zero_picks(CFG)
        assert zero.shape == (2,)

    def test_one_published_layer_is_two_entries_of_the_tree(self, params):
        first, second = params["layers_0"], params["layers_1"]
        assert set(first) == set(second) == {
            "attn", "mlp", "input_norm", "post_attention_norm"}
        assert set(first["attn"]) == set(second["attn"]) == {
            "q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj_with_mqa",
            "kv_a_norm", "kv_b_proj", "o_proj"}
        assert set(first["mlp"]) == {"router", "e_score_correction_bias",
                                     "experts", "shared_expert"}
        assert set(second["mlp"]) == {"gate_proj", "up_proj", "down_proj"}
        # the router keeps its width; no leaf for an identity expert
        assert first["mlp"]["router"].shape == (32, 24)
        assert first["mlp"]["e_score_correction_bias"].shape == (24,)
        assert first["mlp"]["experts"]["w_gate"].shape == (4, 32, 16)
        assert first["mlp"]["shared_expert"]["gate_proj"]["kernel"].shape \
            == second["mlp"]["gate_proj"]["kernel"].shape == (32, 64)
        named = REF.double_layer_params(params, 0)
        assert named["mlps"][0] is first["mlp"]["shared_expert"]
        assert named["mlps"][1] is second["mlp"]

    #: every leaf of the preset falls under a rule that was there
    WHOLE = (("layers_0/attn/kv_a_proj_with_mqa/kernel", 2),
             ("layers_0/attn/kv_b_proj/kernel", 2),
             ("layers_0/attn/kv_a_norm/scale", 1),
             ("layers_0/attn/q_a_norm/scale", 1),
             ("layers_0/mlp/e_score_correction_bias", 1))
    EXPERT_LAYER = 0
    PLACED_WHOLE = ("layers_1/attn/kv_b_proj/kernel",
                    "layers_0/mlp/e_score_correction_bias")
    test_sharding_rules = contract.ShardingRules.sharding_rules


# -- (f) the engine's path ----------------------------------------------------

class TestEnginePath(contract.ForkedEnginePath):
    test_a_batch_prefills_once_forks_and_decodes_four_a_step = \
        contract.ForkedEnginePath \
        .a_batch_prefills_once_forks_and_decodes_four_a_step
    test_every_image_its_own_expansion_and_one_image_the_old_path = \
        contract.ForkedEnginePath \
        .every_image_its_own_expansion_and_one_image_the_old_path
    CASE = CASE

    def check_traced(self, sites, traced):
        assert sites["latent_forked"] == 4 and sites["latent_expanded"] == 8
        assert "latent_absorbed" not in sites
        # two prefill executables and the forked step, two routers each
        assert traced["moe_shortcuts"] == {
            "chunked": 4, "recurrent": 0, "recurrent_forked": 2}
        assert traced["latent_scaled"] == {
            "latent_expanded": 8, "latent_absorbed": 0, "latent_forked": 4}
        assert traced["expert_products"]["kernel"] == 0      # a CPU

    def check_counted(self, stats, sizes, one):
        assert stats["tokens_no_held_expert"] > 0       # a share
        # picks of 64 steps' four rows on 8 of 24 outputs, two routers:
        # 683 under even routing
        assert 300 < stats["zero_expert_picks"] < 1100
        assert stats["experts_read"] <= 2 * STEPS * 2 * 4
        assert stats["cache_positions"] == {
            "full": 0, "sliding": 0, "latent": 4 * (36 + 4 * 40)}
        text = prometheus.render()
        assert "sdtpu_expander_zero_expert_picks_total " \
            f"{stats['zero_expert_picks']}" in text

    def check_spans(self, by_name, sizes, one):
        (prefill,) = by_name["expand.prefill"]
        assert prefill["latent"] == "latent_expanded"
        assert prefill["moe_shortcuts"] == 2 and prefill["zero_experts"] == 8
        (fork,) = by_name["expand.fork"]
        assert fork["latent"] == "latent_forked"
        assert fork["bytes"] == 4 * 4 * 2 * STEPS * 24 * 4
        for chunk in by_name["expand.decode_chunk"]:
            assert chunk["latent"] == "latent_forked"
            assert chunk["moe_shortcuts"] == 2
        (account,) = by_name["expand.account"]
        # two a call (load, none held), the reads, the unread calls and
        # the identity picks of the two decode calls
        assert account["fetched"] == 2 * 3 + 2 + 2 + 2

    def check_one_image(self, sites, stats):
        assert sites["latent_absorbed"] == 4
        assert stats["moe_shortcuts"]["recurrent"] == 2
        assert stats["zero_expert_picks"] > 0


# -- (g) the published share, from shapes -------------------------------------

class TestThePublishedShare:
    def test_parameters_and_bytes_from_shapes(self):
        share = configs.sd15_longcat_flash_expander().expander
        whole = configs.LONGCAT_FLASH_CHAT
        assert whole.num_layers == 56 and share.num_layers == 8
        assert whole.dense_layers == tuple(range(1, 56, 2))
        assert (whole.num_experts, whole.zero_experts, whole.real_experts,
                whole.num_experts_per_tok) == (768, 256, 512, 12)
        assert share.experts == (0, 16) and share.vocab == (0, 16384)
        assert (whole.latent_q_scale, whole.latent_kv_scale) \
            == (2.0, 12 ** 0.5)
        assert whole.latent_softmax_scale == 192 ** -0.5
        shapes, count = contract.param_shapes(share), contract.count
        attn = count(shapes["layers_0"]["attn"])
        assert attn == count(shapes["layers_1"]["attn"]) \
            == 90_570_752 + 1536 + 512
        dense = 3 * 6144 * 12288
        assert count(shapes["layers_0"]["mlp"]["shared_expert"]) \
            == count(shapes["layers_1"]["mlp"]) == dense
        assert shapes["layers_0"]["mlp"]["router"].shape == (6144, 768)
        expert = 3 * 6144 * 2048
        assert count(shapes["layers_0"]["mlp"]["experts"]) == 16 * expert
        # one published layer: 638.8 M fixed and 16 experts of 37.75 M
        fixed = 2 * attn + 2 * dense + 6144 * 768 + 768 + 4 * 6144
        # without the norms' and the bias's 0.03 M, ISSUE 67's figure
        assert round((2 * 90_570_752 + 2 * dense + 6144 * 768) / 1e6, 1) \
            == 638.8
        assert round(expert / 1e6, 2) == 37.75
        assert count(shapes["layers_0"]) + count(shapes["layers_1"]) \
            == fixed + 16 * expert
        total = count(shapes)
        assert total == 4 * (fixed + 16 * expert) + 2 * 16384 * 6144 + 6144
        assert round(total / 1e6) == 5173      # 5 172.6 without norms
        assert round(total * 2 / 1e9, 2) == 10.35
        # beside SD1.5's 1 066 M: 12.48 GB = 11.62 GiB
        assert round((total + 1066e6) * 2 / 1e9, 2) == 12.48
        assert round((total + 1066e6) * 2 / 2 ** 30, 2) == 11.62
        # ISSUE 67's fallback, 8 experts a layer: 7.93 GB of expander
        assert round((total - 4 * 8 * expert) * 2 / 1e9, 2) == 7.93
        # the whole model: 560.7 B
        assert round((28 * (fixed + 512 * expert) + 2 * 131072 * 6144)
                     / 1e9, 1) == 560.7
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
        v5e): four expert layers through the pipelined kernel, eight
        forked latent sites over 2 560 shared and 256 own rows of 576,
        four routed sums that cross a layer."""
        share = configs.sd15_longcat_flash_expander().expander
        one = contract.cache_structs(share, 2560)
        cache = contract.forked_structs(share, 2560, 4, 256)
        assert [x.shape for x in cache["latent"]] == [(4, 256, 576)] * 8
        shapes = contract.param_shapes(share)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ATTENTION.clear()
        EXPANDER.clear()
        logits, after, routed = contract.sites_of(
            share, shapes, jnp.zeros((4,), jnp.int32), 2200, 4,
            cache, jnp.bfloat16, sequences=True)
        assert logits.shape == (4, 16384)
        assert jax.tree_util.tree_map(lambda x: x.shape, after) \
            == jax.tree_util.tree_map(lambda x: x.shape, cache)
        assert routed[0].shape == (4, 4, 12) and routed[1].shape == (4, 16)
        assert routed[3].shape == (4,)
        stats = EXPANDER.summary()
        assert stats["expert_products"] == {
            "kernel": 4, "loop": 0, "grouped": 0}
        assert stats["moe_shortcuts"]["recurrent_forked"] == 4
        assert stats["latent_scaled"]["latent_forked"] == 8
        assert ATTENTION.summary()["by_shape"] == {
            "T4 S2560+256 D576": {"latent_forked": 8}}
        # a prefill chunk keeps the grouped product and the expanded form
        contract.sites_of(
            share, shapes, jnp.zeros((64,), jnp.int32), 2048, 64,
            one, jnp.bfloat16)
        assert EXPANDER.summary()["expert_products"]["grouped"] == 4
        assert ATTENTION.summary()["latent_expanded"] == 8
        ATTENTION.clear()
        EXPANDER.clear()


# -- (h) the executables the benchmark already runs ---------------------------

#: sha256 (first 16 hex digits) of the lowered text of the expander
#: executables of the four tiny presets tests/test_kanana_expander.py does
#: not pin, at commit b6c8a85 (PR 66), by its ``lowered_texts`` (PR 70
#: replaced the forked decode chunk's of the two with expert layers: it
#: returns the steps that streamed no expert, one more carry of the scan)
PARENT = {
    "TINY_KANANA_EXPAND": {
        "prefill": "7253954c43de28fe", "decode": "2b50df21873624d4",
        "prefill4": "357c49e7d13fa47e", "fork": "e6dc91369fb72543",
        "decode4": "e3f4fa33ed42b179"},
    "TINY_GIGACHAT35_EXPAND": {
        "prefill": "8304aac885c18dd0", "decode": "5790de57a6b41523",
        "prefill4": "27e7bf85b7c0b8b1", "fork": "9ca86017a4d2d88b",
        "decode4": "bef0a63e0d7a27cf"},
    "TINY_OLMO_HYBRID_EXPAND": {
        "prefill": "a2e5b998d6e7f1b5", "decode": "0818c215f6760c06",
        "prefill4": "ad2a6e79a33fcd50", "fork": "682f4357f17940f5",
        "decode4": "4ca3377e0bad1744"},
    "TINY_FALCON_H1_EXPAND": {
        "prefill": "17b1defb9f4a5494", "decode": "ecf84cc933ac00f0",
        "prefill4": "2dcbe1d18bce87cc", "fork": "36a4425e1469bc84",
        "decode4": "9272be7f8d75f32a"},
}


@pytest.mark.parametrize("preset", sorted(PARENT))
def test_the_defaults_lower_every_older_preset_to_the_parents_text(preset):
    """``zero_experts`` 0, ``moe_shortcut`` off and both latent scales 1.0
    trace no op: the lowered text of every expander executable of the
    presets that have latent attention, a share of the experts or neither
    is the parent's, byte for byte (the six older presets are held by
    tests/test_kanana_expander.py's own table)."""
    from tests.test_kanana_expander import lowered_texts

    got = {name: hashlib.sha256(text.encode()).hexdigest()[:16]
           for name, text in lowered_texts(preset).items()}
    assert got == PARENT[preset]


def test_the_new_presets_executables_return_the_identity_picks_last():
    """A decode executable of a router with zero-compute experts returns
    their picks after everything a sibling's returns."""
    from tests.test_kanana_expander import lowered_texts

    texts = lowered_texts("TINY_LONGCAT_FLASH_EXPAND")
    assert set(texts) == {"prefill", "decode", "prefill4", "fork", "decode4"}
    module = lm.DecoderLM(CFG)
    out = jax.eval_shape(
        lm.decode_sequences_fn(module, STEPS), contract.param_shapes(CFG),
        contract.forked_structs(CFG, 128, 4, 32, jnp.float32),
        jax.ShapeDtypeStruct((4,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 4)),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32))
    assert len(out) == 9 and out[-1].shape == (2,) \
        and out[-1].dtype == jnp.int32
    other = configs.TINY_KANANA_EXPAND.expander
    out = jax.eval_shape(
        lm.decode_sequences_fn(lm.DecoderLM(other), STEPS),
        contract.param_shapes(other),
        contract.forked_structs(other, 128, 4, 32, jnp.float32),
        jax.ShapeDtypeStruct((4,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 4)),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32))
    assert len(out) == 8

"""The prompt expander as nine state-space layers ALONE to one unrotated
attention, every one in front of a router over experts held by share beside
a shared expert, under a residual multiplier, a score scale that is not
``head_dim ** -0.5`` and a head that IS the token table
(``TINY_GRANITE_H_EXPAND``; the benchmark's ``sd15_granite_h_expand``).

(a) the program through prefill, fork and forked decode against the plain
reference (benchmarks/reference/granite_h_ref.py: one full float32 forward a
sequence, the recurrence token by token, the experts a plain loop, the head
the table transposed), eight wrong programs that must miss it, four of them
the reference with the same fault; (b) a step over several sequences against
each decoded alone, the fork and the snapshot of a stack that is mostly
state; (c) the three new keys: the tied head, the residual multiplier on
both sublayers, the score scale in each of the three attention forms, what
``__post_init__`` refuses, the defaults; (d) the share test: both shares of
the experts with the shared expert once, both halves of the tied table;
(e) the engine's path with its spans, counters and Prometheus families;
(f) the published share from shapes; (g) the lowered text of the older
presets."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.obs import prometheus
from stable_diffusion_webui_distributed_tpu.ops import moe_kernel, route_kernel
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER, METRICS,
)
from tests import expander_contract as contract
from tests.expander_contract import CAPACITY, STEPS, count, rel_rms, run

REF = contract.load_reference("granite_h")
#: every norm's scale, the skip, ``dt_bias`` and the convolution's bias off
#: the values flax gives them, the decay rates spread from a token to
#: hundreds, as the benchmark seeds them
CASE = contract.Case(
    configs.TINY_GRANITE_H_EXPAND, REF,
    how=(("spread", (("scale", 0.5), ("dt_bias", 0.3), ("D", 0.5),
                     ("conv_bias", 0.3))),
         ("a_log", (-5.0, -3.0, -1.0, 0.5, 1.5, 2.5))),
    tolerance=1e-5, control_floor=1e-2,
    controls=("control", "no_mlp_residual_multiplier",
              "scores_by_root_head_dim", "rotated", "norm_before_gate",
              "no_held_experts", "no_shared_expert", "logits_not_divided"))
FAMILY, CFG = CASE.family, CASE.cfg
#: the tiny stack: two state-space layers, the attention, one more
SSM_LAYERS, LAYERS = 3, 4
#: one sequence's state and kept rows in one state-space layer, float32:
#: 6 heads of 5 over 7-wide states, 3 rows of 44 channels
STATE = (6 * 5 * 7 + 3 * 44) * 4
#: a position's keys and values in the ONE attention layer: 2 KV heads of 8
ROW = 2 * 2 * 8
params, engine = contract.fixtures(CASE)


def replaced(**how):
    return dataclasses.replace(CFG, **how)


# -- (a) program against reference --------------------------------------------

class TestAgainstTheReference(contract.ForkedAgainstTheReference,
                              contract.StagedAsTheTimedPathRunsIt):
    """The chunk-wise recurrence from zero states and attention over what
    the chunk wrote, a copy, a fork into four and one step a sequence over
    its own states and two ranges of unrotated keys, every layer's routed
    sum and shared expert under the residual multiplier, the logits off the
    table: logits to 1e-5 and routing identical. Each wrong program reads
    a thousand times further."""
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
        """A prefill chunk (the chunk-wise form), then one token a step
        through the cache (the recurrence, the one-sequence attention
        step): the reference's one full forward of the one sequence."""
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
        assert len(routed) == 3     # no zero-compute expert: nothing more

    @pytest.mark.parametrize("control", sorted(REF.FAULTS))
    def test_a_wrong_program_is_the_reference_with_the_same_fault(
            self, params, control):
        """The residual multiplier left off the MLP sublayer alone, the
        scores scaled by ``head_dim ** -0.5``, a rotated attention, the norm
        before the gate: each is far from the reference and IS the
        reference made wrong the same way, through the chunk and the forked
        form."""
        inputs, want, _ = CASE.referred(74)
        wrong = CASE.program(**{control: True})(params, *inputs)
        assert rel_rms(wrong, want) > 0.05
        same = jax.jit(lambda p, i, c: REF.forward(
            FAMILY, p, i, c, fault=REF.FAULTS[control]))(params, *inputs)
        assert rel_rms(wrong, same) < 1e-5
        assert rel_rms(same, want) > 0.05

    def test_the_reference_says_the_model_itself(self):
        """It reads widths, ``eps`` and the four scalars' values, tells a
        state-space layer from the attention layer by its leaves, and
        shares nothing with the program or a sibling's reference: a program
        that read its own new keys wrong cannot take the reference with
        it."""
        with open(REF.__file__) as fh:
            text = fh.read()
        forward = text[text.index("# -- the reference"):
                       text.index("# -- the readings")]
        for key in ("layer_types", "layers_of", "kind_parts", "base_kinds",
                    "tied_head", "ssm_norm_before_gate", "ssm_conv_bias",
                    "ssm_chunk", "rope_full", "models.lm", "ops.",
                    "falcon_h1", "_ref"):
            assert key not in forward, key
        assert REF.published(CFG) == {
            "embedding_multiplier": 3.0, "residual_multiplier": 0.6,
            "attention_multiplier": 0.2, "logits_scaling": 4.0}
        share = configs.sd15_granite_h_expander().expander
        assert REF.published(share) == {
            "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
            "attention_multiplier": 0.0078125, "logits_scaling": 16.0}


# -- (b) a step over B sequences ----------------------------------------------

class TestSequencesOfOneStep(contract.SequencesOfOneStep,
                             contract.StatesOfOneStep):
    CASE = CASE
    test_a_snapshot_restores_keys_values_states_and_kept_rows = \
        contract.StatesOfOneStep.a_snapshot_restores_every_buffer
    PARAMETERS = {"test_a_forked_decode_is_each_sequence_alone": [
        ("user,live,batch", [(1, 4, 4), (64, 3, 4)])]}

    test_a_fork_shares_the_one_layers_rows_and_copies_every_state = \
        contract.SequencesOfOneStep \
        .a_fork_shares_what_has_positions_and_copies_the_rest

    def check_fork(self, forked):
        # nine tenths of the stack is state: ONE layer has anything to share
        assert set(forked) == {"k", "v", "k_shared", "v_shared",
                               "ssm_state", "ssm_conv", lm.FORKED_AT}
        assert len(forked["k_shared"]) == len(forked["k"]) == 1
        assert [x.shape for x in forked["ssm_state"]] \
            == [(4, 6, 5, 7)] * SSM_LAYERS
        assert [x.shape for x in forked["ssm_conv"]] \
            == [(4, 3, 44)] * SSM_LAYERS

    def test_bytes_and_positions_of_a_stack_that_is_mostly_state(self):
        assert lm.shares_a_step(CFG)
        assert lm.shares_a_step(configs.sd15_granite_h_expander().expander)
        assert CFG.layers_of("ssm") == (0, 1, 3)
        assert CFG.layers_of("full") == (2,)
        assert lm.buffers_of("ssm", forked=True) == lm.SSM_BUFFERS
        manager = kv.KVCacheManager(CFG, jnp.bfloat16)
        assert manager.positions_in_use(40, 4, 30) == {
            "full": 30 + 4 * 10, "sliding": 0, "ssm": 0}
        assert kv.state_bytes(CFG, 256, jnp.bfloat16) == {
            "full": 256 * ROW * 2, "sliding": 0, "ssm": SSM_LAYERS * STATE}
        assert kv.state_bytes(CFG, 256, jnp.bfloat16, 4, 64) == {
            "full": (256 + 4 * 64) * ROW * 2, "sliding": 0,
            "ssm": 4 * SSM_LAYERS * STATE}
        assert kv.copied_bytes(CFG, jnp.bfloat16, 4) \
            == 4 * SSM_LAYERS * STATE
        assert kv.copied_bytes(CFG, jnp.bfloat16, 1) == 0
        shapes = lm.cache_shapes(CFG, 64)
        assert shapes["k"] == shapes["v"] == [(64, 2, 8)]
        assert shapes["ssm_state"] == [(6, 5, 7)] * SSM_LAYERS


# -- (c) the three new keys ---------------------------------------------------

def _layer(cfg, index, p, x, start=0):
    """One layer of the program over a chunk from an empty cache."""
    tokens = x.shape[0]
    buffers = tuple(
        jnp.zeros(lm.cache_shapes(cfg, 64)[name][0],
                  lm.buffer_dtype(name, jnp.float32))
        for name in lm.buffers_of(cfg.layer_types[index]))
    q_pos = start + jnp.arange(tokens, dtype=jnp.int32)
    return jax.jit(lambda p, x: lm.DecoderLayer(cfg, index).apply(
        {"params": p}, x, q_pos, jnp.int32(start),
        jnp.int32(start + tokens), buffers))(p, x)[0]


class TestTheThreeNewKeys:
    def test_the_tied_head_has_no_leaf_of_its_own(self, params):
        assert CFG.tied_head and "lm_head" not in params
        assert set(params) == {"embed_tokens", "norm"} | {
            f"layers_{i}" for i in range(LAYERS)}
        assert params["embed_tokens"]["embedding"].shape == (256, 32)
        untied = contract.param_shapes(replaced(tied_head=False))
        assert untied["lm_head"]["kernel"].shape == (32, 256)
        assert count(untied) - count(params) == 32 * 256

    def test_a_changed_table_row_changes_the_embedding_and_its_logit(
            self, params):
        """ONE leaf under both: moving row 7 moves what token 7 embeds to
        (every logit of a chunk that holds it) and, in a chunk that does
        not hold it, id 7's logit alone."""
        table = params["embed_tokens"]["embedding"]
        moved = {**params, "embed_tokens": {
            "embedding": table.at[7].add(0.5)}}
        without = jnp.array([3, 9, 11, 20], jnp.int32)
        a, _, _ = run(CFG, params, without, 0, 4, contract.empty(CFG))
        b, _, _ = run(CFG, moved, without, 0, 4, contract.empty(CFG))
        others = np.arange(256) != 7
        assert np.array_equal(np.asarray(a)[:, others],
                              np.asarray(b)[:, others])
        assert np.all(np.asarray(a)[:, 7] != np.asarray(b)[:, 7])
        holding = without.at[1].set(7)
        a, _, _ = run(CFG, params, holding, 0, 4, contract.empty(CFG))
        b, _, _ = run(CFG, moved, holding, 0, 4, contract.empty(CFG))
        # the row before token 7 never saw it; every row from it on did
        assert np.array_equal(np.asarray(a)[0, others],
                              np.asarray(b)[0, others])
        assert rel_rms(b[1:, others], a[1:, others]) > 1e-3

    @pytest.mark.parametrize("index", [0, 2])
    def test_one_layer_is_the_five_equations_layer(self, params, index):
        """``h + m M(N(h))`` then ``h + m (R(n) + S(n))``: a state-space
        layer and the attention layer of the program against the
        reference's one function, and each told apart from a layer that
        leaves the multiplier off its expert sublayer."""
        x = jax.random.normal(jax.random.key(index), (24, 32))
        p = params[f"layers_{index}"]
        got = _layer(CFG, index, p, x)
        want, _ = jax.jit(lambda p, x: REF.layer_forward(
            CFG, x, p, REF.published(CFG)))(p, x)
        assert rel_rms(got, want) < 1e-5
        unscaled, _ = REF.layer_forward(CFG, x, p, REF.published(CFG),
                                        fault="mlp_unscaled")
        assert rel_rms(got, unscaled) > 0.1
        assert rel_rms(_layer(replaced(residual_multiplier=1.0), index, p,
                              x), want) > 0.1

    @pytest.mark.parametrize("form", ["chunk", "step", "forked"])
    def test_the_scores_scale_in_each_form_of_the_attention(self, params,
                                                            form):
        """A chunk, a one-sequence step, a forked step over shared and own
        rows: each multiplies its scores by ``attention_scale``, and each
        is told apart from ``head_dim ** -0.5``."""
        assert CFG.attention_scale == 0.2 != CFG.head_dim ** -0.5
        wrong_cfg = replaced(attention_scale=0.0)
        if form == "forked":
            inputs, want, _ = CASE.referred(37)
            prefix, user, decoded = REF.split(37)
            rows = slice(prefix + user, None)
            got = CASE.program()(params, *inputs)
            wrong = CASE.program(scores_by_root_head_dim=True)(
                params, *inputs)
        else:
            ids, continuations = REF.inputs(FAMILY, 11, 37)
            whole = jnp.concatenate([ids, continuations[0]])
            want = jax.jit(lambda p, i, c: REF.forward(FAMILY, p, i, c))(
                params, ids, continuations[:1])
            split = whole.shape[0] if form == "chunk" else ids.shape[0]
            rows = slice(0, None) if form == "chunk" else slice(split, None)

            def through(cfg):
                out, cache, _ = run(cfg, params, whole[:split], 0, split,
                                    contract.empty(cfg, 64))
                outs = [out]
                for t in range(split, whole.shape[0]):
                    out, cache, _ = run(cfg, params, whole[t:t + 1], t, 1,
                                        cache)
                    outs.append(out)
                return jnp.concatenate(outs)

            got, wrong = through(CFG), through(wrong_cfg)
        assert rel_rms(got[rows], want[rows]) < 1e-5
        assert rel_rms(wrong[rows], want[rows]) > 1e-2

    def test_what_post_init_refuses(self):
        with pytest.raises(ValueError, match="residual_multiplier"):
            dataclasses.replace(configs.TINY_LATENT_LM,
                                residual_multiplier=0.5)
        assert configs.TINY_LATENT_LM.residual_streams > 1
        with pytest.raises(ValueError, match="residual_multiplier"):
            dataclasses.replace(configs.TINY_LONGCAT_FLASH_LM,
                                residual_multiplier=0.5)
        assert configs.TINY_LONGCAT_FLASH_LM.moe_shortcut
        # one stream, sums added in place: taken
        assert replaced(residual_multiplier=0.5).residual_multiplier == 0.5

    def test_the_keys_at_their_defaults_are_the_old_model(self):
        """Every older preset says nothing of the new keys: no scalar is
        applied, no leaf goes, no attribute is added to a span."""
        for name in ("TINY_EXPAND", "TINY_DELTA_EXPAND", "TINY_LOOP_EXPAND",
                     "TINY_GIGACHAT35_EXPAND", "TINY_KANANA_EXPAND",
                     "TINY_OLMO_HYBRID_EXPAND", "TINY_CONV_EXPAND",
                     "TINY_LATENT_EXPAND", "TINY_WINDOW_EXPAND",
                     "TINY_FALCON_H1_EXPAND", "TINY_LONGCAT_FLASH_EXPAND"):
            cfg = getattr(configs, name).expander
            assert (cfg.tied_head, cfg.residual_multiplier,
                    cfg.attention_scale) == (False, 1.0, 0.0)
            assert not {"tied_head", "residual_multiplier",
                        "attention_scale"} & set(lm.site_attrs(cfg))
            assert "lm_head" in contract.param_shapes(cfg)
        assert configs.TINY_FALCON_H1_EXPAND.expander.multipliers_applied \
            == 14
        # the table's, the logits', the residual's and the scores'
        assert CFG.multipliers_applied == 4
        assert replaced(attention_scale=0.0).multipliers_applied == 3
        assert replaced(residual_multiplier=1.0).multipliers_applied == 3
        assert lm.site_attrs(CFG) == {
            "unrotated": 1, "ssm_layers": 3, "multipliers": 4,
            "tied_head": True, "residual_multiplier": 0.6,
            "attention_scale": 0.2}

    def test_the_int8_control_takes_the_table_transposed(self, params):
        """``quant_linears`` has no leaf to refuse: the head's int8 product
        reads the slice transposed (a copy the control makes and the
        program does not) and lands near the float32 logits."""
        ids = jnp.arange(3, 23, dtype=jnp.int32)
        want, _, _ = run(CFG, params, ids, 0, 20, contract.empty(CFG))
        module = lm.DecoderLM(CFG, quant_linears=True)
        got, _, _ = jax.jit(lambda p, c: module.apply(
            {"params": p}, ids, jnp.int32(0), jnp.int32(20), c))(
                params, contract.empty(CFG))
        assert 1e-4 < rel_rms(got, want) < 0.2


# -- (d) the shares add up ----------------------------------------------------

def _moe(cfg, p, n):
    valid = jnp.ones((n.shape[0],), bool)
    return jax.jit(lambda p, n, v, m=lm.MoE(cfg): m.apply(
        {"params": p}, n, v))(p, n, valid)


class TestTheSharesAddUp:
    def test_both_shares_of_the_experts_and_the_shared_expert_once(self):
        """The guide's share test: the held parts that every share of the
        experts gives for one layer, with the shared expert counted ONCE,
        add up to the uncut reference's ``R(n) + S(n)``, through the
        program's own expert layer."""
        whole = configs.lm_share(configs.TINY_GRANITE_H_LM, 4, chips=1,
                                 rank=0)
        assert whole.experts == (0, 12) and whole.num_experts == 12
        uncut = CASE.params(3, whole)
        n = jax.random.normal(jax.random.key(9), (6, 32))
        p = uncut["layers_0"]["mlp"]
        chosen, weights = REF.route(whole, n, p)
        np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
        shared = REF.shared_expert(n, p["shared_expert"])
        want = REF.routed_sum(n, chosen, weights, p["experts"], (0, 12)) \
            + shared
        chips, parts = 3, []
        for rank in range(chips):
            share = configs.lm_share(configs.TINY_GRANITE_H_LM, 4,
                                     chips=chips, rank=rank)
            assert share.experts == (4 * rank, 4)
            assert share.num_experts == 12     # the router keeps its width
            mine = {**p, "experts": {
                name: w[4 * rank:4 * rank + 4]
                for name, w in p["experts"].items()}}
            out, beside = _moe(share, mine, n)
            assert np.array_equal(np.sort(beside[0], -1),
                                  np.sort(chosen, -1))
            parts.append(out - shared)      # every chip runs the shared one
        assert rel_rms(parts[0] + shared, want) > 0.05
        np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-4,
                                   atol=2e-5)
        out, _ = _moe(whole, p, n)      # the uncut layer itself
        np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)

    def test_both_halves_of_the_tied_table_give_the_uncut_logits(self):
        """The table lies two ways over the vocabulary and is the head:
        each half, fed ids of its own slice, gives its half of the uncut
        model's logits."""
        whole = configs.lm_share(configs.TINY_GRANITE_H_LM, 4, chips=1,
                                 rank=0)
        assert whole.vocab == (0, 512)
        uncut = CASE.params(3, whole)
        table = uncut["embed_tokens"]["embedding"]
        for rank in range(2):
            half = dataclasses.replace(whole, vocab_held=(256 * rank, 256))
            ids = 256 * rank + jnp.arange(5, 25, dtype=jnp.int32)
            want, _, _ = run(whole, uncut, ids, 0, 20,
                             contract.empty(whole))
            mine = {**uncut, "embed_tokens": {
                "embedding": table[256 * rank:256 * rank + 256]}}
            got, _, _ = run(half, mine, ids, 0, 20, contract.empty(half))
            assert got.shape == (20, 256)
            np.testing.assert_allclose(
                got, want[:, 256 * rank:256 * rank + 256], rtol=1e-5,
                atol=1e-6)
        # and an id the other chip holds embeds to nothing here
        half = dataclasses.replace(whole, vocab_held=(0, 256))
        mine = {**uncut, "embed_tokens": {"embedding": table[:256]}}
        a, _, _ = run(half, mine, jnp.array([300, 301], jnp.int32), 0, 2,
                      contract.empty(half))
        b, _, _ = run(half, mine, jnp.array([400, 401], jnp.int32), 0, 2,
                      contract.empty(half))
        assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_the_ninth_expert_shape_and_the_fullest_routing_kernel(self):
        """4096 x 768 tiles at 384 by the kernel's own rule (the first tile
        that is neither the whole width nor a power of two), and a step of
        four rows of ten picks over 36 held experts fills every grid
        slot."""
        assert moe_kernel.f_tile(4096, 768, 2) == 384
        assert moe_kernel.ring(4096, 768, 2) == moe_kernel.Ring(2, 384, 2)
        assert 2 * 3 * 4096 * 384 * 2 <= 24 * 2 ** 20 < 2 * 3 * 4096 * 768 * 2
        assert route_kernel.slots_of(4, 10, 36) == 36
        assert route_kernel.slots_of(1, 10, 36) == 10
        even = 36 * (1 - (62 / 72) ** 4)
        assert round(even, 1) == 16.2 and round(20 / even, 2) == 1.23
        assert round(40 / (36 * (1 - (62 / 72) ** 8)), 2) == 1.59
        # the kernel's routing in interpret mode at the published router:
        # the picks of XLA's chain, more slots than picks held
        from stable_diffusion_webui_distributed_tpu.ops import moe

        logits = jax.random.normal(jax.random.key(2), (4, 72))
        step = route_kernel.routing(
            logits, None, jnp.ones((4,), bool), k=10, renormalise=True,
            scale=1.0, scoring="softmax", eps=0.0, first=0, count=36,
            interpret=True)
        routing = moe.route(logits, 10, renormalise=True, scale=1.0)
        assert np.array_equal(np.sort(step.picks, -1),
                              np.sort(routing.experts, -1))
        load, none_held = moe.load_counts(routing, 0, 36)
        assert np.array_equal(step.load, load)
        assert int(step.held[0]) == int(moe.experts_read(load)) <= 36
        assert step.experts.shape == (36,) and step.weights.shape == (36, 4)
        assert int(load.sum()) == int(np.sum(np.asarray(step.picks) < 36))


# -- (e) the engine's path ----------------------------------------------------

class TestEnginePath(contract.ForkedEnginePath):
    test_a_batch_prefills_once_forks_and_decodes_four_a_step = \
        contract.ForkedEnginePath \
        .a_batch_prefills_once_forks_and_decodes_four_a_step
    test_every_image_its_own_expansion_and_one_image_the_old_path = \
        contract.ForkedEnginePath \
        .every_image_its_own_expansion_and_one_image_the_old_path
    CASE = CASE
    #: how the model departs from rotated attention, a head of its own and
    #: sublayers added unscaled
    DEPARTURES = {"unrotated": 1, "ssm_layers": 3, "multipliers": 4,
                  "tied_head": True, "residual_multiplier": 0.6,
                  "attention_scale": 0.2}

    def check_traced(self, sites, traced):
        assert sum(sites["by_shape"][
            f"T1 S{CAPACITY + 2 * STEPS} D8"].values()) == 1
        # two prefill executables and one forked decode chunk were traced
        forms = {"recurrent": 0, "chunked": 2, "recurrent_forked": 1}
        assert traced["tied_head"] == forms
        assert traced["attention_unrotated"] == forms
        assert traced["ssm_mixers"] == {
            form: SSM_LAYERS * n for form, n in forms.items()}
        assert traced["joined_layers"] == dict.fromkeys(forms, 0)
        assert traced["multipliers_applied"] == 4
        assert traced["sublayer_norms"]["pre"] == {
            form: 2 * LAYERS * n for form, n in forms.items()}
        # a CPU: the grouped product, XLA's routing chain
        assert traced["expert_products"]["grouped"] == 3 * LAYERS
        assert traced["route_products"] == {"kernel": 0,
                                            "xla": 3 * LAYERS}

    def check_counted(self, stats, sizes, one):
        assert stats["cache_positions"] == {
            "full": 36 + 4 * 40, "sliding": 0, "ssm": 0}
        state = SSM_LAYERS * STATE
        assert one["ssm"] == state and sizes["ssm"] == 4 * state
        assert one["full"] == CAPACITY * ROW * 4
        assert stats["fork_bytes_copied"] == 4 * state
        # a step reads and writes each sequence's states once
        assert stats["state_bytes_stepped"] == 2 * STEPS * 2 * 4 * state
        # 4 of 12 experts held, 3 picks a token: some rows find none, and
        # an expert read serves at least one pick
        assert stats["tokens_no_held_expert"] > 0
        assert 0 < stats["experts_read"] <= stats["expert_picks_held"]
        assert stats["expert_picks_held"] <= sum(
            map(sum, stats["expert_tokens"]))
        assert stats["expert_picks_held"] <= 2 * STEPS * 4 * 3 * LAYERS
        # nothing was traced again: the counters of the sites stay 0
        assert stats["tied_head"]["recurrent_forked"] == 0
        assert stats["multipliers_applied"] == 0

    def check_spans(self, by_name, sizes, one):
        state = SSM_LAYERS * STATE
        (prefill,) = by_name["expand.prefill"]
        assert prefill["sequences"] == 4
        assert prefill["form"] == "chunked" and prefill["padded"] == 59
        assert prefill["ssm_state_bytes"] == 2 * state
        (fork,) = by_name["expand.fork"]
        assert fork["ssm"] == "recurrent_forked" and "delta" not in fork
        assert fork["state_bytes_copied"] == 4 * state
        # ONE layer's own rows of 64 slots a sequence and four copies of
        # every state, float32
        assert fork["bytes"] == 4 * 2 * STEPS * ROW * 4 + 4 * state
        chunks = by_name["expand.decode_chunk"]
        assert [a["ssm"] for a in chunks] == ["recurrent_forked"] * 2
        for attrs in [prefill, fork] + chunks:
            assert {k: attrs[k] for k in self.DEPARTURES} == self.DEPARTURES
        hits = [a for a in by_name["expand.prefix_copy"] if a.get("hit")]
        assert hits and hits[0]["bytes"] == sum(one.values())

    def check_one_image(self, sites, stats):
        assert stats["ssm_mixers"]["recurrent"] == SSM_LAYERS
        assert stats["tied_head"]["recurrent"] == 1
        assert stats["attention_unrotated"]["recurrent"] == 1
        assert stats["fork_bytes_copied"] == 2 * SSM_LAYERS * STATE
        # one sequence a step reads as many experts as its picks are held
        assert stats["expert_picks_held"] >= stats["experts_read"] > 0

    def test_the_prometheus_families_and_the_status_keys(self, engine):
        ATTENTION.clear()
        EXPANDER.clear()
        contract.sites_of(
            CFG, CASE.params(), jnp.zeros((4,), jnp.int32), 40, 4,
            contract.forked_structs(CFG, 64, 4, 32, jnp.float32),
            sequences=True)
        summary = METRICS.summary()["expander"]
        assert {"tied_head", "expert_picks_held"} <= set(summary)
        assert summary["tied_head"] == {"recurrent": 0, "chunked": 0,
                                        "recurrent_forked": 1}
        assert summary["expert_picks_held"] == 0
        text = prometheus.render()
        assert 'sdtpu_expander_tied_head_total{form="recurrent_forked"} 1' \
            in text
        assert 'sdtpu_expander_tied_head_total{form="chunked"} 0' in text
        assert "sdtpu_expander_expert_picks_held_total 0" in text
        assert "sdtpu_expander_multipliers_applied 4" in text
        # a sibling has a head of its own
        EXPANDER.clear()
        contract.param_shapes(configs.TINY_FALCON_H1_EXPAND.expander)
        assert not any(EXPANDER.summary()["tied_head"].values())
        # the picks held are the sum of the load
        EXPANDER.record(
            prefilled=0, from_prefix=0, sequences=4, decoded=8,
            decode_steps=2, experts_read=5, load=[[1, 2], [3, 0]],
            none_held=0, positions={}, state_bytes={}, prefix_snapshots=0,
            padded_rows_masked=0, residual_streams=1, sinkhorn_iters=0,
            expert_picks_held=6)
        assert EXPANDER.summary()["expert_picks_held"] == 6
        assert "sdtpu_expander_expert_picks_held_total 6" \
            in prometheus.render()
        ATTENTION.clear()
        EXPANDER.clear()

    def test_a_warm_start_counts_the_sites_again(self):
        """What a trace counted is replayed when its program is loaded
        (serving/aot.py): the rows a capture holds count once more."""
        from stable_diffusion_webui_distributed_tpu.serving import metrics

        EXPANDER.clear()
        with metrics.capture_sites() as rows:
            EXPANDER.record_tied_head("recurrent_forked")
            EXPANDER.record_tied_head("chunked")
        assert rows == [["tied_head", "recurrent_forked"],
                        ["tied_head", "chunked"]]
        EXPANDER.clear()
        metrics.replay_sites(rows)
        assert EXPANDER.summary()["tied_head"] == {
            "recurrent": 0, "chunked": 1, "recurrent_forked": 1}
        EXPANDER.clear()


# -- (f) the published model and its share, from shapes -----------------------

class TestThePublishedShare:
    def test_parameters_and_bytes_from_shapes(self):
        share = configs.sd15_granite_h_expander().expander
        whole = configs.GRANITE_4_H_SMALL
        assert whole.num_layers == 40 and share.num_layers == 10
        assert whole.layers_of("full") == (5, 15, 25, 35)
        assert share.layer_types == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
        assert share.dense_layers == () and len(share.expert_layers) == 10
        assert share.experts == (0, 36) and share.num_experts == 72
        assert share.vocab == (0, 50176) and whole.vocab == (0, 100352)
        assert (share.hidden_size, share.moe_intermediate_size,
                share.shared_expert_intermediate_size,
                share.num_experts_per_tok, share.head_dim,
                share.num_kv_heads, set(share.num_heads_per_layer)) \
            == (4096, 768, 1536, 10, 128, 8, {32})
        assert share.rope_full is None and share.attn_gate == "none"
        assert share.attention_scale == 1 / 128 != 128 ** -0.5
        assert (share.embedding_multiplier, share.logit_multiplier,
                share.residual_multiplier, share.tied_head) \
            == (12.0, 1 / 16, 0.22, True)
        assert (share.router_scoring, share.router_bias,
                share.norm_topk_prob, share.routed_scaling_factor,
                share.shared_expert_gate) \
            == ("softmax", False, True, 1.0, False)
        assert (share.ssm_num_heads, share.ssm_head_dim,
                share.ssm_state_size, share.ssm_num_groups,
                share.ssm_conv_kernel, share.ssm_conv_bias,
                share.ssm_norm_before_gate, share.ssm_chunk,
                share.ssm_inner, share.ssm_conv_channels) \
            == (128, 64, 128, 1, 4, True, False, 256, 8192, 8448)
        shapes = contract.param_shapes(share)
        assert "lm_head" not in shapes
        layer, attention = shapes["layers_0"], shapes["layers_5"]
        mixer = layer["ssm"]
        assert mixer["in_proj"]["kernel"].shape == (4096, 16768)
        assert 16768 == 8192 + 8192 + 128 + 128 + 128
        assert count(mixer) == 102_286_976
        assert count(attention["attn"]) == 41_943_040
        assert set(layer) == {"ssm", "mlp", "input_norm",
                              "post_attention_norm"}
        assert set(attention) == {"attn", "mlp", "input_norm",
                                  "post_attention_norm"}
        mlp = layer["mlp"]
        assert set(mlp) == {"router", "experts", "shared_expert"}
        assert mlp["router"].shape == (4096, 72)
        assert count(mlp["shared_expert"]) == 18_874_368
        assert mlp["experts"]["w_gate"].shape == (36, 4096, 768)
        assert count(mlp["experts"]) == 36 * 9_437_184 == 339_738_624
        outside = count(layer) - count(mlp["experts"])
        assert outside == 121_464_448
        assert count(attention) - count(mlp["experts"]) == 61_120_512
        assert 9 * 121_464_448 + 61_120_512 == 1_154_300_544
        assert count(shapes["embed_tokens"]) == 50176 * 4096 == 205_520_896
        total = count(shapes)
        assert total == 1_154_300_544 + 10 * 339_738_624 + 205_520_896 \
            + 4096 == 4_757_211_776
        assert round(total * 2 / 1e9, 2) == 9.51
        assert round(total * 2 / 2 ** 30, 2) == 8.86
        # beside SD1.5's 1 066 M: 11.65 GB = 10.85 GiB
        assert round((total + 1066e6) * 2 / 1e9, 2) == 11.65
        assert round((total + 1066e6) * 2 / 2 ** 30, 2) == 10.85
        # the whole model from the same shapes: 32.2 B, 8.8 B a token
        published = 36 * 121_464_448 + 4 * 61_120_512 \
            + 40 * 72 * 9_437_184 + 100352 * 4096 + 4096
        assert round(published / 1e9, 1) == 32.2
        active = published - 40 * 62 * 9_437_184
        assert round(active / 1e9, 1) == 8.8
        # the fallback: 18 experts a layer, a quarter of the vocabulary
        fallback = 1_154_300_544 + 10 * 18 * 9_437_184 + 25088 * 4096 + 4096
        assert round(fallback / 1e6) == 2956
        # the caches of four forked sequences at the cell's capacity
        capacity = kv.capacity_for(2048 + 64 + 8 * STEPS)
        assert capacity == 2560
        state = (128 * 64 * 128 + 3 * 8448) * 4
        assert 128 * 64 * 128 * 4 == 4_194_304 and state == 4_295_680
        position = 2 * 8 * 128 * 2
        assert position == 4096
        assert kv.state_bytes(share, capacity, jnp.bfloat16, 4, 8 * STEPS) \
            == {"full": (2560 + 4 * 256) * position, "sliding": 0,
                "ssm": 4 * 9 * state}
        assert kv.copied_bytes(share, jnp.bfloat16, 4) == 154_644_480
        assert round(154_644_480 / 2 ** 20, 1) == 147.5
        assert round(2 * 154_644_480 / 2 ** 20, 1) == 295.0
        # the instruction's snapshot: nine states beside ONE layer's rows
        assert round((9 * state + 2048 * position) / 1e6) == 47

    def test_a_forked_step_of_the_share_traced_as_on_the_chip(self,
                                                              monkeypatch):
        """One decode step of the share the cell runs, traced without
        weights or FLOPs (nothing compiles; tests/test_chip_compile.py
        compiles it for a described v5e): nine state-space mixers a
        recurrence a sequence beside ONE unrotated attention site over
        2 560 shared and 256 own rows, ten expert kernels behind ten
        routing kernels, the logits off the table."""
        share = configs.sd15_granite_h_expander().expander
        cache = contract.forked_structs(share, 2560, 4, 256)
        assert [x.shape for x in cache["k_shared"]] == [(2560, 8, 128)]
        assert [x.shape for x in cache["k"]] == [(4, 256, 8, 128)]
        assert [(x.shape, x.dtype) for x in cache["ssm_state"]] \
            == [((4, 128, 64, 128), jnp.float32)] * 9
        assert [x.shape for x in cache["ssm_conv"]] == [(4, 3, 8448)] * 9
        shapes = contract.param_shapes(share)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ATTENTION.clear()
        EXPANDER.clear()
        logits, after, routed = contract.sites_of(
            share, shapes, jnp.zeros((4,), jnp.int32), 2200, 4,
            cache, jnp.bfloat16, sequences=True)
        assert logits.shape == (4, 50176) and logits.dtype == jnp.float32
        assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), after) \
            == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), cache)
        assert routed[0].shape == (10, 4, 10)
        assert routed[1].shape == (10, 36)
        stats = EXPANDER.summary()
        forked = {"recurrent": 0, "chunked": 0, "recurrent_forked": 1}
        assert stats["tied_head"] == forked
        assert stats["attention_unrotated"] == forked
        assert stats["ssm_mixers"]["recurrent_forked"] == 9
        assert stats["multipliers_applied"] == 4
        assert stats["expert_products"] == {"kernel": 10, "loop": 0,
                                            "grouped": 0}
        assert stats["route_products"] == {"kernel": 10, "xla": 0}
        assert stats["sublayer_norms"]["pre"]["recurrent_forked"] == 20
        (shape, paths), = ATTENTION.summary()["by_shape"].items()
        assert shape == "T1 S2816 D128" and sum(paths.values()) == 1
        ATTENTION.clear()
        EXPANDER.clear()

    def test_the_presets_leaves_all_match_a_sharding_rule(self, params):
        """No new rule: the table lies over ``vp`` (and with it the head,
        whose logits then lie over the vocabulary as a head's would), the
        experts over ``ep``, and everything that makes or reads a
        state-space mixer's own leaves stays whole."""
        from jax.sharding import PartitionSpec as P

        from stable_diffusion_webui_distributed_tpu.parallel.sharding import (
            shard_params, tp_spec_for,
        )

        assert tp_spec_for("embed_tokens/embedding", 2) == P("vp", None)
        for path, ndim in (("layers_0/ssm/in_proj/kernel", 2),
                           ("layers_0/ssm/out_proj/kernel", 2),
                           ("layers_0/ssm/conv_bias", 1),
                           ("layers_0/ssm/norm/scale", 1),
                           ("layers_0/mlp/router", 2)):
            assert tp_spec_for(path, ndim) == P(), path
        assert tp_spec_for("layers_2/mlp/experts/w_up", 3) \
            == P("ep", None, None)
        devices = np.array(jax.devices()[:4]).reshape(2, 2)
        mesh = jax.sharding.Mesh(devices, ("ep", "vp"))
        placed = shard_params(params, mesh)
        assert "lm_head" not in placed
        assert placed["embed_tokens"]["embedding"].sharding.spec \
            == P("vp", None)
        assert placed["layers_0"]["mlp"]["experts"]["w_gate"].sharding.spec \
            == P("ep", None, None)
        assert placed["layers_0"]["ssm"]["in_proj"]["kernel"].sharding.spec \
            == P()


# -- (g) the lowered text of what was there -----------------------------------

#: the lowered text of every expander executable of the newest older preset,
#: by sha256 prefix, at the parent commit (PR 68's tree): the ten before it
#: are held by tests/test_kanana_expander.py's and
#: tests/test_longcat_flash_expander.py's own tables, which this PR leaves
#: as they were (PR 70 replaced the forked decode chunk's: it returns the
#: steps that streamed no expert, one more carry of the scan)
PARENT = {
    "TINY_LONGCAT_FLASH_EXPAND": {
        "prefill": "49fedc4c34157e08", "decode": "23209b30bf115ee9",
        "prefill4": "a497011a1374bba2", "fork": "e6dc91369fb72543",
        "decode4": "bc4d65b54e660ae8"},
}


@pytest.mark.parametrize("preset", sorted(PARENT))
def test_the_defaults_lower_every_older_preset_to_the_parents_text(preset):
    """``tied_head`` off, ``residual_multiplier`` 1.0 and
    ``attention_scale`` 0.0 trace no op: the lowered text of every expander
    executable of every older preset is the parent's, byte for byte."""
    from tests.test_kanana_expander import lowered_texts

    got = {name: hashlib.sha256(text.encode()).hexdigest()[:16]
           for name, text in lowered_texts(preset).items()}
    assert got == PARENT[preset]


def test_the_new_presets_executables_have_no_head_product_of_their_own():
    """The five executables of the new preset lower, the forked decode
    returns what a sibling with experts returns (no count more), and the
    logits' product contracts the table's second axis: no transpose of the
    table is made."""
    from tests.test_kanana_expander import lowered_texts

    texts = lowered_texts("TINY_GRANITE_H_EXPAND")
    assert set(texts) == {"prefill", "decode", "prefill4", "fork", "decode4"}
    for name in ("prefill", "decode", "decode4"):
        # (rows, 32) by the table (256, 32), contracted over both seconds
        assert "contracting_dims = [1] x [1]" in texts[name], name
        assert "tensor<256x32xf32>) -> tensor<32x256xf32>" \
            not in texts[name], name
    module = lm.DecoderLM(CFG)
    out = jax.eval_shape(
        lm.decode_sequences_fn(module, STEPS), contract.param_shapes(CFG),
        contract.forked_structs(CFG, 128, 4, 32, jnp.float32),
        jax.ShapeDtypeStruct((4,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 4)),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32))
    assert len(out) == 8    # the unread calls behind the experts read

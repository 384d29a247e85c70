"""The prompt expander as a dense hybrid whose two kinds of layer are normed
on different sides (``TINY_OLMO_HYBRID_EXPAND``; the benchmark's
``sd15_olmo_hybrid_expand``): gated delta-rule layers that norm their INPUT
and write with a strength up to 2 over states whose key and value widths
differ, beside full attention that norms its OUTPUT, rotates nothing and
norms queries and keys over the whole projection.

(a) the program through prefill, fork and forked decode against the plain
reference (benchmarks/reference/olmo_hybrid_ref.py: one full float32 forward
a sequence, the delta rule token by token), and nine controls that must
miss the tolerance; (b) a step over several sequences against each decoded
alone, a padded group, an ended sequence, the fork and the snapshot; (c)
the three forms of the delta rule at strengths up to 2 and unequal widths;
(d) the tree, the counts and the sharding rules; (e) the engine's path with
its spans, counters and Prometheus families; (f) the published share from
shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.obs import prometheus
from stable_diffusion_webui_distributed_tpu.ops import delta_rule
from stable_diffusion_webui_distributed_tpu.runtime import dtypes
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER, METRICS,
)
from tests import expander_contract as contract
from tests.expander_contract import CAPACITY, STEPS, count, rel_rms

REF = contract.load_reference("olmo_hybrid")
#: every norm's scale off 1 (deviation 0.5), the decay rates spread from a
#: token to hundreds, as the benchmark seeds them, and ``dt_bias`` off 1
CASE = contract.Case(
    configs.TINY_OLMO_HYBRID_EXPAND, REF,
    how=(("spread", (("scale", 0.5), ("dt_bias", 0.3))),
         ("a_log", (-3.0, -0.5, 2.0))),
    extra="with_beta", tolerance=1e-4, rows_tolerance=3e-4,
    step_tolerance=3e-4,
    controls=("control", "state_bf16", "sigmoid_beta", "state_shared",
              "qk_norm_per_head", "rotary", "full_pre_normed",
              "linear_post_normed", "both_normed"))
FAMILY, CFG = CASE.family, CASE.cfg
#: one sequence's state and kept rows in one linear layer, float32
STATE = (3 * 6 * 10 + 3 * (2 * 3 * 6 + 3 * 10)) * 4
LINEAR_LAYERS, FULL_LAYERS = 6, 2
params, engine = contract.fixtures(CASE)


# -- (a) program against reference --------------------------------------------

class TestAgainstTheReference(contract.ForkedAgainstTheReference):
    """Chunk-wise delta rule from a zero state and full attention over
    what it wrote, a copy, a fork into four and a recurrent step a
    sequence with two ranges of keys under one softmax: logits to 1e-4.
    Int8 linears, the state in bfloat16, ``sigmoid(b)`` for ``2
    sigmoid(b)``, one state shared by the sequences, the query and key
    norms per head, a rotary table applied, the full layers pre-normed,
    the linear layers post-normed, both kinds normed both ways: each
    misses ten times over the tolerance the program meets."""
    CASE = CASE
    PROGRAM = {}
    test_prefill_fork_and_decode_match_four_full_forwards = \
        contract.ForkedAgainstTheReference.program_matches_four_full_forwards
    PARAMETERS = {
        "test_prefill_fork_and_decode_match_four_full_forwards": [
            ("size", [37, 148])],
        "test_each_control_is_further_from_the_reference": [
            ("control", [name for name, _ in REF.CONTROLS])]}

    def check_extra(self, got, want, rows):
        # the reference wrote with strengths over 1: half the tokens turn
        # an eigenvalue of the transition negative
        (beta,) = want
        assert 1.5 < float(beta) < 2.0

    def test_the_reference_held_to_the_programs_operand_precision(self):
        """The second limit's reading: with bfloat16 matmul operands the
        program is far from the reference as written (roundoff) and an
        order nearer to the reference that rounds its operands where the
        program does; the state kept in bfloat16, which the first reading
        cannot see under the roundoff, shows against the second."""
        policy = dtypes.Policy(param_dtype=jnp.dtype(jnp.bfloat16))
        assert policy.compute_dtype == jnp.bfloat16
        stored = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), CASE.params())
        ids, continuations = REF.inputs(FAMILY, 3, 148)
        want = jax.jit(lambda p, i, c: REF.forward(FAMILY, p, i, c))(
            stored, ids, continuations)
        held = jax.jit(lambda p, i, c: REF.forward(
            FAMILY, p, i, c, operands=jnp.bfloat16))(
                stored, ids, continuations)
        got = jax.jit(REF.program(FAMILY, policy))(
            stored, ids, continuations)
        lower = jax.jit(REF.program(FAMILY, policy, state_bf16=True))(
            stored, ids, continuations)
        plain, near = rel_rms(got, want), rel_rms(got, held)
        # (at 24 channels roundoff weighs far more than at 3 840)
        assert plain > 1e-2 and near < plain / 8
        assert rel_rms(lower, held) > 1.5 * near
        assert rel_rms(lower, want) < 1.1 * plain       # unseen as written
        # with no dtype to hold it to, it is the reference as written
        same = jax.jit(lambda p, i, c: REF.forward(
            FAMILY, p, i, c, operands=None))(stored, ids, continuations)
        assert np.array_equal(np.asarray(same), np.asarray(want))

    def test_the_reference_says_the_model_itself(self):
        """It reads the layers' kinds and widths from the configuration
        and nothing of what this PR added to it: a program that read its
        own new keys wrong cannot take the reference with it."""
        with open(REF.__file__) as fh:
            text = fh.read()
        forward = text[text.index("# -- the reference"):
                       text.index("# -- the readings")]
        for key in ("norm_placement", "sublayer_norms", "qk_norm_extent",
                    "linear_write_scale", "rope_full", "models.lm",
                    "ops."):
            assert key not in forward, key
        assert REF.WRITE_SCALE == 2.0 == CFG.linear_write_scale


# -- (b) a step over B sequences ----------------------------------------------

class TestSequencesOfOneStep(contract.SequencesOfOneStep,
                             contract.StatesOfOneStep):
    CASE = CASE
    test_a_snapshot_restores_keys_values_and_states = \
        contract.StatesOfOneStep.a_snapshot_restores_every_buffer
    PARAMETERS = {"test_a_forked_decode_is_each_sequence_alone": [
        ("user,live,batch", [(1, 4, 4), (64, 3, 4)])]}

    test_a_fork_shares_every_key_and_value_and_copies_every_state = contract.SequencesOfOneStep \
        .a_fork_shares_what_has_positions_and_copies_the_rest

    def test_bytes_and_positions_of_a_forked_cache_of_both_kinds(self):
        assert lm.shares_a_step(CFG)
        assert lm.shares_a_step(configs.sd15_olmo_hybrid_expander().expander)
        manager = kv.KVCacheManager(CFG, jnp.bfloat16)
        assert manager.positions_in_use(40, 4, 30) == {
            "full": FULL_LAYERS * (30 + 4 * 10), "sliding": 0, "linear": 0}
        row = 2 * 3 * 8 * 2     # a position's keys and values, bfloat16
        assert kv.state_bytes(CFG, 256, jnp.bfloat16) == {
            "full": FULL_LAYERS * 256 * row, "sliding": 0,
            "linear": LINEAR_LAYERS * STATE}
        assert kv.state_bytes(CFG, 256, jnp.bfloat16, 4, 64) == {
            "full": FULL_LAYERS * (256 + 4 * 64) * row, "sliding": 0,
            "linear": 4 * LINEAR_LAYERS * STATE}
        assert kv.copied_bytes(CFG, jnp.bfloat16, 4) \
            == 4 * LINEAR_LAYERS * STATE
        assert kv.copied_bytes(CFG, jnp.bfloat16, 1) == 0


# -- (c) the delta rule at strengths up to 2 and unequal widths ---------------

def _delta_operands(tokens, heads, k_dim, v_dim, seed=0, most=2.0):
    ks = jax.random.split(jax.random.key(seed), 7)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (tokens, heads, k_dim))) * k_dim ** -0.5
    k = unit(jax.random.normal(ks[1], (tokens, heads, k_dim)))
    v = jax.random.normal(ks[2], (tokens, heads, v_dim))
    g = -0.2 * jax.nn.softplus(jax.random.normal(ks[3], (tokens, heads)))
    beta = jax.random.uniform(ks[4], (tokens, heads), minval=0.0,
                              maxval=most)
    state = 0.3 * jax.random.normal(ks[5], (heads, k_dim, v_dim))
    return state, q, k, v, g, beta


RECURRENT = jax.jit(delta_rule.recurrent)
CHUNKED = jax.jit(delta_rule.chunked)


class TestTheDeltaRuleAtStrengthsUpToTwo:
    @pytest.mark.parametrize("tokens,heads,k_dim,v_dim", [
        (200, 5, 96, 192), (64, 3, 6, 10), (130, 30, 12, 24)])
    def test_chunked_is_the_recurrence(self, tokens, heads, k_dim, v_dim):
        """The chunk-wise form's unit lower-triangular solve assumes
        nothing of ``beta``'s range: at strengths drawn over (0, 2), where
        half the tokens turn an eigenvalue of ``I - beta k k^T`` negative,
        at key and value widths that differ and a head count that is no
        power of two, over several chunks (the last one padded), it is
        the token-by-token recurrence."""
        operands = _delta_operands(tokens, heads, k_dim, v_dim)
        assert float(jnp.max(operands[-1])) > 1.9
        want, state = RECURRENT(*operands)
        got, after = CHUNKED(*operands)
        assert got.shape == (tokens, heads, v_dim)
        assert after.shape == (heads, k_dim, v_dim)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(after, state, rtol=2e-4, atol=2e-5)
        # and not the rule at half the strength
        state, q, k, v, g, beta = operands
        half, _ = RECURRENT(state, q, k, v, g, beta / 2)
        assert rel_rms(half, want) > 0.05

    def test_a_step_of_each_is_the_step_of_one(self):
        state, q, k, v, g, beta = _delta_operands(4, 30, 96, 192, seed=1)
        states = jnp.stack([state * (b + 1) for b in range(4)])
        out, after = jax.jit(delta_rule.recurrent_step_each)(
            states, q, k, v, g, beta)
        assert out.shape == (4, 30, 192) and after.shape == (4, 30, 96, 192)
        for b in range(4):
            o, s = delta_rule.recurrent_step(states[b], q[b], k[b], v[b],
                                             g[b], beta[b])
            np.testing.assert_allclose(out[b], o, rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(after[b], s, rtol=1e-6, atol=1e-6)
        # a masked row (g = 0, beta = 0) leaves its state where it was
        _, kept = delta_rule.recurrent_step_each(
            states, q, k, v, g.at[2].set(0.0), beta.at[2].set(0.0))
        assert np.array_equal(np.asarray(kept[2]), np.asarray(states[2]))

    def test_a_write_at_two_reflects_the_state_along_the_key(self):
        """``beta = 2``, no decay: ``S <- (I - 2 k k^T) S + 2 k v^T``, the
        state's component along ``k`` turned about, the eigenvalue -1."""
        state, q, k, v, g, beta = _delta_operands(1, 2, 6, 10, seed=2)
        _, after = delta_rule.recurrent_step(
            state, q[0], k[0], jnp.zeros_like(v[0]), jnp.zeros_like(g[0]),
            jnp.full_like(beta[0], 2.0))
        along = jnp.einsum("hkv,hk->hv", state, k[0])
        np.testing.assert_allclose(
            jnp.einsum("hkv,hk->hv", after, k[0]), -along, rtol=1e-5,
            atol=1e-6)
        np.testing.assert_allclose(
            jnp.linalg.norm(after, axis=(1, 2)),
            jnp.linalg.norm(state, axis=(1, 2)), rtol=1e-5)


# -- (d) the tree, the counts and the rules ------------------------------------

class TestTheTreeAndItsRules(contract.ShardingRules):
    def test_the_leaves_of_each_kind(self, params):
        assert CFG.layer_types == ("linear", "linear", "linear", "full") * 2
        assert CFG.sublayer_norms == ("pre", "pre", "pre", "post") * 2
        assert set(params["layers_0"]) == {
            "delta", "mlp", "input_norm", "post_attention_norm"}
        assert set(params["layers_3"]) == {
            "attn", "mlp", "input_norm_2", "post_attention_norm_2"}
        attn = params["layers_3"]["attn"]
        assert set(attn) == {"q_proj", "k_proj", "v_proj", "o_proj",
                             "q_norm", "k_norm"}
        # one weight a column of the whole projection, not one a head's
        assert attn["q_norm"]["scale"].shape == (3 * 8,)
        assert attn["k_norm"]["scale"].shape == (3 * 8,)
        delta = params["layers_1"]["delta"]
        assert set(delta) == {"qkvz_proj", "ba_proj", "conv_kernel", "A_log",
                              "dt_bias", "norm", "out_proj"}
        assert delta["qkvz_proj"]["kernel"].shape == (24, 18 + 18 + 30 + 30)
        assert delta["conv_kernel"].shape == (4, 66)
        assert delta["norm"]["scale"].shape == (10,)
        assert delta["out_proj"]["kernel"].shape == (30, 24)
        assert set(params["layers_0"]["mlp"]) == {"gate_proj", "up_proj",
                                                  "down_proj"}
        assert set(params["norm"]) == {"scale"}

    def test_the_keys_at_their_defaults_are_the_old_model(self):
        """Every existing preset says nothing of the four new keys and
        reads as it did: input norms alone (or both under
        ``post_sublayer_norm``), a rotary table, norms a head, strength
        under 1."""
        for name in ("TINY_EXPAND", "TINY_DELTA_EXPAND", "TINY_LOOP_EXPAND",
                     "TINY_GIGACHAT35_EXPAND", "TINY_KANANA_EXPAND"):
            cfg = getattr(configs, name).expander
            assert cfg.norm_placement == () and cfg.rope_full is not None
            assert cfg.qk_norm_extent == "head"
            assert cfg.linear_write_scale == 1.0
            assert set(cfg.sublayer_norms) == {
                "both" if cfg.post_sublayer_norm else "pre"}
            assert lm.site_attrs(cfg) == {}
        assert lm.site_attrs(CFG) == {
            "norms_pre": 12, "norms_post": 4, "unrotated": 2,
            "write_strength_bound": 2.0}
        with pytest.raises(ValueError):
            configs.LMConfig(norm_placement=("pre", "post"))
        with pytest.raises(ValueError):
            configs.LMConfig(norm_placement=("after",))

    def test_a_share_keeps_the_placement_of_the_layers_it_holds(self):
        whole = configs.OLMO_HYBRID_7B
        share = configs.lm_share(whole, (0, 3, 4), chips=1, rank=0)
        assert share.layer_types == ("linear", "full", "linear")
        assert share.sublayer_norms == ("pre", "post", "pre")
        assert configs.lm_share(configs.TINY_LM, 2, chips=2,
                                rank=0).norm_placement == ()

    #: the new leaves: a query norm's weight over the whole projection and
    #: the norms after the sublayers are replicated; a state whose widths
    #: differ stays whole on every chip
    WHOLE = (("layers_3/attn/q_norm/scale", 1),
             ("layers_3/attn/k_norm/scale", 1),
             ("layers_3/input_norm_2/scale", 1),
             ("layers_3/post_attention_norm_2/scale", 1),
             ("layers_0/delta/norm/scale", 1),
             ("layers_0/delta/A_log", 1),
             ("layers_0/delta/conv_kernel", 2))
    PLACED_WHOLE = ("layers_3/attn/q_norm/scale",
                    "layers_3/input_norm_2/scale",
                    "layers_0/delta/qkvz_proj/kernel")
    test_sharding_rules = contract.ShardingRules.sharding_rules

    def check_placed(self, placed, mesh):
        # the published state and query norm, as shapes
        share = configs.sd15_olmo_hybrid_expander().expander
        shapes = lm.cache_shapes(share, 2560)
        assert shapes["state"] == [(30, 96, 192)] * 12
        assert shapes["conv"] == [(3, 11520)] * 12
        assert shapes["k"] == [(2560, 30, 128)] * 4


# -- (e) the engine's path ----------------------------------------------------

class TestEnginePath(contract.ForkedEnginePath):
    test_a_batch_prefills_once_forks_and_decodes_four_a_step = contract.ForkedEnginePath \
        .a_batch_prefills_once_forks_and_decodes_four_a_step
    test_every_image_its_own_expansion_and_one_image_the_old_path = contract.ForkedEnginePath \
        .every_image_its_own_expansion_and_one_image_the_old_path
    CASE = CASE
    #: how the model departs from input norms, rotation and strength 1
    DEPARTURES = {"norms_pre": 12, "norms_post": 4, "unrotated": 2,
                  "write_strength_bound": 2.0}

    def check_traced(self, sites, traced):
        assert sum(sites["by_shape"][
            f"T1 S{CAPACITY + 2 * STEPS} D8"].values()) == FULL_LAYERS
        # two prefill executables and one forked decode chunk were traced
        assert traced["delta_mixers"] == {
            "recurrent": 0, "chunked": 2 * LINEAR_LAYERS,
            "recurrent_forked": LINEAR_LAYERS}
        assert traced["delta_steps"] == {"kernel": 0,
                                         "elementwise": LINEAR_LAYERS}
        assert traced["sublayer_norms"] == {
            "pre": {"recurrent": 0, "chunked": 2 * 12,
                    "recurrent_forked": 12},
            "post": {"recurrent": 0, "chunked": 2 * 4,
                     "recurrent_forked": 4}}
        assert traced["attention_unrotated"] == {
            "recurrent": 0, "chunked": 2 * FULL_LAYERS,
            "recurrent_forked": FULL_LAYERS}
        assert traced["write_strength_bound"] == 2.0

    def check_counted(self, stats, sizes, one):
        assert stats["tokens_no_held_expert"] == 0 == stats["experts_read"]
        assert stats["cache_positions"] == {
            "full": FULL_LAYERS * (36 + 4 * 40), "sliding": 0, "linear": 0}
        state = LINEAR_LAYERS * STATE
        assert one["linear"] == state and sizes["linear"] == 4 * state
        assert stats["fork_bytes_copied"] == 4 * state
        # a step reads and writes each sequence's states once
        assert stats["state_bytes_stepped"] == 2 * STEPS * 2 * 4 * state
        # nothing was traced again: the counters of the sites stay 0
        assert stats["write_strength_bound"] == 0.0

    def check_spans(self, by_name, sizes, one):
        state = LINEAR_LAYERS * STATE
        (prefill,) = by_name["expand.prefill"]
        assert prefill["sequences"] == 4    # whose first tokens it draws
        assert prefill["form"] == "chunked" and prefill["padded"] == 59
        (fork,) = by_name["expand.fork"]
        assert fork["delta"] == "recurrent_forked"
        assert fork["state_bytes_copied"] == 4 * state
        # two full layers' own rows of 64 slots a sequence and four copies
        # of every state, float32
        assert fork["bytes"] == FULL_LAYERS * 4 * 2 * STEPS * 2 * 24 * 4 \
            + 4 * state
        chunks = by_name["expand.decode_chunk"]
        assert [a["delta"] for a in chunks] == ["recurrent_forked"] * 2
        for attrs in [prefill, fork] + chunks:
            assert {k: attrs[k] for k in self.DEPARTURES} == self.DEPARTURES
        hits = [a for a in by_name["expand.prefix_copy"] if a.get("hit")]
        assert hits and hits[0]["bytes"] == sum(one.values())

    def check_one_image(self, sites, stats):
        assert stats["delta_mixers"]["recurrent"] == LINEAR_LAYERS
        assert stats["attention_unrotated"]["recurrent"] == FULL_LAYERS
        assert stats["fork_bytes_copied"] == 2 * LINEAR_LAYERS * STATE

    def test_the_prometheus_families_and_the_status_keys(self, engine):
        ATTENTION.clear()
        EXPANDER.clear()
        contract.sites_of(
            CFG, CASE.params(), jnp.zeros((4,), jnp.int32), 40, 4,
            contract.forked_structs(CFG, 64, 4, 32, jnp.float32),
            sequences=True)
        summary = METRICS.summary()["expander"]
        assert {"sublayer_norms", "attention_unrotated",
                "write_strength_bound", "delta_mixers",
                "state_bytes_stepped", "fork_bytes_copied"} <= set(summary)
        assert summary["sublayer_norms"]["post"]["recurrent_forked"] == 4
        text = prometheus.render()
        assert 'sdtpu_expander_sublayer_norms_total{placement="pre",' \
            'form="recurrent_forked"} 12' in text
        assert 'sdtpu_expander_sublayer_norms_total{placement="post",' \
            'form="recurrent_forked"} 4' in text
        assert 'sdtpu_expander_attention_unrotated_total{' \
            'form="recurrent_forked"} 2' in text
        assert "sdtpu_expander_write_strength_bound 2" in text
        # a sibling's mixer writes under 1
        EXPANDER.clear()
        other = configs.TINY_DELTA_EXPAND.expander
        contract.param_shapes(other)
        summary = EXPANDER.summary()
        assert summary["write_strength_bound"] == 1.0
        assert summary["attention_unrotated"]["chunked"] == 0
        assert summary["sublayer_norms"]["post"]["chunked"] == 0
        assert summary["sublayer_norms"]["pre"]["chunked"] == 8
        ATTENTION.clear()
        EXPANDER.clear()

    def test_a_warm_start_counts_the_sites_again(self):
        """What a trace counted is replayed when its program is loaded
        (serving/aot.py): the rows a capture holds count once more."""
        from stable_diffusion_webui_distributed_tpu.serving import metrics

        EXPANDER.clear()
        with metrics.capture_sites() as rows:
            EXPANDER.record_norm("post", "recurrent_forked")
            EXPANDER.record_unrotated("chunked")
            EXPANDER.record_delta("recurrent_forked", 2.0)
        EXPANDER.clear()
        metrics.replay_sites(rows)
        summary = EXPANDER.summary()
        assert summary["sublayer_norms"]["post"]["recurrent_forked"] == 1
        assert summary["attention_unrotated"]["chunked"] == 1
        assert summary["delta_mixers"]["recurrent_forked"] == 1
        assert summary["write_strength_bound"] == 2.0
        EXPANDER.clear()


# -- (f) the published model and its share, from shapes -----------------------

class TestThePublishedShare:
    def test_parameters_and_bytes_from_shapes(self):
        share = configs.sd15_olmo_hybrid_expander().expander
        whole = configs.OLMO_HYBRID_7B
        assert whole.num_layers == 32 and share.num_layers == 16
        assert whole.layers_of("full") == tuple(range(3, 32, 4))
        assert share.layer_types == ("linear", "linear", "linear",
                                     "full") * 4
        assert share.sublayer_norms == ("pre", "pre", "pre", "post") * 4
        assert share.dense_layers == tuple(range(16))
        assert share.vocab == (0, 100352) and not share.expert_layers
        assert (share.hidden_size, share.intermediate_size, share.head_dim,
                share.num_kv_heads) == (3840, 11008, 128, 30)
        assert share.rope_full is None and share.attn_gate == "none"
        assert (share.linear_num_key_heads, share.linear_num_value_heads,
                share.linear_key_head_dim, share.linear_value_head_dim,
                share.linear_conv_kernel, share.linear_write_scale) \
            == (30, 30, 96, 192, 4, 2.0)
        shapes = contract.param_shapes(share)
        delta = shapes["layers_0"]["delta"]
        assert delta["qkvz_proj"]["kernel"].shape == (3840, 11520 + 5760)
        assert delta["ba_proj"]["kernel"].shape == (3840, 60)
        assert delta["out_proj"]["kernel"].shape == (5760, 3840)
        assert delta["conv_kernel"].shape == (4, 11520)
        assert round(count(delta["qkvz_proj"]) / 1e6, 2) == 66.36
        assert round(count(delta["ba_proj"]) / 1e6, 2) == 0.23
        assert round(count(delta["out_proj"]) / 1e6, 2) == 22.12
        assert round(count(delta["conv_kernel"]) / 1e6, 2) == 0.05
        assert round(count(delta) / 1e6, 2) == 88.75
        attn = shapes["layers_3"]["attn"]
        assert attn["q_norm"]["scale"].shape == (3840,)
        assert round(count(attn) / 1e6, 2) == 58.99    # 58.98 + the norms
        assert round(count(shapes["layers_0"]["mlp"]) / 1e6, 2) == 126.81
        linear, full = count(shapes["layers_0"]), count(shapes["layers_3"])
        assert round(linear / 1e6, 2) == 215.57
        assert round(full / 1e6, 2) == 185.81
        assert round((3 * linear + full) / 1e6, 1) == 832.5
        assert count(shapes["embed_tokens"]) == count(shapes["lm_head"]) \
            == 3840 * 100352
        assert round(3840 * 100352 / 1e6, 2) == 385.35
        total = count(shapes)
        # ISSUE 59's 4 100.6 M and the norms' weights, A_log and dt_bias
        assert round(total / 1e6, 1) == 4100.8
        assert round(total * 2 / 1e9, 2) == 8.20
        # beside SD1.5's 1 066 M: 10.33 GB = 9.62 GiB
        assert round((total + 1066e6) * 2 / 1e9, 2) == 10.33
        assert round((total + 1066e6) * 2 / 2 ** 30, 2) == 9.62
        # the whole model from the same shapes: 7 431 M
        published = 8 * (3 * linear + full) + 2 * 3840 * 100352 + 3840
        assert round(published / 1e6) == 7431
        assert round(published * 2 / 1e9, 1) == 14.9
        # twenty layers would fit on paper, without any cache
        twenty = total + 3 * linear + full
        assert round((twenty + 1066e6) * 2 / 2 ** 30, 2) == 11.17
        # the fallback's twelve layers
        assert round((total - 3 * linear - full) / 1e6) == 3268
        # the caches of four forked sequences at the cell's capacity
        capacity = kv.capacity_for(2048 + 64 + 8 * STEPS)
        assert capacity == 2560
        state = (30 * 96 * 192 + 3 * 11520) * 4
        assert 30 * 96 * 192 * 4 == 2_211_840
        position = 2 * 30 * 128 * 2
        assert position == 15_360
        assert kv.state_bytes(share, capacity, jnp.bfloat16, 4, 8 * STEPS) \
            == {"full": 4 * (2560 + 4 * 256) * position, "sliding": 0,
                "linear": 4 * 12 * state}
        assert round(4 * 2560 * position / 1e6) == 157
        assert round(4 * 4 * 256 * position / 1e6) == 63
        assert round(12 * state / 1e6, 2) == 28.2
        assert kv.copied_bytes(share, jnp.bfloat16, 4) == 48 * state
        assert round(48 * state / 2 ** 20, 1) == 107.6
        assert round(2 * 48 * state / 2 ** 20, 1) == 215.2

    def test_a_forked_step_of_the_share_traced_as_on_the_chip(self,
                                                              monkeypatch):
        """One decode step of the share the cell runs, traced without
        weights or FLOPs (nothing compiles; tests/test_chip_compile.py
        compiles it for a described v5e): twelve delta mixers a recurrent
        step a sequence at strength up to 2, four unrotated attention
        sites over 2 560 shared and 256 own rows of 128, 24 norms before
        a sublayer and 8 after."""
        share = configs.sd15_olmo_hybrid_expander().expander
        cache = contract.forked_structs(share, 2560, 4, 256)
        assert [x.shape for x in cache["k_shared"]] == [(2560, 30, 128)] * 4
        assert [x.shape for x in cache["k"]] == [(4, 256, 30, 128)] * 4
        assert [(x.shape, x.dtype) for x in cache["state"]] \
            == [((4, 30, 96, 192), jnp.float32)] * 12
        assert [x.shape for x in cache["conv"]] == [(4, 3, 11520)] * 12
        shapes = contract.param_shapes(share)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ATTENTION.clear()
        EXPANDER.clear()
        logits, after, routed = contract.sites_of(
            share, shapes, jnp.zeros((4,), jnp.int32), 2200, 4,
            cache, jnp.bfloat16, sequences=True)
        assert logits.shape == (4, 100352)
        assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), after) \
            == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), cache)
        assert routed[0].shape == (0, 1, 1)     # no expert layer
        stats = EXPANDER.summary()
        assert stats["delta_mixers"] == {"recurrent": 0, "chunked": 0,
                                         "recurrent_forked": 12}
        assert stats["delta_steps"] == {"kernel": 0, "elementwise": 12}
        assert stats["sublayer_norms"]["pre"]["recurrent_forked"] == 24
        assert stats["sublayer_norms"]["post"]["recurrent_forked"] == 8
        assert stats["attention_unrotated"]["recurrent_forked"] == 4
        assert stats["write_strength_bound"] == 2.0
        assert stats["expert_products"] == {"kernel": 0, "loop": 0,
                                            "grouped": 0}
        (shape, paths), = ATTENTION.summary()["by_shape"].items()
        assert shape == "T1 S2816 D128" and sum(paths.values()) == 4
        ATTENTION.clear()
        EXPANDER.clear()

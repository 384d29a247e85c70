"""The prompt expander as a dense hybrid whose EVERY layer holds two token
mixers under one norm (``TINY_FALCON_H1_EXPAND``; the benchmark's
``sd15_falcon_h1_expand``): rotated grouped-query attention AND a selective
state-space mixer (ops/ssm.py) side by side, then a dense SwiGLU, under
fourteen forward multipliers.

(a) the program through prefill, fork and forked decode against the plain
reference (benchmarks/reference/falcon_h1_ref.py: one full float32 forward a
sequence, the recurrence token by token), twenty-three controls of the
program that must miss the tolerance (each of the fourteen multipliers left
out alone among them) and two faults of the reference itself; (b) a step
over several sequences against each decoded alone, a padded group, an ended
sequence, the fork and the snapshot; (c) the three forms of the recurrence
against the token-by-token rule; (d) the tree, the kinds' one helper and
the sharding rules; (e) the engine's path with its spans, counters and
Prometheus families; (f) the published share from shapes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.obs import prometheus
from stable_diffusion_webui_distributed_tpu.ops import ssm
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER, METRICS,
)
from tests import expander_contract as contract
from tests.expander_contract import CAPACITY, STEPS, count, rel_rms

REF = contract.load_reference("falcon_h1")
#: every norm's scale, the skip, ``dt_bias`` and the convolution's bias off
#: the values flax gives them, the decay rates spread from a token to
#: hundreds, as the benchmark seeds them
CASE = contract.Case(
    configs.TINY_FALCON_H1_EXPAND, REF,
    how=(("spread", (("scale", 0.5), ("dt_bias", 0.3), ("D", 0.5),
                     ("conv_bias", 0.3))),
         ("a_log", (-5.0, -3.0, -1.0, 0.5, 1.5, 2.5))),
    extra="with_decays", tolerance=1e-5,
    controls=tuple(name for name, _ in REF.CONTROLS))
FAMILY, CFG = CASE.family, CASE.cfg
LAYERS = 3
#: one sequence's state and kept rows in one layer's state-space part,
#: float32: 6 heads of 5 over 7-wide states, 3 rows of 72 channels
STATE = (6 * 5 * 7 + 3 * 72) * 4
#: a position's keys and values of one layer: 2 KV heads of 8
ROW = 2 * 2 * 8
params, engine = contract.fixtures(CASE)


# -- (a) program against reference --------------------------------------------

class TestAgainstTheReference(contract.ForkedAgainstTheReference):
    """Attention over what the chunk wrote and the chunk-wise recurrence
    from a zero state, a copy, a fork into four and one step a sequence
    over two ranges of keys and a state of its own: logits to 1e-5. Each
    control misses a hundred times over."""
    CASE = CASE
    PROGRAM = {}
    test_prefill_fork_and_decode_match_four_full_forwards = \
        contract.ForkedAgainstTheReference.program_matches_four_full_forwards
    PARAMETERS = {
        "test_prefill_fork_and_decode_match_four_full_forwards": [
            ("size", [37, 148])],
        "test_each_control_is_further_from_the_reference": [
            ("control", [name for name, _ in REF.CONTROLS])],
        "test_a_fault_of_the_reference_itself_is_seen": [
            ("fault", list(REF.FAULTS))]}

    def check_extra(self, got, want, rows):
        # some head forgot inside a token, some remembers hundreds
        most, least = want
        assert float(most) > 0.99 and float(least) < 1e-3

    def test_the_controls_are_the_issues_list(self):
        names = [name for name, _ in REF.CONTROLS]
        assert names[:9] == [
            "control", "state_bf16", "state_shared", "norm_before_gate",
            "no_conv_bias", "no_skip", "no_dt_bias", "wrong_group",
            "rotary_1e4"]
        assert names[9:] == ["no_" + m for m in REF.MULTIPLIERS]
        assert len(REF.MULTIPLIERS) == 14 == CFG.multipliers_applied
        # every one of the tiny preset's is off 1, the published share's
        # attention input alone is 1
        assert all(v != 1.0 for v in REF.multipliers(CFG).values())
        share = configs.sd15_falcon_h1_expander().expander
        assert [k for k, v in REF.multipliers(share).items() if v == 1.0] \
            == ["attention_in_multiplier"]
        assert share.multipliers_applied == 13
        assert set(REF.CHIP_CONTROLS) <= set(names)

    def test_a_fault_of_the_reference_itself_is_seen(self, params, fault):
        """What no key or leaf of the program can say wrongly (one norm
        group for the groups' own, the mixers reading two norms) is put
        into the reference: the program then misses it."""
        inputs, want, *_ = CASE.referred(CASE.control_size)
        got = CASE.program()(params, *inputs)
        wrong = jax.jit(lambda p, *a: REF.forward(
            FAMILY, p, *a, fault=fault))(params, *inputs)
        assert rel_rms(got, want) < CASE.tolerance
        assert rel_rms(got, wrong) > 0.1

    def test_the_reference_says_the_model_itself(self):
        """It reads widths, ``theta``, ``eps`` and the multipliers' values
        and nothing of how the program spells a layer of two mixers: a
        program that read its own new keys wrong cannot take the
        reference with it."""
        with open(REF.__file__) as fh:
            text = fh.read()
        forward = text[text.index("# -- the reference"):
                       text.index("# -- the readings")]
        for key in ("layer_types", "layers_of", "kind_parts", "base_kinds",
                    "mixer_multiplier(", "ssm_norm_before_gate",
                    "ssm_conv_bias", "ssm_chunk", "ssm_inner",
                    "ssm_conv_channels", "models.lm", "ops."):
            assert key not in forward, key

    def test_the_reference_held_to_the_programs_operand_precision(self):
        """With bfloat16 matmul operands the program is nearer to the
        reference that rounds its operands where the program does than to
        the reference as written."""
        from stable_diffusion_webui_distributed_tpu.runtime import dtypes

        policy = dtypes.Policy(param_dtype=jnp.dtype(jnp.bfloat16))
        stored = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), CASE.params())
        ids, continuations = REF.inputs(FAMILY, 3, 74)
        want = jax.jit(lambda p, i, c: REF.forward(FAMILY, p, i, c))(
            stored, ids, continuations)
        held = jax.jit(lambda p, i, c: REF.forward(
            FAMILY, p, i, c, operands=jnp.bfloat16))(
                stored, ids, continuations)
        got = jax.jit(REF.program(FAMILY, policy))(
            stored, ids, continuations)
        plain, near = rel_rms(got, want), rel_rms(got, held)
        assert plain > 5e-3 and near < plain / 2


# -- (b) a step over B sequences ----------------------------------------------

class TestSequencesOfOneStep(contract.SequencesOfOneStep,
                             contract.StatesOfOneStep):
    CASE = CASE
    test_a_snapshot_restores_keys_values_states_and_kept_rows = \
        contract.StatesOfOneStep.a_snapshot_restores_every_buffer
    PARAMETERS = {"test_a_forked_decode_is_each_sequence_alone": [
        ("user,live,batch", [(1, 4, 4), (64, 3, 4)])]}

    test_a_fork_shares_every_key_and_value_and_copies_every_state = \
        contract.SequencesOfOneStep \
        .a_fork_shares_what_has_positions_and_copies_the_rest

    def check_fork(self, forked):
        # ONE layer's buffers are some shared and some copied
        assert set(forked) == {"k", "v", "k_shared", "v_shared",
                               "ssm_state", "ssm_conv", lm.FORKED_AT}
        assert [x.shape for x in forked["ssm_state"]] \
            == [(4, 6, 5, 7)] * LAYERS
        assert [x.shape for x in forked["ssm_conv"]] == [(4, 3, 72)] * LAYERS

    def test_bytes_and_positions_of_a_cache_of_two_kinds_a_layer(self):
        assert lm.shares_a_step(CFG)
        assert lm.shares_a_step(configs.sd15_falcon_h1_expander().expander)
        manager = kv.KVCacheManager(CFG, jnp.bfloat16)
        assert manager.positions_in_use(40, 4, 30) == {
            "full": LAYERS * (30 + 4 * 10), "sliding": 0, "ssm": 0}
        # by BASE kind: a layer adds its rows to ``full``, its states to
        # ``ssm``
        assert kv.state_bytes(CFG, 256, jnp.bfloat16) == {
            "full": LAYERS * 256 * ROW * 2, "sliding": 0,
            "ssm": LAYERS * STATE}
        assert kv.state_bytes(CFG, 256, jnp.bfloat16, 4, 64) == {
            "full": LAYERS * (256 + 4 * 64) * ROW * 2, "sliding": 0,
            "ssm": 4 * LAYERS * STATE}
        assert kv.copied_bytes(CFG, jnp.bfloat16, 4) == 4 * LAYERS * STATE
        assert kv.copied_bytes(CFG, jnp.bfloat16, 1) == 0


# -- (c) the three forms of the recurrence ------------------------------------

def _ssm_operands(tokens, heads, dim, width, groups, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (tokens, heads, dim))
    b = jax.random.normal(ks[1], (tokens, groups, width))
    c = jax.random.normal(ks[2], (tokens, groups, width))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (tokens, heads)))
    # rates from 0.007 to 55 a unit of dt: a memory of hundreds of tokens
    # down to none
    rate = jnp.exp(jnp.linspace(-5.0, 4.0, heads))
    state = 0.3 * jax.random.normal(ks[5], (heads, dim, width))
    return state, x, b, c, dt, -rate * dt


RECURRENT = jax.jit(ssm.recurrent)
CHUNKED = jax.jit(ssm.chunked, static_argnames="chunk")


class TestTheThreeFormsOfTheRecurrence:
    @pytest.mark.parametrize("tokens,heads,dim,width,groups,chunk", [
        (200, 6, 5, 7, 3, 16), (75, 32, 16, 24, 2, 32), (64, 4, 8, 8, 1, 64),
        (300, 8, 128, 256, 2, 128)])
    def test_chunked_is_the_recurrence(self, tokens, heads, dim, width,
                                       groups, chunk):
        """Over several chunks, at a length that is no multiple of the
        chunk (the last one padded with masked rows), from a state that is
        not zero, with decays from a token to hundreds: the segment sum is
        the token-by-token rule, and finite where a quotient of
        exponentials overflows."""
        operands = _ssm_operands(tokens, heads, dim, width, groups)
        decay = operands[-1]
        assert float(jnp.min(decay)) < -50      # exp(50) a token: a quotient
        assert float(jnp.max(decay)) > -0.01    # of exponentials overflows
        want, state = RECURRENT(*operands)
        got, after = CHUNKED(*operands, chunk=chunk)
        assert got.shape == (tokens, heads, dim)
        assert after.shape == (heads, dim, width)
        assert np.isfinite(got).all() and np.isfinite(after).all()
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * scale)
        np.testing.assert_allclose(after, state, rtol=2e-4,
                                   atol=2e-5 * float(jnp.max(jnp.abs(state))))
        # and not the rule of another group's maps
        state0, x, b, c, dt, decay = operands
        if groups > 1:
            other, _ = RECURRENT(state0, x, jnp.roll(b, 1, 1), c, dt, decay)
            assert rel_rms(other, want) > 0.1

    def test_a_step_of_each_is_the_step_of_one(self):
        state, x, b, c, dt, decay = _ssm_operands(4, 32, 16, 24, 2, seed=1)
        states = jnp.stack([state * (i + 1) for i in range(4)])
        out, after = jax.jit(ssm.step_each)(states, x, b, c, dt, decay)
        assert out.shape == (4, 32, 16) and after.shape == (4, 32, 16, 24)
        for i in range(4):
            y, s = ssm.step(states[i], x[i], b[i], c[i], dt[i], decay[i])
            np.testing.assert_allclose(out[i], y, rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(after[i], s, rtol=1e-6, atol=1e-6)
        # a masked row (dt = 0, so no decay either) leaves its state
        _, kept = ssm.step_each(states, x, b, c, dt.at[2].set(0.0),
                                decay.at[2].set(0.0))
        assert np.array_equal(np.asarray(kept[2]), np.asarray(states[2]))

    def test_the_step_is_the_rule_as_written(self):
        """``S <- a S + dt x B^T``, ``y = S C``, head ``j`` under the maps
        of group ``j // (heads / groups)``."""
        state, x, b, c, dt, decay = _ssm_operands(1, 6, 5, 7, 3, seed=2)
        y, after = ssm.step(state, x[0], b[0], c[0], dt[0], decay[0])
        for j in range(6):
            g = j // 2
            want = np.exp(decay[0, j]) * np.asarray(state[j]) \
                + dt[0, j] * np.outer(x[0, j], b[0, g])
            np.testing.assert_allclose(after[j], want, rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(y[j], want @ np.asarray(c[0, g]),
                                       rtol=1e-5, atol=1e-5)

    def test_the_forms_by_rows_and_whose_they_are(self):
        assert ssm.form(1) == "recurrent" and ssm.form(64) == "chunked"
        assert ssm.form(4, sequences=True) == "recurrent_forked"
        assert ssm.form(1, sequences=True) == "recurrent_forked"


# -- (d) the tree, the kinds' helper and the rules -----------------------------

class TestTheTreeAndItsRules(contract.ShardingRules):
    def test_the_leaves_of_a_layer_of_two_mixers(self, params):
        assert CFG.layer_types == ("full+ssm",) * LAYERS
        assert set(params["layers_0"]) == {
            "attn", "ssm", "mlp", "input_norm", "post_attention_norm"}
        assert set(params["layers_1"]["attn"]) == {
            "q_proj", "k_proj", "v_proj", "o_proj"}
        mixer = params["layers_1"]["ssm"]
        assert set(mixer) == {"in_proj", "conv_kernel", "conv_bias",
                              "A_log", "D", "dt_bias", "norm", "out_proj"}
        # [z 30 | x 30 | B 3 x 7 | C 3 x 7 | dt 6]
        assert mixer["in_proj"]["kernel"].shape == (24, 30 + 30 + 21 + 21 + 6)
        assert mixer["conv_kernel"].shape == (4, 72)
        assert mixer["conv_bias"].shape == (72,)
        assert mixer["norm"]["scale"].shape == (30,)
        assert mixer["out_proj"]["kernel"].shape == (30, 24)
        assert {mixer[n].shape for n in ("A_log", "D", "dt_bias")} == {(6,)}
        assert set(params["layers_0"]["mlp"]) == {"gate_proj", "up_proj",
                                                  "down_proj"}
        assert set(params["norm"]) == {"scale"}

    def test_one_helper_splits_a_kind_and_a_base_kind_is_a_layer(self):
        assert configs.kind_parts("full+ssm") == ("full", "ssm")
        assert configs.kind_parts("linear") == ("linear",)
        assert lm.buffers_of("full+ssm") == ("k", "v", "ssm_state",
                                             "ssm_conv")
        assert lm.buffers_of("full+ssm", forked=True) == (
            "k", "v", "k_shared", "v_shared", "ssm_state", "ssm_conv")
        assert lm.buffers_of("ssm", forked=True) == lm.SSM_BUFFERS
        assert lm.buffers_of("ssm+sliding") == lm.SSM_BUFFERS + ("k", "v")
        for name in lm.SSM_BUFFERS:
            assert lm.slots_axis(name) is None
            assert lm.buffer_dtype(name, jnp.bfloat16) == jnp.float32
        assert CFG.layers_of("full") == CFG.layers_of("ssm") == (0, 1, 2)
        assert CFG.layers_of("linear") == ()
        assert CFG.base_kinds == {"full", "ssm"}
        # state-space layers ALONE alternating with attention layers
        apart = dataclasses.replace(
            CFG, layer_types=("ssm", "full", "ssm"))
        assert apart.layers_of("ssm") == (0, 2)
        assert apart.layers_of("full") == (1,)
        assert lm.shares_a_step(apart)
        shapes = lm.cache_shapes(apart, 64)
        assert shapes["k"] == [(64, 2, 8)]
        assert shapes["ssm_state"] == [(6, 5, 7)] * 2
        assert kv.state_bytes(apart, 64, jnp.bfloat16) == {
            "full": 64 * ROW * 2, "sliding": 0, "ssm": 2 * STATE}
        tree = contract.param_shapes(apart)
        assert set(tree["layers_0"]) == {"ssm", "mlp", "input_norm",
                                         "post_attention_norm"}
        assert set(tree["layers_1"]) == {"attn", "mlp", "input_norm",
                                         "post_attention_norm"}
        # a conv part's kept rows still keep a model to one sequence a step
        assert not lm.shares_a_step(dataclasses.replace(
            CFG, layer_types=("full+ssm", "conv", "full+ssm")))

    def test_the_keys_at_their_defaults_are_the_old_model(self):
        """Every existing preset says nothing of the new keys: no
        multiplier is applied, no layer is joined, no leaf is added."""
        for name in ("TINY_EXPAND", "TINY_DELTA_EXPAND", "TINY_LOOP_EXPAND",
                     "TINY_GIGACHAT35_EXPAND", "TINY_KANANA_EXPAND",
                     "TINY_OLMO_HYBRID_EXPAND", "TINY_CONV_EXPAND",
                     "TINY_LATENT_EXPAND", "TINY_WINDOW_EXPAND"):
            cfg = getattr(configs, name).expander
            assert cfg.multipliers_applied == 0
            assert cfg.mixer_multipliers == ()
            assert all(cfg.mixer_multiplier(kind) == (1.0, 1.0)
                       for kind in cfg.base_kinds)
            assert "ssm" not in cfg.base_kinds
            assert all(len(configs.kind_parts(k)) == 1
                       for k in cfg.layer_types)
            attrs = lm.site_attrs(cfg)
            assert not {"ssm_layers", "joined_layers",
                        "multipliers"} & set(attrs)
        assert lm.site_attrs(CFG) == {"ssm_layers": 3, "joined_layers": 3,
                                      "multipliers": 14}
        assert configs.TINY_DELTA_EXPAND.expander.layers_of("linear") \
            == tuple(i for i, k in enumerate(
                configs.TINY_DELTA_EXPAND.expander.layer_types)
                if k == "linear")

    def test_a_convolutions_bias_is_added_to_every_row(self):
        kernel = jax.random.normal(jax.random.key(0), (4, 6))
        x = jax.random.normal(jax.random.key(1), (9, 6))
        kept = jax.random.normal(jax.random.key(2), (3, 6))
        bias = jnp.arange(6.0)
        plain, rows = lm.causal_conv(kernel, kept, x, 9)
        biased, same = lm.causal_conv(kernel, kept, x, 9, bias)
        np.testing.assert_allclose(biased, plain + bias, rtol=1e-6)
        assert np.array_equal(np.asarray(rows), np.asarray(same))
        each = jnp.stack([kept, kept * 2])
        real = jnp.array([True, False])
        plain, rows = lm.causal_conv_rows(kernel, each, x[:2], real)
        biased, same = lm.causal_conv_rows(kernel, each, x[:2], real, bias)
        np.testing.assert_allclose(biased, plain + bias, rtol=1e-6)
        assert np.array_equal(np.asarray(rows), np.asarray(same))
        assert np.array_equal(np.asarray(rows[1]), np.asarray(each[1]))

    #: the new leaves stay whole on every chip: the fused in_proj's five
    #: ranges would not split on their borders
    WHOLE = (("layers_0/ssm/in_proj/kernel", 2),
             ("layers_0/ssm/out_proj/kernel", 2),
             ("layers_0/ssm/conv_kernel", 2),
             ("layers_0/ssm/conv_bias", 1),
             ("layers_0/ssm/norm/scale", 1),
             ("layers_0/ssm/A_log", 1), ("layers_0/ssm/D", 1),
             ("layers_0/ssm/dt_bias", 1))
    PLACED_WHOLE = ("layers_0/ssm/in_proj/kernel",
                    "layers_0/ssm/conv_bias", "layers_0/input_norm/scale")
    test_sharding_rules = contract.ShardingRules.sharding_rules

    def check_placed(self, placed, mesh):
        # the published leaves and states, as shapes
        share = configs.sd15_falcon_h1_expander().expander
        tree = contract.param_shapes(share)
        mixer = tree["layers_0"]["ssm"]
        assert mixer["in_proj"]["kernel"].shape == (5120, 9248)
        assert mixer["conv_bias"].shape == (5120,)
        shapes = lm.cache_shapes(share, 2560)
        assert shapes["ssm_state"] == [(32, 128, 256)] * 9
        assert shapes["ssm_conv"] == [(3, 5120)] * 9
        assert shapes["k"] == [(2560, 4, 128)] * 9


# -- (e) the engine's path ----------------------------------------------------

class TestEnginePath(contract.ForkedEnginePath):
    test_a_batch_prefills_once_forks_and_decodes_four_a_step = \
        contract.ForkedEnginePath \
        .a_batch_prefills_once_forks_and_decodes_four_a_step
    test_every_image_its_own_expansion_and_one_image_the_old_path = \
        contract.ForkedEnginePath \
        .every_image_its_own_expansion_and_one_image_the_old_path
    CASE = CASE
    #: how the model departs from one mixer a layer and no multiplier
    DEPARTURES = {"ssm_layers": 3, "joined_layers": 3, "multipliers": 14}

    def check_traced(self, sites, traced):
        assert sum(sites["by_shape"][
            f"T1 S{CAPACITY + 2 * STEPS} D8"].values()) == LAYERS
        # two prefill executables and one forked decode chunk were traced
        forms = {"recurrent": 0, "chunked": 2 * LAYERS,
                 "recurrent_forked": LAYERS}
        assert traced["ssm_mixers"] == forms
        assert traced["joined_layers"] == forms
        assert traced["multipliers_applied"] == 14
        assert traced["delta_mixers"] == dict.fromkeys(forms, 0)
        assert traced["sublayer_norms"]["pre"] == {
            form: 2 * n for form, n in forms.items()}
        assert traced["write_strength_bound"] == 0.0

    def check_counted(self, stats, sizes, one):
        assert stats["tokens_no_held_expert"] == 0 == stats["experts_read"]
        assert stats["cache_positions"] == {
            "full": LAYERS * (36 + 4 * 40), "sliding": 0, "ssm": 0}
        state = LAYERS * STATE
        assert one["ssm"] == state and sizes["ssm"] == 4 * state
        assert one["full"] == LAYERS * CAPACITY * ROW * 4
        assert stats["fork_bytes_copied"] == 4 * state
        # a step reads and writes each sequence's states once
        assert stats["state_bytes_stepped"] == 2 * STEPS * 2 * 4 * state
        # nothing was traced again: the counters of the sites stay 0
        assert stats["ssm_mixers"]["recurrent_forked"] == 0
        assert stats["multipliers_applied"] == 0

    def check_spans(self, by_name, sizes, one):
        state = LAYERS * STATE
        (prefill,) = by_name["expand.prefill"]
        assert prefill["sequences"] == 4    # whose first tokens it draws
        assert prefill["form"] == "chunked" and prefill["padded"] == 59
        assert prefill["ssm_state_bytes"] == 2 * state
        (fork,) = by_name["expand.fork"]
        assert fork["ssm"] == "recurrent_forked" and "delta" not in fork
        assert fork["state_bytes_copied"] == 4 * state
        # three layers' own rows of 64 slots a sequence and four copies of
        # every state, float32
        assert fork["bytes"] == LAYERS * 4 * 2 * STEPS * ROW * 4 + 4 * state
        chunks = by_name["expand.decode_chunk"]
        assert [a["ssm"] for a in chunks] == ["recurrent_forked"] * 2
        assert [a["ssm_state_bytes"] for a in chunks] \
            == [STEPS * 2 * 4 * state] * 2
        for attrs in [prefill, fork] + chunks:
            assert {k: attrs[k] for k in self.DEPARTURES} == self.DEPARTURES
        hits = [a for a in by_name["expand.prefix_copy"] if a.get("hit")]
        assert hits and hits[0]["bytes"] == sum(one.values())

    def check_one_image(self, sites, stats):
        assert stats["ssm_mixers"]["recurrent"] == LAYERS
        assert stats["joined_layers"]["recurrent"] == LAYERS
        assert stats["fork_bytes_copied"] == 2 * LAYERS * STATE

    def test_the_prometheus_families_and_the_status_keys(self, engine):
        ATTENTION.clear()
        EXPANDER.clear()
        contract.sites_of(
            CFG, CASE.params(), jnp.zeros((4,), jnp.int32), 40, 4,
            contract.forked_structs(CFG, 64, 4, 32, jnp.float32),
            sequences=True)
        summary = METRICS.summary()["expander"]
        assert {"ssm_mixers", "joined_layers", "multipliers_applied",
                "state_bytes_stepped", "fork_bytes_copied"} <= set(summary)
        assert summary["ssm_mixers"]["recurrent_forked"] == 3
        text = prometheus.render()
        assert 'sdtpu_expander_ssm_mixers_total{form="recurrent_forked"} 3' \
            in text
        assert 'sdtpu_expander_joined_layers_total{' \
            'form="recurrent_forked"} 3' in text
        assert 'sdtpu_expander_ssm_mixers_total{form="chunked"} 0' in text
        assert "sdtpu_expander_multipliers_applied 14" in text
        # a sibling applies none and joins nothing
        EXPANDER.clear()
        contract.param_shapes(configs.TINY_DELTA_EXPAND.expander)
        summary = EXPANDER.summary()
        assert summary["multipliers_applied"] == 0
        assert summary["ssm_mixers"] == summary["joined_layers"] == {
            "recurrent": 0, "chunked": 0, "recurrent_forked": 0}
        assert "sdtpu_expander_multipliers_applied 0" in prometheus.render()
        ATTENTION.clear()
        EXPANDER.clear()

    def test_a_warm_start_counts_the_sites_again(self):
        """What a trace counted is replayed when its program is loaded
        (serving/aot.py): the rows a capture holds count once more."""
        from stable_diffusion_webui_distributed_tpu.serving import metrics

        EXPANDER.clear()
        with metrics.capture_sites() as rows:
            EXPANDER.record_ssm("recurrent_forked")
            EXPANDER.record_joined("chunked")
            EXPANDER.record_multipliers(13)
        EXPANDER.clear()
        metrics.replay_sites(rows)
        summary = EXPANDER.summary()
        assert summary["ssm_mixers"]["recurrent_forked"] == 1
        assert summary["joined_layers"]["chunked"] == 1
        assert summary["multipliers_applied"] == 13
        EXPANDER.clear()


# -- (f) the published model and its share, from shapes -----------------------

class TestThePublishedShare:
    def test_parameters_and_bytes_from_shapes(self):
        share = configs.sd15_falcon_h1_expander().expander
        whole = configs.FALCON_H1_34B
        assert whole.num_layers == 72 and share.num_layers == 9
        assert set(whole.layer_types) == {"full+ssm"}
        assert share.dense_layers == tuple(range(9))
        assert share.vocab == (0, 65280) and whole.vocab == (0, 261120)
        assert not share.expert_layers
        assert (share.hidden_size, share.intermediate_size, share.head_dim,
                share.num_kv_heads, set(share.num_heads_per_layer)) \
            == (5120, 21504, 128, 4, {20})
        assert share.rope_full.theta == 1e11
        assert share.rope_full.partial_rotary_factor == 1.0
        assert not share.rope_full.interleaved and not share.rope_full.factor
        assert (share.ssm_num_heads, share.ssm_head_dim,
                share.ssm_state_size, share.ssm_num_groups,
                share.ssm_conv_kernel, share.ssm_conv_bias, share.ssm_chunk,
                share.ssm_inner, share.ssm_conv_channels) \
            == (32, 128, 256, 2, 4, True, 128, 4096, 5120)
        shapes = contract.param_shapes(share)
        layer = shapes["layers_0"]
        mixer = layer["ssm"]
        assert mixer["in_proj"]["kernel"].shape == (5120, 9248)
        assert 9248 == 4096 + 4096 + 2 * 256 + 2 * 256 + 32
        assert count(mixer["in_proj"]) == 47_349_760
        assert count(mixer["out_proj"]) == 20_971_520
        assert count(mixer["conv_kernel"]) == 20_480
        assert count(mixer["conv_bias"]) == 5_120
        assert sum(count(mixer[n]) for n in ("A_log", "D", "dt_bias")) == 96
        assert count(mixer["norm"]) == 4096
        assert count(mixer) - count(mixer["norm"]) == 68_346_976
        assert count(layer["attn"]) == 31_457_280
        assert count(layer["mlp"]) == 330_301_440
        norms = 5120 + 5120 + 4096
        assert count(layer) - norms == 430_105_696
        assert round(430_105_696 * 2 / 1e6, 1) == 860.2
        assert round(count(layer["mlp"]) / (count(layer) - norms), 2) == 0.77
        assert count(shapes["embed_tokens"]) == count(shapes["lm_head"]) \
            == 5120 * 65280 == 334_233_600
        total = count(shapes)
        assert total - 9 * norms - 5120 == 4_539_418_464
        assert round((total - 9 * norms - 5120) / 1e6, 1) == 4539.4
        assert round(total * 2 / 1e9, 2) == 9.08
        # beside SD1.5's 1 066 M: 11.21 GB = 10.44 GiB
        assert round((total + 1066e6) * 2 / 1e9, 2) == 11.21
        assert round((total + 1066e6) * 2 / 2 ** 30, 2) == 10.44
        # the whole model from the same shapes: 33.64 B, 67.3 GB
        published = 72 * count(layer) + 2 * 5120 * 261120 + 5120
        assert round(published / 1e9, 2) == 33.64
        assert round(published * 2 / 1e9, 1) == 67.3
        # the fallback's eight layers
        assert round((total - count(layer)) / 1e6) == 4109
        assert round((total - count(layer) + 1066e6) * 2 / 2 ** 30, 2) \
            == 9.64
        # the caches of four forked sequences at the cell's capacity
        capacity = kv.capacity_for(2048 + 64 + 8 * STEPS)
        assert capacity == 2560
        state = (32 * 128 * 256 + 3 * 5120) * 4
        assert 32 * 128 * 256 * 4 == 4_194_304 and state == 4_255_744
        position = 2 * 4 * 128 * 2
        assert position == 2048
        assert kv.state_bytes(share, capacity, jnp.bfloat16, 4, 8 * STEPS) \
            == {"full": 9 * (2560 + 4 * 256) * position, "sliding": 0,
                "ssm": 4 * 9 * state}
        assert round(9 * 2560 * position / 1e6) == 47
        assert round(9 * 4 * 256 * position / 1e6) == 19
        assert round(4 * 9 * state / 1e6) == 153
        assert kv.copied_bytes(share, jnp.bfloat16, 4) == 153_206_784
        assert 2 * 4 * 9 * state == 306_413_568
        assert round(153_206_784 / 2 ** 20, 1) == 146.1
        assert round(306_413_568 / 2 ** 20, 1) == 292.2

    def test_a_forked_step_of_the_share_traced_as_on_the_chip(self,
                                                              monkeypatch):
        """One decode step of the share the cell runs, traced without
        weights or FLOPs (nothing compiles; tests/test_chip_compile.py
        compiles it for a described v5e): nine state-space mixers a
        recurrence a sequence beside nine attention sites over 2 560
        shared and 256 own rows of 128, thirteen multipliers applied."""
        share = configs.sd15_falcon_h1_expander().expander
        cache = contract.forked_structs(share, 2560, 4, 256)
        assert [x.shape for x in cache["k_shared"]] == [(2560, 4, 128)] * 9
        assert [x.shape for x in cache["k"]] == [(4, 256, 4, 128)] * 9
        assert [(x.shape, x.dtype) for x in cache["ssm_state"]] \
            == [((4, 32, 128, 256), jnp.float32)] * 9
        assert [x.shape for x in cache["ssm_conv"]] == [(4, 3, 5120)] * 9
        shapes = contract.param_shapes(share)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ATTENTION.clear()
        EXPANDER.clear()
        logits, after, routed = contract.sites_of(
            share, shapes, jnp.zeros((4,), jnp.int32), 2200, 4,
            cache, jnp.bfloat16, sequences=True)
        assert logits.shape == (4, 65280)
        assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), after) \
            == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), cache)
        assert routed[0].shape == (0, 1, 1)     # no expert layer
        stats = EXPANDER.summary()
        assert stats["ssm_mixers"] == {"recurrent": 0, "chunked": 0,
                                       "recurrent_forked": 9}
        assert stats["joined_layers"]["recurrent_forked"] == 9
        assert stats["multipliers_applied"] == 13
        assert stats["delta_mixers"]["recurrent_forked"] == 0
        assert stats["sublayer_norms"]["pre"]["recurrent_forked"] == 18
        assert stats["expert_products"] == {"kernel": 0, "loop": 0,
                                            "grouped": 0}
        (shape, paths), = ATTENTION.summary()["by_shape"].items()
        assert shape == "T1 S2816 D128" and sum(paths.values()) == 9
        ATTENTION.clear()
        EXPANDER.clear()

"""The resident prompt expander whose block is a hybrid: gated delta-rule
layers three in four beside gated latent attention, norms ``x_hat * 2
sigmoid(w)`` before and after every sublayer, clamped SwiGLUs, a held share
of the experts behind a biased sigmoid router; at the shape that makes such
a model work: the images of one request decoded as sequences of ONE step,
forked from one prefill, every latent shared and every recurrent state and
kept row copied once a sequence.

Everything runs the tiny preset (models/configs.py ``TINY_GIGACHAT35_LM``
cut as the published share is cut: a dense linear layer, then latent,
linear, linear, linear over 4 of 16 experts and a quarter of the
vocabulary). The plain reference is the benchmark's own
(benchmarks/reference/gigachat35_ref.py: float32, one sequence, no cache,
the delta rule token by token, the expanded attention only).
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
from stable_diffusion_webui_distributed_tpu.ops import (
    delta_kernel, delta_rule, moe, moe_kernel,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER, METRICS,
)
from tests import expander_contract as contract
from tests.expander_contract import CAPACITY, STEPS, count, rel_rms, run

REF = contract.load_reference("gigachat35")
#: every norm's weight off 0 (deviation 0.5, as the benchmark seeds them),
#: the decay rates spread from a token to hundreds, ``dt_bias`` off 1 and
#: the selection bias off 0 (deviation 0.1)
CASE = contract.Case(
    configs.TINY_GIGACHAT35_EXPAND, REF,
    how=(("spread", (("weight", 0.5), ("scale", 0.5),
                     ("e_score_correction_bias", 0.1), ("dt_bias", 0.3))),
         ("a_log", (-3.0, -1.0, 0.5, 2.0))),
    tolerance=1e-4, staged_tolerance=1e-5, rows_tolerance=3e-4,
    step_tolerance=3e-4,
    controls=("control", "state_bf16", "state_shared", "kept_shared",
              "silu_gate", "plain_norm", "no_post_norm", "no_attn_gate",
              "no_mscale", "no_selection_bias"))
FAMILY, CFG = CASE.family, CASE.cfg
#: one sequence's state and kept rows in one linear layer, float32
STATE = (4 * 8 * 8 + 3 * 64) * 4
params, engine = contract.fixtures(CASE)


def clamp_binds(params):
    """Every SwiGLU's gate and up kernels times eight, so that the clamp
    at 10 binds (at variance 1/fan_in it is inert)."""
    def scaled(path, x):
        names = {getattr(k, "key", "") for k in path}
        return 8.0 * x if names & {"gate_proj", "up_proj", "w_gate",
                                   "w_up"} else x

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(scaled, p))(
        params)


# -- (a) program against reference --------------------------------------------

class TestAgainstTheReference(contract.ForkedAgainstTheReference,
                              contract.StagedAsTheTimedPathRunsIt):
    """Expanded latent form and chunk-wise delta rule, a copy, a fork into
    four and the forked latent form with a recurrent step a sequence:
    logits to 1e-4, routing identical. Int8 linears, the state in
    bfloat16, one state or one set of kept rows shared by the sequences,
    ``silu(z)`` for ``2 sigmoid(z)``, ``1 + w`` for ``2 sigmoid(w)``, the
    post-sublayer norms, the attention gate, ``m^2`` or the selection bias
    left out: each misses ten times over the tolerance the program meets
    ten times over; the state in bfloat16 reads the nearest, 4e-3."""
    CASE = CASE
    test_prefill_fork_and_decode_match_four_full_forwards = \
        contract.ForkedAgainstTheReference.program_matches_four_full_forwards
    PARAMETERS = {
        "test_prefill_fork_and_decode_match_four_full_forwards": [
            ("size", [37, 148])],
        "test_each_control_is_further_from_the_reference": [
            ("control", [name for name, _ in REF.CONTROLS])]}

    def test_the_clamp_where_it_binds(self, params):
        """At variance 1/fan_in a SwiGLU's products have deviation about 1
        and the clamp at 10 is inert (the chip's readings cannot see it
        left out): here every gate and up kernel is scaled by eight, the
        program with the clamp meets the reference and the program
        without it does not."""
        scaled = clamp_binds(params)
        ids, continuations = REF.inputs(FAMILY, 3, 37)
        want = jax.jit(lambda p, i, c: REF.forward(FAMILY, p, i, c))(
            scaled, ids, continuations)
        got = CASE.program()(scaled, ids, continuations)
        assert rel_rms(got, want) < 1e-4
        without = CASE.program(no_clamp=True)(scaled, ids, continuations)
        assert rel_rms(without, want) > 1e-2

    def test_the_selection_bias_changes_most_choices(self, params):
        assert REF.bias_changes_share(CFG, params) > 0.3

    def test_the_norms_the_gates_and_the_clamp(self):
        x = jax.random.normal(jax.random.key(0), (5, 16)) * 3.0
        w = jax.random.normal(jax.random.key(1), (16,))
        x_hat = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        gated = lm.RMSNorm(1e-6, False, 2.0)
        np.testing.assert_allclose(
            gated.apply({"params": {"weight": w}}, x),
            x_hat * 2 * jax.nn.sigmoid(w), rtol=1e-6, atol=1e-6)
        # a gate that is 1 where the weight is 0, and its own leaf name
        zero = gated.init(jax.random.key(0), x)["params"]
        assert set(zero) == {"weight"} and not np.any(zero["weight"])
        np.testing.assert_allclose(gated.apply({"params": zero}, x), x_hat,
                                   rtol=1e-6, atol=1e-6)
        assert set(lm.RMSNorm().init(jax.random.key(0), x)["params"]) \
            == {"scale"}
        # swiglu(g, u) = silu(min(g, L)) * clip(u, -L, L)
        kernels = {name: {"kernel": jnp.eye(16) * s} for name, s in (
            ("gate_proj", 9.0), ("up_proj", -7.0), ("down_proj", 1.0))}
        got = lm.SwiGLU(16, limit=10.0).apply({"params": kernels}, x)
        np.testing.assert_allclose(
            got, jax.nn.silu(jnp.minimum(9 * x, 10.0))
            * jnp.clip(-7 * x, -10.0, 10.0), rtol=1e-5, atol=1e-5)
        assert rel_rms(lm.SwiGLU(16).apply({"params": kernels}, x), got) \
            > 0.1
        # the routed experts' product clamps alike, in every form
        d, f, e = 16, 8, 4
        ks = jax.random.split(jax.random.key(2), 4)
        wg, wu = (4.0 * jax.random.normal(k, (e, d, f)) for k in ks[:2])
        wd = jax.random.normal(ks[2], (e, f, d))
        for rows in (1, 4, 24):
            n = jax.random.normal(ks[3], (rows, d)) * 2.0
            routing = moe.Routing(
                jnp.tile(jnp.array([[3, 0]], jnp.int32), (rows, 1)),
                jnp.tile(jnp.array([[0.6, 0.4]], jnp.float32), (rows, 1)))
            want = sum(
                wt * (jax.nn.silu(jnp.minimum(n @ wg[i], 10.0))
                      * jnp.clip(n @ wu[i], -10.0, 10.0)) @ wd[i]
                for i, wt in ((3, 0.6), (0, 0.4)))
            got, _ = moe.routed_experts(n, routing, wg, wu, wd, first=0,
                                        num_experts=e, limit=10.0)
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
            loose, _ = moe.routed_experts(n, routing, wg, wu, wd, first=0,
                                          num_experts=e)
            assert rel_rms(loose, want) > 0.05

    def test_the_kernels_tile_clamps_as_the_loop_does(self):
        """ops/moe_kernel.py in interpret mode at widths on the lane
        tiling: one row and a block of four, with the limit and
        without."""
        d, f, e = 128, 256, 4
        ks = jax.random.split(jax.random.key(5), 4)
        wg, wu = (0.6 * jax.random.normal(k, (e, d, f)) for k in ks[:2])
        wd = jax.random.normal(ks[2], (e, f, d)) / f ** 0.5
        for rows in (1, 4):
            x = jax.random.normal(ks[3], (rows, d))
            weights = jnp.full((2,) if rows == 1 else (2, rows), 0.5)
            experts = jnp.array([2, 1], jnp.int32)
            got = moe_kernel.chosen_experts(
                x, experts, weights, jnp.int32(2), wg, wu, wd,
                interpret=True, limit=10.0)
            want = sum(0.5 * moe._swiglu(x, wg[i], wu[i], wd[i], 10.0)
                       for i in (2, 1))
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)
            free = moe_kernel.chosen_experts(
                x, experts, weights, jnp.int32(2), wg, wu, wd,
                interpret=True)
            assert rel_rms(free, want) > 0.05


# -- (b) a step over B sequences ----------------------------------------------

class TestSequencesOfOneStep(contract.SequencesOfOneStep,
                             contract.StatesOfOneStep,
                             contract.WhichKindsShareAStep):
    """To 3e-4: thirty-two steps of a float32 recurrence fused two ways. A
    recurrent state with a sequence axis shares a step; a conv layer's
    kept rows and several streams still decode one sequence a step."""
    CASE = CASE
    SHARE = ("sd15_gigachat35_expander", "sd15_qwen3next_expander")
    ONE_A_STEP = ("sd15_lfm2_expander", "sd15_xing4_expander")
    test_a_snapshot_restores_latents_and_states = \
        contract.StatesOfOneStep.a_snapshot_restores_every_buffer
    PARAMETERS = {
        "test_a_forked_decode_is_each_sequence_alone": [
            ("user,live,batch", [(1, 4, 4), (64, 3, 4)])],
        "test_which_kinds_share_a_step": [("preset,shares", [
            ("TINY_GIGACHAT35_EXPAND", True), ("TINY_DELTA_EXPAND", True),
            ("TINY_KANANA_EXPAND", True), ("TINY_LATENT_EXPAND", False),
            ("TINY_CONV_EXPAND", False)])]}

    test_a_fork_shares_every_latent_and_copies_every_state = contract.SequencesOfOneStep \
        .a_fork_shares_what_has_positions_and_copies_the_rest

    def check_fork(self, forked):
        assert lm.buffers_of(lm.LINEAR, forked=True) == ("state", "conv")
        assert lm.slots_axis("state") is None and lm.slots_axis("conv") is None
        assert lm.slots_axis("latent") == -2 and lm.slots_axis("k") == -3

    def test_bytes_and_positions_of_a_forked_cache_of_both_kinds(self):
        manager = kv.KVCacheManager(CFG, jnp.bfloat16)
        assert manager.positions_in_use(40) == {
            "full": 0, "sliding": 0, "linear": 0, "latent": 40}
        assert manager.positions_in_use(40, 4, 30) == {
            "full": 0, "sliding": 0, "linear": 0, "latent": 30 + 4 * 10}
        row = 24 * 2                            # a slot's latent, bfloat16
        assert kv.state_bytes(CFG, 256, jnp.bfloat16) == {
            "full": 0, "sliding": 0, "linear": 4 * STATE,
            "latent": 256 * row}
        # a forked group: the latents once and 64 slots a sequence, the
        # states once a sequence
        assert kv.state_bytes(CFG, 256, jnp.bfloat16, 4, 64) == {
            "full": 0, "sliding": 0, "linear": 4 * 4 * STATE,
            "latent": (256 + 4 * 64) * row}
        assert kv.copied_bytes(CFG, jnp.bfloat16, 4) == 4 * 4 * STATE
        assert kv.copied_bytes(CFG, jnp.bfloat16, 1) == 0
        assert kv.copied_bytes(configs.TINY_KANANA_EXPAND.expander,
                               jnp.bfloat16, 4) == 0
        # the delta-rule sibling's bytes are what they were
        other = configs.TINY_DELTA_EXPAND.expander
        assert kv.state_bytes(other, 256, jnp.float32)["linear"] \
            == 2 * STATE

    def test_the_forms_by_rows_and_whose_they_are(self):
        assert delta_rule.form(64) == "chunked"
        assert delta_rule.form(1) == "recurrent"
        assert delta_rule.form(4, sequences=True) == "recurrent_forked"
        assert delta_rule.form(1, sequences=True) == "recurrent_forked"
        # the step of each is the step of one
        ks = jax.random.split(jax.random.key(0), 6)
        state = jax.random.normal(ks[0], (3, 4, 8, 8))
        q, k = (jax.random.normal(key, (3, 4, 8)) for key in ks[1:3])
        v = jax.random.normal(ks[3], (3, 4, 8))
        g = -jax.nn.softplus(jax.random.normal(ks[4], (3, 4)))
        beta = jax.nn.sigmoid(jax.random.normal(ks[5], (3, 4)))
        out, after = delta_rule.recurrent_step_each(state, q, k, v, g, beta)
        for b in range(3):
            o, s = delta_rule.recurrent_step(state[b], q[b], k[b], v[b],
                                             g[b], beta[b])
            np.testing.assert_allclose(out[b], o, rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(after[b], s, rtol=1e-6, atol=1e-6)
        # a one-row convolution a sequence; a row that does not count
        # keeps the rows it had
        kernel = jax.random.normal(ks[0], (4, 6))
        kept = jax.random.normal(ks[1], (3, 3, 6))
        x = jax.random.normal(ks[2], (3, 6))
        real = jnp.array([True, False, True])
        out, after = lm.causal_conv_rows(kernel, kept, x, real)
        for b in range(3):
            o, a = lm.causal_conv(kernel, kept[b], x[b:b + 1], 1)
            np.testing.assert_allclose(out[b], o[0], rtol=1e-6, atol=1e-6)
            assert np.array_equal(after[b], a if real[b] else kept[b])


# -- (b') the forked step as one kernel ----------------------------------------

@pytest.mark.parametrize("strength", [1.0, 2.0])
@pytest.mark.parametrize("shape", [(4, 8, 128, 128), (2, 16, 128, 128),
                                   (4, 8, 128, 256)])
def test_the_kernel_steps_as_the_elementwise_form(shape, strength):
    """ops/delta_kernel.py in interpret mode against
    ``recurrent_step_each`` at unit keys, at write strengths under 1 and
    under 2: the sums over ``K`` run in another order and nothing else
    differs; a masked sequence's state comes back bit for bit."""
    b, h, k_dim, v_dim = shape
    ks = jax.random.split(jax.random.key(b * h + v_dim), 6)

    def unit(x):
        return x * jax.lax.rsqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    state = jax.random.normal(ks[0], shape)
    q = unit(jax.random.normal(ks[1], (b, h, k_dim))) * k_dim ** -0.5
    k = unit(jax.random.normal(ks[2], (b, h, k_dim)))
    v = jax.random.normal(ks[3], (b, h, v_dim))
    masked = jnp.arange(b)[:, None] == 1
    g = jnp.where(masked, 0.0,
                  -jax.nn.softplus(jax.random.normal(ks[4], (b, h))))
    beta = jnp.where(masked, 0.0, strength * jax.nn.sigmoid(
        jax.random.normal(ks[5], (b, h))))
    want_out, want = delta_rule.recurrent_step_each(state, q, k, v, g, beta)
    out, after = delta_kernel.recurrent_step_each(state, q, k, v, g, beta,
                                                  interpret=True)
    assert after.dtype == jnp.float32 and out.shape == (b, h, v_dim)
    np.testing.assert_allclose(after, want, rtol=0, atol=5e-6)
    np.testing.assert_allclose(out, want_out, rtol=0, atol=5e-6)
    assert np.array_equal(after[1], state[1])
    assert not np.array_equal(after[0], state[0])


@pytest.mark.parametrize("platform,meshed,dtype,shape,form", [
    ("tpu", False, jnp.float32, (4, 64, 128, 128), "kernel"),   # published
    ("tpu", False, jnp.float32, (2, 64, 128, 128), "kernel"),
    ("tpu", False, jnp.float32, (8, 16, 64, 256), "kernel"),
    ("cpu", False, jnp.float32, (4, 64, 128, 128), "elementwise"),
    ("tpu", True, jnp.float32, (4, 64, 128, 128), "elementwise"),
    # one sequence: nothing to batch, ``recurrent_step``'s own business
    ("tpu", False, jnp.float32, (1, 64, 128, 128), "elementwise"),
    # Olmo-Hybrid's: V off the lanes (and 30 heads off the sublanes)
    ("tpu", False, jnp.float32, (4, 30, 96, 192), "elementwise"),
    ("tpu", False, jnp.float32, (4, 32, 96, 192), "elementwise"),
    ("tpu", False, jnp.float32, (4, 30, 96, 256), "elementwise"),
    ("tpu", False, jnp.float32, (4, 32, 100, 128), "elementwise"),
    # the lower-precision control's state
    ("tpu", False, jnp.bfloat16, (4, 64, 128, 128), "elementwise"),
    # the tiny presets' on the chip
    ("tpu", False, jnp.float32, (4, 4, 8, 8), "elementwise"),
])
def test_the_rule_that_picks_the_forked_step(platform, meshed, dtype, shape,
                                             form):
    assert delta_rule.step_form(platform, dtype, shape,
                                meshed=meshed) == form
    assert set(delta_rule.FORKED_STEPS) == {"kernel", "elementwise"}


def test_a_grid_steps_heads_fit_its_share_of_vmem():
    # the published 64 heads of 128 x 128: as many as fit twice in and out
    block = delta_kernel.head_block(64, 128, 128)
    assert 64 % block == 0 and block % 8 == 0
    assert 4 * block * 128 * 128 * 4 <= delta_kernel._STATE_VMEM
    assert delta_kernel.head_block(8, 128, 128) == 8
    assert delta_kernel.head_block(8, 4096, 1024) is None   # none fits
    with pytest.raises(ValueError, match="does not tile"):
        delta_kernel.recurrent_step_each(
            jnp.zeros((2, 30, 96, 192)), *(jnp.zeros((2, 30, w))
                                           for w in (96, 96, 192)),
            jnp.zeros((2, 30)), jnp.zeros((2, 30)), interpret=True)


def test_a_warm_start_counts_the_steps_again():
    """What a trace counted is replayed when its program is loaded
    (serving/aot.py): the benchmark's ``delta_kernel_sites`` reads the
    same after a warm start."""
    from stable_diffusion_webui_distributed_tpu.serving import metrics

    EXPANDER.clear()
    with metrics.capture_sites() as rows:
        for _ in range(4):
            EXPANDER.record_delta_step("kernel")
        EXPANDER.record_delta_step("elementwise")
    assert rows == [["delta_step", "kernel"]] * 4 \
        + [["delta_step", "elementwise"]]
    EXPANDER.clear()
    metrics.replay_sites(rows)
    assert EXPANDER.summary()["delta_steps"] == {"kernel": 4,
                                                 "elementwise": 1}
    EXPANDER.clear()


# -- (c) the delta-rule configuration through the shared step ------------------

SHARED, OWN = 21, 12


def _forked_program(cfg):
    """A prefill of ``SHARED`` tokens, a fork into as many sequences as
    there are continuations and a step for all a position, teacher-forced:
    (sequences, ``OWN``, vocabulary) logits."""
    module = lm.DecoderLM(cfg)

    def program(params, ids, continuations):
        batch = continuations.shape[0]
        _, cache, _ = module.apply(
            {"params": params}, ids, jnp.int32(0), jnp.int32(SHARED),
            lm.empty_cache(cfg, 64, jnp.float32))
        cache = kv.fork(cache, batch, OWN)

        def step(carry, tokens):
            cache, position = carry
            logits, cache, _ = module.apply(
                {"params": params}, tokens, position, jnp.int32(batch),
                cache, sequences=True)
            return (cache, position + 1), logits

        _, logits = jax.lax.scan(step, (cache, jnp.int32(SHARED)),
                                 continuations.T)
        return jnp.moveaxis(logits, 1, 0)

    return jax.jit(program)


@pytest.mark.parametrize("batch", [2, 4])
def test_the_delta_rule_preset_shares_a_step_against_its_reference(batch):
    """``sd15_qwen3next_expand``'s tiny preset (linear, linear, sliding,
    full: rings and a buffer beside two recurrent states) prefilled once,
    forked into ``batch`` and decoded a step for all, teacher-forced on
    continuations that differ, against its OWN plain reference's full
    forward of each whole sequence."""
    from tests.test_delta_expander import CASE as DELTA

    first, count = DELTA.cfg.vocab
    ids = jax.random.randint(jax.random.key(4), (SHARED,), first,
                             first + count)
    continuations = jax.random.randint(jax.random.key(5), (batch, OWN),
                                       first, first + count)
    got = _forked_program(DELTA.cfg)(DELTA.params(), ids, continuations)
    for b in range(batch):
        _, want, _ = DELTA.referred_on(
            jnp.concatenate([ids, continuations[b]]))
        assert rel_rms(got[b], want[SHARED:]) < 1e-4, b
    assert rel_rms(got[1], got[0]) > 0.1


# -- (d) the tree, the shares and the rules -----------------------------------

def _dense_layer(cfg, n, p):
    """An expert layer's routed part over ALL its experts, and its shared
    expert, in plain float32."""
    chosen, weights = REF.route(cfg, n, p)
    whole = dataclasses.replace(cfg, experts_held=None)
    return (REF.routed_part(whole, n, chosen, weights, p["experts"]),
            REF._swiglu(cfg, n, p["shared_expert"]))


class TestTheTreeAndItsRules(contract.ShardingRules):
    def test_the_leaves_of_each_kind(self, params):
        assert CFG.layer_types == ("linear", "latent", "linear", "linear",
                                   "linear") and CFG.dense_layers == (0,)
        norms = {"input_norm", "input_norm_2", "post_attention_norm",
                 "post_attention_norm_2"}
        assert set(params["layers_0"]) == {"delta", "mlp"} | norms
        assert set(params["layers_1"]) == {"attn", "mlp"} | norms
        for name in norms | {"delta"}:
            leaf = params["layers_0"][name]
            assert set(leaf.get("norm", leaf)) == {"weight"}
        assert set(params["norm"]) == {"weight"}
        attn = params["layers_1"]["attn"]
        assert set(attn) == {"q_a_proj", "q_a_norm", "q_b_proj",
                             "kv_a_proj_with_mqa", "kv_a_norm", "kv_b_proj",
                             "g_proj", "o_proj"}
        assert attn["g_proj"]["kernel"].shape == (32, 4 * 8)
        assert set(attn["q_a_norm"]) == set(attn["kv_a_norm"]) == {"weight"}
        delta = params["layers_2"]["delta"]
        assert set(delta) == {"qkvz_proj", "ba_proj", "conv_kernel", "A_log",
                              "dt_bias", "norm", "out_proj"}
        assert delta["qkvz_proj"]["kernel"].shape == (32, 16 + 16 + 32 + 32)
        assert set(params["layers_0"]["mlp"]) == {"gate_proj", "up_proj",
                                                  "down_proj"}
        mlp = params["layers_1"]["mlp"]
        assert set(mlp) == {"router", "e_score_correction_bias", "experts",
                            "shared_expert"}
        assert mlp["router"].shape == (32, 16)          # every expert
        assert mlp["experts"]["w_gate"].shape == (4, 32, 16)    # its share
        assert params["lm_head"]["kernel"].shape == (32, 128)
        # the siblings keep the gate they had: none on a latent layer
        for name in ("TINY_LATENT_EXPAND", "TINY_KANANA_EXPAND"):
            other = getattr(configs, name).expander
            assert other.attn_gate == "none"
            shapes = contract.param_shapes(other)
            assert "g_proj" not in shapes["layers_0"]["attn"]

    def test_the_share_holds_the_published_layers_it_names(self):
        whole = configs.TINY_GIGACHAT35_LM
        assert whole.layer_types[3::4] == ("latent",) * 2
        assert whole.dense_layers == (0, 1, 2)
        again = configs.lm_share(whole, (0, 3, 4, 5, 6), chips=4, rank=1,
                                 vocab_chips=2)
        assert again.layer_types == CFG.layer_types
        assert again.dense_layers == (0,)
        assert again.experts == (4, 4) and again.vocab == (256, 256)
        # a count of layers is what it always was
        assert configs.lm_share(whole, 5, chips=4, rank=0).dense_layers \
            == (0, 1, 2)

    def test_the_shares_add_up_to_the_uncut_layer_and_head(self):
        """The guide's share test: the four shares' routed parts (sixteen
        at the published 16 of 256) with the shared expert counted once
        add up to the uncut layer's, through the program's own expert
        layer; and the slices' logits side by side are the uncut head's."""
        whole = configs.lm_share(configs.TINY_GIGACHAT35_LM,
                                 (0, 3, 4, 5, 6), chips=1, rank=0)
        uncut = CASE.params(3, whole)
        n = jax.random.normal(jax.random.key(9), (6, 32))
        p = uncut["layers_1"]["mlp"]
        routed, shared = _dense_layer(whole, n, p)
        chips = 4
        held = whole.num_experts // chips
        parts = []
        for rank in range(chips):
            share = configs.lm_share(configs.TINY_GIGACHAT35_LM,
                                     (0, 3, 4, 5, 6), chips=chips,
                                     rank=rank)
            lo = rank * held
            mine = {**p, "experts": {
                name: w[lo:lo + held] for name, w in p["experts"].items()}}
            out, _ = jax.jit(lambda p, m=lm.MoE(share): m.apply(
                {"params": p}, n, jnp.ones((6,), bool)))(mine)
            parts.append(out - shared)
        assert rel_rms(parts[0], routed) > 0.1
        np.testing.assert_allclose(sum(parts), routed, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(sum(parts) + shared, routed + shared,
                                   rtol=2e-4, atol=2e-5)
        # the head: every slice of the vocabulary from the one final norm
        x = jax.random.normal(jax.random.key(10), (6, 32))
        head = uncut["lm_head"]["kernel"]
        slices = [x @ head[:, lo:lo + 128] for lo in range(0, 512, 128)]
        np.testing.assert_allclose(jnp.concatenate(slices, -1), x @ head,
                                   rtol=1e-6, atol=1e-6)
        # a token whose id another slice holds gets nothing from the table
        inside, outside = (
            run(CFG, CASE.params(), jnp.array(ids, jnp.int32), 0, 2,
                contract.empty(CFG, 8))[0] for ids in ([5, 6], [5, 300]))
        assert np.allclose(inside[0], outside[0], atol=1e-6)
        assert not np.allclose(inside[1], outside[1], atol=1e-3)

    #: the new leaves: what makes and reads the latent or the recurrent
    #: state stays whole on every chip, a norm's ``weight`` is replicated
    #: (a latent layer's ``g_proj`` splits by its columns as ``q_proj``)
    WHOLE = (("layers_1/attn/kv_a_proj_with_mqa/kernel", 2),
             ("layers_1/attn/kv_b_proj/kernel", 2),
             ("layers_1/attn/kv_a_norm/weight", 1),
             ("layers_1/input_norm_2/weight", 1),
             ("layers_0/delta/norm/weight", 1),
             ("layers_0/delta/A_log", 1),
             ("layers_0/delta/conv_kernel", 2),
             ("layers_1/mlp/e_score_correction_bias", 1))
    EXPERT_LAYER = 1
    PLACED_WHOLE = ("layers_1/attn/g_proj/kernel",
                    "layers_0/delta/qkvz_proj/kernel",
                    "layers_1/input_norm_2/weight")
    test_sharding_rules = contract.ShardingRules.sharding_rules


# -- (e) the engine's path ----------------------------------------------------

class TestEnginePath(contract.ForkedEnginePath):
    test_a_batch_prefills_once_forks_and_decodes_four_a_step = contract.ForkedEnginePath \
        .a_batch_prefills_once_forks_and_decodes_four_a_step
    test_every_image_its_own_expansion_and_one_image_the_old_path = contract.ForkedEnginePath \
        .every_image_its_own_expansion_and_one_image_the_old_path
    CASE = CASE

    def check_traced(self, sites, traced):
        assert sites["latent_forked"] == 1 and sites["latent_expanded"] == 2
        assert "latent_absorbed" not in sites
        assert sites["by_shape"][f"T4 S{CAPACITY}+{2 * STEPS} D24"] \
            == {"latent_forked": 1}
        assert traced["delta_mixers"] == {"recurrent": 0, "chunked": 8,
                                          "recurrent_forked": 4}
        # a CPU: every forked mixer steps its states element-wise
        assert traced["delta_steps"] == {"kernel": 0, "elementwise": 4}

    def check_counted(self, stats, sizes, one):
        # a quarter of the experts is held: some tokens find none
        assert 0 < stats["tokens_no_held_expert"] < 4 * (5 + 4 * 2 * STEPS)
        assert 0 < stats["experts_read"] <= 2 * STEPS * 4 * 4
        assert stats["expert_products"]["kernel"] == 0      # a CPU
        assert stats["cache_positions"] == {
            "full": 0, "sliding": 0, "linear": 0, "latent": 36 + 4 * 40}
        assert one["linear"] == 4 * STATE and sizes["linear"] == 16 * STATE
        assert stats["fork_bytes_copied"] == 16 * STATE
        # a step reads and writes each sequence's states once
        assert stats["state_bytes_stepped"] == 2 * STEPS * 2 * 16 * STATE
        text = prometheus.render()
        assert 'sdtpu_expander_delta_mixers_total{form="recurrent_forked"}' \
            in text
        # counted when traced (``check_traced``), rendered as they stand
        assert set(stats["delta_steps"]) == {"kernel", "elementwise"}
        for step, n in stats["delta_steps"].items():
            assert f'sdtpu_expander_delta_steps_total{{step="{step}"}} {n}' \
                in text
        assert "sdtpu_expander_state_bytes_stepped_total " \
            f"{stats['state_bytes_stepped']}" in text
        assert f"sdtpu_expander_fork_bytes_copied_total {16 * STATE}" in text

    def check_spans(self, by_name, sizes, one):
        (prefill,) = by_name["expand.prefill"]
        assert prefill["sequences"] == 4    # whose first tokens it draws
        assert prefill["latent"] == "latent_expanded"
        assert prefill["form"] == "chunked" and prefill["padded"] == 59
        (fork,) = by_name["expand.fork"]
        assert fork["latent"] == "latent_forked"
        assert fork["delta"] == "recurrent_forked"
        assert fork["state_bytes_copied"] == 16 * STATE
        # one latent layer's own rows of 64 slots a sequence and four
        # copies of every state, float32
        assert fork["bytes"] == 4 * 2 * STEPS * 24 * 4 + 16 * STATE
        assert [(a["latent"], a["delta"])
                for a in by_name["expand.decode_chunk"]] \
            == [("latent_forked", "recurrent_forked")] * 2
        hits = [a for a in by_name["expand.prefix_copy"] if a.get("hit")]
        assert hits and hits[0]["bytes"] == sum(one.values())

    def check_one_image(self, sites, stats):
        assert sites["latent_absorbed"] == 1
        assert stats["delta_mixers"]["recurrent"] == 4
        assert stats["fork_bytes_copied"] == 2 * 4 * STATE

    def test_the_status_keys(self, engine):
        summary = METRICS.summary()["expander"]
        assert {"delta_mixers", "delta_steps", "state_bytes_stepped",
                "fork_bytes_copied",
                "conv_mixers", "experts_read", "expert_products",
                "tokens_no_held_expert"} <= set(summary)


# -- (f) the published share, from shapes -------------------------------------

class TestThePublishedShare:
    def test_parameters_and_bytes_from_shapes(self):
        share = configs.sd15_gigachat35_expander().expander
        whole = configs.GIGACHAT_3_5
        assert whole.num_layers == 40 and whole.dense_layers == (0, 1, 2)
        assert whole.layers_of("latent") == tuple(range(3, 40, 4))
        assert len(whole.layers_of("linear")) == 30
        assert share.layer_types == ("linear", "latent", "linear", "linear",
                                     "linear")
        assert share.dense_layers == (0,)
        assert share.experts == (0, 16) and share.vocab == (0, 16032)
        assert (share.num_experts, share.num_experts_per_tok) == (256, 8)
        assert round(whole.latent_softmax_scale
                     / (192 ** -0.5 * (0.1 * np.log(8) + 1) ** 2), 9) == 1
        assert whole.rope_full.interleaved and whole.rope_full.factor == 8
        assert (whole.routed_scaling_factor, whole.norm_topk_eps,
                whole.swiglu_limit, whole.norm_sigmoid_scale,
                whole.linear_sigmoid_gate_scale) == (2.5, 1e-20, 10, 2, 2)
        assert moe_kernel.f_tile(7168, 2048, 2) == 128     # long rows
        shapes = contract.param_shapes(share)
        delta = count(shapes["layers_2"]["delta"])
        assert round(delta / 1e6, 1) == 235.9
        attn = shapes["layers_1"]["attn"]
        gate = count(attn["g_proj"])
        assert gate == 7168 * 64 * 128 and round(gate / 1e6, 1) == 58.7
        assert round((count(attn) - gate) / 1e6, 1) == 101.1
        mlp = shapes["layers_1"]["mlp"]
        assert count(mlp["experts"]) == 16 * 44_040_192
        assert round(16 * 44.040192, 1) == 704.6
        assert count(mlp["shared_expert"]) == 44_040_192
        assert mlp["router"].shape == (7168, 256)
        assert round(count(shapes["layers_0"]["mlp"]) / 1e6, 1) == 396.4
        assert count(shapes["embed_tokens"]) == count(shapes["lm_head"]) \
            == 7168 * 16032
        assert round(count(shapes["layers_0"]) / 1e6, 1) == 632.3
        assert round(count(shapes["layers_1"]) / 1e6, 1) == 910.4
        assert round(count(shapes["layers_2"]) / 1e6, 1) == 986.4
        total = count(shapes)
        assert round(total / 1e6) == 4732
        assert round(total * 2 / 1e9, 2) == 9.46
        # beside SD1.5's 1 066 M: 11.60 GB = 10.80 GiB
        assert round((total + 1066e6) * 2 / 1e9, 2) == 11.60
        assert round((total + 1066e6) * 2 / 2 ** 30, 2) == 10.80
        # ISSUE 56's fallback, 8 experts a layer: 3 322 M
        assert round((total - 4 * 8 * 44_040_192) / 1e6) == 3322
        # the whole model from the same shapes: 432 B, 28 B a token's
        linear_layer = delta + 4 * 7168
        latent_layer = count(attn) + 4 * 7168
        experts = 256 * 44_040_192 + 44_040_192 + 7168 * 256 + 256
        dense = count(shapes["layers_0"]["mlp"])
        published = (30 * linear_layer + 10 * latent_layer + 3 * dense
                     + 37 * experts + 2 * 7168 * 128256 + 7168)
        # (the two next-token modules, left out, are the rest of 432 B)
        assert round(published / 1e9, 1) == 430.5
        active = (published - 37 * 248 * 44_040_192)
        assert round(active / 1e9, 1) == 26.4
        # the caches of four forked sequences at the cell's capacity
        capacity = kv.capacity_for(2048 + 64 + 8 * STEPS)
        assert capacity == 2560
        state = 64 * 128 * 128 * 4 + 3 * 16384 * 4
        assert kv.state_bytes(share, capacity, jnp.bfloat16, 4, 8 * STEPS) \
            == {"full": 0, "sliding": 0, "linear": 4 * 4 * state,
                "latent": (2560 + 4 * 256) * 576 * 2}
        assert kv.copied_bytes(share, jnp.bfloat16, 4) == 16 * state
        assert round(16 * state / 2 ** 20, 1) == 67.0

    def test_on_the_chip_a_forked_step_takes_the_kernel(self, monkeypatch):
        """One decode step of the share the cell runs, traced without
        weights or FLOPs with the choosers told they are on a TPU (nothing
        compiles; tests/test_chip_compile.py compiles it for a described
        v5e): four expert layers through the pipelined kernel, four
        delta mixers a recurrent step a sequence, one forked latent site
        over 2 560 shared and 256 own rows of 576."""
        share = configs.sd15_gigachat35_expander().expander
        one = contract.cache_structs(share, 2560)
        cache = contract.forked_structs(share, 2560, 4, 256)
        assert [x.shape for x in cache["latent_shared"]] == [(2560, 576)]
        assert [x.shape for x in cache["latent"]] == [(4, 256, 576)]
        assert [(x.shape, x.dtype) for x in cache["state"]] \
            == [((4, 64, 128, 128), jnp.float32)] * 4
        assert [x.shape for x in cache["conv"]] == [(4, 3, 16384)] * 4
        shapes = contract.param_shapes(share)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ATTENTION.clear()
        EXPANDER.clear()
        logits, after, routed = contract.sites_of(
            share, shapes, jnp.zeros((4,), jnp.int32), 2200, 4,
            cache, jnp.bfloat16, sequences=True)
        assert logits.shape == (4, 16032)
        assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), after) \
            == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), cache)
        assert routed[0].shape == (4, 4, 8) and routed[1].shape == (4, 16)
        stats = EXPANDER.summary()
        assert stats["expert_products"] == {"kernel": 4, "loop": 0,
                                            "grouped": 0}
        assert stats["delta_mixers"] == {"recurrent": 0, "chunked": 0,
                                         "recurrent_forked": 4}
        # every forked mixer's (4, 64, 128, 128) float32 states through
        # the kernel that holds a head's state in VMEM
        assert stats["delta_steps"] == {"kernel": 4, "elementwise": 0}
        assert ATTENTION.summary()["by_shape"] == {
            "T4 S2560+256 D576": {"latent_forked": 1}}
        # a prefill chunk keeps the grouped product, the chunk-wise rule
        # and the expanded form
        contract.sites_of(
            share, shapes, jnp.zeros((64,), jnp.int32), 2048, 64,
            one, jnp.bfloat16)
        stats = EXPANDER.summary()
        assert stats["expert_products"]["grouped"] == 4
        assert stats["delta_mixers"]["chunked"] == 4
        assert stats["delta_steps"] == {"kernel": 4, "elementwise": 0}
        assert ATTENTION.summary()["latent_expanded"] == 1
        ATTENTION.clear()
        EXPANDER.clear()


# -- (g) the executables the benchmark already runs ---------------------------

#: sha256 (first 16 hex digits) of the lowered text of the ONE-SEQUENCE
#: expander executables of every tiny preset the repository had, at commit
#: 02288e3 (PR 55), by tests/test_kanana_expander.py:lowered_texts
PARENT_ONE_SEQUENCE = {
    "TINY_EXPAND": ("bc2e7bd7d29e1c3a", "21684674f1ee90da"),
    "TINY_DELTA_EXPAND": ("da95d3f5fde87d89", "f874bd6fc3fd9312"),
    "TINY_LATENT_EXPAND": ("a0a2d734b4c65d95", "e9c357475afd73d4"),
    "TINY_CONV_EXPAND": ("c56a134d182e06b7", "71408dd293f37716"),
    "TINY_WINDOW_EXPAND": ("6e701a11514cb72f", "7b5c4d4699af4ff2"),
    "TINY_LOOP_EXPAND": ("ca81e0aa2715817e", "543a1489c79eee98"),
    "TINY_KANANA_EXPAND": ("7253954c43de28fe", "2b50df21873624d4"),
}


@pytest.mark.parametrize("preset", sorted(PARENT_ONE_SEQUENCE))
def test_one_sequence_executables_lower_to_the_parents_text(preset):
    """New keys of ``LMConfig`` at their defaults, a norm's third field,
    a SwiGLU's limit of 0, a latent layer with no gate and a delta mixer
    told it has one sequence leave every one-sequence executable of every
    existing preset as it was: the lowered text of its prefill and its
    decode chunk is the parent's, byte for byte."""
    from tests.test_kanana_expander import lowered_texts

    texts = lowered_texts(preset)
    got = tuple(hashlib.sha256(texts[name].encode()).hexdigest()[:16]
                for name in ("prefill", "decode"))
    assert got == PARENT_ONE_SEQUENCE[preset]

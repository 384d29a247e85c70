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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.obs import prometheus
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER,
)
from tests import expander_contract as contract
from tests.expander_contract import CAPACITY, STEPS, rel_rms

REF = contract.load_reference("kanana2")
#: the norms off 1 and the selection bias off 0 (deviation 0.1, as the
#: benchmark seeds it)
CASE = contract.Case(
    configs.TINY_KANANA_EXPAND, REF,
    how=(("spread", (("scale", 0.2), ("e_score_correction_bias", 0.1))),),
    control_floor=5e-2, control_size=74,
    controls=("control", "rotate_half", "no_selection_bias",
              "narrow_shared_expert", "own_rows_dropped",
              "shared_without_prompt"))
CFG = CASE.cfg
params, engine = contract.fixtures(CASE)


# -- (a) program against reference --------------------------------------------

class TestAgainstTheReference(contract.ForkedAgainstTheReference,
                              contract.StagedAsTheTimedPathRunsIt):
    """Expanded form, a copy, a fork into four and the forked absorbed
    form, logits to 1e-5 and routing identical; int8 linears,
    ``rotate_half`` pairing in place of the interleaved one, the selection
    bias left out of the choice, a shared expert of the routed width, a
    sequence's own rows dropped, the shared range attended without the
    prompt's rows: each reads far from the reference."""
    CASE = CASE
    test_prefill_fork_and_decode_match_four_full_forwards = \
        contract.ForkedAgainstTheReference.program_matches_four_full_forwards
    PARAMETERS = {
        "test_prefill_fork_and_decode_match_four_full_forwards": [
            ("size", [37, 148])],
        "test_each_control_is_further_from_the_reference": [
            ("control", [name for name, _ in REF.CONTROLS])]}

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

class TestSequencesOfOneStep(contract.SequencesOfOneStep,
                             contract.WhichKindsShareAStep):
    """Latent layers of one stream share a step, and since PR 56 a
    recurrent state with a sequence axis; four streams and a conv layer's
    kept rows still decode one sequence a step."""
    CASE = CASE
    SHARE, ONE_A_STEP = ("sd15_kanana2_expander",), ("sd15_xing4_expander",)
    PARAMETERS = {
        "test_a_forked_decode_is_each_sequence_alone": [
            ("user,live,batch", [(1, 4, 4), (64, 3, 4), (16, 2, 2)])],
        "test_which_kinds_share_a_step": [("preset,shares", [
            ("TINY_KANANA_EXPAND", True), ("TINY_WINDOW_EXPAND", True),
            ("TINY_LOOP_EXPAND", True), ("TINY_LATENT_EXPAND", False),
            ("TINY_DELTA_EXPAND", True), ("TINY_CONV_EXPAND", False)])]}

    test_a_fork_copies_nothing = contract.SequencesOfOneStep \
        .a_fork_shares_what_has_positions_and_copies_the_rest

    def check_fork(self, forked):
        assert lm.buffers_of(lm.LATENT) == ("latent",)
        assert lm.buffers_of(lm.LATENT, forked=True) == ("latent",
                                                         "latent_shared")
        assert lm.slots_axis("latent") == -2 and lm.slots_axis("k") == -3

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

class TestTheTreeAndItsRules(contract.ShardingRules):
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
        shapes = contract.param_shapes(other)
        assert {"q_a_proj", "q_a_norm", "q_b_proj"} \
            <= set(shapes["layers_0"]["attn"])
        assert "q_proj" not in shapes["layers_0"]["attn"]

    #: ``q_proj`` takes the rule every ``q_proj`` takes (its columns, whole
    #: heads, over ``tp``: the path does not say the layer's kind); what
    #: makes and reads the latent stays whole on every chip
    WHOLE = (("layers_0/attn/kv_a_proj_with_mqa/kernel", 2),
             ("layers_0/attn/kv_b_proj/kernel", 2),
             ("layers_0/attn/kv_a_norm/scale", 1),
             ("layers_1/mlp/e_score_correction_bias", 1))
    EXPERT_LAYER = 1
    PLACED_WHOLE = ("layers_1/attn/q_proj/kernel",
                    "layers_1/attn/kv_b_proj/kernel")
    test_sharding_rules = contract.ShardingRules.sharding_rules

    def check_placed(self, placed, mesh):
        from jax.sharding import PartitionSpec as P

        from stable_diffusion_webui_distributed_tpu.parallel.sharding import (
            tp_spec_for,
        )

        assert tp_spec_for("layers_0/attn/q_proj/kernel", 2) \
            == P(None, "tp")


# -- (d) the engine's path ----------------------------------------------------

class TestEnginePath(contract.ForkedEnginePath):
    test_a_batch_prefills_once_forks_and_decodes_four_a_step = contract.ForkedEnginePath \
        .a_batch_prefills_once_forks_and_decodes_four_a_step
    test_every_image_its_own_expansion_and_one_image_the_old_path = contract.ForkedEnginePath \
        .every_image_its_own_expansion_and_one_image_the_old_path
    """Image ``i`` gets what a one-image request with seed ``s + i``
    gets."""
    CASE = CASE

    def check_traced(self, sites, traced):
        assert sites["latent_forked"] == 4 and sites["latent_expanded"] == 8
        assert "latent_absorbed" not in sites
        assert sites["by_shape"][f"T4 S{CAPACITY}+{2 * STEPS} D24"] \
            == {"latent_forked": 4}

    def check_counted(self, stats, sizes, one):
        assert stats["tokens_no_held_expert"] == 0
        picks = 2 * STEPS * 3 * 4       # steps x layers x k, one sequence
        assert picks <= stats["experts_read"] < 4 * picks
        assert stats["expert_products"]["kernel"] == 0      # a CPU
        assert stats["cache_positions"] == {
            "full": 0, "sliding": 0, "latent": 4 * (36 + 4 * 40)}
        steps = range(36, 36 + 2 * STEPS)
        assert stats["rows_attended"] == sum(4 * (p + 1) for p in steps)
        assert stats["rows_read"] == sum(36 + 4 * (p + 1 - 36)
                                         for p in steps)
        assert stats["rows_read_shared"] == 2 * STEPS * 36
        text = prometheus.render()
        assert "sdtpu_expander_rows_attended_total " \
            f"{stats['rows_attended']}" in text
        assert 'sdtpu_expander_rows_read_total{range="shared"} ' \
            f"{2 * STEPS * 36}" in text
        assert 'sdtpu_expander_rows_read_total{range="own"} ' \
            f"{stats['rows_read'] - 2 * STEPS * 36}" in text

    def check_spans(self, by_name, sizes, one):
        (prefill,) = by_name["expand.prefill"]
        assert prefill["sequences"] == 4    # whose first tokens it draws
        assert prefill["latent"] == "latent_expanded"
        (fork,) = by_name["expand.fork"]
        assert fork["latent"] == "latent_forked"
        # four layers' own rows of 64 slots a sequence, float32
        assert fork["bytes"] == 4 * 4 * 2 * STEPS * 24 * 4
        assert [a["latent"] for a in by_name["expand.decode_chunk"]] \
            == ["latent_forked"] * 2

    def check_one_image(self, sites, stats):
        # the absorbed form, and the pair's shared rows once saved
        assert sites["latent_absorbed"] == 4
        assert stats["rows_attended"] - stats["rows_read"] == 2 * STEPS * 36


# -- (e) the published share, from shapes -------------------------------------

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
        shapes, count = contract.param_shapes(share), contract.count
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
        one = contract.cache_structs(share, 2560)
        cache = contract.forked_structs(share, 2560, 4, 256)
        assert [x.shape for x in cache["latent_shared"]] \
            == [(2560, 576)] * 8
        assert [x.shape for x in cache["latent"]] == [(4, 256, 576)] * 8
        shapes = contract.param_shapes(share)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ATTENTION.clear()
        EXPANDER.clear()
        logits, after, routed = contract.sites_of(
            share, shapes, jnp.zeros((4,), jnp.int32), 2200, 4,
            cache, jnp.bfloat16, sequences=True)
        assert logits.shape == (4, 128256)
        assert jax.tree_util.tree_map(lambda x: x.shape, after) \
            == jax.tree_util.tree_map(lambda x: x.shape, cache)
        assert routed[0].shape == (7, 4, 6) and routed[1].shape == (7, 128)
        assert EXPANDER.summary()["expert_products"] == {
            "kernel": 7, "loop": 0, "grouped": 0}
        assert ATTENTION.summary()["by_shape"] == {
            "T4 S2560+256 D576": {"latent_forked": 8}}
        # a prefill chunk keeps the grouped product and the expanded form
        contract.sites_of(
            share, shapes, jnp.zeros((64,), jnp.int32), 2048, 64,
            one, jnp.bfloat16)
        assert EXPANDER.summary()["expert_products"]["grouped"] == 7
        assert ATTENTION.summary()["latent_expanded"] == 8
        ATTENTION.clear()
        EXPANDER.clear()


# -- (f) the executables the benchmark already runs ---------------------------

#: sha256 (first 16 hex digits) of the lowered text of the tiny presets'
#: expander executables at commit eed35c3 (PR 51), by :func:`lowered_texts`
#: (PR 70 replaced the forked decode chunk's of the three presets that have
#: expert layers: it returns the steps that streamed no expert, one more
#: carry of the scan; a preset without expert layers keeps the parent's)
PARENT = {
    "TINY_EXPAND": {
        "prefill": "bc2e7bd7d29e1c3a", "decode": "21684674f1ee90da",
        "prefill4": "ff959a44160abdee", "fork": "326e63be1749961c",
        "decode4": "e8426e6065c05198",
    },
    "TINY_DELTA_EXPAND": {
        "prefill": "da95d3f5fde87d89", "decode": "f874bd6fc3fd9312",
        # new at PR 56, when a recurrent state got a sequence axis and the
        # preset began to share a step: this PR's own, no parent's
        "prefill4": "a473ed63136570d9", "fork": "0cdae66e2a7352fe",
        "decode4": "66493441c013d2ec",
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
        "decode4": "3e5996bd8d896fab",
    },
    "TINY_LOOP_EXPAND": {
        "prefill": "ca81e0aa2715817e", "decode": "543a1489c79eee98",
        "prefill4": "429d4c7d764a7f0c", "fork": "3e46abbd36be45ca",
        "decode4": "4c0eb8c4d9f46fb5",
    },
}


@functools.lru_cache(maxsize=None)
def lowered_texts(preset, capacity=256, sequences=4, own_slots=64):
    """The lowered text of every expander executable of a tiny preset, as
    pipeline/expand.py builds them: the one-sequence prefill and decode
    chunk and, where the preset shares a step, the prefill that draws
    ``sequences`` first tokens, the fork and the forked decode chunk."""
    cfg = getattr(configs, preset).expander
    module = lm.DecoderLM(cfg)
    s = jax.ShapeDtypeStruct
    cache = contract.cache_structs(cfg, capacity, jnp.float32)
    params = contract.param_shapes(cfg)
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

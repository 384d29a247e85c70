"""The resident prompt expander whose every layer keeps keys and values
(three window layers under plain RoPE, then a full one under YaRN, the
period's LAST; ungated grouped-query attention; a renormalised softmax
router over experts that are all held, no shared one), at the shape that
makes such a model work: an instruction longer than the window, so the
rings wrap, and the images of one request decoded as sequences of ONE
step, forked from one prefill.

Everything runs the tiny preset (models/configs.py ``TINY_WINDOW_LM``: a
window of 8, 4 heads of width 8 over 2 KV heads, YaRN over an original
length of 16, 8 experts top-2). The plain reference is the benchmark's own
(benchmarks/reference/mellum2_ref.py: float32, one sequence, no cache, no
ring, the window a mask on the whole score matrix).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.ops import moe
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    Base64Text, prompt_expansion_args,
)
from stable_diffusion_webui_distributed_tpu.runtime import dtypes
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER,
)
from tests import expander_contract as contract
from tests.expander_contract import (
    CAPACITY, STEPS, assert_own_rows, rel_rms, run,
)

REF = contract.load_reference("mellum2")
#: the norms off 1, so that reading one as another would show
CASE = contract.Case(
    configs.TINY_WINDOW_EXPAND, REF, how=(("spread", (("scale", 0.2),)),),
    control_floor=1e-2, control_size=74)
FAMILY, CFG = CASE.family, CASE.cfg
params, engine = contract.fixtures(CASE)


def prefilled(params):
    """One chunk of 21 positions, none padded: the rings of 8 have
    wrapped."""
    return contract.prefilled(CFG, params, 21, prefix=0, capacity=256,
                              bucket=21)


# -- (a) program against reference --------------------------------------------

class TestAgainstTheReference(contract.ForkedAgainstTheReference):
    """The int8 linears, the windows attending everything, the windows
    under the full layers' table and the aliased rings each read far from
    the reference where the program reads 1e-6: the second and the fourth
    only because the context is over the window."""
    CASE = CASE
    PARAMETERS = {
        "test_chunks_fork_and_decode_match_four_full_forwards": [
            ("size", [37, 74])],
        "test_each_control_is_further_from_the_reference": [
            ("control", [name for name, _ in REF.CONTROLS])]}

    def test_chunks_fork_and_decode_match_four_full_forwards(self, params,
                                                             size):
        """The prefix as one chunk four and eight times the window (the
        rings wrap as often): logits to 1e-5, routing identical."""
        prefix, _, decoded = REF.split(size)
        assert prefix >= 4 * CFG.sliding_window and decoded >= 4
        self.program_matches_four_full_forwards(params, size)

    @pytest.mark.parametrize("control", [None, "aliased_rings"])
    def test_the_two_executables_give_what_the_one_gives(self, params,
                                                         control):
        """The chip's readings run the chunks with the fork and the decode
        steps as two executables, as the timed path does: the same logits
        and routing as the one jitted whole."""
        ids, continuations = REF.inputs(FAMILY, 3, 74)
        kwargs = dict(REF.CONTROLS)[control] if control else {}
        whole, chose = CASE.program(with_routing=True, **kwargs)(
            params, ids, continuations)
        got, chose_staged = REF.staged(FAMILY, dtypes.F32, params, ids,
                                       continuations, **kwargs)
        np.testing.assert_allclose(got, whole, rtol=1e-6, atol=1e-6)
        assert np.array_equal(chose, chose_staged)

    def test_a_context_inside_the_window_hides_two_of_the_controls(
            self, params):
        """What the old traffic would have measured: at 7 positions the
        window of 8 never binds, and a ring taken for a full buffer reads
        as the program does."""
        inputs, want, _ = CASE.referred(7)
        lower = CASE.program(windows_attend_all=True)(params, *inputs)
        assert rel_rms(lower, want) < 1e-5

    def test_yarn_from_its_five_numbers(self):
        """The reference's own frequencies against the program's, at the
        published rope: the fast pairs kept, the slow ones divided by 16,
        a ramp between, and the window layers' plain table apart."""
        rope = configs.MELLUM2_12B_A2_5B.rope_full
        own = REF.inverse_frequencies(rope, 128)
        np.testing.assert_allclose(own, lm.rope_frequencies(rope, 128),
                                   rtol=1e-12)
        plain = REF.inverse_frequencies(
            configs.MELLUM2_12B_A2_5B.rope_sliding, 128)
        ratio = plain / own
        assert ratio[0] == 1.0 and ratio[-1] == pytest.approx(16.0)
        between = (ratio > 1.0 + 1e-9) & (ratio < 16.0 - 1e-9)
        assert 10 < int(between.sum()) < 40
        assert rope.attention_factor == pytest.approx(
            0.1 * np.log(16.0) + 1.0)
        # the tiny preset's ramp lies inside a test's positions
        tiny = REF.inverse_frequencies(CFG.rope_full, CFG.head_dim)
        assert (tiny != REF.inverse_frequencies(
            CFG.rope_sliding, CFG.head_dim)).sum() == 3


# -- (b) a step over B sequences ----------------------------------------------

class TestSequencesOfOneStep(contract.SequencesOfOneStep,
                             contract.WhichKindsShareAStep):
    """At the chunk bucket's edges: a prompt of 1 leaves 63 padded rows
    behind the fork, one of 64 none. The prefix's 21 positions have
    wrapped the rings of 8."""
    CASE = CASE
    PARAMETERS = {
        "test_a_forked_decode_is_each_sequence_alone": [
            ("live,batch", [(2, 2), (4, 4), (3, 4)]),
            ("user", [1, 16, 63, 64])],
        "test_which_kinds_share_a_step": [("preset,shares", [
            ("TINY_WINDOW_EXPAND", True), ("TINY_EXPAND", True),
            ("TINY_DELTA_EXPAND", True), ("TINY_LATENT_EXPAND", False),
            ("TINY_CONV_EXPAND", False)])]}

    @pytest.mark.parametrize("live,batch", [(1, 1), (2, 2), (4, 4), (3, 4)])
    def test_each_sequence_gets_what_it_gets_alone(self, params, live,
                                                   batch):
        """From one chunk of 21 positions, none padded, and with a
        buffer's own count of slots a sequence: the same tokens, the same
        rows in the cache, a load that leaves the pad out, and never more
        distinct experts a step than picks."""
        contract.forked_against_alone(CASE, params, live, batch, own_slots=0,
                                      user=21, prefix=0, capacity=256,
                                      bucket=21)

    def test_a_step_gives_each_sequence_the_logits_it_gets_alone(
            self, params):
        """Teacher-forced on continuations that differ: every sequence's
        logits at every step, and both ring and buffer rows."""
        _, cache, length = prefilled(params)
        forced = jax.random.randint(jax.random.key(8), (12, 4), 0, 512)
        together = kv.fork(cache, 4)
        alone = [cache] * 4
        for t in range(12):
            logits, together, routed = run(
                CFG, params, forced[t], length + t, 4, together,
                sequences=True)
            assert logits.shape == (4, CFG.vocab[1])
            for b in range(4):
                want, alone[b], own = run(
                    CFG, params, forced[t, b][None], length + t, 1, alone[b])
                np.testing.assert_allclose(logits[b], want[0], rtol=1e-5,
                                           atol=1e-5)
                assert np.array_equal(np.sort(routed[0][:, b], -1),
                                      np.sort(own[0][:, 0], -1))
        # 21 + 12 positions through rings of 8: alone every slot is
        # rewritten, forked the ring is as the prefill left it
        for b in range(4):
            assert_own_rows(alone[b], together, b, length, 12)
        for mine, theirs in zip(cache["k"], together["k_shared"]):
            assert np.array_equal(np.asarray(mine), np.asarray(theirs))

    test_a_fork_copies_nothing = contract.SequencesOfOneStep \
        .a_fork_shares_what_has_positions_and_copies_the_rest

    @pytest.mark.parametrize("images,bucket", [
        (1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (8, 8), (11, 8)])
    def test_sequence_buckets(self, images, bucket):
        assert kv.sequence_bucket(images) == bucket

    def test_bytes_and_positions_times_sequences(self):
        manager = kv.KVCacheManager(CFG, jnp.bfloat16)
        # past the window the two kinds finally differ
        assert manager.positions_in_use(40) == {"full": 40,
                                                "sliding": 3 * 8}
        # four sequences forked at 30: what lies before it once (a ring
        # its 8), the 10 behind it once each
        assert manager.positions_in_use(40, 4, 30) == {
            "full": 30 + 4 * 10, "sliding": 3 * (8 + 4 * 8)}
        assert manager.positions_in_use(34, 4, 30) == {
            "full": 30 + 4 * 4, "sliding": 3 * (8 + 4 * 4)}
        one = kv.state_bytes(CFG, 256, jnp.bfloat16)
        row = 2 * 8 * 2                     # a slot's keys, bfloat16
        assert one == {"full": 2 * 256 * row, "sliding": 3 * 2 * 8 * row}
        # a forked group: every buffer once and 64 slots a sequence
        assert kv.state_bytes(CFG, 256, jnp.bfloat16, 4, 64) == {
            "full": 2 * (256 + 4 * 64) * row,
            "sliding": 3 * 2 * (8 + 4 * 64) * row}

    def test_experts_read_counts_each_held_expert_once(self):
        routing = moe.Routing(
            jnp.asarray([[0, 1], [1, 2], [1, 9]], jnp.int32),
            jnp.ones((3, 2), jnp.float32))
        load, _ = moe.load_counts(routing, 0, 8, jnp.ones(3, bool))
        assert int(load.sum()) == 5 and int(moe.experts_read(load)) == 3
        load, _ = moe.load_counts(routing, 0, 8,
                                  jnp.asarray([True, False, False]))
        assert int(moe.experts_read(load)) == 2
        assert moe.experts_read(jnp.zeros((4, 8), jnp.int32)).shape == (4,)


# -- the engine's path ----------------------------------------------------------


class TestOneImageTakesTheOneSequencePath:
    def test_executable_key_cache_and_lowered_text(self, engine):
        """(c): what one image runs is what the one-sequence path gives
        without ``expand_batch`` in the way: the key it has always had, a
        cache with no sequence axis, the ``loop`` product on a CPU, and the
        decode function's lowered text."""
        EXPANDER.clear()
        engine.txt2img(CASE.payload())
        keys = {k for k in engine.executable_keys()
                if k[0].startswith("expand")}
        assert keys == {("expand_prefill", 64, CAPACITY),
                        ("expand_decode_chunk", STEPS, CAPACITY),
                        # one dispatch each: the image's key, a
                        # snapshot's copy
                        ("expand_keys", 1), ("expand_copy", CAPACITY)}
        stats = EXPANDER.summary()
        assert stats["expert_products"] == {"kernel": 0, "loop": 4,
                                            "grouped": 4}
        assert stats["sequences"] == 1 and stats["tokens_decoded"] == 40
        assert stats["decode_steps"] == 2 * STEPS
        # a step of one token reads as many experts as it has picks
        assert stats["experts_read"] == 2 * STEPS * 4 * 2
        assert stats["state_bytes"] == kv.state_bytes(CFG, CAPACITY,
                                                      jnp.float32)
        module = engine.expander.module
        cache = lm.empty_cache(CFG, CAPACITY, jnp.float32)
        assert [x.ndim for x in cache["k"]] == [3] * 4
        args = (engine.params["expander"], cache, jnp.int32(0),
                jnp.int32(36), jax.random.key(0), jnp.float32(1.0))
        served = engine.expander._decode_fn(CAPACITY).lower(*args).as_text()
        plain = jax.jit(lm.decode_chunk_fn(module, STEPS),
                        donate_argnums=(1,)).lower(*args).as_text()
        assert served == plain
        batched = engine.expander._decode_fn(CAPACITY, 2)
        assert batched is not engine.expander._decode_fn(CAPACITY)

    def test_a_one_image_expand_batch_is_expand(self, engine):
        args = prompt_expansion_args(CASE.payload())
        one = engine.expander.expand("a cow in a valley", args, 1234, 0)
        assert engine.expander.expand_batch(
            "a cow in a valley", args, 1234, [0]) == [one]
        assert one == engine.txt2img(CASE.payload()).prompts[0]


class TestABatchOfImages(contract.ForkedEnginePath):
    CASE, KEYS = CASE, None

    def test_every_image_its_own_expansion_in_any_range(self, engine):
        """(d): images 2-3 of a four-image request get what they get in
        the whole request and what four one-image requests with seeds
        ``s + i`` give."""
        whole = engine.txt2img(CASE.payload(batch_size=4))
        assert len(set(whole.prompts)) == 4
        part = engine.generate_range(CASE.payload(batch_size=4), 2, 2)
        assert part.prompts == whole.prompts[2:]
        assert part.images == whole.images[2:]
        # four PNGs go back as the encoder's own base64, which the server
        # copies into the response unread (server/api.py:json_body)
        assert all(type(png) is Base64Text for png in whole.images)
        for i in range(4):
            solo = engine.txt2img(CASE.payload(seed=1234 + i))
            assert solo.prompts[0] == whole.prompts[i], i
        # three images: a batch of four whose fourth repeats the third
        three = engine.txt2img(CASE.payload(batch_size=3))
        assert three.prompts == whole.prompts[:3]
        keys = {k for k in engine.executable_keys()
                if k[0] == "expand_decode_chunk"}
        assert keys == {("expand_decode_chunk", STEPS, CAPACITY),
                        ("expand_decode_chunk", STEPS, CAPACITY, 2),
                        ("expand_decode_chunk", STEPS, CAPACITY, 4)}

    test_one_prefill_and_four_sequences_a_step = contract.ForkedEnginePath \
        .a_batch_prefills_once_forks_and_decodes_four_a_step

    def check_counted(self, stats, sizes, one):
        picks = 2 * STEPS * 4 * 2       # steps x layers x k, one sequence
        assert picks <= stats["experts_read"] <= min(4 * picks,
                                                     2 * STEPS * 4 * 8)
        routed = sum(map(sum, stats["expert_tokens"]))
        assert routed == 5 * 4 * 2 + 4 * picks
        assert stats["experts_read"] < 4 * picks
        # forked at 31 + 5: those positions once (a ring the last 8),
        # the 40 behind them once a sequence (a window of them)
        assert stats["cache_positions"] == {
            "full": 36 + 4 * 40, "sliding": 3 * (8 + 4 * 8)}
        steps = range(36, 36 + 2 * STEPS)
        assert stats["rows_attended"] == sum(4 * (p + 1) for p in steps)
        assert stats["rows_read"] == sum(36 + 4 * (p + 1 - 36)
                                         for p in steps)

    def check_spans(self, by_name, sizes, one):
        # four layers' keys and values of 64 slots a sequence, float32;
        # rings and buffers stay where they are
        (fork,) = by_name["expand.fork"]
        assert fork["bytes"] == 4 * 2 * 4 * 2 * STEPS * 2 * 8 * 4

    def test_the_decode_trace_takes_the_grouped_product(self):
        """A fresh engine's four-image request traces one prefill chunk
        (31 and 5 tokens both pad to 64: one executable at one sequence,
        one that draws four first tokens) and the four-sequence scan: all
        three through the grouped product, four expert layers each."""
        fresh = CASE.engine()
        EXPANDER.clear()
        ATTENTION.clear()
        fresh.txt2img(CASE.payload(batch_size=4))
        stats = EXPANDER.summary()
        assert stats["expert_products"] == {"kernel": 0, "loop": 0,
                                            "grouped": 12}
        keys = {k for k in fresh.executable_keys()
                if k[0].startswith("expand")}
        assert keys == {("expand_prefill", 64, CAPACITY),
                        ("expand_prefill", 64, CAPACITY, 4),
                        ("expand_fork", CAPACITY, 4, 2 * STEPS),
                        ("expand_decode_chunk", STEPS, CAPACITY, 4),
                        ("expand_keys", 4), ("expand_copy", CAPACITY)}
        sites = ATTENTION.summary()["by_shape"]
        # a forked step's keys: the ring or the buffer as the prefill
        # left it, and a sequence's own 64 slots behind it
        assert sites[f"T1 S{8 + 2 * STEPS} D8"] == {"xla": 3}
        assert sites[f"T1 S{CAPACITY + 2 * STEPS} D8"] == {"xla": 1}
        ATTENTION.clear()

    def test_same_seed_images_are_expanded_once(self, engine):
        EXPANDER.clear()
        out = engine.txt2img(CASE.payload(batch_size=3, same_seed=True))
        assert len(set(out.prompts)) == 1
        assert out.prompts[0] == engine.txt2img(CASE.payload()).prompts[0]
        assert EXPANDER.summary()["sequences"] == 2     # 1 + the solo

    def test_a_prompt_matrix_groups_by_text(self, engine):
        """Images whose prompts differ are groups of their own, in the
        order of their first image."""
        request = CASE.payload(batch_size=4, all_prompts=[
            "a cow in a valley", "a red fox", "a cow in a valley",
            "a red fox"])
        EXPANDER.clear()
        out = engine.generate_range(request, 0, 4)
        assert EXPANDER.summary()["requests"] == 2
        assert EXPANDER.summary()["sequences"] == 4
        assert out.prompts[0].startswith("a cow in a valley")
        assert out.prompts[1].startswith("a red fox")
        assert out.prompts[2] == engine.txt2img(
            CASE.payload(seed=1236)).prompts[0]
        assert out.prompts[3] == engine.txt2img(
            CASE.payload(prompt="a red fox", seed=1237)).prompts[0]

    def test_eos_cuts_one_sequence_and_an_interrupt_ends_all(
            self, engine, monkeypatch):
        """(e), on the tokens the stage makes."""
        args = prompt_expansion_args(CASE.payload(alwayson_scripts=CASE.script(
            max_new_tokens=3 * STEPS, context_chunks=None)))
        stage = engine.expander
        images = [0, 1, 2, 3]
        full = stage._generate("a cow in a valley", args, 1234, images)
        assert [len(one) for one in full] == [3 * STEPS] * 4
        # a token the second image draws early and the others never
        others = {t for b in (0, 2, 3) for t in full[b]}
        at, eos = next((i, t) for i, t in enumerate(full[1])
                       if t not in others)
        assert 0 < at < STEPS
        monkeypatch.setattr(stage.tokenizer, "eos", eos)
        cut = args.model_copy(update={"ignore_eos": False})
        EXPANDER.clear()
        got = stage._generate("a cow in a valley", cut, 1234, images)
        assert got[1] == full[1][:at]
        assert [got[b] for b in (0, 2, 3)] == [full[b] for b in (0, 2, 3)]
        assert EXPANDER.summary()["tokens_decoded"] == 9 * STEPS + at
        # both sequences drew it in the first chunk: the loop ends with
        # the chunk enqueued ahead of that one's fetch, two of three
        both = stage._generate("a cow in a valley", cut, 1234, [1, 1])
        assert both == [full[1][:at]] * 2
        assert EXPANDER.summary()["decode_steps"] == 3 * STEPS + 2 * STEPS
        # an interrupt between chunks ends every sequence
        real = stage._decode_fn(CAPACITY, 4)

        def then_interrupt(*a):
            engine.state.flag.interrupt()
            return real(*a)

        monkeypatch.setattr(stage, "_decode_fn",
                            lambda capacity, sequences=1: then_interrupt)
        try:
            ended = stage._generate("a cow in a valley", args, 1234, images)
        finally:
            engine.state.flag.clear()
        assert ended == [one[:1 + STEPS] for one in full]


# -- (f) the published share, from shapes -------------------------------------

class TestThePublishedShare:
    def test_parameters_and_bytes_from_shapes(self):
        share = configs.sd15_mellum2_expander().expander
        whole = configs.MELLUM2_12B_A2_5B
        assert share.layer_types == ("sliding", "sliding", "sliding",
                                     "full") * 2
        assert whole.layers_of("full") == tuple(range(3, 28, 4))
        assert share.experts == (0, 64) and share.vocab == (0, 98304)
        assert whole.intermediate_size == 8 * whole.moe_intermediate_size
        shapes, count = contract.param_shapes(share), contract.count
        layer = shapes["layers_0"]
        assert count(layer["attn"]) == 21_233_664
        assert layer["mlp"]["router"].shape == (2304, 64)
        assert count(layer["mlp"]["experts"]) == 64 * 6_193_152
        assert set(layer["mlp"]) == {"router", "experts"}
        assert set(layer["attn"]) == {"q_proj", "k_proj", "v_proj",
                                      "o_proj"}
        norms = 2 * 2304
        assert count(layer) == 417_742_848 + norms
        assert count(shapes["embed_tokens"]) == count(shapes["lm_head"]) \
            == 226_492_416
        total = count(shapes)
        assert total == 8 * (417_742_848 + norms) + 2 * 226_492_416 + 2304
        assert round(total / 1e6) == 3795
        assert round((28 * 417_742_848 + 2 * 226_492_416) / 1e9, 2) == 12.15
        # three periods would be 5 466 M: 10.93 GB beside SD1.5's 2.13
        assert round((total + 4 * (417_742_848 + norms)) / 1e6) == 5466
        # a decoded token alone: 8 x 42.8 MB, the head, 64 experts
        fixed = 8 * (21_233_664 + 147_456) * 2 + 226_492_416 * 2
        assert round(fixed / 1e6) == 795
        assert round((fixed + 64 * 6_193_152 * 2) / 1e6) == 1588
        # the caches of four forked sequences at the cell's capacity:
        # every buffer once and 256 slots a sequence
        capacity = kv.capacity_for(2048 + 64 + 8 * STEPS)
        sizes = kv.state_bytes(share, capacity, jnp.bfloat16, 4, 8 * STEPS)
        row = 2 * 4 * 128 * 2               # a layer's slot
        assert sizes == {"full": 2 * (capacity + 4 * 256) * row,
                         "sliding": 6 * (1024 + 4 * 256) * row}

    def test_one_step_of_four_sequences_traces_the_grouped_product(self):
        """One decode step of the share the cell runs, traced without
        weights or FLOPs: eight expert layers through the grouped
        product, six ring sites and two buffer sites."""
        share = configs.sd15_mellum2_expander().expander
        cache = contract.forked_structs(share, 2560, 4, 256)
        shapes = contract.param_shapes(share)
        ATTENTION.clear()
        EXPANDER.clear()
        logits, after, routed = contract.sites_of(
            share, shapes, jnp.zeros((4,), jnp.int32), 2200, 4,
            cache, jnp.bfloat16, sequences=True)
        assert logits.shape == (4, 98304)
        assert [x.shape for x in after["k"]] == [x.shape for x in cache["k"]]
        assert routed[0].shape == (8, 4, 8) and routed[1].shape == (8, 64)
        assert EXPANDER.summary()["expert_products"] == {
            "kernel": 0, "loop": 0, "grouped": 8}
        assert [x.shape for x in after["k_shared"]] \
            == [(1024, 4, 128)] * 3 + [(2560, 4, 128)] \
            + [(1024, 4, 128)] * 3 + [(2560, 4, 128)]
        assert {x.shape for x in after["k"]} == {(4, 256, 4, 128)}
        assert ATTENTION.summary()["by_shape"] == {
            "T1 S1280 D128": {"xla": 6}, "T1 S2816 D128": {"xla": 2}}
        assert moe.row_tile(4, 8, 64) == 8      # a trip an expert
        ATTENTION.clear()
        EXPANDER.clear()

    @pytest.mark.parametrize("sequences", [2, 4, 8])
    def test_on_the_chip_that_step_takes_the_kernel(self, monkeypatch,
                                                    sequences):
        """The same trace with the chooser told it is on a TPU (nothing
        compiles): all eight expert layers take the pipelined kernel with
        the whole block of rows, as the cell's ``m2_expert_kernel_sites``
        reads it, and a prefill chunk keeps the grouped product."""
        share = configs.sd15_mellum2_expander().expander
        cache = contract.forked_structs(share, 2560, sequences, 256)
        shapes = contract.param_shapes(share)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        EXPANDER.clear()
        tokens = jnp.zeros((sequences,), jnp.int32)
        contract.sites_of(share, shapes, tokens, 2200, sequences, cache,
                          jnp.bfloat16, sequences=True)
        assert EXPANDER.summary()["expert_products"] == {
            "kernel": 8, "loop": 0, "grouped": 0}
        one = contract.cache_structs(share, 2560)
        contract.sites_of(
            share, shapes, jnp.zeros((64,), jnp.int32), 2048, 64,
            one, jnp.bfloat16)
        assert EXPANDER.summary()["expert_products"] == {
            "kernel": 8, "loop": 0, "grouped": 8}
        ATTENTION.clear()
        EXPANDER.clear()

"""The fifth prompt-expander cell (``sd15_mellum2_expand_b4``) rehearsed on
the CPU at tiny widths through the real ``run.py``, and the files it
brought: the traffic's token counts, the byte count of a step of several
sequences against a hand count, the two readers, the op classes, the metric
files. A rehearsal yields counts and correctness, never a speed."""

import json
import os
import re
import types

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_mellum2_expand_b4"
CONFIG = "sd15_mellum2_expand"
TRAFFIC = "sd15_2048_b4_expand256"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_mellum2_expander")
NEW = ["m2_expand_ms", "m2_expand_prefill_ms", "m2_expand_decode_ms",
       "m2_expand_fork_ms", "m2_linear_device_ms", "m2_full_attn_device_ms",
       "m2_window_attn_device_ms", "m2_expert_device_ms",
       "m2_other_device_ms", "m2_decode_bytes_util",
       "m2_experts_read_per_step", "m2_tokens_per_step"]
#: read from what only a TPU's trace or memory_stats() holds
CHIP_ONLY = {"peak_hbm_gib"} | {n for n in NEW if "device" in n
                                or "bytes" in n}
BENCH = files.Bench(rehearsal.REPO)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rehearsal.make_root(str(tmp_path_factory.mktemp("m2")))
    rehearsal._rewrite(
        os.path.join(root, "benchmarks", "configs", CONFIG + ".json"),
        lambda c: c.update(factory=TINY_FACTORY, policy="F32"))

    def shorter(traffic):
        # 31 tokens with BOS: still four times the tiny window of 8
        args = traffic["payload"]["alwayson_scripts"][
            "prompt expansion"]["args"][0]
        args.update(max_new_tokens=40, context_chunks=1,
                    instruction=" ".join(args["instruction"].split()[:30]))

    rehearsal._rewrite(
        os.path.join(root, "benchmarks", "traffic", TRAFFIC + ".json"),
        shorter)
    return root


def metric_names(kind):
    return {m["name"] for m in BENCH.manifest[kind]
            if "workloads" not in m or CELL in m["workloads"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_and_prints_the_contract_line(root, trace):
    rc, result, output = rehearsal.drive(root, CELL, trace, seconds=3.0)
    assert rc == 0 and result is not None, output[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    kind = "per_layer" if trace else "end_to_end"
    reported = set(result["metrics"])
    assert reported <= metric_names(kind)
    assert metric_names(kind) - reported <= CHIP_ONLY
    assert "raised" not in output
    assert "nothing compiled inside the window" in output
    if trace:
        m = {k: v["value"] for k, v in result["metrics"].items()}
        assert m["m2_expand_ms"] > m["m2_expand_decode_ms"] > 0
        assert m["m2_expand_prefill_ms"] > 0 and m["m2_expand_fork_ms"] > 0
        # the other expanders' metrics list their own cells
        assert not {"expand_ms", "lfm_expand_ms", "x4_expand_ms",
                    "expert_kernel_sites", "lm_linear_device_ms"} & set(m)
        # four images a step: 40 tokens a sequence over two chunks of 32
        assert m["m2_tokens_per_step"] == pytest.approx(4 * 40 / 64)
        # the tiny preset has 4 expert layers of 8 experts, 2 a token; the
        # metric divides by the published share's 8
        a_layer = m["m2_experts_read_per_step"] * 8 / 4
        assert 2 <= a_layer <= 8


def test_the_traffic_is_a_batch_behind_an_instruction_twice_the_window():
    cell = BENCH.cell(CELL)
    assert cell["config"] == CONFIG and cell["traffic"] == TRAFFIC
    assert cell["chips"] == 1 and cell["mesh"] is None
    assert cell["server_env"] == {"SDTPU_BATCH_LADDER": "4"}
    assert cell["warmup_requests"] == 1
    assert cell["trace"] == {"requests": 2, "max_seconds": 12.0}
    from stable_diffusion_webui_distributed_tpu.models.tokenizer import (
        load_lm_tokenizer,
    )
    share = files.resolve_family(BENCH.config(CONFIG)).expander
    assert share.vocab == (0, 98304)
    tok = load_lm_tokenizer(None, *share.vocab)
    traffic = BENCH.traffic(TRAFFIC)
    old = BENCH.traffic("sd15_512_expand384")
    payload = traffic["payload"]
    args = payload["alwayson_scripts"]["prompt expansion"]["args"][0]
    prefix = [tok.bos] + tok.encode(args["instruction"])
    assert len(prefix) == 2048 == 2 * share.sliding_window
    assert all(0 <= i < 98304 for i in prefix)
    lengths = [len(tok.encode(p)) for p in traffic["cycle"]["prompt"]]
    assert min(lengths) == 16 and max(lengths) == 64
    assert traffic["cycle"] == old["cycle"]
    assert args["max_new_tokens"] == 256 and args["ignore_eos"] is True
    assert args["temperature"] == 1.0 and args["context_chunks"] == 3
    assert payload["batch_size"] == 4 and traffic["clients"] == 1
    for key in ("steps", "width", "height", "sampler_name", "cfg_scale"):
        assert payload[key] == old["payload"][key], key
    # its words are the siblings'
    theirs = set(old["payload"]["alwayson_scripts"]["prompt expansion"][
        "args"][0]["instruction"].split())
    assert set(args["instruction"].split()) <= theirs
    # what the timed path sizes from them: one chunk of the prefix, one
    # bucket of the prompt, eight chunks of decode steps
    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.pipeline import expand

    assert kv.chunk_bucket(2048) == 2048 and kv.chunk_bucket(64) == 64
    chunks = -(-(256 - 1) // expand.DECODE_STEPS)
    assert chunks == 8
    assert kv.capacity_for(2048 + 64 + chunks * expand.DECODE_STEPS) == 2560
    # the reference's own run takes its readings at the timed sizes (a
    # process an executable); verify_reference.py's one process runs a
    # quarter of them, and says why
    assert BENCH.reference(BENCH.config(CONFIG)).TIMED_POSITIONS \
        == 2048 + 64 + 256
    assert BENCH.config(CONFIG)["reference_latent"] == 512 + 16 + 64


def test_the_configuration_holds_the_published_config_but_for_reduced():
    config = BENCH.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(json.loads(line) for line in fh if re.search(
            '"name": "Mellum2-12B-A2.5B-Instruct"', line))
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8     # two periods: ISSUE 45's
    assert "RESOURCE_EXHAUSTED" in config["held_here"]["layers"]    # rule
    assert len(config["assumed"]) >= 8 and config["counter"] is None
    assert config["components"] == "unet_clip_vae_lm_table"
    for key in ("published", "held_here", "deployment", "assumed"):
        assert config[key], key
    assert "four chips" in config["deployment"]
    assert config["diffusion"] == BENCH.read("configs", "sd15.json")["model"]
    share = files.resolve_family(config).expander
    assert share.num_layers == config["num_hidden_layers"]
    # every expert and every id is held: the cut is in depth alone
    assert share.experts == (0, config["num_experts"]) == (0, 64)
    assert share.vocab == (0, config["vocab_size"]) == (0, 98304)
    kinds = {"sliding_attention": "sliding", "full_attention": "full"}
    assert share.layer_types == tuple(
        kinds[kind] for kind in config["layer_types"][:8])
    assert share.layer_types.count("sliding") == 6
    assert share.dense_layers == ()
    ropes = config["rope_parameters"]
    full, window = ropes["full_attention"], ropes["sliding_attention"]
    assert share.rope_full.theta == full["rope_theta"] \
        == share.rope_sliding.theta == window["rope_theta"]
    assert share.rope_full.factor == full["factor"]
    assert share.rope_full.original_max_position \
        == full["original_max_position_embeddings"]
    assert share.rope_full.beta_fast == full["beta_fast"]
    assert share.rope_full.beta_slow == full["beta_slow"]
    assert share.rope_full.attention_factor == full["attention_factor"]
    assert share.rope_sliding.factor == 0
    assert share.rope_full.partial_rotary_factor == 1.0 \
        == share.rope_sliding.partial_rotary_factor
    for ours, theirs in (
            ("hidden_size", "hidden_size"), ("head_dim", "head_dim"),
            ("intermediate_size", "intermediate_size"),
            ("moe_intermediate_size", "moe_intermediate_size"),
            ("num_experts", "num_experts"),
            ("num_experts_per_tok", "num_experts_per_tok"),
            ("norm_topk_prob", "norm_topk_prob"),
            ("num_kv_heads", "num_key_value_heads"),
            ("sliding_window", "sliding_window"),
            ("rms_norm_eps", "rms_norm_eps")):
        assert getattr(share, ours) == config[theirs], ours
    assert share.num_heads_per_layer == (config["num_attention_heads"],) * 8
    assert share.attn_gate == "none" and not share.qk_norm
    assert share.shared_expert_intermediate_size == 0
    assert share.routed_scaling_factor == 1.0
    assert config["attention_bias"] is False


def test_the_leaf_rules_and_the_shares_parameters():
    """Shapes only: nothing is drawn."""
    import jax

    from benchmarks.harness import weights

    config = BENCH.config(CONFIG)
    components = BENCH.components(config)
    family = files.resolve_family(config)
    module, args = components.component_inits(family)["expander"]
    shapes = weights.param_shapes(module, args)
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = sum(leaf.size for _, leaf in flat)
    assert round(total / 1e6) \
        == config["parameters_millions"]["expander_share"] == 3795
    assert round(total * 2 / 1e9, 2) == 7.59
    assert round(total * 2 / 2 ** 30, 2) == 7.07
    assert round((total / 1e6 + config["parameters_millions"]["sd15"])
                 * 2e6 / 1e9, 2) == 9.72
    rules = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        rules[name] = (components.leaf_rule(name, leaf.shape)
                       or weights.leaf_rule(name, leaf.shape)) \
            + (tuple(leaf.shape),)
    assert rules["layers_0/mlp/router"] \
        == ("draw", (3 / 2304) ** 0.5, (2304, 64))
    # the table at variance 1: a token's row weighs what a sublayer adds
    assert rules["embed_tokens/embedding"] == ("draw", 3 ** 0.5,
                                               (98304, 2304))
    other = BENCH.load("components", "unet_clip_vae_lm")
    assert other.leaf_rule("embed_tokens/embedding", (98304, 2304)) is None
    assert components.leaf_rule("text_model/token_embedding/embedding",
                                (49408, 768)) is None
    assert rules["layers_0/attn/q_proj/kernel"][2] == (2304, 4096)
    assert rules["layers_3/attn/k_proj/kernel"][2] == (2304, 512)
    assert rules["layers_7/attn/o_proj/kernel"][2] == (4096, 2304)
    assert rules["lm_head/kernel"][2] == (2304, 98304)
    assert not any(part in name for name in rules for part in (
        "shared_expert", "g_proj", "q_norm", "up_proj", "bias"))
    # each stacked expert kernel is a draw of its own
    big = [r for r in rules.values() if len(r[2]) == 3]
    assert len(big) == 24 and len(set(big)) == 24
    assert {r[2] for r in big} == {(64, 2304, 896), (64, 896, 2304)}


def test_bytes_a_decode_step_needs_against_a_hand_count():
    count = BENCH.load("harness", "bytes_mellum2")
    cfg = files.resolve_family(BENCH.config(CONFIG)).expander
    d = 2304
    attn = (2 * d * 4096 + 2 * d * 512) * 2
    router = d * 64 * 2
    head = d * 98304 * 2
    expert = 3 * d * 896 * 2
    assert count.attention_layer_bytes(cfg, 0) == attn == 42_467_328
    assert count.layer_fixed_bytes(cfg, 3) == attn + router
    assert round((attn + router) / 1e6, 1) == 42.8
    assert count.expert_bytes(cfg) == expert == 12_386_304
    # 835 MB a layer as it lies; 795 MB fixed a step
    assert count.layer_bytes(cfg, 0) == attn + router + 64 * expert
    assert round(count.layer_bytes(cfg, 0) / 1e6) == 835
    assert count.fixed_bytes(cfg) == 8 * (attn + router) + head
    assert round(count.fixed_bytes(cfg) / 1e6) == 795
    # 2 048 B of keys and values a row; past the window the kinds part
    assert count.cache_bytes(cfg, 0) == 8 * 2048
    assert count.cache_bytes(cfg, 1023) == 8 * 1024 * 2048
    assert count.cache_bytes(cfg, 2299) \
        == (2 * 2300 + 6 * 1024) * 2048
    # a token alone: 64 experts, 1 588 MB of weights
    alone = count.decode_bytes(cfg, 2112, 1, 64.0)
    assert alone == count.fixed_bytes(cfg) + 64 * expert \
        + count.cache_bytes(cfg, 2112)
    assert round((alone - count.cache_bytes(cfg, 2112)) / 1e6) == 1588
    # a step of four under even routing: 26.5 distinct experts a layer
    even = 64 * (1 - 0.875 ** 4)
    assert round(even, 1) == 26.5
    four = count.decode_bytes(cfg, 2299, 1, 8 * even, 4)
    assert four == pytest.approx(
        count.fixed_bytes(cfg) + 8 * even * expert
        + 4 * count.cache_bytes(cfg, 2299))
    assert 3.50e9 < four < 3.52e9
    assert round(8 * even * expert / four, 2) == 0.75
    assert round(4 * count.cache_bytes(cfg, 2299) / 1e6) == 88
    # counting picks where the program reads distinct experts would read
    # a fifth high
    picks = count.decode_bytes(cfg, 2299, 1, 8 * 32, 4)
    assert 1.15 < picks / four < 1.25
    # distinct experts: never over the picks, never under one sequence's
    assert count.decode_bytes(cfg, 2299, 1, 8 * 8, 4) < four < picks
    assert count.decode_bytes(cfg, 600, 2, 300.0, 4) == pytest.approx(
        count.decode_bytes(cfg, 600, 1, 300.0, 4)
        + count.decode_bytes(cfg, 601, 1, 300.0, 4))


def _status(steps, decoded, read, routed=0, prefilled=0):
    return {"serving": {"expander": {
        "tokens_prefilled": prefilled, "decode_steps": steps,
        "tokens_decoded": decoded, "experts_read": read, "sequences": 0,
        "expert_tokens": [[routed, 0], [0, 0]]}}}


def test_bytes_util_steps_reads_the_programs_counters():
    reader = BENCH.load("readers", "bytes_util_steps")
    spec = BENCH.layer_metric("m2_decode_bytes_util")
    assert spec["reader"] == "bytes_util_steps"
    traffic = BENCH.traffic(TRAFFIC)
    payload = dict(traffic["payload"], prompt=traffic["cycle"]["prompt"][0])
    context = {
        "records": [types.SimpleNamespace(traced=True, payload=payload)],
        "trace": {"modules": {"jit_expand_decode_chunk": 1.4}},
        "family": files.resolve_family(BENCH.config(CONFIG)),
        # two requests of 256 steps, four tokens and 212 distinct experts
        # a step; the picks (expert_tokens) are not what is read
        "status_before": _status(256, 1024, 80000, routed=5),
        "status_after": _status(768, 3072, 80000 + 512 * 212,
                                routed=10 ** 6),
        "chips": 1, "peak": {"hbm_bytes_per_s": 819e9}, "bench": BENCH,
    }
    count = BENCH.load("harness", "bytes_mellum2")
    cfg = context["family"].expander
    want = 100 * count.decode_bytes(cfg, 2048 + 16, 256, 212.0, 4.0) \
        / (1.4 * 819e9)
    assert reader.read(context, **spec["args"]) == pytest.approx(want)
    assert 75 < want < 85       # 3.5 GB a step, 256 steps, in 1.4 seconds
    assert reader.read(dict(context, trace=None), **spec["args"]) is None
    # the parent's status has neither counter: nothing to read, no raise
    old = {"serving": {"expander": {"tokens_prefilled": 1,
                                    "decode_steps": 9,
                                    "tokens_decoded": 9}}}
    assert reader.read(dict(context, status_before=old, status_after=old),
                       **spec["args"]) is None
    # a window in which no step ran
    same = _status(256, 1024, 80000)
    assert reader.read(dict(context, status_before=same, status_after=same),
                       **spec["args"]) is None


def test_the_ratio_metrics_read_the_windows_growth_or_nothing():
    reader = BENCH.load("readers", "status_ratio")
    context = {"status_before": _status(256, 1024, 80000),
               "status_after": _status(768, 3072, 80000 + 512 * 212)}
    spec = BENCH.layer_metric("m2_tokens_per_step")
    assert reader.read(context, **spec["args"]) == 4.0
    spec = BENCH.layer_metric("m2_experts_read_per_step")
    assert reader.read(context, **spec["args"]) == pytest.approx(26.5)
    # the parent's /internal/status has no experts_read
    old = {"serving": {"expander": {"decode_steps": 9,
                                    "tokens_decoded": 9}}}
    assert reader.read({"status_before": old, "status_after": old},
                       **spec["args"]) is None
    assert reader.read({"status_before": {}, "status_after": {}},
                       **spec["args"]) is None
    same = {"status_before": context["status_before"],
            "status_after": context["status_before"]}
    assert reader.read(same, **spec["args"]) is None


@pytest.mark.parametrize("classes", ["mellum2_decode", "mellum2_prefill"])
def test_op_classes_partition_by_flax_module(classes):
    reader = BENCH.load("readers", "op_class_ms")
    spec = BENCH.read("op_classes", classes + ".json")
    rules = spec["classes"]
    assert spec["module"] == {
        "mellum2_decode": "jit_expand_decode_chunk",
        "mellum2_prefill": "jit_expand_prefill"}[classes]
    base = "jit(f)/jit(main)/while/body/DecoderLM/"
    cases = {
        "layers_0/attn/q_proj/dot_general": "linear",
        "layers_3/attn/k_proj/dot_general": "linear",
        "layers_7/attn/v_proj/dot_general": "linear",
        "layers_6/attn/o_proj/dot_general": "linear",
        "lm_head/dot_general": "linear",
        "layers_3/attn/exp": "full_attn",
        "layers_7/attn/vmap(one)/dot_general": "full_attn",
        "layers_7/attn/dynamic_update_slice": "full_attn",
        "layers_0/attn/exp": "window_attn",
        "layers_1/attn/vmap(one)/dot_general": "window_attn",
        "layers_6/attn/dynamic_update_slice": "window_attn",
        "layers_13/attn/exp": "window_attn",    # not layer 3 by its tail
        "layers_17/attn/exp": "window_attn",
        "layers_0/mlp/top_k": "expert",
        "layers_3/mlp/while/body/dot_general": "expert",
        "layers_7/mlp/sort": "expert",
        "layers_5/mlp/scatter-add": "expert",
        "layers_1/input_norm/rsqrt": "other",
        "layers_4/post_attention_norm/rsqrt": "other",
        "embed_tokens/gather": "other",
        "norm/rsqrt": "other",
    }
    for scope, want in cases.items():
        row = {"scope": base + scope, "category": "x", "name": "fusion.1"}
        assert reader.classify(row, rules) == want, scope
    # XLA's asynchronous copies carry no flax scope
    loose = {"scope": "jit(expand_decode_chunk)/while", "category": "x"}
    assert reader.classify(dict(loose, name="copy-done.7"), rules) \
        == "linear"
    assert reader.classify(dict(loose, name="slice-start.2"), rules) \
        == "linear"
    assert reader.classify(dict(loose, name="copy.3"), rules) == "other"
    order = [r["class"] for r in rules]
    assert sorted(set(order)) == ["expert", "full_attn", "linear", "other",
                                  "window_attn"]
    assert order[-1] == "other"
    assert not {"scope", "category", "name"} & set(rules[-1])


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_names_a_reader_and_a_class_that_exist(name):
    spec = BENCH.layer_metric(name)
    entry = next(m for m in BENCH.manifest["per_layer"]
                 if m["name"] == name)
    assert entry["workloads"] == [CELL]
    for key in ("layer", "unit", "better", "source", "moves"):
        assert entry[key] == spec[key], key
    assert entry["moves"] == "request_p50_s"
    assert hasattr(BENCH.load("readers", spec["reader"]), "read")
    if spec["reader"] == "op_class_ms":
        classes = BENCH.read("op_classes", spec["args"]["classes"] + ".json")
        assert spec["args"]["cls"] in {r["class"] for r in classes["classes"]}
    if spec["reader"] == "bytes_util_steps":
        assert hasattr(BENCH.load("harness", spec["args"]["needs"]),
                       "decode_bytes")
        from stable_diffusion_webui_distributed_tpu.pipeline import expand

        assert spec["args"]["steps_per_call"] == expand.DECODE_STEPS


def test_the_reference_file_holds_both_limits_and_three_seeds():
    """What the chip gave (PR 45): three seeds at the timed positions, of
    the program and of every control that ended; the earlier readings at a
    quarter of them beside; both limits between their two readings at
    both sizes; the control that did not end named as such."""
    recorded = BENCH.read("reference", CONFIG + ".json")
    limit = recorded["tolerance_held_to_routing_relative_rms"]
    assert 0 < limit < recorded["tolerance_relative_rms"] < 1
    assert recorded["tolerance_reason"] \
        and recorded["tolerance_held_to_routing_reason"]
    assert recorded["device"]["platform"] == "tpu"
    assert recorded["latent"] == 2048 + 64 + 256
    held = "_vs_reference_held_to_the_programs_routing_relative_rms"
    ref = BENCH.reference(BENCH.config(CONFIG))
    controls = [name for name, _ in ref.CONTROLS]
    for key, positions in (("diagnostics", 2368),
                           ("diagnostics_at_592", 592)):
        seeds = recorded[key]
        assert len(seeds) >= 3
        assert len({d["seed"] for d in seeds}) == len(seeds)
        for reading in seeds:
            assert reading["positions"] == positions
            assert reading["sequences"] == 4
            assert reading["program_vs_reference_relative_rms"] \
                < recorded["tolerance_relative_rms"] \
                < reading["control_vs_reference_relative_rms"]
            assert reading["program_vs_reference_held_to_its_routing_"
                           "relative_rms"] < limit
            read = [name for name in controls if name + held in reading]
            assert "control" in read
            assert all(reading[name + held] > limit for name in read)
            # a control is read or named as failed, never passed over
            if positions == 2368:
                assert set(read) | set(reading.get("failed", {})) \
                    == set(controls)
    timed = recorded["diagnostics"]
    assert all({"windows_attend_all", "aliased_rings"} <= {
        name for name in controls if name + held in r} for r in timed)
    assert "DID NOT END" in recorded["what"]

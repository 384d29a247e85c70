"""The fourth prompt-expander cell (``sd15_lfm2_expand_solo``) rehearsed on
the CPU at tiny widths through the real ``run.py``, and the files it
brought: the components' leaf rules, the byte count against a hand count,
the op classes, the metric files. A rehearsal yields counts and
correctness, never a speed."""

import json
import os
import re
import types

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_lfm2_expand_solo"
CONFIG = "sd15_lfm2_expand"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_lfm2_expander")
NEW = ["lfm_expand_ms", "lfm_expand_prefill_ms", "lfm_expand_decode_ms",
       "lfm_linear_device_ms", "lfm_conv_device_ms", "lfm_attn_device_ms",
       "lfm_expert_device_ms", "lfm_other_device_ms",
       "lfm_decode_bytes_util", "lfm_expert_kernel_sites", "lfm_conv_sites"]
#: read from what only a TPU's trace or memory_stats() holds
CHIP_ONLY = {"peak_hbm_gib"} | {n for n in NEW if "device" in n
                                or "bytes" in n}
BENCH = files.Bench(rehearsal.REPO)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rehearsal.make_root(str(tmp_path_factory.mktemp("lfm")))
    rehearsal._rewrite(
        os.path.join(root, "benchmarks", "configs", CONFIG + ".json"),
        lambda c: c.update(factory=TINY_FACTORY, policy="F32"))

    def shorter(traffic):
        args = traffic["payload"]["alwayson_scripts"][
            "prompt expansion"]["args"][0]
        args.update(max_new_tokens=40, context_chunks=1,
                    instruction=" ".join(args["instruction"].split()[:30]))

    rehearsal._rewrite(
        os.path.join(root, "benchmarks", "traffic",
                     "sd15_512_expand384.json"), shorter)
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
        assert m["lfm_expand_ms"] > m["lfm_expand_decode_ms"] > 0
        assert m["lfm_expand_prefill_ms"] > 0
        # the other expanders' metrics list their own cells
        assert not {"expand_ms", "q3n_expand_ms", "x4_expand_ms",
                    "expert_kernel_sites", "lm_linear_device_ms"} & set(m)
        assert m["attention_tiled_sites"] == 0
        # the tiny preset's five conv mixers, traced once at one token; on
        # a CPU an expert layer takes the loop
        assert m["lfm_conv_sites"] == 5
        assert m["lfm_expert_kernel_sites"] == 0


def test_the_cell_is_the_other_expander_cells_request():
    cell = BENCH.cell(CELL)
    for name in ("sd15_expand_solo", "sd15_qwen3next_expand_solo",
                 "sd15_xing4_expand_solo"):
        other = BENCH.cell(name)
        assert cell["traffic"] == other["traffic"] == "sd15_512_expand384"
        for key in ("chips", "mesh", "server_env", "warmup_requests",
                    "trace"):
            assert cell[key] == other[key], key
    assert cell["config"] == CONFIG
    # the hash tokenizer maps a word to one id of the 65 536: the
    # traffic's token counts hold whatever the vocabulary
    from stable_diffusion_webui_distributed_tpu.models.tokenizer import (
        load_lm_tokenizer,
    )
    share = files.resolve_family(BENCH.config(CONFIG)).expander
    assert share.vocab == (0, 65536)
    tok = load_lm_tokenizer(None, *share.vocab)
    traffic = BENCH.traffic(cell["traffic"])
    args = traffic["payload"]["alwayson_scripts"]["prompt expansion"][
        "args"][0]
    prefix = [tok.bos] + tok.encode(args["instruction"])
    assert len(prefix) == 512
    lengths = [len(tok.encode(p)) for p in traffic["cycle"]["prompt"]]
    assert min(lengths) == 16 and max(lengths) == 64
    assert all(0 <= i < 65536 for i in prefix)
    assert args["max_new_tokens"] == 384 and args["ignore_eos"] is True


def test_the_configuration_holds_the_published_config_but_for_reduced():
    config = BENCH.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(json.loads(line) for line in fh if re.search(
            '"name": "LFM2-24B-A2B"', line))
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        elif key == "layer_types":      # the held layers' entries
            assert config[key] == value[:config["num_hidden_layers"]]
        else:
            assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 10
    assert len(config["assumed"]) >= 10 and config["counter"] is None
    for key in ("published", "held_here", "deployment", "assumed"):
        assert config[key], key
    assert "five chips" in config["deployment"]
    assert config["diffusion"] == BENCH.read("configs", "sd15.json")["model"]
    share = files.resolve_family(config).expander
    assert share.num_layers == config["num_hidden_layers"]
    # every expert and every id is held: the cut is in depth alone
    assert share.experts == (0, config["num_experts"]) == (0, 64)
    assert share.vocab == (0, config["vocab_size"]) == (0, 65536)
    kinds = {"conv": "conv", "full_attention": "full"}
    assert share.layer_types == tuple(
        kinds[kind] for kind in config["layer_types"])
    assert share.layer_types.count("conv") == 8
    assert share.dense_layers == tuple(range(config["num_dense_layers"]))
    assert share.rope_full.theta == config["rope_parameters"]["rope_theta"]
    assert share.rope_full.factor == 0 \
        and share.rope_full.partial_rotary_factor == 1.0
    for ours, theirs in (
            ("hidden_size", "hidden_size"),
            ("intermediate_size", "intermediate_size"),
            ("moe_intermediate_size", "moe_intermediate_size"),
            ("num_experts", "num_experts"),
            ("num_experts_per_tok", "num_experts_per_tok"),
            ("norm_topk_prob", "norm_topk_prob"),
            ("routed_scaling_factor", "routed_scaling_factor"),
            ("router_bias", "use_expert_bias"),
            ("num_kv_heads", "num_key_value_heads"),
            ("conv_taps", "conv_L_cache"), ("rms_norm_eps", "norm_eps")):
        assert getattr(share, ours) == config[theirs], ours
    assert share.num_heads_per_layer == (config["num_attention_heads"],) * 10
    assert share.head_dim * config["num_attention_heads"] \
        == config["hidden_size"]
    assert share.router_scoring == "sigmoid" and share.norm_topk_eps == 1e-6
    assert share.attn_gate == "none" and share.qk_norm
    assert share.shared_expert_intermediate_size == 0
    assert config["conv_bias"] is False


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
        == config["parameters_millions"]["expander_share"] == 5401
    assert round(total * 2 / 2 ** 30, 2) == 10.06
    assert round((total / 1e6 + config["parameters_millions"]["sd15"])
                 * 2e6 / 2 ** 30, 2) == 12.05
    rules = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        rules[name] = (components.leaf_rule(name, leaf.shape)
                       or weights.leaf_rule(name, leaf.shape)) \
            + (tuple(leaf.shape),)
    # every tap alike: the kept rows' two weigh as much as the current
    assert rules["layers_0/short_conv/conv_kernel"] \
        == ("draw", 1.0, (3, 2048))
    assert rules["layers_0/short_conv/in_proj/kernel"] \
        == ("draw", (3 / 2048) ** 0.5, (2048, 6144))
    assert rules["layers_9/short_conv/out_proj/kernel"][2] == (2048, 2048)
    assert rules["layers_2/mlp/e_score_correction_bias"] \
        == ("draw", 0.1 * 3 ** 0.5, (64,))
    assert rules["layers_2/mlp/router"] \
        == ("draw", (3 / 2048) ** 0.5, (2048, 64))
    assert rules["layers_2/attn/q_norm/scale"] == ("ones", 0.0, (64,))
    assert rules["layers_2/attn/k_proj/kernel"][2] == (2048, 512)
    assert "layers_2/attn/g_proj/kernel" not in rules       # no gate
    assert rules["layers_1/mlp/up_proj/kernel"][2] == (2048, 11776)
    assert "layers_1/mlp/router" not in rules       # the second dense layer
    assert not any("shared_expert" in name for name in rules)
    assert "layers_2/short_conv/in_proj/kernel" not in rules    # attention
    assert rules["lm_head/kernel"][2] == (2048, 65536)
    # each stacked expert kernel is a draw of its own
    big = [r for r in rules.values() if len(r[2]) == 3]
    assert len(big) == 24 and len(set(big)) == 24
    assert {r[2] for r in big} == {(64, 2048, 1536), (64, 1536, 2048)}


def test_bytes_a_decoded_token_needs_against_a_hand_count():
    count = BENCH.load("harness", "bytes_lfm2")
    cfg = files.resolve_family(BENCH.config(CONFIG)).expander
    d = 2048
    conv = (d * 6144 + 3 * d + d * d) * 2
    attn = (2 * d * d + 2 * d * 512) * 2
    dense = 3 * d * 11776 * 2
    moe = (d * 64 + 64) * 2
    head = (d + d * 65536) * 2
    assert count.conv_layer_bytes(cfg) == conv
    assert round(conv / 1e6, 2) == 33.57
    assert count.attention_layer_bytes(cfg, 2) == attn
    assert round(attn / 1e6, 2) == 20.97
    assert count.fixed_bytes(cfg) \
        == 8 * conv + 2 * attn + 2 * dense + 8 * moe + head
    assert round(count.fixed_bytes(cfg) / 1e6, 1) == 870.4
    assert count.expert_bytes(cfg) == 3 * d * 1536 * 2 == 18874368
    # two rows of 2048 float32 a conv layer, read and written
    assert count.kept_rows_bytes(cfg) == 2 * 8 * 2 * d * 4 == 262144
    # 2 048 B of keys and values a position a layer, two layers
    assert count.cache_bytes(cfg, 0) == 2 * 2048
    assert count.cache_bytes(cfg, 959) == 2 * 960 * 2048
    one = count.decode_bytes(cfg, 600, 1, 32.0)
    assert one == count.fixed_bytes(cfg) + count.kept_rows_bytes(cfg) \
        + 32 * count.expert_bytes(cfg) + count.cache_bytes(cfg, 600)
    # 1 474 MB of weights a token, 41 % of them chosen experts
    assert 1.476e9 < one < 1.478e9
    assert round(32 * count.expert_bytes(cfg) / (
        count.fixed_bytes(cfg) + 32 * count.expert_bytes(cfg)), 2) == 0.41
    assert count.decode_bytes(cfg, 600, 2, 32.0) \
        == one + count.decode_bytes(cfg, 601, 1, 32.0)


def test_bytes_util_reads_the_programs_counter():
    reader = BENCH.load("readers", "bytes_util")
    spec = BENCH.layer_metric("lfm_decode_bytes_util")
    traffic = BENCH.traffic("sd15_512_expand384")
    payload = dict(traffic["payload"], prompt=traffic["cycle"]["prompt"][0])
    status = lambda tokens, routed: {"serving": {"expander": {   # noqa: E731
        "tokens_prefilled": tokens, "decode_steps": 0,
        "expert_tokens": [[routed, 0], [0, 0]]}}}
    context = {
        "records": [types.SimpleNamespace(traced=True, payload=payload)],
        "trace": {"modules": {"jit_expand_decode_chunk": 1.0}},
        "family": files.resolve_family(BENCH.config(CONFIG)),
        "status_before": status(100, 50), "status_after": status(200, 3250),
        "chips": 1, "peak": {"hbm_bytes_per_s": 819e9}, "bench": BENCH,
    }
    count = BENCH.load("harness", "bytes_lfm2")
    cfg = context["family"].expander
    want = 100 * count.decode_bytes(cfg, 512 + 16, 384, 32.0) / 819e9
    assert reader.read(context, **spec["args"]) == pytest.approx(want)
    assert 65 < want < 75       # 1.48 GB a token, 384 tokens, in a second
    assert reader.read(dict(context, trace=None), **spec["args"]) is None


def test_the_sites_metrics_read_nothing_from_a_program_without_them():
    """The parent's /internal/status has no conv_mixers: the metric is left
    out of its line and nothing raises."""
    reader = BENCH.load("readers", "status_value")
    spec = BENCH.layer_metric("lfm_conv_sites")
    old = {"status_before": {"serving": {"expander": {
        "expert_products": {"kernel": 18, "loop": 0, "grouped": 36}}}}}
    assert reader.read(old, **spec["args"]) is None
    new = {"status_before": {"serving": {"expander": {
        "conv_mixers": {"step": 8, "chunk": 16},
        "expert_products": {"kernel": 8, "loop": 0, "grouped": 16}}}}}
    assert reader.read(new, **spec["args"]) == 8.0
    spec = BENCH.layer_metric("lfm_expert_kernel_sites")
    assert reader.read(new, **spec["args"]) == 8.0


@pytest.mark.parametrize("classes", ["lfm2_decode", "lfm2_prefill"])
def test_op_classes_partition_by_flax_module(classes):
    reader = BENCH.load("readers", "op_class_ms")
    spec = BENCH.read("op_classes", classes + ".json")
    rules = spec["classes"]
    assert spec["module"] == {"lfm2_decode": "jit_expand_decode_chunk",
                              "lfm2_prefill": "jit_expand_prefill"}[classes]
    base = "jit(f)/jit(main)/while/body/DecoderLM/"
    cases = {
        "layers_0/short_conv/in_proj/dot_general": "linear",
        "layers_1/short_conv/out_proj/dot_general": "linear",
        "layers_2/attn/q_proj/dot_general": "linear",
        "layers_2/attn/k_proj/dot_general": "linear",
        "layers_6/attn/v_proj/dot_general": "linear",
        "layers_6/attn/o_proj/dot_general": "linear",
        "layers_0/mlp/up_proj/dot_general": "linear",
        "lm_head/dot_general": "linear",
        "layers_0/short_conv/mul": "conv",
        "layers_3/short_conv/concatenate": "conv",
        "layers_9/short_conv/dynamic_slice": "conv",
        "layers_9/short_conv/add": "conv",
        "layers_2/attn/q_norm/rsqrt": "attn",
        "layers_2/attn/k_norm/rsqrt": "attn",
        "layers_6/attn/exp": "attn",
        "layers_6/attn/dynamic_update_slice": "attn",
        "layers_2/mlp/pallas_call": "expert",
        "layers_9/mlp/top_k": "expert",
        "layers_5/mlp/logistic": "expert",
        "layers_0/mlp/mul": "other",                 # a dense layer's SiLU
        "layers_1/mlp/logistic": "other",
        "layers_1/input_norm/rsqrt": "other",
        "layers_4/post_attention_norm/rsqrt": "other",
        "embed_tokens/gather": "other",
        "norm/rsqrt": "other",
    }
    for scope, want in cases.items():
        row = {"scope": base + scope, "category": "x", "name": "fusion.1"}
        assert reader.classify(row, rules) == want, scope
    # XLA's asynchronous copies carry no flax scope: they stream the
    # Linears' kernels ahead of their products
    loose = {"scope": "jit(expand_decode_chunk)/while", "category": "x"}
    assert reader.classify(dict(loose, name="copy-done.7"), rules) \
        == "linear"
    assert reader.classify(dict(loose, name="slice-start.2"), rules) \
        == "linear"
    assert reader.classify(dict(loose, name="copy.3"), rules) == "other"
    assert reader.classify(
        {"scope": base + "layers_0/short_conv/x", "category": "x",
         "name": "copy-done.1"}, rules) == "conv"
    order = [r["class"] for r in rules]
    assert sorted(set(order)) == ["attn", "conv", "expert", "linear",
                                  "other"]
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
    if spec["reader"] == "bytes_util":
        assert hasattr(BENCH.load("harness", spec["args"]["needs"]),
                       "decode_bytes")
        from stable_diffusion_webui_distributed_tpu.pipeline import expand

        assert spec["args"]["steps_per_call"] == expand.DECODE_STEPS


def test_the_reference_file_holds_both_limits_and_three_seeds():
    recorded = BENCH.read("reference", CONFIG + ".json")
    assert 0 < recorded["tolerance_held_to_routing_relative_rms"] \
        < recorded["tolerance_relative_rms"] < 1
    assert recorded["tolerance_reason"] \
        and recorded["tolerance_held_to_routing_reason"]
    seeds = recorded["diagnostics"]
    assert len(seeds) >= 3 and len({d["seed"] for d in seeds}) == len(seeds)
    held = "_vs_reference_held_to_the_programs_routing_relative_rms"
    for reading in seeds:
        assert reading["program_vs_reference_relative_rms"] \
            < recorded["tolerance_relative_rms"]
        assert reading["program_vs_reference_held_to_its_routing_"
                       "relative_rms"] \
            < recorded["tolerance_held_to_routing_relative_rms"]
        # each control fails at least one limit
        for control in ("control", "dropped_kept_rows", "bf16_taps"):
            assert (reading[control + "_vs_reference_relative_rms"]
                    > recorded["tolerance_relative_rms"]
                    or reading[control + held]
                    > recorded["tolerance_held_to_routing_relative_rms"])

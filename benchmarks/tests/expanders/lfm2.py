"""The fourth prompt-expander cell (``sd15_lfm2_expand_solo``) rehearsed on
the CPU at tiny widths through the real ``run.py``, and the files it
brought: the components' leaf rules, the op classes, the metric files (a step's bytes by ``harness/bytes_lm.py``
against a hand count from the published widths). A rehearsal yields counts and
correctness, never a speed."""

import functools
import json
import re

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_lfm2_expand_solo"
CONFIG = "sd15_lfm2_expand"
TRAFFIC = "sd15_512_expand384"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_lfm2_expander")
BENCH = files.Bench(rehearsal.REPO)


def check_the_cell_is_the_other_expander_cells_request():
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


def check_the_configuration_holds_the_published_config_but_for_reduced():
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


def check_the_leaf_rules_and_the_shares_parameters():
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


def check_the_sites_metrics_read_nothing_from_a_program_without_them():
    """The parent's /internal/status has no conv_mixers: the metric is left
    out of its line and nothing raises."""
    reader = BENCH.load("readers", "status_value")
    spec = BENCH.layer_metric("conv_sites")
    old = {"status_before": {"serving": {"expander": {
        "expert_products": {"kernel": 18, "loop": 0, "grouped": 36}}}}}
    assert reader.read(old, **spec["args"]) is None
    new = {"status_before": {"serving": {"expander": {
        "conv_mixers": {"step": 8, "chunk": 16},
        "expert_products": {"kernel": 8, "loop": 0, "grouped": 16}}}}}
    assert reader.read(new, **spec["args"]) == 8.0
    spec = BENCH.layer_metric("expert_kernel_sites")
    assert reader.read(new, **spec["args"]) == 8.0


def check_op_classes_partition_by_flax_module(classes):
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


def check_the_reference_file_holds_both_limits_and_three_seeds():
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


def traced(m):
    """What the traced rehearsal's per-layer metrics must say."""
    # the tiny preset's five conv mixers, traced once at one token; on
    # a CPU an expert layer takes the loop
    assert m["conv_sites"] == 5
    assert m["expert_kernel_sites"] == 0


def _walker_and_share():
    return (BENCH.load("harness", "bytes_lm"),
            files.resolve_family(BENCH.config(CONFIG)).expander)


def _rows(count, cfg, forked_at, step, sequences=1):
    """The key, value and latent rows one step needs, all layers."""
    terms = count.step_bytes(cfg, forked_at, step, 0.0, sequences)
    return terms["rows_shared"] + terms["rows_own"]


def check_bytes_a_decoded_token_needs_against_a_hand_count():
    """From the published widths: hidden 2 048; a conv layer's in_proj to
    three times the width, 3 taps, out_proj; attention of 32 heads of 64
    over 8 key heads; a router of 64 with its selection bias."""
    count, cfg = _walker_and_share()
    d = 2048
    conv = (d * 6144 + 3 * d + d * d) * 2
    attn = (2 * d * d + 2 * d * 512) * 2
    dense = 3 * d * 11776 * 2
    moe = (d * 64 + 64) * 2
    head = d * 65536 * 2
    assert count.mixer_bytes(cfg, 0) == conv and round(conv / 1e6, 2) == 33.57
    assert count.mixer_bytes(cfg, 2) == attn and round(attn / 1e6, 2) == 20.97
    assert count.mlp_bytes(cfg, 0) == dense and count.mlp_bytes(cfg, 2) == moe
    assert count.fixed_bytes(cfg, 1) \
        == 8 * conv + 2 * attn + 2 * dense + 8 * moe + head + d * 2
    assert round(count.fixed_bytes(cfg, 1) / 1e6, 1) == 870.4
    assert count.expert_bytes(cfg) == 3 * d * 1536 * 2 == 18874368
    # two rows of 2048 float32 a conv layer, read and written
    assert count.state_bytes(cfg, "conv") == 2 * d * 4
    kept = count.step_bytes(cfg, 600, 0, 0.0, 1)["states"]
    assert kept == 2 * 8 * 2 * d * 4 == 262144
    # 2 048 B of keys and values a position a layer, two layers
    assert count.row_bytes(cfg, "full") == 2 * 8 * 64 * 2 == 2048
    assert count.row_bytes(cfg, "conv") == 0
    assert _rows(count, cfg, 0, 0) == 2 * 2048
    assert _rows(count, cfg, 959, 0) == 2 * 960 * 2048
    one = count.decode_bytes(cfg, 600, 1, 32.0)
    assert one == count.fixed_bytes(cfg, 1) + kept \
        + 32 * count.expert_bytes(cfg) + 2 * 601 * 2048
    # 1 474 MB of weights a token, 41 % of them chosen experts
    assert 1.476e9 < one < 1.478e9
    assert round(32 * count.expert_bytes(cfg) / (
        count.fixed_bytes(cfg, 1) + 32 * count.expert_bytes(cfg)), 2) == 0.41
    assert count.decode_bytes(cfg, 600, 2, 32.0) \
        == one + count.decode_bytes(cfg, 601, 1, 32.0)


CHECKS = [check_bytes_a_decoded_token_needs_against_a_hand_count,
          check_the_cell_is_the_other_expander_cells_request,
          check_the_configuration_holds_the_published_config_but_for_reduced,
          check_the_leaf_rules_and_the_shares_parameters,
          check_the_sites_metrics_read_nothing_from_a_program_without_them,
          functools.partial(check_op_classes_partition_by_flax_module, 'lfm2_decode'),
          functools.partial(check_op_classes_partition_by_flax_module, 'lfm2_prefill'),
          check_the_reference_file_holds_both_limits_and_three_seeds]

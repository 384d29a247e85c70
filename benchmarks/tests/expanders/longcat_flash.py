"""The eleventh prompt-expander cell (``sd15_longcat_flash_expand_b4``)
rehearsed on the CPU at tiny widths through the real ``run.py``, and the
files it brought: the configuration against the catalog's row, the leaf
rules, the readers, the op classes, the metric files (a step's bytes by
``harness/bytes_lm.py`` against a hand count from the published widths). A
rehearsal yields counts and correctness, never a speed."""

import functools
import json
import math
import re

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_longcat_flash_expand_b4"
CONFIG = "sd15_longcat_flash_expand"
TRAFFIC = "sd15_2048_b4_expand256"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_longcat_flash_expander")
BENCH = files.Bench(rehearsal.REPO)


def check_the_traffic_file_is_the_sibling_cells_unchanged():
    cell = BENCH.cell(CELL)
    for sibling in ("sd15_mellum2_expand_b4", "sd15_kanana2_expand_b4",
                    "sd15_gigachat35_expand_b4"):
        other = BENCH.cell(sibling)
        assert cell["traffic"] == TRAFFIC == other["traffic"]
        for key in ("server_env", "warmup_requests", "trace", "mesh"):
            assert cell[key] == other[key], key
    assert cell["config"] == CONFIG and cell["chips"] == 1
    why = BENCH.read("workloads", CELL + ".json")["why"]
    for said in ("1/32 of the deployment's expert load", "seven times",
                 "outweigh their deployment share", "no exchange"):
        assert said in why, said
    entry = next(w for w in BENCH.manifest["workloads"]
                 if w["name"] == CELL)
    assert "1/32" in entry["why"] and len(entry["why"]) <= 200
    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.models.tokenizer import (
        load_lm_tokenizer,
    )
    from stable_diffusion_webui_distributed_tpu.pipeline import expand

    share = files.resolve_family(BENCH.config(CONFIG)).expander
    tok = load_lm_tokenizer(None, *share.vocab)
    traffic = BENCH.traffic(TRAFFIC)
    args = traffic["payload"]["alwayson_scripts"]["prompt expansion"][
        "args"][0]
    prefix = [tok.bos] + tok.encode(args["instruction"])
    # every id from the held eighth of the vocabulary
    assert len(prefix) == 2048 and all(0 <= i < 16384 for i in prefix)
    lengths = [len(tok.encode(p)) for p in traffic["cycle"]["prompt"]]
    assert min(lengths) == 16 and max(lengths) == 64
    assert args["max_new_tokens"] == 256 and args["ignore_eos"] is True
    assert traffic["payload"]["batch_size"] == 4
    chunks = -(-(256 - 1) // expand.DECODE_STEPS)
    assert kv.capacity_for(2048 + 64 + chunks * expand.DECODE_STEPS) == 2560
    reference = BENCH.reference(BENCH.config(CONFIG))
    assert reference.TIMED_POSITIONS == 2048 + 64 + 256
    assert reference.split(2368) == (2048, 64, 256)
    assert reference.SEQUENCES == 4


def check_the_configuration_holds_the_published_config_but_for_reduced():
    config = BENCH.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(json.loads(line) for line in fh if re.search(
            '"name": "LongCat-Flash-Chat"', line))
    assert config["source"] == row["source_url"]
    entry = next(c for c in BENCH.manifest["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    assert len(entry["why"]) <= 200
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 16, 16384)
    # the guide's floors: four periods (no leading dense layer), at least
    # 8 experts, at least an eighth of the vocabulary
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["n_routed_experts"] * 32 \
        == config["published"]["n_routed_experts"]
    assert "32 chips share each layer" in config["deployment"]
    assert "1/32 of the deployment's expert load" in config["deployment"]
    listed = " ".join(config["assumed"])
    for reading in ("shortcut-connected", "mla_scale_q_lora",
                    "mla_scale_kv_lora", "3.4641", "NOT renormalised",
                    "identity", "rotate_half", "0.5 / 768",
                    "multi-token-prediction", "untied", "variance 1",
                    "float32", "hash fallback"):
        assert reading in listed, reading
    for key in ("published", "held_here", "deployment", "assumed"):
        assert config[key], key
    assert config["counter"] is None
    assert config["components"] == "unet_clip_vae_lm_longcat_flash"
    assert config["diffusion"] == BENCH.read("configs", "sd15.json")["model"]
    # no width is changed: the program's share has the published ones
    share = files.resolve_family(config).expander
    assert (share.hidden_size, share.intermediate_size,
            share.shared_expert_intermediate_size,
            share.moe_intermediate_size, share.num_experts_per_tok) \
        == (config["hidden_size"], config["ffn_hidden_size"],
            config["ffn_hidden_size"], config["expert_ffn_hidden_size"],
            config["moe_topk"]) == (6144, 12288, 12288, 2048, 12)
    assert (share.q_lora_rank, share.kv_lora_rank, share.qk_nope_head_dim,
            share.qk_rope_head_dim, share.v_head_dim) \
        == (config["q_lora_rank"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"]) == (1536, 512, 128, 64, 128)
    # the router keeps its published width: real + zero-compute outputs
    assert share.num_experts == config["published"]["n_routed_experts"] \
        + config["zero_expert_num"] == 768
    assert share.zero_experts == config["zero_expert_num"] == 256
    assert share.real_experts == 512 and share.experts == (0, 16)
    assert share.vocab == (0, 16384)
    assert share.routed_scaling_factor == config["routed_scaling_factor"]
    assert share.rms_norm_eps == config["rms_norm_eps"]
    assert share.rope_full.theta == config["rope_theta"]
    assert share.rope_full.factor == 0 and not share.rope_full.interleaved
    assert share.router_scoring == "softmax" and share.router_bias
    assert not share.norm_topk_prob and share.attn_gate == "none"
    # one published layer is two entries: an expert layer, then a dense one
    assert share.num_layers == 2 * config["num_layers"] == 8
    assert share.layer_types == ("latent",) * 8
    assert share.num_heads_per_layer == (config["num_attention_heads"],) * 8
    assert share.dense_layers == (1, 3, 5, 7) and share.moe_shortcut
    assert share.expert_layers == (0, 2, 4, 6)
    assert share.latent_q_scale == 2.0
    assert share.latent_kv_scale == pytest.approx(12 ** 0.5)
    assert share.latent_softmax_scale == 192 ** -0.5


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
        == config["parameters_millions"]["expander_share"] == 5173
    assert round(total * 2 / 1e9, 2) == 10.35
    assert round((total / 1e6 + config["parameters_millions"]["sd15"])
                 * 2e6 / 2 ** 30, 2) == 11.62
    rules = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        rules[name] = (components.leaf_rule(name, leaf.shape)
                       or weights.leaf_rule(name, leaf.shape)) \
            + (tuple(leaf.shape),)
    assert rules["layers_0/mlp/router"] \
        == ("draw", (3 / 6144) ** 0.5, (6144, 768))
    # half a typical softmax score over 768 outputs
    assert rules["layers_2/mlp/e_score_correction_bias"] \
        == ("draw", 0.5 / 768, (768,))
    # the table at variance 1: a token's row weighs what a sublayer adds
    assert rules["embed_tokens/embedding"] == ("draw", 3 ** 0.5,
                                               (16384, 6144))
    assert rules["lm_head/kernel"][2] == (6144, 16384)
    for layer in range(8):
        attn = f"layers_{layer}/attn/"
        assert rules[attn + "q_a_proj/kernel"][2] == (6144, 1536)
        assert rules[attn + "q_b_proj/kernel"][2] == (1536, 64 * 192)
        assert rules[attn + "kv_a_proj_with_mqa/kernel"][2] == (6144, 576)
        assert rules[attn + "kv_b_proj/kernel"][2] == (512, 64 * 256)
        assert rules[attn + "o_proj/kernel"][2] == (8192, 6144)
    # drawn at 1/scale times the fan-in's deviation: the SCALED queries,
    # keys and values are of order one
    assert rules["layers_3/attn/q_b_proj/kernel"][1] == pytest.approx(
        math.sqrt(3 / 1536) / 2.0, rel=1e-6)
    assert rules["layers_3/attn/kv_b_proj/kernel"][1] == pytest.approx(
        math.sqrt(3 / 512) / 12 ** 0.5, rel=1e-6)
    assert rules["layers_3/attn/q_a_proj/kernel"][1] == pytest.approx(
        math.sqrt(3 / 6144), rel=1e-6)
    # every 2-D kernel a draw of its own: no stacked 2.4 GB draw
    flat_2d = [r for n, r in rules.items() if n.endswith("/kernel")
               and len(r[2]) == 2 and n.split("/")[0].startswith("layers_")]
    assert len(set(flat_2d)) == len(flat_2d) == 8 * 5 + 8 * 3
    assert rules["layers_1/mlp/gate_proj/kernel"][2] == (6144, 12288)
    assert rules["layers_0/mlp/shared_expert/down_proj/kernel"][2] \
        == (12288, 6144)
    assert "layers_1/mlp/router" not in rules
    assert not any(part in name for name in rules for part in (
        "attn_hc", "g_proj", "shared_expert_gate", "/q_proj/"))
    # each stacked expert kernel is a draw of its own: 16 held, no leaf for
    # an identity expert
    big = [r for r in rules.values() if len(r[2]) == 3]
    assert len(big) == 12 and len(set(big)) == 12
    assert {r[2] for r in big} == {(16, 6144, 2048), (16, 2048, 6144)}


def _status(steps, decoded, read, attended=0, rows=0, zero=0):
    return {"serving": {"expander": {
        "tokens_prefilled": 0, "decode_steps": steps,
        "tokens_decoded": decoded, "experts_read": read, "sequences": 0,
        "rows_attended": attended, "rows_read": rows,
        "zero_expert_picks": zero,
        "expert_tokens": [[0, 0], [0, 0]]}}}


def check_the_ratio_and_value_metrics_read_the_status_or_nothing():
    ratio = BENCH.load("readers", "status_ratio")
    context = {
        "family": files.resolve_family(BENCH.config(CONFIG)),
        "status_before": _status(256, 1024, 40000, 10, 10, 500),
        "status_after": _status(768, 3072, 40000 + 512 * 4, 10 + 34,
                                10 + 10, 500 + 2048 * 4 * 4.25)}
    assert ratio.read(context, **BENCH.layer_metric(
        "lm_tokens_per_step")["args"]) == 4.0
    # over the configuration's own four routers
    assert ratio.read(context, **BENCH.layer_metric(
        "experts_read_per_step")["args"]) == pytest.approx(1.0)
    assert ratio.read(context, **BENCH.layer_metric(
        "zero_expert_picks_per_token")["args"]) == pytest.approx(4.25)
    assert ratio.read(context, **BENCH.layer_metric(
        "fork_rows_attended_per_row_read")["args"]) == pytest.approx(3.4)
    # a program without the counter (the parent): nothing, and no raise
    bare = {"serving": {"expander": {"tokens_decoded": 5}}}
    assert ratio.read(
        dict(context, status_before=bare, status_after=bare),
        **BENCH.layer_metric("zero_expert_picks_per_token")["args"]) is None
    value = BENCH.load("readers", "status_value")
    status = {"serving": {
        "attention": {"latent_forked": 8, "xla": 3},
        "expander": {"expert_products": {"kernel": 4},
                     "moe_shortcuts": {"recurrent": 0, "chunked": 8,
                                       "recurrent_forked": 4}}}}
    assert value.read({"status_before": status}, **BENCH.layer_metric(
        "latent_forked_sites")["args"]) == 8
    assert value.read({"status_before": status}, **BENCH.layer_metric(
        "expert_kernel_sites")["args"]) == 4
    assert value.read({"status_before": status}, **BENCH.layer_metric(
        "moe_shortcut_sites")["args"]) == 4
    bare = {"serving": {"attention": {"xla": 3}, "expander": {}}}
    assert value.read({"status_before": bare}, **BENCH.layer_metric(
        "moe_shortcut_sites")["args"]) is None
    for name in ("zero_expert_picks_per_token", "moe_shortcut_sites"):
        entry = next(m for m in BENCH.manifest["per_layer"]
                     if m["name"] == name)
        assert entry["workloads"] == [CELL]


def check_op_classes_partition_by_flax_module(classes):
    reader = BENCH.load("readers", "op_class_ms")
    spec = BENCH.read("op_classes", classes + ".json")
    rules = spec["classes"]
    assert spec["module"] == {
        "longcat_flash_decode": "jit_expand_decode_chunk",
        "longcat_flash_prefill": "jit_expand_prefill"}[classes]
    base = "jit(f)/jit(main)/while/body/DecoderLM/"
    cases = {
        "layers_0/attn/q_a_proj/dot_general": "linear",
        "layers_3/attn/q_b_proj/dot_general": "linear",
        "layers_3/attn/kv_a_proj_with_mqa/dot_general": "linear",
        "layers_6/attn/o_proj/dot_general": "linear",
        "layers_1/mlp/gate_proj/dot_general": "linear",
        # the first dense SwiGLU is the expert layer's shared expert
        "layers_4/mlp/shared_expert/down_proj/dot_general": "linear",
        "lm_head/dot_general": "linear",
        "layers_3/attn/exp": "latent",
        "layers_7/attn/kv_b_proj/reshape": "latent",
        "layers_0/attn/kv_a_norm/rsqrt": "latent",
        "layers_0/attn/q_a_norm/rsqrt": "latent",
        "layers_5/attn/mul": "latent",              # a latent scale
        "layers_7/attn/dynamic_update_slice": "latent",
        "layers_0/mlp/top_k": "expert",
        "layers_2/mlp/dot_general": "expert",       # the router's product
        "layers_6/mlp/pallas_call": "expert",
        "layers_4/mlp/exp": "expert",               # the softmax
        "layers_4/mlp/mul": "expert",               # the identity term
        "layers_10/mlp/top_k": "expert",
        "layers_1/mlp/logistic": "other",      # a dense layer's SiLU
        "layers_11/mlp/logistic": "other",
        "layers_2/mlp/shared_expert/logistic": "other",
        "layers_1/input_norm/rsqrt": "other",
        "layers_3/add": "other",                # where the routed sum lands
        "embed_tokens/gather": "other",
        "norm/rsqrt": "other",
    }
    for scope, want in cases.items():
        row = {"scope": base + scope, "category": "x", "name": "fusion.1"}
        assert reader.classify(row, rules) == want, scope
    loose = {"scope": "jit(expand_decode_chunk)/while", "category": "x"}
    assert reader.classify(dict(loose, name="copy-done.7"), rules) \
        == "linear"
    assert reader.classify(dict(loose, name="copy.3"), rules) == "other"
    order = [r["class"] for r in rules]
    assert sorted(set(order)) == ["expert", "latent", "linear", "other"]
    assert order[-1] == "other"
    assert not {"scope", "category", "name"} & set(rules[-1])


def check_the_reference_file_holds_both_limits_and_the_wrong_programs():
    """What the chip gave (PR 67): the program under both limits at every
    seed at the timed positions, each of the six wrong programs over
    both; and the first seeding's readings, which told nothing apart."""
    recorded = BENCH.read("reference", CONFIG + ".json")
    limit = recorded["tolerance_relative_rms"]
    held_limit = recorded["tolerance_held_to_routing_relative_rms"]
    assert 0 < held_limit < limit < 1
    assert recorded["tolerance_reason"] \
        and recorded["tolerance_held_to_routing_reason"]
    assert recorded["device"]["platform"] == "tpu"
    assert recorded["latent"] == 2048 + 64 + 256
    assert recorded["program_vs_reference_relative_rms"] < limit \
        < recorded["control_vs_reference_relative_rms"]
    assert recorded["passed"] is True
    reference = BENCH.reference(BENCH.config(CONFIG))
    controls = [name for name, _ in reference.CONTROLS]
    assert controls == ["control", "no_identity_term", "no_held_experts",
                        "no_shortcut", "no_q_scale", "no_kv_scale"]
    seeds = recorded["diagnostics"]
    assert len(seeds) >= 3
    assert len({d["seed"] for d in seeds}) == len(seeds)
    for reading in seeds:
        assert reading["positions"] == 2368 and reading["sequences"] == 4
        assert reading["program_vs_reference_relative_rms"] < limit
        assert reading["program_vs_reference_held_to_its_routing_"
                       "relative_rms"] < held_limit
        for name in controls:
            assert reading[name + "_vs_reference_relative_rms"] > limit, name
            assert reading[name + reference.HELD] > held_limit, name
            assert reading[name + "_" + reference.DIFFER] \
                > reading[reference.DIFFER], name
        assert 0 < reading["selection_bias_changes_the_choice_share"] < 1
        assert 3 < reading["picks_a_pair"]["identity"] < 5
    # at variance 1/fan_in under the two scales nothing was told apart
    first = recorded["seeding_first_tried"]["readings"]
    assert first["program_vs_reference_relative_rms"] > 0.3
    assert abs(first["no_held_experts_vs_reference_relative_rms"]
               - first["program_vs_reference_relative_rms"]) < 0.01


def traced(m):
    """What the traced rehearsal's per-layer metrics must say."""
    assert m["expand_fork_ms"] > 0
    # four images a step: 40 tokens a sequence over two chunks of 32
    assert m["lm_tokens_per_step"] == pytest.approx(4 * 40 / 64)
    # the tiny preset has 4 latent entries, 2 of them expert layers of 16
    # experts and 8 identity experts, 4 a token, 4 of the 16 held
    assert m["latent_forked_sites"] == 4
    assert m["moe_shortcut_sites"] == 2
    assert m["expert_kernel_sites"] == 0      # a CPU
    assert 0 < m["experts_read_per_step"] <= 4
    # 4 x 8/24 = 1.33 a token a router under even routing (the steps' rows
    # over the tokens made: 64 steps over 40 tokens a sequence)
    assert 0.5 < m["zero_expert_picks_per_token"] < 4
    assert 1.5 < m["fork_rows_attended_per_row_read"] < 4


def _walker_and_share():
    return (BENCH.load("harness", "bytes_lm"),
            files.resolve_family(BENCH.config(CONFIG)).expander)


def check_bytes_a_forked_step_needs_against_a_hand_count():
    """From the published widths: hidden 6 144, a query latent of 1 536, a
    key-value latent of 512 + 64 rotated, 64 heads of 128 + 64 and 128, two
    dense SwiGLUs of 12 288 and one router of 768 outputs a published
    layer, experts of 2 048."""
    count, cfg = _walker_and_share()
    d = 6144
    attn = (d * 1536 + 1536 * 64 * 192 + d * 576 + 512 * 64 * 256
            + 8192 * d) * 2
    assert attn == 2 * 90_570_752
    dense = 3 * d * 12288 * 2
    router = (d * 768 + 768) * 2
    head = d * 16384 * 2
    for layer in range(8):
        assert count.mixer_bytes(cfg, layer) == attn
    # an even entry: the router, its bias and the FIRST dense SwiGLU (the
    # shared-expert spelling); an odd one the second
    assert count.mlp_bytes(cfg, 0) == router + dense
    assert count.mlp_bytes(cfg, 1) == dense
    assert count.head_bytes(cfg) == head
    fixed = 8 * attn + 8 * dense + 4 * router + head
    assert count.fixed_bytes(cfg, 4) == fixed + 4 * d * 2
    # 5.312 GB: attention 1.449, dense MLPs 3.624, routers 0.038, head 0.201
    assert round(fixed / 1e9, 3) == 5.312
    assert round(8 * attn / 1e9, 3) == 1.449
    assert round(8 * dense / 1e9, 3) == 3.624
    assert round(4 * router / 1e9, 3) == 0.038
    assert round(head / 1e9, 3) == 0.201
    # one published layer's fixed weights: 638.8 M parameters
    assert round((2 * attn + 2 * dense + router - 768 * 2) / 2 / 1e6, 1) \
        == 638.8
    # a real expert: 75.5 MB; an identity expert: nothing
    assert count.expert_bytes(cfg) == 3 * d * 2048 * 2 == 75_497_472
    # 1 152 B a position a sublayer, eight sublayers that keep latents
    assert count.row_bytes(cfg, "latent") == 576 * 2 == 1152
    terms = count.step_bytes(cfg, 0, 0, 0.0, 1)
    assert terms["rows_shared"] + terms["rows_own"] == 8 * 1152
    assert terms["states"] == 0
    # a step of four under even routing: 48 picks over 768 outputs, 16 held
    even = 16 * (1 - (1 - 12 / 768) ** 4)
    assert round(even, 2) == 0.98
    step = count.decode_bytes(cfg, 2112, 1, 4 * even, 4)
    assert step == pytest.approx(
        count.fixed_bytes(cfg, 4) + 4 * even * 75_497_472
        + (2112 + 4) * 8 * 1152)
    assert 5.60e9 < step < 5.64e9
    assert round(4 * even * 75_497_472 / 1e9, 3) == 0.295
    # the shared rows once a step, a sequence's own once each: 256 steps
    whole = count.decode_bytes(cfg, 2112, 256, 4 * even, 4)
    rows = 256 * 2112 + 4 * 256 * 257 / 2
    assert whole == pytest.approx(
        256 * (count.fixed_bytes(cfg, 4) + 4 * even * 75_497_472)
        + rows * 8 * 1152)


CHECKS = [check_bytes_a_forked_step_needs_against_a_hand_count,
          check_the_traffic_file_is_the_sibling_cells_unchanged,
          check_the_configuration_holds_the_published_config_but_for_reduced,
          check_the_leaf_rules_and_the_shares_parameters,
          check_the_ratio_and_value_metrics_read_the_status_or_nothing,
          functools.partial(check_op_classes_partition_by_flax_module,
                            'longcat_flash_decode'),
          functools.partial(check_op_classes_partition_by_flax_module,
                            'longcat_flash_prefill'),
          check_the_reference_file_holds_both_limits_and_the_wrong_programs]

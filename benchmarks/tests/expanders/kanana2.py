"""The seventh prompt-expander cell (``sd15_kanana2_expand_b4``) rehearsed
on the CPU at tiny widths through the real ``run.py``, and the files it
brought: the configuration against the catalog's row, the leaf rules, the
readers, the op classes, the metric files (a step's bytes by ``harness/bytes_lm.py``
against a hand count from the published widths). A rehearsal yields counts and correctness, never a
speed."""

import functools
import json
import re

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_kanana2_expand_b4"
CONFIG = "sd15_kanana2_expand"
TRAFFIC = "sd15_2048_b4_expand256"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_kanana2_expander")
BENCH = files.Bench(rehearsal.REPO)


def check_the_traffic_file_is_the_mellum2_cells_unchanged():
    cell = BENCH.cell(CELL)
    other = BENCH.cell("sd15_mellum2_expand_b4")
    assert cell["config"] == CONFIG and cell["traffic"] == TRAFFIC \
        == other["traffic"]
    assert cell["chips"] == 1 and cell["mesh"] is None
    for key in ("server_env", "warmup_requests", "trace"):
        assert cell[key] == other[key], key
    assert "six times" in BENCH.read("workloads", CELL + ".json")["why"]
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
    assert len(prefix) == 2048 and all(0 <= i < 128256 for i in prefix)
    lengths = [len(tok.encode(p)) for p in traffic["cycle"]["prompt"]]
    assert min(lengths) == 16 and max(lengths) == 64
    assert args["max_new_tokens"] == 256 and args["ignore_eos"] is True
    assert traffic["payload"]["batch_size"] == 4
    chunks = -(-(256 - 1) // expand.DECODE_STEPS)
    assert kv.capacity_for(2048 + 64 + chunks * expand.DECODE_STEPS) == 2560
    # the reference reads at the timed sizes
    reference = BENCH.reference(BENCH.config(CONFIG))
    assert reference.TIMED_POSITIONS == 2048 + 64 + 256
    assert reference.split(2368) == (2048, 64, 256)


def check_the_configuration_holds_the_published_config_but_for_reduced():
    config = BENCH.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(json.loads(line) for line in fh if re.search(
            '"name": "kanana-2-30b-a3b-instruct-2601"', line))
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8
    assert config["q_lora_rank"] is None and config["rope_scaling"] is None
    assert len(config["assumed"]) >= 8 and config["counter"] is None
    assert config["components"] == "unet_clip_vae_lm_kanana2"
    for key in ("published", "held_here", "deployment", "assumed"):
        assert config[key], key
    assert "seven chips" in config["deployment"]
    assert "share-adds-up test does not apply" in config["held_here"][
        "experts"]
    assert config["diffusion"] == BENCH.read("configs", "sd15.json")["model"]
    share = files.resolve_family(config).expander
    assert share.num_layers == config["num_hidden_layers"]
    # every expert and every id is held: the cut is in depth alone
    assert share.experts == (0, config["n_routed_experts"]) == (0, 128)
    assert share.vocab == (0, config["vocab_size"]) == (0, 128256)
    assert share.layer_types == ("latent",) * 8
    assert share.dense_layers == tuple(range(config["first_k_dense_replace"]))
    assert share.rope_full.theta == config["rope_theta"]
    assert share.rope_full.interleaved is config["rope_interleave"] is True
    assert share.rope_full.factor == 0 and share.residual_streams == 1
    for ours, theirs in (
            ("hidden_size", "hidden_size"),
            ("intermediate_size", "intermediate_size"),
            ("moe_intermediate_size", "moe_intermediate_size"),
            ("num_experts", "n_routed_experts"),
            ("num_experts_per_tok", "num_experts_per_tok"),
            ("norm_topk_prob", "norm_topk_prob"),
            ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"),
            ("routed_scaling_factor", "routed_scaling_factor"),
            ("router_scoring", "scoring_func"),
            ("rms_norm_eps", "rms_norm_eps")):
        assert getattr(share, ours) == config[theirs], ours
    assert share.q_lora_rank == 0 and share.router_bias
    assert share.shared_expert_intermediate_size \
        == config["n_shared_experts"] * config["moe_intermediate_size"]
    assert share.num_heads_per_layer == (config["num_attention_heads"],) * 8
    assert config["qk_head_dim"] == share.qk_nope_head_dim \
        + share.qk_rope_head_dim


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
        == config["parameters_millions"]["expander_share"] == 5070
    assert round(total * 2 / 1e9, 2) == 10.14
    assert round((total / 1e6 + config["parameters_millions"]["sd15"])
                 * 2e6 / 2 ** 30, 2) == 11.43
    rules = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        rules[name] = (components.leaf_rule(name, leaf.shape)
                       or weights.leaf_rule(name, leaf.shape)) \
            + (tuple(leaf.shape),)
    assert rules["layers_1/mlp/router"] \
        == ("draw", (3 / 2048) ** 0.5, (2048, 128))
    assert rules["layers_1/mlp/e_score_correction_bias"] \
        == ("draw", 0.1 * 3 ** 0.5, (128,))
    # the table at variance 1: a token's row weighs what a sublayer adds
    assert rules["embed_tokens/embedding"] == ("draw", 3 ** 0.5,
                                               (128256, 2048))
    assert rules["layers_0/attn/q_proj/kernel"][2] == (2048, 32 * 192)
    assert rules["layers_3/attn/kv_a_proj_with_mqa/kernel"][2] == (2048, 576)
    assert rules["layers_3/attn/kv_b_proj/kernel"][2] == (512, 32 * 256)
    assert rules["layers_7/attn/o_proj/kernel"][2] == (4096, 2048)
    assert rules["layers_0/mlp/gate_proj/kernel"][2] == (2048, 6144)
    assert rules["layers_2/mlp/shared_expert/up_proj/kernel"][2] \
        == (2048, 1536)
    assert not any(part in name for name in rules for part in (
        "q_a_proj", "q_a_norm", "q_b_proj", "attn_hc", "g_proj"))
    # each stacked expert kernel is a draw of its own
    big = [r for r in rules.values() if len(r[2]) == 3]
    assert len(big) == 21 and len(set(big)) == 21
    assert {r[2] for r in big} == {(128, 2048, 768), (128, 768, 2048)}


def _status(steps, decoded, read, attended=0, rows=0):
    return {"serving": {"expander": {
        "tokens_prefilled": 0, "decode_steps": steps,
        "tokens_decoded": decoded, "experts_read": read, "sequences": 0,
        "rows_attended": attended, "rows_read": rows,
        "expert_tokens": [[0, 0], [0, 0]]}}}


def check_the_ratio_and_value_metrics_read_the_status_or_nothing():
    ratio = BENCH.load("readers", "status_ratio")
    context = {
        "family": files.resolve_family(BENCH.config(CONFIG)),
        "status_before": _status(256, 1024, 40000, 10, 10),
        "status_after": _status(768, 3072, 40000 + 512 * 157,
                                10 + 34, 10 + 10)}
    assert ratio.read(context, **BENCH.layer_metric(
        "lm_tokens_per_step")["args"]) == 4.0
    assert ratio.read(context, **BENCH.layer_metric(
        "experts_read_per_step")["args"]) == pytest.approx(157 / 7)
    assert ratio.read(context, **BENCH.layer_metric(
        "fork_rows_attended_per_row_read")["args"]) == pytest.approx(3.4)
    assert ratio.read({"status_before": {}, "status_after": {}},
                      **BENCH.layer_metric(
                          "lm_tokens_per_step")["args"]) is None
    value = BENCH.load("readers", "status_value")
    status = {"serving": {"attention": {"latent_forked": 8, "xla": 3},
                          "expander": {"expert_products": {"kernel": 7}}}}
    assert value.read({"status_before": status}, **BENCH.layer_metric(
        "latent_forked_sites")["args"]) == 8
    assert value.read({"status_before": status}, **BENCH.layer_metric(
        "expert_kernel_sites")["args"]) == 7
    # a program without the form (the parent): nothing, and no raise
    bare = {"serving": {"attention": {"xla": 3}, "expander": {}}}
    assert value.read({"status_before": bare}, **BENCH.layer_metric(
        "latent_forked_sites")["args"]) is None


def check_op_classes_partition_by_flax_module(classes):
    reader = BENCH.load("readers", "op_class_ms")
    spec = BENCH.read("op_classes", classes + ".json")
    rules = spec["classes"]
    assert spec["module"] == {
        "kanana2_decode": "jit_expand_decode_chunk",
        "kanana2_prefill": "jit_expand_prefill"}[classes]
    base = "jit(f)/jit(main)/while/body/DecoderLM/"
    cases = {
        "layers_0/attn/q_proj/dot_general": "linear",
        "layers_3/attn/kv_a_proj_with_mqa/dot_general": "linear",
        "layers_6/attn/o_proj/dot_general": "linear",
        "layers_0/mlp/gate_proj/dot_general": "linear",
        "layers_4/mlp/shared_expert/down_proj/dot_general": "linear",
        "lm_head/dot_general": "linear",
        "layers_3/attn/exp": "latent",
        "layers_7/attn/kv_b_proj/reshape": "latent",
        "layers_0/attn/kv_a_norm/rsqrt": "latent",
        "layers_7/attn/dynamic_update_slice": "latent",
        "layers_1/mlp/top_k": "expert",
        "layers_7/mlp/pallas_call": "expert",
        "layers_12/mlp/logistic": "expert",
        "layers_0/mlp/logistic": "other",      # the dense layer's SiLU
        "layers_3/mlp/shared_expert/logistic": "other",
        "layers_1/input_norm/rsqrt": "other",
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


def check_the_reference_file_holds_both_limits_and_three_seeds():
    """What the chip gave (PR 52): three seeds at the timed positions, of
    the program and of every control; both limits between their two
    readings."""
    recorded = BENCH.read("reference", CONFIG + ".json")
    limit = recorded["tolerance_held_to_routing_relative_rms"]
    assert 0 < limit < recorded["tolerance_relative_rms"] < 1
    assert recorded["tolerance_reason"] \
        and recorded["tolerance_held_to_routing_reason"]
    assert recorded["device"]["platform"] == "tpu"
    assert recorded["latent"] == 2048 + 64 + 256
    held = "_vs_reference_held_to_the_programs_routing_relative_rms"
    controls = [name for name, _ in
                BENCH.reference(BENCH.config(CONFIG)).CONTROLS]
    seeds = recorded["diagnostics"]
    assert len(seeds) >= 3
    assert len({d["seed"] for d in seeds}) == len(seeds)
    for reading in seeds:
        assert reading["positions"] == 2368 and reading["sequences"] == 4
        assert reading["program_vs_reference_relative_rms"] \
            < recorded["tolerance_relative_rms"] \
            < reading["control_vs_reference_relative_rms"]
        assert reading["program_vs_reference_held_to_its_routing_"
                       "relative_rms"] < limit
        assert all(reading[name + held] > limit for name in controls)


def traced(m):
    """What the traced rehearsal's per-layer metrics must say."""
    assert m["expand_fork_ms"] > 0
    # four images a step: 40 tokens a sequence over two chunks of 32
    assert m["lm_tokens_per_step"] == pytest.approx(4 * 40 / 64)
    # the tiny preset has 4 latent layers, 3 of them expert layers of
    # 16 experts, 4 a token; the metric divides by the configuration's 3
    assert m["latent_forked_sites"] == 4
    assert m["expert_kernel_sites"] == 0      # a CPU
    assert 4 <= m["experts_read_per_step"] <= 16
    assert 1.5 < m["fork_rows_attended_per_row_read"] < 4


def _walker_and_share():
    return (BENCH.load("harness", "bytes_lm"),
            files.resolve_family(BENCH.config(CONFIG)).expander)


def _rows(count, cfg, forked_at, step, sequences=1):
    """The key, value and latent rows one step needs, all layers."""
    terms = count.step_bytes(cfg, forked_at, step, 0.0, sequences)
    return terms["rows_shared"] + terms["rows_own"]


def check_bytes_a_forked_step_needs_against_a_hand_count():
    """From the published widths: hidden 2 048, no query latent, a
    key-value latent of 512 + 64 rotated, 32 heads of 128 + 64 and 128,
    128 experts of 768 and a shared one of 1 536."""
    count, cfg = _walker_and_share()
    d = 2048
    attn = (d * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d) * 2
    assert count.mixer_bytes(cfg, 0) == attn == 2 * 26_345_472
    dense = 3 * d * 6144 * 2
    # router, its selection bias (bytes_kanana2.py left it out), shared
    beside = (d * 128 + 128 + 3 * d * 1536) * 2
    head = d * 128256 * 2
    assert count.mlp_bytes(cfg, 0) == dense and count.mlp_bytes(cfg, 1) == beside
    assert count.head_bytes(cfg) == head and round(head / 1e6) == 525
    assert count.fixed_bytes(cfg, 4) \
        == 8 * attn + dense + 7 * beside + head + 4 * d * 2
    assert round(count.fixed_bytes(cfg, 4) / 1e6, 1) == 1158.2
    assert count.expert_bytes(cfg) == 3 * d * 768 * 2 == 9_437_184
    assert count.row_bytes(cfg, "latent") == 576 * 2
    assert _rows(count, cfg, 0, 0) == 8 * 576 * 2
    # a step of four under even routing: 22.4 distinct experts a layer
    even = 128 * (1 - (1 - 6 / 128) ** 4)
    assert round(even, 1) == 22.4
    step = count.decode_bytes(cfg, 2112, 1, 7 * even, 4)
    assert step == pytest.approx(
        count.fixed_bytes(cfg, 4) + 7 * even * 9_437_184
        + (2112 + 4) * 9216)
    assert 2.63e9 < step < 2.66e9
    assert round(7 * even * 9_437_184 / 1e9, 2) == 1.48
    # the shared rows once a step, a sequence's own once each: 256 steps
    whole = count.decode_bytes(cfg, 2112, 256, 7 * even, 4)
    rows = 256 * 2112 + 4 * 256 * 257 / 2
    assert whole == pytest.approx(
        256 * (count.fixed_bytes(cfg, 4) + 7 * even * 9_437_184)
        + rows * 9216)
    # counting position + 1 rows a sequence would count 3.4 times the rows
    copied = 4 * sum(2112 + i + 1 for i in range(256))
    assert 3.3 < copied / rows < 3.5


CHECKS = [check_bytes_a_forked_step_needs_against_a_hand_count,
          check_the_traffic_file_is_the_mellum2_cells_unchanged,
          check_the_configuration_holds_the_published_config_but_for_reduced,
          check_the_leaf_rules_and_the_shares_parameters,
          check_the_ratio_and_value_metrics_read_the_status_or_nothing,
          functools.partial(check_op_classes_partition_by_flax_module, 'kanana2_decode'),
          functools.partial(check_op_classes_partition_by_flax_module, 'kanana2_prefill'),
          check_the_reference_file_holds_both_limits_and_three_seeds]

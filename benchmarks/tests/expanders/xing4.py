"""The third prompt-expander cell (``sd15_xing4_expand_solo``) rehearsed on
the CPU at tiny widths through the real ``run.py``, and the files it
brought: the components' leaf rules, the op classes, the metric files (a step's bytes by ``harness/bytes_lm.py``
against a hand count from the published widths). A rehearsal yields counts and
correctness, never a speed."""

import dataclasses
import functools
import json
import re

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_xing4_expand_solo"
CONFIG = "sd15_xing4_expand"
TRAFFIC = "sd15_512_expand384"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_xing4_expander")
BENCH = files.Bench(rehearsal.REPO)


def check_the_cell_is_the_other_expander_cells_request():
    cell = BENCH.cell(CELL)
    for name in ("sd15_expand_solo", "sd15_qwen3next_expand_solo"):
        other = BENCH.cell(name)
        assert cell["traffic"] == other["traffic"] == "sd15_512_expand384"
        for key in ("chips", "mesh", "server_env", "warmup_requests",
                    "trace"):
            assert cell[key] == other[key], key
    assert cell["config"] == CONFIG
    # the hash tokenizer maps a word to one id of the held slice: the
    # traffic's token counts hold whatever the slice
    from stable_diffusion_webui_distributed_tpu.models.tokenizer import (
        load_lm_tokenizer,
    )
    share = files.resolve_family(BENCH.config(CONFIG)).expander
    tok = load_lm_tokenizer(None, *share.vocab)
    traffic = BENCH.traffic(cell["traffic"])
    args = traffic["payload"]["alwayson_scripts"]["prompt expansion"][
        "args"][0]
    prefix = [tok.bos] + tok.encode(args["instruction"])
    assert len(prefix) == 512
    lengths = [len(tok.encode(p)) for p in traffic["cycle"]["prompt"]]
    assert min(lengths) == 16 and max(lengths) == 64
    first, count = share.vocab
    assert all(first <= i < first + count for i in prefix)


def check_the_configuration_holds_the_published_config_but_for_reduced():
    config = BENCH.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(json.loads(line) for line in fh if re.search(
            '"name": "Xing4.0-29B-A4B"', line))
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (20, 16, 32768)
    assert len(config["assumed"]) >= 10 and config["counter"] is None
    assert "four chips" in config["deployment"] \
        and "8 chips" in config["deployment"]
    assert config["diffusion"] == BENCH.read("configs", "sd15.json")["model"]
    share = files.resolve_family(config).expander
    assert (share.num_layers, share.experts[1], share.vocab[1]) \
        == (config["num_hidden_layers"], 16, 32768)
    assert share.layer_types == ("latent",) * 20
    assert share.dense_layers == tuple(
        range(config["first_k_dense_replace"]))
    assert share.num_experts == config["published"]["n_routed_experts"]
    scaling = config["rope_scaling"]
    assert (share.rope_full.theta, share.rope_full.factor,
            share.rope_full.original_max_position,
            share.rope_full.beta_fast, share.rope_full.beta_slow,
            share.rope_mscale_all_dim) == (
                config["rope_theta"], scaling["factor"],
                scaling["original_max_position_embeddings"],
                scaling["beta_fast"], scaling["beta_slow"],
                scaling["mscale_all_dim"])
    assert share.rope_full.attention_factor == 1.0      # mscale / all_dim
    assert share.latent_softmax_scale == pytest.approx(
        192 ** -0.5 * 1.41589 ** 2, rel=1e-5)
    assert share.hc_res_clamp == (config["mhc_h_res_clamp_min"],
                                  config["mhc_h_res_clamp_max"])
    for ours, theirs in (
            ("hidden_size", "hidden_size"),
            ("intermediate_size", "intermediate_size"),
            ("moe_intermediate_size", "moe_intermediate_size"),
            ("num_experts_per_tok", "num_experts_per_tok"),
            ("norm_topk_prob", "norm_topk_prob"),
            ("routed_scaling_factor", "routed_scaling_factor"),
            ("router_scoring", "scoring_func"),
            ("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"), ("residual_streams", "hc_mult"),
            ("sinkhorn_iters", "hc_sinkhorn_iters"), ("hc_eps", "hc_eps"),
            ("rms_norm_eps", "rms_norm_eps")):
        assert getattr(share, ours) == config[theirs], ours
    assert share.num_heads_per_layer == (config["num_attention_heads"],) * 20
    assert share.shared_expert_intermediate_size \
        == config["n_shared_experts"] * config["moe_intermediate_size"]
    assert share.router_bias and config["topk_method"] == "noaux_tc"


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
    assert round(sum(leaf.size for _, leaf in flat) / 1e6) \
        == config["parameters_millions"]["expander_share"] == 4389
    rules = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        rules[name] = (components.leaf_rule(name, leaf.shape)
                       or weights.leaf_rule(name, leaf.shape)) \
            + (tuple(leaf.shape),)
    assert rules["layers_0/attn_hc/phi"] \
        == ("draw", 0.5 * (3 / 14336) ** 0.5, (14336, 24))
    assert rules["layers_7/mlp_hc/alpha"] == ("ones", 0.0, (3,))
    assert rules["layers_7/mlp_hc/b_res"] == ("draw", 1.0, (4, 4))
    assert rules["layers_7/mlp_hc/b_pre"][:2] == ("draw", 0.01 * 3 ** 0.5)
    assert rules["layers_7/mlp_hc/norm/scale"] == ("ones", 0.0, (14336,))
    assert rules["layers_2/mlp/e_score_correction_bias"] \
        == ("draw", 0.1 * 3 ** 0.5, (64,))
    assert rules["layers_2/mlp/router"] \
        == ("draw", (3 / 3584) ** 0.5, (3584, 64))
    assert rules["layers_0/attn/kv_b_proj/kernel"][1:] \
        == ((3 / 512) ** 0.5, (512, 32 * 256))
    assert rules["layers_0/attn/q_b_proj/kernel"][1:] \
        == ((3 / 768) ** 0.5, (768, 32 * 192))
    assert rules["layers_0/attn/kv_a_proj_with_mqa/kernel"][2] \
        == (3584, 576)
    assert rules["layers_1/mlp/up_proj/kernel"][2] == (3584, 9216)
    assert "layers_1/mlp/router" not in rules       # the second dense layer
    # each stacked expert kernel is a draw of its own
    big = [r for r in rules.values() if len(r[2]) == 3]
    assert len(big) == 54 and len(set(big)) == 54
    assert {r[2] for r in big} == {(16, 3584, 1024), (16, 1024, 3584)}


def check_the_sites_metrics_read_nothing_from_a_program_without_them():
    """The parent's /internal/status has no latent_absorbed: the metric is
    left out of its line and nothing raises."""
    reader = BENCH.load("readers", "status_value")
    spec = BENCH.layer_metric("latent_absorbed_sites")
    old = {"status_before": {"serving": {"attention": {"tiled": 0,
                                                       "xla": 40}}}}
    assert reader.read(old, **spec["args"]) is None
    new = {"status_before": {"serving": {"attention": {
        "tiled": 0, "xla": 40, "latent_absorbed": 20,
        "latent_expanded": 40}}}}
    assert reader.read(new, **spec["args"]) == 20.0
    spec = BENCH.layer_metric("expert_kernel_sites")
    assert reader.read({"status_before": {"serving": {"expander": {
        "expert_products": {"kernel": 18, "loop": 0, "grouped": 36}}}}},
        **spec["args"]) == 18.0


def check_op_classes_partition_by_flax_module(classes):
    reader = BENCH.load("readers", "op_class_ms")
    spec = BENCH.read("op_classes", classes + ".json")
    rules = spec["classes"]
    assert spec["module"] == {"xing4_decode": "jit_expand_decode_chunk",
                              "xing4_prefill": "jit_expand_prefill"}[classes]
    base = "jit(f)/jit(main)/while/body/DecoderLM/"
    cases = {
        "layers_0/attn/q_a_proj/dot_general": "linear",
        "layers_1/attn/q_b_proj/dot_general": "linear",
        "layers_2/attn/kv_a_proj_with_mqa/dot_general": "linear",
        "layers_3/attn/o_proj/dot_general": "linear",
        "layers_0/mlp/up_proj/dot_general": "linear",
        "layers_5/mlp/shared_expert/down_proj/dot_general": "linear",
        "lm_head/dot_general": "linear",
        "layers_0/attn/kv_b_proj/convert_element_type": "latent",
        "layers_0/attn/thd,rhd->thr/dot_general": "latent",
        "layers_4/attn/q_a_norm/rsqrt": "latent",
        "layers_4/attn/kv_a_norm/rsqrt": "latent",
        "layers_7/attn/exp": "latent",
        "layers_7/attn/dynamic_update_slice": "latent",
        "layers_0/attn_hc/norm/rsqrt": "hc",
        "layers_0/attn_hc/dot_general": "hc",
        "layers_19/mlp_hc/div": "hc",
        "layers_19/mlp_hc/reduce_sum": "hc",
        "layers_3/attn_hc/mul": "hc",
        "layers_2/mlp/pallas_call": "expert",
        "layers_19/mlp/top_k": "expert",
        "layers_12/mlp/logistic": "expert",
        "layers_0/mlp/mul": "other",                 # a dense layer's SiLU
        "layers_1/mlp/logistic": "other",
        "layers_2/mlp/shared_expert/mul": "other",
        "layers_1/input_norm/rsqrt": "other",
        "embed_tokens/gather": "other",
        "reduce_sum": "other",                       # the streams' sum
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
        {"scope": base + "layers_0/attn_hc/x", "category": "x",
         "name": "copy-done.1"}, rules) == "hc"
    order = [r["class"] for r in rules]
    assert sorted(set(order)) == ["expert", "hc", "latent", "linear",
                                  "other"]
    assert order[-1] == "other"
    assert not {"scope", "category", "name"} & set(rules[-1])


def traced(m):
    """What the traced rehearsal's per-layer metrics must say."""
    # every layer's decode step attends the cache as it lies; on a
    # CPU an expert layer takes the loop
    assert m["latent_absorbed_sites"] == 4
    assert m["expert_kernel_sites"] == 0
    assert "x4_mixer_kernel_sites" in m


def _walker_and_share():
    return (BENCH.load("harness", "bytes_lm"),
            files.resolve_family(BENCH.config(CONFIG)).expander)


def _rows(count, cfg, forked_at, step, sequences=1):
    """The key, value and latent rows one step needs, all layers."""
    terms = count.step_bytes(cfg, forked_at, step, 0.0, sequences)
    return terms["rows_shared"] + terms["rows_own"]


def check_bytes_a_decoded_token_needs_against_a_hand_count():
    """From the published widths: hidden 3 584, a query latent of 768, a
    key-value latent of 512 + 64 rotated, 32 heads of 128 + 64 and 128,
    four residual streams mixed around both sublayers."""
    count, cfg = _walker_and_share()
    d = 3584
    latent = (d * 768 + 768 * 6144 + d * 576 + 512 * 8192 + 4096 * d) * 2
    mixers = 2 * 14336 * 24 * 2     # 2 sublayers x (4 d) x (16 + 8)
    dense = 3 * d * 9216 * 2
    moe = (d * 64 + 64 + 3 * d * 1024) * 2   # router, bias, shared expert
    head = d * 32768 * 2
    assert round(latent / 1e6, 1) == 56.8
    assert count.mixer_bytes(cfg, 0) == latent + mixers == 58_195_968
    one_stream = dataclasses.replace(cfg, residual_streams=1)
    assert count.mixer_bytes(one_stream, 0) == latent
    assert count.mlp_bytes(cfg, 0) == dense and count.mlp_bytes(cfg, 2) == moe
    assert count.fixed_bytes(cfg, 1) \
        == 20 * (latent + mixers) + 2 * dense + 18 * moe + head + d * 2
    assert round(count.fixed_bytes(cfg, 1) / 1e6) == 2200
    assert count.expert_bytes(cfg) == 3 * d * 1024 * 2 == 22020096
    # one row of 576 a position a layer, whatever the 32 heads
    assert count.row_bytes(cfg, "latent") == 1152
    assert count.state_bytes(cfg, "latent") == 0
    assert _rows(count, cfg, 0, 0) == 20 * 1152
    assert _rows(count, cfg, 959, 0) == 20 * 960 * 1152
    one = count.decode_bytes(cfg, 600, 1, 18.0)
    assert one == count.fixed_bytes(cfg, 1) + 18 * count.expert_bytes(cfg) \
        + 20 * 601 * 1152
    assert 2.55e9 < one < 2.65e9
    assert count.decode_bytes(cfg, 600, 2, 18.0) \
        == one + count.decode_bytes(cfg, 601, 1, 18.0)


CHECKS = [check_bytes_a_decoded_token_needs_against_a_hand_count,
          check_the_cell_is_the_other_expander_cells_request,
          check_the_configuration_holds_the_published_config_but_for_reduced,
          check_the_leaf_rules_and_the_shares_parameters,
          check_the_sites_metrics_read_nothing_from_a_program_without_them,
          functools.partial(check_op_classes_partition_by_flax_module, 'xing4_decode'),
          functools.partial(check_op_classes_partition_by_flax_module, 'xing4_prefill')]

"""The second prompt-expander cell (``sd15_qwen3next_expand_solo``)
rehearsed on the CPU at tiny widths through the real ``run.py``, and the
files it brought: the components' leaf rules, the op classes, the metric
files (a step's bytes by ``harness/bytes_lm.py``
against a hand count from the published widths). A rehearsal yields counts
and correctness, never a speed."""

import functools
import json
import re

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_qwen3next_expand_solo"
CONFIG = "sd15_qwen3next_expand"
TRAFFIC = "sd15_512_expand384"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_delta_expander")
BENCH = files.Bench(rehearsal.REPO)


def check_the_cell_is_the_other_expander_cells_request():
    cell, other = BENCH.cell(CELL), BENCH.cell("sd15_expand_solo")
    assert cell["traffic"] == other["traffic"] == "sd15_512_expand384"
    for key in ("chips", "mesh", "server_env", "warmup_requests", "trace"):
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
            '"name": "Qwen3-Next-80B-A3B-Instruct"', line))
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (12, 128, 37984)
    assert len(config["assumed"]) >= 8 and config["counter"] is None
    assert "four chips" in config["deployment"] \
        and "16 chips" in config["deployment"]
    assert config["diffusion"] == BENCH.read("configs", "sd15.json")["model"]
    share = files.resolve_family(config).expander
    assert (share.num_layers, share.experts[1], share.vocab[1]) \
        == (config["num_hidden_layers"], 128, 37984)
    assert share.hidden_size == config["hidden_size"]
    assert share.num_experts == config["published"]["num_experts"]
    assert share.num_experts_per_tok == config["num_experts_per_tok"]
    assert share.head_dim == config["head_dim"]
    assert share.rope_full.theta == config["rope_theta"]
    assert share.rope_full.partial_rotary_factor \
        == config["partial_rotary_factor"]
    assert (share.linear_num_key_heads, share.linear_num_value_heads,
            share.linear_key_head_dim, share.linear_value_head_dim,
            share.linear_conv_kernel) == tuple(config[k] for k in (
                "linear_num_key_heads", "linear_num_value_heads",
                "linear_key_head_dim", "linear_value_head_dim",
                "linear_conv_kernel_dim"))
    interval = config["full_attention_interval"]
    assert share.layer_types == tuple(
        "full" if (i + 1) % interval == 0 else "linear"
        for i in range(share.num_layers))
    assert config["layer_types"][:4] == ["linear_attention"] * 3 \
        + ["full_attention"]


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
        == config["parameters_millions"]["expander_share"] == 5423
    rules = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        rules[name] = (components.leaf_rule(name, leaf.shape)
                       or weights.leaf_rule(name, leaf.shape)) \
            + (tuple(leaf.shape),)
    assert rules["layers_0/delta/A_log"] == ("draw", 4.0, (32,))
    assert rules["layers_0/delta/conv_kernel"] \
        == ("draw", (3 / 4) ** 0.5, (4, 8192))
    assert rules["layers_0/delta/norm/scale"][0] == "ones"
    assert rules["layers_0/input_norm/weight"][:2] \
        == ("draw", 0.01 * 3 ** 0.5)
    assert rules["layers_0/delta/qkvz_proj/kernel"][1:] \
        == ((3 / 2048) ** 0.5, (2048, 12288))
    assert rules["layers_3/attn/q_proj/kernel"][2] == (2048, 8192)
    assert rules["layers_0/mlp/router"] \
        == ("draw", (3 / 2048) ** 0.5, (2048, 512))
    # each stacked expert kernel is a draw of its own
    big = [r for r in rules.values() if len(r[2]) == 3]
    assert len(big) == 36 and len(set(big)) == 36
    assert components.leaf_rule("layers_3/attn/o_proj/kernel",
                                (4096, 2048)) is None


def check_op_classes_partition_by_flax_module(classes):
    reader = BENCH.load("readers", "op_class_ms")
    spec = BENCH.read("op_classes", classes + ".json")
    rules = spec["classes"]
    assert spec["module"] == {"qwen3next_decode": "jit_expand_decode_chunk",
                              "qwen3next_prefill": "jit_expand_prefill"}[
                                  classes]
    base = "jit(f)/jit(main)/while/body/DecoderLM/"
    cases = {
        "layers_0/delta/qkvz_proj/dot_general": "linear",
        "layers_1/delta/ba_proj/dot_general": "linear",
        "layers_2/delta/out_proj/dot_general": "linear",
        "layers_3/attn/q_proj/dot_general": "linear",
        "layers_0/mlp/shared_expert/up_proj/dot_general": "linear",
        "layers_0/mlp/shared_expert_gate/dot_general": "linear",
        "lm_head/dot_general": "linear",
        "layers_0/delta/mul": "delta",
        "layers_4/delta/norm/rsqrt": "delta",
        "layers_5/delta/dynamic_slice": "delta",
        "layers_3/attn/q_norm/rsqrt": "attn",
        "layers_7/attn/exp": "attn",
        "layers_0/mlp/while/body/dot_general": "expert",
        "layers_11/mlp/top_k": "expert",
        "layers_0/mlp/shared_expert/mul": "other",
        "layers_1/input_norm/rsqrt": "other",
        "embed_tokens/gather": "other",
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
        {"scope": base + "layers_0/delta/x", "category": "x",
         "name": "copy-done.1"}, rules) == "delta"
    order = [r["class"] for r in rules]
    assert sorted(set(order)) == ["attn", "delta", "expert", "linear",
                                  "other"]
    assert order[-1] == "other"
    assert not {"scope", "category", "name"} & set(rules[-1])


def traced(m):
    """What the traced rehearsal's per-layer metrics must say."""
    assert m["expert_kernel_sites"] == 0      # a CPU


def _walker_and_share():
    return (BENCH.load("harness", "bytes_lm"),
            files.resolve_family(BENCH.config(CONFIG)).expander)


def _rows(count, cfg, forked_at, step, sequences=1):
    """The key, value and latent rows one step needs, all layers."""
    terms = count.step_bytes(cfg, forked_at, step, 0.0, sequences)
    return terms["rows_shared"] + terms["rows_own"]


def check_bytes_a_decoded_token_needs_against_a_hand_count():
    """From the published widths: hidden 2 048; a linear layer of 16 key
    and 32 value heads of 128 (conv over 8 192 channels, 4 taps); a full
    layer of 16 heads of 256 gated element by element, 2 key heads."""
    count, cfg = _walker_and_share()
    d = 2048
    linear = (d * 12288 + d * 64 + 4096 * d + 4 * 8192 + 64) * 2
    full = (d * 8192 + 2 * d * 512 + 4096 * d) * 2
    moe = (d * 512 + 3 * d * 512 + d) * 2   # router, shared expert, its gate
    head = d * 37984 * 2
    assert count.mixer_bytes(cfg, 0) == linear == 67_436_672
    assert count.mixer_bytes(cfg, 3) == full == 54_525_952
    assert count.mlp_bytes(cfg, 0) == count.mlp_bytes(cfg, 3) == moe
    assert count.head_bytes(cfg) == head
    assert count.fixed_bytes(cfg, 1) \
        == 9 * linear + 3 * full + 12 * moe + head + d * 2
    # 9 x 67.4 + 3 x 54.5 + 12 x 8.4 + 155.6 MB
    assert round(count.fixed_bytes(cfg, 1) / 1e6) == 1027
    assert count.expert_bytes(cfg) == 3 * d * 512 * 2
    # S (32, 128, 128) and three rows of 8192 inputs, float32, read and
    # written, nine layers
    state = (32 * 128 * 128 + 3 * 8192) * 4
    assert count.state_bytes(cfg, "linear") == state == 2_195_456
    assert count.step_bytes(cfg, 600, 0, 0.0, 1)["states"] == 2 * 9 * state
    row = 2 * 2 * 256 * 2
    assert count.row_bytes(cfg, "full") == row
    assert count.row_bytes(cfg, "linear") == 0
    assert _rows(count, cfg, 0, 0) == 3 * row
    assert _rows(count, cfg, 899, 0) == 3 * 900 * row
    one = count.decode_bytes(cfg, 600, 1, 30.0)
    assert one == count.fixed_bytes(cfg, 1) + 2 * 9 * state \
        + 30 * count.expert_bytes(cfg) + 3 * 601 * row
    assert 1.2e9 < one < 1.3e9
    assert count.decode_bytes(cfg, 600, 2, 30.0) \
        == one + count.decode_bytes(cfg, 601, 1, 30.0)
    # the tiny preset's sliding layer is capped at its window
    tiny = files.resolve_family({"factory": TINY_FACTORY}).expander
    assert _rows(count, tiny, 99, 0) == (8 + 100) * 2 * 2 * 16 * 2


CHECKS = [check_bytes_a_decoded_token_needs_against_a_hand_count,
          check_the_cell_is_the_other_expander_cells_request,
          check_the_configuration_holds_the_published_config_but_for_reduced,
          check_the_leaf_rules_and_the_shares_parameters,
          functools.partial(check_op_classes_partition_by_flax_module, 'qwen3next_decode'),
          functools.partial(check_op_classes_partition_by_flax_module, 'qwen3next_prefill')]

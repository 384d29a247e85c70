"""The second prompt-expander cell (``sd15_qwen3next_expand_solo``)
rehearsed on the CPU at tiny widths through the real ``run.py``, and the
files it brought: the components' leaf rules, the byte count against a hand
count, the op classes, the metric files. A rehearsal yields counts and
correctness, never a speed."""

import json
import os
import re
import types

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_qwen3next_expand_solo"
CONFIG = "sd15_qwen3next_expand"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_delta_expander")
NEW = ["q3n_expand_ms", "q3n_expand_prefill_ms", "q3n_expand_decode_ms",
       "q3n_linear_device_ms", "q3n_delta_device_ms", "q3n_attn_device_ms",
       "q3n_expert_device_ms", "q3n_other_device_ms",
       "q3n_decode_bytes_util"]
#: read from what only a TPU's trace or memory_stats() holds
CHIP_ONLY = {"peak_hbm_gib"} | {n for n in NEW if "device" in n
                                or "bytes" in n}
BENCH = files.Bench(rehearsal.REPO)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rehearsal.make_root(str(tmp_path_factory.mktemp("q3n")))
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
        assert m["q3n_expand_ms"] > m["q3n_expand_decode_ms"] > 0
        assert m["q3n_expand_prefill_ms"] > 0
        # the other expander's metrics list their own cell
        assert not {"expand_ms", "lm_linear_device_ms"} & set(m)
        assert m["attention_tiled_sites"] == 0


def test_the_cell_is_the_other_expander_cells_request():
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


def test_the_configuration_holds_the_published_config_but_for_reduced():
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


def test_bytes_a_decoded_token_needs_against_a_hand_count():
    count = BENCH.load("harness", "bytes_qwen3next")
    cfg = files.resolve_family(BENCH.config(CONFIG)).expander
    d = 2048
    linear = (d * 12288 + d * 64 + 4096 * d + 4 * 8192 + 64) * 2
    full = (d * 8192 + 2 * d * 512 + 4096 * d) * 2
    moe = (d * 512 + 3 * d * 512 + d) * 2
    head = (d + d * 37984) * 2
    assert count.linear_layer_bytes(cfg) == linear
    assert count.full_layer_bytes(cfg, 3) == full
    assert count.fixed_bytes(cfg) == 9 * linear + 3 * full + 12 * moe + head
    # 9 x 67.4 + 3 x 54.5 + 12 x 8.4 + 155.6 MB
    assert round(count.fixed_bytes(cfg) / 1e6) == 1027
    assert count.expert_bytes(cfg) == 3 * d * 512 * 2
    # S (32, 128, 128) and three rows of 8192 inputs, float32, read and
    # written, nine layers
    assert count.state_bytes(cfg) \
        == 2 * 9 * (32 * 128 * 128 + 3 * 8192) * 4
    row = 2 * 2 * 256 * 2
    assert count.cache_bytes(cfg, 0) == 3 * row
    assert count.cache_bytes(cfg, 899) == 3 * 900 * row
    one = count.decode_bytes(cfg, 600, 1, 30.0)
    assert one == count.fixed_bytes(cfg) + count.state_bytes(cfg) \
        + 30 * count.expert_bytes(cfg) + count.cache_bytes(cfg, 600)
    assert 1.2e9 < one < 1.3e9
    assert count.decode_bytes(cfg, 600, 2, 30.0) \
        == one + count.decode_bytes(cfg, 601, 1, 30.0)
    # the tiny preset's sliding layer is capped at its window
    tiny = files.resolve_family({"factory": TINY_FACTORY}).expander
    assert count.cache_bytes(tiny, 99) == (8 + 100) * 2 * 2 * 16 * 2


def test_bytes_util_reads_the_new_counter():
    reader = BENCH.load("readers", "bytes_util")
    spec = BENCH.layer_metric("q3n_decode_bytes_util")
    traffic = BENCH.traffic("sd15_512_expand384")
    payload = dict(traffic["payload"], prompt=traffic["cycle"]["prompt"][0])
    status = lambda tokens, routed: {"serving": {"expander": {   # noqa: E731
        "tokens_prefilled": tokens, "decode_steps": 0,
        "expert_tokens": [[routed, 0], [0, 0]]}}}
    context = {
        "records": [types.SimpleNamespace(traced=True, payload=payload)],
        "trace": {"modules": {"jit_expand_decode_chunk": 1.0}},
        "family": files.resolve_family(BENCH.config(CONFIG)),
        "status_before": status(100, 50), "status_after": status(200, 3050),
        "chips": 1, "peak": {"hbm_bytes_per_s": 819e9}, "bench": BENCH,
    }
    count = BENCH.load("harness", "bytes_qwen3next")
    cfg = context["family"].expander
    want = 100 * count.decode_bytes(cfg, 512 + 16, 384, 30.0) / 819e9
    assert reader.read(context, **spec["args"]) == pytest.approx(want)
    assert 50 < want < 70       # 1.26 GB a token, 384 tokens, in one second
    assert reader.read(dict(context, trace=None), **spec["args"]) is None


@pytest.mark.parametrize("classes", ["qwen3next_decode",
                                     "qwen3next_prefill"])
def test_op_classes_partition_by_flax_module(classes):
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

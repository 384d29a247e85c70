"""The prompt-expander cell (``sd15_expand_solo``) rehearsed on the CPU at
tiny widths through the real ``run.py``, and the files it brought: the
components' leaf rule, the byte count, the ``bytes_util`` reader, the op
classes. A rehearsal yields counts and correctness, never a speed."""

import json
import os
import types

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_expand_solo"
CONFIG = "sd15_laguna_expand"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_expander")
#: read from what only a TPU's trace or memory_stats() holds
CHIP_ONLY = {"peak_hbm_gib", "expert_device_ms", "lm_attn_device_ms",
             "lm_linear_device_ms", "lm_other_device_ms",
             "lm_decode_bytes_util"}
BENCH = files.Bench(rehearsal.REPO)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rehearsal.make_root(str(tmp_path_factory.mktemp("expand")))
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
        assert m["expand_ms"] > m["expand_decode_ms"] > 0
        assert m["expand_prefill_ms"] > 0
        assert m["expert_load_max_over_mean"] >= 1.0
        # the expander's sites are masked grouped-query ones: all on XLA
        assert m["attention_tiled_sites"] == 0


def test_the_traffic_is_what_the_cell_is_named_for():
    traffic = BENCH.traffic("sd15_512_expand384")
    payload = traffic["payload"]
    args = payload["alwayson_scripts"]["prompt expansion"]["args"][0]
    assert (payload["width"], payload["height"], payload["steps"],
            payload["batch_size"], payload["sampler_name"],
            payload["cfg_scale"]) == (512, 512, 20, 1, "Euler a", 7.0)
    assert traffic["loop"] == "closed" and traffic["clients"] == 1
    assert len(args["instruction"].split()) + 1 == 512      # with BOS
    assert (args["max_new_tokens"], args["temperature"],
            args["ignore_eos"], args["context_chunks"]) \
        == (384, 1.0, True, 3)
    lengths = [len(p.split()) for p in traffic["cycle"]["prompt"]]
    assert len(lengths) == 8 and min(lengths) == 16 and max(lengths) == 64
    cell = BENCH.cell(CELL)
    assert cell["warmup_requests"] == 1
    assert cell["server_env"] == {"SDTPU_BATCH_LADDER": "1"}


def test_the_configuration_holds_the_published_config_but_for_reduced():
    import re

    config = BENCH.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        published = next(json.loads(line)["config"] for line in fh
                         if re.search('"name": "Laguna-S-2.1"', line))
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 128, 50176)
    assert len(config["assumed"]) >= 5 and config["counter"] is None
    assert config["diffusion"] == BENCH.read("configs", "sd15.json")["model"]
    share = files.resolve_family(config).expander
    assert (share.num_layers, share.experts[1], share.vocab[1]) \
        == (5, 128, 50176)
    assert share.hidden_size == config["hidden_size"]
    assert share.num_experts == config["published"]["num_experts"]
    assert share.num_experts_per_tok == config["num_experts_per_tok"]
    assert share.rope_full.attention_factor \
        == config["rope_parameters"]["full_attention"]["attention_factor"]


def test_the_share_has_5572_million_parameters_and_each_expert_kernel_is_its_own_draw():
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
    assert round(total / 1e6) == 5572
    groups = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        rule = components.leaf_rule(name, leaf.shape) \
            or weights.leaf_rule(name, leaf.shape)
        groups.setdefault(rule + (tuple(leaf.shape),), []).append(name)
    big = [names for key, names in groups.items()
           if len(key[2]) == 3]
    assert len(big) == 12 and all(len(names) == 1 for names in big)
    width = {names[0].rsplit("/", 1)[-1]: key[1]
             for key, names in groups.items() if len(key[2]) == 3}
    assert abs(width["w_gate"] - (3 / 3072) ** 0.5) < 1e-9
    assert abs(width["w_down"] - (3 / 1024) ** 0.5) < 1e-9
    router = components.leaf_rule("layers_1/mlp/router", (3072, 256))
    assert router == ("draw", (3 / 3072) ** 0.5)
    assert components.leaf_rule("layers_0/attn/q_proj/kernel",
                                (3072, 6144)) is None


def test_bytes_a_decoded_token_needs():
    count = BENCH.load("harness", "bytes_laguna")
    cfg = files.resolve_family(BENCH.config(CONFIG)).expander
    # attention 44.2 + 3 x 63.1 + 44.2 M, dense MLP 113.2 M, 4 routers and
    # shared experts, the head's 154.1 M: 0.54 + 0.23 + 0.08 + 0.31 GB
    assert round(count.fixed_bytes(cfg) / 1e6) == 1172
    assert count.expert_bytes(cfg) == 3 * 3072 * 1024 * 2
    row = 2 * 8 * 128 * 2
    assert count.cache_bytes(cfg, 0) == 5 * row
    assert count.cache_bytes(cfg, 899) == (2 * 900 + 3 * 512) * row
    one = count.decode_bytes(cfg, 600, 1, 20.0)
    assert one == count.fixed_bytes(cfg) + 20 * count.expert_bytes(cfg) \
        + count.cache_bytes(cfg, 600)
    assert 1.5e9 < one < 1.6e9
    assert count.decode_bytes(cfg, 600, 2, 20.0) \
        == one + count.decode_bytes(cfg, 601, 1, 20.0)


def _context(steps=384, busy=1.0):
    traffic = BENCH.traffic("sd15_512_expand384")
    payload = dict(traffic["payload"], prompt=traffic["cycle"]["prompt"][0])
    status = lambda tokens, routed: {"serving": {"expander": {   # noqa: E731
        "tokens_prefilled": tokens, "decode_steps": 0,
        "expert_tokens": [[routed, 0], [0, 0]]}}}
    return {
        "records": [types.SimpleNamespace(traced=True, payload=payload)],
        "trace": {"modules": {"jit_expand_decode_chunk": busy}},
        "family": files.resolve_family(BENCH.config(CONFIG)),
        "status_before": status(100, 50), "status_after": status(200, 2050),
        "chips": 1, "peak": {"hbm_bytes_per_s": 819e9}, "bench": BENCH,
    }


def test_bytes_util_reader():
    reader = BENCH.load("readers", "bytes_util")
    spec = BENCH.layer_metric("lm_decode_bytes_util")
    context = _context()
    count = BENCH.load("harness", "bytes_laguna")
    cfg = context["family"].expander
    want = 100 * count.decode_bytes(cfg, 512 + 16, 384, 20.0) / 819e9
    assert reader.read(context, **spec["args"]) == pytest.approx(want)
    assert 60 < want < 80       # 1.5 GB a token, 384 tokens, in one second
    # a program without the counter, a slice without the executable
    assert reader.read(dict(context, status_before={"serving": {}}),
                       **spec["args"]) is None
    assert reader.read(dict(context, trace={"modules": {}}),
                       **spec["args"]) is None
    assert reader.read(dict(context, trace=None), **spec["args"]) is None


@pytest.mark.parametrize("classes", ["laguna_decode", "laguna_prefill"])
def test_op_classes_partition_by_flax_module(classes):
    reader = BENCH.load("readers", "op_class_ms")
    rules = BENCH.read("op_classes", classes + ".json")["classes"]
    base = "jit(f)/jit(main)/while/body/DecoderLM/"
    cases = {
        "layers_1/attn/q_proj/dot_general": "linear",
        "layers_0/mlp/down_proj/dot_general": "linear",
        "layers_2/mlp/shared_expert/up_proj/dot_general": "linear",
        "lm_head/dot_general": "linear",
        "layers_4/attn/exp": "attn",
        "layers_1/attn/scatter": "attn",
        "layers_2/mlp/while/body/dot_general": "expert",
        "layers_12/mlp/top_k": "expert",
        "layers_3/mlp/shared_expert/mul": "other",
        "layers_0/mlp/mul": "other",
        "layers_1/input_norm/rsqrt": "other",
        "embed_tokens/gather": "other",
    }
    for scope, want in cases.items():
        row = {"scope": base + scope, "category": "x", "name": "fusion.1"}
        assert reader.classify(row, rules) == want, scope
    assert [r["class"] for r in rules] == ["linear", "attn", "expert",
                                           "other"]
    assert not {"scope", "category", "name"} & set(rules[-1])

"""The prompt-expander cell (``sd15_expand_solo``) rehearsed on the CPU at
tiny widths through the real ``run.py``, and the files it brought: the
components' leaf rule, the op classes, a step's bytes by
``harness/bytes_lm.py`` against a hand count from the published widths. A rehearsal yields counts and
correctness, never a speed."""

import functools
import json

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_expand_solo"
CONFIG = "sd15_laguna_expand"
TRAFFIC = "sd15_512_expand384"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_expander")
BENCH = files.Bench(rehearsal.REPO)


def check_the_traffic_is_what_the_cell_is_named_for():
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


def check_the_configuration_holds_the_published_config_but_for_reduced():
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


def check_the_share_has_5572_million_parameters_and_each_expert_kernel_is_its_own_draw():
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


def check_op_classes_partition_by_flax_module(classes):
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


def traced(m):
    """What the traced rehearsal's per-layer metrics must say."""
    assert m["expert_load_max_over_mean"] >= 1.0
    # on a CPU an expert layer takes the loop
    assert m["expert_kernel_sites"] == 0


def _walker_and_share():
    return (BENCH.load("harness", "bytes_lm"),
            files.resolve_family(BENCH.config(CONFIG)).expander)


def _rows(count, cfg, forked_at, step, sequences=1):
    """The key, value and latent rows one step needs, all layers."""
    terms = count.step_bytes(cfg, forked_at, step, 0.0, sequences)
    return terms["rows_shared"] + terms["rows_own"]


def check_bytes_a_decoded_token_needs_against_a_hand_count():
    """From the published widths: hidden 3 072, 48 query heads on a full
    layer and 72 on a sliding one, 8 key heads of 128, a gate a head."""
    count, cfg = _walker_and_share()
    d = 3072
    full = (d * 48 * 128 + 2 * d * 8 * 128 + 48 * 128 * d + d * 48) * 2
    ring = (d * 72 * 128 + 2 * d * 8 * 128 + 72 * 128 * d + d * 72) * 2
    assert count.mixer_bytes(cfg, 0) == full == 88_375_296
    assert count.mixer_bytes(cfg, 1) == ring == 126_271_488
    dense = 3 * d * 12288 * 2
    beside = (d * 256 + 3 * d * 1024) * 2       # router, shared expert
    head = d * 50176 * 2                        # the held half of the ids
    assert count.mlp_bytes(cfg, 0) == dense == 226_492_416
    assert count.mlp_bytes(cfg, 1) == beside == 20_447_232
    assert count.head_bytes(cfg) == head == 308_281_344
    # attention 44.2 + 3 x 63.1 + 44.2 M, dense MLP 113.2 M, 4 routers and
    # shared experts, the head's 154.1 M: 0.54 + 0.23 + 0.08 + 0.31 GB
    assert count.fixed_bytes(cfg, 1) \
        == 2 * full + 3 * ring + dense + 4 * beside + head + d * 2
    assert round(count.fixed_bytes(cfg, 1) / 1e6) == 1172
    assert count.expert_bytes(cfg) == 3 * 3072 * 1024 * 2
    # keys and values of 8 heads of 128: 4 096 B a position a layer; a
    # ring of 512 never gives more
    row = 2 * 8 * 128 * 2
    assert count.row_bytes(cfg, "full") == row \
        == count.row_bytes(cfg, "sliding")
    assert _rows(count, cfg, 0, 0) == 5 * row
    assert _rows(count, cfg, 899, 0) == (2 * 900 + 3 * 512) * row
    assert count.state_bytes(cfg, "full") == 0
    one = count.decode_bytes(cfg, 600, 1, 20.0)
    assert one == count.fixed_bytes(cfg, 1) + 20 * count.expert_bytes(cfg) \
        + (2 * 601 + 3 * 512) * row
    assert 1.5e9 < one < 1.6e9
    assert count.decode_bytes(cfg, 600, 2, 20.0) \
        == one + count.decode_bytes(cfg, 601, 1, 20.0)


CHECKS = [check_bytes_a_decoded_token_needs_against_a_hand_count,
          check_the_traffic_is_what_the_cell_is_named_for,
          check_the_configuration_holds_the_published_config_but_for_reduced,
          check_the_share_has_5572_million_parameters_and_each_expert_kernel_is_its_own_draw,
          functools.partial(check_op_classes_partition_by_flax_module, 'laguna_decode'),
          functools.partial(check_op_classes_partition_by_flax_module, 'laguna_prefill')]

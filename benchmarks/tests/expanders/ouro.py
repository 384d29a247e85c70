"""The sixth prompt-expander cell (``sd15_ouro_expand_b4``) rehearsed on the
CPU at tiny widths through the real ``run.py``, and the files it brought:
the traffic's token counts, the configuration against the catalog's row key
for key, the readers, the op classes, the metric files, the reference's
recorded readings and a looped step's bytes by
``harness/bytes_lm.py`` against a hand count from the published widths. A rehearsal yields counts and correctness, never a
speed."""

import dataclasses
import functools
import json
import re

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_ouro_expand_b4"
CONFIG = "sd15_ouro_expand"
TRAFFIC = "sd15_256_b4_expand64"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_ouro_expander")
BENCH = files.Bench(rehearsal.REPO)


def check_the_traffic_is_a_batch_behind_an_instruction_of_a_paragraph():
    cell = BENCH.cell(CELL)
    assert cell["config"] == CONFIG and cell["traffic"] == TRAFFIC
    assert cell["chips"] == 1 and cell["mesh"] is None
    assert cell["server_env"] == {"SDTPU_BATCH_LADDER": "4"}
    assert cell["warmup_requests"] == 1
    assert cell["trace"] == {"requests": 2, "max_seconds": 12.0}
    from stable_diffusion_webui_distributed_tpu.models.tokenizer import (
        load_lm_tokenizer,
    )
    model = files.resolve_family(BENCH.config(CONFIG)).expander
    assert model.vocab == (0, 49152)
    tok = load_lm_tokenizer(None, *model.vocab)
    traffic = BENCH.traffic(TRAFFIC)
    old = BENCH.traffic("sd15_512_expand384")
    sibling = BENCH.traffic("sd15_2048_b4_expand256")
    payload = traffic["payload"]
    args = payload["alwayson_scripts"]["prompt expansion"]["args"][0]
    prefix = [tok.bos] + tok.encode(args["instruction"])
    assert len(prefix) == 256
    assert all(0 <= i < 49152 for i in prefix)
    lengths = [len(tok.encode(p)) for p in traffic["cycle"]["prompt"]]
    assert min(lengths) == 16 and max(lengths) == 64
    assert traffic["cycle"] == old["cycle"]
    assert args["max_new_tokens"] == 64 and args["ignore_eos"] is True
    assert args["temperature"] == 1.0 and args["context_chunks"] == 3
    assert traffic["loop"] == "closed" and traffic["clients"] == 1
    assert {k: v for k, v in payload.items() if k != "alwayson_scripts"} \
        == {k: v for k, v in sibling["payload"].items()
            if k != "alwayson_scripts"}
    assert payload["batch_size"] == 4 and payload["steps"] == 20
    # its words are drawn as the sibling's are: random.Random(49) over the
    # sorted set of the words after the siblings' first sentence
    import random

    theirs = old["payload"]["alwayson_scripts"]["prompt expansion"][
        "args"][0]["instruction"].split()
    first = args["instruction"].split()[:14]
    assert first == theirs[:14] and first[-1] == "model."
    words = sorted(set(theirs[14:]))
    assert len(words) == 108
    draw = random.Random(49)
    assert args["instruction"].split()[14:] \
        == [draw.choice(words) for _ in range(255 - 14)]
    # what the timed path sizes from them: one chunk of the prefix, one
    # bucket of the prompt, two chunks of decode steps, capacity 512
    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.pipeline import expand

    assert kv.chunk_bucket(256) == 256 and kv.chunk_bucket(64) == 64
    chunks = -(-(64 - 1) // expand.DECODE_STEPS)
    assert chunks == 2
    assert kv.capacity_for(256 + 64 + chunks * expand.DECODE_STEPS) == 512
    assert BENCH.reference(BENCH.config(CONFIG)).TIMED_POSITIONS \
        == 256 + 64 + 64 == BENCH.config(CONFIG)["reference_latent"]


def check_the_configuration_holds_the_published_config_key_for_key():
    config = BENCH.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(json.loads(line) for line in fh if re.search(
            '"name": "Ouro-2.6B"', line))
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == value, key
    # the cut: none
    assert config["reduced"] == [] and config["published"] == {}
    entry = next(c for c in BENCH.manifest["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    assert len(config["assumed"]) >= 10 and config["counter"] is None
    assert config["components"] == "unet_clip_vae_lm_ouro"
    assert config["reference"] == "ouro_ref" and config["weight_seed"] == 49
    for key in ("held_here", "deployment", "assumed"):
        assert config[key], key
    assert "nothing is divided" in config["deployment"]
    assert config["diffusion"] == BENCH.read("configs", "sd15.json")["model"]
    model = files.resolve_family(config).expander
    assert model.num_layers == config["num_hidden_layers"] == 48
    assert model.layer_types == ("full",) * 48
    assert set(config["layer_types"]) == {"full_attention"}
    assert model.total_ut_steps == config["total_ut_steps"] == 4
    assert model.early_exit_threshold == config["early_exit_threshold"] == 1
    assert model.post_sublayer_norm
    assert model.vocab == (0, config["vocab_size"]) == (0, 49152)
    assert model.expert_layers == () and model.experts == (0, 0)
    assert model.rope_full.theta == config["rope_theta"] == 1e6
    assert model.rope_full.factor == 0 and config["rope_scaling"] is None
    assert model.rope_full.partial_rotary_factor == 1.0
    for ours, theirs in (
            ("hidden_size", "hidden_size"), ("head_dim", "head_dim"),
            ("intermediate_size", "intermediate_size"),
            ("num_kv_heads", "num_key_value_heads"),
            ("rms_norm_eps", "rms_norm_eps")):
        assert getattr(model, ours) == config[theirs], ours
    assert model.num_heads_per_layer == (config["num_attention_heads"],) * 48
    assert model.num_kv_heads == config["num_attention_heads"] == 16
    assert model.attn_gate == "none" and not model.qk_norm
    assert config["tie_word_embeddings"] is False


def check_the_leaf_rules_and_the_models_parameters():
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
        == config["parameters_millions"]["expander"] == 2668
    assert round(total * 2 / 1e9, 2) == 5.34
    assert round((total / 1e6 + config["parameters_millions"]["sd15"])
                 * 2e6 / 1e9, 2) == 7.47
    rules = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        rules[name] = (components.leaf_rule(name, leaf.shape)
                       or weights.leaf_rule(name, leaf.shape)) \
            + (tuple(leaf.shape),)
    # the table at variance 1, the gate at a quarter of the default
    assert rules["embed_tokens/embedding"] == ("draw", 3 ** 0.5,
                                               (49152, 2048))
    assert rules["early_exit_gate/kernel"] \
        == ("draw", 0.5 * (3 / 2048) ** 0.5, (2048, 1))
    assert rules["early_exit_gate/bias"] == ("zeros", 0.0, (1,))
    assert rules["layers_0/attn/q_proj/kernel"] \
        == ("draw", (3 / 2048) ** 0.5, (2048, 2048))
    assert rules["layers_47/mlp/down_proj/kernel"][2] == (5632, 2048)
    assert rules["lm_head/kernel"][2] == (2048, 49152)
    # the norms before the sublayers and the final one at 1, those after
    # them at deviation 0.1: a pass refines the state
    for norm in ("input_norm", "post_attention_norm"):
        assert rules[f"layers_9/{norm}/scale"] == ("ones", 0.0, (2048,))
        assert rules[f"layers_9/{norm}_2/scale"] \
            == ("draw", 0.1 * 3 ** 0.5, (2048,))
    assert rules["norm/scale"] == ("ones", 0.0, (2048,))
    assert not any(part in name for name in rules for part in (
        "router", "experts", "g_proj", "q_norm", "shared_expert"))
    assert components.leaf_rule("text_model/token_embedding/embedding",
                                (49408, 768)) is None
    # a family without a gate is drawn as unet_clip_vae_lm_table draws it
    other = BENCH.load("components", "unet_clip_vae_lm_table")
    assert other.leaf_rule("early_exit_gate/kernel", (2048, 1)) is None
    # the cache the harness traces with: a buffer a layer, a pass axis
    cache = args[3]
    assert len(cache["k"]) == len(cache["v"]) == 48
    assert cache["k"][0].shape == (4, 8, 16, 128)


def _status(steps, decoded, passes):
    return {"serving": {"expander": {
        "tokens_prefilled": 0, "decode_steps": steps,
        "tokens_decoded": decoded, "experts_read": 0,
        "layer_passes": passes}}}


def check_the_counter_metrics_read_the_status_or_nothing():
    ratio = BENCH.load("readers", "status_ratio")
    context = {"status_before": _status(64, 256, 256),
               "status_after": _status(192, 768, 768),
               "bench": BENCH}
    assert ratio.read(context, **BENCH.layer_metric(
        "lm_tokens_per_step")["args"]) == 4.0
    assert ratio.read(context, **BENCH.layer_metric(
        "passes_per_token")["args"]) == 4.0
    # the parent's /internal/status has no layer_passes
    old = {"serving": {"expander": {"decode_steps": 9,
                                    "tokens_decoded": 9}}}
    for name in ("passes_per_token",):
        assert ratio.read({"status_before": old, "status_after": old},
                          **BENCH.layer_metric(name)["args"]) is None


def check_op_classes_partition_by_flax_module(classes):
    reader = BENCH.load("readers", "op_class_ms")
    spec = BENCH.read("op_classes", classes + ".json")
    rules = spec["classes"]
    assert spec["module"] == {
        "ouro_decode": "jit_expand_decode_chunk",
        "ouro_prefill": "jit_expand_prefill"}[classes]
    base = "jit(f)/jit(main)/while/body/DecoderLM/while/body/"
    cases = {
        "layers_0/attn/q_proj/dot_general": "linear",
        "layers_3/attn/k_proj/dot_general": "linear",
        "layers_47/attn/o_proj/dot_general": "linear",
        "layers_6/mlp/gate_proj/dot_general": "linear",
        "layers_6/mlp/up_proj/dot_general": "linear",
        "layers_6/mlp/down_proj/dot_general": "linear",
        "layers_6/mlp/mul": "linear",
        "lm_head/dot_general": "linear",
        "layers_3/attn/exp": "attn",
        "layers_7/attn/vmap(one)/dot_general": "attn",
        "layers_7/attn/dynamic_update_slice": "attn",
        "layers_1/input_norm/rsqrt": "norm",
        "layers_1/input_norm_2/rsqrt": "norm",
        "layers_4/post_attention_norm/rsqrt": "norm",
        "layers_4/post_attention_norm_2/mul": "norm",
        "norm/rsqrt": "norm",
        "embed_tokens/gather": "other",
        "early_exit_gate/dot_general": "other",
        "cumsum": "other",
    }
    for scope, want in cases.items():
        row = {"scope": base + scope, "category": "x", "name": "fusion.1"}
        assert reader.classify(row, rules) == want, scope
    # XLA's asynchronous ops carry the loop's scope and go by name: the
    # copies stream the Linears' kernels, the slices copy a pass's rows
    loose = {"scope": "jit(expand_decode_chunk)/while/body/closed_call/"
                      "DecoderLM/while", "category": "x"}
    assert reader.classify(dict(loose, name="copy-done.7"), rules) \
        == "linear"
    assert reader.classify(dict(loose, name="slice-done.2"), rules) \
        == "attn"
    assert reader.classify(dict(loose, name="slice-start.2"), rules) \
        == "attn"
    assert reader.classify(dict(loose, name="copy.3"), rules) == "other"
    order = [r["class"] for r in rules]
    assert sorted(set(order)) == ["attn", "linear", "norm", "other"]
    assert order[-1] == "other"
    assert not {"scope", "category", "name"} & set(rules[-1])


def check_the_reference_file_holds_the_limits_and_its_readings():
    """What the chip gave (PR 49): the program under both limits at every
    seed read, at the timed positions, and every control over the logits'
    limit wherever it was read."""
    recorded = BENCH.read("reference", CONFIG + ".json")
    limit = recorded["tolerance_relative_rms"]
    assert 0 < limit < 1 and 0 < recorded["tolerance_gates_max_abs"] < 1
    assert recorded["tolerance_reason"] and recorded["tolerance_gates_reason"]
    assert recorded["device"]["platform"] == "tpu"
    assert recorded["latent"] == 256 + 64 + 64
    ref = BENCH.reference(BENCH.config(CONFIG))
    controls = [name for name, _ in ref.CONTROLS]
    assert controls == ["last_pass_cache", "one_pass_fewer", "no_post_norms",
                        "norm_after_last_pass", "control"]
    seeds = recorded["diagnostics"]
    assert len(seeds) >= 2 and len({d["seed"] for d in seeds}) == len(seeds)
    read = set()
    for reading in seeds:
        assert reading["positions"] == 384 and reading["sequences"] == 4
        assert reading["program_vs_reference_relative_rms"] < limit
        assert reading["gates_max_abs_difference"] \
            < recorded["tolerance_gates_max_abs"]
        assert reading["reference_lambda_max"] < 0.9999
        assert reading["reference_rows_by_chosen_pass"][:3] == [0, 0, 0]
        for name in controls:
            if name + ref.READING in reading:
                assert reading[name + ref.READING] > limit, name
                read.add(name)
    assert read == set(controls)


def traced(m):
    """What the traced rehearsal's per-layer metrics must say."""
    assert m["expand_fork_ms"] > 0
    # four images a step: 40 tokens a sequence over two chunks of 32
    assert m["lm_tokens_per_step"] == pytest.approx(4 * 40 / 64)
    # the tiny preset passes its stack three times
    assert m["passes_per_token"] == 3.0


def _walker_and_share():
    return (BENCH.load("harness", "bytes_lm"),
            files.resolve_family(BENCH.config(CONFIG)).expander)


def _rows(count, cfg, forked_at, step, sequences=1):
    """The key, value and latent rows one step needs, all layers."""
    terms = count.step_bytes(cfg, forked_at, step, 0.0, sequences)
    return terms["rows_shared"] + terms["rows_own"]


def check_bytes_a_decode_step_needs_against_a_hand_count():
    """From the published widths: hidden 2 048, 16 heads of 128 with as
    many key heads, a SwiGLU of 5 632, 48 layers run four times."""
    count, cfg = _walker_and_share()
    layer = (4 * 2048 * 2048 + 3 * 2048 * 5632) * 2
    head = 2048 * 49152 * 2
    assert count.mixer_bytes(cfg, 0) + count.mlp_bytes(cfg, 0) == layer \
        == 102_760_448
    assert count.stack_bytes(cfg) == 48 * layer
    assert count.head_bytes(cfg) == head == 201_326_592
    # the stack TIMES the passes, the head once, four table rows
    assert count.fixed_bytes(cfg, 4) == 4 * 48 * layer + head + 4 * 4096
    # 8 192 B of keys and values a position a (layer, pass): 1.5 MiB
    assert 48 * count.row_bytes(cfg, "full") == 192 * 8192 == 3 * 2 ** 19
    # the step at position 329 of four sequences forked at 296: 296 shared
    # rows once and 34 own rows four times, 432 rows where bytes_ouro.py
    # had 4 x 330 = 1 320 (the shared range once a sequence, as before
    # PR 51)
    step = count.step_bytes(cfg, 296, 33, 99.0, 4)
    assert step["rows_shared"] == 296 * 3 * 2 ** 19
    assert step["rows_own"] == 4 * 34 * 3 * 2 ** 19
    assert step["experts"] == 0 and step["states"] == 0  # dense, no state
    assert round(sum(step.values()) / 1e9, 2) == 20.61
    # a model of one pass: a quarter of the stack's reads and of a row
    once = dataclasses.replace(cfg, total_ut_steps=1)
    assert count.fixed_bytes(once, 1) == 48 * layer + head + 4096
    assert 48 * count.row_bytes(once, "full") == 48 * 8192


CHECKS = [check_bytes_a_decode_step_needs_against_a_hand_count,
          check_the_traffic_is_a_batch_behind_an_instruction_of_a_paragraph,
          check_the_configuration_holds_the_published_config_key_for_key,
          check_the_leaf_rules_and_the_models_parameters,
          check_the_counter_metrics_read_the_status_or_nothing,
          functools.partial(check_op_classes_partition_by_flax_module, 'ouro_decode'),
          functools.partial(check_op_classes_partition_by_flax_module, 'ouro_prefill'),
          check_the_reference_file_holds_the_limits_and_its_readings]

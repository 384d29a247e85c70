"""The sixth prompt-expander cell (``sd15_ouro_expand_b4``) rehearsed on the
CPU at tiny widths through the real ``run.py``, and the files it brought:
the traffic's token counts, the configuration against the catalog's row key
for key, the byte count of a looped step against a hand count, the readers,
the op classes, the metric files, the reference's recorded readings. A
rehearsal yields counts and correctness, never a speed."""

import json
import os
import re
import types

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_ouro_expand_b4"
CONFIG = "sd15_ouro_expand"
TRAFFIC = "sd15_256_b4_expand64"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_ouro_expander")
NEW = ["ou_expand_ms", "ou_expand_prefill_ms", "ou_expand_decode_ms",
       "ou_expand_fork_ms", "ou_linear_device_ms", "ou_attn_device_ms",
       "ou_norm_device_ms", "ou_other_device_ms", "ou_decode_bytes_util",
       "ou_passes_per_token", "ou_tokens_per_step",
       "ou_cache_mib_per_position"]
#: read from what only a TPU's trace or memory_stats() holds
CHIP_ONLY = {"peak_hbm_gib"} | {n for n in NEW if "device" in n
                                or "bytes" in n}
BENCH = files.Bench(rehearsal.REPO)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rehearsal.make_root(str(tmp_path_factory.mktemp("ou")))
    rehearsal._rewrite(
        os.path.join(root, "benchmarks", "configs", CONFIG + ".json"),
        lambda c: c.update(factory=TINY_FACTORY, policy="F32"))

    def shorter(traffic):
        args = traffic["payload"]["alwayson_scripts"][
            "prompt expansion"]["args"][0]
        args.update(max_new_tokens=40, context_chunks=1,
                    instruction=" ".join(args["instruction"].split()[:30]))

    rehearsal._rewrite(
        os.path.join(root, "benchmarks", "traffic", TRAFFIC + ".json"),
        shorter)
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
        assert m["ou_expand_ms"] > m["ou_expand_decode_ms"] > 0
        assert m["ou_expand_prefill_ms"] > 0 and m["ou_expand_fork_ms"] > 0
        # the other expanders' metrics list their own cells
        assert not {"expand_ms", "m2_expand_ms", "m2_tokens_per_step",
                    "expert_kernel_sites", "lm_linear_device_ms"} & set(m)
        # four images a step: 40 tokens a sequence over two chunks of 32
        assert m["ou_tokens_per_step"] == pytest.approx(4 * 40 / 64)
        # the tiny preset passes its stack three times
        assert m["ou_passes_per_token"] == 3.0
        # 4 layers x 3 passes x (k + v) x 4 heads x 16 x float32, over the
        # metric's own divisor (4 sequences at the published capacity 512;
        # the rehearsal's capacity is 256)
        tiny_position = 4 * 3 * 2 * 4 * 16 * 4
        assert m["ou_cache_mib_per_position"] == pytest.approx(
            tiny_position * 256 / 512 / 2 ** 20)


def test_the_traffic_is_a_batch_behind_an_instruction_of_a_paragraph():
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


def test_the_configuration_holds_the_published_config_key_for_key():
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


def test_the_leaf_rules_and_the_models_parameters():
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


def test_bytes_a_decode_step_needs_against_a_hand_count():
    count = BENCH.load("harness", "bytes_ouro")
    cfg = files.resolve_family(BENCH.config(CONFIG)).expander
    d = 2048
    layer = (4 * d * 2048 + 3 * d * 5632) * 2
    head = d * 49152 * 2
    assert count.layer_bytes(cfg, 0) == layer == 102_760_448
    assert count.stack_bytes(cfg) == 48 * layer
    assert round(48 * layer / 1e9, 2) == 4.93
    assert count.head_bytes(cfg) == head == 201_326_592
    # the stack TIMES the passes, the head once
    assert count.fixed_bytes(cfg) == 4 * 48 * layer + head
    assert round(count.fixed_bytes(cfg) / 1e9, 2) == 19.93
    # 8 192 B of keys and values a row a slot, 192 slots: 1.5 MiB
    assert count.cache_bytes(cfg, 0) == 192 * 8192 == 3 * 2 ** 19
    assert count.cache_bytes(cfg, 329) == 330 * 192 * 8192
    # a step of four sequences at 330 positions: about 22 GB
    four = count.decode_bytes(cfg, 329, 1, 0.0, 4)
    assert four == count.fixed_bytes(cfg) + 4 * 330 * 192 * 8192
    assert 21.9e9 < four < 22.1e9
    assert round(4 * count.cache_bytes(cfg, 329) / 1e9, 2) == 2.08
    # what the reader hands in for the experts counts for nothing
    assert count.decode_bytes(cfg, 329, 1, 99.0, 4) == four
    # one sequence a step would stream the same weights for one token
    alone = count.decode_bytes(cfg, 329, 1, 0.0, 1)
    assert 0.9 < alone / four < 0.95
    assert count.decode_bytes(cfg, 320, 2, 0.0, 4) == pytest.approx(
        count.decode_bytes(cfg, 320, 1, 0.0, 4)
        + count.decode_bytes(cfg, 321, 1, 0.0, 4))
    # a model of one pass: a quarter of the stack's reads, as its siblings
    import dataclasses

    once = dataclasses.replace(cfg, total_ut_steps=1)
    assert count.fixed_bytes(once) == 48 * layer + head
    assert count.cache_bytes(once, 0) == 48 * 8192


def _status(steps, decoded, passes, full=0):
    return {"serving": {"expander": {
        "tokens_prefilled": 0, "decode_steps": steps,
        "tokens_decoded": decoded, "experts_read": 0,
        "layer_passes": passes, "state_bytes": {"full": full,
                                                "sliding": 0}}}}


def test_bytes_util_steps_reads_the_programs_counters():
    reader = BENCH.load("readers", "bytes_util_steps")
    spec = BENCH.layer_metric("ou_decode_bytes_util")
    assert spec["reader"] == "bytes_util_steps"
    traffic = BENCH.traffic(TRAFFIC)
    payload = dict(traffic["payload"], prompt=traffic["cycle"]["prompt"][0])
    context = {
        "records": [types.SimpleNamespace(traced=True, payload=payload)],
        "trace": {"modules": {"jit_expand_decode_chunk": 1.9}},
        "family": files.resolve_family(BENCH.config(CONFIG)),
        # two requests of 64 steps, four tokens a step
        "status_before": _status(64, 256, 256),
        "status_after": _status(192, 768, 768),
        "chips": 1, "peak": {"hbm_bytes_per_s": 819e9}, "bench": BENCH,
    }
    count = BENCH.load("harness", "bytes_ouro")
    cfg = context["family"].expander
    want = 100 * count.decode_bytes(cfg, 256 + 16, 64, 0.0, 4.0) \
        / (1.9 * 819e9)
    assert reader.read(context, **spec["args"]) == pytest.approx(want)
    assert 85 < want < 95       # 22 GB a step, 64 steps, in 1.9 seconds
    assert reader.read(dict(context, trace=None), **spec["args"]) is None
    # the parent's status has no experts_read under an expander it cannot
    # build anyway: nothing to read, no raise
    old = {"serving": {"expander": {"decode_steps": 9,
                                    "tokens_decoded": 9}}}
    assert reader.read(dict(context, status_before=old, status_after=old),
                       **spec["args"]) is None


def test_the_counter_metrics_read_the_status_or_nothing():
    ratio = BENCH.load("readers", "status_ratio")
    context = {"status_before": _status(64, 256, 256),
               "status_after": _status(192, 768, 768, full=3 * 2 ** 30),
               "bench": BENCH}
    assert ratio.read(context, **BENCH.layer_metric(
        "ou_tokens_per_step")["args"]) == 4.0
    assert ratio.read(context, **BENCH.layer_metric(
        "ou_passes_per_token")["args"]) == 4.0
    scaled = BENCH.load("readers", "status_scaled")
    spec = BENCH.layer_metric("ou_cache_mib_per_position")
    assert spec["reader"] == "status_scaled"
    # four sequences at the capacity of 512: 3 GiB, 1.5 MiB a position
    assert scaled.read(context, **spec["args"]) == pytest.approx(1.5)
    # the parent's /internal/status has no layer_passes
    old = {"serving": {"expander": {"decode_steps": 9,
                                    "tokens_decoded": 9}}}
    for name in ("ou_passes_per_token",):
        assert ratio.read({"status_before": old, "status_after": old},
                          **BENCH.layer_metric(name)["args"]) is None
    for status in (old, {}, {"serving": {}}):
        assert scaled.read({"status_after": status, "bench": BENCH},
                           **spec["args"]) is None


@pytest.mark.parametrize("classes", ["ouro_decode", "ouro_prefill"])
def test_op_classes_partition_by_flax_module(classes):
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
    if spec["reader"] == "bytes_util_steps":
        assert hasattr(BENCH.load("harness", spec["args"]["needs"]),
                       "decode_bytes")
        from stable_diffusion_webui_distributed_tpu.pipeline import expand

        assert spec["args"]["steps_per_call"] == expand.DECODE_STEPS


def test_the_reference_file_holds_the_limits_and_its_readings():
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

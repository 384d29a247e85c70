"""The eighth prompt-expander cell (``sd15_gigachat35_expand_b4``) rehearsed
on the CPU at tiny widths through the real ``run.py``, and the files it
brought: the configuration against the catalog's row, the leaf rules, the
byte count of a forked step over both kinds of state against a hand count,
the readers, the op classes, the metric files. A rehearsal yields counts
and correctness, never a speed."""

import json
import math
import os
import re
import types

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_gigachat35_expand_b4"
CONFIG = "sd15_gigachat35_expand"
TRAFFIC = "sd15_2048_b4_expand256"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_gigachat35_expander")
#: six, not the seventeen ISSUE 56 lists: BENCHMARK.json may hold 128
#: per-layer metrics and held 122 (PERF.md section 7 names the eleven left
#: out and where each can be read)
NEW = ["g35_delta_device_ms", "g35_decode_bytes_util",
       "g35_delta_forked_sites", "g35_latent_forked_sites",
       "g35_expert_kernel_sites", "g35_tokens_per_step"]
#: read from what only a TPU's trace or memory_stats() holds
CHIP_ONLY = {"peak_hbm_gib"} | {n for n in NEW if "device" in n
                                or "bytes" in n}
BENCH = files.Bench(rehearsal.REPO)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rehearsal.make_root(str(tmp_path_factory.mktemp("g35")))
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
    assert result["attempted"] >= (1 if trace else 2)   # a slow CPU
    kind = "per_layer" if trace else "end_to_end"
    reported = set(result["metrics"])
    assert reported <= metric_names(kind)
    assert metric_names(kind) - reported <= CHIP_ONLY | {
        "between_requests_ms"}      # a window of one request has no gap
    assert "raised" not in output
    assert "nothing compiled inside the window" in output
    if trace:
        m = {k: v["value"] for k, v in result["metrics"].items()}
        # the other expanders' metrics list their own cells
        assert not {"expand_ms", "k2_expand_ms", "m2_expand_ms",
                    "k2_latent_forked_sites"} & set(m)
        # four images a step: 40 tokens a sequence over two chunks of 32
        assert m["g35_tokens_per_step"] == pytest.approx(4 * 40 / 64)
        # the tiny share is the published one's five layers
        assert m["g35_delta_forked_sites"] == 4
        assert m["g35_latent_forked_sites"] == 1
        assert m["g35_expert_kernel_sites"] == 0      # a CPU
        assert m["expand_ahead_ms"] > 0


def test_the_traffic_file_is_the_sibling_cells_unchanged():
    cell = BENCH.cell(CELL)
    for sibling in ("sd15_mellum2_expand_b4", "sd15_kanana2_expand_b4"):
        other = BENCH.cell(sibling)
        assert cell["traffic"] == TRAFFIC == other["traffic"]
        for key in ("server_env", "warmup_requests", "trace", "mesh"):
            assert cell[key] == other[key], key
    assert cell["config"] == CONFIG and cell["chips"] == 1
    why = BENCH.read("workloads", CELL + ".json")["why"]
    assert "eight times" in why and "outweigh their deployment share" in why
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
    assert len(prefix) == 2048 and all(0 <= i < 16032 for i in prefix)
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


def test_the_configuration_holds_the_published_config_but_for_reduced():
    config = BENCH.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(json.loads(line) for line in fh if re.search(
            '"name": "GigaChat3.5-432B-A28B"', line))
    assert config["source"] == row["source_url"]
    entry = next(c for c in BENCH.manifest["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 16032)
    # the guide's floors: a whole period and four layers after the dense
    # ones, at least 8 experts, at least an eighth of the vocabulary
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert "sixteen chips share each layer" in config["deployment"]
    listed = " ".join(config["assumed"])
    for reading in ("2 sigmoid(w)", "pre_post", "swiglu_limit", "(1 + w_o)",
                    "g_proj", "noaux_tc", "WITHOUT bias", "column order",
                    "float32", "next-token modules", "A_log",
                    "variance 1"):
        assert reading in listed, reading
    # no width is changed: the program's share has the published ones
    share = files.resolve_family(config).expander
    assert (share.hidden_size, share.intermediate_size,
            share.moe_intermediate_size, share.num_experts_per_tok,
            share.num_experts) == (7168, 18432, 2048, 8, 256)
    assert (share.q_lora_rank, share.kv_lora_rank, share.qk_nope_head_dim,
            share.qk_rope_head_dim, share.v_head_dim) \
        == (1536, 512, 128, 64, 128)
    assert (share.linear_num_key_heads, share.linear_num_value_heads,
            share.linear_key_head_dim, share.linear_value_head_dim,
            share.linear_conv_kernel) == (32, 64, 128, 128, 4)
    assert share.experts == (0, 16) and share.vocab == (0, 16032)
    assert share.layer_types == ("linear", "latent", "linear", "linear",
                                 "linear")


def test_the_leaf_rules():
    components = BENCH.components(BENCH.config(CONFIG))
    assert components.leaf_rule("embed_tokens/embedding", (16032, 7168)) \
        == ("draw", math.sqrt(3.0))
    assert components.leaf_rule("layers_0/delta/A_log", (64,)) \
        == ("draw", 4.0)
    assert components.leaf_rule("layers_0/delta/conv_kernel", (4, 16384)) \
        == ("draw", math.sqrt(3.0 / 4))
    kind, width = components.leaf_rule(
        "layers_1/mlp/e_score_correction_bias", (256,))
    assert kind == "draw" and width == pytest.approx(0.1 * math.sqrt(3))
    for path in ("layers_1/input_norm_2/weight", "norm/weight",
                 "layers_0/delta/norm/weight", "layers_1/attn/q_a_norm/weight"):
        kind, width = components.leaf_rule(path, (7168,))
        assert kind == "draw" and width == pytest.approx(0.5 * math.sqrt(3))
    kind, width = components.leaf_rule("layers_1/mlp/experts/w_down",
                                       (16, 2048, 7168))
    assert kind == "draw" and width == pytest.approx(
        math.sqrt(3.0 / 2048), rel=1e-6)
    assert components.leaf_rule("layers_1/mlp/router", (7168, 256)) \
        == ("draw", math.sqrt(3.0 / 7168))
    # a Linear, dt_bias and the other components' leaves keep the default
    assert components.leaf_rule("layers_0/delta/qkvz_proj/kernel",
                                (7168, 24576)) is None
    assert components.leaf_rule("layers_0/delta/dt_bias", (64,)) is None


def test_bytes_a_forked_step_needs_against_a_hand_count():
    count = BENCH.load("harness", "bytes_gigachat35")
    cfg = files.resolve_family(BENCH.config(CONFIG)).expander
    d = 7168
    delta = (d * 24576 + d * 128 + 8192 * d) * 2
    assert count.linear_layer_bytes(cfg) == delta
    attn = (1536 * (d + 64 * 192) + d * 576 + 512 * 64 * 256 + 8192 * d
            + d * 8192) * 2
    assert count.latent_layer_bytes(cfg, 1) == attn
    dense = 3 * d * 18432 * 2
    beside = (d * 256 + 3 * d * 2048) * 2       # router, shared expert
    head = d * 16032 * 2
    assert count.fixed_bytes(cfg) == 4 * delta + attn + dense \
        + 4 * beside + head
    assert round(count.fixed_bytes(cfg) / 1e9, 2) == 3.60
    assert round(4 * delta / 1e9, 2) == 1.89 and round(dense / 1e9, 2) \
        == 0.79 and round(attn / 1e9, 2) == 0.32
    assert count.expert_bytes(cfg) == 3 * d * 2048 * 2 == 88_080_384
    assert count.row_bytes(cfg) == 576 * 2
    state = 4 * 4 * (64 * 128 * 128 + 3 * 16384)
    assert count.state_bytes(cfg) == state
    # a step of four under even routing: 1.91 distinct held experts a layer
    even = 16 * (1 - (1 - 8 / 256) ** 4)
    assert round(even, 2) == 1.91
    step = count.decode_bytes(cfg, 2112, 1, 4 * even, 4)
    assert step == pytest.approx(
        count.fixed_bytes(cfg) + 4 * even * 88_080_384 + 2 * 4 * state
        + (2112 + 4) * 1152)
    assert 4.39e9 < step < 4.43e9
    assert round(step / (0.88 * 819e9) * 1e3, 1) == 6.1      # ms a step
    # the linear layers, mixers and states, are 46 % of a step's bytes
    assert round((4 * delta + 8 * state) / step, 2) == 0.46
    whole = count.decode_bytes(cfg, 2112, 256, 4 * even, 4)
    rows = 256 * 2112 + 4 * 256 * 257 / 2
    assert whole == pytest.approx(
        256 * (count.fixed_bytes(cfg) + 4 * even * 88_080_384 + 8 * state)
        + rows * 1152)
    # one image after the other streams the fixed weights four times
    alone = 4 * count.decode_bytes(cfg, 2112, 1, 4 * 0.5, 1)
    assert 14.5e9 < alone < 15.5e9
    # the latent layers' bytes are the sibling's count of the same shapes
    other = files.resolve_family(BENCH.config("sd15_xing4_expand")).expander
    assert count.latent_layer_bytes(other, 0) \
        == BENCH.load("harness", "bytes_xing4").latent_layer_bytes(other, 0)


def _status(steps, decoded, read):
    return {"serving": {"expander": {
        "tokens_prefilled": 0, "decode_steps": steps,
        "tokens_decoded": decoded, "experts_read": read, "sequences": 0,
        "expert_tokens": [[0, 0], [0, 0]]}}}


def test_bytes_util_steps_reads_the_programs_counters():
    reader = BENCH.load("readers", "bytes_util_steps")
    spec = BENCH.layer_metric("g35_decode_bytes_util")
    assert spec["reader"] == "bytes_util_steps"
    traffic = BENCH.traffic(TRAFFIC)
    payload = dict(traffic["payload"], prompt=traffic["cycle"]["prompt"][0])
    context = {
        "records": [types.SimpleNamespace(traced=True, payload=payload)],
        "trace": {"modules": {"jit_expand_decode_chunk": 1.6}},
        "family": files.resolve_family(BENCH.config(CONFIG)),
        # two requests of 256 steps, four tokens and 7.6 distinct experts
        # a step
        "status_before": _status(256, 1024, 4000),
        "status_after": _status(768, 3072, 4000 + 3891),
        "chips": 1, "peak": {"hbm_bytes_per_s": 819e9}, "bench": BENCH,
    }
    count = BENCH.load("harness", "bytes_gigachat35")
    cfg = context["family"].expander
    want = 100 * count.decode_bytes(cfg, 2048 + 16, 256, 3891 / 512, 4.0) \
        / (1.6 * 819e9)
    assert reader.read(context, **spec["args"]) == pytest.approx(want)
    assert 80 < want < 95       # 4.4 GB a step, 256 steps, in 1.6 seconds
    assert reader.read(dict(context, trace=None), **spec["args"]) is None
    # a status without the counters (the parent cannot run the cell): None
    old = {"serving": {"expander": {"decode_steps": 9, "tokens_decoded": 9}}}
    assert reader.read(dict(context, status_before=old, status_after=old),
                       **spec["args"]) is None


def test_the_ratio_and_value_metrics_read_the_status_or_nothing():
    ratio = BENCH.load("readers", "status_ratio")
    context = {"status_before": _status(256, 1024, 4000),
               "status_after": _status(768, 3072, 7891)}
    assert ratio.read(context, **BENCH.layer_metric(
        "g35_tokens_per_step")["args"]) == 4.0
    assert ratio.read({"status_before": {}, "status_after": {}},
                      **BENCH.layer_metric(
                          "g35_tokens_per_step")["args"]) is None
    value = BENCH.load("readers", "status_value")
    status = {"serving": {
        "attention": {"latent_forked": 1, "xla": 3},
        "expander": {"expert_products": {"kernel": 4},
                     "delta_mixers": {"recurrent": 0, "chunked": 8,
                                      "recurrent_forked": 4}}}}
    for name, want in (("g35_latent_forked_sites", 1),
                       ("g35_expert_kernel_sites", 4),
                       ("g35_delta_forked_sites", 4)):
        assert value.read({"status_before": status},
                          **BENCH.layer_metric(name)["args"]) == want
    # a program without the counter (the parent): nothing, and no raise
    bare = {"serving": {"attention": {"xla": 3}, "expander": {}}}
    for name in ("g35_latent_forked_sites", "g35_delta_forked_sites"):
        assert value.read({"status_before": bare},
                          **BENCH.layer_metric(name)["args"]) is None


@pytest.mark.parametrize("classes", ["gigachat35_decode",
                                     "gigachat35_prefill"])
def test_op_classes_partition_by_flax_module(classes):
    reader = BENCH.load("readers", "op_class_ms")
    spec = BENCH.read("op_classes", classes + ".json")
    lm = "jit(f)/DecoderLM/layers_{}/{}"
    rows = {
        lm.format(0, "delta/qkvz_proj/dot_general"): "linear",
        lm.format(2, "delta/out_proj/dot_general"): "linear",
        lm.format(1, "attn/g_proj/dot_general"): "linear",
        lm.format(1, "attn/q_b_proj/dot_general"): "linear",
        lm.format(0, "mlp/down_proj/dot_general"): "linear",
        lm.format(3, "mlp/shared_expert/up_proj/dot_general"): "linear",
        "jit(f)/DecoderLM/lm_head/dot_general": "linear",
        lm.format(2, "delta/mul"): "delta",
        lm.format(0, "delta/norm/rsqrt"): "delta",
        lm.format(4, "delta/reduce_sum"): "delta",
        lm.format(1, "attn/kv_a_norm/mul"): "latent",
        lm.format(1, "attn/dot_general"): "latent",
        lm.format(1, "attn/logistic"): "latent",
        lm.format(1, "mlp/dot_general"): "expert",
        lm.format(4, "mlp/experts/pallas_call"): "expert",
        lm.format(0, "mlp/mul"): "other",           # the dense layer
        lm.format(3, "mlp/shared_expert/mul"): "other",
        lm.format(2, "input_norm_2/mul"): "other",
        "jit(f)/DecoderLM/norm/mul": "other",
    }
    table = [{"module": spec["module"], "scope": scope, "category": "x",
              "name": "fusion", "seconds": 1.0} for scope in rows]
    table.append({"module": spec["module"], "scope": "", "category": "x",
                  "name": "copy-done.3", "seconds": 1.0})
    table.append({"module": "jit_other", "scope": lm.format(0, "delta/mul"),
                  "category": "x", "name": "fusion", "seconds": 9.0})
    for row, want in zip(table, list(rows.values()) + ["linear"]):
        assert reader.classify(row, spec["classes"]) == want, row["scope"]
    context = {"trace": {"op_table": table}, "bench": BENCH,
               "records": [types.SimpleNamespace(traced=True)]}
    sums = reader.by_class(context, classes)
    assert sum(sums.values()) == len(rows) + 1      # a partition
    assert sums["delta"] == 3.0
    if classes == "gigachat35_decode":
        assert reader.read(context, **BENCH.layer_metric(
            "g35_delta_device_ms")["args"]) == 3000.0
    assert reader.read({"trace": None, "records": [], "bench": BENCH},
                       classes, "delta") is None


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_names_a_reader_and_a_layer_that_exist(name):
    spec = BENCH.layer_metric(name)
    entry = next(m for m in BENCH.manifest["per_layer"]
                 if m["name"] == name)
    assert entry["workloads"] == [CELL]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["moves"] == "request_p50_s"
    assert os.path.exists(BENCH.path("readers", spec["reader"] + ".py"))
    layers = {m["layer"] for m in BENCH.manifest["per_layer"]
              if not m["name"].startswith("g35_")}
    assert entry["layer"] in layers
    if "classes" in spec["args"]:
        assert os.path.exists(BENCH.path(
            "op_classes", spec["args"]["classes"] + ".json"))
    if "needs" in spec["args"]:
        assert os.path.exists(BENCH.path(
            "harness", spec["args"]["needs"] + ".py"))


def test_the_manifest_gains_one_configuration_one_cell_and_six_metrics():
    manifest = BENCH.manifest
    assert manifest["configs"][-1]["name"] == CONFIG
    assert manifest["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
        "why": manifest["workloads"][-1]["why"]}
    assert [m["name"] for m in manifest["per_layer"][-6:]] == NEW
    assert len(manifest["per_layer"]) == 128        # the manifest's limit
    assert len(manifest["configs"]) == 9 and len(manifest["workloads"]) == 10
    for name in ("between_requests_ms", "expand_ahead_ms"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"][-1] == CELL


def test_the_reference_file_holds_both_limits_and_three_seeds():
    recorded = BENCH.read("reference", CONFIG + ".json")
    overall = recorded["tolerance_relative_rms"]
    held = recorded["tolerance_held_to_routing_relative_rms"]
    assert recorded["passed"] is True and recorded["latent"] == 2368
    assert recorded["device"]["platform"] == "tpu"
    seeds = recorded["diagnostics"]
    assert len(seeds) == 3 and len({d["seed"] for d in seeds}) == 3
    own = "program_vs_reference_held_to_its_routing_relative_rms"
    suffix = "_vs_reference_held_to_the_programs_routing_relative_rms"
    for d in seeds:
        assert d["positions"] == 2368 and d["sequences"] == 4
        assert d["program_vs_reference_relative_rms"] < overall \
            < d["control_vs_reference_relative_rms"]
        assert d[own] < held
        for control in ("control", "state_bf16"):
            assert d[control + suffix] > held, control

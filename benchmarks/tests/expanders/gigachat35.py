"""The eighth prompt-expander cell (``sd15_gigachat35_expand_b4``) rehearsed
on the CPU at tiny widths through the real ``run.py``, and the files it
brought: the configuration against the catalog's row, the leaf rules, the
readers, the op classes, the metric files (a step's bytes by ``harness/bytes_lm.py``
against a hand count from the published widths). A rehearsal yields counts and correctness, never a
speed."""

import dataclasses
import functools
import json
import math
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
BENCH = files.Bench(rehearsal.REPO)


def check_the_traffic_file_is_the_sibling_cells_unchanged():
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


def check_the_configuration_holds_the_published_config_but_for_reduced():
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


def check_the_leaf_rules():
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


def _status(steps, decoded, read):
    return {"serving": {"expander": {
        "tokens_prefilled": 0, "decode_steps": steps,
        "tokens_decoded": decoded, "experts_read": read, "sequences": 0,
        "expert_tokens": [[0, 0], [0, 0]]}}}


def check_the_ratio_and_value_metrics_read_the_status_or_nothing():
    ratio = BENCH.load("readers", "status_ratio")
    context = {"status_before": _status(256, 1024, 4000),
               "status_after": _status(768, 3072, 7891)}
    assert ratio.read(context, **BENCH.layer_metric(
        "lm_tokens_per_step")["args"]) == 4.0
    assert ratio.read({"status_before": {}, "status_after": {}},
                      **BENCH.layer_metric(
                          "lm_tokens_per_step")["args"]) is None
    value = BENCH.load("readers", "status_value")
    status = {"serving": {
        "attention": {"latent_forked": 1, "xla": 3},
        "expander": {"expert_products": {"kernel": 4},
                     "delta_mixers": {"recurrent": 0, "chunked": 8,
                                      "recurrent_forked": 4}}}}
    for name, want in (("latent_forked_sites", 1),
                       ("expert_kernel_sites", 4),
                       ("delta_forked_sites", 4)):
        assert value.read({"status_before": status},
                          **BENCH.layer_metric(name)["args"]) == want
    # a program without the counter (the parent): nothing, and no raise
    bare = {"serving": {"attention": {"xla": 3}, "expander": {}}}
    for name in ("latent_forked_sites", "delta_forked_sites"):
        assert value.read({"status_before": bare},
                          **BENCH.layer_metric(name)["args"]) is None


def check_op_classes_partition_by_flax_module(classes):
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
        # the metric names no file: the configuration's stem finds it
        assert BENCH.layer_metric("lm_delta_device_ms")["args"] \
            == {"cls": "delta"}
        assert reader.read(dict(context, config=BENCH.config(CONFIG)),
                           **BENCH.layer_metric(
                               "lm_delta_device_ms")["args"]) == 3000.0
        assert reader.read(context, **BENCH.layer_metric(
            "lm_delta_device_ms")["args"]) is None     # no configuration
    assert reader.read({"trace": None, "records": [], "bench": BENCH},
                       classes, "delta") is None


def check_the_reference_file_holds_both_limits_and_three_seeds():
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


def traced(m):
    """What the traced rehearsal's per-layer metrics must say."""
    assert m["expand_fork_ms"] > 0
    # four images a step: 40 tokens a sequence over two chunks of 32
    assert m["lm_tokens_per_step"] == pytest.approx(4 * 40 / 64)
    # the tiny share is the published one's five layers
    assert m["delta_forked_sites"] == 4
    assert m["latent_forked_sites"] == 1
    assert m["expert_kernel_sites"] == 0      # a CPU
    assert m["experts_read_per_step"] > 0
    assert 1.5 < m["fork_rows_attended_per_row_read"] < 4


def _walker_and_share():
    return (BENCH.load("harness", "bytes_lm"),
            files.resolve_family(BENCH.config(CONFIG)).expander)


def _rows(count, cfg, forked_at, step, sequences=1):
    """The key, value and latent rows one step needs, all layers."""
    terms = count.step_bytes(cfg, forked_at, step, 0.0, sequences)
    return terms["rows_shared"] + terms["rows_own"]


def check_bytes_a_forked_step_needs_against_a_hand_count():
    """From the published widths: hidden 7 168; a delta layer of 32 key
    and 64 value heads of 128 (conv over 16 384 channels, 4 taps); latent
    attention behind a query latent of 1 536, gated element by element."""
    count, cfg = _walker_and_share()
    d = 7168
    # qkvz_proj, ba_proj, out_proj and what bytes_gigachat35.py left out:
    # the taps, A_log and dt_bias
    delta = (d * 24576 + d * 128 + 8192 * d + 4 * 16384 + 128) * 2
    assert count.mixer_bytes(cfg, 0) == delta == 471_728_384
    attn = (1536 * (d + 64 * 192) + d * 576 + 512 * 64 * 256 + 8192 * d
            + d * 8192) * 2
    assert count.mixer_bytes(cfg, 1) == attn == 319_684_608
    dense = 3 * d * 18432 * 2
    beside = (d * 256 + 256 + 3 * d * 2048) * 2  # router, bias, shared
    head = d * 16032 * 2
    assert count.mlp_bytes(cfg, 0) == dense and count.mlp_bytes(cfg, 1) == beside
    assert count.fixed_bytes(cfg, 4) == 4 * delta + attn + dense \
        + 4 * beside + head + 4 * d * 2
    assert round(count.fixed_bytes(cfg, 4) / 1e9, 2) == 3.60
    assert round(4 * delta / 1e9, 2) == 1.89 and round(dense / 1e9, 2) \
        == 0.79 and round(attn / 1e9, 2) == 0.32
    assert count.expert_bytes(cfg) == 3 * d * 2048 * 2 == 88_080_384
    assert count.row_bytes(cfg, "latent") == 576 * 2
    assert count.row_bytes(cfg, "linear") == 0
    # S (64, 128, 128) and three rows of 16 384 inputs, float32, a layer a
    # sequence: four layers, four sequences
    state = 4 * 4 * 4 * (64 * 128 * 128 + 3 * 16384)
    assert count.step_bytes(cfg, 2112, 0, 0.0, 4)["states"] == 2 * state
    # a step of four under even routing: 1.91 distinct held experts a layer
    even = 16 * (1 - (1 - 8 / 256) ** 4)
    assert round(even, 2) == 1.91
    step = count.decode_bytes(cfg, 2112, 1, 4 * even, 4)
    assert step == pytest.approx(
        count.fixed_bytes(cfg, 4) + 4 * even * 88_080_384 + 2 * state
        + (2112 + 4) * 1152)
    assert 4.39e9 < step < 4.43e9
    assert round(step / (0.88 * 819e9) * 1e3, 1) == 6.1      # ms a step
    # the linear layers, mixers and states, are 46 % of a step's bytes
    assert round((4 * delta + 2 * state) / step, 2) == 0.46
    whole = count.decode_bytes(cfg, 2112, 256, 4 * even, 4)
    rows = 256 * 2112 + 4 * 256 * 257 / 2
    assert whole == pytest.approx(
        256 * (count.fixed_bytes(cfg, 4) + 4 * even * 88_080_384 + 2 * state)
        + rows * 1152)
    # one image after the other streams the fixed weights four times
    alone = 4 * count.decode_bytes(cfg, 2112, 1, 4 * 0.5, 1)
    assert 14.5e9 < alone < 15.5e9
    # the latent layer's bytes are the sibling's count of the same shapes
    other = files.resolve_family(BENCH.config("sd15_xing4_expand")).expander
    x = other.hidden_size
    assert count.mixer_bytes(dataclasses.replace(other, residual_streams=1),
                             0) \
        == (x * 768 + 768 * 6144 + x * 576 + 512 * 8192 + 4096 * x) * 2


CHECKS = [check_bytes_a_forked_step_needs_against_a_hand_count,
          check_the_traffic_file_is_the_sibling_cells_unchanged,
          check_the_configuration_holds_the_published_config_but_for_reduced,
          check_the_leaf_rules,
          check_the_ratio_and_value_metrics_read_the_status_or_nothing,
          functools.partial(check_op_classes_partition_by_flax_module, 'gigachat35_decode'),
          functools.partial(check_op_classes_partition_by_flax_module, 'gigachat35_prefill'),
          check_the_reference_file_holds_both_limits_and_three_seeds]

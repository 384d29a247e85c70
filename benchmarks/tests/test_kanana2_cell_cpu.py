"""The seventh prompt-expander cell (``sd15_kanana2_expand_b4``) rehearsed
on the CPU at tiny widths through the real ``run.py``, and the files it
brought: the configuration against the catalog's row, the leaf rules, the
byte count of a forked latent step against a hand count, the readers, the
op classes, the metric files. A rehearsal yields counts and correctness,
never a speed."""

import json
import os
import re
import types

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_kanana2_expand_b4"
CONFIG = "sd15_kanana2_expand"
TRAFFIC = "sd15_2048_b4_expand256"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_kanana2_expander")
NEW = ["k2_expand_ms", "k2_expand_prefill_ms", "k2_expand_fork_ms",
       "k2_expand_decode_ms", "k2_linear_device_ms", "k2_latent_device_ms",
       "k2_expert_device_ms", "k2_other_device_ms", "k2_decode_bytes_util",
       "k2_latent_forked_sites", "k2_expert_kernel_sites",
       "k2_experts_read_per_step", "k2_tokens_per_step",
       "k2_fork_rows_attended_per_row_read"]
#: read from what only a TPU's trace or memory_stats() holds
CHIP_ONLY = {"peak_hbm_gib"} | {n for n in NEW if "device" in n
                                or "bytes" in n}
BENCH = files.Bench(rehearsal.REPO)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rehearsal.make_root(str(tmp_path_factory.mktemp("k2")))
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
    assert metric_names(kind) - reported <= CHIP_ONLY
    assert "raised" not in output
    assert "nothing compiled inside the window" in output
    if trace:
        m = {k: v["value"] for k, v in result["metrics"].items()}
        assert m["k2_expand_ms"] > m["k2_expand_decode_ms"] > 0
        assert m["k2_expand_prefill_ms"] > 0 and m["k2_expand_fork_ms"] > 0
        # the other expanders' metrics list their own cells
        assert not {"expand_ms", "m2_expand_ms", "x4_expand_ms",
                    "x4_latent_absorbed_sites",
                    "fork_rows_attended_per_row_read"} & set(m)
        # four images a step: 40 tokens a sequence over two chunks of 32
        assert m["k2_tokens_per_step"] == pytest.approx(4 * 40 / 64)
        # the tiny preset has 4 latent layers, 3 of them expert layers of
        # 16 experts, 4 a token; the metric divides by the share's 7
        assert m["k2_latent_forked_sites"] == 4
        assert m["k2_expert_kernel_sites"] == 0      # a CPU
        a_layer = m["k2_experts_read_per_step"] * 7 / 3
        assert 4 <= a_layer <= 16
        assert 1.5 < m["k2_fork_rows_attended_per_row_read"] < 4


def test_the_traffic_file_is_the_mellum2_cells_unchanged():
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


def test_the_configuration_holds_the_published_config_but_for_reduced():
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


def test_bytes_a_forked_step_needs_against_a_hand_count():
    count = BENCH.load("harness", "bytes_kanana2")
    cfg = files.resolve_family(BENCH.config(CONFIG)).expander
    d = 2048
    attn = (d * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d) * 2
    assert count.latent_layer_bytes(cfg, 0) == attn == 2 * 26_345_472
    dense = 3 * d * 6144 * 2
    beside = (d * 128 + 3 * d * 1536) * 2       # router, shared expert
    head = d * 128256 * 2
    assert count.fixed_bytes(cfg) == 8 * attn + dense + 7 * beside + head
    assert round(count.fixed_bytes(cfg) / 1e6, 1) == 1158.2
    assert round(head / 1e6) == 525
    assert count.expert_bytes(cfg) == 3 * d * 768 * 2 == 9_437_184
    assert count.row_bytes(cfg) == 8 * 576 * 2
    # a step of four under even routing: 22.4 distinct experts a layer
    even = 128 * (1 - (1 - 6 / 128) ** 4)
    assert round(even, 1) == 22.4
    step = count.decode_bytes(cfg, 2112, 1, 7 * even, 4)
    assert step == pytest.approx(
        count.fixed_bytes(cfg) + 7 * even * 9_437_184
        + (2112 + 4) * 9216)
    assert 2.63e9 < step < 2.66e9
    assert round(7 * even * 9_437_184 / 1e9, 2) == 1.48
    # the shared rows once a step, a sequence's own once each: 256 steps
    whole = count.decode_bytes(cfg, 2112, 256, 7 * even, 4)
    rows = 256 * 2112 + 4 * 256 * 257 / 2
    assert whole == pytest.approx(
        256 * (count.fixed_bytes(cfg) + 7 * even * 9_437_184)
        + rows * 9216)
    # counting position + 1 rows a sequence would count 3.4 times the rows
    copied = 4 * sum(2112 + i + 1 for i in range(256))
    assert 3.3 < copied / rows < 3.5
    # a model WITH a query latent counts its two query kernels
    other = files.resolve_family(BENCH.config("sd15_xing4_expand")).expander
    assert count.latent_layer_bytes(other, 0) \
        == BENCH.load("harness", "bytes_xing4").latent_layer_bytes(other, 0)


def _status(steps, decoded, read, attended=0, rows=0):
    return {"serving": {"expander": {
        "tokens_prefilled": 0, "decode_steps": steps,
        "tokens_decoded": decoded, "experts_read": read, "sequences": 0,
        "rows_attended": attended, "rows_read": rows,
        "expert_tokens": [[0, 0], [0, 0]]}}}


def test_bytes_util_steps_reads_the_programs_counters():
    reader = BENCH.load("readers", "bytes_util_steps")
    spec = BENCH.layer_metric("k2_decode_bytes_util")
    assert spec["reader"] == "bytes_util_steps"
    traffic = BENCH.traffic(TRAFFIC)
    payload = dict(traffic["payload"], prompt=traffic["cycle"]["prompt"][0])
    context = {
        "records": [types.SimpleNamespace(traced=True, payload=payload)],
        "trace": {"modules": {"jit_expand_decode_chunk": 0.9}},
        "family": files.resolve_family(BENCH.config(CONFIG)),
        # two requests of 256 steps, four tokens and 157 distinct experts
        # a step
        "status_before": _status(256, 1024, 40000),
        "status_after": _status(768, 3072, 40000 + 512 * 157),
        "chips": 1, "peak": {"hbm_bytes_per_s": 819e9}, "bench": BENCH,
    }
    count = BENCH.load("harness", "bytes_kanana2")
    cfg = context["family"].expander
    want = 100 * count.decode_bytes(cfg, 2048 + 16, 256, 157.0, 4.0) \
        / (0.9 * 819e9)
    assert reader.read(context, **spec["args"]) == pytest.approx(want)
    assert 85 < want < 95       # 2.64 GB a step, 256 steps, in 0.9 seconds
    assert reader.read(dict(context, trace=None), **spec["args"]) is None
    # the parent cannot run the cell; a status without the counters: None
    old = {"serving": {"expander": {"decode_steps": 9, "tokens_decoded": 9}}}
    assert reader.read(dict(context, status_before=old, status_after=old),
                       **spec["args"]) is None


def test_the_ratio_and_value_metrics_read_the_status_or_nothing():
    ratio = BENCH.load("readers", "status_ratio")
    context = {
        "status_before": _status(256, 1024, 40000, 10, 10),
        "status_after": _status(768, 3072, 40000 + 512 * 157,
                                10 + 34, 10 + 10)}
    assert ratio.read(context, **BENCH.layer_metric(
        "k2_tokens_per_step")["args"]) == 4.0
    assert ratio.read(context, **BENCH.layer_metric(
        "k2_experts_read_per_step")["args"]) == pytest.approx(157 / 7)
    assert ratio.read(context, **BENCH.layer_metric(
        "k2_fork_rows_attended_per_row_read")["args"]) == pytest.approx(3.4)
    assert ratio.read({"status_before": {}, "status_after": {}},
                      **BENCH.layer_metric(
                          "k2_tokens_per_step")["args"]) is None
    value = BENCH.load("readers", "status_value")
    status = {"serving": {"attention": {"latent_forked": 8, "xla": 3},
                          "expander": {"expert_products": {"kernel": 7}}}}
    assert value.read({"status_before": status}, **BENCH.layer_metric(
        "k2_latent_forked_sites")["args"]) == 8
    assert value.read({"status_before": status}, **BENCH.layer_metric(
        "k2_expert_kernel_sites")["args"]) == 7
    # a program without the form (the parent): nothing, and no raise
    bare = {"serving": {"attention": {"xla": 3}, "expander": {}}}
    assert value.read({"status_before": bare}, **BENCH.layer_metric(
        "k2_latent_forked_sites")["args"]) is None


@pytest.mark.parametrize("classes", ["kanana2_decode", "kanana2_prefill"])
def test_op_classes_partition_by_flax_module(classes):
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


def test_the_manifest_gains_one_configuration_one_cell_and_the_k2_metrics():
    manifest = BENCH.manifest
    assert [c["name"] for c in manifest["configs"]][-1] == CONFIG
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL
    assert [m["name"] for m in manifest["per_layer"]][-len(NEW):] == NEW
    assert sum(CELL in m.get("workloads", ()) for m in manifest["per_layer"]) \
        == len(NEW)


def test_the_reference_file_holds_both_limits_and_three_seeds():
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

"""The twelfth prompt-expander cell (``sd15_granite_h_expand_b4``) rehearsed
on the CPU at tiny widths through the real ``run.py``, and the files it
brought: the configuration against the catalog's row, the leaf rules, the
readers, the op classes, the metric files (a step's bytes by
``harness/bytes_lm.py`` against a hand count from the published widths). A
rehearsal yields counts and correctness, never a speed."""

import functools
import json
import math
import re

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_granite_h_expand_b4"
CONFIG = "sd15_granite_h_expand"
TRAFFIC = "sd15_2048_b4_expand256"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_granite_h_expander")
BENCH = files.Bench(rehearsal.REPO)
#: one sequence's state and kept rows in one state-space layer, float32
STATE = (128 * 64 * 128 + 3 * 8448) * 4
#: the metrics the cell joined, by its name appended at the end
JOINED = (
    "self_attn_roofline", "expand_ms", "expand_prefill_ms",
    "expand_decode_ms", "expand_ahead_ms", "expand_fork_ms",
    "between_requests_ms", "lm_decode_bytes_util", "lm_linear_device_ms",
    "lm_attn_device_ms", "lm_ssm_device_ms", "lm_other_device_ms",
    "expert_device_ms", "expert_kernel_sites", "route_kernel_sites",
    "experts_read_per_step", "lm_tokens_per_step",
    "fork_rows_attended_per_row_read", "state_mib_per_step",
    "state_mib_copied_per_fork", "ssm_forked_sites")
NEW = ("held_picks_per_expert_read", "tied_head_sites")


def share():
    return files.resolve_family(BENCH.config(CONFIG)).expander


def check_the_traffic_file_is_the_sibling_cells_unchanged():
    cell = BENCH.cell(CELL)
    for sibling in ("sd15_mellum2_expand_b4", "sd15_falcon_h1_expand_b4",
                    "sd15_longcat_flash_expand_b4"):
        other = BENCH.cell(sibling)
        assert cell["traffic"] == TRAFFIC == other["traffic"]
        for key in ("server_env", "warmup_requests", "trace", "mesh"):
            assert cell[key] == other[key], key
    assert cell["config"] == CONFIG and cell["chips"] == 1
    why = BENCH.read("workloads", CELL + ".json")["why"]
    for said in ("HALF the deployment's expert load", "four times",
                 "no exchange", "second SD1.5 batch", "384-wide tile",
                 "36 grid slots"):
        assert said in why, said
    entry = next(w for w in BENCH.manifest["workloads"]
                 if w["name"] == CELL)
    assert "half a deployment's expert load" in entry["why"]
    assert len(entry["why"]) <= 200
    assert BENCH.manifest["workloads"][-1] == entry     # added at the end
    for name in JOINED:
        metric = next(m for m in BENCH.manifest["per_layer"]
                      if m["name"] == name)
        assert metric["workloads"][-1] == CELL, name
    for name in NEW:
        metric = next(m for m in BENCH.manifest["per_layer"]
                      if m["name"] == name)
        assert metric["workloads"] == [CELL], name
    assert [m["name"] for m in BENCH.manifest["per_layer"][-2:]] \
        == list(NEW)
    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.models.tokenizer import (
        load_lm_tokenizer,
    )
    from stable_diffusion_webui_distributed_tpu.pipeline import expand

    tok = load_lm_tokenizer(None, *share().vocab)
    traffic = BENCH.traffic(TRAFFIC)
    args = traffic["payload"]["alwayson_scripts"]["prompt expansion"][
        "args"][0]
    prefix = [tok.bos] + tok.encode(args["instruction"])
    # every id from the held half of the vocabulary
    assert len(prefix) == 2048 and all(0 <= i < 50176 for i in prefix)
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
            '"name": "granite-4.0-h-small"', line))
    assert config["source"] == row["source_url"]
    entry = next(c for c in BENCH.manifest["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_local_experts",
        "vocab_size"]
    assert len(entry["why"]) <= 200
    assert BENCH.manifest["configs"][-1] == entry
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_local_experts"],
            config["vocab_size"]) == (10, 36, 50176)
    # the FIRST ten of the forty: one period, the published 9 : 1
    assert config["layer_types"] == row["config"]["layer_types"][:10]
    assert config["layer_types"].count("attention") == 1
    assert config["layer_types"].index("attention") == 5
    assert config["tie_word_embeddings"] is True
    # the guide's floors: a whole period, at least 8 experts, at least an
    # eighth of the vocabulary; no width among the reduced keys
    assert config["vocab_size"] * 2 == config["published"]["vocab_size"]
    assert config["num_local_experts"] * 2 \
        == config["published"]["num_local_experts"]
    assert "TWO chips share each layer" in config["deployment"]
    assert "HALF the deployment's expert load" in config["deployment"]
    assert "8 chips" in config["deployment"]
    listed = " ".join(config["assumed"])
    for reading in ("ONE expert's width", "4096 / 32", "ten chosen LOGITS",
                    "ONE RMS over all 8 192", "NOT clamped",
                    "sd15_falcon_h1_expand seeds", "x_hat * w", "nope",
                    "FIRST ten", "mamba_chunk_size 256", "hash fallback",
                    "THE TABLE IS ALSO THE HEAD", "256", "0.33",
                    "under 1 %", "1/0.22", "3.36"):
        assert reading in listed, reading
    for key in ("published", "held_here", "deployment", "assumed"):
        assert config[key], key
    assert config["counter"] is None
    assert config["components"] == "unet_clip_vae_lm_granite_h"
    assert config["diffusion"] == BENCH.read("configs", "sd15.json")["model"]
    assert config["parameters_millions"] == {
        "expander_share": 4757, "published_model": 32207, "sd15": 1066}
    # no width is changed: the program's share has the published ones
    cfg = share()
    assert (cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.shared_expert_intermediate_size, cfg.num_experts_per_tok) \
        == (config["hidden_size"], config["intermediate_size"],
            config["shared_intermediate_size"],
            config["num_experts_per_tok"]) == (4096, 768, 1536, 10)
    assert (cfg.num_heads_per_layer, cfg.num_kv_heads, cfg.head_dim) == (
        (config["num_attention_heads"],) * 10,
        config["num_key_value_heads"], 128)
    assert (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size,
            cfg.ssm_num_groups, cfg.ssm_conv_kernel, cfg.ssm_conv_bias,
            cfg.ssm_chunk) \
        == (config["mamba_n_heads"], config["mamba_d_head"],
            config["mamba_d_state"], config["mamba_n_groups"],
            config["mamba_d_conv"], config["mamba_conv_bias"],
            config["mamba_chunk_size"]) == (128, 64, 128, 1, 4, True, 256)
    assert cfg.ssm_inner == config["mamba_expand"] * config["hidden_size"]
    assert not cfg.ssm_norm_before_gate
    # the router keeps its published width
    assert cfg.num_experts == config["published"]["num_local_experts"] == 72
    assert cfg.experts == (0, 36) and cfg.vocab == (0, 50176)
    assert cfg.layer_types == tuple(
        {"mamba": "ssm", "attention": "full"}[k]
        for k in config["layer_types"])
    assert cfg.dense_layers == () and cfg.zero_experts == 0
    # the four scalars, and the head that is the table
    assert cfg.embedding_multiplier == config["embedding_multiplier"] == 12
    assert cfg.residual_multiplier == config["residual_multiplier"] == 0.22
    assert cfg.attention_scale == config["attention_multiplier"] == 1 / 128
    assert cfg.logit_multiplier == 1 / config["logits_scaling"] == 1 / 16
    assert cfg.tied_head is config["tie_word_embeddings"] is True
    assert cfg.rope_full is None \
        and config["position_embedding_type"] == "nope"
    assert cfg.rms_norm_eps == config["rms_norm_eps"]
    assert cfg.router_scoring == "softmax" and not cfg.router_bias
    assert cfg.norm_topk_prob and cfg.routed_scaling_factor == 1.0
    assert not cfg.shared_expert_gate and cfg.attn_gate == "none"
    assert cfg.multipliers_applied == 4


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
    total = sum(leaf.size for _, leaf in flat)
    assert total == 4_757_211_776
    assert round(total / 1e6) \
        == config["parameters_millions"]["expander_share"]
    assert round(total * 2 / 1e9, 2) == 9.51
    assert round((total / 1e6 + config["parameters_millions"]["sd15"])
                 * 2e6 / 2 ** 30, 2) == 10.85
    rules = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        rules[name] = (components.leaf_rule(name, leaf.shape)
                       or weights.leaf_rule(name, leaf.shape)) \
            + (tuple(leaf.shape),)
    assert "lm_head/kernel" not in rules        # the head is the table

    def near(got, want):
        return abs(got / want - 1) < 1e-6

    plain = math.sqrt(3.0 / 4096)
    # the ONE leaf under x12 and /16: deviation 1 once multiplied
    kind, width, shape = rules["embed_tokens/embedding"]
    assert (kind, shape) == ("draw", (50176, 4096))
    assert near(width, math.sqrt(3.0) / 12)
    assert rules["layers_0/mlp/router"] == ("draw", plain, (4096, 72))
    mixer = "layers_3/ssm/"
    assert rules[mixer + "in_proj/kernel"][2] == (4096, 16768)
    assert near(rules[mixer + "in_proj/kernel"][1], plain)
    assert near(rules[mixer + "out_proj/kernel"][1],
                math.sqrt(3.0 / 8192) / 0.22)
    assert rules[mixer + "A_log"][:2] == ("draw", 4.0)
    assert rules[mixer + "conv_kernel"] == ("draw", math.sqrt(3 / 4),
                                            (4, 8448))
    for name, deviation, size in (("dt_bias", 0.5, 128),
                                  ("conv_bias", 0.5, 8448), ("D", 8.0, 128)):
        assert near(rules[mixer + name][1], deviation * math.sqrt(3.0))
        assert rules[mixer + name][2] == (size,)
    assert near(rules[mixer + "norm/scale"][1], math.sqrt(3.0))
    attn = "layers_5/attn/"
    root = (128 ** 0.5 / 128) ** -0.5
    assert round(root, 2) == 3.36
    assert near(rules[attn + "q_proj/kernel"][1], plain * root)
    assert near(rules[attn + "k_proj/kernel"][1], plain * root)
    assert near(rules[attn + "v_proj/kernel"][1], plain)
    assert near(rules[attn + "o_proj/kernel"][1], plain / 0.22)
    shared = "layers_5/mlp/shared_expert/"
    assert near(rules[shared + "gate_proj/kernel"][1], plain)
    assert near(rules[shared + "down_proj/kernel"][1],
                math.sqrt(3.0 / 1536) / 0.22)
    experts = "layers_9/mlp/experts/"
    assert rules[experts + "w_gate"][2] == (36, 4096, 768)
    assert near(rules[experts + "w_up"][1], plain)
    assert near(rules[experts + "w_down"][1], math.sqrt(3.0 / 768) / 0.22)
    for path in ("layers_3/input_norm/scale",
                 "layers_3/post_attention_norm/scale", "norm/scale"):
        assert rules[path][:2] == ("ones", 0.0)
    # every large kernel a draw of its own: no stacked draw of 1.2 GB
    big = [r for n, r in rules.items()
           if len(r[2]) >= 2 and n.startswith("layers_")
           and not n.endswith(("conv_kernel", "router"))]
    assert len(set(big)) == len(big) == 9 * 2 + 4 + 10 * (3 + 3)
    names = {name.rsplit("/", 1)[-1] for name in rules}
    assert names == {"kernel", "scale", "embedding", "conv_kernel",
                     "conv_bias", "A_log", "D", "dt_bias", "router",
                     "w_gate", "w_up", "w_down"}


def _status(steps, requests, read, held, stepped, copied, decoded=0):
    return {"serving": {"expander": {
        "decode_steps": steps, "requests": requests, "experts_read": read,
        "expert_picks_held": held, "state_bytes_stepped": stepped,
        "fork_bytes_copied": copied, "tokens_decoded": decoded}}}


def check_the_new_metrics_and_the_state_metrics_read_the_status():
    ratio = BENCH.load("readers", "status_ratio")
    per_fork, per_step = 4 * 9 * STATE, 2 * 4 * 9 * STATE
    assert (per_step, per_fork) == (309_288_960, 154_644_480)
    context = {
        "family": files.resolve_family(BENCH.config(CONFIG)),
        "status_before": _status(256, 1, 256 * 162, 256 * 200,
                                 256 * per_step, per_fork, 1024),
        "status_after": _status(2816, 11, 2816 * 162, 2816 * 200,
                                2816 * per_step, 11 * per_fork, 11264)}
    assert round(ratio.read(context, **BENCH.layer_metric(
        "state_mib_per_step")["args"]), 1) == 295.0
    assert round(ratio.read(context, **BENCH.layer_metric(
        "state_mib_copied_per_fork")["args"]), 1) == 147.5
    # over the configuration's own ten routers
    assert ratio.read(context, **BENCH.layer_metric(
        "experts_read_per_step")["args"]) == pytest.approx(16.2)
    assert ratio.read(context, **BENCH.layer_metric(
        "lm_tokens_per_step")["args"]) == 4.0
    held = BENCH.layer_metric("held_picks_per_expert_read")
    assert held["reader"] == "status_ratio" and "per" not in held["args"]
    assert ratio.read(context, **held["args"]) == pytest.approx(200 / 162)
    # a program without the counter (the parent): nothing, and no raise
    bare = {"serving": {"expander": {"experts_read": 5}}}
    assert ratio.read(dict(context, status_before=bare, status_after=bare),
                      **held["args"]) is None
    value = BENCH.load("readers", "status_value")
    tied = BENCH.layer_metric("tied_head_sites")
    assert tied["args"]["path"] == ["serving", "expander", "tied_head"]
    status = {"serving": {"expander": {
        "tied_head": {"recurrent": 0, "chunked": 2, "recurrent_forked": 1},
        "ssm_mixers": {"recurrent": 0, "chunked": 18,
                       "recurrent_forked": 9},
        "expert_products": {"kernel": 10},
        "route_products": {"kernel": 10, "xla": 20}}}}
    before = {"status_before": status}
    assert value.read(before, **tied["args"]) == 1
    for name, want in (("ssm_forked_sites", 9), ("expert_kernel_sites", 10),
                       ("route_kernel_sites", 10)):
        assert value.read(before, **BENCH.layer_metric(name)["args"]) \
            == want, name
    for bare in ({"serving": {"expander": {"ssm_mixers": {}}}},
                 {"serving": {}}, {}):
        assert value.read({"status_before": bare}, **tied["args"]) is None
    for name in NEW:
        spec = BENCH.layer_metric(name)
        entry = next(m for m in BENCH.manifest["per_layer"]
                     if m["name"] == name)
        assert {k: spec[k] for k in ("layer", "unit", "better", "source",
                                     "moves")} \
            == {k: entry[k] for k in ("layer", "unit", "better", "source",
                                      "moves")}


def check_op_classes_partition_by_flax_module(classes):
    reader = BENCH.load("readers", "op_class_ms")
    spec = BENCH.read("op_classes", classes + ".json")
    rules = spec["classes"]
    assert spec["module"] == {
        "granite_h_decode": "jit_expand_decode_chunk",
        "granite_h_prefill": "jit_expand_prefill"}[classes]
    base = "jit(f)/jit(main)/while/body/DecoderLM/"
    cases = {
        "layers_0/ssm/in_proj/dot_general": "linear",
        "layers_9/ssm/out_proj/dot_general": "linear",
        "layers_5/attn/q_proj/dot_general": "linear",
        "layers_5/attn/o_proj/dot_general": "linear",
        "layers_4/mlp/shared_expert/gate_proj/dot_general": "linear",
        "layers_4/mlp/shared_expert/down_proj/dot_general": "linear",
        # the table's product as the head, under the scope a head has
        "lm_head/dot_general": "linear",
        "layers_2/ssm/mul": "ssm",
        "layers_0/ssm/norm/rsqrt": "ssm",
        "layers_7/ssm/softplus": "ssm",
        "layers_1/ssm/exp": "ssm",
        "layers_5/attn/exp": "attn",
        "layers_5/attn/dynamic_update_slice": "attn",
        "layers_5/attn/mul": "attn",                # the scores' 1/128
        "layers_0/mlp/top_k": "expert",
        "layers_2/mlp/dot_general": "expert",       # the router's product
        "layers_6/mlp/pallas_call": "expert",
        "layers_10/mlp/_route_call/pallas_call": "expert",
        "layers_4/mlp/exp": "expert",               # the softmax
        "layers_2/mlp/shared_expert/logistic": "other",
        "layers_1/input_norm/rsqrt": "other",
        "layers_3/mul": "other",            # the residual multiplier
        "layers_3/add": "other",
        "embed_tokens/gather": "other",
        "mul": "other",                     # the table's x12
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
    assert sorted(set(order)) == ["attn", "expert", "linear", "other",
                                  "ssm"]
    assert order[-1] == "other"
    assert not {"scope", "category", "name"} & set(rules[-1])
    # every class of the decode file is a metric the cell is listed under
    by_class = {"linear": "lm_linear_device_ms", "ssm": "lm_ssm_device_ms",
                "attn": "lm_attn_device_ms", "expert": "expert_device_ms",
                "other": "lm_other_device_ms"}
    for name in by_class.values():
        assert name in JOINED


def check_the_reference_file_holds_both_limits_and_the_wrong_programs():
    """What the chip gave (PR 69): the program under both limits at every
    seed at the timed positions; each wrong program read over one limit at
    least at every seed it was read at (the control over the second, the
    other seven over both), all eight at the configuration's own seed."""
    recorded = BENCH.read("reference", CONFIG + ".json")
    limit = recorded["tolerance_relative_rms"]
    held_limit = recorded["tolerance_held_to_routing_relative_rms"]
    assert 0 < held_limit <= limit < 1
    assert recorded["tolerance_reason"] \
        and recorded["tolerance_held_to_routing_reason"]
    assert recorded["device"]["platform"] == "tpu"
    assert recorded["latent"] == 2048 + 64 + 256
    assert recorded["program_vs_reference_relative_rms"] < limit \
        < recorded["control_vs_reference_relative_rms"]
    assert recorded["passed"] is True
    reference = BENCH.reference(BENCH.config(CONFIG))
    controls = [name for name, _ in reference.CONTROLS]
    assert controls == [
        "control", "no_mlp_residual_multiplier", "scores_by_root_head_dim",
        "rotated", "norm_before_gate", "no_held_experts",
        "no_shared_expert", "logits_not_divided"]
    assert set(reference.FAULTS) < set(controls)
    assert sorted(recorded["the_wrong_programs_at_published_widths"]) \
        == sorted(controls)
    for name, read in recorded[
            "the_wrong_programs_at_published_widths"].items():
        assert read["vs_reference_relative_rms"] > limit, name
    seeds = recorded["diagnostics"]
    assert len(seeds) >= 3
    assert len({d["seed"] for d in seeds}) == len(seeds)
    own = BENCH.config(CONFIG)["weight_seed"]
    for reading in seeds:
        assert reading["positions"] == 2368 and reading["sequences"] == 4
        assert reading["finite"] is True
        assert reading["program_vs_reference_relative_rms"] < limit
        assert reading["program_vs_reference_held_to_its_routing_"
                       "relative_rms"] < held_limit
        read = [name for name in controls
                if name + "_vs_reference_relative_rms" in reading]
        assert read == controls if reading["seed"] == own \
            else {"control", "rotated"} <= set(read)
        for name in read:
            assert reading[name + reference.HELD] > held_limit, name
            if name != "control":       # told apart by the second alone
                assert reading[name + "_vs_reference_relative_rms"] \
                    > limit, name
        # at ONE seed the control reads over the program's own reading
        assert reading["control_vs_reference_relative_rms"] \
            > reading["program_vs_reference_relative_rms"]
        # half of every router's experts are held
        assert 4.5 < reading["picks_a_pair"]["held"] < 5.5
        # the tied head's pull on a row's own token: always the arg-max,
        # drawn in under 1 % of steps at temperature 1.0
        pull = reading["tied_head"]
        others = 50175 * math.exp(pull["logits_deviation_a_row"] ** 2 / 2)
        mine = math.exp(pull["own_token_logit_mean"])
        assert mine / (mine + others) < 0.01


def traced(m):
    """What the traced rehearsal's per-layer metrics must say."""
    assert m["expand_fork_ms"] > 0
    # four images a step: 40 tokens a sequence over two chunks of 32
    assert m["lm_tokens_per_step"] == pytest.approx(4 * 40 / 64)
    # the tiny share: three state-space layers of 6 heads of 5 over 7-wide
    # states and 44 channels, one attention, four routers over 12 experts of
    # which 4 are held, 3 a token
    assert m["ssm_forked_sites"] == 3
    assert m["tied_head_sites"] == 1
    state = (6 * 5 * 7 + 3 * 44) * 4
    assert m["state_mib_per_step"] == pytest.approx(
        2 * 4 * 3 * state / 2 ** 20)
    assert m["state_mib_copied_per_fork"] == pytest.approx(
        4 * 3 * state / 2 ** 20)
    assert m["expert_kernel_sites"] == 0      # a CPU
    assert m["route_kernel_sites"] == 0
    assert 0 < m["experts_read_per_step"] <= 4
    # an expert read serves at least one pick, at most the step's four rows
    assert 1.0 <= m["held_picks_per_expert_read"] <= 4.0
    assert 1.5 < m["fork_rows_attended_per_row_read"] < 4


def check_bytes_a_forked_step_needs_against_a_hand_count():
    """From the published widths: hidden 4 096; a state-space mixer of 128
    heads of 64 over 128-wide states in one group behind a 4-tap
    convolution with bias; ONE attention of 32 heads over 8 KV heads of
    128; a router of 72 outputs, experts of 768 and a shared expert of
    1 536 in every layer; the table's 50 176 held rows as the head. The
    three new keys move no byte of the walker's count: a tied head is
    counted as the head it replaces."""
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.cache import kv

    count, cfg = BENCH.load("harness", "bytes_lm"), share()
    d = 4096
    mixer = (d * (8192 + 8448 + 128) + 8192 * d + 4 * 8448 + 8448
             + 3 * 128) * 2
    assert mixer == 2 * 102_278_784     # the gated norm's weight left out
    attention = (2 * d * 4096 + 2 * d * 1024) * 2
    assert attention == 2 * 41_943_040
    router, shared = d * 72 * 2, 3 * d * 1536 * 2
    head = d * 50176 * 2
    for layer in range(10):
        assert count.mixer_bytes(cfg, layer) \
            == (attention if layer == 5 else mixer), layer
        assert count.mlp_bytes(cfg, layer) == router + shared
    assert count.head_bytes(cfg) == head
    fixed = 9 * mixer + attention + 10 * (router + shared) + head
    assert count.fixed_bytes(cfg, 4) == fixed + 4 * d * 2
    # 2.72 GB: state-space mixers 1.841, attention 0.084, shared experts
    # 0.377, routers 0.006, the table as the head 0.411
    assert round(fixed / 1e9, 2) == 2.72
    assert round(9 * mixer / 1e9, 3) == 1.841
    assert round(attention / 1e9, 3) == 0.084
    assert round(10 * shared / 1e9, 3) == 0.377
    assert round(10 * router / 1e9, 3) == 0.006
    assert round(head / 1e9, 3) == 0.411
    # 18.87 MB an expert read
    assert count.expert_bytes(cfg) == 3 * d * 768 * 2 == 18_874_368
    # 4.30 MB of state a layer a sequence, read and written; the program's
    # own cache/kv.py agrees to the byte
    assert count.state_bytes(cfg, "ssm") == STATE == 4_295_680
    assert count.state_bytes(cfg, "full") == 0
    assert kv.state_bytes(cfg, 2560, jnp.bfloat16)["ssm"] == 9 * STATE
    # 4 096 B a position in ONE layer
    assert count.row_bytes(cfg, "full") == 2 * 8 * 128 * 2 == 4096
    assert count.row_bytes(cfg, "ssm") == 0
    assert kv.state_bytes(cfg, 2560, jnp.bfloat16)["full"] == 2560 * 4096
    terms = count.step_bytes(cfg, 2112, 0, 0.0, 4)
    assert terms["rows_shared"] == 2112 * 4096
    assert terms["rows_own"] == 4 * 4096
    assert terms["states"] == 2 * 4 * 9 * STATE == 309_288_960
    # a step of four under even routing: 40 picks over 72 outputs, 36 held
    even = 36 * (1 - (1 - 10 / 72) ** 4)
    assert round(even, 1) == 16.2
    step = count.decode_bytes(cfg, 2112, 1, 10 * even, 4)
    assert step == pytest.approx(
        count.fixed_bytes(cfg, 4) + 10 * even * 18_874_368
        + 309_288_960 + (2112 + 4) * 4096)
    assert round(10 * even * 18_874_368 / 1e9, 2) == 3.06
    assert 6.05e9 < step < 6.15e9
    # the experts are half of a step, the states a twentieth
    assert 0.49 < 10 * even * 18_874_368 / step < 0.51
    # the shared rows once a step, a sequence's own once each: 256 steps
    whole = count.decode_bytes(cfg, 2112, 256, 10 * even, 4)
    rows = 256 * 2112 + 4 * 256 * 257 / 2
    assert whole == pytest.approx(
        256 * (count.fixed_bytes(cfg, 4) + 10 * even * 18_874_368
               + 309_288_960) + rows * 4096)


CHECKS = [check_bytes_a_forked_step_needs_against_a_hand_count,
          check_the_traffic_file_is_the_sibling_cells_unchanged,
          check_the_configuration_holds_the_published_config_but_for_reduced,
          check_the_leaf_rules_and_the_shares_parameters,
          check_the_new_metrics_and_the_state_metrics_read_the_status,
          functools.partial(check_op_classes_partition_by_flax_module,
                            'granite_h_decode'),
          functools.partial(check_op_classes_partition_by_flax_module,
                            'granite_h_prefill'),
          check_the_reference_file_holds_both_limits_and_the_wrong_programs]

"""The fifth prompt-expander cell (``sd15_mellum2_expand_b4``) rehearsed on
the CPU at tiny widths through the real ``run.py``, and the files it
brought: the traffic's token counts, the readers, the op classes, the
metric files (a step's bytes by ``harness/bytes_lm.py``
against a hand count from the published widths). A rehearsal yields
counts and correctness, never a speed."""

import functools
import json
import re

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_mellum2_expand_b4"
CONFIG = "sd15_mellum2_expand"
TRAFFIC = "sd15_2048_b4_expand256"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_mellum2_expander")
BENCH = files.Bench(rehearsal.REPO)


def check_the_traffic_is_a_batch_behind_an_instruction_twice_the_window():
    cell = BENCH.cell(CELL)
    assert cell["config"] == CONFIG and cell["traffic"] == TRAFFIC
    assert cell["chips"] == 1 and cell["mesh"] is None
    assert cell["server_env"] == {"SDTPU_BATCH_LADDER": "4"}
    assert cell["warmup_requests"] == 1
    assert cell["trace"] == {"requests": 2, "max_seconds": 12.0}
    from stable_diffusion_webui_distributed_tpu.models.tokenizer import (
        load_lm_tokenizer,
    )
    share = files.resolve_family(BENCH.config(CONFIG)).expander
    assert share.vocab == (0, 98304)
    tok = load_lm_tokenizer(None, *share.vocab)
    traffic = BENCH.traffic(TRAFFIC)
    old = BENCH.traffic("sd15_512_expand384")
    payload = traffic["payload"]
    args = payload["alwayson_scripts"]["prompt expansion"]["args"][0]
    prefix = [tok.bos] + tok.encode(args["instruction"])
    assert len(prefix) == 2048 == 2 * share.sliding_window
    assert all(0 <= i < 98304 for i in prefix)
    lengths = [len(tok.encode(p)) for p in traffic["cycle"]["prompt"]]
    assert min(lengths) == 16 and max(lengths) == 64
    assert traffic["cycle"] == old["cycle"]
    assert args["max_new_tokens"] == 256 and args["ignore_eos"] is True
    assert args["temperature"] == 1.0 and args["context_chunks"] == 3
    assert payload["batch_size"] == 4 and traffic["clients"] == 1
    for key in ("steps", "width", "height", "sampler_name", "cfg_scale"):
        assert payload[key] == old["payload"][key], key
    # its words are the siblings'
    theirs = set(old["payload"]["alwayson_scripts"]["prompt expansion"][
        "args"][0]["instruction"].split())
    assert set(args["instruction"].split()) <= theirs
    # what the timed path sizes from them: one chunk of the prefix, one
    # bucket of the prompt, eight chunks of decode steps
    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.pipeline import expand

    assert kv.chunk_bucket(2048) == 2048 and kv.chunk_bucket(64) == 64
    chunks = -(-(256 - 1) // expand.DECODE_STEPS)
    assert chunks == 8
    assert kv.capacity_for(2048 + 64 + chunks * expand.DECODE_STEPS) == 2560
    # the reference's own run takes its readings at the timed sizes (a
    # process an executable); verify_reference.py's one process runs a
    # quarter of them, and says why
    assert BENCH.reference(BENCH.config(CONFIG)).TIMED_POSITIONS \
        == 2048 + 64 + 256
    assert BENCH.config(CONFIG)["reference_latent"] == 512 + 16 + 64


def check_the_configuration_holds_the_published_config_but_for_reduced():
    config = BENCH.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(json.loads(line) for line in fh if re.search(
            '"name": "Mellum2-12B-A2.5B-Instruct"', line))
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8     # two periods: ISSUE 45's
    assert "RESOURCE_EXHAUSTED" in config["held_here"]["layers"]    # rule
    assert len(config["assumed"]) >= 8 and config["counter"] is None
    assert config["components"] == "unet_clip_vae_lm_table"
    for key in ("published", "held_here", "deployment", "assumed"):
        assert config[key], key
    assert "four chips" in config["deployment"]
    assert config["diffusion"] == BENCH.read("configs", "sd15.json")["model"]
    share = files.resolve_family(config).expander
    assert share.num_layers == config["num_hidden_layers"]
    # every expert and every id is held: the cut is in depth alone
    assert share.experts == (0, config["num_experts"]) == (0, 64)
    assert share.vocab == (0, config["vocab_size"]) == (0, 98304)
    kinds = {"sliding_attention": "sliding", "full_attention": "full"}
    assert share.layer_types == tuple(
        kinds[kind] for kind in config["layer_types"][:8])
    assert share.layer_types.count("sliding") == 6
    assert share.dense_layers == ()
    ropes = config["rope_parameters"]
    full, window = ropes["full_attention"], ropes["sliding_attention"]
    assert share.rope_full.theta == full["rope_theta"] \
        == share.rope_sliding.theta == window["rope_theta"]
    assert share.rope_full.factor == full["factor"]
    assert share.rope_full.original_max_position \
        == full["original_max_position_embeddings"]
    assert share.rope_full.beta_fast == full["beta_fast"]
    assert share.rope_full.beta_slow == full["beta_slow"]
    assert share.rope_full.attention_factor == full["attention_factor"]
    assert share.rope_sliding.factor == 0
    assert share.rope_full.partial_rotary_factor == 1.0 \
        == share.rope_sliding.partial_rotary_factor
    for ours, theirs in (
            ("hidden_size", "hidden_size"), ("head_dim", "head_dim"),
            ("intermediate_size", "intermediate_size"),
            ("moe_intermediate_size", "moe_intermediate_size"),
            ("num_experts", "num_experts"),
            ("num_experts_per_tok", "num_experts_per_tok"),
            ("norm_topk_prob", "norm_topk_prob"),
            ("num_kv_heads", "num_key_value_heads"),
            ("sliding_window", "sliding_window"),
            ("rms_norm_eps", "rms_norm_eps")):
        assert getattr(share, ours) == config[theirs], ours
    assert share.num_heads_per_layer == (config["num_attention_heads"],) * 8
    assert share.attn_gate == "none" and not share.qk_norm
    assert share.shared_expert_intermediate_size == 0
    assert share.routed_scaling_factor == 1.0
    assert config["attention_bias"] is False


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
    assert round(total / 1e6) \
        == config["parameters_millions"]["expander_share"] == 3795
    assert round(total * 2 / 1e9, 2) == 7.59
    assert round(total * 2 / 2 ** 30, 2) == 7.07
    assert round((total / 1e6 + config["parameters_millions"]["sd15"])
                 * 2e6 / 1e9, 2) == 9.72
    rules = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        rules[name] = (components.leaf_rule(name, leaf.shape)
                       or weights.leaf_rule(name, leaf.shape)) \
            + (tuple(leaf.shape),)
    assert rules["layers_0/mlp/router"] \
        == ("draw", (3 / 2304) ** 0.5, (2304, 64))
    # the table at variance 1: a token's row weighs what a sublayer adds
    assert rules["embed_tokens/embedding"] == ("draw", 3 ** 0.5,
                                               (98304, 2304))
    other = BENCH.load("components", "unet_clip_vae_lm")
    assert other.leaf_rule("embed_tokens/embedding", (98304, 2304)) is None
    assert components.leaf_rule("text_model/token_embedding/embedding",
                                (49408, 768)) is None
    assert rules["layers_0/attn/q_proj/kernel"][2] == (2304, 4096)
    assert rules["layers_3/attn/k_proj/kernel"][2] == (2304, 512)
    assert rules["layers_7/attn/o_proj/kernel"][2] == (4096, 2304)
    assert rules["lm_head/kernel"][2] == (2304, 98304)
    assert not any(part in name for name in rules for part in (
        "shared_expert", "g_proj", "q_norm", "up_proj", "bias"))
    # each stacked expert kernel is a draw of its own
    big = [r for r in rules.values() if len(r[2]) == 3]
    assert len(big) == 24 and len(set(big)) == 24
    assert {r[2] for r in big} == {(64, 2304, 896), (64, 896, 2304)}


def _status(steps, decoded, read, routed=0, prefilled=0):
    return {"serving": {"expander": {
        "tokens_prefilled": prefilled, "decode_steps": steps,
        "tokens_decoded": decoded, "experts_read": read, "sequences": 0,
        "expert_tokens": [[routed, 0], [0, 0]]}}}


def check_the_ratio_metrics_read_the_windows_growth_or_nothing():
    reader = BENCH.load("readers", "status_ratio")
    family = files.resolve_family(BENCH.config(CONFIG))
    context = {"family": family,
               "status_before": _status(256, 1024, 80000),
               "status_after": _status(768, 3072, 80000 + 512 * 212)}
    spec = BENCH.layer_metric("lm_tokens_per_step")
    assert reader.read(context, **spec["args"]) == 4.0
    spec = BENCH.layer_metric("experts_read_per_step")
    # over the share's own 8 expert layers, read from its LMConfig
    assert spec["args"]["per"] == "expert_layers"
    assert len(family.expander.expert_layers) == 8
    assert reader.read(context, **spec["args"]) == pytest.approx(26.5)
    # a family without an expander has no layers to divide by
    assert reader.read(dict(context, family=None), **spec["args"]) is None
    # the parent's /internal/status has no experts_read
    old = {"serving": {"expander": {"decode_steps": 9,
                                    "tokens_decoded": 9}}}
    assert reader.read({"family": family, "status_before": old,
                        "status_after": old}, **spec["args"]) is None
    assert reader.read({"family": family, "status_before": {},
                        "status_after": {}}, **spec["args"]) is None
    same = {"family": family, "status_before": context["status_before"],
            "status_after": context["status_before"]}
    assert reader.read(same, **spec["args"]) is None


def check_op_classes_partition_by_flax_module(classes):
    reader = BENCH.load("readers", "op_class_ms")
    spec = BENCH.read("op_classes", classes + ".json")
    rules = spec["classes"]
    assert spec["module"] == {
        "mellum2_decode": "jit_expand_decode_chunk",
        "mellum2_prefill": "jit_expand_prefill"}[classes]
    base = "jit(f)/jit(main)/while/body/DecoderLM/"
    cases = {
        "layers_0/attn/q_proj/dot_general": "linear",
        "layers_3/attn/k_proj/dot_general": "linear",
        "layers_7/attn/v_proj/dot_general": "linear",
        "layers_6/attn/o_proj/dot_general": "linear",
        "lm_head/dot_general": "linear",
        "layers_3/attn/exp": "attn",
        "layers_7/attn/vmap(one)/dot_general": "attn",
        "layers_7/attn/dynamic_update_slice": "attn",
        "layers_0/attn/exp": "window_attn",
        "layers_1/attn/vmap(one)/dot_general": "window_attn",
        "layers_6/attn/dynamic_update_slice": "window_attn",
        "layers_13/attn/exp": "window_attn",    # not layer 3 by its tail
        "layers_17/attn/exp": "window_attn",
        "layers_0/mlp/top_k": "expert",
        "layers_3/mlp/while/body/dot_general": "expert",
        "layers_7/mlp/sort": "expert",
        "layers_5/mlp/scatter-add": "expert",
        "layers_1/input_norm/rsqrt": "other",
        "layers_4/post_attention_norm/rsqrt": "other",
        "embed_tokens/gather": "other",
        "norm/rsqrt": "other",
    }
    for scope, want in cases.items():
        row = {"scope": base + scope, "category": "x", "name": "fusion.1"}
        assert reader.classify(row, rules) == want, scope
    # XLA's asynchronous copies carry no flax scope
    loose = {"scope": "jit(expand_decode_chunk)/while", "category": "x"}
    assert reader.classify(dict(loose, name="copy-done.7"), rules) \
        == "linear"
    assert reader.classify(dict(loose, name="slice-start.2"), rules) \
        == "linear"
    assert reader.classify(dict(loose, name="copy.3"), rules) == "other"
    order = [r["class"] for r in rules]
    # the full layers' class is "attn" since PR 58 (it was "full_attn"):
    # one name a thing, so lm_attn_device_ms lists this cell too
    assert sorted(set(order)) == ["attn", "expert", "linear", "other",
                                  "window_attn"]
    assert order[-1] == "other"
    assert not {"scope", "category", "name"} & set(rules[-1])


def check_the_reference_file_holds_both_limits_and_three_seeds():
    """What the chip gave (PR 45): three seeds at the timed positions, of
    the program and of every control that ended; the earlier readings at a
    quarter of them beside; both limits between their two readings at
    both sizes; the control that did not end named as such."""
    recorded = BENCH.read("reference", CONFIG + ".json")
    limit = recorded["tolerance_held_to_routing_relative_rms"]
    assert 0 < limit < recorded["tolerance_relative_rms"] < 1
    assert recorded["tolerance_reason"] \
        and recorded["tolerance_held_to_routing_reason"]
    assert recorded["device"]["platform"] == "tpu"
    assert recorded["latent"] == 2048 + 64 + 256
    held = "_vs_reference_held_to_the_programs_routing_relative_rms"
    ref = BENCH.reference(BENCH.config(CONFIG))
    controls = [name for name, _ in ref.CONTROLS]
    for key, positions in (("diagnostics", 2368),
                           ("diagnostics_at_592", 592)):
        seeds = recorded[key]
        assert len(seeds) >= 3
        assert len({d["seed"] for d in seeds}) == len(seeds)
        for reading in seeds:
            assert reading["positions"] == positions
            assert reading["sequences"] == 4
            assert reading["program_vs_reference_relative_rms"] \
                < recorded["tolerance_relative_rms"] \
                < reading["control_vs_reference_relative_rms"]
            assert reading["program_vs_reference_held_to_its_routing_"
                           "relative_rms"] < limit
            read = [name for name in controls if name + held in reading]
            assert "control" in read
            assert all(reading[name + held] > limit for name in read)
            # a control is read or named as failed, never passed over
            if positions == 2368:
                assert set(read) | set(reading.get("failed", {})) \
                    == set(controls)
    timed = recorded["diagnostics"]
    assert all({"windows_attend_all", "aliased_rings"} <= {
        name for name in controls if name + held in r} for r in timed)
    assert "DID NOT END" in recorded["what"]


def traced(m):
    """What the traced rehearsal's per-layer metrics must say."""
    assert m["expand_fork_ms"] > 0
    # four images a step: 40 tokens a sequence over two chunks of 32
    assert m["lm_tokens_per_step"] == pytest.approx(4 * 40 / 64)
    # the tiny preset has 4 expert layers of 8 experts, 2 a token, and the
    # metric divides by the configuration's own 4
    assert 2 <= m["experts_read_per_step"] <= 8
    assert 1.5 < m["fork_rows_attended_per_row_read"] < 4


def _walker_and_share():
    return (BENCH.load("harness", "bytes_lm"),
            files.resolve_family(BENCH.config(CONFIG)).expander)


def _rows(count, cfg, forked_at, step, sequences=1):
    """The key, value and latent rows one step needs, all layers."""
    terms = count.step_bytes(cfg, forked_at, step, 0.0, sequences)
    return terms["rows_shared"] + terms["rows_own"]


def check_bytes_a_decode_step_needs_against_a_hand_count():
    """From the published widths: hidden 2 304, 32 heads of 128 over 4 key
    heads, a window of 1 024 on three layers in four, 64 experts of 896."""
    count, cfg = _walker_and_share()
    d = 2304
    attn = (2 * d * 4096 + 2 * d * 512) * 2
    router = d * 64 * 2
    head = d * 98304 * 2
    expert = 3 * d * 896 * 2
    assert count.mixer_bytes(cfg, 0) == count.mixer_bytes(cfg, 3) == attn \
        == 42_467_328
    assert count.mlp_bytes(cfg, 3) == router
    assert round((attn + router) / 1e6, 1) == 42.8
    assert count.expert_bytes(cfg) == expert == 12_386_304
    assert count.head_bytes(cfg) == head
    # 795 MB fixed a step, whatever its sequences but for their table rows
    assert count.fixed_bytes(cfg, 4) == 8 * (attn + router) + head + 4 * d * 2
    assert round(count.fixed_bytes(cfg, 4) / 1e6) == 795
    # 2 048 B of keys and values a row; past the window the kinds part
    row = 2 * 4 * 128 * 2
    assert count.row_bytes(cfg, "full") == count.row_bytes(cfg, "sliding") \
        == row == 2048
    assert _rows(count, cfg, 0, 0) == 8 * row
    assert _rows(count, cfg, 1023, 0) == 8 * 1024 * row
    assert _rows(count, cfg, 2299, 0) == (2 * 2300 + 6 * 1024) * row
    # a token alone: 64 experts, 1 588 MB of weights
    alone = count.decode_bytes(cfg, 2112, 1, 64.0)
    assert alone == count.fixed_bytes(cfg, 1) + 64 * expert \
        + (2 * 2113 + 6 * 1024) * row
    assert round((alone - _rows(count, cfg, 2112, 0)) / 1e6) == 1588
    # a step of four under even routing: 26.5 distinct experts a layer
    even = 64 * (1 - 0.875 ** 4)
    assert round(even, 1) == 26.5
    # position 2 299 of four sequences forked at 2 112: a full layer reads
    # the 2 112 shared rows ONCE and 188 own rows four times; a ring holds
    # 1 024, the 188 own first, so 836 of the shared are left in it.
    # bytes_mellum2.py had 4 x (2 x 2 300 + 6 x 1 024) rows, 88 MB: the
    # shared range once a sequence, as before PR 51
    rows = (2 * (2112 + 4 * 188) + 6 * (836 + 4 * 188)) * row
    assert _rows(count, cfg, 2112, 187, 4) == rows
    assert round(rows / 1e6, 1) == 31.2
    assert round(4 * (2 * 2300 + 6 * 1024) * row / 1e6) == 88
    four = sum(count.step_bytes(cfg, 2112, 187, 8 * even, 4).values())
    assert four == pytest.approx(
        count.fixed_bytes(cfg, 4) + 8 * even * expert + rows)
    assert 3.44e9 < four < 3.46e9
    assert round(8 * even * expert / four, 2) == 0.76
    # counting picks where the program reads distinct experts would read
    # a fifth high
    picks = sum(count.step_bytes(cfg, 2112, 187, 8 * 32, 4).values())
    assert 1.15 < picks / four < 1.25
    # distinct experts: never over the picks, never under one sequence's
    assert sum(count.step_bytes(cfg, 2112, 187, 8 * 8, 4).values()) \
        < four < picks
    assert count.decode_bytes(cfg, 600, 2, 300.0, 4) == pytest.approx(
        sum(count.step_bytes(cfg, 600, 0, 300.0, 4).values())
        + sum(count.step_bytes(cfg, 600, 1, 300.0, 4).values()))


CHECKS = [check_bytes_a_decode_step_needs_against_a_hand_count,
          check_the_traffic_is_a_batch_behind_an_instruction_twice_the_window,
          check_the_configuration_holds_the_published_config_but_for_reduced,
          check_the_leaf_rules_and_the_shares_parameters,
          check_the_ratio_metrics_read_the_windows_growth_or_nothing,
          functools.partial(check_op_classes_partition_by_flax_module, 'mellum2_decode'),
          functools.partial(check_op_classes_partition_by_flax_module, 'mellum2_prefill'),
          check_the_reference_file_holds_both_limits_and_three_seeds]

"""The ninth prompt-expander cell (``sd15_olmo_hybrid_expand_b4``) rehearsed
on the CPU at tiny widths through the real ``run.py``, and the files it
brought: the configuration against the catalog's row, the leaf rules it
borrows, the op classes, the two metric files, what the decode trace of the
published share must count, and a step's bytes by ``harness/bytes_lm.py``
against a hand count from the published widths. A rehearsal yields counts
and correctness, never a speed."""

import functools
import json
import math
import re
import types

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_olmo_hybrid_expand_b4"
CONFIG = "sd15_olmo_hybrid_expand"
TRAFFIC = "sd15_2048_b4_expand256"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_olmo_hybrid_expander")
BENCH = files.Bench(rehearsal.REPO)
#: one sequence's state and kept rows in one linear layer, float32
STATE = (30 * 96 * 192 + 3 * 11520) * 4


def check_the_traffic_file_is_the_sibling_cells_unchanged():
    cell = BENCH.cell(CELL)
    for sibling in ("sd15_mellum2_expand_b4", "sd15_kanana2_expand_b4",
                    "sd15_gigachat35_expand_b4"):
        other = BENCH.cell(sibling)
        assert cell["traffic"] == TRAFFIC == other["traffic"]
        for key in ("server_env", "warmup_requests", "trace", "mesh"):
            assert cell[key] == other[key], key
    assert cell["config"] == CONFIG and cell["chips"] == 1
    why = BENCH.read("workloads", CELL + ".json")["why"]
    assert "about twice the deployment's" in why and "7.43 GB" in why
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
    assert len(prefix) == 2048 and all(0 <= i < 100352 for i in prefix)
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
            '"name": "Olmo-Hybrid-7B"', line))
    assert config["source"] == row["source_url"]
    entry = next(c for c in BENCH.manifest["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 16
    assert config["rope_parameters"] == {"rope_theta": None}
    assert config["linear_allow_neg_eigval"] is True
    assert "two chips hold the 32 layers" in config["deployment"]
    listed = " ".join(config["assumed"])
    for reading in ("norm placement", "NO rotary embedding",
                    "WHOLE projection", "factor 2", "[q | k | v | z]",
                    "WITHOUT bias", "silu(z)", "float32", "no grouping",
                    "A_log", "variance 1", "scale 1"):
        assert reading in listed, reading
    # no width, head count or vocabulary is changed
    share = files.resolve_family(config).expander
    assert (share.hidden_size, share.intermediate_size, share.head_dim,
            share.num_kv_heads, share.vocab) \
        == (3840, 11008, 128, 30, (0, 100352))
    assert set(share.num_heads_per_layer) == {30}
    assert (share.linear_num_key_heads, share.linear_num_value_heads,
            share.linear_key_head_dim, share.linear_value_head_dim,
            share.linear_conv_kernel) == (30, 30, 96, 192, 4)
    kinds = {"linear_attention": "linear", "full_attention": "full"}
    assert share.layer_types == tuple(
        kinds[k] for k in row["config"]["layer_types"][:16])
    assert share.sublayer_norms == tuple(
        "post" if k == "full" else "pre" for k in share.layer_types)
    assert share.rope_full is None and share.linear_write_scale == 2.0
    assert share.qk_norm and share.qk_norm_extent == "projection"
    assert share.attn_gate == "none" and not share.expert_layers


def check_the_leaf_rules_it_borrows_cover_its_leaves():
    config = BENCH.config(CONFIG)
    assert config["components"] == "unet_clip_vae_lm_gigachat35"
    components = BENCH.components(config)
    assert components.leaf_rule("embed_tokens/embedding", (100352, 3840)) \
        == ("draw", math.sqrt(3.0))
    assert components.leaf_rule("layers_0/delta/A_log", (30,)) \
        == ("draw", 4.0)
    assert components.leaf_rule("layers_0/delta/conv_kernel", (4, 11520)) \
        == ("draw", math.sqrt(3.0 / 4))
    # every norm is a scale, drawn as 1 by the harness; a Linear and
    # dt_bias keep the default
    weights = BENCH.load("harness", "weights")
    for path, shape in (("layers_3/input_norm_2/scale", (3840,)),
                        ("layers_3/attn/q_norm/scale", (3840,)),
                        ("layers_0/delta/norm/scale", (192,)),
                        ("norm/scale", (3840,))):
        assert components.leaf_rule(path, shape) is None
        assert weights.leaf_rule(path, shape) == ("ones", 0.0)
    assert components.leaf_rule("layers_0/delta/qkvz_proj/kernel",
                                (3840, 17280)) is None
    assert components.leaf_rule("layers_0/delta/dt_bias", (30,)) is None
    # and the model has no leaf the borrowed file's other rules would take
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.models import lm

    share = files.resolve_family(config).expander
    shapes = jax.eval_shape(lambda: lm.DecoderLM(share).init(
        jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
        jnp.int32(4), lm.empty_cache(share, 8, jnp.float32)))["params"]
    names = {getattr(path[-1], "key", "") for path, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert names == {"kernel", "scale", "embedding", "conv_kernel", "A_log",
                     "dt_bias"}


def _status(steps, requests, stepped, copied):
    return {"serving": {"expander": {
        "decode_steps": steps, "requests": requests,
        "state_bytes_stepped": stepped, "fork_bytes_copied": copied}}}


def check_the_two_new_metrics_read_the_status_or_nothing():
    ratio = BENCH.load("readers", "status_ratio")
    per_fork, per_step = 48 * STATE, 2 * 48 * STATE
    context = {"status_before": _status(256, 1, 256 * per_step, per_fork),
               "status_after": _status(2816, 11, 2816 * per_step,
                                       11 * per_fork)}
    step = BENCH.layer_metric("state_mib_per_step")
    fork = BENCH.layer_metric("state_mib_copied_per_fork")
    assert step["args"]["scale"] == fork["args"]["scale"] == 2.0 ** -20
    assert round(ratio.read(context, **step["args"]), 1) == 215.2
    assert round(ratio.read(context, **fork["args"]), 1) == 107.6
    # a program without the counters (the parent of PR 56): nothing
    bare = {"status_before": {"serving": {"expander": {"decode_steps": 1}}},
            "status_after": {"serving": {"expander": {"decode_steps": 9}}}}
    for spec in (step, fork):
        assert ratio.read(bare, **spec["args"]) is None
        assert ratio.read({"status_before": {}, "status_after": {}},
                          **spec["args"]) is None
        entry = next(m for m in BENCH.manifest["per_layer"]
                     if m["name"] == spec["name"])
        assert entry["workloads"] == [CELL]
    value = BENCH.load("readers", "status_value")
    status = {"serving": {"expander": {"delta_mixers": {
        "recurrent": 0, "chunked": 24, "recurrent_forked": 12}}}}
    assert value.read({"status_before": status},
                      **BENCH.layer_metric("delta_forked_sites")["args"]) \
        == 12


def check_op_classes_partition_by_flax_module(classes):
    reader = BENCH.load("readers", "op_class_ms")
    spec = BENCH.read("op_classes", classes + ".json")
    lm = "jit(f)/DecoderLM/layers_{}/{}"
    rows = {
        lm.format(0, "delta/qkvz_proj/dot_general"): "linear",
        lm.format(1, "delta/ba_proj/dot_general"): "linear",
        lm.format(2, "delta/out_proj/dot_general"): "linear",
        lm.format(3, "attn/q_proj/dot_general"): "linear",
        lm.format(3, "attn/o_proj/dot_general"): "linear",
        lm.format(0, "mlp/down_proj/dot_general"): "linear",
        lm.format(3, "mlp/gate_proj/dot_general"): "linear",
        "jit(f)/DecoderLM/lm_head/dot_general": "linear",
        lm.format(2, "delta/mul"): "delta",
        lm.format(0, "delta/norm/rsqrt"): "delta",
        lm.format(4, "delta/reduce_sum"): "delta",
        lm.format(1, "delta/logistic"): "delta",
        lm.format(3, "attn/q_norm/mul"): "attn",
        lm.format(3, "attn/k_norm/rsqrt"): "attn",
        lm.format(7, "attn/dot_general"): "attn",
        lm.format(0, "mlp/mul"): "other",
        lm.format(0, "input_norm/mul"): "other",
        lm.format(3, "input_norm_2/mul"): "other",
        lm.format(3, "post_attention_norm_2/rsqrt"): "other",
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
    assert {r["class"] for r in spec["classes"]} \
        == {"linear", "delta", "attn", "other"}
    context = {"trace": {"op_table": table}, "bench": BENCH,
               "records": [types.SimpleNamespace(traced=True)]}
    sums = reader.by_class(context, classes)
    assert sum(sums.values()) == len(rows) + 1      # a partition
    assert sums["delta"] == 4.0 and sums["attn"] == 3.0
    if classes == "olmo_hybrid_decode":
        # the metrics name no file: the configuration's stem finds it
        for cls, want in (("delta", 4000.0), ("attn", 3000.0)):
            args = BENCH.layer_metric(f"lm_{cls}_device_ms")["args"]
            assert args == {"cls": cls}
            assert reader.read(dict(context, config=BENCH.config(CONFIG)),
                               **args) == want
    assert reader.read({"trace": None, "records": [], "bench": BENCH},
                       classes, "delta") is None


def check_the_reference_file_holds_both_limits_and_three_seeds():
    recorded = BENCH.read("reference", CONFIG + ".json")
    limit = recorded["tolerance_relative_rms"]
    held = recorded["tolerance_held_to_operand_precision_relative_rms"]
    assert recorded["passed"] is True and recorded["latent"] == 2368
    assert recorded["device"]["platform"] == "tpu"
    seeds = recorded["diagnostics"]
    assert len(seeds) == 3 and len({d["seed"] for d in seeds}) == 3
    own = "program_vs_reference_held_to_its_operand_precision_relative_rms"
    suffix = ("_vs_reference_held_to_the_programs_operand_precision_"
              "relative_rms")
    for d in seeds:
        assert d["positions"] == 2368 and d["sequences"] == 4
        assert d["program_vs_reference_relative_rms"] < limit
        for control in ("control", "sigmoid_beta"):
            assert d[control + "_vs_reference_relative_rms"] > limit, control
        # the state in bfloat16 is told apart by the second limit alone
        assert d["state_bf16_vs_reference_relative_rms"] < limit
        assert d[own] < held
        for control in ("control", "state_bf16", "sigmoid_beta"):
            assert d[control + suffix] > held, control
        # the reference wrote at strengths over 1
        assert 1.9 < d["reference_write_strength_max"] <= 2.0


def check_what_the_decode_trace_of_the_share_must_count():
    """One forked decode step of the published share, traced without
    weights or FLOPs: 24 norms before a sublayer and 8 after, 4 attention
    sites without a rotary table, 12 mixers a recurrent step a sequence,
    write strength bound 2.0."""
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.models import lm
    from stable_diffusion_webui_distributed_tpu.serving.metrics import (
        EXPANDER,
    )

    share = files.resolve_family(BENCH.config(CONFIG)).expander
    module = lm.DecoderLM(share, dtype=jnp.bfloat16)
    s = jax.ShapeDtypeStruct
    one = {name: [s(shape, lm.buffer_dtype(name, jnp.bfloat16))
                  for shape in rows]
           for name, rows in lm.cache_shapes(share, 2560).items()}
    cache = jax.eval_shape(lambda c: kv.fork(c, 4, 256), one)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
        jnp.int32(4), lm.empty_cache(share, 8, jnp.float32)))
    EXPANDER.clear()
    jax.eval_shape(
        lambda v, c: module.apply(v, jnp.zeros((4,), jnp.int32),
                                  jnp.int32(2200), jnp.int32(4), c,
                                  sequences=True), shapes, cache)
    stats = EXPANDER.summary()
    EXPANDER.clear()
    form = "recurrent_forked"
    assert stats["sublayer_norms"]["pre"][form] == 24
    assert stats["sublayer_norms"]["post"][form] == 8
    assert stats["attention_unrotated"][form] == 4
    assert stats["delta_mixers"][form] == 12
    assert stats["write_strength_bound"] == 2.0
    assert lm.site_attrs(share) == {
        "norms_pre": 24, "norms_post": 8, "unrotated": 4,
        "write_strength_bound": 2.0}


def traced(m):
    """What the traced rehearsal's per-layer metrics must say."""
    assert m["expand_fork_ms"] > 0
    # four images a step: 40 tokens a sequence over two chunks of 32
    assert m["lm_tokens_per_step"] == pytest.approx(4 * 40 / 64)
    # the tiny share: six linear layers of 3 heads of 6 x 10, 66 channels
    assert m["delta_forked_sites"] == 6
    state = (3 * 6 * 10 + 3 * 66) * 4
    assert m["state_mib_per_step"] == pytest.approx(
        2 * 4 * 6 * state / 2 ** 20)
    assert m["state_mib_copied_per_fork"] == pytest.approx(
        4 * 6 * state / 2 ** 20)
    assert 1.5 < m["fork_rows_attended_per_row_read"] < 4


def check_bytes_a_forked_step_needs_against_a_hand_count():
    """From the published widths: hidden 3 840; a delta layer of 30 key
    and 30 value heads of 96 and 192 (conv over 11 520 channels, 4 taps);
    attention of 30 ungrouped heads of 128; a SwiGLU of 11 008 in every
    layer; vocabulary 100 352."""
    count = BENCH.load("harness", "bytes_lm")
    cfg = files.resolve_family(BENCH.config(CONFIG)).expander
    d = 3840
    # qkvz_proj, ba_proj, out_proj, the taps, A_log and dt_bias
    delta = (d * 17280 + d * 60 + 5760 * d + 4 * 11520 + 60) * 2
    assert count.mixer_bytes(cfg, 0) == delta == 177_500_280
    attn = 4 * d * d * 2            # q, k, v, o: a head a KV head, no gate
    assert count.mixer_bytes(cfg, 3) == attn == 117_964_800
    mlp = 3 * d * 11008 * 2
    assert all(count.mlp_bytes(cfg, layer) == mlp for layer in range(16))
    head = d * 100352 * 2
    fixed = 12 * delta + 4 * attn + 16 * mlp + head + 4 * d * 2
    assert count.fixed_bytes(cfg, 4) == fixed
    assert round(fixed / 1e9, 2) == 7.43
    assert (round(12 * delta / 1e9, 2), round(4 * attn / 1e9, 2),
            round(16 * mlp / 1e9, 2), round(head / 1e9, 2)) \
        == (2.13, 0.47, 4.06, 0.77)
    assert count.expert_bytes(cfg) == 0         # no expert anywhere
    assert count.row_bytes(cfg, "full") == 2 * 30 * 128 * 2 == 15_360
    assert count.row_bytes(cfg, "linear") == 0
    # S (30, 96, 192) and three rows of 11 520 inputs, float32, a layer a
    # sequence: twelve layers, four sequences, read and written
    assert count.state_bytes(cfg, "linear") == STATE == 2_350_080
    states = 2 * 4 * 12 * STATE
    assert count.step_bytes(cfg, 2112, 0, 0.0, 4)["states"] == states
    assert round(states / 1e9, 3) == 0.226
    assert round(states / 2 ** 20, 1) == 215.2
    assert round(states / 2 / 2 ** 20, 1) == 107.6    # what a fork copies
    # a step in the middle of the decode, forked at ~2 090
    step = count.decode_bytes(cfg, 2090, 1, 0.0, 4, first_step=128)
    assert step == pytest.approx(
        fixed + states + 4 * (2090 + 4 * 129) * 15_360)
    assert round(4 * 2090 * 15_360 / 1e9, 3) == 0.128
    assert round(4 * 4 * 129 * 15_360 / 1e9, 2) == 0.03
    assert 7.79e9 < step < 7.83e9
    assert round(step / (0.87 * 819e9) * 1e3, 1) == 11.0      # ms a step
    # the linear layers are 69 % of a step's bytes, their mixers and
    # states 30 %; the full layers' own 8 %
    linear = 12 * (delta + mlp) + states
    assert round(linear / step, 2) == 0.69
    assert round((12 * delta + states) / step, 2) == 0.30
    assert round((4 * attn + 4 * (2090 + 4 * 129) * 15_360) / step, 2) \
        == 0.08
    whole = count.decode_bytes(cfg, 2112, 256, 0.0, 4)
    rows = 256 * 2112 + 4 * 256 * 257 / 2
    assert whole == pytest.approx(
        256 * (count.fixed_bytes(cfg, 4) + states) + rows * 4 * 15_360)
    # one image after the other streams the fixed weights four times
    alone = 4 * count.decode_bytes(cfg, 2112, 1, 0.0, 1)
    assert 30.0e9 < alone < 30.5e9


CHECKS = [check_bytes_a_forked_step_needs_against_a_hand_count,
          check_the_traffic_file_is_the_sibling_cells_unchanged,
          check_the_configuration_holds_the_published_config_but_for_reduced,
          check_the_leaf_rules_it_borrows_cover_its_leaves,
          check_the_two_new_metrics_read_the_status_or_nothing,
          functools.partial(check_op_classes_partition_by_flax_module,
                            "olmo_hybrid_decode"),
          functools.partial(check_op_classes_partition_by_flax_module,
                            "olmo_hybrid_prefill"),
          check_what_the_decode_trace_of_the_share_must_count,
          check_the_reference_file_holds_both_limits_and_three_seeds]

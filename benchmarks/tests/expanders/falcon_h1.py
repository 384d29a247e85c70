"""The tenth prompt-expander cell (``sd15_falcon_h1_expand_b4``) rehearsed
on the CPU at tiny widths through the real ``run.py``, and the files it
brought: the configuration against the catalog's row, its leaf rules, the op
classes, the two metric files, what the decode trace of the published share
must count, a step's bytes by ``harness/bytes_lm.py`` on the REAL
``LMConfig`` against ``test_bytes_lm.py``'s stand-in and a hand count, and
the program's own ``cache/kv.py`` against the walker's states. A rehearsal
yields counts and correctness, never a speed."""

import functools
import json
import math
import re
import types

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

CELL = "sd15_falcon_h1_expand_b4"
CONFIG = "sd15_falcon_h1_expand"
TRAFFIC = "sd15_2048_b4_expand256"
TINY_FACTORY = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                "tiny_falcon_h1_expander")
BENCH = files.Bench(rehearsal.REPO)
LAYERS = 9
#: one sequence's state and kept rows in one layer's state-space part,
#: float32
STATE = (32 * 128 * 256 + 3 * 5120) * 4
SIBLINGS = ("sd15_mellum2_expand_b4", "sd15_kanana2_expand_b4",
            "sd15_gigachat35_expand_b4", "sd15_olmo_hybrid_expand_b4")


def share():
    return files.resolve_family(BENCH.config(CONFIG)).expander


def check_the_traffic_file_is_the_sibling_cells_unchanged():
    cell = BENCH.cell(CELL)
    for sibling in SIBLINGS:
        other = BENCH.cell(sibling)
        assert cell["traffic"] == TRAFFIC == other["traffic"]
        for key in ("server_env", "warmup_requests", "trace", "mesh"):
            assert cell[key] == other[key], key
    assert cell["config"] == CONFIG and cell["chips"] == 1
    why = BENCH.read("workloads", CELL + ".json")["why"]
    assert "about eight times the deployment's" in why and "8.41 GB" in why
    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.models.tokenizer import (
        load_lm_tokenizer,
    )
    from stable_diffusion_webui_distributed_tpu.pipeline import expand

    tok = load_lm_tokenizer(None, *share().vocab)
    traffic = BENCH.traffic(TRAFFIC)
    args = traffic["payload"]["alwayson_scripts"]["prompt expansion"][
        "args"][0]
    # the instruction and every prompt lie inside the 65 280 held ids
    prefix = [tok.bos] + tok.encode(args["instruction"])
    assert len(prefix) == 2048 and all(0 <= i < 65280 for i in prefix)
    encoded = [tok.encode(p) for p in traffic["cycle"]["prompt"]]
    assert all(0 <= i < 65280 for ids in encoded for i in ids)
    lengths = [len(ids) for ids in encoded]
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
            '"name": "Falcon-H1-34B-Instruct"', line))
    assert config["source"] == row["source_url"]
    entry = next(c for c in BENCH.manifest["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] \
        == ["num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"]) == (9, 65280)
    assert config["published"] == {"num_hidden_layers": 72,
                                   "vocab_size": 261120}
    assert "eight chips hold the 72 layers" in config["deployment"]
    assert "four ways" in config["deployment"]
    listed = " ".join(config["assumed"])
    for reading in ("where each multiplier sits", "ONE input norm",
                    "[z 4096 | x 4096 | B 2 x 256 | C 2 x 256 | dt 32]",
                    "WITH bias", "softplus", "group j // 16", "THEN an RMS",
                    "FULL head width", "attn_layer_indices null",
                    "float32", "1/m times", "A_log", "hash fallback"):
        assert reading in listed, reading
    # no width or head count is changed, and the program's LMConfig says
    # every number of the row
    cfg = share()
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.head_dim,
            cfg.num_kv_heads, cfg.vocab, cfg.vocab_size) \
        == (5120, 21504, 128, 4, (0, 65280), 261120)
    assert cfg.layer_types == ("full+ssm",) * LAYERS
    assert set(cfg.num_heads_per_layer) == {20}
    published = row["config"]
    assert (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size,
            cfg.ssm_num_groups, cfg.ssm_conv_kernel, cfg.ssm_conv_bias,
            cfg.ssm_chunk, cfg.ssm_norm_before_gate) == tuple(
        published[k] for k in (
            "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_n_groups", "mamba_d_conv", "mamba_conv_bias",
            "mamba_chunk_size", "mamba_norm_before_gate"))
    assert cfg.ssm_inner == published["mamba_d_ssm"]
    assert cfg.rope_full.theta == published["rope_theta"]
    assert cfg.rms_norm_eps == published["rms_norm_eps"]
    assert (cfg.embedding_multiplier, cfg.logit_multiplier,
            cfg.key_multiplier) == tuple(published[k] for k in (
                "embedding_multiplier", "lm_head_multiplier",
                "key_multiplier"))
    assert dict((kind, pair) for kind, *pair in cfg.mixer_multipliers) == {
        "full": [published["attention_in_multiplier"],
                 published["attention_out_multiplier"]],
        "ssm": [published["ssm_in_multiplier"],
                published["ssm_out_multiplier"]]}
    assert list(cfg.ssm_multipliers) == published["ssm_multipliers"]
    assert list(cfg.mlp_multipliers) == published["mlp_multipliers"]
    assert cfg.multipliers_applied == 13    # attention's input is 1
    assert cfg.attn_gate == "none" and not cfg.qk_norm
    assert not cfg.expert_layers and cfg.residual_streams == 1


def check_the_leaf_rules_scale_what_a_multiplier_scales():
    config = BENCH.config(CONFIG)
    assert config["components"] == "unet_clip_vae_lm_falcon_h1"
    components = BENCH.components(config)
    cfg = share()
    components.component_inits(files.resolve_family(config))

    def width(path, shape):
        kind, half = components.leaf_rule(path, shape)
        assert kind == "draw"
        return half

    def near(got, want):
        return abs(got / want - 1) < 1e-6

    plain = math.sqrt(3.0 / 5120)
    assert near(width("embed_tokens/embedding", (65280, 5120)),
                math.sqrt(3.0) / cfg.embedding_multiplier)
    assert near(width("lm_head/kernel", (5120, 65280)), plain * 128)
    layer = "layers_3/"
    assert near(width(layer + "attn/q_proj/kernel", (5120, 2560)), plain)
    assert near(width(layer + "attn/v_proj/kernel", (5120, 512)), plain)
    assert near(width(layer + "attn/k_proj/kernel", (5120, 512)),
                plain / cfg.key_multiplier)
    assert near(width(layer + "attn/o_proj/kernel", (2560, 5120)),
                math.sqrt(3.0 / 2560) / 0.0375)
    spread = math.prod(cfg.ssm_multipliers) ** 0.2
    assert near(width(layer + "ssm/in_proj/kernel", (5120, 9248)),
                plain / (0.25 * spread))
    assert near(width(layer + "ssm/out_proj/kernel", (4096, 5120)),
                math.sqrt(3.0 / 4096) / cfg.mixer_multiplier("ssm")[1])
    assert near(width(layer + "mlp/gate_proj/kernel", (5120, 21504)),
                plain / cfg.mlp_multipliers[0])
    assert near(width(layer + "mlp/up_proj/kernel", (5120, 21504)), plain)
    assert near(width(layer + "mlp/down_proj/kernel", (21504, 5120)),
                math.sqrt(3.0 / 21504) / cfg.mlp_multipliers[1])
    # no two large kernels share a draw
    assert width("layers_0/mlp/up_proj/kernel", (5120, 21504)) \
        != width("layers_1/mlp/up_proj/kernel", (5120, 21504))
    assert width(layer + "ssm/A_log", (32,)) == 4.0
    assert width(layer + "ssm/conv_kernel", (4, 5120)) == math.sqrt(3 / 4)
    for name, deviation in (("dt_bias", 0.5), ("conv_bias", 0.5),
                            ("D", 8.0)):
        assert near(width(layer + "ssm/" + name, (32,)),
                    deviation * math.sqrt(3.0))
    assert near(width(layer + "ssm/norm/scale", (4096,)), math.sqrt(3.0))
    # the layers' norms are scale 1, by the harness
    weights = BENCH.load("harness", "weights")
    for path in (layer + "input_norm/scale",
                 layer + "post_attention_norm/scale", "norm/scale"):
        assert components.leaf_rule(path, (5120,)) is None
        assert weights.leaf_rule(path, (5120,)) == ("ones", 0.0)
    # and these are all the leaves the model has
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.models import lm

    shapes = jax.eval_shape(lambda: lm.DecoderLM(cfg).init(
        jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
        jnp.int32(4), lm.empty_cache(cfg, 8, jnp.float32)))["params"]
    names = {getattr(path[-1], "key", "") for path, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert names == {"kernel", "scale", "embedding", "conv_kernel",
                     "conv_bias", "A_log", "D", "dt_bias"}


def _status(steps, requests, stepped, copied):
    return {"serving": {"expander": {
        "decode_steps": steps, "requests": requests,
        "state_bytes_stepped": stepped, "fork_bytes_copied": copied}}}


def check_the_two_new_metrics_and_the_state_metrics_read_the_status():
    ratio = BENCH.load("readers", "status_ratio")
    per_fork, per_step = 4 * LAYERS * STATE, 2 * 4 * LAYERS * STATE
    assert (per_step, per_fork) == (306413568, 153206784)
    context = {"status_before": _status(256, 1, 256 * per_step, per_fork),
               "status_after": _status(2816, 11, 2816 * per_step,
                                       11 * per_fork)}
    step = BENCH.layer_metric("state_mib_per_step")
    fork = BENCH.layer_metric("state_mib_copied_per_fork")
    assert round(ratio.read(context, **step["args"]), 1) == 292.2
    assert round(ratio.read(context, **fork["args"]), 1) == 146.1
    for name in ("state_mib_per_step", "state_mib_copied_per_fork"):
        entry = next(m for m in BENCH.manifest["per_layer"]
                     if m["name"] == name)
        assert entry["workloads"][-1] == CELL       # appended at the end
    value = BENCH.load("readers", "status_value")
    sites = BENCH.layer_metric("ssm_forked_sites")
    assert sites["args"]["path"] == ["serving", "expander", "ssm_mixers"]
    status = {"serving": {"expander": {"ssm_mixers": {
        "recurrent": 0, "chunked": 18, "recurrent_forked": 9}}}}
    assert value.read({"status_before": status}, **sites["args"]) == 9
    # a program without the counter (the parent): nothing, and no raise
    for bare in ({"serving": {"expander": {"delta_mixers": {}}}},
                 {"serving": {}}, {}):
        assert value.read({"status_before": bare}, **sites["args"]) is None
    for name in ("lm_ssm_device_ms", "ssm_forked_sites"):
        entry = next(m for m in BENCH.manifest["per_layer"]
                     if m["name"] == name)
        assert entry["workloads"] == [CELL]


def check_op_classes_partition_by_flax_module(classes):
    reader = BENCH.load("readers", "op_class_ms")
    spec = BENCH.read("op_classes", classes + ".json")
    lm = "jit(f)/DecoderLM/layers_{}/{}"
    rows = {
        lm.format(0, "ssm/in_proj/dot_general"): "linear",
        lm.format(2, "ssm/out_proj/dot_general"): "linear",
        lm.format(3, "attn/q_proj/dot_general"): "linear",
        lm.format(3, "attn/k_proj/dot_general"): "linear",
        lm.format(3, "attn/o_proj/dot_general"): "linear",
        lm.format(0, "mlp/down_proj/dot_general"): "linear",
        lm.format(8, "mlp/gate_proj/dot_general"): "linear",
        "jit(f)/DecoderLM/lm_head/dot_general": "linear",
        lm.format(2, "ssm/mul"): "ssm",
        lm.format(0, "ssm/norm/rsqrt"): "ssm",
        lm.format(4, "ssm/reduce_sum"): "ssm",
        lm.format(1, "ssm/softplus"): "ssm",
        lm.format(1, "ssm/exp"): "ssm",
        lm.format(3, "attn/mul"): "attn",
        lm.format(3, "attn/cos"): "attn",
        lm.format(7, "attn/dot_general"): "attn",
        lm.format(0, "mlp/mul"): "other",
        lm.format(0, "input_norm/mul"): "other",
        lm.format(3, "post_attention_norm/rsqrt"): "other",
        lm.format(3, "mul"): "other",
        "jit(f)/DecoderLM/norm/mul": "other",
        "jit(f)/DecoderLM/mul": "other",
    }
    table = [{"module": spec["module"], "scope": scope, "category": "x",
              "name": "fusion", "seconds": 1.0} for scope in rows]
    table.append({"module": spec["module"], "scope": "", "category": "x",
                  "name": "copy-done.3", "seconds": 1.0})
    table.append({"module": "jit_other", "scope": lm.format(0, "ssm/mul"),
                  "category": "x", "name": "fusion", "seconds": 9.0})
    for row, want in zip(table, list(rows.values()) + ["linear"]):
        assert reader.classify(row, spec["classes"]) == want, row["scope"]
    assert {r["class"] for r in spec["classes"]} \
        == {"linear", "ssm", "attn", "other"}
    context = {"trace": {"op_table": table}, "bench": BENCH,
               "records": [types.SimpleNamespace(traced=True)]}
    sums = reader.by_class(context, classes)
    assert sum(sums.values()) == len(rows) + 1      # a partition
    assert sums["ssm"] == 5.0 and sums["attn"] == 3.0
    if classes == "falcon_h1_decode":
        # the metrics name no file: the configuration's stem finds it
        for cls, want in (("ssm", 5000.0), ("attn", 3000.0)):
            args = BENCH.layer_metric(f"lm_{cls}_device_ms")["args"]
            assert args == {"cls": cls}
            assert reader.read(dict(context, config=BENCH.config(CONFIG)),
                               **args) == want
        # a sibling's configuration has no such class: nothing, no raise
        assert reader.read(
            dict(context, config=BENCH.config("sd15_olmo_hybrid_expand")),
            cls="ssm") is None
    assert reader.read({"trace": None, "records": [], "bench": BENCH},
                       classes, "ssm") is None


def check_the_class_files_name_the_published_shares_flax_scopes():
    """Every parameter of the published share lies under a module whose
    scope the class files' rules take: each Linear's name is in the
    ``linear`` rule, the rest of a mixer under ``/ssm/`` or ``/attn/``."""
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.models import lm

    cfg = share()
    shapes = jax.eval_shape(lambda: lm.DecoderLM(cfg).init(
        jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
        jnp.int32(4), lm.empty_cache(cfg, 8, jnp.float32)))["params"]
    assert set(shapes["layers_0"]) == {"attn", "ssm", "mlp", "input_norm",
                                       "post_attention_norm"}
    linears = {path[-2].key for path, leaf in
               jax.tree_util.tree_flatten_with_path(shapes)[0]
               if path[-1].key == "kernel"}
    for which in ("decode", "prefill"):
        spec = BENCH.read("op_classes", f"falcon_h1_{which}.json")
        rule = spec["classes"][0]["scope"]
        named = set(re.search(r"\((.*?)\)", rule).group(1).split("|"))
        assert linears == named


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
    reference = BENCH.reference(BENCH.config(CONFIG))
    for d in seeds:
        assert d["positions"] == 2368 and d["sequences"] == 4
        assert d["program_vs_reference_relative_rms"] < limit
        assert d[own] < held
        for control in reference.CHIP_CONTROLS:
            assert d[control + suffix] > held, control
        # some head forgets inside a token, some remembers hundreds
        assert d["reference_decay_min"] < 1e-3
        assert d["reference_decay_max"] > 0.99


def check_what_the_decode_trace_of_the_share_must_count():
    """One forked decode step of the published share, traced without
    weights or FLOPs: nine state-space mixers a recurrence a sequence, nine
    layers of two mixers under one norm, thirteen multipliers off 1, nine
    attention sites over the shared range and the own rows."""
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.models import lm
    from stable_diffusion_webui_distributed_tpu.serving.metrics import (
        ATTENTION, EXPANDER,
    )

    cfg = share()
    module = lm.DecoderLM(cfg, dtype=jnp.bfloat16)
    s = jax.ShapeDtypeStruct
    one = {name: [s(shape, lm.buffer_dtype(name, jnp.bfloat16))
                  for shape in rows]
           for name, rows in lm.cache_shapes(cfg, 2560).items()}
    cache = jax.eval_shape(lambda c: kv.fork(c, 4, 256), one)
    assert [(x.shape, x.dtype) for x in cache["ssm_state"]] \
        == [((4, 32, 128, 256), jnp.float32)] * LAYERS
    assert [(x.shape, x.dtype) for x in cache["ssm_conv"]] \
        == [((4, 3, 5120), jnp.float32)] * LAYERS
    assert [x.shape for x in cache["k_shared"]] == [(2560, 4, 128)] * LAYERS
    assert [x.shape for x in cache["k"]] == [(4, 256, 4, 128)] * LAYERS
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
        jnp.int32(4), lm.empty_cache(cfg, 8, jnp.float32)))
    EXPANDER.clear()
    ATTENTION.clear()
    logits, *_ = jax.eval_shape(
        lambda v, c: module.apply(v, jnp.zeros((4,), jnp.int32),
                                  jnp.int32(2200), jnp.int32(4), c,
                                  sequences=True), shapes, cache)
    stats, sites = EXPANDER.summary(), ATTENTION.summary()
    EXPANDER.clear()
    ATTENTION.clear()
    assert logits.shape == (4, 65280)
    form = "recurrent_forked"
    assert stats["ssm_mixers"] == {"recurrent": 0, "chunked": 0, form: 9}
    assert stats["joined_layers"][form] == 9
    assert stats["multipliers_applied"] == 13
    assert stats["delta_mixers"][form] == 0
    assert stats["sublayer_norms"]["pre"][form] == 18
    (shape, paths), = sites["by_shape"].items()
    assert shape == "T1 S2816 D128" and sum(paths.values()) == 9
    assert lm.site_attrs(cfg) == {"ssm_layers": 9, "joined_layers": 9,
                                  "multipliers": 13}


def traced(m):
    """What the traced rehearsal's per-layer metrics must say."""
    assert m["expand_fork_ms"] > 0
    # four images a step: 40 tokens a sequence over two chunks of 32
    assert m["lm_tokens_per_step"] == pytest.approx(4 * 40 / 64)
    # the tiny share: three layers of 6 heads of 5 over 7-wide states, 72
    # channels
    assert m["ssm_forked_sites"] == 3
    state = (6 * 5 * 7 + 3 * 72) * 4
    assert m["state_mib_per_step"] == pytest.approx(
        2 * 4 * 3 * state / 2 ** 20)
    assert m["state_mib_copied_per_fork"] == pytest.approx(
        4 * 3 * state / 2 ** 20)
    assert 1.5 < m["fork_rows_attended_per_row_read"] < 4


def check_bytes_a_forked_step_needs_against_a_hand_count():
    """The REAL ``LMConfig`` of the share against
    ``test_bytes_lm.py:falcon_share``'s stand-in, term by term, and both
    against the hand count from the published widths; then the program's
    own ``cache/kv.py`` against the walker's states (PERF.md section 7,
    PR 63 (1): the kept rows are float32 on both sides, so they agree to
    the byte)."""
    import jax.numpy as jnp

    from benchmarks.tests import test_bytes_lm as stand_in
    from stable_diffusion_webui_distributed_tpu.cache import kv

    count = BENCH.load("harness", "bytes_lm")
    cfg, literal = share(), stand_in.falcon_share()
    for attr in ("ssm_num_heads", "ssm_head_dim", "ssm_state_size",
                 "ssm_num_groups", "ssm_conv_kernel", "ssm_conv_bias",
                 "hidden_size", "layer_types", "num_heads_per_layer",
                 "num_kv_heads", "head_dim", "residual_streams",
                 "total_ut_steps", "dense_layers", "intermediate_size",
                 "moe_intermediate_size", "vocab"):
        assert getattr(cfg, attr) == getattr(literal, attr), attr
    attn, ssm, mlp = (stand_in.ATTN_PART, stand_in.SSM_PART, stand_in.MLP)
    assert (attn, ssm, mlp) == (62914560, 136693952, 660602880)
    for layer in range(LAYERS):
        assert count.mixer_bytes(cfg, layer) == attn + ssm \
            == count.mixer_bytes(literal, layer)
        assert count.mlp_bytes(cfg, layer) == mlp
    assert attn + ssm + mlp == 860211392            # a layer, 2 B a weight
    assert (attn + ssm + mlp) // 2 == 430105696     # its parameters
    assert count.head_bytes(cfg) == 5120 * 65280 * 2 == 668467200
    assert count.fixed_bytes(cfg, 4) == 8410410688 \
        == count.fixed_bytes(literal, 4)
    assert (round(9 * ssm / 1e9, 2), round(9 * attn / 1e9, 2),
            round(9 * mlp / 1e9, 2), round(668467200 / 1e9, 2)) \
        == (1.23, 0.57, 5.95, 0.67)
    assert count.expert_bytes(cfg) == 0
    assert count.row_bytes(cfg, "full+ssm") == 2 * 4 * 128 * 2 == 2048
    assert count.state_bytes(cfg, "full+ssm") == STATE == 4255744
    for at in (0, 31, 255):
        assert count.step_bytes(cfg, 2112, at, 0.0, 4) \
            == count.step_bytes(literal, 2112, at, 0.0, 4)
    step = count.step_bytes(cfg, 2112, 0, 0.0, 4)
    assert step == {"weights": 8410410688, "experts": 0.0,
                    "rows_shared": 38928384, "rows_own": 73728,
                    "states": 306413568}
    assert sum(step.values()) == 8755826368
    assert count.decode_bytes(cfg, 2112, 32, 0.0, 4) == 280223012864
    assert round(step["states"] / 2 ** 20, 1) == 292.2
    assert round(step["states"] / 2 / 2 ** 20, 1) == 146.1
    # the state-space mixers' weights and states are 18 % of a step, the
    # head's slice 8 %
    assert round((9 * ssm + step["states"]) / sum(step.values()), 2) == 0.18
    assert round(668467200 / sum(step.values()), 2) == 0.08
    assert round(sum(step.values()) / (0.87 * 819e9) * 1e3, 1) == 12.3
    # the program's own cache against the walker's states
    assert kv.copied_bytes(cfg, jnp.bfloat16, 4) == step["states"] // 2
    sizes = kv.state_bytes(cfg, 2560, jnp.bfloat16, 4, 256)
    assert sizes == {"full": LAYERS * (2560 + 4 * 256) * 2048, "sliding": 0,
                     "ssm": 4 * LAYERS * STATE}
    assert sizes["ssm"] == 153206784
    alone = kv.state_bytes(cfg, 2560, jnp.bfloat16)
    assert alone["ssm"] == LAYERS * count.state_bytes(cfg, "ssm")
    assert alone["full"] == LAYERS * 2560 * count.row_bytes(cfg, "full")
    # one image after the other streams the fixed weights four times
    alone = 4 * count.decode_bytes(cfg, 2112, 1, 0.0, 1)
    assert 33.9e9 < alone < 34.2e9


CHECKS = [check_bytes_a_forked_step_needs_against_a_hand_count,
          check_the_traffic_file_is_the_sibling_cells_unchanged,
          check_the_configuration_holds_the_published_config_but_for_reduced,
          check_the_leaf_rules_scale_what_a_multiplier_scales,
          check_the_two_new_metrics_and_the_state_metrics_read_the_status,
          functools.partial(check_op_classes_partition_by_flax_module,
                            "falcon_h1_decode"),
          functools.partial(check_op_classes_partition_by_flax_module,
                            "falcon_h1_prefill"),
          check_the_class_files_name_the_published_shares_flax_scopes,
          check_what_the_decode_trace_of_the_share_must_count,
          check_the_reference_file_holds_both_limits_and_three_seeds]

"""A later PR adds a configuration, a cell, a traffic mix, a per-layer
metric, a reader, a generator and a whole architecture (its weights'
components, its counter of operations, its plain reference) as NEW files
plus entries in BENCHMARK.json, and edits no file that is there.

In BENCHMARK.json it appends entries, and may make ONE edit of an entry
that is there (PR 58): its new cell's name appended at the END of the
``workloads`` list of a metric the cell reports, nothing removed and
nothing reordered (:func:`manifest_faults`). That is how a cell joins a
metric that exists where it would once have cloned it under a prefix; an
expander's device classes and its roofline need no file either, they are
found from its configuration (the last test here)."""

import dataclasses
import hashlib
import json
import os
import sys

import jax.numpy as jnp
import pytest

from benchmarks.harness import files, weights
from benchmarks.tests import rehearsal

READER = '''
"""share of the window's requests that were answered, in per cent"""


def read(context, of):
    return 100.0 * len(context["records"]) / max(1, len(context[of]))
'''

GENERATOR = '''
"""a closed loop that stops after a fixed number of requests"""


def run(traffic, send, draw, seconds=None, max_requests=None):
    count = traffic["requests"] if max_requests is None else max_requests
    return [send(draw()) for _ in range(count)]
'''

# -- an architecture that is NOT models/unet.py: a family with no ``unet``,
# ``text_encoder`` or ``vae``, whose denoiser is a small transformer block
# with one stacked expert kernel (experts, in, out) ------------------------

STANDIN_MODEL = '''
import dataclasses

import flax.linen as nn
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class StandIn:
    name: str = "standin"
    width: int = 32
    hidden: int = 64
    experts: int = 4
    tokens: int = 16


class Denoiser(nn.Module):
    cfg: StandIn
    dtype: jnp.dtype = jnp.float32
    low: bool = False          # the lower-precision path: bfloat16 matmuls

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        dt = jnp.bfloat16 if self.low else self.dtype
        h = nn.LayerNorm(dtype=jnp.float32, name="ln")(x)
        gate = nn.softmax(nn.Dense(c.experts, dtype=jnp.float32,
                                   name="router")(h))
        w_in = self.param("experts_in", nn.initializers.lecun_normal(),
                          (c.experts, c.width, c.hidden))
        w_out = self.param("experts_out", nn.initializers.lecun_normal(),
                           (c.experts, c.hidden, c.width))
        up = jnp.einsum("btd,edf->btef", h.astype(dt), w_in.astype(dt))
        down = jnp.einsum("btef,efd->bted", nn.gelu(up), w_out.astype(dt))
        mixed = jnp.einsum("bted,bte->btd", down.astype(jnp.float32), gate)
        return x + mixed


def family():
    return StandIn()
'''

STANDIN_COMPONENTS = '''
"""Where the stand-in's seeded weights come from."""
import math

import jax
import jax.numpy as jnp

from standin_model import Denoiser


def component_inits(family):
    return {"denoiser": (Denoiser(family), [jax.ShapeDtypeStruct(
        (1, family.tokens, family.width), jnp.float32)])}


def leaf_rule(path, shape):
    """A stacked expert kernel (experts, in, out) has fan-in ``in``, not
    experts x in as the name-blind rule would take it."""
    if path.rsplit("/", 1)[-1] in ("experts_in", "experts_out"):
        return "draw", math.sqrt(3.0 / shape[-2])
    return None
'''

STANDIN_COUNTER = '''
"""Operations one image of the stand-in needs: router and both expert
matmuls for every token, every step."""


def flops_per_image(family, payload):
    c = family
    block = 2 * c.tokens * c.width * c.experts \\
        + 2 * 2 * c.tokens * c.experts * c.width * c.hidden
    return int(payload["steps"]) * block
'''

STANDIN_REFERENCE = '''
"""The stand-in's forward pass in plain jax.numpy, float32."""
import jax
import jax.numpy as jnp

COMPONENT = "denoiser"
CONTROL = "the module's own bfloat16 matmuls (low=True)"


def inputs(family, seed, size):
    return [jax.random.normal(jax.random.key(seed),
                              (1, size, family.width), jnp.float32)]


def program(family, policy, control=False):
    from standin_model import Denoiser

    module = Denoiser(family, dtype=policy.compute_dtype, low=control)
    return lambda params, *args: module.apply({"params": params}, *args)


def forward(family, p, x):
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    h = (x - mean) / jnp.sqrt(var + 1e-6) * f32(p["ln"]["scale"]) \\
        + f32(p["ln"]["bias"])
    gate = jax.nn.softmax(h @ f32(p["router"]["kernel"])
                          + f32(p["router"]["bias"]))
    up = jnp.einsum("btd,edf->btef", h, f32(p["experts_in"]),
                    precision="highest")
    down = jnp.einsum("btef,efd->bted", jax.nn.gelu(up),
                      f32(p["experts_out"]), precision="highest")
    return x + jnp.einsum("bted,bte->btd", down, gate)
'''

# -- the end-to-end rehearsal keeps a UNet family (the engine serves nothing
# else yet) and sends it through every hook a new architecture would use ---

TINY_V_COMPONENTS = '''
"""tiny-v's components: the UNet family's, through a file of its own."""
from benchmarks.components.unet_clip_vae import component_inits  # noqa: F401


def leaf_rule(path, shape):
    if path == "conv_in/kernel":
        print("tiny_v leaf_rule asked", flush=True)
    return None
'''

TINY_V_COUNTER = '''
"""tiny-v's counter: the UNet's count, under a name of its own."""
from benchmarks.harness.flops import unet_flops_per_image as flops_per_image  # noqa: F401,E501
'''


def digest(root):
    out = {}
    for folder, _, names in os.walk(os.path.join(root, "benchmarks")):
        if "__pycache__" in folder:
            continue
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as fh:
                out[path] = hashlib.sha256(fh.read()).hexdigest()
    return out


def write(root, rel, data):
    path = os.path.join(root, "benchmarks", rel)
    assert not os.path.exists(path)
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(data if isinstance(data, str) else json.dumps(data))


def edit_manifest(root, change):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        manifest = json.load(fh)
    change(manifest)
    with open(path, "w") as fh:
        json.dump(manifest, fh)


def read_manifest(root):
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def manifest_faults(before, after):
    """What an edit of BENCHMARK.json did beyond what a PR that adds may
    do: a list of sentences, empty when the edit only appended entries and
    appended NEW cells' names to ``workloads`` lists that were there."""
    faults = []
    for key in ("command", "paths", "run_seconds"):
        if before[key] != after[key]:
            faults.append(f"{key} changed")
    new_cells = {w["name"] for w in after["workloads"]} \
        - {w["name"] for w in before["workloads"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        was, now = before[group], after[group]
        if [e["name"] for e in now[:len(was)]] != [e["name"] for e in was]:
            faults.append(f"{group}: an entry removed, renamed, reordered "
                          "or put before one that was there")
            continue
        for old, new in zip(was, now):
            rest = {k: v for k, v in new.items() if k != "workloads"}
            if rest != {k: v for k, v in old.items() if k != "workloads"}:
                faults.append(f"{group}/{old['name']}: a key changed")
            if ("workloads" in old) != ("workloads" in new):
                faults.append(f"{group}/{old['name']}: workloads list "
                              "added or taken away")
                continue
            cells, more = old.get("workloads", []), new.get("workloads", [])
            if more[:len(cells)] != cells:
                faults.append(f"{group}/{old['name']}: a cell removed from "
                              "workloads, or the list reordered")
            elif not set(more[len(cells):]) <= new_cells:
                faults.append(f"{group}/{old['name']}: an accepted cell "
                              "appended to workloads")
    return faults


def test_the_one_permitted_edit_of_an_entry_and_every_other_one():
    import copy

    before = read_manifest(rehearsal.REPO)

    def edited(change):
        after = copy.deepcopy(before)
        after["workloads"].append({"name": "ninth", "config": "c",
                                   "traffic": "t", "chips": 1, "why": "w"})
        change(after)
        return manifest_faults(before, after)

    def metric(manifest, name):
        return next(m for m in manifest["per_layer"] if m["name"] == name)

    assert edited(lambda m: None) == []
    assert edited(lambda m: metric(m, "expand_ms")["workloads"].append(
        "ninth")) == []
    assert edited(lambda m: m["per_layer"].append(
        dict(metric(m, "expand_ms"), name="new_thing_ms"))) == []
    # every other edit of what is there
    for change, word in (
            (lambda m: metric(m, "expand_ms")["workloads"].insert(
                0, "ninth"), "reordered"),
            (lambda m: metric(m, "expand_ms")["workloads"].pop(), "removed"),
            (lambda m: metric(m, "expand_ms")["workloads"].reverse(),
             "reordered"),
            (lambda m: metric(m, "lm_norm_device_ms")["workloads"].append(
                "sd15_expand_solo"), "accepted cell"),
            (lambda m: metric(m, "host_overhead_ms").update(
                workloads=["ninth"]), "added or taken away"),
            (lambda m: metric(m, "expand_ms").pop("workloads"),
             "added or taken away"),
            (lambda m: metric(m, "expand_ms").update(unit="s"),
             "a key changed"),
            (lambda m: m["end_to_end"][0].update(bound=0.05),
             "a key changed"),
            (lambda m: m["per_layer"].pop(3), "removed"),
            (lambda m: m["per_layer"].insert(0, dict(
                metric(m, "expand_ms"), name="first")), "put before"),
            (lambda m: m.update(run_seconds=10), "run_seconds changed")):
        faults = edited(change)
        assert faults and any(word in f for f in faults), (word, faults)


def flops_metric(name, counter, cell):
    spec = {"name": name, "layer": "models and XLA kernels", "unit": "%",
            "better": "higher", "source": "device_trace",
            "moves": "images_per_s", "reader": "flops_util",
            "args": {"counter": counter}}
    entry = {k: spec[k] for k in ("name", "unit", "better", "source",
                                  "layer", "moves")}
    return spec, dict(entry, workloads=[cell])


def test_new_config_cell_metric_reader_generator_without_an_edit(tmp_path):
    root = rehearsal.make_root(str(tmp_path))
    before = digest(root)
    # a family through an importable factory, with components and a
    # counter of its own
    os.makedirs(os.path.join(root, "newmodel"))
    with open(os.path.join(root, "newmodel", "__init__.py"), "w") as fh:
        fh.write("from stable_diffusion_webui_distributed_tpu.models."
                 "configs import TINY_V\n\ndef family():\n    return TINY_V\n")
    write(root, "components/tiny_v_parts.py", TINY_V_COMPONENTS)
    write(root, "harness/flops_tiny_v.py", TINY_V_COUNTER)
    write(root, "configs/tiny_v.json", {
        "name": "tiny_v", "factory": "newmodel:family", "policy": "F32",
        "weight_seed": 5, "source": "test", "reduced": [],
        "components": "tiny_v_parts", "counter": "flops_tiny_v"})
    write(root, "traffic/three_requests.json", {
        "loop": "counted", "requests": 3,
        "payload": {"prompt": "x", "steps": 2, "width": 32, "height": 32,
                    "batch_size": 1, "sampler_name": "Euler a"}})
    write(root, "workloads/tiny_v_counted.json", {
        "config": "tiny_v", "traffic": "three_requests", "chips": 1,
        "mesh": None, "warmup_requests": 1, "why": "test",
        "server_env": {"SDTPU_BUCKET_LADDER": "32x32",
                       "SDTPU_BATCH_LADDER": "1"}})
    write(root, "layer_metrics/answered_share.json", {
        "name": "answered_share", "layer": "HTTP surface and host tail",
        "unit": "%", "better": "higher", "source": "program_counter",
        "moves": "images_per_s", "reader": "answered", "args":
        {"of": "records"}})
    spec, entry = flops_metric("tiny_v_flops_util", "flops_tiny_v",
                               "tiny_v_counted")
    write(root, "layer_metrics/tiny_v_flops_util.json", spec)
    write(root, "readers/answered.py", READER)
    write(root, "generators/counted.py", GENERATOR)

    def change(manifest):
        manifest["configs"].append({
            "name": "tiny_v", "source": "test",
            "file": "benchmarks/configs/tiny_v.json", "reduced": [],
            "why": "test"})
        manifest["workloads"].append({
            "name": "tiny_v_counted", "config": "tiny_v",
            "traffic": "three_requests", "chips": 1, "why": "test"})
        manifest["per_layer"].append({
            "name": "answered_share", "unit": "%", "better": "higher",
            "source": "program_counter",
            "layer": "HTTP surface and host tail",
            "moves": "images_per_s", "workloads": ["tiny_v_counted"]})
        manifest["per_layer"].append(entry)
        # the UNet's own utilisation is asked for here too: its counter is
        # not this configuration's, so it must be left out, not raise
        next(m for m in manifest["per_layer"]
             if m["name"] == "unet_flops_util")["workloads"].append(
                 "tiny_v_counted")

    was = read_manifest(root)
    edit_manifest(root, change)
    assert manifest_faults(was, read_manifest(root)) == []
    rc, result, output = rehearsal.drive(root, "tiny_v_counted", trace=1)
    assert rc == 0 and result is not None, output[-3000:]
    assert result["correct"] is True
    # the slice took one of the three, the counted loop sent three more
    assert result["attempted"] >= 3
    assert result["metrics"]["answered_share"]["value"] == 100.0
    assert "collective_share" not in result["metrics"]
    assert "tiny_v leaf_rule asked" in output
    assert result["metrics"]["tiny_v_flops_util"]["value"] > 0
    assert "unet_flops_util" not in result["metrics"]
    assert "raised" not in output
    after = digest(root)
    assert {p: h for p, h in after.items() if p in before} == before


@pytest.fixture()
def standin_root(tmp_path):
    """The benchmark's copy plus an architecture the harness has never
    heard of, as new files only."""
    root = rehearsal.make_root(str(tmp_path))
    before = digest(root)
    with open(os.path.join(root, "standin_model.py"), "w") as fh:
        fh.write(STANDIN_MODEL)
    write(root, "components/standin_parts.py", STANDIN_COMPONENTS)
    write(root, "harness/flops_standin.py", STANDIN_COUNTER)
    write(root, "reference/standin_ref.py", STANDIN_REFERENCE)
    write(root, "reference/standin.json", {
        "tolerance_relative_rms": 1e-4,
        "tolerance_reason": "float32 against float32 differs by the order "
        "of sums (1e-6 here); bfloat16 matmuls read 3e-3 and must fail"})
    write(root, "configs/standin.json", {
        "name": "standin", "factory": "standin_model:family",
        "policy": "F32", "weight_seed": 11, "source": "test", "reduced": [],
        "components": "standin_parts", "counter": "flops_standin",
        "reference": "standin_ref", "reference_latent": 16})
    spec, entry = flops_metric("standin_flops_util", "flops_standin",
                               "standin_cell")
    write(root, "layer_metrics/standin_flops_util.json", spec)
    was = read_manifest(root)
    edit_manifest(root, lambda m: (
        m["configs"].append({
            "name": "standin", "source": "test", "reduced": [],
            "file": "benchmarks/configs/standin.json", "why": "test"}),
        m["per_layer"].append(entry)))
    assert manifest_faults(was, read_manifest(root)) == []
    sys.path.insert(0, root)
    try:
        yield root
    finally:
        sys.path.remove(root)
        sys.modules.pop("standin_model", None)
    after = digest(root)
    assert {p: h for p, h in after.items() if p in before} == before


def test_an_architecture_that_is_not_the_unet_by_new_files_only(
        standin_root, capsys):
    bench = files.Bench(standin_root)
    config = bench.config("standin")
    family = files.resolve_family(config)
    assert not any(hasattr(family, name)
                   for name in ("unet", "text_encoder", "vae"))

    # weights: through its own components file, the stacked expert kernel
    # at the stated variance 1/in (the name-blind rule: 1/(experts x in))
    params = weights.family_params(bench.components(config), family,
                                   jnp.float32, int(config["weight_seed"]))
    assert set(params) == {"denoiser", "text_encoder_2"}
    stacked = params["denoiser"]["experts_in"]
    assert stacked.shape == (family.experts, family.width, family.hidden)
    assert float(stacked.std()) == pytest.approx(family.width ** -0.5,
                                                 rel=0.05)
    assert float(params["denoiser"]["router"]["kernel"].std()) \
        == pytest.approx(family.width ** -0.5, rel=0.15)

    # its counter feeds a FLOP metric; the UNet's is left out, not raised
    @dataclasses.dataclass
    class Rec:
        payload: dict
        traced: bool = True

    context = {
        "records": [Rec({"steps": 4, "batch_size": 2})], "family": family,
        "chips": 1, "trace": {"busy_s": 2.0},
        "peak": {"bf16_flops_per_s": 1e9}, "config": config,
        "counter": bench.counter(config), "bench": bench}
    read = bench.load("readers", "flops_util").read
    own = bench.layer_metric("standin_flops_util")["args"]
    per_image = 4 * (2 * 16 * 32 * 4 + 4 * 16 * 4 * 32 * 64)
    assert read(context, **own) == pytest.approx(
        100.0 * 2 * per_image / (2.0 * 1e9))
    assert read(context,
                **bench.layer_metric("unet_flops_util")["args"]) is None
    # and a configuration that says it has no counter reads nothing
    assert bench.counter({"counter": None}) == (None, None)
    assert read(dict(context, counter=(None, None)), **own) is None

    # verify_reference runs ITS reference, under ITS tolerance
    import benchmarks.verify_reference as verify

    assert verify.main(["--config", "standin"], root=standin_root) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["passed"] and result["tolerance_relative_rms"] == 1e-4
    assert result["program_vs_reference_relative_rms"] < 1e-5
    assert result["control_vs_reference_relative_rms"] > 1e-3
    assert result["control"].startswith("the module's own bfloat16")


# -- a ninth prompt expander joins the metrics that exist -------------------

TINY_EXPANDER = ("stable_diffusion_webui_distributed_tpu.models.configs:"
                 "tiny_expander")
#: what an expander cell with full and window attention, a dense layer
#: and experts reports besides what every cell reports
JOINED = ["expand_ms", "expand_prefill_ms", "expand_decode_ms",
          "expand_ahead_ms", "expert_kernel_sites", "lm_linear_device_ms",
          "expert_device_ms", "lm_attn_device_ms", "lm_other_device_ms",
          "lm_decode_bytes_util"]


def test_an_expander_cell_joins_by_its_name_appended_and_no_metric_file(
        tmp_path):
    root = rehearsal.make_root(str(tmp_path))
    before = digest(root)
    bench = files.Bench(root)
    config = dict(bench.config("sd15_laguna_expand"), name="tiny_ninth",
                  factory=TINY_EXPANDER, policy="F32")
    assert config["op_classes"] == "laguna"     # its class file, by stem
    write(root, "configs/tiny_ninth.json", config)
    traffic = bench.traffic("sd15_512_expand384")
    args = traffic["payload"]["alwayson_scripts"]["prompt expansion"][
        "args"][0]
    args.update(max_new_tokens=40, context_chunks=1,
                instruction=" ".join(args["instruction"].split()[:30]))
    write(root, "traffic/tiny_expand40.json", traffic)
    write(root, "workloads/tiny_ninth_solo.json", dict(
        bench.read("workloads", "sd15_expand_solo.json"),
        config="tiny_ninth", traffic="tiny_expand40"))

    def change(manifest):
        manifest["configs"].append({
            "name": "tiny_ninth", "source": "test", "reduced": [],
            "file": "benchmarks/configs/tiny_ninth.json", "why": "test"})
        manifest["workloads"].append({
            "name": "tiny_ninth_solo", "config": "tiny_ninth",
            "traffic": "tiny_expand40", "chips": 1, "why": "test"})
        for name in JOINED:
            next(m for m in manifest["per_layer"]
                 if m["name"] == name)["workloads"].append("tiny_ninth_solo")

    was = read_manifest(root)
    edit_manifest(root, change)
    now = read_manifest(root)
    assert manifest_faults(was, now) == []
    assert len(now["per_layer"]) == len(was["per_layer"])   # no clone
    rc, result, output = rehearsal.drive(root, "tiny_ninth_solo", trace=1,
                                         seconds=3.0)
    assert rc == 0 and result is not None, output[-3000:]
    assert result["correct"] is True and "raised" not in output
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["expand_ms"] > m["expand_decode_ms"] > 0
    assert m["expand_prefill_ms"] > 0 and "expand_ahead_ms" in m
    assert "expert_kernel_sites" in m
    # the metrics of other kinds of expander list their own cells
    assert not {"expand_fork_ms", "lm_tokens_per_step",
                "lm_latent_device_ms", "conv_sites"} & set(m)
    after = digest(root)
    assert {p: h for p, h in after.items() if p in before} == before
    assert not [p for p in after if p not in before
                and os.sep + "layer_metrics" + os.sep in p]

    # what only a chip's trace feeds, through the readers as run.py calls
    # them: the class file and the bytes are found from the configuration
    bench = files.Bench(root)
    config = bench.config("tiny_ninth")
    family = files.resolve_family(config)

    @dataclasses.dataclass
    class Rec:
        payload: dict
        traced: bool = True

    payload = dict(traffic["payload"], prompt="a red fox")
    scope = "jit(f)/DecoderLM/while/body/layers_1/"
    rows = [{"module": "jit_expand_decode_chunk", "seconds": seconds,
             "scope": scope + tail, "category": "x", "name": "fusion"}
            for tail, seconds in (("attn/q_proj/dot_general", 0.004),
                                  ("attn/exp", 0.002),
                                  ("mlp/top_k", 0.003),
                                  ("input_norm/rsqrt", 0.001))]
    status = {"serving": {"expander": {
        "decode_steps": 0, "tokens_decoded": 0, "experts_read": 0}}}
    after_window = {"serving": {"expander": {
        "decode_steps": 64, "tokens_decoded": 64, "experts_read": 128}}}
    context = {
        "records": [Rec(payload)], "family": family, "chips": 1,
        "config": config, "bench": bench,
        "trace": {"op_table": rows, "devices": {0: {}},
                  "modules": {"jit_expand_decode_chunk": 0.01},
                  # 40 tokens: two launches of 32 steps, both in the trace
                  "module_calls": {"jit_expand_decode_chunk": 2}},
        "peak": {"hbm_bytes_per_s": 1e9},
        "status_before": status, "status_after": after_window}
    read = {name: bench.load("readers", spec["reader"]).read(
        context, **spec["args"])
        for name in JOINED[5:]
        for spec in [bench.layer_metric(name)]}
    assert read["lm_linear_device_ms"] == pytest.approx(4.0)
    assert read["lm_attn_device_ms"] == pytest.approx(2.0)
    assert read["expert_device_ms"] == pytest.approx(3.0)
    assert read["lm_other_device_ms"] == pytest.approx(1.0)
    walker = bench.load("harness", "bytes_lm")
    first = 1 + len(args["instruction"].split()) + 3
    assert read["lm_decode_bytes_util"] == pytest.approx(
        100 * walker.decode_bytes(family.expander, first, 64, 2.0, 1.0)
        / (0.01 * 1e9))
    # a class its file lacks, a configuration that names no stem: nothing
    classer = bench.load("readers", "op_class_ms")
    assert classer.read(context, cls="norm") is None
    assert classer.read(dict(context, config={}), cls="linear") is None
    assert classer.read(dict(context, config={}), cls="linear",
                        classes="laguna_decode") == pytest.approx(4.0)

"""Every cell's files, end to end on the CPU through run.py, and what each
expander configuration brought, as cases of parametrised tests.

The eight prompt-expander configurations each have a module of
``expanders/`` that holds ONLY what is its own: the tiny family its
rehearsal swaps in (``TINY_FACTORY``), what its traced rehearsal must read
(``traced``) and its checks of its traffic, its configuration against the
catalog's row, its leaf rules, its op classes and its reference's
recorded readings (``CHECKS``). What is the same for all of them, the
rehearsal itself and the metrics' files, is once, here. A ninth
configuration ADDS a module there, named by its ``op_classes`` stem, and
edits nothing: the modules are found by their file names (until PR 58 it
added a ``test_<arch>_cell_cpu.py`` of four hundred lines, most of them a
copy)."""

import glob
import importlib
import os

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

MANIFEST = rehearsal.manifest_with_prepared()
BENCH = files.Bench(rehearsal.REPO)
#: every module of expanders/, by its file's name: no list to add a row to
EXPANDERS = {
    name: importlib.import_module("benchmarks.tests.expanders." + name)
    for name in sorted(
        os.path.basename(path)[:-len(".py")] for path in glob.glob(
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "expanders", "[!_]*.py")))}
BY_CELL = {module.CELL: module for module in EXPANDERS.values()}
CELLS = [(w["name"], w["chips"]) for w in MANIFEST["workloads"]]


def metrics_of(kind, cell):
    return [m for m in MANIFEST[kind]
            if "workloads" not in m or cell in m["workloads"]]


def chip_only(cell):
    """Read from what only a TPU's trace or memory_stats() holds: the
    CPU's trace names no executable and no tf_op, and reports no memory.
    Of the device's metrics the idle ones and the collectives' read the
    CPU's trace too."""
    return {m["name"] for m in metrics_of("per_layer", cell)
            if m["name"] == "peak_hbm_gib" or (
                m["source"] == "device_trace"
                and BENCH.layer_metric(m["name"])["reader"] in (
                    "op_class_ms", "kernel_roofline", "bytes_util_steps"))}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The benchmark's copy at the tiny families: SD's by rehearsal.py, each
    expander's by its module's factory, their requests cut to 30 words of
    instruction and 40 tokens decoded in one context chunk."""
    root = rehearsal.make_root(str(tmp_path_factory.mktemp("bench")))
    for module in EXPANDERS.values():
        rehearsal._rewrite(
            os.path.join(root, "benchmarks", "configs",
                         module.CONFIG + ".json"),
            lambda c, m=module: c.update(factory=m.TINY_FACTORY,
                                         policy="F32"))

    def shorter(traffic):
        args = traffic["payload"]["alwayson_scripts"][
            "prompt expansion"]["args"][0]
        args.update(max_new_tokens=40, context_chunks=1,
                    instruction=" ".join(args["instruction"].split()[:30]))

    for name in {module.TRAFFIC for module in EXPANDERS.values()}:
        rehearsal._rewrite(os.path.join(root, "benchmarks", "traffic",
                                        name + ".json"), shorter)
    return root


@pytest.mark.parametrize("cell,chips", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contract_line(root, cell, chips, trace):
    expander = BY_CELL.get(cell)
    # an expanded request is slower and a traced window spends its head in
    # the profiler: six seconds hold the traced request and several behind
    # it, which ``between_requests_ms`` reads
    rc, result, output = rehearsal.drive(
        root, cell, trace, chips, seconds=6.0 if expander else 2.0)
    assert rc == 0 and result is not None, output[-3000:]
    want = set(rehearsal.RESULT_KEYS) | ({"breakdown"} if trace else set())
    assert set(result) == want
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    assert result["device"]["count"] == chips
    assert result["device"]["platform"] == "cpu"   # a rehearsal, no speed
    kind = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in metrics_of(kind, cell)}
    reported = set(result["metrics"])
    assert reported <= names
    assert names - reported <= (chip_only(cell) if trace else set())
    assert "raised" not in output
    for value in result["metrics"].values():
        assert set(value) == {"value", "unit"}
        assert isinstance(value["value"], float)
    if trace:
        assert result["device"]["busy_s"] > 0
        assert result["device"]["window_s"] > result["device"]["busy_s"]
        assert 1 <= len(result["breakdown"]["device_ops"]) <= 10
        assert len(result["breakdown"]["idle_gaps"]) <= 10
        named = [name for name, _ in result["breakdown"]["idle_gaps"]]
        assert any(n.startswith("head: ") for n in named)
    assert "nothing compiled inside the window" in output
    if expander and trace:
        m = {k: v["value"] for k, v in result["metrics"].items()}
        # every expander cell under the one name a span
        assert m["expand_ms"] > m["expand_decode_ms"] > 0
        assert m["expand_prefill_ms"] > 0 and m["expand_ahead_ms"] > 0
        # the expander's sites are masked grouped-query ones: all on XLA
        assert m["attention_tiled_sites"] == 0
        expander.traced(m)


CHECKS = [(arch, check) for arch, module in EXPANDERS.items()
          for check in module.CHECKS]


def check_id(check):
    func = getattr(check, "func", check)
    return func.__name__[len("check_"):] + "".join(
        "-" + str(a) for a in getattr(check, "args", ()))


@pytest.mark.parametrize("arch,check", CHECKS,
                         ids=[f"{a}-{check_id(c)}" for a, c in CHECKS])
def test_what_each_expander_configuration_brought(arch, check):
    check()


OF_A_CELL = [(module.CELL, m["name"]) for module in EXPANDERS.values()
             for m in metrics_of("per_layer", module.CELL)
             if "workloads" in m]


@pytest.mark.parametrize("cell,name", OF_A_CELL)
def test_every_listed_metric_names_a_reader_and_files_that_exist(cell, name):
    """Each metric that lists an expander cell, in that cell: its entry
    matches its file, its reader loads, and what the reader looks up by
    name for this cell's configuration is there."""
    spec = BENCH.layer_metric(name)
    entry = next(m for m in BENCH.manifest["per_layer"]
                 if m["name"] == name)
    for key in ("layer", "unit", "better", "source", "moves"):
        assert entry[key] == spec[key], key
    assert hasattr(BENCH.load("readers", spec["reader"]), "read")
    config = BENCH.config(BENCH.cell(cell)["config"])
    if spec["reader"] == "op_class_ms":
        reader = BENCH.load("readers", "op_class_ms")
        stem = spec["args"].get("classes") or reader.decode_classes(
            {"config": config})
        classes = BENCH.read("op_classes", stem + ".json")
        assert spec["args"]["cls"] in {r["class"] for r in classes["classes"]}
    if spec["reader"] == "bytes_util_steps":
        assert hasattr(BENCH.load("harness", spec["args"]["needs"]),
                       "decode_bytes")
        from stable_diffusion_webui_distributed_tpu.pipeline import expand

        assert spec["args"]["steps_per_call"] == expand.DECODE_STEPS
    if "moves" in entry:
        assert entry["moves"] in ("request_p50_s", "images_per_s")


def test_each_expander_has_its_class_files_and_no_bytes_file():
    for arch, module in EXPANDERS.items():
        config = BENCH.config(module.CONFIG)
        assert config["op_classes"] == arch
        for phase in ("decode", "prefill"):
            assert os.path.exists(BENCH.path(
                "op_classes", f"{arch}_{phase}.json"))
    assert [os.path.basename(p) for p in glob.glob(
        BENCH.path("harness", "bytes_*.py"))] == ["bytes_lm.py"]


def test_refuses_a_machine_without_the_chip(root):
    """No switch in the child: the CPU is refused, no result is printed."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, os.path.join(rehearsal.REPO, "benchmarks", "run.py"),
         "--workload", CELLS[0][0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=rehearsal.REPO, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout

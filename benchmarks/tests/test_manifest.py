"""BENCHMARK.json against the files it names and the contract's limits."""

import json
import os
import re

from benchmarks.tests import rehearsal

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = os.path.join(rehearsal.REPO, "benchmarks")


def load(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as fh:
        return json.load(fh)


MANIFEST = load(rehearsal.REPO, "BENCHMARK.json")


def test_keys_names_units_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(rehearsal.REPO,
                                        "BENCHMARK.json")) < 64 * 1024
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for metric in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
    for metric in MANIFEST["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "bound", "source"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)
    for cell in MANIFEST["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
        assert NAME.match(cell["traffic"])


def test_every_entry_has_its_files():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for config in configs.values():
        data = load(rehearsal.REPO, config["file"])
        assert data["reduced"] == config["reduced"]
        assert "family" in data or "factory" in data
    for cell in MANIFEST["workloads"]:
        assert cell["config"] in configs
        data = load(BENCH, "workloads", cell["name"] + ".json")
        assert data["config"] == cell["config"]
        assert data["traffic"] == cell["traffic"]
        assert data["chips"] == cell["chips"]
        traffic = load(BENCH, "traffic", cell["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            BENCH, "generators", traffic["loop"] + ".py"))
    used = {c["config"] for c in MANIFEST["workloads"]}
    assert used == set(configs)


def test_per_layer_entries_match_their_files():
    end_to_end = {m["name"]: m for m in MANIFEST["end_to_end"]}
    cells = {w["name"] for w in MANIFEST["workloads"]}
    for metric in MANIFEST["per_layer"]:
        assert set(metric) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        spec = load(BENCH, "layer_metrics", metric["name"] + ".json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == metric[key], (metric["name"], key)
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
        moved = end_to_end[metric["moves"]]
        assert (set(metric.get("workloads", cells))
                <= set(moved.get("workloads", cells)))
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")


def specs_of(cell):
    """(entry, its file) of every per-layer metric the cell reports."""
    return [(m, load(BENCH, "layer_metrics", m["name"] + ".json"))
            for m in MANIFEST["per_layer"]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def config_of(cell):
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == cell["config"])
    return load(rehearsal.REPO, entry["file"])


def decode_classes(config):
    """The class file a configuration names for its decode executable, as
    readers/op_class_ms.py finds it."""
    stem = config.get("op_classes")
    return stem + "_decode" if stem else None


def resolved(spec, config):
    """A metric's (reader, args) in one cell, with the file names its reader
    would find from the cell's configuration written out: two metrics that
    resolve alike in a cell read the same thing there."""
    args = dict(spec.get("args", {}))
    if spec["reader"] == "op_class_ms" and "classes" not in args:
        args["classes"] = decode_classes(config)
    return spec["reader"], json.dumps(args, sort_keys=True)


def clones(manifest, spec_of):
    """Metrics that read what another metric reads: everywhere (the same
    reader and args) or in one cell (alike once the configuration's file
    names are written out). ``[(cell or None, metric, its twin)]``."""
    out, seen = [], {}
    for metric in manifest["per_layer"]:
        spec = spec_of(metric["name"])
        key = (spec["reader"], json.dumps(spec.get("args", {}),
                                          sort_keys=True))
        if key in seen:
            out.append((None, metric["name"], seen[key]))
        seen.setdefault(key, metric["name"])
    for cell in manifest["workloads"]:
        config, found = config_of(cell), {}
        for metric in manifest["per_layer"]:
            if "workloads" in metric and cell["name"] not in metric[
                    "workloads"]:
                continue
            key = resolved(spec_of(metric["name"]), config)
            if key in found:
                out.append((cell["name"], metric["name"], found[key]))
            found.setdefault(key, metric["name"])
    return out


def spec_on_disk(name):
    return load(BENCH, "layer_metrics", name + ".json")


def test_no_two_metrics_of_a_cell_read_the_same_thing():
    """One metric a thing measured: a cell that joins appends its name to
    the ``workloads`` list of the metric that exists (README, "Adding
    things"); a copy of a metric under another name cannot come back."""
    assert len(MANIFEST["per_layer"]) <= 128
    assert clones(MANIFEST, spec_on_disk) == []


def test_a_clone_under_a_prefix_or_a_named_class_file_is_found():
    """What seven model_config PRs did (PR 52: fourteen times), and the
    one way around the first check: naming the class file the
    configuration already names."""
    def with_metric(name, like, args=None, cells=None):
        spec = dict(spec_on_disk(like), name=name)
        if args is not None:
            spec["args"] = args
        entry = dict(next(m for m in MANIFEST["per_layer"]
                          if m["name"] == like), name=name)
        if cells is not None:
            entry["workloads"] = cells
        manifest = dict(MANIFEST, per_layer=MANIFEST["per_layer"] + [entry])
        return manifest, lambda n: spec if n == name else spec_on_disk(n)

    cell = "sd15_ouro_expand_b4"
    assert clones(*with_metric("o9_expand_ms", "expand_ms", cells=[cell])) \
        == [(None, "o9_expand_ms", "expand_ms"),
            (cell, "o9_expand_ms", "expand_ms")]
    named = {"classes": "ouro_decode", "cls": "norm"}
    assert clones(*with_metric("o9_norm_device_ms", "lm_norm_device_ms",
                               args=named)) \
        == [(cell, "o9_norm_device_ms", "lm_norm_device_ms")]
    # another configuration's file is another thing: no clone
    other = {"classes": "laguna_decode", "cls": "attn"}
    assert clones(*with_metric("o9_attn_device_ms", "lm_norm_device_ms",
                               args=other)) == []


def classes_of(config, spec):
    name = spec["args"].get("classes") or decode_classes(config)
    return name and load(BENCH, "op_classes", name + ".json")


def test_op_class_metrics_name_a_class_of_their_file():
    for metric in MANIFEST["per_layer"]:
        spec = load(BENCH, "layer_metrics", metric["name"] + ".json")
        if spec["reader"] != "op_class_ms":
            continue
        assert metric.get("workloads"), metric["name"]
        for cell in MANIFEST["workloads"]:
            if cell["name"] not in metric["workloads"]:
                continue
            # the named file, or the one the cell's configuration names
            classes = classes_of(config_of(cell), spec)
            assert classes, (metric["name"], cell["name"])
            names = [rule["class"] for rule in classes["classes"]]
            assert spec["args"]["cls"] in names, (metric["name"],
                                                  cell["name"])
            # the last rule takes what is left: the classes partition
            assert not {"scope", "category", "name"} & set(
                classes["classes"][-1])


def test_every_class_of_a_cells_decode_file_is_a_metric_the_cell_reports():
    """The classes of a configuration's decode file partition its
    executable: a cell reports all of them (their sum is the executable's
    device time) and is listed under no class its file lacks."""
    by_class = {}
    for metric in MANIFEST["per_layer"]:
        spec = load(BENCH, "layer_metrics", metric["name"] + ".json")
        if spec["reader"] == "op_class_ms" and "classes" not in spec["args"]:
            by_class[spec["args"]["cls"]] = set(metric["workloads"])
    stems = set()
    for cell in MANIFEST["workloads"]:
        config = config_of(cell)
        if not config.get("op_classes"):
            assert not any(cell["name"] in cells
                           for cells in by_class.values())
            continue
        stems.add(config["op_classes"])
        classes = load(BENCH, "op_classes",
                       decode_classes(config) + ".json")
        assert classes["module"] == "jit_expand_decode_chunk"
        have = {rule["class"] for rule in classes["classes"]}
        listed = {cls for cls, cells in by_class.items()
                  if cell["name"] in cells}
        assert listed == have, (cell["name"], listed ^ have)
    # every configuration that names a stem has a cell that was looked at,
    # each stem its own: the eight of PR 58 and whoever came later
    named = [load(rehearsal.REPO, c["file"]).get("op_classes")
             for c in MANIFEST["configs"]]
    named = [stem for stem in named if stem]
    assert sorted(stems) == sorted(named) and len(stems) >= 8


def test_a_count_a_layer_divides_by_the_configurations_own_layers():
    """``status_ratio``'s ``per`` names a tuple of the expander's LMConfig:
    every listed cell's family has it, and it is not empty."""
    from benchmarks.harness import files

    bench = files.Bench(rehearsal.REPO)
    for metric in MANIFEST["per_layer"]:
        spec = load(BENCH, "layer_metrics", metric["name"] + ".json")
        per = spec.get("args", {}).get("per")
        if spec["reader"] != "status_ratio" or per is None:
            continue
        assert "scale" not in spec["args"]      # no literal a layer count
        for name in metric["workloads"]:
            config = bench.config(bench.cell(name)["config"])
            model = files.resolve_family(config).expander
            assert len(getattr(model, per)) > 0, (metric["name"], name)


def test_the_decode_roofline_is_one_metric_one_reader_one_file():
    found = [load(BENCH, "layer_metrics", m["name"] + ".json")
             for m in MANIFEST["per_layer"]
             if m["name"].endswith("decode_bytes_util")]
    assert [s["name"] for s in found] == ["lm_decode_bytes_util"]
    assert found[0]["reader"] == "bytes_util_steps"
    assert found[0]["args"]["needs"] == "bytes_lm"
    names = os.listdir(os.path.join(BENCH, "harness"))
    assert [n for n in names if n.startswith("bytes_")] == ["bytes_lm.py"]
    assert not os.path.exists(os.path.join(BENCH, "readers",
                                           "bytes_util.py"))


def test_every_metric_file_is_an_entry():
    """No file of layer_metrics/ is left behind by a merge (but
    collective_share: reader and test exist, no admitted cell has a
    mesh; and what prepared.json's cells bring)."""
    with open(os.path.join(BENCH, "prepared.json")) as fh:
        prepared = {m["name"] for m in json.load(fh)["per_layer"]}
    files_ = {n[:-5] for n in os.listdir(os.path.join(BENCH,
                                                      "layer_metrics"))}
    entries = {m["name"] for m in MANIFEST["per_layer"]}
    assert entries <= files_
    assert files_ - entries - prepared <= {"collective_share"}


def test_sdxl_pair_sends_sdxl_solos_request_from_two_clients():
    solo = load(BENCH, "traffic", "sdxl_1024_b1.json")
    pair = load(BENCH, "traffic", "sdxl_1024_b1_x2.json")
    assert pair["payload"] == solo["payload"]
    assert (pair["loop"], pair["route"]) == (solo["loop"], solo["route"])
    assert (solo["clients"], pair["clients"]) == (1, 2)
    cell = load(BENCH, "workloads", "sdxl_pair.json")
    assert cell["server_env"] == {"SDTPU_BATCH_LADDER": "2"}
    assert cell["trace"]["requests"] == 2


def test_nothing_imports_the_programs_own_benchmarks():
    banned = re.compile(
        r"^\s*(import|from)\s+(bench|chip_smoke|tools|tests)\b", re.M)
    for folder, _, names in os.walk(BENCH):
        if os.sep + "." in folder:
            continue
        for name in names:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as fh:
                    assert not banned.search(fh.read()), name

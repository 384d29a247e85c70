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

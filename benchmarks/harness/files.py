"""Finds everything by name: BENCHMARK.json names a cell, the cell's file
names its configuration and traffic, metric files name their readers, and
readers and generators are modules loaded from their own files. What
belongs to one architecture is found from the configuration's file the same
way: its ``components`` (where seeded weights come from), its ``counter``
(operations its work needs), its ``reference`` and, for a prompt expander,
its ``op_classes`` (the stem of its class files: readers/op_class_ms.py).
Adding a cell, configuration, architecture, metric, reader or generator
adds files and edits none, but for the cell's name appended to the
``workloads`` lists of the metrics it reports (README, "Adding things").
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os


def read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


class Bench:
    """The benchmark's files under one repository root."""

    def __init__(self, root: str) -> None:
        self.root = root
        self.dir = os.path.join(root, "benchmarks")
        self.manifest = read_json(os.path.join(root, "BENCHMARK.json"))

    def path(self, *parts: str) -> str:
        return os.path.join(self.dir, *parts)

    def read(self, *parts: str) -> dict:
        return read_json(self.path(*parts))

    def cell(self, name: str) -> dict:
        """The manifest's entry merged over the cell's own file."""
        entry = next((w for w in self.manifest["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            known = ", ".join(w["name"] for w in self.manifest["workloads"])
            raise SystemExit(f"unknown workload {name!r} (known: {known})")
        cell = self.read("workloads", name + ".json")
        for key in ("config", "traffic", "chips"):
            if key in cell and cell[key] != entry[key]:
                raise SystemExit(
                    f"workloads/{name}.json says {key}={cell[key]!r}, "
                    f"BENCHMARK.json says {entry[key]!r}")
        return {**cell, **entry}

    def config(self, name: str) -> dict:
        entry = next(c for c in self.manifest["configs"]
                     if c["name"] == name)
        return read_json(os.path.join(self.root, entry["file"]))

    def traffic(self, name: str) -> dict:
        return self.read("traffic", name + ".json")

    def metrics(self, kind: str, cell: str) -> list[dict]:
        """The manifest's metrics of one kind that this cell reports."""
        return [m for m in self.manifest[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def layer_metric(self, name: str) -> dict:
        return self.read("layer_metrics", name + ".json")

    def components(self, config: dict):
        """``components/<name>.py`` of the configuration: where its seeded
        weights come from (harness/weights.py). A configuration that names
        none is a UNet + CLIP + VAE family."""
        return self.load("components",
                         config.get("components", "unet_clip_vae"))

    def counter(self, config: dict):
        """``harness/<name>.py`` with ``flops_per_image(family, payload)``:
        the operations one image needs, from shapes. ``(name, module)``;
        ``"counter": null`` in the file means there is none: (None, None).
        A configuration that names none gets the UNet's, ``flops``."""
        name = config.get("counter", "flops")
        return (name, self.load("harness", name)) if name else (None, None)

    def reference(self, config: dict):
        """``reference/<name>.py``: the configuration's plain reference
        (verify_reference.py says what it asks of one)."""
        return self.load("reference", config.get("reference", "unet_ref"))

    def load(self, folder: str, name: str):
        """The module ``benchmarks/<folder>/<name>.py``, by file."""
        path = self.path(folder, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"benchmarks_{folder}_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module


def resolve_family(config: dict):
    """A configuration names its family by key in the program's FAMILIES,
    or (for one the program does not list yet) by ``factory``:
    ``"package.module:callable"`` returning a ModelFamily."""
    if config.get("factory"):
        module, _, attr = config["factory"].partition(":")
        return getattr(importlib.import_module(module), attr)()
    from stable_diffusion_webui_distributed_tpu.models.configs import FAMILIES

    return FAMILIES[config["family"]]


def resolve_policy(config: dict):
    from stable_diffusion_webui_distributed_tpu.runtime import dtypes

    return getattr(dtypes, config.get("policy", "TPU"))

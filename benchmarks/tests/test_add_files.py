"""A later PR adds a configuration, a cell, a traffic mix, a per-layer
metric, a reader and a generator as NEW files plus entries in
BENCHMARK.json, and edits no file that is there."""

import hashlib
import json
import os

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


def digest(root):
    out = {}
    for folder, _, names in os.walk(os.path.join(root, "benchmarks")):
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


def test_new_config_cell_metric_reader_generator_without_an_edit(tmp_path):
    root = rehearsal.make_root(str(tmp_path))
    before = digest(root)
    # a family the harness has never heard of, through an importable factory
    os.makedirs(os.path.join(root, "newmodel"))
    with open(os.path.join(root, "newmodel", "__init__.py"), "w") as fh:
        fh.write("from stable_diffusion_webui_distributed_tpu.models."
                 "configs import TINY_V\n\ndef family():\n    return TINY_V\n")
    write(root, "configs/tiny_v.json", {
        "name": "tiny_v", "factory": "newmodel:family", "policy": "F32",
        "weight_seed": 5, "source": "test", "reduced": []})
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
    write(root, "readers/answered.py", READER)
    write(root, "generators/counted.py", GENERATOR)

    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        manifest = json.load(fh)
    manifest["configs"].append({
        "name": "tiny_v", "source": "test",
        "file": "benchmarks/configs/tiny_v.json", "reduced": [],
        "why": "test"})
    manifest["workloads"].append({
        "name": "tiny_v_counted", "config": "tiny_v",
        "traffic": "three_requests", "chips": 1, "why": "test"})
    manifest["per_layer"].append({
        "name": "answered_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "HTTP surface and host tail",
        "moves": "images_per_s", "workloads": ["tiny_v_counted"]})
    with open(path, "w") as fh:
        json.dump(manifest, fh)

    rc, result, output = rehearsal.drive(root, "tiny_v_counted", trace=1)
    assert rc == 0 and result is not None, output[-3000:]
    assert result["correct"] is True
    # the slice took one of the three, the counted loop sent three more
    assert result["attempted"] >= 3
    assert result["metrics"]["answered_share"]["value"] == 100.0
    assert "collective_share" not in result["metrics"]
    after = digest(root)
    assert {p: h for p, h in after.items() if p in before} == before

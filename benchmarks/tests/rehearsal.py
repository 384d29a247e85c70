"""CPU rehearsals drive the real ``run.py`` on a temporary copy of the
benchmark whose configurations name the tiny families and whose payloads
are tiny; the device check is switched here, by the test, in the child
process. A rehearsal yields counts and correctness, never a speed."""

from __future__ import annotations

import glob
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = {"sd15": "tiny", "sdxl_base": "tiny-xl",
        "sdxl_refiner": "tiny-refiner"}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}

DRIVER = """
import sys
sys.path[:0] = [{root!r}, {repo!r}]
import benchmarks.harness.device as device
device.ACCEPTED_PLATFORMS = ("cpu",)
import benchmarks.run as run
sys.exit(run.main(sys.argv[1:], root={root!r}))
"""


def _rewrite(path: str, change) -> None:
    with open(path, encoding="utf-8") as fh:
        data = json.load(fh)
    change(data)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(data, fh)


def manifest_with_prepared() -> dict:
    """BENCHMARK.json plus the cells of benchmarks/prepared.json: built
    and kept sound, not admitted (PERF.md section 7)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    with open(os.path.join(REPO, "benchmarks", "prepared.json")) as fh:
        prepared = json.load(fh)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        manifest[group] += prepared[group]
    return manifest


def make_root(tmp: str) -> str:
    """A copy of BENCHMARK.json (with the prepared cells) and benchmarks/
    under ``tmp``, cut to the tiny families at 32x32 and 2 steps."""
    root = os.path.join(tmp, "root")
    os.makedirs(root)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(manifest_with_prepared(), fh)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    for name, family in TINY.items():
        _rewrite(os.path.join(root, "benchmarks", "configs", name + ".json"),
                 lambda c, f=family: c.update(family=f, policy="F32"))
    for path in glob.glob(os.path.join(root, "benchmarks", "traffic",
                                       "*.json")):
        _rewrite(path, lambda t: t["payload"].update(
            width=32, height=32, steps=2))
    for path in glob.glob(os.path.join(root, "benchmarks", "workloads",
                                       "*.json")):
        _rewrite(path, lambda w: w["server_env"].update(
            SDTPU_BUCKET_LADDER="32x32"))
    return root


def drive(root: str, workload: str, trace: int, chips: int = 1,
          seconds: float = 2.0, seed: int = 2_500_000_011):
    """(exit code, parsed last stdout line or None, whole stdout)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER.format(root=root, repo=REPO),
         "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    lines = proc.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except ValueError:
        result = None
    if not isinstance(result, dict) or "correct" not in result:
        result = None
    return proc.returncode, result, proc.stdout + proc.stderr

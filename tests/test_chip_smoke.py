"""chip_smoke.py off the chip: the command refuses a CPU, and its phase
functions, driven here with tiny shapes and the tiny family, take the paths
the chip run takes. What only a TPU can satisfy is expected to FAIL here by
name — that list is the reason the command exists."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (repo root on path)


def _run(script, *args, **env):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script), *args],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


def test_command_refuses_a_cpu():
    proc = _run("chip_smoke.py")
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "check" not in proc.stdout     # no phase ran, nothing measured


def test_bench_config_refuses_a_cpu_before_building_a_model():
    proc = _run("bench.py", "--config", "1", SDTPU_BENCH_TINY="")
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""          # no metric of any name
    assert "zero-init" not in proc.stderr     # refused before any weights


def test_kernel_phase_on_cpu_agrees_but_has_no_kernel():
    report = chip_smoke.Report()
    chip_smoke.phase_kernels(report, [(2, 2, 128, 32)], seed=1)
    # interpret mode and the dense reference serve a CPU: right answers,
    # and no Mosaic call in the compiled text — the hidden fallback the
    # chip run exists to rule out
    assert sorted(report.failed) == [
        "flash kernel in compiled text [B2 H2 T128 D32]",
        "ragged kernel in compiled text [B2 H2 T128 D32]",
    ]
    # both times are printed beside what the default path takes there
    timed = report.facts["attention alone [B2 H2 T128 D32]"]
    assert timed.startswith("tiled ") and " ms, XLA " in timed
    assert timed.endswith("the default path takes xla")


@pytest.mark.slow
def test_serve_phase_with_the_tiny_family(monkeypatch):
    from stable_diffusion_webui_distributed_tpu.models.configs import TINY
    from stable_diffusion_webui_distributed_tpu.runtime import dtypes

    # the default ladder would pad 64x64 up to 512x512
    monkeypatch.setenv("SDTPU_BUCKET_LADDER", "64x64")
    report = chip_smoke.Report()
    chip_smoke.phase_serve(report, chip_smoke.CompileCounter(), TINY,
                           dtypes.TPU, 64, 64, 4, seed=1)
    assert report.failed == ["device reports peak memory"]   # a CPU has none


@pytest.mark.slow
def test_mesh_phase_with_the_tiny_family(monkeypatch):
    import jax

    from stable_diffusion_webui_distributed_tpu.models.configs import TINY
    from stable_diffusion_webui_distributed_tpu.runtime import dtypes

    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:4])
    report = chip_smoke.Report()
    chip_smoke.phase_mesh(report, chip_smoke.CompileCounter(), TINY,
                          dtypes.TPU, 64, 64, 4, seed=1)
    # memory_stats() is None on a CPU; everything else holds on four
    # virtual devices
    assert report.failed == ["mesh dp=4: every device holds bytes",
                             "mesh dp=2,tp=2: every device holds bytes"]
    # on CPU devices a dp split is bit-exact with the batch-1 program
    assert report.facts["mesh dp=4 against each image computed alone, "
                        "largest pixel difference"] == "0 levels"

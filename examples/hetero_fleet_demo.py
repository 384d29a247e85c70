"""Heterogeneous-fleet demo: a master with its own engine drives a second
`serve` process over HTTP — the reference's core deployment shape (master
webui + remote sdwui workers, /root/reference/scripts/distributed.py:284-319)
reproduced end-to-end with this framework on both ends of the wire.

What it proves, with real engines (no stubs):
  1. both nodes load the same checkpoint from disk (ldm safetensors ->
     converted Flax params);
  2. the World plans a split, fans out over HTTP, and merges a gallery in
     global image order with per-image worker attribution;
  3. the fleet's seed contract holds: images [start, start+n) produced by
     the remote worker are bitwise-identical to the master producing them
     itself (the TPU replacement for per-worker seed offsets);
  4. fleet restart reaches the remote via /server-restart.

Run:  python examples/hetero_fleet_demo.py
(Both processes run on the CPU by default. With SDTPU_DEMO_PLATFORM=tpu the
master takes the chip and the worker stays on CPU — same code path.)
"""

from __future__ import annotations

import builtins
import functools
import json
import os
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))  # tiny-checkpoint synthesizer


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def wait_for(url: str, timeout: float = 120.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            urllib.request.urlopen(url, timeout=2)
            return
        except Exception:
            time.sleep(0.5)
    raise TimeoutError(f"{url} not up after {timeout}s")


print = functools.partial(builtins.print, flush=True)  # killed-run visibility


def main() -> int:
    # SDTPU_DEMO_PLATFORM=tpu puts the master on the chip; the default is
    # the CPU. Set before anything imports jax.
    platform = os.environ.get("SDTPU_DEMO_PLATFORM", "cpu")
    os.environ["JAX_PLATFORMS"] = platform

    scratch = tempfile.mkdtemp(prefix="sdtpu-demo-")
    model_dir = os.path.join(scratch, "models")
    from test_registry import write_tiny_checkpoint  # tests/ helper

    write_tiny_checkpoint(model_dir)
    print(f"demo: tiny checkpoint written under {model_dir}")

    # pre-calibrated worker config (what production nodes carry after their
    # first sweep): a fresh node would otherwise self-benchmark with the
    # reference's fixed 512x512/20-step payload on this demo's single core
    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        BenchmarkPayload, ConfigModel, WorkerModel, save_config,
    )

    tiny_bp = BenchmarkPayload(width=64, height=64, steps=4)
    save_config(
        ConfigModel(benchmark_payload=tiny_bp,
                    workers=[{"master": WorkerModel(master=True,
                                                    avg_ipm=10.0)}]),
        os.path.join(scratch, "worker-config.json"))

    port = free_port()
    env = dict(os.environ)
    # the worker node ALWAYS stays on CPU: a chip belongs to one process,
    # and with SDTPU_DEMO_PLATFORM=tpu that is the master
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    worker = subprocess.Popen(
        [sys.executable, "-m", "stable_diffusion_webui_distributed_tpu.cli",
         "--model-dir", model_dir,
         "--distributed-config", os.path.join(scratch, "worker-config.json"),
         "--port", str(port), "serve"],
        env=env, cwd=scratch)
    try:
        wait_for(f"http://127.0.0.1:{port}/sdapi/v1/memory")
        print(f"demo: worker node serving on :{port} (pid {worker.pid})")

        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            GenerationPayload,
        )
        from stable_diffusion_webui_distributed_tpu.pipeline.registry import (
            ModelRegistry,
        )
        from stable_diffusion_webui_distributed_tpu.scheduler.worker import (
            HTTPBackend, LocalBackend, WorkerNode,
        )
        from stable_diffusion_webui_distributed_tpu.scheduler.world import (
            World,
        )

        # same dtype policy as the serve node's registry default — the seed
        # contract guarantees identical images only across engines with the
        # same numerics (policy is part of a fleet's model configuration)
        registry = ModelRegistry(model_dir)
        engine = registry.activate("tinymodel")
        world = World(ConfigModel(),
                      config_path=os.path.join(scratch, "master-config.json"))
        world.current_model = "tinymodel"
        # preset calibration on the master side too (see worker note above)
        world.add_worker(WorkerNode("master", LocalBackend(engine),
                                    master=True, benchmark_payload=tiny_bp,
                                    avg_ipm=10.0))
        world.add_worker(WorkerNode("remote",
                                    HTTPBackend("127.0.0.1", port),
                                    benchmark_payload=tiny_bp, avg_ipm=10.0))

        payload = GenerationPayload(prompt="a herd of cows", steps=4,
                                    width=64, height=64, batch_size=4,
                                    seed=1234)
        result = world.execute(payload)
        assert len(result.images) == 4, result.worker_labels
        assert result.seeds == [1234, 1235, 1236, 1237]
        by_worker = {}
        for lbl in result.worker_labels:
            by_worker[lbl] = by_worker.get(lbl, 0) + 1
        print(f"demo: merged gallery of 4 images, split {by_worker}, "
              f"seeds {result.seeds}")
        assert len(by_worker) == 2, "expected BOTH nodes to produce images"

        # seed contract: whatever range the remote produced, the master
        # reproduces pixel-identically (PNG bytes may differ: the serve
        # node uses the native encoder, this process the PIL fallback)
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            b64png_to_array,
        )
        import numpy as np

        start = result.worker_labels.index("remote")
        n = by_worker["remote"]
        local = engine.generate_range(payload, start, n)
        for j in range(n):
            a = np.asarray(b64png_to_array(local.images[j]))
            b = np.asarray(b64png_to_array(result.images[start + j]))
            assert np.array_equal(a, b), \
                f"remote image {start + j} differs from master's"
        print(f"demo: seed contract holds — remote images [{start}"
              f"..{start + n}) match the master pixel-for-pixel")

        restarted = world.restart_all()
        assert restarted == {"remote": True}, restarted
        print("demo: fleet restart delivered to the remote")

        print("DEMO PASSED: heterogeneous fleet end-to-end over HTTP")
        return 0
    finally:
        worker.terminate()
        try:
            worker.wait(timeout=10)
        except subprocess.TimeoutExpired:
            worker.kill()


if __name__ == "__main__":
    raise SystemExit(main())

"""TPU tuning sweep over the bench configs and policy knobs.

Each cell runs in its OWN subprocess: the parent never imports jax, so the
chip belongs to one child at a time, and a cell that dies (OOM) releases
the chip and its HBM on exit and cannot poison later cells — a round-3
one-process run showed an SDXL OOM leaving HBM unusable for every
subsequent cell, even with ``jax.clear_caches()`` between them. The
per-cell backend init is the price of isolation; the children share one
compile cache (runtime/mesh.py).

Results stream to ``PERF_SWEEP.jsonl`` (one JSON object per completed
cell) so a mid-sweep abort still leaves data.

Usage: python tools/sweep.py [cell ...]   (default: all cells)
Cells are named, e.g. ``c1-bf16``, ``c1-chunk10``, ``c1-flash``,
``c2-bf16``; ``--list`` prints them. A global deadline
(SDTPU_SWEEP_DEADLINE seconds, default 3300) stops launching new cells;
a running cell is never killed externally.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _policy(param="bf16", attention="xla", remat=False, decode_bf16=False,
            int8=False, int8_conv=False):
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.runtime import dtypes

    return dtypes.Policy(
        param_dtype=jnp.dtype(jnp.bfloat16 if param == "bf16"
                              else jnp.float32),
        attention_impl=attention,
        use_remat=remat,
        decode_in_bf16=decode_bf16,
        unet_int8=int8,
        unet_int8_conv=int8_conv,
    )


#: cell name -> (config number, policy kwargs, chunk size[, env overrides])
CELLS = {
    "c1-f32":     (1, {"param": "f32"}, 5),
    "c1-bf16":    (1, {}, 5),
    "c1-chunk10": (1, {}, 10),
    "c1-chunk20": (1, {}, 20),
    "c1-flash":   (1, {"attention": "flash"}, 5),
    "c1-chunk8":  (1, {}, 8),
    "c1-flash10": (1, {"attention": "flash"}, 10),
    "c2-bf16":    (2, {}, 5),
    "c2-chunk10": (2, {}, 10),   # round-3's c2 row predates the chunk-10
                                 # default win on c1 — measure it on SDXL
    "c2-flash":   (2, {"attention": "flash"}, 10),  # 4096-token SDXL attn
    "c2-remat":   (2, {"remat": True}, 5),
    "c3-bf16":    (3, {}, 5),
    "c4-bf16":    (4, {}, 5),
    "c5-bf16":    (5, {}, 5),
    # hires 2048² second pass: 65536-token SD1.5 self-attention is the
    # quadratic blowup flash attention exists for
    "c5-flash":   (5, {"attention": "flash"}, 10),
    # bf16 decoder convs (f32 GroupNorm/conv_out): halves the decode's
    # scratch and HBM bytes (the decode runs one image a dispatch);
    # quality vs f32 must be eyeballed with real weights before this
    # becomes a default
    "c2-decodebf16": (2, {"decode_bf16": True}, 10),
    # dynamic W8A8 transformer linears (ops/quant.py): the int8-MXU lever
    # from PERF.md's roofline; throughput row only — image fidelity needs
    # real weights to judge
    "c2-int8":    (2, {"int8": True}, 10),   # control: c2-chunk10
    "c4-int8":    (4, {"int8": True}, 10),
    "c4-chunk10": (4, {}, 10),               # chunk-10 control for c4-int8
    # conv-dominated configs want the conv half of the int8 lever too
    # (chunk-10 controls: c1-chunk10 / c3-chunk10)
    "c1-int8":    (1, {"int8": True, "int8_conv": True}, 10),
    "c3-int8":    (3, {"int8": True, "int8_conv": True}, 10),
    "c3-chunk10": (3, {}, 10),
}

DEFAULT_ORDER = [
    "c1-bf16", "c1-chunk10", "c1-chunk20", "c1-flash",
    "c3-bf16", "c5-bf16", "c4-bf16", "c2-bf16",
]

#: sentinel line prefix the child prints its result row behind
_ROW_MARK = "SWEEP_ROW:"


def run_cell(name):
    """Child-process body: take the chip, run one cell, print the row."""
    import bench  # noqa: E402  (repo root on path)

    from stable_diffusion_webui_distributed_tpu.runtime import dtypes

    # share the on-disk executable cache across cells — normally done by
    # bench.main(), which this child path bypasses
    from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
        enable_compilation_cache,
    )

    enable_compilation_cache()

    cfg_n, pol_kwargs, chunk = CELLS[name]
    dtypes.TPU = _policy(**pol_kwargs)  # bench._make_engine reads dtypes.TPU
    os.environ["SDTPU_CHUNK"] = str(chunk)

    # SDTPU_BENCH_TINY=1 rehearses the whole sweep machinery (subprocess
    # choreography, row parsing, jsonl append) on CPU with tiny models —
    # the measurement plumbing is validated by tests, not first exercised
    # on the chip
    tiny = bench.tiny_env()
    t0 = time.time()
    out = bench.run_config(cfg_n, tiny=tiny)
    out["cell"] = name
    out["wall_s"] = round(time.time() - t0, 1)
    return out


def _child_main(name):
    try:
        row = run_cell(name)
    except Exception as e:  # noqa: BLE001 — report and exit nonzero
        row = {"cell": name, "error": f"{type(e).__name__}: {e}"}
        print(_ROW_MARK + json.dumps(row), flush=True)
        sys.exit(1)
    print(_ROW_MARK + json.dumps(row), flush=True)


def main():
    if "--run-cell" in sys.argv:
        _child_main(sys.argv[sys.argv.index("--run-cell") + 1])
        return
    if "--list" in sys.argv:
        print("\n".join(CELLS))
        return
    cells = [a for a in sys.argv[1:] if not a.startswith("-")]
    cells = cells or DEFAULT_ORDER
    unknown = [c for c in cells if c not in CELLS]
    if unknown:
        raise SystemExit(f"unknown cells {unknown}; --list to see all")

    deadline = time.time() + float(
        os.environ.get("SDTPU_SWEEP_DEADLINE", "3300"))
    # SDTPU_SWEEP_OUT overrides the result file; tiny-mode rehearsals
    # additionally DEFAULT away from the silicon record, so forgetting the
    # override can never mix logic-check rows into PERF_SWEEP.jsonl
    import bench  # no jax at import time; same parse as run_cell

    tiny = bench.tiny_env()
    default_name = "PERF_SWEEP_TINY.jsonl" if tiny else "PERF_SWEEP.jsonl"
    out_path = os.environ.get("SDTPU_SWEEP_OUT",
                              os.path.join(_REPO, default_name))

    for name in cells:
        if time.time() > deadline - 120:
            print(f"sweep: deadline reached, stopping before {name}",
                  file=sys.stderr, flush=True)
            break
        print(f"sweep: === {name} ===", file=sys.stderr, flush=True)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--run-cell", name],
            stdout=subprocess.PIPE, text=True)
        row = None
        for line in (proc.stdout or "").splitlines():
            if line.startswith(_ROW_MARK):
                row = json.loads(line[len(_ROW_MARK):])
        if row is None:
            row = {"cell": name,
                   "error": f"child exited rc={proc.returncode} with no row"}
        if "error" in row:
            print(f"sweep: {name} FAILED: {row['error'][:300]}",
                  file=sys.stderr, flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(f"sweep: {json.dumps(row)[:500]}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()

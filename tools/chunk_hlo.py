"""What the TPU's compiler makes of a cell's denoise executable, without the
chip.

    python3 tools/chunk_hlo.py --workload sdxl_solo [--scope REGEX]
        [--keep chunk.hlo] [--min-mb 4] [--batch-size 2]

Builds the cell's engine here on the CPU with zeros for weights, sends the
cell's own request, and where the engine would run its first ``run_chunk``
compiles it instead for one chip of a described ``v5e:2x2`` (the TPU
compiler is installed here; ``tests/test_chip_compile.py`` does the same for
the kernels), with ``jax.default_backend`` answering ``tpu`` while it is
traced so that attention takes the path it takes on the chip. Then it reads
the optimised HLO's largest computation (the scan's body) and prints, as one
JSON object:

- ``temp_mb``: the executable's temporaries;
- ``outputs``: the ops that write ``--min-mb`` or more, summed by opcode
  (fusions by kind) and element type: ``[op, dtype, count, MB]``, largest
  first. float32 ``copy`` and ``broadcast`` rows of activation size are
  layout copies (PERF.md section 6, PR 29; through HBM at SDXL's 128x128
  level, out of on-chip memory and nearly free where the shape carries
  ``S(1)``: PR 65);
- ``float32_mb``, ``pad_mb``: of those ops, what the float32 ones and the
  ``pad`` ones write (custom calls left out of the first);
- ``hbm_mb``, ``hbm_layout_mb``: what they write outside on-chip memory
  (the result's layout carries no ``S(1)``; custom calls and the weights'
  asynchronous slices left out), all of them and the ops that only move
  (``copy``, ``reshape``, ``pad``, ``broadcast``, loop fusions). Memory
  space decides what a copy costs: 874 MB a step in ``S(1)`` cost SD1.5
  6 ms a request (PR 65), 650 MB a step out of HBM gained SDXL 17 and
  820 MB another way 12 (PR 66), so neither sum prices a form: time it;
- ``two_row_tile_mb``: what they write in ``T(2,128)`` tiles, the batch's two
  rows alone on the sublanes: every pass over such a tensor fills a quarter
  of a register (PR 65);
- ``plain_convolutions``: the convolutions of flax scopes that XLA runs
  batch-major (``dim_labels=b01f...``: at two rows a third of the speed of
  the spatial-major form ``0b1f``, whose batch is rows x column blocks; same
  entry), read off the convolution instruction inside its fusion, so a
  fusion that also writes the next norm's sums counts (until PR 65 the
  result's shape was matched, which missed those). At eight rows every
  convolution is batch-major, at full speed: the list tells at fewer;
- with ``--scope``: the same sums over the ops whose flax scope matches.

An op whose result is a tuple (a fusion with several outputs) is in none of
the sums.

About three minutes for SDXL. No times: a time comes from the chip
(``benchmarks/op_table.py``); this says which ops the chip will run.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1,
         "s8": 1, "u8": 1}
NOT_RUN = {"bitcast", "get-tuple-element", "tuple", "parameter", "constant",
           "copy-start", "copy-done"}
COMPUTATION = re.compile(
    r"^(?:ENTRY )?%?[\w.\-]+ \(.*?\) -> .*? \{\n(.*?)^\}", re.S | re.M)
#: ops that compute nothing: they move or re-lay what another op wrote
LAYOUT_ONLY = {"copy", "reshape", "pad", "broadcast", "fusion:Loop"}
#: not the scan body's own traffic through HBM: kernels' results, and the
#: weights streamed in beside the step
NOT_ACTIVATIONS = {"custom-call", "slice-start", "slice-done"}
OP = re.compile(r"\s*(?:ROOT )?(\S+) = (\S+) ([\w-]+)\(")
#: a convolution instruction that takes the batch as its batch (``b01f``) and
#: not rows (``0b1f``: the spatial-major form), with its result and scope
BATCH_MAJOR = re.compile(
    r" = (\S+) convolution\(.*dim_labels=b01f_\w+->b01f.*"
    r'op_name="([^"]*conv_general_dilated)"')


def _rows(text: str):
    """(opcode, dtype, bytes written, result shape, flax scope) of every op
    of the largest computation that runs on its own."""
    body = max(COMPUTATION.findall(text), key=lambda b: b.count("\n"))
    for line in body.splitlines():
        found = OP.match(line)
        if not found or found.group(3) in NOT_RUN:
            continue
        shape, op = found.group(2), found.group(3)
        written, dtype = 0, "?"
        for i, (dt, dims) in enumerate(re.findall(r"(\w+)\[([\d,]*)\]",
                                                  shape)):
            count = 1
            for d in filter(None, dims.split(",")):
                count *= int(d)
            written += count * BYTES.get(dt, 4)
            dtype = dt if i == 0 else dtype
        if op == "fusion":
            kind = re.search(r"kind=k(\w+)", line)
            op = "fusion:" + (kind.group(1) if kind else "")
        scope = re.search(r'op_name="([^"]*)"', line)
        yield op, dtype, written, shape, scope.group(1) if scope else ""


def summarise(text: str, min_mb: float = 4.0, scope: str | None = None):
    """The sums the module docstring names, out of optimised HLO text."""
    total: dict = collections.Counter()
    count: dict = collections.Counter()
    two_row = hbm = hbm_layout = 0
    wanted = re.compile(scope) if scope else None
    for op, dtype, written, shape, where in _rows(text):
        if written < min_mb * 1e6 or (wanted and not wanted.search(where)):
            continue
        total[op, dtype] += written
        count[op, dtype] += 1
        two_row += written if "T(2,128)" in shape else 0
        if "S(1)" not in shape and op not in NOT_ACTIVATIONS:
            hbm += written
            hbm_layout += written if op in LAYOUT_ONLY else 0
    plain = sorted({(where.split("closed_call/")[-1], shape.split("{")[0])
                    for shape, where in BATCH_MAJOR.findall(text)})
    return {"outputs": [[op, dtype, count[op, dtype], round(mb / 1e6, 1)]
                        for (op, dtype), mb in total.most_common()],
            "float32_mb": round(sum(
                mb for (op, dtype), mb in total.items()
                if dtype == "f32" and op != "custom-call") / 1e6, 1),
            "pad_mb": round(sum(mb for (op, _), mb in total.items()
                                if op == "pad") / 1e6, 1),
            "hbm_mb": round(hbm / 1e6, 1),
            "hbm_layout_mb": round(hbm_layout / 1e6, 1),
            "two_row_tile_mb": round(two_row / 1e6, 1),
            "plain_convolutions": [list(row) for row in plain]}


def compile_chunk(workload: str,
                  batch_size: int | None = None) -> tuple[str, float]:
    """(optimised HLO text, temporaries in MB) of the cell's first chunk
    executable, compiled for one described v5e chip; ``batch_size`` in
    place of the request's own (``sdxl_pair``'s two clients send one image
    each and the dispatcher makes them one batch of two: four rows)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness import files, weights
    from stable_diffusion_webui_distributed_tpu.pipeline import denoise
    from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        GenerationPayload,
    )

    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    bench = files.Bench(ROOT)
    cell = bench.cell(workload)
    for key, value in cell.get("server_env", {}).items():
        os.environ[key] = str(value)
    config = bench.config(cell["config"])
    family, policy = files.resolve_family(config), files.resolve_policy(config)
    shapes = jax.eval_shape(lambda: weights.family_params(
        bench.components(config), family, policy.param_dtype, 1))
    engine = Engine(family, jax.tree.map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), shapes), policy=policy)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    compiled = []

    class Compiled(Exception):
        pass

    build = denoise.build

    def compiling_build(*args, **kwargs):
        fn = build(*args, **kwargs)

        def compile_instead(*call_args, **call_kwargs):
            described = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)
                if hasattr(x, "shape") else x, (call_args, call_kwargs))
            backend, jax.default_backend = jax.default_backend, lambda: "tpu"
            try:
                lowered = fn.lower(*described[0], **described[1])
            finally:
                jax.default_backend = backend
            compiled.append(lowered.compile())
            raise Compiled

        return compile_instead

    denoise.build = compiling_build
    try:
        payload = dict(bench.traffic(cell["traffic"])["payload"], seed=1)
        if batch_size:
            payload["batch_size"] = batch_size
        engine.txt2img(GenerationPayload(**payload))
    except Compiled:
        pass
    finally:
        denoise.build = build
    (executable,) = compiled
    return (executable.as_text(),
            executable.memory_analysis().temp_size_in_bytes / 1e6)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--scope", help="regular expression on the flax scope")
    ap.add_argument("--keep", help="write the optimised HLO text here")
    ap.add_argument("--min-mb", type=float, default=4.0)
    ap.add_argument("--batch-size", type=int,
                    help="images a request, in place of the traffic's own "
                    "(2 for the program sdxl_pair's coalesced pair runs)")
    args = ap.parse_args(argv)
    text, temp_mb = compile_chunk(args.workload, args.batch_size)
    if args.keep:
        with open(args.keep, "w") as fh:
            fh.write(text)
    out = {"workload": args.workload, "temp_mb": round(temp_mb, 1),
           **summarise(text, args.min_mb)}
    if args.scope:
        out["scope"] = summarise(text, args.min_mb, args.scope)["outputs"]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

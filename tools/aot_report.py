#!/usr/bin/env python
"""Render + verify the store of kept stage programs (serving/aot.py).

Reads the manifest in the ``sdtpu-programs`` directory of the persistent
compile cache (``JAX_COMPILATION_CACHE_DIR``, else the checkout's
``.jax_cache``: where ``runtime/mesh.py:enable_compilation_cache`` places
it; or ``--dir``) and reports every cell — stage kind, compile key,
artifact size, the runtime fingerprint it was built under (jax, the
backend's build, the package's sources) and whether that fingerprint
matches THIS process, whether it was refused — plus per-kind byte totals
and the process-local hit/miss/saved/fallback/refused tallies.

    python tools/aot_report.py                      # JSON to stdout
    python tools/aot_report.py --dir /tmp/xla/sdtpu-programs
    python tools/aot_report.py -o aot.json          # ... or to a file

The verify pass is the gate: every kept cell's artifact must exist on
disk with the manifest's content hash, and every ``*.aotx`` file must be
claimed by some cell. Exit code 0 when the store is coherent, 1 on any
divergence (missing artifact, content-hash mismatch, orphan artifact),
2 when the store root does not exist.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from stable_diffusion_webui_distributed_tpu.serving import (  # noqa: E402
    aot as aot_mod,
)


def default_dir():
    """Where this process would keep its programs: beside the compile
    cache as placed (``aot.store_dir``), else where
    ``enable_compilation_cache`` would place it."""
    from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
        DEFAULT_COMPILE_CACHE,
    )

    return aot_mod.store_dir() \
        or os.path.join(DEFAULT_COMPILE_CACHE, aot_mod.SUBDIR)


def build_report(root=None):
    store = aot_mod.AotStore(root or default_dir())
    verify = store.verify()
    cells = verify["cells"]
    by_kind = {}
    total_bytes = 0
    for c in cells:
        k = str(c.get("kind"))
        row = by_kind.setdefault(k, {"cells": 0, "bytes": 0})
        row["cells"] += 1
        row["bytes"] += int(c.get("bytes") or 0)
        total_bytes += int(c.get("bytes") or 0)
        c["fingerprint_match"] = (c.get("fingerprint_id")
                                  == verify["fingerprint_id"])
    report = {
        "root": verify["root"],
        "runtime_fingerprint": verify["fingerprint"],
        "runtime_fingerprint_id": verify["fingerprint_id"],
        "cells": cells,
        "cell_count": len(cells),
        "total_bytes": total_bytes,
        "by_kind": dict(sorted(by_kind.items())),
        "divergent": verify["divergent"],
        "orphans": verify["orphans"],
        "stats": store.stats_snapshot(),
        "ok": verify["ok"],
    }
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=None,
                    help="store root (default: sdtpu-programs inside the "
                         "compile cache's directory)")
    ap.add_argument("-o", "--output", default=None,
                    help="write JSON here instead of stdout")
    args = ap.parse_args(argv)

    root = args.dir or default_dir()
    if not os.path.isdir(root):
        print(f"aot_report: store root {root} does not exist",
              file=sys.stderr)
        return 2
    report = build_report(root)
    text = json.dumps(report, indent=2, sort_keys=True) + "\n"
    if args.output:
        with open(args.output, "w", encoding="utf-8") as fh:
            fh.write(text)
        print(f"wrote {args.output} ({report['cell_count']} cell(s), "
              f"ok={report['ok']})", file=sys.stderr)
    else:
        sys.stdout.write(text)
    if not report["ok"]:
        print("aot_report: DIVERGENT — "
              + ", ".join(report["divergent"]
                          + [f"orphan:{o}" for o in report["orphans"]]),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

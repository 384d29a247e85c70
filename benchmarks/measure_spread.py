"""Runs one cell as the builder's contract asks before a bound is set: two
sets of runs with the same seeds in both, every run a new process, then the
spread of each end-to-end metric: (Q3 - Q1) / median per set, with
``statistics.quantiles(values, n=4)``, and the wider of the two.

    chiprun -- python3 benchmarks/measure_spread.py --workload sdxl_solo

This parent never imports JAX (a chip belongs to one process). Every result
line is kept in ``chiprun_out/measure/<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = [2147483659, 2500000001, 3000000019, 3500000017, 4000000007,
         4294967291]


def one_run(cell: str, seed: int, seconds: int, trace: int) -> dict:
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if proc.returncode == 0 and lines else {}
    result.update(rc=proc.returncode, seed=seed, trace=trace,
                  process_s=time.time() - t0,
                  setup_split=next((json.loads(line.split(": ", 1)[1])
                                    for line in lines
                                    if line.startswith("setup split: ")),
                                   None))
    if proc.returncode:
        result["stderr_tail"] = proc.stderr[-2000:]
    return result


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6, help="runs per set")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", type=int, default=2,
                    help="--trace 1 runs after the sets")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        seconds = json.load(fh)["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "measure")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, args.workload + ".jsonl"), "a")
    sets: list[list[dict]] = []
    plan = [(s, seed, 0) for s in range(args.sets)
            for seed in SEEDS[:args.runs]]
    plan += [(args.sets, SEEDS[i % len(SEEDS)], 1)
             for i in range(args.traced)]
    for number, (which, seed, trace) in enumerate(plan):
        result = one_run(args.workload, seed, seconds, trace)
        result.update(set=which, run=number)
        log.write(json.dumps(result) + "\n")
        log.flush()
        brief = {k: round(v["value"], 5)
                 for k, v in result.get("metrics", {}).items()}
        print(f"run {number} set {which} seed {seed} trace {trace} rc "
              f"{result['rc']} correct {result.get('correct')} process "
              f"{result['process_s']:.1f} s {json.dumps(brief)}", flush=True)
        if trace == 0 and result["rc"] == 0:
            while len(sets) <= which:
                sets.append([])
            sets[which].append(result)
    names = sorted({k for runs in sets for r in runs for k in r["metrics"]})
    for name in names:
        row = []
        for runs in sets:
            values = [r["metrics"][name]["value"] for r in runs]
            # the first run of a call may compile: its set-up is kept apart
            if name == "setup_s" and runs is sets[0]:
                values = values[1:]
            if len(values) >= 2:
                row.append((statistics.median(values), spread(values)))
        print(f"{name}: " + "; ".join(
            f"set {i} median {m:.6g} spread {100 * s:.3f} %"
            for i, (m, s) in enumerate(row))
            + (f"; widest {100 * max(s for _, s in row):.3f} %"
               if row else ""), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

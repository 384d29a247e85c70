#!/usr/bin/env python
"""Diff two bench measurements against per-metric regression thresholds.

The perf ledger (``bench.py --ledger``) appends structural rows to
``BENCH_LEDGER.jsonl``; this tool compares any two of them — or any two
``BENCH_*.json`` artifacts — metric by metric and exits nonzero when a
watched metric regressed past its threshold. That makes "did this PR make
serving structurally worse?" a one-command tier-1 check instead of a
manual read of two JSON files.

    python tools/bench_compare.py BENCH_LEDGER.jsonl          # oldest vs newest
    python tools/bench_compare.py BENCH_LEDGER.jsonl --kind serving
    python tools/bench_compare.py old.json new.json           # two artifacts
    python tools/bench_compare.py BENCH_serving.json BENCH_serving.json
    python tools/bench_compare.py ledger.jsonl --base 0 --head -1 --json

Thresholds are structural, not wall-clock: compile counts, coalesce
factor, padding ratio, FLOPs/image and SLO attainment are
platform-independent, so a CPU tiny run can gate a regression that would
cost real money on a TPU. A metric missing from either side is reported
and skipped, never failed — artifacts of different kinds share only some
metrics.

Exit codes: 0 no watched metric regressed; 1 at least one regression;
2 artifact missing/unparseable or no comparable rows.
"""

from __future__ import annotations

import argparse
import json
import sys

import benchjson

#: metric -> (direction, mode, threshold). direction "up" = higher is a
#: regression, "down" = lower is a regression. mode "abs" compares the
#: raw delta, "rel" the delta as a fraction of the base value.
THRESHOLDS = {
    "chunk_compiles": ("up", "abs", 0.0),
    "coalesce_factor": ("down", "rel", 0.10),
    "avg_padding_ratio": ("up", "rel", 0.05),
    # ragged rows (bench.py run_ragged): conditioning token padding is
    # structural for the fixed prompt mix, and the census alarm firing at
    # all means the executable budget contract broke
    "token_padding_ratio": ("up", "rel", 0.05),
    "census_alarm": ("up", "abs", 0.0),
    "bucket_hit_rate": ("down", "abs", 0.10),
    "unet_flops_per_image": ("up", "rel", 0.02),
    "slo_attainment": ("down", "abs", 0.10),
    "quota_throttle_rate": ("up", "abs", 0.10),
    # watchdog rows (bench.py run_watchdog): the structural scenario is
    # deterministic, so any movement at all is a behavior change
    "watchdog_stalls": ("up", "abs", 0.0),
    "requeue_recovery_rate": ("down", "abs", 0.0),
    # lint rows (bench.py _run_lint_metrics): the repo gate is clean, so
    # the finding count moving up at all means someone landed a finding
    # without fixing or allowlisting it (wall time is trajectory-only —
    # machine-dependent, never gated)
    "lint_finding_count": ("up", "abs", 0.0),
    # concurrency tier (same lint row): a lock-order cycle reachable
    # from a thread entry point is a deadlock waiting for a schedule —
    # zero tolerance; fewer clean explorer seeds means an interleaving
    # started deadlocking or breaking an invariant
    "lock_cycles": ("up", "abs", 0.0),
    "schedule_explorer_seeds": ("down", "abs", 0.0),
    # caching-tier rows (bench.py run_cache): the redundant mix is fixed,
    # so hit rates and the prefix FLOP cut are structural — meaningful
    # movement means a key family broke (over-keying kills dedupe) or the
    # resume point moved
    "embed_cache_hit_rate": ("down", "abs", 0.05),
    "result_dedupe_hit_rate": ("down", "abs", 0.05),
    # lora rows (bench.py run_lora): recompile-free serving is the whole
    # contract — ANY chunk compile or host merge during the traced churn
    # phase means adapter identity leaked back into a compile key or the
    # merge path re-engaged; the embed cache surviving switches is what
    # distinguishes content-addressed keys from epoch bumps
    "lora_traced_chunk_compiles": ("up", "abs", 0.0),
    "lora_traced_merges": ("up", "abs", 0.0),
    "lora_embed_hit_rate": ("down", "abs", 0.05),
    "prefix_flops_reduction_pct": ("down", "abs", 5.0),
    # scenario rows (bench.py run_scenarios): requeue_recovery_rate and
    # slo_attainment above gate these too; per-scenario worst-class p95
    # is timing-based so it gets a wide relative band, and a double-merge
    # (the same image range landing twice after a chaos requeue) is a
    # correctness bug at any count
    "scenario_p95_s": ("up", "rel", 0.50),
    "double_merged_images": ("up", "abs", 0.0),
    # alert rows (bench.py run_alerts): the labeled phase protocol is
    # deterministic, so a single false-positive firing on steady traffic
    # or any recall lost on the injected fault windows is a detector
    # regression at any size
    "alert_false_positives": ("up", "abs", 0.0),
    "alert_recall": ("down", "abs", 0.0),
    # federation rows (bench.py run_federation): the kill-one-worker
    # protocol is deterministic — a dropped webhook or a steady-state
    # stale verdict is a paging/federation regression at any size
    "notify_delivery_rate": ("down", "abs", 0.0),
    "federation_staleness_fp": ("up", "abs", 0.0),
    # push control plane rows (bench.py run_obsplane): cursor-resume
    # delta streaming is lossless by contract — ANY lost entry is a
    # protocol break; a misrouted notification (page landing on the warn
    # channel or vice versa) is a paging bug at any count; and push
    # staleness regressing past the poll baseline removes the plane's
    # whole reason to exist (the in-run check also hard-fails on it)
    "push_event_loss": ("up", "abs", 0.0),
    "notify_misrouted": ("up", "abs", 0.0),
    "push_staleness_p95_s": ("up", "rel", 0.25),
}

#: bench.py artifacts keep the headline number under "value"; map it back
#: to the metric name THRESHOLDS knows, per artifact kind.
_VALUE_ALIASES = {
    "serving_coalesce_factor": "coalesce_factor",
    "tiny_serving_coalesce_factor": "coalesce_factor",
    "cache_embed_hit_rate": "embed_cache_hit_rate",
    "tiny_cache_embed_hit_rate": "embed_cache_hit_rate",
}


def _unwrap(doc):
    """Some BENCH_*.json artifacts are run wrappers ({"n", "cmd", "rc",
    "parsed": {...}}) around the measurement document."""
    if isinstance(doc.get("parsed"), dict):
        return doc["parsed"]
    return doc


def _metrics_of(doc):
    """Flatten one measurement (ledger row or BENCH_*.json) into a
    {metric: number} dict restricted to the watched metrics."""
    doc = _unwrap(doc)
    src = dict(doc.get("metrics") or {}) if "metrics" in doc else dict(doc)
    alias = _VALUE_ALIASES.get(str(src.get("metric", "")))
    if alias and alias not in src:
        src[alias] = src.get("value")
    out = {}
    for name in THRESHOLDS:
        v = src.get(name)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[name] = float(v)
    return out


def _label(doc, fallback):
    doc = _unwrap(doc)
    if "kind" in doc:
        return f"ledger[{doc.get('kind')}]"
    return str(doc.get("metric") or fallback)


def compare(base, head):
    """Compare two measurement dicts; returns the verdict document."""
    base_m, head_m = _metrics_of(base), _metrics_of(head)
    rows, regressions, skipped = [], [], []
    for name, (direction, mode, threshold) in sorted(THRESHOLDS.items()):
        if name not in base_m or name not in head_m:
            skipped.append(name)
            continue
        b, h = base_m[name], head_m[name]
        delta = h - b
        if mode == "rel":
            scale = abs(b) if b else 0.0
            measured = delta / scale if scale else (0.0 if not delta
                                                   else float("inf"))
        else:
            measured = delta
        if direction == "down":
            measured = -measured
        regressed = measured > threshold
        rows.append({"metric": name, "base": b, "head": h,
                     "delta": round(delta, 6), "direction": direction,
                     "mode": mode, "threshold": threshold,
                     "regressed": regressed})
        if regressed:
            regressions.append(name)
    return {
        "base": _label(base, "base"),
        "head": _label(head, "head"),
        "rows": rows,
        "compared": len(rows),
        "skipped": skipped,
        "regressions": regressions,
        "ok": not regressions,
    }


def render(verdict):
    lines = [f"bench comparison — {verdict['base']} -> {verdict['head']}",
             "",
             f"{'metric':<22} {'base':>12} {'head':>12} {'delta':>12} "
             f"{'verdict':>10}"]
    for r in verdict["rows"]:
        word = "REGRESSED" if r["regressed"] else "ok"
        lines.append(
            f"{r['metric']:<22} {benchjson.fmt(r['base']):>12} "
            f"{benchjson.fmt(r['head']):>12} "
            f"{benchjson.fmt(r['delta']):>12} {word:>10}")
    if verdict["skipped"]:
        lines.append("")
        lines.append("not comparable (missing on one side): "
                     + ", ".join(verdict["skipped"]))
    lines.append("")
    lines.append("verdict: " + ("OK" if verdict["ok"] else
                                "REGRESSED — " +
                                ", ".join(verdict["regressions"])))
    return "\n".join(lines)


def _ledger_rows(path, kind):
    rows = benchjson.load_ledger(path, "bench_compare")
    if kind:
        rows = [r for r in rows if r.get("kind") == kind]
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("base", help="BENCH_LEDGER.jsonl, or the base "
                                 "BENCH_*.json artifact")
    ap.add_argument("head", nargs="?", default=None,
                    help="head BENCH_*.json (omit to compare two rows of "
                         "a ledger file)")
    ap.add_argument("--kind", default=None,
                    help="ledger mode: restrict to rows of this kind "
                         "(serving, fleet, watchdog)")
    ap.add_argument("--base-row", type=int, default=0,
                    help="ledger mode: base row index (default 0, oldest)")
    ap.add_argument("--head-row", type=int, default=-1,
                    help="ledger mode: head row index (default -1, newest)")
    ap.add_argument("--json", action="store_true",
                    help="emit the verdict as JSON")
    args = ap.parse_args(argv)

    try:
        if args.head is None:
            rows = _ledger_rows(args.base, args.kind)
            if len(rows) < 2:
                print(f"bench_compare: {args.base} holds "
                      f"{len(rows)} comparable row(s); need 2",
                      file=sys.stderr)
                return 2
            try:
                base, head = rows[args.base_row], rows[args.head_row]
            except IndexError:
                print(f"bench_compare: row index out of range "
                      f"({len(rows)} rows)", file=sys.stderr)
                return 2
        else:
            base = benchjson.load_bench(args.base, "bench_compare")
            head = benchjson.load_bench(args.head, "bench_compare")
    except benchjson.BenchJsonError as e:
        print(e, file=sys.stderr)
        return 2

    verdict = compare(base, head)
    if not verdict["compared"]:
        print("bench_compare: no metric present on both sides — nothing "
              "to compare", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(verdict, indent=2))
    else:
        print(render(verdict))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

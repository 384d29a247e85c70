#!/usr/bin/env python
"""Human-readable view of an sdtpu span trace.

Takes the Chrome trace-event JSON served at ``/internal/trace.json`` (or a
flight-recorder dump from ``/internal/flightrec`` / ``bench.py``'s on-error
artifact) and prints, per request, the span tree with millisecond durations,
plus a top-k table of the slowest span names across the whole file.

    curl -s localhost:7860/internal/trace.json > trace.json
    python tools/trace_report.py trace.json
    python tools/trace_report.py trace.json --request 5f3a... --top 5

For the full flame-graph view load the same file in ui.perfetto.dev; this
tool is the no-browser triage path.

Exit codes: 0 printed a report, 1 no spans in the file, 2 unreadable input.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple


def load_events(data: Any) -> List[Dict[str, Any]]:
    """Extract trace events from any of the three artifact shapes:
    ``{"traceEvents": [...]}``, a flight-recorder dump ``{"entries": [{...,
    "spans": [...]}]}``, or a bare event list. A span that ran on the
    device (``device.run``) is an async pair: its "b" half carries ``dur``
    and stands for it, its "e" half is dropped."""
    if isinstance(data, dict) and "traceEvents" in data:
        data = data["traceEvents"]
    elif isinstance(data, dict):
        data = [e for entry in data.get("entries", [])
                for e in entry.get("spans", [])]
    if not isinstance(data, list):
        return []
    return [e for e in data if isinstance(e, dict) and e.get("ph") != "e"]


def group_requests(events: List[Dict[str, Any]]
                   ) -> "OrderedDict[str, List[Dict[str, Any]]]":
    """Events keyed by request id, in first-seen order."""
    out: "OrderedDict[str, List[Dict[str, Any]]]" = OrderedDict()
    for e in events:
        rid = str(e.get("args", {}).get("request_id", "?"))
        out.setdefault(rid, []).append(e)
    return out


def _ids(e: Dict[str, Any]) -> Tuple[Optional[int], Optional[int]]:
    args = e.get("args", {})
    return args.get("span_id"), args.get("parent_id")


def render_tree(events: List[Dict[str, Any]]) -> List[str]:
    """Indented span tree for one request's events. Roots are spans whose
    parent is absent from the set (the request root has no parent at all);
    children sort by start time."""
    by_id: Dict[int, Dict[str, Any]] = {}
    children: Dict[Optional[int], List[Dict[str, Any]]] = {}
    for e in events:
        sid, _pid = _ids(e)
        if sid is not None:
            by_id[sid] = e
    for e in events:
        _sid, pid = _ids(e)
        key = pid if pid in by_id else None
        children.setdefault(key, []).append(e)
    for kids in children.values():
        kids.sort(key=lambda e: e.get("ts", 0))

    lines: List[str] = []

    def walk(e: Dict[str, Any], depth: int) -> None:
        dur_ms = float(e.get("dur", 0)) / 1000.0
        extras = {k: v for k, v in e.get("args", {}).items()
                  if k not in ("request_id", "span_id", "parent_id")}
        extra = ("  " + " ".join(f"{k}={v}" for k, v in sorted(extras.items()))
                 if extras else "")
        lines.append(f"{'  ' * depth}{e.get('name', '?'):<24s} "
                     f"{dur_ms:10.3f} ms{extra}")
        sid, _pid = _ids(e)
        for kid in children.get(sid, []):
            if kid is not e:
                walk(kid, depth + 1)

    for root in children.get(None, []):
        walk(root, 0)
    return lines


def top_stages(events: List[Dict[str, Any]], k: int = 10
               ) -> List[Dict[str, Any]]:
    """Span names ranked by total duration across the whole file."""
    agg: Dict[str, Dict[str, float]] = {}
    for e in events:
        name = str(e.get("name", "?"))
        dur_ms = float(e.get("dur", 0)) / 1000.0
        a = agg.setdefault(name, {"count": 0, "total_ms": 0.0, "max_ms": 0.0})
        a["count"] += 1
        a["total_ms"] += dur_ms
        a["max_ms"] = max(a["max_ms"], dur_ms)
    rows = [{"name": n, **v} for n, v in agg.items()]
    rows.sort(key=lambda r: -r["total_ms"])
    return rows[:k]


def build_report(data: Any, request_id: Optional[str] = None,
                 top: int = 10) -> Dict[str, Any]:
    events = load_events(data)
    grouped = group_requests(events)
    if request_id is not None:
        grouped = OrderedDict((rid, evs) for rid, evs in grouped.items()
                              if rid.startswith(request_id))
    return {
        "requests": OrderedDict(
            (rid, render_tree(evs)) for rid, evs in grouped.items()),
        "top_stages": top_stages(events, top),
        "event_count": len(events),
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="trace.json / flightrec dump ('-' = stdin)")
    ap.add_argument("--request", default=None,
                    help="only requests whose id starts with this prefix")
    ap.add_argument("--top", type=int, default=10,
                    help="rows in the slowest-span table (default 10)")
    args = ap.parse_args(argv)

    try:
        if args.trace == "-":
            data = json.load(sys.stdin)
        else:
            with open(args.trace, "r", encoding="utf-8") as fh:
                data = json.load(fh)
    except (OSError, json.JSONDecodeError) as e:
        print(f"trace_report: cannot read {args.trace}: {e}",
              file=sys.stderr)
        return 2

    report = build_report(data, request_id=args.request, top=args.top)
    if not report["event_count"]:
        print("trace_report: no span events in input", file=sys.stderr)
        return 1
    for rid, lines in report["requests"].items():
        print(f"request {rid}")
        for line in lines:
            print(f"  {line}")
        print()
    print(f"top {len(report['top_stages'])} spans by total time:")
    print(f"  {'name':<24s} {'count':>6s} {'total ms':>12s} {'max ms':>12s}")
    for row in report["top_stages"]:
        print(f"  {row['name']:<24s} {row['count']:>6d} "
              f"{row['total_ms']:>12.3f} {row['max_ms']:>12.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

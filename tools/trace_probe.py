"""One run of a benchmark cell, read through the program's own tracing.

    python3 tools/trace_probe.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1> [--prepared] [--tag <name>]

Runs ``benchmarks/run.py`` unchanged in this process (one process holds the
chip) and keeps what that run reads and throws away: ``/internal/trace.json``
(every request's span tree), the two ``/internal/status`` reads (the
``serving.xla`` block after warm-up and after the window) and, with
``--trace 1``, the slice's ``.xplane.pb``. From them it writes
``chiprun_out/probe/<tag>.json``:

- ``span_median_ms``: per span name the median over the window's requests
  of that span's summed milliseconds in a request, and ``tree``: the last
  window request as a tree with self times (duration minus children);
- ``xla_setup``: ``serving.xla`` as read after warm-up (totals and the ten
  functions with most seconds), ``xla_window``: what the window added;
- with ``--trace 1``: ``annotated_missing`` (spans of the traced request
  that are not on a host plane as ``sdtpu:<name>`` with its id), ``gaps``
  (the device's longest idle gaps in the slice, its head and its tail, each
  with the innermost program span over it and the spans inside it) and
  ``op_meta`` (what the trace's event metadata says of a few ``XLA Ops``
  events: ``tf_op`` carries the flax module path, and ``flops`` and
  ``bytes_accessed`` ride beside it; ``ProfileData`` does not show event
  metadata, so this reads the raw proto where ``tensorflow.tsl`` has it).

``--prepared`` runs a cell of ``benchmarks/prepared.json`` (built, not
admitted: ``refiner_img2img``) from a scratch copy of the manifest under
``.verify-tmp/``; ``BENCHMARK.json`` is not touched. This is how PERF.md
section 6's tables of PR 24 were made; a later ``benchmark`` PR should fold
the gap owners into ``benchmarks/harness/trace_reduce.py``.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import shutil
import statistics
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OWN = ("request_id", "span_id", "parent_id")


class Tee(io.TextIOBase):
    def __init__(self, stream):
        self.stream, self.kept = stream, []

    def write(self, text):
        self.kept.append(text)
        return self.stream.write(text)

    def flush(self):
        self.stream.flush()


def prepared_root() -> str:
    """BENCHMARK.json plus prepared.json's entries, beside a copy of
    benchmarks/, under .verify-tmp/ (which .gitignore lists)."""
    root = os.path.join(REPO, ".verify-tmp", "probe-root")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    with open(os.path.join(REPO, "benchmarks", "prepared.json")) as fh:
        prepared = json.load(fh)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        manifest[group] += prepared[group]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(manifest, fh)
    return root


def by_request(trace_json: dict) -> dict:
    out: dict = {}
    for event in trace_json.get("traceEvents", []):
        out.setdefault(event["args"]["request_id"], []).append(event)
    return out


def span_medians(requests: dict) -> dict:
    """{span name: median over requests of its summed ms in a request}"""
    sums: dict = {}
    for events in requests.values():
        per: dict = {}
        for e in events:
            per[e["name"]] = per.get(e["name"], 0.0) + e["dur"] / 1e3
        for name, ms in per.items():
            sums.setdefault(name, []).append(ms)
    return {name: statistics.median(v) for name, v in sorted(sums.items())}


def tree(events: list) -> list:
    """[[depth, name, ms, self ms, attrs]] in time order, depth first."""
    kids: dict = {}
    for e in sorted(events, key=lambda e: e["ts"]):
        kids.setdefault(e["args"].get("parent_id"), []).append(e)
    rows = []

    def walk(e, depth):
        below = kids.get(e["args"]["span_id"], [])
        rows.append([depth, e["name"], e["dur"] / 1e3,
                     (e["dur"] - sum(k["dur"] for k in below)) / 1e3,
                     {k: v for k, v in e["args"].items() if k not in OWN}])
        for k in below:
            walk(k, depth + 1)

    for top in kids.get(None, []):
        walk(top, 0)
    return rows


def xla_delta(before: dict, after: dict) -> dict:
    keys = ("executables", "traces", "trace_s", "lower_s", "backend_s",
            "cache_hits", "cache_misses", "cache_retrieval_s")
    rows = {r["fun_name"]: r for r in before.get("top", [])}
    return {
        "totals": {k: after[k] - before[k] for k in keys},
        "made": [r["fun_name"] for r in after.get("top", [])
                 if r["executables"]
                 > rows.get(r["fun_name"], {}).get("executables", 0)]}


def read_xplane(path: str, traced_events: list) -> dict:
    import jax.profiler

    from benchmarks.harness import trace_reduce

    profile = jax.profiler.ProfileData.from_file(path)
    spans = []          # (start_ns, end_ns, name, request id, span id)
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("sdtpu:"):
                    stats = dict(e.stats)
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name[6:], stats.get("request_id"),
                                  stats.get("span_id")))
    have = {(s[3], s[4]) for s in spans}
    missing = sorted({e["name"] for e in traced_events
                      if (e["args"]["request_id"], e["args"]["span_id"])
                      not in have})

    def owner(start, end):
        """{ms, owner: the shortest program span that holds the whole
        interval, inside: the spans that only overlap it, most first}"""
        over = [s for s in spans if s[0] <= start and s[1] >= end]
        best = min(over, key=lambda s: s[1] - s[0]) if over else None
        part = sorted(((min(s[1], end) - max(s[0], start), s[2])
                       for s in spans if s not in over
                       and min(s[1], end) > max(s[0], start)), reverse=True)
        return {"ms": (end - start) / 1e6,
                "owner": f"{best[2]} ({best[3]})" if best
                else "no program span",
                "inside": [[name, ns / 1e6] for ns, name in part[:4]]}

    out = {"annotated_missing": missing, "annotations": len(spans),
           "gaps": [], "op_meta": op_meta(path)}
    devices = trace_reduce._device_ops(profile)
    if devices:
        ops = devices[min(devices)]
        _, merged = trace_reduce.union_ns((s, e) for s, e, _ in ops)
        idle = sorted(((b[0] - a[1], a[1], b[0])
                       for a, b in zip(merged, merged[1:])), reverse=True)
        out["gaps"] = [owner(s, e) for _, s, e in idle[:5]]
        if spans:       # the exchange's two ends bound the slice
            out["head"] = owner(min(s[0] for s in spans), merged[0][0])
            out["tail"] = owner(merged[-1][1], max(s[1] for s in spans))
    return out


def op_meta(path: str, want: int = 6) -> list:
    """Event metadata of the first device's longest ``XLA Ops`` (one entry
    per HLO op, all its stats as text), or a note why not."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError as err:
        return [{"unread": str(err)}]
    from benchmarks.harness import trace_reduce

    space = xplane_pb2.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        time_ps: dict = {}
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                for e in line.events:
                    time_ps[e.metadata_id] = (time_ps.get(e.metadata_id, 0)
                                              + e.duration_ps)
        out = []
        for mid in sorted(time_ps, key=time_ps.get, reverse=True):
            meta = plane.event_metadata[mid]
            if trace_reduce.CONTAINERS.match(
                    trace_reduce.short_name(meta.name)):
                continue
            stats = {}
            for st in meta.stats:
                value = (st.str_value or names.get(st.ref_value)
                         or st.int64_value or st.uint64_value
                         or st.double_value)
                stats[names[st.metadata_id]] = str(value)[:240]
            out.append({"op": trace_reduce.short_name(meta.name),
                        "device_ms": time_ps[mid] / 1e9, "meta": stats})
            if len(out) == want:
                return out
        return out
    return [{"unread": "no device plane"}]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--prepared", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)
    root = prepared_root() if args.prepared else REPO
    sys.path[:0] = [root, REPO]

    import benchmarks.run as run
    from benchmarks.harness import loadgen, trace_reduce

    fetched: dict = {}
    get_json = loadgen.get_json

    def keeping_get_json(base, route):
        out = get_json(base, route)
        fetched.setdefault(route, []).append(out)
        return out

    kept = tempfile.mkdtemp(prefix="probe-xplane-")
    reduce = trace_reduce.reduce

    def keeping_reduce(path, *a, **kw):
        shutil.copy(path, os.path.join(kept, "slice.xplane.pb"))
        return reduce(path, *a, **kw)

    loadgen.get_json = keeping_get_json
    trace_reduce.reduce = keeping_reduce
    tee = Tee(sys.stdout)
    sys.stdout = tee
    try:
        rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds),
                       "--trace", str(args.trace)], root=root)
    finally:
        sys.stdout = tee.stream
    lines = "".join(tee.kept).strip().splitlines()
    out: dict = {"rc": rc, "argv": vars(args)}
    try:
        out["result"] = json.loads(lines[-1])
    except (ValueError, IndexError):
        out["result"] = None
    out["setup_lines"] = [ln for ln in lines
                          if ln.startswith(("setup", "warm-up", "XLA",
                                            "traced slice", "window"))]
    statuses = fetched.get("/internal/status", [])
    if len(statuses) >= 2 and (statuses[0].get("serving") or {}).get("xla"):
        before, after = (s["serving"]["xla"] for s in statuses[:2])
        out["xla_setup"] = before
        out["xla_window"] = xla_delta(before, after)
    requests = by_request((fetched.get("/internal/trace.json") or [{}])[-1])
    window = {rid: ev for rid, ev in requests.items()
              if rid.startswith("w-")}
    n_traced = 0
    xplane = os.path.join(kept, "slice.xplane.pb")
    if os.path.exists(xplane):
        traced = [ln for ln in lines if ln.startswith("traced slice:")]
        n_traced = int(traced[0].split()[2]) if traced else 0
        first = [e for i in range(n_traced)
                 for e in window.get(f"w-{i}", [])]
        out.update(read_xplane(xplane, first))
    shutil.rmtree(kept, ignore_errors=True)
    untraced = {rid: ev for rid, ev in window.items()
                if int(rid[2:]) >= n_traced} or window
    out["requests"] = len(untraced)
    out["span_median_ms"] = span_medians(untraced)
    if untraced:
        last = max(untraced, key=lambda rid: int(rid[2:]))
        out["tree"] = tree(untraced[last])
    tag = args.tag or f"{args.workload}-{args.seed}-t{args.trace}"
    path = os.path.join(REPO, "chiprun_out", "probe", tag + ".json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
    print(f"probe: wrote {path}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())

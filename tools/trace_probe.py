"""One run of a benchmark cell, read through the program's own tracing.

    python3 tools/trace_probe.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1> [--prepared] [--keep-slow] [--watch] [--tag <name>]

Runs ``benchmarks/run.py`` unchanged in this process (one process holds the
chip) and keeps what that run reads and throws away: ``/internal/trace.json``
(every request's span tree), the two ``/internal/status`` reads (the
``serving.xla`` block after warm-up and after the window) and, with
``--trace 1``, the slice's ``.xplane.pb``. From them it writes
``chiprun_out/probe/<tag>.json``:

- ``span_median_ms``: per span name the median over the window's requests
  of that span's summed milliseconds in a request, and ``tree``: the last
  window request as a tree with self times (duration minus children);
- ``plan_attrs``: over the window's requests, how many ``request.plan`` /
  ``denoise.plan`` spans read ``ladder`` hit or built and how many
  ``denoise.inputs`` read ``added_cond`` hit, built or none, and
  ``plan_counts``: ``serving.plan`` of the last ``/internal/status`` (PR
  38: after the warm-up request everything should read hit; PR 55:
  ``ahead``, how many expanded ranges had their first group ``drawn``
  under the expander's decode, ``taken`` and ``dropped``, process-wide
  like the tables and printed with them on the ``plan:`` line: every
  expanded request, the warm-up's among them, should add one to the
  first two);
- ``attention_sites`` (printed as the ``attention:`` line):
  ``serving.attention`` of the last ``/internal/status`` without its
  ``by_shape``: the sites by path, counted at trace time, and (PR 57)
  ``tiled_layout``, the tiled kernel's calls by the layout they were
  handed (``lanes`` or ``heads_major``; a parent before PR 57 has none);
- ``dispatch_attrs``: over the window's requests, how many
  ``coalesce.window`` spans read ``ended_by`` full or timer and how many
  ``dispatch.device`` spans carried 1, 2, ... ``requests``, and
  ``coalesce_windows``: ``sdtpu_coalesce_window_total`` by ``ended_by``
  for the whole process (PR 50: a group that is full goes at once);
- ``xla_setup``: ``serving.xla`` as read after warm-up (totals and the ten
  functions with most seconds), ``xla_window``: what the window added,
  and ``programs_setup``: ``serving.programs`` then (PR 53: the stages
  loaded from the store beside the compile cache or traced, with the
  seconds of loading, by stage kind);
- ``host`` (PR 54; printed as the ``host:`` line): what no request's tree
  covers, over the window: ``serving.host`` of ``/internal/status`` after
  the window less before it (``stalls``, ``stall_ms``: the host clock woke
  late, obs/watchdog.py; ``gc_pause_ms`` and ``gc_collections``;
  ``exchanges``, ``betweens``, ``between_ms``), ``gc_pause_ms_by_generation``
  (``sdtpu_gc_pause_seconds_total`` over the same two reads), the largest
  single readings where the window raised them (``*_ms_max``; None: the
  set-up's stands), ``stalls_in_window`` (the clock's ring, the
  ``host.stall`` events of ``/internal/trace.json``, from the window's
  first request on), ``http_accept_ms`` and ``http_between_ms`` (the
  medians of ``http.accept`` and of ``http.between``, the gap a request
  that found the server empty has beside its root) and (PR 71)
  ``stall_ms_no_request``: the ms of the window's stalls whose ``alive``
  reads 0 (no request was alive when the clock woke: between two, or the
  one that was had ended by then), which ``host_stall_ms`` cannot see;
- ``device`` (PR 71; printed as the ``device:`` line): the device's side
  of the window's untraced requests by the program's own ``device.run``
  spans (obs/spans.py, profiler off): per request the median ms the device
  ran (``busy_ms``: the union inside ``dispatch.device``; ``by_kind_ms``
  the sums by executable) and had nothing of the request to run
  (``idle_ms``), the ``dry`` enqueues and ``late`` fences a request,
  ``exact_share`` of the stamps, ``serving.device`` over the window, and
  ``threads``, the census taken while the server was up (a
  ``device-watcher`` is there only after a capture, ``--watch`` or the
  slow rule). With ``--trace 1`` also ``traced``: for each traced request
  the program's busy and idle ms beside the xplane's busy time inside
  the same ``dispatch.device`` (``sdtpu:dispatch.device`` on the
  profiler's clock) and the difference as a share of the request.
  ``--watch`` arms the device watcher for the whole run (what
  ``/internal/trace.json?device=1`` does), so every stamp is exact: the
  cost of that is what a pair of runs with and without it shows;
- with ``--trace 1``: ``annotated_missing`` (spans of the traced request
  that are not on a host plane as ``sdtpu:<name>`` with its id), ``gaps``
  (the device's longest idle gaps in the slice, its head and its tail, each
  with the innermost program span over it and the spans inside it),
  ``idle_by_name_ms`` and ``idle_by_class_ms`` (ALL the slice's idle time
  by the span that owns it, a traced request: the table
  ``benchmarks/readers/idle_by_owner.py`` makes, printed here too) and
  ``op_meta`` (the reducer's rows of the ops with most time: ``scope``
  carries the flax module path, ``flops`` and ``bytes`` ride beside it).
  The proto is read through ``benchmarks/harness/xplane_proto.py``;
- with ``--keep-slow``: ``slow``, every request the flight recorder kept as
  ``slow`` (obs/spans.py: 1.5 x the running median of its class, or
  ``SDTPU_OBS_SLOW_S``) as a tree whose rows end with the span's excess
  over the median tree (its ms less the median over the window's requests
  of the name's summed ms, shared among the name's spans), printed too,
  with its ``live`` sample where the host clock caught it while it was
  still slow (its age and open spans, then every thread's stack, the one
  that owns its innermost open span first, and the stalls before it);
  and ``timeline``, every exchange's ms and the ms until the next one
  starts, then the clock's stalls that began in that stretch (``[ms into
  it, ms long]``), the rows that stand out printed: a window that lost time
  lost it inside a request or between two.

``--prepared`` runs a cell of ``benchmarks/prepared.json`` (built, not
admitted: ``refiner_img2img``) from a scratch copy of the manifest under
``.verify-tmp/``; ``BENCHMARK.json`` is not touched. This is how PERF.md
section 6's tables of PR 24 were made; a later ``benchmark`` PR should fold
the gap owners into ``benchmarks/harness/trace_reduce.py``.
"""

from __future__ import annotations

import argparse
import collections
import io
import json
import os
import re
import shutil
import statistics
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OWN = ("request_id", "span_id", "parent_id")
#: found after the fact (obs/spans.py add_span / add_child, an exchange's
#: gap): never an annotation with the store's span id
AFTER_THE_FACT = ("xla.compile", "host.stall", "http.between")


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


def by_request(trace_json: dict, ph: str = "X") -> dict:
    """The requests' spans on the host's threads; ``ph`` "b": the ones
    that ran on the device (``device.run``: an async pair, whose "b" event
    carries ``dur``)."""
    out: dict = {}
    for event in trace_json.get("traceEvents", []):
        if event.get("ph", "X") == ph:
            out.setdefault(event["args"]["request_id"], []).append(event)
    return out


def union_inside(section: tuple, intervals: list) -> float:
    """us of ``section`` (start, end) that the intervals' union covers."""
    reach, total = section[0], 0.0
    for start, end in sorted(intervals):
        start, end = max(start, reach), min(end, section[1])
        if end > start:
            total += end - start
            reach = end
    return total


def device_of(events: list, runs: list):
    """One request's device side: busy and idle ms inside its
    ``dispatch.device``, busy by kind, dry enqueues, late fences, exact
    stamps; None for a tree without the section or without a run."""
    sections = [(e["ts"], e["ts"] + e["dur"]) for e in events
                if e["name"] == "dispatch.device"]
    if not sections or not runs:
        return None
    spans = [(r["ts"], r["ts"] + r["dur"]) for r in runs]
    busy = sum(union_inside(sec, spans) for sec in sections)
    by_kind: dict = {}
    for r in runs:
        kind = r["args"]["kind"]
        by_kind[kind] = by_kind.get(kind, 0.0) + r["dur"] / 1e3
    return {"busy_ms": busy / 1e3,
            "idle_ms": (sum(b - a for a, b in sections) - busy) / 1e3,
            "by_kind_ms": by_kind, "dispatches": len(runs),
            "dry": sum(1 for r in runs if r["args"].get("dry")),
            "fences": sum(1 for e in events if "late" in e["args"]),
            "late": sum(1 for e in events if e["args"].get("late")),
            "exact": sum(1 for r in runs if r["args"].get("exact"))}


def device_block(host: dict, runs: dict, statuses: list, threads: list):
    """The ``device`` entry (module docstring), printed as ``device:``;
    None where no request of the window has a ``device.run``."""
    rows = [row for row in (device_of(ev, runs.get(rid, []))
                            for rid, ev in host.items()) if row]
    if not rows:
        return None
    kinds = sorted({k for row in rows for k in row["by_kind_ms"]})
    out = {key: statistics.median(row[key] for row in rows)
           for key in ("busy_ms", "idle_ms", "dispatches", "dry", "fences",
                       "late")}
    out["requests"] = len(rows)
    out["by_kind_ms"] = {k: statistics.median(
        row["by_kind_ms"].get(k, 0.0) for row in rows) for k in kinds}
    out["exact_share"] = sum(r["exact"] for r in rows) / max(
        1, sum(r["dispatches"] for r in rows))
    out["idle_ms_all"] = sorted(round(row["idle_ms"], 3) for row in rows)
    before, after = ((s.get("serving") or {}).get("device")
                     for s in (statuses + [{}, {}])[:2])
    if before and after:
        out["serving_device"] = {
            k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float))
            and not isinstance(after[k], bool)}
        out["watcher"] = after.get("watcher")
    out["threads"] = threads
    print("device: " + json.dumps({
        k: round(v, 3) if isinstance(v, float) else v
        for k, v in out.items() if k != "idle_ms_all"}))
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


def attr_counts(requests: dict, attrs: tuple) -> dict:
    """{"<span>.<attr>": {value: spans}} over the requests, for the attrs
    that say which way a span went (``ladder``, ``added_cond``: hit, built
    or none, PR 38; ``ended_by``, ``requests``, PR 50)."""
    out: dict = {}
    for events in requests.values():
        for e in events:
            for attr in attrs:
                if attr in e["args"]:
                    row = out.setdefault(f"{e['name']}.{attr}", {})
                    value = str(e["args"][attr])
                    row[value] = row.get(value, 0) + 1
    return out


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


def read_xplane(path: str, traced_events: list, summary: dict,
                n_traced: int, traced_runs: list = ()) -> dict:
    """``summary``: what ``trace_reduce.reduce`` made of the same file;
    ``traced_runs``: the traced requests' ``device.run`` events."""
    from benchmarks.harness import files, trace_reduce, xplane_proto

    space = xplane_proto.read_xspace(path)
    spans = []     # (start_ns, end_ns, name, request id, span id, thread)
    for plane in trace_reduce._host_planes(space):
        stat = xplane_proto.stat_names(plane)
        names = {e.key: e.value.name for e in plane.event_metadata}
        for line in plane.lines:
            for start, end, ev in trace_reduce._events(line):
                name = names[ev.metadata_id]
                if name.startswith("sdtpu:"):
                    stats = xplane_proto.stats_of(ev, stat)
                    spans.append((start, end, name[6:],
                                  stats.get("request_id"),
                                  stats.get("span_id"),
                                  (plane.id, line.id)))
    have = {(s[3], s[4]) for s in spans}
    # no request id exists while http.accept is open: by span id alone
    have |= {(None, s[4]) for s in spans}
    missing = sorted({
        e["name"] for e in traced_events
        if e["name"] not in AFTER_THE_FACT and (
            None if e["name"] == "http.accept" else e["args"]["request_id"],
            e["args"]["span_id"]) not in have})

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
           "gaps": [], "op_meta": [
               {"op": row["name"], "device_ms": row["seconds"] * 1e3,
                "meta": {k: row[k] for k in ("scope", "category", "module",
                                             "calls", "flops", "bytes",
                                             "shape")}}
               for row in summary["op_table"][:6]]}
    bench = files.Bench(REPO)
    reader = bench.load("readers", "idle_by_owner")
    merged = reader.first_device_busy(space)
    bounds = trace_reduce._slice_bounds(space)
    if merged:
        idle = sorted(((b[0] - a[1], a[1], b[0])
                       for a, b in zip(merged, merged[1:])), reverse=True)
        out["gaps"] = [owner(s, e) for _, s, e in idle[:5]]
    if merged and bounds:       # the load generator's marks bound the slice
        out["head"] = owner(bounds[0], merged[0][0])
        out["tail"] = owner(merged[-1][1], bounds[1])
    # the program's device.run beside the device's own busy intervals,
    # inside each traced request's dispatch.device on either clock
    out["traced"] = []
    for sec in (e for e in traced_events if e["name"] == "dispatch.device"):
        rid = sec["args"]["request_id"]
        mine = [s for s in spans if s[2] == "dispatch.device" and s[3] == rid
                and s[4] == sec["args"]["span_id"]]
        runs = [r for r in traced_runs if r["args"]["request_id"] == rid]
        if not mine or not runs or not merged:
            continue
        xbusy = union_inside((mine[0][0], mine[0][1]), merged) / 1e6
        section_ms = sec["dur"] / 1e3
        pbusy = union_inside(
            (sec["ts"], sec["ts"] + sec["dur"]),
            [(r["ts"], r["ts"] + r["dur"]) for r in runs]) / 1e3
        by_kind: dict = {}
        for r in runs:
            by_kind[r["args"]["kind"]] = by_kind.get(
                r["args"]["kind"], 0.0) + r["dur"] / 1e3
        request_ms = sum(e["dur"] for e in traced_events
                         if e["args"]["request_id"] == rid
                         and "parent_id" not in e["args"]
                         and e["name"] not in ("http.between",
                                               "http.accept")) / 1e3
        row = {"request_id": rid, "request_ms": request_ms,
               "section_ms": section_ms,
               "section_ms_xplane": (mine[0][1] - mine[0][0]) / 1e6,
               "program_busy_ms": pbusy, "xplane_busy_ms": xbusy,
               "program_idle_ms": section_ms - pbusy,
               "xplane_idle_ms": (mine[0][1] - mine[0][0]) / 1e6 - xbusy,
               "busy_diff_share_of_request":
                   (pbusy - xbusy) / request_ms if request_ms else None,
               "program_by_kind_ms": by_kind,
               "xplane_modules_ms": {k: v * 1e3 / max(1, n_traced)
                                     for k, v in sorted(
                                         summary.get("modules", {}).items(),
                                         key=lambda kv: -kv[1])[:8]},
               "exact": sum(1 for r in runs if r["args"].get("exact")),
               "dispatches": len(runs)}
        out["traced"].append(row)
        print("traced device: " + json.dumps(
            {k: round(v, 3) if isinstance(v, float) else v
             for k, v in row.items()}))
    if spans and bounds and n_traced:
        spec = bench.read("idle_classes", "request.json")
        by_name = reader.idle_by_name(
            [s[:3] + s[5:] for s in spans], merged, bounds,
            yields=tuple(spec.get("yields", ())))
        out["idle_by_name_ms"] = dict(sorted(
            ((k, v / 1e6 / n_traced) for k, v in by_name.items()),
            key=lambda kv: -kv[1]))
        by_class = out["idle_by_class_ms"] = reader.by_class(
            out["idle_by_name_ms"], spec["classes"])
        print(f"idle ms a traced request, by class: "
              f"{json.dumps({k: round(v, 2) for k, v in by_class.items()})}")
        for name, ms in out["idle_by_name_ms"].items():
            print(f"  {ms:9.3f}  {name}")
    return out


def host_block(statuses: list, gc_seconds: list, stalls: list,
               window: dict, medians: dict):
    """The ``host`` entry (module docstring), printed as the ``host:``
    line; None where the program's status has no ``serving.host``."""
    before, after = ((s.get("serving") or {}).get("host")
                     for s in (statuses + [{}, {}])[:2])
    if not before or not after:
        return None
    out = {k: after[k] - before[k] for k in after
           if not k.endswith("_max") and k != "gc_collections"}
    out["gc_collections"] = {g: n - before["gc_collections"][g]
                             for g, n in after["gc_collections"].items()}
    out["gc_pause_ms_by_generation"] = {
        key[0]: (s - gc_seconds[0].get(key, 0.0)) * 1e3
        for key, s in sorted(gc_seconds[1].items())}
    out.update({k: after[k] if after[k] > before[k] else None
                for k in after if k.endswith("_max")})
    first = min((e["ts"] for ev in window.values() for e in ev), default=0.0)
    out["stalls_in_window"] = [
        {"ms": e["dur"] / 1e3, "at_ms": (e["ts"] - first) / 1e3,
         "requests": e["args"]["requests"], "spans": e["args"]["spans"]}
        for e in stalls if e["ts"] >= first]
    out["stall_ms_no_request"] = sum(
        row["ms"] for row, e in zip(out["stalls_in_window"],
                                    (e for e in stalls if e["ts"] >= first))
        if e["args"].get("alive") == 0)
    out["http_accept_ms"] = medians.get("http.accept")
    out["http_between_ms"] = medians.get("http.between")
    print("host: " + json.dumps({k: round(v, 3) if isinstance(v, float)
                                 else v for k, v in out.items()}))
    return out


def print_live(live: dict) -> None:
    """A kept request's sample, taken while it was still slow."""
    print(f"  live at {live['age_ms']:.1f} ms, open: " + ", ".join(
        f"{sp['name']} ({sp['age_ms']:.1f} ms)" for sp in live["open"]))
    for stall in live["stalls"]:
        print(f"    stall {stall['dur'] / 1e3:.1f} ms under "
              f"{stall['args']['spans']}")
    for row in live.get("device", ()):      # PR 71: what it had enqueued
        came = "not ready by the request's end" if row["ready_ms"] is None \
            else (f"ready at {row['ready_ms']:.1f} ms"
                  + ("" if row["exact"] else " or before"))
        print(f"    device {row['kind']} enqueued at "
              f"{row['enqueued_ms']:.1f} ms, "
              + ("ready" if row.get("ready_at_sample", row["ready"])
                 else "NOT ready") + f" at the sample; {came}")
    owner = live["open"][0]["thread"] if live["open"] else None
    stacks = re.split(r"(?m)^(?=Thread )", live["stacks"])
    # the thread that owns the innermost open span first (a stable sort)
    for stack in sorted(stacks, key=lambda st: f"(ident={owner})" not in st):
        print("    " + stack.rstrip().replace("\n", "\n    "))


def slow_entries(medians: dict) -> list:
    """The flight recorder's ``slow`` entries, each with its tree; a row
    ends with the span's excess over the median tree (a name that occurs
    k times in the request is held to a k-th of the median sum)."""
    from stable_diffusion_webui_distributed_tpu.obs import flightrec

    out = []
    for entry in flightrec.RECORDER.dump()["entries"]:
        if entry["reason"] != "slow":
            continue
        host = [e for e in entry["spans"] if e.get("ph", "X") == "X"]
        times = collections.Counter(e["name"] for e in host)
        rows = [row + [row[2] - medians.get(row[1], 0.0) / times[row[1]]]
                for row in tree(host)]
        out.append({"request_id": entry["request_id"],
                    "detail": entry["detail"], "tree": rows,
                    "live": entry.get("live")})
        print(f"slow: {entry['request_id']}: {entry['detail']}")
        for depth, name, ms, self_ms, _attrs, excess in rows:
            print(f"  {'  ' * depth}{name}  {ms:.2f} ms  "
                  f"(self {self_ms:.2f}, over the median {excess:+.2f})")
        if entry.get("live"):
            print_live(entry["live"])
    return out


def timeline(window: dict, stalls: list) -> list:
    """[[request id, ms of its exchange (its first span's start to its last
    span's end), ms until the next exchange starts, the host clock's stalls
    that began before the next one did]] in time order: of a
    window that lost time it says whether a request stretched (which the
    recorder's rule is there to keep) or the time lies BETWEEN requests,
    where no span of the program is alive. Printed: the medians and every
    row more than a fifth of the median exchange over either."""
    ends = sorted((min(e["ts"] for e in ev),
                   max(e["ts"] + e["dur"] for e in ev), rid)
                  for rid, ev in window.items())
    rows = [[rid, (end - start) / 1e3, (nxt[0] - end) / 1e3,
             [[(e["ts"] - start) / 1e3, e["dur"] / 1e3] for e in stalls
              if start <= e["ts"] < nxt[0]]]
            for (start, end, rid), nxt in zip(ends, ends[1:])]
    if rows:
        took, gap = (statistics.median(r[i] for r in rows) for i in (1, 2))
        print(f"timeline: {len(rows)} exchanges, median {took:.1f} ms, "
              f"then {gap:.1f} ms to the next")
        for rid, ms, after, stalled in rows:
            if ms > 1.2 * took or after > gap + 0.2 * took or stalled:
                print(f"  {rid}: {ms:.1f} ms, then {after:.1f} ms" + "".join(
                    f"; stall {long:.1f} ms at {at:.1f}"
                    for at, long in stalled))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--prepared", action="store_true")
    ap.add_argument("--keep-slow", action="store_true")
    ap.add_argument("--watch", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)
    root = prepared_root() if args.prepared else REPO
    sys.path[:0] = [root, REPO]

    import benchmarks.run as run
    from benchmarks.harness import loadgen, trace_reduce
    from stable_diffusion_webui_distributed_tpu.obs import prometheus, spans

    if args.watch and hasattr(spans.TRACER, "armed"):
        spans.TRACER.armed = True
    fetched: dict = {}
    get_json = loadgen.get_json

    def keeping_get_json(base, route):
        out = get_json(base, route)
        fetched.setdefault(route, []).append(out)
        if route == "/internal/trace.json":     # the server is still up
            import threading

            fetched["threads"] = sorted(
                t.name for t in threading.enumerate())
        if route == "/internal/status":     # what the block holds by now
            gc_seconds = getattr(prometheus, "GC_PAUSE_COUNTER", None)
            fetched.setdefault("gc_seconds", []).append(
                gc_seconds.snapshot() if gc_seconds else {})
        return out

    kept = tempfile.mkdtemp(prefix="probe-xplane-")
    reduce = trace_reduce.reduce

    def keeping_reduce(path, *a, **kw):
        shutil.copy(path, os.path.join(kept, "slice.xplane.pb"))
        fetched["reduce"] = reduce(path, *a, **kw)
        return fetched["reduce"]

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
        out["programs_setup"] = statuses[0]["serving"].get("programs")
        print(f"programs: {json.dumps(out['programs_setup'])}")
    trace_json = (fetched.get("/internal/trace.json") or [{}])[-1]
    requests = by_request(trace_json)
    runs = by_request(trace_json, "b")
    window = {rid: ev for rid, ev in requests.items()
              if rid.startswith("w-")}
    n_traced = 0
    xplane = os.path.join(kept, "slice.xplane.pb")
    if os.path.exists(xplane):
        traced = [ln for ln in lines if ln.startswith("traced slice:")]
        n_traced = int(traced[0].split()[2]) if traced else 0
        first = [e for i in range(n_traced)
                 for e in window.get(f"w-{i}", [])]
        out.update(read_xplane(
            xplane, first, fetched["reduce"], n_traced,
            [r for i in range(n_traced) for r in runs.get(f"w-{i}", [])]))
    shutil.rmtree(kept, ignore_errors=True)
    untraced = {rid: ev for rid, ev in window.items()
                if int(rid[2:]) >= n_traced} or window
    out["requests"] = len(untraced)
    out["span_median_ms"] = span_medians(untraced)
    out["plan_attrs"] = attr_counts(window, ("ladder", "added_cond"))
    out["plan_counts"] = (statuses[-1].get("serving") or {}).get("plan") \
        if statuses else None
    print(f"plan: {json.dumps(out['plan_attrs'])} "
          f"{json.dumps(out['plan_counts'])}")
    attention = dict((statuses[-1].get("serving") or {}).get("attention")
                     or {}) if statuses else {}
    attention.pop("by_shape", None)
    out["attention_sites"] = attention or None
    print(f"attention: {json.dumps(out['attention_sites'])}")
    out["dispatch_attrs"] = attr_counts(window, ("ended_by", "requests"))
    out["coalesce_windows"] = {
        key[0]: n for key, n in
        prometheus.COALESCE_WINDOW_COUNTER.snapshot().items()}
    print(f"dispatch: {json.dumps(out['dispatch_attrs'])} "
          f"{json.dumps(out['coalesce_windows'])}")
    if untraced:
        last = max(untraced, key=lambda rid: int(rid[2:]))
        out["tree"] = tree(untraced[last])
    stalls = requests.get("host", [])
    # an exchange begins at its accept: the gap before it is the row above's
    exchanges = {rid: [e for e in ev if e["name"] != "http.between"]
                 for rid, ev in window.items()}
    out["host"] = host_block(statuses, fetched.get("gc_seconds", []), stalls,
                             exchanges, out["span_median_ms"])
    out["device"] = device_block(untraced, runs, statuses,
                                 fetched.get("threads", []))
    if args.keep_slow:
        out["slow"] = slow_entries(out["span_median_ms"])
        out["timeline"] = timeline(exchanges, stalls)
    tag = args.tag or f"{args.workload}-{args.seed}-t{args.trace}"
    path = os.path.join(REPO, "chiprun_out", "probe", tag + ".json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
    print(f"probe: wrote {path}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())

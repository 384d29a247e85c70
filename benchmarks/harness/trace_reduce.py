"""From a profiler trace (``.xplane.pb``) to numbers. Read through
``xplane_proto`` (``google.protobuf`` alone): the proto shows what
``jax.profiler.ProfileData`` hides, the event metadata that says what each
device op is and what XLA reckons it costs.

What counts as the device: every plane named ``/device:TPU:<n>``; on it,
the line ``XLA Ops`` holds one event per executed HLO op (checked by hand on
a v5e trace, PR 23) and ``XLA Modules`` one per executable run. Busy time is
the union of the ops' intervals, so ops that overlap (an async collective
under a fusion) are not counted twice. A trace without TPU planes (the CPU
rehearsals) takes the host events that carry an ``hlo_op`` stat as the one
device's ops.

An op's event metadata carries ``tf_op`` (the flax scope and the JAX
primitive: ``jit(run_chunk)/while/body/closed_call/UNet/up_1_attn_1/
block_0/attn1/dot_general:``), ``hlo_category``, ``flops`` and
``bytes_accessed`` (XLA's own count for one call), ``shape_with_layout``
(the result) and ``program_id`` (the number in the module's name). The
per-op table keeps them; readers take it from ``context["trace"]
["op_table"]``.

The slice is what the benchmark's own marks bound (``bench:request``, a
``TraceAnnotation`` the load generator opens around each traced request):
the device's idle time before its first op and after its last is a gap like
those between ops, named ``head`` and ``tail``.

The work is bounded: every event is visited once, names are only looked at
for ops, and the host's events are only matched against the few longest
gaps.
"""

from __future__ import annotations

import glob
import os
import re

from benchmarks.harness import xplane_proto
from benchmarks.harness.loadgen import SLICE_MARK

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: ops that only wrap other ops of the same line (a scan is one ``while``
#: around every step): counted in the busy union, left out of the tables
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|collective-permute|all-to-all|reduce-scatter"
    r"|collective-broadcast", re.IGNORECASE)
#: segments of ``tf_op`` that name no module of the model
_PLUMBING = re.compile(
    r"^(p?jit\(.*\)|while|body|cond|branch_\d+_fun|closed_call|checkpoint"
    r"|remat\d*|custom_jvp_call|custom_vjp_call|core_call|shard_map)$")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union_ns(intervals) -> tuple[float, list]:
    """(length of the union in ns, the merged intervals) of (start, end)."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged), merged


def short_name(name: str) -> str:
    """A TPU trace names an op by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...)``): keep ``fusion.12``. A module
    is ``jit_run_chunk(1234567)``: keep ``jit_run_chunk``."""
    return name.split(" = ", 1)[0].split("(", 1)[0].lstrip("%").strip()


def scope_tail(scope: str, keep: int = 3) -> str:
    """The last ``keep`` module names of a ``tf_op``: the primitive at its
    end and the plumbing (``jit(...)``, ``while``, ``body``, ...) left
    out. ``jit(run_chunk)/while/body/closed_call/UNet/up_1_attn_1/block_0/
    attn1/dot_general`` gives ``up_1_attn_1/block_0/attn1``."""
    parts = [p for p in scope.split("/")[:-1] if not _PLUMBING.match(p)]
    return "/".join(parts[-keep:])


def display_name(row: dict) -> str:
    tail = scope_tail(row["scope"])
    return f"{tail} {row['name']}" if tail else row["name"]


def _events(line):
    """(start_ns, end_ns, event) of one line's events."""
    base = line.timestamp_ns
    for e in line.events:
        start = base + e.offset_ps / 1e3
        yield start, start + e.duration_ps / 1e3, e


def _device_planes(space) -> dict:
    """{device id: plane}"""
    out = {}
    for plane in space.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            out[int(match.group(1))] = plane
    return out


def _host_planes(space):
    return [p for p in space.planes if p.name.startswith("/host:")]


def _has_stat(plane, name: str):
    """A test ``event -> bool`` for 'carries the stat ``name``'."""
    ids = {e.key for e in plane.stat_metadata if e.value.name == name}
    return lambda event: any(s.metadata_id in ids for s in event.stats)


def _read_device(plane) -> tuple[list, dict, dict, dict]:
    """One device plane: ([(start_ns, end_ns, metadata id)] of XLA Ops,
    {metadata id: table row without times}, {module name: seconds},
    {module name: launches})."""
    names = xplane_proto.stat_names(plane)
    ops, modules, programs, calls = [], {}, {}, {}
    metadata = {e.key: e.value for e in plane.event_metadata}
    for line in plane.lines:
        if line.name == OPS_LINE:
            ops.extend((s, e, ev.metadata_id) for s, e, ev in _events(line))
        elif line.name == MODULES_LINE:
            for start, end, ev in _events(line):
                full = metadata[ev.metadata_id].name
                name = short_name(full)
                modules[name] = modules.get(name, 0.0) + (end - start) / 1e9
                calls[name] = calls.get(name, 0) + 1
                programs[full[len(name):].strip("()")] = name
    rows = {}
    for mid in {m for _, _, m in ops}:
        meta = metadata[mid]
        stats = xplane_proto.stats_of(meta, names)
        rows[mid] = {
            "name": short_name(meta.name),
            "scope": str(stats.get("tf_op", "")).rstrip(":"),
            "category": str(stats.get("hlo_category", "")),
            "module": programs.get(str(stats.get("program_id", "")), ""),
            "flops": float(stats.get("flops") or 0),
            "bytes": float(stats.get("bytes_accessed") or 0),
            "shape": str(stats.get("shape_with_layout", "")),
        }
    return ops, rows, modules, calls


def _read_rehearsal(space) -> tuple[list, dict]:
    """No TPU plane (a CPU rehearsal): the host events with an ``hlo_op``
    stat are the one device's ops; nothing says what they cost."""
    ops, rows = [], {}
    for plane in _host_planes(space):
        is_op = _has_stat(plane, "hlo_op")
        metadata = {e.key: e.value for e in plane.event_metadata}
        for line in plane.lines:
            for start, end, ev in _events(line):
                if end > start and is_op(ev):
                    key = (plane.id, ev.metadata_id)
                    ops.append((start, end, key))
                    if key not in rows:
                        rows[key] = {
                            "name": metadata[ev.metadata_id].name,
                            "scope": "", "category": "", "module": "",
                            "flops": 0.0, "bytes": 0.0, "shape": ""}
    return ops, rows


def _slice_bounds(space) -> tuple[float, float] | None:
    """(start_ns, end_ns) over the load generator's marks, if any."""
    lo = hi = None
    for plane in _host_planes(space):
        marks = {e.key for e in plane.event_metadata
                 if e.value.name == SLICE_MARK}
        if not marks:
            continue
        for line in plane.lines:
            for start, end, ev in _events(line):
                if ev.metadata_id in marks:
                    lo = start if lo is None else min(lo, start)
                    hi = end if hi is None else max(hi, end)
    return None if lo is None else (lo, hi)


def _host_events_in(space, gaps, device_planes: bool):
    """For each (start, end) gap, the host event that says most about it:
    the SHORTEST event that covers at least half of the gap (host events
    nest, and the outermost covers everything), else the event with the
    longest overlap. The load generator's own marks are no answer.
    [(name, overlap_ns)] in the order of ``gaps``."""
    # per gap: (covers half, -duration or overlap, name, overlap)
    best = [(False, 0.0, "nothing recorded on the host", 0.0)] * len(gaps)
    if not gaps:
        return []
    lo = min(g[0] for g in gaps)
    hi = max(g[1] for g in gaps)
    for plane in _host_planes(space):
        is_op = (lambda event: False) if device_planes \
            else _has_stat(plane, "hlo_op")
        names = {e.key: e.value.name for e in plane.event_metadata}
        for line in plane.lines:
            thread = line.name.split("/")[0]
            for start, end, ev in _events(line):
                if end <= lo or start >= hi or end == start or is_op(ev):
                    continue
                name = names[ev.metadata_id]
                if name == SLICE_MARK:
                    continue
                for i, (gs, ge) in enumerate(gaps):
                    overlap = min(end, ge) - max(start, gs)
                    if overlap <= 0:
                        continue
                    covers = 2 * overlap >= ge - gs
                    rank = (covers, start - end if covers else overlap)
                    if rank > best[i][:2]:
                        best[i] = rank + (f"{thread}: {name}", overlap)
    return [(b[2], b[3]) for b in best]


def reduce(xplane_path: str, top: int = 10, gaps: int = 7,
           modules: int = 4) -> dict:
    """The trace's summary:

    ``devices``: per device id ``busy_s``, ``first_ns``, ``last_ns``,
    ``collective_s``, ``ops``;
    ``busy_s``: mean over devices; ``span_s``: first op start to last op end
    over all devices; ``collective_s``: mean over devices of the summed
    durations of collective ops;
    ``modules``: {executable: seconds summed over all devices};
    ``module_calls``: {executable: launches the trace holds, summed over
    all devices}: the events ``modules`` sums. A profiler that came back
    short (a host stall inside the slice) lacks launches in BOTH, so a
    reader that sets work against an executable's seconds counts the work
    of the launches that are there;
    ``op_table``: one row per HLO op (containers left out), most time
    first: ``name``, ``scope`` (its ``tf_op``), ``category``, ``module``,
    ``calls`` and ``seconds`` summed over all devices, and for ONE call
    XLA's ``flops`` and ``bytes`` and the result's ``shape``;
    ``device_ops``: at most ``top`` entries of [name, seconds]: the
    ``modules`` executables with most time (``module <name>``: which
    program the time is in), then the ops with most time, each named by
    the tail of its scope and its HLO name;
    ``idle_gaps``: the ``gaps`` longest idle gaps on the first device with
    what the host was doing, as [name, seconds]; where the load generator
    marked the slice, the time before the first op and after the last are
    among them as ``head: ...`` and ``tail: ...``;
    ``head_s``, ``tail_s``: those two, 0.0 without marks.
    """
    space = xplane_proto.read_xspace(xplane_path)
    planes = _device_planes(space)
    out: dict = {"devices": {}, "device_ops": [], "idle_gaps": [],
                 "busy_s": 0.0, "span_s": 0.0, "collective_s": 0.0,
                 "events": 0, "modules": {}, "module_calls": {},
                 "op_table": [],
                 "head_s": 0.0, "tail_s": 0.0}
    per_device = {}
    for dev, plane in sorted(planes.items()):
        ops, rows, mods, calls = _read_device(plane)
        if ops:
            per_device[dev] = (ops, rows)
        for name, seconds in mods.items():
            out["modules"][name] = out["modules"].get(name, 0.0) + seconds
            out["module_calls"][name] = (out["module_calls"].get(name, 0)
                                         + calls[name])
    if not planes:
        ops, rows = _read_rehearsal(space)
        if ops:
            per_device[0] = (ops, rows)
    if not per_device:
        return out
    table: dict = {}
    merged_first = None
    for dev, (ops, rows) in sorted(per_device.items()):
        busy_ns, merged = union_ns((s, e) for s, e, _ in ops)
        coll_ns = 0.0
        for start, end, mid in ops:
            row = rows[mid]
            if COLLECTIVE.search(row["name"]):
                coll_ns += end - start
            if CONTAINERS.match(row["name"]):
                continue
            key = (row["module"], row["name"], row["scope"])
            entry = table.get(key)
            if entry is None:
                entry = table[key] = dict(row, calls=0, seconds=0.0)
            entry["calls"] += 1
            entry["seconds"] += (end - start) / 1e9
        out["devices"][dev] = {
            "busy_s": busy_ns / 1e9, "collective_s": coll_ns / 1e9,
            "first_ns": merged[0][0], "last_ns": merged[-1][1],
            "ops": len(ops)}
        out["events"] += len(ops)
        if merged_first is None:
            merged_first = merged
    n = len(out["devices"])
    out["busy_s"] = sum(d["busy_s"] for d in out["devices"].values()) / n
    out["collective_s"] = sum(
        d["collective_s"] for d in out["devices"].values()) / n
    out["span_s"] = (max(d["last_ns"] for d in out["devices"].values())
                     - min(d["first_ns"] for d in out["devices"].values())
                     ) / 1e9
    out["op_table"] = sorted(table.values(), key=lambda r: -r["seconds"])
    by_time = lambda pairs: sorted(pairs.items(), key=lambda kv: -kv[1])
    out["device_ops"] = [[f"module {name}", seconds] for name, seconds
                         in by_time(out["modules"])[:modules]]
    by_display: dict = {}
    for row in out["op_table"]:
        name = display_name(row)
        by_display[name] = by_display.get(name, 0.0) + row["seconds"]
    out["device_ops"] += [[name, seconds] for name, seconds
                          in by_time(by_display)][:top - len(out["device_ops"])]
    idle = [(b[0] - a[1], a[1], b[0], "")
            for a, b in zip(merged_first, merged_first[1:])]
    bounds = _slice_bounds(space)
    if bounds:
        first, last = merged_first[0][0], merged_first[-1][1]
        if bounds[0] < first:
            idle.append((first - bounds[0], bounds[0], first, "head: "))
            out["head_s"] = (first - bounds[0]) / 1e9
        if bounds[1] > last:
            idle.append((bounds[1] - last, last, bounds[1], "tail: "))
            out["tail_s"] = (bounds[1] - last) / 1e9
    idle = sorted(idle, reverse=True)[:gaps]
    names = _host_events_in(space, [(s, e) for _, s, e, _ in idle],
                            bool(planes))
    out["idle_gaps"] = [[which + name, length / 1e9]
                        for (length, _, _, which), (name, _)
                        in zip(idle, names)]
    return out


def _device_ops(profile) -> dict:
    """{device id: [(start_ns, end_ns, name)]} of a
    ``jax.profiler.ProfileData``: nothing here calls it; tools/
    trace_probe.py does, and goes over to ``reduce`` in the PR that may edit
    it (PERF.md section 7)."""
    devices: dict = {}
    for plane in profile.planes:
        match = DEVICE_PLANE.match(plane.name)
        for line in plane.lines if match else ():
            if line.name == OPS_LINE:
                devices.setdefault(int(match.group(1)), []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns,
                     short_name(e.name)) for e in line.events)
    return devices

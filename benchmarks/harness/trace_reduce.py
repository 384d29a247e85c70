"""From a profiler trace (``.xplane.pb``) to numbers. Read with JAX's own
``ProfileData``; nothing else is needed.

What counts as the device: every plane named ``/device:TPU:<n>``; on it,
the line ``XLA Ops`` holds one event per executed HLO op (checked by hand on
a v5e trace, PR 23). Busy time is the union of those events' intervals, so
ops that overlap (an async collective under a fusion) are not counted twice.
A trace without TPU planes (the CPU rehearsals) takes the host events that
carry an ``hlo_op`` stat as the one device's ops.

The work is bounded: every event is visited once, names are only looked at
for ops, and the host's events are only matched against the few longest
gaps.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: ops that only wrap other ops of the same line (a scan is one ``while``
#: around every step): counted in the busy union, left out of the top list
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|collective-permute|all-to-all|reduce-scatter"
    r"|collective-broadcast", re.IGNORECASE)


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


def _modules(profile) -> dict:
    """{module name: summed seconds over all devices} from XLA Modules."""
    out: dict = {}
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for e in line.events:
                        name = short_name(e.name)
                        out[name] = out.get(name, 0.0) + e.duration_ns / 1e9
    return out


def _device_ops(profile) -> dict:
    """{device id: [(start_ns, end_ns, name), ...]}"""
    devices: dict = {}
    for plane in profile.planes:
        match = DEVICE_PLANE.match(plane.name)
        if not match:
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            devices.setdefault(int(match.group(1)), []).extend(
                (e.start_ns, e.start_ns + e.duration_ns, short_name(e.name))
                for e in line.events)
    if devices:
        return devices
    ops = []
    for plane in profile.planes:        # CPU rehearsal: see the docstring
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns > 0 and any(
                        k == "hlo_op" for k, _ in e.stats):
                    ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return {0: ops} if ops else {}


def _host_events_in(profile, gaps, device_planes: bool):
    """For each (start, end) gap, the host event that says most about it:
    the SHORTEST event that covers at least half of the gap (host events
    nest, and the outermost covers everything), else the event with the
    longest overlap. [(name, overlap_ns)] in the order of ``gaps``."""
    # per gap: (covers half, -duration or overlap, name, overlap)
    best = [(False, 0.0, "nothing recorded on the host", 0.0)] * len(gaps)
    if not gaps:
        return []
    lo = min(g[0] for g in gaps)
    hi = max(g[1] for g in gaps)
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread = line.name.split("/")[0]
            for e in line.events:
                start = e.start_ns
                end = start + e.duration_ns
                if end <= lo or start >= hi or end == start:
                    continue
                if not device_planes and any(
                        k == "hlo_op" for k, _ in e.stats):
                    continue
                for i, (gs, ge) in enumerate(gaps):
                    overlap = min(end, ge) - max(start, gs)
                    if overlap <= 0:
                        continue
                    covers = 2 * overlap >= ge - gs
                    rank = (covers, start - end if covers else overlap)
                    if rank > best[i][:2]:
                        best[i] = rank + (f"{thread}: {e.name}", overlap)
    return [(b[2], b[3]) for b in best]


def reduce(xplane_path: str, top: int = 10, gaps: int = 5,
           modules: int = 4) -> dict:
    """The trace's summary:

    ``devices``: per device id ``busy_s``, ``first_ns``, ``last_ns``,
    ``collective_s``, ``ops``;
    ``busy_s``: mean over devices; ``span_s``: first op start to last op end
    over all devices; ``collective_s``: mean over devices of the summed
    durations of collective ops; ``device_ops``: at most ``top`` entries of
    [name, seconds summed over all devices]: the ``modules`` executables
    with most time (``module <name>``, from XLA Modules: which program the
    time is in), then the ops with most time, containers left out;
    ``idle_gaps``: the ``gaps`` longest idle gaps on the first device with
    what the host was doing, as [name, seconds].
    """
    import jax.profiler

    profile = jax.profiler.ProfileData.from_file(xplane_path)
    devices = _device_ops(profile)
    real = any(DEVICE_PLANE.match(p.name) for p in profile.planes)
    out: dict = {"devices": {}, "device_ops": [], "idle_gaps": [],
                 "busy_s": 0.0, "span_s": 0.0, "collective_s": 0.0,
                 "events": 0}
    if not devices:
        return out
    by_name: dict = {}
    merged_first = None
    for dev, ops in sorted(devices.items()):
        busy_ns, merged = union_ns((s, e) for s, e, _ in ops)
        coll_ns = 0.0
        for s, e, name in ops:
            by_name[name] = by_name.get(name, 0.0) + (e - s)
            if COLLECTIVE.search(name):
                coll_ns += e - s
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
    by_time = lambda table: sorted(table.items(), key=lambda kv: -kv[1])
    out["device_ops"] = [[f"module {name}", seconds] for name, seconds
                         in by_time(_modules(profile))[:modules]]
    out["device_ops"] += [
        [name, ns / 1e9] for name, ns in by_time(by_name)
        if not CONTAINERS.match(name)][:top - len(out["device_ops"])]
    idle = sorted(((b[0] - a[1], a[1], b[0]) for a, b in
                   zip(merged_first, merged_first[1:])), reverse=True)[:gaps]
    names = _host_events_in(profile, [(s, e) for _, s, e in idle], real)
    out["idle_gaps"] = [[name, length / 1e9]
                        for (length, _, _), (name, _) in zip(idle, names)]
    return out

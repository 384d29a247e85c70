"""The device's idle time inside the traced slice, ALL of it (every gap, the
head and the tail, not the few longest), given to the program span that was
alive at each instant, in milliseconds a traced request.

From ``context["xplane"]`` (through ``harness/xplane_proto.py``, as
``trace_reduce`` reads it): the first device's merged busy intervals inside
the slice the load generator marked (``bench:request``), and every host
event named ``sdtpu:<name>`` (obs/spans.py opens one per live span while a
capture runs). An idle instant goes to the span alive at it with the latest
start: spans of one thread nest, so every thread puts up its innermost span
and the latest-started of those wins, except that a name listed under
``yields`` in the class file (a follower's ``coalesced.wait``) owns an
instant only when no other thread puts up a span. The owner's NAME is classed by the
first matching rule of ``idle_classes/<classes>.json``; no span alive counts
under ``unowned``. The classes partition: their sum is the slice less the
device's busy time, which is what ``device_idle_share`` reports as a share.

The first metric that asks prints the whole table by span name on a stdout
line of its own (``idle by owner: {...}``, most first), so a chip run shows
which span to shorten. No trace, no marks or no ``sdtpu:`` event (a program
without the spans): nothing to read.
"""

import json
import re

import numpy as np

from benchmarks.harness import trace_reduce, xplane_proto

PREFIX = "sdtpu:"
NOBODY = "(no span)"


def program_spans(space) -> list:
    """[(start_ns, end_ns, name, thread)] of the host events named
    sdtpu:<name>; a thread is one line of a host plane."""
    out = []
    for plane in trace_reduce._host_planes(space):
        ours = {e.key: e.value.name[len(PREFIX):]
                for e in plane.event_metadata
                if e.value.name.startswith(PREFIX)}
        if not ours:
            continue
        for line in plane.lines:
            thread = (plane.id, line.id)
            for start, end, ev in trace_reduce._events(line):
                if ev.metadata_id in ours and end > start:
                    out.append((start, end, ours[ev.metadata_id], thread))
    return out


def first_device_busy(space) -> list:
    """Merged [start_ns, end_ns] of the first device's ops (a CPU
    rehearsal: of the host events that carry ``hlo_op``)."""
    planes = trace_reduce._device_planes(space)
    if planes:
        ops = trace_reduce._read_device(planes[min(planes)])[0]
    else:
        ops = trace_reduce._read_rehearsal(space)[0]
    return trace_reduce.union_ns((s, e) for s, e, _ in ops)[1]


def busy_before(merged: list):
    """t -> ns of busy time before t (vectorised over t)."""
    if not merged:
        return lambda t: np.zeros_like(np.asarray(t, float))
    starts = np.array([m[0] for m in merged], float)
    ends = np.array([m[1] for m in merged], float)
    done = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def at(t):
        t = np.asarray(t, float)
        i = np.searchsorted(starts, t, side="right") - 1
        inside = np.clip(t - starts[np.maximum(i, 0)], 0.0,
                         (ends - starts)[np.maximum(i, 0)])
        return np.where(i < 0, 0.0, done[np.maximum(i, 0)] + inside)

    return at


def idle_by_name(spans: list, merged: list, bounds: tuple,
                 yields=()) -> dict:
    """{owner's name: idle ns} over [bounds): the slice cut at every span's
    start and end; in each piece every thread puts up its innermost span
    (the latest-started one alive on it), the latest-started of those that
    does not yield owns the piece (a yielding one if there is no other),
    and the piece's idle time is its length less the busy time inside."""
    lo, hi = bounds
    starts = np.array([s[0] for s in spans], float)
    ends = np.array([s[1] for s in spans], float)
    cuts = np.unique(np.clip(np.concatenate([[lo, hi], starts, ends]),
                             lo, hi))
    busy = busy_before(merged)(cuts)
    idle = np.diff(cuts) - np.diff(busy)
    out: dict = {}
    for a, b, ns in zip(cuts[:-1], cuts[1:], idle):
        if ns <= 0:
            continue
        innermost: dict = {}        # thread -> its latest-started span
        for i in np.nonzero((starts <= a) & (ends >= b))[0]:
            start, _, name, thread = spans[i]
            if thread not in innermost or start >= innermost[thread][0]:
                innermost[thread] = (start, name)
        put_up = sorted(innermost.values())
        name = next((n for _, n in reversed(put_up) if n not in yields),
                    put_up[-1][1] if put_up else NOBODY)
        out[name] = out.get(name, 0.0) + float(ns)
    return out


def classify(name: str, rules: list) -> str:
    if name == NOBODY:
        return "unowned"
    for rule in rules:
        if "name" not in rule or re.search(rule["name"], name):
            return rule["class"]
    raise ValueError(f"no rule of the class file takes {name!r}")


def by_class(by_name: dict, rules: list) -> dict:
    """{class: sum of its owners' values}, every class of the file there."""
    out = {rule["class"]: 0.0 for rule in rules}
    for name, value in by_name.items():
        out[classify(name, rules)] += value
    return out


def tables(context: dict, classes: str):
    """({span name: ms a traced request}, {class: ms a traced request}) or
    None; computed and printed once a run."""
    memo = context.setdefault("idle_by_owner", {})
    if classes not in memo:
        memo[classes] = _tables(context, classes)
        if memo[classes] is not None:
            by_name = memo[classes][0]
            print("idle by owner: " + json.dumps(
                {k: round(v, 3) for k, v in sorted(
                    by_name.items(), key=lambda kv: -kv[1])}), flush=True)
    return memo[classes]


def _tables(context: dict, classes: str):
    traced = [r for r in context["records"] if r.traced]
    if not context.get("xplane") or not traced:
        return None
    space = xplane_proto.read_xspace(context["xplane"])
    bounds = trace_reduce._slice_bounds(space)
    spans = program_spans(space)
    if bounds is None or not spans:
        return None
    spec = context["bench"].read("idle_classes", classes + ".json")
    ns = idle_by_name(spans, first_device_busy(space), bounds,
                      yields=tuple(spec.get("yields", ())))
    by_name = {name: v / 1e6 / len(traced) for name, v in ns.items()}
    return by_name, by_class(by_name, spec["classes"])


def read(context: dict, classes: str, cls: str):
    found = tables(context, classes)
    return None if found is None else found[1].get(cls)

"""Per request the part of one named span (``section``: the dispatcher's
``dispatch.device``) that the intervals of another (``span``: the
program's ``device.run``, obs/spans.py, one an executable the request
enqueued, from where the device could start it to where its output was
ready) do or do not cover, in milliseconds, median over the window's
requests. ``part`` is ``uncovered`` (the section less the union of the
intervals inside it: the device had nothing of this request to run) or
``covered`` (the union itself: the device ran). The intervals may nest,
touch or lie apart; only their union counts, cut to the section.

Intervals are needed, not durations, so the trees are read from the
program's own store (``obs/spans.py``, what ``/internal/trace.json``
serves; the benchmark's server runs in this process and its store outlives
the server) as ``span_self`` reads them. A ``device.run`` ran on no host
thread: the export gives it as an async pair, whose "b" event carries
``ts`` and ``dur``; a span of the host's threads is one "X" event. A
request without the section is left out (a coalesced follower: its
leader's tree holds the dispatch). Requests sent while the profiler ran
are left out when others exist. A program without that store, or none of
whose requests has an interval of ``span`` (before PR 71): nothing to
read."""

import statistics


def covered_us(sections: list, intervals: list) -> float:
    """Microseconds of the ``sections`` [(start, end)] inside the union of
    the ``intervals``."""
    total = 0.0
    for lo, hi in sections:
        reach = lo
        for start, end in sorted(intervals):
            start, end = max(start, reach), min(end, hi)
            if end > start:
                total += end - start
                reach = end
    return total


def read(context: dict, section: str, span: str, part: str = "uncovered"):
    if part not in ("covered", "uncovered"):
        raise ValueError(f"part {part!r}: covered or uncovered")
    try:
        from stable_diffusion_webui_distributed_tpu.obs import spans
        events = spans.TRACER.export_chrome()["traceEvents"]
    except (ImportError, AttributeError, KeyError):
        return None
    by_request: dict = {}
    for event in events:
        if event.get("ph") in ("X", "b") and event["name"] in (section,
                                                               span):
            by_request.setdefault(event["args"]["request_id"], {}) \
                .setdefault(event["name"], []).append(
                    (event["ts"], event["ts"] + event["dur"]))
    if not any(span in have for have in by_request.values()):
        return None
    records = ([r for r in context["records"] if not r.traced]
               or context["records"])
    found = []
    for record in records:
        have = by_request.get(record.request_id, {})
        if section not in have:
            continue
        inside = covered_us(have[section], have.get(span, []))
        found.append(inside if part == "covered" else
                     sum(hi - lo for lo, hi in have[section]) - inside)
    return statistics.median(found) / 1e3 if found else None

"""Host milliseconds of a request that only a parent's self time covers:
the request's top-level spans (``http.read_parse`` + the root +
``http.respond``) less the union of the intervals of its LEAF spans (a span
no other span of the request names as parent), median over the window's
requests. What is left is time no span describes: a parent ran code of its
own between its children.

The trees are the program's own (``obs/spans.py``'s store, what
``/internal/trace.json`` serves; the benchmark's server runs in this
process and its store outlives the server). Requests sent while the
profiler ran are left out when others exist. A program without that store,
or no tree for any record: nothing to read."""

import statistics


def unspanned_us(events: list) -> float:
    """Top-level span time less the union of the leaf spans, one request."""
    parents = {e["args"].get("parent_id") for e in events}
    top = sum(e["dur"] for e in events if "parent_id" not in e["args"])
    covered, reach = 0.0, None
    for start, end in sorted(
            (e["ts"], e["ts"] + e["dur"]) for e in events
            if e["args"]["span_id"] not in parents):
        if reach is None or start > reach:
            covered += end - start
            reach = end
        elif end > reach:
            covered += end - reach
            reach = end
    return top - covered


def read(context: dict):
    try:
        from stable_diffusion_webui_distributed_tpu.obs import spans
        events = spans.TRACER.export_chrome()["traceEvents"]
    except (ImportError, AttributeError, KeyError):
        return None
    by_request: dict = {}
    for event in events:
        if event.get("ph") == "X":
            by_request.setdefault(event["args"]["request_id"],
                                  []).append(event)
    records = ([r for r in context["records"] if not r.traced]
               or context["records"])
    found = [unspanned_us(by_request[r.request_id]) for r in records
             if r.request_id in by_request]
    return statistics.median(found) / 1e3 if found else None

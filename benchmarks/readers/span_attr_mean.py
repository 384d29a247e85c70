"""The mean of one numeric attribute over the spans of one name in the
window's request trees: ``dispatch.device`` carries ``requests``, how many
requests the dispatch ran for (1: alone; 2: a coalesced pair, whose
follower has no such span of its own), so the mean over the window's
dispatches is the coalesce factor.

The attribute is read from the program's own store (``obs/spans.py``,
what ``/internal/trace.json`` serves), as ``span_self`` reads the trees:
``context["spans"]`` keeps durations only. Requests sent while the
profiler ran are left out when others exist. A program without that
store, no span of that name in the window, or none that carries the
attribute as a number: nothing to read."""


def read(context: dict, span: str, attr: str):
    try:
        from stable_diffusion_webui_distributed_tpu.obs import spans
        events = spans.TRACER.export_chrome()["traceEvents"]
    except (ImportError, AttributeError, KeyError):
        return None
    records = ([r for r in context["records"] if not r.traced]
               or context["records"])
    wanted = {r.request_id for r in records}
    found = [event["args"][attr] for event in events
             if event.get("ph") == "X" and event["name"] == span
             and event["args"].get("request_id") in wanted
             and isinstance(event["args"].get(attr), (int, float))
             and not isinstance(event["args"][attr], bool)]
    return float(sum(found)) / len(found) if found else None

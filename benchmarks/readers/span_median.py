"""Median duration, in milliseconds, of one named span of the program's
request trees (/internal/trace.json; host clock, never syncs the device).
Requests sent while the profiler ran are left out when others exist."""

import statistics


def durations(context: dict, span: str) -> list[float]:
    records = ([r for r in context["records"] if not r.traced]
               or context["records"])
    found = []
    for record in records:
        spans = context["spans"].get(record.request_id, {}).get(span)
        if spans:
            found.append(sum(spans))
    return found


def read(context: dict, span: str):
    found = durations(context, span)
    return statistics.median(found) * 1e3 if found else None

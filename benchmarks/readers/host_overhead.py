"""Client's request seconds minus the spans named in ``inside`` of the same
request, median, in milliseconds: what HTTP, JSON, payload handling and PNG
encoding cost outside the dispatcher's queue and the engine."""

import statistics


def read(context: dict, inside: list[str]):
    records = ([r for r in context["records"] if not r.traced]
               or context["records"])
    found = []
    for record in records:
        spans = context["spans"].get(record.request_id, {})
        if all(name in spans for name in inside):
            found.append(record.seconds
                         - sum(sum(spans[name]) for name in inside))
    return statistics.median(found) * 1e3 if found else None

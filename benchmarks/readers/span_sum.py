"""Per request the summed seconds of several named spans of the program's
request trees (/internal/trace.json; host clock), then the median over
requests, in milliseconds. A span a request did not record adds nothing
(an img2img whose strength leaves no step waits on no chunk); a request
with none of the spans is left out, and where no request has any (a
program without these spans) there is nothing to read. Requests sent while
the profiler ran are left out when others exist."""

import statistics


def read(context: dict, spans: list[str]):
    records = ([r for r in context["records"] if not r.traced]
               or context["records"])
    found = []
    for record in records:
        have = context["spans"].get(record.request_id, {})
        if any(name in have for name in spans):
            found.append(sum(sum(have.get(name, ())) for name in spans))
    return statistics.median(found) * 1e3 if found else None

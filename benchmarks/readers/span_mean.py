"""Per request the summed seconds of several named spans of the program's
request trees (/internal/trace.json; host clock), a request WITHOUT them
counting 0, then the mean over requests, in milliseconds. For spans that
are rare events (a stall of the host): a median over the requests that
have one would say how long a stall is, not how much of them a request
meets. Only a request that recorded ``witness`` counts, a span that every
request of a program able to record the named ones has: where none has it
(a program without these spans) there is nothing to read. Requests sent
while the profiler ran are left out when others exist."""


def read(context: dict, spans: list[str], witness: str):
    records = ([r for r in context["records"] if not r.traced]
               or context["records"])
    found = []
    for record in records:
        have = context["spans"].get(record.request_id, {})
        if witness in have:
            found.append(sum(sum(have.get(name, ())) for name in spans))
    return sum(found) / len(found) * 1e3 if found else None

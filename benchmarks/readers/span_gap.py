"""Median duration, in milliseconds, of one named span that a request
records for the gap BEFORE it (/internal/trace.json; host clock): the time
since the exchange before it ended. Requests sent while the profiler ran
are left out, and so is the first one sent after them: its gap holds the
profiler's stop and the reduction of the slice, the harness's own pause
and none of the program's. A request without the span adds nothing (it
began while another was in flight); where none of the requests left has it
there is nothing to read."""

import statistics


def read(context: dict, span: str):
    records = sorted(context["records"], key=lambda r: r.start)
    after = [r for r in records if not r.traced]
    if len(after) < len(records):
        after = after[1:]       # the one that follows the traced slice
    found = [sum(have[span]) for have in
             (context["spans"].get(r.request_id, {}) for r in after)
             if span in have]
    return statistics.median(found) * 1e3 if found else None

"""Closed loop: each client sends its next request when the last one has
been answered, so a slower server is offered less load. Callers that each
wait for a reply (a webui user, the upstream extension's master waiting on
a worker) make this loop.

Traffic-file fields: ``clients`` (default 1).
"""

from __future__ import annotations

import threading
import time


def run(traffic: dict, send, draw, seconds=None, max_requests=None) -> list:
    """Send until ``seconds`` have passed (a request started inside them is
    let finish) or ``max_requests`` have been sent. Returns the records in
    order of completion. ``draw`` is called under a lock: the order of
    payloads follows from the seed, whichever client takes them."""
    clients = int(traffic.get("clients", 1))
    deadline = None if seconds is None else time.perf_counter() + seconds
    lock = threading.Lock()
    records: list = []
    sent = [0]

    def take():
        with lock:
            if max_requests is not None and sent[0] >= max_requests:
                return None
            if deadline is not None and time.perf_counter() >= deadline:
                return None
            sent[0] += 1
            return draw()

    def client():
        while (payload := take()) is not None:
            record = send(payload)
            with lock:
                records.append(record)

    if clients == 1:
        client()
    else:
        threads = [threading.Thread(target=client, name=f"client-{i}")
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return records

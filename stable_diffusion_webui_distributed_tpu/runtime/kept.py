"""What a request's plan derives from its key alone is built once and kept.

A sigma ladder is a function of (schedule name, trained noise schedule,
steps) and SDXL's time-id embedding of (ids, rows, embed dim): a dozen eager
device ops and a fetch each, which every request paid with the device idle
(PERF.md section 6, PR 38). :class:`KeptTable` holds such values for the
life of the process: the first caller of a key builds the value with the
code that built it before, every later caller gets the same object and
runs nothing on the device.

A table is bounded (the least recently used entry goes) and safe from
several threads. The build runs under the table's lock, so two threads
that ask for one new key build it once; a build is milliseconds and
happens once a key, and a builder takes no lock of this package.

What is kept is shared by every later request: a value must never be
donated to an executable nor written in place.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, Hashable, Tuple


class KeptTable:
    """Bounded map from a key to the value built for it once."""

    def __init__(self, limit: int = 64) -> None:
        self.limit = max(1, int(limit))
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Hashable, Any]" = \
            OrderedDict()  # guarded-by: _lock

    def get(self, key: Hashable,
            build: Callable[[], Any]) -> Tuple[Any, bool]:
        """``(value, hit)``: the kept value of ``key``, or ``build()``'s,
        kept from now on. ``hit`` is False for the call that built it."""
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return self._entries[key], True
            value = build()
            self._entries[key] = value
            while len(self._entries) > self.limit:
                self._entries.popitem(last=False)
            return value, False

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

"""Failure flight recorder: the last N failed/interrupted/slow requests.

A postmortem needs MORE than aggregate metrics — it needs the dead
request's own span tree and the log lines it emitted. ``obs/spans.py``
hands every non-``ok`` request trace here at close time (exported trace
events, so entries stay plain JSON), and this module attaches the
correlated log lines captured by ``runtime/logging.py``'s per-request
index. The ring is bounded (``SDTPU_OBS_FLIGHTREC`` entries, default 16 —
the same capacity instinct as the GUI log ring). Two read it:
``/internal/flightrec`` serves it, and ``tools/trace_probe.py --keep-slow``
prints each ``slow`` entry's tree and, where the host clock caught the
request while it was still slow (obs/watchdog.py), its ``live`` sample.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

from stable_diffusion_webui_distributed_tpu.runtime.config import env_int

#: Default retained failure entries.
DEFAULT_CAPACITY = 16


class FlightRecorder:
    """Bounded ring of failure records (thread-safe, JSON-plain entries)."""

    def __init__(self, capacity: Optional[int] = None) -> None:
        if capacity is None:
            capacity = env_int("SDTPU_OBS_FLIGHTREC", DEFAULT_CAPACITY)
        self._lock = threading.Lock()
        self._entries: Deque[Dict[str, Any]] = deque(
            maxlen=max(1, int(capacity or DEFAULT_CAPACITY)))  # guarded-by: _lock

    def record(self, request_id: str, reason: str, detail: str,
               events: List[Dict[str, Any]],
               duration_s: float = 0.0,
               perf: Optional[Dict[str, Any]] = None,
               live: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Append one failure entry; returns it (already JSON-plain).
        ``live`` is the host clock's one sample of the request while it
        ran (thread stacks, open spans, recent stalls), where it took one.

        ``perf`` carries the failing request's device-time attribution
        (padding / compile totals); left ``None`` the recorder pulls
        the perf ledger's last-dispatch snapshot itself, so span-layer
        callers need no knowledge of the ledger."""
        from stable_diffusion_webui_distributed_tpu.runtime.logging import (
            lines_for_request,
        )

        if perf is None:
            try:
                from stable_diffusion_webui_distributed_tpu.obs import (
                    perf as obs_perf,
                )

                perf = obs_perf.LEDGER.last_dispatch()
            except Exception:  # noqa: BLE001 — recorder must never fail
                perf = None
        entry = {
            "request_id": str(request_id),
            "reason": str(reason),
            "detail": str(detail),
            # wall clock, not perf_counter: postmortems are read next to
            # log files and dashboards, which speak wall time
            "recorded_at": time.time(),  # sdtpu-lint: wallclock
            "duration_s": float(duration_s),
            # None until a dispatch ran with SDTPU_PERF on
            "perf": perf,
            "spans": list(events),
            "logs": lines_for_request(request_id),
            "live": live,
            # what the detectors saw (satellite: postmortem enrichment)
            # — both None with the SDTPU_ALERTS / SDTPU_TSDB gates off
            "alerts": self._alert_snapshot(),
            "tsdb": self._tsdb_window(),
        }
        with self._lock:
            self._entries.append(entry)
        return entry

    @staticmethod
    def _alert_snapshot() -> Optional[Dict[str, Any]]:
        try:
            from stable_diffusion_webui_distributed_tpu.obs import (
                alerts as obs_alerts,
            )

            return obs_alerts.state_snapshot()
        except Exception:  # noqa: BLE001 — recorder must never fail
            return None

    @staticmethod
    def _tsdb_window() -> Optional[Dict[str, Any]]:
        try:
            from stable_diffusion_webui_distributed_tpu.obs import (
                tsdb as obs_tsdb,
            )

            return obs_tsdb.flight_window()
        except Exception:  # noqa: BLE001 — recorder must never fail
            return None

    def dump(self) -> Dict[str, Any]:
        """All retained entries, oldest first (the /internal/flightrec
        body)."""
        with self._lock:
            entries = list(self._entries)
            capacity = self._entries.maxlen
        return {"entries": entries, "capacity": capacity,
                "count": len(entries)}

    def dump_to_file(self, path: str) -> str:
        """Write :meth:`dump` as JSON (bench.py's on-error escape hatch)."""
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.dump(), f, indent=2, default=str)
        return path

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


#: Process-wide recorder (obs/spans.py feeds it; bench.py dumps it).
RECORDER = FlightRecorder()

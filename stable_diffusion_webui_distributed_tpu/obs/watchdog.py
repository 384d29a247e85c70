"""The host clock, and the hang watchdog for dispatches and remote jobs.

**On whenever spans are (``SDTPU_OBS``, the default): the clock.** Every
span and counter of the program lies INSIDE a request's tree. What lies
under all of them at once has no span: a stopped interpreter (a
collection, a C call that keeps the GIL, the process descheduled).
:class:`HostClock` is one ``StoppableDaemon`` a server (``ApiServer``
starts and stops it) that asks to wake every TICK_S and reads how late it
woke; a lag of STALL_S is a ``host.stall``, kept in a ring and added to
the tree of every request active then. The same tick counts the
collections its ``gc.callbacks`` entry stamped, and takes ONE sample
(every thread's stack, the open spans, the last stalls) of an active
request that is alive past the slow rule's ratio (obs/spans.py), which
the flight recorder keeps as the entry's ``live``. Its ``stats`` are
``serving.host`` of ``/internal/status`` (serving/metrics.py).

**Beside it, only while asked for: the device watcher.** A request's own
thread notes when an executable's output became ready only where it
happens to wait for it (obs/spans.py: ``device.run``). :class:`DeviceWatcher`
is one more ``StoppableDaemon`` the clock owns, whose thread exists from
the first dispatch it is handed: it blocks on each output in the order
they were enqueued and stamps the moment it returns. It is handed the
dispatches of a request the slow rule has flagged (whose sample carries
``device``: every dispatch it registered, ready or not), and every
dispatch while a profiler capture runs or ``/internal/trace.json
?device=1`` has armed it.

**Gated off by default: ``arm``.** The scheduler already *predicts* how
long a job should take (scheduler/eta.py); :func:`arm` starts a timer
thread around one operation with a known ETA, and if the operation has not
disarmed it after ``SDTPU_WATCHDOG_FACTOR`` x ETA seconds the watchdog

- captures a full thread-stack dump into the flight recorder
  (:mod:`.flightrec`) so the hang site is diagnosable post-mortem,
- bumps ``sdtpu_watchdog_stalls_total`` (:mod:`.prometheus`),
- journals a ``watchdog_stall`` event (:mod:`.journal`, when on), and
- invokes the caller's ``on_stall`` hook — ``World.execute`` uses it to
  abandon the stalled job thread so the slice falls into the existing
  ``_requeue_failed`` path.

``SDTPU_WATCHDOG_FACTOR`` <= 0 (the default 0) means :func:`arm` returns
``None`` and nothing is spawned. The arm/disarm shape mirrors
``WorkerNode._start_interrupt_watchdog``.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
import traceback
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional

from ..runtime.config import env_float
from ..runtime.daemon import StoppableDaemon
from ..serving.metrics import HostStats
from . import prometheus, spans

#: the clock asks to wake this often: what it can resolve, at 100 wake-ups
#: a second of tens of microseconds each
TICK_S = 0.010
#: a wake-up this late is a stall: twice the tick, over a loaded host's
#: scheduling jitter, under the shortest pause worth a line (PR 27: 30 ms)
STALL_S = 0.020
#: stalls kept for /internal/trace.json and a live sample: a 40 s window
#: that stalls more often has its story in the counters
STALL_RING = 64
#: stalls a live sample carries: enough to show one under the request
SAMPLE_STALLS = 8
#: the device watcher looks this often for work nobody woke it for
WATCH_IDLE_S = 1.0


def factor() -> float:
    """Stall threshold as a multiple of the operation's ETA; <= 0 = off.
    Re-read per call so tests can flip the env var."""
    return env_float("SDTPU_WATCHDOG_FACTOR", 0.0) or 0.0


def enabled() -> bool:
    return factor() > 0.0


def dump_stacks(max_frames: int = 40) -> str:
    """Format every live thread's stack (named, most frames first)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    chunks = []
    for tid, frame in sorted(sys._current_frames().items()):
        name = names.get(tid, "?")
        stack = "".join(traceback.format_stack(frame)[-max_frames:])
        chunks.append(f"Thread {name} (ident={tid}):\n{stack}")
    return "\n".join(chunks)


def arm(request_id: str, name: str, eta_s: Optional[float],
        on_stall: Optional[Callable[[], None]] = None,
        ) -> Optional[StoppableDaemon]:
    """Start watching one operation; returns the disarm handle, or
    ``None`` when the watchdog is off or no ETA is known. The caller
    MUST :func:`disarm` the returned handle from a ``finally`` block."""
    k = factor()
    if k <= 0.0 or not eta_s or eta_s <= 0.0:
        return None
    deadline_s = k * float(eta_s)

    def fire() -> None:
        _record_stall(request_id, name, float(eta_s), deadline_s)
        if on_stall is not None:
            try:
                on_stall()
            except Exception:
                pass

    timer = StoppableDaemon.one_shot(f"watchdog-{name}", deadline_s, fire)
    timer.start()
    return timer


def disarm(timer: Optional[StoppableDaemon]) -> None:
    if timer is not None:
        timer.halt()  # signal only: disarm runs on request hot paths


def _record_stall(request_id: str, name: str, eta_s: float,
                  waited_s: float) -> None:
    from . import flightrec, journal
    from . import prometheus as prom
    from ..runtime.logging import get_logger

    stacks = dump_stacks()
    prom.count_watchdog_stall(name)
    if journal.enabled():
        journal.emit("watchdog_stall", request_id or "", name=name,
                     eta_s=eta_s, waited_s=waited_s)
    get_logger().warning(
        "watchdog: %s stalled past %.2fs (%.2gx ETA %.2fs), request '%s'",
        name, waited_s, factor(), eta_s, request_id)
    flightrec.RECORDER.record(
        request_id or "", "watchdog_stall",
        f"{name} exceeded {factor():g}x ETA ({eta_s:.2f}s ETA, waited "
        f"{waited_s:.2f}s); thread stacks:\n{stacks}",
        events=[], duration_s=waited_s)


class DeviceWatcher:
    """See the module docstring. ``drain()`` runs inline in a test."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter) -> None:
        self._clock = clock
        #: dispatches to wait for, oldest first (deque operations are
        #: atomic: an enqueueing thread appends, the watcher pops)
        self._pending: Deque[spans.DeviceWork] = deque()
        self.stamped = 0    # only ``drain`` adds
        self._daemon = StoppableDaemon("device-watcher", self.drain,
                                       WATCH_IDLE_S)

    def put(self, work: "spans.DeviceWork") -> None:
        self._pending.append(work)
        self._daemon.start()
        self._daemon.wake()

    def follow(self, req: "spans.RequestTrace") -> None:
        """Every dispatch of ``req``, those it has yet to make too."""
        req.watched = True
        for work in list(req.works):
            if work.ready is None and work.output is not None:
                self.put(work)

    def drain(self) -> None:
        while self._pending:
            work = self._pending.popleft()
            output = work.output
            if work.ready is not None or output is None:
                continue
            try:
                output.block_until_ready()
            except RuntimeError:    # deleted under it: the fence has it
                continue
            spans.TRACER.settle(self._clock(), work, exact=True)
            self.stamped += 1

    def alive(self) -> bool:
        return self._daemon.alive()

    def stop(self) -> None:
        self._pending.clear()
        self._daemon.stop()


#: whose ``request_id`` the clock's own events carry
_HOST = spans.RequestTrace("host", "host", {})


class HostClock:
    """See the module docstring. ``tick()`` runs inline in a test, with
    ``clock`` injected; ``start()`` / ``stop()`` also register and remove
    the ``gc.callbacks`` entry, so a stopped clock leaves nothing behind."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter) -> None:
        self._clock = clock
        self._due: Optional[float] = None
        self.stats = HostStats()
        #: the last stalls as ``host.stall`` spans (attrs ``requests``,
        #: ``spans``), oldest first; only ``tick`` appends
        self.ring: Deque[spans.Span] = deque(maxlen=STALL_RING)
        #: (seconds, generation) a collection: ALL the callback shares
        #: with ``tick`` (deque appends are atomic)
        self._collections: Deque[tuple] = deque()
        self._gc_t0 = 0.0
        self.watcher = DeviceWatcher(clock)
        self._daemon = StoppableDaemon("host-clock", self.tick, TICK_S,
                                       immediate=False)

    def start(self) -> "HostClock":
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        self._due = None
        spans.TRACER.watcher = self.watcher
        self._daemon.start()
        return self

    def stop(self) -> None:
        self._daemon.stop()
        if spans.TRACER.watcher is self.watcher:
            spans.TRACER.watcher = None
        self.watcher.stop()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        """Takes no lock and calls nothing of the tracer: a collection can
        begin inside ``SpanTracer.record`` with its lock held."""
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self._collections.append(
                (time.perf_counter() - self._gc_t0, info["generation"]))

    def tick(self) -> None:
        now = self._clock()
        lag = 0.0 if self._due is None else now - self._due
        active, late = spans.TRACER.watch(now)
        stalled = lag >= STALL_S
        self.stats.ticked(lag if stalled else 0.0)
        if stalled:
            prometheus.count_host_stall(lag)
            innermost = [max(req.open.copy().values(), default=req,
                             key=lambda sp: sp.t0).name for req in active]
            self.ring.append(spans.Span(
                next(spans._IDS), None, "host.stall", self._due, lag,
                threading.get_ident(),
                {"requests": [req.request_id for req in active],
                 "spans": innermost, "alive": len(active)}))
            for req in active:      # cut to where the request began
                start = max(self._due, req.t0)
                spans.add_span(req, "host.stall", start, now - start)
        while self._collections:
            seconds, generation = self._collections.popleft()
            self.stats.collected(generation, seconds)
            prometheus.count_gc_pause(generation, seconds)
        for req in late:
            req.live = self._sample(req, now)
            self.watcher.follow(req)
        self._due = self._clock() + TICK_S

    def _sample(self, req: Any, now: float) -> Dict[str, Any]:
        """Every thread's stack, the request's open spans (innermost
        first, each with the thread that opened it), the last stalls and
        the executables it enqueued, each ready by now or not (the
        request's end fills in when the rest were)."""
        spans_open = sorted(req.open.copy().values(), key=lambda sp: -sp.t0)
        spans.TRACER.settle(time.perf_counter())  # the spans' clock
        return {"age_ms": (now - req.t0) * 1e3,
                "device": spans.device_sample(req),
                "open": [{"name": sp.name, "age_ms": (now - sp.t0) * 1e3,
                          "thread": sp.tid} for sp in spans_open],
                "stacks": dump_stacks(),
                "stalls": self.events()[-SAMPLE_STALLS:]}

    def events(self) -> List[Dict[str, Any]]:
        """The ring as Chrome complete events on the clock's own ``tid``
        (``request_id`` "host": the process's own row of a by-request
        reading), for ``/internal/trace.json`` and a live sample."""
        return [spans._span_event(_HOST, sp) for sp in list(self.ring)]

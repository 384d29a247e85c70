"""Per-request span trees behind a ``contextvars`` request context.

One :class:`SpanTracer` (the module singleton :data:`TRACER`) holds every
in-flight and recently-finished request trace. A request context is minted
at API ingress (or lazily by the serving dispatcher for direct callers) via
:func:`request`; any code on that thread — or on a thread entered through
:func:`bind_current` — can then open child spans with :func:`span`, and
``runtime/trace.py`` runs every ``StageStats.timer`` block as one.

Coalesced dispatches link leader and followers: a follower's wait on its
leader is its own live span (``coalesced.wait``, serving/dispatcher.py)
carrying ``leader_request_id`` / ``leader_span_id``, so a follower's tree
still shows where its wall-clock went even though another request drove the
TPU.

Timing is host-side ``time.perf_counter()`` only — recording a span never
syncs the device. While a ``jax.profiler`` capture runs (whoever started
it), every span opened through :func:`request` or :func:`span` is also a
``TraceAnnotation`` named ``sdtpu:<span name>`` carrying ``request_id`` and
``span_id``, so the capture holds the span tree on its host planes, on the
profiler's clock; with no capture running that costs one flag check. A wait
that ends inside another context manager's body is opened and closed by hand
(:func:`open_span` / :func:`close_span`). Intervals recorded after the fact
(:func:`add_span`) exist only here, as do the stalls the host clock finds
(obs/watchdog.py: ``host.stall``), which can also name the spans a request
has OPEN. What the DEVICE did with each executable a request enqueued is
in the same store, on the same clock, with no profiler (:func:`device_work`:
a ``device.run`` span an executable, from where it could start to where
its output was ready). The store is bounded (``SDTPU_OBS_MAX_REQUESTS``
finished traces) and lock-disciplined: one lock for the trees and one for
the device's queue, never both, nothing external called while holding
either. Export is Chrome trace-event JSON ("X" complete events with
ph/ts/dur/pid/tid; a ``device.run`` ran on no host thread and is a pair of
async events, "b" and "e"), loadable in Perfetto / ``chrome://tracing``.
"""

from __future__ import annotations

import contextlib
import contextvars
import hashlib
import itertools
import math
import os
import statistics
import threading
import time
import uuid
from collections import deque
from typing import Any, Deque, Dict, Iterator, List, Optional, Tuple

from stable_diffusion_webui_distributed_tpu.obs import flightrec, prometheus
from stable_diffusion_webui_distributed_tpu.runtime.config import (
    env_flag, env_float, env_int,
)

#: Finished request traces retained for /internal/trace.json.
DEFAULT_MAX_REQUESTS = 256
#: e2e latency (seconds) above which a request is flight-recorded as a
#: slow outlier; 0 disables slow capture (the rule below too).
DEFAULT_SLOW_S = 30.0
#: A request is also slow when its root exceeds SLOW_RATIO x the median of
#: the last SLOW_WINDOW ``ok`` requests of its class (root name, ``width``,
#: ``height``, ``steps`` of the root's attrs); never before SLOW_MIN_SAMPLES
#: of them (three: with the warm-up's two, the first of which holds the
#: program loads, a window's first request already makes the steady request
#: the median), never for a root without the three attrs. The host clock
#: holds an ACTIVE request to the same rule (:meth:`SpanTracer.watch`).
SLOW_RATIO = 1.5
SLOW_WINDOW = 32
SLOW_MIN_SAMPLES = 3
#: classes whose durations are kept (the oldest goes first)
SLOW_MAX_CLASSES = 64

#: perf_counter base for trace-event timestamps (µs since process start of
#: tracing, not wall clock — Perfetto only needs a shared monotonic base).
_EPOCH = time.perf_counter()
_PID = os.getpid()
#: the ``tid`` of a span that ran on the device, on no host thread
DEVICE_TID = 0
#: a parent's attrs that its ``device.run`` repeats: the work it stands for
_WORK_ATTRS = ("steps", "tokens")
#: the spans whose time less the ``device.run`` inside is the device's idle
#: time in a request (``serving.device`` ``idle_s``): the dispatcher's
#: device section, or for a caller of the engine itself its range
_DEVICE_SECTIONS = ("dispatch.device", "generate_range")

#: Process-wide span-id allocator. ``next()`` on itertools.count is atomic
#: under the GIL, so ids are unique without touching the tracer lock.
_IDS = itertools.count(1)

#: ``jax.profiler.TraceAnnotation``, imported by the first span: jax is
#: heavy and this module is imported by code that never traces.
_ANNOTATION = None


def _annotate(name: str, **meta: Any):
    """An entered ``sdtpu:<name>`` annotation while a profiler capture is
    running, else None (a flag check)."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    if not _ANNOTATION.is_enabled():
        return None
    ann = _ANNOTATION("sdtpu:" + name, **meta)
    ann.__enter__()
    return ann


#: (RequestTrace, parent span id) for the code currently executing, or None
#: outside any request. Thread- and contextvars-scoped: HTTP handler
#: threads each see only their own request. (The id is None only while
#: ``http.respond`` opens beside the root.)
_CURRENT: "contextvars.ContextVar[Optional[Tuple[RequestTrace, int]]]" = \
    contextvars.ContextVar("sdtpu_obs_request", default=None)  # sdtpu-lint: metric

#: The HTTP exchange this thread is serving (:class:`Exchange`), or None.
_EXCHANGE: "contextvars.ContextVar[Optional[Exchange]]" = \
    contextvars.ContextVar("sdtpu_obs_exchange", default=None)  # sdtpu-lint: metric


class Span:
    """One timed region. ``t0`` is perf_counter seconds, ``dur`` seconds."""

    __slots__ = ("span_id", "parent_id", "name", "t0", "dur", "tid", "attrs")

    def __init__(self, span_id: int, parent_id: Optional[int], name: str,
                 t0: float, dur: float, tid: int,
                 attrs: Dict[str, Any]) -> None:
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.t0 = t0
        self.dur = dur
        self.tid = tid
        self.attrs = attrs


class RequestTrace:
    """All spans of one request plus its terminal status."""

    __slots__ = ("request_id", "name", "attrs", "t0", "dur", "status",
                 "detail", "spans", "root_id", "open", "live", "works",
                 "watched", "fences")

    def __init__(self, request_id: str, name: str,
                 attrs: Dict[str, Any]) -> None:
        self.request_id = request_id
        self.name = name
        self.attrs = attrs
        self.t0 = time.perf_counter()
        self.dur = 0.0
        self.status = "active"  # active | ok | error | interrupted | slow
        self.detail = ""
        self.spans: List[Span] = []  # appended under TRACER's lock
        self.root_id = next(_IDS)
        #: the spans open now, by id: :func:`open_span` fills and
        #: :func:`close_span` empties it (a dict operation each under the
        #: GIL, no lock); the host clock reads a copy, and leaves its ONE
        #: sample of a request alive past the slow rule's ratio in ``live``
        self.open: Dict[int, Span] = {}
        self.live: Optional[Dict[str, Any]] = None
        #: the executables it enqueued, in order (:func:`device_work`;
        #: appended on the thread that enqueues), whether the device
        #: watcher follows them (the slow rule's doing), and its fences:
        #: [all, those the host reached after the device]
        self.works: List[DeviceWork] = []
        self.watched = False
        self.fences = [0, 0]


def _span_event(req: RequestTrace, sp: Span) -> Dict[str, Any]:
    """One Chrome trace-event ("X" = complete event, timestamps in µs)."""
    args: Dict[str, Any] = {"request_id": req.request_id,
                            "span_id": sp.span_id}
    if sp.parent_id is not None:
        args["parent_id"] = sp.parent_id
    for k, v in sp.attrs.items():
        args.setdefault(str(k), v)
    return {
        "ph": "X",
        "cat": "sdtpu",
        "name": sp.name,
        "pid": _PID,
        "tid": sp.tid,
        "ts": (sp.t0 - _EPOCH) * 1e6,
        "dur": sp.dur * 1e6,
        "args": args,
    }


def _span_events(req: RequestTrace, sp: Span) -> List[Dict[str, Any]]:
    """A span as Chrome trace events: one complete event, or for a span
    that ran on the device (``tid`` DEVICE_TID) a nestable async pair. The
    host's spans nest on their threads and a reader may take every "X"
    event of a request for host time (``request_unspanned_ms`` does); a
    ``device.run`` overlaps them all. The "b" event carries ``dur`` too,
    so a reader need not pair them, and the "e" event a ``dur`` of 0 for
    one that adds ``ts`` and ``dur`` of whatever it is given."""
    event = _span_event(req, sp)
    if sp.tid != DEVICE_TID:
        return [event]
    event.update(ph="b", cat="sdtpu.device", id=sp.span_id)
    return [event, {"ph": "e", "cat": "sdtpu.device", "name": sp.name,
                    "pid": _PID, "tid": sp.tid, "id": sp.span_id,
                    "ts": event["ts"] + event["dur"], "dur": 0.0,
                    "args": {"request_id": req.request_id}}]


class SpanTracer:
    """Bounded, lock-disciplined store of request traces."""

    def __init__(self, enabled: Optional[bool] = None,
                 max_requests: Optional[int] = None,
                 slow_s: Optional[float] = None) -> None:
        if enabled is None:
            enabled = env_flag("SDTPU_OBS", True)
        if max_requests is None:
            max_requests = env_int("SDTPU_OBS_MAX_REQUESTS",
                                   DEFAULT_MAX_REQUESTS)
        if slow_s is None:
            slow_s = env_float("SDTPU_OBS_SLOW_S", DEFAULT_SLOW_S)
        #: set once at construction; tests flip it
        self.enabled = bool(enabled)
        self.slow_s = max(0.0, float(slow_s or 0.0))
        self._lock = threading.Lock()
        self._active: Dict[str, RequestTrace] = {}  # guarded-by: _lock
        self._done: Deque[RequestTrace] = deque(
            maxlen=max(1, int(max_requests or DEFAULT_MAX_REQUESTS)))  # guarded-by: _lock
        #: class -> durations of its last ``ok`` requests, and their median
        #: from SLOW_MIN_SAMPLES on: taken where a duration joins, so the
        #: host clock's tick compares one float a request
        self._ok_durs: Dict[tuple, Deque[float]] = {}  # guarded-by: _lock
        self._medians: Dict[tuple, float] = {}  # guarded-by: _lock
        #: the device's queue as the program knows it: the executables
        #: enqueued whose output has no ready stamp yet, oldest first, and
        #: the newest one enqueued (whose stamp the next one starts from)
        self._device_lock = threading.Lock()
        self._queue: Deque[DeviceWork] = deque()  # guarded-by: _device_lock
        self._newest: Optional[DeviceWork] = None  # guarded-by: _device_lock
        #: obs/watchdog.py's DeviceWatcher while a host clock runs, and
        #: whether it is to stamp EVERY dispatch (``/internal/trace.json
        #: ?device=1``); a profiler capture and the slow rule ask by
        #: themselves
        self.watcher: Any = None
        self.armed = False

    # -- store ------------------------------------------------------------

    def open(self, req: RequestTrace) -> None:
        with self._lock:
            self._active[req.request_id] = req

    def close(self, req: RequestTrace) -> None:
        with self._lock:
            self._active.pop(req.request_id, None)
            self._done.append(req)

    def record(self, req: Optional[RequestTrace], sp: Span) -> None:
        """Append a finished span to a trace (any thread)."""
        if req is None or not self.enabled:
            return
        with self._lock:
            req.spans.append(sp)

    def clear(self) -> None:
        with self._lock:
            self._active.clear()
            self._done.clear()
            self._ok_durs.clear()
            self._medians.clear()
        with self._device_lock:
            self._queue.clear()
            self._newest = None

    # -- the device's side ------------------------------------------------

    def settle(self, now: float, work: Optional["DeviceWork"] = None,
               exact: bool = False) -> bool:
        """``work``'s output was ready at ``now`` (where it has no stamp
        yet; ``exact``: a wait just returned), and so, by ``now`` at the
        latest, was every output at the queue's head that says it is
        ready: the device runs what it is given in order. Each gets its
        ``device.run``. Returns whether everything enqueued is now known
        to be done (the device has run dry)."""
        done: List[DeviceWork] = []
        with self._device_lock:
            if work is not None and work.ready is None:
                work.ready, work.exact = now, exact
            queue = self._queue
            while queue:
                head = queue[0]
                if head.ready is None:
                    if not _is_ready(head.output):
                        break
                    head.ready = now
                done.append(queue.popleft())
            if work is not None and work in queue:
                # stamped behind one that cannot be told (another device)
                queue.remove(work)
                done.append(work)
            dry = not queue
        for one in done:
            one.ran(self)
        return dry

    def enqueued(self, work: "DeviceWork") -> None:
        with self._device_lock:
            work.prev, self._newest = self._newest, work
            self._queue.append(work)

    @staticmethod
    def _class(req: RequestTrace) -> Optional[tuple]:
        """The slow rule's class of a request; None without the attrs."""
        shape = tuple(req.attrs.get(k) for k in ("width", "height", "steps"))
        return None if None in shape else (req.name,) + shape

    def slow_detail(self, req: RequestTrace) -> Optional[str]:
        """Why a finished request counts as slow, or None; the duration of
        one that does not joins its class's window."""
        if self.slow_s <= 0:
            return None
        if req.dur >= self.slow_s:
            return f"e2e {req.dur:.3f}s >= {self.slow_s:.3f}s threshold"
        key = self._class(req)
        if key is None:
            return None
        with self._lock:
            recent = self._ok_durs.get(key)
            if recent is None:
                if len(self._ok_durs) >= SLOW_MAX_CLASSES:
                    self._medians.pop(next(iter(self._ok_durs)), None)
                    self._ok_durs.pop(next(iter(self._ok_durs)))
                recent = self._ok_durs[key] = deque(maxlen=SLOW_WINDOW)
            median = self._medians.get(key, math.inf)
            if req.dur > SLOW_RATIO * median:
                return (f"e2e {req.dur:.3f}s > {SLOW_RATIO} x median "
                        f"{median:.3f}s of the last {len(recent)} ok "
                        f"{req.name} {key[1]}x{key[2]} {key[3]} steps")
            recent.append(req.dur)
            if len(recent) >= SLOW_MIN_SAMPLES:
                self._medians[key] = statistics.median(recent)
        return None

    def watch(self, now: float) -> Tuple[List[RequestTrace],
                                         List[RequestTrace]]:
        """For the host clock (obs/watchdog.py): the active requests, and
        those of them alive by ``now`` for longer than the rule lets a
        finished one take, that hold no sample yet."""
        with self._lock:
            active = list(self._active.values())
            late = [] if self.slow_s <= 0 else [
                req for req in active if req.live is None
                and now - req.t0 > SLOW_RATIO * self._medians.get(
                    self._class(req), math.inf)]
        return active, late

    # -- export -----------------------------------------------------------

    def export_chrome(self) -> Dict[str, Any]:
        """All retained traces as a Chrome trace-event JSON object."""
        events: List[Dict[str, Any]] = []
        with self._lock:
            reqs = list(self._done) + list(self._active.values())
            for req in reqs:
                for sp in req.spans:
                    events.extend(_span_events(req, sp))
        # clock_us lets a remote puller (obs/stitch.py) estimate this
        # process's trace-clock offset from one RTT-bracketed fetch.
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "clock_us": now_us()}

    def events_for(self, req: RequestTrace) -> List[Dict[str, Any]]:
        with self._lock:
            return [event for sp in req.spans
                    for event in _span_events(req, sp)]

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "enabled": self.enabled,
                "active": len(self._active),
                "retained": len(self._done),
                "capacity": self._done.maxlen,
                "slow_threshold_s": self.slow_s,
            }

    def finished(self) -> List[RequestTrace]:
        with self._lock:
            return list(self._done)


#: Process-wide tracer (mirrors trace.STATS / metrics.METRICS).
TRACER = SpanTracer()


# -- request / span context managers ----------------------------------------

@contextlib.contextmanager
def request(request_id: Optional[str] = None, name: str = "request",
            **attrs: Any) -> Iterator[Optional[RequestTrace]]:
    """Root context for one request. Mints/propagates the request id, opens
    the root span, and on exit records e2e latency, feeds the e2e histogram
    and hands failed/interrupted/slow traces to the flight recorder."""
    tr = TRACER
    if not tr.enabled:
        yield None
        return
    rid = str(request_id or uuid.uuid4().hex)
    req = RequestTrace(rid, name, dict(attrs))
    tr.open(req)
    exchange = _EXCHANGE.get()
    if exchange is not None:
        exchange.adopt(req)
    token = _CURRENT.set((req, req.root_id))
    ann = _annotate(name, request_id=rid, span_id=req.root_id)
    error: Optional[str] = None
    try:
        yield req
    except BaseException as e:  # noqa: BLE001 — recorded, then re-raised
        error = f"{type(e).__name__}: {e}"
        raise
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)
        _CURRENT.reset(token)
        _finish(tr, req, error)


def _finish(tr: SpanTracer, req: RequestTrace, error: Optional[str]) -> None:
    req.dur = time.perf_counter() - req.t0
    if error is not None:
        req.status, req.detail = "error", error
    elif req.status == "interrupted":
        pass  # marked mid-flight by cancel/interrupt
    else:
        slow = tr.slow_detail(req)
        if slow is None:
            req.status = "ok"
        else:
            req.status, req.detail = "slow", slow
    root = Span(req.root_id, None, req.name, req.t0, req.dur,
                threading.get_ident(), dict(req.attrs, status=req.status))
    tr.record(req, root)
    if req.works:
        _account_device(tr, req)
    tr.close(req)
    prometheus.observe_hist("e2e", req.dur)
    if req.status != "ok":
        flightrec.RECORDER.record(
            request_id=req.request_id, reason=req.status, detail=req.detail,
            duration_s=req.dur, events=tr.events_for(req), live=req.live)


def open_span(name: str, t0: Optional[float] = None, **attrs: Any):
    """Open a child span under the active request WITHOUT a ``with`` block:
    for a wait that ends inside another context manager's body (the
    dispatcher's ``queue_wait`` and ``engine.wait`` end inside
    ``_device()``). Returns the handle :func:`close_span` takes, None
    outside a request. Hand-opened spans close on the thread that opened
    them, innermost first, like nested ``with`` blocks. ``t0`` backdates
    the span's recorded start (the annotation starts now)."""
    tr = TRACER
    ctx = _CURRENT.get()
    if ctx is None or not tr.enabled:
        return None
    req, parent = ctx
    sp = Span(next(_IDS), parent, name,
              time.perf_counter() if t0 is None else t0, 0.0,
              threading.get_ident(), attrs)
    token = _CURRENT.set((req, sp.span_id))
    req.open[sp.span_id] = sp
    ann = _annotate(name, request_id=req.request_id, span_id=sp.span_id)
    return sp, req, token, ann


def close_span(handle) -> None:
    """End a span :func:`open_span` opened and record it."""
    if handle is None:
        return
    sp, req, token, ann = handle
    if ann is not None:
        ann.__exit__(None, None, None)
    _CURRENT.reset(token)
    sp.dur = time.perf_counter() - sp.t0
    req.open.pop(sp.span_id, None)
    TRACER.record(req, sp)


@contextlib.contextmanager
def span(name: str, **attrs: Any) -> Iterator[Optional[Span]]:
    """Child span under the active request; cheap no-op outside one."""
    handle = open_span(name, **attrs)
    try:
        yield None if handle is None else handle[0]
    finally:
        close_span(handle)


@contextlib.contextmanager
def maybe_request(request_id: Optional[str] = None, name: str = "request",
                  **attrs: Any) -> Iterator[Optional[RequestTrace]]:
    """:func:`request` unless one is already active (the HTTP ingress minted
    it); then just yield the active trace. Lets the dispatcher serve both
    API traffic and direct callers without double-rooting."""
    ctx = _CURRENT.get()
    if ctx is not None:
        yield ctx[0]
        return
    with request(request_id, name, **attrs) as req:
        yield req


# -- the HTTP exchange around a request ---------------------------------------

class Accept:
    """``http.accept`` of a connection's first request: from the accept
    (``stamp``, read on the accept thread) to where ``http.read_parse``
    begins: the handler thread's start and the standard library's read of
    the request line and headers. Made as the handler's first act on its
    own thread: the annotation begins there, the record at the stamp. No
    request id exists yet: the annotation carries the ``span_id`` alone."""

    __slots__ = ("t0", "started", "span_id", "_ann")

    def __init__(self, stamp: float) -> None:
        self.t0 = stamp
        self.started = time.perf_counter()
        self.span_id = next(_IDS)
        self._ann = _annotate("http.accept", span_id=self.span_id)

    def close(self) -> None:
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)


class Exchange:
    """The intervals of one HTTP exchange that lie outside the root span:
    ``http.between`` (where the exchange found the server empty: from the
    end of the last exchange in flight, ``between`` seconds before this one
    began) and ``http.accept`` (a connection's first request only) end
    where ``http.read_parse`` starts, that ends where the root starts (the
    trace, and the request id, exist only from there), ``http.respond``
    starts after it has closed. All are recorded into the request's trace
    with no parent, beside the root; the finished trace stays in the
    store, so the export holds them."""

    __slots__ = ("t0", "req", "attrs", "accept", "between", "_ann")

    def __init__(self, accept: Optional[Accept] = None,
                 between: Optional[float] = None) -> None:
        self.t0 = time.perf_counter()
        self.req: Optional[RequestTrace] = None
        #: of ``http.read_parse`` (the handler notes the body's ``bytes``,
        #: and ``reused`` on a kept connection's later request)
        self.attrs: Dict[str, Any] = {}
        self.accept, self.between = accept, between
        if accept is not None:
            accept.close()
        self._ann = _annotate("http.read_parse")

    def adopt(self, req: RequestTrace) -> None:
        """``req`` is the request this exchange carries: its root has just
        opened, so ``http.read_parse`` ends here."""
        if self.req is not None:
            return      # a second root on this thread is not the exchange's
        self.req = req
        tid = threading.get_ident()
        sp = Span(next(_IDS), None, "http.read_parse", self.t0,
                  req.t0 - self.t0, tid, self.attrs)
        if self._ann is not None:
            self._ann.set_metadata(request_id=req.request_id,
                                   span_id=sp.span_id)
        self.close_read()
        TRACER.record(req, sp)
        acc, began = self.accept, self.t0
        if acc is not None:
            began = acc.t0
            TRACER.record(req, Span(
                acc.span_id, None, "http.accept", began, self.t0 - began,
                tid, {"thread_start_ms": (acc.started - began) * 1e3}))
        if self.between is not None:    # found after the fact: no annotation
            TRACER.record(req, Span(
                next(_IDS), None, "http.between", began - self.between,
                self.between, tid, {}))

    def close_read(self) -> None:
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)


@contextlib.contextmanager
def http_exchange(accept: Optional[Accept] = None,
                  between: Optional[float] = None
                  ) -> Iterator[Optional[Exchange]]:
    """Around one HTTP handler call (server/api.py ``_dispatch``), from
    before the body is read until the response is written; ``accept`` is
    the connection's :class:`Accept` on its first request, ``between`` the
    seconds the server had been empty. Records nothing unless the handler
    mints a request."""
    if not TRACER.enabled:
        yield None
        return
    exchange = Exchange(accept, between)
    token = _EXCHANGE.set(exchange)
    try:
        yield exchange
    finally:
        _EXCHANGE.reset(token)
        exchange.close_read()


@contextlib.contextmanager
def http_respond() -> Iterator[Optional[Span]]:
    """``http.respond`` of the exchange's request: serialising and writing
    the response, each a child span (:func:`span` works inside). A no-op
    when this exchange minted no request."""
    exchange = _EXCHANGE.get()
    req = None if exchange is None else exchange.req
    if req is None:
        yield None
        return
    # the request's context again, under no parent: beside the root
    token = _CURRENT.set((req, None))
    try:
        with span("http.respond") as sp:
            yield sp
    finally:
        _CURRENT.reset(token)


# -- cross-thread / cross-request recording ----------------------------------

def now_us() -> float:
    """Current trace-clock reading (µs on the same base as event ``ts``)."""
    return (time.perf_counter() - _EPOCH) * 1e6


def traceparent() -> Optional[str]:
    """W3C traceparent for the active request (trace id derived from the
    request id so every hop agrees without coordination), or None outside
    a request context."""
    ctx = _CURRENT.get()
    if ctx is None:
        return None
    req, parent = ctx
    trace_id = hashlib.sha256(req.request_id.encode("utf-8")).hexdigest()[:32]
    span_id = f"{parent & ((1 << 64) - 1):016x}"
    return f"00-{trace_id}-{span_id}-01"


def current() -> Optional[RequestTrace]:
    ctx = _CURRENT.get()
    return None if ctx is None else ctx[0]


def current_request_id() -> Optional[str]:
    ctx = _CURRENT.get()
    return None if ctx is None else ctx[0].request_id


def add_span(req: Optional[RequestTrace], name: str, t0: float, dur: float,
             attrs: Optional[Dict[str, Any]] = None,
             parent_id: Optional[int] = None) -> Optional[Span]:
    """Record an already-measured interval into ``req`` from any thread
    (the coalesce leader records queue waits for its followers, the host
    clock its stalls)."""
    if req is None or not TRACER.enabled:
        return None
    sp = Span(next(_IDS), req.root_id if parent_id is None else parent_id,
              name, t0, max(0.0, dur), threading.get_ident(),
              dict(attrs or {}))
    TRACER.record(req, sp)
    return sp


def add_child(name: str, seconds: float, **attrs: Any) -> Optional[Span]:
    """Record an interval that ended just now as a child of the span open
    on this thread (serving/metrics.py: the compile listener hears of an
    executable only when it is made). A no-op outside a request."""
    ctx = _CURRENT.get()
    if ctx is None:
        return None
    req, parent = ctx
    return add_span(req, name, time.perf_counter() - seconds, seconds,
                    attrs, parent_id=parent)


# -- the device's side of a request -------------------------------------------

def _is_ready(output: Any) -> bool:
    """Whether the device has made ``output``, without waiting. One that
    its request let go at its end (None: nobody will wait for it) or the
    next call deleted (which :meth:`DeviceWork.queued` is told never to be
    handed) is done with as far as the queue is concerned."""
    if output is None:
        return True
    try:
        return output.is_ready()
    except RuntimeError:
        return True


def _capturing() -> bool:
    return _ANNOTATION is not None and _ANNOTATION.is_enabled()


def _open_span() -> Optional[Span]:
    """The innermost span open on this thread."""
    ctx = _CURRENT.get()
    return None if ctx is None else ctx[0].open.get(ctx[1])


class DeviceWork:
    """One executable a request enqueues, from before its call
    (:func:`device_work`) to the stamp of when its output was ready.

    The stamp is ``exact`` where a thread was blocked on the output when it
    came (the request's own fence, :func:`fence`, or the device watcher,
    obs/watchdog.py) and else a bound: the first moment after it at which
    some thread asked (``is_ready()`` at the next enqueue or fence). The
    ``device.run`` span it leaves runs from where the device could start
    (the call's return, or the stamp of the executable enqueued before it,
    whichever is later) to the stamp."""

    __slots__ = ("kind", "req", "span", "dry", "queued_at", "output",
                 "ready", "exact", "prev", "run")

    def __init__(self, kind: str, req: RequestTrace, span: Optional[Span],
                 dry: bool) -> None:
        self.kind, self.req, self.span, self.dry = kind, req, span, dry
        self.queued_at = 0.0
        self.output: Any = None
        self.ready: Optional[float] = None
        self.exact = False
        self.prev: Optional[DeviceWork] = None
        self.run: Optional[Span] = None

    def queued(self, output: Any, watch: bool = True) -> None:
        """The call has returned. ``output`` is one array it made that NO
        later call is given to donate (the chunk's fence, not its carry):
        it is asked ``is_ready()`` from any thread until it is, and with
        ``watch`` the device watcher may block on it. ``watch=False`` for
        an output that is donated AFTER this thread has fenced it inline
        (a fork's rows): nobody touches it once it is stamped."""
        deleted = getattr(output, "is_deleted", None)
        if deleted is not None and deleted():
            raise ValueError(
                f"device_work({self.kind!r}): the output is already "
                "deleted (donated into a later call); register an output "
                "that no call donates")
        self.queued_at = time.perf_counter()
        self.output = output
        self.req.works.append(self)
        tr = TRACER
        tr.enqueued(self)
        watcher = tr.watcher
        if watch and watcher is not None and (
                tr.armed or self.req.watched or _capturing()):
            watcher.put(self)

    def ran(self, tr: SpanTracer) -> None:
        """Its ``device.run``, under the span that enqueued it."""
        prev, self.prev = self.prev, None
        start = self.queued_at
        if prev is not None and prev.ready is not None:
            start = max(start, prev.ready)
        start = min(start, self.ready)
        parent = self.span
        attrs = {"kind": self.kind, "dry": self.dry,
                 "exact" if self.exact else "bound": True}
        if parent is not None:
            attrs.update((k, parent.attrs[k]) for k in _WORK_ATTRS
                         if k in parent.attrs)
        self.run = Span(
            next(_IDS), self.req.root_id if parent is None
            else parent.span_id, "device.run", start, self.ready - start,
            DEVICE_TID, attrs)
        tr.record(self.req, self.run)

    def sample(self) -> Dict[str, Any]:
        """A row of a ``live`` sample's ``device``."""
        t0 = self.req.t0
        return {"kind": self.kind,
                "enqueued_ms": (self.queued_at - t0) * 1e3,
                "ready": self.ready is not None,
                "ready_ms": None if self.ready is None
                else (self.ready - t0) * 1e3,
                "exact": self.exact}


class _Fence:
    """Around a wait on a dispatch's output (:func:`fence`)."""

    __slots__ = ("work",)

    def __init__(self, work: DeviceWork) -> None:
        self.work = work

    def __enter__(self) -> None:
        work = self.work
        TRACER.settle(time.perf_counter())
        late = work.ready is not None
        fences = work.req.fences
        fences[0] += 1
        fences[1] += late
        sp = _open_span()
        if sp is not None:
            sp.attrs["late"] = late

    def __exit__(self, *exc: Any) -> None:
        if self.work.ready is None:
            TRACER.settle(time.perf_counter(), self.work, exact=True)


class _NoWork:
    """What an enqueue site and a fence hold outside a request."""

    def queued(self, output: Any, watch: bool = True) -> None:
        pass

    def __enter__(self) -> None:
        pass

    def __exit__(self, *exc: Any) -> None:
        pass


_NO_WORK = _NoWork()


def device_work(kind: str):
    """BEFORE an executable of the request path is enqueued, inside the
    span that wraps the enqueue: notes on that span, and on the
    ``device.run`` to come, whether the device had run ``dry`` (everything
    enqueued before is done: it waits for the host), and returns the
    handle whose :meth:`DeviceWork.queued` takes the call's output.
    Outside a request, nothing."""
    tr = TRACER
    ctx = _CURRENT.get()
    if ctx is None or not tr.enabled:
        return _NO_WORK
    req, parent = ctx
    dry = tr.settle(time.perf_counter())
    sp = req.open.get(parent)
    if sp is not None:      # a span that enqueues several: any of them
        sp.attrs["dry"] = dry or sp.attrs.get("dry", False)
    return DeviceWork(kind, req, sp, dry)


def fence(output: Any):
    """Context manager around a wait for ``output``, the array a
    :meth:`DeviceWork.queued` of this request took: it asks ``is_ready()``
    first (``late`` on the open span: the device was done before the host
    came, and the stamp it has is all that is known) and else stamps the
    moment the wait returns."""
    req = current()
    if req is not None:
        for work in reversed(req.works):
            if work.output is output:
                return _Fence(work)
    return _NO_WORK


def device_sample(req: RequestTrace) -> List[Dict[str, Any]]:
    """Every dispatch ``req`` has registered: kind, when, ready or not."""
    return [work.sample() for work in list(req.works)]


def _uncovered(sections: List[Span], runs: List[Span]) -> float:
    """Seconds of ``sections`` that no interval of ``runs`` covers."""
    idle = 0.0
    runs = sorted(runs, key=lambda sp: sp.t0)
    for section in sections:
        reach, end = section.t0, section.t0 + section.dur
        for run in runs:
            if run.t0 > reach:
                idle += min(run.t0, end) - reach
            reach = max(reach, min(run.t0 + run.dur, end))
            if reach >= end:
                break
        idle += max(0.0, end - reach)
    return idle


def _account_device(tr: SpanTracer, req: RequestTrace) -> None:
    """At a request's end: its dispatches to ``serving.device`` and the
    two Prometheus families, and its arrays let go."""
    # lazy: serving/ imports this module
    from stable_diffusion_webui_distributed_tpu.serving.metrics import DEVICE

    tr.settle(time.perf_counter())
    works = list(req.works)
    runs = [work.run for work in works if work.run is not None]
    busy: Dict[str, float] = {}
    for run in runs:
        kind = run.attrs["kind"]
        busy[kind] = busy.get(kind, 0.0) + run.dur
    dispatches: Dict[str, int] = {}
    for work in works:
        dispatches[work.kind] = dispatches.get(work.kind, 0) + 1
        work.output = None
    dry = sum(work.dry for work in works)
    with tr._lock:
        spans_now = list(req.spans)
    sections: List[Span] = []
    for name in _DEVICE_SECTIONS:
        sections = sections or [sp for sp in spans_now if sp.name == name]
    DEVICE.record(dispatches=dispatches, busy_s=busy, dry_enqueues=dry,
                  fences=req.fences[0], late_fences=req.fences[1],
                  idle_s=_uncovered(sections, runs))
    prometheus.count_device(busy, dry)
    if req.live is not None:
        at_sample = req.live.get("device", ())
        req.live["device"] = rows = device_sample(req)
        for row, was in zip(rows, at_sample):
            row["ready_at_sample"] = was["ready"]


def mark(req: Optional[RequestTrace], status: str, detail: str = "") -> None:
    """Flag an in-flight request (e.g. "interrupted"); picked up when its
    root context exits."""
    if req is None:
        return
    req.status = status
    if detail:
        req.detail = detail


def bind_current(fn):
    """Wrap ``fn`` so it runs under the caller's request context in another
    thread (contextvars don't cross thread starts on their own)."""
    ctx = contextvars.copy_context()

    def run(*args: Any, **kwargs: Any) -> Any:
        return ctx.run(fn, *args, **kwargs)

    return run

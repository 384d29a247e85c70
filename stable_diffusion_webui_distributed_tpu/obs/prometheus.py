"""Prometheus text exposition (format 0.0.4) — no client library needed.

Four fixed-ladder latency histograms give real p50/p95/p99 where
``StageStats`` only has rolling means:

- ``sdtpu_request_e2e_seconds`` — full request latency (obs/spans.py
  observes it when a request context closes);
- ``sdtpu_queue_wait_seconds`` — coalesce-queue wait (dispatcher);
- ``sdtpu_device_dispatch_seconds`` — denoise-chunk device time
  (fed from ``StageStats.timer("denoise_chunk")`` via
  :func:`observe_stage`);
- ``sdtpu_decode_seconds`` — VAE decode fetch (the wait and the copy down;
  the executable's own seconds are ``sdtpu_device_busy_seconds_total``
  ``{kind="decode_u8"}``).

:func:`render` additionally exposes every ``DispatchMetrics`` and
``StageStats`` scalar plus the live ETA mean-percent-error gauge
(:data:`ETA_GAUGE`, fed by ``scheduler/eta.record_eta_error``), so
``/internal/metrics`` is a strict superset of ``/internal/status``'s
numbers in scrapeable form.
"""

from __future__ import annotations

import bisect
import re
import threading
from collections import deque
from typing import Any, Deque, Dict, Iterable, List, Optional, Tuple

#: Fixed bucket ladder (seconds). Spans sub-ms host work up to the minutes
#: an XLA compile can take; identical for every histogram so dashboards can
#: aggregate across them.
BUCKETS: Tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 25.0, 60.0, 120.0)


def _fmt(v: Any) -> str:
    """Prometheus sample value: ints bare, floats via repr, None -> NaN."""
    if v is None:
        return "NaN"
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


#: Longest label value exposed; tenant/class names are user-supplied and a
#: kilobyte tenant string must not bloat every scrape.
_MAX_LABEL_LEN = 100


def sanitize_label_value(v: Any) -> str:
    """User-supplied label values (tenant names, fleet classes, precision
    aliases) made exposition-safe: C0 control characters and DEL are
    dropped (``\\n`` survives — it escapes losslessly), then the value is
    truncated. Escaping alone is NOT enough: a ``\\r`` would survive the
    0.0.4 escape rules verbatim and split the sample line."""
    s = str(v)
    s = "".join(ch for ch in s if (ord(ch) >= 32 or ch == "\n")
                and ord(ch) != 127)
    return s[:_MAX_LABEL_LEN]


def _label(v: Any) -> str:
    s = sanitize_label_value(v)
    return s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


# -- metric registry ---------------------------------------------------------

#: Legal metric-family name: the Prometheus exposition grammar. The
#: ``sdtpu_`` prefix discipline is lexical (OB002 flags prefixed literals
#: outside this module), not a registry constraint — tests register
#: throwaway families under other names.
_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")

_REGISTRY_LOCK = threading.Lock()
#: family name -> (type, help). Every family this module exposes is
#: declared here; lint rule OB002 (analysis/metricrules.py) forbids ad-hoc
#: ``sdtpu_``-prefixed metric-name strings anywhere else in the package,
#: so this registry IS the metric namespace.
_REGISTRY: Dict[str, Tuple[str, str]] = {}  # guarded-by: _REGISTRY_LOCK


class MetricRegistrationError(ValueError):
    """Bad metric name, bad type, or a name re-registered as a different
    type (two families colliding on one name corrupts the exposition)."""


def register_metric(name: str, mtype: str, help_text: str) -> str:
    """Declare (idempotently) a metric family; returns the name so call
    sites can use it inline. The single sanctioned way to mint a
    ``sdtpu_*`` metric name (OB002)."""
    if not _NAME_RE.match(name):
        raise MetricRegistrationError(
            f"metric name {name!r} must match {_NAME_RE.pattern}")
    if mtype not in ("counter", "gauge", "histogram"):
        raise MetricRegistrationError(
            f"metric type {mtype!r} must be counter/gauge/histogram")
    with _REGISTRY_LOCK:
        prev = _REGISTRY.get(name)
        if prev is not None and prev[0] != mtype:
            raise MetricRegistrationError(
                f"metric {name} already registered as {prev[0]}, "
                f"not {mtype}")
        _REGISTRY[name] = (mtype, help_text)
    return name


def registered_metrics() -> Dict[str, Tuple[str, str]]:
    """Snapshot of the declared families (name -> (type, help))."""
    with _REGISTRY_LOCK:
        return dict(_REGISTRY)


def _bucket_label(b: float) -> str:
    return _fmt(b) if b != int(b) else f"{b:.1f}"


class Histogram:
    """Thread-safe fixed-bucket histogram (cumulative ``le`` exposition)."""

    def __init__(self, name: str, help_text: str,
                 buckets: Iterable[float] = BUCKETS,
                 labels: str = "") -> None:
        self.name = register_metric(name, "histogram", help_text)
        self.help = help_text
        #: pre-rendered label body (e.g. ``class="interactive"``) merged
        #: into every sample; HELP/TYPE are emitted by the caller when a
        #: labeled family has several instances
        self.labels = labels
        self.bounds: Tuple[float, ...] = tuple(sorted(buckets))
        self._lock = threading.Lock()
        self._counts: List[int] = [0] * (len(self.bounds) + 1)  # guarded-by: _lock
        self._sum = 0.0  # guarded-by: _lock
        self._count = 0  # guarded-by: _lock

    def observe(self, value: float) -> None:
        v = float(value)
        idx = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self._counts[idx] += 1
            self._sum += v
            self._count += 1

    def clear(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self.bounds) + 1)
            self._sum = 0.0
            self._count = 0

    def snapshot(self) -> Tuple[List[int], float, int]:
        """(per-bucket counts incl. +Inf overflow, sum, count)."""
        with self._lock:
            return list(self._counts), self._sum, self._count

    def quantile(self, q: float) -> float:
        """Bucket-upper-bound estimate of the q-quantile (0 when empty)."""
        counts, _total, n = self.snapshot()
        if n <= 0:
            return 0.0
        target = q * n
        running = 0
        for i, c in enumerate(counts):
            running += c
            if running >= target:
                return self.bounds[i] if i < len(self.bounds) \
                    else self.bounds[-1]
        return self.bounds[-1]

    def render(self, header: bool = True) -> List[str]:
        counts, total, n = self.snapshot()
        lines = []
        if header:
            lines += [f"# HELP {self.name} {self.help}",
                      f"# TYPE {self.name} histogram"]
        pre = f"{self.labels}," if self.labels else ""
        suf = f"{{{self.labels}}}" if self.labels else ""
        running = 0
        for bound, c in zip(self.bounds, counts):
            running += c
            lines.append(f'{self.name}_bucket{{{pre}le='
                         f'"{_bucket_label(bound)}"}} {running}')
        lines.append(f'{self.name}_bucket{{{pre}le="+Inf"}} {n}')
        lines.append(f"{self.name}_sum{suf} {_fmt(total)}")
        lines.append(f"{self.name}_count{suf} {n}")
        return lines


HISTOGRAMS: Dict[str, Histogram] = {
    "e2e": Histogram(
        "sdtpu_request_e2e_seconds",
        "End-to-end request latency (span-root duration)."),
    "queue_wait": Histogram(
        "sdtpu_queue_wait_seconds",
        "Time a request waited in the coalesce queue before its device "
        "dispatch."),
    "device_dispatch": Histogram(
        "sdtpu_device_dispatch_seconds",
        "Denoise-chunk device dispatch latency (host-observed)."),
    "decode": Histogram(
        "sdtpu_decode_seconds",
        "VAE decode fetch latency: the wait for the decode executable "
        "and the copy down, an image."),
    "lora_apply": Histogram(
        "sdtpu_lora_apply_seconds",
        "LoRA adapter activation latency: traced factor-set builds "
        "(SDTPU_LORA_TRACED, host-side padding/bucketing only — zero "
        "merges, zero recompiles) observed per build."),
    "cold_start": Histogram(
        "sdtpu_cold_start_seconds",
        "Fresh-engine time to first served image (AOT bench and warm "
        "pool spawns, serving/aot.py + fleet/pool.py)."),
}

#: StageStats stage name -> histogram key (stages not listed only appear as
#: ``sdtpu_stage_seconds`` gauges).
STAGE_TO_HIST: Dict[str, str] = {
    "denoise_chunk": "device_dispatch",
    "vae_decode_fetch": "decode",
}


def observe_hist(name: str, value: float) -> None:
    h = HISTOGRAMS.get(name)
    if h is not None:
        h.observe(value)


def observe_lora_apply(seconds: float) -> None:
    """One traced factor-set build (``Engine._traced_set_for`` cache
    miss): the full host cost of an adapter activation on the traced
    path — the merged path's equivalent is a param-tree merge plus a
    recompile, which this histogram exists to show the absence of."""
    HISTOGRAMS["lora_apply"].observe(seconds)


def observe_stage(stage: str, seconds: float) -> None:
    key = STAGE_TO_HIST.get(stage)
    if key is not None:
        HISTOGRAMS[key].observe(seconds)


def clear_histograms() -> None:
    for h in HISTOGRAMS.values():
        h.clear()
    with _FLEET_LOCK:
        _FLEET_QUEUE_WAIT.clear()
    with _COMPILE_LOCK:
        _COMPILE_LAT.clear()
    with _AOT_LOAD_LOCK:
        _AOT_LOAD_LAT.clear()
    for c in FLEET_COUNTERS.values():
        c.clear()
    PRECISION_COUNTER.clear()
    COALESCE_WINDOW_COUNTER.clear()
    HOST_STALL_COUNTER.clear()
    GC_PAUSE_COUNTER.clear()
    DEVICE_BUSY_COUNTER.clear()
    DEVICE_DRY_COUNTER.clear()
    LORA_SWITCH_COUNTER.clear()
    AOT_COUNTER.clear()
    for c in WORKER_COUNTERS.values():
        c.clear()
    WATCHDOG_COUNTER.clear()
    CACHE_COUNTER.clear()
    SIM_FAULT_COUNTER.clear()
    ALERT_COUNTER.clear()
    NOTIFY_COUNTER.clear()
    with _ALERT_LOCK:
        _ALERT_STATE.clear()
    set_sim_slo_burn(None)
    with _WORKER_LOCK:
        _WORKER_LATENCY_EWMA.clear()


# -- compile latency (pipeline/engine.py via obs/perf.py) --------------------

_COMPILE_LOCK = threading.Lock()
#: per-stage-kind compile-latency histograms, created on first build
_COMPILE_LAT: Dict[str, Histogram] = {}  # guarded-by: _COMPILE_LOCK


def observe_compile(kind: str, seconds: float) -> None:
    """One compiled-stage build's latency (``Engine._cached`` reports it
    through the perf ledger; gated there on ``SDTPU_PERF``)."""
    with _COMPILE_LOCK:
        h = _COMPILE_LAT.get(kind)
        if h is None:
            h = Histogram(
                "sdtpu_compile_seconds",
                "XLA stage-build (compile) latency by stage kind.",
                labels=f'kind="{_label(kind)}"')
            _COMPILE_LAT[kind] = h
    h.observe(seconds)


# -- AOT executable artifacts (serving/aot.py) -------------------------------

_AOT_LOAD_LOCK = threading.Lock()
#: per-stage-kind artifact-deserialize latency, created on first load.
#: A SIBLING of sdtpu_compile_seconds, never the same family: MFU/ledger
#: analysis must not mistake a 200ms deserialize for a real compile.
_AOT_LOAD_LAT: Dict[str, Histogram] = {}  # guarded-by: _AOT_LOAD_LOCK


def observe_aot_load(kind: str, seconds: float) -> None:
    """One artifact deserialize's latency by stage kind (the cheap
    hydration that replaces a fresh compile on an AOT hit)."""
    with _AOT_LOAD_LOCK:
        h = _AOT_LOAD_LAT.get(kind)
        if h is None:
            h = Histogram(
                "sdtpu_aot_load_seconds",
                "AOT artifact deserialize latency by stage kind.",
                labels=f'kind="{_label(kind)}"')
            _AOT_LOAD_LAT[kind] = h
    h.observe(seconds)


def observe_cold_start(seconds: float) -> None:
    """One fresh engine's time-to-first-image (bench arms, pool spawns)."""
    HISTOGRAMS["cold_start"].observe(seconds)


# -- fleet tier (fleet/ package) --------------------------------------------

class LabeledCounter:
    """Thread-safe counter family with a fixed label-name tuple."""

    def __init__(self, name: str, help_text: str,
                 label_names: Tuple[str, ...]) -> None:
        self.name = register_metric(name, "counter", help_text)
        self.help = help_text
        self.label_names = label_names
        self._lock = threading.Lock()
        self._counts: Dict[Tuple[str, ...], float] = {}  # guarded-by: _lock

    def inc(self, n: float = 1.0, **labels: Any) -> None:
        key = tuple(str(labels.get(ln, "")) for ln in self.label_names)
        with self._lock:
            self._counts[key] = self._counts.get(key, 0.0) + float(n)

    def value(self, **labels: Any) -> float:
        key = tuple(str(labels.get(ln, "")) for ln in self.label_names)
        with self._lock:
            return self._counts.get(key, 0.0)

    def total(self) -> float:
        with self._lock:
            return sum(self._counts.values())

    def snapshot(self) -> Dict[Tuple[str, ...], float]:
        with self._lock:
            return dict(self._counts)

    def clear(self) -> None:
        with self._lock:
            self._counts = {}

    def render(self) -> List[str]:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} counter"]
        for key in sorted(self.snapshot()):
            body = ",".join(f'{ln}="{_label(v)}"'
                            for ln, v in zip(self.label_names, key))
            lines.append(f"{self.name}{{{body}}} "
                         f"{_fmt(self.snapshot()[key])}")
        return lines


#: Fleet-tier counter families (fleet/policy.py, fleet/admission.py and
#: the dispatcher feed these; /internal/metrics renders them).
FLEET_COUNTERS: Dict[str, LabeledCounter] = {
    "admissions": LabeledCounter(
        "sdtpu_fleet_admissions_total",
        "Admission decisions by class and outcome "
        "(accept/degrade/reject).", ("class", "decision")),
    "quota_throttles": LabeledCounter(
        "sdtpu_fleet_quota_throttles_total",
        "Requests throttled by per-tenant token-bucket quotas.",
        ("tenant",)),
    "preemptions": LabeledCounter(
        "sdtpu_fleet_preemptions_total",
        "Chunk-boundary device yields by the preempted job's class.",
        ("class",)),
    "requests": LabeledCounter(
        "sdtpu_fleet_requests_total",
        "Requests entering the fleet gate by tenant and class.",
        ("tenant", "class")),
}

#: Device dispatches by resolved serving precision (pipeline/precision.py;
#: the dispatcher counts one increment per device batch, weighted by the
#: requests it carried via :func:`count_precision`).
PRECISION_COUNTER = LabeledCounter(
    "sdtpu_dispatch_precision_total",
    "Requests dispatched to the device by resolved serving precision.",
    ("precision",))

#: Coalesce windows by what ended them: ``full`` — the leader's group
#: reached ``max_batch`` and could take no joiner, so it went at once;
#: ``timer`` — the group stayed open for the whole window. One increment
#: a leader (serving/dispatcher.py), with or without tracing.
COALESCE_WINDOW_COUNTER = LabeledCounter(
    "sdtpu_coalesce_window_total",
    "Coalesce windows a group's leader waited, by what ended the wait "
    "(full/timer).",
    ("ended_by",))

#: Seconds the interpreter was not to be had, by the host clock's lag, and
#: seconds of garbage collection by generation (obs/watchdog.py's clock
#: feeds them where it feeds ``serving.host``).
HOST_STALL_COUNTER = LabeledCounter(
    "sdtpu_host_stall_seconds_total",
    "Seconds the host clock woke late by 20 ms or more.", ())
GC_PAUSE_COUNTER = LabeledCounter(
    "sdtpu_gc_pause_seconds_total",
    "Seconds of garbage collection, by generation.", ("generation",))

#: The operator's "is my chip busy", with no profiler: seconds the device
#: ran the requests' executables, by kind, and the enqueues that found it
#: with nothing left to run (obs/spans.py feeds both at a request's end,
#: where it feeds ``serving.device``).
DEVICE_BUSY_COUNTER = LabeledCounter(
    "sdtpu_device_busy_seconds_total",
    "Seconds the device ran executables of the request path, by kind "
    "(device.run spans).", ("kind",))
DEVICE_DRY_COUNTER = LabeledCounter(
    "sdtpu_device_dry_enqueues_total",
    "Enqueues that found every earlier executable done: the device "
    "waited for the host.", ())

#: Adapter-set activations by serving mode: ``merged`` — host merge into
#: the param tree (epoch bump, caches retired); ``traced`` — factor set
#: installed as jit arguments (SDTPU_LORA_TRACED, no merge, no epoch
#: bump). The engine feeds this through :func:`count_lora_switch`.
LORA_SWITCH_COUNTER = LabeledCounter(
    "sdtpu_lora_switch_total",
    "LoRA adapter-set switches by serving mode (merged/traced).",
    ("mode",))


def count_lora_switch(mode: str, n: float = 1.0) -> None:
    """One adapter-set switch: ``mode`` is ``merged`` (host merge path)
    or ``traced`` (recompile-free traced path)."""
    LORA_SWITCH_COUNTER.inc(n, mode=mode)


#: Kept-program events by outcome: ``hit`` (executable deserialized),
#: ``miss`` (no cell — traced), ``saved`` (the traced program kept),
#: ``fallback`` (cell present but corrupt or unloadable — traced instead,
#: journaled as ``aot_fallback``), ``refused`` (an artifact that could
#: not be made or did not load: not kept). Fed by serving/aot.py through
#: :func:`aot_count`.
AOT_COUNTER = LabeledCounter(
    "sdtpu_aot_total",
    "Kept stage program events (serving/aot.py) by outcome.",
    ("outcome",))


def aot_count(outcome: str, n: float = 1.0) -> None:
    AOT_COUNTER.inc(n, outcome=outcome)

# -- scheduler tier (scheduler/worker.py health + obs/watchdog.py) -----------

#: Worker-health counter families (WorkerNode.health and World._requeue
#: feed these; /internal/metrics renders them).
WORKER_COUNTERS: Dict[str, LabeledCounter] = {
    "requests": LabeledCounter(
        "sdtpu_worker_requests_total",
        "Generation requests sent to each worker backend.", ("worker",)),
    "failures": LabeledCounter(
        "sdtpu_worker_failures_total",
        "Failed generation requests per worker.", ("worker",)),
    "requeued_images": LabeledCounter(
        "sdtpu_worker_requeued_images_total",
        "Images requeued away from a failed worker.", ("worker",)),
    "transitions": LabeledCounter(
        "sdtpu_worker_state_transitions_total",
        "Worker state-machine transitions by destination state.",
        ("worker", "to")),
}

#: Stall detections by the hang watchdog (obs/watchdog.py), labeled with
#: the watched operation's name (job-<worker> / dispatch.device).
WATCHDOG_COUNTER = LabeledCounter(
    "sdtpu_watchdog_stalls_total",
    "Dispatches or remote jobs that exceeded k x their ETA "
    "(SDTPU_WATCHDOG_FACTOR).", ("name",))

# -- caching tier (cache/: embed dedupe, result dedupe, prefix sharing) ------

#: Cache events by layer (embed_pos/embed_neg/result/prefix) and outcome
#: (hit/miss/joined/resumed/captured). The cache modules feed this through
#: :func:`cache_count`; /internal/metrics and /internal/cache render it.
CACHE_COUNTER = LabeledCounter(
    "sdtpu_cache_events_total",
    "Caching-tier events (SDTPU_CACHE) by layer and outcome.",
    ("layer", "outcome"))


def cache_count(layer: str, outcome: str, n: float = 1.0) -> None:
    """One caching-tier event: ``layer`` names the cache (embed_pos,
    embed_neg, result, prefix), ``outcome`` what happened there (hit,
    miss, joined, resumed, captured)."""
    CACHE_COUNTER.inc(n, layer=layer, outcome=outcome)


# -- alerting plane (obs/alerts.py state machine) ----------------------------

#: Alert state transitions by rule and state (firing / resolved); the
#: alert engine feeds this through :func:`alert_count`.
ALERT_COUNTER = LabeledCounter(
    "sdtpu_alerts_total",
    "Alert state transitions (SDTPU_ALERTS) by rule and state.",
    ("rule", "state"))

_ALERT_LOCK = threading.Lock()
#: rule name -> 1.0 while firing, 0.0 after resolve; absent until the
#: rule's first transition (the family renders only what happened).
_ALERT_STATE: Dict[str, float] = {}  # guarded-by: _ALERT_LOCK


def alert_count(rule: str, state: str, n: float = 1.0) -> None:
    ALERT_COUNTER.inc(n, rule=rule, state=state)


def set_alert_state(rule: str, value: float) -> None:
    with _ALERT_LOCK:
        _ALERT_STATE[str(rule)] = float(value)


def alert_states() -> Dict[str, float]:
    with _ALERT_LOCK:
        return dict(_ALERT_STATE)


#: Webhook delivery outcomes (sent / failed / deduped / dropped) from
#: obs/notify.py, by channel (severity route; "default" for the single
#: SDTPU_NOTIFY_URL channel). Zero families with no route configured.
NOTIFY_COUNTER = LabeledCounter(
    "sdtpu_notify_total",
    "Alert notification delivery outcomes (SDTPU_NOTIFY_URL / "
    "SDTPU_NOTIFY_ROUTES) by channel and outcome.",
    ("channel", "outcome"))


def notify_count(outcome: str, n: float = 1.0,
                 channel: str = "default") -> None:
    NOTIFY_COUNTER.inc(n, channel=channel, outcome=outcome)


# -- scenario engine (sim/: chaos injection + SLO scoring) -------------------

#: Chaos faults actually delivered by sim/chaos.py, by fault kind
#: (kill / stall / slow / http_error). Zero outside scenario runs.
SIM_FAULT_COUNTER = LabeledCounter(
    "sdtpu_sim_faults_total",
    "Chaos faults injected by the scenario engine (SDTPU_SIM) by kind.",
    ("kind",))

_SIM_LOCK = threading.Lock()
#: worst per-(tenant, class) SLO burn rate from the last scored scenario
#: run; None until sim/score.py scores one, omitted from /internal/metrics
#: while None.
_SIM_SLO_BURN: Optional[float] = None  # guarded-by: _SIM_LOCK


def sim_fault_count(kind: str, n: float = 1.0) -> None:
    SIM_FAULT_COUNTER.inc(n, kind=kind)


def set_sim_slo_burn(value: Optional[float]) -> None:
    """Record the last scenario run's worst SLO burn rate (sim/score.py)."""
    global _SIM_SLO_BURN
    with _SIM_LOCK:
        _SIM_SLO_BURN = None if value is None else float(value)


def sim_slo_burn() -> Optional[float]:
    with _SIM_LOCK:
        return _SIM_SLO_BURN

_WORKER_LOCK = threading.Lock()
#: per-worker generate-latency EWMA gauge values
_WORKER_LATENCY_EWMA: Dict[str, float] = {}  # guarded-by: _WORKER_LOCK


def worker_count(name: str, n: float = 1.0, **labels: Any) -> None:
    c = WORKER_COUNTERS.get(name)
    if c is not None:
        c.inc(n, **labels)


def set_worker_latency(worker: str, ewma_s: float) -> None:
    with _WORKER_LOCK:
        _WORKER_LATENCY_EWMA[str(worker)] = float(ewma_s)


def count_watchdog_stall(name: str) -> None:
    WATCHDOG_COUNTER.inc(name=name)


def watchdog_stalls_total() -> float:
    return WATCHDOG_COUNTER.total()


_FLEET_LOCK = threading.Lock()
#: per-class queue-wait histograms, created on first observation
_FLEET_QUEUE_WAIT: Dict[str, Histogram] = {}  # guarded-by: _FLEET_LOCK


def fleet_count(name: str, n: float = 1.0, **labels: Any) -> None:
    c = FLEET_COUNTERS.get(name)
    if c is not None:
        c.inc(n, **labels)


def count_precision(precision: str, n: float = 1.0) -> None:
    """One device dispatch carrying ``n`` requests at ``precision``."""
    if precision:
        PRECISION_COUNTER.inc(n, precision=precision)


def count_coalesce_window(ended_by: str) -> None:
    """One leader's coalesce window, ended by ``full`` or ``timer``."""
    COALESCE_WINDOW_COUNTER.inc(ended_by=ended_by)


def count_host_stall(seconds: float) -> None:
    HOST_STALL_COUNTER.inc(seconds)


def count_gc_pause(generation: int, seconds: float) -> None:
    GC_PAUSE_COUNTER.inc(seconds, generation=generation)


def count_device(busy_s: Dict[str, float], dry_enqueues: int) -> None:
    """One finished request's ``device.run`` seconds by kind."""
    for kind, seconds in busy_s.items():
        DEVICE_BUSY_COUNTER.inc(seconds, kind=kind)
    if dry_enqueues:
        DEVICE_DRY_COUNTER.inc(dry_enqueues)


def fleet_observe_queue_wait(cls: str, seconds: float) -> None:
    """Per-class companion to the unlabeled ``queue_wait`` histogram —
    the autoscaler keys its p95 signal on these."""
    with _FLEET_LOCK:
        h = _FLEET_QUEUE_WAIT.get(cls)
        if h is None:
            h = Histogram(
                "sdtpu_fleet_queue_wait_seconds",
                "Gate queue wait by priority class.",
                labels=f'class="{_label(cls)}"')
            _FLEET_QUEUE_WAIT[cls] = h
    h.observe(seconds)


def fleet_queue_wait_p95(cls: Optional[str] = None) -> float:
    """p95 gate wait for one class, or the worst class when ``cls`` is
    None (the autoscale signal keys on the most-starved class)."""
    with _FLEET_LOCK:
        hists = ([_FLEET_QUEUE_WAIT[cls]]
                 if cls is not None and cls in _FLEET_QUEUE_WAIT
                 else list(_FLEET_QUEUE_WAIT.values()))
    if not hists:
        return 0.0
    return max(h.quantile(0.95) for h in hists)


class EtaGauge:
    """Live predicted-vs-actual ETA calibration across every backend.

    Mirrors the paper's per-worker MPE feedback (scheduler/eta.py,
    reference worker.py:476-492) as one process-wide gauge: same window,
    same |error| >= 500% rejection, fed by ``record_eta_error``."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        #: window/rejection adopted from scheduler.eta at first record —
        #: importing the scheduler package here (obs import time) would
        #: drag worker/world in and risk an import cycle
        self._errors: Optional[Deque[float]] = None  # guarded-by: _lock
        self._samples = 0  # guarded-by: _lock
        self._last_predicted: Optional[float] = None  # guarded-by: _lock
        self._last_actual: Optional[float] = None  # guarded-by: _lock

    def record(self, predicted: float, actual: float) -> None:
        from stable_diffusion_webui_distributed_tpu.scheduler import (
            eta as eta_mod,
        )

        if actual <= 0 or predicted <= 0:
            return
        error = (predicted - actual) / actual * 100.0
        if abs(error) >= eta_mod.MPE_REJECT_ABS_PERCENT:
            return
        with self._lock:
            if self._errors is None:
                self._errors = deque(maxlen=eta_mod.MPE_WINDOW)
            self._errors.append(error)
            self._samples += 1
            self._last_predicted = float(predicted)
            self._last_actual = float(actual)

    def mpe(self) -> float:
        with self._lock:
            if not self._errors:
                return 0.0
            return sum(self._errors) / len(self._errors)

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            mpe = (sum(self._errors) / len(self._errors)
                   if self._errors else 0.0)
            return {
                "mpe_percent": mpe,
                "samples": self._samples,
                "last_predicted_s": self._last_predicted,
                "last_actual_s": self._last_actual,
            }

    def clear(self) -> None:
        with self._lock:
            self._errors = None
            self._samples = 0
            self._last_predicted = None
            self._last_actual = None


#: Process-wide ETA calibration gauge (scheduler/eta.py feeds it).
ETA_GAUGE = EtaGauge()


def _scalar(lines: List[str], name: str, mtype: str, help_text: str,
            value: Any, labels: str = "") -> None:
    register_metric(name, mtype, help_text)
    lines.append(f"# HELP {name} {help_text}")
    lines.append(f"# TYPE {name} {mtype}")
    lines.append(f"{name}{labels} {_fmt(value)}")


def _labeled_family(lines: List[str], name: str, mtype: str,
                    help_text: str,
                    samples: List[Tuple[str, Any]]) -> None:
    """One HELP/TYPE header + one sample per (label-body, value) pair;
    families with no samples are omitted entirely."""
    if not samples:
        return
    register_metric(name, mtype, help_text)
    lines.append(f"# HELP {name} {help_text}")
    lines.append(f"# TYPE {name} {mtype}")
    for body, value in samples:
        lines.append(f"{name}{{{body}}} {_fmt(value)}")


def _render_perf(lines: List[str]) -> None:
    """The perf-ledger families: per-(bucket, cadence, precision)
    padding / device-time attribution and per-(tenant, class) SLO gauges.
    All pulled live from obs/perf.py's LEDGER — empty (and absent from
    the exposition) until SDTPU_PERF turns recording on."""
    from stable_diffusion_webui_distributed_tpu.obs import perf as obs_perf

    s = obs_perf.LEDGER.summary()

    def body(g):
        # lora: traced-adapter cell ("r8s1") or "" — adapter-active MFU
        # rows stay separable from the adapterless baseline
        return (f'bucket="{_label(g["bucket"])}",'
                f'cadence="{g["cadence"]}",'
                f'precision="{_label(g["precision"])}",'
                f'lora="{_label(g.get("lora", ""))}"')

    groups = s["groups"]
    _labeled_family(
        lines, "sdtpu_perf_dispatches_total", "counter",
        "Device dispatches by serving group (perf ledger).",
        [(body(g), g["dispatches"]) for g in groups])
    _labeled_family(
        lines, "sdtpu_perf_device_seconds_total", "counter",
        "Host-observed device-dispatch seconds by serving group.",
        [(body(g), g["device_s"]) for g in groups])
    _labeled_family(
        lines, "sdtpu_perf_padding_ratio", "gauge",
        "Padded-dispatched pixels / true-requested pixels by group.",
        [(body(g), g["padding_ratio"]) for g in groups])
    _labeled_family(
        lines, "sdtpu_perf_padding_waste", "gauge",
        "Fraction of dispatched pixels that were bucket padding.",
        [(body(g), g["padding_waste"]) for g in groups])
    _labeled_family(
        lines, "sdtpu_perf_compute_padding_ratio", "gauge",
        "Attention-computed pixels / true-requested pixels by group "
        "(masked ragged rows excluded from the numerator).",
        [(body(g), g.get("compute_padding_ratio")) for g in groups])
    _labeled_family(
        lines, "sdtpu_perf_token_padding_ratio", "gauge",
        "Padded conditioning tokens / true prompt tokens by group.",
        [(body(g), g.get("token_padding_ratio")) for g in groups])

    def slo_body(r):
        return (f'tenant="{_label(r["tenant"])}",'
                f'class="{_label(r["class"])}"')

    slo = s["slo"]
    _labeled_family(
        lines, "sdtpu_fleet_slo_attainment", "gauge",
        "Fraction of fleet-gated requests meeting their SLO, by tenant "
        "and class.", [(slo_body(r), r["attainment"]) for r in slo])
    _labeled_family(
        lines, "sdtpu_fleet_slo_burn_rate", "gauge",
        "Windowed SLO miss fraction over the error budget (1.0 = burning "
        "exactly the budget).", [(slo_body(r), r["burn_rate"]) for r in slo])


def render() -> str:
    """The full /internal/metrics body (Prometheus text format 0.0.4)."""
    # lazy imports: this module must stay importable without dragging the
    # serving/runtime stacks in at obs import time (no cycles)
    from stable_diffusion_webui_distributed_tpu.runtime.trace import STATS
    from stable_diffusion_webui_distributed_tpu.serving.metrics import (
        METRICS,
    )

    lines: List[str] = []
    for h in HISTOGRAMS.values():
        lines.extend(h.render())

    s = METRICS.summary()
    _scalar(lines, "sdtpu_serving_requests_total", "counter",
            "Requests accepted by the serving dispatcher.", s["requests"])
    _scalar(lines, "sdtpu_serving_bucket_hits_total", "counter",
            "Requests whose shape matched a bucket exactly.",
            s["bucket_hits"])
    _scalar(lines, "sdtpu_serving_bucket_misses_total", "counter",
            "Requests padded up to a bucket.", s["bucket_misses"])
    _scalar(lines, "sdtpu_serving_bucket_bypasses_total", "counter",
            "Requests that bypassed bucketing (hires/img2img/no fit).",
            s["bucket_bypasses"])
    _scalar(lines, "sdtpu_serving_bucket_hit_rate", "gauge",
            "bucket_hits / (bucket_hits + bucket_misses).",
            s["bucket_hit_rate"])
    _scalar(lines, "sdtpu_serving_dispatches_total", "counter",
            "Device batches executed.", s["dispatches"])
    _scalar(lines, "sdtpu_serving_coalesced_dispatches_total", "counter",
            "Dispatches that merged >= 2 requests.",
            s["coalesced_dispatches"])
    _scalar(lines, "sdtpu_serving_coalesce_factor", "gauge",
            "Mean requests per device dispatch.", s["coalesce_factor"])
    _scalar(lines, "sdtpu_serving_avg_queue_wait_seconds", "gauge",
            "Rolling mean coalesce-queue wait.", s["avg_queue_wait_s"])
    _scalar(lines, "sdtpu_serving_avg_padding_ratio", "gauge",
            "Mean bucket-px / requested-px over bucketed requests.",
            s["avg_padding_ratio"])
    _scalar(lines, "sdtpu_serving_unet_images_total", "counter",
            "Images decoded to outputs.", s["unet_images"])
    expander = s["expander"]
    _scalar(lines, "sdtpu_expander_rows_attended_total", "counter",
            "Cache positions the prompt expander's decode steps' queries "
            "attended in a layer that keeps every position (keys and "
            "values, or latents), a sequence at a time.",
            expander["rows_attended"])
    shared = expander["rows_read_shared"]
    _labeled_family(
        lines, "sdtpu_expander_rows_read_total", "counter",
        "Cache positions read for them: the shared range before a fork "
        "once a step for all its sequences, a sequence's own rows once "
        "each.",
        [('range="own"', expander["rows_read"] - shared),
         ('range="shared"', shared)])
    _scalar(lines, "sdtpu_expander_layer_passes_total", "counter",
            "Passes of the whole stack the prompt expander's token steps "
            "ran (a looped model: total_ut_steps a step).",
            expander["layer_passes"])
    _labeled_family(
        lines, "sdtpu_expander_exit_pass_total", "counter",
        "Tokens the prompt expander made, by the pass whose state the "
        "head read (1 is the first).",
        [(f'pass="{i + 1}"', n) for i, n in enumerate(expander["exit_pass"])])
    _scalar(lines, "sdtpu_expander_exit_lambda_max", "gauge",
            "Largest exit probability a pass's gate gave.",
            expander["exit_lambda_max"])
    _labeled_family(
        lines, "sdtpu_expander_delta_mixers_total", "counter",
        "Gated-delta-rule mixers traced, by the form their recurrence "
        "took (recurrent_forked: one token each of several sequences, "
        "every one over a state of its own).",
        [(f'form="{_label(form)}"', n)
         for form, n in sorted(expander["delta_mixers"].items())])
    _labeled_family(
        lines, "sdtpu_expander_delta_steps_total", "counter",
        "Forked gated-delta-rule mixers traced, by the step that moves "
        "their states (kernel: one Pallas kernel that holds a head's state "
        "in VMEM; elementwise: XLA's fusions).",
        [(f'step="{_label(step)}"', n)
         for step, n in sorted(expander["delta_steps"].items())])
    _labeled_family(
        lines, "sdtpu_expander_ssm_mixers_total", "counter",
        "Selective state-space mixers traced, by the form their "
        "recurrence took (recurrent_forked: one token each of several "
        "sequences, every one over a state of its own).",
        [(f'form="{_label(form)}"', n)
         for form, n in sorted(expander["ssm_mixers"].items())])
    _labeled_family(
        lines, "sdtpu_expander_joined_layers_total", "counter",
        "Layers traced with several token mixers side by side under one "
        "norm, by the form of the executable.",
        [(f'form="{_label(form)}"', n)
         for form, n in sorted(expander["joined_layers"].items())])
    _scalar(lines, "sdtpu_expander_multipliers_applied", "gauge",
            "Forward multipliers off 1 in the last language model traced "
            "(0: the forward pass scales nothing).",
            expander["multipliers_applied"])
    _labeled_family(
        lines, "sdtpu_expander_moe_shortcuts_total", "counter",
        "Expert layers traced whose routed sum crosses into the next "
        "layer, by the form of the executable.",
        [(f'form="{_label(form)}"', n)
         for form, n in sorted(expander["moe_shortcuts"].items())])
    _labeled_family(
        lines, "sdtpu_expander_route_products_total", "counter",
        "Expert layers traced, by what stood between the router's logits "
        "and the experts' product (kernel: one Pallas launch; xla: the "
        "chain of top_k, sorts and gathers).",
        [(f'form="{_label(form)}"', n)
         for form, n in sorted(expander["route_products"].items())])
    _labeled_family(
        lines, "sdtpu_expander_latent_scaled_total", "counter",
        "Latent-attention sites traced with a query or a latent scale off "
        "1, by the form of the site.",
        [(f'form="{_label(form)}"', n)
         for form, n in sorted(expander["latent_scaled"].items())])
    _scalar(lines, "sdtpu_expander_zero_expert_picks_total", "counter",
            "Picks of the prompt expander's decode steps that fell on "
            "zero-compute (identity) experts.",
            expander["zero_expert_picks"])
    _labeled_family(
        lines, "sdtpu_expander_tied_head_total", "counter",
        "Executables traced whose logits are read off the token table "
        "itself (a tied head), by the form of the executable.",
        [(f'form="{_label(form)}"', n)
         for form, n in sorted(expander["tied_head"].items())])
    _scalar(lines, "sdtpu_expander_expert_picks_held_total", "counter",
            "Picks of the prompt expander's decode steps that fell on an "
            "expert held here.",
            expander["expert_picks_held"])
    _scalar(lines, "sdtpu_expander_expert_calls_total", "counter",
            "Routed sums of the prompt expander's decode steps, one an "
            "expert layer a step (a call of the expert kernel on the chip).",
            expander["expert_calls"])
    _scalar(lines, "sdtpu_expander_expert_calls_unread_total", "counter",
            "Those calls whose rows chose no expert held here: they read "
            "nothing of the experts' kernels.",
            expander["expert_calls_unread"])
    _scalar(lines, "sdtpu_expander_state_bytes_stepped_total", "counter",
            "Bytes of recurrent states and kept inputs (linear layers', "
            "state-space mixers') the "
            "prompt expander's decode steps read and wrote.",
            expander["state_bytes_stepped"])
    _scalar(lines, "sdtpu_expander_fork_bytes_copied_total", "counter",
            "Bytes of recurrent state and kept inputs the prompt "
            "expander's forks copied, once a sequence.",
            expander["fork_bytes_copied"])
    _labeled_family(
        lines, "sdtpu_expander_sublayer_norms_total", "counter",
        "Sublayer norms traced, by where they stand (pre: a sublayer's "
        "input, post: its output) and the form of the executable.",
        [(f'placement="{_label(placement)}",form="{_label(form)}"', n)
         for placement, by_form in sorted(
             expander["sublayer_norms"].items())
         for form, n in sorted(by_form.items())])
    _labeled_family(
        lines, "sdtpu_expander_attention_unrotated_total", "counter",
        "Attention sites traced that built no rotary table, by the form "
        "of the executable.",
        [(f'form="{_label(form)}"', n)
         for form, n in sorted(expander["attention_unrotated"].items())])
    _scalar(lines, "sdtpu_expander_write_strength_bound", "gauge",
            "Largest write strength the last delta-rule mixer traced can "
            "give (1 for sigmoid(b), 2 for 2 sigmoid(b); 0: none traced).",
            expander["write_strength_bound"])

    _labeled_family(
        lines, "sdtpu_stage_compiles_total", "counter",
        "XLA stage builds (one compile each) by stage kind.",
        [(f'kind="{_label(kind)}"', s["compiles"][kind])
         for kind in sorted(s["compiles"])])
    _labeled_family(
        lines, "sdtpu_stage_cache_hits_total", "counter",
        "Compiled-stage cache hits by stage kind.",
        [(f'kind="{_label(kind)}"', s["cache_hits"][kind])
         for kind in sorted(s["cache_hits"])])

    timings = STATS.summary()
    _labeled_family(
        lines, "sdtpu_stage_seconds", "gauge",
        "Rolling stage wall-clock stats (StageStats window).",
        [(f'stage="{_label(stage)}",stat="{stat}"', timings[stage][stat])
         for stage in sorted(timings)
         for stat in ("mean", "p50", "last")])
    _labeled_family(
        lines, "sdtpu_stage_samples", "gauge",
        "Rolling StageStats sample count per stage.",
        [(f'stage="{_label(stage)}"', timings[stage]["count"])
         for stage in sorted(timings)])

    lines.extend(PRECISION_COUNTER.render())
    lines.extend(COALESCE_WINDOW_COUNTER.render())
    lines.extend(HOST_STALL_COUNTER.render())
    lines.extend(GC_PAUSE_COUNTER.render())
    lines.extend(DEVICE_BUSY_COUNTER.render())
    lines.extend(DEVICE_DRY_COUNTER.render())
    lines.extend(LORA_SWITCH_COUNTER.render())
    lines.extend(AOT_COUNTER.render())
    for c in FLEET_COUNTERS.values():
        lines.extend(c.render())
    for c in WORKER_COUNTERS.values():
        lines.extend(c.render())
    lines.extend(WATCHDOG_COUNTER.render())
    lines.extend(CACHE_COUNTER.render())
    lines.extend(SIM_FAULT_COUNTER.render())
    lines.extend(ALERT_COUNTER.render())
    lines.extend(NOTIFY_COUNTER.render())
    _labeled_family(
        lines, "sdtpu_alert_state", "gauge",
        "Current alert state by rule (1 = firing, 0 = resolved/ok); "
        "rules absent until their first transition.",
        [(f'rule="{_label(k)}"', v)
         for k, v in sorted(alert_states().items())])
    burn = sim_slo_burn()
    if burn is not None:
        _scalar(lines, "sdtpu_sim_slo_burn", "gauge",
                "Worst per-(tenant, class) SLO burn rate from the last "
                "scored scenario run (sim/score.py).", burn)
    with _WORKER_LOCK:
        worker_lat = dict(_WORKER_LATENCY_EWMA)
    _labeled_family(
        lines, "sdtpu_worker_latency_ewma_seconds", "gauge",
        "EWMA of per-worker generate latency (WorkerHealth window).",
        [(f'worker="{_label(k)}"', v)
         for k, v in sorted(worker_lat.items())])
    with _FLEET_LOCK:
        fleet_hists = [_FLEET_QUEUE_WAIT[k]
                       for k in sorted(_FLEET_QUEUE_WAIT)]
    for i, h in enumerate(fleet_hists):
        lines.extend(h.render(header=(i == 0)))
    with _COMPILE_LOCK:
        compile_hists = [_COMPILE_LAT[k] for k in sorted(_COMPILE_LAT)]
    for i, h in enumerate(compile_hists):
        lines.extend(h.render(header=(i == 0)))
    with _AOT_LOAD_LOCK:
        aot_hists = [_AOT_LOAD_LAT[k] for k in sorted(_AOT_LOAD_LAT)]
    for i, h in enumerate(aot_hists):
        lines.extend(h.render(header=(i == 0)))
    _render_perf(lines)

    eta = ETA_GAUGE.summary()
    _scalar(lines, "sdtpu_eta_mpe_percent", "gauge",
            "Live ETA mean percent error (paper MPE window).",
            eta["mpe_percent"])
    _scalar(lines, "sdtpu_eta_samples_total", "counter",
            "Accepted predicted-vs-actual ETA samples.", eta["samples"])
    return "\n".join(lines) + "\n"

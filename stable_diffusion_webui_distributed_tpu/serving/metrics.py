"""Dispatch metrics for the serving layer.

A single process-wide :data:`METRICS` object counts the events that decide
serving latency on an XLA backend: how many compiled stages were BUILT
(each build is one XLA compile on first dispatch — minutes on TPU), how
often a request's shape landed on an already-compiled bucket, how many
requests each device dispatch carried (the coalesce factor), and how long
requests waited in the coalesce queue. Everything here is host-side
counting — safe to assert in CPU tests, unlike wall-clock.

``Engine._cached`` reports every stage build/hit; the serving dispatcher
reports requests, dispatches and queue waits; ``handle_internal_status``
exposes :meth:`DispatchMetrics.summary` under ``"serving"``.

What XLA really did is counted at the source: :data:`XLA` listens to
``jax.monitoring`` (:func:`install_xla_listener`) and sums, per jitted
function, the seconds of tracing, lowering and backend compile (a compile
on a persistent-cache miss, a load on a hit), with the cache's own hits,
misses and retrieval seconds. ``summary()["xla"]`` carries it.

Which attention the UNet's sites took is counted where they are traced:
:data:`ATTENTION` (``summary()["attention"]``), fed by
``models/unet.py:Attention`` (and by ``models/lm.py:Attention`` for the
resident language model's sites). What that model's ``expand`` stage did
with tokens, experts and its cache is :data:`EXPANDER`
(``summary()["expander"]``). How many images a VAE decode dispatch carried
is ``summary()["decode"]`` (``rows`` over ``dispatches``, fed by
``Engine._queue_decoded``). Which form the UNet's and the VAE decoder's
upsample sites took is :data:`UPSAMPLE` (``summary()["upsample"]``), fed
by ``ops/upsample.py``; which form a GroupNorm site took is :data:`NORM`
(``summary()["norm"]``), fed by ``models/unet.py``. How often a request's
plan met a kept sigma ladder or a kept time-id embedding (runtime/kept.py)
is :data:`PLAN` (``summary()["plan"]``). How each stage's program came to
be since the process started is ``summary()["programs"]``: ``loaded`` from
the kept programs (serving/aot.py) with the seconds that took
(``load_s``), or ``traced``.

The counters fed at trace time count nothing when a stage's program is
loaded instead of traced, so what a trace counted is kept beside the
program (:func:`capture_sites`) and counted again when it is loaded
(:func:`replay_sites`).
"""

from __future__ import annotations

import contextlib
import threading
from collections import defaultdict
from typing import Any, Dict, Iterator, List


class DispatchMetrics:
    """Thread-safe counters; every mutator is O(1) under one lock."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.clear()

    def clear(self) -> None:
        # __init__ creates _lock before the first clear(); external resets
        # (tests, status handlers) serialize against every mutator
        with self._lock:
            #: stage-kind ("chunk", "decode_u8", "encode", ...) -> builds
            self.compiles: Dict[str, int] = defaultdict(int)  # guarded-by: _lock
            #: stage-kind -> cache hits (stage already built)
            self.cache_hits: Dict[str, int] = defaultdict(int)  # guarded-by: _lock
            #: stage-kind -> executables loaded from the kept programs
            #: (serving/aot.py)
            self.aot_loads: Dict[str, int] = defaultdict(int)  # guarded-by: _lock
            #: stage-kind -> seconds reading and deserialising them
            self.aot_load_s: Dict[str, float] = defaultdict(float)  # guarded-by: _lock
            #: stage-kind -> programs traced and lowered (where programs
            #: are kept: one an executable; elsewhere one a stage build)
            self.traced: Dict[str, int] = defaultdict(int)  # guarded-by: _lock
            self.requests = 0  # guarded-by: _lock
            #: request shape already equal to its bucket
            self.bucket_hits = 0  # guarded-by: _lock
            #: request shape padded up to a bucket
            self.bucket_misses = 0  # guarded-by: _lock
            #: request bypassed bucketing (hires/img2img/no ladder fit)
            self.bucket_bypasses = 0  # guarded-by: _lock
            #: device batches executed by the dispatcher
            self.dispatches = 0  # guarded-by: _lock
            #: dispatches that merged >= 2 requests
            self.coalesced_dispatches = 0  # guarded-by: _lock
            #: sum over dispatches of requests merged (factor numerator)
            self.coalesced_requests = 0  # guarded-by: _lock
            self.queue_wait_total = 0.0  # guarded-by: _lock
            self.queue_wait_count = 0  # guarded-by: _lock
            #: sum of (bucket px / requested px) per bucketed request
            self.padding_ratio_total = 0.0  # guarded-by: _lock
            self.padding_ratio_count = 0  # guarded-by: _lock
            #: images decoded to outputs (pad-and-drop rows are not)
            self.unet_images = 0  # guarded-by: _lock
            #: VAE decode executables enqueued for them: images over
            #: dispatches is what a decode dispatch carried
            self.decode_dispatches = 0  # guarded-by: _lock
            #: resolved precision name -> device dispatches / requests
            #: carried (pipeline/precision.py; "" = caller didn't say)
            self.precision_dispatches: Dict[str, int] = defaultdict(int)  # guarded-by: _lock
            self.precision_requests: Dict[str, int] = defaultdict(int)  # guarded-by: _lock

    # -- engine-side ------------------------------------------------------

    def record_compile(self, kind: str) -> None:
        with self._lock:
            self.compiles[str(kind)] += 1

    def record_cache_hit(self, kind: str) -> None:
        with self._lock:
            self.cache_hits[str(kind)] += 1

    def record_aot_load(self, kind: str, seconds: float = 0.0) -> None:
        with self._lock:
            self.aot_loads[str(kind)] += 1
            self.aot_load_s[str(kind)] += float(seconds)

    def record_traced(self, kind: str) -> None:
        with self._lock:
            self.traced[str(kind)] += 1

    # -- dispatcher-side --------------------------------------------------

    def record_request(self, bucketed: bool, bypassed: bool = False,
                       padding_ratio: float = 1.0) -> None:
        with self._lock:
            self.requests += 1
            if bypassed:
                self.bucket_bypasses += 1
                return
            if bucketed:
                self.bucket_misses += 1
            else:
                self.bucket_hits += 1
            self.padding_ratio_total += float(padding_ratio)
            self.padding_ratio_count += 1

    def record_dispatch(self, n_requests: int, precision: str = "") -> None:
        with self._lock:
            self.dispatches += 1
            self.coalesced_requests += int(n_requests)
            if n_requests >= 2:
                self.coalesced_dispatches += 1
            if precision:
                self.precision_dispatches[str(precision)] += 1
                self.precision_requests[str(precision)] += int(n_requests)

    def record_queue_wait(self, seconds: float) -> None:
        with self._lock:
            self.queue_wait_total += float(seconds)
            self.queue_wait_count += 1

    def record_decoded(self, rows: int, dispatches: int) -> None:
        """``rows`` kept images went to the VAE as ``dispatches``
        executables (``Engine._queue_decoded``)."""
        with self._lock:
            self.unet_images += int(rows)
            self.decode_dispatches += int(dispatches)

    # -- readers ----------------------------------------------------------

    def compile_count(self, kind: str = "chunk") -> int:
        with self._lock:
            return self.compiles.get(kind, 0)

    def aot_load_count(self, kind: str = "chunk") -> int:
        with self._lock:
            return self.aot_loads.get(kind, 0)

    def coalesce_factor(self) -> float:
        """Mean requests per device dispatch (1.0 = no coalescing yet)."""
        with self._lock:
            if not self.dispatches:
                return 0.0
            return self.coalesced_requests / self.dispatches

    def avg_queue_wait(self) -> float:
        with self._lock:
            if not self.queue_wait_count:
                return 0.0
            return self.queue_wait_total / self.queue_wait_count

    def avg_padding_ratio(self) -> float:
        """Mean bucket-px / requested-px over bucketed requests (>= 1)."""
        with self._lock:
            if not self.padding_ratio_count:
                return 1.0
            return self.padding_ratio_total / self.padding_ratio_count

    def summary(self) -> Dict:
        with self._lock:
            total_buckets = self.bucket_hits + self.bucket_misses
            out = {
                "compiles": dict(self.compiles),
                "cache_hits": dict(self.cache_hits),
                "aot_loads": dict(self.aot_loads),
                "programs": {
                    "loaded": sum(self.aot_loads.values()),
                    "traced": sum(self.traced.values()),
                    "load_s": sum(self.aot_load_s.values()),
                    "by_kind": {
                        kind: {"loaded": self.aot_loads.get(kind, 0),
                               "traced": self.traced.get(kind, 0),
                               "load_s": self.aot_load_s.get(kind, 0.0)}
                        for kind in sorted(set(self.aot_loads)
                                           | set(self.traced))}},
                "requests": self.requests,
                "bucket_hits": self.bucket_hits,
                "bucket_misses": self.bucket_misses,
                "bucket_bypasses": self.bucket_bypasses,
                "bucket_hit_rate": (self.bucket_hits / total_buckets
                                    if total_buckets else None),
                "dispatches": self.dispatches,
                "coalesced_dispatches": self.coalesced_dispatches,
                "coalesce_factor": (self.coalesced_requests / self.dispatches
                                    if self.dispatches else None),
                "avg_queue_wait_s": (self.queue_wait_total
                                     / self.queue_wait_count
                                     if self.queue_wait_count else None),
                "avg_padding_ratio": (self.padding_ratio_total
                                      / self.padding_ratio_count
                                      if self.padding_ratio_count else None),
                "unet_images": self.unet_images,
                "decode": {"dispatches": self.decode_dispatches,
                           "rows": self.unet_images},
                # per-precision dispatch mix (flows into /internal/status
                # under serving.precision; ISSUE 7 observability)
                "precision": {
                    name: {
                        "dispatches": self.precision_dispatches.get(name, 0),
                        "requests": self.precision_requests.get(name, 0),
                    }
                    for name in sorted(set(self.precision_dispatches)
                                       | set(self.precision_requests))
                },
            }
        out["xla"] = XLA.summary()    # its own lock, never under this one
        out["attention"] = ATTENTION.summary()
        out["upsample"] = UPSAMPLE.summary()
        out["norm"] = NORM.summary()
        out["expander"] = EXPANDER.summary()
        out["plan"] = PLAN.summary()
        out["device"] = DEVICE.summary()
        return out     # server/api.py adds "host" beside a host clock


#: jax.monitoring duration events of one executable's making -> the key
#: its seconds are summed under. jax names the function ``fun_name`` on all
#: three: ``run_chunk`` when tracing, ``jit(run_chunk)`` or
#: ``jit_run_chunk`` from lowering on (:func:`_fun_name` folds them).
_XLA_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_XLA_BACKEND = "/jax/core/compile/backend_compile_duration"
_XLA_STAGES = {
    _XLA_TRACE: "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    _XLA_BACKEND: "backend_s",
}
_XLA_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_XLA_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
#: rows of ``summary()["top"]``
_XLA_TOP = 10


def _fun_name(name: Any) -> str:
    name = str(name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name.removeprefix("jit_")


class XlaCompileStats:
    """Process-wide compile accounting from ``jax.monitoring``.

    ``backend_s`` is the time inside ``compile_or_get_cached``: an XLA
    compile when the persistent cache misses (or is off, or the compile is
    under its floor), a deserialise-and-load when it hits; ``executables``
    counts those calls. A function jitted inside another's trace makes no
    executable and its tracing lies inside the outer one's: it counts in
    ``traces``, its seconds only in the outermost function's ``trace_s``,
    so the seconds add up to time that passed. The persistent cache's
    hits and misses (a miss is counted when the new executable is written:
    compiles under the cache's floor are neither) happen inside a backend
    compile, so they are also counted in that function's row: a function
    that misses in every process is one whose cache key does not hold."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        #: per thread: ``depth`` of open jaxpr traces, ``compiling`` the
        #: function whose backend compile is open, ``hits`` of the
        #: persistent cache since the thread started
        self._thread = threading.local()
        self.clear()

    @staticmethod
    def _new_row() -> Dict[str, float]:
        return {"executables": 0, "traces": 0, "trace_s": 0.0,
                "lower_s": 0.0, "backend_s": 0.0, "cache_hits": 0,
                "cache_misses": 0}

    def clear(self) -> None:
        with self._lock:
            #: fun_name -> :meth:`_new_row`
            self.functions: Dict[str, Dict[str, float]] = {}  # guarded-by: _lock
            self.cache: Dict[str, float] = {  # guarded-by: _lock
                "cache_hits": 0, "cache_misses": 0, "cache_retrieval_s": 0.0}

    def on_event(self, event: str, **_kw: Any) -> None:
        key = _XLA_CACHE_EVENTS.get(event)
        if key is None:
            return
        fun = getattr(self._thread, "compiling", None)
        if key == "cache_hits":
            self._thread.hits = getattr(self._thread, "hits", 0) + 1
        with self._lock:
            self.cache[key] += 1
            if fun is not None:
                self.functions.setdefault(fun, self._new_row())[key] += 1

    def on_scalar(self, event: str, _value: float, **kw: Any) -> None:
        """jax reports a timed block's start as a scalar of its event."""
        if event == _XLA_TRACE:
            self._thread.depth = getattr(self._thread, "depth", 0) + 1
        elif event == _XLA_BACKEND:
            self._thread.compiling = _fun_name(kw.get("fun_name", ""))

    def on_duration(self, event: str, seconds: float, **kw: Any) -> None:
        key = _XLA_STAGES.get(event)
        if key is None:
            if event == _XLA_CACHE_RETRIEVAL:
                with self._lock:
                    self.cache["cache_retrieval_s"] += float(seconds)
            return
        if key == "trace_s":
            depth = self._thread.depth = max(
                0, getattr(self._thread, "depth", 1) - 1)
            if depth:
                seconds = 0.0   # inside the outer function's seconds
        fun = _fun_name(kw.get("fun_name", ""))
        with self._lock:
            row = self.functions.setdefault(fun, self._new_row())
            row[key] += float(seconds)
            if key == "trace_s":
                row["traces"] += 1
            elif key == "backend_s":
                row["executables"] += 1
        if key == "backend_s":
            self._thread.compiling = None
            # lazy: obs/spans.py pulls this module in through prometheus
            from stable_diffusion_webui_distributed_tpu.obs import (
                spans as obs_spans,
            )

            obs_spans.add_child("xla.compile", float(seconds), fun_name=fun,
                                stage="backend_compile")

    def executables(self, fun_name: str) -> int:
        with self._lock:
            return int(self.functions.get(fun_name, {}).get("executables", 0))

    def cache_hits_on_thread(self) -> int:
        """Executables the persistent cache has handed this thread: one
        more after a compile than before it means that compile's was
        (serving/aot.py asks)."""
        return getattr(self._thread, "hits", 0)

    def summary(self) -> Dict[str, Any]:
        """Totals, the functions the persistent cache missed (``missed``),
        then the :data:`_XLA_TOP` functions with most seconds."""
        with self._lock:
            rows: List[Dict[str, Any]] = [
                dict(row, fun_name=fun,
                     seconds=row["trace_s"] + row["lower_s"]
                     + row["backend_s"])
                for fun, row in self.functions.items()]
            out: Dict[str, Any] = dict(self.cache)
        for key in ("executables", "traces", "trace_s", "lower_s",
                    "backend_s"):
            out[key] = sum(row[key] for row in rows)
        out["functions"] = len(rows)
        out["missed"] = sorted(row["fun_name"] for row in rows
                               if row["cache_misses"])
        rows.sort(key=lambda row: row["seconds"], reverse=True)
        out["top"] = rows[:_XLA_TOP]
        return out


#: Process-wide compile accounting (fed once :func:`install_xla_listener`
#: has run).
XLA = XlaCompileStats()

_xla_install_lock = threading.Lock()
_xla_installed = False  # guarded-by: _xla_install_lock


def install_xla_listener() -> None:
    """Register :data:`XLA` with ``jax.monitoring``, once per process
    however often it is called. ``runtime/mesh.enable_compilation_cache``
    and ``Engine`` call it, so executables made before an engine exists
    (weights, warm-up) are counted from the first one on."""
    global _xla_installed
    import jax.monitoring

    with _xla_install_lock:
        if _xla_installed:
            return
        jax.monitoring.register_event_listener(XLA.on_event)
        jax.monitoring.register_scalar_listener(XLA.on_scalar)
        jax.monitoring.register_event_duration_secs_listener(
            XLA.on_duration)
        _xla_installed = True


#: per thread: the open :func:`capture_sites` list, if any
_SITES = threading.local()


@contextlib.contextmanager
def capture_sites() -> Iterator[List[list]]:
    """While open, every count the trace-time counters take on this thread
    (:data:`ATTENTION`, :data:`UPSAMPLE`, :data:`NORM`, ``EXPANDER``'s
    products, mixers and convs) is also appended to the list yielded, as
    JSON-able rows that :func:`replay_sites` counts again."""
    was = getattr(_SITES, "rows", None)
    rows: List[list] = []
    _SITES.rows = rows
    try:
        yield rows
    finally:
        _SITES.rows = was


def _note_site(counter: str, *args: Any) -> None:
    rows = getattr(_SITES, "rows", None)
    if rows is not None:
        rows.append([counter, *args])


def replay_sites(rows) -> None:
    """Count again what a trace counted (rows of :func:`capture_sites`)."""
    counters = {"attention": ATTENTION.record,
                "attention_layout": ATTENTION.record_layout,
                "upsample": UPSAMPLE.record,
                "norm_site": NORM.record,
                "product": EXPANDER.record_product,
                "route": EXPANDER.record_route,
                "mixer": EXPANDER.record_mixer,
                "conv": EXPANDER.record_conv,
                "delta": EXPANDER.record_delta,
                "delta_step": EXPANDER.record_delta_step,
                "norm": EXPANDER.record_norm,
                "unrotated": EXPANDER.record_unrotated,
                "ssm": EXPANDER.record_ssm,
                "joined": EXPANDER.record_joined,
                "multipliers": EXPANDER.record_multipliers,
                "shortcut": EXPANDER.record_shortcut,
                "latent_scaled": EXPANDER.record_latent_scaled,
                "tied_head": EXPANDER.record_tied_head}
    for counter, *args in rows:
        counters[counter](*args)


class AttentionSites:
    """Attention sites by the path they took, counted when a UNet (or
    ControlNet) is traced: ``tiled`` (ops/flash_attention.py), ``xla``
    (``jax.nn.dot_product_attention``), and ``ragged`` or ``ring`` where a
    request or a mesh asked for those. A site is one ``Attention`` call in
    one trace, so a model traced twice (the chunk executable, then the
    FLOPs pricing of pipeline/stepcache.py) counts twice; nothing is
    counted when an executable runs. A site of a looped language model
    (models/lm.py: its layers are alike and share ONE trace of a layer an
    executable, so it records one site) carries the passes it runs: ``P4``
    after its shape. A site whose keys are two ranges named apart (a latent
    layer's forked step: the prefill's rows, shared, and a sequence's own
    behind them) carries both: ``T4 S2560+256 D576`` is four sequences'
    rows over 2 560 shared slots and 256 own ones of width 576.

    ``tiled_layout`` is the tiled kernel's calls by the layout it was
    handed, counted where that is chosen (ops/flash_attention.py, at trace
    time too): ``lanes`` (heads side by side in ``(B, T, H*D)``, nothing
    transposed in HBM) or ``heads_major`` (``(B*H, T, D)`` through copies).
    A UNet's ``tiled`` sites are the sum of the two."""

    LAYOUTS = ("lanes", "heads_major")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.clear()

    def clear(self) -> None:
        with self._lock:
            #: (path, tokens, context tokens, head_dim, passes, own slots)
            #: -> sites
            self.sites: Dict[tuple, int] = defaultdict(int)  # guarded-by: _lock
            self.layouts: Dict[str, int] = defaultdict(int)  # guarded-by: _lock

    def record_layout(self, layout: str) -> None:
        _note_site("attention_layout", str(layout))
        with self._lock:
            self.layouts[layout] += 1

    def record(self, path: str, t: int, s: int, head_dim: int,
               passes: int = 1, own: int = 0) -> None:
        _note_site("attention", str(path), int(t), int(s), int(head_dim),
                   int(passes), int(own))
        with self._lock:
            self.sites[(path, int(t), int(s), int(head_dim), int(passes),
                        int(own))] += 1

    def summary(self) -> Dict[str, Any]:
        """``{"tiled": n, "xla": m, "tiled_layout": {"lanes": n,
        "heads_major": 0}, "by_shape": {"T4096 S4096 D64": {"tiled": n},
        ...}}``; other paths appear once they are taken."""
        with self._lock:
            sites = dict(self.sites)
            layouts = dict(self.layouts)
        out: Dict[str, Any] = {"tiled": 0, "xla": 0}
        out["tiled_layout"] = {layout: layouts.get(layout, 0)
                               for layout in self.LAYOUTS}
        by_shape: Dict[str, Dict[str, int]] = {}
        for (path, t, s, d, passes, own), n in sorted(sites.items()):
            out[path] = out.get(path, 0) + n
            shape = (f"T{t} S{s}" + (f"+{own}" if own else "") + f" D{d}"
                     + (f" P{passes}" if passes > 1 else ""))
            by_shape.setdefault(shape, {})[path] = n
        out["by_shape"] = by_shape
        return out


class FormSites:
    """Sites of one kind by the form they took, counted when a model is
    applied under a trace, never when it is initialised nor when an
    executable runs. Per trace, as :class:`AttentionSites` counts.
    ``FORMS`` names the forms ``summary()`` reports, ``NOTE`` the rows
    :func:`capture_sites` keeps for :func:`replay_sites`."""

    FORMS: tuple = ()
    NOTE = ""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.clear()

    def clear(self) -> None:
        with self._lock:
            self.sites: Dict[str, int] = defaultdict(int)  # guarded-by: _lock

    def record(self, form: str) -> None:
        _note_site(self.NOTE, str(form))
        with self._lock:
            self.sites[form] += 1

    def summary(self) -> Dict[str, int]:
        """``{form: sites}`` over ``FORMS``."""
        with self._lock:
            return {form: self.sites.get(form, 0) for form in self.FORMS}


class UpsampleSites(FormSites):
    """Upsample sites (nearest-2x then a 3x3 convolution: the UNet's
    ``up_{level}_us``, the VAE decoder's): ``folded`` (the four 2x2 phases
    as one convolution of the low-resolution input, ops/upsample.py) or
    ``plain`` (the 3x3 on the upsampled image: the int8 convolutions)."""

    FORMS = ("folded", "plain")
    NOTE = "upsample"


class NormSites(FormSites):
    """GroupNorm sites (models/unet.py ``GroupNorm32``: the UNet's, the
    VAE's): ``pinned`` (the whole norm reads the activation behind an
    optimisation barrier: a narrow activation of 128 x 128 positions or
    more), ``stats_pinned`` (only the statistics read a pinned copy, the
    normalise reads the activation as it lies: a narrow activation of two
    rows or fewer below that size) or ``plain``."""

    FORMS = ("pinned", "stats_pinned", "plain")
    NOTE = "norm_site"


class ExpanderStats:
    """What the resident prompt expander (models/lm.py, the engine's
    ``expand`` stage) did: tokens prefilled, tokens whose cache came from
    the kept instruction prefix, the sequences decoded (the images of a
    request that shared their decode steps count one each), tokens decoded
    over all sequences and the steps that made them apart (their quotient
    is the tokens a step), the distinct held experts whose kernels the
    decode steps streamed (summed over layers and steps: a step of several
    sequences reads an expert once however many of them chose it), the
    positions the decode steps' queries attended in a layer that keeps every
    position (``rows_attended``: a step at position ``p`` counts ``p + 1`` a
    sequence) and the positions read for them (``rows_read``: what lies
    before a fork is read once a step for all its sequences, so their
    quotient is the queries a row read serves; one sequence counts the same
    in both; ``rows_read_shared`` is the part of them before the fork, the
    shared range, and the rest the sequences' own rows), how many
    tokens the router
    sent to each expert held here (load and its imbalance), tokens none of
    whose chosen experts is held here, the cache positions the last
    request's sequences occupied and the bytes their caches took by layer
    kind (keys and
    values of full and sliding layers, a linear layer's recurrent state and
    kept convolution inputs, a latent layer's latents, a conv layer's kept
    rows), the instruction
    prefixes held as snapshots, the padded prefill rows that were masked
    out of a recurrence, and the residual streams a token has between
    sublayers with the Sinkhorn iterations each of their mixers runs (1
    and 0: a plain residual).
    ``expert_products`` counts expert layers by the product they took
    (ops/moe.py:choose) when the model was TRACED, as :class:`AttentionSites`
    counts its sites: nothing is counted when an executable runs.
    ``route_products`` counts them again by what stood between the router's
    logits and the product: ``kernel`` (ops/route_kernel.py, one launch in
    front of the expert kernel) or ``xla`` (ops/moe.py:route and the chain
    behind it).
    ``mixer_products`` counts the residual streams' mixers the same way, by
    the form ops/stream_mixer.py:choose gave them, and ``conv_mixers`` the
    short-convolution mixers (models/lm.py:ShortConv), by whether the
    trace was of one token (``step``) or of a longer chunk, and
    ``delta_mixers`` the gated-delta-rule mixers (models/lm.py:DeltaMixer)
    by the form their recurrence took (ops/delta_rule.py:form:
    ``recurrent`` one token, ``chunked`` a longer chunk,
    ``recurrent_forked`` one token each of several sequences, every one
    over a state of its own), and ``delta_steps`` the ``recurrent_forked``
    ones again by the step ops/delta_rule.py:step_form gave them:
    ``kernel`` (ops/delta_kernel.py: a head's state held in VMEM across its
    decay, write and read) or ``elementwise`` (XLA's fusions).
    ``state_bytes_stepped`` is what the decode steps read and wrote of
    linear layers' recurrent states and kept inputs (a step reads and
    writes each of its sequences' once a layer),
    ``fork_bytes_copied`` what forks copied of them (once a sequence; a
    buffer that keeps positions is never copied).
    ``sublayer_norms`` counts, the same way and by the same three forms of
    the executable traced, a layer's norms by where they stand (``pre``: a
    sublayer's input, ``post``: its output; a layer normed both ways
    counts under both), ``attention_unrotated`` the attention sites traced
    with no rotary table, and ``write_strength_bound`` is the largest write
    strength the last delta-rule mixer traced can give (``LMConfig.
    linear_write_scale``: 1.0 for ``sigmoid(b)``, 2.0 for ``2 sigmoid(b)``;
    0.0 before any was traced).
    ``ssm_mixers`` counts the selective state-space mixers
    (models/lm.py:SSMMixer) by the same three forms (ops/ssm.py:form),
    ``joined_layers`` the layers traced with several token mixers side by
    side under ONE norm, by the form of the executable, and
    ``multipliers_applied`` is how many of the forward multipliers of the
    last model traced are off 1 (``LMConfig.multipliers_applied``; 0: the
    forward pass scales nothing). ``state_bytes_stepped`` and
    ``fork_bytes_copied`` count a state-space part's state and kept inputs
    as they count a linear layer's.
    ``moe_shortcuts`` counts, by the same three forms of the executable
    traced, the expert layers whose routed sum crossed into the next layer
    (``LMConfig.moe_shortcut``: 0 where every sum is added in place),
    ``latent_scaled`` the latent-attention sites traced with a query or a
    latent scale off 1, by the form of the site (models/lm.py:latent_form),
    and ``zero_expert_picks`` the picks of the decode steps' rows that
    count which fell on zero-compute experts (``LMConfig.zero_experts``;
    counted on the device beside the load; over ``tokens_decoded`` and the
    expert layers: identity picks a token a router).
    ``tied_head`` counts, by the same three forms of the executable traced,
    the sites where the logits were read off the token table itself
    (``LMConfig.tied_head``: no ``lm_head`` exists; 0 where a model has a
    head of its own), and ``expert_picks_held`` the picks of the decode
    steps' rows that count which fell on an expert held here (the sum of
    the load those steps counted on the device; over ``experts_read``: the
    rows an expert's kernels serve once streamed, 1 at one sequence).
    ``expert_calls`` counts the routed sums the decode steps made (one an
    expert layer a step: on the chip one call of ops/moe_kernel.py each)
    and ``expert_calls_unread`` those whose rows chose no expert held
    here, so that the call read nothing of the experts' kernels (counted
    on the device: ``tokens_no_held_expert`` of a step of one sequence,
    the steps that streamed no expert of one of several).
    Of a looped model (``LMConfig.total_ut_steps`` over 1): ``layer_passes``,
    the passes of the whole stack its decode steps ran (every pass of every
    step, whichever the head read; over ``decode_steps``: passes a token); ``exit_pass``, the tokens made by the
    pass whose state the head read for them (index 0 is the first pass);
    ``exit_lambda_max``, the largest exit probability a gate gave. A model
    of one pass leaves them 0, empty and 0.
    ``requests`` counts SCANS (one ``record`` each: a request the
    dispatcher ran solo is one or more of its own). ``scans_joined`` are
    those among them whose sequences continued prompts of their own behind
    one kept instruction (pipeline/expand.py:expand_group: the requests of
    a dispatch group), ``requests_joined`` the requests they carried and
    ``prompts_joined`` their distinct prompts: 2.0 and 2.0 a joined scan
    where two clients' requests pair every time, 0 where every request
    runs alone. A joined scan's steps are in ``decode_steps`` ONCE and its
    tokens in ``tokens_decoded`` once a sequence, as a forked one's."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.clear()

    def clear(self) -> None:
        with self._lock:
            self.requests = 0          # guarded-by: _lock
            self.scans_joined = 0      # guarded-by: _lock
            self.requests_joined = 0   # guarded-by: _lock
            self.prompts_joined = 0    # guarded-by: _lock
            self.layer_passes = 0      # guarded-by: _lock
            self.exit_pass: List[int] = []  # guarded-by: _lock
            self.exit_lambda_max = 0.0  # guarded-by: _lock
            self.prefilled = 0         # guarded-by: _lock
            self.from_prefix = 0       # guarded-by: _lock
            self.sequences = 0         # guarded-by: _lock
            self.decoded = 0           # guarded-by: _lock
            self.decode_steps = 0      # guarded-by: _lock
            self.experts_read = 0      # guarded-by: _lock
            self.rows_attended = 0     # guarded-by: _lock
            self.rows_read = 0         # guarded-by: _lock
            self.rows_read_shared = 0  # guarded-by: _lock
            self.none_held = 0         # guarded-by: _lock
            self.expert_calls = 0      # guarded-by: _lock
            self.expert_calls_unread = 0  # guarded-by: _lock
            #: per expert layer, tokens sent to each held expert
            self.load: List[List[int]] = []  # guarded-by: _lock
            self.positions: Dict[str, int] = {}  # guarded-by: _lock
            self.state_bytes: Dict[str, int] = {}  # guarded-by: _lock
            self.prefix_snapshots = 0  # guarded-by: _lock
            self.padded_rows_masked = 0  # guarded-by: _lock
            self.residual_streams = 1  # guarded-by: _lock
            self.sinkhorn_iters = 0    # guarded-by: _lock
            self.products = {"kernel": 0, "loop": 0,
                             "grouped": 0}  # guarded-by: _lock
            self.routes = {"kernel": 0, "xla": 0}  # guarded-by: _lock
            self.mixers = {"kernel": 0, "loop": 0}  # guarded-by: _lock
            self.convs = {"step": 0, "chunk": 0}  # guarded-by: _lock
            self.deltas = {"recurrent": 0, "chunked": 0,
                           "recurrent_forked": 0}  # guarded-by: _lock
            self.delta_steps = {"kernel": 0,
                                "elementwise": 0}  # guarded-by: _lock
            self.state_bytes_stepped = 0  # guarded-by: _lock
            self.fork_bytes_copied = 0  # guarded-by: _lock
            self.norms = {placement: dict.fromkeys(self.deltas, 0)
                          for placement in ("pre", "post")}  # guarded-by: _lock
            self.unrotated = dict.fromkeys(self.deltas, 0)  # guarded-by: _lock
            self.write_bound = 0.0     # guarded-by: _lock
            self.ssms = dict.fromkeys(self.deltas, 0)  # guarded-by: _lock
            self.joined = dict.fromkeys(self.deltas, 0)  # guarded-by: _lock
            self.multipliers = 0       # guarded-by: _lock
            self.shortcuts = dict.fromkeys(self.deltas, 0)  # guarded-by: _lock
            self.latent_scaled = {"latent_absorbed": 0, "latent_expanded": 0,
                                  "latent_forked": 0}  # guarded-by: _lock
            self.zero_picks = 0        # guarded-by: _lock
            self.tied_heads = dict.fromkeys(self.deltas, 0)  # guarded-by: _lock
            self.picks_held = 0        # guarded-by: _lock

    def record_product(self, path: str) -> None:
        """One expert layer in one trace took product ``path``."""
        _note_site("product", str(path))
        with self._lock:
            self.products[path] += 1

    def record_route(self, path: str) -> None:
        """One expert layer's routing in one trace took form ``path``."""
        _note_site("route", str(path))
        with self._lock:
            self.routes[path] += 1

    def record_mixer(self, path: str) -> None:
        """One stream mixer in one trace took form ``path``."""
        _note_site("mixer", str(path))
        with self._lock:
            self.mixers[path] += 1

    def record_conv(self, form: str) -> None:
        """One short-convolution mixer in one trace, of ``form``."""
        _note_site("conv", str(form))
        with self._lock:
            self.convs[form] += 1

    def record_delta(self, form: str, bound: float = 1.0) -> None:
        """One gated-delta-rule mixer in one trace, of ``form``, whose
        write strength lies under ``bound``."""
        _note_site("delta", str(form), float(bound))
        with self._lock:
            self.deltas[form] += 1
            self.write_bound = float(bound)

    def record_delta_step(self, path: str) -> None:
        """One forked gated-delta-rule mixer in one trace stepped its
        states by ``path``."""
        _note_site("delta_step", str(path))
        with self._lock:
            self.delta_steps[path] += 1

    def record_norm(self, placement: str, form: str) -> None:
        """One sublayer norm in one trace of an executable of ``form``,
        before (``pre``) or after (``post``) its sublayer."""
        _note_site("norm", str(placement), str(form))
        with self._lock:
            self.norms[placement][form] += 1

    def record_unrotated(self, form: str) -> None:
        """One attention site in one trace that built no rotary table."""
        _note_site("unrotated", str(form))
        with self._lock:
            self.unrotated[form] += 1

    def record_ssm(self, form: str) -> None:
        """One state-space mixer in one trace, of ``form``."""
        _note_site("ssm", str(form))
        with self._lock:
            self.ssms[form] += 1

    def record_joined(self, form: str) -> None:
        """One layer of several token mixers under one norm in one trace
        of an executable of ``form``."""
        _note_site("joined", str(form))
        with self._lock:
            self.joined[form] += 1

    def record_multipliers(self, applied: int) -> None:
        """The model traced applies ``applied`` forward multipliers."""
        _note_site("multipliers", int(applied))
        with self._lock:
            self.multipliers = int(applied)

    def record_shortcut(self, form: str) -> None:
        """One expert layer in one trace of an executable of ``form``
        whose routed sum crosses into the next layer."""
        _note_site("shortcut", str(form))
        with self._lock:
            self.shortcuts[form] += 1

    def record_latent_scaled(self, form: str) -> None:
        """One latent-attention site of ``form`` in one trace whose
        queries or latent are scaled."""
        _note_site("latent_scaled", str(form))
        with self._lock:
            self.latent_scaled[form] += 1

    def record_tied_head(self, form: str) -> None:
        """One trace of an executable of ``form`` read its logits off the
        token table."""
        _note_site("tied_head", str(form))
        with self._lock:
            self.tied_heads[form] += 1

    def record(self, *, prefilled: int, from_prefix: int, sequences: int,
               decoded: int, decode_steps: int, experts_read: int, load,
               none_held: int,
               positions: Dict[str, int], state_bytes: Dict[str, int],
               prefix_snapshots: int, padded_rows_masked: int,
               residual_streams: int, sinkhorn_iters: int,
               rows_attended: int = 0, rows_read: int = 0,
               rows_read_shared: int = 0,
               layer_passes: int = 0, exit_pass=(),
               exit_lambda_max: float = 0.0, state_bytes_stepped: int = 0,
               fork_bytes_copied: int = 0, zero_expert_picks: int = 0,
               expert_picks_held: int = 0, expert_calls: int = 0,
               expert_calls_unread: int = 0, requests_joined: int = 0,
               prompts_joined: int = 0) -> None:
        """``load`` is (expert layers, held experts) counts of one
        request; ``decode_steps`` the steps its decode executables ran
        (whole chunks, so at least ``decoded / sequences - 1``), each a
        token of every one of its ``sequences``."""
        rows = [[int(n) for n in row] for row in load]
        with self._lock:
            self.requests += 1
            self.scans_joined += bool(requests_joined)
            self.requests_joined += int(requests_joined)
            self.prompts_joined += int(prompts_joined)
            self.prefilled += int(prefilled)
            self.from_prefix += int(from_prefix)
            self.sequences += int(sequences)
            self.decoded += int(decoded)
            self.decode_steps += int(decode_steps)
            self.experts_read += int(experts_read)
            self.rows_attended += int(rows_attended)
            self.rows_read += int(rows_read)
            self.rows_read_shared += int(rows_read_shared)
            self.none_held += int(none_held)
            if len(self.load) != len(rows):
                self.load = rows
            else:
                self.load = [[a + b for a, b in zip(old, new)]
                             for old, new in zip(self.load, rows)]
            self.positions = dict(positions)
            self.state_bytes = dict(state_bytes)
            self.prefix_snapshots = int(prefix_snapshots)
            self.padded_rows_masked += int(padded_rows_masked)
            self.residual_streams = int(residual_streams)
            self.sinkhorn_iters = int(sinkhorn_iters)
            self.layer_passes += int(layer_passes)
            self.state_bytes_stepped += int(state_bytes_stepped)
            self.fork_bytes_copied += int(fork_bytes_copied)
            self.zero_picks += int(zero_expert_picks)
            self.picks_held += int(expert_picks_held)
            self.expert_calls += int(expert_calls)
            self.expert_calls_unread += int(expert_calls_unread)
            if len(exit_pass):
                old = self.exit_pass or [0] * len(exit_pass)
                self.exit_pass = [a + int(b)
                                  for a, b in zip(old, exit_pass)]
            self.exit_lambda_max = max(self.exit_lambda_max,
                                       float(exit_lambda_max))

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            flat = [n for row in self.load for n in row]
            mean = sum(flat) / len(flat) if flat else 0.0
            return {
                "requests": self.requests,
                "scans_joined": self.scans_joined,
                "requests_joined": self.requests_joined,
                "prompts_joined": self.prompts_joined,
                "tokens_prefilled": self.prefilled,
                "tokens_from_prefix_cache": self.from_prefix,
                "sequences": self.sequences,
                "tokens_decoded": self.decoded,
                "decode_steps": self.decode_steps,
                "experts_read": self.experts_read,
                "rows_attended": self.rows_attended,
                "rows_read": self.rows_read,
                "rows_read_shared": self.rows_read_shared,
                "tokens_no_held_expert": self.none_held,
                "expert_tokens": [list(row) for row in self.load],
                "expert_load_max_over_mean":
                    (max(flat) / mean if mean else 0.0),
                "cache_positions": dict(self.positions),
                "state_bytes": dict(self.state_bytes),
                "prefix_snapshots": self.prefix_snapshots,
                "padded_rows_masked": self.padded_rows_masked,
                "residual_streams": self.residual_streams,
                "sinkhorn_iters": self.sinkhorn_iters,
                "expert_products": dict(self.products),
                "route_products": dict(self.routes),
                "mixer_products": dict(self.mixers),
                "conv_mixers": dict(self.convs),
                "delta_mixers": dict(self.deltas),
                "delta_steps": dict(self.delta_steps),
                "state_bytes_stepped": self.state_bytes_stepped,
                "fork_bytes_copied": self.fork_bytes_copied,
                "sublayer_norms": {placement: dict(by_form) for
                                   placement, by_form in self.norms.items()},
                "attention_unrotated": dict(self.unrotated),
                "write_strength_bound": self.write_bound,
                "ssm_mixers": dict(self.ssms),
                "joined_layers": dict(self.joined),
                "multipliers_applied": self.multipliers,
                "moe_shortcuts": dict(self.shortcuts),
                "latent_scaled": dict(self.latent_scaled),
                "zero_expert_picks": self.zero_picks,
                "tied_head": dict(self.tied_heads),
                "expert_picks_held": self.picks_held,
                "expert_calls": self.expert_calls,
                "expert_calls_unread": self.expert_calls_unread,
                "layer_passes": self.layer_passes,
                "exit_pass": list(self.exit_pass),
                "exit_lambda_max": self.exit_lambda_max,
            }


class PlanStats:
    """Lookups of what a request's plan keeps by key (runtime/kept.py):
    the sigma ``ladder`` (samplers/kdiffusion.py, every caller of
    ``build_sigmas``) and SDXL's time-id embedding, ``added_cond``
    (pipeline/engine.py). A ``build`` ran the device ops and the fetch, a
    ``hit`` ran nothing; after a warm-up request every request of one
    sampler, step count and size should only hit.

    ``ahead``: the first group of an expanded txt2img range made while the
    expander decoded (pipeline/engine.py:Drawn): ``drawn`` by the closure
    that ran under a decode chunk, ``taken`` by a range that used one,
    ``dropped`` where one was drawn and not used (an interrupt, a group of
    another shape). Every expanded request of a steady window draws one
    and takes it."""

    AHEAD = ("drawn", "taken", "dropped")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.clear()

    def clear(self) -> None:
        with self._lock:
            #: (table, "hits" | "builds") -> lookups; ("ahead", kind)
            self.lookups: Dict[tuple, int] = defaultdict(int)  # guarded-by: _lock

    def record(self, table: str, hit: bool) -> None:
        with self._lock:
            self.lookups[(table, "hits" if hit else "builds")] += 1

    def record_ahead(self, kind: str) -> None:
        with self._lock:
            self.lookups[("ahead", kind)] += 1

    def summary(self) -> Dict[str, Dict[str, int]]:
        """``{"ladder": {"hits": n, "builds": m}, "added_cond": {...},
        "ahead": {"drawn": n, "taken": n, "dropped": 0}}``."""
        with self._lock:
            lookups = dict(self.lookups)
        out = {table: {kind: lookups.get((table, kind), 0)
                       for kind in ("hits", "builds")}
               for table in ("ladder", "added_cond")}
        out["ahead"] = {kind: lookups.get(("ahead", kind), 0)
                        for kind in self.AHEAD}
        return out


class HostStats:
    """``serving.host``, the time no request's tree covers; one a host
    clock (obs/watchdog.py), which feeds ``ticks``, ``stalls`` and the
    collections. server/api.py feeds ``exchanges`` and, where one began
    with none in flight, the ms since the last ended (``betweens``)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.counts: Dict[str, Any] = dict(  # guarded-by: _lock
            ticks=0, stalls=0, stall_ms=0.0, stall_ms_max=0.0,
            gc_pause_ms=0.0, gc_pause_ms_max=0.0,
            gc_collections={"0": 0, "1": 0, "2": 0}, exchanges=0,
            betweens=0, between_ms=0.0, between_ms_max=0.0)
        self._in_flight = 0  # guarded-by: _lock
        self._idle_since: Any = None  # guarded-by: _lock

    @staticmethod
    def _add(counts: Dict[str, Any], key: str, ms: float) -> None:
        counts[key] += ms
        counts[key + "_max"] = max(counts[key + "_max"], ms)

    def ticked(self, stall_s: float = 0.0) -> None:
        with self._lock:
            self.counts["ticks"] += 1
            if stall_s:
                self.counts["stalls"] += 1
                self._add(self.counts, "stall_ms", stall_s * 1e3)

    def collected(self, generation: int, seconds: float) -> None:
        with self._lock:
            self.counts["gc_collections"][str(generation)] += 1
            self._add(self.counts, "gc_pause_ms", seconds * 1e3)

    def exchange_began(self, at: float) -> Any:
        """The seconds the server had been empty, where it was."""
        with self._lock:
            self.counts["exchanges"] += 1
            self._in_flight += 1
            since, self._idle_since = self._idle_since, None
            if since is None or at < since:     # accepted with one in flight
                return None
            self.counts["betweens"] += 1
            self._add(self.counts, "between_ms", (at - since) * 1e3)
            return at - since

    def exchange_ended(self, at: float) -> None:
        with self._lock:
            self._in_flight -= 1
            if self._in_flight <= 0:
                self._in_flight, self._idle_since = 0, at

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self.counts, gc_collections=dict(
                self.counts["gc_collections"]))


class DeviceStats:
    """``serving.device``: what the device did with the executables the
    requests enqueued, as the program's own spans have it (obs/spans.py:
    ``device.run``), profiler off. Fed once a request, at its end, from
    its tree: the enqueue path takes no lock of this. ``busy_s`` by kind
    sums the ``device.run`` spans; ``idle_s`` is the requests' device
    sections (``dispatch.device``) less the ``device.run`` inside them;
    ``dry_enqueues`` the enqueues that found the device with nothing left
    to run (it waited for the host); ``late_fences`` of ``fences`` the
    waits that found the device already done."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.clear()

    def clear(self) -> None:
        with self._lock:
            self.counts: Dict[str, Any] = dict(  # guarded-by: _lock
                requests=0, dispatches={}, busy_s={}, dry_enqueues=0,
                fences=0, late_fences=0, idle_s=0.0)

    def record(self, dispatches: Dict[str, int], busy_s: Dict[str, float],
               dry_enqueues: int, fences: int, late_fences: int,
               idle_s: float) -> None:
        with self._lock:
            counts = self.counts
            counts["requests"] += 1
            for key, by_kind in (("dispatches", dispatches),
                                 ("busy_s", busy_s)):
                for kind, n in by_kind.items():
                    counts[key][kind] = counts[key].get(kind, 0) + n
            counts["dry_enqueues"] += dry_enqueues
            counts["fences"] += fences
            counts["late_fences"] += late_fences
            counts["idle_s"] += idle_s

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            out = dict(self.counts, dispatches=dict(self.counts["dispatches"]),
                       busy_s=dict(self.counts["busy_s"]))
        # the totals a quotient of two counters can name
        out["dispatches_total"] = sum(out["dispatches"].values())
        out["busy_s_total"] = sum(out["busy_s"].values())
        return out


#: Process-wide counts of kept-plan lookups (``summary()["plan"]``).
PLAN = PlanStats()

#: Process-wide device counters of the requests' trees (``["device"]``).
DEVICE = DeviceStats()

#: Process-wide prompt-expander counters (``summary()["expander"]``).
EXPANDER = ExpanderStats()

#: Process-wide count of attention sites by path (fed at trace time).
ATTENTION = AttentionSites()

#: Process-wide count of upsample sites by form (fed at trace time).
UPSAMPLE = UpsampleSites()

#: Process-wide count of GroupNorm sites by form (fed at trace time).
NORM = NormSites()

#: Process-wide metrics instance (mirrors ``trace.STATS``).
METRICS = DispatchMetrics()

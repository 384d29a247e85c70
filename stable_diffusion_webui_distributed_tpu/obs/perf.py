"""Perf ledger: always-on device-time attribution and self-checking budgets.

PERF.md's roofline was computed by hand from one-shot BENCH files; this
module makes the same numbers *live*. The serving dispatcher reports every
device dispatch here — host-observed seconds and true-vs-padded shapes —
and the ledger folds them into per-(bucket, cadence, precision) groups
carrying:

- **padding waste**: true-requested pixels vs padded-dispatched pixels —
  the per-bucket version of BENCH_serving.json's ``avg_padding_ratio``,
  the gauge the ragged-dispatch work will be judged against (ROADMAP);
- **compile latency** per stage kind (``Engine._cached`` reports builds);
- **SLO attainment + burn rate** per (tenant, class) when the fleet gate
  is on (burn rate = windowed miss fraction / error budget, the
  Google-SRE multi-window signal shape).

Everything is gated on ``SDTPU_PERF`` (default OFF): with the knob off
every record call is a cheap no-op and the dispatch path stays
byte-identical to the uninstrumented build. Recording is host-side
arithmetic under one lock — never a device sync. ``/internal/perf``
serves :meth:`PerfLedger.summary`; ``obs/prometheus.py`` renders the same
groups as ``sdtpu_perf_*`` families.

:func:`executables_census` is the compile-budget self-check behind
``/internal/executables``: it groups the engine's live compiled-stage
keys by shape bucket and alarms when any bucket exceeds the contracted
≤2 step-cache × ≤3 precision chunk executables (PR 3 / PR 7 invariants).
"""

from __future__ import annotations

import threading
from collections import OrderedDict, deque
from typing import Any, Deque, Dict, Iterable, List, Optional, Tuple

from stable_diffusion_webui_distributed_tpu.runtime.config import (
    env_flag, env_float, env_int,
)

#: Default cap on distinct (bucket, cadence, precision) ledger groups and
#: on distinct (tenant, class) SLO rows — adversarial tenant names must
#: not grow the ledger without bound (oldest-touched rows are evicted).
DEFAULT_GROUPS = 64
#: Sliding window (dispatch completions) behind the SLO burn-rate gauge.
SLO_WINDOW = 64
#: Default SLO attainment target: burn rate 1.0 means missing exactly the
#: (1 - target) error budget.
DEFAULT_SLO_TARGET = 0.95

#: Contracted executable budget per shape bucket (PR 3: plain + step-cache
#: variants; PR 7: ≤3 precision rungs over the same param tree).
STEP_CACHE_BUDGET = 2
PRECISION_BUDGET = 3
#: Distinct traced-LoRA (rank_bucket, slot_count) cells allowed per shape
#: bucket (SDTPU_LORA_TRACED): adapter NAMES never mint executables — only
#: ladder cells do — so this bounds the whole adapter-diverse workload.
#: The adapterless variant ("" sig) rides outside this allowance.
LORA_BUDGET = 4

#: bf16 peak FLOPs/s per chip, keyed by the exact ``device_kind`` string
#: JAX reports on that chip (``jax.devices()[0].device_kind``). Source:
#: Google Cloud documentation, "TPU v5e" system architecture — 197 TFLOP/s
#: bf16 and 393 TOP/s int8 per chip. A kind that is not a key here has no
#: peak: add a row with its source once a run on that chip has printed its
#: kind. bench.py's MFU estimate shares this table via
#: :func:`peak_flops_for`.
PEAK_FLOPS_BF16: Dict[str, float] = {
    "TPU v5 lite": 197e12,
}
#: int8 MXU peak relative to bf16 (BENCH_int8.json's mxu_peak_ratio).
INT8_PEAK_RATIO = 2.0


def enabled() -> bool:
    """Live read of the master knob — tests and bench phases flip the env
    var at runtime, so this is re-read per record call (it is one dict
    lookup; the off path must stay near-free)."""
    return env_flag("SDTPU_PERF", False)


def peak_flops_for(device_kind: str, precision: str = "bf16"
                   ) -> Optional[float]:
    """Peak FLOPs/s for a device kind at a serving precision, or ``None``
    when the kind is not in :data:`PEAK_FLOPS_BF16` (CPU dev boxes, chips
    the table has not met: no denominator is invented)."""
    peak = PEAK_FLOPS_BF16.get(str(device_kind or ""))
    if peak is not None and str(precision or "").startswith("int8"):
        return peak * INT8_PEAK_RATIO
    return peak


def _device_kind() -> str:
    """Best-effort device kind for the summary. jax is already imported
    by the time anything dispatches; failure means "unknown", never an
    exception on the dispatch path."""
    try:
        import jax

        return jax.devices()[0].device_kind
    except Exception:  # noqa: BLE001 — telemetry must not fail dispatch
        return ""


class PerfLedger:
    """Thread-safe accumulator behind ``/internal/perf``.

    Group rows and SLO rows are bounded ``OrderedDict`` rings: recording
    touches move a row to the back, and inserts beyond ``max_groups``
    evict the least-recently-touched row (counted, so the summary can say
    coverage was dropped rather than silently truncating)."""

    def __init__(self, max_groups: Optional[int] = None,
                 slo_target: Optional[float] = None) -> None:
        if max_groups is None:
            max_groups = env_int("SDTPU_PERF_GROUPS", DEFAULT_GROUPS)
        if slo_target is None:
            slo_target = env_float("SDTPU_PERF_SLO_TARGET",
                                   DEFAULT_SLO_TARGET)
        self.max_groups = max(1, int(max_groups or DEFAULT_GROUPS))
        self.slo_target = min(0.9999, max(0.0, float(slo_target)))
        self._lock = threading.Lock()
        self._groups: \
            "OrderedDict[Tuple[str, int, str, str], Dict[str, float]]" \
            = OrderedDict()  # guarded-by: _lock
        self._groups_evicted = 0  # guarded-by: _lock
        self._compiles: Dict[str, Dict[str, float]] = {}  # guarded-by: _lock
        #: artifact deserializes, keyed like _compiles but never mixed in
        #: (serving/aot.py record_compile(source="aot_load"))
        self._aot_loads: Dict[str, Dict[str, float]] = {}  # guarded-by: _lock
        self._slo: "OrderedDict[Tuple[str, str], Dict[str, Any]]" \
            = OrderedDict()  # guarded-by: _lock
        self._slo_evicted = 0  # guarded-by: _lock
        self._last_dispatch: Optional[Dict[str, Any]] = None  # guarded-by: _lock
        self._device_kind: Optional[str] = None  # guarded-by: _lock

    # -- recording (dispatcher / engine side) ------------------------------

    def record_dispatch(self, *, bucket: str, cadence: int, precision: str,
                        lora: str = "",
                        device_s: float, requests: int,
                        batch_raw: int, batch_run: int, true_pixels: int,
                        padded_pixels: int, masked_pixels: int = 0,
                        true_tokens: int = 0, padded_tokens: int = 0,
                        hbm: Optional[Dict[str, int]] = None
                        ) -> None:
        """One device dispatch: host-observed seconds + true-vs-padded
        shape accounting.
        No-op (and never raises) when ``SDTPU_PERF`` is off.

        ``padded_pixels`` counts everything RESIDENT in the dispatch
        (bucket area x batch_run); ``masked_pixels`` is the slice of that
        the ragged attention kernel masks instead of attending to —
        resident HBM but no attention FLOPs — so the summary can split
        masked padding from compute padding. ``true_tokens`` /
        ``padded_tokens`` carry the conditioning's true-vs-padded token
        counts behind the ``token_padding_ratio`` gauge.

        ``hbm`` is the device-memory sample for this dispatch
        (``obs/tsdb.dispatch_memory_sample()``: bytes_in_use /
        peak_bytes_in_use / live_buffers keys as available) — ``None``
        on CPU or when memory_stats is unsupported, and the group row
        then reports null watermarks rather than fabricating them.

        ``lora`` is the traced-adapter cell label (``"r8s1"``-style, "" on
        adapterless and merged-path dispatches) — appended as the LAST
        group-key axis so adapter-active traffic gets its own rows
        without disturbing key[0..2] consumers."""
        if not enabled():
            return
        try:
            key = (str(bucket), int(cadence), str(precision), str(lora))
            with self._lock:
                if self._device_kind is None:
                    self._device_kind = _device_kind()
                g = self._groups.get(key)
                if g is None:
                    if len(self._groups) >= self.max_groups:
                        self._groups.popitem(last=False)
                        self._groups_evicted += 1
                    g = {"dispatches": 0, "requests": 0, "device_s": 0.0,
                         "true_pixels": 0, "padded_pixels": 0,
                         "batch_raw": 0, "batch_run": 0, "masked_pixels": 0,
                         "true_tokens": 0, "padded_tokens": 0}
                    self._groups[key] = g
                else:
                    self._groups.move_to_end(key)
                g["dispatches"] += 1
                g["requests"] += int(requests)
                g["device_s"] += max(0.0, float(device_s))
                g["true_pixels"] += int(true_pixels)
                g["padded_pixels"] += int(padded_pixels)
                g["batch_raw"] += int(batch_raw)
                g["batch_run"] += int(batch_run)
                g["masked_pixels"] += int(masked_pixels)
                g["true_tokens"] += int(true_tokens)
                g["padded_tokens"] += int(padded_tokens)
                if hbm:
                    # watermark semantics: keep the highest peak / latest
                    # in-use the group has seen (never fabricated on CPU)
                    if hbm.get("peak_bytes_in_use") is not None:
                        g["hbm_peak_bytes"] = max(
                            int(g.get("hbm_peak_bytes", 0)),
                            int(hbm["peak_bytes_in_use"]))
                    if hbm.get("bytes_in_use") is not None:
                        g["hbm_bytes_in_use"] = int(hbm["bytes_in_use"])
                    if hbm.get("live_buffers") is not None:
                        g["live_buffers"] = int(hbm["live_buffers"])
                compiles_total = sum(int(c["count"])
                                     for c in self._compiles.values())
                self._last_dispatch = self._dispatch_entry(
                    key, g, device_s, compiles_total)
        except Exception:  # noqa: BLE001 — telemetry must not fail dispatch
            pass

    def record_compile(self, kind: str, seconds: float,
                       source: str = "fresh_compile") -> None:
        """One compiled-stage build (``Engine._cached``); also feeds the
        per-kind Prometheus compile-latency histogram. ``source`` splits
        the accounting: ``fresh_compile`` is a real XLA build,
        ``aot_load`` is an artifact deserialize (serving/aot.py) — the
        two land in separate accumulators and separate Prometheus
        families so MFU/ledger analysis never mistakes a 200ms hydration
        for a compile."""
        if not enabled():
            return
        try:
            aot = str(source) == "aot_load"
            with self._lock:
                table = self._aot_loads if aot else self._compiles
                c = table.setdefault(
                    str(kind), {"count": 0, "total_s": 0.0, "max_s": 0.0,
                                "last_s": 0.0})
                c["count"] += 1
                c["total_s"] += max(0.0, float(seconds))
                c["max_s"] = max(c["max_s"], float(seconds))
                c["last_s"] = float(seconds)
            from stable_diffusion_webui_distributed_tpu.obs import (
                prometheus as obs_prom,
            )

            if aot:
                obs_prom.observe_aot_load(str(kind), float(seconds))
            else:
                obs_prom.observe_compile(str(kind), float(seconds))
        except Exception:  # noqa: BLE001 — telemetry must not fail compiles
            pass

    def record_slo(self, *, tenant: str, cls: str, slo_s: float,
                   latency_s: float, ok: bool = True) -> None:
        """One fleet-gated request completion against its resolved SLO.
        ``met`` requires both success and on-time delivery — an errored
        request burns the same budget as a late one."""
        if not enabled():
            return
        try:
            met = bool(ok) and float(latency_s) <= float(slo_s)
            key = (str(tenant), str(cls))
            with self._lock:
                row = self._slo.get(key)
                if row is None:
                    if len(self._slo) >= self.max_groups:
                        self._slo.popitem(last=False)
                        self._slo_evicted += 1
                    row = {"total": 0, "met": 0, "slo_s": float(slo_s),
                           "window": deque(maxlen=SLO_WINDOW)}
                    self._slo[key] = row
                else:
                    self._slo.move_to_end(key)
                row["total"] += 1
                row["met"] += 1 if met else 0
                row["slo_s"] = float(slo_s)
                row["window"].append(1 if met else 0)
        except Exception:  # noqa: BLE001 — telemetry must not fail dispatch
            pass

    # -- derivation --------------------------------------------------------

    @staticmethod
    def _dispatch_entry(key: Tuple[str, int, str, str],
                        g: Dict[str, float], device_s: float,
                        compiles_total: int) -> Dict[str, Any]:
        # static: the caller (record_dispatch, under _lock) passes the
        # guarded values in, so this stays pure derivation; computes the
        # flight-recorder snapshot for THIS dispatch (instant values, not
        # the group's running sums)
        true_px = g["true_pixels"]
        padded_px = g["padded_pixels"]
        return {
            "bucket": key[0], "cadence": key[1], "precision": key[2],
            "lora": key[3],
            "device_s": round(float(device_s), 6),
            "padding_ratio": (padded_px / true_px) if true_px else None,
            "compiles_total": int(compiles_total),
        }

    @staticmethod
    def _group_row(key: Tuple[str, int, str, str],
                   g: Dict[str, float]) -> Dict[str, Any]:
        # static for the same reason as _dispatch_entry (LK001 discipline)
        true_px, padded_px = g["true_pixels"], g["padded_pixels"]
        ratio = (padded_px / true_px) if true_px else None
        # ragged split (defaulted 0 so pre-ragged rows read identically):
        # masked pixels are resident-but-not-attended — subtracting them
        # gives the padding you actually pay attention FLOPs for
        masked_px = int(g.get("masked_pixels", 0))
        true_tok = int(g.get("true_tokens", 0))
        padded_tok = int(g.get("padded_tokens", 0))
        return {
            "bucket": key[0], "cadence": key[1], "precision": key[2],
            "lora": key[3],
            "dispatches": int(g["dispatches"]),
            "requests": int(g["requests"]),
            "device_s": g["device_s"],
            "padding_ratio": ratio,
            "padding_waste": (1.0 - true_px / padded_px) if padded_px
            else None,
            "batch_raw": int(g["batch_raw"]),
            "batch_run": int(g["batch_run"]),
            "masked_pixels": masked_px,
            "compute_padding_ratio": ((padded_px - masked_px) / true_px)
            if true_px else None,
            "token_padding_ratio": (padded_tok / true_tok)
            if true_tok else None,
            # device-memory watermark (defaulted None: CPU rows and
            # pre-telemetry rows read identically — never fabricated)
            "hbm_peak_bytes": g.get("hbm_peak_bytes"),
            "hbm_bytes_in_use": g.get("hbm_bytes_in_use"),
            "live_buffers": g.get("live_buffers"),
        }

    def _slo_row(self, key: Tuple[str, str],
                 row: Dict[str, Any]) -> Dict[str, Any]:
        window = list(row["window"])
        misses = window.count(0)
        budget = 1.0 - self.slo_target
        burn = (misses / len(window)) / budget if window and budget > 0 \
            else 0.0
        return {
            "tenant": key[0], "class": key[1], "slo_s": row["slo_s"],
            "total": row["total"], "met": row["met"],
            "attainment": row["met"] / row["total"] if row["total"] else None,
            "window": len(window), "window_misses": misses,
            "burn_rate": burn,
        }

    # -- readers -----------------------------------------------------------

    def last_dispatch(self) -> Optional[Dict[str, Any]]:
        """The most recent dispatch's perf snapshot (flight recorder)."""
        with self._lock:
            return dict(self._last_dispatch) if self._last_dispatch else None

    def summary(self) -> Dict[str, Any]:
        """The ``/internal/perf`` body."""
        with self._lock:
            groups = [self._group_row(k, g)
                      for k, g in self._groups.items()]
            slo = [self._slo_row(k, r) for k, r in self._slo.items()]
            compiles = {k: dict(c) for k, c in self._compiles.items()}
            aot_loads = {k: dict(c) for k, c in self._aot_loads.items()}
            evicted, slo_evicted = self._groups_evicted, self._slo_evicted
            device_kind = self._device_kind or ""
        # hit rate over the stage materializations this ledger saw:
        # loads / (loads + fresh compiles); None until either happens
        n_loads = sum(int(c["count"]) for c in aot_loads.values())
        n_fresh = sum(int(c["count"]) for c in compiles.values())
        out = {
            "enabled": enabled(),
            "device_kind": device_kind,
            "peak_flops_bf16": peak_flops_for(device_kind, "bf16"),
            "groups": groups,
            "groups_evicted": evicted,
            "compiles": compiles,
            "aot_loads": aot_loads,
            "aot_hit_rate": (n_loads / (n_loads + n_fresh)
                             if (n_loads + n_fresh) else None),
            "slo": slo,
            "slo_evicted": slo_evicted,
            "slo_target": self.slo_target,
        }
        try:
            # caching tier (SDTPU_CACHE): hit/miss/bytes per layer ride
            # along in the perf body so one scrape answers "is the cache
            # pulling its weight"; {"enabled": False} when gated off
            from stable_diffusion_webui_distributed_tpu import cache
            out["cache"] = (cache.summary() if cache.enabled()
                            else {"enabled": False})
        except Exception:  # noqa: BLE001 — perf body stays best-effort
            out["cache"] = {"enabled": False}
        return out

    def clear(self) -> None:
        with self._lock:
            self._groups.clear()
            self._compiles.clear()
            self._aot_loads.clear()
            self._slo.clear()
            self._groups_evicted = 0
            self._slo_evicted = 0
            self._last_dispatch = None
            self._device_kind = None


#: Process-wide ledger (mirrors METRICS / STATS / TRACER).
LEDGER = PerfLedger()


# -- executable census -------------------------------------------------------

def census_from_keys(keys: Iterable[Tuple],
                     step_cache_budget: int = STEP_CACHE_BUDGET,
                     precision_budget: int = PRECISION_BUDGET,
                     lora_budget: int = LORA_BUDGET
                     ) -> Dict[str, Any]:
    """Group compiled-stage cache keys by shape bucket and check the
    chunk-executable budget. A chunk key is a ``pipeline/denoise.py:Variant``
    (read by name through its ``parse_key``): the step-cache bit, the
    precision and the traced-LoRA cell (``lora_sig``: "" adapterless,
    ``"lora:rXsY"`` per ladder cell) are the budgeted variants, every other
    field identifies the bucket. The lora allowance is PER CELL, not per
    adapter — any number of adapter combos share a cell's executables,
    which is the recompile-free serving contract."""
    from stable_diffusion_webui_distributed_tpu.pipeline.denoise import (
        parse_key,
    )

    buckets: "OrderedDict[Tuple, Dict[str, Any]]" = OrderedDict()
    other = 0
    total_chunks = 0
    for k in keys:
        v = parse_key(k)
        if v is None:
            other += 1
            continue
        total_chunks += 1
        ident = v._replace(lora_sig="", step_cache=False, precision="")
        b = buckets.get(ident)
        if b is None:
            b = {
                "bucket": f"{v.sampler}/{v.steps}st {v.width}x{v.height} "
                          f"b{v.batch}",
                "executables": 0,
                "step_cache_variants": set(),
                "precision_variants": set(),
                "lora_variants": set(),
            }
            buckets[ident] = b
        b["executables"] += 1
        b["step_cache_variants"].add(v.step_cache)
        b["precision_variants"].add(str(v.precision))
        b["lora_variants"].add(v.lora_sig)
    rows: List[Dict[str, Any]] = []
    over: List[str] = []
    for b in buckets.values():
        sc, prec = b["step_cache_variants"], b["precision_variants"]
        n_lora = len([v for v in b["lora_variants"] if v])
        over_budget = (len(sc) > step_cache_budget
                       or len(prec) > precision_budget
                       or n_lora > lora_budget
                       or b["executables"] > step_cache_budget
                       * precision_budget * (1 + n_lora))
        rows.append({
            "bucket": b["bucket"],
            "executables": b["executables"],
            "step_cache_variants": len(sc),
            "precisions": sorted(prec),
            "lora_variants": n_lora,
            "over_budget": over_budget,
        })
        if over_budget:
            over.append(b["bucket"])
    return {
        "buckets": rows,
        "chunk_executables": total_chunks,
        "other_executables": other,
        "budget": {"step_cache": step_cache_budget,
                   "precision": precision_budget,
                   "lora": lora_budget,
                   "per_bucket": step_cache_budget * precision_budget},
        "over_budget": over,
        "alarm": bool(over),
    }


def executables_census(engine: Any) -> Dict[str, Any]:
    """Live census over an engine's compiled-stage cache (the
    ``/internal/executables`` body). Pure read — no compiles, no device
    work."""
    return census_from_keys(engine.executable_keys())

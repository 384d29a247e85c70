"""Config schema + persistence.

Capability parity with the reference's config system
(/root/reference/scripts/spartan/pmodels.py:4-46 and
/root/reference/scripts/spartan/world.py:616-722): a pydantic-validated JSON
file holding the worker registry (here: TPU slices / serving backends), each
worker's benchmark calibration (avg images-per-minute, ETA error history,
pixel cap), the shared benchmark payload, and scheduler settings
(``job_timeout``, enable flags, complementary production, step scaling).
Includes legacy-format migration and corrupt-file quarantine
(world.py:632-659 semantics).
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

from pydantic import BaseModel, Field, field_validator


def _logger():
    """Lazy logger lookup: importing this module must not configure logging
    or create distributed.log (file-writing side effects on import are
    hostile for a library — ADVICE r1)."""
    from stable_diffusion_webui_distributed_tpu.runtime.logging import get_logger

    return get_logger()

# -- environment knobs ------------------------------------------------------
#
# Every SDTPU_* environment read in the package goes through these helpers:
# one warn-and-default policy instead of per-module try/except copies, and
# one place the static analyzer (analysis/envrules.py, rule EV001) sanctions
# for raw ``os.environ`` access. A malformed value never crashes startup —
# it warns once and falls back, matching the config loader's quarantine
# philosophy above.
#
# Step-cache knobs (pipeline/stepcache.py; README "TPU policy knobs"):
#
# - ``SDTPU_DEEPCACHE`` (int, default 1 = off): deep-feature refresh
#   cadence. At N > 1 the UNet's deep blocks (below models/unet.py
#   CACHE_SPLIT, plus the mid block) run once every N steps; in between,
#   only the shallow down blocks + up path run against the cached deep
#   feature. Values quantize DOWN onto stepcache.CADENCE_LADDER
#   (1/2/3/4/6/8) before influencing anything compile-shaped (RC001);
#   per-request override: ``override_settings.deepcache``.
# - ``SDTPU_CFG_CUTOFF`` (float sigma, default 0 = off): below this
#   sigma the CFG uncond half is dropped and the UNet runs cond-only
#   rows. Mapped host-side onto the built sigma ladder and carried as a
#   traced step index; per-request: ``override_settings.cfg_cutoff``.
#
# Defaults keep both levers off: generation stays byte-identical to the
# plain executable unless a deployment opts into the FLOP/quality trade.
#
# Precision knobs (pipeline/precision.py; README "Precision modes"):
#
# - ``SDTPU_UNET_INT8`` / ``SDTPU_UNET_INT8_CONV`` (flags, default off):
#   the server's DEFAULT serving precision ("int8" / "int8+conv").
#   Defaults only — every request resolves its own precision through the
#   3-rung ladder (``override_settings.precision`` or the payload
#   ``precision`` field wins), so these flags never pin a deployment to
#   one rung.
# - ``SDTPU_WARMUP_PRECISIONS`` (comma list, default "" = policy default
#   only): extra precision rungs the AOT warmup sweep pre-builds per
#   bucket (serving/warmup.py) — precision is a static compile-key axis.
#
# Traced-LoRA knobs (models/lora.py; README "Recompile-free LoRA"):
#
# - ``SDTPU_LORA_TRACED`` (flag, default off): serve LoRA adapters as
#   TRACED jit arguments instead of host-merging them into the param
#   tree. Adapter up/down factors are padded onto a static
#   (rank-bucket, slot-count) ladder and applied as ``W x + s·up(down x)``
#   at each Dense site, so switching adapters changes only array
#   CONTENTS — zero recompiles, zero cache purges (embed/result/prefix
#   keys fold the set's content address instead of a model epoch). Off
#   (the default), the merged path runs byte-identical to the pre-knob
#   build; adaptive samplers and un-bucketable sets fall back to it
#   even when on.
# - ``SDTPU_LORA_RANKS`` (comma int list, default "8,16,32,64"): the
#   rank-bucket ladder. Adapter ranks pad UP onto it; each distinct
#   bucket is one executable variant per shape bucket.
# - ``SDTPU_LORA_SLOTS`` (comma int list, default "1,2,4"): the
#   adapter-slot ladder — how many simultaneous adapters a traced set
#   can stack per request before falling back to the merge path.
# - ``SDTPU_LORA_CACHE_MB`` (float MB, default 256): byte cap on the
#   registry's loaded-adapter LRU (pipeline/registry.py); entries are
#   mtime-validated so an adapter edited on disk reloads instead of
#   serving stale.
# - ``SDTPU_WARMUP_LORA`` (comma ``rXsY`` list, default "" = none):
#   traced-LoRA ladder cells the AOT warmup sweep pre-builds with
#   all-zero stand-in sets (serving/warmup.py) — every real adapter
#   bucketed into a warmed cell shares its executables.
#
# Ragged-dispatch knobs (serving/bucketer.py, ops/ragged_attention.py;
# README "Ragged dispatch"):
#
# - ``SDTPU_RAGGED`` (flag, default off): true-length batching. On,
#   coalescable txt2img requests match a bucket on WIDTH only and run at
#   the tallest ladder height for that width; each batch row carries its
#   true latent-row count and true conditioning-token counts as TRACED
#   int32 vectors, the attention kernels mask the padded tail, and the
#   serving layer crops top-aligned. Heterogeneous heights thereby share
#   ONE chunk executable per width class instead of one per ladder rung.
#   Off (the default), the classic area-ladder path runs byte-identical
#   to the unragged build (hash-pinned in tests/test_ragged.py).
# - ``SDTPU_RAGGED_LADDER`` (comma WxH list, default "" = the regular
#   bucket ladder): an explicitly coarse shape list ragged matching
#   scans instead — the knob that collapses a fine classic ladder down
#   to one bucket per width class without touching classic traffic.
#
# Observability knobs (obs/ package; README "Observability"):
#
# - ``SDTPU_OBS`` (flag, default on): per-request span tracing. Spans are
#   host-side perf_counter intervals — never a device sync — so they stay
#   on by default; ``0`` turns :func:`obs.spans.span` into a no-op.
# - ``SDTPU_OBS_MAX_REQUESTS`` (int, default 256): finished request
#   traces retained for ``/internal/trace.json`` (bounded store; oldest
#   evicted first).
# - ``SDTPU_OBS_FLIGHTREC`` (int, default 16): failed/interrupted/slow
#   request entries the flight recorder keeps (``/internal/flightrec``).
# - ``SDTPU_OBS_SLOW_S`` (float seconds, default 30): e2e latency above
#   which a request is flight-recorded as a slow outlier; ``0`` disables
#   slow capture (errors and interrupts are always recorded).
#
# Fleet-scheduler knobs (fleet/ package; README "Fleet scheduling"):
#
# - ``SDTPU_FLEET`` (flag, default off): master switch for the multi-tenant
#   tier — weighted-fair device gate, per-tenant quotas, ETA-SLO admission
#   and chunk-boundary preemption. Off keeps the dispatcher's plain
#   exec-lock path byte-identical to the pre-fleet build. The config field
#   ``fleet_enabled`` sets the same switch; the env var wins.
# - ``SDTPU_FLEET_CLASSES`` (``name:weight`` list, default
#   ``interactive:8,batch:2,best_effort:1``): WFQ weight per priority
#   class. Unknown names define extra classes scheduled like ``batch``.
# - ``SDTPU_SLO_INTERACTIVE_S`` (float seconds, default 30): completion
#   SLO the admission controller enforces for ``interactive`` requests;
#   0 disables SLO admission. Per-request ``slo_s`` overrides it.
# - ``SDTPU_QUOTA_IPM`` (float images/minute, default 0 = unlimited):
#   per-tenant token-bucket refill rate; ``SDTPU_QUOTA_BURST`` (float,
#   default 8) is the bucket depth. Exhausted tenants get 429 +
#   Retry-After.
# - ``SDTPU_FLEET_AGING_S`` (float seconds, default 10): waiters older
#   than this are served oldest-first regardless of fair-queue tags
#   (starvation bound).
# - ``SDTPU_FLEET_QUANTUM_S`` (float seconds, default 0.25): minimum
#   device tenure before a preemptible job may be asked to yield.
# - ``SDTPU_FLEET_FEWSTEP`` (int, default 12): step budget the deepest
#   admission degrade rung clamps to before rejecting; 0 disables the
#   few-step rung.
# - ``SDTPU_AUTOSCALE_UP_S`` / ``SDTPU_AUTOSCALE_DOWN_S`` /
#   ``SDTPU_AUTOSCALE_COOLDOWN_S`` (floats, defaults 5 / 0.5 / 60):
#   slice autoscale thresholds — scale a slice group up when the worst
#   per-class queue-wait p95 crosses UP_S, down when it falls below
#   DOWN_S, with at most one decision per slice per cooldown
#   (fleet/slices.py; decision engine + hooks only, no provisioning).
# - ``SDTPU_AUTOSCALE_AUDIT`` (int, default 256): autoscale decision
#   audit-ring capacity behind ``/internal/autoscale`` — every retained
#   decision with its wall-clock timestamp (fleet/slices.py).
# - ``SDTPU_PERF`` (flag, default off): the perf ledger (obs/perf.py).
#   On, every device dispatch reports host-observed seconds into
#   per-(bucket, cadence, precision) padding-waste groups served at ``/internal/perf`` and as ``sdtpu_perf_*``
#   Prometheus families; compile builds and fleet SLO outcomes feed the
#   same ledger. Off (the default), every record call is a no-op and
#   the dispatch path is byte-identical to the uninstrumented build.
# - ``SDTPU_PERF_GROUPS`` (int, default 64): bounded ledger width —
#   distinct (bucket, cadence, precision) rows and distinct (tenant,
#   class) SLO rows each; least-recently-touched rows are evicted (and
#   counted) so adversarial tenant names cannot grow the ledger.
# - ``SDTPU_PERF_SLO_TARGET`` (float, default 0.95): SLO attainment
#   target behind the burn-rate gauge — burn 1.0 means consuming the
#   (1 - target) error budget exactly.
# - ``SDTPU_JOURNAL`` (flag, default off): the request lifecycle journal
#   (obs/journal.py). On, every request's journey (received -> admitted/
#   throttled -> bucketed -> coalesced -> dispatched -> decoded ->
#   merged -> completed/failed, plus scheduler-tier plan/requeue events)
#   is recorded with monotonic timestamps, causal parent seqs and
#   payload fingerprints, served at ``GET /internal/journal`` and
#   replayable with ``tools/replay.py``. Off (the default), every emit
#   returns before touching the buffer and the serving path is
#   byte-identical to the unjournaled build.
# - ``SDTPU_JOURNAL_MAX`` (int, default 4096): journal ring capacity in
#   events; oldest events are dropped first (the ring never blocks or
#   grows unbounded).
# - ``SDTPU_JOURNAL_SINK`` (path, default '' = off): JSONL spill file
#   for ring-evicted journal events — each event the ring drops is
#   appended as one JSON line (best-effort; write errors are swallowed),
#   so ring + sink stay a complete record on runs longer than the ring.
#   ``tools/replay.py`` and ``sim/workload.py`` load sink files directly.
# - ``SDTPU_SIM`` (flag, default off): the scenario engine (sim/).
#   When 1, chaos fault plans may be armed into the CHAOS_HOOK seams
#   (scheduler/worker.py, scheduler/world.py, serving/dispatcher.py) and
#   scenario runs are scored/recorded at ``/internal/sim``. Off (the
#   default), sim.chaos.arm refuses, every hook stays None, and the
#   serving/scheduler paths are byte-identical to the ungated build
#   (hash-pinned in tests/test_sim.py).
# - ``SDTPU_SIM_SEED`` (int, default 0): default seed for workload
#   generation and chaos plans in ``bench.py --scenarios`` — one seed
#   reproduces the whole scenario matrix byte-for-byte.
# - ``SDTPU_HEARTBEAT_S`` (float seconds, default 0 = off): worker
#   heartbeat prober period — a daemon sweep of ``ping_workers`` so an
#   UNAVAILABLE remote recovers to IDLE (and its health window updates)
#   without an operator ping (scheduler/world.py start_heartbeat).
# - ``SDTPU_WATCHDOG_FACTOR`` (float, default 0 = off): hang watchdog
#   multiple — a dispatch or remote job still running past FACTOR x its
#   predicted ETA gets a thread-stack dump into the flight recorder, a
#   ``sdtpu_watchdog_stalls_total`` bump, and (remote jobs) a nudge into
#   the requeue path (obs/watchdog.py). Only armed where an ETA exists
#   (benchmarked calibration); 0 never arms and the join path is
#   byte-identical to the unwatched build.
# - ``SDTPU_LOCKSAN`` (flag, default off): runtime lockset sanitizer
#   (runtime/locksan.py). When 1, tests/conftest.py wraps the
#   ``threading`` lock factories to record observed lock-acquisition
#   order and diffs it against the static LK005 lock-order graph at
#   session end; any ordering the static model missed fails the run.
#   Off by default: nothing is patched and the lock path is
#   byte-identical to stock threading. Test harness only — never set in
#   production serving.
# - ``SDTPU_LOCKSAN_ORDER`` (flag, default ON when SDTPU_LOCKSAN=1):
#   the ordering layer of the session gate (tests/conftest.py). Adds
#   three checks on top of the divergence diff: Goodlock-style cycle
#   detection over the union of per-thread observed acquisition edges
#   (opposite orders that really executed fail the run even when this
#   schedule happened not to deadlock), ``Condition.wait`` entered
#   while holding an unrelated lock, and ``lockorder a<b`` annotations
#   the suite never exercised (an undemonstrated order may not suppress
#   LK005). Set 0 to drop back to the divergence diff alone while
#   debugging.
# - ``SDTPU_SCHED_SEEDS`` (int, default 64): seeds per subsystem
#   harness for the deterministic schedule explorer sweep in
#   ``bench.py --ledger`` (sim/sched.py + sim/harnesses.py). Each seed
#   is one PCT-style priority interleaving; the ledger's
#   ``schedule_explorer_seeds`` counts the clean ones. Same seed, same
#   trace — raise it for a deeper prowl, never for determinism.
# - ``SDTPU_CACHE`` (flag, default off): million-user caching tier
#   (cache/). When 1, three layers arm over one bounded LRU store:
#   content-addressed embedding dedupe over the CLIP text tower
#   (keyed on prompt text + clip_skip + model/tower fingerprint),
#   seed-keyed result dedupe with single-flight leader election
#   (byte-exact payload repeats return cached images before bucketing,
#   never consuming a dispatch slot or feeding queue-wait/ETA
#   accounting), and denoise prefix sharing (requests identical up to
#   step k resume from a mid-denoise carry captured at a step-cache
#   chunk boundary). Off: nothing is cached and every path is
#   byte-identical to the ungated build.
# - ``SDTPU_CACHE_EMBED_MB`` (float MB, default 64): embed-cache byte
#   cap. Oldest conditioning entries evict LRU past it.
# - ``SDTPU_CACHE_RESULT_MB`` (float MB, default 256): result-dedupe
#   byte cap over cached images + infotexts.
# - ``SDTPU_CACHE_PREFIX_MB`` (float MB, default 128): prefix-latent
#   byte cap; each entry holds one full sampler carry (latents +
#   multistep history).
# - ``SDTPU_CACHE_PREFIX_MIN_STEPS`` (int, default 4): shallowest
#   denoise step a prefix may be captured or resumed at — captures
#   shallower than this are noise-dominated and not worth the bytes.
# - ``SDTPU_JOURNAL_SINK_MAX_MB`` (float MB, default 0 = unbounded):
#   size cap on the journal sink file. When the next spilled line would
#   push the sink past the cap it rotates once via ``os.replace`` to
#   ``<sink>.1`` (the previous ``.1`` is discarded — at most two files
#   ever exist), so a long-running serving box keeps a bounded, recent
#   tail. ``tools/replay.py`` loads the rotated pair as one contiguous
#   stream; ``sink_status()`` reports bytes written and rotations.
# - ``SDTPU_TSDB`` (flag, default off): in-process metric history
#   (obs/tsdb.py) — a bounded ring buffer per series, sampled from the
#   registered Prometheus families plus derived series (rank-
#   interpolated queue-wait/e2e p95, per-tenant SLO burn, device-memory
#   watermarks), served at ``GET /internal/tsdb`` and queried by the
#   alert engine. Off (the default), no daemon starts, ``tick()`` is a
#   no-op, and the serving path is byte-identical to the unsampled
#   build (hash-pinned in tests/test_tsdb.py).
# - ``SDTPU_TSDB_INTERVAL_S`` (float seconds, default 1.0, floor 0.01):
#   sampling daemon cadence.
# - ``SDTPU_TSDB_POINTS`` (int, default 512, floor 8): per-series ring
#   depth; with the default 1s cadence that is ~8.5 minutes of history.
# - ``SDTPU_ALERTS`` (flag, default off): the alert engine
#   (obs/alerts.py) over the TSDB — multi-window multi-burn-rate SLO
#   alerts, EWMA z-score anomaly detectors (queue wait, compile rate,
#   error rate) and deterministic increase detectors (worker flap,
#   watchdog stall) run through a pending/firing/resolved state machine.
#   Transitions journal as ``alert_firing``/``alert_resolved``, export
#   ``sdtpu_alert_state``/``sdtpu_alerts_total``, land firing flight-
#   recorder entries, and feed the autoscaler's scale-up signal.
#   Needs ``SDTPU_TSDB=1`` for data; off, ``evaluate()`` returns
#   immediately and nothing changes.
# - ``SDTPU_ALERT_TIMESCALE`` (float, default 1.0): multiplier on every
#   rule's wall-clock windows so scenario runs compress the 5m/1h/6h
#   SLO windows into seconds (``0.01`` -> 3s/36s/216s) without touching
#   thresholds — ``bench.py --alerts`` validates with it.
# - ``SDTPU_FEDERATION`` (flag, default off): fleet-federated metrics
#   (obs/federation.py) — the master-side prober scrapes every HTTP
#   worker's ``/internal/metrics`` + ``/internal/tsdb`` on the TSDB
#   sampler's cadence, records ``worker:<label>/...`` series plus
#   ``fleet/...`` aggregates (worst-of-fleet queue-wait p95, mean error
#   rate, stale-worker count), serves ``GET /internal/fleet``, and arms
#   the ``worker_metrics_stale`` / ``fleet_error_rate`` alert rules and
#   the autoscaler's fleet-wide scale signal. Off (the default) no
#   source registers, ``tick()`` is a no-op, and the serving path is
#   byte-identical (hash-pinned in tests/test_federation.py).
# - ``SDTPU_TSDB_DIR`` (path, default unset): TSDB durability — the
#   sampling daemon snapshots every ring to
#   ``<dir>/tsdb_snapshot.json`` every 10 ticks and at shutdown
#   (tmp + ``os.replace``, crash-safe), and a (re)start merges the
#   on-disk history back in (future-stamped samples from a prior boot
#   are dropped), so ``quantile_over_time`` windows survive restarts.
#   Corrupt or truncated snapshots load as nothing, never an error.
# - ``SDTPU_NOTIFY_URL`` (url, default unset): alert notification
#   delivery (obs/notify.py) — every alert firing/resolved transition
#   is queued (bounded) and POSTed as JSON to this webhook by a drain
#   thread with retry + exponential backoff; outcomes count into
#   ``sdtpu_notify_total{outcome}`` and journal as ``notify_sent`` /
#   ``notify_failed``. Unset (the default) the queue is never touched
#   and no thread starts.
# - ``SDTPU_NOTIFY_DEDUP_S`` (float seconds, default 60): identical
#   (channel, rule, event) transitions inside this window are dropped
#   (outcome ``deduped``) so a flapping rule cannot page-storm.
# - ``SDTPU_NOTIFY_ROUTES`` (default unset): severity-routed delivery —
#   comma-separated ``key=url`` entries where ``key`` is a severity
#   (``page``/``warn``/``info``) or a tenant-scoped override
#   (``tenant:severity``). Resolution precedence: tenant:severity ->
#   severity -> the ``SDTPU_NOTIFY_URL`` default channel -> drop.
#   Each channel gets its own bounded queue and per-channel outcome
#   counts (``sdtpu_notify_total{channel,outcome}``); malformed
#   entries are skipped. ``bench.py --obsplane`` validates the routing
#   matrix (page and warn never cross channels).
# - ``SDTPU_PUSH`` (flag, default off): the push control plane
#   (obs/push.py) — workers buffer their journal events, federated
#   TSDB samples and counter totals behind cursor-indexed ``GET
#   /internal/deltas`` long-polls; the master runs one DeltaSubscriber
#   daemon per worker that resumes from its cursor after a disconnect
#   (no loss, no duplicates) and writes the *same*
#   ``worker:<label>/...`` + ``fleet/...`` series the poll prober
#   fills, so alert rules and the autoscaler are plane-agnostic.
#   Streamed journal events merge into the fleet timeline
#   (obs/fleetlog.py, ``GET /internal/fleet/timeline``) with RTT-
#   midpoint clock offsets. A worker answering 404 demotes its
#   subscriber to the poll path (``push_fallback`` journaled) — push
#   is an upgrade, never a requirement. Off (the default)
#   ``/internal/deltas`` answers 404, no source registers, no daemon
#   starts, and the serving path is byte-identical to the poll-only
#   build (pinned to the same golden in tests/test_push.py).
# - ``SDTPU_PUSH_CURSOR_BUF`` (int, default 1024, floor 16): worker-
#   side retained-entry depth; past it the oldest entries are evicted
#   (counted, journaled as ``push_buffer_evicted``, and reported as
#   ``lost`` to any consumer whose cursor predates the window).
# - ``SDTPU_PUSH_WAIT_S`` (float seconds, default 0.25, floor 0): how
#   long one ``/internal/deltas`` request may hold the connection
#   waiting for fresh entries before answering empty.
# - ``SDTPU_OBS_HTTP_TIMEOUT_S`` (float seconds, floor 0.05): the one
#   obs-plane outbound HTTP timeout — trace stitching, federation
#   polls, webhook delivery, and the HTTP backend's control-plane
#   probes all resolve through ``obs/stitch.py:http_timeout_s`` so a
#   hung worker costs one bounded timeout, never a stalled sweep.
#   Unset, each call site keeps its historical default (stitch 5.0,
#   backend probes 3.0).
#
# Warm-pool knobs (fleet/pool.py; README "Kept programs & warm pools").
# Kept programs (serving/aot.py) have NO knob: wherever the persistent
# compile cache is placed (``JAX_COMPILATION_CACHE_DIR``, else
# runtime/mesh.py:enable_compilation_cache) every ``Engine._cached`` cell
# loads its executable from ``sdtpu-programs/`` inside that directory
# before it traces, and keeps what it traces; with no cache placed a
# stage is the plain ``jax.jit``. Every ``SDTPU_*`` variable as set is
# part of a kept program's id (many are read at trace time), so changing
# one makes the next start trace again.
#
# - ``SDTPU_POOL`` (flag, default off): the warm engine pool
#   (fleet/pool.py). On, a dispatcher constructed with ``pool=`` checks
#   each execution out to the least-loaded ready resident; autoscale
#   decisions attached via ``WarmPool.attach_autoscale`` spawn/retire
#   residents for real and upgrade their ``/internal/autoscale`` audit
#   entries from ``no_executor`` to ``executed``/``failed``. Off, the
#   dispatcher runs every request on its own engine, unchanged.
# - ``SDTPU_POOL_SIZE`` (int, default 2): the pool's target ready
#   resident count — ``heal()`` spawns back up to it after a chaos
#   kill or a crash.
# - ``SDTPU_POOL_COOLDOWN_S`` (float seconds, default 0): minimum wall
#   time between autoscale-driven spawn/retire executions; a decision
#   landing inside the window records ``failed``/``cooldown`` in the
#   audit ring instead of thrashing capacity.


def read_env(name: str, default: str = "") -> str:
    """The package's only sanctioned raw environment read (EV001)."""
    return os.environ.get(name, default)


def env_as_set(prefix: str, names=()) -> list:
    """``[(name, value), ...]``, sorted: every variable whose name starts
    with ``prefix`` or is one of ``names``, as set now. What was read at
    trace time is part of a kept program's id (serving/aot.py)."""
    return sorted((k, v) for k, v in os.environ.items()
                  if k.startswith(prefix) or k in names)


def env_str(name: str, default: str = "") -> str:
    val = read_env(name, "").strip()
    return val if val else default


def env_flag(name: str, default: bool = False) -> bool:
    """'' -> default; '0'/'false'/'off'/'no' -> False; anything else -> True."""
    raw = read_env(name, "").strip().lower()
    if raw == "":
        return default
    return raw not in ("0", "false", "off", "no")


def env_parsed(name: str, parse, default, what: str = "value"):
    """Warn-and-default parse of an env var: unset -> default, unparseable
    -> UserWarning + default. ``parse`` gets the raw string and may raise
    ValueError/TypeError to reject it. ``warnings`` (not the logger) is the
    channel: a bad knob is an operator-facing config mistake, and it must
    surface even before logging is configured."""
    raw = read_env(name, "")
    if raw.strip() == "":
        return default
    try:
        return parse(raw)
    except (ValueError, TypeError) as e:
        import warnings

        warnings.warn(f"{name}={raw!r} is not a valid {what} ({e}); "
                      f"using default {default!r}", stacklevel=3)
        return default


def env_int(name: str, default: Optional[int] = None) -> Optional[int]:
    return env_parsed(name, lambda raw: int(raw.strip()), default, "int")


def env_float(name: str, default: Optional[float] = None) -> Optional[float]:
    return env_parsed(name, lambda raw: float(raw.strip()), default, "float")


#: Benchmark protocol constants (reference: shared.py:63-64).
WARMUP_SAMPLES = 2
RECORDED_SAMPLES = 3


class BenchmarkPayload(BaseModel):
    """The fixed calibration workload (reference: shared.py:67-77, pmodels.py:4-10)."""

    prompt: str = "A herd of cows grazing at the bottom of a sunny valley"
    negative_prompt: str = ""
    steps: int = 20
    width: int = 512
    height: int = 512
    batch_size: int = 1
    sampler_name: str = "Euler a"


class WorkerModel(BaseModel):
    """Per-worker (per-slice) persisted state (reference: pmodels.py:12-34).

    In the TPU build a "worker" is a generation backend: the in-process mesh
    slice (master), another slice of the same pod, or a remote host reachable
    over the sdapi-compatible control plane. Calibration fields survive
    restarts so scheduling stays warm (world.py:705-722 semantics).
    """

    address: str = "localhost"
    port: int = 7860
    avg_ipm: Optional[float] = None  # images per minute; None = not benchmarked
    master: bool = False
    # ETA mean-percent-error history, most recent last (worker.py:483-490).
    eta_percent_error: List[float] = Field(default_factory=list)
    user: Optional[str] = None
    password: Optional[str] = None
    tls: bool = False
    disabled: bool = False
    # Maximum width*height*batch this worker will accept; 0 = uncapped
    # (reference: world.py:62-72 pixel-cap guard in Job.add_work; the
    # reference's -1 "no limit" sentinel is normalized to 0 on load).
    pixel_cap: int = 0
    # Pin this worker to a specific checkpoint: model sync sends this name
    # instead of the fleet's current model (reference ui.py:161-171 exposes
    # it per worker; persisted here so the pin survives restarts).
    model_override: Optional[str] = None
    # TPU-native extension: which local devices this backend drives
    # (empty = all visible devices; remote workers leave it empty).
    device_ids: List[int] = Field(default_factory=list)

    @field_validator("pixel_cap")
    @classmethod
    def _normalize_pixel_cap(cls, v: int) -> int:
        # Reference-era configs carry pixel_cap: -1 for "no limit"
        # (pmodels.py:34); any non-positive value means uncapped here.
        return 0 if v <= 0 else v


class ConfigModel(BaseModel):
    """Root config (reference: pmodels.py:36-46)."""

    workers: List[Dict[str, WorkerModel]] = Field(default_factory=list)
    benchmark_payload: BenchmarkPayload = Field(default_factory=BenchmarkPayload)
    # Seconds of predicted stall we tolerate before deferring a worker's
    # images to faster peers (reference: pmodels.py:42, default 3).
    job_timeout: int = 3
    enabled: bool = True
    # img2img tab enabled by default, matching the reference (pmodels.py:44).
    enabled_i2i: bool = True
    # Let slow (deferred) workers produce "bonus" images in their slack time
    # (reference optimize_jobs step 4, world.py:519-543).
    complement_production: bool = True
    # If a complementary worker can't fit one image in the slack window,
    # give it one image at reduced step count (world.py:547-557).
    step_scaling: bool = False
    # Master schedules only remotes, producing no images itself
    # (reference "thin-client mode", world.py:109-110 analogue).
    thin_client_mode: bool = False
    # TPU-native additions (absent from the reference's schema):
    model_dir: str = "models"
    default_model: str = ""
    mesh_axes: Dict[str, int] = Field(default_factory=dict)  # e.g. {"dp": 4, "tp": 2}
    # -- serving-layer knobs (serving/ package) ---------------------------
    # Shape-bucket ladder: comma list of WxH resolutions requests are
    # padded UP to before execution, so the engine compiles at most one
    # chunk executable per (bucket, batch) instead of one per unique
    # request shape. Images are center-cropped back to the requested size.
    # Env SDTPU_BUCKET_LADDER overrides; malformed values warn and fall
    # back to "512x512,640x640,768x768,1024x1024".
    bucket_ladder: str = ""
    # Batch ladder: comma list of device batch sizes the coalescer pads
    # merged batches up to (pad-and-drop). Env SDTPU_BATCH_LADDER
    # overrides; default "1,2,4,8".
    batch_ladder: str = ""
    # Coalesce window (seconds): how long the first request of a
    # compatible group waits for concurrent requests to merge into its
    # device batch. 0 disables waiting (requests still merge while the
    # engine is busy with a previous batch). Env SDTPU_COALESCE_WINDOW
    # overrides; default 0.05.
    coalesce_window: Optional[float] = None
    # Multi-tenant fleet tier (fleet/ package): priority classes, quotas,
    # SLO admission and preemption. None = off unless SDTPU_FLEET says
    # otherwise (the env var always wins; see the knob block above).
    fleet_enabled: Optional[bool] = None


def default_config_path() -> str:
    return env_str("SDTPU_CONFIG", "distributed-config.json")


def load_config(path: Optional[str] = None) -> ConfigModel:
    """Read + validate the JSON config; migrate or quarantine unreadable files.

    Mirrors the reference's ``World.config`` (world.py:616-659): a missing
    file yields defaults, a legacy ``workers.json``-style list is migrated,
    and a corrupt file is renamed aside rather than crashing startup.
    """
    path = path or default_config_path()
    if not os.path.exists(path):
        _logger().debug("config %s not found, using defaults", path)
        return ConfigModel()
    try:
        with open(path, "r", encoding="utf-8") as f:
            raw = json.load(f)
    except (json.JSONDecodeError, OSError) as e:
        return _quarantine(path, "corrupt", e)

    try:
        if isinstance(raw, list):
            # Legacy format: bare list of worker dicts (world.py:632-649).
            _logger().info("migrating legacy worker-list config %s", path)
            workers = []
            for entry in raw:
                label = entry.pop("label", entry.get("address", "worker"))
                workers.append({label: WorkerModel(**entry)})
            return ConfigModel(workers=workers)
        return ConfigModel(**raw)
    except Exception as e:
        return _quarantine(path, "invalid", e)


def _quarantine(path: str, kind: str, err: Exception) -> ConfigModel:
    """Rename a bad config aside rather than crashing startup (world.py:655-659)."""
    quarantine = f"{path}.{kind}-{int(time.time())}"
    _logger().warning("config %s %s (%s); moving to %s", path, kind, err, quarantine)
    try:
        os.replace(path, quarantine)
    except OSError:
        pass
    return ConfigModel()


def save_config(cfg: ConfigModel, path: Optional[str] = None) -> None:
    """Atomically persist the config (reference: world.py:705-722)."""
    path = path or default_config_path()
    tmp = f"{path}.tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(cfg.model_dump(), f, indent=2)
    os.replace(tmp, path)
    _logger().debug("config saved to %s", path)

"""Continuous-batching dispatcher: coalesce compatible requests into one
device batch.

The HTTP layer (``server/api.py``) runs one thread per request; without
this module a single engine serializes them whole-request-at-a-time.  The
dispatcher instead gives every request a ticket and groups compatible
concurrent tickets — same sampler / steps / cfg / negative prompt /
clip-skip and the same shape BUCKET (see :mod:`.bucketer`) — into one
merged denoise loop, then splits images, seeds and infotext back per
requester.  The first ticket of a group becomes the *leader*: it waits
for followers to join, at the longest one coalesce window
(``SDTPU_COALESCE_WINDOW`` / ``ConfigModel.coalesce_window``, seconds: the
longest the first request of a group waits; a group that is full goes at
once), runs the merged batch under the engine-execution lock, and wakes
the followers with their slice.

Seed-exactness: every stochastic draw in the engine is keyed by
``(request seed + image index)`` and never by batch position
(``runtime/rng.py``), and per-image conditioning rides as batched context
rows — so each requester's seeds, subseeds and infotext are byte-identical
to a serial run of the same payload through this dispatcher.  (Pixel
bytes match too whenever the merged prompts tokenize to the same context
chunk count; a longer neighbor prompt pads every context in the batch,
which is the same rule the fleet scheduler pins via
``payload.context_chunks``.)

Per-request cancellation: ``cancel(request_id)`` marks one ticket; the
merged device batch keeps running (removing rows would need a recompile)
but the cancelled requester's images are dropped at split time and no
other requester is affected.  The global interrupt flag keeps its
engine-wide semantics.

Requests that cannot merge (img2img, hires, ControlNet, LoRA tags,
per-image prompts, adaptive samplers — the DPM adaptive controller
consumes ONE error norm over the whole batch, so merging would change
pixels) run solo under the same execution lock, still shape-bucketed when
possible.

Requests whose prompts the resident language model rewrites first (the
``prompt expansion`` script, pipeline/expand.py) merge like any other
where the expander can decode several sequences a step and the batch
ladder has a rung for a second request: the script's arguments are part of
the group key, so a group has one instruction and one token budget, and
the group's ``expand`` stage decodes every live ticket's images as the
sequences of ONE scan (``PromptExpander.expand_group``) before the
per-ticket encodes. Each image is keyed by its own request's seed and its
own index, so it gets the text it gets alone.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
import uuid
from typing import Dict, List, Optional

from stable_diffusion_webui_distributed_tpu.fleet import (
    admission as fleet_admission,
)
from stable_diffusion_webui_distributed_tpu.fleet import (
    policy as fleet_policy,
)
from stable_diffusion_webui_distributed_tpu.fleet import (
    quotas as fleet_quotas,
)
from stable_diffusion_webui_distributed_tpu.obs import (
    journal as obs_journal,
    perf as obs_perf,
    prometheus as obs_prom,
    tsdb as obs_tsdb,
    watchdog as obs_watchdog,
)
from stable_diffusion_webui_distributed_tpu.obs import spans as obs_spans
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    prompt_expansion_args,
)
from stable_diffusion_webui_distributed_tpu.runtime import trace
from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
    ShapeBucketer, ragged_enabled,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import METRICS

DEFAULT_COALESCE_WINDOW = 0.05
#: where a group key holds the expansion script's arguments (``_group_key``)
EXPANSION_AT = 11

#: Sanctioned chaos-injection hook (sim/chaos.py). When armed, it is
#: consulted once per submitted request (after seed fixing, before any
#: admission/journal work) so step-indexed fault plans advance their
#: request counter on the serving path. ``None`` (the default) costs
#: one identity check.
CHAOS_HOOK = None


def _coalesce_window(cfg=None) -> float:
    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        env_float, env_str,
    )

    if not env_str("SDTPU_COALESCE_WINDOW") and cfg is not None:
        val = getattr(cfg, "coalesce_window", None)
        if val is not None:
            return max(0.0, float(val))
    val = env_float("SDTPU_COALESCE_WINDOW", DEFAULT_COALESCE_WINDOW)
    return max(0.0, val)


class Ticket:
    """One queued request: original payload + bucketed execution copy."""

    def __init__(self, payload, run, job: str, bucketed: bool,
                 request_id: str) -> None:
        self.payload = payload          # user-visible metadata source
        self.run = run                  # execution payload (bucket dims)
        self.job = job
        self.bucketed = bucketed
        self.request_id = request_id
        self.fleet_class = ""           # resolved class name (fleet on)
        self.enqueued = time.monotonic()
        self.enqueued_perf = time.perf_counter()
        self.done = threading.Event()
        self.cancelled = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        #: submitting thread's obs trace; the coalesce leader records a
        #: follower's queue wait into it cross-thread
        self.obs_req = obs_spans.current()
        #: the wait spans this ticket's own thread holds open by hand
        #: (``queue_wait``, then ``engine.wait``), innermost last
        self.waits: list = []
        #: the dispatch that carried a grouped ticket: the leader's request
        #: id and its ``dispatch.device`` span id, set by the leader as the
        #: attrs of a follower's ``coalesced.wait``
        self.leader_link: dict = {}


class _Group:
    def __init__(self, key) -> None:
        self.key = key
        self.tickets: List[Ticket] = []
        self.images = 0
        self.closed = False
        #: set, under the dispatcher's ``_lock``, by the ticket that brings
        #: ``images`` to ``max_batch``: no joiner fits any more (a cancelled
        #: ticket keeps its rows), so the leader's window ends there
        self.full = threading.Event()


class ServingDispatcher:
    """Leader/follower coalescer in front of a single :class:`Engine`."""

    def __init__(self, engine, bucketer: Optional[ShapeBucketer] = None,
                 window: Optional[float] = None, config=None,
                 calibration=None, pool=None) -> None:
        self.engine = engine
        # warm pool (SDTPU_POOL, fleet/pool.py): when attached, each
        # leader/solo execution checks out the least-loaded healthy
        # resident and runs on ITS engine; grouping/bucketing decisions
        # keep reading self.engine (residents are factory-homogeneous).
        # None (default): every self._engine() read resolves to
        # self.engine and the dispatch path is unchanged.
        self.pool = pool
        self._exec_engine = threading.local()
        self.bucketer = bucketer or (
            ShapeBucketer.from_config(config) if config is not None
            else ShapeBucketer())
        self.window = _coalesce_window(config) if window is None \
            else max(0.0, float(window))
        self.max_batch = max(self.bucketer.batches)
        # _lock guards the grouping tables; _exec_lock serializes engine
        # execution. Order discipline: _exec_lock may be taken first and
        # _lock nested inside it, never the reverse (sdtpu-lint LK003
        # watches the acquisition graph)
        self._lock = threading.Lock()
        self._exec_lock = threading.Lock()
        self._groups: Dict[tuple, _Group] = {}  # guarded-by: _lock
        self._tickets: Dict[str, Ticket] = {}  # guarded-by: _lock
        # fleet tier (SDTPU_FLEET, fleet/): the bare exec lock becomes a
        # weighted-fair gate with per-tenant quotas and ETA-SLO admission.
        # Disabled (default): all three stay None and every fleet branch
        # below is dead code — dispatch order, seeds and outputs are
        # byte-identical to the pre-fleet build.
        self.fleet: Optional[fleet_policy.FleetGate] = None
        self.quotas: Optional[fleet_quotas.QuotaLedger] = None
        self.admission: Optional[fleet_admission.AdmissionController] = None
        if fleet_policy.fleet_enabled(config):
            self.fleet = fleet_policy.FleetGate(
                fleet_policy.FleetPolicy.from_env())
            self.quotas = fleet_quotas.QuotaLedger.from_env()
            self.admission = fleet_admission.AdmissionController(
                calibration=calibration)

    # -- public API --------------------------------------------------------

    def submit(self, payload, job: str = "txt2img"):
        """Execute ``payload`` (blocking) and return its GenerationResult.

        Called concurrently from HTTP handler threads; compatible callers
        arriving within one coalesce window share a device batch."""
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            apply_scripts, fix_seed,
        )

        payload = apply_scripts(payload.model_copy())
        payload.seed = fix_seed(payload.seed)
        payload.subseed = fix_seed(payload.subseed)

        rid = str(getattr(payload, "request_id", "") or uuid.uuid4().hex)
        if CHAOS_HOOK is not None:
            CHAOS_HOOK("dispatcher.submit", payload=payload, rid=rid)
        # root the obs trace here for direct callers; HTTP ingress already
        # minted one for API traffic (maybe_request joins it)
        with obs_spans.maybe_request(
                rid, name=f"serve.{job}", width=payload.width,
                height=payload.height, steps=payload.steps):
            jr_on = obs_journal.enabled()
            if jr_on:
                # post-fix_seed dump: the replay anchor (tools/replay.py)
                dump = payload.model_dump()
                obs_journal.emit("received", rid, job=job, payload=dump,
                                 fingerprint=obs_journal.fingerprint(dump))
            fleet_class = ""
            if self.fleet is not None:
                # quota + SLO gate BEFORE any metrics accounting: a
                # never-admitted request must not feed the queue-wait
                # histogram or the ETA calibration
                try:
                    fleet_class = self._admit_fleet(payload)
                except fleet_admission.FleetRejected as e:
                    if jr_on:
                        obs_journal.emit(
                            "throttled", rid,
                            reason=getattr(e, "reason", ""),
                            detail=str(getattr(e, "detail", e)))
                    raise
                if jr_on:
                    obs_journal.emit("admitted", rid,
                                     **{"class": fleet_class})
                    degraded = (payload.override_settings
                                or {}).get("fleet_degraded")
                    if degraded:
                        obs_journal.emit("degraded", rid,
                                         detail=str(degraded))
            # Result dedupe (cache/, SDTPU_CACHE): a byte-exact payload
            # repeat is served from the cache HERE — before bucketing, so
            # a hit never consumes a dispatch slot, feeds the queue-wait
            # histogram, or skews the ETA calibration (the same accounting
            # class the cancelled-ticket fix keeps clean). N concurrent
            # identical requests elect one generating leader; the rest
            # block on its flight and return copies of its result.
            cache_mod = ckey = flight = None
            from stable_diffusion_webui_distributed_tpu import (
                cache as _cache_pkg,
            )

            if _cache_pkg.enabled():
                cache_mod = _cache_pkg
                # traced-adapter content rides the key (it is resolvable
                # BEFORE _apply_prompt_loras runs); "" on the merged path,
                # where model_fingerprint's _model_epoch already moves
                lora_fn = getattr(self.engine, "traced_content_for_payload",
                                  None)
                ckey = _cache_pkg.keys.result_key(
                    payload, _cache_pkg.keys.model_fingerprint(self.engine),
                    job, lora=lora_fn(payload) if lora_fn else "")
                role, cached, flight = cache_mod.result_acquire(ckey)
                if cached is not None:
                    if jr_on:
                        obs_journal.emit("result_dedupe_hit", rid,
                                         mode=role, key=ckey[:16])
                        obs_journal.emit(
                            "completed", rid, images=len(cached.images),
                            seeds=list(cached.seeds),
                            infotexts=list(cached.infotexts))
                    return cached.model_copy(deep=True)

            ticket = None
            try:
                bypass = bool(payload.init_images or payload.enable_hr)
                if bypass:
                    run, bucketed = payload.model_copy(), False
                    METRICS.record_request(False, bypassed=True)
                else:
                    ragged = ragged_enabled() \
                        and self._ragged_eligible(payload)
                    run, bucketed = self.bucketer.bucket_payload(
                        payload, ragged=ragged)
                    # batch-ladder padding folds into the ratio only for
                    # work that pads ALONE up the ladder; coalescable rows
                    # fill via merging, so charging bucket_batch(n)/n to
                    # them would book phantom waste
                    solo_batch = None if self._coalescable(run) \
                        else payload.total_images
                    METRICS.record_request(
                        bucketed,
                        padding_ratio=self.bucketer.padding_ratio(
                            payload.width, payload.height,
                            batch=solo_batch))
                if jr_on:
                    obs_journal.emit("bucketed", rid, bucketed=bucketed,
                                     bypassed=bypass,
                                     bucket=f"{run.width}x{run.height}")

                ticket = Ticket(payload, run, job, bucketed, rid)
                ticket.fleet_class = fleet_class
                with self._lock:
                    self._tickets[rid] = ticket
                if self._coalescable(run):
                    self._run_grouped(ticket)
                else:
                    self._run_solo(ticket)
                if ticket.error is not None:
                    if jr_on:
                        obs_journal.emit(
                            "failed", rid,
                            error=f"{type(ticket.error).__name__}: "
                                  f"{ticket.error}")
                    raise ticket.error
                if flight is not None and self._cacheable(ticket):
                    # the cache keeps its own deep copy: the one being
                    # returned belongs to the caller, who may mutate it
                    cache_mod.result_publish(
                        ckey, flight, ticket.result.model_copy(deep=True))
                    flight = None
                if jr_on:
                    r = ticket.result
                    # journaled outcome for the replay byte-compare
                    obs_journal.emit(
                        "completed", rid,
                        images=len(r.images) if r else 0,
                        seeds=list(r.seeds) if r else [],
                        infotexts=list(r.infotexts) if r else [])
                return ticket.result
            finally:
                if flight is not None:
                    # leader left without publishing (failure, cancel,
                    # partial output): wake followers empty-handed so
                    # they re-elect rather than block forever
                    cache_mod.result_abandon(ckey, flight)
                if ticket is not None:
                    with self._lock:
                        self._tickets.pop(rid, None)

    @staticmethod
    def _cacheable(ticket: Ticket) -> bool:
        """Only a COMPLETE result may enter the dedupe cache: a cancelled
        or interrupted run returns fewer images than the payload asked
        for, and serving that to a byte-exact repeat would be wrong."""
        r = ticket.result
        return (r is not None and not ticket.cancelled.is_set()
                and len(r.images) == ticket.payload.total_images)

    def cancel(self, request_id: str) -> bool:
        """Cancel ONE queued/running request; its images are dropped at
        split time and co-batched requests are untouched."""
        with self._lock:
            t = self._tickets.get(str(request_id))
        if t is None:
            return False
        t.cancelled.set()
        obs_spans.mark(t.obs_req, "interrupted", "cancelled by client")
        return True

    def eta_overhead(self, payload=None) -> Dict[str, float]:
        """Serving-layer additions for :func:`scheduler.eta.predict_eta`:
        expected queue wait (observed average, floored at half the
        coalesce window) and the padding-overhead factor for this
        payload's bucket."""
        wait = METRICS.avg_queue_wait() or (self.window / 2.0)
        if payload is not None:
            pad = self.bucketer.padding_ratio(payload.width, payload.height)
        else:
            pad = METRICS.avg_padding_ratio()
        return {"queue_wait": wait, "padding_overhead": pad}

    def set_calibration(self, cal, benchmark=None) -> None:
        """Attach an ETA calibration (scheduler/eta.py) so SLO admission
        can predict completion times; without one every request is
        accepted untouched."""
        if self.admission is not None:
            self.admission.calibration = cal
            self.admission.benchmark = benchmark

    def fleet_summary(self) -> Optional[Dict[str, object]]:
        """Live fleet state for /internal/status; None when fleet is off."""
        if self.fleet is None:
            return None
        out = self.fleet.summary()
        if self.quotas is not None:
            out["quotas"] = self.quotas.summary()
        if self.admission is not None:
            cal = self.admission.calibration
            out["admission"] = {
                "calibrated": bool(cal is not None and cal.benchmarked),
                "fewstep": self.admission.fewstep,
            }
        return out

    # -- fleet admission ---------------------------------------------------

    def _admit_fleet(self, payload) -> str:
        """Quota + ETA-SLO gate (fleet/): returns the resolved class name,
        mutates the payload on degrade (step-cache cadence / few-step
        budget), raises :class:`fleet_admission.FleetRejected` on refusal."""
        pol = self.fleet.policy.resolve(payload.priority_class)
        slo = float(getattr(payload, "slo_s", 0.0) or 0.0)
        if slo > 0:  # per-request SLO overrides the class default
            pol = dataclasses.replace(pol, slo_s=slo)
        tenant = str(getattr(payload, "tenant", "") or "default")
        obs_prom.fleet_count("requests", tenant=tenant,
                             **{"class": pol.name})
        metered = 0
        if self.quotas is not None and self.quotas.enabled:
            retry = self.quotas.admit(tenant, payload.total_images)
            if retry is not None:
                obs_prom.fleet_count("quota_throttles", tenant=tenant)
                raise fleet_admission.FleetRejected(
                    "quota",
                    f"tenant {tenant!r} image quota exhausted",
                    retry_after=retry)
            metered = payload.total_images
        decision = self.admission.decide(payload, pol,
                                         self.eta_overhead(payload))
        obs_prom.fleet_count("admissions", decision=decision.action,
                             **{"class": pol.name})
        if decision.action == "reject":
            if metered:
                # the quota withdrawal preceded the SLO verdict; a
                # rejected request performed no work, so its tokens
                # go back
                self.quotas.refund(tenant, metered)
            raise fleet_admission.FleetRejected(
                "slo", decision.detail,
                retry_after=max(1.0, (decision.predicted_s or 0.0)
                                - (decision.slo_s or 0.0)))
        if decision.action == "degrade":
            ov = dict(payload.override_settings or {})
            ov.update(decision.overrides)
            # marker key: consumers read override_settings with .get only,
            # so this rides through to result.parameters for visibility
            ov["fleet_degraded"] = decision.detail
            payload.override_settings = ov
            if decision.steps:
                payload.steps = decision.steps
        return pol.name

    def _engine(self):
        """The engine this thread should execute on: the pool resident
        checked out for the current leader/solo execution, else the
        primary. Pre-execution decisions (grouping, coalescability) read
        ``self.engine`` directly — residents are factory-homogeneous, so
        those answers are the same on every engine."""
        return getattr(self._exec_engine, "engine", None) or self.engine

    @contextlib.contextmanager
    def _checkout_engine(self):
        """Borrow a pool resident for one execution (SDTPU_POOL with a
        pool attached; otherwise the primary engine and zero overhead).
        The resident rides thread-local state so the nested device/
        execute/finalize path — all on the leader's thread — resolves to
        it through :meth:`_engine`."""
        from stable_diffusion_webui_distributed_tpu.fleet import (
            pool as fleet_pool,
        )

        if self.pool is None or not fleet_pool.enabled():
            yield self.engine
            return
        res = self.pool.acquire()
        self._exec_engine.engine = res.engine
        try:
            yield res.engine
        finally:
            self._exec_engine.engine = None
            self.pool.release(res)

    @contextlib.contextmanager
    def _device(self, tickets: List[Ticket], images: int):
        """The engine-execution critical section.  Fleet off: the plain
        exec lock, untouched.  Fleet on: a weighted-fair gate entry per
        dispatch, with the chunk-boundary preempt hook installed when the
        work is preemptible and preempt-safe."""
        if self.fleet is None:
            with self._exec_lock:
                yield
            return
        gate = self.fleet
        with self._lock:
            tickets = list(tickets)  # group lists grow until close
        lead = tickets[0]
        pol = gate.policy.resolve(lead.fleet_class)
        for t in tickets[1:]:
            p = gate.policy.resolve(t.fleet_class)
            if p.weight > pol.weight:
                pol = p  # a mixed group schedules at its strongest class
        entry = fleet_policy.GateEntry(
            pol, tenant=str(getattr(lead.payload, "tenant", "") or "default"),
            cost=max(1, images), request_id=lead.request_id)
        gate.acquire(entry)
        engine = self._engine()
        prev = engine.preempt_hook
        hooked = False
        try:
            if pol.preemptible \
                    and all(self._preempt_safe(t.run) for t in tickets):
                # save/restore prev so nested installs (an interloper that
                # is itself preemptible) cannot clear the outer hook
                engine.preempt_hook = fleet_policy.EnginePreemptHook(
                    gate, entry)
                hooked = True
            yield
        finally:
            if hooked:
                engine.preempt_hook = prev
            gate.release(entry)

    def _preempt_safe(self, p) -> bool:
        """May this payload yield mid-denoise?  MERGED LoRA work cannot —
        an interloper's tagless run restores pristine params under it —
        but a traced set (SDTPU_LORA_TRACED) rides as jit arguments and
        never touches the param tree, so nothing an interloper does can
        corrupt it and resume re-installs the set without a re-merge.
        Adaptive samplers drive a separate loop without the hook."""
        from stable_diffusion_webui_distributed_tpu.samplers import (
            kdiffusion as kd,
        )

        if "<lora:" in (p.prompt or "") and self._traced_rowspec(p) is None:
            return False
        return not kd.resolve_sampler(p.sampler_name).adaptive

    # -- grouping ----------------------------------------------------------

    def _traced_rowspec(self, p):
        """Traced-LoRA row cell for a payload: ``(0, 0)`` for tagless
        rows, the ``(rank_bucket, slot_count)`` cell its TracedSet
        occupies when SDTPU_LORA_TRACED serves the tags, and ``None``
        when the tags must take the merged path (gate off, adaptive
        sampler, or a set the bucketing ladder can't hold). The cell is
        the ONLY adapter fact the group key needs: every set in one cell
        runs the same chunk executable, so heterogeneous adapter combos
        coalesce row-wise (stack_row_sets) — the direct unlock ISSUE 16
        names for adapter-diverse traffic.

        Tolerates ``self`` being None / engineless — tests call
        ``_group_key`` unbound, and ETA probes have no engine."""
        from stable_diffusion_webui_distributed_tpu.models import (
            lora as lora_mod,
        )
        from stable_diffusion_webui_distributed_tpu.samplers import (
            kdiffusion as kd,
        )

        if "<lora:" not in (p.prompt or ""):
            return (0, 0)
        if not lora_mod.traced_enabled():
            return None
        _, tags = lora_mod.extract_lora_tags(p.prompt or "")
        if not tags:
            return (0, 0)
        if kd.resolve_sampler(p.sampler_name).adaptive:
            return None
        engine = getattr(self, "engine", None)
        if engine is None or not hasattr(engine, "_traced_set_for"):
            return None
        ts = engine._traced_set_for(tuple(tags))
        return None if ts is None else (ts.rank_bucket, ts.slots)

    def _coalescable(self, p) -> bool:
        from stable_diffusion_webui_distributed_tpu.samplers import (
            kdiffusion as kd,
        )

        if p.init_images or p.enable_hr or p.all_prompts:
            return False
        if p.refiner_checkpoint and p.refiner_switch_at < 1.0:
            return False
        if "<lora:" in (p.prompt or "") and self._traced_rowspec(p) is None:
            # merged-path adapters mutate engine params per request and
            # can never share a dispatch; traced sets ride as per-row jit
            # arguments and coalesce within their (rank, slots) cell
            return False
        if kd.resolve_sampler(p.sampler_name).adaptive:
            return False
        if self.engine._parse_controlnet_units(p):
            return False
        if self.engine.family.inpaint:
            return False
        if self._expansion(p) is not None:
            # an expanded request runs a token loop before the denoise and
            # ends with its own prompt. It shares a dispatch, and the
            # group's ONE decode scan, with other expanded requests of
            # equal script arguments (``_group_key``) where (a) the
            # resident expander can decode several sequences a step and
            # (b) the batch ladder has a rung that holds a second request
            # of its size; elsewhere it runs solo, its images the
            # sequences of its own scan (engine._expand_prompts). It
            # never joins a plain request: the key keeps them apart.
            return (self.engine.expander.shares_a_step
                    and 2 * p.total_images <= self.max_batch)
        return p.total_images <= self.max_batch

    def _expansion(self, p):
        """The request's ``prompt expansion`` arguments where this worker
        has a resident expander to run them, else None: a plain request.
        Tolerates ``self`` being None / engineless, as
        :meth:`_traced_rowspec` does."""
        engine = getattr(self, "engine", None)
        if getattr(engine, "expander", None) is None:
            return None
        return prompt_expansion_args(p)

    def _ragged_eligible(self, p) -> bool:
        """May this payload run ragged (SDTPU_RAGGED)? The coalescable
        exclusion set, plus step-cache work: a resolved cadence's deep-
        feature carry assumes the dense row layout, so those requests
        keep their classic executables and cadence semantics."""
        from stable_diffusion_webui_distributed_tpu.pipeline import (
            stepcache,
        )

        if stepcache.resolve(p).active or self._expansion(p) is not None:
            # (an expanded request's true context length waits for its text)
            return False
        return self._coalescable(p)

    def _precision_name(self, run) -> str:
        """Resolved serving precision for a request (pipeline/precision.py)
        — the last group-key axis and the label on the dispatch span /
        ``sdtpu_dispatch_precision_total`` counter."""
        from stable_diffusion_webui_distributed_tpu.pipeline import (
            precision as precision_mod,
        )

        # self may be None (tests call _group_key unbound) or hold no
        # engine (ETA-overhead probes): bf16 default either way
        policy = getattr(getattr(self, "engine", None), "policy", None)
        return precision_mod.resolve(run, policy).name

    def _group_key(self, run) -> tuple:
        from stable_diffusion_webui_distributed_tpu.pipeline import (
            stepcache,
        )

        # step-cache knobs join the key: merged requests run ONE denoise
        # range, so they must agree on the resolved (bucketed) cadence and
        # CFG cutoff or the coalesced batch would change their outputs.
        # The ragged marker joins too (as a bool, NOT the true shape —
        # heterogeneous true shapes coalescing is the whole point): a
        # ragged and a classic request at the same bucket run different
        # executables, and SDTPU_RAGGED can flip mid-flight under tests.
        # The resolved precision name is the LAST axis (consumers read
        # key[-1]): int8 and bf16 requests coalesce separately — a merged
        # batch runs one chunk executable, and precision is static in it.
        # The traced-LoRA cell (rank_bucket, slot_count) sits at
        # key[-3:-1]: (0, 0) for tagless rows, so adapterless grouping is
        # untouched, while any two adapter combos in one cell share a
        # group — the adapter NAMES never enter the key (they are traced
        # inputs, not executable identity).
        # The expansion script's arguments sit at key[EXPANSION_AT], in
        # front of the adapter cell: None for a plain request, so a plain
        # request never joins an expanded one, and a group of expanded
        # requests has ONE instruction (one kept prefix), one token budget,
        # one temperature and one context length: its scan is one program
        # over one shared range (pipeline/expand.py:expand_group).
        sc = stepcache.resolve(run)
        rs = ServingDispatcher._traced_rowspec(self, run) or (0, 0)
        expansion = ServingDispatcher._expansion(self, run)
        return ("txt2img", run.sampler_name, int(run.steps),
                int(run.width), int(run.height), float(run.cfg_scale),
                run.negative_prompt or "", int(run.clip_skip or 0),
                sc.cadence, sc.cutoff_sigma,
                bool((run.override_settings or {}).get("ragged_true_wh")),
                None if expansion is None
                else tuple(expansion.model_dump().values()),
                int(rs[0]), int(rs[1]),
                ServingDispatcher._precision_name(self, run))

    def _dispatch_eta(self, run, batch_size: int) -> Optional[float]:
        """Predicted device seconds for the hang watchdog, from the SLO
        admission controller's ETA calibration when one is attached and
        benchmarked; None (nothing armed) otherwise — without a
        calibration there is no deadline to compare against."""
        if not obs_watchdog.enabled() or self.admission is None:
            return None
        cal = getattr(self.admission, "calibration", None)
        if cal is None or not getattr(cal, "benchmarked", False):
            return None
        from stable_diffusion_webui_distributed_tpu.scheduler import (
            eta as eta_mod,
        )
        try:
            return eta_mod.predict_eta(
                cal, run, getattr(self.admission, "benchmark", None),
                batch_size=batch_size,
                precision=self._precision_name(run))
        except (ValueError, TypeError):
            return None

    def _run_grouped(self, ticket: Ticket) -> None:
        key = self._group_key(ticket.run)
        n = ticket.run.total_images
        with self._lock:
            g = self._groups.get(key)
            if g is None or g.closed or g.images + n > self.max_batch:
                g = _Group(key)
                self._groups[key] = g
                leader = True
            else:
                leader = False
            g.tickets.append(ticket)
            g.images += n
            if g.images >= self.max_batch:
                g.full.set()
            leader_rid = g.tickets[0].request_id
        if obs_journal.enabled():
            # journal the join decision for replay: a follower's outcome
            # depends on its leader's batch, so record the linkage
            obs_journal.emit(
                "coalesced_leader" if leader else "coalesced_follower",
                ticket.request_id, images=n, leader_request_id=leader_rid)
        if not leader:
            with obs_spans.span("coalesced.wait") as sp:
                ticket.done.wait()
                if sp is not None:
                    sp.attrs.update(ticket.leader_link)
            return
        self._begin_wait(ticket)
        try:
            if self.window > 0:
                with obs_spans.span("coalesce.window",
                                    window_s=self.window) as sp:
                    ended_by = "full" if g.full.wait(self.window) \
                        else "timer"
                    if sp is not None:
                        sp.attrs["ended_by"] = ended_by
                obs_prom.count_coalesce_window(ended_by)
            self._begin_engine_wait(ticket)
            with self._checkout_engine():
                self._run_grouped_leader(g, key)
        finally:
            self._end_wait(ticket)      # a wait that never reached the device

    # -- the wait before the device, live ----------------------------------
    #
    # ``queue_wait`` (ticket creation -> start of the device section) and
    # its child ``engine.wait`` end inside the bodies of _checkout_engine()
    # and _device(), so they are opened and closed by hand, on the ticket's
    # own thread: the leader's or the solo request's. A follower's
    # ``queue_wait`` ends when its LEADER starts and stays an after-the-fact
    # record; its live span is ``coalesced.wait``.

    def _begin_wait(self, ticket: Ticket) -> None:
        ticket.waits.append(obs_spans.open_span(
            "queue_wait", t0=ticket.enqueued_perf))

    def _begin_engine_wait(self, ticket: Ticket) -> None:
        """``engine.wait``: the engine checkout and the device lock or
        fleet gate. ``queued``: the other tickets in the dispatcher (waiting
        or running) when the wait began."""
        if ticket.waits[-1] is None:    # not traced
            return
        with self._lock:
            queued = len(self._tickets) - 1
        ticket.waits.append(obs_spans.open_span("engine.wait", queued=queued))

    def _end_wait(self, ticket: Ticket) -> None:
        while ticket.waits:
            obs_spans.close_span(ticket.waits.pop())

    def _run_grouped_leader(self, g: _Group, key) -> None:
        """The leader's execution, on this thread and on the engine
        :meth:`_checkout_engine` resolved."""
        with self._device(g.tickets, g.images):
            # close AFTER taking the engine: followers kept joining while
            # a previous batch held the device (continuous batching)
            with self._lock:
                g.closed = True
                if self._groups.get(key) is g:
                    self._groups.pop(key)
            start = time.monotonic()
            start_perf = time.perf_counter()
            self._end_wait(g.tickets[0])
            leader_req = obs_spans.current()
            jr_on = obs_journal.enabled()
            # adapter cell label for spans/journal/ledger; only attached
            # when the group actually runs traced adapters, so the
            # adapterless record stream is field-identical to before
            lora_cell = {} if not (g.key[-3] or g.key[-2]) else \
                {"lora": f"r{g.key[-3]}s{g.key[-2]}"}
            for t in g.tickets:
                if t.cancelled.is_set():
                    # never dispatched: its wait must not feed the
                    # histogram or the ETA calibration
                    continue
                wait = start - t.enqueued
                METRICS.record_queue_wait(wait)
                obs_prom.observe_hist("queue_wait", wait)
                if self.fleet is not None:
                    obs_prom.fleet_observe_queue_wait(
                        self.fleet.policy.resolve(t.fleet_class).name, wait)
                if t is not g.tickets[0]:
                    # a follower's wait ended when this leader started
                    obs_spans.add_span(t.obs_req, "queue_wait",
                                       t.enqueued_perf,
                                       start_perf - t.enqueued_perf)
                if jr_on:
                    obs_journal.emit("dispatched", t.request_id,
                                     group=len(g.tickets),
                                     precision=str(g.key[-1]), **lora_cell)
            dsp = None
            wd = obs_watchdog.arm(
                g.tickets[0].request_id, "dispatch.device",
                self._dispatch_eta(g.tickets[0].run, g.images))
            try:
                # precision attribute rides the device span so the flight
                # recorder shows which precision a failed request ran at
                with obs_spans.span("dispatch.device",
                                    requests=len(g.tickets),
                                    precision=g.key[-1],
                                    **lora_cell) as dsp:
                    self._execute_group(g)
            except BaseException as e:  # noqa: BLE001 — delivered per ticket
                for t in g.tickets:
                    if t.error is None and t.result is None:
                        t.error = e
            finally:
                obs_watchdog.disarm(wd)
                self._finish_group(g, dsp, leader_req)

    def _finish_group(self, g: _Group, dsp, leader_req) -> None:
        """Terminal bookkeeping for a dispatched group: hand every
        follower the link to the leader's device span (its
        ``coalesced.wait`` carries it), record SLO samples, and release
        every waiting ticket."""
        link = {}
        if dsp is not None and leader_req is not None:
            link = {"leader_request_id": leader_req.request_id,
                    "leader_span_id": dsp.span_id}
        for t in g.tickets:
            self._record_slo(t)
            t.leader_link = link
            t.done.set()

    def _record_slo(self, ticket: Ticket) -> None:
        """Feed the perf ledger's per-(tenant, class) SLO attainment and
        burn-rate rows (fleet + SDTPU_PERF on; never raises — observability
        must not fail a finished request)."""
        if self.fleet is None or not obs_perf.enabled():
            return
        try:
            if ticket.cancelled.is_set():
                return  # never dispatched / abandoned: not an SLO sample
            pol = self.fleet.policy.resolve(ticket.fleet_class)
            slo = float(getattr(ticket.payload, "slo_s", 0.0) or 0.0) \
                or float(pol.slo_s or 0.0)
            if slo <= 0:
                return  # best-effort class with no target: nothing to meet
            obs_perf.LEDGER.record_slo(
                tenant=str(getattr(ticket.payload, "tenant", "")
                           or "default"),
                cls=pol.name, slo_s=slo,
                latency_s=time.monotonic() - ticket.enqueued,
                ok=ticket.error is None)
        except Exception:  # noqa: BLE001 — observability stays best-effort
            pass

    def _drain_cache_notes(self, rid: str, *, embed: bool = True,
                           prefix: bool = True) -> None:
        """Journal cache-layer activity at the dispatcher tier.

        The engine records embed-cache hits and prefix resumes in
        thread-local notes on the generating thread; this drains them on
        that same thread — always, so a note can never leak into the
        next request served by it — and emits journal events only when
        journaling is on. Best-effort: a finished request never fails on
        observability.
        """
        try:
            from stable_diffusion_webui_distributed_tpu import cache
            if not cache.enabled():
                return
            jr_on = obs_journal.enabled()
            if embed:
                pos_hits, neg_hits = cache.embed_layer.take_request_hits()
                if jr_on and (pos_hits or neg_hits):
                    obs_journal.emit("embed_cache_hit", rid,
                                     positive=pos_hits, negative=neg_hits)
            if prefix:
                note = cache.prefix_layer.take_resume_note()
                if jr_on and note:
                    obs_journal.emit("prefix_resumed", rid, **note)
        except Exception:  # noqa: BLE001 — observability stays best-effort
            pass

    def _run_solo(self, ticket: Ticket) -> None:
        self._begin_wait(ticket)
        self._begin_engine_wait(ticket)
        try:
            with self._checkout_engine():
                self._run_solo_inner(ticket)
        finally:
            self._end_wait(ticket)      # cancelled before dispatch

    def _run_solo_inner(self, ticket: Ticket) -> None:
        engine = self._engine()
        with self._device([ticket], ticket.run.total_images):
            try:
                engine.state.begin_request()
                if ticket.cancelled.is_set():
                    # cancelled before dispatch: record neither a queue
                    # wait nor a dispatch (queue-depth accounting fix)
                    ticket.result = self._empty_result(ticket)
                    return
                wait = time.monotonic() - ticket.enqueued
                METRICS.record_queue_wait(wait)
                obs_prom.observe_hist("queue_wait", wait)
                if self.fleet is not None:
                    obs_prom.fleet_observe_queue_wait(
                        self.fleet.policy.resolve(
                            ticket.fleet_class).name, wait)
                self._end_wait(ticket)
                prec = self._precision_name(ticket.run)
                METRICS.record_dispatch(1, precision=prec)
                obs_prom.count_precision(prec, 1)
                rs = self._traced_rowspec(ticket.run)
                lora_cell = {"lora": f"r{rs[0]}s{rs[1]}"} \
                    if rs and rs != (0, 0) else {}
                if obs_journal.enabled():
                    obs_journal.emit("dispatched", ticket.request_id,
                                     group=1, precision=prec, **lora_cell)
                # perf ledger (SDTPU_PERF): same passive attribution as
                # the grouped path — no-op with the knob off
                perf_on = obs_perf.enabled()
                if perf_on:
                    t0_dev = time.perf_counter()
                wd = obs_watchdog.arm(
                    ticket.request_id, "dispatch.device",
                    self._dispatch_eta(ticket.run,
                                       ticket.run.total_images))
                try:
                    with obs_spans.span("dispatch.device", requests=1,
                                        precision=prec, **lora_cell):
                        result = engine.generate_range(
                            ticket.run, 0, None, ticket.job)
                finally:
                    obs_watchdog.disarm(wd)
                if perf_on:
                    from stable_diffusion_webui_distributed_tpu.pipeline \
                        import stepcache
                    n_img = ticket.run.total_images
                    # batch-ladder attribution (solo work pads alone): the
                    # engine pad-and-drops a remainder group up to the
                    # group size whenever the full-group executable exists
                    # — _has_batch_bucket is the same predicate it used
                    group = max(1, ticket.run.group_size
                                or ticket.run.batch_size)
                    full, rem = divmod(n_img, group)
                    n_run = n_img
                    if rem and (full > 0 or engine._has_batch_bucket(
                            ticket.run.sampler_name, ticket.run.steps,
                            ticket.run.width, ticket.run.height, group)):
                        n_run = (full + 1) * group
                    masked_px = 0
                    wh = engine._ragged_plan(ticket.run)
                    if wh is not None:
                        f = engine.family.vae_scale_factor
                        lat_h = ticket.run.height // f
                        tr = min(lat_h, -(-wh[1] // f))
                        masked_px = (lat_h - tr) * f \
                            * ticket.run.width * n_run
                    try:
                        tok_t, tok_p = engine.request_token_stats(
                            ticket.run)
                    except Exception:  # noqa: BLE001 — telemetry passive
                        tok_t = tok_p = 0
                    obs_perf.LEDGER.record_dispatch(
                        bucket=f"{ticket.run.width}x{ticket.run.height}",
                        cadence=int(stepcache.resolve(ticket.run).cadence),
                        precision=prec,
                        lora=(f"r{rs[0]}s{rs[1]}"
                              if rs and rs != (0, 0) else ""),
                        device_s=time.perf_counter() - t0_dev,
                        requests=1, batch_raw=n_img, batch_run=n_run,
                        true_pixels=ticket.payload.width
                        * ticket.payload.height * n_img,
                        padded_pixels=ticket.run.width
                        * ticket.run.height * n_run,
                        masked_pixels=masked_px,
                        true_tokens=tok_t, padded_tokens=tok_p,
                        hbm=obs_tsdb.dispatch_memory_sample())
                elif obs_tsdb.enabled():
                    obs_tsdb.dispatch_memory_sample()
                if ticket.bucketed:
                    result = self._restore_solo(result, ticket)
                ticket.result = result
            except BaseException as e:  # noqa: BLE001
                ticket.error = e
            finally:
                self._drain_cache_notes(ticket.request_id)
                self._record_slo(ticket)
                ticket.done.set()

    # -- merged execution --------------------------------------------------

    def _execute_group(self, g: _Group) -> None:
        """A group's four stages, back to back on the calling thread."""
        later: list = []    # an expansion's counters' fetches
        try:
            with obs_spans.span("prepare", requests=len(g.tickets)):
                built = self._group_build_inputs(g, later)
            if built is None:
                return
            latents = self._group_denoise(g, built)
            entries = self._group_decode(g, built, latents)
            self._group_merge(g, built, entries)
        finally:
            # where no chunk was enqueued to run them under (an error, an
            # interrupt): /internal/status read after a group shows it
            self._run_each(later)

    @staticmethod
    def _run_each(later: list) -> None:
        """Every fetch an expansion left for later, once."""
        while later:
            later.pop(0)()

    def _group_expand(self, live: List[Ticket], runs: list, meanwhile,
                      later: list) -> list:
        """The group's ``expand`` stage: every live ticket's images are
        the sequences of one decode scan behind the group's one
        instruction (``PromptExpander.expand_group``: at most the largest
        of cache/kv.py:SEQUENCE_BUCKETS a scan, padded up to the group's
        rung as its UNet rows are), each keyed by ITS request's seed and
        image index. ``runs`` (the tickets' execution payloads, copies)
        get their texts as ``engine._expand_prompts`` gives a solo
        request its: the prompt, or a prompt an image, and the script's
        context length. Returns the tickets' user-visible payloads with
        the same texts, for the galleries' infotexts. ``meanwhile`` runs
        under the first decode chunk; the counters' fetch goes to
        ``later``."""
        engine = self._engine()
        expansion = prompt_expansion_args(runs[0])
        members = [(p.prompt, p.seed,
                    [0 if p.same_seed else i
                     for i in range(p.total_images)]) for p in runs]
        texts = engine.expander.expand_group(
            members, expansion,
            rows=self.bucketer.bucket_batch(sum(p.total_images
                                                for p in runs)),
            meanwhile=meanwhile, later=later)
        shown = []
        for t, p, mine in zip(live, runs, texts):
            seen = t.payload.model_copy()
            for payload in (p, seen):
                if len(mine) == 1:
                    payload.prompt = mine[0]
                else:
                    payload.all_prompts = list(mine)
                if expansion.context_chunks:
                    payload.context_chunks = int(expansion.context_chunks)
            shown.append(seen)
        return shown

    def _group_build_inputs(self, g: _Group,
                            later: list) -> Optional[Dict]:
        """Encode stage: cancellation filter, the group's ``expand`` stage
        where its requests are expanded ones, per-ticket prompt encodes +
        noise draws, batch concat, pad-and-drop, LoRA row stacking, and
        the initial latent placement. Returns the denoise/decode/merge
        inputs, or None when no ticket is still live."""
        import jax.numpy as jnp

        from stable_diffusion_webui_distributed_tpu.runtime import rng
        from stable_diffusion_webui_distributed_tpu.samplers import (
            kdiffusion as kd,
        )

        engine = self._engine()
        live = [t for t in g.tickets if not t.cancelled.is_set()]
        for t in g.tickets:
            if t not in live:
                t.result = self._empty_result(t)
        if not live:
            return
        METRICS.record_dispatch(len(live), precision=g.key[-1])
        obs_prom.count_precision(g.key[-1], len(live))

        rp = live[0].run.model_copy()
        width, height = rp.width, rp.height
        h, w = engine._latent_hw(width, height)
        C = engine.family.vae.latent_channels
        with obs_spans.span("request.plan") as plan_span:
            spec = kd.resolve_sampler(rp.sampler_name)
            sigmas = engine._ladder(spec, rp.steps, plan_span).sigmas

            engine.state.begin_request()
            engine._adaptive_incomplete = False
            # tagless groups: restores pristine params; traced groups
            # (non-zero cell in the key): restores pristine params too —
            # the deltas ride as jit arguments, installed per member below
            engine._apply_prompt_loras(rp)
        # traced-LoRA cell from the group key (key[-3:-1]): every member
        # carries SOME adapter set in this (rank_bucket, slot_count) cell,
        # possibly a different one per member — each row gets its own
        # factor stack and one executable serves them all
        lora_rb, lora_sc = int(g.key[-3]), int(g.key[-2])
        traced_group = bool(lora_rb or lora_sc)
        row_sets = []
        if traced_group:
            from stable_diffusion_webui_distributed_tpu.models import (
                lora as lora_mod,
            )

        f = engine.family.vae_scale_factor
        # ragged group (SDTPU_RAGGED, a _group_key axis — uniform across
        # the group): every ticket carries its true shape in the marker,
        # noise is drawn at the TRUE latent rows and zero-padded to the
        # shared bucket, and the per-row true lengths ride into the
        # denoise as traced vectors — heterogeneous shapes, one executable
        ragged_mode = engine._ragged_plan(rp) is not None
        runs = [t.run.model_copy() for t in live]
        shown = [t.payload for t in live]   # what a gallery's infotext says
        ahead: Dict[int, tuple] = {}    # a ticket's place -> its draw
        pinned = 0      # chunks an expansion script pins the context to

        def draw(p):
            """(noise, true latent rows) of one ticket: what reads nothing
            of its prompt's text."""
            tr = h      # ragged: the TRUE latent rows, zero-padded to h
            if ragged_mode:
                tw, th = engine._ragged_plan(p) or (width, height)
                tr = min(h, -(-th // f))
            with obs_spans.span("noise"):
                return rng.batch_noise(
                    p.seed, p.subseed, p.subseed_strength, 0,
                    p.total_images, (tr, w, C),
                    seed_resize=engine._seed_resize_latent(p),
                    pin_index=p.same_seed), tr

        if g.key[EXPANSION_AT] is not None:
            # the tickets' noise is drawn under the scan's first decode
            # chunk, when this thread has nothing to do but wait
            shown = self._group_expand(
                live, runs,
                lambda: ahead.update(
                    (k, draw(p)) for k, p in enumerate(runs)), later)
            pinned = int(runs[0].context_chunks or 0)
        # context length pinned to the group max so every merged request
        # pads its conditioning identically (same contract the fleet pins
        # via payload.context_chunks); an expansion's own pin floors it,
        # as it does a solo request's
        chunks = max([engine.request_context_chunks(p) for p in runs]
                     + [pinned])
        perf_on = obs_perf.enabled()
        counts, noise_parts, key_parts = [], [], []
        ctx_rows, pooled_rows = [], []
        true_rows_l, ctx_true_u_l, ctx_true_c_l = [], [], []
        true_tok = padded_tok = 0
        ctx_u = pooled_u = None
        for k, (t, p) in enumerate(zip(live, runs)):
            p.context_chunks = chunks
            n_p = p.total_images
            counts.append(n_p)
            if traced_group:
                # install THIS member's set before its encode so its TE
                # deltas (and the content-addressed cond-cache key) apply
                # to its own conditioning rows
                _, tags = lora_mod.extract_lora_tags(p.prompt or "")
                ts = engine._traced_set_for(tuple(tags))
                if ts is None:
                    # registry changed between grouping and execution
                    raise RuntimeError(
                        f"traced LoRA set for {tags!r} no longer "
                        f"resolvable at dispatch")
                engine._traced_lora = ts
                row_sets += [ts] * n_p
            part, tr = ahead.pop(k, None) or draw(p)
            # an expanded request of several images: a text an image
            own = {"prompts": p.all_prompts} if p.all_prompts else {}
            if ragged_mode:
                noise_parts.append(jnp.pad(
                    part, ((0, 0), (0, h - tr), (0, 0), (0, 0))))
                (cu, cc), (pu, pc), (ct_u, ct_c) = engine.encode_prompts(
                    p, ragged=True)     # (never an expanded request's)
                true_rows_l += [tr] * n_p
                ctx_true_u_l += [ct_u] * n_p
                ctx_true_c_l += [ct_c] * n_p
            else:
                noise_parts.append(part)
                (cu, cc), (pu, pc) = engine.encode_prompts(p, **own)
            if perf_on:
                try:
                    tt, pt = engine.request_token_stats(p, chunks=chunks)
                    true_tok += tt
                    padded_tok += pt
                except Exception:  # noqa: BLE001 — telemetry stays passive
                    pass
            with obs_spans.span("batch.assemble", rows=n_p):
                key_parts.append(engine._image_keys(p, 0, n_p))
                self._drain_cache_notes(t.request_id, prefix=False)
                ctx_rows.append(jnp.broadcast_to(cc, (n_p,) + cc.shape[1:]))
                pooled_rows.append(
                    jnp.broadcast_to(pc, (n_p,) + pc.shape[1:]))
            if ctx_u is None:
                ctx_u, pooled_u = cu, pu  # equal negatives across the key

        with obs_spans.span("batch.assemble", rows=sum(counts)):
            b_raw = sum(counts)
            b_run = self.bucketer.bucket_batch(b_raw)
            noise = jnp.concatenate(noise_parts, axis=0)
            keys = jnp.concatenate(key_parts, axis=0)
            ctx_c = jnp.concatenate(ctx_rows, axis=0)
            pooled_c = jnp.concatenate(pooled_rows, axis=0)
            if b_run > b_raw:
                # pad-and-drop up to the batch bucket: the extra rows repeat
                # the last image and are discarded after decode
                pad = b_run - b_raw

                def _pad(a):
                    return jnp.concatenate(
                        [a, jnp.repeat(a[-1:], pad, axis=0)], axis=0)

                noise, keys = _pad(noise), _pad(keys)
                ctx_c, pooled_c = _pad(ctx_c), _pad(pooled_c)
                if ragged_mode:
                    true_rows_l += [true_rows_l[-1]] * pad
                    ctx_true_u_l += [ctx_true_u_l[-1]] * pad
                    ctx_true_c_l += [ctx_true_c_l[-1]] * pad
            ragged_arg = None
            if ragged_mode:
                ragged_arg = (jnp.asarray(true_rows_l, jnp.int32),
                              jnp.asarray(ctx_true_u_l, jnp.int32),
                              jnp.asarray(ctx_true_c_l, jnp.int32))
            lora_arg = None
            if traced_group:
                # per-row factor stack (pad rows repeat the last member's set,
                # matching the pad-and-drop image rows); content joins each
                # DISTINCT member content so prefix capture can't alias across
                # adapter combos
                uniq: List[str] = []
                for ts in row_sets:
                    if ts.content not in uniq:
                        uniq.append(ts.content)
                lora_arg = (row_sets[0].sig, "|".join(uniq),
                            lora_mod.stack_row_sets(row_sets, b_run)["unet"])

            x = engine._place_batch(noise.astype(jnp.float32) * sigmas[0])
        return {
            "live": live, "counts": counts, "rp": rp, "shown": shown,
            # an expansion's counters, fetched once the UNet is queued
            "account": functools.partial(self._run_each, later)
            if later else None,
            "width": width, "height": height, "h": h, "f": f,
            "x": x, "keys": keys,
            "ctx": (ctx_u, ctx_c), "pooled": (pooled_u, pooled_c),
            "ragged": ragged_arg, "lora": lora_arg,
            "ragged_mode": ragged_mode, "b_raw": b_raw, "b_run": b_run,
            "true_rows": true_rows_l,
            "true_tok": true_tok, "padded_tok": padded_tok,
            "perf_on": perf_on, "traced_group": traced_group,
            "lora_rb": lora_rb, "lora_sc": lora_sc,
        }

    def _group_denoise(self, g: _Group, built: Dict):
        """Denoise stage: the single coalesced ``_denoise_range`` call
        plus its perf-ledger record."""
        engine = self._engine()
        live, counts, rp = built["live"], built["counts"], built["rp"]
        width, height = built["width"], built["height"]
        ctx_u, ctx_c = built["ctx"]
        pooled_u, pooled_c = built["pooled"]
        b_raw, b_run = built["b_raw"], built["b_run"]
        perf_on = built["perf_on"]
        # perf ledger (SDTPU_PERF): host-observed denoise seconds —
        # passive perf_counter reads, no extra device syncs, and with the
        # knob off record_dispatch is a no-op (dispatch stays byte-
        # identical to the uninstrumented path)
        if perf_on:
            t0_dev = time.perf_counter()
        latents = engine._denoise_range(
            rp, built["x"], built["keys"], (ctx_u, ctx_c),
            (pooled_u, pooled_c),
            width, height, 0, rp.steps, "txt2img", None, None, (),
            ragged=built["ragged"], lora=built["lora"],
            account=built["account"])
        self._drain_cache_notes(live[0].request_id, embed=False)
        if perf_on:
            # masked pixels: resident tail rows the ragged kernel skips —
            # reported separately so padding attribution can split masked
            # residency from compute padding
            masked_px = 0
            if built["ragged_mode"]:
                masked_px = (built["h"] * b_run
                             - sum(built["true_rows"])) * built["f"] * width
            obs_perf.LEDGER.record_dispatch(
                bucket=f"{width}x{height}", cadence=int(g.key[8]),
                precision=str(g.key[-1]),
                lora=(f"r{built['lora_rb']}s{built['lora_sc']}"
                      if built["traced_group"] else ""),
                device_s=time.perf_counter() - t0_dev,
                requests=len(live), batch_raw=b_raw, batch_run=b_run,
                true_pixels=sum(t.payload.width * t.payload.height * n_p
                                for t, n_p in zip(live, counts)),
                padded_pixels=width * height * b_run,
                masked_pixels=masked_px,
                true_tokens=built["true_tok"],
                padded_tokens=built["padded_tok"],
                hbm=obs_tsdb.dispatch_memory_sample())
        elif obs_tsdb.enabled():
            # per-dispatch HBM watermark still lands in the TSDB series
            # even when the perf ledger is off
            obs_tsdb.dispatch_memory_sample()
        return latents

    def _group_decode(self, g: _Group, built: Dict, latents):
        """Decode stage: dispatch the VAE on the denoised latents. The
        returned entries hold device arrays — nothing blocks here; the
        merge stage's np fetch is the materialization point."""
        return self._engine()._queue_decoded(
            latents, 0, built["b_raw"], built["width"], built["height"])

    def _group_merge(self, g: _Group, built: Dict, entries) -> None:
        """Merge stage: walk the decode dispatches in order (one image
        each, ``engine._queue_decoded``): wait for image i and copy it
        down, crop it if bucketed and encode it into the ticket that owns
        it, all while the device decodes image i+1. A ticket's result
        (gallery, journal record) is set once all of ITS images are in; a
        cancelled ticket's images are still fetched, and dropped. Finishes
        the progress record."""
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            GenerationResult,
        )

        engine = self._engine()
        live, counts = built["live"], built["counts"]
        # ragged rows are TOP-aligned (valid latent rows form a prefix);
        # only the width snap center-crops
        crop = self.bucketer.crop_ragged if built["ragged_mode"] \
            else self.bucketer.crop
        jr_on = obs_journal.enabled()
        # the batch's kept rows in order: the ticket that owns the row (its
        # place in the group, the ticket, its gallery), the row's index in
        # the gallery, whether it is the ticket's last
        owners = []
        for k, (t, n_p, seen) in enumerate(zip(live, counts,
                                               built["shown"])):
            out = GenerationResult(parameters=seen.model_dump())
            owners += [(k, t, seen, out, j, j + 1 == n_p)
                       for j in range(n_p)]
        for (img_dev, *_), (k, t, seen, out, j, last) in zip(entries,
                                                           owners):
            with trace.STATS.timer("vae_decode_fetch"):
                img = engine._fetch_decoded(img_dev)
            if jr_on and (k, j) == (0, 0):
                obs_journal.emit("decoded", live[0].request_id,
                                 images=built["b_raw"],
                                 batch_run=built["b_run"])
            with obs_spans.span("merge.split", request=k, image=j):
                ow, oh = t.payload.width, t.payload.height
                if not t.cancelled.is_set():
                    # (an expanded ticket's: with the text it drew from)
                    engine._append_image(
                        out, seen,
                        crop(img, ow, oh) if t.bucketed else img, j, ow, oh)
                if last and t.cancelled.is_set():
                    t.result = self._empty_result(t)
                elif last:
                    t.result = out
                    if jr_on:
                        obs_journal.emit("merged", t.request_id,
                                         images=j + 1)
        engine.state.finish()

    # -- result fix-up -----------------------------------------------------

    def _empty_result(self, ticket: Ticket):
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            GenerationResult,
        )

        params = ticket.payload.model_dump()
        params["cancelled"] = True
        return GenerationResult(parameters=params)

    def _restore_solo(self, result, ticket: Ticket):
        """Crop a bucketed solo run back to the requested size and rebuild
        infotext from the ORIGINAL payload so user-visible metadata shows
        the requested dimensions."""
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            b64png_to_array, build_infotext, encode_b64png,
        )

        orig = ticket.payload
        bw, bh = ticket.run.width, ticket.run.height
        crop = self.bucketer.crop_ragged \
            if self._engine()._ragged_plan(ticket.run) is not None \
            else self.bucketer.crop
        for i, b64 in enumerate(result.images):
            arr = b64png_to_array(b64)
            if arr.shape[:2] != (bh, bw):
                continue  # hires/second-pass output: not bucket-sized
            with obs_spans.span("png_encode", recrop=True) as sp:
                result.images[i], strips = encode_b64png(
                    crop(arr, orig.width, orig.height))
                if sp is not None:
                    sp.attrs["strips"] = strips
            suffix = ""
            if i < len(result.infotexts) and \
                    result.infotexts[i].endswith(", DPM adaptive: incomplete"):
                suffix = ", DPM adaptive: incomplete"
            prompt_i = result.prompts[i] if i < len(result.prompts) \
                else orig.prompt
            result.infotexts[i] = build_infotext(
                orig, int(result.seeds[i]), int(result.subseeds[i]),
                self._engine().model_name, orig.width, orig.height,
                prompt_override=prompt_i) + suffix
        return result

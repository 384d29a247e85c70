"""Generation backends ("workers") and their health state machine.

A :class:`WorkerNode` is one schedulable generation backend. The reference's
worker is always a remote sdwui HTTP process
(/root/reference/scripts/spartan/worker.py:51-758); here a backend is
pluggable:

- :class:`LocalBackend` — the in-process Engine on the local TPU mesh (the
  "master" role; the reference times local generation the same way,
  world.py:188-197);
- :class:`HTTPBackend` — a remote sdapi-v1 server (another host running
  this framework, or an actual sdwui instance) — capability parity with the
  reference's transport (worker.py:288-504);
- :class:`StubBackend` — deterministic fake for tests and failure injection
  (SURVEY.md §4 test strategy).

State machine parity (worker.py:36-41, 719-758): 5 states with guarded
transitions; a demotion to UNAVAILABLE invalidates the loaded-model cache so
a reconnect forces re-sync.
"""

from __future__ import annotations

import dataclasses
import enum
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Protocol, Tuple

from stable_diffusion_webui_distributed_tpu.obs import (
    prometheus as obs_prom,
)
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
    GenerationResult,
)
from stable_diffusion_webui_distributed_tpu.runtime.config import (
    BenchmarkPayload,
    WARMUP_SAMPLES,
    RECORDED_SAMPLES,
)
from stable_diffusion_webui_distributed_tpu.runtime.daemon import (
    StoppableDaemon,
)
from stable_diffusion_webui_distributed_tpu.runtime.logging import get_logger
from stable_diffusion_webui_distributed_tpu.scheduler import eta as eta_mod


class State(enum.Enum):
    IDLE = 1
    WORKING = 2
    INTERRUPTED = 3
    UNAVAILABLE = 4
    DISABLED = 5


#: Guarded transition table (reference worker.py:738-743). UNAVAILABLE is
#: reachable from anywhere except DISABLED (handled specially in set_state).
TRANSITIONS = {
    State.IDLE: {State.IDLE, State.WORKING, State.DISABLED},
    State.WORKING: {State.WORKING, State.IDLE, State.INTERRUPTED},
    State.UNAVAILABLE: {State.IDLE},
    State.INTERRUPTED: {State.WORKING, State.IDLE},
    State.DISABLED: {State.IDLE},
}


class WorkerHealth:
    """Rolling health telemetry for one worker.

    The state machine says what a worker IS (idle/working/unavailable);
    this says how it has been BEHAVING: error rate over a bounded outcome
    window, latency EWMA, consecutive-failure streak, images requeued
    away from it, and a ring of recent state transitions. Always on (it
    never touches response bytes); the summary feeds
    ``GET /internal/workers``, the ``sdtpu_worker_*`` Prometheus families
    and the fleet autoscaler's health veto (fleet/slices.py).
    """

    WINDOW = 32           # request outcomes retained
    TRANSITION_RING = 32  # state transitions retained
    EWMA_ALPHA = 0.3

    def __init__(self, label: str):
        self.label = label
        self._lock = threading.Lock()
        self._window: Deque[bool] = deque(
            maxlen=self.WINDOW)  # guarded-by: _lock
        self._transitions: Deque[Tuple[float, str, str]] = deque(
            maxlen=self.TRANSITION_RING)  # guarded-by: _lock
        self.requests = 0               # guarded-by: _lock
        self.failures = 0               # guarded-by: _lock
        self.consecutive_failures = 0   # guarded-by: _lock
        self.requeued_images = 0        # guarded-by: _lock
        self.latency_ewma_s: Optional[float] = None  # guarded-by: _lock

    def record_result(self, ok: bool,
                      latency_s: Optional[float] = None) -> None:
        """One generate outcome; metrics are bumped outside the lock."""
        with self._lock:
            self.requests += 1
            self._window.append(bool(ok))
            if ok:
                self.consecutive_failures = 0
                if latency_s is not None:
                    prev = self.latency_ewma_s
                    self.latency_ewma_s = (
                        float(latency_s) if prev is None
                        else self.EWMA_ALPHA * float(latency_s)
                        + (1.0 - self.EWMA_ALPHA) * prev)
            else:
                self.failures += 1
                self.consecutive_failures += 1
            ewma = self.latency_ewma_s
        obs_prom.worker_count("requests", worker=self.label)
        if not ok:
            obs_prom.worker_count("failures", worker=self.label)
        elif ewma is not None:
            obs_prom.set_worker_latency(self.label, ewma)

    def record_requeue(self, images: int) -> None:
        """``images`` of this worker's slice were requeued elsewhere."""
        with self._lock:
            self.requeued_images += int(images)
        obs_prom.worker_count("requeued_images", int(images),
                              worker=self.label)

    def record_transition(self, frm: str, to: str) -> None:
        at = time.time()  # sdtpu-lint: wallclock — operator-facing timeline
        with self._lock:
            self._transitions.append((at, frm, to))
        obs_prom.worker_count("transitions", worker=self.label, to=to)

    def error_rate(self) -> float:
        with self._lock:
            if not self._window:
                return 0.0
            return (sum(1 for ok in self._window if not ok)
                    / len(self._window))

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            window = list(self._window)
            return {
                "requests": self.requests,
                "failures": self.failures,
                "window": len(window),
                "error_rate": ((sum(1 for ok in window if not ok)
                                / len(window)) if window else 0.0),
                "consecutive_failures": self.consecutive_failures,
                "latency_ewma_s": self.latency_ewma_s,
                "requeued_images": self.requeued_images,
                "transitions": [{"at": at, "from": f, "to": t}
                                for at, f, t in self._transitions],
            }


#: Sanctioned chaos-injection hook (sim/chaos.py). When armed, it is
#: consulted inside :meth:`WorkerNode.request`'s try-block just before
#: ``backend.generate`` — a raised exception lands in the existing
#: failure/demote/requeue path, a sleep models a stall or slow worker.
#: ``None`` (the default) costs one identity check on the hot path.
CHAOS_HOOK = None


class Backend(Protocol):
    """What a schedulable backend must provide."""

    def generate(self, payload: GenerationPayload, start_index: int,
                 count: int) -> GenerationResult: ...

    def reachable(self) -> bool: ...

    def interrupt(self) -> None: ...

    def restart(self) -> None: ...

    def load_options(self, model: str, vae: str = "") -> None: ...

    def available_models(self) -> List[str]: ...

    def memory_info(self) -> Dict[str, Any]: ...


class WorkerNode:
    """One schedulable backend + its calibration, state, and caps."""

    def __init__(
        self,
        label: str,
        backend: Backend,
        master: bool = False,
        pixel_cap: int = 0,
        avg_ipm: Optional[float] = None,
        eta_percent_error: Optional[List[float]] = None,
        benchmark_payload: Optional[BenchmarkPayload] = None,
        model_override: Optional[str] = None,
    ):
        self.label = label
        self.backend = backend
        self.master = master
        self.pixel_cap = pixel_cap  # 0 = uncapped (reference -1, pmodels.py:34)
        self.cal = eta_mod.EtaCalibration(
            avg_ipm=avg_ipm,
            eta_percent_error=list(eta_percent_error or []),
        )
        self.benchmark_payload = benchmark_payload or BenchmarkPayload()
        # the state machine and model-sync cache are read by HTTP config
        # handlers, ping sweeps, and request threads concurrently; every
        # access outside __init__ must hold _lock (verified by sdtpu-lint
        # rule LK001)
        self.state = State.IDLE  # guarded-by: _lock
        self.loaded_model: Optional[str] = None  # guarded-by: _lock
        self.loaded_vae: Optional[str] = None  # guarded-by: _lock
        # script titles this backend supports (reference queries
        # /script-info per worker at ping time, world.py:744-763); None =
        # unknown (send everything)
        self.supported_scripts: Optional[List[str]] = None
        # checkpoint pin for this worker (reference ui.py:161-171); honored
        # by load_options and persisted via World.save_config
        self.model_override: Optional[str] = model_override
        # pin provenance: True = checked against the node's model list,
        # False = accepted while the node was unreachable (typo'd pins
        # stay visible, not latent), None = no pin / not yet checked.
        # Re-validated by World.ping_workers on the next successful ping.
        self.pin_validated: Optional[bool] = None
        # once a pin is positively refuted against a LIVE model list, ping
        # sweeps stop re-fetching it (no per-ping RPC/log spam); cleared on
        # pin change or node reconnect
        self._pin_refuted = False
        self.response_time: Optional[float] = None
        # free accelerator memory observed at first contact (the reference
        # queries /memory on a worker's first request, worker.py:319-340)
        self.free_memory: Optional[int] = None
        # interrupt rendezvous polled while a remote request is in flight
        # (None = the process-wide runtime.interrupt.STATE)
        self.interrupt_state = None
        self.interrupt_poll_s = 0.5  # reference's poll cadence
        # rolling behavioural telemetry (own lock; never nested under
        # _lock — set_state records transitions after releasing it)
        self.health = WorkerHealth(label)

        self._lock = threading.Lock()

    # -- state machine ------------------------------------------------------

    def set_state(self, state: State, expect_cycle: bool = False) -> bool:
        """Guarded transition; returns True if the state changed/held legally."""
        ok, changed = self._transition(state, expect_cycle)
        if changed is not None:
            # recorded after _lock is released (health has its own lock)
            self.health.record_transition(*changed)
        return ok

    def _transition(self, state: State, expect_cycle: bool,
                    ) -> Tuple[bool, Optional[Tuple[str, str]]]:
        """(legal, (from, to) if the state actually moved)."""
        log = get_logger()
        with self._lock:
            if state == State.UNAVAILABLE:
                if self.state == State.DISABLED:
                    log.debug("%s: disabled, refusing UNAVAILABLE", self.label)
                    return False, None
                prev = self.state
                # invalidate model cache so reconnection forces re-sync
                # (reference worker.py:747-755)
                self.loaded_model = None
                self.loaded_vae = None
                log.warning("worker '%s' unreachable; avoided until "
                            "reconnection", self.label)
                self.state = State.UNAVAILABLE
                return True, (prev.name, state.name)
            if state in TRANSITIONS.get(self.state, set()):
                if state != self.state or expect_cycle:
                    prev = self.state
                    log.debug("%s: %s -> %s", self.label, prev.name,
                              state.name)
                    self.state = state
                    return True, (prev.name, state.name)
                return True, None
            log.debug("%s: invalid transition %s -> %s", self.label,
                      self.state.name, state.name)
            return False, None

    @property
    def available(self) -> bool:
        with self._lock:
            return self.state not in (State.UNAVAILABLE, State.DISABLED)

    def current_state(self) -> State:
        """Locked state read for cross-thread callers (the scheduler's
        sweep/fan-out loops must not read ``state`` bare)."""
        with self._lock:
            return self.state

    # -- ETA ----------------------------------------------------------------

    def eta(self, payload, batch_size: Optional[int] = None,
            steps: Optional[int] = None, queue_wait: float = 0.0,
            padding_overhead: float = 1.0) -> float:
        # queue_wait/padding_overhead: serving-dispatcher additions for
        # backends behind a coalescing front end (scheduler/eta.py).
        # precision: the payload's requested serving precision scales the
        # compute part via the per-precision factor (int8 ~2x) so mixed
        # fleets predict each request at its own speed
        return eta_mod.predict_eta(self.cal, payload, self.benchmark_payload,
                                   batch_size=batch_size, steps=steps,
                                   queue_wait=queue_wait,
                                   padding_overhead=padding_overhead,
                                   precision=self._payload_precision(payload))

    @staticmethod
    def _payload_precision(payload) -> str:
        """Resolved precision name for ETA purposes (payload channel only
        — a remote backend's env defaults are not visible here, so an
        unspecified precision calibrates as the bf16 baseline)."""
        from stable_diffusion_webui_distributed_tpu.pipeline import (
            precision as precision_mod,
        )

        return precision_mod.resolve(payload).name

    # -- request lifecycle --------------------------------------------------

    def request(self, payload: GenerationPayload, start_index: int,
                count: int) -> Optional[GenerationResult]:
        """Generate images [start_index, start_index+count); returns None on
        failure (the reference logs and drops the worker's images,
        distributed.py:158-169 + worker.py:494-500)."""
        log = get_logger()
        # wait out a prior request still in flight (reference busy-wait,
        # worker.py:301-315)
        deadline = time.monotonic() + 30.0
        while self.current_state() == State.WORKING \
                and time.monotonic() < deadline:
            time.sleep(0.1)
        self.set_state(State.WORKING)

        payload = self.filter_payload_scripts(payload)
        if self.free_memory is None:
            self._probe_memory()
        predicted = None
        if self.cal.benchmarked:
            try:
                predicted = self.eta(payload, batch_size=count)
            except ValueError:
                predicted = None
        from stable_diffusion_webui_distributed_tpu.obs import (
            spans as obs_spans,
        )

        started = time.monotonic()
        stop_watch = self._start_interrupt_watchdog()
        try:
            with obs_spans.span("worker.generate", worker=self.label,
                                start=int(start_index), count=int(count),
                                predicted_s=predicted) as wsp:
                if CHAOS_HOOK is not None:
                    CHAOS_HOOK("worker.generate", worker=self.label,
                               payload=payload, count=int(count))
                result = self.backend.generate(payload, start_index, count)
        except Exception as e:  # noqa: BLE001 — any backend failure demotes
            log.error("worker '%s' failed request: %s", self.label, e)
            self.health.record_result(False)
            self.set_state(State.UNAVAILABLE)
            return None
        finally:
            if stop_watch is not None:
                stop_watch.halt()  # hot path: signal only, never join
        elapsed = time.monotonic() - started
        self.response_time = elapsed
        self.health.record_result(True, elapsed)
        if wsp is not None:
            # predicted-vs-actual on the span itself: one request's ETA
            # calibration quality is readable straight off its trace
            wsp.attrs["actual_s"] = elapsed
        if predicted is not None:
            # precision-scoped: an int8 sample refines the int8 factor
            # only and never enters the bf16 MPE window (scheduler/eta.py)
            eta_mod.record_eta_error(self.cal, predicted, elapsed,
                                     precision=self._payload_precision(
                                         payload))
        self.set_state(State.IDLE)
        return result

    def _start_interrupt_watchdog(self) -> Optional[StoppableDaemon]:
        """Poll the local interrupt flag every 0.5 s while a request is in
        flight and fire ``backend.interrupt()`` the moment it latches — the
        reference's mid-request propagation loop
        (/root/reference/scripts/spartan/worker.py:440-448). The master's
        LocalBackend needs no watchdog: its chunked denoise loop reads the
        same flag between dispatches."""
        if self.master:
            return None
        from stable_diffusion_webui_distributed_tpu.runtime import (
            interrupt as interrupt_mod,
        )

        state = self.interrupt_state or interrupt_mod.STATE

        def watch():
            if not state.flag.interrupted:
                return
            get_logger().info(
                "interrupt: aborting in-flight request on '%s'",
                self.label)
            try:
                self.backend.interrupt()
            except Exception as e:  # noqa: BLE001
                get_logger().error(
                    "in-flight interrupt of '%s' failed: %s",
                    self.label, e)
            daemon.halt()  # fired once: the watch is done

        # immediate=False: first poll lands one period in, like the
        # reference's stop.wait(period) loop
        daemon = StoppableDaemon(f"interrupt-watch-{self.label}", watch,
                                 self.interrupt_poll_s, immediate=False)
        daemon.start()
        return daemon

    def _probe_memory(self) -> None:
        """First-contact memory probe (reference worker.py:319-340): record
        free accelerator memory, warn when it looks too tight for the
        workload; failures are non-fatal."""
        try:
            info = self.backend.memory_info()
        except Exception:  # noqa: BLE001
            self.free_memory = -1
            return
        free = None
        cuda = info.get("cuda") or {}
        if isinstance(cuda, dict):
            free = (cuda.get("system") or {}).get("free")
        if free is None:
            tpu = info.get("tpu") or {}
            # devices without memory stats (bytes_limit 0, e.g. CPU test
            # platforms) don't count as "0 bytes free"
            devs = [d for d in (tpu.get("devices") or [])
                    if d.get("bytes_limit", 0) > 0]
            if devs:
                free = sum(max(0, d["bytes_limit"]
                               - d.get("bytes_in_use", 0)) for d in devs)
        self.free_memory = int(free) if free is not None else -1
        if 0 <= self.free_memory < 2 << 30:
            get_logger().warning(
                "worker '%s' reports only %.1f GiB free accelerator memory",
                self.label, self.free_memory / (1 << 30))

    def interrupt(self) -> None:
        try:
            self.backend.interrupt()
            self.set_state(State.INTERRUPTED)
        except Exception as e:  # noqa: BLE001
            get_logger().error("interrupt of '%s' failed: %s", self.label, e)
            self.set_state(State.UNAVAILABLE)

    def restart(self) -> bool:
        """Ask this backend's server process to restart (reference
        worker.py:690-717). The node goes UNAVAILABLE with its model cache
        invalidated; the next ping sweep revives it once it's back."""
        try:
            self.backend.restart()
        except Exception as e:  # noqa: BLE001
            get_logger().error("restart of '%s' failed: %s", self.label, e)
            return False
        self.set_state(State.UNAVAILABLE)
        return True

    def reachable(self) -> bool:
        try:
            ok = self.backend.reachable()
        except Exception:  # noqa: BLE001
            return False
        if ok:
            # re-query at every ping: a restarted worker may have gained or
            # lost script support (reference re-discovers per ping sweep,
            # world.py:744-763)
            try:
                self.supported_scripts = self.backend.script_info()
            except Exception:  # noqa: BLE001
                pass  # keep the previous knowledge
        return ok

    def filter_payload_scripts(self, payload: GenerationPayload
                               ) -> GenerationPayload:
        """Strip alwayson-script args this backend doesn't support — the
        reference's per-worker compat filter (worker.py:375-404; script
        discovery at world.py:744-763)."""
        if not payload.alwayson_scripts or self.supported_scripts is None:
            return payload
        supported = {s.lower() for s in self.supported_scripts}
        kept = {k: v for k, v in payload.alwayson_scripts.items()
                if k.lower() in supported}
        if len(kept) == len(payload.alwayson_scripts):
            return payload
        dropped = set(payload.alwayson_scripts) - set(kept)
        get_logger().debug("worker '%s': dropping unsupported script args %s",
                           self.label, sorted(dropped))
        payload = payload.model_copy()
        payload.alwayson_scripts = kept
        return payload

    def load_options(self, model: str, vae: str = "") -> bool:
        """Sync the loaded checkpoint (reference worker.py:646-688)."""
        if self.model_override:
            model = self.model_override
        with self._lock:
            if self.loaded_model == model and self.loaded_vae == vae:
                return True
        try:
            t0 = time.monotonic()
            self.backend.load_options(model, vae)
            get_logger().info("worker '%s' loaded model '%s' in %.1fs",
                              self.label, model, time.monotonic() - t0)
            with self._lock:
                self.loaded_model, self.loaded_vae = model, vae
            return True
        except Exception as e:  # noqa: BLE001
            get_logger().error("model sync to '%s' failed: %s", self.label, e)
            self.set_state(State.UNAVAILABLE)
            return False

    # -- benchmark ----------------------------------------------------------

    def benchmark(self, rebenchmark: bool = False) -> Optional[float]:
        """2 warmup + 3 recorded samples of the fixed benchmark payload ->
        avg images/minute (reference worker.py:506-575, shared.py:63-64)."""
        log = get_logger()
        if self.cal.benchmarked and not rebenchmark:
            return self.cal.avg_ipm
        if not self.reachable():
            self.set_state(State.UNAVAILABLE)
            return None
        bp = self.benchmark_payload
        payload = GenerationPayload(
            prompt=bp.prompt, negative_prompt=bp.negative_prompt,
            steps=bp.steps, width=bp.width, height=bp.height,
            batch_size=bp.batch_size, sampler_name=bp.sampler_name, seed=1,
        )
        ipms = []
        for i in range(WARMUP_SAMPLES + RECORDED_SAMPLES):
            t0 = time.monotonic()
            try:
                result = self.backend.generate(payload, 0, bp.batch_size)
            except Exception as e:  # noqa: BLE001
                log.error("benchmark of '%s' failed: %s", self.label, e)
                self.set_state(State.UNAVAILABLE)
                return None
            elapsed = time.monotonic() - t0
            sample_ipm = len(result.images) / (elapsed / 60.0)
            if i < WARMUP_SAMPLES:
                log.debug("benchmark '%s' warmup %d: %.2f ipm",
                          self.label, i, sample_ipm)
            else:
                ipms.append(sample_ipm)
                log.debug("benchmark '%s' sample %d: %.2f ipm",
                          self.label, i - WARMUP_SAMPLES, sample_ipm)
        self.cal.avg_ipm = sum(ipms) / len(ipms)
        self.cal.eta_percent_error.clear()  # stale MPE dies with re-bench
        log.info("worker '%s': %.2f ipm", self.label, self.cal.avg_ipm)
        return self.cal.avg_ipm


# --------------------------------------------------------------------------
# backends
# --------------------------------------------------------------------------

class LocalBackend:
    """The in-process Engine (master role)."""

    def __init__(self, engine):
        self.engine = engine

    def generate(self, payload, start_index, count):
        return self.engine.generate_range(payload, start_index, count)

    def reachable(self) -> bool:
        return True

    def interrupt(self) -> None:
        self.engine.state.flag.interrupt()

    def restart(self) -> None:
        # the master restarts through its own /server-restart route (the
        # serve loop re-execs); a cluster restart fan-out skips it
        raise RuntimeError("local master cannot restart itself")

    def load_options(self, model: str, vae: str = "") -> None:
        # local model switching is handled by the ModelRegistry at the
        # server layer; the engine itself holds one loaded family
        self.engine.model_name = model or self.engine.model_name

    def script_info(self) -> List[str]:
        return ["controlnet"]  # natively supported in-graph

    def available_models(self) -> List[str]:
        return [self.engine.model_name]

    def memory_info(self) -> Dict[str, Any]:
        import jax

        devices = []
        for d in jax.devices():
            try:
                stats = d.memory_stats() or {}
            except Exception:  # noqa: BLE001 — CPU backends lack stats
                stats = {}
            devices.append({
                "id": d.id, "kind": d.device_kind,
                "bytes_in_use": stats.get("bytes_in_use", 0),
                "bytes_limit": stats.get("bytes_limit", 0),
            })
        # same shape the sdapi /memory route serves, so _probe_memory
        # parses local and remote backends identically
        return {"tpu": {"devices": devices}}


@dataclasses.dataclass
class StubBehavior:
    """Failure-injection knobs for tests."""

    seconds_per_image: float = 0.0
    fail_generate: bool = False
    fail_reachable: bool = False
    fail_after_n_requests: Optional[int] = None
    supported_scripts: Tuple[str, ...] = ("controlnet",)


class StubBackend:
    """Deterministic in-process fake worker (SURVEY §4: failure injection)."""

    def __init__(self, behavior: Optional[StubBehavior] = None):
        self.behavior = behavior or StubBehavior()
        self.requests: List[Dict[str, Any]] = []
        self.interrupted = False
        self.restarted = False
        self.options: Dict[str, str] = {}
        self.models: List[str] = ["stub-model"]

    def generate(self, payload, start_index, count):
        n = len(self.requests)
        self.requests.append(
            {"payload": payload, "start": start_index, "count": count})
        b = self.behavior
        if b.fail_generate or (
            b.fail_after_n_requests is not None
            and n >= b.fail_after_n_requests
        ):
            raise ConnectionError("stub backend injected failure")
        result = GenerationResult()
        pinned = payload.same_seed or payload.subseed_strength > 0
        for i in range(start_index, start_index + count):
            if b.seconds_per_image:
                # sleep in slices so an interrupt lands mid-flight, like a
                # real remote that returns the images finished so far
                deadline = time.monotonic() + b.seconds_per_image
                while time.monotonic() < deadline and not self.interrupted:
                    time.sleep(0.01)
            if self.interrupted:
                break
            # per-image seed/prompt arithmetic mirrors Engine._append_image
            seed_i = payload.seed + (0 if pinned else i)
            sub_i = payload.subseed + (0 if payload.same_seed else i)
            prompt_i = payload.prompt
            if payload.all_prompts and i < len(payload.all_prompts):
                prompt_i = payload.all_prompts[i]
            result.images.append(f"stub-image-{seed_i}")
            result.seeds.append(seed_i)
            result.subseeds.append(sub_i)
            result.prompts.append(prompt_i)
            result.negative_prompts.append(payload.negative_prompt)
            result.infotexts.append(f"{prompt_i}, Seed: {seed_i}")
            result.worker_labels.append("")
        return result

    def reachable(self) -> bool:
        return not self.behavior.fail_reachable

    def interrupt(self) -> None:
        self.interrupted = True

    def restart(self) -> None:
        if self.behavior.fail_reachable:
            raise ConnectionError("stub: restart failure")
        self.restarted = True

    def load_options(self, model: str, vae: str = "") -> None:
        if self.behavior.fail_generate:
            raise ConnectionError("stub: load_options failure")
        self.options = {"model": model, "vae": vae}

    def script_info(self) -> List[str]:
        return list(self.behavior.supported_scripts)

    def available_models(self) -> List[str]:
        return list(self.models)

    def memory_info(self) -> Dict[str, Any]:
        return {"ram": {"free": 1 << 30, "used": 0, "total": 1 << 30}}


class HTTPBackend:
    """Remote sdapi-v1 server over HTTP(S) — the reference's entire transport
    (worker.py:192-203 route table, 288-504 request path), kept for parity so
    a pool of this framework's servers (or legacy sdwui nodes) can be driven.
    """

    def __init__(self, address: str, port: int, tls: bool = False,
                 user: Optional[str] = None, password: Optional[str] = None,
                 verify_tls: bool = True, timeout: Optional[float] = None):
        self.address = address
        self.port = port
        self.tls = tls
        self.user = user
        self.password = password
        self.verify_tls = verify_tls
        if timeout is None:
            # control-plane probe timeout (reachable/interrupt/heartbeat
            # sweeps): the obs-plane-wide SDTPU_OBS_HTTP_TIMEOUT_S knob
            # bounds it, defaulting to the historical 3.0s
            from ..obs import stitch as obs_stitch

            timeout = obs_stitch.http_timeout_s(3.0)
        self.timeout = timeout
        import requests

        self.session = requests.Session()
        self.session.verify = verify_tls
        if user or password:
            self.session.auth = (user or "", password or "")

    def url(self, route: str) -> str:
        scheme = "https" if self.tls else "http"
        return f"{scheme}://{self.address}:{self.port}/sdapi/v1/{route}"

    def close(self) -> None:
        """Release pooled connections (called when a backend is replaced by
        an endpoint edit, or a transient validation probe is done)."""
        self.session.close()

    def generate(self, payload: GenerationPayload, start_index: int,
                 count: int) -> GenerationResult:
        from stable_diffusion_webui_distributed_tpu.obs import (
            spans as obs_spans,
        )

        # cross-node trace propagation: the remote roots its own spans
        # under the same request id (obs/stitch.py correlates on it).
        # Session headers, not a per-call kwarg, so every hop (including
        # the sampler-fallback retry) carries them.
        rid = obs_spans.current_request_id()
        if rid:
            self.session.headers["X-SDTPU-Request-Id"] = rid
            tp = obs_spans.traceparent()
            if tp:
                self.session.headers["traceparent"] = tp
        else:
            self.session.headers.pop("X-SDTPU-Request-Id", None)
            self.session.headers.pop("traceparent", None)
        body = payload.model_dump()
        # seed fan-out arithmetic, identical to the reference master
        # (distributed.py:297-305): offset by prior images. Same-seed
        # batches (prompt matrix) pin every image to the request seed.
        if payload.subseed_strength == 0 and not payload.same_seed:
            body["seed"] = payload.seed + start_index
        if not payload.same_seed:
            body["subseed"] = payload.subseed + start_index
        # per-image prompts: the remote gets ITS slice, indexed from 0
        if payload.all_prompts:
            body["all_prompts"] = \
                payload.all_prompts[start_index:start_index + count]
        body["batch_size"] = count
        body["n_iter"] = 1
        route = "img2img" if payload.init_images else "txt2img"
        r = self.session.post(self.url(route), json=body, timeout=3600)
        if r.status_code == 404 and "sampler" in r.text.lower():
            # legacy remote doesn't know this sampler: retry with Euler a,
            # the reference's degraded-capability fallback (worker.py:457-467)
            get_logger().warning(
                "remote %s:%d lacks sampler '%s'; retrying with Euler a",
                self.address, self.port, body.get("sampler_name"))
            body["sampler_name"] = "Euler a"
            r = self.session.post(self.url(route), json=body, timeout=3600)
        r.raise_for_status()
        data = r.json()
        result = GenerationResult(images=data.get("images", []))
        info = data.get("info")
        if isinstance(info, str):
            import json as _json

            try:
                info = _json.loads(info)
            except ValueError:
                info = {}
        info = info or {}
        result.seeds = info.get("all_seeds",
                                [body["seed"] + i for i in range(count)])
        result.subseeds = info.get("all_subseeds",
                                   [body["subseed"] + i for i in range(count)])
        result.prompts = info.get("all_prompts", [payload.prompt] * count)
        result.negative_prompts = info.get(
            "all_negative_prompts", [payload.negative_prompt] * count)
        result.infotexts = info.get("infotexts", [""] * count)
        result.worker_labels = [""] * len(result.images)
        return result

    def reachable(self) -> bool:
        try:
            r = self.session.get(self.url("memory"), timeout=self.timeout)
            return r.ok
        except Exception:  # noqa: BLE001
            return False

    def interrupt(self) -> None:
        self.session.post(self.url("interrupt"), timeout=self.timeout)

    def restart(self) -> None:
        """POST /server-restart (the reference's fleet-restart leg,
        worker.py:690-717). A server that re-execs before answering drops
        the connection or never flushes a response — both count as
        delivered; only failing to CONNECT is a real failure."""
        import requests

        try:
            self.session.post(self.url("server-restart"),
                              timeout=self.timeout)
        except requests.exceptions.ConnectTimeout:
            raise  # never reached the worker
        except (requests.exceptions.ConnectionError,
                requests.exceptions.ReadTimeout):
            return  # process went down (or stopped answering) to restart

    def load_options(self, model: str, vae: str = "") -> None:
        body = {"sd_model_checkpoint": model}
        if vae:
            body["sd_vae"] = vae
        r = self.session.post(self.url("options"), json=body, timeout=600)
        r.raise_for_status()

    def script_info(self) -> List[str]:
        r = self.session.get(self.url("script-info"), timeout=self.timeout)
        r.raise_for_status()
        names = []
        for entry in r.json():
            if isinstance(entry, dict) and entry.get("name"):
                names.append(entry["name"])
            elif isinstance(entry, str):
                names.append(entry)
        return names

    def available_models(self) -> List[str]:
        r = self.session.get(self.url("sd-models"), timeout=self.timeout)
        r.raise_for_status()
        return [m.get("model_name", m.get("title", "?")) for m in r.json()]

    def memory_info(self) -> Dict[str, Any]:
        r = self.session.get(self.url("memory"), timeout=self.timeout)
        r.raise_for_status()
        return r.json()

"""sdapi-v1 HTTP server on the stdlib ThreadingHTTPServer (no extra deps).

Route surface mirrors what the reference consumes from each worker
(/root/reference/scripts/spartan/worker.py:192-203) plus the webui response
shapes it decodes (images as base64 PNG, ``info`` as a JSON-encoded string
with ``all_seeds``/``infotexts`` — distributed.py:103-181). ``/memory``
reports TPU HBM in both a native ``tpu`` section and the legacy
``cuda.system`` shape the reference's VRAM probe reads (worker.py:322-340).
"""

from __future__ import annotations

import base64
import json
import os
import signal
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, Optional, Tuple

from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    Base64Text,
    GenerationPayload,
    GenerationResult,
)
from stable_diffusion_webui_distributed_tpu.runtime import config as config_mod
from stable_diffusion_webui_distributed_tpu.runtime import interrupt as interrupt_mod
from stable_diffusion_webui_distributed_tpu.runtime.logging import get_logger
from stable_diffusion_webui_distributed_tpu.samplers.kdiffusion import SAMPLERS


#: stands where a response's images go while the rest is serialised
_IMAGES_MARK = "\x00sdtpu:images\x00"
_IMAGES_MARK_JSON = json.dumps(_IMAGES_MARK)


def json_body(obj: Any) -> Tuple[bytes, int]:
    """(``json.dumps(obj).encode()`` byte for byte, images copied). A
    response's ``images`` that are all the encoder's own base64
    (``payload.Base64Text``) are copied in as bytes and not read character
    by character: a four-image response is 3 to 4 MB of them, and reading
    them was 6 of ``respond.serialize``'s 17 ms with the device idle
    (PERF.md section 6, PR 45). Anything else, images from another worker
    among it, goes through ``json.dumps`` whole."""
    images = obj.get("images") if type(obj) is dict else None
    if type(images) is list and images and all(
            type(image) is Base64Text for image in images):
        head, found, tail = json.dumps(
            {**obj, "images": _IMAGES_MARK}).partition(_IMAGES_MARK_JSON)
        if found and _IMAGES_MARK_JSON not in tail:
            return b"".join([
                head.encode(), b'["',
                b'", "'.join(image.encode("ascii") for image in images),
                b'"]', tail.encode()]), len(images)
    return json.dumps(obj).encode(), 0


class TextResponse(str):
    """A handler return value sent as plain text instead of JSON/HTML
    (Prometheus exposition needs ``text/plain; version=0.0.4``)."""

    content_type = "text/plain; version=0.0.4; charset=utf-8"


class _StampingServer(ThreadingHTTPServer):
    """Stamps a connection on the accept thread, where ``http.accept``
    begins. ``accepted`` (socket -> stamp) and ``host`` (the host clock's
    stats) are ``_bind``'s, and None with spans off."""

    accepted = host = None

    def get_request(self):
        request, address = super().get_request()
        if self.accepted is not None:
            self.accepted[request] = time.perf_counter()
        return request, address

    def shutdown_request(self, request):
        if self.accepted is not None:   # one no handler took up
            self.accepted.pop(request, None)
        super().shutdown_request(request)


class ApiServer:
    """One generation node's REST surface.

    ``source`` is whatever executes payloads: a ``World`` (distributed
    fan-out) or anything with ``execute(payload) -> GenerationResult`` /
    an ``Engine`` (single backend). Model switching goes through an optional
    ``registry`` (see pipeline/registry.py).
    """

    def __init__(
        self,
        source,
        registry=None,
        state: Optional[interrupt_mod.GenerationState] = None,
        host: str = "127.0.0.1",
        port: int = 7860,
        user: Optional[str] = None,
        password: Optional[str] = None,
    ):
        self.source = source
        self.registry = registry
        self.state = state or getattr(source, "state", None) \
            or interrupt_mod.STATE
        self.host = host
        self.port = port
        self._auth = None
        if user or password:
            token = base64.b64encode(
                f"{user or ''}:{password or ''}".encode()).decode()
            self._auth = f"Basic {token}"
        self.options: Dict[str, Any] = {
            "sd_model_checkpoint": getattr(registry, "current_name", "") or
            getattr(source, "current_model", "") or
            getattr(source, "model_name", ""),
            "sd_vae": "Automatic",
            "CLIP_stop_at_last_layers": 1,
        }
        self._httpd: Optional[ThreadingHTTPServer] = None
        self.host_clock = None      # obs/watchdog.py's, while serving
        self._busy = threading.Lock()
        self._benchmarking = threading.Lock()
        self.restart_requested = False
        self._styles_cache: Tuple = ((None, None), {})
        # continuous-batching front end for bare-Engine sources: shape
        # bucketing + request coalescing (serving/dispatcher.py). World
        # sources keep their fleet scheduler (SDTPU_SERVING=0 disables).
        self.dispatcher = None
        if not hasattr(source, "execute") \
                and hasattr(source, "generate_range") \
                and config_mod.env_flag("SDTPU_SERVING", True):
            from stable_diffusion_webui_distributed_tpu.serving.dispatcher \
                import ServingDispatcher

            self.dispatcher = ServingDispatcher(source)

    # -- request execution --------------------------------------------------

    def _execute(self, payload: GenerationPayload) -> GenerationResult:
        if hasattr(self.source, "execute"):
            return self.source.execute(payload)  # World resets the latch
        # bare Engine: this request is the top level — reset the latch and
        # expand native scripts here
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            apply_scripts,
        )

        self.state.begin_request()
        return self.source.generate_range(apply_scripts(payload))

    def _generation_response(self, result: GenerationResult) -> Dict[str, Any]:
        images = list(result.images)
        infotexts = list(result.infotexts)
        # webui prepends a grid image when return_grid is on and more than
        # one image came back (the reference's thin-client path rebuilds the
        # same grid, world.py:588-591)
        if self.options.get("return_grid") and len(images) > 1:
            grid = _make_grid_b64(images)
            if grid is not None:
                images.insert(0, grid)
                infotexts.insert(0, infotexts[0] if infotexts else "")
        info = {
            "all_seeds": result.seeds,
            "all_subseeds": result.subseeds,
            "all_prompts": result.prompts,
            "all_negative_prompts": result.negative_prompts,
            "infotexts": infotexts,
            "seed": result.seeds[0] if result.seeds else -1,
            "subseed": result.subseeds[0] if result.subseeds else -1,
        }
        return {
            "images": images,
            "parameters": result.parameters,
            # webui encodes info as a JSON string; the reference re-parses it
            "info": json.dumps(info),
        }

    # -- handlers ------------------------------------------------------------

    def _apply_styles(self, payload: GenerationPayload) -> None:
        if not payload.styles:
            return
        from stable_diffusion_webui_distributed_tpu.pipeline.styles import (
            apply_styles, load_styles,
        )

        model_dir = getattr(self.registry, "model_dir", ".") \
            if self.registry is not None else "."
        path = os.path.join(model_dir, "styles.csv")
        try:
            mtime = os.path.getmtime(path)
        except OSError:
            mtime = None
        if self._styles_cache[0] != (path, mtime):
            self._styles_cache = ((path, mtime), load_styles(path))
        apply_styles(payload, self._styles_cache[1])

    def _expand_scripts(self, payload: GenerationPayload) -> GenerationPayload:
        """Script expansion up front so invalid user input (e.g. a prompt
        matrix past the combination cap) surfaces as 422, not a 500 from
        deep inside the engine. apply_scripts is idempotent, so the later
        call in World.execute/Engine is a no-op."""
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            apply_scripts,
        )

        try:
            return apply_scripts(payload)
        except ValueError as e:
            raise ApiError(422, str(e))

    def _mint_request(self, payload: GenerationPayload, route: str):
        """Root obs span context for one API generation request.

        The request id comes from the client (``request_id`` in the
        payload — same field ``/internal/cancel`` addresses) or is minted
        here; either way it is pinned back onto the payload so the
        dispatcher, flight recorder and log correlation all agree on it."""
        from stable_diffusion_webui_distributed_tpu.obs import (
            spans as obs_spans,
        )

        rid = str(getattr(payload, "request_id", "") or uuid.uuid4().hex)
        payload.request_id = rid
        # width, height and steps class the request for the flight
        # recorder's slow rule (obs/spans.py:SLOW_RATIO)
        return obs_spans.request(rid, name=route.rsplit("/", 1)[-1],
                                 route=route, width=payload.width,
                                 height=payload.height, steps=payload.steps)

    def _submit_dispatch(self, payload: GenerationPayload,
                         job: str) -> GenerationResult:
        """Dispatcher submit with fleet admission mapped to HTTP: a
        quota/SLO refusal (fleet/admission.py) becomes 429 + Retry-After
        instead of a 500."""
        from stable_diffusion_webui_distributed_tpu.fleet.admission import (
            FleetRejected,
        )

        try:
            return self.dispatcher.submit(payload, job=job)
        except FleetRejected as e:
            raise ApiError(429, e.detail, headers={
                "Retry-After": str(max(1, round(e.retry_after)))})

    def handle_txt2img(self, body: Dict[str, Any]) -> Dict[str, Any]:
        from stable_diffusion_webui_distributed_tpu.pipeline.xyz import is_xyz

        payload = GenerationPayload(**body)
        self._apply_styles(payload)
        payload = self._expand_scripts(payload)
        with self._mint_request(payload, "/sdapi/v1/txt2img"):
            if self.dispatcher is not None and not is_xyz(payload):
                # continuous-batching path: the dispatcher owns
                # serialization (its exec lock) so concurrent compatible
                # requests can merge during the coalesce window instead of
                # queuing on _busy
                result = self._submit_dispatch(payload, job="txt2img")
                return self._generation_response(result)
            with self._busy:
                result = self._run_scripted(payload)
            return self._generation_response(result)

    def handle_img2img(self, body: Dict[str, Any]) -> Dict[str, Any]:
        payload = GenerationPayload(**body)
        if not payload.init_images:
            raise ApiError(422, "img2img requires init_images")
        self._apply_styles(payload)
        payload = self._expand_scripts(payload)
        with self._mint_request(payload, "/sdapi/v1/img2img"):
            if self.dispatcher is not None:
                result = self._submit_dispatch(payload, job="img2img")
                return self._generation_response(result)
            with self._busy:
                result = self._run_scripted(payload)
            return self._generation_response(result)

    def _run_scripted(self, payload: GenerationPayload) -> GenerationResult:
        """Dispatch through master-side multi-generation scripts (x/y/z
        plot runs one full — fleet-distributed — generation per cell)."""
        from stable_diffusion_webui_distributed_tpu.pipeline.xyz import (
            is_xyz,
            run_xyz,
        )

        if is_xyz(payload):
            try:
                return run_xyz(payload, self._execute,
                               known_samplers=list(SAMPLERS))
            except ValueError as e:
                raise ApiError(422, str(e))
        return self._execute(payload)

    def handle_options_get(self) -> Dict[str, Any]:
        return dict(self.options)

    def handle_options_post(self, body: Dict[str, Any]) -> Dict[str, Any]:
        model = body.get("sd_model_checkpoint")
        vae = body.get("sd_vae")
        if model:
            if self.registry is not None:
                # blocking load, like webui's POST /options (the reference
                # waits on it when syncing checkpoints, worker.py:646-688)
                self.registry.activate(model)
                # sd_vae is sticky across model loads (webui behavior):
                # re-apply the standing override to the fresh engine
                standing = vae if vae is not None else \
                    self.options.get("sd_vae")
                if standing and standing not in ("Automatic", "None") \
                        and hasattr(self.registry, "set_vae"):
                    self.registry.set_vae(standing)
            self.options["sd_model_checkpoint"] = model
        if vae is not None and model is None and self.registry is not None \
                and hasattr(self.registry, "set_vae"):
            self.registry.set_vae(vae)
        if (model or vae is not None) and hasattr(self.source, "sync_models"):
            # checkpoint/VAE-change fan-out to the fleet (world.py:784-811)
            sync_model = model or self.options.get("sd_model_checkpoint", "")
            sync_vae = vae if vae is not None else \
                self.options.get("sd_vae", "")
            if model:
                self.source.current_model = sync_model
            if hasattr(self.source, "current_vae") and vae is not None:
                # store the normalized wire form so the per-job dedupe in
                # Worker.load_options compares like with like
                self.source.current_vae = _vae_for_sync(sync_vae)
            if sync_model:
                self.source.sync_models(sync_model, _vae_for_sync(sync_vae))
        # runtime scheduler settings (the reference's Settings tab fields,
        # ui.py:26-55), accepted bare or with the webui-style
        # ``distributed_`` prefix and applied live to the World
        if hasattr(self.source, "apply_settings"):
            settings = {}
            for key in ("job_timeout", "complement_production",
                        "step_scaling", "thin_client_mode"):
                if key in body:
                    settings[key] = body[key]
                elif f"distributed_{key}" in body:
                    settings[key] = body[f"distributed_{key}"]
            if settings:
                self.source.apply_settings(settings)
        for k, v in body.items():
            if k != "sd_model_checkpoint":
                self.options[k] = v
        return {}

    def handle_progress(self) -> Dict[str, Any]:
        p = self.state.progress_snapshot()
        eta = p.eta_seconds()
        return {
            "progress": p.fraction,
            "eta_relative": eta if eta is not None else 0.0,
            "state": {
                "job": p.job,
                "sampling_step": p.sampling_step,
                "sampling_steps": p.sampling_steps,
                "interrupted": p.interrupted,
            },
            "current_image": None,
            "textinfo": None,
        }

    def handle_interrupt(self) -> Dict[str, Any]:
        self.state.flag.interrupt()
        if hasattr(self.source, "interrupt_all"):
            self.source.interrupt_all()
        return {}

    def handle_cancel(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """Per-request cancel (vs /interrupt's engine-wide latch): drops
        ONE coalesced requester's images at split time; co-batched
        requests are unaffected. Clients pass ``request_id`` in the
        generation payload to make their request addressable."""
        rid = str(body.get("request_id", "") or "")
        if not rid:
            raise ApiError(422, "request_id required")
        cancelled = (self.dispatcher is not None
                     and self.dispatcher.cancel(rid))
        return {"cancelled": cancelled}

    def handle_sd_models(self) -> Any:
        if self.registry is not None:
            return [
                {"title": name, "model_name": name,
                 "filename": path, "hash": None, "sha256": None}
                for name, path in self.registry.available().items()
            ]
        name = getattr(self.source, "model_name", "unknown")
        return [{"title": name, "model_name": name, "filename": "",
                 "hash": None, "sha256": None}]

    def handle_samplers(self) -> Any:
        return [{"name": n, "aliases": [], "options": {}} for n in SAMPLERS]

    def handle_embeddings(self) -> Dict[str, Any]:
        """webui's GET /sdapi/v1/embeddings shape: loaded textual-inversion
        embeddings with their vector counts (models/embeddings.py)."""
        loaded: Dict[str, Any] = {}
        skipped: Dict[str, Any] = {}
        store = getattr(self.registry, "embedding_store", None)
        if store is not None:
            for name in store.names():
                e = store.lookup(name)
                if e is None:  # unloadable file — webui lists it as skipped
                    skipped[name] = {}
                    continue
                loaded[name] = {
                    "step": None, "sd_checkpoint": None,
                    "sd_checkpoint_name": None,
                    "shape": int(e.clip_l.shape[1]),
                    "vectors": int(e.n_vectors),
                }
        return {"loaded": loaded, "skipped": skipped}

    def handle_script_info(self) -> Any:
        # advertised to masters that filter per-worker script args
        # (world.py:744-763): this node applies ControlNet units in-graph
        # and expands the selectable scripts natively (payload.apply_scripts)
        return [
            {"name": "controlnet", "is_alwayson": True, "is_img2img": True,
             "args": []},
            {"name": "prompt matrix", "is_alwayson": False,
             "is_img2img": False, "args": []},
            {"name": "prompts from file or textbox", "is_alwayson": False,
             "is_img2img": False, "args": []},
            {"name": "x/y/z plot", "is_alwayson": False,
             "is_img2img": True, "args": []},
        ]

    def handle_refresh(self) -> Dict[str, Any]:
        if self.registry is not None:
            self.registry.refresh()
        return {}

    def handle_server_restart(self) -> Dict[str, Any]:
        # the reference's /server-restart relaunches the webui process
        # (worker.py:690-717); here we flag the host process to re-exec
        self.restart_requested = True
        threading.Thread(target=self._shutdown_later, daemon=True).start()
        return {}

    def _shutdown_later(self):
        time.sleep(0.2)
        self.stop()

    # -- memory (real implementation) ---------------------------------------

    def _memory(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        try:
            with open("/proc/meminfo") as f:
                mem = {l.split(":")[0]: int(l.split()[1]) * 1024
                       for l in f if ":" in l}
            total = mem.get("MemTotal", 0)
            free = mem.get("MemAvailable", 0)
            out["ram"] = {"free": free, "used": total - free, "total": total}
        except OSError:
            out["ram"] = {}
        hbm_free = hbm_total = 0
        try:
            import jax

            devs = []
            for d in jax.devices():
                stats = {}
                try:
                    stats = d.memory_stats() or {}
                except Exception:  # noqa: BLE001
                    pass
                in_use = stats.get("bytes_in_use", 0)
                limit = stats.get("bytes_limit", 0)
                hbm_free += max(0, limit - in_use)
                hbm_total += limit
                devs.append({"id": d.id, "kind": d.device_kind,
                             "bytes_in_use": in_use, "bytes_limit": limit})
            out["tpu"] = {"devices": devs}
        except Exception:  # noqa: BLE001
            out["tpu"] = {"devices": []}
        # legacy shape the reference's VRAM probe reads (worker.py:322-340)
        out["cuda"] = {"system": {"free": hbm_free, "used":
                                  hbm_total - hbm_free, "total": hbm_total}}
        return out

    # -- HTTP plumbing -------------------------------------------------------

    def handle_internal_status(self) -> Dict[str, Any]:
        """Everything the status panel shows (reference Status tab data:
        worker lines at world.py:603-614, log ring at ui.py:72-88)."""
        from stable_diffusion_webui_distributed_tpu.obs import (
            flightrec, spans as obs_spans,
        )
        from stable_diffusion_webui_distributed_tpu.runtime import trace
        from stable_diffusion_webui_distributed_tpu.runtime.logging import (
            get_ring_buffer,
        )

        workers = []
        if hasattr(self.source, "workers"):
            for w in _fleet_workers(self.source):
                workers.append(_worker_dict(w))
        p = self.state.progress_snapshot()
        settings = None
        if hasattr(self.source, "job_timeout"):
            settings = {
                "job_timeout": self.source.job_timeout,
                "complement_production": getattr(
                    self.source, "complement_production", True),
                "step_scaling": getattr(self.source, "step_scaling", False),
                "thin_client_mode": getattr(
                    self.source, "thin_client_mode", False),
            }
        serving = None
        if self.dispatcher is not None:
            from stable_diffusion_webui_distributed_tpu.serving.metrics \
                import METRICS

            serving = METRICS.summary()
            serving["coalesce_window_s"] = self.dispatcher.window
            serving["bucket_ladder"] = [
                f"{w}x{h}" for w, h in self.dispatcher.bucketer.shapes]
            serving["batch_ladder"] = list(self.dispatcher.bucketer.batches)
            serving["eta_overhead"] = self.dispatcher.eta_overhead()
            serving["fleet"] = self.dispatcher.fleet_summary()
            if self.host_clock is not None:
                serving["host"] = self.host_clock.stats.summary()
                watcher = self.host_clock.watcher
                serving["device"]["watcher"] = {
                    "armed": obs_spans.TRACER.armed,
                    "alive": watcher.alive(), "stamped": watcher.stamped}
        obs = obs_spans.TRACER.summary()
        obs["flightrec_entries"] = len(flightrec.RECORDER)
        # warm pool (SDTPU_POOL, fleet/pool.py): resident table when one
        # is installed, a bare {"enabled": False} otherwise — so the
        # block is always present and schema-stable
        from stable_diffusion_webui_distributed_tpu.fleet import (
            pool as fleet_pool,
        )

        active_pool = fleet_pool.get_pool()
        pool_block = active_pool.summary() if active_pool is not None \
            else {"enabled": fleet_pool.enabled()}
        return {
            "model": self.options.get("sd_model_checkpoint", ""),
            "workers": workers,
            "settings": settings,
            "serving": serving,
            "pool": pool_block,
            "obs": obs,
            "progress": {
                "job": p.job,
                "sampling_step": p.sampling_step,
                "sampling_steps": p.sampling_steps,
                "fraction": p.fraction,
                "interrupted": p.interrupted,
            },
            "timings": trace.STATS.summary(),
            "logs": get_ring_buffer().dump(),
        }

    def handle_trace_json(self, query: Optional[Dict[str, str]] = None
                          ) -> Dict[str, Any]:
        """Chrome trace-event JSON of every retained request trace — save
        the body and load it in Perfetto / chrome://tracing (PERF.md).
        ``?device=1`` arms the device watcher (obs/watchdog.py: every
        dispatch from now on gets the exact moment its output was ready),
        ``?device=0`` disarms it."""
        from stable_diffusion_webui_distributed_tpu.obs import (
            spans as obs_spans,
        )

        if query and "device" in query:
            obs_spans.TRACER.armed = query["device"] not in ("", "0")
        doc = obs_spans.TRACER.export_chrome()
        if self.host_clock is not None:     # its ring, as ``host.stall``
            doc["traceEvents"].extend(self.host_clock.events())
        return doc

    def handle_stitched_trace(self) -> Dict[str, Any]:
        """Cross-node merged Chrome trace (obs/stitch.py): the master's
        spans plus every reachable remote's trace, clock-corrected from
        fetch RTT and retagged pid="worker:<label>"."""
        from stable_diffusion_webui_distributed_tpu.obs import stitch

        return stitch.stitch(self.source)

    def handle_journal_get(self, query: Dict[str, str]) -> Dict[str, Any]:
        """Request lifecycle journal (obs/journal.py; SDTPU_JOURNAL=1).
        ``?request_id=`` narrows to one request's event slice — the input
        to ``tools/replay.py``."""
        from stable_diffusion_webui_distributed_tpu.obs import journal

        return journal.JOURNAL.snapshot(query.get("request_id") or None)

    def handle_metrics(self) -> "TextResponse":
        """Prometheus text exposition: latency histograms (e2e / queue
        wait / device dispatch / decode), every DispatchMetrics and
        StageStats scalar, and the live ETA MPE gauge."""
        from stable_diffusion_webui_distributed_tpu.obs import prometheus

        return TextResponse(prometheus.render())

    def handle_flightrec(self) -> Dict[str, Any]:
        """The failure flight recorder: last N failed/interrupted/slow
        requests' span trees + correlated log lines."""
        from stable_diffusion_webui_distributed_tpu.obs import flightrec

        return flightrec.RECORDER.dump()

    def handle_profile(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """Start/stop a jax.profiler capture (runtime/trace.py). The client
        names the capture, not its location: traces always land under
        ./profile-traces/<basename> so a network client cannot write to
        arbitrary filesystem paths."""
        import os

        from stable_diffusion_webui_distributed_tpu.runtime import trace

        action = body.get("action", "")
        if action == "start":
            name = os.path.basename(str(body.get("dir", "trace")))
            if name in ("", ".", ".."):
                name = "trace"
            log_dir = os.path.join("profile-traces", name)
            ok = trace.start_trace(log_dir)
            return {"started": ok, "dir": log_dir}
        if action == "stop":
            return {"stopped_dir": trace.stop_trace()}
        raise ApiError(422, "action must be 'start' or 'stop'")

    def handle_profile_get(self, query: Dict[str, str]) -> Dict[str, Any]:
        """One-shot jax.profiler capture: ``GET /internal/profile?seconds=N``
        starts a trace, sleeps N seconds, stops it and returns the capture
        directory. Same basename jail as the POST start/stop surface."""
        import os
        import time as _time

        from stable_diffusion_webui_distributed_tpu.runtime import trace

        try:
            seconds = float(query.get("seconds", "1"))
        except ValueError:
            raise ApiError(422, "seconds must be a number")
        seconds = min(60.0, max(0.1, seconds))
        name = os.path.basename(str(query.get("dir", "trace")))
        if name in ("", ".", ".."):
            name = "trace"
        log_dir = os.path.join("profile-traces", name)
        if not trace.start_trace(log_dir):
            raise ApiError(409, "a profiler capture is already running")
        _time.sleep(seconds)
        return {"captured_dir": trace.stop_trace(), "seconds": seconds}

    def handle_perf(self) -> Dict[str, Any]:
        """Perf-ledger summary (obs/perf.py): per-(bucket, cadence,
        precision) padding-waste rows, compile latencies, and
        per-(tenant, class) SLO attainment. Empty until SDTPU_PERF=1."""
        from stable_diffusion_webui_distributed_tpu.obs import perf

        return perf.LEDGER.summary()

    def handle_cache(self) -> Dict[str, Any]:
        """Caching-tier summary (cache/): per-layer entries/bytes/hit
        rates for the embed, result-dedupe and prefix caches plus
        single-flight counters. ``{"enabled": False}`` until
        SDTPU_CACHE=1."""
        from stable_diffusion_webui_distributed_tpu import cache

        if not cache.enabled():
            return {"enabled": False}
        return cache.summary()

    def handle_sim(self) -> Dict[str, Any]:
        """Scenario-engine state (sim/): gate, journal sink spill status,
        armed chaos plan, and the last scored run. ``enabled`` is False
        until SDTPU_SIM=1 (the summary itself is always served)."""
        from stable_diffusion_webui_distributed_tpu import sim

        return sim.summary()

    def handle_tsdb(self) -> Dict[str, Any]:
        """Metric-history store (obs/tsdb.py): gate, sampling cadence,
        and the per-series ring contents. ``enabled`` is False until
        SDTPU_TSDB=1 (the summary itself is always served)."""
        from stable_diffusion_webui_distributed_tpu.obs import tsdb

        return tsdb.summary()

    def handle_alerts(self) -> Dict[str, Any]:
        """Alert-engine state (obs/alerts.py): the closed rule registry,
        per-rule pending/firing state, and the transition history."""
        from stable_diffusion_webui_distributed_tpu.obs import alerts

        return alerts.summary()

    def handle_fleet(self) -> Dict[str, Any]:
        """Fleet-federated metrics view (obs/federation.py): per-worker
        poll/staleness status and the latest fleet aggregates.
        ``enabled`` is False until SDTPU_FEDERATION=1 (the summary
        itself is always served)."""
        from stable_diffusion_webui_distributed_tpu.obs import federation

        return federation.summary()

    def handle_deltas(self, query: Dict[str, str]) -> Dict[str, Any]:
        """Push control plane worker feed (obs/push.py; SDTPU_PUSH=1):
        ``?cursor=N`` long-polls for journal events / TSDB samples /
        counter deltas after N. Answers 404 with the gate off — a
        push-preferring master reads that as "poll this node"."""
        from stable_diffusion_webui_distributed_tpu.obs import push

        if not push.enabled():
            raise ApiError(404, "push plane disabled (SDTPU_PUSH=0)")
        try:
            cursor = int(query.get("cursor", "0"))
        except ValueError:
            raise ApiError(422, "cursor must be an integer")
        try:
            hold = float(query.get("wait_s", str(push.wait_s())))
        except ValueError:
            raise ApiError(422, "wait_s must be a number")
        return push.serve_deltas(cursor, hold_s=min(5.0, max(0.0, hold)))

    def handle_push(self) -> Dict[str, Any]:
        """Push-plane status (obs/push.py): per-worker subscriber mode
        (push vs poll fallback), cursors, loss/duplicate accounting, and
        the worker-side buffer stats. Always served; ``enabled`` is
        False until SDTPU_PUSH=1. (/internal/fleet's key set is frozen
        by tests, so push status lives on its own endpoint.)"""
        from stable_diffusion_webui_distributed_tpu.obs import push

        return push.summary()

    def handle_fleet_timeline(self, query: Dict[str, str]
                              ) -> Dict[str, Any]:
        """Fleet-merged journal timeline (obs/fleetlog.py): the local
        journal + every push-streamed worker journal on one
        clock-corrected, causally-ordered axis. ``?request_id=``
        narrows to one request's cross-node story."""
        from stable_diffusion_webui_distributed_tpu.obs import fleetlog

        return fleetlog.timeline(query.get("request_id") or None)

    def handle_executables(self) -> Dict[str, Any]:
        """Live compiled-executable census against the serving budget of
        <=2 step-cache x <=3 precision variants per shape bucket; the
        ``alarm`` flag trips when any bucket exceeds it."""
        from stable_diffusion_webui_distributed_tpu.obs import perf

        engine = getattr(self.dispatcher, "engine", None) \
            if self.dispatcher is not None else None
        if engine is None or not hasattr(engine, "executable_keys"):
            return {"available": False}
        census = perf.executables_census(engine)
        census["available"] = True
        return census

    def handle_autoscale(self) -> Dict[str, Any]:
        """Autoscale decision audit (fleet/slices.py): the bounded ring of
        every scale decision with wall-clock timestamps."""
        from stable_diffusion_webui_distributed_tpu.fleet import slices

        engine = slices.get_autoscale()
        if engine is None:
            return {"active": False}
        return engine.audit()

    def handle_reset_mpe(self) -> Dict[str, Any]:
        """Clear every worker's ETA error history (the reference's
        debug-mode 'reset mpe' button, ui.py:282-287)."""
        cleared = []
        if hasattr(self.source, "workers"):
            # under _busy: save_config must not interleave with the
            # end-of-generation save (both write the same .tmp file)
            with self._busy:
                for w in _fleet_workers(self.source):
                    if w.cal.eta_percent_error:
                        w.cal.eta_percent_error.clear()
                        cleared.append(w.label)
                if hasattr(self.source, "save_config"):
                    self.source.save_config()
        return {"cleared": cleared}

    def handle_user_script(self) -> Dict[str, Any]:
        """Run the operator's ``sync*`` script (reference user_script_btn,
        ui.py:26-55) — e.g. an rsync-models-to-workers hook placed under
        ``<config dir>/user/``."""
        if not hasattr(self.source, "run_user_script"):
            raise ApiError(400, "no fleet attached to this node")
        return {"ran": self.source.run_user_script()}

    def handle_restart_all(self) -> Dict[str, Any]:
        """Fleet restart fan-out (the reference's 'Restart All Workers'
        button, ui.py:274-280 + javascript/distributed.js:2-4 — its confirm
        dialog lives client-side; API callers are their own confirmation)."""
        if not hasattr(self.source, "restart_all"):
            raise ApiError(400, "no fleet attached to this node")
        return {"restarted": self.source.restart_all()}

    def handle_workers_get(self) -> Any:
        """Worker-config read surface (reference Worker Config tab,
        ui.py:90-214)."""
        if not hasattr(self.source, "workers"):
            return []
        return [_worker_dict(w) for w in _fleet_workers(self.source)]

    def handle_workers_post(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """Worker CRUD (reference Worker Config tab, ui.py:90-214):
        ``action`` = "update" (default — model_override/pixel_cap/disabled),
        "add" (label+address+port join the fleet live), or "remove"."""
        if not hasattr(self.source, "configure_worker"):
            raise ApiError(400, "no fleet attached to this node")
        label = body.get("label", "")
        if not label:
            raise ApiError(422, "label required")
        action = body.get("action", "update")
        if action == "add":
            try:
                with self._busy:
                    self.source.add_remote_worker(
                        label, body.get("address", ""),
                        int(body.get("port", 7860)),
                        tls=bool(body.get("tls", False)),
                        user=body.get("user") or None,
                        password=body.get("password") or None,
                        pixel_cap=int(body.get("pixel_cap", 0)))
            except (ValueError, TypeError) as e:
                # TypeError: JSON null / non-scalar port etc. — same
                # malformed-field class as ValueError, so same 422
                raise ApiError(422, str(e))
            return {"added": label}
        if action == "remove":
            try:
                with self._busy:
                    ok = self.source.remove_worker(label)
            except ValueError as e:
                raise ApiError(422, str(e))
            if not ok:
                raise ApiError(404, f"no worker '{label}'")
            return {"removed": label}
        if action != "update":
            raise ApiError(422, f"unknown action '{action}'")
        # in-place endpoint edit (reference save_worker_btn, ui.py:100-159)
        endpoint = {k: body[k] for k in
                    ("address", "port", "tls", "user", "password")
                    if k in body}
        kwargs = {}
        for key in ("model_override", "pixel_cap", "disabled"):
            if key in body:
                kwargs[key] = body[key]
        # validation BEFORE any mutation so a 422 cannot leave the edit
        # half-applied (a changed endpoint with a rejected pin); with
        # endpoint fields in flight, validate against the CANDIDATE
        # endpoint — that is where the pinned model must exist
        pin_validated = None
        if kwargs.get("model_override"):
            pin_validated = self._validate_model_pin(
                label, kwargs["model_override"], endpoint or None)
        if endpoint and not hasattr(self.source, "update_worker_endpoint"):
            # never pretend the edit applied: echoing unapplied endpoint
            # fields in a 200 would hide the dropped change (this source —
            # e.g. a bare registry in tests — has no endpoint support)
            raise ApiError(
                422, "this server's worker source does not support "
                f"endpoint edits (fields: {', '.join(sorted(endpoint))})")
        if endpoint:
            try:
                with self._busy:
                    ok = self.source.update_worker_endpoint(label, **endpoint)
            except (ValueError, TypeError) as e:
                raise ApiError(422, str(e))
            if not ok:
                raise ApiError(404, f"no worker '{label}'")
        if kwargs or not endpoint:
            with self._busy:
                ok = self.source.configure_worker(label, **kwargs)
            if not ok:
                raise ApiError(404, f"no worker '{label}'")
        if pin_validated is not None:
            # promote the provenance configure_worker reset to False:
            # True only when the node's model list positively contained
            # the pin (unreachable nodes stay False — visible in the
            # panel until ping_workers re-validates; VERDICT r4 item 6)
            cand = self._find_worker(label)
            if cand is not None and cand.model_override:
                cand.pin_validated = pin_validated
        # password is write-only everywhere (_worker_dict): never echo it
        endpoint.pop("password", None)
        return {"updated": label, **endpoint, **kwargs}

    def _find_worker(self, label: str):
        """The single worker-by-label lookup (sources without a registry —
        e.g. a bare Engine — simply have no ``workers`` attribute)."""
        for w in getattr(self.source, "workers", []):
            if w.label == label:
                return w
        return None

    def _validate_model_pin(self, label: str, pin: str,
                            endpoint: Optional[Dict[str, Any]] = None) -> bool:
        """Reject a checkpoint pin the worker does not actually serve (the
        reference feeds its override dropdown from the remote's /sd-models,
        ui.py:161-171 + worker.py:623-645 — free text would only fail at
        the next load_options). ``endpoint``: pending endpoint-field edits;
        the probe then targets the merged candidate endpoint instead of the
        current backend. An unreachable worker or an empty model list skips
        validation: better to accept the pin than to block config on a node
        that is momentarily down — but the skip is RECORDED: returns True
        only on a positive match, False when validation was skipped, so the
        caller can flag the pin as unvalidated (VERDICT r4 item 6) and
        ping_workers can re-check it later."""
        w = self._find_worker(label)
        if w is None:
            return False
        backend, transient = w.backend, None
        if endpoint and hasattr(self.source, "candidate_backend"):
            try:
                # the World owns the field-merge (same one the edit itself
                # applies), so validation probes exactly the endpoint that
                # would be saved
                transient = self.source.candidate_backend(label, **endpoint)
            except (ValueError, TypeError):
                return False  # malformed fields fail in update_worker_endpoint
            if transient is not None:
                backend = transient
        try:
            models = backend.available_models()
        except Exception:  # noqa: BLE001 — node down; accept unvalidated
            get_logger().warning(
                "worker '%s' unreachable; accepting pin '%s' UNVALIDATED",
                label, pin)
            return False
        finally:
            if transient is not None:
                transient.close()
        if models and pin not in models:
            raise ApiError(
                422, f"worker '{label}' does not serve model '{pin}' "
                f"(available: {', '.join(models[:20])})")
        return bool(models)

    def handle_worker_models(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """Model list of ONE worker's backend — feeds the panel's checkpoint
        pin dropdown (the reference populates its override dropdown from
        the remote's /sd-models the same way, ui.py:161-171)."""
        label = body.get("label", "")
        w = self._find_worker(label)
        if w is None:
            raise ApiError(404, f"no worker '{label}'")
        try:
            return {"label": label, "models": w.backend.available_models()}
        except Exception as e:  # noqa: BLE001 — node down
            return {"label": label, "models": [], "error": str(e)}

    def handle_benchmark(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """Kick a fleet benchmark sweep in the background (the reference's
        "Redo benchmark" debug button, ui.py:282-287 area). Returns
        immediately; progress is visible as worker speeds update."""
        if not hasattr(self.source, "benchmark_all"):
            raise ApiError(400, "no fleet attached to this node")
        # non-blocking acquire, released by the worker thread: a locked()
        # pre-check would race a double-click into two full sweeps
        if not self._benchmarking.acquire(blocking=False):
            return {"started": False, "reason": "benchmark already running"}

        def run():
            try:
                self.source.benchmark_all(
                    rebenchmark=bool(body.get("rebenchmark", True)))
            except Exception as e:  # noqa: BLE001
                get_logger().error("benchmark sweep failed: %s", e)
            finally:
                self._benchmarking.release()

        threading.Thread(target=run, daemon=True,
                         name="benchmark-sweep").start()
        return {"started": True}

    def handle_panel(self) -> str:
        from stable_diffusion_webui_distributed_tpu.server.panel import (
            PANEL_HTML,
        )

        return PANEL_HTML

    def routes(self) -> Dict[Tuple[str, str], Callable]:
        return {
            # _dispatch rstrips trailing slashes, so "/" arrives as ""
            ("GET", ""): self.handle_panel,
            ("GET", "/internal/status"): self.handle_internal_status,
            ("GET", "/internal/trace.json"): self.handle_trace_json,
            ("GET", "/internal/stitched-trace.json"):
                self.handle_stitched_trace,
            ("GET", "/internal/journal"): self.handle_journal_get,
            ("GET", "/internal/metrics"): self.handle_metrics,
            ("GET", "/internal/flightrec"): self.handle_flightrec,
            ("GET", "/internal/perf"): self.handle_perf,
            ("GET", "/internal/cache"): self.handle_cache,
            ("GET", "/internal/sim"): self.handle_sim,
            ("GET", "/internal/tsdb"): self.handle_tsdb,
            ("GET", "/internal/alerts"): self.handle_alerts,
            ("GET", "/internal/fleet"): self.handle_fleet,
            ("GET", "/internal/fleet/timeline"): self.handle_fleet_timeline,
            ("GET", "/internal/deltas"): self.handle_deltas,
            ("GET", "/internal/push"): self.handle_push,
            ("GET", "/internal/executables"): self.handle_executables,
            ("GET", "/internal/autoscale"): self.handle_autoscale,
            ("GET", "/internal/profile"): self.handle_profile_get,
            ("POST", "/internal/profile"): self.handle_profile,
            ("POST", "/internal/reset-mpe"): self.handle_reset_mpe,
            ("POST", "/internal/restart-all"): self.handle_restart_all,
            ("POST", "/internal/user-script"): self.handle_user_script,
            ("POST", "/internal/benchmark"): self.handle_benchmark,
            ("GET", "/internal/workers"): self.handle_workers_get,
            ("POST", "/internal/workers"): self.handle_workers_post,
            ("POST", "/internal/worker-models"): self.handle_worker_models,
            ("POST", "/sdapi/v1/txt2img"): self.handle_txt2img,
            ("POST", "/sdapi/v1/img2img"): self.handle_img2img,
            ("GET", "/sdapi/v1/options"): self.handle_options_get,
            ("POST", "/sdapi/v1/options"): self.handle_options_post,
            ("GET", "/sdapi/v1/progress"): self.handle_progress,
            ("POST", "/sdapi/v1/interrupt"): self.handle_interrupt,
            ("POST", "/internal/cancel"): self.handle_cancel,
            ("GET", "/sdapi/v1/memory"): self._memory,
            ("GET", "/sdapi/v1/sd-models"): self.handle_sd_models,
            ("GET", "/sdapi/v1/embeddings"): self.handle_embeddings,
            ("GET", "/sdapi/v1/samplers"): self.handle_samplers,
            ("GET", "/sdapi/v1/script-info"): self.handle_script_info,
            ("POST", "/sdapi/v1/refresh-checkpoints"): self.handle_refresh,
            ("POST", "/sdapi/v1/refresh-loras"): self.handle_refresh,
            ("POST", "/sdapi/v1/server-restart"): self.handle_server_restart,
        }

    def make_handler(self):
        from stable_diffusion_webui_distributed_tpu.obs import (
            spans as obs_spans,
        )

        server = self
        routes = self.routes()
        log = get_logger()

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # route to our logger
                log.debug("http: " + fmt, *args)

            def _check_auth(self) -> bool:
                if server._auth is None:
                    return True
                if self.headers.get("Authorization") == server._auth:
                    return True
                self.send_response(401)
                self.send_header("WWW-Authenticate", "Basic")
                self.send_header("Content-Length", "0")
                self.end_headers()
                return False

            def setup(self):
                # this thread's first act: http.accept's annotation begins;
                # the exchange only once finish() is sure to end it
                stamps = self.server.accepted
                stamp = stamps.pop(self.request, None) if stamps else None
                self._accept = stamp and obs_spans.Accept(stamp)
                super().setup()
                self._between = stamp and self.server.host.exchange_began(
                    stamp)

            def finish(self):
                super().finish()
                if self._accept is not None:    # no request ever came
                    self._accept.close()
                    self.server.host.exchange_ended(time.perf_counter())

            def _dispatch(self, method: str):
                # what of a request's exchange lies outside its root span
                host = self.server.host
                if host is None:        # spans are off
                    return self._route(method, None)
                accept, self._accept = self._accept, None
                between = self._between if accept is not None else \
                    host.exchange_began(time.perf_counter())    # a kept one's
                try:
                    with obs_spans.http_exchange(accept, between) as exchange:
                        if accept is None and exchange is not None:
                            exchange.attrs["reused"] = True
                        self._route(method, exchange)
                finally:
                    host.exchange_ended(time.perf_counter())

            def _route(self, method: str, exchange):
                if not self._check_auth():
                    return
                key = (method, self.path.split("?")[0].rstrip("/"))
                fn = routes.get(key)
                if fn is None:
                    self._send(404, {"detail": "Not Found"})
                    return
                try:
                    if method == "POST":
                        length = int(self.headers.get("Content-Length", 0))
                        raw = self.rfile.read(length) if length else b"{}"
                        if exchange is not None:
                            exchange.attrs["bytes"] = length
                        body = json.loads(raw or b"{}")
                        if key[1] in ("/sdapi/v1/txt2img",
                                      "/sdapi/v1/img2img") \
                                and isinstance(body, dict) \
                                and not body.get("request_id"):
                            # cross-node trace join: a master's scheduler
                            # stamps the request id on the outbound hop
                            # (HTTPBackend.generate) so this worker roots
                            # its trace under the same id
                            rid_hdr = self.headers.get("X-SDTPU-Request-Id")
                            if rid_hdr:
                                body["request_id"] = rid_hdr
                        result = fn(body) if fn.__code__.co_argcount > 1 \
                            else fn()
                    elif fn.__code__.co_argcount > 1:
                        # GET handlers that declare a parameter receive the
                        # query string as a flat single-value dict
                        from urllib.parse import parse_qs

                        query = {k: v[-1] for k, v in parse_qs(
                            self.path.partition("?")[2]).items()}
                        result = fn(query)
                    else:
                        result = fn()
                    if isinstance(result, TextResponse):
                        self._send_text(200, result)
                    elif isinstance(result, str):
                        self._send_html(200, result)
                    else:
                        self._send(200, result if result is not None else {})
                except ApiError as e:
                    self._send(e.status, {"detail": e.detail},
                               headers=e.headers)
                except Exception as e:  # noqa: BLE001
                    log.error("api error on %s %s: %s", method, self.path, e)
                    self._send(500, {"detail": str(e)})

            def _send(self, status: int, obj: Any,
                      headers: Optional[Dict[str, str]] = None):
                with obs_spans.http_respond() as sp:
                    with obs_spans.span("respond.serialize") as part:
                        data, copied = json_body(obj)
                        if part is not None:
                            part.attrs.update(bytes=len(data),
                                              images_copied=copied)
                    if sp is not None:
                        sp.attrs.update(bytes=len(data), status=status)
                    with obs_spans.span("respond.write", bytes=len(data)):
                        self.send_response(status)
                        self.send_header("Content-Type", "application/json")
                        self.send_header("Content-Length", str(len(data)))
                        for k, v in (headers or {}).items():
                            self.send_header(k, v)
                        self.end_headers()
                        self.wfile.write(data)

            def _send_html(self, status: int, text: str):
                data = text.encode()
                self.send_response(status)
                self.send_header("Content-Type", "text/html; charset=utf-8")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _send_text(self, status: int, text: "TextResponse"):
                data = str(text).encode()
                self.send_response(status)
                self.send_header("Content-Type", text.content_type)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                self._dispatch("GET")

            def do_POST(self):
                self._dispatch("POST")

        return Handler

    def _bind(self) -> ThreadingHTTPServer:
        """Bound; with spans on it stamps accepts and the host clock runs."""
        from stable_diffusion_webui_distributed_tpu.obs import spans, watchdog

        httpd = _StampingServer((self.host, self.port), self.make_handler())
        self.port = httpd.server_port  # resolves port 0
        if spans.TRACER.enabled:
            self.host_clock = watchdog.HostClock().start()
            httpd.accepted, httpd.host = {}, self.host_clock.stats
        return httpd

    def start(self) -> "ApiServer":
        """Serve in a daemon thread; returns self when the port is bound."""
        self._httpd = self._bind()
        t = threading.Thread(target=self._httpd.serve_forever,
                             name="sdapi-server", daemon=True)
        t.start()
        get_logger().info("sdapi server on %s:%d", self.host, self.port)
        return self

    def serve_forever(self) -> None:
        """Blocking serve with SIGINT/SIGTERM cleanup (the reference chains
        handlers that save config before exiting, distributed.py:359-375)."""
        self._httpd = self._bind()
        previous = {}

        def on_signal(signum, frame):
            get_logger().info("signal %d: saving config and shutting down",
                              signum)
            if hasattr(self.source, "save_config"):
                try:
                    self.source.save_config()
                except Exception:  # noqa: BLE001
                    pass
            threading.Thread(target=self._httpd.shutdown,
                             daemon=True).start()
            prev = previous.get(signum)
            if callable(prev):
                prev(signum, frame)

        for sig in (signal.SIGINT, signal.SIGTERM):
            previous[sig] = signal.getsignal(sig)
            signal.signal(sig, on_signal)
        get_logger().info("sdapi server on %s:%d", self.host, self.port)
        self._httpd.serve_forever()

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self.host_clock is not None:
            self.host_clock.stop()
            self.host_clock = None


class ApiError(Exception):
    def __init__(self, status: int, detail: str,
                 headers: Optional[Dict[str, str]] = None):
        super().__init__(detail)
        self.status = status
        self.detail = detail
        self.headers = headers or {}


def _fleet_workers(source) -> list:
    """Point-in-time worker list: the World's locked snapshot when it has
    one (HTTP add/remove mutates the registry concurrently with these
    handlers), else a plain copy for bare test doubles."""
    snap = getattr(source, "workers_snapshot", None)
    if callable(snap):
        return snap()
    return list(getattr(source, "workers", []))


def _worker_dict(w) -> Dict[str, Any]:
    """One worker's control-surface row: state/speed plus the editable
    fields the panel prefills (endpoint fields only for HTTP remotes;
    password is write-only and never serialized back out)."""
    state = w.current_state() if hasattr(w, "current_state") else w.state
    d = {
        "label": w.label,
        "state": state.name,
        "avg_ipm": w.cal.avg_ipm,
        "master": w.master,
        "pixel_cap": w.pixel_cap,
        "model_override": w.model_override,
        "pin_validated": w.pin_validated,
        "disabled": state.name == "DISABLED",
    }
    health = getattr(w, "health", None)
    if health is not None and hasattr(health, "summary"):
        # rolling error rate / latency EWMA / transition timeline
        # (scheduler/worker.py WorkerHealth) — guarded for bare doubles
        d["health"] = health.summary()
    backend = w.backend
    if hasattr(backend, "address"):
        d["address"] = backend.address
        d["port"] = backend.port
        d["tls"] = getattr(backend, "tls", False)
        d["user"] = getattr(backend, "user", None) or ""
    return d


def _vae_for_sync(vae: str) -> str:
    """'Automatic'/'None' mean "checkpoint default" — send empty on the wire."""
    return "" if vae in ("Automatic", "None") else (vae or "")


def _make_grid_b64(images_b64) -> Optional[str]:
    """Assemble a near-square grid of equally sized images (webui
    image_grid semantics; reference world.py:588-591)."""
    import math

    import numpy as np

    from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
        array_to_b64png, b64png_to_array,
    )

    try:
        arrays = [b64png_to_array(b) for b in images_b64]
        h, w, c = arrays[0].shape
        if any(a.shape != (h, w, c) for a in arrays):
            return None
        n = len(arrays)
        cols = math.ceil(math.sqrt(n))
        rows = math.ceil(n / cols)
        grid = np.zeros((rows * h, cols * w, c), arrays[0].dtype)
        for i, a in enumerate(arrays):
            r, col = divmod(i, cols)
            grid[r * h:(r + 1) * h, col * w:(col + 1) * w] = a
        return array_to_b64png(grid)
    except Exception:  # noqa: BLE001 — a grid is decorative, never fatal
        return None

"""sdapi-v1 server tests: every route the reference consumes
(/root/reference/scripts/spartan/worker.py:192-203), driven over real HTTP
against a stub world, plus auth and the HTTPBackend client closing the loop
(this framework's own World driving this framework's own server)."""

import json
import urllib.error
import urllib.request

import pytest

from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
)
from stable_diffusion_webui_distributed_tpu.runtime.config import ConfigModel
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.scheduler.worker import (
    HTTPBackend, StubBackend, WorkerNode,
)
from stable_diffusion_webui_distributed_tpu.scheduler.world import World
from stable_diffusion_webui_distributed_tpu.server.api import ApiServer


def make_world():
    w = World(ConfigModel())
    w.add_worker(WorkerNode("m", StubBackend(), master=True, avg_ipm=10.0))
    return w


@pytest.fixture(scope="module")
def server():
    state = GenerationState()
    srv = ApiServer(make_world(), state=state, host="127.0.0.1", port=0)
    srv.start()
    yield srv
    srv.stop()


def call(server, route, body=None, method=None, headers=None):
    url = f"http://127.0.0.1:{server.port}{route}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method or ("POST" if data else "GET"),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read() or b"{}")


class TestRoutes:
    def test_txt2img(self, server):
        out = call(server, "/sdapi/v1/txt2img",
                   {"prompt": "cow", "batch_size": 2, "seed": 50,
                    "steps": 4, "width": 64, "height": 64})
        assert len(out["images"]) == 2
        info = json.loads(out["info"])
        assert info["all_seeds"] == [50, 51]
        assert info["seed"] == 50

    def test_img2img_requires_init(self, server):
        with pytest.raises(urllib.error.HTTPError) as e:
            call(server, "/sdapi/v1/img2img", {"prompt": "x"})
        assert e.value.code == 422

    def test_prompt_matrix_over_cap_is_422(self, server):
        # 11 options -> over the 2^10 combination cap: client error, not
        # a 500 from deep inside the engine
        with pytest.raises(urllib.error.HTTPError) as e:
            call(server, "/sdapi/v1/txt2img",
                 {"prompt": "base|" + "|".join(f"o{i}" for i in range(11)),
                  "script_name": "prompt matrix", "steps": 1,
                  "width": 64, "height": 64})
        assert e.value.code == 422

    def test_progress(self, server):
        out = call(server, "/sdapi/v1/progress")
        assert {"progress", "eta_relative", "state"} <= set(out)

    def test_interrupt(self, server):
        call(server, "/sdapi/v1/interrupt", {})
        assert server.state.flag.interrupted
        server.state.flag.clear()

    def test_memory_shapes(self, server):
        out = call(server, "/sdapi/v1/memory")
        assert "ram" in out and "tpu" in out
        # legacy probe shape the reference reads (worker.py:322-340)
        assert "free" in out["cuda"]["system"]

    def test_sd_models_and_samplers(self, server):
        models = call(server, "/sdapi/v1/sd-models")
        assert isinstance(models, list) and models
        samplers = call(server, "/sdapi/v1/samplers")
        names = {s["name"] for s in samplers}
        assert {"Euler a", "DPM++ 2M Karras"} <= names

    def test_script_info_advertises_controlnet(self, server):
        info = call(server, "/sdapi/v1/script-info")
        assert any(s["name"] == "controlnet" for s in info)

    def test_options_roundtrip(self, server):
        call(server, "/sdapi/v1/options", {"CLIP_stop_at_last_layers": 2})
        out = call(server, "/sdapi/v1/options")
        assert out["CLIP_stop_at_last_layers"] == 2

    def test_404(self, server):
        with pytest.raises(urllib.error.HTTPError) as e:
            call(server, "/sdapi/v1/nope")
        assert e.value.code == 404

    def test_workers_control_surface(self, server):
        world = server.source
        extra = WorkerNode("r1", StubBackend(), avg_ipm=5.0)
        world.add_worker(extra)
        try:
            # read surface (reference Worker Config tab, ui.py:90-214)
            rows = call(server, "/internal/workers")
            by_label = {r["label"]: r for r in rows}
            assert by_label["r1"]["model_override"] is None
            # write surface: pin + cap round-trip (the pin is validated
            # against the worker's actual model list, ui.py:161-171)
            out = call(server, "/internal/workers",
                       {"label": "r1", "model_override": "stub-model",
                        "pixel_cap": 123456})
            assert out["updated"] == "r1"
            assert extra.model_override == "stub-model"
            assert extra.pixel_cap == 123456
            rows = call(server, "/internal/workers")
            by_label = {r["label"]: r for r in rows}
            assert by_label["r1"]["model_override"] == "stub-model"
            # a pin the worker does not serve -> 422, nothing changed
            with pytest.raises(urllib.error.HTTPError) as e:
                call(server, "/internal/workers",
                     {"label": "r1", "model_override": "typo-model"})
            assert e.value.code == 422
            assert extra.model_override == "stub-model"
            # unknown label -> 404
            with pytest.raises(urllib.error.HTTPError) as e:
                call(server, "/internal/workers", {"label": "ghost",
                                                   "pixel_cap": 1})
            assert e.value.code == 404
        finally:
            world.workers.remove(extra)

    def test_worker_models_route(self, server):
        world = server.source
        extra = WorkerNode("rm", StubBackend(), avg_ipm=5.0)
        world.add_worker(extra)
        try:
            out = call(server, "/internal/worker-models", {"label": "rm"})
            assert out["models"] == ["stub-model"]
            with pytest.raises(urllib.error.HTTPError) as e:
                call(server, "/internal/worker-models", {"label": "ghost"})
            assert e.value.code == 404
        finally:
            world.workers.remove(extra)

    def test_worker_endpoint_edit_route(self, server):
        """In-place address/port/credential edit (reference save_worker_btn,
        ui.py:100-159) through POST /internal/workers."""
        world = server.source
        out = call(server, "/internal/workers",
                   {"action": "add", "label": "ed", "address": "h1",
                    "port": 7861, "user": "u1", "password": "p1"})
        assert out["added"] == "ed"
        try:
            w = world.get_worker("ed")
            out = call(server, "/internal/workers",
                       {"label": "ed", "address": "h2", "port": 7999,
                        "tls": True, "user": "u2"})
            assert out["updated"] == "ed"
            assert w.backend.address == "h2"
            assert w.backend.port == 7999
            assert w.backend.tls is True
            assert w.backend.user == "u2"
            assert w.backend.password == "p1"  # omitted field is kept
            # cached sync state forgotten: new endpoint = new process
            assert w.loaded_model is None and w.supported_scripts is None
            # editing the master's endpoint -> 422
            with pytest.raises(urllib.error.HTTPError) as e:
                call(server, "/internal/workers",
                     {"label": "m", "address": "h3"})
            assert e.value.code == 422
        finally:
            call(server, "/internal/workers",
                 {"action": "remove", "label": "ed"})

    def test_embeddings_route_tolerates_broken_file(self, tmp_path):
        from safetensors.numpy import save_file
        import numpy as np
        import types

        from stable_diffusion_webui_distributed_tpu.models.embeddings import (
            EmbeddingStore,
        )

        save_file({"emb_params": np.ones((2, 8), np.float32)},
                  str(tmp_path / "good.safetensors"))
        (tmp_path / "broken.safetensors").write_bytes(b"junk")
        registry = types.SimpleNamespace(
            embedding_store=EmbeddingStore(str(tmp_path)))
        srv = ApiServer(make_world(), registry=registry,
                        host="127.0.0.1", port=0)
        srv.start()
        try:
            out = call(srv, "/sdapi/v1/embeddings")
        finally:
            srv.stop()
        assert out["loaded"]["good"]["vectors"] == 2
        assert "broken" in out["skipped"]  # unloadable must not 500

    def test_workers_add_remove_routes(self, server):
        world = server.source
        out = call(server, "/internal/workers",
                   {"action": "add", "label": "new-r", "address": "h1",
                    "port": 7861})
        assert out["added"] == "new-r"
        assert world.get_worker("new-r") is not None
        try:
            # duplicate add -> 422
            with pytest.raises(urllib.error.HTTPError) as e:
                call(server, "/internal/workers",
                     {"action": "add", "label": "new-r", "address": "h1",
                      "port": 7861})
            assert e.value.code == 422
        finally:
            out = call(server, "/internal/workers",
                       {"action": "remove", "label": "new-r"})
        assert out["removed"] == "new-r"
        assert world.get_worker("new-r") is None
        with pytest.raises(urllib.error.HTTPError) as e:
            call(server, "/internal/workers",
                 {"action": "remove", "label": "new-r"})
        assert e.value.code == 404

    def test_restart_all_route(self, server):
        world = server.source
        extra = WorkerNode("r2", StubBackend(), avg_ipm=5.0)
        world.add_worker(extra)
        try:
            out = call(server, "/internal/restart-all", {})
            assert out["restarted"] == {"r2": True}
            assert extra.backend.restarted
        finally:
            world.workers.remove(extra)

    def test_benchmark_route_sweeps_fleet(self, server):
        import time

        world = server.source
        fresh = WorkerNode("r3", StubBackend())  # no calibration yet
        world.add_worker(fresh)
        try:
            out = call(server, "/internal/benchmark", {"rebenchmark": False})
            assert out["started"] is True
            deadline = time.monotonic() + 20
            while fresh.cal.avg_ipm is None and time.monotonic() < deadline:
                time.sleep(0.05)
            assert fresh.cal.avg_ipm and fresh.cal.avg_ipm > 0
        finally:
            world.workers.remove(fresh)

    def test_status_reports_settings(self, server):
        out = call(server, "/internal/status")
        s = out["settings"]
        assert {"job_timeout", "complement_production", "step_scaling",
                "thin_client_mode"} <= set(s)

    def test_options_apply_scheduler_settings(self, server):
        world = server.source
        old = world.job_timeout
        try:
            call(server, "/sdapi/v1/options",
                 {"distributed_job_timeout": 11, "step_scaling": True})
            assert world.job_timeout == 11.0
            assert world.step_scaling is True
        finally:
            world.job_timeout = old
            world.step_scaling = False

    def test_status_panel_html(self, server):
        url = f"http://127.0.0.1:{server.port}/"
        with urllib.request.urlopen(url, timeout=10) as r:
            assert "text/html" in r.headers["Content-Type"]
            body = r.read().decode()
        assert "sdtpu" in body and "/internal/status" in body
        # pin UX (VERDICT r4 items 6/7): datalist-fed pin input + the
        # unvalidated-pin warning marker wired into the worker table
        assert 'list="ew_pin_models"' in body
        assert 'datalist id="ew_pin_models"' in body
        assert "pin_validated" in body

    def test_internal_status(self, server):
        out = call(server, "/internal/status")
        assert {"model", "workers", "progress", "timings", "logs"} <= set(out)
        labels = {w["label"] for w in out["workers"]}
        assert "m" in labels

    def test_stage_timings_recorded(self, server):
        from stable_diffusion_webui_distributed_tpu.runtime import trace

        trace.STATS.record("unit-test-stage", 0.25)
        out = call(server, "/internal/status")
        assert out["timings"]["unit-test-stage"]["count"] >= 1

    def test_reset_mpe(self, server):
        w = server.source.workers[0]
        w.cal.eta_percent_error.extend([5.0, -3.0])
        out = call(server, "/internal/reset-mpe", {})
        assert out["cleared"] == ["m"]
        assert w.cal.eta_percent_error == []

    def test_profile_endpoint_validates(self, server):
        with pytest.raises(urllib.error.HTTPError) as e:
            call(server, "/internal/profile", {"action": "bogus"})
        assert e.value.code == 422


class TestStylesAndGrid:
    def test_styles_applied(self, tmp_path):
        from stable_diffusion_webui_distributed_tpu.pipeline.styles import (
            apply_styles, load_styles,
        )
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            GenerationPayload,
        )

        csv_path = tmp_path / "styles.csv"
        csv_path.write_text(
            "name,prompt,negative_prompt\n"
            "anime,\"{prompt}, anime style\",\"ugly\"\n"
            "suffix-only,\"best quality\",\"\"\n")
        styles = load_styles(str(csv_path))
        p = GenerationPayload(prompt="a cow", styles=["anime", "suffix-only",
                                                      "missing"])
        apply_styles(p, styles)
        assert p.prompt == "a cow, anime style, best quality"
        assert p.negative_prompt == "ugly"
        assert p.styles == []

    def test_return_grid_option(self, server):
        call(server, "/sdapi/v1/options", {"return_grid": True})
        try:
            out = call(server, "/sdapi/v1/txt2img",
                       {"prompt": "g", "batch_size": 3, "seed": 5,
                        "steps": 2, "width": 64, "height": 64})
            # stub images aren't decodable PNGs -> grid skipped gracefully
            assert len(out["images"]) == 3
        finally:
            call(server, "/sdapi/v1/options", {"return_grid": False})

    def test_make_grid(self):
        import numpy as np

        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            array_to_b64png, b64png_to_array,
        )
        from stable_diffusion_webui_distributed_tpu.server.api import (
            _make_grid_b64,
        )

        imgs = [array_to_b64png(np.full((8, 8, 3), i * 40, np.uint8))
                for i in range(3)]
        grid = b64png_to_array(_make_grid_b64(imgs))
        assert grid.shape == (16, 16, 3)  # 2x2 grid with one empty cell
        assert grid[0, 0, 0] == 0 and grid[0, 8, 0] == 40
        assert grid[8, 0, 0] == 80 and grid[8, 8, 0] == 0


class TestAuth:
    def test_basic_auth(self):
        srv = ApiServer(make_world(), host="127.0.0.1", port=0,
                        user="u", password="p")
        srv.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                call(srv, "/sdapi/v1/progress")
            assert e.value.code == 401
            import base64

            tok = base64.b64encode(b"u:p").decode()
            out = call(srv, "/sdapi/v1/progress",
                       headers={"Authorization": f"Basic {tok}"})
            assert "progress" in out
        finally:
            srv.stop()


class TestLoopClosure:
    """This framework's HTTPBackend drives this framework's server: the
    distributed deployment story (master World -> remote node) end to end."""

    def test_http_backend_roundtrip(self, server):
        backend = HTTPBackend("127.0.0.1", server.port)
        assert backend.reachable()
        payload = GenerationPayload(prompt="net cow", batch_size=4, seed=200,
                                    steps=4, width=64, height=64)
        # remote generates the sub-range [2, 4) — seed offset arithmetic
        # rides the wire exactly like the reference (distributed.py:297-305)
        result = backend.generate(payload, 2, 2)
        assert len(result.images) == 2
        assert result.seeds == [202, 203]

    def test_world_of_http_workers(self, server):
        w = World(ConfigModel())
        w.add_worker(WorkerNode(
            "remote", HTTPBackend("127.0.0.1", server.port), avg_ipm=10.0))
        r = w.execute(GenerationPayload(prompt="dist", batch_size=3,
                                        seed=300, steps=4, width=64,
                                        height=64))
        assert len(r.images) == 3
        assert r.seeds == [300, 301, 302]
        assert all("Worker Label: remote" in t for t in r.infotexts)

    def test_sampler_404_retries_with_euler_a(self):
        """A legacy remote that 404s an unknown sampler gets one retry with
        Euler a (reference worker.py:457-467)."""
        import http.server
        import threading

        seen = []

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):  # noqa: D102
                pass

            def do_POST(self):
                body = json.loads(self.rfile.read(
                    int(self.headers["Content-Length"])))
                seen.append(body["sampler_name"])
                if len(seen) == 1:
                    payload = b'{"detail": "Sampler not found"}'
                    self.send_response(404)
                else:
                    payload = json.dumps(
                        {"images": ["ok"], "info": "{}"}).encode()
                    self.send_response(200)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

        httpd = http.server.HTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            backend = HTTPBackend("127.0.0.1", httpd.server_port)
            result = backend.generate(GenerationPayload(
                prompt="x", sampler_name="Fancy New Sampler", seed=1), 0, 1)
            assert result.images == ["ok"]
            assert seen == ["Fancy New Sampler", "Euler a"]
        finally:
            httpd.shutdown()

    def test_models_and_options_via_backend(self, server):
        backend = HTTPBackend("127.0.0.1", server.port)
        assert isinstance(backend.available_models(), list)
        backend.load_options("some-model")  # no registry -> option recorded
        assert backend.memory_info()["cuda"]["system"]["free"] >= 0


class TestEndpointEditUnsupportedSource:
    def test_endpoint_fields_422_not_silently_dropped(self):
        """A source without update_worker_endpoint must reject endpoint
        edits (advisor r4: a 200 echoing unapplied fields hides the drop)."""

        class BareSource:
            workers = []

            def execute(self, payload):
                raise NotImplementedError

            def configure_worker(self, label, **kw):
                return True

        from stable_diffusion_webui_distributed_tpu.server.api import (
            ApiError, ApiServer,
        )

        srv = ApiServer(BareSource(), state=GenerationState())
        with pytest.raises(ApiError) as e:
            srv.handle_workers_post(
                {"label": "w", "address": "10.0.0.1", "port": 7860})
        assert e.value.status == 422
        assert "endpoint edits" in e.value.detail


class TestPinValidatedSurface:
    def test_worker_rows_carry_pin_validated(self, server):
        world = server.source
        n = WorkerNode("pv", StubBackend(), avg_ipm=5.0)
        n.backend.models = ["served.safetensors"]
        world.add_worker(n)
        try:
            call(server, "/internal/workers",
                 {"label": "pv", "model_override": "served.safetensors"})
            rows = call(server, "/internal/workers")
            row = next(r for r in rows if r["label"] == "pv")
            # validated live against the stub's model list
            assert row["pin_validated"] is True
        finally:
            world.workers.remove(n)

    def test_unreachable_node_pin_flagged_unvalidated(self, server):
        from stable_diffusion_webui_distributed_tpu.scheduler.worker import (
            StubBehavior,
        )

        world = server.source
        n = WorkerNode("down", StubBackend(StubBehavior(fail_reachable=True)),
                       avg_ipm=5.0)

        def boom():
            raise ConnectionError("down")

        n.backend.available_models = boom
        world.add_worker(n)
        try:
            call(server, "/internal/workers",
                 {"label": "down", "model_override": "typo.safetensors"})
            rows = call(server, "/internal/workers")
            row = next(r for r in rows if r["label"] == "down")
            assert row["pin_validated"] is False
        finally:
            world.workers.remove(n)


class TestJsonBody:
    """``api.json_body`` gives ``json.dumps(obj).encode()`` byte for byte,
    whichever way it takes: the encoder's own base64 copied in as bytes
    (PR 45: 16 ms of a four-image response's serialising), or everything
    through ``json.dumps``."""

    MARK = "\x00sdtpu:images\x00"

    @staticmethod
    def png(seed: int):
        import numpy as np

        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            encode_b64png,
        )

        return encode_b64png(np.random.default_rng(seed).integers(
            0, 256, (16, 16, 3), dtype=np.uint8))[0]

    @pytest.mark.parametrize("case", [
        "one image", "four images", "a grid first", "another worker's image",
        "the mark in a prompt", "the mark before the images", "no images",
        "no dict"])
    def test_byte_for_byte(self, case):
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            Base64Text,
        )
        from stable_diffusion_webui_distributed_tpu.server import api

        images = [self.png(i) for i in range(4)]
        assert all(type(i) is Base64Text for i in images)
        rest = {"parameters": {"prompt": "a \"quoted\" café", "n": 1},
                "info": json.dumps({"infotexts": ["x\ny"]})}
        obj = {
            "one image": {"images": images[:1], **rest},
            "four images": {"images": images, **rest},
            "a grid first": {"images": [self.png(9)] + images, **rest},
            "another worker's image": {"images": [str(images[0])]
                                       + images[1:], **rest},
            "the mark in a prompt": {"images": images[:2],
                                     "parameters": {"prompt": self.MARK}},
            "the mark before the images": {"prompt": self.MARK,
                                           "images": images[:2]},
            "no images": {"images": [], **rest},
            "no dict": [1, "two"],
        }[case]
        data, copied = api.json_body(obj)
        assert data == json.dumps(obj).encode()
        assert json.loads(data) == obj
        own = case in ("one image", "four images", "a grid first")
        assert copied == (len(obj["images"]) if own else 0)

    @pytest.mark.parametrize("grid", [False, True])
    def test_a_generation_response_takes_the_copy(self, grid, monkeypatch):
        """The response built from a result whose images the engine
        encoded (``out.images.append``, as ``_append_image`` does), with
        and without webui's grid in front: what ``json.dumps`` would have
        sent, and ``json.dumps`` never reads an image."""
        from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
            GenerationResult,
        )
        from stable_diffusion_webui_distributed_tpu.server import api

        result = GenerationResult(parameters={"prompt": "p"})
        for i in range(4):
            result.images.append(self.png(i))
            result.seeds.append(i)
            result.infotexts.append(f"p\nSeed: {i}")
        srv = ApiServer(make_world(), state=GenerationState(),
                        host="127.0.0.1", port=0)
        srv.options["return_grid"] = grid
        body = srv._generation_response(result)
        assert len(body["images"]) == 4 + grid
        want = json.dumps(body).encode()
        seen, dumps = [], json.dumps

        def watching(obj, *a, **kw):
            seen.append(obj)
            return dumps(obj, *a, **kw)

        monkeypatch.setattr(api.json, "dumps", watching)
        assert api.json_body(body) == (want, 4 + grid)
        assert [obj["images"] for obj in seen] == [api._IMAGES_MARK]

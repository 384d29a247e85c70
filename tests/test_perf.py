"""PR 8 observability: the perf ledger and its serving surfaces.

Covers the device-time-attribution layer end to end on CPU:

- ledger math (padding ratio/waste, dispatch counts, bounded
  group rings, SLO attainment + burn rate) on fresh ``PerfLedger``s;
- the executable census against the contracted <=2 step-cache x <=3
  precision budget, driven by REAL mixed cadence+precision traffic
  through a ``ServingDispatcher`` on one shape bucket, plus a synthetic
  over-budget key set that must trip the alarm;
- the off-by-default discipline: with every ``SDTPU_PERF*`` knob unset
  the dispatch output is byte-identical to the instrumented-on run;
- Prometheus label hygiene for user-supplied tenant/class names
  (control characters, quotes, newlines, kilobyte strings);
- flight-recorder perf attribution and ring boundedness under churn;
- the ``/internal/status`` schema snapshot and the new ``/internal/perf``,
  ``/internal/executables``, ``/internal/autoscale`` and GET
  ``/internal/profile`` endpoints over real HTTP.
"""

import json
import os
import urllib.error
import urllib.request

import pytest

from stable_diffusion_webui_distributed_tpu.fleet import slices
from stable_diffusion_webui_distributed_tpu.models.configs import TINY
from stable_diffusion_webui_distributed_tpu.obs import perf
from stable_diffusion_webui_distributed_tpu.obs import prometheus as obs_prom
from stable_diffusion_webui_distributed_tpu.obs.flightrec import (
    FlightRecorder,
)
from stable_diffusion_webui_distributed_tpu.pipeline import denoise
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
)
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
    ShapeBucketer,
)
from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
    ServingDispatcher,
)
from test_pipeline import init_params


def payload(**kw):
    defaults = dict(prompt="a cow", steps=4, width=32, height=32,
                    seed=7, sampler_name="Euler a")
    defaults.update(kw)
    return GenerationPayload(**defaults)


@pytest.fixture(scope="module")
def engine():
    return Engine(TINY, init_params(TINY), chunk_size=4,
                  state=GenerationState())


def _dispatcher(engine):
    # one disjoint (48, 48) bucket at batch 2: compile keys stay exact
    # and never collide with other modules' buckets on a shared cache
    return ServingDispatcher(
        engine, bucketer=ShapeBucketer(shapes=[(48, 48)], batches=[2]),
        window=0.0)


@pytest.fixture()
def clean_ledger():
    perf.LEDGER.clear()
    yield perf.LEDGER
    perf.LEDGER.clear()


def _record_one(led, **kw):
    args = dict(bucket="64x64", cadence=1, precision="bf16",
                device_s=2.0, requests=2, batch_raw=2,
                batch_run=4, true_pixels=3000, padded_pixels=4000)
    args.update(kw)
    led.record_dispatch(**args)


# -- ledger math -------------------------------------------------------------

class TestLedgerMath:
    def test_padding_and_dispatch_counts(self, monkeypatch):
        monkeypatch.setenv("SDTPU_PERF", "1")
        monkeypatch.setattr(perf, "_device_kind", lambda: "TPU v5 lite")
        led = perf.PerfLedger(max_groups=8)
        _record_one(led)
        (g,) = led.summary()["groups"]
        assert g["bucket"] == "64x64"
        assert g["padding_ratio"] == pytest.approx(4000 / 3000)
        assert g["padding_waste"] == pytest.approx(0.25)
        assert g["dispatches"] == 1 and g["requests"] == 2

    def test_disabled_is_a_noop(self, monkeypatch):
        monkeypatch.delenv("SDTPU_PERF", raising=False)
        led = perf.PerfLedger(max_groups=8)
        _record_one(led)
        led.record_compile("chunk", 1.0)
        led.record_slo(tenant="t", cls="c", slo_s=1.0, latency_s=0.1)
        s = led.summary()
        assert s["enabled"] is False
        assert s["groups"] == [] and s["slo"] == [] and s["compiles"] == {}
        assert led.last_dispatch() is None

    def test_group_ring_evicts_oldest_and_counts_it(self, monkeypatch):
        monkeypatch.setenv("SDTPU_PERF", "1")
        led = perf.PerfLedger(max_groups=2)
        for bucket in ("a", "b", "c"):
            _record_one(led, bucket=bucket)
        s = led.summary()
        assert [g["bucket"] for g in s["groups"]] == ["b", "c"]
        assert s["groups_evicted"] == 1  # dropped coverage is declared

    def test_slo_attainment_and_burn_rate(self, monkeypatch):
        # 1 miss in a 10-deep window against a 5% error budget: burn 2.0
        monkeypatch.setenv("SDTPU_PERF", "1")
        led = perf.PerfLedger(slo_target=0.95)
        for _ in range(9):
            led.record_slo(tenant="acme", cls="interactive",
                           slo_s=1.0, latency_s=0.2)
        led.record_slo(tenant="acme", cls="interactive",
                       slo_s=1.0, latency_s=3.0)   # late: burns budget
        (row,) = led.summary()["slo"]
        assert (row["tenant"], row["class"]) == ("acme", "interactive")
        assert row["total"] == 10 and row["met"] == 9
        assert row["attainment"] == pytest.approx(0.9)
        assert row["burn_rate"] == pytest.approx((1 / 10) / 0.05)

    def test_errored_request_burns_budget_even_if_fast(self, monkeypatch):
        monkeypatch.setenv("SDTPU_PERF", "1")
        led = perf.PerfLedger(slo_target=0.95)
        led.record_slo(tenant="t", cls="c", slo_s=1.0, latency_s=0.1,
                       ok=False)
        (row,) = led.summary()["slo"]
        assert row["met"] == 0

    def test_garbage_input_never_raises(self, monkeypatch):
        # telemetry must not fail the dispatch path
        monkeypatch.setenv("SDTPU_PERF", "1")
        led = perf.PerfLedger(max_groups=8)
        _record_one(led, cadence="not-a-number")
        assert led.summary()["groups"] == []


class TestPeakFlops:
    def test_known_chip_is_keyed_by_reported_device_kind(self):
        assert perf.peak_flops_for("TPU v5 lite") == pytest.approx(197e12)

    def test_int8_doubles_the_mxu_peak(self):
        assert perf.peak_flops_for("TPU v5 lite", "int8") \
            == pytest.approx(2 * 197e12)

    @pytest.mark.parametrize("kind", [
        "cpu", "", "TPU v5e", "TPU v5", "tpu v5 lite", "TPU v5 lite pod",
    ])
    def test_kind_outside_the_table_is_none(self, kind, monkeypatch):
        # no substring matching and no env override: a kind the table has
        # not met gets no peak, at any precision
        monkeypatch.setenv("SDTPU_PERF_PEAK_FLOPS", "123e9")
        assert perf.peak_flops_for(kind) is None
        assert perf.peak_flops_for(kind, "int8") is None


# -- executable census -------------------------------------------------------

class TestCensus:
    def test_mixed_traffic_holds_the_budget(self, engine, clean_ledger,
                                            monkeypatch):
        # real traffic on ONE bucket across the two budgeted axes: plain
        # bf16, step-cache (deepcache cadence 2), and the int8 rung —
        # cadence is a runtime arg, so this mints exactly 3 chunk
        # executables (2 step-cache variants x 2 precisions actually used)
        monkeypatch.setenv("SDTPU_PERF", "1")
        disp = _dispatcher(engine)
        disp.submit(payload(seed=41))
        disp.submit(payload(seed=42, override_settings={"deepcache": 2}))
        disp.submit(payload(seed=43,
                            override_settings={"precision": "int8"}))

        census = perf.executables_census(engine)
        assert census["alarm"] is False and census["over_budget"] == []
        assert census["budget"] == {"step_cache": 2, "precision": 3,
                                    "lora": 4, "per_bucket": 6}
        (row,) = [r for r in census["buckets"]
                  if r["bucket"] == "Euler a/4st 48x48 b2"]
        assert row["executables"] == 3
        assert row["step_cache_variants"] == 2
        assert row["precisions"] == ["bf16", "int8"]
        assert row["over_budget"] is False

        # the same traffic fed the ledger: three (bucket, cadence,
        # precision) groups, padding accounted (32x32 true vs 48x48 run)
        groups = {(g["cadence"], g["precision"]): g
                  for g in perf.LEDGER.summary()["groups"]
                  if g["bucket"] == "48x48"}
        assert set(groups) == {(1, "bf16"), (2, "bf16"), (1, "int8")}
        g = groups[(1, "bf16")]
        assert g["device_s"] > 0
        # 1 request padded to batch 2 at 48x48 vs one true 32x32 image
        assert g["padding_ratio"] == pytest.approx(
            (48 * 48 * 2) / (32 * 32), rel=1e-6)

    def test_synthetic_over_budget_trips_the_alarm(self):
        def key(sc, prec):
            return denoise.Variant("chunk", "Euler a", 4, 64, 64, 4, 1,
                                   family="sd", step_cache=sc,
                                   precision=prec).key()

        keys = [key(False, "bf16"), key(True, "bf16"), key("half", "bf16")]
        census = perf.census_from_keys(keys)
        assert census["alarm"] is True
        assert census["over_budget"] == ["Euler a/4st 64x64 b4"]
        (row,) = census["buckets"]
        assert row["step_cache_variants"] == 3      # > the budget of 2
        assert row["over_budget"] is True

    def test_non_chunk_keys_are_counted_not_budgeted(self):
        census = perf.census_from_keys([("decode", 64, 64, 4)])
        assert census["buckets"] == []
        assert census["other_executables"] == 1
        assert census["alarm"] is False


# -- off-by-default byte identity -------------------------------------------

class TestByteIdentity:
    def test_perf_on_output_matches_perf_off(self, engine, clean_ledger,
                                             monkeypatch):
        disp = _dispatcher(engine)
        monkeypatch.delenv("SDTPU_PERF", raising=False)
        off = disp.submit(payload(seed=77))
        assert perf.LEDGER.last_dispatch() is None   # truly dormant

        monkeypatch.setenv("SDTPU_PERF", "1")
        on = disp.submit(payload(seed=77))
        assert on.images == off.images               # byte-identical pngs
        assert on.seeds == off.seeds
        last = perf.LEDGER.last_dispatch()
        assert last is not None and last["bucket"] == "48x48"
        assert last["precision"] == "bf16" and last["device_s"] > 0


# -- prometheus label hygiene ------------------------------------------------

class TestPromLabels:
    def test_sanitize_drops_controls_keeps_newline(self):
        assert obs_prom.sanitize_label_value("a\rb\x00c\x7fd") == "abcd"
        assert obs_prom.sanitize_label_value("a\nb") == "a\nb"
        assert len(obs_prom.sanitize_label_value("x" * 4096)) == 100

    def test_adversarial_tenant_renders_on_one_line(self, clean_ledger,
                                                    monkeypatch):
        monkeypatch.setenv("SDTPU_PERF", "1")
        perf.LEDGER.record_slo(tenant='evil"tenant\n\rX',
                               cls="interactive\x00", slo_s=1.0,
                               latency_s=0.5)
        body = obs_prom.render()
        lines = [ln for ln in body.splitlines()
                 if ln.startswith("sdtpu_fleet_slo_attainment{")]
        assert lines, "slo family missing from exposition"
        (line,) = lines
        # \r and NUL dropped by sanitation; " and \n escaped losslessly
        assert 'tenant="evil\\"tenant\\nX"' in line
        assert 'class="interactive"' in line
        assert line.endswith(" 1")
        assert "sdtpu_fleet_slo_burn_rate" in body

    def test_registry_rejects_bad_names_and_collisions(self):
        with pytest.raises(obs_prom.MetricRegistrationError):
            obs_prom.register_metric("Bad Name", "counter", "x")
        obs_prom.register_metric("sdtpu_test_collision_total",
                                 "counter", "x")
        with pytest.raises(obs_prom.MetricRegistrationError):
            obs_prom.register_metric("sdtpu_test_collision_total",
                                     "gauge", "x")


# -- flight recorder ---------------------------------------------------------

class TestFlightRec:
    def test_ring_stays_bounded_under_churn(self):
        rec = FlightRecorder(capacity=8)
        for i in range(100):
            rec.record(f"r{i}", "error", "boom", events=[])
        doc = rec.dump()
        assert doc["capacity"] == 8 and doc["count"] == 8
        assert len(rec) == 8
        assert [e["request_id"] for e in doc["entries"]] \
            == [f"r{i}" for i in range(92, 100)]

    def test_entries_carry_last_dispatch_perf(self, clean_ledger,
                                              monkeypatch):
        monkeypatch.setenv("SDTPU_PERF", "1")
        _record_one(perf.LEDGER)
        rec = FlightRecorder(capacity=2)
        entry = rec.record("rid-1", "interrupted", "detail", events=[])
        assert entry["perf"]["bucket"] == "64x64"
        assert entry["perf"]["precision"] == "bf16"

    def test_perf_field_is_null_before_any_dispatch(self, clean_ledger):
        rec = FlightRecorder(capacity=2)
        assert rec.record("rid-2", "error", "d", events=[])["perf"] is None


# -- HTTP surfaces -----------------------------------------------------------

def call(server, route, body=None, method=None):
    url = f"http://127.0.0.1:{server.port}{route}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method or ("POST" if data else "GET"),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read() or b"{}")


@pytest.fixture(scope="class")
def server(engine):
    from stable_diffusion_webui_distributed_tpu.server.api import ApiServer

    srv = ApiServer(engine, state=engine.state,
                    host="127.0.0.1", port=0).start()
    # the auto-created dispatcher carries the default 512x ladder; swap in
    # the test bucketer so any traffic shares this module's compile keys
    srv.dispatcher = _dispatcher(engine)
    yield srv
    srv.stop()


class TestEndpoints:
    def test_status_schema_snapshot(self, server):
        # the /internal/status contract: exact top-level shape, pinned so
        # panel consumers (and this repo's own tools) notice breakage
        out = call(server, "/internal/status")
        assert set(out) == {"model", "workers", "settings", "serving",
                            "pool", "obs", "progress", "timings", "logs"}
        # no pool installed: the block is a bare gate echo
        assert out["pool"] == {"enabled": False}
        assert set(out["progress"]) == {"job", "sampling_step",
                                        "sampling_steps", "fraction",
                                        "interrupted"}
        serving = out["serving"]
        assert serving is not None  # engine-backed: dispatcher is live
        for key in ("coalesce_window_s", "bucket_ladder", "batch_ladder",
                    "eta_overhead", "fleet", "requests", "dispatches"):
            assert key in serving, key

    def test_perf_endpoint_serves_ledger(self, server, clean_ledger,
                                         monkeypatch):
        monkeypatch.setenv("SDTPU_PERF", "1")
        _record_one(perf.LEDGER, bucket="48x48")
        perf.LEDGER.record_compile("chunk", 0.25)
        out = call(server, "/internal/perf")
        assert out["enabled"] is True
        assert [g["bucket"] for g in out["groups"]] == ["48x48"]
        assert out["compiles"]["chunk"]["count"] == 1
        assert out["slo_target"] == pytest.approx(0.95)

    def test_executables_endpoint_census(self, server):
        out = call(server, "/internal/executables")
        assert out["available"] is True
        assert out["alarm"] is False
        assert out["budget"]["per_bucket"] == 6
        assert isinstance(out["buckets"], list)

    def test_dispatcher_tier_journal_journey(self, server, monkeypatch):
        # the serving-tier lifecycle events (PR 9): a real engine dispatch
        # must journal the full received -> ... -> completed journey with
        # an intact causal parent chain
        from stable_diffusion_webui_distributed_tpu.obs import (
            journal as obs_journal,
        )

        monkeypatch.setenv("SDTPU_JOURNAL", "1")
        obs_journal.JOURNAL.clear()
        try:
            out = call(server, "/sdapi/v1/txt2img",
                       {"prompt": "a cow", "batch_size": 2, "seed": 3,
                        "steps": 4, "width": 32, "height": 32,
                        "request_id": "rid-disp-journey"})
            assert len(out["images"]) == 2
            events = call(server,
                          "/internal/journal?request_id=rid-disp-journey"
                          )["events"]
            names = [e["event"] for e in events]
            assert names == ["received", "bucketed", "coalesced_leader",
                             "dispatched", "decoded", "merged",
                             "completed"]
            by_name = {e["event"]: e for e in events}
            assert by_name["bucketed"]["attrs"]["bucket"] == "48x48"
            assert by_name["received"]["attrs"]["fingerprint"]
            assert by_name["completed"]["attrs"]["seeds"] == [3, 4]
            seqs = {e["seq"] for e in events}
            assert events[0]["parent"] is None
            assert all(e["parent"] in seqs for e in events[1:])
        finally:
            obs_journal.JOURNAL.clear()

    def test_autoscale_endpoint_audit_ring(self, server):
        slices.set_autoscale(None)
        try:
            assert call(server, "/internal/autoscale") == {"active": False}
            reg = slices.SliceRegistry()
            reg.register(slices.SliceInfo(name="s0", group="tiny/bf16",
                                          replicas=1, max_replicas=4))
            eng = slices.AutoscaleEngine(
                reg, quantile_source=lambda: 10.0, up_p95_s=5.0,
                down_p95_s=0.5, cooldown_s=0.0)   # registers itself
            assert eng.decide(), "expected an up decision"
            out = call(server, "/internal/autoscale")
            assert out["active"] is True
            assert out["decisions_total"] == 1
            (d,) = out["decisions"]
            assert d["direction"] == "up" and d["slice_name"] == "s0"
            assert d["decided_at"] > 0      # wall clock for correlation
            # no executor attached: the outcome field says so explicitly
            assert d["execution"] == {"outcome": "no_executor"}
        finally:
            slices.set_autoscale(None)

    def test_autoscale_audit_ring_is_bounded(self, monkeypatch):
        monkeypatch.setenv("SDTPU_AUTOSCALE_AUDIT", "4")
        try:
            reg = slices.SliceRegistry()
            reg.register(slices.SliceInfo(name="s0", group="g",
                                          min_replicas=1, max_replicas=2))
            p95 = [0.0]
            eng = slices.AutoscaleEngine(
                reg, quantile_source=lambda: p95[0], up_p95_s=5.0,
                down_p95_s=0.5, cooldown_s=0.0)
            for i in range(10):
                p95[0] = 10.0 if i % 2 == 0 else 0.1  # up, down, up, ...
                assert eng.decide()
            audit = eng.audit()
            assert audit["capacity"] == 4
            assert audit["decisions_total"] == 10
            assert len(audit["decisions"]) == 4     # ring wrapped
        finally:
            slices.set_autoscale(None)

    def test_profile_get_validates_seconds(self, server):
        with pytest.raises(urllib.error.HTTPError) as e:
            call(server, "/internal/profile?seconds=abc")
        assert e.value.code == 422

    def test_profile_get_one_shot_capture(self, server, monkeypatch,
                                          tmp_path):
        # a real (tiny) jax.profiler capture; chdir jails the trace dir
        # under tmp so nothing lands in the repo
        monkeypatch.chdir(tmp_path)
        out = call(server, "/internal/profile?seconds=0.1&dir=t1")
        assert out["seconds"] == pytest.approx(0.1)
        assert out["captured_dir"] == os.path.join("profile-traces", "t1")
        assert (tmp_path / "profile-traces" / "t1").is_dir()

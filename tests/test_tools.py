"""Tests for the bench tooling under tools/: the sweep's cell table and
subprocess choreography, and the report/compare scripts. No TPU needed."""

import json
import subprocess
import sys

import pytest

sys.path.insert(0, "tools")


class TestSweepCells:
    def test_cells_unpack(self):
        import sweep

        for name, cell in sweep.CELLS.items():
            cfg_n, pol_kwargs, chunk = cell
            assert 1 <= cfg_n <= 5, name
            assert isinstance(pol_kwargs, dict), name
            assert chunk > 0, name


@pytest.mark.slow
class TestSweepRehearsal:
    """End-to-end rehearsal of the sweep machinery on CPU tiny mode: the
    subprocess choreography, SWEEP_ROW parsing, and jsonl append are the
    exact code path a chip run takes — validated here instead of being
    first exercised on the chip."""

    def test_one_cell_tiny(self, tmp_path):
        import os

        out_file = tmp_path / "sweep_out.jsonl"
        env = dict(os.environ)
        env.update(JAX_PLATFORMS="cpu", SDTPU_BENCH_TINY="1",
                   SDTPU_SWEEP_OUT=str(out_file),
                   SDTPU_SWEEP_DEADLINE="3000")
        proc = subprocess.run(
            [sys.executable, "tools/sweep.py", "c1-bf16"],
            capture_output=True, text=True, env=env, timeout=900)
        assert proc.returncode == 0, proc.stderr[-2000:]
        rows = [json.loads(l) for l in out_file.read_text().splitlines()]
        assert len(rows) == 1
        row = rows[0]
        assert row["cell"] == "c1-bf16"
        assert row.get("value"), row      # a real ipm number came through
        assert row["unit"] == "images/min"
        assert "wall_s" in row


class TestTraceReport:
    """tools/trace_report.py: span-tree rendering and the slowest-span
    roll-up over the /internal/trace.json artifact shape."""

    @staticmethod
    def _event(name, rid, span_id, parent_id=None, ts=0.0, dur_us=1000.0,
               **attrs):
        args = {"request_id": rid, "span_id": span_id, **attrs}
        if parent_id is not None:
            args["parent_id"] = parent_id
        return {"ph": "X", "cat": "sdtpu", "name": name, "pid": 1, "tid": 2,
                "ts": ts, "dur": dur_us, "args": args}

    @pytest.fixture()
    def trace(self):
        e = self._event
        return {"traceEvents": [
            # request A: root(1) > dispatch(2) > denoise_chunk(3)
            e("txt2img", "aaa", 1, ts=0.0, dur_us=50_000.0),
            e("dispatch.device", "aaa", 2, parent_id=1, ts=5_000.0,
              dur_us=40_000.0),
            e("denoise_chunk", "aaa", 3, parent_id=2, ts=6_000.0,
              dur_us=30_000.0),
            # request B: a follower's wait on its leader's dispatch
            e("txt2img", "bbb", 4, ts=1_000.0, dur_us=48_000.0),
            e("coalesced.wait", "bbb", 5, parent_id=4, ts=1_200.0,
              dur_us=44_000.0, leader_request_id="aaa", leader_span_id=2),
        ], "displayTimeUnit": "ms"}

    def test_tree_structure_and_grouping(self, trace):
        import trace_report

        report = trace_report.build_report(trace)
        assert report["event_count"] == 5
        assert list(report["requests"]) == ["aaa", "bbb"]
        tree_a = report["requests"]["aaa"]
        assert len(tree_a) == 3
        assert tree_a[0].lstrip().startswith("txt2img")
        # nesting depth shows in indentation: root < child < grandchild
        indents = [len(l) - len(l.lstrip()) for l in tree_a]
        assert indents[0] < indents[1] < indents[2]
        # the link to the leader survives into the rendered line
        assert any("leader_request_id=aaa" in l
                   for l in report["requests"]["bbb"])

    def test_top_stages_ranked_by_total(self, trace):
        import trace_report

        rows = trace_report.top_stages(trace_report.load_events(trace), k=2)
        assert len(rows) == 2
        assert rows[0]["name"] == "txt2img"          # 50+48 ms total
        assert rows[0]["count"] == 2
        assert rows[0]["total_ms"] >= rows[1]["total_ms"]

    def test_flightrec_shape_accepted(self, trace):
        import trace_report

        dump = {"entries": [
            {"request_id": "aaa", "reason": "error",
             "spans": trace["traceEvents"][:3]}], "capacity": 16, "count": 1}
        assert len(trace_report.load_events(dump)) == 3

    def test_request_filter(self, trace):
        import trace_report

        report = trace_report.build_report(trace, request_id="bb")
        assert list(report["requests"]) == ["bbb"]
        assert report["event_count"] == 5  # top table still whole-file

    def test_main_exit_codes(self, tmp_path, trace, capsys):
        import trace_report

        p = tmp_path / "trace.json"
        p.write_text(json.dumps(trace))
        assert trace_report.main([str(p)]) == 0
        out = capsys.readouterr().out
        assert "request aaa" in out and "top" in out

        empty = tmp_path / "empty.json"
        empty.write_text(json.dumps({"traceEvents": []}))
        assert trace_report.main([str(empty)]) == 1
        assert trace_report.main([str(tmp_path / "missing.json")]) == 2

    def test_a_device_pair_shows_once_under_its_enqueue(self, trace):
        """``device.run`` (ISSUE 71) comes as an async pair: the "b" half
        is the span, the "e" half is dropped."""
        import trace_report

        run = self._event("device.run", "aaa", 6, parent_id=3, ts=7_000.0,
                          dur_us=28_000.0, kind="run_chunk", exact=True)
        run.update(ph="b", cat="sdtpu.device", id=6, tid=0)
        end = {"ph": "e", "cat": "sdtpu.device", "name": "device.run",
               "pid": 1, "tid": 0, "id": 6, "ts": 35_000.0, "dur": 0.0,
               "args": {"request_id": "aaa"}}
        trace["traceEvents"] += [run, end]
        report = trace_report.build_report(trace)
        assert report["event_count"] == 6
        tree_a = report["requests"]["aaa"]
        assert len(tree_a) == 4 and "device.run" in tree_a[3]
        assert "kind=run_chunk" in tree_a[3]


class TestTraceProbe:
    """tools/trace_probe.py's reading of the device's side (ISSUE 71)."""

    @staticmethod
    def _events():
        def host(name, sid, parent, ts, dur, **attrs):
            return {"ph": "X", "name": name, "ts": ts, "dur": dur,
                    "args": {"request_id": "w-1", "span_id": sid,
                             "parent_id": parent, **attrs}}

        def run(sid, parent, ts, dur, kind, **attrs):
            return {"ph": "b", "name": "device.run", "ts": ts, "dur": dur,
                    "id": sid, "args": {"request_id": "w-1", "span_id": sid,
                                        "parent_id": parent, "kind": kind,
                                        **attrs}}

        events = [
            host("dispatch.device", 1, 0, 0.0, 10_000.0, requests=1),
            host("chunk.enqueue", 2, 1, 100.0, 400.0, dry=True),
            host("chunk.enqueue", 3, 1, 600.0, 300.0, dry=False),
            host("chunk.fence_wait", 4, 1, 900.0, 3_100.0, late=False),
            host("chunk.fence_wait", 5, 1, 4_000.0, 3_000.0, late=False),
            host("vae_decode_dispatch", 6, 1, 7_100.0, 200.0, dry=True),
            host("decode.wait", 7, 1, 7_400.0, 10.0, late=True),
            run(8, 2, 500.0, 3_500.0, "run_chunk", exact=True, dry=True),
            run(9, 3, 4_000.0, 3_000.0, "run_chunk", exact=True, dry=False),
            run(10, 6, 7_300.0, 100.0, "decode_u8", bound=True, dry=True),
            {"ph": "e", "name": "device.run", "ts": 4_000.0, "dur": 0.0,
             "id": 8, "args": {"request_id": "w-1"}},
        ]
        return {"traceEvents": events}

    def test_by_request_keeps_the_device_off_the_host_rows(self):
        import trace_probe

        doc = self._events()
        assert len(trace_probe.by_request(doc)["w-1"]) == 7
        assert [e["args"]["span_id"]
                for e in trace_probe.by_request(doc, "b")["w-1"]] \
            == [8, 9, 10]

    @pytest.mark.parametrize("intervals, inside", [
        ([], 0.0), ([(1.0, 2.0), (3.0, 4.0)], 2.0),
        ([(1.0, 6.0), (2.0, 3.0), (6.0, 7.0)], 6.0),
        ([(-5.0, 1.0), (9.0, 20.0)], 2.0)])
    def test_union_inside(self, intervals, inside):
        import trace_probe

        assert trace_probe.union_inside((0.0, 10.0), intervals) \
            == pytest.approx(inside)

    def test_device_of_a_request(self):
        import trace_probe

        doc = self._events()
        row = trace_probe.device_of(trace_probe.by_request(doc)["w-1"],
                                    trace_probe.by_request(doc, "b")["w-1"])
        assert row["busy_ms"] == pytest.approx(6.6)
        assert row["idle_ms"] == pytest.approx(3.4)
        assert row["by_kind_ms"] == pytest.approx(
            {"run_chunk": 6.5, "decode_u8": 0.1})
        assert (row["dispatches"], row["dry"], row["fences"], row["late"],
                row["exact"]) == (3, 2, 3, 1, 2)
        # a follower's tree (no section) and a parent's (no run): nothing
        assert trace_probe.device_of([], []) is None
        assert trace_probe.device_of(
            trace_probe.by_request(doc)["w-1"], []) is None

    def test_device_block_is_the_window_s_median(self, capsys):
        import trace_probe

        doc = self._events()
        host, runs = (trace_probe.by_request(doc, ph) for ph in "Xb")
        before = {"serving": {"device": {"requests": 1, "idle_s": 0.5,
                                         "dispatches": {"run_chunk": 2}}}}
        after = {"serving": {"device": {
            "requests": 3, "idle_s": 0.75, "dispatches": {"run_chunk": 6},
            "watcher": {"armed": False, "alive": False, "stamped": 0}}}}
        out = trace_probe.device_block(host, runs, [before, after],
                                       ["MainThread", "host-clock"])
        assert out["requests"] == 1 and out["busy_ms"] == pytest.approx(6.6)
        assert out["exact_share"] == pytest.approx(2 / 3)
        assert out["serving_device"] == {"requests": 2, "idle_s": 0.25}
        assert out["watcher"]["alive"] is False
        assert out["threads"] == ["MainThread", "host-clock"]
        assert capsys.readouterr().out.startswith("device: {")
        assert trace_probe.device_block({}, {}, [], []) is None


class TestFleetReport:
    """tools/fleet_report.py: the BENCH_fleet.json digest — per-class
    rows, the FIFO-vs-fleet p95 delta, and the exit-code contract."""

    @staticmethod
    def _doc(**over):
        doc = {
            "metric": "tiny_fleet_interactive_p95_s",
            "device": "cpu",
            "classes": {
                "interactive": {"requests": 6, "completed": 6,
                                "throttled": 0, "rejected": 0,
                                "p50_s": 2.0, "p95_s": 4.0,
                                "slo_s": 10.0, "slo_attainment": 1.0},
                "batch": {"requests": 3, "completed": 3, "throttled": 0,
                          "rejected": 0, "p50_s": 20.0, "p95_s": 30.0},
                "best_effort": {"requests": 10, "completed": 8,
                                "throttled": 2, "rejected": 0,
                                "p50_s": 12.0, "p95_s": 16.0},
            },
            "baseline_fifo": {
                "interactive": {"p95_s": 16.0, "slo_attainment": 0.5},
                "batch": {"p95_s": 24.0},
                "best_effort": {"p95_s": 20.0},
            },
            "preemptions": 2,
            "quota_throttle_rate": 0.105,
            "queue_wait_p95_s": 12.5,
            "errors": [],
        }
        doc.update(over)
        return doc

    def test_summary_rows_and_delta(self):
        import fleet_report

        s = fleet_report.build_summary(self._doc())
        by_cls = {r["class"]: r for r in s["rows"]}
        assert list(by_cls) == ["interactive", "batch", "best_effort"]
        # fleet p95 4.0 vs FIFO 16.0: a 75% cut, signed negative
        assert by_cls["interactive"]["p95_delta_pct"] == -75.0
        # batch pays for the interactive win: positive delta
        assert by_cls["batch"]["p95_delta_pct"] == 25.0
        assert s["completed"] == 17
        assert s["slo_attainment"] == 1.0
        assert s["fifo_slo_attainment"] == 0.5
        assert s["preemptions"] == 2

    def test_missing_baseline_renders_dashes(self):
        import fleet_report

        s = fleet_report.build_summary(self._doc(baseline_fifo={}))
        assert all(r["p95_delta_pct"] is None for r in s["rows"])
        text = fleet_report.render(s)
        assert "interactive" in text and "-" in text

    def test_main_exit_codes(self, tmp_path, capsys):
        import fleet_report

        p = tmp_path / "BENCH_fleet.json"
        p.write_text(json.dumps(self._doc()))
        assert fleet_report.main([str(p)]) == 0
        out = capsys.readouterr().out
        assert "interactive SLO" in out and "preemptions: 2" in out

        assert fleet_report.main([str(p), "--json"]) == 0
        digest = json.loads(capsys.readouterr().out)
        assert digest["completed"] == 17

        dead = self._doc()
        for cls in dead["classes"].values():
            cls["completed"] = 0
        (tmp_path / "dead.json").write_text(json.dumps(dead))
        assert fleet_report.main([str(tmp_path / "dead.json")]) == 1

        (tmp_path / "garbage.json").write_text("{not json")
        assert fleet_report.main([str(tmp_path / "garbage.json")]) == 2
        assert fleet_report.main([str(tmp_path / "missing.json")]) == 2


class TestInt8Report:
    """tools/int8_report.py: the BENCH_int8.json digest — per-cell floor
    verdicts and the exit-code contract (1 = floors broken)."""

    @staticmethod
    def _doc(**over):
        doc = {
            "metric": "tiny_int8_min_psnr_db",
            "device": "cpu",
            "steps": 8,
            "psnr_floor_db": 20.0,
            "ssim_floor": 0.6,
            "mxu_peak_ratio_int8_vs_bf16": 2.0,
            "cells": [
                {"cell": "c1-bf16", "precision": "bf16", "cadence": 1,
                 "unet_flops_per_image": 3.78e9, "chunk_executables": 1},
                {"cell": "c1-int8", "precision": "int8", "cadence": 1,
                 "unet_flops_per_image": 3.87e9, "chunk_executables": 1,
                 "psnr_db_vs_bf16": 34.5, "ssim_vs_bf16": 0.997},
                {"cell": "c3-int8+conv", "precision": "int8+conv",
                 "cadence": 3, "unet_flops_per_image": 2.35e9,
                 "chunk_executables": 1,
                 "psnr_db_vs_bf16": 28.5, "ssim_vs_bf16": 0.985},
            ],
        }
        doc.update(over)
        return doc

    def test_summary_floor_verdicts(self):
        import int8_report

        s = int8_report.build_summary(self._doc())
        by_cell = {r["cell"]: r for r in s["rows"]}
        assert by_cell["c1-bf16"]["floors_ok"] is None  # control row
        assert by_cell["c1-int8"]["floors_ok"] is True
        assert s["quantized_cells"] == 2
        assert s["min_psnr_db"] == 28.5
        assert s["min_ssim"] == 0.985
        assert s["floors_ok"] is True

    def test_broken_floor_flips_verdict(self):
        import int8_report

        doc = self._doc()
        doc["cells"][2]["psnr_db_vs_bf16"] = 12.0
        s = int8_report.build_summary(doc)
        assert s["floors_ok"] is False
        assert "BROKEN" in int8_report.render(s)

    def test_main_exit_codes(self, tmp_path, capsys):
        import int8_report

        p = tmp_path / "BENCH_int8.json"
        p.write_text(json.dumps(self._doc()))
        assert int8_report.main([str(p)]) == 0
        out = capsys.readouterr().out
        assert "floors" in out and "HOLD" in out

        assert int8_report.main([str(p), "--json"]) == 0
        digest = json.loads(capsys.readouterr().out)
        assert digest["min_psnr_db"] == 28.5

        broken = self._doc()
        broken["cells"][1]["ssim_vs_bf16"] = 0.1
        (tmp_path / "broken.json").write_text(json.dumps(broken))
        assert int8_report.main([str(tmp_path / "broken.json")]) == 1

        empty = self._doc(cells=[])
        (tmp_path / "empty.json").write_text(json.dumps(empty))
        assert int8_report.main([str(tmp_path / "empty.json")]) == 1

        (tmp_path / "garbage.json").write_text("{not json")
        assert int8_report.main([str(tmp_path / "garbage.json")]) == 2
        assert int8_report.main([str(tmp_path / "missing.json")]) == 2


class TestBenchJson:
    """tools/benchjson.py: the shared bench-artifact I/O contract every
    report CLI loads through."""

    def test_load_bench_roundtrip(self, tmp_path):
        import benchjson

        p = tmp_path / "BENCH_x.json"
        p.write_text(json.dumps({"metric": "m", "value": 1.5}))
        assert benchjson.load_bench(str(p), "t")["value"] == 1.5

    def test_load_bench_errors_are_operator_ready(self, tmp_path):
        import benchjson

        with pytest.raises(benchjson.BenchJsonError) as e:
            benchjson.load_bench(str(tmp_path / "nope.json"), "mytool",
                                 hint="python bench.py --fleet")
        assert "mytool:" in str(e.value)
        assert "python bench.py --fleet" in str(e.value)

        garbage = tmp_path / "g.json"
        garbage.write_text("{not json")
        with pytest.raises(benchjson.BenchJsonError):
            benchjson.load_bench(str(garbage), "t")

        arr = tmp_path / "a.json"
        arr.write_text("[1, 2]")
        with pytest.raises(benchjson.BenchJsonError) as e:
            benchjson.load_bench(str(arr), "t")
        assert "not a JSON object" in str(e.value)

    def test_load_ledger_skips_blanks_keeps_order(self, tmp_path):
        import benchjson

        p = tmp_path / "L.jsonl"
        p.write_text('{"kind": "serving"}\n\n{"kind": "fleet"}\n')
        rows = benchjson.load_ledger(str(p), "t")
        assert [r["kind"] for r in rows] == ["serving", "fleet"]

    def test_load_ledger_errors(self, tmp_path):
        import benchjson

        with pytest.raises(benchjson.BenchJsonError):
            benchjson.load_ledger(str(tmp_path / "nope.jsonl"), "t")
        empty = tmp_path / "e.jsonl"
        empty.write_text("\n\n")
        with pytest.raises(benchjson.BenchJsonError) as e:
            benchjson.load_ledger(str(empty), "t")
        assert "no ledger rows" in str(e.value)
        bad = tmp_path / "b.jsonl"
        bad.write_text('{"ok": 1}\n[1]\n')
        with pytest.raises(benchjson.BenchJsonError) as e:
            benchjson.load_ledger(str(bad), "t")
        assert "line 2" in str(e.value)

    def test_fmt_placeholder_and_precision(self):
        import benchjson

        assert benchjson.fmt(None) == "-"
        assert benchjson.fmt(0.5) == "0.500"
        assert benchjson.fmt(3) == "3"
        assert benchjson.fmt(2.0, suffix="x") == "2.000x"

    def test_write_json_file_and_stdout(self, tmp_path, capsys):
        import benchjson

        out = tmp_path / "r.json"
        benchjson.write_json({"a": 1}, str(out))
        assert json.loads(out.read_text()) == {"a": 1}
        assert out.read_text().endswith("\n")
        benchjson.write_json({"b": 2})
        assert json.loads(capsys.readouterr().out) == {"b": 2}


class TestBenchCompare:
    """tools/bench_compare.py: the regression gate over ledger rows and
    BENCH artifacts — exit 0 clean, 1 regressed, 2 unusable input."""

    @staticmethod
    def _row(kind="serving", **metrics):
        base = {"chunk_compiles": 2, "coalesce_factor": 4.0,
                "bucket_hit_rate": 0.5, "avg_padding_ratio": 1.19,
                "unet_flops_per_image": 1.0e10}
        base.update(metrics)
        return {"schema": 1, "kind": kind, "device": "cpu", "tiny": True,
                "metrics": base}

    def test_identical_rows_are_clean(self):
        import bench_compare

        v = bench_compare.compare(self._row(), self._row())
        assert v["ok"] is True and v["regressions"] == []
        assert v["compared"] == 5

    def test_compile_count_regression_has_zero_tolerance(self):
        import bench_compare

        v = bench_compare.compare(self._row(),
                                  self._row(chunk_compiles=3))
        assert v["ok"] is False
        assert v["regressions"] == ["chunk_compiles"]

    def test_relative_threshold_allows_noise(self):
        import bench_compare

        # coalesce_factor tolerance is 10% relative: a 5% dip is noise,
        # a 25% dip is a regression
        ok = bench_compare.compare(self._row(),
                                   self._row(coalesce_factor=3.8))
        assert ok["ok"] is True
        bad = bench_compare.compare(self._row(),
                                    self._row(coalesce_factor=3.0))
        assert bad["regressions"] == ["coalesce_factor"]

    def test_improvements_never_fail(self):
        import bench_compare

        v = bench_compare.compare(
            self._row(),
            self._row(chunk_compiles=1, coalesce_factor=8.0,
                      avg_padding_ratio=1.0, bucket_hit_rate=1.0,
                      unet_flops_per_image=5.0e9))
        assert v["ok"] is True

    def test_value_alias_maps_bench_headline(self):
        import bench_compare

        base = {"metric": "tiny_serving_coalesce_factor", "value": 4.0}
        head = {"metric": "tiny_serving_coalesce_factor", "value": 1.0}
        v = bench_compare.compare(base, head)
        assert v["regressions"] == ["coalesce_factor"]

    def test_ledger_mode_oldest_vs_newest(self, tmp_path):
        import bench_compare

        p = tmp_path / "L.jsonl"
        rows = [self._row(), {"schema": 1, "kind": "fleet",
                              "metrics": {"slo_attainment": 1.0}},
                self._row(coalesce_factor=4.2)]
        p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
        assert bench_compare.main([str(p), "--kind", "serving"]) == 0

        rows.append(self._row(chunk_compiles=4))    # seeded regression
        p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
        assert bench_compare.main([str(p), "--kind", "serving"]) == 1

    def test_unusable_input_exits_2(self, tmp_path, capsys):
        import bench_compare

        assert bench_compare.main([str(tmp_path / "nope.jsonl")]) == 2
        one = tmp_path / "one.jsonl"
        one.write_text(json.dumps(self._row()) + "\n")
        assert bench_compare.main([str(one)]) == 2       # need 2 rows
        assert bench_compare.main([str(one), "--base-row", "5"]) == 2

        # artifact mode: nothing watched on either side
        a = tmp_path / "a.json"
        a.write_text(json.dumps({"foo": 1}))
        assert bench_compare.main([str(a), str(a)]) == 2
        assert "nothing" in capsys.readouterr().err

    def test_json_verdict_and_current_artifacts(self, capsys):
        import bench_compare

        # the committed BENCH files must compare clean against themselves
        # (wrapper artifacts unwrap through "parsed")
        for name in ("BENCH_serving.json", "BENCH_fleet.json"):
            assert bench_compare.main([name, name, "--json"]) == 0
            v = json.loads(capsys.readouterr().out)
            assert v["ok"] is True and v["compared"] >= 2


class TestLintReport:
    """tools/lint_report.py: the JSON roll-up plus the SARIF 2.1.0 log
    code-scanning endpoints ingest. Scoped to one fixture file so the
    test stays fast; the full-package run is TestRepoGate's job."""

    FIXTURE = ["tests/lint_fixtures/env_bad.py"]

    def _report(self):
        import lint_report

        return lint_report.build_report(paths=self.FIXTURE,
                                        use_allowlist=False)

    def test_report_carries_wall_time_and_counts(self):
        rep = self._report()
        assert isinstance(rep["wall_time_s"], float)
        assert rep["wall_time_s"] >= 0.0
        assert rep["finding_count"] == 2
        assert rep["counts_by_rule"] == {"EV001": 2}

    def test_sarif_log_shape(self):
        import lint_report

        rep = self._report()
        log = lint_report.to_sarif(rep)
        assert log["version"] == "2.1.0"
        assert log["$schema"].endswith("sarif-schema-2.1.0.json")
        assert len(log["runs"]) == 1
        run = log["runs"][0]
        driver = run["tool"]["driver"]
        assert driver["name"] == "sdtpu-lint"
        rule_ids = {r["id"] for r in driver["rules"]}
        assert rule_ids == set(rep["rules"])
        for r in driver["rules"]:
            assert r["shortDescription"]["text"]
        assert len(run["results"]) == rep["finding_count"]
        for res in run["results"]:
            assert res["ruleId"] in rule_ids
            assert res["message"]["text"]
            loc = res["locations"][0]["physicalLocation"]
            assert loc["artifactLocation"]["uri"] == self.FIXTURE[0]
            assert loc["region"]["startLine"] >= 1

    def test_sarif_cli_writes_the_log(self, tmp_path):
        import lint_report

        out = tmp_path / "lint.sarif"
        rc = lint_report.main(
            self.FIXTURE + ["--no-allowlist", "--sarif", str(out),
                            "-o", str(tmp_path / "lint.json")])
        assert rc == 1  # the fixture has findings by design
        log = json.loads(out.read_text())
        assert log["version"] == "2.1.0"
        assert log["runs"][0]["results"]

    def test_suppressed_findings_carry_suppressions(self, tmp_path):
        import lint_report

        allow = tmp_path / "allow.json"
        allow.write_text(json.dumps([{
            "rule": "EV001", "path": self.FIXTURE[0],
            "symbol": "read_knob", "reason": "fixture exercise"}]))
        rep = lint_report.build_report(paths=self.FIXTURE,
                                       allowlist_path=str(allow))
        log = lint_report.to_sarif(rep)
        results = log["runs"][0]["results"]
        flagged = [r for r in results if "suppressions" in r]
        assert len(flagged) == 1
        assert flagged[0]["suppressions"][0]["kind"] == "external"

    def test_lint_ledger_row_gates_finding_count(self):
        import bench_compare

        def row(count, wall):
            return {"schema": 1, "kind": "lint", "device": "cpu",
                    "tiny": True, "metrics": {
                        "lint_finding_count": count,
                        "lint_wall_time_s": wall,
                        "lint_modules": 84}}

        # wall time is trajectory-only: doubling it alone stays clean
        ok = bench_compare.compare(row(0, 4.0), row(0, 9.0))
        assert ok["ok"] is True
        # the finding count has zero tolerance
        bad = bench_compare.compare(row(0, 4.0), row(1, 4.0))
        assert bad["ok"] is False
        assert bad["regressions"] == ["lint_finding_count"]


class TestAlertReport:
    @staticmethod
    def _doc(fps=0, missed=False):
        steady_fired = ["queue_wait_anomaly"] if fps else []
        kill_fired = [] if missed else ["error_rate_anomaly"]
        phases = [
            {"name": "steady", "expected": [], "fired": steady_fired,
             "false_positives": len(steady_fired), "detected": None},
            {"name": "chaos_kill",
             "expected": ["error_rate_anomaly", "worker_flap"],
             "fired": kill_fired, "false_positives": 0,
             "detected": bool(kill_fired)},
            {"name": "chaos_stall", "expected": ["watchdog_stall"],
             "fired": ["watchdog_stall"], "false_positives": 0,
             "detected": True},
        ]
        detected = sum(1 for p in phases if p["detected"])
        faults = 2
        return {
            "device": "cpu",
            "validation": {
                "phases": phases,
                "alert_false_positives": len(steady_fired),
                "false_positive_rules": steady_fired,
                "faults": faults,
                "detected": detected,
                "alert_recall": detected / faults,
            },
            "history": [
                {"rule": "watchdog_stall", "from": "pending",
                 "to": "firing", "t": 1.0, "value": 1.0,
                 "detail": "window increase 1 vs 1"},
                {"rule": "watchdog_stall", "from": "firing", "to": "ok",
                 "t": 2.0, "value": 0.0, "detail": "aged out"},
            ],
        }

    def test_rule_scores_arithmetic(self):
        import alert_report

        scores = alert_report.rule_scores(self._doc()["validation"]
                                          ["phases"])
        # fired in its expected window, never in steady
        assert scores["error_rate_anomaly"] == {
            "true_positives": 1, "false_positives": 0,
            "fault_windows": 1, "precision": 1.0, "recall": 1.0}
        # expected but silent: sibling covered the window, still recall 0
        # for the rule itself
        assert scores["worker_flap"]["recall"] == 0.0
        assert scores["worker_flap"]["precision"] is None
        fp = alert_report.rule_scores(self._doc(fps=1)["validation"]
                                      ["phases"])
        assert fp["queue_wait_anomaly"]["false_positives"] == 1
        assert fp["queue_wait_anomaly"]["precision"] == 0.0

    def test_main_exit_codes(self, tmp_path, capsys):
        import alert_report

        clean = tmp_path / "BENCH_alerts.json"
        clean.write_text(json.dumps(self._doc()))
        assert alert_report.main([str(clean)]) == 0
        out = capsys.readouterr().out
        assert "CLEAN" in out and "DETECTED" in out
        assert "firing history" in out

        assert alert_report.main([str(clean), "--json"]) == 0
        digest = json.loads(capsys.readouterr().out)
        assert digest["alert_recall"] == 1.0

        fp = tmp_path / "fp.json"
        fp.write_text(json.dumps(self._doc(fps=1)))
        assert alert_report.main([str(fp)]) == 1
        assert "FALSE POSITIVE" in capsys.readouterr().out

        miss = tmp_path / "miss.json"
        miss.write_text(json.dumps(self._doc(missed=True)))
        assert alert_report.main([str(miss)]) == 1
        assert "MISSED" in capsys.readouterr().out

        (tmp_path / "garbage.json").write_text("{not json")
        assert alert_report.main([str(tmp_path / "garbage.json")]) == 2
        assert alert_report.main([str(tmp_path / "missing.json")]) == 2
        # an artifact from a bench that died before phase validation
        (tmp_path / "dead.json").write_text(json.dumps({"device": "cpu"}))
        assert alert_report.main([str(tmp_path / "dead.json")]) == 2


class TestFedReport:
    @staticmethod
    def _fleet_doc(stale=False):
        return {
            "enabled": True, "stale_after_s": 0.5, "ticks": 4,
            "polls_total": 8, "poll_failures_total": 2 if stale else 0,
            "daemon": False,
            "workers": {
                "alpha": {"polls": 4, "failures": 0, "staleness_s": 0.05,
                          "stale": False, "rtt_s": 0.01,
                          "last_error": None, "error_rate": 0.0,
                          "queue_wait_p95_s": 0.2},
                "victim": {"polls": 4, "failures": 2 if stale else 0,
                           "staleness_s": 1.4 if stale else 0.06,
                           "stale": stale, "rtt_s": 0.01,
                           "last_error": ("ConnectionError: refused"
                                          if stale else None),
                           "error_rate": 1.0 if stale else 0.0,
                           "queue_wait_p95_s": None},
            },
            "fleet": {"queue_wait_p95_s": 0.2,
                      "error_rate": 0.5 if stale else 0.0,
                      "worker_stale_count": 1.0 if stale else 0.0},
        }

    @staticmethod
    def _snapshot_doc(stale=False):
        tail = 5.0 if stale else 0.1
        return {
            "schema": 1, "points": 512, "saved_t_mono": 100.0,
            "series": {
                "worker:alpha/staleness_s": [[t, 0.1] for t in range(8)],
                "worker:alpha/error_rate": [[t, 0.0] for t in range(8)],
                "worker:alpha/queue_wait_p95_s":
                    [[t, 0.2] for t in range(8)],
                "worker:victim/staleness_s":
                    [[t, 0.1] for t in range(6)] + [[6, tail], [7, tail]],
                "worker:victim/error_rate": [[t, 0.0] for t in range(8)],
                "fleet/queue_wait_p95_s": [[7, 0.2]],
                "fleet/error_rate": [[7, 0.0]],
                "fleet/worker_stale_count":
                    [[7, 1.0 if stale else 0.0]],
            },
        }

    def test_sparkline_shapes(self):
        import fed_report

        assert fed_report.sparkline([]) == "-"
        flat = fed_report.sparkline([1.0, 1.0, 1.0])
        assert flat == fed_report.SPARK[1] * 3
        ramp = fed_report.sparkline([0.0, 1.0])
        assert ramp[0] == fed_report.SPARK[0]
        assert ramp[-1] == fed_report.SPARK[-1]
        # trailing-window trim
        assert len(fed_report.sparkline(range(100))) == 16

    def test_build_summary_fleet_doc(self):
        import fed_report

        summary = fed_report.build_summary(self._fleet_doc(stale=True))
        assert summary["kind"] == "fleet"
        assert summary["stale_workers"] == ["victim"]
        assert summary["stale_after_s"] == 0.5
        by_name = {r["worker"]: r for r in summary["workers"]}
        assert not by_name["alpha"]["stale"]
        assert by_name["victim"]["error_rate"] == 1.0

    def test_build_summary_snapshot_doc(self):
        import fed_report

        summary = fed_report.build_summary(self._snapshot_doc(stale=True),
                                           stale_after_s=3.0)
        assert summary["kind"] == "snapshot"
        assert summary["stale_workers"] == ["victim"]
        by_name = {r["worker"]: r for r in summary["workers"]}
        # sparkline drawn from the staleness history
        assert len(by_name["victim"]["sparklines"]["staleness_s"]) == 8
        assert summary["fleet"]["worker_stale_count"] == 1.0

    def test_main_exit_codes(self, tmp_path, capsys):
        import fed_report

        clean = tmp_path / "fleet.json"
        clean.write_text(json.dumps(self._fleet_doc()))
        assert fed_report.main([str(clean)]) == 0
        out = capsys.readouterr().out
        assert "alpha" in out and "victim" in out

        assert fed_report.main([str(clean), "--json"]) == 0
        digest = json.loads(capsys.readouterr().out)
        assert digest["stale_workers"] == []

        stale = tmp_path / "stale.json"
        stale.write_text(json.dumps(self._fleet_doc(stale=True)))
        assert fed_report.main([str(stale)]) == 1
        err = capsys.readouterr().err
        assert "stale worker" in err and "victim" in err

        snap = tmp_path / "tsdb_snapshot.json"
        snap.write_text(json.dumps(self._snapshot_doc(stale=True)))
        assert fed_report.main([str(snap), "--stale-after", "3.0"]) == 1
        assert fed_report.main([str(snap), "--stale-after", "10.0"]) == 0

        (tmp_path / "garbage.json").write_text("{not json")
        assert fed_report.main([str(tmp_path / "garbage.json")]) == 2
        assert fed_report.main([str(tmp_path / "missing.json")]) == 2
        # a document that is neither summary nor snapshot
        (tmp_path / "other.json").write_text(json.dumps({"device": "cpu"}))
        assert fed_report.main([str(tmp_path / "other.json")]) == 2


class TestAotReport:
    """tools/aot_report.py: manifest rendering + divergence gate over
    the AOT artifact store (serving/aot.py)."""

    def _store(self, tmp_path):
        from stable_diffusion_webui_distributed_tpu.serving import (
            aot as aot_mod,
        )

        store = aot_mod.AotStore(str(tmp_path))
        store.save("('chunk', 'k1')", "d0=f32[1]", "chunk", b"exe-one")
        store.save("('encode', 'k2')", "d0=i32[77]", "encode", b"exe-two")
        return store

    def test_report_renders_cells_and_totals(self, tmp_path):
        import aot_report

        self._store(tmp_path)
        report = aot_report.build_report(str(tmp_path))
        assert report["ok"] and report["cell_count"] == 2
        assert report["by_kind"]["chunk"]["cells"] == 1
        assert report["total_bytes"] == len(b"exe-one") + len(b"exe-two")
        assert all(c["fingerprint_match"] for c in report["cells"])
        assert report["divergent"] == [] and report["orphans"] == []

    def test_exit_codes_gate_divergence(self, tmp_path, capsys):
        import aot_report

        store = self._store(tmp_path)
        assert aot_report.main(["--dir", str(tmp_path)]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["cell_count"] == 2

        # damage one artifact: content hash diverges -> rc 1
        (cell,) = [c for c in store.manifest()["cells"].values()
                   if c["kind"] == "chunk"]
        (tmp_path / cell["file"]).write_bytes(b"bit-flipped")
        assert aot_report.main(["--dir", str(tmp_path)]) == 1
        capsys.readouterr()

        # an unclaimed artifact on disk is divergence too
        (tmp_path / cell["file"]).write_bytes(b"exe-one")
        (tmp_path / "feedface.aotx").write_bytes(b"orphan")
        assert aot_report.main(["--dir", str(tmp_path)]) == 1
        capsys.readouterr()

        assert aot_report.main(["--dir",
                                str(tmp_path / "missing-root")]) == 2

    def test_output_file_and_fingerprint_mismatch_note(self, tmp_path,
                                                       capsys):
        import aot_report
        from stable_diffusion_webui_distributed_tpu.serving import (
            aot as aot_mod,
        )

        alien = aot_mod.AotStore(
            str(tmp_path), fingerprint={"jax": "elsewhere"})
        alien.save("('chunk', 'k1')", "d0=f32[1]", "chunk", b"exe")
        out_path = tmp_path / "aot.json"
        assert aot_report.main(["--dir", str(tmp_path),
                                "-o", str(out_path)]) == 0
        capsys.readouterr()
        report = json.loads(out_path.read_text())
        # coherent store, but the cell was built on another runtime:
        # the report flags it so an operator sees hydration will miss
        assert report["ok"]
        assert report["cells"][0]["fingerprint_match"] is False


class TestChunkHlo:
    """tools/chunk_hlo.py's reading of optimised HLO text (the compile for a
    described chip takes minutes and is not run here)."""

    HLO = """HloModule jit_run_chunk

%fused_computation.1 (p: bf16[2,8]) -> bf16[2,8] {
  ROOT %p = bf16[2,8]{1,0} parameter(0)
}

%fused_computation.2 (p: bf16[128,16,17,960], k: bf16[3,3,960,320]) -> bf16[128,16,17,320] {
  %p = bf16[128,16,17,960]{3,1,2,0:T(8,128)(2,1)} parameter(0)
  %k = bf16[3,3,960,320]{3,2,1,0:T(8,128)(2,1)} parameter(1)
  ROOT %conv.1 = bf16[128,16,17,320]{3,1,2,0:T(8,128)(2,1)} convolution(%p, %k), window={size=3x3 pad=1_1x1_1}, dim_labels=0b1f_01io->0b1f, metadata={op_name="jit(run_chunk)/while/body/closed_call/UNet/up_0_res_0/conv1/conv_general_dilated"}
}

%fused_computation.3 (p: bf16[2,32,32,1280], k: bf16[3,3,1280,1280]) -> (f32[2,1280], bf16[2,32,32,1280]) {
  %p = bf16[2,32,32,1280]{3,0,2,1:T(2,128)(2,1)} parameter(0)
  %k = bf16[3,3,1280,1280]{3,2,1,0:T(8,128)(2,1)} parameter(1)
  %conv.2 = bf16[2,32,32,1280]{3,0,2,1:T(2,128)(2,1)} convolution(%p, %k), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(run_chunk)/while/body/closed_call/UNet/up_2_res_0/conv2/conv_general_dilated"}
  %sum.3 = f32[2,1280]{1,0} reduce(%conv.2), dimensions={1,2}
  ROOT %both = (f32[2,1280]{1,0}, bf16[2,32,32,1280]{3,0,2,1:T(2,128)(2,1)}) tuple(%sum.3, %conv.2)
}

%body (arg: (f32[2])) -> (f32[2]) {
  %x = bf16[128,16,17,960]{3,1,2,0:T(8,128)(2,1)} parameter(0)
  %copy.1 = f32[128,16,17,960]{0,3,2,1:T(8,128)} copy(%x), metadata={op_name="jit(run_chunk)/while/body/closed_call/UNet/up_0_res_0/norm1/convert_element_type"}
  %broadcast.2 = f32[128,2,136,960]{0,3,2,1:T(8,128)} broadcast(%x), dimensions={1,3}, metadata={op_name="jit(run_chunk)/while/body/closed_call/UNet/up_0_res_0/norm1/mul"}
  %bitcast.3 = f32[128,16,17,960]{0,3,2,1:T(8,128)} bitcast(%broadcast.2)
  %fusion.4 = bf16[128,16,17,320]{3,1,2,0:T(8,128)(2,1)} fusion(%x), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(run_chunk)/while/body/closed_call/UNet/up_0_res_0/conv1/conv_general_dilated"}
  %fusion.5 = (f32[2,1280]{1,0}, bf16[2,32,32,1280]{3,0,2,1:T(2,128)(2,1)}) fusion(%x), kind=kOutput, calls=%fused_computation.3, metadata={op_name="jit(run_chunk)/while/body/closed_call/UNet/up_2_attn_0/norm/reduce_sum"}
  %pad.7 = bf16[2,36,32,1280]{3,0,2,1:T(2,128)(2,1)} pad(%x, %x), padding=0_0x2_2x0_0x0_0
  %small.6 = f32[2,960]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  ROOT %t = (f32[2]) tuple(%small.6)
}

ENTRY %main (a: f32[2]) -> f32[2] {
  ROOT %a = f32[2]{0} parameter(0)
}
"""

    def test_sums_the_body_by_op_and_dtype_and_names_plain_convolutions(self):
        import chunk_hlo

        out = chunk_hlo.summarise(self.HLO)
        each = 128 * 16 * 17 * 960 * 4 / 1e6
        assert out["outputs"][:2] == [
            ["copy", "f32", 1, round(each, 1)],
            ["broadcast", "f32", 1, round(each, 1)]]
        assert out["outputs"][2] == [
            "fusion:Output", "bf16", 1,
            round(128 * 16 * 17 * 320 * 2 / 1e6, 1)]
        assert not any(op == "bitcast" for op, *_ in out["outputs"])
        # read off the instruction, so a convolution whose fusion also
        # writes the next norm's sums (a tuple, under that norm's scope)
        # is found, and the spatial-major one is not
        assert out["plain_convolutions"] == [
            ["UNet/up_2_res_0/conv2/conv_general_dilated",
             "bf16[2,32,32,1280]"]]
        pad = round(2 * 36 * 32 * 1280 * 2 / 1e6, 1)
        assert out["float32_mb"] == round(2 * each, 1)
        assert out["pad_mb"] == out["two_row_tile_mb"] == pad
        scoped = chunk_hlo.summarise(self.HLO, scope=r"/norm1/")["outputs"]
        assert [row[0] for row in scoped] == ["copy", "broadcast"]

    @pytest.mark.parametrize("space,op,hbm,layout", [
        ("", "copy", 1, 1), ("S(1)", "copy", 0, 0),
        ("", "custom-call", 0, 0), ("", "slice-done", 0, 0),
        ("", "convolution", 1, 0)])
    def test_tells_hbm_from_on_chip_memory(self, space, op, hbm, layout):
        """``hbm_mb`` sums what lies outside ``S(1)`` (memory space decides
        what a copy costs: PR 65), kernels' results and the weights'
        asynchronous slices left out; ``hbm_layout_mb`` of it the ops that
        compute nothing. One more op of 20 MB beside the class's body."""
        import chunk_hlo

        one = 128 * 2 * 128 * 320 * 2 / 1e6
        line = (f"  %more.8 = bf16[128,2,128,320]{{3,0,2,1:T(8,128)(2,1){space}}}"
                f" {op}(%x)\n")
        base = chunk_hlo.summarise(self.HLO)
        out = chunk_hlo.summarise(
            self.HLO.replace("  %small.6 =", line + "  %small.6 ="))
        each = 128 * 16 * 17 * 960 * 4 / 1e6
        conv = 128 * 16 * 17 * 320 * 2 / 1e6
        pad = 2 * 36 * 32 * 1280 * 2 / 1e6
        assert base["hbm_mb"] == round(2 * each + conv + pad, 1)
        assert base["hbm_layout_mb"] == round(2 * each + pad, 1)
        assert out["hbm_mb"] == round(2 * each + conv + pad + hbm * one, 1)
        assert out["hbm_layout_mb"] == round(2 * each + pad + layout * one, 1)


class TestDecodeHlo:
    """tools/decode_hlo.py's count of a decode step's launches (the compile
    for a described chip is tests/test_chip_compile.py's)."""

    RULES = [{"class": "linear", "scope": "/(gate_proj|lm_head)/[^/]*$"},
             {"class": "expert",
              "scope": "/layers_[0-9]+/mlp/(?!shared_expert)"},
             {"class": "other"}]

    @staticmethod
    def line(name, op, scope):
        return (f'  %{name} = f32[1,8]{{1,0}} {op}(%x), metadata='
                f'{{op_name="jit(expand_decode_chunk)/{scope}"}}')

    def test_counts_the_expert_class_a_layer(self):
        import decode_hlo
        from benchmarks.readers.op_class_ms import classify

        body = "while/body/closed_call/DecoderLM/"
        text = "\n".join([
            self.line("fusion.1", "fusion", body + "layers_2/mlp/dot_general"),
            self.line("_route_call.3", "custom-call",
                      body + "layers_2/mlp/jit(_route_call)/pallas_call"),
            self.line("_call.4", "custom-call",
                      body + "layers_2/mlp/jit(_call)/pallas_call"),
            self.line("fusion.5", "fusion", body + "layers_10/mlp/dot_general"),
            self.line("fusion.6", "fusion",
                      body + "layers_10/mlp/shared_expert/gate_proj/dot"),
            self.line("fusion.7", "fusion", body + "layers_10/mlp/mul"),
            self.line("fusion.8", "fusion", body + "norm/mul"),
            # not a launch of the step: outside the body, or no launch
            self.line("fusion.9", "fusion", "DecoderLM/layers_2/mlp/mul"),
            self.line("bitcast.10", "bitcast", body + "layers_2/mlp/reshape"),
        ])
        launches, by_layer = decode_hlo.count_launches(
            text, self.RULES, classify)
        assert launches == {"expert": 5, "linear": 1, "other": 1}
        assert by_layer == [3, 2]       # layer 2, then layer 10

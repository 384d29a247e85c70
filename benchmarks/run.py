"""The benchmark's command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: seeded weights on the device, the engine behind the real HTTP
server, a warm-up of the cell's own shapes, then a window of requests on the
client's clock. ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics (a short profiled slice, then the rest of
the window untraced). The last stdout line is the result; everything else
(phases of set-up, checks, sample counts) is on earlier lines. A run that
finds no TPU, or too few chips, prints no result and exits non-zero.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()     # process start, as near as Python can say

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


class Phases:
    """The set-up's own clock: seconds of each phase, printed as they end."""

    def __init__(self, t0: float) -> None:
        self.last = t0
        self.seconds: dict[str, float] = {}

    def done(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self.last
        print(f"setup phase {name}: {now - self.last:.2f} s", flush=True)
        self.last = now


def response_ok(record, want_images: int) -> bool:
    if record.status != 200:
        return False
    try:
        out = record.parsed
        seeds = json.loads(out["info"])["all_seeds"]
    except (ValueError, KeyError):
        return False
    first = record.payload["seed"]
    return (len(out["images"]) == want_images
            and seeds == list(range(first, first + want_images)))


def spans_by_request(trace_json: dict) -> dict:
    """{request id: {span name: [seconds, ...]}} from /internal/trace.json"""
    out: dict = {}
    for event in trace_json.get("traceEvents", []):
        rid = event.get("args", {}).get("request_id")
        if rid is not None and event.get("ph") == "X":
            out.setdefault(rid, {}).setdefault(event["name"], []).append(
                event["dur"] / 1e6)
    return out


def run(args, root: str) -> int:
    sys.path.insert(0, root)
    from benchmarks.harness import files

    bench = files.Bench(root)
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    phases = Phases(T0)

    import jax

    from benchmarks.harness import checks, device, loadgen, serve, weights
    from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
        enable_compilation_cache,
    )

    chips = int(cell["chips"])
    dev = device.require(chips)
    print(f"device: {json.dumps(dev)}", flush=True)
    print(f"compile cache: {enable_compilation_cache()}", flush=True)
    counter = checks.CompileCounter()
    report = checks.Report()
    family = files.resolve_family(config)
    policy = files.resolve_policy(config)
    phases.done("imports")

    params = weights.family_params(family, policy.param_dtype,
                                   int(config["weight_seed"]))
    jax.block_until_ready(params)
    print(f"weights: {family.name} seed {config['weight_seed']}, "
          f"{weights.describe(params)}", flush=True)
    phases.done("weights")

    server, base = serve.start(family, params, policy, cell)
    del params          # the engine holds (and on a mesh, re-places) them
    phases.done("server")

    send = loadgen.Sender(base, traffic.get("route", "/sdapi/v1/txt2img"))
    generator = bench.load("generators", traffic["loop"])
    batch = int(traffic["payload"].get("batch_size", 1))
    width, height = traffic["payload"]["width"], traffic["payload"]["height"]
    try:
        # -- warm-up: the cell's own shapes, nothing else ----------------
        warm_source = loadgen.PayloadSource(traffic, int(config["weight_seed"]),
                                            "warm")
        warm = [send(warm_source.draw())
                for _ in range(int(cell.get("warmup_requests", 2)))]
        for i, rec in enumerate(warm):
            print(f"warm-up request {i}: HTTP {rec.status}, "
                  f"{rec.seconds:.3f} s", flush=True)
        phases.done("warmup")
        for rec in warm:
            report.check(f"{rec.request_id} answered 200 with {batch} "
                         "image(s) and the seeds asked",
                         response_ok(rec, batch))
        checks.check_images(report, "warm-up", warm[0].parsed["images"],
                            width, height)
        source = loadgen.PayloadSource(traffic, args.seed, "w")
        status0 = loadgen.get_json(base, "/internal/status")
        xla0 = counter.counts["executables"]
        phases.done("checks")
        setup_s = time.perf_counter() - T0

        # -- the window ----------------------------------------------------
        records, trace_summary, slice_s = [], None, 0.0
        window_t0 = time.perf_counter()
        if args.trace:
            from benchmarks.harness import trace_reduce

            spec = cell.get("trace", {})
            per_request = warm[-1].seconds
            n = max(1, min(int(spec.get("requests", 3)),
                           int(spec.get("max_seconds", 8.0) / per_request)))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
                jax.profiler.start_trace(tdir, profiler_options=options)
                send.traced = True
                try:
                    records += generator.run(traffic, send, source.draw,
                                             max_requests=n)
                finally:
                    send.traced = False
                    jax.profiler.stop_trace()
                t_red = time.perf_counter()
                xplane = trace_reduce.find_xplane(tdir)
                size = os.path.getsize(xplane)
                trace_summary = trace_reduce.reduce(xplane)
            slice_s = records[-1].end - records[0].start
            print(f"traced slice: {n} request(s), {slice_s:.3f} s, "
                  f"{size / 2**20:.1f} MiB, {trace_summary['events']} "
                  f"device events, reduced in "
                  f"{time.perf_counter() - t_red:.2f} s", flush=True)
        left = args.seconds - (time.perf_counter() - window_t0)
        if left > 0:
            records += generator.run(traffic, send, source.draw,
                                     seconds=left)
        window_s = records[-1].end - records[0].start

        # -- after the window: deltas, repeat, checks -----------------------
        status1 = loadgen.get_json(base, "/internal/status")
        xla = counter.counts["executables"] - xla0
        builds = (sum(status1["serving"]["compiles"].values())
                  - sum(status0["serving"]["compiles"].values()))
        report.check("nothing compiled inside the window",
                     xla == 0 and builds == 0,
                     f"{builds} stage builds, {xla} XLA executables made")
        good = [r for r in records if response_ok(r, batch)]
        failed = len(records) - len(good)
        report.check("every request answered 200 with its images and seeds",
                     not failed, f"{failed} of {len(records)} failed")
        repeat = send(dict(warm[0].payload, request_id="repeat"))
        report.check("the first warm-up request sent again is "
                     "byte-identical",
                     repeat.status == 200 and repeat.parsed["images"]
                     == warm[0].parsed["images"])
        if len(good) >= 2:
            a, b = (checks.decode_png(r.parsed["images"][0])
                    for r in good[:2])
            report.check("two seeds give different images",
                         checks.image_distance(a, b)[0] > 0)
        if good:
            checks.check_images(report, "window sample",
                                good[-1].parsed["images"][:1], width,
                                height)
        memory = device.memory(chips)
        if chips > 1 and (dev["platform"] == "tpu" or any(memory)):
            report.check(
                f"every one of the {chips} devices holds bytes",
                all(m.get("bytes_in_use") for m in memory),
                ", ".join(str(m.get("bytes_in_use")) for m in memory))
        spans = spans_by_request(loadgen.get_json(base,
                                                  "/internal/trace.json"))
    finally:
        server.stop()

    seconds = [r.seconds for r in good]
    images = batch * len(good)
    print(f"window: {len(records)} requests, {len(good)} good, {images} "
          f"images in {window_s:.3f} s; samples {len(seconds)}", flush=True)
    print("setup split: " + json.dumps(
        {k: round(v, 3) for k, v in phases.seconds.items()}), flush=True)
    print("XLA: " + json.dumps(counter.counts), flush=True)

    values: dict[str, float] = {}
    if not args.trace:
        measured = {
            "setup_s": setup_s,
            "request_p50_s": loadgen.quantile(seconds, 0.5),
            "request_p90_s": loadgen.quantile(seconds, 0.9),
            "images_per_s": images / window_s,
        }
        for metric in bench.metrics("end_to_end", cell["name"]):
            values[metric["name"]] = (measured[metric["name"]],
                                      metric["unit"])
    else:
        peaks = files.read_json(bench.path("harness", "peaks.json"))
        table = dict(peaks["kinds"])
        if dev["platform"] != "tpu":        # only a test gets here
            table.update(peaks["rehearsal"]["kinds"])
        if dev["kind"] not in table:
            raise SystemExit(f"no peak for device kind {dev['kind']!r} in "
                             "benchmarks/harness/peaks.json")
        context = {
            "records": good, "spans": spans, "trace": trace_summary,
            "slice_s": slice_s, "family": family, "chips": chips,
            "peak": table[dev["kind"]], "memory": memory,
            "status_before": status0, "status_after": status1,
        }
        for metric in bench.metrics("per_layer", cell["name"]):
            spec = bench.layer_metric(metric["name"])
            reader = bench.load("readers", spec["reader"])
            value = reader.read(context, **spec.get("args", {}))
            if value is not None:
                values[metric["name"]] = (value, metric["unit"])

    dev_out = dict(dev, memory_peak_bytes=max(
        (m.get("peak_bytes_in_use", 0) for m in memory), default=0))
    result = {
        "correct": not report.failed,
        "attempted": len(records), "failed": failed,
        "metrics": {k: {"value": v, "unit": u}
                    for k, (v, u) in values.items()},
        "device": dev_out,
    }
    if args.trace:
        dev_out["busy_s"] = trace_summary["busy_s"]
        dev_out["window_s"] = slice_s
        result["breakdown"] = {
            "device_ops": trace_summary["device_ops"][:10],
            "idle_gaps": trace_summary["idle_gaps"][:10]}
    if report.failed:
        print("failed checks: " + "; ".join(report.failed), flush=True)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


def main(argv=None, root: str | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run(args, root or os.path.dirname(HERE))
    except Exception:   # reported, never passed over: no result line
        traceback.print_exc()
        sys.stderr.flush()
        return 1


if __name__ == "__main__":
    sys.exit(main())

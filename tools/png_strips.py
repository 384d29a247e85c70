"""How the PNG encode scales over this host's cores (PERF.md section 6, PR 32).

    python3 tools/png_strips.py --fetch sdxl_solo [--fetch sd15_expand_solo]
    python3 tools/png_strips.py [image.npy ...]

``--fetch <cell>`` serves one request of a benchmark cell on the chip (the
harness's own weights, server and payload) and keeps the decoded pixels in
``chiprun_out/png_strips/<cell>.npy``: the noise-like image that random
weights draw (timed interleaved and, as ``<cell>_planes``, as the three
planes in which a TPU hands it to the host). One process holds the chip, so each fetch is a process of its
own, started by this one, which stays off JAX.

Without ``--fetch`` it times the encode of every image given (plus a smooth
seeded field, ``benchmarks/harness/loadgen.py:seeded_png``'s, and a
photograph, matplotlib's ``grace_hopper.jpg`` mirrored to 1024x1024) two
ways and writes ``chiprun_out/png_strips/scaling.json``:

- ``proto``: the construction in plain Python, K given: K threads, each
  building its strip's filter-0 scanlines and deflating them raw at level 6
  (``zlib`` releases the GIL) from the 32 KiB before it as dictionary, a
  sync flush between strips, one Adler-32.
  It needs nothing of ``native/png_encoder.cpp`` and is what chose the
  encoder's floor and cap;
- ``lib``: ``runtime/native.py:encode_png`` with this process held to K
  cores (``sched_setaffinity``): the encoder takes its K from the cores it
  may run on and from the image's bytes, so that is the only way to ask it
  for a K, and the K it reports is in the row. ``rested_p50_ms`` is the
  same call after 0.3 s of sleep: what a served encode meets.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import struct
import subprocess
import sys
import threading
import time
import zlib

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "chiprun_out", "png_strips")
KS = (1, 2, 3, 4, 6, 8, 12, 16)
REPEATS = 9
WINDOW = 32768


def fetch(cell_name: str, seed: int) -> None:
    """One request of ``cell_name`` through the benchmark's own set-up."""
    sys.path.insert(0, REPO)
    from benchmarks.harness import (
        checks, device, files, loadgen, serve, weights,
    )
    from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
        enable_compilation_cache,
    )

    bench = files.Bench(REPO)
    cell = bench.cell(cell_name)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    print("device:", device.require(int(cell["chips"])), flush=True)
    enable_compilation_cache()
    family = files.resolve_family(config)
    policy = files.resolve_policy(config)
    params = weights.family_params(bench.components(config), family,
                                   policy.param_dtype,
                                   int(config["weight_seed"]))
    server, base = serve.start(family, params, policy, cell)
    del params
    try:
        send = loadgen.Sender(base, traffic.get("route",
                                                "/sdapi/v1/txt2img"))
        rec = send(loadgen.PayloadSource(traffic, seed, "w").draw())
    finally:
        server.stop()
    img = checks.decode_png(rec.parsed["images"][0])
    os.makedirs(OUT, exist_ok=True)
    np.save(os.path.join(OUT, cell_name + ".npy"), img)
    print(f"{cell_name}: HTTP {rec.status}, {rec.seconds:.2f} s, "
          f"image {img.shape}", flush=True)


def scanlines(img: np.ndarray, lo: int = 0, hi: int | None = None) -> bytes:
    """Rows ``lo:hi`` as PNG scanlines of filter 0 (a zero before each)."""
    rows = img[lo:hi]
    out = np.zeros((rows.shape[0], rows.shape[1] * rows.shape[2] + 1),
                   np.uint8)
    out[:, 1:] = rows.reshape(rows.shape[0], -1)
    return out.tobytes()


def proto_zlib_stream(img: np.ndarray, k: int, level: int = 6) -> bytes:
    """One zlib stream of the image's scanlines, deflated as ``k`` strips
    of whole rows on ``k`` threads; every strip but the first starts from
    the 32 KiB of scanlines before it as its dictionary (pigz's way)."""
    h = img.shape[0]
    stride = img.shape[1] * img.shape[2] + 1
    back = -(-WINDOW // stride)             # rows that hold 32 KiB
    bounds = [h * i // k for i in range(k + 1)]
    parts: list = [None] * k
    sums: list = [None] * k

    def work(i: int) -> None:
        lo, hi = bounds[i], bounds[i + 1]
        first = max(0, lo - back)
        raw = scanlines(img, first, hi)
        own = (lo - first) * stride
        co = zlib.compressobj(level, zlib.DEFLATED, -15,
                              **({"zdict": raw[max(0, own - WINDOW):own]}
                                 if own else {}))
        body = memoryview(raw)[own:]
        parts[i] = co.compress(body) + co.flush(
            zlib.Z_FINISH if i == k - 1 else zlib.Z_SYNC_FLUSH)
        sums[i] = body

    threads = [threading.Thread(target=work, args=(i,)) for i in range(1, k)]
    for t in threads:
        t.start()
    work(0)
    for t in threads:
        t.join()
    adler = 1
    for body in sums:
        adler = zlib.adler32(body, adler)
    # the header compress2 writes at level 6: CM 8, 32 KiB window, FLEVEL 2
    return b"\x78\x9c" + b"".join(parts) + struct.pack(">I", adler)


def times_ms(fn, rest: float = 0.0, repeats: int = REPEATS) -> list:
    """Milliseconds of each of ``repeats`` calls; with ``rest``, the process
    sleeps that long before each (a served encode finds the other cores
    asleep, a loop of encodes does not)."""
    out = []
    for _ in range(repeats):
        time.sleep(rest)
        t0 = time.perf_counter()
        fn()
        out.append((time.perf_counter() - t0) * 1e3)
    return out


def summary(ms: list) -> dict:
    q = statistics.quantiles(ms, n=4)
    return {"min_ms": min(ms), "p50_ms": statistics.median(ms),
            "q1_ms": q[0], "q3_ms": q[2]}


def images(paths: list) -> dict:
    from PIL import Image

    sys.path.insert(0, REPO)
    from benchmarks.harness import checks, loadgen

    out = {}
    for path in paths:
        name = os.path.splitext(os.path.basename(path))[0]
        out[name] = np.ascontiguousarray(np.load(path))
        # as the engine holds it: a TPU hands the host three planes
        out[name + "_planes"] = np.moveaxis(
            np.ascontiguousarray(np.moveaxis(out[name], -1, 0)), 0, -1)
    for side in (1024, 512, 256, 128):
        out[f"smooth_{side}"] = checks.decode_png(
            loadgen.seeded_png(32, side, side))
    try:
        from matplotlib import cbook

        photo = np.asarray(Image.open(cbook.get_sample_data(
            "grace_hopper.jpg", asfileobj=False)).convert("RGB"))[:512, :512]
        wide = np.concatenate([photo, photo[:, ::-1]], axis=1)
        out["photo_1024"] = np.ascontiguousarray(
            np.concatenate([wide, wide[::-1]], axis=0))
        out["photo_512"] = np.ascontiguousarray(photo)
    except Exception as e:      # no matplotlib here: the table says so
        print(f"no photograph: {e}", flush=True)
    return out


def scale(paths: list) -> None:
    sys.path.insert(0, REPO)
    from stable_diffusion_webui_distributed_tpu.runtime import native

    cores = sorted(os.sched_getaffinity(0))
    host = {"cores": len(cores), "cpu_count": os.cpu_count(),
            "loadavg": os.getloadavg(),
            "encoder": native.active_encoder()}
    with open("/proc/cpuinfo") as fh:
        models = {line.split(":", 1)[1].strip() for line in fh
                  if line.startswith("model name")}
    host["model"] = sorted(models)
    print("host:", json.dumps(host), flush=True)
    rows = []
    for name, img in images(paths).items():
        raw = scanlines(img)
        one = None
        for k in KS:
            if k > img.shape[0]:
                continue
            stream = proto_zlib_stream(img, k)
            assert zlib.decompress(stream) == raw, (name, k)
            row = {"image": name, "how": "proto", "k": k,
                   "shape": list(img.shape), "raw_bytes": len(raw),
                   "zlib_bytes": len(stream),
                   **summary(times_ms(lambda: proto_zlib_stream(img, k)))}
            if k == 1:
                assert stream == zlib.compress(raw, 6), name
                one = row
            row["speedup"] = one["p50_ms"] / row["p50_ms"]
            row["size_vs_one"] = len(stream) / one["zlib_bytes"]
            rows.append(row)
            print(json.dumps(row), flush=True)
        for k in KS:
            if k > len(cores):
                continue
            os.sched_setaffinity(0, set(cores[:k]))
            try:
                got = native.encode_png(img)
                ms = times_ms(lambda: native.encode_png(img))
                rested = times_ms(lambda: native.encode_png(img), rest=0.3,
                                  repeats=5)
            finally:
                os.sched_setaffinity(0, set(cores))
            if got is None:
                break
            png, strips = got
            row = {"image": name, "how": "lib", "cores": k,
                   "strips": strips, "png_bytes": len(png), **summary(ms),
                   "rested_p50_ms": statistics.median(rested)}
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "scaling.json"), "w") as fh:
        json.dump({"host": host, "rows": rows}, fh, indent=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fetch", action="append", default=[],
                    help="benchmark cell to serve one image of (chip)")
    ap.add_argument("--fetch-here", help=argparse.SUPPRESS)
    ap.add_argument("--seed", type=int, default=2_400_000_032)
    ap.add_argument("images", nargs="*", help=".npy files of (H, W, 3) "
                    "uint8 pixels; the fetched ones are added")
    args = ap.parse_args(argv)
    if args.fetch_here:
        fetch(args.fetch_here, args.seed)
        return 0
    paths = list(args.images)
    for cell in args.fetch:
        rc = subprocess.run([sys.executable, os.path.abspath(__file__),
                             "--fetch-here", cell, "--seed",
                             str(args.seed)]).returncode
        if rc:
            return rc
        paths.append(os.path.join(OUT, cell + ".npy"))
    scale(paths)
    return 0


if __name__ == "__main__":
    sys.exit(main())

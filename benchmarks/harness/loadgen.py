"""The load generator's core: payloads drawn from a traffic file and a
seed, one HTTP request timed on the client's clock, and the record kept of
it. The loop that decides WHEN to send lives in generators/<loop>.py.
"""

from __future__ import annotations

import base64
import dataclasses
import functools
import io
import json
import random
import time
import urllib.error
import urllib.request


@dataclasses.dataclass
class Record:
    request_id: str
    payload: dict
    start: float            # perf_counter when the request was sent
    end: float              # ... when the whole body had been read
    status: int
    body: bytes
    traced: bool = False    # sent while the profiler was running
    due: float | None = None  # open loops: when it should have been sent

    @property
    def seconds(self) -> float:
        return self.end - (self.start if self.due is None else self.due)

    @functools.cached_property
    def parsed(self) -> dict:
        return json.loads(self.body)


def seeded_png(seed: int, width: int, height: int) -> str:
    """A base64 PNG made from ``seed``: a smooth colour field (a 16x16
    random grid, bicubic) under mild pixel noise, so the file has a photo's
    size and not a noise image's."""
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(seed)
    grid = Image.fromarray(rng.integers(0, 256, (16, 16, 3), dtype=np.uint8))
    field = np.asarray(grid.resize((width, height), Image.BICUBIC), np.int16)
    noisy = field + rng.integers(-6, 7, field.shape, dtype=np.int16)
    out = io.BytesIO()
    Image.fromarray(np.clip(noisy, 0, 255).astype(np.uint8)).save(
        out, format="PNG")
    return base64.b64encode(out.getvalue()).decode()


class PayloadSource:
    """Draws request bodies from a traffic file's ``payload`` (fixed
    fields), ``cycle`` (per-field lists every seed walks in full, each in
    its own order, so a seed reorders the work and never changes it) and
    ``init_image_pool`` (img2img: that many seeded PNGs of the payload's
    size, made once here and walked in turn). Every request gets its own
    image seed, drawn from the run's seed."""

    def __init__(self, traffic: dict, seed: int, tag: str) -> None:
        self.fixed = dict(traffic["payload"])
        self.rng = random.Random(seed)
        self.tag = tag
        self.count = 0
        self.cycles = {}
        for field, values in sorted(traffic.get("cycle", {}).items()):
            order = list(values)
            self.rng.shuffle(order)
            self.cycles[field] = order
        pool = int(traffic.get("init_image_pool", 0))
        if pool:
            first = self.rng.randrange(2 ** 31)
            self.cycles["init_images"] = [
                [seeded_png(first + i, self.fixed["width"],
                            self.fixed["height"])] for i in range(pool)]

    def draw(self) -> dict:
        body = dict(self.fixed)
        for field, order in self.cycles.items():
            body[field] = order[self.count % len(order)]
        body["seed"] = self.rng.randrange(1, 2 ** 31 - 64)
        body["request_id"] = f"{self.tag}-{self.count}"
        self.count += 1
        return body


class Sender:
    """POSTs one payload and times it until the body is read."""

    def __init__(self, base_url: str, route: str = "/sdapi/v1/txt2img",
                 timeout: float = 1100.0) -> None:
        self.url = base_url + route
        self.timeout = timeout
        self.traced = False

    def __call__(self, payload: dict, due: float | None = None) -> Record:
        data = json.dumps(payload).encode()
        req = urllib.request.Request(
            self.url, data=data,
            headers={"Content-Type": "application/json"})
        start = time.perf_counter()
        try:
            with urllib.request.urlopen(req, timeout=self.timeout) as resp:
                status, body = resp.status, resp.read()
        except urllib.error.HTTPError as err:
            status, body = err.code, err.read()
        end = time.perf_counter()
        return Record(payload["request_id"], payload, start, end, status,
                      body, traced=self.traced, due=due)


def get_json(base_url: str, route: str) -> dict:
    with urllib.request.urlopen(base_url + route, timeout=120) as resp:
        return json.loads(resp.read())


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile of a non-empty list (numpy's default
    rule, written out so the yardstick does not move with a library)."""
    ordered = sorted(values)
    pos = q * (len(ordered) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)

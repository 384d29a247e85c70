"""What decides ``correct``. Copied in spirit from chip_smoke.py's Report,
CompileCounter, check_images and image_distance; that file stays the smoke.
"""

from __future__ import annotations

import base64
import io


class Report:
    """Prints every check as it happens and remembers what failed."""

    def __init__(self) -> None:
        self.failed: list[str] = []

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"check {'ok  ' if ok else 'FAIL'} {name}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok


class CompileCounter:
    """What XLA really did, from JAX's monitoring events: executables made
    (compiled, or loaded from the persistent cache), cache hits and misses."""

    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }
    _EXECUTABLE = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.counts = {"executables": 0, "cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        key = self._EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == self._EXECUTABLE:
            self.counts["executables"] += 1


def decode_png(b64: str):
    """A base64 PNG as a uint8 array (PIL; the program's decoder is not
    the yardstick)."""
    import numpy as np
    from PIL import Image

    return np.asarray(Image.open(io.BytesIO(base64.b64decode(b64)))
                      .convert("RGB"))


def check_images(report: Report, name: str, b64_images, width: int,
                 height: int) -> list:
    """Decoded PNGs have the requested size and pixels that a broken
    pipeline does not produce: a NaN latent decodes to one flat value."""
    import numpy as np

    images = [decode_png(s) for s in b64_images]
    for i, img in enumerate(images):
        flat = img.astype(np.float64)
        saturated = float(np.mean((img == 0) | (img == 255)))
        report.check(
            f"{name} image {i} is {height}x{width}x3 uint8, not constant",
            img.shape == (height, width, 3) and img.dtype == np.uint8
            and float(flat.std()) > 1.0 and saturated < 0.5,
            f"mean {flat.mean():.1f} std {flat.std():.1f} "
            f"saturated {saturated:.3f}")
    return images


def image_distance(a, b) -> tuple[int, float]:
    """(largest pixel difference in levels, PSNR in dB; 99 when identical)."""
    import numpy as np

    diff = a.astype(np.float64) - b.astype(np.float64)
    mse = float(np.mean(diff ** 2))
    psnr = 99.0 if mse == 0 else float(10.0 * np.log10(255.0 ** 2 / mse))
    return int(np.max(np.abs(diff))), psnr

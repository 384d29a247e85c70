"""Native PNG encoder tests: build, correctness vs PIL decode, the strips
an image is deflated as and the one zlib stream they make, fallback."""

import contextlib
import io
import os
import struct
import threading
import zlib

import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.runtime import native
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    array_to_b64png, b64png_to_array, encode_b64png,
)

RNG = np.random.default_rng(11)

#: native/png_encoder.cpp's STRIP_FLOOR and STRIP_CAP (PERF.md section 6,
#: PR 32, has the scaling they were chosen from)
STRIP_FLOOR = 48 * 1024
STRIP_CAP = 8

PNG_SIGNATURE = b"\x89PNG\r\n\x1a\n"


def encode(img, level=6):
    got = native.encode_png(img, level)
    if got is None:
        pytest.skip("native toolchain unavailable")
    return got


def scanlines(img) -> bytes:
    """Filter 0: a zero byte, then the row."""
    rows = img.reshape(img.shape[0], -1)
    return np.concatenate(
        [np.zeros((rows.shape[0], 1), np.uint8), rows], axis=1).tobytes()


def expected_strips(img) -> int:
    """The rule of png_encoder.cpp:plan_strips, for the cores this test may
    run on."""
    return max(1, min(len(scanlines(img)) // STRIP_FLOOR, STRIP_CAP,
                      len(os.sched_getaffinity(0)), img.shape[0]))


def chunks(png: bytes) -> list:
    """[(type, data)] of a PNG file, every CRC checked."""
    assert png[:8] == PNG_SIGNATURE
    out, at = [], 8
    while at < len(png):
        (n,) = struct.unpack(">I", png[at:at + 4])
        kind, data = png[at + 4:at + 8], png[at + 8:at + 8 + n]
        (crc,) = struct.unpack(">I", png[at + 8 + n:at + 12 + n])
        assert crc == zlib.crc32(kind + data), kind
        out.append((kind, data))
        at += 12 + n
    assert at == len(png)
    return out


def one_strip_file(img, level=6) -> bytes:
    """The file the encoder wrote before it had strips: compress2 over all
    scanlines in one IDAT."""
    def chunk(kind, data):
        return (struct.pack(">I", len(data)) + kind + data
                + struct.pack(">I", zlib.crc32(kind + data)))

    h, w, c = img.shape
    ihdr = struct.pack(">IIBBBBB", w, h, 8, 2 if c == 3 else 6, 0, 0, 0)
    return (PNG_SIGNATURE + chunk(b"IHDR", ihdr)
            + chunk(b"IDAT", zlib.compress(scanlines(img), level))
            + chunk(b"IEND", b""))


def noise(shape):
    return RNG.integers(0, 256, shape, np.uint8)


def smooth(side: int):
    """A smooth colour field under mild pixel noise: a photo's size of file,
    not a noise image's (benchmarks/harness/loadgen.py:seeded_png's)."""
    from PIL import Image

    rng = np.random.default_rng(32)
    grid = Image.fromarray(rng.integers(0, 256, (16, 16, 3), dtype=np.uint8))
    field = np.asarray(grid.resize((side, side), Image.BICUBIC), np.int16)
    noisy = field + rng.integers(-6, 7, field.shape, dtype=np.int16)
    return np.clip(noisy, 0, 255).astype(np.uint8)


@contextlib.contextmanager
def one_core():
    """This thread held to one of its CPUs: the encoder sees one core and
    deflates any image as one strip."""
    before = os.sched_getaffinity(0)
    os.sched_setaffinity(0, {min(before)})
    try:
        yield
    finally:
        os.sched_setaffinity(0, before)


#: (shape, strips on a host with cores to spare): RGB and RGBA at one
#: strip, two and the cap; rows the strips do not divide; fewer rows than
#: the cap; one row
SHAPES = {
    "rgb-small": ((48, 64, 3), 1),
    "rgba-small": ((16, 16, 4), 1),
    "rgb-two": ((128, 341, 3), 2),
    "rgba-two": ((130, 255, 4), 2),
    "rgb-cap": ((1024, 1024, 3), STRIP_CAP),
    "rgba-cap": ((512, 512, 4), STRIP_CAP),
    "rows-1023": ((1023, 777, 3), STRIP_CAP),
    "rows-under-cap": ((5, 100_000, 3), 5),
    "one-row": ((1, 300_000, 3), 1),
    "one-row-tiny": ((1, 5, 3), 1),
}


class TestNativePng:
    def test_roundtrip_via_pil(self):
        img = RNG.integers(0, 256, (48, 64, 3), np.uint8)
        data, _ = encode(img)
        from PIL import Image

        decoded = np.asarray(Image.open(io.BytesIO(data)).convert("RGB"))
        np.testing.assert_array_equal(decoded, img)

    def test_rgba(self):
        img = RNG.integers(0, 256, (16, 16, 4), np.uint8)
        data, _ = encode(img)
        from PIL import Image

        decoded = np.asarray(Image.open(io.BytesIO(data)))
        np.testing.assert_array_equal(decoded, img)

    def test_invalid_inputs_return_none(self):
        assert native.encode_png(np.zeros((4, 4), np.uint8)) is None
        assert native.encode_png(np.zeros((4, 4, 3), np.float32)) is None

    def test_payload_helper_roundtrip(self):
        # whichever path (native or PIL) serves array_to_b64png, the wire
        # format must decode back to the same pixels
        img = RNG.integers(0, 256, (32, 32, 3), np.uint8)
        b64 = array_to_b64png(img)
        np.testing.assert_array_equal(b64png_to_array(b64), img)

    def test_pil_fallback_says_one_strip(self, monkeypatch):
        monkeypatch.setattr(native, "encode_png", lambda *a, **k: None)
        img = noise((1024, 512, 3))
        b64, strips = encode_b64png(img)
        assert strips == 1
        np.testing.assert_array_equal(b64png_to_array(b64), img)
        assert array_to_b64png(img) == b64


class TestStrips:
    @pytest.mark.parametrize("case", sorted(SHAPES))
    def test_one_stream_of_every_scanline(self, case):
        """PIL reads the pixels back; the file is IHDR, one IDAT, IEND; the
        IDAT is ONE zlib stream of the filter-0 scanlines (zlib checks the
        Adler-32 and needs the last block's final bit); and the strips are
        what the image's bytes, its rows and this host's cores give."""
        from PIL import Image

        shape, with_cores = SHAPES[case]
        img = noise(shape)
        assert expected_strips(img) == min(with_cores,
                                           len(os.sched_getaffinity(0)))
        data, strips = encode(img)
        assert strips == expected_strips(img)
        decoded = np.asarray(Image.open(io.BytesIO(data)))
        np.testing.assert_array_equal(decoded, img)
        kinds = chunks(data)
        assert [k for k, _ in kinds] == [b"IHDR", b"IDAT", b"IEND"]
        stream = zlib.decompressobj()
        assert stream.decompress(kinds[1][1]) == scanlines(img)
        assert stream.eof and not stream.unused_data

    @pytest.mark.parametrize("view", ["planes", "planes-rgba", "crop",
                                      "mirrored", "planes-small"])
    def test_pixels_as_they_lie(self, view):
        """The array goes to the encoder with its strides: the three planes
        a TPU hands the host (channel the slowest axis), a crop of a wider
        image, a view walked backwards. The file is the one of the same
        pixels laid out afresh."""
        if view.startswith("planes"):
            h, w = (64, 48) if view.endswith("small") else (512, 640)
            c = 4 if view.endswith("rgba") else 3
            img = np.moveaxis(noise((c, h, w)), 0, -1)
            assert img.strides == (w, 1, h * w)
        elif view == "crop":
            img = noise((600, 700, 3))[7:519, 11:651]
        else:
            img = noise((512, 640, 3))[::-1, ::-1]
        assert not img.flags["C_CONTIGUOUS"]
        data, strips = encode(img)
        assert strips == expected_strips(img)
        assert (data, strips) == encode(np.ascontiguousarray(img))
        assert zlib.decompress(chunks(data)[1][1]) == scanlines(
            np.ascontiguousarray(img))

    @pytest.mark.parametrize("level", [1, 4, 6, 9])
    def test_level_is_the_callers(self, level):
        """The stream's header names the level's class as deflateInit's
        does, and every strip is deflated at that level."""
        img = smooth(512)
        data, _ = encode(img, level)
        idat = chunks(data)[1][1]
        assert idat[:2] == zlib.compress(b"", level)[:2]
        assert zlib.decompress(idat) == scanlines(img)
        with one_core():
            assert encode(img, level)[0] == one_strip_file(img, level)

    @pytest.mark.parametrize("case", ["rgb-small", "rgba-small", "rgb-two",
                                      "rgb-cap", "rows-1023", "one-row"])
    def test_one_strip_is_the_old_file(self, case):
        """At K = 1 (a small image, or one core to run on) the bytes are the
        ones compress2 over the whole image gave."""
        img = noise(SHAPES[case][0])
        with one_core():
            data, strips = encode(img)
        assert strips == 1
        assert data == one_strip_file(img)

    @pytest.mark.parametrize("kind", ["noise", "smooth"])
    def test_joins_cost_under_half_a_percent(self, kind):
        img = noise((1024, 1024, 3)) if kind == "noise" else smooth(1024)
        data, _ = encode(img)
        one = len(one_strip_file(img))
        assert abs(len(data) - one) <= 0.005 * one

    def test_four_threads_at_once(self):
        imgs = [noise((512, 768, 3)), smooth(512), noise((700, 300, 4)),
                noise((48, 64, 3))]
        out = [None] * len(imgs)

        def work(i):
            for _ in range(3):
                out[i] = native.encode_png(imgs[i])

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(len(imgs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if out[0] is None:
            pytest.skip("native toolchain unavailable")
        from PIL import Image

        for img, (data, _) in zip(imgs, out):
            np.testing.assert_array_equal(
                np.asarray(Image.open(io.BytesIO(data))), img)

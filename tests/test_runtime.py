"""Core runtime tests: config persistence/migration, RNG seed contract, logging."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.runtime import rng
from stable_diffusion_webui_distributed_tpu.runtime.config import (
    BenchmarkPayload,
    ConfigModel,
    WorkerModel,
    load_config,
    save_config,
)
from stable_diffusion_webui_distributed_tpu.runtime.logging import (
    configure,
    get_ring_buffer,
)


class TestConfig:
    def test_defaults_match_reference_schema(self):
        cfg = ConfigModel()
        # Reference defaults: pmodels.py:42 job_timeout=3; shared.py:67-77 payload.
        assert cfg.job_timeout == 3
        bp = cfg.benchmark_payload
        assert bp.prompt.startswith("A herd of cows")
        assert (bp.width, bp.height, bp.steps, bp.batch_size) == (512, 512, 20, 1)

    def test_roundtrip(self, tmp_path):
        path = str(tmp_path / "cfg.json")
        cfg = ConfigModel(
            workers=[{"slice0": WorkerModel(address="10.0.0.2", avg_ipm=12.5)}],
            job_timeout=7,
        )
        save_config(cfg, path)
        loaded = load_config(path)
        assert loaded.job_timeout == 7
        assert loaded.workers[0]["slice0"].avg_ipm == 12.5

    def test_missing_file_yields_defaults(self, tmp_path):
        cfg = load_config(str(tmp_path / "nope.json"))
        assert cfg == ConfigModel()

    def test_corrupt_file_quarantined(self, tmp_path):
        path = str(tmp_path / "cfg.json")
        with open(path, "w") as f:
            f.write("{not json")
        cfg = load_config(path)
        assert cfg == ConfigModel()
        assert not os.path.exists(path)  # moved aside
        assert any("corrupt" in p for p in os.listdir(tmp_path))

    def test_legacy_list_migration(self, tmp_path):
        path = str(tmp_path / "workers.json")
        with open(path, "w") as f:
            json.dump([{"label": "gpu1", "address": "host1", "port": 7861}], f)
        cfg = load_config(path)
        assert cfg.workers[0]["gpu1"].address == "host1"

    def test_legacy_list_with_bad_entry_quarantined(self, tmp_path):
        # A non-dict entry in a legacy list must quarantine, not crash
        # (ADVICE r1: migration was outside the try/except).
        path = str(tmp_path / "workers.json")
        with open(path, "w") as f:
            json.dump([{"label": "ok", "address": "host1"}, "not-a-dict"], f)
        cfg = load_config(path)
        assert cfg == ConfigModel()
        assert any("invalid" in p for p in os.listdir(tmp_path))

    def test_reference_format_config_accepted(self, tmp_path):
        # A reference-era distributed-config.json carries worker fields this
        # schema doesn't define (`state`) and the -1 pixel_cap sentinel
        # (reference pmodels.py:12-34). It must load, not quarantine
        # (VERDICT r1 weak #5).
        path = str(tmp_path / "cfg.json")
        ref_cfg = {
            "workers": [
                {
                    "laptop": {
                        "address": "192.168.1.3",
                        "port": 7860,
                        "avg_ipm": 4.2,
                        "master": False,
                        "eta_percent_error": [1.5, -2.0],
                        "user": None,
                        "password": None,
                        "tls": False,
                        "state": 1,
                        "disabled": False,
                        "pixel_cap": -1,
                    }
                }
            ],
            "benchmark_payload": {
                "prompt": "A herd of cows grazing at the bottom of a sunny valley",
                "negative_prompt": "",
                "steps": 20,
                "width": 512,
                "height": 512,
                "batch_size": 1,
            },
            "job_timeout": 3,
            "enabled": True,
            "enabled_i2i": True,
            "complement_production": True,
            "step_scaling": False,
        }
        with open(path, "w") as f:
            json.dump(ref_cfg, f)
        cfg = load_config(path)
        assert os.path.exists(path)  # not quarantined
        w = cfg.workers[0]["laptop"]
        assert w.avg_ipm == 4.2
        assert w.pixel_cap == 0  # -1 sentinel normalized to uncapped

    def test_defaults_parity_with_reference(self):
        cfg = ConfigModel()
        assert cfg.enabled_i2i is True  # reference pmodels.py:44


@pytest.mark.slow
class TestRng:
    """The seed contract: image i depends only on (seed + i) — the reference's
    seed-offset fan-out (distributed.py:297-305) reproduced exactly.

    (marked slow: the sub-batch/seed-resize cases jit real noise pipelines,
    ~30 s of the module's wall time)"""

    def test_subbatch_equals_full_batch(self):
        shape = (4, 8, 8)
        full = rng.batch_noise(123, 0, 0.0, 0, 6, shape)
        part = rng.batch_noise(123, 0, 0.0, 4, 2, shape)
        np.testing.assert_array_equal(np.asarray(full[4:6]), np.asarray(part))

    def test_offset_seed_equivalence(self):
        # Worker B starting at index 3 of seed 100 == fresh request seeded 103.
        shape = (2, 4, 4)
        a = rng.batch_noise(100, 0, 0.0, 3, 1, shape)
        b = rng.batch_noise(103, 0, 0.0, 0, 1, shape)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_seed_resize_pastes_centered(self):
        # webui seed-resize: noise drawn at the "from" latent size lands
        # centered in the target; the uncovered border stays zero.
        shape = (8, 8, 4)
        src = rng.batch_noise(42, 0, 0.0, 0, 2, (4, 4, 4))
        out = rng.batch_noise(42, 0, 0.0, 0, 2, shape, seed_resize=(4, 4))
        np.testing.assert_array_equal(
            np.asarray(out[:, 2:6, 2:6]), np.asarray(src))
        border = np.asarray(out).copy()
        border[:, 2:6, 2:6] = 0
        assert not border.any()
        # larger-than-target from-size: the CENTER of the source is kept
        big = rng.batch_noise(42, 0, 0.0, 0, 2, (8, 8, 4))
        crop = rng.batch_noise(42, 0, 0.0, 0, 2, (4, 4, 4),
                               seed_resize=(8, 8))
        np.testing.assert_array_equal(
            np.asarray(big[:, 2:6, 2:6]), np.asarray(crop))
        # sub-batch contract survives seed-resize
        part = rng.batch_noise(42, 0, 0.0, 1, 1, shape, seed_resize=(4, 4))
        np.testing.assert_array_equal(np.asarray(out[1:2]), np.asarray(part))

    def test_different_seeds_differ(self):
        shape = (2, 4, 4)
        a = rng.noise_for_image(1, 0, 0.0, 0, shape)
        b = rng.noise_for_image(2, 0, 0.0, 0, shape)
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_subseed_blend(self):
        shape = (2, 4, 4)
        base = rng.noise_for_image(1, 999, 0.0, 0, shape)
        blended = rng.noise_for_image(1, 999, 0.5, 0, shape)
        pure_sub = rng.noise_for_image(999, 0, 0.0, 0, shape)
        assert not np.array_equal(np.asarray(base), np.asarray(blended))
        assert not np.array_equal(np.asarray(pure_sub), np.asarray(blended))
        # strength 0 reproduces the base exactly
        again = rng.noise_for_image(1, 999, 0.0, 0, shape)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(again))

    def test_variation_batch_shares_base_noise(self):
        # webui/reference contract (distributed.py:297-305): with
        # subseed_strength > 0 the base seed does NOT advance per image —
        # only the subseed does. Images at different indices must converge
        # to the SAME base noise as strength -> 0.
        shape = (2, 4, 4)
        eps = 1e-4
        near0_idx0 = rng.noise_for_image(7, 99, eps, 0, shape)
        near0_idx3 = rng.noise_for_image(7, 99, eps, 3, shape)
        base = rng.noise_for_image(7, 99, 0.0, 0, shape)
        np.testing.assert_allclose(
            np.asarray(near0_idx0), np.asarray(base), atol=1e-2
        )
        np.testing.assert_allclose(
            np.asarray(near0_idx3), np.asarray(base), atol=1e-2
        )
        # while at real strength the subseed component still varies by index
        s_idx0 = rng.noise_for_image(7, 99, 0.5, 0, shape)
        s_idx3 = rng.noise_for_image(7, 99, 0.5, 3, shape)
        assert not np.array_equal(np.asarray(s_idx0), np.asarray(s_idx3))

    def test_jittable_with_traced_seed(self):
        import jax

        f = jax.jit(lambda s: rng.noise_for_image(s, 0, 0.0, 0, (2, 2)))
        a, b = f(jnp.uint32(5)), f(jnp.uint32(6))
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_slerp_endpoints(self):
        a = jnp.ones((8,))
        b = -jnp.ones((8,)) + 0.1
        np.testing.assert_allclose(np.asarray(rng.slerp(0.0, a, b)), np.asarray(a), atol=1e-6)
        np.testing.assert_allclose(np.asarray(rng.slerp(1.0, a, b)), np.asarray(b), atol=1e-5)


class TestLogging:
    def test_ring_buffer(self):
        logger = configure(debug=True, use_rich=False)
        ring = get_ring_buffer()
        ring.clear()
        for i in range(20):
            logger.info("msg %d", i)
        lines = ring.dump()
        assert len(lines) == 16  # capacity parity with shared.py:44
        assert lines[-1].endswith("msg 19")
        assert lines[0].endswith("msg 4")


class TestBatchKeys:
    """rng.batch_keys carries the sampler-key seed discipline (start-offset
    continuity + variation pinning) that engine._image_keys delegates to —
    pinned here against the eager per-image form."""

    def test_subrange_matches_full(self):
        import numpy as np

        full = rng.batch_keys(1234, 0, 6)
        sub = rng.batch_keys(1234, 2, 3)
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(full))[2:5],
            np.asarray(jax.random.key_data(sub)))

    def test_matches_eager_key_for_image(self):
        import numpy as np

        keys = rng.batch_keys(77, 3, 2)
        for j, i in enumerate((3, 4)):
            np.testing.assert_array_equal(
                np.asarray(jax.random.key_data(keys))[j],
                np.asarray(jax.random.key_data(rng.key_for_image(77, i))))

    def test_pin_index_fixes_every_key(self):
        import numpy as np

        keys = np.asarray(jax.random.key_data(
            rng.batch_keys(9, 5, 4, pin_index=True)))
        base = np.asarray(jax.random.key_data(rng.key_for_image(9, 0)))
        for row in keys:
            np.testing.assert_array_equal(row, base)

    def test_full_uint32_seed_range(self):
        rng.batch_keys(2 ** 32 - 1, 0, 2)  # must not overflow


class TestCompileCachePlacement:
    """runtime/mesh.py: the compile cache is placed from outside
    (JAX_COMPILATION_CACHE_DIR) or at one fixed path inside the checkout."""

    @pytest.fixture(autouse=True)
    def _restore_jax_config(self):
        from jax.experimental.compilation_cache import compilation_cache

        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs")
        was = {k: getattr(jax.config, k) for k in keys}
        yield
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()

    def test_env_placement_sets_no_directory_in_code(self, monkeypatch,
                                                     tmp_path):
        from stable_diffusion_webui_distributed_tpu.runtime import mesh

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "sentinel-untouched")
        assert mesh.enable_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "sentinel-untouched"

    def test_default_is_the_fixed_path_inside_the_checkout(self,
                                                           monkeypatch):
        from stable_diffusion_webui_distributed_tpu.runtime import mesh

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert mesh.enable_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert os.path.isdir(want)

    def test_two_calls_give_the_same_path(self, monkeypatch):
        from stable_diffusion_webui_distributed_tpu.runtime import mesh

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert mesh.enable_compilation_cache() \
            == mesh.enable_compilation_cache() \
            == mesh.DEFAULT_COMPILE_CACHE


def test_importing_the_package_keeps_freed_host_memory_mapped():
    """runtime/malloc.py: after the import a 4 MiB block comes from an
    arena's heap, not from an mmap of its own (glibc's default would map
    and unmap it, and a request's buffers would fault in again every
    time)."""
    import subprocess
    import sys

    script = """
import ctypes, sys
libc = ctypes.CDLL(None)
libc.malloc.restype = ctypes.c_void_p
libc.free.argtypes = [ctypes.c_void_p]
class MallInfo(ctypes.Structure):
    _fields_ = [(n, ctypes.c_int) for n in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
        "fsmblks", "uordblks", "fordblks", "keepcost")]
libc.mallinfo.restype = MallInfo
def mapped_blocks_while_holding_4_mib():
    before = libc.mallinfo().hblks
    block = libc.malloc(4 * 2 ** 20)
    held = libc.mallinfo().hblks - before
    libc.free(block)
    return held
if sys.argv[1] == "with":
    import stable_diffusion_webui_distributed_tpu  # noqa: F401
    from stable_diffusion_webui_distributed_tpu.runtime.malloc import (
        retain_freed_memory)
    assert retain_freed_memory()
print(mapped_blocks_while_holding_4_mib())
"""
    out = {}
    for which in ("without", "with"):
        proc = subprocess.run([sys.executable, "-c", script, which],
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        out[which] = proc.stdout.strip().splitlines()[-1]
    assert out == {"without": "1", "with": "0"}

"""Step-cache subsystem tests (pipeline/stepcache.py + the engine's
step-cache chunk variant).

Host-side policy tests (cadence bucketing, cutoff mapping, schedule
mirror, serving group key) are tier-1 fast; everything that compiles a
tiny pipeline is marked slow, like the other compiled-pipeline modules.

The correctness contract under test:

- cadence 1 + cutoff 0 (the default) routes to the UNCHANGED plain
  executable — outputs byte-identical, zero new compiles;
- cadence > 1 / cutoff > 0 changes pixels only within a bounded PSNR
  drift against the exact baseline;
- the levers add exactly ONE static compile-key bit, so a shape bucket
  holds at most two chunk executables and cadence/cutoff changes on a
  warm bucket never recompile;
- carry/cache donation is declared on the chunk executables and the
  uint8 decode input.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import quality
from stable_diffusion_webui_distributed_tpu.models.configs import TINY
from stable_diffusion_webui_distributed_tpu.models.unet import (
    deep_cache_shape,
)
from stable_diffusion_webui_distributed_tpu.pipeline import denoise, stepcache
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
)
from stable_diffusion_webui_distributed_tpu.samplers import kdiffusion as kd
from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
    ServingDispatcher,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import METRICS

#: Documented quality floor for the bench/bench-tier cadence-3 + cutoff
#: configuration on the tiny families (measured ~24-26 dB; see PERF.md).
PSNR_FLOOR_DB = 20.0


class TestBucketCadence:
    def test_ladder_rounds_down(self):
        assert stepcache.bucket_cadence(1) == 1
        assert stepcache.bucket_cadence(2) == 2
        assert stepcache.bucket_cadence(3) == 3
        assert stepcache.bucket_cadence(5) == 4
        assert stepcache.bucket_cadence(7) == 6
        assert stepcache.bucket_cadence(100) == 8  # clamps to top rung

    def test_garbage_means_off(self):
        assert stepcache.bucket_cadence(None) == 1
        assert stepcache.bucket_cadence("junk") == 1
        assert stepcache.bucket_cadence(-3) == 1
        assert stepcache.bucket_cadence(0) == 1

    def test_every_rung_is_a_fixed_point(self):
        for rung in stepcache.CADENCE_LADDER:
            assert stepcache.bucket_cadence(rung) == rung


class TestCutoffStep:
    SIGMAS = [8.0, 4.0, 2.0, 1.0, 0.5, 0.0]  # 5 steps + final x0

    def test_disabled_never_fires(self):
        # cfg_stop == n means the in-graph i >= cfg_stop never triggers
        assert stepcache.cutoff_step(self.SIGMAS, 0.0) == 5
        assert stepcache.cutoff_step(self.SIGMAS, -1.0) == 5

    def test_mid_ladder(self):
        # steps whose sigma is below 1.2 (indices 3, 4) run cond-only
        assert stepcache.cutoff_step(self.SIGMAS, 1.2) == 3

    def test_above_sigma_max_truncates_everything(self):
        assert stepcache.cutoff_step(self.SIGMAS, 100.0) == 0

    def test_below_sigma_min_never_fires(self):
        assert stepcache.cutoff_step(self.SIGMAS, 0.1) == 5

    def test_monotone_in_threshold(self):
        stops = [stepcache.cutoff_step(self.SIGMAS, s)
                 for s in (0.1, 0.7, 1.5, 3.0, 6.0, 9.0)]
        assert stops == sorted(stops, reverse=True)


class TestResolve:
    def test_defaults_off(self, monkeypatch):
        monkeypatch.delenv("SDTPU_DEEPCACHE", raising=False)
        monkeypatch.delenv("SDTPU_CFG_CUTOFF", raising=False)
        sc = stepcache.resolve(None)
        assert sc == stepcache.StepCacheSpec(1, 0.0)
        assert not sc.active

    def test_env_defaults(self, monkeypatch):
        monkeypatch.setenv("SDTPU_DEEPCACHE", "3")
        monkeypatch.setenv("SDTPU_CFG_CUTOFF", "1.5")
        sc = stepcache.resolve(None)
        assert (sc.cadence, sc.cutoff_sigma) == (3, 1.5)
        assert sc.active

    def test_override_settings_win_and_bucket(self, monkeypatch):
        monkeypatch.setenv("SDTPU_DEEPCACHE", "2")
        p = GenerationPayload(prompt="x",
                              override_settings={"deepcache": 5,
                                                 "cfg_cutoff": "0.7"})
        sc = stepcache.resolve(p)
        assert sc.cadence == 4  # 5 rounds DOWN onto the ladder
        assert sc.cutoff_sigma == pytest.approx(0.7)

    def test_bad_override_values(self, monkeypatch):
        monkeypatch.delenv("SDTPU_DEEPCACHE", raising=False)
        p = GenerationPayload(prompt="x",
                              override_settings={"deepcache": "junk",
                                                 "cfg_cutoff": "junk"})
        sc = stepcache.resolve(p)
        assert sc == stepcache.StepCacheSpec(1, 0.0)


class TestServingGroupKey:
    """Coalesced requests share ONE denoise range, so the resolved
    step-cache knobs must be part of the dispatcher's group key."""

    def _key(self, **ov):
        p = GenerationPayload(prompt="k", steps=8, width=64, height=64,
                              override_settings=ov or {})
        return ServingDispatcher._group_key(None, p)

    def test_knobs_split_groups(self):
        base = self._key()
        assert self._key(deepcache=3) != base
        assert self._key(cfg_cutoff=1.0) != base
        assert self._key(deepcache=3) != self._key(deepcache=2)

    def test_bucketed_cadences_merge(self):
        # 5 and 4 land on the same ladder rung -> same group
        assert self._key(deepcache=5) == self._key(deepcache=4)


# -- compiled-pipeline tests (slow tier, like test_pipeline) ---------------


@pytest.fixture(scope="module")
def engine():
    return quality.make_engine(TINY, chunk_size=4)


def _payload(**kw):
    kw.setdefault("prompt", "a cow")
    kw.setdefault("steps", 8)
    kw.setdefault("width", 32)
    kw.setdefault("height", 32)
    kw.setdefault("batch_size", 2)
    kw.setdefault("seed", 42)
    return GenerationPayload(**kw)


@pytest.fixture(scope="module")
def baseline(engine):
    return engine.txt2img(_payload())


@pytest.mark.slow
class TestCacheCorrectness:
    def test_inactive_is_byte_identical_and_plain(self, engine, baseline):
        before = METRICS.compile_count("chunk")
        r = engine.txt2img(_payload(
            override_settings={"deepcache": 1, "cfg_cutoff": 0.0}))
        # default knobs route to the plain executable already compiled by
        # the baseline run: same bytes, zero new chunk compiles
        assert r.images == baseline.images
        assert METRICS.compile_count("chunk") == before

    def test_cadence_drift_is_bounded(self, engine, baseline):
        r = engine.txt2img(_payload(
            override_settings={"deepcache": 3, "cfg_cutoff": 2.0}))
        db = quality.mean_psnr(r.images, baseline.images)
        assert db < quality.IDENTICAL_DB  # the levers actually engaged
        assert db >= PSNR_FLOOR_DB
        assert quality.mean_ssim(r.images, baseline.images) >= 0.5

    def test_knob_changes_do_not_recompile(self, engine, baseline):
        # first cached run on this bucket mints exactly one extra
        # executable (the step-cache variant)...
        engine.txt2img(_payload(override_settings={"deepcache": 2}))
        before = METRICS.compile_count("chunk")
        # ...after which cadence and cutoff travel as traced data
        engine.txt2img(_payload(
            override_settings={"deepcache": 3, "cfg_cutoff": 1.0}))
        engine.txt2img(_payload(
            override_settings={"deepcache": 4, "cfg_cutoff": 2.5}))
        assert METRICS.compile_count("chunk") == before

    def test_at_most_two_executables_per_bucket(self, engine):
        buckets = {}
        for v in map(denoise.parse_key, engine.executable_keys()):
            if v is not None:
                buckets.setdefault(v._replace(step_cache=False),
                                   set()).add(v.step_cache)
        assert buckets, "no chunk executables compiled?"
        for bucket, variants in buckets.items():
            assert len(variants) <= 2, (bucket, variants)
            assert variants <= {False, True}

    def test_interrupt_then_rerun_matches(self, engine, baseline):
        """An interrupted cached run must not poison later runs: the
        deep-feature cache lives in the chunk-loop scan state, and every
        fresh range enters INVALID (refresh on first step)."""
        from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
            GenerationState,
        )

        st = GenerationState()
        eng2 = quality.make_engine(TINY, chunk_size=2)
        eng2.state = st
        ov = {"deepcache": 8, "cfg_cutoff": 0.0}  # one refresh per range
        ref = eng2.txt2img(_payload(override_settings=ov))

        armed = {"on": True}
        st.add_listener(
            lambda prog: st.flag.interrupt() if armed["on"] else None)
        partial = eng2.txt2img(_payload(override_settings=ov))
        assert len(partial.images) == 2  # partial latents still decoded
        assert st.progress.sampling_step < 8

        armed["on"] = False
        again = eng2.txt2img(_payload(override_settings=ov))
        assert again.images == ref.images


@pytest.mark.slow
class TestDonationDeclared:
    """The chunk executables donate their carry (and cache) inputs and the
    uint8 decode donates its latent rows — asserted on the lowered HLO
    (`tf.aliasing_output` is how declared+usable donation surfaces)."""

    def _chunk_args(self, engine, batch=1, lat=4):
        ucfg = engine.family.unet
        x = jnp.zeros((batch, lat, lat, ucfg.in_channels), jnp.float32)
        carry = kd.init_carry(x)
        ctx = jnp.zeros((1, 77, ucfg.cross_attention_dim), jnp.float32)
        keys = jax.random.split(jax.random.key(0), batch)
        return x, carry, ctx, keys

    def test_plain_chunk_aliases_carry(self, engine):
        fn = denoise.build(
            denoise.Variant("chunk", "Euler", 4, 32, 32, 1, 2),
            denoise.Deps(engine.unet, engine.controlnet_module,
                         engine.schedule))
        x, carry, ctx, keys = self._chunk_args(engine)
        hlo = fn.lower(
            engine.params["unet"], carry, jnp.int32(0),
            denoise.Inputs(ctx, ctx, jnp.float32(7.0), keys)).as_text()
        assert "tf.aliasing_output" in hlo

    def test_stepcache_chunk_aliases_carry_and_cache(self, engine):
        fn = denoise.build(
            denoise.Variant("chunk", "Euler", 4, 32, 32, 1, 2,
                            step_cache=True),
            denoise.Deps(engine.unet, engine.controlnet_module,
                         engine.schedule))
        x, carry, ctx, keys = self._chunk_args(engine)
        cache = jnp.zeros(deep_cache_shape(engine.family.unet, 2, 4, 4),
                          jnp.float32)
        hlo = fn.lower(
            engine.params["unet"],
            denoise.CachedState(carry, cache, jnp.asarray(False)),
            jnp.int32(0),
            denoise.Inputs(ctx, ctx, jnp.float32(7.0), keys,
                           cadence=jnp.int32(3),
                           cfg_stop=jnp.int32(2))).as_text()
        assert hlo.count("tf.aliasing_output") >= 2  # carry.x AND cache

    def test_decode_u8_declares_unusable_donation(self, engine):
        # f32 latents can never alias the u8 output: the declaration must
        # still be present (JAX tells us via the donated-buffers warning;
        # the dispatch site in _queue_decoded suppresses exactly this)
        fn = engine._decode_u8_fn(32, 32, 1)
        lat = jnp.zeros((1, 4, 4, 4), jnp.float32)
        with pytest.warns(UserWarning,
                          match="donated buffers were not usable"):
            fn.lower(engine.params["vae"], lat).compile()

"""Recompile-free traced-LoRA serving (SDTPU_LORA_TRACED).

Fast tier (no pipeline compiles): the rank/slot bucketing ladder,
traced-set construction / zero-padding / content addressing, the batched
delta einsums against a numpy reference, heterogeneous row stacking, the
merge-latch regression (an identical partially-resolved set repeated
must be a no-op), the registry's mtime-validated adapter cache, the
group-key cell axes, the executables-census lora budget, warmup cell
parsing and the cache-key lora fold.

Slow tier (full TINY pipelines): traced output quality against the
merged reference, adapter-churn executable/merge stability with cache
survival, and batch-split identity under a traced set.
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.models import lora as lora_mod
from stable_diffusion_webui_distributed_tpu.models.configs import TINY
from stable_diffusion_webui_distributed_tpu.obs import perf as obs_perf
from stable_diffusion_webui_distributed_tpu.pipeline import denoise
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
)
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.serving.dispatcher import (
    ServingDispatcher,
)

import quality


def make_lora_sd(rank=4, scale=0.3, seed=0, te=True):
    """Synthetic kohya adapter touching TINY's first UNet attn1 q and
    (optionally) the text encoder's layer-0 q projection."""
    rng = np.random.default_rng(seed)
    mods = [("lora_unet_input_blocks_1_1_transformer_blocks_0_attn1_to_q",
             32)]
    if te:
        mods.append(
            ("lora_te_text_model_encoder_layers_0_self_attn_q_proj", 32))
    sd = {}
    for module, d in mods:
        sd[f"{module}.lora_down.weight"] = (
            rng.standard_normal((rank, d)).astype(np.float32) * scale)
        sd[f"{module}.lora_up.weight"] = (
            rng.standard_normal((d, rank)).astype(np.float32) * scale)
        sd[f"{module}.alpha"] = np.float32(rank)
    return sd


def make_engine(loras, seed=0):
    return Engine(TINY, quality.init_params(TINY, seed=seed), chunk_size=4,
                  state=GenerationState(),
                  lora_provider=loras.get if loras is not None else None)


def payload(prompt, seed=3, steps=4, batch=1, **kw):
    return GenerationPayload(prompt=prompt, steps=steps, width=32,
                             height=32, seed=seed, batch_size=batch, **kw)


class TestLadder:
    def test_default_ladders_and_bucketing(self):
        assert lora_mod.rank_ladder() == (8, 16, 32, 64)
        assert lora_mod.slot_ladder() == (1, 2, 4)
        assert lora_mod.bucket_rank(1) == 8
        assert lora_mod.bucket_rank(8) == 8
        assert lora_mod.bucket_rank(9) == 16
        assert lora_mod.bucket_rank(64) == 64
        assert lora_mod.bucket_rank(65) is None
        assert lora_mod.bucket_slots(1) == 1
        assert lora_mod.bucket_slots(3) == 4
        assert lora_mod.bucket_slots(5) is None

    def test_env_ladder_override(self, monkeypatch):
        monkeypatch.setenv("SDTPU_LORA_RANKS", "4,12")
        monkeypatch.setenv("SDTPU_LORA_SLOTS", "2")
        assert lora_mod.rank_ladder() == (4, 12)
        assert lora_mod.bucket_rank(5) == 12
        assert lora_mod.bucket_slots(1) == 2
        assert lora_mod.bucket_slots(3) is None


class TestTracedSetBuild:
    def test_padding_and_content_address(self, monkeypatch):
        monkeypatch.setenv("SDTPU_LORA_TRACED", "1")
        params = quality.init_params(TINY)
        loras = {"a": make_lora_sd(seed=1), "b": make_lora_sd(seed=2)}
        ts = lora_mod.build_traced_set((("a", 0.8, 0.8),), loras.get,
                                       TINY, params)
        assert (ts.sig, ts.rank_bucket, ts.slots) == ("lora:r8s1", 8, 1)
        assert ts.applied == 2 and ts.skipped == 0
        site = ts.tree["unet"]["down_0_attn_0"]["block_0"]["attn1"]["qkv"]
        # rank 4 pads up to the 8-bucket; the padded tail must be exact 0
        assert site["down"].shape == (1, 8, 32)
        assert site["up"].shape == (1, 96, 8)
        assert np.all(site["down"][:, 4:, :] == 0)
        assert np.all(site["up"][:, :, 4:] == 0)
        # a site no adapter touches is all-zero (contributes exactly 0)
        off = ts.tree["unet"]["mid_attn"]["proj_in"]
        assert not np.any(off["down"])
        # content addressing: same specs reproduce, any change re-keys
        again = lora_mod.build_traced_set((("a", 0.8, 0.8),), loras.get,
                                          TINY, params)
        assert again.content == ts.content
        other = lora_mod.build_traced_set((("a", 1.0, 0.8),), loras.get,
                                          TINY, params)
        assert other.content != ts.content
        # this adapter carries TE factors, so the TE address is non-empty
        assert ts.te_content and ts.te_content != ts.content

    def test_two_slot_and_unresolvable(self, monkeypatch):
        monkeypatch.setenv("SDTPU_LORA_TRACED", "1")
        params = quality.init_params(TINY)
        loras = {"a": make_lora_sd(seed=1), "b": make_lora_sd(seed=2)}
        ts = lora_mod.build_traced_set(
            (("a", 0.8, 0.8), ("b", 1.0, 1.0)), loras.get, TINY, params)
        assert (ts.rank_bucket, ts.slots) == (8, 2)
        # an unknown name cannot ride traced — merged-path fallback
        assert lora_mod.build_traced_set(
            (("nope", 1.0, 1.0),), loras.get, TINY, params) is None
        # a rank past the ladder cannot ride either
        big = {"big": make_lora_sd(rank=96, seed=3)}
        assert lora_mod.build_traced_set(
            (("big", 1.0, 1.0),), big.get, TINY, params) is None

    def test_zero_set_is_exact_noop_contribution(self):
        params = quality.init_params(TINY)
        zs = lora_mod.zero_set(params, TINY, 8, 1)
        assert zs.sig == "lora:r8s1" and zs.content == "zero"
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (2, 5, 32)).astype(np.float32))
        site = zs.tree["unet"]["down_0_attn_0"]["proj_in"]
        assert not np.any(np.asarray(lora_mod.delta_out(x, site)))


class TestDeltaMath:
    def _site(self, rng, s, r, i, o, batched=None):
        shape_d = (s, r, i) if batched is None else (batched, s, r, i)
        shape_u = (s, o, r) if batched is None else (batched, s, o, r)
        return {
            "down": jnp.asarray(
                rng.standard_normal(shape_d).astype(np.float32)),
            "up": jnp.asarray(
                rng.standard_normal(shape_u).astype(np.float32)),
        }

    def test_shared_site_matches_numpy(self):
        rng = np.random.default_rng(0)
        site = self._site(rng, s=2, r=4, i=8, o=6)
        x = jnp.asarray(rng.standard_normal((3, 5, 8)).astype(np.float32))
        got = np.asarray(lora_mod.delta_out(x, site))
        want = np.zeros((3, 5, 6), np.float32)
        for s in range(2):
            want += np.asarray(x) @ np.asarray(site["down"][s]).T \
                @ np.asarray(site["up"][s]).T
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_per_row_site_matches_rowwise(self):
        rng = np.random.default_rng(1)
        site = self._site(rng, s=2, r=4, i=8, o=6, batched=3)
        x = jnp.asarray(rng.standard_normal((3, 5, 8)).astype(np.float32))
        got = np.asarray(lora_mod.delta_out(x, site))
        for b in range(3):
            row_site = {"down": site["down"][b], "up": site["up"][b]}
            row = np.asarray(lora_mod.delta_out(x[b:b + 1], row_site))
            np.testing.assert_allclose(got[b:b + 1], row,
                                       rtol=2e-5, atol=2e-5)

    def test_apply_site_adds_delta_and_passes_through(self):
        rng = np.random.default_rng(2)
        site = self._site(rng, s=1, r=4, i=8, o=6)
        x = jnp.asarray(rng.standard_normal((2, 5, 8)).astype(np.float32))
        y = jnp.asarray(rng.standard_normal((2, 5, 6)).astype(np.float32))
        out = lora_mod.apply_site(y, x, {"k": site}, "k")
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(y) + np.asarray(lora_mod.delta_out(x, site)),
            rtol=2e-5, atol=2e-5)
        assert lora_mod.apply_site(y, x, None, "k") is y
        assert lora_mod.apply_site(y, x, {"other": site}, "k") is y


class TestStackRows:
    def test_heterogeneous_rows_stack_and_pad(self, monkeypatch):
        monkeypatch.setenv("SDTPU_LORA_TRACED", "1")
        params = quality.init_params(TINY)
        loras = {"a": make_lora_sd(seed=1), "b": make_lora_sd(seed=2)}
        ta = lora_mod.build_traced_set((("a", 0.8, 0.8),), loras.get,
                                       TINY, params)
        tb = lora_mod.build_traced_set((("b", 1.0, 1.0),), loras.get,
                                       TINY, params)
        st = lora_mod.stack_row_sets([ta, tb], 2)
        site = st["unet"]["down_0_attn_0"]["block_0"]["attn1"]["qkv"]
        assert site["down"].shape == (2, 1, 8, 32)
        np.testing.assert_array_equal(
            site["down"][0],
            ta.tree["unet"]["down_0_attn_0"]["block_0"]["attn1"]["qkv"]
            ["down"])
        np.testing.assert_array_equal(
            site["down"][1],
            tb.tree["unet"]["down_0_attn_0"]["block_0"]["attn1"]["qkv"]
            ["down"])
        # a short list self-pads to the batch by repeating its last row
        padded = lora_mod.stack_row_sets([ta], 3)
        p = padded["unet"]["down_0_attn_0"]["block_0"]["attn1"]["qkv"]
        assert p["down"].shape[0] == 3
        np.testing.assert_array_equal(p["down"][1], p["down"][0])
        np.testing.assert_array_equal(p["down"][2], p["down"][0])

    def test_mixed_cells_refused(self, monkeypatch):
        monkeypatch.setenv("SDTPU_LORA_TRACED", "1")
        params = quality.init_params(TINY)
        loras = {"a": make_lora_sd(seed=1), "b": make_lora_sd(seed=2)}
        one = lora_mod.build_traced_set((("a", 0.8, 0.8),), loras.get,
                                        TINY, params)
        two = lora_mod.build_traced_set(
            (("a", 0.8, 0.8), ("b", 1.0, 1.0)), loras.get, TINY, params)
        with pytest.raises(AssertionError):
            lora_mod.stack_row_sets([one, two], 2)


class _CountingProvider:
    """Registry stand-in: counts lookups, exposes the reload generation
    the engine's merge latch keys on."""

    def __init__(self, loras):
        self.loras = loras
        self.lora_generation = 0
        self.calls = 0

    def provider(self, name):
        self.calls += 1
        return self.loras.get(name)


class TestMergeLatchRegression:
    def test_identical_unresolved_set_is_noop(self):
        # Regression for the _UNRESOLVED latch: a set with one skipped
        # name used to defeat the latch entirely, re-merging from base on
        # EVERY request. The resolved outcome (skips included) is now
        # latched, so an identical repeat touches neither the provider
        # nor the param tree.
        src = _CountingProvider({"good": make_lora_sd(seed=1)})
        eng = Engine(TINY, quality.init_params(TINY), chunk_size=4,
                     state=GenerationState(), lora_provider=src.provider)
        specs = (("good", 1.0, 1.0), ("nope", 1.0, 1.0))
        eng.set_loras(specs)
        assert eng._lora_merge_total == 1
        calls, epoch = src.calls, eng._model_epoch
        eng.set_loras(specs)
        assert eng._lora_merge_total == 1
        assert src.calls == calls
        assert eng._model_epoch == epoch

    def test_provider_generation_retries_skips(self):
        # /refresh-loras bumps the generation: the SAME specs must
        # re-resolve exactly once (the file may exist now), not never.
        src = _CountingProvider({"good": make_lora_sd(seed=1)})
        eng = Engine(TINY, quality.init_params(TINY), chunk_size=4,
                     state=GenerationState(), lora_provider=src.provider)
        specs = (("good", 1.0, 1.0), ("late", 1.0, 1.0))
        eng.set_loras(specs)
        assert eng._lora_merge_total == 1
        src.loras["late"] = make_lora_sd(seed=2)
        eng.set_loras(specs)  # same generation: still latched
        assert eng._lora_merge_total == 1
        src.lora_generation += 1
        eng.set_loras(specs)  # rescan: retries, both resolve now
        assert eng._lora_merge_total == 3

    def test_empty_set_after_rescan_stays_cheap(self):
        src = _CountingProvider({})
        eng = Engine(TINY, quality.init_params(TINY), chunk_size=4,
                     state=GenerationState(), lora_provider=src.provider)
        eng.set_loras((("nope", 1.0, 1.0),))
        epoch = eng._model_epoch
        src.lora_generation += 1
        # already pristine: a rescan can't change "no adapters", so the
        # latch refreshes without the cache-retiring epoch bump
        eng.set_loras(())
        assert eng._model_epoch == epoch + 1  # the unlatch restored base
        eng.set_loras(())
        assert eng._model_epoch == epoch + 1


class TestRegistryAdapterCache:
    def _registry(self, tmp_path):
        from stable_diffusion_webui_distributed_tpu.pipeline.registry \
            import ModelRegistry

        return ModelRegistry(model_dir=str(tmp_path))

    def _write_adapter(self, path, seed=1):
        from safetensors.numpy import save_file

        sd = make_lora_sd(seed=seed)
        save_file({k: np.asarray(v) for k, v in sd.items()}, path)

    def test_mtime_invalidation_reloads(self, tmp_path):
        reg = self._registry(tmp_path)
        path = str(tmp_path / "a.safetensors")
        self._write_adapter(path)
        reg._lora_paths = {"a": path}
        sd1 = reg.lora_provider("a")
        assert sd1 is not None
        assert reg.lora_provider("a") is sd1  # cached: same object
        # edit the file in place: the stale mtime must force a reload
        st = os.stat(path)
        os.utime(path, (st.st_atime + 5, st.st_mtime + 5))
        sd2 = reg.lora_provider("a")
        assert sd2 is not sd1
        assert reg.lora_provider("nope") is None

    def test_byte_cap_disables_retention(self, tmp_path):
        reg = self._registry(tmp_path)
        path = str(tmp_path / "a.safetensors")
        self._write_adapter(path)
        reg._lora_paths = {"a": path}
        reg._lora_cache.max_bytes = 1  # nothing fits: loads still serve
        sd1 = reg.lora_provider("a")
        sd2 = reg.lora_provider("a")
        assert sd1 is not None and sd2 is not None and sd2 is not sd1

    def test_refresh_bumps_generation_and_drops_cache(self, tmp_path):
        reg = self._registry(tmp_path)
        path = str(tmp_path / "a.safetensors")
        self._write_adapter(path)
        reg._lora_paths = {"a": path}
        sd1 = reg.lora_provider("a")
        gen = reg.lora_generation
        reg.refresh()
        assert reg.lora_generation == gen + 1
        reg._lora_paths = {"a": path}  # the empty scan dropped it
        assert reg.lora_provider("a") is not sd1


class TestGroupKeyCells:
    def test_gate_off_tagged_keys_adapterless_cell(self):
        p = payload("a cow <lora:a:0.8>")
        key = ServingDispatcher._group_key(None, p)
        # (fifteen since PR 72: the expansion script's arguments, None for
        # a plain request, in front of the adapter cell)
        assert len(key) == 15
        assert key[-3:-1] == (0, 0)
        assert isinstance(key[-1], str)
        # tagless payloads share the cell — adapterless grouping intact
        assert ServingDispatcher._group_key(None, payload("a cow"))[-3:-1] \
            == (0, 0)

    def test_rowspec_cells(self, monkeypatch):
        import types

        assert ServingDispatcher._traced_rowspec(None, payload("x")) \
            == (0, 0)
        tagged = payload("x <lora:a:0.8>")
        assert ServingDispatcher._traced_rowspec(None, tagged) is None
        monkeypatch.setenv("SDTPU_LORA_TRACED", "1")
        # engineless (ETA probes): merged-path conservatism
        assert ServingDispatcher._traced_rowspec(None, tagged) is None
        stub = types.SimpleNamespace(engine=types.SimpleNamespace(
            _traced_set_for=lambda specs: types.SimpleNamespace(
                rank_bucket=16, slots=2)))
        assert ServingDispatcher._traced_rowspec(stub, tagged) == (16, 2)
        # the adaptive sampler's attempt executable has no delta args
        adaptive = payload("x <lora:a:0.8>",
                           sampler_name="DPM adaptive")
        assert ServingDispatcher._traced_rowspec(stub, adaptive) is None


class TestCensusLoraBudget:
    def _keys(self, sigs):
        keys = []
        for i, sig in enumerate(sigs):
            for sc in (False, True):
                keys.append(denoise.Variant(
                    "chunk", "Euler a", 8, 64, 64, 1, lora_sig=sig,
                    step_cache=sc, precision="bf16").key())
        return keys

    def test_ladder_cells_within_budget_stay_silent(self):
        sigs = ["", "lora:r8s1", "lora:r16s1", "lora:r32s2", "lora:r64s4"]
        census = obs_perf.census_from_keys(self._keys(sigs))
        assert not census["alarm"]
        assert census["budget"]["lora"] == obs_perf.LORA_BUDGET == 4
        assert census["buckets"][0]["lora_variants"] == 4

    def test_cell_explosion_alarms(self):
        sigs = ["", "lora:r8s1", "lora:r8s2", "lora:r16s1", "lora:r16s2",
                "lora:r32s1"]
        census = obs_perf.census_from_keys(self._keys(sigs))
        assert census["alarm"]

    def test_adapterless_keys_use_no_lora_allowance(self):
        census = obs_perf.census_from_keys(self._keys([""]))
        assert not census["alarm"]
        assert census["buckets"][0]["lora_variants"] == 0

    def test_a_key_of_another_layout_is_not_a_chunk(self):
        # one layout, read by name: a tuple that is not a Variant is
        # counted with the other executables, never guessed at
        keys = [("chunk", "Euler a", 8, 64, 64, 1, sc, "bf16")
                for sc in (1, 2)]
        census = obs_perf.census_from_keys(keys)
        assert census["chunk_executables"] == 0
        assert census["other_executables"] == 2


class TestWarmupCells:
    def test_parse_and_bucket(self, monkeypatch):
        from stable_diffusion_webui_distributed_tpu.serving.warmup import (
            _warmup_lora_cells,
        )

        monkeypatch.setenv("SDTPU_LORA_TRACED", "1")
        monkeypatch.setenv("SDTPU_WARMUP_LORA",
                           "r16s1, r10s3,junk,r999s1,r16s1")
        assert _warmup_lora_cells() == [None, (16, 1), (16, 4)]
        monkeypatch.setenv("SDTPU_WARMUP_LORA", "r16s1")
        monkeypatch.delenv("SDTPU_LORA_TRACED")
        assert _warmup_lora_cells() == [None]


class TestCacheKeyFold:
    def test_empty_lora_preserves_digests(self):
        from stable_diffusion_webui_distributed_tpu.cache import keys as K

        fp = ("tiny", 0, 0)
        assert K.embed_key("a cow", 0, 1, fp) == \
            K.embed_key("a cow", 0, 1, fp, lora="")
        assert K.embed_key("a cow", 0, 1, fp, lora="x") != \
            K.embed_key("a cow", 0, 1, fp)
        p = payload("a cow")
        assert K.result_key(p, fp, "txt2img") == \
            K.result_key(p, fp, "txt2img", lora="")
        assert K.result_key(p, fp, "txt2img", lora="x") != \
            K.result_key(p, fp, "txt2img")
        kw = dict(model_fp=fp, batch=1, width=32, height=32, steps=4,
                  cadence=1, sc_active=False, precision="bf16")
        assert K.prefix_key(p, **kw) == K.prefix_key(p, lora="", **kw)
        assert K.prefix_key(p, lora="x", **kw) != K.prefix_key(p, **kw)


@pytest.mark.slow
class TestTracedPipeline:
    def test_traced_matches_merged_quality(self, monkeypatch):
        loras = {"a": make_lora_sd(seed=1)}
        p = payload("a cow <lora:a:0.8>")
        merged_eng = make_engine(loras)
        ref = merged_eng.txt2img(p)
        assert merged_eng._lora_merge_total >= 1
        monkeypatch.setenv("SDTPU_LORA_TRACED", "1")
        traced_eng = make_engine(loras)
        out = traced_eng.txt2img(p)
        assert traced_eng._lora_merge_total == 0
        assert traced_eng._traced_lora is not None
        assert quality.mean_psnr(ref.images, out.images) >= 28.0
        assert quality.mean_ssim(ref.images, out.images) >= 0.985
        # and the adapter genuinely changes the output
        plain = traced_eng.txt2img(payload("a cow"))
        assert plain.images[0] != out.images[0]

    def test_churn_mints_no_executables_and_no_merges(self, monkeypatch):
        monkeypatch.setenv("SDTPU_LORA_TRACED", "1")
        loras = {n: make_lora_sd(seed=i + 1)
                 for i, n in enumerate(("a", "b", "c"))}
        eng = make_engine(loras)
        base = eng.txt2img(payload("a cow", seed=3))
        first = eng.txt2img(payload("a cow <lora:a:0.8>", seed=3))
        n_exec = len(eng.executable_keys())
        outs = {}
        for i, n in enumerate(("b", "c", "a", "b")):
            outs[(i, n)] = eng.txt2img(
                payload(f"a cow <lora:{n}:0.8>", seed=3))
        # THE tentpole claim: adapter switches are compile-free,
        # merge-free, and epoch-free
        assert len(eng.executable_keys()) == n_exec
        assert eng._lora_merge_total == 0
        census = obs_perf.census_from_keys(eng.executable_keys())
        assert not census["alarm"]
        # content actually switches: distinct adapters, distinct pixels;
        # the same adapter reproduces bit-exactly across the churn
        assert outs[(0, "b")].images[0] != outs[(1, "c")].images[0]
        assert outs[(3, "b")].images[0] == outs[(0, "b")].images[0]
        assert outs[(2, "a")].images[0] == first.images[0]
        # and the pristine tree never moved: tagless still matches base
        again = eng.txt2img(payload("a cow", seed=3))
        assert again.images[0] == base.images[0]

    def test_batch_split_identity_under_traced_set(self, monkeypatch):
        monkeypatch.setenv("SDTPU_LORA_TRACED", "1")
        loras = {"a": make_lora_sd(seed=1)}
        eng = make_engine(loras)
        p = payload("a cow <lora:a:0.8>", batch=2)
        full = eng.txt2img(p)
        assert eng._lora_merge_total == 0
        eng.state.begin_request()
        lo = eng.generate_range(p, 0, 1)
        hi = eng.generate_range(p, 1, 1)
        # the worker-side fan-out unit: per-image bytes must not depend
        # on which sub-range (or batch row) carried the traced factors
        assert lo.images[0] == full.images[0]
        assert hi.images[0] == full.images[1]

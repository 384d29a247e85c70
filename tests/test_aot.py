"""Kept programs (serving/aot.py) + warm engine pool (SDTPU_POOL,
fleet/pool.py).

The contract under test: wherever a persistent compile cache is placed, a
second engine loads every stage's program from the store beside it and
traces none, byte for byte the same images; anything that could change a
traced program (the package's sources, an ``SDTPU_*`` variable, the
backend's build, the model's configuration) is in the id, so it misses and
traces; an artifact that does not load is refused once and never tried
again; a damaged one falls back to a fresh trace (journaled, never a
crash); and where no cache is placed nothing is kept or written. The pool
side: least-loaded checkout, chaos-kill isolation (inflight work keeps its
engine), heal to target size, and autoscale decisions upgraded from
``no_executor`` to ``executed``/``failed`` in the audit ring.
"""

import os
import shutil

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from stable_diffusion_webui_distributed_tpu.fleet import pool as fleet_pool
from stable_diffusion_webui_distributed_tpu.fleet.slices import (
    AutoscaleEngine, SliceInfo, SliceRegistry,
)
from stable_diffusion_webui_distributed_tpu.models.configs import TINY
from stable_diffusion_webui_distributed_tpu.obs import journal as obs_journal
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
)
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.serving import aot as aot_mod
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, METRICS, NORM, UPSAMPLE, XLA,
)
from test_pipeline import init_params

#: the functions the tiny engine's three stages jit
STAGE_FUNCTIONS = {"encode", "run_chunk", "decode_u8"}


def _place(directory):
    jax.config.update("jax_compilation_cache_dir", directory)
    compilation_cache.reset_cache()
    with aot_mod._STORE_LOCK:
        aot_mod._STORES.clear()


@pytest.fixture
def placed(tmp_path):
    """The persistent compile cache placed in ``tmp_path``, every
    executable kept in it (no floor on the compile's seconds), and no
    store object left from another test; yields the store's directory."""
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _place(str(tmp_path / "xla"))
    yield str(tmp_path / "xla" / aot_mod.SUBDIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])
    _place(was[0])


def payload(**kw):
    defaults = dict(prompt="an aot cow", steps=4, width=32, height=32,
                    seed=7, sampler_name="Euler a")
    defaults.update(kw)
    return GenerationPayload(**defaults)


def fresh_engine(params=None):
    return Engine(TINY, params or init_params(TINY), chunk_size=4,
                  state=GenerationState())


def restart():
    """What a new process starts from: no store object, counters at 0."""
    with aot_mod._STORE_LOCK:
        aot_mod._STORES.clear()
    METRICS.clear()
    XLA.clear()
    ATTENTION.clear()
    UPSAMPLE.clear()
    NORM.clear()


def run(p=None, params=None):
    """(the images of one request on a new engine, serving.programs, the
    stage functions jax traced, the trace-time site counts)"""
    restart()
    result = fresh_engine(params).txt2img(p or payload(seed=41))
    summary = METRICS.summary()
    with XLA._lock:
        traced = {fun for fun, row in XLA.functions.items()
                  if row["traces"]} & STAGE_FUNCTIONS
    sites = (summary["attention"], summary["upsample"], summary["norm"])
    return result.images, summary["programs"], traced, sites


# -- unit plumbing over a tiny jit cell --------------------------------------

def _double_build():
    return jax.jit(lambda x: x * 2.0)


def _cell(store, **kw):
    return aot_mod.AotFunction(("unit", "double"), _double_build,
                               weights=0, store=store, **kw)


class TestStoreUnit:
    def test_miss_save_then_hit_across_instances(self, tmp_path):
        store = aot_mod.AotStore(str(tmp_path))
        x = jnp.arange(4.0)
        a = _cell(store)
        assert list(a(x)) == [0.0, 2.0, 4.0, 6.0]
        assert store.stats_snapshot() == {"hit": 0, "miss": 1, "saved": 1,
                                          "fallback": 0, "refused": 0}
        # a "restarted process": same store dir, fresh everything
        store2 = aot_mod.AotStore(str(tmp_path))
        b = _cell(store2)
        assert list(b(x)) == list(a(x))
        assert store2.stats_snapshot()["hit"] == 1
        assert store2.stats_snapshot()["miss"] == 0

    def test_one_key_many_signatures(self, tmp_path):
        """One compile key hosts one executable PER call signature (the
        encode stage retraces per chunk count)."""
        store = aot_mod.AotStore(str(tmp_path))
        a = _cell(store)
        a(jnp.arange(4.0))
        a(jnp.arange(8.0))
        assert a.executable_count() == 2
        assert len(store.manifest()["cells"]) == 2

    def test_another_fingerprint_misses_and_both_are_kept(self, tmp_path):
        """The fingerprint is part of a cell's id: a runtime that differs
        finds nothing, traces, and keeps its own beside the other's."""
        store = aot_mod.AotStore(str(tmp_path))
        x = jnp.arange(4.0)
        _cell(store)(x)  # populate
        alien = aot_mod.AotStore(
            str(tmp_path), fingerprint={"jax": "not-this-runtime"})
        assert alien.load(repr(("unit", "double")),
                          aot_mod.call_signature((x,), {}))[0] == "miss"
        c = _cell(alien)
        assert list(c(x)) == [0.0, 2.0, 4.0, 6.0]  # traced
        assert alien.stats_snapshot()["miss"] == 1
        assert alien.stats_snapshot()["saved"] == 1
        cells = aot_mod.AotStore(str(tmp_path)).manifest()["cells"]
        assert sorted(c["fingerprint_id"] for c in cells.values()) \
            == sorted([store.fp_id, alien.fp_id])
        # and the first runtime still loads its own
        again = aot_mod.AotStore(str(tmp_path))
        _cell(again)(x)
        assert again.stats_snapshot()["hit"] == 1

    def test_corrupt_artifact_falls_back_and_backfills(self, tmp_path,
                                                       monkeypatch):
        monkeypatch.setenv("SDTPU_JOURNAL", "1")    # part of the id
        store = aot_mod.AotStore(str(tmp_path))
        x = jnp.arange(4.0)
        _cell(store)(x)
        (cell,) = store.manifest()["cells"].values()
        with open(tmp_path / cell["file"], "wb") as f:
            f.write(b"truncated garbage")  # content hash now diverges
        obs_journal.JOURNAL.clear()
        store2 = aot_mod.AotStore(str(tmp_path))
        c = _cell(store2)
        assert list(c(x)) == [0.0, 2.0, 4.0, 6.0]
        stats = store2.stats_snapshot()
        assert stats["fallback"] == 1 and stats["hit"] == 0
        assert stats["saved"] == 1  # the fresh trace re-filled the cell
        events = obs_journal.JOURNAL.snapshot()["events"]
        fb = [e for e in events if e["event"] == "aot_fallback"]
        assert fb and fb[0]["attrs"]["reason"] == "corrupt"
        store3 = aot_mod.AotStore(str(tmp_path))
        _cell(store3)(x)
        assert store3.stats_snapshot()["hit"] == 1

    def test_damaged_manifest_is_an_empty_store(self, tmp_path):
        (tmp_path / aot_mod.MANIFEST_NAME).write_text("{not json")
        store = aot_mod.AotStore(str(tmp_path))
        assert store.manifest()["cells"] == {}
        assert _cell(store)(jnp.arange(4.0)) is not None
        assert store.stats_snapshot()["saved"] == 1

    def test_verify_flags_divergence_and_orphans(self, tmp_path):
        store = aot_mod.AotStore(str(tmp_path))
        _cell(store)(jnp.arange(4.0))
        assert store.verify()["ok"]
        (cell,) = store.manifest()["cells"].values()
        with open(tmp_path / cell["file"], "wb") as f:
            f.write(b"flip")
        v = store.verify()
        assert not v["ok"] and v["cells"][0]["status"] == "sha_mismatch"
        os.remove(tmp_path / cell["file"])
        assert store.verify()["cells"][0]["status"] == "missing"
        (tmp_path / ("deadbeef" + aot_mod.ARTIFACT_SUFFIX)).write_bytes(
            b"unclaimed")
        v = store.verify()
        assert v["orphans"] == ["deadbeef" + aot_mod.ARTIFACT_SUFFIX]

    def test_two_writers_of_one_directory_keep_both_cells(self, tmp_path):
        """A pool's workers share the directory: a store object that read
        the manifest before another wrote to it does not write over the
        other's cell."""
        a = aot_mod.AotStore(str(tmp_path))
        b = aot_mod.AotStore(str(tmp_path))
        assert a.manifest()["cells"] == {} == b.manifest()["cells"]
        a.save("('k', 1)", "d0", "k", b"one")
        b.save("('k', 2)", "d0", "k", b"two")
        cells = aot_mod.AotStore(str(tmp_path)).manifest()["cells"]
        assert sorted(c["key"] for c in cells.values()) \
            == ["('k', 1)", "('k', 2)"]

    def test_an_executable_that_does_not_serialize_is_refused_once(
            self, tmp_path, monkeypatch):
        """Nothing is written for it, and a later process does not try
        again; it traces."""
        def broken(exe):
            raise ValueError("Compilation does not support serialization")

        monkeypatch.setattr(aot_mod, "_serialize_compiled", broken)
        store = aot_mod.AotStore(str(tmp_path))
        x = jnp.arange(4.0)
        assert list(_cell(store)(x)) == [0.0, 2.0, 4.0, 6.0]
        assert store.stats_snapshot()["refused"] == 1
        assert store.stats_snapshot()["saved"] == 0
        (cell,) = store.manifest()["cells"].values()
        assert cell["refused"].startswith("save:")
        assert not [f for f in os.listdir(tmp_path)
                    if f.endswith(aot_mod.ARTIFACT_SUFFIX)]
        assert store.verify()["ok"]
        tried = []
        monkeypatch.setattr(aot_mod, "_serialize_compiled",
                            lambda exe: tried.append(exe) or b"")
        later = aot_mod.AotStore(str(tmp_path))
        assert list(_cell(later)(x)) == [0.0, 2.0, 4.0, 6.0]
        assert later.stats_snapshot() == {"hit": 0, "miss": 1, "saved": 0,
                                          "fallback": 0, "refused": 0}
        assert not tried

    def test_an_artifact_that_does_not_load_is_refused_once(
            self, tmp_path, monkeypatch):
        """Found at the first load, not at the save: that process traces
        (journaled as a fallback), marks the cell, and the one after it
        neither loads nor keeps it again."""
        store = aot_mod.AotStore(str(tmp_path))
        x = jnp.arange(4.0)
        _cell(store)(x)
        real = aot_mod._deserialize_compiled
        loads = []

        def broken(blob):
            loads.append(1)
            raise RuntimeError("NOT_FOUND: add_convert_fusion")

        monkeypatch.setattr(aot_mod, "_deserialize_compiled", broken)
        later = aot_mod.AotStore(str(tmp_path))
        assert list(_cell(later)(x)) == [0.0, 2.0, 4.0, 6.0]
        stats = later.stats_snapshot()
        assert stats["fallback"] == 1 and stats["refused"] == 1
        assert stats["saved"] == 0
        (cell,) = later.manifest()["cells"].values()
        assert cell["refused"].startswith("load:")
        assert "add_convert_fusion" in cell["refused"]
        last = aot_mod.AotStore(str(tmp_path))
        assert list(_cell(last)(x)) == [0.0, 2.0, 4.0, 6.0]
        assert last.stats_snapshot() == {"hit": 0, "miss": 1, "saved": 0,
                                         "fallback": 0, "refused": 0}
        assert len(loads) == 1
        monkeypatch.setattr(aot_mod, "_deserialize_compiled", real)


class TestCallPath:
    def test_weights_are_not_in_what_a_call_is_told_apart_by(
            self, tmp_path, monkeypatch):
        """The full signature (every leaf of the weights) is taken once an
        executable, at its lookup; a call takes the shapes behind the
        weights alone."""
        taken = []
        real = aot_mod.call_signature
        monkeypatch.setattr(
            aot_mod, "call_signature",
            lambda *a, **k: taken.append(1) or real(*a, **k))
        store = aot_mod.AotStore(str(tmp_path))
        cell = aot_mod.AotFunction(
            ("unit", "affine"),
            lambda: jax.jit(lambda w, x: w["a"] * x + w["b"]),
            weights=1, store=store)
        w = {"a": jnp.float32(2.0), "b": jnp.float32(1.0)}
        sig = cell.bound_signature((w, jnp.arange(4.0)), {})
        assert sig == cell.bound_signature(
            ({"other": jnp.zeros((3, 3))}, jnp.arange(4.0)), {})
        assert sig != cell.bound_signature((w, jnp.arange(8.0)), {})
        for _ in range(3):
            assert list(cell(w, jnp.arange(4.0))) == [1.0, 3.0, 5.0, 7.0]
        assert len(taken) == 1 and cell.executable_count() == 1
        cell(w, jnp.arange(8.0))
        assert len(taken) == 2 and cell.executable_count() == 2

    def test_static_values_tell_executables_apart(self, tmp_path):
        store = aot_mod.AotStore(str(tmp_path))
        cell = aot_mod.AotFunction(
            ("unit", "scale"),
            lambda: jax.jit(lambda x, n: x * n, static_argnums=(1,)),
            static_argnums=(1,), weights=0, store=store)
        x = jnp.arange(3.0)
        assert list(cell(x, 2)) == [0.0, 2.0, 4.0]
        assert list(cell(x, 3)) == [0.0, 3.0, 6.0]
        assert cell.executable_count() == 2
        warm = aot_mod.AotFunction(
            ("unit", "scale"),
            lambda: jax.jit(lambda x, n: x * n, static_argnums=(1,)),
            static_argnums=(1,), weights=0,
            store=aot_mod.AotStore(str(tmp_path)))
        assert list(warm(x, 3)) == [0.0, 3.0, 6.0]
        assert warm._store().stats_snapshot()["hit"] == 1

    def test_a_loaded_executable_donates_like_jit(self, tmp_path):
        def build():
            return jax.jit(lambda w, x: x + w, donate_argnums=(1,))

        w = jnp.float32(1.0)
        cold = aot_mod.AotFunction(("unit", "donate"), build,
                                   store=aot_mod.AotStore(str(tmp_path)))
        x = jnp.arange(4.0)
        assert list(cold(w, x)) == [1.0, 2.0, 3.0, 4.0]
        assert x.is_deleted()
        store = aot_mod.AotStore(str(tmp_path))
        warm = aot_mod.AotFunction(("unit", "donate"), build, store=store)
        y = jnp.arange(4.0)
        assert list(warm(w, y)) == [1.0, 2.0, 3.0, 4.0]
        assert store.stats_snapshot()["hit"] == 1
        assert y.is_deleted()

    def test_a_call_under_a_trace_inlines_the_jitted_function(
            self, tmp_path):
        store = aot_mod.AotStore(str(tmp_path))
        inner = _cell(store)
        outer = jax.jit(lambda x: inner(x) + 1.0)
        assert list(outer(jnp.arange(3.0))) == [1.0, 3.0, 5.0]
        assert inner.executable_count() == 0
        assert store.manifest()["cells"] == {}

    def test_another_context_is_another_cell(self, tmp_path):
        """Two models whose keys and parameter shapes are alike (the
        expander's keys name no model) do not meet."""
        store = aot_mod.AotStore(str(tmp_path))
        x = jnp.arange(4.0)
        _cell(store, context="window=1024")(x)
        other = aot_mod.AotStore(str(tmp_path))
        _cell(other, context="window=2048")(x)
        assert other.stats_snapshot()["hit"] == 0
        assert other.stats_snapshot()["miss"] == 1
        assert len(other.manifest()["cells"]) == 2


# -- the engine path ---------------------------------------------------------

class TestEngineLoads:
    def test_second_engine_loads_every_stage_and_traces_none(self, placed):
        """The acceptance bar: a restarted engine over a filled store
        traces NOTHING (every stage deserializes; jax's own trace events
        for the stage functions stay silent), gives the same image bytes,
        and the trace-time counters read what the first engine's read."""
        cold, programs, traced, sites = run()
        assert programs["loaded"] == 0 and programs["traced"] >= 3
        assert traced == STAGE_FUNCTIONS
        stages = programs["traced"]
        warm, programs, traced, warm_sites = run()
        assert warm == cold
        assert programs["traced"] == 0 and programs["loaded"] == stages
        assert programs["load_s"] > 0
        assert traced == set()
        assert warm_sites == sites and sites[0]["xla"] > 0
        summary = METRICS.summary()
        assert summary["aot_loads"].get("chunk") == 1
        assert summary["aot_loads"].get("encode") == 1
        store = aot_mod.get_store()
        assert store.root == placed and store.verify()["ok"]
        kinds = {c["kind"] for c in store.manifest()["cells"].values()}
        assert {"encode", "chunk", "decode-u8"} <= kinds

    @pytest.mark.parametrize("changed", ["source", "env",
                                         "platform_version", "family"])
    def test_what_a_program_was_made_from_is_in_its_id(
            self, placed, monkeypatch, changed):
        """An edited source, a changed SDTPU_* value, another build of the
        backend, another model configuration: each misses and traces, and
        leaves the first tree's programs where they were."""
        cold, programs, _, _ = run()
        stages = programs["traced"]
        with monkeypatch.context() as m:
            if changed == "source":
                m.setattr(aot_mod, "source_digest", lambda: "edited")
            elif changed == "env":
                m.setenv("SDTPU_SOME_KNOB", "2")
            elif changed == "platform_version":
                real = aot_mod.runtime_fingerprint()
                m.setattr(aot_mod, "runtime_fingerprint", lambda: dict(
                    real, platform_version="another libtpu build"))
            else:
                import dataclasses

                m.setattr(Engine, "_program_context", lambda self: repr(
                    dataclasses.replace(self.family, name="tiny-other")))
            images, programs, traced, _ = run()
            assert (programs["loaded"], programs["traced"],
                    programs["load_s"]) == (0, stages, 0.0)
            assert traced == STAGE_FUNCTIONS
            assert images == cold
        images, programs, traced, _ = run()
        assert programs["traced"] == 0 and programs["loaded"] == stages
        assert images == cold

    def test_engines_of_other_weights_share_programs_not_images(
            self, placed):
        """Weights are arguments: a second engine with other weights loads
        the first's programs and gives its own images."""
        other = jax.tree_util.tree_map(lambda a: a * 1.05 + 0.01,
                                       init_params(TINY))
        jax.config.update("jax_compilation_cache_dir", None)
        want, programs, _, _ = run(params=other)    # the plain jax.jit
        assert programs["loaded"] == 0
        _place(os.path.dirname(placed))
        first, _, _, _ = run()
        got, programs, traced, _ = run(params=other)
        assert programs["traced"] == 0 and traced == set()
        assert got == want and got != first

    def test_a_program_the_compile_cache_handed_over_loads_or_is_refused(
            self, placed, monkeypatch):
        """The order of PR 34's defect: the persistent cache holds the
        executables (a first engine compiled them), the store is empty, so
        what would be serialized is what the cache handed over, and on
        XLA:CPU that loads and then fails when it runs. Every cell is
        kept or refused, none crashes, and a third engine loads the kept
        ones, traces the refused ones and gives the same bytes."""
        from stable_diffusion_webui_distributed_tpu.runtime import mesh
        from stable_diffusion_webui_distributed_tpu.serving.bucketer import (
            ShapeBucketer,
        )
        from stable_diffusion_webui_distributed_tpu.serving.warmup import (
            warmup_engine,
        )

        # tests/test_serving.py's warm-up, first: it fills the compile cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(mesh, "DEFAULT_COMPILE_CACHE",
                            os.path.dirname(placed))
        warmup_engine(fresh_engine(),
                      ShapeBucketer(shapes=[(32, 32)], batches=[1]),
                      steps=4, sampler="Euler a")
        assert aot_mod.store_dir() == placed
        cold, _, _, _ = run()
        shutil.rmtree(placed)
        second, programs, _, _ = run()
        stats = aot_mod.get_store().stats_snapshot()
        assert second == cold
        assert programs["loaded"] == 0
        assert stats["saved"] + stats["refused"] == programs["traced"]
        third, programs, _, _ = run()
        assert third == cold
        assert programs["loaded"] == stats["saved"]
        assert programs["traced"] == stats["refused"]
        # what was refused was not marked: the cell stays open for a
        # process that compiles it itself (this one was handed it again)
        again = aot_mod.get_store().stats_snapshot()
        assert again["refused"] == stats["refused"]
        assert again["saved"] == 0 and again["fallback"] == 0
        assert aot_mod.get_store().verify()["ok"]

    def test_no_cache_directory_placed_nothing_kept_or_written(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("HOME", str(tmp_path / "home"))
        monkeypatch.chdir(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        assert aot_mod.store_dir() is None and aot_mod.get_store() is None
        restart()
        engine = fresh_engine()
        engine.txt2img(payload(seed=41))
        assert not any(isinstance(fn, aot_mod.AotFunction)
                       for fn in engine._cache.values())
        programs = METRICS.summary()["programs"]
        assert programs["loaded"] == 0 and programs["traced"] >= 3
        written = [os.path.join(d, f) for d, _, files in os.walk(tmp_path)
                   for f in files if not f.endswith(".log")]
        assert written == []


# -- warm pool ---------------------------------------------------------------

class TestWarmPool:
    def _pool(self, size=2):
        made = []

        def factory(name):
            made.append(name)
            return {"engine": name}

        return fleet_pool.WarmPool(factory, size=size), made

    def test_heal_to_target_and_least_loaded_checkout(self):
        pool, made = self._pool(size=2)
        assert pool.heal() == ["resident-1", "resident-2"]
        a = pool.acquire()
        b = pool.acquire()
        assert {a.name, b.name} == {"resident-1", "resident-2"}
        pool.release(a)
        pool.release(b)
        assert pool.summary()["ready"] == 2
        assert all(r["inflight"] == 0
                   for r in pool.summary()["residents"])

    def test_kill_isolates_inflight_and_heal_respawns(self):
        pool, made = self._pool(size=2)
        pool.heal()
        res = pool.acquire()  # inflight work on resident-1
        assert pool.kill(res.name)
        assert not pool.kill(res.name)  # already dead
        # the dead resident takes no new checkouts; its inflight work
        # keeps its own engine (no double-merge onto a replacement)
        other = pool.acquire()
        assert other.name != res.name
        assert res.state == "dead" and res.inflight == 1
        healed = pool.heal()
        assert healed == ["resident-3"]
        assert pool.summary()["ready"] == 2
        pool.release(res)
        pool.release(other)

    def test_retire_refuses_last_ready_resident(self):
        pool, _ = self._pool(size=1)
        pool.heal()
        assert pool.retire_one() is None
        pool.spawn()
        assert pool.retire_one() is not None
        assert pool.retire_one() is None

    def test_empty_pool_acquire_spawns(self):
        pool, made = self._pool(size=2)
        res = pool.acquire()
        assert made == ["resident-1"]
        assert res.inflight == 1
        pool.release(res)

    def test_autoscale_decisions_get_executed(self, monkeypatch):
        """up -> spawn, down -> retire, and the audit ring's execution
        field records it (the /internal/autoscale contract)."""
        pool, _ = self._pool(size=2)
        pool.heal()
        reg = SliceRegistry()
        reg.register(SliceInfo("s0", max_replicas=3))
        p95 = [10.0]
        eng = AutoscaleEngine(reg, quantile_source=lambda: p95[0],
                              up_p95_s=5.0, down_p95_s=0.5,
                              cooldown_s=0.0)
        pool.attach_autoscale(eng)
        (up,) = eng.decide()
        assert up.direction == "up"
        assert pool.summary()["ready"] == 3
        p95[0] = 0.1
        (down,) = eng.decide()
        assert down.direction == "down"
        assert pool.summary()["ready"] == 2
        outcomes = [(e["direction"], e["execution"]["outcome"])
                    for e in eng.audit()["decisions"]]
        assert outcomes == [("up", "executed"), ("down", "executed")]

    def test_autoscale_cooldown_reports_failed(self):
        pool, _ = self._pool(size=2)
        pool.cooldown_s = 3600.0
        pool.heal()
        reg = SliceRegistry()
        reg.register(SliceInfo("s0", max_replicas=3))
        eng = AutoscaleEngine(reg, quantile_source=lambda: 10.0,
                              up_p95_s=5.0, down_p95_s=0.5,
                              cooldown_s=0.0)
        pool.attach_autoscale(eng)
        eng.decide()  # first execution consumes the cooldown window
        eng.decide()
        entries = eng.audit()["decisions"]
        assert entries[0]["execution"]["outcome"] == "executed"
        assert entries[1]["execution"] == {
            "outcome": "failed", "detail": "cooldown",
            "executed_at": entries[1]["execution"]["executed_at"]}

    def test_module_level_active_pool(self):
        pool, _ = self._pool()
        fleet_pool.set_pool(pool)
        try:
            assert fleet_pool.get_pool() is pool
        finally:
            fleet_pool.set_pool(None)
        assert fleet_pool.get_pool() is None


class TestDispatcherCheckout:
    def test_checkout_routes_to_resident_and_restores(self, monkeypatch):
        from stable_diffusion_webui_distributed_tpu.serving.dispatcher \
            import ServingDispatcher

        pool = fleet_pool.WarmPool(lambda name: {"engine": name}, size=1)
        pool.heal()
        disp = ServingDispatcher(engine="primary", window=0.0, pool=pool)
        monkeypatch.setenv("SDTPU_POOL", "1")
        assert disp._engine() == "primary"
        with disp._checkout_engine() as eng:
            assert eng == {"engine": "resident-1"}
            assert disp._engine() is eng  # stage helpers follow the lease
        assert disp._engine() == "primary"
        assert pool.summary()["residents"][0]["inflight"] == 0

    def test_gate_off_checkout_is_primary(self):
        from stable_diffusion_webui_distributed_tpu.serving.dispatcher \
            import ServingDispatcher

        pool = fleet_pool.WarmPool(lambda name: {"engine": name}, size=1)
        disp = ServingDispatcher(engine="primary", window=0.0, pool=pool)
        with disp._checkout_engine() as eng:  # SDTPU_POOL unset
            assert eng == "primary"
        assert pool.summary()["spawns_total"] == 0

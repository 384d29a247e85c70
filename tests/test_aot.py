"""AOT executable artifacts (SDTPU_AOT, serving/aot.py) + warm engine
pool (SDTPU_POOL, fleet/pool.py).

The contract under test: a warm engine hydrates every compiled stage
from the artifact store byte-for-byte (zero fresh chunk compiles, same
images), a fingerprint mismatch or damaged artifact FALLS BACK to a
fresh compile (journaled, never a crash, never a wrong executable), and
with the gate off ``Engine._cached`` takes its pre-existing path —
hash-pinned through tests/goldens.json. The pool side: least-loaded
checkout, chaos-kill isolation (inflight work keeps its engine), heal
to target size, and autoscale decisions upgraded from ``no_executor``
to ``executed``/``failed`` in the audit ring.
"""

import os

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
from stable_diffusion_webui_distributed_tpu.serving.metrics import METRICS
from test_goldens import _check
from test_pipeline import init_params


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A test that ran earlier in this process may have placed JAX's
    persistent cache (serving/warmup.py through tests/test_serving.py): a
    CPU executable that the "cold" engine is then handed from that cache
    serializes to an artifact whose functions are not found at load. The
    store under test is SDTPU_AOT's: keep the other cache off around
    these."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def payload(**kw):
    defaults = dict(prompt="an aot cow", steps=4, width=32, height=32,
                    seed=7, sampler_name="Euler a")
    defaults.update(kw)
    return GenerationPayload(**defaults)


def fresh_engine():
    return Engine(TINY, init_params(TINY), chunk_size=4,
                  state=GenerationState())


# -- unit plumbing over a tiny jit cell --------------------------------------

def _double_build():
    return jax.jit(lambda x: x * 2.0)


def _cell(store):
    return aot_mod.AotFunction(("unit", "double"), _double_build,
                               store=store)


class TestStoreUnit:
    def test_miss_save_then_hit_across_instances(self, tmp_path):
        store = aot_mod.AotStore(str(tmp_path))
        x = jnp.arange(4.0)
        a = _cell(store)
        assert list(a(x)) == [0.0, 2.0, 4.0, 6.0]
        assert store.stats_snapshot() == {"hit": 0, "miss": 1,
                                          "saved": 1, "fallback": 0}
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

    def test_fingerprint_mismatch_falls_back_and_journals(
            self, tmp_path, monkeypatch):
        store = aot_mod.AotStore(str(tmp_path))
        x = jnp.arange(4.0)
        _cell(store)(x)  # populate
        alien = aot_mod.AotStore(
            str(tmp_path), fingerprint={"jax": "not-this-runtime"})
        assert alien.load(repr(("unit", "double")),
                          aot_mod.call_signature((x,), {}))[0] \
            == "fingerprint_mismatch"
        monkeypatch.setenv("SDTPU_JOURNAL", "1")
        obs_journal.JOURNAL.clear()
        c = _cell(alien)
        assert list(c(x)) == [0.0, 2.0, 4.0, 6.0]  # fell back to compile
        assert alien.stats_snapshot()["fallback"] == 1
        events = obs_journal.JOURNAL.snapshot()["events"]
        fb = [e for e in events if e["event"] == "aot_fallback"]
        assert fb and fb[0]["attrs"]["reason"] == "fingerprint_mismatch"

    def test_corrupt_artifact_falls_back_and_backfills(self, tmp_path):
        store = aot_mod.AotStore(str(tmp_path))
        x = jnp.arange(4.0)
        _cell(store)(x)
        (cell,) = store.manifest()["cells"].values()
        with open(tmp_path / cell["file"], "wb") as f:
            f.write(b"truncated garbage")  # content hash now diverges
        store2 = aot_mod.AotStore(str(tmp_path))
        c = _cell(store2)
        assert list(c(x)) == [0.0, 2.0, 4.0, 6.0]
        stats = store2.stats_snapshot()
        assert stats["fallback"] == 1 and stats["hit"] == 0
        assert stats["saved"] == 1  # the fresh compile re-filled the cell
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


# -- the engine path ---------------------------------------------------------

class TestEngineHydration:
    def test_warm_engine_hydrates_byte_identical(self, tmp_path,
                                                 monkeypatch):
        """The acceptance bar: a restarted engine over a populated store
        compiles NOTHING (every stage deserializes) and produces the
        same image bytes."""
        monkeypatch.setenv("SDTPU_AOT", "1")
        monkeypatch.setenv("SDTPU_AOT_DIR", str(tmp_path))
        p = payload(seed=41)
        METRICS.clear()
        cold = fresh_engine().txt2img(p)
        s = METRICS.summary()
        assert s["compiles"].get("chunk") == 1
        assert not s["aot_loads"]
        METRICS.clear()
        warm = fresh_engine().txt2img(p)
        s = METRICS.summary()
        assert warm.images == cold.images
        assert warm.seeds == cold.seeds
        assert s["compiles"] == {}  # zero fresh compiles of ANY kind
        assert s["aot_loads"].get("chunk") == 1
        assert s["aot_loads"].get("encode") == 1
        store = aot_mod.get_store()
        assert store.verify()["ok"]
        manifest = store.manifest()
        kinds = {c["kind"] for c in manifest["cells"].values()}
        assert {"encode", "chunk"} <= kinds


class TestGateOff:
    def test_gate_off_golden_pin(self):
        """SDTPU_AOT=0 (the default) is hash-pinned: the AOT landing must
        leave the plain ``Engine._cached`` path byte-identical, and every
        later PR inherits the pin."""
        assert not aot_mod.enabled()
        p = payload(prompt="aot gate pin", seed=77, n_iter=2)
        _check("aot/gate-off", fresh_engine().txt2img(p))


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

"""pipeline/denoise.py: the one place a denoise executable is built.

- every variant built through ``build()`` returns what a plain Python loop
  over the shared parts returns (no scan, no jit, no ``lax.cond``), on the
  tiny families in float32;
- the residual stage's rows are the fused chunk's rows;
- the variant record refuses what no executable serves;
- the cache key is field for field the tuple the engine used before the
  record existed, and the census reads it by name;
- one request traces ``run_chunk`` once, and nothing prices the UNet.
"""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import quality
from stable_diffusion_webui_distributed_tpu.models import lora as lora_mod
from stable_diffusion_webui_distributed_tpu.models.configs import (
    TINY, TINY_INPAINT, TINY_REFINER, TINY_XL,
)
from stable_diffusion_webui_distributed_tpu.models.controlnet import (
    ControlNet,
)
from stable_diffusion_webui_distributed_tpu.models.unet import (
    UNet, deep_cache_shape, make_added_cond,
)
from stable_diffusion_webui_distributed_tpu.obs import perf as obs_perf
from stable_diffusion_webui_distributed_tpu.obs import spans as obs_spans
from stable_diffusion_webui_distributed_tpu.pipeline import denoise as D
from stable_diffusion_webui_distributed_tpu.pipeline import (
    engine as engine_mod,
)
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
)
from stable_diffusion_webui_distributed_tpu.samplers import kdiffusion as kd
from stable_diffusion_webui_distributed_tpu.samplers import schedules as sched
from stable_diffusion_webui_distributed_tpu.runtime.kept import KeptTable
from stable_diffusion_webui_distributed_tpu.serving.metrics import PLAN, XLA
from test_lora_traced import make_lora_sd

STEPS, START, LENGTH, B, LAT = 4, 1, 2, 2, 4
SCHEDULE = sched.sd_schedule()


def _rand(seed, shape, scale=1.0):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32) * scale


@pytest.fixture(scope="module")
def nets():
    """{family name: (Deps, unet params)}, and one ControlNet's params with
    its zero convolutions filled, so that its residuals are not zero."""
    out = {}
    ctx = jnp.zeros((2, 77, TINY.unet.cross_attention_dim))
    for family in (TINY, TINY_INPAINT):
        unet = UNet(family.unet)
        out[family.name] = (
            D.Deps(unet, ControlNet(family.unet), SCHEDULE),
            unet.init(jax.random.key(3), jnp.zeros(
                (2, LAT, LAT, family.unet.in_channels)), jnp.ones((2,)),
                ctx)["params"])
    cn = ControlNet(TINY.unet).init(
        jax.random.key(5), jnp.zeros((2, LAT, LAT, 4)), jnp.ones((2,)), ctx,
        jnp.zeros((2, LAT * 8, LAT * 8, 3)))["params"]
    leaves, tree = jax.tree_util.tree_flatten(cn)
    out["controlnet"] = jax.tree_util.tree_unflatten(tree, [
        leaf if np.any(np.asarray(leaf)) else _rand(40 + i, leaf.shape, 0.05)
        for i, leaf in enumerate(leaves)])
    return out


def _inputs(**more):
    ctx = TINY.unet.cross_attention_dim
    return D.Inputs(_rand(1, (1, 77, ctx)), _rand(2, (1, 77, ctx)),
                    jnp.float32(6.5), jax.random.split(jax.random.key(9), B),
                    **more)


def _controls(nets):
    return ((nets["controlnet"], _rand(6, (1, LAT * 8, LAT * 8, 3)), 0.8,
             0.0, 1.0),)


def _lora_rows(params):
    ts = lora_mod.build_traced_set(
        (("a", 0.8, 0.8),), {"a": make_lora_sd(seed=1, te=False)}.get, TINY,
        {"unet": params})
    return lora_mod.broadcast_set(ts, B)["unet"]


def plain_loop(v, deps, params, inp, x, length=LENGTH):
    """Euler over steps START … START + length of variant ``v``, one op at a
    time: Python ``if`` where the executable has ``lax.cond``, a ``for``
    where it scans."""
    cache, valid = None, False
    sigmas = kd.build_sigmas(kd.resolve_sampler("Euler"), SCHEDULE, v.steps)
    v_pred = SCHEDULE.prediction_type == "v_prediction"
    P = {"params": params}
    lora2 = (None if inp.lora is None else jax.tree_util.tree_map(
        lambda a: jnp.concatenate([a, a]), inp.lora))
    for i in range(START, START + length):
        sigma = sigmas[i]
        xin, t = D.scale_in(SCHEDULE, x, sigma)
        trunc = v.step_cache and i >= int(inp.cfg_stop)
        latent, unet_in, tb, ctx, added = D.cfg_rows(
            xin, t, inp, v.inpaint, trunc)
        lora = inp.lora if trunc else lora2
        kw = {}
        if v.step_cache:
            if not valid or i % int(inp.cadence) == 0:
                cache = deps.unet.apply(P, unet_in, tb, ctx, added,
                                        cache_mode="deep", lora=lora)
                cache = jnp.concatenate([cache, cache]) if trunc else cache
                valid = True
            kw = {"cache": cache[B:] if trunc else cache,
                  "cache_mode": "reuse"}
        if v.ragged:
            true_rows, ctx_true_u, ctx_true_c = inp.ragged
            kw = {"true_rows": jnp.concatenate([true_rows, true_rows]),
                  "ctx_true": jnp.concatenate([ctx_true_u, ctx_true_c])}
        residuals = D.control_residuals(
            deps.controlnet, inp.controls, latent, tb, ctx, added,
            jnp.int32(i), v.steps) if inp.controls else None
        out = deps.unet.apply(P, unet_in, tb, ctx, added,
                              control_residuals=residuals, lora=lora, **kw)
        guided = out.astype(jnp.float32) if trunc else D.guide(out, inp.cfg)
        x0 = D.to_x0(x, sigma, guided, v_pred)
        x = x + (x - x0) / sigma * (sigmas[i + 1] - sigma)
        if v.masked:
            x = D.pin_unmasked(x, inp.mask_lat, inp.init_lat, inp.image_keys,
                               sigmas[i + 1], 1_000_000 + i)
        if v.ragged:
            rows = jnp.arange(x.shape[1])[None, :] < true_rows[:, None]
            x = jnp.where(rows[:, :, None, None], x, 0.0)
    return x


def chunk_case(name, nets):
    """(variant, deps, params, inputs) of a fixed-step case."""
    family = TINY_INPAINT if name == "inpaint-cond" else TINY
    deps, params = nets[family.name]
    static, more = {}, {}
    if name == "masked":
        static = {"masked": True}
        more = {"mask_lat": (_rand(11, (B, LAT, LAT, 1)) > 0).astype(
            jnp.float32), "init_lat": _rand(12, (B, LAT, LAT, 4))}
    elif name == "inpaint-cond":
        static = {"inpaint": True}
        more = {"inpaint_cond": _rand(13, (B, LAT, LAT, 5))}
    elif name == "ragged":
        static = {"ragged": True}
        more = {"ragged": (jnp.array([LAT, LAT - 1], jnp.int32),
                           jnp.array([77, 40], jnp.int32),
                           jnp.array([60, 77], jnp.int32))}
    elif name.startswith("step-cache"):
        # cutoff 9: every step full CFG; cutoff 2: step 1 full, step 2 the
        # cond half alone, the feature kept from step 1 (cadence 4)
        static = {"step_cache": True}
        more = {"cadence": jnp.int32(4),
                "cfg_stop": jnp.int32(9 if name.endswith("full") else 2)}
    elif name == "traced-lora":
        static = {"lora_sig": "lora:r8s1"}
        more = {"lora": _lora_rows(params)}
    elif name == "controlnet-fused":
        static = {"n_controls": 1}
        more = {"controls": _controls(nets)}
    v = D.Variant("chunk", "Euler", STEPS, LAT * 8, LAT * 8, B, LENGTH,
                  family=family.name, precision="bf16", **static)
    return v, deps, params, _inputs(**more)


CHUNK_CASES = ("plain", "masked", "inpaint-cond", "ragged",
               "step-cache-full", "step-cache-past-cutoff", "traced-lora",
               "controlnet-fused")


@pytest.mark.parametrize("name", CHUNK_CASES)
def test_built_chunk_is_the_plain_loop_over_the_parts(name, nets):
    v, deps, params, inp = chunk_case(name, nets)
    x = _rand(20, (B, LAT, LAT, 4), 3.0)
    if v.ragged:
        x = x.at[1, LAT - 1].set(0.0)
    want = plain_loop(v, deps, params, inp, x)
    state = kd.init_carry(x)
    if v.step_cache:
        state = D.CachedState(state, jnp.zeros(
            deep_cache_shape(TINY.unet, 2 * B, LAT, LAT)), jnp.asarray(False))
    state, fence = D.build(v, deps)(params, state, jnp.int32(START), inp)
    got = state.carry.x if v.step_cache else state.x
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert float(fence[0]) == float(got.reshape(-1)[0])
    if v.step_cache:
        assert bool(state.valid)


def test_adaptive_attempt_is_the_solver_over_the_parts(nets):
    deps, params = nets[TINY.name]
    inp = _inputs()
    v = D.Variant("adaptive", width=LAT * 8, height=LAT * 8, batch=B,
                  family=TINY.name, precision="bf16")

    def denoise(x, sigma, step):
        xin, t = D.scale_in(SCHEDULE, x, sigma)
        _, unet_in, tb, ctx, added = D.cfg_rows(xin, t, inp)
        out = deps.unet.apply({"params": params}, unet_in, tb, ctx, added)
        return D.to_x0(x, sigma, D.guide(out, inp.cfg), False)

    x = _rand(22, (B, LAT, LAT, 4), 3.0)
    args = (x, x, jnp.float32(-1.0), jnp.float32(0.2), jnp.float32(0.05),
            jnp.float32(0.0078))
    want = kd.make_adaptive_attempt(denoise)(*args)
    got = D.build(v, deps)(params, *args, inp)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def test_adaptive_pin_keeps_its_own_noise_domain():
    x, init = _rand(30, (B, LAT, LAT, 4)), _rand(31, (B, LAT, LAT, 4))
    mask = (_rand(32, (B, LAT, LAT, 1)) > 0).astype(jnp.float32)
    keys = jax.random.split(jax.random.key(9), B)
    pin = D.build(D.Variant("adaptive-pin", family=TINY.name), None)
    got = pin(x, mask, init, keys, jnp.float32(0.7), jnp.int32(3))
    noise = jax.vmap(lambda k: jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(k, 2_000_000), 3),
        init.shape[1:], jnp.float32))(keys)
    np.testing.assert_allclose(
        got, mask * x + (1 - mask) * (init + noise * 0.7), rtol=1e-5)
    fixed_grid = D.pin_unmasked(x, mask, init, keys, jnp.float32(0.7),
                                1_000_000 + 3)
    assert not np.allclose(got, fixed_grid)


def test_residual_stage_rows_are_the_fused_rows_bit_for_bit():
    """The staged stage and the fused chunk take their rows from one
    ``cfg_rows``: traced or not, the same bits, and the cond-only form is
    the cond half of the doubled one."""
    inp = _inputs(added_u=_rand(3, (1, 6)), added_c=_rand(4, (1, 6)))
    xin, t = _rand(23, (B, LAT, LAT, 4)), jnp.float32(500.0)
    fused = D.cfg_rows(xin, t, inp)
    staged = jax.jit(lambda a, b: D.cfg_rows(a, b, inp))(xin, t)
    cond = D.cfg_rows(xin, t, inp, cond_only=True)
    assert fused[0].shape == (2 * B, LAT, LAT, 4) and fused[1] is fused[0]
    for f, s, c in zip(fused, staged, cond):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(s))
        np.testing.assert_array_equal(np.asarray(f)[B:], np.asarray(c))


@pytest.mark.parametrize("fields", [
    {"ragged": True, "step_cache": True},
    {"ragged": True, "masked": True},
    {"ragged": True, "n_controls": 1},
    {"ragged": True, "inpaint": True},
    {"step_cache": True, "n_controls": 1},
    {"kind": "adaptive", "lora_sig": "lora:r8s1"},
    {"kind": "adaptive", "step_cache": True},
    {"kind": "chunks"},
], ids=lambda f: "+".join(f"{k}={v}" for k, v in f.items()))
def test_variant_refuses_what_no_executable_serves(fields):
    v = D.Variant("chunk", "Euler", 4, 32, 32, 1, 2)._replace(**fields)
    with pytest.raises(ValueError):
        D.check(v)
    with pytest.raises(ValueError):
        D.build(v, None)


@pytest.mark.parametrize("kind", ["chunk", "adaptive", "adaptive-pin"])
def test_check_accepts_the_three_kinds(kind):
    D.check(D.Variant(kind, "Euler", 4, 32, 32, 1, 2))


#: keys an engine at the parent of the PR that added the record held after
#: a plain, a step-cache and an int8 request on the tiny family
RECORDED = [
    ("chunk", "Euler a", 4, 32, 32, 1, 4, False, 0, False, "tiny", False,
     "", False, "bf16"),
    ("chunk", "Euler a", 6, 32, 32, 1, 4, False, 0, False, "tiny", False,
     "", True, "bf16"),
    ("chunk", "Euler a", 4, 32, 32, 1, 4, False, 0, False, "tiny", False,
     "", False, "int8"),
]


def test_key_is_the_recorded_tuple_and_the_census_reads_it_by_name():
    variants = [
        D.Variant("chunk", "Euler a", 4, 32, 32, 1, 4, family="tiny",
                  precision="bf16"),
        D.Variant("chunk", "Euler a", 6, 32, 32, 1, 4, family="tiny",
                  step_cache=True, precision="bf16"),
        D.Variant("chunk", "Euler a", 4, 32, 32, 1, 4, family="tiny",
                  precision="int8"),
    ]
    assert [v.key() for v in variants] == RECORDED
    assert [D.parse_key(k) for k in RECORDED] == variants
    assert D.parse_key(("decode", 32, 32, 1, "tiny")) is None
    assert D.parse_key(RECORDED[0][:-1]) is None
    census = obs_perf.census_from_keys(
        RECORDED + [("decode", 32, 32, 1, "tiny")])
    assert census["buckets"] == [
        {"bucket": "Euler a/4st 32x32 b1", "executables": 2,
         "step_cache_variants": 1, "precisions": ["bf16", "int8"],
         "lora_variants": 0, "over_budget": False},
        {"bucket": "Euler a/6st 32x32 b1", "executables": 1,
         "step_cache_variants": 1, "precisions": ["bf16"],
         "lora_variants": 0, "over_budget": False}]
    assert (census["chunk_executables"], census["other_executables"],
            census["alarm"]) == (3, 1, False)


def test_other_kinds_keep_their_keys():
    v = D.Variant("chunk", "Euler", 8, 64, 64, 2, n_controls=1,
                  family="tiny", precision="bf16")
    assert v._replace(kind="adaptive", inpaint=True).key() == (
        "adaptive", 64, 64, 2, 1, True, "tiny", "bf16")
    assert v._replace(kind="adaptive-pin").key() == ("adaptive-pin", "tiny")


def test_one_request_traces_run_chunk_once_and_prices_nothing():
    engine = quality.make_engine(TINY, chunk_size=4)
    XLA.clear()
    first = engine.txt2img(GenerationPayload(
        prompt="a cow", steps=4, width=32, height=32, seed=1))
    rows = dict(XLA.functions)
    assert rows["run_chunk"]["traces"] == 1
    assert rows["run_chunk"]["executables"] == 1
    assert "call" not in rows     # FlopsAccountant's second UNet trace
    assert RECORDED[0] in engine.executable_keys()
    engine.txt2img(GenerationPayload(
        prompt="a herd of goats", steps=4, width=32, height=32, seed=77))
    assert XLA.functions["run_chunk"]["traces"] == 1
    assert "call" not in XLA.functions
    assert len(first.images) == 1


# -- what a request's plan keeps by key (runtime/kept.py, PR 38) -------------

def _uncached_added_cond(family, pooled_u, pooled_c, width, height,
                         score=6.0):
    """``Engine._added_cond`` as every request ran it before it kept the
    embedded ids."""
    ucfg = family.unet
    dim = ucfg.addition_time_embed_dim
    n_ids = (ucfg.projection_input_dim - ucfg.addition_embed_dim) // dim
    if n_ids == 5:
        ids_c, ids_u = ([height, width, 0, 0, score],
                        [height, width, 0, 0, 2.5])
    else:
        ids_c = ids_u = [height, width, 0, 0, height, width]
    tid_u = jnp.broadcast_to(jnp.asarray([ids_u], jnp.float32),
                             (pooled_u.shape[0], n_ids))
    tid_c = jnp.broadcast_to(jnp.asarray([ids_c], jnp.float32),
                             (pooled_c.shape[0], n_ids))
    return (make_added_cond(pooled_u, tid_u, dim),
            make_added_cond(pooled_c, tid_c, dim))


def _bits(a):
    a = np.asarray(a)
    return a.dtype, a.shape, a.tobytes()


@pytest.mark.parametrize("rows", [1, 2])
@pytest.mark.parametrize("family", [TINY_XL, TINY_REFINER],
                         ids=["base-6-ids", "refiner-5-ids"])
def test_kept_time_ids_give_the_uncached_added_cond_bit_for_bit(
        family, rows):
    engine_mod._TIME_IDS.clear()
    this = types.SimpleNamespace(family=family)
    width = family.unet.addition_embed_dim
    pooled_u = _rand(11, (rows, width)).astype(jnp.bfloat16)
    pooled_c = _rand(12, (rows, width))
    want = _uncached_added_cond(family, pooled_u, pooled_c, 64, 96)
    before = PLAN.summary()["added_cond"]
    spans = [types.SimpleNamespace(attrs={}) for _ in range(3)]
    for span in spans[:2]:
        got = Engine._added_cond(this, pooled_u, pooled_c, 64, 96,
                                 span=span)
        assert [_bits(g) for g in got] == [_bits(w) for w in want]
        assert got[0].dtype == jnp.float32
        assert got[0].shape == (rows, family.unet.projection_input_dim)
    # the negative half differs from the positive in the refiner alone
    assert (_bits(want[0][:, width:]) != _bits(want[1][:, width:])) \
        == (family is TINY_REFINER)
    kept = len(engine_mod._TIME_IDS)
    assert kept == (2 if family is TINY_REFINER else 1)
    # a second size keys apart, and so does another row count
    other = Engine._added_cond(this, pooled_u, pooled_c, 96, 64,
                               span=spans[2])
    assert [_bits(g) for g in other] == [_bits(w) for w in
                                         _uncached_added_cond(
                                             family, pooled_u, pooled_c,
                                             96, 64)]
    assert _bits(other[1]) != _bits(want[1])
    assert len(engine_mod._TIME_IDS) == 2 * kept
    assert [s.attrs["added_cond"] for s in spans] == [
        "built", "hit", "built"]
    after = PLAN.summary()["added_cond"]
    assert (after["builds"] - before["builds"],
            after["hits"] - before["hits"]) == (2, 1)


def test_a_family_without_added_cond_says_none():
    span = types.SimpleNamespace(attrs={})
    this = types.SimpleNamespace(family=TINY)
    assert Engine._added_cond(this, None, None, 64, 64, span=span) \
        == (None, None)
    assert span.attrs == {"added_cond": "none"}
    assert Engine._added_cond(this, None, None, 64, 64) == (None, None)


def _plan_attrs(req):
    return {name: [s.attrs.get(attr) for s in req.spans if s.name == name]
            for name, attr in (("request.plan", "ladder"),
                               ("denoise.plan", "ladder"),
                               ("denoise.inputs", "added_cond"))}


@pytest.mark.parametrize("family,added", [
    (TINY, ("none", "none")), (TINY_XL, ("built", "hit"))],
    ids=["tiny", "tiny-xl"])
def test_first_request_builds_the_second_hits_and_the_images_are_one(
        family, added, monkeypatch):
    """A fixed seed gives the same bytes from the request that builds the
    kept ladder (and time ids), from one that meets them, and from one made
    to build everything anew as every request did before."""
    engine_mod._TIME_IDS.clear()
    engine = quality.make_engine(family, chunk_size=4)   # its own schedule
    payload = GenerationPayload(prompt="a cow", steps=5, width=32,
                                height=32, seed=38, sampler_name="Euler a")
    images, attrs = [], []
    for rid in ("built", "hit"):
        with obs_spans.request(f"rid-kept-{family.name}-{rid}") as req:
            images.append(engine.txt2img(payload).images)
        attrs.append(_plan_attrs(req))
        if rid == "built":
            kept = kd.ladder(kd.resolve_sampler("Euler a"),
                             engine.schedule, 5)[0]
            kept_bits = _bits(kept.sigmas), _bits(kept.host)
    assert attrs[0] == {"request.plan": ["built"], "denoise.plan": ["hit"],
                        "denoise.inputs": [added[0]]}
    assert attrs[1] == {"request.plan": ["hit"], "denoise.plan": ["hit"],
                        "denoise.inputs": [added[1]]}
    assert images[0] == images[1] and len(images[0]) == 1
    # nothing donated or wrote into what is kept
    again = kd.ladder(kd.resolve_sampler("Euler a"), engine.schedule, 5)[0]
    assert again.sigmas is kept.sigmas and again.host is kept.host
    assert (_bits(again.sigmas), _bits(again.host)) == kept_bits
    assert kept_bits[0] == _bits(jnp.asarray(
        sched.default_sigmas(engine.schedule, 5)))
    monkeypatch.setattr(KeptTable, "get",
                        lambda self, key, build: (build(), False))
    assert engine.txt2img(payload).images == images[0]

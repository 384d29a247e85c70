"""The routing kernel (ops/route_kernel.py) against XLA's chain: the same
picks in the same order, the expert kernel's operands slot for slot, the
same counts. On a CPU the kernel runs in Pallas' interpreter; what Mosaic
makes of it is tests/test_chip_compile.py's."""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.obs import prometheus
from stable_diffusion_webui_distributed_tpu.ops import (
    moe, moe_kernel, route_kernel,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import EXPANDER

#: the eight published routers, by the factory of their share
PUBLISHED = ["sd15_laguna_expander", "sd15_qwen3next_expander",
             "sd15_xing4_expander", "sd15_lfm2_expander",
             "sd15_mellum2_expander", "sd15_kanana2_expander",
             "sd15_gigachat35_expander", "sd15_longcat_flash_expander"]
#: two ulp of a float32
ULP2 = 2.5e-7


def router_of(factory: str) -> dict:
    cfg = getattr(configs, factory)().expander
    first, count = cfg.experts
    return dict(experts=cfg.num_experts, k=cfg.num_experts_per_tok,
                first=first, count=count, scoring=cfg.router_scoring,
                biased=cfg.router_bias, renormalise=cfg.norm_topk_prob,
                eps=cfg.norm_topk_eps, scale=cfg.routed_scaling_factor,
                zero_experts=cfg.zero_experts)


def chain(logits, bias, valid, *, experts, k, first, count, scoring,
          renormalise, eps, scale, zero_experts, monkeypatch, **_):
    """What XLA's chain hands on: ``route``, then the operands ``_chosen``
    or ``_block`` give the expert kernel (caught at its door), then the
    counts."""
    rows = logits.shape[0]
    routing = moe.route(logits, k, renormalise=renormalise, scale=scale,
                        scoring=scoring, bias=bias, eps=eps)
    caught = {}

    def door(x, slot_ids, weights, held, *kernels, **_):
        caught.update(experts=slot_ids, weights=weights, held=held)
        return jnp.zeros(x.shape, jnp.float32)

    monkeypatch.setattr(moe_kernel, "chosen_experts", door)
    x = jnp.zeros((rows, 8), jnp.float32)
    kernels = (jnp.zeros((count, 8, 8)),) * 3
    if rows == 1:
        moe._chosen(x, routing, *kernels, first, kernel=True)
    else:
        moe._block(x, routing, *kernels, first)
    load, none_held = moe.load_counts(routing, first, count, valid)
    out = dict(caught, picks=routing.experts, load=load,
               none_held=none_held)
    if zero_experts:
        real = experts - zero_experts
        out["identity_weight"] = moe.identity_part(
            jnp.ones((rows, 1)), routing, real)
        out["identity_picks"] = moe.identity_picks(routing, real, valid)
    return out


def kernel(logits, bias, valid, router):
    return route_kernel.routing(
        logits, bias, valid, interpret=True,
        **{key: router[key] for key in (
            "k", "renormalise", "scale", "scoring", "eps", "first", "count",
            "zero_experts")})


def agree(logits, bias, valid, router, monkeypatch):
    """The kernel's step equals the chain's; the held count."""
    want = chain(logits, bias, valid, monkeypatch=monkeypatch, **router)
    got = kernel(logits, bias, valid, router)
    held = int(want["held"])
    assert int(got.held[0]) == held
    np.testing.assert_array_equal(got.picks, want["picks"])
    # the slots that hold an expert; behind them the expert kernel reads
    # nothing, and the routing kernel leaves id 0 and weight 0
    np.testing.assert_array_equal(got.experts[:held],
                                  want["experts"][:held])
    np.testing.assert_array_equal(got.experts[held:], 0)
    assert got.weights.shape == want["weights"].shape
    np.testing.assert_allclose(got.weights, want["weights"], rtol=ULP2,
                               atol=0)
    np.testing.assert_array_equal(got.load, want["load"])
    assert int(got.none_held) == int(want["none_held"])
    if router["zero_experts"]:
        np.testing.assert_allclose(got.identity_weight,
                                   want["identity_weight"], rtol=2 * ULP2)
        assert int(got.identity_picks) == int(want["identity_picks"])
    else:
        assert got.identity_weight is None and got.identity_picks is None
    return held


def operands(router, rows, seed, spread=2.0):
    logits = spread * jax.random.normal(
        jax.random.key(seed), (rows, router["experts"]), jnp.float32)
    bias = 0.1 * jax.random.normal(
        jax.random.key(seed + 1), (router["experts"],),
        jnp.float32) if router["biased"] else None
    valid = jnp.arange(rows) < max(1, rows - 1)     # the last row is padding
    return logits, bias, valid


@pytest.mark.parametrize("rows", [1, 2, 4, 8])
@pytest.mark.parametrize("factory", PUBLISHED)
def test_a_published_router_routes_as_the_chain_does(monkeypatch, factory,
                                                     rows):
    router = router_of(factory)
    agree(*operands(router, rows, seed=rows), router, monkeypatch)


@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("scoring,biased,renormalise,eps", [
    case for case in itertools.product(
        ["softmax", "sigmoid"], [False, True], [False, True], [0.0, 1e-6])
    if case[2] or not case[3]])     # eps is the renormalising sum's
def test_every_scoring_rule_over_a_share_in_the_middle(
        monkeypatch, scoring, biased, renormalise, eps, rows):
    """48 experts (under one register's lanes) of which this chip holds 16
    .. 39: the held range starts off a lane boundary."""
    router = dict(experts=48, k=4, first=16, count=24, scoring=scoring,
                  biased=biased, renormalise=renormalise, eps=eps,
                  scale=2.5, zero_experts=0)
    agree(*operands(router, rows, seed=3), router, monkeypatch)


@pytest.mark.parametrize("factory,rows", [
    ("sd15_qwen3next_expander", 1), ("sd15_xing4_expander", 1),
    ("sd15_mellum2_expander", 4), ("sd15_longcat_flash_expander", 4),
    ("sd15_kanana2_expander", 8)])
def test_exact_ties_go_to_the_lower_id(monkeypatch, factory, rows):
    """Logits of a few whole numbers: most scores tie exactly, and
    ``lax.top_k`` takes the lower id first."""
    router = router_of(factory)
    logits, bias, valid = operands(router, rows, seed=7)
    logits = jnp.round(logits)
    if bias is not None:
        bias = jnp.zeros_like(bias)     # the ties survive the bias
    agree(logits, bias, valid, router, monkeypatch)


@pytest.mark.parametrize("rows", [1, 4])
def test_a_step_none_of_whose_picks_is_held(monkeypatch, rows):
    """Every pick falls on another chip's experts: no slot holds one, every
    row that counts is a row with no held expert."""
    router = dict(router_of("sd15_gigachat35_expander"), first=240)
    logits, bias, valid = operands(router, rows, seed=11)
    logits = logits.at[:, :64].add(40.0)
    held = agree(logits, bias, valid, router, monkeypatch)
    assert held == 0


@pytest.mark.parametrize("rows", [1, 4])
def test_a_step_all_of_whose_picks_are_identity_experts(monkeypatch, rows):
    router = router_of("sd15_longcat_flash_expander")
    logits, bias, valid = operands(router, rows, seed=13)
    bias = bias.at[512:].add(10.0)      # the bias chooses
    got = kernel(logits, bias, valid, router)
    assert agree(logits, bias, valid, router, monkeypatch) == 0
    assert int(got.identity_picks) == router["k"] * int(jnp.sum(valid))
    assert int(jnp.sum(got.load)) == 0


@pytest.mark.parametrize("platform,tokens,dtype,meshed,want", [
    ("tpu", 1, jnp.bfloat16, False, moe.KERNEL),
    ("tpu", 8, jnp.bfloat16, False, moe.KERNEL),
    ("tpu", 64, jnp.bfloat16, False, moe.GROUPED),      # a prefill chunk
    ("tpu", 1, jnp.float32, False, moe.LOOP),
    ("cpu", 1, jnp.bfloat16, False, moe.LOOP),
    ("cpu", 4, jnp.bfloat16, False, moe.GROUPED),
    ("tpu", 1, jnp.bfloat16, True, moe.LOOP),           # a mesh
    ("tpu", 4, jnp.bfloat16, True, moe.GROUPED)])
def test_one_predicate_picks_both_kernels(monkeypatch, platform, tokens,
                                          dtype, meshed, want):
    """``models/lm.py:MoE`` traces the routing kernel exactly where
    ``ops/moe.py:choose`` answers ``kernel``, and ``route`` with the chain
    behind it everywhere else."""
    assert moe.choose(platform, tokens, dtype, 128, 128,
                      meshed=meshed) == want
    cfg = dataclasses.replace(
        configs.TINY_LM, hidden_size=128, moe_intermediate_size=128,
        shared_expert_intermediate_size=0)      # widths that tile
    module = lm.MoE(cfg, dtype, meshed=meshed)
    n = jax.random.normal(jax.random.key(0), (tokens, 128), jnp.float32)
    valid = jnp.ones((tokens,), bool)
    params = jax.eval_shape(module.init, jax.random.key(1), n, valid)
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    called = []
    monkeypatch.setattr(
        moe, "kernel_step", lambda x, *a, **kw: called.append(1) or (
            jnp.zeros(x.shape, jnp.float32), route_kernel.Step(
                *[jnp.zeros((), jnp.int32)] * 6, None, None)))
    if want != moe.KERNEL:      # the chain's own products, on this CPU
        monkeypatch.setattr(
            moe, "routed_experts", lambda x, *a, **kw: (
                jnp.zeros(x.shape, jnp.float32), want))
    EXPANDER.clear()
    jax.eval_shape(module.apply, params, n, valid)
    taken = want == moe.KERNEL
    assert bool(called) == taken
    assert EXPANDER.summary()["route_products"] == {
        "kernel": int(taken), "xla": int(not taken)}
    assert EXPANDER.summary()["expert_products"][want] == 1


class TestAnExpertLayerThroughBothKernels:
    """``MoE`` with the choice held to ``kernel`` (both kernels in the
    interpreter) against the same layer on the chain."""

    #: 24 experts of which the last 8 are identity experts, 4 a token by
    #: biased softmax scores, at widths the expert kernel tiles
    CFG = dataclasses.replace(
        configs.TINY_LONGCAT_FLASH_LM, hidden_size=128,
        moe_intermediate_size=128, moe_shortcut=False)

    @pytest.mark.parametrize("rows", [1, 4])
    def test_the_layer_adds_what_the_chain_adds(self, monkeypatch, rows):
        module = lm.MoE(self.CFG, jnp.float32)
        n = jax.random.normal(jax.random.key(0), (rows, 128), jnp.float32)
        valid = jnp.arange(rows) < max(1, rows - 1)
        params = module.init(jax.random.key(1), n, valid)
        params = jax.tree_util.tree_map(
            lambda x: x + 0.1 * jax.random.normal(
                jax.random.key(2), x.shape), params)
        want, beside = module.apply(params, n, valid)
        monkeypatch.setattr(moe, "choose", lambda *a, **kw: moe.KERNEL)
        got, beside_kernel = module.apply(params, n, valid)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        assert len(beside) == len(beside_kernel) == 4
        for ours, theirs in zip(beside_kernel, beside):
            assert ours.shape == theirs.shape and ours.dtype == theirs.dtype
            np.testing.assert_array_equal(ours, theirs)


def test_the_counter_replays_with_a_loaded_program():
    """``route_products`` is counted at trace time, kept with a stage's
    program and counted again when the program is loaded, as
    ``expert_products`` is."""
    from stable_diffusion_webui_distributed_tpu.serving import metrics

    EXPANDER.clear()
    with metrics.capture_sites() as rows:
        EXPANDER.record_route("kernel")
        EXPANDER.record_route("xla")
        EXPANDER.record_route("kernel")
    assert rows == [["route", "kernel"], ["route", "xla"],
                    ["route", "kernel"]]
    EXPANDER.clear()
    metrics.replay_sites(rows)
    assert EXPANDER.summary()["route_products"] == {"kernel": 2, "xla": 1}
    text = prometheus.render()      # the Prometheus twin
    assert 'sdtpu_expander_route_products_total{form="kernel"} 2' in text
    assert 'sdtpu_expander_route_products_total{form="xla"} 1' in text


def test_more_rows_than_a_register_holds_are_refused():
    with pytest.raises(ValueError, match="rows"):
        route_kernel.routing(
            jnp.zeros((9, 64)), None, jnp.ones((9,), bool), k=2,
            renormalise=True, scale=1.0, scoring="softmax", eps=0.0,
            first=0, count=64)

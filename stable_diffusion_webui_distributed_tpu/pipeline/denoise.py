"""Every way a denoise executable is made, and nothing of the host loop.

``build(variant, deps)`` composes the parts below into the jitted function
the host loops of ``pipeline/engine.py`` call: plain Python that the one
outer ``jax.jit`` inlines, with no nested jit, ``eval_shape``, ``checkpoint``
or ``named_call`` around them, because tracing time is paid at every set-up
(PERF.md section 6, PR 30 and PR 31).

A fixed-step kind is called ``fn(unet_params, state, start, inputs) ->
(state, fence)``. ``state`` (the sampler's carry, or a :class:`CachedState`)
is donated, which halves peak latent HBM: the host paces on the tiny
data-dependent ``fence`` and never touches a carry once a later chunk is in
flight. The chunk function is named ``run_chunk``: the benchmark's trace
reducer classes device ops by the module ``jit_run_chunk``.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.samplers import kdiffusion as kd


class Variant(NamedTuple):
    """What is static in a denoise executable; a chunk's tuple IS its cache
    key (the AOT store and the census of ``obs/perf.py`` depend on it).
    ``kind``: ``chunk`` (a scan over ``length`` steps from a traced index),
    ``adaptive`` (one DPM-adaptive attempt) or ``adaptive-pin``. Prompt,
    seed, cfg, adapter names and ranks, ragged lengths, cadence and cutoff
    are data (sdtpu-lint RC001):
    ``lora_sig`` is "" or a ladder cell ``lora:r{rb}s{sc}``, ``precision`` a
    rung of pipeline/precision.py's ladder."""

    kind: str
    sampler: str = ""
    steps: int = 1
    width: int = 0
    height: int = 0
    batch: int = 0
    length: int = 1
    masked: bool = False
    n_controls: int = 0
    inpaint: bool = False
    family: str = ""
    ragged: bool = False
    lora_sig: str = ""
    step_cache: bool = False
    precision: str = ""

    def key(self) -> Tuple:
        """The whole record for a chunk; what can differ for the others."""
        if self.kind == "chunk":
            return tuple(self)
        if self.kind == "adaptive-pin":
            return (self.kind, self.family)
        return (self.kind, self.width, self.height, self.batch,
                self.n_controls, self.inpaint, self.family, self.precision)


def parse_key(key: Any) -> Optional[Variant]:
    """The :class:`Variant` of a chunk's cache key; None for another key."""
    if (isinstance(key, tuple) and len(key) == len(Variant._fields)
            and key[0] == "chunk"):
        return Variant._make(key)
    return None


def check(v: Variant) -> None:
    """Refuse a combination no executable serves."""
    why = None
    if v.kind not in ("chunk", "adaptive", "adaptive-pin"):
        why = "unknown kind"
    elif v.ragged and v.step_cache:
        why = "ragged chunks disable the step cache"
    elif v.ragged and (v.masked or v.n_controls or v.inpaint):
        why = "ragged dispatch covers the plain txt2img path only"
    elif v.step_cache and v.n_controls:
        # residuals feed the deep blocks: a stale deep feature drops them
        why = "ControlNet windows bypass the step cache"
    elif v.kind != "chunk" and (v.ragged or v.step_cache or v.lora_sig):
        why = f"a {v.kind} carries no ragged rows, step cache or traced LoRA"
    if why:
        raise ValueError(f"{why}: {v}")


class Deps(NamedTuple):
    """From the engine: the precision's module pair (weights stay jit
    ARGUMENTS), the noise schedule."""

    unet: Any
    controlnet: Any
    schedule: Any


class Inputs(NamedTuple):
    """The step's traced inputs; an absent one is ``None``, no pytree leaf,
    so an executable takes only what it uses. ``ctx_*``, ``added_*``: the CFG
    halves' contexts and SDXL added-cond, one row or one per image.
    ``controls``: ``(cn_params, hint(B,H,W,3), weight, g_start, g_end)`` per
    active unit. ``lora``: per-row ``[B, slots, ...]`` UNet deltas
    (models/lora.py). ``ragged``: ``(true_rows, ctx_true_u, ctx_true_c)``,
    (B,) int32 valid latent rows and context tokens. ``cadence``, ``cfg_stop``:
    the step cache's refresh cadence and first cond-only step."""

    ctx_u: Any
    ctx_c: Any
    cfg: Any = None
    image_keys: Any = None
    added_u: Any = None
    added_c: Any = None
    mask_lat: Any = None
    init_lat: Any = None
    controls: Tuple = ()
    inpaint_cond: Any = None
    lora: Any = None
    ragged: Any = None
    cadence: Any = None
    cfg_stop: Any = None


class CachedState(NamedTuple):
    """A step-cache chunk's scan state: the deep feature (models/unet.py:
    CACHE_SPLIT) holds [uncond; cond] rows; a fresh range starts invalid."""

    carry: kd.Carry
    cache: jax.Array
    valid: jax.Array


def scale_in(schedule, x, sigma):
    """(the latent scaled to the model's input, its timestep)"""
    c_in = 1.0 / jnp.sqrt(sigma**2 + 1.0)
    t = schedule.sigma_to_t(sigma)
    return (x * c_in).astype(x.dtype), t


def cfg_rows(xin, t, inp: Inputs, inpaint: bool = False,
             cond_only: bool = False):
    """The rows of one UNet or ControlNet call: ``(latent, unet input,
    timesteps, context, added cond)``, each [uncond; cond], or the cond half
    alone (the CFG-truncated step). ``unet input`` adds an inpainting model's
    [mask, masked-image latent] channels; ControlNet sees the bare latent."""
    B = xin.shape[0]

    def halves(u, c):
        c = jnp.broadcast_to(c, (B,) + c.shape[1:])
        if cond_only:
            return c
        return jnp.concatenate([jnp.broadcast_to(u, (B,) + u.shape[1:]), c])

    latent = halves(xin, xin)
    tb = jnp.full(latent.shape[:1], t, jnp.float32)
    ctx = halves(inp.ctx_u, inp.ctx_c)
    added = (None if inp.added_u is None
             else halves(inp.added_u, inp.added_c))
    unet_in = latent
    if inpaint:
        cond = halves(inp.inpaint_cond, inp.inpaint_cond).astype(xin.dtype)
        unet_in = jnp.concatenate([latent, cond], axis=-1)
    return latent, unet_in, tb, ctx, added


def control_residuals(controlnet, controls, latent, tb, ctx, added, step,
                      total_steps: int, residuals=None):
    """Sum of every unit's residual tuple on [uncond; cond] rows, each
    gated by its guidance step-fraction window (webui unit semantics; the
    reference serializes exactly these fields, control_net.py:20-79)."""
    B = latent.shape[0] // 2
    frac = (step.astype(jnp.float32) + 0.5) / total_steps
    for cn_params, hint, weight, g_start, g_end in controls:
        gate = jnp.where((frac >= g_start) & (frac <= g_end), weight,
                         0.0).astype(jnp.float32)
        hint_b = jnp.broadcast_to(hint, (B,) + hint.shape[1:])
        rs = controlnet.apply({"params": cn_params}, latent, tb, ctx,
                              jnp.concatenate([hint_b, hint_b]), added)
        rs = tuple(r.astype(jnp.float32) * gate for r in rs)
        residuals = rs if residuals is None else tuple(
            a + b for a, b in zip(residuals, rs))
    return residuals


def guide(out, cfg):
    """Classifier-free guidance over [uncond; cond] rows."""
    out_u, out_c = jnp.split(out.astype(jnp.float32), 2, axis=0)
    return out_u + cfg * (out_c - out_u)


def to_x0(x, sigma, guided, v_pred: bool):
    """The guided eps (or v) prediction as an x0 prediction."""
    if v_pred:
        c_skip = 1.0 / (sigma**2 + 1.0)
        c_out = sigma / jnp.sqrt(sigma**2 + 1.0)
        return x * c_skip - guided * c_out
    return x - sigma * guided


def pin_unmasked(x, mask_lat, init_lat, image_keys, sigma, *domain):
    """Inpaint: pin the unmasked region to the init latent re-noised to
    ``sigma``. ``domain`` is folded into each image's key in turn:
    ``1_000_000 + i`` on the fixed grid, ``2_000_000, n`` on the adaptive
    path, so the two never share noise and cadence never moves it."""
    def renoise(k):
        for d in domain:
            k = jax.random.fold_in(k, d)
        return jax.random.normal(k, init_lat.shape[1:], jnp.float32)

    pinned = init_lat + jax.vmap(renoise)(image_keys) * sigma
    return mask_lat * x + (1 - mask_lat) * pinned


def scan_chunk(step, state, start, length: int):
    """Steps ``start … start + length``: (state, fence)."""
    state, _ = jax.lax.scan(step, state, start + jnp.arange(length))
    carry = state.carry if isinstance(state, CachedState) else state
    return state, carry.x.reshape(-1)[:1]


def make_denoise(v: Variant, deps: Deps, unet_params, inp: Inputs):
    """``(rows, denoise)``: ``rows(xin, t, step, cond_only=False, **cache)``
    is the UNet's output on the CFG rows of an already scaled latent,
    ``denoise(x, sigma, step)`` the guided x0 prediction the samplers take."""
    params = {"params": unet_params}
    v_pred = deps.schedule.prediction_type == "v_prediction"
    # each image's adapter set rides both of its CFG rows
    lora2 = (None if inp.lora is None else jax.tree_util.tree_map(
        lambda a: jnp.concatenate([a, a]), inp.lora))
    ragged = {}
    if v.ragged:
        true_rows, ctx_true_u, ctx_true_c = inp.ragged
        ragged = {"true_rows": jnp.concatenate([true_rows, true_rows]),
                  "ctx_true": jnp.concatenate([ctx_true_u, ctx_true_c])}

    def rows(xin, t, step, cond_only=False, **cache):
        latent, unet_in, tb, ctx, added = cfg_rows(
            xin, t, inp, v.inpaint, cond_only)
        residuals = None
        if inp.controls:
            residuals = control_residuals(
                deps.controlnet, inp.controls, latent, tb, ctx, added, step,
                v.steps)
        return deps.unet.apply(
            params, unet_in, tb, ctx, added, control_residuals=residuals,
            lora=inp.lora if cond_only else lora2, **ragged, **cache)

    def denoise(x, sigma, step):
        xin, t = scale_in(deps.schedule, x, sigma)
        return to_x0(x, sigma, guide(rows(xin, t, step), inp.cfg), v_pred)

    return rows, denoise


def make_step(v: Variant, deps: Deps, sigmas, unet_params, inp: Inputs):
    """The scan step ``(state, i) -> (state, ())`` of a fixed-step kind."""
    spec = kd.resolve_sampler(v.sampler)
    v_pred = deps.schedule.prediction_type == "v_prediction"
    rows, denoise = make_denoise(v, deps, unet_params, inp)

    def sample(carry, i, denoise):
        carry, _ = kd.make_sampler_step(
            spec, denoise, sigmas, inp.image_keys)(carry, i)
        if v.masked:
            carry = carry._replace(x=pin_unmasked(
                carry.x, inp.mask_lat, inp.init_lat, inp.image_keys,
                sigmas[i + 1], 1_000_000 + i))
        if v.ragged:
            # ancestral samplers inject noise everywhere; re-zero the masked
            # tail so padded rows stay exactly 0 into the next step's convs:
            # solo==group byte identity rests on row independence
            lat_h = carry.x.shape[1]
            row_mask = (jnp.arange(lat_h, dtype=jnp.int32)[None, :]
                        < inp.ragged[0][:, None])[:, :, None, None]
            carry = carry._replace(x=jnp.where(row_mask, carry.x, 0.0))
        return carry

    if not v.step_cache:
        return lambda carry, i: (sample(carry, i, denoise), ())

    def cached_step(state, i):
        # Refreshed BEFORE the sampler step when the bit is unset or the
        # step lands on the (traced) cadence, so every UNet eval of the step
        # reuses a feature of the step's own entry latent. A truncated
        # refresh mirrors the cond half: buffer shapes never change.
        carry, cache, valid = state
        xin, t = scale_in(deps.schedule, carry.x, sigmas[i])
        refresh = jnp.logical_or(
            jnp.logical_not(valid), jnp.mod(i, inp.cadence) == 0)

        def do_refresh(_):
            def deep_full(_):
                return rows(xin, t, i, cache_mode="deep")

            def deep_trunc(_):
                d = rows(xin, t, i, True, cache_mode="deep")
                return jnp.concatenate([d, d])

            return jax.lax.cond(i >= inp.cfg_stop, deep_trunc, deep_full,
                                None).astype(cache.dtype)

        new_cache = jax.lax.cond(refresh, do_refresh, lambda _: cache, None)

        def denoise_cached(x, sigma, step):
            xe, te = scale_in(deps.schedule, x, sigma)

            def eval_full(_):
                return guide(rows(xe, te, step, cache=new_cache,
                                  cache_mode="reuse"), inp.cfg)

            def eval_trunc(_):
                return rows(xe, te, step, True, cache=new_cache[v.batch:],
                            cache_mode="reuse").astype(jnp.float32)

            guided = jax.lax.cond(step >= inp.cfg_stop, eval_trunc,
                                  eval_full, None)
            return to_x0(x, sigma, guided, v_pred)

        return CachedState(sample(carry, i, denoise_cached), new_cache,
                           jnp.full_like(valid, True)), ()

    return cached_step


def build(v: Variant, deps: Deps) -> Callable:
    """The jitted function of a variant."""
    check(v)
    if v.kind == "adaptive-pin":
        def pin(x, mask_lat, init_lat, image_keys, sigma, n):
            return pin_unmasked(x, mask_lat, init_lat, image_keys, sigma,
                                2_000_000, n)

        return jax.jit(pin)

    if v.kind == "adaptive":
        # s, h (log-sigma position and step) are data: one executable for
        # the whole trajectory; the host loop gates ControlNet by weight
        def run(unet_params, x, x_prev, s, h, rtol, atol, inputs):
            _, denoise = make_denoise(v, deps, unet_params, inputs)
            return kd.make_adaptive_attempt(denoise)(
                x, x_prev, s, h, rtol, atol)

        return jax.jit(run)

    sigmas = kd.build_sigmas(kd.resolve_sampler(v.sampler), deps.schedule,
                             v.steps)
    def run_chunk(unet_params, state, start, inputs):
        return scan_chunk(make_step(v, deps, sigmas, unet_params, inputs),
                          state, start, v.length)

    return jax.jit(run_chunk, donate_argnums=(1,))

"""The generation engine: compiled txt2img / img2img / hires-fix.

This is the TPU rebirth of what each remote sdwui process does when the
reference POSTs ``/sdapi/v1/txt2img`` (/root/reference/scripts/spartan/
worker.py:421-443): encode prompts, denoise with the named sampler, decode,
return base64 PNGs with per-image seeds/infotext.

Key properties:
- **Seed-exact sharding:** ``generate_range(payload, start, count)`` produces
  images [start, start+count) of the request bitwise-identically whether run
  on one device or split across many — the TPU equivalent of the reference's
  seed fan-out (distributed.py:297-305). All stochasticity is keyed by
  (request seed + global image index); batch position never enters.
- **Chunked interrupt:** the denoise loop runs ``chunk_size`` steps per
  device dispatch; between dispatches the host checks the interrupt flag and
  reports progress — the compiled-loop version of the reference's 0.5 s
  interrupt poll (worker.py:440-448).
- **Compile caching:** jitted stages are cached per (resolution, batch,
  steps, sampler) bucket; the same compiled function serves every prompt,
  seed, and CFG value at that bucket (they are data, not constants).
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from stable_diffusion_webui_distributed_tpu.models.clip import CLIPTextModel
from stable_diffusion_webui_distributed_tpu.models.configs import ModelFamily
from stable_diffusion_webui_distributed_tpu.models.unet import (
    UNet,
    cache_supported,
    deep_cache_shape,
    join_added_cond,
    time_id_embedding,
)
from stable_diffusion_webui_distributed_tpu.models.vae import VAE
from stable_diffusion_webui_distributed_tpu.models.tokenizer import load_tokenizer
from stable_diffusion_webui_distributed_tpu.pipeline import (
    precision as precision_mod,
)
from stable_diffusion_webui_distributed_tpu.pipeline import denoise, stepcache
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
    GenerationResult,
    apply_scripts,
    b64png_to_array,
    build_infotext,
    encode_b64png,
    fix_seed,
    prompt_expansion_args,
)
from stable_diffusion_webui_distributed_tpu.obs import spans as obs_spans
from stable_diffusion_webui_distributed_tpu.runtime import dtypes, rng, trace
from stable_diffusion_webui_distributed_tpu.runtime.kept import KeptTable
from stable_diffusion_webui_distributed_tpu.runtime import interrupt as interrupt_mod
from stable_diffusion_webui_distributed_tpu.samplers import kdiffusion as kd
from stable_diffusion_webui_distributed_tpu.samplers import schedules as sched
from stable_diffusion_webui_distributed_tpu.serving import aot as aot_mod
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    METRICS, PLAN, install_xla_listener,
)

#: SDXL's embedded time ids by (ids, rows, embed dim): ``_added_cond``.
_TIME_IDS = KeptTable(64)


def _embedded_time_ids(ids, rows: int, dim: int):
    """``(embedding, hit)``: the (rows, len(ids) * dim) float32 Fourier
    embedding of ``rows`` copies of ``ids``, built by the ops every request
    ran before it was kept."""
    def build():
        tid = jnp.broadcast_to(jnp.asarray([ids], jnp.float32),
                               (rows, len(ids)))
        return time_id_embedding(tid, dim)

    return _TIME_IDS.get((tuple(ids), rows, dim), build)


class RequestPlan(NamedTuple):
    """What a txt2img range settles before its first group (span
    ``request.plan``)."""

    sigmas: jax.Array
    controls: tuple
    refiner: Optional["Engine"]


class RangePlan(NamedTuple):
    """The part of a denoise range's plan (span ``denoise.plan``) that
    reads the payload's settings alone."""

    sc: Any             # stepcache.resolve
    prec: Any           # precision_mod.resolve
    cfg_stop: int       # the step-cache cutoff sigma's step on the ladder


class Drawn(NamedTuple):
    """The first group of a txt2img range as far as it reads nothing of the
    prompt's text: the seed, the size, the sampler, the step count. An
    expanded request makes it under the expander's first decode chunk
    (``Engine._draw_ahead``, span ``expand.ahead``), when the device is
    busy and the engine's thread has nothing to do but wait;
    ``_run_txt2img`` and ``_denoise_range_timed`` then take from it what
    they would have made themselves, by the same functions, with the
    device idle. A value handed from one to the next, never kept."""

    group: tuple            # (first image, rows, width, height): its use
    plan: RequestPlan
    x: jax.Array
    keys: jax.Array
    cfg: jax.Array
    carry: kd.Carry
    range_plan: RangePlan


class Engine:
    """One loaded model family + its compiled stages on the local device(s)."""

    def __init__(
        self,
        family: ModelFamily,
        params: Dict[str, Any],
        tokenizer=None,
        policy: dtypes.Policy = dtypes.F32,
        model_name: str = "",
        state: Optional[interrupt_mod.GenerationState] = None,
        chunk_size: int = 10,  # measured best on v5e (PERF.md round-3 sweep)
        schedule: Optional[sched.NoiseSchedule] = None,
        mesh=None,
        lora_provider: Optional[Callable[[str], Optional[Dict]]] = None,
        controlnet_provider: Optional[Callable[[str], Optional[Dict]]] = None,
        engine_provider: Optional[Callable[[str], Optional["Engine"]]] = None,
        upscaler_provider: Optional[Callable[[str], Optional[Callable]]] = None,
        embedding_store=None,
    ):
        install_xla_listener()  # /internal/status serving.xla; idempotent
        self.family = family
        self.policy = policy
        self.model_name = model_name or family.name
        self.state = state or interrupt_mod.STATE
        self.chunk_size = max(1, chunk_size)
        self.mesh = mesh
        self.schedule = schedule or sched.sd_schedule(
            prediction_type=family.prediction_type
        )
        self.tokenizer = tokenizer or load_tokenizer(
            None, family.text_encoder.vocab_size
        )

        cast = lambda t: dtypes.cast_floating(t, policy.param_dtype)
        self.params = {k: (cast(v) if v is not None else None)
                       for k, v in params.items()}
        if mesh is not None:
            # Megatron-pattern TP placement (or replication at tp=1); the
            # batch axis is placed per request in _place_batch. XLA's SPMD
            # partitioner handles the rest (parallel/sharding.py).
            from stable_diffusion_webui_distributed_tpu.parallel.sharding import (
                shard_params,
            )

            self.params = {k: (shard_params(v, mesh) if v is not None else None)
                           for k, v in self.params.items()}

        # LoRA: merged host-side on request boundaries (the jitted stages
        # take params as arguments, so adapter swaps never recompile), or
        # — under SDTPU_LORA_TRACED — carried as traced jit arguments with
        # the param tree left pristine (models/lora.py TracedSet).
        # _active_loras latches () (pristine, initial) or the
        # (spec-tuple, provider-generation) pair the last merge ran for —
        # missing names included, so an identical repeat of a partially
        # resolved set is a no-op until /refresh-loras bumps the
        # registry's lora_generation and the retry actually sees new
        # files.
        self.lora_provider = lora_provider
        self._base_params = self.params
        self._active_loras: Tuple = ()

        # ControlNet: same-architecture residual network; params arrive per
        # request via the provider (name -> converted param tree).
        self.controlnet_provider = controlnet_provider
        from stable_diffusion_webui_distributed_tpu.models.controlnet import (
            ControlNet,
        )

        # (the ControlNet module is constructed below, after the
        # attention impl/mesh are resolved, so it mirrors the UNet's)
        # resolves another loaded engine by checkpoint name — the SDXL
        # base+refiner handoff (BASELINE config #2)
        self.engine_provider = engine_provider
        # ESRGAN-family image-space hires upscalers (models/esrgan.py);
        # None -> latent-space upscaling only
        self.upscaler_provider = upscaler_provider
        # textual-inversion embeddings (models/embeddings.py); None ->
        # prompt names are ordinary tokens
        self.embedding_store = embedding_store

        cd = policy.compute_dtype
        self.text_encoder = CLIPTextModel(family.text_encoder, dtype=cd)
        self.text_encoder_2 = (
            CLIPTextModel(family.text_encoder_2, dtype=cd)
            if family.text_encoder_2 else None
        )
        attn_impl = policy.attention_impl
        attn_mesh = None
        if mesh is not None and mesh.shape.get("sp", 1) > 1:
            # sequence parallelism: latent-token self-attention rides the
            # sp ring (ops/ring_attention.py); other impls keep their role
            # for meshes without an sp axis
            attn_impl = "ring"
            attn_mesh = mesh
        elif mesh is not None and mesh.size > 1 and attn_impl == "auto":
            # pjit does not partition a pallas_call: under a dp or tp mesh
            # the tiled kernel would be run whole on every chip, so the
            # partitioned program keeps XLA's attention (PERF.md section 6,
            # PR 25)
            attn_impl = "xla"
        self.unet = UNet(family.unet, dtype=cd,
                         attention_impl=attn_impl,
                         use_remat=policy.use_remat,
                         mesh=attn_mesh,
                         quant_linears=getattr(policy, "unet_int8", False),
                         quant_convs=getattr(policy, "unet_int8_conv",
                                             False))
        # the CN copy mirrors the UNet's full block configuration —
        # attention impl/mesh included, so sequence parallelism and the
        # int8 flags cover the CN's ~half-a-UNet of FLOPs too
        self.controlnet_module = ControlNet(
            family.unet, dtype=cd,
            use_remat=policy.use_remat,
            attention_impl=attn_impl, mesh=attn_mesh,
            quant_linears=getattr(policy, "unet_int8", False),
            quant_convs=getattr(policy, "unet_int8_conv", False))
        # Per-request serving precision (pipeline/precision.py): module
        # variants keyed by canonical precision name. Flax modules are
        # config holders — quantization happens at apply time and params
        # are jit ARGUMENTS — so every variant shares the ONE param tree;
        # only the traced computation differs. The policy-default name is
        # seeded with the EXACT modules built above, so requests that
        # specify nothing route to the unchanged executables byte-for-byte.
        self._attn_impl = attn_impl
        self._attn_mesh = attn_mesh
        self._default_precision = precision_mod.policy_default(policy)
        self._module_variants: Dict[str, Tuple[Any, Any]] = {
            self._default_precision.name:
                (self.unet, self.controlnet_module),
        }  # guarded-by: _module_lock
        self._module_lock = threading.Lock()
        vae_cfg = family.vae
        if getattr(policy, "decode_in_bf16", False) and \
                vae_cfg.force_decoder_f32:
            # policy opt-in (SDTPU_DECODE_DTYPE=bf16): decoder convs in the
            # compute dtype; GroupNorm stats and conv_out stay f32 (vae.py)
            import dataclasses as _dc

            vae_cfg = _dc.replace(vae_cfg, force_decoder_f32=False)
        self.vae = VAE(vae_cfg, dtype=cd)

        self._cache: Dict[Tuple, Callable] = {}  # guarded-by: _cache_lock
        self._cache_lock = threading.Lock()
        #: :meth:`_program_context`, once it has been asked for
        self._context: Optional[str] = None
        # resident prompt expander (pipeline/expand.py): only a family that
        # has one, loaded with its weights, gets the ``expand`` stage; every
        # other engine ignores the always-on script
        self.expander = None
        if family.expander is not None \
                and self.params.get("expander") is not None:
            from stable_diffusion_webui_distributed_tpu.pipeline.expand \
                import PromptExpander

            self.expander = PromptExpander(self)
        # blank hybrid-conditioning latents per (batch, size); VAE-derived,
        # so set_vae clears it
        self._blank_cond_cache: Dict[Tuple, Any] = {}
        # cross-request conditioning cache (webui keeps cached_c/cached_uc
        # across same-prompt requests, processing.py); keyed on prompt text
        # + clip_skip + chunk count, epoch-invalidated on LoRA merges and
        # embedding-store rescans. Entries are ~1 MB of device arrays.
        from collections import OrderedDict

        self._cond_cache: "OrderedDict[Tuple, Tuple]" = OrderedDict()
        self._cond_epoch = 0
        self._COND_CACHE_MAX = 64
        # traced-adapter serving state (SDTPU_LORA_TRACED): the active
        # TracedSet (None = adapterless), an LRU of built sets keyed
        # (specs, provider generation), and host-merge accounting the
        # adapter-churn bench reads (the traced arm must hold at 0)
        self._traced_lora = None
        self._traced_cache: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._TRACED_CACHE_MAX = 8
        self._lora_merge_total = 0
        self._lora_merge_seconds = 0.0
        # weights-identity epoch for the cache tier (cache/keys.py
        # model_fingerprint): bumped whenever the served weights change
        # under one model_name — LoRA merges AND VAE swaps — so every
        # content-addressed artifact computed under the old weights
        # retires by key, with no invalidation walk
        self._model_epoch = 0
        # Cooperative chunk-boundary preemption (fleet/policy.py): when a
        # preemptible job runs, the fleet gate installs an object with
        # should_yield()/yield_device() here; the denoise loop polls it
        # between chunk dispatches (the same boundary the interrupt flag
        # uses). The hook is thread-filtered — work executing DURING a
        # yield sees the same attribute and no-ops — so installation needs
        # no lock: only the gate-holding thread ever swaps it.
        self.preempt_hook = None

    # -- compiled stage factories ------------------------------------------

    def _cached(self, key: Tuple, build: Callable[[], Callable],
                static_argnums: Tuple[int, ...] = (),
                weights: int = 1) -> Callable:
        """The stage of ``key``, built once. Where a persistent compile
        cache is placed the stage keeps its programs beside it and a later
        process loads them instead of tracing (serving/aot.py; the first
        ``weights`` arguments of the stage are parameter trees); elsewhere
        it is the plain ``jax.jit`` that ``build`` makes."""
        with self._cache_lock:
            fn = self._cache.get(key)
            if fn is not None:
                METRICS.record_cache_hit(key[0])
                return fn
            # each build is one stage of this exact shape key: a trace and
            # an XLA compile at first dispatch, or a load; the serving
            # layer asserts on this counter (compile count, bucket hit
            # rate) instead of wall-clock. build() only makes the jit
            # wrapper: the compile's seconds are the xla.compile span and
            # serving.xla, which way a program came serving.programs
            # (serving/metrics.py)
            METRICS.record_compile(key[0])
            if aot_mod.store_dir() is None:
                METRICS.record_traced(key[0])
                fn = build()
            else:
                fn = aot_mod.AotFunction(
                    key, build, static_argnums=static_argnums,
                    weights=weights, context=self._program_context())
            self._cache[key] = fn
        return fn

    def _program_context(self) -> str:
        """The engine's part of a kept program's id (serving/aot.py): what
        its stages' functions close over beside their compile keys. The
        family's and the modules' own hyperparameters (the flax
        dataclasses' ``repr``: configuration, dtype, attention
        implementation, quantisation, mesh), the policy's dtypes, the
        noise schedule's tables and the mesh. Weights are arguments of
        every stage and are not here."""
        if self._context is None:
            def told(value) -> str:     # a table by its bytes
                if hasattr(value, "shape"):
                    return hashlib.sha256(
                        np.asarray(value).tobytes()).hexdigest()
                return repr(value)

            parts = [self.family, self.policy, self.mesh, self.unet,
                     self.controlnet_module, self.vae, self.text_encoder,
                     self.text_encoder_2,
                     self.expander and self.expander.module]
            parts += [(f.name, told(getattr(self.schedule, f.name)))
                      for f in dataclasses.fields(self.schedule)]
            self._context = hashlib.sha256("\n".join(
                map(repr, parts)).encode("utf-8")).hexdigest()[:16]
        return self._context

    def executable_keys(self) -> list:
        """Snapshot of the live compiled-stage cache keys — the input to
        the /internal/executables budget census (obs/perf.py)."""
        with self._cache_lock:
            return list(self._cache)

    def _has_batch_bucket(self, sampler: str, steps: int, width: int,
                          height: int, batch: int) -> bool:
        """Is a chunk executable for this (payload, batch) bucket already
        compiled? Drives the pad-and-drop remainder policy."""
        want = (sampler, steps, width, height, batch)
        return any(
            v is not None
            and (v.sampler, v.steps, v.width, v.height, v.batch) == want
            for v in map(denoise.parse_key, self.executable_keys()))

    def _modules_for(self, precision_name: str) -> Tuple[Any, Any]:
        """(UNet, ControlNet) module pair for a resolved precision name.

        The policy-default name returns the EXACT constructor-built pair
        (so the default path keeps its executables); other ladder rungs
        are built lazily and cached per engine. Building a variant is
        host-side module construction only — no params, no compile; the
        compile happens when a chunk executable for that precision is
        first dispatched (and is counted by METRICS like any other)."""
        from stable_diffusion_webui_distributed_tpu.models.controlnet import (
            ControlNet,
        )

        name = precision_mod.bucket_precision(
            precision_name, self._default_precision.name)
        with self._module_lock:
            pair = self._module_variants.get(name)
            if pair is None:
                spec = precision_mod.from_name(name)
                cd = self.policy.compute_dtype
                unet = UNet(self.family.unet, dtype=cd,
                            attention_impl=self._attn_impl,
                            use_remat=self.policy.use_remat,
                            mesh=self._attn_mesh,
                            quant_linears=spec.quant_linears,
                            quant_convs=spec.quant_convs)
                cn = ControlNet(self.family.unet, dtype=cd,
                                use_remat=self.policy.use_remat,
                                attention_impl=self._attn_impl,
                                mesh=self._attn_mesh,
                                quant_linears=spec.quant_linears,
                                quant_convs=spec.quant_convs)
                pair = (unet, cn)
                self._module_variants[name] = pair
        return pair

    # sdtpu-lint: jitted(static=4)
    def _encode_fn(self, lora_sig: str = "") -> Callable:
        """(te_params, te2_params, ids, weights, clip_skip static) ->
        (context (1, chunks*77, D), pooled). Params are jit ARGUMENTS, never
        closure constants — so LoRA-patched trees swap in without
        recompiling and weights are not baked into the executable.

        ``ids``/``weights`` are (n_chunks, 77): long prompts ride as extra
        batch rows through the encoder, then concatenate along the sequence
        axis (webui unlimited-length convention). Emphasis weights scale the
        embeddings with chunk-mean restoration (webui semantics).

        ``lora_sig`` (SDTPU_LORA_TRACED, models/lora.py) selects the
        variant whose trailing ``te_lora``/``te2_lora`` factor trees are
        live: one executable per (rank_bucket, slot_count) cell serves
        every adapter set in it. Empty sig keeps the key — and the traced
        graph — identical to the adapterless build, and is what unet-only
        adapter sets route to (their conditioning IS the adapterless
        conditioning, so the embed cache survives the switch)."""

        def build():
            def encode(te_params, te2_params, ids, weights, skip,
                       inj_mask, inj_l, inj_g, te_lora=None, te2_lora=None):
                # skip=0 -> model default (None); webui clip_skip N maps to N-1.
                skip_arg = skip if skip else None
                ctx, pooled = self.text_encoder.apply(
                    {"params": te_params}, ids, skip=skip_arg,
                    inject_values=inj_l, inject_mask=inj_mask,
                    lora=te_lora,
                )
                if self.text_encoder_2 is not None:
                    ctx2, pooled2 = self.text_encoder_2.apply(
                        {"params": te2_params}, ids, skip=skip_arg,
                        inject_values=inj_g, inject_mask=inj_mask,
                        lora=te2_lora,
                    )
                    ctx = jnp.concatenate(
                        [ctx.astype(jnp.float32), ctx2.astype(jnp.float32)],
                        axis=-1)
                    pooled = pooled2
                ctx = ctx.astype(jnp.float32)
                # emphasis: scale tokens, restore the chunk mean
                orig_mean = ctx.mean(axis=(1, 2), keepdims=True)
                ctx = ctx * weights[:, :, None]
                new_mean = ctx.mean(axis=(1, 2), keepdims=True)
                ratio = jnp.where(jnp.abs(new_mean) > 1e-7,
                                  orig_mean / new_mean, 1.0)
                ctx = ctx * ratio
                # chunks -> one long context row
                ctx = ctx.reshape(1, -1, ctx.shape[-1])
                pooled = pooled[:1]  # SDXL pooled comes from the first chunk
                return ctx, pooled.astype(jnp.float32)

            return jax.jit(encode, static_argnums=(4,))

        key = ("encode",) if not lora_sig else ("encode", lora_sig)
        return self._cached(key, build, static_argnums=(4,), weights=2)

    def _denoise_fn(self, kind: str, sampler_name: str = "", steps: int = 1,
                    width: int = 0, height: int = 0, batch: int = 0,
                    length: int = 1, precision: str = "",
                    **static) -> Callable:
        """The compiled denoise executable of one static variant
        (pipeline/denoise.py:Variant, whose tuple is the cache key; prompt,
        seed and cfg are data). ``precision`` is a resolved or requested
        serving precision name; the policy default's module pair IS the
        constructor-built one, so a request that names nothing routes to
        the unchanged executables."""
        prec = precision_mod.bucket_precision(
            precision, self._default_precision.name)
        variant = denoise.Variant(
            kind, sampler_name, steps, width, height, batch, length,
            family=self.family.name, precision=prec, **static)
        denoise.check(variant)
        unet, controlnet = self._modules_for(prec)
        deps = denoise.Deps(unet, controlnet, self.schedule)
        # every kind's first argument is the UNet's parameters, but the
        # pin's, which takes none
        return self._cached(variant.key(),
                            lambda: denoise.build(variant, deps),
                            weights=0 if kind == "adaptive-pin" else 1)

    def _denoise_adaptive(self, payload, x, image_keys, conds, pooleds,
                          width, height, start_step, steps, job,
                          mask_lat, init_lat, controls, end_step,
                          inpaint_cond):
        """DPM adaptive: host-side PID loop over the compiled attempt
        (k-diffusion sample_dpm_adaptive semantics — the step slider only
        sizes the sigma ladder's endpoints; the controller picks the actual
        steps). Interrupt is polled between attempts, so latency is one
        attempt (3 UNet evals). ControlNet guidance windows are gated
        host-side per attempt: the current sigma is located on the built
        sigma ladder (searchsorted) and converted to the SAME
        ``(step + 0.5) / steps`` fraction the fixed-grid in-graph gate
        uses, then each unit's weight is zeroed outside its window
        (weights are traced data, so crossing a boundary never
        recompiles). Gating granularity is per accepted attempt, so
        boundaries land within one attempt of the fixed-grid step they
        correspond to — not exactly on it."""
        spec = kd.resolve_sampler(payload.sampler_name)
        # the ladder's host copy: every use below reads single sigmas
        sigmas = self._ladder(spec, steps).host
        end = steps if end_step is None else min(end_step, steps)
        if start_step >= end:
            return x
        sigma_max = float(sigmas[start_step])
        sig_end = float(sigmas[end])
        # steps=1 gives sigmas=[sigma_max, 0]: falling back to
        # sigmas[end-1] would be sigma_max itself and the guard below
        # would return pure noise — integrate the schedule's full range
        # instead, like webui's DPM adaptive ignoring the slider.
        sigma_min = sig_end if sig_end > 0 else max(
            float(self.schedule.sigma_min),
            float(sigmas[end - 1]) if end - 1 > start_step else 0.0)
        if sigma_max <= sigma_min:
            return x

        (ctx_u, ctx_c) = conds
        au, ac = self._added_cond(*pooleds, width, height)
        batch = x.shape[0]
        cfg = jnp.float32(payload.cfg_scale)
        inpainting = self.family.inpaint and inpaint_cond is not None
        masked = mask_lat is not None
        inputs = denoise.Inputs(
            ctx_u, ctx_c, cfg, added_u=au, added_c=ac,
            inpaint_cond=inpaint_cond if inpainting else None)
        # Guidance-window gating happens HERE on the host, per attempt: the
        # in-graph gate sees total_steps=1 (frozen fraction 0.5), so each
        # unit's window is widened to (0, 1) in-graph and its WEIGHT is
        # zeroed host-side while the trajectory sits outside the window.
        # Weight is traced data — toggling it never recompiles. The current
        # sigma is mapped onto the BUILT sigma ladder (searchsorted), so
        # the fraction agrees with the fixed-grid gate's
        # (step + 0.5)/steps at the ladder's own spacing regardless of the
        # schedule's log-sigma curvature (ref CN window fields,
        # control_net.py:20-79).
        import numpy as _np

        # ascending view of the (decreasing) ladder for searchsorted
        _ladder_asc = _np.asarray(sigmas, dtype=_np.float64)[::-1].copy()
        _n_lad = len(sigmas) - 1          # number of steps on the ladder
        windows = [(g_start, g_end) for (_p, _h, _w, g_start, g_end)
                   in controls]
        wide = tuple((p, h, w, 0.0, 1.0) for (p, h, w, _s, _e) in controls)

        def controls_at(s_val: float):
            # step index i with sigmas[i] >= s_val > sigmas[i+1]
            j = int(_np.searchsorted(_ladder_asc, s_val, side="left"))
            idx = min(max(_n_lad - j, 0), max(_n_lad - 1, 0))
            frac = (idx + 0.5) / max(_n_lad, 1)
            # zero with a PYTHON float: a jnp scalar here would flip the
            # arg's weak_type at the window boundary and retrace the
            # 3-UNet-eval attempt executable mid-generation
            return tuple(
                (p, h, float(w) if gs <= frac <= ge else 0.0, lo, hi)
                for (p, h, w, lo, hi), (gs, ge) in zip(wide, windows))

        fn = self._denoise_fn(
            "adaptive", width=width, height=height, batch=batch,
            n_controls=len(controls), inpaint=inpainting,
            precision=precision_mod.resolve(payload, self.policy).name)
        pin = self._denoise_fn("adaptive-pin") if masked else None

        def attempt_fn(xx, x_prev, s, h, rtol, atol):
            with trace.STATS.timer("denoise_chunk"), \
                    obs_spans.span("chunk.enqueue", adaptive=True):
                return fn(self.params["unet"], xx, x_prev, s, h, rtol, atol,
                          inputs._replace(controls=controls_at(float(s))))

        # progress: accepted steps against the slider value (the controller
        # ignores the slider, so the bar is indicative, like webui's)
        self.state.begin(job, end - start_step)

        def on_accept(xx, sigma, n):
            self.state.step(min(n, end - start_step))
            if masked:
                # noise domain 2_000_000+n: disjoint from the fixed grid's
                xx = pin(xx, mask_lat, init_lat, image_keys,
                         jnp.float32(sigma), jnp.int32(n))
            return xx

        x_out, info = kd.sample_dpm_adaptive(
            attempt_fn, x, sigma_max, sigma_min,
            should_stop=lambda: self.state.flag.interrupted,
            on_accept=on_accept)
        if masked and info["completed"] and end == steps:
            # terminal pin at sigma=0: the protected region must come back
            # as the CLEAN init latent, exactly like the fixed-grid path's
            # last step (which pins with sigmas[steps] == 0) — without this
            # the whole unmasked area keeps sigma_min-level grain
            x_out = pin(x_out, mask_lat, init_lat, image_keys,
                        jnp.float32(0.0), jnp.int32(0))
        from stable_diffusion_webui_distributed_tpu.runtime.logging import (
            get_logger,
        )

        get_logger().debug(
            "dpm adaptive: %d accepted / %d rejected steps, %d UNet evals",
            info["n_accept"], info["n_reject"], info["nfe"])
        if not info["completed"] and not self.state.flag.interrupted:
            # non-interrupt incompletion (max_attempts backstop — e.g. a
            # pathological rtol rejecting forever): the latent handed to
            # the VAE is only partially denoised. Warn AND mark the
            # image's infotext so a user can tell a half-solved image
            # from a finished one (VERDICT r4 item 5).
            get_logger().warning(
                "dpm adaptive stopped INCOMPLETE after %d attempts "
                "(%d accepted); the image is partially denoised — "
                "marked in infotext", info["steps"], info["n_accept"])
            self._adaptive_incomplete = True
        self.state.finish()
        return x_out

    def _decode_fn(self, width: int, height: int, batch: int) -> Callable:
        key = ("decode", width, height, batch, self.family.name)

        def build():
            scale = self.family.vae.scaling_factor

            def decode(vae_params, latents):
                imgs = self.vae.apply(
                    {"params": vae_params}, latents / scale,
                    method=VAE.decode)
                return jnp.clip(imgs * 0.5 + 0.5, 0.0, 1.0)

            return jax.jit(decode)

        return self._cached(key, build)

    def _decode_u8_fn(self, width: int, height: int, batch: int) -> Callable:
        """Decode straight to uint8 pixels on-device: the host fetch moves
        4x fewer bytes than the f32 image."""
        key = ("decode-u8", width, height, batch, self.family.name)
        # resolve the float decode OUTSIDE the cached build: _cached holds a
        # non-reentrant lock, so a nested _decode_fn lookup would deadlock
        decode = self._decode_fn(width, height, batch)

        def build():
            def decode_u8(vae_params, latents):
                return (decode(vae_params, latents) * 255.0 + 0.5
                        ).astype(jnp.uint8)

            # the latent rows handed in by _queue_decoded are per-dispatch
            # slices, dead after decode — donate them so decoder scratch
            # reuses their HBM
            return jax.jit(decode_u8, donate_argnums=(1,))

        return self._cached(key, build)

    def _encode_image_fn(self, width: int, height: int, batch: int) -> Callable:
        key = ("img-encode", width, height, batch, self.family.name)

        def build():
            scale = self.family.vae.scaling_factor

            def encode(vae_params, images):
                mean, _ = self.vae.apply(
                    {"params": vae_params}, images * 2.0 - 1.0,
                    method=VAE.encode)
                return mean.astype(jnp.float32) * scale

            return jax.jit(encode)

        return self._cached(key, build)

    # -- LoRA ---------------------------------------------------------------

    def _lora_provider_gen(self) -> int:
        """The provider's reload generation (ModelRegistry.lora_generation,
        bumped by /refresh-loras); 0 for plain-callable providers. Folded
        into the merge latch and the traced-set LRU so a registry rescan
        retries unresolved names and rebuilds factor sets, while identical
        repeats stay no-ops."""
        owner = getattr(self.lora_provider, "__self__", None)
        return int(getattr(owner, "lora_generation", 0) or 0)

    def set_loras(self, specs) -> None:
        """Activate a stack of (name, unet_weight, te_weight) adapters
        (webui ``<lora:name:w[:te_w]>`` semantics; BASELINE config #4) by
        host merge. Re-merges from the pristine base on every change, so
        removing an adapter is exact, not approximate. The RESOLVED
        OUTCOME is latched — skipped names included, keyed by the
        provider's reload generation — so an identical repeat of a
        partially-resolved set is a no-op instead of a full re-merge;
        /refresh-loras bumps the generation and the next request retries
        (covers the add-file-then-refresh flow without the old
        merge-per-request tax)."""
        from stable_diffusion_webui_distributed_tpu.models import lora as lora_mod

        key = tuple(specs)
        gen = self._lora_provider_gen()
        if self._active_loras == () and not key:
            return  # pristine engine, empty request: nothing to undo
        if self._active_loras == (key, gen):
            return
        if not key and self._active_loras[0] == ():
            # already pristine, older provider generation — a rescan
            # can't change "no adapters"; refresh the latch, skip the
            # no-op merge and the cache-retiring epoch bumps
            self._active_loras = ((), gen)
            return
        params = self._base_params
        merged = 0
        t0 = time.perf_counter()
        for name, weight, te_weight in specs:
            sd = self.lora_provider(name) if self.lora_provider else None
            if sd is None:
                from stable_diffusion_webui_distributed_tpu.runtime.logging import (
                    get_logger,
                )

                get_logger().warning("lora '%s' not found; skipping", name)
                continue
            params, applied, skipped = lora_mod.merge_lora(
                params, sd, weight, self.family, te_weight=te_weight)
            merged += 1
        self.params = params
        self._active_loras = (key, gen)
        if merged:
            from stable_diffusion_webui_distributed_tpu.obs import (
                prometheus as obs_prom,
            )

            self._lora_merge_total += merged
            self._lora_merge_seconds += time.perf_counter() - t0
            obs_prom.count_lora_switch("merged")
        # TE weights changed: conds computed under the old merge are stale
        self._cond_epoch += 1
        self._cond_cache.clear()
        self._model_epoch += 1

    def _traced_set_for(self, specs: Tuple):
        """TracedSet for a spec tuple under SDTPU_LORA_TRACED, or None
        when the set can't ride the bucketing ladder (the caller then
        falls back to the merge path). LRU-cached per (specs, provider
        generation); a hit revalidates each adapter's state-dict IDENTITY
        against the provider, so the registry's mtime invalidation (an
        edited file reloads to a NEW dict) can never serve stale
        factors."""
        from stable_diffusion_webui_distributed_tpu.models import lora as lora_mod
        from stable_diffusion_webui_distributed_tpu.obs import (
            prometheus as obs_prom,
        )

        key = (tuple(specs), self._lora_provider_gen())
        ts = self._traced_cache.get(key)
        if ts is not None:
            if self.lora_provider is not None and all(
                    self.lora_provider(name) is src
                    for (name, _w, _tw), src in zip(ts.specs, ts.srcs)):
                self._traced_cache.move_to_end(key)
                return ts
            del self._traced_cache[key]
        t0 = time.perf_counter()
        ts = lora_mod.build_traced_set(
            specs, self.lora_provider, self.family, self._base_params)
        obs_prom.observe_lora_apply(time.perf_counter() - t0)
        if ts is None:
            return None
        self._traced_cache[key] = ts
        if len(self._traced_cache) > self._TRACED_CACHE_MAX:
            self._traced_cache.popitem(last=False)
        return ts

    def traced_te_content(self) -> str:
        """Content address of the ACTIVE traced set's text-encoder deltas,
        "" when no traced set is live or none of its factors touch the TE.
        cache/embed.py folds it into conditioning keys: a traced TE
        adapter can't alias the adapterless entry, while unet-only sets
        leave keys — and the embed cache — untouched across switches."""
        ts = self._traced_lora
        return ts.te_content if ts is not None and ts.te_content else ""

    def traced_content_for_payload(self, payload) -> str:
        """Content address of the traced set this payload WOULD serve
        under, resolvable before _apply_prompt_loras runs — the
        dispatcher folds it into result-dedupe keys at submit time. "" on
        the merged path (those keys already fold _model_epoch)."""
        from stable_diffusion_webui_distributed_tpu.models import lora as lora_mod

        if not lora_mod.traced_enabled():
            return ""
        _, tags = lora_mod.extract_lora_tags(payload.prompt)
        if not tags or kd.resolve_sampler(payload.sampler_name).adaptive:
            return ""
        ts = self._traced_set_for(tuple(tags))
        return ts.content if ts is not None else ""

    def _apply_prompt_loras(self, payload: GenerationPayload) -> None:
        """Activate adapters named in the prompt. The payload keeps its tags
        — infotext/result prompts must round-trip them (webui convention);
        only tokenization strips them (see encode_prompts).

        Under SDTPU_LORA_TRACED the tags resolve to a TracedSet instead
        of a host merge: factors ride as jit arguments, the param tree
        stays pristine, and NO epoch bumps (cache keys fold the set's
        content address instead). Sets the ladder can't bucket — and the
        DPM-adaptive sampler, whose attempt executable carries no delta
        arguments — fall back to the merged path unchanged."""
        from stable_diffusion_webui_distributed_tpu.models import lora as lora_mod

        _, tags = lora_mod.extract_lora_tags(payload.prompt)
        if lora_mod.traced_enabled() and not kd.resolve_sampler(
                payload.sampler_name).adaptive:
            ts = self._traced_set_for(tuple(tags)) if tags else None
            if ts is None and not tags:
                # warmup sweep: an all-zero stand-in set at an explicit
                # ladder cell pre-builds that cell's executables without
                # needing a real adapter on disk (serving/warmup.py)
                cell = getattr(self, "_warmup_lora", None)
                if cell is not None:
                    ts = lora_mod.zero_set(
                        self._base_params, self.family, *cell)
            if ts is not None or not tags:
                if self._active_loras:
                    # an earlier merged set is live on self.params —
                    # restore the pristine tree the traced deltas assume
                    self.set_loras(())
                changed = (ts.content if ts is not None else None) != \
                    (self._traced_lora.content
                     if self._traced_lora is not None else None)
                self._traced_lora = ts
                if changed and ts is not None:
                    from stable_diffusion_webui_distributed_tpu.obs import (
                        prometheus as obs_prom,
                    )

                    obs_prom.count_lora_switch("traced")
                return
        self._traced_lora = None
        if tags or self._active_loras:
            self.set_loras(tags)

    # -- VAE override -------------------------------------------------------

    def set_vae(self, vae_params: Optional[Dict]) -> None:
        """Swap in a standalone VAE (webui's sd_vae option; the reference
        syncs the choice across workers via /options, worker.py:646-688).
        ``None`` restores the checkpoint's own VAE."""
        if not hasattr(self, "_checkpoint_vae"):
            self._checkpoint_vae = self._base_params["vae"]
        target = self._checkpoint_vae if vae_params is None else \
            dtypes.cast_floating(vae_params, self.policy.param_dtype)
        if self.mesh is not None:
            from stable_diffusion_webui_distributed_tpu.parallel.sharding import (
                shard_params,
            )

            target = shard_params(target, self.mesh)
        self._base_params = {**self._base_params, "vae": target}
        self.params = {**self.params, "vae": target}
        self._blank_cond_cache.clear()  # conditioning latents are VAE-derived
        self._model_epoch += 1  # decoded bytes change: retire cached results

    # -- ControlNet ---------------------------------------------------------

    def _parse_controlnet_units(self, payload: GenerationPayload):
        """Extract enabled ControlNet units from ``alwayson_scripts`` —
        the same payload shape the reference packs (control_net.py:20-79;
        both Mikubill-style flat 'image' and Forge-style dict accepted)."""
        scripts = payload.alwayson_scripts or {}
        for key in ("controlnet", "ControlNet"):
            if key in scripts:
                units = []
                for u in scripts[key].get("args", []):
                    if not isinstance(u, dict) or not u.get("enabled", True):
                        continue
                    image = u.get("image") or u.get("input_image")
                    mask = u.get("mask")
                    if isinstance(image, dict):
                        # Mikubill dict form carries the mask channel the
                        # inpaint module consumes
                        mask = image.get("mask") or mask
                        image = image.get("image")
                    if not image:
                        continue
                    units.append({**u, "image": image, "mask": mask})
                return units
        return []

    def _prepare_controls(self, payload: GenerationPayload,
                          width: int, height: int):
        """Units -> (cn_params, hint(1,H,W,3), weight, g_start, g_end)."""
        units = self._parse_controlnet_units(payload)
        if not units:
            return ()
        from stable_diffusion_webui_distributed_tpu.models.controlnet import (
            run_preprocessor,
        )
        from stable_diffusion_webui_distributed_tpu.runtime.logging import (
            get_logger,
        )

        controls = []
        for u in units:
            name = u.get("model", "")
            cn_params = (self.controlnet_provider(name)
                         if self.controlnet_provider else None)
            if cn_params is None:
                get_logger().warning(
                    "controlnet model '%s' not found; unit skipped", name)
                continue
            img = b64png_to_array(u["image"])
            mask = b64png_to_array(u["mask"]) if u.get("mask") else None
            processed = run_preprocessor(u.get("module", "none"), img,
                                         mask=mask)
            # the hint embedder downsamples x8 into latent space; size the
            # hint so hint/8 == latent dims (equals width x height for real
            # SD families whose VAE factor is 8)
            lat_h, lat_w = self._latent_hw(width, height)
            processed = _resize_image(
                np.asarray(processed, np.float32), lat_w * 8, lat_h * 8)
            hint = jnp.asarray(processed)[None]
            # weights/windows stay python floats: the chunk loop uses them
            # host-side to skip ControlNet compute for chunks entirely
            # outside the guidance window
            controls.append((
                cn_params, hint,
                float(u.get("weight", 1.0)),
                float(u.get("guidance_start", 0.0)),
                float(u.get("guidance_end", 1.0)),
            ))
        return tuple(controls)

    # -- prompt conditioning -----------------------------------------------

    def encode_prompts(self, payload: GenerationPayload, prompts=None,
                       ragged=False):
        """Conditioning for the request.

        Default: one prompt -> ctx (1, L, D), broadcast over the batch in
        the denoiser. With ``prompts`` (per-image variation: prompt matrix
        etc.) each image gets its own row — ctx (B, L, D) — distinct
        prompts encoded once, all chunk-padded to one context length.
        Textual-inversion mentions resolve against the embedding store
        (models/embeddings.py) and ride as injection arrays.

        ``ragged`` (SDTPU_RAGGED conditioning): each prompt encodes at its
        TRUE chunk count (the embed cache keys on it — one entry per
        prompt, not per group max) and the *encoded* rows are zero-padded
        to the request context length; returns an extra
        ``(ctx_true_u, ctx_true_c)`` pair of valid token counts that the
        denoiser masks cross-attention with. Zero-padded rows are never
        attended to, so the pad value is inert.
        """
        from stable_diffusion_webui_distributed_tpu.models.embeddings import (
            build_injection_arrays,
        )
        from stable_diffusion_webui_distributed_tpu.models.lora import (
            extract_lora_tags,
        )
        from stable_diffusion_webui_distributed_tpu.models.prompt import (
            pad_chunks,
            tokenize_with_embeddings,
        )

        tok = self.tokenizer
        counts = self._embedding_counts()
        prompt_list = [payload.prompt] if prompts is None else list(prompts)
        cleaned = [extract_lora_tags(p)[0] for p in prompt_list]
        with obs_spans.span("tokenize", prompts=len(cleaned) + 1):
            toks = [tokenize_with_embeddings(tok, c, counts)
                    for c in cleaned]
            ids_u, w_u, inj_u = tokenize_with_embeddings(
                tok, payload.negative_prompt, counts)
        # cond and uncond must agree on context length (webui pads both);
        # payload.context_chunks floors it at the REQUEST-wide max so an
        # image's conditioning doesn't depend on its dispatch group /
        # worker slice (seed-exactness across the fan-out, payload.py)
        n = max([t[0].shape[0] for t in toks] + [ids_u.shape[0]]
                + ([payload.context_chunks] if payload.context_chunks
                   else []))
        bos, eos = tok.bos, tok.eos

        h_l = self.family.text_encoder.hidden_size
        h_g = (self.family.text_encoder_2.hidden_size
               if self.family.text_encoder_2 else 0)
        width = ids_u.shape[1]

        def inj_arrays(injections, n_enc):
            mask, val_l, val_g = build_injection_arrays(
                injections, n_enc, width, self.embedding_store, h_l, h_g)
            return (jnp.asarray(mask), jnp.asarray(val_l),
                    jnp.asarray(val_g))

        # clamp to webui's 1..12 range (0 = model default) AND the model's
        # usable depth (skip must leave at least one layer): clip_skip is a
        # static argument of the jitted encoder, so an unbounded request
        # value would mint one XLA executable per distinct int — and one
        # past the encoder depth asserts inside the trace
        depth = self.family.text_encoder.num_layers
        if self.family.text_encoder_2 is not None:
            depth = min(depth, self.family.text_encoder_2.num_layers)
        skip = min(12, depth - 1, max(0, int(payload.clip_skip or 0)))
        # traced TE adapters (SDTPU_LORA_TRACED): only sets whose factors
        # actually touch a text tower route to the sig'd encode variant —
        # unet-only sets keep the adapterless executable AND its cached
        # conditioning (unchanged by construction) across the switch
        ts = self._traced_lora
        te_sig = ts.sig if ts is not None and ts.te_content else ""
        enc = self._encode_fn(te_sig)
        te = self.params["text_encoder"]
        te2 = self.params["text_encoder_2"]
        store_gen = (self.embedding_store.generation
                     if self.embedding_store is not None else 0)

        # cache tier (cache/embed.py): with SDTPU_CACHE=1 the process-wide
        # content-addressed store supersedes the per-engine LRU below —
        # same texts, byte-capped, with per-half hit accounting. Gate off
        # (default): embed_cache stays None and the path is untouched.
        embed_cache = None
        from stable_diffusion_webui_distributed_tpu.cache import (
            keys as cache_keys,
        )

        if cache_keys.enabled():
            from stable_diffusion_webui_distributed_tpu.cache import (
                embed as embed_cache,
            )

        def encode_fresh(ids_c, w_c, inj_c, n_enc):
            pi, wi = pad_chunks(ids_c, w_c, n_enc, eos, bos)
            args = (te, te2, jnp.asarray(pi), jnp.asarray(wi), skip,
                    *inj_arrays(inj_c, n_enc))
            more = {} if not te_sig else {
                "te_lora": ts.tree.get("text_encoder"),
                "te2_lora": ts.tree.get("text_encoder_2")}
            work = obs_spans.device_work("encode")
            out = enc(*args, **more)
            work.queued(out[0])     # kept conditioning: never donated
            return out

        def cached_enc(raw, ids_c, w_c, inj_c, negative=False, n_enc=None):
            # cross-request cache (webui's cached_c/uc): same text at the
            # same clip_skip/chunk-count under the same TE weights and
            # embedding files encodes to the same conditioning. The ragged
            # path keys on the TRUE chunk count (n_enc), so one entry
            # serves the prompt under any group composition.
            n_enc = n if n_enc is None else n_enc
            if embed_cache is not None:
                return embed_cache.lookup_or_encode(
                    self, raw, skip, n_enc, negative,
                    lambda: encode_fresh(ids_c, w_c, inj_c, n_enc))
            key = (raw, skip, n_enc, self._cond_epoch, store_gen,
                   self.traced_te_content())
            hit = self._cond_cache.get(key)
            if hit is not None:
                self._cond_cache.move_to_end(key)
                return hit
            out = encode_fresh(ids_c, w_c, inj_c, n_enc)
            self._cond_cache[key] = out
            if len(self._cond_cache) > self._COND_CACHE_MAX:
                self._cond_cache.popitem(last=False)
            return out

        from stable_diffusion_webui_distributed_tpu.models.clip import (
            pad_encoded_context,
        )

        with trace.STATS.timer("text_encode"):
            ctxs, pooleds = [], []
            for (ids_c, w_c, inj_c), raw in zip(toks, cleaned):
                ctx, pooled = cached_enc(
                    raw, ids_c, w_c, inj_c,
                    n_enc=int(ids_c.shape[0]) if ragged else n)
                if ragged:
                    ctx = pad_encoded_context(ctx, n, width)
                ctxs.append(ctx)
                pooleds.append(pooled)
            ctx_c = ctxs[0] if len(ctxs) == 1 else jnp.concatenate(ctxs, 0)
            pooled_c = pooleds[0] if len(pooleds) == 1 \
                else jnp.concatenate(pooleds, 0)
            ctx_u, pooled_u = cached_enc(
                payload.negative_prompt, ids_u, w_u, inj_u, negative=True,
                n_enc=int(ids_u.shape[0]) if ragged else n)
            if ragged:
                ctx_u = pad_encoded_context(ctx_u, n, width)
        if ragged:
            # valid context tokens per CFG half (single-prompt path only —
            # the dispatcher's coalescable gate excludes all_prompts)
            ctx_true = (int(ids_u.shape[0]) * width,
                        int(toks[0][0].shape[0]) * width)
            return (ctx_u, ctx_c), (pooled_u, pooled_c), ctx_true
        return (ctx_u, ctx_c), (pooled_u, pooled_c)

    def _embedding_counts(self):
        """name -> n_vectors map for the tokenizer, or None when no
        embedding store is attached / the directory is empty."""
        if self.embedding_store is None:
            return None
        counts = self.embedding_store.vector_counts()
        return counts or None

    def request_context_chunks(self, payload: GenerationPayload) -> int:
        """Max context length in 77-token chunks over the request's full
        prompt set (every all_prompts row + the negative prompt). The
        planning master pins this into ``payload.context_chunks`` before
        any slicing so every dispatch group on every worker pads
        conditioning to the same chunk count (see payload.py)."""
        from stable_diffusion_webui_distributed_tpu.models.lora import (
            extract_lora_tags,
        )
        from stable_diffusion_webui_distributed_tpu.models.prompt import (
            tokenize_with_embeddings,
        )

        counts = self._embedding_counts()
        prompts = list(payload.all_prompts or [payload.prompt])
        lengths = [
            tokenize_with_embeddings(
                self.tokenizer, extract_lora_tags(p)[0],
                counts)[0].shape[0]
            for p in prompts
        ]
        lengths.append(tokenize_with_embeddings(
            self.tokenizer, payload.negative_prompt, counts)[0].shape[0])
        return int(max(lengths))

    def request_token_stats(self, payload: GenerationPayload,
                            chunks: Optional[int] = None):
        """(true_tokens, padded_tokens) for the request's conditioning —
        the perf ledger's ``token_padding_ratio`` feed. True tokens are
        BOS + content + closing EOS per chunk of the prompt and negative
        prompt (models/prompt.py ``true_token_count``); padded tokens are
        both halves grown to ``chunks`` (default: the request max) times
        the 77-token window. Tokenizes again, so callers gate on
        SDTPU_PERF."""
        from stable_diffusion_webui_distributed_tpu.models.lora import (
            extract_lora_tags,
        )
        from stable_diffusion_webui_distributed_tpu.models.prompt import (
            tokenize_with_embeddings, true_token_count,
        )

        counts = self._embedding_counts()
        eos = self.tokenizer.eos
        ids_c, _, _ = tokenize_with_embeddings(
            self.tokenizer, extract_lora_tags(payload.prompt)[0], counts)
        ids_u, _, _ = tokenize_with_embeddings(
            self.tokenizer, payload.negative_prompt, counts)
        if chunks is None:
            chunks = max(ids_c.shape[0], ids_u.shape[0],
                         int(payload.context_chunks or 0))
        width = ids_c.shape[1]
        true = true_token_count(ids_c, eos) + true_token_count(ids_u, eos)
        return true, 2 * int(chunks) * int(width)

    def _ragged_plan(self, payload: GenerationPayload):
        """(true_w, true_h) when this execution payload carries the ragged
        marker (serving/bucketer.py ``bucket_payload(ragged=True)``); None
        otherwise. The marker is only minted for dispatcher-coalescable
        txt2img work, so the ragged denoise never meets hires, refiner
        handoffs, masks, inpainting conditioning or ControlNet."""
        wh = (payload.override_settings or {}).get("ragged_true_wh")
        if not wh:
            return None
        return int(wh[0]), int(wh[1])

    def _added_cond(self, pooled_u, pooled_c, width, height,
                    aesthetic_score: float = 6.0, span=None):
        """SDXL micro-conditioning. The id-vector length is derived from the
        projection width: 6 ids for the base model (orig/crop/target sizes),
        5 for the refiner (sizes + aesthetic score). ``span`` (obs/spans.py,
        may be None) gets the attr ``added_cond``: ``none`` for a family
        without it, else ``hit`` | ``built`` by whether the embedded ids
        were kept."""
        ucfg = self.family.unet
        if not ucfg.addition_embed_dim:
            if span is not None:
                span.attrs["added_cond"] = "none"
            return None, None
        n_ids = (ucfg.projection_input_dim - ucfg.addition_embed_dim) \
            // ucfg.addition_time_embed_dim
        if n_ids == 5:
            # refiner: the negative branch is conditioned with a LOW
            # aesthetic score (sgm convention: 6.0 positive, 2.5 negative)
            ids_c = [height, width, 0, 0, aesthetic_score]
            ids_u = [height, width, 0, 0, 2.5]
        else:
            ids_c = [height, width, 0, 0, height, width][:n_ids]
            ids_u = ids_c
        # time-id rows track the pooled batch (per-image prompts make
        # pooled_c (B, D) rather than (1, D)). Their embedding depends on
        # (ids, rows, embed dim) alone and is kept; the request pays the
        # concatenation with its own pooled text.
        dim = ucfg.addition_time_embed_dim
        emb_u, hit_u = _embedded_time_ids(ids_u, pooled_u.shape[0], dim)
        emb_c, hit_c = _embedded_time_ids(ids_c, pooled_c.shape[0], dim)
        hit = hit_u and hit_c
        PLAN.record("added_cond", hit)
        if span is not None:
            span.attrs["added_cond"] = "hit" if hit else "built"
        return (join_added_cond(pooled_u, emb_u),
                join_added_cond(pooled_c, emb_c))

    # -- generation ---------------------------------------------------------

    def generate_range(
        self,
        payload: GenerationPayload,
        start_index: int = 0,
        count: Optional[int] = None,
        job: str = "txt2img",
    ) -> GenerationResult:
        """Produce images [start_index, start_index+count) of the request.

        This is the worker-side unit of the batch-DP split: the scheduler
        assigns each backend a contiguous range, exactly as the reference
        assigns each HTTP worker a sub-batch plus a seed offset
        (distributed.py:284-319)."""
        payload = payload.model_copy()
        payload.seed = fix_seed(payload.seed)
        payload.subseed = fix_seed(payload.subseed)
        # safety reset of the DPM-adaptive incompletion latch (set by
        # _denoise_adaptive; snapshot-and-cleared PER GROUP by
        # _queue_decoded so complete batches are never mislabeled)
        self._adaptive_incomplete = False
        expansion = prompt_expansion_args(payload) \
            if self.expander is not None else None
        drawn = account = None
        if expansion is not None:
            count = payload.total_images if count is None else count
            drawn, account = self._expand_prompts(
                payload, expansion, start_index, count)
        try:
            if payload.all_prompts and payload.context_chunks is None:
                # full-request entry (a sub-range over HTTP arrives with
                # the master's value): pin the request-wide context length
                # so group membership can't change an image's conditioning
                payload.context_chunks = self.request_context_chunks(payload)
            self._apply_prompt_loras(payload)
            count = payload.total_images if count is None else count
            with obs_spans.span("generate_range", job=job,
                                start=int(start_index), count=int(count),
                                size=f"{payload.width}x{payload.height}"):
                if payload.init_images:
                    if account is not None:     # where it always ran
                        account()
                    return self._run_img2img(payload, start_index, count,
                                             job)
                return self._run_txt2img(payload, start_index, count, job,
                                         drawn=drawn, account=account)
        finally:
            # an expansion's counters, where no chunk was enqueued to run
            # them under (an interrupt, an error): /internal/status read
            # after a request shows that request
            if account is not None:
                account()

    def _draws_ahead(self, payload) -> bool:
        """Whether an expanded range's first group can be drawn before its
        text is known: txt2img (img2img's noise waits for the init
        latents) at the payload's own size with a fixed ladder of steps,
        and nothing in the draw that is set up after it (ControlNet hints,
        a refiner hand-over, the hires pass) or that reads the text (a
        ragged range's true context length)."""
        return not (
            payload.init_images or payload.enable_hr
            or (payload.refiner_checkpoint
                and payload.refiner_switch_at < 1.0)
            or self._ragged_plan(payload) is not None
            or kd.resolve_sampler(payload.sampler_name).adaptive
            or self._parse_controlnet_units(payload))

    def _draw_ahead(self, payload, start: int, count: int) -> Drawn:
        """:class:`Drawn` for the range's first group, through the
        functions ``_run_txt2img`` and ``_denoise_range_timed`` run for a
        range that brings none. Its few device programs (the noise, its
        scale, the carry's zeros, the keys) queue behind the decode chunk
        in flight."""
        width, height = payload.width, payload.height
        plan = self._request_plan(payload, width, height)
        _, gen_n = self._group_rows(payload, start, count, width, height)
        x, keys, _ = self._draw_group(payload, start, gen_n, width, height,
                                      plan.sigmas)
        with obs_spans.span("denoise.inputs"):
            cfg, carry = self._range_start(payload, x)
        with obs_spans.span("denoise.plan") as plan_span:
            range_plan = self._range_plan(payload, payload.steps, plan_span)
        PLAN.record_ahead("drawn")
        return Drawn((start, gen_n, width, height), plan, x, keys, cfg,
                     carry, range_plan)

    def _expand_prompts(self, payload, expansion, start: int, count: int):
        """The ``expand`` stage: images [start, start+count) get their
        prompts continued by the resident language model, each keyed by
        its own seed, so a sub-range expands exactly its share. The images
        of the range whose prompt text is equal (every image of a plain
        batch; not a prompt matrix's) form one group, the groups run in
        the order of their first image, and a group is expanded together
        (pipeline/expand.py:expand_batch: one prefill, every image a
        sequence of the decode steps where the expander's layer kinds
        allow). Mutates ``payload`` (generate_range's copy).

        Returns ``(drawn, account)``. Under the first group's first decode
        chunk the range's own first group is drawn (:class:`Drawn`; None
        where ``_draws_ahead`` says no, where no chunk was enqueued, or
        after an interrupt: ``_run_txt2img`` then draws as ever).
        ``account`` fetches the counters the expansion left on the device
        (``serving.expander``) and is safe to call again: the caller runs
        it once the UNet is queued."""
        total = payload.total_images
        prompts = list(payload.all_prompts or [payload.prompt] * total)
        groups: dict = {}   # prompt text -> {image's key index: images}
        for i in range(start, min(start + count, len(prompts))):
            groups.setdefault(prompts[i], {}).setdefault(
                0 if payload.same_seed else i, []).append(i)
        drawn: list = []
        accounts: list = []
        meanwhile = (lambda: drawn.append(
            self._draw_ahead(payload, start, count))) \
            if self._draws_ahead(payload) else None
        for text, by_index in groups.items():
            expanded = self.expander.expand_batch(
                text, expansion, payload.seed, list(by_index),
                meanwhile=meanwhile, later=accounts)
            meanwhile = None    # the first group's alone
            for images, new in zip(by_index.values(), expanded):
                for i in images:
                    prompts[i] = new
        if total == 1 and not payload.all_prompts:
            payload.prompt = prompts[0]
        else:
            payload.all_prompts = prompts
        if expansion.context_chunks:
            payload.context_chunks = int(expansion.context_chunks)

        def account() -> None:
            while accounts:
                accounts.pop(0)()

        return (drawn[0] if drawn else None), account

    def txt2img(self, payload: GenerationPayload) -> GenerationResult:
        # top-level request: reset the interrupt latch and expand native
        # scripts (prompt matrix). generate_range must do NEITHER — it is
        # the per-worker unit of a fleet fan-out: clearing the latch there
        # would race the remote watchdogs out of a live interrupt, and
        # re-expansion would change image counts mid-plan (World.execute
        # owns both at fleet scope).
        self.state.begin_request()
        return self.generate_range(apply_scripts(payload), 0, None,
                                   "txt2img")

    def img2img(self, payload: GenerationPayload) -> GenerationResult:
        self.state.begin_request()
        return self.generate_range(apply_scripts(payload), 0, None,
                                   "img2img")

    # -- internals -----------------------------------------------------------

    def _latent_hw(self, width, height):
        f = self.family.vae_scale_factor
        return height // f, width // f

    def _place_batch(self, x):
        """Split the batch over the mesh's dp axis when it divides evenly;
        the remainder case falls back to single-placement (pad-and-mask is
        the scheduler's job via mesh.pad_batch)."""
        if self.mesh is None:
            return x
        dp = self.mesh.shape.get("dp", 1)
        if dp <= 1 or x.shape[0] % dp != 0:
            return x
        from stable_diffusion_webui_distributed_tpu.parallel.sharding import (
            place_batch,
        )

        return place_batch(x, self.mesh)

    def _image_keys(self, payload, start, batch):
        # ENSD (eta_noise_seed_delta) offsets the SAMPLER noise seed only —
        # init noise is untouched — matching webui, where ancestral noise
        # is seeded with seed+ENSD. Carried in override_settings like the
        # sdapi payloads the reference forwards.
        ensd = int((payload.override_settings or {})
                   .get("eta_noise_seed_delta", 0) or 0)
        # wrap like a 32-bit seed register: seed+ENSD can leave uint32
        # range (seed near 2**32 with the community ENSD 31337, or a
        # negative ENSD) and the host-side uint32 cast would raise
        seed = (payload.seed + ensd) % (2 ** 32)
        # variation/same-seed batches pin every key to image 0
        # (see runtime/rng.py); jitted — one dispatch, not an eager vmap
        pin = payload.subseed_strength > 0 or payload.same_seed
        return rng.batch_keys(seed, start, batch, pin_index=pin)

    def _group_conds(self, payload, pos, gen_n, refiner):
        """Per-image conditioning for images [pos, pos+gen_n) of a request
        carrying ``all_prompts``; pad-and-drop tail rows repeat the last
        prompt (those images are discarded)."""
        prompts = list(payload.all_prompts[pos:pos + gen_n])
        if not prompts:
            prompts = [payload.prompt]
        while len(prompts) < gen_n:
            prompts.append(prompts[-1])
        conds, pooleds = self.encode_prompts(payload, prompts=prompts)
        ref_cond = (refiner.encode_prompts(payload, prompts=prompts)
                    if refiner else None)
        return conds, pooleds, ref_cond

    def _seed_resize_latent(self, payload):
        """(from_h, from_w) in latent units, or None when disabled."""
        if payload.seed_resize_from_w > 0 and payload.seed_resize_from_h > 0:
            f = self.family.vae_scale_factor
            return (payload.seed_resize_from_h // f,
                    payload.seed_resize_from_w // f)
        return None

    def _apply_inpaint_fill(self, payload, init_lat, mask_lat, image_keys):
        """webui ``inpainting_fill`` masked-content modes (the enum the
        reference ships untouched in payloads): 1 = original (default),
        0 = fill with the unmasked region's mean color, 2 = latent noise,
        3 = latent nothing (zeros)."""
        fill = payload.inpainting_fill
        if mask_lat is None or fill == 1:
            return init_lat
        m = mask_lat  # 1 = repaint
        if fill == 3:
            return init_lat * (1.0 - m)
        if fill == 2:
            def fill_noise(k):
                return jax.random.normal(
                    jax.random.fold_in(k, 3_000_000), init_lat.shape[1:],
                    jnp.float32)

            # UNIT-variance fill (webui create_random_tensors): the img2img
            # loop adds sigma-scaled sampling noise on top, landing the
            # masked region at std sqrt(1+sigma^2) like webui
            extra = jax.vmap(fill_noise)(image_keys)
            return init_lat * (1.0 - m) + m * extra
        if fill == 0:
            keep = jnp.maximum(1e-6, (1.0 - m).sum(axis=(1, 2),
                                                   keepdims=True))
            mean = (init_lat * (1.0 - m)).sum(axis=(1, 2),
                                              keepdims=True) / keep
            return init_lat * (1.0 - m) + m * mean
        return init_lat

    def _denoise(self, payload, x, image_keys, conds, pooleds, width, height,
                 start_step, steps, job, controls=()):
        return self._denoise_range(payload, x, image_keys, conds, pooleds,
                                   width, height, start_step, steps, job,
                                   None, None, controls)

    def _denoise_range(self, payload, x, image_keys, conds, pooleds,
                       width, height, start_step, steps, job,
                       mask_lat, init_lat, controls=(), end_step=None,
                       inpaint_cond=None, ragged=None, lora=None,
                       drawn=None, account=None):
        """Obs-span wrapper around the chunk loop: one ``denoise_range``
        span (host-side perf_counter, no extra device sync) grouping the
        per-chunk ``denoise_chunk`` spans StageStats feeds in, each the
        parent of its ``chunk.enqueue`` and ``chunk.fence_wait``."""
        with obs_spans.span("denoise_range", sampler=payload.sampler_name,
                            steps=int(steps), start_step=int(start_step),
                            batch=int(x.shape[0]), size=f"{width}x{height}"):
            return self._denoise_range_timed(
                payload, x, image_keys, conds, pooleds, width, height,
                start_step, steps, job, mask_lat, init_lat, controls,
                end_step, inpaint_cond, ragged, lora, drawn, account)

    def _range_start(self, payload, x):
        """``(cfg scale, fresh carry)``: the part of ``denoise.inputs``
        that reads no conditioning."""
        return jnp.float32(payload.cfg_scale), kd.init_carry(x)

    def _range_plan(self, payload, steps, span=None) -> RangePlan:
        """The part of ``denoise.plan`` that reads no conditioning.

        Step-cache policy (pipeline/stepcache.py): deep-feature reuse +
        CFG truncation. Inactive (cadence 1, cutoff 0 — the default)
        routes every chunk to the UNCHANGED plain executable, so default
        outputs stay byte-identical by construction. The cutoff sigma is
        located on the built ladder host-side (searchsorted, like the
        adaptive path's CN window gating) and rides into the executable
        as a traced step index.

        Serving precision (pipeline/precision.py): resolved once per
        range, static in the chunk executable key. A request that
        specifies nothing resolves to the policy default, whose module
        pair IS the constructor-built one — the default path routes to
        the unchanged executables byte-for-byte. The int8 activation
        scales are computed inside the traced fn per call (dynamic
        per-tensor, ops/quant.py), so they never recompile anything."""
        spec = kd.resolve_sampler(payload.sampler_name)
        sc = stepcache.resolve(payload)
        prec = precision_mod.resolve(payload, self.policy)
        ladder = self._ladder(spec, steps, span)
        cfg_stop = stepcache.cutoff_step(ladder.host, sc.cutoff_sigma)
        return RangePlan(sc, prec, cfg_stop)

    def _denoise_range_timed(self, payload, x, image_keys, conds, pooleds,
                             width, height, start_step, steps, job,
                             mask_lat, init_lat, controls=(), end_step=None,
                             inpaint_cond=None, ragged=None, lora=None,
                             drawn=None, account=None):
        """Host-side chunk loop with interrupt/progress between dispatches
        (compiled-loop version of the reference's 0.5 s poll,
        worker.py:440-448). ``steps`` sizes the sigma ladder; the loop runs
        [start_step, end_step or steps) — a partial range is how the
        base half of a base+refiner pass stops at the switch point.

        ``ragged``: ``(true_rows, ctx_true_u, ctx_true_c)`` traced (B,)
        int32 vectors (serving/dispatcher.py ragged mode). Routes every
        chunk to the ragged executable variant; the step cache and prefix
        sharing are disabled for ragged ranges (their carries assume the
        dense row layout end to end).

        ``lora``: ``(sig, content, rows_tree)`` — the traced adapter
        triple (models/lora.py): static sig for the chunk key, content
        address for the prefix key, per-row [B, slots, ...] UNet delta
        tree as traced data. None (the default) adopts the engine's
        active traced set (_apply_prompt_loras), broadcast over this
        range's batch — the dispatcher passes an explicit stacked triple
        for heterogeneous coalesced groups.

        ``drawn``: what an expanded request made of this range while its
        expander decoded (:class:`Drawn`: the carry of ``x``, the cfg
        scalar, the plan); None makes them here. ``account``: the
        expander's counters' fetch, run once the first chunk is queued."""
        if kd.resolve_sampler(payload.sampler_name).adaptive:
            if account is not None:     # no chunk to run it under
                account()
            # the adaptive attempt executable carries no delta args;
            # _apply_prompt_loras routes adaptive requests to the merged
            # path, so no traced set can be live here
            return self._denoise_adaptive(
                payload, x, image_keys, conds, pooleds, width, height,
                start_step, steps, job, mask_lat, init_lat, controls,
                end_step, inpaint_cond)
        # everything up to the first enqueue runs with the device idle:
        # these two spans own it
        with obs_spans.span("denoise.inputs") as inputs_span:
            (ctx_u, ctx_c) = conds
            au, ac = self._added_cond(*pooleds, width, height,
                                      span=inputs_span)
            batch = x.shape[0]
            if lora is None and self._traced_lora is not None:
                from stable_diffusion_webui_distributed_tpu.models import (
                    lora as lora_mod,
                )

                ts = self._traced_lora
                lora = (ts.sig, ts.content,
                        lora_mod.broadcast_set(ts, batch)["unet"])
            lora_sig, lora_content, lora_rows = lora or ("", "", None)
            masked = mask_lat is not None
            inpainting = self.family.inpaint and inpaint_cond is not None
            if drawn is not None:
                assert drawn.x is x and start_step == 0
                cfg, carry = drawn.cfg, drawn.carry
            else:
                cfg, carry = self._range_start(payload, x)
            inputs = denoise.Inputs(
                ctx_u, ctx_c, cfg, image_keys,
                au, ac, mask_lat, init_lat if masked else None,
                inpaint_cond=inpaint_cond if inpainting else None,
                lora=lora_rows, ragged=ragged)
            end = steps if end_step is None else min(end_step, steps)
        with obs_spans.span("denoise.plan") as plan_span:
            sc, prec, cfg_stop = drawn.range_plan \
                if drawn is not None \
                else self._range_plan(payload, steps, plan_span)
            use_cache = (sc.active and cache_supported(self.family.unet)
                         and ragged is None)
            cache = valid = None
            if use_cache:
                # [uncond; cond] deep-feature rows; a fresh range starts
                # INVALID so the first step always refreshes — which is also
                # what makes an interrupt-resume boundary safe mid-cadence
                cache = jnp.zeros(
                    deep_cache_shape(self.family.unet, 2 * batch,
                                     x.shape[1], x.shape[2]),
                    self.policy.compute_dtype)
                valid = jnp.asarray(False)
                cached_inputs = inputs._replace(cadence=jnp.int32(sc.cadence),
                                                cfg_stop=jnp.int32(cfg_stop))

            # Denoise prefix sharing (cache/prefix.py, SDTPU_CACHE): only for
            # ranges where a captured prefix can be BYTE-identical — the plain
            # txt2img base range with nothing that injects per-step state the
            # capture can't carry (masks, inpaint conditioning, ControlNet
            # windows) and nothing already consumed (start_step 0).
            prefix_plan = None
            if (job == "txt2img" and start_step == 0 and not masked
                    and not inpainting and not controls and end > 0
                    and ragged is None):
                from stable_diffusion_webui_distributed_tpu.cache import (
                    keys as cache_keys,
                )

                if cache_keys.enabled():
                    from stable_diffusion_webui_distributed_tpu.cache import (
                        prefix as cache_prefix,
                    )

                    prefix_plan = cache_prefix.plan(
                        self, payload, batch=batch, width=width, height=height,
                        steps=steps, end=end,
                        cadence=(sc.cadence if use_cache else 1),
                        sc_active=use_cache, precision=prec.name,
                        cfg_stop=cfg_stop, lora=lora_content)

            self.state.begin(job, end - start_step)
        done = 0
        pos = start_step
        if prefix_plan is not None and prefix_plan.resume is not None:
            # resume mid-trajectory: the captured carry (latent + full
            # multistep history) re-placed on the mesh replaces the fresh
            # init_carry; the loop re-enters the same chunk executables a
            # continuous run would use at this boundary. The deep-feature
            # cache stays invalid — prefix_boundary only blessed split
            # points where the continuous run refreshes anyway.
            k, leaves = prefix_plan.resume
            carry = kd.Carry(
                self._place_batch(jnp.asarray(leaves[0])),
                self._place_batch(jnp.asarray(leaves[1])),
                jnp.asarray(leaves[2]),
                self._place_batch(jnp.asarray(leaves[3])),
                self._place_batch(jnp.asarray(leaves[4])),
                jnp.asarray(leaves[5]))
            pos = k
            done = k
            self.state.step(done)
        # Depth-1 pipelining: dispatch chunk i while chunk i-1 still runs
        # on-device, so the host->device roundtrip overlaps compute.
        # Interrupt latency stays <= 2
        # chunks: the flag is checked before every dispatch and at most
        # one extra chunk is in flight when it flips. The host paces on
        # each chunk's FENCE output, never its carry — the carry buffers
        # are donated into the next dispatch.
        pending = None  # (fence, chunk_length) still running on-device
        while pos < end:
            if self.state.flag.interrupted:
                break
            hook = self.preempt_hook
            if hook is not None and hook.should_yield():
                # chunk-boundary yield: drain the in-flight chunk so the
                # device is quiet, then block in the gate until the fleet
                # hands it back. Everything the loop needs (carry, cache,
                # valid, pos) lives in this frame — resumption is
                # byte-identical and reuses the same executables.
                if pending is not None:
                    with obs_spans.span("chunk.fence_wait",
                                        steps=pending[1]), \
                            obs_spans.fence(pending[0]):
                        pending[0].block_until_ready()
                    done += pending[1]
                    self.state.step(done)
                    pending = None
                interrupted_before_yield = self.state.flag.interrupted
                hook.yield_device()
                # an interloper that carried <lora:...> tags patched the
                # live params during the yield; re-resolve THIS payload's
                # adapter set so the remaining chunks run on the weights
                # the request started with (tagless -> pristine base)
                self._apply_prompt_loras(payload)
                # the interloper also drove the shared progress record and
                # interrupt latch (its begin_request clears the flag, and
                # an interrupt aimed at IT may still be latched); restore
                # this range's view of both
                self.state.begin(job, end - start_step)
                if done:
                    self.state.step(done)
                self.state.restore_interrupt(interrupted_before_yield)
                continue  # re-check the restored latch at the loop top
            length = min(self.chunk_size, end - pos)
            # drop units whose guidance window misses this chunk entirely —
            # a gated-off ControlNet forward is ~half a UNet of wasted MXU
            lo = (pos + 0.5) / steps
            hi = (pos + length - 0.5) / steps
            active = tuple(c for c in controls
                           if c[3] <= hi and c[4] >= lo)
            # ControlNet windows bypass the step cache: residuals feed the
            # deep blocks, so a stale deep feature would drop them
            cached_chunk = use_cache and not active
            fn = self._denoise_fn(
                "chunk", payload.sampler_name, steps, width, height, batch,
                length, prec.name, masked=masked, n_controls=len(active),
                inpaint=inpainting, ragged=ragged is not None,
                lora_sig=lora_sig, step_cache=cached_chunk)
            state, chunk_inputs = carry, inputs._replace(controls=active)
            if cached_chunk:
                state = denoise.CachedState(carry, cache, valid)
                chunk_inputs = cached_inputs
            # denoise_chunk is the enqueue of chunk i plus the wait on chunk
            # i-1's fence: its two children say which
            with trace.STATS.timer("denoise_chunk"):
                with obs_spans.span("chunk.enqueue", pos=pos, steps=length):
                    work = obs_spans.device_work("run_chunk")
                    state, fence = fn(self.params["unet"], state,
                                      jnp.int32(pos), chunk_inputs)
                    work.queued(fence)      # the carry is donated: never it
                    if cached_chunk:
                        carry, cache, valid = state
                    else:
                        carry = state
                        if valid is not None:
                            # a plain (CN-active) chunk advanced the latent
                            # outside the cache's view — refresh on re-entry
                            valid = jnp.asarray(False)
                if pending is not None:
                    with obs_spans.span("chunk.fence_wait",
                                        steps=pending[1]), \
                            obs_spans.fence(pending[0]):
                        pending[0].block_until_ready()
                    done += pending[1]
                    self.state.step(done)
            pending = (fence, length)
            pos += length
            if account is not None:
                # the UNet is queued: what the expander counted comes down
                # under it
                account()
                account = None
            if prefix_plan is not None and not prefix_plan.captured:
                # capture at the designated chunk boundary: np.asarray
                # materializes host copies of the carry NOW — the next
                # dispatch donates these buffers, after which they are
                # gone. The implied device sync is the price of the
                # gated-on path only.
                cache_prefix.maybe_capture(prefix_plan, pos, tuple(carry))
        if pending is not None:
            with obs_spans.span("chunk.fence_wait", steps=pending[1]), \
                    obs_spans.fence(pending[0]):
                pending[0].block_until_ready()
            done += pending[1]
            self.state.step(done)
        self.state.finish()
        return carry.x

    def _ladder(self, spec, steps, span=None) -> kd.Ladder:
        """This engine's kept sigma ladder; ``span`` (may be None) gets the
        attr ``ladder``: ``hit`` | ``built``."""
        ladder, hit = kd.ladder(spec, self.schedule, steps)
        if span is not None:
            span.attrs["ladder"] = "hit" if hit else "built"
        return ladder

    # -- inpainting-model (hybrid) conditioning -----------------------------

    def _blank_inpaint_cond(self, batch, width, height):
        """txt2img / maskless-img2img conditioning for an inpainting
        checkpoint: repaint-everything mask + VAE-encoded blank (mid-gray)
        image — webui's txt2img_image_conditioning for hybrid models.
        Depends only on (batch, size) and the VAE, so it's cached per
        bucket; ``set_vae`` invalidates (engine.py)."""
        key = (batch, width, height)
        cached = self._blank_cond_cache.get(key)
        if cached is not None:
            return cached
        h, w = self._latent_hw(width, height)
        # encode ONE gray frame and tile: rows are identical, and a
        # batch-1 encode keeps VAE scratch flat at SDXL sizes
        gray = jnp.full((1, height, width, 3), 0.5, jnp.float32)
        lat = self._encode_image_fn(width, height, 1)(
            self.params["vae"], gray)
        mask = jnp.ones((1, h, w, 1), jnp.float32)
        cond = jnp.tile(jnp.concatenate([mask, lat], axis=-1),
                        (batch, 1, 1, 1))
        self._blank_cond_cache[key] = cond
        return cond

    def _masked_inpaint_cond(self, batch, width, height, init, mask_pixels):
        """Real-mask conditioning: rounded mask + VAE encode of the masked
        init image (masked region mid-gray, webui's
        img2img_image_conditioning for hybrid models)."""
        h, w = self._latent_hw(width, height)
        m = np.round(np.clip(mask_pixels, 0.0, 1.0))
        masked = init * (1.0 - m) + 0.5 * m
        # identical rows: batch-1 encode + repeat (bounded VAE scratch)
        lat = jnp.repeat(self._encode_image_fn(width, height, 1)(
            self.params["vae"], jnp.asarray(masked)[None]), batch, axis=0)
        mask_lat = jnp.round(jnp.asarray(np.asarray(
            jax.image.resize(m, (h, w, 1), "bilinear")),
            jnp.float32))[None].repeat(batch, axis=0)
        return jnp.concatenate([mask_lat, lat], axis=-1)

    def _request_plan(self, payload, width, height) -> RequestPlan:
        with obs_spans.span("request.plan") as plan_span:
            spec = kd.resolve_sampler(payload.sampler_name)
            sigmas = self._ladder(spec, payload.steps, plan_span).sigmas
            controls = self._prepare_controls(payload, width, height)
            refiner = self._refiner_engine(payload)
        return RequestPlan(sigmas, controls, refiner)

    def _group_rows(self, payload, pos, remaining, width, height):
        """``(images kept, rows generated)`` of the group that starts at
        image ``pos`` with ``remaining`` still to make."""
        group = max(1, payload.group_size or payload.batch_size)
        n = min(group, remaining)
        if n < group and self._has_batch_bucket(
                payload.sampler_name, payload.steps, width, height, group):
            # pad-and-drop: reuse the already-compiled full-group
            # executable instead of compiling a remainder bucket (the
            # TPU replacement for the reference's remainder round-robin,
            # SURVEY.md §7 layer 5; extra images cost FLOPs once, a new
            # compile costs minutes)
            return n, group
        return n, n

    def _draw_group(self, payload, pos, gen_n, width, height, sigmas,
                    ragged_wh=None, ctx_true=None):
        """``(x, image keys, ragged)`` of the ``gen_n`` rows from image
        ``pos`` on: the noise at the ladder's first sigma."""
        h, w = self._latent_hw(width, height)
        # sampled latent channels — NOT unet.in_channels, which counts the
        # mask/masked-image conditioning of inpainting checkpoints too
        C = self.family.vae.latent_channels
        # ragged: true latent rows (ceil: a partial row still needs
        # its pixels); noise drawn at the TRUE height and
        # zero-padded so the masked tail starts exactly 0 and row
        # content is independent of the bucket height the request
        # landed in
        tr = h if ragged_wh is None else min(
            h, -(-ragged_wh[1] // self.family.vae_scale_factor))
        with obs_spans.span("noise"):
            noise = rng.batch_noise(
                payload.seed, payload.subseed,
                payload.subseed_strength, pos, gen_n, (tr, w, C),
                seed_resize=self._seed_resize_latent(payload),
                pin_index=payload.same_seed)
        ragged = None
        with obs_spans.span("batch.assemble"):
            if ragged_wh is not None:
                noise = jnp.pad(
                    noise, ((0, 0), (0, h - tr), (0, 0), (0, 0)))
                ragged = (
                    jnp.full((gen_n,), tr, jnp.int32),
                    jnp.full((gen_n,), ctx_true[0], jnp.int32),
                    jnp.full((gen_n,), ctx_true[1], jnp.int32))
            x = self._place_batch(
                noise.astype(jnp.float32) * sigmas[0])
            keys = self._image_keys(payload, pos, gen_n)
        return x, keys, ragged

    def _run_txt2img(self, payload, start, count, job,
                     width=None, height=None, drawn=None,
                     account=None) -> GenerationResult:
        """``drawn``: the first group as an expanded request drew it ahead
        (:class:`Drawn`); ``account``: the expansion's counters' fetch,
        for the first denoise range to run once its first chunk is queued
        (``generate_range`` runs it where none was)."""
        width = width or payload.width
        height = height or payload.height
        sigmas, controls, refiner = drawn.plan if drawn is not None \
            else self._request_plan(payload, width, height)
        # ragged solo dispatch (SDTPU_RAGGED): the bucketer stamped the
        # true requested shape; denoise at the bucket shape with the true
        # latent row count as traced data. Guarded by the same exclusions
        # the dispatcher's coalescable gate applies, so a hand-built
        # marker on ineligible work degrades to the classic path.
        ragged_wh = None
        if not (payload.all_prompts or payload.enable_hr or refiner
                or controls or self.family.inpaint):
            ragged_wh = self._ragged_plan(payload)
        conds = pooleds = ref_cond = None
        ctx_true = None
        if not payload.all_prompts:
            # conditioning resolved ONCE per request, not per batch group;
            # per-image prompts resolve per group in the loop instead
            with obs_spans.span("prepare"):
                if ragged_wh is not None:
                    conds, pooleds, ctx_true = self.encode_prompts(
                        payload, ragged=True)
                else:
                    conds, pooleds = self.encode_prompts(payload)
                ref_cond = refiner.encode_prompts(payload) \
                    if refiner else None
        out = GenerationResult(parameters=payload.model_dump())

        # Generate in groups of batch_size so the compiled batch dim is
        # stable across n_iter (reference batches the same way).
        pos = start
        remaining = count
        pending = []
        while remaining > 0 and not self.state.flag.interrupted:
            n, gen_n = self._group_rows(payload, pos, remaining, width,
                                        height)
            with obs_spans.span("prepare", group=pos, images=gen_n):
                taken = None
                if drawn is not None:
                    # the first group's; one of another shape (the ladder
                    # of batch buckets moved under the expansion) is of no
                    # use
                    if drawn.group == (pos, gen_n, width, height):
                        taken = drawn
                    PLAN.record_ahead("taken" if taken else "dropped")
                    drawn = None
                if taken is not None:
                    x, keys, ragged = taken.x, taken.keys, None
                else:
                    x, keys, ragged = self._draw_group(
                        payload, pos, gen_n, width, height, sigmas,
                        ragged_wh, ctx_true)
                if payload.all_prompts:
                    conds, pooleds, ref_cond = self._group_conds(
                        payload, pos, gen_n, refiner)
                inp = (self._blank_inpaint_cond(gen_n, width, height)
                       if self.family.inpaint else None)
            latents = self._split_denoise(
                payload, x, keys, conds, pooleds, width, height, job,
                controls, refiner, ref_cond, payload.steps, 0,
                inpaint_cond=inp, ragged=ragged, drawn=taken,
                account=account)
            account = None      # the first range's
            out_w, out_h = width, height
            if payload.enable_hr and not self.state.flag.interrupted:
                latents, out_w, out_h = self._hires_pass(
                    payload, latents, keys, conds, pooleds, job,
                    refiner, ref_cond)
            pending.extend(self._queue_decoded(latents, pos, n, out_w, out_h))
            # depth-1 pipeline: keep only the newest decode in flight so
            # large n_iter jobs don't accumulate decoded buffers in HBM
            if len(pending) > 1:
                self._flush_decoded(out, payload, pending[:-1])
                pending = pending[-1:]
            pos += n
            remaining -= n
        if drawn is not None:   # an interrupt before the first group
            PLAN.record_ahead("dropped")
        self._flush_decoded(out, payload, pending)
        return out

    def _refiner_engine(self, payload) -> Optional["Engine"]:
        if not payload.refiner_checkpoint or payload.refiner_switch_at >= 1.0:
            return None
        if self.engine_provider is None:
            return None
        return self.engine_provider(payload.refiner_checkpoint)

    def _split_denoise(self, payload, x, keys, conds, pooleds, width, height,
                       job, controls, refiner, ref_cond, steps, start_step,
                       inpaint_cond=None, ragged=None, drawn=None,
                       account=None):
        """Denoise [start_step, steps) with an optional refiner handoff: the
        base model runs up to the switch point, then the refiner — its own
        text conditioning and aesthetic micro-conditioning — finishes on the
        same latents and sigma ladder (webui refiner_switch_at semantics;
        BASELINE config #2's base+refiner pass). Applies to the hires second
        pass as well, like webui. The sampler's multistep history resets at
        the switch, like a fresh sampling run. An interrupt during the base
        phase skips the refiner phase."""
        if refiner is None or ref_cond is None:
            return self._denoise_range(payload, x, keys, conds, pooleds,
                                       width, height, start_step, steps, job,
                                       None, None, controls,
                                       inpaint_cond=inpaint_cond,
                                       ragged=ragged, drawn=drawn,
                                       account=account)
        # refiner handoff is ragged-ineligible, and nothing is drawn ahead
        # of one
        assert ragged is None and drawn is None
        switch = int(steps * payload.refiner_switch_at)
        switch = max(start_step, min(steps - 1, switch))
        latents = x
        if switch > start_step:
            latents = self._denoise_range(
                payload, latents, keys, conds, pooleds, width, height,
                start_step, steps, job, None, None, controls,
                end_step=switch, inpaint_cond=inpaint_cond, account=account)
            account = None
        if self.state.flag.interrupted:
            return latents
        ref_conds, ref_pooleds = ref_cond
        return refiner._denoise_range(
            payload, latents, keys, ref_conds, ref_pooleds, width, height,
            switch, steps, job + "+refiner", None, None, account=account)

    def _hires_pass(self, payload, latents, image_keys, conds, pooleds, job,
                    refiner=None, ref_cond=None):
        """Latent-space hires fix: bilinear latent upscale, re-noise to the
        strength point, second denoise pass at the target resolution
        (webui's "Latent" upscaler; reference ETA semantics at
        worker.py:205-228). No VAE/PNG roundtrip between passes."""
        if payload.hr_resize_x and payload.hr_resize_y:
            tw, th = payload.hr_resize_x, payload.hr_resize_y
        else:
            tw = int(payload.width * payload.hr_scale)
            th = int(payload.height * payload.hr_scale)
        f = self.family.vae_scale_factor
        tw, th = (tw // f) * f, (th // f) * f
        steps2 = payload.hr_second_pass_steps or payload.steps
        spec = kd.resolve_sampler(payload.sampler_name)
        sigmas2 = kd.build_sigmas(spec, self.schedule, steps2)
        t_enc = int(min(payload.denoising_strength, 0.999) * steps2)
        start2 = steps2 - t_enc

        n, _, _, C = latents.shape
        up = None
        name = payload.hr_upscaler or "Latent"
        if "latent" not in name.lower() and self.upscaler_provider:
            upscale = self.upscaler_provider(name)
            if upscale is not None:
                # image-space (ESRGAN-family) hires: decode -> model
                # upscale to target -> re-encode (webui's non-latent path);
                # rows are DISTINCT images: one image a VAE dispatch at
                # each stage, as in _queue_decoded
                decode = self._decode_fn(payload.width, payload.height, 1)
                encode = self._encode_image_fn(tw, th, 1)
                with trace.STATS.timer("hires_upscale"):
                    ups = [encode(self.params["vae"], upscale(
                        decode(self.params["vae"], latents[s:s + 1]),
                        tw, th)) for s in range(n)]
                    up = ups[0] if n == 1 else jnp.concatenate(ups)
        if up is None:
            up = jax.image.resize(latents, (n, th // f, tw // f, C),
                                  _latent_resize_method(payload.hr_upscaler))
        # Fresh per-image noise for the second pass, disjoint from both the
        # init-noise stream and the sampler's ancestral stream.
        def hr_noise(k):
            return jax.random.normal(
                jax.random.fold_in(k, 2_000_000), up.shape[1:], jnp.float32)

        noise = jax.vmap(hr_noise)(image_keys)
        x = up + noise * sigmas2[start2]

        hires = payload.model_copy()
        hires.steps = steps2
        # ControlNet conditions the hires pass too (webui behavior); hints
        # re-prepared at the target resolution; the refiner switch applies
        # within the hires pass as well
        controls2 = self._prepare_controls(payload, tw, th)
        inp2 = (self._blank_inpaint_cond(n, tw, th)
                if self.family.inpaint else None)
        latents2 = self._split_denoise(
            hires, x, image_keys, conds, pooleds, tw, th, job + "+hr",
            controls2, refiner, ref_cond, steps2, start2, inpaint_cond=inp2)
        return latents2, tw, th

    def _run_img2img(self, payload, start, count, job) -> GenerationResult:
        width, height = payload.width, payload.height
        h, w = self._latent_hw(width, height)
        spec = kd.resolve_sampler(payload.sampler_name)
        sigmas = kd.build_sigmas(spec, self.schedule, payload.steps)
        # webui: t_enc = int(min(strength, 0.999) * steps)
        t_enc = int(min(payload.denoising_strength, 0.999) * payload.steps)
        start_step = payload.steps - t_enc

        with obs_spans.span("prepare"):
            with obs_spans.span("init_image",
                                bytes=len(payload.init_images[0])):
                with obs_spans.span("png_decode"):
                    init = b64png_to_array(payload.init_images[0])
                init = _resize_image(init.astype(np.float32) / 255.0,
                                     width, height)
                with obs_spans.span("upload"):
                    init_dev = jnp.asarray(init)[None]
            controls = self._prepare_controls(payload, width, height)
            # inpainting never uses the refiner (mask pinning is tied to
            # the base chunk loop) — don't load a refiner checkpoint for it
            refiner = None if payload.mask is not None \
                else self._refiner_engine(payload)
            conds = pooleds = ref_cond = None
            if not payload.all_prompts:
                conds, pooleds = self.encode_prompts(payload)
                ref_cond = refiner.encode_prompts(payload) \
                    if refiner else None
            # the init image is one frame shared by every row: encode it
            # ONCE at batch 1 (flat VAE scratch at SDXL sizes) and repeat
            # per group
            with obs_spans.span("vae_encode"):
                work = obs_spans.device_work("vae_encode")
                init_lat1 = self._encode_image_fn(width, height, 1)(
                    self.params["vae"], init_dev)
                work.queued(init_lat1)      # repeated a group, not donated

        mask_lat = None
        mask_pixels = None
        if payload.mask is not None:
            m = b64png_to_array(payload.mask).astype(np.float32) / 255.0
            m = _resize_image(m, width, height)[..., :1]
            mask_pixels = m  # pre-blur: hybrid conditioning wants it sharp
            if payload.mask_blur > 0:
                # soften the seam (webui gaussian-blurs the pixel mask by
                # mask_blur); the soft values survive into the latent mask
                # so per-step pinning blends smoothly at the boundary
                m = _box_blur(m, payload.mask_blur)
            mask_lat = jnp.asarray(
                np.asarray(jax.image.resize(m, (h, w, 1), "bilinear")),
                jnp.float32)[None]
            mask_lat = jnp.clip(mask_lat * 1.02, 0.0, 1.0)  # keep core at 1

        out = GenerationResult(parameters=payload.model_dump())
        group = max(1, payload.group_size or payload.batch_size)
        pos, remaining = start, count
        pending = []
        while remaining > 0 and not self.state.flag.interrupted:
            n = min(group, remaining)
            with obs_spans.span("prepare", group=pos, images=n):
                init_lat = jnp.repeat(init_lat1, n, axis=0)
                keys = self._image_keys(payload, pos, n)
                init_lat = self._apply_inpaint_fill(
                    payload, init_lat, mask_lat, keys)
                if payload.all_prompts:
                    conds, pooleds, ref_cond = self._group_conds(
                        payload, pos, n, refiner)
                inp = None
                if self.family.inpaint:
                    inp = (self._masked_inpaint_cond(n, width, height, init,
                                                     mask_pixels)
                           if mask_pixels is not None
                           else self._blank_inpaint_cond(n, width, height))
                with obs_spans.span("noise"):
                    noise = rng.batch_noise(
                        payload.seed, payload.subseed,
                        payload.subseed_strength, pos, n,
                        init_lat.shape[1:],
                        seed_resize=self._seed_resize_latent(payload),
                        pin_index=payload.same_seed)
                x = self._place_batch(
                    init_lat
                    + noise.astype(jnp.float32) * sigmas[start_step])
            if mask_lat is None:
                # plain img2img honors the refiner switch too (webui does);
                # inpainting stays base-only — the per-step mask pinning is
                # tied to the base chunk loop
                latents = self._split_denoise(
                    payload, x, keys, conds, pooleds, width, height, job,
                    controls, refiner, ref_cond, payload.steps, start_step,
                    inpaint_cond=inp)
            else:
                latents = self._denoise_range(
                    payload, x, keys, conds, pooleds, width, height,
                    start_step, payload.steps, job, mask_lat, init_lat,
                    controls, inpaint_cond=inp)
            pending.extend(self._queue_decoded(latents, pos, n, width,
                                               height))
            if len(pending) > 1:  # depth-1 decode pipeline (see txt2img)
                self._flush_decoded(out, payload, pending[:-1])
                pending = pending[-1:]
            pos += n
            remaining -= n
        self._flush_decoded(out, payload, pending)
        return out

    def _queue_decoded(self, latents, pos, n, width, height):
        """Dispatch the VAE decode WITHOUT waiting: the returned device
        arrays materialize later, so the decode of group i pipelines with
        the denoise of group i+1 (SURVEY.md §7 hard part #6 overlap).

        Returns a LIST of pending entries, one an image. ONE IMAGE A
        DISPATCH A CHIP, enqueued back to back: ``_flush_decoded`` (and the
        dispatcher's merge stage) walk the entries in order, so image i's
        copy-down and PNG encode run on the host while the device decodes
        image i+1. The f32 decoder costs more an image in a batch than
        alone at every size measured on a v5e (PERF.md section 5, "the
        decode by batch"): four 512x512 images in one dispatch take 7.56 x
        one image's time and 6.9 x its scratch (120.5 ms and 3 770 MB
        against 15.9 and 549), four 256x256 27.3 ms against 17.0 as four
        dispatches, and two dispatches of one cost exactly twice one. One
        executable key a size serves every batch size. Latents split over
        a mesh's ``dp`` axis (``_place_batch``) go as ONE partitioned
        dispatch, every chip decoding its own rows: a row sliced out of
        them would be decoded by every chip.

        ``n`` is how many images to KEEP; latents may carry extra
        pad-and-drop rows, which one chip never decodes."""
        import warnings

        from stable_diffusion_webui_distributed_tpu.serving.metrics import (
            METRICS,
        )

        # snapshot-and-clear the adaptive incompletion latch HERE, at the
        # only point that knows which images a denoise produced — a sticky
        # engine-level flag would mislabel other (complete) batches of the
        # same request once the depth-1 decode pipeline interleaves flushes
        incomplete = getattr(self, "_adaptive_incomplete", False)
        self._adaptive_incomplete = False
        keep = min(n, latents.shape[0])
        # rows a dispatch: all of them where they are split over chips
        split = latents.sharding.shard_shape(latents.shape)[0] < len(latents)
        per = len(latents) if split else 1
        starts = range(0, keep, per)
        # FLOPs-per-image denominator: every kept row is one output image,
        # counted at the single point all decode paths (engine loops, the
        # serving dispatcher) funnel through
        METRICS.record_decoded(rows=keep, dispatches=len(starts))
        decode = self._decode_u8_fn(width, height, per)
        entries = []
        with warnings.catch_warnings():
            # the latent rows are f32 and the output is uint8 pixels, so
            # the declared donation can never alias an output buffer —
            # JAX flags that at first lowering; expected, not actionable
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            for s in starts:
                with trace.STATS.timer("vae_decode_dispatch"):
                    work = obs_spans.device_work("decode_u8")
                    imgs = decode(self.params["vae"], latents[s:s + per])
                    work.queued(imgs)
                # a whole slice of an array is the array: no copy at per 1
                entries += [(imgs[r:r + 1], pos + s + r, width, height,
                             incomplete) for r in range(min(per, keep - s))]
        return entries

    @staticmethod
    def _fetch_decoded(img_dev) -> np.ndarray:
        """A decode dispatch's image on the host, inside the caller's
        ``vae_decode_fetch``: the wait for the decode executable (the device
        busy), then the copy down (the device idle)."""
        with obs_spans.span("decode.wait", rows=int(img_dev.shape[0])), \
                obs_spans.fence(img_dev):
            jax.block_until_ready(img_dev)
        with obs_spans.span("fetch.copy", bytes=int(img_dev.nbytes)):
            return np.asarray(img_dev)[0]

    def _flush_decoded(self, out, payload, pending) -> None:
        for img_dev, i, width, height, incomplete in pending:
            with trace.STATS.timer("vae_decode_fetch"):
                img = self._fetch_decoded(img_dev)
            self._append_image(out, payload, img, i, width, height,
                               incomplete=incomplete)

    def _append_image(self, out, payload, img, i, width, height,
                      incomplete=False):
        """Encode image ``i`` of the request into its gallery."""
        pinned = payload.subseed_strength > 0 or payload.same_seed
        seed_i = payload.seed + (0 if pinned else i)
        sub_i = payload.subseed + (0 if payload.same_seed else i)
        prompt_i = payload.prompt
        if payload.all_prompts and i < len(payload.all_prompts):
            prompt_i = payload.all_prompts[i]
        with obs_spans.span("png_encode") as sp:
            png, strips = encode_b64png(img)
            if sp is not None:
                sp.attrs["bytes"] = len(png) * 3 // 4  # base64 -> PNG
                sp.attrs["strips"] = strips
        out.images.append(png)
        out.seeds.append(int(seed_i))
        out.subseeds.append(int(sub_i))
        out.prompts.append(prompt_i)
        out.negative_prompts.append(payload.negative_prompt)
        text = build_infotext(
            payload, int(seed_i), int(sub_i), self.model_name,
            width, height, prompt_override=prompt_i)
        if incomplete:
            # DPM adaptive hit its attempt backstop before reaching
            # sigma_min — flag the partially-denoised result where
            # webui users read generation provenance
            text += ", DPM adaptive: incomplete"
        out.infotexts.append(text)
        out.worker_labels.append("")


def _box1d(a: np.ndarray, r: int, axis: int) -> np.ndarray:
    """Zero-padded box filter of width 2r+1 along ``axis`` via a cumsum
    sliding window — one vectorized pass instead of a Python call per row."""
    k = 2 * r + 1
    pad = [(0, 0)] * a.ndim
    pad[axis] = (r + 1, r)
    c = np.cumsum(np.pad(a, pad), axis=axis, dtype=np.float32)
    hi = [slice(None)] * a.ndim
    hi[axis] = slice(k, None)
    lo = [slice(None)] * a.ndim
    lo[axis] = slice(0, c.shape[axis] - k)
    return (c[tuple(hi)] - c[tuple(lo)]) / np.float32(k)


def _box_blur(img: np.ndarray, radius: int) -> np.ndarray:
    """Three separable box passes ~ gaussian blur of the given radius."""
    r = max(1, int(radius))
    out = img.astype(np.float32)
    for _ in range(3):
        out = _box1d(out, r, 0)
        out = _box1d(out, r, 1)
    return out


def _latent_resize_method(hr_upscaler: str) -> str:
    """webui latent-upscaler names -> jax.image.resize methods. Non-latent
    (ESRGAN-family) names are handled upstream via the engine's
    upscaler_provider when a matching model file exists (models/esrgan.py);
    reaching here means no file matched — fall back to bilinear latent
    upscaling with a log line (degraded-capability pattern, reference
    worker.py:457-467)."""
    name = (hr_upscaler or "Latent").lower()
    if "latent" in name:
        if "nearest" in name:
            return "nearest"
        if "bicubic" in name:
            return "cubic"
        return "linear"
    from stable_diffusion_webui_distributed_tpu.runtime.logging import (
        get_logger,
    )

    get_logger().warning(
        "hires upscaler '%s' unavailable; using latent bilinear", hr_upscaler)
    return "linear"


def _resize_image(img: np.ndarray, width: int, height: int) -> np.ndarray:
    """Host-side image resize to the requested generation size."""
    if img.shape[0] == height and img.shape[1] == width:
        return img
    import jax.image

    return np.asarray(jax.image.resize(
        jnp.asarray(img), (height, width, img.shape[2]), "bilinear"))

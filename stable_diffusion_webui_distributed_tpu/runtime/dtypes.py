"""Dtype policy: bf16 compute, f32 accumulate where it matters.

TPU MXU natively multiplies bf16 with f32 accumulation; we keep params and
activations in bf16 and pin numerically sensitive pieces (sampler state,
sigmas, group-norm statistics, final VAE output) to f32.
"""

from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Policy:
    param_dtype: jnp.dtype = jnp.dtype(jnp.float32)   # storage dtype of weights
    compute_dtype: jnp.dtype = jnp.dtype(jnp.bfloat16)  # matmul/conv dtype
    sampler_dtype: jnp.dtype = jnp.dtype(jnp.float32)   # latent/sigma math
    # "auto": ops/attention.py chooses per attention site, from platform,
    # shape and dtype, between the tiled Pallas kernel and XLA. "xla" and
    # "flash" force one side (tests, chip_smoke.py).
    attention_impl: str = "auto"
    # rematerialize transformer blocks: trades UNet FLOPs for HBM at large
    # batch/resolution (SDTPU_REMAT=1 flips the default TPU policy).
    use_remat: bool = False
    # Decoder conv dtype override (SDTPU_DECODE_DTYPE=bf16): runs the VAE
    # decoder's convs in bf16 while GroupNorm statistics and the final
    # conv_out stay f32 (models/vae.py). Halves the decode's HBM scratch
    # and the bytes it moves (one image a dispatch:
    # pipeline/engine.py:_queue_decoded). Off by default: banding risk
    # is unvalidated without real weights
    # (README "numerical-parity status"); measure via sweep cell
    # c2-decodebf16 before promoting.
    decode_in_bf16: bool = False
    # Dynamic W8A8 int8 for the UNet transformer linears
    # (SDTPU_UNET_INT8=1; ops/quant.py). The int8 MXU path is the only
    # single-chip lever above the bf16 roofline (PERF.md round-5
    # analysis: 0.96 vs 0.48 img/s/chip ceiling on SDXL b8). Since the
    # serving-precision ladder (pipeline/precision.py) this flag sets
    # only the server's DEFAULT precision — a per-request ``precision``
    # override ("bf16"/"int8"/"int8+conv") always wins, and the engine
    # keeps one module variant per rung over the SAME param tree.
    # Quality is gated by the tier-1 floors (tests/test_quality_int8.py);
    # throughput by bench.py --int8 / sweep cells c2-int8/c4-int8.
    unet_int8: bool = False
    # ...and the same lever for the ResBlock/Down/Up convs
    # (SDTPU_UNET_INT8_CONV=1, the "int8+conv" rung) — configs #1/#3 are
    # conv-dominated, so int8 linears alone barely move them.
    unet_int8_conv: bool = False


def _env_choice(name: str, default: str, choices) -> str:
    from stable_diffusion_webui_distributed_tpu.runtime.config import env_parsed

    def parse(raw: str) -> str:
        value = raw.strip().lower()
        if value not in choices:
            raise ValueError(f"want one of {tuple(choices)}")
        return value

    return env_parsed(name, parse, default, "choice")


def _env_flag(name: str) -> bool:
    from stable_diffusion_webui_distributed_tpu.runtime.config import env_flag

    return env_flag(name, False)


def _default_param_dtype() -> jnp.dtype:
    """Weight storage dtype on TPU (SDTPU_PARAM_DTYPE=bf16|f32).

    bf16 storage halves HBM weight traffic per UNet call — the dominant
    byte stream at inference batch sizes — and halves resident model
    memory (SDXL base+refiner fit on one 16 GB v5e). Numerics stay f32
    where it matters: sigma/sampler math is pinned f32 by
    ``sampler_dtype`` and flax group norms compute statistics in f32.

    Default is bf16: measured on silicon (round-3 sweep, PERF.md) it
    wins config #1 27.2 ipm vs 22.4 ipm for f32 storage (+21%).
    """
    value = _env_choice("SDTPU_PARAM_DTYPE", "bf16",
                        ("bf16", "bfloat16", "f32", "float32", "fp32"))
    if value in ("bf16", "bfloat16"):
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(jnp.float32)


def _default_decode_bf16() -> bool:
    value = _env_choice("SDTPU_DECODE_DTYPE", "f32",
                        ("bf16", "bfloat16", "f32", "float32", "fp32"))
    return value in ("bf16", "bfloat16")


#: Default policy for real TPU runs.
TPU = Policy(param_dtype=_default_param_dtype(),
             use_remat=_env_flag("SDTPU_REMAT"),
             decode_in_bf16=_default_decode_bf16(),
             unet_int8=_env_flag("SDTPU_UNET_INT8"),
             unet_int8_conv=_env_flag("SDTPU_UNET_INT8_CONV"))
#: Full-f32 policy for numerics tests on CPU.
F32 = Policy(compute_dtype=jnp.dtype(jnp.float32))


def _needs_cast(x, dtype):
    return (hasattr(x, "dtype")
            and jnp.issubdtype(x.dtype, jnp.floating)
            and x.dtype != dtype)


@functools.lru_cache(maxsize=None)
def _tree_cast(dtype):
    import jax

    return jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if _needs_cast(x, dtype) else x, t))


def cast_floating(tree, dtype):
    """Cast floating leaves of a pytree to ``dtype`` (params → bf16 etc.).

    Host (numpy) trees — freshly converted checkpoints — are cast leaf by
    leaf ON HOST: no XLA compile, and the device never holds the f32
    source alongside the downcast copy (for SDXL that transient would be
    ~15 GB, an OOM at load on a 16 GB v5e chip).

    Device trees are cast inside a single ``jit`` call: per-leaf
    ``astype`` would compile one tiny convert executable per unique leaf
    shape (hundreds for a UNet), which is minutes of compile time on a
    TPU backend; one jitted tree-cast is one compile, cached per target
    dtype so repeated casts of same-structure trees (e.g. VAE toggles)
    reuse the executable. Leaves already in ``dtype`` pass through
    untouched, so a no-op cast stays free.
    """
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    if not any(_needs_cast(x, dtype) for x in leaves):
        return tree
    if not any(isinstance(x, jax.Array) for x in leaves):
        import numpy as np

        return jax.tree_util.tree_map(
            lambda x: np.asarray(x).astype(dtype)
            if _needs_cast(x, dtype) else x, tree)
    return _tree_cast(jnp.dtype(dtype))(tree)

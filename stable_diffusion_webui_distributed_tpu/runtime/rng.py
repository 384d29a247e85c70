"""Seed discipline.

The reference preserves per-image seed continuity across workers by offsetting
each job's starting seed by the number of images assigned before it
(/root/reference/scripts/distributed.py:297-305: ``seed += prior_images`` when
``subseed_strength == 0``, else ``subseed += prior_images``). We reproduce the
same *user-visible contract* — image ``i`` of a batch depends only on
``(seed + i)`` — with JAX PRNG keys: image ``i``'s initial latent noise is
``normal(key(seed + i))``, so any contiguous sub-batch [lo, hi) of a request
can be generated on any shard/slice and produce bitwise-identical latents.

Subseed (variation seed) support mirrors webui semantics exactly
(distributed.py:297-305): the *main* seed advances with the image index only
when ``subseed_strength == 0``; with strength > 0 the base seed is fixed for
every image of the request and only the subseed advances, so a variation
batch explores the neighbourhood of ONE base noise. The init noise is
``slerp(strength, noise(seed [+ i if strength==0]), noise(subseed + i))``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def key_for_image(seed, image_index) -> jax.Array:
    """PRNG key for image ``image_index`` of a request seeded with ``seed``.

    Accepts traced values: seeds stay *data*, not compile-time constants, so
    one compiled pipeline serves every seed.
    """
    seed = jnp.asarray(seed, jnp.uint32)
    idx = jnp.asarray(image_index, jnp.uint32)
    return _key_from_seed(seed + idx)


def _key_from_seed(seed: jax.Array) -> jax.Array:
    # jax.random.PRNGKey is not traceable pre-0.4; key_from_seed via fold_in is.
    base = jax.random.key(0)
    return jax.random.fold_in(base, seed.astype(jnp.uint32))


def noise_for_image(
    seed,
    subseed,
    subseed_strength,
    image_index,
    shape: Sequence[int],
    dtype=jnp.float32,
) -> jax.Array:
    """Initial latent noise for one image, with variation-seed blending.

    With ``subseed_strength == 0`` this is exactly ``N(key(seed+i))``. With
    strength > 0 the base seed does NOT advance with the image index — only
    the subseed does (reference: distributed.py:297-305, mirroring webui's
    ``all_seeds``/``all_subseeds`` arithmetic) — so every image of a
    variation batch perturbs the same base noise.
    """
    strength = jnp.asarray(subseed_strength, dtype)
    idx = jnp.asarray(image_index, jnp.uint32)
    main_idx = jnp.where(strength > 0, jnp.uint32(0), idx)
    main = jax.random.normal(key_for_image(seed, main_idx), shape, dtype)

    def blended(_):
        sub = jax.random.normal(key_for_image(subseed, idx), shape, dtype)
        return slerp(strength, main, sub)

    return jax.lax.cond(strength > 0, blended, lambda _: main, operand=None)


def batch_noise(
    seed,
    subseed,
    subseed_strength,
    start_index,
    batch_size: int,
    shape: Sequence[int],
    dtype=jnp.float32,
    seed_resize: Optional[Tuple[int, int]] = None,
    pin_index: bool = False,
) -> jax.Array:
    """Noise for a contiguous sub-batch starting at global image ``start_index``.

    ``pin_index=True`` gives EVERY image index-0 noise (same-seed batches:
    webui's prompt matrix pins all_seeds so prompts compare at one seed).

    This is the sharding-safe primitive: a job assigned images
    [start, start+batch) calls this and gets latents identical to a
    single-host run — seed-exact gallery merging for free.

    ``seed_resize=(from_h, from_w)`` (latent units) reproduces webui's
    seed-resize: noise (including any variation blend) is drawn at the
    "from" resolution and pasted centered into the target latent — the
    uncovered border stays zero, exactly webui's quirk — so one seed keeps
    its composition across aspect-ratio changes.

    Jitted (seeds/strength/start are data; batch/shape/resize/pin key the
    executable): the eager vmap-of-cond form cost ~1.9 s of host tracing
    per request (CPU run) and dispatched each tiny op to the device
    separately. One compiled call per (batch, shape) bucket instead.
    """
    # cast seeds on the host: webui seeds span the full uint32 range, which
    # overflows jit's default int32 argument conversion
    return _batch_noise_jit(
        jnp.asarray(seed, jnp.uint32), jnp.asarray(subseed, jnp.uint32),
        subseed_strength, jnp.asarray(start_index, jnp.uint32),
        int(batch_size), tuple(shape), jnp.dtype(dtype),
        tuple(seed_resize) if seed_resize is not None else None,
        bool(pin_index))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _batch_noise_jit(seed, subseed, subseed_strength, start_index,
                     batch_size, shape, dtype, seed_resize, pin_index):
    idx = jnp.arange(batch_size, dtype=jnp.uint32) + start_index
    if pin_index:
        idx = jnp.zeros_like(idx)
    if seed_resize is None:
        return jax.vmap(
            lambda i: noise_for_image(seed, subseed, subseed_strength, i, shape, dtype)
        )(idx)

    fh, fw = seed_resize
    from_shape = (fh, fw) + tuple(shape[2:])
    noise = jax.vmap(
        lambda i: noise_for_image(seed, subseed, subseed_strength, i,
                                  from_shape, dtype)
    )(idx)
    return _paste_centered(noise, (batch_size,) + tuple(shape), dtype)


def batch_keys(seed, start_index, batch_size: int,
               pin_index: bool = False) -> jax.Array:
    """Per-image PRNG keys for images [start, start+batch) — the jitted
    companion of :func:`batch_noise` for sampler-noise keys (same eager-
    dispatch concern; ``pin_index`` fixes every key to image 0 for
    variation/same-seed batches)."""
    return _batch_keys_jit(jnp.asarray(seed, jnp.uint32),
                           jnp.asarray(start_index, jnp.uint32),
                           int(batch_size), bool(pin_index))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _batch_keys_jit(seed, start_index, batch_size, pin_index):
    idx = jnp.arange(batch_size, dtype=jnp.uint32) + start_index
    if pin_index:
        idx = jnp.zeros_like(idx)
    return jax.vmap(lambda i: key_for_image(seed, i))(idx)


def folded_keys(seed, image_indices, domain) -> jax.Array:
    """``fold_in(key_for_image(seed, i), domain)`` for the image index or
    the ``(batch,)`` indices given, bit for bit what the eager calls give:
    the keys of a stage that must not share the noise's stream
    (pipeline/expand.py). Meant to be jitted whole, like
    :func:`batch_keys`: one dispatch a request, not a trace of a bare
    ``vmap`` every time."""
    def one(i):
        return jax.random.fold_in(key_for_image(seed, i), domain)

    return one(image_indices) if jnp.ndim(image_indices) == 0 \
        else jax.vmap(one)(image_indices)


def _paste_centered(noise: jax.Array, target_shape: Sequence[int],
                    dtype) -> jax.Array:
    """Center-paste (B, fh, fw, C) noise into zeros of (B, H, W, C) —
    cropping when the source is larger (webui create_random_tensors)."""
    _, fh, fw, _ = noise.shape
    _, H, W, _ = target_shape
    dy, dx = (H - fh) // 2, (W - fw) // 2
    ty, sy = max(0, dy), max(0, -dy)
    tx, sx = max(0, dx), max(0, -dx)
    h, w = min(fh, H), min(fw, W)
    out = jnp.zeros(target_shape, dtype)
    return out.at[:, ty:ty + h, tx:tx + w].set(
        noise[:, sy:sy + h, sx:sx + w])


def slerp(t: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """Spherical linear interpolation between noise tensors (webui semantics)."""
    a_flat = a.reshape(-1)
    b_flat = b.reshape(-1)
    a_norm = a_flat / (jnp.linalg.norm(a_flat) + 1e-12)
    b_norm = b_flat / (jnp.linalg.norm(b_flat) + 1e-12)
    dot = jnp.clip(jnp.dot(a_norm, b_norm), -1.0, 1.0)
    theta = jnp.arccos(dot)
    sin_theta = jnp.sin(theta)

    def lerp(_):
        return (1.0 - t) * a + t * b

    def true_slerp(_):
        wa = jnp.sin((1.0 - t) * theta) / sin_theta
        wb = jnp.sin(t * theta) / sin_theta
        return wa * a + wb * b

    return jax.lax.cond(jnp.abs(sin_theta) < 1e-6, lerp, true_slerp, operand=None)

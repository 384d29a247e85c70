"""The plain reference of the prompt expander's language model
(``family.expander``, a Laguna-S-2.1 share): one full forward pass over all
positions in float32 at the highest matmul precision, in plain
``jax.numpy``: no cache, no kernels, no batching, no chunks. It reads the
same parameter tree the program's ``models/lm.py`` holds and the same
``LMConfig``, and shares no code with it.

The layer, with ``n = RMSNorm(x)``:

    h = x + Attn_l(n);  y = h + MLP_l(RMSNorm(h))

``Attn_l``: ``q = W_q n`` in (T, H_l, D) with H_l from
``num_heads_per_layer``; ``k, v`` in (T, KV, D); RoPE on q and k (full
layers: YaRN-scaled frequencies on the first ``partial_rotary_factor`` of
the dims, cos and sin multiplied by ``attention_factor``; sliding layers:
plain RoPE on all dims); query head j attends KV head ``j * KV // H_l``;
scores scaled by D**-0.5, causal, and in sliding layers only keys with
``0 <= i - j < sliding_window``; ``o_j <- sigmoid(W_g n)_j * o_j``; output
``W_o concat(o)``. ``MLP_l`` of a dense layer is
``W_d(silu(W_g' n) * W_u n)``; of an expert layer: ``p = softmax_f32(W_r
n)`` over all experts, ``S`` the ``k`` largest, ``w_e = scale * p_e /
sum_S p``, ``sum_{e in S and held} w_e E_e(n) + E_shared(n)``. Final
RMSNorm, then the head over the held slice of the vocabulary.

Five things the published config does not say, set by the key names'
convention (the configuration's ``assumed``): the activation is SiLU; router
scores are a float32 softmax over all experts, then top-k; the shared
expert is added ungated; the per-head gate is ``sigmoid(W_g n)``, one
scalar a query head from the normed layer input, applied before
``o_proj``; there is no q/k norm.

Held experts are upcast to float32 one at a time (a loop over the held
experts, each applied to every token and weighted by what the router gave
it, zero where it was not chosen), so the reference fits beside the bf16
weights.

    python3 benchmarks/reference/laguna_ref.py --config sd15_laguna_expand

prints the diagnostic readings ``reference/<config>.json`` keeps beside the
tolerance: the share of (token, expert layer) pairs whose chosen experts
differ between program and reference, and the program against the reference
held to the program's choices (routing flips on near-ties apart from
arithmetic error).
"""

from __future__ import annotations

import math

COMPONENT = "expander"
CONTROL = "the program's dynamic int8 linears (quant_linears) on attention " \
          "projections, dense MLP, shared expert and head"


def split(size: int) -> tuple[int, int, int]:
    """(prefix, user chunk, decoded) positions of ``size``: at 960 the
    timed path's 512 + 64 + 384."""
    decoded = size * 2 // 5
    prefill = size - decoded
    prefix = prefill * 8 // 9
    return prefix, prefill - prefix, decoded


def inputs(family, seed: int, size: int):
    """``size`` seeded ids from the held slice of the vocabulary."""
    import jax

    first, count = family.expander.vocab
    return (jax.random.randint(jax.random.key(seed + 7), (size,), first,
                               first + count),)


def program(family, policy, control: bool = False, with_routing=False):
    """What the timed path runs at the timed sizes: the prefix's prefill,
    the user chunk's prefill against it, then every further position
    decoded through the cache one token a step, teacher-forced on the
    seeded ids. Logits at every position, float32."""
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.models import lm

    cfg = family.expander
    module = lm.DecoderLM(cfg, dtype=policy.compute_dtype,
                          quant_linears=control)

    def run(params, ids):
        prefix, user, decoded = split(ids.shape[0])
        cache = lm.empty_cache(cfg, ids.shape[0], policy.compute_dtype)
        apply = lambda t, start, c: module.apply(   # noqa: E731
            {"params": params}, t, jnp.int32(start), jnp.int32(t.shape[0]),
            c)
        l0, cache, r0 = apply(ids[:prefix], 0, cache)
        l1, cache, r1 = apply(ids[prefix:prefix + user], prefix, cache)

        def step(carry, token):
            cache, position = carry
            logits, cache, routed = module.apply(
                {"params": params}, token[None], position, jnp.int32(1),
                cache)
            return (cache, position + 1), (logits[0], routed[0][:, 0])

        _, (l2, r2) = jax.lax.scan(
            step, (cache, jnp.int32(prefix + user)), ids[prefix + user:])
        logits = jnp.concatenate([l0, l1, l2])
        if not with_routing:
            return logits
        return logits, jnp.concatenate(
            [r0[0], r1[0], jnp.moveaxis(r2, 0, 1)], axis=1)

    return run


# -- the reference -----------------------------------------------------------

def _inv_freq(rope, head_dim: int):
    import numpy as np

    dim = int(head_dim * rope.partial_rotary_factor)
    base = np.float64(rope.theta)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation = 1.0 / pos_freqs
    if not rope.factor:
        return extrapolation
    interpolation = 1.0 / (rope.factor * pos_freqs)

    def correction_dim(rotations):
        return (dim * math.log(rope.original_max_position
                               / (rotations * 2 * math.pi))
                ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    keep = 1 - ramp
    return interpolation * (1 - keep) + extrapolation * keep


def _rope(x, rope, head_dim: int):
    """HF's ``apply_rotary_pos_emb`` on ``(T, H, D)``: cos and sin are
    ``cat(freqs, freqs)`` over the rotary dims, ``rotate_half`` swaps the
    halves with a sign."""
    import jax.numpy as jnp

    inv = jnp.asarray(_inv_freq(rope, head_dim), jnp.float32)
    freqs = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos = (jnp.cos(emb) * rope.attention_factor)[:, None, :]
    sin = (jnp.sin(emb) * rope.attention_factor)[:, None, :]
    rot = emb.shape[-1]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    rotated = jnp.concatenate([-x_rot[..., half:], x_rot[..., :half]], -1)
    return jnp.concatenate([x_rot * cos + rotated * sin, x_pass], axis=-1)


def _rms(x, scale, eps):
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def _w(leaf):
    import jax.numpy as jnp

    return leaf.astype(jnp.float32)


def _swiglu(n, p):
    import jax

    gate = n @ _w(p["gate_proj"]["kernel"])
    up = n @ _w(p["up_proj"]["kernel"])
    return (jax.nn.silu(gate) * up) @ _w(p["down_proj"]["kernel"])


def _attention(cfg, layer: int, n, p):
    import jax
    import jax.numpy as jnp

    tokens = n.shape[0]
    heads = cfg.num_heads_per_layer[layer]
    kv, dim = cfg.num_kv_heads, cfg.head_dim
    sliding = cfg.layer_types[layer] == "sliding"
    rope = cfg.rope_sliding if sliding else cfg.rope_full
    q = _rope((n @ _w(p["q_proj"]["kernel"])).reshape(tokens, heads, dim),
              rope, dim)
    k = _rope((n @ _w(p["k_proj"]["kernel"])).reshape(tokens, kv, dim),
              rope, dim)
    v = (n @ _w(p["v_proj"]["kernel"])).reshape(tokens, kv, dim)
    # query head j attends KV head j * KV // H
    of = jnp.arange(heads) * kv // heads
    scores = jnp.einsum("ihd,jhd->hij", q, k[:, of]) * dim ** -0.5
    i = jnp.arange(tokens)[:, None]
    j = jnp.arange(tokens)[None, :]
    seen = j <= i
    if sliding:
        seen &= i - j < cfg.sliding_window
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hij,jhd->ihd", probs, v[:, of])
    gate = jax.nn.sigmoid(n @ _w(p["g_proj"]["kernel"]))      # (T, H)
    out = out * gate[:, :, None]
    return out.reshape(tokens, heads * dim) @ _w(p["o_proj"]["kernel"])


def route(cfg, n, p, forced=None):
    """(chosen experts (T, k), their weights (T, k)): a float32 softmax
    over every expert, the k largest, renormalised and scaled. ``forced``
    gives the experts instead (the diagnostic reading); their weights are
    still this side's own scores."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.softmax(n @ _w(p["router"]), axis=-1)
    if forced is None:
        top, chosen = jax.lax.top_k(scores, cfg.num_experts_per_tok)
    else:
        chosen = forced
        top = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return chosen, top * cfg.routed_scaling_factor


def routed_part(n, chosen, weights, experts, first: int):
    """``sum over the chosen experts held here of w_e E_e(n)``: a loop over
    the held experts, each upcast alone and applied to every token."""
    import jax
    import jax.numpy as jnp

    held = experts["w_gate"].shape[0]

    def one(e, acc):
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        gate = n @ _w(experts["w_gate"][e])
        up = n @ _w(experts["w_up"][e])
        out = (jax.nn.silu(gate) * up) @ _w(experts["w_down"][e])
        return acc + w_e[:, None] * out

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(n))


def _moe(cfg, n, p, forced=None):
    chosen, weights = route(cfg, n, p, forced)
    routed = routed_part(n, chosen, weights, p["experts"], cfg.experts[0])
    return routed + _swiglu(n, p["shared_expert"]), chosen


def layer_forward(cfg, layer: int, x, p, forced=None):
    """One decoder layer: (y, experts chosen or None)."""
    h = x + _attention(cfg, layer,
                       _rms(x, p["input_norm"]["scale"], cfg.rms_norm_eps),
                       p["attn"])
    n = _rms(h, p["post_attention_norm"]["scale"], cfg.rms_norm_eps)
    if layer in cfg.dense_layers:
        return h + _swiglu(n, p["mlp"]), None
    out, chosen = _moe(cfg, n, p["mlp"], forced)
    return h + out, chosen


def forward(family, params, ids, forced=None, with_routing=False):
    """Logits ``(T, held vocabulary)`` float32 at every position.
    ``forced`` ``(expert layers, T, k)`` holds the routing to the experts
    given."""
    import jax
    import jax.numpy as jnp

    cfg = family.expander
    with jax.default_matmul_precision("highest"):
        first, count = cfg.vocab
        table = params["embed_tokens"]["embedding"]
        here = (ids >= first) & (ids < first + count)
        x = jnp.where(here[:, None], table[jnp.clip(ids - first, 0, count - 1)]
                      .astype(jnp.float32), 0.0)
        routing = []
        for layer in range(cfg.num_layers):
            x, chosen = layer_forward(
                cfg, layer, x, params[f"layers_{layer}"],
                None if forced is None or layer in cfg.dense_layers
                else forced[len(routing)])
            if chosen is not None:
                routing.append(chosen)
        n = _rms(x, params["norm"]["scale"], cfg.rms_norm_eps)
        logits = n @ _w(params["lm_head"]["kernel"])
    if with_routing:
        return logits, jnp.stack(routing)
    return logits


def diagnose(bench, config: dict, seed=None) -> dict:
    """The readings beside the tolerance (see the module's text), at the
    configuration's ``weight_seed`` or another: weights and ids both come
    from it."""
    import sys

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, bench.root)
    from benchmarks.harness import files, weights
    from benchmarks.verify_reference import relative_rms

    family = files.resolve_family(config)
    policy = files.resolve_policy(config)
    components = bench.components(config)
    module, args = components.component_inits(family)[COMPONENT]
    seed = int(config["weight_seed"]) if seed is None else int(seed)
    params = weights.fill(weights.param_shapes(module, args),
                          policy.param_dtype, seed,
                          getattr(components, "leaf_rule", None))
    (ids,) = inputs(family, seed, int(config.get("reference_latent", 960)))
    got, chose = jax.jit(program(family, policy, with_routing=True))(
        params, ids)
    want, own = jax.jit(
        lambda p, i: forward(family, p, i, with_routing=True))(params, ids)
    held = jax.jit(lambda p, i, f: forward(family, p, i, forced=f))(
        params, ids, chose)
    control = jax.jit(program(family, policy, control=True))(params, ids)
    differ = jnp.any(jnp.sort(chose, -1) != jnp.sort(own, -1), axis=-1)
    return {
        "positions": int(ids.shape[0]), "seed": seed,
        "control_vs_reference_relative_rms": relative_rms(control, want),
        "program_vs_reference_relative_rms": relative_rms(got, want),
        "routing_pairs_that_differ_share": float(jnp.mean(differ)),
        "program_vs_reference_held_to_its_routing_relative_rms":
            relative_rms(got, held),
        "token_agreement_argmax_share": float(jnp.mean(
            jnp.argmax(got, -1) == jnp.argmax(want, -1))),
    }


if __name__ == "__main__":
    import argparse
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from benchmarks.harness import device, files

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=None,
                    help="weights and ids (default: the file's weight_seed)")
    args = ap.parse_args()
    bench = files.Bench(root)
    out = diagnose(bench, bench.config(args.config), args.seed)
    out["device"] = device.record()
    print(json.dumps(out), flush=True)

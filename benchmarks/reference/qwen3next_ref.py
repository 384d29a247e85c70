"""The plain reference of the prompt expander's language model when it is a
Qwen3-Next share (``family.expander``: gated-delta-rule linear-attention
layers beside gated full-attention layers, every MLP a router over experts
with a gated shared expert): one forward pass over all positions in float32
at the highest matmul precision, in plain ``jax.numpy``: no cache, no
kernels, no batching, no chunks, **the recurrence token by token** (never
the chunk-wise form). It reads the same parameter tree the program's
``models/lm.py`` holds and the same ``LMConfig``, and shares no code with
it.

With ``d`` the hidden size, every norm but the gated one ``x_hat * (1 +
w)``, ``x_hat = x / sqrt(mean(x^2) + eps)``:

    h = x + mixer_l(norm(x));  y = h + moe(norm(h))

*Linear mixer* (``layer_types[l] == "linear"``): ``[q | k | v | z] =
W_qkvz n``, ``[b | a] = W_ba n``; ``[q | k | v]`` passes a causal
depth-wise convolution of ``linear_conv_kernel`` taps without bias (tap
``j`` of row ``t`` reads row ``t - taps + 1 + j``, zero before position 0),
then SiLU; ``q`` and ``k`` are L2-normalised per head (``x * rsqrt(sum x^2
+ 1e-6)``), each key head serves ``value heads / key heads`` consecutive
value heads, ``q`` is scaled by ``key width ** -0.5``. Per value head:
``beta_t = sigmoid(b_t)``, ``g_t = -exp(A_log) * softplus(a_t +
dt_bias)``; ``S`` of shape (key width, value width) is zero at position 0;
``S <- exp(g_t) S``; ``u_t = beta_t (v_t - S^T k_t)``; ``S <- S + k_t
u_t^T``; ``o_t = S^T q_t``. Output ``rmsnorm(o_t) * w_norm * silu(z_t)`` per
head (a plain weight), then ``W_out``.

*Full attention*: ``q_proj`` gives per head a query and a gate of
``head_dim`` each; ``q`` and ``k`` pass a per-head norm; rotary
(``rotate_half`` convention) on the first ``partial_rotary_factor`` of the
dims; query head ``j`` attends KV head ``j * KV // H``; causal softmax at
scale ``head_dim ** -0.5``; the output is multiplied element-wise by
``sigmoid(gate)`` before ``o_proj``. A ``"sliding"`` layer (the tiny test
preset has one) is the same with only the keys ``0 <= i - j < window``.

*Expert layer*: ``p = softmax_f32(W_r n)`` over all experts, ``S`` the ``k``
largest, ``w_e = p_e / sum_S p`` (times ``routed_scaling_factor``, 1 here),
``sum_{e in S and held} w_e E_e(n) + sigmoid(w_s^T n) E_shared(n)``, every
expert a SwiGLU with SiLU. Final norm, then the head over the held slice of
the vocabulary.

Departures from the published model, all in the configuration's
``assumed``: the multi-token-prediction module is left out (the catalog's
config does not describe it); the columns of the fused ``W_qkvz`` and
``W_ba`` lie ``[q | k | v | z]`` and ``[b | a]`` (the published checkpoint
interleaves them by key head; with seeded weights every fixed order is the
same model); the state is float32.

Held experts are upcast to float32 one at a time (a loop over the held
experts, each applied to every token and weighted by what the router gave
it, zero where it was not chosen), so the reference fits beside the bf16
weights.

    python3 benchmarks/reference/qwen3next_ref.py --config sd15_qwen3next_expand

prints the diagnostic readings ``reference/<config>.json`` keeps beside the
tolerance: the share of (token, expert layer) pairs whose chosen experts
differ between program and reference, the program against the reference
held to the program's choices (routing flips on near-ties apart from
arithmetic error), and the same two readings with the recurrent state kept
in bfloat16 between tokens. The held reading has a limit of its own in that
file (``tolerance_held_to_routing_relative_rms``): the float32 state must
meet it and the bfloat16 state must miss it, or the exit code is 1.
"""

from __future__ import annotations

COMPONENT = "expander"
CONTROL = "the program's dynamic int8 linears (quant_linears) on the " \
          "linear mixers' and attention's projections, shared experts and " \
          "their gates, and the head"


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


def program(family, policy, control: bool = False, with_routing=False,
            state_dtype=None):
    """What the timed path runs at the timed sizes: the prefix's prefill
    (chunk-wise delta rule), a copy of the cache and state as they stand
    at the prefix's last token (the kept snapshot), the user chunk's
    prefill against that copy, then every further position decoded through
    the cache and the recurrent state one token a step, teacher-forced on
    the seeded ids. Logits at every position, float32. ``state_dtype``
    (a diagnostic reading only) keeps the recurrent state in that dtype
    between tokens instead of float32."""
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.models import lm

    cfg = family.expander
    module = lm.DecoderLM(cfg, dtype=policy.compute_dtype,
                          quant_linears=control)

    def run(params, ids):
        prefix, user, decoded = split(ids.shape[0])
        cache = lm.empty_cache(cfg, ids.shape[0], policy.compute_dtype)
        if state_dtype is not None:
            cache["state"] = [s.astype(state_dtype) for s in cache["state"]]
        apply = lambda t, start, c: module.apply(   # noqa: E731
            {"params": params}, t, jnp.int32(start), jnp.int32(t.shape[0]),
            c)
        l0, snapshot, r0 = apply(ids[:prefix], 0, cache)
        cache = jax.tree_util.tree_map(jnp.copy, snapshot)
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

def _w(leaf):
    import jax.numpy as jnp

    return leaf.astype(jnp.float32)


def _norm(x, p, eps, zero_centred=True):
    """``x_hat * (1 + weight)``, or ``x_hat * scale`` for the plain one."""
    import jax.numpy as jnp

    x_hat = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    if zero_centred:
        return x_hat * (1.0 + _w(p["weight"]))
    return x_hat * _w(p["scale"])


def _swiglu(n, p):
    import jax

    gate = n @ _w(p["gate_proj"]["kernel"])
    up = n @ _w(p["up_proj"]["kernel"])
    return (jax.nn.silu(gate) * up) @ _w(p["down_proj"]["kernel"])


def _rope(x, rope, head_dim: int):
    """HF's ``apply_rotary_pos_emb`` on ``(T, H, D)``: plain frequencies
    over the first ``partial_rotary_factor`` of the dims, cos and sin
    ``cat(freqs, freqs)``, ``rotate_half`` swaps the halves with a sign."""
    import jax.numpy as jnp
    import numpy as np

    rot = int(head_dim * rope.partial_rotary_factor)
    inv = 1.0 / np.float64(rope.theta) ** (
        np.arange(0, rot, 2, dtype=np.float64) / rot)
    freqs = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[:, None, :], jnp.sin(emb)[:, None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    rotated = jnp.concatenate([-x_rot[..., half:], x_rot[..., :half]], -1)
    return jnp.concatenate([x_rot * cos + rotated * sin, x_pass], axis=-1)


def _attention(cfg, layer: int, n, p):
    import jax
    import jax.numpy as jnp

    tokens = n.shape[0]
    heads = cfg.num_heads_per_layer[layer]
    kv, dim = cfg.num_kv_heads, cfg.head_dim
    sliding = cfg.layer_types[layer] == "sliding"
    rope = cfg.rope_sliding if sliding else cfg.rope_full
    both = (n @ _w(p["q_proj"]["kernel"])).reshape(tokens, heads, 2 * dim)
    q, gate = both[..., :dim], both[..., dim:]
    k = (n @ _w(p["k_proj"]["kernel"])).reshape(tokens, kv, dim)
    v = (n @ _w(p["v_proj"]["kernel"])).reshape(tokens, kv, dim)
    q = _rope(_norm(q, p["q_norm"], cfg.rms_norm_eps), rope, dim)
    k = _rope(_norm(k, p["k_norm"], cfg.rms_norm_eps), rope, dim)
    of = jnp.arange(heads) * kv // heads
    scores = jnp.einsum("ihd,jhd->hij", q, k[:, of]) * dim ** -0.5
    i = jnp.arange(tokens)[:, None]
    j = jnp.arange(tokens)[None, :]
    seen = j <= i
    if sliding:
        seen &= i - j < cfg.sliding_window
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hij,jhd->ihd", probs, v[:, of]) * jax.nn.sigmoid(gate)
    return out.reshape(tokens, heads * dim) @ _w(p["o_proj"]["kernel"])


def _delta_mixer(cfg, n, p):
    """The linear mixer over all positions, the state updated one token at
    a time from zero."""
    import jax
    import jax.numpy as jnp

    tokens = n.shape[0]
    kh, vh = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    kd, vd = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    taps = cfg.linear_conv_kernel
    mixed = n @ _w(p["qkvz_proj"]["kernel"])
    ba = n @ _w(p["ba_proj"]["kernel"])
    wide = 2 * kh * kd + vh * vd
    qkv, z = mixed[:, :wide], mixed[:, wide:]
    b, a = ba[:, :vh], ba[:, vh:]
    kernel = _w(p["conv_kernel"])                       # (taps, channels)
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, wide), jnp.float32), qkv])
    qkv = jax.nn.silu(sum(kernel[j][None, :] * padded[j:j + tokens]
                          for j in range(taps)))
    q = qkv[:, :kh * kd].reshape(tokens, kh, kd)
    k = qkv[:, kh * kd:2 * kh * kd].reshape(tokens, kh, kd)
    v = qkv[:, 2 * kh * kd:].reshape(tokens, vh, vd)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q), vh // kh, axis=1) * kd ** -0.5
    k = jnp.repeat(l2(k), vh // kh, axis=1)
    beta = jax.nn.sigmoid(b)                            # (T, vh)
    g = -jnp.exp(_w(p["A_log"])) * jax.nn.softplus(a + _w(p["dt_bias"]))

    def token(state, row):
        q_t, k_t, v_t, g_t, beta_t = row
        state = state * jnp.exp(g_t)[:, None, None]
        seen = jnp.einsum("hkv,hk->hv", state, k_t)
        u_t = beta_t[:, None] * (v_t - seen)
        state = state + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, out = jax.lax.scan(token, jnp.zeros((vh, kd, vd), jnp.float32),
                          (q, k, v, g, beta))
    out = _norm(out, p["norm"], cfg.rms_norm_eps, zero_centred=False) \
        * jax.nn.silu(z.reshape(tokens, vh, vd))
    return out.reshape(tokens, vh * vd) @ _w(p["out_proj"]["kernel"])


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


def shared_part(n, p):
    """``sigmoid(w_s^T n) E_shared(n)``: what every chip of a layer
    computes alike."""
    import jax

    return jax.nn.sigmoid(n @ _w(p["shared_expert_gate"]["kernel"])) \
        * _swiglu(n, p["shared_expert"])


def _moe(cfg, n, p, forced=None):
    chosen, weights = route(cfg, n, p, forced)
    routed = routed_part(n, chosen, weights, p["experts"], cfg.experts[0])
    return routed + shared_part(n, p), chosen


def layer_forward(cfg, layer: int, x, p, forced=None):
    """One decoder layer: (y, experts chosen)."""
    n = _norm(x, p["input_norm"], cfg.rms_norm_eps)
    if cfg.layer_types[layer] == "linear":
        h = x + _delta_mixer(cfg, n, p["delta"])
    else:
        h = x + _attention(cfg, layer, n, p["attn"])
    n = _norm(h, p["post_attention_norm"], cfg.rms_norm_eps)
    out, chosen = _moe(cfg, n, p["mlp"], forced)
    return h + out, chosen


def forward(family, params, ids, forced=None, with_routing=False):
    """Logits ``(T, held vocabulary)`` float32 at every position.
    ``forced`` ``(layers, T, k)`` holds the routing to the experts
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
                None if forced is None else forced[layer])
            routing.append(chosen)
        n = _norm(x, params["norm"], cfg.rms_norm_eps)
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
    narrow = jax.jit(program(family, policy, state_dtype=jnp.bfloat16))(
        params, ids)
    differ = jnp.any(jnp.sort(chose, -1) != jnp.sort(own, -1), axis=-1)
    return {
        "positions": int(ids.shape[0]), "seed": seed,
        "control_vs_reference_relative_rms": relative_rms(control, want),
        "program_vs_reference_relative_rms": relative_rms(got, want),
        "routing_pairs_that_differ_share": float(jnp.mean(differ)),
        "program_vs_reference_held_to_its_routing_relative_rms":
            relative_rms(got, held),
        "bf16_state_vs_reference_relative_rms": relative_rms(narrow, want),
        "bf16_state_vs_reference_held_to_the_programs_routing_relative_rms":
            relative_rms(narrow, held),
        "bf16_state_vs_program_relative_rms": relative_rms(narrow, got),
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
    # the second limit: arithmetic apart from routing flips
    limit = bench.read("reference", args.config + ".json").get(
        "tolerance_held_to_routing_relative_rms")
    if limit is not None:
        out["tolerance_held_to_routing_relative_rms"] = float(limit)
        out["passed"] = (
            out["program_vs_reference_held_to_its_routing_relative_rms"]
            < limit < out["bf16_state_vs_reference_held_to_the_programs_"
                          "routing_relative_rms"])
    out["device"] = device.record()
    print(json.dumps(out), flush=True)
    sys.exit(0 if out.get("passed", True) else 1)

"""The plain reference of the prompt expander's language model when it is a
Xing4.0 share (``family.expander``: latent attention, several residual
streams mixed by Sinkhorn-projected hyper-connections around every
sublayer, dense layers then a sigmoid router with a selection bias over
experts with one shared expert): one forward pass over all positions in
float32 at the highest matmul precision, in plain ``jax.numpy``: no cache,
no kernels, no batching, no chunks, **the expanded attention only** (every
head's keys and values are made for every position; nothing is absorbed).
It reads the same parameter tree the program's ``models/lm.py`` holds and
the same ``LMConfig``, and shares no code with it.

Every norm is ``x_hat * scale``, ``x_hat = x / sqrt(mean(x^2) + eps)``. A
token's state is ``X`` of shape (streams ``n``, hidden ``C``); ``X_0`` is
the token's embedding in every row. Around each sublayer ``F`` (attention,
then the MLP), with the sublayer's own mixer parameters ``phi`` ``(nC, n^2
+ 2n)``, ``alpha`` (three scalars: pre, post, res), ``b_pre``, ``b_post``
``(n,)`` and ``b_res`` ``(n, n)``:

    x~      = norm_nC(vec(X))                       # its own scale vector
    [p|q|R] = x~ phi                                # n, n, n*n columns
    H_pre   = sigmoid(alpha_pre p + b_pre)
    H_post  = 2 sigmoid(alpha_post q + b_post)
    M       = exp(clip(alpha_res mat(R) + b_res, -30, 30))
    20 x:     M <- M / (sum over rows of M + eps)   # every column sums to 1
              M <- M / (sum over columns of M + eps)
    H_res   = M
    y       = F(norm_C(H_pre X))
    X'      = H_res X + H_post (outer) y

and after the last layer ``sum_i X[i]`` goes through the final norm and the
head over the held slice of the vocabulary.

*Latent attention*, per head ``h`` of ``H``: ``c_q = norm(W_qa x)``,
``[q_nope | q_pe]_h = (W_qb c_q)_h`` (``nope`` + ``rope`` wide); ``[c | k_pe]
= W_kva x``, ``c <- norm(c)``; ``k_pe`` (ONE key, shared by the heads) and
``q_pe`` are rotated (``rotate_half`` pairing, YaRN's blended frequencies,
tables unscaled); ``[k_nope | v]_h = (W_kvb c)_h``; ``score_h(i, j) =
(q_nope_h(i) . k_nope_h(j) + q_pe_h(i) . k_pe(j)) s`` for ``j <= i``, ``s =
(nope + rope)^-1/2 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``; the
heads' ``softmax(score) v`` side by side go through ``W_o``.

*Expert layer*: ``s = sigmoid(W_r n)`` over all experts; the ``k`` with the
largest ``s + b`` are chosen, ``b`` the per-expert selection bias; ``w_e =
s_e / sum over the chosen of s`` (without ``b``), times
``routed_scaling_factor``; ``sum_{e chosen and held} w_e E_e(n) +
E_shared(n)``, every expert a SwiGLU with SiLU, the shared one ungated.

Departures from the published model are the configuration's ``assumed``:
the multi-token-prediction module is left out; ``X_0`` copies the embedding
into every stream and the output is the streams' sum; Sinkhorn normalises
columns first with ``eps`` in the denominator; ``rotate_half`` pairing.

Held experts are upcast to float32 one at a time (a loop over the held
experts, each applied to every token and weighted by what the router gave
it, zero where it was not chosen), so the reference fits beside the bf16
weights.

    python3 benchmarks/reference/xing4_ref.py --config sd15_xing4_expand

prints the diagnostic readings ``reference/<config>.json`` keeps beside the
tolerance: the share of (token, expert layer) pairs whose chosen experts
differ between program and reference, the program against the reference
held to the program's choices (routing flips apart from arithmetic error),
those readings with the residual streams kept in bfloat16 between
sublayers and with Sinkhorn's iterations run in bfloat16, the share of
pairs in which the selection bias changes the chosen set, and how far
``H_res`` is from the identity and from uniform. The held reading has a
limit of its own in that file (``tolerance_held_to_routing_relative_rms``):
the float32 program must meet it and each of the two bfloat16 controls must
miss it, or the exit code is 1.
"""

from __future__ import annotations

import math

COMPONENT = "expander"
CONTROL = "the program's dynamic int8 linears (quant_linears) on latent " \
          "attention's four Linear projections and o_proj, the dense MLPs, " \
          "the shared experts and the head"


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
            stream_dtype=None, sinkhorn_dtype=None):
    """What the timed path runs at the timed sizes: the prefix's prefill
    (expanded attention), a copy of the latent cache as it stands at the
    prefix's last token (the kept snapshot), the user chunk's prefill
    against that copy, then every further position decoded through the
    latent cache one token a step (absorbed attention), teacher-forced on
    the seeded ids. Logits at every position, float32. ``stream_dtype`` and
    ``sinkhorn_dtype`` (diagnostic readings only) keep the residual streams
    between sublayers, and run Sinkhorn's iterations, in that dtype instead
    of float32."""
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.models import lm

    cfg = family.expander
    lower = {name: dtype for name, dtype in (
        ("stream_dtype", stream_dtype), ("sinkhorn_dtype", sinkhorn_dtype))
        if dtype is not None}
    module = lm.DecoderLM(cfg, dtype=policy.compute_dtype,
                          quant_linears=control, **lower)

    def run(params, ids):
        prefix, user, decoded = split(ids.shape[0])
        cache = lm.empty_cache(cfg, ids.shape[0], policy.compute_dtype)
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


def _norm(x, p, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _w(p["scale"])


def _swiglu(n, p):
    import jax

    gate = n @ _w(p["gate_proj"]["kernel"])
    up = n @ _w(p["up_proj"]["kernel"])
    return (jax.nn.silu(gate) * up) @ _w(p["down_proj"]["kernel"])


def _inv_freq(rope, dim: int):
    """YaRN's frequencies as HF's ``_compute_yarn_parameters`` blends them:
    interpolated by ``factor`` where a pair turns fewer than ``beta_slow``
    times over the original context, kept where it turns more than
    ``beta_fast`` times, a linear ramp between."""
    import numpy as np

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


def _rope(x, rope):
    """HF's ``apply_rotary_pos_emb`` on ``(T, H, D)``, every dim rotated:
    cos and sin are ``cat(freqs, freqs)``, ``rotate_half`` swaps the halves
    with a sign. The tables are not scaled (``mscale == mscale_all_dim``)."""
    import jax.numpy as jnp

    inv = jnp.asarray(_inv_freq(rope, x.shape[-1]), jnp.float32)
    freqs = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[:, None, :], jnp.sin(emb)[:, None, :]
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def softmax_scale(cfg) -> float:
    m = 0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_full.factor) + 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _latent_attention(cfg, layer: int, n, p):
    import jax
    import jax.numpy as jnp

    tokens = n.shape[0]
    heads = cfg.num_heads_per_layer[layer]
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    rope, v_dim = cfg.qk_rope_head_dim, cfg.v_head_dim
    c_q = _norm(n @ _w(p["q_a_proj"]["kernel"]), p["q_a_norm"],
                cfg.rms_norm_eps)
    q = (c_q @ _w(p["q_b_proj"]["kernel"])).reshape(tokens, heads,
                                                    nope + rope)
    kva = n @ _w(p["kv_a_proj_with_mqa"]["kernel"])
    c = _norm(kva[:, :rank], p["kv_a_norm"], cfg.rms_norm_eps)
    k_pe = _rope(kva[:, None, rank:], cfg.rope_full)            # (T, 1, rope)
    q_pe = _rope(q[..., nope:], cfg.rope_full)
    kv = (c @ _w(p["kv_b_proj"]["kernel"])).reshape(tokens, heads,
                                                    nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("ihd,jhd->hij", q[..., :nope], k_nope)
              + jnp.einsum("ihd,jd->hij", q_pe, k_pe[:, 0])) \
        * softmax_scale(cfg)
    seen = jnp.arange(tokens)[None, :] <= jnp.arange(tokens)[:, None]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hij,jhd->ihd", probs, v)
    return out.reshape(tokens, heads * v_dim) @ _w(p["o_proj"]["kernel"])


def stream_maps(cfg, streams, p):
    """(H_pre ``(T, n)``, H_post ``(T, n)``, H_res ``(T, n, n)``) of one
    sublayer's mixer from the tokens' states ``(T, n, C)``."""
    import jax
    import jax.numpy as jnp

    tokens, n, _ = streams.shape
    flat = _norm(streams.reshape(tokens, -1), p["norm"], cfg.rms_norm_eps)
    mixed = flat @ _w(p["phi"])
    alpha = _w(p["alpha"])
    h_pre = jax.nn.sigmoid(alpha[0] * mixed[:, :n] + _w(p["b_pre"]))
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * mixed[:, n:2 * n]
                                  + _w(p["b_post"]))
    low, high = cfg.hc_res_clamp
    m = jnp.exp(jnp.clip(
        alpha[2] * mixed[:, 2 * n:].reshape(tokens, n, n) + _w(p["b_res"]),
        low, high))
    for _ in range(cfg.sinkhorn_iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + cfg.hc_eps)   # columns
        m = m / (jnp.sum(m, axis=2, keepdims=True) + cfg.hc_eps)   # rows
    return h_pre, h_post, m


def hyper_connected(cfg, streams, p, sublayer):
    """``H_res X + H_post (outer) F(H_pre X)`` for every token."""
    import jax.numpy as jnp

    h_pre, h_post, h_res = stream_maps(cfg, streams, p)
    out = sublayer(jnp.einsum("tn,tnc->tc", h_pre, streams))
    return (jnp.einsum("tij,tjc->tic", h_res, streams)
            + h_post[:, :, None] * out[:, None, :]), h_res


def route(cfg, n, p, forced=None):
    """(chosen experts (T, k), their weights (T, k), whether the bias
    changed the chosen set (T,)): float32 sigmoid scores over every expert,
    the k with the largest score + bias, weights the chosen scores without
    the bias, renormalised and scaled. ``forced`` gives the experts instead
    (the diagnostic reading); their weights are still this side's own
    scores."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(n @ _w(p["router"]))
    k = cfg.num_experts_per_tok
    _, biased = jax.lax.top_k(scores + _w(p["e_score_correction_bias"]), k)
    _, plain = jax.lax.top_k(scores, k)
    moved = jnp.any(jnp.sort(biased, -1) != jnp.sort(plain, -1), axis=-1)
    chosen = biased if forced is None else forced
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return chosen, top * cfg.routed_scaling_factor, moved


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
    """The ungated shared expert: what every chip of a layer computes
    alike."""
    return _swiglu(n, p["shared_expert"])


def _moe(cfg, n, p, forced=None):
    chosen, weights, moved = route(cfg, n, p, forced)
    routed = routed_part(n, chosen, weights, p["experts"], cfg.experts[0])
    return routed + shared_part(n, p), (chosen, moved)


def layer_forward(cfg, layer: int, streams, p, forced=None):
    """One decoder layer over ``(T, n, C)``: (streams after it, (experts
    chosen, whether the bias moved the choice) or None for a dense layer,
    the two mixers' H_res)."""
    routed = None

    def attention(u):
        return _latent_attention(
            cfg, layer, _norm(u, p["input_norm"], cfg.rms_norm_eps),
            p["attn"])

    def mlp(u):
        nonlocal routed
        n = _norm(u, p["post_attention_norm"], cfg.rms_norm_eps)
        if layer in cfg.dense_layers:
            return _swiglu(n, p["mlp"])
        out, routed = _moe(cfg, n, p["mlp"], forced)
        return out

    streams, res_a = hyper_connected(cfg, streams, p["attn_hc"], attention)
    streams, res_m = hyper_connected(cfg, streams, p["mlp_hc"], mlp)
    return streams, routed, (res_a, res_m)


def forward(family, params, ids, forced=None, with_routing=False,
            with_mixing=False):
    """Logits ``(T, held vocabulary)`` float32 at every position.
    ``forced`` ``(expert layers, T, k)`` holds the routing to the experts
    given. ``with_routing`` adds the chosen experts ``(expert layers, T,
    k)``; ``with_mixing`` adds the bias's moved choices ``(expert layers,
    T)`` and every mixer's H_res ``(2 * layers, T, n, n)``."""
    import jax
    import jax.numpy as jnp

    cfg = family.expander
    with jax.default_matmul_precision("highest"):
        first, count = cfg.vocab
        table = params["embed_tokens"]["embedding"]
        here = (ids >= first) & (ids < first + count)
        x = jnp.where(here[:, None], table[jnp.clip(ids - first, 0, count - 1)]
                      .astype(jnp.float32), 0.0)
        streams = jnp.repeat(x[:, None, :], cfg.residual_streams, axis=1)
        routing, moved, mixing = [], [], []
        for layer in range(cfg.num_layers):
            expert_layer = len(routing)
            streams, routed, res = layer_forward(
                cfg, layer, streams, params[f"layers_{layer}"],
                None if forced is None or layer in cfg.dense_layers
                else forced[expert_layer])
            mixing.extend(res)
            if routed is not None:
                routing.append(routed[0])
                moved.append(routed[1])
        n = _norm(jnp.sum(streams, axis=1), params["norm"], cfg.rms_norm_eps)
        logits = n @ _w(params["lm_head"]["kernel"])
    out = (logits,)
    if with_routing:
        out += (jnp.stack(routing),)
    if with_mixing:
        out += (jnp.stack(moved), jnp.stack(mixing))
    return out if len(out) > 1 else logits


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
    want, own, moved, mixing = jax.jit(lambda p, i: forward(
        family, p, i, with_routing=True, with_mixing=True))(params, ids)
    held = jax.jit(lambda p, i, f: forward(family, p, i, forced=f))(
        params, ids, chose)
    out = {
        "positions": int(ids.shape[0]), "seed": seed,
        "program_vs_reference_relative_rms": relative_rms(got, want),
        "routing_pairs_that_differ_share": float(jnp.mean(jnp.any(
            jnp.sort(chose, -1) != jnp.sort(own, -1), axis=-1))),
        "program_vs_reference_held_to_its_routing_relative_rms":
            relative_rms(got, held),
        "token_agreement_argmax_share": float(jnp.mean(
            jnp.argmax(got, -1) == jnp.argmax(want, -1))),
        "pairs_whose_choice_the_bias_changes_share":
            float(jnp.mean(moved)),
    }
    row_max = jnp.max(mixing, axis=-1)
    out["h_res_row_maximum_min_median_max"] = [
        float(jnp.min(row_max)), float(jnp.median(row_max)),
        float(jnp.max(row_max))]
    out["h_res_row_and_column_sums_max_off_one"] = float(jnp.maximum(
        jnp.max(jnp.abs(jnp.sum(mixing, -1) - 1)),
        jnp.max(jnp.abs(jnp.sum(mixing, -2) - 1))))
    for name, kwargs in (
            ("control", {"control": True}),
            ("bf16_streams", {"stream_dtype": jnp.bfloat16}),
            ("bf16_sinkhorn", {"sinkhorn_dtype": jnp.bfloat16})):
        lower = jax.jit(program(family, policy, **kwargs))(params, ids)
        out[f"{name}_vs_reference_relative_rms"] = relative_rms(lower, want)
        out[f"{name}_vs_reference_held_to_the_programs_routing_relative_rms"] \
            = relative_rms(lower, held)
    return out


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
        held = "_vs_reference_held_to_the_programs_routing_relative_rms"
        out["tolerance_held_to_routing_relative_rms"] = float(limit)
        out["passed"] = (
            out["program_vs_reference_held_to_its_routing_relative_rms"]
            < limit < min(out["bf16_streams" + held],
                          out["bf16_sinkhorn" + held]))
    out["device"] = device.record()
    print(json.dumps(out), flush=True)
    sys.exit(0 if out.get("passed", True) else 1)

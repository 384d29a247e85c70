"""The plain reference of the prompt expander's language model when it is a
GigaChat3.5-432B-A28B share (``family.expander``; ``model_type:
gigachat3_5``: gated delta-rule layers three in four beside gated latent
attention, sandwich norms under sigmoid gates, clamped SwiGLUs, leading
dense layers, then a sigmoid router with a selection bias over the experts
held here and one shared expert): one forward pass over all positions of
ONE sequence in float32 at the highest matmul precision, in plain
``jax.numpy``: no cache, no kernel, no batch, no chunks, the delta rule
**token by token**, **the expanded attention only** (every head's keys and
values are made for every position; nothing is absorbed, nothing forked).
It reads the same parameter tree the program's ``models/lm.py`` holds and
the same ``LMConfig``, and shares no code with it or with ``ops/``.

``N(x; w) = x / sqrt(mean(x^2) + eps) * c sigmoid(w)``, ``c =
layernorm_gating_weight = 2`` (``ZeroCenteredGatedNorm``: 1 at ``w = 0``).
A layer is ``h = x + N(Mix(N(x)))``, ``out = h + N(MLP(N(h)))``
(``pre_post``: four norms of their own weights); one final ``N``, then the
untied head over the held slice. ``swiglu(g, u) = silu(min(g, L)) *
clip(u, -L, L)``, ``L = swiglu_limit = 10``, in every dense, shared and
routed MLP. No bias anywhere.

*Linear layer* (``H_k`` key heads, ``H_v`` value heads, key head ``j``
serving the ``H_v / H_k`` consecutive value heads from ``j H_v / H_k``):
``[q | k | v | z] = n W_qkvz``, ``[b | a] = n W_ba``; ``[q | k | v]`` pass a
causal depth-wise convolution of ``taps`` taps (zeros before position 0, no
bias) and SiLU; ``q`` and ``k`` are L2-normalised per head (eps 1e-6) and
``q`` scaled by ``d_k^-1/2``. Per value head with state ``S`` ``(d_k,
d_v)`` from zero: ``g = -exp(A_log) softplus(a + dt_bias)``, ``beta =
sigmoid(b)``; ``S <- exp(g) S``; ``u = beta (v - S^T k)``; ``S <- S + k
u^T``; ``o = S^T q``. Read-out ``o / sqrt(mean(o^2) + eps) * (1 + w_o) * s
sigmoid(z)`` per head (``s = linear_sigmoid_gate_scale = 2``), then
``W_out``.

*Latent layer*, per head ``h``: ``[q_nope | q_pe]_h = (W_qb N(W_qa n))_h``;
``[c | k_pe] = W_kva n``, ``c <- N(c)``, ``k_pe`` ONE key for all heads.
``q_pe``, ``k_pe`` are rotated as ``rope_interleave: true`` has it in
transformers: de-interleaved (evens, then odds), then ``rotate_half`` under
``cat(freqs, freqs)``, the frequencies YaRN's blend (factor, original
length, ``beta_fast`` / ``beta_slow``), the tables unscaled (``mscale ==
mscale_all_dim``). ``[k_nope | v]_h = (W_kvb c)_h``; ``score = (q_nope .
k_nope + q_pe . k_pe) (nope + rope)^-1/2 m^2``, ``m = 0.1 mscale_all_dim
ln(factor) + 1``, causal; ``out_h = softmax(score) v_h * sigmoid((W_g
n)_h)`` element-wise, the heads side by side through ``W_o``.

*MLP*: the held dense layer one SwiGLU; an expert layer ``s = sigmoid(W_r
n)`` over ALL the layer's experts, the ``k`` with the largest ``s + b``
chosen, ``w_e = s_e / (sum of the chosen s + 1e-20) * scale``; ``sum over
the chosen experts HELD HERE of w_e E_e(n) + E_shared(n)``: what the absent
experts would add is left out, here as in the program.

Departures from the published model are the configuration's ``assumed``.
The clamp is inert at weights of variance 1/fan_in (a SwiGLU's products
have deviation about 1, far under 10): the chip's readings cannot see it
left out, and the control that leaves it out (``no_clamp``) is read in
tests/test_gigachat_expander.py on weights scaled so that it binds.

Held experts are upcast to float32 one at a time (a loop over the held
experts, each applied to every token and weighted by what the router gave
it, zero where it was not chosen), and attention and the head are applied
a block of rows at a time, so the reference fits beside the bf16 weights.

What is compared is what the timed path runs: ``SEQUENCES`` sequences that
share their first positions (the instruction and the prompt) and then
differ. :func:`forward` is one full forward of each WHOLE sequence, one
after the other; :func:`program` is the prefix's chunk (expanded latent
form, chunk-wise delta rule), a copy of the cache, the prompt's chunk, a
fork into ``SEQUENCES`` (latents shared, every state and every kept row
copied once a sequence) and one decode step over all of them a position.
Both give float32 logits at every distinct position: the shared rows once,
then each sequence's own rows.

    python3 benchmarks/reference/gigachat35_ref.py --config sd15_gigachat35_expand

prints the readings ``reference/<config>.json`` keeps beside the tolerance,
at the timed path's 2 368 positions unless ``--size`` says otherwise: the
share of (token, expert layer) pairs whose chosen experts differ between
program and reference, the program against the reference held to the
program's choices (routing flips apart from arithmetic error), and those
readings for the controls of :data:`CONTROLS`. The held reading has a
limit of its own in that file (``tolerance_held_to_routing_relative_rms``):
the program must meet it and each control must miss it, or the exit code is
1. The command itself stays off JAX and runs a PROCESS A STAGE
(:func:`read_stages`), as ``kanana2_ref.py`` does and for its reason.
"""

from __future__ import annotations

import math

COMPONENT = "expander"
CONTROL = "the program's dynamic int8 linears (quant_linears) on the " \
          "delta mixers' and latent attention's Linear projections, the " \
          "dense MLP, the shared experts and the head"
#: sequences forked from the one prefill: the images of the cell's request
SEQUENCES = 4


def split(size: int) -> tuple[int, int, int]:
    """(prefix, prompt chunk, decoded) positions of ``size``: at 2368 the
    timed path's 2048 + 64 + 256; at 74 it is 64 + 2 + 8."""
    decoded = max(1, size * 4 // 37)
    user = max(1, size // 37)
    return size - user - decoded, user, decoded


def inputs(family, seed: int, size: int):
    """Seeded ids of the vocabulary: the shared ``(prefix + prompt,)`` and
    ``(SEQUENCES, decoded)`` continuations that differ from their first
    token on."""
    import jax

    first, count = family.expander.vocab
    prefix, user, decoded = split(size)
    key = jax.random.key(seed + 7)
    return (jax.random.randint(key, (prefix + user,), first, first + count),
            jax.random.randint(jax.random.fold_in(key, 1),
                               (SEQUENCES, decoded), first, first + count))


def _plain_read_out(params):
    """``params`` with every delta read-out norm's zero-centred ``weight``
    as the plain ``scale`` ``1 + weight`` (the control that gates by
    ``silu(z)`` reads a plain norm)."""
    out = dict(params)
    for name, layer in params.items():
        if name.startswith("layers_") and "delta" in layer:
            norm = {"scale": 1.0 + layer["delta"]["norm"]["weight"].astype(
                "float32")}
            out[name] = {**layer, "delta": {**layer["delta"], "norm": norm}}
    return out


def stages(family, policy, control: bool = False, state_bf16: bool = False,
           state_shared: bool = False, kept_shared: bool = False,
           silu_gate: bool = False, plain_norm: bool = False,
           no_post_norm: bool = False, no_attn_gate: bool = False,
           no_mscale: bool = False, no_selection_bias: bool = False,
           no_clamp: bool = False):
    """What the timed path runs at the timed sizes, as the two executables
    it runs them as (:func:`program` joins them, :func:`staged` runs them
    apart): the prefix's prefill as one chunk, a copy of the cache as it
    stands at the prefix's last token (the kept snapshot: latents, states
    and kept rows), the prompt chunk's prefill against that copy, a fork of
    the cache into ``SEQUENCES``, then every further position decoded one
    step over all sequences at a time, teacher-forced on the seeded
    continuations. Logits ``(prefix + prompt + SEQUENCES * decoded,
    vocabulary)``: the shared rows, then each sequence's. The controls,
    each a fault the comparison must see: ``state_bf16`` keeps the
    recurrent states in bfloat16 between tokens; ``state_shared`` hands
    every sequence sequence 0's state before each step and
    ``kept_shared`` its kept rows; ``silu_gate`` gates the delta read-out
    by ``silu(z)`` under a plain norm; ``plain_norm`` reads every norm as
    ``x_hat * (1 + w)``; ``no_post_norm`` leaves out the norms after the
    sublayers; ``no_attn_gate`` the attention's output gate; ``no_mscale``
    the ``m^2`` of the softmax scale; ``no_selection_bias`` chooses by the
    scores alone; ``no_clamp`` leaves the SwiGLUs unclamped."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.models import lm

    cfg = family.expander
    if silu_gate:
        cfg = dataclasses.replace(cfg, linear_sigmoid_gate_scale=0.0)
    if plain_norm:
        cfg = dataclasses.replace(cfg, norm_sigmoid_scale=0.0,
                                  zero_centred_norm=True)
    if no_post_norm:
        cfg = dataclasses.replace(cfg, post_sublayer_norm=False)
    if no_attn_gate:
        cfg = dataclasses.replace(cfg, attn_gate="none")
    if no_mscale:
        cfg = dataclasses.replace(cfg, rope_mscale_all_dim=0.0)
    if no_selection_bias:
        cfg = dataclasses.replace(cfg, router_bias=False)
    if no_clamp:
        cfg = dataclasses.replace(cfg, swiglu_limit=0.0)
    module = lm.DecoderLM(cfg, dtype=policy.compute_dtype,
                          quant_linears=control)

    def held(params):
        return _plain_read_out(params) if silu_gate else params

    def prefills(params, ids, decoded: int):
        """The two chunks and the fork: (their logits, the forked cache,
        the experts their rows chose ``(layers, rows, k)``)."""
        size = ids.shape[0] + decoded
        prefix = split(size)[0]
        cache = lm.empty_cache(cfg, size, policy.compute_dtype)
        if state_bf16:
            cache["state"] = [x.astype(jnp.bfloat16) for x in cache["state"]]
        apply = lambda t, start, c: module.apply(   # noqa: E731
            {"params": held(params)}, t, jnp.int32(start),
            jnp.int32(t.shape[0]), c)
        l0, snapshot, r0 = apply(ids[:prefix], 0, cache)
        cache = jax.tree_util.tree_map(jnp.copy, snapshot)
        l1, cache, r1 = apply(ids[prefix:], prefix, cache)
        return (jnp.concatenate([l0, l1]),
                kv.fork(cache, SEQUENCES, decoded),
                jnp.concatenate([r0[0], r1[0]], axis=1))

    def decodes(params, cache, continuations, shared: int):
        """Every further position, one step over all sequences a time:
        (each sequence's logits in turn, the experts chosen)."""
        def first_for_all(rows):
            return [jnp.broadcast_to(x[:1], x.shape) for x in rows]

        def between(cache):
            if state_shared:
                cache = {**cache, "state": first_for_all(cache["state"])}
            if kept_shared:
                cache = {**cache, "conv": first_for_all(cache["conv"])}
            return cache

        def step(carry, tokens):
            cache, position = carry
            logits, cache, routed = module.apply(
                {"params": held(params)}, tokens, position,
                jnp.int32(SEQUENCES), between(cache), sequences=True)
            return (cache, position + 1), (logits, routed[0])

        _, (l2, r2) = jax.lax.scan(
            step, (cache, jnp.int32(shared)), continuations.T)
        # (steps, sequences, ...) -> each sequence's rows in turn
        l2 = jnp.moveaxis(l2, 1, 0).reshape(-1, l2.shape[-1])
        # (steps, layers, sequences, k) -> (layers, each sequence's rows, k)
        r2 = jnp.transpose(r2, (1, 2, 0, 3)).reshape(
            r2.shape[1], -1, r2.shape[3])
        return l2, r2

    return prefills, decodes


def program(family, policy, control: bool = False, with_routing=False,
            **controls):
    """:func:`stages` as one function of ``(params, ids, continuations)``:
    logits, and ``with_routing`` the experts chosen ``(layers, rows, k)``
    beside them. ``control`` is the int8 Linears; ``controls`` the other
    faults :func:`stages` can be given."""
    import jax.numpy as jnp

    prefills, decodes = stages(family, policy, control, **controls)

    def run(params, ids, continuations):
        shared, cache, r01 = prefills(params, ids, continuations.shape[1])
        own, r2 = decodes(params, cache, continuations, ids.shape[0])
        logits = jnp.concatenate([shared, own])
        if not with_routing:
            return logits
        return logits, jnp.concatenate([r01, r2], axis=1)

    return run


def staged(family, policy, params, ids, continuations, **controls):
    """(logits, experts chosen) of :func:`program` with the chunks and the
    fork as one executable and the decode steps as another, the cache
    handed from one to the other on the device: the two executables the
    timed path builds."""
    import jax
    import jax.numpy as jnp

    prefills, decodes = stages(family, policy, **controls)
    decoded = int(continuations.shape[1])
    shared, cache, r01 = jax.jit(prefills, static_argnums=2)(
        params, ids, decoded)
    own, r2 = jax.jit(decodes, static_argnums=3)(
        params, cache, continuations, int(ids.shape[0]))
    return jnp.concatenate([shared, own]), jnp.concatenate([r01, r2], axis=1)


# -- the reference -----------------------------------------------------------

def _w(leaf):
    import jax.numpy as jnp

    return leaf.astype(jnp.float32)


def _x_hat(x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _norm(cfg, x, p):
    """``N(x; w)``: ``x_hat * c sigmoid(w)``."""
    import jax

    return _x_hat(x, cfg.rms_norm_eps) * (
        cfg.norm_sigmoid_scale * jax.nn.sigmoid(_w(p["weight"])))


def _inv_freq(rope, dim: int):
    """YaRN's frequencies as transformers' ``_compute_yarn_parameters``
    blends them: interpolated by ``factor`` where a pair turns fewer than
    ``beta_slow`` times over the original context, kept where it turns more
    than ``beta_fast`` times, a linear ramp between."""
    import numpy as np

    base = np.float64(rope.theta)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, \
        1.0 / (rope.factor * pos_freqs)

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
    return interpolation * ramp + extrapolation * (1 - ramp)


def _rope(x, rope):
    """transformers' ``apply_rotary_pos_emb_interleave`` on ``(T, H, D)``,
    every dim rotated: the dims de-interleaved (evens, then odds), then
    ``x cos + rotate_half(x) sin`` under ``cat(freqs, freqs)``, YaRN's
    frequencies, the tables unscaled."""
    import jax.numpy as jnp

    tokens, heads, dim = x.shape
    x = x.reshape(tokens, heads, dim // 2, 2).swapaxes(-1, -2).reshape(
        tokens, heads, dim)
    inv = jnp.asarray(_inv_freq(rope, dim), jnp.float32)
    freqs = jnp.arange(tokens, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[:, None, :], jnp.sin(emb)[:, None, :]
    rotated = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rotated * sin


def _row_block(tokens: int, most: int = 256) -> int:
    """The largest divisor of ``tokens`` at or under ``most``."""
    return max(b for b in range(1, most + 1) if tokens % b == 0)


def softmax_scale(cfg) -> float:
    m = 0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_full.factor) + 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def attention(cfg, layer: int, n, p):
    """Gated latent attention over the whole sequence, expanded: every
    head's keys and values are made from every position's latent."""
    import jax
    import jax.numpy as jnp

    tokens = n.shape[0]
    heads = cfg.num_heads_per_layer[layer]
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    rope, v_dim = cfg.qk_rope_head_dim, cfg.v_head_dim
    c_q = _norm(cfg, n @ _w(p["q_a_proj"]["kernel"]), p["q_a_norm"])
    q = (c_q @ _w(p["q_b_proj"]["kernel"])).reshape(tokens, heads,
                                                    nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], cfg.rope_full)], -1)
    down = n @ _w(p["kv_a_proj_with_mqa"]["kernel"])
    c = _norm(cfg, down[:, :rank], p["kv_a_norm"])
    k_pe = _rope(down[:, None, rank:], cfg.rope_full)   # one key a position
    up = (c @ _w(p["kv_b_proj"]["kernel"])).reshape(
        tokens, heads, nope + v_dim)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_pe, (tokens, heads, rope))], -1)
    v = up[..., nope:]
    j = jnp.arange(tokens)[None, :]
    block = _row_block(tokens)
    scale = softmax_scale(cfg)

    def rows(at):
        i = at + jnp.arange(block)[:, None]
        scores = jnp.einsum(
            "ihd,jhd->hij", jax.lax.dynamic_slice_in_dim(q, at, block), k) \
            * scale
        probs = jax.nn.softmax(
            jnp.where((i - j >= 0)[None], scores, -jnp.inf), -1)
        return jnp.einsum("hij,jhd->ihd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, tokens, block)).reshape(
        tokens, heads * v_dim)
    gate = jax.nn.sigmoid(n @ _w(p["g_proj"]["kernel"]))
    return (out * gate) @ _w(p["o_proj"]["kernel"])


def delta_mixer(cfg, n, p):
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
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

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
    out = _x_hat(out, cfg.rms_norm_eps) * (1.0 + _w(p["norm"]["weight"])) \
        * (cfg.linear_sigmoid_gate_scale
           * jax.nn.sigmoid(z.reshape(tokens, vh, vd)))
    return out.reshape(tokens, vh * vd) @ _w(p["out_proj"]["kernel"])


def _swiglu(cfg, n, p):
    import jax
    import jax.numpy as jnp

    limit = cfg.swiglu_limit
    gate = jnp.minimum(n @ _w(p["gate_proj"]["kernel"]), limit)
    up = jnp.clip(n @ _w(p["up_proj"]["kernel"]), -limit, limit)
    return (jax.nn.silu(gate) * up) @ _w(p["down_proj"]["kernel"])


def route(cfg, n, p, forced=None):
    """(chosen experts (T, k), their weights (T, k)): float32 sigmoids over
    every expert, the k largest of score + bias, their scores (without the
    bias) over their sum + 1e-20, scaled. ``forced`` gives the experts
    instead (the diagnostic reading); their weights are still this side's
    own scores."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(n @ _w(p["router"]))
    _, chosen = jax.lax.top_k(
        scores + _w(p["e_score_correction_bias"]), cfg.num_experts_per_tok)
    if forced is not None:
        chosen = forced
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return chosen, top * cfg.routed_scaling_factor


def routed_part(cfg, n, chosen, weights, experts):
    """``sum over the chosen experts held here of w_e E_e(n)``: a loop over
    the held experts, each upcast alone and applied to every token; the
    chosen experts other chips hold add nothing."""
    import jax
    import jax.numpy as jnp

    first, held = cfg.experts
    limit = cfg.swiglu_limit

    def one(e, acc):
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        gate = jnp.minimum(n @ _w(experts["w_gate"][e]), limit)
        up = jnp.clip(n @ _w(experts["w_up"][e]), -limit, limit)
        out = (jax.nn.silu(gate) * up) @ _w(experts["w_down"][e])
        return acc + w_e[:, None] * out

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(n))


def layer_forward(cfg, layer: int, x, p, forced=None):
    """One decoder layer over ``(T, C)``: (x after it, experts chosen; None
    for the dense layer)."""
    n = _norm(cfg, x, p["input_norm"])
    if cfg.layer_types[layer] == "linear":
        mixed = delta_mixer(cfg, n, p["delta"])
    else:
        mixed = attention(cfg, layer, n, p["attn"])
    x = x + _norm(cfg, mixed, p["input_norm_2"])
    n = _norm(cfg, x, p["post_attention_norm"])
    if layer in cfg.dense_layers:
        out, chosen = _swiglu(cfg, n, p["mlp"]), None
    else:
        chosen, weights = route(cfg, n, p["mlp"], forced)
        out = routed_part(cfg, n, chosen, weights, p["mlp"]["experts"]) \
            + _swiglu(cfg, n, p["mlp"]["shared_expert"])
    return x + _norm(cfg, out, p["post_attention_norm_2"]), chosen


def trunk(cfg, params, ids, forced=None):
    """(the final norm's output ``(T, C)``, the experts chosen ``(expert
    layers, T, k)``) of one whole sequence."""
    import jax.numpy as jnp

    x = params["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    routing = []
    for layer in range(cfg.num_layers):
        x, chosen = layer_forward(
            cfg, layer, x, params[f"layers_{layer}"],
            None if forced is None or layer in cfg.dense_layers
            else forced[len(routing)])
        if chosen is not None:
            routing.append(chosen)
    return _norm(cfg, x, params["norm"]), jnp.stack(routing)


def forward(family, params, ids, continuations, forced=None,
            with_routing=False):
    """Logits at every distinct position, in :func:`program`'s order: one
    full forward over each whole sequence (the shared ids, then its own
    continuation), one sequence after the other; the head over the shared
    rows of the first and the own rows of each. ``forced`` ``(expert
    layers, rows, k)`` in the same order of rows holds the routing to the
    experts given. ``with_routing`` adds the chosen experts."""
    import jax
    import jax.numpy as jnp

    cfg = family.expander
    shared = ids.shape[0]
    own = continuations.shape[1]

    def of_sequence(b, rows):
        """``rows`` ``(layers, shared + SEQUENCES * own, k)`` as sequence
        ``b``'s ``(layers, shared + own, k)``."""
        return jnp.concatenate(
            [rows[:, :shared], jax.lax.dynamic_slice_in_dim(
                rows, shared + b * own, own, 1)], axis=1)

    def whole(b):
        return trunk(cfg, params,
                     jnp.concatenate([ids, continuations[b]]),
                     None if forced is None else of_sequence(b, forced))

    with jax.default_matmul_precision("highest"):
        n, chosen = jax.lax.map(whole, jnp.arange(continuations.shape[0]))
        rows = jnp.concatenate(
            [n[0, :shared], n[:, shared:].reshape(-1, n.shape[-1])])
        head = params["lm_head"]["kernel"]
        block = _row_block(rows.shape[0])
        # a block of rows at a time
        logits = jax.lax.map(
            lambda part: part @ _w(head),
            rows.reshape(-1, block, rows.shape[-1])).reshape(
                rows.shape[0], -1)
    if not with_routing:
        return logits
    return logits, jnp.concatenate(
        [chosen[0, :, :shared]] + [chosen[b, :, shared:]
                                   for b in range(chosen.shape[0])], axis=1)


#: the controls' readings, by name: the keyword arguments of :func:`program`
CONTROLS = (
    ("control", {"control": True}),
    ("state_bf16", {"state_bf16": True}),
    ("state_shared", {"state_shared": True}),
    ("kept_shared", {"kept_shared": True}),
    ("silu_gate", {"silu_gate": True}),
    ("plain_norm", {"plain_norm": True}),
    ("no_post_norm", {"no_post_norm": True}),
    ("no_attn_gate", {"no_attn_gate": True}),
    ("no_mscale", {"no_mscale": True}),
    ("no_selection_bias", {"no_selection_bias": True}),
)
HELD = "_vs_reference_held_to_the_programs_routing_relative_rms"
#: the timed path's positions (2 048 + 64 + 256): what the readings are
#: taken at unless ``--size`` says otherwise
TIMED_POSITIONS = 2368


def _blocks(rows: int, most: int = 256):
    return ((at, min(at + most, rows)) for at in range(0, rows, most))


def relative_rms(got, want) -> float:
    """Relative RMS of two host arrays of logits, summed in float64 a block
    of rows at a time."""
    import numpy as np

    error = norm = 0.0
    for lo, hi in _blocks(got.shape[0]):
        w = np.asarray(want[lo:hi], np.float64)
        error += float(np.sum((np.asarray(got[lo:hi], np.float64) - w) ** 2))
        norm += float(np.sum(w ** 2))
    return math.sqrt(error / norm)


def argmax_agreement(got, want) -> float:
    import numpy as np

    same = sum(int(np.sum(np.argmax(got[lo:hi], -1)
                          == np.argmax(want[lo:hi], -1)))
               for lo, hi in _blocks(got.shape[0]))
    return same / got.shape[0]


def bias_changes_share(cfg, params, n_rows=512, seed=0) -> float:
    """The share of random normed rows whose chosen set the selection bias
    changes, over the expert layers' routers (a property of the seeded
    weights: how often the control ``no_selection_bias`` can show)."""
    import jax
    import jax.numpy as jnp

    n = jax.random.normal(jax.random.key(seed), (n_rows, cfg.hidden_size))
    changed = []
    for layer in cfg.expert_layers:
        p = params[f"layers_{layer}"]["mlp"]
        scores = jax.nn.sigmoid(n @ _w(p["router"]))
        _, with_bias = jax.lax.top_k(
            scores + _w(p["e_score_correction_bias"]),
            cfg.num_experts_per_tok)
        _, without = jax.lax.top_k(scores, cfg.num_experts_per_tok)
        changed.append(jnp.mean(jnp.any(
            jnp.sort(with_bias, -1) != jnp.sort(without, -1), axis=-1)))
    return float(jnp.mean(jnp.stack(changed)))


def read_stage(bench, config: dict, stage: str, keep: str, seed=None,
               size=None, apart: bool = False) -> dict:
    """ONE process's share of the readings beside the tolerance (see the
    module's text), at the configuration's ``weight_seed`` or another:
    weights and ids both come from it. Stage ``readings``: the program, the
    reference and the reference held to the program's routing, each pulled
    to the host as it ends and its reading printed on stderr at once; the
    two references are left in ``keep`` as ``.npy``. Any other stage is a
    name of :data:`CONTROLS`: that control alone, as the FIRST and only
    program-sized executable of its process, read against the two files.
    ``apart``: the program through :func:`staged`."""
    import os
    import sys
    import time

    import jax
    import numpy as np

    sys.path.insert(0, bench.root)
    from benchmarks.harness import files, weights

    t0 = time.perf_counter()

    def say(text):
        print(f"[{time.perf_counter() - t0:7.1f} s] seed {seed} {stage}: "
              f"{text}", file=sys.stderr, flush=True)

    def host(step, arrays):
        """The arrays on the host, their device copies dropped."""
        out = [np.asarray(a) for a in jax.block_until_ready(arrays)]
        for a in arrays:
            a.delete()
        say(step)
        return out

    def run(**kwargs):
        if apart:
            return staged(family, policy, params, ids, continuations,
                          **kwargs)
        return jax.jit(program(family, policy, with_routing=True, **kwargs))(
            params, ids, continuations)

    family = files.resolve_family(config)
    policy = files.resolve_policy(config)
    components = bench.components(config)
    module, args = components.component_inits(family)[COMPONENT]
    seed = int(config["weight_seed"]) if seed is None else int(seed)
    params = jax.block_until_ready(weights.fill(
        weights.param_shapes(module, args), policy.param_dtype, seed,
        getattr(components, "leaf_rule", None)))
    say("weights")
    ids, continuations = inputs(family, seed, int(size or TIMED_POSITIONS))
    if stage != "readings":
        lower, _ = host("ran", run(**dict(CONTROLS)[stage]))
        out = {}
        for name, against in (("_vs_reference_relative_rms", "want"),
                              (HELD, "held")):
            out[stage + name] = relative_rms(lower, np.load(
                os.path.join(keep, against + ".npy"), mmap_mode="r"))
            say(f"{stage + name} {out[stage + name]:.6g}")
        return out
    out = {
        "positions": int(sum(split(ids.shape[0] + continuations.shape[1]))),
        "sequences": int(continuations.shape[0]), "seed": seed,
        "executables": "chunks and steps apart" if apart else "one",
        "selection_bias_changes_the_choice_share": bias_changes_share(
            family.expander, params),
    }
    got, chose = host("program", run())
    out["rows_compared"] = int(got.shape[0])
    want, own = host("reference", jax.jit(lambda p, i, c: forward(
        family, p, i, c, with_routing=True))(params, ids, continuations))
    np.save(os.path.join(keep, "want.npy"), want)
    for name, value in (
            ("program_vs_reference_relative_rms", relative_rms(got, want)),
            ("routing_pairs_that_differ_share", float(np.mean(np.any(
                np.sort(chose, -1) != np.sort(own, -1), axis=-1)))),
            ("token_agreement_argmax_share", argmax_agreement(got, want))):
        out[name] = value
        say(f"{name} {value:.6g}")
    del want
    held, = host("reference held to the program's routing", [jax.jit(
        lambda p, i, c, f: forward(family, p, i, c, forced=f))(
            params, ids, continuations, chose)])
    np.save(os.path.join(keep, "held.npy"), held)
    name = "program_vs_reference_held_to_its_routing_relative_rms"
    out[name] = relative_rms(got, held)
    say(f"{name} {out[name]:.6g}")
    from benchmarks.harness import device

    out["device"] = device.record()
    return out


def read_stages(argv: list, stages: list, timeout: float, keep: str,
                out: dict) -> None:
    """Adds to ``out`` what each of ``stages`` reads, a process a stage
    (this one stays off JAX: a chip belongs to one process at a time):
    ``argv`` is this file's command line without a stage. A stage that ends
    badly or outlasts ``timeout`` seconds is named under ``failed`` and the
    others still run."""
    import json
    import subprocess
    import sys

    for stage in stages:
        try:
            done = subprocess.run(
                [sys.executable] + argv + ["--stage", stage, "--keep", keep],
                stdout=subprocess.PIPE, timeout=timeout, text=True)
            fault = None if done.returncode == 0 \
                else f"exit code {done.returncode}"
        except subprocess.TimeoutExpired:
            fault = f"no end after {timeout:.0f} s"
        if fault is None:
            out.update(json.loads(done.stdout.strip().splitlines()[-1]))
        else:
            out.setdefault("failed", {})[stage] = fault
            print(f"{' '.join(argv[1:])} --stage {stage}: {fault}",
                  file=sys.stderr, flush=True)


if __name__ == "__main__":
    import argparse
    import json
    import os
    import shutil
    import sys
    import tempfile

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from benchmarks.harness import files

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, nargs="*", default=[None],
                    help="weights and ids, one reading a seed (default: "
                         "the file's weight_seed)")
    ap.add_argument("--size", type=int, default=TIMED_POSITIONS,
                    help="positions (default: the timed path's)")
    ap.add_argument("--controls", default=None,
                    help="comma-separated names of CONTROLS (default: all)")
    ap.add_argument("--staged", action="store_true",
                    help="the chunks and the steps as two executables")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="seconds a stage's process may take")
    ap.add_argument("--stage", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--keep", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    bench = files.Bench(root)
    if args.stage:      # one process of read_stages'
        print(json.dumps(read_stage(
            bench, bench.config(args.config), args.stage, args.keep,
            args.seed[0], args.size, args.staged)), flush=True)
        sys.exit(0)
    names = [n for n, _ in CONTROLS] if args.controls is None else \
        [n for n in args.controls.split(",") if n]
    # the second limit: arithmetic apart from routing flips
    limit = bench.read("reference", args.config + ".json").get(
        "tolerance_held_to_routing_relative_rms")
    own = "program_vs_reference_held_to_its_routing_relative_rms"
    seeds = args.seed or [None]
    argvs = [[os.path.abspath(__file__), "--config", args.config,
              "--size", str(args.size)]
             + ([] if seed is None else ["--seed", str(seed)])
             + (["--staged"] if args.staged else []) for seed in seeds]
    keeps = [tempfile.mkdtemp(prefix="gigachat35-ref-") for _ in seeds]
    outs: list = [{} for _ in seeds]
    passed = True
    try:
        # every seed's own readings first: they are what the limits are
        # set from, and a control that hangs costs its whole timeout
        for argv, keep, out in zip(argvs, keeps, outs):
            read_stages(argv, ["readings"], args.timeout, keep, out)
        given_up: dict = {}     # a control that failed once is not tried again
        for argv, keep, out in zip(argvs, keeps, outs):
            if "failed" not in out:
                read_stages(argv, [n for n in names if n not in given_up],
                            args.timeout, keep, out)
                for name, fault in given_up.items():
                    out.setdefault("failed", {})[name] = fault
                for name in out.get("failed", {}):
                    given_up.setdefault(
                        name, f"not tried: failed at seed {out['seed']}")
            if limit is not None:
                out["tolerance_held_to_routing_relative_rms"] = float(limit)
                out["passed"] = "failed" not in out and (
                    out[own] < limit < min([out[n + HELD] for n in names]
                                           or [float("inf")]))
                passed &= out["passed"]
            print(json.dumps(out), flush=True)
    finally:
        for keep in keeps:
            shutil.rmtree(keep, ignore_errors=True)
    sys.exit(0 if passed else 1)

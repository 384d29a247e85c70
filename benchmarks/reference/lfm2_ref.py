"""The plain reference of the prompt expander's language model when it is an
LFM2 share (``family.expander``: gated short-convolution layers with a
grouped-query attention layer one in four, dense layers then a sigmoid
router with a selection bias over experts that are all held and no shared
expert): one forward pass over all positions in float32 at the highest
matmul precision, in plain ``jax.numpy``: no cache, no kernels, no
batching, no chunks, no kept rows. It reads the same parameter tree the
program's ``models/lm.py`` holds and the same ``LMConfig``, and shares no
code with it.

Every norm is ``x_hat * scale``, ``x_hat = x / sqrt(mean(x^2) + eps)``.
Every layer is ``x = x + mixer(norm(x)); x = x + mlp(norm(x))`` with its own
two norms; one final norm, then the head over the held vocabulary.

*Conv layer*, over the whole sequence ``n`` ``(T, C)``:

    [B | C | x~] = n W_in                  # C -> 3C, no bias
    u            = B * x~
    c_t          = sum_{j=0..taps-1} w_j * u_{t-(taps-1)+j}   # u_{<0} = 0
    y            = (C * c) W_out

the convolution written as ``taps`` shifted element-wise products of the
whole sequence, per channel, causal, with NO activation after it.

*Attention layer*, per query head ``h`` of ``H`` over KV head ``h // (H /
KV)``: ``q = norm_D(W_q n)_h``, ``k = norm_D(W_k n)_g`` (an RMS norm per
head, over the head's width, before the rotation), both rotated over all
``D`` dims (``rotate_half`` pairing, plain frequencies ``theta^(-2i/D)``);
``softmax(q k^T D^-1/2)`` over ``j <= i``; the heads' sums side by side
through ``W_o``. No gate on the output, no bias.

*Expert layer*: ``s = sigmoid(W_r n)`` over all experts; the ``k`` with the
largest ``s + b`` are chosen, ``b`` the per-expert selection bias; ``w_e =
s_e / (sum over the chosen of s + eps)`` (without ``b``; ``eps``
``norm_topk_eps``), times ``routed_scaling_factor``; ``sum_{e chosen and
held} w_e E_e(n)``, every expert a SwiGLU with SiLU. There is no shared
expert. A dense layer's MLP is one SwiGLU.

Departures from the published model are the configuration's ``assumed``:
the head untied from the table; the fused projection's column order ``[B |
C | x~]``; ``rotate_half`` pairing; the ``eps`` in the weights' denominator.

Held experts are upcast to float32 one at a time (a loop over the held
experts, each applied to every token and weighted by what the router gave
it, zero where it was not chosen), so the reference fits beside the bf16
weights.

    python3 benchmarks/reference/lfm2_ref.py --config sd15_lfm2_expand

prints the diagnostic readings ``reference/<config>.json`` keeps beside the
tolerance: the share of (token, expert layer) pairs whose chosen experts
differ between program and reference, the program against the reference
held to the program's choices (routing flips apart from arithmetic error),
the share of pairs in which the selection bias changes the chosen set, and
those readings for three controls: the program's int8 linears, the kept
rows ZEROED between every two calls of the program (a chunk or a decode
step that sees no earlier input of its convolutions), and the gates' and
taps' products in bfloat16. The held reading has a limit of its own in that
file (``tolerance_held_to_routing_relative_rms``): the program must meet it
and each of the three controls must miss it, or the exit code is 1.
"""

from __future__ import annotations

COMPONENT = "expander"
CONTROL = "the program's dynamic int8 linears (quant_linears) on the conv " \
          "mixers' in_proj and out_proj, attention's four projections, " \
          "the dense MLPs and the head"


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
            drop_kept_rows: bool = False, conv_dtype=None):
    """What the timed path runs at the timed sizes: the prefix's prefill, a
    copy of the cache (two layers' keys and values, eight layers' kept
    rows) as it stands at the prefix's last token (the kept snapshot), the
    user chunk's prefill against that copy, then every further position
    decoded through the cache one token a step, teacher-forced on the
    seeded ids. Logits at every position, float32. ``drop_kept_rows``
    (a control) zeroes every conv layer's kept rows between two calls;
    ``conv_dtype`` (a diagnostic reading) multiplies the gates and the taps
    in that dtype instead of float32."""
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.models import lm

    cfg = family.expander
    lower = {} if conv_dtype is None else {"conv_dtype": conv_dtype}
    module = lm.DecoderLM(cfg, dtype=policy.compute_dtype,
                          quant_linears=control, **lower)

    def between(cache):
        if drop_kept_rows:
            cache = dict(cache, kept=[jnp.zeros_like(rows)
                                      for rows in cache["kept"]])
        return cache

    def run(params, ids):
        prefix, user, decoded = split(ids.shape[0])
        cache = lm.empty_cache(cfg, ids.shape[0], policy.compute_dtype)
        apply = lambda t, start, c: module.apply(   # noqa: E731
            {"params": params}, t, jnp.int32(start), jnp.int32(t.shape[0]),
            c)
        l0, snapshot, r0 = apply(ids[:prefix], 0, cache)
        cache = between(jax.tree_util.tree_map(jnp.copy, snapshot))
        l1, cache, r1 = apply(ids[prefix:prefix + user], prefix, cache)

        def step(carry, token):
            cache, position = carry
            logits, cache, routed = module.apply(
                {"params": params}, token[None], position, jnp.int32(1),
                between(cache))
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


def short_conv(cfg, n, p):
    """The gated short convolution over the whole sequence ``(T, C)``."""
    import jax.numpy as jnp

    tokens, taps = n.shape[0], cfg.conv_taps
    b, c, x = jnp.split(n @ _w(p["in_proj"]["kernel"]), 3, axis=-1)
    u = jnp.pad(b * x, ((taps - 1, 0), (0, 0)))    # nothing before row 0
    w = _w(p["conv_kernel"])
    mixed = sum(w[j][None, :] * u[j:j + tokens] for j in range(taps))
    return (c * mixed) @ _w(p["out_proj"]["kernel"])


def _rope(x, theta: float):
    """HF's ``apply_rotary_pos_emb`` on ``(T, H, D)``, every dim rotated:
    cos and sin are ``cat(freqs, freqs)``, ``rotate_half`` swaps the halves
    with a sign."""
    import jax.numpy as jnp
    import numpy as np

    dim = x.shape[-1]
    inv = jnp.asarray(1.0 / np.float64(theta) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim), jnp.float32)
    freqs = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[:, None, :], jnp.sin(emb)[:, None, :]
    rotated = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rotated * sin


def attention(cfg, layer: int, n, p):
    """Ungated grouped-query attention with a norm per head on queries and
    keys before the rotation."""
    import jax
    import jax.numpy as jnp

    tokens = n.shape[0]
    heads, kv, dim = (cfg.num_heads_per_layer[layer], cfg.num_kv_heads,
                      cfg.head_dim)
    q = (n @ _w(p["q_proj"]["kernel"])).reshape(tokens, heads, dim)
    k = (n @ _w(p["k_proj"]["kernel"])).reshape(tokens, kv, dim)
    v = (n @ _w(p["v_proj"]["kernel"])).reshape(tokens, kv, dim)
    q = _rope(_norm(q, p["q_norm"], cfg.rms_norm_eps), cfg.rope_full.theta)
    k = _rope(_norm(k, p["k_norm"], cfg.rms_norm_eps), cfg.rope_full.theta)
    k = jnp.repeat(k, heads // kv, axis=1)     # head h reads KV head h // g
    v = jnp.repeat(v, heads // kv, axis=1)
    scores = jnp.einsum("ihd,jhd->hij", q, k) * dim ** -0.5
    seen = jnp.arange(tokens)[None, :] <= jnp.arange(tokens)[:, None]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hij,jhd->ihd", probs, v)
    return out.reshape(tokens, heads * dim) @ _w(p["o_proj"]["kernel"])


def route(cfg, n, p, forced=None):
    """(chosen experts (T, k), their weights (T, k), whether the bias
    changed the chosen set (T,)): float32 sigmoid scores over every expert,
    the k with the largest score + bias, weights the chosen scores without
    the bias over (their sum + eps), scaled. ``forced`` gives the experts
    instead (the diagnostic reading); their weights are still this side's
    own scores."""
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
        top = top / (jnp.sum(top, axis=-1, keepdims=True)
                     + cfg.norm_topk_eps)
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


def _moe(cfg, n, p, forced=None):
    chosen, weights, moved = route(cfg, n, p, forced)
    return routed_part(n, chosen, weights, p["experts"],
                       cfg.experts[0]), (chosen, moved)


def layer_forward(cfg, layer: int, x, p, forced=None):
    """One decoder layer over ``(T, C)``: (x after it, (experts chosen,
    whether the bias moved the choice) or None for a dense layer)."""
    n = _norm(x, p["input_norm"], cfg.rms_norm_eps)
    if cfg.layer_types[layer] == "conv":
        x = x + short_conv(cfg, n, p["short_conv"])
    else:
        x = x + attention(cfg, layer, n, p["attn"])
    n = _norm(x, p["post_attention_norm"], cfg.rms_norm_eps)
    if layer in cfg.dense_layers:
        return x + _swiglu(n, p["mlp"]), None
    out, routed = _moe(cfg, n, p["mlp"], forced)
    return x + out, routed


def forward(family, params, ids, forced=None, with_routing=False,
            with_moved=False):
    """Logits ``(T, held vocabulary)`` float32 at every position.
    ``forced`` ``(expert layers, T, k)`` holds the routing to the experts
    given. ``with_routing`` adds the chosen experts ``(expert layers, T,
    k)``; ``with_moved`` adds the bias's moved choices ``(expert layers,
    T)``."""
    import jax
    import jax.numpy as jnp

    cfg = family.expander
    with jax.default_matmul_precision("highest"):
        first, count = cfg.vocab
        table = params["embed_tokens"]["embedding"]
        here = (ids >= first) & (ids < first + count)
        x = jnp.where(here[:, None], table[jnp.clip(ids - first, 0, count - 1)]
                      .astype(jnp.float32), 0.0)
        routing, moved = [], []
        for layer in range(cfg.num_layers):
            x, routed = layer_forward(
                cfg, layer, x, params[f"layers_{layer}"],
                None if forced is None or layer in cfg.dense_layers
                else forced[len(routing)])
            if routed is not None:
                routing.append(routed[0])
                moved.append(routed[1])
        n = _norm(x, params["norm"], cfg.rms_norm_eps)
        logits = n @ _w(params["lm_head"]["kernel"])
    out = (logits,)
    if with_routing:
        out += (jnp.stack(routing),)
    if with_moved:
        out += (jnp.stack(moved),)
    return out if len(out) > 1 else logits


#: the controls' readings, by name: the keyword arguments of :func:`program`
CONTROLS = (
    ("control", {"control": True}),
    ("dropped_kept_rows", {"drop_kept_rows": True}),
    ("bf16_taps", {"conv_dtype": "bfloat16"}),
)
HELD = "_vs_reference_held_to_the_programs_routing_relative_rms"


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
    want, own, moved = jax.jit(lambda p, i: forward(
        family, p, i, with_routing=True, with_moved=True))(params, ids)
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
    for name, kwargs in CONTROLS:
        lower = jax.jit(program(family, policy, **kwargs))(params, ids)
        out[f"{name}_vs_reference_relative_rms"] = relative_rms(lower, want)
        out[name + HELD] = relative_rms(lower, held)
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
        out["tolerance_held_to_routing_relative_rms"] = float(limit)
        out["passed"] = (
            out["program_vs_reference_held_to_its_routing_relative_rms"]
            < limit < min(out[name + HELD] for name, _ in CONTROLS))
    out["device"] = device.record()
    print(json.dumps(out), flush=True)
    sys.exit(0 if out.get("passed", True) else 1)

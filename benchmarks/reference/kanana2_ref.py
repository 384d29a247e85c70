"""The plain reference of the prompt expander's language model when it is a
kanana-2-30b-a3b share (``family.expander``; Hugging Face ``deepseek_v3``:
latent attention with NO query latent and interleaved rotary pairs, one
leading dense layer, then a sigmoid router with a selection bias over
experts that are all held, with a shared expert of twice the routed
width): one forward pass over all positions of ONE sequence in float32 at
the highest matmul precision, in plain ``jax.numpy``: no cache, no kernel,
no batch, no chunks, **the expanded attention only** (every head's keys and
values are made for every position; nothing is absorbed, nothing forked).
It reads the same parameter tree the program's ``models/lm.py`` holds and
the same ``LMConfig``, and shares no code with it.

Every norm is ``x_hat * scale``, ``x_hat = x / sqrt(mean(x^2) + eps)``.
Every layer is ``h = x + attn(norm(x)); out = h + mlp(norm(h))`` with its
own two norms; one final norm, then the untied head over the vocabulary.
No bias anywhere.

*Latent attention*, per head ``h`` of ``H``: ``[q_nope | q_pe]_h = (W_q
n)_h`` (``nope`` + ``rope`` wide; no latent and no norm on the query path);
``[c | k_pe] = W_kva n``, ``c <- norm(c)``; ``k_pe`` is ONE key shared by
the heads. ``q_pe`` and ``k_pe`` are rotated over all ``rope`` dims as
``rope_interleave: true`` has it in transformers: the dims are first
de-interleaved (the even ones, then the odd ones) and then turned by
``rotate_half`` under ``cos`` and ``sin`` of ``cat(freqs, freqs)``, ``freqs
= pos * theta^(-2i/rope)``, unscaled: dims ``(2i, 2i + 1)`` of what the
projection gave make a pair that turns by ``pos * theta^(-2i/rope)``.
``[k_nope | v]_h = (W_kvb c)_h``; ``score_h(i, j) = (q_nope_h(i) .
k_nope_h(j) + q_pe_h(i) . k_pe(j)) (nope + rope)^-1/2`` for ``j <= i`` (no
``mscale``: ``rope_scaling`` is null); the heads' ``softmax(score) v`` side
by side go through ``W_o``.

*Layer 0*: a dense SwiGLU (SiLU). *The others*: ``s = sigmoid(W_r n)`` over
all experts in float32; the ``k`` with the largest ``s + b`` are chosen,
``b`` the per-expert selection bias (``n_group`` 1, ``topk_group`` 1: no
group limit); ``w_e = s_e / (sum over the chosen of s + 1e-20)`` (without
``b``), times ``routed_scaling_factor``; ``sum_{e chosen} w_e E_e(n) +
E_shared(n)``, every routed expert a SwiGLU, the shared one ONE ungated
SwiGLU of ``n_shared_experts`` times the routed width.

Departures from the published model are the configuration's ``assumed``.

Held experts are upcast to float32 one at a time (a loop over the held
experts, each applied to every token and weighted by what the router gave
it, zero where it was not chosen), and the head is applied a block of rows
at a time, so the reference fits beside the bf16 weights.

What is compared is what the timed path runs: ``SEQUENCES`` sequences that
share their first positions (the instruction and the prompt) and then
differ. :func:`forward` is one full forward of each WHOLE sequence, one
after the other; :func:`program` is the prefix's chunk (expanded form), a
copy of the cache, the prompt's chunk, a fork into ``SEQUENCES`` and one
decode step over all of them a position (the forked absorbed form). Both
give logits at every distinct position: the shared rows once, then each
sequence's own rows. Where the weights are stored in bfloat16 (the chip,
at the published widths) the logits are handed back in float16 (three
arrays of 3 136 x 128 256 beside 10.1 GB of weights do not fit in float32;
float16's 2.8e-4 of rounding is far under the smallest reading taken
there). At float32 weights (the tests) they stay float32.

    python3 benchmarks/reference/kanana2_ref.py --config sd15_kanana2_expand

prints the readings ``reference/<config>.json`` keeps beside the tolerance,
at the timed path's 2 368 positions unless ``--size`` says otherwise: the
share of (token, expert layer) pairs whose chosen experts differ between
program and reference, the program against the reference held to the
program's choices (routing flips apart from arithmetic error), and those
readings for the controls of :data:`CONTROLS`. The held reading has a
limit of its own in that file (``tolerance_held_to_routing_relative_rms``):
the program must meet it and each control must miss it, or the exit code is
1. The command itself stays off JAX and runs a PROCESS A STAGE
(:func:`read_stages`), as ``mellum2_ref.py`` does and for its reason: a
second program-sized executable in one process has hung this device.
"""

from __future__ import annotations

import math

COMPONENT = "expander"
CONTROL = "the program's dynamic int8 linears (quant_linears) on latent " \
          "attention's three Linear projections, the dense MLP, the shared " \
          "experts and the head"
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


def _out_dtype(params):
    """What the logits are handed back in (see the module's text)."""
    import jax.numpy as jnp

    stored = params["embed_tokens"]["embedding"].dtype
    return jnp.float16 if stored == jnp.bfloat16 else jnp.float32


def _narrow_shared(params, width: int):
    """``params`` with every shared expert cut to its first ``width``
    columns (and ``down_proj`` to as many rows)."""
    out = dict(params)
    for name, layer in params.items():
        shared = layer.get("mlp", {}).get("shared_expert") \
            if name.startswith("layers_") else None
        if shared is None:
            continue
        cut = {"gate_proj": {"kernel": shared["gate_proj"]["kernel"][:, :width]},
               "up_proj": {"kernel": shared["up_proj"]["kernel"][:, :width]},
               "down_proj": {"kernel": shared["down_proj"]["kernel"][:width]}}
        out[name] = {**layer, "mlp": {**layer["mlp"], "shared_expert": cut}}
    return out


def stages(family, policy, control: bool = False, rotate_half: bool = False,
           no_selection_bias: bool = False,
           narrow_shared_expert: bool = False,
           own_rows_dropped: bool = False,
           shared_without_prompt: bool = False):
    """What the timed path runs at the timed sizes, as the two executables
    it runs them as (:func:`program` joins them, :func:`staged` runs them
    apart): the prefix's prefill as one chunk, a copy of the cache as it
    stands at the prefix's last token (the kept snapshot), the prompt
    chunk's prefill against that copy, a fork of the cache into
    ``SEQUENCES``, then every further position decoded one step over all
    sequences at a time, teacher-forced on the seeded continuations.
    Logits ``(prefix + prompt + SEQUENCES * decoded, vocabulary)``: the
    shared rows, then each sequence's. The controls, each a fault the
    comparison must see: ``rotate_half`` pairs the rotary dims ``(i, i +
    half)``; ``no_selection_bias`` chooses by the scores alone;
    ``narrow_shared_expert`` cuts the shared expert to the routed width;
    ``own_rows_dropped`` empties every sequence's own rows before each
    step; ``shared_without_prompt`` hands the fork the shared latents with
    the prompt's rows zero (what a fork of the kept snapshot would)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.models import lm

    cfg = family.expander
    if rotate_half:
        cfg = dataclasses.replace(cfg, rope_full=dataclasses.replace(
            cfg.rope_full, interleaved=False))
    if no_selection_bias:
        cfg = dataclasses.replace(cfg, router_bias=False)
    if narrow_shared_expert:
        cfg = dataclasses.replace(
            cfg, shared_expert_intermediate_size=cfg.moe_intermediate_size)
    module = lm.DecoderLM(cfg, dtype=policy.compute_dtype,
                          quant_linears=control)

    def held(params):
        if not narrow_shared_expert:
            return params
        return _narrow_shared(params, cfg.moe_intermediate_size)

    def prefills(params, ids, decoded: int):
        """The two chunks and the fork: (their logits, the forked cache,
        the experts their rows chose ``(layers, rows, k)``)."""
        size = ids.shape[0] + decoded
        prefix = split(size)[0]
        cache = lm.empty_cache(cfg, size, policy.compute_dtype)
        apply = lambda t, start, c: module.apply(   # noqa: E731
            {"params": held(params)}, t, jnp.int32(start),
            jnp.int32(t.shape[0]), c)
        l0, snapshot, r0 = apply(ids[:prefix], 0, cache)
        cache = jax.tree_util.tree_map(jnp.copy, snapshot)
        l1, cache, r1 = apply(ids[prefix:], prefix, cache)
        if shared_without_prompt:
            cache = {name: [x.at[prefix:].set(0) for x in rows]
                     for name, rows in cache.items()}
        # each part cast before they are joined: the float32 whole would
        # be a GB more beside the weights
        out = _out_dtype(params)
        return (jnp.concatenate([l0.astype(out), l1.astype(out)]),
                kv.fork(cache, SEQUENCES, decoded),
                jnp.concatenate([r0[0], r1[0]], axis=1))

    def decodes(params, cache, continuations, shared: int):
        """Every further position, one step over all sequences a time:
        (each sequence's logits in turn, the experts chosen)."""
        own = lm.LATENT_BUFFERS[0]

        def between(cache):
            if not own_rows_dropped:
                return cache
            return {**cache, own: [jnp.zeros_like(x) for x in cache[own]]}

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
        return l2.astype(_out_dtype(params)), r2

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


def _norm(x, p, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _w(p["scale"])


def _rope(x, theta: float):
    """transformers' ``apply_rotary_pos_emb_interleave`` on ``(T, H, D)``,
    every dim rotated: the dims de-interleaved (evens, then odds), then
    ``x cos + rotate_half(x) sin`` under ``cat(freqs, freqs)``."""
    import jax.numpy as jnp

    tokens, heads, dim = x.shape
    x = x.reshape(tokens, heads, dim // 2, 2).swapaxes(-1, -2).reshape(
        tokens, heads, dim)
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    freqs = jnp.arange(tokens, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[:, None, :], jnp.sin(emb)[:, None, :]
    rotated = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rotated * sin


def _row_block(tokens: int, most: int = 256) -> int:
    """The largest divisor of ``tokens`` at or under ``most``."""
    return max(b for b in range(1, most + 1) if tokens % b == 0)


def attention(cfg, layer: int, n, p):
    """Latent attention over the whole sequence, expanded: every head's
    keys and values are made from every position's latent."""
    import jax
    import jax.numpy as jnp

    tokens = n.shape[0]
    heads = cfg.num_heads_per_layer[layer]
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    rope, v_dim = cfg.qk_rope_head_dim, cfg.v_head_dim
    theta = cfg.rope_full.theta
    q = (n @ _w(p["q_proj"]["kernel"])).reshape(tokens, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    down = n @ _w(p["kv_a_proj_with_mqa"]["kernel"])
    c = _norm(down[:, :rank], p["kv_a_norm"], cfg.rms_norm_eps)
    k_pe = _rope(down[:, None, rank:], theta)           # one key a position
    up = (c @ _w(p["kv_b_proj"]["kernel"])).reshape(
        tokens, heads, nope + v_dim)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_pe, (tokens, heads, rope))], -1)
    v = up[..., nope:]
    j = jnp.arange(tokens)[None, :]
    block = _row_block(tokens)

    def rows(at):
        i = at + jnp.arange(block)[:, None]
        scores = jnp.einsum(
            "ihd,jhd->hij", jax.lax.dynamic_slice_in_dim(q, at, block), k) \
            * (nope + rope) ** -0.5
        probs = jax.nn.softmax(
            jnp.where((i - j >= 0)[None], scores, -jnp.inf), -1)
        return jnp.einsum("hij,jhd->ihd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, tokens, block))
    return out.reshape(tokens, heads * v_dim) @ _w(p["o_proj"]["kernel"])


def _swiglu(n, p):
    import jax

    return (jax.nn.silu(n @ _w(p["gate_proj"]["kernel"]))
            * (n @ _w(p["up_proj"]["kernel"]))) @ _w(p["down_proj"]["kernel"])


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


def routed_part(n, chosen, weights, experts):
    """``sum over the chosen experts of w_e E_e(n)``: a loop over the held
    experts (all of them), each upcast alone and applied to every token."""
    import jax
    import jax.numpy as jnp

    held = experts["w_gate"].shape[0]

    def one(e, acc):
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        gate = n @ _w(experts["w_gate"][e])
        up = n @ _w(experts["w_up"][e])
        out = (jax.nn.silu(gate) * up) @ _w(experts["w_down"][e])
        return acc + w_e[:, None] * out

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(n))


def layer_forward(cfg, layer: int, x, p, forced=None):
    """One decoder layer over ``(T, C)``: (x after it, experts chosen; None
    for the dense layer)."""
    x = x + attention(cfg, layer, _norm(x, p["input_norm"],
                                        cfg.rms_norm_eps), p["attn"])
    n = _norm(x, p["post_attention_norm"], cfg.rms_norm_eps)
    if layer in cfg.dense_layers:
        return x + _swiglu(n, p["mlp"]), None
    chosen, weights = route(cfg, n, p["mlp"], forced)
    return x + routed_part(n, chosen, weights, p["mlp"]["experts"]) \
        + _swiglu(n, p["mlp"]["shared_expert"]), chosen


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
    return _norm(x, params["norm"], cfg.rms_norm_eps), jnp.stack(routing)


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
        # a block of rows at a time: the float32 whole is 1.6 GB
        logits = jax.lax.map(
            lambda part: (part @ _w(head)).astype(_out_dtype(params)),
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
    ("rotate_half", {"rotate_half": True}),
    ("no_selection_bias", {"no_selection_bias": True}),
    ("narrow_shared_expert", {"narrow_shared_expert": True}),
    ("own_rows_dropped", {"own_rows_dropped": True}),
    ("shared_without_prompt", {"shared_without_prompt": True}),
)
HELD = "_vs_reference_held_to_the_programs_routing_relative_rms"
#: the timed path's positions (2 048 + 64 + 256): what the readings are
#: taken at unless ``--size`` says otherwise
TIMED_POSITIONS = 2368


def _blocks(rows: int, most: int = 256):
    return ((at, min(at + most, rows)) for at in range(0, rows, most))


def relative_rms(got, want) -> float:
    """Relative RMS of two host arrays of logits, summed in float64 a block
    of rows at a time (the whole in float64 would be 3.2 GB a side)."""
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
    keeps = [tempfile.mkdtemp(prefix="kanana2-ref-") for _ in seeds]
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

"""The plain reference of the prompt expander's language model when it is a
LongCat-Flash-Chat share (``family.expander``; Hugging Face
``LongcatFlashForCausalLM``): one forward pass over all positions of ONE
sequence in float32 at the highest matmul precision, in plain
``jax.numpy``: no cache, no kernel, no batch, no chunks, **the expanded
attention only**. It reads the parameter tree the program's ``models/lm.py``
holds (under the published names, :func:`double_layer_params`) and the
widths of the same ``LMConfig``, and shares no code with it; in particular
it knows nothing of the program's spelling of one published layer as two
entries of its lists: a layer here is ONE function, :func:`double_layer`.

Every norm is ``x_hat * scale``, ``x_hat = x / sqrt(mean(x^2) + eps)``. No
bias anywhere but the router's selection bias. One final norm, then the
untied head over the held slice of the vocabulary.

*The shortcut-connected double layer* (``LongcatFlashDecoderLayer``), with
``A_0, A_1`` latent attentions, ``F_0, F_1`` dense SwiGLUs (SiLU), ``M`` the
router's sum and four norms::

    x1 = x  + A_0(N_a0(x));      n = N_m0(x1)
    s  = M(n)                    # NOT added here
    x2 = x1 + F_0(n)
    x3 = x2 + A_1(N_a1(x2))
    y  = x3 + F_1(N_m1(x3)) + s  # one attention and one MLP later

*Latent attention*, per head ``h`` of ``H``: ``[q_nope | q_pe]_h = (W_qb
norm(W_qa n))_h * (hidden / q_lora_rank)^0.5`` (``mla_scale_q_lora``: both
parts); ``[c | k_pe] = W_kva n``, ``c <- norm(c) * (hidden /
kv_lora_rank)^0.5`` (``mla_scale_kv_lora``: before ``W_kvb``, so the
un-rotated keys and the values carry it and the rotated key does not);
``k_pe`` is ONE key shared by the heads; ``q_pe`` and ``k_pe`` turn by
``rotate_half`` under ``cos`` and ``sin`` of ``cat(freqs, freqs)``, ``freqs
= pos * theta^(-2i/rope)``, unscaled (the repo's pairing; the published
code pairs neighbours, a fixed permutation of ``W_qb``'s and ``W_kva``'s
columns under seeded weights); ``[k_nope | v]_h = (W_kvb c)_h``;
``score_h(i, j) = (q_nope_h(i) . k_nope_h(j) + q_pe_h(i) . k_pe(j)) (nope +
rope)^-1/2`` for ``j <= i``; the heads' ``softmax(score) v`` side by side go
through ``W_o``.

*The router's sum* (``LongcatFlashTopkRouter``, ``LongcatFlashMoE``): ``p =
softmax(W_r n)`` over ALL the router's outputs, the real experts and then
the zero-compute ones; the ``k`` with the largest ``p +
e_score_correction_bias`` are chosen; their weights are
``routed_scaling_factor * p`` without the bias, NOT renormalised. An id at
or over the count of real experts is an identity expert, ``E_e(n) = n``::

    M(n) = sum_{k: e_k real, held here} w_k SwiGLU_{e_k}(n)
           + (sum_{k: e_k identity} w_k) n

Only the held range of the real experts adds its part (the chip's share of
a stated deployment; what the absent ones would add is left out, here and
in the program alike); every identity expert is held wherever the token
lives. Departures from the published model are the configuration's
``assumed``: the rotary pairing above, no multi-token-prediction module, a
seeded selection bias.

Held experts are upcast to float32 one at a time (a plain loop over the
held ids, each applied to every token and weighted by what the router gave
it, zero where it was not chosen), attention runs a block of query rows at
a time and the head a block of rows at a time, so the reference fits
beside the bf16 weights.

What is compared is what the timed path runs: ``SEQUENCES`` sequences that
share their first positions (the instruction and the prompt) and then
differ. :func:`forward` is one full forward of each WHOLE sequence, one
after the other; :func:`program` is the prefix's chunk (expanded form), a
copy of the cache, the prompt's chunk, a fork into ``SEQUENCES`` and one
decode step over all of them a position (the forked absorbed form, the
routed sum forking with the rows). Both give float32 logits at every
distinct position: the shared rows once, then each sequence's own rows.

    python3 benchmarks/reference/longcat_flash_ref.py --config sd15_longcat_flash_expand

prints the readings ``reference/<config>.json`` keeps beside the tolerance,
at the timed path's 2 368 positions unless ``--size`` says otherwise: the
share of (token, router) pairs whose chosen experts differ between program
and reference, the program against the reference held to the program's
choices (routing flips apart from arithmetic error), the share of pairs
whose choice the selection bias changes, the picks by kind (held, identity,
absent), and those readings for the wrong programs of :data:`CONTROLS`.
The program must meet both limits of that file (``tolerance_relative_rms``
overall, ``tolerance_held_to_routing_relative_rms`` held to its routing)
and each wrong program must miss both, or the exit code is 1. The command
itself stays off JAX and runs a PROCESS A STAGE
(:func:`read_stages`), as ``kanana2_ref.py`` does and for its reason: a
second program-sized executable in one process has hung this device.
"""

from __future__ import annotations

import math

COMPONENT = "expander"
CONTROL = "the router's product with bfloat16 operands (router_dtype) " \
          "where the program multiplies in float32 at the highest precision"
#: sequences forked from the one prefill: the images of the cell's request
SEQUENCES = 4


def split(size: int) -> tuple[int, int, int]:
    """(prefix, prompt chunk, decoded) positions of ``size``: at 2368 the
    timed path's 2048 + 64 + 256; at 74 it is 64 + 2 + 8."""
    decoded = max(1, size * 4 // 37)
    user = max(1, size // 37)
    return size - user - decoded, user, decoded


def inputs(family, seed: int, size: int):
    """Seeded ids of the held slice of the vocabulary: the shared ``(prefix
    + prompt,)`` and ``(SEQUENCES, decoded)`` continuations that differ from
    their first token on."""
    import jax

    first, count = family.expander.vocab
    prefix, user, decoded = split(size)
    key = jax.random.key(seed + 7)
    return (jax.random.randint(key, (prefix + user,), first, first + count),
            jax.random.randint(jax.random.fold_in(key, 1),
                               (SEQUENCES, decoded), first, first + count))


def _without_held_experts(params):
    """``params`` with every held expert's ``w_down`` zero: the held
    experts' part of every routed sum is then zero."""
    import jax.numpy as jnp

    out = dict(params)
    for name, layer in params.items():
        experts = layer.get("mlp", {}).get("experts") \
            if name.startswith("layers_") else None
        if experts is None:
            continue
        out[name] = {**layer, "mlp": {**layer["mlp"], "experts": {
            **experts, "w_down": jnp.zeros_like(experts["w_down"])}}}
    return out


def stages(family, policy, control: bool = False,
           no_identity_term: bool = False, no_held_experts: bool = False,
           no_shortcut: bool = False, no_q_scale: bool = False,
           no_kv_scale: bool = False):
    """What the timed path runs at the timed sizes, as the two executables
    it runs them as (:func:`program` joins them, :func:`staged` runs them
    apart): the prefix's prefill as one chunk, a copy of the cache as it
    stands at the prefix's last token (the kept snapshot), the prompt
    chunk's prefill against that copy, a fork of the cache into
    ``SEQUENCES``, then every further position decoded one step over all
    sequences at a time, teacher-forced on the seeded continuations.
    Logits ``(prefix + prompt + SEQUENCES * decoded, vocabulary)``: the
    shared rows, then each sequence's. The wrong programs, each a fault the
    comparison must see: ``control`` makes the router's product in
    bfloat16; ``no_identity_term`` gives the zero-compute experts' picks
    nothing; ``no_held_experts`` zeroes the held experts' part;
    ``no_shortcut`` adds the routed sum at its own residual, a sublayer
    early; ``no_q_scale`` and ``no_kv_scale`` leave one latent scale
    out."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.models import lm

    cfg = family.expander
    if no_identity_term:    # the router keeps its width: such a pick is absent
        cfg = dataclasses.replace(cfg, zero_experts=0,
                                  experts_held=cfg.experts)
    if no_shortcut:
        cfg = dataclasses.replace(cfg, moe_shortcut=False)
    if no_q_scale:
        cfg = dataclasses.replace(cfg, latent_q_scale=1.0)
    if no_kv_scale:
        cfg = dataclasses.replace(cfg, latent_kv_scale=1.0)
    module = lm.DecoderLM(
        cfg, dtype=policy.compute_dtype,
        router_dtype=jnp.bfloat16 if control else jnp.float32)

    def held(params):
        return _without_held_experts(params) if no_held_experts else params

    def prefills(params, ids, decoded: int):
        """The two chunks and the fork: (their logits, the forked cache,
        the experts their rows chose ``(routers, rows, k)``)."""
        size = ids.shape[0] + decoded
        prefix = split(size)[0]
        cache = lm.empty_cache(cfg, size, policy.compute_dtype)
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
        def step(carry, tokens):
            cache, position = carry
            logits, cache, routed = module.apply(
                {"params": held(params)}, tokens, position,
                jnp.int32(SEQUENCES), cache, sequences=True)
            return (cache, position + 1), (logits, routed[0])

        _, (l2, r2) = jax.lax.scan(
            step, (cache, jnp.int32(shared)), continuations.T)
        # (steps, sequences, ...) -> each sequence's rows in turn
        l2 = jnp.moveaxis(l2, 1, 0).reshape(-1, l2.shape[-1])
        # (steps, routers, sequences, k) -> (routers, each sequence's rows, k)
        r2 = jnp.transpose(r2, (1, 2, 0, 3)).reshape(
            r2.shape[1], -1, r2.shape[3])
        return l2, r2

    return prefills, decodes


def program(family, policy, control: bool = False, with_routing=False,
            **controls):
    """:func:`stages` as one function of ``(params, ids, continuations)``:
    logits, and ``with_routing`` the experts chosen ``(routers, rows, k)``
    beside them. ``control`` is the router's product in bfloat16;
    ``controls`` the other faults :func:`stages` can be given."""
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
    """``x cos + rotate_half(x) sin`` on ``(T, H, D)``, every dim rotated,
    under ``cat(freqs, freqs)`` of positions 0..T-1."""
    import jax.numpy as jnp

    tokens, _, dim = x.shape
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    freqs = jnp.arange(tokens, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[:, None, :], jnp.sin(emb)[:, None, :]
    rotated = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rotated * sin


def _row_block(tokens: int, most: int = 256) -> int:
    """The largest divisor of ``tokens`` at or under ``most``."""
    return max(b for b in range(1, most + 1) if tokens % b == 0)


def attention(cfg, n, p, q_scaled: bool = True, kv_scaled: bool = True):
    """Latent attention over the whole sequence, expanded: every head's
    keys and values are made from every position's latent. ``q_scaled``,
    ``kv_scaled`` False leave a published scale out (the faults of the
    reference itself the tests tell apart)."""
    import jax
    import jax.numpy as jnp

    tokens, hidden = n.shape
    heads = cfg.num_heads_per_layer[0]
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    rope, v_dim = cfg.qk_rope_head_dim, cfg.v_head_dim
    theta = cfg.rope_full.theta
    q = (_norm(n @ _w(p["q_a_proj"]["kernel"]), p["q_a_norm"],
               cfg.rms_norm_eps) @ _w(p["q_b_proj"]["kernel"])).reshape(
                   tokens, heads, nope + rope)
    if q_scaled:        # mla_scale_q_lora: both parts
        q = q * (hidden / cfg.q_lora_rank) ** 0.5
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    down = n @ _w(p["kv_a_proj_with_mqa"]["kernel"])
    c = _norm(down[:, :rank], p["kv_a_norm"], cfg.rms_norm_eps)
    if kv_scaled:       # mla_scale_kv_lora: before kv_b_proj
        c = c * (hidden / rank) ** 0.5
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
    """(chosen ids (T, k) over ALL the router's outputs, their weights (T,
    k)): a float32 softmax over every output, the k largest of score +
    bias, ``routed_scaling_factor`` times their scores (without the bias),
    not renormalised. ``forced`` gives the ids instead (the diagnostic
    reading); their weights are still this side's own scores."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.softmax(n @ _w(p["router"]), axis=-1)
    _, chosen = jax.lax.top_k(
        scores + _w(p["e_score_correction_bias"]), cfg.num_experts_per_tok)
    if forced is not None:
        chosen = forced
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, top * cfg.routed_scaling_factor


def routed_sum(cfg, n, chosen, weights, experts, held=None):
    """``M(n)``: a plain loop over the held ids of the real experts (each
    upcast alone and applied to every token, weighted by what the router
    gave it, zero where it was not chosen), then the identity branch: an
    id at or over the count of real experts returns its input. ``held``
    ``(first, count)`` of the real experts whose kernels ``experts``
    stacks (default: the configuration's share)."""
    import jax
    import jax.numpy as jnp

    real = cfg.num_experts - cfg.zero_experts
    first, count = held or cfg.experts
    acc = jnp.zeros_like(n)
    for at in range(count):
        w_e = jnp.sum(jnp.where(chosen == first + at, weights, 0.0), axis=-1)
        gate = n @ _w(experts["w_gate"][at])
        up = n @ _w(experts["w_up"][at])
        out = (jax.nn.silu(gate) * up) @ _w(experts["w_down"][at])
        acc = acc + w_e[:, None] * out
    w_identity = jnp.sum(jnp.where(chosen >= real, weights, 0.0), axis=-1)
    return acc + w_identity[:, None] * n


def double_layer_params(params, index: int) -> dict:
    """Published layer ``index`` of the program's tree under the published
    names: two attentions, four norms, two dense MLPs, one router."""
    first = params[f"layers_{2 * index}"]
    second = params[f"layers_{2 * index + 1}"]
    return {"self_attn": (first["attn"], second["attn"]),
            "input_layernorm": (first["input_norm"], second["input_norm"]),
            "post_attention_layernorm": (first["post_attention_norm"],
                                         second["post_attention_norm"]),
            "mlps": (first["mlp"]["shared_expert"], second["mlp"]),
            "mlp": {"router": first["mlp"]["router"],
                    "e_score_correction_bias":
                        first["mlp"]["e_score_correction_bias"],
                    "experts": first["mlp"]["experts"]}}


def double_layer(cfg, x, p, forced=None, fault: str = ""):
    """One shortcut-connected layer over ``(T, C)``: (x after it, the ids
    its router chose). ``fault`` names a wrong form of it, for the tests:
    ``"in_place"`` adds the routed sum at its own residual, ``"no_q_scale"``
    / ``"no_kv_scale"`` leave a latent scale out."""
    eps = cfg.rms_norm_eps
    scaled = {"q_scaled": fault != "no_q_scale",
              "kv_scaled": fault != "no_kv_scale"}
    x = x + attention(cfg, _norm(x, p["input_layernorm"][0], eps),
                      p["self_attn"][0], **scaled)
    n = _norm(x, p["post_attention_layernorm"][0], eps)
    chosen, weights = route(cfg, n, p["mlp"], forced)
    s = routed_sum(cfg, n, chosen, weights, p["mlp"]["experts"])
    x = x + _swiglu(n, p["mlps"][0])
    if fault == "in_place":
        x, s = x + s, 0.0
    x = x + attention(cfg, _norm(x, p["input_layernorm"][1], eps),
                      p["self_attn"][1], **scaled)
    x = x + _swiglu(_norm(x, p["post_attention_layernorm"][1], eps),
                    p["mlps"][1])
    return x + s, chosen


def trunk(cfg, params, ids, forced=None, fault: str = ""):
    """(the final norm's output ``(T, C)``, the ids chosen ``(routers, T,
    k)``) of one whole sequence. The table is the held slice's."""
    import jax.numpy as jnp

    x = params["embed_tokens"]["embedding"][ids - cfg.vocab[0]].astype(
        jnp.float32)
    routing = []
    for index in range(cfg.num_layers // 2):
        x, chosen = double_layer(
            cfg, x, double_layer_params(params, index),
            None if forced is None else forced[index], fault)
        routing.append(chosen)
    return _norm(x, params["norm"], cfg.rms_norm_eps), jnp.stack(routing)


def forward(family, params, ids, continuations, forced=None,
            with_routing=False, fault: str = ""):
    """Logits at every distinct position, in :func:`program`'s order: one
    full forward over each whole sequence (the shared ids, then its own
    continuation), one sequence after the other; the head over the shared
    rows of the first and the own rows of each. ``forced`` ``(routers,
    rows, k)`` in the same order of rows holds the routing to the ids
    given. ``with_routing`` adds the chosen ids."""
    import jax
    import jax.numpy as jnp

    cfg = family.expander
    shared = ids.shape[0]
    own = continuations.shape[1]

    def of_sequence(b, rows):
        """``rows`` ``(routers, shared + SEQUENCES * own, k)`` as sequence
        ``b``'s ``(routers, shared + own, k)``."""
        return jnp.concatenate(
            [rows[:, :shared], jax.lax.dynamic_slice_in_dim(
                rows, shared + b * own, own, 1)], axis=1)

    def whole(b):
        return trunk(cfg, params,
                     jnp.concatenate([ids, continuations[b]]),
                     None if forced is None else of_sequence(b, forced),
                     fault)

    with jax.default_matmul_precision("highest"):
        n, chosen = jax.lax.map(whole, jnp.arange(continuations.shape[0]))
        rows = jnp.concatenate(
            [n[0, :shared], n[:, shared:].reshape(-1, n.shape[-1])])
        head = params["lm_head"]["kernel"]
        block = _row_block(rows.shape[0])
        logits = jax.lax.map(
            lambda part: part @ _w(head),
            rows.reshape(-1, block, rows.shape[-1])).reshape(
                rows.shape[0], -1)
    if not with_routing:
        return logits
    return logits, jnp.concatenate(
        [chosen[0, :, :shared]] + [chosen[b, :, shared:]
                                   for b in range(chosen.shape[0])], axis=1)


#: the wrong programs' readings, by name: the keyword arguments of
#: :func:`program`
CONTROLS = (
    ("control", {"control": True}),
    ("no_identity_term", {"no_identity_term": True}),
    ("no_held_experts", {"no_held_experts": True}),
    ("no_shortcut", {"no_shortcut": True}),
    ("no_q_scale", {"no_q_scale": True}),
    ("no_kv_scale", {"no_kv_scale": True}),
)
HELD = "_vs_reference_held_to_the_programs_routing_relative_rms"
#: the timed path's positions (2 048 + 64 + 256): what the readings are
#: taken at unless ``--size`` says otherwise
TIMED_POSITIONS = 2368


#: the share of (token, router) pairs whose chosen set is not the
#: reference's: the program's own, or ``<control>_`` in front
DIFFER = "routing_pairs_that_differ_share"


def pairs_that_differ(chose, own) -> float:
    """The share of (token, router) pairs of host ids ``(routers, rows,
    k)`` whose chosen SET differs."""
    import numpy as np

    return float(np.mean(np.any(
        np.sort(chose, -1) != np.sort(own, -1), axis=-1)))


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
    changes, over the routers (a property of the seeded weights)."""
    import jax
    import jax.numpy as jnp

    n = jax.random.normal(jax.random.key(seed), (n_rows, cfg.hidden_size))
    changed = []
    for index in range(cfg.num_layers // 2):
        p = double_layer_params(params, index)["mlp"]
        scores = jax.nn.softmax(n @ _w(p["router"]), axis=-1)
        _, with_bias = jax.lax.top_k(
            scores + _w(p["e_score_correction_bias"]),
            cfg.num_experts_per_tok)
        _, without = jax.lax.top_k(scores, cfg.num_experts_per_tok)
        changed.append(jnp.mean(jnp.any(
            jnp.sort(with_bias, -1) != jnp.sort(without, -1), axis=-1)))
    return float(jnp.mean(jnp.stack(changed)))


def picks_by_kind(cfg, chosen) -> dict:
    """Mean picks a (token, router) pair by kind, of host ids ``(routers,
    rows, k)``: on a held expert, on an identity expert, on an absent one,
    and the share of pairs with no held expert."""
    import numpy as np

    chosen = np.asarray(chosen)
    first, count = cfg.experts
    held = (chosen >= first) & (chosen < first + count)
    identity = chosen >= cfg.num_experts - cfg.zero_experts
    pairs = chosen.shape[0] * chosen.shape[1]
    return {"held": float(held.sum() / pairs),
            "identity": float(identity.sum() / pairs),
            "absent": float((~held & ~identity).sum() / pairs),
            "pairs_with_no_held_expert_share":
                float(np.mean(~held.any(-1)))}


def read_stage(bench, config: dict, stage: str, keep: str, seed=None,
               size=None, apart: bool = False) -> dict:
    """ONE process's share of the readings beside the tolerance (see the
    module's text), at the configuration's ``weight_seed`` or another:
    weights and ids both come from it. Stage ``readings``: the program, the
    reference and the reference held to the program's routing, each pulled
    to the host as it ends and its reading printed on stderr at once; the
    two references are left in ``keep`` as ``.npy``. Any other stage is a
    name of :data:`CONTROLS`: that wrong program alone, as the FIRST and
    only program-sized executable of its process, read against the two
    files. ``apart``: the program through :func:`staged`."""
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
        lower, chose = host("ran", run(**dict(CONTROLS)[stage]))
        out = {}
        for name, against in (("_vs_reference_relative_rms", "want"),
                              (HELD, "held")):
            out[stage + name] = relative_rms(lower, np.load(
                os.path.join(keep, against + ".npy"), mmap_mode="r"))
            say(f"{stage + name} {out[stage + name]:.6g}")
        name = stage + "_" + DIFFER
        out[name] = pairs_that_differ(
            chose, np.load(os.path.join(keep, "own.npy")))
        say(f"{name} {out[name]:.6g}")
        return out
    cfg = family.expander
    out = {
        "positions": int(sum(split(ids.shape[0] + continuations.shape[1]))),
        "sequences": int(continuations.shape[0]), "seed": seed,
        "executables": "chunks and steps apart" if apart else "one",
        "selection_bias_changes_the_choice_share": bias_changes_share(
            cfg, params),
    }
    got, chose = host("program", run())
    out["rows_compared"] = int(got.shape[0])
    out["picks_a_pair"] = picks_by_kind(cfg, chose)
    want, own = host("reference", jax.jit(lambda p, i, c: forward(
        family, p, i, c, with_routing=True))(params, ids, continuations))
    np.save(os.path.join(keep, "want.npy"), want)
    np.save(os.path.join(keep, "own.npy"), own)
    for name, value in (
            ("program_vs_reference_relative_rms", relative_rms(got, want)),
            (DIFFER, pairs_that_differ(chose, own)),
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
    recorded = bench.read("reference", args.config + ".json")
    # (the limit, the program's reading it is over, the controls' it is
    # under): overall, and held to the program's routing
    limits = [(recorded.get("tolerance_relative_rms"),
               "program_vs_reference_relative_rms",
               "_vs_reference_relative_rms"),
              (recorded.get("tolerance_held_to_routing_relative_rms"),
               "program_vs_reference_held_to_its_routing_relative_rms",
               HELD)]
    seeds = args.seed or [None]
    argvs = [[os.path.abspath(__file__), "--config", args.config,
              "--size", str(args.size)]
             + ([] if seed is None else ["--seed", str(seed)])
             + (["--staged"] if args.staged else []) for seed in seeds]
    keeps = [tempfile.mkdtemp(prefix="longcat-flash-ref-") for _ in seeds]
    outs: list = [{} for _ in seeds]
    passed = True
    try:
        # every seed's own readings first: they are what the limit is set
        # from, and a control that hangs costs its whole timeout
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
            out["passed"] = "failed" not in out and all(
                out[own] < limit < min([out[n + theirs] for n in names]
                                       or [float("inf")])
                for limit, own, theirs in limits if limit is not None)
            passed &= out["passed"]
            print(json.dumps(out), flush=True)
    finally:
        for keep in keeps:
            shutil.rmtree(keep, ignore_errors=True)
    sys.exit(0 if passed else 1)

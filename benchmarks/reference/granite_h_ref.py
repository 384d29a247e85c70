"""The plain reference of the prompt expander's language model when it is a
granite-4.0-h-small share (``family.expander``; Hugging Face
``GraniteMoeHybridForCausalLM``, ``model_type: granitemoehybrid``): one
forward pass over all positions of ONE sequence in float32 at the highest
matmul precision, in plain ``jax.numpy``: no cache, no kernel, no batch, no
chunks, the state-space recurrence **token by token**, the experts a plain
loop over the held ids, the head the table's own slice transposed. It reads
the parameter tree the program's ``models/lm.py`` holds and the widths of the
same ``LMConfig``, and shares no code with it, with ``ops/`` or with any
other reference of this directory.

``N(x; w) = x / sqrt(mean(x^2) + eps) * w``. The five equations, as
``GraniteMoeHybridDecoderLayer`` has them (``E`` the token table, of which
this chip holds a slice of rows)::

    h      = embedding_multiplier * E[token]
    h      = h + residual_multiplier * M(N_in(h))      # the layer's ONE mixer
    n      = N_post(h)
    h      = h + residual_multiplier * (R(n) + S(n))   # routed + shared
    logits = (N_f(h) E^T) / logits_scaling             # the table again

Which mixer a layer has is read off its leaves (a layer that holds ``ssm``
is a state-space layer, one that holds ``attn`` an attention layer), not
off the program's list of kinds.

*M, a state-space layer* (``GraniteMoeHybridMambaLayer``): ``[z | xBC | dt]
= n W_in`` (``z`` heads x head width, ``xBC = [x | B | C]`` with ``B`` and
``C`` groups x state width, ``dt`` one a head); ``xBC = silu(conv(xBC) +
b)``, causal and depth-wise over ``taps`` taps (zeros before position 0);
head ``j`` reads the ``B`` and ``C`` of group ``j // (heads / groups)``;
``dt_j = softplus(dt_j + dt_bias_j)`` (not clamped), ``a_j = exp(-exp(
A_log_j) dt_j)``, the state ``S_j`` (head width x state width, from zero)
``S_j <- a_j S_j + dt_j x_j B^T``, ``y_j = S_j C + D_j x_j``; ``g = y *
silu(z)``, THEN one RMS over each group's channels (all of them at one
group) times a weight a channel; ``out = g W_out``.

*M, an attention layer* (``GraniteMoeHybridAttention``,
``position_embedding_type: nope``): ``q``, ``k``, ``v`` from ``n``, NOTHING
rotated and no other position signal; query head ``j`` attends KV head ``j
// (heads / kv heads)``; ``softmax(q . k * attention_multiplier)`` causal
over every earlier position (the multiplier is the published 1/128, not
``head_dim ** -0.5``); the heads through ``W_o``. No bias, no gate, no query
or key norm.

*R* (``GraniteMoeHybridTopKGating``, ``...ParallelExperts``): ``l = n W_r``
over ALL the router's outputs, no bias; the ``k`` largest ``l``; their
weights a float32 softmax over those ``k`` logits alone; ``E_e(n) = (silu(n
W_g) * (n W_u)) W_d``. Only the held range adds its part (the chip's share
of a stated deployment: what the absent experts would add is left out, here
and in the program alike). *S*: the same SwiGLU at the shared width,
ungated, always on.

Departures from the published code, each without effect on the numbers:
its ``input_linear`` holds ``W_g`` and ``W_u`` side by side in one leaf
(this tree has two); its state-space layer runs a chunk-wise scan where
this file runs the recurrence it equals; the weights are seeded
(``components/unet_clip_vae_lm_granite_h.py``), not a checkpoint's.

Held experts are upcast to float32 one at a time, attention runs a block of
query rows at a time and the head a block of rows at a time, so the
reference fits beside the bf16 weights.

What is compared is what the timed path runs: ``SEQUENCES`` sequences that
share their first positions (the instruction and the prompt) and then
differ. :func:`forward` is one full forward of each WHOLE sequence, one
after the other; :func:`program` is the prefix's chunk (attention over what
it writes, the state-space layers chunk-wise from zero states), a copy of
the cache, the prompt's chunk, a fork into ``SEQUENCES`` (the ONE attention
layer's keys and values shared, every state and every kept row copied once
a sequence) and one decode step over all of them a position. Both give
float32 logits at every distinct position: the shared rows once, then each
sequence's own rows.

    python3 benchmarks/reference/granite_h_ref.py --config sd15_granite_h_expand

prints the readings ``reference/<config>.json`` keeps beside the tolerance,
at the timed path's 2 368 positions unless ``--size`` says otherwise: the
share of (token, router) pairs whose chosen experts differ between program
and reference, the program against the reference held to the program's
choices (routing flips apart from arithmetic error), the picks by kind
(held, absent), how often the arg-max of a row of logits is the row's own
input token (a tied head's pull), and those readings for the wrong programs
of :data:`CONTROLS`. The program must meet both limits of that file
(``tolerance_relative_rms`` overall, ``tolerance_held_to_routing_relative_
rms`` held to its routing) and each wrong program must miss ONE of them at
least, or the exit code is 1 (``told_apart_by`` says which it missed: seven
miss both by far; the router's product in bfloat16 flips a few more
near-ties than bfloat16 operands flip anyway, so overall it reads 4-9 %
over the program's own reading at ONE seed and inside the program's range
ACROSS seeds, and is told apart by the limit held to the program's
routing, where it reads twice the program's). The command itself stays
off JAX and runs a PROCESS A STAGE (:func:`read_stages`), as its siblings
do and for their reason: a second program-sized executable in one process
has hung this device.
"""

from __future__ import annotations

import math

COMPONENT = "expander"
CONTROL = "the router's product with bfloat16 operands (router_dtype) " \
          "where the program multiplies in float32 at the highest precision"
#: sequences forked from the one prefill: the images of the cell's request
SEQUENCES = 4
#: the timed path's positions (2 048 + 64 + 256): what the readings are
#: taken at unless ``--size`` says otherwise
TIMED_POSITIONS = 2368


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


def published(cfg) -> dict:
    """The four scalars of the forward pass under their published names."""
    return {"embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attention_scale,
            "logits_scaling": 1.0 / cfg.logit_multiplier}


# -- the program, as the timed path runs it -----------------------------------

def _faulted(params, cfg, no_held_experts: bool, no_shared_expert: bool,
             no_mlp_residual_multiplier: bool):
    """``params`` with every held expert's ``w_down`` zero (the held
    experts' part of every routed sum is then zero), every shared expert's
    ``down_proj`` zero, or both of them divided by the residual multiplier
    (what the MLP sublayer adds is then NOT scaled, the token mixer's
    still is: the one scalar left off one sublayer alone, which no key of
    the program can say)."""
    import jax.numpy as jnp

    if not (no_held_experts or no_shared_expert
            or no_mlp_residual_multiplier):
        return params
    out = dict(params)
    for name, layer in params.items():
        mlp = layer.get("mlp") if name.startswith("layers_") else None
        if mlp is None or "experts" not in mlp:
            continue
        down, shared = mlp["experts"]["w_down"], \
            mlp["shared_expert"]["down_proj"]["kernel"]
        if no_held_experts:
            down = jnp.zeros_like(down)
        if no_shared_expert:
            shared = jnp.zeros_like(shared)
        if no_mlp_residual_multiplier:
            down = (down.astype(jnp.float32)
                    / cfg.residual_multiplier).astype(down.dtype)
            shared = (shared.astype(jnp.float32)
                      / cfg.residual_multiplier).astype(shared.dtype)
        out[name] = {**layer, "mlp": {
            **mlp, "experts": {**mlp["experts"], "w_down": down},
            "shared_expert": {**mlp["shared_expert"],
                              "down_proj": {"kernel": shared}}}}
    return out


def stages(family, policy, control: bool = False,
           no_mlp_residual_multiplier: bool = False,
           scores_by_root_head_dim: bool = False, rotated: bool = False,
           norm_before_gate: bool = False, no_held_experts: bool = False,
           no_shared_expert: bool = False, logits_not_divided: bool = False):
    """What the timed path runs at the timed sizes, as the two executables
    it runs them as (:func:`program` joins them, :func:`staged` runs them
    apart): the prefix's prefill as one chunk, a copy of the cache as it
    stands at the prefix's last token (the kept snapshot: the attention
    layer's keys and values, every state and every kept row), the prompt
    chunk's prefill against that copy, a fork of the cache into
    ``SEQUENCES``, then every further position decoded one step over all
    sequences at a time, teacher-forced on the seeded continuations.
    Logits ``(prefix + prompt + SEQUENCES * decoded, vocabulary)``: the
    shared rows, then each sequence's. The wrong programs, each a fault the
    comparison must see: ``control`` makes the router's product in
    bfloat16; ``no_mlp_residual_multiplier`` leaves the residual multiplier
    off the MLP sublayer alone; ``scores_by_root_head_dim`` scales the
    scores by ``head_dim ** -0.5``; ``rotated`` turns queries and keys by
    their position (theta 1e4); ``norm_before_gate`` norms a state-space
    read-out and then gates it; ``no_held_experts`` zeroes the held
    experts' part; ``no_shared_expert`` the shared expert;
    ``logits_not_divided`` leaves the logits unscaled."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.models import configs, lm

    cfg = right = family.expander
    if scores_by_root_head_dim:
        cfg = dataclasses.replace(cfg, attention_scale=0.0)
    if rotated:
        cfg = dataclasses.replace(cfg, rope_full=configs.RopeConfig())
    if norm_before_gate:
        cfg = dataclasses.replace(cfg, ssm_norm_before_gate=True)
    if logits_not_divided:
        cfg = dataclasses.replace(cfg, logit_multiplier=1.0)
    module = lm.DecoderLM(
        cfg, dtype=policy.compute_dtype,
        router_dtype=jnp.bfloat16 if control else jnp.float32)

    def held(params):
        return {"params": _faulted(params, right, no_held_experts,
                                   no_shared_expert,
                                   no_mlp_residual_multiplier)}

    def prefills(params, ids, decoded: int):
        """The two chunks and the fork: (their logits, the forked cache,
        the experts their rows chose ``(layers, rows, k)``)."""
        size = ids.shape[0] + decoded
        prefix = split(size)[0]
        cache = lm.empty_cache(cfg, size, policy.compute_dtype)
        apply = lambda t, start, c: module.apply(   # noqa: E731
            held(params), t, jnp.int32(start), jnp.int32(t.shape[0]), c)
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
                held(params), tokens, position, jnp.int32(SEQUENCES),
                cache, sequences=True)
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
    """``N(x; w)`` over the last axis."""
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _w(p["scale"])


def _row_block(tokens: int, most: int = 256) -> int:
    """The largest divisor of ``tokens`` at or under ``most``."""
    return max(b for b in range(1, most + 1) if tokens % b == 0)


def _turned(x, theta: float = 1e4):
    """``x`` ``(T, heads, d)`` turned by its position over the whole head
    width, dim ``i`` paired with dim ``i + d / 2``: what the published
    model does NOT do (the fault ``"rotated"``)."""
    import jax.numpy as jnp

    tokens, _, dim = x.shape
    half = dim // 2
    inverse = jnp.asarray(
        [theta ** (-2.0 * i / dim) for i in range(half)], jnp.float32)
    angles = jnp.arange(tokens, dtype=jnp.float32)[:, None] * inverse
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg, n, p, scale: float, fault: str = ""):
    """Causal attention over the whole sequence, a group of query heads a
    KV head, nothing rotated, the scores times ``scale``; a block of query
    rows at a time."""
    import jax
    import jax.numpy as jnp

    tokens = n.shape[0]
    kv, dim = cfg.num_kv_heads, cfg.head_dim
    heads = p["q_proj"]["kernel"].shape[1] // dim
    q = (n @ _w(p["q_proj"]["kernel"])).reshape(tokens, heads, dim)
    k = (n @ _w(p["k_proj"]["kernel"])).reshape(tokens, kv, dim)
    v = (n @ _w(p["v_proj"]["kernel"])).reshape(tokens, kv, dim)
    if fault == "rotated":
        q, k = _turned(q), _turned(k)
    q = q.reshape(tokens, kv, heads // kv, dim)     # head j: KV head j // n
    j = jnp.arange(tokens)[None, :]
    block = _row_block(tokens)

    def rows(at):
        i = at + jnp.arange(block)[:, None]
        scores = jnp.einsum(
            "ignd,jgd->gnij", jax.lax.dynamic_slice_in_dim(q, at, block),
            k) * scale
        probs = jax.nn.softmax(
            jnp.where((i - j >= 0)[None, None], scores, -jnp.inf), -1)
        return jnp.einsum("gnij,jgd->ignd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, tokens, block))
    return out.reshape(tokens, heads * dim) @ _w(p["o_proj"]["kernel"])


def state_space(cfg, n, p, fault: str = ""):
    """A state-space layer's mixer over all positions: the state updated
    one token at a time from zero. ``fault`` ``"norm_before_gate"``: the
    read-out normed and then gated."""
    import jax
    import jax.numpy as jnp

    tokens = n.shape[0]
    heads, dim = cfg.ssm_num_heads, cfg.ssm_head_dim
    groups, width = cfg.ssm_num_groups, cfg.ssm_state_size
    taps = p["conv_kernel"].shape[0]
    inner, wide = heads * dim, groups * width
    proj = n @ _w(p["in_proj"]["kernel"])       # [z | x | B | C | dt]
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * wide],
                  proj[:, 2 * inner + 2 * wide:])
    kernel = _w(p["conv_kernel"])                       # (taps, channels)
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32), xbc])
    xbc = jax.nn.silu(sum(kernel[j][None, :] * padded[j:j + tokens]
                          for j in range(taps)) + _w(p["conv_bias"]))
    x = xbc[:, :inner].reshape(tokens, heads, dim)
    per = heads // groups       # head j reads group j // per
    b = jnp.repeat(xbc[:, inner:inner + wide].reshape(
        tokens, groups, width), per, axis=1)
    c = jnp.repeat(xbc[:, inner + wide:].reshape(
        tokens, groups, width), per, axis=1)
    dt = jax.nn.softplus(dt + _w(p["dt_bias"]))         # (T, heads)
    decay = jnp.exp(-jnp.exp(_w(p["A_log"])) * dt)

    def token(state, row):
        x_t, b_t, c_t, dt_t, a_t = row
        state = a_t[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((heads, dim, width), jnp.float32),
                        (x, b, c, dt, decay))
    y = (y + _w(p["D"])[:, None] * x).reshape(tokens, inner)
    gate = jax.nn.silu(z)

    def normed(g):
        g = g.reshape(tokens, groups, inner // groups)
        return (g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                             + cfg.rms_norm_eps)).reshape(tokens, inner) \
            * _w(p["norm"]["scale"])

    g = normed(y) * gate if fault == "norm_before_gate" else normed(y * gate)
    return g @ _w(p["out_proj"]["kernel"])


def _swiglu(n, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(n @ _w(w_gate)) * (n @ _w(w_up))) @ _w(w_down)


def shared_expert(n, p):
    return _swiglu(n, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                   p["down_proj"]["kernel"])


def route(cfg, n, p, forced=None):
    """(chosen ids (T, k) over ALL the router's outputs, their weights (T,
    k)): the ``k`` largest logits, weighed by a float32 softmax over those
    ``k`` alone. ``forced`` gives the ids instead (the diagnostic
    reading); their weights are still this side's own."""
    import jax
    import jax.numpy as jnp

    logits = n @ _w(p["router"])
    top, chosen = jax.lax.top_k(logits, cfg.num_experts_per_tok)
    if forced is not None:
        chosen = forced
        top = jnp.take_along_axis(logits, chosen, axis=-1)
    return chosen, jax.nn.softmax(top, axis=-1)


def routed_sum(n, chosen, weights, experts, held):
    """``R(n)``: a plain loop over the held ids ``held`` ``(first,
    count)``, each expert upcast alone and applied to every token, weighed
    by what the router gave it, zero where it was not chosen."""
    import jax
    import jax.numpy as jnp

    first, count = held

    def one(at, acc):
        w_e = jnp.sum(jnp.where(chosen == first + at, weights, 0.0), axis=-1)
        return acc + w_e[:, None] * _swiglu(
            n, experts["w_gate"][at], experts["w_up"][at],
            experts["w_down"][at])

    return jax.lax.fori_loop(0, count, one, jnp.zeros_like(n))


def layer_forward(cfg, x, p, m, forced=None, fault: str = ""):
    """(x after one layer over ``(T, C)``, the ids its router chose)."""
    eps = cfg.rms_norm_eps
    n = _norm(x, p["input_norm"], eps)
    if "ssm" in p:
        mixed = state_space(cfg, n, p["ssm"], fault)
    else:
        scale = cfg.head_dim ** -0.5 if fault == "scores_by_root_head_dim" \
            else m["attention_multiplier"]
        mixed = attention(cfg, n, p["attn"], scale, fault)
    h = x + m["residual_multiplier"] * mixed
    n = _norm(h, p["post_attention_norm"], eps)
    chosen, weights = route(cfg, n, p["mlp"], forced)
    added = routed_sum(n, chosen, weights, p["mlp"]["experts"], cfg.experts) \
        + shared_expert(n, p["mlp"]["shared_expert"])
    if fault != "mlp_unscaled":
        added = m["residual_multiplier"] * added
    return h + added, chosen


def trunk(cfg, params, ids, forced=None, fault: str = ""):
    """(the final norm's output ``(T, C)``, the ids chosen ``(layers, T,
    k)``) of one whole sequence. The table is the held slice's."""
    import jax.numpy as jnp

    m = published(cfg)
    x = m["embedding_multiplier"] * params["embed_tokens"]["embedding"][
        ids - cfg.vocab[0]].astype(jnp.float32)
    routing = []
    for layer in range(sum(name.startswith("layers_") for name in params)):
        x, chosen = layer_forward(
            cfg, x, params[f"layers_{layer}"], m,
            None if forced is None else forced[layer], fault)
        routing.append(chosen)
    return _norm(x, params["norm"], cfg.rms_norm_eps), jnp.stack(routing)


def forward(family, params, ids, continuations, forced=None,
            with_routing=False, fault: str = ""):
    """Logits at every distinct position, in :func:`program`'s order: one
    full forward over each whole sequence (the shared ids, then its own
    continuation), one sequence after the other; the head, which is the
    table's own slice transposed, over the shared rows of the first and the
    own rows of each, a block of rows at a time. ``forced`` ``(layers,
    rows, k)`` in the same order of rows holds the routing to the ids
    given. ``with_routing`` adds the chosen ids. ``fault``: the reference
    itself made wrong in one way (``"mlp_unscaled"``,
    ``"scores_by_root_head_dim"``, ``"rotated"``, ``"norm_before_gate"``),
    for the tests: the program with the same fault must then meet it."""
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
                     None if forced is None else of_sequence(b, forced),
                     fault)

    with jax.default_matmul_precision("highest"):
        n, chosen = jax.lax.map(whole, jnp.arange(continuations.shape[0]))
        rows = jnp.concatenate(
            [n[0, :shared], n[:, shared:].reshape(-1, n.shape[-1])])
        table = params["embed_tokens"]["embedding"]     # tied: the head
        block = _row_block(rows.shape[0])
        logits = jax.lax.map(
            lambda part: part @ _w(table).T,
            rows.reshape(-1, block, rows.shape[-1])).reshape(
                rows.shape[0], -1) / published(cfg)["logits_scaling"]
    if not with_routing:
        return logits
    return logits, jnp.concatenate(
        [chosen[0, :, :shared]] + [chosen[b, :, shared:]
                                   for b in range(chosen.shape[0])], axis=1)


# -- the readings -------------------------------------------------------------

#: the wrong programs' readings, by name: the keyword arguments of
#: :func:`program`
CONTROLS = tuple((name, {name: True}) for name in (
    "control", "no_mlp_residual_multiplier", "scores_by_root_head_dim",
    "rotated", "norm_before_gate", "no_held_experts", "no_shared_expert",
    "logits_not_divided"))
#: what the reference can be made to say wrongly (:func:`forward`'s
#: ``fault``), by the wrong program that then meets it
FAULTS = {"no_mlp_residual_multiplier": "mlp_unscaled",
          "scores_by_root_head_dim": "scores_by_root_head_dim",
          "rotated": "rotated", "norm_before_gate": "norm_before_gate"}
HELD = "_vs_reference_held_to_the_programs_routing_relative_rms"
#: the share of (token, router) pairs whose chosen set is not the
#: reference's: the program's own, or ``<control>_`` in front
DIFFER = "routing_pairs_that_differ_share"


def pairs_that_differ(chose, own) -> float:
    """The share of (token, router) pairs of host ids ``(layers, rows, k)``
    whose chosen SET differs."""
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


def own_token_pull(cfg, logits, ids, continuations) -> dict:
    """What a tied head makes of a row's OWN input token, over host logits
    in :func:`program`'s order of rows: how often it is the row's arg-max,
    its mean logit and the spread (deviation) of a row's logits."""
    import numpy as np

    tokens = np.concatenate([np.asarray(ids).reshape(-1),
                             np.asarray(continuations).reshape(-1)]) \
        - cfg.vocab[0]
    top = own = 0.0
    for lo, hi in _blocks(logits.shape[0]):
        rows = np.asarray(logits[lo:hi], np.float64)
        mine = rows[np.arange(hi - lo), tokens[lo:hi]]
        top += float(np.sum(np.argmax(rows, -1) == tokens[lo:hi]))
        own += float(np.sum(mine))
    return {"own_token_is_argmax_share": top / logits.shape[0],
            "own_token_logit_mean": own / logits.shape[0],
            "logits_deviation_a_row": float(np.mean(
                np.std(np.asarray(logits[:256], np.float64), axis=-1)))}


def picks_by_kind(cfg, chosen) -> dict:
    """Mean picks a (token, router) pair by kind, of host ids ``(layers,
    rows, k)``: on a held expert, on an absent one, and the share of pairs
    with no held expert."""
    import numpy as np

    chosen = np.asarray(chosen)
    first, count = cfg.experts
    held = (chosen >= first) & (chosen < first + count)
    pairs = chosen.shape[0] * chosen.shape[1]
    return {"held": float(held.sum() / pairs),
            "absent": float((~held).sum() / pairs),
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
    from benchmarks.harness import device, files, weights

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
            ("token_agreement_argmax_share", argmax_agreement(got, want)),
            ("reference_rms", float(np.sqrt(np.mean(
                want.astype(np.float64) ** 2))))):
        out[name] = value
        say(f"{name} {value:.6g}")
    out["tied_head"] = own_token_pull(cfg, want, ids, continuations)
    say(f"tied_head {out['tied_head']}")
    out["finite"] = bool(np.isfinite(got).all() and np.isfinite(want).all())
    del want
    held, = host("reference held to the program's routing", [jax.jit(
        lambda p, i, c, f: forward(family, p, i, c, forced=f))(
            params, ids, continuations, chose)])
    np.save(os.path.join(keep, "held.npy"), held)
    name = "program_vs_reference_held_to_its_routing_relative_rms"
    out[name] = relative_rms(got, held)
    say(f"{name} {out[name]:.6g}")
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
    keeps = [tempfile.mkdtemp(prefix="granite-h-ref-") for _ in seeds]
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
            # the limits each wrong program read over: one at least
            out["told_apart_by"] = {
                n: [own for limit, own, theirs in limits
                    if limit is not None and out[n + theirs] > limit]
                for n in names if "failed" not in out}
            out["passed"] = "failed" not in out and out["finite"] and all(
                out[own] < limit for limit, own, _ in limits
                if limit is not None) and all(out["told_apart_by"].values())
            passed &= out["passed"]
            print(json.dumps(out), flush=True)
    finally:
        for keep in keeps:
            shutil.rmtree(keep, ignore_errors=True)
    sys.exit(0 if passed else 1)

"""The plain reference of the prompt expander's language model when it is an
Olmo-Hybrid-7B share (``family.expander``; ``model_type: olmo_hybrid``: a
dense hybrid, gated delta-rule layers three in four beside unrotated full
attention): one forward pass over all positions of ONE sequence in float32
at the highest matmul precision, in plain ``jax.numpy``: no cache, no
kernel, no batch, no chunks, the delta rule **token by token**. It reads
the same parameter tree the program's ``models/lm.py`` holds and the same
``LMConfig``, and shares no code with it or with ``ops/``.

``N(x; w) = x / sqrt(mean(x^2) + eps) * w`` (a plain scale). One final
``N``, then the untied head. ``MLP(n) = W_d(silu(W_g n) * W_u n)`` in every
layer. No bias anywhere.

*Linear layer*: ``h = x + GDN(N_1(x))``, ``out = h + MLP(N_2(h))``: the
sublayers' INPUT is normed. With ``n = N_1(x)``: ``[q | k | v | z] = n
W_qkvz``, ``[b | a] = n W_ba``; ``[q | k | v]`` pass a causal depth-wise
convolution of ``taps`` taps (zeros before position 0, no bias) and SiLU;
``q`` and ``k`` are L2-normalised per head (eps 1e-6) and ``q`` scaled by
``d_k^-1/2``. Per head with state ``S`` ``(d_k, d_v)`` from zero: ``g =
-exp(A_log) softplus(a + dt_bias)``, ``beta = c sigmoid(b)`` with ``c =
linear_write_scale = 2`` (``linear_allow_neg_eigval``: the transition ``I -
beta k k^T`` has an eigenvalue ``1 - beta`` in (-1, 1)); ``S <- exp(g) S``;
``u = beta (v - S^T k)``; ``S <- S + k u^T``; ``o = S^T q``. Read-out ``N(o;
w_o)`` over the head's ``d_v`` channels times ``silu(z)``, the heads side by
side through ``W_out``.

*Full layer*: ``h = x + N_1(Attn(x))``, ``out = h + N_2(MLP(h))``: the
sublayers read the stream UN-normed and their OUTPUT is normed. ``q =
N_q(W_q x)``, ``k = N_k(W_k x)``, each norm over ALL the projection's
outputs (one RMS over ``heads * head_dim``), then cut into heads; ``v = W_v
x``; **no rotation**; ``softmax(q_j . k_j * head_dim^-1/2)`` causal over
every earlier position; the heads through ``W_o``. A head a KV head.

Which layer is which comes from ``LMConfig.layer_types``; that a linear
layer norms before and a full layer after, the factor 2, the absent rotary
table and the norms' extent are written out HERE, not read from the
configuration's keys: a program that read its keys wrong must miss this
file.

What is compared is what the timed path runs: ``SEQUENCES`` sequences that
share their first positions (the instruction and the prompt) and then
differ. :func:`forward` is one full forward of each WHOLE sequence, one
after the other, attention and the head a block of rows at a time so that
it fits beside the bfloat16 weights; :func:`program` is the prefix's chunk
(chunk-wise delta rule), a copy of the cache, the prompt's chunk, a fork
into ``SEQUENCES`` (keys and values shared, every state and every kept row
copied once a sequence) and one decode step over all of them a position.
Both give float32 logits at every distinct position: the shared rows once,
then each sequence's own rows.

    python3 benchmarks/reference/olmo_hybrid_ref.py --config sd15_olmo_hybrid_expand

prints the readings ``reference/<config>.json`` keeps beside the tolerance,
at the timed path's 2 368 positions unless ``--size`` says otherwise, and
those of the controls of :data:`CONTROLS`, each a fault the comparison must
see. There are two limits. ``tolerance_relative_rms`` is against the
reference as written above; what the program reads there is the roundoff of
its bfloat16 matmul operands, which covers a fault as small as a state
kept in bfloat16. So the reference is run once more HELD TO THE PROGRAM'S
OPERAND PRECISION (:func:`forward` with ``operands``: every matmul's
activations, the queries, keys, values and attention weights rounded to the
policy's compute dtype where the program rounds them, everything else
float32 as before), and ``tolerance_held_to_operand_precision_relative_rms``
is against that: roundoff apart, what is left is the mathematics. The
program must meet both and each control must miss the second, or the exit
code is 1. The command itself stays off JAX and runs a PROCESS A STAGE
(:func:`read_stages`), as ``gigachat35_ref.py`` does and for its reason.
"""

from __future__ import annotations

import math

COMPONENT = "expander"
CONTROL = "the program's dynamic int8 linears (quant_linears) on the " \
          "delta mixers' qkvz, ba and out projections, attention's four, " \
          "the SwiGLUs' three and the head"
#: sequences forked from the one prefill: the images of the cell's request
SEQUENCES = 4
#: the timed path's positions (2 048 + 64 + 256): what the readings are
#: taken at unless ``--size`` says otherwise
TIMED_POSITIONS = 2368
#: what the reference itself says of the model (see the module's text)
WRITE_SCALE = 2.0
QK_EPS = 1e-6


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


# -- the program, as the timed path runs it -----------------------------------

def _placed(params, cfg):
    """``params`` with each layer's sublayer norms under the names
    ``cfg``'s placement reads them by (a control that norms a layer on its
    other side, or on both, reads the layer's one pair of weights there),
    and a per-head query or key norm's weight cut to one head's."""
    out = dict(params)
    for layer, placement in enumerate(cfg.sublayer_norms):
        p = dict(params[f"layers_{layer}"])
        for name in ("input_norm", "post_attention_norm"):
            before, after = p.pop(name, None), p.pop(name + "_2", None)
            held = after if before is None else before
            if placement != "post":
                p[name] = held
            if placement != "pre":
                p[name + "_2"] = held
        if "attn" in p and cfg.qk_norm_extent == "head":
            p["attn"] = {**p["attn"], **{
                n: {"scale": p["attn"][n]["scale"][:cfg.head_dim]}
                for n in ("q_norm", "k_norm")}}
        out[f"layers_{layer}"] = p
    return out


def stages(family, policy, control: bool = False, state_bf16: bool = False,
           sigmoid_beta: bool = False, state_shared: bool = False,
           qk_norm_per_head: bool = False, rotary: bool = False,
           full_pre_normed: bool = False, linear_post_normed: bool = False,
           both_normed: bool = False):
    """What the timed path runs at the timed sizes, as the two executables
    it runs them as (:func:`program` joins them): the prefix's prefill as
    one chunk, a copy of the cache as it stands at the prefix's last token
    (the kept snapshot: keys, values, states and kept rows), the prompt
    chunk's prefill against that copy, a fork of the cache into
    ``SEQUENCES``, then every further position decoded one step over all
    sequences at a time, teacher-forced on the seeded continuations.
    Logits ``(prefix + prompt + SEQUENCES * decoded, vocabulary)``: the
    shared rows, then each sequence's. The controls: ``control`` the int8
    Linears; ``state_bf16`` keeps the recurrent states in bfloat16 between
    tokens; ``sigmoid_beta`` writes with ``sigmoid(b)``; ``state_shared``
    hands every sequence sequence 0's state before each step;
    ``qk_norm_per_head`` norms queries and keys a head at a time;
    ``rotary`` rotates them under a table; ``full_pre_normed`` norms the
    full layers' input, ``linear_post_normed`` the linear layers' output,
    ``both_normed`` every layer both ways."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.models import lm
    from stable_diffusion_webui_distributed_tpu.models.configs import (
        RopeConfig,
    )

    cfg = family.expander
    if sigmoid_beta:
        cfg = dataclasses.replace(cfg, linear_write_scale=1.0)
    if qk_norm_per_head:
        cfg = dataclasses.replace(cfg, qk_norm_extent="head")
    if rotary:
        cfg = dataclasses.replace(cfg, rope_full=RopeConfig(theta=5e5))
    for on, placement in ((full_pre_normed, "pre"),
                          (linear_post_normed, "post"),
                          (both_normed, "both")):
        if on:
            cfg = dataclasses.replace(
                cfg, norm_placement=(placement,) * cfg.num_layers)
    module = lm.DecoderLM(cfg, dtype=policy.compute_dtype,
                          quant_linears=control)

    def prefills(params, ids, decoded: int):
        """The two chunks and the fork: (their logits, the forked
        cache)."""
        size = ids.shape[0] + decoded
        prefix = split(size)[0]
        cache = lm.empty_cache(cfg, size, policy.compute_dtype)
        if state_bf16:
            cache["state"] = [x.astype(jnp.bfloat16) for x in cache["state"]]
        apply = lambda t, start, c: module.apply(   # noqa: E731
            {"params": _placed(params, cfg)}, t, jnp.int32(start),
            jnp.int32(t.shape[0]), c)
        l0, snapshot, _ = apply(ids[:prefix], 0, cache)
        cache = jax.tree_util.tree_map(jnp.copy, snapshot)
        l1, cache, _ = apply(ids[prefix:], prefix, cache)
        return jnp.concatenate([l0, l1]), kv.fork(cache, SEQUENCES, decoded)

    def decodes(params, cache, continuations, shared: int):
        """Every further position, one step over all sequences a time:
        each sequence's logits in turn."""
        def between(cache):
            if not state_shared:
                return cache
            return {**cache, "state": [jnp.broadcast_to(x[:1], x.shape)
                                       for x in cache["state"]]}

        def step(carry, tokens):
            cache, position = carry
            logits, cache, _ = module.apply(
                {"params": _placed(params, cfg)}, tokens, position,
                jnp.int32(SEQUENCES), between(cache), sequences=True)
            return (cache, position + 1), logits

        _, own = jax.lax.scan(
            step, (cache, jnp.int32(shared)), continuations.T)
        # (steps, sequences, vocabulary) -> each sequence's rows in turn
        return jnp.moveaxis(own, 1, 0).reshape(-1, own.shape[-1])

    return prefills, decodes


def program(family, policy, control: bool = False, **controls):
    """:func:`stages` as one function of ``(params, ids, continuations)``
    that gives the logits. ``control`` is the int8 Linears; ``controls``
    the other faults :func:`stages` can be given."""
    import jax.numpy as jnp

    prefills, decodes = stages(family, policy, control, **controls)

    def run(params, ids, continuations):
        shared, cache = prefills(params, ids, continuations.shape[1])
        return jnp.concatenate(
            [shared, decodes(params, cache, continuations, ids.shape[0])])

    return run


# -- the reference -----------------------------------------------------------

def _w(leaf):
    import jax.numpy as jnp

    return leaf.astype(jnp.float32)


def _norm(x, p, eps):
    """``N(x; w)`` over the last axis."""
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _w(p["scale"])


def _r(x, operands):
    """``x`` as a matmul operand of the program's: rounded to ``operands``
    (None: left float32). ``reduce_precision`` and not a cast there and
    back, which XLA may drop on a TPU (it allows itself excess precision:
    on the chip the reference so held read what the reference as written
    reads, to three digits; my chip runs, PR 59, call 2)."""
    import jax
    import jax.numpy as jnp

    if operands is None:
        return x
    info = jnp.finfo(operands)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _row_block(tokens: int, most: int = 256) -> int:
    """The largest divisor of ``tokens`` at or under ``most``."""
    return max(b for b in range(1, most + 1) if tokens % b == 0)


def attention(cfg, x, p, operands=None):
    """Causal attention over the whole sequence, a head a KV head, nothing
    rotated, queries and keys normed over the whole projection; a block of
    query rows at a time."""
    import jax
    import jax.numpy as jnp

    tokens = x.shape[0]
    heads, dim = cfg.num_kv_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    x = _r(x, operands)
    q = _r(_norm(x @ _w(p["q_proj"]["kernel"]), p["q_norm"], eps),
           operands).reshape(tokens, heads, dim)
    k = _r(_norm(x @ _w(p["k_proj"]["kernel"]), p["k_norm"], eps),
           operands).reshape(tokens, heads, dim)
    v = _r(x @ _w(p["v_proj"]["kernel"]), operands).reshape(
        tokens, heads, dim)
    j = jnp.arange(tokens)[None, :]
    block = _row_block(tokens)

    def rows(at):
        i = at + jnp.arange(block)[:, None]
        scores = jnp.einsum(
            "ihd,jhd->hij", jax.lax.dynamic_slice_in_dim(q, at, block), k) \
            * dim ** -0.5
        probs = jax.nn.softmax(
            jnp.where((i - j >= 0)[None], scores, -jnp.inf), -1)
        return jnp.einsum("hij,jhd->ihd", _r(probs, operands), v)

    out = jax.lax.map(rows, jnp.arange(0, tokens, block))
    return _r(out.reshape(tokens, heads * dim), operands) \
        @ _w(p["o_proj"]["kernel"])


def delta_mixer(cfg, n, p, operands=None):
    """(the linear mixer's output over all positions, the largest write
    strength any token of any head had): the state updated one token at a
    time from zero."""
    import jax
    import jax.numpy as jnp

    tokens = n.shape[0]
    n = _r(n, operands)
    heads = cfg.linear_num_value_heads      # a key head a value head
    kd, vd = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    taps = cfg.linear_conv_kernel
    mixed = n @ _w(p["qkvz_proj"]["kernel"])
    ba = n @ _w(p["ba_proj"]["kernel"])
    wide = heads * (2 * kd + vd)
    qkv, z = mixed[:, :wide], mixed[:, wide:]
    b, a = ba[:, :heads], ba[:, heads:]
    kernel = _w(p["conv_kernel"])                       # (taps, channels)
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, wide), jnp.float32), qkv])
    qkv = jax.nn.silu(sum(kernel[j][None, :] * padded[j:j + tokens]
                          for j in range(taps)))
    q = qkv[:, :heads * kd].reshape(tokens, heads, kd)
    k = qkv[:, heads * kd:2 * heads * kd].reshape(tokens, heads, kd)
    v = qkv[:, 2 * heads * kd:].reshape(tokens, heads, vd)

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + QK_EPS)

    q, k = l2(q) * kd ** -0.5, l2(k)
    beta = WRITE_SCALE * jax.nn.sigmoid(b)              # (T, heads)
    g = -jnp.exp(_w(p["A_log"])) * jax.nn.softplus(a + _w(p["dt_bias"]))

    def token(state, row):
        q_t, k_t, v_t, g_t, beta_t = row
        state = state * jnp.exp(g_t)[:, None, None]
        seen = jnp.einsum("hkv,hk->hv", state, k_t)
        u_t = beta_t[:, None] * (v_t - seen)
        state = state + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, out = jax.lax.scan(token, jnp.zeros((heads, kd, vd), jnp.float32),
                          (q, k, v, g, beta))
    out = _norm(out, p["norm"], cfg.rms_norm_eps) \
        * jax.nn.silu(z.reshape(tokens, heads, vd))
    return (_r(out.reshape(tokens, heads * vd), operands)
            @ _w(p["out_proj"]["kernel"]), jnp.max(beta))


def swiglu(n, p, operands=None):
    import jax

    n = _r(n, operands)
    gate = n @ _w(p["gate_proj"]["kernel"])
    up = n @ _w(p["up_proj"]["kernel"])
    return _r(jax.nn.silu(gate) * up, operands) @ _w(p["down_proj"]["kernel"])


def layer_forward(cfg, layer: int, x, p, operands=None):
    """(x after one layer over ``(T, C)``, the largest write strength seen;
    0 in a full layer)."""
    eps = cfg.rms_norm_eps
    if cfg.layer_types[layer] == "linear":      # the INPUT is normed
        mixed, beta = delta_mixer(cfg, _norm(x, p["input_norm"], eps),
                                  p["delta"], operands)
        x = x + mixed
        return x + swiglu(_norm(x, p["post_attention_norm"], eps),
                          p["mlp"], operands), beta
    # the OUTPUT is normed; the sublayers read the stream as it is
    x = x + _norm(attention(cfg, x, p["attn"], operands),
                  p["input_norm_2"], eps)
    return x + _norm(swiglu(x, p["mlp"], operands),
                     p["post_attention_norm_2"], eps), 0.0


def trunk(cfg, params, ids, operands=None):
    """(the final norm's output ``(T, C)``, the largest write strength) of
    one whole sequence."""
    import jax.numpy as jnp

    x = params["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    most = jnp.float32(0)
    for layer in range(cfg.num_layers):
        x, beta = layer_forward(cfg, layer, x, params[f"layers_{layer}"],
                                operands)
        most = jnp.maximum(most, beta)
    return _norm(x, params["norm"], cfg.rms_norm_eps), most


def forward(family, params, ids, continuations, with_beta: bool = False,
            operands=None):
    """Logits at every distinct position, in :func:`program`'s order: one
    full forward over each whole sequence (the shared ids, then its own
    continuation), one sequence after the other; the head over the shared
    rows of the first and the own rows of each, a block of rows at a time.
    ``with_beta`` adds the largest write strength any token had.
    ``operands``: the reference HELD to the program's operand precision
    (the module's text): a dtype every matmul's activations, the queries,
    keys, values and attention weights are rounded to."""
    import jax
    import jax.numpy as jnp

    cfg = family.expander
    shared = ids.shape[0]

    def whole(b):
        return trunk(cfg, params, jnp.concatenate([ids, continuations[b]]),
                     operands)

    with jax.default_matmul_precision("highest"):
        n, beta = jax.lax.map(whole, jnp.arange(continuations.shape[0]))
        rows = jnp.concatenate(
            [n[0, :shared], n[:, shared:].reshape(-1, n.shape[-1])])
        head = params["lm_head"]["kernel"]
        block = _row_block(rows.shape[0])
        logits = jax.lax.map(
            lambda part: _r(part, operands) @ _w(head),
            rows.reshape(-1, block, rows.shape[-1])).reshape(
                rows.shape[0], -1)
    return (logits, jnp.max(beta)) if with_beta else logits


# -- the readings -------------------------------------------------------------

#: the controls' readings, by name: the keyword arguments of :func:`program`
CONTROLS = tuple((name, {name: True}) for name in (
    "control", "state_bf16", "sigmoid_beta", "state_shared",
    "qk_norm_per_head", "rotary", "full_pre_normed", "linear_post_normed",
    "both_normed"))
READING = "_vs_reference_relative_rms"
HELD = "_vs_reference_held_to_the_programs_operand_precision_relative_rms"
OWN_HELD = "program_vs_reference_held_to_its_operand_precision_relative_rms"


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


def read_stage(bench, config: dict, stage: str, keep: str, seed=None,
               size=None) -> dict:
    """ONE process's share of the readings beside the tolerance, at the
    configuration's ``weight_seed`` or another: weights and ids both come
    from it. Stage ``readings``: the program, the reference and the
    reference held to the program's operand precision, each pulled to the
    host as it ends and its reading printed on stderr at once; the two
    references are left in ``keep`` as ``.npy``. Any other stage is a name
    of :data:`CONTROLS`: that control alone, as the FIRST and only
    program-sized executable of its process, read against the two files."""
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

    def host(step, array):
        """The array on the host, its device copy dropped."""
        out = np.asarray(jax.block_until_ready(array))
        array.delete()
        say(step)
        return out

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
        lower = host("ran", jax.jit(program(
            family, policy, **dict(CONTROLS)[stage]))(
                params, ids, continuations))
        out = {}
        for name, against in ((READING, "want"), (HELD, "held")):
            out[stage + name] = relative_rms(lower, np.load(
                os.path.join(keep, against + ".npy"), mmap_mode="r"))
            say(f"{stage + name} {out[stage + name]:.6g}")
        return out
    out = {"positions": int(ids.shape[0] + continuations.shape[1]),
           "sequences": int(continuations.shape[0]), "seed": seed}
    got = host("program", jax.jit(program(family, policy))(
        params, ids, continuations))
    want, beta = jax.jit(lambda p, i, c: forward(
        family, p, i, c, with_beta=True))(params, ids, continuations)
    out["reference_write_strength_max"] = float(beta)
    want = host("reference", want)
    np.save(os.path.join(keep, "want.npy"), want)
    out.update(
        rows_compared=int(got.shape[0]),
        program_vs_reference_relative_rms=relative_rms(got, want),
        token_agreement_argmax_share=argmax_agreement(got, want),
        reference_rms=float(np.sqrt(np.mean(want.astype(np.float64) ** 2))),
        finite=bool(np.isfinite(got).all() and np.isfinite(want).all()),
        device=device.record())
    for name in ("program_vs_reference_relative_rms",
                 "token_agreement_argmax_share",
                 "reference_write_strength_max"):
        say(f"{name} {out[name]:.6g}")
    del want
    held = host("reference held to the program's operand precision", jax.jit(
        lambda p, i, c: forward(family, p, i, c,
                                operands=policy.compute_dtype))(
            params, ids, continuations))
    np.save(os.path.join(keep, "held.npy"), held)
    out[OWN_HELD] = relative_rms(got, held)
    say(f"{OWN_HELD} {out[OWN_HELD]:.6g}")
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
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="seconds a stage's process may take")
    ap.add_argument("--stage", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--keep", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    bench = files.Bench(root)
    if args.stage:      # one process of read_stages'
        print(json.dumps(read_stage(
            bench, bench.config(args.config), args.stage, args.keep,
            args.seed[0], args.size)), flush=True)
        sys.exit(0)
    names = [n for n, _ in CONTROLS] if args.controls is None else \
        [n for n in args.controls.split(",") if n]
    recorded = bench.read("reference", args.config + ".json")
    limit = float(recorded["tolerance_relative_rms"])
    held_limit = float(
        recorded["tolerance_held_to_operand_precision_relative_rms"])
    passed = True
    for seed in args.seed or [None]:
        argv = [os.path.abspath(__file__), "--config", args.config,
                "--size", str(args.size)] \
            + ([] if seed is None else ["--seed", str(seed)])
        keep = tempfile.mkdtemp(prefix="olmo-hybrid-ref-")
        out: dict = {}
        try:
            read_stages(argv, ["readings"], args.timeout, keep, out)
            if "failed" not in out:
                read_stages(argv, names, args.timeout, keep, out)
        finally:
            shutil.rmtree(keep, ignore_errors=True)
        out["tolerance_relative_rms"] = limit
        out["tolerance_held_to_operand_precision_relative_rms"] = held_limit
        out["passed"] = "failed" not in out and out["finite"] and (
            out["program_vs_reference_relative_rms"] < limit) and (
            out[OWN_HELD] < held_limit
            < min([out[n + HELD] for n in names] or [float("inf")]))
        passed &= out["passed"]
        print(json.dumps(out), flush=True)
    sys.exit(0 if passed else 1)

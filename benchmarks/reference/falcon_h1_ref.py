"""The plain reference of the prompt expander's language model when it is a
Falcon-H1-34B-Instruct share (``family.expander``; ``model_type:
falcon_h1``: every layer an attention AND a selective state-space mixer
side by side under ONE norm, then a dense SwiGLU, under fourteen forward
multipliers): one forward pass over all positions of ONE sequence in
float32 at the highest matmul precision, in plain ``jax.numpy``: no cache,
no kernel, no batch, no chunks, the state-space recurrence **token by
token**. It reads the same parameter tree the program's ``models/lm.py``
holds and the same ``LMConfig``, and shares no code with it or with
``ops/``.

``N(x; w) = x / sqrt(mean(x^2) + eps) * w``. Tokens: ``x_0 = m_e E[id]``.
Logits: ``m_h W_h N_f(x_L)``, the head untied. No bias in any Linear.
Every layer alike::

    n = N_1(x);   h = x + m_ao Attn(m_ai n) + m_so SSM(m_si n)
    out = h + m_d W_d( silu(m_g W_g N_2(h)) * W_u N_2(h) )

*Attn(u)*: ``q = W_q u``, ``k = m_k W_k u``, ``v = W_v u``; ``q`` and ``k``
rotated over the WHOLE head width in the half-split pairing (dims ``i`` and
``i + d/2``) at the configuration's ``theta``; query head ``j`` attends KV
head ``j // (heads / kv heads)``; ``softmax(q . k * d^-1/2)`` causal over
every earlier position; the heads through ``W_o``. No query or key norm, no
gate.

*SSM(u)*: ``p = (W_in u) * mu``, ``W_in``'s columns ``[z | x | B | C |
dt]`` (``z`` and ``x`` heads x head width each, ``B`` and ``C`` groups x
state width each, ``dt`` one a head) and ``mu`` the five ``ssm_multipliers``
over those ranges in that order. ``[x | B | C]`` pass a causal depth-wise
convolution of ``taps`` taps WITH bias (zeros before position 0), then SiLU.
Head ``j`` reads the ``B`` and ``C`` of group ``j // (heads / groups)``:
``dt_j = softplus(dt_j + dt_bias_j)``, ``a_j = exp(-exp(A_log_j) dt_j)``,
the state ``S_j`` (head width x state width, from zero) ``S_j <- a_j S_j +
dt_j x_j B^T``, ``y_j = S_j C + D_j x_j``. Read-out: ``g = y * silu(z)``,
THEN the RMS over each of the groups' runs of channels times a weight a
channel (the norm AFTER the gate), through ``W_out``.

Where each multiplier sits, the column order, the pairing of the rotation,
which group a head reads, that both mixers read the ONE norm and that the
norm follows the gate are written out HERE; of the configuration this file
reads the widths, ``theta``, ``eps`` and the multipliers' VALUES under the
published names (:func:`multipliers`): a program that read its keys wrong
must miss this file.

What is compared is what the timed path runs: ``SEQUENCES`` sequences that
share their first positions (the instruction and the prompt) and then
differ. :func:`forward` is one full forward of each WHOLE sequence, one
after the other, attention and the head a block of rows at a time so that
it fits beside the bfloat16 weights; :func:`program` is the prefix's chunk
(attention over what it writes, the state-space part chunk-wise), a copy of
the cache, the prompt's chunk, a fork into ``SEQUENCES`` (keys and values
shared, every state and every kept row copied once a sequence) and one
decode step over all of them a position. Both give float32 logits at every
distinct position: the shared rows once, then each sequence's own rows.

    python3 benchmarks/reference/falcon_h1_ref.py --config sd15_falcon_h1_expand

prints the readings ``reference/<config>.json`` keeps beside the tolerance,
at the timed path's 2 368 positions unless ``--size`` says otherwise, and
those of the controls of :data:`CONTROLS`, each a fault the comparison must
see. There are two limits, as ``olmo_hybrid_ref.py`` has them and for its
reason: ``tolerance_relative_rms`` is against the reference as written
above (what the program reads there is the roundoff of its bfloat16 matmul
operands), ``tolerance_held_to_operand_precision_relative_rms`` against the
reference HELD TO THE PROGRAM'S OPERAND PRECISION (:func:`forward` with
``operands``). The program must meet both and each control must miss the
second, or the exit code is 1. The command itself stays off JAX and runs a
PROCESS A STAGE (:func:`read_stages`).
"""

from __future__ import annotations

import math

COMPONENT = "expander"
CONTROL = "the program's dynamic int8 linears (quant_linears) on " \
          "attention's four projections, the state-space mixers' in_proj " \
          "and out_proj, the SwiGLUs' three and the head"
#: sequences forked from the one prefill: the images of the cell's request
SEQUENCES = 4
#: the timed path's positions (2 048 + 64 + 256): what the readings are
#: taken at unless ``--size`` says otherwise
TIMED_POSITIONS = 2368
#: the fourteen forward multipliers, under the published names (the five
#: ``ssm_multipliers`` and the two ``mlp_multipliers`` by what they scale)
MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
    "attention_in_multiplier", "attention_out_multiplier",
    "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multiplier_z",
    "ssm_multiplier_x", "ssm_multiplier_b", "ssm_multiplier_c",
    "ssm_multiplier_dt", "mlp_gate_multiplier", "mlp_down_multiplier")


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


def multipliers(cfg) -> dict:
    """The fourteen multipliers' values by their published names."""
    mixers = {kind: pair for kind, *pair in cfg.mixer_multipliers}
    attention = mixers.get("full", (1.0, 1.0))
    space = mixers.get("ssm", (1.0, 1.0))
    return dict(zip(MULTIPLIERS, (
        cfg.embedding_multiplier, cfg.logit_multiplier, cfg.key_multiplier,
        *attention, *space, *cfg.ssm_multipliers, *cfg.mlp_multipliers)))


# -- the program, as the timed path runs it -----------------------------------

def _without(cfg, name: str):
    """``cfg`` with the multiplier published as ``name`` set to 1."""
    import dataclasses

    values = dict(multipliers(cfg), **{name: 1.0})
    v = [values[n] for n in MULTIPLIERS]
    return dataclasses.replace(
        cfg, embedding_multiplier=v[0], logit_multiplier=v[1],
        key_multiplier=v[2],
        mixer_multipliers=(("full", v[3], v[4]), ("ssm", v[5], v[6])),
        ssm_multipliers=tuple(v[7:12]), mlp_multipliers=tuple(v[12:14]))


def _faulted(params, cfg, no_skip: bool, no_dt_bias: bool,
             wrong_group: bool):
    """``params`` with every state-space mixer's skip ``D`` or ``dt_bias``
    zeroed, or its ``B`` and ``C`` handed on by one group (the columns of
    ``in_proj`` and the convolution's channels together: a head then reads
    the maps of the group after its own)."""
    import jax.numpy as jnp

    if not (no_skip or no_dt_bias or wrong_group):
        return params
    inner, wide = cfg.ssm_inner, cfg.ssm_num_groups * cfg.ssm_state_size

    def rolled(x, at):
        """The ``B`` and ``C`` runs of the last axis from ``at`` on, each
        moved on by one group."""
        runs = [jnp.roll(
            x[..., lo:lo + wide].reshape(
                x.shape[:-1] + (cfg.ssm_num_groups, -1)), 1, axis=-2
        ).reshape(x.shape[:-1] + (wide,))
            for lo in (at, at + wide)]
        return jnp.concatenate(
            [x[..., :at], *runs, x[..., at + 2 * wide:]], axis=-1)

    out = dict(params)
    for layer in cfg.layers_of("ssm"):
        p = dict(params[f"layers_{layer}"])
        mixer = dict(p["ssm"])
        if no_skip:
            mixer["D"] = jnp.zeros_like(mixer["D"])
        if no_dt_bias:
            mixer["dt_bias"] = jnp.zeros_like(mixer["dt_bias"])
        if wrong_group:
            mixer["in_proj"] = {"kernel": rolled(
                mixer["in_proj"]["kernel"], 2 * inner)}
            mixer["conv_kernel"] = rolled(mixer["conv_kernel"], inner)
            mixer["conv_bias"] = rolled(mixer["conv_bias"], inner)
        p["ssm"] = mixer
        out[f"layers_{layer}"] = p
    return out


def stages(family, policy, control: bool = False, state_bf16: bool = False,
           state_shared: bool = False, norm_before_gate: bool = False,
           no_conv_bias: bool = False, no_skip: bool = False,
           no_dt_bias: bool = False, wrong_group: bool = False,
           rotary_1e4: bool = False, without: str = ""):
    """What the timed path runs at the timed sizes, as the two executables
    it runs them as (:func:`program` joins them): the prefix's prefill as
    one chunk, a copy of the cache as it stands at the prefix's last token
    (the kept snapshot: keys, values, states and kept rows), the prompt
    chunk's prefill against that copy, a fork of the cache into
    ``SEQUENCES``, then every further position decoded one step over all
    sequences at a time, teacher-forced on the seeded continuations.
    Logits ``(prefix + prompt + SEQUENCES * decoded, vocabulary)``: the
    shared rows, then each sequence's. The controls: ``control`` the int8
    Linears; ``state_bf16`` keeps the state-space states in bfloat16
    between tokens; ``state_shared`` hands every sequence sequence 0's
    states before each step; ``norm_before_gate`` norms the read-out and
    then gates it; ``no_conv_bias``, ``no_skip`` and ``no_dt_bias`` leave
    out the convolution's bias, ``D`` and ``dt_bias``; ``wrong_group``
    hands every head the ``B`` and ``C`` of another group; ``rotary_1e4``
    builds the table at ``theta`` 1e4; ``without`` names ONE of
    :data:`MULTIPLIERS` that is left out (set to 1)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.models import lm

    cfg = family.expander
    if without:
        cfg = _without(cfg, without)
    if norm_before_gate:
        cfg = dataclasses.replace(cfg, ssm_norm_before_gate=True)
    if no_conv_bias:
        cfg = dataclasses.replace(cfg, ssm_conv_bias=False)
    if rotary_1e4:
        cfg = dataclasses.replace(
            cfg, rope_full=dataclasses.replace(cfg.rope_full, theta=1e4))
    module = lm.DecoderLM(cfg, dtype=policy.compute_dtype,
                          quant_linears=control)

    def held(params):
        return {"params": _faulted(params, cfg, no_skip, no_dt_bias,
                                   wrong_group)}

    def prefills(params, ids, decoded: int):
        """The two chunks and the fork: (their logits, the forked
        cache)."""
        size = ids.shape[0] + decoded
        prefix = split(size)[0]
        cache = lm.empty_cache(cfg, size, policy.compute_dtype)
        if state_bf16:
            cache["ssm_state"] = [x.astype(jnp.bfloat16)
                                  for x in cache["ssm_state"]]
        apply = lambda t, start, c: module.apply(   # noqa: E731
            held(params), t, jnp.int32(start), jnp.int32(t.shape[0]), c)
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
            return {**cache, "ssm_state": [
                jnp.broadcast_to(x[:1], x.shape)
                for x in cache["ssm_state"]]}

        def step(carry, tokens):
            cache, position = carry
            logits, cache, _ = module.apply(
                held(params), tokens, position, jnp.int32(SEQUENCES),
                between(cache), sequences=True)
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


def _norm(x, weight, eps):
    """``N(x; w)`` over the last axis."""
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _w(weight)


def _r(x, operands):
    """``x`` as a matmul operand of the program's: rounded to ``operands``
    (None: left float32). ``reduce_precision`` and not a cast there and
    back, which XLA may drop on a TPU (olmo_hybrid_ref.py:_r)."""
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


def _rotated(x, theta: float):
    """``x`` ``(T, heads, d)`` turned by its position over the whole head
    width, dim ``i`` paired with dim ``i + d / 2``."""
    import jax.numpy as jnp

    tokens, _, dim = x.shape
    half = dim // 2
    inverse = jnp.asarray(
        [theta ** (-2.0 * i / dim) for i in range(half)], jnp.float32)
    angles = jnp.arange(tokens, dtype=jnp.float32)[:, None] * inverse
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg, u, p, m, operands=None):
    """Causal attention over the whole sequence, a group of query heads a
    KV head, rotated over the whole head width; a block of query rows at a
    time."""
    import jax
    import jax.numpy as jnp

    tokens = u.shape[0]
    kv, dim = cfg.num_kv_heads, cfg.head_dim
    heads = p["q_proj"]["kernel"].shape[1] // dim
    theta = cfg.rope_full.theta
    u = _r(u, operands)
    q = _r(_rotated((u @ _w(p["q_proj"]["kernel"])).reshape(
        tokens, heads, dim), theta), operands)
    k = _r(_rotated((m["key_multiplier"] * (u @ _w(p["k_proj"]["kernel"]))
                     ).reshape(tokens, kv, dim), theta), operands)
    v = _r(u @ _w(p["v_proj"]["kernel"]), operands).reshape(tokens, kv, dim)
    q = q.reshape(tokens, kv, heads // kv, dim)     # head j: KV head j // n
    j = jnp.arange(tokens)[None, :]
    block = _row_block(tokens)

    def rows(at):
        i = at + jnp.arange(block)[:, None]
        scores = jnp.einsum(
            "ignd,jgd->gnij", jax.lax.dynamic_slice_in_dim(q, at, block),
            k) * dim ** -0.5
        probs = jax.nn.softmax(
            jnp.where((i - j >= 0)[None, None], scores, -jnp.inf), -1)
        return jnp.einsum("gnij,jgd->ignd", _r(probs, operands), v)

    out = jax.lax.map(rows, jnp.arange(0, tokens, block))
    return _r(out.reshape(tokens, heads * dim), operands) \
        @ _w(p["o_proj"]["kernel"])


def ssm_mixer(cfg, u, p, m, operands=None, fault: str = ""):
    """(the state-space mixer's output over all positions, the largest and
    the smallest per-token decay any head had): the state updated one token
    at a time from zero. ``fault`` ``"one_norm_group"``: the read-out
    normed over all channels at once."""
    import jax
    import jax.numpy as jnp

    tokens = u.shape[0]
    heads, dim = cfg.ssm_num_heads, cfg.ssm_head_dim
    groups, width = cfg.ssm_num_groups, cfg.ssm_state_size
    taps = cfg.ssm_conv_kernel
    inner, wide = heads * dim, groups * width
    proj = _r(u, operands) @ _w(p["in_proj"]["kernel"])
    at = [inner, 2 * inner, 2 * inner + wide, 2 * inner + 2 * wide]
    z = proj[:, :at[0]] * m["ssm_multiplier_z"]
    xbc = jnp.concatenate(
        [proj[:, at[0]:at[1]] * m["ssm_multiplier_x"],
         proj[:, at[1]:at[2]] * m["ssm_multiplier_b"],
         proj[:, at[2]:at[3]] * m["ssm_multiplier_c"]], axis=-1)
    dt = proj[:, at[3]:] * m["ssm_multiplier_dt"]
    kernel = _w(p["conv_kernel"])                       # (taps, channels)
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32), xbc])
    xbc = jax.nn.silu(sum(kernel[j][None, :] * padded[j:j + tokens]
                          for j in range(taps)) + _w(p["conv_bias"]))
    x = xbc[:, :inner].reshape(tokens, heads, dim)
    # head j reads group j // (heads / groups)
    per = heads // groups
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
    y = y + _w(p["D"])[:, None] * x
    g = (y * jax.nn.silu(z.reshape(tokens, heads, dim))).reshape(
        tokens, inner)
    runs = 1 if fault == "one_norm_group" else groups
    g = g.reshape(tokens, runs, inner // runs)
    g = (g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                      + cfg.rms_norm_eps)).reshape(tokens, inner) \
        * _w(p["norm"]["scale"])
    return (_r(g, operands) @ _w(p["out_proj"]["kernel"]),
            jnp.max(decay), jnp.min(decay))


def swiglu(n, p, m, operands=None):
    import jax

    n = _r(n, operands)
    gate = m["mlp_gate_multiplier"] * (n @ _w(p["gate_proj"]["kernel"]))
    up = n @ _w(p["up_proj"]["kernel"])
    return m["mlp_down_multiplier"] * (
        _r(jax.nn.silu(gate) * up, operands) @ _w(p["down_proj"]["kernel"]))


def layer_forward(cfg, x, p, m, operands=None, fault: str = ""):
    """(x after one layer over ``(T, C)``, the largest and smallest decay
    seen). Both mixers read the ONE norm (``fault`` ``"two_norms"``: the
    state-space mixer the MLP's)."""
    eps = cfg.rms_norm_eps
    n = _norm(x, p["input_norm"]["scale"], eps)
    other = _norm(x, p["post_attention_norm"]["scale"], eps) \
        if fault == "two_norms" else n
    mixed, most, least = ssm_mixer(
        cfg, m["ssm_in_multiplier"] * other, p["ssm"], m, operands, fault)
    h = x + m["attention_out_multiplier"] * attention(
        cfg, m["attention_in_multiplier"] * n, p["attn"], m, operands) \
        + m["ssm_out_multiplier"] * mixed
    return h + swiglu(_norm(h, p["post_attention_norm"]["scale"], eps),
                      p["mlp"], m, operands), most, least


def trunk(cfg, params, ids, operands=None, fault: str = ""):
    """(the final norm's output ``(T, C)``, the largest and the smallest
    per-token decay) of one whole sequence."""
    import jax.numpy as jnp

    m = multipliers(cfg)
    x = m["embedding_multiplier"] * params["embed_tokens"]["embedding"][
        ids - cfg.vocab[0]].astype(jnp.float32)
    most, least = jnp.float32(0), jnp.float32(1)
    for layer in range(cfg.num_layers):
        x, high, low = layer_forward(cfg, x, params[f"layers_{layer}"], m,
                                     operands, fault)
        most, least = jnp.maximum(most, high), jnp.minimum(least, low)
    return (_norm(x, params["norm"]["scale"], cfg.rms_norm_eps), most,
            least)


def forward(family, params, ids, continuations, with_decays: bool = False,
            operands=None, fault: str = ""):
    """Logits at every distinct position, in :func:`program`'s order: one
    full forward over each whole sequence (the shared ids, then its own
    continuation), one sequence after the other; the head over the shared
    rows of the first and the own rows of each, a block of rows at a time.
    ``with_decays`` adds the largest and the smallest per-token decay any
    head of any layer had. ``operands``: the reference HELD to the
    program's operand precision (the module's text): a dtype every
    matmul's activations, the queries, keys, values and attention weights
    are rounded to. ``fault``: the reference itself made wrong in one way
    the program cannot be (``"one_norm_group"``, ``"two_norms"``), for the
    tests: the program must then miss it."""
    import jax
    import jax.numpy as jnp

    cfg = family.expander
    shared = ids.shape[0]

    def whole(b):
        return trunk(cfg, params, jnp.concatenate([ids, continuations[b]]),
                     operands, fault)

    with jax.default_matmul_precision("highest"):
        n, most, least = jax.lax.map(
            whole, jnp.arange(continuations.shape[0]))
        rows = jnp.concatenate(
            [n[0, :shared], n[:, shared:].reshape(-1, n.shape[-1])])
        head = params["lm_head"]["kernel"]
        block = _row_block(rows.shape[0])
        logits = multipliers(cfg)["lm_head_multiplier"] * jax.lax.map(
            lambda part: _r(part, operands) @ _w(head),
            rows.reshape(-1, block, rows.shape[-1])).reshape(
                rows.shape[0], -1)
    return (logits, jnp.max(most), jnp.min(least)) if with_decays \
        else logits


# -- the readings -------------------------------------------------------------

#: the controls' readings, by name: the keyword arguments of :func:`program`
CONTROLS = tuple((name, {name: True}) for name in (
    "control", "state_bf16", "state_shared", "norm_before_gate",
    "no_conv_bias", "no_skip", "no_dt_bias", "wrong_group", "rotary_1e4")) \
    + tuple(("no_" + name, {"without": name}) for name in MULTIPLIERS)
#: what the reference can be made to say wrongly (:func:`forward`'s
#: ``fault``): the faults no key or leaf of the program can express
FAULTS = ("one_norm_group", "two_norms")
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
    want, most, least = jax.jit(lambda p, i, c: forward(
        family, p, i, c, with_decays=True))(params, ids, continuations)
    out["reference_decay_max"] = float(most)
    out["reference_decay_min"] = float(least)
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
                 "token_agreement_argmax_share", "reference_rms",
                 "reference_decay_max", "reference_decay_min"):
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


#: the controls a chip reading takes unless ``--controls`` says otherwise
#: (every other name of :data:`CONTROLS` is read by the CPU tests)
CHIP_CONTROLS = ("control", "state_bf16", "state_shared",
                 "norm_before_gate", "no_key_multiplier",
                 "no_ssm_out_multiplier")


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
                    help="comma-separated names of CONTROLS (default: "
                         "CHIP_CONTROLS; 'all': every one)")
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
    names = list(CHIP_CONTROLS) if args.controls is None else \
        [n for n, _ in CONTROLS] if args.controls == "all" else \
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
        keep = tempfile.mkdtemp(prefix="falcon-h1-ref-")
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

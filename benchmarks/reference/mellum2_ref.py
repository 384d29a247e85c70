"""The plain reference of the prompt expander's language model when it is a
Mellum2 share (``family.expander``: grouped-query attention in every layer,
three layers in four over a window of the last ``sliding_window`` positions
under plain RoPE and the fourth over every position under YaRN, every MLP a
softmax router over experts that are all held, with no shared expert): one
forward pass over all positions of ONE sequence in float32 at the highest
matmul precision, in plain ``jax.numpy``: no cache, no ring, no kernel, no
batch, no chunks. It reads the same parameter tree the program's
``models/lm.py`` holds and the same ``LMConfig``, and shares no code with
it.

Every norm is ``x_hat * scale``, ``x_hat = x / sqrt(mean(x^2) + eps)``.
Every layer is ``x = x + attn(norm(x)); x = x + moe(norm(x))`` with its own
two norms; one final norm, then the head over the held vocabulary. No bias
anywhere.

*Attention*, per query head ``h`` of ``H`` over KV head ``h // (H / KV)``:
``q = (W_q n)_h``, ``k = (W_k n)_g``, ``v = (W_v n)_g``, queries and keys
rotated over all ``D`` dims (``rotate_half`` pairing), ``softmax(q k^T
D^-1/2)`` over the keys the layer's kind lets position ``i`` see, the heads'
sums side by side through ``W_o``. No q/k norm, no gate.

- ``sliding``: ``j`` with ``0 <= i - j < sliding_window``, written as that
  mask on the whole score matrix (computed in blocks of query rows so that
  it fits); plain frequencies ``theta^(-2m/D)``.
- ``full``: every ``j <= i``; YaRN's frequencies from its five numbers
  (``theta``, ``factor``, ``original_max_position``, ``beta_fast``,
  ``beta_slow``): pair ``m`` turns ``r_m = original * theta^(-2m/D) / 2 pi``
  times over the original context; the pairs up to the one that turns
  ``beta_fast`` times keep ``theta^(-2m/D)``, those from the one that turns
  ``beta_slow`` times on are divided by ``factor``, and between the two
  pair indices (the first rounded down, the second up) the two blend
  linearly in ``m``; cos and sin are multiplied by ``attention_factor``.

*Expert layer*: ``p = softmax(W_r n)`` over all experts in float32; the ``k``
largest are chosen; ``w_e = p_e / sum over the chosen of p``
(``norm_topk_prob``), times ``routed_scaling_factor`` (1); ``sum_{e chosen}
w_e E_e(n)``, every expert a SwiGLU with SiLU. No shared expert, no
selection bias, no dense layer.

Departures from the published model are the configuration's ``assumed``:
no q/k norm (the config has no key for one); SiLU; the MTP head left out
(the config has no key for it); ``rotate_half`` pairing; the head untied.

Held experts are upcast to float32 one at a time (a loop over the held
experts, each applied to every token and weighted by what the router gave
it, zero where it was not chosen), so the reference fits beside the bf16
weights.

What is compared is what the timed path runs: ``SEQUENCES`` sequences that
share their first positions (the instruction and the prompt) and then
differ. :func:`forward` is one full forward of each WHOLE sequence, one
after the other; :func:`program` is the prefix's chunk, a copy of the
cache, the prompt's chunk, a fork into ``SEQUENCES`` and one decode step
over all of them a position. Both give logits at every distinct position:
the shared rows once, then each sequence's own rows. Where the weights are
stored in bfloat16 (the chip, at the published widths) the logits are
handed back in float16: three arrays of 3 136 x 98 304 beside 10.9 GB of
weights and the program's own buffers do not fit in float32, and float16's
2.8e-4 of rounding is a fiftieth of the smallest reading taken there. At
float32 weights (the tests) they stay float32.

    python3 benchmarks/reference/mellum2_ref.py --config sd15_mellum2_expand

prints the diagnostic readings ``reference/<config>.json`` keeps beside the
tolerance, at the timed path's 2 368 positions unless ``--size`` says
otherwise: the share of (token, expert layer) pairs whose chosen experts
differ between program and reference, the program against the reference
held to the program's choices (routing flips apart from arithmetic error),
and those readings for four controls: the program's int8 linears; the
window layers attending every position (a ring as long as the sequence: a
ring taken for a full buffer); the window layers rotated with the full
layers' YaRN table; and every sequence reading sequence 0's rings (a fork
that aliases). The held reading has a limit of its own in that file
(``tolerance_held_to_routing_relative_rms``): the program must meet it and
each of the four controls must miss it, or the exit code is 1.

The command itself stays off JAX and runs a PROCESS A STAGE
(:func:`read_stages`): every seed's ``readings`` first (program, reference,
held reference; each pulled to the host and compared there, its reading
printed on stderr the moment it is taken, the two references left as
``.npy`` in a temporary directory), then every control alone against those
files. At the timed sizes a second program-sized executable in one process
hung the device every time it was tried (PR 45; the cause is not known),
so none runs two; a stage that outlasts ``--timeout`` is killed, named
under ``failed`` and not tried again on a later seed.
"""

from __future__ import annotations

import math

COMPONENT = "expander"
CONTROL = "the program's dynamic int8 linears (quant_linears) on " \
          "attention's four projections and the head"
#: sequences forked from the one prefill: the images of the cell's request
SEQUENCES = 4


def split(size: int) -> tuple[int, int, int]:
    """(prefix, prompt chunk, decoded) positions of ``size``: at 2368 the
    timed path's 2048 + 64 + 256; at 37 it is 32 + 1 + 4."""
    decoded = max(1, size * 4 // 37)
    user = max(1, size // 37)
    return size - user - decoded, user, decoded


def inputs(family, seed: int, size: int):
    """Seeded ids from the held slice of the vocabulary: the shared
    ``(prefix + prompt,)`` and ``(SEQUENCES, decoded)`` continuations that
    differ from their first token on."""
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


def stages(family, policy, control: bool = False,
           windows_attend_all: bool = False,
           windows_under_yarn: bool = False, aliased_rings: bool = False):
    """What the timed path runs at the timed sizes, as the two executables
    it runs them as (:func:`program` joins them, :func:`staged` runs them
    apart): the prefix's prefill as
    one chunk (longer than the window), a copy of the cache as it stands at
    the prefix's last token (the kept snapshot), the prompt chunk's prefill
    against that copy, a fork of the cache into ``SEQUENCES``, then every
    further position decoded one step over all sequences at a time,
    teacher-forced on the seeded continuations. Logits ``(prefix + prompt +
    SEQUENCES * decoded, vocabulary)``: the shared rows, then each
    sequence's. The controls: ``windows_attend_all`` gives the window
    layers rings as long as the sequence, ``windows_under_yarn`` rotates
    them with the full layers' table, ``aliased_rings`` hands every
    sequence sequence 0's rings before each step."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.models import lm

    base = family.expander

    def model(size):
        cfg = base
        if windows_attend_all:
            cfg = dataclasses.replace(cfg, sliding_window=size)
        if windows_under_yarn:
            cfg = dataclasses.replace(cfg, rope_sliding=cfg.rope_full)
        return cfg, lm.DecoderLM(cfg, dtype=policy.compute_dtype,
                                 quant_linears=control)

    def prefills(params, ids, decoded: int):
        """The two chunks and the fork: (their logits, the forked cache,
        the experts their rows chose ``(layers, rows, k)``)."""
        size = ids.shape[0] + decoded
        cfg, module = model(size)
        prefix = split(size)[0]
        cache = lm.empty_cache(cfg, size, policy.compute_dtype)
        apply = lambda t, start, c: module.apply(   # noqa: E731
            {"params": params}, t, jnp.int32(start), jnp.int32(t.shape[0]),
            c)
        l0, snapshot, r0 = apply(ids[:prefix], 0, cache)
        cache = jax.tree_util.tree_map(jnp.copy, snapshot)
        l1, cache, r1 = apply(ids[prefix:], prefix, cache)
        # each part cast before they are joined: the float32 whole would
        # be 0.8 GB more beside the weights
        out = _out_dtype(params)
        return (jnp.concatenate([l0.astype(out), l1.astype(out)]),
                kv.fork(cache, SEQUENCES),
                jnp.concatenate([r0[0], r1[0]], axis=1))

    def decodes(params, cache, continuations, shared: int):
        """Every further position, one step over all sequences a time:
        (each sequence's logits in turn, the experts chosen)."""
        cfg, module = model(shared + continuations.shape[1])
        rings = [i for i, kind in enumerate(cfg.layer_types)
                 if kind == "sliding"]

        def between(cache):
            if not aliased_rings:
                return cache
            return {name: [jnp.broadcast_to(rows[:1], rows.shape)
                           if i in rings else rows
                           for i, rows in enumerate(buffers)]
                    for name, buffers in cache.items()}

        def step(carry, tokens):
            cache, position = carry
            logits, cache, routed = module.apply(
                {"params": params}, tokens, position, jnp.int32(SEQUENCES),
                between(cache), sequences=True)
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
    import jax
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
    handed from one to the other on the device: the same operations as
    ``jax.jit(program(...))``, and the two executables the timed path
    builds. ``--staged`` takes the readings so."""
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


def inverse_frequencies(rope, dim: int):
    """``(dim / 2,)`` float64: plain ``theta^(-2m/dim)``, or YaRN's where
    the layer kind's rope has a ``factor``."""
    import numpy as np

    pairs = np.arange(dim // 2, dtype=np.float64)
    plain = rope.theta ** (-2.0 * pairs / dim)
    if not rope.factor:
        return plain

    def pair_turning(rotations: float) -> float:
        """The (fractional) pair index that turns ``rotations`` times over
        the original context."""
        return dim * math.log(rope.original_max_position
                              / (rotations * 2.0 * math.pi)) \
            / (2.0 * math.log(rope.theta))

    low = max(math.floor(pair_turning(rope.beta_fast)), 0)
    high = min(math.ceil(pair_turning(rope.beta_slow)), dim - 1)
    if high == low:
        high += 0.001
    interpolated = np.clip((pairs - low) / (high - low), 0.0, 1.0)
    return plain * (1.0 - interpolated) + plain / rope.factor * interpolated


def _rope(x, rope):
    """HF's ``apply_rotary_pos_emb`` on ``(T, H, D)``, every dim rotated:
    cos and sin are ``cat(freqs, freqs)`` times the attention factor,
    ``rotate_half`` swaps the halves with a sign."""
    import jax.numpy as jnp

    dim = x.shape[-1]
    inv = jnp.asarray(inverse_frequencies(rope, dim), jnp.float32)
    freqs = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos = jnp.cos(emb)[:, None, :] * rope.attention_factor
    sin = jnp.sin(emb)[:, None, :] * rope.attention_factor
    rotated = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rotated * sin


def _row_block(tokens: int, most: int = 256) -> int:
    """The largest divisor of ``tokens`` at or under ``most``."""
    return max(b for b in range(1, most + 1) if tokens % b == 0)


def attention(cfg, layer: int, n, p):
    """Grouped-query attention over the whole sequence; a sliding layer's
    window is a mask on the whole score matrix."""
    import jax
    import jax.numpy as jnp

    tokens = n.shape[0]
    heads, kv, dim = (cfg.num_heads_per_layer[layer], cfg.num_kv_heads,
                      cfg.head_dim)
    sliding = cfg.layer_types[layer] == "sliding"
    rope = cfg.rope_sliding if sliding else cfg.rope_full
    q = (n @ _w(p["q_proj"]["kernel"])).reshape(tokens, heads, dim)
    k = (n @ _w(p["k_proj"]["kernel"])).reshape(tokens, kv, dim)
    v = (n @ _w(p["v_proj"]["kernel"])).reshape(tokens, kv, dim)
    q, k = _rope(q, rope), _rope(k, rope)
    k = jnp.repeat(k, heads // kv, axis=1)     # head h reads KV head h // g
    v = jnp.repeat(v, heads // kv, axis=1)
    j = jnp.arange(tokens)[None, :]
    block = _row_block(tokens)

    def rows(at):
        i = at + jnp.arange(block)[:, None]
        seen = (i - j >= 0)
        if sliding:
            seen &= (i - j < cfg.sliding_window)
        scores = jnp.einsum(
            "ihd,jhd->hij", jax.lax.dynamic_slice_in_dim(q, at, block), k) \
            * dim ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hij,jhd->ihd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, tokens, block))
    return out.reshape(tokens, heads * dim) @ _w(p["o_proj"]["kernel"])


def route(cfg, n, p, forced=None):
    """(chosen experts (T, k), their weights (T, k)): a float32 softmax
    over every expert, the k largest, their scores over their sum, scaled.
    ``forced`` gives the experts instead (the diagnostic reading); their
    weights are still this side's own scores."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.softmax(n @ _w(p["router"]), axis=-1)
    top, chosen = jax.lax.top_k(scores, cfg.num_experts_per_tok)
    if forced is not None:
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


def layer_forward(cfg, layer: int, x, p, forced=None):
    """One decoder layer over ``(T, C)``: (x after it, experts chosen)."""
    x = x + attention(cfg, layer, _norm(x, p["input_norm"],
                                        cfg.rms_norm_eps), p["attn"])
    n = _norm(x, p["post_attention_norm"], cfg.rms_norm_eps)
    chosen, weights = route(cfg, n, p["mlp"], forced)
    return x + routed_part(n, chosen, weights, p["mlp"]["experts"],
                           cfg.experts[0]), chosen


def trunk(cfg, params, ids, forced=None):
    """(the final norm's output ``(T, C)``, the experts chosen ``(layers,
    T, k)``) of one whole sequence."""
    import jax.numpy as jnp

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
    return _norm(x, params["norm"], cfg.rms_norm_eps), jnp.stack(routing)


def forward(family, params, ids, continuations, forced=None,
            with_routing=False):
    """Logits at every distinct position, in :func:`program`'s order: one
    full forward over each whole sequence (the shared ids, then its own
    continuation), one sequence after the other; the head over the shared
    rows of the first and the own rows of each. ``forced`` ``(layers, rows,
    k)`` in the same order of rows holds the routing to the experts given.
    ``with_routing`` adds the chosen experts ``(layers, rows, k)``."""
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
        logits = (rows @ _w(params["lm_head"]["kernel"])).astype(
            _out_dtype(params))
    if not with_routing:
        return logits
    return logits, jnp.concatenate(
        [chosen[0, :, :shared]] + [chosen[b, :, shared:]
                                   for b in range(chosen.shape[0])], axis=1)


#: the controls' readings, by name: the keyword arguments of :func:`program`
CONTROLS = (
    ("control", {"control": True}),
    ("windows_attend_all", {"windows_attend_all": True}),
    ("windows_under_yarn", {"windows_under_yarn": True}),
    ("aliased_rings", {"aliased_rings": True}),
)
HELD = "_vs_reference_held_to_the_programs_routing_relative_rms"
#: the timed path's positions (2 048 + 64 + 256): what the readings are
#: taken at unless ``--size`` says otherwise
TIMED_POSITIONS = 2368


def _blocks(rows: int, most: int = 256):
    return ((at, min(at + most, rows)) for at in range(0, rows, most))


def relative_rms(got, want) -> float:
    """Relative RMS of two host arrays of logits, summed in float64 a block
    of rows at a time (the whole in float64 would be 2.5 GB a side)."""
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
               size=None, apart: bool = False) -> dict:
    """ONE process's share of the readings beside the tolerance (see the
    module's text), at the configuration's ``weight_seed`` or another:
    weights and ids both come from it. Stage ``readings``: the program, the
    reference and the reference held to the program's routing, each pulled
    to the host as it ends and its reading printed on stderr at once; the
    two references are left in ``keep`` as ``.npy``. Any other stage is a
    name of :data:`CONTROLS`: that control alone, as the FIRST and only
    program-sized executable of its process, read against the two files.
    ``apart``: the program through :func:`staged`. (PR 45: at the timed
    sizes a second program-sized executable in one process hung the
    device every time it was tried, so no process runs two.)"""
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
    keeps = [tempfile.mkdtemp(prefix="mellum2-ref-") for _ in seeds]
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

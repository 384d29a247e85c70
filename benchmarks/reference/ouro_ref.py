"""The plain reference of the prompt expander's language model when it is a
looped dense model (``family.expander``: Ouro-2.6B; the LoopLM family,
arXiv 2510.25741): one forward pass over all positions of ONE sequence in
float32 at the highest matmul precision, in plain ``jax.numpy``: no cache,
no pass axis, no batch, no chunks. It reads the same parameter tree the
program's ``models/lm.py`` holds and the same ``LMConfig``, and shares no
code with it.

Every norm is ``x_hat * scale``, ``x_hat = x / sqrt(mean(x^2) + eps)``. No
bias anywhere but the gate's.

*A layer*, the same ``L`` in every pass, four norms::

    a = Attn(N1(x));    x = x + N2(a)
    m = SwiGLU(N3(x));  x = x + N4(m)

``Attn``: ``q, k, v = n W_q, n W_k, n W_v`` (``H`` heads of width ``D``, as
many KV heads: no grouping), queries and keys rotated over all ``D`` dims
(``rotate_half`` pairing, plain frequencies ``theta^(-2m/D)``, the same
position ids in every pass), ``softmax(q k^T D^-1/2)`` over ``j <= i``, the
heads side by side through ``W_o``. ``SwiGLU``: ``down(silu(gate n) * up
n)``.

*The model*: ``h_0 = E[token]``; for ``t = 1..T`` (``total_ut_steps``)
``h_t = N_f(Stack(h_{t-1}))``: the whole stack again over the same weights,
the final norm closing EVERY pass, and pass ``t``'s attention over the keys
and values pass ``t`` itself makes of its input. ``lambda_t = sigmoid(w_g .
h_t + b_g)``; ``S_t = sum_{i <= t} lambda_i prod_{j < i} (1 - lambda_j)``,
``S_T := 1``; the head reads ``h_{t*}``, ``t* = min{t : S_t >=
early_exit_threshold}``; ``logits = h_{t*} W_head``. The passes are a
``lax.scan`` whose body is the plain stack, so that 48 layers are compiled
and not 192; everything inside it is written out.

Departures from the published model are the configuration's ``assumed``.

What is compared is what the timed path runs: ``SEQUENCES`` sequences that
share their first positions (the instruction and the prompt) and then
differ. :func:`forward` is one full forward of each WHOLE sequence, one
after the other; :func:`program` is the prefix's chunk, a copy of the
cache, the prompt's chunk, a fork into ``SEQUENCES`` and one decode step
over all of them a position, through the (pass, layer) cache. Both give, at
every distinct position (the shared rows once, then each sequence's own
rows), the logits, the ``T`` gates and ``t*``.

    python3 benchmarks/reference/ouro_ref.py --config sd15_ouro_expand

prints the readings ``reference/<config>.json`` keeps beside the
tolerance, at the timed path's 384 positions unless ``--size`` says
otherwise: the program against the reference in logits (relative RMS) and
in gates (largest absolute difference), the largest ``lambda`` the
reference saw, the passes the rule chose, and the logits' reading for five
controls that must each miss the tolerance: ``last_pass_cache`` (every
pass of a decode step attends the LAST pass's keys and values of the
earlier positions: the paper's own cache-sharing shortcut, a different
model), ``one_pass_fewer`` (three passes of the published four),
``no_post_norms`` (the norms after the sublayers
left out), ``norm_after_last_pass`` (the final norm after the last pass
only; a variant of the reference itself, read against the reference) and
``control``, the program's int8 linears.

The command itself stays off JAX and runs a PROCESS A STAGE, as
``mellum2_ref.py`` does (a chip belongs to one process at a time, and one
process does not hold two program-sized executables): every seed's
``readings`` first (program and reference, the reference's logits left as
``.npy`` in a temporary directory), then every control alone against that
file. A stage that outlasts ``--timeout`` is killed and named under
``failed``.
"""

from __future__ import annotations

import math

COMPONENT = "expander"
CONTROL = "the program's dynamic int8 linears (quant_linears) on " \
          "attention's four projections, the SwiGLU's three and the head"
#: sequences forked from the one prefill: the images of the cell's request
SEQUENCES = 4
#: the timed path's positions (256 + 64 + 64)
TIMED_POSITIONS = 384


def split(size: int) -> tuple[int, int, int]:
    """(prefix, prompt chunk, decoded) positions of ``size``: at 384 the
    timed path's 256 + 64 + 64; at 24 it is 16 + 4 + 4."""
    part = max(1, size // 6)
    return size - 2 * part, part, part


def inputs(family, seed: int, size: int):
    """Seeded ids: the shared ``(prefix + prompt,)`` and ``(SEQUENCES,
    decoded)`` continuations that differ from their first token on."""
    import jax

    first, count = family.expander.vocab
    prefix, user, decoded = split(size)
    key = jax.random.key(seed + 7)
    return (jax.random.randint(key, (prefix + user,), first, first + count),
            jax.random.randint(jax.random.fold_in(key, 1),
                               (SEQUENCES, decoded), first, first + count))


# -- the program, as the timed path runs it -----------------------------------

def program(family, policy, control: bool = False, with_gates: bool = False,
            last_pass_cache: bool = False, one_pass_fewer: bool = False,
            no_post_norms: bool = False):
    """``(params, ids, continuations) -> logits`` ``(prefix + prompt +
    SEQUENCES * decoded, vocabulary)``, and ``with_gates`` the gates
    ``(passes, rows)`` and the chosen passes ``(rows,)`` beside them: the
    prefix's prefill as one chunk, a copy of the cache at its last token
    (the kept snapshot), the prompt chunk's prefill against the copy, a
    fork into ``SEQUENCES``, then every further position one step over all
    sequences, teacher-forced on the seeded continuations. ``control``:
    the int8 linears; the other three are the module text's controls."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.cache import kv
    from stable_diffusion_webui_distributed_tpu.models import lm

    cfg = family.expander
    if one_pass_fewer:
        cfg = dataclasses.replace(cfg,
                                  total_ut_steps=cfg.total_ut_steps - 1)
    if no_post_norms:
        cfg = dataclasses.replace(cfg, post_sublayer_norm=False)
    module = lm.DecoderLM(cfg, dtype=policy.compute_dtype,
                          quant_linears=control)

    def apply(params, *args, **kwargs):
        """(logits, cache, gates (passes, rows), chosen (rows,))"""
        (logits, cache, _), sown = module.apply(
            {"params": params}, *args, mutable=["passes"], **kwargs)
        return (logits, cache, sown["passes"]["gates"][0],
                sown["passes"]["exit"][0])

    def run(params, ids, continuations):
        shared, decoded = ids.shape[0], continuations.shape[1]
        prefix = split(shared + decoded)[0]
        cache = lm.empty_cache(cfg, shared + decoded, policy.compute_dtype)
        l0, snapshot, g0, c0 = apply(params, ids[:prefix], jnp.int32(0),
                                     jnp.int32(prefix), cache)
        cache = jax.tree_util.tree_map(jnp.copy, snapshot)
        l1, cache, g1, c1 = apply(params, ids[prefix:], jnp.int32(prefix),
                                  jnp.int32(shared - prefix), cache)
        cache = kv.fork(cache, SEQUENCES)

        def between(cache):
            """``last_pass_cache``: every pass finds the last pass's rows."""
            if not last_pass_cache:
                return cache
            return jax.tree_util.tree_map(
                lambda rows: jnp.broadcast_to(rows[:, -1:], rows.shape),
                cache)

        def step(carry, tokens):
            cache, position = carry
            logits, cache, gates, chosen = apply(
                params, tokens, position, jnp.int32(SEQUENCES),
                between(cache), sequences=True)
            return (cache, position + 1), (logits, gates, chosen)

        _, (l2, g2, c2) = jax.lax.scan(
            step, (cache, jnp.int32(shared)), continuations.T)
        # (steps, sequences, ...) -> each sequence's rows in turn
        logits = jnp.concatenate(
            [l0, l1, jnp.moveaxis(l2, 1, 0).reshape(-1, l2.shape[-1])])
        if not with_gates:
            return logits
        # (steps, passes, sequences) -> (passes, each sequence's rows)
        g2 = jnp.transpose(g2, (1, 2, 0)).reshape(g2.shape[1], -1)
        return (logits, jnp.concatenate([g0, g1, g2], axis=1),
                jnp.concatenate([c0, c1, c2.T.reshape(-1)]))

    return run


# -- the reference ------------------------------------------------------------

def _w(leaf):
    import jax.numpy as jnp

    return leaf.astype(jnp.float32)


def _norm(x, p, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _w(p["scale"])


def _rope(x, theta: float):
    """HF's ``apply_rotary_pos_emb`` on ``(T, H, D)``, every dim rotated:
    cos and sin are ``cat(freqs, freqs)``, ``rotate_half`` swaps the halves
    with a sign."""
    import jax.numpy as jnp
    import numpy as np

    dim = x.shape[-1]
    inv = jnp.asarray(
        theta ** (-2.0 * np.arange(dim // 2, dtype=np.float64) / dim),
        jnp.float32)
    freqs = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[:, None, :], jnp.sin(emb)[:, None, :]
    rotated = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rotated * sin


def attention(cfg, n, p):
    """Causal attention over the whole sequence, a head a KV head."""
    import jax
    import jax.numpy as jnp

    tokens = n.shape[0]
    heads, dim = cfg.num_kv_heads, cfg.head_dim
    q = (n @ _w(p["q_proj"]["kernel"])).reshape(tokens, heads, dim)
    k = (n @ _w(p["k_proj"]["kernel"])).reshape(tokens, heads, dim)
    v = (n @ _w(p["v_proj"]["kernel"])).reshape(tokens, heads, dim)
    q, k = _rope(q, cfg.rope_full.theta), _rope(k, cfg.rope_full.theta)
    scores = jnp.einsum("ihd,jhd->hij", q, k) * dim ** -0.5
    seen = jnp.arange(tokens)[:, None] >= jnp.arange(tokens)[None, :]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
    out = jnp.einsum("hij,jhd->ihd", probs, v)
    return out.reshape(tokens, heads * dim) @ _w(p["o_proj"]["kernel"])


def swiglu(n, p):
    import jax

    gate = n @ _w(p["gate_proj"]["kernel"])
    up = n @ _w(p["up_proj"]["kernel"])
    return (jax.nn.silu(gate) * up) @ _w(p["down_proj"]["kernel"])


def layer_forward(cfg, x, p):
    """One sandwich-normed layer over ``(T, C)``."""
    eps = cfg.rms_norm_eps
    a = attention(cfg, _norm(x, p["input_norm"], eps), p["attn"])
    x = x + _norm(a, p["input_norm_2"], eps)
    m = swiglu(_norm(x, p["post_attention_norm"], eps), p["mlp"])
    return x + _norm(m, p["post_attention_norm_2"], eps)


def exit_rule(lam, threshold: float):
    """``t*`` ``(rows,)`` (0 is the first pass) from the gates ``(T,
    rows)``, by the module text's rule."""
    import jax.numpy as jnp

    passes = lam.shape[0]
    survive = jnp.ones_like(lam[0])     # prod_{j < t} (1 - lambda_j)
    total = jnp.zeros_like(lam[0])      # S_t
    # S_T := 1: the last pass, unless an earlier one reaches the threshold
    chosen = jnp.full(lam.shape[1:], passes - 1, jnp.int32)
    found = jnp.zeros(lam.shape[1:], bool)
    for t in range(passes - 1):
        total = total + lam[t] * survive
        survive = survive * (1.0 - lam[t])
        first = (total >= threshold) & ~found
        chosen = jnp.where(first, t, chosen)
        found = found | first
    return chosen


def whole_sequence(cfg, params, ids, norm_every_pass: bool = True):
    """(the states the head may read ``(T, rows, C)``, the gates ``(T,
    rows)``) of one whole sequence."""
    import jax
    import jax.numpy as jnp

    first, count = cfg.vocab
    table = params["embed_tokens"]["embedding"]
    here = (ids >= first) & (ids < first + count)
    x = jnp.where(here[:, None], table[jnp.clip(ids - first, 0, count - 1)]
                  .astype(jnp.float32), 0.0)

    def one_pass(x, _):
        for layer in range(cfg.num_layers):
            x = layer_forward(cfg, x, params[f"layers_{layer}"])
        closed = _norm(x, params["norm"], cfg.rms_norm_eps)
        return (closed if norm_every_pass else x), closed

    _, h = jax.lax.scan(one_pass, x, None, length=cfg.total_ut_steps)
    gate = params["early_exit_gate"]
    lam = jax.nn.sigmoid((h @ _w(gate["kernel"]))[..., 0] + _w(gate["bias"]))
    return h, lam


def forward(family, params, ids, continuations, with_gates: bool = False,
            norm_every_pass: bool = True):
    """Logits at every distinct position, in :func:`program`'s order: one
    full forward over each whole sequence (the shared ids, then its own
    continuation), one sequence after the other; the shared rows of the
    first and the own rows of each. ``with_gates`` adds the gates ``(T,
    rows)`` and ``t*`` ``(rows,)``. ``norm_every_pass`` False is the
    control ``norm_after_last_pass``."""
    import jax
    import jax.numpy as jnp

    cfg = family.expander
    shared = ids.shape[0]

    def whole(b):
        return whole_sequence(cfg, params,
                              jnp.concatenate([ids, continuations[b]]),
                              norm_every_pass)

    with jax.default_matmul_precision("highest"):
        h, lam = jax.lax.map(whole, jnp.arange(continuations.shape[0]))
        # (sequences, T, positions, ...) -> (T, distinct rows, ...)
        h = jnp.concatenate(
            [h[0, :, :shared]] + [h[b, :, shared:]
                                  for b in range(h.shape[0])], axis=1)
        lam = jnp.concatenate(
            [lam[0, :, :shared]] + [lam[b, :, shared:]
                                    for b in range(lam.shape[0])], axis=1)
        chosen = exit_rule(lam, cfg.early_exit_threshold)
        read = jnp.take_along_axis(h, chosen[None, :, None], axis=0)[0]
        logits = read @ _w(params["lm_head"]["kernel"])
    if not with_gates:
        return logits
    return logits, lam, chosen


# -- the readings -------------------------------------------------------------

#: the controls, by name: (which side they change, its keyword arguments)
CONTROLS = (
    ("last_pass_cache", ("program", {"last_pass_cache": True})),
    ("one_pass_fewer", ("program", {"one_pass_fewer": True})),
    ("no_post_norms", ("program", {"no_post_norms": True})),
    ("norm_after_last_pass", ("reference", {"norm_every_pass": False})),
    ("control", ("program", {"control": True})),
)
READING = "_vs_reference_relative_rms"


def relative_rms(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(math.sqrt(np.sum((got - want) ** 2) / np.sum(want ** 2)))


def read_stage(bench, config: dict, stage: str, keep: str, seed=None,
               size=None) -> dict:
    """ONE process's share of the readings, at the configuration's
    ``weight_seed`` or another (weights and ids both come from it). Stage
    ``readings``: the program and the reference, logits and gates; the
    reference's logits are left in ``keep`` as ``want.npy``. Any other
    stage is a name of :data:`CONTROLS`: that control alone, read against
    the file."""
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
        side, kwargs = dict(CONTROLS)[stage]
        if side == "program":
            got = jax.jit(program(family, policy, **kwargs))(
                params, ids, continuations)
        else:
            got = jax.jit(lambda p, i, c: forward(family, p, i, c, **kwargs))(
                params, ids, continuations)
        got = np.asarray(got)
        say("ran")
        out = {stage + READING: relative_rms(got, np.load(
            os.path.join(keep, "want.npy"), mmap_mode="r"))}
        say(f"{stage + READING} {out[stage + READING]:.6g}")
        return out
    out = {"positions": int(ids.shape[0] + continuations.shape[1]),
           "sequences": int(continuations.shape[0]), "seed": seed}
    got, gates, chose = (np.asarray(a) for a in jax.jit(program(
        family, policy, with_gates=True))(params, ids, continuations))
    say("program")
    want, lam, own = (np.asarray(a) for a in jax.jit(
        lambda p, i, c: forward(family, p, i, c, with_gates=True))(
            params, ids, continuations))
    say("reference")
    np.save(os.path.join(keep, "want.npy"), want)
    passes = lam.shape[0]
    out.update(
        rows_compared=int(got.shape[0]),
        program_vs_reference_relative_rms=relative_rms(got, want),
        gates_max_abs_difference=float(np.max(np.abs(gates - lam))),
        chosen_passes_agree_share=float(np.mean(chose == own)),
        token_agreement_argmax_share=float(np.mean(
            np.argmax(got, -1) == np.argmax(want, -1))),
        reference_lambda_max=float(np.max(lam)),
        reference_lambda_mean_by_pass=[float(x) for x in lam.mean(axis=1)],
        reference_rows_by_chosen_pass=[int(np.sum(own == t))
                                       for t in range(passes)],
        program_rows_by_chosen_pass=[int(np.sum(chose == t))
                                     for t in range(passes)],
        reference_rms=float(np.sqrt(np.mean(want.astype(np.float64) ** 2))),
        finite=bool(np.isfinite(got).all() and np.isfinite(want).all()),
        device=device.record())
    for name in ("program_vs_reference_relative_rms",
                 "gates_max_abs_difference", "reference_lambda_max"):
        say(f"{name} {out[name]:.6g}")
    return out


def read_stages(argv: list, stages: list, timeout: float, keep: str,
                out: dict) -> None:
    """Adds to ``out`` what each of ``stages`` reads, a process a stage
    (this one stays off JAX): ``argv`` is this file's command line without
    a stage. A stage that ends badly or outlasts ``timeout`` seconds is
    named under ``failed`` and the others still run."""
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
    ap.add_argument("--timeout", type=float, default=900.0,
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
    gates_limit = float(recorded["tolerance_gates_max_abs"])
    passed = True
    for seed in args.seed or [None]:
        argv = [os.path.abspath(__file__), "--config", args.config,
                "--size", str(args.size)] \
            + ([] if seed is None else ["--seed", str(seed)])
        keep = tempfile.mkdtemp(prefix="ouro-ref-")
        out: dict = {}
        try:
            read_stages(argv, ["readings"], args.timeout, keep, out)
            if "failed" not in out:
                read_stages(argv, names, args.timeout, keep, out)
        finally:
            shutil.rmtree(keep, ignore_errors=True)
        out["tolerance_relative_rms"] = limit
        out["tolerance_gates_max_abs"] = gates_limit
        out["passed"] = "failed" not in out and out["finite"] and (
            out["program_vs_reference_relative_rms"] < limit
            < min([out[n + READING] for n in names] or [float("inf")])
        ) and out["gates_max_abs_difference"] < gates_limit
        passed &= out["passed"]
        print(json.dumps(out), flush=True)
    sys.exit(0 if passed else 1)

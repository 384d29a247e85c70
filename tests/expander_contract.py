"""What every resident prompt expander's tests say alike, once: the helpers,
a record a model (:class:`Case`) and the classes of tests that read it. A
model's file (``tests/test_<model>_expander.py``) is its ``Case``, one
``class TestX(contract.X): CASE = CASE`` for each contract it is held to,
and the tests of what only it has. A new expander adds a ``Case`` and its
own kind's tests, not a copy of a file.

A case pays for what it tests: parameters come from ONE jitted program
(:func:`lm_params`), every prefill, step and chunk goes through an
executable jitted once a ``(cfg, static arguments)`` (:func:`run`,
:func:`executables`), and nothing calls ``module.apply`` eagerly, where
every primitive of every new shape is a compile of its own (566 of them in
one case before this file; CHANGES.md, PR 61).

A base class's parametrised test takes its cases from the ``PARAMETERS`` of
the class that binds it (``tests/conftest.py:pytest_generate_tests``), and a
check the files name differently has no ``test_`` prefix here and is bound
under each file's name (``test_x = contract.X.check``), so that each model
keeps the ids it had.
"""

import dataclasses
import functools
import importlib.util
import os
import types
import zlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.obs import spans
from stable_diffusion_webui_distributed_tpu.pipeline import expand
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
)
from stable_diffusion_webui_distributed_tpu.runtime import dtypes, rng
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER, METRICS,
)
from tests.test_pipeline import init_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = expand.DECODE_STEPS
#: the engine tests' request: a 30-word instruction (31 tokens), a 5-token
#: prompt in a chunk of 64, two decode chunks
CAPACITY = kv.capacity_for(31 + 64 + 2 * STEPS)


# -- the helpers ----------------------------------------------------------------

def load_reference(name):
    """``benchmarks/reference/<name>_ref.py``, the benchmark's own plain
    reference, as a module of its own."""
    path = os.path.join(ROOT, "benchmarks", "reference", f"{name}_ref.py")
    spec = importlib.util.spec_from_file_location(f"{name}_ref_for_tests",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def close(a, b, tol=2e-5):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(x, y, rtol=tol, atol=tol)


def count(tree):
    """The parameters of a tree of arrays or of shapes."""
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(tree))


def _init(cfg, key, **how):
    return lm.DecoderLM(cfg).init(
        key, jnp.zeros((4,), jnp.int32), jnp.int32(0), jnp.int32(4),
        lm.empty_cache(cfg, 8, jnp.float32), **how)["params"]


def param_shapes(cfg, **how):
    """``DecoderLM.init``'s tree as shapes: nothing runs or compiles."""
    return jax.eval_shape(lambda: _init(cfg, jax.random.key(0), **how))


@functools.lru_cache(maxsize=None)
def _seeded(cfg, spread, shift, a_log):
    """The program behind :func:`lm_params`, compiled once for every seed
    of a config."""
    spread, shift = dict(spread), dict(shift)

    def make(key, noise):
        def off(path, x):
            name = getattr(path[-1], "key", "")
            if name == "A_log" and a_log is not None:
                return jnp.asarray(a_log, jnp.float32)
            if name in spread:
                x = x + spread[name] * jax.random.normal(
                    jax.random.fold_in(
                        noise, zlib.crc32(str(path).encode()) % 2 ** 31),
                    x.shape)
            return x + shift[name] if name in shift else x

        return jax.tree_util.tree_map_with_path(off, _init(cfg, key))

    return jax.jit(make)


@functools.lru_cache(maxsize=None)
def lm_params(cfg, seed=0, spread=(), shift=(), a_log=None):
    """``DecoderLM.init``'s tree from ONE jitted program, with the leaves a
    model would otherwise read alike moved off their initial values, so
    that reading one norm as another, leaving a bias out or sharing a
    state would show. ``spread``: ``(leaf name, deviation)`` pairs, the
    leaf plus that much seeded noise (a norm's ``scale`` off 1, a
    selection bias off 0); ``shift``: ``(leaf name, constant)`` pairs;
    ``a_log``: every delta mixer's decay rates. The tree is cached: do not
    write to it."""
    return _seeded(cfg, spread, shift, a_log)(
        jax.random.key(seed), jax.random.key(seed + 100))


@functools.lru_cache(maxsize=None)
def _applied(cfg, how):
    module = lm.DecoderLM(cfg)
    return jax.jit(lambda params, ids, start, length, cache: module.apply(
        {"params": params}, ids, start, length, cache, **dict(how)))


def run(cfg, params, ids, start, length, cache, **how):
    """``DecoderLM(cfg).apply`` on a chunk, through an executable jitted
    once a ``(cfg, how)`` (and compiled once a shape): (logits, the cache
    after it, the routing)."""
    return _applied(cfg, tuple(sorted(how.items())))(
        params, ids, jnp.int32(start), jnp.int32(length), cache)


def sites_of(cfg, params, ids, start, length, cache, dtype=jnp.float32,
             **how):
    """The same call traced and not run, on arrays or on shapes: what a
    trace counts (attention sites, mixers, products) is counted, nothing
    compiles."""
    module = lm.DecoderLM(cfg, dtype=dtype)
    return jax.eval_shape(lambda p, c: module.apply(
        {"params": p}, ids, jnp.int32(start), jnp.int32(length), c, **how),
        params, cache)


def empty(cfg, capacity=64):
    return lm.empty_cache(cfg, capacity, jnp.float32)


def cache_structs(cfg, capacity, dtype=jnp.bfloat16):
    """A one-sequence cache as shapes, each buffer in the dtype it has."""
    return {name: [jax.ShapeDtypeStruct(shape, lm.buffer_dtype(name, dtype))
                   for shape in rows]
            for name, rows in lm.cache_shapes(cfg, capacity).items()}


def forked_structs(cfg, capacity, sequences, own_slots, dtype=jnp.bfloat16):
    """The shapes of a forked cache, as ``kv.fork`` lays it out."""
    return jax.eval_shape(lambda c: kv.fork(c, sequences, own_slots),
                          cache_structs(cfg, capacity, dtype))


def keys(indices, seed=77):
    return jnp.stack([rng.key_for_image(seed, i) for i in indices])


class Executables(NamedTuple):
    """Of one config: the one-sequence decode chunk, the several-sequences
    one, and a step of each that returns (logits, the cache after it)."""
    alone: object
    together: object
    one_step: object
    forked_step: object


@functools.lru_cache(maxsize=None)
def executables(cfg, steps=STEPS):
    module = lm.DecoderLM(cfg)
    one, forked = _applied(cfg, ()), _applied(cfg, (("sequences", True),))
    return Executables(
        jax.jit(lm.decode_chunk_fn(module, steps)),
        jax.jit(lm.decode_sequences_fn(module, steps)),
        lambda params, cache, token, position: one(
            params, token[None], position, jnp.int32(1), cache)[:2],
        lambda params, cache, tokens, position, live: forked(
            params, tokens, position, live, cache)[:2])


def decoded(cfg, params, steps, calls, capacity=128):
    """(the tokens, the cache) of ``calls`` decode chunks of ``steps`` from
    an empty cache, one key, temperature 1."""
    fn = executables(cfg, steps).alone
    cache = empty(cfg, capacity)
    token, position, made = jnp.int32(cfg.vocab[0] + 3), jnp.int32(0), []
    for _ in range(calls):
        cache, token, position, out, *_ = fn(
            params, cache, token, position, jax.random.key(11),
            jnp.float32(1.0))
        made += np.asarray(out).tolist()
    return made, cache


def prefilled(cfg, params, user, prefix=21, capacity=None, bucket=None):
    """(the prompt's last row of logits, the cache, its length) after a
    prefix's chunk (none at ``prefix`` 0) and a prompt of ``user`` real
    tokens in its padded chunk: the bucket's other rows land behind the
    prompt in every buffer that has positions, where a forked step must
    not see them, and must leave a state and kept rows where the prompt's
    last real token put them."""
    bucket = bucket or kv.chunk_bucket(user)
    capacity = capacity or kv.capacity_for(prefix + bucket + 2 * STEPS)
    first, held = cfg.vocab
    ids = jax.random.randint(jax.random.key(user), (prefix + bucket,),
                             first, first + held)
    cache = empty(cfg, capacity)
    if prefix:
        _, cache, _ = run(cfg, params, ids[:prefix], 0, prefix, cache,
                          all_logits=False)
    row, cache, _ = run(cfg, params, ids[prefix:], prefix, user, cache,
                        all_logits=False)
    return row[0], cache, prefix + user


def assert_own_rows(alone, forked, b, first, steps, tol=2e-5):
    """Sequence ``b`` of a forked cache against the cache of that sequence
    decoded alone for ``steps`` positions from ``first``. What has no
    positions (``lm.slots_axis`` None: a state, kept rows) is the
    sequence's own copy, whole. What has them: position ``p`` lies in the
    sequence's own slot ``(p - first) % its slots``, and alone in slot ``p %
    slots`` of a buffer or a ring, which keeps the last ``slots`` only."""
    for name, rows in alone.items():
        axis = lm.slots_axis(name)
        for mine, theirs in zip(rows, forked[name]):
            mine, theirs = np.asarray(mine), np.asarray(theirs[b])
            if axis is not None:
                slots = mine.shape[axis]
                positions = np.arange(max(first, first + steps - slots),
                                      first + steps)
                mine = np.take(mine, positions % slots, axis=axis)
                theirs = np.take(theirs,
                                 (positions - first) % theirs.shape[axis],
                                 axis=axis)
            np.testing.assert_allclose(mine, theirs, rtol=tol, atol=tol,
                                       err_msg=name)


def forked_against_alone(case, params, live, batch, own_slots=2 * STEPS,
                         **prefill):
    """``batch`` sequences forked from one prefill (:func:`prefilled` with
    ``prefill``) against each of the ``live`` decoded alone from the same
    cache by the one-sequence executable: a chunk of steps token for
    token, the rows, states and kept rows written, the experts' load
    without the pad, a pad's copies left as the fork made them, and the
    logits of a few teacher-forced steps after it. Gives back what the
    chunk of all returned beside its cache and tokens, and what each
    sequence's own did, for what only one model counts."""
    cfg = case.cfg
    fns = executables(cfg)
    row, cache, length = prefilled(cfg, params, **prefill)
    own_keys = keys(list(range(live)) + [live - 1] * (batch - live))
    first = lm.sample_each(row, own_keys, length, jnp.float32(1.0),
                           cfg.vocab[0])
    (forked, tokens, position, made, load, none_held, read,
     *rest) = fns.together(
        params, kv.fork(cache, batch, own_slots), first, jnp.int32(length),
        own_keys, jnp.float32(1.0), jnp.int32(live))
    assert made.shape == (STEPS, batch) and int(position) == length + STEPS
    # the shared rows are the prefill's, untouched
    for name, shared in lm.SHARED_OF.items():
        for mine, theirs in zip(cache.get(name, ()), forked.get(shared, ())):
            assert np.array_equal(np.asarray(mine), np.asarray(theirs))
    assert int(forked[lm.FORKED_AT][0][0, 0]) == length
    own, total, none, rests, nones = [], 0, 0, [], []
    for b in range(live):
        assert int(first[b]) == int(lm.sample(
            row, own_keys[b], length, jnp.float32(1.0), cfg.vocab[0]))
        after, last, _, steps, own_load, own_none, *own_rest = fns.alone(
            params, cache, first[b], jnp.int32(length), own_keys[b],
            jnp.float32(1.0))
        rests.append(own_rest)
        assert np.array_equal(steps, made[:, b]), b
        assert int(last) == int(tokens[b])
        assert_own_rows(after, forked, b, length, STEPS, case.rows_tolerance)
        own.append(after)
        total, none = total + own_load, none + own_none
        nones.append(np.asarray(own_none))
    assert len({tuple(np.asarray(made[:, b])) for b in range(live)}) == live
    if cfg.expert_layers:
        # the steps in which NO row chose a held expert: each sequence
        # found none in those at least, and one sequence's are its own
        unread, *rest = rest
        assert unread.shape == none_held.shape and unread.dtype == jnp.int32
        assert np.all(unread <= np.min(nones, axis=0))
        if live == 1:
            assert np.array_equal(unread, none_held)
        assert np.array_equal(load, total)      # the pad is not counted
        assert np.array_equal(none_held, none)
        if cfg.experts[1] == cfg.num_experts:
            # all held: distinct experts a step, never over the picks,
            # never under one sequence's a layer
            k = cfg.num_experts_per_tok
            assert int(none_held.sum()) == 0
            assert np.all(read >= STEPS * k) and np.all(
                read <= STEPS * min(live * k, cfg.num_experts))
            assert int(read.sum()) <= int(load.sum())
            if live == 1:   # a step of one token reads as many as it picks
                assert int(read.sum()) == STEPS * k * len(cfg.expert_layers)
        else:
            assert int(none_held.sum()) > 0     # a share: some find none
    for b in range(live, batch):                # a pad's copies stay
        for name in cache:
            if lm.slots_axis(name) is None:
                for mine, theirs in zip(cache[name], forked[name]):
                    assert np.array_equal(np.asarray(mine),
                                          np.asarray(theirs[b]))
    forced = jax.random.randint(jax.random.key(8), (4, batch),
                                *np.cumsum(cfg.vocab))
    tol = case.step_tolerance
    for t, row in enumerate(forced):
        at = jnp.int32(length + STEPS + t)
        logits, forked = fns.forked_step(params, forked, row, at,
                                         jnp.int32(live))
        for b in range(live):
            want, own[b] = fns.one_step(params, own[b], row[b], at)
            np.testing.assert_allclose(logits[b], want[0], rtol=tol,
                                       atol=tol)
    return rest, rests


@functools.lru_cache(maxsize=None)
def _joiner(region, slots):
    return jax.jit(functools.partial(kv.joined_rows, region=region,
                                     slots=slots))


def joined_against_alone(case, params, users, batch, prefix=21,
                         capacity=256):
    """``len(users)`` sequences that continue prompts of their OWN
    (``users``: their lengths, unequal) behind one shared prefix, JOINED
    into one cache (cache/kv.py:joined_rows) and decoded as ``batch``
    sequences a step, against each decoded alone from its own
    one-sequence cache: a chunk of steps token for token (every draw keyed
    by the sequence's own position), the shared range untouched, the
    experts' load without the pad, and the logits of a few teacher-forced
    steps after it. The prefix's 21 positions have wrapped a ring of 8, and
    a window's reach into it differs a sequence."""
    cfg = case.cfg
    fns = executables(cfg)
    first, held = cfg.vocab
    live = len(users)
    heat = jnp.float32(1.0)
    ids = jax.random.randint(jax.random.key(1), (prefix,), first,
                             first + held)
    _, shared, _ = run(cfg, params, ids, 0, prefix, empty(cfg, capacity),
                       all_logits=False)
    own_keys = keys(list(range(live)) + [live - 1] * (batch - live))
    caches, firsts = [], []
    for b, user in enumerate(users):
        ids = jax.random.randint(jax.random.key(100 + b),
                                 (kv.chunk_bucket(user),), first,
                                 first + held)
        row, cache, _ = run(cfg, params, ids, prefix, user, shared,
                            all_logits=False)
        caches.append(cache)
        firsts.append(lm.sample(row[0], own_keys[b], prefix + user, heat,
                                first))
    pad = batch - live
    region = max(kv.chunk_bucket(user) for user in users)
    own = _joiner(region, 2 * STEPS)(
        tuple(caches + caches[-1:] * pad),
        jnp.asarray(list(users) + list(users[-1:]) * pad, jnp.int32),
        jnp.int32(prefix))
    assert np.array_equal(own[lm.OWN_FROM][0][:live, 0],
                          region - np.asarray(users))
    joined, tokens, position, made, load, none_held, *_ = fns.together(
        params, kv.forked(shared, own), jnp.stack(firsts + firsts[-1:] * pad),
        jnp.int32(prefix + region), own_keys, heat, jnp.int32(live))
    assert made.shape == (STEPS, batch)
    assert int(position) == prefix + region + STEPS
    for name, twin in lm.SHARED_OF.items():     # the shared range: as it was
        for mine, theirs in zip(shared.get(name, ()), joined.get(twin, ())):
            assert np.array_equal(np.asarray(mine), np.asarray(theirs))
    assert np.all(np.asarray(joined[lm.FORKED_AT][0]) == prefix)
    alone, total = [], 0
    for b, user in enumerate(users):
        after, last, at, steps, own_load, *_ = fns.alone(
            params, caches[b], firsts[b], jnp.int32(prefix + user),
            own_keys[b], heat)
        assert np.array_equal(steps, made[:, b]), (b, user)
        assert int(last) == int(tokens[b])
        alone.append(after)
        total = total + own_load
    if cfg.expert_layers:
        assert np.array_equal(load, total)      # the pad is not counted
    forced = jax.random.randint(jax.random.key(8), (4, batch),
                                *np.cumsum(cfg.vocab))
    # (a joined step sums a prompt's keys among its sequence's own rows,
    # a step alone among the buffer's: another order of float32 sums)
    tol = 3 * case.step_tolerance
    for t, row in enumerate(forced):
        logits, joined = fns.forked_step(
            params, joined, row, jnp.int32(prefix + region + STEPS + t),
            jnp.int32(live))
        for b, user in enumerate(users):
            want, alone[b] = fns.one_step(
                params, alone[b], row[b],
                jnp.int32(prefix + user + STEPS + t))
            np.testing.assert_allclose(logits[b], want[0], rtol=tol,
                                       atol=tol)


@functools.lru_cache(maxsize=None)
def tiny_params():
    """The tiny SD family's weights without a program: ``init_params``'
    tree as shapes, filled on the host as its initialisers fill it (a
    kernel N(0, 1 / fan_in), a norm's scale 1, a bias 0, a table N(0,
    0.02)). ``init_params`` itself is one compile a leaf, 30 s a process,
    and stays as it is for tests/goldens.json, which pins its bits;
    nothing here reads an image against a golden."""
    rng = np.random.default_rng(0)

    def fill(path, x):
        name = getattr(path[-1], "key", "")
        if name in ("bias", "scale"):
            return jnp.asarray(np.full(x.shape, name == "scale", x.dtype))
        deviation = np.prod(x.shape[:-1]) ** -0.5 if name == "kernel" \
            else 0.02
        return jnp.asarray(rng.normal(0.0, deviation, x.shape), x.dtype)

    return jax.tree_util.tree_map_with_path(
        fill, jax.eval_shape(lambda: init_params(configs.TINY)))


def engine_for(family, cfg=None, **how):
    """An engine of the tiny SD family with ``cfg``'s seeded weights
    (``family``'s own expander's by default) as its expander."""
    params = dict(tiny_params())
    params["expander"] = lm_params(cfg or family.expander, 1, **how)
    return Engine(family, params, chunk_size=4, state=GenerationState())


def span_events():
    return [e for e in spans.TRACER.export_chrome()["traceEvents"]
            if e.get("ph") == "X"]


def assert_expand_spans_under_expand(events):
    """Every ``expand.*`` span lies under ``expand`` but the accounting,
    which comes down once the UNet is queued."""
    by_id = {e["args"]["span_id"]: e for e in events}
    for e in events:
        if e["name"].startswith("expand."):
            assert by_id[e["args"]["parent_id"]]["name"] == (
                "denoise_range" if e["name"] == "expand.account"
                else "expand")


# -- a model's record -------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Case:
    """What the contracts read of one model."""
    #: the tiny family preset whose ``expander`` is the model
    family: object
    #: its plain reference (:func:`load_reference`)
    reference: types.ModuleType
    #: how :func:`lm_params` moves its leaves off their initial values
    how: tuple = ()
    #: the instruction's words (the older files say ``rule``)
    word: str = "word"
    #: program against reference, relative RMS of the logits
    tolerance: float = 1e-5
    #: what the reference is asked for beside the logits: ``with_routing``
    #: (the experts chosen, which the program's must equal)
    extra: str = "with_routing"
    #: a control misses the reference by more than this, at this size
    control_floor: float = 1e-3
    control_size: int = 148
    #: its controls by name, in order (None: the reference's own list is
    #: not pinned)
    controls: tuple = None
    #: the staged two executables against the one, absolute and relative
    staged_tolerance: float = 1e-6
    #: forked against alone: the rows written, a teacher-forced step
    rows_tolerance: float = 2e-5
    step_tolerance: float = 1e-5

    @property
    def cfg(self):
        return self.family.expander

    def params(self, seed=0, cfg=None):
        return lm_params(cfg or self.cfg, seed, **dict(self.how))

    def script(self, **args):
        instruction = " ".join(f"{self.word}{i}" for i in range(30))
        return {"prompt expansion": {"args": [dict(
            {"instruction": instruction, "max_new_tokens": 40,
             "temperature": 1.0, "ignore_eos": True, "context_chunks": 1},
            **args)]}}

    def payload(self, **kw):
        base = dict(prompt="a cow in a valley", steps=4, width=32,
                    height=32, seed=1234, alwayson_scripts=self.script())
        base.update(kw)
        return GenerationPayload(**base)

    def engine(self, family=None):
        return engine_for(family or self.family, self.cfg, **dict(self.how))

    @functools.cached_property
    def _forward(self):
        return jax.jit(lambda p, *a: self.reference.forward(
            self.family, p, *a, **{self.extra: True}))

    def referred_on(self, *inputs):
        """(the inputs, the reference's logits on them, then what it
        returns beside them) on the seeded weights."""
        return (inputs, *self._forward(self.params(), *inputs))

    @functools.lru_cache(maxsize=None)
    def referred(self, size, seed=3):
        """:meth:`referred_on` the reference file's own inputs of ``size``
        positions."""
        return self.referred_on(*self.reference.inputs(self.family, seed,
                                                       size))

    @functools.lru_cache(maxsize=None)
    def program(self, **how):
        """The reference file's program of the serving path, jitted once
        a set of controls."""
        return jax.jit(self.reference.program(self.family, dtypes.F32,
                                              **how))


def fixtures(case):
    """The module-scoped ``params`` and ``engine`` of a model's file:
    ``params, engine = contract.fixtures(CASE)``."""
    return (pytest.fixture(scope="module", name="params")(case.params),
            pytest.fixture(scope="module", name="engine")(case.engine))


# -- the contracts ----------------------------------------------------------------

class ForkedAgainstTheReference:
    """A model whose images of one request are sequences of one step: the
    reference file's program (the prefix as one chunk, a copy, the
    prompt's chunk, a fork into four and one step over all four a
    position) against a full forward of each whole sequence."""
    CASE: Case
    #: what the program is asked for beside the logits
    PROGRAM = {"with_routing": True}

    def check_extra(self, got, want, rows):
        """What program and reference gave beside the logits: the routing,
        identical."""
        cfg = self.CASE.cfg
        (got,), (want,) = got, want
        assert got.shape == (len(cfg.expert_layers), rows,
                             cfg.num_experts_per_tok)
        assert np.array_equal(np.sort(got, -1), np.sort(want, -1))

    def program_matches_four_full_forwards(self, params, size):
        case, ref = self.CASE, self.CASE.reference
        prefix, user, decoded = ref.split(size)
        inputs, want, *own = case.referred(size)
        out = case.program(**self.PROGRAM)(params, *inputs)
        got, *extra = out if self.PROGRAM else (out,)
        rows = prefix + user + ref.SEQUENCES * decoded
        assert got.shape == want.shape == (rows, case.cfg.vocab[1])
        assert got.dtype == want.dtype == jnp.float32
        assert rel_rms(got, want) < case.tolerance
        self.check_extra(extra, own, rows)
        # the four continuations part at their first row
        tails = np.asarray(got[prefix + user:]).reshape(
            ref.SEQUENCES, decoded, -1)
        assert rel_rms(tails[1], tails[0]) > 0.1

    def test_each_control_is_further_from_the_reference(self, params,
                                                        control):
        """Each control of the reference file misses the reference by far
        more than the tolerance the program meets."""
        case, ref = self.CASE, self.CASE.reference
        inputs, want, *_ = case.referred(case.control_size)
        lower = case.program(**dict(ref.CONTROLS)[control])(params, *inputs)
        assert rel_rms(lower, want) > case.control_floor, control
        if case.controls:
            assert tuple(name for name, _ in ref.CONTROLS) == case.controls


class StagedAsTheTimedPathRunsIt:
    """The chip's readings may run the chunks with the fork and the decode
    steps as two executables, as the timed path does: the same logits and
    routing as the one jitted whole."""
    CASE: Case

    def test_the_two_executables_give_what_the_one_gives(self, params):
        case, ref = self.CASE, self.CASE.reference
        inputs = ref.inputs(case.family, 3, 37)
        whole, chose = case.program(with_routing=True)(params, *inputs)
        got, chose_staged = ref.staged(case.family, dtypes.F32, params,
                                       *inputs)
        tol = case.staged_tolerance
        np.testing.assert_allclose(got, whole, rtol=tol, atol=tol)
        assert np.array_equal(chose, chose_staged)


OWN_ROWS = jax.jit(kv.own_rows, static_argnums=(1, 2))


class SequencesOfOneStep:
    """A step over several sequences forked from one prefill against each
    decoded alone."""
    CASE: Case

    def test_a_forked_decode_is_each_sequence_alone(self, params, user,
                                                    live, batch):
        forked_against_alone(self.CASE, params, live, batch, user=user)

    @pytest.mark.parametrize("users,batch", [
        ((5, 16, 63), 4),       # one chunk bucket, a pad
        ((7, 100), 2)])         # across two
    def test_a_joined_decode_is_each_sequence_alone(self, params, users,
                                                    batch):
        """Sequences that continue prompts of their own behind one kept
        prefix (the requests of a dispatch group), joined into one scan."""
        joined_against_alone(self.CASE, params, users, batch)

    def check_fork(self, forked):
        """What only this model's forked cache must show."""

    def a_fork_shares_what_has_positions_and_copies_the_rest(self, params):
        """The shared buffers ARE the prefill's, buffers, rings and latents
        alike; what is made is a few rows a sequence, a copy a sequence of
        what has no positions, and the position, not yet known. The
        engine's fork executable makes the same from shapes."""
        cfg = self.CASE.cfg
        _, cache, _ = prefilled(cfg, params, 5)
        shapes = lm.cache_shapes(cfg, 256)
        forked = kv.fork(cache, 4, 2 * STEPS)
        again = kv.forked(cache, OWN_ROWS(cache, 4, 2 * STEPS))
        assert jax.tree_util.tree_structure(again) \
            == jax.tree_util.tree_structure(forked)
        assert set(forked) == set(shapes) | {lm.FORKED_AT} | {
            lm.SHARED_OF[name] for name in shapes if name in lm.SHARED_OF}
        for name, rows in shapes.items():
            axis = lm.slots_axis(name)
            if axis is None:        # no positions: every sequence its copy
                for mine, theirs, made in zip(cache[name], forked[name],
                                              again[name]):
                    assert np.any(np.asarray(mine))
                    for b in range(4):
                        assert np.array_equal(np.asarray(mine),
                                              np.asarray(theirs[b]))
                    assert np.array_equal(np.asarray(theirs),
                                          np.asarray(made))
                continue
            for one in (forked, again):
                assert all(mine is theirs for mine, theirs
                           in zip(cache[name], one[lm.SHARED_OF[name]]))
            own = [list(shape) for shape in rows]
            for shape in own:
                shape[axis] = 2 * STEPS
            assert [x.shape for x in forked[name]] \
                == [(4, *shape) for shape in own]
            assert not any(np.any(np.asarray(x)) for x in forked[name])
            # without a count of slots a sequence gets a buffer's own
            assert [x.shape for x in kv.fork(cache, 2)[name]] \
                == [(2, *shape) for shape in rows]
        (at,) = forked[lm.FORKED_AT]
        assert at.shape == (4, 1) and np.all(np.asarray(at) == -1)
        self.check_fork(forked)


class StatesOfOneStep:
    """What a model with a recurrent state adds to a shared step: every
    sequence its own copy, which no neighbour and no snapshot's later user
    can move."""
    CASE: Case

    def test_a_sequence_that_has_ended_leaves_the_others_alone(self, params):
        """A sequence goes on being stepped after its end-of-sequence (its
        tokens are cut afterwards): whatever it is fed, the other
        sequences' logits and every buffer of theirs are bit for bit what
        they are beside any other neighbour."""
        cfg = self.CASE.cfg
        fns = executables(cfg)
        row, cache, length = prefilled(cfg, params, 7)
        tokens = jnp.array([130, 131, 132, 133], jnp.int32) % cfg.vocab[1]
        results = []
        for fed in (5, 99):
            forked = kv.fork(cache, 4, STEPS)
            for t in range(3):
                logits, forked = fns.forked_step(
                    params, forked, tokens.at[2].set(fed + t),
                    jnp.int32(length + t), jnp.int32(4))
            results.append((logits, forked))
        (a, ca), (b, cb) = results
        others = np.array([0, 1, 3])
        assert np.array_equal(np.asarray(a)[others], np.asarray(b)[others])
        assert not np.array_equal(np.asarray(a)[2], np.asarray(b)[2])
        for name in lm.cache_shapes(cfg, 8):
            for mine, theirs in zip(ca[name], cb[name]):
                assert np.array_equal(np.asarray(mine)[others],
                                      np.asarray(theirs)[others]), name
                assert not np.array_equal(np.asarray(mine)[2],
                                          np.asarray(theirs)[2]), name

    def a_snapshot_restores_every_buffer(self, params):
        """What the manager keeps after the instruction's last token is a
        copy of every kind of buffer; a request that starts from it gets
        copies again, whatever the one before did to its own."""
        cfg = self.CASE.cfg
        manager = kv.KVCacheManager(cfg, jnp.float32)
        prefix = tuple(range(1, 22))
        cache, held = manager.acquire(prefix, 256)
        assert held == 0 and not any(
            np.any(np.asarray(x)) for x in jax.tree_util.tree_leaves(cache))
        _, cache, _ = run(cfg, params, jnp.asarray(prefix, jnp.int32), 0, 21,
                          cache, all_logits=False)
        manager.keep_prefix(prefix, 256, cache)
        kept = jax.tree_util.tree_map(np.asarray, cache)
        first, held = manager.acquire(prefix, 256)
        assert held == 21 and manager.snapshots == 1
        # the request runs on and spoils its copy
        _, spoiled, _ = run(cfg, params, jnp.arange(5, dtype=jnp.int32), 21,
                            5, first, all_logits=False)
        assert not any(np.array_equal(np.asarray(mine), theirs)
                       for mine, theirs in zip(
                           jax.tree_util.tree_leaves(spoiled),
                           jax.tree_util.tree_leaves(kept)))
        second, held = manager.acquire(prefix, 256)
        assert held == 21
        assert set(kept) == set(lm.cache_shapes(cfg, 256))
        for name in kept:       # copies: the executables donate their cache
            for mine, theirs, made in zip(kept[name], second[name],
                                          cache[name]):
                assert theirs is not made
                assert np.array_equal(mine, np.asarray(theirs)), name


class WhichKindsShareAStep:
    #: the published shares (``configs.<name>()``) that share a step, and
    #: those that do not
    SHARE, ONE_A_STEP = (), ()

    def test_which_kinds_share_a_step(self, preset, shares):
        """Every layer a row a position or a recurrent state with a
        sequence axis, and one stream: else one sequence a step, and the
        model refuses ``sequences``."""
        cfg = getattr(configs, preset).expander
        assert lm.shares_a_step(cfg) is shares
        if not shares:
            with pytest.raises(ValueError):
                param_shapes(cfg, sequences=True)
        for name in self.SHARE + self.ONE_A_STEP:
            assert lm.shares_a_step(getattr(configs, name)().expander) \
                is (name in self.SHARE)


class OneSequenceAgainstTheReference:
    """A model decoded one sequence a step: the program (prefix prefill,
    the user chunk against a copy of the snapshot, then one token a step
    through the cache) against the reference's one full forward."""
    CASE: Case
    #: the buffer a decode step cannot do without
    DROPPED: str

    def prefill_then_decode_matches_the_full_forward(self, params, size):
        case = self.CASE
        inputs, want, own = case.referred(size)
        got, chose = case.program(with_routing=True)(params, *inputs)
        assert got.shape == want.shape == (size, case.cfg.vocab[1])
        assert rel_rms(got, want) < case.tolerance
        assert np.array_equal(np.sort(chose, -1), np.sort(own, -1))

    def a_buffer_that_is_dropped_shows(self, params):
        """The tolerance means something: decoding from a zeroed state,
        latent or kept rows is far from the reference."""
        case, cfg = self.CASE, self.CASE.cfg
        (ids,), want, _ = case.referred(40)
        _, cache, _ = run(cfg, params, ids[:30], 0, 30, empty(cfg))
        kept, _, _ = run(cfg, params, ids[30:31], 30, 1, cache)
        cache[self.DROPPED] = [jnp.zeros_like(x)
                               for x in cache[self.DROPPED]]
        dropped, _, _ = run(cfg, params, ids[30:31], 30, 1, cache)
        assert rel_rms(kept, want[30:31]) < case.tolerance
        assert rel_rms(dropped, want[30:31]) > 1e-2


class OneChunkAgainstTheReference:
    CASE: Case

    def test_one_chunk_matches_the_full_forward(self, params, size):
        case, cfg = self.CASE, self.CASE.cfg
        (ids,), want, own = case.referred(size)
        got, _, routed = run(cfg, params, ids, 0, size,
                             empty(cfg, kv.capacity_for(size)))
        assert got.shape == want.shape == (size, cfg.vocab[1])
        assert rel_rms(got, want) < case.tolerance
        assert np.array_equal(np.sort(routed[0], -1), np.sort(own, -1))


class PaddingAndSnapshots:
    CASE: Case

    def test_snapshot_plus_prompt_equals_one_whole_prefill(self, params):
        cfg = self.CASE.cfg
        (ids,) = self.CASE.reference.inputs(self.CASE.family, 7, 48)
        whole, cache_w, _ = run(cfg, params, ids, 0, 48, empty(cfg))
        first, snapshot, _ = run(cfg, params, ids[:31], 0, 31, empty(cfg))
        copy = jax.tree_util.tree_map(jnp.copy, snapshot)
        rest, cache_s, _ = run(cfg, params, ids[31:], 31, 17, copy)
        np.testing.assert_allclose(jnp.concatenate([first, rest]), whole,
                                   rtol=5e-5, atol=5e-5)
        for name in cache_w:        # a ring keeps other rows; the rest alike
            axis = lm.slots_axis(name)
            written = [[x if axis is None else np.take(x, range(48), axis)
                        for x in cache[name]
                        if axis is None or x.shape[axis] >= 48]
                       for cache in (cache_s, cache_w)]
            close(*written, 5e-5)
        # the snapshot itself is as the prefix's last token left it
        again, _, _ = run(cfg, params, ids[31:], 31, 17, snapshot)
        np.testing.assert_array_equal(again, rest)

    def test_decoding_cut_into_chunks_equals_one_scan(self, params):
        cfg = self.CASE.cfg
        one, cache_one = decoded(cfg, params, 64, 1)
        cut, cache_cut = decoded(cfg, params, 32, 2)
        assert one == cut and len(set(one)) > 8
        close(cache_one, cache_cut)


class TheCacheManager:
    CASE: Case

    def a_snapshot_is_handed_out_as_a_copy(self):
        cfg = self.CASE.cfg
        manager = kv.KVCacheManager(cfg, jnp.float32)
        cache, held = manager.acquire([1, 2, 3], 256)
        assert held == 0 and manager.snapshots == 0
        manager.keep_prefix([1, 2, 3], 256,
                            jax.tree_util.tree_map(lambda x: x + 1, cache))
        again, held = manager.acquire([1, 2, 3], 256)
        assert held == 3 and manager.snapshots == 1
        assert set(again) == set(lm.cache_shapes(cfg, 256))
        assert all(float(x.min()) == float(x.max()) == 1.0
                   for x in jax.tree_util.tree_leaves(again))
        for rows in again.values():     # what is handed out is not held
            rows[-1] = rows[-1] + 1
        third, _ = manager.acquire([1, 2, 3], 256)
        assert all(float(x.max()) == 1.0
                   for x in jax.tree_util.tree_leaves(third))
        # a shorter prefix is another prefix: a state cannot be cut back
        assert manager.acquire([1, 2], 256)[1] == 0


class ShardingRules:
    """What makes and reads a model's own leaves stays whole on every chip
    (no rule splits it over ``tp``), the experts lie over ``ep`` and the
    head over ``vp``."""
    #: (path, dimensions) of leaves no rule may split
    WHOLE = ()
    #: a layer with experts (None: the model has none)
    EXPERT_LAYER = None
    #: key paths of placed leaves that stay whole on an ``(ep, vp)`` mesh
    PLACED_WHOLE = ()

    def check_placed(self, placed, mesh):
        """What else this model's placed tree must show."""

    def sharding_rules(self, params):
        from jax.sharding import PartitionSpec as P

        from stable_diffusion_webui_distributed_tpu.parallel.sharding import (
            shard_params, tp_spec_for,
        )

        for path, ndim in self.WHOLE:
            assert tp_spec_for(path, ndim) == P(), path
        devices = np.array(jax.devices()[:4]).reshape(2, 2)
        mesh = jax.sharding.Mesh(devices, ("ep", "vp"))
        placed = shard_params(params, mesh)
        if self.EXPERT_LAYER is not None:
            layer = f"layers_{self.EXPERT_LAYER}"
            assert tp_spec_for(f"{layer}/mlp/experts/w_up", 3) \
                == P("ep", None, None)
            assert placed[layer]["mlp"]["experts"]["w_gate"].sharding.spec \
                == P("ep", None, None)
        for path in self.PLACED_WHOLE:      # no tp axis on this mesh
            leaf = placed
            for key in path.split("/"):
                leaf = leaf[key]
            assert leaf.sharding.spec == P(), path
        assert placed["lm_head"]["kernel"].sharding.spec == P(None, "vp")
        self.check_placed(placed, mesh)


class SoloEnginePath:
    """The engine's path of a model that expands one image after the
    other."""
    CASE: Case
    #: what ``serving.expander`` must have after a request
    STATUS_KEYS: frozenset = frozenset()
    #: the spans of a request from the kept snapshot
    SPANS = ("expand", "expand.prefix_copy", "expand.prefill",
             "expand.decode_chunk", "expand.fence_wait", "prepare")

    def check_stats(self, stats):
        """What only this model counts, after the two requests."""

    def check_prefill_span(self, args):
        """What only this model's ``expand.prefill`` says."""

    def test_a_request_from_the_kept_snapshot_equals_the_first(self, engine):
        case = self.CASE
        EXPANDER.clear()
        a = engine.txt2img(case.payload())      # prefills the instruction
        b = engine.txt2img(case.payload())      # starts from its snapshot
        plain = engine.txt2img(case.payload(alwayson_scripts={}))
        assert a.images == b.images and a.prompts == b.prompts
        assert a.images != plain.images
        words = a.prompts[0].split()
        assert len(words) == 45 and len(set(words[5:])) > 8
        stats = EXPANDER.summary()
        assert stats["requests"] == 2
        assert stats["tokens_prefilled"] == 31 + 5 + 5
        assert stats["tokens_from_prefix_cache"] == 31
        assert stats["prefix_snapshots"] == 1
        assert stats["state_bytes"] == kv.state_bytes(case.cfg, CAPACITY,
                                                      jnp.float32)
        self.check_stats(stats)

    def test_another_seed_gets_another_expansion(self, engine):
        assert engine.txt2img(self.CASE.payload()).prompts \
            != engine.txt2img(self.CASE.payload(seed=99)).prompts

    def spans_of_a_request(self, engine):
        case = self.CASE
        engine.txt2img(case.payload())      # the snapshot is held from here
        spans.TRACER.clear()
        with spans.request("rid-solo"):
            engine.txt2img(case.payload())
        events = span_events()
        names = [e["name"] for e in events]
        for name in self.SPANS:
            assert name in names, name
        assert_expand_spans_under_expand(events)
        prefill = next(e for e in events if e["name"] == "expand.prefill")
        assert prefill["args"]["tokens"] == 5
        assert prefill["args"]["prefix_hit"] is True
        self.check_prefill_span(prefill["args"])
        copy = next(e for e in events if e["name"] == "expand.prefix_copy")
        assert copy["args"]["hit"] is True
        assert copy["args"]["bytes"] == sum(
            kv.state_bytes(case.cfg, CAPACITY, jnp.float32).values())
        self.check_events(events)

    def check_events(self, events):
        """What else this model's request must show."""

    def test_status_block(self, engine):
        engine.txt2img(self.CASE.payload())
        block = METRICS.summary()["expander"]
        assert self.STATUS_KEYS <= set(block)
        assert set(block["state_bytes"]) == set(
            kv.state_bytes(self.CASE.cfg, CAPACITY, jnp.float32))
        self.check_status(block)

    def check_status(self, block):
        """What only this model's block says."""


class ForkedEnginePath:
    """The engine's path of a model whose images share a step: a
    four-image request prefills once, forks and decodes four a step."""
    CASE: Case
    #: the executables a four-image request from a kept snapshot leaves an
    #: engine that has run nothing else (None: the module's engine has)
    KEYS = frozenset({
        ("expand_prefill", 64, CAPACITY), ("expand_prefill", 64, CAPACITY, 4),
        ("expand_fork", CAPACITY, 4, 2 * STEPS),
        ("expand_decode_chunk", STEPS, CAPACITY, 4),
        # one dispatch each: the images' keys, a snapshot's copy
        ("expand_keys", 4), ("expand_copy", CAPACITY)})

    def check_traced(self, sites, traced):
        """The attention sites and the counters of the first request's
        traces."""

    def check_counted(self, stats, sizes, one):
        """The counters of the second request, from the kept snapshot;
        ``sizes``, ``one``: the state's bytes of four forked sequences and
        of one."""

    def check_spans(self, by_name, sizes, one):
        """The attributes of the second request's spans."""

    def check_one_image(self, sites, stats):
        """What two one-image requests and a pair counted."""

    def a_batch_prefills_once_forks_and_decodes_four_a_step(self, engine):
        """The spans and counters of a four-image request from the kept
        snapshot; the second request repeats the first byte for byte."""
        case, cfg = self.CASE, self.CASE.cfg
        assert engine.expander.shares_a_step
        ATTENTION.clear()
        EXPANDER.clear()
        whole = engine.txt2img(case.payload(batch_size=4))  # the snapshot
        assert len(set(whole.prompts)) == 4
        if self.KEYS:
            assert {k for k in engine.executable_keys()
                    if k[0].startswith("expand")} == self.KEYS
        self.check_traced(ATTENTION.summary(), EXPANDER.summary())
        EXPANDER.clear()
        spans.TRACER.clear()
        with spans.request("rid-forked"):
            again = engine.txt2img(case.payload(batch_size=4))
        assert again.prompts == whole.prompts
        assert again.images == whole.images
        stats = METRICS.summary()["expander"]
        assert stats["requests"] == 1 and stats["sequences"] == 4
        assert stats["tokens_prefilled"] == 5       # the prompt, once
        assert stats["tokens_from_prefix_cache"] == 31
        assert stats["tokens_decoded"] == 4 * 40
        assert stats["decode_steps"] == 2 * STEPS
        # forked at 31 + 5: those positions once, the 40 behind them once
        # a sequence
        assert stats["cache_positions"] == kv.KVCacheManager(
            cfg, jnp.float32).positions_in_use(36 + 40, 4, 36)
        sizes = kv.state_bytes(cfg, CAPACITY, jnp.float32, 4, 2 * STEPS)
        one = kv.state_bytes(cfg, CAPACITY, jnp.float32)
        assert stats["state_bytes"] == sizes
        self.check_counted(stats, sizes, one)
        events = span_events()
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e["args"])
        assert [a["sequences"] for a in by_name["expand"]] == [4]
        (prefill,) = by_name["expand.prefill"]
        assert prefill["tokens"] == 5
        (fork,) = by_name["expand.fork"]
        assert fork["sequences"] == 4
        # the bytes a fork makes: the sequences' own rows and their copies
        # of what has no positions; what the prefill left stays where it is
        assert fork["bytes"] == sum(sizes.values()) - sum(one.values()) \
            + kv.copied_bytes(cfg, jnp.float32, 4) // 4
        assert [a["sequences"] for a in by_name["expand.decode_chunk"]] \
            == [4, 4]
        assert_expand_spans_under_expand(events)
        self.check_spans(by_name, sizes, one)

    def every_image_its_own_expansion_and_one_image_the_old_path(
            self, engine):
        case = self.CASE
        whole = engine.txt2img(case.payload(batch_size=4))
        ATTENTION.clear()
        EXPANDER.clear()
        for i in (0, 3):
            solo = engine.txt2img(case.payload(seed=1234 + i))
            assert solo.prompts[0] == whole.prompts[i], i
        part = engine.generate_range(case.payload(batch_size=4), 2, 2)
        assert part.prompts == whole.prompts[2:]
        # one image: the one-sequence executable under the key it has
        # always had
        assert ("expand_decode_chunk", STEPS, CAPACITY) \
            in set(engine.executable_keys())
        self.check_one_image(ATTENTION.summary(), EXPANDER.summary())
        ATTENTION.clear()

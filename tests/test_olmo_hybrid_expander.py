"""The prompt expander as a dense hybrid whose two kinds of layer are normed
on different sides (``TINY_OLMO_HYBRID_EXPAND``; the benchmark's
``sd15_olmo_hybrid_expand``): gated delta-rule layers that norm their INPUT
and write with a strength up to 2 over states whose key and value widths
differ, beside full attention that norms its OUTPUT, rotates nothing and
norms queries and keys over the whole projection.

(a) the program through prefill, fork and forked decode against the plain
reference (benchmarks/reference/olmo_hybrid_ref.py: one full float32 forward
a sequence, the delta rule token by token), and nine controls that must
miss the tolerance; (b) a step over several sequences against each decoded
alone, a padded group, an ended sequence, the fork and the snapshot; (c)
the three forms of the delta rule at strengths up to 2 and unequal widths;
(d) the tree, the counts and the sharding rules; (e) the engine's path with
its spans, counters and Prometheus families; (f) the published share from
shapes."""

import functools
import importlib.util
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import configs, lm
from stable_diffusion_webui_distributed_tpu.obs import prometheus
from stable_diffusion_webui_distributed_tpu.ops import delta_rule
from stable_diffusion_webui_distributed_tpu.pipeline import expand
from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    GenerationPayload,
)
from stable_diffusion_webui_distributed_tpu.runtime import dtypes
from stable_diffusion_webui_distributed_tpu.runtime.interrupt import (
    GenerationState,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import (
    ATTENTION, EXPANDER, METRICS,
)
from tests.test_pipeline import init_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _load(os.path.join(ROOT, "benchmarks", "reference",
                         "olmo_hybrid_ref.py"), "olmo_hybrid_ref_for_tests")
FAMILY = configs.TINY_OLMO_HYBRID_EXPAND
CFG = FAMILY.expander
STEPS = expand.DECODE_STEPS
#: decay rates from a token to hundreds, as the benchmark seeds them
A_LOG = (-3.0, -0.5, 2.0)
#: one sequence's state and kept rows in one linear layer, float32
STATE = (3 * 6 * 10 + 3 * (2 * 3 * 6 + 3 * 10)) * 4
LINEAR_LAYERS, FULL_LAYERS = 6, 2


@functools.lru_cache(maxsize=None)
def lm_params(cfg, seed=0):
    """``DecoderLM.init``'s tree with every norm's scale off 1 (deviation
    0.5), the decay rates spread and ``dt_bias`` off 1, so that a norm on
    the wrong side, a shared state or a norm of another extent would
    show."""
    params = jax.jit(lambda key: lm.DecoderLM(cfg).init(
        key, jnp.zeros((4,), jnp.int32), jnp.int32(0), jnp.int32(4),
        lm.empty_cache(cfg, 8, jnp.float32)))(jax.random.key(seed))["params"]
    key = jax.random.key(seed + 100)

    def off(path, x):
        name = getattr(path[-1], "key", "")
        noise = jax.random.normal(
            jax.random.fold_in(key, zlib.crc32(str(path).encode()) % 2 ** 31),
            x.shape)
        if name == "scale":
            return x + 0.5 * noise
        if name == "dt_bias":
            return x + 0.3 * noise
        if name == "A_log":
            return jnp.asarray(A_LOG, jnp.float32)
        return x

    return jax.tree_util.tree_map_with_path(off, params)


@pytest.fixture(scope="module")
def params():
    return lm_params(CFG)


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


@functools.lru_cache(maxsize=None)
def _reference(size):
    """(ids, continuations, the reference's logits, the largest write
    strength it saw) at ``size`` positions, the tiny preset's weights."""
    ids, continuations = REF.inputs(FAMILY, 3, size)
    want, beta = jax.jit(lambda p, i, c: REF.forward(
        FAMILY, p, i, c, with_beta=True))(lm_params(CFG), ids, continuations)
    return ids, continuations, want, float(beta)


# -- (a) program against reference --------------------------------------------

class TestAgainstTheReference:
    @pytest.mark.parametrize("size", [37, 148])
    def test_prefill_fork_and_decode_match_four_full_forwards(self, params,
                                                              size):
        """The prefix as one chunk (chunk-wise delta rule from a zero
        state, full attention over what it wrote), a copy, the prompt's
        chunk, a fork into four and one step over all four a position (a
        recurrent step a sequence, two ranges of keys under one softmax),
        against a full forward of each whole sequence: logits to 1e-4."""
        prefix, user, decoded = REF.split(size)
        ids, continuations, want, beta = _reference(size)
        got = jax.jit(REF.program(FAMILY, dtypes.F32))(
            params, ids, continuations)
        rows = prefix + user + REF.SEQUENCES * decoded
        assert got.shape == want.shape == (rows, CFG.vocab[1])
        assert got.dtype == want.dtype == jnp.float32
        assert rel_rms(got, want) < 1e-4
        # the reference wrote with strengths over 1: half the tokens turn
        # an eigenvalue of the transition negative
        assert 1.5 < beta < 2.0
        # the four continuations part at their first row
        tails = np.asarray(got[prefix + user:]).reshape(
            REF.SEQUENCES, decoded, -1)
        assert rel_rms(tails[1], tails[0]) > 0.1

    @pytest.mark.parametrize("control", [name for name, _ in REF.CONTROLS])
    def test_each_control_is_further_from_the_reference(self, params,
                                                        control):
        """Int8 linears, the state in bfloat16, ``sigmoid(b)`` for ``2
        sigmoid(b)``, one state shared by the sequences, the query and key
        norms per head, a rotary table applied, the full layers
        pre-normed, the linear layers post-normed, both kinds normed both
        ways: each misses ten times over the tolerance (1e-4) the program
        meets."""
        ids, continuations, want, _ = _reference(148)
        lower = jax.jit(REF.program(
            FAMILY, dtypes.F32, **dict(REF.CONTROLS)[control]))(
                params, ids, continuations)
        assert rel_rms(lower, want) > 1e-3, control
        assert [name for name, _ in REF.CONTROLS] == [
            "control", "state_bf16", "sigmoid_beta", "state_shared",
            "qk_norm_per_head", "rotary", "full_pre_normed",
            "linear_post_normed", "both_normed"]

    def test_the_reference_held_to_the_programs_operand_precision(self):
        """The second limit's reading: with bfloat16 matmul operands the
        program is far from the reference as written (roundoff) and an
        order nearer to the reference that rounds its operands where the
        program does; the state kept in bfloat16, which the first reading
        cannot see under the roundoff, shows against the second."""
        policy = dtypes.Policy(param_dtype=jnp.dtype(jnp.bfloat16))
        assert policy.compute_dtype == jnp.bfloat16
        stored = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), lm_params(CFG))
        ids, continuations = REF.inputs(FAMILY, 3, 148)
        want = jax.jit(lambda p, i, c: REF.forward(FAMILY, p, i, c))(
            stored, ids, continuations)
        held = jax.jit(lambda p, i, c: REF.forward(
            FAMILY, p, i, c, operands=jnp.bfloat16))(
                stored, ids, continuations)
        got = jax.jit(REF.program(FAMILY, policy))(
            stored, ids, continuations)
        lower = jax.jit(REF.program(FAMILY, policy, state_bf16=True))(
            stored, ids, continuations)
        plain, near = rel_rms(got, want), rel_rms(got, held)
        # (at 24 channels roundoff weighs far more than at 3 840)
        assert plain > 1e-2 and near < plain / 8
        assert rel_rms(lower, held) > 1.5 * near
        assert rel_rms(lower, want) < 1.1 * plain       # unseen as written
        # with no dtype to hold it to, it is the reference as written
        same = jax.jit(lambda p, i, c: REF.forward(
            FAMILY, p, i, c, operands=None))(stored, ids, continuations)
        assert np.array_equal(np.asarray(same), np.asarray(want))

    def test_the_reference_says_the_model_itself(self):
        """It reads the layers' kinds and widths from the configuration
        and nothing of what this PR added to it: a program that read its
        own new keys wrong cannot take the reference with it."""
        with open(REF.__file__) as fh:
            text = fh.read()
        forward = text[text.index("# -- the reference"):
                       text.index("# -- the readings")]
        for key in ("norm_placement", "sublayer_norms", "qk_norm_extent",
                    "linear_write_scale", "rope_full", "models.lm",
                    "ops."):
            assert key not in forward, key
        assert REF.WRITE_SCALE == 2.0 == CFG.linear_write_scale


# -- (b) a step over B sequences ----------------------------------------------

def _keys(indices, seed=77):
    from stable_diffusion_webui_distributed_tpu.runtime import rng

    return jnp.stack([rng.key_for_image(seed, i) for i in indices])


@functools.lru_cache(maxsize=None)
def _executables(cfg):
    """(the one-sequence decode chunk, the several-sequences one, a step
    of each that returns its logits), jitted once a config."""
    module = lm.DecoderLM(cfg)

    def one_step(params, cache, token, position):
        return module.apply({"params": params}, token[None], position,
                            jnp.int32(1), cache)[:2]

    def forked_step(params, cache, tokens, position, live):
        return module.apply({"params": params}, tokens, position, live,
                            cache, sequences=True)[:2]

    return (jax.jit(lm.decode_chunk_fn(module, STEPS)),
            jax.jit(lm.decode_sequences_fn(module, STEPS)),
            jax.jit(one_step), jax.jit(forked_step))


def _prefilled(cfg, params, user, prefix=21, capacity=None):
    """(the prompt's last row of logits, the cache, its length) after a
    prefix's chunk and a prompt of ``user`` real tokens in its padded
    chunk: the bucket's other rows must leave the states and the kept rows
    where the prompt's last real token put them."""
    module = lm.DecoderLM(cfg)
    bucket = kv.chunk_bucket(user)
    capacity = capacity or kv.capacity_for(prefix + bucket + 2 * STEPS)
    first, count = cfg.vocab
    ids = jax.random.randint(jax.random.key(user), (prefix + bucket,),
                             first, first + count)
    _, cache, _ = module.apply(
        {"params": params}, ids[:prefix], jnp.int32(0), jnp.int32(prefix),
        lm.empty_cache(cfg, capacity, jnp.float32), all_logits=False)
    row, cache, _ = module.apply(
        {"params": params}, ids[prefix:], jnp.int32(prefix),
        jnp.int32(user), cache, all_logits=False)
    return row[0], cache, prefix + user


def assert_own_rows(alone, forked, b, first, steps):
    """Sequence ``b``'s states, kept rows and own keys and values against
    the cache of that sequence decoded alone."""
    for name in lm.LINEAR_BUFFERS:
        for mine, theirs in zip(alone[name], forked[name]):
            np.testing.assert_allclose(mine, theirs[b], rtol=3e-4,
                                       atol=3e-4)
    positions = np.arange(first, first + steps)
    for name in lm.ATTENTION_BUFFERS:
        for mine, theirs in zip(alone[name], forked[name]):
            np.testing.assert_allclose(
                np.asarray(mine)[positions],
                np.asarray(theirs[b])[(positions - first) % theirs.shape[1]],
                rtol=3e-4, atol=3e-4)


class TestSequencesOfOneStep:
    @pytest.mark.parametrize("user,live,batch", [(1, 4, 4), (64, 3, 4)])
    def test_a_forked_decode_is_each_sequence_alone(self, params, user,
                                                    live, batch):
        """``batch`` sequences forked from one prefill against each of the
        ``live`` decoded alone from the same cache by the one-sequence
        executable: a chunk of steps token for token, the states, kept
        rows, keys and values written, and the logits of a few
        teacher-forced steps after it. A group of three padded to four
        leaves the pad's copies as the fork made them."""
        alone, together, one_step, forked_step = _executables(CFG)
        row, cache, length = _prefilled(CFG, params, user)
        keys = _keys(list(range(live)) + [live - 1] * (batch - live))
        first = lm.sample_each(row, keys, length, jnp.float32(1.0),
                               CFG.vocab[0])
        start = kv.fork(cache, batch, 2 * STEPS)
        forked, tokens, position, made, *_ = together(
            params, start, first, jnp.int32(length), keys,
            jnp.float32(1.0), jnp.int32(live))
        assert int(position) == length + STEPS
        # the shared keys and values are the prefill's, untouched
        for name, shared in lm.SHARED_OF.items():
            for mine, theirs in zip(cache.get(name, ()),
                                    forked.get(shared, ())):
                assert np.array_equal(np.asarray(mine), np.asarray(theirs))
        assert int(forked[lm.FORKED_AT][0][0, 0]) == length
        own = []
        for b in range(live):
            after, last, _, steps, *_ = alone(
                params, cache, first[b], jnp.int32(length), keys[b],
                jnp.float32(1.0))
            assert np.array_equal(steps, made[:, b]), b
            assert int(last) == int(tokens[b])
            assert_own_rows(after, forked, b, length, STEPS)
            own.append(after)
        for b in range(live, batch):            # a pad's copies stay
            for name in lm.LINEAR_BUFFERS:
                for mine, theirs in zip(cache[name], forked[name]):
                    assert np.array_equal(np.asarray(mine),
                                          np.asarray(theirs[b]))
        assert len({tuple(np.asarray(made[:, b])) for b in range(live)}) \
            == live
        forced = jax.random.randint(jax.random.key(8), (3, batch),
                                    *np.cumsum(CFG.vocab))
        for t, row in enumerate(forced):
            at = jnp.int32(length + STEPS + t)
            logits, forked = forked_step(params, forked, row, at,
                                         jnp.int32(live))
            for b in range(live):
                want, own[b] = one_step(params, own[b], row[b], at)
                np.testing.assert_allclose(logits[b], want[0], rtol=3e-4,
                                           atol=3e-4)

    def test_a_sequence_that_has_ended_leaves_the_others_alone(self, params):
        """A sequence goes on being stepped after its end-of-sequence (its
        tokens are cut afterwards): whatever it is fed, the other
        sequences' logits, states, kept rows, keys and values are bit for
        bit what they are beside any other neighbour."""
        *_, forked_step = _executables(CFG)
        row, cache, length = _prefilled(CFG, params, 7)
        tokens = jnp.array([130, 131, 132, 133], jnp.int32) % CFG.vocab[1]
        results = []
        for fed in (5, 99):
            forked = kv.fork(cache, 4, STEPS)
            for t in range(3):
                logits, forked = forked_step(
                    params, forked, tokens.at[2].set(fed + t),
                    jnp.int32(length + t), jnp.int32(4))
            results.append((logits, forked))
        (a, ca), (b, cb) = results
        others = np.array([0, 1, 3])
        assert np.array_equal(np.asarray(a)[others], np.asarray(b)[others])
        assert not np.array_equal(np.asarray(a)[2], np.asarray(b)[2])
        for name in ("state", "conv", "k", "v"):
            for mine, theirs in zip(ca[name], cb[name]):
                assert np.array_equal(np.asarray(mine)[others],
                                      np.asarray(theirs)[others]), name
                assert not np.array_equal(np.asarray(mine)[2],
                                          np.asarray(theirs)[2]), name

    def test_a_fork_shares_every_key_and_value_and_copies_every_state(
            self, params):
        _, cache, _ = _prefilled(CFG, params, 5)
        forked = kv.fork(cache, 4, 2 * STEPS)
        assert set(forked) == {"k", "v", "k_shared", "v_shared", "state",
                               "conv", "forked_at"}
        for name, shared in (("k", "k_shared"), ("v", "v_shared")):
            assert all(mine is theirs for mine, theirs
                       in zip(cache[name], forked[shared]))
            assert [x.shape for x in forked[shared]] \
                == [(256, 3, 8)] * FULL_LAYERS
            assert [x.shape for x in forked[name]] \
                == [(4, 64, 3, 8)] * FULL_LAYERS
            assert not any(np.any(np.asarray(x)) for x in forked[name])
        # a state whose widths differ, three heads: no power of two
        assert [x.shape for x in forked["state"]] \
            == [(4, 3, 6, 10)] * LINEAR_LAYERS
        assert [x.shape for x in forked["conv"]] \
            == [(4, 3, 66)] * LINEAR_LAYERS
        for name in lm.LINEAR_BUFFERS:
            for mine, theirs in zip(cache[name], forked[name]):
                assert np.any(np.asarray(mine))
                for b in range(4):
                    assert np.array_equal(np.asarray(mine),
                                          np.asarray(theirs[b]))
        # what the engine's fork executable makes: the same, in one call
        made = jax.jit(lambda c: kv.own_rows(c, 4, 2 * STEPS))(cache)
        again = kv.forked(cache, made)
        assert jax.tree_util.tree_structure(again) \
            == jax.tree_util.tree_structure(forked)
        for name in lm.LINEAR_BUFFERS:
            for mine, theirs in zip(forked[name], again[name]):
                assert np.array_equal(np.asarray(mine), np.asarray(theirs))

    def test_a_snapshot_restores_keys_values_and_states(self, params):
        """What the manager keeps after the instruction's last token is a
        copy of every kind of buffer; a request that starts from it gets
        copies again, whatever the one before did to its own."""
        manager = kv.KVCacheManager(CFG, jnp.float32)
        prefix = tuple(range(1, 22))
        cache, held = manager.acquire(prefix, 256)
        assert held == 0 and not np.any(np.asarray(cache["state"][0]))
        module = lm.DecoderLM(CFG)
        apply = jax.jit(lambda t, start, c: module.apply(
            {"params": params}, t, start, jnp.int32(t.shape[0]), c,
            all_logits=False))
        _, cache, _ = apply(jnp.asarray(prefix, jnp.int32), jnp.int32(0),
                            cache)
        manager.keep_prefix(prefix, 256, cache)
        kept = jax.tree_util.tree_map(np.asarray, cache)
        first, held = manager.acquire(prefix, 256)
        assert held == 21 and manager.snapshots == 1
        # the request runs on and spoils its copy
        _, spoiled, _ = apply(jnp.arange(5, dtype=jnp.int32), jnp.int32(21),
                              first)
        assert not np.array_equal(np.asarray(spoiled["state"][0]),
                                  kept["state"][0])
        second, held = manager.acquire(prefix, 256)
        assert held == 21
        for name in ("k", "v", "state", "conv"):
            assert len(kept[name]) in (FULL_LAYERS, LINEAR_LAYERS)
            for mine, theirs in zip(kept[name], second[name]):
                assert np.array_equal(mine, np.asarray(theirs)), name

    def test_bytes_and_positions_of_a_forked_cache_of_both_kinds(self):
        assert lm.shares_a_step(CFG)
        assert lm.shares_a_step(configs.sd15_olmo_hybrid_expander().expander)
        manager = kv.KVCacheManager(CFG, jnp.bfloat16)
        assert manager.positions_in_use(40, 4, 30) == {
            "full": FULL_LAYERS * (30 + 4 * 10), "sliding": 0, "linear": 0}
        row = 2 * 3 * 8 * 2     # a position's keys and values, bfloat16
        assert kv.state_bytes(CFG, 256, jnp.bfloat16) == {
            "full": FULL_LAYERS * 256 * row, "sliding": 0,
            "linear": LINEAR_LAYERS * STATE}
        assert kv.state_bytes(CFG, 256, jnp.bfloat16, 4, 64) == {
            "full": FULL_LAYERS * (256 + 4 * 64) * row, "sliding": 0,
            "linear": 4 * LINEAR_LAYERS * STATE}
        assert kv.copied_bytes(CFG, jnp.bfloat16, 4) \
            == 4 * LINEAR_LAYERS * STATE
        assert kv.copied_bytes(CFG, jnp.bfloat16, 1) == 0


# -- (c) the delta rule at strengths up to 2 and unequal widths ---------------

def _delta_operands(tokens, heads, k_dim, v_dim, seed=0, most=2.0):
    ks = jax.random.split(jax.random.key(seed), 7)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (tokens, heads, k_dim))) * k_dim ** -0.5
    k = unit(jax.random.normal(ks[1], (tokens, heads, k_dim)))
    v = jax.random.normal(ks[2], (tokens, heads, v_dim))
    g = -0.2 * jax.nn.softplus(jax.random.normal(ks[3], (tokens, heads)))
    beta = jax.random.uniform(ks[4], (tokens, heads), minval=0.0,
                              maxval=most)
    state = 0.3 * jax.random.normal(ks[5], (heads, k_dim, v_dim))
    return state, q, k, v, g, beta


class TestTheDeltaRuleAtStrengthsUpToTwo:
    @pytest.mark.parametrize("tokens,heads,k_dim,v_dim", [
        (200, 5, 96, 192), (64, 3, 6, 10), (130, 30, 12, 24)])
    def test_chunked_is_the_recurrence(self, tokens, heads, k_dim, v_dim):
        """The chunk-wise form's unit lower-triangular solve assumes
        nothing of ``beta``'s range: at strengths drawn over (0, 2), where
        half the tokens turn an eigenvalue of ``I - beta k k^T`` negative,
        at key and value widths that differ and a head count that is no
        power of two, over several chunks (the last one padded), it is
        the token-by-token recurrence."""
        operands = _delta_operands(tokens, heads, k_dim, v_dim)
        assert float(jnp.max(operands[-1])) > 1.9
        want, state = jax.jit(delta_rule.recurrent)(*operands)
        got, after = jax.jit(delta_rule.chunked)(*operands)
        assert got.shape == (tokens, heads, v_dim)
        assert after.shape == (heads, k_dim, v_dim)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(after, state, rtol=2e-4, atol=2e-5)
        # and not the rule at half the strength
        state, q, k, v, g, beta = operands
        half, _ = jax.jit(delta_rule.recurrent)(state, q, k, v, g, beta / 2)
        assert rel_rms(half, want) > 0.05

    def test_a_step_of_each_is_the_step_of_one(self):
        state, q, k, v, g, beta = _delta_operands(4, 30, 96, 192, seed=1)
        states = jnp.stack([state * (b + 1) for b in range(4)])
        out, after = jax.jit(delta_rule.recurrent_step_each)(
            states, q, k, v, g, beta)
        assert out.shape == (4, 30, 192) and after.shape == (4, 30, 96, 192)
        for b in range(4):
            o, s = delta_rule.recurrent_step(states[b], q[b], k[b], v[b],
                                             g[b], beta[b])
            np.testing.assert_allclose(out[b], o, rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(after[b], s, rtol=1e-6, atol=1e-6)
        # a masked row (g = 0, beta = 0) leaves its state where it was
        _, kept = delta_rule.recurrent_step_each(
            states, q, k, v, g.at[2].set(0.0), beta.at[2].set(0.0))
        assert np.array_equal(np.asarray(kept[2]), np.asarray(states[2]))

    def test_a_write_at_two_reflects_the_state_along_the_key(self):
        """``beta = 2``, no decay: ``S <- (I - 2 k k^T) S + 2 k v^T``, the
        state's component along ``k`` turned about, the eigenvalue -1."""
        state, q, k, v, g, beta = _delta_operands(1, 2, 6, 10, seed=2)
        _, after = delta_rule.recurrent_step(
            state, q[0], k[0], jnp.zeros_like(v[0]), jnp.zeros_like(g[0]),
            jnp.full_like(beta[0], 2.0))
        along = jnp.einsum("hkv,hk->hv", state, k[0])
        np.testing.assert_allclose(
            jnp.einsum("hkv,hk->hv", after, k[0]), -along, rtol=1e-5,
            atol=1e-6)
        np.testing.assert_allclose(
            jnp.linalg.norm(after, axis=(1, 2)),
            jnp.linalg.norm(state, axis=(1, 2)), rtol=1e-5)


# -- (d) the tree, the counts and the rules ------------------------------------

class TestTheTreeAndItsRules:
    def test_the_leaves_of_each_kind(self, params):
        assert CFG.layer_types == ("linear", "linear", "linear", "full") * 2
        assert CFG.sublayer_norms == ("pre", "pre", "pre", "post") * 2
        assert set(params["layers_0"]) == {
            "delta", "mlp", "input_norm", "post_attention_norm"}
        assert set(params["layers_3"]) == {
            "attn", "mlp", "input_norm_2", "post_attention_norm_2"}
        attn = params["layers_3"]["attn"]
        assert set(attn) == {"q_proj", "k_proj", "v_proj", "o_proj",
                             "q_norm", "k_norm"}
        # one weight a column of the whole projection, not one a head's
        assert attn["q_norm"]["scale"].shape == (3 * 8,)
        assert attn["k_norm"]["scale"].shape == (3 * 8,)
        delta = params["layers_1"]["delta"]
        assert set(delta) == {"qkvz_proj", "ba_proj", "conv_kernel", "A_log",
                              "dt_bias", "norm", "out_proj"}
        assert delta["qkvz_proj"]["kernel"].shape == (24, 18 + 18 + 30 + 30)
        assert delta["conv_kernel"].shape == (4, 66)
        assert delta["norm"]["scale"].shape == (10,)
        assert delta["out_proj"]["kernel"].shape == (30, 24)
        assert set(params["layers_0"]["mlp"]) == {"gate_proj", "up_proj",
                                                  "down_proj"}
        assert set(params["norm"]) == {"scale"}

    def test_the_keys_at_their_defaults_are_the_old_model(self):
        """Every existing preset says nothing of the four new keys and
        reads as it did: input norms alone (or both under
        ``post_sublayer_norm``), a rotary table, norms a head, strength
        under 1."""
        for name in ("TINY_EXPAND", "TINY_DELTA_EXPAND", "TINY_LOOP_EXPAND",
                     "TINY_GIGACHAT35_EXPAND", "TINY_KANANA_EXPAND"):
            cfg = getattr(configs, name).expander
            assert cfg.norm_placement == () and cfg.rope_full is not None
            assert cfg.qk_norm_extent == "head"
            assert cfg.linear_write_scale == 1.0
            assert set(cfg.sublayer_norms) == {
                "both" if cfg.post_sublayer_norm else "pre"}
            assert lm.site_attrs(cfg) == {}
        assert lm.site_attrs(CFG) == {
            "norms_pre": 12, "norms_post": 4, "unrotated": 2,
            "write_strength_bound": 2.0}
        with pytest.raises(ValueError):
            configs.LMConfig(norm_placement=("pre", "post"))
        with pytest.raises(ValueError):
            configs.LMConfig(norm_placement=("after",))

    def test_a_share_keeps_the_placement_of_the_layers_it_holds(self):
        whole = configs.OLMO_HYBRID_7B
        share = configs.lm_share(whole, (0, 3, 4), chips=1, rank=0)
        assert share.layer_types == ("linear", "full", "linear")
        assert share.sublayer_norms == ("pre", "post", "pre")
        assert configs.lm_share(configs.TINY_LM, 2, chips=2,
                                rank=0).norm_placement == ()

    def test_sharding_rules(self, params):
        """The new leaves: a query norm's weight over the whole projection
        and the norms after the sublayers are replicated; a state whose
        widths differ stays whole on every chip."""
        from jax.sharding import PartitionSpec as P

        from stable_diffusion_webui_distributed_tpu.parallel.sharding import (
            shard_params, tp_spec_for,
        )

        for path, ndim in (("layers_3/attn/q_norm/scale", 1),
                           ("layers_3/attn/k_norm/scale", 1),
                           ("layers_3/input_norm_2/scale", 1),
                           ("layers_3/post_attention_norm_2/scale", 1),
                           ("layers_0/delta/norm/scale", 1),
                           ("layers_0/delta/A_log", 1),
                           ("layers_0/delta/conv_kernel", 2)):
            assert tp_spec_for(path, ndim) == P(), path
        devices = np.array(jax.devices()[:4]).reshape(2, 2)
        mesh = jax.sharding.Mesh(devices, ("ep", "vp"))
        placed = shard_params(params, mesh)
        for leaf in (placed["layers_3"]["attn"]["q_norm"]["scale"],
                     placed["layers_3"]["input_norm_2"]["scale"],
                     placed["layers_0"]["delta"]["qkvz_proj"]["kernel"]):
            assert leaf.sharding.spec == P()    # no tp axis on this mesh
        assert placed["lm_head"]["kernel"].sharding.spec == P(None, "vp")
        # the published state and query norm, as shapes
        share = configs.sd15_olmo_hybrid_expander().expander
        shapes = lm.cache_shapes(share, 2560)
        assert shapes["state"] == [(30, 96, 192)] * 12
        assert shapes["conv"] == [(3, 11520)] * 12
        assert shapes["k"] == [(2560, 30, 128)] * 4


# -- (e) the engine's path ----------------------------------------------------

INSTRUCTION = " ".join(f"word{i}" for i in range(30))


def script(**args):
    return {"prompt expansion": {"args": [dict(
        {"instruction": INSTRUCTION, "max_new_tokens": 40,
         "temperature": 1.0, "ignore_eos": True, "context_chunks": 1},
        **args)]}}


@pytest.fixture(scope="module")
def engine():
    params = init_params(configs.TINY)
    params["expander"] = lm_params(CFG, seed=1)
    return Engine(configs.tiny_olmo_hybrid_expander(), params, chunk_size=4,
                  state=GenerationState())


def payload(**kw):
    base = dict(prompt="a cow in a valley", steps=4, width=32, height=32,
                seed=1234, alwayson_scripts=script())
    base.update(kw)
    return GenerationPayload(**base)


CAPACITY = kv.capacity_for(31 + 64 + 2 * STEPS)


class TestEnginePath:
    def test_a_batch_prefills_once_forks_and_decodes_four_a_step(self,
                                                                 engine):
        """The spans, counters and Prometheus families of a four-image
        request from the kept snapshot; the second request repeats the
        first byte for byte."""
        from stable_diffusion_webui_distributed_tpu.obs import spans

        assert engine.expander.shares_a_step
        ATTENTION.clear()
        EXPANDER.clear()
        whole = engine.txt2img(payload(batch_size=4))   # keeps the snapshot
        assert len(set(whole.prompts)) == 4
        keys = {k for k in engine.executable_keys()
                if k[0].startswith("expand")}
        assert keys == {("expand_prefill", 64, CAPACITY),
                        ("expand_prefill", 64, CAPACITY, 4),
                        ("expand_fork", CAPACITY, 4, 2 * STEPS),
                        ("expand_decode_chunk", STEPS, CAPACITY, 4),
                        ("expand_keys", 4), ("expand_copy", CAPACITY)}
        sites = ATTENTION.summary()["by_shape"]
        assert sum(sites[f"T1 S{CAPACITY + 2 * STEPS} D8"].values()) \
            == FULL_LAYERS
        traced = EXPANDER.summary()
        # two prefill executables and one forked decode chunk were traced
        assert traced["delta_mixers"] == {
            "recurrent": 0, "chunked": 2 * LINEAR_LAYERS,
            "recurrent_forked": LINEAR_LAYERS}
        assert traced["sublayer_norms"] == {
            "pre": {"recurrent": 0, "chunked": 2 * 12,
                    "recurrent_forked": 12},
            "post": {"recurrent": 0, "chunked": 2 * 4,
                     "recurrent_forked": 4}}
        assert traced["attention_unrotated"] == {
            "recurrent": 0, "chunked": 2 * FULL_LAYERS,
            "recurrent_forked": FULL_LAYERS}
        assert traced["write_strength_bound"] == 2.0
        EXPANDER.clear()
        spans.TRACER.clear()
        with spans.request("rid-olmo-hybrid"):
            again = engine.txt2img(payload(batch_size=4))
        assert again.prompts == whole.prompts
        assert again.images == whole.images
        stats = METRICS.summary()["expander"]
        assert stats["requests"] == 1 and stats["sequences"] == 4
        assert stats["tokens_prefilled"] == 5       # the prompt, once
        assert stats["tokens_from_prefix_cache"] == 31
        assert stats["tokens_decoded"] == 4 * 40
        assert stats["decode_steps"] == 2 * STEPS
        assert stats["tokens_no_held_expert"] == 0 == stats["experts_read"]
        assert stats["cache_positions"] == {
            "full": FULL_LAYERS * (36 + 4 * 40), "sliding": 0, "linear": 0}
        sizes = kv.state_bytes(CFG, CAPACITY, jnp.float32, 4, 2 * STEPS)
        assert stats["state_bytes"] == sizes
        one = kv.state_bytes(CFG, CAPACITY, jnp.float32)
        state = LINEAR_LAYERS * STATE
        assert one["linear"] == state and sizes["linear"] == 4 * state
        assert stats["fork_bytes_copied"] == 4 * state
        # a step reads and writes each sequence's states once
        assert stats["state_bytes_stepped"] == 2 * STEPS * 2 * 4 * state
        # nothing was traced again: the counters of the sites stay 0
        assert stats["write_strength_bound"] == 0.0
        events = [e for e in spans.TRACER.export_chrome()["traceEvents"]
                  if e.get("ph") == "X"]
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e["args"])
        assert [a["sequences"] for a in by_name["expand"]] == [4]
        departures = {"norms_pre": 12, "norms_post": 4, "unrotated": 2,
                      "write_strength_bound": 2.0}
        (prefill,) = by_name["expand.prefill"]
        assert prefill["tokens"] == 5 and prefill["sequences"] == 4
        assert prefill["form"] == "chunked" and prefill["padded"] == 59
        (fork,) = by_name["expand.fork"]
        assert fork["sequences"] == 4
        assert fork["delta"] == "recurrent_forked"
        assert fork["state_bytes_copied"] == 4 * state
        # the bytes a fork makes: two full layers' own rows of 64 slots a
        # sequence and four copies of every state, float32
        assert fork["bytes"] == FULL_LAYERS * 4 * 2 * STEPS * 2 * 24 * 4 \
            + 4 * state
        chunks = by_name["expand.decode_chunk"]
        assert [(a["sequences"], a["delta"]) for a in chunks] \
            == [(4, "recurrent_forked")] * 2
        for attrs in [prefill, fork] + chunks:
            assert {k: attrs[k] for k in departures} == departures
        hits = [a for a in by_name["expand.prefix_copy"] if a.get("hit")]
        assert hits and hits[0]["bytes"] == sum(one.values())

    def test_the_prometheus_families_and_the_status_keys(self, engine):
        ATTENTION.clear()
        EXPANDER.clear()
        module = lm.DecoderLM(CFG)
        cache = jax.eval_shape(
            lambda: kv.fork(lm.empty_cache(CFG, 64, jnp.float32), 4, 32))
        jax.eval_shape(
            lambda p, c: module.apply({"params": p}, jnp.zeros((4,), int),
                                      jnp.int32(40), jnp.int32(4), c,
                                      sequences=True),
            lm_params(CFG), cache)
        summary = METRICS.summary()["expander"]
        assert {"sublayer_norms", "attention_unrotated",
                "write_strength_bound", "delta_mixers",
                "state_bytes_stepped", "fork_bytes_copied"} <= set(summary)
        assert summary["sublayer_norms"]["post"]["recurrent_forked"] == 4
        text = prometheus.render()
        assert 'sdtpu_expander_sublayer_norms_total{placement="pre",' \
            'form="recurrent_forked"} 12' in text
        assert 'sdtpu_expander_sublayer_norms_total{placement="post",' \
            'form="recurrent_forked"} 4' in text
        assert 'sdtpu_expander_attention_unrotated_total{' \
            'form="recurrent_forked"} 2' in text
        assert "sdtpu_expander_write_strength_bound 2" in text
        # a sibling's mixer writes under 1
        EXPANDER.clear()
        other = configs.TINY_DELTA_EXPAND.expander
        jax.eval_shape(lambda: lm.DecoderLM(other).init(
            jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
            jnp.int32(4), lm.empty_cache(other, 8, jnp.float32)))
        summary = EXPANDER.summary()
        assert summary["write_strength_bound"] == 1.0
        assert summary["attention_unrotated"]["chunked"] == 0
        assert summary["sublayer_norms"]["post"]["chunked"] == 0
        assert summary["sublayer_norms"]["pre"]["chunked"] == 8
        ATTENTION.clear()
        EXPANDER.clear()

    def test_a_warm_start_counts_the_sites_again(self):
        """What a trace counted is replayed when its program is loaded
        (serving/aot.py): the rows a capture holds count once more."""
        from stable_diffusion_webui_distributed_tpu.serving import metrics

        EXPANDER.clear()
        with metrics.capture_sites() as rows:
            EXPANDER.record_norm("post", "recurrent_forked")
            EXPANDER.record_unrotated("chunked")
            EXPANDER.record_delta("recurrent_forked", 2.0)
        EXPANDER.clear()
        metrics.replay_sites(rows)
        summary = EXPANDER.summary()
        assert summary["sublayer_norms"]["post"]["recurrent_forked"] == 1
        assert summary["attention_unrotated"]["chunked"] == 1
        assert summary["delta_mixers"]["recurrent_forked"] == 1
        assert summary["write_strength_bound"] == 2.0
        EXPANDER.clear()

    def test_every_image_its_own_expansion_and_one_image_the_old_path(
            self, engine):
        whole = engine.txt2img(payload(batch_size=4))
        EXPANDER.clear()
        for i in (0, 3):
            solo = engine.txt2img(payload(seed=1234 + i))
            assert solo.prompts[0] == whole.prompts[i], i
        part = engine.generate_range(payload(batch_size=4), 2, 2)
        assert part.prompts == whole.prompts[2:]
        assert ("expand_decode_chunk", STEPS, CAPACITY) \
            in set(engine.executable_keys())
        stats = EXPANDER.summary()
        assert stats["delta_mixers"]["recurrent"] == LINEAR_LAYERS
        assert stats["attention_unrotated"]["recurrent"] == FULL_LAYERS
        assert stats["fork_bytes_copied"] == 2 * LINEAR_LAYERS * STATE
        ATTENTION.clear()


# -- (f) the published model and its share, from shapes -----------------------

def _published_shapes(share):
    return jax.eval_shape(lambda: lm.DecoderLM(share).init(
        jax.random.key(0), jnp.zeros((4,), jnp.int32), jnp.int32(0),
        jnp.int32(4), lm.empty_cache(share, 8, jnp.float32)))["params"]


def _count(tree):
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(tree))


class TestThePublishedShare:
    def test_parameters_and_bytes_from_shapes(self):
        share = configs.sd15_olmo_hybrid_expander().expander
        whole = configs.OLMO_HYBRID_7B
        assert whole.num_layers == 32 and share.num_layers == 16
        assert whole.layers_of("full") == tuple(range(3, 32, 4))
        assert share.layer_types == ("linear", "linear", "linear",
                                     "full") * 4
        assert share.sublayer_norms == ("pre", "pre", "pre", "post") * 4
        assert share.dense_layers == tuple(range(16))
        assert share.vocab == (0, 100352) and not share.expert_layers
        assert (share.hidden_size, share.intermediate_size, share.head_dim,
                share.num_kv_heads) == (3840, 11008, 128, 30)
        assert share.rope_full is None and share.attn_gate == "none"
        assert (share.linear_num_key_heads, share.linear_num_value_heads,
                share.linear_key_head_dim, share.linear_value_head_dim,
                share.linear_conv_kernel, share.linear_write_scale) \
            == (30, 30, 96, 192, 4, 2.0)
        shapes = _published_shapes(share)
        delta = shapes["layers_0"]["delta"]
        assert delta["qkvz_proj"]["kernel"].shape == (3840, 11520 + 5760)
        assert delta["ba_proj"]["kernel"].shape == (3840, 60)
        assert delta["out_proj"]["kernel"].shape == (5760, 3840)
        assert delta["conv_kernel"].shape == (4, 11520)
        assert round(_count(delta["qkvz_proj"]) / 1e6, 2) == 66.36
        assert round(_count(delta["ba_proj"]) / 1e6, 2) == 0.23
        assert round(_count(delta["out_proj"]) / 1e6, 2) == 22.12
        assert round(_count(delta["conv_kernel"]) / 1e6, 2) == 0.05
        assert round(_count(delta) / 1e6, 2) == 88.75
        attn = shapes["layers_3"]["attn"]
        assert attn["q_norm"]["scale"].shape == (3840,)
        assert round(_count(attn) / 1e6, 2) == 58.99    # 58.98 + the norms
        assert round(_count(shapes["layers_0"]["mlp"]) / 1e6, 2) == 126.81
        linear, full = _count(shapes["layers_0"]), _count(shapes["layers_3"])
        assert round(linear / 1e6, 2) == 215.57
        assert round(full / 1e6, 2) == 185.81
        assert round((3 * linear + full) / 1e6, 1) == 832.5
        assert _count(shapes["embed_tokens"]) == _count(shapes["lm_head"]) \
            == 3840 * 100352
        assert round(3840 * 100352 / 1e6, 2) == 385.35
        total = _count(shapes)
        # ISSUE 59's 4 100.6 M and the norms' weights, A_log and dt_bias
        assert round(total / 1e6, 1) == 4100.8
        assert round(total * 2 / 1e9, 2) == 8.20
        # beside SD1.5's 1 066 M: 10.33 GB = 9.62 GiB
        assert round((total + 1066e6) * 2 / 1e9, 2) == 10.33
        assert round((total + 1066e6) * 2 / 2 ** 30, 2) == 9.62
        # the whole model from the same shapes: 7 431 M
        published = 8 * (3 * linear + full) + 2 * 3840 * 100352 + 3840
        assert round(published / 1e6) == 7431
        assert round(published * 2 / 1e9, 1) == 14.9
        # twenty layers would fit on paper, without any cache
        twenty = total + 3 * linear + full
        assert round((twenty + 1066e6) * 2 / 2 ** 30, 2) == 11.17
        # the fallback's twelve layers
        assert round((total - 3 * linear - full) / 1e6) == 3268
        # the caches of four forked sequences at the cell's capacity
        capacity = kv.capacity_for(2048 + 64 + 8 * STEPS)
        assert capacity == 2560
        state = (30 * 96 * 192 + 3 * 11520) * 4
        assert 30 * 96 * 192 * 4 == 2_211_840
        position = 2 * 30 * 128 * 2
        assert position == 15_360
        assert kv.state_bytes(share, capacity, jnp.bfloat16, 4, 8 * STEPS) \
            == {"full": 4 * (2560 + 4 * 256) * position, "sliding": 0,
                "linear": 4 * 12 * state}
        assert round(4 * 2560 * position / 1e6) == 157
        assert round(4 * 4 * 256 * position / 1e6) == 63
        assert round(12 * state / 1e6, 2) == 28.2
        assert kv.copied_bytes(share, jnp.bfloat16, 4) == 48 * state
        assert round(48 * state / 2 ** 20, 1) == 107.6
        assert round(2 * 48 * state / 2 ** 20, 1) == 215.2

    def test_a_forked_step_of_the_share_traced_as_on_the_chip(self,
                                                              monkeypatch):
        """One decode step of the share the cell runs, traced without
        weights or FLOPs (nothing compiles; tests/test_chip_compile.py
        compiles it for a described v5e): twelve delta mixers a recurrent
        step a sequence at strength up to 2, four unrotated attention
        sites over 2 560 shared and 256 own rows of 128, 24 norms before
        a sublayer and 8 after."""
        share = configs.sd15_olmo_hybrid_expander().expander
        module = lm.DecoderLM(share, dtype=jnp.bfloat16)
        s = jax.ShapeDtypeStruct
        one = {name: [s(shape, lm.buffer_dtype(name, jnp.bfloat16))
                      for shape in rows]
               for name, rows in lm.cache_shapes(share, 2560).items()}
        cache = jax.eval_shape(lambda c: kv.fork(c, 4, 256), one)
        assert [x.shape for x in cache["k_shared"]] == [(2560, 30, 128)] * 4
        assert [x.shape for x in cache["k"]] == [(4, 256, 30, 128)] * 4
        assert [(x.shape, x.dtype) for x in cache["state"]] \
            == [((4, 30, 96, 192), jnp.float32)] * 12
        assert [x.shape for x in cache["conv"]] == [(4, 3, 11520)] * 12
        shapes = {"params": _published_shapes(share)}
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ATTENTION.clear()
        EXPANDER.clear()
        logits, after, routed = jax.eval_shape(
            lambda v, c: module.apply(v, jnp.zeros((4,), jnp.int32),
                                      jnp.int32(2200), jnp.int32(4), c,
                                      sequences=True), shapes, cache)
        assert logits.shape == (4, 100352)
        assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), after) \
            == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), cache)
        assert routed[0].shape == (0, 1, 1)     # no expert layer
        stats = EXPANDER.summary()
        assert stats["delta_mixers"] == {"recurrent": 0, "chunked": 0,
                                         "recurrent_forked": 12}
        assert stats["sublayer_norms"]["pre"]["recurrent_forked"] == 24
        assert stats["sublayer_norms"]["post"]["recurrent_forked"] == 8
        assert stats["attention_unrotated"]["recurrent_forked"] == 4
        assert stats["write_strength_bound"] == 2.0
        assert stats["expert_products"] == {"kernel": 0, "loop": 0,
                                            "grouped": 0}
        (shape, paths), = ATTENTION.summary()["by_shape"].items()
        assert shape == "T1 S2816 D128" and sum(paths.values()) == 4
        ATTENTION.clear()
        EXPANDER.clear()

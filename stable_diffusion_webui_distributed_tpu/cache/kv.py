"""Per-layer state of the resident language model (models/lm.py), and the
instruction prefix's state kept across requests.

One manager holds five kinds of layer. A FULL layer keeps every position,
so its key and value buffers are as long as the sequence may get; lengths
are bucketed (:data:`CAPACITY_STEP`) so that one executable serves every
request of a traffic mix. A SLIDING layer only ever attends the last
``sliding_window`` positions, so its buffers are rings of that many slots
whatever the length. A LINEAR layer has no positions: its recurrent state
and the convolution's last inputs are of one size at every length. A LATENT
layer keeps every position as a FULL one does, in one buffer whose row is
the position's latent and its one rotated key, not every head's keys and
values. A CONV layer has no positions either and no recurrence: all it
keeps is the last ``taps - 1`` inputs of its convolution. A layer may hold
several token mixers side by side (``configs.kind_parts``): it then has the
buffers of each of its parts, a state-space (``ssm``) part's recurrence and
kept inputs (no positions, as a LINEAR layer's, under names of their own)
beside an attention part's keys and values, and everything here that counts
does so by BASE kind.

The expander's requests all begin with the operator's instruction text.
What its layers hold after the prefix's LAST token (every kind) is computed
once and kept here as a snapshot, next to cache/embed.py (conditioning) and
cache/prefix.py (denoise carries), which do the same for their artifacts:
a request whose prefix is held starts from a copy and prefills only its own
prompt. A snapshot serves exactly the prefix it was taken after: a key
buffer could be read up to a shorter ``end``, a recurrent state cannot be
cut back, so the key is the whole prefix. The executables donate the cache
they are given, so what is kept is never handed out itself.

The images of one request continue one prompt, each under its own key. Where
every layer keeps a row a position (keys and values, or latents) or a
recurrent state (``lm.shares_a_step``) they are decoded as
sequences of one step: the prompt is prefilled once and its cache
:func:`fork`-ed. A fork copies nothing that has positions. Every such
buffer, a full layer's, a
ring and a latent layer's alike, stays where the prefill left it, held once,
read by every
sequence and written by none (``k_shared``, ``v_shared``,
``latent_shared``), and gets behind
it a few rows a sequence (as many as it will decode) for what each makes
itself (``k``, ``v``: ``(sequences, [passes,] slots, kv heads,
head_dim)``; ``latent``: ``(sequences, slots, width)``: which axis counts
the slots is ``lm.slots_axis`` of the buffer's name). The prompt's rows are
most of what a step attends, so a step
reads them once where copies would be read once a sequence; a ring is
never overwritten because a sequence's new rows go to its own, not to the
ring. Both kinds go one way because a fork is handed buffers, not a
config, and a ring cannot be told from a buffer by its length.
What has NO positions is copied: a linear layer's (a state-space part's)
recurrent state and its convolution's kept inputs are the whole past folded
into one size, each
sequence folds its own tokens into them from the fork on, and so each gets
its own copy under the buffer's name, ``(sequences, ...)`` (4.39 MB a
layer a sequence at 64 value heads of 128 x 128 and 3 x 16 384 kept
inputs). The copies are made in the fork's one executable; the prefill's
own state is let go with the cache it came in.

The sequences of a step may also continue DIFFERENT prompts behind the one
instruction: the requests of a dispatch group (serving/dispatcher.py,
pipeline/expand.py:expand_group). What they share is then the instruction's
rows alone, and what a fork leaves in the shared range, a prompt's rows,
is each sequence's own: :func:`joined_rows` makes, from the sequences'
one-sequence caches after their prompts' prefills, own rows that begin with
the prompt's (right-aligned in one region as wide as the widest prompt's
chunk, so that every sequence's decode slots begin at the same slot and a
step still writes one slot for all), each sequence's copy of what keeps no
positions, and a vector that says where each one's real rows begin
(``lm.OWN_FROM``); :func:`forked` puts them behind a cache that stands at
the instruction's end, exactly as it puts a fork's. The scan and the step
are the fork's own: a sequence's position is the step's common one less
its offset.

A looped model (``LMConfig.total_ut_steps`` over 1) passes a token through
its whole stack several times over one set of weights, and pass ``t`` of a
layer attends what pass ``t`` wrote for the earlier positions. So a full
layer's key and value buffers carry a PASS AXIS in front of their slots,
``(passes, capacity, kv heads, head_dim)``: a position occupies one row of
every pass, the model has ``passes x layers`` cache slots a position, and
everything here that counts or copies takes the axis with it: the shapes
come from ``lm.cache_shapes``, a snapshot copies whole buffers, a fork's
own rows have the sequences in front of the passes, and the positions in
use are a position a pass a layer.
"""

from __future__ import annotations

import functools
import math
import threading
from collections import OrderedDict
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.models import lm

#: full layers' buffers grow in steps of this many positions
CAPACITY_STEP = 256
#: a prefill chunk is padded up to a power of two, at least this
MIN_CHUNK = 64
#: instruction prefixes kept, each a whole cache: :func:`state_bytes` of the
#: model at the capacity says how large (``serving.expander.state_bytes``)
MAX_PREFIXES = 4


def chunk_bucket(tokens: int) -> int:
    """Padded length of a prefill chunk of ``tokens`` real tokens."""
    return max(MIN_CHUNK, 1 << max(0, tokens - 1).bit_length())


def capacity_for(positions: int) -> int:
    return -(-positions // CAPACITY_STEP) * CAPACITY_STEP


#: sequences one decode executable takes: a group of ``n`` is padded up to
#: the first of these at or over ``n`` and cut into several over the last
SEQUENCE_BUCKETS = (1, 2, 4, 8)


def sequence_bucket(sequences: int) -> int:
    return next(b for b in SEQUENCE_BUCKETS
                if b >= min(sequences, SEQUENCE_BUCKETS[-1]))


def own_rows(cache: Dict, sequences: int, slots: int = 0) -> Dict:
    """What a fork of ``cache`` makes anew: for each of its buffers that
    keep positions ``sequences`` times ``slots`` empty rows (0: as many as
    the buffer has) under the buffer's name, for each that keeps none (a
    linear layer's state and kept inputs) ``sequences`` copies of it, and
    the position of the fork, not yet
    known (negative: the first step sets it to where it stands), a row a
    sequence like the rest. Of a buffer that keeps positions only the
    shape is read, so traced into an executable of its own this touches
    none of them."""
    def rows(name, x):
        shape, axis = list(x.shape), lm.slots_axis(name)
        if axis is None:        # no positions: every sequence its copy
            return jnp.broadcast_to(x, (sequences, *shape))
        shape[axis] = slots or shape[axis]
        return jnp.zeros((sequences, *shape), x.dtype)

    own = {name: [rows(name, x) for x in cache[name]]
           for name in sorted(cache)}
    own[lm.FORKED_AT] = [jnp.full((sequences, 1), -1, jnp.int32)]
    return own


def joined_rows(caches: Sequence[Dict], lengths: jax.Array, forked_at,
                region: int, slots: int) -> Dict:
    """What a JOIN makes anew, as :func:`own_rows` does for a fork: the own
    rows of ``len(caches)`` sequences that continue prompts of their OWN
    behind one shared range. ``caches[b]`` is sequence ``b``'s one-sequence
    cache after its prompt's prefill: the shared range's rows (the kept
    instruction's, positions before ``forked_at``) and behind them its
    prompt's ``lengths[b]``. Of each buffer that keeps positions a
    sequence gets ``region + slots`` rows: its prompt's rows RIGHT-aligned
    in the first ``region`` (``region`` at least the longest prompt: the
    widest chunk bucket), then ``slots`` empty ones for what it decodes.
    Right-aligned, every sequence's first decoded row falls on slot
    ``region`` and a step writes ONE slot for all of them; what differs is
    each sequence's first real slot, ``region - lengths[b]``, which the
    cache carries as ``lm.OWN_FROM`` ``(sequences, 1)``, and a sequence's
    position is the step's common one less that. Slot ``j`` of sequence
    ``b`` is its position ``forked_at + j - own_from[b]``, read from the
    slot of its buffer, or of its ring, that holds it; a slot in front of
    ``own_from[b]``, and a ring's row that has left the window, holds some
    other row, which no query sees (models/lm.py:own_positions, the
    window). What keeps no positions is each sequence's own copy, from its
    own cache. The fork's position is known: ``forked_at`` for all. The
    shared range is not made here: :func:`forked` takes it from a cache
    that stands at ``forked_at`` (a copy of the kept snapshot)."""
    own_from = region - lengths.astype(jnp.int32)

    def rows(name, buffers):
        axis = lm.slots_axis(name)
        if axis is None:        # no positions: every sequence its own
            return jnp.stack(buffers)
        first = buffers[0]
        held = first.shape[axis]
        taken = [jnp.take(x, (forked_at - own_from[b]
                              + jnp.arange(region)) % held, axis=axis)
                 for b, x in enumerate(buffers)]
        shape = list(first.shape)
        shape[axis] = slots
        empty = jnp.zeros((len(buffers), *shape), first.dtype)
        return jnp.concatenate([jnp.stack(taken), empty],
                               axis=axis % first.ndim + 1)

    own = {name: [rows(name, layer) for layer in zip(
               *(cache[name] for cache in caches))]
           for name in sorted(caches[0])}
    own[lm.FORKED_AT] = [jnp.full((len(caches), 1), forked_at, jnp.int32)]
    own[lm.OWN_FROM] = [own_from[:, None]]
    return own


def forked(cache: Dict, own: Dict) -> Dict:
    """``cache`` of one sequence and :func:`own_rows` of it as one cache:
    the shared buffers ARE ``cache``'s (a buffer without positions has no
    shared twin: ``own`` holds the sequences' copies of it)."""
    return {**own, **{lm.SHARED_OF[name]: cache[name] for name in cache
                      if name in lm.SHARED_OF}}


def fork(cache: Dict, sequences: int, own_slots: int = 0) -> Dict:
    """``cache`` of one sequence as that of ``sequences`` which all stand
    where it stands and go on apart for at most ``own_slots`` positions
    (0: as many as a buffer has slots). Nothing that has positions is
    copied: the module's text says where everything lies."""
    return forked(cache, own_rows(cache, sequences, own_slots))


def copied_bytes(config, dtype, sequences: int) -> int:
    """Bytes a :func:`fork` into ``sequences`` copies: the buffers that
    keep no positions (:func:`state_bytes` of the linear and the
    state-space kind), once a sequence. 0 for a model whose every buffer
    keeps positions, and at one sequence, which forks nothing."""
    if sequences < 2:
        return 0
    shapes = lm.cache_shapes(config, 0)     # no capacity is read
    return sequences * sum(
        math.prod(shape) * lm.buffer_dtype(name, dtype).itemsize
        for name in lm.LINEAR_BUFFERS + lm.SSM_BUFFERS
        for shape in shapes.get(name, ()))


def copy_tree(cache: Dict) -> Dict:
    """Every buffer of ``cache`` anew. Jitted whole it is ONE dispatch a
    snapshot where ``tree_map(jnp.copy, ...)`` called eagerly is one a
    buffer, each a few hundred microseconds of the host's with the device
    idle for a copy that takes the device microseconds."""
    return jax.tree_util.tree_map(jnp.copy, cache)


#: for a manager whose maker hands it no executable of its own
_COPY = jax.jit(copy_tree)


def state_bytes(config, capacity: int, dtype, sequences: int = 1,
                own_slots: int = 0) -> Dict[str, int]:
    """Bytes the caches of ``sequences`` sequences take at ``capacity``,
    by BASE kind (a layer of several mixers adds to each of its parts':
    ``full`` its rows, ``ssm`` its states), from the shapes: keys, values
    and latents in ``dtype``, a linear layer's or a state-space part's
    state and every kept convolution input in float32. Several sequences
    are a :func:`fork` of one: every buffer that keeps positions once and
    ``own_slots`` rows of it a sequence, one that keeps none once a
    sequence.
    Full and sliding are always named; linear, latent and conv where the
    model has such layers. A request asks for the sizes of its model at
    its capacity, the same as the request before it: kept by argument."""
    return dict(_state_bytes(config, capacity, dtype, sequences, own_slots))


@functools.lru_cache(maxsize=64)
def _state_bytes(config, capacity: int, dtype, sequences: int,
                 own_slots: int) -> Dict[str, int]:
    shapes = {name: iter(rows)
              for name, rows in lm.cache_shapes(config, capacity).items()}

    def held(name, shape) -> int:
        """A buffer's elements, and its sequences' own rows behind it."""
        if sequences == 1:
            return math.prod(shape)
        axis = lm.slots_axis(name)
        if axis is None:
            return sequences * math.prod(shape)
        slots = shape[axis]
        return math.prod(shape) // slots * (slots + sequences * own_slots)

    out = {lm.FULL: 0, lm.SLIDING: 0}
    for kind in config.layer_types:
        for part in lm.kind_parts(kind):
            out[part] = out.get(part, 0) + sum(
                held(name, next(shapes[name]))
                * lm.buffer_dtype(name, dtype).itemsize
                for name in lm.buffers_of(part))
    return out


class KVCacheManager:
    """Hands out caches of one model at bucketed capacities, keeps the
    snapshots of instruction prefixes, and counts what is in use."""

    def __init__(self, config, dtype,
                 copier: Optional[Callable[[int], Callable]] = None) -> None:
        """``copier(capacity)`` is :func:`copy_tree` as one executable for
        the caches of that capacity (the expander's comes through the
        engine's cache of stages, so a warm start loads it); without one
        the manager jits its own."""
        self.config = config
        self.dtype = dtype
        self._copier = copier or (lambda capacity: _COPY)
        self._lock = threading.Lock()
        #: (prefix ids, capacity) -> snapshot at the prefix's last token
        self._prefixes: "OrderedDict[Tuple, Dict]" = OrderedDict()  # guarded-by: _lock
        self.prefix_hits = 0    # guarded-by: _lock
        self.prefix_misses = 0  # guarded-by: _lock

    def acquire(self, prefix: Sequence[int], capacity: int):
        """(cache, positions already in it): a copy of the held prefix's
        cache and its length, or an empty cache and 0."""
        key = (tuple(prefix), capacity)
        with self._lock:
            held = self._prefixes.get(key)
            if held is not None:
                self._prefixes.move_to_end(key)
                self.prefix_hits += 1
            else:
                self.prefix_misses += 1
        if held is None:
            return lm.empty_cache(self.config, capacity, self.dtype), 0
        return self._copier(capacity)(held), len(key[0])

    def keep_prefix(self, prefix: Sequence[int], capacity: int,
                    cache: Dict) -> None:
        """Keeps a copy of ``cache`` as the state after ``prefix``."""
        copy = self._copier(capacity)(cache)
        with self._lock:
            self._prefixes[(tuple(prefix), capacity)] = copy
            while len(self._prefixes) > MAX_PREFIXES:
                self._prefixes.popitem(last=False)

    @property
    def snapshots(self) -> int:
        """Instruction prefixes held."""
        with self._lock:
            return len(self._prefixes)

    def positions_in_use(self, length: int, sequences: int = 1,
                         forked_at: int = 0) -> Dict[str, int]:
        """Cache positions ``sequences`` sequences of ``length`` occupy,
        by base kind, summed over the layers that have the kind; a linear,
        a conv or a state-space mixer uses none at any length, a latent
        layer one a position, a full layer of a looped model one a pass.
        Sequences
        forked at ``forked_at`` hold the positions before it once and the
        rest once each (a sliding layer at most its window of either; a
        latent layer as a full one)."""
        cfg = self.config
        own, window = length - forked_at, cfg.sliding_window
        out = {
            lm.FULL: len(cfg.layers_of(lm.FULL)) * cfg.total_ut_steps
            * (forked_at + sequences * own),
            lm.SLIDING: len(cfg.layers_of(lm.SLIDING))
            * (min(forked_at, window) + sequences * min(own, window)),
        }
        for kind in (lm.LINEAR, lm.CONV, lm.SSM):
            if kind in cfg.base_kinds:
                out[kind] = 0
        if lm.LATENT in cfg.base_kinds:
            out[lm.LATENT] = len(cfg.layers_of(lm.LATENT)) * (
                forked_at + sequences * own)
        return out

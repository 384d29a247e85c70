"""Key/value cache of the resident language model (models/lm.py), and the
instruction prefix's cache kept across requests.

One manager holds two kinds of layer. A FULL layer keeps every position, so
its buffer is as long as the sequence may get; lengths are bucketed
(:data:`CAPACITY_STEP`) so that one executable serves every request of a
traffic mix. A SLIDING layer only ever attends the last ``sliding_window``
positions, so its buffer is a ring of that many slots whatever the length.

The expander's requests all begin with the operator's instruction text.
Its cache (both kinds, as they stand after the prefix's last token) is
computed once and kept here, next to cache/embed.py (conditioning) and
cache/prefix.py (denoise carries), which do the same for their artifacts:
a request whose prefix is held starts from a copy and prefills only its own
prompt. The executables donate the cache they are given, so what is kept is
never handed out itself.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.models import lm

#: full layers' buffers grow in steps of this many positions
CAPACITY_STEP = 256
#: a prefill chunk is padded up to a power of two, at least this
MIN_CHUNK = 64
#: instruction prefixes kept (each is one cache: ~15 MB at 1024 positions)
MAX_PREFIXES = 4


def chunk_bucket(tokens: int) -> int:
    """Padded length of a prefill chunk of ``tokens`` real tokens."""
    return max(MIN_CHUNK, 1 << max(0, tokens - 1).bit_length())


def capacity_for(positions: int) -> int:
    return -(-positions // CAPACITY_STEP) * CAPACITY_STEP


class KVCacheManager:
    """Hands out caches of one model at bucketed capacities, keeps the
    caches of instruction prefixes, and counts what is in use."""

    def __init__(self, config, dtype) -> None:
        self.config = config
        self.dtype = dtype
        self._lock = threading.Lock()
        #: (prefix ids, capacity) -> cache after the prefix's last token
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
        return jax.tree_util.tree_map(jnp.copy, held), len(key[0])

    def keep_prefix(self, prefix: Sequence[int], capacity: int,
                    cache: Dict) -> None:
        """Keeps a copy of ``cache`` as the state after ``prefix``."""
        copy = jax.tree_util.tree_map(jnp.copy, cache)
        with self._lock:
            self._prefixes[(tuple(prefix), capacity)] = copy
            while len(self._prefixes) > MAX_PREFIXES:
                self._prefixes.popitem(last=False)

    def positions_in_use(self, length: int) -> Dict[str, int]:
        """Cache positions a sequence of ``length`` occupies, by layer
        kind, summed over the layers of the kind."""
        cfg = self.config
        return {
            lm.FULL: len(cfg.layers_of(lm.FULL)) * length,
            lm.SLIDING: len(cfg.layers_of(lm.SLIDING))
            * min(length, cfg.sliding_window),
        }

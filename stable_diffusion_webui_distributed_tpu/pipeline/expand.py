"""The engine's ``expand`` stage: the family's resident language model
(``ModelFamily.expander``, models/lm.py) continues the operator's
instruction and the user's prompt, and the continuation goes on to the
prompt parser, CLIP, UNet and VAE like any prompt.

The stage's step yields a token, not a denoise step. It has two kinds of
executable, both built through ``Engine._cached`` like ``run_chunk``:
``expand_prefill`` (one padded chunk of tokens against the cache; one per
chunk length) and ``expand_decode_chunk`` (a scan of :data:`DECODE_STEPS`
tokens, each fed back as the next input). Decoding is enqueued a chunk
ahead: chunk ``i`` is dispatched before the host waits on chunk ``i - 1``'s
tokens, and the interrupt flag is polled between chunks, as the denoise
loop does between its chunks.

Draws are keyed by the IMAGE's seed (runtime/rng.py ``key_for_image``) and
the position of the token being made, so image ``i`` of a request gets the
same expansion on whichever worker its sub-range lands
(scheduler/world.py), and however decoding was cut into chunks.

What the layers hold after the instruction's last token (keys and values,
latents, recurrent state, a convolution's inputs) is kept across requests
as a snapshot (cache/kv.py): from the second request on only the user's
own tokens are prefilled, against a copy of it. Every kind of state rides in
the one cache tree, so the decode scan carries it and the executables
donate it whole.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import lm
from stable_diffusion_webui_distributed_tpu.models.tokenizer import (
    load_lm_tokenizer,
)
from stable_diffusion_webui_distributed_tpu.obs import spans as obs_spans
from stable_diffusion_webui_distributed_tpu.ops import delta_rule
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    PromptExpansion,
)
from stable_diffusion_webui_distributed_tpu.runtime import rng
from stable_diffusion_webui_distributed_tpu.serving.metrics import EXPANDER

#: tokens one decode executable makes
DECODE_STEPS = 32
#: folded into the image's key so its expansion and its noise differ
_KEY_DOMAIN = 0x6C6D


class PromptExpander:
    """The stage, for one engine whose family has an ``expander`` and whose
    ``params`` hold its weights under ``"expander"``."""

    def __init__(self, engine, tokenizer=None) -> None:
        self.engine = engine
        self.config = engine.family.expander
        mesh = engine.mesh
        self.module = lm.DecoderLM(
            self.config, dtype=engine.policy.compute_dtype,
            meshed=mesh is not None and mesh.size > 1)
        self.tokenizer = tokenizer or load_lm_tokenizer(
            None, *self.config.vocab)
        self.cache = kv.KVCacheManager(self.config,
                                       engine.policy.compute_dtype)

    # -- executables ---------------------------------------------------------

    def _prefill_fn(self, chunk: int, capacity: int):
        return self.engine._cached(
            ("expand_prefill", chunk, capacity),
            lambda: jax.jit(lm.prefill_fn(self.module), donate_argnums=(1,)))

    def _decode_fn(self, capacity: int):
        return self.engine._cached(
            ("expand_decode_chunk", DECODE_STEPS, capacity),
            lambda: jax.jit(lm.decode_chunk_fn(self.module, DECODE_STEPS),
                            donate_argnums=(1,)))

    # -- the stage -----------------------------------------------------------

    def expand(self, prompt: str, args: PromptExpansion, seed: int,
               image_index: int) -> str:
        """``prompt`` + the model's continuation of ``instruction`` +
        ``prompt``, cut to the script's ``context_chunks``."""
        with obs_spans.span("expand", new_tokens=args.max_new_tokens):
            made = self._generate(prompt, args, seed, image_index)
            with obs_spans.span("expand.detokenize", tokens=len(made)):
                text = self.tokenizer.decode(made)
                return self._fit(f"{prompt} {text}".strip(),
                                 args.context_chunks)

    def _generate(self, prompt: str, args: PromptExpansion, seed: int,
                  image_index: int) -> List[int]:
        tok = self.tokenizer
        params = self.engine.params["expander"]
        with obs_spans.span("expand.tokenize"):
            prefix = [tok.bos] + tok.encode(args.instruction)
            user = tok.encode(prompt) or [tok.eos]
        with obs_spans.span("expand.setup"):
            chunks = -(-(args.max_new_tokens - 1) // DECODE_STEPS)
            capacity = kv.capacity_for(
                len(prefix) + kv.chunk_bucket(len(user))
                + chunks * DECODE_STEPS)
            key = jax.random.fold_in(rng.key_for_image(seed, image_index),
                                     _KEY_DOMAIN)
            temperature = jnp.float32(args.temperature)
            sizes = kv.state_bytes(self.config, capacity, self.cache.dtype)
            copied = sum(sizes.values())
        with obs_spans.span("expand.prefix_copy", bytes=copied) as sp:
            cache, held = self.cache.acquire(prefix, capacity)
            if sp is not None:
                sp.attrs["hit"] = bool(held)
        recurrent = lm.LINEAR in self.config.layer_types
        conv = lm.CONV in self.config.layer_types
        latent = lm.LATENT in self.config.layer_types
        routed = []       # per executable call: (load, none held)
        masked = 0        # padded rows kept out of a recurrence or kept rows
        token = None
        for ids, start, keep in ((prefix, 0, True),
                                 (user, len(prefix), False)):
            if keep and held:
                continue
            padded = np.zeros(kv.chunk_bucket(len(ids)), np.int32)
            padded[:len(ids)] = ids
            attrs = {"tokens": len(ids), "prefix_hit": bool(held)}
            if recurrent or conv:   # rows kept out of the layers' state
                attrs["padded"] = len(padded) - len(ids)
            if recurrent:           # the form its recurrence takes
                attrs["form"] = delta_rule.form(len(padded))
            if latent:        # the form its attention takes over the cache
                attrs["latent"] = lm.latent_form(len(padded))
            with obs_spans.span("expand.prefill", **attrs):
                cache, token, step_load, step_none = self._prefill_fn(
                    len(padded), capacity)(
                        params, cache, padded, jnp.int32(start),
                        jnp.int32(len(ids)), key, temperature)
                # fenced: the span is the chunk's device time, not its
                # enqueue
                jax.block_until_ready(token)
            if keep:
                with obs_spans.span("expand.prefix_copy", hit=False,
                                    bytes=copied):
                    self.cache.keep_prefix(prefix, capacity, cache)
            routed.append((step_load, step_none))
            masked += attrs.get("padded", 0)
        made: List[int] = [int(token)]
        position = jnp.int32(len(prefix) + len(user))
        decode = self._decode_fn(capacity)
        pending = []      # at most one chunk whose tokens are not fetched
        steps = 0

        def fetch(out) -> None:
            with obs_spans.span("expand.fence_wait"):
                made.extend(np.asarray(jax.device_get(out)).tolist())

        for _ in range(chunks):
            if self.engine.state.flag.interrupted \
                    or (not args.ignore_eos and tok.eos in made):
                break
            with obs_spans.span("expand.decode_chunk", tokens=DECODE_STEPS):
                cache, token, position, out, step_load, step_none = decode(
                    params, cache, token, position, key, temperature)
            steps += DECODE_STEPS
            routed.append((step_load, step_none))
            pending.append(out)
            if len(pending) > 1:
                fetch(pending.pop(0))
        for out in pending:
            fetch(out)
        # the cut and the counters' fetch: host work with the device idle
        with obs_spans.span("expand.account", fetched=2 * len(routed)):
            made = made[:args.max_new_tokens]
            if not args.ignore_eos and tok.eos in made:
                made = made[:made.index(tok.eos)]
            length = len(prefix) + len(user) + len(made)
            loads, none_held = zip(*jax.device_get(routed))
            EXPANDER.record(
                prefilled=len(user) + (0 if held else len(prefix)),
                from_prefix=held, decoded=len(made), decode_steps=steps,
                load=np.sum(loads, axis=0), none_held=int(np.sum(none_held)),
                positions=self.cache.positions_in_use(length),
                state_bytes=sizes, prefix_snapshots=self.cache.snapshots,
                padded_rows_masked=masked,
                residual_streams=self.config.residual_streams,
                sinkhorn_iters=(self.config.sinkhorn_iters
                                if self.config.residual_streams > 1 else 0))
        return made

    def _fit(self, text: str, chunks: Optional[int]) -> str:
        """The longest tail of ``text``'s words whose CLIP tokens fit
        ``chunks`` 77-token windows (75 content tokens each)."""
        if not chunks:
            return text
        room = 75 * int(chunks)
        clip = self.engine.tokenizer
        words = text.split()
        kept = len(words)
        for word in reversed(words):
            room -= max(1, len(clip.encode(word)))
            if room < 0:
                break
            kept -= 1
        return " ".join(words[kept:])

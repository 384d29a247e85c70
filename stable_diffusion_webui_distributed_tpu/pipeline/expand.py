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

The images of one request that continue the same prompt differ in their
key alone, so where the model's kinds allow (models/lm.py:shares_a_step)
:meth:`PromptExpander.expand_batch` decodes them as sequences of ONE step:
the prompt is prefilled once at one sequence, each image's first token is
drawn from that one row of logits under its own key, the cache is forked
(cache/kv.py:fork: the prompt's rows stay where the prefill left them, held
once, each sequence gets rows of its own for what it decodes, and what has
no positions, a linear layer's or a state-space part's recurrent state and
kept inputs, is copied once a sequence) and the
scan runs over all of them, so a step streams the fixed weights once, each
distinct expert once and the prompt's keys and values (a latent layer's
latents) once. Their count is
padded up to one of cache/kv.py:SEQUENCE_BUCKETS with repeats of the last,
whose tokens are dropped. One image takes the one-sequence executables
under the keys they have always had.

The requests of one dispatch group (serving/dispatcher.py: expanded
requests of equal script arguments, where the batch ladder has a rung for
a second one) share their scans too (:meth:`PromptExpander.expand_group`):
every image of every request is a sequence of one scan, keyed by its own
request's seed and its own index. Their prompts differ, so what the
sequences share is the kept instruction alone: each starts from a copy of
it, runs the ONE-sequence prefill executable over its own prompt, and one
executable (``expand_join``, cache/kv.py:joined_rows) joins the caches into
a forked one whose shared range is the instruction's rows and whose own
rows begin with each sequence's prompt. The decode scan is the same
function; a step streams the fixed weights once, each distinct expert once
and the instruction's rows once for every request in it. ``serving.
expander`` counts such scans (``scans_joined``) and the requests and
distinct prompts they carried; a joined scan's steps count once in
``decode_steps`` and its tokens once a sequence in ``tokens_decoded``.

The stage's host work follows one rule: a piece of it runs while the
device is busy, or as one dispatch, never piece by piece while the device
waits for it. The images' keys are one jitted call
(runtime/rng.py:folded_keys) and a snapshot's copy another
(cache/kv.py:copy_tree), both kept stages of the engine; the instruction's
token ids are kept by its text; the caller's own work that needs none of
the text (``meanwhile``: the engine draws its first group's noise, keys
and carry, pipeline/engine.py:Drawn) runs under the first decode chunk
(span ``expand.ahead``); and the fetch of the counters the executables
leave on the device (span ``expand.account``) is handed to the caller
(``later``), who runs it once the UNet's first chunk is queued.

A looped model (``LMConfig.total_ut_steps`` over 1) runs its whole stack
that many times a token inside each executable; its spans carry ``passes``
and its executables return, beside the rest, which pass's state the head
read for each token made and the largest exit probability
(models/lm.py:apply_counting), which go to ``serving.expander`` as
``exit_pass``, ``exit_lambda_max`` and ``layer_passes``.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from stable_diffusion_webui_distributed_tpu.cache import kv
from stable_diffusion_webui_distributed_tpu.models import lm
from stable_diffusion_webui_distributed_tpu.models.tokenizer import (
    load_lm_tokenizer,
)
from stable_diffusion_webui_distributed_tpu.obs import spans as obs_spans
from stable_diffusion_webui_distributed_tpu.ops import delta_rule, ssm
from stable_diffusion_webui_distributed_tpu.pipeline.payload import (
    PromptExpansion,
)
from stable_diffusion_webui_distributed_tpu.runtime import rng
from stable_diffusion_webui_distributed_tpu.runtime.kept import KeptTable
from stable_diffusion_webui_distributed_tpu.serving.metrics import EXPANDER

#: tokens one decode executable makes
DECODE_STEPS = 32
#: folded into the image's key so its expansion and its noise differ
_KEY_DOMAIN = 0x6C6D


def rows_of(sequences: int, forked_at: int, steps: int,
            behind: int = 0) -> dict:
    """``rows_attended``, ``rows_read`` and ``rows_read_shared`` of
    ``EXPANDER.record``: the positions ``steps`` decode steps' queries
    attended in a layer that keeps every position (keys and values, or
    latents), step ``i``'s at ``forked_at + i`` once a sequence, and the
    positions read for them: what lies before the fork (the shared range)
    once a step for all sequences, a sequence's own rows once each.
    ``behind``: the rows the sequences hold of their own behind the fork
    before their first step, summed over them (a joined scan's prompts:
    read every step, by their own sequence alone)."""
    shared = steps * forked_at
    own = sequences * (steps * (steps + 1) // 2) + steps * behind
    return {"rows_attended": sequences * shared + own,
            "rows_read": shared + own, "rows_read_shared": shared}


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
                                       engine.policy.compute_dtype,
                                       copier=self._copy_fn)
        #: instruction text -> its token ids behind ``bos``: as many as the
        #: cache keeps snapshots of, and like them the same every request
        self._prefix_ids = KeptTable(kv.MAX_PREFIXES)

    # -- executables ---------------------------------------------------------

    def _prefill_fn(self, chunk: int, capacity: int, sequences: int = 1):
        """``sequences`` over 1: the chunk's one sequence, and the first
        token of each of that many that go on from it."""
        many = sequences > 1
        return self.engine._cached(
            ("expand_prefill", chunk, capacity)
            + ((sequences,) if many else ()),
            lambda: jax.jit(lm.prefill_fn(self.module, sequences=many),
                            donate_argnums=(1,)))

    def _fork_fn(self, capacity: int, sequences: int, own_slots: int):
        """What a fork makes anew (cache/kv.py:own_rows): of a buffer that
        keeps positions the cache it is called with gives the shape and
        is not read; one that keeps none (a linear layer's state and kept
        inputs) is copied once a sequence, all in this one executable."""
        return self.engine._cached(
            ("expand_fork", capacity, sequences, own_slots),
            lambda: jax.jit(functools.partial(
                kv.own_rows, sequences=sequences, slots=own_slots)),
            weights=0)

    def _join_fn(self, capacity: int, sequences: int, region: int,
                 own_slots: int):
        """What a join makes anew (cache/kv.py:joined_rows): the
        sequences' prompts' rows right-aligned in ``region`` slots of
        their own, ``own_slots`` behind them, and each one's copy of what
        keeps no positions; and their first tokens as the scan takes
        them, ``(sequences,)``: all in this one executable."""
        def expand_join(caches, firsts, lengths, forked_at):
            return kv.joined_rows(caches, lengths, forked_at, region,
                                  own_slots), jnp.stack(firsts)

        return self.engine._cached(
            ("expand_join", capacity, sequences, region, own_slots),
            lambda: jax.jit(expand_join), weights=0)

    def _copy_fn(self, capacity: int):
        """A snapshot's copy as one dispatch (cache/kv.py:copy_tree)."""
        return self.engine._cached(("expand_copy", capacity),
                                   lambda: jax.jit(kv.copy_tree), weights=0)

    def _keys_fn(self, batch: int):
        """The images' keys as one dispatch (runtime/rng.py:folded_keys):
        one key at one sequence, ``(batch,)`` at several."""
        return self.engine._cached(("expand_keys", batch),
                                   lambda: jax.jit(rng.folded_keys),
                                   weights=0)

    def _decode_fn(self, capacity: int, sequences: int = 1,
                   region: int = 0):
        """One image keeps the key and the function it has always had.
        ``region`` over 0: the scan over a joined cache whose sequences'
        prompts lie in that many slots of their own (the same function:
        which cache it is handed is read off the cache)."""
        many = sequences > 1
        make = lm.decode_sequences_fn if many else lm.decode_chunk_fn
        return self.engine._cached(
            ("expand_decode_chunk", DECODE_STEPS, capacity)
            + ((sequences,) if many else ())
            + (("joined", region) if region else ()),
            lambda: jax.jit(make(self.module, DECODE_STEPS),
                            donate_argnums=(1,)))

    # -- the stage -----------------------------------------------------------

    @property
    def shares_a_step(self) -> bool:
        """Whether :meth:`expand_batch` decodes its images together."""
        return lm.shares_a_step(self.config)

    def expand(self, prompt: str, args: PromptExpansion, seed: int,
               image_index: int) -> str:
        """``prompt`` + the model's continuation of ``instruction`` +
        ``prompt``, cut to the script's ``context_chunks``."""
        return self.expand_batch(prompt, args, seed, [image_index])[0]

    def expand_batch(self, prompt: str, args: PromptExpansion, seed: int,
                     image_indices: Sequence[int],
                     meanwhile: Optional[Callable[[], None]] = None,
                     later: Optional[List[Callable[[], None]]] = None
                     ) -> List[str]:
        """:meth:`expand` for each of ``image_indices``, which all continue
        the one ``prompt``: decoded together, at most the largest of
        cache/kv.py:SEQUENCE_BUCKETS a time, where the model's kinds allow
        and else one after the other. Image ``i`` gets what its own seed
        gives, whoever it is decoded beside. This is ONE request's
        expansion (the engine's own path: a request the dispatcher runs
        solo). Requests that share a dispatch share their scans too:
        :meth:`expand_group`.

        ``meanwhile`` is host work of the caller's that needs none of the
        text: it is called once, on this thread, under the first group's
        first decode chunk (:meth:`_generate`), or never where the
        expansion enqueues none. The counters' fetch of each group
        (``serving.expander``) is run before this returns, or handed to
        ``later`` for the caller to run when the device is busy again."""
        most = kv.SEQUENCE_BUCKETS[-1] if self.shares_a_step else 1
        texts: List[str] = []
        for at in range(0, len(image_indices), most):
            group = list(image_indices[at:at + most])
            with obs_spans.span("expand", new_tokens=args.max_new_tokens,
                                sequences=len(group), requests=1):
                made = self._generate(prompt, args, seed, group,
                                      meanwhile if at == 0 else None, later)
                texts += self._texts([prompt] * len(group), made, args)
        return texts

    def expand_group(self, members: Sequence[Tuple[str, int, Sequence[int]]],
                     args: PromptExpansion, rows: int = 0,
                     meanwhile: Optional[Callable[[], None]] = None,
                     later: Optional[List[Callable[[], None]]] = None
                     ) -> List[List[str]]:
        """The expansions of SEVERAL requests behind one instruction
        (serving/dispatcher.py: the live tickets of a group, whose key
        holds the script's arguments), a list of texts a member.
        ``members`` are ``(prompt, seed, image indices)`` a request. Every
        image of every member is a sequence of ONE scan (at most the
        largest of cache/kv.py:SEQUENCE_BUCKETS a scan, the rest in turns,
        as :meth:`expand_batch` does), keyed by ITS request's seed and its
        own index: image ``i`` gets what its own seed gives, whoever it is
        decoded beside, across requests as across a batch. The prompts
        differ, so the scan is a JOINED one (:meth:`_generate`): the kept
        instruction's rows are the shared range, read once a step for all,
        and each prompt's rows are its sequence's own.

        ``rows`` is the group's rung of the batch ladder: a scan is padded
        up to ``kv.sequence_bucket(rows)`` sequences, as the group's UNet
        rows are padded up to the rung, so that a request alone under a
        ladder whose lowest rung is above one runs (and compiles) what a
        full group runs. Which path a scan takes follows from that alone:
        one sequence with no pad takes the one-sequence executables,
        anything wider the join. A model whose kinds share no step
        (``shares_a_step`` false) decodes each image alone, in turn.
        ``meanwhile`` and ``later`` as :meth:`expand_batch`'s."""
        most = kv.SEQUENCE_BUCKETS[-1] if self.shares_a_step else 1
        flat = [(k, prompt, (int(seed) + int(i)) % 2 ** 32)
                for k, (prompt, seed, indices) in enumerate(members)
                for i in indices]
        texts: List[List[str]] = [[] for _ in members]
        for at in range(0, len(flat), most):
            turn = flat[at:at + most]
            owners, prompts, numbers = zip(*turn)
            batch = kv.sequence_bucket(max(len(turn), min(rows, most)))
            requests = len(set(owners))
            with obs_spans.span("expand", new_tokens=args.max_new_tokens,
                                sequences=len(turn), requests=requests):
                # a key is made of seed + index alone (runtime/rng.py:
                # key_for_image): seed 0 and the sums give each sequence
                # the key its own request gives it
                made = self._generate(
                    prompts[0] if batch == 1 else list(prompts), args, 0,
                    list(numbers), meanwhile if at == 0 else None, later,
                    batch=batch, requests=requests)
                for k, text in zip(owners,
                                   self._texts(prompts, made, args)):
                    texts[k].append(text)
        return texts

    def _texts(self, prompts: Sequence[str], made: List[List[int]],
               args: PromptExpansion) -> List[str]:
        """Each prompt and the tokens made for it as the text that goes on
        to the prompt parser, cut to the script's ``context_chunks``."""
        with obs_spans.span("expand.detokenize",
                            tokens=sum(map(len, made))):
            return [self._fit(
                f"{prompt} {self.tokenizer.decode(one)}".strip(),
                args.context_chunks) for prompt, one in zip(prompts, made)]

    def _generate(self, prompt: Union[str, Sequence[str]],
                  args: PromptExpansion, seed: int,
                  image_indices: Sequence[int],
                  meanwhile: Optional[Callable[[], None]] = None,
                  later: Optional[List[Callable[[], None]]] = None,
                  batch: int = 0, requests: int = 1) -> List[List[int]]:
        """The tokens made for each image. ``live`` images are ``batch``
        sequences of the executables (1: the one-sequence ones; given, a
        bucket at or over ``live``: the caller's pad).

        ``prompt`` is ONE text that all the images continue: it is
        prefilled once and its cache forked (cache/kv.py:fork). Or a LIST,
        a text a sequence (the sequences of several ``requests``: they
        continue prompts of their own): each sequence starts from a copy
        of the kept instruction, its prompt goes through the one-sequence
        prefill executable, and one executable JOINS the caches
        (cache/kv.py:joined_rows): the instruction's rows are the shared
        range, held once and read once a step for all, and each prompt's
        rows are its sequence's own, right-aligned in ``region`` slots (the
        widest prompt's chunk bucket) in front of its decode slots. The
        scan is the same function over either cache; the step's position
        is then common to the sequences and each stands its pad short of
        it (models/lm.py:OWN_FROM).

        ``meanwhile`` runs under the first decode chunk (span
        ``expand.ahead``). The fetch of the counters the executables left
        on the device (span ``expand.account``) goes to ``later`` where
        there is one: nothing of the request reads what it fetches, so it
        need not run here, with the device idle and the text encoder
        waiting behind it."""
        tok = self.tokenizer
        live = len(image_indices)
        joined = not isinstance(prompt, str)
        batch = max(batch, kv.sequence_bucket(live))
        params = self.engine.params["expander"]
        with obs_spans.span("expand.tokenize"):
            prefix, _ = self._prefix_ids.get(
                args.instruction,
                lambda: (tok.bos, *tok.encode(args.instruction)))
            users = [tok.encode(text) or [tok.eos]
                     for text in (prompt if joined else [prompt])]
        # the slots of their own the sequences' prompts take: none where
        # they share the one prompt, which lies in the shared range
        region = max(kv.chunk_bucket(len(user)) for user in users)
        with obs_spans.span("expand.setup"):
            chunks = -(-(args.max_new_tokens - 1) // DECODE_STEPS)
            capacity = kv.capacity_for(
                len(prefix) + region + chunks * DECODE_STEPS)
            # one dispatch: a key for one image, a row of them for
            # several, whose pad repeats the last
            indices = image_indices[0] if batch == 1 else \
                list(image_indices) + [image_indices[-1]] * (batch - live)
            key = self._keys_fn(batch)(
                np.uint32(seed), np.asarray(indices, np.uint32),
                np.uint32(_KEY_DOMAIN))
            temperature = jnp.float32(args.temperature)
            # a sequence's own rows behind a fork: a slot a decode step
            # (and a joined one's prompt in front of them)
            own_slots = chunks * DECODE_STEPS
            sizes = kv.state_bytes(self.config, capacity, self.cache.dtype,
                                   batch, own_slots + joined * region)
            alone = kv.state_bytes(     # one sequence's: a snapshot
                self.config, capacity, self.cache.dtype)
            copied = sum(alone.values())
            # what a fork copies once a sequence, and what a step of the
            # sequences reads and writes of it
            fork_copied = kv.copied_bytes(self.config, self.cache.dtype,
                                          batch)
            states = alone.get(lm.LINEAR, 0) + alone.get(lm.SSM, 0)
            stepped = 2 * batch * states
        kinds = self.config.base_kinds
        recurrent, space = lm.LINEAR in kinds, lm.SSM in kinds
        conv, latent = lm.CONV in kinds, lm.LATENT in kinds
        passes = self.config.total_ut_steps
        # span attributes of a looped model alone
        looped = {"passes": passes} if passes > 1 else {}
        # and of one whose latent layers share a step: the form a decode
        # step takes over the cache
        how = {"latent": lm.latent_form(1, sequences=batch > 1)} \
            if latent and self.shares_a_step else {}
        if recurrent and self.shares_a_step:    # and its recurrence
            how["delta"] = delta_rule.form(1, sequences=batch > 1)
        if space and self.shares_a_step:    # a state-space part's
            how["ssm"] = ssm.form(1, sequences=batch > 1)
        # and of one whose layers depart from pre-normed, rotated ones
        sites = lm.site_attrs(self.config)
        # the states a decode chunk's steps read and wrote
        moved = {"ssm_state_bytes":
                 DECODE_STEPS * 2 * batch * alone[lm.SSM]} if space else {}
        exits = []        # per executable call of a looped model
        routed = []       # per executable call: (load, none held)
        masked = 0        # padded rows kept out of a recurrence or kept rows
        from_prefix = 0   # tokens whose rows came as a copy of the kept ones

        def acquired():
            """A cache that stands behind the instruction: a copy of the
            kept one, or (the first request of an instruction) an empty
            one through the instruction's own chunk, which is then kept."""
            nonlocal from_prefix
            with obs_spans.span("expand.prefix_copy", bytes=copied) as sp:
                cache, held = self.cache.acquire(prefix, capacity)
                if sp is not None:
                    sp.attrs["hit"] = bool(held)
            from_prefix += held
            if not held:
                cache, _ = chunk(cache, held, prefix,
                                 key[0] if batch > 1 else key, keep=True)
                with obs_spans.span("expand.prefix_copy", hit=False,
                                    bytes=copied):
                    self.cache.keep_prefix(prefix, capacity, cache)
            return cache, held

        def chunk(cache, held, ids, key, firsts=1, keep=False):
            """(the cache, the token(s) drawn behind it) of one padded
            chunk: the instruction's (``keep``: it yields no token that is
            kept and runs at one sequence whatever follows it) or a
            prompt's behind it, which draws the first token of ``firsts``
            sequences."""
            nonlocal masked
            start = 0 if keep else len(prefix)
            padded = np.zeros(kv.chunk_bucket(len(ids)), np.int32)
            padded[:len(ids)] = ids
            attrs = {"tokens": len(ids), "prefix_hit": bool(held), **looped,
                     **sites}
            if recurrent or conv or space:  # rows kept out of the state
                attrs["padded"] = len(padded) - len(ids)
            if recurrent or space:  # the form its recurrence takes
                attrs["form"] = delta_rule.form(len(padded))
            if space:       # one sequence's states, read and written
                attrs["ssm_state_bytes"] = 2 * alone[lm.SSM]
            if latent:        # the form its attention takes over the cache
                attrs["latent"] = lm.latent_form(len(padded))
            if (latent or recurrent or space) and self.shares_a_step:
                # whose first tokens the chunk draws
                attrs["sequences"] = 1 if keep or joined else live
            with obs_spans.span("expand.prefill", **attrs):
                work = obs_spans.device_work("expand_prefill")
                cache, token, step_load, step_none, *chose = \
                    self._prefill_fn(len(padded), capacity, firsts)(
                        params, cache, padded, jnp.int32(start),
                        jnp.int32(len(ids)), key, temperature)
                work.queued(token)      # the cache is donated: never it
                # fenced: the span is the chunk's device time, not its
                # enqueue
                with obs_spans.fence(token):
                    jax.block_until_ready(token)
            routed.append((step_load, step_none))
            if not keep:    # the instruction's chunk yields no token
                exits.extend(chose)
            masked += attrs.get("padded", 0)
            return cache, token

        if not joined:
            cache, held = acquired()
            cache, token = chunk(cache, held, users[0], key, batch)
            forked_at = len(prefix) + len(users[0])
            position = jnp.int32(forked_at)
            if batch > 1:
                # the bytes made: the prompt's rows stay where they are,
                # and what has no positions is copied once a sequence (the
                # prefill's own is let go)
                with obs_spans.span("expand.fork", sequences=batch,
                                    bytes=sum(sizes.values()) - copied
                                    + states,
                                    state_bytes_copied=fork_copied,
                                    **looped, **how, **sites):
                    work = obs_spans.device_work("expand_fork")
                    own = self._fork_fn(capacity, batch, own_slots)(cache)
                    cache = kv.forked(cache, own)
                    # every row it made goes into the first decode chunk,
                    # but only after the fence below: nobody else may wait
                    # on one
                    one = jax.tree_util.tree_leaves(own)[0]
                    work.queued(one, watch=False)
                    with obs_spans.fence(one):
                        jax.block_until_ready(cache)    # as a prefill is
        else:
            # the shared range: the instruction's rows as its last token
            # left them, which no prompt's chunk has written behind
            shared, held = acquired()
            each = [chunk(*acquired(), user, key[b])
                    for b, user in enumerate(users)]
            each += each[-1:] * (batch - live)      # the pad repeats the last
            caches, firsts = zip(*each)
            forked_at = len(prefix)
            position = jnp.int32(forked_at + region)
            lengths = [len(user) for user in users]
            lengths += lengths[-1:] * (batch - live)
            with obs_spans.span("expand.fork", sequences=batch, joined=True,
                                prompts=len(set(prompt)),
                                bytes=sum(sizes.values()) - copied + states,
                                state_bytes_copied=fork_copied,
                                **looped, **how, **sites):
                work = obs_spans.device_work("expand_join")
                own, token = self._join_fn(
                    capacity, batch, region, own_slots)(
                        caches, firsts, np.asarray(lengths, np.int32),
                        jnp.int32(forked_at))
                cache = kv.forked(shared, own)
                one = jax.tree_util.tree_leaves(own)[0]
                work.queued(one, watch=False)
                with obs_spans.fence(one):
                    jax.block_until_ready(cache)
            del caches, each    # the sequences' own: joined, and let go
        # (live, tokens so far): the first of each from its prompt's row
        made = np.asarray(token).reshape(-1, 1)[:live].tolist()
        decode = self._decode_fn(capacity, batch, region) if joined \
            else self._decode_fn(capacity, batch)
        more = () if batch == 1 else (jnp.int32(live),)
        pending = []      # at most one chunk whose tokens are not fetched
        steps = 0
        decoded_from = len(routed)    # the executable calls that decode
        reads = []        # per decode call of several sequences
        unread = []       # such a call's routed sums that read nothing
        zeros = []        # per decode call: picks on zero-compute experts

        def fetch(out) -> None:
            with obs_spans.span("expand.fence_wait"), obs_spans.fence(out):
                out = np.asarray(jax.device_get(out)).reshape(
                    DECODE_STEPS, -1)
                for one, column in zip(made, out.T):
                    one.extend(column.tolist())

        for _ in range(chunks):
            if self.engine.state.flag.interrupted or (
                    not args.ignore_eos
                    and all(tok.eos in one for one in made)):
                break
            with obs_spans.span("expand.decode_chunk", tokens=DECODE_STEPS,
                                sequences=live, requests=requests,
                                **looped, **how, **sites, **moved):
                work = obs_spans.device_work("expand_decode_chunk")
                cache, token, position, out, step_load, step_none, *read = \
                    decode(params, cache, token, position, key,
                           temperature, *more)
                work.queued(out)
            steps += DECODE_STEPS
            routed.append((step_load, step_none))
            if self.config.zero_experts:    # the last of what it returns
                zeros.append(read.pop())
            if looped:      # the last of what a looped model returns
                exits.append(read.pop())
            reads += read[:1]
            unread += read[1:]
            pending.append(out)
            if meanwhile is not None:
                # the device has a chunk to run and this thread nothing to
                # do but wait for it
                with obs_spans.span("expand.ahead"):
                    meanwhile()
                meanwhile = None
            if len(pending) > 1:
                fetch(pending.pop(0))
        for out in pending:
            fetch(out)
        made = [one[:args.max_new_tokens] for one in made]
        if not args.ignore_eos:     # each sequence is cut at its own
            made = [one[:one.index(tok.eos)] if tok.eos in one else one
                    for one in made]
        # a joined scan's sequences: the longest prompt's
        length = forked_at + joined * max(map(len, users)) \
            + max(map(len, made))
        decoded = sum(map(len, made))

        def account() -> None:
            with obs_spans.span("expand.account",
                                fetched=2 * len(routed) + len(reads)
                                + len(unread) + 2 * len(exits)
                                + len(zeros)):
                loads, none_held = zip(*jax.device_get(routed))
                # a step of one token reads as many experts as it has picks
                # held; a step of several the distinct ones, counted beside
                # the load on the device
                held_picks = int(np.sum(loads[decoded_from:]))
                if reads:
                    read, none_read = map(np.sum,
                                          jax.device_get((reads, unread)))
                else:   # a step of one token: its tokens with no held pick
                    read = held_picks
                    none_read = np.sum(none_held[decoded_from:])
                EXPANDER.record(
                    prefilled=sum(map(len, users))
                    + (0 if held else len(prefix)),
                    from_prefix=from_prefix, sequences=live,
                    decoded=decoded, decode_steps=steps,
                    experts_read=int(read),
                    load=np.sum(loads, axis=0),
                    none_held=int(np.sum(none_held)),
                    **rows_of(live, forked_at, steps,
                              joined * sum(map(len, users))),
                    positions=self.cache.positions_in_use(
                        length, live, forked_at if batch > 1 else 0),
                    state_bytes=sizes,
                    prefix_snapshots=self.cache.snapshots,
                    padded_rows_masked=masked,
                    residual_streams=self.config.residual_streams,
                    sinkhorn_iters=(self.config.sinkhorn_iters
                                    if self.config.residual_streams > 1
                                    else 0),
                    state_bytes_stepped=steps * stepped,
                    fork_bytes_copied=fork_copied,
                    zero_expert_picks=int(np.sum(jax.device_get(zeros)))
                    if zeros else 0,
                    expert_picks_held=held_picks,
                    expert_calls=steps * len(self.config.expert_layers),
                    expert_calls_unread=int(none_read),
                    requests_joined=joined * requests,
                    prompts_joined=len(set(prompt)) if joined else 0,
                    **self._passes_run(exits, steps))

        if later is None:
            account()
        else:
            later.append(account)
        return made

    def _passes_run(self, exits, steps: int) -> dict:
        """What a looped model adds to ``EXPANDER.record``: the passes of
        the stack its ``steps`` decode steps ran (every pass of every
        step; a prefill chunk's are not among them), the tokens by the
        pass the head read, the largest exit probability. ``exits`` are
        the executables' own counts, still on the device. A prefill of
        several sequences reads ONE row for all their first tokens, and
        counts it once."""
        if not exits:
            return {}
        counts, gates = zip(*jax.device_get(exits))
        return {"layer_passes": self.config.total_ut_steps * steps,
                "exit_pass": np.sum(counts, axis=0),
                "exit_lambda_max": float(np.max(gates))}

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

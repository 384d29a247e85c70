"""Kept programs: a stage's compiled executable is serialized when it is
first made, and a process that finds it loads it instead of tracing.

A restarted worker traces and lowers every stage again, programs the
process before it traced byte for byte, with the device idle; JAX's
persistent compile cache (``runtime/mesh.py``) only spares it the backend
compile. So wherever that cache is placed, ``Engine._cached`` makes each
cell an :class:`AotFunction`: the first call of a signature looks the
executable up in the store, a subdirectory (:data:`SUBDIR`) of the compile
cache's own directory, and deserializes it
(``jax.experimental.serialize_executable``); a miss traces, compiles
(through the compile cache, as ever) and keeps the result. Whoever empties
or moves the compile cache empties or moves the store with it. Where no
cache directory is placed (:func:`store_dir` is None: most unit tests)
nothing is kept and ``_cached`` builds the plain ``jax.jit``. There is no
switch.

What a cell's id holds, so that a parent's program never answers its
change, nor one model's another's:

- the ``_cached`` compile key and the full call signature (static values,
  pytree structure, every leaf's shape, dtype and weak type), as before;
- the engine's *context* (``Engine._program_context``): the hyperparameters
  of every module a stage applies, the policy's dtypes, the noise schedule,
  the mesh. The expander's keys name no model; the context does;
- :func:`env_digest`: every ``SDTPU_*``, ``XLA_FLAGS`` and
  ``LIBTPU_INIT_ARGS`` variable as set when the stage is made (many are
  read at trace time);
- the store's fingerprint (:func:`runtime_fingerprint`): jax and jaxlib,
  the backend's platform and ``platform_version`` (the libtpu build),
  device kind and count, process count, and :func:`source_digest`, a hash
  of every ``*.py`` of this package.

Everything else a stage's function closes over is a function of those: the
sigma ladder of ``run_chunk`` (sampler, steps, schedule), the VAE's scaling
factor, the modules. Weights are arguments, never constants.

Safety:

- **Never a wrong program.** Anything that could change the traced program
  is in the id; a different id is a miss.
- **Never a crash, never a loop on a bad file.** An artifact that cannot
  be serialized, or that a later process fails to load, is *refused*: the
  cell is marked so in the manifest, and every process after that traces
  that stage as it always did, without trying again. An artifact damaged
  on disk (content hash) is dropped and made again. An executable that
  the persistent compile cache handed over is kept only where the backend
  can serialize such a one whole (:func:`reserializes_whole`: a TPU's can,
  XLA:CPU's cannot, and its artifact would load and then fail when it
  RUNS, which no load shows); elsewhere that process keeps nothing of it.
  Nothing is loaded back when it is saved: on the chip every artifact of
  every cell loaded, and the proof cost a cold start as much again as a
  warm one's loads (PERF.md section 6, PR 53).

The call path after a signature's first call is a dict lookup on the
shapes of the arguments that are not weights (the compile key fixes the
weights' shapes; ``weights`` says how many leading arguments they are) and
the ``Compiled`` object's own call, which donates what ``jax.jit`` would.

What the trace-time counters counted (attention sites, upsample forms, the
expander's products) is kept in the manifest beside the program and
counted again at a load (serving/metrics.py:replay_sites), so
``/internal/status`` reads the same either way. ``serving.programs``
counts stages ``loaded`` and ``traced`` and the seconds of loading;
``sdtpu_aot_total{outcome}`` and ``sdtpu_aot_load_seconds`` the same per
event. ``tools/aot_report.py`` renders the manifest and verifies it
against the artifacts on disk.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import pickle
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

from stable_diffusion_webui_distributed_tpu.runtime.config import env_as_set

MANIFEST_NAME = "manifest.json"
#: Artifact filename suffix (the compressed pickle of the serialization).
ARTIFACT_SUFFIX = ".aotx"
#: Manifest schema version (bumped on layout changes; a reader that meets
#: another schema treats every cell as a miss rather than guessing).
SCHEMA = 2
#: the store's directory inside the compile cache's
SUBDIR = "sdtpu-programs"
#: environment variables read while a stage is traced or compiled
_ENV_PREFIX = "SDTPU_"
_ENV_NAMES = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")


def store_dir() -> Optional[str]:
    """Where programs are kept: inside the directory the persistent
    compile cache is placed in (``JAX_COMPILATION_CACHE_DIR`` or
    ``runtime/mesh.py:enable_compilation_cache``), or None where none is
    placed or the cache is switched off. Read from ``jax.config`` at every
    call: a fact of the process, not a setting of this module."""
    import jax

    placed = jax.config.jax_compilation_cache_dir
    if not placed or not jax.config.jax_enable_compilation_cache:
        return None
    return os.path.join(str(placed), SUBDIR)


# -- what a program was made from --------------------------------------------

@functools.lru_cache(maxsize=1)
def source_digest() -> str:
    """A hash of every ``*.py`` under this package (paths and bytes): the
    code a stage was traced from. 35 k lines hash in milliseconds."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            h.update(os.path.relpath(path, root).encode("utf-8") + b"\0")
            with open(path, "rb") as f:
                h.update(f.read() + b"\0")
    return h.hexdigest()[:16]


def env_digest() -> str:
    """A hash of every ``SDTPU_*``, ``XLA_FLAGS`` and ``LIBTPU_INIT_ARGS``
    variable as set now."""
    return hashlib.sha256(json.dumps(
        env_as_set(_ENV_PREFIX, _ENV_NAMES)).encode("utf-8")).hexdigest()[:16]


def runtime_fingerprint() -> Dict[str, str]:
    """The facts that make an executable transferable: same jax/jaxlib,
    same backend platform and build, same device kind, same device/process
    topology, same sources. Anything else and a deserialized program could
    silently be another program, or target hardware it was not compiled
    for."""
    import jax
    import jaxlib

    devs = jax.devices()
    return {
        "jax": str(jax.__version__),
        "jaxlib": str(getattr(jaxlib, "__version__", "")),
        "platform": str(devs[0].platform),
        "platform_version": str(devs[0].client.platform_version),
        "device_kind": str(devs[0].device_kind),
        "device_count": str(len(devs)),
        "process_count": str(jax.process_count()),
        "source": source_digest(),
    }


def fingerprint_id(fp: Dict[str, str]) -> str:
    data = json.dumps(fp, sort_keys=True).encode("utf-8")
    return hashlib.sha256(data).hexdigest()[:16]


# -- call signatures ---------------------------------------------------------

def _leaf_sig(leaf: Any) -> str:
    import jax

    if isinstance(leaf, jax.core.Tracer):  # callers filter; belt-and-braces
        raise TypeError("tracer leaf has no concrete call signature")
    try:
        aval = jax.api_util.shaped_abstractify(leaf)
        return (f"{aval.dtype.name}{list(aval.shape)}"
                f"w{int(bool(getattr(aval, 'weak_type', False)))}")
    except Exception:  # noqa: BLE001 — non-array leaf: identity by repr
        return f"py:{leaf!r}"


def _tree_sig(obj: Any) -> str:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(obj)
    return str(treedef) + "|" + ";".join(_leaf_sig(l) for l in leaves)


def call_signature(args: Tuple, kwargs: Dict,
                   static_argnums: Tuple[int, ...] = ()) -> str:
    """Stable string identity of one concrete call, weights included:
    static positions by value (they are baked into the executable),
    dynamic positions and kwargs by pytree structure + per-leaf
    shape/dtype/weak-type. Taken once a signature, when its executable is
    looked up in the store; :meth:`AotFunction.bound_signature` is what
    every call takes."""
    static = set(int(i) for i in static_argnums)
    parts = []
    for i, a in enumerate(args):
        if i in static:
            parts.append(f"s{i}={a!r}")
        else:
            parts.append(f"d{i}={_tree_sig(a)}")
    for k in sorted(kwargs):
        parts.append(f"k:{k}={_tree_sig(kwargs[k])}")
    return "&".join(parts)


# -- the artifact store ------------------------------------------------------

class AotStore:
    """Content-addressed executable artifacts + JSON manifest on disk.

    Layout: ``<root>/manifest.json`` maps cell ids (hash of compile key +
    call signature + the store's fingerprint) to artifact records;
    ``<root>/<sha256>.aotx`` holds the pickled serialization, named by its
    own content hash so a truncated or bit-flipped file can never satisfy
    its manifest entry. The fingerprint is part of the id, so the programs
    of two source trees (a parent and its change) or two libtpu builds
    live side by side in one directory and neither answers the other.
    Several processes may share the directory (a pool's workers): the
    manifest on disk is read again before every write and written
    tmp+rename under a name of the writer's own, so a crashed or racing
    writer leaves a whole manifest; a cell lost to a race is traced again
    by the next process, never served wrong."""

    def __init__(self, root: str,
                 fingerprint: Optional[Dict[str, str]] = None) -> None:
        self.root = root
        self.fp = dict(fingerprint) if fingerprint is not None \
            else runtime_fingerprint()
        self.fp_id = fingerprint_id(self.fp)
        # RLock: the manifest helpers re-enter the guard held by their
        # public callers, so lock-holding stays lexical in every frame.
        self._lock = threading.RLock()
        self._manifest: Optional[Dict[str, Any]] = None  # guarded-by: _lock
        #: load/save outcome tallies for this process (the warmup report
        #: reads them; /internal exposure rides sdtpu_aot_total)
        self.stats: Dict[str, int] = {"hit": 0, "miss": 0, "saved": 0,
                                      "fallback": 0,
                                      "refused": 0}  # guarded-by: _lock

    # -- manifest ---------------------------------------------------------

    def cell_id(self, key_str: str, sig_str: str) -> str:
        data = json.dumps([key_str, sig_str, self.fp_id]).encode("utf-8")
        return hashlib.sha256(data).hexdigest()[:32]

    def _read_manifest(self) -> Dict[str, Any]:
        try:
            with open(os.path.join(self.root, MANIFEST_NAME),
                      encoding="utf-8") as f:
                loaded = json.load(f)
            if isinstance(loaded, dict) \
                    and loaded.get("schema") == SCHEMA \
                    and isinstance(loaded.get("cells"), dict):
                return loaded
        except (OSError, ValueError):
            pass  # absent or damaged manifest = empty store
        return {"schema": SCHEMA, "cells": {}}

    def _load_manifest_locked(self) -> Dict[str, Any]:
        with self._lock:  # re-entrant under callers already holding it
            if self._manifest is None:
                self._manifest = self._read_manifest()
            return self._manifest

    def _update_manifest_locked(self, cid: str,
                                cell: Optional[Dict[str, Any]]) -> None:
        """Set (or, with None, drop) one cell, over what the directory's
        other writers have put there since this process last read it."""
        os.makedirs(self.root, exist_ok=True)
        path = os.path.join(self.root, MANIFEST_NAME)
        tmp = f"{path}.{os.getpid()}.tmp"
        with self._lock:  # re-entrant under callers already holding it
            doc = self._manifest = self._read_manifest()
            if cell is None:
                doc["cells"].pop(cid, None)
            else:
                doc["cells"][cid] = cell
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, path)

    def manifest(self) -> Dict[str, Any]:
        """Deep-ish copy of the manifest document (cells copied)."""
        with self._lock:
            doc = self._load_manifest_locked()
            return {"schema": doc.get("schema"),
                    "cells": {k: dict(v) for k, v in doc["cells"].items()}}

    def stats_snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.stats)

    def _count(self, outcome: str) -> None:
        with self._lock:
            self.stats[outcome] = self.stats.get(outcome, 0) + 1
        from stable_diffusion_webui_distributed_tpu.obs import (
            prometheus as obs_prom,
        )

        obs_prom.aot_count(outcome)

    # -- load / save ------------------------------------------------------

    def load(self, key_str: str, sig_str: str
             ) -> Tuple[str, Optional[bytes], List[list]]:
        """Look one cell up. Returns ``(outcome, blob, sites)`` where
        outcome is ``hit`` (blob is the serialization, sites what its
        trace counted), ``miss`` (no such cell under this fingerprint),
        ``refused`` (its artifact did not load once: trace, keep nothing)
        or ``corrupt`` (artifact missing or content hash diverged — the
        cell is dropped so a fresh trace re-fills it). Never raises."""
        cid = self.cell_id(key_str, sig_str)
        with self._lock:
            doc = self._load_manifest_locked()
            cell = doc["cells"].get(cid)
            if cell is None:
                return "miss", None, []
            if cell.get("refused"):
                return "refused", None, []
            cell = dict(cell)
        status, blob = self._artifact(cell)
        if status != "ok":
            try:
                self._update_manifest_locked(cid, None)
            except OSError:
                pass
            return "corrupt", None, []
        return "hit", blob, list(cell.get("sites") or [])

    def _artifact(self, cell: Dict[str, Any]
                  ) -> Tuple[str, Optional[bytes]]:
        """``("ok", bytes)`` of a kept cell's artifact, or ``missing`` /
        ``sha_mismatch`` and None: named by its content hash, so a
        truncated or bit-flipped file never answers its manifest entry."""
        try:
            with open(os.path.join(self.root, str(cell.get("file", ""))),
                      "rb") as f:
                blob = f.read()
        except OSError:
            return "missing", None
        if hashlib.sha256(blob).hexdigest() != str(cell.get("sha256", "")):
            return "sha_mismatch", None
        return "ok", blob

    def _cell(self, key_str: str, sig_str: str, kind: str,
              **more: Any) -> Dict[str, Any]:
        return {"kind": str(kind), "key": key_str, "sig": sig_str,
                "fingerprint_id": self.fp_id, "fingerprint": dict(self.fp),
                "created_at": time.time(),  # sdtpu-lint: wallclock
                **more}

    def save(self, key_str: str, sig_str: str, kind: str,
             blob: bytes, sites: Optional[List[list]] = None) -> bool:
        """Persist one executable's serialization, with what its trace
        counted, and back-fill the manifest. Content-addressed: the
        artifact file is named by its sha256. Best-effort — a full disk
        loses the artifact, never the request."""
        sha = hashlib.sha256(blob).hexdigest()
        fname = sha + ARTIFACT_SUFFIX
        try:
            os.makedirs(self.root, exist_ok=True)
            path = os.path.join(self.root, fname)
            if not os.path.exists(path):
                tmp = f"{path}.{os.getpid()}.tmp"
                with open(tmp, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
            self._update_manifest_locked(
                self.cell_id(key_str, sig_str),
                self._cell(key_str, sig_str, kind, file=fname,
                           bytes=len(blob), sha256=sha,
                           sites=list(sites or [])))
        except OSError:
            return False
        self._count("saved")
        return True

    def refuse(self, key_str: str, sig_str: str, kind: str,
               why: str) -> None:
        """Mark a cell whose artifact does not load: no process tries to
        keep or load it again (until the fingerprint changes)."""
        try:
            self._update_manifest_locked(
                self.cell_id(key_str, sig_str),
                self._cell(key_str, sig_str, kind, refused=str(why)[:200]))
        except OSError:
            pass
        self._count("refused")

    def verify(self) -> Dict[str, Any]:
        """Manifest/artifact divergence check (``tools/aot_report.py``):
        every kept cell's artifact must exist with the recorded content
        hash, and every ``*.aotx`` on disk must be claimed by some cell."""
        doc = self.manifest()
        cells = doc["cells"]
        rows, bad = [], []
        claimed = set()
        for cid, cell in sorted(cells.items()):
            if cell.get("refused"):
                status = "refused"
            else:
                claimed.add(str(cell.get("file", "")))
                status = self._artifact(cell)[0]
            if status not in ("ok", "refused"):
                bad.append(cid)
            rows.append({"cell": cid, "kind": cell.get("kind"),
                         "key": cell.get("key"), "sig": cell.get("sig"),
                         "bytes": cell.get("bytes"),
                         "fingerprint_id": cell.get("fingerprint_id"),
                         "status": status})
        orphans = []
        try:
            for fname in sorted(os.listdir(self.root)):
                if fname.endswith(ARTIFACT_SUFFIX) \
                        and fname not in claimed:
                    orphans.append(fname)
        except OSError:
            pass
        return {"root": self.root, "fingerprint": dict(self.fp),
                "fingerprint_id": self.fp_id, "cells": rows,
                "divergent": bad, "orphans": orphans,
                "ok": not bad and not orphans}


# -- process-wide store (keyed by resolved directory) ------------------------

_STORE_LOCK = threading.Lock()
_STORES: Dict[str, AotStore] = {}  # guarded-by: _STORE_LOCK


def get_store() -> Optional[AotStore]:
    """The store under the CURRENT compile cache directory, or None where
    none is placed — re-resolved per call, so a process whose cache is
    placed late, or moved, follows it."""
    root = store_dir()
    if root is None:
        return None
    with _STORE_LOCK:
        store = _STORES.get(root)
        if store is None:
            store = AotStore(root)
            _STORES[root] = store
        return store


# -- the per-cell wrapper ----------------------------------------------------

def _compress(data: bytes) -> bytes:
    """As JAX's own cache compresses its entries: zstandard where it is
    installed, else zlib; the first four bytes say which. A TPU stage's
    serialization is 30-220 MB and shrinks about sixfold."""
    try:
        import zstandard
    except ImportError:
        return b"ZLIB" + zlib.compress(data, 1)
    # level 1 on every core: a cold start waits for this
    return b"ZSTD" + zstandard.ZstdCompressor(
        level=1, threads=-1).compress(data)


def _decompress(blob: bytes) -> bytes:
    if blob[:4] == b"ZSTD":
        import zstandard

        return zstandard.ZstdDecompressor().decompress(blob[4:])
    if blob[:4] == b"ZLIB":
        return zlib.decompress(blob[4:])
    raise ValueError("not a kept program")


def _serialize_compiled(compiled) -> bytes:
    from jax.experimental import serialize_executable as se

    payload_bytes, in_tree, out_tree = se.serialize(compiled)
    # the ids of the devices the program runs on ride along: loading
    # defaults to EVERY device of the backend, which an executable compiled
    # for fewer (one chip of a host, a mesh slice) then refuses to run on
    device_ids = [d.id for d in
                  compiled.runtime_executable().local_devices()]
    return _compress(pickle.dumps(
        (payload_bytes, in_tree, out_tree, device_ids)))


def _deserialize_compiled(blob: bytes):
    import jax
    from jax.experimental import serialize_executable as se

    payload_bytes, in_tree, out_tree, device_ids = pickle.loads(
        _decompress(blob))
    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        payload_bytes, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


@functools.lru_cache(maxsize=1)
def reserializes_whole() -> bool:
    """Whether this backend can keep an executable that was itself loaded
    from a serialization, which is what the persistent compile cache hands
    over: asked of the backend itself, once a process, with a program of
    one fusion (serialize, load, serialize THAT, load, run). XLA:CPU
    cannot: the second artifact loads and then lacks its functions when it
    runs (``NOT_FOUND: Function add_convert_fusion not found``), which no
    load at save time shows."""
    import jax
    import jax.numpy as jnp

    x = jnp.arange(8.0)
    try:
        exe = jax.jit(lambda x: (x * 2.0 + 1.0).astype(jnp.int32)
                      ).lower(x).compile()
        for _ in range(2):
            exe = _deserialize_compiled(_serialize_compiled(exe))
        return [int(v) for v in exe(x)] == list(range(1, 16, 2))
    except Exception:  # noqa: BLE001 — "no" is an answer
        return False


class AotFunction:
    """One ``Engine._cached`` cell where programs are kept: a lazy
    dispatcher from concrete call signatures to loaded-or-traced
    executables.

    The wrapped ``build()`` is the same zero-cost jit-factory the plain
    path caches; it is only invoked when a signature actually needs a
    trace (or when the call carries tracers and must inline). Compiled
    executables take DYNAMIC arguments only — static positions are baked
    in at lower time and dropped at call time. The first ``weights``
    positional arguments are parameter trees whose shapes the compile key
    fixes: they are in the store's id (:func:`call_signature`) and not in
    what a call is told apart by (:meth:`bound_signature`). ``context`` is
    the engine's part of the id.

    Thread shape: the instance lock guards only the executable table and
    the built jit function; deserialize/compile/IO all run outside it
    (two racing threads may duplicate a compile — the dispatcher's
    execution lock makes that unreachable in serving, and it is merely
    wasteful, never wrong)."""

    def __init__(self, key: Tuple, build: Callable[[], Callable],
                 static_argnums: Tuple[int, ...] = (), weights: int = 1,
                 context: str = "",
                 store: Optional[AotStore] = None) -> None:
        self.key = key
        self.kind = str(key[0])
        self.key_str = repr(key) + (f"|{context}" if context else "")
        self.static_argnums = tuple(int(i) for i in static_argnums)
        self.weights = int(weights)
        self._build = build
        self._explicit_store = store
        self._lock = threading.Lock()
        self._jit: Optional[Callable] = None  # guarded-by: _lock
        self._exes: Dict[Any, Any] = {}  # guarded-by: _lock

    # -- plumbing ---------------------------------------------------------

    def _store(self) -> Optional[AotStore]:
        return self._explicit_store if self._explicit_store is not None \
            else get_store()

    def _jit_fn(self) -> Callable:
        with self._lock:
            fn = self._jit
        if fn is None:
            fn = self._build()  # cheap: creates the jit wrapper only
            with self._lock:
                if self._jit is None:
                    self._jit = fn
                fn = self._jit
        return fn

    def _dynamic(self, args: Tuple) -> Tuple:
        static = self.static_argnums
        if not static:
            return args
        return tuple(a for i, a in enumerate(args) if i not in static)

    def executable_count(self) -> int:
        with self._lock:
            return len(self._exes)

    def bound_signature(self, args: Tuple, kwargs: Dict):
        """What tells this cell's executables apart, hashable and cheap:
        the static values, and tree structure, shape, dtype and weak type
        of the arguments behind the weights. None when one of them is a
        tracer (the call is inside another function's trace)."""
        import jax

        static = self.static_argnums
        light = [a for i, a in enumerate(args)
                 if i >= self.weights and i not in static]
        leaves, treedef = jax.tree_util.tree_flatten((light, kwargs))
        shapes = []
        for leaf in leaves:
            if isinstance(leaf, jax.core.Tracer):
                return None
            dtype = getattr(leaf, "dtype", None)
            shapes.append(type(leaf) if dtype is None else
                          (leaf.shape, dtype,
                           getattr(leaf, "weak_type", False)))
        return (tuple(args[i] for i in static), treedef, tuple(shapes))

    # -- the call path ----------------------------------------------------

    def __call__(self, *args, **kwargs):
        sig = self.bound_signature(args, kwargs)
        if sig is None:
            # called from inside another trace (e.g. decode under the
            # decode-u8 jit): inline through the plain jitted function
            return self._jit_fn()(*args, **kwargs)
        with self._lock:
            exe = self._exes.get(sig)
        if exe is None:
            exe = self._materialize(args, kwargs)
            with self._lock:
                exe = self._exes.setdefault(sig, exe)
        try:
            return exe(*self._dynamic(args), **kwargs)
        except TypeError:
            # the weights are not what this signature's executable was made
            # for (a swapped VAE of other shapes): jax.jit retraces where
            # Compiled refuses, before anything runs or is donated
            return self._jit_fn()(*args, **kwargs)

    def _load(self, store: AotStore, key_str: str, sig: str):
        """(the kept executable of this signature or None, the outcome)"""
        from stable_diffusion_webui_distributed_tpu.obs import (
            perf as obs_perf,
            spans as obs_spans,
        )
        from stable_diffusion_webui_distributed_tpu.serving.metrics import (
            METRICS, replay_sites,
        )

        t0 = time.perf_counter()
        outcome, blob, sites = store.load(key_str, sig)
        if blob is None:
            return None, outcome
        try:
            with obs_spans.span("aot_load", kind=self.kind,
                                key=self.key_str):
                exe = _deserialize_compiled(blob)
        except Exception as e:  # noqa: BLE001 — never crash on an artifact
            store.refuse(key_str, sig, self.kind, f"load: {e!r}")
            return None, "unloadable"
        seconds = time.perf_counter() - t0
        replay_sites(sites)
        store._count("hit")
        METRICS.record_aot_load(self.kind, seconds)
        obs_perf.LEDGER.record_compile(self.kind, seconds,
                                       source="aot_load")
        return exe, outcome

    def _materialize(self, args: Tuple, kwargs: Dict):
        from stable_diffusion_webui_distributed_tpu.obs import (
            journal as obs_journal,
            perf as obs_perf,
            spans as obs_spans,
        )
        from stable_diffusion_webui_distributed_tpu.serving.metrics import (
            METRICS, XLA, capture_sites, install_xla_listener,
        )

        install_xla_listener()
        store = self._store()
        outcome = key_str = sig = ""
        if store is not None:
            key_str = f"{self.key_str}|env:{env_digest()}"
            sig = call_signature(args, kwargs, self.static_argnums)
            exe, outcome = self._load(store, key_str, sig)
            if exe is not None:
                return exe
            if outcome in ("corrupt", "unloadable"):
                # damaged artifact: fall back to a fresh trace — journaled
                # so an operator can see the store decay
                store._count("fallback")
                if obs_journal.enabled():
                    obs_journal.emit("aot_fallback", f"aot-{self.kind}",
                                     reason=outcome, key=self.key_str,
                                     sig=sig[:128])
            else:
                store._count("miss")
        METRICS.record_traced(self.kind)
        t0 = time.perf_counter()
        hits = XLA.cache_hits_on_thread()
        with obs_spans.span("compile", kind=self.kind, key=self.key_str), \
                capture_sites() as sites:
            exe = self._jit_fn().lower(*args, **kwargs).compile()
        obs_perf.LEDGER.record_compile(
            self.kind, time.perf_counter() - t0, source="fresh_compile")
        if outcome in ("miss", "corrupt"):
            self._keep(store, key_str, sig, exe, sites,
                       handed_over=XLA.cache_hits_on_thread() > hits)
        return exe

    def _keep(self, store: AotStore, key_str: str, sig: str, exe,
              sites: List[list], handed_over: bool) -> None:
        """Serialize and save; an executable that cannot be serialized is
        refused for good. One that the persistent compile cache handed
        over is kept only where the backend can
        (:func:`reserializes_whole`); elsewhere nothing is written and the
        cell stays open for a process that compiles it."""
        if handed_over and not reserializes_whole():
            store._count("refused")
            return
        try:
            blob = _serialize_compiled(exe)
        except Exception as e:  # noqa: BLE001 — persistence is best-effort
            store.refuse(key_str, sig, self.kind, f"save: {e!r}")
            return
        store.save(key_str, sig, self.kind, blob, sites)

"""AOT executable artifacts: serialize compiled stages, hydrate cold engines.

A fresh engine pays the whole bucket-ladder compile before its first
request — minutes on TPU — even though every executable it is about to
build was already built, byte for byte, by the process it replaced. The
persistent XLA cache (``runtime/mesh.py``) softens this but still re-runs
tracing, lowering and cache probing per stage. This module closes the
loop the way ahead-of-time compilation systems do: each compiled stage is
serialized once (``jax.experimental.serialize_executable``) and persisted
under ``SDTPU_AOT_DIR``, keyed by the EXISTING
``Engine._cached`` compile key plus the *call signature* (abstract shapes
/ dtypes / static values of one concrete call — one compile key can host
several executables, e.g. the encode stage retraces per chunk count) plus
a device/topology/jaxlib fingerprint. A restarted engine then
*deserializes* instead of compiling: ``Engine._cached`` wraps each cell
in an :class:`AotFunction` whose first call per signature tries
load-before-build.

Safety contract (the acceptance bar for this tier):

- **Never a wrong executable.** The manifest records the runtime
  fingerprint (jax/jaxlib versions, backend platform, device kind and
  count, process count) per cell; a mismatch is a *fallback to compile*,
  journaled as ``aot_fallback`` — never a deserialize attempt.
- **Never a crash.** A corrupt, truncated or unpicklable artifact (the
  content hash in the manifest catches byte damage before pickle sees
  it) falls back to a fresh compile and back-fills the store.
- **Gate off = byte-identical.** ``SDTPU_AOT`` defaults off; with it off
  ``Engine._cached`` takes its pre-existing path untouched (hash-pinned
  in tests/test_aot.py).

Evidence: every artifact event counts into ``sdtpu_aot_total{outcome}``
(hit / miss / saved / fallback), deserialize latency lands in the
``sdtpu_aot_load_seconds`` sibling of ``sdtpu_compile_seconds`` (so MFU /
ledger analysis never mistakes a 200ms load for a real compile), and
``DispatchMetrics.aot_loads`` mirrors the per-kind compile counters the
serving asserts key on. ``tools/aot_report.py`` renders the manifest and
verifies it against the artifacts on disk.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from stable_diffusion_webui_distributed_tpu.runtime.config import (
    env_flag, env_str,
)

MANIFEST_NAME = "manifest.json"
#: Artifact filename suffix (pickled (payload, in_tree, out_tree) triple).
ARTIFACT_SUFFIX = ".aotx"
#: Manifest schema version (bumped on layout changes; a reader that meets
#: a newer schema treats every cell as a miss rather than guessing).
SCHEMA = 1


def enabled() -> bool:
    """Master gate — re-read per call so tests/bench phases can flip it."""
    return env_flag("SDTPU_AOT", False)


def default_dir() -> str:
    """Artifact root: ``SDTPU_AOT_DIR``, default ``~/.cache/sdtpu-aot``."""
    return env_str("SDTPU_AOT_DIR",
                   os.path.expanduser("~/.cache/sdtpu-aot"))


# -- runtime fingerprint -----------------------------------------------------

def runtime_fingerprint() -> Dict[str, str]:
    """The facts that make an executable transferable: same jax/jaxlib,
    same backend platform, same device kind, same device/process
    topology. Anything else and a deserialized program could silently
    target hardware it was not compiled for."""
    import jax
    import jaxlib

    devs = jax.devices()
    return {
        "jax": str(jax.__version__),
        "jaxlib": str(getattr(jaxlib, "__version__", "")),
        "platform": str(devs[0].platform),
        "device_kind": str(devs[0].device_kind),
        "device_count": str(len(devs)),
        "process_count": str(jax.process_count()),
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


def has_tracer(args: Tuple, kwargs: Dict) -> bool:
    """Is any leaf of this call a tracer? (The decode-u8 stage calls the
    cached float decode INSIDE its own trace — that call must inline
    through the plain jitted function, never touch an executable.)"""
    import jax

    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        if isinstance(leaf, jax.core.Tracer):
            return True
    return False


def call_signature(args: Tuple, kwargs: Dict,
                   static_argnums: Tuple[int, ...] = ()) -> str:
    """Stable string identity of one concrete call: static positions by
    value (they are baked into the executable), dynamic positions and
    kwargs by pytree structure + per-leaf shape/dtype/weak-type."""
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
    call signature) to artifact records; ``<root>/<sha256>.aotx`` holds
    the pickled ``(payload, in_tree, out_tree)`` serialization triple,
    named by its own content hash so a truncated or bit-flipped file can
    never satisfy its manifest entry. Writes are tmp+rename so a crashed
    writer leaves the previous manifest intact."""

    def __init__(self, root: Optional[str] = None,
                 fingerprint: Optional[Dict[str, str]] = None) -> None:
        self.root = root or default_dir()
        self.fp = dict(fingerprint) if fingerprint is not None \
            else runtime_fingerprint()
        self.fp_id = fingerprint_id(self.fp)
        # RLock: the manifest helpers re-enter the guard held by their
        # public callers, so lock-holding stays lexical in every frame.
        self._lock = threading.RLock()
        self._manifest: Optional[Dict[str, Any]] = None  # guarded-by: _lock
        #: load/save outcome tallies for this process (the warmup report
        #: and bench read them; /internal exposure rides sdtpu_aot_total)
        self.stats: Dict[str, int] = {"hit": 0, "miss": 0, "saved": 0,
                                      "fallback": 0}  # guarded-by: _lock

    # -- manifest ---------------------------------------------------------

    @staticmethod
    def cell_id(key_str: str, sig_str: str) -> str:
        data = json.dumps([key_str, sig_str]).encode("utf-8")
        return hashlib.sha256(data).hexdigest()[:32]

    def _load_manifest_locked(self) -> Dict[str, Any]:
        with self._lock:  # re-entrant under callers already holding it
            if self._manifest is None:
                doc: Dict[str, Any] = {"schema": SCHEMA, "cells": {}}
                try:
                    with open(os.path.join(self.root, MANIFEST_NAME),
                              encoding="utf-8") as f:
                        loaded = json.load(f)
                    if isinstance(loaded, dict) \
                            and loaded.get("schema") == SCHEMA \
                            and isinstance(loaded.get("cells"), dict):
                        doc = loaded
                except (OSError, ValueError):
                    pass  # absent or damaged manifest = empty store
                self._manifest = doc
            return self._manifest

    def _write_manifest_locked(self) -> None:
        os.makedirs(self.root, exist_ok=True)
        path = os.path.join(self.root, MANIFEST_NAME)
        tmp = path + ".tmp"
        with self._lock:  # re-entrant under callers already holding it
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(self._manifest, f, indent=1, sort_keys=True)
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
             ) -> Tuple[str, Optional[bytes]]:
        """Look one cell up. Returns ``(outcome, blob)`` where outcome is
        ``hit`` (blob is the serialization triple), ``miss`` (no such
        cell), ``fingerprint_mismatch`` (cell exists but was built on a
        different runtime/topology) or ``corrupt`` (artifact missing or
        content hash diverged — the cell is dropped so a fresh compile
        re-fills it). Never raises."""
        cid = self.cell_id(key_str, sig_str)
        with self._lock:
            doc = self._load_manifest_locked()
            cell = doc["cells"].get(cid)
            if cell is None:
                return "miss", None
            if cell.get("fingerprint_id") != self.fp_id:
                return "fingerprint_mismatch", None
            fname, want_sha = str(cell.get("file", "")), \
                str(cell.get("sha256", ""))
        blob = None
        try:
            with open(os.path.join(self.root, fname), "rb") as f:
                blob = f.read()
        except OSError:
            blob = None
        if blob is None \
                or hashlib.sha256(blob).hexdigest() != want_sha:
            with self._lock:
                doc = self._load_manifest_locked()
                doc["cells"].pop(cid, None)
                try:
                    self._write_manifest_locked()
                except OSError:
                    pass
            return "corrupt", None
        return "hit", blob

    def save(self, key_str: str, sig_str: str, kind: str,
             blob: bytes) -> bool:
        """Persist one executable's serialization triple and back-fill
        the manifest. Content-addressed: the artifact file is named by
        its sha256. Best-effort — a full disk loses the artifact, never
        the request."""
        sha = hashlib.sha256(blob).hexdigest()
        fname = sha + ARTIFACT_SUFFIX
        cid = self.cell_id(key_str, sig_str)
        try:
            os.makedirs(self.root, exist_ok=True)
            path = os.path.join(self.root, fname)
            if not os.path.exists(path):
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
            with self._lock:
                doc = self._load_manifest_locked()
                doc["cells"][cid] = {
                    "kind": str(kind),
                    "key": key_str,
                    "sig": sig_str,
                    "file": fname,
                    "bytes": len(blob),
                    "sha256": sha,
                    "fingerprint_id": self.fp_id,
                    "fingerprint": dict(self.fp),
                    "created_at": time.time(),  # sdtpu-lint: wallclock
                }
                self._write_manifest_locked()
        except OSError:
            return False
        self._count("saved")
        return True

    def verify(self) -> Dict[str, Any]:
        """Manifest/artifact divergence check (``tools/aot_report.py``):
        every cell's artifact must exist with the recorded content hash,
        and every ``*.aotx`` on disk must be claimed by some cell."""
        doc = self.manifest()
        cells = doc["cells"]
        rows, bad = [], []
        claimed = set()
        for cid, cell in sorted(cells.items()):
            fname = str(cell.get("file", ""))
            claimed.add(fname)
            status = "ok"
            try:
                with open(os.path.join(self.root, fname), "rb") as f:
                    blob = f.read()
                if hashlib.sha256(blob).hexdigest() \
                        != str(cell.get("sha256", "")):
                    status = "sha_mismatch"
            except OSError:
                status = "missing"
            if status != "ok":
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


def get_store() -> AotStore:
    """The store for the CURRENT ``SDTPU_AOT_DIR`` — re-resolved per call
    so bench phases and tests can point successive engines at fresh
    directories without process restarts."""
    root = default_dir()
    with _STORE_LOCK:
        store = _STORES.get(root)
        if store is None:
            store = AotStore(root)
            _STORES[root] = store
        return store


# -- the per-cell wrapper ----------------------------------------------------

def _serialize_compiled(compiled) -> bytes:
    from jax.experimental import serialize_executable as se

    payload_bytes, in_tree, out_tree = se.serialize(compiled)
    # the ids of the devices the program runs on ride along: loading
    # defaults to EVERY device of the backend, which an executable compiled
    # for fewer (one chip of a host, a mesh slice) then refuses to run on
    device_ids = [d.id for d in
                  compiled.runtime_executable().local_devices()]
    return pickle.dumps((payload_bytes, in_tree, out_tree, device_ids))


def _deserialize_compiled(blob: bytes):
    import jax
    from jax.experimental import serialize_executable as se

    payload_bytes, in_tree, out_tree, device_ids = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        payload_bytes, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


class AotFunction:
    """One ``Engine._cached`` cell under ``SDTPU_AOT``: a lazy dispatcher
    from concrete call signatures to loaded-or-compiled executables.

    The wrapped ``build()`` is the same zero-cost jit-factory the plain
    path caches; it is only invoked when a signature actually needs a
    fresh compile (or when the call carries tracers and must inline).
    Compiled executables take DYNAMIC arguments only — static positions
    are baked in at lower time and dropped at call time.

    Thread shape: the instance lock guards only the executable table and
    the built jit function; deserialize/compile/IO all run outside it
    (two racing threads may duplicate a compile — the dispatcher's
    execution lock makes that unreachable in serving, and it is merely
    wasteful, never wrong)."""

    def __init__(self, key: Tuple, build: Callable[[], Callable],
                 static_argnums: Tuple[int, ...] = (),
                 store: Optional[AotStore] = None) -> None:
        self.key = key
        self.kind = str(key[0])
        self.key_str = repr(key)
        self.static_argnums = tuple(int(i) for i in static_argnums)
        self._build = build
        self._explicit_store = store
        self._lock = threading.Lock()
        self._jit: Optional[Callable] = None  # guarded-by: _lock
        self._exes: Dict[str, Any] = {}  # guarded-by: _lock

    # -- plumbing ---------------------------------------------------------

    def _store(self) -> AotStore:
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
        static = set(self.static_argnums)
        return tuple(a for i, a in enumerate(args) if i not in static)

    def executable_count(self) -> int:
        with self._lock:
            return len(self._exes)

    # -- the call path ----------------------------------------------------

    def __call__(self, *args, **kwargs):
        if has_tracer(args, kwargs):
            # called from inside another trace (e.g. decode under the
            # decode-u8 jit): inline through the plain jitted function
            return self._jit_fn()(*args, **kwargs)
        sig = call_signature(args, kwargs, self.static_argnums)
        with self._lock:
            exe = self._exes.get(sig)
        if exe is None:
            exe = self._materialize(sig, args, kwargs)
            with self._lock:
                exe = self._exes.setdefault(sig, exe)
        return exe(*self._dynamic(args), **kwargs)

    def _materialize(self, sig: str, args: Tuple, kwargs: Dict):
        from stable_diffusion_webui_distributed_tpu.obs import (
            journal as obs_journal,
            perf as obs_perf,
            spans as obs_spans,
        )
        from stable_diffusion_webui_distributed_tpu.serving.metrics import (
            METRICS,
        )

        store = self._store()
        outcome, blob = store.load(self.key_str, sig)
        if blob is not None:
            t0 = time.perf_counter()
            try:
                with obs_spans.span("aot_load", kind=self.kind,
                                    key=self.key_str):
                    exe = _deserialize_compiled(blob)
            except Exception:  # noqa: BLE001 — never crash on an artifact
                outcome, exe = "corrupt", None
            if exe is not None:
                store._count("hit")
                METRICS.record_aot_load(self.kind)
                obs_perf.LEDGER.record_compile(
                    self.kind, time.perf_counter() - t0,
                    source="aot_load")
                return exe
        if outcome in ("fingerprint_mismatch", "corrupt"):
            # wrong-topology or damaged artifact: fall back to a fresh
            # compile — journaled so an operator can see hydration decay
            store._count("fallback")
            if obs_journal.enabled():
                obs_journal.emit("aot_fallback", f"aot-{self.kind}",
                                 reason=outcome, key=self.key_str,
                                 sig=sig[:128])
        else:
            store._count("miss")
        METRICS.record_compile(self.kind)
        t0 = time.perf_counter()
        with obs_spans.span("compile", kind=self.kind, key=self.key_str):
            jf = self._jit_fn()
            exe = jf.lower(*args, **kwargs).compile()
        obs_perf.LEDGER.record_compile(
            self.kind, time.perf_counter() - t0, source="fresh_compile")
        try:
            store.save(self.key_str, sig, self.kind,
                       _serialize_compiled(exe))
        except Exception:  # noqa: BLE001 — persistence is best-effort
            pass
        return exe

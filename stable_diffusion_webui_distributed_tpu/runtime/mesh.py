"""Device mesh construction: the TPU substrate for the batch split.

Where the reference's "world" is a pool of HTTP hosts (one GPU each,
/root/reference/scripts/spartan/world.py:75-145), this framework's first
tier of parallelism is a ``jax.sharding.Mesh`` over local chips: the batch
axis is sharded over ``dp`` (XLA emits ICI collectives; no request fan-out,
no HTTP). The World scheduler (scheduler/) then balances *across* meshes —
slices/hosts — the way the reference balances across HTTP workers.

Axis names: ``dp`` (batch data-parallel), ``tp`` (tensor parallel within the
UNet/VAE), reserved ``sp`` (latent-token sequence parallel for very high
resolutions). ``--mesh "dp=4,tp=2"`` flag parsing lives here (the flag is
registered at runtime/flags.py:33-38).
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

import numpy as np

AXIS_ORDER = ("dp", "tp", "sp")


#: where ``JAX_COMPILATION_CACHE_DIR`` places nothing: one fixed directory
#: inside the checkout (git-ignored). The path is part of XLA's cache key,
#: so it never carries a home directory, temp name, pid or time.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Persist XLA executables across process restarts (first SDXL compile
    costs ~minutes on TPU; a restarted node re-serves in seconds). The
    reference's workers pay webui's model-load on every restart with no
    equivalent escape hatch.

    Placement comes from outside: when ``JAX_COMPILATION_CACHE_DIR`` is set
    JAX already reads it and no directory is set in code; otherwise
    :data:`DEFAULT_COMPILE_CACHE` is used. Returns the active directory so
    callers (serving/warmup.py, chip_smoke.py) can report where executables
    land. A directory that cannot be created raises: a cache that silently
    stays off turns every restart back into a full compile."""
    import jax

    from stable_diffusion_webui_distributed_tpu.runtime.config import env_str
    from stable_diffusion_webui_distributed_tpu.serving.metrics import (
        install_xla_listener,
    )

    # whoever places the cache is about to compile: count from here on,
    # hits and misses included (/internal/status serving.xla)
    install_xla_listener()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    placed = env_str("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    os.makedirs(DEFAULT_COMPILE_CACHE, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE


def init_multihost(coordinator: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None) -> bool:
    """Join a multi-host JAX runtime over DCN (``jax.distributed``).

    Within a host, parallelism is the mesh's problem (ICI collectives);
    across hosts this makes every chip of every host visible to one global
    mesh — the DCN tier the reference approximates with its HTTP worker
    pool (SURVEY.md §2 distributed backend). No-ops (returning False) when
    no coordinator is configured, so single-host flows never pay it.
    Environment fallbacks: SDTPU_COORDINATOR, SDTPU_NUM_PROCESSES,
    SDTPU_PROCESS_ID (or the cloud auto-detection jax.distributed ships).
    """
    import jax

    from stable_diffusion_webui_distributed_tpu.runtime.config import (
        env_int, env_str,
    )

    coordinator = coordinator or env_str("SDTPU_COORDINATOR") or None
    if not coordinator:
        return False
    kwargs = {"coordinator_address": coordinator}
    num_processes = num_processes if num_processes is not None else \
        env_int("SDTPU_NUM_PROCESSES")
    process_id = process_id if process_id is not None else \
        env_int("SDTPU_PROCESS_ID")
    if num_processes is not None:
        kwargs["num_processes"] = int(num_processes)
    if process_id is not None:
        kwargs["process_id"] = int(process_id)
    jax.distributed.initialize(**kwargs)
    return True


def parse_mesh_spec(spec: Optional[str]) -> Dict[str, int]:
    """'dp=4,tp=2' -> {'dp': 4, 'tp': 2}. Empty/None -> {} (all devices on dp)."""
    if not spec:
        return {}
    out: Dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"bad mesh axis '{part}' (want name=size)")
        name, _, size = part.partition("=")
        name = name.strip()
        if name not in AXIS_ORDER:
            raise ValueError(f"unknown mesh axis '{name}' (known: {AXIS_ORDER})")
        out[name] = int(size)
        if out[name] <= 0:
            raise ValueError(f"mesh axis {name} must be positive")
    return out


def build_mesh(spec: Optional[str] = None, devices: Optional[Sequence] = None):
    """Construct a Mesh from a spec string over the given (or all) devices.

    Unspecified axes get size 1; if no axes are given, every device lands on
    ``dp`` — the TPU analogue of the reference's default equal batch split
    (world.py:111-115).
    """
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    axes = parse_mesh_spec(spec)
    if not axes:
        axes = {"dp": len(devices)}
    sizes = [axes.get(a, 1) for a in AXIS_ORDER]
    total = int(np.prod(sizes))
    if total != len(devices):
        # Allow a spec that uses a subset (e.g. dp=4 of 8 devices).
        if total < len(devices) and len(devices) % total == 0:
            devices = devices[:total]
        else:
            raise ValueError(
                f"mesh spec {axes} needs {total} devices, have {len(devices)}"
            )
    arr = np.array(devices).reshape(sizes)
    return Mesh(arr, AXIS_ORDER)


def batch_sharding(mesh):
    """NamedSharding that splits axis 0 (the image batch) over ``dp``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P("dp"))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def pad_batch(n: int, mesh) -> int:
    """Images to generate so the batch divides the dp axis: pad-and-drop,
    the TPU replacement for the reference's remainder round-robin
    (world.py:482-510)."""
    dp = mesh.shape["dp"]
    return ((n + dp - 1) // dp) * dp

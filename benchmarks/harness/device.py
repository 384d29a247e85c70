"""The device, as JAX reports it. A run that finds no TPU fails: there is
no CPU fallback. Tests switch ``ACCEPTED_PLATFORMS``; run.py has no option
for it."""

from __future__ import annotations

ACCEPTED_PLATFORMS = ("tpu",)


class NoDevice(RuntimeError):
    pass


def record() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require(chips: int) -> dict:
    rec = record()
    if rec["platform"] not in ACCEPTED_PLATFORMS:
        raise NoDevice(f"needs a TPU, JAX found {rec['platform']!r}")
    if rec["count"] < chips:
        raise NoDevice(f"cell needs {chips} chip(s), JAX sees {rec['count']}")
    return rec


def memory(chips: int) -> list[dict]:
    """memory_stats() of the first ``chips`` devices ({} where the backend
    reports none, as the CPU does)."""
    import jax

    return [(d.memory_stats() or {}) for d in jax.devices()[:chips]]

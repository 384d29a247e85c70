"""Tracing / profiling: jax.profiler capture + per-stage wall-clock stats.

The reference has no tracer — its only timing is ad-hoc wall clock feeding
the benchmark/ETA loop (SURVEY.md §5: response_time at worker.py:477-481 is
the de-facto profiler). Here that idea is kept (stage timings feed the
status surface) and real tracing is added: ``capture()`` wraps
``jax.profiler`` so a TensorBoard-loadable trace of the XLA execution can be
taken around any request.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict, deque
from typing import Deque, Dict, Iterator, Optional


class StageStats:
    """Thread-safe rolling wall-clock stats per pipeline stage."""

    def __init__(self, window: int = 64):
        self._window = window
        self._samples: Dict[str, Deque[float]] = defaultdict(
            lambda: deque(maxlen=window))
        self._lock = threading.Lock()

    def record(self, stage: str, seconds: float) -> None:
        with self._lock:
            self._samples[stage].append(seconds)

    @contextlib.contextmanager
    def timer(self, stage: str) -> Iterator[None]:
        """Time the block as ``stage``: a rolling sample here, the stage's
        latency histogram, and a span of the active request (obs/spans.py;
        while a profiler capture runs also an ``sdtpu:<stage>``
        annotation), so spans opened inside are its children."""
        # lazy: trace is imported everywhere, obs pulls serving/metrics
        from stable_diffusion_webui_distributed_tpu.obs import (
            prometheus as obs_prom,
            spans as obs_spans,
        )

        t0 = time.perf_counter()
        try:
            with obs_spans.span(stage):
                yield
        finally:
            dur = time.perf_counter() - t0
            self.record(stage, dur)
            obs_prom.observe_stage(stage, dur)

    def summary(self) -> Dict[str, Dict[str, float]]:
        """{stage: {count, mean, p50, last}} over the rolling window."""
        with self._lock:
            out = {}
            for stage, samples in self._samples.items():
                if not samples:
                    continue
                ordered = sorted(samples)
                out[stage] = {
                    "count": len(samples),
                    "mean": sum(samples) / len(samples),
                    "p50": ordered[len(ordered) // 2],
                    "last": samples[-1],
                }
            return out

    def clear(self) -> None:
        with self._lock:
            self._samples.clear()


#: Process-wide stats the engine and server share.
STATS = StageStats()


_trace_lock = threading.Lock()
_trace_dir: Optional[str] = None


def start_trace(log_dir: str) -> bool:
    """Begin a jax.profiler capture (TensorBoard format). Returns False if a
    capture is already running."""
    global _trace_dir
    import jax

    with _trace_lock:
        if _trace_dir is not None:
            return False
        jax.profiler.start_trace(log_dir)
        _trace_dir = log_dir
        return True


def stop_trace() -> Optional[str]:
    """End the running capture; returns its directory (None if none ran)."""
    global _trace_dir
    import jax

    with _trace_lock:
        if _trace_dir is None:
            return None
        jax.profiler.stop_trace()
        out, _trace_dir = _trace_dir, None
        return out


@contextlib.contextmanager
def capture(log_dir: str) -> Iterator[None]:
    """Trace the wrapped block. If another capture is already running, this
    becomes a no-op rather than hijacking (and stopping) it."""
    started = start_trace(log_dir)
    try:
        yield
    finally:
        if started:
            stop_trace()

"""Tracing / profiling hooks (SURVEY.md section 5.1 rebuild).

The reference has no profiler at all — its only timing is wall-clock
minutes stored in checkpoints (reference worker.py:378,452) and derived
rates printed every 10 s (worker.py:126,135). Here:

- `start_profiler_server(port)` exposes the live process to
  `xprof`/TensorBoard-profile capture at any time (device + host traces).
- `trace_to(dir)` context manager records a bounded trace programmatically
  (e.g. `--profile-dir` on the trainer CLI traces the first post-warmup
  updates, where the steady-state pipeline shape is visible).
- `span(name)` / `step_span(name, step)` annotate HOST-side phases (replay
  sample, block pack, priority update) so they line up against device
  activity in the trace viewer. They are no-ops costing one context-manager
  enter/exit when no trace is being captured, so the hot paths keep them
  permanently.
- `TransferTimer` is the tiered replay plane's staging accountant: it
  measures how much of the host->HBM copy time is hidden behind update
  compute (the plane's whole reason to exist), without needing a trace
  capture.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Iterator, Optional

import jax

_server = None


class TransferTimer:
    """Host->device staging overlap accountant (tiered replay plane).

    Two accumulators, fed from different threads:
    - `h2d(nbytes)` spans wrap the STAGING side of a chunk — host window
      gather + device_put + transfer completion — measured on the staging
      thread, off the critical path.
    - `wait()` spans wrap the CONSUMER side — the time the update loop
      actually stalled waiting for a staged chunk to be ready.

    overlap_fraction = 1 - wait/h2d, clamped to [0, 1]: 1.0 means every
    byte of copy time was hidden behind compute (the consumer never
    waited), 0.0 means staging was fully serialized ahead of the updates
    (the inline host plane's behavior). Thread-safe; `reset()` rebases the
    window so a bench can exclude compile/warmup chunks."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.h2d_seconds = 0.0
            self.wait_seconds = 0.0
            self.bytes_staged = 0
            self.chunks = 0

    @contextlib.contextmanager
    def h2d(self, nbytes: int = 0) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self.h2d_seconds += dt
                self.bytes_staged += nbytes
                self.chunks += 1

    @contextlib.contextmanager
    def wait(self) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self.wait_seconds += dt

    def overlap_fraction(self) -> float:
        with self._lock:
            if self.h2d_seconds <= 0.0:
                return 1.0
            return max(0.0, min(1.0, 1.0 - self.wait_seconds / self.h2d_seconds))

    def stats(self) -> dict:
        """One flat dict for metrics/bench JSON."""
        with self._lock:
            h2d, wait = self.h2d_seconds, self.wait_seconds
            chunks, staged = self.chunks, self.bytes_staged
        frac = 1.0 if h2d <= 0.0 else max(0.0, min(1.0, 1.0 - wait / h2d))
        return {
            "h2d_overlap_fraction": round(frac, 4),
            "h2d_seconds": round(h2d, 4),
            "h2d_wait_seconds": round(wait, 4),
            "h2d_chunks": chunks,
            "h2d_gbytes_staged": round(staged / 1e9, 3),
        }


def start_profiler_server(port: int = 9012) -> None:
    """Idempotent: starts the jax.profiler server once per process."""
    global _server
    if _server is None:
        _server = jax.profiler.start_server(port)


@contextlib.contextmanager
def trace_to(log_dir: Optional[str]) -> Iterator[None]:
    """Record a profiler trace into `log_dir` for the duration of the
    context; None disables (zero overhead)."""
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """Named host-span annotation visible in the trace viewer."""
    return jax.profiler.TraceAnnotation(name)


def step_span(name: str, step: int):
    """Step-correlated span: groups device work under learner step N."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step)

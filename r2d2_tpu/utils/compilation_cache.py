"""Persistent XLA compilation cache: where it lives, and whether it served.

The flagship program set (fused megastep + eval collector + acting
forward, each serve bucket) compiles for tens of seconds cold, and every
fresh process (each curriculum stage, each bench run, each eval pass, a
resumed trainer, a restarted server) would repay it. jax's persistent
compilation cache makes a multi-process run pay once per distinct
program, not once per process.

ONE directory rule, here and nowhere else (`cache_dir_to_set`):

  1. `JAX_COMPILATION_CACHE_DIR` set: jax already has its directory and
     this program sets none. That variable is how a cache is placed from
     outside (a driver that keeps one across runs, a CPU run that wants
     one at all).
  2. unset, TPU backend: `<checkout>/.jax_cache` (git-ignored). A fixed
     path — the directory is part of the cache key, so a temporary name,
     a pid or a time in it would never hit.
  3. unset, any other backend: no cache. XLA:CPU AOT cache loads warn
     about machine-feature mismatches ("could lead to SIGILL") and CPU
     compiles are cheap; tier-1 runs this way.

To measure a true cold compile use jax's own switch,
`JAX_ENABLE_COMPILATION_CACHE=0`.

Hit/miss accounting: enable_compilation_cache registers a
jax.monitoring listener counting the persistent-cache events jax's
compiler emits; log_compile_cache_stats() prints one
`[compile-cache] dir=... source=... hits=H misses=M` line (the CLIs call
it after warmup/run so a driver log shows whether the cache served, and
who chose the directory). The same listener sums jax's own duration events
(trace, lowering, backend compile or cache load; they carry `fun_name`) per
function: `compile_seconds()` is their total, and the report's second line
names the ten costliest."""

from __future__ import annotations

import os
import time
from typing import Dict, List, Mapping, Optional

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# persistent-cache event counters (jax._src.compiler emits these names)
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_REQ_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_counts = {_HIT_EVENT: 0, _REQ_EVENT: 0}
_listener_installed = False

# duration events (jax._src.dispatch / pxla / compiler emit these names)
_TRACE, _LOWER, _COMPILE = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_seconds: Dict[str, List[float]] = {}  # function -> [trace, lower, compile] s
_total = [0.0]
_open_traces: List[tuple] = []  # (start, duration) of traces not yet claimed by a parent


def _count_event(event: str, **kwargs) -> None:
    if event in _counts:
        _counts[event] += 1


def _sum_duration(event: str, duration: float, fun_name: str = "?", **kwargs) -> None:
    if event not in (_TRACE, _LOWER, _COMPILE):
        return
    name = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
    row = _seconds.setdefault(name, [0.0, 0.0, 0.0])
    row[(_TRACE, _LOWER, _COMPILE).index(event)] += duration
    if event == _TRACE:
        # an inner jit is traced inside its caller's trace and reports first:
        # the total takes each second once (a parent subtracts its children)
        start, whole = time.perf_counter() - duration, duration
        while _open_traces and _open_traces[-1][0] >= start:
            duration -= _open_traces.pop()[1]
        _open_traces.append((start, whole))
    _total[0] += max(duration, 0.0)


def _install_listener() -> None:
    global _listener_installed
    if _listener_installed:
        return
    import jax

    jax.monitoring.register_event_listener(_count_event)
    jax.monitoring.register_event_duration_secs_listener(_sum_duration)
    _listener_installed = True


def cache_dir_to_set(environ: Mapping[str, str], backend: str) -> Optional[str]:
    """The directory rule as a pure function: the path this program hands
    to jax, or None when it sets none (the module docstring's three
    cases)."""
    if environ.get(_ENV_VAR):
        return None
    return _DEFAULT_DIR if backend == "tpu" else None


def compile_cache_stats() -> dict:
    """(hits, misses) observed by this process so far. A `miss` is a
    compile request that consulted the cache and fell through to XLA —
    cold programs that get WRITTEN for the next process to hit."""
    hits = _counts[_HIT_EVENT]
    return {"hits": hits, "misses": max(_counts[_REQ_EVENT] - hits, 0)}


def compile_seconds() -> float:
    """Seconds this process has spent tracing, lowering and compiling (or
    loading from the persistent cache) so far, by jax's own duration events."""
    return _total[0]


def costliest_compiles(n: int = 10) -> List[tuple]:
    """[(function, trace s, lower s, compile s), ...] by their sum; a
    function's trace seconds include the inner jits traced inside it."""
    rows = sorted(_seconds.items(), key=lambda kv: -sum(kv[1]))[:n]
    return [(name, *secs) for name, secs in rows]


def log_compile_cache_stats(prefix: str = "compile-cache") -> str:
    """Print the cache report the CLIs emit (and the ten costliest compiles
    beneath it); returns its first line."""
    import jax

    d = jax.config.jax_compilation_cache_dir
    source = "off" if not d else "env" if os.environ.get(_ENV_VAR) else "checkout"
    s = compile_cache_stats()
    line = (
        f"[{prefix}] dir={d or '<disabled>'} source={source} "
        f"hits={s['hits']} misses={s['misses']}"
    )
    print(line, flush=True)
    rows = costliest_compiles()
    if rows:
        print(f"[{prefix}] {compile_seconds():.1f}s in trace+lower+compile; costliest: "
              + ", ".join(f"{n} {t + l + c:.2f}s ({t:.2f}+{l:.2f}+{c:.2f})" for n, t, l, c in rows),
              flush=True)
    return line


def enable_compilation_cache() -> bool:
    """Idempotently apply the directory rule and install the hit/miss
    listener. Returns True when a persistent cache is in effect."""
    import jax

    _install_listener()
    cache_dir = cache_dir_to_set(os.environ, jax.default_backend())
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if not jax.config.jax_compilation_cache_dir:
        return False
    # the default 1 s floor would skip many of the small eval/acting
    # programs whose compiles still dominate short runs in aggregate —
    # whoever chose the directory
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return True

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
who chose the directory)."""

from __future__ import annotations

import os
from typing import Mapping, Optional

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


def _count_event(event: str, **kwargs) -> None:
    if event in _counts:
        _counts[event] += 1


def _install_listener() -> None:
    global _listener_installed
    if _listener_installed:
        return
    import jax

    jax.monitoring.register_event_listener(_count_event)
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


def log_compile_cache_stats(prefix: str = "compile-cache") -> str:
    """Print and return the one-line cache report the CLIs emit."""
    import jax

    d = jax.config.jax_compilation_cache_dir
    source = "off" if not d else "env" if os.environ.get(_ENV_VAR) else "checkout"
    s = compile_cache_stats()
    line = (
        f"[{prefix}] dir={d or '<disabled>'} source={source} "
        f"hits={s['hits']} misses={s['misses']}"
    )
    print(line, flush=True)
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

"""Persistent XLA compilation-cache wiring.

Short grid runs are dominated by jit warm-up: every shape signature in a
Section 7 sweep costs a fresh XLA compile even though the programs are
byte-identical across invocations.  jax ships a persistent compilation
cache (``jax.experimental.compilation_cache``) that serializes compiled
executables to disk; ``enable`` points it at one directory and drops the
min-compile-time floor to zero, because the grid's per-cell programs are
exactly the small ones the default 1s floor would skip.  Re-runs then skip
XLA entirely for every program already seen.

Placement follows one rule.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
the cache lives in exactly that directory and nowhere else.  Otherwise it
lives in ``.jax_cache/`` at the root of the checkout (gitignored), or in
the directory a caller names explicitly.  jax's own cache key already
holds the jax version, backend and device, so one directory serves every
backend.  ``enable`` also keys it on the program's op names: jax strips
them by default, and a program that differs from a cached one only in
its ``named_scope``s would be served the older executable, whose profile
names the older scopes.  Locations then carry op names only, no source
lines, so the key does not move with an edit elsewhere in a file or with
the call site.

``counters()`` exposes the process-wide hit/miss counts via jax's
monitoring events — surfaced as the ``derived`` column of the bench's
``compile_time_s/*`` rows (``benchmarks/kernels_bench.py``) and printed
by ``chip_smoke.py``, so a record shows whether a warm-up was served from
disk.

CLI entry points: ``--compile-cache DIR|auto`` on
``repro.launch.experiments`` and ``repro.launch.train``.
"""
from __future__ import annotations

import os

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_REQ_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), *([os.pardir] * 3),
    ".jax_cache")

_COUNTS = {"hits": 0, "requests": 0}
_LISTENING = False
_DIR: str | None = None


def default_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` at the
    root of the checkout."""
    return os.path.abspath(os.environ.get(ENV_VAR) or CHECKOUT_CACHE)


def _on_event(event, **kwargs):
    if event == _HIT_EVENT:
        _COUNTS["hits"] += 1
    elif event == _REQ_EVENT:
        _COUNTS["requests"] += 1


def _listen():
    global _LISTENING
    if _LISTENING:
        return
    try:
        from jax._src import monitoring
        monitoring.register_event_listener(_on_event)
    except Exception:        # pragma: no cover - jax internals moved
        return               # cache still works, counters just stay 0
    _LISTENING = True


def enable(cache_dir: str = "") -> str:
    """Turn on jax's persistent compilation cache and register the
    hit/miss listeners.  The directory is ``$JAX_COMPILATION_CACHE_DIR``
    whenever that is set (an explicit ``cache_dir`` does not override
    it); else ``cache_dir``, where ``''``/``'auto'`` mean the checkout's
    ``.jax_cache/``.  Created if missing.  Idempotent — repeated calls
    just re-point the directory.  Returns the resolved absolute path."""
    global _DIR
    import jax

    if os.environ.get(ENV_VAR) or cache_dir in ("", "auto"):
        path = default_cache_dir()
    else:
        path = os.path.abspath(os.path.expanduser(cache_dir))
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the default 1s floor skips exactly the small
    # per-cell programs the grid compiles most of
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # a served executable carries the scopes of the program that asked
    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # jax probes the cache config ONCE, at the first compile, and latches
    # cache-off for the whole process if no directory was set yet —
    # reset_cache clears that latch (NOT any compiled executable), so
    # enabling after warm-up compiles still takes effect
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
    _listen()
    _DIR = path
    return path


def cache_dir():
    """The directory ``enable`` resolved to, or None before ``enable``."""
    return _DIR


def counters() -> dict:
    """Process-wide persistent-cache counters since import: ``hits``
    (executables deserialized from disk) and ``misses`` (lookups that
    fell through to a fresh XLA compile — jax emits no miss event, so
    this is requests minus hits).  Only meaningful after ``enable``."""
    return dict(hits=_COUNTS["hits"],
                misses=max(0, _COUNTS["requests"] - _COUNTS["hits"]))

"""The `device` layer's compile accounting: the persistent XLA
compilation cache (the one place its directory is chosen) and the
process's `jax.monitoring` listeners (the one place they are registered).

A fresh process pays every sweep/scoring program's XLA compile; JAX's
persistent compilation cache makes every run after the first start warm.
That holds for the sweep too: its programs take the dataset as jit
arguments (`parallel/sweep.py`), so a module's hash depends on shapes,
dtypes and static hyper-parameters only — a new table of the same shape
is the same entry, and no entry carries a dataset's bytes.
The cache directory is part of each entry's key, so it must not move:

- `JAX_COMPILATION_CACHE_DIR` set: JAX already has its directory. This
  module writes NO `jax_compilation_cache_dir` (logged once) — whoever
  launched the process placed the cache.
- unset: `<store root>/xla-cache`, resolved like every other kind of
  shared state (store/config.py): fixed inside the checkout by default
  (never `$HOME`, a temp name, a pid or a timestamp), and following
  `TRANSMOGRIFAI_STORE_DIR` so replicas on one shared store replay each
  other's compiles.

The directory is chosen on the FIRST call in a process and later calls
return it: JAX opens its cache once per process and reads the directory
into every key, so re-pointing it mid-process would write entries no
later process can find.

Called by bench.py, __graft_entry__, the WorkflowRunner/CLI, the serving
layer and the test suite's conftest.

Every XLA compile is also a span where it happened
(`register_compile_listeners`): JAX reports a backend compile's duration
on the thread that asked for it, so the listener backdates a
`compile:<current span>/<fun_name>` span under that thread's current
`obs.trace` span — a family's sweep dispatch, the winner's refit, a
feature stage — and `COMPILE_STATS` keeps the process totals. JAX times
the backend compile AROUND its look into the persistent cache, so on a
warm cache a `compile:*` span is a cache load, not a compile: the span
says which in its `cache_hit` attribute.
"""

from __future__ import annotations

import logging
import os
import threading

from transmogrifai_tpu.obs.trace import TRACER, now_s
from transmogrifai_tpu.store.config import resolve_dir

log = logging.getLogger(__name__)

ENV_JAX_CACHE = "JAX_COMPILATION_CACHE_DIR"

_dir: str | None = None  # this process's cache directory, once chosen

# Process totals from JAX's own monitoring events: compile requests that
# consulted the persistent cache, how many it answered, and the seconds
# the backend spent on the rest (summed over the compiling threads).
# Readers diff two copies around the interval they care about.
COMPILE_STATS = {"requests": 0, "cache_hits": 0, "backend_compile_s": 0.0}
_stats_lock = threading.Lock()
_listening = False
# whether the persistent cache answered this thread's compile request:
# the hit event fires on the compiling thread, inside the interval JAX
# reports as the backend compile's duration
_request = threading.local()


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _request.hit = False
        with _stats_lock:
            COMPILE_STATS["requests"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        _request.hit = True
        with _stats_lock:
            COMPILE_STATS["cache_hits"] += 1


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event != "/jax/core/compile/backend_compile_duration":
        return
    with _stats_lock:
        COMPILE_STATS["backend_compile_s"] += duration_secs
    # called on the compiling thread as the compile returns: the span
    # ends now and started `duration_secs` ago, under whatever program
    # span that thread has open. The owner is in the NAME because the
    # benchmark's readers see (name, duration) pairs only.
    owner = TRACER.current()
    end = now_s()
    hit, _request.hit = getattr(_request, "hit", False), False
    TRACER.span_at(
        f"compile:{owner.name if owner is not None else '-'}"
        f"/{kw.get('fun_name', '?')}",
        end - duration_secs, end, parent=owner, category="compile",
        cache_hit=hit)


def register_compile_listeners() -> None:
    """Register this module's `jax.monitoring` listeners, once per
    process (JAX keeps every registration for the process's life)."""
    global _listening
    with _stats_lock:
        if _listening:
            return
        _listening = True
    from jax import monitoring
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def enable_compile_cache(min_compile_s: float = 0.5) -> str | None:
    """Turn the persistent cache on and return its directory (None when
    the store directory cannot be created — an unwritable checkout
    must never break startup).

    `min_compile_s` is the persistence threshold: the 0.5s default skips
    throwaway programs during training, while the serving layer passes
    0.0 — a bucket ladder is MANY small programs, and a replica's
    cold-start-to-first-score is their compile-time SUM, so each one is
    worth persisting even where a single compile is cheap. The threshold
    is process-global; the last caller wins."""
    global _dir
    import jax

    register_compile_listeners()
    if _dir is None:
        path = os.environ.get(ENV_JAX_CACHE)
        if path:
            log.info("%s=%s places the XLA compile cache; no in-code "
                     "directory is applied", ENV_JAX_CACHE, path)
        else:
            path = resolve_dir("xla-cache")
            try:
                os.makedirs(path, exist_ok=True)
            except OSError:
                return None
            jax.config.update("jax_compilation_cache_dir", path)
        _dir = path
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_s))
    return _dir


def compile_cache_entries() -> int:
    """Entries in this process's cache directory right now (0 while the
    directory does not exist yet). The directory is fixed, so a boot is
    XLA-cold only if this read 0 before it — any earlier run in the same
    place has already warmed the cache."""
    path = _dir or enable_compile_cache()
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0

"""One resolution point for every shared on-disk location.

`TRANSMOGRIFAI_STORE_DIR` moves the WHOLE root (feature cache, perf
corpus, warm-up manifests, XLA compile cache all follow), while each
subsystem's own env var still wins for its subtree (for the compile
cache that is the standard `JAX_COMPILATION_CACHE_DIR`,
utils/compile_cache.py). Unset, the root is `DEFAULT_ROOT`: a fixed,
git-ignored directory INSIDE the checkout, so what a run keeps (the
compile cache's keys hold the path) never passes between two checkouts
on one machine through `$HOME`.
"""

from __future__ import annotations

import os

__all__ = [
    "DEFAULT_ROOT",
    "ENV_STORE",
    "cache_root",
    "resolve_dir",
    "store_configured",
]

ENV_STORE = "TRANSMOGRIFAI_STORE_DIR"

# subsystem env overrides, kept here so callers and docs agree on the
# precedence order: explicit arg > subsystem env > store root env >
# DEFAULT_ROOT
ENV_FEATURE_CACHE = "TRANSMOGRIFAI_FEATURE_CACHE_DIR"
ENV_PERF_CORPUS = "TRANSMOGRIFAI_PERF_CORPUS_DIR"

# <checkout>/.transmogrifai_store (listed in .gitignore/.chiprunignore)
DEFAULT_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".transmogrifai_store")


def store_configured() -> bool:
    """True when a shared store root was explicitly pointed somewhere —
    the signal consumers use to ALSO publish replica-portable artifacts
    (warmup manifests, corpus shards) instead of only local sidecars."""
    return bool(os.environ.get(ENV_STORE))


def cache_root() -> str:
    return os.environ.get(ENV_STORE) or DEFAULT_ROOT


def resolve_dir(kind: str, env: str | None = None,
                explicit: str | None = None) -> str:
    """Resolve the directory for one artifact kind.

    Precedence: explicit caller arg, then the subsystem's own env var,
    then `<store root>/<kind>` (where the store root itself honors
    `TRANSMOGRIFAI_STORE_DIR` before falling back to `DEFAULT_ROOT`).
    """
    if explicit:
        return explicit
    if env:
        val = os.environ.get(env)
        if val:
            return val
    return os.path.join(cache_root(), kind)

"""PerfModelParams: configuration for the learned cost model.

Lives in `perf/` (not `workflow/params.py`) for the same reason
`FeatureCacheParams` lives in `data/feature_cache.py`: the subsystem
owns its config shape, and `workflow/params.py` imports it for the
JSON-loadable `OpParams.perf_model` block. No heavy imports here —
`workflow.params` must stay importable without touching jax.

Process-default installation mirrors the feature cache: `set_params`
replaces the process default, `params_scope` installs one for a `with`
extent (used by `Workflow.train`), and every perf consumer resolves the
active params through `get_params()` at decision time. Environment
knobs override nothing structurally — they fill the DEFAULTS, so a
params file or CLI flag always wins:

- ``TRANSMOGRIFAI_PERF_MODEL=0``       kill switch (all consumers cold)
- ``TRANSMOGRIFAI_PERF_CORPUS_DIR``    corpus directory
- ``TRANSMOGRIFAI_PERF_TARGET_BLOCK_S``scheduler seconds-per-block target
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Dict, Optional

__all__ = ["PerfModelParams", "get_params", "set_params", "params_scope",
           "enabled", "resolved_corpus_dir", "target_block_s"]

_DEFAULT_TARGET_BLOCK_S = 30.0


@dataclass
class PerfModelParams:
    """JSON-loadable cost-model config (`OpParams.perf_model`).

    `model_path` points at a fitted model JSON (`CostModel.save`) so a
    saved workflow ships with the predictor that tuned it; when unset,
    the model is fitted lazily from the corpus. `min_rows` is the
    cold-start floor: a target with fewer training rows predicts None
    and every consumer falls back to today's heuristics exactly."""

    enabled: bool = True
    corpus_dir: Optional[str] = None      # default: env / <store root>/perf
    model_path: Optional[str] = None      # fitted model JSON to load
    target_block_s: Optional[float] = None  # scheduler width sizing
    min_rows: int = 8                     # per-target cold-start floor

    _FIELDS = ("enabled", "corpus_dir", "model_path", "target_block_s",
               "min_rows")

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "PerfModelParams":
        return PerfModelParams(**{k: d[k] for k in PerfModelParams._FIELDS
                                  if k in d})

    def to_json(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self._FIELDS}


_LOCK = threading.Lock()
_PARAMS = PerfModelParams()


def get_params() -> PerfModelParams:
    return _PARAMS


def set_params(params: Optional[PerfModelParams]) -> None:
    """Replace the process-default perf params (None → factory
    defaults)."""
    global _PARAMS
    with _LOCK:
        _PARAMS = params if params is not None else PerfModelParams()


@contextmanager
def params_scope(params):
    """Install `params` (a PerfModelParams, a JSON dict, or None) as the
    process default for the scope's extent. None is a no-op — the
    ambient params stay active, so a train without a perf_model block
    inherits the process/env configuration. Restore only when our
    install is still the active one (overlapping scopes must not wipe a
    live policy — same contract as feature_cache.cache_scope)."""
    if params is None:
        yield
        return
    if isinstance(params, dict):
        params = PerfModelParams.from_json(params)
    global _PARAMS
    with _LOCK:
        prev = _PARAMS
        _PARAMS = params
    try:
        yield
    finally:
        with _LOCK:
            if _PARAMS is params:
                _PARAMS = prev


def enabled() -> bool:
    """The master switch: env kill switch beats everything, then the
    active params."""
    if os.environ.get("TRANSMOGRIFAI_PERF_MODEL", "1") == "0":
        return False
    return bool(_PARAMS.enabled)


def resolved_corpus_dir() -> str:
    # one resolution point with the artifact store: params arg wins,
    # then the subsystem env, then <store root>/perf — so pointing
    # TRANSMOGRIFAI_STORE_DIR at shared storage moves the corpus too
    from transmogrifai_tpu.store.config import resolve_dir
    return resolve_dir("perf", env="TRANSMOGRIFAI_PERF_CORPUS_DIR",
                       explicit=_PARAMS.corpus_dir)


def target_block_s() -> float:
    if _PARAMS.target_block_s is not None:
        return float(_PARAMS.target_block_s)
    try:
        return float(os.environ.get("TRANSMOGRIFAI_PERF_TARGET_BLOCK_S",
                                    _DEFAULT_TARGET_BLOCK_S))
    except ValueError:
        return _DEFAULT_TARGET_BLOCK_S


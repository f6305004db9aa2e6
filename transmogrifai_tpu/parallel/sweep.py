"""The sweep engine: folds × models × grids as batched XLA programs.

Reference parity: `OpValidator.getSummary` / `OpCrossValidation.validate`
(`core/.../tuning/OpValidator.scala:299-358`, `OpCrossValidation.scala:87-147`)
— the reference dispatches each model×grid×fold fit as a Future running
Spark jobs; here EVERY model family (logistic, linear, GLM, SVC, NB, MLP,
random forest / decision tree, GBT / XGBoost) compiles its whole grid×fold
block into ONE XLA program: fit → predict → masked device metric
(`evaluators/device_metrics.py`), no host round-trips inside the sweep.

Every array that depends on the dataset — `X`, `y`, the binned `Xb`, the
one-hot `Y`, the fold masks `W`, `V` — reaches a sweep program as a jit
ARGUMENT, never as a closure constant: a program is built from a hashable
key alone (`_block_program`, `_gbt_rounds_program`, `_gbt_score_program`)
and held, so a second sweep on a same-shaped table compiles nothing, and a
new process finds the identical modules in the persistent cache.

Static-shape strategy per family:
- linear-like: grids share one compile per distinct `max_iter`; the
  regularization axis is a traced vector, vmapped.
- trees: `max_depth` grids are PADDED to the group's largest depth and
  grown with a traced `active_depth` (models/trees.py), so a
  {3, 6, 12} depth grid is one compile; `min_child_weight`,
  `learning_rate`, `reg_lambda` are traced vectors.
- grid axis execution: `vmap` (parallel) when the sweep axis is sharded
  over a mesh or the family is cheap; `lax.scan`-based `lax.map`
  (sequential, single compile) for deep trees on a single device to bound
  the histogram working set.

Fault tolerance mirrors `OpValidator.scala:324-353`: a failing model family
is dropped with a warning; only all-families-failing raises.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu import types as T
from transmogrifai_tpu.obs import export as obs_export
from transmogrifai_tpu.obs.trace import TRACER, pull, upload
from transmogrifai_tpu.data.columns import Column
from transmogrifai_tpu.evaluators.device_metrics import (
    device_metric, make_device_metric)
from transmogrifai_tpu.models.base import n_classes_of, stated_n_classes
from transmogrifai_tpu.models.glm import (
    OpGeneralizedLinearRegression, fit_glm, predict_glm)
from transmogrifai_tpu.models.linear import (
    OpLinearRegression, fit_linreg, fit_linreg_enet, predict_linreg)
from transmogrifai_tpu.models.linear_svc import (
    OpLinearSVC, fit_linear_svc, predict_linear_svc)
from transmogrifai_tpu.models.logistic import (
    OpLogisticRegression, enet_iters, fit_logreg, fit_logreg_enet,
    predict_logreg)
from transmogrifai_tpu.models.mlp import (
    OpMultilayerPerceptronClassifier, fit_mlp, predict_mlp)
from transmogrifai_tpu.models.naive_bayes import (
    OpNaiveBayes, fit_naive_bayes, predict_naive_bayes)
from transmogrifai_tpu.models.trees import (
    OpDecisionTreeClassifier, OpDecisionTreeRegressor, OpGBTClassifier,
    OpGBTRegressor, OpRandomForestClassifier, OpRandomForestRegressor,
    OpXGBoostClassifier, OpXGBoostRegressor,
    bin_features, dispatch_plan, fit_forest, fit_gbt,
    forest_classification_pred, forest_regression_pred,
    gbt_base_score, gbt_pred_from_margin,
    gbt_train_summary, edges_site, hist_layout, hist_reads, hist_slots,
    indicator_columns, quantile_bin_edges, tree_span_attrs)
from transmogrifai_tpu.runtime.faults import (
    SITE_RUN_BLOCK, fault_point, is_oom_error)

log = logging.getLogger(__name__)


# --------------------------------------------------------------------------- #
# block journaling + fault-resilient group execution                          #
# --------------------------------------------------------------------------- #

# Per-family sweep state set by run_sweep for the duration of one family's
# handler call. Thread-local on purpose: families sweep concurrently on the
# selector's thread pool, each with its OWN journal file.
_SWEEP_TL = threading.local()


def _active_journal():
    return getattr(_SWEEP_TL, "journal", None)


class _BestTracker:
    """Running best-so-far (mean metric + grid) recorded into each journal
    entry, so a resumed operator can see where an interrupted sweep stood."""

    def __init__(self, larger_is_better: bool):
        self.sign = 1.0 if larger_is_better else -1.0
        self.best: Optional[Dict[str, Any]] = None

    def note(self, grid: Dict, row: List[float]) -> Optional[Dict[str, Any]]:
        mean = float(np.mean(row)) if row else float("nan")
        if np.isfinite(mean) and (
                self.best is None
                or self.sign * mean > self.sign * self.best["mean"]):
            self.best = {"mean": mean, "grid": grid}
        return self.best


def journal_prefill(journal, grids: List[Dict],
                    metrics: List[Optional[List[float]]],
                    event: str = "journal_resume") -> int:
    """Fill journaled rows into `metrics`; returns how many were skipped.
    Journal floats round-trip JSON exactly, so a resumed sweep's metric
    matrix is bit-identical to an uninterrupted run's. The ONE resume-
    skip implementation: the in-family path below, the distributed
    scheduler's per-job resume, and the pod scheduler's cross-host
    merge all route through it. `event` names the timeline event: a
    resume credits the journal with blocks it AVOIDED re-running
    ("journal_resume" savings in the goodput report), while a pod
    host merging shards for blocks other hosts ran THIS run records
    "pod_merge" — fleet work, not savings."""
    if journal is None:
        return 0
    hits = 0
    saved_s = 0.0
    for i, g in enumerate(grids):
        if metrics[i] is not None:
            continue
        row = journal.lookup(g)
        if row is not None:
            metrics[i] = row
            saved_s += journal.duration_of(g)
            hits += 1
    if hits:
        if event == "journal_resume":
            log.info("sweep journal: resuming past %d/%d completed blocks",
                     hits, len(grids))
            # resume-skip savings into the unified timeline + event log:
            # the goodput report credits the journal with the blocks it
            # avoided
            obs_export.record_event("journal_resume", blocks=hits,
                                    total=len(grids),
                                    saved_s=round(saved_s, 6))
        else:
            log.info("sweep journal: merged %d/%d foreign blocks (%s)",
                     hits, len(grids), event)
            obs_export.record_event(event, blocks=hits,
                                    total=len(grids),
                                    foreign_s=round(saved_s, 6))
    return hits


def _journal_prefill(grids: List[Dict],
                     metrics: List[Optional[List[float]]]) -> int:
    return journal_prefill(_active_journal(), grids, metrics)


def _journal_commit(grids: List[Dict],
                    metrics: List[Optional[List[float]]],
                    idxs: List[int],
                    block_s: Optional[float] = None,
                    facts: Optional[Dict] = None) -> None:
    journal = _active_journal()
    if journal is None:
        return
    best = getattr(_SWEEP_TL, "best", None)
    # the block ran its configs as one program: attribute wall time evenly
    per_cfg = (block_s / len(idxs)) if (block_s and idxs) else None
    block_facts = None
    if facts is not None and block_s is not None:
        # static-signature facts + the block's wall cost, stamped on
        # every record of the block under one block_key so a resumed
        # run's journal contributes training rows to the cost-model
        # corpus (perf/corpus.harvest_journal dedupes per block)
        block_facts = dict(facts)
        block_facts["block_s"] = round(float(block_s), 6)
        block_facts["block_key"] = _block_key_fn(grids)(idxs)
    for i in idxs:
        row = metrics[i]
        if row is None or any(m is None for m in row):
            continue
        journal.append(grids[i], row,
                       best=best.note(grids[i], row) if best else None,
                       duration_s=per_cfg, facts=block_facts)


def _run_groups_resilient(groups: Dict[Tuple, List[int]], run_one,
                          commit, family: str, facts=None,
                          block_key=None) -> None:
    """Execute grid-block groups with the fault-tolerance contract:

    - `fault_point(SITE_RUN_BLOCK)` fires before every block, so a chaos
      plan can kill/fail the sweep at any block boundary;
    - a device-OOM failure HALVES the block width and retries each half
      before surfacing (narrower blocks fit where wide ones did not —
      the compiled program per half persists in the compile cache); the
      failed wide attempt's wall time is recorded as an ``oom_redo``
      badput event on the enclosing span;
    - `commit(idxs, block_s, facts)` journals a block only after it
      fully completes, stamped with its wall cost + static-signature
      facts (resume-skip accounting and cost-model training rows).

    `facts(static, idxs)` returns the block's cost-model feature dict
    (`perf/features.block_features`); when provided, every executed
    block records its measured wall time (and predicted-vs-measured
    residual, when the model was warm) into the perf corpus — it only
    collects rows (the mesh scheduler's LPT order reads the model),
    nothing about this execution changes.
    `block_key(idxs)` stamps each row with the block's content key
    (same formula as the journal's `facts["block_key"]`) so a later
    `harvest_journal` of this run's journal recognizes the block as
    already recorded instead of duplicating it.
    """
    perf = model = None
    if facts is not None:
        try:
            from transmogrifai_tpu import perf
            model = perf.get_model()
        except Exception:
            perf = None

    def run(static, idxs):
        feats = facts(static, idxs) if facts is not None else None
        pred = (model.predict("block_runtime", feats)
                if model is not None and feats is not None else None)
        t0 = time.perf_counter()
        try:
            with TRACER.span("sweep:block", category="sweep",
                             family=family, static=repr(static),
                             configs=len(idxs)):
                fault_point(SITE_RUN_BLOCK)
                run_one(static, idxs)
        except Exception as e:
            if len(idxs) <= 1 or not is_oom_error(e):
                raise
            wasted = time.perf_counter() - t0
            obs_export.record_event("oom_redo", family=family,
                                    configs=len(idxs),
                                    wasted_s=round(wasted, 6))
            mid = (len(idxs) + 1) // 2
            log.warning(
                "sweep %s block %r: device OOM with %d configs (%s) — "
                "halving block width and retrying", family, static,
                len(idxs), e)
            run(static, idxs[:mid])
            run(static, idxs[mid:])
            return
        block_s = time.perf_counter() - t0
        if feats is not None and perf is not None:
            try:
                perf.note("block_runtime", feats, pred, block_s,
                          **({"block_key": block_key(idxs)}
                             if block_key is not None else {}))
            except Exception:
                log.debug("perf recording failed", exc_info=True)
        commit(idxs, block_s, feats)

    for static, idxs in groups.items():
        run(static, idxs)


# --------------------------------------------------------------------------- #
# host-path fallback (LambdaEvaluator / unknown model classes)                #
# --------------------------------------------------------------------------- #

def _metric(evaluator, y: np.ndarray, pred: Dict[str, np.ndarray],
            val_mask: np.ndarray) -> float:
    idx = val_mask > 0.5
    label = Column(T.RealNN, {
        "value": y[idx], "mask": np.ones(int(idx.sum()), dtype=bool)})
    pcol = Column(T.Prediction, {k: np.asarray(v)[idx] for k, v in pred.items()})
    return evaluator.metric_value(label, pcol)


def _sweep_generic(est, grids: List[Dict], X, y, folds, evaluator,
                   ctx) -> List[List[float]]:
    """Fallback: python loop over grids × folds (host metric path). A
    grid config is the journaling block here: journaled configs are
    skipped, completed configs append as soon as their folds finish."""
    from transmogrifai_tpu.models.trees import _TreeEstimatorBase
    out: List[List[float]] = []
    y_np = np.asarray(pull(f"sweep:{type(est).__name__}", y))
    journal = _active_journal()
    best = getattr(_SWEEP_TL, "best", None)
    bin_cache: Dict = {}  # shared across the family: bin X once per max_bins
    hits, saved_s = 0, 0.0
    for grid in grids:
        cached = journal.lookup(grid) if journal is not None else None
        if cached is not None:
            out.append(cached)
            if best is not None:
                best.note(grid, cached)
            hits += 1
            saved_s += journal.duration_of(grid)
            continue
        t0 = time.perf_counter()
        with TRACER.span("sweep:block", category="sweep",
                         family=type(est).__name__, configs=1):
            fault_point(SITE_RUN_BLOCK)
            clone = type(est)(**{**{k: v for k, v in est.params.items()
                                    if k != "uid"}, **grid})
            if isinstance(clone, _TreeEstimatorBase):
                clone._bin_cache = bin_cache
            row = []
            for tr, va in folds:
                with _dispatch_span(type(est).__name__):
                    model = clone.fit_arrays(
                        X, y, upload("sweep:folds", tr), ctx)
                    pred = model.predict_arrays(X)
                row.append(_metric(
                    evaluator, y_np,
                    pull(f"sweep:{type(est).__name__}", dict(pred)), va))
        out.append(row)
        if journal is not None:
            journal.append(grid, row,
                           best=best.note(grid, row) if best else None,
                           duration_s=time.perf_counter() - t0)
    if hits:
        obs_export.record_event("journal_resume", blocks=hits,
                                total=len(grids),
                                saved_s=round(saved_s, 6))
    return out


# --------------------------------------------------------------------------- #
# batched execution scaffold                                                  #
# --------------------------------------------------------------------------- #

class SweepStats:
    """Per-process dispatch accounting (SURVEY §5.1 'measure instead'):
    how many timed dispatches the tree families' sweep loops made and
    the thread-seconds they held (a first dispatch's compile included;
    the `compile:sweep:dispatch:*` spans say how much of it that was).
    `bench.py` resets before a sweep and reports the fraction."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with self._lock:
            self.dispatch_s = 0.0
            self.dispatches = 0

    def record(self, seconds: float) -> None:
        with self._lock:
            self.dispatch_s += seconds
            self.dispatches += 1


SWEEP_STATS = SweepStats()


@contextlib.contextmanager
def _dispatch_span(family: str, timed: bool = False, **attributes):
    """One device dispatch: holds a `sweep:dispatch:<family>` span open
    (`attributes`: what the dispatch holds, on the span),
    so the dispatch — and the XLA compile a first dispatch asks for, as
    the span's `compile:*` child — sits in the run's timeline
    (`instrumented_jit` drops a `recompile` event on it when the program
    is traced). `timed`
    (the tree families' host loops) also counts a dispatch that
    completed, and its wall, in `SWEEP_STATS`."""
    with TRACER.span(f"sweep:dispatch:{family}", category="sweep_dispatch",
                     **attributes):
        t0 = time.perf_counter()
        yield
        if timed:
            SWEEP_STATS.record(time.perf_counter() - t0)


def _grid_param(est, grid: Dict, name: str) -> Any:
    return grid.get(name, getattr(est, name, est.params.get(name)))


class HostMetricFallback:
    """Marker metric_fn: run the batched fit+predict XLA program, but score
    with a host evaluator (LambdaEvaluator / metrics with no device kernel).
    """

    def __init__(self, evaluator):
        self.evaluator = evaluator


def _shard_dyn(dyn: Dict[str, jnp.ndarray],
               sharding) -> Tuple[Dict[str, jnp.ndarray], int]:
    """Place the grid axis on the mesh's sweep axis; PAD a non-divisible
    group to the next multiple by repeating the last config (padded rows
    compute real but discarded fits — cheaper than replicating the whole
    group on every shard). Returns (dyn, original_g)."""
    if sharding is None:
        return dyn, next(iter(dyn.values())).shape[0]
    g = next(iter(dyn.values())).shape[0]
    n_shards = sharding.mesh.shape[sharding.spec[0]] if sharding.spec else 1
    if n_shards > 1 and g % n_shards != 0:
        pad = n_shards - g % n_shards
        dyn = {k: jnp.concatenate([v, jnp.repeat(v[-1:], pad, 0)])
               for k, v in dyn.items()}
    return {k: jax.device_put(v, sharding) for k, v in dyn.items()}, g


def _run_block(prog: Callable, data, W, V, dyn: Dict[str, jnp.ndarray],
               sharding, family: str = "generic", **attributes):
    """Execute one grid block: `prog(data, W, V, dyn)` is a
    `_block_program` over the grid axis of `dyn`; the dataset pytree and
    the fold masks are its non-mapped ARGUMENTS (under a mesh, the
    `NamedSharding`-placed arrays `_run_sweep` built — jit keeps their
    sharding). Returns the raw jax output (a (g, k) metric array, or a
    prediction pytree with leading (g, k) axes on the host-metric
    fallback path). `attributes` go on the dispatch's span.
    """
    dyn, g = _shard_dyn(dyn, sharding)
    with _dispatch_span(family, **attributes):
        out = jax.block_until_ready(prog(data, W, V, dyn))
    return jax.tree_util.tree_map(lambda a: a[:g], out)  # drop pad rows


# How many sweep programs a process holds per builder. A program is
# small now that no dataset is baked into it; the bound only keeps a
# process that sweeps ever-new static groups from growing for ever.
_HELD_PROGRAMS = 64


@functools.lru_cache(maxsize=_HELD_PROGRAMS)
def _block_program(family: str, static: Tuple, shape: Tuple,
                   metric_key: Optional[Tuple], mode: str) -> Callable:
    """The jitted fit→predict→metric program of one static group, built
    from its hashable key ALONE and held between sweeps: nothing reaches
    the program except through the key (static hyper-parameters and
    shape-like scalars) or through an argument (every array that depends
    on the dataset), so its HLO depends on shapes, dtypes and the key
    only. A second sweep on a same-shaped table — a refreshed table, the
    next workflow-CV fold — finds the program here and compiles nothing;
    a new process builds the identical module and finds it in the
    persistent cache.

    `_FIT_PREDICT[family](static, *shape)` gives `fit_predict(data, d,
    w, v) -> pred`; `metric_key` None keeps the prediction pytree (host
    metric fallback). `mode`: "pairs" maps grid×fold pairs — `prog(data,
    dchunk, Wsel, Vsel)`; "vmap" / "map" map the grid axis in parallel /
    in sequence (one compile, bounded histogram working set) with every
    fold vmapped inside — `prog(data, W, V, dyn)`.
    """
    from transmogrifai_tpu.analysis.retrace import instrumented_jit
    fit_predict = _FIT_PREDICT[family](static, *shape)
    metric_fn = None if metric_key is None else device_metric(metric_key)

    def one_pair(data, d, w, v):
        pred = fit_predict(data, d, w, v)
        return pred if metric_fn is None else metric_fn(data["y"], pred, v)

    def one_cfg(data, W, V, d):
        return jax.vmap(lambda w, v: one_pair(data, d, w, v))(W, V)

    label = f"sweep:{family}:{static!r}"
    if mode == "pairs":
        return instrumented_jit(jax.vmap(one_pair, in_axes=(None, 0, 0, 0)),
                                label=label + ":pairs")
    if mode == "vmap":
        return instrumented_jit(
            jax.vmap(one_cfg, in_axes=(None, None, None, 0)), label=label)
    return instrumented_jit(
        lambda data, W, V, dyn: jax.lax.map(
            lambda d: one_cfg(data, W, V, d), dyn), label=label)


def _sweep_blocks(grids: List[Dict], W, V, metric_fn, sharding,
                  family: str,
                  static_of: Callable[[Dict], Tuple],
                  dyn_of: Callable[[Dict], Dict[str, Any]],
                  data_of: Callable[[Tuple], Dict[str, Any]],
                  shape_of: Callable[[Tuple, List[int]], Tuple]
                  = lambda s, i: (),
                  grid_vmap: Callable[[Tuple, List[int]], bool] = lambda s, i: True,
                  host_dispatch: bool = False,
                  pair_width: Callable[[Tuple, List[int], int], int]
                  = lambda s, i, k: 1,
                  x_info: Optional[Tuple[int, int]] = None,
                  dispatch_attrs: Callable[[Tuple, List[int]], Dict]
                  = lambda s, i: {},
                  ) -> List[List[float]]:
    """Shared scaffold: group grids by static params; per group, stack the
    dynamic params into traced vectors and run fit→predict→metric as one
    program. The program is `_block_program(family, static,
    shape_of(static, idxs), …)`; `data_of(static)` is the family's pytree
    of device arrays for the group (`X`, `y` for the linear families, NB
    and MLP; `Xb`, `Y`, `y` for the forest; `Xb`, `y` for the boosted
    family) and reaches the program as an argument. The metric takes `y`
    from it.

    A `HostMetricFallback` metric_fn (custom/LambdaEvaluator metrics with no
    device kernel) keeps the batched fit+predict program but evaluates the
    wrapped evaluator over the materialized (g, k, n, …) prediction pytree
    on host — fits stay one XLA program per group either way.
    `dispatch_attrs(static, idxs)`: what a group's dispatches say of
    themselves on their `sweep:dispatch:<family>` spans.

    `host_dispatch` (tree families, single device only): compile ONE
    fit→predict→metric program per static group and dispatch it per
    grid×fold pair from the host instead of folding the whole group into a
    single giant execution. Compile count is unchanged; per-dispatch device
    time stays seconds even for 20-tree depth-12 forests on 100k rows,
    and the host loop bounds peak HBM to one chunk. With a mesh
    (`sharding`), the batched path runs so the grid axis shards.

    `x_info` = (n_features, wire dtype bytes) of the training matrix —
    the handlers pass it so every executed block can be described to
    the cost model (`perf/features.block_features`) without this
    scaffold touching X itself.
    """
    metrics: List[Optional[List[float]]] = [None] * len(grids)
    _journal_prefill(grids, metrics)  # resume: skip completed blocks
    groups: Dict[Tuple, List[int]] = {}
    for i, g in enumerate(grids):
        if metrics[i] is None:
            groups.setdefault(static_of(g), []).append(i)
    host = isinstance(metric_fn, HostMetricFallback)
    metric_key = None if host else metric_fn.key
    n_folds = int(W.shape[0])
    V_np = pull(f"sweep:{family}", V) if host else None

    def _run_group(static, idxs):
        dyn_dicts = [dyn_of(grids[i]) for i in idxs]
        dyn = {k: jnp.asarray([d[k] for d in dyn_dicts],
                              jnp.int32 if isinstance(dyn_dicts[0][k], int)
                              else jnp.float32)
               for k in dyn_dicts[0]}
        data = data_of(static)
        shape = shape_of(static, idxs)
        y_np = pull(f"sweep:{family}", data["y"]) if host else None

        if host_dispatch and sharding is None:
            n_pairs = len(idxs) * n_folds
            width = max(1, min(n_pairs,
                               pair_width(static, idxs, n_folds)))
            # flat pair index p ↔ (grid row, fold) = divmod(p, n_folds);
            # pad the final chunk by repeating the last pair (computed,
            # discarded). Dispatching `width` vmapped pairs at a time
            # amortizes the per-dispatch overhead over `width` fits while
            # each chunk is scored before the next dispatch, so peak HBM
            # is one chunk, not the whole group.
            prog = _block_program(family, static, shape, metric_key,
                                  "pairs")
            s = 0
            # device-metric path: every chunk's output is a tiny (width,)
            # metric vector, and each np.asarray is a blocking
            # device→host sync — so chunks accumulate as DEVICE arrays
            # and materialize in ONE fetch after the loop (the sweep
            # analogue of score_stream(fetch_group)). The host-metric fallback still
            # fetches per chunk: it needs the full prediction pytree and
            # bounding peak HBM to one chunk matters there.
            pend: List[Tuple[int, Any]] = []  # (s, device out)
            while s < n_pairs:
                ps = [min(s + t, n_pairs - 1) for t in range(width)]
                gs = [p // n_folds for p in ps]
                fs = [p % n_folds for p in ps]
                dchunk = {k: v[jnp.asarray(gs)] for k, v in dyn.items()}
                with _dispatch_span(family, timed=True,
                                    **dispatch_attrs(static, idxs)):
                    out = jax.block_until_ready(
                        prog(data, dchunk, W[jnp.asarray(fs)],
                             V[jnp.asarray(fs)]))
                if host:
                    out_np = pull(f"sweep:{family}", out)
                    for t in range(min(width, n_pairs - s)):
                        row_i, j = divmod(s + t, n_folds)
                        if metrics[idxs[row_i]] is None:
                            metrics[idxs[row_i]] = [None] * n_folds  # type: ignore
                        metrics[idxs[row_i]][j] = _metric(  # type: ignore
                            metric_fn.evaluator, y_np,
                            jax.tree_util.tree_map(
                                lambda a, t=t: a[t], out_np), V_np[j])
                else:
                    pend.append((s, out))
                s += width
            if pend:
                with TRACER.span(f"sweep:fetch:{family}",
                                 category="sweep_fetch"):
                    flat = pull(f"sweep:{family}", jnp.concatenate(
                        [jnp.asarray(o, jnp.float32) for _, o in pend]))
                for c, (s0, _) in enumerate(pend):
                    for t in range(min(width, n_pairs - s0)):
                        row_i, j = divmod(s0 + t, n_folds)
                        if metrics[idxs[row_i]] is None:
                            metrics[idxs[row_i]] = [None] * n_folds  # type: ignore
                        metrics[idxs[row_i]][j] = float(  # type: ignore
                            flat[c * width + t])
            return

        batched = grid_vmap(static, idxs) or sharding is not None
        gk = _run_block(
            _block_program(family, static, shape, metric_key,
                           "vmap" if batched else "map"),
            data, W, V, dyn, sharding, family=family,
            **dispatch_attrs(static, idxs))
        if host:
            pred_np = pull(f"sweep:{family}", gk)
            for row_i, grid_i in enumerate(idxs):
                metrics[grid_i] = [
                    _metric(metric_fn.evaluator, y_np,
                            {k: v[row_i, fold_j] for k, v in pred_np.items()},
                            V_np[fold_j])
                    for fold_j in range(V_np.shape[0])]
        else:
            with TRACER.span(f"sweep:fetch:{family}",
                             category="sweep_fetch"):
                gk = pull(f"sweep:{family}", gk)
            for row_i, grid_i in enumerate(idxs):
                metrics[grid_i] = [float(m) for m in gk[row_i]]

    # groups run SEQUENTIALLY on purpose (families already overlap on
    # the selector's thread pool): fanning groups out as well would
    # multiply concurrently-live dispatch chunks past the per-dispatch
    # memory budget (`dispatch_plan`; device OOM faults poison the
    # process on this serving stack).
    _run_groups_resilient(
        groups, _run_group,
        commit=lambda idxs, block_s=None, facts=None: _journal_commit(
            grids, metrics, idxs, block_s, facts),
        family=family,
        facts=_block_facts_fn(family, W, x_info),
        block_key=_block_key_fn(grids))
    return metrics  # type: ignore[return-value]


def _block_key_fn(grids: List[Dict]):
    """Content key of a block (the grids it ran), matching
    `_journal_commit`'s `facts["block_key"]` — one identity shared by
    live corpus rows and journal records so harvests never duplicate a
    block this process already recorded."""
    from transmogrifai_tpu.runtime.journal import SweepJournal

    def key(idxs: List[int]) -> str:
        return SweepJournal.key_of({"block": [grids[i] for i in idxs]})
    return key


def _x_info(X) -> Tuple[int, int]:
    """(n_features, wire dtype bytes) of a training matrix — the shape
    facts the cost model keys block features on."""
    try:
        return int(X.shape[1]), int(np.dtype(X.dtype).itemsize)
    except (AttributeError, IndexError, TypeError):
        return 0, 4


def _block_facts_fn(family: str, W, x_info: Optional[Tuple[int, int]]):
    """The `facts(static, idxs)` callback `_run_groups_resilient` feeds
    the cost model; None (no x_info) keeps the group runner silent."""
    if x_info is None:
        return None
    n_cols, dtype_bytes = x_info
    n_folds, n_rows = (int(n) for n in np.shape(W))

    def facts(static, idxs):
        from transmogrifai_tpu.perf.features import block_features
        return block_features(family, static, len(idxs), n_rows, n_cols,
                              n_folds, dtype_bytes)
    return facts


# --------------------------------------------------------------------------- #
# family handlers                                                             #
# --------------------------------------------------------------------------- #

def _enet_of(est, g) -> float:
    return float(_grid_param(est, g, "elastic_net_param") or 0.0)


def _l1_l2_of(est, g) -> Dict[str, float]:
    """Spark penalty split: reg·α → L1, reg·(1−α) → L2
    (`DefaultSelectorParams.scala:48` ElasticNet {0.1, 0.5})."""
    reg = float(_grid_param(est, g, "reg_param"))
    alpha = _enet_of(est, g)
    return {"l1": reg * alpha, "l2": reg * (1.0 - alpha)}


# -- per-family static grouping keys ---------------------------------------- #
# Module-level (not closures inside the handlers) on purpose: the
# distributed scheduler (parallel/scheduler.py) partitions a family's
# grids into work blocks along EXACTLY these boundaries, so a scheduled
# block regroups into one compiled program on its worker — the same
# static-shape strategy as the single-device sweep, just spread over the
# mesh. The handlers below pass these same functions to `_sweep_blocks`.

def _static_logistic(est, g) -> Tuple:
    return (int(_grid_param(est, g, "max_iter")), _enet_of(est, g) > 0.0)


def _static_linreg(est, g) -> Tuple:
    return (_enet_of(est, g) > 0.0,)


def _static_svc(est, g) -> Tuple:
    return (int(_grid_param(est, g, "max_iter")),)


def _static_glm(est, g) -> Tuple:
    ln = _grid_param(est, g, "link")
    return (str(_grid_param(est, g, "family")),
            int(_grid_param(est, g, "max_iter")),
            float(_grid_param(est, g, "var_power")),
            str(ln) if ln is not None else None)


def _static_nb(est, g) -> Tuple:
    return ()


def _static_mlp(est, g) -> Tuple:
    return (tuple(_grid_param(est, g, "hidden_layers")),
            int(_grid_param(est, g, "max_iter")))


def _static_forest(est, g) -> Tuple:
    return (int(_grid_param(est, g, "n_trees")),
            int(_grid_param(est, g, "max_bins")),
            bool(_grid_param(est, g, "subsample_features")),
            _depth_bucket(int(_grid_param(est, g, "max_depth"))))


def _static_gbt(est, g) -> Tuple:
    return (int(_grid_param(est, g, "n_estimators")),
            int(_grid_param(est, g, "max_bins")),
            int(_grid_param(est, g, "early_stopping_rounds") or 0),
            _depth_bucket(int(_grid_param(est, g, "max_depth"))))


def static_signature(est, grid: Dict) -> Tuple:
    """The (family, static-group) key a grid config compiles under.

    Two grids with equal signatures share one batched XLA program in the
    family handlers; the distributed scheduler uses this to cut a
    family's grid list into blocks that never split a compiled group
    (a split group would compile twice at different dyn-vector shapes).
    Unknown estimator classes fall back to per-config blocks (they run
    the eager `_sweep_generic` path, where a config IS the unit)."""
    if isinstance(est, (OpXGBoostClassifier, OpXGBoostRegressor,
                        OpGBTClassifier, OpGBTRegressor)):
        return ("gbt", _static_gbt(est, grid))
    if isinstance(est, (OpRandomForestRegressor, OpDecisionTreeRegressor,
                        OpRandomForestClassifier, OpDecisionTreeClassifier)):
        return ("forest", _static_forest(est, grid))
    if isinstance(est, OpLogisticRegression):
        return ("logistic", _static_logistic(est, grid))
    if isinstance(est, OpLinearRegression):
        return ("linreg", _static_linreg(est, grid))
    if isinstance(est, OpLinearSVC):
        return ("svc", _static_svc(est, grid))
    if isinstance(est, OpGeneralizedLinearRegression):
        return ("glm", _static_glm(est, grid))
    if isinstance(est, OpNaiveBayes):
        return ("naive_bayes", _static_nb(est, grid))
    if isinstance(est, OpMultilayerPerceptronClassifier):
        return ("mlp", _static_mlp(est, grid))
    from transmogrifai_tpu.runtime.journal import SweepJournal
    return ("generic", SweepJournal.key_of(grid))


# -- per-family program bodies ---------------------------------------------- #
# `_FIT_PREDICT[family](static, *shape)` -> `fit_predict(data, d, w, v)`:
# module-level builders of hashable scalars only (the group's static key
# and the shape-like scalars the handler derives — `n_classes`, `seed`,
# `pad_depth`, the dispatch width), so `_block_program` can hold what
# they build. Every array with a row axis arrives in `data`.

def _fp_logistic(static, n_classes):
    max_iter, enet = static
    if enet:  # FISTA path — one compile covers the whole (l1, l2) grid
        iters = enet_iters(max_iter)
        return lambda data, d, w, v: predict_logreg(
            fit_logreg_enet(data["X"], data["y"], w, d["l1"], d["l2"],
                            n_classes, iters), data["X"])
    return lambda data, d, w, v: predict_logreg(
        fit_logreg(data["X"], data["y"], w, d["l2"], n_classes, max_iter),
        data["X"])


def _fp_linreg(static):
    if static[0]:  # any L1 in the group → FISTA elastic net
        return lambda data, d, w, v: predict_linreg(
            fit_linreg_enet(data["X"], data["y"], w, d["l1"], d["l2"]),
            data["X"])
    return lambda data, d, w, v: predict_linreg(
        fit_linreg(data["X"], data["y"], w, d["l2"]), data["X"])


def _fp_svc(static):
    return lambda data, d, w, v: predict_linear_svc(
        fit_linear_svc(data["X"], data["y"], w, d["reg"], static[0]),
        data["X"])


def _fp_glm(static):
    family, max_iter, var_power, link = static
    return lambda data, d, w, v: predict_glm(
        fit_glm(data["X"], data["y"], w, d["reg"], family, max_iter,
                var_power, link),
        data["X"], family, link, var_power)


def _fp_nb(static, n_classes):
    return lambda data, d, w, v: predict_naive_bayes(
        fit_naive_bayes(data["X"], data["y"], w, d["smoothing"], n_classes),
        data["X"])


def _fp_mlp(static, n_features, n_classes, seed):
    hidden, max_iter = static
    layers = (n_features,) + tuple(hidden) + (n_classes,)
    return lambda data, d, w, v: predict_mlp(
        fit_mlp(data["X"], data["y"], w, layers, max_iter, d["lr"], seed),
        data["X"])


def _fp_forest(static, pad_depth, divisor, n_out, seed, bootstrap,
               regression, blocks):
    """`blocks`: the histogram operand's (d_wide, d_ind), or None for the
    one uniform block; the columns' positions arrive in `data["layout"]`
    (`_binned_cache`), so same-count tables share the program."""
    n_trees, max_bins, subsample = static[:3]
    pred_fn = forest_regression_pred if regression \
        else forest_classification_pred

    def fit_predict(data, d, w, v):
        trees = fit_forest(data["Xb"], data["Y"], w, n_trees, pad_depth,
                           max_bins, n_out, seed, subsample, d["mcw"],
                           active_depth=d["depth"], bootstrap=bootstrap,
                           tree_budget_divisor=divisor,
                           min_gain=d["min_gain"],
                           layout=data["layout"] if blocks else None)
        # small predict chunk: the dispatch vmaps `divisor` pairs, so
        # the per-chunk (c, n, m->128) slab multiplies by the width
        return pred_fn(trees, data["Xb"], chunk=8)
    return fit_predict


def _fp_gbt(static, pad_depth, n_classes, seed, objective, eval_metric,
            blocks):
    """The boosted family's single-program path (a mesh): the whole fit,
    with in-scan early-stop masking; a "softmax" chain boosts
    `n_classes` margins. `blocks` as in `_fp_forest`."""
    n_estimators, max_bins, esr = static[:3]

    def fit_predict(data, d, w, v):
        # the scan carry is the final training-matrix margin — no
        # post-fit forest re-walk needed
        _, margin = fit_gbt(
            data["Xb"], data["y"], w, n_estimators, pad_depth, max_bins,
            d["lr"], d["lam"], objective, min_child_weight=d["mcw"],
            active_depth=d["depth"], gamma=d["gamma"], alpha=d["alpha"],
            subsample=d["subsample"], colsample=d["colsample"], seed=seed,
            val_w=v, early_stopping_rounds=esr,
            min_gain_norm=d["min_gain_norm"], eval_metric=eval_metric,
            layout=data["layout"] if blocks else None,
            n_classes=n_classes if objective == "softmax" else 0)
        return gbt_pred_from_margin(margin, objective)
    return fit_predict


_FIT_PREDICT: Dict[str, Callable] = {
    "logistic": _fp_logistic, "linreg": _fp_linreg, "svc": _fp_svc,
    "glm": _fp_glm, "naive_bayes": _fp_nb, "mlp": _fp_mlp,
    "forest": _fp_forest, "gbt": _fp_gbt}


def _xy_data(X, y) -> Callable[[Tuple], Dict[str, Any]]:
    """`data_of` of the families that fit on the raw matrix: one pytree
    for every static group."""
    data = {"X": X, "y": y}
    return lambda static: data


def _sweep_logistic(est, grids, X, y, W, V, metric_fn, ctx, sharding):
    n_classes = n_classes_of(est, y, ctx)
    return _sweep_blocks(
        grids, W, V, metric_fn, sharding, "logistic",
        static_of=lambda g: _static_logistic(est, g),
        dyn_of=lambda g: _l1_l2_of(est, g),
        data_of=_xy_data(X, y),
        shape_of=lambda st, idxs: (n_classes,), x_info=_x_info(X))


def _sweep_linreg(est, grids, X, y, W, V, metric_fn, ctx, sharding):
    return _sweep_blocks(
        grids, W, V, metric_fn, sharding, "linreg",
        static_of=lambda g: _static_linreg(est, g),
        dyn_of=lambda g: _l1_l2_of(est, g),
        data_of=_xy_data(X, y), x_info=_x_info(X))


def _sweep_svc(est, grids, X, y, W, V, metric_fn, ctx, sharding):
    return _sweep_blocks(
        grids, W, V, metric_fn, sharding, "svc",
        static_of=lambda g: _static_svc(est, g),
        dyn_of=lambda g: {"reg": float(_grid_param(est, g, "reg_param"))},
        data_of=_xy_data(X, y), x_info=_x_info(X))


def _sweep_glm(est, grids, X, y, W, V, metric_fn, ctx, sharding):
    return _sweep_blocks(
        grids, W, V, metric_fn, sharding, "glm",
        static_of=lambda g: _static_glm(est, g),
        dyn_of=lambda g: {"reg": float(_grid_param(est, g, "reg_param"))},
        data_of=_xy_data(X, y), x_info=_x_info(X))


def _sweep_nb(est, grids, X, y, W, V, metric_fn, ctx, sharding):
    # Spark parity: family fails on negative features, selector drops it.
    # The host read is a blocking device sync, so
    # the verdict is cached per training matrix on the FitContext — one
    # sync per selector fit, not one per NB sweep/fold.
    cache = getattr(ctx, "_nb_nonneg_cache", None) if ctx is not None else None
    if cache is None or cache[0] is not X:
        cache = (X, bool(jnp.any(X < 0)))
        if ctx is not None:
            ctx._nb_nonneg_cache = cache
    if cache[1]:
        raise ValueError(
            "NaiveBayes requires non-negative features (Spark parity)")
    n_classes = n_classes_of(est, y, ctx)
    return _sweep_blocks(
        grids, W, V, metric_fn, sharding, "naive_bayes",
        static_of=lambda g: _static_nb(est, g),
        dyn_of=lambda g: {"smoothing": float(_grid_param(est, g, "smoothing"))},
        data_of=_xy_data(X, y),
        shape_of=lambda st, idxs: (n_classes,), x_info=_x_info(X))


def _sweep_mlp(est, grids, X, y, W, V, metric_fn, ctx, sharding):
    n_classes = n_classes_of(est, y, ctx)
    seed = int(ctx.seed) if ctx is not None else 0
    return _sweep_blocks(
        grids, W, V, metric_fn, sharding, "mlp",
        static_of=lambda g: _static_mlp(est, g),
        dyn_of=lambda g: {"lr": float(_grid_param(est, g, "learning_rate"))},
        data_of=_xy_data(X, y),
        shape_of=lambda st, idxs: (int(X.shape[1]), n_classes, seed),
        x_info=_x_info(X))


# --------------------------------------------------------------------------- #
# tree families: padded-depth trick, one compile per (bins, trees) group      #
# --------------------------------------------------------------------------- #

def _binned_cache(est, grids, X, ctx, n_classes: int = 0) -> Tuple[
        Dict[int, jnp.ndarray], Optional[Dict], Optional[Tuple[int, int]]]:
    """Bin X once per distinct max_bins ACROSS tree families in a sweep:
    the cache lives on the FitContext, so RF and XGB in the same selector
    share the quantile binning of the identical training matrix. (The eager
    fallback path has its own per-estimator `_bin_cache`.) Returns the
    binned matrices by max_bins, the histogram layout (`hist_layout`:
    which columns are indicators, read off X once) and its two block
    widths (None with no indicator column: a program's key).

    Quantile edges come from the UNPADDED rows (`ctx._sweep_n_rows`): mesh
    padding appends zero-weight rows which must not shift bin edges, or
    sharded sweeps would silently deviate from unsharded ones. Of a
    device matrix only the non-indicator columns' order statistics cross
    to the host for them (the span's `edges` attribute says which).
    `n_classes` is the calling family's (a classifier forest's K, else
    0): the span of the family that bins carries that family's value
    columns and its passes over the histogram operand a tree level
    (`hist_reads`).

    Guarded by a lock: tree families now sweep on a thread pool, and two
    families hitting the same max_bins must not double-build the (n, d)
    binned matrix."""
    with _BIN_CACHE_LOCK:
        out = (getattr(ctx, "_sweep_bin_cache", None)
               if ctx is not None else None)
        if out is None:
            out = {}
            if ctx is not None:
                ctx._sweep_bin_cache = out
        n = getattr(ctx, "_sweep_n_rows", None) if ctx is not None else None
        for g in grids:
            mb = int(_grid_param(est, g, "max_bins"))
            if mb in out:
                continue
            # a miss: the one family that bins, the others wait on the lock
            with TRACER.span("sweep:bin", category="sweep",
                             max_bins=mb) as sp:
                X_edges = X if n is None else X[:n]
                # the waiters need exactly this binned matrix: they stall
                # behind the two reads (`pull:tree:*`) on purpose
                if "indicator" not in out:      # no shared context
                    # conc-ok: C003 (waiters reuse the binned matrix)
                    out["indicator"] = indicator_columns(X_edges)
                if "layout" not in out:
                    out["layout"] = hist_layout(out["indicator"])
                # conc-ok: C003 (waiters reuse the binned matrix)
                edges = quantile_bin_edges(X_edges, mb, out["indicator"])
                out[mb] = bin_features(upload("sweep:bin", X),
                                       upload("sweep:bin", edges))
                sp.set(hist_slots=hist_slots(int(X.shape[1]), mb,
                                             out["layout"]),
                       edges=edges_site(X_edges),
                       value_columns=n_classes or 1,
                       hist_reads=hist_reads(n_classes))
        layout = out.get("layout")
        blocks = None if layout is None else tuple(
            int(layout[b].shape[0]) for b in ("wide", "ind"))
        return out, layout, blocks


_BIN_CACHE_LOCK = threading.Lock()
_SWEEP_DATA_LOCK = threading.Lock()


_DEPTH_BUCKETS = (4, 6, 8, 10, 12, 14)


def _depth_bucket(depth: int) -> int:
    """Quantize a max_depth to a padding bucket. Two jobs (VERDICT r3 #2):
    grids in DIFFERENT buckets compile separately, so a depth-3 config no
    longer pays the 2^12-node histogram cost of sharing a depth-12
    program (level cost doubles per level — sharing one padded program
    across {3,6,12} made the shallow 2/3 of the reference RF grid ~50×
    more expensive than needed); and the padded shape depends only on the
    bucket, not on which exact depths co-occur in a grid, so compiled
    shapes stay stable across grid edits for the persistent cache."""
    for b in _DEPTH_BUCKETS:
        if depth <= b:
            return b
    return _DEPTH_BUCKETS[-1]


def _pad_depth_of(est, grids, idxs) -> int:
    return _depth_bucket(
        max(int(_grid_param(est, grids[i], "max_depth")) for i in idxs))


def _sweep_forest(est, grids, X, y, W, V, metric_fn, ctx, sharding,
                  regression: bool):
    if regression:
        Y = upload("sweep:label", y)[:, None]
        n_out = 1
    else:       # the labels themselves: `grow_tree`'s class form
        n_out = n_classes_of(est, y, ctx)
        Y = upload("sweep:label", y).astype(jnp.int32)
    xb_by_bins, layout, blocks = _binned_cache(
        est, grids, X, ctx, n_classes=0 if regression else n_out)
    seed = int(ctx.seed) if ctx is not None else 0
    # single deterministic tree for DT estimators (no Poisson bootstrap), so
    # sweep metrics describe exactly what the refit fit_arrays produces
    bootstrap = not isinstance(
        est, (OpDecisionTreeClassifier, OpDecisionTreeRegressor))

    n_folds, n_rows = (int(n) for n in W.shape)
    d_feat = int(X.shape[1])

    def width_of(st, idxs):
        n_trees, max_bins, _ = st[:3]
        # capped at the pair count itself — keeps the fit_forest chunk
        # budget in step with actual live instances
        return dispatch_plan(n_rows, hist_slots(d_feat, max_bins, layout),
                             _pad_depth_of(est, grids, idxs), n_trees,
                             len(idxs) * n_folds, value_columns=n_out)[0]

    def shape_of(st, idxs):
        # unsharded → host dispatch of `width` vmapped pairs at a time;
        # sharded → the whole grid×fold block is vmapped. Either way the
        # tree-chunking inside fit_forest budgets for every simultaneous
        # instance.
        divisor = (width_of(st, idxs) if sharding is None
                   else max(1, len(idxs) * n_folds))
        return (_pad_depth_of(est, grids, idxs), divisor, n_out, seed,
                bootstrap, regression, blocks)

    def dyn_of(g):
        mcw = max(float(_grid_param(est, g, "min_child_weight") or 1.0),
                  float(_grid_param(est, g, "min_instances_per_node") or 1.0))
        return {"depth": int(_grid_param(est, g, "max_depth")),
                "mcw": mcw,
                "min_gain": float(_grid_param(est, g, "min_info_gain") or 0.0)}

    # one PADDED compile per (family group, depth bucket): traced
    # active_depth masks unused levels within a bucket, while the bucket
    # split keeps shallow configs off the deep configs' 2^depth node cost
    # (the persistent compile cache absorbs the extra program per bucket)
    return _sweep_blocks(
        grids, W, V, metric_fn, sharding, "forest",
        static_of=lambda g: _static_forest(est, g),
        dyn_of=dyn_of,
        data_of=lambda st: {"Xb": xb_by_bins[st[1]], "Y": Y, "y": y,
                            **({"layout": layout} if blocks else {})},
        shape_of=shape_of,
        grid_vmap=lambda st, idxs: _pad_depth_of(est, grids, idxs) <= 6,
        host_dispatch=True,
        pair_width=lambda st, idxs, k: width_of(st, idxs),
        x_info=_x_info(X),
        dispatch_attrs=lambda st, idxs: tree_span_attrs(
            _pad_depth_of(est, grids, idxs), 0 if regression else n_out))


@functools.lru_cache(maxsize=_HELD_PROGRAMS)
def _gbt_rounds_program(static: Tuple, pad_depth: int, objective: str,
                        eval_metric: str,
                        blocks: Optional[Tuple[int, int]]) -> Callable:
    """`prog(data, dchunk, Wsel, Vsel, margin, best, since, keys)`: one
    chunk of boosting rounds (as many as `keys` holds) for `width`
    vmapped grid×fold pairs, carrying the early-stopping state. Built
    from its key alone and held, like `_block_program`; `blocks` as in
    `_fp_forest`."""
    from transmogrifai_tpu.analysis.retrace import instrumented_jit
    from transmogrifai_tpu.models.trees import fit_gbt_chunk
    _, max_bins, esr = static[:3]

    def chunk_pair(data, d, w, v, margin, best, since, ks):
        (m, b, s), _ = fit_gbt_chunk(
            data["Xb"], data["y"], w, v, margin, best, since, ks,
            int(ks.shape[0]), pad_depth, max_bins, d["lr"], d["lam"],
            objective, d["mcw"], d["depth"], d["gamma"], d["alpha"],
            d["subsample"], d["colsample"], esr, d["min_gain_norm"],
            eval_metric, data["layout"] if blocks else None)
        return m, b, s

    return instrumented_jit(
        jax.vmap(chunk_pair, in_axes=(None, 0, 0, 0, 0, 0, 0, None)),
        label=f"sweep:gbt:{static!r}:rounds")


@functools.lru_cache(maxsize=_HELD_PROGRAMS)
def _gbt_score_program(static: Tuple, objective: str,
                       metric_key: Optional[Tuple]) -> Callable:
    """`prog(y, margin, Vsel, Wsel)`: the chunked boosted sweep's final
    margins to (fold metrics, `gbt_train_summary` under the folds'
    training weights) — or, without a device kernel (`metric_key` None),
    the prediction pytree the host evaluator reads in the metrics'
    place."""
    from transmogrifai_tpu.analysis.retrace import instrumented_jit
    metric_fn = None if metric_key is None else device_metric(metric_key)

    def score(y, margin, v, w):
        pred = gbt_pred_from_margin(margin, objective)
        return (pred if metric_fn is None else metric_fn(y, pred, v),
                gbt_train_summary(margin, y, w, objective))

    return instrumented_jit(
        jax.vmap(score, in_axes=(None, 0, 0, 0)),
        label=f"sweep:gbt:{static!r}:"
              + ("pred" if metric_fn is None else "metric"))


def _sweep_gbt(est, grids, X, y, W, V, metric_fn, ctx, sharding):
    xb_by_bins, layout, blocks = _binned_cache(est, grids, X, ctx)
    n_classes = 2
    if est._objective == "logistic":
        n_classes = n_classes_of(est, y, ctx)
    objective = est.objective_of(n_classes)
    seed = int(ctx.seed) if ctx is not None else 0
    # a softmax round grows a tree a class: K in the plan, on the spans,
    # and in the trees one histogram product batches
    classes = n_classes if objective == "softmax" else 0
    chain_attrs = {"classes": classes} if classes else {}

    def lr_of(grid) -> float:
        v = grid.get("eta", grid.get("learning_rate"))
        if v is None:
            v = est.params.get("eta", getattr(est, "learning_rate", 0.1))
        return float(v)

    n_folds, n_rows = (int(n) for n in W.shape)
    d_feat = int(X.shape[1])

    eval_metric = str(getattr(est, "eval_metric", "logloss") or "logloss")

    def static_of(g):
        return _static_gbt(est, g)

    def data_of(st):
        return {"Xb": xb_by_bins[st[1]], "y": y,
                **({"layout": layout} if blocks else {})}

    def dyn_of(g):
        mcw = max(float(_grid_param(est, g, "min_child_weight") or 1.0),
                  float(_grid_param(est, g, "min_instances_per_node") or 1.0))
        return {
            "depth": int(_grid_param(est, g, "max_depth")),
            "lr": lr_of(g),
            "lam": float(_grid_param(est, g, "reg_lambda")),
            "mcw": mcw,
            "gamma": float(_grid_param(est, g, "gamma") or 0.0),
            "alpha": float(_grid_param(est, g, "alpha") or 0.0),
            "subsample": float(_grid_param(est, g, "subsample") or 1.0),
            "colsample": float(
                _grid_param(est, g, "colsample_bytree") or 1.0),
            "min_gain_norm": float(
                _grid_param(est, g, "min_info_gain") or 0.0)}

    if sharding is not None:
        # mesh-sharded grids (dryrun/pod shapes) keep the single-program
        # path (`_fp_gbt`): the whole fit (with in-scan early-stop
        # masking — same key stream and state transitions as the chunked
        # loop, so metrics agree) vmaps over the grid axis
        def width_of(st, idxs):
            n_estimators, max_bins = st[0], st[1]
            return dispatch_plan(
                n_rows, hist_slots(d_feat, max_bins, layout),
                _pad_depth_of(est, grids, idxs), n_estimators,
                len(idxs) * n_folds, classes=classes)[0]

        return _sweep_blocks(
            grids, W, V, metric_fn, sharding, "gbt",
            static_of=static_of, dyn_of=dyn_of, data_of=data_of,
            shape_of=lambda st, idxs: (
                _pad_depth_of(est, grids, idxs), n_classes, seed,
                objective, eval_metric, blocks),
            grid_vmap=lambda st, idxs: _pad_depth_of(est, grids, idxs) <= 6,
            pair_width=lambda st, idxs, k: width_of(st, idxs),
            x_info=_x_info(X),
            dispatch_attrs=lambda st, idxs: dict(tree_span_attrs(
                _pad_depth_of(est, grids, idxs), batched_trees=classes or 1),
                objective=objective, **chain_attrs))

    # ---- single device: ROUND-CHUNKED host dispatch ---- #
    # Each dispatch runs `rpd` boosting rounds for `width` vmapped
    # grid×fold pairs, carrying (margin, best_val, since) across
    # dispatches instead of one 200-round execution: once EVERY pair in
    # the chunk reports since >= early_stopping_rounds the remaining
    # rounds are skipped outright — the host-loop analogue of the
    # reference's numEarlyStoppingRounds (DefaultSelectorParams.scala:74).
    metrics: List[Optional[List[float]]] = [None] * len(grids)
    _journal_prefill(grids, metrics)  # resume: skip completed blocks
    groups: Dict[Tuple, List[int]] = {}
    for i, g in enumerate(grids):
        if metrics[i] is None:
            groups.setdefault(static_of(g), []).append(i)
    host = isinstance(metric_fn, HostMetricFallback)
    y_np = np.asarray(pull("sweep:gbt", y)) if host else None
    V_np = pull("sweep:gbt", V) if host else None

    def _run_gbt_group(static, idxs):
        n_est, max_bins, esr = static[:3]
        data = data_of(static)
        pad_depth = _pad_depth_of(est, grids, idxs)
        dyn_dicts = [dyn_of(grids[i]) for i in idxs]
        dyn = {k: jnp.asarray([dd[k] for dd in dyn_dicts],
                              jnp.int32 if isinstance(dyn_dicts[0][k], int)
                              else jnp.float32)
               for k in dyn_dicts[0]}
        n_pairs = len(idxs) * n_folds
        # a power-of-two width, NOT clamped to the remaining pair count:
        # the pair-index padding (`ps` repeats the last pair) keeps the
        # tail chunk at the same compiled shape instead of forcing a
        # second compile; `rpd` divides the rounds, so one chunk shape
        width, rpd = dispatch_plan(
            n_rows, hist_slots(d_feat, max_bins, layout), pad_depth, n_est,
            n_pairs, pad_tail=True, classes=classes)

        prog = _gbt_rounds_program(static, pad_depth, objective, eval_metric,
                                   blocks)
        score_prog = _gbt_score_program(
            static, objective, None if host else metric_fn.key)
        keys_all = jax.random.split(jax.random.PRNGKey(seed), n_est)

        s = 0
        while s < n_pairs:
            ps = [min(s + t, n_pairs - 1) for t in range(width)]
            gs = [p // n_folds for p in ps]
            fs = [p % n_folds for p in ps]
            dchunk = {k: v_[jnp.asarray(gs)] for k, v_ in dyn.items()}
            Wsel = W[jnp.asarray(fs)]
            Vsel = V[jnp.asarray(fs)]
            if objective == "squared":
                # each pair's chain starts at its own fold's weighted mean
                base = jax.vmap(lambda w: gbt_base_score(
                    data["y"], w, objective))(Wsel)
                margin = jnp.broadcast_to(base[:, None], (width, n_rows))
            elif classes:
                margin = jnp.zeros((width, n_rows, classes), jnp.float32)
            else:
                margin = jnp.zeros((width, n_rows), jnp.float32)
            best = jnp.full((width,), jnp.inf, jnp.float32)
            since = jnp.zeros((width,), jnp.int32)
            done = 0
            while done < n_est:
                ks = keys_all[done:done + rpd]
                # `pairs`: the dispatch's real pairs (a padded tail
                # repeats the last one up to the compiled width)
                with _dispatch_span("gbt", timed=True,
                                    rounds=int(ks.shape[0]),
                                    pairs=min(width, n_pairs - s),
                                    pad_depth=pad_depth,
                                    objective=objective,
                                    **tree_span_attrs(
                                        pad_depth,
                                        batched_trees=classes or 1),
                                    **chain_attrs):
                    margin, best, since = jax.block_until_ready(
                        prog(data, dchunk, Wsel, Vsel, margin, best, since,
                             ks))
                done += int(ks.shape[0])
                if (esr > 0 and done < n_est
                        and bool(np.all(pull("sweep:gbt", since) >= esr))):
                    log.info("gbt sweep: early stop after %d/%d rounds "
                             "(%d pairs)", done, n_est, width)
                    break
            if host:
                pred_np = pull(
                    "sweep:gbt", score_prog(data["y"], margin, Vsel, Wsel)[0])
                row_metrics = [
                    _metric(metric_fn.evaluator, y_np,
                            jax.tree_util.tree_map(
                                lambda a, t=t: a[t], pred_np),
                            V_np[fs[t]])
                    for t in range(width)]
            else:
                with TRACER.span("sweep:fetch:gbt",
                                 category="sweep_fetch") as fetch:
                    row_metrics, fitted = pull(
                        "sweep:gbt", score_prog(data["y"], margin, Vsel, Wsel))
                    row_metrics = [float(m) for m in row_metrics]
                    # the dispatch's real pairs, as (grid, fold), and
                    # what each chain says of its training rows
                    real = range(min(width, n_pairs - s))
                    fetch.set(grids=[idxs[gs[t]] for t in real],
                              folds=[fs[t] for t in real],
                              **{k: [float(v[t]) for t in real]
                                 for k, v in fitted.items()})
            for t in range(min(width, n_pairs - s)):
                row_i, j = divmod(s + t, n_folds)
                if metrics[idxs[row_i]] is None:
                    metrics[idxs[row_i]] = [None] * n_folds  # type: ignore
                metrics[idxs[row_i]][j] = row_metrics[t]  # type: ignore
            s += width

    _run_groups_resilient(
        groups, _run_gbt_group,
        commit=lambda idxs, block_s=None, facts=None: _journal_commit(
            grids, metrics, idxs, block_s, facts),
        family="gbt",
        facts=_block_facts_fn("gbt", W, _x_info(X)),
        block_key=_block_key_fn(grids))
    return metrics  # type: ignore[return-value]


# --------------------------------------------------------------------------- #
# dispatch                                                                    #
# --------------------------------------------------------------------------- #

def _dispatch(est) -> Optional[Callable]:
    # order matters: subclasses before parents
    if isinstance(est, (OpXGBoostClassifier, OpXGBoostRegressor,
                        OpGBTClassifier, OpGBTRegressor)):
        return _sweep_gbt
    if isinstance(est, (OpRandomForestRegressor, OpDecisionTreeRegressor)):
        return lambda *a: _sweep_forest(*a, regression=True)
    if isinstance(est, (OpRandomForestClassifier, OpDecisionTreeClassifier)):
        return lambda *a: _sweep_forest(*a, regression=False)
    if isinstance(est, OpLogisticRegression):
        return _sweep_logistic
    if isinstance(est, OpLinearRegression):
        return _sweep_linreg
    if isinstance(est, OpLinearSVC):
        return _sweep_svc
    if isinstance(est, OpGeneralizedLinearRegression):
        return _sweep_glm
    if isinstance(est, OpNaiveBayes):
        return _sweep_nb
    if isinstance(est, OpMultilayerPerceptronClassifier):
        return _sweep_mlp
    return None


def run_sweep(est, grids: List[Dict], X, y, folds, evaluator, ctx,
              sharding=None, journal=None) -> List[List[float]]:
    """Metric matrix [grid][fold] for one model family.

    `journal`: optional `runtime.journal.SweepJournal` — completed grid
    blocks append as soon as their fold metrics are final, and already-
    journaled configs are skipped, so a killed sweep resumed with the
    same journal re-runs only un-journaled blocks and reproduces the
    bit-identical metric matrix (journal floats round-trip exactly)."""
    _SWEEP_TL.journal = journal
    best = None
    if journal is not None:
        best = _BestTracker(getattr(evaluator, "is_larger_better", True))
        # seed from EVERY journaled row (not just this call's grids): a
        # post-resume record's `best` annotation must account for pre-
        # kill blocks, including — on the distributed scheduler path,
        # where each worker's run_sweep sees only its own block — the
        # grids other workers completed
        for g, row in journal.rows():
            best.note(g, row)
    _SWEEP_TL.best = best
    try:
        return _run_sweep(est, grids, X, y, folds, evaluator, ctx, sharding)
    finally:
        _SWEEP_TL.journal = None
        _SWEEP_TL.best = None


def _run_sweep(est, grids: List[Dict], X, y, folds, evaluator, ctx,
               sharding=None) -> List[List[float]]:
    handler = _dispatch(est)
    if handler is None:
        return _sweep_generic(est, grids, X, y, folds, evaluator, ctx)
    # no device kernel for this evaluator (or a multiclass one and nobody
    # stated the number of classes) → batched fits, host metrics
    metric_fn = (make_device_metric(
        evaluator, n_classes=stated_n_classes(est, ctx))
        or HostMetricFallback(evaluator))
    # the cache entry RETAINS the keying objects so `is` comparisons are
    # safe (an id()-only key could false-hit after GC address reuse): a
    # FitContext reused with different X/y/folds (public run_sweep callers)
    # must not silently get the first call's arrays back
    def _same_data(key_objs) -> bool:
        kX, ky, kfolds = key_objs
        return (kX is X and ky is y and len(kfolds) == len(folds)
                and all(a is c and b is d
                        for (a, b), (c, d) in zip(kfolds, folds)))

    # families arrive together on the selector's thread pool: ONE of them
    # prepares the sweep's shared data, the others wait and reuse it (two
    # that both miss would each reset the binned-X cache under the other).
    # A lock of its own: `_BIN_CACHE_LOCK` is held through the tree
    # families' binning, which a linear family has no reason to wait for
    with _SWEEP_DATA_LOCK:
        cached = getattr(ctx, "_sweep_data_cache", None) if ctx is not None else None
        if cached is not None and _same_data(cached[0]):
            _, X, y, W, V = cached  # same selector fit: reuse padded/sharded set
        else:
            key_objs = (X, y, list(folds))
            W = upload("sweep:folds", np.stack([tr for tr, _ in folds]))
            V = upload("sweep:folds", np.stack([va for _, va in folds]))
            if ctx is not None and ctx.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                from transmogrifai_tpu.parallel.mesh import DATA_AXIS
                data_size = ctx.mesh.shape.get(DATA_AXIS, 1)
                n = int(np.asarray(y).shape[0])
                if data_size > 1:
                    # every fit/metric is weight-masked, so rows pad with zero
                    # weight in ALL folds — sharding never silently degrades to
                    # replication on uneven row counts. Tree binning must ignore
                    # the pad rows (see _binned_cache); bootstrap streams are
                    # prefix-stable across the padded shape.
                    ctx._sweep_n_rows = n
                    pad = (-n) % data_size
                    if pad:
                        X = jnp.concatenate(
                            [X, jnp.zeros((pad, X.shape[1]), X.dtype)])
                        y = jnp.concatenate([y, jnp.zeros((pad,), y.dtype)])
                        W = jnp.concatenate(
                            [W, jnp.zeros((W.shape[0], pad), W.dtype)], axis=1)
                        V = jnp.concatenate(
                            [V, jnp.zeros((V.shape[0], pad), V.dtype)], axis=1)
                    mesh = ctx.mesh
                    # the waiters need exactly these arrays: they stall
                    # behind the placement on purpose, not by accident
                    # conc-ok: C003 (waiters reuse the placed arrays)
                    X = jax.device_put(X, NamedSharding(mesh, P(DATA_AXIS, None)))
                    # conc-ok: C003 (waiters reuse the placed arrays)
                    y = jax.device_put(y, NamedSharding(mesh, P(DATA_AXIS)))
                    # conc-ok: C003 (waiters reuse the placed arrays)
                    W = jax.device_put(W, NamedSharding(mesh, P(None, DATA_AXIS)))
                    # conc-ok: C003 (waiters reuse the placed arrays)
                    V = jax.device_put(V, NamedSharding(mesh, P(None, DATA_AXIS)))
            if ctx is not None:
                ctx._sweep_data_cache = (key_objs, X, y, W, V)
                # the binned-X cache is per-data too. Which columns are
                # 0/1 indicators (the tree histograms' layout) is read off
                # the matrix HERE, before any family has a program in the
                # device's queue: from a tree family's thread the reduction
                # would wait behind the logistic block, and the bin edges
                # behind it
                # conc-ok: C003 (waiters reuse the layout read here)
                ctx._sweep_bin_cache = {"indicator": indicator_columns(X)}
    return handler(est, grids, X, y, W, V, metric_fn, ctx, sharding)

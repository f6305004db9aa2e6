"""SanityChecker & MinVarianceFilter: automated feature validation.

Reference parity: `core/.../preparators/SanityChecker.scala:232-656`
(sampling, Pearson/Spearman label correlations, full feature-feature
correlation matrix, categorical contingency stats — Cramér's V, pointwise
mutual information, mutual information, association-rule max confidence —
drop rules from `DerivedFeatureFilterUtils.scala:355-385`, defaults
`SanityChecker.scala:561-578`), statistics math from
`utils/.../stats/OpStatistics.scala:180-320`, and
`MinVarianceFilter.scala:58,145`.

TPU-first: moments, label correlation and the feature-feature Gram matrix
are ONE fused device pass over (n, d+1) — `Z^T Z` rides the MXU and every
term is a row-axis sum (`psum`-ready under a data-sharded mesh). Spearman
reuses the same pass over host-ranked columns. The row sample is a device
gather, and every categorical group's contingency table is a slice of ONE
whole-number-exact product `X^T onehot(label)` on the device: the encoded
matrix stays there, only (d,) moments, the Gram and a (d, labels) table
cross. Drop decisions (data-dependent shapes) resolve on host at fit
time; the fitted model is a static-index column gather.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.nn
import jax.numpy as jnp
import numpy as np

log = logging.getLogger(__name__)

from transmogrifai_tpu import types as T
from transmogrifai_tpu.data.columns import Column
from transmogrifai_tpu.data.metadata import VectorMetadata
from transmogrifai_tpu.obs.trace import TRACER, pull, upload
from transmogrifai_tpu.stages.base import Estimator, FitContext, Transformer

# reference defaults (SanityChecker.scala:561-578)
CHECK_SAMPLE = 1.0
SAMPLE_LOWER_LIMIT = 1_000
SAMPLE_UPPER_LIMIT = 1_000_000
MAX_CORRELATION = 0.95
MAX_FEATURE_CORR = 0.99
MIN_CORRELATION = 0.0
MIN_VARIANCE = 1e-5
MAX_CRAMERS_V = 0.95
MAX_RULE_CONFIDENCE = 1.0       # 1.0/1.0 = rule-confidence check off
MIN_REQUIRED_RULE_SUPPORT = 1.0


@dataclass
class ColumnStats:
    name: str
    mean: float
    variance: float
    min: float
    max: float
    corr_label: float
    cramers_v: Optional[float]
    mutual_info: Optional[float] = None
    max_rule_confidence: Optional[float] = None
    support: Optional[float] = None
    dropped: List[str] = field(default_factory=list)

    def to_json(self) -> Dict:
        return {
            "name": self.name, "mean": self.mean, "variance": self.variance,
            "min": self.min, "max": self.max, "corrLabel": self.corr_label,
            "cramersV": self.cramers_v, "mutualInfo": self.mutual_info,
            "maxRuleConfidence": self.max_rule_confidence,
            "support": self.support, "dropped": self.dropped,
        }


@dataclass
class CategoricalGroupStats:
    """Per categorical group (OpStatistics.ContingencyStats analogue)."""

    group: str
    cramers_v: float
    mutual_info: float
    pointwise_mutual_info: Dict[str, List[float]]
    max_rule_confidences: List[float]
    supports: List[float]

    def to_json(self) -> Dict:
        return {
            "group": self.group, "cramersV": self.cramers_v,
            "mutualInfo": self.mutual_info,
            "pointwiseMutualInfo": self.pointwise_mutual_info,
            "maxRuleConfidences": self.max_rule_confidences,
            "supports": self.supports,
        }


@dataclass
class SanityCheckerSummary:
    """Persisted fit diagnostics (SanityCheckerMetadata analogue)."""

    n_rows: int
    stats: List[ColumnStats]
    kept_indices: List[int]
    dropped_indices: List[int]
    correlation_type: str = "pearson"
    sample_fraction: float = 1.0
    categorical_stats: List[CategoricalGroupStats] = field(default_factory=list)

    def to_json(self) -> Dict:
        return {
            "n_rows": self.n_rows,
            "stats": [s.to_json() for s in self.stats],
            "kept": self.kept_indices, "dropped": self.dropped_indices,
            "correlationType": self.correlation_type,
            "sampleFraction": self.sample_fraction,
            "categoricalStats": [c.to_json() for c in self.categorical_stats],
        }


@jax.jit
def _column_reductions(X: jnp.ndarray, y: Optional[jnp.ndarray] = None):
    """One fused pass (one program: op by op, `X * X` is a buffer the
    size of X): per-column moments (+ label terms when y given —
    correlations now come from the `_corr_matrix` Gram pass, so the
    checker calls this with y=None).

    Every term is a sum over rows → shard the row axis, `psum` the sums.
    """
    n = X.shape[0]
    out = {"n": n, "sx": X.sum(0), "sxx": (X * X).sum(0),
           "min": X.min(0) if n else jnp.zeros(X.shape[1]),
           "max": X.max(0) if n else jnp.zeros(X.shape[1])}
    if y is not None:
        out.update({"sy": y.sum(), "syy": (y * y).sum(), "sxy": X.T @ y})
    return out


def _corr_matrix(Z: jnp.ndarray) -> np.ndarray:
    """Full correlation matrix of (n, k) via one Gram matmul (MXU path;
    rows sharded → psum). Columns with zero variance correlate as 0."""
    n = Z.shape[0]
    mean = Z.mean(0)
    Zc = Z - mean
    cov = pull("sanity:corr", Zc.T @ Zc) / max(n - 1, 1)
    sd = np.sqrt(np.maximum(np.diag(cov), 0.0))
    denom = np.outer(sd, sd)
    with np.errstate(divide="ignore", invalid="ignore"):
        corr = np.where(denom > 0, np.asarray(cov) / denom, 0.0)
    return corr


_WIDE_D = 8192  # feature count beyond which the (d, d) corr never materializes


def _corr_label_and_hits_blocked(Cx: jnp.ndarray, cy: jnp.ndarray,
                                 thr: float, block: Optional[int] = None):
    """Wide-feature-axis path (SURVEY.md §5.7): label-correlation vector +
    the SPARSE set of feature-feature pairs with |corr| > thr, computed in
    column blocks of the Gram product — the full (d, d) matrix (17G entries
    at the 2^17 hashing limit) never exists. Each block is one MXU matmul
    with the row axis `psum`-ready; hit pairs extract on device via a
    fixed-size nonzero so only O(hits) crosses back to host.

    Returns (corr_y (d,), {i: [(j, corr_ij), ...] with j < i}).
    """
    n, d = Cx.shape
    mean = Cx.mean(0)
    Zc = Cx - mean
    sd = jnp.sqrt(jnp.maximum((Zc * Zc).sum(0), 0.0))
    U = jnp.where(sd > 0, Zc / sd, 0.0)
    yc = cy - cy.mean()
    ysd = jnp.sqrt(jnp.maximum((yc * yc).sum(), 0.0))
    uy = jnp.where(ysd > 0, yc / ysd, 0.0)
    corr_y = np.asarray(pull("sanity:corr", U.T @ uy), dtype=np.float64)

    if block is None:  # ≤ ~128M-entry (512MB f32) block products
        block = max(128, min(d, (1 << 27) // max(d, 1)))
    cap = 16 * block  # duplicates are sparse; truncation is logged

    @jax.jit
    def block_hits(Ub, a):  # Ub (n, block), a = column offset
        C = Ub.T @ U  # (block, d)
        rows = a + jnp.arange(Ub.shape[1])[:, None]
        cols = jnp.arange(d)[None, :]
        mask = (jnp.abs(C) > thr) & (cols < rows)
        ri, ci = jnp.nonzero(mask, size=cap, fill_value=-1)
        return ri, ci, C[ri, ci], mask.sum()

    pairs: Dict[int, List[Tuple[int, float]]] = {}
    pad = (-d) % block
    Upad = jnp.pad(U, ((0, 0), (0, pad))) if pad else U
    for a in range(0, d, block):
        ri, ci, vals, total = block_hits(
            jax.lax.dynamic_slice_in_dim(Upad, a, block, 1), a)
        ri, ci, vals, total = pull("sanity:corr", (ri, ci, vals, total))
        k = int((ri >= 0).sum())
        if int(total) > cap:
            log.warning(
                "feature-feature corr: %d hits in block %d..%d truncated "
                "to %d — raise max_feature_corr or lower the hash width",
                int(total), a, min(a + block, d), cap)
        for t in range(k):
            i, j = int(ri[t]) + a, int(ci[t])
            if i < d:  # pad columns are all-zero and never hit, but guard
                pairs.setdefault(i, []).append((j, float(vals[t])))
    for i in pairs:
        pairs[i].sort()
    return corr_y, pairs


def _rank_transform(A: np.ndarray) -> np.ndarray:
    """Average-tie ranks per column (Spearman = Pearson over ranks)."""
    import pandas as pd
    return pd.DataFrame(A).rank(method="average").to_numpy(dtype=np.float32)


def _label_codes(y: np.ndarray, max_card: int,
                 force: Optional[bool] = None) -> Optional[Tuple[np.ndarray, int]]:
    """(int32 code of every row's label level, number of levels) for the
    contingency tests, or None if the label is not categorical.
    `force=True` treats the (rounded) label as categorical regardless of
    the integrality/cardinality heuristics (categoricalLabel param)."""
    if force is False:
        return None
    yi = np.round(y).astype(np.int64)
    if force is not True and not np.allclose(y, yi, atol=1e-6):
        return None
    levels, codes = np.unique(yi, return_inverse=True)
    if len(levels) < 2 or (force is not True and len(levels) > max_card):
        return None
    return codes.astype(np.int32), len(levels)


@jax.jit
def _take_rows(X: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """The sampled rows, gathered where the matrix lives (`idx` sorted,
    distinct and in range: `_sample_rows` draws it so)."""
    return X.at[idx].get(mode="promise_in_bounds", unique_indices=True,
                         indices_are_sorted=True)


# rows one float32 count can take exactly: every whole number up to 2^24
_COUNT_EXACT_ROWS = 1 << 24


@partial(jax.jit, static_argnames=("width", "chunks"))
def _label_counts(X: jnp.ndarray, codes: jnp.ndarray, width: int,
                  chunks: int) -> jnp.ndarray:
    """(d, chunks * width) float32: every column of X summed by the
    label code (below `width`) of its row, `X^T onehot(codes)` as one
    product contracted over the row axis (rows sharded → one `psum`, like
    the Gram). The float32 operands enter at `Precision.HIGHEST` (whole)
    and a one-hot operand picks each value of X out as it is, so a 0/1
    column's sums are counts, exact in the float32 accumulator while they
    stay under 2^24: the rows are cut into `chunks` runs no longer than
    that, each summed into label columns of its own. The product is bound
    by its one read of X, so the rows of columns nobody reads cost
    nothing worth a gather."""
    n = X.shape[0]
    run = -(-n // chunks)
    oh = jax.nn.one_hot(codes + (jnp.arange(n) // run) * width,
                        chunks * width, dtype=X.dtype)
    return jax.lax.dot_general(
        X, oh, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _contingency_counts(X: jnp.ndarray, codes: np.ndarray, n_levels: int,
                        max_card: int) -> np.ndarray:
    """(d, n_levels) float64 table of every column of the device matrix
    against the label: what `X.T.astype(float64) @ onehot` gives on the
    host, to the bit for 0/1 columns. The table that crosses is as wide
    as the next multiple of `max_card` (the most levels an unforced label
    may have), not as the levels this sample happens to hold: a rare
    label in or out of the sample compiles nothing, and the label lanes
    of the product are padded far past that anyway. One shape, one path:
    a sample of more than 2^24 rows (a raised `sample_upper_limit`) is
    the same program with its rows in several runs, whose tables add here
    in float64."""
    step = max(max_card, 1)     # a forced label's levels ignore the limit
    width = -(-n_levels // step) * step
    chunks = max(1, -(-X.shape[0] // _COUNT_EXACT_ROWS))
    counts = pull("sanity:contingency", _label_counts(
        X, upload("sanity:label", codes), width, chunks))
    return counts.astype(np.float64).reshape(
        X.shape[1], chunks, width).sum(axis=1)[:, :n_levels]


def cramers_v(contingency: np.ndarray) -> float:
    """Cramér's V from a levels × labels count table, empty rows/cols
    filtered first (OpStatistics.chiSquaredTest, OpStatistics.scala:188)."""
    cont = contingency[contingency.sum(1) > 0][:, contingency.sum(0) > 0]
    if cont.shape[0] < 2 or cont.shape[1] < 2:
        return 0.0
    n = cont.sum()
    if n == 0:
        return 0.0
    row = cont.sum(axis=1, keepdims=True)
    col = cont.sum(axis=0, keepdims=True)
    expected = row @ col / n
    with np.errstate(divide="ignore", invalid="ignore"):
        chi2 = np.where(expected > 0,
                        (cont - expected) ** 2 / expected, 0.0).sum()
    denom = n * (min(cont.shape) - 1)
    return float(np.sqrt(chi2 / denom)) if denom > 0 else 0.0


def contingency_stats(cont: np.ndarray) -> Dict:
    """PMI / mutual info / association-rule confidences from a levels ×
    labels table (OpStatistics.mutualInfo:234-276, maxConfidences:280-296).
    """
    total = cont.sum()
    row = cont.sum(axis=1)          # per level
    col = cont.sum(axis=0)          # per label
    with np.errstate(divide="ignore", invalid="ignore"):
        pmi = np.where(
            (cont > 0) & (row[:, None] > 0) & (col[None, :] > 0),
            np.log2(np.maximum(cont, 1e-99) * total
                    / np.maximum(row[:, None] * col[None, :], 1e-99)),
            0.0)
        mi = float((pmi * cont / max(total, 1)).sum())
        conf = np.where(row > 0, cont.max(axis=1) / np.maximum(row, 1), 0.0)
    supports = (row / max(total, 1)).tolist()
    pmi_map = {str(j): pmi[:, j].tolist() for j in range(cont.shape[1])}
    return {"cramers_v": cramers_v(cont), "mutual_info": mi,
            "pmi": pmi_map, "max_confidences": conf.tolist(),
            "supports": supports}


class SanityCheckerModel(Transformer):
    """Fitted checker: static column gather of the kept indices."""

    out_type = T.OPVector
    response_aware = True  # inputs stay (label, vector) post-fit

    def __init__(self, indices: Sequence[int], meta: Optional[Dict] = None,
                 summary: Optional[Dict] = None, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.indices = list(int(i) for i in indices)
        self._meta_json = (meta.to_json() if isinstance(meta, VectorMetadata)
                           else meta)
        self.summary = summary

    def device_apply(self, enc, dev):
        X = jnp.asarray(dev[-1])
        return X[:, jnp.asarray(self.indices, dtype=jnp.int32)]

    def output_meta(self) -> Optional[VectorMetadata]:
        if self._meta_json is None:
            return None
        return VectorMetadata.from_json(self._meta_json)

    def get_params(self):
        return {"indices": self.indices, "meta": self._meta_json,
                "summary": self.summary}


class SanityChecker(Estimator):
    """BinaryEstimator(RealNN label, OPVector) → cleaned OPVector.

    Drop rules (DerivedFeatureFilterUtils.scala:355-385): variance below
    `min_variance`; |corr(feature, label)| above `max_correlation`
    (leakage) or below `min_correlation`; |corr| with an EARLIER feature
    column above `max_feature_corr` (duplicates — later column dropped);
    categorical-group Cramér's V above `max_cramers_v`; association-rule
    confidence above `max_rule_confidence` at support above
    `min_required_rule_support`.
    """

    in_types = (T.RealNN, T.OPVector)
    out_type = T.OPVector
    response_aware = True  # slot 0 is the label

    def __init__(self, max_correlation: float = MAX_CORRELATION,
                 min_correlation: float = MIN_CORRELATION,
                 max_feature_corr: float = MAX_FEATURE_CORR,
                 min_variance: float = MIN_VARIANCE,
                 max_cramers_v: float = MAX_CRAMERS_V,
                 max_rule_confidence: float = MAX_RULE_CONFIDENCE,
                 min_required_rule_support: float = MIN_REQUIRED_RULE_SUPPORT,
                 correlation_type: str = "pearson",
                 check_sample: float = CHECK_SAMPLE,
                 sample_lower_limit: int = SAMPLE_LOWER_LIMIT,
                 sample_upper_limit: int = SAMPLE_UPPER_LIMIT,
                 sample_seed: int = 42,
                 remove_bad_features: bool = True,
                 categorical_label: Optional[bool] = None,
                 categorical_label_max_card: int = 30,
                 uid: Optional[str] = None):
        if correlation_type not in ("pearson", "spearman"):
            raise ValueError("correlation_type must be pearson or spearman")
        super().__init__(
            uid=uid, max_correlation=max_correlation,
            min_correlation=min_correlation, max_feature_corr=max_feature_corr,
            min_variance=min_variance, max_cramers_v=max_cramers_v,
            max_rule_confidence=max_rule_confidence,
            min_required_rule_support=min_required_rule_support,
            correlation_type=correlation_type, check_sample=check_sample,
            sample_lower_limit=sample_lower_limit,
            sample_upper_limit=sample_upper_limit, sample_seed=sample_seed,
            remove_bad_features=remove_bad_features,
            categorical_label=categorical_label,
            categorical_label_max_card=categorical_label_max_card)
        self.max_correlation = max_correlation
        self.min_correlation = min_correlation
        self.max_feature_corr = max_feature_corr
        self.min_variance = min_variance
        self.max_cramers_v = max_cramers_v
        self.max_rule_confidence = max_rule_confidence
        self.min_required_rule_support = min_required_rule_support
        self.correlation_type = correlation_type
        self.check_sample = check_sample
        self.sample_lower_limit = sample_lower_limit
        self.sample_upper_limit = sample_upper_limit
        self.sample_seed = sample_seed
        self.remove_bad_features = remove_bad_features
        self.categorical_label = categorical_label
        self.categorical_label_max_card = categorical_label_max_card

    # ------------------------------------------------------------------ #

    def _sample_rows(self, n: int) -> Optional[np.ndarray]:
        """Row subsample for the statistics pass (checkSample/limits,
        SanityChecker.scala:60-92); None = use everything."""
        target = n
        if self.check_sample < 1.0:
            target = int(n * self.check_sample)
        target = min(target, self.sample_upper_limit)
        target = max(target, min(n, self.sample_lower_limit))
        if target >= n:
            return None
        rng = np.random.default_rng(self.sample_seed)
        return np.sort(rng.choice(n, size=target, replace=False))

    def fit_model(self, cols: Sequence[Column], ctx: FitContext) -> Transformer:
        # four phases, each a span under the stage's `stage:fit:*`:
        # moments (the row sample, raw moments), corr (the Gram pass),
        # contingency (categorical groups against the label), decide
        # (the drop rules)
        label_col, vec_col = cols
        with TRACER.span("sanity:moments", category="sanity") as sp:
            y_np = np.asarray(label_col.data["value"], dtype=np.float64)
            X_all = vec_col.device_value()
            n_total = X_all.shape[0]

            # the sample is taken where the matrix lives: a device
            # gather of the sorted index (4 B a row go up), or a host
            # fancy index of a host array
            sample_idx = self._sample_rows(n_total)
            if sample_idx is None:
                X_rows, sampled = X_all, "whole"
            elif isinstance(X_all, jax.Array):
                X_rows, sampled = _take_rows(X_all, upload(
                    "sanity:sample", sample_idx.astype(np.int32))), "device"
            else:
                X_rows, sampled = X_all[sample_idx], "host"
            sp.set(sample=sampled)
            if sample_idx is not None:
                y_np = y_np[sample_idx]
            X_dev = upload("sanity:sample", X_rows)
            n, d = X_dev.shape

            # Spearman = Pearson over average-tie ranks (host rank
            # transform feeding the identical device passes, the one
            # caller that reads the sampled rows on the host); `Cx/cy`
            # are the correlation inputs, raw-X moments are reported in
            # the stats either way
            spearman = self.correlation_type == "spearman"
            if spearman:
                Cx = upload("sanity:sample", _rank_transform(
                    pull("sanity:matrix", X_rows)))
                cy = upload("sanity:sample",
                            _rank_transform(y_np[:, None])[:, 0])
            else:
                Cx = X_dev
                cy = upload("sanity:sample", y_np.astype(np.float32))

            need_ff = self.max_feature_corr < 1.0
            if need_ff:  # corr comes from the Gram pass; raw moments here
                red = pull("sanity:moments", _column_reductions(X_dev))
            else:        # label terms ride the same single reduction pass
                redc = pull("sanity:moments", _column_reductions(Cx, cy))
                red = (pull("sanity:moments", _column_reductions(X_dev))
                       if spearman else redc)
            mean = red["sx"] / max(n, 1)
            var = (red["sxx"] - n * mean ** 2) / max(n - 1, 1)
            var = np.maximum(var, 0.0)
        hit_pairs: Dict[int, List[Tuple[int, float]]] = {}
        with TRACER.span("sanity:corr", category="sanity"):
            if need_ff and d > _WIDE_D:
                # wide-X: blocked Gram — label corr + sparse duplicate
                # pairs, no (d, d) materialization (SURVEY.md §5.7)
                corr, hit_pairs = _corr_label_and_hits_blocked(
                    Cx, cy, self.max_feature_corr)
                feat_corr = None
            elif need_ff:
                # full corr matrix of [X | y]: ONE Gram matmul on the MXU
                corr_all = _corr_matrix(
                    jnp.concatenate([Cx, cy[:, None]], 1))
                corr = corr_all[:d, d]
                feat_corr = corr_all[:d, :d]
            else:
                # duplicates check disabled → O(n·d) label terms suffice
                cmean = redc["sx"] / max(n, 1)
                cvar = np.maximum(
                    (redc["sxx"] - n * cmean ** 2) / max(n - 1, 1), 0.0)
                y_mean = redc["sy"] / max(n, 1)
                y_var = max(
                    (redc["syy"] - n * y_mean ** 2) / max(n - 1, 1), 0.0)
                cov = (redc["sxy"] - n * cmean * y_mean) / max(n - 1, 1)
                denom = np.sqrt(cvar * y_var)
                with np.errstate(divide="ignore", invalid="ignore"):
                    corr = np.where(denom > 0, cov / denom, 0.0)
                feat_corr = None

        meta = vec_col.meta
        names = (meta.column_names() if meta is not None
                 else [f"col_{i}" for i in range(d)])

        # categorical groups → contingency stats vs a categorical label
        group_stats: Dict[int, Tuple[str, Dict]] = {}
        cat_groups: List[CategoricalGroupStats] = []
        with TRACER.span("sanity:contingency", category="sanity") as sp:
            coded = None if meta is None else _label_codes(
                y_np, self.categorical_label_max_card,
                force=self.categorical_label)
            # no label levels: a label of too many (or fractional)
            # values, every decision is from the moments and the label
            # correlations
            groups: Dict[str, List[int]] = {}
            if coded is not None:
                for i, c in enumerate(meta.columns):
                    if c.indicator_value is not None:
                        groups.setdefault(c.grouping_key(), []).append(i)
                sp.set(groups=len(groups))
            sp.set(categorical_label=coded is not None,
                   tables="device" if groups else "none")
            if groups:
                # the rows the moments and the Gram saw, every column
                counts = _contingency_counts(
                    X_dev, *coded, self.categorical_label_max_card)
                for key, idxs in groups.items():
                    cs = contingency_stats(counts[idxs])
                    cat_groups.append(CategoricalGroupStats(
                        group=key, cramers_v=cs["cramers_v"],
                        mutual_info=cs["mutual_info"],
                        pointwise_mutual_info=cs["pmi"],
                        max_rule_confidences=cs["max_confidences"],
                        supports=cs["supports"]))
                    for li, i in enumerate(idxs):
                        group_stats[i] = (key, {
                            "cramers_v": cs["cramers_v"],
                            "mutual_info": cs["mutual_info"],
                            "conf": cs["max_confidences"][li],
                            "support": cs["supports"][li]})

        with TRACER.span("sanity:decide", category="sanity",
                         encoded_width=d) as sp:
            kept, stats = self._decide(
                var, mean, red, corr, feat_corr, hit_pairs, group_stats,
                names)
            sp.set(selected_width=len(kept))

        kept_set = set(kept)
        summary = SanityCheckerSummary(
            n_rows=n, stats=stats, kept_indices=kept,
            dropped_indices=[i for i in range(d) if i not in kept_set],
            correlation_type=self.correlation_type,
            sample_fraction=n / max(n_total, 1),
            categorical_stats=cat_groups)
        sel_meta = meta.select(kept) if meta is not None else None
        return SanityCheckerModel(kept, meta=sel_meta, summary=summary.to_json())

    def _decide(self, var, mean, red, corr, feat_corr, hit_pairs,
                group_stats, names) -> Tuple[List[int], List[ColumnStats]]:
        """The drop rules over the gathered statistics: (kept column
        indices, every column's stats with its drop reasons)."""
        d = len(names)
        # feature-feature duplicates: vectorized candidate pairs, then the
        # "later column drops" scan ("dropping the later features",
        # DerivedFeatureFilterUtils:376). The wide path already produced
        # `hit_pairs`; the dense path extracts them from the matrix.
        if feat_corr is not None and self.max_feature_corr < 1.0 and d > 1:
            hit = np.abs(np.tril(feat_corr, k=-1)) > self.max_feature_corr
            for i in np.flatnonzero(hit.any(axis=1)):
                hit_pairs[int(i)] = [(int(j), float(feat_corr[i, j]))
                                     for j in np.flatnonzero(hit[i])]

        stats: List[ColumnStats] = []
        kept: List[int] = []
        dropped_so_far: set = set()
        for i in range(d):
            reasons: List[str] = []
            if var[i] < self.min_variance:
                reasons.append(f"variance {var[i]:.2e} < {self.min_variance}")
            ac = abs(float(corr[i]))
            if ac > self.max_correlation:
                reasons.append(f"label corr {ac:.3f} > {self.max_correlation}")
            elif self.min_correlation > 0 and ac < self.min_correlation:
                reasons.append(f"label corr {ac:.3f} < {self.min_correlation}")
            for j, cij in hit_pairs.get(i, ()):
                if j not in dropped_so_far:
                    reasons.append(
                        f"corr {cij:.3f} with column "
                        f"{names[j]!r} > {self.max_feature_corr}")
                    break
            gs = group_stats.get(i)
            gv = mi = conf = sup = None
            if gs is not None:
                key, s = gs
                gv, mi = s["cramers_v"], s["mutual_info"]
                conf, sup = s["conf"], s["support"]
                if gv > self.max_cramers_v:
                    reasons.append(f"cramersV {gv:.3f} > {self.max_cramers_v}")
                if (conf > self.max_rule_confidence
                        and sup > self.min_required_rule_support):
                    reasons.append(
                        f"rule confidence {conf:.3f} > "
                        f"{self.max_rule_confidence} at support {sup:.3f}")
            stats.append(ColumnStats(
                name=names[i], mean=float(mean[i]), variance=float(var[i]),
                min=float(red["min"][i]), max=float(red["max"][i]),
                corr_label=float(corr[i]), cramers_v=gv, mutual_info=mi,
                max_rule_confidence=conf, support=sup, dropped=reasons))
            if not reasons or not self.remove_bad_features:
                kept.append(i)
            elif reasons:
                dropped_so_far.add(i)

        if not kept:  # never drop everything (reference keeps result usable)
            kept = list(range(d))
            for s in stats:
                s.dropped.append("retained: all columns flagged")
        return kept, stats


class MinVarianceFilterModel(SanityCheckerModel):
    pass


class MinVarianceFilter(Estimator):
    """Unary OPVector → OPVector: drop near-constant columns
    (MinVarianceFilter.scala — the unlabeled SanityChecker)."""

    in_types = (T.OPVector,)
    out_type = T.OPVector

    def __init__(self, min_variance: float = 1e-5, uid: Optional[str] = None):
        super().__init__(uid=uid, min_variance=min_variance)
        self.min_variance = min_variance

    def fit_model(self, cols: Sequence[Column], ctx: FitContext) -> Transformer:
        vec_col = cols[0]
        X = jnp.asarray(vec_col.device_value())
        n, d = X.shape
        mean = np.asarray(X.mean(0))
        var = np.asarray(((X - mean) ** 2).sum(0)) / max(n - 1, 1)
        kept = [i for i in range(d) if var[i] >= self.min_variance]
        if not kept:
            kept = list(range(d))
        meta = vec_col.meta
        sel_meta = meta.select(kept) if meta is not None else None
        summary = {"n_rows": int(n), "kept": kept,
                   "dropped": [i for i in range(d) if var[i] < self.min_variance]}
        return MinVarianceFilterModel(kept, meta=sel_meta, summary=summary)

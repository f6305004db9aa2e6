"""CompiledScorer: the fitted DAG as fused XLA program segments.

This is the TPU replacement for both the reference's fused row transform
(`FitStagesUtil.applyOpTransformations`, FitStagesUtil.scala:96-119) and its
Spark-free MLeap scoring path (`local/.../OpWorkflowModelLocal.scala:79-122`):

- host phase (per batch): materialize raw columns, call each jittable
  stage's `host_prepare` (string → ids etc.)
- device phase: consecutive jittable stages compile into ONE `jax.jit`
  program — XLA fuses imputation, one-hot, concat, and the model matmul;
  with a mesh, the batch axis shards over devices.

Topologies where a HostTransformer consumes a device-produced feature
(e.g. `(sibSp + parCh).alias(...)`) split the plan into alternating
host/device SEGMENTS: each device segment is still one fused XLA program,
and device outputs materialize to host columns only when a host stage
actually reads them. A pipeline with no such crossing keeps the single
fused program.

Roofline scoring (PR 13): tabular scoring is memory-bound, so the hot
path is engineered against the HBM roofline rather than MFU:

- each device segment's program returns ONLY the outputs something
  downstream actually reads (a later host segment or a result feature)
  — intermediates stay fusion-eligible instead of being forced into
  HBM as program outputs;
- plans with a single trailing device segment (the overwhelmingly
  common shape — host string work happens in `host_prepare`, not in
  host stages) score through `score_padded`'s fused fast path: ONE
  device dispatch per call, accounted per segment in
  `analysis.retrace.DISPATCHES` and as `device_dispatch` trace events
  (bytes in/out per dispatch — the numerator of the achieved-bandwidth
  roofline `bench.py` reports as `scoring_hbm_frac`);
- `quant=ScoringQuant("int8"|"int4")` turns on end-to-end quantized
  inference: the request matrix ships on a per-batch affine uint8 wire
  (int4 packs two features per byte — same nibble layout as
  `data/feature_cache.QuantPlan`/`parallel/bigdata._unpack_dequant`)
  with dequant fused into the scoring program, and fitted tables
  compute from narrowed dtypes (`Transformer.narrow_device_constants`:
  f16 tree thresholds, uint8 bin ids, bf16 linear weights). Stated
  tolerance per feature: scale/2 = (hi − lo)/(2·(2^bits − 1)) on the
  batch's own [lo, hi] range; masks ride the wire as exact uint8 0/1.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu import types as T
from transmogrifai_tpu.analysis.retrace import DISPATCHES
from transmogrifai_tpu.data.columns import Column
from transmogrifai_tpu.data.dataset import Dataset
from transmogrifai_tpu.features.dag import topological_layers
from transmogrifai_tpu.obs.trace import TRACER, add_event
from transmogrifai_tpu.stages.base import (
    HOST_KINDS as _HOST_KINDS, FeatureGeneratorStage, HostTransformer,
    Transformer, is_host_stage)


@dataclass(frozen=True)
class ScoringQuant:
    """Quantized-inference mode for the compiled scorer: ``"int8"``
    ships 1 byte/element on the wire, ``"int4"`` half that (two
    features per byte). Per-feature max abs error is scale/2 with
    scale = (hi − lo)/(2^bits − 1).

    ``calibrated=False`` (batch-relative, the PR-13 wire): [lo, hi] is
    each BATCH's own value range — a request quantizes against its
    batchmates, so repeat scoring of one row in different batches
    agrees within the stated tolerance, not bitwise.

    ``calibrated=True`` (``"int8-calibrated"``/``"int4-calibrated"``):
    [lo, hi] comes from the per-feature ranges captured at FIT time and
    persisted with the model (``WorkflowModel.quant_calibration``, the
    fingerprint pass's range sidecar) — scale/lo are constants of the
    model, quantization is a single vectorized pass with no per-batch
    range scan, and repeat scores of one row are BIT-STABLE across
    batch compositions. Serving values outside the training range clip
    to it (the fleet-wide contract: the model never saw them either).
    A model with no captured calibration falls back batch-relative."""

    mode: str = "int8"
    calibrated: bool = False

    def __post_init__(self) -> None:
        if self.mode not in ("int8", "int4"):
            raise ValueError(
                f"quantized scoring mode must be 'int8' or 'int4', "
                f"got {self.mode!r}")

    @property
    def bits(self) -> int:
        return 4 if self.mode == "int4" else 8

    @staticmethod
    def resolve(q: Any) -> Optional["ScoringQuant"]:
        """None | "int8[-calibrated]" | "int4[-calibrated]" |
        ScoringQuant -> Optional[ScoringQuant]."""
        if q is None or isinstance(q, ScoringQuant):
            return q
        s = str(q)
        if s.endswith("-calibrated"):
            return ScoringQuant(s[:-len("-calibrated")], calibrated=True)
        return ScoringQuant(s)


# -- quantized request wire -------------------------------------------------- #

def _pack4_np(q: np.ndarray) -> np.ndarray:
    """(n, d) uint8 in [0,15] -> (n, ceil(d/2)) uint8; feature 2j in the
    low nibble, 2j+1 high — the `data/feature_cache._pack4` layout, so
    the device unpack below and `parallel/bigdata._unpack_dequant` agree
    on the wire format."""
    n, d = q.shape
    if d % 2:
        q = np.concatenate([q, np.zeros((n, 1), np.uint8)], axis=1)
    return (q[:, 0::2] | (q[:, 1::2] << 4)).astype(np.uint8)


def quantize_leaf(arr: np.ndarray, bits: int,
                  lo: Optional[np.ndarray] = None,
                  hi: Optional[np.ndarray] = None
                  ) -> Dict[str, np.ndarray]:
    """Host half of the quantized wire: per-feature affine uint8 of one
    (n,) or (n, d) float leaf. NaN quantizes to lo (uint8 casts of NaN
    are platform-undefined), values outside [lo, hi] clip to the range
    bounds. The "q1" key marks a 1-D leaf so the device side restores
    the original rank.

    With ``lo``/``hi`` given (CALIBRATED ranges captured at fit time),
    the batch's own min/max pass is skipped entirely and the affine
    constants are batch-independent — repeat scores are bit-stable
    across batch compositions. Without them, [lo, hi] is the batch's
    own finite range (a single ±inf must not degenerate the fit and
    corrupt its finite batchmates)."""
    a = np.asarray(arr, np.float32)
    one_d = a.ndim == 1
    if one_d:
        a = a[:, None]
    if lo is None or hi is None:
        import warnings
        with np.errstate(invalid="ignore"), warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            fin = np.where(np.isfinite(a), a, np.nan)
            lo = np.nanmin(fin, axis=0) if a.shape[0] \
                else np.zeros(a.shape[1])
            hi = np.nanmax(fin, axis=0) if a.shape[0] \
                else np.zeros(a.shape[1])
        lo = np.where(np.isfinite(lo), lo, 0.0).astype(np.float32)
        hi = np.where(np.isfinite(hi), hi, lo).astype(np.float32)
    else:
        lo = np.asarray(lo, np.float32).reshape(-1)
        hi = np.asarray(hi, np.float32).reshape(-1)
    qmax = float((1 << bits) - 1)
    scale = np.where(hi > lo, (hi - lo) / qmax, 1.0).astype(np.float32)
    q = np.rint((a - lo) / scale)
    q = np.where(np.isnan(q), 0.0, q)
    q = np.clip(q, 0.0, qmax).astype(np.uint8)
    if bits == 4:
        q = _pack4_np(q)
    return {("q1" if one_d else "q"): q, "scale": scale, "lo": lo}


def dequantize_leaf(wire: Dict[str, Any], bits: int):
    """Device half (pure jnp, traced INSIDE the scoring program so the
    dequant fuses with the first consumer): affine x = q·scale + lo,
    unpacking int4 nibbles first. Mirrors `bigdata._unpack_dequant`."""
    one_d = "q1" in wire
    q = wire["q1"] if one_d else wire["q"]
    scale, lo = wire["scale"], wire["lo"]
    d = scale.shape[0]
    if bits == 4:
        lo_nib = q & jnp.uint8(0x0F)
        hi_nib = (q >> 4).astype(jnp.uint8)
        q = jnp.stack([lo_nib, hi_nib], axis=-1) \
            .reshape(q.shape[0], -1)[:, :d]
    x = q.astype(jnp.float32) * scale + lo
    return x[:, 0] if one_d else x


_WIRE_KEYS = ({"q", "scale", "lo"}, {"q1", "scale", "lo"})


def quantize_wire(tree: Any, bits: int,
                  ranges: Optional[Dict[str, Any]] = None) -> Any:
    """Structure-preserving wire form of a host device-input pytree:
    float numpy leaves become affine uint8 wire dicts, "mask" leaves
    (exact 0/1 floats by the Column contract) become exact uint8, and
    anything already on device (jax arrays from an earlier segment)
    passes through untouched.

    ``ranges`` maps column uid (the tree's top-level keys) to
    ``{"lo": [...], "hi": [...]}`` calibrated fit-time ranges: a leaf
    whose uid has a matching-width entry quantizes against the FIXED
    range (bit-stable across batches); others fall back to the
    batch-relative pass."""
    def leaf_ranges(rng, width: int):
        if rng is None:
            return None, None
        lo = np.asarray(rng.get("lo"), np.float32).reshape(-1)
        hi = np.asarray(rng.get("hi"), np.float32).reshape(-1)
        if lo.shape[0] != width or hi.shape[0] != width:
            return None, None  # stale calibration: batch-relative leaf
        return lo, hi

    def walk(node, key=None, rng=None):
        if isinstance(node, dict):
            return {k: walk(v, k,
                            (ranges.get(k) if ranges is not None
                             and k in ranges else rng))
                    for k, v in node.items()}
        if isinstance(node, np.ndarray) and node.dtype.kind == "f":
            if key == "mask":
                return node.astype(np.uint8)
            if node.ndim in (1, 2):
                width = 1 if node.ndim == 1 else node.shape[1]
                lo, hi = leaf_ranges(rng, width)
                return quantize_leaf(node, bits, lo=lo, hi=hi)
        return node
    return walk(tree)


def dequantize_wire(tree: Any, bits: int) -> Any:
    """Inverse walk, traced inside the jitted program: wire dicts
    dequantize, uint8 mask leaves cast back to the f32 0/1 contract,
    device-resident leaves pass through."""
    def walk(node):
        if isinstance(node, dict):
            if set(node) in _WIRE_KEYS:
                return dequantize_leaf(node, bits)
            return {k: walk(v) for k, v in node.items()}
        if getattr(node, "dtype", None) == np.uint8:
            return node.astype(jnp.float32)
        return node
    return walk(tree)


def _tree_nbytes(tree: Any) -> int:
    """Total array bytes in a pytree (the wire/HBM traffic a dispatch
    ships and returns — the roofline numerator per call)."""
    return int(sum(getattr(leaf, "nbytes", 0)
                   for leaf in jax.tree_util.tree_leaves(tree)))


def pad_dataset(dataset: Dataset, target_rows: int) -> Dataset:
    """Pad a Dataset to `target_rows` by repeating its last row.

    Shape-bucket discipline: the serving batcher and the streaming
    ragged-tail path never hand the compiled scorer a novel batch shape —
    they pad up to an already-compiled bucket and slice the result back.
    Repeating a REAL row (instead of synthesizing nulls) guarantees the
    pad rows take the exact host-encode path the valid rows take, so
    padding can never introduce a new code path or dtype."""
    n = len(dataset)
    if target_rows < n:
        raise ValueError(f"cannot pad {n} rows down to {target_rows}")
    if target_rows == n:
        return dataset
    if n == 0:
        raise ValueError("cannot pad an empty dataset (no row to repeat)")
    pad_idx = np.full(target_rows - n, n - 1, dtype=np.int64)
    return Dataset.concat([dataset, dataset.take(pad_idx)])


def slice_result_tree(value: Any, start: int, stop: int) -> Any:
    """Slice every batch-leading array leaf of a scoring result pytree
    (dicts of arrays, Prediction dicts, bare arrays) to rows
    [start, stop) — the inverse of batch coalescing/padding."""
    if isinstance(value, dict):
        return {k: slice_result_tree(v, start, stop)
                for k, v in value.items()}
    if getattr(value, "ndim", 0) >= 1:
        return value[start:stop]
    return value


def _column_from_device(ftype: type, dev) -> Column:
    """Wrap a device pytree back into a host Column (segment boundary)."""
    if isinstance(dev, dict) and "prediction" in dev:
        return Column(T.Prediction,
                      {k: np.asarray(v) for k, v in dev.items()})
    if isinstance(dev, dict) and "value" in dev:
        return Column(ftype, {
            "value": np.asarray(dev["value"], dtype=np.float64),
            "mask": np.asarray(dev["mask"]) > 0.5})
    return Column(T.OPVector, np.asarray(dev))


class CompiledScorer:
    def __init__(self, model, sharding: Optional[Any] = None,
                 quant: Any = None):
        self.model = model
        # optional jax.sharding.NamedSharding for the batch (row) axis:
        # raw device inputs are placed with it, so the fused program's
        # elementwise/encode work shards across the mesh and XLA inserts
        # any cross-shard collectives (batch scoring is embarrassingly
        # row-parallel, so there are none in practice)
        self.sharding = sharding
        # quantized inference mode (module docstring): request matrix on
        # the narrow wire, fitted tables in narrowed dtypes
        self.quant = ScoringQuant.resolve(quant)
        # calibrated quant ranges: fit-time per-column [lo, hi] persisted
        # with the model (uid -> {"lo": [...], "hi": [...]}). Scale/lo
        # ride as traced ARGUMENTS, so calibrated and batch-relative
        # builds share the same compiled programs (and the fleet's
        # program-sharing signature) — only the wire constants differ.
        self._cal_ranges: Optional[Dict[str, Any]] = None
        if self.quant is not None and self.quant.calibrated:
            cal = getattr(model, "quant_calibration", None)
            if cal:
                self._cal_ranges = dict(cal)
            else:
                import logging
                logging.getLogger(__name__).warning(
                    "calibrated quantization requested but the model "
                    "carries no quant_calibration (artifact predates "
                    "fit-time range capture); falling back to "
                    "batch-relative ranges")
        layers = topological_layers(model.result_features)
        self.generators: List[FeatureGeneratorStage] = list(layers[0]) if layers else []
        ordered: List[Transformer] = []
        for layer in layers[1:]:
            for stage in layer:
                fitted = model.fitted.get(stage.uid)
                if fitted is None:
                    raise RuntimeError(f"Unfitted stage {stage.uid}")
                ordered.append(fitted)
        self._stage_out_uid = {
            s.uid: s.get_output().uid for s in ordered}
        # alternating host/device segments in topo order, split by the
        # shared `is_host_stage` rule (stages/base.py) — the same rule the
        # static validator checks against
        self.segments: List[Tuple[str, List[Transformer]]] = []
        for s in ordered:
            kind = "host" if is_host_stage(s) else "device"
            if not self.segments or self.segments[-1][0] != kind:
                self.segments.append((kind, []))
            self.segments[-1][1].append(s)
        # per-segment needed outputs: a device segment returns ONLY what
        # a later segment's stage or a result feature reads. Everything
        # else stays an XLA-internal value — fusion-eligible instead of
        # a forced HBM materialization (the roofline win: the old
        # every-stage-output contract made each intermediate a program
        # output the device had to write back per call).
        result_uids = {f.uid for f in model.result_features}
        self._seg_out_uids: List[List[str]] = []
        for i, (kind, stages) in enumerate(self.segments):
            produced = {self._stage_out_uid[s.uid] for s in stages}
            needed = set(result_uids)
            for _, later in self.segments[i + 1:]:
                for s2 in later:
                    needed.update(f.uid for f in s2.input_features)
            self._seg_out_uids.append(sorted(produced & needed))
        # instrumented jit: the retrace monitor counts traces per segment
        # (label = stage ops), so per-batch shape drift shows up as churn
        # on a NAMED program instead of silent recompiles
        from transmogrifai_tpu.analysis.retrace import instrumented_jit
        self._seg_labels = [
            "compiled:seg%d[%s]%s" % (
                i, ",".join(s.operation_name for s in stages),
                f"@{self.quant.mode}" if self.quant else "")
            if kind == "device" else None
            for i, (kind, stages) in enumerate(self.segments)]
        self._seg_fns = [
            (instrumented_jit(
                self._make_segment_fn(stages, self._seg_out_uids[i]),
                label=self._seg_labels[i])
             if kind == "device" else None)
            for i, (kind, stages) in enumerate(self.segments)]
        self.device_stages: List[Transformer] = [
            s for kind, stages in self.segments if kind == "device"
            for s in stages]
        # megabyte-scale fitted arrays (tree tables, lifted linear/GLM
        # weights) flow into the jitted segments as ARGUMENTS: closure
        # constants are baked into the executable, and value-baked
        # weights would force every tenant onto its own compiled
        # program (serving/fleet.py). In
        # quantized mode the stage may narrow its tables (shape-gated
        # dtype rules only, so same-signature tenants narrow alike).
        self._consts: Dict[str, Any] = {}
        for s in self.device_stages:
            c = s.device_constants()
            if c is not None:
                self._consts[s.uid] = (
                    s.narrow_device_constants(c) if self.quant else c)

    # ------------------------------------------------------------------ #

    def _make_segment_fn(self, stages: List[Transformer],
                         out_uids: Optional[List[str]] = None):
        out_uid = self._stage_out_uid
        quant = self.quant

        def seg_fn(consts: Dict[str, Any], encs: Dict[str, Any],
                   dev_vals: Dict[str, Any]):
            if quant is not None:
                # dequant INSIDE the program: XLA fuses the affine
                # x = q·scale + lo into each leaf's first consumer, so
                # the f32 request matrix never lands in HBM at full width
                dev_vals = dequantize_wire(dev_vals, quant.bits)
            vals = dict(dev_vals)
            for stage in stages:
                dev_inputs = [vals.get(f.uid) for f in stage.input_features]
                if stage.uid in consts:
                    out = stage.device_apply_with(
                        consts[stage.uid], encs.get(stage.uid), dev_inputs)
                else:
                    out = stage.device_apply(encs.get(stage.uid), dev_inputs)
                vals[out_uid[stage.uid]] = out
            keep = out_uids if out_uids is not None else \
                [out_uid[s.uid] for s in stages]
            return {u: vals[u] for u in keep}

        return seg_fn

    def _dispatch(self, seg_idx: int, encs: Dict[str, Any],
                  dev_vals: Dict[str, Any]) -> Dict[str, Any]:
        """The ONE device-dispatch site: per-segment dispatch counts land
        in `analysis.retrace.DISPATCHES` (the roofline smoke asserts one
        dispatch per score call on fused plans) and a `device_dispatch`
        event carries the bytes shipped/returned for the current obs
        span (serving batch spans, bench runs) — fusion and wire wins
        are visible per call, not just in aggregate."""
        label = self._seg_labels[seg_idx]
        t0 = time.perf_counter()
        out = self._seg_fns[seg_idx](self._consts, encs, dev_vals)
        DISPATCHES.record(label)
        if TRACER.current() is not None:
            # byte accounting only when a span will actually keep the
            # event — two pytree walks are waste on an untraced hot path
            add_event("device_dispatch", segment=label,
                      bytes_in=_tree_nbytes((encs, dev_vals)),
                      bytes_out=_tree_nbytes(out),
                      # async dispatch: this is time-to-enqueue, not
                      # device execution — the honest per-call host cost
                      dispatch_s=round(time.perf_counter() - t0, 6),
                      quant=self.quant.mode if self.quant else None)
        return out

    def _fused_index(self) -> int:
        """Index of the single trailing device segment, or raise."""
        dev_segs = [i for i, (k, _) in enumerate(self.segments)
                    if k == "device"]
        if len(dev_segs) != 1 or dev_segs[0] != len(self.segments) - 1:
            raise RuntimeError(
                "pipeline does not compile to a single trailing device "
                "segment; use __call__")
        return dev_segs[0]

    @property
    def fusable(self) -> bool:
        """True when the whole pipeline collapses to ONE device program
        per batch shape (host prefix + a single trailing device segment
        — `score_padded` then takes the one-dispatch fast path; plans
        with a host stage BETWEEN device segments fall back to the
        general segmented `__call__`)."""
        cached = getattr(self, "_fusable", None)
        if cached is None:
            try:
                self._fused_index()
                cached = True
            except RuntimeError:
                cached = False
            self._fusable = cached
        return cached

    # the driver's single-chip compile check (__graft_entry__) jits this
    @property
    def _device_fn(self):
        return self._make_segment_fn(self.segments[self._fused_index()][1])

    def fused_jitted(self):
        """The ALREADY-jitted trailing device segment (streaming path —
        shares the compile cache with __call__)."""
        return self._seg_fns[self._fused_index()]

    def host_phase(self, dataset: Dataset):
        """Raw materialization + host-prefix stages + host_prepare for the
        single-trailing-device-segment fast path (driver entry + streaming
        overlap; __call__ handles the general segmented case)."""
        columns: Dict[str, Column] = {}
        for gen in self.generators:
            columns[gen.get_output().uid] = gen.materialize(
                dataset, allow_missing_response=True)
        for kind, stages in self.segments[:-1]:  # host prefix
            if kind != "host":
                raise RuntimeError("host_phase requires a host-prefix plan")
            for stage in stages:
                inputs = [columns[f.uid] for f in stage.input_features]
                columns[self._stage_out_uid[stage.uid]] = \
                    stage.transform(inputs)
        encs: Dict[str, Any] = {}
        for stage in self.device_stages:
            cols = [columns.get(f.uid) for f in stage.input_features]
            enc = stage.host_prepare(cols)
            if enc is not None:
                encs[stage.uid] = enc
        raw_dev: Dict[str, Any] = {}
        for uid, c in columns.items():
            if c.kind not in _HOST_KINDS:
                dv = c.device_value()
                if dv is not None:
                    raw_dev[uid] = dv
        if self.quant is not None:
            # quantize HERE, before placement: streaming workers
            # device_put this pytree, so the narrow wire is what crosses
            # the host→device link (1 byte/elem int8, 0.5 int4)
            raw_dev = quantize_wire(raw_dev, self.quant.bits,
                                    ranges=self._cal_ranges)
        n_rows = len(dataset)
        return (self._place(encs, n_rows), self._place(raw_dev, n_rows),
                columns)

    def _place(self, pytree, n_rows: int):
        """Shard arrays whose leading dim IS the batch axis over the row
        sharding. Matching on `n_rows` (not mere divisibility) keeps
        non-batch arrays — e.g. a (d,) encoding vector whose length
        happens to divide by the shard count — replicated instead of
        feature-axis-sharded (which would be value-correct but insert
        pointless resharding collectives)."""
        if self.sharding is None:
            return pytree
        import jax.tree_util as jtu

        # only dim 0 of the spec shards the row axis; its entry may be an
        # axis name or a tuple of axis names
        spec = self.sharding.spec
        dim0 = spec[0] if len(spec) else None
        axes = (dim0 if isinstance(dim0, tuple)
                else (dim0,) if dim0 is not None else ())
        shards = int(np.prod([self.sharding.mesh.shape[a]
                              for a in axes])) if axes else 1

        def put(a):
            arr = np.asarray(a) if not hasattr(a, "sharding") else a
            if (getattr(arr, "ndim", 0) >= 1 and arr.shape[0] == n_rows
                    and n_rows % shards == 0):
                return jax.device_put(arr, self.sharding)
            return a
        return jtu.tree_map(put, pytree)

    # ------------------------------------------------------------------ #

    def run(self, dataset: Dataset):
        """Execute all segments; returns (dev_vals, columns)."""
        n_rows = len(dataset)
        columns: Dict[str, Column] = {}
        dev_vals: Dict[str, Any] = {}
        for gen in self.generators:
            f = gen.get_output()
            c = gen.materialize(dataset, allow_missing_response=True)
            columns[f.uid] = c
            if c.kind not in _HOST_KINDS:
                dev_vals[f.uid] = c.device_value()
        if self.quant is None:
            # quantized mode defers placement to the dispatch site so
            # the NARROW wire (not the f32 original) crosses the link
            dev_vals = self._place(dev_vals, n_rows)

        for seg_idx, (kind, stages) in enumerate(self.segments):
            if kind == "host":
                for stage in stages:
                    inputs = []
                    for f in stage.input_features:
                        c = columns.get(f.uid)
                        if c is None:  # device-produced → materialize once
                            c = _column_from_device(f.ftype, dev_vals[f.uid])
                            columns[f.uid] = c
                        inputs.append(c)
                    out_col = stage.transform(inputs)
                    uid = self._stage_out_uid[stage.uid]
                    columns[uid] = out_col
                    dv = out_col.device_value()
                    if dv is not None:
                        # quantized mode keeps host outputs HOST-side
                        # until the dispatch site quantizes+places them:
                        # placing here would ship full-width f32 and make
                        # numerics depend on whether sharding is set
                        dev_vals[uid] = dv if self.quant is not None \
                            else self._place(dv, n_rows)
            else:
                encs: Dict[str, Any] = {}
                for stage in stages:
                    cols = [columns.get(f.uid) for f in stage.input_features]
                    enc = stage.host_prepare(cols)
                    if enc is not None:
                        encs[stage.uid] = enc
                args = dev_vals
                if self.quant is not None:
                    # wire form of the still-host-resident leaves only;
                    # device arrays from earlier segments pass through
                    # (quantizing them would round-trip HBM→host)
                    args = self._place(
                        quantize_wire(dev_vals, self.quant.bits,
                                      ranges=self._cal_ranges), n_rows)
                dev_vals.update(
                    self._dispatch(seg_idx, self._place(encs, n_rows), args))
        return dev_vals, columns

    def __call__(self, dataset: Dataset) -> Dict[str, Any]:
        dev_vals, columns = self.run(dataset)
        result: Dict[str, Any] = {}
        for f in self.model.result_features:
            if f.uid in dev_vals:
                result[f.name] = dev_vals[f.uid]
            else:  # host-kind result feature
                result[f.name] = columns[f.uid].data
        return result

    def score_fused(self, dataset: Dataset) -> Dict[str, Any]:
        """One-dispatch scoring for single-trailing-device-segment plans:
        host phase (generators + host prefix + host_prepare + wire
        quantization) then EXACTLY ONE device dispatch of the fused
        program, which returns only the result features. Raises
        RuntimeError on multi-device-segment plans — `__call__` is the
        general fallback."""
        fi = self._fused_index()
        encs, raw_dev, columns = self.host_phase(dataset)
        out = self._dispatch(fi, encs, raw_dev)
        result: Dict[str, Any] = {}
        for f in self.model.result_features:
            if f.uid in out:
                result[f.name] = out[f.uid]
            else:
                c = columns[f.uid]
                dv = c.device_value()
                # raw/host-prefix result features never ride the wire:
                # their original (unquantized) host values are returned
                # exactly, matching __call__'s dev_vals
                result[f.name] = dv if dv is not None else c.data
        return result

    def score_padded(self, dataset: Dataset,
                     pad_to: int) -> Dict[str, Any]:
        """Score `dataset` padded up to `pad_to` rows (a shape bucket),
        returning results for ONLY the valid rows.

        The validity mask is positional — pad rows are appended, so rows
        [0, n_valid) of every result leaf are the real ones and the tail
        is sliced off before anything leaves this call. Each distinct
        `pad_to` value compiles once; every batch size <= `pad_to` then
        reuses that program (the serving batcher's bucket ladder).

        Fusable plans (the serving hot path) route through `score_fused`:
        one device dispatch per call, result features only. Pad rows
        repeat a REAL row, so they never widen the quantized wire's
        per-batch [lo, hi] range — valid-row results are invariant to
        the bucket they were padded to."""
        n_valid = len(dataset)
        padded = pad_dataset(dataset, pad_to)
        out = self.score_fused(padded) if self.fusable else self(padded)
        if pad_to == n_valid:
            return out
        return {name: slice_result_tree(v, 0, n_valid)
                for name, v in out.items()}

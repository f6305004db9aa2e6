"""One timeline for a train pass: program spans on the profiler's clock,
XLA compiles as spans under the span that asked for them, the sweep's
dispatches and the selector's phases spanned, kernels under stable
scope names, and the benchmark's readers of those spans.

Everything that starts the JAX profiler lives in this file, so one
xdist worker owns it.
"""

import glob
import importlib.util
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.automl import transmogrify
from transmogrifai_tpu.data import Dataset
from transmogrifai_tpu.evaluators import (
    BinaryClassificationEvaluator, MultiClassificationEvaluator,
    RegressionEvaluator)
from transmogrifai_tpu.evaluators.device_metrics import make_device_metric
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.models import (
    OpLogisticRegression, OpRandomForestClassifier, OpXGBoostClassifier)
from transmogrifai_tpu.models import trees
from transmogrifai_tpu.models.linear import fit_linreg_enet
from transmogrifai_tpu.models.logistic import fit_logreg_enet
from transmogrifai_tpu.obs import goodput as obsg
from transmogrifai_tpu.obs.trace import (
    TRACER, RequestTrace, Tracer, now_s, pull, train_passes, upload,
    uploading)
from transmogrifai_tpu.parallel.sweep import SWEEP_STATS, run_sweep
from transmogrifai_tpu.selector import (
    BinaryClassificationModelSelector, DataSplitter)
from transmogrifai_tpu.stages.base import FitContext
from transmogrifai_tpu.utils import compile_cache
from transmogrifai_tpu.workflow import Workflow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAMILIES = {  # estimator -> (sweep family name, grid)
    "logistic": (OpLogisticRegression(max_iter=8),
                 [{"reg_param": 0.01, "elastic_net_param": 0.1},
                  {"reg_param": 0.1, "elastic_net_param": 0.5}]),
    "forest": (OpRandomForestClassifier(n_trees=1, max_bins=8),
               [{"max_depth": 2}]),
    "gbt": (OpXGBoostClassifier(n_estimators=2, max_bins=8),
            [{"max_depth": 2}]),
}


def _children(spans, parent):
    return sorted((s for s in spans if s.parent_id == parent.span_id),
                  key=lambda s: (s.start_s, s.span_id))


# --------------------------------------------------------------------- #
# A. one clock                                                          #
# --------------------------------------------------------------------- #

def test_span_sits_in_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TRACER.span("timeline:probe"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    host = [ev for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if ev.name == "timeline:probe"]
    assert len(host) == 1
    assert host[0].duration_ns >= 2e6


def _new_spans(mark):
    return [s for s in TRACER.spans() if s.span_id > mark]


def _mark():
    return max((s.span_id for s in TRACER.spans()), default=0)


def test_pull_sits_in_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    x = jnp.arange(4096, dtype=jnp.float32) * 2.0
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = pull("timeline:probe", x)
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(got, np.arange(4096) * 2.0)
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    host = [ev for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if ev.name == "pull:timeline:probe"]
    assert len(host) == 1


_PULL_RNG = np.random.default_rng(11)
PULL_CASES = {
    # value -> (device leaves' bytes, or None for no span)
    "numpy": (lambda: _PULL_RNG.normal(size=(5, 3)), None),
    "jax": (lambda: jnp.asarray(_PULL_RNG.normal(size=(7, 3)),
                                jnp.float32), 7 * 3 * 4),
    "scalar": (lambda: jnp.float32(2.5), 4),
    "pytree": (lambda: {"a": jnp.arange(6, dtype=jnp.int32),
                        "b": (np.arange(4.0), jnp.ones((2, 2), jnp.bfloat16)),
                        "c": None}, 6 * 4 + 4 * 2),
    "host-pytree": (lambda: {"a": np.arange(3), "b": [np.ones(2)]}, None),
}


@pytest.mark.parametrize("case", sorted(PULL_CASES))
def test_pull_is_np_asarray_under_a_span_with_the_device_bytes(case):
    make, nbytes = PULL_CASES[case]
    x = make()
    want = jax.tree_util.tree_map(np.asarray, x)
    mark = _mark()
    with TRACER.span("timeline:owner") as owner:
        got = pull("timeline:site", x)
    spans = [s for s in _new_spans(mark) if s is not owner]
    if nbytes is None:
        assert spans == []      # already on the host: as it is, no span
        assert got is x
        return
    sp, = spans
    assert sp.name == "pull:timeline:site" and sp.category == "transfer"
    assert sp.parent_id == owner.span_id
    assert sp.attributes["bytes"] == nbytes
    assert sp.attributes["wait_s"] >= 0 and sp.attributes["copy_s"] >= 0
    assert sp.attributes["wait_s"] + sp.attributes["copy_s"] <= sp.duration_s
    assert jax.tree_util.tree_structure(got) \
        == jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert isinstance(g, np.ndarray) and g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("dtype", [None, jnp.float32, jnp.int32,
                                   jnp.bfloat16, bool],
                         ids=["none", "float32", "int32", "bfloat16", "bool"])
@pytest.mark.parametrize("host", [True, False], ids=["host", "device"])
def test_upload_is_jnp_asarray_under_a_span_with_the_host_bytes(dtype, host):
    x = np.arange(12, dtype=np.float64).reshape(4, 3) % 3
    if not host:
        x = jnp.asarray(x)
    want = jnp.asarray(x, dtype)
    mark = _mark()
    with TRACER.span("timeline:owner") as owner:
        got = upload("timeline:site", x, dtype)
    assert isinstance(got, jax.Array) and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    spans = [s for s in _new_spans(mark) if s is not owner]
    if not host:
        assert spans == []          # no crossing, no span
        return
    sp, = spans
    assert sp.name == "upload:timeline:site" and sp.category == "transfer"
    assert sp.parent_id == owner.span_id
    assert sp.attributes == {"bytes": 4 * 3 * 8}


def test_uploading_counts_the_numpy_operands_of_a_jnp_call():
    cols = [[np.ones(8, np.float32), jnp.zeros(8)], [np.ones(8, np.float32)]]
    mark = _mark()
    with uploading("timeline:stack", cols) as sp:
        out = jnp.stack([c for g in cols for c in g], axis=1)
    assert out.shape == (8, 3)
    assert sp.name == "upload:timeline:stack"
    assert sp.attributes == {"bytes": 2 * 8 * 4}
    def uploads():
        return [s.name for s in _new_spans(mark)
                if s.category == "transfer"]
    assert uploads() == ["upload:timeline:stack"]
    with uploading("timeline:stack", [jnp.zeros(8)]) as none:
        pass
    assert none is None and len(uploads()) == 1


def test_span_at_backdates_a_finished_span_under_the_current_one():
    tr = Tracer()
    with tr.span("owner") as owner:
        t1 = now_s()
        sp = tr.span_at("late", t1 - 0.5, t1, category="compile", k=1)
    assert sp.parent_id == owner.span_id and sp.trace_id == owner.trace_id
    assert sp.duration_s == pytest.approx(0.5)
    assert sp.category == "compile" and sp.attributes == {"k": 1}
    assert [s.name for s in tr.spans()] == ["late", "owner"]
    # an end before the start clamps to an empty span, never negative
    assert tr.span_at("odd", 2.0, 1.0).duration_s == 0.0
    # the request buffer backdates through the same code and stays out
    # of the ring until the tail sampler keeps it
    rt = RequestTrace()
    child = rt.child_at("serving:pad", 1.0, 1.25, error="boom")
    assert child.duration_s == pytest.approx(0.25)
    assert child.error == "boom" and child.parent_id == rt.root.span_id
    assert child in rt.spans and child not in TRACER.spans()


# --------------------------------------------------------------------- #
# B. every XLA compile is a span where it happens                       #
# --------------------------------------------------------------------- #

def test_compile_is_one_span_under_the_workers_current_span():
    def timeline_probe_fn(x):
        return (x * 3.0 + 1.0).sum()

    compile_cache.register_compile_listeners()
    compile_cache.register_compile_listeners()  # once per process
    prog = jax.jit(timeline_probe_fn)
    x = np.ones((7, 3), np.float32)
    seen = {}

    def work():
        with TRACER.span("timeline:owner") as owner:
            seen["owner"] = owner
            before = dict(compile_cache.COMPILE_STATS)
            mark = max((s.span_id for s in TRACER.spans()), default=0)
            prog(x).block_until_ready()
            seen["first"] = [s for s in TRACER.spans() if s.span_id > mark]
            mark = max((s.span_id for s in TRACER.spans()), default=mark)
            prog(x).block_until_ready()
            seen["second"] = [s for s in TRACER.spans() if s.span_id > mark]
            seen["stats"] = {k: compile_cache.COMPILE_STATS[k] - before[k]
                             for k in before}

    worker = threading.Thread(target=work, name="timeline-worker")
    worker.start()
    worker.join(timeout=120)
    assert not worker.is_alive()
    name = "compile:timeline:owner/jit(timeline_probe_fn)"
    mine = [s for s in seen["first"] if s.name == name]
    assert len(mine) == 1
    sp = mine[0]
    assert sp.category == "compile"
    assert sp.parent_id == seen["owner"].span_id
    assert sp.thread_name == "timeline-worker"
    assert sp.end_s is not None and sp.duration_s > 0.0
    assert seen["owner"].start_s <= sp.start_s <= sp.end_s
    assert not [s for s in seen["second"] if s.category == "compile"]
    assert seen["stats"]["backend_compile_s"] >= sp.duration_s
    assert seen["stats"]["requests"] >= 1


def test_compile_outside_any_span_is_named_for_no_owner():
    mark = max((s.span_id for s in TRACER.spans()), default=0)
    assert TRACER.current() is None
    compile_cache._on_duration(
        "/jax/core/compile/backend_compile_duration", 0.25,
        fun_name="jit(f)")
    compile_cache._on_duration("/jax/core/compile/jaxpr_trace_duration",
                               9.0, fun_name="f")
    new = [s for s in TRACER.spans() if s.span_id > mark]
    assert [(s.name, s.parent_id) for s in new] == [("compile:-/jit(f)",
                                                     None)]
    assert new[0].duration_s == pytest.approx(0.25)


def test_persistent_cache_hit_is_an_event_on_the_current_span():
    # JAX times a backend compile around its look into the persistent
    # cache: the `compile:*` span of a request the cache answered says
    # so (`cache_hit`), and no event lands on the owner
    hits = compile_cache.COMPILE_STATS["cache_hits"]
    use, hit, took = ("/jax/compilation_cache/compile_requests_use_cache",
                      "/jax/compilation_cache/cache_hits",
                      "/jax/core/compile/backend_compile_duration")
    mark = _mark()
    with TRACER.span("timeline:hit") as sp:
        compile_cache._on_event(use)
        compile_cache._on_event(hit)
        compile_cache._on_event("/jax/compilation_cache/cache_misses")
        compile_cache._on_duration(took, 0.01, fun_name="jit(loaded)")
        compile_cache._on_event(use)
        compile_cache._on_duration(took, 0.02, fun_name="jit(built)")
    assert sp.events == []
    assert compile_cache.COMPILE_STATS["cache_hits"] == hits + 1
    compiles = {s.name: s.attributes for s in _new_spans(mark)
                if s.category == "compile"}
    assert compiles == {
        "compile:timeline:hit/jit(loaded)": {"cache_hit": True},
        "compile:timeline:hit/jit(built)": {"cache_hit": False}}


def test_goodput_counts_compile_spans_as_recompile_seconds():
    tr = Tracer()
    with tr.span("run", new_trace=True) as root:
        root.event("recompile", trace_s=0.003)
        t1 = now_s()
        tr.span_at("compile:run/jit(f)", t1 - 0.01, t1, category="compile",
                   cache_hit=False)
        tr.span_at("compile:run/jit(g)", t1 - 0.002, t1, category="compile",
                   cache_hit=True)
        time.sleep(0.03)
    report = obsg.build_report(root, tr.trace_spans(root.trace_id))
    # the bucket holds compiles and cache loads alike; the loads counted
    assert report.buckets["recompile_s"] == pytest.approx(0.015)
    assert report.counts["recompiles"] == 1
    assert report.counts["compile_cache_loads"] == 1
    assert sum(report.buckets.values()) == pytest.approx(
        report.wall_s, rel=1e-6)


# --------------------------------------------------------------------- #
# C. every sweep dispatch is a span                                     #
# --------------------------------------------------------------------- #

def _sweep_inputs(n=160, seed=3):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(n, 5)).astype(np.float32))
    y = jnp.asarray((rng.normal(size=n) > 0).astype(np.float32))
    folds = [((np.arange(n) % 2 != f).astype(np.float32),
              (np.arange(n) % 2 == f).astype(np.float32))
             for f in range(2)]
    return X, y, folds


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_sweep_dispatches_are_spans_under_the_block(family):
    est, grids = FAMILIES[family]
    X, y, folds = _sweep_inputs()
    d0 = SWEEP_STATS.dispatches
    with TRACER.span("run:sweep", new_trace=True) as root:
        rows = run_sweep(est, grids, X, y, folds,
                         BinaryClassificationEvaluator(),
                         FitContext(n_rows=int(X.shape[0]), seed=7))
    assert all(np.isfinite(m) for row in rows for m in row)
    spans = TRACER.trace_spans(root.trace_id)
    by_id = {s.span_id: s for s in spans}
    dispatches = [s for s in spans if s.category == "sweep_dispatch"]
    assert dispatches
    assert {s.name for s in dispatches} == {f"sweep:dispatch:{family}"}
    assert {by_id[s.parent_id].name for s in dispatches} == {"sweep:block"}
    fetches = [s for s in spans if s.name == f"sweep:fetch:{family}"]
    assert fetches
    assert {by_id[s.parent_id].name for s in fetches} == {"sweep:block"}
    # the tree families time every dispatch into SWEEP_STATS; the
    # logistic block is spanned and not counted there
    counted = SWEEP_STATS.dispatches - d0
    assert counted == (0 if family == "logistic" else len(dispatches))
    # a tree dispatch says which form its trees' leaf sums take (the
    # padded depth's leaf count through `trees.leaf_sums_form`)
    assert {s.attributes.get("leaf_sums") for s in dispatches} == {
        None if family == "logistic" else "product"}
    # and how many levels take their histograms by sibling subtraction
    # (`trees.hist_subtract_levels`: none in a depth-2 tree)
    assert {s.attributes.get("hist_subtract") for s in dispatches} == {
        None if family == "logistic" else 0}
    # a first dispatch's compile is a child of the dispatch
    compiles = [s for s in spans if s.name.startswith(
        f"compile:sweep:dispatch:{family}/")]
    assert {by_id[s.parent_id].name for s in compiles} <= {
        f"sweep:dispatch:{family}"}


# --------------------------------------------------------------------- #
# D. the selector's phases and the entry's remainder                    #
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def train_spans():
    """One tiny LR + RF + GBT train under a root span: (root, spans,
    tree-family dispatches SWEEP_STATS counted)."""
    rng = np.random.default_rng(0)
    n = 240
    x1, x2 = rng.normal(size=n), rng.normal(size=n)
    y = (x1 + 0.5 * x2 + rng.normal(0, 0.5, n) > 0).astype(int)
    ds = Dataset.from_rows([{"x1": float(x1[i]), "x2": float(x2[i]),
                             "y": int(y[i])} for i in range(n)])
    preds, label = FeatureBuilder.from_dataset(ds, response="y")
    sel = BinaryClassificationModelSelector.with_cross_validation(
        models=[FAMILIES[f] for f in ("logistic", "forest", "gbt")],
        n_folds=2, splitter=DataSplitter(reserve_test_fraction=0.2))
    pf = sel.set_input(label, transmogrify(preds)).get_output()
    d0 = SWEEP_STATS.dispatches
    with TRACER.span("run:train", new_trace=True) as root:
        Workflow().set_result_features(pf, label) \
            .set_input_dataset(ds).train()
    return (root, TRACER.trace_spans(root.trace_id),
            SWEEP_STATS.dispatches - d0)


def test_selector_phases_in_order_under_the_stage_under_the_train(
        train_spans):
    root, spans, _ = train_spans
    train, = [s for s in spans if s.name == "workflow:train"]
    assert train.parent_id == root.span_id
    under_train = _children(spans, train)
    assert under_train[0].name == "workflow:materialize"
    assert [s.name for s in under_train].count("workflow:materialize") == 1
    stage, = [s for s in under_train
              if s.name.startswith("stage:fit:") and "ModelSelector" in s.name]
    phases = [s for s in _children(spans, stage)
              if s.name.startswith("selector:")]
    assert [s.name for s in phases] == [
        "selector:prepare", "selector:sweep", "selector:refit",
        "selector:evaluate"]
    assert all(a.end_s <= b.start_s for a, b in zip(phases, phases[1:]))
    # the phases leave next to nothing of the selector's fit unnamed
    assert stage.duration_s - sum(s.duration_s for s in phases) < 0.05
    # no new span takes a name the benchmark's older readers sum
    new = [s for s in spans if s.name.startswith(
        ("selector:", "workflow:", "sweep:dispatch:", "sweep:fetch:",
         "compile:"))]
    assert not [s for s in new if s.name.startswith(
        ("stage:fit:", "stage:transform:", "sweep:family:"))]


@pytest.mark.parametrize("family,est", [
    ("logistic", "OpLogisticRegression"),
    ("forest", "OpRandomForestClassifier"),
    ("gbt", "OpXGBoostClassifier")])
def test_train_nests_dispatch_under_block_under_family_under_sweep(
        train_spans, family, est):
    _, spans, _ = train_spans
    by_id = {s.span_id: s for s in spans}
    dispatches = [s for s in spans if s.name == f"sweep:dispatch:{family}"]
    assert dispatches
    for sp in dispatches:
        chain = []
        while sp.parent_id is not None:
            sp = by_id[sp.parent_id]
            chain.append(sp.name)
        assert chain[:3] == ["sweep:block", f"sweep:family:{est}",
                             "selector:sweep"]
        assert chain[-2:] == ["workflow:train", "run:train"]


def test_tree_family_dispatch_spans_equal_the_sweeps_own_count(train_spans):
    _, spans, counted = train_spans
    tree = [s for s in spans if s.name in ("sweep:dispatch:forest",
                                           "sweep:dispatch:gbt")]
    assert counted > 0 and len(tree) == counted


def test_refit_and_evaluate_compiles_are_named_for_their_phase(train_spans):
    _, spans, _ = train_spans
    by_id = {s.span_id: s for s in spans}
    compiles = [s for s in spans if s.category == "compile"]
    assert compiles
    for sp in compiles:  # the name carries the owner the parent link has
        owner = by_id[sp.parent_id].name
        assert sp.name.startswith(f"compile:{owner}/jit(")


# --------------------------------------------------------------------- #
# D2. a typed table: the pivot's and the checker's phases, the counters #
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def typed_spans():
    """One tiny train over integers with holes and picklists, through
    the checker: (root, spans)."""
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.automl.sanity_checker import SanityChecker
    rng = np.random.default_rng(2)
    n = 300
    y = (rng.uniform(size=n) < 0.4).astype(np.float64)
    count = np.floor(np.exp(rng.normal(1.0, 1.0, n))) + 3 * y
    count[rng.uniform(size=n) < 0.2] = np.nan
    level = np.asarray(["a", "b", "c", "d"], object)[
        rng.choice(4, size=n, p=[0.5, 0.3, 0.15, 0.05])]
    level[rng.uniform(size=n) < 0.1] = None
    other = np.asarray(["u", "v"], object)[(rng.uniform(size=n) < 0.5) * 1]
    ds = Dataset({"count": count, "level": level, "other": other, "y": y},
                 {"count": T.Integral, "level": T.PickList,
                  "other": T.PickList, "y": T.Integral})
    preds, label = FeatureBuilder.from_dataset(ds, response="y")
    checked = SanityChecker().set_input(
        label, transmogrify(preds)).get_output()
    sel = BinaryClassificationModelSelector.with_cross_validation(
        models=[FAMILIES[f] for f in ("forest", "gbt")],
        n_folds=2, splitter=DataSplitter(reserve_test_fraction=0.2))
    pf = sel.set_input(label, checked).get_output()
    with TRACER.span("run:train-typed", new_trace=True) as root:
        Workflow().set_result_features(pf, label) \
            .set_input_dataset(ds).train()
    return root, TRACER.trace_spans(root.trace_id)


def test_checker_phases_are_siblings_in_order_under_its_stage(typed_spans):
    _, spans = typed_spans
    stage, = [s for s in spans if s.name == "stage:fit:SanityChecker"]
    phases = [s for s in _children(spans, stage)
              if s.name.startswith("sanity:")]
    assert [s.name for s in phases] == [
        "sanity:moments", "sanity:corr", "sanity:contingency",
        "sanity:decide"]
    assert sum(s.duration_s for s in phases) <= stage.duration_s
    decide = phases[-1]
    # count + null, 4 + OTHER + null, 2 + OTHER + null; the never-set
    # columns (two OTHERs, one null) go, and the second of the
    # two-level column's levels (the first one's complement)
    assert decide.attributes["encoded_width"] == 2 + 6 + 4
    assert decide.attributes["selected_width"] == 8
    assert phases[2].attributes["groups"] == 3


def test_pivot_spans_sit_under_the_pivots_stage_spans(typed_spans):
    _, spans = typed_spans
    fit, = [s for s in spans if s.name == "pivot:fit"]
    by_id = {s.span_id: s for s in spans}
    assert by_id[fit.parent_id].name == "stage:fit:OneHotVectorizer"
    assert fit.attributes["columns"] == 2
    encodes = [s for s in spans if s.name == "pivot:encode"]
    assert encodes and all(
        by_id[s.parent_id].name == "stage:transform:OneHotVectorizer"
        for s in encodes)
    assert encodes[0].attributes["cells"] == 2 * 300


def test_binning_is_a_span_with_the_operands_slots(typed_spans):
    _, spans = typed_spans
    bins = [s for s in spans if s.name == "sweep:bin"]
    assert len(bins) == 1       # one family bins, the other finds it done
    # 8 kept columns: the mode-filled count is wide, 7 are indicators
    assert {s.attributes["hist_slots"] for s in bins} == {8 + 2 * 7}
    assert {s.attributes["max_bins"] for s in bins} == {8}


@pytest.fixture(scope="module")
def multi_spans():
    """One tiny train of a four-label table through the multiclass
    selector (the top label never falls): (root, spans)."""
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.automl.sanity_checker import SanityChecker
    from transmogrifai_tpu.selector import (
        DataCutter, MultiClassificationModelSelector)
    rng = np.random.default_rng(4)
    n = 300
    y = rng.choice(3, size=n, p=[0.6, 0.3, 0.1]).astype(np.float64)
    ds = Dataset(
        {"rate": np.round(rng.uniform(size=n), 2) + 0.3 * y,
         "count": np.floor(np.exp(rng.normal(1.0, 1.0, n))) + 2 * y,
         "flag": (rng.uniform(size=n) < 0.3 + 0.2 * y) * 1.0, "y": y},
        {"rate": T.Real, "count": T.Integral, "flag": T.Binary,
         "y": T.Integral})
    preds, label = FeatureBuilder.from_dataset(ds, response="y")
    checked = SanityChecker().set_input(
        label, transmogrify(preds)).get_output()
    sel = MultiClassificationModelSelector.with_cross_validation(
        models=[FAMILIES[f] for f in ("logistic", "forest")], n_folds=2,
        splitter=DataCutter(reserve_test_fraction=0.2), n_classes=4)
    pf = sel.set_input(label, checked).get_output()
    with TRACER.span("run:train-multi", new_trace=True) as root:
        Workflow().set_result_features(pf, label) \
            .set_input_dataset(ds).train()
    return root, TRACER.trace_spans(root.trace_id)


def test_cutter_is_a_span_under_prepare_with_its_counts(multi_spans):
    _, spans = multi_spans
    cut, = [s for s in spans if s.name == "cutter:prepare"]
    by_id = {s.span_id: s for s in spans}
    assert by_id[cut.parent_id].name == "selector:prepare"
    assert cut.attributes == {"labels_seen": 3, "labels_kept": 3,
                              "rows_dropped": 0}


def test_sweep_states_the_classes_and_the_operands_reads(multi_spans):
    _, spans = multi_spans
    sweep, = [s for s in spans if s.name == "selector:sweep"]
    assert sweep.attributes["classes"] == 4     # stated, not max(y) + 1
    binned, = [s for s in spans if s.name == "sweep:bin"]
    assert binned.attributes["value_columns"] == 4
    assert binned.attributes["hist_reads"] == 1     # one composite read


@pytest.mark.parametrize("est,y_of,columns,reads", [
    (lambda: OpRandomForestClassifier(n_trees=1, max_depth=2, max_bins=8,
                                      n_classes=5),
     lambda rng, n: rng.integers(0, 3, n), 5, 1),
    (lambda: trees.OpRandomForestRegressor(n_trees=1, max_depth=2,
                                           max_bins=8),
     lambda rng, n: rng.normal(size=n), 1, 2),
], ids=["classifier", "regressor"])
def test_an_estimators_own_binning_states_value_columns_and_reads(
        est, y_of, columns, reads):
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(64, 3)), jnp.float32)
    y = jnp.asarray(y_of(rng, 64), jnp.float32)
    with TRACER.span("run:edges", new_trace=True) as root:
        est().fit_arrays(X, y, jnp.ones(64), FitContext(n_rows=64, seed=1))
    edges, = [s for s in TRACER.trace_spans(root.trace_id)
              if s.name == "tree:edges"]
    assert edges.attributes["value_columns"] == columns
    assert edges.attributes["hist_reads"] == reads
    assert edges.attributes["leaf_sums"] == "product"


@pytest.mark.parametrize("depth,classes,form", [
    (13, 0, "product"), (14, 0, "scatter"),
    (12, 5, "product"), (13, 5, "scatter")])
def test_an_estimators_own_binning_states_its_leaf_sums(depth, classes,
                                                        form):
    # the attribute alone (a depth-14 fit is not run here): what the
    # span is given is the rule at the estimator's own depth and classes
    est = (OpRandomForestClassifier(n_trees=1, max_depth=depth, max_bins=8,
                                    n_classes=classes) if classes
           else trees.OpRandomForestRegressor(n_trees=1, max_depth=depth,
                                              max_bins=8))
    X = jnp.asarray(np.random.default_rng(0).normal(size=(32, 2)),
                    jnp.float32)
    with TRACER.span("run:edges", new_trace=True) as root:
        est._edges_binned(X, FitContext(n_rows=32, seed=1),
                          n_classes=classes)
    edges, = [s for s in TRACER.trace_spans(root.trace_id)
              if s.name == "tree:edges"]
    assert edges.attributes["leaf_sums"] == form


@pytest.mark.parametrize("est,classes,levels", [
    (lambda: OpRandomForestClassifier(n_trees=1, max_depth=12, max_bins=8,
                                      n_classes=23), 23, 11),
    (lambda: OpRandomForestClassifier(n_trees=1, max_depth=12, max_bins=8,
                                      n_classes=2), 2, 8),
    (lambda: trees.OpRandomForestRegressor(n_trees=1, max_depth=12,
                                           max_bins=8), 0, 0),
    (lambda: OpXGBoostClassifier(n_estimators=2, max_depth=10, max_bins=8),
     0, 0),
], ids=["classifier-k23", "classifier-k2", "regressor", "boosted"])
def test_an_estimators_own_binning_states_its_subtracted_levels(
        est, classes, levels, monkeypatch):
    # the count `hist_subtract_levels` gives at the estimator's own depth
    # and classes: a regressor's and a boosted round's signed values stay
    # direct in the default bf16 mode
    monkeypatch.setattr(trees, "HIST_PRECISION", "bf16")
    X = jnp.asarray(np.random.default_rng(0).normal(size=(32, 2)),
                    jnp.float32)
    with TRACER.span("run:edges", new_trace=True) as root:
        est()._edges_binned(X, FitContext(n_rows=32, seed=1),
                            n_classes=classes)
    edges, = [s for s in TRACER.trace_spans(root.trace_id)
              if s.name == "tree:edges"]
    assert edges.attributes["hist_subtract"] == levels


@pytest.fixture(scope="module")
def softmax_spans():
    """One tiny multiclass train of a softmax boosted family (K = 4)
    under a root span: (root, spans)."""
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.selector import MultiClassificationModelSelector
    rng = np.random.default_rng(5)
    n = 240
    y = rng.integers(0, 4, n).astype(np.float64)
    ds = Dataset({"a": rng.normal(size=n) + y, "b": rng.normal(size=n) - y,
                  "y": y}, {"a": T.Real, "b": T.Real, "y": T.Integral})
    preds, label = FeatureBuilder.from_dataset(ds, response="y")
    sel = MultiClassificationModelSelector.with_cross_validation(
        models=[(OpXGBoostClassifier(n_estimators=3, max_bins=8),
                 [{"max_depth": 2}, {"max_depth": 3}])],
        n_folds=2, n_classes=4)
    pf = sel.set_input(label, transmogrify(preds)).get_output()
    with TRACER.span("run:train-softmax", new_trace=True) as root:
        Workflow().set_result_features(pf, label) \
            .set_input_dataset(ds).train()
    return root, TRACER.trace_spans(root.trace_id)


def test_a_softmax_dispatch_states_its_chain(softmax_spans):
    """The round-chunked loop's dispatches of a softmax chain: the
    objective, the classes, the rounds and real pairs, the padded depth
    and the trees' forms, as a binary chain's dispatches state theirs."""
    _, spans = softmax_spans
    disp = [s.attributes for s in spans if s.name == "sweep:dispatch:gbt"]
    assert disp
    for at in disp:
        assert at["objective"] == "softmax" and at["classes"] == 4
        assert at["pad_depth"] == 4 and at["rounds"] >= 1
        # the round's 4 trees batch each product: level 3's right
        # children are 4 · 4 = 16 A-side rows, one tile, by subtraction
        assert at["leaf_sums"] == "product" and at["hist_subtract"] == 1
    # every round of every (configuration, fold) chain, once
    assert sum(at["rounds"] * at["pairs"] for at in disp) == 3 * 2 * 2


def test_a_softmax_fetch_states_each_chains_cross_entropy(softmax_spans):
    _, spans = softmax_spans
    fetch = [s.attributes for s in spans if s.name == "sweep:fetch:gbt"]
    pairs = sorted((g, f) for at in fetch
                   for g, f in zip(at["grids"], at["folds"]))
    assert pairs == [(0, 0), (0, 1), (1, 0), (1, 1)]
    losses = [v for at in fetch for v in at["train_loss"]]
    # 3 rounds from the uniform start: under log K, above 0
    assert all(0 < v < np.log(4) for v in losses)
    # a fold's training rows: the other fold's validation rows, both
    # configurations' chains alike
    weight = {(g, f): w for at in fetch for g, f, w in zip(
        at["grids"], at["folds"], at["train_weight"])}
    total = weight[0, 0] + weight[0, 1]
    assert weight[1, 0] + weight[1, 1] == total
    assert all(0.4 * total < w < 0.6 * total for w in weight.values())


def test_a_softmax_chain_is_no_single_program_dispatch(softmax_spans):
    """No dispatch of the boosted family's one-program form (the mesh's)
    ran on one device: every one is a chunk of rounds."""
    _, spans = softmax_spans
    compiles = [s.name for s in spans
                if s.name.startswith("compile:sweep:dispatch:gbt/")]
    assert compiles and all("chunk_pair" in c for c in compiles)


# --------------------------------------------------------------------- #
# D3. the host-device boundary: every crossing a span under its phase   #
# --------------------------------------------------------------------- #

CROSSINGS = [
    # (fixture, span, the phase it nests under)
    ("train_spans", "upload:stage:RealVectorizer", "stage:fit:RealVectorizer"),
    ("train_spans", "pull:impute:fills", "stage:fit:RealVectorizer"),
    ("train_spans", "upload:stage:RealVectorizerModel",
     "stage:transform:RealVectorizer"),
    ("train_spans", "upload:selector:rows", "selector:prepare"),
    ("train_spans", "upload:selector:label", "selector:prepare"),
    ("train_spans", "upload:sweep:folds", "selector:sweep"),
    ("train_spans", "pull:tree:indicator", "selector:sweep"),
    ("train_spans", "pull:tree:edges", "sweep:bin"),
    ("train_spans", "pull:sweep:logistic", "sweep:fetch:logistic"),
    ("train_spans", "pull:sweep:forest", "sweep:fetch:forest"),
    ("train_spans", "pull:sweep:gbt", "sweep:fetch:gbt"),
    ("train_spans", "upload:evaluate:rows", "selector:evaluate"),
    ("train_spans", "pull:evaluate:probability", "selector:evaluate"),
    ("train_spans", "pull:evaluate:prediction", "selector:evaluate"),
    ("typed_spans", "upload:stage:IntegralVectorizerModel",
     "stage:transform:IntegralVectorizer"),
    ("typed_spans", "upload:pivot", "stage:transform:OneHotVectorizer"),
    ("typed_spans", "pull:sanity:contingency", "sanity:contingency"),
    ("typed_spans", "upload:sanity:sample", "sanity:moments"),
    ("typed_spans", "pull:sanity:moments", "sanity:moments"),
    ("typed_spans", "pull:sanity:corr", "sanity:corr"),
    ("typed_spans", "pull:sweep:gbt", "sweep:fetch:gbt"),
    ("typed_spans", "pull:fit:trees", "selector:refit"),
    ("typed_spans", "pull:tree:edges", "tree:edges"),
    ("multi_spans", "upload:stage:BinaryVectorizerModel",
     "stage:transform:BinaryVectorizer"),
    ("multi_spans", "pull:sanity:contingency", "sanity:contingency"),
    ("multi_spans", "pull:sweep:logistic", "sweep:fetch:logistic"),
    ("multi_spans", "pull:sweep:forest", "sweep:fetch:forest"),
    ("multi_spans", "upload:evaluate:label", "selector:evaluate"),
    ("multi_spans", "pull:evaluate:confusion", "selector:evaluate"),
]


@pytest.mark.parametrize("fixture,name,phase", CROSSINGS, ids=[
    f"{f.split('_')[0]}-{n}" for f, n, _ in CROSSINGS])
def test_a_crossing_is_a_span_under_the_phase_that_makes_it(
        request, fixture, name, phase):
    spans = request.getfixturevalue(fixture)[1]
    by_id = {s.span_id: s for s in spans}
    mine = [s for s in spans if s.name == name]
    chains = []
    for sp in mine:
        assert sp.category == "transfer" and sp.attributes["bytes"] > 0
        if name.startswith("pull:"):
            assert sp.attributes["wait_s"] + sp.attributes["copy_s"] \
                <= sp.duration_s
        chains.append([])
        while sp.parent_id is not None:
            sp = by_id[sp.parent_id]
            chains[-1].append(sp.name)
        assert "workflow:train" in chains[-1]
    # a site several phases share (a tree's edges: the sweep's binning
    # and the refit's own) is under this one at least once
    assert any(phase in chain for chain in chains)
    if phase.startswith("sweep:fetch:"):    # the fetch's own child
        assert {by_id[s.parent_id].name for s in mine} == {phase}


@pytest.mark.parametrize("fixture", ["typed_spans", "multi_spans"])
def test_the_checker_reads_tables_and_moments_never_the_matrix(
        request, fixture):
    spans = request.getfixturevalue(fixture)[1]
    moments, = [s for s in spans if s.name == "sanity:moments"]
    tables, = [s for s in spans if s.name == "sanity:contingency"]
    # 300 rows, under the sample's lower limit: the column's own array
    assert moments.attributes["sample"] == "whole"
    assert tables.attributes["tables"] == "device"
    assert tables.attributes["categorical_label"] is True
    assert not [s for s in spans if s.name == "pull:sanity:matrix"]
    width, = [s.attributes["encoded_width"] for s in spans
              if s.name == "sanity:decide"]
    pulled, = [s for s in spans if s.name == "pull:sanity:contingency"]
    # as wide as an unforced label's most levels, not as this sample's
    assert pulled.attributes["bytes"] == 4 * width * 30
    codes, = [s for s in spans if s.name == "upload:sanity:label"]
    assert codes.parent_id == tables.span_id
    assert codes.attributes["bytes"] == 4 * 300


def test_a_spearman_fit_still_reads_its_rows_under_a_span():
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.automl.sanity_checker import SanityChecker
    rng = np.random.default_rng(6)
    n = 2400
    y = (rng.uniform(size=n) < 0.4).astype(np.float64)
    ds = Dataset({"x": rng.normal(size=n) + y, "level": np.asarray(
        ["a", "b", "c"], object)[rng.integers(0, 3, n)], "y": y},
        {"x": T.Real, "level": T.PickList, "y": T.Integral})
    preds, label = FeatureBuilder.from_dataset(ds, response="y")
    checked = SanityChecker(
        correlation_type="spearman", sample_upper_limit=1200).set_input(
        label, transmogrify(preds)).get_output()
    with TRACER.span("run:check-spearman", new_trace=True) as root:
        Workflow().set_result_features(checked, label) \
            .set_input_dataset(ds).train()
    spans = TRACER.trace_spans(root.trace_id)
    moments, = [s for s in spans if s.name == "sanity:moments"]
    assert moments.attributes["sample"] == "device"
    width, = [s.attributes["encoded_width"] for s in spans
              if s.name == "sanity:decide"]
    # the ranks are host pandas: the SAMPLED rows come down, no more
    matrix, = [s for s in spans if s.name == "pull:sanity:matrix"]
    assert matrix.parent_id == moments.span_id
    assert matrix.attributes["bytes"] == 4 * 1200 * width
    tables, = [s for s in spans if s.name == "sanity:contingency"]
    assert tables.attributes["tables"] == "device"


@pytest.mark.parametrize("fixture", ["train_spans", "typed_spans",
                                     "multi_spans"])
def test_a_model_stages_prediction_is_pulled_under_its_transform(
        request, fixture):
    spans = request.getfixturevalue(fixture)[1]
    by_id = {s.span_id: s for s in spans}
    stage, = [s for s in spans if s.name.startswith("stage:transform:")
              and "ModelSelector" in s.name]
    pulls = [s for s in spans if s.parent_id == stage.span_id
             and s.name.startswith("pull:stage:")]
    assert len(pulls) == 1 and pulls[0].attributes["bytes"] > 0
    # no transfer span takes a name an older reader of the benchmark sums
    assert not [s for s in spans if s.category == "transfer"
                and not s.name.startswith(("pull:", "upload:"))]
    assert by_id[stage.parent_id].name == "workflow:train"


def test_train_passes_groups_the_ring_by_the_pass_a_span_descends_from():
    tr = Tracer()
    with tr.span("stray"):                  # under no pass: left out
        pass
    roots = []
    for k in range(2):
        with tr.span("run", new_trace=True):
            with tr.span("workflow:train") as root:
                roots.append(root)
                with tr.span("selector:sweep") as sweep:
                    def work(parent=sweep, k=k):
                        with tr.span("sweep:family:X", parent=parent):
                            with tr.span("pull:sweep:x", bytes=8 * (k + 1)):
                                pass
                    worker = threading.Thread(target=work)
                    worker.start()
                    worker.join(timeout=60)
                t1 = now_s()
                tr.span_at("compile:workflow:train/jit(f)", t1 - 0.001, t1,
                           category="compile")
    with tr.span("workflow:train"):         # still open: not a pass yet
        passes = train_passes(tr)
    assert [p["root"] for p in passes] == roots
    for k, p in enumerate(passes):
        assert [s.name for s in p["spans"]] == [
            "selector:sweep", "sweep:family:X", "pull:sweep:x",
            "compile:workflow:train/jit(f)"]
        assert p["spans"][2].attributes == {"bytes": 8 * (k + 1)}
        assert p["spans"][2].thread_name != p["root"].thread_name
    assert train_passes(Tracer()) == []


# --------------------------------------------------------------------- #
# E. stable kernel names                                                #
# --------------------------------------------------------------------- #

def _tree_inputs(n=64, d=3, n_bins=4):
    rng = np.random.default_rng(1)
    Xb = jnp.asarray(rng.integers(0, n_bins, size=(n, d)), jnp.int8)
    G = jnp.asarray(rng.normal(size=(n, 1)).astype(np.float32))
    H = jnp.ones((n,), jnp.float32)
    return Xb, G, H


def _lower_grow_tree():
    Xb, G, H = _tree_inputs()
    return jax.jit(lambda a, g, h: trees.grow_tree(a, g, h, 2, 4)) \
        .lower(Xb, G, H)


def _lower_grow_tree_depth6():
    """A depth-6 regressor's tree: a squared-loss boosted round's."""
    Xb, G, H = _tree_inputs()
    return jax.jit(lambda a, g, h: trees.grow_tree(a, g, h, 6, 4)) \
        .lower(Xb, G, H)


def _lower_grow_tree_depth14():
    """16,384 leaves: past the crossover, the leaf sums scatter."""
    Xb, G, H = _tree_inputs()
    return jax.jit(lambda a, g, h: trees.grow_tree(a, g, h, 14, 4)) \
        .lower(Xb, G, H)


def _lower_gbt_chunk():
    """Two boosted rounds as the sweep's round-chunked dispatch runs
    them."""
    Xb, G, H = _tree_inputs()
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    return trees.fit_gbt_chunk.lower(
        Xb, G[:, 0], H, jnp.zeros_like(H), jnp.zeros_like(H),
        jnp.float32(jnp.inf), jnp.int32(0), keys, n_rounds=2, max_depth=6,
        n_bins=4, learning_rate=0.1, reg_lambda=1.0, objective="squared",
        min_child_weight=1.0, active_depth=None, gamma=0.0, alpha=0.0,
        subsample=1.0, colsample=1.0, early_stopping_rounds=0)


def _lower_gbt_chunk_softmax():
    """Two softmax rounds of 5 classes at depth 6, as the sweep's
    round-chunked dispatch runs them: levels 3-5 by subtraction."""
    Xb, _, H = _tree_inputs()
    y = jnp.asarray(np.arange(64) % 5, jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    return trees.fit_gbt_chunk.lower(
        Xb, y, H, jnp.zeros_like(H), jnp.zeros((64, 5), jnp.float32),
        jnp.float32(jnp.inf), jnp.int32(0), keys, n_rounds=2, max_depth=6,
        n_bins=4, learning_rate=0.1, reg_lambda=1.0, objective="softmax",
        min_child_weight=1.0, active_depth=None, gamma=0.0, alpha=0.0,
        subsample=1.0, colsample=1.0, early_stopping_rounds=0)


def _lower_forest():
    Xb, G, H = _tree_inputs()
    return trees.fit_forest.lower(Xb, G, H, n_trees=2, max_depth=2,
                                  n_bins=4, n_outputs=1, seed=0)


def _lower_grow_tree_two_blocks():
    Xb, G, H = _tree_inputs()
    layout = trees.hist_layout(np.asarray([False, True, True]))
    return jax.jit(lambda a, g, h, lay: trees.grow_tree(
        a, g, h, 2, 4, layout=lay)).lower(Xb, G, H, layout)


def _lower_grow_tree_classes(layout=None):
    """A classifier's tree: labels in, the composite class histograms."""
    Xb, _, H = _tree_inputs()
    y = jnp.asarray(np.arange(64) % 3, jnp.int32)
    if layout is None:
        return jax.jit(lambda a, g, h: trees.grow_tree(
            a, g, h, 2, 4, n_classes=3)).lower(Xb, y, H)
    return jax.jit(lambda a, g, h, lay: trees.grow_tree(
        a, g, h, 2, 4, layout=lay, n_classes=3)).lower(Xb, y, H, layout)


def _lower_grow_tree_classes_two_blocks():
    return _lower_grow_tree_classes(
        trees.hist_layout(np.asarray([False, True, True])))


def _lower_grow_tree_classes_deep(layout=None):
    """A 3-class tree of depth 6: levels 4 and 5 by sibling subtraction
    (`trees.hist_subtract_levels`)."""
    Xb, _, H = _tree_inputs()
    y = jnp.asarray(np.arange(64) % 3, jnp.int32)
    return jax.jit(lambda a, g, h, lay: trees.grow_tree(
        a, g, h, 6, 4, layout=lay, n_classes=3)).lower(Xb, y, H, layout)


def _lower_grow_tree_classes_deep_two_blocks():
    return _lower_grow_tree_classes_deep(
        trees.hist_layout(np.asarray([False, True, True])))


def _lower_forest_regressor_depth12():
    """A regression forest padded to depth 12, as `airlines.train`'s."""
    Xb, G, H = _tree_inputs()
    return trees.fit_forest.lower(Xb, G, H, n_trees=1, max_depth=12,
                                  n_bins=4, n_outputs=1, seed=0)


def _lower_multiclass_metric(batch=None):
    """The weighted F1's program (the confusion product inside it); with
    `batch`, under the sweep's vmap over predictions and fold masks."""
    fn = make_device_metric(MultiClassificationEvaluator("F1"), n_classes=5)
    y = jnp.asarray(np.arange(16) % 4, jnp.float32)
    p, m = jnp.asarray(np.arange(16) % 3, jnp.float32), jnp.ones(16)

    def one(pred, mask):
        return fn(y, {"prediction": pred}, mask)

    if batch is None:
        return jax.jit(one).lower(p, m)
    return jax.jit(jax.vmap(one)).lower(jnp.tile(p, (batch, 1)),
                                        jnp.tile(m, (batch, 1)))


def _lower_multiclass_metric_vmap():
    return _lower_multiclass_metric(3)


def _lower_bin():
    X = jnp.ones((16, 3), jnp.float32)
    edges = jnp.asarray(np.linspace(-1, 1, 3 * 3).reshape(3, 3), jnp.float32)
    return jax.jit(trees.bin_features).lower(X, edges)


def _lower_edges():
    """The order statistics behind `quantile_bin_edges` of a device
    matrix (the scope has to reach into the loop over its columns)."""
    X = jnp.ones((16, 3), jnp.float32)
    return trees._order_statistics.lower(
        X, jnp.asarray([0, 2], jnp.int32),
        jnp.asarray([3, 4, 7, 8], jnp.int32))


def _lower_predict():
    Xb, G, H = _tree_inputs()
    tree = trees.grow_tree(Xb, G, H, 2, 4)
    return jax.jit(trees.predict_tree).lower(tree, Xb)


def _lower_logreg():
    X = jnp.ones((16, 3), jnp.float32)
    y = jnp.asarray(np.arange(16) % 2, jnp.float32)
    return jax.jit(lambda a, b, w: fit_logreg_enet(
        a, b, w, 0.01, 0.01, 2, max_iter=3)).lower(X, y, jnp.ones(16))


def _lower_linreg():
    X = jnp.ones((16, 3), jnp.float32)
    y = jnp.asarray(np.arange(16), jnp.float32)
    return jax.jit(lambda a, b, w: fit_linreg_enet(
        a, b, w, 0.01, 0.01, max_iter=3)).lower(X, y, jnp.ones(16))


def _lower_linreg_block():
    """The sweep's least-squares block as `_block_program` builds it: the
    elastic-net fit and its prediction under the grid and fold vmaps."""
    from transmogrifai_tpu.parallel import sweep as S
    fit_predict = S._fp_linreg((True,))
    X = jnp.ones((16, 3), jnp.float32)
    data = {"X": X, "y": jnp.asarray(np.arange(16), jnp.float32)}

    def one(d, w):
        return fit_predict(data, d, w, w)["prediction"]

    return jax.jit(jax.vmap(one)).lower(
        {"l1": jnp.full(2, 0.01), "l2": jnp.full(2, 0.01)},
        jnp.ones((2, 16)))


def _lower_regression_metric_vmap():
    return _lower_metric(RegressionEvaluator(), "prediction", batch=3)


def _lower_selector_regression_metric():
    """The program behind a regression selector's train and holdout
    metrics (`RegressionEvaluator.evaluate_device`)."""
    from transmogrifai_tpu.evaluators.device_metrics import (
        regression_metrics_dev)
    return regression_metrics_dev("RMSE").lower(jnp.ones(16), jnp.ones(16))


def _lower_metric(evaluator, pred_key, batch=None):
    """The metric's program; with `batch`, under the sweep's vmap over
    score vectors and fold masks."""
    fn = make_device_metric(evaluator)
    y = jnp.asarray(np.arange(16) % 2, jnp.float32)
    s, m = jnp.linspace(0.0, 1.0, 16), jnp.ones(16)

    def one(scores, mask):
        return fn(y, {pred_key: scores}, mask)

    if batch is None:
        return jax.jit(one).lower(s, m)
    return jax.jit(jax.vmap(one)).lower(jnp.tile(s, (batch, 1)),
                                        jnp.tile(m, (batch, 1)))


SCOPES = [
    ("tree:hist", _lower_grow_tree), ("tree:split", _lower_grow_tree),
    ("tree:route", _lower_grow_tree), ("tree:bootstrap", _lower_forest),
    ("tree:hist", _lower_forest), ("tree:bin", _lower_bin),
    ("tree:edges", _lower_edges),
    ("tree:hist:wide", _lower_grow_tree),
    ("tree:hist:wide", _lower_grow_tree_two_blocks),
    ("tree:hist:ind", _lower_grow_tree_two_blocks),
    ("tree:split", _lower_grow_tree_two_blocks),
    ("tree:predict", _lower_predict), ("linear:fista", _lower_logreg),
    ("linear:fista", _lower_linreg),
    ("metric:aupr", lambda: _lower_metric(
        BinaryClassificationEvaluator("AuPR"), "prediction")),
    ("metric:auroc", lambda: _lower_metric(
        BinaryClassificationEvaluator("AuROC"), "prediction")),
    ("metric:rmse", lambda: _lower_metric(
        RegressionEvaluator(), "prediction")),
    ("tree:hist:classes", _lower_grow_tree_classes),
    ("tree:hist:wide", _lower_grow_tree_classes),
    ("tree:hist:classes", _lower_grow_tree_classes_two_blocks),
    ("tree:hist:ind", _lower_grow_tree_classes_two_blocks),
    ("metric:f1", _lower_multiclass_metric),
    ("metric:f1", _lower_multiclass_metric_vmap),
    ("linear:fista", _lower_linreg_block),
    ("metric:rmse", _lower_regression_metric_vmap),
    ("metric:rmse", _lower_selector_regression_metric),
    ("tree:leaf", _lower_grow_tree_depth6),
    ("tree:leaf:product", _lower_grow_tree_depth6),
    ("tree:leaf", _lower_gbt_chunk),
    ("tree:leaf:product", _lower_gbt_chunk),
    ("tree:leaf", _lower_grow_tree_classes),
    ("tree:leaf:product", _lower_grow_tree_classes),
    ("tree:leaf:product", _lower_forest),
    ("tree:leaf:scatter", _lower_grow_tree_depth14),
    ("tree:hist:subtract", _lower_grow_tree_classes_deep),
    ("tree:hist:subtract", _lower_grow_tree_classes_deep_two_blocks),
    ("tree:hist:classes", _lower_grow_tree_classes_deep),
    ("tree:hist:subtract", _lower_gbt_chunk_softmax),
]


@pytest.mark.parametrize(
    "scope,lower", SCOPES,
    ids=[f"{scope}-{lower.__name__.strip('_<>')}-{i}"
         for i, (scope, lower) in enumerate(SCOPES)])
def test_kernel_scope_is_in_the_lowered_programs_op_names(scope, lower):
    # op_name reads `jit(f)/tree:hist/dot_general`; under a vmap
    # `jit(f)/vmap(tree:hist)/dot_general`; inside a nested jit the
    # callee's own locations start at the scope
    text = lower().as_text(debug_info=True)
    assert re.search(rf'["/(]{re.escape(scope)}[/)]', text)


@pytest.mark.parametrize("metric", ["AuPR", "AuROC"])
@pytest.mark.parametrize("batch", [None, 3], ids=["plain", "vmap"])
def test_rank_metric_programs_hold_no_loop_and_no_gather(metric, batch):
    # tie-group ends come from a running minimum over the sorted scores
    # and the weights ride the sort: a `searchsorted` would show here as
    # a `while`, an `argsort`-then-index as a `gather`, and on the chip
    # each is a serial pass over the rows
    lowered = _lower_metric(BinaryClassificationEvaluator(metric),
                            "prediction", batch)
    ops = set(re.findall(r"\b(?:stablehlo|mhlo|chlo)\.([a-z_]+)",
                         lowered.as_text()))
    assert "sort" in ops
    assert not ops & {"while", "gather", "dynamic_gather", "scatter",
                      "dynamic_slice", "case"}, sorted(ops)
    assert not re.search(r"\b(while|gather)\(", lowered.compile().as_text())


@pytest.mark.parametrize("batch", [None, 3], ids=["plain", "vmap"])
def test_multiclass_metric_program_is_a_product_and_no_scatter(batch):
    # the (K, K) confusion matrix is two one-hots and one product; a
    # scatter-add of every row is a serial pass on the chip
    lowered = _lower_multiclass_metric(batch)
    ops = set(re.findall(r"\b(?:stablehlo|mhlo|chlo)\.([a-z_]+)",
                         lowered.as_text()))
    assert "dot_general" in ops
    # (the (K, K) table's diagonal is read by a K-element gather)
    assert not ops & {"scatter", "while", "sort"}, sorted(ops)
    assert not re.search(r"\bscatter\(", lowered.compile().as_text())


def test_a_classifiers_tree_program_reads_the_operand_once_a_level():
    # depth 2, one block: one histogram product a level (the per-column
    # form would hold K + 1 = 4 a level) and ONE for the leaves (PR 33:
    # all the tree's leaf sums are one one-hot product)
    text = _lower_grow_tree_classes().as_text()
    assert len(re.findall(r"stablehlo\.dot_general", text)) == 2 + 1
    per_column = _lower_grow_tree().as_text()      # one target + weights
    assert len(re.findall(r"stablehlo\.dot_general", per_column)) == 4 + 1


@pytest.mark.parametrize("lower", [
    _lower_grow_tree_depth6, _lower_gbt_chunk, _lower_grow_tree_classes],
    ids=["regressor-depth6", "gbt-chunk", "classifier"])
def test_a_tree_program_holds_no_scatter_where_the_rule_says_product(lower):
    # a scatter-add of every row is a serial pass on the chip: 39 ms for
    # 4.5 M rows, twice a tree, where the product takes 1.5 ms
    # (the per-level `feat` and `bin` tables are set by scatters of a few
    # nodes, under no leaf scope: the ones looked for are the leaves')
    lowered = lower()
    text = lowered.as_text(debug_info=True)
    assert "tree:leaf:product" in text
    assert not re.search(r'tree:leaf[^"\n]*scatter', text)
    assert not [line for line in lowered.compile().as_text().splitlines()
                if re.search(r"\bscatter\(", line) and "tree:leaf" in line]


@pytest.mark.parametrize("lower", [
    _lower_grow_tree_depth14, _lower_forest_regressor_depth12,
    _lower_gbt_chunk, _lower_grow_tree_classes],
    ids=["regressor-depth14", "regression-forest-depth12", "gbt-chunk",
         "classifier-depth2"])
def test_signed_values_and_shallow_trees_take_no_subtraction(lower,
                                                             monkeypatch):
    # in the default bf16 mode a regressor's and a boosted round's
    # gradient histograms stay direct at every depth (a deep node's
    # parent − right would cancel bf16-rounded terms), and a tree whose
    # levels all sit under a tile has nothing to subtract
    monkeypatch.setattr(trees, "HIST_PRECISION", "bf16")
    assert "tree:hist:subtract" not in lower().as_text(debug_info=True)


def test_a_deep_classifiers_program_multiplies_only_the_right_children():
    # depth 6, K = 3, one block: levels 0-3 direct (3 · 2^level A-side
    # rows), levels 4 and 5 a product over the right children (3 · 8 and
    # 3 · 16 rows), then the leaves' one product
    text = _lower_grow_tree_classes_deep().as_text()
    rows = [int(r) for r in re.findall(
        r"stablehlo\.dot_general[^\n]*-> tensor<(\d+)x", text)]
    assert rows == [3, 6, 12, 24, 24, 48, 64], rows


def test_a_softmax_rounds_program_multiplies_only_the_right_children():
    # depth 6, K = 5 trees a product: levels 0-2 direct (5 · 2^level
    # A-side rows), levels 3-5 over the right children (5 · 4, 5 · 8 and
    # 5 · 16 rows), a gradient and a hessian product each, then the
    # leaves' one product
    text = _lower_gbt_chunk_softmax().as_text()
    nodes = [int(r) for r in re.findall(
        r"stablehlo\.dot_general[^\n]*-> tensor<5x(\d+)x12xf32>", text)]
    assert nodes == [1, 1, 2, 2, 4, 4, 4, 4, 8, 8, 16, 16], nodes


def test_a_tree_program_past_the_crossover_scatters_its_leaves():
    text = _lower_grow_tree_depth14().as_text(debug_info=True)
    assert re.search(r'tree:leaf/tree:leaf:scatter/scatter-add"', text)
    assert "tree:leaf:product" not in text


# --------------------------------------------------------------------- #
# the benchmark's readers of these spans                                #
# --------------------------------------------------------------------- #

def _reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"timeline_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


PASS_A = {"wall_s": 20.0, "spans": [
    ("workflow:train", 19.6), ("workflow:materialize", 1.0),
    ("stage:fit:RealVectorizer", 0.5), ("stage:transform:RealVectorizer", 0.25),
    ("stage:fit:ModelSelector", 17.0), ("stage:transform:ModelSelector", 0.75),
    ("selector:prepare", 1.0), ("selector:sweep", 12.0),
    ("selector:refit", 3.0), ("selector:evaluate", 0.5),
    ("sweep:family:OpXGBoostClassifier", 12.0), ("sweep:block", 11.0),
    ("sweep:dispatch:gbt", 6.0), ("sweep:dispatch:gbt", 1.0),
    ("sweep:dispatch:logistic", 3.0), ("sweep:fetch:gbt", 0.5),
    ("sweep:bin", 4.0), ("sweep:bin", 0.5), ("tree:edges", 0.25),
    ("compile:sweep:dispatch:gbt/jit(chunk_pair)", 4.0),
    ("compile:sweep:dispatch:logistic/jit(one_cfg)", 2.0),
    ("compile:selector:refit/jit(fit_gbt)", 2.5),
    ("compile:sweep:fetch:gbt/jit(<lambda>)", 0.25)]}
PASS_B = {"wall_s": 10.0, "spans": [
    ("workflow:train", 9.9), ("workflow:materialize", 1.0),
    ("stage:fit:ModelSelector", 8.0), ("stage:transform:ModelSelector", 0.5),
    ("selector:prepare", 1.0), ("selector:sweep", 5.0),
    ("selector:refit", 1.0), ("selector:evaluate", 0.5),
    ("sweep:dispatch:gbt", 2.0), ("sweep:dispatch:logistic", 1.0),
    ("sweep:bin", 1.5)]}
# a pass of a program from before these spans existed
PASS_OLD = {"wall_s": 20.0, "spans": [
    ("stage:fit:RealVectorizer", 0.5), ("stage:fit:ModelSelector", 17.0),
    ("sweep:family:OpXGBoostClassifier", 12.0), ("sweep:block", 11.0)]}

# a typed pass: the pivot's and the checker's spans, and the typed
# driver's counters
PASS_T = {"wall_s": 30.0, "spans": [
    ("stage:fit:OneHotVectorizer", 6.5), ("pivot:fit", 6.0),
    ("stage:transform:OneHotVectorizer", 4.5), ("pivot:encode", 4.0),
    ("stage:fit:SanityChecker", 9.0), ("sanity:moments", 3.0),
    ("sanity:corr", 1.0), ("sanity:contingency", 4.0),
    ("sanity:decide", 0.5)],
    "counters": {"encoded_width": 548, "selected_width": 528,
                 "hist_slots": 1446, "pivot_cells": 26000000}}
PASS_U = {"wall_s": 20.0, "spans": [
    ("pivot:fit", 5.0), ("pivot:encode", 3.0), ("sanity:moments", 2.0),
    ("sanity:corr", 1.0), ("sanity:contingency", 3.0),
    ("sanity:decide", 0.5)],
    "counters": {"encoded_width": 546, "selected_width": 526,
                 "hist_slots": 1442, "pivot_cells": 26000000}}
TYPED_READINGS = {
    "train_pivot_s": (10.0, 9.0),
    "train_sanity_s": (8.5, 7.5),
    "train_encoded_width": (548, 547),
    "train_hist_slots": (1446, 1444),
}


@pytest.mark.parametrize("name", sorted(TYPED_READINGS))
def test_typed_layer_metric_reader_on_a_hand_made_window(name):
    read = _reader(name)
    one, two = TYPED_READINGS[name]
    assert read({"window": {"passes": [PASS_T]}}) == pytest.approx(one)
    assert read({"window": {"passes": [PASS_T, PASS_U]}}) \
        == pytest.approx(two)
    assert read({"window": {"passes": []}}) is None
    assert read({"window": {}}) is None
    # a program from before these spans and counters, and a pass of the
    # untyped driver (no `counters`), give nothing and do not raise
    assert read({"window": {"passes": [PASS_OLD]}}) is None
    assert read({"window": {"passes": [PASS_T, PASS_A]}}) is None


@pytest.mark.parametrize("name", ["train_typed_mfu_pct",
                                  "train_typed_busy_mfu_pct"])
def test_typed_share_of_the_peak_reads_on_the_chip_only(name, monkeypatch):
    import json
    import sys
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    sys.modules.pop("work_typed", None)
    read = _reader(name)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "criteo.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peaks = json.load(fh)["TPU v5 lite"]
    obs = {"window": {"passes": [PASS_T], "rows": 1_000_000},
           "config": config, "peaks": None,
           "trace": {"n_ops": 5, "busy_s": 12.0}}
    assert read(obs) is None                         # off the chip
    share = read(dict(obs, peaks=peaks))
    # 1.6e12 bytes at 819 GB/s = 1.95 s of a 30 s pass / of 12 s busy
    least = 1598777600000.0 / 819e9
    assert share == pytest.approx(
        100 * least / (30.0 if name == "train_typed_mfu_pct" else 12.0))
    assert 0 < share < 100


# a many-label pass: the cutter's span and the driver's counters
PASS_M = {"wall_s": 24.0, "spans": [
    ("selector:prepare", 1.0), ("cutter:prepare", 0.25),
    ("sweep:bin", 0.5)],
    "counters": {"hist_slots": 1042, "value_columns": 23, "hist_reads": 1,
                 "labels_seen": 22, "labels_kept": 22, "rows_dropped": 0}}
PASS_N = {"wall_s": 20.0, "spans": [
    ("selector:prepare", 1.0), ("cutter:prepare", 0.75),
    ("sweep:bin", 0.5)],
    "counters": {"hist_slots": 1042, "value_columns": 23, "hist_reads": 24,
                 "labels_seen": 23, "labels_kept": 23, "rows_dropped": 0}}
MULTI_READINGS = {
    "train_hist_reads": (1, 12.5),
    "train_cutter_s": (0.25, 0.5),
}


@pytest.mark.parametrize("name", sorted(MULTI_READINGS))
def test_multi_layer_metric_reader_on_a_hand_made_window(name):
    read = _reader(name)
    one, two = MULTI_READINGS[name]
    assert read({"window": {"passes": [PASS_M]}}) == pytest.approx(one)
    assert read({"window": {"passes": [PASS_M, PASS_N]}}) \
        == pytest.approx(two)
    assert read({"window": {"passes": []}}) is None
    assert read({"window": {}}) is None
    # a program without the span or the counter gives nothing
    assert read({"window": {"passes": [PASS_OLD]}}) is None
    assert read({"window": {"passes": [PASS_T]}}) is None


@pytest.mark.parametrize("name", ["train_multi_mfu_pct",
                                  "train_multi_busy_mfu_pct"])
def test_multi_share_of_the_peak_reads_on_the_chip_only(name, monkeypatch):
    import json
    import sys
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    sys.modules.pop("work_multi", None)
    read = _reader(name)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kddcup99.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peaks = json.load(fh)["TPU v5 lite"]
    obs = {"window": {"passes": [PASS_M], "rows": 2_000_000},
           "config": config, "peaks": None,
           "trace": {"n_ops": 5, "busy_s": 12.0}}
    assert read(obs) is None                         # off the chip
    share = read(dict(obs, peaks=peaks))
    import work_multi
    work = work_multi.train_pass(config, 2_000_000)
    least = max(work["ops"] / 197e12, work["bytes"] / 819e9)
    assert share == pytest.approx(
        100 * least / (24.0 if name == "train_multi_mfu_pct" else 12.0))
    assert 0 < share < 100


READINGS = {
    # (one pass, mean of two passes)
    "train_compile_span_s": (8.75, 4.375),
    "train_sweep_wait_s": (4.0, 3.5),
    "train_refit_s": (3.0, 2.0),
    "train_evaluate_s": (0.5, 0.5),
    "train_bin_s": (4.5, 3.0),
    # A: (20 - 18.5 - 1) + (17 - 16.5); B: (10 - 8.5 - 1) + (8 - 7.5)
    "train_unspanned_s": (1.0, 1.0),
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_layer_metric_reader_on_a_hand_made_window(name):
    read = _reader(name)
    one, two = READINGS[name]
    assert read({"window": {"passes": [PASS_A]}}) == pytest.approx(one)
    assert read({"window": {"passes": [PASS_A, PASS_B]}}) \
        == pytest.approx(two)
    assert read({"window": {"passes": []}}) is None
    assert read({"window": {}}) is None
    assert read({"window": {"passes": [PASS_OLD]}}) is None


def test_compile_reader_reads_zero_once_the_program_spans_compiles():
    read = _reader("train_compile_span_s")
    assert read({"window": {"passes": [PASS_B]}}) == 0.0


def test_unspanned_reader_takes_the_traced_window_for_the_traced_pass():
    read = _reader("train_unspanned_s")
    # the profiler's start and stop sit in the first pass's host wall
    # (20 s) and not in the annotated window (19.5 s)
    obs = {"window": {"passes": [PASS_A, PASS_B]},
           "trace": {"window_s": 19.5}}
    assert read(obs) == pytest.approx((0.5 + 1.0) / 2)
    assert read({"window": {"passes": [PASS_A]}, "trace": None}) \
        == pytest.approx(1.0)


# the boundary's readers take the window's passes from the program's own
# ring (`obs.trace.train_passes()`): hand-made passes go in at its end
TRANSFER_PASSES = [
    (12.0, [("pull:sanity:matrix", 3.0, {"bytes": 2000, "wait_s": 0.5,
                                          "copy_s": 2.5}),
            ("pull:sweep:gbt", 1.0, {"bytes": 48, "wait_s": 0.9,
                                      "copy_s": 0.1}),
            ("upload:pivot", 0.5, {"bytes": 400}),
            ("sanity:moments", 4.0, {})]),
    (8.0, [("pull:sanity:matrix", 1.0, {"bytes": 2000, "wait_s": 0.25,
                                         "copy_s": 0.75}),
           ("upload:pivot", 0.25, {"bytes": 400})]),
    (9.0, [("pull:evaluate:probability", 2.0, {"bytes": 100, "wait_s": 0.5,
                                                "copy_s": 1.5}),
           ("upload:selector:rows", 0.125, {"bytes": 64})]),
]
TRANSFER_READINGS = {
    # (the last pass alone, the mean of the three)
    "train_pull_s": (2.0, 7.0 / 3),
    "train_pull_wait_s": (0.5, 2.15 / 3),
    "train_pull_bytes": (100, 4148 / 3),
    "train_upload_s": (0.125, 0.875 / 3),
    "train_upload_bytes": (64, 864 / 3),
    # 12 less the median of 8 and 9; one pass has no later ones
    "train_first_pass_extra_s": (None, 3.5),
}


def _hand_made_passes(passes):
    for wall, spans in passes:
        t1 = now_s()
        root = TRACER.span_at("workflow:train", t1 - wall, t1)
        for name, seconds, attrs in spans:
            TRACER.span_at(name, t1 - wall, t1 - wall + seconds,
                           parent=root, category="transfer", **attrs)


@pytest.mark.parametrize("name", sorted(TRANSFER_READINGS))
def test_transfer_layer_metric_reader_on_hand_made_passes(name, monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    read = _reader(name)
    one, three = TRANSFER_READINGS[name]
    _hand_made_passes(TRANSFER_PASSES)
    window = {"passes": [PASS_A]}
    assert read({"window": window}) == (
        None if one is None else pytest.approx(one))
    assert read({"window": {"passes": [PASS_A, PASS_B, PASS_A]}}) \
        == pytest.approx(three)
    assert read({"window": {"passes": []}}) is None
    assert read({"window": {}}) is None
    # passes of a program without these spans give nothing (the wall of
    # a pass is there whatever the program spans)
    _hand_made_passes([(5.0, [("sanity:moments", 1.0, {})]),
                       (4.0, [("sanity:moments", 1.0, {})])])
    got = read({"window": {"passes": [PASS_A, PASS_B]}})
    assert got == (pytest.approx(1.0) if name == "train_first_pass_extra_s"
                   else None)
    # more passes asked for than the ring holds: nothing, and no raise
    assert read({"window": {"passes": [PASS_A] * 10_000}}) is None


NEW_READERS = set(TRANSFER_READINGS)
TRANSFER_SPANS = [
    ("pull:sanity:matrix", 3.0), ("upload:sanity:sample", 0.5),
    ("pull:sanity:moments", 0.25), ("pull:sanity:corr", 0.25),
    ("upload:pivot", 0.5), ("upload:stage:RealVectorizerModel", 0.5),
    ("pull:impute:fills", 0.75), ("upload:selector:rows", 0.125),
    ("pull:sweep:gbt", 1.0), ("pull:sweep:logistic", 0.5),
    ("upload:sweep:folds", 0.125), ("pull:fit:trees", 0.25),
    ("pull:evaluate:probability", 0.25), ("pull:stage:XGBModel", 0.5)]


def _older_readers():
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return [m["name"] for m in json.load(fh)["per_layer"]
                if m["name"] not in NEW_READERS]


@pytest.mark.parametrize("name", _older_readers())
def test_an_older_reader_reads_the_same_with_the_transfer_spans(
        name, monkeypatch):
    import json
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    read = _reader(name)
    cfg = ("criteo" if "typed" in name else "kddcup99" if "multi" in name
           else "airlines" if "reg" in name
           else "dionis" if "softmax" in name else "higgs")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           cfg + ".json")) as fh:
        config = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peaks = json.load(fh)["TPU v5 lite"]

    def obs(passes):
        passes = [dict(p, sweep_dispatches=9, sweep_dispatch_s=6.0)
                  for p in passes]
        return {"window": {"passes": passes, "rows": 1_000_000,
                           "compiles": {"requests": 3, "cache_hits": 1}},
                "config": config, "peaks": peaks,
                "trace": {"n_ops": 5, "busy_s": 12.0, "window_s": 19.5,
                          "idle_share": 0.4}}

    def with_transfers(p):
        return dict(p, spans=p["spans"] + TRANSFER_SPANS)

    read_any = False
    boosted = dict(PASS_A, counters={"boost_rounds": 60, "hist_reads": 2,
                                     "hist_slots": 318})
    for passes in ([PASS_A], [PASS_A, PASS_B], [PASS_T], [PASS_T, PASS_U],
                   [PASS_M, PASS_N], [boosted]):
        plain = read(obs(passes))
        assert read(obs([with_transfers(p) for p in passes])) == plain
        read_any = read_any or plain is not None
    assert read_any

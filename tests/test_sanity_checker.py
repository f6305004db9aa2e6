"""SanityChecker / MinVarianceFilter tests (reference: SanityCheckerTest.scala)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import transmogrifai_tpu.types as t
from transmogrifai_tpu.automl.sanity_checker import (
    MinVarianceFilter, SanityChecker, cramers_v)
from transmogrifai_tpu.data import Column
from transmogrifai_tpu.data.metadata import VectorColumnMetadata, VectorMetadata
from transmogrifai_tpu.stages.base import FeatureGeneratorStage, FitContext


def _vec_col(X, names=None, groups=None, indicators=None):
    X = np.asarray(X, dtype=np.float32)
    cols = []
    for i in range(X.shape[1]):
        cols.append(VectorColumnMetadata(
            parent_name=(names[i] if names else f"f{i}"),
            parent_type="Real",
            grouping=(groups[i] if groups else None),
            indicator_value=(indicators[i] if indicators else None)))
    meta = VectorMetadata("v", tuple(cols)).with_indices()
    return Column.vector(X, meta)


def _label(y):
    y = np.asarray(y, dtype=np.float64)
    return Column(t.RealNN, {"value": y, "mask": np.ones(len(y), dtype=bool)})


def _fit(est, label, vec):
    lf = FeatureGeneratorStage(name="y", ftype=t.RealNN, is_response=True).get_output()
    vf = FeatureGeneratorStage(name="v", ftype=t.OPVector).get_output()
    est.set_input(lf, vf)
    return est.fit([label, vec], FitContext(len(label.data["value"])))


def _typed_table(seed, n, n_labels, on_device=True):
    """(label column, vector column, X, y): two numeric columns, a
    single-pick group of six levels, a multi-pick group of four (several
    1s a row), a two-level group and a two-level group the label's parity
    decides (dropped), against a skewed label of `n_labels` whole
    values."""
    rng = np.random.default_rng(seed)
    p = 1.0 / (1.0 + np.arange(n_labels))
    y = rng.choice(n_labels, size=n, p=p / p.sum())
    pick = (y + rng.integers(0, 3, n)) % 6
    multi = rng.uniform(size=(n, 4)) < 0.2 + 0.3 * (y[:, None] % 4
                                                   == np.arange(4))
    coin = rng.uniform(size=n) < 0.5
    X = np.concatenate([
        (rng.normal(size=n) + 0.3 * y)[:, None], rng.uniform(size=(n, 1)),
        np.eye(6)[pick], multi, np.stack([coin, ~coin], 1),
        np.eye(2)[y % 2]], axis=1).astype(np.float32)
    spec = ([("num", None, None), ("unif", None, None)]
            + [("pick", "pick", f"p{i}") for i in range(6)]
            + [("multi", "multi", f"m{i}") for i in range(4)]
            + [("coin", "coin", v) for v in "ht"]
            + [("parity", "parity", v) for v in "eo"])
    meta = VectorMetadata("v", tuple(
        VectorColumnMetadata(parent_name=name, parent_type="Real",
                             grouping=group, indicator_value=value)
        for name, group, value in spec)).with_indices()
    vec = Column(t.OPVector, jnp.asarray(X) if on_device else X, meta=meta)
    return _label(y), vec, X, y.astype(np.float64)


def test_drops_low_variance_and_leakage():
    rng = np.random.default_rng(0)
    n = 400
    y = (rng.uniform(size=n) > 0.5).astype(float)
    good = rng.normal(size=n)
    constant = np.full(n, 3.0)
    leak = y * 2 - 1 + rng.normal(0, 1e-3, n)  # corr ≈ 1 with label
    X = np.stack([good, constant, leak], axis=1)
    model = _fit(SanityChecker(), _label(y), _vec_col(X, names=["good", "const", "leak"]))
    assert model.indices == [0]
    s = model.summary
    assert s["kept"] == [0]
    reasons = {st["name"]: st["dropped"] for st in s["stats"]}
    assert any("variance" in r for r in reasons["const_1"])
    assert any("label corr" in r for r in reasons["leak_2"])
    # transform slices kept columns
    out = model.transform([_label(y), _vec_col(X)])
    assert np.asarray(out.data).shape == (n, 1)
    assert model.output_meta().size == 1


def test_cramers_v_leakage_drop():
    rng = np.random.default_rng(1)
    n = 600
    y = (rng.uniform(size=n) > 0.5).astype(float)
    # categorical group perfectly aligned with the label (one-hot of y)
    cat_a = (y == 1).astype(np.float32)
    cat_b = (y == 0).astype(np.float32)
    noise = rng.normal(size=n).astype(np.float32)
    X = np.stack([cat_a, cat_b, noise], axis=1)
    vec = _vec_col(
        X, names=["c", "c", "x"], groups=["c", "c", None],
        indicators=["a", "b", None])
    model = _fit(SanityChecker(), _label(y), vec)
    assert model.indices == [2]  # both group columns dropped via Cramér's V
    stats = model.summary["stats"]
    assert stats[0]["cramersV"] == pytest.approx(1.0, abs=0.01)


def test_keeps_everything_when_clean():
    rng = np.random.default_rng(2)
    n = 300
    y = (rng.uniform(size=n) > 0.5).astype(float)
    X = rng.normal(size=(n, 4)).astype(np.float32)
    model = _fit(SanityChecker(), _label(y), _vec_col(X))
    assert model.indices == [0, 1, 2, 3]


def test_never_drops_all():
    n = 100
    y = np.zeros(n)
    X = np.ones((n, 2), dtype=np.float32)  # all constant
    model = _fit(SanityChecker(), _label(y), _vec_col(X))
    assert model.indices == [0, 1]  # retained despite flags


def test_cramers_v_function():
    # perfect association → 1
    assert cramers_v(np.array([[50, 0], [0, 50]])) == pytest.approx(1.0)
    # independence → 0
    assert cramers_v(np.array([[25, 25], [25, 25]])) == pytest.approx(0.0)
    assert cramers_v(np.zeros((2, 2))) == 0.0


def test_min_variance_filter():
    rng = np.random.default_rng(3)
    n = 200
    X = np.stack([rng.normal(size=n), np.full(n, 7.0)], axis=1)
    vf = FeatureGeneratorStage(name="v", ftype=t.OPVector).get_output()
    est = MinVarianceFilter().set_input(vf)
    model = est.fit([_vec_col(X)], FitContext(n))
    assert model.indices == [0]
    out = model.transform([_vec_col(X)])
    assert np.asarray(out.data).shape == (n, 1)


class TestReferenceDepth:
    """VERDICT r1 #7: Spearman, feature-feature corr, PMI/MI/rule
    confidence, sampling."""

    def _fit(self, X, y, meta=None, **kw):
        from transmogrifai_tpu.automl.sanity_checker import SanityChecker
        from transmogrifai_tpu.data.columns import Column
        import transmogrifai_tpu.types as T
        lcol = Column(T.RealNN, {"value": y.astype(np.float64),
                                 "mask": np.ones(len(y), dtype=bool)})
        vcol = Column(T.OPVector, X.astype(np.float32), meta=meta)
        est = SanityChecker(**kw)
        return est.fit_model([lcol, vcol], FitContext(n_rows=len(y)))

    def test_duplicated_column_dropped_by_feature_corr(self, rng):
        n = 300
        x = rng.normal(size=n)
        y = (x + rng.normal(0, 1, size=n) > 0).astype(float)
        X = np.stack([x, rng.normal(size=n), x * 1.0], axis=1)  # col2 = col0
        model = self._fit(X, y)
        assert model.indices == [0, 1]  # the LATER duplicate dropped
        reasons = model.summary["stats"][2]["dropped"]
        assert any("corr" in r and "col_0" in r for r in reasons), reasons

    def test_spearman_detects_monotone_nonlinear(self, rng):
        n = 400
        x = rng.uniform(size=n)
        y = np.exp(6 * x)  # monotone but very non-linear
        X = np.stack([x, rng.normal(size=n)], axis=1)
        pear = self._fit(X, y, correlation_type="pearson",
                         max_feature_corr=1.0)
        spear = self._fit(X, y, correlation_type="spearman",
                          max_feature_corr=1.0)
        sp = spear.summary["stats"][0]["corrLabel"]
        pe = pear.summary["stats"][0]["corrLabel"]
        assert sp > 0.99            # rank corr is exactly monotone
        assert pe < 0.95            # pearson understates it
        assert spear.summary["correlationType"] == "spearman"

    def test_rule_confidence_drop(self, rng):
        from transmogrifai_tpu.data.metadata import (
            VectorColumnMetadata, VectorMetadata)
        n = 200
        y = (np.arange(n) % 2).astype(float)
        # one-hot "level A" column that PERFECTLY implies label 1
        a = (y == 1.0).astype(np.float32)
        b = rng.normal(size=n).astype(np.float32)
        X = np.stack([a, 1.0 - a, b], axis=1)
        meta = VectorMetadata("v", (
            VectorColumnMetadata("cat", "PickList", grouping="cat",
                                 indicator_value="A"),
            VectorColumnMetadata("cat", "PickList", grouping="cat",
                                 indicator_value="B"),
            VectorColumnMetadata("num", "Real"),
        )).with_indices()
        model = self._fit(X, y, meta=meta, max_rule_confidence=0.9,
                          min_required_rule_support=0.1,
                          max_cramers_v=2.0,       # isolate the rule check
                          max_correlation=2.0, max_feature_corr=1.0)
        dropped = set(model.summary["dropped"])
        assert 0 in dropped and 1 in dropped  # the perfect-rule group
        assert 2 in model.indices
        cats = model.summary["categoricalStats"]
        assert cats and cats[0]["maxRuleConfidences"][0] == 1.0
        assert "pointwiseMutualInfo" in cats[0]
        assert cats[0]["mutualInfo"] > 0.5  # ~1 bit for a perfect predictor

    def test_sampling_limits(self, rng):
        n = 5000
        X = rng.normal(size=(n, 3))
        y = (X[:, 0] > 0).astype(float)
        model = self._fit(X, y, check_sample=0.2, sample_lower_limit=100,
                          max_feature_corr=1.0)
        s = model.summary
        assert s["n_rows"] == 1000  # 20% sample
        assert abs(s["sampleFraction"] - 0.2) < 1e-9
        # statistics still sound on the sample
        assert abs(s["stats"][0]["corrLabel"]) > 0.5


class TestWideFeatureAxis:
    """Blocked Gram path for wide X (SURVEY.md §5.7): no (d, d) matrix."""

    def test_blocked_matches_dense(self):
        from transmogrifai_tpu.automl.sanity_checker import (
            _corr_label_and_hits_blocked, _corr_matrix)
        import jax.numpy as jnp
        rng = np.random.default_rng(5)
        n, d = 300, 37
        X = rng.normal(size=(n, d)).astype(np.float32)
        X[:, 7] = X[:, 3] * 2.0 + 1e-6          # duplicate pair (7, 3)
        X[:, 20] = -X[:, 11]                     # anti-correlated pair
        y = (X[:, 0] > 0).astype(np.float32)
        corr_y, pairs = _corr_label_and_hits_blocked(
            jnp.asarray(X), jnp.asarray(y), thr=0.95, block=8)
        dense = _corr_matrix(jnp.asarray(
            np.concatenate([X, y[:, None]], axis=1)))
        np.testing.assert_allclose(corr_y, dense[:d, d], atol=1e-4)
        assert 7 in pairs and pairs[7][0][0] == 3
        assert 20 in pairs and pairs[20][0][0] == 11
        assert abs(pairs[7][0][1] - dense[7, 3]) < 1e-4
        # no spurious pairs beyond the two planted ones
        assert set(pairs) == {7, 20}

    def test_wide_duplicate_column_dropped(self, monkeypatch):
        import transmogrifai_tpu.automl.sanity_checker as sc_mod
        monkeypatch.setattr(sc_mod, "_WIDE_D", 16)  # force the wide path
        rng = np.random.default_rng(6)
        n, d = 400, 24
        X = rng.normal(size=(n, d)).astype(np.float32)
        X[:, 13] = X[:, 4]
        y = (X[:, 0] + rng.normal(0, 0.5, n) > 0).astype(np.float64)
        label = Column(t.RealNN, {"value": y, "mask": np.ones(n, bool)})
        vec = Column(t.OPVector, X)
        model = SanityChecker(max_feature_corr=0.99).fit_model(
            [label, vec], FitContext(n_rows=n, seed=0))
        kept = model.indices
        assert 4 in kept and 13 not in kept  # later duplicate dropped
        reasons = model.summary["stats"][13]["dropped"]
        assert any("corr" in r for r in reasons)


# --------------------------------------------------------------------- #
# the encoded matrix stays on the device: the sample a gather, every    #
# group's table a slice of one product (PR 35)                          #
# --------------------------------------------------------------------- #

def _host_tables(X, y, meta):
    """The tables as the host built them before PR 35: one float64
    matmul a group over the (sampled) host matrix against a float32
    one-hot of the rounded label's sorted levels."""
    yi = np.round(y).astype(np.int64)
    oh = (yi[:, None] == np.unique(yi)[None, :]).astype(np.float32)
    groups = {}
    for i, c in enumerate(meta.columns):
        if c.indicator_value is not None:
            groups.setdefault(c.grouping_key(), []).append(i)
    return {key: (idxs, X[:, idxs].T.astype(np.float64) @ oh)
            for key, idxs in groups.items()}


def _place(vec, how):
    """The vector column's matrix as a host array, on one device, or its
    rows over four of the suite's virtual devices."""
    if how == "sharded":
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
        vec.data = jax.device_put(np.asarray(vec.data), NamedSharding(
            mesh, PartitionSpec("data", None)))
    elif how == "host":
        vec.data = np.asarray(vec.data)
    return vec


def _traced_fit(est, label, vec):
    from transmogrifai_tpu.obs.trace import TRACER
    with TRACER.span("run:check", new_trace=True) as root:
        model = est.fit_model([label, vec], FitContext(n_rows=len(vec)))
    return model, {s.name: s for s in TRACER.trace_spans(root.trace_id)}


TABLE_CASES = [
    # (seed, rows, labels, the checker's parameters, where the matrix
    #  lives, `sample` on sanity:moments, `tables` on sanity:contingency)
    (21, 704, 2, {}, "device", "whole", "device"),
    (11, 3000, 2, {"sample_upper_limit": 1000}, "device", "device",
     "device"),
    (12, 1500, 23, {}, "device", "whole", "device"),
    (22, 4000, 23, {"check_sample": 0.5}, "device", "device", "device"),
    (23, 2000, 40, {"categorical_label": True}, "device", "whole",
     "device"),
    (23, 2000, 40, {}, "device", "whole", "none"),
    (21, 704, 2, {"categorical_label": False}, "device", "whole", "none"),
    (21, 704, 2, {"categorical_label": True,
                  "categorical_label_max_card": 0}, "device", "whole",
     "device"),
    (11, 3000, 2, {"sample_upper_limit": 1000}, "host", "host", "device"),
    (12, 1500, 23, {}, "host", "whole", "device"),
    (21, 704, 2, {}, "sharded", "whole", "device"),
    (22, 4000, 23, {"check_sample": 0.5}, "sharded", "device", "device"),
]


@pytest.mark.parametrize(
    "seed,n,k,params,where,sample,tables", TABLE_CASES, ids=[
        f"{k}labels-{n}rows-{where}-{sample}-{tables}"
        + "".join(f"-{key}{value}" for key, value in params.items()
                  if key.startswith("cat"))
        for _, n, k, params, where, sample, tables in TABLE_CASES])
def test_every_groups_table_is_the_host_formulas_to_the_bit(
        seed, n, k, params, where, sample, tables):
    from transmogrifai_tpu.automl.sanity_checker import (
        CategoricalGroupStats, _contingency_counts, _label_codes,
        contingency_stats)
    label, vec, X, y = _typed_table(seed, n, k)
    est = SanityChecker(**params)
    model, spans = _traced_fit(est, label, _place(vec, where))
    assert spans["sanity:moments"].attributes["sample"] == sample
    assert spans["sanity:contingency"].attributes["tables"] == tables
    # Pearson reads no row of the matrix on the host
    assert "pull:sanity:matrix" not in spans
    idx = est._sample_rows(n)
    Xs, ys = (X, y) if idx is None else (X[idx], y[idx])
    assert model.summary["n_rows"] == len(ys)
    if tables == "none":
        assert model.summary["categoricalStats"] == []
        assert "pull:sanity:contingency" not in spans
        assert "upload:sanity:label" not in spans
        return
    host = _host_tables(Xs, ys, vec.meta)
    codes, levels = _label_codes(ys, est.categorical_label_max_card,
                                 force=est.categorical_label)
    assert levels == k and codes.dtype == np.int32
    assert spans["sanity:contingency"].attributes["groups"] == len(host)
    # as wide as the levels an unforced label may have (30, or the next
    # multiple of it under a forced one), whatever the sample holds: a
    # rare label out of it compiles nothing
    step = max(est.categorical_label_max_card, 1)
    assert spans["pull:sanity:contingency"].attributes["bytes"] == \
        4 * X.shape[1] * -(-k // step) * step
    assert spans["upload:sanity:label"].attributes["bytes"] == 4 * len(ys)
    counts = _contingency_counts(jnp.asarray(Xs), codes, levels, step)
    assert counts.dtype == np.float64 and counts.shape == (X.shape[1], k)
    # several 1s a row in the multi-pick group: its table sums past n
    assert host["multi_multi"][1].sum() > len(ys)
    want = []
    for key, (idxs, table) in host.items():
        assert np.array_equal(counts[idxs], table), key
        cs = contingency_stats(table)
        want.append(CategoricalGroupStats(
            group=key, cramers_v=cs["cramers_v"],
            mutual_info=cs["mutual_info"], pointwise_mutual_info=cs["pmi"],
            max_rule_confidences=cs["max_confidences"],
            supports=cs["supports"]).to_json())
    # and the fit's own tables were those: same numbers out of them
    assert model.summary["categoricalStats"] == want


def test_counts_past_a_float32s_whole_numbers_add_on_the_host(monkeypatch):
    import transmogrifai_tpu.automl.sanity_checker as sc
    label, vec, X, y = _typed_table(31, 1000, 5)
    codes, levels = sc._label_codes(y, 30)
    whole = sc._contingency_counts(jnp.asarray(X), codes, levels, 30)
    # as if a float32 held whole numbers up to 256 only: four runs of rows
    monkeypatch.setattr(sc, "_COUNT_EXACT_ROWS", 256)
    runs = sc._contingency_counts(jnp.asarray(X), codes, levels, 30)
    # the 0/1 columns' counts to the bit; the two numeric columns' sums
    # (no table reads them) in another order
    assert np.array_equal(runs[2:], whole[2:])
    np.testing.assert_allclose(runs[:2], whole[:2], rtol=1e-5)
    assert sc._label_counts(jnp.asarray(X), jnp.asarray(codes), 30,
                            4).shape == (X.shape[1], 4 * 30)
    for key, (idxs, table) in _host_tables(X, y, vec.meta).items():
        assert np.array_equal(whole[idxs], table), key


def test_a_label_level_out_of_the_sample_compiles_nothing():
    import transmogrifai_tpu.automl.sanity_checker as sc
    label, vec, _, y = _typed_table(12, 1500, 23)
    full = SanityChecker().fit_model([label, vec], FitContext(n_rows=1500))
    held = sc._label_counts._cache_size()
    # the rarest label's rows fall out: 22 levels, the same program
    fewer = SanityChecker().fit_model(
        [_label(np.where(y == 22, 0, y)), vec], FitContext(n_rows=1500))
    assert sc._label_counts._cache_size() == held
    pmi = [len(m.summary["categoricalStats"][0]["pointwiseMutualInfo"])
           for m in (full, fewer)]
    assert pmi == [23, 22]


def _same_json(got, want, tol, path="summary"):
    """Equal but for a float's last bits (another CPU's vector width
    orders a float32 sum otherwise; the tables' bit-equality is the test
    above): a float within `tol` relative and `tol / 10` absolute."""
    if isinstance(want, dict):
        assert set(got) == set(want), path
        for key in want:
            _same_json(got[key], want[key], tol, f"{path}.{key}")
    elif isinstance(want, list):
        assert len(got) == len(want), path
        for i, (g, w) in enumerate(zip(got, want)):
            _same_json(g, w, tol, f"{path}[{i}]")
    elif isinstance(want, float):
        assert got == pytest.approx(want, rel=tol, abs=tol / 10), path
    else:
        assert got == want, path


@pytest.mark.parametrize("name,seed,n,k,params", [
    ("binary_sampled", 11, 3000, 2, {"sample_upper_limit": 1000}),
    ("labels23_whole", 12, 1500, 23, {})])
@pytest.mark.parametrize("where", ["device", "host", "sharded"])
def test_summary_is_the_one_the_host_tables_gave(name, seed, n, k, params,
                                                 where):
    """`tests/fixtures/sanity/*.json`: `model.summary` of these two fits
    at the commit before PR 35 (the matrix pulled, the sample a host
    fancy index, the tables float64 host matmuls)."""
    import json
    import os
    label, vec, _, _ = _typed_table(seed, n, k)
    model = SanityChecker(**params).fit_model(
        [label, _place(vec, where)], FitContext(n_rows=n))
    with open(os.path.join(os.path.dirname(__file__), "fixtures", "sanity",
                           f"{name}.json")) as f:
        frozen = json.load(f)
    assert model.summary["kept"] == frozen["kept"]
    assert model.summary["dropped"] == frozen["dropped"]
    # rows over several devices: the Gram's float32 sums in another order
    _same_json(model.summary, frozen, 1e-4 if where == "sharded" else 1e-6)


def test_spearman_reads_the_sampled_rows_only():
    label, vec, X, y = _typed_table(11, 3000, 2)
    est = SanityChecker(correlation_type="spearman", sample_upper_limit=1000)
    model, spans = _traced_fit(est, label, vec)
    assert spans["sanity:moments"].attributes["sample"] == "device"
    assert spans["pull:sanity:matrix"].attributes["bytes"] == \
        1000 * X.shape[1] * 4
    assert spans["sanity:contingency"].attributes["tables"] == "device"
    # a host array is ranked where it is: nothing to pull
    hosted, spans = _traced_fit(est, label, _place(vec, "host"))
    assert "pull:sanity:matrix" not in spans
    assert hosted.summary == model.summary


# --------------------------------------------------------------------- #
# the table product as the chip's compiler leaves it (no chip: a        #
# described v5e, `on-chip-measurement` guide section 2)                 #
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n,d,k", [(1_000_000, 548, 30),
                                   (1_000_000, 116, 30)])
def test_the_chips_product_keeps_float32_operands_and_sums(one_chip, n, d, k):
    import re
    from transmogrifai_tpu.automl.sanity_checker import _label_counts
    shape = lambda s, dt: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip)
    compiled = _label_counts.lower(
        shape((n, d), jnp.float32), shape((n,), jnp.int32), width=k,
        chunks=1).compile()
    text = compiled.as_text()
    product, = re.findall(r"^.* convolution\(.*$", text, re.M)
    # float32 sums of operands the compiler was told to keep whole
    assert re.search(rf"= f32\[{d},{k}\]\S* convolution\(", product)
    assert "operand_precision={highest,highest}" in product
    # nothing in the program is narrower than float32 but the one-hot's
    # own 0/1 (a predicate): no bfloat16 copy of a column that a table
    # reads
    assert not re.search(r"\b(bf16|f16|f8\w*)\[", text)
    operands = re.search(r"convolution\(([^)]*)\)", product).group(1)
    for name in (o.strip().split(" ")[-1] for o in operands.split(",")):
        kind, = re.findall(rf"^\s*(?:ROOT )?{re.escape(name)} = (\w+)\[",
                           text, re.M)
        assert kind in ("f32", "pred"), (name, kind)
    # neither the (n, labels) one-hot nor a copy of X is a buffer
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20

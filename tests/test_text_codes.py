"""A text column's factorization (`Column.factorization()`: integer codes
into the distinct levels) and the pivots that read it, held against a
plain reference written here: a `Counter` for the fit and a dict for the
encode, cell by cell, as the pivots were before they read codes.
"""

from collections import Counter

import numpy as np
import pytest

from transmogrifai_tpu import types as T
from transmogrifai_tpu.automl import transmogrify
from transmogrifai_tpu.data import Dataset
from transmogrifai_tpu.data.columns import Column, factorize_text
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.models import OpLogisticRegression
from transmogrifai_tpu.obs.trace import TRACER
from transmogrifai_tpu.ops.categorical import (
    OneHotModel, OneHotVectorizer, level_counts, pivot_encode_ids,
    rank_levels, top_k_levels)
from transmogrifai_tpu.ops.maps import TextMapPivotVectorizer
from transmogrifai_tpu.ops.text import PIVOT, SmartTextVectorizer
from transmogrifai_tpu.selector import (
    BinaryClassificationModelSelector, DataSplitter)
from transmogrifai_tpu.workflow import Workflow

NAN = float("nan")


def _missing(v) -> bool:
    return v is None or v != v


def ref_vocab(cells, top_k, min_support):
    counter = Counter(v for v in cells if not _missing(v))
    eligible = [(c, lvl) for lvl, c in counter.items() if c >= min_support]
    eligible.sort(key=lambda t: (-t[0], t[1]))
    return [lvl for _, lvl in eligible[:top_k]]


def ref_ids(cells, vocab):
    lut = {lvl: i for i, lvl in enumerate(vocab)}
    k = len(vocab)
    return np.array([k + 1 if _missing(v) else lut.get(v, k) for v in cells],
                    dtype=np.int32)


def obj(cells) -> np.ndarray:
    arr = np.empty(len(cells), dtype=object)
    arr[:] = cells
    return arr


def _zipf(n, levels, seed, missing=0.2):
    rng = np.random.default_rng(seed)
    r = np.minimum(rng.zipf(1.3, n), levels)
    return [None if m else f"L{j:03d}"
            for j, m in zip(r, rng.random(n) < missing)]


# name -> (fit cells, transform cells, top_k, min_support)
CASES = {
    "none_cells": (["a", None, "b", "a", None, "a", "b", "c"], None, 20, 1),
    "nan_cells": (["a", NAN, "b", "a", None, NAN, "b", "a"], None, 20, 1),
    "all_missing": ([None, NAN, None, None], None, 20, 1),
    "empty": ([], None, 20, 1),
    # b, c, d tie at two cells: the cut at top_k 2 takes b by its string
    "tie_at_top_k": (["d", "c", "b", "a", "a", "a", "d", "c", "b"],
                     None, 2, 1),
    "tie_spans_top_k": (["d", "c", "b", "a", "d", "c", "b", "a"], None, 3, 2),
    # a: 3 cells, b: 2 (exactly min_support), c: 1 (under it)
    "min_support_edge": (["a", "b", "a", "c", "b", "a"], None, 20, 2),
    "nothing_eligible": (["a", "b", "c"], None, 20, 2),
    "more_than_top_k": (_zipf(4000, 60, 1), None, 20, 10),
    "many_levels_top_3": (_zipf(4000, 300, 2), None, 3, 1),
    "unseen_at_transform": (["a", "b", "a", "b", "a"],
                            ["a", "zz", None, "b", "yy", NAN], 20, 1),
    "empty_string_is_a_level": (["", "a", "", None, ""], None, 20, 1),
    "top_k_zero": (["a", "a", "b"], None, 0, 1),
    "min_support_zero": (["a", "a", "b", None], None, 20, 0),
}


@pytest.fixture(params=sorted(CASES))
def case(request):
    fit, transform, top_k, min_support = CASES[request.param]
    return fit, fit if transform is None else transform, top_k, min_support


def _fit(cells, top_k, min_support, track_nulls=True) -> OneHotModel:
    est = OneHotVectorizer(top_k=top_k, min_support=min_support,
                           track_nulls=track_nulls)
    return est.fit_model([Column(T.PickList, obj(cells))], None)


def test_vocabulary_equals_the_counters(case):
    fit, _, top_k, min_support = case
    assert _fit(fit, top_k, min_support).vocabs == [
        ref_vocab(fit, top_k, min_support)]


def test_ids_equal_the_dict_lookups(case):
    fit, transform, top_k, min_support = case
    model = _fit(fit, top_k, min_support)
    ids, = model.host_prepare([Column(T.PickList, obj(transform))])
    want = ref_ids(transform, ref_vocab(fit, top_k, min_support))
    assert ids.dtype == np.int32 and ids.flags.writeable
    np.testing.assert_array_equal(ids, want)


def test_materialized_column_gives_the_same_as_one_made_on_demand(case):
    """`from_values` of str|None storage attaches the codes; a bare
    `Column` makes them on first use: one path, the same lists."""
    fit, transform, top_k, min_support = case
    # (NaN is not storage `from_values` takes: None stands in for it)
    fit = [None if _missing(v) else v for v in fit]
    transform = [None if _missing(v) else v for v in transform]
    made = Column.from_values(T.PickList, obj(fit))
    bare = Column(T.PickList, obj(fit))
    assert made.factorized and not bare.factorized
    est = OneHotVectorizer(top_k=top_k, min_support=min_support)
    a, b = est.fit_model([made], None), est.fit_model([bare], None)
    assert bare.factorized
    assert a.vocabs == b.vocabs
    seen = Column.from_values(T.PickList, obj(transform))
    np.testing.assert_array_equal(
        a.host_prepare([seen])[0],
        b.host_prepare([Column(T.PickList, obj(transform))])[0])


def test_raw_cells_encode_like_a_column(case):
    fit, transform, top_k, min_support = case
    vocab = ref_vocab(fit, top_k, min_support)
    lut = {lvl: i for i, lvl in enumerate(vocab)}
    want = ref_ids(transform, vocab)
    for values in (obj(transform), list(transform),
                   Column(T.PickList, obj(transform))):
        np.testing.assert_array_equal(
            pivot_encode_ids(values, lut, len(vocab)), want)


@pytest.mark.parametrize("track_nulls", [True, False])
def test_track_nulls_changes_widths_not_ids(track_nulls):
    cells = ["a", None, "b", "a", "zz", None]
    model = _fit(cells, 20, 1, track_nulls=track_nulls)
    ids, = model.host_prepare([Column(T.PickList, obj(cells))])
    np.testing.assert_array_equal(ids, ref_ids(cells, ["a", "b", "zz"]))
    assert model._widths() == [5 if track_nulls else 4]
    out = np.asarray(model.device_apply([ids], None))
    assert out.shape == (6, 5 if track_nulls else 4)
    # a missing cell's row: the NULL column when tracked, else all zero
    assert out[1].sum() == (1.0 if track_nulls else 0.0)


# --------------------------------------------------------------------- #
# the factorization on the Column                                       #
# --------------------------------------------------------------------- #

def test_factorize_text_codes_and_levels():
    codes, levels = factorize_text(obj(["b", None, "a", NAN, "b", ""]))
    assert codes.dtype == np.int32
    np.testing.assert_array_equal(codes, [0, -1, 1, -1, 0, 2])
    assert list(levels) == ["b", "a", ""] and levels.dtype == object


def test_from_values_aliases_the_storage_and_makes_the_codes_once():
    arr = obj(["x", None, "y", "x"])
    col = Column.from_values(T.PickList, arr)
    assert col.data is arr and col.factorized
    codes, levels = col.factorization()
    assert col.factorization()[0] is codes      # kept, not made again
    np.testing.assert_array_equal(codes, [0, -1, 1, 0])
    assert list(levels) == ["x", "y"]
    assert [v.value for v in col.to_values()] == ["x", None, "y", "x"]


def test_replaced_data_is_factorized_again():
    col = Column.from_values(T.PickList, obj(["x", None, "y", "x"]))
    col.data = obj(["q", "q", None])
    assert not col.factorized
    codes, levels = col.factorization()
    np.testing.assert_array_equal(codes, [0, 0, -1])
    assert list(levels) == ["q"] and col.factorized


@pytest.mark.parametrize("idx", [
    np.array([5, 0, 3, 3]), np.arange(8) % 2 == 0, slice(2, 7),
    np.array([], dtype=np.int64)], ids=["fancy", "mask", "slice", "none"])
def test_take_carries_codes_and_levels(idx):
    cells = ["a", None, "b", "a", "c", "b", None, "a"]
    col = Column.from_values(T.PickList, obj(cells))
    sub = col.take(idx)
    assert sub.factorized
    codes, levels = sub.factorization()
    assert levels is col.factorization()[1]
    np.testing.assert_array_equal(codes, col.factorization()[0][idx])
    want = list(obj(cells)[idx])
    assert [None if c < 0 else levels[c] for c in codes] == want
    # the subset's pivot is the pivot of the subset's cells: a level of
    # the parent with no cell here is not a level, whatever min_support
    for min_support in (0, 1, 2):
        est = OneHotVectorizer(top_k=2, min_support=min_support)
        model = est.fit_model([sub], None)
        assert model.vocabs == [ref_vocab(want, 2, max(min_support, 1))]
        np.testing.assert_array_equal(
            model.host_prepare([sub])[0], ref_ids(want, model.vocabs[0]))


def test_take_of_an_unfactorized_column_makes_nothing():
    sub = Column(T.PickList, obj(["a", None, "b"])).take(np.array([2, 0]))
    assert not sub.factorized
    assert list(sub.factorization()[1]) == ["b", "a"]


class _Unhashable:
    __hash__ = None


@pytest.mark.parametrize("cells, lands", [
    (["a", T.PickList("b"), None, "a"], ["a", "b", None, "a"]),
    (["a", T.PickList(None), "b"], ["a", None, "b"]),
    (["a", 3, None], T.FeatureTypeError),
    (["a", NAN, "b"], T.FeatureTypeError),
    (["a", _Unhashable(), "b"], T.FeatureTypeError),
], ids=["wrapped", "wrapped_empty", "number", "nan", "unhashable"])
def test_other_than_str_or_none_takes_the_per_cell_path(cells, lands):
    """Exactly what `from_values` did before it looked at levels: a
    FeatureType instance is unwrapped, anything else is the type's to
    refuse; the column made is a copy and carries no codes yet."""
    arr = obj(cells)
    if not isinstance(lands, list):
        with pytest.raises(lands):
            Column.from_values(T.PickList, arr)
        return
    col = Column.from_values(T.PickList, arr)
    assert col.data is not arr and not col.factorized
    assert list(col.data) == lands
    model = OneHotVectorizer(min_support=1).fit_model([col], None)
    assert model.vocabs == [ref_vocab(lands, 20, 1)]
    np.testing.assert_array_equal(
        model.host_prepare([col])[0], ref_ids(lands, model.vocabs[0]))


@pytest.mark.parametrize("values", [
    ["a", None, "b"], ("a", None), np.array(["a", "b"]), []],
    ids=["list", "tuple", "str_array", "empty_list"])
def test_not_an_object_array_is_copied_cell_by_cell(values):
    col = Column.from_values(T.Text, values)
    assert not col.factorized and col.data.dtype == object
    assert list(col.data) == list(values)


def test_level_counts_and_rank_levels_are_the_counter_and_top_k():
    cells = _zipf(3000, 40, 5)
    levels, counts = level_counts(obj(cells))
    counter = Counter(v for v in cells if v is not None)
    assert dict(zip(levels, counts.tolist())) == dict(counter)
    for top_k, min_support in [(20, 10), (5, 1), (100, 1), (1, 50)]:
        want = ref_vocab(cells, top_k, min_support)
        assert rank_levels(levels, counts, top_k, min_support) == want
        assert top_k_levels(counter, top_k, min_support) == want


# --------------------------------------------------------------------- #
# the other pivots that share the helpers                               #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("made", [True, False],
                         ids=["materialized", "on_demand"])
def test_smart_text_pivot_branch(made):
    few = _zipf(600, 6, 3)                         # 6 levels: pivot
    ids_like = [f"id{i}" for i in range(600)]      # all distinct: ignored
    none = [None] * 600
    build = (lambda c: Column.from_values(T.Text, obj(c))) if made \
        else (lambda c: Column(T.Text, obj(c)))
    cols = [build(few), build(ids_like), build(none)]
    est = SmartTextVectorizer(max_cardinality=10, top_k=4, min_support=5,
                              num_features=8)
    model = est.fit_model(cols, None)
    assert model.strategies == [PIVOT, "ignore", "ignore"]
    vocab = ref_vocab(few, 4, 5)
    assert model.vocabs == [vocab, [], []]
    new = ["L001", "nope", None, "L002", NAN]
    block = model.host_prepare(
        [Column(T.Text, obj(new)), Column(T.Text, obj(new)),
         Column(T.Text, obj(new))])[0]
    want = np.zeros((5, len(vocab) + 2), np.float32)
    want[np.arange(5), ref_ids(new, vocab)] = 1.0
    np.testing.assert_array_equal(block, want)


def test_text_map_pivot_still_encodes_raw_cells():
    maps = [{"k": "a"}, {"k": "b"}, None, {"k": "a"}, {"j": "x"}, {"k": "zz"}]
    col = Column(T.TextMap, obj(maps))
    est = TextMapPivotVectorizer(top_k=2, min_support=1)
    model = est.fit_model([col], None)
    block = np.asarray(model.transform([col], None).data)
    keys = model.keys_per_feature[0]
    k_at = sum(len(model.vocabs[0][key]) + 2 for key in keys[:keys.index("k")])
    vocab = model.vocabs[0]["k"]
    cells = [None if m is None else m.get("k") for m in maps]
    want = np.zeros((6, len(vocab) + 2), np.float32)
    want[np.arange(6), ref_ids(cells, vocab)] = 1.0
    np.testing.assert_array_equal(
        block[:, k_at:k_at + len(vocab) + 2], want)


# --------------------------------------------------------------------- #
# the counters that say the mechanism engaged                           #
# --------------------------------------------------------------------- #

def _typed_table(n=300, wrapped=False):
    rng = np.random.default_rng(4)
    x = rng.normal(size=n)
    cats = {f"c{j}": obj([None if m else f"v{r}" for r, m in zip(
        rng.integers(0, 4 + j, n), rng.random(n) < 0.1)]) for j in range(3)}
    if wrapped:
        cats["c1"][7] = T.PickList("v1")   # not a str: the per-cell path
    y = (x + (cats["c0"] == "v1") + rng.normal(0, 0.5, n) > 0.5)
    cols = {"x": x, **cats, "y": y.astype(np.float64)}
    types = {"x": T.Real, "c0": T.PickList, "c1": T.PickList,
             "c2": T.PickList, "y": T.Integral}
    return Dataset(cols, types)


def _train_spans(ds):
    preds, label = FeatureBuilder.from_dataset(ds, response="y")
    sel = BinaryClassificationModelSelector.with_cross_validation(
        models=[(OpLogisticRegression(max_iter=8), [{"reg_param": 0.01}])],
        n_folds=2, splitter=DataSplitter(reserve_test_fraction=0.2))
    pf = sel.set_input(label, transmogrify(preds)).get_output()
    with TRACER.span("run:train", new_trace=True) as root:
        model = Workflow().set_result_features(pf, label) \
            .set_input_dataset(ds).train()
    return model, {s.name: s for s in TRACER.trace_spans(root.trace_id)
                   if s.name in ("workflow:materialize", "pivot:fit",
                                 "pivot:encode")}


def test_training_pass_factorizes_once_and_the_pivot_reuses_it():
    ds = _typed_table()
    model, spans = _train_spans(ds)
    mat = spans["workflow:materialize"].attributes
    assert (mat["text_columns"], mat["text_cells"],
            mat["text_factorized"]) == (3, 900, 3)
    fit, enc = spans["pivot:fit"].attributes, spans["pivot:encode"].attributes
    assert fit["columns"] == 3 and fit["codes_reused"] == 3
    assert enc["cells"] == 900 and enc["codes_reused"] == 3
    assert fit["levels"] == enc["levels"] == 4 + 5 + 6
    pivot, = [m for m in model.fitted.values() if isinstance(m, OneHotModel)]
    for f, vocab in zip(pivot.input_features, pivot.vocabs):
        assert vocab == ref_vocab(list(ds.column(f.name)), 20, 10)
    # the columns alias the table: nothing was copied
    for f in pivot.input_features:
        assert model.train_columns[f.uid].data is ds.column(f.name)


def test_a_per_cell_fallback_shows_in_the_counters():
    model, spans = _train_spans(_typed_table(wrapped=True))
    mat = spans["workflow:materialize"].attributes
    assert (mat["text_columns"], mat["text_factorized"]) == (3, 2)
    assert spans["pivot:fit"].attributes["codes_reused"] == 2
    # made on demand under pivot:fit, so the encode finds all three
    assert spans["pivot:encode"].attributes["codes_reused"] == 3
    pivot, = [m for m in model.fitted.values() if isinstance(m, OneHotModel)]
    assert "v1" in pivot.vocabs[1]

"""A finished tree's leaf sums (`models/trees.py` `_leaf_sums`): the
one-hot product against float64 sums, against the scatter-add it
replaced and against the bfloat16 narrowing it must not be; the rule
that picks the form; the trees `grow_tree` and `fit_gbt` grow under
either form; the product's program as the chip's compiler leaves it."""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models import trees


def _rows(n, leaves, m, seed):
    """Skewed leaves, whole-number weights with zeros (a bootstrap under
    a fold mask), heavy-tailed values up to 1,800 times the weight."""
    rng = np.random.default_rng(seed)
    idx = rng.choice(leaves, size=n,
                     p=rng.dirichlet(np.full(leaves, 0.3))).astype(np.int32)
    w = rng.integers(0, 4, n).astype(np.float32)
    tail = np.minimum(np.exp(rng.normal(2.0, 1.5, (n, m))), 1800.0)
    G = ((tail - 8.5) * w[:, None]).astype(np.float32)
    return idx, G, w


def _exact(idx, V, leaves):
    out = np.zeros((leaves,) + V.shape[1:], np.float64)
    np.add.at(out, idx, V.astype(np.float64))
    return out


def _gap(got, ref):
    return float(np.max(np.abs(np.asarray(got, np.float64) - ref))
                 / np.max(np.abs(ref)))


def _scatter(idx, G, H, leaves):
    return (jnp.zeros((leaves, G.shape[1]), G.dtype).at[idx].add(G),
            jnp.zeros((leaves,), H.dtype).at[idx].add(H))


def _narrowed(idx, G, H, leaves):
    """The benchmark's planted control: bfloat16 values, float32 sums."""
    A = jax.nn.one_hot(idx, leaves, dtype=jnp.bfloat16)
    V = jnp.concatenate([G, H[:, None]], 1).astype(jnp.bfloat16)
    s = jnp.matmul(A.T, V, preferred_element_type=jnp.float32)
    return s[:, :-1], s[:, -1]


def _batched(fn, batch):
    return jax.jit(fn if batch is None else jax.vmap(fn))


def _stack(arrays, batch):
    return [jnp.asarray(a[0] if batch is None else np.stack(a))
            for a in arrays]


# (rows, leaves, value columns or classes, vmap batch, kind); the leaf
# counts sit on both sides of each kind's crossover
CASES = [
    (20000, 64, 1, None, "values"), (20000, 64, 2, None, "values"),
    (20000, 64, 1, 3, "values"), (20000, 64, 2, 3, "values"),
    (20000, 1024, 1, None, "values"), (20000, 1024, 2, None, "values"),
    (6000, 1024, 1, 3, "values"), (3000, 4096, 1, None, "values"),
    (3000, 8192, 1, None, "values"), (3000, 8192, 2, 2, "values"),
    (3000, 16384, 1, None, "values"), (3000, 16384, 2, 2, "values"),
    (20000, 64, 3, None, "classes"), (20000, 64, 23, None, "classes"),
    (6000, 1024, 23, 2, "classes"), (3000, 4096, 2, None, "classes"),
    (3000, 4096, 23, None, "classes"), (3000, 4096, 5, 2, "classes"),
    (3000, 8192, 23, None, "classes"), (3000, 8192, 3, 2, "classes"),
]


@pytest.mark.parametrize("n,leaves,m,batch,kind", CASES, ids=[
    f"{k}-{n}x{leaves}x{m}-{'plain' if b is None else f'vmap{b}'}"
    for n, leaves, m, b, k in CASES])
def test_leaf_sums_against_float64_the_scatter_and_the_narrowing(
        n, leaves, m, batch, kind):
    data = [_rows(n, leaves, m if kind == "values" else 1, seed)
            for seed in range(batch or 1)]
    idx, G, H = _stack(list(zip(*data)), batch)
    if kind == "classes":
        # a classifier: labels in, one (non-whole) weight a row, some
        # labels outside [0, K) as `grow_tree` hands them on (clipped,
        # their weight zeroed)
        rng = np.random.default_rng(7)
        cls = jnp.asarray(rng.integers(0, m, idx.shape).astype(np.int32))
        H = H * jnp.asarray(rng.uniform(0.5, 1.5, H.shape), jnp.float32)
        leaf_g, leaf_h = _batched(
            lambda i, c, h: trees._leaf_sums(i, c, h, leaves, c, m),
            batch)(idx, cls, H)
        for b in range(batch or 1):
            sel = (lambda a: a) if batch is None else (lambda a, b=b: a[b])
            comp = np.asarray(sel(idx)).astype(np.int64) * m \
                + np.asarray(sel(cls))
            ref = _exact(comp, np.asarray(sel(H)), leaves * m) \
                .reshape(leaves, m)
            assert _gap(sel(leaf_g), ref) < 1e-6
            assert _gap(sel(leaf_h), ref.sum(1)) < 1e-6
        return
    leaf_g, leaf_h = _batched(
        lambda i, g, h: trees._leaf_sums(i, g, h, leaves), batch)(idx, G, H)
    old_g, _ = _batched(partial(_scatter, leaves=leaves), batch)(idx, G, H)
    low_g, _ = _batched(partial(_narrowed, leaves=leaves), batch)(idx, G, H)
    assert leaf_g.dtype == jnp.float32 and leaf_h.dtype == jnp.float32
    form = trees.leaf_sums_form(leaves, m)
    for b in range(batch or 1):
        sel = (lambda a: a) if batch is None else (lambda a, b=b: a[b])
        ref_g = _exact(np.asarray(sel(idx)), np.asarray(sel(G)), leaves)
        ref_h = _exact(np.asarray(sel(idx)), np.asarray(sel(H)), leaves)
        gap = _gap(sel(leaf_g), ref_g)
        assert gap < 1e-6
        assert _gap(sel(leaf_h), ref_h) == 0.0     # whole numbers
        if form == "product":
            # exact terms summed in float32 in blocks: no worse than
            # the scatter's one add a row
            assert gap <= max(_gap(sel(old_g), ref_g), 2e-7)
        # what the change must not be: the same bound tells the
        # narrowed values from it
        assert _gap(sel(low_g), ref_g) > 1e-6


# the five shapes the crossover was read at on the chip (PR 33), and
# both sides of each kind's crossover
@pytest.mark.parametrize("leaves,columns,classes,form", [
    (64, 1, 0, "product"), (1024, 1, 0, "product"),
    (4096, 1, 0, "product"), (4096, 23, 23, "product"),
    (4096, 2, 2, "product"), (8192, 1, 0, "product"),
    (16384, 1, 0, "scatter"), (8192, 23, 23, "scatter"),
    (64, 2, 0, "product"), (2, 1, 0, "product"),
    (4096, 64, 64, "scatter"), (2048, 64, 64, "product"),
    (8192, 50, 0, "scatter"), (4096, 50, 0, "product"),
])
def test_the_form_is_a_function_of_static_shapes(leaves, columns, classes,
                                                 form):
    assert trees.leaf_sums_form(leaves, columns, classes) == form


def test_three_bfloat16_pieces_sum_to_the_float32_value_exactly():
    rng = np.random.default_rng(3)
    v = np.concatenate([rng.normal(size=4000) * 10.0 ** rng.integers(
        -20, 20, 4000), [0.0, 1800.0, -1800.0 * 3, 1e-30, 3.3e38]])
    v = jnp.asarray(v.astype(np.float32))
    pieces = jax.jit(trees._bf16_pieces)(v)
    assert [p.dtype for p in pieces] == [jnp.bfloat16] * 3
    back = (pieces[2].astype(jnp.float32) + pieces[1].astype(jnp.float32)
            + pieces[0].astype(jnp.float32))
    assert np.array_equal(np.asarray(back), np.asarray(v))


def _tree_inputs(n=6000, d=5, n_bins=8, seed=2):
    rng = np.random.default_rng(seed)
    Xb = jnp.asarray(rng.integers(0, n_bins, size=(n, d)), jnp.int8)
    w = rng.integers(0, 3, n).astype(np.float32)
    y = (np.asarray(Xb[:, 0], np.float32) * 3.0
         + np.minimum(np.exp(rng.normal(2.0, 1.5, n)), 1800.0))
    return Xb, jnp.asarray(y.astype(np.float32)), jnp.asarray(w)


def _under_both_forms(monkeypatch, build):
    """`build()` traced under the rule's form and with every leaf sum a
    scatter-add (the form before PR 33)."""
    new = jax.tree_util.tree_map(np.asarray, build())
    monkeypatch.setattr(trees, "leaf_sums_form",
                        lambda *a, **k: "scatter")
    old = jax.tree_util.tree_map(np.asarray, build())
    return new, old


def _same_tree(new, old):
    assert np.array_equal(new["feat"], old["feat"])
    assert np.array_equal(new["bin"], old["bin"])
    scale = np.max(np.abs(old["leaf"]))
    assert np.max(np.abs(new["leaf"] - old["leaf"])) <= 1e-6 * scale


@pytest.mark.parametrize("kind,depth", [
    ("regressor", 3), ("regressor", 6), ("classifier", 4)])
def test_grow_tree_grows_the_scatter_forms_tree(monkeypatch, kind, depth):
    Xb, y, w = _tree_inputs()
    assert trees.leaf_sums_form(2 ** depth, 1, 3 * (kind == "classifier")) \
        == "product"
    if kind == "classifier":
        G = (y.astype(jnp.int32) % 3)
        build = lambda: jax.jit(lambda a, g, h: trees.grow_tree(  # noqa: E731
            a, g, h, depth, 8, n_classes=3))(Xb, G, w * 0.75)
    else:
        build = lambda: jax.jit(lambda a, g, h: trees.grow_tree(  # noqa: E731
            a, g, h, depth, 8))(Xb, (y * w)[:, None], w)
    _same_tree(*_under_both_forms(monkeypatch, build))


@pytest.mark.parametrize("objective", ["squared", "logistic"])
def test_fit_gbt_grows_the_scatter_forms_chain(monkeypatch, objective):
    Xb, y, w = _tree_inputs()
    if objective == "logistic":
        y = (y > jnp.median(y)).astype(jnp.float32)

    def build():        # a fresh trace: `fit_gbt` holds its programs
        fit = jax.jit(trees.fit_gbt.__wrapped__, static_argnames=(
            "n_estimators", "max_depth", "n_bins", "objective",
            "early_stopping_rounds", "eval_metric"))
        return fit(Xb, y, w, n_estimators=3, max_depth=4, n_bins=8,
                   learning_rate=0.3, reg_lambda=1.0, objective=objective)

    (new, new_margin), (old, old_margin) = _under_both_forms(
        monkeypatch, build)
    _same_tree(new, old)
    assert np.max(np.abs(new_margin - old_margin)) <= 1e-5 * np.max(
        np.abs(old_margin))


# --------------------------------------------------------------------- #
# the product as the chip's compiler leaves it (no chip: a described    #
# v5e, `on-chip-measurement` guide section 2)                           #
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


@pytest.mark.parametrize("n,leaves", [(4_500_000, 64), (2_160_000, 1024),
                                      (4_500_000, 4096)])
def test_the_chips_program_keeps_the_split_and_builds_no_indicator(
        one_chip, n, leaves):
    shape = lambda s, dt: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip)
    compiled = jax.jit(
        lambda i, g, h: trees._leaf_sums(i, g, h, leaves)).lower(
        shape((n,), jnp.int32), shape((n, 1), jnp.float32),
        shape((n,), jnp.float32)).compile()
    text = compiled.as_text()
    # a convert to bfloat16 and back is dropped by this compiler (then
    # two of the three pieces are zeros); `reduce_precision` stays
    assert len(re.findall(r" reduce-precision\(", text)) >= 3
    assert " convolution(" in text and not re.search(r"\bscatter\(", text)
    # the (n, leaves) indicator is the product's fused operand, never a
    # buffer: 37 GB at 4.5 M rows × 4,096 leaves
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20

"""The forest rule of each configuration, and the reference against the
program's own reference walk on the CPU."""
import numpy as np
import pytest

from bench import reference
from bench.catalog import Catalog
from bench.forest import draw_forest


@pytest.fixture(scope="module")
def catalog():
    return Catalog()


def forest_of(catalog, name, seed):
    cfg = catalog.config(name)
    rows = catalog.rows(cfg["rows"]["generator"]).Rows(cfg, seed)
    return cfg, rows, draw_forest(cfg, rows, seed)


def test_intreeger_rf_shape(catalog):
    cfg, _, forest = forest_of(catalog, "intreeger-rf", 2**31 + 7)
    counts = forest.node_counts
    assert (len(counts), forest.n_features_, forest.n_classes_) == (128, 87, 8)
    assert forest.max_depth == 10
    # 63 nodes to depth 5, then 32 branching processes of mean 1.2 over
    # five levels: about 350 a tree; the largest near the trainer's 471
    assert 300 < counts.mean() < 400
    assert 420 < counts.max() < 640


def test_hb_rf_covtype_is_complete(catalog):
    cfg, _, forest = forest_of(catalog, "hb-rf-covtype", 3)
    assert len(forest.trees_) == 500
    assert set(forest.node_counts) == {511}
    assert (forest.max_depth, forest.n_features_, forest.n_classes_) == (8, 54, 7)
    assert forest.leaf_count == 500 * 256


def test_same_seed_same_forest(catalog):
    a = forest_of(catalog, "intreeger-rf", 11)[2]
    b = forest_of(catalog, "intreeger-rf", 11)[2]
    c = forest_of(catalog, "intreeger-rf", 12)[2]
    assert all(np.array_equal(x.threshold, y.threshold) for x, y in zip(a.trees_, b.trees_))
    assert not np.array_equal(a.trees_[0].threshold, c.trees_[0].threshold)
    # shapes are the same for every seed, in another order, so the programs
    # compiled for one seed serve the next
    assert sorted(a.node_counts) == sorted(c.node_counts)
    assert not np.array_equal(a.node_counts, c.node_counts)
    assert a.max_depth == c.max_depth


def test_children_follow_parents(catalog):
    forest = forest_of(catalog, "intreeger-rf", 5)[2]
    for t in forest.trees_:
        inner = np.flatnonzero(t.feature >= 0)
        assert (t.left[inner] > inner).all() and (t.right[inner] > inner).all()
        leaves = np.flatnonzero(t.feature < 0)
        assert np.allclose(t.leaf_probs[leaves].sum(axis=1), 1.0)


def test_rows_never_repeat(catalog):
    for name in ("intreeger-rf", "hb-rf-covtype"):
        cfg, rows, _ = forest_of(catalog, name, 9)
        X = np.concatenate([rows.take(3, k, 256) for k in range(40)])
        assert len(np.unique(X, axis=0)) == len(X)
        assert X.shape[1] == cfg["n_features"]
        assert np.array_equal(rows.take(3, 5, 7), rows.take(3, 5, 7))


@pytest.mark.parametrize("name", ["intreeger-rf", "hb-rf-covtype"])
def test_reference_equals_program_reference(catalog, name):
    """At a tiny size on the CPU, the plain reference gives the same uint32
    sums as the program's ``integer:reference`` walk."""
    import jax

    from repro.core.ensemble import predict_partials_mode
    from repro.ir import ForestIR

    cfg = dict(catalog.config(name), n_trees=6, max_depth=5)
    cfg["shape"] = dict(cfg["shape"], full_depth=min(cfg["shape"]["full_depth"], 3))
    rows = catalog.rows(cfg["rows"]["generator"]).Rows(cfg, 21)
    forest = draw_forest(cfg, rows, 21)
    X = rows.take(3, 0, 300)
    want, cls = reference.scores(forest, X)
    with jax.default_device(jax.devices("cpu")[0]):
        got = np.asarray(predict_partials_mode(
            ForestIR.from_forest(forest).materialize("padded"), X, "integer"))
    assert np.array_equal(got.astype(np.uint64), want)
    assert np.array_equal(got.argmax(axis=1), cls)


def test_control_differs_from_reference(catalog):
    cfg = dict(catalog.config("intreeger-rf"), n_trees=8, max_depth=4)
    cfg["shape"] = dict(cfg["shape"], full_depth=2)
    rows = catalog.rows("esa_like").Rows(cfg, 4)
    forest = draw_forest(cfg, rows, 4)
    X = rows.take(3, 0, 200)
    exact, _ = reference.scores(forest, X)
    low, _ = reference.scores(forest, X, bits=16)
    assert (exact != low).any(axis=1).mean() > 0.9
    assert np.abs(exact.astype(np.int64) - low.astype(np.int64)).max() < 2**20

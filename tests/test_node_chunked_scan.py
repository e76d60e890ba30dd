"""The leaf_major scan with its node axis cut over the grid: trees too large
for one grid cell, served bit-identically to both references.

The budgets are lowered (the ``small_smem`` fixture) so that a seeded
forest of depth-16 trees with 2-4k nodes (``deep``) spans several node
blocks; at the real budgets the same cut serves unpruned forests of
~50k-node trees (``test_tpu_compile.py`` compiles that for the chip).
"""
import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.catalog import Catalog
from bench.forest import SHAPE_SEED, draw_forest, draw_topology
from repro.backends import create_backend
from repro.core.flint import float_to_key
from repro.ir import ForestIR
from repro.kernels import ops
from repro.kernels.ref import tree_predict_integer_ref
from repro.serve.gateway import Gateway
from repro.serve.registry import ModelRegistry

@pytest.fixture
def walks(monkeypatch):
    """The ``impl`` of every kernel call the Pallas backend makes."""
    seen, sound = [], ops.packed_partials

    def spy(packed, X, *, impl, tables, **kw):
        seen.append(impl)
        return sound(packed, X, impl=impl, tables=tables, **kw)

    monkeypatch.setattr(ops, "packed_partials", spy)
    return seen


def _shape(art):
    t, n = art.feature.shape
    return t, n, art.n_features, art.leaf_fixed.shape[-1]


def _tables(art):
    return [jnp.asarray(a) for a in (art.feature, art.threshold_key, art.left,
                                     art.right, art.leaf_fixed)]


def test_deep_forest_spans_node_blocks(deep, small_smem):
    _, ir, _ = deep
    t, n, f, c = _shape(ir.materialize("leaf_major"))
    assert not ops.holds_whole_trees(t, n, f, c)
    for b in (5, 300):
        block_b, block_t, block_n = ops.pick_blocks(b, t, n, f, c,
                                                    chunk_nodes=True)
        assert block_t == t and block_n % 128 == 0
        assert -(-n // block_n) >= 3
        assert ops._fits(block_b, block_t, block_n, f, c, chunked=True)
    with pytest.raises(ValueError, match="leaf_major scan"):
        ops.pick_blocks(300, t, n, f, c)


@pytest.mark.parametrize("rows", [5, 300])
def test_chunked_scan_matches_both_references(deep, small_smem, rows):
    forest, ir, X = deep
    X = X[:rows]
    lm, padded = ir.materialize("leaf_major"), ir.materialize("padded")
    keys = float_to_key(jnp.asarray(X))
    got = ops.tree_predict_integer(keys, *_tables(lm), depth=lm.max_depth,
                                   impl="leaf_major",
                                   internal_counts=lm.internal_counts)
    ref = tree_predict_integer_ref(keys, *_tables(padded), padded.max_depth)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    want = reference.partials(forest, X)
    np.testing.assert_array_equal(np.asarray(got).astype(np.uint64), want)


def test_chunked_scan_through_the_backend(deep, small_smem, walks):
    """``impl="auto"`` on leaf_major runs the chunked scan at every batch
    size: the small-batch gather switch is not taken for trees that gather
    cannot hold."""
    forest, ir, X = deep
    backend = create_backend("pallas", ir.materialize("leaf_major"),
                             mode="integer")
    assert backend.impl == "leaf_major"
    for rows in (3, 70, 300):
        got = backend.predict_partials(X[:rows])
        np.testing.assert_array_equal(got.astype(np.uint64),
                                      reference.partials(forest, X[:rows]))
    assert walks == ["leaf_major"] * 3


def test_small_batches_still_gather_whole_trees(deep, walks):
    """At the real budgets the same forest fits whole, and a batch under 64
    rows keeps taking the gather walk."""
    forest, ir, X = deep
    backend = create_backend("pallas", ir.materialize("leaf_major"),
                             mode="integer")
    got = backend.predict_partials(X[:3])
    np.testing.assert_array_equal(got.astype(np.uint64),
                                  reference.partials(forest, X[:3]))
    assert walks == ["gather"]


@pytest.mark.parametrize("impl", ["gather", "onehot"])
def test_pinned_whole_tree_walk_refuses_what_it_cannot_hold(deep, small_smem, impl):
    _, ir, _ = deep
    for layout in ("leaf_major", "padded"):
        with pytest.raises(ValueError, match="leaf_major scan"):
            create_backend("pallas", ir.materialize(layout), mode="integer",
                           impl=impl)
    # auto on the padded layout resolves to gather, which is refused too
    with pytest.raises(ValueError, match="leaf_major scan"):
        create_backend("pallas", ir.materialize("padded"), mode="integer")


def test_chunked_scan_through_the_gateway(deep, small_smem, tmp_path):
    forest, ir, X = deep
    path = tmp_path / "deep.itrf"
    ir.to_itrf(str(path))
    reg = ModelRegistry()
    reg.register_artifact("m", str(path))
    gw = Gateway(reg, "integer:pallas@leaf_major", max_delay_ms=1.0,
                 cache_rows=0)
    sizes = [1, 7, 64, 228]
    starts = np.cumsum([0] + sizes)

    async def run():
        try:
            return await asyncio.gather(*(
                gw.submit("m", X[a:b]) for a, b in zip(starts, starts[1:])))
        finally:
            await gw.close()

    answers = asyncio.run(run())
    scores = np.concatenate([np.asarray(s) for s, _ in answers])
    preds = np.concatenate([np.asarray(p) for _, p in answers])
    want, cls = reference.scores(forest, X)
    np.testing.assert_array_equal(scores.astype(np.uint64), want)
    np.testing.assert_array_equal(preds, cls)


@pytest.mark.parametrize("name", ["intreeger-rf", "hb-rf-covtype"])
def test_benchmark_forests_take_one_node_block(name):
    """The accepted cells' forests fit whole at every row bucket, so their
    scan runs one node block: the program it ran before the cut."""
    cfg = Catalog().config(name)
    rng = np.random.default_rng(SHAPE_SEED)
    n = max(len(draw_topology(rng, max_depth=int(cfg["max_depth"]),
                              full_depth=int(cfg["shape"]["full_depth"]),
                              split_prob=float(cfg["shape"]["split_prob"]))[0])
            for _ in range(int(cfg["n_trees"])))
    t, f, c = int(cfg["n_trees"]), int(cfg["n_features"]), int(cfg["n_classes"])
    for b in (1, 2, 4, 8, 16, 32, 64, 128, 256):
        _, _, block_n = ops.pick_blocks(b, t, n, f, c, chunk_nodes=True)
        assert block_n == -(-n // 128) * 128, (b, block_n)
    assert ops.holds_whole_trees(t, n, f, c)


def test_unpruned_forest_is_cut_not_refused():
    """scikit-learn's default forest on Covertype's widths: no whole-tree
    tiling fits, the scan's fits at the tree floor, and a walk that cannot
    cut its nodes is refused with the shapes named."""
    t, n, f, c = 100, 53_311, 54, 7
    assert ops.pick_blocks(256, t, n, f, c, chunk_nodes=True) == (256, 8, 2048)
    with pytest.raises(ValueError, match="100 trees of 53311 nodes"):
        ops.pick_blocks(256, t, n, f, c)
    assert not ops.holds_whole_trees(t, n, f, c)


@pytest.fixture(scope="module")
def esa_forest():
    """intreeger-rf as the benchmark draws it: 128 trees of up to 495 nodes,
    87 features, 8 classes, on the leaf_major layout."""
    catalog = Catalog()
    cfg = catalog.config("intreeger-rf")
    rows = catalog.rows(cfg["rows"]["generator"]).Rows(cfg, 2**31 + 5)
    return ForestIR.from_forest(draw_forest(cfg, rows, 2**31 + 5)).materialize(
        "leaf_major")


@pytest.fixture
def tilings(monkeypatch):
    """The (impl, block_b, block_t, block_n) of every kernel call, the
    kernel itself stubbed out: what is checked here is the choice."""
    seen = []

    def stub(x_keys, feature, key, left, right, leaf, nint, *, depth,
             block_b, block_t, block_n, impl, interpret):
        seen.append((impl, block_b, block_t, block_n))
        return jnp.zeros((x_keys.shape[0], leaf.shape[-1]), jnp.uint32)

    monkeypatch.setattr(ops, "_traverse", stub)
    return seen


def test_pinned_tree_blocks_serve_every_warm_bucket(esa_forest, tilings):
    """On intreeger-rf's shape the budgets' own tiling holds whole trees at
    every warm bucket, as before the node axis was cut, and a block_t
    pinned by hand past what gather can hold (64 trees of 512 nodes need
    1 MiB of SMEM) keeps small batches on the scan: every bucket the warm-up
    compiles, 1 to 256 rows, finds a tiling."""
    t, n, f, c = _shape(esa_forest)
    assert not ops.holds_whole_trees(t, n, f, c, 64)
    npad = -(-n // 128) * 128
    for rows in (1, 2, 4, 8, 16, 32, 64, 128, 256):
        assert ops.pick_blocks(rows, t, n, f, c, chunk_nodes=True)[2] == npad
    for pinned in ({}, {"block_b": 256, "block_t": 64}):
        backend = create_backend("pallas", esa_forest, mode="integer", **pinned)
        tilings.clear()
        for rows in (1, 2, 4, 8, 16, 32, 64, 128, 256):
            backend.predict_partials(np.zeros((rows, f), np.float32))
        gathers = [s for s in tilings if s[0] == "gather"]
        assert all(s[3] == npad for s in gathers), (pinned, tilings)
        assert len(tilings) == 9
    assert not gathers  # the hand-pinned block_t=64: scan at every bucket

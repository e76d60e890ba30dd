"""Pallas tree-traversal kernel vs the pure-jnp oracle: shape/dtype sweeps,
both gather strategies, padding paths — bit-identical uint32 scores — the
tiling-aligned block picker (rows in 128-lane multiples, trees in
multiples of 8 or the whole forest), and the float entry point that keys
the rows inside the kernel's one program."""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.flint import float_to_key, float_to_key_np
from repro.core.packing import pack_forest
from repro.kernels import ops
from repro.kernels.ops import (
    _VMEM_BUDGET_BYTES, _align_block_t, _block_words, _fits, _smem_words,
    pick_blocks, tree_predict_integer,
)
from repro.kernels.ref import tree_predict_integer_ref
from repro.trees.forest import RandomForestClassifier


def _forest(n_trees, depth, n_features, n_classes, seed=0, n=1500):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, n_features)).astype(np.float32)
    y = rng.integers(0, n_classes, n)
    # inject signal so trees are non-trivial
    y = np.where(X[:, 0] > 0.5, (y + 1) % n_classes, y)
    rf = RandomForestClassifier(n_estimators=n_trees, max_depth=depth, seed=seed).fit(X, y)
    return pack_forest(rf), X


def _args(packed):
    return (
        jnp.asarray(packed.feature),
        jnp.asarray(packed.threshold_key),
        jnp.asarray(packed.left),
        jnp.asarray(packed.right),
        jnp.asarray(packed.leaf_fixed),
    )


@pytest.mark.parametrize("impl", ["gather", "onehot"])
@pytest.mark.parametrize(
    "n_trees,depth,n_features,n_classes",
    [(3, 3, 4, 2), (7, 5, 7, 7), (12, 6, 11, 3), (5, 4, 87, 2)],
)
def test_kernel_matches_ref_sweep(impl, n_trees, depth, n_features, n_classes):
    packed, X = _forest(n_trees, depth, n_features, n_classes)
    keys = float_to_key(jnp.asarray(X[:300]))
    feature, tkey, left, right, leaf = _args(packed)
    ref = tree_predict_integer_ref(keys, feature, tkey, left, right, leaf, packed.max_depth)
    out = tree_predict_integer(
        keys, feature, tkey, left, right, leaf,
        depth=packed.max_depth, block_b=64, impl=impl,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert out.dtype == jnp.uint32


@given(
    bb=st.sampled_from([16, 64, 128]),
    bt=st.integers(min_value=1, max_value=7),
    rows=st.integers(min_value=1, max_value=200),
)
@settings(max_examples=12, deadline=None)
def test_kernel_block_shapes_property(bb, bt, rows):
    """Any requested (block_b, block_t, n_rows) combination is aligned by the
    wrapper and stays bit-identical to ref."""
    packed, X = _forest(7, 4, 5, 3, seed=2)
    t, n = packed.feature.shape
    auto_b, auto_t, auto_n = pick_blocks(rows, t, n, 5, 3, bb)
    assert auto_n == -(-n // 128) * 128  # whole trees
    assert _aligned(auto_b, auto_t, t)
    assert _aligned(auto_b, _align_block_t(bt, t), t)
    keys = float_to_key(jnp.asarray(X[:rows]))
    feature, tkey, left, right, leaf = _args(packed)
    ref = tree_predict_integer_ref(keys, feature, tkey, left, right, leaf, packed.max_depth)
    out = tree_predict_integer(
        keys, feature, tkey, left, right, leaf,
        depth=packed.max_depth, block_b=bb, block_t=min(bt, packed.n_trees),
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_packed_entry_point(small_packed, shuttle_small):
    """Float rows over placed padded tables, the walk given: the partials
    and their first-largest classes equal the integer reference."""
    from repro.core.ensemble import predict_integer

    _, _, Xte, _ = shuttle_small
    acc_ref, pred_ref = predict_integer(small_packed, Xte[:200])
    acc_k = ops.packed_partials(small_packed, Xte[:200], impl="gather",
                                tables=ops.place_tables(small_packed),
                                block_b=32)
    np.testing.assert_array_equal(np.asarray(acc_k), np.asarray(acc_ref))
    np.testing.assert_array_equal(np.argmax(np.asarray(acc_k), axis=1),
                                  np.asarray(pred_ref))


def _aligned(bb, bt, t):
    return bb % 128 == 0 and bb >= 128 and (bt % 8 == 0 or bt == t)


def test_vmem_budget_picker():
    bb, bt, bn = pick_blocks(b=4096, t=128, n=2047, f=87, c=8)
    assert bn == 2048
    # x tiles, node chunks (4 fields padded to 8 rows), leaf chunks, out
    # tiles at padded widths, two pipeline buffers each
    words = 2 * (bb * 88 + bt * 2048 * 8 + bt * 2048 * 8 + bb * 8)
    assert words == _block_words(bb, bt, 2047, 87, 8)
    assert words * 4 <= 8 * 1024 * 1024
    assert _smem_words(bt, 2047) * 4 <= 512 * 1024
    assert _aligned(bb, bt, 128)
    assert (bb, bt) == (256, 8)  # SMEM bounds the tree block at depth 10


def test_vmem_budget_picker_wide_leaf_tables():
    """Regression: with c large relative to n the output tiles and leaf
    chunks can bust the budget at the smallest tree block — the picker used
    to return it unchecked.  The row block must shrink until the working set
    fits, and every choice stays tiling-aligned; where even the smallest
    aligned tiling, (128, min(t, 8)), is over budget the picker raises
    instead of returning it."""
    cases = [
        dict(b=4096, t=4, n=31, f=16, c=16384),   # output block dominates
        dict(b=4096, t=2, n=3, f=8, c=400000),    # degenerate: even bt=1 huge
        dict(b=4096, t=128, n=2047, f=87, c=8),   # the historical case
        dict(b=4096, t=16, n=127, f=8, c=850),    # fits once the rows halve
    ]
    for kw in cases:
        floor = (128, min(kw["t"], 8), kw["n"], kw["f"], kw["c"])
        if not _fits(*floor):
            assert _block_words(*floor) * 4 > _VMEM_BUDGET_BYTES
            for chunk_nodes in (False, True):  # one chunk: nothing to cut
                with pytest.raises(ValueError, match="no tiling"):
                    pick_blocks(**kw, chunk_nodes=chunk_nodes)
            continue
        bb, bt, bn = pick_blocks(**kw)
        assert _aligned(bb, bt, kw["t"]), kw
        assert _fits(bb, bt, bn, kw["f"], kw["c"]), kw
        assert bn == -(-kw["n"] // 128) * 128


@pytest.mark.parametrize(
    "n_trees,depth,n_features,n_classes",
    [(3, 3, 4, 2), (7, 5, 7, 7), (12, 6, 11, 3)],
)
def test_leaf_major_scan_matches_ref_sweep(n_trees, depth, n_features, n_classes):
    """The linear-scan kernel over leaf_major tables == the jnp oracle over
    the padded tables, across forest shapes and with row/tree padding."""
    packed, X = _forest(n_trees, depth, n_features, n_classes)
    keys = float_to_key(jnp.asarray(X[:217]))  # odd rows: padding path
    feature, tkey, left, right, leaf = _args(packed)
    ref = tree_predict_integer_ref(keys, feature, tkey, left, right, leaf, packed.max_depth)
    lm = packed.to_ir().materialize("leaf_major")
    out = tree_predict_integer(
        keys,
        jnp.asarray(lm.feature), jnp.asarray(lm.threshold_key),
        jnp.asarray(lm.left), jnp.asarray(lm.right), jnp.asarray(lm.leaf_fixed),
        depth=lm.max_depth, block_b=64, block_t=2,
        impl="leaf_major", internal_counts=lm.internal_counts,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert out.dtype == jnp.uint32


def test_leaf_major_impl_requires_internal_counts():
    packed, X = _forest(3, 3, 4, 2)
    keys = float_to_key(jnp.asarray(X[:8]))
    feature, tkey, left, right, leaf = _args(packed)
    with pytest.raises(ValueError, match="internal_counts"):
        tree_predict_integer(
            keys, feature, tkey, left, right, leaf,
            depth=packed.max_depth, impl="leaf_major",
        )


def test_packed_entry_point_auto_impl(small_packed, shuttle_small):
    """The serving backend resolves ``impl="auto"`` per layout — the scan on
    leaf_major, gather on padded — and stays bit-identical; a scan pinned
    on a padded backend is refused."""
    from repro.backends import create_backend
    from repro.core.ensemble import predict_integer

    _, _, Xte, _ = shuttle_small
    acc_ref, _ = predict_integer(small_packed, Xte[:150])
    lm = small_packed.to_ir().materialize("leaf_major")
    for packed, walk in ((lm, "leaf_major"), (small_packed, "gather")):
        backend = create_backend("pallas", packed, mode="integer",
                                 block_b=32)
        assert backend.impl == backend.walk(150) == walk
        np.testing.assert_array_equal(backend.predict_partials(Xte[:150]),
                                      np.asarray(acc_ref))
    with pytest.raises(ValueError, match="'padded' layout"):
        create_backend("pallas", small_packed, mode="integer",
                       impl="leaf_major")


# FlInt's edge cases: both zeros (one key), infinities, subnormals of both
# signs, the largest subnormal and finite values, negatives
_SPECIAL = np.array([-0.0, 0.0, np.inf, -np.inf, 1e-45, -1e-45,
                     1.1754942e-38, -1.1754942e-38, 3.4e38, -2.5],
                    np.float32)


def _special_rows(X, rows=203):
    """``rows`` of ``X`` (not a multiple of 128) with every other row
    negated and the first 40 rows made of ``_SPECIAL`` values."""
    X = np.array(X[:rows], np.float32)
    X[1::2] *= -1
    i, j = np.indices((40, X.shape[1]))
    X[:40] = _SPECIAL[(i + j) % len(_SPECIAL)]
    return X


# walk -> (impl of the direct call, request sizes sent through the Gateway,
# the walk its batches take there)
_FUSED_WALKS = {
    "scan": ("leaf_major", [203], {"leaf_major"}),
    "gather": ("gather", [29] * 7, {"gather"}),
    "chunked_scan": ("leaf_major", [5, 198], {"leaf_major"}),
}


@pytest.mark.parametrize("walk", list(_FUSED_WALKS))
def test_fused_key_transform_is_bit_identical(deep, walk, request, tmp_path):
    """The float rows keyed inside the kernel's program: partials equal to
    the jnp oracle on ``float_to_key_np`` keys, and the Gateway's answers to
    the plain reference, on -0.0/+0.0, infinities, subnormals and negatives,
    for the scan, the gather walk and the node-chunked scan."""
    from bench import reference
    from repro.obs import Tracer
    from repro.serve.gateway import Gateway
    from repro.serve.registry import ModelRegistry

    impl, sizes, walks = _FUSED_WALKS[walk]
    if walk == "chunked_scan":
        request.getfixturevalue("small_smem")
    forest, ir, X = deep
    X = _special_rows(X)
    lm, padded = ir.materialize("leaf_major"), ir.materialize("padded")
    t, n = lm.feature.shape
    f, c = lm.n_features, lm.leaf_fixed.shape[-1]
    block_n = pick_blocks(len(X), t, n, f, c, chunk_nodes=True)[2]
    assert (block_n < n) == (walk == "chunked_scan")

    got = ops.packed_partials(lm, X, impl=impl, tables=ops.place_tables(lm))
    want = tree_predict_integer_ref(
        jnp.asarray(float_to_key_np(X)), *_args(padded), padded.max_depth)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    path = tmp_path / "deep.itrf"
    ir.to_itrf(str(path))
    reg = ModelRegistry()
    reg.register_artifact("m", str(path))
    tracer = Tracer()
    gw = Gateway(reg, "integer:pallas@leaf_major", max_delay_ms=1.0,
                 cache_rows=0, tracer=tracer)
    starts = np.cumsum([0] + sizes)

    async def run():
        try:
            return [await gw.submit("m", X[a:b])
                    for a, b in zip(starts, starts[1:])]
        finally:
            await gw.close()

    answers = asyncio.run(run())
    scores = np.concatenate([np.asarray(s) for s, _ in answers])
    preds = np.concatenate([np.asarray(p) for _, p in answers])
    np.testing.assert_array_equal(scores.astype(np.uint64),
                                  reference.partials(forest, X))
    np.testing.assert_array_equal(preds, reference.scores(forest, X)[1])
    assert {s.attrs["impl"] for s in tracer.spans()
            if s.name == "launch" and "impl" in s.attrs} == walks


def test_fused_path_traces_the_key_transform_once(small_packed, shuttle_small,
                                                  monkeypatch):
    """Two calls at one shape: ``float_to_key`` is traced into the kernel's
    program once, and no call runs it eagerly."""
    _, _, Xte, _ = shuttle_small
    lm = small_packed.to_ir().materialize("leaf_major")
    seen, real = [], ops.float_to_key

    def counting(x):
        seen.append(isinstance(x, jax.core.Tracer))
        return real(x)

    monkeypatch.setattr(ops, "float_to_key", counting)
    ops._traverse.clear_cache()  # a program of this shape is traced here
    tables = ops.place_tables(lm)
    want = tree_predict_integer_ref(
        float_to_key(jnp.asarray(Xte[:37])), *_args(small_packed),
        small_packed.max_depth)
    for _ in range(2):
        got = ops.packed_partials(lm, Xte[:37], impl="leaf_major",
                                  tables=tables)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert seen == [True]

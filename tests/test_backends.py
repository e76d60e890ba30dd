"""Cross-(backend, layout) conformance: the IR/backend layers' anchor suite.

InTreeger's claim — one trained ensemble, bit-identical integer-only
inference on any hardware — becomes testable through the TreeBackend
protocol and the ForestIR layout layer: for the deterministic modes
(flint/integer), every registered backend must produce *bit-identical*
scores and predictions on randomized forests, through every ForestIR layout
it declares (padded / ragged / leaf_major), including degenerate forests
(single-node stumps, T == 1, strongly depth-skewed).  Plus: registry
lookup/error behavior, capability/layout validation, TreeEngine bucketing
edge cases, and the deep-tree C emitter guard.

Run standalone via ``make conformance``.
"""
import numpy as np
import pytest

from repro.backends import (
    BackendCapabilities,
    TreeBackend,
    available_backends,
    backend_class,
    create_backend,
)
from repro.ir import ForestIR
from repro.serve.engine import TreeEngine, bucket_rows

ALL_BACKENDS = [
    "reference",
    "pallas",
    "bitvector",
    pytest.param("native_c", marks=pytest.mark.requires_gcc),
    pytest.param("native_c_table", marks=pytest.mark.requires_gcc),
    pytest.param("native_c_bitvector", marks=pytest.mark.requires_gcc),
]


@pytest.fixture(scope="module", params=[(3, 7, 5), (11, 16, 7)],
                ids=["t7d5", "t16d7"])
def random_case(request):
    """(packed, rows): a randomized forest + probe rows, per param seed."""
    from repro.core.packing import pack_forest
    from repro.data.tabular import make_shuttle_like, train_test_split
    from repro.trees.forest import RandomForestClassifier

    seed, n_trees, depth = request.param
    X, y = make_shuttle_like(n=3000, seed=seed)
    Xtr, ytr, Xte, _ = train_test_split(X, y, seed=seed)
    rf = RandomForestClassifier(
        n_estimators=n_trees, max_depth=depth, seed=seed
    ).fit(Xtr, ytr)
    return pack_forest(rf), Xte[:97]  # odd row count: exercises padding


def _scores(backend, rows):
    s, p = backend.predict_scores(rows)
    return np.asarray(s), np.asarray(p)


# ------------------------------------------------------------------ registry

def test_registry_has_all_six_backends():
    assert {"reference", "pallas", "native_c", "native_c_table",
            "bitvector", "native_c_bitvector"} <= set(available_backends())


def test_registry_unknown_name_lists_available(small_packed):
    with pytest.raises(KeyError, match="reference"):
        backend_class("no-such-backend")
    with pytest.raises(KeyError, match="no-such-backend"):
        create_backend("no-such-backend", small_packed)


def test_backend_rejects_unsupported_mode(small_packed):
    # pallas runs the integer accumulation; since the partials/finalize
    # split that serves both deterministic modes, but never float
    assert backend_class("pallas").capabilities.modes == ("flint", "integer")
    with pytest.raises(ValueError, match="pallas"):
        create_backend("pallas", small_packed, mode="float")


def test_capability_flags():
    ref = backend_class("reference").capabilities
    nat = backend_class("native_c").capabilities
    pal = backend_class("pallas").capabilities
    tbl = backend_class("native_c_table").capabilities
    assert set(ref.modes) == {"float", "flint", "integer"}
    assert ref.deterministic_modes == ("flint", "integer")
    assert pal.deterministic_modes == ("flint", "integer")
    assert ref.compiles_per_shape and pal.compiles_per_shape
    assert not nat.compiles_per_shape  # the C loop takes any row count
    assert pal.preferred_block_rows == 256  # aligns buckets with kernel tiles
    # layout axis: node-table backends walk both (T, N) orderings; the
    # table-walk C backend is the ragged layout's consumer.  Pallas prefers
    # leaf_major (the linear-scan kernel's layout); the others stay padded.
    # reference additionally serves the packed_leaf artifact layout by
    # decoding the group-quantized leaf table through the exact codec.
    assert set(ref.supported_layouts) == {"padded", "leaf_major",
                                          "packed_leaf"}
    for caps in (pal, nat):
        assert set(caps.supported_layouts) == {"padded", "leaf_major"}
    assert ref.preferred_layout == "padded"
    assert nat.preferred_layout == "padded"
    assert pal.preferred_layout == "leaf_major"
    assert tbl.supported_layouts == ("ragged",)
    assert tbl.preferred_layout == "ragged"
    assert set(tbl.modes) == {"flint", "integer"}  # integer-compare modes only
    assert tbl.preferred_block_rows == 8  # row-blocked table walk default
    assert not tbl.compiles_per_shape
    # the QuickScorer pair both walk (only) the bitvector layout; the jnp
    # path jit-compiles per batch shape, the C path takes any row count
    bv = backend_class("bitvector").capabilities
    cbv = backend_class("native_c_bitvector").capabilities
    for caps in (bv, cbv):
        assert set(caps.modes) == {"flint", "integer"}
        assert caps.deterministic_modes == ("flint", "integer")
        assert caps.supported_layouts == ("bitvector",)
        assert caps.preferred_layout == "bitvector"
    assert bv.compiles_per_shape
    assert not cbv.compiles_per_shape


def test_backend_rejects_unsupported_layout(small_packed):
    ragged = small_packed.to_ir().materialize("ragged")
    with pytest.raises(ValueError, match="layout"):
        create_backend("pallas", ragged, mode="integer")
    with pytest.raises(ValueError, match="layout"):
        create_backend("native_c_table", small_packed, mode="integer")
    with pytest.raises(ValueError, match="layout"):
        TreeEngine(small_packed, mode="integer", backend="reference",
                   layout="ragged")
    # a pre-constructed backend instance cannot satisfy a conflicting pin —
    # silently serving its existing artifact would ignore the request
    from repro.backends import ReferenceBackend

    with pytest.raises(ValueError, match="conflicts"):
        TreeEngine(backend=ReferenceBackend(small_packed, "integer"),
                   layout="leaf_major")


# --------------------------------------------------- cross-backend identity

def test_reference_vs_pallas_integer_bit_identical(random_case):
    packed, rows = random_case
    s_ref, p_ref = _scores(create_backend("reference", packed, mode="integer"), rows)
    s_pal, p_pal = _scores(create_backend("pallas", packed, mode="integer"), rows)
    np.testing.assert_array_equal(s_ref, s_pal)
    np.testing.assert_array_equal(p_ref, p_pal)


@pytest.mark.requires_gcc
@pytest.mark.parametrize("mode", ["flint", "integer"])
def test_reference_vs_native_c_bit_identical(random_case, mode):
    packed, rows = random_case
    s_ref, p_ref = _scores(create_backend("reference", packed, mode=mode), rows)
    s_nat, p_nat = _scores(create_backend("native_c", packed, mode=mode), rows)
    assert s_nat.dtype == s_ref.dtype
    np.testing.assert_array_equal(s_ref, s_nat)
    np.testing.assert_array_equal(p_ref, p_nat)


@pytest.mark.requires_gcc
def test_all_backends_identical_through_engine(small_packed, shuttle_small):
    """The acceptance property, at the TreeEngine level: same model, three
    backends, bit-identical integer scores through the bucketed path."""
    _, _, Xte, _ = shuttle_small
    rows = Xte[:50]
    outs = {
        name: TreeEngine(small_packed, mode="integer", backend=name).predict_scores(rows)
        for name in ("reference", "pallas", "native_c")
    }
    s_ref, p_ref = outs["reference"]
    for name in ("pallas", "native_c"):
        np.testing.assert_array_equal(outs[name][0], s_ref)
        np.testing.assert_array_equal(outs[name][1], p_ref)


@pytest.mark.requires_gcc
def test_gateway_serves_same_model_through_every_backend(small_forest, shuttle_small):
    """Gateway/ModelRegistry route per-(model, mode, backend) and all
    deterministic-mode responses are bit-identical across backends."""
    import asyncio

    from repro.serve.gateway import Gateway
    from repro.serve.registry import ModelRegistry

    _, _, Xte, _ = shuttle_small
    rows = Xte[:16]
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)

    results = {}
    for name in ("reference", "pallas", "native_c"):
        gw = Gateway(reg, mode="integer", backend=name, max_delay_ms=1.0)
        s, p = asyncio.run(gw.submit("m", rows))
        asyncio.run(gw.close())
        results[name] = (s, p)
    s_ref, p_ref = results["reference"]
    for name in ("pallas", "native_c"):
        np.testing.assert_array_equal(results[name][0], s_ref)
        np.testing.assert_array_equal(results[name][1], p_ref)
    # one engine per (mode, backend) route, memoized on the version
    mv = reg.get("m")
    assert mv.engine("integer", backend="pallas") is mv.engine("integer", backend="pallas")
    assert mv.engine("integer", backend="pallas") is not mv.engine("integer")


# ----------------------------------------------- cross-layout conformance

from forest_cases import (  # shared with test_plans.py
    DEGENERATE_FORESTS as _DEGENERATE,
    forest_from_trees as _forest_from_trees,
)


@pytest.fixture(scope="module", params=sorted(_DEGENERATE), ids=sorted(_DEGENERATE))
def degenerate_case(request):
    """(ForestIR, probe rows) for one degenerate forest shape."""
    forest = _DEGENERATE[request.param]()
    ir = ForestIR.from_forest(forest)
    rng = np.random.default_rng(hash(request.param) % 2**32)
    rows = rng.normal(0.0, 6.0, (33, ir.n_features)).astype(np.float32)
    return ir, rows


def _layout_mode_pairs(backend):
    caps = backend_class(backend).capabilities
    return [(lay, mode) for lay in caps.supported_layouts
            for mode in caps.deterministic_modes]


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_cross_layout_bit_identity_randomized(random_case, backend):
    """The acceptance property: flint/integer scores bit-identical across
    every (layout, backend) pair the backend declares, randomized forests."""
    packed, rows = random_case
    ir = packed.to_ir()
    ref = {}  # one reference run per mode; layouts reuse it
    for layout, mode in _layout_mode_pairs(backend):
        if mode not in ref:
            ref[mode] = _scores(create_backend("reference", packed, mode=mode), rows)
        s_ref, p_ref = ref[mode]
        eng = TreeEngine(ir, mode=mode, backend=backend, layout=layout)
        s, p = eng.predict_scores(rows)
        assert eng.layout == layout
        np.testing.assert_array_equal(np.asarray(s), s_ref,
                                      err_msg=f"{backend}/{layout}/{mode}")
        np.testing.assert_array_equal(np.asarray(p), p_ref,
                                      err_msg=f"{backend}/{layout}/{mode}")


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_cross_layout_bit_identity_degenerate(degenerate_case, backend):
    """Stumps, T == 1, and depth-skewed forests through every (layout, mode)
    pair of every backend — the packing edge cases padding used to hide."""
    ir, rows = degenerate_case
    ref = {}
    for layout, mode in _layout_mode_pairs(backend):
        if mode not in ref:
            ref[mode] = _scores(
                create_backend("reference", ir.materialize("padded"), mode=mode),
                rows,
            )
        s_ref, p_ref = ref[mode]
        eng = TreeEngine(ir, mode=mode, backend=backend, layout=layout)
        s, p = eng.predict_scores(rows)
        np.testing.assert_array_equal(np.asarray(s), s_ref,
                                      err_msg=f"{backend}/{layout}/{mode}")
        np.testing.assert_array_equal(np.asarray(p), p_ref,
                                      err_msg=f"{backend}/{layout}/{mode}")


# ------------------------------------------- execution-variant conformance
# The layout axis above is crossed with each backend's execution variants:
# the Pallas walk strategies (per-depth gather / onehot select / leaf_major
# linear scan) and the table-walk C row-block sizes.  Every variant must be
# bit-identical to the reference walk on randomized AND degenerate forests.

PALLAS_IMPLS = ["gather", "onehot", "leaf_major"]
BLOCK_ROWS = [1, 4, 8]


def _pallas_variant_engine(ir, impl):
    layout = "leaf_major" if impl == "leaf_major" else "padded"
    return TreeEngine(ir, mode="integer", backend="pallas", layout=layout,
                      backend_kwargs={"impl": impl})


@pytest.mark.parametrize("impl", PALLAS_IMPLS)
def test_pallas_impl_variants_randomized(random_case, impl):
    packed, rows = random_case
    s_ref, p_ref = _scores(create_backend("reference", packed, mode="integer"), rows)
    eng = _pallas_variant_engine(packed.to_ir(), impl)
    assert eng.backend.impl == impl
    s, p = eng.predict_scores(rows)
    np.testing.assert_array_equal(np.asarray(s), s_ref, err_msg=f"pallas/{impl}")
    np.testing.assert_array_equal(np.asarray(p), p_ref, err_msg=f"pallas/{impl}")


@pytest.mark.parametrize("impl", ["gather", "leaf_major"])
def test_pallas_impl_variants_degenerate(degenerate_case, impl):
    """Stumps (no internal prefix at all), T == 1, and depth-skewed trees
    through both the gather walk and the linear scan."""
    ir, rows = degenerate_case
    s_ref, p_ref = _scores(
        create_backend("reference", ir.materialize("padded"), mode="integer"), rows
    )
    s, p = _pallas_variant_engine(ir, impl).predict_scores(rows)
    np.testing.assert_array_equal(np.asarray(s), s_ref, err_msg=f"pallas/{impl}")
    np.testing.assert_array_equal(np.asarray(p), p_ref, err_msg=f"pallas/{impl}")


def test_pallas_leaf_major_impl_rejects_padded_artifact(small_packed):
    with pytest.raises(ValueError, match="leaf_major"):
        create_backend("pallas", small_packed, mode="integer", impl="leaf_major")


def _child_before_parent_forest():
    """A topologically valid tree whose arrays order an internal child
    *before* its parent (0 -> 3 -> 1) — legal for every gather walker, but
    it breaks the forward-scan invariant; imported artifacts can look like
    this."""
    from repro.trees.cart import TreeArrays

    feature = np.array([0, 0, -1, 0, -1, -1, -1], np.int32)
    threshold = np.array([0.0, -2.0, 0, 2.0, 0, 0, 0], np.float32)
    left = np.array([3, 4, 2, 1, 4, 5, 6], np.int32)
    right = np.array([2, 5, 2, 6, 4, 5, 6], np.int32)
    probs = np.zeros((7, 3))
    for leaf, c in ((2, 0), (4, 1), (5, 2), (6, 0)):
        probs[leaf, c] = 1.0
    tree = TreeArrays(feature=feature, threshold=threshold, left=left,
                      right=right, leaf_probs=probs, depth=3)
    return _forest_from_trees([tree], 3, 2)


def test_pallas_auto_falls_back_to_gather_on_unscannable_order():
    """leaf_major materialization of a child-before-parent forest records no
    internal prefix; impl='auto' gather-walks it and stays bit-identical,
    while pinning the scan fails loudly instead of mis-scoring."""
    ir = ForestIR.from_forest(_child_before_parent_forest())
    lm = ir.materialize("leaf_major")
    assert lm.internal_counts is None
    rows = np.random.default_rng(3).normal(0, 3, (29, 2)).astype(np.float32)
    s_ref, p_ref = _scores(
        create_backend("reference", ir.materialize("padded"), mode="integer"), rows
    )
    eng = TreeEngine(ir, mode="integer", backend="pallas", layout="leaf_major")
    assert eng.backend.impl == "gather"  # auto resolved away from the scan
    s, p = eng.predict_scores(rows)
    np.testing.assert_array_equal(np.asarray(s), s_ref)
    np.testing.assert_array_equal(np.asarray(p), p_ref)
    with pytest.raises(ValueError, match="scannable"):
        create_backend("pallas", lm, mode="integer", impl="leaf_major")


# case -> (the forest's artifact, the batch's rows, the walk it takes)
_WALK_RULE = {
    "63_rows_gather": ("leaf_major", 63, "gather"),
    "64_rows_scan": ("leaf_major", 64, "leaf_major"),
    "too_large_for_a_cell_scan": ("deep", 1, "leaf_major"),
    "padded_gather": ("padded", 256, "gather"),
    "unscannable_gather": ("unscannable", 256, "gather"),
}


@pytest.mark.parametrize("case", list(_WALK_RULE))
def test_pallas_walk_rule(case, request, small_packed, monkeypatch):
    """The backend's one walk rule with ``impl="auto"``: the scan on a
    scannable leaf_major layout, gather on padded or where the node order
    cannot be scanned, gather below 64 rows where whole trees fit.  The walk
    asked of ``walk`` is the one the kernel's call is launched with."""
    import jax.numpy as jnp

    import repro.kernels.ops as ops

    art, rows, want = _WALK_RULE[case]
    if art == "deep":
        request.getfixturevalue("small_smem")
        art = request.getfixturevalue("deep")[1].materialize("leaf_major")
    elif art == "unscannable":
        art = ForestIR.from_forest(
            _child_before_parent_forest()).materialize("leaf_major")
    else:
        art = small_packed.to_ir().materialize(art)
    launched = []

    def stub(x, *tables, impl, **kw):  # the choice is checked, not the kernel
        launched.append(impl)
        return jnp.zeros((x.shape[0], tables[4].shape[-1]), jnp.uint32)

    monkeypatch.setattr(ops, "_traverse", stub)
    backend = create_backend("pallas", art, mode="integer")
    assert backend.walk(rows) == want
    backend.predict_partials(np.zeros((rows, art.n_features), np.float32))
    assert launched == [want]


def test_pallas_places_its_tables_once(random_case, monkeypatch):
    """The serving backend puts its node tables on the device on its first
    batch and walks that one copy after, through the scan and the
    small-batch gather fallback alike, with partials bit-identical to the
    reference walk."""
    import repro.kernels.ops as ops

    packed, rows = random_case
    ref = create_backend("reference", packed, mode="integer")
    eng = TreeEngine(packed.to_ir(), "integer:pallas@leaf_major")
    walked, real = [], ops.packed_partials

    def spy(packed, X, *, impl, tables, **kw):
        walked.append((impl, tables))
        return real(packed, X, impl=impl, tables=tables, **kw)

    monkeypatch.setattr(ops, "packed_partials", spy)
    for n in (3, 97, 17, 40):  # buckets 4, 128, 32, 64: gather, scan, gather, scan
        np.testing.assert_array_equal(eng.predict_partials(rows[:n]),
                                      np.asarray(ref.predict_partials(rows[:n])))
    assert [impl for impl, _ in walked] == ["gather", "leaf_major"] * 2
    assert all(tables is walked[0][1] for _, tables in walked)
    assert eng.drain_stage_timings()["place"][1] == 1


@pytest.mark.requires_gcc
@pytest.mark.parametrize("block_rows", BLOCK_ROWS)
@pytest.mark.parametrize("mode", ["flint", "integer"])
def test_table_walk_block_rows_randomized(random_case, block_rows, mode):
    """Scalar vs row-blocked table-walk C: bit-identical at every block
    size, including batches that leave a partial tail block (97 rows)."""
    packed, rows = random_case
    s_ref, p_ref = _scores(create_backend("reference", packed, mode=mode), rows)
    eng = TreeEngine(packed.to_ir(), mode=mode, backend="native_c_table",
                     backend_kwargs={"block_rows": block_rows})
    assert eng.backend.block_rows == block_rows
    s, p = eng.predict_scores(rows)
    np.testing.assert_array_equal(np.asarray(s), s_ref,
                                  err_msg=f"table/{block_rows}/{mode}")
    np.testing.assert_array_equal(np.asarray(p), p_ref,
                                  err_msg=f"table/{block_rows}/{mode}")


@pytest.mark.requires_gcc
@pytest.mark.parametrize("block_rows", BLOCK_ROWS)
def test_table_walk_block_rows_degenerate(degenerate_case, block_rows):
    """Degenerate forests through the blocked walk: stumps never enter the
    level loop, depth-skewed trees exercise the all-leaves early exit."""
    ir, rows = degenerate_case
    s_ref, p_ref = _scores(
        create_backend("reference", ir.materialize("padded"), mode="integer"), rows
    )
    eng = TreeEngine(ir, mode="integer", backend="native_c_table",
                     backend_kwargs={"block_rows": block_rows})
    s, p = eng.predict_scores(rows)
    np.testing.assert_array_equal(np.asarray(s), s_ref,
                                  err_msg=f"table/{block_rows}")
    np.testing.assert_array_equal(np.asarray(p), p_ref,
                                  err_msg=f"table/{block_rows}")


@pytest.mark.requires_gcc
@pytest.mark.parametrize("interleave", [1, 4, 8])
@pytest.mark.parametrize("mode", ["flint", "integer"])
def test_bitvector_interleave_widths_randomized(random_case, interleave, mode):
    """v-QuickScorer interleaved comparison groups: every width is
    bit-identical to the reference walk — the stream pads with inert entries
    (a key that never tests true, an all-ones mask) and grouping never
    reorders a real mask application."""
    packed, rows = random_case
    s_ref, p_ref = _scores(create_backend("reference", packed, mode=mode), rows)
    eng = TreeEngine(packed.to_ir(), mode=mode, backend="native_c_bitvector",
                     backend_kwargs={"interleave": interleave})
    assert eng.backend.interleave == interleave
    s, p = eng.predict_scores(rows)
    np.testing.assert_array_equal(np.asarray(s), s_ref,
                                  err_msg=f"bitvector/k{interleave}/{mode}")
    np.testing.assert_array_equal(np.asarray(p), p_ref,
                                  err_msg=f"bitvector/k{interleave}/{mode}")


@pytest.mark.requires_gcc
@pytest.mark.parametrize("interleave", [1, 4, 8])
def test_bitvector_interleave_widths_degenerate(degenerate_case, interleave):
    """Degenerate forests through the interleaved scorer: stumps contribute
    no comparisons at all (pure padding groups), single-tree forests leave
    most of a K-group inert."""
    ir, rows = degenerate_case
    s_ref, p_ref = _scores(
        create_backend("reference", ir.materialize("padded"), mode="integer"), rows
    )
    eng = TreeEngine(ir, mode="integer", backend="native_c_bitvector",
                     backend_kwargs={"interleave": interleave})
    s, p = eng.predict_scores(rows)
    np.testing.assert_array_equal(np.asarray(s), s_ref,
                                  err_msg=f"bitvector/k{interleave}")
    np.testing.assert_array_equal(np.asarray(p), p_ref,
                                  err_msg=f"bitvector/k{interleave}")


@pytest.fixture(scope="module")
def itrf_case(random_case, tmp_path_factory):
    """The same randomized forest, round-tripped through an ITRF artifact
    and reloaded as zero-copy mmap views — the registry's load path."""
    packed, rows = random_case
    ir = packed.to_ir()
    path = tmp_path_factory.mktemp("itrf") / "conformance.itrf"
    ir.to_itrf(str(path), pack_leaves=True)
    return ir, ForestIR.from_itrf(str(path), mmap=True), rows


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_mmap_artifact_bit_identity(itrf_case, backend):
    """The conformance matrix over an mmap-loaded artifact: every (layout,
    mode) pair of every backend, built from read-only views over the file's
    pages, must match the direct in-memory IR bit for bit."""
    ir, ir_mmap, rows = itrf_case
    assert not ir_mmap.feature.flags.writeable  # really the mapped pages
    for layout, mode in _layout_mode_pairs(backend):
        s_ref, p_ref = _scores(
            create_backend("reference", ir.materialize("padded"), mode=mode),
            rows)
        eng = TreeEngine(ir_mmap, mode=mode, backend=backend, layout=layout)
        s, p = eng.predict_scores(rows)
        np.testing.assert_array_equal(np.asarray(s), s_ref,
                                      err_msg=f"itrf/{backend}/{layout}/{mode}")
        np.testing.assert_array_equal(np.asarray(p), p_ref,
                                      err_msg=f"itrf/{backend}/{layout}/{mode}")


def test_degenerate_ragged_has_no_padding_waste(degenerate_case):
    ir, _ = degenerate_case
    sizes = ir.nbytes_by_layout(mode="integer")
    if ir.max_nodes > int(ir.node_counts.min()):
        assert sizes["ragged"] < sizes["padded"]


# -------------------------------------------------------- engine bucketing

def test_bucket_rows_at_and_past_the_cap():
    assert bucket_rows(4096, max_bucket=4096) == 4096
    assert bucket_rows(4097, max_bucket=4096) == 8192
    assert bucket_rows(8, max_bucket=8) == 8
    assert bucket_rows(9, max_bucket=8) == 16
    assert bucket_rows(17, max_bucket=8) == 24


class _RaisingBackend(TreeBackend):
    name = "raising-stub"
    capabilities = BackendCapabilities(
        modes=("integer",), deterministic_modes=("integer",)
    )

    def predict_scores(self, X):
        raise RuntimeError("backend exploded")


def test_failed_predict_does_not_mark_bucket_compiled(small_packed):
    eng = TreeEngine(backend=_RaisingBackend(small_packed, "integer"))
    with pytest.raises(RuntimeError, match="exploded"):
        eng.predict(np.zeros((5, small_packed.n_features), np.float32))
    assert eng.compiled_buckets == set()  # a raising predict compiled nothing


def test_warm_covers_max_bucket_multiples(small_packed, shuttle_small):
    """warm() must pre-compile the max_bucket-multiple shapes that batches
    with b >= max_bucket are padded to, not just the power-of-two buckets."""
    _, _, Xte, _ = shuttle_small
    eng = TreeEngine(small_packed, mode="integer", max_bucket=8)
    eng.warm(20)
    assert eng.compiled_buckets == {1, 2, 4, 8, 16, 24}
    # every batch size the warm range promises is now a known bucket
    pre = set(eng.compiled_buckets)
    for b in (3, 8, 9, 20):
        eng.predict_scores(Xte[:b])
    assert eng.compiled_buckets == pre


def test_warm_covers_rounded_up_power_of_two(small_packed, shuttle_small):
    """A non-power-of-two max_rows must still warm the bucket its largest
    batches round UP to (warm(20) serves 17..20-row batches from bucket 32)."""
    _, _, Xte, _ = shuttle_small
    eng = TreeEngine(small_packed, mode="integer", max_bucket=64)
    eng.warm(20)
    assert eng.compiled_buckets == {1, 2, 4, 8, 16, 32}
    pre = set(eng.compiled_buckets)
    eng.predict_scores(Xte[:17])
    assert eng.compiled_buckets == pre


def test_engine_skips_padding_for_shape_oblivious_backends(small_packed, shuttle_small):
    class Probe(TreeBackend):
        name = "probe"
        capabilities = BackendCapabilities(
            modes=("integer",), deterministic_modes=("integer",),
            compiles_per_shape=False,
        )
        seen = []

        def predict_scores(self, X):
            self.seen.append(X.shape[0])
            c = self.packed.n_classes
            return (np.zeros((X.shape[0], c), np.uint32),
                    np.zeros(X.shape[0], np.int32))

    _, _, Xte, _ = shuttle_small
    eng = TreeEngine(backend=Probe(small_packed, "integer"))
    eng.predict_scores(Xte[:5])
    assert eng.backend.seen == [5]  # not padded to 8
    eng.warm(64)
    assert eng.backend.seen == [5, 1]  # warm = one artifact-building call

"""Execution-plan conformance: the sharded-serving layer's anchor suite.

The paper's integer-only accumulation makes the ensemble sum associative, so
a forest can be carved across devices or backends and the partial scores
merged with zero precision loss.  This suite pins that as an invariant: for
the deterministic modes (flint/integer), every execution plan — single-shard,
tree-parallel over {2, 3, 8} shards (threaded per-shard backends, or one
shard_map'd device computation when XLA exposes enough devices — ``make
conformance`` forces 8 host devices to run that path for real), row-parallel
over {2, 4} shards, and heterogeneous tree-parallel plans mixing two
backends — must produce scores *bit-identical* to the single-shard reference,
through every (backend, layout) route, on randomized AND degenerate forests.

Plus: ``ForestIR.subset`` round trips (slice bit-identity, partial-sum
re-concatenation, quantization-scale carrying), capability-driven plan
auto-selection, warm() covering every shard, and per-shard timing drains.

Run with ``make conformance``.
"""
import numpy as np
import pytest

from forest_cases import DEGENERATE_FORESTS
from repro.backends import backend_class, create_backend
from repro.core.ensemble import finalize_partials
from repro.ir import ForestIR
from repro.plan import (
    RowParallelPlan,
    SingleShardPlan,
    TreeParallelPlan,
    available_plans,
    create_plan,
    plan_class,
    select_plan,
    thread_shard_cap,
    tree_ranges,
)
from repro.serve.engine import TreeEngine

ALL_BACKENDS = [
    "reference",
    "pallas",
    "bitvector",
    pytest.param("native_c", marks=pytest.mark.requires_gcc),
    pytest.param("native_c_table", marks=pytest.mark.requires_gcc),
    pytest.param("native_c_bitvector", marks=pytest.mark.requires_gcc),
]

# the acceptance matrix: every plan spec below x every backend x its layouts
PLAN_SPECS = [
    ("single", None),
    ("tree_parallel", 2),
    ("tree_parallel", 3),
    ("tree_parallel", 8),
    ("row_parallel", 2),
    ("row_parallel", 4),
]


def _scores(obj, rows):
    s, p = obj.predict_scores(rows)
    return np.asarray(s), np.asarray(p)


def _layout_mode_pairs(backend):
    caps = backend_class(backend).capabilities
    return [(lay, mode) for lay in caps.supported_layouts
            for mode in caps.deterministic_modes]


@pytest.fixture(scope="module")
def probe_rows(shuttle_small):
    _, _, Xte, _ = shuttle_small
    return Xte[:33]  # odd row count: partial row-parallel chunks + padding


@pytest.fixture(scope="module")
def reference_scores(small_packed, probe_rows):
    """One single-shard reference run per mode; every plan case reuses it."""
    return {
        mode: _scores(create_backend("reference", small_packed, mode=mode),
                      probe_rows)
        for mode in ("flint", "integer")
    }


# ------------------------------------------------------------------ registry

def test_plan_registry_contents():
    assert {"single", "tree_parallel", "row_parallel"} <= set(available_plans())
    with pytest.raises(KeyError, match="single"):
        plan_class("no-such-plan")


def test_plan_auto_selection(small_packed):
    sel = lambda **kw: select_plan(None, **{"backend": "reference", **kw})
    assert sel(mode="integer") == "single"
    assert sel(mode="integer", shards=1) == "single"
    assert sel(mode="integer", shards=4, model=small_packed) == "tree_parallel"
    assert sel(mode="flint", shards=2, model=small_packed) == "tree_parallel"
    # float has no integer partials -> shard the batch instead
    assert sel(mode="float", shards=4, model=small_packed) == "row_parallel"
    # a sequence of backends IS a heterogeneous tree-parallel request
    assert select_plan(None, mode="integer",
                       backend=("reference", "pallas")) == "tree_parallel"
    # explicit names pass through; unknown ones fail fast
    assert select_plan("row_parallel", mode="integer",
                       backend="reference", shards=8) == "row_parallel"
    with pytest.raises(KeyError, match="no-such"):
        select_plan("no-such-plan", mode="integer", backend="reference")


def test_tree_parallel_rejects_float(small_packed):
    with pytest.raises(ValueError, match="partials"):
        create_plan("tree_parallel", small_packed, mode="float", shards=2)


def test_single_plan_rejects_multi_shards(small_packed):
    with pytest.raises(ValueError, match="single"):
        create_plan("single", small_packed, mode="integer", shards=3)


def test_tree_ranges_contiguous_and_capped():
    assert tree_ranges(9, 3) == [(0, 3), (3, 6), (6, 9)]
    assert tree_ranges(9, 2) == [(0, 4), (4, 9)] or \
        tree_ranges(9, 2) == [(0, 5), (5, 9)]
    # more shards than trees: empties dropped, one tree per shard
    assert tree_ranges(3, 8) == [(0, 1), (1, 2), (2, 3)]
    spans = tree_ranges(11, 4)
    assert spans[0][0] == 0 and spans[-1][1] == 11
    assert all(a2 == b1 for (_, b1), (a2, _) in zip(spans[:-1], spans[1:]))


def test_threaded_shards_clamped_to_core_budget(small_packed, probe_rows,
                                                reference_scores, monkeypatch):
    """BENCH_7 regression: oversubscribed threaded fan-out (s4/s8 on a 1-core
    host ran 1.4-1.8x slower than single-shard) is clamped to the core budget
    — and clamping never perturbs the merged partials."""
    monkeypatch.setattr("os.cpu_count", lambda: 2)
    assert thread_shard_cap() == 2
    ir = small_packed.to_ir()
    thr = {"device_parallel": False}
    eng = TreeEngine(ir, mode="integer", plan="tree_parallel", shards=8,
                     plan_kwargs=thr)
    assert not eng.plan.fused and eng.n_shards == 2
    s, p = _scores(eng, probe_rows)
    np.testing.assert_array_equal(s, reference_scores["integer"][0])
    np.testing.assert_array_equal(p, reference_scores["integer"][1])
    # the floor keeps two shards even on a single core (s2 beat single there)
    monkeypatch.setattr("os.cpu_count", lambda: 1)
    assert thread_shard_cap() == 2
    # clamp_shards=False opts out — scaling benches measure the full sweep
    eng = TreeEngine(ir, mode="integer", plan="tree_parallel", shards=8,
                     plan_kwargs={**thr, "clamp_shards": False})
    assert eng.n_shards == min(8, ir.n_trees)
    # an explicit heterogeneous mix is an explicit fan-out request: honored
    eng = TreeEngine(ir, mode="integer",
                     backend=("reference", "reference", "reference",
                              "reference"), plan_kwargs=thr)
    assert eng.n_shards == min(4, ir.n_trees)
    import jax

    if len(jax.devices()) >= 8:  # the forced-device conformance config
        # the fused shard_map path is never capped: devices are not cores
        eng = TreeEngine(ir, mode="integer", plan="tree_parallel", shards=8)
        assert eng.plan.fused and eng.n_shards == min(8, ir.n_trees)


# ----------------------------------------------------- the acceptance matrix

@pytest.mark.parametrize("plan,shards", PLAN_SPECS,
                         ids=[f"{p}-{s}" for p, s in PLAN_SPECS])
@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_plan_bit_identity_randomized(small_packed, probe_rows,
                                      reference_scores, backend, plan, shards):
    """flint/integer scores bit-identical across {single, tree_parallel(2,3,8),
    row_parallel(2,4)} x all four backends x every layout each declares."""
    ir = small_packed.to_ir()
    for layout, mode in _layout_mode_pairs(backend):
        s_ref, p_ref = reference_scores[mode]
        eng = TreeEngine(ir, mode=mode, backend=backend, layout=layout,
                         plan=plan, shards=shards)
        s, p = _scores(eng, probe_rows)
        np.testing.assert_array_equal(
            s, s_ref, err_msg=f"{plan}({shards})/{backend}/{layout}/{mode}")
        np.testing.assert_array_equal(
            p, p_ref, err_msg=f"{plan}({shards})/{backend}/{layout}/{mode}")
        assert eng.plan_name == plan
        if plan == "tree_parallel":
            # the fused device path keeps the requested carve; the threaded
            # path additionally caps fan-out at the host's core budget
            want = min(shards, ir.n_trees)
            if not eng.plan.fused:
                want = min(want, thread_shard_cap())
            assert eng.n_shards == want


@pytest.mark.parametrize("plan,shards",
                         [("tree_parallel", 3), ("row_parallel", 2)])
@pytest.mark.parametrize("case", sorted(DEGENERATE_FORESTS))
def test_plan_bit_identity_degenerate(case, plan, shards):
    """Stumps, T == 1, and depth-skewed forests through the sharded plans:
    subsetting must survive single-node trees and shard counts exceeding the
    tree count (tree_parallel over one tree degenerates to single-shard)."""
    ir = ForestIR.from_forest(DEGENERATE_FORESTS[case]())
    rng = np.random.default_rng(hash(case) % 2**32)
    rows = rng.normal(0.0, 6.0, (19, ir.n_features)).astype(np.float32)
    for mode in ("flint", "integer"):
        s_ref, p_ref = _scores(
            create_backend("reference", ir.materialize("padded"), mode=mode),
            rows,
        )
        eng = TreeEngine(ir, mode=mode, plan=plan, shards=shards)
        s, p = _scores(eng, rows)
        np.testing.assert_array_equal(s, s_ref, err_msg=f"{plan}/{case}/{mode}")
        np.testing.assert_array_equal(p, p_ref, err_msg=f"{plan}/{case}/{mode}")


@pytest.mark.parametrize("plan,shards", [("tree_parallel", 3),
                                         ("row_parallel", 4)])
def test_threaded_pallas_shards_place_their_tables_once(
        small_packed, probe_rows, reference_scores, plan, shards):
    """Threaded plans over Pallas shards place each backend's tables once,
    on the first batch: a tree shard its own sub-forest, the row shards the
    one backend they share (its threads race to place it), and the merge
    stays bit-exact on every batch."""
    s_ref, p_ref = reference_scores["integer"]
    eng = TreeEngine(small_packed, f"integer:pallas+{plan}:{shards}")
    for _ in range(3):
        s, p = _scores(eng, probe_rows)
        np.testing.assert_array_equal(s, s_ref, err_msg=plan)
        np.testing.assert_array_equal(p, p_ref, err_msg=plan)
    backends = eng.plan.backends
    assert eng.drain_stage_timings()["place"][1] == len(backends)
    assert [b._tables[0].shape for b in backends] == [
        b.packed.feature.shape for b in backends]
    assert sum(b.packed.n_trees for b in backends) == small_packed.n_trees


def test_heterogeneous_tree_parallel_reference_plus_pallas(
        small_packed, probe_rows, reference_scores):
    """A tree-parallel plan mixing two *different* backends — half the forest
    on the jnp walk, half on the Pallas kernel — stays bit-identical."""
    for mode in ("flint", "integer"):
        s_ref, p_ref = reference_scores[mode]
        eng = TreeEngine(small_packed, mode=mode,
                         backend=("reference", "pallas"), shards=2)
        assert eng.plan_name == "tree_parallel"
        assert [b.name for b in eng.plan.backends] == ["reference", "pallas"]
        # each shard materializes its own preferred layout from one IR
        assert eng.layout == "padded+leaf_major"
        s, p = _scores(eng, probe_rows)
        np.testing.assert_array_equal(s, s_ref, err_msg=f"hetero/{mode}")
        np.testing.assert_array_equal(p, p_ref, err_msg=f"hetero/{mode}")


@pytest.mark.requires_gcc
def test_heterogeneous_tree_parallel_with_compiled_c(
        small_packed, probe_rows, reference_scores):
    """Heterogeneous across the jnp/compiled-C divide: shards on the ragged
    table-walk C and the reference walk, cycled over 3 shards."""
    s_ref, p_ref = reference_scores["integer"]
    eng = TreeEngine(small_packed, mode="integer",
                     backend=("native_c_table", "reference"), shards=3)
    assert [b.name for b in eng.plan.backends] == \
        ["native_c_table", "reference", "native_c_table"]
    s, p = _scores(eng, probe_rows)
    np.testing.assert_array_equal(s, s_ref)
    np.testing.assert_array_equal(p, p_ref)


def test_fused_and_threaded_tree_parallel_agree(small_packed, probe_rows):
    """The two tree-parallel strategies (shard_map fused vs per-shard
    threaded backends) are bit-identical; which one runs depends on the
    device count, and forcing threads must always work."""
    ir = small_packed.to_ir()
    eng_auto = TreeEngine(ir, mode="integer", plan="tree_parallel", shards=2)
    eng_thr = TreeEngine(ir, mode="integer", plan="tree_parallel", shards=2,
                         plan_kwargs={"device_parallel": False})
    assert not eng_thr.plan.fused
    s_a, p_a = _scores(eng_auto, probe_rows)
    s_t, p_t = _scores(eng_thr, probe_rows)
    np.testing.assert_array_equal(s_a, s_t)
    np.testing.assert_array_equal(p_a, p_t)
    import jax

    if len(jax.devices()) >= 2:  # the forced-device conformance config
        assert eng_auto.plan.fused


def test_engine_partials_match_scores(small_packed, probe_rows):
    """Engine-level predict_partials == the integer scores, through the
    bucketed path, for single and sharded plans alike."""
    for plan, shards in (("single", None), ("tree_parallel", 3),
                         ("row_parallel", 2)):
        eng = TreeEngine(small_packed, mode="integer", plan=plan, shards=shards)
        acc = eng.predict_partials(probe_rows)
        s, _ = _scores(eng, probe_rows)
        np.testing.assert_array_equal(acc, s, err_msg=f"{plan}")


# --------------------------------------------------- ForestIR.subset round trips

def test_subset_slices_are_bit_identical(small_packed):
    ir = small_packed.to_ir()
    sub = ir.subset(2, 5)
    assert sub.n_trees == 3
    lo, hi = int(ir.node_offsets[2]), int(ir.node_offsets[5])
    for name in ("feature", "threshold", "threshold_key", "left", "right",
                 "leaf_probs", "leaf_fixed"):
        np.testing.assert_array_equal(getattr(sub, name),
                                      getattr(ir, name)[lo:hi])
    np.testing.assert_array_equal(sub.node_offsets,
                                  ir.node_offsets[2:6] - lo)
    np.testing.assert_array_equal(sub.tree_depths, ir.tree_depths[2:5])
    # the parent's quantization scale rides along — never recomputed from
    # the subset's smaller tree count
    assert sub.scale == ir.scale
    assert sub.scale != ir.subset(0, 2).n_trees  # sanity: not scale_for(2)
    assert sub.materialize("padded").scale == ir.scale
    assert sub.materialize("ragged").scale == ir.scale
    # slice syntax and bounds checking
    assert ir.subset(slice(2, 5)).n_trees == 3
    full = ir.subset(0, ir.n_trees)
    np.testing.assert_array_equal(full.feature, ir.feature)
    with pytest.raises(ValueError, match="out of bounds"):
        ir.subset(0, ir.n_trees + 1)
    with pytest.raises(ValueError, match="out of bounds"):
        ir.subset(3, 3)
    with pytest.raises(ValueError, match="contiguous"):
        ir.subset(slice(0, 4, 2))


@pytest.mark.parametrize("splits", [2, 3, 9], ids=["s2", "s3", "s9"])
def test_subset_partials_reconcat_bit_identical(small_packed, shuttle_small,
                                                splits):
    """Subsetting then re-summing partial scores == the full forest, and
    finalize over the merged partials == full-forest flint scores."""
    _, _, Xte, _ = shuttle_small
    rows = Xte[:29]
    ir = small_packed.to_ir()
    full = np.asarray(
        create_backend("reference", small_packed, mode="integer").predict_partials(rows)
    )
    merged = np.zeros_like(full)
    for a, b in tree_ranges(ir.n_trees, splits):
        sub = ir.subset(a, b)
        merged = merged + np.asarray(
            create_backend("reference", sub.materialize("padded"),
                           mode="integer").predict_partials(rows)
        )
    np.testing.assert_array_equal(merged, full)
    s_fl, p_fl = finalize_partials("flint", merged, ir.n_trees, ir.scale)
    s_ref, p_ref = _scores(
        create_backend("reference", small_packed, mode="flint"), rows)
    np.testing.assert_array_equal(s_fl, s_ref)
    np.testing.assert_array_equal(p_fl, p_ref)


@pytest.mark.parametrize("case", sorted(DEGENERATE_FORESTS))
def test_subset_roundtrip_degenerate(case):
    """Single-tree, stump, and depth-skewed forests: per-tree subsets re-sum
    to the full partials, and a whole-forest subset is a no-op."""
    ir = ForestIR.from_forest(DEGENERATE_FORESTS[case]())
    rng = np.random.default_rng(hash(case) % 2**31)
    rows = rng.normal(0.0, 5.0, (17, ir.n_features)).astype(np.float32)
    full = np.asarray(
        create_backend("reference", ir.materialize("padded"),
                       mode="integer").predict_partials(rows)
    )
    merged = np.zeros_like(full)
    for t in range(ir.n_trees):  # one shard per tree — the finest carve
        sub = ir.subset(t, t + 1)
        assert sub.n_trees == 1 and sub.scale == ir.scale
        merged = merged + np.asarray(
            create_backend("reference", sub.materialize("padded"),
                           mode="integer").predict_partials(rows)
        )
    np.testing.assert_array_equal(merged, full)


# ------------------------------------------------------------- warm + timing

def test_warm_covers_every_shard(small_packed, monkeypatch):
    """warm() must pre-compile the *shard-level* shapes (row chunks, not just
    whole-forest buckets): the first post-warm predict presents no new shape
    to any shard backend, i.e. no compile happens on the request path."""
    from repro.backends.reference import ReferenceBackend

    seen = []
    orig = ReferenceBackend.predict_partials

    def spy(self, X):
        seen.append((id(self), np.asarray(X).shape[0]))
        return orig(self, X)

    monkeypatch.setattr(ReferenceBackend, "predict_partials", spy)
    for plan, shards in (("row_parallel", 4), ("tree_parallel", 3)):
        eng = TreeEngine(small_packed, mode="integer", plan=plan,
                         shards=shards, max_bucket=16,
                         plan_kwargs=({"device_parallel": False}
                                      if plan == "tree_parallel" else None))
        seen.clear()
        eng.warm(16)
        warm_shapes = set(seen)
        assert warm_shapes, plan  # warm really drove the shard backends
        seen.clear()
        for b in (1, 5, 13, 16):
            eng.predict(np.zeros((b, small_packed.n_features), np.float32))
        assert set(seen) <= warm_shapes, f"{plan}: post-warm shapes compiled"


def test_plan_shard_timings_drain(small_packed, shuttle_small):
    _, _, Xte, _ = shuttle_small
    eng = TreeEngine(small_packed, mode="integer", plan="tree_parallel",
                     shards=3, plan_kwargs={"device_parallel": False})
    eng.predict_scores(Xte[:8])
    t = eng.drain_shard_timings()
    assert len(t) == min(3, thread_shard_cap())  # threaded -> core-capped
    for label, (ms, calls) in t.items():
        assert label.startswith("s") and ms >= 0 and calls == 1
    assert eng.drain_shard_timings() == {}  # drained


def test_gateway_surfaces_shard_timings(small_forest, shuttle_small):
    import asyncio

    from repro.serve.gateway import Gateway
    from repro.serve.registry import ModelRegistry

    _, _, Xte, _ = shuttle_small
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    gw = Gateway(reg, mode="integer", plan="tree_parallel", shards=2,
                 max_delay_ms=1.0)
    asyncio.run(gw.submit("m", Xte[:8]))
    asyncio.run(gw.close())
    shards = gw.stats()["per_model"]["m"]["shards"]
    assert shards and all(v["calls"] >= 1 for v in shards.values())

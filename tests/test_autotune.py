"""The warm-time measured autotuner (``repro.serve.autotune``).

What must hold:
  * determinism — an injected constant-time measure resolves ties to the
    static default, and an injected ranking picks the same winner every run;
  * bit-identity — serving on any tuned config equals the untuned scores
    (the knobs only re-tile work; conformance crosses them independently);
  * caching — the winner lands in the owning ``ModelVersion``'s store, a
    hot-swapped version inherits it and serves tuned *without* re-measuring;
  * escape hatches — ``REPRO_AUTOTUNE=0`` kills tuning globally, caller-
    pinned ``backend_kwargs`` knobs are never overridden, and non-single
    plans / non-tunable backends never arm the tuner;
  * accounting — the measuring cost drains through ``drain_compile_timings``
    under the ``"tune"`` key and flows into the metrics ``tuned`` column and
    the compile ledger without breaking the int-keyed bucket sort.
"""
import numpy as np
import pytest

from repro.serve import autotune as at
from repro.serve.engine import TreeEngine
from repro.serve.registry import ModelRegistry

pytestmark = pytest.mark.requires_gcc


@pytest.fixture()
def probe(shuttle_small):
    _, _, Xte, _ = shuttle_small
    return Xte[:48]


def _fake_measure(winner_key, winner_val):
    """A deterministic measure: the candidate whose kwargs contain
    ``winner_key == winner_val`` is fastest, everything else ties slower."""
    def measure(backend, X):
        kw = {"interleave": getattr(backend, "interleave", None),
              "block_rows": getattr(backend, "block_rows", None)}
        return 1.0 if kw.get(winner_key) == winner_val else 2.0
    return measure


def test_candidate_grids_default_first(small_packed):
    ir = small_packed.to_ir()
    tbl = at.candidate_grid("native_c_table", ir.materialize("ragged"))
    assert tbl[0] == {"block_rows": 8}  # the static default leads
    assert {c["block_rows"] for c in tbl} == {1, 4, 8, 16}
    bv = at.candidate_grid("native_c_bitvector", ir.materialize("bitvector"))
    assert bv[0] == {"interleave": 8}
    assert {c["interleave"] for c in bv} == {1, 4, 8}
    pal = at.candidate_grid("pallas", ir.materialize("leaf_major"))
    assert pal and all({"block_b", "block_t"} == set(c) for c in pal)
    from repro.kernels.ops import pick_blocks

    t, n = ir.materialize("leaf_major").feature.shape
    auto = pick_blocks(at._TUNE_ROWS, t, n, ir.n_features, ir.n_classes)
    assert (pal[0]["block_b"], pal[0]["block_t"]) == auto[:2]  # heuristic leads
    assert at.candidate_grid("reference", small_packed) == []


def test_tune_is_deterministic_and_ties_go_to_default(small_packed):
    ir = small_packed.to_ir()
    art = ir.materialize("bitvector")
    # constant timer: every candidate ties -> the default (grid[0]) wins
    const = lambda backend, X: 1.0
    winners = {at.tune_backend("native_c_bitvector", art, "integer",
                               measure=const)[0]["interleave"]
               for _ in range(3)}
    assert winners == {8}
    # a ranked timer picks the same non-default winner every run
    for _ in range(2):
        w, wb, report = at.tune_backend(
            "native_c_bitvector", art, "integer",
            measure=_fake_measure("interleave", 4))
        assert w == {"interleave": 4} and wb.interleave == 4
        assert [kw["interleave"] for kw, _ in report] == [8, 1, 4]


def test_warm_tunes_and_stays_bit_identical(small_packed, probe, monkeypatch):
    ref = TreeEngine(small_packed, mode="integer").predict_scores(probe)
    monkeypatch.setattr(at, "measure_backend", _fake_measure("interleave", 1))
    store = {}
    eng = TreeEngine(small_packed, mode="integer",
                     backend="native_c_bitvector", autotune=True,
                     tuned_store=store)
    assert eng._pending_tune and eng.tuned_config is None
    eng.warm(32)
    assert eng.tuned_config == "interleave=1"
    assert eng.backend.interleave == 1
    assert store == {("native_c_bitvector", None, "integer"):
                     {"interleave": 1}}
    tune_ms = eng.drain_compile_timings()["tune"]
    assert tune_ms >= 0
    s, p = eng.predict_scores(probe)
    np.testing.assert_array_equal(s, ref[0])
    np.testing.assert_array_equal(p, ref[1])


def test_cached_winner_reused_without_measuring(small_packed, monkeypatch):
    calls = []

    def spy(*a, **kw):
        calls.append(a)
        return None, None, []

    monkeypatch.setattr(at, "tune_backend", spy)
    store = {("native_c_table", None, "integer"): {"block_rows": 4}}
    eng = TreeEngine(small_packed, mode="integer", backend="native_c_table",
                     autotune=True, tuned_store=store)
    # the cached winner applies at construction; warm() must not re-measure
    assert not eng._pending_tune
    assert eng.tuned_config == "block_rows=4"
    assert eng.backend.block_rows == 4
    eng.warm(16)
    assert calls == []


def test_env_kill_switch_and_ineligible_routes(small_packed, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    eng = TreeEngine(small_packed, mode="integer",
                     backend="native_c_bitvector", autotune=True)
    assert not eng._pending_tune and eng.tuned_config is None
    eng.warm(16)
    assert eng.backend.interleave == 8  # the static default, untouched
    monkeypatch.delenv("REPRO_AUTOTUNE")
    # non-tunable backend: never armed
    assert not TreeEngine(small_packed, mode="integer",
                          autotune=True)._pending_tune
    # multi-shard plans are not tuned (per-shard artifacts differ)
    assert not TreeEngine(small_packed, mode="integer",
                          backend="native_c_bitvector", plan="tree_parallel",
                          shards=2, autotune=True)._pending_tune


def test_caller_pinned_knob_is_never_overridden(small_packed, monkeypatch):
    monkeypatch.setattr(at, "measure_backend", _fake_measure("interleave", 1))
    eng = TreeEngine(small_packed, mode="integer",
                     backend="native_c_bitvector", autotune=True,
                     backend_kwargs={"interleave": 4})
    eng.warm(16)
    assert eng.backend.interleave == 4  # the pin survives warm
    assert eng.tuned_config is None     # and no winner is reported


def test_hot_swap_inherits_tuned_winner(small_forest, probe, monkeypatch):
    monkeypatch.setattr(at, "measure_backend", _fake_measure("interleave", 4))
    reg = ModelRegistry()
    mv1 = reg.register_forest("m", small_forest)
    eng1 = mv1.engine("integer", backend="native_c_bitvector", autotune=True)
    eng1.warm(32)
    assert eng1.tuned_config == "interleave=4"
    # hot-swap: the new version must inherit the measurement and serve tuned
    # from construction, without tune_backend running again
    calls = []
    real = at.tune_backend

    def spy(*a, **kw):
        calls.append(a)
        return real(*a, **kw)

    monkeypatch.setattr(at, "tune_backend", spy)
    mv2 = reg.register_forest("m", small_forest)
    assert mv2.version == mv1.version + 1
    eng2 = mv2.engine("integer", backend="native_c_bitvector", autotune=True)
    assert not eng2._pending_tune
    assert eng2.tuned_config == "interleave=4"
    assert eng2.backend.interleave == 4
    eng2.warm(32)
    assert calls == []
    s1 = eng1.predict_scores(probe)
    s2 = eng2.predict_scores(probe)
    np.testing.assert_array_equal(s1[0], s2[0])
    np.testing.assert_array_equal(s1[1], s2[1])


def test_gateway_surfaces_tuned_column(small_forest, shuttle_small,
                                       monkeypatch):
    import asyncio

    from repro.serve.gateway import Gateway

    monkeypatch.setattr(at, "measure_backend", _fake_measure("block_rows", 1))
    _, _, Xte, _ = shuttle_small
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    gw = Gateway(reg, mode="integer", backend="native_c_table",
                 autotune=True, max_delay_ms=1.0)
    reg.get("m").engine("integer", backend="native_c_table",
                        autotune=True).warm(16)
    asyncio.run(gw.submit("m", Xte[:8]))
    asyncio.run(gw.close())
    st = gw.stats()["per_model"]["m"]
    assert st["tuned"] == "block_rows=1"
    assert st["compile_ms_by_bucket"]["tune"] >= 0
    # the mixed int/str bucket keys must survive every exposition surface
    gw.render_table()
    from repro.obs.export import render_prometheus

    assert 'bucket="tune"' in render_prometheus(gw.stats()["per_model"])

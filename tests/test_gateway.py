"""Gateway subsystem: bucketed engine, micro-batcher, registry, cache,
end-to-end bit-identity of gateway outputs vs direct engine calls."""
import asyncio
import time

import numpy as np
import pytest

from repro.serve.cache import QuantizedKeyCache, row_keys
from repro.serve.engine import TreeEngine, bucket_rows
from repro.serve.gateway import Gateway
from repro.serve.queue import AdmissionError, MicroBatcher
from repro.serve.registry import ModelRegistry


# ------------------------------------------------------------------ engine

def test_bucket_rows():
    assert [bucket_rows(b) for b in (1, 2, 3, 5, 64, 65, 1000)] == [
        1, 2, 4, 8, 64, 128, 1024
    ]
    assert bucket_rows(4097, max_bucket=4096) == 8192
    assert bucket_rows(5000, max_bucket=4096) == 8192
    with pytest.raises(ValueError):
        bucket_rows(0)


def test_engine_bucketing_bit_identical(small_packed, shuttle_small):
    """Padded-bucket execution must not perturb real rows."""
    _, _, Xte, _ = shuttle_small
    eng = TreeEngine(small_packed, mode="integer")
    s_full, p_full = eng.predict_scores(Xte[:64])
    for b in (1, 5, 37, 64):
        s, p = eng.predict_scores(Xte[:b])
        np.testing.assert_array_equal(s, s_full[:b])
        np.testing.assert_array_equal(p, p_full[:b])
    # 1, 5->8, 37->64, 64: three compiled buckets, not four shapes
    assert eng.compiled_buckets == {1, 8, 64}


# ------------------------------------------------------------------- cache

def test_cache_lru_and_counters():
    c = QuantizedKeyCache(capacity_rows=2)
    k = lambda i: c.key_for("m", 1, "integer", bytes([i]))
    assert c.get(k(0)) is None and c.misses == 1
    c.put(k(0), np.array([1, 2]), 0)
    c.put(k(1), np.array([3, 4]), 1)
    assert c.get(k(0))[1] == 0 and c.hits == 1
    c.put(k(2), np.array([5, 6]), 1)  # evicts k(1), the LRU entry
    assert len(c) == 2 and c.evictions == 1
    assert c.get(k(1)) is None
    assert c.get(k(0)) is not None and c.get(k(2)) is not None


def test_row_keys_quantized_exact_match():
    X = np.array([[0.5, -1.25], [0.5, -1.25], [0.5, -1.0]], np.float32)
    k = row_keys(X)
    assert k[0] == k[1] and k[0] != k[2]


# ---------------------------------------------------------------- batcher

def _fake_execute(model_id, X):
    # scores = row sums so results are easy to verify per row
    s = X.sum(axis=1, keepdims=True)
    return s, np.arange(len(X), dtype=np.int32) * 0, len(X), None


def test_micro_batcher_coalesces_and_scatters():
    batches = []

    async def run():
        mb = MicroBatcher(_fake_execute, max_batch_rows=64, max_delay_ms=100,
                          on_batch=lambda m, r, p: batches.append(r))
        reqs = [np.full((1, 3), float(i), np.float32) for i in range(8)]
        outs = await asyncio.gather(*[mb.submit("m", r) for r in reqs])
        await mb.close()
        return outs

    outs = asyncio.run(run())
    for i, (scores, preds, _meta) in enumerate(outs):
        assert scores.shape == (1, 1) and scores[0, 0] == 3.0 * i
    # 8 one-row submissions coalesced into far fewer engine dispatches
    assert sum(batches) == 8 and len(batches) < 8


def test_micro_batcher_admission_control():
    def slow_execute(model_id, X):
        time.sleep(0.15)
        return X.sum(axis=1, keepdims=True), np.zeros(len(X), np.int32), len(X), None

    async def run():
        mb = MicroBatcher(slow_execute, max_batch_rows=1, max_delay_ms=0.1,
                          max_queue_rows=4)
        first = asyncio.ensure_future(mb.submit("m", np.zeros((1, 2), np.float32)))
        await asyncio.sleep(0.05)  # worker is now busy executing `first`
        backlog = [asyncio.ensure_future(mb.submit("m", np.zeros((1, 2), np.float32)))
                   for _ in range(4)]
        await asyncio.sleep(0)  # let the submits enqueue
        with pytest.raises(AdmissionError):
            await mb.submit("m", np.zeros((1, 2), np.float32))
        await asyncio.gather(first, *backlog)
        await mb.close()

    asyncio.run(run())


def test_micro_batcher_close_drains_pending_submits():
    """close() drains: everything submitted before it resolves to a real
    result (never "batcher closed"), and nothing is stranded.  Submissions
    arriving after close() fail fast."""
    def slow_execute(model_id, X):
        time.sleep(0.2)
        return X.sum(axis=1, keepdims=True), np.zeros(len(X), np.int32), len(X), None

    async def run():
        mb = MicroBatcher(slow_execute, max_batch_rows=1, max_delay_ms=0.1)
        subs = [asyncio.ensure_future(mb.submit("m", np.zeros((1, 2), np.float32)))
                for _ in range(3)]
        await asyncio.sleep(0.05)  # first is executing, rest are queued
        await mb.close()
        done = await asyncio.wait_for(
            asyncio.gather(*subs, return_exceptions=True), timeout=2.0
        )
        with pytest.raises(RuntimeError):
            await mb.submit("m", np.zeros((1, 2), np.float32))
        return done

    done = asyncio.run(run())
    # every caller that submitted before close() got its real result
    assert all(isinstance(r, tuple) for r in done)


def test_micro_batcher_close_timeout_fails_stragglers():
    """A lane that overruns close_timeout_s is cancelled and its remaining
    callers failed — drain must not hang forever on a wedged executor."""
    def wedged_execute(model_id, X):
        time.sleep(1.2)  # >> close_timeout_s; asyncio.run reaps the thread
        return X.sum(axis=1, keepdims=True), np.zeros(len(X), np.int32), len(X), None

    async def run():
        mb = MicroBatcher(wedged_execute, max_batch_rows=1, max_delay_ms=0.1,
                          close_timeout_s=0.2)
        subs = [asyncio.ensure_future(mb.submit("m", np.zeros((1, 2), np.float32)))
                for _ in range(3)]
        await asyncio.sleep(0.05)
        await mb.close()
        return await asyncio.wait_for(
            asyncio.gather(*subs, return_exceptions=True), timeout=10.0
        )

    done = asyncio.run(run())
    assert all(isinstance(r, (tuple, RuntimeError)) for r in done)
    assert any(isinstance(r, RuntimeError) for r in done)


# --------------------------------------------------------------- registry

def test_registry_versioning_and_hot_swap(small_forest, small_packed):
    reg = ModelRegistry()
    v1 = reg.register_forest("m", small_forest)
    assert v1.version == 1 and reg.version("m") == 1
    v2 = reg.register_packed("m", small_packed)
    assert v2.version == 2 and reg.get("m") is v2
    # the old version object stays usable for in-flight batches
    assert v1.packed.n_trees == small_packed.n_trees
    with pytest.raises(KeyError):
        reg.get("nope")


def test_registry_json_load_path_bit_identical(small_forest, shuttle_small):
    from repro.trees.io import forest_to_json

    _, _, Xte, _ = shuttle_small
    reg = ModelRegistry()
    reg.register_forest("direct", small_forest)
    reg.register_json("via-json", forest_to_json(small_forest))
    s1, p1 = reg.get("direct").engine("integer").predict_scores(Xte[:40])
    s2, p2 = reg.get("via-json").engine("integer").predict_scores(Xte[:40])
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(p1, p2)


# ---------------------------------------------------------------- gateway

def test_gateway_bit_identical_with_cache_and_batching(small_forest, shuttle_small):
    _, _, Xte, _ = shuttle_small
    reg = ModelRegistry()
    reg.register_forest("m1", small_forest)
    gw = Gateway(reg, mode="integer", max_batch_rows=32, max_delay_ms=2.0)
    direct = reg.get("m1").engine("integer")

    async def run():
        rows = Xte[:24]
        # mixed-size concurrent submissions covering the same 24 rows
        parts = [rows[:1], rows[1:3], rows[3:10], rows[10:24]]
        outs = await asyncio.gather(*[gw.submit("m1", p) for p in parts])
        scores = np.concatenate([s for s, _ in outs])
        preds = np.concatenate([p for _, p in outs])
        # resubmit the same rows: every row must now be a cache hit
        s2, p2 = await gw.submit("m1", rows)
        await gw.close()
        return scores, preds, s2, p2

    scores, preds, s2, p2 = asyncio.run(run())
    d_scores, d_preds = direct.predict_scores(Xte[:24])
    np.testing.assert_array_equal(scores, d_scores)
    np.testing.assert_array_equal(preds, d_preds)
    np.testing.assert_array_equal(s2, d_scores)
    np.testing.assert_array_equal(p2, d_preds)
    assert gw.cache.hits >= 24  # the resubmission was served from cache
    st = gw.stats()["per_model"]["m1"]
    assert st["cache_hit_rate"] > 0
    assert st["batches"] >= 1 and st["batch_occupancy"] >= 1.0


def test_gateway_hot_swap_routes_new_version(small_forest, shuttle_small):
    Xtr, ytr, Xte, _ = shuttle_small
    from repro.trees.forest import RandomForestClassifier

    other = RandomForestClassifier(n_estimators=3, max_depth=4, seed=42).fit(
        Xtr[:1500], ytr[:1500]
    )
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    gw = Gateway(reg, mode="integer", max_delay_ms=1.0)

    async def run():
        s_v1, _ = await gw.submit("m", Xte[:8])
        mv2 = reg.register_forest("m", other)  # hot-swap under the gateway
        s_v2, _ = await gw.submit("m", Xte[:8])
        await gw.close()
        return s_v1, s_v2, mv2

    s_v1, s_v2, mv2 = asyncio.run(run())
    d_v2, _ = mv2.engine("integer").predict_scores(Xte[:8])
    np.testing.assert_array_equal(s_v2, d_v2)  # new traffic hits v2
    assert mv2.version == 2
    # v1-keyed cache entries must not leak into v2 responses
    assert not np.array_equal(s_v1, s_v2)


def test_gateway_hot_swap_places_the_new_forest(small_forest, shuttle_small):
    """On the Pallas route a hot-swapped version walks tables of its own:
    the gateway answers with the new forest's scores, and the new version's
    backend places its own copy on the device (a second ``place``)."""
    Xtr, ytr, Xte, _ = shuttle_small
    from repro.trees.forest import RandomForestClassifier

    other = RandomForestClassifier(n_estimators=3, max_depth=4, seed=42).fit(
        Xtr[:1500], ytr[:1500]
    )
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    gw = Gateway(reg, "integer:pallas@leaf_major", max_delay_ms=1.0,
                 cache_rows=0)

    async def run():
        await gw.submit("m", Xte[:8])
        placed = [reg.get("m").engine(gw.spec).backend]
        reg.register_forest("m", other)  # hot-swap under the gateway
        s_v2, _ = await gw.submit("m", Xte[:8])
        placed.append(reg.get("m").engine(gw.spec).backend)
        await gw.close()
        return s_v2, placed

    s_v2, placed = asyncio.run(run())
    d_v2, _ = reg.get("m").engine("integer").predict_scores(Xte[:8])
    np.testing.assert_array_equal(s_v2, d_v2)
    assert gw.stats()["per_model"]["m"]["stages"]["place"]["count"] == 2
    assert [b._tables[0].shape[0] for b in placed] == [
        small_forest.n_estimators, other.n_estimators]


def test_gateway_survives_event_loop_reuse(small_forest, shuttle_small):
    """asyncio.run tears down lane workers with its loop; a later loop must
    respawn them instead of hanging on a dead queue."""
    _, _, Xte, _ = shuttle_small
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    gw = Gateway(reg, mode="integer", max_delay_ms=1.0)
    s1, _ = asyncio.run(gw.submit("m", Xte[:4]))
    s2, _ = asyncio.run(gw.submit("m", Xte[4:8]))  # fresh loop, cache-cold rows
    direct = reg.get("m").engine("integer")
    np.testing.assert_array_equal(s2, direct.predict_scores(Xte[4:8])[0])
    np.testing.assert_array_equal(s1, direct.predict_scores(Xte[:4])[0])


def test_gateway_cache_hit_requests_record_latency(small_forest, shuttle_small):
    """Requests served entirely from cache must land in the per-model latency
    histogram and request counters (and the hit_requests counter) — pinned by
    test so the all-hit fast path can never silently start timing misses
    only, which would skew p50/p95 on high-hit-rate streams."""
    _, _, Xte, _ = shuttle_small
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    gw = Gateway(reg, mode="integer", max_delay_ms=1.0)

    async def run():
        s1, _ = await gw.submit("m", Xte[:6])
        s2, _ = await gw.submit("m", Xte[:6])  # every row now a cache hit
        await gw.close()
        return s1, s2

    s1, s2 = asyncio.run(run())
    np.testing.assert_array_equal(s1, s2)
    mm = gw.metrics.model("m")
    assert mm.hit_requests == 1
    assert mm.requests == 2
    assert mm.latency.count == 2  # the hit request was timed too
    st = gw.stats()["per_model"]["m"]
    assert st["hit_requests"] == 1 and st["requests"] == 2
    assert np.isfinite(st["p50_ms"]) and np.isfinite(st["p99_ms"])


def test_gateway_layout_routing_bit_identical(small_forest, shuttle_small):
    """A layout-pinned gateway serves bit-identically to the default route,
    and cache keys stay layout-agnostic (same key space, either fills it)."""
    _, _, Xte, _ = shuttle_small
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    gw_default = Gateway(reg, mode="integer", max_delay_ms=1.0)
    gw_lm = Gateway(reg, mode="integer", layout="leaf_major", max_delay_ms=1.0)

    async def run(gw):
        out = await gw.submit("m", Xte[:12])
        await gw.close()
        return out

    s_d, p_d = asyncio.run(run(gw_default))
    s_l, p_l = asyncio.run(run(gw_lm))
    np.testing.assert_array_equal(s_d, s_l)
    np.testing.assert_array_equal(p_d, p_l)
    assert reg.get("m").engine("integer", layout="leaf_major").layout == "leaf_major"
    with pytest.raises(ValueError, match="layout"):
        Gateway(reg, mode="integer", backend="pallas", layout="ragged")


def test_gateway_float_mode_disables_cache(small_packed):
    reg = ModelRegistry()
    reg.register_packed("m", small_packed)
    gw = Gateway(reg, mode="float")
    assert gw.cache.capacity_rows == 0


def test_gateway_plan_routing_bit_identical(small_forest, shuttle_small):
    """A sharded-plan gateway serves bit-identically to the single-shard
    route — deterministic outputs are bit-identical across plans, which is
    exactly why cache keys can stay plan-agnostic."""
    _, _, Xte, _ = shuttle_small
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    gw_single = Gateway(reg, mode="integer", max_delay_ms=1.0)
    gw_tp = Gateway(reg, mode="integer", plan="tree_parallel", shards=3,
                    max_delay_ms=1.0)

    async def run(gw):
        out = await gw.submit("m", Xte[:12])
        await gw.close()
        return out

    s_s, p_s = asyncio.run(run(gw_single))
    s_t, p_t = asyncio.run(run(gw_tp))
    np.testing.assert_array_equal(s_s, s_t)
    np.testing.assert_array_equal(p_s, p_t)
    mv = reg.get("m")
    eng = mv.engine("integer", plan="tree_parallel", shards=3)
    from repro.plan import thread_shard_cap

    want = 3 if eng.plan.fused else min(3, thread_shard_cap())
    assert eng.plan_name == "tree_parallel" and eng.n_shards == want
    # the route is memoized separately from the single-shard engine
    assert eng is not mv.engine("integer")
    assert eng is mv.engine("integer", plan="tree_parallel", shards=3)
    with pytest.raises(KeyError, match="no-such"):
        Gateway(reg, mode="integer", plan="no-such-plan")


def test_gateway_hot_swap_with_multi_shard_plan_in_flight(small_forest,
                                                          shuttle_small):
    """Hot-swap while a tree-parallel plan is serving: the swapped-in version
    gets its *own* plan (its own shard carve — the new forest has a different
    tree count), responses never mix partials across versions, and the new
    traffic is bit-identical to a direct sharded engine on v2."""
    Xtr, ytr, Xte, _ = shuttle_small
    from repro.trees.forest import RandomForestClassifier

    other = RandomForestClassifier(n_estimators=5, max_depth=4, seed=77).fit(
        Xtr[:1500], ytr[:1500]
    )
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    gw = Gateway(reg, mode="integer", plan="tree_parallel", shards=3,
                 max_delay_ms=1.0)

    async def run():
        s_v1, _ = await gw.submit("m", Xte[:8])
        mv2 = reg.register_forest("m", other)  # hot-swap under the gateway
        s_v2, p_v2 = await gw.submit("m", Xte[:8])
        await gw.close()
        return s_v1, s_v2, p_v2, mv2

    s_v1, s_v2, p_v2, mv2 = asyncio.run(run())
    assert mv2.version == 2
    # v2 traffic == direct tree-parallel engine on v2 (5 trees -> 3 shards)
    eng2 = mv2.engine("integer", plan="tree_parallel", shards=3)
    d_s, d_p = eng2.predict_scores(Xte[:8])
    np.testing.assert_array_equal(s_v2, d_s)
    np.testing.assert_array_equal(p_v2, d_p)
    # ... and == the single-shard walk on v2 (no cross-version partial mixing:
    # a v1 shard summed into v2 could not reproduce this bit-exactly)
    d1_s, d1_p = mv2.engine("integer").predict_scores(Xte[:8])
    np.testing.assert_array_equal(s_v2, d1_s)
    assert not np.array_equal(s_v1, s_v2)  # v1 cache never leaks into v2

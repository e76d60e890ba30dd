"""Observability layer: log-scale histograms, the span tracer, end-to-end
trace integrity through the gateway, and the exposition renderers.

The trace-integrity tests pin the span contract the serving stack promises:
per-request spans nest inside the request interval, every stage the request
paid for (queue wait, pad, shard execute ×N, merge, finalize) appears in its
tree, the per-request *direct* children never sum past the request's wall
time, and a gateway with tracing disabled pays nothing measurable.
"""
import asyncio
import json
import math

import numpy as np
import pytest

from repro.obs import (
    NULL_SPAN,
    NULL_TRACER,
    LogHistogram,
    Tracer,
    render_flame,
    render_prometheus,
    request_trees,
    snapshot_json,
    spans_to_jsonl,
    write_jsonl,
)
from repro.serve.gateway import Gateway
from repro.serve.metrics import MetricsRegistry, ModelMetrics
from repro.serve.registry import ModelRegistry


# ----------------------------------------------------------------- histogram

def test_histogram_percentiles_vs_numpy():
    """p50/p95/p99 land within one log bucket (factor 2**(1/sub)) of the
    exact sample percentiles — the accuracy contract that let the histogram
    replace the unbounded reservoir."""
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=1.0, sigma=1.2, size=5000)
    h = LogHistogram()
    for v in samples:
        h.record(v)
    width = 2 ** (1 / h.sub)
    for q in (50, 95, 99):
        exact = float(np.percentile(samples, q))
        est = h.percentile(q)
        assert exact / width <= est <= exact * width, (q, exact, est)
    assert h.count == len(samples)
    assert h.total == pytest.approx(samples.sum(), rel=1e-9)
    assert h.mean == pytest.approx(samples.mean(), rel=1e-9)


def test_histogram_merge_equals_combined():
    rng = np.random.default_rng(1)
    a, b = rng.exponential(3.0, 800), rng.exponential(0.2, 800)
    ha, hb, hc = LogHistogram(), LogHistogram(), LogHistogram()
    for v in a:
        ha.record(v)
        hc.record(v)
    for v in b:
        hb.record(v)
        hc.record(v)
    ha.merge(hb)
    assert ha.count == hc.count and ha.total == pytest.approx(hc.total)
    for q in (50, 95, 99):
        assert ha.percentile(q) == pytest.approx(hc.percentile(q))
    snap = ha.snapshot()
    assert snap["count"] == 1600
    assert sum(c for _, c in snap["buckets"]) == 1600


def test_histogram_under_overflow_and_empty():
    h = LogHistogram(lo=1.0, hi=100.0)
    h.record(1e-9)   # underflow bucket
    h.record(1e9)    # overflow bucket
    h.record(0.0)    # non-positive -> underflow, must not blow up log2
    assert h.count == 3
    snap = h.snapshot()
    assert snap["buckets"][-1][0] is None  # +Inf edge
    # percentile stays clamped to observed extremes
    assert h.percentile(99) <= h.vmax
    empty = LogHistogram()
    assert math.isnan(empty.percentile(50))
    assert math.isnan(empty.snapshot()["p50"])
    with pytest.raises(ValueError):
        LogHistogram(lo=1.0, hi=100.0).merge(LogHistogram(lo=2.0, hi=100.0))


# -------------------------------------------------------------------- tracer

def test_disabled_tracer_hands_out_null_spans():
    t = Tracer(enabled=False)
    s = t.request_span("request")
    assert s is NULL_SPAN and not s
    assert s.child("x") is NULL_SPAN
    s.end()
    assert t.spans() == [] and t.started == 0
    # null parent -> null child, record under null parent is a no-op
    assert t.child(None, "x") is NULL_SPAN
    t.record("x", 0, 1, parent=NULL_SPAN)
    assert NULL_TRACER.request_span("request") is NULL_SPAN


def test_disabled_tracer_overhead_guard():
    """The disabled path must cost no more than a few microseconds per
    request worth of span calls (falsy checks, no allocations)."""
    import time

    t = Tracer(enabled=False)
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        s = t.request_span("request", rows=1)
        c = t.child(s, "batch")
        t.record("stage", 0, 1, parent=c)
        s.end()
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 20e-6, f"{per_call * 1e6:.2f}us per disabled request"


def test_deterministic_sampling():
    t = Tracer(sample=0.5)
    roots = [t.request_span("request") for _ in range(100)]
    live = [s for s in roots if s]
    assert len(live) == 50  # accumulator sampling: exactly half, no RNG
    for s in live:
        s.end()
    assert len(t.spans()) == 50


def test_span_nesting_and_ring_bound():
    t = Tracer(capacity=8)
    with t.request_span("request") as root:
        with root.child("inner") as c:
            c.annotate(k=1)
    spans = t.spans()
    by_name = {s.name: s for s in spans}
    inner, req = by_name["inner"], by_name["request"]
    assert inner.parent_id == req.span_id and inner.trace_id == req.trace_id
    assert req.t0 <= inner.t0 and inner.t1 <= req.t1
    assert inner.attrs == {"k": 1}
    for _ in range(50):
        t.request_span("request").end()
    assert len(t.spans()) <= 8 and t.dropped > 0


# ------------------------------------------------------- metrics regressions

def test_rejected_requests_advance_throughput_span():
    """Satellite fix: rejections must touch t_first/t_last.  A gateway that
    only shed load for a while used to freeze its clock, inflating
    rows_per_s over the real serving span."""
    import time

    mm = ModelMetrics()
    mm.record_request(10, 1.0)
    time.sleep(0.02)
    mm.record_rejected()
    span = mm.t_last - mm.t_first
    assert span >= 0.015, "rejection did not extend the throughput span"
    st = mm.stats()
    assert st["rejected"] == 1
    # 10 rows over >=15ms, not over the ~0ms request-only span
    assert st["rows_per_s"] <= 10 / 0.015


def test_render_table_columns_and_nan():
    reg = MetricsRegistry()
    mm = reg.model("m1")
    mm.record_request(4, 2.0)
    mm.hit_requests += 1
    table = reg.render_table()
    head = table.splitlines()[0]
    for col in ("hit_req", "shards", "queue_ms", "pad_ms", "shard_ms"):
        assert col in head, f"missing column {col!r}"
    # no stage samples yet -> those cells render '-', never a bare 'nan'
    assert "nan" not in table
    assert "-" in table.splitlines()[2]


def test_registry_aggregate_merges_histograms():
    reg = MetricsRegistry()
    reg.model("a").record_request(1, 1.0)
    reg.model("b").record_request(1, 100.0)
    reg.model("a").record_stage("queue", 0.5)
    reg.model("b").record_stage("queue", 5.0)
    agg = reg.aggregate()
    assert agg["models"] == 2 and agg["requests"] == 2
    assert agg["latency"]["count"] == 2
    assert agg["stages"]["queue"]["count"] == 2
    # the merged p99 reflects the slow model, not either alone
    assert agg["latency"]["p99"] > 50


# ----------------------------------------------------- gateway trace integrity

def _run_traced_gateway(small_forest, Xte, *, tracer, plan=None, shards=None,
                        n_requests=6):
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    gw = Gateway(reg, mode="integer", max_delay_ms=1.0, plan=plan,
                 shards=shards, tracer=tracer)

    async def run():
        outs = []
        for i in range(n_requests):
            outs.append(await gw.submit("m", Xte[i * 4:(i + 1) * 4]))
        await gw.close()
        return outs

    outs = asyncio.run(run())
    return gw, outs


def _assert_trace_integrity(spans, *, expect_shards=None):
    by_id = {s.span_id: s for s in spans}
    roots = [s for s in spans if s.name == "request"]
    assert roots, "no request spans recorded"
    for s in spans:
        assert s.t1 is not None and s.t1 >= s.t0
        if s.parent_id and s.parent_id in by_id:
            p = by_id[s.parent_id]
            assert p.t0 <= s.t0 and s.t1 <= p.t1, (
                f"{s.name} [{s.t0},{s.t1}] escapes parent "
                f"{p.name} [{p.t0},{p.t1}]"
            )
    # per-request DIRECT children must not sum past the request wall time
    # (parallel shard spans under the batch may overlap — that's the point)
    for r in roots:
        direct = [s for s in spans if s.parent_id == r.span_id]
        assert sum(s.t1 - s.t0 for s in direct) <= (r.t1 - r.t0)
    trees = request_trees(spans)
    assert len(trees) == len(roots)

    def names(node, acc):
        acc.append(node["name"])
        for c in node["children"]:
            names(c, acc)
        return acc

    shard_counts = []
    saw_stages = set()
    for t in trees:
        ns = names(t, [])
        saw_stages.update(n.split(":")[0] for n in ns)
        shard_counts.append(sum(1 for n in ns if n.startswith("shard:")))
    for stage in ("request", "cache_probe", "queue", "batch", "pad",
                  "shard", "upload", "launch", "fetch", "finalize", "stitch"):
        assert stage in saw_stages, f"stage {stage!r} missing from traces"
    # the shard call's stages are spans under its shard:* span
    for s in spans:
        if s.name in ("upload", "launch", "fetch"):
            assert by_id[s.parent_id].name.startswith("shard:"), s
    if expect_shards is not None:
        assert max(shard_counts) >= expect_shards, (
            f"expected >= {expect_shards} shard spans per batch, "
            f"got {shard_counts}"
        )
    return trees


def test_gateway_trace_single_plan(small_forest, shuttle_small):
    _, _, Xte, _ = shuttle_small
    tracer = Tracer()
    gw, _ = _run_traced_gateway(small_forest, Xte, tracer=tracer)
    _assert_trace_integrity(tracer.spans(), expect_shards=1)
    # the always-on stage columns got fed regardless of tracing
    st = gw.stats()["per_model"]["m"]
    for stage in ("queue", "pad", "shard", "finalize"):
        assert st["stages"][stage]["count"] > 0
        assert np.isfinite(st[f"{stage}_ms"])


def test_gateway_trace_tree_parallel(small_forest, shuttle_small):
    """Threaded tree-parallel: one shard span per sub-forest plus an explicit
    merge span, all inside the batch span."""
    _, _, Xte, _ = shuttle_small
    tracer = Tracer()
    from repro.plan import thread_shard_cap

    gw, _ = _run_traced_gateway(small_forest, Xte, tracer=tracer,
                                plan="tree_parallel", shards=3)
    n = min(3, thread_shard_cap())  # threaded fan-out is core-capped
    trees = _assert_trace_integrity(tracer.spans(), expect_shards=n)
    flat = []

    def walk(n):
        flat.append(n["name"])
        for c in n["children"]:
            walk(c)

    for t in trees:
        walk(t)
    assert any(n == "merge" for n in flat)
    st = gw.stats()["per_model"]["m"]
    assert st["stages"]["merge"]["count"] > 0
    assert len(st["shards"]) == n


def test_gateway_trace_row_parallel(small_forest, shuttle_small):
    _, _, Xte, _ = shuttle_small
    tracer = Tracer()
    gw, _ = _run_traced_gateway(small_forest, Xte, tracer=tracer,
                                plan="row_parallel", shards=2)
    _assert_trace_integrity(tracer.spans(), expect_shards=1)
    st = gw.stats()["per_model"]["m"]
    assert st["stages"]["merge"]["count"] > 0


def test_engine_fused_or_threaded_shard_spans(small_packed, shuttle_small):
    """Direct engine attach (no gateway): the shard spans reflect the
    execution strategy — ``shard:fused:*`` for the shard_map path, one span
    per shard backend otherwise."""
    from repro.serve.engine import TreeEngine

    _, _, Xte, _ = shuttle_small
    eng = TreeEngine(small_packed, mode="integer", plan="tree_parallel",
                     shards=2)
    tracer = Tracer()
    root = tracer.request_span("request")
    eng.attach_trace(tracer, root)
    try:
        eng.predict_scores(Xte[:8])
    finally:
        eng.detach_trace()
    root.end()
    shard_spans = [s for s in tracer.spans() if s.name.startswith("shard:")]
    if eng.plan.fused:
        assert len(shard_spans) == 1 and "fused" in shard_spans[0].name
    else:
        assert len(shard_spans) == eng.n_shards
    # compile/warm cost of the bucket this batch hit was tracked
    assert 8 in eng.drain_compile_timings()


def test_gateway_batch_riders_grafted(small_forest, shuttle_small):
    """Coalesced requests share one batch span; the export layer grafts the
    batch subtree under every rider request."""
    _, _, Xte, _ = shuttle_small
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    tracer = Tracer()
    gw = Gateway(reg, mode="integer", max_delay_ms=20.0, cache_rows=0,
                 tracer=tracer)

    async def run():
        await asyncio.gather(*[gw.submit("m", Xte[i:i + 1]) for i in range(4)])
        await gw.close()

    asyncio.run(run())
    spans = tracer.spans()
    batches = [s for s in spans if s.name == "batch"]
    assert batches
    coalesced = max(batches, key=lambda s: len(s.attrs.get("riders", [])))
    riders = coalesced.attrs["riders"]
    assert len(riders) >= 2, "batcher did not coalesce under a 20ms deadline"
    trees = request_trees(spans)
    with_batch = [t for t in trees
                  if any(c["name"] == "batch" for c in t["children"])]
    assert len(with_batch) >= len(riders)


def test_gateway_disabled_tracing_collects_nothing(small_forest, shuttle_small):
    _, _, Xte, _ = shuttle_small
    gw, _ = _run_traced_gateway(small_forest, Xte, tracer=None, n_requests=3)
    assert gw.tracer is NULL_TRACER and len(gw.tracer.spans()) == 0
    # stage metrics still flow (they are always-on, tracing is opt-in)
    st = gw.stats()["per_model"]["m"]
    assert st["stages"]["pad"]["count"] > 0


# ------------------------------------------- shard-call stages, process spans

def _stage_plan(small_packed):
    from repro.plan import create_plan

    return create_plan("single", small_packed, mode="integer",
                       backend="reference")


def test_stage_sink_one_sample_per_call_is_the_sum_of_intervals(small_packed):
    """Two upload intervals in one shard call make ONE upload sample, equal
    to the sum of the two (read back from their spans, which are not merged
    because a launch lies between them)."""
    import time

    from repro.obs import stage

    plan = _stage_plan(small_packed)
    tracer = Tracer()
    plan.attach_tracer(tracer)
    root = tracer.request_span("request")

    def call():
        with stage("upload", bytes=3):
            time.sleep(0.002)
        with stage("launch", programs=2):
            pass
        with stage("upload", bytes=4):
            time.sleep(0.001)
        with stage("fetch"):
            pass
        return 7

    assert plan._timed("x", call, span_parent=root) == 7
    root.end()
    st = plan.drain_stage_timings()
    assert set(st) == {"upload", "launch", "fetch"}
    assert all(calls == 1 for _, calls in st.values())
    spans = tracer.spans()
    shard = next(s for s in spans if s.name == "shard:x")
    kids = [s for s in spans if s.parent_id == shard.span_id]
    assert [s.name for s in sorted(kids, key=lambda s: s.t0)] == [
        "upload", "launch", "upload", "fetch"]
    ups = [s for s in kids if s.name == "upload"]
    assert st["upload"][0] == pytest.approx(
        sum(s.t1 - s.t0 for s in ups) / 1e6, abs=1e-9)
    assert st["upload"][0] >= 3.0
    assert sorted(s.attrs["bytes"] for s in ups) == [3, 4]
    (shard_ms, calls), = plan.drain_timings().values()
    assert calls == 1 and sum(ms for ms, _ in st.values()) <= shard_ms
    # adjacent intervals of one stage merge into one span, attrs summed

    def three_launches():
        for _ in range(3):
            with stage("launch", programs=1):
                pass

    plan._timed("y", three_launches, span_parent=tracer.request_span("request"))
    launches = [s for s in tracer.spans() if s.name == "launch"]
    assert launches[-1].attrs == {"programs": 3}


def test_stage_outside_a_plan_is_a_noop_and_cheap():
    """With no shard call on this thread, ``stage`` hands out one shared
    do-nothing context: one thread-local lookup, well under 5 us a call."""
    import time

    from repro.obs import stage
    from repro.obs.stages import _NULL_STAGE

    assert stage("upload", bytes=1) is _NULL_STAGE
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with stage("upload", bytes=1):
            pass
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 5e-6, f"{per_call * 1e6:.2f}us per stage outside a plan"


def test_gateway_pallas_route_stages(small_forest, shuttle_small):
    """The interpreted Pallas route: upload, launch and fetch are each one
    sample per batch and sum to no more than the shard stage; traced, each
    is a span under ``shard:*`` carrying its bytes and program count.  The
    forest was placed on the device once, in the warm-up, so a batch
    uploads its rows alone."""
    _, _, Xte, _ = shuttle_small
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    tracer = Tracer()
    gw = Gateway(reg, "integer:pallas@leaf_major", max_delay_ms=1.0,
                 cache_rows=0, tracer=tracer)
    eng = reg.get("m").engine(gw.spec)
    eng.warm(4)  # no compile inside the batches
    # the warm-up's samples, drained here so that the batches' are their own
    assert eng.drain_stage_timings()["place"][1] == 1
    eng.drain_shard_timings()

    async def run():
        for i in range(3):
            await gw.submit("m", Xte[i * 4:(i + 1) * 4])
        await gw.close()

    asyncio.run(run())
    st = gw.stats()["per_model"]["m"]
    stages = st["stages"]
    for name in ("upload", "launch", "fetch"):
        assert stages[name]["count"] == st["batches"] == 3, name
    assert "place" not in stages
    assert (sum(stages[n]["sum"] for n in ("upload", "launch", "fetch"))
            <= stages["shard"]["sum"])
    spans = tracer.spans()
    _assert_trace_integrity(spans, expect_shards=1)
    shards = [s for s in spans if s.name == "shard:s0:pallas"]
    assert len(shards) == 3
    for shard in shards:
        kids = [s for s in spans if s.parent_id == shard.span_id]
        assert {s.name for s in kids} == {"upload", "launch", "fetch"}
        # the rows alone, a 4-row bucket of float32; one program, which keys
        # the rows, pads them and runs the kernel
        ups = [s.attrs["bytes"] for s in kids if s.name == "upload"]
        assert ups == [4 * Xte.shape[1] * 4]
        assert sum(s.attrs["programs"] for s in kids if s.name == "launch") == 1


def _launch_notes(forest, batches):
    """The ``impl`` and ``node_blocks`` of each traced batch's launch span,
    on the Pallas route."""
    reg = ModelRegistry()
    reg.register_forest("m", forest)
    tracer = Tracer()
    gw = Gateway(reg, "integer:pallas@leaf_major", max_delay_ms=1.0,
                 cache_rows=0, tracer=tracer)

    async def run():
        for X in batches:
            await gw.submit("m", X)
        await gw.close()

    asyncio.run(run())
    # a compile inside the launch splits its span; the kernel's part notes
    return [(s.attrs["impl"], s.attrs["node_blocks"]) for s in tracer.spans()
            if s.name == "launch" and "impl" in s.attrs]


def test_launch_span_names_the_walk(small_forest, shuttle_small):
    """The kernel's launch notes which walk ran over how many node blocks:
    whole trees are one block, and a small batch takes the gather walk."""
    _, _, Xte, _ = shuttle_small
    notes = _launch_notes(small_forest, [Xte[:4], Xte[:100]])
    assert notes == [("gather", 1), ("leaf_major", 1)]


def test_launch_span_counts_node_blocks_of_deep_trees(monkeypatch):
    """Trees too large for a grid cell (the SMEM budget lowered so 1-2.5k
    nodes are): every batch runs the scan over several node blocks."""
    from repro.kernels import ops
    from repro.trees.forest import RandomForestClassifier

    rng = np.random.default_rng(0)
    X = rng.normal(size=(6000, 6)).astype(np.float32)
    forest = RandomForestClassifier(n_estimators=3, max_depth=16, seed=0).fit(
        X, rng.integers(0, 3, 6000))
    monkeypatch.setattr(ops, "_SMEM_BUDGET_BYTES", 96 * 1024)
    notes = _launch_notes(forest, [X[:4], X[:100]])
    assert [impl for impl, _ in notes] == ["leaf_major"] * 2
    assert all(blocks > 1 for _, blocks in notes), notes


def test_gc_spans_follow_an_enabled_tracer(small_forest):
    """A disabled tracer leaves ``gc.callbacks`` alone; an enabled one gets
    a hook for its gateway's life, whose spans are process roots that no
    request tree takes for a request."""
    import gc

    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    before = list(gc.callbacks)
    Gateway(reg, mode="integer")
    Gateway(reg, mode="integer", tracer=Tracer(enabled=False))
    assert gc.callbacks == before
    tracer = Tracer()
    gw = Gateway(reg, mode="integer", tracer=tracer)
    assert len(gc.callbacks) == len(before) + 1
    gc.collect()
    spans = [s for s in tracer.spans() if s.name == "gc"]
    assert any(s.attrs["generation"] == 2 for s in spans)
    assert all("collected" in s.attrs and s.parent_id == 0 for s in spans)
    assert request_trees(tracer.spans()) == []
    assert "gc" in render_flame(tracer.spans())
    asyncio.run(gw.close())
    assert gc.callbacks == before


def test_cold_bucket_records_a_compile_stage_and_span(small_forest, shuttle_small):
    """A first batch compiles: always-on ``compile`` stage sample, a span
    under its shard span; a compile outside any shard call is a process
    span of the watching tracer."""
    import jax
    import jax.numpy as jnp

    _, _, Xte, _ = shuttle_small
    reg = ModelRegistry()
    reg.register_forest("m", small_forest)
    tracer = Tracer()
    gw = Gateway(reg, mode="integer", cache_rows=0, tracer=tracer)

    async def run():
        await gw.submit("m", Xte[:5])

    asyncio.run(run())
    st = gw.stats()["per_model"]["m"]
    assert st["stages"]["compile"]["count"] >= 1
    spans = tracer.spans()
    by_id = {s.span_id: s for s in spans}
    inside = [s for s in spans if s.name == "compile" and s.parent_id]
    assert inside and all(by_id[s.parent_id].name.startswith("shard:")
                          for s in inside)
    assert all(isinstance(s.attrs["cached"], bool) for s in inside)
    jax.jit(lambda x: x * 5 - 2)(jnp.arange(3)).block_until_ready()
    outside = [s for s in tracer.spans() if s.name == "compile"
               and not s.parent_id]
    assert outside and all(s.t1 >= s.t0 for s in outside)
    asyncio.run(gw.close())
    n = len(tracer.spans())
    jax.jit(lambda x: x * 7 - 2)(jnp.arange(3)).block_until_ready()
    assert len(tracer.spans()) == n  # closed: no longer watched


# ---------------------------------------------------------------- exposition

def _sample_stats():
    reg = MetricsRegistry()
    mm = reg.model("m")
    mm.record_request(4, 2.5)
    mm.record_request(4, 7.5)
    mm.record_batch(8, 8)
    mm.record_cache(2, 6)
    mm.record_stage("queue", 0.3)
    mm.record_shards({"s0:reference[0:5]": (1.5, 1)})
    mm.record_compiles({8: 12.0})
    return reg.stats()


def test_render_prometheus_format():
    text = render_prometheus(_sample_stats())
    assert '# TYPE repro_requests_total counter' in text
    assert 'repro_requests_total{model="m"} 2' in text
    assert '# TYPE repro_request_latency_ms histogram' in text
    assert 'le="+Inf"' in text
    assert 'repro_request_latency_ms_count{model="m"} 2' in text
    assert 'repro_stage_ms_bucket{model="m",stage="queue"' in text
    assert 'repro_shard_ms_total{model="m",shard="s0:reference[0:5]"} 1.5' in text
    assert 'repro_bucket_compile_ms{model="m",bucket="8"} 12.0' in text
    # cumulative: the +Inf bucket equals the count
    lat = [l for l in text.splitlines()
           if l.startswith('repro_request_latency_ms_bucket') and '+Inf' in l]
    assert lat[0].rsplit(" ", 1)[1] == "2"


def test_snapshot_json_strict():
    stats = _sample_stats()
    stats["m"]["broken"] = float("nan")  # must sanitize, not crash
    out = snapshot_json(stats, run="test")
    doc = json.loads(out)  # strict parse: would fail on NaN tokens
    assert doc["run"] == "test"
    assert doc["stats"]["m"]["broken"] is None
    assert doc["stats"]["m"]["requests"] == 2


def test_jsonl_roundtrip_and_flame(tmp_path):
    tracer = Tracer()
    with tracer.request_span("request", rows=2) as root:
        with root.child("batch") as b:
            tracer.record("shard:s0", b.t0, b.t0 + 1000, parent=b)
    spans = tracer.spans()
    text = spans_to_jsonl(spans)
    lines = [json.loads(l) for l in text.splitlines()]
    assert len(lines) == len(spans) == 3
    assert {l["name"] for l in lines} == {"request", "batch", "shard:s0"}
    path = tmp_path / "trace.jsonl"
    assert write_jsonl(spans, path) == 3
    assert len(path.read_text().splitlines()) == 3
    flame = render_flame(spans)
    assert "request" in flame and "shard:s0" in flame

"""Multi-process partial-merge fabric: wire protocol round-trips, cross-
process bit-identity (the conformance invariant asserted *across sockets*),
straggler/kill re-dispatch, gateway integration, and lifecycle teardown.

Worker processes are spawned on loopback via
``repro.serve.worker.spawn_local_workers``; the plan under test is
``remote_tree_parallel`` (``repro.plan.remote``).
"""
import threading
import time

import numpy as np
import pytest

from repro.serve import wire
from repro.serve.spec import EngineSpec
from repro.serve.worker import spawn_local_workers


def _kill_all(procs):
    for p in procs:
        if p.poll() is None:
            p.kill()
        if p.stdout is not None:
            p.stdout.close()


@pytest.fixture(scope="module")
def worker_pair():
    """Two plain loopback worker processes shared by the happy-path tests."""
    procs, addrs = spawn_local_workers(2)
    yield addrs
    _kill_all(procs)


@pytest.fixture()
def remote_engine(small_packed, worker_pair):
    """Factory: an engine on the remote plan against the shared pair."""
    made = []

    def make(mode, **plan_kwargs):
        from repro.serve.engine import TreeEngine

        eng = TreeEngine(
            small_packed,
            EngineSpec(mode=mode, backend="reference",
                       plan="remote_tree_parallel", shards=2),
            plan_kwargs={"workers": list(worker_pair), "model_id": "t",
                         "version": 1, **plan_kwargs},
        )
        made.append(eng)
        return eng

    yield make
    for eng in made:
        eng.close()


# ---------------------------------------------------------------------------
# wire protocol
# ---------------------------------------------------------------------------

def test_wire_partials_roundtrip():
    acc = np.arange(4 * 7, dtype=np.uint32).reshape(4, 7) * 2654435761
    payload = wire.encode_partials(9, 3, acc, spans=[("predict", 100, 2500)])
    rid, sid, out, spans = wire.decode_partials(payload)
    assert (rid, sid) == (9, 3)
    assert out.dtype == np.uint32 and np.array_equal(out, acc)
    assert spans == [("predict", 100, 2500)]
    assert out.flags.writeable  # decoded copy, not a view of the recv buffer


def test_wire_pack_arrays_roundtrip():
    arrays = {
        "feature": np.array([0, -1, 2], np.int32),
        "threshold": np.array([0.5, 1.5], np.float32),
        "leaf_fixed": np.array([[1, 2], [3, 4]], np.uint32),
        "offsets": np.array([0, 3], np.int64),
    }
    payload = wire.pack_arrays({"model": "m", "version": 3}, arrays)
    meta, out = wire.unpack_arrays(payload)
    assert meta == {"model": "m", "version": 3}
    for name, a in arrays.items():
        assert out[name].dtype == a.dtype
        assert np.array_equal(out[name], a)


def test_wire_frame_rejects_bad_magic():
    import io
    import socket

    a, b = socket.socketpair()
    try:
        a.sendall(b"XXXX" + bytes(5))
        with pytest.raises(wire.ConnectionClosed):
            wire.read_frame(b)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# cross-process conformance: merged remote partials == single-process walk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["flint", "integer"])
def test_two_worker_bit_identity(small_packed, remote_engine, shuttle_small,
                                 mode):
    from repro.serve.engine import TreeEngine

    X = shuttle_small[2][:96].astype(np.float32)
    ref_s, ref_p = TreeEngine(small_packed, mode).predict_scores(X)
    eng = remote_engine(mode)
    s, p = eng.predict_scores(X)
    assert np.array_equal(s, ref_s)
    assert np.array_equal(p, ref_p)
    # every shard executed on a worker, none locally
    labels = list(eng.drain_shard_timings())
    assert labels and all(lbl.startswith("w") for lbl in labels)


def test_remote_rejects_float_mode(small_packed, worker_pair):
    from repro.serve.engine import TreeEngine

    with pytest.raises(ValueError):
        TreeEngine(small_packed,
                   EngineSpec(mode="float", plan="remote_tree_parallel"),
                   plan_kwargs={"workers": list(worker_pair)})


def test_connect_cost_lands_in_compile_ledger(remote_engine, shuttle_small):
    eng = remote_engine("integer")
    eng.predict_scores(shuttle_small[2][:8].astype(np.float32))
    drained = eng.drain_compile_timings()
    assert "remote" in drained and drained["remote"] > 0.0


def test_worker_kill_redispatch_bit_identity(small_packed, shuttle_small):
    """Kill a straggling worker mid-request: its shard re-dispatches to the
    survivor and the merged result stays bit-identical."""
    from repro.serve.engine import TreeEngine

    X = shuttle_small[2][:64].astype(np.float32)
    ref_s, ref_p = TreeEngine(small_packed, "integer").predict_scores(X)
    procs, addrs = spawn_local_workers(2, delays=[3000, 0])
    try:
        eng = TreeEngine(
            small_packed,
            EngineSpec(mode="integer", backend="reference",
                       plan="remote_tree_parallel", shards=2),
            plan_kwargs={"workers": addrs, "model_id": "t", "version": 1},
        )
        # worker 0 sleeps 3 s before answering; kill it mid-request
        killer = threading.Timer(0.5, procs[0].kill)
        killer.start()
        try:
            s, p = eng.predict_scores(X)
        finally:
            killer.cancel()
        assert np.array_equal(s, ref_s)
        assert np.array_equal(p, ref_p)
        assert eng.plan.redispatches >= 1
        assert [w["alive"] for w in eng.plan.workers()] == [False, True]
        eng.close()
    finally:
        _kill_all(procs)


@pytest.mark.slow
def test_straggler_deadline_redispatch(small_packed, shuttle_small):
    """A worker that exceeds the per-shard deadline is evicted and its shard
    re-dispatched — without killing the process."""
    from repro.serve.engine import TreeEngine

    X = shuttle_small[2][:32].astype(np.float32)
    ref_s, ref_p = TreeEngine(small_packed, "integer").predict_scores(X)
    procs, addrs = spawn_local_workers(2, delays=[5000, 0])
    try:
        eng = TreeEngine(
            small_packed,
            EngineSpec(mode="integer", backend="reference",
                       plan="remote_tree_parallel", shards=2),
            plan_kwargs={"workers": addrs, "model_id": "t", "version": 1,
                         "deadline_ms": None},  # no deadline during warm
        )
        eng.plan.deadline_ms = 1500.0
        t0 = time.perf_counter()
        s, p = eng.predict_scores(X)
        dt = time.perf_counter() - t0
        assert np.array_equal(s, ref_s)
        assert np.array_equal(p, ref_p)
        assert eng.plan.redispatches >= 1
        assert dt < 4.5  # did not wait out the 5 s straggler
        eng.close()
    finally:
        _kill_all(procs)


@pytest.mark.requires_gcc
def test_heterogeneous_worker_backends(small_packed, worker_pair,
                                       shuttle_small):
    """Compiled-C shard next to a reference shard, each on its own worker."""
    from repro.serve.engine import TreeEngine

    X = shuttle_small[2][:48].astype(np.float32)
    ref_s, ref_p = TreeEngine(small_packed, "integer").predict_scores(X)
    eng = TreeEngine(
        small_packed,
        EngineSpec(mode="integer", backend=("reference", "native_c"),
                   plan="remote_tree_parallel", shards=2),
        plan_kwargs={"workers": list(worker_pair), "model_id": "t",
                     "version": 1},
    )
    s, p = eng.predict_scores(X)
    assert np.array_equal(s, ref_s)
    assert np.array_equal(p, ref_p)
    eng.close()


def test_engine_close_reaps_owned_workers(small_packed, shuttle_small):
    """workers=N spawns processes the plan owns; close() terminates them."""
    from repro.serve.engine import TreeEngine

    eng = TreeEngine(
        small_packed,
        EngineSpec(mode="integer", plan="remote_tree_parallel", shards=2),
        plan_kwargs={"workers": 2, "model_id": "t", "version": 1},
    )
    eng.predict_scores(shuttle_small[2][:8].astype(np.float32))
    procs = [c.proc for c in eng.plan._conns if c.proc is not None]
    assert len(procs) == 2
    eng.close()
    for p in procs:
        assert p.wait(timeout=10) is not None


# ---------------------------------------------------------------------------
# gateway integration
# ---------------------------------------------------------------------------

def test_gateway_remote_spec_end_to_end(small_packed, worker_pair,
                                        shuttle_small):
    import asyncio

    from repro.obs import Tracer
    from repro.serve import Gateway, ModelRegistry
    from repro.serve.engine import TreeEngine

    X = shuttle_small[2][:40].astype(np.float32)
    ref_s, ref_p = TreeEngine(small_packed, "integer").predict_scores(X)
    reg = ModelRegistry()
    reg.register_packed("m", small_packed)
    tracer = Tracer(sample=1.0)

    async def run():
        gw = Gateway(reg, "integer:reference+remote_tree_parallel:2",
                     plan_kwargs={"workers": list(worker_pair)},
                     cache_rows=0, tracer=tracer)
        s, p = await gw.submit("m", X)
        st = gw.stats()["per_model"]["m"]
        await gw.close()
        return s, p, st

    s, p, st = asyncio.run(run())
    assert np.array_equal(s, ref_s)
    assert np.array_equal(p, ref_p)
    assert st["spec"] == "integer:reference+remote_tree_parallel:2"
    assert "remote" in st["compile_ms_by_bucket"]
    assert all(lbl.startswith("w") for lbl in st["shards"])
    # worker-side spans were grafted under the shard dispatch spans
    spans = tracer.spans()
    shard_ids = {s_.span_id for s_ in spans if s_.name.startswith("shard:w")}
    worker_spans = [s_ for s_ in spans if s_.name.startswith("worker:")]
    assert shard_ids and worker_spans
    assert all(s_.parent_id in shard_ids for s_ in worker_spans)


def test_gateway_close_drains_inflight(small_packed, shuttle_small):
    """close() resolves requests already enqueued instead of failing them."""
    import asyncio

    from repro.serve import Gateway, ModelRegistry
    from repro.serve.engine import TreeEngine

    X = shuttle_small[2][:16].astype(np.float32)
    ref_s, _ = TreeEngine(small_packed, "integer").predict_scores(X)
    reg = ModelRegistry()
    reg.register_packed("m", small_packed)

    async def run():
        gw = Gateway(reg, "integer:reference+tree_parallel:2", cache_rows=0,
                     max_delay_ms=50.0)
        tasks = [asyncio.ensure_future(gw.submit("m", X)) for _ in range(4)]
        await asyncio.sleep(0)  # let every submit reach its queue
        await gw.close()  # must drain, not cancel
        return await asyncio.gather(*tasks)

    for s, _ in asyncio.run(run()):
        assert np.array_equal(s, ref_s)


def test_worker_span_jsonl(small_packed, shuttle_small, tmp_path):
    """Workers append per-request span JSONL when given --span-out."""
    import json

    from repro.serve.engine import TreeEngine

    procs, addrs = spawn_local_workers(1, span_dir=str(tmp_path))
    try:
        eng = TreeEngine(
            small_packed,
            EngineSpec(mode="integer", plan="remote_tree_parallel", shards=1),
            plan_kwargs={"workers": addrs, "model_id": "t", "version": 1},
        )
        eng.predict_scores(shuttle_small[2][:8].astype(np.float32))
        eng.close()
        time.sleep(0.2)  # the worker flushes per line; give it a beat
        files = list(tmp_path.glob("worker_*.jsonl"))
        assert files
        recs = [json.loads(ln) for f in files
                for ln in f.read_text().splitlines()]
        assert recs
        assert all("spans" in r and r["model"] == "t" for r in recs)
        names = {sp["name"] for r in recs for sp in r["spans"]}
        assert "predict" in names
    finally:
        _kill_all(procs)


def test_spawn_refuses_when_caller_holds_an_accelerator(monkeypatch):
    """A device belongs to one process: once the caller has initialised a
    non-CPU backend, spawned workers could not reach it, so spawning fails
    up front with a clear error instead of contending for the chip."""
    from repro.serve import worker

    assert worker._held_accelerator() is None  # this suite runs on CPU
    monkeypatch.setattr(worker, "_held_accelerator", lambda: "tpu")
    with pytest.raises(RuntimeError, match="already holds the 'tpu' device"):
        spawn_local_workers(1)

"""The unpruned forest's cell and the bursty, hot-set cell: their catalog
entries, the forest rule's node counts, the kernel readers the deep cell
reports, and the hot set's rows regenerated from each record's key."""
import asyncio
from types import SimpleNamespace

import numpy as np
import pytest

from bench.catalog import Catalog
from bench.forest import SHAPE_SEED, draw_topology
from bench.harness import Load
from bench.trace_reduce import DeviceTrace
from bench.traffic.onoff_hot import HOT_KEY, HotLoad, hot_keys
from bench.work import ForestWork, least_time_of_batches

V5E = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def catalog():
    return Catalog()


@pytest.mark.parametrize("cell,config,traffic,per_layer", [
    ("sklearn-rf-covtype.batch", "sklearn-rf-covtype", "batch-8x256",
     ["kernel_roofline", "device_idle_share", "step_mfu", "kernel_ms.tput"]),
    ("rf-esa.burst", "intreeger-rf", "esa-burst", ["device_idle_share"]),
])
def test_new_cells_resolve(catalog, cell, config, traffic, per_layer):
    got = catalog.cell(cell)
    assert got.chips == 1
    assert got.config["name"] == config and got.traffic["name"] == traffic
    assert [m["name"] for m in got.end_to_end] == ["rows_per_s", "setup_s"]
    assert [m["name"] for m in got.per_layer] == per_layer
    assert got.config["route"] == "integer:pallas@leaf_major"
    catalog.generator(got.traffic["kind"]).drive


def test_sklearn_rf_covtype_config(catalog):
    cfg = catalog.config("sklearn-rf-covtype")
    assert (cfg["n_trees"], cfg["max_depth"], cfg["n_features"],
            cfg["n_classes"]) == (100, 48, 54, 7)
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    hb = catalog.config("hb-rf-covtype")
    assert cfg["rows"] == hb["rows"] and cfg["serve"] == hb["serve"]
    assert {"nodes", "max_depth", "leaves", "rows"} <= set(cfg["assumed"])


def test_sklearn_rf_covtype_node_counts(catalog):
    """The rule's topologies alone (no fill): the counts the configuration
    states under ``assumed``, every tree at depth 48."""
    cfg = catalog.config("sklearn-rf-covtype")
    rng = np.random.default_rng(SHAPE_SEED)
    tops = [draw_topology(rng, max_depth=cfg["max_depth"],
                          full_depth=cfg["shape"]["full_depth"],
                          split_prob=cfg["shape"]["split_prob"])
            for _ in range(cfg["n_trees"])]
    counts = np.asarray([len(split) for split, _, _ in tops])
    assert (counts.min(), counts.max()) == (31_461, 53_311)
    assert counts.mean() == pytest.approx(40_418.16)
    assert {depth for _, _, depth in tops} == {48}
    # every internal node splits in two: one more leaf than splits
    assert all(2 * split.sum() + 1 == len(split) for split, _, _ in tops)


def test_deep_readers_on_a_synthetic_trace(catalog):
    work = ForestWork(n_trees=100, depth=48, n_features=54, n_classes=7,
                      nodes=4_041_816, leaves=2_020_958)
    batches = [256] * 30
    ctx = SimpleNamespace(
        trace=DeviceTrace(busy_s=0.95, kernel_calls=[
            ("%tree_traverse_leaf_major.1", 0.03)] * 30),
        batch_rows=batches, peaks=V5E, work=work, trace_window_s=1.0)
    least, bound = least_time_of_batches(work, batches, V5E)
    assert bound == "bytes"
    read = lambda name: catalog.reader(name).read(ctx)
    assert read("kernel_ms.tput") == pytest.approx(30.0)
    assert read("kernel_roofline") == pytest.approx(100 * least / 0.9)
    assert read("step_mfu") == pytest.approx(100 * least / 1.0)
    assert 0 < read("kernel_roofline") < 1
    untraced = SimpleNamespace(trace=None, batch_rows=None, peaks=V5E,
                               work=work, trace_window_s=None)
    for name in ("kernel_ms.tput", "kernel_roofline", "step_mfu"):
        assert catalog.reader(name).read(untraced) is None


class SeededRows:
    def take(self, stream, k, n):
        return np.random.default_rng([stream, k, n]).random((n, 3), np.float32)


class EchoGateway:
    async def submit(self, model, X):
        await asyncio.sleep(0)
        return X.copy(), np.zeros(len(X), np.int32)


def test_hot_set_is_cut_into_keys_of_every_size(catalog):
    mix = catalog.traffic("esa-burst")
    keys = hot_keys(mix)
    assert sorted(keys) == list(range(1, 9))
    assert sum(n * len(k) for n, k in keys.items()) == 1024
    flat = [k for ks in keys.values() for k in ks]
    assert len(set(flat)) == len(flat) and min(flat) == HOT_KEY


def test_onoff_hot_rows_regenerate_from_the_records_key(catalog):
    """Each record's ``k`` and size give back the rows it sent: a hot one's
    key lies in the hot set, a fresh one's is its own index; the shares
    follow the mix."""
    mix = dict(catalog.traffic("esa-burst"), rate_rps=400)
    rows = SeededRows()
    load = Load(EchoGateway(), rows)
    window = asyncio.run(catalog.generator("onoff_hot").drive(
        load, mix, 1.0, 2**31 + 17, 3))
    recs = window.records
    assert len(recs) == 400 and all(r.ok for r in recs)  # one whole period
    hot = [r for r in recs if r.k >= HOT_KEY]
    fresh = [r for r in recs if r.k < HOT_KEY]
    for r in recs:
        assert np.array_equal(r.answer[0], rows.take(3, r.k, r.rows))
    sizes = {k: n for n, ks in hot_keys(mix).items() for k in ks}
    assert all(sizes[r.k] == r.rows for r in hot)
    assert len({r.k for r in fresh}) == len(fresh)
    assert 0.6 < sum(r.rows for r in hot) / sum(r.rows for r in recs) < 0.8
    # the same seed sends the same keys
    again = asyncio.run(catalog.generator("onoff_hot").drive(
        Load(EchoGateway(), rows), mix, 1.0, 2**31 + 17, 3))
    assert sorted(r.k for r in again.records) == sorted(r.k for r in recs)


def test_hot_load_keeps_fresh_requests_fresh():
    mix = {"rows": {"lo": 1, "hi": 8}, "hot": {"share": 0.0, "rows": 1024}}
    load = HotLoad(Load(EchoGateway(), SeededRows()), mix, 5)
    assert all(load.key(3, k, 1 + k % 8) == k for k in range(200))

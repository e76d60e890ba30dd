"""The readers of the dispatch's stages (``upload``, ``launch``, ``fetch``):
each reads the mean of its stage over the window from the gateway's
differenced counters, and finds nothing where the program marks no such
stage."""
from types import SimpleNamespace

import pytest

from bench.catalog import Catalog

READERS = [(f"{stage}_ms.{kind}", stage) for stage in ("upload", "launch", "fetch")
           for kind in ("lat", "tput")]


@pytest.mark.parametrize("metric, stage", READERS)
def test_reader_reads_its_stage(metric, stage):
    stages = {"shard": (4, 40.0), "upload": (4, 6.0), "launch": (4, 10.0),
              "fetch": (4, 8.0)}
    ctx = SimpleNamespace(counters={"stages": stages})
    n, total = stages[stage]
    assert Catalog().reader(metric).read(ctx) == pytest.approx(total / n)


@pytest.mark.parametrize("metric, stage", READERS)
def test_reader_without_its_stage_reads_nothing(metric, stage):
    """A program without the stage (the parent of this change) or a window
    without a batch: the line leaves the metric out."""
    absent = SimpleNamespace(counters={"stages": {"shard": (4, 40.0)}})
    assert Catalog().reader(metric).read(absent) is None
    empty = SimpleNamespace(counters={"stages": {stage: (0, 0.0)}})
    assert Catalog().reader(metric).read(empty) is None


def test_each_stage_metric_is_declared_for_its_cell():
    spec = {m["name"]: m for m in Catalog().spec["per_layer"]}
    for metric, _ in READERS:
        m = spec[metric]
        assert (m["unit"], m["better"], m["source"]) == ("ms", "lower", "program_span")
        cell = "rf-esa.steady" if metric.endswith(".lat") else "hb-rf-covtype.batch"
        assert m["workloads"] == [cell]
        assert m["moves"] == ("p50_ms" if metric.endswith(".lat") else "rows_per_s")


def test_clock_check_counts_kernels_inside_launch_to_fetch():
    """``bench/clock_check.py``: a batch's window runs from its first launch
    to its fetch's end; a kernel call moved by the offset is inside one, or
    is not, and the margins are those of the calls inside."""
    from bench.clock_check import batch_windows, inside
    from repro.obs import Tracer

    tracer = Tracer()
    root = tracer.request_span("request")
    for t in (1_000, 5_000):
        shard = tracer.child(root, "shard:s0:pallas")
        tracer.record("upload", t, t + 100, parent=shard, bytes=8)
        tracer.record("launch", t + 100, t + 300, parent=shard, programs=6)
        tracer.record("fetch", t + 300, t + 900, parent=shard)
        shard.end()
    root.end()
    windows = batch_windows(tracer.spans())
    assert [w[:2] for w in windows] == [(1_100, 1_900), (5_100, 5_900)]
    off = 10_000
    kernels = [(11_200, 11_800), (15_150, 15_850), (13_000, 13_100)]
    got = inside(kernels, windows, off)
    assert (got["kernels"], got["inside"]) == (3, 2)
    assert got["share"] == pytest.approx(2 / 3)
    assert got["min_lead_us"] == pytest.approx(0.05)
    assert got["min_tail_us"] == pytest.approx(0.05)
    assert inside([], windows, off)["share"] is None

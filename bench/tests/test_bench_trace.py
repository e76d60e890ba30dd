"""The reduction from a profiler trace to busy time, kernel calls and the
breakdown."""
import pytest

from bench import trace_reduce
from bench.trace_reduce import merge, reduce

MS = 1_000_000  # ns


def test_merge_unions_overlaps():
    got = merge([(5, 7), (0, 2), (1, 3), (7, 9)])
    assert got.tolist() == [[0, 3], [5, 9]]
    assert merge([]).shape == (0, 2)


def test_reduce_by_hand():
    ops = [("fusion.1", 0, 2 * MS), ("tree_traverse_leaf_major", 1 * MS, 4 * MS),
           ("copy.2", 10 * MS, 11 * MS), ("tree_traverse_gather", 20 * MS, 21 * MS)]
    host = [("PjitFunction(_traverse)", 3 * MS, 12 * MS),
            ("ThreadpoolListener", 0, 30 * MS), ("TransferToDevice", 12 * MS, 19 * MS)]
    dt = reduce({"/device:TPU:0": ops}, host)
    assert dt.busy_s == pytest.approx(0.006)  # [0,4] + [10,11] + [20,21]
    assert dt.kernel_calls == [("tree_traverse_leaf_major", 0.003),
                               ("tree_traverse_gather", 0.001)]
    assert dt.device_ops[0] == ["tree_traverse_leaf_major", 0.003]
    # gaps [11,20] and [4,10]; each named by the host event overlapping it
    # most while lying mostly inside it, not the thread's enclosing event
    names = [n for n, _ in dt.idle_gaps]
    assert names == ["TransferToDevice", "PjitFunction(_traverse)"]
    assert [s for _, s in dt.idle_gaps] == pytest.approx([0.009, 0.006])


def test_devices_are_averaged_and_an_empty_trace_refused():
    a = [("x", 0, 4 * MS)]
    b = [("x", 0, 2 * MS)]
    assert reduce({"d0": a, "d1": b}, []).busy_s == pytest.approx(0.003)
    with pytest.raises(ValueError):
        reduce({}, [])
    assert reduce({"d0": a + [("y", 6 * MS, 7 * MS)]}, []).idle_gaps == [["idle", 0.002]]


def test_recorded_chip_trace():
    """A 0.3 s window of ``rf-esa.steady`` traced on a TPU v5 lite."""
    from bench.tests.conftest import DATA

    devices, host, modules = trace_reduce.read_planes(DATA / "rf-esa.steady.xplane.pb.gz")
    assert list(devices) == ["/device:TPU:0"] and len(host) > 1000
    assert len(modules) == 366 and modules[0][0].startswith("jit_bitcast_convert_type")
    dt = reduce(devices, host)
    # 61 calls of the scan kernel and nothing else by that name: an op whose
    # HLO text only mentions the kernel is not a call
    assert len(dt.kernel_calls) == 61
    assert {n for n, _ in dt.kernel_calls} == {"%tree_traverse_leaf_major.1"}
    kernel_s = sum(s for _, s in dt.kernel_calls)
    assert kernel_s == pytest.approx(0.022477054, rel=1e-6)
    assert dt.busy_s == pytest.approx(0.023316852, rel=1e-6)
    assert kernel_s < dt.busy_s
    assert dt.device_ops[0][0] == "%tree_traverse_leaf_major.1"
    assert len(dt.idle_gaps) == 10 and dt.idle_gaps[0][0] == "shard_args"
    assert dt.idle_gaps[0][1] == pytest.approx(0.006450001, rel=1e-6)


def test_clock_offset_moves_spans_onto_the_trace():
    """The mark's first run on the device against its dispatch on the host
    clock: spans moved by the offset name the gaps they fill."""
    modules = [("jit_other(1)", 1 * MS, 2 * MS), ("jit_bench_clock_mark(7)", 5 * MS, 6 * MS),
               ("jit_bench_clock_mark(7)", 9 * MS, 10 * MS)]
    offset = trace_reduce.clock_offset_ns(modules, "jit_bench_clock_mark", 1_000 * MS)
    assert offset == 5 * MS - 1_000 * MS
    assert trace_reduce.clock_offset_ns(modules, "jit_absent", 0) is None
    spans = [("shard", 1_010 * MS, 1_019 * MS), ("request", 990 * MS, 1_100 * MS)]
    host = [(n, s + offset, e + offset) for n, s, e in spans]
    ops = {"/device:TPU:0": [("k", 5 * MS, 6 * MS), ("k", 24 * MS, 25 * MS)]}
    assert reduce(ops, host).idle_gaps[0][0] == "shard"

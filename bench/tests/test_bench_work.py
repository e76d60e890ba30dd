"""bench/work.py against a count made by hand."""
import numpy as np
import pytest

from bench.forest import Forest, Tree
from bench.work import ForestWork, least_time, least_time_of_batches

PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def stump_and_pair(n_classes=3):
    """Tree 0: one split (3 nodes, 2 leaves); tree 1: root, one inner split
    on the left (5 nodes, 3 leaves)."""
    def tree(feature, left, right, depth):
        n = len(feature)
        probs = np.zeros((n, n_classes))
        probs[np.asarray(feature) < 0] = 1.0 / n_classes
        return Tree(np.asarray(feature, np.int32), np.zeros(n, np.float32),
                    np.asarray(left, np.int32), np.asarray(right, np.int32),
                    probs, depth)

    t0 = tree([0, -1, -1], [1, 1, 2], [2, 1, 2], 1)
    t1 = tree([1, 0, -1, -1, -1], [1, 3, 2, 3, 4], [2, 4, 2, 3, 4], 2)
    return Forest([t0, t1], n_classes, 4)


def test_counts_by_hand():
    w = ForestWork.of(stump_and_pair(), depth=2)
    assert (w.nodes, w.leaves) == (8, 5)
    # 10 rows x 2 trees x (2 * depth 2 + 3 classes)
    assert w.ops(10) == 10 * 2 * 7 == 140
    # keys in 10*4*4, partials out 10*3*4, nodes 8*16, leaves 5*3*4
    assert w.bytes(10) == 160 + 120 + 128 + 60 == 468


def test_least_time_names_its_roof():
    w = ForestWork.of(stump_and_pair(), depth=2)
    t, bound = least_time(w, 10, PEAKS)
    assert bound == "bytes" and t == pytest.approx(468 / 819e9)
    t, bound = least_time(w, 10, {"int8_ops_per_s": 1.0, "hbm_bytes_per_s": 819e9})
    assert bound == "ops" and t == 140.0


def test_least_time_sums_batches():
    w = ForestWork.of(stump_and_pair(), depth=2)
    total, bound = least_time_of_batches(w, [10, 1, 256], PEAKS)
    assert bound == "bytes"
    assert total == pytest.approx(sum(w.bytes(r) for r in (10, 1, 256)) / 819e9)

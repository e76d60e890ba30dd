"""The work of serving rows through a forest, counted from the forest alone.

Whatever walk implements it, one batch of ``rows`` rows through ``T`` trees
of depth ``depth`` over ``C`` classes needs at least:

- ops: ``rows * T * (2 * depth + C)``, one int32 compare and one select per
  level of each tree, and ``C`` adds per tree;
- bytes: the row keys in (``rows * F * 4``), the partials out
  (``rows * C * 4``), and the forest read once: 16 bytes per real node
  (feature, key, left, right) and ``C * 4`` per real leaf.

Real node counts, not padded ones: a later change that pads differently or
swaps the walk leaves this yardstick where it is.  The least time is the
larger of ops over the peak op rate and bytes over the memory bandwidth.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ForestWork:
    n_trees: int
    depth: int
    n_features: int
    n_classes: int
    nodes: int   # real nodes, all trees
    leaves: int  # real leaves, all trees

    @classmethod
    def of(cls, forest, depth: int) -> "ForestWork":
        return cls(n_trees=len(forest.trees_), depth=int(depth),
                   n_features=forest.n_features_, n_classes=forest.n_classes_,
                   nodes=int(forest.node_counts.sum()),
                   leaves=forest.leaf_count)

    def ops(self, rows: int) -> int:
        return rows * self.n_trees * (2 * self.depth + self.n_classes)

    def bytes(self, rows: int) -> int:
        return (rows * self.n_features * 4 + rows * self.n_classes * 4
                + self.nodes * 16 + self.leaves * self.n_classes * 4)


def least_time(work: ForestWork, rows: int, peaks: dict) -> tuple:
    """(seconds, bound) of one batch: the bound is ``"ops"`` or ``"bytes"``.

    The op rate is the chip's published int8 peak: no int32 vector peak is
    published, and a higher peak only makes a share smaller."""
    t_ops = work.ops(rows) / float(peaks["int8_ops_per_s"])
    t_bytes = work.bytes(rows) / float(peaks["hbm_bytes_per_s"])
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")


def least_time_of_batches(work: ForestWork, batch_rows, peaks: dict) -> tuple:
    """Summed least time of many batches, and the roof that bounds most of
    it (``"ops"``, ``"bytes"``)."""
    total, by = 0.0, {"ops": 0.0, "bytes": 0.0}
    for rows in batch_rows:
        t, bound = least_time(work, int(rows), peaks)
        total += t
        by[bound] += t
    return total, max(by, key=by.get)

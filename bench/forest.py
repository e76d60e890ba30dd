"""Draw a forest from the seed instead of training one.

Training at full width takes tens of seconds, and every run of every check
would pay for it.  The benchmark draws the trees:

- every node splits down to ``shape.full_depth``; below it, each node splits
  with probability ``shape.split_prob`` until ``max_depth`` (a complete tree
  when ``full_depth == max_depth``);
- a split's feature is uniform over the features, and its threshold is that
  feature's value in a row drawn from the configuration's row generator, so
  rows take both branches;
- a leaf holds a class-probability vector drawn from a Dirichlet
  distribution.

The seed changes what the trees hold and their order, never their shapes:
the topologies come from :data:`SHAPE_SEED`, so every seed walks the same
node counts and depths, its programs have the same shapes, and a run finds
every compiled program in the persistent cache whatever its seed.

Nodes are numbered level by level, so every child comes after its parent.
The result has the attributes ``ForestIR.from_forest`` reads
(``trees_``, ``n_classes_``, ``n_features_``) and is what the reference walks.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

POOL_ROWS = 4096  # rows the thresholds are drawn from
POOL_STREAM = 2   # row stream of the threshold pool (requests use others)
SHAPE_SEED = 5    # the topologies' seed, the same for every run; at intreeger-rf
                  # widths its largest tree has 495 nodes, 4 chunks of 128 as
                  # the 471 of the program's trained forest


@dataclass
class Tree:
    feature: np.ndarray     # (n,) int32, -1 on leaves
    threshold: np.ndarray   # (n,) float32, 0 on leaves
    left: np.ndarray        # (n,) int32, a leaf points at itself
    right: np.ndarray       # (n,) int32
    leaf_probs: np.ndarray  # (n, C) float64, zero rows on internal nodes
    depth: int

    @property
    def n_nodes(self) -> int:
        return len(self.feature)


@dataclass
class Forest:
    trees_: list
    n_classes_: int
    n_features_: int

    @property
    def node_counts(self) -> np.ndarray:
        return np.asarray([t.n_nodes for t in self.trees_])

    @property
    def leaf_count(self) -> int:
        return int(sum((t.feature < 0).sum() for t in self.trees_))

    @property
    def max_depth(self) -> int:
        return max(t.depth for t in self.trees_)


def draw_topology(rng, *, max_depth, full_depth, split_prob):
    """-> (split flag, first child) of each node, level by level."""
    levels = []  # per level: (split flags, first child index of each split)
    width, total = 1, 1
    for d in range(max_depth + 1):
        if d == max_depth:
            split = np.zeros(width, bool)
        elif d < full_depth:
            split = np.ones(width, bool)
        else:
            split = rng.random(width) < split_prob
        k = int(split.sum())
        first = np.full(width, -1, np.int64)
        first[split] = total + 2 * np.arange(k)
        levels.append((split, first))
        total += 2 * k
        width = 2 * k
        if width == 0:
            break
    return (np.concatenate([s for s, _ in levels]),
            np.concatenate([f for _, f in levels]), len(levels) - 1)


def fill_tree(rng, topology, pool, *, n_classes, alpha) -> Tree:
    split, first, depth = topology
    n = len(split)
    idx = np.arange(n, dtype=np.int32)
    n_split = int(split.sum())
    feature = np.full(n, -1, np.int32)
    feature[split] = rng.integers(0, pool.shape[1], n_split)
    threshold = np.zeros(n, np.float32)
    threshold[split] = pool[rng.integers(0, len(pool), n_split), feature[split]]
    left = np.where(split, first, idx).astype(np.int32)
    right = np.where(split, first + 1, idx).astype(np.int32)
    probs = np.zeros((n, n_classes), np.float64)
    probs[~split] = rng.dirichlet(np.full(n_classes, alpha), n - n_split)
    return Tree(feature, threshold, left, right, probs, depth=depth)


def draw_forest(cfg: dict, rows, seed: int) -> Forest:
    """The configuration's forest for ``seed``; ``rows`` is its row
    generator (``rows/<generator>.py`` ``Rows``)."""
    shape = cfg["shape"]
    rng_shape = np.random.default_rng(SHAPE_SEED)
    topologies = [
        draw_topology(rng_shape, max_depth=int(cfg["max_depth"]),
                      full_depth=int(shape["full_depth"]),
                      split_prob=float(shape.get("split_prob", 1.0)))
        for _ in range(int(cfg["n_trees"]))
    ]
    pool = rows.take(POOL_STREAM, 0, POOL_ROWS)
    rng = np.random.default_rng([seed, 1])
    trees = [fill_tree(rng, topologies[i], pool, n_classes=int(cfg["n_classes"]),
                       alpha=float(cfg["leaves"]["dirichlet_alpha"]))
             for i in rng.permutation(len(topologies))]
    return Forest(trees, int(cfg["n_classes"]), int(cfg["n_features"]))

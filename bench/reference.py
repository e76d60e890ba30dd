"""The plain reference: a numpy walk of the drawn forest.

It follows the paper's definition and nothing of the program: a row goes
left where ``x[feature] <= threshold`` in float32 (the FlInt key compare is
order-preserving, so the integer walk must agree), and each tree adds its
leaf's class probabilities in unsigned fixed point, ``floor(p * scale)``
with ``scale = floor((2**bits - 1) / n_trees)`` computed in float64, so the
sum of ``n_trees`` addends cannot overflow ``bits`` bits.  At ``bits=32``
that is the served integer mode; the predicted class is the first largest
score.  Rows are walked in blocks, so a sample of any size fits.
"""
from __future__ import annotations

import numpy as np

BLOCK_ROWS = 8192


def leaf_fixed(tree, n_trees: int, bits: int = 32) -> np.ndarray:
    scale = ((1 << bits) - 1) // n_trees
    return np.floor(tree.leaf_probs * float(scale)).astype(np.uint64)


def walk(tree, X: np.ndarray) -> np.ndarray:
    """Leaf index of every row of ``X`` in one tree."""
    node = np.zeros(len(X), np.int64)
    rows = np.arange(len(X))
    for _ in range(tree.depth):  # a leaf points at itself
        f = np.maximum(tree.feature[node], 0)
        go_left = X[rows, f] <= tree.threshold[node]
        node = np.where(go_left, tree.left[node], tree.right[node])
    return node


def partials(forest, X: np.ndarray, bits: int = 32) -> np.ndarray:
    """(B, C) fixed-point class sums at ``bits`` bits, as uint64."""
    X = np.asarray(X, np.float32)
    T = len(forest.trees_)
    tables = [leaf_fixed(t, T, bits) for t in forest.trees_]
    out = np.zeros((len(X), forest.n_classes_), np.uint64)
    for lo in range(0, len(X), BLOCK_ROWS):
        block = X[lo:lo + BLOCK_ROWS]
        acc = out[lo:lo + len(block)]
        for tree, table in zip(forest.trees_, tables):
            acc += table[walk(tree, block)]
    if out.max(initial=0) >= (1 << bits):
        raise OverflowError("fixed-point sum overflowed its bits")
    return out


def scores(forest, X: np.ndarray, bits: int = 32):
    """Served-mode answer: (uint32-valued scores at the 32-bit scale, class).

    ``bits < 32`` is the control: the same walk with leaves quantized at
    ``bits`` bits, rescaled to the 32-bit scale for the comparison."""
    acc = partials(forest, X, bits)
    if bits != 32:
        T = len(forest.trees_)
        ratio = (((1 << 32) - 1) // T) / (((1 << bits) - 1) // T)
        acc = np.rint(acc.astype(np.float64) * ratio).astype(np.uint64)
    return acc, np.argmax(acc, axis=1)

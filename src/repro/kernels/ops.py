"""Jitted public wrapper around the tree-traversal Pallas kernels.

Picks tiling-aligned blocks inside the VMEM/SMEM budgets, pads (rows to
``block_b`` multiples, trees to ``block_t`` multiples and nodes to whole
node blocks of 128-node chunks, all with inert self-looping zero-mass
entries), lays the tables out for the kernel (``tree_traverse`` module
docstring), and exposes an ensemble-level entry point.  ``interpret=None`` lets the platform decide:
compiled on TPU, interpreted on CPU.

Layout contract (ForestIR): the kernel consumes dense ``(T, N)`` node tables
— the IR's ``padded`` or ``leaf_major`` materializations (the paper's codegen
step re-targeted at tensors); the ``ragged`` layout has no VMEM-tileable
shape and belongs to the table-walk C backend instead.  This module takes
the walk (``impl``) as given: which walk a batch runs is the serving
backend's choice (``repro.backends.pallas``), made from the budget facts
:func:`holds_whole_trees` and :func:`pick_blocks` report.

A batch is one device program: the kernel's jitted call (``_traverse``)
takes the float32 rows and the tables, and keys the rows (``float_to_key``),
pads them and the tables, lays them out and runs the kernel inside it.  No
argmax is launched for the partials.

Host steps are marked as the shard call's stages (``repro.obs.stages``):
handing host arrays to the device is ``upload`` (``bytes``), and the block
choice and the jitted call are ``launch`` (``programs=1``, the one program
a batch; it also notes ``impl``, the walk, and ``node_blocks``, the scan's
node blocks).

The key-level entry point, :func:`tree_predict_integer`, takes host arrays
and uploads them on every call.  A serving backend instead places its node
tables once with :func:`place_tables` (the ``place`` stage) and hands the
device-resident copy to :func:`packed_partials`; each batch then uploads
only its rows.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flint import float_to_key
from repro.kernels.tree_traverse import LANES, resolve_interpret, tree_traverse
from repro.obs import stage

# per-grid-cell budgets for the pipeline's double-buffered blocks; the v5e
# compiler reports 1 MiB of SMEM, and VMEM's default scoped limit is larger
# than this VMEM budget
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024
_SMEM_BUDGET_BYTES = 512 * 1024

# below this many rows a full-forest grid cell pays the whole per-cell scan
# for a handful of rows; block_t is scaled down proportionally instead.  A
# host-clock cut from a CPU snapshot, not yet measured on the chip.
_TINY_BATCH_ROWS = 64


def _round_up(v, m):
    return -(-v // m) * m


def _upload(*arrays, name="upload"):
    """Each host (numpy) array put on the device as stage ``name``, whose
    ``bytes`` are theirs; without one, ``arrays`` come back as they are
    (device arrays, tracers, None), and no stage is marked."""
    host = [a for a in arrays if isinstance(a, np.ndarray)]
    if not host:
        return arrays
    with stage(name, bytes=sum(a.nbytes for a in host)):
        return tuple(jnp.asarray(a) if isinstance(a, np.ndarray) else a
                     for a in arrays)


def place_tables(packed):
    """``packed``'s node tables put on the default device as the ``place``
    stage, for :func:`packed_partials`' ``tables``: feature, threshold key,
    left, right, fixed-point leaves and the leaf_major internal counts
    (None off that layout, or where its node order is not scannable)."""
    return _upload(packed.feature, packed.threshold_key, packed.left,
                   packed.right, packed.leaf_fixed, packed.internal_counts,
                   name="place")


def _block_words(block_b, block_t, n, f, c):
    """VMEM words per grid cell, two pipeline buffers per block: the x tiles,
    the chunked node fields (4 rows, sublane-padded to 8), the chunked leaf
    table and the output tiles, at the kernel's padded widths.  ``n`` is the
    nodes a cell holds: whole trees, or one node block of the scan."""
    n, c = _round_up(n, LANES), _round_up(c, 8)
    return 2 * (block_b * _round_up(f, 8) + block_t * n * (8 + c)
                + block_b * c)


def _smem_words(block_t, n):
    """SMEM words per grid cell: the scalar node fields (feature, key, left,
    right) of ``block_t`` trees' ``n`` nodes, two pipeline buffers."""
    return 2 * block_t * 4 * _round_up(n, LANES)


def _fits(block_b, block_t, n, f, c, chunked=False):
    vmem = _block_words(block_b, block_t, n, f, c)
    if chunked:  # the scan's scratch: each row's node per tree, (1, 128)
        vmem += block_t * block_b * 8  # rows padded to 8 sublanes
    return (vmem * 4 <= _VMEM_BUDGET_BYTES
            and _smem_words(block_t, n) * 4 <= _SMEM_BUDGET_BYTES)


def _node_block(block_b, block_t, n, f, c):
    """Nodes per grid cell of the scan at (block_b, block_t): every node of
    a tree when whole trees fit, else the fewest node blocks that fit, as
    even as 128-node chunks allow; None when not even one chunk fits."""
    npad = _round_up(n, LANES)
    if _fits(block_b, block_t, npad, f, c):
        return npad
    lo, hi = 0, npad // LANES - 1  # chunks a block may hold: lo fits, hi not
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if _fits(block_b, block_t, mid * LANES, f, c, chunked=True):
            lo = mid
        else:
            hi = mid - 1
    if lo == 0:
        return None
    blocks = -(-npad // (lo * LANES))
    return _round_up(-(-npad // blocks), LANES)


def _align_block_b(block_b, b):
    """Rows per grid cell: a multiple of 128 (rows ride the lanes), no more
    than the batch padded to 128."""
    return max(LANES, min(_round_up(block_b, LANES), _round_up(b, LANES)))


def _align_block_t(block_t, t):
    """Trees per grid cell: a multiple of 8, or the whole tree dimension."""
    return t if block_t >= t else min(t, _round_up(max(1, block_t), 8))


def holds_whole_trees(t, n, f, c, block_t=None):
    """Whether whole trees fit one grid cell of 128 rows at ``block_t``
    trees (aligned), or at the smallest aligned tiling, (128, min(t, 8)),
    when it is None: the gather and onehot walks need it."""
    block_t = min(t, 8) if block_t is None else _align_block_t(block_t, t)
    return _fits(LANES, block_t, n, f, c)


def pick_blocks(b, t, n, f, c, block_b=256, *, chunk_nodes=False):
    """Choose aligned (block_b, block_t, block_n) so a cell fits the
    VMEM/SMEM budgets.

    ``block_b`` is a multiple of 128, ``block_t`` a multiple of 8 or ``t``,
    and ``block_n`` a multiple of 128: the nodes of a tree a cell holds,
    every node (``n`` padded to 128) unless ``chunk_nodes``.  The tree
    dimension shrinks first.  Once ``block_t`` is at its floor, ``min(t,
    8)``, the scan (``chunk_nodes``) cuts the node axis into the fewest
    blocks that fit (:func:`_node_block`).  When that is over budget too
    (wide leaf tables make the output tiles and leaf chunks dominate), the
    row block halves and the search repeats.  Nothing fitting even at
    (128, min(t, 8), 128) raises ``ValueError``.

    Tiny batches (``b < 64``) additionally clamp ``block_t`` proportionally
    to the rows that amortize it (a host-clock cut, not yet measured on the
    chip; the clamp only ever shrinks, so the fit is preserved).

    Memoized: a serving backend asks again at the same shapes every batch.
    """
    return _pick_blocks(b, t, n, f, c, block_b, chunk_nodes,
                        _VMEM_BUDGET_BYTES, _SMEM_BUDGET_BYTES)


@lru_cache(maxsize=1024)
def _pick_blocks(b, t, n, f, c, block_b, chunk_nodes, *budgets):
    """:func:`pick_blocks`' search: pure in its arguments and the module's
    budgets, which ride in the key so that a changed budget is searched
    anew."""
    block_b = _align_block_b(block_b, b)
    npad = _round_up(n, LANES)
    sizes = [t] + list(range(_round_up(t, 8) - 8, 0, -8))
    while True:
        for block_t in sizes:
            if _fits(block_b, block_t, npad, f, c):
                if b < _TINY_BATCH_ROWS:
                    block_t = min(block_t, _align_block_t(
                        (t * b) // _TINY_BATCH_ROWS, t))
                return block_b, block_t, npad
        block_n = (_node_block(block_b, sizes[-1], n, f, c)
                   if chunk_nodes else None)
        if block_n is not None:
            return block_b, sizes[-1], block_n
        if block_b == LANES:
            raise ValueError(
                f"no tiling of {t} trees of {n} nodes, {f} features and {c} "
                f"classes fits the budgets ({_VMEM_BUDGET_BYTES} B of VMEM, "
                f"{_SMEM_BUDGET_BYTES} B of SMEM) even at (128, {sizes[-1]}, "
                f"{128 if chunk_nodes else npad})"
                + ("" if chunk_nodes or npad == LANES else
                   "; the leaf_major scan can cut the node axis"))
        block_b = _align_block_b(block_b // 2, b)


def _pinned_node_block(block_b, block_t, n, f, c, chunk_nodes):
    """``block_n`` for a pinned (block_b, block_t): whole trees where they
    fit, node blocks on the scan, None where nothing fits.  Only the kernel
    tests pin ``block_t`` (the block-shape sweeps and the pinned tree-block
    checks); no served route does."""
    if chunk_nodes:
        return _node_block(block_b, block_t, n, f, c)
    npad = _round_up(n, LANES)
    return npad if _fits(block_b, block_t, npad, f, c) else None


@partial(jax.jit, static_argnames=("depth", "block_b", "block_t", "block_n",
                                   "impl", "interpret"))
def _traverse(x, feature, key, left, right, leaf, nint, *,
              depth, block_b, block_t, block_n, impl, interpret):
    """Key the rows, pad and lay out them and the (T, N) tables for the
    kernel, run it, and return (B, C) uint32 partials: one device program.

    ``x``: float32 rows, keyed here (``float_to_key``) before they are
    padded, so padded rows carry key 0; or int32 keys, walked as given."""
    if x.dtype == jnp.float32:
        x = float_to_key(x)
    b, f = x.shape
    t, n = feature.shape
    c = leaf.shape[-1]
    bp, tp, npad = _round_up(b, block_b), _round_up(t, block_t), _round_up(n, block_n)

    # inert padding: feature-less self-looping nodes with zero leaf mass
    # fill every tree to npad nodes and the forest to tp trees
    def pad(a, fill=0):
        return jnp.pad(a, ((0, tp - t), (0, npad - n)), constant_values=fill)

    inside = (jnp.arange(tp)[:, None] < t) & (jnp.arange(npad)[None, :] < n)
    selfloop = jnp.broadcast_to(jnp.arange(npad, dtype=jnp.int32), (tp, npad))
    fields = jnp.stack([
        pad(feature, -1),
        pad(key),
        jnp.where(inside, pad(left), selfloop),
        jnp.where(inside, pad(right), selfloop),
    ], axis=1)
    leaf = jax.lax.bitcast_convert_type(
        jnp.pad(leaf, ((0, tp - t), (0, npad - n), (0, 0))), jnp.int32)
    leaf = leaf.reshape(tp, npad // LANES, LANES, c).transpose(0, 1, 3, 2)
    x = jnp.pad(x, ((0, bp - b), (0, 0)))
    x = x.reshape(bp // LANES, LANES, f).transpose(0, 2, 1)
    if nint is not None:  # padding trees have no internal prefix to scan
        nint = jnp.pad(nint.astype(jnp.int32), (0, tp - t))
    out = tree_traverse(x, fields, leaf, nint, depth=depth, block_b=block_b,
                        block_t=block_t, block_n=block_n, impl=impl,
                        interpret=interpret)
    out = out.transpose(0, 2, 1).reshape(bp, c)[:b]
    return jax.lax.bitcast_convert_type(out, jnp.uint32)


def _launch(x, feature, threshold_key, left, right, leaf_fixed, nint, *,
            depth, impl, block_b=256, block_t=None, interpret=None):
    """The ``launch`` stage: choose the blocks and start ``_traverse`` on
    device arrays, one program; -> its (B, C) uint32 partials."""
    if impl == "leaf_major" and nint is None:
        raise ValueError(
            "impl='leaf_major' needs the layout's internal_counts, which "
            "only the leaf_major layout records (see repro.ir.layouts)"
        )
    with stage("launch", programs=1) as launch:
        b, f = x.shape
        t, n = feature.shape
        c = leaf_fixed.shape[-1]
        chunk_nodes = impl == "leaf_major"
        block_b, auto_t, block_n = pick_blocks(b, t, n, f, c, block_b,
                                               chunk_nodes=chunk_nodes)
        if block_t is None:
            block_t = auto_t
        else:
            block_t = _align_block_t(block_t, t)
            block_n = _pinned_node_block(block_b, block_t, n, f, c,
                                         chunk_nodes)
            if block_n is None:
                raise ValueError(
                    f"block_t={block_t} at block_b={block_b} holds no "
                    f"{impl} block of {n}-node trees inside the budgets")
        launch.note(impl=impl, node_blocks=-(-_round_up(n, LANES) // block_n))
        return _traverse(
            x, feature, threshold_key, left, right, leaf_fixed, nint,
            depth=depth, block_b=block_b, block_t=block_t, block_n=block_n,
            impl=impl, interpret=resolve_interpret(interpret),
        )


def tree_predict_integer(
    x_keys,
    feature,
    threshold_key,
    left,
    right,
    leaf_fixed,
    *,
    depth: int,
    block_b: int = 256,
    block_t: int | None = None,
    impl: str = "gather",
    interpret: bool | None = None,
    internal_counts=None,
):
    """Integer ensemble inference via the Pallas kernel, any B/T.

    ``impl="leaf_major"`` selects the linear-scan kernel and requires
    ``internal_counts`` (the leaf_major layout's per-tree internal-prefix
    lengths); the other impls walk any node-table ordering.  ``block_b`` caps
    the rows per grid cell and an explicit ``block_t`` is aligned up (see
    :func:`pick_blocks`); the scan's node block follows from the budgets,
    the other walks hold whole trees.  ``interpret=None`` lets the platform
    decide.  The ``launch`` stage of the kernel's call records the walk
    (``impl``) and its ``node_blocks``.  Returns (B, C) uint32 scores,
    bit-identical to ``ref.tree_predict_integer_ref``.
    """
    x_keys, feature, threshold_key, left, right, leaf_fixed, nint = _upload(
        x_keys, feature, threshold_key, left, right, leaf_fixed,
        internal_counts if impl == "leaf_major" else None)
    return _launch(jnp.asarray(x_keys, jnp.int32), feature, threshold_key,
                   left, right, leaf_fixed, nint, depth=depth, impl=impl,
                   block_b=block_b, block_t=block_t, interpret=interpret)


def packed_partials(packed, X, *, impl, tables, **kw):
    """Walk ``packed``'s device-resident node ``tables`` (:func:`place_tables`
    of this same artifact) over the float rows ``X`` with the walk
    ``impl``, as one device program (:func:`_traverse` keys the rows
    itself): -> (B, C) uint32 partials.  Only ``X`` is uploaded.

    ``packed``: the node-table artifact (``padded`` or ``leaf_major``
    layout) the tables came from.  The walk is taken as given.  ``kw``:
    ``block_b``, ``block_t`` and ``interpret``, as
    :func:`tree_predict_integer` takes them.
    """
    # float32 on either side: the program keys float32 rows
    X = (jnp.asarray(X, jnp.float32) if isinstance(X, jax.Array)
         else np.asarray(X, np.float32))
    *nodes, nint = tables
    (x,) = _upload(X)
    return _launch(x, *nodes, nint if impl == "leaf_major" else None,
                   depth=packed.max_depth, impl=impl, **kw)

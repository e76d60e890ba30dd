"""Jitted public wrapper around the tree-traversal Pallas kernels.

Picks tiling-aligned blocks inside the VMEM/SMEM budgets, pads (rows to
``block_b`` multiples, trees to ``block_t`` multiples and nodes to 128-node
chunks, all with inert self-looping zero-mass entries), lays the tables out
for the kernel (``tree_traverse`` module docstring), and exposes an
ensemble-level entry point.  ``interpret=None`` lets the platform decide:
compiled on TPU, interpreted on CPU.

Layout contract (ForestIR): the kernel consumes dense ``(T, N)`` node tables
— the IR's ``padded`` or ``leaf_major`` materializations (the paper's codegen
step re-targeted at tensors).  ``packed_predict_integer`` accepts a
``ForestIR`` directly and materializes the layout its resolved impl walks
(``leaf_major`` for the linear-scan kernel, ``padded`` otherwise); the
``ragged`` layout has no VMEM-tileable shape and belongs to the table-walk C
backend instead.

Host steps are marked as the shard call's stages (``repro.obs.stages``):
handing host arrays to the device is ``upload`` (``bytes``), and the key
transform, the block choice, the kernel's jitted call and the argmax are
``launch`` (``programs``: device programs started).

The entry points take host arrays and upload them on every call.  A
serving backend instead places its node tables once with
:func:`place_tables` (the ``place`` stage) and hands the device-resident
copy to ``packed_predict_integer(..., tables=...)``; each batch then
uploads only its rows.
"""
from __future__ import annotations

from functools import partial

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
# for a handful of rows; block_t is scaled down proportionally instead
_TINY_BATCH_ROWS = 64

# float_to_key runs eagerly, one program each: bitcast, less, subtract, where
_KEY_PROGRAMS = 4


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


def _host_tables(packed):
    """The node tables the kernel walks: feature, threshold key, left,
    right, fixed-point leaves and the leaf_major internal counts (None off
    that layout, or where its node order is not scannable)."""
    return (packed.feature, packed.threshold_key, packed.left, packed.right,
            packed.leaf_fixed, packed.internal_counts)


def place_tables(packed):
    """``packed``'s node tables put on the default device as the ``place``
    stage, for ``packed_predict_integer``'s ``tables``: the conversion each
    call's upload makes, so the kernel's jitted call sees the same shapes
    and dtypes either way."""
    return _upload(*_host_tables(packed), name="place")


def _block_words(block_b, block_t, n, f, c):
    """VMEM words per grid cell, two pipeline buffers per block: the x tiles,
    the chunked node fields (4 rows, sublane-padded to 8), the chunked leaf
    table and the output tiles, at the kernel's padded widths."""
    n, c = _round_up(n, LANES), _round_up(c, 8)
    return 2 * (block_b * _round_up(f, 8) + block_t * n * (8 + c)
                + block_b * c)


def _smem_words(block_t, n):
    """SMEM words per grid cell: the scalar node fields (feature, key, left,
    right) of ``block_t`` trees, two pipeline buffers."""
    return 2 * block_t * 4 * _round_up(n, LANES)


def _fits(block_b, block_t, n, f, c):
    return (_block_words(block_b, block_t, n, f, c) * 4 <= _VMEM_BUDGET_BYTES
            and _smem_words(block_t, n) * 4 <= _SMEM_BUDGET_BYTES)


def _align_block_b(block_b, b):
    """Rows per grid cell: a multiple of 128 (rows ride the lanes), no more
    than the batch padded to 128."""
    return max(LANES, min(_round_up(block_b, LANES), _round_up(b, LANES)))


def _align_block_t(block_t, t):
    """Trees per grid cell: a multiple of 8, or the whole tree dimension."""
    return t if block_t >= t else min(t, _round_up(max(1, block_t), 8))


def pick_blocks(b, t, n, f, c, block_b=256):
    """Choose aligned (block_b, block_t) so a cell fits the VMEM/SMEM budgets.

    ``block_b`` is a multiple of 128 and ``block_t`` a multiple of 8 or
    ``t``.  The tree dimension shrinks first; when even the smallest aligned
    ``block_t`` is over budget (wide leaf tables make the output tiles and
    leaf chunks dominate), the row block halves and the search repeats.  The
    floor is (128, min(t, 8)), the smallest aligned tiling.

    Tiny batches (``b < 64``) additionally clamp ``block_t`` proportionally
    to the rows that amortize it (a heuristic from host timings; the clamp
    only ever shrinks, so the fit is preserved).
    """
    block_b = _align_block_b(block_b, b)
    sizes = [t] + list(range(_round_up(t, 8) - 8, 0, -8))
    while True:
        for block_t in sizes:
            if _fits(block_b, block_t, n, f, c):
                if b < _TINY_BATCH_ROWS:
                    block_t = min(block_t, _align_block_t(
                        (t * b) // _TINY_BATCH_ROWS, t))
                return block_b, block_t
        if block_b == LANES:
            return LANES, sizes[-1]  # nothing left to shrink
        block_b = _align_block_b(block_b // 2, b)


def pick_blocks_candidates(b, t, n, f, c, block_b=256):
    """The measured-autotune grid around the heuristic: the ``pick_blocks``
    choice plus its aligned, budget-feasible half/double neighbours along
    each axis.

    The heuristic optimizes a *budget*, not a runtime; ``TreeEngine.warm``'s
    autotuner times these candidates on the live host and pins the winner.
    Deduplicated, heuristic first (ties resolve to it), every entry fits the
    budgets, so any candidate is safe to pin.
    """
    auto_b, auto_t = pick_blocks(b, t, n, f, c, block_b)
    cands = [(auto_b, auto_t)]
    for bb, bt in (
        (auto_b, _align_block_t(auto_t // 2, t)),
        (_align_block_b(auto_b // 2, b), auto_t),
        (auto_b, _align_block_t(auto_t * 2, t)),
    ):
        if (bb, bt) not in cands and _fits(bb, bt, n, f, c):
            cands.append((bb, bt))
    return cands


@partial(jax.jit, static_argnames=("depth", "block_b", "block_t", "impl", "interpret"))
def _traverse(x_keys, feature, key, left, right, leaf, nint, *,
              depth, block_b, block_t, impl, interpret):
    """Pad and lay out the (T, N) tables for the kernel, run it, and return
    (B, C) uint32 partials."""
    b, f = x_keys.shape
    t, n = feature.shape
    c = leaf.shape[-1]
    bp, tp, npad = _round_up(b, block_b), _round_up(t, block_t), _round_up(n, LANES)

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
    x = jnp.pad(x_keys, ((0, bp - b), (0, 0)))
    x = x.reshape(bp // LANES, LANES, f).transpose(0, 2, 1)
    if nint is not None:  # padding trees have no internal prefix to scan
        nint = jnp.pad(nint, (0, tp - t))
    out = tree_traverse(x, fields, leaf, nint, depth=depth, block_b=block_b,
                        block_t=block_t, impl=impl, interpret=interpret)
    out = out.transpose(0, 2, 1).reshape(bp, c)[:b]
    return jax.lax.bitcast_convert_type(out, jnp.uint32)


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
    :func:`pick_blocks`).  ``interpret=None`` lets the platform decide.
    Returns (B, C) uint32 scores, bit-identical to
    ``ref.tree_predict_integer_ref``.
    """
    if impl == "leaf_major" and internal_counts is None:
        raise ValueError(
            "impl='leaf_major' needs the layout's internal_counts; "
            "materialize the forest as leaf_major (see repro.ir.layouts)"
        )
    x_keys, feature, threshold_key, left, right, leaf_fixed, nint = _upload(
        x_keys, feature, threshold_key, left, right, leaf_fixed,
        internal_counts if impl == "leaf_major" else None)
    with stage("launch", programs=1):
        x_keys = jnp.asarray(x_keys, jnp.int32)
        b, f = x_keys.shape
        t, n = feature.shape
        c = leaf_fixed.shape[-1]
        block_b, auto_t = pick_blocks(b, t, n, f, c, block_b)
        block_t = auto_t if block_t is None else _align_block_t(block_t, t)
        if nint is not None:
            nint = jnp.asarray(nint, jnp.int32)
        return _traverse(
            x_keys, feature, threshold_key, left, right, leaf_fixed, nint,
            depth=depth, block_b=block_b, block_t=block_t, impl=impl,
            interpret=resolve_interpret(interpret),
        )


def packed_predict_integer(packed, X, impl: str = "auto", tables=None, **kw):
    """Node-table entry point: float features in, (scores, preds) out.

    ``packed``: a node-table artifact (``PackedEnsemble`` in ``padded`` or
    ``leaf_major`` layout) or a ``ForestIR``.  ``impl="auto"`` resolves per
    layout — the linear-scan kernel on ``leaf_major`` tables, ``gather`` on
    ``padded`` — and a ForestIR is materialized into whichever layout the
    resolved impl walks (``leaf_major`` for the scan, ``padded`` otherwise).
    Pinning ``impl="leaf_major"`` on a padded artifact re-materializes it as
    leaf_major through the IR back-reference.

    ``tables``: :func:`place_tables` of this same artifact, walked in place
    of its host arrays, so that only ``X`` is uploaded; without it the
    tables are uploaded with every call.
    """
    if hasattr(packed, "materialize"):  # a ForestIR: take the kernel's layout
        packed = packed.materialize(
            "leaf_major" if impl in ("auto", "leaf_major") else "padded"
        )
    layout = getattr(packed, "layout", "padded")
    if layout not in ("padded", "leaf_major"):
        raise ValueError(
            f"the Pallas kernel walks (T, N) node tables, not the {layout!r} "
            "layout; ragged belongs to the table-walk C backend"
        )
    if impl == "auto":
        # the scan needs the leaf_major internal prefix and its children-
        # after-parents order (internal_counts is None when an imported
        # forest violates it); any node order gather-walks fine
        impl = ("leaf_major"
                if layout == "leaf_major"
                and getattr(packed, "internal_counts", None) is not None
                else "gather")
    if impl == "leaf_major" and layout != "leaf_major":
        from repro.ir import resolve_artifact

        packed = resolve_artifact(packed, "leaf_major")
    if isinstance(X, np.ndarray):
        X = np.asarray(X, np.float32)
    x, = _upload(X)
    with stage("launch", programs=_KEY_PROGRAMS):
        keys = float_to_key(x)
    *nodes, nint = tables or _host_tables(packed)
    *nodes, nint = _upload(*nodes, nint if impl == "leaf_major" else None)
    acc = tree_predict_integer(keys, *nodes, depth=packed.max_depth, impl=impl,
                               internal_counts=nint, **kw)
    with stage("launch", programs=1):
        return acc, jnp.argmax(acc, axis=1).astype(jnp.int32)

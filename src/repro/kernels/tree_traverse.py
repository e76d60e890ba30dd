"""Pallas TPU kernels: batched integer-only tree-ensemble traversal.

TPU adaptation of the paper's if-else trees (DESIGN.md Sec. 2): branches
become breadth-batched node-table walks.  One grid cell processes a block of
rows against a block of ``block_t`` trees; leaves self-loop.  Class scores are
uint32 fixed-point sums (paper Sec. III-A), accumulated here as int32 (the
same bits: addition is identical mod 2^32) and bitcast back by the wrapper.

Kernel-side layout (built by ``ops.py`` from the ForestIR ``(T, N)`` tables):
rows ride the 128 lanes and node tables are cut into 128-node chunks, so
every block is tiling-aligned and every lookup is a form Mosaic lowers.
    x tiles:      (B/128, F, 128)          int32 keys, row r*128+l at [r, :, l]
    node fields:  (T, 4, N)                feature, key, left, right (SMEM)
    node chunks:  (T, N/128, 4, 128)       the same fields, chunked (VMEM)
    leaf chunks:  (T, N/128, C, 128)       int32 bits of uint32 leaf values
    out tiles:    (B/128, C, 128)          int32 bits of uint32 partials
``N`` is padded to a multiple of 128 (of the node block, on the scan) with
inert self-looping nodes.

Grid: ``(B/block_b, T/block_t)`` with the tree dimension innermost, so each
output block stays resident while all tree-blocks accumulate into it.  The
linear scan adds a third, innermost axis over ``N/block_n`` node blocks, so
a tree of any size streams through SMEM and VMEM a block of nodes at a time
(:func:`_kernel_scan`); the other walks hold whole trees in a grid cell.
``ops.pick_blocks`` keeps the double-buffered blocks inside the VMEM and SMEM
budgets; the v5e compiler reports 1 MiB of SMEM.

Three walk strategies, selected statically:
  * ``impl="gather"``: per depth level, look up the current node's four
    fields for every row with lane gathers (``tpu.dynamic_gather``), one
    128-node chunk at a time; the row's feature value is a masked sum over
    the feature axis.
  * ``impl="onehot"``: per depth level, compare every row's node with every
    node index and select that node's scalar fields (SMEM) on a hit — only
    elementwise compare+select, O(block_b * N) per level.
  * ``impl="leaf_major"``: the layout-specialized linear scan over each
    tree's internal-node prefix, node block by node block (see
    :func:`_kernel_scan`).
All three finish a tree with the same chunked leaf lookup, and all are
bit-identical to ``ref.py`` (interpret-mode tests) and compile for the chip
(``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # rows per lane tile, and nodes per table chunk

IMPLS = ("gather", "onehot", "leaf_major")


@functools.cache
def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas tree kernels run compiled on 'tpu' or interpreted on "
        f"'cpu'; the default backend is {backend!r}"
    )


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> the platform decides (interpreter on CPU, compiled kernel
    on TPU, anything else is an error); an explicit bool is kept, so a CPU
    process can still lower for the chip's compiler."""
    return _default_interpret() if interpret is None else bool(interpret)


def _chunk_lookup(chunks_ref, t, node):
    """Column ``node[l]`` of tree ``t``'s chunked table, for every lane ``l``.

    ``chunks_ref``: (block_t, N/128, K, 128); ``node``: (1, 128) int32.
    Returns (K, 128).  Each chunk is one lane gather within a vreg; the
    chunk whose index matches ``node >> 7`` wins the select.
    """
    k_rows = chunks_ref.shape[2]
    hi = node >> 7
    lo = jnp.broadcast_to(node & (LANES - 1), (k_rows, LANES))

    def chunk(k, out):
        got = jnp.take_along_axis(chunks_ref[t, k], lo, axis=1)
        return jnp.where(hi == k, got, out)

    return jax.lax.fori_loop(0, chunks_ref.shape[1], chunk,
                             jnp.zeros((k_rows, LANES), jnp.int32))


def _feature_values(x, feat):
    """``x[..., feat[l], l]`` for every lane: a masked int32 sum over the
    feature axis (-2).  ``x``: (..., F, 128); ``feat``: (..., 1, 128)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 2)
    return jnp.sum(jnp.where(iota == feat, x, 0), axis=-2, keepdims=True)


def _add_leaves(leaf_ref, t, node, out_ref):
    """out tile r += tree t's leaf row at node[r], for every row tile; a
    node outside the block's chunks adds nothing."""
    for r in range(node.shape[0]):
        out_ref[r] += _chunk_lookup(leaf_ref, t, node[r])


def _init_out(out_ref, *axes):
    """Zero the resident output block on the first step of every
    accumulating grid axis (the tree axis, and the node axis if any)."""
    first = pl.program_id(1) == 0
    for axis in axes:
        first = first & (pl.program_id(axis) == 0)

    @pl.when(first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _kernel_gather(x_ref, nodes_ref, leaf_ref, out_ref, *, depth):
    _init_out(out_ref)
    block_t = nodes_ref.shape[0]

    def per_row_tile(r, carry):
        def per_tree(t, acc):
            def level(_, node):
                fields = _chunk_lookup(nodes_ref, t, node)  # (4, 128)
                xv = _feature_values(x_ref[r], fields[0:1])
                return jnp.where(xv <= fields[1:2], fields[2:3], fields[3:4])

            node = jax.lax.fori_loop(0, depth, level,
                                     jnp.zeros((1, LANES), jnp.int32))
            return acc + _chunk_lookup(leaf_ref, t, node)

        out_ref[r] += jax.lax.fori_loop(0, block_t, per_tree,
                                        jnp.zeros(out_ref.shape[1:], jnp.int32))
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], per_row_tile, 0)


def _kernel_onehot(x_ref, fields_ref, leaf_ref, out_ref, *, depth):
    _init_out(out_ref)
    block_t, _, n = fields_ref.shape
    zeros = jnp.zeros((x_ref.shape[0], 1, LANES), jnp.int32)

    def per_tree(t, carry):
        def level(_, node):
            def pick(j, fields):
                hit = node == j
                return tuple(jnp.where(hit, fields_ref[t, k, j], v)
                             for k, v in enumerate(fields))

            feat, thr, nl, nr = jax.lax.fori_loop(0, n, pick, (zeros,) * 4)
            xv = _feature_values(x_ref[...], feat)
            return jnp.where(xv <= thr, nl, nr)

        _add_leaves(leaf_ref, t, jax.lax.fori_loop(0, depth, level, zeros),
                    out_ref)
        return carry

    jax.lax.fori_loop(0, block_t, per_tree, 0)


def _kernel_scan(nint_ref, x_ref, fields_ref, leaf_ref, out_ref, *node_ref,
                 node_blocks):
    """Linear-scan walk over the leaf_major layout's internal-node prefix.

    The layout guarantees (a) tree nodes are permuted internal-first, so
    indices [0, n_internal) are exactly the split nodes and every leaf lies
    after every internal node, and (b) every child sits at a strictly larger
    index than its parent.  One forward pass over the prefix therefore
    routes every row to its leaf: when the scan reaches node j, any row
    currently parked at j steps to a child with index > j, which a later
    scan step (or the leaf lookup) picks up.  The scanned node's fields are
    SMEM scalars and its feature is one row of the x tiles, so each step is
    a broadcast compare+select over the row block.  Padding trees have
    ``n_internal == 0`` and skip the scan entirely.

    The forward pass may be cut anywhere, which is what the node axis of
    the grid does: node block ``k`` holds nodes ``[k*block_n,
    (k+1)*block_n)``.  Each row's current node, per tree of the block, lives
    in the VMEM scratch ``node_ref`` across node blocks (zeroed, the root,
    at block 0).  Block ``k`` scans the internal nodes ``[k*block_n,
    min((k+1)*block_n, n_internal))`` and then adds the leaf of every row
    whose node lies in the block.  Before block ``k`` every row's node is a
    leaf or at least ``k*block_n`` (by (b), a scan only moves rows forward,
    and block ``k-1`` moved every row parked inside it).  So after block
    ``k``'s scan a row whose node lies in the block sits on a leaf, its walk
    done: by (a) no later scan step can reach it.  Each row's leaf lies in
    exactly one block, and is added exactly once.

    With one node block (``node_blocks == 1``) the scan keeps its nodes in
    registers and the program is the whole-tree walk.
    """
    _init_out(out_ref, 2)
    block_t, _, block_n = fields_ref.shape
    t0 = pl.program_id(1) * block_t
    rows = (x_ref.shape[0], 1, LANES)
    if node_blocks == 1:
        lo = 0
    else:
        node_ref, = node_ref
        lo = pl.program_id(2) * block_n

        @pl.when(pl.program_id(2) == 0)
        def _():
            node_ref[...] = jnp.zeros_like(node_ref)

    def per_tree(t, carry):
        def scan_node(j, node):
            i = j if node_blocks == 1 else j - lo  # index within the block
            feat = jnp.maximum(fields_ref[t, 0, i], 0)
            xv = x_ref[:, pl.ds(feat, 1), :]  # (R, 1, 128)
            nxt = jnp.where(xv <= fields_ref[t, 1, i],
                            fields_ref[t, 2, i], fields_ref[t, 3, i])
            return jnp.where(node == j, nxt, node)

        if node_blocks == 1:
            node = jax.lax.fori_loop(0, nint_ref[t0 + t], scan_node,
                                     jnp.zeros(rows, jnp.int32))
            _add_leaves(leaf_ref, t, node, out_ref)
        else:
            end = jnp.minimum(nint_ref[t0 + t], lo + block_n)
            node = jax.lax.fori_loop(lo, end, scan_node, node_ref[t])
            node_ref[t] = node
            _add_leaves(leaf_ref, t, node - lo, out_ref)
        return carry

    jax.lax.fori_loop(0, block_t, per_tree, 0)


def tree_traverse(x_tiles, fields, leaf_chunks, internal_counts=None, *,
                  depth: int, block_b: int, block_t: int, impl: str,
                  block_n: int | None = None, interpret: bool | None = None):
    """Raw pallas_call over the kernel-side layout (module docstring).

    Shapes must already divide evenly: ``block_b`` is a multiple of 128
    dividing ``B``, and ``block_t`` divides ``T`` and is a multiple of 8 or
    ``T`` itself (``ops.tree_predict_integer`` pads and aligns).  ``impl=
    "leaf_major"`` needs ``internal_counts`` (T,) and takes ``block_n``, a
    multiple of 128 dividing ``N`` (default: ``N``, one node block); the
    other walks hold whole trees.  Returns the (B/128, C, 128) int32
    partial tiles.
    """
    r_tot, f, _ = x_tiles.shape
    t, _, n = fields.shape
    c = leaf_chunks.shape[2]
    tiles = block_b // LANES
    assert block_b % LANES == 0 and r_tot % tiles == 0 and t % block_t == 0
    grid = (r_tot // tiles, t // block_t)
    interpret = resolve_interpret(interpret)

    def spec(shape, memory_space=None):
        lead = len(shape) - 1
        return pl.BlockSpec(
            shape, lambda i, j, *_: (j,) + (0,) * lead,
            **({} if memory_space is None else {"memory_space": memory_space}))

    x_spec = pl.BlockSpec((tiles, f, LANES), lambda i, j, *_: (i, 0, 0))
    out_spec = pl.BlockSpec((tiles, c, LANES), lambda i, j, *_: (i, 0, 0))
    leaf_spec = spec((block_t,) + leaf_chunks.shape[1:])
    smem_fields = spec((block_t, 4, n), pltpu.SMEM)
    semantics = ("parallel", "arbitrary")
    if impl == "leaf_major":
        if internal_counts is None:
            raise ValueError("impl='leaf_major' needs internal_counts")
        block_n = n if block_n is None else block_n
        assert block_n % LANES == 0 and n % block_n == 0
        node_blocks = n // block_n
        grid += (node_blocks,)
        semantics += ("arbitrary",)
        kernel = functools.partial(_kernel_scan, node_blocks=node_blocks)
        # the row's current node per tree of the block, kept across the
        # node blocks; one node block keeps it in registers instead
        scratch = ([] if node_blocks == 1 else
                   [pltpu.VMEM((block_t, tiles, 1, LANES), jnp.int32)])
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[
                x_spec,
                pl.BlockSpec((block_t, 4, block_n),
                             lambda i, j, k, *_: (j, 0, k),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((block_t, block_n // LANES, c, LANES),
                             lambda i, j, k, *_: (j, k, 0, 0)),
            ],
            out_specs=out_spec, scratch_shapes=scratch)
        args = (internal_counts, x_tiles, fields, leaf_chunks)
    elif impl == "gather":
        kernel = functools.partial(_kernel_gather, depth=depth)
        nodes = fields.reshape(t, 4, n // LANES, LANES).transpose(0, 2, 1, 3)
        grid_spec = pl.GridSpec(
            grid=grid, in_specs=[x_spec, spec((block_t,) + nodes.shape[1:]),
                                 leaf_spec], out_specs=out_spec)
        args = (x_tiles, nodes, leaf_chunks)
    elif impl == "onehot":
        kernel = functools.partial(_kernel_onehot, depth=depth)
        grid_spec = pl.GridSpec(grid=grid,
                                in_specs=[x_spec, smem_fields, leaf_spec],
                                out_specs=out_spec)
        args = (x_tiles, fields, leaf_chunks)
    else:
        raise ValueError(f"unknown impl {impl!r}; have {IMPLS}")
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r_tot, c, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name=f"tree_traverse_{impl}",
    )(*args)

"""PallasBackend: the VMEM-tiled TPU kernel behind the TreeBackend protocol.

Wraps ``repro.kernels.ops.packed_partials`` and is the one place that
decides how a batch runs on the chip: which walk (:meth:`PallasBackend.walk`)
and the tiling caps fed to the kernel (``block_b``, ``block_t``; the kernel
wrapper aligns them to the budgets via ``pick_blocks``), plus the
``preferred_block_rows`` hint that makes the serving layer pad batches to
shapes aligned with the kernel's ``block_b`` tiling.  ``interpret=None``
(the default) compiles the kernel on TPU and interprets it on CPU; a kernel
that fails to compile raises, with no fallback.

A batch is one device program: the rows are uploaded as float32 and the
kernel's jitted call keys them (FlInt), pads them and runs the kernel; the
partials come back and nothing else is launched (the ``launch`` stage's
``programs`` reads 1).

The node tables are device-resident: the first ``predict_partials`` places
them on the default device (``kernels.ops.place_tables``, the ``place``
stage) and every later batch walks that copy, uploading only its rows.
The copy lives as long as the backend, i.e. its ``ModelVersion``'s
engine, so a hot-swapped version places its own.

Layout-specialized: the backend prefers the ``leaf_major`` layout, where the
linear-scan kernel (``impl="leaf_major"``) walks each tree's internal-node
prefix front-to-back with compare+select steps — no per-depth node-table
gathers.  ``impl="auto"`` (the default) scans a scannable ``leaf_major``
layout and runs the per-level ``gather`` walk on ``padded`` tables or where
the node order cannot be scanned; small batches take the gather walk where
whole trees fit (:meth:`PallasBackend.walk`).  Only the scan cuts the node
axis, so it alone serves trees too large for one grid cell (unpruned
forests); a gather or onehot walk that cannot hold a whole tree is refused
at construction.

The kernel implements exactly the paper's integer accumulation (int32 FlInt
compares, uint32 fixed-point adds) — which, since the partials/finalize
split, is the *whole* deterministic-mode story: the kernel produces the
uint32 partial accumulators and the shared finalize turns them into scores.
``flint`` therefore rides the same kernel (its finalize is one reciprocal
multiply), so ``modes == ("flint", "integer")``.  uint32 addition is
associative mod 2^32, which is why the tiled accumulation is bit-identical
to the reference walk no matter how the grid is carved — and why a
tree-parallel plan can merge per-shard kernel partials bit-exactly.
"""
from __future__ import annotations

import threading
from typing import Optional

import numpy as np

from repro.backends.base import BackendCapabilities, TreeBackend, register_backend
from repro.core.packing import PackedEnsemble
from repro.obs import stage

_DEFAULT_BLOCK_B = 256  # the kernel wrapper's row-tile default

# when impl="auto" resolves to the linear scan, batches below this row count
# run the gather walk instead: the scan's per-cell prefix pass costs the same
# for 2 rows as for 256.  A host-clock cut from a CPU snapshot, not yet
# measured on the chip.  Both impls produce identical uint32 partials, so the
# switch is invisible to conformance.  Only forests whose whole trees fit a
# grid cell take it: the gather walk cannot cut the node axis as the scan
# does.
_SMALL_BATCH_GATHER_ROWS = 64


@register_backend
class PallasBackend(TreeBackend):
    name = "pallas"
    capabilities = BackendCapabilities(
        modes=("flint", "integer"),
        deterministic_modes=("flint", "integer"),
        preferred_block_rows=_DEFAULT_BLOCK_B,
        compiles_per_shape=True,
        # the kernel consumes dense (T, N) VMEM-resident tables, so both
        # node-table orderings are walkable; leaf_major is preferred because
        # the linear-scan impl replaces depth-many gathers with one forward
        # pass over the internal-node prefix
        supported_layouts=("leaf_major", "padded"),
        preferred_layout="leaf_major",
    )

    def __init__(self, packed: PackedEnsemble, mode: str = "integer", *,
                 block_b: int = _DEFAULT_BLOCK_B, block_t: Optional[int] = None,
                 impl: str = "auto", interpret: Optional[bool] = None):
        from repro.kernels.ops import holds_whole_trees

        super().__init__(packed, mode)
        t, n = self.packed.feature.shape
        f, c = self.packed.n_features, self.packed.leaf_fixed.shape[-1]
        self._shape = (t, n, f, c)
        self._asked = impl
        # the leaf_major internal prefix AND its children-after-parents order
        # (internal_counts is None when an imported forest violates it)
        self._scannable = getattr(packed, "internal_counts", None) is not None
        # whole trees in one grid cell at the tiling that would run: the
        # pinned block_t, or the smallest aligned one
        self._whole_trees = holds_whole_trees(t, n, f, c, block_t)
        self.impl = self.walk()  # raises for a walk these tables refuse
        self._tiling = dict(block_b=block_b, block_t=block_t,
                            interpret=interpret)
        self._tables = None  # the device-resident node tables, once placed
        self._place_lock = threading.Lock()  # shard threads share a backend

    def walk(self, rows: Optional[int] = None) -> str:
        """The walk a batch of ``rows`` rows takes (``rows=None``: any batch;
        this is ``self.impl``).

        ``impl="auto"`` scans a scannable ``leaf_major`` layout and gathers
        on ``padded`` tables or where the node order cannot be scanned;
        below ``_SMALL_BATCH_GATHER_ROWS`` rows it gathers instead of
        scanning where whole trees fit.  A pinned walk runs every batch.
        Refused with ``ValueError``: the scan of tables that cannot be
        scanned, and a whole-tree walk (gather, onehot) of trees that do not
        fit a grid cell.
        """
        scan = self.layout == "leaf_major" and self._scannable
        impl = self._asked
        if impl == "auto":
            small = rows is not None and rows < _SMALL_BATCH_GATHER_ROWS
            impl = ("leaf_major" if scan and not (small and self._whole_trees)
                    else "gather")
        if impl == "leaf_major":
            if not scan:
                raise ValueError(
                    "impl='leaf_major' scans the leaf_major internal-node "
                    f"prefix; this backend was materialized on the "
                    f"{self.layout!r} layout"
                    + ("" if self._scannable
                       else " without a scannable node order"))
        elif not self._whole_trees:
            t, n, f, c = self._shape
            raise ValueError(
                f"impl={impl!r} holds whole trees in a grid cell, and {t} "
                f"trees of {n} nodes ({f} features, {c} classes) do not fit "
                "one; the leaf_major scan (impl='leaf_major', or 'auto' on "
                "the leaf_major layout) cuts the node axis into blocks")
        return impl

    def _resident_tables(self):
        """The node tables on the device, placed by the first call."""
        if self._tables is None:
            from repro.kernels.ops import place_tables

            with self._place_lock:
                if self._tables is None:
                    self._tables = place_tables(self.packed)
        return self._tables

    def predict_partials(self, X):
        from repro.kernels.ops import packed_partials

        acc = packed_partials(self.packed, X, impl=self.walk(len(X)),
                              tables=self._resident_tables(), **self._tiling)
        with stage("fetch"):  # the wait on the device and the copy back
            return np.asarray(acc)

"""PallasBackend: the VMEM-tiled TPU kernel behind the TreeBackend protocol.

Wraps ``repro.kernels.ops.packed_partials`` and owns the blocking
decisions: the row/tree block sizes fed to the kernel (budgeted and
tiling-aligned via ``pick_blocks``) and the ``preferred_block_rows`` hint
that makes the serving layer pad batches to shapes aligned with the
kernel's ``block_b`` tiling.  ``interpret=None`` (the default) compiles the
kernel on TPU and interprets it on CPU; a kernel that fails to compile
raises, with no fallback.

A batch is one device program: the rows are uploaded as float32 and the
kernel's jitted call keys them (FlInt), pads them and runs the kernel; the
partials come back and nothing else is launched (the ``launch`` stage's
``programs`` reads 1).

The node tables are device-resident: the first ``predict_partials`` places
them on the default device (``kernels.ops.place_tables``, the ``place``
stage) and every later batch walks that copy, uploading only its rows.
The copy lives as long as the backend, i.e. its ``ModelVersion``'s
engine, so a hot-swapped version places its own.  The kernel wrappers'
host-array entry points still upload the tables with every call.

Layout-specialized: the backend prefers the ``leaf_major`` layout, where the
linear-scan kernel (``impl="leaf_major"``) walks each tree's internal-node
prefix front-to-back with compare+select steps — no per-depth node-table
gathers.  ``impl="auto"`` (the default) resolves per layout: linear scan on
``leaf_major`` tables, the per-level ``gather`` walk on ``padded`` ones —
i.e. pinning ``layout="padded"`` falls back to padded+gather untouched.
Only the scan cuts the node axis, so it alone serves trees too large for
one grid cell (unpruned forests); a gather or onehot walk that cannot hold
a whole tree is refused at construction.

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

# when impl="auto" resolved to the linear scan, batches below this row count
# run the gather walk instead: the scan's per-cell prefix pass costs the same
# for 2 rows as for 256, so at tiny batches the cheaper per-call gather wins
# (measured on the BENCH_7 b32 pathology).  Both impls produce identical
# uint32 partials, so the switch is invisible to conformance.  Only forests
# whose whole trees fit a grid cell take it: the gather walk cannot cut the
# node axis as the scan does.
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
        scannable = getattr(packed, "internal_counts", None) is not None
        was_auto = impl == "auto"
        if impl == "auto":
            # the linear scan needs the layout's internal prefix AND its
            # children-after-parents ordering (internal_counts is None when
            # an imported forest violates it) — otherwise gather-walk the
            # tables, which any node order satisfies
            impl = "leaf_major" if self.layout == "leaf_major" and scannable \
                else "gather"
        if impl == "leaf_major" and not (self.layout == "leaf_major" and scannable):
            raise ValueError(
                "impl='leaf_major' scans the leaf_major internal-node prefix; "
                f"this backend was materialized on the {self.layout!r} layout"
                + ("" if scannable else " without a scannable node order")
            )
        t, n = self.packed.feature.shape
        f, c = self.packed.n_features, self.packed.leaf_fixed.shape[-1]
        if impl in ("gather", "onehot") and not holds_whole_trees(t, n, f, c):
            raise ValueError(
                f"impl={impl!r} holds whole trees in a grid cell, and {t} "
                f"trees of {n} nodes ({f} features, {c} classes) do not fit "
                "one; the leaf_major scan (impl='leaf_major', or 'auto' on "
                "the leaf_major layout) cuts the node axis into blocks")
        self.impl = impl
        # only an *auto* resolution may fall back per batch — an explicitly
        # pinned impl is a routing decision the caller owns — and only where
        # gather holds whole trees at the tiling it would run: the pinned
        # block_t, or its own floor
        self._auto_small_batch = (impl == "leaf_major" and was_auto
                                  and holds_whole_trees(t, n, f, c, block_t))
        self._kernel_kwargs = dict(
            block_b=block_b, block_t=block_t, impl=impl, interpret=interpret
        )
        self._tables = None  # the device-resident node tables, once placed
        self._place_lock = threading.Lock()  # shard threads share a backend

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

        kw = self._kernel_kwargs
        if self._auto_small_batch and len(X) < _SMALL_BATCH_GATHER_ROWS:
            kw = dict(kw, impl="gather")
        acc = packed_partials(self.packed, X, tables=self._resident_tables(),
                              **kw)
        with stage("fetch"):  # the wait on the device and the copy back
            return np.asarray(acc)

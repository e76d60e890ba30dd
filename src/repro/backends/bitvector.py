"""BitvectorBackend: QuickScorer-style traversal-free scoring, pure jnp.

The fifth backend, and the first consumer of the ``bitvector`` ForestIR
layout (``repro.ir.bitvector``): no per-row node walk at all — every
internal-node test in the forest is evaluated as one data-parallel compare
grid, false-node masks are OR/AND-folded into per-tree live-leaf bitvectors,
and each tree's exit leaf is its lowest surviving bit (see the kernel
docstring for the uint32-word mechanics under JAX's x64-disabled config).

Deterministic modes only: the QuickScorer tables hold FlInt int32 keys and
uint32 fixed-point leaves, so partials are the exact associative accumulators
every other backend produces — bit-identical to ``reference`` by the
conformance suite, shardable by every execution plan, and finalized by the
one shared numpy step.  The emitted-C sibling (``native_c_bitvector``)
streams the same tables sequentially with the sorted-list early exit.
"""
from __future__ import annotations

import numpy as np

from repro.backends.base import (BackendCapabilities, TreeBackend, device_call,
                                 register_backend)
from repro.kernels.bitvector import make_bitvector_partials_fn


@register_backend
class BitvectorBackend(TreeBackend):
    name = "bitvector"
    capabilities = BackendCapabilities(
        modes=("flint", "integer"),
        deterministic_modes=("flint", "integer"),
        preferred_block_rows=None,
        compiles_per_shape=True,
        supported_layouts=("bitvector",),
        preferred_layout="bitvector",
    )

    def __init__(self, packed, mode: str = "integer"):
        super().__init__(packed, mode)
        # flint and integer share the one integer accumulation; the modes
        # differ only in the shared finalize step
        self._partials_fn = make_bitvector_partials_fn(packed)

    def predict_partials(self, X):
        return device_call(self._partials_fn, np.asarray(X, np.float32))

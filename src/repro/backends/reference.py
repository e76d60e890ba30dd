"""ReferenceBackend: the pure-jnp breadth-batched node-table walk.

This is the semantic oracle: one jitted accumulate per (model, mode), built
from the shared mode spec in ``repro.core.ensemble``.  Every other backend's
flint/integer output is defined as "bit-identical to this".  Deterministic
modes run through the partials/finalize split (jitted uint32 accumulation,
shared numpy finalize); the float mode keeps its fused jitted predict.
"""
from __future__ import annotations

import numpy as np

from repro.backends.base import (BackendCapabilities, TreeBackend, device_call,
                                 register_backend)
from repro.core.ensemble import MODES, make_partials_fn, make_predict_fn
from repro.core.packing import PackedEnsemble


@register_backend
class ReferenceBackend(TreeBackend):
    name = "reference"
    capabilities = BackendCapabilities(
        modes=MODES,
        deterministic_modes=("flint", "integer"),
        preferred_block_rows=None,  # any padded shape is fine
        compiles_per_shape=True,
        # the jnp walk gathers by node index over (T, N) tables, so any
        # node-table layout works; node order cannot perturb scores.
        # packed_leaf is served by decoding its exact group-quantized leaf
        # payload into dense tables at construction (deterministic modes
        # only — the packed payload is fixed-point)
        supported_layouts=("padded", "leaf_major", "packed_leaf"),
        preferred_layout="padded",
    )

    def __init__(self, packed: PackedEnsemble, mode: str = "integer"):
        super().__init__(packed, mode)
        walk = packed
        if getattr(packed, "layout", "padded") == "packed_leaf":
            if not self.deterministic:
                raise ValueError(
                    "layout 'packed_leaf' stores fixed-point leaves only; "
                    "serve it in a deterministic mode (flint/integer)"
                )
            walk = packed.decoded_tables()
        if self.deterministic:
            self._partials_fn = make_partials_fn(walk, mode)
        else:
            self._fn = make_predict_fn(walk, mode)

    def predict_partials(self, X):
        if not self.deterministic:
            return super().predict_partials(X)  # raises with the shared message
        return device_call(self._partials_fn, np.asarray(X, np.float32))

    def predict_scores(self, X):
        if self.deterministic:
            return super().predict_scores(X)  # finalize(partials)
        return device_call(self._fn, np.asarray(X, np.float32))

"""The TreeBackend protocol and the name-keyed backend registry.

InTreeger's central claim is that one trained ensemble yields bit-identical
integer-only inference on any hardware.  This module makes that claim an
*interface*: every execution strategy for a :class:`~repro.core.packing.
PackedEnsemble` — the jnp reference walk, the Pallas VMEM-tiled kernel, the
paper's literal emitted C — implements the same surface

    predict_partials(X) -> (B, C) uint32 partial accumulators
    predict_scores(X)   -> (scores, preds)

and declares what it can do via :class:`BackendCapabilities`.  The serving
stack (``repro.serve``) routes per-(model, mode, backend) purely through this
layer — through an execution plan (``repro.plan``) that may carve the forest
into tree shards, call ``predict_partials`` on each, merge the exact integer
partial sums, and run the standalone finalize step once; nothing above a
backend may special-case how inference runs.

``predict_partials`` is the shardable half of inference: for the
deterministic modes (flint/integer) it returns the raw uint32 fixed-point
accumulator, which is associative, so partials of a sub-forest artifact
(``ForestIR.subset``) merge into the full forest's accumulator bit-exactly.
``predict_scores`` is kept as the compatibility wrapper — for deterministic
modes the base class implements it as ``finalize(predict_partials(X))`` with
the one shared :func:`repro.core.ensemble.finalize_partials`, so every
backend's scores are the same function of the same exact integers.

Scores are mode-typed exactly as in ``repro.core.ensemble``: float32 average
probabilities for ``float``/``flint``, uint32 fixed-point class sums for
``integer``.  For the deterministic modes (flint/integer) every backend must
be bit-identical to :class:`~repro.backends.reference.ReferenceBackend` —
the cross-backend conformance suite (``tests/test_backends.py``, ``make
conformance``) enforces this on randomized forests.
"""
from __future__ import annotations

import abc
from dataclasses import dataclass
from typing import ClassVar, Optional

import jax.numpy as jnp
import numpy as np

from repro.obs import stage


def device_call(fn, X):
    """``fn`` (one jitted program) on the device copy of the host array
    ``X``, fetched back to numpy (a tuple result element-wise): the shard
    call's ``upload``, ``launch`` and ``fetch`` stages (``repro.obs.
    stages``), for a backend whose whole device path is that one call."""
    with stage("upload", bytes=X.nbytes if isinstance(X, np.ndarray) else 0):
        x = jnp.asarray(X)
    with stage("launch", programs=1):
        out = fn(x)
    with stage("fetch"):
        if isinstance(out, tuple):
            return tuple(np.asarray(a) for a in out)
        return np.asarray(out)


class BackendUnavailable(RuntimeError):
    """The backend cannot run on this host (e.g. no C toolchain)."""


@dataclass(frozen=True)
class BackendCapabilities:
    """What a backend supports and how the serving layer should drive it.

    modes:               inference modes the backend implements
                         (subset of ``repro.core.ensemble.MODES``).
    deterministic_modes: modes whose scores are bit-exact integers —
                         cacheable by the gateway's QuantizedKeyCache and
                         required to match the reference backend bit-for-bit.
    preferred_block_rows: row-blocking hint.  When set, ``TreeEngine`` uses
                         it as the default ``max_bucket`` so padded batch
                         shapes line up with the backend's internal tiling.
    compiles_per_shape:  True when each padded row bucket costs one compile
                         (jitted backends).  False for shape-oblivious
                         backends (native C), where the engine skips
                         bucket padding entirely.
    supported_layouts:   ForestIR layouts this backend can walk (see
                         ``repro.ir.layouts``).  The node-table backends take
                         ``padded``/``leaf_major`` (same (T, N) surface); the
                         table-walk C backend takes ``ragged``.
    preferred_layout:    the layout the serving layer materializes when the
                         caller does not pin one.  Deterministic-mode scores
                         are bit-identical across layouts, so this is purely
                         a performance/footprint choice.
    """

    modes: tuple
    deterministic_modes: tuple
    preferred_block_rows: Optional[int] = None
    compiles_per_shape: bool = True
    supported_layouts: tuple = ("padded",)
    preferred_layout: str = "padded"

    def require_layout(self, layout: str, backend_name: str) -> None:
        """Fail fast when ``layout`` is not walkable — the ONE validation
        every routing layer (backend ctor, engine, gateway) calls."""
        if layout not in self.supported_layouts:
            raise ValueError(
                f"backend {backend_name!r} cannot walk layout {layout!r}; "
                f"supported layouts: {self.supported_layouts}"
            )


class TreeBackend(abc.ABC):
    """One execution strategy for a materialized forest, fixed to one mode.

    ``packed`` is the layout artifact the backend walks — a
    :class:`~repro.core.packing.PackedEnsemble` for the node-table layouts, a
    :class:`~repro.ir.layouts.RaggedEnsemble` for ``ragged``.  The attribute
    keeps its historical name; every artifact exposes the same metadata
    surface (``n_trees``/``n_classes``/``n_features``/``max_depth``/
    ``scale``/``layout``/``nbytes_*``).
    """

    name: ClassVar[str]
    capabilities: ClassVar[BackendCapabilities]

    def __init__(self, packed, mode: str = "integer"):
        if mode not in self.capabilities.modes:
            raise ValueError(
                f"backend {self.name!r} does not implement mode {mode!r}; "
                f"supported modes: {self.capabilities.modes}"
            )
        self.capabilities.require_layout(getattr(packed, "layout", "padded"),
                                         self.name)
        self.packed = packed
        self.mode = mode

    @property
    def layout(self) -> str:
        """The layout of the artifact this backend was built on."""
        return getattr(self.packed, "layout", "padded")

    @property
    def deterministic(self) -> bool:
        """True when outputs are bit-exact integer scores (cacheable)."""
        return self.mode in self.capabilities.deterministic_modes

    def predict_partials(self, X):
        """Float features (B, F) in -> (B, C) uint32 partial accumulators.

        The shardable half of inference: the raw fixed-point sums *before*
        the finalize step, exact and associative, so a plan can merge them
        across tree shards bit-losslessly.  Defined for the deterministic
        modes; backends serving only non-deterministic modes (float) leave
        this unimplemented.  ``X`` is always in the *float* domain; the
        backend owns its own domain transform (FlInt keying).
        """
        raise NotImplementedError(
            f"backend {self.name!r} does not expose integer partials for "
            f"mode {self.mode!r}"
        )

    def predict_scores(self, X):
        """Float features (B, F) in -> (scores (B, C), preds (B,) int32).

        Compatibility wrapper over the partials/finalize split: for the
        deterministic modes this is ``finalize_partials(predict_partials(X))``
        — one shared numpy finalize, so scores cannot diverge across
        backends.  Backends with non-deterministic modes (float) override.
        """
        from repro.core.ensemble import finalize_partials

        if not self.deterministic:
            raise NotImplementedError(
                f"backend {self.name!r} must override predict_scores for "
                f"the non-deterministic mode {self.mode!r}"
            )
        acc = self.predict_partials(X)
        return finalize_partials(self.mode, acc, self.packed.n_trees,
                                 self.packed.scale)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<{type(self).__name__} mode={self.mode!r}>"


# ---------------------------------------------------------------------------
# name-keyed registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register_backend(cls):
    """Class decorator: make ``cls`` constructible via :func:`create_backend`."""
    if not (isinstance(cls, type) and issubclass(cls, TreeBackend)):
        raise TypeError(f"register_backend expects a TreeBackend subclass, got {cls!r}")
    _REGISTRY[cls.name] = cls
    return cls


def available_backends() -> list:
    return sorted(_REGISTRY)


def backend_class(name: str):
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None


def create_backend(name: str, packed, *, mode: str = "integer",
                   **kwargs) -> TreeBackend:
    """Instantiate a registered backend by name for one (model, mode).

    ``packed`` must already be materialized in a layout the backend supports
    (see :func:`repro.ir.resolve_artifact`; ``TreeEngine`` does this
    resolution for the serving stack).
    """
    return backend_class(name)(packed, mode, **kwargs)

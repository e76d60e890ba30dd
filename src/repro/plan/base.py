"""The ExecutionPlan protocol and the name-keyed plan registry.

A plan sits between the serving engine and the backend layer and decides how
one logical forest is *carved* across executors:

    engine -> ExecutionPlan -> backend.predict_partials -> merge -> finalize

The paper's integer-only accumulation is what makes this split sound: the
deterministic modes (flint/integer) accumulate exact uint32 fixed-point
partials, and uint32 addition is associative, so a forest can be cut into
tree-contiguous sub-forests (``ForestIR.subset``), each shard's partials
computed on a different jax device or a different backend entirely, and the
merged sum is *bit-identical* to the single-shard walk.  Finalize
(reciprocal-multiply averaging + argmax, ``repro.core.ensemble.
finalize_partials``) runs exactly once, on the merged accumulator.

Four registered plans:
  * ``single``        — today's path: one backend, the whole forest.
  * ``tree_parallel`` — shard trees across jax devices (``shard_map`` over a
                        stacked sub-forest table) or across per-shard
                        backends, possibly heterogeneous; integer merge.
  * ``row_parallel``  — shard the batch; rows are independent, so this is
                        bit-exact for *every* mode, float included.
  * ``remote_tree_parallel`` — tree shards on worker *processes* (loopback
                        or other hosts) over the wire protocol in
                        ``repro.serve.wire``; uint32 partials merge at the
                        gateway, stragglers/deaths re-dispatch.

*Adding a plan*: subclass :class:`ExecutionPlan`, set ``name``, implement
``predict_partials`` (and ``predict_scores`` if the plan serves
non-deterministic modes), decorate with ``@register_plan``; the serving stack
picks it up by name (``TreeEngine(spec="integer:reference+myplan:4")``,
``Gateway(registry, spec)``, ``--gw-spec``).  Plans that can only serve
exact-integer partial modes set ``deterministic_only = True`` so the
gateway rejects the route up front.  Plans that own executors beyond the
calling thread — thread pools, worker processes, sockets — override
``close()`` (drain in-flight work, then release); one-time setup cost
(connect/handshake) goes in the dict ``drain_setup_timings()`` returns
(e.g. ``{"remote": ms}``), which the engine folds into its compile/warm
ledger.  Remote plans additionally need a worker-side contract: ship the
model + shard table in one handshake so *any* worker can serve *any*
shard, which is what makes re-dispatching a dead worker's shard trivial
(see ``repro.plan.remote``).
"""
from __future__ import annotations

import abc
import threading
import time
from typing import ClassVar, Optional

import numpy as np

from repro.core.ensemble import finalize_partials, mode_spec
from repro.obs import stages


def build_backend(backend, model, mode: str, layout: Optional[str],
                  backend_kwargs: Optional[dict]):
    """Resolve one shard's backend: a registered name (materialize the wanted
    ForestIR layout, then construct) or an already-built instance (then the
    artifact/mode are taken from it; a conflicting layout pin fails loudly).

    This is THE one place plan code turns (model, backend spec) into an
    executor — the logic the pre-plan ``TreeEngine`` constructor owned.
    """
    from repro.backends import backend_class, create_backend
    from repro.ir import resolve_artifact

    if isinstance(backend, str):
        caps = backend_class(backend).capabilities
        wanted = layout or caps.preferred_layout
        caps.require_layout(wanted, backend)
        return create_backend(
            backend, resolve_artifact(model, wanted), mode=mode,
            **(backend_kwargs or {})
        )
    if layout is not None and getattr(backend, "layout", "padded") != layout:
        raise ValueError(
            f"layout {layout!r} conflicts with the constructed "
            f"backend's artifact (layout {backend.layout!r}); "
            "materialize the backend on the wanted layout instead"
        )
    return backend


def as_ir(model):
    """The canonical ForestIR behind ``model`` (IR or any layout artifact)."""
    from repro.ir import ForestIR

    if isinstance(model, ForestIR):
        return model
    ir = getattr(model, "ir", None)
    if ir is not None:
        return ir
    if hasattr(model, "to_ir"):
        return model.to_ir()
    raise ValueError(
        f"cannot shard a {type(model).__name__!r} artifact: no ForestIR "
        "back-reference to carve sub-forests from"
    )


class ExecutionPlan(abc.ABC):
    """How one logical forest is executed: shards, merge, finalize.

    Subclasses own their backends; the engine above sees the same surface a
    bare backend exposes (``predict_partials``/``predict_scores`` plus the
    capability aggregates the bucketing layer consults), so a plan composes
    with shape bucketing, the gateway, and the registry unchanged.
    """

    name: ClassVar[str]
    #: True for plans that only serve exact-integer partial modes (the
    #: gateway validates the route against this before building engines)
    deterministic_only: ClassVar[bool] = False

    def __init__(self, model, *, mode: str = "integer"):
        self.mode = mode
        self._spec = mode_spec(mode)
        # the FULL ensemble's finalize constants — a sub-forest's partials
        # must be averaged at the whole forest's (n_trees, scale)
        self._n_trees = getattr(model, "n_trees", None)
        self._scale = getattr(model, "scale", None)
        self._timings: dict = {}
        self._stages: dict = {}
        self._timings_lock = threading.Lock()
        # observability attach: the tracer is plan-wide, the active parent
        # span is per-*thread* (set by the dispatching thread — the gateway's
        # batch executor — and handed to shard pool threads explicitly at
        # submit time, so concurrent dispatches never cross-parent spans)
        self._tracer = None
        self._trace_tls = threading.local()
        # compiles inside a shard call become its ``compile`` stage
        stages.watch_compiles()

    # ------------------------------------------------------------ execution
    @abc.abstractmethod
    def predict_partials(self, X):
        """Float features (B, F) -> merged (B, C) uint32 partials."""

    def predict_scores(self, X):
        """(scores, preds) via the standalone finalize over merged partials."""
        if not self.deterministic:
            raise NotImplementedError(
                f"plan {self.name!r} must override predict_scores for the "
                f"non-deterministic mode {self.mode!r}"
            )
        acc = self.predict_partials(X)
        t0 = time.perf_counter_ns()
        out = finalize_partials(self.mode, acc, self._n_trees, self._scale)
        t1 = time.perf_counter_ns()
        self._record_stage("finalize", (t1 - t0) / 1e9)
        self._span("finalize", t0, t1, self.trace_parent)
        return out

    # ------------------------------------------------------- shard metadata
    @property
    @abc.abstractmethod
    def backends(self) -> tuple:
        """The shard backends (may be empty for fused device execution)."""

    @property
    @abc.abstractmethod
    def packed(self):
        """A metadata-bearing artifact for the full forest (n_features etc)."""

    @property
    def n_shards(self) -> int:
        return max(len(self.backends), 1)

    @property
    def deterministic(self) -> bool:
        return self._spec.deterministic

    @property
    def compiles_per_shape(self) -> bool:
        return any(b.capabilities.compiles_per_shape for b in self.backends)

    @property
    def preferred_block_rows(self) -> Optional[int]:
        hints = [b.capabilities.preferred_block_rows for b in self.backends]
        hints = [h for h in hints if h]
        return max(hints) if hints else None

    @property
    def layout(self) -> str:
        layouts = []
        for b in self.backends:
            if b.layout not in layouts:
                layouts.append(b.layout)
        return "+".join(layouts) if layouts else "padded"

    @property
    def backend_name(self) -> str:
        names = []
        for b in self.backends:
            if b.name not in names:
                names.append(b.name)
        return "+".join(names) if names else self.name

    def describe(self) -> dict:
        return {
            "plan": self.name,
            "mode": self.mode,
            "shards": self.n_shards,
            "backends": [b.name for b in self.backends],
            "layout": self.layout,
        }

    # ------------------------------------------------- timing + trace spans
    def attach_tracer(self, tracer) -> None:
        """Attach a :class:`repro.obs.Tracer` (plan-wide; idempotent)."""
        self._tracer = tracer

    @property
    def trace_parent(self):
        """The span that parents this *thread's* execution spans (set by the
        dispatching thread via the setter; ``None`` when untraced)."""
        return getattr(self._trace_tls, "parent", None)

    @trace_parent.setter
    def trace_parent(self, span) -> None:
        self._trace_tls.parent = span

    def _span(self, name: str, t0_ns: int, t1_ns: int, parent, **attrs) -> None:
        """Commit one completed span under ``parent`` (no-op when untraced —
        the one branch the disabled path pays)."""
        if parent and self._tracer is not None:
            self._tracer.record(name, t0_ns, t1_ns, parent=parent, **attrs)

    def _record(self, label: str, seconds: float) -> None:
        with self._timings_lock:
            ms, calls = self._timings.get(label, (0.0, 0))
            self._timings[label] = (ms + seconds * 1e3, calls + 1)

    def _record_stage(self, stage: str, seconds: float) -> None:
        """Accumulate one pipeline-stage sample (pad/merge/finalize — the
        engine adds pad — and the shard call's upload/launch/fetch/compile);
        drained separately from shard labels."""
        with self._timings_lock:
            ms, calls = self._stages.get(stage, (0.0, 0))
            self._stages[stage] = (ms + seconds * 1e3, calls + 1)

    def _timed(self, label: str, fn, *args, span_parent=None):
        """Run ``fn`` timing it into the shard ledger, with a stage sink
        (:mod:`repro.obs.stages`) held for its length: each stage the code
        below marks (upload / launch / fetch / compile) lands in the stage
        ledger as one sample per call, the sum of its intervals.  When
        ``span_parent`` is a live span, a ``shard:<label>`` span is opened
        before the call and each run of one stage becomes a span under it.
        Shard pool threads receive the parent explicitly (captured by the
        dispatching thread), never via the thread-local."""
        span = None
        if span_parent and self._tracer is not None:
            span = self._tracer.child(span_parent, f"shard:{label}", label=label)
        sink = stages.Sink(traced=bool(span))
        prev = stages.install(sink)
        try:
            t0 = time.perf_counter_ns()
            out = fn(*args)
            t1 = time.perf_counter_ns()
        finally:
            stages.install(prev)
        self._record(label, (t1 - t0) / 1e9)
        for name, ns in sink.totals.items():
            self._record_stage(name, ns / 1e9)
        if span:
            for name, s0, s1, attrs in sink.runs:
                self._tracer.record(name, s0, s1, parent=span, **attrs)
            span.end()
        return out

    def drain_timings(self) -> dict:
        """Per-shard wall time accumulated since the last drain:
        ``{label: (ms_total, calls)}``.  The gateway feeds this into
        ``serve.metrics`` after each batch execute."""
        with self._timings_lock:
            out, self._timings = self._timings, {}
        return out

    def drain_stage_timings(self) -> dict:
        """Pipeline-stage wall time since the last drain:
        ``{stage: (ms_total, calls)}`` — pad / merge / finalize and the
        shard call's upload / launch / fetch / compile, fed into
        the per-stage metric histograms alongside the shard ledger."""
        with self._timings_lock:
            out, self._stages = self._stages, {}
        return out

    def drain_setup_timings(self) -> dict:
        """One-time setup cost to fold into the engine's compile/warm ledger
        (``{str_key: ms}``, drained once).  Remote plans report their
        connect + handshake wall time here under ``"remote"``."""
        return {}

    # -------------------------------------------------------------- lifecycle
    def close(self) -> None:
        """Release executors the plan owns (thread pools, worker processes,
        sockets).  Default: nothing to release.  Implementations must drain
        in-flight ``predict_partials`` work before tearing down."""


# ---------------------------------------------------------------------------
# name-keyed registry + capability-driven auto-selection
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register_plan(cls):
    """Class decorator: make ``cls`` constructible via :func:`create_plan`."""
    if not (isinstance(cls, type) and issubclass(cls, ExecutionPlan)):
        raise TypeError(f"register_plan expects an ExecutionPlan subclass, got {cls!r}")
    _REGISTRY[cls.name] = cls
    return cls


def available_plans() -> list:
    return sorted(_REGISTRY)


def plan_class(name: str):
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown plan {name!r}; available: {available_plans()}"
        ) from None


def select_plan(plan: Optional[str], *, mode: str, backend, shards=None,
                model=None) -> str:
    """Capability-driven auto-selection (``plan in (None, "auto")``).

    A sequence of backend names means heterogeneous tree-parallel.  One shard
    (or none requested) is the single plan.  Multiple shards pick
    tree-parallel when the mode accumulates exact integer partials and the
    forest has trees to carve; otherwise row-parallel, which is bit-exact for
    any mode because rows are independent.
    """
    if plan not in (None, "auto"):
        plan_class(plan)  # fail fast on unknown names
        return plan
    if not isinstance(backend, str) and isinstance(backend, (list, tuple)):
        return "tree_parallel"
    if shards is None or int(shards) <= 1:
        return "single"
    n_trees = getattr(model, "n_trees", None)
    if mode_spec(mode).deterministic and (n_trees is None or n_trees >= 2):
        return "tree_parallel"
    return "row_parallel"


def create_plan(name: Optional[str], model, *, mode: str = "integer",
                backend="reference", shards=None, layout: Optional[str] = None,
                backend_kwargs: Optional[dict] = None,
                **plan_kwargs) -> ExecutionPlan:
    """Instantiate a plan by name (``None``/"auto" -> :func:`select_plan`)."""
    resolved = select_plan(name, mode=mode, backend=backend, shards=shards,
                           model=model)
    return plan_class(resolved)(
        model, mode=mode, backend=backend, shards=shards, layout=layout,
        backend_kwargs=backend_kwargs, **plan_kwargs
    )

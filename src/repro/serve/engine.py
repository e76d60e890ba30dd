"""The tree serving engine.

``TreeEngine``: the paper's serving path — a thin shape-bucketing wrapper
over one :class:`~repro.plan.ExecutionPlan`, which in turn drives any
registered :class:`~repro.backends.TreeBackend` (reference jnp, Pallas
kernel, or either emitted-C flavor compiled into a shared library) on one or
many forest shards, mirroring InTreeger's "one model, any hardware"
deployment story.  The execution path is

    engine -> ExecutionPlan -> backend.predict_partials -> merge -> finalize

with the default ``single`` plan reproducing the historical engine->backend
route exactly; ``plan="tree_parallel"``/``"row_parallel"`` + ``shards=N``
shard the forest or the batch with bit-identical deterministic-mode outputs.
The plan layer (via ``repro.plan.build_backend``) is also where the ForestIR
pipeline (IR -> layout -> backend) is resolved: it materializes the layout
the backend prefers (or the caller pins) before constructing it, so callers
hand over a ForestIR or any artifact and never deal in layouts unless they
want to.  The engine is the execution layer behind the gateway
(``repro.serve.gateway``): for plans that compile per shape, incoming batches
are padded up to a small set of power-of-two row buckets so each (model,
mode, plan, bucket) compiles exactly once, no matter how ragged the request
stream is.  Tree traversal is row-independent, so padding rows never perturb
real rows — bucketed outputs are bit-identical to unbucketed ones.
Shape-oblivious plans (native C, single shard) skip padding entirely; the
engine consults the plan's aggregated capabilities for both decisions.
"""
from __future__ import annotations

import time
from typing import Optional

import numpy as np

def bucket_rows(b: int, *, max_bucket: int = 4096) -> int:
    """Padded row count for a batch of ``b`` rows: the next power of two,
    capped at ``max_bucket``; beyond the cap, the next ``max_bucket``
    multiple (so huge batches still see a bounded shape vocabulary)."""
    if b <= 0:
        raise ValueError("batch must have at least one row")
    if b >= max_bucket:
        return -(-b // max_bucket) * max_bucket
    return 1 << (b - 1).bit_length()


class TreeEngine:
    """Shape-bucketing wrapper over one :class:`~repro.plan.ExecutionPlan`.

    ``packed`` is a :class:`~repro.ir.ForestIR` or any materialized layout
    artifact.  The route — mode, backend(s), layout, plan, shards, backend
    kwargs — is one :class:`~repro.serve.spec.EngineSpec`, passed
    as ``spec`` (an EngineSpec, a dict, or a spec string like
    ``"integer:bitvector@leaf_major+tree_parallel:4"``); the individual
    keyword arguments survive as a deprecation shim that warns once per
    call site.  Within the spec: ``backend`` is a registered backend name
    (``"reference"``, ``"pallas"``, ``"native_c"``, ``"native_c_table"``,
    ...), a sequence of names (heterogeneous tree-parallel: one per shard,
    cycled), or an already-constructed backend instance (then
    ``packed``/``mode`` are taken from it).  ``plan`` selects the execution
    plan (``"single"`` | ``"tree_parallel"`` | ``"row_parallel"`` |
    ``"remote_tree_parallel"``; ``None``/``"auto"`` picks by capability:
    one shard -> single, many shards -> tree-parallel for the deterministic
    modes, row-parallel otherwise) and ``shards`` the shard count.
    ``layout`` pins a ForestIR layout; by default each shard backend's
    declared ``preferred_layout`` is materialized (resolution goes through
    the artifact's IR back-reference, so a ``pack_forest`` output can feed
    a ragged-only backend without re-quantizing).  ``plan_kwargs`` carries
    plan-specific knobs outside the spec (e.g. the remote plan's
    ``workers``/``deadline_ms`` — deployment facts, not route identity).
    ``predict``/``predict_scores`` accept any row count; for shape-compiling
    plans the batch is padded to a :func:`bucket_rows` bucket so each
    bucket compiles once (tracked in ``compiled_buckets``).  ``max_bucket``
    defaults to the plan's ``preferred_block_rows`` hint so padded shapes
    line up with the backends' internal tiling.  Engines whose plan owns
    executors (thread pools, remote workers) release them via
    :meth:`close`.
    """

    def __init__(self, packed=None, spec=None, *, mode: Optional[str] = None,
                 backend=None, backend_kwargs: Optional[dict] = None,
                 max_bucket: Optional[int] = None, layout: Optional[str] = None,
                 plan: Optional[str] = None, shards: Optional[int] = None,
                 plan_kwargs: Optional[dict] = None):
        from repro.plan import create_plan
        from repro.serve.spec import EngineSpec

        spec = EngineSpec.coerce(spec, caller="TreeEngine", mode=mode,
                                 backend=backend, layout=layout, plan=plan,
                                 shards=shards, backend_kwargs=backend_kwargs)
        self.spec = spec
        mode, backend, layout = spec.mode, spec.backend, spec.layout
        plan, shards = spec.plan, spec.shards
        backend_kwargs = dict(spec.backend_kwargs) if spec.backend_kwargs else None
        self.plan = create_plan(
            plan, packed, mode=mode, backend=backend, shards=shards,
            layout=layout, backend_kwargs=backend_kwargs,
            **(plan_kwargs or {})
        )
        self.packed = self.plan.packed
        self.mode = self.plan.mode
        self.max_bucket = max_bucket or self.plan.preferred_block_rows or 4096
        self.compiled_buckets: set[int] = set()
        # first-execution wall ms per bucket (jit compile / native build /
        # warm cost) plus the registry's artifact-load ms under "load",
        # drained by the gateway into per-model metrics
        self._compile_ms: dict = {}
        # set by close(); the registry's retention policy closes engines of
        # released versions and the gateway prunes closed engines
        self.closed = False

    @property
    def backend(self):
        """The (first) shard backend — the whole backend for single/row
        plans; ``None`` for a fused device-parallel plan (no per-shard
        backend objects exist)."""
        backends = self.plan.backends
        return backends[0] if backends else None

    @property
    def backend_name(self) -> str:
        return self.plan.backend_name

    @property
    def plan_name(self) -> str:
        return self.plan.name

    @property
    def n_shards(self) -> int:
        return self.plan.n_shards

    @property
    def layout(self) -> str:
        """The ForestIR layout(s) the plan's backends are walking."""
        return self.plan.layout

    @property
    def deterministic(self) -> bool:
        """True when outputs are bit-exact integer scores (cacheable)."""
        return self.plan.deterministic

    def simd_isa(self):
        """The SIMD ISA the serving backend dispatches to ("avx2" / "neon" /
        "scalar" for the C backends), or ``None`` for backends without the
        surface (JAX paths, fused device-parallel plans).  May trigger the
        backend's first build — callers wanting a free probe should ask
        after serving has started."""
        fn = getattr(self.backend, "simd_isa", None)
        return fn() if fn is not None else None

    def drain_shard_timings(self) -> dict:
        """Per-shard wall time since the last drain (``{label: (ms, calls)}``)
        — what the gateway records into ``serve.metrics`` per batch."""
        return self.plan.drain_timings()

    def drain_stage_timings(self) -> dict:
        """Pipeline-stage wall time since the last drain — pad (recorded
        here), merge + finalize (recorded by the plan)."""
        return self.plan.drain_stage_timings()

    def drain_compile_timings(self) -> dict:
        """First-execution (compile/warm) wall ms per bucket since the last
        drain: ``{bucket_rows: ms}``, plus the plan's one-time setup cost
        (the remote plan's connect + handshake ms under ``"remote"``)."""
        out, self._compile_ms = self._compile_ms, {}
        out.update(self.plan.drain_setup_timings())
        return out

    def close(self) -> None:
        """Release executors the plan owns: shard thread pools drain and
        re-create lazily; remote worker connections/processes tear down for
        good.  Marks the engine closed so holders (the gateway's engine set)
        can drop their references."""
        self.closed = True
        self.plan.close()

    # ------------------------------------------------------------- tracing
    def attach_trace(self, tracer, parent) -> None:
        """Attach a tracer and the span that parents this *thread's*
        execution spans (pad → shard×N → merge → finalize).  The gateway
        calls this around each batch execute; direct callers can too."""
        self.plan.attach_tracer(tracer)
        self.plan.trace_parent = parent

    def detach_trace(self) -> None:
        """Clear this thread's parent span (the tracer attach persists)."""
        self.plan.trace_parent = None

    def warm(self, max_rows: int) -> None:
        """Pre-compile every bucket any batch of 1..``max_rows`` rows can map
        to: the power-of-two buckets below ``max_bucket``, plus the
        ``max_bucket``-multiple shapes used once batches reach the cap.
        Warming goes *through the plan*, so every shard of a multi-shard plan
        sees exactly the sub-batch shapes real predicts will hand it (chunked
        rows for row-parallel, full buckets per tree shard) — no shard is
        left to compile on the first live request.  For shape-oblivious plans
        one call builds every shard's artifact (e.g. compiles the native
        libraries) and no further shapes exist."""
        zeros = lambda nb: np.zeros((nb, self.packed.n_features), np.float32)
        if not self.plan.compiles_per_shape:
            self.predict(zeros(1))
            return
        # `top` is the bucket the largest batch rounds UP to — walking only to
        # max_rows would leave the covering bucket cold (e.g. 20 rows -> 32)
        top = bucket_rows(max_rows, max_bucket=self.max_bucket)
        nb = 1
        while nb <= top and nb < self.max_bucket:
            self.predict(zeros(nb))
            nb *= 2
        if top >= self.max_bucket:
            for m in range(self.max_bucket, top + 1, self.max_bucket):
                self.predict(zeros(m))

    def padded_rows(self, b: int) -> int:
        """Rows actually executed for a ``b``-row batch: the bucket shape
        for compiling plans, ``b`` itself for shape-oblivious ones."""
        if not self.plan.compiles_per_shape:
            return b
        return bucket_rows(b, max_bucket=self.max_bucket)

    def _pad(self, X):
        X = np.asarray(X, np.float32)
        if X.ndim != 2:
            raise ValueError(f"expected (B, F) features, got shape {X.shape}")
        b = X.shape[0]
        nb = self.padded_rows(b)
        if nb != b:
            X = np.concatenate([X, np.zeros((nb - b, X.shape[1]), np.float32)])
        return X, b, nb

    def _pad_traced(self, X):
        """Bucket-pad under a timed ``pad`` stage (and span when traced)."""
        t0 = time.perf_counter_ns()
        X, b, nb = self._pad(X)
        t1 = time.perf_counter_ns()
        self.plan._record_stage("pad", (t1 - t0) / 1e9)
        self.plan._span("pad", t0, t1, self.plan.trace_parent, rows=b, padded=nb)
        return X, b, nb

    def _run(self, X):
        X, b, nb = self._pad_traced(X)
        cold = self.plan.compiles_per_shape and nb not in self.compiled_buckets
        t0 = time.perf_counter()
        scores, preds = self.plan.predict_scores(X)
        if self.plan.compiles_per_shape:
            # only a predict that actually returned has compiled its bucket
            self.compiled_buckets.add(nb)
            if cold:
                self._compile_ms[nb] = (time.perf_counter() - t0) * 1e3
        return np.asarray(scores)[:b], np.asarray(preds)[:b]

    def predict(self, X) -> np.ndarray:
        _, preds = self._run(X)
        return preds

    def predict_scores(self, X):
        return self._run(X)

    def predict_partials(self, X):
        """Merged (B, C) uint32 partials through the bucketed path
        (deterministic modes)."""
        X, b, nb = self._pad_traced(X)
        cold = self.plan.compiles_per_shape and nb not in self.compiled_buckets
        t0 = time.perf_counter()
        acc = self.plan.predict_partials(X)
        if self.plan.compiles_per_shape:
            self.compiled_buckets.add(nb)
            if cold:
                self._compile_ms[nb] = (time.perf_counter() - t0) * 1e3
        return np.asarray(acc)[:b]

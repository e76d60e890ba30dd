"""Serving subsystem: engines + the async dynamic-batching gateway.

Request path:  client → Gateway.submit → QuantizedKeyCache (per-row probe)
             → MicroBatcher (coalesce to block-shaped batches under a
               latency deadline, admission-controlled) → ModelRegistry
               (versioned, hot-swappable) → TreeEngine (shape-bucketed)
             → ExecutionPlan (single / tree-parallel / row-parallel /
               remote worker shards, exact integer partial merge, one
               finalize)
             → TreeBackend → cache fill → response.

Exports resolve lazily (PEP 562): ``repro.serve.wire`` and
``repro.serve.worker`` — the modules a remote shard worker needs before it
can print WORKER_READY — import without dragging in the jax-heavy engine,
and ``repro.plan.remote`` can import the wire protocol without a circular
trip through the gateway.
"""
from typing import TYPE_CHECKING

_EXPORTS = {
    "AdmissionError": "repro.serve.queue",
    "EngineSpec": "repro.serve.spec",
    "Gateway": "repro.serve.gateway",
    "LMEngine": "repro.serve.lm_engine",
    "MetricsRegistry": "repro.serve.metrics",
    "MicroBatcher": "repro.serve.queue",
    "ModelMetrics": "repro.serve.metrics",
    "ModelRegistry": "repro.serve.registry",
    "ModelVersion": "repro.serve.registry",
    "QuantizedKeyCache": "repro.serve.cache",
    "TreeEngine": "repro.serve.engine",
    "WorkerServer": "repro.serve.worker",
    "bucket_rows": "repro.serve.engine",
    "row_keys": "repro.serve.cache",
    "spawn_local_workers": "repro.serve.worker",
}

__all__ = sorted(_EXPORTS)

if TYPE_CHECKING:  # pragma: no cover - static analysis only
    from repro.serve.cache import QuantizedKeyCache, row_keys  # noqa: F401
    from repro.serve.engine import TreeEngine, bucket_rows  # noqa: F401
    from repro.serve.gateway import Gateway  # noqa: F401
    from repro.serve.lm_engine import LMEngine  # noqa: F401
    from repro.serve.metrics import MetricsRegistry, ModelMetrics  # noqa: F401
    from repro.serve.queue import AdmissionError, MicroBatcher  # noqa: F401
    from repro.serve.registry import ModelRegistry, ModelVersion  # noqa: F401
    from repro.serve.spec import EngineSpec  # noqa: F401
    from repro.serve.worker import WorkerServer, spawn_local_workers  # noqa: F401


def __getattr__(name: str):
    module = _EXPORTS.get(name)
    if module is None:
        raise AttributeError(f"module 'repro.serve' has no attribute {name!r}")
    import importlib

    obj = getattr(importlib.import_module(module), name)
    globals()[name] = obj  # cache: next access skips __getattr__
    return obj


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))

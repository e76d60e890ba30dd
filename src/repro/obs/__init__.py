"""Observability: request tracing, histogram telemetry, exposition.

The serving stack's measurement layer, threaded through the whole execution
path — ``Gateway`` admission → cache probe → micro-batch queue wait → engine
bucket/pad → ``ExecutionPlan`` dispatch → per-shard ``predict_partials`` →
merge → finalize → response stitch:

  * :mod:`repro.obs.trace` — staged spans: nested, thread-safe, sampled,
    near-zero cost when disabled (``NULL_SPAN`` propagation).
  * :mod:`repro.obs.stages` — the stages inside one shard call (``upload``
    with ``bytes``, ``launch`` with ``programs``, ``fetch``, ``compile``),
    marked with ``with stage(...)`` below the plan and summed by the plan
    into one sample each per call; and the process-level ``gc`` and
    ``compile`` spans that name device idle gaps no request explains.
  * :mod:`repro.obs.histogram` — fixed log-scale bucket histograms: O(1)
    record, exact counters, mergeable across shards and models.
  * :mod:`repro.obs.export` — JSONL trace export, flame-style summaries,
    Prometheus-text + strict-JSON metric snapshots.

Attach a tracer with ``Gateway(..., tracer=Tracer())`` (or ``--gw-trace`` /
``--gw-trace-out`` on ``repro.launch.serve``); stage histograms are always
on — they cost one ``perf_counter_ns`` pair per stage — and surface as the
``queue_ms`` / ``pad_ms`` / ``shard_ms`` / ``finalize_ms`` columns in
``MetricsRegistry.stats()``, with the shard call's ``upload`` / ``launch``
/ ``fetch`` / ``compile`` stages beside them in ``stats()["stages"]``.
"""
from repro.obs.export import (render_flame, render_prometheus, request_trees,
                              snapshot_json, spans_to_jsonl, write_jsonl)
from repro.obs.histogram import LogHistogram
from repro.obs.stages import ProcessSpans, stage
from repro.obs.trace import NULL_SPAN, NULL_TRACER, Span, Tracer

__all__ = [
    "LogHistogram",
    "NULL_SPAN",
    "NULL_TRACER",
    "ProcessSpans",
    "Span",
    "Tracer",
    "render_flame",
    "render_prometheus",
    "request_trees",
    "snapshot_json",
    "spans_to_jsonl",
    "stage",
    "write_jsonl",
]

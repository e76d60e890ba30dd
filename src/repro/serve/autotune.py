"""Warm-time measured autotuning of backend construction knobs.

The C emitters and the Pallas wrapper each expose one or two performance
knobs whose best value is a property of the *host*, not the model: the
table-walk C backend's ``block_rows`` (rows in flight per tree), the
bitvector backend's v-QuickScorer ``interleave`` width (trees per comparison
group), and the Pallas kernel's ``(block_b, block_t)`` VMEM tiling.  The
static defaults are sensible medians, but BENCH_7 showed the medians can be
1.3-1.8x off on a given machine.  This module is the measured answer: during
``TreeEngine.warm()`` each candidate is built on the engine's *already
materialized* layout artifact and timed (min-of-rounds ``predict_partials``
on deterministic pseudo-random rows), and the winner's kwargs are pinned.

Every candidate produces bit-identical uint32 partials (the knobs only
re-tile or re-group work — the conformance suite crosses them), so tuning
can never change an answer, only its latency.  Winner selection is
deterministic: strict-min time with the static default first, so ties — and
an injected constant timer — resolve to the default.

The winner is cached per (backend, layout, mode) route in the owning
``ModelVersion`` and copied across hot-swaps by the registry, so a swapped-in
version of the same model reuses the measurement instead of re-timing; the
measuring cost itself is surfaced through ``drain_compile_timings`` under the
``"tune"`` key and the chosen config through the metrics ``tuned`` column.

``REPRO_AUTOTUNE=0`` is the global kill switch; tuning is otherwise opt-in
per engine/gateway (``TreeEngine(autotune=True)``, ``Gateway(...,
autotune=True)``, ``--gw-autotune``).
"""
from __future__ import annotations

import os
import time

import numpy as np

# rows the candidates are timed on — one serving-sized bucket, enough to
# amortize per-call overheads without making warm() noticeably slower
_TUNE_ROWS = 256
_ROUNDS = 3
_WARMUP = 1

# backends with a measurable construction knob; anything else is a no-op
TUNABLE_BACKENDS = ("native_c_table", "native_c_bitvector", "pallas")


def autotune_enabled(flag) -> bool:
    """``flag`` gated by the ``REPRO_AUTOTUNE=0`` environment kill switch."""
    return bool(flag) and os.environ.get("REPRO_AUTOTUNE", "1") != "0"


def config_str(kwargs: dict) -> str:
    """Compact human form of a winner, e.g. ``interleave=4`` — the metrics
    ``tuned`` column and the gateway table cell."""
    return ",".join(f"{k}={v}" for k, v in sorted(kwargs.items())) or "-"


def candidate_grid(backend_name: str, artifact, rows: int = _TUNE_ROWS) -> list:
    """The candidate ``backend_kwargs`` grid for one backend, static
    default/heuristic FIRST (ties resolve to it).  Empty when the backend has
    no tunable knob."""
    if backend_name == "native_c_table":
        return [{"block_rows": r} for r in (8, 1, 4, 16)]
    if backend_name == "native_c_bitvector":
        return [{"interleave": k} for k in (8, 1, 4)]
    if backend_name == "pallas":
        from repro.kernels.ops import pick_blocks_candidates

        t, n = artifact.feature.shape
        c = artifact.leaf_fixed.shape[-1]
        # the scan (auto on a scannable leaf_major artifact) cuts the node
        # axis; its node block follows from the pinned (block_b, block_t)
        scan = (getattr(artifact, "layout", "padded") == "leaf_major"
                and getattr(artifact, "internal_counts", None) is not None)
        return [
            {"block_b": bb, "block_t": bt}
            for bb, bt, _ in pick_blocks_candidates(
                rows, t, n, artifact.n_features, c, chunk_nodes=scan
            )
        ]
    return []


def measure_backend(backend, X, *, rounds: int = _ROUNDS,
                    warmup: int = _WARMUP) -> float:
    """Min-of-rounds ``predict_partials`` wall seconds (warmup first, so a C
    build or jit compile never pollutes the measurement)."""
    for _ in range(warmup):
        backend.predict_partials(X)
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        backend.predict_partials(X)
        best = min(best, time.perf_counter() - t0)
    return best


def tune_backend(backend_name: str, artifact, mode: str, *,
                 rows: int = _TUNE_ROWS, baseline=None, measure=None):
    """Measure the candidate grid on ``artifact`` and return
    ``(winner_kwargs, winner_backend, report)``.

    ``baseline`` (optional) is an already-built backend for the grid's first
    (default) entry — reused instead of rebuilding it.  ``measure`` is
    injectable for deterministic tests.  Returns ``(None, None, [])`` when
    the backend has no grid to sweep.  The report is
    ``[(kwargs, seconds), ...]`` in grid order.
    """
    from repro.backends import create_backend

    # resolve the default at call time so tests can monkeypatch the module
    measure = measure if measure is not None else measure_backend
    grid = candidate_grid(backend_name, artifact, rows)
    if len(grid) < 2:
        return None, None, []
    rng = np.random.default_rng(0)
    X = rng.normal(0.0, 4.0, (rows, artifact.n_features)).astype(np.float32)
    report = []
    best_i, best_t, best_b = 0, float("inf"), None
    for i, kw in enumerate(grid):
        b = (baseline if i == 0 and baseline is not None
             else create_backend(backend_name, artifact, mode=mode, **kw))
        t = float(measure(b, X))
        report.append((dict(kw), t))
        if t < best_t:
            best_i, best_t, best_b = i, t, b
    return dict(grid[best_i]), best_b, report

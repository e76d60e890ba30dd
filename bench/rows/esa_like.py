"""Telemetry rows shaped like ESA-ADB: standard-normal channels, and a small
share of anomalous rows whose informative channels are shifted.

A copy, for the benchmark, of the generator behind the paper's ESA forest
(``make_esa_like`` in the program's data module): the same distribution,
drawn per request so that no row repeats within a run.
"""
from __future__ import annotations

import numpy as np


class Rows:
    """Rows of one run: the anomaly channels are fixed by ``seed``; every
    request ``k`` of ``stream`` gets rows of its own, the same for the same
    ``(seed, stream, k)`` whatever the timing."""

    def __init__(self, cfg: dict, seed: int):
        self.n_features = int(cfg["n_features"])
        params = cfg["rows"]
        self.anomaly_rate = float(params["anomaly_rate"])
        rng = np.random.default_rng([seed, 7])
        n_info = max(4, self.n_features // 8)
        self.info = rng.choice(self.n_features, n_info, replace=False)
        lo, hi = params["shift"]
        self.shift = rng.uniform(lo, hi, size=n_info).astype(np.float32)
        self.seed = seed

    def take(self, stream: int, k: int, n: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, stream, k])
        X = rng.standard_normal((n, self.n_features), dtype=np.float32)
        anomalous = rng.random(n) < self.anomaly_rate
        X[np.ix_(anomalous, self.info)] += self.shift
        return X

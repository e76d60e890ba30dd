"""Rows shaped like UCI Covertype: quantitative integer columns, then the
one-hot wilderness-area and soil-type groups (10 + 4 + 40 = 54 columns).

The configuration gives each quantitative column as ``[name, mean, sd, lo,
hi]`` (a normal clipped to ``[lo, hi]`` and rounded, as the data set holds
integers), the wilderness areas' shares, and the Zipf exponent of the soil
types.  Ten real-valued columns keep rows from repeating.
"""
from __future__ import annotations

import numpy as np


class Rows:
    def __init__(self, cfg: dict, seed: int):
        params = cfg["rows"]
        self.n_features = int(cfg["n_features"])
        cols = np.asarray([c[1:] for c in params["quantitative"]], np.float64)
        self.mean, self.sd, self.lo, self.hi = cols.T
        self.wild_p = np.asarray(params["wilderness_p"], np.float64)
        self.wild_p /= self.wild_p.sum()
        n_soil = int(params["soil_types"])
        soil = 1.0 / np.arange(1, n_soil + 1) ** float(params["soil_zipf_s"])
        # which soil type is common is the run's, from the seed
        self.soil_p = np.random.default_rng([seed, 7]).permutation(soil / soil.sum())
        if len(cols) + len(self.wild_p) + n_soil != self.n_features:
            raise ValueError("covtype_like: column groups do not add up to "
                             f"n_features={self.n_features}")
        self.seed = seed

    def take(self, stream: int, k: int, n: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, stream, k])
        q = rng.standard_normal((n, len(self.mean))) * self.sd + self.mean
        q = np.rint(np.clip(q, self.lo, self.hi))
        X = np.zeros((n, self.n_features), np.float32)
        nq, nw = len(self.mean), len(self.wild_p)
        X[:, :nq] = q
        X[np.arange(n), nq + rng.choice(nw, n, p=self.wild_p)] = 1.0
        X[np.arange(n), nq + nw + rng.choice(len(self.soil_p), n, p=self.soil_p)] = 1.0
        return X

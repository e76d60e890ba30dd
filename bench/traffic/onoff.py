"""Open loop with bursts: a mean rate ``rate_rps`` reshaped by a period.

In each ``period_s``, the first ``on_share`` of the period runs at
``rate_rps * on_factor`` and the rest at ``rate_rps * off_factor``.  The
factors should keep the mean, ``on_share * on_factor + (1 - on_share) *
off_factor == 1``.
"""
from __future__ import annotations

import numpy as np

from bench.traffic import open_loop


def profile(mix: dict, seconds: float):
    """(cumulative, inverse) of the piecewise-constant rate."""
    rate, period = float(mix["rate_rps"]), float(mix["period_s"])
    on = float(mix["on_share"]) * period
    starts = np.arange(int(np.ceil(seconds / period)) + 1) * period
    t = np.sort(np.concatenate([starts, starts + on]))
    rates = np.tile([rate * float(mix["on_factor"]),
                     rate * float(mix["off_factor"])], len(starts))[:len(t) - 1]
    cum = np.concatenate([[0.0], np.cumsum(rates * np.diff(t))])
    return (lambda x: float(np.interp(x, t, cum)),
            lambda a: np.interp(a, cum, t))


async def drive(load, mix: dict, seconds: float, seed: int, stream: int):
    cumulative, inverse = profile(mix, seconds)
    return await open_loop.drive(load, mix, seconds, seed, stream,
                                 cumulative=cumulative, inverse=inverse)

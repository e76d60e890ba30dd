"""The open loop shared by the arrival kinds (``poisson.py``, ``onoff.py``).

A run's work is fixed, and the seed only orders it: the request sizes are
the stratified quantiles of the mix's size distribution, and the gaps are
the stratified quantiles of a unit exponential in operational time.  Each
seed permutes both, and the gaps are scaled so that the ``N = rate *
seconds`` arrivals fill the window exactly.  A rate profile ``Lambda(t)``
(cumulative expected arrivals) maps operational time to real time, so
bursts only reshape when the same work arrives.

Requests are sent at absolute due times and timed from them: a stalled
sender delays the later requests, and their latency shows it.  How late
each send ran is recorded.
"""
from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field

import numpy as np

LEAD_S = 0.05  # the first due time lies this far past the schedule's start


@dataclass
class Record:
    k: int             # request index within its stream
    rows: int
    t_due: float       # perf_counter seconds; the send time in a closed loop
    t_sent: float = 0.0
    t_done: float = 0.0
    answer: tuple = None  # (scores, preds) as served
    refused: bool = False
    error: str = None

    @property
    def ok(self) -> bool:
        return self.answer is not None

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_due

    @property
    def late_s(self) -> float:
        return self.t_sent - self.t_due


@dataclass
class Window:
    t0: float          # first due time: the end of set-up
    seconds: float
    records: list = field(default_factory=list)


def size_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` request sizes at the stratified quantiles of ``spec``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec.get("lo", 1)), int(spec.get("hi", spec.get("lo", 1)))
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, lo, np.int64)
    if dist == "uniform":
        return lo + np.floor(u * (hi - lo + 1)).astype(np.int64)
    if dist == "log_uniform":
        s = np.exp(np.log(lo) + u * (np.log(hi + 1) - np.log(lo)))
        return np.clip(np.floor(s), lo, hi).astype(np.int64)
    raise ValueError(f"unknown size distribution {dist!r}")


def schedule(mix: dict, seconds: float, seed: int, cumulative, inverse):
    """(offsets from the first due time, sizes) of one run.

    ``cumulative(t)`` is the expected number of arrivals by ``t``;
    ``inverse`` maps expected arrivals back to time."""
    n = max(1, int(round(cumulative(seconds))))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    sizes = size_quantiles(mix["rows"], n)
    rng = np.random.default_rng([seed, 5])
    gaps, sizes = rng.permutation(gaps), rng.permutation(sizes)
    points = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum()
    return inverse(points * cumulative(seconds)), sizes


async def drive(load, mix: dict, seconds: float, seed: int, stream: int,
                cumulative, inverse) -> Window:
    offsets, sizes = schedule(mix, seconds, seed, cumulative, inverse)
    t0 = time.perf_counter() + LEAD_S
    window = Window(t0=t0, seconds=seconds)
    tasks = []
    for k, (off, n) in enumerate(zip(offsets, sizes)):
        X = load.rows(stream, k, int(n))
        rec = Record(k=k, rows=int(n), t_due=t0 + float(off))
        delay = rec.t_due - time.perf_counter()
        await asyncio.sleep(max(delay, 0.0))  # yields even when behind
        tasks.append(asyncio.ensure_future(load.send(rec, X)))
        window.records.append(rec)
        await asyncio.sleep(0)  # the send starts before the next rows are made
    await asyncio.gather(*tasks)
    return window

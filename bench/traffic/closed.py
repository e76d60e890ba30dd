"""Closed loop: ``clients`` callers, each sending a request and waiting for
its answer before the next, until the window closes.

Each request is timed from its send.  Request ``k`` gets the rows of index
``k`` whichever client sends it, so a seed gives the same rows in the same
order.  Sizes come from the mix's ``rows`` distribution at the request's
stratified quantile within a block of ``clients`` requests.
"""
from __future__ import annotations

import asyncio
import itertools
import time

from bench.traffic.open_loop import LEAD_S, Record, Window, size_quantiles


async def drive(load, mix: dict, seconds: float, seed: int, stream: int):
    clients = int(mix["clients"])
    sizes = size_quantiles(mix["rows"], clients)
    t0 = time.perf_counter() + LEAD_S
    t_end = t0 + seconds
    window = Window(t0=t0, seconds=seconds)
    counter = itertools.count()
    await asyncio.sleep(LEAD_S)

    async def client():
        while time.perf_counter() < t_end:
            k = next(counter)
            n = int(sizes[k % clients])
            X = load.rows(stream, k, n)
            rec = Record(k=k, rows=n, t_due=time.perf_counter())
            window.records.append(rec)
            await load.send(rec, X)

    await asyncio.gather(*(client() for _ in range(clients)))
    return window

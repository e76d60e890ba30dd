"""Open loop, Poisson arrivals at the mix's ``rate_rps`` (requests/s)."""
from __future__ import annotations

from bench.traffic import open_loop


async def drive(load, mix: dict, seconds: float, seed: int, stream: int):
    rate = float(mix["rate_rps"])
    return await open_loop.drive(load, mix, seconds, seed, stream,
                                 cumulative=lambda t: rate * t,
                                 inverse=lambda a: a / rate)

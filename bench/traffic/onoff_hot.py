"""Open loop with bursts in which most requests re-send rows of a hot set:
the ``onoff`` arrivals and sizes, each request drawn hot with probability
``hot.share``.

The hot set is ``hot.rows`` rows cut into keys, one request's rows each,
of the sizes ``rows.lo`` to ``rows.hi`` in turn (1, 2, ..., 8, 1, 2, ...)
until the rows are spent.  A hot request of ``n`` rows re-sends the rows of
one key of size ``n``, picked from the seed; a size with no key is sent
fresh.  Keys lie at :data:`HOT_KEY` and above, where no fresh request's
index reaches, and the request's record takes its key as ``k``, so that the
check regenerates the rows the request sent.
"""
from __future__ import annotations

import numpy as np

from bench.traffic import onoff

HOT_KEY = 1 << 40  # the first hot key; fresh requests count up from 0
HOT_STREAM = 8     # the seed's draws of which request is hot, and its key


def hot_keys(mix: dict) -> dict:
    """size -> the hot set's keys of that size."""
    lo, hi = int(mix["rows"]["lo"]), int(mix["rows"]["hi"])
    keys, left, size, key = {}, int(mix["hot"]["rows"]), lo, HOT_KEY
    while left > 0:
        n = min(size, left)
        keys.setdefault(n, []).append(key)
        key, left = key + 1, left - n
        size = lo if size == hi else size + 1
    return keys


class HotLoad:
    """The client side of the gateway, with hot requests' rows and keys."""

    def __init__(self, load, mix: dict, seed: int):
        self._load, self._seed = load, seed
        self._keys = hot_keys(mix)
        self._share = float(mix["hot"]["share"])
        self._sent_as = {}  # request index -> the hot key its rows came from

    def key(self, stream: int, k: int, n: int) -> int:
        """The key whose rows request ``k`` of ``n`` rows sends."""
        rng = np.random.default_rng([self._seed, HOT_STREAM, stream, k])
        keys = self._keys.get(n)
        if keys and rng.random() < self._share:
            return keys[int(rng.integers(len(keys)))]
        return k

    def rows(self, stream: int, k: int, n: int) -> np.ndarray:
        key = self.key(stream, k, n)
        if key != k:
            self._sent_as[k] = key
        return self._load.rows(stream, key, n)

    async def send(self, rec, X) -> None:
        rec.k = self._sent_as.pop(rec.k, rec.k)
        await self._load.send(rec, X)


async def drive(load, mix: dict, seconds: float, seed: int, stream: int):
    return await onoff.drive(HotLoad(load, mix, seed), mix, seconds, seed, stream)

"""Find what stops a served run for tens of milliseconds or more, and where.

    python3 bench/stalls.py --workload rf-esa.steady --seed 7 --seconds 30 \
        [--min-ms 30] [--out stalls.json]

One run of the cell as ``bench/run.py`` makes it (set-up, warm-up, window),
with the program's spans on and four watchers, each waking every
millisecond and noting when it woke late by ``--min-ms`` or more:

- ``loop``: a task on the event loop that drives the traffic.  It is late
  whenever the generator is.
- ``thread``: a Python thread.  It is late only while another thread holds
  the interpreter's lock, or while the whole process is stopped.
- ``process``: a child process of plain Python, without JAX.  It is late
  only while the machine stops everything in it.
- ``stacks``: at every wake the thread watcher notes the innermost Python
  frames of every other thread; for each of its stalls, the notes from
  just before and just after are kept, and for each stall of the loop,
  where the main thread was most often during it.  (``faulthandler``'s dumps, taken
  without the lock, crashed the process.)

Python's collections are timed as well.  For each stall of the loop, the
output names the watchers that saw it, the collections and the program's
serving stages (spans) in progress.  Not part of a run of the benchmark.
"""
from __future__ import annotations

import argparse
import asyncio
import collections
import gc
import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent

CHILD = """
import signal, sys, time
signal.signal(signal.SIGTERM, lambda *a: sys.exit(0))
min_s = float(sys.argv[1])
last = time.perf_counter()
while True:
    time.sleep(0.001)
    now = time.perf_counter()
    if now - last - 0.001 > min_s:
        print(last, now, flush=True)
    last = now
"""


class Watchers:
    """The four watchers and the collection timer; ``stop()`` -> findings."""

    def __init__(self, min_s: float):
        self.min_s = min_s
        self.late = {"loop": [], "thread": [], "process": []}
        self.gcs, self._gc_t0 = [], None
        self.stacks = []  # (stall start, end, {thread: frames before}, after)
        self.loop_where = []  # per loop stall: the main thread's frames in it
        self._recent = collections.deque(maxlen=4000)  # (t, frames) samples
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.child = subprocess.Popen([sys.executable, "-c", CHILD, str(min_s)],
                                      stdout=subprocess.PIPE, text=True)
        gc.callbacks.append(self._gc)
        self._thread = threading.Thread(target=self._watch_thread, daemon=True)
        self._thread.start()

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gcs.append((self._gc_t0, time.perf_counter(), info["generation"]))

    @staticmethod
    def _frames(depth: int = 4) -> dict:
        me, names = threading.get_ident(), {t.ident: t.name for t in threading.enumerate()}
        out = {}
        for ident, f in sys._current_frames().items():
            if ident == me:
                continue
            where = []
            while f is not None and len(where) < depth:
                where.append(f"{Path(f.f_code.co_filename).name}:{f.f_lineno} {f.f_code.co_name}")
                f = f.f_back
            out[names.get(ident, str(ident))] = where
        return out

    def _watch_thread(self):
        last, before = time.perf_counter(), self._frames()
        while not self._stop.is_set():
            time.sleep(0.001)
            now = time.perf_counter()
            after = self._frames()
            with self._lock:
                self._recent.append((now, after))
            if now - last - 0.001 > self.min_s:
                self.late["thread"].append((last, now))
                self.stacks.append((last, now, before, after))
            last, before = now, after

    async def watch_loop(self):
        last = time.perf_counter()
        while not self._stop.is_set():
            await asyncio.sleep(0.001)
            now = time.perf_counter()
            if now - last - 0.001 > self.min_s:
                self.late["loop"].append((last, now))
                with self._lock:
                    seen = [f.get("MainThread", []) for t, f in self._recent
                            if last <= t <= now]
                tops = collections.Counter(" < ".join(x[:2]) for x in seen)
                self.loop_where.append(tops.most_common(3))
            last = now

    def stop(self):
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._gc)
        self.child.terminate()
        out, _ = self.child.communicate()
        self.late["process"] = [tuple(map(float, ln.split()))
                                for ln in out.splitlines() if ln.strip()]


def overlapping(intervals, s, e):
    return [iv for iv in intervals if iv[0] < e and iv[1] > s]


def report(w: Watchers, spans: list, window) -> dict:
    stalls = []
    for (s, e), where in zip(w.late["loop"], w.loop_where):
        stages = {}
        for sp in spans:
            t0, t1 = sp.t0 / 1e9, sp.t1 / 1e9
            ov = min(e, t1) - max(s, t0)
            if ov > 0 and sp.name != "request":
                stages[sp.name] = max(stages.get(sp.name, 0.0), ov * 1e3)
        stalls.append({
            "at_s": s - window.t0, "ms": (e - s) * 1e3,
            "thread_ms": sum((b - a) * 1e3 for a, b in overlapping(w.late["thread"], s, e)),
            "process_ms": sum((b - a) * 1e3 for a, b in overlapping(w.late["process"], s, e)),
            "gc_ms": sum((b - a) * 1e3 for a, b, _ in overlapping(w.gcs, s, e)),
            "stages_ms": {k: round(v, 3) for k, v in sorted(stages.items())},
            "main_thread": where,
            "stacks": [{"before": b, "after": a}
                       for ts, te, b, a in w.stacks if ts < e and te > s]})
    done = [r for r in window.records if r.ok]
    lat = np.asarray([r.latency_s for r in done]) * 1e3
    return {"requests": len(window.records), "refused": sum(r.refused for r in window.records),
            "p50_ms": float(np.percentile(lat, 50)), "p99_ms": float(np.percentile(lat, 99)),
            "late_counts": {k: len(v) for k, v in w.late.items()},
            "late_ms": {k: sorted(round((b - a) * 1e3, 3) for a, b in v)[-10:]
                        for k, v in w.late.items()},
            "gc_over_5ms": [[g, round((b - a) * 1e3, 3)] for a, b, g in w.gcs
                            if b - a > 0.005],
            "stalls": stalls}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--min-ms", type=float, default=30.0)
    ap.add_argument("--out", default="stalls.json")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.catalog import Catalog, use_compile_cache
    from bench.harness import WARMUP_STREAM, WINDOW_STREAM, set_up
    from repro.obs import Tracer

    use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("bench/stalls.py: no TPU; stalls are looked for on the chip",
              file=sys.stderr)
        return 1
    catalog = Catalog()
    cell = catalog.cell(args.workload)
    mix = cell.traffic
    tracer = Tracer(capacity=1 << 22)
    served = set_up(cell, catalog, args.seed, tracer=tracer)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)

    async def go():
        try:
            await served.drive(served.load, mix, float(mix["warmup_s"]),
                               args.seed, WARMUP_STREAM)
            gc.collect()
            gc.freeze()
            tracer.drain()
            w = Watchers(args.min_ms / 1e3)
            loop_task = asyncio.ensure_future(w.watch_loop())
            window = await served.drive(served.load, mix, args.seconds,
                                        args.seed, WINDOW_STREAM)
            w.stop()
            await loop_task
            return w, window
        finally:
            await served.gateway.close()

    w, window = asyncio.run(go())
    found = dict(workload=cell.name, seed=args.seed,
                 **report(w, tracer.drain(), window))
    out.write_text(json.dumps(found, indent=1) + "\n")
    print(json.dumps({k: v for k, v in found.items() if k != "stalls"}), flush=True)
    for s in found["stalls"]:
        print(json.dumps({k: v for k, v in s.items() if k != "stacks"}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

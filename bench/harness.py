"""One run of one cell: set-up, a measured window, the check, the metrics.

Set-up draws the configuration's forest from the seed, writes it as an ITRF
artifact and loads it the way users do (``ModelRegistry.register_artifact``),
builds the ``Gateway`` on the configuration's route, warms the engine's row
buckets, and runs a short phase of the cell's own traffic.  The window then
drives ``Gateway.submit`` with the cell's traffic for ``seconds``; every
answer it returns is kept.  After the window, a sample of the answered
requests, drawn from the seed and holding the largest, is compared with
:mod:`bench.reference`, and the metrics' readers turn what was recorded
into numbers.

This module never looks for a chip; ``bench/run.py`` does, before it calls
:func:`run_cell`.
"""
from __future__ import annotations

import asyncio
import gc
import os
import resource
import shutil
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from bench import reference
from bench.catalog import BENCH_DIR, Catalog, Cell
from bench.forest import draw_forest
from bench.work import ForestWork

MODEL = "bench"
MARK = "jit_bench_clock_mark"  # the module clock_mark() runs
WINDOW_STREAM, WARMUP_STREAM = 3, 4  # row streams: no row of one is in another
OUT_DIR = BENCH_DIR / "out"


@dataclass
class Context:
    """What a metric's reader may read (``metrics/<name>.py``)."""

    window: object                 # traffic.open_loop.Window
    setup_s: float
    counters: dict                 # gateway counters over the window
    work: ForestWork
    peaks: dict = None
    trace: object = None           # trace_reduce.DeviceTrace, traced runs
    trace_window_s: float = None
    batch_rows: list = None        # rows of each engine batch, traced runs

    @property
    def answered(self) -> list:
        return [r for r in self.window.records if r.ok]


class Load:
    """The client side of the gateway: rows for request ``k`` and the send."""

    def __init__(self, gateway, rows):
        from repro.serve.queue import AdmissionError

        self.gateway, self._rows = gateway, rows
        self._refused = AdmissionError

    def rows(self, stream: int, k: int, n: int) -> np.ndarray:
        return self._rows.take(stream, k, n)

    async def send(self, rec, X) -> None:
        rec.t_sent = time.perf_counter()
        try:
            rec.answer = await self.gateway.submit(MODEL, X)
        except self._refused:
            rec.refused = True
        except Exception as e:  # the check reports it as unanswered
            rec.error = repr(e)
        rec.t_done = time.perf_counter()


def counters(gateway) -> dict:
    """The gateway's always-on counters and stage sums, to be differenced."""
    mm = gateway.metrics.model(MODEL)
    out = {k: getattr(mm, k) for k in ("batches", "batched_rows", "padded_rows",
                                        "cache_hits", "cache_misses", "rejected")}
    out["stages"] = {name: (h.count, h.total) for name, h in mm.stages.items()}
    return out


def difference(after: dict, before: dict) -> dict:
    out = {k: after[k] - before[k] for k in after if k != "stages"}
    out["stages"] = {}
    for name, (n, tot) in after["stages"].items():
        n0, t0 = before["stages"].get(name, (0, 0.0))
        out["stages"][name] = (n - n0, tot - t0)
    return out


@dataclass
class Served:
    """The system under test, set up."""

    gateway: object
    load: Load
    forest: object
    drive: object  # the traffic kind's drive()


def set_up(cell: Cell, catalog: Catalog, seed: int, *, tracer=None) -> Served:
    from repro.ir import ForestIR
    from repro.serve.gateway import Gateway
    from repro.serve.registry import ModelRegistry

    cfg, mix = cell.config, cell.traffic
    rows = catalog.rows(cfg["rows"]["generator"]).Rows(cfg, seed)
    forest = draw_forest(cfg, rows, seed)
    OUT_DIR.mkdir(exist_ok=True)
    artifact = OUT_DIR / f"{cfg['name']}.itrf"
    ForestIR.from_forest(forest).to_itrf(str(artifact))
    registry = ModelRegistry()
    registry.register_artifact(MODEL, str(artifact))
    serve = cfg["serve"]
    gateway = Gateway(registry, cfg["route"],
                      max_batch_rows=int(serve["max_batch_rows"]),
                      max_delay_ms=float(serve["max_delay_ms"]),
                      max_queue_rows=int(serve["max_queue_rows"]),
                      cache_rows=int(serve["cache_rows"]), tracer=tracer)
    registry.get(MODEL).engine(cfg["route"]).warm(int(serve["max_batch_rows"]))
    return Served(gateway, Load(gateway, rows), forest,
                  catalog.generator(mix["kind"]).drive)


def check(served: Served, window, seed: int, sample_rows: int, bits: int = 32) -> dict:
    """Compare a seeded sample of the window's answers, the largest request
    among them, with the reference; ``bits < 32`` puts the reference's
    lower-precision control in the program's place.

    -> the numbers compared, each ``{"value", "limit"}``."""
    recs = window.records
    answered = [r for r in recs if r.ok]
    lost = sum(1 for r in recs if not r.ok and not r.refused)
    order = np.random.default_rng([seed, 6]).permutation(len(answered))
    picked, n_rows = [], 0
    if answered:
        largest = max(range(len(answered)), key=lambda i: answered[i].rows)
        for i in [largest] + [int(i) for i in order if i != largest]:
            if n_rows >= sample_rows:
                break
            picked.append(answered[i])
            n_rows += answered[i].rows
    wrong_rows = wrong_class = 0
    gap = 0
    if picked:
        X = np.concatenate([served.load.rows(WINDOW_STREAM, r.k, r.rows)
                            for r in picked])
        want, want_cls = reference.scores(served.forest, X, bits=32)
        if bits != 32:
            got = reference.scores(served.forest, X, bits=bits)
        else:
            got = (np.concatenate([np.asarray(r.answer[0]) for r in picked]),
                   np.concatenate([np.asarray(r.answer[1]) for r in picked]))
        got_s = np.asarray(got[0]).astype(np.int64)
        diff = np.abs(got_s - want.astype(np.int64))
        wrong_rows = int((diff.max(axis=1) > 0).sum())
        wrong_class = int((np.asarray(got[1]) != want_cls).sum())
        gap = int(diff.max())
    return {
        "rows_checked": {"value": n_rows, "limit": f">= {min(sample_rows, 1)}"},
        "unanswered": {"value": lost, "limit": 0},
        "wrong_rows": {"value": wrong_rows, "limit": 0},
        "wrong_class": {"value": wrong_class, "limit": 0},
        "max_score_gap": {"value": gap, "limit": 0},
    }


def passes(checks: dict) -> bool:
    return (checks["rows_checked"]["value"] >= 1
            and all(c["value"] <= c["limit"] for name, c in checks.items()
                    if name != "rows_checked"))


def run_window(served: Served, mix: dict, seconds: float, seed: int, *,
               warmup_s: float, before_window=None):
    """Warm-up phase, then the window; -> (window, counters over it).

    Between the two, the objects that set-up made (JAX's traced programs,
    the forest, the warm-up's garbage) are collected once and frozen, as a
    server does once it is warm: the window's collections then walk only
    what the window makes, and its tail is not set by passes over set-up's
    heap.  The window's own garbage is still collected inside it."""

    async def go():
        try:
            await served.drive(served.load, mix, warmup_s, seed, WARMUP_STREAM)
            gc.collect()
            gc.freeze()
            c0 = counters(served.gateway)
            if before_window is not None:
                before_window()
            window = await served.drive(served.load, mix, seconds, seed,
                                        WINDOW_STREAM)
            return window, difference(counters(served.gateway), c0)
        finally:
            await served.gateway.close()

    return asyncio.run(go())


def clock_mark():
    """Compile a tiny device program; -> run(), which runs it and returns
    ``perf_counter_ns`` at its dispatch.  Its module's start in a trace ties
    the profiler's clock to ``perf_counter``, to within a dispatch."""
    import jax
    import jax.numpy as jnp

    def bench_clock_mark(x):
        return x + 1

    f, x = jax.jit(bench_clock_mark), jnp.zeros((), jnp.int32)
    f(x).block_until_ready()

    def run() -> int:
        t = time.perf_counter_ns()
        f(x).block_until_ready()
        return t

    return run


def host_usage() -> np.ndarray:
    """(process CPU s, involuntary context switches, machine CPU busy s,
    machine CPU stolen s): differenced over a window, they say whether the
    process was short of CPU and who else used it."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    try:
        with open("/proc/stat") as f:
            t = [int(v) for v in f.readline().split()[1:9]]
        busy = (t[0] + t[1] + t[2] + t[5] + t[6]) / os.sysconf("SC_CLK_TCK")
        steal = t[7] / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        busy = steal = float("nan")
    return np.array([ru.ru_utime + ru.ru_stime, ru.ru_nivcsw, busy, steal])


def read_metrics(entries: list, ctx: Context, catalog: Catalog) -> dict:
    """Each metric's reader; one that finds nothing to read is left out."""
    out = {}
    for m in entries:
        value = catalog.reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, catalog: Catalog, *, seed: int, seconds: float,
             trace: bool, t_start: float, device: dict, peaks: dict,
             memory_peak=None, log=print) -> dict:
    """One run; -> the result line's object (without printing it).

    ``t_start`` is the process's start on the ``perf_counter`` clock, so
    ``setup_s`` runs from it to the window's first due time.  ``memory_peak``
    reads the fullest device's peak bytes once the window has closed."""
    cfg, mix = cell.config, cell.traffic
    tracer = None
    if trace:
        from repro.obs import Tracer
        tracer = Tracer(capacity=1 << 22)
    served = set_up(cell, catalog, seed, tracer=tracer)
    counts = served.forest.node_counts
    log(f"forest {cfg['name']}: {len(counts)} trees, nodes per tree mean "
        f"{counts.mean():.1f} max {counts.max()}, depth {served.forest.max_depth}")

    trace_dir = OUT_DIR / "trace"
    traced, usage = {}, {}

    def start_window():
        if trace:
            import jax
            tracer.drain()
            mark = clock_mark()
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            # the device's planes alone: the host tracer (levels 1 and 2)
            # slowed the hb-rf-covtype.batch host path five times over
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            traced["t0"] = time.perf_counter()
            traced["mark_ns"] = mark()
        usage["before"] = host_usage()

    window, delta = run_window(served, mix, seconds, seed,
                               warmup_s=float(mix["warmup_s"]),
                               before_window=start_window)
    used = host_usage() - usage["before"]
    log(f"host over the window: process CPU {used[0]:.2f} s, {used[1]:.0f} "
        f"involuntary context switches; machine CPU busy {used[2]:.2f} s, "
        f"stolen {used[3]:.2f} s; load average {os.getloadavg()[0]:.2f}")
    ctx = Context(window=window, setup_s=window.t0 - t_start,
                  counters=delta, work=ForestWork.of(served.forest, cfg["max_depth"]),
                  peaks=peaks)
    if trace:
        import jax
        ctx.trace_window_s = time.perf_counter() - traced["t0"]
        jax.profiler.stop_trace()
    device = dict(device)
    device["memory_peak_bytes"] = int(memory_peak()) if memory_peak else 0

    checks = check(served, window, seed, int(mix["check_rows"]))
    if trace:
        from bench import trace_reduce
        spans = tracer.drain()
        t1 = window.t0 + window.seconds
        ctx.batch_rows = [s.attrs["rows"] for s in spans
                          if s.name == "batch" and s.t0 >= window.t0 * 1e9
                          and s.t0 <= t1 * 1e9 + 1e9]
        devices, host, modules = trace_reduce.read_planes(
            trace_reduce.find_xplane(trace_dir))
        # the program's spans, moved onto the profiler's clock, say what the
        # host was doing in each idle gap of the device
        offset = trace_reduce.clock_offset_ns(modules, MARK, traced["mark_ns"])
        if offset is not None:
            host = host + [(s.name, s.t0 + offset, s.t1 + offset) for s in spans]
        ctx.trace = trace_reduce.reduce(devices, host)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace_window_s
    else:
        log(f"per-layer readings of the untraced window: "
            f"{read_metrics(cell.per_layer, ctx, catalog)}")
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, ctx, catalog)
    result = {
        "correct": passes(checks),
        "attempted": len(window.records),
        "failed": sum(1 for r in window.records if not r.ok),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = {"device_ops": ctx.trace.device_ops,
                               "idle_gaps": ctx.trace.idle_gaps}
    result["checks"] = checks
    return result

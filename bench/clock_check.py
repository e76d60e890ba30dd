"""Check the program's spans against the device trace, in one traced run.

    python3 bench/clock_check.py --workload rf-esa.steady --seed 7 \
        --seconds 30 [--marks 20] [--out check.json]

One run of the cell as ``bench/run.py --trace 1`` makes it (device planes
only, the program's spans on), with three readings of its own:

- ``inside``: the share of the kernel's device calls (``tree_traverse_*``)
  that lie, after the marker's clock offset, inside some batch's span from
  its first ``launch`` start to its ``fetch`` end; and the least margins
  by which they do (kernel start after launch start, fetch end after
  kernel end).
- ``offset``: the marker program run ``--marks`` times: the spread of
  their offsets (device start minus host dispatch), against the first's,
  which is the one the harness uses.
- ``traced``: the run's own end-to-end metrics, answered requests, and
  the tracing's cost inside the dispatch: the mean ``shard:*`` span less
  the mean ``shard`` stage sample, per batch.

It also sums the window's ``gc`` and ``compile`` spans, names the longest
idle gaps as the harness does, and lists for each of the five longest the
spans that overlap it (name, ms of overlap, ms long).  Not part of a run of
the benchmark; prints one JSON object.
"""
from __future__ import annotations

import argparse
import bisect
import json
import shutil
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def batch_windows(spans) -> list:
    """(launch start, fetch end, shard span id) of each traced shard call
    with both stages, sorted."""
    kids: dict = {}
    for s in spans:
        if s.name in ("launch", "fetch"):
            kids.setdefault(s.parent_id, []).append(s)
    out = []
    for s in spans:
        if s.name.startswith("shard:"):
            ks = kids.get(s.span_id, [])
            launch = [k.t0 for k in ks if k.name == "launch"]
            fetch = [k.t1 for k in ks if k.name == "fetch"]
            if launch and fetch:
                out.append((min(launch), max(fetch), s.span_id))
    return sorted(out)


def inside(kernels, windows, offset: int) -> dict:
    """Share of kernel calls ``(start, end)`` (device clock) inside a batch
    window moved by ``offset``, and the least margins of those inside."""
    starts = [w[0] + offset for w in windows]
    n_in, lead, tail = 0, [], []
    for ks, ke in kernels:
        i = bisect.bisect_right(starts, ks) - 1
        if i >= 0 and ke <= windows[i][1] + offset:
            n_in += 1
            lead.append(ks - starts[i])
            tail.append(windows[i][1] + offset - ke)
    return {"kernels": len(kernels), "inside": n_in,
            "share": n_in / len(kernels) if kernels else None,
            "min_lead_us": min(lead) / 1e3 if lead else None,
            "min_tail_us": min(tail) / 1e3 if tail else None}


def gap_spans(devices: dict, host: list, top: int = 5) -> list:
    """The ``top`` longest idle gaps of the first device, each with the eight
    host spans that overlap it most: ``[gap ms, [[name, overlap ms, span
    ms], ...]]``."""
    from bench.trace_reduce import merge

    merged = merge([(s, e) for _, s, e in next(iter(devices.values()))])
    gaps = sorted(zip(merged[:-1, 1], merged[1:, 0]), key=lambda g: g[0] - g[1])
    out = []
    for gs, ge in gaps[:top]:
        over = [[name, (min(ge, he) - max(gs, hs)) / 1e6, (he - hs) / 1e6]
                for name, hs, he in host if min(ge, he) > max(gs, hs)]
        out.append([(ge - gs) / 1e6, sorted(over, key=lambda o: -o[1])[:8]])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--marks", type=int, default=20)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import trace_reduce
    from bench.catalog import Catalog, use_compile_cache
    from bench.harness import (MARK, OUT_DIR, Context, clock_mark, read_metrics,
                               run_window, set_up)
    from bench.readers import stage_mean_ms
    from bench.work import ForestWork
    from repro.obs import Tracer

    use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("bench/clock_check.py: no TPU; the device trace comes only from "
              "the chip", file=sys.stderr)
        return 1
    catalog = Catalog()
    cell = catalog.cell(args.workload)
    tracer = Tracer(capacity=1 << 22)
    served = set_up(cell, catalog, args.seed, tracer=tracer)
    trace_dir = OUT_DIR / "clock_check"
    marks: list = []

    def start_window():
        tracer.drain()
        mark = clock_mark()
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        marks.extend(mark() for _ in range(args.marks))

    window, delta = run_window(served, cell.traffic, args.seconds, args.seed,
                               warmup_s=float(cell.traffic["warmup_s"]),
                               before_window=start_window)
    jax.profiler.stop_trace()
    spans = tracer.drain()
    devices, _, modules = trace_reduce.read_planes(trace_reduce.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    mark_starts = sorted(s for name, s, _ in modules if name.startswith(MARK))
    offsets = np.asarray(mark_starts[:len(marks)]) - np.asarray(marks[:len(mark_starts)])
    offset = int(offsets[0])  # the harness's: the first mark's
    kernels = sorted((s, e) for ops in devices.values() for name, s, e in ops
                     if trace_reduce.KERNEL_PREFIX in name)
    windows = batch_windows(spans)

    ctx = Context(window=window, setup_s=0.0, counters=delta,
                  work=ForestWork.of(served.forest, cell.config["max_depth"]))
    t0_ns, t1_ns = window.t0 * 1e9, (window.t0 + window.seconds) * 1e9
    in_window = [s for s in spans if t0_ns <= s.t0 <= t1_ns]
    shard = [s.t1 - s.t0 for s in in_window if s.name.startswith("shard:")]
    host = [(s.name, s.t0 + offset, s.t1 + offset) for s in spans]
    reduced = trace_reduce.reduce(devices, host)
    result = {
        "workload": cell.name, "seed": args.seed,
        "device": jax.devices()[0].device_kind,
        "inside": inside(kernels, windows, offset),
        "offset": {"marks": len(offsets),
                   "spread_us": float(offsets.max() - offsets.min()) / 1e3,
                   "first_minus_least_us": float(offsets[0] - offsets.min()) / 1e3},
        "traced": {
            "attempted": len(window.records), "answered": len(ctx.answered),
            "end_to_end": {k: v["value"] for k, v in
                           read_metrics(cell.end_to_end, ctx, catalog).items()
                           if k != "setup_s"},
            "stages_ms": {name: stage_mean_ms(ctx, name)
                          for name in sorted(delta["stages"])},
            "tracing_in_dispatch_us": (float(np.mean(shard)) / 1e3
                                       - stage_mean_ms(ctx, "shard") * 1e3
                                       if shard else None),
            "spans_per_batch": len(in_window) / max(delta["batches"], 1),
        },
        "process_spans": {
            name: {"count": len(ds), "total_ms": sum(ds), "max_ms": max(ds, default=0.0)}
            for name in ("gc", "compile")
            for ds in [[(s.t1 - s.t0) / 1e6 for s in in_window if s.name == name]]},
        "idle_gaps": reduced.idle_gaps,
        "gap_spans": gap_spans(devices, host),
    }
    print(json.dumps(result), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  ``--trace 0`` measures the cell's end-to-end
metrics; ``--trace 1`` records a profiler trace of the window and reports
its per-layer metrics.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` when traced, and ``checks``: each number compared with the
reference beside its limit); the compared numbers are also the last lines
of standard error.

It runs only on a TPU: on any other platform, with fewer chips than the
cell asks for, on a device missing from ``bench/peaks.json``, or outside a
checkout that holds the program (``src/repro``), it exits non-zero and
prints no result.  JAX's persistent compilation cache lives at the
checkout's ``.jax_cache`` and keeps every compile, so only the first run
in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def process_age_s() -> float:
    """Seconds since this process started, by the kernel's account (the
    interpreter's own start-up counts as set-up too); 0 where unknown."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def fail(msg: str, code: int = 1) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return code


def main(argv=None) -> int:
    t_start = T_START - process_age_s()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no program at {ROOT / 'src' / 'repro'}: run from a "
                    "checkout of the repository", 2)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.catalog import Catalog, peaks_for, use_compile_cache
    from bench.harness import run_cell

    catalog = Catalog()
    try:
        cell = catalog.cell(args.workload)
    except KeyError as e:
        return fail(str(e), 2)

    import jax

    use_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != "tpu":
        return fail(f"no TPU: JAX found {device}; the benchmark runs only on "
                    "the chip")
    if device["count"] < cell.chips:
        return fail(f"{cell.name} needs {cell.chips} chips, found {device['count']}")
    try:
        peaks = peaks_for(device["kind"])
    except KeyError as e:
        return fail(str(e))

    def memory_peak() -> int:
        return max(d.memory_stats().get("peak_bytes_in_use", 0) for d in devices)

    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    log(f"device: {device}; cell {cell.name}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}")
    result = run_cell(cell, catalog, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=t_start, device=device,
                      peaks=peaks, memory_peak=memory_peak, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

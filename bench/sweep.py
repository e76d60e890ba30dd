"""Find the knee of an open-loop mix on the chip: the highest offered rate
at which completions keep pace with arrivals.

    python3 bench/sweep.py --workload rf-esa.steady --rates 500,1000,2000 \
        --seconds 8 --seed 1 [--out sweep.jsonl]

One process sets the cell up once and offers each rate in turn, for
``--seconds`` each, with the cell's mix and only its rate changed.  A rate
is kept up when no request was refused and the backlog did not grow: the
median latency of the last fifth of the requests is under twice that of
the first fifth plus 5 ms, and the rows answered per second reach 97% of
the rows offered.  Not a cell: it prints one JSON line per rate and the
knee, and writes them to ``--out``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def summarize(window, rate: float) -> dict:
    recs = window.records
    done = [r for r in recs if r.ok]
    lat = np.asarray([r.latency_s for r in done]) * 1e3
    fifth = max(1, len(done) // 5)
    by_due = sorted(done, key=lambda r: r.t_due)
    first = np.median([r.latency_s for r in by_due[:fifth]]) * 1e3
    last = np.median([r.latency_s for r in by_due[-fifth:]]) * 1e3
    end = max(r.t_done for r in done)
    offered = sum(r.rows for r in recs) / window.seconds
    answered = sum(r.rows for r in done) / (end - window.t0)
    refused = sum(1 for r in recs if r.refused)
    kept = refused == 0 and last < 2 * first + 5 and answered >= 0.97 * offered
    return {"rate_rps": rate, "requests": len(recs), "refused": refused,
            "offered_rows_per_s": offered, "answered_rows_per_s": answered,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "p50_first_fifth_ms": float(first), "p50_last_fifth_ms": float(last),
            "gen_late_p99_ms": float(np.percentile([r.late_s for r in recs], 99)) * 1e3,
            "kept_up": bool(kept)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.catalog import Catalog, use_compile_cache
    from bench.harness import set_up

    use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("bench/sweep.py: no TPU; the sweep runs only on the chip",
              file=sys.stderr)
        return 1
    catalog = Catalog()
    cell = catalog.cell(args.workload)
    if cell.traffic["kind"] not in ("poisson", "onoff"):
        print(f"{cell.name} is not an open-loop mix", file=sys.stderr)
        return 2
    served = set_up(cell, catalog, args.seed)
    rates = [float(r) for r in args.rates.split(",")]

    async def go():
        out = []
        try:
            for i, rate in enumerate(rates):
                mix = dict(cell.traffic, rate_rps=rate)
                # streams of their own per rate: no row is seen twice
                await served.drive(served.load, mix, 1.0, args.seed, 200 + i)
                window = await served.drive(served.load, mix, args.seconds,
                                            args.seed, 100 + i)
                out.append(summarize(window, rate))
                print(json.dumps(out[-1]), flush=True)
                await asyncio.sleep(0.5)  # let a backlog drain
        finally:
            await served.gateway.close()
        return out

    t = time.perf_counter()
    rows = asyncio.run(go())
    kept = [r["rate_rps"] for r in rows if r["kept_up"]]
    knee = {"workload": cell.name, "knee_rps": max(kept) if kept else None,
            "seconds_per_rate": args.seconds, "sweep_s": time.perf_counter() - t}
    print(json.dumps(knee), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(json.dumps(r) for r in rows + [knee]) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

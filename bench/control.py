"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload rf-esa.steady --seeds 1,2,3 \
        --seconds 3 [--faults 3] [--out control.jsonl]

For each seed, in one process: the cell set up at its own size, a window of
``--seconds`` at the cell's own load, and the numbers the check compares,
read twice over the same sample: once for the program's answers, and once
with the control in its place, the reference with its leaves at 16-bit
fixed point (the precision step below the configuration's 32 bits).  With
``--faults n``, the first ``n`` seeds run again with each fault the served
path can have planted in the program: an answer altered where it is
produced, and half of a batch left out.  Not part of a run of the
benchmark; one JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
FAULTS = ("answer_altered", "half_batch_left_out")


def plant(fault):
    """Break the Pallas backend's partials in place; -> undo()."""
    from repro.backends.pallas import PallasBackend

    sound = PallasBackend.predict_partials

    def predict_partials(self, X):
        acc = np.array(sound(self, X))
        if fault == "answer_altered":
            acc[0, 0] += 1
        else:
            acc[len(acc) // 2:] = 0
        return acc

    PallasBackend.predict_partials = predict_partials
    return lambda: setattr(PallasBackend, "predict_partials", sound)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.catalog import Catalog, use_compile_cache
    from bench.harness import check, run_window, set_up

    use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("bench/control.py: no TPU; readings come only from the chip",
              file=sys.stderr)
        return 1
    catalog = Catalog()
    cell = catalog.cell(args.workload)
    mix = cell.traffic
    lines = []

    def reading(seed, fault=None):
        undo = plant(fault) if fault else (lambda: None)
        try:
            served = set_up(cell, catalog, seed)
            window, _ = run_window(served, mix, args.seconds, seed,
                                   warmup_s=float(mix["warmup_s"]))
        finally:
            undo()
        out = [{"seed": seed, "side": fault or "program",
                **{k: v["value"] for k, v in
                   check(served, window, seed, int(mix["check_rows"])).items()}}]
        if fault is None:
            out.append({"seed": seed, "side": "control", **{
                k: v["value"] for k, v in
                check(served, window, seed, int(mix["check_rows"]), bits=16).items()}})
        return out

    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        for fault in (None,) + (FAULTS if i < args.faults else ()):
            for line in reading(seed, fault):
                lines.append(line)
                print(json.dumps(line), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("".join(json.dumps(x) + "\n" for x in lines))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

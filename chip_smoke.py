"""Chip smoke test: serve the full-width intreeger-rf forest on a TPU.

Drives the objects ``python -m repro.launch.serve --trees --gateway`` drives
(``ModelRegistry`` -> ``Gateway`` -> ``TreeEngine`` -> plan -> backend) at
the widths of ``configs/intreeger_rf.py``: a 128-tree depth-10 forest over
87 features and 8 classes, trained from a seed on Shuttle-like rows and
registered through the ITRF artifact path.  Every response must be
bit-identical to the same forest's reference partials computed on the host
CPU device.

    python chip_smoke.py             # one chip: routes integer:pallas and
                                     # integer:reference
    python chip_smoke.py --chips 4   # only the fused tree_parallel:4 route
                                     # and its one-chip twin

Without a TPU, or outside a checkout of this repository, it exits non-zero
and prints no result.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
MODEL = "intreeger-rf"
ONE_CHIP_ROUTES = ("integer:pallas", "integer:reference")
FOUR_CHIP_ROUTES = ("integer:reference+tree_parallel:4", "integer:reference")
TRAIN_ROWS = 20000
REQUESTS = 48  # per route
LABEL_NOISE = 0.3
# both sides of the Pallas backend's 64-row switch to the gather walk
ROW_CHOICES = (1, 3, 8, 17, 40, 63, 64, 65, 100, 128, 200, 256)


def build_forest(cfg, *, rows: int, seed: int):
    """Train the forest at ``cfg``'s widths; -> (forest, held-out rows).

    Shuttle-like classes separate within a few splits, so a share of the
    labels is redrawn at random: the trees then keep splitting to
    ``cfg.tree_depth`` and the forest takes the configuration's full widths.
    """
    from repro.data.tabular import make_shuttle_like, train_test_split
    from repro.trees.forest import RandomForestClassifier

    X, y = make_shuttle_like(n=rows, n_features=cfg.n_tab_features,
                             n_classes=cfg.n_classes, seed=seed)
    rng = np.random.default_rng(seed)
    y = np.where(rng.random(len(y)) < LABEL_NOISE,
                 rng.integers(0, cfg.n_classes, len(y)), y)
    Xtr, ytr, Xte, _ = train_test_split(X, y, seed=seed)
    rf = RandomForestClassifier(n_estimators=cfg.n_trees,
                                max_depth=cfg.tree_depth, seed=seed)
    return rf.fit(Xtr, ytr), Xte


def widths(model) -> tuple:
    """(trees, depth, features, classes) of a forest IR or a ModelConfig."""
    if hasattr(model, "tree_depth"):
        return (model.n_trees, model.tree_depth, model.n_tab_features,
                model.n_classes)
    return (model.n_trees, model.max_depth, model.n_features, model.n_classes)


def host_reference(ir, X) -> np.ndarray:
    """(B, C) uint32 partials of the jnp reference walk on the host CPU."""
    import jax

    from repro.core.ensemble import predict_partials_mode

    with jax.default_device(jax.devices("cpu")[0]):
        return np.asarray(predict_partials_mode(ir.materialize("padded"), X,
                                                "integer"))


def serve_routes(registry, routes, pool, *, n_requests: int, seed: int,
                 max_batch_rows: int = 256) -> dict:
    """Warm and serve every route through its own Gateway; -> {route:
    {results, warm_s, serve_s, engine}}.  The same seeded requests go to
    each route; the seconds are host wall clock."""
    from repro.launch.serve import run_gateway_workload
    from repro.serve.gateway import Gateway

    out = {}
    for route in routes:
        gateway = Gateway(registry, route, max_batch_rows=max_batch_rows)
        engine = registry.get(MODEL).engine(route)
        t0 = time.perf_counter()
        engine.warm(max_batch_rows)
        warm_s = time.perf_counter() - t0

        async def run():
            try:
                return await run_gateway_workload(
                    gateway, {MODEL: pool}, n_requests=n_requests,
                    rate_hz=200.0, seed=seed, row_choices=ROW_CHOICES)
            finally:
                await gateway.close()

        t0 = time.perf_counter()
        results, rejected = asyncio.run(run())
        serve_s = time.perf_counter() - t0
        if rejected or len(results) != n_requests:
            raise RuntimeError(f"{route}: {len(results)} of {n_requests} "
                               f"served, {rejected} rejected")
        out[route] = dict(results=results, warm_s=warm_s, serve_s=serve_s,
                          engine=engine)
    return out


def check_against_reference(served: dict, ir) -> dict:
    """Per route: do all responses equal the host-CPU reference partials?"""
    rows = [X for r in served.values() for _, X, _ in r["results"]]
    ref = host_reference(ir, np.concatenate(rows))
    verdict, at = {}, 0
    for route, r in served.items():
        same = True
        for _, X, (scores, preds) in r["results"]:
            want = ref[at:at + len(X)]
            at += len(X)
            same &= bool(np.array_equal(np.asarray(scores), want)
                         and np.array_equal(preds, want.argmax(axis=1)))
        verdict[route] = same
    return verdict


def compare_fused(served: dict):
    """The four-chip check: -> (is the tree_parallel:4 plan the fused
    shard_map?, are its responses bit-identical to the one-chip twin's?,
    the device ids of its mesh)."""
    fused, twin = (served[r] for r in FOUR_CHIP_ROUTES)
    same = all(np.array_equal(a[2][0], b[2][0])
               for a, b in zip(fused["results"], twin["results"]))
    plan = fused["engine"].plan
    return bool(plan.fused), same, plan.describe().get("devices", [])


def pallas_executables(ir, n_features: int) -> dict:
    """impl -> does the compiled Pallas program hold a ``tpu_custom_call``
    (a compiled kernel, not the interpreter)?  Lowered on the same tables
    and the two shapes that reach each walk."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import tree_predict_integer

    lm = ir.materialize("leaf_major")
    tables = [jnp.asarray(a) for a in (lm.feature, lm.threshold_key, lm.left,
                                       lm.right, lm.leaf_fixed)]
    found = {}
    for impl, rows in (("leaf_major", 256), ("gather", 8)):
        fn = lambda x, *t, impl=impl: tree_predict_integer(
            x, *t, depth=lm.max_depth, impl=impl,
            internal_counts=lm.internal_counts if impl == "leaf_major" else None)
        x = jnp.zeros((rows, n_features), jnp.int32)
        text = jax.jit(fn).lower(x, *tables).compile().as_text()
        found[impl] = "tpu_custom_call" in text
    return found


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the fused tree_parallel:4 route and "
                         "its one-chip twin")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the data, the forest and the requests")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py must run from a checkout of this repository "
              "(src/repro is missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.device import device_report, enable_compile_cache

    cache_dir = enable_compile_cache()
    device = device_report()
    print(f"devices: {device}")
    if device["platform"] != "tpu":
        print("no TPU found: chip_smoke.py runs only on the chip",
              file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{device['count']}", file=sys.stderr)
        return 1
    print(f"compile cache: {cache_dir}")

    from repro.configs.intreeger_rf import CONFIG
    from repro.ir import ForestIR
    from repro.kernels.tree_traverse import resolve_interpret
    from repro.serve.registry import ModelRegistry

    t0 = time.perf_counter()
    rf, pool = build_forest(CONFIG, rows=TRAIN_ROWS, seed=args.seed)
    ir = ForestIR.from_forest(rf)
    artifact = ROOT / "build" / f"{MODEL}.itrf"
    artifact.parent.mkdir(exist_ok=True)
    ir.to_itrf(str(artifact))
    registry = ModelRegistry()
    registry.register_artifact(MODEL, str(artifact))
    print(f"forest: trained and registered in {time.perf_counter() - t0:.1f}s; "
          f"padded tables (T, N, C) = {ir.materialize('padded').leaf_fixed.shape}")
    if widths(ir) != widths(CONFIG):
        print(f"forest widths {widths(ir)} are not the configuration's "
              f"{widths(CONFIG)}", file=sys.stderr)
        return 1

    routes = FOUR_CHIP_ROUTES if args.chips == 4 else ONE_CHIP_ROUTES
    served = serve_routes(registry, routes, pool, n_requests=REQUESTS,
                          seed=args.seed)
    for route, r in served.items():
        n_rows = sum(len(X) for _, X, _ in r["results"])
        print(f"route {route}: warm (compile) {r['warm_s']:.2f}s over buckets "
              f"{sorted(r['engine'].compiled_buckets)}; served "
              f"{len(r['results'])} requests, {n_rows} rows in "
              f"{r['serve_s']:.2f}s wall; plan {r['engine'].plan.describe()}")
    ok = True
    for route, same in check_against_reference(served, ir).items():
        print(f"bit-identical to host-CPU reference partials: {route}: {same}")
        ok &= same

    if args.chips == 4:
        fused, same, devices = compare_fused(served)
        print(f"fused tree_parallel:4 == one chip (bit-identical): {same}")
        print(f"fused mesh devices: {devices} "
              f"({len(set(devices))} distinct of {device['count']})")
        ok &= fused and same and len(set(devices)) == 4
    else:
        interpret = resolve_interpret(None)
        found = pallas_executables(ir, CONFIG.n_tab_features)
        print(f"pallas interpret mode on this platform: {interpret}")
        print(f"pallas executable holds tpu_custom_call: {found}")
        ok &= (not interpret) and all(found.values())

    if not ok:
        print("chip smoke FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

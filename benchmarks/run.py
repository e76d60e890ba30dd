"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = the figure's headline
quantity).  CPU-backend wall times are used for *relative* comparisons
(float vs FlInt vs integer), mirroring the paper's relative-cycles axis;
absolute TPU projections live in the roofline table (§Roofline).

  Fig. 2  -> accuracy_identity        (pred identity + prob-delta magnitude)
  Fig. 3  -> perf_float_flint_integer (3 impls x 2 datasets x n_trees)
  IV-C    -> instruction_count_proxy  (HLO op counts per impl)
  IV-E    -> memory_footprint         (artifact bytes, MCU-style)
  IV-F    -> energy_model             (paper's E_saved formula)
  kernels -> kernel_identity          (Pallas kernel == oracle, us/row)
  plans   -> plan_scaling             (ns/row vs shard count, tree/row-parallel)
  §Roofline -> roofline_table         (from dry-run artifacts)
"""
from __future__ import annotations

import json
import os
import pathlib
import time

import numpy as np

ART = pathlib.Path(__file__).resolve().parent / "artifacts"
ROWS = []

# REPRO_BENCH_TINY=1 (the CI bench-smoke job) shrinks datasets/forests so the
# full pipeline runs in seconds: numbers are still *reported* but only prove
# every backend executes — perf conclusions need the full-size run.
TINY = bool(int(os.environ.get("REPRO_BENCH_TINY", "0") or "0"))

# REPRO_BENCH_DEVICES=N forces N XLA host-platform devices *before* jax is
# first imported (all jax imports in this harness are lazy), so the
# plan_scaling section can exercise real shard_map tree-parallel execution
# on a CPU-only host — the same trick the CI conformance job uses.
_N_DEV = os.environ.get("REPRO_BENCH_DEVICES")
if _N_DEV and "jax" not in __import__("sys").modules:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={int(_N_DEV)}"
    ).strip()


def emit(name: str, us_per_call: float, derived: str):
    row = f"{name},{us_per_call:.3f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def host_info() -> dict:
    """The machine the numbers were taken on: every perf row in the JSON is
    meaningless without the CPU, its SIMD capabilities, the core count, and
    the compiler that built the C backends."""
    import platform
    import shutil
    import subprocess

    info = {
        "machine": platform.machine(),
        "cores": os.cpu_count(),
        "python": platform.python_version(),
    }
    cpu_model, flags = None, ""
    try:  # /proc/cpuinfo: "model name" on x86, "Features"/"flags" lists ISA
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                low = line.lower()
                if cpu_model is None and low.startswith("model name"):
                    cpu_model = line.split(":", 1)[1].strip()
                elif low.startswith(("flags", "features")):
                    flags = line.split(":", 1)[1]
    except OSError:
        pass
    info["cpu"] = cpu_model or platform.processor() or "unknown"
    fl = set(flags.split())
    info["avx2"] = "avx2" in fl
    info["neon"] = bool({"neon", "asimd"} & fl)
    info["gcc"] = None
    if shutil.which("gcc"):
        try:
            out = subprocess.run(["gcc", "--version"], capture_output=True,
                                 text=True, timeout=10).stdout
            info["gcc"] = out.splitlines()[0] if out else None
        except (OSError, subprocess.SubprocessError):
            pass
    return info


def _isa_of(eng) -> str:
    """The SIMD ISA an engine's backend dispatches to ('-' for non-C
    backends and fused plans with no per-shard backend objects)."""
    fn = getattr(eng.backend, "simd_isa", None)
    return (fn() or "-") if fn is not None else "-"


def _time(fn, *args, reps=5, warmup=2):
    for _ in range(warmup):
        fn(*args)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    elif isinstance(out, tuple) and hasattr(out[0], "block_until_ready"):
        out[0].block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6  # us


def _datasets():
    from repro.data.tabular import make_esa_like, make_shuttle_like, train_test_split

    n = 2500 if TINY else 20000
    shuttle = train_test_split(*make_shuttle_like(n=n, seed=0), seed=0)
    esa = train_test_split(*make_esa_like(n=n, seed=0), seed=0)
    return {"shuttle": shuttle, "esa": esa}


def _forest(data, n_trees, depth=7, seed=0):
    from repro.core.packing import pack_forest
    from repro.trees.forest import RandomForestClassifier

    Xtr, ytr, Xte, yte = data
    rf = RandomForestClassifier(n_estimators=n_trees, max_depth=depth, seed=seed).fit(Xtr, ytr)
    return rf, pack_forest(rf), Xte, yte


def accuracy_identity():
    """Fig. 2: integer vs float predictions identical; prob deltas ~n/2^32."""
    from repro.core.ensemble import predict_float, predict_integer
    from repro.core.fixedpoint import fixed_to_prob_np

    for dname, data in _datasets().items():
        for n_trees in (1, 10, 50, 100):
            t0 = time.perf_counter()
            rf, packed, Xte, yte = _forest(data, n_trees, depth=6)
            _, predf = predict_float(packed, Xte)
            acc, predi = predict_integer(packed, Xte)
            identical = bool((np.asarray(predf) == np.asarray(predi)).all())
            oracle = rf.predict_proba(Xte)
            delta = np.abs(
                fixed_to_prob_np(np.asarray(acc), n_trees) - oracle
            ).max()
            us = (time.perf_counter() - t0) * 1e6
            emit(
                f"fig2_identity_{dname}_t{n_trees}",
                us,
                f"identical={identical};max_prob_delta={delta:.3e}",
            )
            assert identical


def perf_float_flint_integer():
    """Fig. 3: relative runtime of float / flint / integer paths."""
    from repro.core.ensemble import make_predict_fn

    for dname, data in _datasets().items():
        for n_trees in (10, 50):
            rf, packed, Xte, yte = _forest(data, n_trees, depth=7)
            Xte = Xte[:4096]
            times = {}
            for mode in ("float", "flint", "integer"):
                fn = make_predict_fn(packed, mode)
                times[mode] = _time(fn, Xte)
            speedup = times["float"] / times["integer"]
            emit(
                f"fig3_perf_{dname}_t{n_trees}_float", times["float"] / len(Xte),
                f"us_per_row",
            )
            emit(
                f"fig3_perf_{dname}_t{n_trees}_flint", times["flint"] / len(Xte),
                f"rel={times['float']/times['flint']:.3f}x",
            )
            emit(
                f"fig3_perf_{dname}_t{n_trees}_integer", times["integer"] / len(Xte),
                f"speedup_vs_float={speedup:.3f}x",
            )


def gbt_identity():
    """GBT support (paper Sec. II-B): integer-only signed-margin
    accumulation agrees with the float GBT on argmax."""
    from repro.trees.gbt import GradientBoostedClassifier, pack_gbt, predict_gbt_integer

    data = _datasets()["shuttle"]
    Xtr, ytr, Xte, yte = data
    t0 = time.perf_counter()
    gbt = GradientBoostedClassifier(n_estimators=12, max_depth=4, seed=0).fit(
        Xtr[:8000], ytr[:8000]
    )
    packed = pack_gbt(gbt)
    pred_f = gbt.predict(Xte[:2000])
    pred_i = predict_gbt_integer(packed, Xte[:2000])
    agree = (pred_f == pred_i).mean()
    acc = (pred_i == yte[:2000]).mean()
    emit(
        "gbt_identity", (time.perf_counter() - t0) * 1e6,
        f"agree={agree:.4f};acc={acc:.4f};scale={packed.scale:.3e}",
    )
    assert agree >= 0.999


def perf_native_c():
    """Fig. 3, faithfully: the emitted if-else C compiled -O3 and timed on
    this host's x86 core — float vs FlInt vs InTreeger, both datasets.
    (The paper's ARM/RISC-V columns need those ISAs; noted in EXPERIMENTS.)"""
    import shutil

    if shutil.which("gcc") is None:
        emit("fig3_native_c", 0, "gcc unavailable; skipped")
        return
    from repro.codegen.native_bench import compile_and_time

    for dname, data in _datasets().items():
        for n_trees in (10, 50):
            rf, packed, Xte, yte = _forest(data, n_trees, depth=7)
            X = Xte[:4096]
            res = {m: compile_and_time(packed, X, m) for m in ("float", "flint", "integer")}
            # all three must agree on every argmax (checksum = sum of classes)
            assert res["float"]["checksum"] == res["integer"]["checksum"] == res["flint"]["checksum"]
            f, fl, i = (res[m]["ns_per_row"] / 1e3 for m in ("float", "flint", "integer"))
            emit(f"fig3c_{dname}_t{n_trees}_float", f, "us_per_row")
            emit(f"fig3c_{dname}_t{n_trees}_flint", fl, f"rel={f/fl:.3f}x")
            emit(
                f"fig3c_{dname}_t{n_trees}_integer", i,
                f"speedup_vs_float={f/i:.3f}x;binary_bytes={res['integer']['binary_bytes']}",
            )


def instruction_count_proxy():
    """IV-C analog: compiled op counts per implementation (no ISA on TPU —
    HLO instruction count is the portable analogue)."""
    import jax
    import jax.numpy as jnp
    from repro.core.ensemble import ensemble_device_arrays, _predict
    from repro.core.flint import float_to_key

    data = _datasets()["shuttle"]
    rf, packed, Xte, yte = _forest(data, 20, depth=6)
    x = jnp.asarray(Xte[:512], jnp.float32)
    counts = {}
    for mode, acc_dtype in (("float", jnp.float32), ("integer", jnp.uint32)):
        arrays = ensemble_device_arrays(packed, mode)
        xx = x if mode == "float" else float_to_key(x)
        lowered = jax.jit(
            lambda a, v: _predict(a, v, packed.max_depth, acc_dtype)
        ).lower(arrays, xx)
        txt = lowered.compile().as_text()
        counts[mode] = sum(1 for l in txt.splitlines() if "=" in l and "%" in l)
    emit(
        "ivc_hlo_ops_float", counts["float"],
        f"integer={counts['integer']};ratio={counts['integer']/counts['float']:.3f}",
    )


def memory_footprint():
    """IV-E analog: deployable artifact size (the MCU had 43.5 kB total),
    now broken out per ForestIR layout — padded tables pay O(T * max_nodes)
    while ragged pays O(sum(nodes)), so the gap widens with depth skew."""
    from repro.codegen.c_emitter import emit_c

    data = _datasets()["shuttle"]
    rf, packed, Xte, _ = _forest(data, 30, depth=5)  # the paper's MCU config
    int_bytes = packed.nbytes_integer()
    float_bytes = packed.nbytes_float()
    c_src = len(emit_c(packed, mode="integer").encode())
    emit(
        "ive_artifact_bytes", int_bytes,
        f"float_bytes={float_bytes};ratio={int_bytes/float_bytes:.3f};c_source={c_src}",
    )
    per_layout = packed.ir.nbytes_by_layout(mode="integer")
    emit(
        "ive_bytes_per_layout", per_layout["padded"],
        ";".join(f"{name}={nb}" for name, nb in sorted(per_layout.items()))
        + f";ragged_saving={1 - per_layout['ragged']/per_layout['padded']:.3f}",
    )


def energy_model():
    """IV-F: the paper's E_saved formula with measured runtime ratio.

    The paper measured T_float=19.36s, T_int=7.79s, P_high=2.81W,
    P_low=1.81W -> 21.3% saved.  We plug OUR measured runtimes into the SAME
    formula with the paper's power constants (no power meter in container).
    """
    import shutil

    data = _datasets()["shuttle"]
    rf, packed, Xte, yte = _forest(data, 50, depth=7)  # paper's energy config
    Xte = Xte[:4096]
    if shutil.which("gcc"):
        # the faithful measurement: emitted if-else C at -O3 (paper IV-F)
        from repro.codegen.native_bench import compile_and_time

        t_float = compile_and_time(packed, Xte, "float")["ns_per_row"]
        t_int = compile_and_time(packed, Xte, "integer")["ns_per_row"]
    else:
        from repro.core.ensemble import make_predict_fn

        t_float = _time(make_predict_fn(packed, "float"), Xte)
        t_int = _time(make_predict_fn(packed, "integer"), Xte)
    p_high, p_low = 2.81, 1.81
    e_saved = 1 - (t_int * p_high + (t_float - t_int) * p_low) / (t_float * p_high)
    emit(
        "ivf_energy_saved", t_int,
        f"t_float={t_float:.1f};t_int={t_int:.1f};E_saved={e_saved*100:.1f}%"
        f";paper=21.3%",
    )
    # paper's own constants reproduce the paper's number (formula check)
    e_paper = 1 - (7.79 * 2.81 + (19.36 - 7.79) * 1.81) / (19.36 * 2.81)
    assert abs(e_paper - 0.213) < 0.005


def kernel_identity():
    """Pallas kernel (interpret mode) == jnp oracle; per-row cost of the jnp
    deployment path (interpret-mode kernel timing is not meaningful)."""
    from repro.backends.pallas import PallasBackend
    from repro.core.ensemble import make_predict_fn

    data = _datasets()["shuttle"]
    rf, packed, Xte, _ = _forest(data, 16, depth=6)
    Xte = Xte[:1024]
    fn = make_predict_fn(packed, "integer")
    scores_ref, _ = fn(Xte)
    scores_k = PallasBackend(packed, "integer").predict_partials(Xte)
    same = bool((np.asarray(scores_ref) == np.asarray(scores_k)).all())
    us = _time(fn, Xte, reps=3)
    emit("kernel_identity", us / len(Xte), f"bit_identical={same}")
    assert same


def gateway_vs_naive():
    """Gateway throughput vs naive per-request predict on a single-row
    request stream (the motivating workload: millions of independent rows).
    The gateway coalesces the stream into block-shaped batches and serves
    repeated quantized keys from cache; the naive baseline dispatches the
    engine once per request.  Low rates are arrival-bound (wall time is
    dominated by Poisson pacing); ``rate=inf`` is a burst and measures pure
    serving capacity, the apples-to-apples comparison with the closed-loop
    naive baseline."""
    import asyncio

    from repro.launch.serve import run_gateway_workload
    from repro.serve.gateway import Gateway
    from repro.serve.registry import ModelRegistry

    data = _datasets()["shuttle"]
    rf, packed, Xte, _ = _forest(data, 16, depth=6)
    reg = ModelRegistry()
    mv = reg.register_packed("shuttle", packed)
    eng = mv.engine("integer")
    eng.warm(64)  # compile shape buckets so jit doesn't skew either side

    # reference: the bare engine loop (no server at all), one call per row
    t0 = time.perf_counter()
    for i in range(200):
        eng.predict(Xte[i:i + 1])
    bare_rows_per_s = 200 / (time.perf_counter() - t0)

    def run_server(rate, batched: bool):
        # naive = same async server, but no coalescing and no cache
        gw = Gateway(reg, mode="integer",
                     max_batch_rows=64 if batched else 1,
                     max_delay_ms=4.0 if batched else 0.0,
                     max_queue_rows=8192,
                     cache_rows=65536 if batched else 0)
        t0 = time.perf_counter()
        results, rejected = asyncio.run(run_gateway_workload(
            gw, {"shuttle": Xte}, n_requests=400, rate_hz=rate,
            seed=17, row_choices=(1,),
        ))
        dt = time.perf_counter() - t0
        st = gw.stats()["per_model"]["shuttle"]
        asyncio.run(gw.close())
        rows = sum(len(X) for _, X, _ in results)
        return rows, dt, st, rejected

    def stage_cols(st):
        # always-on per-stage attribution (mean wall ms per sample); NaN
        # (no samples for a stage) renders as the literal "nan", fine in CSV
        return (f"queue_ms={st['queue_ms']:.3f};pad_ms={st['pad_ms']:.3f};"
                f"shard_ms={st['shard_ms']:.3f};finalize_ms={st['finalize_ms']:.3f}")

    for rate in (500.0, 2000.0, float("inf")):
        rows, gw_dt, st, rejected = run_server(rate, batched=True)
        n_rows, n_dt, n_st, n_rej = run_server(rate, batched=False)
        tag = "inf" if rate == float("inf") else str(int(rate))
        emit(
            f"gateway_rate{tag}", gw_dt / max(rows, 1) * 1e6,
            f"rows_per_s={rows/gw_dt:.0f};naive_rows_per_s={n_rows/n_dt:.0f};"
            f"speedup_vs_naive={(n_dt/n_rows)/(gw_dt/rows):.2f}x;"
            f"bare_loop_rows_per_s={bare_rows_per_s:.0f};"
            f"occupancy={st['batch_occupancy']:.1f};hit_rate={st['cache_hit_rate']:.2f};"
            f"p95_ms={st['p95_ms']:.2f}(naive={n_st['p95_ms']:.2f});"
            f"rejected={rejected}(naive={n_rej});" + stage_cols(st),
        )


def gateway_stage_breakdown():
    """Where a traced request's wall time goes, from actual span trees: one
    fully-traced burst through the gateway, stage totals aggregated from the
    per-request spans (queue wait, cache probe, pad, shard execute, merge,
    finalize, stitch).  Runs separately from ``gateway_vs_naive`` so the
    timed comparison rows stay untraced."""
    import asyncio

    from repro.launch.serve import run_gateway_workload
    from repro.obs import Tracer, request_trees
    from repro.serve.gateway import Gateway
    from repro.serve.registry import ModelRegistry

    data = _datasets()["shuttle"]
    rf, packed, Xte, _ = _forest(data, 16, depth=6)
    reg = ModelRegistry()
    mv = reg.register_packed("shuttle", packed)
    mv.engine("integer").warm(64)

    tracer = Tracer(sample=1.0)
    gw = Gateway(reg, mode="integer", max_batch_rows=64, max_delay_ms=4.0,
                 max_queue_rows=8192, tracer=tracer)
    t0 = time.perf_counter()
    results, _ = asyncio.run(run_gateway_workload(
        gw, {"shuttle": Xte}, n_requests=200, rate_hz=float("inf"),
        seed=17, row_choices=(1,),
    ))
    dt = time.perf_counter() - t0
    asyncio.run(gw.close())

    trees = request_trees(tracer.spans())

    def fold(node, acc):
        # batch children are shared across riders; folding per tree counts
        # each request's view of its stages, which is the per-request story
        key = node["name"].split(":")[0]  # shard:s0[...] -> shard
        acc[key] = acc.get(key, 0.0) + node["dur_ms"]
        for c in node["children"]:
            fold(c, acc)
        return acc

    totals: dict = {}
    for t in trees:
        fold(t, totals)
    n = max(len(trees), 1)
    req_ms = totals.pop("request", 0.0)
    stages = ";".join(f"{k}_ms={v / n:.3f}" for k, v in sorted(totals.items()))
    emit(
        "gateway_stage_breakdown", dt / max(len(results), 1) * 1e6,
        f"traced_requests={len(trees)};request_ms={req_ms / n:.3f};" + stages,
    )


def backend_matrix():
    """Backend axis: one model served through every registered backend *and
    execution variant* at several batch sizes, per-backend ns/row.

    ``reference`` and ``pallas`` are jitted JAX on the host backend (pallas
    runs in interpret mode on CPU, so its absolute time is not meaningful —
    identity is the point; the gather-vs-linear-scan comparison is about op
    structure).  The pallas rows cover both walk strategies: per-depth
    gathers over ``padded`` tables vs the leaf_major linear scan.
    ``native_c`` is the paper's emitted if-else C and ``native_c_table`` the
    ragged-layout table-walk C, benchmarked scalar (``block_rows=1``) vs
    row-blocked (``block_rows=8``), all compiled -O2 into shared libraries
    and driven through ctypes.  All integer scores must be bit-identical
    across every route (the conformance property the IR/backend layers are
    anchored on).

    The shuttle forest is small enough to live in cache, which flatters the
    speculative scalar walk; the ``deep`` rows rerun blocked-vs-scalar on a
    deeper, harder forest (the regime the row-blocking literature targets),
    where the blocked walk's branch-free lockstep chains win.
    """
    from repro.backends import have_c_toolchain
    from repro.serve.engine import TreeEngine

    ds = _datasets()
    rf, packed, Xte, _ = _forest(ds["shuttle"], 4 if TINY else 16,
                                 depth=4 if TINY else 6)
    have_gcc = have_c_toolchain()
    # (route tag, backend, engine kwargs)
    routes = [
        ("reference", "reference", {}),
        ("pallas[gather]", "pallas",
         {"layout": "padded", "backend_kwargs": {"impl": "gather"}}),
        ("pallas[leaf_major]", "pallas",
         {"layout": "leaf_major", "backend_kwargs": {"impl": "leaf_major"}}),
    ]
    if have_gcc:
        routes += [
            ("native_c", "native_c", {}),
            ("native_c_table[block_rows=1]", "native_c_table",
             {"backend_kwargs": {"block_rows": 1}}),
            ("native_c_table[block_rows=8]", "native_c_table",
             {"backend_kwargs": {"block_rows": 8}}),
        ]
    else:
        emit("backend_matrix_native_c", 0,
             "gcc unavailable; native_c + native_c_table skipped")

    batches = (32, 64) if TINY else (64, 256, 1024)
    probe = Xte[: batches[-1]]
    ref_scores = None
    ns_row = {}
    for tag, name, kwargs in routes:
        eng = TreeEngine(packed, mode="integer", backend=name, **kwargs)
        scores, _ = eng.predict_scores(probe)
        if ref_scores is None:
            ref_scores = scores
        else:
            assert (scores == ref_scores).all(), f"{tag} diverged from reference"
        for batch in batches:
            X = Xte[:batch]
            us = _time(eng.predict_scores, X, reps=3)
            ns_row[(tag, batch)] = us * 1e3 / batch
            emit(
                f"backend_{tag}_b{batch}", us,
                f"ns_per_row={us * 1e3 / batch:.1f};layout={eng.layout};"
                f"isa={_isa_of(eng)};buckets={sorted(eng.compiled_buckets)}",
            )

    # small-batch guard for the tiny-batch Pallas fix: pick_blocks shrinks
    # block_t (and the leaf_major wrapper falls back to the gather walk)
    # below _SMALL_BATCH_GATHER_ROWS, so the smallest batch's ns/row must
    # stay within 3x of the next batch up.  BENCH_7 measured a 3.4x cliff
    # (131us/row at b32 vs 38us at b64) before the fix; interpret-mode
    # per-call overhead alone accounts for ~2x at half the rows.
    for tag in ("pallas[gather]", "pallas[leaf_major]"):
        small, nxt = ns_row[(tag, batches[0])], ns_row[(tag, batches[1])]
        assert small <= 3.0 * nxt, (
            f"{tag} small-batch cliff: b{batches[0]}={small:.0f}ns/row vs "
            f"b{batches[1]}={nxt:.0f}ns/row (> 3x)")

    if have_gcc:
        # blocked-vs-scalar where row blocking actually bites: a deep forest
        # whose walks defeat branch prediction and exceed the fast caches.
        # Three table-walk builds of the same artifact: the scalar per-row
        # while loop, the blocked walk with SIMD pinned off, and the full
        # blocked walk (runtime-dispatched AVX2/NEON) — the last pair is the
        # simd-vs-scalar comparison the interleaved gather walker must win.
        # even TINY keeps this forest genuinely deep.  Trained on structured
        # data the trees come out imbalanced — most paths terminate well
        # short of max_depth and the gather walker has little latency to
        # hide — so the deep rows train on featureless gaussian data, which
        # fills the depth budget with balanced trees: every walk is
        # max_depth dependent loads, the regime the SIMD interleave targets.
        from repro.core.packing import pack_forest
        from repro.trees.forest import RandomForestClassifier
        drng = np.random.default_rng(3)
        n_df = 16
        dXtr = drng.standard_normal((4000, n_df)).astype(np.float32)
        dytr = drng.integers(0, 5, 4000)
        drf = RandomForestClassifier(
            n_estimators=24 if TINY else 60, max_depth=10 if TINY else 12,
            seed=3).fit(dXtr, dytr)
        dpacked = pack_forest(drf)
        dXte = drng.standard_normal((1024, n_df)).astype(np.float32)
        engs = {
            "rows": TreeEngine(dpacked, mode="integer",
                               backend="native_c_table",
                               backend_kwargs={"block_rows": 1}),
            "scalar": TreeEngine(dpacked, mode="integer",
                                 backend="native_c_table",
                                 backend_kwargs={"simd": False}),
            "simd": TreeEngine(dpacked, mode="integer",
                               backend="native_c_table"),
        }
        outs = {k: e.predict_scores(dXte[:64])[0] for k, e in engs.items()}
        for k in ("scalar", "simd"):
            assert (outs[k] == outs["rows"]).all(), \
                f"{k} table walk diverged from the per-row walk"
        # compiled C pays no per-shape XLA compile, so even the TINY smoke
        # run can measure at the batch sizes the simd-vs-scalar claim is
        # made for (>= 256 rows; tiny batches are timer noise on CI hosts)
        dbatches = (256, 1024) if TINY else (64, 256, 1024)
        for batch in dbatches:
            X = dXte
            while len(X) < batch:
                X = np.concatenate([X, dXte])
            X = X[:batch]
            t_rows = _time(engs["rows"].predict_scores, X, reps=10)
            t_scalar = _time(engs["scalar"].predict_scores, X, reps=10)
            t_simd = _time(engs["simd"].predict_scores, X, reps=10)
            emit(
                f"backend_deep_table_simd_b{batch}", t_simd,
                f"ns_per_row={t_simd * 1e3 / batch:.1f};"
                f"isa={_isa_of(engs['simd'])};"
                f"scalar_blocked_ns_per_row={t_scalar * 1e3 / batch:.1f};"
                f"per_row_ns_per_row={t_rows * 1e3 / batch:.1f};"
                f"simd_speedup_vs_scalar_blocked={t_scalar / t_simd:.2f}x;"
                f"blocked_speedup_vs_per_row={t_rows / t_scalar:.2f}x",
            )


def backend_bitvector():
    """QuickScorer crossover: the bitvector backends against every node-walk
    backend in the regime the QuickScorer line of work targets — many trees,
    shallow depth, large batches.  There the per-row tree walk pays T root
    dispatches and mispredicted branches per row, while the bitvector scorer
    streams sorted threshold tables shared by the whole 8-row block.  The
    forest is wide enough that the if-else translation unit also falls out
    of the instruction cache — the regime where data-as-arrays must win.

    Every route is asserted bit-identical before timing, and the summary row
    reports whether the best bitvector backend beat every other backend on
    this host (the crossover claim, checked live).
    """
    from repro.backends import have_c_toolchain
    from repro.serve.engine import TreeEngine

    data = _datasets()["shuttle"]
    # the crossover regime needs real width even in the smoke pass — depth-3
    # trees train in seconds, and batch >= 1024 is where the claim lives
    # (the TINY test split is smaller than the batch, so rows are tiled;
    # prediction cost does not care about row uniqueness)
    # T=1200 even in TINY: at T=600 the if-else C's translation unit still
    # fits the instruction cache and sits within host-noise distance of the
    # bitvector scorer; doubling the forest pushes it out (and widens the
    # margin over the table walk), so the crossover verdict is stable on a
    # noisy shared CI core.  Depth-3 trees keep the training cost ~seconds.
    n_trees, depth = 1200, 3
    batch = 1024 if TINY else 2048
    rf, packed, Xte, _ = _forest(data, n_trees, depth=depth)
    X = np.tile(Xte, (batch // len(Xte) + 1, 1))[:batch] \
        if len(Xte) < batch else Xte[:batch]
    routes = [("reference", "reference", {}),
              ("bitvector", "bitvector", {})]
    if have_c_toolchain():
        routes += [("native_c", "native_c", {}),
                   ("native_c_table", "native_c_table", {}),
                   ("native_c_bitvector", "native_c_bitvector", {})]
    else:
        emit("bitvector_native_c", 0, "gcc unavailable; C routes skipped")
    engines, builds, ref_scores = {}, {}, None
    for tag, name, kwargs in routes:
        t0 = time.perf_counter()
        eng = TreeEngine(packed, mode="integer", backend=name, **kwargs)
        scores, _ = eng.predict_scores(X[:64])
        builds[tag] = time.perf_counter() - t0
        if ref_scores is None:
            ref_scores = scores
        else:
            assert (scores == ref_scores).all(), f"{tag} diverged"
        engines[tag] = eng
    # interleaved min-of-rounds timing: on a noisy shared host a transient
    # slowdown (CPU steal, frequency dip) lasting one measurement would land
    # entirely on whichever engine happened to be under the timer, flipping
    # the crossover verdict run to run.  Cycling the engines per round and
    # keeping each engine's best round measures the machine's capability,
    # not its worst moment.
    times = {tag: float("inf") for tag in engines}
    for _ in range(3):
        for tag, eng in engines.items():
            times[tag] = min(times[tag], _time(eng.predict_scores, X, reps=3))
    for tag, us in times.items():
        emit(
            f"bitvector_{tag}_t{n_trees}d{depth}_b{batch}", us,
            f"ns_per_row={us * 1e3 / batch:.1f};isa={_isa_of(engines[tag])};"
            f"build_s={builds[tag]:.1f}",
        )
    bv_routes = {t for t in times if "bitvector" in t}
    others = {t: u for t, u in times.items() if t not in bv_routes}
    if others:
        best_bv = min(bv_routes, key=times.get)
        best_other = min(others, key=others.get)
        emit(
            f"bitvector_crossover_t{n_trees}d{depth}_b{batch}",
            times[best_bv],
            f"winner={best_bv if times[best_bv] < others[best_other] else best_other};"
            f"best_bitvector={best_bv}:{times[best_bv] * 1e3 / batch:.1f}ns;"
            f"best_other={best_other}:{others[best_other] * 1e3 / batch:.1f}ns;"
            f"bitvector_wins={times[best_bv] < others[best_other]}",
        )


def plan_scaling():
    """Execution-plan axis: ns/row vs shard count, tree- and row-parallel.

    Tree-parallel shards a *wide* forest (the tree scan dominates, so carving
    it across devices is the win the paper's associative integer sum makes
    lossless); with ``REPRO_BENCH_DEVICES=8`` the reference shards run as one
    ``shard_map`` over forced host devices (the CI configuration), otherwise
    as a thread pool of sub-forest backends.  Row-parallel shards the batch
    on the same model.  Every plan's scores are asserted bit-identical to
    the single-shard baseline before timing — the conformance property, live
    in the bench.
    """
    import jax

    from repro.serve.engine import TreeEngine

    data = _datasets()["shuttle"]
    # wide & shallow: many trees, small per-tree walk — the tree-parallel
    # regime (depth keeps the padded tables tiny so S copies stay cheap)
    rf, packed, Xte, _ = _forest(data, 24 if TINY else 96, depth=4 if TINY else 6)
    batch = 256 if TINY else 2048
    X = Xte[:batch]

    single = TreeEngine(packed, mode="integer")
    single.warm(batch)
    s_ref, _ = single.predict_scores(X)
    t_single = _time(single.predict_scores, X, reps=3)
    emit(
        f"plan_single_b{batch}", t_single,
        f"ns_per_row={t_single * 1e3 / batch:.1f};shards=1;"
        f"devices={len(jax.devices())}",
    )

    for plan, shard_counts in (("tree_parallel", (2, 4, 8)),
                               ("row_parallel", (2, 4))):
        for shards in shard_counts:
            eng = TreeEngine(packed, mode="integer", plan=plan, shards=shards)
            eng.warm(batch)
            s, _ = eng.predict_scores(X)
            assert (np.asarray(s) == np.asarray(s_ref)).all(), \
                f"{plan}({shards}) diverged from single-shard"
            us = _time(eng.predict_scores, X, reps=3)
            fused = bool(getattr(eng.plan, "fused", False))
            emit(
                f"plan_{plan}_s{shards}_b{batch}", us,
                f"ns_per_row={us * 1e3 / batch:.1f};"
                f"speedup_vs_single={t_single / us:.2f}x;"
                f"fused={fused};shards={eng.n_shards}",
            )


def remote_scaleout():
    """Scale-out axis: rows/s vs loopback worker *process* count.

    The remote_tree_parallel plan ships tree shards to worker processes over
    the ITRG wire protocol and merges their uint32 partials at the gateway —
    the paper's associative integer sum across machine boundaries.  Before
    timing, every worker count's merged output is asserted bit-identical to
    the single-process walk; a final pass re-asserts it for flint AND
    integer *after a forced worker kill mid-request* (straggler re-dispatch
    to the survivor).
    """
    import threading

    from repro.serve.engine import TreeEngine
    from repro.serve.worker import spawn_local_workers

    data = _datasets()["shuttle"]
    rf, packed, Xte, _ = _forest(data, 24 if TINY else 96,
                                 depth=4 if TINY else 6)
    batch = 256 if TINY else 2048
    X = Xte[:batch]
    batch = len(X)

    single = TreeEngine(packed, "integer")
    single.warm(batch)
    s_ref, p_ref = single.predict_scores(X)
    t_single = _time(single.predict_scores, X, reps=3)
    emit(
        f"remote_single_b{batch}", t_single,
        f"ns_per_row={t_single * 1e3 / batch:.1f};workers=0",
    )

    for n in (1, 2, 4):
        eng = TreeEngine(
            packed, f"integer:reference+remote_tree_parallel:{n}",
            plan_kwargs={"workers": n, "model_id": "bench", "version": 1},
        )
        eng.warm(batch)
        s, p = eng.predict_scores(X)
        assert (np.asarray(s) == np.asarray(s_ref)).all() \
            and (np.asarray(p) == np.asarray(p_ref)).all(), \
            f"remote({n} workers) diverged from single-process"
        us = _time(eng.predict_scores, X, reps=3)
        eng.close()
        emit(
            f"remote_scaleout_w{n}_b{batch}", us,
            f"ns_per_row={us * 1e3 / batch:.1f};"
            f"rows_per_s={batch / (us / 1e6):.0f};workers={n};"
            f"speedup_vs_single={t_single / us:.2f}x",
        )

    # conformance under failure: one worker stalls and is killed mid-request;
    # its shard re-dispatches to the survivor, output must not change by a bit
    Xk = X[:min(128, batch)]
    for mode in ("flint", "integer"):
        ref = TreeEngine(packed, mode).predict_scores(Xk)
        procs, addrs = spawn_local_workers(2, delays=[2000, 0])
        try:
            eng = TreeEngine(
                packed, f"{mode}:reference+remote_tree_parallel:2",
                plan_kwargs={"workers": addrs, "model_id": "bench",
                             "version": 1},
            )
            killer = threading.Timer(0.3, procs[0].kill)
            killer.start()
            try:
                s, p = eng.predict_scores(Xk)
            finally:
                killer.cancel()
            identical = bool((np.asarray(s) == np.asarray(ref[0])).all()
                             and (np.asarray(p) == np.asarray(ref[1])).all())
            assert identical, f"{mode}: kill/re-dispatch changed the output"
            emit(
                f"remote_kill_redispatch_{mode}", 0.0,
                f"identical={identical};redispatches={eng.plan.redispatches}",
            )
            eng.close()
        finally:
            for p_ in procs:
                if p_.poll() is None:
                    p_.kill()
                if p_.stdout is not None:
                    p_.stdout.close()


def coldstart_swap():
    """Artifact axis: registry cold-start and hot-swap through the ITRF
    binary artifact vs the JSON boundary.

    ``register_json`` pays JSON parse + requantization on every load;
    ``register_artifact`` is an mmap + header parse — the arrays are
    zero-copy views over page cache, materialized per backend layout only
    when an engine is built.  Cold-start is min-of-5 on *fresh* registries
    (no artifact cache); hot-swap re-registers the same already-mapped path
    and must reuse the mapped ForestIR outright.  Serving identity and the
    packed_leaf < bitvector byte claim are asserted live, so BENCH_10-style
    snapshots can be diffed on all three headline numbers.
    """
    from repro.ir import ForestIR
    from repro.serve.registry import ModelRegistry
    from repro.trees.io import forest_to_json

    data = _datasets()["shuttle"]
    # even TINY keeps T=32: JSON parse cost scales with node count while the
    # mmap load is O(header), so a wider forest keeps the >= 5x claim far
    # from timer noise on shared CI cores
    n_trees, depth = (32, 9) if TINY else (120, 10)
    rf, packed, Xte, _ = _forest(data, n_trees, depth=depth)
    js = forest_to_json(rf)
    ir = ForestIR.from_forest(rf)
    ART.mkdir(parents=True, exist_ok=True)
    path = str(ART / "coldstart.itrf")
    info = ir.to_itrf(path)

    # warm both boundaries once so neither pays first-import costs under
    # the timer, then min-of-5 cold loads on fresh registries
    warm = ModelRegistry()
    warm.register_json("warm", js)
    warm.register_artifact("warm", path)
    t_json = t_art = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        ModelRegistry().register_json("m", js)
        t_json = min(t_json, time.perf_counter() - t0)
        t0 = time.perf_counter()
        ModelRegistry().register_artifact("m", path)
        t_art = min(t_art, time.perf_counter() - t0)
    ratio = t_json / t_art
    emit("coldstart_register_json", t_json * 1e6,
         f"load_ms={t_json * 1e3:.3f};json_bytes={len(js)}")
    emit("coldstart_register_artifact", t_art * 1e6,
         f"load_ms={t_art * 1e3:.3f};file_bytes={info['file_bytes']};"
         f"speedup_vs_json={ratio:.1f}x")
    assert ratio >= 5.0, (
        f"register_artifact only {ratio:.1f}x faster than register_json "
        f"({t_art * 1e3:.3f} ms vs {t_json * 1e3:.3f} ms)")

    # hot-swap: a new version of an already-mapped artifact must reuse the
    # mapped ForestIR (page-cache pages), and the swap cost lands in the
    # engine's compile/warm ledger under the "load" bucket
    reg = ModelRegistry()
    mv1 = reg.register_artifact("m", path)
    t0 = time.perf_counter()
    mv2 = reg.register_artifact("m", path)
    t_swap = time.perf_counter() - t0
    reused = mv2.packed is mv1.packed
    eng = mv2.engine("integer")
    buckets = dict(eng.drain_compile_timings())
    emit("coldstart_hot_swap", t_swap * 1e6,
         f"swap_ms={t_swap * 1e3:.3f};mapped_ir_reused={reused};"
         f"load_bucket_ms={buckets.get('load', 0.0):.3f}")
    assert reused, "hot-swap of an already-mapped artifact re-read the file"
    assert "load" in buckets, "swap latency missing from the engine ledger"

    # serving identity across the boundary: artifact engine == json engine
    X = Xte[:256]
    mv_j = ModelRegistry().register_json("j", js)
    same = bool(np.array_equal(np.asarray(eng.predict(X)),
                               np.asarray(mv_j.engine("integer").predict(X))))
    assert same, "artifact-loaded engine diverged from JSON-loaded engine"

    # IV-E continued: bytes per materialized layout on the bench forest —
    # the packed_leaf group/dictionary codec must beat the bitvector layout
    per_layout = ir.nbytes_by_layout(mode="integer")
    pl, bv = per_layout["packed_leaf"], per_layout["bitvector"]
    emit("coldstart_bytes_per_layout", pl,
         ";".join(f"{k}={v}" for k, v in sorted(per_layout.items()))
         + f";itrf_file={info['file_bytes']};identity={same};"
         f"packed_leaf_saving_vs_bitvector={1 - pl / bv:.3f}")
    assert pl < bv, f"packed_leaf {pl} B not below bitvector {bv} B"


def roofline_table():
    """§Roofline: summarize every dry-run artifact (see EXPERIMENTS.md)."""
    dd = ART / "dryrun"
    if not dd.exists():
        emit("roofline_table", 0, "no dryrun artifacts; run repro.launch.dryrun --all")
        return
    recs = [json.loads(p.read_text()) for p in sorted(dd.glob("*.json"))]
    ok = [r for r in recs if r.get("ok")]
    for r in ok:
        t = r["roofline"]
        emit(
            f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}",
            t["step_time_lb_s"] * 1e6,
            f"dom={t['dominant']};compute_s={t['compute_s']:.3e};"
            f"memory_s={t['memory_s']:.3e};collective_s={t['collective_s']:.3e};"
            f"useful={t['useful_ratio']:.2f};mfu_bound={t['mfu_bound']:.3f}",
        )
    emit("roofline_cells_ok", len(ok), f"total={len(recs)}")


BENCHES = (
    accuracy_identity,
    gbt_identity,
    perf_float_flint_integer,
    perf_native_c,
    instruction_count_proxy,
    memory_footprint,
    energy_model,
    kernel_identity,
    backend_matrix,
    backend_bitvector,
    plan_scaling,
    remote_scaleout,
    gateway_vs_naive,
    gateway_stage_breakdown,
    coldstart_swap,
    roofline_table,
)


def main(argv=None) -> None:
    """Run all benches, or only the ones named on the command line
    (e.g. ``python benchmarks/run.py backend_matrix``)."""
    import sys

    names = list(sys.argv[1:] if argv is None else argv)
    by_name = {fn.__name__: fn for fn in BENCHES}
    unknown = [n for n in names if n not in by_name]
    if unknown:
        raise SystemExit(f"unknown bench(es) {unknown}; have {sorted(by_name)}")
    for fn in [by_name[n] for n in names] or BENCHES:
        fn()
    ART.mkdir(parents=True, exist_ok=True)
    out = ART / "bench_results.csv"
    out.write_text("name,us_per_call,derived\n" + "\n".join(ROWS) + "\n")
    # machine-readable mirror: the CI bench-smoke job uploads this artifact
    records = []
    for row in ROWS:
        name, us, derived = row.split(",", 2)
        records.append({"name": name, "us_per_call": float(us), "derived": derived})
    payload = {"tiny": TINY, "host": host_info(), "results": records}
    out_json = ART / "bench_results.json"
    out_json.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# wrote {out} and {out_json}")
    # REPRO_BENCH_SNAPSHOT=<path>: a repo-root snapshot (``make bench-smoke``
    # writes BENCH_8.json) — the host block plus one ns/row entry per bench
    # row that reports one, so perf regressions diff as plain JSON
    snap_path = os.environ.get("REPRO_BENCH_SNAPSHOT")
    if snap_path:
        ns_rows = {}
        for rec in records:
            for part in rec["derived"].split(";"):
                if part.startswith("ns_per_row="):
                    ns_rows[rec["name"]] = float(part.split("=", 1)[1])
        # coldstart_* rows carry ms/bytes headlines, not ns/row — snapshot
        # their derived strings whole so artifact-load regressions diff too
        cold = {rec["name"]: rec["derived"] for rec in records
                if rec["name"].startswith("coldstart_")}
        snap_payload = {"tiny": TINY, "host": payload["host"],
                        "ns_per_row": ns_rows}
        if cold:
            snap_payload["coldstart"] = cold
        snap = pathlib.Path(snap_path)
        snap.write_text(json.dumps(snap_payload, indent=2) + "\n")
        print(f"# wrote {snap}")


if __name__ == "__main__":
    main()

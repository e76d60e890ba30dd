"""Distribution-layer tests that need >1 device run in subprocesses so the
main pytest process keeps a single CPU device (jax locks device count at
first init)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.launch.mesh import elastic_mesh_shape

REPO = Path(__file__).resolve().parents[1]


def _run(py: str, devices: int = 8, timeout: int = 560) -> str:
    code = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        # Auto-sharded meshes, as the program builds them (importing these
        # does not initialize the jax backend)
        "from jax.sharding import AxisType\n"
        "import jax\n"
        + textwrap.dedent(py)
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=timeout,
        # JAX_PLATFORMS pinned: these children force host-platform devices
        # via XLA_FLAGS, so a bundled libtpu must never probe the cloud
        # metadata service for a TPU (minutes of retry when it blackholes).
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_integer_allreduce_matches_float_psum():
    """The paper-math integer all-reduce: deterministic and within bound."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P
        from repro.train.intreeger_allreduce import integer_psum, quantization_error_bound
        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        x = np.random.default_rng(0).normal(size=(8, 1024)).astype(np.float32)
        def f(xs):
            return integer_psum(xs, "data", 8)
        y = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)
        y = np.asarray(y).reshape(8, -1)[0]
        exact = x.sum(axis=0)
        bound = quantization_error_bound(8, float(np.abs(x).max()))
        print(json.dumps({"max_err": float(np.abs(y - exact).max()), "bound": bound}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["max_err"] <= res["bound"] * 1.01
    assert res["max_err"] < 1e-4


def test_sharded_train_step_matches_single_device():
    """Same batch, same seed: 2x4 mesh loss == single-device loss."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.configs.base import smoke_config
        from repro.models import transformer as tfm
        from repro.sharding import rules
        from repro.sharding.ops import use_mesh
        from repro.train import optimizer as opt
        from repro.train.step import make_train_step
        from repro.data.tokens import pipeline_for

        cfg = smoke_config("granite-3-2b")
        pipe = pipeline_for(cfg, 8, 64)
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        ostate = opt.init_opt_state(params)
        step = make_train_step(cfg, opt.AdamWConfig(lr=1e-3))

        # single device
        p1, o1, m1 = jax.jit(step)(params, ostate, batch)

        # 2x4 mesh
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        with mesh, use_mesh(mesh):
            sh = rules.params_shardings(params, mesh)
            pp = jax.tree.map(jax.device_put, params, sh)
            oo = opt.init_opt_state(pp)
            bsh = rules.batch_shardings(mesh, batch)
            bb = jax.tree.map(jax.device_put, batch, bsh)
            p2, o2, m2 = jax.jit(step)(pp, oo, bb)
        print(json.dumps({"l1": float(m1["loss"]), "l2": float(m2["loss"])}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert abs(res["l1"] - res["l2"]) < 5e-2, res


def test_dryrun_entry_on_small_mesh():
    """run_cell machinery end-to-end on a small config x 8-device mesh."""
    out = _run("""
        import jax, json
        import jax.numpy as jnp
        from repro.configs.base import smoke_config
        from repro.launch import jaxpr_cost
        from repro.launch.hlo_analysis import collective_bytes
        from repro.launch.specs import params_specs
        from repro.models import transformer as tfm
        from repro.sharding import rules
        from repro.sharding.ops import use_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = smoke_config("olmoe-1b-7b")
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        with mesh, use_mesh(mesh):
            shapes = tfm.param_shapes(cfg)
            sh = rules.params_shardings(shapes, mesh)
            params = jax.tree.map(lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h), shapes, sh)
            batch = {
                "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32, sharding=NamedSharding(mesh, P("data", None))),
                "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32, sharding=NamedSharding(mesh, P("data", None))),
            }
            fn = lambda p, b: tfm.loss_fn(cfg, p, b)[0]
            jc = jaxpr_cost.analyze(fn, params, batch)
            compiled = jax.jit(fn).lower(params, batch).compile()
            cb = collective_bytes(compiled.as_text())
            ma = compiled.memory_analysis()
        print(json.dumps({"flops": jc["flops"], "coll": cb["total"],
                          "temp": ma.temp_size_in_bytes}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["flops"] > 1e6
    assert res["coll"] > 0  # sharded program must contain collectives
    assert res["temp"] > 0


def test_trip_count_awareness():
    """jaxpr cost scales with scan length; XLA's aggregate does not."""
    out = _run("""
        import jax, jax.numpy as jnp, json
        from repro.launch import jaxpr_cost
        def make(n):
            def f(x, w):
                def body(c, _):
                    return c @ w, None
                y, _ = jax.lax.scan(body, x, None, length=n)
                return y
            return f
        a = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        c1 = jaxpr_cost.analyze(make(1), a, a)
        c10 = jaxpr_cost.analyze(make(10), a, a)
        print(json.dumps({"r": c10["flops"] / c1["flops"]}))
    """, devices=1)
    res = json.loads(out.strip().splitlines()[-1])
    assert 9.0 < res["r"] < 11.0


def test_integer_dp_training_converges():
    """End-to-end: the paper-math integer all-reduce trains as well as the
    exact float path over 25 steps on 8 data shards."""
    out = _run("""
        import jax, jax.numpy as jnp, json
        from repro.configs.base import smoke_config
        from repro.data.tokens import pipeline_for
        from repro.models import transformer as tfm
        from repro.train import optimizer as opt
        from repro.train.step import make_integer_dp_train_step, make_train_step

        cfg = smoke_config("granite-3-2b")
        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        pipe = pipeline_for(cfg, 16, 64)
        ocfg = opt.AdamWConfig(lr=5e-3, warmup_steps=5, total_steps=25)

        def run(step_fn):
            params = tfm.init_params(cfg, jax.random.PRNGKey(0))
            ostate = opt.init_opt_state(params)
            jstep = jax.jit(step_fn, donate_argnums=(0, 1))
            losses = []
            for s in range(25):
                batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(s).items()}
                params, ostate, m = jstep(params, ostate, batch)
                losses.append(float(m["loss"]))
            return losses

        exact = run(make_train_step(cfg, ocfg))
        with mesh:
            integer = run(make_integer_dp_train_step(cfg, mesh, ocfg))
        print(json.dumps({"exact": exact[-1], "integer": integer[-1],
                          "e0": exact[0], "i0": integer[0]}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["integer"] < res["i0"] - 0.2  # clearly descending
    assert abs(res["integer"] - res["exact"]) < 0.15  # tracks the exact path


def test_distributed_attention_matches_local():
    """shard_map attention == local attention across the three layouts."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.models.layers import _attn_core
        from repro.sharding.ops import use_mesh
        rng = np.random.default_rng(0)
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        errs = {}
        # (name, q_shape, kv_shape, kwargs)
        cases = {
          "train_gqa": ((4, 32, 4, 2, 16), (4, 32, 4, 16), dict(causal=True, window=0, q_chunk=8)),
          "decode_mqa_seqshard": ((4, 1, 1, 8, 16), (4, 64, 1, 16),
                                  dict(causal=True, window=0, q_chunk=8, q_offset=40, kv_len=41)),
          "decode_long_batch1": ((1, 1, 4, 2, 16), (1, 128, 4, 16),
                                 dict(causal=True, window=24, q_chunk=8, q_offset=100, kv_len=101)),
        }
        for name, (qs, ks, kw) in cases.items():
            q = jnp.asarray(rng.normal(size=qs), jnp.bfloat16)
            k = jnp.asarray(rng.normal(size=ks), jnp.bfloat16)
            v = jnp.asarray(rng.normal(size=ks), jnp.bfloat16)
            ref = _attn_core(q, k, v, **kw)
            with mesh, use_mesh(mesh):
                got = jax.jit(lambda a,b,c: _attn_core(a,b,c, mesh=mesh, **kw))(q,k,v)
            errs[name] = float(np.abs(np.asarray(ref,np.float32)-np.asarray(got,np.float32)).max())
        print(json.dumps(errs))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    for name, err in res.items():
        assert err < 0.02, (name, err)


def test_tree_serve_step_sharded_matches_local():
    """The pod-scale serving step is bit-identical to the oracle and
    lowers with ZERO collectives (embarrassingly row-parallel)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.core.serving import tree_serve_step
        from repro.core.packing import pack_forest
        from repro.core.flint import float_to_key
        from repro.data.tabular import make_shuttle_like
        from repro.trees.forest import RandomForestClassifier
        from repro.sharding.ops import use_mesh
        from repro.launch.hlo_analysis import collective_bytes

        X, y = make_shuttle_like(n=3000, seed=1)
        rf = RandomForestClassifier(n_estimators=8, max_depth=5, seed=0).fit(X, y)
        packed = pack_forest(rf)
        tables = {k: jnp.asarray(getattr(packed, k)) for k in
                  ("feature", "threshold_key", "left", "right", "leaf_fixed")}
        keys = float_to_key(jnp.asarray(X[:1024]))
        acc_ref, preds_ref = tree_serve_step(tables, keys, packed.max_depth)
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        with mesh, use_mesh(mesh):
            fn = jax.jit(lambda t, x: tree_serve_step(t, x, packed.max_depth))
            acc, preds = fn(tables, keys)
            coll = collective_bytes(fn.lower(tables, keys).compile().as_text())
        same = bool((np.asarray(acc) == np.asarray(acc_ref)).all())
        print(json.dumps({"same": same, "coll": coll["total"]}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["same"]
    assert res["coll"] == 0


def test_elastic_mesh_planner():
    assert elastic_mesh_shape(512) == ((2, 16, 16), ("pod", "data", "model"))
    assert elastic_mesh_shape(256) == ((2, 8, 16), ("pod", "data", "model"))
    # degraded: 480 devices (one host of 32 lost from 512)
    shape, axes = elastic_mesh_shape(480)
    assert np.prod(shape) == 480 and shape[-1] == 16
    # tiny fallback
    shape, axes = elastic_mesh_shape(6, model=16)
    assert np.prod(shape) == 6

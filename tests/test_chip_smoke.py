"""``chip_smoke.py`` and ``repro.launch.device`` without a chip.

The smoke script must refuse (non-zero, no result line) where there is no
TPU or no checkout; its phases — train, register the ITRF artifact, serve
through the Gateway, compare with host-CPU reference partials — are
rehearsed here at the smoke config's widths, on the CPU with Pallas
interpreted, and the four-chip phase on four forced host devices.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

from repro.launch import device  # noqa: E402


def _run(args, cwd, env_extra=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _has_ok_line(stdout):
    return any(line.startswith("{") and '"ok"' in line
               for line in stdout.splitlines())


def test_chip_smoke_refuses_without_tpu(tmp_path):
    out = _run(["chip_smoke.py"], REPO,
               {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert out.returncode != 0
    assert not _has_ok_line(out.stdout)
    assert "no TPU" in out.stderr


def test_chip_smoke_refuses_outside_checkout(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    out = _run(["chip_smoke.py"], tmp_path)
    assert out.returncode != 0
    assert not _has_ok_line(out.stdout)


def _smoke_forest(tmp_path):
    from repro.configs.intreeger_rf import SMOKE
    from repro.ir import ForestIR
    from repro.serve.registry import ModelRegistry

    rf, pool = chip_smoke.build_forest(SMOKE, rows=1500, seed=0)
    ir = ForestIR.from_forest(rf)
    ir.to_itrf(str(tmp_path / "smoke.itrf"))
    registry = ModelRegistry()
    registry.register_artifact(chip_smoke.MODEL, str(tmp_path / "smoke.itrf"))
    return registry, ir, pool


def test_chip_smoke_one_chip_phases_on_cpu(tmp_path):
    from repro.configs.intreeger_rf import SMOKE

    registry, ir, pool = _smoke_forest(tmp_path)
    # label noise grows the trees to the depth limit: the config's widths
    assert chip_smoke.widths(ir) == chip_smoke.widths(SMOKE)
    served = chip_smoke.serve_routes(registry, chip_smoke.ONE_CHIP_ROUTES,
                                     pool, n_requests=12, seed=0)
    assert chip_smoke.check_against_reference(served, ir) == {
        r: True for r in chip_smoke.ONE_CHIP_ROUTES}
    pallas = served["integer:pallas"]["engine"]
    # both sides of the 64-row gather switch were compiled by warm
    assert {1, 32, 64, 256} <= pallas.compiled_buckets
    # interpreted on the CPU: no compiled kernel in the executable
    assert chip_smoke.pallas_executables(ir, ir.n_features) == {
        "leaf_major": False, "gather": False}


def test_chip_smoke_four_chip_phase_on_host_devices():
    code = textwrap.dedent("""
        import json, sys, tempfile
        from pathlib import Path
        sys.path.insert(0, "."); sys.path.insert(0, "src")
        import chip_smoke
        from tests.test_chip_smoke import _smoke_forest
        registry, ir, pool = _smoke_forest(Path(tempfile.mkdtemp()))
        served = chip_smoke.serve_routes(
            registry, chip_smoke.FOUR_CHIP_ROUTES, pool, n_requests=12, seed=0)
        fused, same, devices = chip_smoke.compare_fused(served)
        ref = chip_smoke.check_against_reference(served, ir)
        print(json.dumps({"fused": fused, "same": same, "devices": devices,
                          "ref": all(ref.values())}))
    """)
    out = _run(["-c", code], REPO, {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": str(REPO / "src")})
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res == {"fused": True, "same": True, "devices": [0, 1, 2, 3],
                   "ref": True}


def test_compile_cache_env_dir_wins(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing, and the
    entries of a compile land there."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.device import enable_compile_cache
        print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
    """)
    out = _run(["-c", code], REPO, {
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
        "PYTHONPATH": str(REPO / "src")})
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split() == [str(tmp_path), str(tmp_path)]
    assert any(tmp_path.iterdir())


def test_compile_cache_default_is_fixed_and_git_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = device.enable_compile_cache()
        assert path == jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert Path(path) == REPO / ".jax_cache"
    assert "/.jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_device_report_names_the_platform():
    rep = device.device_report()
    assert rep == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}

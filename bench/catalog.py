"""Find the benchmark's parts by name.

``BENCHMARK.json`` at the checkout's root names every cell, configuration,
traffic mix and metric.  Each of them is a file of its own here:

- ``configs/<config>.json``: one forest deployment (widths, tree-shape rule,
  row generator, route, gateway settings, ``source``/``assumed``/``reduced``);
- ``traffic/<traffic>.json``: one traffic mix, whose ``kind`` names the
  generator ``traffic/<kind>.py``;
- ``rows/<generator>.py``: one row generator, named by a configuration;
- ``metrics/<metric>.py``: one reader per metric, ``read(ctx)``.

A later cell, mix or metric is a new file and a new entry, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one file as a module of its own (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_part_{path.parent.name}_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)  # metric entries
    per_layer: list = field(default_factory=list)   # metric entries


class Catalog:
    """The benchmark's files, resolved against one ``BENCHMARK.json``.

    A part is looked up in ``extra_dir`` first, then in this directory:
    tests put parts of their own there, found by their names alone.
    """

    def __init__(self, benchmark: Path = None, extra_dir: Path = None):
        self.dirs = ([Path(extra_dir)] if extra_dir else []) + [BENCH_DIR]
        self.benchmark_path = Path(benchmark) if benchmark else ROOT / "BENCHMARK.json"
        self.spec = load_json(self.benchmark_path)

    def find(self, kind: str, name: str, suffix: str) -> Path:
        """``<dir>/<kind>/<name><suffix>`` in the first directory holding it."""
        rel = Path(kind) / f"{_checked(name)}{suffix}"
        for d in self.dirs:
            if (d / rel).is_file():
                return d / rel
        raise FileNotFoundError(f"no {rel} under {', '.join(map(str, self.dirs))}")

    # ------------------------------------------------------------ lookups
    def config(self, name: str) -> dict:
        entry = next((c for c in self.spec["configs"] if c["name"] == name), None)
        if entry is None:
            raise KeyError(f"no configuration {name!r} in {self.benchmark_path}")
        cfg = load_json(self.find("configs", name, ".json"))
        cfg.setdefault("name", name)
        return cfg

    def traffic(self, name: str) -> dict:
        mix = load_json(self.find("traffic", name, ".json"))
        mix.setdefault("name", name)
        return mix

    def generator(self, kind: str):
        """``traffic/<kind>.py``: exposes ``async drive(load, mix, seconds,
        seed, stream) -> Window``."""
        return load_module(self.find("traffic", kind, ".py"), kind)

    def rows(self, generator: str):
        """``rows/<generator>.py``: exposes ``Rows(cfg, seed)``."""
        return load_module(self.find("rows", generator, ".py"), generator)

    def reader(self, metric: str):
        """``metrics/<metric>.py``: exposes ``read(ctx) -> float | None``."""
        return load_module(self.find("metrics", metric, ".py"), metric)

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.spec["workloads"] if w["name"] == name), None)
        if entry is None:
            known = ", ".join(w["name"] for w in self.spec["workloads"])
            raise KeyError(f"no workload {name!r}; known: {known}")

        def applies(metric):
            cells = metric.get("workloads")
            return cells is None or name in cells

        return Cell(
            name=name,
            chips=int(entry["chips"]),
            config=self.config(entry["config"]),
            traffic=self.traffic(entry["traffic"]),
            end_to_end=[m for m in self.spec["end_to_end"] if applies(m)],
            per_layer=[m for m in self.spec["per_layer"] if applies(m)],
        )


def use_compile_cache() -> None:
    """Keep every compiled program in the checkout's ``.jax_cache``, a fixed
    path (the path is part of a cached program's key), however short its
    compile: JAX skips compiles under a second by default."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def peaks_for(kind: str, path: Path = None) -> dict:
    """The chip's published peaks, keyed by ``device_kind``.  A device that
    is not in the table is an error: there is no default peak."""
    table = load_json(path or BENCH_DIR / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(known: {', '.join(table)})")
    return table[kind]

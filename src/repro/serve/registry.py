"""Multi-model registry: versioned packed ensembles behind stable model ids.

Models enter through any boundary the repo supports:
  * a trained forest object (``register_forest``),
  * the Treelite-style JSON artifact (``register_json``), i.e. the
    ``trees/io`` exchange format — the path externally-trained models take, or
  * the ITRF binary artifact (``register_artifact``) — the deployment
    boundary: the file is mmap-ed read-only and the version serves zero-copy
    views over the shared pages, so load cost is O(1) in forest size and no
    JSON is parsed.  Re-registering the same (unchanged) artifact file —
    the hot-swap-back case — reuses the already-parsed IR *object*, layouts
    and all, so a swap costs microseconds.  The measured load wall-ms rides
    the compile/warm ledger as the ``"load"`` bucket of the version's first
    engine, next to the remote plan's ``"remote"`` entry.

Each ``register_*`` call creates a new immutable :class:`ModelVersion` and
atomically repoints the model id at it (hot-swap).  In-flight batches formed
against the previous version keep their reference and finish on it; new
requests route to the new version.  Engines are built lazily per (version,
mode, backend, layout) and memoized, so a registry fronts every route —
reference jnp, Pallas kernel, either compiled-C flavor, over any ForestIR
layout the backend walks — with one compile set per version.  The version's
padded tables carry the canonical IR, so every layout materializes from one
quantization.

Retention: superseded versions used to stay resident forever (engines,
compiled C libraries).  The registry now keeps the newest
``retain`` versions per model id (default 2: current + previous, so
in-flight batches on the just-swapped-out version still finish) and
releases anything older — :meth:`ModelVersion.release` closes and drops
every engine.  ``release(model_id, version)`` frees a retained non-current
version explicitly.
"""
from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field

from repro.core.packing import PackedEnsemble, pack_forest
from repro.serve.engine import TreeEngine
from repro.trees.io import forest_from_json


def _freeze(obj):
    """Nested dict/list -> hashable tuples (the plan_kwargs memo-key leg)."""
    if isinstance(obj, dict):
        return tuple(sorted(((k, _freeze(v)) for k, v in obj.items()),
                            key=lambda kv: kv[0]))
    if isinstance(obj, (list, tuple)):
        return tuple(_freeze(v) for v in obj)
    return obj


@dataclass
class ModelVersion:
    model_id: str
    version: int
    packed: PackedEnsemble  # or a ForestIR (register_artifact)
    source: str  # "forest" | "json" | "packed" | "artifact"
    _engines: dict = field(default_factory=dict, repr=False)
    # register_artifact's measured load wall-ms, charged once to the first
    # engine's compile ledger under the "load" bucket
    _load_ms: float = field(default=None, repr=False)
    released: bool = field(default=False, repr=False)
    # wall-ms spent constructing each route's engine (backend builds, native
    # compiles) — the cold-start cost ``describe()`` surfaces per model
    _build_ms: dict = field(default_factory=dict, repr=False)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def engine(self, spec=None, *, mode: str = None, backend=None,
               layout: str = None, backend_kwargs: dict = None,
               plan: str = None, shards: int = None,
               plan_kwargs: dict = None) -> TreeEngine:
        """The memoized TreeEngine for one route.

        The route is an :class:`~repro.serve.spec.EngineSpec` (object, dict,
        or spec string) — ``engine("integer:bitvector+tree_parallel:4")``;
        a bare mode name (``engine("integer")``) and the loose keyword
        arguments remain as the deprecation-shimmed pre-spec API.

        Within the spec: ``layout=None`` resolves to the backend's
        ``preferred_layout`` (and memoizes under the resolved name, so a
        later explicit request for that layout reuses the same engine); a
        sequence of backend names (heterogeneous tree-parallel) memoizes
        under the tuple.  ``backend_kwargs`` only apply on the call that
        first builds the engine; later lookups for the same route return it
        as-is.  ``plan_kwargs`` carries plan deployment knobs (e.g. the remote
        plan's ``workers``) and participates in the memo key; the remote
        plan additionally receives this version's identity so its handshake
        carries the model id + version.
        """
        from repro.backends import backend_class
        from repro.plan import select_plan
        from repro.serve.spec import MODES, EngineSpec

        if isinstance(spec, str) and spec in MODES and mode is None:
            # a bare mode name is valid under both APIs: alone it is simply
            # the spec string "integer" (no deprecation); combined with loose
            # route kwargs it is the pre-spec positional call
            # engine("integer", backend=...) and goes through the shim
            loose = (backend, layout, plan, shards, backend_kwargs)
            if any(v is not None for v in loose):
                mode, spec = spec, None
        spec = EngineSpec.coerce(spec, caller="ModelVersion.engine",
                                 mode=mode, backend=backend, layout=layout,
                                 plan=plan, shards=shards,
                                 backend_kwargs=backend_kwargs)
        if isinstance(spec.backend, str):
            resolved = spec.layout or \
                backend_class(spec.backend).capabilities.preferred_layout
            backend_key = spec.backend
        else:  # heterogeneous shard spec: memoize under the name tuple
            resolved = spec.layout
            backend_key = tuple(spec.backend) \
                if isinstance(spec.backend, tuple) else spec.backend
        # memoize under the *resolved* plan so plan=None / "auto" / "single"
        # (and their equivalent shard counts) share one engine instead of
        # rebuilding — and recompiling — the same route per alias
        resolved_plan = select_plan(spec.plan, mode=spec.mode,
                                    backend=spec.backend, shards=spec.shards,
                                    model=self.packed)
        key = (spec.mode, backend_key, resolved, resolved_plan,
               None if resolved_plan == "single" else spec.shards,
               _freeze(plan_kwargs))
        with self._lock:
            if self.released:
                raise RuntimeError(
                    f"model {self.model_id!r} v{self.version} was released; "
                    f"route new requests through the registry's current "
                    f"version"
                )
            if key not in self._engines:
                t0 = time.perf_counter()
                pk = dict(plan_kwargs or {})
                if resolved_plan == "remote_tree_parallel":
                    # the wire handshake carries the model identity
                    pk.setdefault("model_id", self.model_id)
                    pk.setdefault("version", self.version)
                eng = TreeEngine(
                    self.packed, spec.replace(layout=resolved),
                    plan_kwargs=pk or None,
                )
                if self._load_ms is not None:
                    # the artifact load cost surfaces once, through the same
                    # ledger compile/remote costs already ride
                    eng._compile_ms["load"] = self._load_ms
                    self._load_ms = None
                self._engines[key] = eng
                route = "/".join(
                    str(p) for p in (spec.mode, backend_key, resolved,
                                     resolved_plan)
                )
                self._build_ms[route] = (time.perf_counter() - t0) * 1e3
            return self._engines[key]

    def release(self) -> None:
        """Close and drop every engine this version built (thread pools,
        remote workers, native libraries become collectable).  Idempotent;
        an engine handle obtained before the release stops serving."""
        with self._lock:
            engines = list(self._engines.values())
            self._engines.clear()
            self.released = True
        for eng in engines:
            eng.close()


class ModelRegistry:
    def __init__(self, *, retain: int = 2):
        if retain < 1:
            raise ValueError("retain must keep at least the current version")
        self.retain = retain
        self._models: dict[str, ModelVersion] = {}
        self._history: dict[str, int] = {}  # model_id -> latest version number
        # model_id -> {version: ModelVersion} for the retained window
        self._versions: dict[str, dict[int, ModelVersion]] = {}
        # (realpath, mtime_ns, size) -> ForestIR: hot-swapping back to an
        # already-mapped, unchanged artifact file reuses the parsed IR and
        # its materialized layouts — the pages were never duplicated
        self._artifact_cache: dict = {}
        self._lock = threading.Lock()

    # ---------------------------------------------------------- registration
    def _install(self, model_id: str, packed, source: str) -> ModelVersion:
        with self._lock:
            version = self._history.get(model_id, 0) + 1
            mv = ModelVersion(model_id=model_id, version=version, packed=packed,
                              source=source)
            self._history[model_id] = version
            self._models[model_id] = mv  # atomic repoint = hot-swap
            window = self._versions.setdefault(model_id, {})
            window[version] = mv
            evict = sorted(window)[:-self.retain]
            evicted = [window.pop(v) for v in evict]
        for old in evicted:  # outside the lock: close() may drain executors
            old.release()
        return mv

    def register_packed(self, model_id: str, packed: PackedEnsemble) -> ModelVersion:
        return self._install(model_id, packed, "packed")

    def register_forest(self, model_id: str, forest) -> ModelVersion:
        return self._install(model_id, pack_forest(forest), "forest")

    def register_json(self, model_id: str, payload: str) -> ModelVersion:
        """Load from the trees/io JSON artifact boundary."""
        return self._install(model_id, pack_forest(forest_from_json(payload)), "json")

    def register_artifact(self, model_id: str, path, *,
                          mmap: bool = True) -> ModelVersion:
        """Load an ITRF binary artifact — no JSON parse, no re-quantization.

        With ``mmap=True`` the version's ForestIR is zero-copy read-only
        views over the file mapping; every process registering the same file
        shares one page cache.  The measured load wall-ms lands in the first
        engine's compile ledger under ``"load"``.
        """
        from repro.ir.artifact import read_itrf

        t0 = time.perf_counter()
        cache_key = None
        ir = None
        if mmap:
            try:
                st = os.stat(path)
                cache_key = (os.path.realpath(path), st.st_mtime_ns,
                             st.st_size)
            except OSError:
                cache_key = None
            with self._lock:
                ir = self._artifact_cache.get(cache_key)
        if ir is None:
            ir = read_itrf(path, mmap_arrays=mmap)
            if cache_key is not None:
                with self._lock:
                    self._artifact_cache[cache_key] = ir
        load_ms = (time.perf_counter() - t0) * 1e3
        mv = self._install(model_id, ir, "artifact")
        mv._load_ms = load_ms
        return mv

    def release(self, model_id: str, version: int) -> None:
        """Free a retained, non-current version explicitly (its engines
        close; compiled artifacts become collectable)."""
        with self._lock:
            if self._models.get(model_id) is not None \
                    and self._models[model_id].version == version:
                raise ValueError(
                    f"version {version} is the current version of "
                    f"{model_id!r}; register a replacement before releasing"
                )
            mv = self._versions.get(model_id, {}).pop(version, None)
        if mv is None:
            raise KeyError(f"no retained version {version} for {model_id!r}")
        mv.release()

    # ---------------------------------------------------------------- lookup
    def get(self, model_id: str) -> ModelVersion:
        try:
            return self._models[model_id]
        except KeyError:
            raise KeyError(f"unknown model id {model_id!r}; have {sorted(self._models)}")

    def version(self, model_id: str) -> int:
        return self.get(model_id).version

    def ids(self) -> list:
        return sorted(self._models)

    def describe(self) -> dict:
        out = {}
        for mid, mv in sorted(self._models.items()):
            d = {
                "version": mv.version,
                "source": mv.source,
                "n_trees": mv.packed.n_trees,
                "n_classes": mv.packed.n_classes,
                "n_features": mv.packed.n_features,
                "artifact_kb": mv.packed.nbytes_integer() / 1e3,
            }
            # bytes per layout, for the layouts serving routes have actually
            # materialized (reporting must not force builds of the others)
            from repro.ir import ForestIR

            ir = mv.packed if isinstance(mv.packed, ForestIR) \
                else getattr(mv.packed, "ir", None)
            if ir is not None:
                d["layout_kb"] = {
                    name: ir.materialize(name).nbytes_integer() / 1e3
                    for name in ir.materialized_layouts()
                }
            if mv._build_ms:
                d["engine_builds"] = dict(sorted(mv._build_ms.items()))
            out[mid] = d
        return out

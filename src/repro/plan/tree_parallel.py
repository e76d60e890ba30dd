"""TreeParallelPlan: carve the forest into tree-contiguous shards and merge
exact integer partial sums.

The plan the paper's arithmetic earns: because every tree's contribution is a
uint32 fixed-point addend at a fixed per-ensemble scale, the ensemble sum is
associative — shard partials merge with *zero* precision loss, something a
float-accumulating ensemble cannot promise.  Two execution strategies behind
one plan:

  * **Device-parallel (fused)** — all shards on the jnp reference walk: the
    per-shard padded sub-forest tables are stacked into one ``(S, T', N)``
    array, laid over an ``S``-device mesh, and a single jitted
    ``shard_map`` call computes every shard's partials concurrently (each
    device scans only its trees) and merges them with a uint32 sum.  This is
    the path ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` exercises
    in CI without real accelerators, and the scaling the ``plan_scaling``
    bench measures.
  * **Backend-parallel (threaded)** — one backend per shard, each built on
    ``ForestIR.subset``'s bit-identical sub-forest artifact, executed
    concurrently on a thread pool (jitted JAX and ctypes C both release the
    GIL) and merged on the host.  Shards may run *different* backends — a
    heterogeneous plan can put half the forest on compiled C and half on the
    Pallas kernel and still be bit-identical to single-shard execution.

Deterministic modes only: float accumulation is not associative, so a float
forest cannot be tree-sharded losslessly (use ``row_parallel``, which shards
the batch instead).
"""
from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from functools import reduce
from itertools import cycle, islice
from typing import Optional

import numpy as np

from repro.plan.base import ExecutionPlan, as_ir, build_backend, register_plan

_DEFAULT_SHARDS = 2


def thread_shard_cap() -> int:
    """The threaded path's shard ceiling: one in-flight shard per core, floor
    2.  BENCH_7 measured the cost of ignoring this — s4/s8 ran 1.4–1.8x
    *slower* than single-shard on the 1-core CI host, pure contention with no
    parallelism to buy.  The floor keeps two shards even on one core: the
    second shard overlaps the first's dispatch/merge gap (s2 measurably beat
    single there), and it preserves real multi-shard coverage everywhere.
    Fused (shard_map) plans are never capped — device counts are not core
    counts."""
    return max(os.cpu_count() or 1, 2)


def tree_ranges(n_trees: int, shards: int) -> list:
    """Contiguous, near-equal ``[start, stop)`` tree ranges, empties dropped
    (a 3-tree forest asked for 8 shards runs 3 single-tree shards)."""
    bounds = np.linspace(0, n_trees, min(int(shards), n_trees) + 1).astype(int)
    return [(int(a), int(b)) for a, b in zip(bounds[:-1], bounds[1:]) if b > a]


@register_plan
class TreeParallelPlan(ExecutionPlan):
    name = "tree_parallel"
    deterministic_only = True

    def __init__(self, model, *, mode: str = "integer", backend="reference",
                 shards=None, layout: Optional[str] = None,
                 backend_kwargs: Optional[dict] = None,
                 device_parallel="auto", clamp_shards: bool = True):
        ir = as_ir(model)
        super().__init__(ir, mode=mode)
        if not self._spec.deterministic:
            raise ValueError(
                f"tree_parallel needs exact integer partials; mode {mode!r} "
                "accumulates floats — shard the batch (row_parallel) instead"
            )
        if isinstance(backend, str):
            names = [backend] * int(shards or _DEFAULT_SHARDS)
        else:  # heterogeneous: a sequence of backend names, cycled over shards
            names = list(islice(cycle(backend), int(shards or len(backend))))
        if not names:
            raise ValueError("tree_parallel needs at least one shard")
        self.ir = ir
        self.ranges = tree_ranges(ir.n_trees, len(names))
        names = names[: len(self.ranges)]
        self._names = names
        self._fused = None
        self._shard_backends: tuple = ()
        if self._can_fuse(names, layout, backend_kwargs, device_parallel):
            self._build_fused()
        else:
            if device_parallel is True:
                raise ValueError(
                    "device_parallel=True needs a homogeneous 'reference' "
                    "plan (default layout, no backend kwargs) and at least "
                    f"{len(self.ranges)} jax devices"
                )
            # oversubscription cap (threaded path only): shards beyond the
            # core budget cannot run concurrently, they just contend.  An
            # explicit heterogeneous backend mix is an explicit fan-out
            # request and is honored as asked; clamp_shards=False opts a
            # homogeneous plan out (scaling benches measure the full sweep).
            cap = thread_shard_cap()
            if clamp_shards and isinstance(backend, str) \
                    and len(self.ranges) > cap:
                self.ranges = tree_ranges(ir.n_trees, cap)
                names = names[: len(self.ranges)]
                self._names = names
            self._shard_backends = tuple(
                build_backend(name, ir.subset(a, b), mode, layout, backend_kwargs)
                for name, (a, b) in zip(names, self.ranges)
            )
        self._pool = None  # threaded path: created lazily, released by close()

    # ----------------------------------------------------------- strategies
    def _can_fuse(self, names, layout, backend_kwargs, device_parallel) -> bool:
        if not device_parallel or len(self.ranges) < 2:
            return False
        if any(n != "reference" for n in names) or backend_kwargs:
            return False
        if layout not in (None, "padded"):
            return False
        import jax

        return len(jax.devices()) >= len(self.ranges)

    def _build_fused(self) -> None:
        """Stack per-shard padded tables and jit one shard_map'd accumulate.

        Shards are padded to a common (T', N) with inert trees/nodes
        (self-looping zero-mass leaves), which contribute exactly 0 to the
        uint32 accumulator — the same trick the Pallas wrapper and the padded
        layout already rely on, so fusing cannot perturb partials.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from repro.core.ensemble import _predict

        subs = [self.ir.subset(a, b).materialize("padded") for a, b in self.ranges]
        S = len(subs)
        C = self.ir.n_classes
        Tp = max(s.n_trees for s in subs)
        N = max(s.feature.shape[1] for s in subs)
        selfloop = np.tile(np.arange(N, dtype=np.int32), (Tp, 1))
        feats, keys, lefts, rights, leaves = [], [], [], [], []
        for s in subs:
            T0, N0 = s.feature.shape
            f = np.full((Tp, N), -1, np.int32)
            k = np.zeros((Tp, N), np.int32)
            l, r = selfloop.copy(), selfloop.copy()
            lf = np.zeros((Tp, N, C), np.uint32)
            f[:T0, :N0] = s.feature
            k[:T0, :N0] = s.threshold_key
            l[:T0, :N0] = s.left
            r[:T0, :N0] = s.right
            lf[:T0, :N0] = s.leaf_fixed
            feats.append(f); keys.append(k); lefts.append(l); rights.append(r)
            leaves.append(lf)
        depth = int(self.ir.max_depth)
        mesh = Mesh(np.asarray(jax.devices()[:S]), ("s",))
        # each device holds only its own shard's tables, placed once here
        stacked = tuple(jax.device_put(np.stack(a), NamedSharding(mesh, P("s")))
                        for a in (feats, keys, lefts, rights, leaves))
        self._table_devices = sorted(
            int(d.id) for d in stacked[0].sharding.device_set)

        def shard_fn(feature, key, left, right, leaf, xk):
            # per-device view: the (1, T', N) block of this shard's trees
            arrays = dict(feature=feature[0], threshold=key[0], left=left[0],
                          right=right[0], leaf=leaf[0])
            return _predict(arrays, xk, depth, jnp.uint32)[None]

        sm = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P("s"), P("s"), P("s"), P("s"), P("s"), P()),
            out_specs=P("s"), check_vma=False,
        )

        @jax.jit
        def fused(tables, xk):
            # uint32 merge on device: associative, so the (S, B, C) shard
            # partials collapse to the single-shard accumulator bit-exactly
            return jnp.sum(sm(*tables, xk), axis=0, dtype=jnp.uint32)

        self._fused = lambda xk: fused(stacked, xk)
        self._fused_label = f"fused:reference[x{S}]"

    # ------------------------------------------------------------ execution
    def predict_partials(self, X):
        X = np.asarray(X, np.float32)
        # capture the parent span on the dispatching thread: the shard pool
        # threads get it via submit args, not via the thread-local
        parent = self.trace_parent
        if self._fused is not None:
            from repro.backends.base import device_call
            from repro.core.flint import float_to_key_np

            # fetch inside the timed region: the jitted call dispatches
            # asynchronously, so timing it alone would record ~0ms.  The
            # device-side uint32 merge rides inside this span too.
            return self._timed(self._fused_label, device_call, self._fused,
                               float_to_key_np(X), span_parent=parent)
        labels = [
            f"s{i}:{b.name}[{a}:{e}]"
            for i, (b, (a, e)) in enumerate(zip(self._shard_backends, self.ranges))
        ]
        pool = self._ensure_pool()
        futs = [
            pool.submit(self._timed, lab, b.predict_partials, X,
                        span_parent=parent)
            for lab, b in zip(labels, self._shard_backends)
        ]
        partials = [np.asarray(f.result()) for f in futs]
        # uint32 adds wrap mod 2^32 — the exact merge the IR's scale bound
        # guarantees never actually wraps for a full forest
        t0 = time.perf_counter_ns()
        merged = reduce(np.add, partials)
        t1 = time.perf_counter_ns()
        self._record_stage("merge", (t1 - t0) / 1e9)
        self._span("merge", t0, t1, parent, shards=len(partials))
        return merged

    def _ensure_pool(self) -> ThreadPoolExecutor:
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=len(self._shard_backends),
                thread_name_prefix="tree-shard",
            )
        return self._pool

    def close(self) -> None:
        """Drain in-flight shard dispatches and release the pool.  The plan
        stays usable — the next ``predict_partials`` lazily re-creates the
        pool — because registry-memoized engines outlive one gateway."""
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)

    # -------------------------------------------------------------- metadata
    @property
    def fused(self) -> bool:
        """True when shards run as one shard_map'd device computation."""
        return self._fused is not None

    @property
    def backends(self) -> tuple:
        return self._shard_backends

    @property
    def packed(self):
        return self.ir

    @property
    def n_shards(self) -> int:
        return len(self.ranges)

    @property
    def compiles_per_shape(self) -> bool:
        if self._fused is not None:
            return True  # one jit compile per padded batch shape
        return super().compiles_per_shape

    @property
    def backend_name(self) -> str:
        if self._fused is not None:
            return "reference"
        return super().backend_name

    def describe(self) -> dict:
        d = super().describe()
        d.update(shards=self.n_shards, tree_ranges=self.ranges, fused=self.fused)
        if self._fused is not None:  # the devices holding the shard tables
            d["devices"] = self._table_devices
        return d

import os
import shutil

import numpy as np
import pytest

# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device; only launch/dryrun.py uses 512 placeholders.
# Tests that need a few devices spawn subprocesses (see test_distributed.py).

# The whole suite is host-CPU-only (Pallas kernels run interpreted, sharded
# paths on forced host devices, and tests/test_tpu_compile.py only lowers for
# a described TPU).  On images that bundle libtpu, leaving the
# platform unpinned makes every fresh jax process — this one, the
# test_distributed subprocesses, the remote shard workers — probe the cloud
# metadata service for a TPU, which stalls for minutes when that endpoint
# blackholes instead of refusing.  Pin before anything imports jax; spawned
# children inherit it.  setdefault so a caller pinning a real platform wins.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# ---------------------------------------------------------------------------
# shared `requires_gcc` marker: codegen / native-backend tests need a C
# toolchain; on toolchain-less hosts they must *skip*, not error.  Usage:
#     @pytest.mark.requires_gcc
# ---------------------------------------------------------------------------

def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "requires_gcc: test compiles emitted C; skipped when gcc is absent",
    )
    config.addinivalue_line(
        "markers",
        "slow: long end-to-end runs (training drivers, Poisson gateway "
        'workloads); CI deselects them with -m "not slow", `make check` '
        "still runs everything",
    )


def pytest_collection_modifyitems(config, items):
    if shutil.which("gcc") is not None:
        return
    skip_gcc = pytest.mark.skip(reason="gcc not available")
    for item in items:
        if "requires_gcc" in item.keywords:
            item.add_marker(skip_gcc)


@pytest.fixture(scope="session")
def shuttle_small():
    from repro.data.tabular import make_shuttle_like, train_test_split

    X, y = make_shuttle_like(n=4000, seed=7)
    return train_test_split(X, y, seed=7)


@pytest.fixture(scope="session")
def small_forest(shuttle_small):
    from repro.trees.forest import RandomForestClassifier

    Xtr, ytr, _, _ = shuttle_small
    return RandomForestClassifier(n_estimators=9, max_depth=6, seed=1).fit(Xtr, ytr)


@pytest.fixture(scope="session")
def small_packed(small_forest):
    from repro.core.packing import pack_forest

    return pack_forest(small_forest)


@pytest.fixture(scope="session")
def deep():
    """3 trees of depth 16 with 2,545-3,255 nodes over Covertype-like rows,
    leaves near one-hot, and 300 rows to score: (the drawn forest, its
    ForestIR, the rows).  Under ``small_smem`` they span several node blocks
    of the scan; at the real budgets whole trees fit a grid cell."""
    from bench.catalog import Catalog
    from bench.forest import draw_forest
    from repro.ir import ForestIR

    catalog = Catalog()
    cfg = dict(catalog.config("hb-rf-covtype"), n_trees=3, max_depth=16,
               shape={"full_depth": 5, "split_prob": 0.65},
               leaves={"dirichlet_alpha": 0.1})
    rows = catalog.rows(cfg["rows"]["generator"]).Rows(cfg, 2**31 + 3)
    forest = draw_forest(cfg, rows, 2**31 + 3)
    assert 2000 < forest.node_counts.min() and forest.node_counts.max() < 4000
    assert forest.max_depth == 16
    ir = ForestIR.from_forest(forest)
    return forest, ir, rows.take(3, 0, 300)


@pytest.fixture
def small_smem(monkeypatch):
    """The kernel's SMEM budget lowered to 96 KiB: at 3 trees a block,
    1,024 nodes a block."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_SMEM_BUDGET_BYTES", 96 * 1024)

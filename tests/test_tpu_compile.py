"""Compile the main path for a TPU v5e without a chip.

The chip's compiler is installed with libtpu and lowers for a described
topology, so these tests catch what interpret mode cannot: unaligned blocks,
unsupported lowerings and on-chip memory limits.  They compile the three
Pallas walks and the jnp reference walk at the widths of
``configs/intreeger_rf.py`` (128 trees, depth 10, 87 features, 8 classes),
and the node-chunked scan at the widths of an unpruned forest.  Nothing runs; results are checked by the interpret-mode bit-identity tests.

The topology is described inside a fixture (never at import): only one
process may load libtpu, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.intreeger_rf import CONFIG
from repro.core.ensemble import _predict
from repro.kernels import ops
from repro.kernels.ops import pick_blocks, tree_predict_integer
from repro.kernels.tree_traverse import tree_traverse

T, D, F, C = CONFIG.n_trees, CONFIG.tree_depth, CONFIG.n_tab_features, CONFIG.n_classes
N = 2 ** (D + 1) - 1  # padded nodes per tree


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A program compiled for a described chip cannot be read back from the
    persistent cache without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tables(sharding):
    return ([_shape(sharding, (T, N)) for _ in range(4)]
            + [_shape(sharding, (T, N, C), jnp.uint32)])


def _compile_kernel(sharding, impl, rows, block_t=None):
    def run(x, feature, key, left, right, leaf, counts):
        return tree_predict_integer(
            x, feature, key, left, right, leaf, depth=D, impl=impl,
            block_t=block_t, interpret=False,
            internal_counts=counts if impl == "leaf_major" else None)

    return jax.jit(run).lower(
        _shape(sharding, (rows, F)), *_tables(sharding), _shape(sharding, (T,))
    ).compile()


@pytest.mark.parametrize("rows", [8, 256])
@pytest.mark.parametrize("impl", ["leaf_major", "gather", "onehot"])
def test_pallas_walk_compiles_for_v5e(one_chip, no_compile_cache, impl, rows):
    compiled = _compile_kernel(one_chip, impl, rows)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("impl,rows", [("leaf_major", 256), ("gather", 8)])
def test_fused_key_transform_compiles_for_v5e(one_chip, no_compile_cache,
                                               impl, rows):
    """The serving path's one program: float32 rows keyed, padded and walked
    by the kernel inside one jitted call."""
    def run(x, feature, key, left, right, leaf, counts):
        return ops._launch(x, feature, key, left, right, leaf,
                           counts if impl == "leaf_major" else None,
                           depth=D, impl=impl, interpret=False)

    compiled = jax.jit(run).lower(
        _shape(one_chip, (rows, F), jnp.float32), *_tables(one_chip),
        _shape(one_chip, (T,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [8, 256])
def test_reference_walk_compiles_for_v5e(one_chip, no_compile_cache, rows):
    feature, key, left, right, leaf = _tables(one_chip)
    arrays = dict(feature=feature, threshold=key, left=left, right=right, leaf=leaf)
    compiled = _predict.lower(arrays, _shape(one_chip, (rows, F)), depth=D,
                              acc_dtype=jnp.uint32).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def _compile_raw_scan(sharding, block_t, block_n):
    """The scan's pallas_call alone, at a tiling the caller pins (the
    wrapper would cut the node axis to fit the budgets instead)."""
    npad = -(-N // block_n) * block_n

    def run(x, fields, leaf, counts):
        return tree_traverse(x, fields, leaf, counts, depth=D, block_b=256,
                             block_t=block_t, block_n=block_n,
                             impl="leaf_major", interpret=False)

    return jax.jit(run).lower(
        _shape(sharding, (2, F, 128)), _shape(sharding, (T, 4, npad)),
        _shape(sharding, (T, npad // 128, C, 128)), _shape(sharding, (T,)),
    ).compile()


def test_smem_budget_counts_both_pipeline_buffers(one_chip, no_compile_cache):
    """The picked tree block fits the chip's 1 MiB of SMEM; twice that block
    (the node fields' two pipeline buffers at 1 MiB) is refused, so the
    budget's double-buffer accounting matches the compiler's."""
    _, block_t, block_n = pick_blocks(256, T, N, F, C, chunk_nodes=True)
    assert block_n == 2048  # whole trees: one node block
    _compile_raw_scan(one_chip, block_t, block_n)
    with pytest.raises(Exception, match="smem"):
        _compile_raw_scan(one_chip, 2 * block_t, block_n)


# an unpruned forest: scikit-learn's default random forest on Covertype's
# widths, its largest tree padded to 417 chunks of 128 nodes
DEEP_T, DEEP_D, DEEP_F, DEEP_C, DEEP_N = 100, 48, 54, 7, 53_376


@pytest.mark.parametrize("rows", [8, 256])
def test_node_chunked_scan_compiles_for_v5e(one_chip, no_compile_cache, rows):
    """Trees of 53,376 nodes need 13.7 MB of SMEM whole; the scan cuts the
    node axis into blocks that fit, and the chip's compiler takes it."""
    _, block_t, block_n = pick_blocks(rows, DEEP_T, DEEP_N, DEEP_F, DEEP_C,
                                      chunk_nodes=True)
    assert block_t == 8 and block_n < DEEP_N and DEEP_N // block_n > 1

    def run(x, feature, key, left, right, leaf, counts):
        return tree_predict_integer(
            x, feature, key, left, right, leaf, depth=DEEP_D,
            impl="leaf_major", interpret=False, internal_counts=counts)

    tables = ([_shape(one_chip, (DEEP_T, DEEP_N)) for _ in range(4)]
              + [_shape(one_chip, (DEEP_T, DEEP_N, DEEP_C), jnp.uint32)])
    compiled = jax.jit(run).lower(
        _shape(one_chip, (rows, DEEP_F)), *tables,
        _shape(one_chip, (DEEP_T,))).compile()
    assert "tpu_custom_call" in compiled.as_text()

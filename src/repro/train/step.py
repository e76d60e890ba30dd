"""Train step factory: loss -> grads -> (clip, optional integer DP reduce) ->
AdamW -> new state.  One function serves smoke tests (1 CPU device), the
multi-pod dry-run (abstract lowering), and the runnable examples.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.train import optimizer as opt
from repro.train.intreeger_allreduce import integer_pmean


def make_train_step(cfg: ModelConfig, opt_cfg: Optional[opt.AdamWConfig] = None):
    """Single-step factory with microbatched gradient accumulation.

    ``cfg.microbatches > 1`` splits the global batch on the leading axis and
    scans value_and_grad over the slices, accumulating f32 grads — activation
    stacks shrink by the microbatch factor while arithmetic is unchanged
    (standard virtual-batch training at scale).
    """
    opt_cfg = opt_cfg or opt.AdamWConfig()

    def grads_of(params, batch):
        def lf(p):
            return tfm.loss_fn(cfg, p, batch)

        return jax.value_and_grad(lf, has_aux=True)(params)

    def train_step(params, opt_state, batch):
        from repro.sharding.ops import current_mesh

        # each microbatch must still fill every batch shard: cap the count
        # so B/n_micro stays divisible by the (pod x data) extent
        b = jax.tree.leaves(batch)[0].shape[0]
        mesh = current_mesh()
        dp = 1
        if mesh is not None:
            for a in ("pod", "data"):
                dp *= mesh.shape.get(a, 1)
        n_micro = max(1, min(cfg.microbatches, b // max(dp, 1)))
        while b % (n_micro * dp) and n_micro > 1:
            n_micro -= 1
        if n_micro == 1:
            (loss, parts), grads = grads_of(params, batch)
        else:
            micro = jax.tree.map(
                lambda a: a.reshape((n_micro, a.shape[0] // n_micro) + a.shape[1:]), batch
            )

            def acc_fn(carry, mb):
                (loss, parts), grads = grads_of(params, mb)
                gsum, lsum, psum_ = carry
                gsum = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), gsum, grads)
                return (gsum, lsum + loss, jax.tree.map(jnp.add, psum_, parts)), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            p0 = {"ce": jnp.zeros(()), "aux": jnp.zeros(())}
            (gsum, lsum, psum_), _ = jax.lax.scan(acc_fn, (g0, jnp.zeros(()), p0), micro)
            grads = jax.tree.map(lambda g: g / n_micro, gsum)
            loss = lsum / n_micro
            parts = jax.tree.map(lambda x: x / n_micro, psum_)
        params, opt_state, om = opt.adamw_update(opt_cfg, grads, opt_state, params)
        metrics = {"loss": loss, **parts, **om}
        return params, opt_state, metrics

    return train_step


def make_integer_dp_train_step(cfg: ModelConfig, mesh, opt_cfg: Optional[opt.AdamWConfig] = None):
    """Variant with the paper-math integer all-reduce over the data axis.

    Gradients are computed per data shard (batch split via shard_map), then
    combined with the deterministic int32 fixed-point psum
    (``intreeger_allreduce``).  Params/opt state are replicated over ``data``
    in this mode (pure DP; for FSDP the integer reduce applies to the
    reduce-scatter equivalently).
    """
    opt_cfg = opt_cfg or opt.AdamWConfig()
    n = mesh.shape["data"]

    from jax.sharding import PartitionSpec as P

    def grad_fn(params, batch):
        def lf(p):
            return tfm.loss_fn(cfg, p, batch)

        (loss, _), grads = jax.value_and_grad(lf, has_aux=True)(params)
        grads = jax.tree.map(lambda g: integer_pmean(g, "data", n), grads)
        loss = jax.lax.pmean(loss, "data")
        return loss, grads

    sharded_grad = jax.shard_map(
        grad_fn,
        mesh=mesh,
        in_specs=(P(), P("data")),
        out_specs=(P(), P()),
        check_vma=False,
    )

    def train_step(params, opt_state, batch):
        loss, grads = sharded_grad(params, batch)
        params, opt_state, om = opt.adamw_update(opt_cfg, grads, opt_state, params)
        return params, opt_state, {"loss": loss, **om}

    return train_step

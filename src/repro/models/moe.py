"""Mixture-of-Experts layer: FlInt top-k routing + capacity-factor dispatch.

Paper tie-in (DESIGN.md Sec. 4): expert selection only needs the *order* of
router logits, so top-k runs on FlInt int32 keys (``repro.core.flint``) —
bit-identical selection, integer-only compare path.  This is the
within-LM-stack application of the paper's threshold-comparison insight.

Dispatch is scatter-based (no (T, E, C) one-hot): tokens are scattered into an
(E, C, d) buffer by (expert, slot) with slot = per-expert running count;
overflow beyond capacity drops (mode="drop"), standard Switch/GShard
semantics with capacity_factor.  Experts are sharded on the ``model`` mesh
axis; XLA SPMD inserts the dispatch/combine collectives (baseline; the
hillclimb in EXPERIMENTS.md Sec. Perf attacks exactly these).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.flint import float_to_key
from repro.models.layers import act_fn, dense_init
from repro.sharding.ops import constrain


def moe_params(key, d_model: int, n_experts: int, d_ff: int):
    kg, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "w_router": dense_init(kg, (d_model, n_experts)),
        "w_gate_e": dense_init(k1, (n_experts, d_model, d_ff), in_axis=1),
        "w_up_e": dense_init(k2, (n_experts, d_model, d_ff), in_axis=1),
        "w_down_e": dense_init(k3, (n_experts, d_ff, d_model), in_axis=1),
    }


def flint_topk(logits, k: int):
    """Top-k on int32 FlInt keys: integer compares only, identical order.

    Returns (gate_weights (T,k) f32 softmaxed over the k, expert_ids (T,k)).
    """
    keys = float_to_key(logits.astype(jnp.float32))
    _, ids = jax.lax.top_k(keys, k)  # int32 comparisons
    sel = jnp.take_along_axis(logits.astype(jnp.float32), ids, axis=-1)
    w = jax.nn.softmax(sel, axis=-1)  # normalize over the selected k (qwen3/olmoe)
    return w, ids


def _aux_loss(logits, ids, n_experts):
    """Switch-style load-balancing loss: E * sum_e f_e * p_e."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = probs.mean(axis=0)
    one_hot_top1 = jax.nn.one_hot(ids[:, 0], n_experts, dtype=jnp.float32)
    ce = one_hot_top1.mean(axis=0)
    return n_experts * jnp.sum(me * ce)


def moe_block(params, x, *, n_experts: int, k: int, act: str = "silu",
              capacity_factor: float = 1.25):
    """x: (B, S, D) -> (B, S, D), plus aux load-balancing loss.

    Dispatches to the expert-parallel shard_map path when a mesh with a
    non-trivial ``model`` axis is active (see ``moe_block_ep``); otherwise the
    single-program scatter path below (CPU tests, 1-device meshes).
    """
    from repro.sharding.ops import current_mesh

    mesh = current_mesh()
    if mesh is not None and mesh.shape.get("model", 1) > 1 and n_experts % mesh.shape["model"] == 0:
        # EP only pays off when the per-shard expert batch amortizes the
        # weight gather and keeps capacity sane; at decode (a few tokens per
        # shard) the single-program path is both faster and drop-free.
        b, s, _ = x.shape
        dp = 1
        for a in ("pod", "data"):
            dp *= mesh.shape.get(a, 1)
        t_loc = (b * s) // max(dp, 1)
        if t_loc * k >= 4 * n_experts:
            return moe_block_ep(
                params, x, n_experts=n_experts, k=k, act=act,
                capacity_factor=capacity_factor, mesh=mesh,
            )
    return _moe_block_jit(
        params, x, n_experts=n_experts, k=k, act=act, capacity_factor=capacity_factor
    )


def _moe_block_jit(params, x, *, n_experts: int, k: int, act: str,
                   capacity_factor: float):
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    logits = xt @ params["w_router"].astype(x.dtype)  # (T, E)
    gates, ids = flint_topk(logits, k)  # (T,k)
    aux_loss = _aux_loss(logits, ids, n_experts)

    capacity = int(max(1, (t * k * capacity_factor) // n_experts))

    ids_flat = ids.reshape(-1)  # (T*k,)
    # slot within expert = rank of this pair among same-expert pairs
    onehot = jax.nn.one_hot(ids_flat, n_experts, dtype=jnp.int32)  # (T*k, E)
    slots = (jnp.cumsum(onehot, axis=0) - onehot)  # exclusive prefix count
    slot_flat = jnp.take_along_axis(slots, ids_flat[:, None], axis=1)[:, 0]

    xrep = jnp.repeat(xt, k, axis=0)  # (T*k, D) token copies per routed pair
    buf = jnp.zeros((n_experts, capacity, d), x.dtype)
    buf = buf.at[ids_flat, slot_flat].set(xrep, mode="drop")
    # dispatch buffer lives expert-sharded: the scatter above IS the all-to-all
    buf = constrain(buf, "expert", None, None)

    a = act_fn(act)
    gate = a(jnp.einsum("ecd,edf->ecf", buf, params["w_gate_e"].astype(x.dtype)))
    up = jnp.einsum("ecd,edf->ecf", buf, params["w_up_e"].astype(x.dtype))
    out_e = jnp.einsum("ecf,efd->ecd", gate * up, params["w_down_e"].astype(x.dtype))
    out_e = constrain(out_e, "expert", None, None)

    # combine: read back each pair's slot; dropped pairs (slot >= capacity) -> 0
    in_cap = slot_flat < capacity
    safe_slot = jnp.minimum(slot_flat, capacity - 1)
    yrep = out_e[ids_flat, safe_slot]  # (T*k, D)
    yrep = jnp.where(in_cap[:, None], yrep, 0)
    y = (yrep.reshape(t, k, d) * gates[..., None].astype(x.dtype)).sum(axis=1)
    return y.reshape(b, s, d), aux_loss


# ---------------------------------------------------------------------------
# expert-parallel shard_map path
# ---------------------------------------------------------------------------
#
# GSPMD cannot partition the scatter-based dispatch sanely: the baseline
# dry-run showed 1.1-2.8 TB/device/step of dispatch all-gathers on the MoE
# cells (EXPERIMENTS.md §Perf).  The manual pattern exploits the 2-D mesh
# directly: device (i, j) owns data-shard i's tokens AND model-shard j's
# experts, so dispatch/FFN/partial-combine are fully local; the ONLY
# communication is a psum of the combined output over `model` (plus the FSDP
# weight all-gather over `data`, which AD transposes to the grad
# reduce-scatter).  No all-to-all is needed at all in this topology.

def _ep_body(wr, wg, wu, wd, xb, *, n_experts, e_loc, k, act, capacity_factor,
             batch_axes):
    b, s, d = xb.shape
    t = b * s
    xt = xb.reshape(t, d)
    logits = xt @ wr.astype(xt.dtype)  # (t_loc, E) — full expert range
    gates, ids = flint_topk(logits, k)
    aux = _aux_loss(logits, ids, n_experts)
    aux = jax.lax.pmean(aux, batch_axes)  # identical across `model` already

    lo = jax.lax.axis_index("model") * e_loc
    ids_loc = jnp.where((ids >= lo) & (ids < lo + e_loc), ids - lo, e_loc)
    ids_flat = ids_loc.reshape(-1)  # (t*k,) — e_loc == "not mine"

    capacity = int(max(1, (t * k * capacity_factor) // n_experts))
    onehot = jax.nn.one_hot(ids_flat, e_loc + 1, dtype=jnp.int32)
    slots = jnp.cumsum(onehot, axis=0) - onehot
    slot_flat = jnp.take_along_axis(slots, ids_flat[:, None], axis=1)[:, 0]

    # Compact dispatch: scatter only the (token-id, gate) bookkeeping (a few
    # MB), then GATHER the <= e_loc*capacity landed rows — never materialize
    # the (t*k, d) token-copy tensor (12-16x traffic vs. the landed rows).
    pair_tok = jnp.arange(t * k, dtype=jnp.int32) // k
    src_tok = jnp.full((e_loc, capacity), t, jnp.int32)  # t == padding row
    src_tok = src_tok.at[ids_flat, slot_flat].set(pair_tok, mode="drop")
    gate_slot = jnp.zeros((e_loc, capacity), jnp.float32)
    gate_slot = gate_slot.at[ids_flat, slot_flat].set(gates.reshape(-1), mode="drop")

    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    buf = xt_pad[src_tok]  # (e_loc, capacity, d)

    a = act_fn(act)
    gate = a(jnp.einsum("ecd,edf->ecf", buf, wg.astype(xb.dtype)))
    up = jnp.einsum("ecd,edf->ecf", buf, wu.astype(xb.dtype))
    out_e = jnp.einsum("ecf,efd->ecd", gate * up, wd.astype(xb.dtype))

    # Compact combine: scatter-add the gated expert rows straight into the
    # (t, d) output (padding rows target index t -> dropped).
    contrib = out_e * gate_slot[..., None].astype(xb.dtype)
    y = jnp.zeros((t, d), xb.dtype)
    y = y.at[src_tok.reshape(-1)].add(contrib.reshape(-1, d), mode="drop")
    y = jax.lax.psum(y, "model")  # combine partial expert outputs
    return y.reshape(b, s, d), aux


def moe_block_ep(params, x, *, n_experts: int, k: int, act: str,
                 capacity_factor: float, mesh):
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["model"]
    e_loc = n_experts // tp
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    bspec = P(batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None),
              None, None)
    body = functools.partial(
        _ep_body, n_experts=n_experts, e_loc=e_loc, k=k, act=act,
        capacity_factor=capacity_factor, batch_axes=batch_axes,
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(),  # router weight: replicated (tiny)
            P("model", None, None),  # expert weights: local experts, full d
            P("model", None, None),
            P("model", None, None),
            bspec,  # tokens: local batch shard, replicated over model
        ),
        out_specs=(bspec, P()),
        check_vma=False,
    )
    return fn(params["w_router"], params["w_gate_e"], params["w_up_e"],
              params["w_down_e"], x)

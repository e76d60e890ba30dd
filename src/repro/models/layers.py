"""Shared transformer building blocks (pure-functional, params as dicts).

Conventions:
  * activations are bf16 in compute, params f32 (cast at use),
  * weights are dicts of jnp arrays; every leaf name is matched by
    ``repro.sharding.rules`` to a PartitionSpec,
  * attention is exact chunked ("lazy flash"): queries processed in chunks,
    scores per chunk are (q_chunk, S) — bounded memory at 32k prefill without
    an online-softmax inner loop (simpler HLO, same FLOPs).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

COMPUTE_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0):
    fan_in = shape[in_axis]
    scale = 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.float32)


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, Dh), positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(dh, theta), jnp.float32)  # (Dh/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnDims:
    n_heads: int
    n_kv_heads: int
    head_dim: int


def attn_params(key, d_model: int, dims: AttnDims):
    kq, kk, kv, ko = jax.random.split(key, 4)
    h, k, dh = dims.n_heads, dims.n_kv_heads, dims.head_dim
    return {
        "wq": dense_init(kq, (d_model, h * dh)),
        "wk": dense_init(kk, (d_model, k * dh)),
        "wv": dense_init(kv, (d_model, k * dh)),
        "wo": dense_init(ko, (h * dh, d_model)),
    }


def _chunked_softmax_attn(q, k, v, *, causal: bool, window: int, q_chunk: int,
                          q_offset=0, kv_len: Optional[int] = None):
    """Exact attention, queries chunked.  q: (B,Sq,K,G,Dh) k/v: (B,Skv,K,Dh).

    ``window`` > 0 masks keys older than ``window`` positions (sliding
    window); 0 means full attention.  ``q_offset`` is the absolute position of
    q[0] (decode with cache).  ``kv_len`` masks out cache tail beyond the
    valid length (traced scalar ok).
    """
    b, sq, kh, g, dh = q.shape
    skv = k.shape[1]
    q_chunk = min(q_chunk, sq)
    pad = (-sq) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    nq = q.shape[1] // q_chunk
    qc = q.reshape(b, nq, q_chunk, kh, g, dh)
    scale = 1.0 / np.sqrt(dh)
    kpos = jnp.arange(skv)

    def one_chunk(i, qi):
        # qi: (B, qc, K, G, Dh)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qi.astype(jnp.float32), k.astype(jnp.float32))
        scores *= scale
        qpos = q_offset + i * q_chunk + jnp.arange(q_chunk)
        mask = jnp.ones((q_chunk, skv), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if not (isinstance(window, int) and window == 0):
            # window may be a traced per-layer scalar (gemma3 local:global);
            # window == 0 means full attention.
            w = jnp.asarray(window, jnp.int32)
            mask &= (kpos[None, :] > qpos[:, None] - w) | (w == 0)
        if kv_len is not None:
            mask &= (kpos < kv_len)[None, :]
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)

    out = jax.lax.map(lambda args: one_chunk(*args), (jnp.arange(nq), qc.swapaxes(0, 1)))
    out = out.swapaxes(0, 1).reshape(b, nq * q_chunk, kh, g, dh)
    return out[:, :sq]


def _flash_decode_partial(q, k, v, window, q_offset, kv_len, seq_axis, seq_shards,
                          head_axes=()):
    """Exact attention over a sequence-sharded KV cache (distributed flash
    decode): each shard computes unnormalized (m, l, o) over its local keys;
    a pmax/psum pair over ``seq_axis`` combines them.  q: (B,Sq,KH,G,Dh),
    k/v local: (B,S_loc,KH,Dh)."""
    b, sq, kh, g, dh = q.shape
    s_loc = k.shape[1]
    scale = 1.0 / np.sqrt(dh)
    offset = jax.lax.axis_index(seq_axis) * s_loc
    kpos = offset + jnp.arange(s_loc)
    qpos = q_offset + jnp.arange(sq)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32), k.astype(jnp.float32))
    scores *= scale
    mask = kpos[None, :] <= qpos[:, None]
    if not (isinstance(window, int) and window == 0):
        w = jnp.asarray(window, jnp.int32)
        mask &= (kpos[None, :] > qpos[:, None] - w) | (w == 0)
    mask &= (kpos < kv_len)[None, :]
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    # softmax is shift-invariant: the max is numerical stabilization only, so
    # stopping its gradient is exact (and pmax has no AD rule).
    m_loc = jax.lax.stop_gradient(jnp.max(scores, axis=-1))  # (B,KH,G,Sq)
    p = jnp.exp(scores - m_loc[..., None])
    p = jnp.where(jnp.isfinite(scores), p, 0.0)  # all-masked shard -> zeros
    l_loc = p.sum(axis=-1)
    o_loc = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    m = jax.lax.stop_gradient(jax.lax.pmax(m_loc, seq_axis))
    corr = jnp.where(jnp.isfinite(m_loc), jnp.exp(m_loc - m), 0.0)
    l = jax.lax.psum(l_loc * corr, seq_axis)
    o = jax.lax.psum(o_loc * corr[..., None], seq_axis)
    out = o / jnp.maximum(l[..., None], 1e-30)
    # (B,KH,G,Sq,Dh) -> (B,Sq,KH,G,Dh)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).astype(v.dtype)


def _attn_specs(mesh, b, kh, g, skv, sq):
    """(batch_axes, kv_sharded, seq_axis) placement decisions shared with
    ``repro.sharding.rules.cache_shardings`` — keep the two in sync.

    Sequence sharding (partial-softmax combine) pays a psum of the (.., Sq,
    Dh) output per call — profitable only for decode (sq == 1, giant cache);
    for training it regressed granite-34b train_4k 24.7 -> 44.0 s of
    collective (EXPERIMENTS.md §Perf iteration log), hence the sq gate."""
    tp = mesh.shape.get("model", 1)
    batch_axes = []
    total = 1
    for a in ("pod", "data"):
        size = mesh.shape.get(a, 1)
        if size > 1 and b % (total * size) == 0:
            batch_axes.append(a)
            total *= size
    kv_sharded = tp > 1 and kh % tp == 0
    seq_axis = None
    if sq == 1:
        if not kv_sharded and tp > 1 and skv is not None and skv % tp == 0:
            seq_axis = "model"
        if skv is not None and "data" not in batch_axes and mesh.shape.get("data", 1) > 1 \
                and skv % (mesh.shape["data"] * (tp if seq_axis else 1)) == 0 and b == 1:
            # long-context decode at batch 1: shard the cache seq over data
            seq_axis = seq_axis or "data"
    return tuple(batch_axes), kv_sharded, seq_axis


def attention(params, x, dims: AttnDims, *, positions, causal=True, window=0,
              rope_theta=10000.0, q_chunk=512, kv_cache=None, cache_pos=None):
    """Full attention layer.  x: (B, S, D).

    If ``kv_cache`` is given (dict with k/v of shape (B, Smax, K, Dh)), new
    K/V are written at ``cache_pos`` and attention runs over the cache
    (decode / incremental prefill).  Returns (out, new_cache_or_None).

    Under an active mesh the score/softmax core runs inside ``shard_map``
    (batch x heads manual; partial-softmax combine when the KV sequence is
    sharded) — GSPMD replicates the chunked-attention loop state otherwise
    (measured f32 (B,S,H*Dh) all-gathers per layer, EXPERIMENTS.md §Perf).
    """
    from repro.sharding.ops import constrain, current_mesh

    b, s, _ = x.shape
    h, kh, dh = dims.n_heads, dims.n_kv_heads, dims.head_dim
    g = h // kh
    mesh = current_mesh()
    tp = mesh.shape.get("model", 1) if mesh else 1
    # Keep q in MERGED-head layout (B,S,H,Dh) as long as possible: H usually
    # divides the model axis even when kh/g individually don't, and an early
    # (kh, g) reshape forces GSPMD to all-gather the whole (B,S,H*Dh) tensor
    # (measured 103 GB/device/step on qwen3 train_4k — §Perf).
    xq_m = (x @ params["wq"].astype(x.dtype)).reshape(b, s, h, dh)
    xk = (x @ params["wk"].astype(x.dtype)).reshape(b, s, kh, dh)
    xv = (x @ params["wv"].astype(x.dtype)).reshape(b, s, kh, dh)
    xq_m = constrain(xq_m, "batch", None, "tp", None)
    if kh % tp == 0:
        xk = constrain(xk, "batch", None, "tp", None)
        xv = constrain(xv, "batch", None, "tp", None)
    else:
        # kv heads not shardable: pin K/V replicated over `model` — otherwise
        # GSPMD shards head_dim and all-reduces the (B,KH,G,Sq,Skv) score
        # partials (llava prefill_32k: 3.6 TB/device/step, §Perf).
        xk = constrain(xk, "batch", None, None, None)
        xv = constrain(xv, "batch", None, None, None)
    xq_m = apply_rope(xq_m, positions, rope_theta)
    xk = apply_rope(xk, positions, rope_theta)
    merged_tp = tp > 1 and h % tp == 0 and kh % tp != 0

    new_cache = None
    if kv_cache is not None:
        xq = xq_m.reshape(b, s, kh, g, dh)
        ck = jax.lax.dynamic_update_slice(kv_cache["k"], xk.astype(kv_cache["k"].dtype), (0, cache_pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(kv_cache["v"], xv.astype(kv_cache["v"].dtype), (0, cache_pos, 0, 0))
        new_cache = {"k": ck, "v": cv}
        out = _attn_core(
            xq, ck, cv, causal=causal, window=window, q_chunk=q_chunk,
            q_offset=cache_pos, kv_len=cache_pos + s, mesh=mesh,
        )
    else:
        if merged_tp and s > 1:
            # repeat KV to one head per query head; merged heads shard cleanly
            xk = jnp.repeat(xk, g, axis=2)
            xv = jnp.repeat(xv, g, axis=2)
            xq = xq_m.reshape(b, s, h, 1, dh)
        else:
            xq = xq_m.reshape(b, s, kh, g, dh)
        out = _attn_core(xq, xk, xv, causal=causal, window=window, q_chunk=q_chunk,
                         mesh=mesh)
    out = out.reshape(b, s, h * dh)
    return out @ params["wo"].astype(x.dtype), new_cache


def _attn_core(xq, xk, xv, *, causal=True, window=0, q_chunk=512, q_offset=0,
               kv_len=None, mesh=None):
    """Dispatch: local chunked attention, or shard_map'ed (batch x heads
    manual; seq-sharded partial-softmax flash decode when applicable)."""
    from jax.sharding import PartitionSpec as P

    b, sq, kh, g, dh = xq.shape
    skv = xk.shape[1]
    if mesh is None or all(v <= 1 for v in mesh.shape.values()):
        return _chunked_softmax_attn(
            xq, xk, xv, causal=causal, window=window, q_chunk=q_chunk,
            q_offset=q_offset, kv_len=kv_len,
        )
    batch_axes, kv_sharded, seq_axis = _attn_specs(mesh, b, kh, g, skv, sq)
    tp = mesh.shape.get("model", 1)
    if sq > 1 and not kv_sharded:
        if tp > 1 and (kh * g) % tp == 0:
            # merged-head TP: kh doesn't divide the model axis but H = kh*g
            # does — repeat KV to one head per query head and shard merged
            # heads.  Removes the (B,S,H*Dh) q/k/v all-gathers GSPMD emits
            # for this layout (qwen3 train_4k: 103 GB/device/step).
            xk = jnp.repeat(xk, g, axis=2)
            xv = jnp.repeat(xv, g, axis=2)
            xq = xq.reshape(b, sq, kh * g, 1, dh)
            out = _attn_core(
                xq, xk, xv, causal=causal, window=window, q_chunk=q_chunk,
                q_offset=q_offset, kv_len=kv_len, mesh=mesh,
            )
            return out.reshape(b, sq, kh, g, dh)
        # MQA/small-GQA fallback: GSPMD with the g-dim constraint
        return _chunked_softmax_attn(
            xq, xk, xv, causal=causal, window=window, q_chunk=q_chunk,
            q_offset=q_offset, kv_len=kv_len,
        )
    bax = tuple(batch_axes) if batch_axes else None
    head_kh = "model" if kv_sharded else None
    head_g = "model" if (not kv_sharded and g % mesh.shape.get("model", 1) == 0
                         and mesh.shape.get("model", 1) > 1 and seq_axis != "model") else None
    q_spec = P(bax, None, head_kh, head_g, None)
    kv_spec = P(bax, seq_axis, head_kh, None)
    # traced scalars enter as replicated operands
    w_arr = jnp.asarray(window, jnp.int32)
    off_arr = jnp.asarray(q_offset, jnp.int32)
    len_arr = jnp.asarray(skv if kv_len is None else kv_len, jnp.int32)

    static_window = window if isinstance(window, int) else None

    if seq_axis is None:

        def body(q, k, v, w, off, klen):
            win = static_window if static_window is not None else w
            return _chunked_softmax_attn(
                q, k, v, causal=causal, window=win, q_chunk=q_chunk,
                q_offset=off, kv_len=klen,
            )

    else:
        seq_shards = mesh.shape[seq_axis]

        def body(q, k, v, w, off, klen):
            win = static_window if static_window is not None else w
            return _flash_decode_partial(q, k, v, win, off, klen, seq_axis, seq_shards)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P(), P(), P()),
        out_specs=q_spec,
        check_vma=False,
    )
    return fn(xq, xk, xv, w_arr, off_arr, len_arr)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_params(key, d_model: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff)),
        "w_up": dense_init(k2, (d_model, d_ff)),
        "w_down": dense_init(k3, (d_ff, d_model)),
    }


def mlp(params, x, act: str = "silu"):
    a = act_fn(act)
    gate = a(x @ params["w_gate"].astype(x.dtype))
    up = x @ params["w_up"].astype(x.dtype)
    return (gate * up) @ params["w_down"].astype(x.dtype)

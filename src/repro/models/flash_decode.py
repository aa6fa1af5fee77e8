"""Flash-decoding over a sequence-sharded KV cache (shard_map).

Problem: GQA archs whose KV-head count doesn't divide the TP axis (e.g.
qwen2-72b: 8 KV heads on a 16-way `model` axis) shard the decode cache
along SEQUENCE instead.  Plain einsum attention then makes XLA all-gather
the whole cache every layer (the 18 s collective term in the baseline
roofline).  The fix is the TPU-native form of flash-decoding: each model
shard computes attention over its local S-chunk, and the shards combine
with (max, rescaled-sum) — 3 tiny collectives of (B, H[, hd]) instead of
gathering (B, S, KV, hd).

Math (per head): softmax over the union of chunks
    m_g = pmax(m_i);  num = psum(e^{m_i−m_g}·num_i);  den = psum(e^{m_i−m_g}·den_i)
    out = num / den — exactly softmax(q·Kᵀ)·V, numerically stabilized.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _local_attn(q, k, v, pos, window, *, shard_axis: str, n_rep: int):
    """One shard's partial attention.
    q (Bl, 1, H, hd) full heads; k/v (Bl, Sl, KV, hd) local chunk."""
    bl, sl, kv, hd = k.shape
    i = lax.axis_index(shard_axis)
    kpos = i * sl + jnp.arange(sl)                      # global positions
    valid = kpos <= pos                                 # causal/cache-len
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        valid &= jnp.where(w > 0, kpos > pos - w, True)

    kr = jnp.repeat(k, n_rep, axis=2)                   # (Bl, Sl, H, hd)
    vr = jnp.repeat(v, n_rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    logits = jnp.where(valid[None, None, None, :], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)                        # (Bl, H, 1)
    # all-invalid shard: guard -inf
    m_safe = jnp.where(jnp.isfinite(m), m, -1e30)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(valid[None, None, None, :], p, 0.0)
    den = jnp.sum(p, axis=-1)                           # (Bl, H, 1)
    num = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), vr)

    g_m = lax.pmax(m_safe, shard_axis)
    scale = jnp.exp(m_safe - g_m)                       # (Bl, H, 1)
    num = lax.psum(num * scale.transpose(0, 2, 1)[..., None]
                   .astype(num.dtype), shard_axis)
    den = lax.psum(den * scale, shard_axis)             # (Bl, H, 1)
    out = num / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None] \
        .astype(num.dtype)
    return out                                          # (Bl, 1, H, hd)


def flash_decode(q, ck, cv, pos, *, mesh, dp_axes: tuple, n_rep: int,
                 window=None, shard_axis: str = "model"):
    """q (B,1,H,hd) replicated over `model`; ck/cv (B,S,KV,hd) sharded
    (dp, model) on (B, S).  Returns (B,1,H,hd) sharded on B only."""
    dp = tuple(dp_axes) if dp_axes else None
    fn = partial(_local_attn, shard_axis=shard_axis, n_rep=n_rep)
    return jax.shard_map(
        lambda qq, kk, vv: fn(qq, kk, vv, pos, window),
        mesh=mesh,
        in_specs=(P(dp, None, None, None), P(dp, shard_axis, None, None),
                  P(dp, shard_axis, None, None)),
        out_specs=P(dp, None, None, None),
    )(q, ck, cv)

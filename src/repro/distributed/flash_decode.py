"""Distributed flash-decode: explicit shard_map partial-softmax combine.

HC3 shards the KV cache's *sequence* dim over the "model" axis. Under
plain GSPMD, XLA all-gathers K/V per layer; the production path computes
per-shard partial attention (m, l, acc) with the decode_attention
blockwise math and combines across shards with three small collectives —
O(B·H·hd) on the wire instead of O(B·S·kv·hd):

    m*   = max_shards m_i
    l*   = Σ_i l_i · exp(m_i − m*)
    out  = Σ_i acc_i · exp(m_i − m*) / l*
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _partial_attention(q, k, v, valid):
    """Local partial softmax-attention over this shard's keys.

    q: (B,1,H,hd); k/v: (B,S_loc,KVH,hd); valid: (B,S_loc).
    Returns (m (B,H), l (B,H), acc (B,H,hd)) — unnormalized.
    """
    B, _, H, hd = q.shape
    KVH = k.shape[2]
    if KVH != H:
        k = jnp.repeat(k, H // KVH, axis=2)
        v = jnp.repeat(v, H // KVH, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5      # (B,H,S_loc)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = s.max(-1)                                           # (B,H)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, :], p, 0.0)                # kill exp(-inf-...)
    l = p.sum(-1)
    acc = jnp.einsum("bhk,bkhd->bhd", p, v.astype(jnp.float32))
    return m, l, acc


def sharded_decode_attention(q, k_cache, v_cache, valid, *, mesh,
                             seq_axis: str = "model"):
    """One-token attention with the cache sequence dim sharded over
    ``seq_axis``. q replicated along that axis; returns (B,1,H,hd)."""

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(None, seq_axis, None, None),
                  P(None, seq_axis, None, None), P(None, seq_axis)),
        out_specs=P(),
        check_vma=False)
    def _inner(q, k, v, valid):
        m, l, acc = _partial_attention(q, k, v, valid)
        m_star = jax.lax.pmax(m, seq_axis)                  # (B,H)
        scale = jnp.exp(m - m_star)
        l_star = jax.lax.psum(l * scale, seq_axis)
        out = jax.lax.psum(acc * scale[..., None], seq_axis)
        out = out / jnp.maximum(l_star, 1e-30)[..., None]
        return out[:, None].astype(q.dtype)                 # (B,1,H,hd)

    return _inner(q, k_cache, v_cache, valid)

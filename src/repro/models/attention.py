"""GQA/MHA attention with KV cache, causal and sliding-window masks.

Three entry points:
  * ``attend_full``   — training / prefill over a whole sequence.
  * ``attend_decode`` — one new token against a filled KV cache.
  * ``init_kv_cache`` — cache pytree (used by the rollout engine and the
    decode-shape dry-runs).

The pure-jnp path is the reference; ``repro.kernels.flash_attention`` and
``repro.kernels.decode_attention`` provide the Pallas TPU implementations
selected via ``use_pallas``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rotary, dense, init_dense

NEG_INF = -1e30


def init_attention(key, cfg, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    nh, nkv, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    return {
        "wq": init_dense(ks[0], d, nh * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": init_dense(ks[1], d, nkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": init_dense(ks[2], d, nkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": init_dense(ks[3], nh * hd, d, dtype=dtype),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def sdpa(q, k, v, mask):
    """q: (B,Sq,H,hd) k/v: (B,Sk,H,hd) mask: broadcastable (B,1,Sq,Sk)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores * (hd ** -0.5)
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def grouped_decode_sdpa(q, k, v, valid):
    """One query token against an unrepeated GQA cache.

    q: (B,1,H,hd); k/v: (B,S,KV,hd) with H a multiple of KV; valid: (B,S).
    Each group of H // KV query heads attends its one KV head inside the
    einsums, so the cache is read once and never repeated per query head.
    """
    B, _, nh, hd = q.shape
    nkv = k.shape[2]
    qg = q.reshape(B, nkv, nh // nkv, hd)
    scores = jnp.einsum("bgrd,bkgd->bgrk", qg, k,
                        preferred_element_type=jnp.float32) * (hd ** -0.5)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrk,bkgd->bgrd", w, v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype).reshape(B, 1, nh, hd)


def causal_mask(sq, sk, q_offset=0, window=0):
    """(1,1,sq,sk) causal mask; ``window``>0 adds a sliding-window band."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    m = kpos <= qpos
    if window > 0:
        m = m & (kpos > qpos - window)
    return m[None, None]


def attend_full(p, x, cfg, positions=None, *, window=0, cross_kv=None,
                causal=True, use_pallas=False):
    """Full-sequence attention (train / prefill / encoder / cross).

    cross_kv: optional (k_src, v_src) already-projected encoder memory for
    cross-attention (no mask).
    """
    B, S, _ = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cd = x.dtype
    q = _split_heads(dense(p["wq"], x, cd), nh, hd)
    if cross_kv is None:
        k = _split_heads(dense(p["wk"], x, cd), nkv, hd)
        v = _split_heads(dense(p["wv"], x, cd), nkv, hd)
        if positions is None:
            positions = jnp.arange(S)[None, :]
        q = apply_rotary(q, positions, cfg.rope_theta)
        k = apply_rotary(k, positions, cfg.rope_theta)
    else:
        k, v = cross_kv

    if use_pallas and cross_kv is None and causal:
        from repro.kernels.flash_attention.ops import flash_attention
        out = flash_attention(q, k, v, window=window)
    else:
        kk = _repeat_kv(k, nh // k.shape[2])
        vv = _repeat_kv(v, nh // v.shape[2])
        if cross_kv is not None or not causal:
            mask = jnp.ones((1, 1, S, k.shape[1]), bool)
        else:
            mask = causal_mask(S, S, window=window)
        out = sdpa(q, kk, vv, mask)
    return dense(p["wo"], out.reshape(B, S, nh * hd), cd)


def project_cross_kv(p, memory, cfg):
    """Precompute encoder K/V once for all decode steps."""
    nkv, hd = cfg.num_kv_heads, cfg.head_dim
    k = _split_heads(dense(p["wk"], memory, memory.dtype), nkv, hd)
    v = _split_heads(dense(p["wv"], memory, memory.dtype), nkv, hd)
    return k, v


def init_kv_cache(cfg, batch, length, dtype=jnp.bfloat16, layers=None):
    """Stacked-over-layers GQA cache."""
    L = cfg.num_layers if layers is None else layers
    shape = (L, batch, length, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attend_decode(p, x, layer_cache, pos, cfg, *, ring=False, write=True,
                  use_pallas=False, mesh=None):
    """One-token decode.

    x: (B, 1, d); layer_cache: {"k","v"} of (B, S_cache, nkv, hd);
    pos: (B,) current absolute position of the new token.
    ring=True → sliding-window ring buffer (cache slot = pos % S_cache).
    write=False → read-only attention over the full provided cache (used for
    cross-attention with precomputed encoder K/V); no rotary on q either.
    mesh → route attention through distributed/flash_decode's sharded
    partial-softmax combine (cache seq dim sharded over "model").

    Returns (out (B,1,d), updated layer_cache).
    """
    B = x.shape[0]
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cd = x.dtype
    q = _split_heads(dense(p["wq"], x, cd), nh, hd)

    k_cache, v_cache = layer_cache["k"], layer_cache["v"]
    S = k_cache.shape[1]

    if write:
        q = apply_rotary(q, pos[:, None], cfg.rope_theta)
        k_new = _split_heads(dense(p["wk"], x, cd), nkv, hd)
        v_new = _split_heads(dense(p["wv"], x, cd), nkv, hd)
        k_new = apply_rotary(k_new, pos[:, None], cfg.rope_theta)

        slot = pos % S if ring else jnp.minimum(pos, S - 1)
        bidx = jnp.arange(B)
        k_cache = k_cache.at[bidx, slot].set(k_new[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[bidx, slot].set(v_new[:, 0].astype(v_cache.dtype))

        kpos = jnp.arange(S)[None, :]
        n_filled = jnp.minimum(pos + 1, S)[:, None]
        valid = (kpos < n_filled) if ring else (kpos <= pos[:, None])
    else:
        valid = jnp.ones((B, S), bool)

    if mesh is not None:
        from repro.distributed.flash_decode import sharded_decode_attention
        out = sharded_decode_attention(q, k_cache.astype(cd),
                                       v_cache.astype(cd), valid, mesh=mesh)
    elif use_pallas:
        from repro.kernels.decode_attention.ops import decode_attention
        out = decode_attention(q, k_cache.astype(cd), v_cache.astype(cd), valid)
    else:
        out = grouped_decode_sdpa(q, k_cache.astype(cd), v_cache.astype(cd),
                                  valid)

    out = dense(p["wo"], out.reshape(B, 1, nh * hd), cd)
    return out, {"k": k_cache, "v": v_cache}

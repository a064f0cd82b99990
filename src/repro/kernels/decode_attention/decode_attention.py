"""Flash-decode — single-token attention against a long KV cache.

TPU adaptation: the KV cache is streamed through VMEM in BK-row blocks
along the trailing (sequential) grid dim; the per-(batch, head) partial
softmax state (m, l, acc) is carried in VMEM scratch and finalized on the
last block. A validity mask stream handles ring-buffer/partially-filled
caches. This is the decode_32k / long_500k hotspot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, num_k_blocks, scale):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)              # (BK, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    valid = valid_ref[0]                             # (1, BK)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    s = jnp.where(valid != 0, s, NEG_INF)            # (1, BK)

    m_prev = m_ref[...]                              # (1, 1)
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ v
    m_ref[...] = m_new

    @pl.when(j == num_k_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_kernel(q, k_cache, v_cache, valid, *, block_k=512,
                            interpret=False):
    """q: (B, 1, H, hd); k/v_cache: (B, S, KVH, hd); valid: (B, S) bool.

    Returns (B, 1, H, hd)."""
    B, _, H, hd = q.shape
    S, KVH = k_cache.shape[1], k_cache.shape[2]
    n_rep = H // KVH
    block_k = min(block_k, S)
    assert S % block_k == 0
    nk = S // block_k

    qt = q.transpose(0, 2, 1, 3)                      # (B,H,1,hd)
    kt = k_cache.transpose(0, 2, 1, 3)                # (B,KVH,S,hd)
    vt = v_cache.transpose(0, 2, 1, 3)
    # (B, 1, S) int32: a (1, BK) block of it spans the sublane dim, which
    # a (1, BK) block of the (B, S) mask does not
    vm = valid.astype(jnp.int32)[:, None, :]

    kernel = functools.partial(_decode_kernel, num_k_blocks=nk,
                               scale=hd ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, j, n_rep=n_rep: (b, h // n_rep, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, j, n_rep=n_rep: (b, h // n_rep, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, vm)
    return out.transpose(0, 2, 1, 3)

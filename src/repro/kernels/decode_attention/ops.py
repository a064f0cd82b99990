"""jit'd wrapper; interpret-mode off-TPU. A cache length that does not
divide the block is padded with invalid positions."""
import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import \
    decode_attention_kernel
from repro.kernels.pad_utils import round_up


def decode_attention(q, k_cache, v_cache, valid, *, block_k=512):
    S = k_cache.shape[1]
    bk = min(block_k, S)
    if S % bk:
        pad = round_up(S, bk) - S
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    return decode_attention_kernel(q, k_cache, v_cache, valid, block_k=bk,
                                   interpret=jax.default_backend() != "tpu")

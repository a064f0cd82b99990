"""jit'd public wrapper: Pallas kernel on TPU, interpret-mode elsewhere.
A sequence that does not divide the block is zero-padded to one that
does; causal masking keeps the padded keys out of every real row."""
import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_kernel
from repro.kernels.pad_utils import round_up


def _on_tpu():
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, window=0, block_q=128, block_k=128):
    Sq, Sk = q.shape[1], k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    if not (Sq % bq or Sk % bk):
        return flash_attention_kernel(q, k, v, window=window, block_q=bq,
                                      block_k=bk, interpret=not _on_tpu())
    if Sq != Sk:
        # padded keys sit past every real query only when the two
        # sequences start and end together
        raise ValueError(f"flash_attention: Sq={Sq} and Sk={Sk} differ and "
                         f"do not tile blocks ({bq}, {bk})")
    S = round_up(Sq, max(bq, bk))
    pad = ((0, 0), (0, S - Sq), (0, 0), (0, 0))
    out = flash_attention_kernel(
        jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad), window=window,
        block_q=bq, block_k=bk, interpret=not _on_tpu())
    return out[:, :Sq]

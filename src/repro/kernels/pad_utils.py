"""Pad-and-mask helpers shared by the vocab-streaming kernels.

Pallas grids want block-divisible shapes; real batches rarely oblige.
The convention here:

  * rows (token axis)  — pad with zeros, slice the tail off the outputs.
    Padded rows compute garbage that is never read.
  * vocab (class axis) — pad with ``NEG_INF`` so padded logits vanish
    under exp() inside the online log-sum-exp. Safe because the first
    vocab block always holds real values, so the running max is finite
    before any padded block streams by (exp(NEG_INF - m) underflows
    to exactly 0.0, and 0.0 * NEG_INF never occurs: the kernels multiply
    p * x only where p came from real logits or is exactly zero times a
    finite rescale).

``pick_blocks`` rounds block sizes to hardware-friendly multiples
(8 sublanes, 128 lanes) capped by the padded extent.
"""
from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def pick_blocks(n: int, v: int, block_n: int, block_v: int):
    """Return (bn, bv, n_pad, v_pad): block sizes + padded extents."""
    bn = min(block_n, round_up(n, 8))
    bv = min(block_v, round_up(v, 128))
    return bn, bv, round_up(n, bn), round_up(v, bv)


def pad_logits(x, n_pad: int, v_pad: int):
    """Pad (N, V) logits: zero rows below, NEG_INF columns to the right."""
    n, v = x.shape
    if v_pad > v:
        x = jnp.pad(x, ((0, 0), (0, v_pad - v)),
                    constant_values=jnp.asarray(NEG_INF, x.dtype))
    if n_pad > n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    return x


def pad_rows(x, n_pad: int, fill=0):
    """Pad a per-token (N,) vector with ``fill`` up to n_pad rows."""
    n = x.shape[0]
    if n_pad > n:
        x = jnp.pad(x, (0, n_pad - n), constant_values=fill)
    return x

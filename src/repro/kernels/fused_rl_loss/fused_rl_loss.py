"""Fused GRPO/PPO actor hot path over large-vocab logits — Pallas kernels.

The actor update's dominant cost is touching the (B·S, V) logits, V up
to 256k. Composing ``token_logprobs`` + ``kl_penalty`` +
``clipped_policy_loss`` reads that array once forward and — through
autodiff of log-softmax — again backward, materializing a second
(B·S, V) residual in between. This module streams vocab blocks through
VMEM **once** per pass instead:

forward (``_fwd_kernel``, extends the ``grpo_logprob`` online-LSE
skeleton):

  m, l   — running max / rescaled Σ exp (online log-sum-exp)
  t      — running Σ exp(x − m)·x            (entropy)
  g      — the target token's logit          (iota-compare pick, summed
                                              over blocks)

and, on the last vocab block, finishes the whole per-token epilogue in
registers: logprob ``lp = g − lse``, entropy ``ent = lse − t/l``, k3 KL
``exp(d) − d − 1`` with ``d = ref_lp − lp``, importance ratio
``exp(lp − old_lp)`` and the clipped surrogate
``−min(ratio·A, clip(ratio)·A)``.

backward (``_bwd_kernel``): no (N, V) residual is saved. With
``p = softmax(x)`` recomputed per block from the saved (N,) statistics
(``p = exp(x − lse)``) and ``x̄ = Σ p·x = lse − ent``:

  ∂lp/∂x_j  = δ_jt − p_j
  ∂ent/∂x_j = −p_j (x_j − x̄)

so every per-token output folds into two scalars — ``dlp`` (total
cotangent reaching lp) and ``g_ent`` — and

  dx_j = dlp·δ_jt − p_j (dlp + g_ent·(x_j − x̄))

which the kernel evaluates blockwise in one more single pass over the
logits. The chain-rule scalars live in ``ops.py`` (shared with the
pure-jnp route so both hit the same custom VJP).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pad_utils import (NEG_INF, pad_logits, pad_rows,
                                     pick_blocks)


def _pick_target(x, local):
    """x[r, local[r]] for (BN, BV) x and (BN, 1) block-local targets; 0
    where the target is not in this block. An iota compare and a row sum
    stand in for a gather, which Mosaic does not lower."""
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(cols == local, x, 0.0).sum(-1, keepdims=True)


def _fwd_kernel(logits_ref, target_ref, old_ref, ref_ref, adv_ref,
                lp_ref, ent_ref, kl_ref, pl_ref, ratio_ref, lse_ref,
                m_ref, l_ref, t_ref, g_ref, *,
                block_v, num_v_blocks, clip_eps):
    jv = pl.program_id(1)

    @pl.when(jv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        t_ref[...] = jnp.zeros_like(t_ref)
        g_ref[...] = jnp.zeros_like(g_ref)

    x = logits_ref[...].astype(jnp.float32)          # (BN, BV)

    m_prev = m_ref[...]                              # (BN, 1)
    m_new = jnp.maximum(m_prev, x.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(x - m_new)
    l_ref[...] = alpha * l_ref[...] + p.sum(-1, keepdims=True)
    t_ref[...] = alpha * t_ref[...] + (p * x).sum(-1, keepdims=True)
    m_ref[...] = m_new
    # the target sits in exactly one vocab block: summing the per-block
    # picks over all blocks yields its logit
    g_ref[...] += _pick_target(x, target_ref[...] - jv * block_v)

    @pl.when(jv == num_v_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        lse = m_ref[...] + jnp.log(l)
        lp = g_ref[...] - lse
        ent = lse - t_ref[...] / l

        old = old_ref[...].astype(jnp.float32)
        ref = ref_ref[...].astype(jnp.float32)
        adv = adv_ref[...].astype(jnp.float32)

        ratio = jnp.exp(lp - old)
        unclipped = ratio * adv
        clipped = jnp.clip(ratio, 1.0 - clip_eps, 1.0 + clip_eps) * adv
        d = ref - lp                                  # k3 KL estimator
        kl = jnp.exp(d) - d - 1.0

        lp_ref[...] = lp
        ent_ref[...] = ent
        kl_ref[...] = kl
        pl_ref[...] = -jnp.minimum(unclipped, clipped)
        ratio_ref[...] = ratio
        lse_ref[...] = lse


def _bwd_kernel(logits_ref, target_ref, lse_ref, xbar_ref, dlp_ref,
                gent_ref, dx_ref, *, block_v):
    jv = pl.program_id(1)
    x = logits_ref[...].astype(jnp.float32)          # (BN, BV)
    p = jnp.exp(x - lse_ref[...])                    # softmax, recomputed

    local = target_ref[...] - jv * block_v           # (BN, 1)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
              == local).astype(jnp.float32)

    dlp = dlp_ref[...]
    dx = dlp * onehot - p * (dlp + gent_ref[...] * (x - xbar_ref[...]))
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _row_spec(bn):
    """Per-row vectors travel as (N, 1) columns: a 2-D block whose lane
    dim spans the array is tiled alike by XLA and Mosaic, where a 1-D
    (BN,) block is not."""
    return pl.BlockSpec((bn, 1), lambda i, j: (i, 0))


def _col(x, n_pad):
    return pad_rows(x, n_pad)[:, None]


@functools.partial(jax.jit, static_argnames=("clip_eps", "block_n",
                                             "block_v", "interpret"))
def fused_rl_loss_fwd_kernel(logits, targets, old_logprob, ref_logprob,
                             advantage, *, clip_eps=0.2, block_n=256,
                             block_v=2048, interpret=False):
    """One streamed pass: (N, V) logits + four (N,) vectors ->
    (lp, ent, kl, pl, ratio, lse), each (N,) float32. Any (N, V) shape:
    rows/vocab are padded to block multiples and the tail sliced off."""
    N, V = logits.shape
    bn, bv, n_pad, v_pad = pick_blocks(N, V, block_n, block_v)
    nn, nv = n_pad // bn, v_pad // bv

    lg = pad_logits(logits, n_pad, v_pad)
    rows = [_col(a, n_pad) for a in (targets, old_logprob, ref_logprob,
                                     advantage)]

    kernel = functools.partial(_fwd_kernel, block_v=bv, num_v_blocks=nv,
                               clip_eps=float(clip_eps))
    row = _row_spec(bn)
    outs = pl.pallas_call(
        kernel,
        grid=(nn, nv),
        in_specs=[pl.BlockSpec((bn, bv), lambda i, j: (i, j))] + [row] * 4,
        out_specs=[row] * 6,
        out_shape=[jax.ShapeDtypeStruct((n_pad, 1), jnp.float32)] * 6,
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)] * 4,
        interpret=interpret,
    )(lg, *rows)
    return tuple(o[:N, 0] for o in outs)


@functools.partial(jax.jit, static_argnames=("block_n", "block_v",
                                             "interpret"))
def fused_rl_loss_bwd_kernel(logits, targets, lse, xbar, dlp, g_ent, *,
                             block_n=256, block_v=2048, interpret=False):
    """Second streamed pass: dlogits from saved (N,) statistics only."""
    N, V = logits.shape
    bn, bv, n_pad, v_pad = pick_blocks(N, V, block_n, block_v)
    nn, nv = n_pad // bn, v_pad // bv

    lg = pad_logits(logits, n_pad, v_pad)
    # padded rows: lse=0 makes p = exp(0-0) = 1 — harmless (their
    # dlp/g_ent are 0 and the rows are sliced off)
    rows = [_col(a, n_pad) for a in (targets, lse, xbar, dlp, g_ent)]

    kernel = functools.partial(_bwd_kernel, block_v=bv)
    row = _row_spec(bn)
    dx = pl.pallas_call(
        kernel,
        grid=(nn, nv),
        in_specs=[pl.BlockSpec((bn, bv), lambda i, j: (i, j))] + [row] * 5,
        out_specs=pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, v_pad), logits.dtype),
        interpret=interpret,
    )(lg, *rows)
    return dx[:N, :V]

"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

key = jax.random.PRNGKey(0)


def k(i):
    return jax.random.fold_in(key, i)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 4, 4, 64),       # MHA
    (2, 256, 4, 2, 64),       # GQA 2:1
    (1, 256, 8, 1, 32),       # MQA
    (2, 128, 4, 4, 128),      # MXU-aligned head_dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention(B, S, H, KV, hd, dtype, window):
    from repro.kernels.flash_attention import (flash_attention,
                                               flash_attention_ref)
    q = jax.random.normal(k(1), (B, S, H, hd), dtype)
    kk = jax.random.normal(k(2), (B, S, KV, hd), dtype)
    v = jax.random.normal(k(3), (B, S, KV, hd), dtype)
    out = flash_attention(q, kk, v, window=window)
    ref = flash_attention_ref(q, kk, v, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_odd_shape_falls_back():
    from repro.kernels.flash_attention import flash_attention, \
        flash_attention_ref
    q = jax.random.normal(k(1), (1, 100, 2, 16))
    kv = jax.random.normal(k(2), (1, 100, 2, 16))
    np.testing.assert_allclose(flash_attention(q, kv, kv),
                               flash_attention_ref(q, kv, kv),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention_pads_untiled_length(window):
    """S=200 does not divide the 128 block: the kernel runs on a padded
    sequence instead of handing the call to the oracle."""
    from repro.kernels.flash_attention import flash_attention, \
        flash_attention_ref
    q = jax.random.normal(k(1), (1, 200, 4, 32))
    kv = jax.random.normal(k(2), (1, 200, 2, 32))
    np.testing.assert_allclose(flash_attention(q, kv, kv, window=window),
                               flash_attention_ref(q, kv, kv, window=window),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_untiled_cross_lengths_raise():
    from repro.kernels.flash_attention import flash_attention
    q = jax.random.normal(k(1), (1, 200, 2, 16))
    kv = jax.random.normal(k(2), (1, 300, 2, 16))
    with pytest.raises(ValueError, match="do not tile"):
        flash_attention(q, kv, kv)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd", [
    (2, 1024, 4, 2, 64),
    (1, 2048, 8, 8, 32),
    (3, 512, 4, 1, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, S, H, KV, hd, dtype):
    from repro.kernels.decode_attention import (decode_attention,
                                                decode_attention_ref)
    q = jax.random.normal(k(1), (B, 1, H, hd), dtype)
    kc = jax.random.normal(k(2), (B, S, KV, hd), dtype)
    vc = jax.random.normal(k(3), (B, S, KV, hd), dtype)
    fill = jax.random.randint(k(4), (B,), 1, S + 1)
    valid = jnp.arange(S)[None, :] < fill[:, None]
    out = decode_attention(q, kc, vc, valid)
    ref = decode_attention_ref(q, kc, vc, valid)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_decode_attention_pads_untiled_cache():
    """A 700-long cache does not divide the 512 block: it is padded with
    invalid positions rather than sent to the oracle."""
    from repro.kernels.decode_attention import (decode_attention,
                                                decode_attention_ref)
    B, S, H, KV, hd = 2, 700, 4, 2, 32
    q = jax.random.normal(k(1), (B, 1, H, hd))
    kc = jax.random.normal(k(2), (B, S, KV, hd))
    vc = jax.random.normal(k(3), (B, S, KV, hd))
    valid = jnp.arange(S)[None, :] < jnp.asarray([[650], [700]])
    np.testing.assert_allclose(decode_attention(q, kc, vc, valid),
                               decode_attention_ref(q, kc, vc, valid),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# rglru_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,W", [(1, 256, 128), (2, 512, 256), (3, 128, 384)])
def test_rglru_scan(B, S, W):
    from repro.kernels.rglru_scan import rglru_scan, rglru_scan_ref
    a = jax.random.uniform(k(1), (B, S, W), minval=0.4, maxval=0.999)
    b = jax.random.normal(k(2), (B, S, W))
    np.testing.assert_allclose(rglru_scan(a, b), rglru_scan_ref(a, b),
                               atol=2e-4, rtol=2e-4)


def test_rglru_scan_block_boundary_carry():
    """State must carry exactly across sequence-block boundaries."""
    from repro.kernels.rglru_scan import rglru_scan, rglru_scan_ref
    a = jnp.full((1, 512, 128), 0.9)
    b = jnp.ones((1, 512, 128))
    out = rglru_scan(a, b, block_s=128)
    ref = rglru_scan_ref(a, b)
    np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-4)


# ---------------------------------------------------------------------------
# mamba_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,D,N", [(1, 128, 128, 16), (2, 256, 256, 8)])
def test_mamba_scan(B, S, D, N):
    from repro.kernels.mamba_scan import mamba_scan, mamba_scan_ref
    x = jax.random.normal(k(1), (B, S, D))
    dt = 0.1 * jax.nn.softplus(jax.random.normal(k(2), (B, S, D)))
    a = -jnp.abs(jax.random.normal(k(3), (D, N)))
    b = jax.random.normal(k(4), (B, S, N))
    c = jax.random.normal(k(5), (B, S, N))
    np.testing.assert_allclose(mamba_scan(x, dt, a, b, c),
                               mamba_scan_ref(x, dt, a, b, c),
                               atol=2e-4, rtol=2e-3)


# ---------------------------------------------------------------------------
# grpo_logprob
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,V", [(256, 2048), (512, 4096), (512, 8192)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grpo_logprob(N, V, dtype):
    from repro.kernels.grpo_logprob import grpo_logprob, grpo_logprob_ref
    logits = (5 * jax.random.normal(k(1), (N, V))).astype(dtype)
    tgt = jax.random.randint(k(2), (N,), 0, V)
    lp, ent = grpo_logprob(logits, tgt)
    lpr, entr = grpo_logprob_ref(logits.astype(jnp.float32), tgt)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(lp, lpr, atol=tol, rtol=tol)
    np.testing.assert_allclose(ent, entr, atol=5 * tol, rtol=5 * tol)


def test_grpo_logprob_batched_shape():
    from repro.kernels.grpo_logprob.ops import grpo_logprob
    logits = jax.random.normal(k(1), (2, 8, 512))
    tgt = jax.random.randint(k(2), (2, 8), 0, 512)
    lp, ent = grpo_logprob(logits, tgt)
    assert lp.shape == (2, 8) and ent.shape == (2, 8)
    assert bool((ent >= -1e-3).all())  # entropy non-negative


@pytest.mark.parametrize("N,V", [(100, 1000), (7, 131), (257, 2049)])
def test_grpo_logprob_non_divisible_shapes(N, V):
    """Pad-and-mask: arbitrary (N, V) run through the kernel, no
    block-divisibility requirement."""
    from repro.kernels.grpo_logprob import grpo_logprob, grpo_logprob_ref
    logits = 5 * jax.random.normal(k(1), (N, V))
    tgt = jax.random.randint(k(2), (N,), 0, V)
    lp, ent = grpo_logprob(logits, tgt)
    assert lp.shape == (N,) and ent.shape == (N,)
    lpr, entr = grpo_logprob_ref(logits, tgt)
    np.testing.assert_allclose(lp, lpr, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(ent, entr, atol=5e-4, rtol=5e-4)


# ---------------------------------------------------------------------------
# fused_rl_loss: logprob + entropy + k3 KL + clipped surrogate, custom VJP
# ---------------------------------------------------------------------------

def _rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1.0))


def _fused_inputs(N, V, dtype=jnp.float32):
    logits = (5 * jax.random.normal(k(11), (N, V))).astype(dtype)
    tgt = jax.random.randint(k(12), (N,), 0, V)
    old = 0.1 * jax.random.normal(k(13), (N,)) - 2.0
    ref = 0.1 * jax.random.normal(k(14), (N,)) - 2.0
    adv = jax.random.normal(k(15), (N,))
    return logits, tgt, old, ref, adv


_OUT_NAMES = ("logprob", "entropy", "kl", "policy_loss", "ratio")


@pytest.mark.parametrize("N,V", [(16, 256), (13, 300), (7, 131)])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_fused_rl_loss_values(N, V, use_pallas):
    from repro.kernels.fused_rl_loss import fused_rl_loss, fused_rl_loss_ref
    logits, tgt, old, ref, adv = _fused_inputs(N, V)
    outs = fused_rl_loss(logits, tgt, old, ref, adv,
                         use_pallas=use_pallas, block_n=8, block_v=128)
    refs = fused_rl_loss_ref(logits, tgt, old, ref, adv)
    for name, o, r in zip(_OUT_NAMES, outs, refs):
        assert o.shape == (N,), name
        assert _rel_err(o, r) < 1e-4, name


@pytest.mark.parametrize("N,V", [(16, 256), (13, 300)])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_fused_rl_loss_grads_match_reference(N, V, use_pallas):
    """Hand-written VJP (one streaming vocab pass, softmax recomputed from
    saved statistics) vs jax.grad through the materializing reference —
    gradients for logits, old/ref logprobs and advantages all line up."""
    from repro.kernels.fused_rl_loss import fused_rl_loss, fused_rl_loss_ref
    logits, tgt, old, ref, adv = _fused_inputs(N, V)
    w = [0.3, -0.2, 0.7, 1.0, 0.1]    # mix every output into the scalar

    def scalarize(fn):
        def f(lg, o, r, a):
            outs = fn(lg, tgt, o, r, a)
            return sum(wi * jnp.sum(oi) for wi, oi in zip(w, outs))
        return f

    def fused(lg, t, o, r, a):
        return fused_rl_loss(lg, t, o, r, a, use_pallas=use_pallas,
                             block_n=8, block_v=128)

    g_f = jax.grad(scalarize(fused), argnums=(0, 1, 2, 3))(
        logits, old, ref, adv)
    g_r = jax.grad(scalarize(fused_rl_loss_ref), argnums=(0, 1, 2, 3))(
        logits, old, ref, adv)
    for name, gf, gr in zip(("dlogits", "dold", "dref", "dadv"), g_f, g_r):
        assert _rel_err(gf, gr) < 1e-4, name


def test_fused_rl_loss_bf16_smoke():
    from repro.kernels.fused_rl_loss import fused_rl_loss, fused_rl_loss_ref
    logits, tgt, old, ref, adv = _fused_inputs(16, 256, jnp.bfloat16)
    outs = fused_rl_loss(logits, tgt, old, ref, adv, use_pallas=True,
                         block_n=8, block_v=128)
    refs = fused_rl_loss_ref(logits.astype(jnp.float32), tgt, old, ref, adv)
    for name, o, r in zip(_OUT_NAMES, outs, refs):
        assert _rel_err(o, r) < 5e-2, name


def test_fused_rl_loss_batched_shape():
    from repro.kernels.fused_rl_loss import fused_rl_loss
    B, S, V = 2, 9, 260
    logits = jax.random.normal(k(21), (B, S, V))
    tgt = jax.random.randint(k(22), (B, S), 0, V)
    old = jnp.zeros((B, S))
    refp = jnp.zeros((B, S))
    adv = jnp.ones((B, S))
    outs = fused_rl_loss(logits, tgt, old, refp, adv, block_n=8, block_v=128)
    for o in outs:
        assert o.shape == (B, S)
    lp, ent, kl, _, _ = outs
    assert bool((ent >= -1e-3).all())
    assert bool((kl >= -1e-5).all())   # k3 estimator is non-negative

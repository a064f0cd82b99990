"""Grouped-query decode attention reads the unrepeated KV cache.

``attend_decode``'s jnp path attends each group of H // KV query heads to
its one KV head inside the einsums. The reference here is the form it
replaced: the cache repeated to H heads, then plain attention, computed in
float32 from the same operands. The structural test compiles the rollout's
``_generate_jit`` and checks that no repeated cache is ever built.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cfg
from repro.models import init_params
from repro.models.attention import (NEG_INF, attend_decode,
                                    grouped_decode_sdpa, init_attention)
from repro.models.layers import apply_rotary, dense
from repro.rl.sampling import _generate_jit

KV, HD, S = 2, 32, 24
# bf16 keeps 8 significant bits: rounding the softmax weights and the
# output to it moves an output by up to about one ulp of the largest
# output (2**-7 of it); allow two.
BF16_TOL = 2.0 ** -6


def _key(i):
    return jax.random.fold_in(jax.random.PRNGKey(0), i)


def repeat_sdpa(q, k, v, valid):
    """The repeat formula: K/V expanded to one head per query head."""
    r = q.shape[2] // k.shape[2]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    kk, vv = jnp.repeat(k, r, axis=2), jnp.repeat(v, r, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * q.shape[-1] ** -0.5
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), vv)


def _close(got, want, dtype):
    want = np.asarray(want, np.float32)
    tol = 2e-5 if dtype == jnp.float32 else BF16_TOL * np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=tol, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("r", [1, 2, 7])
def test_grouped_decode_sdpa_matches_repeat(r, dtype):
    """A partly filled cache: the rows hold 1, S / 2 and S valid slots."""
    B = 3
    q = jax.random.normal(_key(1), (B, 1, KV * r, HD), dtype)
    k = jax.random.normal(_key(2), (B, S, KV, HD), dtype)
    v = jax.random.normal(_key(3), (B, S, KV, HD), dtype)
    valid = jnp.arange(S)[None, :] < jnp.asarray([[1], [S // 2], [S]])
    got = grouped_decode_sdpa(q, k, v, valid)
    assert got.shape == q.shape and got.dtype == dtype
    _close(got, repeat_sdpa(q, k, v, valid), dtype)


def _ref_attend_decode(p, x, cache, pos, cfg, *, ring, write):
    """``attend_decode`` with the repeat formula, over the cache it
    returned (the write itself is not what is compared)."""
    B, cd = x.shape[0], x.dtype
    nh, hd = cfg.num_heads, cfg.head_dim
    q = dense(p["wq"], x, cd).reshape(B, 1, nh, hd)
    kpos = jnp.arange(cache["k"].shape[1])[None, :]
    if write:
        q = apply_rotary(q, pos[:, None], cfg.rope_theta)
        n_filled = jnp.minimum(pos + 1, cache["k"].shape[1])[:, None]
        valid = (kpos < n_filled) if ring else (kpos <= pos[:, None])
    else:
        valid = jnp.ones((B, kpos.shape[1]), bool)
    out = repeat_sdpa(q, cache["k"], cache["v"], valid)
    return dense(p["wo"], out.reshape(B, 1, nh * hd), cd)


@pytest.mark.parametrize("mode", ["partial", "ring", "cross"])
@pytest.mark.parametrize("r", [1, 2, 7])
def test_attend_decode_matches_repeat(r, mode):
    """bf16 compute. partial: a linear cache filled to different depths;
    ring: a sliding window, one row wrapped past the cache's end; cross:
    ``write=False`` over precomputed K/V, every slot attended."""
    cfg = tiny_cfg(num_heads=KV * r, num_kv_heads=KV, head_dim=HD)
    p = init_attention(_key(4), cfg)
    B = 3
    x = jax.random.normal(_key(5), (B, 1, cfg.d_model), jnp.bfloat16)
    cache = {n: jax.random.normal(_key(6 + i), (B, S, KV, HD), jnp.bfloat16)
             for i, n in enumerate("kv")}
    pos = jnp.asarray({"partial": [0, 9, S - 2], "ring": [3, S - 1, 2 * S + 5],
                       "cross": [0, 0, 0]}[mode], jnp.int32)
    kw = dict(ring=mode == "ring", write=mode != "cross")
    out, new_cache = attend_decode(p, x, cache, pos, cfg, **kw)
    assert out.shape == x.shape
    _close(out, _ref_attend_decode(p, x, new_cache, pos, cfg, **kw),
           jnp.bfloat16)


def _repeated_arrays(hlo_text, *shapes):
    """Arrays of the compiled program whose dimensions are those of one of
    ``shapes``, in any order."""
    wanted = {tuple(sorted(s)) for s in shapes}
    found = re.findall(r"\b(?:bf16|f32|f16)\[([\d,]+)\]", hlo_text)
    return {f for f in found
            if tuple(sorted(int(d) for d in f.split(","))) in wanted}


def test_generate_holds_no_repeated_kv_cache():
    """The rollout's compiled generate program, GQA 14/2 (r = 7), holds no
    (B, S, H, hd) or (B, S, KV, r, hd) array; the repeat formula compiled
    at the same shapes does, so the check can tell the two apart."""
    r = 7
    cfg = tiny_cfg(num_layers=1, num_heads=KV * r, num_kv_heads=KV,
                   head_dim=8)
    B, Lp, new = 3, 5, 6
    S_cache, nh, hd = Lp + new, cfg.num_heads, cfg.head_dim
    shapes = [(B, S_cache, nh, hd), (B, S_cache, KV, r, hd)]
    params = init_params(_key(7), cfg)
    text = _generate_jit.lower(
        params, cfg, jnp.zeros((B, Lp), jnp.int32),
        jnp.full((B,), Lp, jnp.int32), jax.random.PRNGKey(0),
        max_new=new).compile().as_text()
    assert not _repeated_arrays(text, *shapes)

    q = jnp.zeros((B, 1, nh, hd), jnp.bfloat16)
    kv = jnp.zeros((B, S_cache, KV, hd), jnp.bfloat16)
    valid = jnp.ones((B, S_cache), bool)
    control = jax.jit(repeat_sdpa).lower(q, kv, kv, valid).compile()
    assert _repeated_arrays(control.as_text(), *shapes)

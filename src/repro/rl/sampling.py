"""Autoregressive rollout generation with a KV cache.

The rollout engine's inner loop: batched prompt feed (teacher-forced
decode steps, sharing the exact production serve path) followed by
temperature sampling of up to ``max_new_tokens``, collecting per-token
behavior logprobs — what the actor-update step needs as ``old_logprob``.

Fixed shapes throughout → a single XLA compilation per (B, cache_len).
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.obs import span
from repro.data.tokenizer import ByteTokenizer
from repro.models import decode_step, init_cache


@functools.partial(jax.jit, static_argnames=("cfg", "max_new", "temperature"))
def _generate_jit(params, cfg, prompt_tokens, prompt_lens, rng, *,
                  max_new: int, temperature: float = 1.0):
    """prompt_tokens: (B, Lp) right-padded; prompt_lens: (B,).
    Returns (tokens (B, Lp+max_new), logprobs (B, Lp+max_new), resp_mask)."""
    B, Lp = prompt_tokens.shape
    total = Lp + max_new
    cache = init_cache(cfg, B, total)

    def step(carry, t):
        cache, cur_tok, rng, out_toks, out_lps = carry
        logits, cache = decode_step(params, cfg, cache, cur_tok,
                                    jnp.full((B,), t, jnp.int32))
        logits = logits.astype(jnp.float32) / max(temperature, 1e-6)
        logp = jax.nn.log_softmax(logits, axis=-1)
        rng, sub = jax.random.split(rng)
        sampled = jax.random.categorical(sub, logits)
        # during the prompt: next token is forced; after: sampled
        in_prompt = (t + 1) < prompt_lens
        forced = prompt_tokens[:, jnp.minimum(t + 1, Lp - 1)]
        nxt = jnp.where(in_prompt, forced, sampled)
        tok_lp = jnp.take_along_axis(logp, nxt[:, None], axis=1)[:, 0]
        out_toks = out_toks.at[:, t + 1].set(nxt)
        out_lps = out_lps.at[:, t + 1].set(tok_lp)
        return (cache, nxt, rng, out_toks, out_lps), None

    out_toks = jnp.zeros((B, total), jnp.int32)
    out_toks = out_toks.at[:, 0].set(prompt_tokens[:, 0])
    out_lps = jnp.zeros((B, total), jnp.float32)
    carry = (cache, prompt_tokens[:, 0], rng, out_toks, out_lps)
    (cache, _, _, out_toks, out_lps), _ = jax.lax.scan(
        step, carry, jnp.arange(total - 1))

    pos = jnp.arange(total)[None, :]
    resp_mask = (pos >= prompt_lens[:, None]).astype(jnp.float32)
    return out_toks, out_lps, resp_mask


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def generate_bucket(n_seqs: int, prompt_len: int) -> tuple:
    """(batch, prompt length) a bucketed ``generate`` call of ``n_seqs``
    prompts, the longest ``prompt_len`` tokens, is padded to: one XLA
    compilation per bucket."""
    return _next_pow2(n_seqs), ((prompt_len + 7) // 8) * 8


def generate(params, cfg, prompts: List[np.ndarray], rng_seed: int, *,
             max_new_tokens: int = 16, temperature: float = 1.0,
             eos_id: int = ByteTokenizer.eos_id,
             bucket: bool = True):
    """Returns list of dicts per prompt: tokens, logprobs, response_mask,
    response_ids (trimmed at EOS), prompt_len.

    bucket=True pads the batch dim to a power of two and the prompt length
    to a multiple of 8 so repeated calls reuse one XLA compilation
    (continuous-batching engines do the same bucketing)."""
    tok = ByteTokenizer()
    n_real = len(prompts)
    with span("generate.prepare", n=n_real):
        prompts = list(prompts)
        if bucket:
            target_b, pad_len = generate_bucket(
                n_real, max(len(p) for p in prompts))
            prompts += [prompts[-1]] * (target_b - n_real)
            toks, mask = tok.pad_batch(prompts, length=pad_len)
        else:
            toks, mask = tok.pad_batch(prompts)
        lens = np.asarray([len(p) for p in prompts], np.int32)
        out_toks, out_lps, resp_mask = _generate_jit(
            params, cfg, jnp.asarray(toks), jnp.asarray(lens),
            jax.random.PRNGKey(rng_seed), max_new=max_new_tokens,
            temperature=temperature)
    with span("generate.device"):
        out_toks = np.asarray(out_toks)
        out_lps = np.asarray(out_lps)
        resp_mask = np.asarray(resp_mask)

    with span("generate.rows", n=n_real):
        rows = []
        for i in range(n_real):
            lp_len = int(lens[i])
            resp = out_toks[i, lp_len:]
            cut = np.where(resp == eos_id)[0]
            n_resp = int(cut[0]) + 1 if len(cut) else len(resp)
            m = resp_mask[i].copy()
            m[lp_len + n_resp:] = 0.0
            rows.append(dict(tokens=out_toks[i], logprobs=out_lps[i],
                             response_mask=m, response_ids=resp[:n_resp],
                             prompt_len=lp_len))
    return rows

"""Chip smoke: three GRPO steps of the Trainer on one TPU at Qwen2.5-7B widths.

Drives the main path once through the user entry point,
``Trainer(TrainerConfig(...), model_cfg=cfg).fit()``: rollout, then reward
and group advantage, then the actor update, streamed over the
TransferQueue, with the fused Pallas RL-loss kernel compiled for the chip.
It then checks the run and prints, as its last line,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Run it from the repository root:

    python chip_smoke.py

It exits non-zero without that line when JAX finds no TPU, when the
repository's ``src/`` is not beside it, or when a check fails.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

# Qwen2.5-7B (src/repro/configs/qwen2_5_7b.py) at every published width:
# d_model 3584, 28 query and 4 KV heads of 128, d_ff 18,944, qkv bias.
# Cut to one 16 GB chip: depth 1 of 28 layers (the layers are uniform, so
# one is a whole period), and a vocabulary of 19,008 rows, one chip's
# share of the 152,064-row vocabulary split 8 ways.
NUM_LAYERS = 1
VOCAB = 152_064 // 8

TRAFFIC = dict(algorithm="grpo", mode="async", staleness=1, num_steps=3,
               prompts_per_step=4, group_size=4, max_new_tokens=64,
               seq_len=128, rollout_workers=1, use_pallas=True)

# Tolerances of the fused Pallas loss against the jnp route on the same
# logits. Both reduce in f32, in different orders. A per-token output is
# a sum over V terms, so the two may differ by about
# sqrt(V) * eps_f32 * |logit| ~ 1e-4 at V = 19,008; 1e-3 leaves headroom.
# The gradient is stored in the logits' bf16, where the two routes may
# round one ulp (2^-8) apart; the largest error may be two such ulps of
# the largest entry.
OUT_TOL = 1e-3
GRAD_TOL = 2.0 ** -7
# Tolerance of the actor update's parameter gradient, Pallas route against
# the jnp route, as the largest per-leaf |g_pallas - g_jnp| / |g_jnp| (L2
# norms). The routes differ only in the logits gradient (within GRAD_TOL
# above); the backward through the model is linear in it, and its bf16
# matmuls round each route again by up to 2^-8 of their terms, which
# cancellation in a leaf's sums can magnify a few times: 2^-5 allows that
# while a wrong backward is off by order one.
PARAM_GRAD_TOL = 2.0 ** -5


def loss_agreement(n_rows: int, vocab: int, dtype, seed: int = 0):
    """Errors of the Pallas route of ``fused_rl_loss`` against its
    ``use_pallas=False`` route on seeded (n_rows, vocab) logits.

    Returns (output error, gradient error): the largest
    |pallas - jnp| / (1 + |jnp|) over the five per-token outputs, and the
    largest |pallas - jnp| of the logits gradient over its largest
    entry. Old and reference logprobs sit near the policy's own, so the
    importance ratio straddles the clip range."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.fused_rl_loss import fused_rl_loss

    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    logits = (3.0 * jax.random.normal(ks[0], (n_rows, vocab))).astype(dtype)
    targets = jax.random.randint(ks[1], (n_rows,), 0, vocab)
    lp = jnp.take_along_axis(jax.nn.log_softmax(logits.astype(jnp.float32)),
                             targets[:, None], axis=1)[:, 0]
    old = lp + 0.3 * jax.random.normal(ks[2], (n_rows,))
    ref = lp + 0.1 * jax.random.normal(ks[3], (n_rows,))
    adv = jax.random.normal(ks[4], (n_rows,))
    cts = tuple(jax.random.normal(k, (n_rows,)) for k in ks[5:])

    def outs_and_grad(x, t, o, r, a, c, use_pallas):
        outs, vjp = jax.vjp(lambda y: fused_rl_loss(
            y, t, o, r, a, use_pallas=use_pallas), x)
        return outs, vjp(c)[0].astype(jnp.float32)

    f = jax.jit(outs_and_grad, static_argnames="use_pallas")
    args = (logits, targets, old, ref, adv, cts)
    got, g_got = f(*args, use_pallas=True)
    want, g_want = f(*args, use_pallas=False)
    out_err = max(float(jnp.max(jnp.abs(a - b) / (1.0 + jnp.abs(b))))
                  for a, b in zip(got, want))
    grad_err = float(jnp.max(jnp.abs(g_got - g_want))
                     / jnp.max(jnp.abs(g_want)))
    return out_err, grad_err


def actor_grad_agreement(params, model_cfg, rl, n_rows: int, seq_len: int,
                         seed: int = 0):
    """The actor update's gradient program (``_grad_microbatch``, as the
    Trainer runs it) on one seeded micro-batch with nonzero advantages,
    through the Pallas and the jnp route of the fused loss.

    Returns (gap, norm): the largest per-leaf |g_pallas - g_jnp| / |g_jnp|
    and the smaller of the two routes' global gradient norms. Old
    logprobs are the policy's own plus noise, so the importance ratio
    straddles the clip range and the surrogate has a gradient."""
    import jax
    import jax.numpy as jnp

    from repro.engines.train_engine import _grad_microbatch
    from repro.models import forward

    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    tokens = jax.random.randint(ks[0], (n_rows, seq_len), 0,
                                model_cfg.vocab_size)
    logits = jax.jit(lambda p, t: forward(p, model_cfg,
                                          {"tokens": t})[0])(params, tokens)
    lp = jnp.take_along_axis(
        jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32)),
        tokens[:, 1:, None], axis=-1)[..., 0]
    resp = (jnp.arange(seq_len) >= seq_len // 4).astype(jnp.float32)
    batch = {"tokens": tokens,
             "response_mask": jnp.broadcast_to(resp, (n_rows, seq_len)),
             "old_logprob": jnp.pad(lp, ((0, 0), (1, 0)))
             + 0.3 * jax.random.normal(ks[1], (n_rows, seq_len)),
             "advantage": jax.random.normal(ks[2], (n_rows,))}
    g_pallas, g_jnp = (
        _grad_microbatch(params, model_cfg, dataclasses.replace(
            rl, use_pallas_logprob=flag), batch)[0] for flag in (True, False))
    gap = max(float(jnp.linalg.norm((a - b).ravel())
                    / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30))
              for a, b in zip(jax.tree.leaves(g_pallas),
                              jax.tree.leaves(g_jnp)))
    norm = min(float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                  for x in jax.tree.leaves(g))))
               for g in (g_pallas, g_jnp))
    return gap, norm


def run(model_cfg, tcfg, log=print) -> bool:
    """Fit ``tcfg`` on ``model_cfg`` through the Trainer, log what the run
    did, and check it. Returns whether every check passed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import Trainer

    t0 = time.monotonic()
    trainer = Trainer(tcfg, model_cfg=model_cfg)
    n_params = sum(a.size for a in jax.tree.leaves(trainer.train_engine.params))
    t_init = time.monotonic() - t0
    log(f"model: {model_cfg.name} layers={model_cfg.num_layers} "
        f"d_model={model_cfg.d_model} heads={model_cfg.num_heads}/"
        f"{model_cfg.num_kv_heads}x{model_cfg.head_dim} d_ff={model_cfg.d_ff} "
        f"vocab={model_cfg.vocab_size} params={n_params:,}")
    log("trainer: " + " ".join(f"{k}={getattr(tcfg, k)}" for k in TRAFFIC)
        + f" rollout_batch={tcfg.rollout_batch}"
        f" train_micro_batch={tcfg.train_micro_batch}")

    t1 = time.monotonic()
    result = trainer.fit()
    t_fit = time.monotonic() - t1
    log(f"time: init {t_init:.3f} s, warm-up compile "
        f"{t_fit - result.wall_time_s:.3f} s, run {result.wall_time_s:.3f} s "
        f"({tcfg.num_steps} steps)")
    for m in result.metrics:
        log(f"step {m['step']}: loss {m['loss']:.6g} "
            f"mean_reward {m.get('mean_reward', float('nan')):.4g} "
            f"grad_norm {m['grad_norm']:.6g}")

    n_rows = tcfg.train_micro_batch * (tcfg.seq_len - 1)
    out_err, grad_err = loss_agreement(
        n_rows, model_cfg.vocab_size, jnp.dtype(model_cfg.compute_dtype),
        seed=tcfg.seed)
    log(f"fused loss, pallas vs jnp at ({n_rows}, {model_cfg.vocab_size}) "
        f"{model_cfg.compute_dtype}: output err {out_err:.3g} "
        f"(tol {OUT_TOL:g}), grad err {grad_err:.3g} (tol {GRAD_TOL:g})")
    p_gap, p_norm = actor_grad_agreement(
        trainer.train_engine.params, model_cfg, trainer.train_engine.rl,
        tcfg.train_micro_batch, tcfg.seq_len, seed=tcfg.seed)
    log(f"actor gradient, pallas vs jnp at ({tcfg.train_micro_batch}, "
        f"{tcfg.seq_len}): worst-leaf gap {p_gap:.3g} "
        f"(tol {PARAM_GRAD_TOL:g}), norm {p_norm:.6g}")

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log("peak_bytes_in_use: " + (f"{peak:,}" if peak is not None
                                 else "not reported"))

    want = tcfg.num_steps * tcfg.prompts_per_step * tcfg.group_size
    finite = all(np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"])
                 for m in result.metrics)
    checks = [
        ("samples trained", result.samples_trained == want,
         f"{result.samples_trained} of {want}"),
        ("optimizer steps", len(result.metrics) == tcfg.num_steps,
         f"{len(result.metrics)} of {tcfg.num_steps}"),
        ("loss and grad norm finite", finite, ""),
        ("weight version advanced",
         trainer.train_engine.version == tcfg.num_steps,
         f"version {trainer.train_engine.version}"),
        ("fused loss outputs agree", out_err <= OUT_TOL, f"{out_err:.3g}"),
        ("fused loss gradient agrees", grad_err <= GRAD_TOL,
         f"{grad_err:.3g}"),
        ("actor gradient nonzero", p_norm > 0, f"{p_norm:.6g}"),
        ("actor gradient agrees", p_gap <= PARAM_GRAD_TOL, f"{p_gap:.3g}"),
    ]
    for name, ok, detail in checks:
        log(f"check {name}: {'ok' if ok else 'FAILED'} {detail}".rstrip())
    return all(ok for _, ok, _ in checks)


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    try:
        from repro.api import TrainerConfig
        from repro.configs import get_config
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"script: {e}", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {use_compile_cache()}")
    cfg = dataclasses.replace(get_config("qwen2_5_7b"),
                              num_layers=NUM_LAYERS, vocab_size=VOCAB)
    if not run(cfg, TrainerConfig(**TRAFFIC), log=lambda s: print(s,
                                                                  flush=True)):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

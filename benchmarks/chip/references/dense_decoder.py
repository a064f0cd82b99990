"""Plain float32 reference of a dense decoder LM and its GRPO update.

Follows the published Qwen2 / MiniCPM (llama-like) block: RMSNorm
(eps 1e-6), rotary position embedding on the two halves of each head,
grouped-query attention with a causal mask (query head h reads key/value
head h // (heads / kv heads)), optional q/k/v biases, a SwiGLU MLP, a
final RMSNorm, and an output head or the tied embedding. It imports
nothing of the system under test and takes its weights from the
benchmark's own seeded weights.

Every matrix multiplication runs at ``Precision.HIGHEST``. With
``quant`` set to a float8 dtype, every matrix multiplication instead
takes its two operands rounded to that dtype, and their gradients to
float8 e5m2, each with one scale per tensor (amax scaling), and
accumulates in float32: that is the control, the reference at the next
precision below the configuration's bfloat16.

Besides the forward pass and the update it declares what the harness
needs to run its family: ``SOURCE_KEYS``, ``ARCH`` and ``defaults`` (how
a configuration file becomes the program's ``ModelConfig``) and the
counts of model FLOPs (``matmul_params``, ``attention_flops_fwd``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NORM_EPS = 1e-6

# the family's config.json keys -> the program's ModelConfig fields
SOURCE_KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
               "num_attention_heads": "num_heads",
               "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
               "intermediate_size": "d_ff", "vocab_size": "vocab_size",
               "rope_theta": "rope_theta", "hidden_act": "activation",
               "tie_word_embeddings": "tie_embeddings"}

# the ModelConfig fields that select the block this module computes
ARCH = {"arch_type": "dense", "attention": "gqa"}


def defaults(m: dict) -> dict:
    """The fields a config.json of the family may leave out, at the values
    the family then takes; q, k and v carry no bias unless the
    configuration file's ``program`` says so."""
    return {"head_dim": m["d_model"] // m["num_heads"], "rope_theta": 10000.0,
            "tie_embeddings": False, "qkv_bias": False}


def matmul_params(m: dict) -> int:
    """Parameters used in matrix multiplications per token of a dense
    decoder ``m`` (the configuration file's ``model`` sizes)."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * hd * (2 * m["num_heads"] + 2 * m["num_kv_heads"])
    n_mats = 3 if m.get("activation", "silu") == "silu" else 2
    mlp = n_mats * d * m["d_ff"]
    return m["num_layers"] * (attn + mlp) + d * m["vocab_size"]


def attention_flops_fwd(m: dict, length: int) -> int:
    """Score and value products of causal attention over one sequence of
    ``length`` tokens, forward only: position t attends to t + 1 keys."""
    pairs = length * (length + 1) // 2
    return 4 * pairs * m["num_heads"] * m["head_dim"] * m["num_layers"]


def _round(x, dt):
    """``x`` rounded to ``dt`` with one scale per tensor (amax scaling)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dt).max)
    return (x / scale).astype(dt).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def fake_quant(x, dt):
    """A matmul operand in float8 ``dt``; its gradient is rounded to
    float8 e5m2 the same way, as float8 training does."""
    return _round(x, dt)


def _fq_fwd(x, dt):
    return _round(x, dt), None


def _fq_bwd(dt, _, g):
    return (_round(g, jnp.float8_e5m2),)


fake_quant.defvjp(_fq_fwd, _fq_bwd)


def mm(eq, a, b, quant=None):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant is not None:
        a, b = fake_quant(a, quant), fake_quant(b, quant)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + NORM_EPS) * scale


def rope(x, theta):
    """x: (B, S, H, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _linear(p, x, quant, eq="bsi,io->bso"):
    y = mm(eq, x, p["w"], quant)
    return y + p["b"] if "b" in p else y


def forward(params, m: dict, tokens, quant=None):
    """Logits (B, S, V) in float32 for token ids (B, S)."""
    B, S = tokens.shape
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    x = params["embed"]["table"][tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))
    for li in range(m["num_layers"]):
        blk = jax.tree.map(lambda a: a[li], params["blocks"])
        at = blk["attn"]
        h = rms_norm(x, blk["ln1"]["scale"])
        q = _linear(at["wq"], h, quant).reshape(B, S, H, hd)
        k = _linear(at["wk"], h, quant).reshape(B, S, KV, hd)
        v = _linear(at["wv"], h, quant).reshape(B, S, KV, hd)
        q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
        k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
        s = mm("bqhd,bkhd->bhqk", q, k, quant) * (hd ** -0.5)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm("bhqk,bkhd->bqhd", p, v, quant).reshape(B, S, H * hd)
        x = x + _linear(at["wo"], o, quant)
        h = rms_norm(x, blk["ln2"]["scale"])
        f = blk["ffn"]
        up = _linear(f["up"], h, quant)
        if "gate" in f:
            up = up * jax.nn.silu(_linear(f["gate"], h, quant))
        else:
            up = jax.nn.gelu(up)
        x = x + _linear(f["down"], up, quant)
    x = rms_norm(x, params["final_norm"]["scale"])
    if "lm_head" in params:
        return mm("bsd,dv->bsv", x, params["lm_head"]["w"], quant)
    return mm("bsd,vd->bsv", x, params["embed"]["table"], quant)


def token_logprobs(params, m: dict, tokens, quant=None):
    """(B, S): entry t > 0 is the log-probability of token t given the
    tokens before it; entry 0 is 0."""
    logp = jax.nn.log_softmax(forward(params, m, tokens, quant)[:, :-1], -1)
    lp = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return jnp.pad(lp, ((0, 0), (1, 0)))


def grpo_loss_sum(params, m: dict, mb: dict, clip_eps: float, quant=None):
    """Clipped-surrogate GRPO loss summed over the response tokens of
    some rows: -min(r A, clip(r, 1-e, 1+e) A), with r = exp(logprob -
    behaviour logprob) and A the row's advantage. A micro-batch's loss is
    this sum over its rows divided by its count of response tokens."""
    lp = token_logprobs(params, m, mb["tokens"], quant)[:, 1:]
    ratio = jnp.exp(lp - mb["old_logprob"][:, 1:])
    adv = mb["advantage"][:, None]
    per_tok = -jnp.minimum(ratio * adv,
                           jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * adv)
    return jnp.sum(per_tok * mb["response_mask"][:, 1:])


def adamw(params, grads, state, opt: dict, step: int):
    """One AdamW step as the configuration states it: global-norm clip,
    bias-corrected moments, decoupled weight decay, linear warm-up.
    Returns (params, state, clipped grads)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, opt["grad_clip"]
                                  / jnp.maximum(gnorm, 1e-9)), grads)
    b1, b2 = opt["betas"]
    lr = opt["lr"] * jnp.minimum(1.0, (step + 1) / max(opt["warmup_steps"],
                                                       1))
    mu = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, state[0], grads)
    nu = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, state[1], grads)
    c1, c2 = 1 - b1 ** (step + 1.0), 1 - b2 ** (step + 1.0)
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + opt["eps"])
                                  + opt["weight_decay"] * p),
        params, mu, nu)
    return params, (mu, nu), grads


def leaf_norms(tree) -> dict:
    """{leaf path: L2 norm} of a pytree."""
    paths, leaves = zip(*jax.tree_util.tree_flatten_with_path(tree)[0])
    norms = jax.jit(lambda ls: [jnp.sqrt(jnp.sum(x * x)) for x in ls])(
        list(leaves))
    return {jax.tree_util.keystr(p): float(n) for p, n in zip(paths, norms)}


def follow(params, m: dict, steps, opt: dict, clip_eps: float, quant=None,
           rows_per_pass: int = 0, at_version=None):
    """Follow the program's first optimizer steps. ``steps`` holds, per
    step, its micro-batches in the order the program consumed them. The
    step's loss is the mean of its micro-batch losses and its gradient
    the mean of theirs, as gradient accumulation gives them. A
    micro-batch is taken ``rows_per_pass`` rows at a time (0: whole), so
    that it fits. ``at_version(v, params)``, where given, is called with
    the parameters of every weight version: v = 0 before the first step,
    v = i + 1 after step i.

    Returns (losses, per-leaf norms of the first clipped gradient, final
    parameters)."""
    grad_fn = jax.value_and_grad(functools.partial(
        grpo_loss_sum, m=m, clip_eps=clip_eps, quant=quant))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def accumulate(acc, params, part, scale):
        loss, g = grad_fn(params, mb=part)
        return jax.tree.map(lambda a, b: a + b * scale, acc, g), loss

    step_fn = jax.jit(functools.partial(adamw, opt=opt),
                      donate_argnums=(1, 2))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    state, losses, first = (zeros(params), zeros(params)), [], None
    if at_version is not None:
        at_version(0, params)
    for i, micro in enumerate(steps):
        acc, total = zeros(params), 0.0
        for mb in micro:
            n = len(mb["advantage"])
            k = rows_per_pass or n
            denom = max(float(mb["response_mask"][:, 1:].sum()), 1.0)
            for r in range(0, n, k):
                part = {key: jnp.asarray(v[r:r + k]) for key, v in mb.items()}
                acc, loss = accumulate(acc, params, part,
                                       1.0 / (denom * len(micro)))
                total += float(loss) / denom
        params, state, clipped = step_fn(params, acc, state, step=i)
        del acc
        if at_version is not None:
            at_version(i + 1, params)
        losses.append(total / len(micro))
        if first is None:
            first = leaf_norms(clipped)
        del clipped
    return losses, first, params

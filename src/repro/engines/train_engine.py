"""JAX training engines — the training-cluster backend.

``JaxTrainEngine`` implements the actor-update stage verb
(``update_actor``): it accumulates gradients over streamed micro-batches
and applies the AdamW step once a full global batch has passed through
(so streaming micro-consumption is algorithm-identical to whole-batch
training). ``algorithm="grpo"`` uses the GRPO loss over scalar group
advantages; ``algorithm="ppo"`` uses the actor-only PPO loss over
per-token GAE advantages.

``JaxCriticEngine`` implements the PPO value-side stage verbs:
``compute_values`` (the streaming critic-inference task) and
``update_critic`` (the streaming critic-update task), with the same
gradient-accumulation contract as the actor.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.obs import span
from repro.engines.adapter import EngineRegistry, RLAdapter
from repro.rl.grpo import GRPOConfig, grpo_loss_fn
from repro.rl.ppo import (PPOConfig, critic_forward, ppo_actor_loss_fn,
                          ppo_critic_loss_fn)
from repro.training.optimizer import OptimizerConfig
from repro.training.train_state import TrainState


def pack_rows(batch: Dict[str, list], seq_len: int) -> dict:
    """Variable-length rows from TransferQueue -> fixed-shape jnp batch.

    Packs whatever per-token columns are present (logprob, ref_logprob,
    returns, values) plus the advantage — per-token (PPO/GAE) or scalar
    per-sample (GRPO) — so one packer serves every train-side stage."""
    n = len(batch["response"])
    S = seq_len

    def pad2(rows, dtype=np.float32):
        a = np.zeros((n, S), dtype)
        for i, r in enumerate(rows):
            r = np.asarray(r)[:S]
            a[i, :len(r)] = r
        return a

    tokens = pad2(batch["response"], np.int32)
    if "response_mask" in batch:
        masks = pad2(batch["response_mask"])
    else:
        masks = np.zeros((n, S), np.float32)
        for i, r in enumerate(batch["response"]):
            masks[i, :min(S, len(np.asarray(r)))] = 1.0
    out = {"tokens": jnp.asarray(tokens),
           "response_mask": jnp.asarray(masks)}
    if "logprob" in batch:
        out["old_logprob"] = jnp.asarray(pad2(batch["logprob"]))
    if "advantage" in batch:
        adv = batch["advantage"]
        if n and np.ndim(np.asarray(adv[0])) >= 1:   # per-token (PPO)
            out["advantage"] = jnp.asarray(pad2(adv))
        else:                                         # scalar (GRPO)
            out["advantage"] = jnp.asarray(np.asarray(adv, np.float32))
    for col, key in (("ref_logprob", "ref_logprob"),
                     ("returns", "returns"), ("values", "old_values")):
        if col in batch:
            out[key] = jnp.asarray(pad2(batch[col]))
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "rl"))
def _grad_microbatch(params, cfg, rl, batch):
    (_, metrics), grads = jax.value_and_grad(grpo_loss_fn, has_aux=True)(
        params, cfg, batch, rl)
    return grads, metrics


@functools.partial(jax.jit, static_argnames=("cfg", "rl"))
def _ppo_actor_grad_microbatch(params, cfg, rl, batch):
    (_, metrics), grads = jax.value_and_grad(
        ppo_actor_loss_fn, has_aux=True)(params, cfg, batch, rl)
    return grads, metrics


@functools.partial(jax.jit, static_argnames=("opt_cfg",))
def _apply(state: TrainState, grads, n_micro, opt_cfg):
    grads = jax.tree.map(lambda g: g / n_micro, grads)
    new_state, gnorm = state.apply_gradients(grads, opt_cfg)
    return new_state, gnorm


class _AccumulatingEngine(RLAdapter):
    """Shared gradient-accumulation consumer: collect micro-batch grads
    until a full global batch streamed through, then step the optimizer."""

    def __init__(self, cfg, init_params, *, opt: Optional[OptimizerConfig],
                 global_batch: int, seq_len: int):
        self.cfg = cfg
        self.opt_cfg = opt or OptimizerConfig(lr=3e-4, warmup_steps=2)
        self.state = TrainState.create(init_params)
        self.global_batch = global_batch
        self.seq_len = seq_len
        self._accum = None
        self._accum_n = 0
        self._accum_metrics: List[dict] = []
        self.version = 0

    @property
    def params(self):
        return self.state.params

    def _grad(self, jb):
        raise NotImplementedError

    def _consume(self, batch: Dict[str, list]) -> dict:
        n = len(batch["response"])
        with span("update.pack", n=n):
            jb = pack_rows(batch, self.seq_len)
        with span("update.grad", n=n):
            grads, metrics = self._grad(jb)
            # accumulate before the read-back, so the device runs the
            # adds right after the grad instead of idling for the host
            with span("update.accumulate"):
                if self._accum is None:
                    self._accum = grads
                else:
                    self._accum = jax.tree.map(jnp.add, self._accum, grads)
            self._accum_metrics.append(
                {k: float(v) for k, v in metrics.items()})
        self._accum_n += n

        if self._accum_n >= self.global_batch:
            n_micro = max(1, len(self._accum_metrics))
            with span("update.optimizer"):
                self.state, gnorm = _apply(self.state, self._accum,
                                           float(n_micro), self.opt_cfg)
                gnorm = float(gnorm)
            self.version += 1
            out = {k: float(np.mean([m[k] for m in self._accum_metrics]))
                   for k in self._accum_metrics[0]}
            out["grad_norm"] = gnorm
            if "reward" in batch:
                out["mean_reward"] = float(np.mean(batch["reward"]))
            self._accum, self._accum_n = None, 0
            self._accum_metrics = []
            return out
        return {}

    def get_weights(self):
        return self.state.params

    def load_weights(self, weights) -> None:
        self.state = self.state._replace(params=weights)


@EngineRegistry.register("jax_train")
class JaxTrainEngine(_AccumulatingEngine):
    """Actor-update stage engine (GRPO or PPO-actor loss)."""

    def __init__(self, cfg, init_params, *, rl=None,
                 opt: Optional[OptimizerConfig] = None,
                 global_batch: int = 16, seq_len: int = 32,
                 algorithm: str = "grpo", use_pallas: bool = False):
        super().__init__(cfg, init_params, opt=opt,
                         global_batch=global_batch, seq_len=seq_len)
        self.algorithm = algorithm
        # use_pallas routes the whole actor update through the fused
        # kernels/fused_rl_loss hot path (only consulted when no rl
        # config is passed — an explicit config carries its own flag)
        if algorithm == "ppo":
            self.rl = rl or PPOConfig(use_pallas_logprob=use_pallas)
            self._grad_fn = _ppo_actor_grad_microbatch
        else:
            self.rl = rl or GRPOConfig(use_pallas_logprob=use_pallas)
            self._grad_fn = _grad_microbatch

    def _grad(self, jb):
        return self._grad_fn(self.state.params, self.cfg, self.rl, jb)

    def warm_up(self, row_counts) -> None:
        """Compile the grad step for each micro-batch row count, and the
        optimizer step, before the stage threads start. The engine's
        state is left as it was."""
        S = self.seq_len
        grads = None
        for n in row_counts:
            zeros = [np.zeros(S, np.float32)] * n
            batch = {"response": [np.zeros(S, np.int32)] * n,
                     "response_mask": zeros, "logprob": zeros,
                     "advantage": (zeros if self.algorithm == "ppo"
                                   else [0.0] * n)}
            if self.rl.kl_coef > 0:
                batch["ref_logprob"] = zeros
            grads, _ = self._grad(pack_rows(batch, S))
        jax.block_until_ready(_apply(self.state, grads, 1.0, self.opt_cfg))

    def update(self, batch: Dict[str, list]) -> dict:
        return self._consume(batch)

    def update_actor(self, batch, **kw):
        return self._consume(batch)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _critic_values(critic_params, cfg, tokens):
    return critic_forward(critic_params, cfg, tokens)


@functools.partial(jax.jit, static_argnames=("cfg", "rl"))
def _critic_grad_microbatch(critic_params, cfg, rl, batch):
    (_, metrics), grads = jax.value_and_grad(
        ppo_critic_loss_fn, has_aux=True)(critic_params, cfg, batch, rl)
    return grads, metrics


@EngineRegistry.register("jax_critic")
class JaxCriticEngine(_AccumulatingEngine):
    """PPO value-side stage engine: streaming critic inference
    (``compute_values``) and critic updates (``update_critic``)."""

    def __init__(self, cfg, critic_params, *, rl: Optional[PPOConfig] = None,
                 opt: Optional[OptimizerConfig] = None,
                 global_batch: int = 16, seq_len: int = 32):
        super().__init__(cfg, critic_params, opt=opt,
                         global_batch=global_batch, seq_len=seq_len)
        self.rl = rl or PPOConfig()

    def compute_values(self, batch, **kw):
        """Stage verb: per-token values over each row's full sequence
        (padded to a multiple of 8 for XLA compile reuse)."""
        arrs = [np.asarray(r) for r in batch["response"]]
        S = max(len(a) for a in arrs)
        S = ((S + 7) // 8) * 8
        toks = np.zeros((len(arrs), S), np.int32)
        for i, a in enumerate(arrs):
            toks[i, :len(a)] = a
        vals = np.asarray(_critic_values(self.state.params, self.cfg,
                                         jnp.asarray(toks)))
        return {"updates": {"values":
                            [vals[i, :len(a)].astype(np.float32)
                             for i, a in enumerate(arrs)]}}

    def _grad(self, jb):
        return _critic_grad_microbatch(self.state.params, self.cfg,
                                       self.rl, jb)

    def update_critic(self, batch, **kw):
        return self._consume(batch)

    def update(self, batch):
        return self._consume(batch)

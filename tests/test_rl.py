"""RL algorithm layer: advantages, losses, GRPO/PPO steps, reward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.data.tokenizer import ByteTokenizer
from repro.rl import (GRPOConfig, PPOConfig, clipped_policy_loss, gae,
                      grpo_advantages, grpo_train_step, init_critic_params,
                      kl_penalty, math_reward, ppo_train_step)
from repro.training.optimizer import OptimizerConfig
from repro.training.train_state import TrainState

tok = ByteTokenizer()


@settings(max_examples=40, deadline=None)
@given(st.lists(st.floats(-5, 5, allow_nan=False), min_size=2, max_size=16))
def test_grpo_advantages_normalized(rewards):
    adv = np.asarray(grpo_advantages(np.asarray(rewards, np.float32)))
    assert abs(adv.mean()) < 1e-4
    if np.std(rewards) > 1e-3:
        assert abs(adv.std() - 1.0) < 0.05
    else:
        assert np.abs(adv).max() < 1.0  # degenerate group -> ~zero


def test_gae_terminal_matches_reward():
    adv, ret = gae([1.0, 0.0, 2.0], [0.0, 0.0, 0.0, 0.0], gamma=1.0, lam=1.0)
    assert ret[0] == pytest.approx(3.0)
    assert adv[-1] == pytest.approx(2.0)


def test_clipped_policy_loss_clip_behavior():
    lp_old = jnp.zeros((1, 4))
    mask = jnp.ones((1, 4))
    adv = jnp.asarray([1.0])
    # big positive ratio with positive advantage is clipped at 1+eps
    lp_new = jnp.full((1, 4), 2.0)
    loss, stats = clipped_policy_loss(lp_new, lp_old, adv, mask, clip_eps=0.2)
    assert loss == pytest.approx(-1.2, abs=1e-5)
    assert float(stats["clip_frac"]) == 1.0
    # ratio 1 -> loss = -A
    loss2, _ = clipped_policy_loss(lp_old, lp_old, adv, mask)
    assert loss2 == pytest.approx(-1.0, abs=1e-6)


def test_kl_penalty_nonnegative_zero_at_equal():
    lp = jnp.asarray([[0.5, -1.0]])
    mask = jnp.ones((1, 2))
    assert kl_penalty(lp, lp, mask) == pytest.approx(0.0, abs=1e-7)
    assert float(kl_penalty(lp, lp - 0.3, mask)) > 0


def test_math_reward():
    assert math_reward(12, tok.encode("12", add_bos=False)) == 1.0
    assert math_reward(12, tok.encode("the answer is 12",
                                      add_bos=False)) == pytest.approx(0.2)
    assert math_reward(12, tok.encode("7", add_bos=False)) == pytest.approx(-0.1)
    assert math_reward(-3, tok.encode("-3", add_bos=False)) == 1.0
    assert math_reward(12, tok.encode("123", add_bos=False)) < 1.0


def test_math_reward_skips_ids_beyond_the_byte_vocabulary():
    """A model vocabulary larger than the byte tokenizer's samples ids the
    tokenizer has no byte for; they are skipped, not a crash."""
    ids = np.concatenate([[5000], tok.encode("12", add_bos=False),
                          [ByteTokenizer.vocab_size, 152_063]])
    assert tok.decode(ids) == "12"
    assert math_reward(12, ids) == 1.0


def _rl_batch(cfg, B=4, S=12, seed=0):
    rng = np.random.default_rng(seed)
    adv = rng.normal(size=B).astype(np.float32)
    return {
        "tokens": jnp.asarray(rng.integers(3, cfg.vocab_size, (B, S)),
                              jnp.int32),
        "response_mask": jnp.asarray(rng.integers(0, 2, (B, S)),
                                     jnp.float32),
        "old_logprob": jnp.asarray(-2 + 0.1 * rng.normal(size=(B, S)),
                                   jnp.float32),
        "advantage": jnp.asarray(adv),
    }


def test_grpo_step_moves_logprobs_toward_advantage(tiny_dense_cfg):
    """After several updates on a fixed batch, logprobs of positive-
    advantage samples should rise relative to negative ones."""
    from repro.models import forward, init_params
    from repro.rl.loss import token_logprobs
    cfg = tiny_dense_cfg
    params = init_params(jax.random.PRNGKey(0), cfg)
    state = TrainState.create(params)
    batch = _rl_batch(cfg)
    batch["advantage"] = jnp.asarray([2.0, 2.0, -2.0, -2.0])
    rl, opt = GRPOConfig(clip_eps=10.0), OptimizerConfig(lr=1e-3,
                                                         warmup_steps=1)

    def mean_lp(params):
        logits, _ = forward(params, cfg, {"tokens": batch["tokens"]})
        lp, _ = token_logprobs(logits[:, :-1], batch["tokens"][:, 1:])
        m = batch["response_mask"][:, 1:]
        return (lp * m).sum(1) / jnp.maximum(m.sum(1), 1)

    before = mean_lp(state.params)
    for _ in range(5):
        state, metrics = grpo_train_step(state, cfg, rl, opt, batch)
    after = mean_lp(state.params)
    delta = np.asarray(after - before)
    assert delta[:2].mean() > delta[2:].mean()


def test_ppo_train_step(tiny_dense_cfg):
    from repro.models import init_params
    cfg = tiny_dense_cfg
    actor = TrainState.create(init_params(jax.random.PRNGKey(0), cfg))
    critic = TrainState.create(init_critic_params(jax.random.PRNGKey(1), cfg))
    B, S = 2, 10
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(3, cfg.vocab_size, (B, S)),
                              jnp.int32),
        "response_mask": jnp.ones((B, S), jnp.float32),
        "old_logprob": -2 * jnp.ones((B, S), jnp.float32),
        "advantage": jnp.asarray(rng.normal(size=(B, S)), jnp.float32),
        "returns": jnp.ones((B, S), jnp.float32),
        "old_values": jnp.zeros((B, S), jnp.float32),
    }
    new_actor, new_critic, metrics = ppo_train_step(
        actor, critic, cfg, PPOConfig(), OptimizerConfig(lr=1e-4), batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["value_loss"]))
    assert int(new_actor.step) == 1 and int(new_critic.step) == 1

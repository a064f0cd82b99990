"""End-to-end system behaviour: the full AsyncFlow stack (TransferQueue +
async workflow + real JAX engines + GRPO) on a tiny model, plus the
service API and a subprocess dry-run."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.api import AsyncFlowService, Trainer, TrainerConfig

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fit(mode, steps=3):
    tcfg = TrainerConfig(arch="qwen2_5_7b", mode=mode, num_steps=steps,
                         prompts_per_step=2, group_size=2,
                         rollout_workers=2, rollout_batch=1,
                         train_micro_batch=2, max_new_tokens=4, seq_len=24)
    return Trainer(tcfg).fit()


def test_end_to_end_async_grpo():
    r = _fit("async")
    assert r.samples_trained == 3 * 4
    assert len(r.metrics) == 3                 # one optimizer step per step
    assert max(r.staleness_seen) <= 2
    for m in r.metrics:
        assert np.isfinite(m["loss"])
        assert np.isfinite(m["grad_norm"])


def test_end_to_end_baseline_on_policy():
    r = _fit("baseline")
    assert max(r.staleness_seen) == 0
    assert len(r.metrics) == 3


def test_service_api_roundtrip():
    svc = AsyncFlowService()
    svc.create_queue("exp", capacity=8,
                     tasks={"actor_update": ["prompt", "reward"]})
    svc.put_prompts_data("exp", ["p0", "p1", "p2"])
    svc.put_experience_data(
        "exp", {"prompt": ["x"] * 2, "reward": [1.0, 0.0]})
    # rows with both columns present are consumable
    got = svc.get_experience_data("exp", "actor_update", 2, timeout=1.0)
    assert got is not None and len(got["reward"]) == 2
    # weight sync notify bumps versions
    v1 = svc.weight_sync_notify({"w": np.zeros(2)})
    v2 = svc.weight_sync_notify({"w": np.ones(2)})
    assert v2 == v1 + 1
    recv = svc.register_receiver({"w": np.zeros(2)})
    svc.sender.flush()
    assert recv.wait_and_swap(v2, timeout=2.0)
    assert float(recv.params["w"][0]) == 1.0


def test_dryrun_subprocess_whisper_single():
    """One real dry-run lowering through the CLI (512 fake devices)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper_tiny", "--shape", "decode_32k", "--mesh", "single"],
        capture_output=True, text=True, timeout=900, env=env, cwd=root)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout)
    assert rec["status"] == "ok"
    assert rec["n_chips"] == 256
    assert rec["bottleneck"] in ("compute", "memory", "collective")


def test_end_to_end_grpo_with_kl_reference():
    """Three-task dataflow: rollout + reference inference + actor update,
    all streaming through TransferQueue; KL penalty is finite and the
    ref_logprob column reaches the trainer."""
    tcfg = TrainerConfig(arch="qwen2_5_7b", mode="async", num_steps=2,
                         prompts_per_step=2, group_size=2,
                         rollout_workers=1, rollout_batch=2,
                         train_micro_batch=2, max_new_tokens=4,
                         seq_len=24, kl_coef=0.05)
    r = Trainer(tcfg).fit()
    assert len(r.metrics) == 2
    for m in r.metrics:
        assert np.isfinite(m["loss"])


def test_trainer_checkpoint_roundtrip(tmp_path):
    ckpt = str(tmp_path / "rl_ckpt")
    tcfg = TrainerConfig(arch="qwen2_5_7b", mode="streaming", num_steps=1,
                         prompts_per_step=2, group_size=2,
                         rollout_workers=1, rollout_batch=2,
                         train_micro_batch=4, max_new_tokens=4,
                         seq_len=24, checkpoint_dir=ckpt)
    t = Trainer(tcfg)
    t.fit()
    # a fresh trainer restores the state and continues
    t2 = Trainer(TrainerConfig(arch="qwen2_5_7b", num_steps=1,
                               prompts_per_step=2, group_size=2,
                               rollout_workers=1, rollout_batch=2,
                               train_micro_batch=4, max_new_tokens=4,
                               seq_len=24))
    # the run-snapshot machinery owns the checkpoint_dir root; the
    # legacy single-state dump lands in "<dir>/final"
    step = t2.restore(str(tmp_path / "rl_ckpt" / "final"))
    assert step == 1
    import jax
    for a, b in zip(jax.tree.leaves(t.train_engine.state.params),
                    jax.tree.leaves(t2.train_engine.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _chip_smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_run_at_tiny_width_with_2048_vocab():
    """The chip smoke's checks, on CPU at a tiny width: the Trainer runs
    through reward with a 2,048-id vocabulary (sampled ids past the byte
    tokenizer's 259) and the fused Pallas loss in interpret mode."""
    from conftest import tiny_cfg
    smoke = _chip_smoke()
    tcfg = TrainerConfig(**{**smoke.TRAFFIC, "max_new_tokens": 8,
                            "seq_len": 24})
    lines = []
    assert smoke.run(tiny_cfg(vocab_size=2048), tcfg, log=lines.append), \
        "\n".join(lines)
    assert any(ln.startswith("check samples trained: ok 48 of 48")
               for ln in lines)


def test_chip_smoke_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO_ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("env_dir", ["", "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set; otherwise the
    cache sits at the fixed <repo>/.jax_cache."""
    import jax

    from repro.launch.compile_cache import use_compile_cache
    set_calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: set_calls.append((k, v)))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert use_compile_cache() == env_dir
        assert set_calls == []
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO_ROOT, ".jax_cache")
        assert use_compile_cache() == want
        assert set_calls == [("jax_compilation_cache_dir", want)]

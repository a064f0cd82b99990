"""The harness end to end at a tiny width on the CPU, and its refusals."""
import json
import math
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import chip_bench_tiny as tiny  # puts the harness and the system on the path
import run_cell  # noqa: E402
from repro.configs import get_config  # noqa: E402

ROOT = tiny.ROOT


def test_tiny_run_prints_a_well_formed_line():
    res, lines = run_cell.run(tiny.cell(tiny.LIMITS), 2 ** 31 + 12345, 0.2,
                              False, log=lambda s: None)
    line = json.loads(json.dumps(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, lines
    assert line["attempted"] >= 8 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "peak_hbm_gib",
                                    "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["checks"]) == {"loss_gap", "grad_gap", "update_gap",
                                   "rollout_lp_gap", "rollout_lp_mean_gap"}
    assert all(math.isfinite(c["value"]) for c in line["checks"].values())
    assert len(lines) == 5 and all("limit" in s for s in lines)


def test_main_without_a_tpu_exits_naming_the_platform(capsys):
    rc = run_cell.main(["--workload", "qwen2_5_7b_l1.grpo_long", "--seed",
                        "1", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0 and out == ""
    assert "platform 'cpu'" in err


def test_exits_without_the_system_beside_it(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks" / "chip", tmp_path / "benchmarks"
                    / "chip", ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run_cell.py", "--workload",
         "qwen2_5_7b_l1.grpo_long", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.parametrize("seconds,step_s,min_steps,want", [
    (30, 4.0, 3, 8), (30, 7.5, 3, 4), (30, 40.0, 3, 3), (10, 0.5, 1, 20)])
def test_window_steps_are_whole(seconds, step_s, min_steps, want):
    assert run_cell.window_steps(seconds, step_s, min_steps) == want


def test_window_rate_counts_whole_steps_after_the_first():
    done = [10.0, 14.0, 18.5, 22.0, 23.0]
    tokens = [100, 200, 300, 400, 999]
    rate, secs, toks = run_cell.window_rate(done, tokens, 0, 3)
    assert (secs, toks) == (12.0, 900) and rate == 75.0


def test_trained_length_stops_at_eos_and_seq_len():
    mask = [0, 0, 0, 1, 1, 1, 0, 0]
    assert run_cell.trained_length(mask, 8) == 6
    assert run_cell.trained_length(mask, 5) == 5


def _tiny_config(conf):
    return run_cell.model_config(conf, run_cell.reference(conf))


LAYOUTS = {
    "tiny": lambda: _tiny_config(tiny.CONFIG),
    "tiny_tied_mha": lambda: _tiny_config({**tiny.CONFIG, "config": {
        **tiny.CONFIG["config"], "tie_word_embeddings": True,
        "num_key_value_heads": 4}, "program": {}}),
    **{arch: (lambda arch=arch: get_config(arch).reduced()) for arch in (
        "qwen2_5_7b",          # dense, grouped-query attention
        "minicpm3_4b",         # dense, latent attention (MLA)
        "deepseek_v2_236b",    # experts (MoE) with MLA
        "falcon_mamba_7b",     # state space (mamba)
        "recurrentgemma_9b")}}  # hybrid: RG-LRU and local attention


@pytest.mark.parametrize("name", LAYOUTS)
def test_weights_have_the_program_layout(name):
    import jax
    import numpy as np

    import weights
    from repro.models import init_params
    cfg = LAYOUTS[name]()
    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    got = weights.make(cfg, 7)
    # the maker takes its tree from the same eval_shape, so these two hold
    # by construction; the draw below, and the pinned checksums of the tiny
    # cell's weights, are what can fail
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(want), jax.tree.leaves(got)))
    for path, leaf in jax.tree_util.tree_flatten_with_path(got)[0]:
        centre = 1.0 if getattr(path[-1], "key", None) == "scale" else 0.0
        # a leaf's mean of n draws of N(centre, 0.02^2), within 5 sigma
        assert abs(float(np.mean(leaf)) - centre) < 5 * 0.02 / math.sqrt(
            leaf.size), path


# sha256 (first 16 hex digits) of each leaf's bytes of the tiny cell's
# weights for key seed 1234567, as the maker gave them when it built the
# dense tree by hand: drawing from the program's tree must not move a bit
PINNED = {
    "['blocks']['attn']['wk']['b']": "5405520b6d26f478",
    "['blocks']['attn']['wk']['w']": "5e37a46340d25c0f",
    "['blocks']['attn']['wo']['w']": "db99aabbc1e07609",
    "['blocks']['attn']['wq']['b']": "2dec6ca6c86e7521",
    "['blocks']['attn']['wq']['w']": "0d18fe1001df0d68",
    "['blocks']['attn']['wv']['b']": "ad3293e3f8000111",
    "['blocks']['attn']['wv']['w']": "2f5e96935c9b283f",
    "['blocks']['ffn']['down']['w']": "328aa6d5f3c47623",
    "['blocks']['ffn']['gate']['w']": "a3a7fdd31d3a087b",
    "['blocks']['ffn']['up']['w']": "5a4cba46b034abbc",
    "['blocks']['ln1']['scale']": "f696d19a2f121d8e",
    "['blocks']['ln2']['scale']": "4e5a67131be651db",
    "['embed']['table']": "4a97e65f0972978d",
    "['final_norm']['scale']": "8d3daaacbf88f59a",
    "['lm_head']['w']": "8a6b2817a0cafe72"}


def test_tiny_cell_weights_match_their_pinned_checksums():
    import hashlib

    import jax
    import numpy as np

    import weights
    got = weights.make(_tiny_config(tiny.CONFIG), 1234567)
    assert {jax.tree_util.keystr(path): hashlib.sha256(
        np.asarray(leaf).tobytes()).hexdigest()[:16]
        for path, leaf in jax.tree_util.tree_flatten_with_path(got)[0]
    } == PINNED

"""A cell at a width the CPU runs in seconds, for the harness's tests."""
import copy
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run_cell  # noqa: E402

CONFIG = {"arch": "qwen2_5_7b", "reference": "dense_decoder",
          "config": {"hidden_size": 64, "intermediate_size": 128,
                     "num_attention_heads": 4, "num_key_value_heads": 2,
                     "num_hidden_layers": 2, "vocab_size": 512,
                     "rope_theta": 1e6, "hidden_act": "silu",
                     "tie_word_embeddings": False},
          "program": {"qkv_bias": True}}


# Limits at this width, set as PERF.md says from CPU readings at this
# width with four warm steps: the program's largest over 15 seeds (loss
# 3.4e-4, grad 4.0e-3, update 3.0e-2, log-probability 3.5e-3, its mean
# 9.1e-4) and the smallest of the float8 control (loss 1.7e-3,
# log-probability 2.0e-2, its mean 7.0e-3; its grad and update do not
# separate) and of the faults over 3 seeds (half batch: grad 0.16; a
# state left unchanged: update 1; weights swapped in a version late,
# planted in the program: log-probability 2.8e-2, its mean 8.8e-3).
LIMITS = {"loss_gap": 7e-4, "grad_gap": 0.03, "update_gap": 0.1,
          "rollout_lp_gap": 8e-3, "rollout_lp_mean_gap": 3e-3}


def cell(limits=None, mix="grpo_long") -> "run_cell.Cell":
    """The ``mix`` traffic shrunk to 2 prompts x 4, 8 new tokens, with the
    qwen2_5_7b_l1.grpo_long cell's limits unless ``limits`` is given."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    tr = copy.deepcopy(json.loads(
        (HERE / "traffic" / f"{mix}.json").read_text()))
    tr["trainer"].update(prompts_per_step=2, group_size=4, rollout_batch=2,
                         max_new_tokens=8, seq_len=24, train_micro_batch=4)
    tr.update(prompt_len=[10, 16], check_rows=4, min_window_steps=1)
    if limits is None:
        limits = json.loads(
            (HERE / "limits" / "qwen2_5_7b_l1.grpo_long.json").read_text())
    return run_cell.Cell("tiny", 1, copy.deepcopy(CONFIG), tr, limits,
                         bench["end_to_end"], bench["per_layer"])

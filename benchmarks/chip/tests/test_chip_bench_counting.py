"""Counting functions and peaks, against hand-computed values."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import flops  # noqa: E402
import run_cell  # noqa: E402

dense_decoder = run_cell.reference({"reference": "dense_decoder"})

TINY = {"num_layers": 2, "d_model": 8, "num_heads": 2, "num_kv_heads": 1,
        "head_dim": 4, "d_ff": 16, "vocab_size": 10, "activation": "silu"}


def test_matmul_params_by_hand():
    # attention: q 8x8, k 8x4, v 8x4, o 8x8 = 192; swiglu 3 x 8 x 16 = 384
    # two layers = 1152; head 8 x 10 = 80
    assert dense_decoder.matmul_params(TINY) == 1232


def test_attention_and_model_flops_by_hand():
    # L = 3: 6 (query, key) pairs x 4 x 2 heads x 4 dims x 2 layers = 384
    assert dense_decoder.attention_flops_fwd(TINY, 3) == 384
    assert flops.rollout_flops(dense_decoder, TINY, 3) == 2 * 1232 * 3 + 384
    assert flops.train_flops(dense_decoder, TINY, 3) == (6 * 1232 * 3
                                                         + 3 * 384)


def test_step_mfu_reads_the_number_pinned_for_the_qwen_cell():
    # read, to the last digit, when the counts lived in flops.py
    doc = json.loads((HERE / "configs" / "qwen2_5_7b_l1.json").read_text())
    m = run_cell.model_sizes(doc, dense_decoder)
    assert dense_decoder.matmul_params(m) == 301170688
    rec = run_cell.RunRecord(
        window=(100.0, 130.25), n_steps=12,
        lengths=[1152] * 60 + [700, 901, 1003, 129], spans=[], trace=None,
        model=m, peaks=flops.peaks("TPU v5 lite"), n_chips=1, counters={},
        reference=dense_decoder)
    got = run_cell._module(HERE / "metrics" / "step_mfu.py").read(rec)
    assert got == 2.9445297636528087


def test_fused_loss_cost_by_hand():
    assert flops.fused_rl_loss_cost("fwd", 4, 6, "bf16") == (168, 48 + 160)
    assert flops.fused_rl_loss_cost("bwd", 4, 6, "bf16") == (144, 96 + 80)
    with pytest.raises(ValueError):
        flops.fused_rl_loss_cost("sideways", 4, 6)


def test_call_shape_from_hlo_text():
    text = ("%jvp_jit_fused_rl_loss_fwd_kernel__.1 = (f32[192,1]{1,0}) "
            "custom-call(bf16[192,2048]{1,0:T(8,128)(2,1)} %pad.23, s32[192,1]")
    assert flops.call_shape(text) == ("bf16", 192, 2048)
    with pytest.raises(ValueError):
        flops.call_shape("%fusion.3 = f32[4] fusion(f32[4] %x)")


def test_peaks_known_and_unknown_device():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")

"""A configuration of another family joins the benchmark by new files
alone: its reference module carries the key map, the architecture it
computes and the counts of model FLOPs, and nothing of the harness names
a family.

The stub below stands for a ``references/<name>.py`` of DeepSeek-V2
(latent attention, routed and shared experts); the configuration's
``config`` object holds DeepSeek-V2-Lite's ``config.json`` keys, as the
catalog entry's ``config`` does, at a width the CPU runs.
"""
import types

import pytest

import chip_bench_tiny as tiny  # puts the harness and the system on the path
import flops  # noqa: E402
import run_cell  # noqa: E402
import weights  # noqa: E402

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}


def _stub():
    ref = types.ModuleType("deepseek_v2_stub")
    ref.SOURCE_KEYS = {
        "num_hidden_layers": "num_layers", "hidden_size": "d_model",
        "num_attention_heads": "num_heads",
        "num_key_value_heads": "num_kv_heads",
        "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
        "vocab_size": "vocab_size", "kv_lora_rank": "kv_lora_rank",
        "q_lora_rank": "q_lora_rank", "qk_rope_head_dim": "qk_rope_head_dim",
        "qk_nope_head_dim": "qk_nope_head_dim", "v_head_dim": "v_head_dim",
        "n_routed_experts": "num_experts",
        "n_shared_experts": "num_shared_experts",
        "num_experts_per_tok": "top_k",
        "first_k_dense_replace": "first_dense_layers",
        "rope_theta": "rope_theta", "hidden_act": "activation",
        "tie_word_embeddings": "tie_embeddings"}
    ref.FIXED = {"model_type": "deepseek_v2", "attention_bias": False,
                 "max_position_embeddings": 163840, "moe_layer_freq": 1,
                 "n_group": 1, "topk_group": 1, "topk_method": "greedy",
                 "norm_topk_prob": False, "scoring_func": "softmax",
                 "routed_scaling_factor": 1, "seq_aux": True,
                 "rms_norm_eps": 1e-06, "rope_scaling": YARN}
    ref.ARCH = {"arch_type": "moe", "attention": "mla"}
    ref.defaults = lambda m: {"q_lora_rank": 0, "rope_theta": 10000.0,
                              "tie_embeddings": False}
    # stand-in counts: the test checks which module counts, not how
    ref.matmul_params = lambda m: 1000 * m["num_layers"]
    ref.attention_flops_fwd = lambda m, n: 7 * n * m["kv_lora_rank"]
    return ref


LITE_TINY = {
    "arch": "deepseek_v2_236b", "reference": "deepseek_v2_stub",
    "config": {
        "attention_bias": False, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
        "kv_lora_rank": 32, "max_position_embeddings": 163840,
        "model_type": "deepseek_v2", "moe_intermediate_size": 24,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 8,
        "n_shared_experts": 2, "norm_topk_prob": False,
        "num_attention_heads": 4, "num_experts_per_tok": 6,
        "num_hidden_layers": 3, "num_key_value_heads": 4,
        "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "rms_norm_eps": 1e-06, "rope_scaling": YARN, "rope_theta": 10000,
        "routed_scaling_factor": 1, "scoring_func": "softmax",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "greedy", "v_head_dim": 16, "vocab_size": 256},
    "reduced": {"num_hidden_layers": [27, 3], "vocab_size": [102400, 256]},
    "assumed": ["every width cut to run on the CPU"]}


def _with(config=(), **top):
    """LITE_TINY with ``config`` entries and top-level keys replaced."""
    return {**LITE_TINY, **top, "config": {**LITE_TINY["config"],
                                           **dict(config)}}


def test_model_sizes_by_the_references_key_map():
    m = run_cell.model_sizes(LITE_TINY, _stub())
    assert m == {"num_layers": 3, "d_model": 64, "num_heads": 4,
                 "num_kv_heads": 4, "d_ff": 96, "moe_d_ff": 24,
                 "vocab_size": 256, "kv_lora_rank": 32, "q_lora_rank": 0,
                 "qk_rope_head_dim": 8, "qk_nope_head_dim": 16,
                 "v_head_dim": 16, "num_experts": 8, "num_shared_experts": 2,
                 "top_k": 6, "first_dense_layers": 1, "rope_theta": 10000,
                 "activation": "silu", "tie_embeddings": False}
    cfg = run_cell.model_config(LITE_TINY, _stub())
    assert (cfg.arch_type, cfg.attention) == ("moe", "mla")
    assert all(getattr(cfg, k) == v for k, v in m.items())


@pytest.mark.parametrize("conf,error", [
    (_with({"sliding_window": 64}), KeyError),       # a key the stub maps not
    (_with({"norm_topk_prob": True}), ValueError),   # not what it computes
    (_with({"kv_lora_rank": None}), ValueError),     # a null it gives no value
    (_with(arch="qwen2_5_7b"), ValueError)])         # a dense GQA program
def test_a_file_the_reference_does_not_compute_is_an_error(conf, error):
    with pytest.raises(error):
        run_cell.model_config(conf, _stub())


def test_weights_take_the_programs_tree():
    import jax

    from repro.models import init_params
    cfg = run_cell.model_config(LITE_TINY, _stub())
    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    got = weights.make(cfg, 11)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(got)] == [
        (a.shape, a.dtype) for a in jax.tree.leaves(want)]
    assert got["blocks"]["ffn"]["experts"]["up"].shape[:2] == (2, 8)
    assert "dense_blocks" in got and "w_dkv" in got["blocks"]["attn"]


def test_step_mfu_counts_by_the_cells_reference():
    cell = run_cell.Cell("lite_tiny", 1, LITE_TINY, tiny.cell().traffic, {},
                         [], [])
    cell.reference = _stub()            # as the file's name would load it
    assert cell.model_config.num_experts == 8
    rec = run_cell.RunRecord(
        window=(0.0, 2.0), n_steps=1, lengths=[10, 20], spans=[], trace=None,
        model=cell.model, peaks={"bf16_flops_per_s": 1e6}, n_chips=1,
        counters={}, reference=cell.reference)
    # per row of n: (2 + 6) * 3000 * n + (1 + 3) * 7 * n * 32
    work = sum(8 * 3000 * n + 4 * 7 * n * 32 for n in (10, 20))
    assert flops.train_flops(cell.reference, cell.model, 10) == (
        6 * 3000 * 10 + 3 * 7 * 10 * 32)
    got = run_cell._module(tiny.HERE / "metrics" / "step_mfu.py").read(rec)
    assert got == pytest.approx(100.0 * work / 2e6, rel=1e-12)

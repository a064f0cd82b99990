"""The device-trace reduction and the readers that use it, on a trace
recorded on one TPU v5e: a traced run of the tiny cell
(``chip_bench_tiny``, two window steps) through the harness, whose
result line is kept beside it. The file keeps what the reduction reads:
the device's ``XLA Ops`` and ``XLA Modules`` events and the host's
``bench.*`` annotations inside the window, op names cut to 400
characters; the reduction gives the same numbers on it as on the whole
recording."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import flops  # noqa: E402
import run_cell  # noqa: E402
import trace_reduce  # noqa: E402

DATA = HERE / "testdata"
XPLANE = DATA / "tiny_run.xplane.pb"
RESULT = json.loads((DATA / "tiny_run.result.json").read_text())
dense_decoder = run_cell.reference({"reference": "dense_decoder"})


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(XPLANE)


def test_busy_and_window_as_the_chip_run_read_them(summary):
    assert summary["n_devices"] == 1
    assert summary["window_s"] == RESULT["device"]["window_s"]
    assert summary["busy_s"] == RESULT["device"]["busy_s"]
    assert 0 < summary["busy_s"] < summary["window_s"]


def test_fused_loss_kernel_calls_in_the_window(summary):
    names = [c[0] for c in summary["custom_calls"]]
    fwd = [n for n in names if "fused_rl_loss_fwd_kernel" in n]
    bwd = [n for n in names if "fused_rl_loss_bwd_kernel" in n]
    # two window steps of two micro-batches: one forward and one backward
    # kernel call per micro-batch
    assert len(fwd) == len(bwd) == 4
    for _, seconds, text in summary["custom_calls"]:
        if "fused_rl_loss" in text:
            dtype, n, v = flops.call_shape(text)
            assert dtype == "bf16" and n % 8 == 0 and v % 128 == 0
            assert seconds > 0


def test_breakdown_lists(summary):
    ops, gaps = summary["device_ops"], summary["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert all(":" in name and sec > 0 for name, sec in ops)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert all(n == "none" or n.startswith("bench.") for n, _ in gaps)


def test_readers_on_the_recorded_trace(summary):
    rec = run_cell.RunRecord(
        window=(0.0, summary["window_s"]), n_steps=2, lengths=[24] * 16,
        spans=[], trace=summary, model=run_cell.model_sizes(
            {"reference": "dense_decoder",
             "config": {"hidden_size": 64, "intermediate_size": 128,
                        "num_attention_heads": 4, "num_key_value_heads": 2,
                        "num_hidden_layers": 2, "vocab_size": 512},
             "program": {"qkv_bias": True}}, dense_decoder),
        peaks=flops.peaks("TPU v5 lite"), n_chips=1, counters={},
        reference=dense_decoder)
    read = lambda name: run_cell._module(  # noqa: E731
        HERE / "metrics" / f"{name}.py").read(rec)
    roof = read("fused_rl_loss_roofline")
    assert 0 < roof["value"] <= 100 and roof["bound"] == "memory"
    assert roof["value"] == RESULT["metrics"]["fused_rl_loss_roofline"][
        "value"]
    idle = read("device_idle_share")
    assert idle == RESULT["metrics"]["device_idle_share"]["value"]
    assert 0 < read("step_mfu") < 100
    assert read("weight_sync_s_per_step") is None   # no counters given


def test_weight_sync_reads_the_programs_histogram_change():
    rec = run_cell.RunRecord(
        window=(0.0, 10.0), n_steps=2, lengths=[], spans=[], trace=None,
        model={}, peaks={}, n_chips=1,
        counters={"weight_sync_seconds.publish": 0.5,
                  "weight_sync_count.publish": 2,
                  "weight_sync_seconds.swap": 0.25,
                  "weight_sync_count.swap": 2}, reference=None)
    got = run_cell._module(
        HERE / "metrics" / "weight_sync_s_per_step.py").read(rec)
    assert got == {"value": 0.375, "publish_s": 0.25, "swap_s": 0.125}


def test_no_window_span_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(tmp_path)

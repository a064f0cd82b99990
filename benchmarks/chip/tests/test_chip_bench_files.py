"""The benchmark's files: every piece a cell names is found by name and
loads, and names, units and keys keep to the benchmark's contract."""
import dataclasses
import json
import re
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import run_cell  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(_dim|_rank|_size|_width)$|latent|state|expan|"
                   r"experts_per_tok|head_")


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run_cell.py"
    assert (ROOT / BENCH["command"][1]).is_file()
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)


def test_metric_entries():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in BENCH["workloads"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        layers.setdefault(m["layer"], set()).add(m["name"])
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
    assert len(layers) >= 4


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_loads_and_runs_as_stated(conf):
    from repro.configs import get_config
    doc = json.loads((ROOT / conf["file"]).read_text())
    assert conf["file"].startswith("benchmarks/chip/configs/")
    assert sorted(doc["reduced"]) == sorted(conf["reduced"])
    # the vocabulary may be a chip's share; no width may be cut
    assert not any(WIDTH.search(k) for k in conf["reduced"]
                   if k != "vocab_size")
    for k, (published, run) in doc["reduced"].items():
        assert doc["config"][k] == run != published
    assert (HERE / "references" / f"{doc['reference']}.py").is_file()
    ref = run_cell.reference(doc)
    m = run_cell.model_sizes(doc, ref)
    cfg = dataclasses.replace(get_config(doc["arch"]), **m)
    assert {k: getattr(cfg, k) for k in ref.ARCH} == ref.ARCH
    assert run_cell.model_config(doc, ref) == cfg


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_found_by_name(w):
    cell = run_cell.load_cell(w["name"])
    assert cell.chips == w["chips"] == 1
    assert set(cell.limits) == {"loss_gap", "grad_gap", "update_gap",
                                "rollout_lp_gap", "rollout_lp_mean_gap"}
    assert all(0 < v < 1 for v in cell.limits.values())
    tr = cell.traffic
    lo, hi = tr["prompt_len"]
    assert 0 < lo <= hi
    assert tr["trainer"]["seq_len"] >= -(-hi // 8) * 8 + tr["trainer"][
        "max_new_tokens"]
    # step k's rows come from weights of version k - 2 * staleness or
    # later, so the last warm step checks rows made after a weight swap
    assert tr["warm_steps"] >= 2 * tr["trainer"].get("staleness", 0) + 2
    assert tr["min_window_steps"] >= 1
    assert {m["name"] for m in cell.per_layer} and {
        m["name"] for m in cell.end_to_end} >= {"setup_s"}
    for m in cell.per_layer:
        run_cell._module(HERE / "metrics" / f"{m['name']}.py").read


def test_missing_workload_is_an_error():
    with pytest.raises(KeyError):
        run_cell.load_cell("no_such.cell")

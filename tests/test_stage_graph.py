"""Streaming stage-graph subsystem: topology validation, per-stage
pipeline overlap, fused-vs-staged GRPO equivalence, PPO through the
graph in all three workflow modes, and custom stage registration."""
import dataclasses
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from conftest import tiny_cfg
from repro.api import AsyncFlowService, Trainer, TrainerConfig
from repro.core.obs import MetricsRegistry
from repro.core.workflow import (AsyncRLRunner, StageGraph, StageRunner,
                                 StageSpec, WorkflowConfig, build_dataflow)
from repro.data import PromptDataset
from repro.engines import JaxRolloutEngine, JaxTrainEngine
from repro.models import init_params
from repro.rl.grpo import GRPOConfig
from repro.training.optimizer import OptimizerConfig


# ---------------------------------------------------------------------- #
# topology validation                                                     #
# ---------------------------------------------------------------------- #

def test_graph_missing_producer_rejected():
    g = StageGraph(source_columns=("prompt",))
    g.add(StageSpec("a", inputs=("prompt", "nope"), outputs=("x",)))
    with pytest.raises(ValueError, match="no producer"):
        g.validate()


def test_graph_duplicate_producer_rejected():
    g = StageGraph(source_columns=("prompt",))
    g.add(StageSpec("a", inputs=("prompt",), outputs=("x",)))
    g.add(StageSpec("b", inputs=("prompt",), outputs=("x",)))
    with pytest.raises(ValueError, match="produced by both"):
        g.validate()


def test_graph_cycle_rejected():
    g = StageGraph(source_columns=())
    g.add(StageSpec("a", inputs=("y",), outputs=("x",)))
    g.add(StageSpec("b", inputs=("x",), outputs=("y",)))
    with pytest.raises(ValueError, match="cycle"):
        g.validate()


def test_graph_self_loop_rejected():
    g = StageGraph(source_columns=())
    g.add(StageSpec("a", inputs=("x",), outputs=("x",)))
    with pytest.raises(ValueError, match="own output|cycle"):
        g.validate()


def test_graph_topo_order():
    g = build_dataflow("ppo", kl_coef=0.1)
    order = [s.name for s in g.topo_order()]
    assert order.index("generate") < order.index("values")
    assert order.index("values") < order.index("advantage")
    assert order.index("reward") < order.index("advantage")
    assert order.index("advantage") < order.index("actor_update")
    assert order.index("ref_inference") < order.index("actor_update")


def test_unknown_dataflow():
    with pytest.raises(KeyError, match="unknown dataflow"):
        build_dataflow("definitely_not_registered")


# ---------------------------------------------------------------------- #
# generic StageRunner (no JAX): a 3-stage toy dataflow streams and        #
# overlaps per stage                                                      #
# ---------------------------------------------------------------------- #

def _toy_graph():
    def gen(batch, *, params, rng, version=0, **kw):
        time.sleep(0.01)
        return {"rows": [dict(item=x, token_len=1)
                         for x in batch["prompt"] for _ in range(2)]}

    def enrich(batch, *, indices, **kw):
        time.sleep(0.004)
        return {"updates": {"score": [v + 1 for v in batch["item"]]}}

    def train(batch, **kw):
        time.sleep(0.002)
        assert all(s == v + 1 for v, s in zip(batch["item"],
                                              batch["score"]))
        return {"n": len(batch["version"])}

    g = StageGraph(source_columns=("prompt",))
    g.add(StageSpec("generate", inputs=("prompt",),
                    outputs=("item", "version"), engine="", fn=gen,
                    kind="generate"))
    g.add(StageSpec("enrich", inputs=("item",), outputs=("score",),
                    fn=enrich))
    g.add(StageSpec("actor_update", inputs=("item", "score", "version"),
                    engine="trainer", fn=train, kind="train",
                    drives_steps=True))
    return g


def test_stage_runner_toy_dataflow_streams_per_stage():
    cfg = WorkflowConfig(mode="streaming", num_rollout_workers=2,
                         rollout_batch=2, train_micro_batch=4,
                         prompts_per_step=4, group_size=2, num_steps=3)
    runner = StageRunner(
        cfg, _toy_graph(),
        engines={"trainer": SimpleNamespace(params={"w": 0})},
        prompt_stream=lambda s: [1, 2, 3, 4])
    r = runner.run()
    assert r.samples_trained == 3 * 8
    assert max(r.staleness_seen) == 0          # streaming is on-policy
    kinds = {e.kind for e in r.log.events()}
    assert "enrich" in kinds and "generate" in kinds and "update" in kinds
    # pipeline overlap: the intermediate stage starts before the last
    # generation finishes (no global-batch barrier between stages)
    enrich_ev = [e for e in r.log.events() if e.kind == "enrich"]
    gen_ev = [e for e in r.log.events() if e.kind == "generate"]
    assert min(e.start for e in enrich_ev) < max(e.end for e in gen_ev)


@pytest.mark.parametrize("mode,rows", [("streaming", [2, 4]),
                                       ("baseline", [6])])
def test_stage_runner_warms_engines_before_stage_threads(mode, rows):
    """run() first hands each engine that has a ``warm_up`` the shapes the
    run gives it: every prompt fed, the most prompts per generate call,
    and each row count the step driver fetches. The prompts it looked
    ahead at are fed as they are, each step's fetched once."""
    calls, streamed = [], []

    def gen(batch, *, params, rng, version=0, **kw):
        calls.append("generate")
        return {"rows": [dict(item=x, token_len=1)
                         for x in batch["prompt"] for _ in range(2)]}

    def prompts(step):
        streamed.append(step)
        return [10 * step + i for i in range(3)]

    g = _toy_graph()
    g.stages["generate"] = dataclasses.replace(g.stages["generate"],
                                               engine="rollout", fn=gen)
    engines = {
        "rollout": SimpleNamespace(warm_up=lambda params, ps, k: calls.append(
            ("gen", sorted(ps), k))),
        "trainer": SimpleNamespace(params={"w": 0}, warm_up=lambda r:
                                   calls.append(("train", r)))}
    cfg = WorkflowConfig(mode=mode, num_rollout_workers=1, rollout_batch=2,
                         train_micro_batch=4, prompts_per_step=3,
                         group_size=2, num_steps=2)
    r = StageRunner(cfg, g, engines=engines, prompt_stream=prompts,
                    metrics=MetricsRegistry()).run()
    assert calls[:2] == [("gen", [0, 1, 2, 10, 11, 12], 2), ("train", rows)]
    assert set(calls[2:]) == {"generate"}
    assert streamed == [0, 1]
    assert r.samples_trained == 2 * 6


def test_rollout_warm_up_runs_each_generate_bucket_once(monkeypatch):
    """Batches of 1..3 prompts x G=4 pad to 4, 8 and 16 rows; prompts of
    5, 8 and 13 tokens pad to 8 and 16: six buckets, one call each."""
    from repro.engines import rollout_engine
    shapes = []
    monkeypatch.setattr(rollout_engine, "sample_generate",
                        lambda params, cfg, ps, seed, **kw: shapes.append(
                            (len(ps), len(ps[0]))))
    eng = JaxRolloutEngine(tiny_cfg(), group_size=4)
    eng.warm_up({}, [{"tokens": [1] * n} for n in (5, 8, 13, 5)], 3)
    assert sorted(shapes) == [(b, n) for b in (4, 8, 16) for n in (8, 16)]


def test_stage_runner_raises_when_a_step_gets_no_rows(monkeypatch):
    """A step driver that waits out its row timeout fails the run; it
    does not return a result with fewer steps than asked for."""
    from repro.core.workflow import stage_graph
    monkeypatch.setattr(stage_graph, "STEP_ROWS_TIMEOUT_S", 0.3)

    def gen_first_step_only(batch, *, params, rng, version=0, **kw):
        return {"rows": [dict(item=x, token_len=1)
                         for x in batch["prompt"] if x == 0
                         for _ in range(2)]}

    g = _toy_graph()
    g.stages["generate"] = dataclasses.replace(g.stages["generate"],
                                               fn=gen_first_step_only)
    cfg = WorkflowConfig(mode="streaming", num_rollout_workers=1,
                         rollout_batch=2, train_micro_batch=4,
                         prompts_per_step=4, group_size=2, num_steps=3)
    runner = StageRunner(
        cfg, g, engines={"trainer": SimpleNamespace(params={"w": 0})},
        prompt_stream=lambda s: [s] * 4, metrics=MetricsRegistry())
    with pytest.raises(RuntimeError, match="step 1: no rows"):
        runner.run()
    assert runner.samples_trained == 8


def _graph_with_slow_stream_stage(seconds):
    g = _toy_graph()
    calls = []

    def slow_sink(batch, **kw):
        if not calls:
            time.sleep(seconds)
        calls.append(len(batch["item"]))
        return {"n": len(batch["item"])}

    g.add(StageSpec("critic", inputs=("item", "score"), engine="",
                    fn=slow_sink, kind="train_stream"))
    return g, calls


def _toy_runner(graph):
    cfg = WorkflowConfig(mode="streaming", num_rollout_workers=1,
                         rollout_batch=2, train_micro_batch=4,
                         prompts_per_step=4, group_size=2, num_steps=2)
    return StageRunner(
        cfg, graph, engines={"trainer": SimpleNamespace(params={"w": 0})},
        prompt_stream=lambda s: [1, 2, 3, 4], metrics=MetricsRegistry())


def test_stage_runner_waits_for_streaming_train_stage_to_drain():
    """A streaming train stage still busy when the last step ends drains
    every row before run() returns (it used to be cut off at 5 s)."""
    g, calls = _graph_with_slow_stream_stage(5.5)
    r = _toy_runner(g).run()
    assert sum(calls) == 2 * 8
    assert sum(m["n"] for m in r.aux_metrics["critic"]) == 2 * 8


def test_stage_runner_fails_a_streaming_stage_that_never_drains(
        monkeypatch):
    from repro.core.workflow import stage_graph
    monkeypatch.setattr(stage_graph, "STEP_ROWS_TIMEOUT_S", 0.3)
    g, _ = _graph_with_slow_stream_stage(1.5)
    with pytest.raises(RuntimeError, match="'critic'.*still draining"):
        _toy_runner(g).run()


def test_stage_runner_auto_sizes_zero_worker_stages():
    """auto_size_workers=True planner-sizes every stage left at
    num_workers=0 and the run still trains the exact sample count."""
    cfg = WorkflowConfig(mode="streaming", num_rollout_workers=2,
                         rollout_batch=2, train_micro_batch=4,
                         prompts_per_step=4, group_size=2, num_steps=3,
                         auto_size_workers=True, max_stage_workers=4)
    runner = StageRunner(
        cfg, _toy_graph(),
        engines={"trainer": SimpleNamespace(params={"w": 0})},
        prompt_stream=lambda s: [1, 2, 3, 4], metrics=MetricsRegistry())
    assert set(runner.stage_costs) == {"generate", "enrich", "actor_update"}
    assert runner._desired["actor_update"] == 1
    assert all(1 <= n <= 4 for n in runner._desired.values())
    r = runner.run()
    assert r.samples_trained == 3 * 8
    snap = {tuple(sorted(row["labels"].items())): row["value"]
            for row in runner.registry.get("stage_workers").snapshot()}
    assert snap[(("stage", "actor_update"),)] == 1


def test_stage_runner_elastic_grows_starved_generate_pool():
    """Live rebalance: a single slow generate worker starves the driver,
    the elastic monitor grows the pool mid-run, and the run completes."""
    def slow_gen(batch, *, params, rng, version=0, **kw):
        time.sleep(0.05)
        return {"rows": [dict(item=x, token_len=1)
                         for x in batch["prompt"] for _ in range(2)]}

    g = _toy_graph()
    g.stages["generate"] = dataclasses.replace(g.stages["generate"],
                                               fn=slow_gen)
    cfg = WorkflowConfig(mode="streaming", num_rollout_workers=1,
                         rollout_batch=1, train_micro_batch=4,
                         prompts_per_step=4, group_size=2, num_steps=10,
                         elastic_interval_s=0.1, max_stage_workers=4)
    runner = StageRunner(
        cfg, g, engines={"trainer": SimpleNamespace(params={"w": 0})},
        prompt_stream=lambda s: [1, 2, 3, 4], metrics=MetricsRegistry())
    r = runner.run()
    assert r.samples_trained == 10 * 8
    reb = runner.registry.get("stage_rebalance_total")
    assert reb is not None
    # the starved driver made the monitor grow the generate pool mid-run
    # (it may shrink again once the prompt stream drains at the tail)
    assert reb.value(stage="generate", action="grow") >= 1
    assert runner.registry.get("stage_workers").value(stage="generate") >= 1


def test_stage_runner_requires_generate_and_driver():
    g = StageGraph(source_columns=("prompt",))
    g.add(StageSpec("a", inputs=("prompt",), outputs=("x",)))
    cfg = WorkflowConfig(num_steps=1)
    with pytest.raises(ValueError, match="generate stage"):
        StageRunner(cfg, g, engines={}, prompt_stream=lambda s: [])


# ---------------------------------------------------------------------- #
# GRPO: staged graph reproduces the fused (pre-refactor) pipeline on a    #
# fixed seed                                                              #
# ---------------------------------------------------------------------- #

def test_grpo_staged_matches_fused_fixed_seed():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    common = dict(mode="baseline", num_steps=3, prompts_per_step=2,
                  group_size=2, train_micro_batch=4)
    # deterministic schedule: one worker, whole-step generate batches,
    # one storage unit (atomic batch availability)
    opt = OptimizerConfig(lr=3e-4, warmup_steps=2, total_steps=3,
                          schedule=cfg.lr_schedule
                          if cfg.lr_schedule != "cosine" else "constant")
    fused_train = JaxTrainEngine(cfg, params, rl=GRPOConfig(), opt=opt,
                                 global_batch=4, seq_len=24)
    fused = AsyncRLRunner(
        WorkflowConfig(num_rollout_workers=1, rollout_batch=2,
                       num_storage_units=1, **common),
        rollout_engine=JaxRolloutEngine(cfg, group_size=2,
                                        max_new_tokens=4),
        train_engine=fused_train,
        prompt_stream=lambda s: PromptDataset(seed=0).prompts_for_step(s, 2))
    r_fused = fused.run()

    tcfg = TrainerConfig(num_steps=3, prompts_per_step=2, group_size=2,
                         rollout_workers=1, rollout_batch=2,
                         train_micro_batch=4, max_new_tokens=4, seq_len=24,
                         mode="baseline", num_storage_units=1, seed=0)
    r_staged = Trainer(tcfg, model_cfg=cfg, params=params).fit()

    assert len(r_fused.metrics) == len(r_staged.metrics) == 3
    for mf, ms in zip(r_fused.metrics, r_staged.metrics):
        assert mf["step"] == ms["step"]
        for k in ("loss", "policy_loss", "grad_norm", "mean_reward"):
            np.testing.assert_allclose(mf[k], ms[k], rtol=1e-4, atol=1e-5,
                                       err_msg=k)


# ---------------------------------------------------------------------- #
# GRPO + KL through the graph: ref_inference and reward stream as         #
# distinct overlapping stages                                             #
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("mode", ["baseline", "streaming", "async"])
def test_grpo_kl_stages_stream_and_overlap(mode):
    tcfg = TrainerConfig(mode=mode, num_steps=2, prompts_per_step=4,
                         group_size=2, rollout_workers=2, rollout_batch=2,
                         train_micro_batch=4, max_new_tokens=4, seq_len=24,
                         kl_coef=0.05)
    r = Trainer(tcfg).fit()
    assert len(r.metrics) == 2
    assert all(np.isfinite(m["loss"]) for m in r.metrics)
    assert max(r.staleness_seen) <= (2 if mode == "async" else 0)
    ev = r.log.events()
    ref_ev = [e for e in ev if e.kind == "ref_inference"]
    rew_ev = [e for e in ev if e.kind == "reward"]
    gen_ev = [e for e in ev if e.kind == "generate"]
    assert ref_ev and rew_ev, "ref_inference/reward must be own stages"
    # streaming overlap: intermediate stages start while generation for
    # later rows is still running — no stage waits for the global batch
    assert min(e.start for e in ref_ev) < max(e.end for e in gen_ev)
    assert min(e.start for e in rew_ev) < max(e.end for e in gen_ev)
    # and the bubble accounting sees the new stages as busy time
    bf = r.log.bubble_fraction()
    assert any(k.startswith("ref_inference") for k in bf)
    assert any(k.startswith("reward") for k in bf)


# ---------------------------------------------------------------------- #
# PPO end-to-end through the graph in all three workflow modes            #
# ---------------------------------------------------------------------- #

def test_ppo_all_modes_through_stage_graph():
    for mode in ("baseline", "streaming", "async"):
        tcfg = TrainerConfig(algorithm="ppo", mode=mode, num_steps=2,
                             prompts_per_step=2, group_size=2,
                             rollout_workers=2, rollout_batch=1,
                             train_micro_batch=2, max_new_tokens=4,
                             seq_len=24)
        r = Trainer(tcfg).fit()
        assert r.samples_trained == 2 * 4, mode
        assert len(r.metrics) == 2, mode       # one actor step per step
        assert all(np.isfinite(m["loss"]) for m in r.metrics), mode
        critic = r.aux_metrics.get("critic_update", [])
        assert critic and all(np.isfinite(m["value_loss"]) for m in critic)
        kinds = {e.kind for e in r.log.events()}
        assert {"values", "advantage", "critic_update"} <= kinds, mode
        if mode == "baseline":
            assert max(r.staleness_seen) == 0
        if mode == "async":
            assert max(r.staleness_seen) <= 2


# ---------------------------------------------------------------------- #
# §5.1 service APIs: registering a custom stage onto a built-in dataflow  #
# ---------------------------------------------------------------------- #

def test_service_custom_stage_registration():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    svc = AsyncFlowService()
    graph = svc.build_dataflow("grpo", kl_coef=0.0)

    def seq_stats(batch, *, indices, **kw):
        return {"updates": {"resp_len":
                            [int(np.asarray(m).sum())
                             for m in batch["response_mask"]]}}

    svc.register_stage(graph, StageSpec(
        "seq_stats", inputs=("response_mask",), outputs=("resp_len",),
        fn=seq_stats))
    graph.validate()

    wcfg = WorkflowConfig(mode="streaming", num_rollout_workers=1,
                          rollout_batch=2, train_micro_batch=4,
                          prompts_per_step=2, group_size=2, num_steps=1)
    engines = {
        "rollout": JaxRolloutEngine(cfg, group_size=2, max_new_tokens=4),
        "actor": JaxTrainEngine(cfg, params, global_batch=4, seq_len=24)}
    r = svc.run_dataflow(graph, wcfg,
                         lambda s: PromptDataset(seed=0).prompts_for_step(
                             s, 2),
                         engines=engines)
    assert r.samples_trained == 4
    assert any(e.kind == "seq_stats" for e in r.log.events())


def test_service_register_custom_dataflow():
    svc = AsyncFlowService()
    svc.register_dataflow("toy", lambda **kw: _toy_graph())
    g = svc.build_dataflow("toy")
    assert set(g.stages) == {"generate", "enrich", "actor_update"}

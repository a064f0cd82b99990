"""Async workflow: mode semantics, staleness invariants, weight sync."""
import time

import numpy as np
import pytest

from repro.core.workflow import (AsyncRLRunner, EventLog, WeightChannel,
                                 WeightReceiver, WeightSender,
                                 WorkflowConfig)


class SleepRollout:
    def __init__(self, dt=0.015, group=2):
        self.dt, self.group = dt, group

    def generate(self, params, prompts, rng):
        time.sleep(self.dt * len(prompts))
        return [dict(prompt=p, response=[1, 2], logprob=[0.0, 0.0],
                     response_mask=[0, 1], reward=1.0, advantage=0.5,
                     token_len=2)
                for p in prompts for _ in range(self.group)]


class SleepTrain:
    def __init__(self, dt=0.003):
        self.params = {"w": np.zeros(3)}
        self.dt = dt

    def update(self, batch):
        time.sleep(self.dt * len(batch["version"]))
        return {"loss": 0.0}


def _run(mode, **kw):
    base = dict(num_rollout_workers=2, rollout_batch=2, train_micro_batch=4,
                prompts_per_step=8, group_size=2, num_steps=5)
    base.update(kw)
    cfg = WorkflowConfig(mode=mode, **base)
    return AsyncRLRunner(cfg, rollout_engine=SleepRollout(),
                         train_engine=SleepTrain(),
                         prompt_stream=lambda s: [[1, 2]] * 8).run()


def test_mode_ordering_and_staleness():
    rs = {m: _run(m) for m in ("baseline", "streaming", "async")}
    assert max(rs["baseline"].staleness_seen) == 0
    assert max(rs["streaming"].staleness_seen) == 0
    assert 1 <= max(rs["async"].staleness_seen) <= 2


def test_all_samples_trained_every_mode():
    for m in ("baseline", "streaming", "async"):
        r = _run(m)
        assert len(r.staleness_seen) == r.samples_trained == 5 * 16


def test_staggered_substep_async():
    r = _run("async", staggered=True)
    assert max(r.staleness_seen) <= 2
    assert len(r.staleness_seen) == 80


def test_staleness_property_many_seeds():
    """Hard invariant: async staleness never exceeds cfg.staleness + 1."""
    for workers in (1, 2, 3):
        r = _run("async", num_rollout_workers=workers)
        assert max(r.staleness_seen) <= 2
        assert np.mean(r.staleness_seen) <= 1.0 + 1e-9


def test_weight_sender_receiver_versions():
    ch = WeightChannel()
    s = WeightSender(ch, mode="async")
    r = WeightReceiver(ch, {"w": np.zeros(2)}, version=0)
    s.publish({"w": np.ones(2)}, 1)
    s.flush()
    assert r.staged_version() == 1
    assert r.maybe_swap()
    assert r.version == 1 and float(r.params["w"][0]) == 1.0
    assert not r.maybe_swap()  # idempotent
    # stale publishes never regress
    s.publish({"w": np.zeros(2)}, 1)
    s.flush()
    s.publish({"w": 2 * np.ones(2)}, 3)
    s.flush()
    assert r.wait_and_swap(2, timeout=1.0)
    assert r.version == 3


def test_weight_channel_bandwidth_delay():
    ch = WeightChannel(bandwidth_gbps=1.0)  # 1 Gb/s
    s = WeightSender(ch, mode="sync")
    payload = {"w": np.zeros(125_000, np.int8)}  # 125 KB -> ~1 ms
    t0 = time.monotonic()
    s.publish(payload, 1)
    assert time.monotonic() - t0 >= 0.0009
    assert ch.bytes_sent == 125_000


def test_event_log_bubble_fraction():
    log = EventLog()
    t0 = time.monotonic()
    log.record("i0", "generate", t0, t0 + 1.0)
    log.record("i0", "wait", t0 + 1.0, t0 + 2.0)
    bf = log.bubble_fraction()
    assert abs(bf["i0"] - 0.5) < 1e-6
    assert "i0" in log.render_gantt(width=20)

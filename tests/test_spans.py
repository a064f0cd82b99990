"""The program's span primitive (``repro.core.obs.span``), the spans the
engines and the weight path record with it, and the counters kept at
the same boundaries."""
import collections
import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Trainer, TrainerConfig
from repro.core.obs import scoped, span
from repro.core.obs import spans
from repro.core.transfer_queue import TransferQueue
from repro.core.workflow.events import KINDS, EventLog, kind_info
from repro.core.workflow.weight_sync import (WeightChannel, WeightReceiver,
                                             WeightSender)


@pytest.fixture
def log():
    log = EventLog()
    prev = spans.set_log(log)
    yield log
    spans.set_log(prev)


def test_nested_spans_record_their_parent_and_instance(log):
    with span("update", instance="train-0", step=3, n=4):
        with span("update.grad"):
            pass
        with span("update.optimizer"):
            with span("compile-like"):
                pass
    ev = {e.kind: e for e in log.events()}
    assert ev["update"].parent is None and ev["update"].meta == {
        "step": 3, "n": 4}
    assert ev["update.grad"].parent == "update"
    assert ev["compile-like"].parent == "update.optimizer"
    assert {e.instance for e in ev.values()} == {"train-0"}
    assert ev["update"].start <= ev["update.grad"].start \
        <= ev["update.grad"].end <= ev["update"].end


def test_instance_is_per_thread(log):
    def worker(name, bound):
        if bound:
            spans.bind_instance(name)
        with span("generate"):
            with span("generate.rows"):
                pass

    threads = [threading.Thread(target=worker, args=("rollout-0", True)),
               threading.Thread(target=worker, args=("rollout-1", True)),
               threading.Thread(target=worker, args=("weight-sender", False),
                                name="weight-sender")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    by_inst = collections.defaultdict(set)
    for e in log.events():
        by_inst[e.instance].add((e.kind, e.parent))
    assert set(by_inst) == {"rollout-0", "rollout-1", "weight-sender"}
    assert all(v == {("generate", None), ("generate.rows", "generate")}
               for v in by_inst.values())


def test_without_an_active_log_a_span_only_annotates():
    prev = spans.set_log(None)
    other = EventLog()
    with span("generate.prepare"):
        pass
    spans.set_log(prev)
    assert prev is None and other.events() == []
    # EventLog.span records into its own log, active or not
    with other.span("rollout-0", "generate", n=2):
        pass
    assert [(e.instance, e.kind, e.meta) for e in other.events()] == [
        ("rollout-0", "generate", {"n": 2})]


def test_annotations_land_on_the_profiler_trace(tmp_path, log):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with span("update", instance="train-0", step=3, n=4):
        with span("update.grad"):
            jnp.ones(3).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    got = [(e.name, list(e.stats))
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("asyncflow.")]
    assert ("asyncflow.update", [("step", 3), ("n", 4)]) in got
    assert ("asyncflow.update.grad", []) in got
    assert [e.kind for e in log.events() if e.kind != "compile"] == [
        "update", "update.grad"]


def test_kind_table_drives_busy_wait_and_chrome_categories():
    assert kind_info("weight_sync").blocked and kind_info("wait").blocked
    assert kind_info("staleness_wait").layer == "weight sync"
    assert kind_info("wait").layer == "rollout"
    assert not kind_info("my_custom_stage").blocked
    log = EventLog()
    t = log.t0
    log.record("r", "generate", t, t + 1.0)
    log.record("r", "generate.device", t + 0.2, t + 0.9, parent="generate")
    log.record("r", "staleness_wait", t + 1.0, t + 2.0)
    # nested spans are inside their parent's time: not counted again
    assert log.busy_fraction("r") == pytest.approx(0.5)
    assert log.wait_fraction("r") == pytest.approx(0.5)
    cats = {e["name"]: e["cat"] for e in log.to_chrome_trace()["traceEvents"]
            if e["ph"] == "X"}
    assert cats == {"generate": "stage", "generate.device": "idle",
                    "staleness_wait": "idle"}


def test_compile_counter_counts_new_programs_only(log):
    f = jax.jit(lambda x: x * 2 + 1)
    x5, x7 = jnp.ones(5), jnp.ones(7)
    with scoped() as reg:
        with span("update", instance="train-0"):
            f(x5).block_until_ready()
        n1 = reg.counter("jit_compiles_total").total()
        f(x5).block_until_ready()                     # cached
        n2 = reg.counter("jit_compiles_total").total()
        f(x7).block_until_ready()                     # a new shape
        n3 = reg.counter("jit_compiles_total").total()
        assert reg.histogram("jit_compile_seconds").summary()["count"] == n3
    assert n1 == 1 and n2 == 1 and n3 == 2
    comp = [e for e in log.events() if e.kind == "compile"
            and e.meta["program"] == "jit(<lambda>)"]
    assert len(comp) == 2
    assert comp[0].parent == "update" and comp[0].instance == "train-0"


def test_row_wait_is_observed_once_per_row_handed_out():
    with scoped() as reg:
        tq = TransferQueue(capacity=8, tasks={"t": ["a", "b"]},
                           num_storage_units=1, metrics=reg)
        idx = tq.next_indices(6)
        tq.put_batch(idx, "a", list(range(6)))
        tq.put_batch(idx, "b", list(range(6)))
        got = tq.get("t", 4, consumer="c0", lease=True)
        tq.requeue("t", got["lease"])       # back to ready: handed again
        assert tq.get("t", 6, consumer="c1") is not None
        h = reg.get("tq_row_wait_seconds")
        consumed = reg.get("tq_rows_consumed_total").value(task="t")
        assert consumed == 10
        assert h.summary(task="t")["count"] == consumed
        assert all(w >= 0 for w in h.recent(100, task="t"))
        assert reg.get("tq_requests_total") is None
        assert reg.get("tq_rows_ready_total") is None


def _staged_run(mode, **kw):
    with scoped() as reg:
        tcfg = TrainerConfig(mode=mode, num_steps=3, prompts_per_step=2,
                             group_size=2, rollout_workers=1,
                             rollout_batch=2, train_micro_batch=2,
                             max_new_tokens=4, seq_len=24, **kw)
        r = Trainer(tcfg).fit()
    return r, reg


def test_staged_grpo_records_every_span_for_every_verb_call():
    r, reg = _staged_run("async", staleness=1)
    ev = r.log.events()
    kinds = collections.Counter(e.kind for e in ev)
    by = collections.defaultdict(list)
    for e in ev:
        by[e.kind].append(e)
    # rollout: each generate call has its prepare, device and rows parts
    n_gen = kinds["generate"]
    assert n_gen >= 3
    for part in ("generate.prepare", "generate.device"):
        assert kinds[part] == n_gen
        assert all(e.parent == "generate" for e in by[part])
    assert kinds["generate.rows"] == 2 * n_gen   # sampler and engine
    # update: each micro-batch is packed, graded, accumulated; each step
    # runs the optimizer
    n_up = kinds["update"]
    assert n_up == 3 * 2
    for part, parent in (("update.pack", "update"), ("update.grad", "update"),
                         ("update.accumulate", "update.grad")):
        assert kinds[part] == n_up
        assert all(e.parent == parent and e.instance == "train-0"
                   for e in by[part])
    assert kinds["update.optimizer"] == 3
    # the driver's wait and hand-off keep their kinds and instance
    assert kinds["wait"] >= n_up and kinds["weight_sync"] == 3
    assert {e.instance for e in by["wait"]} == {"train-0"}
    # the publish: a wait part and a copy part per publish, on the
    # sender's own thread
    for part in ("publish.wait", "publish.copy"):
        assert kinds[part] == 3
        assert {(e.instance, e.parent) for e in by[part]} == {
            ("weight-sender", None)}
    # every swap has a span: at the gate, or on the maybe_swap path
    swaps = reg.get("weight_sync_seconds").summary(role="swap")["count"]
    assert kinds["weight_swap"] == swaps
    assert {(e.instance, e.parent) for e in by["weight_swap"]} <= {
        ("rollout-0", None), ("rollout-0", "staleness_wait")}
    assert not any(e.kind not in KINDS for e in ev)
    # the publish histogram holds the whole publish, its wait part apart
    pub = reg.get("weight_sync_seconds").summary(role="publish")
    wait = reg.get("weight_publish_wait_seconds").summary()
    assert pub["count"] == wait["count"] == 3
    assert pub["sum"] >= wait["sum"] > 0
    rows = reg.get("tq_rows_consumed_total").value(task="actor_update")
    assert reg.get("tq_row_wait_seconds").summary(
        task="actor_update")["count"] == rows == r.samples_trained


def test_gate_waits_are_staleness_waits_not_weight_sync():
    r, _ = _staged_run("streaming")
    gate = [e for e in r.log.events() if e.kind == "staleness_wait"]
    assert gate and {e.instance for e in gate} == {"rollout-0"}
    assert not any(e.kind == "weight_sync" and e.instance == "rollout-0"
                   for e in r.log.events())
    # the swap made at the gate is nested in its wait
    assert any(e.kind == "weight_swap" and e.parent == "staleness_wait"
               for e in r.log.events())
    assert np.isfinite(r.metrics[-1]["loss"])


def test_publish_parts_on_the_sender_thread_and_every_swap_path(log):
    with scoped() as reg:
        channel = WeightChannel(metrics=reg)
        sender = WeightSender(channel, mode="async", metrics=reg)
        recv = WeightReceiver(channel, {"w": jnp.zeros(3)}, metrics=reg)
        spans.bind_instance("rollout-0")
        sender.publish({"w": jnp.ones(3)}, 1)
        sender.flush()
        assert recv.maybe_swap()                      # delayed update
        sender.publish({"w": jnp.full(3, 2.0)}, 2)
        with span("staleness_wait"):
            recv.wait_and_swap(2, timeout=5.0)        # the gate
        sender.flush()
        spans.bind_instance(None)
        pub = reg.get("weight_sync_seconds").summary(role="publish")
        wait = reg.get("weight_publish_wait_seconds").summary()
    got = [(e.instance, e.kind, e.parent, e.meta.get("version"))
           for e in log.events()]
    for v in (1, 2):
        assert ("weight-sender", "publish.wait", None, v) in got
        assert ("weight-sender", "publish.copy", None, v) in got
    assert ("rollout-0", "weight_swap", None, 1) in got
    assert ("rollout-0", "weight_swap", "staleness_wait", 2) in got
    assert recv.version == 2 and float(recv.params["w"][0]) == 2.0
    assert pub["count"] == wait["count"] == 2 and pub["sum"] >= wait["sum"]

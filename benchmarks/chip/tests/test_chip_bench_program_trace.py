"""The program's spans on the device trace and the readers built on them.

Most tests read a trace recorded on one TPU v5e: a traced run of the
tiny cell (``chip_bench_tiny``) through the harness, with the program's
``asyncflow.*`` spans, kept by ``record_tiny_trace.py`` beside the
run's result line. Others check the idle-slice charging rule on
made-up spans, the readers' silence on a program without spans, and
the two counter readers."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import program_trace  # noqa: E402
import run_cell  # noqa: E402
import trace_reduce  # noqa: E402

from repro.core.obs import scoped  # noqa: E402

DATA = HERE / "testdata"
XPLANE = DATA / "tiny_spans.xplane.pb"
RESULT = json.loads((DATA / "tiny_spans.result.json").read_text())
IDLE = ("idle_rollout_host_share", "idle_update_host_share",
        "idle_weight_sync_share", "idle_unattributed_share")


def read(name, rec):
    return run_cell._module(HERE / "metrics" / f"{name}.py").read(rec)


@pytest.fixture
def recorded(monkeypatch):
    """The recorded run as its readers saw it."""
    monkeypatch.setattr(program_trace, "xplane", XPLANE)
    summary = trace_reduce.reduce(XPLANE)
    return record(window=(0.0, summary["window_s"]), trace=summary)


def test_program_spans_of_the_driver_rollout_and_sender_threads(recorded):
    got = program_trace.for_run(recorded)
    lines = {line: {k for k, *_ in evs}
             for line, evs in got["program_spans"].items()}
    driver = [ln for ln, ks in lines.items() if {"update", "wait"} <= ks]
    rollout = [ln for ln, ks in lines.items()
               if {"generate", "generate.device"} <= ks]
    sender = [ln for ln, ks in lines.items()
              if {"publish.wait", "publish.copy"} <= ks]
    assert len(driver) == len(rollout) == len(sender) == 1
    assert len({driver[0], rollout[0], sender[0]}) == 3
    w = got["window_s"]
    for evs in got["program_spans"].values():
        assert all(0 <= s <= e <= w for _, s, e, _ in evs)
    update = [st for k, _, _, st in got["program_spans"][driver[0]]
              if k == "update"]
    assert update and all(set(st) == {"step", "n"} for st in update)


def test_idle_shares_partition_the_device_idle_share(recorded):
    shares = {name: read(name, recorded)["value"] for name in IDLE}
    idle = read("device_idle_share", recorded)
    assert idle == RESULT["metrics"]["device_idle_share"]["value"]
    assert sum(shares.values()) == pytest.approx(idle, abs=0.01)
    for name, value in shares.items():
        assert value == RESULT["metrics"][name]["value"]
    assert shares["idle_unattributed_share"] < idle / 4


def record(**kw):
    base = dict(window=(10.0, 20.0), n_steps=2, lengths=[24] * 8, spans=[],
                trace=None, model={}, peaks={}, n_chips=1, counters={},
                reference=None)
    return run_cell.RunRecord(**{**base, **kw})


def test_idle_goes_to_the_span_that_started_last():
    spans = [(0, 10, "generate"), (3, 4, "generate.device"),
             (2, 5, "publish.copy"), (8, 10, "wait")]
    segs = program_trace._segments(spans, 0, 12)
    assert segs == [(0, 2, "generate"), (2, 3, "publish.copy"),
                    (3, 4, "generate.device"), (4, 5, "publish.copy"),
                    (5, 8, "generate"), (8, 10, "wait"), (10, 12, None)]
    # a nested span that starts with its parent is the later one
    assert program_trace._segments([(0, 4, "update"), (0, 2, "update.pack")],
                                   0, 4) == [(0, 2, "update.pack"),
                                             (2, 4, "update")]
    holes = [(1e9, 2.5e9), (3.5e9, 3.6e9), (9e9, 11e9)]
    got = program_trace._charge(holes, program_trace._segments(
        [(s * 1e9, e * 1e9, k) for s, e, k in spans], 0, 12e9))
    assert got == pytest.approx({"generate": 1.0, "publish.copy": 0.5,
                                 "generate.device": 0.1, "wait": 1.0,
                                 None: 1.0})
    assert sum(got.values()) == pytest.approx(3.6)


def test_readers_are_silent_without_the_programs_spans(monkeypatch):
    rec = record(trace={"window_s": 10.0})
    monkeypatch.setattr(program_trace, "kind_layers", lambda: None)
    for name in ("idle_rollout_host_share", "idle_update_host_share",
                 "idle_weight_sync_share", "idle_unattributed_share"):
        assert read(name, rec) is None
        assert read(name, record()) is None        # an untraced run


def test_compiles_are_counted_inside_the_window():
    spans = [("train-0", "compile", 9.0, 9.5), ("train-0", "compile", 11.0,
                                                12.0),
             ("rollout-0", "compile", 19.9, 20.0),
             ("train-0", "update", 12.0, 13.0),
             ("train-0", "compile", 19.5, 20.5)]
    assert read("compiles_in_window", record(spans=spans)) == 2
    assert read("compiles_in_window", record()) == 0


def test_row_wait_reads_the_window_steps_rows():
    # the driver's task: 4 rows a step; 3 warm steps, the window's 2
    # steps (8 rows by their lengths, 4 a step), and the step after
    with scoped() as reg:
        h = reg.histogram("tq_row_wait_seconds")
        for w in [9.0] * 12 + [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0] \
                + [9.0] * 4:
            h.observe(w, task="actor_update")
        h.observe(100.0, task="reward")
        assert read("tq_row_wait_s", record()) == pytest.approx(4.5)
        assert read("tq_row_wait_s", record(lengths=[24] * 40)) is None
    with scoped():
        assert read("tq_row_wait_s", record()) is None   # no such counter

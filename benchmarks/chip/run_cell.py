"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run_cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a model configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``);
its limits are ``limits/<workload>.json`` and each per-layer metric is
read by ``metrics/<metric>.py``. The configuration names its plain
reference (``references/<name>.py``), which holds all that belongs to
the model's family: the key map to the program's ``ModelConfig``, the
architecture it computes and the counts of model FLOPs. Nothing here
names a cell or a family.

A run drives the system's own entry point,
``Trainer(TrainerConfig(...), model_cfg=cfg, params=...).fit()``:

1. Set-up: weights from the seed in one jitted call, the trainer, and a
   first ``fit`` of the mix's warm steps. Those steps are the ones the
   reference follows, and they give the step time that sizes the window.
2. The window: a second ``fit`` on the same trainer of N + 2 steps. Step
   0 fills the pipeline and the last step drains it; the window runs from
   the end of step 0 to the end of step N, with N the whole steps that
   fill ``--seconds`` at the measured step time.
3. ``peak_bytes_in_use`` is read, the program's state is freed, and the
   plain float32 reference checks the warm steps (``check.py``).

It prints the numbers compared, each beside its limit, as the last lines
of standard error, and one JSON object as the last line of standard
output. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
GIB = 2 ** 30
TRACE_DIR = ROOT / ".bench_trace"


def _read(path: Path):
    return json.loads(Path(path).read_text())


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(conf: dict):
    """The plain reference module a configuration file names."""
    return _module(HERE / "references" / f"{conf['reference']}.py")


def model_sizes(conf: dict, ref) -> dict:
    """The sizes a configuration file runs, as the program's ``ModelConfig``
    fields, by the key map of its reference ``ref``: ``SOURCE_KEYS`` maps
    a key of the file's ``config`` to a field, ``FIXED`` (where given)
    holds keys with no field and the one value the reference computes. A
    key in neither, a value other than the fixed one, or a null whose
    field ``ref``'s ``defaults`` does not give is an error; the file's
    ``program`` entries come last."""
    src = conf["config"]
    fixed = getattr(ref, "FIXED", {})
    name = repr(conf["reference"])
    unknown = sorted(set(src) - set(ref.SOURCE_KEYS) - set(fixed))
    if unknown:
        raise KeyError(f"reference {name} maps no key {unknown}")
    other = [k for k in fixed if k in src and src[k] != fixed[k]]
    if other:
        raise ValueError(f"reference {name} computes "
                         + ", ".join(f"{k} {fixed[k]!r}, not {src[k]!r}"
                                     for k in other))
    m = {ref.SOURCE_KEYS[k]: v for k, v in src.items()
         if k in ref.SOURCE_KEYS and v is not None}
    given = ref.defaults(m)
    unset = sorted(k for k, v in src.items() if v is None and k not in fixed
                   and ref.SOURCE_KEYS[k] not in given)
    if unset:
        raise ValueError(f"reference {name} gives no value for the null "
                         f"{unset}")
    return {**given, **m, **conf.get("program", {})}


def model_config(conf: dict, ref):
    """The program's ``ModelConfig`` of the file's ``arch`` at the file's
    sizes; an error unless it is the ``ARCH`` that ``ref`` computes."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(conf["arch"]),
                              **model_sizes(conf, ref))
    got = {k: getattr(cfg, k) for k in ref.ARCH}
    if got != ref.ARCH:
        raise ValueError(f"{conf['arch']} at these sizes is {got}; "
                         f"reference {conf['reference']!r} computes "
                         f"{ref.ARCH}")
    return cfg


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @functools.cached_property
    def reference(self):
        return reference(self.config)

    @property
    def model(self) -> dict:
        return model_sizes(self.config, self.reference)

    @property
    def model_config(self):
        return model_config(self.config, self.reference)


def load_cell(workload: str, bench_file: Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    """Everything a run of ``workload`` needs, found by name."""
    bench = _read(bench_file)
    w = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if w is None:
        raise KeyError(f"no workload {workload!r} in {bench_file}")
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(
        name=workload, chips=w["chips"], config=_read(ROOT / conf["file"]),
        traffic=_read(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_read(HERE / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def key_seed(seed: int) -> int:
    """A 31-bit key for JAX and the trainer from any whole-number seed."""
    return int(np.random.default_rng(seed).integers(1, 2 ** 31 - 1))


def trained_length(mask, seq_len: int) -> int:
    """Tokens of one trained row: prompt plus response up to EOS, as the
    response mask marks them, within the trained sequence length."""
    on = np.flatnonzero(np.asarray(mask)[:seq_len])
    return int(on[-1]) + 1 if len(on) else 0


def window_steps(seconds: float, step_s: float, min_steps: int) -> int:
    """Whole steps that fill ``seconds`` at ``step_s`` per step."""
    return max(min_steps, math.ceil(seconds / max(step_s, 1e-9)))


def window_rate(done_times, step_tokens, first: int, last: int):
    """(tokens/s, seconds, tokens) of the steps first+1 .. last, from the
    end of step ``first`` to the end of step ``last``: whole steps only,
    all their work over all their time."""
    seconds = done_times[last] - done_times[first]
    tokens = sum(step_tokens[first + 1:last + 1])
    return tokens / seconds, seconds, tokens


# --------------------------------------------------------------------- #
# instrumentation                                                        #
# --------------------------------------------------------------------- #

@dataclasses.dataclass
class Step:
    t_done: float
    lengths: list
    micro: list       # the micro-batches as consumed (warm steps only)


class Probe:
    """Wraps, on the trainer's own engine objects, the verbs the stage
    runner calls, in ``jax.profiler.TraceAnnotation`` spans named
    ``bench.*``, and records each optimizer step as the step driver
    completes it: its time, its rows' trained lengths and, while
    ``capture`` is set, the rows themselves. The weight publish and swap
    get annotations too, for the duration of ``installed()``. Behaviour
    is unchanged: every wrapper calls the original and returns its
    result."""

    CAPTURED = ("response", "response_mask", "logprob", "advantage",
                "version")

    def __init__(self, trainer, seq_len: int):
        self.trainer, self.seq_len = trainer, seq_len
        self.steps, self._micro = [], []
        self.capture = False
        self.on_step = None

    def reset(self, capture: bool, on_step=None):
        self.steps, self._micro = [], []
        self.capture, self.on_step = capture, on_step

    @contextlib.contextmanager
    def installed(self):
        from jax.profiler import TraceAnnotation

        from repro.core.workflow import weight_sync

        def annotate(name, fn):
            @functools.wraps(fn)
            def wrapper(*a, **kw):
                with TraceAnnotation(name):
                    return fn(*a, **kw)
            return wrapper

        ro, te = self.trainer.rollout_engine, self.trainer.train_engine
        ro.generate_sequences = annotate("bench.generate",
                                         ro.generate_sequences)
        ro.compute_rewards = annotate("bench.reward", ro.compute_rewards)
        te.update_actor = self._update_wrapper(
            annotate("bench.update_actor", te.update_actor))
        saved = (weight_sync.WeightSender.publish,
                 weight_sync.WeightReceiver._swap)
        weight_sync.WeightSender.publish = annotate("bench.weight_publish",
                                                    saved[0])
        weight_sync.WeightReceiver._swap = annotate("bench.weight_swap",
                                                    saved[1])
        try:
            yield self
        finally:
            (weight_sync.WeightSender.publish,
             weight_sync.WeightReceiver._swap) = saved
            for obj, verb in ((ro, "generate_sequences"),
                              (ro, "compute_rewards"), (te, "update_actor")):
                vars(obj).pop(verb, None)

    def _update_wrapper(self, fn):
        @functools.wraps(fn)
        def update_actor(batch, **kw):
            out = fn(batch, **kw)
            lens = [trained_length(m, self.seq_len)
                    for m in batch["response_mask"]]
            rows = ({k: list(batch[k]) for k in self.CAPTURED}
                    if self.capture else None)
            self._micro.append((lens, rows))
            if out:                       # the optimizer stepped
                t = time.monotonic()
                self.steps.append(Step(
                    t, [n for ls, _ in self._micro for n in ls],
                    [r for _, r in self._micro] if self.capture else []))
                self._micro = []
                if self.on_step is not None:
                    self.on_step(len(self.steps) - 1)
            return out
        return update_actor


@functools.cache
def compile_log() -> list:
    """(time, program) of every program JAX compiles or loads from its
    cache in this process from the first call on."""
    import jax
    log = []

    def listen(event, duration, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            log.append((time.monotonic(), fun_name))
    jax.monitoring.register_event_duration_secs_listener(listen)
    return log


def leaf_norms(tree) -> dict:
    """{leaf path: L2 norm} of a pytree, computed on the device."""
    return diff_norms(tree, None)


def diff_norms(a, b) -> dict:
    """{leaf path: L2 norm of a - b} (of ``a`` where ``b`` is None)."""
    import jax
    paths, xs = zip(*jax.tree_util.tree_flatten_with_path(a)[0])
    ys = None if b is None else jax.tree.leaves(b)
    norms = _norms_fn()(list(xs), ys)
    return {jax.tree_util.keystr(p): float(n) for p, n in zip(paths, norms)}


@functools.cache
def _norms_fn():
    import jax
    import jax.numpy as jnp

    def norms(xs, ys):
        if ys is not None:
            xs = [x.astype(jnp.float32) - y.astype(jnp.float32)
                  for x, y in zip(xs, ys)]
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in xs]
    return jax.jit(norms)


# --------------------------------------------------------------------- #
# a run                                                                  #
# --------------------------------------------------------------------- #

@dataclasses.dataclass
class Warm:
    """The trainer after its warm steps, and what the program did in
    them, as the comparison needs it."""
    trainer: object
    probe: Probe
    key: int
    steps: list
    losses: list
    first_grad: dict
    change: dict
    step_s: float


def setup(cell: Cell, seed: int) -> Warm:
    """Weights, the trainer and its warm steps (a first ``fit``)."""
    from repro.api import Trainer, TrainerConfig

    import traffic as traffic_mod
    import weights

    cfg, tr = cell.model_config, cell.traffic
    key = key_seed(seed)
    trainer = Trainer(TrainerConfig(**tr["trainer"], lr=tr["optimizer"]["lr"],
                                    num_steps=tr["warm_steps"], seed=key),
                      model_cfg=cfg, params=weights.make(cfg, key))
    trainer.dataset = traffic_mod.PromptStream(
        seed, cfg.vocab_size, *tr["prompt_len"])
    probe = Probe(trainer, tr["trainer"]["seq_len"])
    b1 = tr["optimizer"]["betas"][0]
    first = {}

    def on_step(i):
        if i == 0:   # after one step from zero, m = (1 - beta1) g
            first.update({k: v / (1.0 - b1) for k, v in leaf_norms(
                trainer.train_engine.state.opt_state["m"]).items()})

    probe.reset(capture=True, on_step=on_step)
    with probe.installed():
        res = trainer.fit()
    steps = probe.steps
    if len(steps) != tr["warm_steps"]:
        raise RuntimeError(f"{len(steps)} warm steps recorded, "
                           f"{tr['warm_steps']} run")
    p0 = weights.make(cfg, key)
    change = diff_norms(trainer.train_engine.params, p0)
    del p0
    losses = [r["loss"] for r in sorted(res.metrics, key=lambda r: r["step"])]
    return Warm(trainer, probe, key, steps, losses, first, change,
                step_s=steps[1].t_done - steps[0].t_done)


@dataclasses.dataclass
class RunRecord:
    """What the per-layer readers read."""
    window: tuple
    n_steps: int
    lengths: list
    spans: list
    trace: dict | None
    model: dict
    peaks: dict
    n_chips: int
    counters: dict    # the program's counters' change over the window
    reference: object   # the cell's reference module: its FLOP counts

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def span_seconds(self, kind: str, instance: str | None = None) -> float:
        a, b = self.window
        return sum(max(0.0, min(e, b) - max(s, a))
                   for inst, k, s, e in self.spans
                   if k == kind and (instance is None or inst == instance))


def program_counters() -> dict:
    """Run totals of the program's counters that per-layer metrics read:
    seconds and count of the weight publishes (device-to-host copy and
    hand-off, on the sender's thread) and swaps (host-to-device load)."""
    from repro.core.obs import get_registry
    hist = get_registry().get("weight_sync_seconds")
    out = {}
    for role in ("publish", "swap"):
        got = hist.summary(role=role) if hist is not None else {
            "sum": 0.0, "count": 0}
        out[f"weight_sync_seconds.{role}"] = got["sum"]
        out[f"weight_sync_count.{role}"] = got["count"]
    return out


def measure(cell: Cell, warm: Warm, seconds: float, trace: bool,
            log=print) -> dict:
    """The window: a second ``fit`` of N + 2 steps on the same trainer."""
    import jax
    from jax.profiler import TraceAnnotation

    import flops
    import trace_reduce

    tr = cell.traffic
    n = window_steps(seconds, warm.step_s, tr["min_window_steps"])
    trainer, probe = warm.trainer, warm.probe
    trainer.tcfg = dataclasses.replace(trainer.tcfg, num_steps=n + 2)
    marks = {}

    def on_step(i):
        if i == 0:
            if trace:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(TRACE_DIR),
                                         profiler_options=opts)
            # made after the trace starts, or the profiler never sees it
            marks["note"] = TraceAnnotation("bench.window")
            marks["note"].__enter__()
            marks["counters_a"] = program_counters()
            marks["a"] = time.monotonic()
        elif i == n:
            marks["b"] = time.monotonic()
            marks["counters_b"] = program_counters()
            marks.pop("note").__exit__(None, None, None)
            if trace:
                jax.profiler.stop_trace()

    probe.reset(capture=False, on_step=on_step)
    compiles = compile_log()
    with probe.installed():
        res = trainer.fit()
    steps = probe.steps
    inside = [name for t, name in compiles if marks["a"] <= t <= marks["b"]]
    log(f"programs compiled or loaded inside the window: {len(inside)} "
        f"{sorted(set(inside))}")
    if len(steps) != n + 2:
        raise RuntimeError(f"{len(steps)} steps recorded, {n + 2} run")
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    done = [s.t_done for s in steps]
    rate, span, tokens = window_rate(done, [sum(s.lengths) for s in steps],
                                     0, n)
    rows = sum(len(s.lengths) for s in steps[1:n + 1])
    log(f"window: steps 1..{n} of {n + 2}, {span:.3f} s, {tokens} tokens, "
        f"{rows} rows; warm step {warm.step_s:.3f} s")
    out = {
        "attempted": rows, "failed": 0,
        "e2e": {"train_tokens_per_s": rate,
                "peak_hbm_gib": stats.get("peak_bytes_in_use", math.nan)
                / GIB,
                "setup_s": done[0] - T_START},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": stats.get("peak_bytes_in_use")},
    }
    if trace:
        summary = trace_reduce.reduce(trace_reduce.find_xplane(TRACE_DIR))
        out["device"].update(busy_s=summary["busy_s"],
                             window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
        t0 = res.log.t0
        record = RunRecord(
            window=(marks["a"], marks["b"]), n_steps=n,
            lengths=[x for s in steps[1:n + 1] for x in s.lengths],
            spans=[(e.instance, e.kind, t0 + e.start, t0 + e.end)
                   for e in res.log.events()],
            trace=summary, model=cell.model,
            peaks=flops.peaks(dev.device_kind), n_chips=cell.chips,
            counters={k: v - marks["counters_a"][k]
                      for k, v in marks["counters_b"].items()},
            reference=cell.reference)
        out["per_layer"] = {}
        for metric in cell.per_layer:
            got = _module(HERE / "metrics" / f"{metric['name']}.py").read(
                record)
            if got is None:
                continue
            got = got if isinstance(got, dict) else {"value": got}
            out["per_layer"][metric["name"]] = {**got, "unit": metric["unit"]}
    return out


def free(warm: Warm) -> None:
    """Drop every reference to the program's state and collect it."""
    warm.trainer = warm.probe = None
    gc.collect()


# --------------------------------------------------------------------- #
# the comparison                                                         #
# --------------------------------------------------------------------- #

def pack(rows: list, seq_len: int) -> dict:
    """Rows as the update trains them: padded or cut to ``seq_len``."""
    n = len(rows["response"])
    out = {k: np.zeros((n, seq_len), dt) for k, dt in (
        ("tokens", np.int32), ("response_mask", np.float32),
        ("old_logprob", np.float32))}
    for i in range(n):
        for k, src in (("tokens", "response"), ("response_mask",
                                                "response_mask"),
                       ("old_logprob", "logprob")):
            r = np.asarray(rows[src][i])[:seq_len]
            out[k][i, :len(r)] = r
    out["advantage"] = np.asarray(rows["advantage"], np.float32)
    return out


def sample_rows(warm_steps: list, seed: int, k: int) -> list:
    """A seeded sample of about ``k`` rows of the warm steps, as
    (tokens, recorded log-probabilities, response mask, weight version):
    an even share of every weight version the rows were generated with,
    each share holding that version's row of most response tokens."""
    rows = [(np.asarray(mb["response"][i]), np.asarray(mb["logprob"][i]),
             np.asarray(mb["response_mask"][i]), int(mb["version"][i]))
            for s in warm_steps for mb in s.micro
            for i in range(len(mb["response"]))]
    versions = sorted({r[3] for r in rows})
    if versions[-1] == 0:
        raise RuntimeError("no row of the warm steps was generated after a "
                           "weight swap")
    per = math.ceil(k / len(versions))
    rng = np.random.default_rng([seed, 7])
    out = []
    for v in versions:
        mine = [i for i, r in enumerate(rows) if r[3] == v]
        longest = max(mine, key=lambda i: rows[i][2].sum())
        rest = [i for i in mine if i != longest]
        out += [rows[i] for i in [longest, *rng.permutation(rest)[:per - 1]]]
    return out


def reference_side(cell: Cell, warm_steps: list, key: int, sample: list,
                   quant=None, drop_half: bool = False,
                   rows_per_pass: int = 0, lag: int = 0) -> dict:
    """What the plain reference (or, with ``quant``, the control) gives
    for the warm steps and the sampled rows: each row's log-probabilities
    under the reference's parameters of the row's weight version (with
    ``lag``, of ``lag`` versions before it: weights swapped in late)."""
    import jax

    import weights

    ref = cell.reference
    m, tr = cell.model, cell.traffic
    S = tr["trainer"]["seq_len"]
    steps = []
    for s in warm_steps:
        micro = [pack(mb, S) for mb in s.micro]
        if drop_half:
            micro = [{k: v[: max(1, len(v) // 2)] for k, v in mb.items()}
                     for mb in micro]
        steps.append(micro)
    lp_fn = jax.jit(functools.partial(ref.token_logprobs, m=m, quant=quant))
    lps = [None] * len(sample)

    def at_version(v, params):
        for i, row in enumerate(sample):
            if max(row[3] - lag, 0) == v:
                lps[i] = reference_lps(lp_fn, params, [row])[0]

    with jax.default_matmul_precision("highest"):
        p0 = weights.make(cell.model_config, key)
        losses, grad, p_end = ref.follow(p0, m, steps, tr["optimizer"],
                                         tr["clip_eps"], quant=quant,
                                         rows_per_pass=rows_per_pass,
                                         at_version=at_version)
        change = diff_norms(p_end, p0)
        del p_end
    if any(lp is None for lp in lps):
        raise RuntimeError("a sampled row's weight version is past the "
                           "followed steps")
    return {"losses": losses, "grad": grad, "change": change, "lps": lps}


def reference_lps(lp_fn, params, rows: list) -> list:
    """The reference's log-probability of every token of each row."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return [np.asarray(lp_fn(params, tokens=jnp.asarray(r[0][None])))[0]
                for r in rows]


def adv_scales(warm_steps: list, seq_len: int) -> list:
    """Per step, the mean over its micro-batches of the masked mean
    |advantage|: the scale of that step's loss."""
    out = []
    for s in warm_steps:
        per = []
        for mb in s.micro:
            p = pack(mb, seq_len)
            mask = p["response_mask"][:, 1:]
            per.append(float((np.abs(p["advantage"])[:, None] * mask).sum()
                             / max(mask.sum(), 1.0)))
        out.append(float(np.mean(per)))
    return out


def readings(prog: dict, ref: dict, scales: list, sample: list) -> dict:
    import check
    quiet = check.quiet_leaves(ref["grad"])
    gaps = {}
    for (_, lp_rec, mask, v), lp_ref in zip(prog["sample"], ref["lps"]):
        gaps.setdefault(v, []).append(np.abs(lp_rec - lp_ref)[mask > 0])
    gaps = {v: np.concatenate(g) for v, g in gaps.items()}
    return {"loss_gap": check.loss_gap(prog["losses"], ref["losses"],
                                       scales),
            "grad_gap": check.leaf_gap(prog["grad"], ref["grad"], quiet),
            "update_gap": check.leaf_gap(prog["change"], ref["change"],
                                         quiet),
            "rollout_lp_gap": max(float(g.max()) for g in gaps.values()),
            "rollout_lp_mean_gap": max(float(g.mean())
                                       for g in gaps.values())}


def program_side(warm: Warm, sample: list) -> dict:
    return {"losses": warm.losses, "grad": warm.first_grad,
            "change": warm.change, "sample": sample}


def compare(cell: Cell, warm: Warm, seed: int, log=print) -> dict:
    import check
    S = cell.traffic["trainer"]["seq_len"]
    sample = sample_rows(warm.steps, seed, cell.traffic["check_rows"])
    ref = reference_side(cell, warm.steps, warm.key, sample)
    log(f"leaves left out as quiet: {sorted(check.quiet_leaves(ref['grad']))}")
    return readings(program_side(warm, sample), ref,
                    adv_scales(warm.steps, S), sample)


# --------------------------------------------------------------------- #
# entry                                                                  #
# --------------------------------------------------------------------- #

def run(cell: Cell, seed: int, seconds: float, trace: bool, log=print):
    """One run of ``cell``. Returns the result line's object and one line
    per number compared, with its limit."""
    import check

    import jax

    compile_log()
    warm = setup(cell, seed)
    # where in the run the process's peak is reached: set-up or the window
    log("peak_bytes_in_use after set-up: {}".format(
        (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")))
    out = measure(cell, warm, seconds, trace, log=log)
    free(warm)
    log("setup_s {setup_s:.3f}, peak_hbm_gib {peak_hbm_gib:.4f}, "
        "train_tokens_per_s {train_tokens_per_s:.1f}".format(**out["e2e"]))
    t_ref = time.monotonic()
    got = compare(cell, warm, seed, log=log)
    log(f"reference: {time.monotonic() - t_ref:.3f} s; readings "
        + json.dumps(got))
    correct, lines = check.judge(got, cell.limits)
    names = (cell.per_layer if trace else cell.end_to_end)
    source = out["per_layer"] if trace else {
        k: {"value": v} for k, v in out["e2e"].items()}
    metrics = {}
    for m in names:
        if m["name"] in source:
            metrics[m["name"]] = {**source[m["name"]], "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": out["device"]}
    if trace:
        result["breakdown"] = out["breakdown"]
    result["checks"] = {k: {"value": got.get(k, math.nan), "limit": v}
                        for k, v in sorted(cell.limits.items())}
    return result, lines


def use_compile_cache() -> str:
    """JAX's persistent compile cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), for programs of any size."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as program_cache
    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        cell = load_cell(args.workload)
        import repro  # noqa: F401  (the system under test)
    except (OSError, KeyError, ImportError, StopIteration) as e:
        print(f"run_cell: cannot set up {args.workload!r}: {e!r}",
              file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"run_cell: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} device(s) of platform {devs[0].platform!r}",
              file=sys.stderr)
        return 1
    use_compile_cache()
    result, lines = run(cell, args.seed, args.seconds, bool(args.trace),
                        log=lambda s: print(s, file=sys.stderr, flush=True))
    sys.stdout.flush()
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

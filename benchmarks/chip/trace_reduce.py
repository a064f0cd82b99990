"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: the device's busy time in the measured window, its idle
gaps named by the harness annotation open on the host at that moment,
the device ops that took most time (a loop op is not counted beside the
ops of its body), and every custom call (the Pallas kernels among them)
with its duration and HLO text.

The window is the host span the harness records as ``bench.window``; the
device planes are ``/device:TPU:<n>``, whose ``XLA Ops`` line holds one
event per operation run, and ``XLA Modules`` one per program run. Busy
time is the union of the op intervals inside the window, averaged over
the device planes.
"""
from __future__ import annotations

import bisect
import collections
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench."
WINDOW = "bench.window"
TOP = 10


def find_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _short(op_text: str) -> str:
    return op_text.split(" = ", 1)[0].lstrip("%")


def _module(name: str) -> str:
    return name.split("(", 1)[0]


def reduce(path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    notes, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        notes.append((e.start_ns, e.end_ns, e.name))
        elif plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
    windows = [n for n in notes if n[2] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span in {path}, found "
                         f"{len(windows)}")
    w0, w1, _ = windows[0]
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}* plane in {path}")
    notes = sorted(n for n in notes if n[2] != WINDOW)
    op_time = collections.Counter()
    custom, busy, gaps = [], [], []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted((e.start_ns, e.end_ns, _module(e.name))
                      for e in lines[MODULES_LINE].events) \
            if MODULES_LINE in lines else []
        mod_starts = [m[0] for m in mods]
        ops = sorted((max(e.start_ns, w0), min(e.end_ns, w1), e.start_ns,
                      e.end_ns, e.name)
                     for e in (lines[OPS_LINE].events if OPS_LINE in lines
                               else ()))
        ops = [o for o in ops if o[1] > o[0]]
        spans = [(s, t) for s, t, _, _, _ in ops]
        for k, (s, t, start, end, text) in enumerate(ops):
            if k + 1 < len(ops) and ops[k + 1][2] < end:
                continue     # a loop or call op: its body's ops are counted
            i = bisect.bisect_right(mod_starts, start) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= end else "?"
            op_time[f"{mod}:{_short(text)}"] += (t - s) / 1e9
            if "custom-call(" in text:
                custom.append((_short(text), (end - start) / 1e9,
                               text[:400]))
        merged = union(spans)
        busy.append(sum(t - s for s, t in merged) / 1e9)
        edges = [w0] + [x for st in merged for x in st] + [w1]
        for s, t in zip(edges[::2], edges[1::2]):
            if t > s:
                gaps.append(((t - s) / 1e9, (s + t) / 2))
    gaps.sort(key=lambda g: -g[0])
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(busy) / len(busy),
            "n_devices": len(devices),
            "device_ops": [[k, v] for k, v in op_time.most_common(TOP)],
            "idle_gaps": [[_open_note(notes, mid), s]
                          for s, mid in gaps[:TOP]],
            "custom_calls": custom}


def _open_note(notes, t) -> str:
    """The harness annotation open at time ``t`` that started last."""
    best = None
    for s, e, name in notes:
        if s > t:
            break
        if e >= t:
            best = name
    return best or "none"

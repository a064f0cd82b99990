"""The program's own spans on a profiler trace, and the device's idle
time named by them.

The program annotates its work as ``asyncflow.<kind>`` spans on the
host plane, one line per thread, on the device trace's clock (its span
primitive, ``repro.core.obs.span``). ``reduce`` reads them inside the
harness's ``bench.window`` and charges every slice of the window in
which no device op ran to the layer of the program span that started
last among those open at that instant, on any thread (the rule
``trace_reduce`` names idle gaps by); a slice under no program span is
``unattributed``. Layers come from the program's kind table
(``repro.core.workflow.events.KINDS``); a kind it does not list is
charged to ``other``. Device busy time is computed as ``trace_reduce``
computes it, so the layers' idle seconds sum to its window minus its
busy time.

The traced run's trace is the one under ``TRACE_DIR``, where
``run_cell`` writes it; ``xplane`` may be set to read another file.
"""
from __future__ import annotations

import bisect
import functools
import heapq
from pathlib import Path

import trace_reduce

PREFIX = "asyncflow."
LAYERS = ("rollout", "actor update", "weight sync")
UNATTRIBUTED = "unattributed"
TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_trace"
xplane = None      # a trace file to read in place of TRACE_DIR's


def kind_layers():
    """{kind: layer} from the program's kind table, or None where the
    program has none."""
    try:
        from repro.core.workflow.events import KINDS
    except ImportError:
        return None
    return {k: v.layer for k, v in KINDS.items()}


def for_run(run):
    """The reduction of the run's trace, or None: no trace, a program
    without spans, or a trace that is not the run's."""
    if run.trace is None:
        return None
    layers = kind_layers()
    if layers is None:
        return None
    path = xplane or trace_reduce.find_xplane(TRACE_DIR)
    got = reduce(str(path), tuple(sorted(layers.items())))
    if not got["program_spans"] or got["window_s"] != run.trace["window_s"]:
        return None
    return got


def idle_share(run, layer: str):
    """{"value": % of the window the device idled under ``layer``'s
    spans (``unattributed``: under none, or under a kind of no listed
    layer), "largest_gap": [kind it fell under, seconds]} or None."""
    got = for_run(run)
    if got is None:
        return None
    secs = sum(v for k, v in got["idle_by_layer"].items()
               if _charged(k, layer))
    gaps = [g for g in got["idle_gaps"] if _charged(g[1], layer)]
    out = {"value": 100.0 * secs / got["window_s"]}
    if gaps:
        out["largest_gap"] = [gaps[0][0], gaps[0][2]]
    return out


def _charged(to: str, layer: str) -> bool:
    return to not in LAYERS if layer == UNATTRIBUTED else to == layer


@functools.lru_cache(maxsize=2)
def reduce(path: str, layers: tuple = ()) -> dict:
    """``program_spans``: {host line: [[kind, start s, end s, stats]]},
    times from the window's start, clipped to it. ``idle_by_layer``:
    {layer: idle seconds}, averaged over the device planes, and
    ``idle_by_kind`` the same by span kind (None: under no span).
    ``idle_gaps``: the largest device idle gaps as [kind of the span
    open at the gap's middle, its layer, seconds]."""
    from jax.profiler import ProfileData

    layer_of = dict(layers)
    pd = ProfileData.from_file(path)
    window, spans, devices = None, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name == trace_reduce.WINDOW:
                        window = (e.start_ns, e.end_ns)
                    elif e.name.startswith(PREFIX):
                        spans.setdefault(f"{line.name}:{i}", []).append(
                            (e.start_ns, e.end_ns, e.name[len(PREFIX):],
                             dict(e.stats)))
        elif plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            devices.append(plane)
    if window is None:
        raise ValueError(f"no {trace_reduce.WINDOW} span in {path}")
    if not devices:
        raise ValueError(f"no {trace_reduce.DEVICE_PREFIX}* plane in {path}")
    w0, w1 = window
    inside = {line: sorted((max(s, w0), min(e, w1), k, st)
                           for s, e, k, st in evs if min(e, w1) > max(s, w0))
              for line, evs in spans.items()}
    inside = {line: evs for line, evs in inside.items() if evs}
    segments = _segments(
        [(s, e, k) for evs in inside.values() for s, e, k, _ in evs], w0, w1)
    idle, by_kind, gaps = {}, {}, []
    for plane in devices:
        ops = next((ln.events for ln in plane.lines
                    if ln.name == trace_reduce.OPS_LINE), ())
        busy = trace_reduce.union(
            (max(e.start_ns, w0), min(e.end_ns, w1)) for e in ops
            if min(e.end_ns, w1) > max(e.start_ns, w0))
        edges = [w0] + [x for st in busy for x in st] + [w1]
        holes = [(s, t) for s, t in zip(edges[::2], edges[1::2]) if t > s]
        for kind, secs in _charge(holes, segments).items():
            by_kind[kind] = by_kind.get(kind, 0.0) + secs / len(devices)
            lay = UNATTRIBUTED if kind is None else layer_of.get(kind,
                                                                 "other")
            idle[lay] = idle.get(lay, 0.0) + secs / len(devices)
        starts = [seg[0] for seg in segments]
        for s, t in holes:
            j = max(0, bisect.bisect_right(starts, (s + t) / 2) - 1)
            kind = segments[j][2] if segments else None
            gaps.append((kind, (t - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "program_spans": {
            line: [[k, (s - w0) / 1e9, (e - w0) / 1e9, st]
                   for s, e, k, st in evs] for line, evs in inside.items()},
        "idle_by_layer": idle,
        "idle_by_kind": by_kind,
        "idle_gaps": [[k, UNATTRIBUTED if k is None
                       else layer_of.get(k, "other"), s]
                      for k, s in gaps[:trace_reduce.TOP]],
    }


def _segments(spans, w0, w1):
    """[w0, w1] cut at every span's start and end, as disjoint
    (start, end, kind) pieces: ``kind`` is that of the span that started
    last among those open over the piece, None where none is."""
    bounds = sorted({w0, w1, *(s for s, _, _ in spans),
                     *(e for _, e, _ in spans)})
    spans = sorted(spans, key=lambda x: (x[0], -x[1]))  # parents first
    out, heap, k = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(spans) and spans[k][0] <= a:
            s, e, kind = spans[k]
            heapq.heappush(heap, (-s, -k, e, kind))
            k += 1
        while heap and heap[0][2] <= a:      # closed: only the top matters
            heapq.heappop(heap)
        out.append((a, b, heap[0][3] if heap else None))
    return out


def _charge(holes, segments) -> dict:
    """{kind or None: seconds} of the idle ``holes`` over ``segments``."""
    out, j = {}, 0
    for s, t in holes:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        i = j
        while i < len(segments) and segments[i][0] < t:
            a, b, kind = segments[i]
            over = min(b, t) - max(a, s)
            if over > 0:
                out[kind] = out.get(kind, 0.0) + over / 1e9
            i += 1
    return out

"""Record the tiny cell's traced run for the trace readers' tests.

    python benchmarks/chip/tests/record_tiny_trace.py record <out_dir>
        on one TPU: a traced run of the tiny cell (``chip_bench_tiny``)
        through the harness; writes the profiler's ``.xplane.pb`` and
        the run's result line to ``out_dir``.
    python benchmarks/chip/tests/record_tiny_trace.py keep <xplane> <out>
        anywhere TensorFlow's ``xplane_pb2`` imports: writes to ``out``
        only what the readers read, so the reductions give the same
        numbers on it as on the whole recording: the device's ``XLA Ops``
        and ``XLA Modules`` events and the host's ``bench.*`` and
        ``asyncflow.*`` events that overlap ``bench.window``, op names
        cut to 400 characters.
"""
import json
import shutil
import sys
from pathlib import Path

KEEP_HOST = ("bench.", "asyncflow.")
KEEP_DEVICE = ("XLA Ops", "XLA Modules")


def record(out_dir: Path, seed: int = 2 ** 31 + 7) -> None:
    import chip_bench_tiny as tiny
    import run_cell
    import trace_reduce

    res, lines = run_cell.run(tiny.cell(tiny.LIMITS), seed, 0.2, True,
                              log=lambda s: print(s, file=sys.stderr))
    out_dir.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace_reduce.find_xplane(run_cell.TRACE_DIR),
                out_dir / "tiny_spans.full.xplane.pb")
    (out_dir / "tiny_spans.result.json").write_text(
        json.dumps(res, indent=1) + "\n")
    print("\n".join(lines))


def keep(src: Path, dst: Path) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(src.read_bytes())
    host = [p for p in space.planes if p.name.startswith("/host:")]
    window = next(
        (e.offset_ps, e.offset_ps + e.duration_ps, line.timestamp_ns)
        for p in host for line in p.lines for e in line.events
        if p.event_metadata[e.metadata_id].name == "bench.window")
    w0 = window[2] * 1000 + window[0]      # absolute ps
    w1 = window[2] * 1000 + window[1]
    kept = []
    for plane in space.planes:
        is_host = plane.name.startswith("/host:")
        if not (is_host or plane.name.startswith("/device:TPU:")):
            continue
        names = {i: m.name for i, m in plane.event_metadata.items()}
        lines = []
        for line in plane.lines:
            if not is_host and line.name not in KEEP_DEVICE:
                continue
            base = line.timestamp_ns * 1000
            events = [e for e in line.events
                      if base + e.offset_ps < w1
                      and base + e.offset_ps + e.duration_ps > w0
                      and (not is_host
                           or names[e.metadata_id].startswith(KEEP_HOST))]
            if events:
                del line.events[:]
                line.events.extend(events)
                lines.append(line)
        del plane.lines[:]
        plane.lines.extend(lines)
        used = {e.metadata_id for line in lines for e in line.events}
        for i in list(plane.event_metadata):
            if i not in used:
                del plane.event_metadata[i]
            else:
                m = plane.event_metadata[i]
                m.name = m.name[:400]
                m.display_name = m.display_name[:400]
        kept.append(plane)
    del space.planes[:]
    space.planes.extend(kept)
    dst.write_bytes(space.SerializeToString())


if __name__ == "__main__":
    here = Path(__file__).resolve().parent
    sys.path[:0] = [str(here), str(here.parent)]
    if sys.argv[1] == "record":
        record(Path(sys.argv[2]))
    else:
        keep(Path(sys.argv[2]), Path(sys.argv[3]))

"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV on stdout (strictly CSV: errors
and tracebacks go to stderr when recording a trajectory). Mapping:
  ablation            — Table 1 (baseline / +TransferQueue / +Async)
  scaling             — Fig. 10 (32→1024 chips, AsyncFlow vs colocated)
  gantt               — Fig. 11 (bubble fractions per instance)
  stability           — Fig. 12 (async vs sync reward)
  transfer_queue      — §3.5 (concurrency micro-benchmarks)
  stage_graph         — §4.1 (fused vs. staged pipeline bubbles)
  chaos               — fault injection (0/5/15% crash rates: graceful
                        degradation with exactly-once recovery)
  rollout             — §3.3 (fixed-batch vs continuous-batching rollout)
  kernels             — kernel oracle timings + kernel-vs-oracle error
  roofline            — deliverable (g): dry-run roofline summary

Trajectory convention (``--json``)
----------------------------------
``python -m benchmarks.run --json BENCH_<tag>.json [suite ...]`` writes
the machine-readable suite output next to the CSV: every row (name,
us_per_call, derived), the git revision, a UTC timestamp and the host
config, under schema ``asyncflow-bench-trajectory/v1``. One file is
committed per milestone tag (``BENCH_pr6.json``, ...), so
``git log --oneline -- 'BENCH_*.json'`` is the repo's performance
trajectory; CI records ``BENCH_ci.json`` as a build artifact on every
push. Suites that fail are recorded with their traceback under
``suites.<name>.error`` and the process exits nonzero — after the JSON
and all valid CSV rows are flushed.
"""
from __future__ import annotations

import argparse
import json
import platform
import subprocess
import sys
import time
import traceback


def _git_rev() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "HEAD"],
            stderr=subprocess.DEVNULL).decode().strip()
    except Exception:                                # noqa: BLE001
        return "unknown"


def _host_config() -> dict:
    cfg = {"python": platform.python_version(),
           "platform": platform.platform()}
    try:
        import jax
        cfg["jax"] = jax.__version__
        cfg["jax_backend"] = jax.default_backend()
    except Exception:                                # noqa: BLE001
        pass
    return cfg


def main(argv=None) -> None:
    from benchmarks import (ablation, gantt, kernel_bench, rollout_bench,
                            roofline, scaling, stability, stage_graph_bench,
                            transfer_queue_bench)

    suites = [
        ("ablation", ablation.run),
        ("scaling", scaling.run),
        ("gantt", gantt.run),
        ("stability", stability.run),
        ("transfer_queue", transfer_queue_bench.run),
        ("stage_graph", stage_graph_bench.run),
        ("chaos", stage_graph_bench.run_chaos),
        ("rollout", rollout_bench.run),
        ("kernels", kernel_bench.run),
        ("roofline", roofline.run),
    ]
    ap = argparse.ArgumentParser(
        description="AsyncFlow benchmark harness (CSV on stdout)")
    ap.add_argument("--json", dest="json_path", default="", metavar="PATH",
                    help="also record a BENCH_<tag>.json trajectory file")
    ap.add_argument("names", nargs="*",
                    help=f"suites to run (default: all) — "
                         f"{', '.join(n for n, _ in suites)}")
    args = ap.parse_args(argv)
    only = set(args.names)
    unknown = only - {n for n, _ in suites}
    if unknown:
        ap.error(f"unknown suite(s): {sorted(unknown)}")

    t_start = time.time()
    record: dict = {}
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in suites:
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            rows = [dict(name=r["name"], us_per_call=float(r["us_per_call"]),
                         derived=r["derived"]) for r in fn()]
        except Exception:
            failed += 1
            record[name] = {"rows": [], "error": traceback.format_exc(),
                            "elapsed_s": round(time.perf_counter() - t0, 3)}
            # stdout stays strictly CSV under --json: the ERROR row moves
            # to stderr with the traceback; flush first so streams never
            # interleave mid-row
            sys.stdout.flush()
            err_stream = sys.stderr if args.json_path else sys.stdout
            print(f"{name},ERROR,0", file=err_stream)
            err_stream.flush()
            traceback.print_exc(file=sys.stderr)
            sys.stderr.flush()
            continue
        for row in rows:
            print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
        record[name] = {"rows": rows, "error": None,
                        "elapsed_s": round(time.perf_counter() - t0, 3)}

    if args.json_path:
        doc = {
            "schema": "asyncflow-bench-trajectory/v1",
            "git_rev": _git_rev(),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                       time.gmtime(t_start)),
            "elapsed_s": round(time.time() - t_start, 3),
            "config": _host_config(),
            "suites": record,
        }
        with open(args.json_path, "w") as fh:
            json.dump(doc, fh, indent=2, default=str)
            fh.write("\n")
    # exit nonzero only after every valid row and the JSON are flushed
    sys.stdout.flush()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()

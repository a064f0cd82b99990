"""Readings that the limits of a cell are set from, on the chip.

    python3 benchmarks/chip/control.py --workload <name> \
        --seeds <n> [<n> ...] [--faults <k>]

For every seed it builds the cell's trainer and runs its warm steps, as
a benchmark run does, and reads the program against the float32
reference: the lower readings. For the first ``k`` seeds it also reads,
against the same reference:

* the control: the reference computed with float8 (e4m3) matrix
  multiplications in the program's place;
* half of each micro-batch left out, the mean taken over the rest (the
  reference in the program's place);
* one response token of each sampled row altered where it is produced,
  its recorded behaviour log-probability kept;
* weights swapped in one version late: each sampled row generated after
  a swap reads its log-probabilities under the weights of the version
  before the one it is labelled with (the reference in the program's
  place).

A step that returns its state unchanged reads 1 on ``update_gap`` by the
measure's definition and needs no run. The benchmark's own runs never
run this. It prints one JSON line per seed and kind, and a summary of
the largest and smallest readings of each.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run_cell as rc


def fault_sides(cell, warm, seed):
    """(kind, readings) of the control and the planted faults."""
    import jax.numpy as jnp

    S = cell.traffic["trainer"]["seq_len"]
    sample = rc.sample_rows(warm.steps, seed, cell.traffic["check_rows"])
    scales = rc.adv_scales(warm.steps, S)
    ref = rc.reference_side(cell, warm.steps, warm.key, sample)
    yield "program", rc.readings(rc.program_side(warm, sample), ref, scales,
                                 sample)

    def as_program(side, rows):
        return {"losses": side["losses"], "grad": side["grad"],
                "change": side["change"],
                "sample": [(t, lp, mask, v) for (t, _, mask, v), lp
                           in zip(rows, side["lps"])]}

    ctl = rc.reference_side(cell, warm.steps, warm.key, sample,
                            quant=jnp.float8_e4m3fn, rows_per_pass=1)
    yield "control_fp8", rc.readings(as_program(ctl, sample), ref, scales,
                                     sample)
    half = rc.reference_side(cell, warm.steps, warm.key, sample,
                             drop_half=True)
    yield "half_batch", rc.readings(as_program(half, sample), ref, scales,
                                    sample)
    late = rc.reference_side(cell, warm.steps, warm.key, sample, lag=1)
    yield "stale_swap", rc.readings(as_program(late, sample), ref, scales,
                                    sample)
    vocab = cell.model["vocab_size"]
    altered = []
    for t, lp, mask, v in sample:
        t = t.copy()
        pos = int(np.flatnonzero(mask)[0])
        t[pos] = (t[pos] + 1) % vocab
        altered.append((t, lp, mask, v))
    alt = rc.reference_side(cell, warm.steps, warm.key, altered)
    yield "token_altered", rc.readings(rc.program_side(warm, altered), alt,
                                       scales, altered)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=3,
                    help="seeds (the first ones) that also read the "
                         "control and the faults")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(rc.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    rc.use_compile_cache()
    cell = rc.load_cell(args.workload)
    table = {}
    for i, seed in enumerate(args.seeds):
        t0 = time.monotonic()
        warm = rc.setup(cell, seed)
        rc.free(warm)
        for kind, got in fault_sides(cell, warm, seed):
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "kind": kind, **got}), flush=True)
            for k, v in got.items():
                table.setdefault((kind, k), []).append(v)
            if i >= args.faults:
                break
        print(f"seed {seed}: {time.monotonic() - t0:.1f} s", file=sys.stderr,
              flush=True)
    for (kind, k), vs in sorted(table.items()):
        print(f"{kind:14s} {k:15s} n={len(vs):2d} min {min(vs):.4g} "
              f"max {max(vs):.4g}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

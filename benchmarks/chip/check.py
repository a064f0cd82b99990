"""The comparison that decides ``correct``: the program's first optimizer
steps and its behaviour log-probabilities against the plain float32
reference, each number beside its limit (``limits/<workload>.json``).

Numbers compared (all are shares; lower is closer):

* ``loss_gap``: the largest, over the followed steps, of
  |program loss - reference loss| over the step's mean |advantage| (the
  loss is linear in the advantages, so that is its scale).
* ``grad_gap``: the first gradient as the optimizer got it (averaged over
  micro-batches and clipped), read from the program's first moment after
  one step (m = (1 - beta1) g). For each leaf, |program norm - reference
  norm| over the larger of the reference's norm of that leaf and of the
  median leaf; the worst leaf.
* ``update_gap``: the same for each leaf's change over the followed steps.
* ``rollout_lp_gap``: the largest |behaviour log-probability the sampler
  recorded - reference log-probability| over the response tokens of a
  seeded sample of the warm steps' rows, each read under the reference's
  weights of the version the row is labelled with (rows of every
  version, so the weight swap is covered).
* ``rollout_lp_mean_gap``: the mean of that gap over one version's
  sampled response tokens; the worst version.

Leaves whose reference first gradient is under a thousandth of the median
leaf's move under Adam by round-off alone; they are left out of
``grad_gap`` and ``update_gap`` by that rule, not by name.
"""
from __future__ import annotations

import math

import numpy as np

QUIET_LEAF = 1e-3


def leaf_gap(prog: dict, ref: dict, quiet: set) -> float:
    """Worst-leaf gap of per-leaf norms (dicts keyed by leaf path). Where
    the reference moved nothing there is nothing to compare: NaN, which
    no limit passes."""
    scale = float(np.median(list(ref.values())))
    if not scale > 0:
        return math.nan
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], scale, 1e-30)
            for k in ref if k not in quiet]
    return max(gaps) if gaps else math.nan


def quiet_leaves(ref_grad_norms: dict) -> set:
    med = float(np.median(list(ref_grad_norms.values())))
    return {k for k, v in ref_grad_norms.items() if v < QUIET_LEAF * med}


def loss_gap(prog_losses, ref_losses, adv_scales) -> float:
    return max(abs(p - r) / max(s, 1e-30)
               for p, r, s in zip(prog_losses, ref_losses, adv_scales))


def judge(readings: dict, limits: dict):
    """(correct, lines): every number finite and within its limit, and
    one line per number with its limit."""
    lines, ok = [], True
    for name in sorted(limits):
        v = readings.get(name, math.nan)
        good = math.isfinite(v) and v <= limits[name]
        ok &= good
        lines.append(f"{name} {v:.6g} limit {limits[name]:.6g}"
                     + ("" if good else " FAILED"))
    return ok, lines

"""The comparison that decides ``correct`` fails the control and each
fault a one-chip training cell can have, at a tiny width on the CPU.

Each fault is planted under the timed path (the system's own modules)
for one run, which then goes through set-up, the window and the check
as any run does, past the harness's look for a chip."""
import jax.numpy as jnp
import pytest

import chip_bench_tiny as tiny  # puts the harness and the system on the path
import check  # noqa: E402
import control  # noqa: E402
import run_cell  # noqa: E402
from repro.core.workflow import weight_sync  # noqa: E402
from repro.engines import train_engine  # noqa: E402
from repro.rl import sampling  # noqa: E402


def _state_unchanged(mp):
    mp.setattr(train_engine, "_apply",
               lambda state, grads, n, opt: (state, jnp.zeros(())))


def _half_batch(mp):
    pack = train_engine.pack_rows

    def half(batch, seq_len):
        k = max(1, len(batch["response"]) // 2)
        return pack({c: v[:k] for c, v in batch.items()}, seq_len)
    mp.setattr(train_engine, "pack_rows", half)


def _token_altered(mp):
    gen = sampling._generate_jit

    def altered(*a, **kw):
        toks, lps, mask = gen(*a, **kw)
        vocab = a[1].vocab_size
        return toks.at[:, -1].set((toks[:, -1] + 1) % vocab), lps, mask
    mp.setattr(sampling, "_generate_jit", altered)


def _stale_swap(mp):
    swap = weight_sync.WeightReceiver._swap

    def stale(self, vw):
        live = self.params
        swap(self, vw)
        self.params = live      # the new version's label on the old weights
    mp.setattr(weight_sync.WeightReceiver, "_swap", stale)


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch,
                                   _token_altered, _stale_swap],
                         ids=lambda f: f.__name__.strip("_"))
def test_fault_under_the_timed_path_is_not_correct(plant, monkeypatch):
    plant(monkeypatch)
    res, lines = run_cell.run(tiny.cell(tiny.LIMITS), 424242, 0.2, False,
                              log=lambda s: None)
    assert res["correct"] is False, lines


def test_control_in_the_programs_place_is_not_correct():
    cell = tiny.cell(tiny.LIMITS)
    warm = run_cell.setup(cell, 515151)
    run_cell.free(warm)
    got = dict(control.fault_sides(cell, warm, 515151))
    assert check.judge(got["program"], cell.limits)[0] is True
    assert check.judge(got["control_fp8"], cell.limits)[0] is False

"""Model FLOPs of the window's steps over the window and the chip's peak.

Each trained row of length L (prompt plus response up to EOS) counts the
rollout's forward, 2 P L plus causal attention, and the update's forward
and backward, 6 P L plus three times that attention (``flops.py``, with
P and the attention counted by the cell's reference module). In a
steady step the rollout generates one step's rows while the trainer
trains the previous step's, so the window's trained rows stand for both.
"""
import flops


def read(run):
    ref, m = run.reference, run.model
    work = sum(flops.rollout_flops(ref, m, n) + flops.train_flops(ref, m, n)
               for n in run.lengths)
    return 100.0 * work / (run.window_s * run.peaks["bf16_flops_per_s"]
                           * run.n_chips)

"""Model FLOPs of the window's steps over the window and the chip's peak.

Each trained row of length L (prompt plus response up to EOS) counts the
rollout's forward, 2 P L plus causal attention, and the update's forward
and backward, 6 P L plus three times that attention (``flops.py``). In a
steady step the rollout generates one step's rows while the trainer
trains the previous step's, so the window's trained rows stand for both.
"""
import flops


def read(run):
    work = sum(flops.rollout_flops(run.model, n) + flops.train_flops(
        run.model, n) for n in run.lengths)
    return 100.0 * work / (run.window_s * run.peaks["bf16_flops_per_s"]
                           * run.n_chips)

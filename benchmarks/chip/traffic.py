"""The seeded prompt stream every cell feeds the trainer.

A mix file (``traffic/<mix>.json``) gives the prompt-length range. Each
step's batch has the same multiset of lengths, spread evenly over the
range, in an order drawn from the seed; token ids are drawn uniformly
from the model's vocabulary past the tokenizer's specials, after a BOS,
and each prompt carries an integer answer for the rule-based reward. So
every seed gives the same sizes and a different order and content.
"""
from __future__ import annotations

import numpy as np

BOS = 1          # the tokenizer's begin-of-sequence id
FIRST_ID = 3     # ids below are the tokenizer's specials (pad, bos, eos)
ANSWER_RANGE = 100


def lengths(n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` prompt lengths spread evenly over [lo, hi]."""
    return lo + (np.arange(n) * (hi - lo + 1)) // max(n, 1)


class PromptStream:
    """Stands in for the trainer's dataset: ``prompts_for_step`` returns
    the next ``n`` prompts of the stream, a dict each with ``tokens``,
    ``text`` and ``answer`` as the program's own dataset gives them. The
    k-th call draws from (seed, k), so a trainer that asks for the same
    step twice, as a second ``fit`` does, gets new prompts."""

    def __init__(self, seed: int, vocab: int, len_lo: int, len_hi: int):
        self.seed, self.vocab = seed, vocab
        self.len_lo, self.len_hi = len_lo, len_hi
        self.calls = 0

    def prompts_for_step(self, step: int, n: int) -> list:
        rng = np.random.default_rng([self.seed, self.calls])
        self.calls += 1
        out = []
        for ln in rng.permutation(lengths(n, self.len_lo, self.len_hi)):
            toks = rng.integers(FIRST_ID, self.vocab, int(ln), dtype=np.int32)
            toks[0] = BOS
            out.append({"tokens": toks, "text": "",
                        "answer": int(rng.integers(ANSWER_RANGE))})
        return out

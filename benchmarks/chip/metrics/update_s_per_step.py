"""Seconds of the step driver's ``update`` spans (one per micro-batch:
pack, gradient, and at a step's end the optimizer) in the window per
step."""


def read(run):
    return run.span_seconds("update", "train-0") / run.n_steps

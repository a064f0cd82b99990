"""Seconds of rollout ``generate`` spans in the window per step."""


def read(run):
    return run.span_seconds("generate") / run.n_steps

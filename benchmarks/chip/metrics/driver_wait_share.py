"""Share of the window the step driver (``train-0``) spent in ``wait``
spans: blocked on the TransferQueue for rows the rollout has not made."""


def read(run):
    return 100.0 * run.span_seconds("wait", "train-0") / run.window_s

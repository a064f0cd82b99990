"""Mean seconds a row of the window's steps waited in the TransferQueue
between ready (its last column written) and handed to the step driver:
the program's ``tq_row_wait_seconds`` histogram for the driver's task.
The harness's second ``fit`` runs one step after the window and the
driver takes its rows in step order, so the window's rows are the
driver's last observations but one step's."""

DRIVER_TASK = "actor_update"    # the step driver's task in GRPO and PPO


def read(run):
    from repro.core.obs import get_registry
    hist = get_registry().get("tq_row_wait_seconds")
    if hist is None or not hasattr(hist, "recent") or not run.lengths:
        return None
    rows = len(run.lengths)
    per_step = rows // run.n_steps
    waits = hist.recent(rows + per_step, task=DRIVER_TASK)[:rows]
    if len(waits) < rows:
        return None
    return sum(waits) / rows

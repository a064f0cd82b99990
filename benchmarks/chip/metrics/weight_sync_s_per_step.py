"""Seconds of weight hand-off per step in the window, from the program's
``weight_sync_seconds`` histogram (its change between the window's
marks): every publish, the device-to-host copy of the trained weights and
their hand-off to the channel on the sender's thread, plus every swap,
the host-to-device load of the rollout copy. The publish overlaps the
next step; this is the layer's cost, not its share of the critical
path."""


def read(run):
    c = run.counters
    if c.get("weight_sync_count.publish", 0) == 0:
        return None
    return {"value": (c["weight_sync_seconds.publish"]
                      + c["weight_sync_seconds.swap"]) / run.n_steps,
            "publish_s": c["weight_sync_seconds.publish"] / run.n_steps,
            "swap_s": c["weight_sync_seconds.swap"] / run.n_steps}

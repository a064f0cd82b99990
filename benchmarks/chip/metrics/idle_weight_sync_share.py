"""Share of the traced window in which the device idled under a weight
sync span of the program (``publish.wait``, ``publish.copy``,
``weight_swap``, ``staleness_wait``, the driver's ``weight_sync``)."""
import program_trace


def read(run):
    return program_trace.idle_share(run, "weight sync")

"""Share of the traced window in which the device idled under a rollout
span of the program (``generate`` and its parts, the stages that finish
its rows, and the step driver's ``wait`` for them)."""
import program_trace


def read(run):
    return program_trace.idle_share(run, "rollout")

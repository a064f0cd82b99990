"""Share of the traced window in which the device idled under no span
of the program (or under a kind its table charges to no layer): the
idle time the program's spans do not name."""
import program_trace


def read(run):
    return program_trace.idle_share(run, program_trace.UNATTRIBUTED)

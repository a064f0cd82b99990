"""Share of the traced window in which the device idled under an actor
update span of the program (``update`` and its parts: pack, grad,
accumulate, optimizer)."""
import program_trace


def read(run):
    return program_trace.idle_share(run, "actor update")

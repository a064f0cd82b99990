"""Programs compiled or loaded from the compile cache inside the window:
the program's compile listener, which keeps ``jit_compiles_total``,
records each one into the run's event log as a ``compile`` event ending
when the program is ready; these are counted by that end."""


def read(run):
    try:
        import repro.core.obs.spans  # noqa: F401  (the listener)
    except ImportError:
        return None
    a, b = run.window
    return sum(1 for _, kind, _, end in run.spans
               if kind == "compile" and a <= end <= b)

"""compiles_in_window — programs built inside the measured window."""


def read(run, spec):
    return float(run.window_compiles)

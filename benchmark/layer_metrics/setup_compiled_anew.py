"""setup_compiled_anew — programs of the program's compiled anew before
the window, from the start-up record (benchmark/setup_phases.py)."""

import setup_phases


def read(run, spec):
    found = setup_phases.table(run)
    return None if found is None else float(found["anew"])

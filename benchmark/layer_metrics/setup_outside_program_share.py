"""setup_outside_program_share — one of the five shares of `setup_s` that
benchmark/setup_phases.py reads from the program's start-up record
(its one table a run prints them all)."""

import setup_phases


def read(run, spec):
    return setup_phases.share(run, "outside_program")

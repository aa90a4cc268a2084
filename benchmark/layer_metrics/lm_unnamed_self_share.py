"""lm_unnamed_self_share — the part of the solver programs' device time
that no scope names, by self time and after adoption
(benchmark/self_time.py, whose one table a run this prints)."""

import self_time


def read(run, spec):
    found = self_time.table(run)
    if found is None:
        return None
    return 100.0 * sum(found["unnamed_s"].values()) / found["programs_s"]

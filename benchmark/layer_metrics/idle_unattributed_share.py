"""idle_unattributed_share — device idle time under no span of the
program's (benchmark/span_reduce.py)."""

import span_reduce


def read(run, spec):
    idle = span_reduce.idle_summary(run)
    if idle is None or idle["window_s"] <= 0:
        return None
    return 100.0 * idle["by_span_s"].get(span_reduce.NO_SPAN, 0.0) \
        / idle["window_s"]

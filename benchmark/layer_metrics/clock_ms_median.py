"""clock_ms_median — the body of the slice times whose tail is
clock_ms_tail_p95, from the harness's own samples."""

import statistics


def read(run, spec):
    if len(run.call_times) < 2:
        return None               # one call a window: one sample, no body
    return statistics.median(1e3 * s / run.call_clocks
                             for s in run.call_times)

"""clock_ms_tail_p95 — the tail of the window's slice times, per clock
(the accepted benchmark's end-to-end clock_ms_p95, by its rule)."""

import math


def read(run, spec):
    if len(run.call_times) < 2:
        return None               # one call a window: one sample, no tail
    per_clock_ms = sorted(1e3 * s / run.call_clocks for s in run.call_times)
    return per_clock_ms[min(len(per_clock_ms) - 1,
                            math.ceil(0.95 * len(per_clock_ms)) - 1)]

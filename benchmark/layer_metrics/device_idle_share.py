"""device_idle_share — from the device trace alone."""


def read(run, spec):
    t = run.trace_summary
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

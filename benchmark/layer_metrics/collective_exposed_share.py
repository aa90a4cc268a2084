"""collective_exposed_share — collectives nothing hides, from the trace."""


def read(run, spec):
    t = run.trace_summary
    if not t or t["chips"] < 2 or t["collective_s"] <= 0:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]

"""lm_update_roofline_share — the least the chip could take for one
worker update of the language-model family against the device's own
time an update, read as the period of an instruction that runs once an
update (a program of this cell outlasts the traced seconds)."""

import bisect
import re

import peaks
import span_reduce


def marker_period(ops, runs, tables, scope):
    """(seconds from one start to the next, starts seen) of the
    instruction under `scope` that started most often inside `runs`
    [(start, end)], or None.  `ops`: [(event name, start, end)];
    `tables`: the candidates for {instruction: op_name} of the
    programs' HLO, of which the one naming the most traced operations
    counts."""
    starts_of_runs = [s for s, _ in runs]
    seen = {}
    for name, s, _ in ops:
        i = bisect.bisect_right(starts_of_runs, s) - 1
        if i >= 0 and s < runs[i][1]:
            seen.setdefault(span_reduce.instruction_name(name),
                            []).append(s)
    table = max(tables, key=lambda t: len(seen.keys() & t.keys()),
                default={})
    marked = [sorted(starts) for inst, starts in seen.items()
              if scope in table.get(inst, "")]
    most = max(marked, key=len, default=[])
    if len(most) < 3:
        return None
    return (most[-1] - most[0]) / (len(most) - 1), len(most)


def read(run, spec):
    last = getattr(run.app, "last_run", None) or {}
    counters = last.get("counters")
    data = span_reduce.trace_data(run)
    costs = run.family.costs
    if not counters or data is None or not hasattr(costs, "updates_counted"):
        return None
    patterns = spec["solver_module_patterns"]
    wanted = [re.compile(p) for p in patterns]
    ops, modules = span_reduce.device_op_events(data, run.trace_cfg)
    runs = sorted((s, e) for m, s, e in modules
                  if any(p.search(m) for p in wanted))
    tables = [t for ts in span_reduce.executables_op_names(patterns).values()
              for t in ts]
    found = marker_period(ops, runs, tables, spec["marker_scope"])
    updates = costs.updates_counted(costs.model_file(run.cfg), run.cfg,
                                    counters)
    if found is None or not updates:
        return None
    period, starts = found
    host = last["seconds"] / updates
    flops, bytes_ = costs.update(run.cfg)
    kind = run.devices[0].device_kind
    least, bound = peaks.least_seconds(flops, bytes_, kind)
    print(f"[bench] lm_update_roofline_share: {flops:.4g} FLOP and "
          f"{bytes_:.4g} bytes per update, least {least * 1e3:.4f} ms "
          f"({bound}-bound) on {kind}; the marker under "
          f"{spec['marker_scope']} started {starts} times in the trace, "
          f"{period * 1e3:.4f} ms apart on the device's clock; the "
          f"window's call took {host * 1e3:.4f} ms an update on the "
          f"host's", flush=True)
    if abs(period - host) > spec["period_host_tolerance"] * host:
        return None
    return 100.0 * least / period

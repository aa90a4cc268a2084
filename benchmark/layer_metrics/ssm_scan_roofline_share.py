"""ssm_scan_roofline_share — the least the chip could take for the
state-space recurrence of the scan chunks the run counted, against the
device time under `kps.ssm.scan`."""

import peaks
import span_reduce


def read(run, spec):
    last = getattr(run.app, "last_run", None) or {}
    counters = last.get("counters") or {}
    data = span_reduce.trace_data(run)
    costs = run.family.costs
    if (not counters.get(spec["counter"]) or data is None
            or not hasattr(costs, "ssm_scan")):
        return None
    patterns = spec["solver_module_patterns"]
    found = span_reduce.seconds_by_scope(
        data, run.trace_cfg, spec["scopes"], patterns,
        span_reduce.executables_op_names(patterns))
    if found is None:
        return None
    scope_s = sum(found["by_scope_s"].get(s, 0.0)
                  for s in [spec["scope"], *spec["kernel_scopes"]])
    updates = costs.updates_counted(costs.model_file(run.cfg), run.cfg,
                                    counters)
    if scope_s <= 0 or not updates:
        return None
    # seconds under the scope per update: the scope's share of the
    # solver programs' time in the trace (a program of this cell
    # outlasts the traced seconds, so no whole run of it is in them)
    # times the window call's own seconds per update
    per_update_s = (scope_s / found["programs_s"]) * last["seconds"] / updates
    chunks = counters[spec["counter"]]
    flops, bytes_ = costs.ssm_scan(run.cfg, chunks)
    kind = run.devices[0].device_kind
    least, bound = peaks.least_seconds(flops / updates, bytes_ / updates,
                                       kind)
    print(f"[bench] ssm_scan_roofline_share: {updates:.0f} updates counted "
          f"{chunks} scan chunks (every pass of every Mamba-2 block): "
          f"{flops / updates:.4g} FLOP and {bytes_ / updates:.4g} bytes an "
          f"update by the recurrence itself, least {least * 1e3:.4f} ms "
          f"({bound}-bound) on {kind}; under {spec['scope']} and "
          f"{spec['kernel_scopes']} {per_update_s * 1e3:.4f} ms an update "
          f"of {1e3 * last['seconds'] / updates:.4f}", flush=True)
    return 100.0 * least / per_update_s

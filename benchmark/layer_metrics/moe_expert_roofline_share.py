"""moe_expert_roofline_share — the least the chip could take for the
held experts' grouped products, for the assignments the run counted,
against the device time under `kps.moe.experts`."""

import peaks
import span_reduce


def read(run, spec):
    last = getattr(run.app, "last_run", None) or {}
    counters = last.get("counters")
    data = span_reduce.trace_data(run)
    costs = run.family.costs
    if not counters or data is None or not hasattr(costs, "expert_products"):
        return None
    patterns = spec["solver_module_patterns"]
    found = span_reduce.seconds_by_scope(
        data, run.trace_cfg, spec["scopes"], patterns,
        span_reduce.executables_op_names(patterns))
    if found is None:
        return None
    scope_s = sum(found["by_scope_s"].get(s, 0.0)
                  for s in [spec["scope"], *spec["kernel_scopes"]])
    m = costs.model_file(run.cfg)
    updates = costs.updates_counted(m, run.cfg, counters)
    if scope_s <= 0 or not updates:
        return None
    # seconds under the scope per update: the scope's share of the
    # solver programs' time in the trace (a program of this cell
    # outlasts the traced seconds, so no whole run of it is in them)
    # times the window call's own seconds per update
    per_update_s = (scope_s / found["programs_s"]) * last["seconds"] / updates
    k = run.cfg.model.num_max_iter
    blocks = costs.expert_blocks(m)
    grad = counters["moe.assignments_here_grad"]
    loss = counters["moe.assignments_here"] - grad
    flops, bytes_ = costs.expert_products(m, grad, loss, updates * blocks * k,
                                          updates * blocks)
    kind = run.devices[0].device_kind
    least, bound = peaks.least_seconds(flops / updates, bytes_ / updates,
                                       kind)
    print(f"[bench] moe_expert_roofline_share: {updates:.0f} updates "
          f"counted {grad} + {loss} assignments here (gradient passes + "
          f"loss passes): {flops / updates:.4g} FLOP and "
          f"{bytes_ / updates:.4g} bytes an update, least "
          f"{least * 1e3:.4f} ms ({bound}-bound) on {kind}; under "
          f"{spec['scope']} and {spec['kernel_scopes']} "
          f"{per_update_s * 1e3:.4f} ms an update of "
          f"{1e3 * last['seconds'] / updates:.4f}", flush=True)
    return 100.0 * least / per_update_s

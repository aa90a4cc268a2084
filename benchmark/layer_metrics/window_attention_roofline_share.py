"""window_attention_roofline_share — the least the chip could take for
the score and value products of the (query, key) pairs inside the mask
that the run counted, against the device time under `kps.attn.window`
and `kps.attn.full`."""

import peaks
import span_reduce


def read(run, spec):
    last = getattr(run.app, "last_run", None) or {}
    counters = last.get("counters") or {}
    data = span_reduce.trace_data(run)
    costs = run.family.costs
    window, full = (counters.get(name, 0) for name in spec["counters"])
    if (not window + full or data is None
            or not hasattr(costs, "attention_core")):
        return None
    patterns = spec["solver_module_patterns"]
    found = span_reduce.seconds_by_scope(
        data, run.trace_cfg, spec["scopes"], patterns,
        span_reduce.executables_op_names(patterns))
    if found is None:
        return None
    scope_s = sum(found["by_scope_s"].get(s, 0.0)
                  for s in [*spec["core_scopes"], *spec["kernel_scopes"]])
    updates = costs.updates_counted(costs.model_file(run.cfg), run.cfg,
                                    counters)
    if scope_s <= 0 or not updates:
        return None
    # seconds under the scopes per update: their share of the solver
    # programs' time in the trace (a program of this cell outlasts the
    # traced seconds, so no whole run of it is in them) times the
    # window call's own seconds per update
    per_update_s = (scope_s / found["programs_s"]) * last["seconds"] / updates
    flops, bytes_ = costs.attention_core(run.cfg, window, full)
    kind = run.devices[0].device_kind
    least, bound = peaks.least_seconds(flops / updates, bytes_ / updates,
                                       kind)
    blocks = counters.get(spec["block_counter"], 0)
    print(f"[bench] window_attention_roofline_share: {updates:.0f} updates "
          f"counted {window} + {full} x {costs.PAIRS_UNIT} pairs inside the "
          f"mask (sliding + full layers, every pass) and {blocks} inside "
          f"the blocks computed ({blocks / (window + full):.4f} of them): "
          f"{flops / updates:.4g} FLOP and {bytes_ / updates:.4g} bytes an "
          f"update, least {least * 1e3:.4f} ms ({bound}-bound) on {kind}; "
          f"under {spec['core_scopes']} and {spec['kernel_scopes']} "
          f"{per_update_s * 1e3:.4f} ms an update of "
          f"{1e3 * last['seconds'] / updates:.4f}", flush=True)
    return 100.0 * least / per_update_s

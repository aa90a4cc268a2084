"""param_plane_share — the part of the solver programs' device time
spent moving the parameter plane (step, delta, sum, apply, the flat
vector's copies), by named scope (benchmark/span_reduce.py)."""

import span_reduce


def read(run, spec):
    data = span_reduce.trace_data(run)
    if data is None:
        return None
    patterns = spec["solver_module_patterns"]
    found = span_reduce.seconds_by_scope(
        data, run.trace_cfg, spec["scopes"], patterns,
        span_reduce.executables_op_names(patterns))
    if found is None:
        return None
    plane = {s: found["by_scope_s"].get(s, 0.0) for s in spec["plane_scopes"]}
    print("[bench] param_plane_share: seconds by scope "
          f"{ {s: round(v, 6) for s, v in plane.items()} } of "
          f"{found['programs_s']:.6f}s", flush=True)
    return 100.0 * sum(plane.values()) / found["programs_s"]

"""ssm_share — the Mamba-2 mixers' part of the solver programs' device
time, by named scope (benchmark/span_reduce.py): everything under
`kps.ssm`, whose parts (the projections, the convolution, the scan, the
gated norm) the printed table gives apart."""

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
    under = {s: v for s, v in found["by_scope_s"].items()
             if s.startswith(spec["scope"])}
    if not under:
        return None
    print("[bench] ssm_share: solver programs' device time by named scope, "
          f"% of their {found['programs_s']:.6f}s on chip 0 (leaf "
          "operations): "
          f"{span_reduce.percent(found['by_scope_s'], found['programs_s'], '(no scope)')}",
          flush=True)
    return 100.0 * sum(under.values()) / found["programs_s"]

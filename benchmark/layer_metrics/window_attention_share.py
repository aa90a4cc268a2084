"""window_attention_share — the attention core's part of the solver
programs' device time, by named scope (benchmark/span_reduce.py):
scores, mask, softmax and values under `kps.attn.window` (the sliding
layers) and `kps.attn.full`; the printed table gives the projections
(`kps.attn.proj`) and the rest apart."""

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
    core = [found["by_scope_s"][s] for s in spec["core_scopes"]
            if s in found["by_scope_s"]]
    if not core:
        return None
    print("[bench] window_attention_share: solver programs' device time by "
          f"named scope, % of their {found['programs_s']:.6f}s on chip 0 "
          "(leaf operations): "
          f"{span_reduce.percent(found['by_scope_s'], found['programs_s'], '(no scope)')}",
          flush=True)
    return 100.0 * sum(core) / found["programs_s"]

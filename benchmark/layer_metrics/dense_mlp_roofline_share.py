"""dense_mlp_roofline_share — the least the chip could take for the
dense SwiGLU MLPs' products, for the positions the run counted, against
the self time, own and adopted (benchmark/self_time.py), under
`kps.mlp`."""

import peaks
import self_time


def read(run, spec):
    counters = (getattr(run.app, "last_run", None) or {}).get("counters") or {}
    costs = run.family.costs
    mlp_rows = counters.get(spec["counter"])
    if not mlp_rows or not hasattr(costs, "dense_mlp"):
        return None
    found = self_time.table(run)
    if found is None:
        return None
    # `share` is 100 x the scopes' self seconds / the programs' time
    scope_s = (self_time.share(found, spec["scopes"]) / 100.0
               * found["programs_s"] / found["updates"])
    m = costs.model_file(run.cfg)
    updates = costs.updates_counted(m, run.cfg, counters)
    if scope_s <= 0 or not updates:
        return None
    flops, bytes_ = costs.dense_mlp(
        m, mlp_rows, run.cfg.buffer.max_size, run.cfg.model.num_max_iter)
    kind = run.devices[0].device_kind
    least, bound = peaks.least_seconds(flops / updates, bytes_ / updates,
                                       kind)
    print(f"[bench] dense_mlp_roofline_share: {updates:.0f} updates "
          f"counted {mlp_rows} x {costs.ROWS_UNIT} positions through a "
          f"dense MLP (every layer, every pass): {flops / updates:.4g} FLOP "
          f"and {bytes_ / updates:.4g} bytes an update, least "
          f"{least * 1e3:.4f} ms ({bound}-bound) on {kind}; self time under "
          f"{spec['scopes']} {scope_s * 1e3:.4f} ms an update of "
          f"{found['period_s'] * 1e3:.4f}", flush=True)
    return 100.0 * least / scope_s

"""short_conv_roofline_share — the least the chip's memory could take
for the gate - filter - gate chain of the short convolutions, for the
positions the run counted, against the self time, own and adopted
(benchmark/self_time.py), under `kps.ssm.conv`."""

import peaks
import self_time


def read(run, spec):
    counters = (getattr(run.app, "last_run", None) or {}).get("counters") or {}
    costs = run.family.costs
    mix_rows = counters.get(spec["counter"])
    if not mix_rows or not hasattr(costs, "short_conv_mix"):
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
    flops, bytes_ = costs.short_conv_mix(
        m, mix_rows, run.cfg.buffer.max_size, run.cfg.model.num_max_iter)
    kind = run.devices[0].device_kind
    least, bound = peaks.least_seconds(flops / updates, bytes_ / updates,
                                       kind)
    print(f"[bench] short_conv_roofline_share: {updates:.0f} updates "
          f"counted {mix_rows} x {costs.ROWS_UNIT} positions through a "
          f"convolution chain (every conv layer, every pass): "
          f"{flops / updates:.4g} FLOP and {bytes_ / updates:.4g} bytes an "
          f"update, least {least * 1e3:.4f} ms ({bound}-bound) on {kind}; "
          f"self time under {spec['scopes']} {scope_s * 1e3:.4f} ms an "
          f"update of {found['period_s'] * 1e3:.4f}", flush=True)
    return 100.0 * least / scope_s

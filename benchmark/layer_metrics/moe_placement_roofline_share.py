"""moe_placement_roofline_share — the least the chip could take for the
products with the expert layer's 0/1 placement matrix, for the (placed
row, token) pairs the run counted, against the self time, own and
adopted (benchmark/self_time.py), under `kps.moe.place`,
`kps.moe.combine` and `kps.moe.experts` alone."""

import peaks
import self_time


def read(run, spec):
    counters = (getattr(run.app, "last_run", None) or {}).get("counters") or {}
    costs = run.family.costs
    pairs = counters.get(spec["counter"])
    if not pairs or not hasattr(costs, "placement_products"):
        return None
    found = self_time.table(run)
    if found is None:
        return None
    # `share` is 100 x the scopes' self seconds / the programs' time
    scope_s = (self_time.share(found, spec["placement_scopes"]) / 100.0
               * found["programs_s"] / found["updates"])
    m = costs.model_file(run.cfg)
    updates = costs.updates_counted(m, run.cfg, counters)
    if scope_s <= 0 or not updates:
        return None
    flops, bytes_ = costs.placement_products(
        m, pairs, run.cfg.buffer.max_size, run.cfg.model.num_max_iter)
    kind = run.devices[0].device_kind
    least, bound = peaks.least_seconds(flops / updates, bytes_ / updates,
                                       kind)
    print(f"[bench] moe_placement_roofline_share: {updates:.0f} updates "
          f"counted {pairs} x {costs.PAIRS_UNIT} (placed row, token) pairs "
          f"({counters.get('moe.passes_over_bound', 0)} expert-layer passes "
          f"over the bound): {flops / updates:.4g} FLOP and "
          f"{bytes_ / updates:.4g} bytes an update, least "
          f"{least * 1e3:.4f} ms ({bound}-bound) on {kind}; self time under "
          f"{spec['placement_scopes']} {scope_s * 1e3:.4f} ms an update of "
          f"{found['period_s'] * 1e3:.4f}", flush=True)
    return 100.0 * least / scope_s

"""layer_loop_self_share — what a family's loop over its layers costs
by itself: self time, own and adopted (benchmark/self_time.py), under
`kps.lm.layers` ALONE, apart from every layer's own scopes inside it."""

import self_time


def read(run, spec):
    found = self_time.table(run)
    if found is None or spec["scope"] not in found["by_scope_s"]:
        return None
    counters = (getattr(run.app, "last_run", None) or {}).get("counters") or {}
    costs = run.family.costs
    if spec["counter"] in counters and hasattr(costs, "updates_counted"):
        count = counters[spec["counter"]]
        updates = costs.updates_counted(costs.model_file(run.cfg), run.cfg,
                                        counters)
        line = found["by_scope_s"][spec["scope"]]
        per = 1e3 / found["updates"]
        print(f"[bench] layer_loop_self_share: {spec['counter']} {count} "
              f"over {updates:.0f} updates, {count / max(updates, 1):.2f} "
              f"layer applications an update; under {spec['scope']} alone "
              f"{line['own'] * per:.4f} ms own + {line['adopted'] * per:.4f} "
              f"ms adopted an update of {found['period_s'] * 1e3:.4f}",
              flush=True)
    return self_time.share(found, [spec["scope"]])

"""expert_load_max_over_mean — how uneven the routing over the held
experts was, from the program's own counters of its last drive call."""


def read(run, spec):
    counters = (getattr(run.app, "last_run", None) or {}).get("counters")
    if not counters or not counters.get("moe.assignments_here"):
        return None
    costs = run.family.costs
    model = costs.model_file(run.cfg)
    passes = (costs.updates_counted(model, run.cfg, counters)
              * costs.expert_blocks(model)
              * (run.cfg.model.num_max_iter + 1))
    mean = counters["moe.assignments_here"] / model["experts_held"] / passes
    largest = counters["moe.expert_load_max"] / passes
    print(f"[bench] expert_load_max_over_mean: counters {counters}: "
          f"{passes:.0f} expert-layer passes, largest group "
          f"{largest:.2f} and mean group {mean:.2f} assignments a pass",
          flush=True)
    return largest / mean

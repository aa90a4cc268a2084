"""step_roofline_share — the solver/step programs' device time against
the least the chip could take for the work they did."""

import re

import peaks


def read(run, spec):
    t = run.trace_summary
    if not t:
        return None

    def matching(table, patterns):
        found = [re.compile(p) for p in patterns]
        return {name: v for name, v in table.items()
                if any(p.search(name) for p in found)}

    if run.traced_updates:
        # whole calls between two device syncs: every run is whole and
        # the harness knows the updates they applied
        times = matching(t["module_time_s"], spec["solver_module_patterns"])
        updates = run.traced_updates / t["chips"]
    else:
        # a trace cut out of one long call: the runs that are surely
        # whole, each one scan chunk of the app's
        times = matching(t["module_whole_time_s"],
                         spec["solver_module_patterns"])
        runs = sum(matching(t["module_whole_runs"],
                            spec["solver_module_patterns"]).values())
        updates = runs * run.chunk_clocks * run.workers / t["chips"]
    solver_s = sum(times.values())
    if solver_s <= 0 or not updates:
        return None
    costs = run.family.costs      # the family's operations and bytes
    flops, bytes_ = costs.update(run.cfg)
    # counted only where every solver program in the trace carries it:
    # a share may read low, never high
    with_eval = (matching(times, spec["eval_rides_along_patterns"])
                 == times)
    if with_eval:
        e_flops, e_bytes = costs.evaluation(run.cfg, run.test)
        flops, bytes_ = flops + e_flops, bytes_ + e_bytes
    kind = run.devices[0].device_kind
    least, bound = peaks.least_seconds(flops, bytes_, kind)
    print(f"[bench] step_roofline_share: {flops:.4g} FLOP and {bytes_:.4g} "
          f"bytes per update{' and its test-set evaluation' if with_eval else ''}"
          f", least {least * 1e3:.4f} ms ({bound}-bound) on {kind}; "
          f"{sorted(times)} took {solver_s:.4f} s a chip for "
          f"{updates:.0f} updates a chip = "
          f"{1e3 * solver_s / updates:.4f} ms each, "
          f"{flops * updates / solver_s / 1e12:.2f} TFLOP/s", flush=True)
    return 100.0 * least * updates / solver_s

"""The control of `correct`: the reference put in the program's place,
with what a lower-precision program would hold in the precision below.

    python benchmark/control.py --workload <cell> --seeds 1,2,3

For each seed it makes the cell's items, runs the family's reference and
each of its controls (`reference.CONTROLS`: the reference's own keywords
by name, and what each stands for) through the cell's first clocks, and
prints the numbers the benchmark compares (`delta_norm_gap`, `loss_gap`)
for control against reference.  The benchmark's own runs never call
this; its readings set the limits in the cell's file (PERF.md gives
them).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.dirname(HERE), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def readings(cell: dict, seed: int, shrink=None, shrink_data=None) -> dict:
    import numpy as np

    import run as harness
    from kafka_ps_tpu.cli import run as cli

    cfg = cli.cfg_from_args(cli.build_parser().parse_args(
        harness.cli_flags(cell, shrink)))
    family = harness.Family(cell)
    reference = family.reference
    shapes = reference.shapes(cfg)
    data = dict(cell["config"]["data"], **(shrink_data or {}))
    train, _ = family.datagen.make(seed, cfg, data)
    slabs = family.datagen.slabs(train, cfg.num_workers)
    chk = cell["traffic"]["check"]
    clocks, stride = chk["clocks"], chk["stride_clocks"]
    theta0 = np.asarray(reference.init_params(shapes))
    want_t, want_l = reference.Reference(shapes).run(theta0, slabs, clocks,
                                                     keep_every=stride)
    out = {}
    for name, kwargs in reference.CONTROLS.items():
        got_t, got_l = reference.Reference(shapes, **kwargs).run(
            theta0, slabs, clocks, keep_every=stride)
        out[name] = {
            "delta_norm_gap": max(
                reference.param_gap(got, want, theta0, shapes)
                for got, want in zip(got_t, want_t)),
            "loss_gap": max(harness.relative_gap(g, r)
                            for g, r in zip(got_l, want_l))}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ns = ap.parse_args(argv)
    import run as harness
    from kafka_ps_tpu.cli import run as cli
    cli.apply_platform_env()
    cell = harness.load_cell(ns.workload)
    for seed in (int(s) for s in ns.seeds.split(",")):
        print(json.dumps({"workload": ns.workload, "seed": seed,
                          **readings(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

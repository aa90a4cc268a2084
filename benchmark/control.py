"""The control of `correct`: the reference put in the program's place,
with what a lower-precision program would hold rounded to bfloat16.

    python benchmark/control.py --workload <cell> --seeds 1,2,3

For each seed it makes the cell's rows, runs the float32 reference and
each control through the cell's first clocks, and prints the numbers the
benchmark compares (`delta_norm_gap`, `loss_gap`) for control against
reference.  The benchmark's own runs never call this; its readings set
the limits in the cell's file (PERF.md gives them).

Controls:
  * `theta_bf16` — the shared parameters held in bfloat16 between
    clocks (what halving the 16.9 MB broadcast and delta would do): the
    control that has to come out as not correct;
  * `slab_bf16` — the worker slabs held in bfloat16 (`--slab-dtype
    bf16`): recorded to show what the comparison can NOT see.  On the
    chip the program's matrix products already round the slab to
    bfloat16 (default precision), so against a `highest` reference this
    control and the sound program read alike.
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
    import jax.numpy as jnp
    import numpy as np

    import reference
    import run as harness
    from kafka_ps_tpu.cli import run as cli

    cfg = cli.cfg_from_args(cli.build_parser().parse_args(
        harness.cli_flags(cell, shrink)))
    shapes = harness.reference_shapes(cfg)
    data = dict(cell["config"]["data"], **(shrink_data or {}))
    w, rows = cfg.num_workers, data["rows_per_worker"]
    x, y, _, _ = harness.make_rows(cfg, data, seed)
    # row i goes to worker i % w, as datagen.feed delivers them
    slabs = [(x[i::w], y[i::w], np.ones((rows,), np.float32))
             for i in range(w)]
    chk = cell["traffic"]["check"]
    clocks, stride = chk["clocks"], chk["stride_clocks"]
    theta0 = np.asarray(reference.init_params(shapes))
    want_t, want_l = reference.Reference(shapes).run(theta0, slabs, clocks)
    out = {}
    for name, kwargs in (("theta_bf16", {"theta_dtype": jnp.bfloat16}),
                         ("slab_bf16", {"slab_dtype": jnp.bfloat16})):
        got_t, got_l = reference.Reference(shapes, **kwargs).run(
            theta0, slabs, clocks)
        out[name] = {
            "delta_norm_gap": max(
                reference.leaf_norm_gap(got_t[c - 1], want_t[c - 1], theta0,
                                        shapes)
                for c in range(stride, clocks + 1, stride)),
            "loss_gap": max(reference.relative_gap(g, r)
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

"""Shared by the harness tests: the tiny size every cell is shrunk to,
and one run of `run.main` with its last line parsed."""

import json

import run as harness

# widths small enough for a CPU test run; the cell's files are the real
# ones, so flags, drive, slice rule and limits are what the chip runs
SHRINK = {"--hidden_dim": "64", "--num_features": "32", "-max": "64",
          "-min": "16"}
DATA = {"rows_per_worker": 64, "test_rows": 1000}
# a CPU trace has no TPU planes: operations run on host threads
CPU_TRACE = {"device_plane_prefix": "/host:CPU", "op_line": "tf_XLA.*",
             "module_line": "none", "start_after_s": 0.2, "seconds": 0.5}


def run_cell(capsys, cell, workers, *, trace=0, seconds=1.5, seed=5,
             break_step=None):
    rc = harness.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        platform="cpu", shrink=dict(SHRINK, **{"--num_workers": workers}),
        shrink_data=DATA, break_step=break_step, trace_layout=CPU_TRACE)
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    return rc, json.loads(lines[-1]), out

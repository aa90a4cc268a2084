"""Shared by the harness tests: the tiny size each cell is shrunk to,
which its family states (`tiny.json` beside the family's modules), and
one run of `run.main` with its last line parsed."""

import json

import run as harness

# a CPU trace has no TPU planes: operations run on host threads
CPU_TRACE = {"device_plane_prefix": "/host:CPU", "op_line": "tf_XLA.*",
             "module_line": "none", "start_after_s": 0.2, "seconds": 0.5}


def tiny(cell, workers, manifest=None):
    """(flags replaced by name, keys of the data block replaced): widths
    small enough for a CPU test run; the cell's files are the real ones,
    so flags, drive, slice rule and limits are what the chip runs."""
    size = harness.load_json(harness.load_cell(cell, manifest)["family_dir"],
                             "tiny.json")
    return dict(size["shrink"], **{"--num_workers": workers}), size["data"]


def run_cell(capsys, cell, workers, *, trace=0, seconds=1.5, seed=5,
             break_step=None, manifest=None):
    shrink, data = tiny(cell, workers, manifest)
    rc = harness.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        platform="cpu", shrink=shrink, shrink_data=data,
        break_step=break_step, trace_layout=CPU_TRACE, manifest=manifest)
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    return rc, json.loads(lines[-1]), out

"""The harness end to end at a tiny size on the CPU: every cell of
BENCHMARK.json through `run.main`, untraced and traced."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from helpers import run_cell

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [(w["name"], "8" if w["chips"] == 4 else "4")
         for w in MANIFEST["workloads"]]


def names(group, cell):
    return {m["name"] for m in MANIFEST[group]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell,workers", CELLS)
def test_untraced_run_reports_the_end_to_end_metrics(capsys, cell, workers):
    rc, result, out = run_cell(capsys, cell, workers)
    assert rc == 0
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == names("end_to_end", cell)
    for metric in result["metrics"].values():
        assert metric["value"] > 0
    device = result["device"]
    assert device["platform"] == "cpu"
    # the two parts of the memory reading are reported apart
    assert device["memory_peak_bytes"] == (device["memory_live_peak_bytes"]
                                           + device["memory_scratch_bytes"])
    mode = json.load(open(os.path.join(
        BENCH, "workloads", cell + ".json")))["window"]["mode"]
    calls = int(out.split("[bench] window: ")[1].split(" call(s)")[0])
    assert (calls == 1) if mode == "one_call" else (calls > 1)
    # every number compared is printed beside its limit
    assert out.count("[bench] compare ") >= 12


@pytest.mark.parametrize("cell,workers", CELLS)
def test_traced_run_reports_per_layer_metrics_and_breakdown(capsys, cell,
                                                            workers):
    rc, result, out = run_cell(capsys, cell, workers, trace=1)
    assert rc == 0 and result["correct"] is True, out
    assert set(result) == RESULT_KEYS | {"breakdown"}
    got = set(result["metrics"])
    # on the CPU there are no XLA module events, so the roofline reader
    # finds nothing to read and its metric is left out of the line
    assert got <= names("per_layer", cell)
    assert "device_idle_share" in got
    assert "compiles_in_window" in got
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] >= result["device"]["busy_s"]
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert len(result["breakdown"]["idle_gaps"]) <= 10


def test_four_chip_cell_shards_over_four_devices(capsys):
    import jax
    assert len(jax.devices()) == 4
    rc, result, out = run_cell(capsys, "mlp-4096-x4.fused-bsp", "8")
    assert rc == 0 and result["correct"] is True, out
    assert result["device"]["count"] == 4


def test_a_run_without_the_accelerator_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "mlp-4096.fused-bsp", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "nothing was run" in p.stderr

"""The harness end to end at a tiny size on the CPU: every cell of
BENCHMARK.json through `run.main`, untraced and traced."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from helpers import run_cell

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
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
    # every number compared is printed beside its limit, and stands
    # last in the result's line
    assert out.count("[bench] compare ") >= 12
    assert list(result)[-1] == "compared"
    assert len(result["compared"]) == out.count("[bench] compare ")
    for number in result["compared"].values():
        assert number["value"] <= number["limit"]


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


def test_the_numbers_compared_are_the_last_lines_of_standard_error(capsys):
    import run as harness
    from helpers import CPU_TRACE, tiny
    cell = CELLS[0][0]
    shrink, data = tiny(cell, "4")
    assert harness.main(["--workload", cell, "--seed", "11", "--seconds",
                         "1", "--trace", "0"], platform="cpu", shrink=shrink,
                        shrink_data=data, trace_layout=CPU_TRACE) == 0
    captured = capsys.readouterr()
    result = json.loads(captured.out.splitlines()[-1])
    last = captured.err.splitlines()[-len(result["compared"]):]
    assert [ln.split()[1] for ln in last] == list(result["compared"])
    assert all(ln.startswith("compare ") and ln.endswith(" ok")
               for ln in last)


class _Builds:
    """Stands in for run.Compiles: what each call built, in turn."""

    def __init__(self):
        self.pending = (0, 0)

    def take(self):
        taken, self.pending = self.pending, (0, 0)
        return taken


def test_a_one_call_window_is_sized_from_warm_calls(capsys):
    """A process with a cold compile cache compiles inside its probes:
    seconds that a call does not cost.  Such a probe is made again, so
    the window is the one a warm process gets; a warm process, which
    only reads the cache, makes each probe once."""
    import run as harness

    def sized(calls):
        """calls: (seconds, programs built, compiled anew) in turn."""
        run = harness.Run.__new__(harness.Run)
        run.traffic = {"window": {"probe_chunks": [1, 9]}}
        run.chunk_clocks, run.seconds = 8, 20.0
        builds = _Builds()
        todo = list(calls)

        def drive(clocks):
            took, built, anew = todo.pop(0)
            builds.pending = (built, anew)
            return took
        run.drive = drive
        run.size_one_call(builds)
        assert not todo
        return run.call_clocks // 8

    # 1.65 s a call + 0.354 s a chunk: 51 chunks last 20 s
    warm = sized([(2.004, 0, 0), (4.836, 2, 0)])
    assert warm == 51
    # the cold process: 1.7 s of compiling inside the 1-chunk probe
    # would read as a call's fixed cost and size the window at 100
    cold = sized([(3.704, 1, 1), (2.004, 0, 0), (4.836, 2, 0)])
    assert cold == warm
    out = capsys.readouterr().out
    assert out.count("not a warm call") == 1
    # a process that never settles is sized from its last try
    assert sized([(3.7, 1, 1)] * harness.PROBE_TRIES
                 + [(4.836, 0, 0)]) > warm


def test_a_program_read_from_the_cache_is_built_but_not_compiled_anew():
    import threading

    import run as harness
    compiles = harness.Compiles()
    compiles._on_event(harness.CACHE_HIT_EVENT)
    compiles._on_built(harness.COMPILE_EVENT, 0.01)      # the read
    compiles._on_built(harness.COMPILE_EVENT, 1.5)       # a compile
    other = threading.Thread(
        target=lambda: compiles._on_event(harness.CACHE_HIT_EVENT))
    other.start()
    other.join()          # another thread's hit is not this thread's
    compiles._on_built(harness.COMPILE_EVENT, 0.5)
    compiles._on_built("/jax/other", 1.0)
    assert compiles.take() == (3, 2)
    assert compiles.take() == (0, 0)

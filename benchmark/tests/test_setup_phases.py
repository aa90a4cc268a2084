"""setup_phases.py and the six `setup_*` readers: on a hand-made record
of the program's, where every share is known by hand; against a program
that keeps no record; and through the harness on the CPU, a cold run and
a warm one in the same compile-cache directory."""

import json
import os
import types
from collections import deque

import pytest

import setup_phases
from conftest import ROOT
from helpers import run_cell
from test_span_reduce import metric

READERS = ["setup_backend_share", "setup_app_init_share",
           "setup_build_share", "setup_compiled_anew",
           "setup_warm_calls_share", "setup_outside_program_share"]
SHARES = [name for name in READERS if name.endswith("_share")]
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def a_record():
    """Two `main()` runs of one process.  Run 1: T0 101, the app built
    [106, 110), calls at 130 and 133, the window's call at 140.  Run 2:
    T0 200, the app built [206, 210) with a compile of 2 s inside, the
    reference [212, 224) with a compile of 10 s of its own, two calls
    before the window — a trace, a lowering and a cache read of 1.5 s
    in all inside the first — and the window's call at 240."""
    return {
        "process_start": 100.0,
        "phases": deque([("import", 100.0, 103.0), ("backend", 103.0, 105.0),
                         ("app_init", 106.0, 110.0),
                         ("app_init", 206.0, 210.0)]),
        "marks": {"first_update": 132.0},
        "builds": {
            "compile": [(1.0, "jit(old)", 107.0, 108.0),
                        (2.0, "jit(init)", 207.0, 209.0),
                        (10.0, "jit(reference)", 213.0, 223.0)],
            "cache_load": [(1.0, "jit(step)", 230.5, 231.5)],
            "lower": [(0.3, "jit(step)", 230.2, 230.5)],
            # a traced function's inner function is an event inside it
            "trace": [(0.2, "jit(step)", 230.0, 230.2),
                      (0.1, "jit(inner)", 230.05, 230.15)]},
        "dropped": {"compile": [0, 0.0], "cache_load": [0, 0.0],
                    "lower": [0, 0.0], "trace": [40, 0.25]},
        "calls": deque([(130.0, 2.0), (133.0, 3.0), (140.0, 20.0),
                        (230.0, 2.0), (233.0, 3.0), (240.0, 20.0)]),
        "first_call": {"path": "fused", "seconds": 2.0, "started": 130.0,
                       "theta_up_s": 0.5, "slab_refresh_s": 0.25,
                       "device_wait_s": 1.0, "theta_down_s": 0.125,
                       "slab_refreshes": 1}}


def test_the_second_run_of_a_process_is_cut_at_its_own_calls():
    # 40 s from T0 to the window, 12 of them the reference's
    found = setup_phases.reduce(a_record(), 1, 28.0, 13.0, t0=200.0)
    assert (found["t0"], found["window_start"]) == (200.0, 240.0)
    assert found["calls_before_window"] == 2
    assert found["seconds"] == pytest.approx({
        "compile": 2.0, "cache_load": 1.0, "lower": 0.3, "trace": 0.2,
        "import": 0.0, "backend": 0.0, "app_init": 2.0, "call": 3.5,
        "other": 31.0})
    assert sum(found["seconds"].values()) == pytest.approx(40.0)
    assert found["outside_s"] == pytest.approx(19.0)      # 31 less 12
    assert (found["built"], found["anew"]) == (2, 1)
    assert found["first_update_after_s"] is None          # run 1's mark
    assert found["first_call"] is None                    # and run 1's call
    assert [p for p, _ in found["longest"]] == ["jit(init)", "jit(step)",
                                                "jit(inner)"]
    assert dict(found["longest"])["jit(step)"] == pytest.approx(
        {"compile": 0.0, "cache_load": 1.0, "lower": 0.3, "trace": 0.2})
    # the reference's compile lies in no phase and no call
    other = found["not_the_programs"]
    assert (other["built"], other["anew"], other["compile"]) == (1, 1, 10.0)
    gap = found["gaps"][0]
    assert (gap["seconds"], gap["after"], gap["before"]) == (
        20.0, "setup.app_init", "call 1")
    assert gap["builds_not_the_programs"] == 1
    shares = setup_phases.shares(found)
    assert shares == pytest.approx({
        "backend": 0.0, "app_init": 100 * 2 / 28, "build": 100 * 3.5 / 28,
        "warm_calls": 100 * 3.5 / 28, "outside_program": 100 * 19 / 28})
    assert sum(shares.values()) == pytest.approx(100.0)


def test_the_first_run_reads_the_process_from_its_start():
    record = a_record()
    record["calls"] = deque(list(record["calls"])[:3])
    record["phases"].pop()
    # no T0 known: the window's start less setup_s less reference_s
    found = setup_phases.reduce(record, 1, 35.0, 4.0)
    assert (found["t0"], found["window_start"]) == (101.0, 140.0)
    assert found["seconds"] == pytest.approx({
        "compile": 1.0, "cache_load": 0.0, "lower": 0.0, "trace": 0.0,
        "import": 2.0, "backend": 2.0, "app_init": 3.0, "call": 5.0,
        "other": 26.0})
    assert found["first_update_after_s"] == pytest.approx(31.0)
    assert found["first_call"]["device_wait_s"] == 1.0
    assert found["dropped"]["trace"] == [40, 0.25]
    shares = setup_phases.shares(found)
    assert shares["backend"] == pytest.approx(100 * 4 / 35)
    assert shares["outside_program"] == pytest.approx(100 * 22 / 35)
    assert sum(shares.values()) == pytest.approx(100.0)
    line = setup_phases.printed(found)
    assert line.startswith("[bench] set-up by phase: setup_s 35.0000")
    assert "\n" not in line and '"jit(old)", 1.0' in line
    # the first call whole, and what the build seconds can lack at most
    assert ('the first of them whole {"path": "fused", "seconds": 2.0, '
            '"theta_up_s": 0.5, "slab_refresh_s": 0.25, "device_wait_s": '
            '1.0, "theta_down_s": 0.125}') in line
    assert '"trace": [40, 0.25]}' in line


def test_the_table_and_the_programs_line_are_one_sweep():
    """The reducer cuts an interval as `device.startup_split` does —
    the `[startup]` line's sweep — class for class."""
    from kafka_ps_tpu.utils import device
    record = a_record()
    found = setup_phases.reduce(record, 1, 28.0, 13.0, t0=200.0)
    assert found["seconds"] == device.startup_split(200.0, 240.0,
                                                    record)["seconds"]


@pytest.mark.parametrize("calls,setup_s", [(0, 28.0), (7, 28.0), (1, 0.0)])
def test_a_window_the_record_does_not_hold_reads_nothing(calls, setup_s):
    assert setup_phases.reduce(a_record(), calls, setup_s, 13.0) is None


def fake_run(calls=1):
    """What the readers see of benchmark/run.py's Run, of a module
    that holds no T0."""
    return types.SimpleNamespace(call_times=[20.0] * calls, setup_s=28.0,
                                 reference_s=12.0)


def test_the_readers_share_one_table_a_run(monkeypatch, capsys):
    monkeypatch.setattr(setup_phases.program, "STARTUP", a_record())
    run = fake_run()
    got = {name: metric(name)[0](run, metric(name)[1]) for name in READERS}
    assert capsys.readouterr().out.count("[bench] set-up by phase") == 1
    assert got == pytest.approx({
        "setup_backend_share": 0.0, "setup_app_init_share": 100 * 2 / 28,
        "setup_build_share": 100 * 3.5 / 28, "setup_compiled_anew": 1.0,
        "setup_warm_calls_share": 100 * 3.5 / 28,
        "setup_outside_program_share": 100 * 19 / 28})
    assert sum(got[name] for name in SHARES) == pytest.approx(100.0)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_record_reads_nothing(monkeypatch, name,
                                                    capsys):
    """What the parent of the PR that brought the record gives: None,
    never a raise, and no table."""
    monkeypatch.setattr(setup_phases, "startup_split", None)
    read, spec = metric(name)
    assert read(fake_run(), spec) is None
    assert "set-up by phase" not in capsys.readouterr().out


@pytest.mark.parametrize("name", READERS)
def test_the_manifest_lists_the_reader_for_every_cell(name):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert (entry["layer"], entry["moves"], entry["better"]) == (
        "entry / start-up", "setup_s", "lower")
    assert entry["workloads"] == [w["name"] for w in MANIFEST["workloads"]]
    assert metric(name)[1]["source"] == entry["source"]


@pytest.fixture
def compile_cache(tmp_path, monkeypatch):
    """The persistent compile cache on, in a directory of the test's
    (conftest.py turns it off for these tests)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    before = (jax.config.jax_enable_compilation_cache,
              jax.config.jax_compilation_cache_dir)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before[0])
    jax.config.update("jax_compilation_cache_dir", before[1])
    compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", ["mlp-4096.fused-bsp",
                                  "mlp-4096.pernode-bsp"])
def test_a_cold_run_compiles_anew_and_the_warm_one_after_it_does_not(
        capsys, monkeypatch, compile_cache, cell):
    """A `one_call` cell and the `slices` cell at their tiny size, twice
    in one cache directory, each run as a process of its own would see
    it: `T0` where the run begins, no program kept in memory."""
    import time

    import jax

    import run as harness
    got = []
    for _ in ("cold", "warm"):
        jax.clear_caches()
        monkeypatch.setattr(harness, "T0", time.time())
        rc, result, out = run_cell(capsys, cell, "4", trace=1)
        assert rc == 0 and result["correct"] is True, out
        assert out.count("[bench] set-up by phase") == 1
        metrics = {k: v["value"] for k, v in result["metrics"].items()}
        assert set(READERS) <= set(metrics)
        assert sum(metrics[name] for name in SHARES) == pytest.approx(
            100.0, abs=0.1)
        assert all(metrics[name] >= 0 for name in READERS)
        assert metrics["setup_build_share"] > 0
        assert metrics["setup_app_init_share"] > 0
        assert metrics["setup_warm_calls_share"] > 0
        got.append(metrics)
    cold, warm = got
    assert cold["setup_compiled_anew"] >= 1
    assert warm["setup_compiled_anew"] == 0

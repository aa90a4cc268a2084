"""A second family from files alone: the fixture manifest's one cell
(benchmark/tests/fixtures/README.txt) goes through `run.main` and is
judged by its own reference, on rows of its own generator."""

import os
import shutil
import sys

import control
import run as harness
from conftest import BENCH
from helpers import run_cell, tiny
from test_manifest import FIXTURE_MANIFEST

CELL = "rows-f64.fused-bsp"
FAMILY_MODULES = {"family_rows_f64_reference", "family_rows_f64_datagen",
                  "family_rows_f64_costs"}


def test_the_fixture_family_runs_and_is_correct(capsys):
    for name in FAMILY_MODULES:
        sys.modules.pop(name, None)
    rc, result, out = run_cell(capsys, CELL, "4", manifest=FIXTURE_MANIFEST)
    assert rc == 0 and result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0
    # the harness called the family's files, and compared what the
    # cell's limits name: the family's own evaluation, not the default's
    assert FAMILY_MODULES <= set(sys.modules)
    here = os.path.join(BENCH, "tests", "fixtures", "families", "rows-f64")
    assert os.path.dirname(
        sys.modules["family_rows_f64_reference"].__file__) == here
    assert "final_eval_hit_rate_gap" in result["compared"]
    assert not [k for k in result["compared"] if "f1" in k]


def test_one_wrong_line_in_the_familys_reference_is_not_correct(capsys,
                                                                 tmp_path):
    """The same files with the reference's step doubled: the run has to
    come out as not correct, so the harness really judges by them."""
    twin = shutil.copytree(os.path.dirname(FIXTURE_MANIFEST),
                           str(tmp_path / "fixtures"))
    path = os.path.join(twin, "families", "rows-f64", "reference.py")
    body = open(path).read()
    sound = "t = t - s.lr * _grad(t, x, y, mask, s)"
    assert body.count(sound) == 1
    with open(path, "w") as fh:
        fh.write(body.replace(sound, "t = t - 2 * s.lr * _grad(t, x, y, "
                                     "mask, s)"))
    rc, result, out = run_cell(capsys, CELL, "4",
                               manifest=os.path.join(twin, "BENCHMARK.json"))
    assert rc == 0
    assert result["correct"] is False
    assert "compare delta_norm_gap " in out and "FAIL" in out
    compared = result["compared"]["delta_norm_gap"]
    assert compared["value"] > compared["limit"]


def test_the_fixture_familys_control_fails_its_limit():
    cell = harness.load_cell(CELL, FIXTURE_MANIFEST)
    limit = cell["traffic"]["check"]["limits"]["delta_norm_gap"]
    for seed in (1, 2, 3):
        got = control.readings(cell, seed,
                               *tiny(CELL, "4", FIXTURE_MANIFEST))
        assert got["theta_f16"]["delta_norm_gap"] > 3 * limit, got

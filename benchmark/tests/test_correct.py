"""`correct` has to be able to come out false: the lower-precision
control fails the cells' own limit, and a timed path broken underneath
the harness is seen."""

import dataclasses
import json
import os

import pytest

import control
import run as harness
from conftest import BENCH, ROOT
from helpers import run_cell, tiny

# every cell of the manifest, with the workers it is shrunk to
CELLS = [(w["name"], "8" if w["chips"] == 4 else "4")
         for w in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))[
             "workloads"]]


@pytest.mark.parametrize("cell,workers", [
    c for c in CELLS if not harness.load_cell(c[0])["family"]])
def test_parameters_held_in_bf16_fail_the_cells_limit(cell, workers):
    """The cells of the default family (a family that is named brings
    its own controls: `reference.CONTROLS`)."""
    loaded = harness.load_cell(cell)
    limits = loaded["traffic"]["check"]["limits"]
    for seed in (1, 2, 3):
        got = control.readings(loaded, seed, *tiny(cell, workers))
        assert got["theta_bf16"]["delta_norm_gap"] > 3 * limits[
            "delta_norm_gap"], got
        # what the comparison cannot see, and why (control.py): the
        # slabs in bf16 stay far below the limit
        assert got["slab_bf16"]["delta_norm_gap"] < limits["delta_norm_gap"]


def frozen_step(run):
    """A fused step that returns its state unchanged."""
    import jax.numpy as jnp
    app = run.app

    def unchanged(theta, x, y, mask):
        return theta, jnp.zeros((app.FUSED_CHUNK_ROUNDS,), jnp.float32)
    app._fused_programs[("bsp", run.workers, run.mesh)] = {
        "step": lambda theta, x, y, mask: (theta, jnp.float32(0.0)),
        "multi_step": unchanged}


def halved_deltas(run):
    """Every worker's answer altered where it is produced."""
    from kafka_ps_tpu.runtime import fabric as fabric_mod
    send = run.app.fabric.send

    def altered(topic, key, msg):
        if topic == fabric_mod.GRADIENTS_TOPIC:
            msg = dataclasses.replace(msg, values=msg.values * 0.5)
        return send(topic, key, msg)
    run.app.fabric.send = altered


# how each drive's timed path is broken, and the number that sees it
SABOTAGE = {"fused": (frozen_step, "delta_norm_gap"),
            "serial": (halved_deltas, "delta_norm_gap")}


def drive_of(cell):
    return json.load(open(os.path.join(BENCH, "workloads",
                                       cell + ".json")))["drive"]


@pytest.mark.parametrize("cell,workers", CELLS)
def test_a_broken_timed_path_is_not_correct(capsys, cell, workers):
    sabotage, fault = SABOTAGE[drive_of(cell)]
    rc, result, out = run_cell(capsys, cell, workers, break_step=sabotage)
    assert rc == 0
    assert result["correct"] is False
    assert f"compare {fault} " in out and "FAIL" in out
    assert "NOT CORRECT" in out

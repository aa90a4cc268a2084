"""`correct` has to be able to come out false: the lower-precision
control fails the cells' own limit, and a timed path broken underneath
the harness is seen."""

import dataclasses

import pytest

import control
import run as harness
from helpers import DATA, SHRINK, run_cell

SMALL = dict(SHRINK, **{"--num_workers": "4"})


@pytest.mark.parametrize("cell", ["mlp-4096.fused-bsp",
                                  "mlp-4096.pernode-bsp"])
def test_parameters_held_in_bf16_fail_the_cells_limit(cell):
    loaded = harness.load_cell(cell)
    limits = loaded["traffic"]["check"]["limits"]
    for seed in (1, 2, 3):
        got = control.readings(loaded, seed, SMALL, DATA)
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
    app._fused_programs[("bsp", run.workers, None)] = {
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


@pytest.mark.parametrize("cell,sabotage,fault", [
    ("mlp-4096.fused-bsp", frozen_step, "delta_norm_gap"),
    ("mlp-4096.pernode-bsp", halved_deltas, "delta_norm_gap"),
])
def test_a_broken_timed_path_is_not_correct(capsys, cell, sabotage, fault):
    rc, result, out = run_cell(capsys, cell, "4", break_step=sabotage)
    assert rc == 0
    assert result["correct"] is False
    assert f"compare {fault} " in out and "FAIL" in out
    assert "NOT CORRECT" in out

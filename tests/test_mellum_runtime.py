"""The `mellum` family through the runtime: its record for the contract
every language-model family is held to (tests/lm_family_contract.py),
and the task through `StreamingPSApp`'s fused loop for 8 clocks
against the benchmark's reference.  tests/test_mellum.py holds the
model against its reference."""

import dataclasses
import importlib.util
import os
import sys

import numpy as np
import pytest

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import mellum
from lm_family_contract import *  # noqa: F401,F403 — the contract's cases
from lm_family_contract import ROOT, Family, rows_of


def reads(c):
    assert c.attention_block == 8
    assert c.layer_types == (mellum.SLIDING,) * 3 + (mellum.FULL,)
    assert c.rope(mellum.FULL)["rope_type"] == "yarn"
    assert c.rope(mellum.SLIDING) == {"rope_type": "default",
                                      "rope_theta": 500000}


def counted(task, counters):
    c = task.arch
    # 32 updates x (k + 1) passes x 2 rows x the pairs of a row's pass,
    # in units of 1,024 pairs rounded down a pass
    window, full, blocks = mellum.pair_counts(c)
    assert counters["attn.pairs_window"] == 32 * 3 * (2 * window // 1024)
    assert counters["attn.pairs_full"] == 32 * 3 * (2 * full // 1024)
    assert counters["attn.block_pairs"] == 32 * 3 * (2 * blocks // 1024)
    # every token chooses 2 of 8 experts in each of 4 layers, and a
    # quarter of the experts is held here
    slots = 32 * 3 * 2 * c.sequence_length * 2 * 4
    assert counters["moe.assignments_here"] \
        + counters["moe.assignments_away"] == slots
    assert 0.10 * slots < counters["moe.assignments_here"] < 0.45 * slots
    # the placement: 48 rows x 48 tokens a layer a pass under the
    # bound, 96 x 48 in a pass over it
    over = counters["moe.passes_over_bound"]
    under, beyond = mellum.place_pairs(2 * c.sequence_length, c)
    assert counters["moe.place_pairs"] == (
        32 * 3 * (4 * under // 1024) + over * ((beyond - under) // 1024))
    assert counters["moe.place_pairs"] > 0
    assert counters["moe.place_pairs_dense"] == counters["moe.place_pairs"]
    # q's and k's head rows through every layer
    assert counters["attn.norm_rope_rows"] == 32 * 3 * (
        2 * c.sequence_length * c.num_hidden_layers
        * (c.num_attention_heads + c.num_key_value_heads) // 1024) > 0


FAMILY = Family(
    name="mellum", module=mellum,
    tiny="benchmark/families/mellum/tiny.model.json",
    digests="mellum_tiny_stablehlo.json", reads=reads, counted=counted,
    counter_names=lm.COUNTERS + (
        "attn.pairs_window", "attn.pairs_full", "attn.block_pairs",
        "attn.kernel_block_pairs", "attn.norm_rope_rows",
        "attn.norm_rope_kernel_rows", "moe.place_pairs_dense",
        "moe.place_pairs"),
    slots_a_token=2 * 4,        # 2 of 8 experts in each of 4 layers
    # its router (a softmax renormalised over the chosen) and the
    # expert layer that counts the placement are its own, and its task
    # adds the placement's counters to the frame's
    own=("load_config", "num_params", "route", "expert_layer"),
    overrides=("fit_counted",))


def _reference():
    name = "mellum_family_test_reference"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            ROOT, "benchmark", "families", "mellum", "reference.py"))
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


def test_eight_fused_clocks_through_the_app_are_the_references(
        task, ps_cfg, folded_app):
    """`StreamingPSApp.run_fused_bsp` — the entry, buffers, slab and
    folded programs the benchmark's cell drives — for 8 clocks from the
    stated start, against 8 reference rounds on what the buffers hold:
    parameters, the workers' losses a clock, and the server's
    evaluation row at clock 8."""
    ref = _reference()
    app = folded_app()
    cfg = dataclasses.replace(ps_cfg, num_workers=2)
    s = ref.shapes(cfg)
    theta0 = ref.init_params(s)
    assert np.array_equal(np.asarray(app.server.theta), theta0)
    slabs = [b.snapshot() for b in app.buffers]
    assert [int(m.sum()) for _, _, m in slabs] == [2, 2]
    want_t, want_l = ref.Reference(s).run(theta0, slabs, 8, keep_every=8)
    app.run_fused_bsp(max_server_iterations=8 * 2)
    assert app.server.iterations == 16
    got = np.asarray(app.server.theta)
    scale = 3 * 1e-5 * 8
    assert ref.param_gap(got, want_t[-1], theta0, s) <= scale
    assert np.max(np.abs(got - want_t[-1])) <= scale * np.max(
        np.abs(want_t[-1] - theta0))
    last = app.server.last_metrics
    want = ref.Reference(s).evaluate(want_t[-1], (rows_of(task, 2, seed=8),
                                                  None))
    assert float(last.loss) == pytest.approx(want["loss"], rel=scale)
    assert float(last.accuracy) == pytest.approx(want["accuracy"], abs=1e-6)
    assert want_l[-1] < want_l[0]               # and it learns

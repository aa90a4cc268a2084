"""The reader the `mellum` family brought (moe_placement_roofline_share)
on the hand-made trace and HLO text of tests/fixtures/self_time_tiny.json,
whose table tests/test_self_time.py knows by hand: a share known by hand
and under 100%, nothing (`None`, never a raise) where the counter, the
family's `placement_products` or the trace is missing — what the parent
of the PR that brought it, and every other family, gives; and the
family shrunk to its `tiny.json`."""

import importlib.util
import json
import os
import sys
import types

import pytest

import run as harness
from conftest import BENCH, ROOT
from helpers import tiny
from test_self_time_readers import texts, traced_run  # noqa: F401
from test_span_reduce import metric

MODEL = "benchmark/families/mellum/tiny.model.json"


def family_costs(name="mellum"):
    module = "family_" + name + "_costs"
    if module not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            module, os.path.join(BENCH, "families", name, "costs.py"))
        sys.modules[module] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[module])
    return sys.modules[module]


def of_the_family(run, counters=None, costs=None):
    """The run as one of the `mellum` family's at its tiny size: 1 row
    of 24 tokens a worker, k = 2."""
    run.family = types.SimpleNamespace(costs=costs or family_costs())
    run.cfg = types.SimpleNamespace(
        model=types.SimpleNamespace(model_json=MODEL, num_max_iter=2),
        buffer=types.SimpleNamespace(max_size=1), num_workers=4)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    if counters is not None:
        run.app.last_run["counters"] = counters
    return run


# 4 updates of 1 row x 24 tokens: 48 slots a layer, a quarter held, a
# bound of 24 rows x 24 tokens; 4 layers x 3 passes an update, as if the
# unit were one pair a count
COUNTERS = {"data.tokens": 4 * 24, "data.pad_tokens": 0,
            "moe.passes_over_bound": 0,
            "moe.place_pairs": 4 * 4 * 3 * 24 * 24}


def test_the_placements_roofline_share_is_known_by_hand(texts, capsys):
    read, spec = metric("moe_placement_roofline_share")
    assert spec["counter"] == "moe.place_pairs"
    assert set(spec["placement_scopes"]) < set(
        metric("moe_placement_self_share")[1]["placement_scopes"])
    costs = family_costs()
    run = of_the_family(traced_run(), dict(COUNTERS))
    got = read(run, spec)
    # place 1 s + combine 1 s + the cond's own 0.5 s of an update's 10
    # (the sort and the grouped product are not the 0/1 products')
    m = json.load(open(os.path.join(ROOT, MODEL)))
    flops, bytes_ = costs.placement_products(m, COUNTERS["moe.place_pairs"],
                                             1, 2)
    least = max(flops / 4 / 197e12, bytes_ / 4 / 819e9)
    assert got == pytest.approx(100.0 * least / 2.5) and 0 < got < 100
    # by hand: a unit is 1,024 pairs and weighs (4 x 2 + 2) / 3
    # products of 2 x 64 operations; each product reads the 0/1 matrix
    # (2 bytes a pair) and 24 + 24 float32 rows of 64
    products = COUNTERS["moe.place_pairs"] * 1024 * 10 / 3
    assert flops == pytest.approx(products * 2 * 64)
    assert bytes_ == pytest.approx(
        products * 2 + products / (24 * 24) * 4 * 64 * 48)
    out = capsys.readouterr().out
    assert "4 updates counted 27648 x 1024 (placed row, token) pairs" in out
    assert "2500.0000 ms an update" in out


@pytest.mark.parametrize("what", ["counter", "zero", "costs", "trace",
                                  "program"])
def test_nothing_to_read_reads_nothing(texts, what):
    read, spec = metric("moe_placement_roofline_share")
    counters, costs = dict(COUNTERS), None
    if what == "counter":               # the parent's program, any other's
        del counters["moe.place_pairs"]
    elif what == "zero":
        counters["moe.place_pairs"] = 0
    elif what == "costs":               # a family without the function
        costs = family_costs("afmoe")
        assert not hasattr(costs, "placement_products")
    run = of_the_family(traced_run(), counters, costs)
    if what == "trace":                 # --trace 0
        run.trace_dir = run.span_trace_data = None
    elif what == "program":             # no executable of that name alive
        del texts["jit_scanned"]
    assert read(run, spec) is None
    run.app = types.SimpleNamespace()   # no record of a drive call at all
    assert read(run, spec) is None


def test_the_family_shrinks_to_its_tiny_size():
    cell = "mellum2-12b-ep4.fused-bsp"
    loaded = harness.load_cell(cell)
    assert loaded["family"] == "mellum"
    shrink, data = tiny(cell, "4")
    assert shrink["--model_json"] == MODEL and data == {"test_rows": 3}
    body = json.load(open(os.path.join(ROOT, MODEL)))
    assert (body["num_hidden_layers"], body["sequence_length"],
            body["hidden_size"], body["num_attention_heads"],
            body["num_key_value_heads"], body["head_dim"],
            body["moe_intermediate_size"], body["num_experts"],
            body["experts_held"], body["num_experts_per_tok"],
            body["sliding_window"], body["vocab_held"]) == (
        4, 24, 64, 4, 2, 16, 32, 8, 2, 2, 8, 64)
    # the cell's own files: every published width, all 32 + 4 heads,
    # the window and both RoPE rules; depth, experts held and the
    # vocabulary's slice alone are cut
    real = json.load(open(os.path.join(ROOT, loaded["config"]["flags"][3])))
    assert list(loaded["config"]["reduced"]) == [
        "num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts",
        "vocab_size"]
    assert (real["hidden_size"], real["moe_intermediate_size"],
            real["head_dim"], real["num_attention_heads"],
            real["num_key_value_heads"], real["num_experts"],
            real["num_experts_per_tok"], real["experts_held"],
            real["sliding_window"], real["vocab_size"], real["vocab_held"],
            real["num_hidden_layers"], real["sequence_length"]) == (
        2304, 896, 128, 32, 4, 64, 8, 16, 1024, 98304, 24576, 4, 4096)
    assert real["rope_parameters"]["full_attention"]["factor"] == 16
    assert loaded["config"]["data"]["test_rows"] * real["sequence_length"] \
        == 8192
    assert loaded["config"]["num_params"] == 595_154_176
    assert "no routed token is dropped" in loaded["config"]["guarantees"]
    # the traffic's table, to the letter
    flags = loaded["config"]["flags"] + loaded["traffic"]["flags"]
    for flag, value in (("--num_workers", "4"), ("-min", "1"), ("-max", "1"),
                        ("--local_iterations", "2"), ("-c", "0"),
                        ("--eval_every", "8")):
        assert flags[flags.index(flag) + 1] == value
    assert "--fused" in flags
    assert loaded["config"]["data"] == {
        "rows_per_worker": 1, "test_rows": 2, "zipf_exponent": 1.0}
    assert loaded["traffic"]["check"]["clocks"] == 8

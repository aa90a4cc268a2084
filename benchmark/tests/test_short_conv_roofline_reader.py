"""The reader the `lfm2-moe` family brought (short_conv_roofline_share)
on the hand-made trace and HLO text of tests/fixtures/self_time_tiny.json,
whose table tests/test_self_time.py knows by hand, with the canned
program's placing product read as a convolution chain (its scope
renamed `kps.ssm.conv`, as `test_self_time_readers.py` renames it
`kps.mlp`): a share known by hand and under 100%, nothing (`None`, never
a raise) where the counter, the family's `short_conv_mix`, the scope or
the trace is missing — what the parent of the PR that brought it, and
every other family, gives; and the family shrunk to its `tiny.json`."""

import json
import os
import types

import pytest

import run as harness
from conftest import ROOT
from helpers import tiny
from test_placement_roofline_reader import family_costs
from test_self_time_readers import HLO, texts, traced_run  # noqa: F401
from test_span_reduce import metric

MODEL = "benchmark/families/lfm2-moe/tiny.model.json"
CHAINED = HLO.replace("kps.moe.place", "kps.ssm/kps.ssm.conv")


def of_the_family(run, counters=None, costs=None):
    """The run as one of the `lfm2-moe` family's at its tiny size: 1 row
    of 24 tokens a worker, k = 2."""
    run.family = types.SimpleNamespace(
        costs=costs or family_costs("lfm2-moe"))
    run.cfg = types.SimpleNamespace(
        model=types.SimpleNamespace(model_json=MODEL, num_max_iter=2),
        buffer=types.SimpleNamespace(max_size=1), num_workers=4)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    if counters is not None:
        run.app.last_run["counters"] = counters
    return run


# 4 updates of 1 row x 24 tokens: 4 conv layers x 3 passes an update x
# 24 positions, as if the unit were one position a count
COUNTERS = {"data.tokens": 4 * 24, "data.pad_tokens": 0,
            "conv.mix_rows": 4 * 4 * 3 * 24}


def test_the_chains_roofline_share_is_known_by_hand(texts, capsys):
    read, spec = metric("short_conv_roofline_share")
    assert spec["counter"] == "conv.mix_rows"
    assert spec["scopes"] == ["kps.ssm.conv"]
    texts["jit_scanned"] = [CHAINED]
    costs = family_costs("lfm2-moe")
    run = of_the_family(traced_run(), dict(COUNTERS))
    got = read(run, spec)
    # the renamed fusions' 1 s of an update's 10
    m = json.load(open(os.path.join(ROOT, MODEL)))
    flops, bytes_ = costs.short_conv_mix(m, COUNTERS["conv.mix_rows"], 1, 2)
    least = max(flops / 4 / 197e12, bytes_ / 4 / 819e9)
    assert least == bytes_ / 4 / 819e9          # the memory's
    assert got == pytest.approx(100.0 * least / 1.0) and 0 < got < 100
    # by hand: a unit is 1,024 positions of 64 float32 channels; of an
    # update's 3 passes 2 weigh 11 arrays and 1 weighs 4; the taps [64,
    # 3] are read a layer a pass and their gradient written in 2 of 3
    positions = COUNTERS["conv.mix_rows"] * 1024
    assert bytes_ == pytest.approx(
        4 * 64 * positions * (2 * 11 + 4) / 3
        + 4 * 64 * 3 * (positions / 24) * (1 + 2 / 3))
    assert flops == pytest.approx(7 * 64 * positions * (2 * 3 + 1) / 3)
    out = capsys.readouterr().out
    assert "4 updates counted 1152 x 1024 positions" in out
    assert "1000.0000 ms an update" in out


@pytest.mark.parametrize("what", ["counter", "zero", "costs", "scope",
                                  "trace", "program"])
def test_nothing_to_read_reads_nothing(texts, what):
    read, spec = metric("short_conv_roofline_share")
    texts["jit_scanned"] = [CHAINED]
    counters, costs = dict(COUNTERS), None
    if what == "counter":               # the parent's program, any other's
        del counters["conv.mix_rows"]
    elif what == "zero":
        counters["conv.mix_rows"] = 0
    elif what == "costs":               # a family without the function
        costs = family_costs("nemotron-h")
        assert not hasattr(costs, "short_conv_mix")
    elif what == "scope":               # a program without the chain
        texts["jit_scanned"] = [HLO]
    run = of_the_family(traced_run(), counters, costs)
    if what == "trace":                 # --trace 0
        run.trace_dir = run.span_trace_data = None
    elif what == "program":             # no executable of that name alive
        del texts["jit_scanned"]
    assert read(run, spec) is None
    run.app = types.SimpleNamespace()   # no record of a drive call at all
    assert read(run, spec) is None


def test_the_family_shrinks_to_its_tiny_size():
    cell = "lfm2-24b-a2b-ep8.fused-bsp"
    loaded = harness.load_cell(cell)
    assert loaded["family"] == "lfm2-moe"
    shrink, data = tiny(cell, "4")
    assert shrink["--model_json"] == MODEL and data == {"test_rows": 3}
    body = json.load(open(os.path.join(ROOT, MODEL)))
    assert (body["num_hidden_layers"], body["num_dense_layers"],
            body["sequence_length"], body["hidden_size"],
            body["num_attention_heads"], body["num_key_value_heads"],
            body["intermediate_size"], body["moe_intermediate_size"],
            body["num_experts"], body["experts_held"],
            body["num_experts_per_tok"], body["conv_L_cache"],
            body["vocab_held"]) == (5, 1, 24, 64, 4, 2, 96, 32, 8, 2, 2, 3,
                                    64)
    assert body["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                   "conv"]
    # the cell's own files: every published width, all 32 + 8 heads of
    # 64, the 3 taps and theta; depth, experts held and the vocabulary's
    # slice alone are cut
    real = json.load(open(os.path.join(ROOT, loaded["config"]["flags"][3])))
    assert list(loaded["config"]["reduced"]) == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    assert (real["hidden_size"], real["intermediate_size"],
            real["moe_intermediate_size"], real["num_attention_heads"],
            real["num_key_value_heads"], real["num_experts"],
            real["num_experts_per_tok"], real["experts_held"],
            real["conv_L_cache"], real["vocab_size"], real["vocab_held"],
            real["num_hidden_layers"], real["sequence_length"]) == (
        2048, 11776, 1536, 32, 8, 64, 4, 8, 3, 65536, 8192, 5, 4096)
    assert real["layer_types"] == body["layer_types"]
    assert real["rope_parameters"] == {"rope_theta": 1000000,
                                       "rope_type": "default"}
    assert loaded["config"]["data"]["test_rows"] * real["sequence_length"] \
        == 8192
    assert loaded["config"]["num_params"] == 469_285_248
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
    assert loaded["traffic"]["check"]["stride_clocks"] == 8
    assert loaded["traffic"]["window"]["probe_chunks"] == [1, 2]
    assert loaded["traffic"]["window_programs"] == ["^jit_scanned$"]

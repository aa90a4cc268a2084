"""The reader the `granite-hybrid` family brought
(dense_mlp_roofline_share) on the hand-made trace and HLO text of
tests/fixtures/self_time_tiny.json, whose table tests/test_self_time.py
knows by hand, with the canned program's placing product read as a
dense MLP beside the one fusion it has under `kps.mlp` (the scope
renamed, as `test_self_time_readers.py` renames it): a share known by
hand and under 100%, nothing (`None`,
never a raise) where the counter, the family's `dense_mlp`, the scope
or the trace is missing — what the parent of the PR that brought it,
and every other family, gives; and the family shrunk to its
`tiny.json`."""

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

MODEL = "benchmark/families/granite-hybrid/tiny.model.json"
DENSE = HLO.replace("kps.moe.place", "kps.mlp")


def of_the_family(run, counters=None, costs=None):
    """The run as one of the `granite-hybrid` family's at its tiny size:
    1 row of 32 tokens a worker, k = 2."""
    run.family = types.SimpleNamespace(
        costs=costs or family_costs("granite-hybrid"))
    run.cfg = types.SimpleNamespace(
        model=types.SimpleNamespace(model_json=MODEL, num_max_iter=2),
        buffer=types.SimpleNamespace(max_size=1), num_workers=4)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    if counters is not None:
        run.app.last_run["counters"] = counters
    return run


# 4 updates of 1 row x 32 tokens: 10 layers x 3 passes an update x 32
# positions, as if the unit were one position a count
COUNTERS = {"data.tokens": 4 * 32, "data.pad_tokens": 0,
            "mlp.rows": 4 * 10 * 3 * 32}


def test_the_mlps_roofline_share_is_known_by_hand(texts, capsys):
    read, spec = metric("dense_mlp_roofline_share")
    assert spec["counter"] == "mlp.rows"
    assert spec["scopes"] == ["kps.mlp"]
    texts["jit_scanned"] = [DENSE]
    costs = family_costs("granite-hybrid")
    run = of_the_family(traced_run(), dict(COUNTERS))
    got = read(run, spec)
    # the renamed fusions' 1 s and the program's own 0.25 s under
    # `kps.mlp`, of an update's 10
    m = json.load(open(os.path.join(ROOT, MODEL)))
    flops, bytes_ = costs.dense_mlp(m, COUNTERS["mlp.rows"], 1, 2)
    least = max(flops / 4 / 197e12, bytes_ / 4 / 819e9)
    assert got == pytest.approx(100.0 * least / 1.25) and 0 < got < 100
    # by hand: a unit is 1,024 positions; hidden 64, width 96; of an
    # update's 3 passes 2 weigh three forwards and 1 weighs one; the
    # rows are read or written 5 times in a gradient pass and twice in
    # a forward one, the three matrices three times and once a layer a
    # pass (a layer's pass is 32 positions)
    positions = COUNTERS["mlp.rows"] * 1024
    assert flops == pytest.approx(2 * 3 * 64 * 96 * positions * (2 * 3 + 1) / 3)
    assert bytes_ == pytest.approx(
        4 * 64 * positions * (2 * 5 + 2) / 3
        + 4 * 3 * 64 * 96 * (positions / 32) * (2 * 3 + 1) / 3)
    out = capsys.readouterr().out
    assert "4 updates counted 3840 x 1024 positions" in out
    assert "1250.0000 ms an update" in out


def test_at_the_cells_size_the_products_are_the_mxus():
    """2,048 positions a layer a pass at hidden 2048 and width 8192: the
    least is the MXU's, 73.3 ms an update."""
    costs = family_costs("granite-hybrid")
    m = json.load(open(os.path.join(
        ROOT, "benchmark/configs/granite-4.0-h-micro-pp4.model.json")))
    flops, bytes_ = costs.dense_mlp(m, 10 * 3 * 2048 // costs.ROWS_UNIT, 1, 2)
    assert flops / 197e12 == pytest.approx(73.25e-3, rel=1e-3)
    assert bytes_ / 819e9 == pytest.approx(19.67e-3, rel=1e-3)


@pytest.mark.parametrize("what", ["counter", "zero", "costs", "scope",
                                  "trace", "program"])
def test_nothing_to_read_reads_nothing(texts, what):
    read, spec = metric("dense_mlp_roofline_share")
    texts["jit_scanned"] = [DENSE]
    counters, costs = dict(COUNTERS), None
    if what == "counter":               # the parent's program, any other's
        del counters["mlp.rows"]
    elif what == "zero":
        counters["mlp.rows"] = 0
    elif what == "costs":               # a family without the function
        costs = family_costs("ouro")
        assert not hasattr(costs, "dense_mlp")
    elif what == "scope":               # a program without a dense MLP
        texts["jit_scanned"] = [HLO.replace("kps.mlp", "kps.moe.shared")]
    run = of_the_family(traced_run(), counters, costs)
    if what == "trace":                 # --trace 0
        run.trace_dir = run.span_trace_data = None
    elif what == "program":             # no executable of that name alive
        del texts["jit_scanned"]
    assert read(run, spec) is None
    run.app = types.SimpleNamespace()   # no record of a drive call at all
    assert read(run, spec) is None


def test_the_family_shrinks_to_its_tiny_size():
    cell = "granite-4.0-h-micro-pp4.fused-bsp"
    loaded = harness.load_cell(cell)
    assert loaded["family"] == "granite-hybrid"
    shrink, data = tiny(cell, "4")
    assert shrink["--model_json"] == MODEL and data == {"test_rows": 3}
    body = json.load(open(os.path.join(ROOT, MODEL)))
    assert (body["num_hidden_layers"], body["sequence_length"],
            body["hidden_size"], body["num_attention_heads"],
            body["num_key_value_heads"], body["shared_intermediate_size"],
            body["mamba_n_heads"], body["mamba_d_head"],
            body["mamba_d_state"], body["mamba_n_groups"],
            body["mamba_chunk_size"], body["mamba_d_conv"],
            body["vocab_size"], body["vocab_held"]) == (
        10, 32, 64, 4, 2, 96, 8, 16, 16, 1, 8, 4, 64, 16)
    # the cell's own files: every published width, all 32 + 8 heads of
    # 64, 64 scan heads of 64 at a state of 128 in ONE group, chunks of
    # 256 and the four multipliers; depth, the vocabulary's slice and
    # the row length alone are cut
    real = json.load(open(os.path.join(ROOT, loaded["config"]["flags"][3])))
    assert list(loaded["config"]["reduced"]) == [
        "num_hidden_layers", "layer_types", "vocab_size", "sequence_length"]
    assert (real["hidden_size"], real["shared_intermediate_size"],
            real["num_attention_heads"], real["num_key_value_heads"],
            real["mamba_n_heads"], real["mamba_d_head"],
            real["mamba_d_state"], real["mamba_n_groups"],
            real["mamba_chunk_size"], real["mamba_d_conv"],
            real["vocab_size"], real["vocab_held"],
            real["num_hidden_layers"]) == (
        2048, 8192, 32, 8, 64, 64, 128, 1, 256, 4, 100352, 25088, 10)
    assert real["layer_types"] == body["layer_types"]
    for key in ("attention_multiplier", "embedding_multiplier",
                "residual_multiplier", "logits_scaling"):
        assert real[key] == body[key], key
    assert loaded["config"]["data"]["test_rows"] * real["sequence_length"] \
        == 8192
    assert loaded["config"]["num_params"] == 797_850_560
    assert len(loaded["config"]["guarantees"]) == 4
    # the traffic's table, to the letter
    flags = loaded["config"]["flags"] + loaded["traffic"]["flags"]
    for flag, value in (("--num_workers", "4"), ("-min", "1"), ("-max", "1"),
                        ("--local_iterations", "2"), ("-c", "0"),
                        ("--eval_every", "8")):
        assert flags[flags.index(flag) + 1] == value
    assert "--fused" in flags
    assert loaded["config"]["data"]["rows_per_worker"] == 1
    assert loaded["config"]["data"]["zipf_exponent"] == 1.0
    assert loaded["traffic"]["check"]["clocks"] == 8
    assert loaded["traffic"]["check"]["stride_clocks"] == 8
    assert loaded["traffic"]["window"]["probe_chunks"] == [1, 2]
    assert loaded["traffic"]["window_programs"] == ["^jit_scanned$"]

"""The reader the `ouro` family brought (layer_loop_self_share) on the
hand-made trace and HLO text of tests/fixtures/self_time_tiny.json with
two of its instructions moved under `kps.lm.layers` alone: a share
known by hand and under 100%, the compiler's own copy ADOPTED by the
loop's scope counted with it, nothing (`None`, never a raise) where the
scope or the trace is missing; the accepted
window_attention_roofline_share on a synthetic run of the new family,
whose `attn.pairs_window` reads 0; and the family shrunk to its
`tiny.json`."""

import importlib.util
import json
import os
import sys
import types

import pytest

import run as harness
import self_time
from conftest import BENCH, ROOT
from helpers import tiny
from test_span_reduce import metric
from test_self_time_readers import HLO, traced_run
from test_window_attention_readers import tables, traced  # noqa: F401
from test_span_reduce import fake_run

MODEL = "benchmark/families/ouro/tiny.model.json"
# the block norm's and the dense MLP's fusions (0.25 s each of an
# update's 10) moved under the loop's scope alone, as its residual adds
# and its gradient's sum are; the unnamed copy both read (0.5 s) then
# has users that agree, and the loop's scope adopts it
LOOPED = (HLO.replace("checkpoint/kps.lm.norm/mul",
                      "jvp(kps.lm.layers)/while/body/add")
          .replace("checkpoint/kps.mlp/dot_general",
                   "transpose(jvp(kps.lm.layers))/while/body/add_any"))


def family_costs():
    name = "family_ouro_costs"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, "families", "ouro", "costs.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def of_the_family(run, counters=None):
    """The run as one of the `ouro` family's at its tiny size: 1 row of
    24 tokens a worker, k = 2."""
    run.family = types.SimpleNamespace(costs=family_costs())
    run.cfg = types.SimpleNamespace(
        model=types.SimpleNamespace(model_json=MODEL, num_max_iter=2),
        buffer=types.SimpleNamespace(max_size=1), num_workers=4)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    if counters is not None:
        run.app.last_run["counters"] = counters
    return run


@pytest.fixture
def texts(monkeypatch):
    table = {"jit_scanned": [LOOPED]}
    monkeypatch.setattr(self_time, "hlo_texts", lambda patterns: table)
    return table


def test_the_loops_share_is_known_by_hand_with_what_it_adopted(texts, capsys):
    read, spec = metric("layer_loop_self_share")
    assert spec["scope"] == "kps.lm.layers"
    assert spec["scope"] in self_time.table_spec()["scopes"]
    # 3 layers x 4 steps x 3 passes a row an update, 4 updates
    run = of_the_family(traced_run(), {
        "data.tokens": 4 * 24, "data.pad_tokens": 0,
        "lm.layer_passes": 4 * 36})
    # own 0.25 + 0.25, adopted 0.5 (copy.7), of an update's 10 s
    got = read(run, spec)
    assert got == pytest.approx(10.0) and 0 < got < 100
    found = run.self_time_table["by_scope_s"]["kps.lm.layers"]
    assert found["own"] == pytest.approx(3 * 0.5)
    assert found["adopted"] == pytest.approx(3 * 0.5)
    out = capsys.readouterr().out
    assert "lm.layer_passes 144 over 4 updates, 36.00 layer applications" \
        in out
    assert "500.0000 ms own + 500.0000 ms adopted" in out
    # what the loop adopted is no longer unnamed: fusion.15's 0.3 s is
    read, spec = metric("lm_unnamed_self_share")
    assert read(run, spec) == pytest.approx(3.0)


def test_the_share_is_read_without_the_counter(texts, capsys):
    read, spec = metric("layer_loop_self_share")
    run = of_the_family(traced_run())
    assert read(run, spec) == pytest.approx(10.0)
    assert "layer_loop_self_share:" not in capsys.readouterr().out


def test_nothing_without_the_scope_or_a_trace(texts):
    read, spec = metric("layer_loop_self_share")
    run = of_the_family(traced_run())
    run.trace_dir = run.span_trace_data = None          # --trace 0
    assert read(run, spec) is None
    # a program whose layers are no loop: the other families', the
    # parent's
    texts["jit_scanned"] = [HLO]
    assert "kps.lm.layers" not in HLO
    assert read(of_the_family(traced_run()), spec) is None
    # no executable of that name alive
    del texts["jit_scanned"]
    assert read(of_the_family(traced_run()), spec) is None


def test_the_accepted_core_roofline_reads_a_family_with_no_sliding_layer(
        tables, capsys):
    """`window_attention_roofline_share` on a synthetic run of the new
    family: `attn.pairs_window` 0, every pair a full layer's, the
    operations and bytes the family's own `attention_core` — a layer
    APPLICATION counts."""
    read, spec = metric("window_attention_roofline_share")
    costs = family_costs()
    # 4 updates x 3 passes x 12 applications x 300 pairs, as if the unit
    # were one pair a count
    counters = {"data.tokens": 4 * 24, "data.pad_tokens": 0,
                "attn.pairs_window": 0, "attn.pairs_full": 12 * 12 * 300,
                "attn.block_pairs": 12 * 12 * 384,
                "lm.layer_passes": 4 * 36}
    app = types.SimpleNamespace(last_run={
        "path": "fused", "seconds": 40.0, "counters": counters})
    run = of_the_family(fake_run(traced(), window_from="device_ops",
                                 app=app))
    got = read(run, spec)
    flops, bytes_ = costs.attention_core(run.cfg, 0, 12 * 12 * 300)
    least = max(flops / 4 / 197e12, bytes_ / 4 / 819e9)
    # the two cores hold 4 of the program's 10 traced seconds; the call
    # took 40 s for 4 updates: 4 s of core an update
    assert got == pytest.approx(100.0 * least / 4.0) and 0 < got < 100
    assert "4 updates counted 0 + 43200 x 1024 pairs" in \
        capsys.readouterr().out
    # by hand: a unit is 1,024 pairs and weighs 7/3 forward pairs, each
    # 4 x 16 operations on each of 4 query heads; the pairs stand for
    # row passes of 24 tokens x 4 x 16 x (2 x 4 + 2 x 4) bytes
    forward = 1024 * 7 / 3
    assert flops == pytest.approx(43200 * forward * 4 * 16 * 4)
    assert bytes_ == pytest.approx(43200 / 300 * forward * 24 * 4 * 16 * 16)
    # the cell's own arithmetic: 26.0 TFLOP an update, of which the
    # core 3.7%, the head 5.6%
    m = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                    "ouro-2.6b.model.json")))
    total = 7 * 1024 * costs.forward_flops_per_token(m)
    core = 7 * 32 * costs.pairs_in_mask(1024) * costs.core_flops_per_pair(m)
    assert total == pytest.approx(26.0e12, rel=0.005)
    assert core / total == pytest.approx(0.037, abs=0.001)
    assert 7 * 1024 * 2.0 * 2048 * 49152 / total == pytest.approx(
        0.056, abs=0.001)


def test_the_family_shrinks_to_its_tiny_size():
    cell = "ouro-2.6b.fused-bsp"
    loaded = harness.load_cell(cell)
    assert loaded["family"] == "ouro"
    shrink, data = tiny(cell, "4")
    assert shrink["--model_json"] == MODEL and data == {"test_rows": 3}
    body = json.load(open(os.path.join(ROOT, MODEL)))
    assert (body["num_hidden_layers"], body["total_ut_steps"],
            body["sequence_length"], body["hidden_size"],
            body["num_attention_heads"], body["num_key_value_heads"],
            body["head_dim"], body["intermediate_size"],
            body["vocab_size"]) == (3, 4, 24, 64, 4, 4, 16, 176, 64)
    # the cell's own files: every published width, 16 + 16 heads, all 4
    # steps and the whole vocabulary; the depth alone is cut
    real = json.load(open(os.path.join(
        ROOT, loaded["config"]["flags"][3])))
    assert list(loaded["config"]["reduced"]) == ["num_hidden_layers",
                                                 "layer_types"]
    assert (real["hidden_size"], real["intermediate_size"], real["head_dim"],
            real["num_attention_heads"], real["num_key_value_heads"],
            real["total_ut_steps"], real["vocab_size"], real["vocab_held"],
            real["num_hidden_layers"], real["sequence_length"]) == (
        2048, 5632, 128, 16, 16, 4, 49152, 49152, 8, 1024)
    assert loaded["config"]["data"]["test_rows"] * real["sequence_length"] \
        == 8192
    assert "no routed token is dropped" not in loaded["config"]["guarantees"]

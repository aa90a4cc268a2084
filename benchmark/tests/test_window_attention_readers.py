"""The two readers the `afmoe` family brought (window_attention_share,
window_attention_roofline_share) on a hand-made trace where every share
is known by hand: a share under 100%, nothing (`None`, never a raise)
where the scope or the counter is missing — what a program without
them, such as the parent of the PR that brought them, gives — and a
kernel's call found by its name where the compiler drops the scope."""

import importlib.util
import os
import sys
import types

import pytest

import span_reduce
from conftest import BENCH
from test_span_reduce import chip, fake_run, metric
from test_trace_reduce import _Data

MODEL = "benchmark/families/afmoe/tiny.model.json"


def family_costs():
    name = "family_afmoe_costs"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, "families", "afmoe", "costs.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def traced():
    """One run of the folded chunk over [0, 10): the projections 2 s, a
    sliding layer's core 3 s (a product of 2 s, the softmax 1 s), the
    full layer's core 1 s, a reshape under kps.attn alone 0.5 s, the
    dense MLP 1 s, a gradient fusion outside attention 1.5 s, a kernel
    call that carries no scope 0.5 s, an unnamed copy 0.5 s."""
    ops = [("%while.1 = (s32[]) while(...)", 0.0, 10.0),
           ("%fusion.1 = f32[24,64] fusion(...)", 0.0, 2.0),
           ("%fusion.2 = f32[2,2,8,16] fusion(...)", 2.0, 4.0),
           ("%fusion.3 = f32[2,2,8,16] fusion(...)", 4.0, 5.0),
           ("%fusion.4 = f32[2,2,8,24] fusion(...)", 5.0, 6.0),
           ("%bitcast.5 = f32[24,64] bitcast(...)", 6.0, 6.5),
           ("%fusion.6 = f32[24,96] fusion(...)", 6.5, 7.5),
           ("%fusion.7 = f32[64,64] fusion(...)", 7.5, 9.0),
           ("%attn-core.8 = f32[24,64] custom-call(...)", 9.0, 9.5),
           ("%copy.9 = f32[8] copy(...)", 9.5, 10.0)]
    return _Data([chip("/device:TPU:0", ops, [("jit_scanned(3)", 0.0, 10.0)])])


TABLES = {"jit_scanned": [{
    "fusion.1": "jit(scanned)/kps.fit.grad/kps.attn/kps.attn.proj/dot_general",
    "fusion.2": "jit(scanned)/kps.fit.grad/kps.attn/kps.attn.window/"
                "checkpoint/dot_general",
    "fusion.3": "jit(scanned)/kps.fit.grad/kps.attn/kps.attn.window/exp",
    "fusion.4": "jit(scanned)/kps.fit.grad/kps.attn/kps.attn.full/exp",
    "bitcast.5": "jit(scanned)/kps.fit.grad/kps.attn/reshape",
    "fusion.6": "jit(scanned)/kps.fit.grad/kps.mlp/dot_general",
    "fusion.7": "jit(scanned)/kps.fit.grad/kps.moe.shared/dot_general",
    "attn-core.8": "attn-core"}]}


@pytest.fixture
def tables():
    real = span_reduce.executables_op_names
    table = {k: [dict(t) for t in v] for k, v in TABLES.items()}
    span_reduce.executables_op_names = lambda patterns: table
    yield table
    span_reduce.executables_op_names = real


def run_with(counters, seconds=40.0):
    """A traced run whose window call made 4 updates of 1 row of the
    tiny model (24 tokens, a window of 8; 4 sliding layers and 1 full;
    k = 2)."""
    app = types.SimpleNamespace(last_run={
        "path": "fused", "seconds": seconds, "counters": counters})
    run = fake_run(traced(), window_from="device_ops", app=app)
    run.family = types.SimpleNamespace(costs=family_costs())
    run.cfg = types.SimpleNamespace(
        model=types.SimpleNamespace(model_json=MODEL, num_max_iter=2),
        buffer=types.SimpleNamespace(max_size=1), num_workers=4)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    return run


# 4 updates x 3 passes of (4 sliding layers x 164, 1 full layer x 300,
# blocks 4 x 320 + 384) pairs, as if the unit were one pair a count
COUNTERS = {"data.tokens": 4 * 24, "data.pad_tokens": 0,
            "attn.pairs_window": 12 * 4 * 164, "attn.pairs_full": 12 * 300,
            "attn.block_pairs": 12 * (4 * 320 + 384)}


def test_window_attention_share_sums_the_two_kinds_of_core(tables, capsys):
    read, spec = metric("window_attention_share")
    assert spec["scopes"][:5] == ["kps.attn.window", "kps.attn.full",
                                  "kps.attn.proj", "kps.attn", "kps.mlp"]
    # the sliding core 3 + the full core 1, of the program's 10 s
    assert read(run_with(COUNTERS), spec) == pytest.approx(40.0)
    out = capsys.readouterr().out
    assert '"kps.attn.window": 30.0' in out and '"kps.attn.full": 10.0' in out
    assert '"kps.attn.proj": 20.0' in out and '"kps.attn": 5.0' in out
    assert '"kps.mlp": 10.0' in out and '"(no scope)": 10.0' in out
    # a program with sliding layers only reads those
    tables["jit_scanned"][0]["fusion.4"] = "jit(scanned)/kps.fit.grad/exp"
    assert read(run_with(COUNTERS), spec) == pytest.approx(30.0)


def test_window_attention_share_finds_nothing_without_the_scope(tables):
    read, spec = metric("window_attention_share")
    # the other language models' programs: kps.attn, but no core's scope
    tables["jit_scanned"] = [{"fusion.2": "jit(scanned)/kps.attn/dot",
                              "fusion.7": "jit(scanned)/kps.moe.shared/dot"}]
    assert read(run_with(COUNTERS), spec) is None
    # a program with no scope at all, and a run with no trace
    tables["jit_scanned"] = [{"fusion.7": "jit(scanned)/dot_general"}]
    assert read(run_with(COUNTERS), spec) is None
    run = run_with(COUNTERS)
    run.trace_dir = run.span_trace_data = None
    assert read(run, spec) is None


def test_window_attention_roofline_share_is_known_by_hand(tables, capsys):
    read, spec = metric("window_attention_roofline_share")
    costs = family_costs()
    run = run_with(COUNTERS)
    got = read(run, spec)
    # the two cores hold 4 of the program's 10 traced seconds; the call
    # took 40 s for 4 updates: 4 s of core an update
    flops, bytes_ = costs.attention_core(
        run.cfg, COUNTERS["attn.pairs_window"], COUNTERS["attn.pairs_full"])
    least = max(flops / 4 / 197e12, bytes_ / 4 / 819e9)
    assert got == pytest.approx(100.0 * least / 4.0)
    assert 0 < got < 100
    out = capsys.readouterr().out
    assert "4 updates counted 7872 + 3600 x 1024 pairs" in out
    assert "(1.7406 of them)" in out           # 19968 / 11472
    # by hand: a counted unit is 1,024 pairs and weighs 7/3 forward
    # pairs, each 4 x 16 operations on each of 4 query heads; the pairs
    # stand for row passes of 24 tokens x 4 x 16 x (2 x 4 + 2 x 2) bytes
    forward = 1024 * 7 / 3
    assert flops == pytest.approx((7872 + 3600) * forward * 4 * 16 * 4)
    assert bytes_ == pytest.approx(
        (7872 / 164 + 3600 / 300) * forward * 24 * 4 * 16 * 12)


@pytest.mark.parametrize("counters", [
    None, {}, {"data.tokens": 96, "data.pad_tokens": 0},
    {"data.tokens": 96, "data.pad_tokens": 0, "attn.pairs_window": 0,
     "attn.pairs_full": 0, "attn.block_pairs": 0}])
def test_the_roofline_share_without_the_counters_reads_nothing(tables,
                                                                counters):
    read, spec = metric("window_attention_roofline_share")
    assert read(run_with(counters), spec) is None


def test_the_roofline_share_without_the_scope_reads_nothing(tables):
    read, spec = metric("window_attention_roofline_share")
    for table in tables["jit_scanned"]:
        for name in ("fusion.2", "fusion.3", "fusion.4"):
            table[name] = "jit(scanned)/kps.fit.grad/dot_general"
    assert read(run_with(COUNTERS), spec) is None
    # a family whose costs know no core, and an app that keeps no record
    run = run_with(COUNTERS)
    run.family = types.SimpleNamespace(costs=types.SimpleNamespace())
    assert read(run, spec) is None
    run = run_with(COUNTERS)
    run.app = types.SimpleNamespace()
    assert read(run, spec) is None


def test_a_kernels_call_is_found_where_the_compiler_drops_the_scope(tables):
    """A core that is one kernel leaves no operation under the scopes;
    `kernel_scopes` names its call, as `ragged-dot` is named for the
    grouped products."""
    read, spec = metric("window_attention_roofline_share")
    assert spec["kernel_scopes"] == []          # the core is jax.numpy
    for table in tables["jit_scanned"]:
        for name in ("fusion.2", "fusion.3", "fusion.4"):
            table[name] = "jit(scanned)/kps.fit.grad/dot_general"
    named = dict(spec, kernel_scopes=["attn-core"],
                 scopes=["attn-core", *spec["scopes"]])
    got = read(run_with(COUNTERS), named)
    costs = family_costs()
    flops, bytes_ = costs.attention_core(
        run_with(COUNTERS).cfg, COUNTERS["attn.pairs_window"],
        COUNTERS["attn.pairs_full"])
    least = max(flops / 4 / 197e12, bytes_ / 4 / 819e9)
    # the call holds 0.5 of the 10 traced seconds: 0.5 s an update
    assert got == pytest.approx(100.0 * least / 0.5)

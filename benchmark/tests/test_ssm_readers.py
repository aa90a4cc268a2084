"""The two readers the `nemotron-h` family brought (ssm_share,
ssm_scan_roofline_share) on a hand-made trace where every share is
known by hand: a share under 100%, and nothing (`None`, never a raise)
where the scope or the counter is missing — what a program without
them, such as the parent of the PR that brought them, gives."""

import importlib.util
import os
import sys
import types

import pytest

import span_reduce
from conftest import BENCH
from test_span_reduce import chip, fake_run, metric
from test_trace_reduce import _Data

MODEL = "benchmark/families/nemotron-h/tiny.model.json"


def family_costs():
    name = "family_nemotron_h_costs"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, "families", "nemotron-h", "costs.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def traced():
    """One run of the folded chunk over [0, 10): the in-projection 2 s,
    the convolution 1 s, the scan 3 s (one fusion of 2 s, one of 1 s),
    a slice under kps.ssm alone 0.5 s, attention 1 s, a gradient
    fusion outside the mixers 2 s, an unnamed copy 0.5 s."""
    ops = [("%while.1 = (s32[]) while(...)", 0.0, 10.0),
           ("%fusion.1 = f32[16,64] fusion(...)", 0.0, 2.0),
           ("%fusion.2 = f32[16,96] fusion(...)", 2.0, 3.0),
           ("%fusion.3 = f32[4,4,8,8] fusion(...)", 3.0, 5.0),
           ("%fusion.4 = f32[16,8,8] fusion(...)", 5.0, 6.0),
           ("%slice.5 = f32[16,64] slice(...)", 6.0, 6.5),
           ("%fusion.6 = f32[16,16] fusion(...)", 6.5, 7.5),
           ("%fusion.7 = f32[64,64] fusion(...)", 7.5, 9.5),
           ("%copy.8 = f32[8] copy(...)", 9.5, 10.0)]
    return _Data([chip("/device:TPU:0", ops, [("jit_scanned(3)", 0.0, 10.0)])])


TABLES = {"jit_scanned": [{
    "fusion.1": "jit(scanned)/kps.fit.grad/kps.ssm/kps.ssm.proj/dot_general",
    "fusion.2": "jit(scanned)/kps.fit.grad/kps.ssm/kps.ssm.conv/mul",
    "fusion.3": "jit(scanned)/kps.fit.grad/kps.ssm/kps.ssm.scan/dot_general",
    "fusion.4": "jit(scanned)/kps.fit.grad/kps.ssm/kps.ssm.scan/while",
    "slice.5": "jit(scanned)/kps.fit.grad/kps.ssm/slice",
    "fusion.6": "jit(scanned)/kps.fit.grad/kps.attn/dot_general",
    "fusion.7": "jit(scanned)/kps.fit.grad/kps.moe.shared/dot_general"}]}


@pytest.fixture
def tables():
    real = span_reduce.executables_op_names
    table = {k: [dict(t) for t in v] for k, v in TABLES.items()}
    span_reduce.executables_op_names = lambda patterns: table
    yield table
    span_reduce.executables_op_names = real


def run_with(counters, seconds=40.0):
    """A traced run whose window call made 4 updates of 1 row of the
    tiny model (16 tokens, 4 chunks of 4; 2 Mamba-2 blocks; k = 2)."""
    app = types.SimpleNamespace(last_run={
        "path": "fused", "seconds": seconds, "counters": counters})
    run = fake_run(traced(), window_from="device_ops", app=app)
    run.family = types.SimpleNamespace(costs=family_costs())
    run.cfg = types.SimpleNamespace(
        model=types.SimpleNamespace(model_json=MODEL, num_max_iter=2),
        buffer=types.SimpleNamespace(max_size=1), num_workers=4)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    return run


COUNTERS = {"data.tokens": 4 * 16, "data.pad_tokens": 0,
            "ssm.chunks": 4 * 3 * 4 * 2}


def test_ssm_share_sums_the_mixers_parts(tables, capsys):
    read, spec = metric("ssm_share")
    assert spec["scopes"][:6] == ["kps.ssm.scan", "kps.ssm.conv",
                                  "kps.ssm.proj", "kps.ssm.norm", "kps.ssm",
                                  "kps.attn"]
    # proj 2 + conv 1 + scan 3 + what is left under kps.ssm alone 0.5,
    # of the program's 10 s
    assert read(run_with(COUNTERS), spec) == pytest.approx(65.0)
    out = capsys.readouterr().out
    assert '"kps.ssm.scan": 30.0' in out and '"kps.attn": 10.0' in out
    assert '"kps.moe.shared": 20.0' in out and '"(no scope)": 5.0' in out


def test_ssm_share_finds_nothing_in_a_program_without_the_scope(tables):
    read, spec = metric("ssm_share")
    # the other language model's program: scopes, but no mixer's
    tables["jit_scanned"] = [{"fusion.7": "jit(scanned)/kps.moe.shared/dot"}]
    assert read(run_with(COUNTERS), spec) is None
    # a program with no scope at all, and a run with no trace
    tables["jit_scanned"] = [{"fusion.7": "jit(scanned)/dot_general"}]
    assert read(run_with(COUNTERS), spec) is None
    run = run_with(COUNTERS)
    run.trace_dir = run.span_trace_data = None
    assert read(run, spec) is None


def test_ssm_scan_roofline_share_is_known_by_hand(tables, capsys):
    read, spec = metric("ssm_scan_roofline_share")
    costs = family_costs()
    run = run_with(COUNTERS)
    got = read(run, spec)
    # the scan holds 3 of the program's 10 traced seconds; the call took
    # 40 s for 4 updates: 3 s of scan an update
    flops, bytes_ = costs.ssm_scan(run.cfg, COUNTERS["ssm.chunks"])
    least = max(flops / 4 / 197e12, bytes_ / 4 / 819e9)
    assert got == pytest.approx(100.0 * least / 3.0)
    assert 0 < got < 100
    assert "4 updates counted 96 scan chunks" in capsys.readouterr().out
    # by the recurrence itself: 96 counted chunks weigh 7/3 forward
    # chunks each, of 4 tokens, 8 heads of 5 * 8 * 16 + 3 * 8 operations
    assert flops == pytest.approx(96 * 7 / 3 * 4 * 8 * (5 * 8 * 16 + 3 * 8))
    assert bytes_ == pytest.approx(96 * 7 / 3 * 4 * 4 * (2 * 64 + 2 * 32 + 8))


@pytest.mark.parametrize("counters", [
    None, {}, {"data.tokens": 64, "data.pad_tokens": 0},
    {"data.tokens": 64, "data.pad_tokens": 0, "ssm.chunks": 0}])
def test_ssm_scan_roofline_share_without_the_counter_reads_nothing(
        tables, counters):
    read, spec = metric("ssm_scan_roofline_share")
    assert read(run_with(counters), spec) is None


def test_ssm_scan_roofline_share_without_the_scope_reads_nothing(tables):
    read, spec = metric("ssm_scan_roofline_share")
    for table in tables["jit_scanned"]:
        for name in ("fusion.3", "fusion.4"):
            table[name] = "jit(scanned)/kps.fit.grad/dot_general"
    assert read(run_with(COUNTERS), spec) is None
    # a family whose costs know no scan, and an app that keeps no record
    run = run_with(COUNTERS)
    run.family = types.SimpleNamespace(costs=types.SimpleNamespace())
    assert read(run, spec) is None
    run = run_with(COUNTERS)
    run.app = types.SimpleNamespace()
    assert read(run, spec) is None

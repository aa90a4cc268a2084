"""The three readers of the self-time PR (lm_unnamed_self_share,
moe_placement_self_share, fused_call_edge_share) on the hand-made trace
and HLO text of tests/fixtures/self_time_tiny.json, whose table
tests/test_self_time.py knows by hand: a number there; nothing (`None`,
never a raise) on an untraced run and on a program without the scopes
or the counters — what the parent of the PR that brought them gives."""

import json
import os
import types

import pytest

import self_time
from conftest import BENCH, ROOT
from test_span_reduce import chip, fake_run, metric
from test_trace_reduce import _Data

TINY = json.load(open(os.path.join(ROOT, "tests", "fixtures",
                                   "self_time_tiny.json")))
HLO = "\n".join(TINY["hlo"])
LAST_RUN = {"path": "fused", "seconds": 20.0, "slab_refresh_s": 0.01,
            "theta_up_s": 0.5, "device_wait_s": 17.0, "theta_down_s": 0.75}


def traced_run():
    app = types.SimpleNamespace(last_run=dict(LAST_RUN))
    return fake_run(_Data([chip("/device:TPU:0", TINY["events"],
                                TINY["modules"])]),
                    window_from="device_ops", app=app)


@pytest.fixture
def texts(monkeypatch):
    """What the backend's live executables would hand out."""
    table = {"jit_scanned": [HLO]}
    asked = []

    def hlo_texts(patterns):
        asked.append(patterns)
        return table
    monkeypatch.setattr(self_time, "hlo_texts", hlo_texts)
    table["asked"] = asked
    return table


def test_the_unnamed_share_is_known_by_hand(texts, capsys):
    read, spec = metric("lm_unnamed_self_share")
    # copy.7 0.5 s and fusion.15 0.3 s of an update's 10
    assert read(traced_run(), spec) == pytest.approx(8.0)
    out = capsys.readouterr().out
    assert out.count("[bench] self time by scope") == 1
    assert '"(no scope)": 500.0' in out and '"kps.fit.grad alone": 300.0' in out


def test_the_placement_share_is_known_by_hand(texts):
    read, spec = metric("moe_placement_self_share")
    # place 1 + combine 1 + the cond's own 0.5 of an update's 10; the
    # grouped product (1.5, `ragged-dot`) is not the placement's
    assert read(traced_run(), spec) == pytest.approx(25.0)


def test_one_table_a_run_whoever_asks(texts, capsys):
    run = traced_run()
    for name in ("moe_placement_self_share", "lm_unnamed_self_share"):
        read, spec = metric(name)
        assert read(run, spec) is not None
    assert len(texts["asked"]) == 1
    assert capsys.readouterr().out.count("[bench] self time by scope") == 1


@pytest.mark.parametrize("name", ["lm_unnamed_self_share",
                                  "moe_placement_self_share"])
def test_nothing_without_a_trace_or_the_scopes(texts, name):
    read, spec = metric(name)
    run = traced_run()
    run.trace_dir = run.span_trace_data = None          # --trace 0
    assert read(run, spec) is None
    # the parent's program: the scopes it has (kps.moe.experts,
    # kps.fit.*) without the ones this PR wrote into it
    older = HLO
    for scope in self_time.table_spec()["needs_one_of"] + [
            "kps.moe.combine", "kps.moe.sort", "kps.bsp.carry"]:
        older = older.replace("/" + scope, "")
    assert "kps.moe.experts" in older and "kps.moe.place" not in older
    texts["jit_scanned"] = [older]
    assert read(traced_run(), spec) is None
    # no executable of that name alive
    del texts["jit_scanned"]
    assert read(traced_run(), spec) is None


def test_a_program_without_an_expert_layer_has_no_placement(texts):
    read, spec = metric("moe_placement_self_share")
    texts["jit_scanned"] = [HLO.replace("kps.moe.place", "kps.mlp")
                            .replace("kps.moe.combine", "kps.mlp")]
    assert read(traced_run(), spec) is None
    read, spec = metric("lm_unnamed_self_share")
    assert read(traced_run(), spec) == pytest.approx(8.0)


def test_the_call_edge_share_reads_the_programs_own_record(capsys):
    read, spec = metric("fused_call_edge_share")
    assert read(traced_run(), spec) == pytest.approx(100 * 1.25 / 20.0)
    assert "device_wait_s 17.000000" in capsys.readouterr().out


@pytest.mark.parametrize("last_run", [
    None, {}, {"path": "serial", "seconds": 1.0, "theta_up_s": 0.0,
               "device_wait_s": 0.0, "theta_down_s": 0.0},
    {"path": "fused", "seconds": 20.0, "slab_refresh_s": 0.01},   # parent
    {"path": "fused", "seconds": 0.0, "theta_up_s": 0.0,
     "device_wait_s": 0.0, "theta_down_s": 0.0}])
def test_the_call_edge_share_without_the_record_reads_nothing(last_run):
    read, spec = metric("fused_call_edge_share")
    run = traced_run()
    run.app = types.SimpleNamespace(last_run=last_run)
    assert read(run, spec) is None
    run.app = types.SimpleNamespace()
    assert read(run, spec) is None


@pytest.mark.parametrize("fixture", ["fused_one_chip", "pernode_one_chip"])
def test_on_a_trace_recorded_on_the_chip_the_lines_sum_to_its_busy_time(
        fixture):
    """The operation line of a real program nests three deep (a scan's
    `while` over the fold's over the fusions) and a few events overlap
    without nesting: summed as durations the events give 1.5-2.6 times
    the chip's busy seconds, as self time exactly those."""
    import span_reduce
    import trace_reduce
    cfg = json.load(open(os.path.join(BENCH, "trace.json")))
    data = trace_reduce.load(os.path.join(BENCH, "fixtures",
                                          fixture + ".xplane.pb.gz"))
    ops, _ = span_reduce.device_op_events(data, cfg)
    busy = sum(e - s for s, e in trace_reduce.merged(
        [(s, e) for _, s, e in ops]))
    got = self_time.self_seconds(ops)
    assert abs(sum(got.values()) - busy) < 1e-9
    assert sum(e - s for _, s, e in ops) > 1.5 * busy
    assert all(v >= 0.0 for v in got.values())

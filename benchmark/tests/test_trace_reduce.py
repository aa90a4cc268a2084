"""trace_reduce.py: its interval arithmetic on hand-made intervals, and
the whole reduction against a small trace recorded on the chip
(benchmark/fixtures/README.txt says what was read from it by hand)."""

import json
import os

import pytest

import trace_reduce
from conftest import BENCH

CFG = json.load(open(os.path.join(BENCH, "trace.json")))
FIXTURE = os.path.join(BENCH, "fixtures", "fused_one_chip.xplane.pb.gz")


def test_merge_counts_overlaps_once():
    spans = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.75)]
    assert trace_reduce.merged(spans) == [(0.0, 3.0), (5.0, 6.0)]


def test_subtract_length_is_what_nothing_covers():
    collectives = [(0.0, 4.0), (10.0, 11.0)]
    compute = [(1.0, 2.0), (3.0, 5.0), (20.0, 30.0)]
    # of [0,4): [0,1) and [2,3) are exposed; [10,11) wholly
    assert trace_reduce.subtract_length(collectives, compute) == \
        pytest.approx(3.0)
    assert trace_reduce.subtract_length(collectives, []) == pytest.approx(5.0)
    assert trace_reduce.subtract_length(collectives, [(0.0, 11.0)]) == 0.0


def test_a_gap_is_shared_out_among_the_innermost_host_annotations():
    host = [("bench.run_slice", 0.0, 10.0), ("bench.flush_logs", 6.0, 9.0),
            ("bench.run_slice", 11.0, 20.0)]
    got = trace_reduce.split_by_host_activity(5.0, 12.0, host)
    assert got == pytest.approx({"bench.run_slice": 1.0 + 1.0 + 1.0,
                                 "bench.flush_logs": 3.0,
                                 "unannotated": 1.0})
    assert trace_reduce.host_activity(5.0, 12.0, host) in (
        "bench.run_slice", "bench.flush_logs")
    assert trace_reduce.host_activity(6.5, 8.0, host) == "bench.flush_logs"


def test_module_name_drops_the_program_id():
    assert trace_reduce.module_name("jit_scanned(1107038667)") == \
        "jit_scanned"
    assert trace_reduce.module_name("jit__unknown") == "jit__unknown"


class _Event:
    def __init__(self, name, start_s, end_s):
        self.name = name
        self.start_ns = start_s * 1e9
        self.duration_ns = (end_s - start_s) * 1e9


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Data:
    def __init__(self, planes):
        self.planes = planes


def test_two_chips_with_a_half_hidden_all_reduce():
    """Chip 0: a loop over [0,10) whose body is compute [0,4), an
    all-reduce [4,6) half under a fusion [5,8), idle, compute [9,10).
    Chip 1: busy [0,10) with the all-reduce [4,6) wholly exposed."""
    def chip(name, ops):
        return _Plane(name, [
            _Line("XLA Ops", [_Event(*o) for o in ops]),
            _Line("XLA Modules", [_Event("jit_shard_body(7)", 0.0, 10.0)])])
    data = _Data([
        chip("/device:TPU:0", [("%while.1 = loop", 0.0, 10.0),
                               ("%fusion.1 = f32[]", 0.0, 4.0),
                               ("%all-reduce.1 = f32[]", 4.0, 6.0),
                               ("%fusion.2 = f32[]", 5.0, 8.0),
                               ("%fusion.3 = f32[]", 9.0, 10.0)]),
        chip("/device:TPU:1", [("%fusion.1 = f32[]", 0.0, 4.0),
                               ("%all-reduce.1 = f32[]", 4.0, 6.0),
                               ("%fusion.2 = f32[]", 6.0, 10.0)]),
        _Plane("/host:CPU", [_Line("python", [
            _Event("bench.run_slice", 0.0, 10.0)])])])
    got = trace_reduce.reduce(data, CFG, chips=2)
    assert got["chips"] == 2
    assert got["window_s"] == pytest.approx(10.0)
    assert got["busy_s"] == pytest.approx(10.0)      # the loop spans it all
    assert got["collective_s"] == pytest.approx(2.0)
    assert got["collective_exposed_s"] == pytest.approx((1.0 + 2.0) / 2)
    assert got["module_time_s"] == {"jit_shard_body": pytest.approx(10.0)}
    assert got["module_runs"] == {"jit_shard_body": 1}


def test_a_trace_cut_out_of_one_call_keeps_only_the_whole_runs():
    """`window_from: device_ops`: five runs of the step program, the
    first and the last cut by the trace's edges; a stray annotation does
    not set the window."""
    runs = [(0.0, 0.3), (0.5, 1.5), (1.6, 2.6), (2.7, 3.7), (3.8, 4.0)]
    data = _Data([
        _Plane("/device:TPU:0", [
            _Line("XLA Ops", [_Event("%fusion.1 = f32[]", s, e)
                              for s, e in runs]),
            _Line("XLA Modules", [_Event("jit__unknown(7)", s, e)
                                  for s, e in runs])]),
        _Plane("/host:CPU", [_Line("python", [
            _Event("bench.sync", 3.9, 9.0)])])])
    got = trace_reduce.reduce(data, dict(CFG, window_from="device_ops"),
                              chips=1)
    assert got["window_s"] == pytest.approx(4.0)
    assert got["busy_s"] == pytest.approx(0.3 + 3.0 + 0.2)
    assert got["module_runs"] == {"jit__unknown": 5}
    assert got["module_whole_runs"] == {"jit__unknown": pytest.approx(3.0)}
    assert got["module_whole_time_s"] == {
        "jit__unknown": pytest.approx(3.0)}
    # with annotations as the window every run counts as whole
    got = trace_reduce.reduce(data, CFG, chips=1)
    assert got["window_s"] == pytest.approx(5.1)
    assert got["module_whole_runs"] == {"jit__unknown": pytest.approx(1.0)}


def test_a_trace_with_no_device_operation_is_an_error():
    data = _Data([_Plane("/host:CPU", [_Line("python", [
        _Event("bench.run_slice", 0.0, 1.0)])])])
    with pytest.raises(RuntimeError, match="no device operation"):
        trace_reduce.reduce(data, CFG, chips=1)


def test_the_recorded_trace_reduces_to_what_was_read_by_hand():
    got = trace_reduce.reduce(trace_reduce.load(FIXTURE), CFG, chips=1)
    # the cut runs from 1.40 s to 2.90 s after the trace's first
    # operation; the window is what the host annotations span
    assert got["window_s"] == pytest.approx(1.50, abs=1e-6)
    # two whole runs of the scan program (0.48 s each) and the tails of
    # its neighbours inside the cut: 0.0406 s before, 0.0949 s after
    assert got["module_runs"]["jit__unknown"] == 2
    assert got["module_time_s"]["jit__unknown"] == pytest.approx(0.96,
                                                                 abs=0.001)
    assert got["busy_s"] == pytest.approx(0.96 + 0.0406 + 0.0949, abs=0.005)
    # one idle gap, 1.9209 -> 2.3249: the log flush until 2.0077, then
    # run_fused_bsp re-uploading the slabs before its first dispatch
    idle = got["idle_by_host_activity_s"]
    assert idle["bench.flush_logs"] == pytest.approx(0.0868, abs=0.002)
    # (0.3169 s), plus 0.003 s after the last whole operation in the cut
    assert idle["bench.run_slice"] == pytest.approx(0.3169 + 0.0033,
                                                    abs=0.002)
    assert sum(idle.values()) == pytest.approx(
        got["window_s"] - got["busy_s"], abs=1e-6)
    # the eval and log-fetch programs run briefly inside that gap, so
    # the longest stretch with nothing on the device starts after them
    assert got["longest_gaps_s"][0][0] == "bench.run_slice"
    assert got["longest_gaps_s"][0][1] == pytest.approx(0.369, abs=0.002)
    assert got["breakdown"]["device_ops"][0][0] == "jit__unknown"
    assert got["collective_s"] == 0.0


def test_the_programs_spans_name_idle_gaps_and_do_not_set_the_window():
    """`idle_gap_prefixes`: a gap goes to the innermost of the harness's
    annotations and the program's `kps.*` spans, whatever thread they
    are on; the window still runs over the `bench.*` annotations alone,
    and in a trace cut out of one call over the device operations."""
    def data():
        return _Data([
            _Plane("/device:TPU:0", [_Line("XLA Ops", [
                _Event("%fusion.1 = f32[]", 1.0, 4.0),
                _Event("%fusion.1 = f32[]", 7.0, 9.0)])]),
            _Plane("/host:CPU", [
                _Line("python", [_Event("bench.run_slice", 1.0, 9.0),
                                 _Event("kps.log.flush", 4.0, 6.0),
                                 _Event("kps.bsp.step", 0.0, 12.0),
                                 _Event("other.span", 4.0, 7.0)]),
                _Line("kps-log-drain", [_Event("kps.log.drain", 6.5, 6.75)]),
            ])])
    got = trace_reduce.reduce(data(), CFG, chips=1)
    assert got["window_s"] == pytest.approx(8.0)     # kps.bsp.step is wider
    assert got["idle_by_host_activity_s"] == pytest.approx(
        {"kps.log.flush": 2.0, "kps.log.drain": 0.25,
         "bench.run_slice": 0.75})
    assert got["breakdown"]["idle_gaps"][0] == ["kps.log.flush",
                                                pytest.approx(2.0)]
    cut = trace_reduce.reduce(data(), dict(CFG, window_from="device_ops"),
                              chips=1)
    assert cut["window_s"] == pytest.approx(8.0)
    # no annotation of the harness's alone: the gaps go by kps.* too
    only_program = dict(CFG, idle_gap_prefixes=["kps."],
                        window_from="device_ops")
    assert trace_reduce.reduce(data(), only_program, chips=1)[
        "idle_by_host_activity_s"] == pytest.approx(
        {"kps.log.flush": 2.0, "kps.log.drain": 0.25, "kps.bsp.step": 0.75})


def test_the_recorded_per_node_trace_names_its_gaps_by_the_programs_spans():
    """fixtures/README.txt: the slice boundary's dry spell is the sinks'
    flush, the gang's drain and its dispatch call; with `kps.*` among
    the prefixes the ledger's `idle_gaps` says so."""
    path = os.path.join(BENCH, "fixtures", "pernode_one_chip.xplane.pb.gz")
    got = trace_reduce.reduce(trace_reduce.load(path), CFG, chips=1)
    assert got["window_s"] == pytest.approx(0.7989, abs=1e-3)
    idle = got["idle_by_host_activity_s"]
    named = sum(s for name, s in idle.items() if name.startswith("kps."))
    assert named > 0.9 * sum(idle.values())
    top = [name for name, _ in got["breakdown"]["idle_gaps"][:4]]
    assert "kps.worker.local_update" in top and "kps.log.flush" in top
    assert "unannotated" not in top
    # the window and the busy time are what they were by bench.* alone
    alone = trace_reduce.reduce(trace_reduce.load(path),
                                dict(CFG, idle_gap_prefixes=["bench."]),
                                chips=1)
    assert (alone["window_s"], alone["busy_s"]) == (got["window_s"],
                                                    got["busy_s"])
    assert set(alone["idle_by_host_activity_s"]) <= {
        "bench.run_slice", "bench.flush_logs", "bench.sync", "unannotated"}

"""span_reduce.py: the attribution of device idle time to the program's
own spans and of device operations to named scopes, on hand-made planes
where every share is known by hand, on a trace recorded on the chip
(benchmark/fixtures/README.txt), and through the
harness on the CPU."""

import importlib.util
import json
import os
import types

import pytest

import span_reduce
import trace_reduce
from conftest import BENCH
from helpers import run_cell
from test_trace_reduce import _Data, _Event, _Line, _Plane

CFG = json.load(open(os.path.join(BENCH, "trace.json")))
WHAT = span_reduce.spec()
FIXTURE = os.path.join(BENCH, "fixtures", "pernode_one_chip.xplane.pb.gz")
FIXTURE_OP_NAMES = os.path.join(BENCH, "fixtures",
                                "pernode_one_chip.op_names.json")
IDLE_METRICS = ["fused_host_exposed_share", "pernode_host_exposed_share",
                "log_eval_exposed_share", "idle_unattributed_share"]


def metric(name):
    """(read, spec) of benchmark/layer_metrics/<name>, as run.py loads
    them."""
    base = os.path.join(BENCH, "layer_metrics", name)
    module_spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name, base + ".py")
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read, json.load(open(base + ".json"))


def fake_run(data, window_from="annotations", chips=1, app=None):
    """What a reader sees of benchmark/run.py's Run."""
    run = types.SimpleNamespace(
        trace_dir="unused", span_trace_data=data, devices=[None] * chips,
        trace_cfg=dict(CFG, window_from=window_from),
        app=app or types.SimpleNamespace())
    return run


def read_idle_metrics(run) -> dict:
    out = {}
    for name in IDLE_METRICS:
        read, spec = metric(name)
        out[name] = read(run, spec)
    return out


def host(*lines):
    return _Plane("/host:CPU", [_Line("python", [_Event(*e) for e in evs])
                                for evs in lines])


def chip(name, ops, modules=()):
    return _Plane(name, [_Line("XLA Ops", [_Event(*o) for o in ops]),
                         _Line("XLA Modules", [_Event(*m) for m in modules])])


def slices_trace(dispatching, *others):
    """One chip busy [1,3), [4,5) and [8,9) of the window [0,10) that
    the harness's annotations span: idle [0,1), [3,4), [5,8), [9,10)."""
    return _Data([
        chip("/device:TPU:0", [("%fusion.1 = f32[]", 1.0, 3.0),
                               ("%fusion.2 = f32[]", 4.0, 5.0),
                               ("%fusion.3 = f32[]", 8.0, 9.0)]),
        host([("bench.run_slice", 0.0, 7.0), ("bench.sync", 7.0, 10.0)]
             + list(dispatching), *others)])


def test_the_four_shares_are_known_by_hand_and_sum_to_the_idle_share():
    data = slices_trace(
        [("kps.serial.round", 0.0, 6.0),          # self: [0,0.5)
         ("kps.gang.drain", 0.5, 3.5),            # idle [0.5,1), [3,3.5)
         ("kps.server.apply", 3.5, 4.0),          # idle [3.5,4)
         ("kps.app.flush_logs", 6.0, 7.0),        # idle [6,6.2), [6.8,7)
         ("kps.log.flush", 6.2, 6.8)],            # idle [6.2,6.8)
        [("kps.log.drain", 0.0, 10.0)])           # another thread
    got = read_idle_metrics(fake_run(data))
    # [5,6) is the round's own time; [7,8) and [9,10) are under no span
    assert got == {
        "fused_host_exposed_share": pytest.approx(0.0),
        "pernode_host_exposed_share": pytest.approx(
            10.0 * (0.5 + 0.5 + 0.5 + 0.5 + 1.0)),
        "log_eval_exposed_share": pytest.approx(10.0 * (0.4 + 0.6)),
        "idle_unattributed_share": pytest.approx(10.0 * 2.0)}
    idle = trace_reduce.reduce(data, CFG, chips=1)
    assert sum(got.values()) == pytest.approx(
        100.0 * (1 - idle["busy_s"] / idle["window_s"]))


def test_the_innermost_span_takes_the_instant():
    """Idle [5,8) under log.fetch in log.flush in fused.log_rows in
    fused.chunk goes to the sinks, not to the fused loop."""
    data = slices_trace([("kps.fused.chunk", 0.0, 10.0),
                         ("kps.bsp.step", 0.0, 1.0),
                         ("kps.fused.log_rows", 4.5, 9.5),
                         ("kps.log.flush", 5.0, 8.5),
                         ("kps.log.fetch", 5.5, 7.0)])
    idle = span_reduce.idle_by_span(data, CFG, 1, WHAT)
    assert idle["by_span_s"] == pytest.approx({
        "kps.bsp.step": 1.0, "kps.fused.chunk": 1.0 + 0.5,
        "kps.fused.log_rows": 0.5, "kps.log.flush": 0.5 + 1.0,
        "kps.log.fetch": 1.5, span_reduce.NO_SPAN: 0.0})
    got = read_idle_metrics(fake_run(data))
    assert got["log_eval_exposed_share"] == pytest.approx(30.0)
    assert got["fused_host_exposed_share"] == pytest.approx(30.0)
    assert got["idle_unattributed_share"] == pytest.approx(0.0)


def test_only_the_dispatching_thread_attributes():
    """The drain thread's span covers every gap; the dispatching line
    (it holds the harness's run_slice) has a span over [3,4) alone."""
    data = slices_trace([("kps.serial.round", 3.0, 4.0)],
                        [("kps.log.drain", 0.0, 10.0),
                         ("kps.log.fetch", 0.0, 10.0)])
    idle = span_reduce.idle_by_span(data, CFG, 1, WHAT)
    assert idle["dispatch_line"] == "python#0"
    assert idle["by_span_s"] == pytest.approx({
        "kps.serial.round": 1.0, span_reduce.NO_SPAN: 5.0})
    assert idle["other_lines"] == {"python#1": {
        "kps.log.drain": pytest.approx(10.0),
        "kps.log.fetch": pytest.approx(10.0)}}
    got = read_idle_metrics(fake_run(data))
    assert got["log_eval_exposed_share"] == pytest.approx(0.0)
    assert got["idle_unattributed_share"] == pytest.approx(50.0)


def test_a_trace_cut_out_of_one_call_finds_the_line_by_the_step_span():
    """`window_from: device_ops`: no annotation of the harness's is
    whole; the line that holds kps.bsp.step dispatches."""
    data = _Data([
        chip("/device:TPU:0", [("%fusion.1 = f32[]", 0.0, 2.0),
                               ("%fusion.2 = f32[]", 3.0, 4.0)]),
        host([("kps.log.drain", 0.0, 4.0)],
             [("kps.fused.chunk", 1.0, 3.5), ("kps.bsp.step", 1.0, 1.5),
              ("kps.fused.log_rows", 2.0, 2.75)])])
    run = fake_run(data, window_from="device_ops")
    got = read_idle_metrics(run)
    assert run.span_idle["dispatch_line"] == "python#1"
    assert got["fused_host_exposed_share"] == pytest.approx(25.0)
    assert got["idle_unattributed_share"] == pytest.approx(0.0)


def test_without_a_program_span_all_idle_time_is_unattributed():
    """The parent of the PR that brought the spans: three readers find
    nothing to read, the fourth reports the whole idle share."""
    got = read_idle_metrics(fake_run(slices_trace([])))
    assert got == {"fused_host_exposed_share": None,
                   "pernode_host_exposed_share": None,
                   "log_eval_exposed_share": None,
                   "idle_unattributed_share": pytest.approx(60.0)}


def test_idle_seconds_are_averaged_over_the_chips_used():
    data = _Data([
        chip("/device:TPU:0", [("%fusion.1 = f32[]", 0.0, 6.0),
                               ("%fusion.2 = f32[]", 8.0, 10.0)]),
        chip("/device:TPU:1", [("%fusion.1 = f32[]", 0.0, 10.0)]),
        host([("bench.run_slice", 0.0, 10.0), ("kps.bsp.step", 6.0, 9.0)])])
    got = read_idle_metrics(fake_run(data, chips=2))
    idle = trace_reduce.reduce(data, CFG, chips=2)
    assert got["fused_host_exposed_share"] == pytest.approx(10.0)
    assert sum(v for v in got.values() if v) == pytest.approx(
        100.0 * (1 - idle["busy_s"] / idle["window_s"]))


# -- named scopes --------------------------------------------------------------

SCOPES = metric("solver_param_step_share")[1]


def test_an_operation_goes_to_the_first_listed_scope_it_lies_under():
    order = SCOPES["scopes"]
    assert span_reduce.scope_of(
        "jit(f)/vmap(kps.gang.fit)/while/body/kps.fit.param_step/sub",
        order) == "kps.fit.param_step"
    assert span_reduce.scope_of(
        "jit(f)/vmap(kps.gang.eval)/jit(_evaluate)/kps.eval/dot_general",
        order) == "kps.gang.eval"
    assert span_reduce.scope_of(
        "jit(f)/transpose(jvp(kps.fit.grad))/dot_general", order) \
        == "kps.fit.grad"
    assert span_reduce.scope_of("jit(f)/while/body/add", order) == ""


def test_op_names_are_read_from_a_compiled_program_s_text():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("kps.fit.grad"):
            y = jnp.sin(x) @ x
        with jax.named_scope("kps.fit.param_step"):
            return x - 0.1 * y
    text = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
    table = span_reduce.op_names_from_hlo(text)
    scopes = {span_reduce.scope_of(v, SCOPES["scopes"])
              for v in table.values()}
    assert {"kps.fit.grad", "kps.fit.param_step"} <= scopes
    # the entry computation's fusion counts for its root
    roots = {k: v for k, v in table.items() if "fusion" in k}
    assert any("kps.fit.param_step" in v for v in roots.values()), table


def scoped_trace():
    """Two runs of the solver program [0,4) and [5,9) and one of another
    program; per run: a loop over the whole run, a gradient fusion 2 s,
    a parameter step 1 s, an unnamed copy 0.5 s."""
    ops, modules = [], []
    for t in (0.0, 5.0):
        modules.append(("jit__unknown(7)", t, t + 4.0))
        ops += [("%while.3 = (s32[]) while(...)", t, t + 4.0),
                ("%fusion.84 = f32[64,4096] fusion(...)", t, t + 2.0),
                ("%multiply_subtract_fusion.2 = f32[64] fusion(...)",
                 t + 2.0, t + 3.0),
                ("%copy.19 = f32[8] copy(...)", t + 3.0, t + 3.5)]
    modules.append(("jit_log_stack(9)", 4.2, 4.4))
    ops.append(("%fusion.84 = f32[2] fusion(...)", 4.2, 4.4))
    return _Data([chip("/device:TPU:0", ops, modules)])


def test_device_seconds_by_scope_are_the_leaf_operations_of_the_programs():
    tables = {"jit__unknown": [
        {"fusion.84": "stale/no/scope"},        # an older executable
        {"fusion.84": "jit(f)/vmap(jvp(kps.fit.grad))/dot_general",
         "multiply_subtract_fusion.2": "jit(f)/kps.fit.param_step/sub",
         "while.3": "jit(f)/kps.fit.grad/while"}]}
    got = span_reduce.seconds_by_scope(
        scoped_trace(), CFG, SCOPES["scopes"],
        SCOPES["solver_module_patterns"], tables)
    assert got["programs_s"] == pytest.approx(8.0)
    assert got["by_scope_s"] == pytest.approx({
        "kps.fit.grad": 4.0, "kps.fit.param_step": 2.0, "": 1.0})
    run = fake_run(scoped_trace())
    read, spec = metric("solver_param_step_share")
    real = span_reduce.executables_op_names
    span_reduce.executables_op_names = lambda patterns: tables
    try:
        assert read(run, spec) == pytest.approx(25.0)
        # a program without the scopes: nothing to read
        tables["jit__unknown"] = [{"fusion.84": "jit(f)/dot_general"}]
        assert read(run, spec) is None
    finally:
        span_reduce.executables_op_names = real


def test_slab_refresh_share_reads_the_program_s_record_of_its_last_call():
    read, spec = metric("slab_refresh_share")
    app = types.SimpleNamespace(last_run={
        "path": "fused", "seconds": 20.0, "slab_refreshes": 1,
        "slab_refresh_s": 0.5, "slab_refresh_bytes": 268435456})
    assert read(fake_run(None, app=app), spec) == pytest.approx(2.5)
    app.last_run = {"path": "serial", "seconds": 1.0, "slab_refreshes": 0,
                    "slab_refresh_s": 0.0, "slab_refresh_bytes": 0}
    assert read(fake_run(None, app=app), spec) is None
    # the parent's app keeps no such record
    assert read(fake_run(None), spec) is None


# -- the recorded trace --------------------------------------------------------

def test_the_recorded_trace_gives_what_was_read_by_hand():
    """fixtures/README.txt: two slices of the per-node
    cell; the gaps and the spans over them were listed from the full
    trace, seconds to four places."""
    data = trace_reduce.load(FIXTURE)
    run = fake_run(data)
    got = read_idle_metrics(run)
    idle = trace_reduce.reduce(data, CFG, chips=1)
    assert idle["window_s"] == pytest.approx(0.7989, abs=1e-4)
    by_hand = {"kps.worker.local_update": 0.0108 + 0.0113,
               "kps.gang.drain": 0.0031 + 0.0031,
               "kps.log.flush": 0.0084 + 0.0083 + 0.0018,
               "kps.log.fetch": 0.0012 + 0.0011 + 0.0014 + 0.0013
               + 0.0014 + 0.0011 + 0.0007,
               "kps.app.flush_logs": 0.0014 + 0.0022 + 0.0006}
    for span, secs in by_hand.items():
        assert run.span_idle["by_span_s"][span] == pytest.approx(
            secs, abs=0.0006), span
    assert run.span_idle["dispatch_line"] == "python#1"
    assert set(run.span_idle["other_lines"]) == {"python#0", "python#2"}
    assert got["fused_host_exposed_share"] == pytest.approx(0.0)
    assert got["pernode_host_exposed_share"] == pytest.approx(
        100 * 0.0283 / 0.7989, abs=0.1)
    assert got["log_eval_exposed_share"] == pytest.approx(
        100 * 0.0309 / 0.7989, abs=0.15)
    assert got["idle_unattributed_share"] < 0.05
    assert sum(got.values()) == pytest.approx(
        100.0 * (1 - idle["busy_s"] / idle["window_s"]), abs=1e-6)
    # the gang program's eight runs by named scope, against the table
    # of its instructions' op_name (the program's own HLO text)
    scopes = span_reduce.seconds_by_scope(
        data, CFG, SCOPES["scopes"], SCOPES["solver_module_patterns"],
        json.load(open(FIXTURE_OP_NAMES)))
    assert scopes["programs_s"] == pytest.approx(0.7263, abs=1e-4)
    share = {k: 100 * v / scopes["programs_s"]
             for k, v in scopes["by_scope_s"].items()}
    assert share["kps.fit.param_step"] == pytest.approx(10.45, abs=0.05)
    assert share["kps.gang.eval"] == pytest.approx(17.57, abs=0.05)
    assert share["kps.fit.grad"] == pytest.approx(32.88, abs=0.05)
    assert share[""] == pytest.approx(31.07, abs=0.05)


# -- through the harness, on the CPU ------------------------------------------

@pytest.mark.parametrize("cell,workers,layer", [
    ("mlp-4096.pernode-bsp", "4", "pernode_host_exposed_share"),
    ("mlp-4096-x4.fused-bsp", "8", "fused_host_exposed_share")])
def test_a_traced_run_partitions_its_idle_share(capsys, cell, workers,
                                                layer):
    rc, result, out = run_cell(capsys, cell, workers, trace=1)
    assert rc == 0 and result["correct"] is True, out
    values = {k: v["value"] for k, v in result["metrics"].items()}
    parts = [layer, "log_eval_exposed_share", "idle_unattributed_share"]
    assert set(parts) <= set(values), values
    assert sum(values[p] for p in parts) == pytest.approx(
        values["device_idle_share"], abs=0.05)
    assert values[layer] > 0
    assert "[bench] idle by program span" in out
    if "fused" in cell:
        assert 0 < values["slab_refresh_share"] < 100

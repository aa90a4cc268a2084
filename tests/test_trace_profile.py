"""One clock: the program's spans in the profiler's trace, the named
scopes in the device programs, and the record of the last drive call
(docs/OBSERVABILITY.md "One clock").  CPU; a profiler session is
process-wide, so every traced run of this file is made once, in one
fixture."""

import functools
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.data.synth import generate
from kafka_ps_tpu.runtime.app import StreamingPSApp
from kafka_ps_tpu.utils import asynclog, trace
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig
from kafka_ps_tpu.utils.trace import NULL_TRACER, Tracer

FUSED_ROUNDS = 16          # two 8-round scan chunks at eval_every 8
# the span that only the dispatching thread opens, by drive path
# ("folded": the fused loop of a task whose workers are folded one at a
# time, a language-model family at its tiny size)
MARKER = {"fused": "bsp.step", "serial": "serial.round",
          "folded": "bsp.step"}
GLM_TINY = "benchmark/families/glm4-moe-lite/tiny.model.json"
CALL_EDGES = ["fused.theta_up", "fused.wait_device", "fused.theta_down"]
EDGE_SECONDS = ["theta_up_s", "device_wait_s", "theta_down_s"]
DISPATCH_SPANS = {
    "fused": ["fused.chunk", "fused.slab_refresh", "bsp.step",
              "fused.publish", "fused.eval", "fused.log_rows",
              "app.flush_logs", "eval.drain", "log.flush", "log.fetch"],
    "serial": ["serial.round", "gang.drain", "serial.deliver",
               "serial.collect", "worker.local_update", "server.apply",
               "eval.submit", "app.flush_logs", "eval.drain", "log.flush",
               "log.fetch"],
    "folded": CALL_EDGES + ["fused.chunk", "bsp.step", "fused.eval"],
}
# a child lies inside one event of its parent, on the same line
NESTING = {
    "fused": [("fused.slab_refresh", "fused.chunk"),
              ("bsp.step", "fused.chunk"), ("fused.publish", "fused.chunk"),
              ("fused.eval", "fused.chunk"),
              ("fused.log_rows", "fused.chunk"),
              ("eval.drain", "app.flush_logs"),
              ("log.flush", "app.flush_logs"), ("log.fetch", "log.flush")],
    "serial": [("gang.drain", "serial.round"),
               ("serial.deliver", "serial.round"),
               ("serial.collect", "serial.round"),
               ("worker.local_update", "gang.drain"),
               ("server.apply", "serial.round"),
               ("eval.submit", "serial.round"),
               ("eval.drain", "app.flush_logs"),
               ("log.flush", "app.flush_logs")],
}


def make_app(tracer=None, task="logreg", eval_every=1, workers=2,
             server_log=None):
    cfg = PSConfig(
        num_workers=workers, task=task, eval_every=eval_every,
        model=ModelConfig(num_features=16, num_classes=3, hidden_dim=8),
        buffer=BufferConfig(min_size=4, max_size=8))
    x, y = generate(40, 16, 3, seed=0)
    app = StreamingPSApp(cfg, test_x=x[-8:], test_y=y[-8:], tracer=tracer,
                         server_log=server_log)
    for i in range(8 * workers):
        app.data_sink(i % workers, {j: float(x[i, j]) for j in range(16)},
                      int(y[i]))
    return app


def make_folded_app(tracer=None, server_log=None):
    """Two workers of the first language-model family at its tiny
    size, a row of tokens each."""
    from kafka_ps_tpu.models.task import get_task
    cfg = PSConfig(
        num_workers=2, task="glm4_moe_lite", eval_every=8,
        model=ModelConfig(num_max_iter=2, local_learning_rate=0.05,
                          model_json=GLM_TINY),
        buffer=BufferConfig(min_size=1, max_size=2))
    c = get_task("glm4_moe_lite", cfg.model).arch
    rows = np.random.default_rng(3).integers(
        0, c.vocab_held, size=(6, c.row_width)).astype(np.int32)
    app = StreamingPSApp(cfg, test_x=rows[4:], test_y=np.zeros(2, np.int32),
                         tracer=tracer, server_log=server_log)
    for i, row in enumerate(rows[:4]):
        app.data_sink(i % 2, row, 0)
    return app


def drive(path: str, app) -> None:
    if path in ("fused", "folded"):
        app.run_fused_bsp(
            max_server_iterations=FUSED_ROUNDS * app.cfg.num_workers)
    else:
        app.run_serial(max_server_iterations=8, pump=lambda: None)


def host_lines(trace_dir: str) -> dict[str, list]:
    """{line name: [(span name, start_ns, end_ns, stats)]} for the
    `kps.*` events of the newest trace under `trace_dir`."""
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    out: dict[str, list] = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            events = [(ev.name[4:], ev.start_ns,
                       ev.start_ns + ev.duration_ns, dict(ev.stats))
                      for ev in line.events if ev.name.startswith("kps.")]
            if events:
                out[f"{i}:{line.name}"] = events
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Each drive path under a profiler session, with no Tracer built:
    {path: {"lines", "events_recorded", "app"}}."""
    out = {}
    for path in ("fused", "serial", "folded"):
        rows: list[str] = []
        app = (make_folded_app(server_log=rows.append) if path == "folded"
               else make_app(task="mlp" if path == "fused" else "logreg",
                             eval_every=8 if path == "fused" else 1,
                             server_log=rows.append))
        assert app.tracer is NULL_TRACER
        drive(path, app)                       # compile outside the session
        trace_dir = str(tmp_path_factory.mktemp("profile-" + path))
        with trace.device_trace(trace_dir):
            drive_again = (2 * FUSED_ROUNDS * app.cfg.num_workers
                           if path != "serial" else 16)
            if path != "serial":
                app.run_fused_bsp(max_server_iterations=drive_again)
            else:
                app.run_serial(max_server_iterations=drive_again,
                               pump=lambda: None)
            # the sinks' own thread: one row whose scalar is a device
            # value, left for the drain thread to find ready and write
            # (a span still open when the session stops is not kept)
            app.server.log.submit("row {}", jnp.float32(1.0))
            deadline = time.time() + 10.0
            while "row 1.0" not in rows and time.time() < deadline:
                time.sleep(0.05)
            time.sleep(0.05)
        out[path] = {"lines": host_lines(trace_dir), "app": app,
                     "last_run": dict(app.last_run),
                     "events_recorded": len(NULL_TRACER._events)}
        app.close_logs()
    return out


def dispatch_line(lines: dict, marker: str) -> list:
    return next(evs for evs in lines.values()
                if any(name == marker for name, *_ in evs))


@pytest.mark.parametrize("path,span", [
    (path, span) for path, spans in DISPATCH_SPANS.items() for span in spans])
def test_span_is_on_the_dispatching_line(traced, path, span):
    line = dispatch_line(traced[path]["lines"], MARKER[path])
    assert any(name == span for name, *_ in line), (
        span, sorted({n for n, *_ in line}))


@pytest.mark.parametrize("path,child,parent", [
    (path, child, parent) for path, pairs in NESTING.items()
    for child, parent in pairs])
def test_span_nests_under_its_parent(traced, path, child, parent):
    line = dispatch_line(traced[path]["lines"], MARKER[path])
    parents = [(s, e) for name, s, e, _ in line if name == parent]
    children = [(s, e) for name, s, e, _ in line if name == child]
    assert children and parents
    inside = [any(ps <= s and e <= pe for ps, pe in parents)
              for s, e in children]
    assert any(inside), (child, parent)


@pytest.mark.parametrize("path", ["fused", "serial"])
def test_other_threads_have_lines_of_their_own(traced, path):
    lines = traced[path]["lines"]
    main = dispatch_line(lines, MARKER[path])
    drains = [evs for evs in lines.values()
              if any(name == "log.drain" for name, *_ in evs)]
    assert drains and all(evs is not main for evs in drains)
    if path == "serial":        # the eval engine's thread
        evals = [evs for evs in lines.values()
                 if any(name == "server.eval" for name, *_ in evs)]
        assert evals and all(evs is not main for evs in evals)


@pytest.mark.parametrize("path,span,arg", [
    ("fused", "bsp.step", "rounds"), ("fused", "fused.slab_refresh", "bytes"),
    ("fused", "fused.log_rows", "rows"), ("fused", "log.flush", "reason"),
    ("fused", "log.fetch", "scalars"), ("serial", "eval.submit", "clock")])
def test_span_arguments_are_statistics_of_the_event(traced, path, span, arg):
    events = [ev for evs in traced[path]["lines"].values() for ev in evs
              if ev[0] == span]
    assert events and all(arg in stats for *_, stats in events)


def test_fused_step_span_counts_dispatches_not_rounds(traced):
    line = dispatch_line(traced["fused"]["lines"], "bsp.step")
    steps = [stats for name, _, _, stats in line if name == "bsp.step"]
    assert [int(s["rounds"]) for s in steps] == [8, 8]
    rows = [int(stats["rows"]) for name, _, _, stats in line
            if name == "fused.log_rows"]
    assert rows == [16, 16]                    # 8 rounds x 2 workers


@pytest.mark.parametrize("path", ["fused", "serial"])
def test_null_tracer_records_nothing(traced, path):
    assert traced[path]["app"].tracer is NULL_TRACER
    assert traced[path]["events_recorded"] == 0
    assert NULL_TRACER.counters() == {}


def test_no_session_no_trace(tmp_path):
    """Outside a session a span is the annotation's inactive check."""
    app = make_app()
    drive("serial", app)
    app.close_logs()
    assert NULL_TRACER._events == [] and NULL_TRACER.span_stats() == {}


@pytest.mark.parametrize("path,names", [
    ("fused", ["bsp.step", "fused.chunk", "fused.publish", "app.flush_logs"]),
    ("serial", ["worker.local_update", "server.apply", "server.eval",
                "serial.round", "eval.drain"])])
def test_enabled_tracer_still_records_chrome_events(path, names):
    tracer = Tracer()
    app = make_app(tracer=tracer, eval_every=8 if path == "fused" else 1)
    drive(path, app)
    app.close_logs()
    stats = tracer.span_stats()
    assert not set(names) - set(stats), sorted(stats)
    assert not any(name.startswith("kps.") for name in stats)
    if path == "fused":     # one span a dispatch, as tests/test_trace.py
        assert stats["bsp.step"]["count"] == 2
        assert tracer.counters()["bsp.steps"] == 2


def test_fused_theta_is_bitwise_the_same_with_a_tracer_on():
    thetas = []
    for tracer in (None, Tracer()):
        app = make_app(tracer=tracer, task="mlp", eval_every=8)
        drive("fused", app)
        app.close_logs()
        thetas.append(np.asarray(app.server.theta))
    assert np.array_equal(thetas[0], thetas[1])


def test_last_run_counts_the_refresh_and_its_bytes():
    app = make_app(task="mlp", eval_every=8)
    assert app.last_run == {}
    drive("fused", app)
    slabs = [b.snapshot() for b in app.buffers]
    last = app.last_run
    assert last["path"] == "fused" and last["slab_refreshes"] == 1
    assert last["slab_refresh_bytes"] == sum(
        a.nbytes for slab in slabs for a in slab)
    assert 0.0 < last["slab_refresh_s"] < last["seconds"]
    app.run_serial(max_server_iterations=app.server.iterations + 4,
                   pump=lambda: None)
    assert app.last_run["path"] == "serial"
    assert app.last_run["slab_refreshes"] == 0
    assert sorted(app.last_run) == sorted(last)
    app.close_logs()


# -- the head and the tail of a folded task's fused call ----------------------

def test_the_calls_edges_lie_outside_its_chunks_in_their_order(traced):
    """The flat vector up before the first chunk; where the loop ends
    the wait for the queued chunks, then the vector down: three spans
    of the dispatching line, none inside a `fused.chunk`."""
    line = dispatch_line(traced["folded"]["lines"], "bsp.step")
    at = {name: [(s, e) for n, s, e, _ in line if n == name]
          for name in CALL_EDGES + ["fused.chunk"]}
    assert [len(at[name]) for name in CALL_EDGES] == [1, 1, 1]
    chunks = at["fused.chunk"]
    assert len(chunks) == 2
    (up,), (wait,), (down,) = (at[name] for name in CALL_EDGES)
    assert up[1] <= min(s for s, _ in chunks)
    assert max(e for _, e in chunks) <= wait[0] and wait[1] <= down[0]
    stats = [st for n, _, _, st in line if n in ("fused.theta_up",
                                                 "fused.theta_down")]
    assert all(int(st["bytes"]) == 4 * traced["folded"]["app"].server
               .task.num_params for st in stats)


def test_last_run_times_the_calls_edges_where_there_are_any(traced):
    """Three counters, always on: a folded call fills them in and they
    lie inside the call; every other path has the keys at 0.0."""
    last = traced["folded"]["last_run"]
    assert all(last[k] > 0.0 for k in EDGE_SECONDS)
    assert sum(last[k] for k in EDGE_SECONDS) <= last["seconds"]
    for path in ("fused", "serial"):            # unfolded, per-node
        last = traced[path]["last_run"]
        assert [last[k] for k in EDGE_SECONDS] == [0.0, 0.0, 0.0]
        assert not any(name in CALL_EDGES
                       for evs in traced[path]["lines"].values()
                       for name, *_ in evs)
    assert sorted(traced["folded"]["last_run"]) == sorted(
        [*traced["fused"]["last_run"], "counters"])


def test_the_folded_calls_theta_is_the_programs_own(traced):
    """Timing the edges changed no number: the call's parameters are,
    bit for bit, what the three programs give when they are called one
    after another with nothing between them — as the loop called them
    before its edges had spans — and a tracer changes none either."""
    from kafka_ps_tpu.parallel import bsp
    thetas = []
    for tracer in (None, Tracer()):
        app = make_folded_app(tracer=tracer)
        start = np.asarray(app.server.theta).copy()
        drive("folded", app)
        app.close_logs()
        thetas.append(np.asarray(app.server.theta))
    assert np.array_equal(thetas[0], thetas[1])
    task, cfg = app.server.task, app.cfg
    cut, join, _ = bsp.folded_edges(task)
    chunk = bsp.make_bsp_multi_step(cfg.model, 2, cfg.server_lr, 8,
                                    task=task)
    x, y, mask = (jnp.asarray(np.stack(part)) for part in zip(
        *(b.snapshot() for b in app.buffers)))
    leaves = cut(start)
    for _ in range(FUSED_ROUNDS // 8):
        leaves, _, _ = chunk(leaves, x, y, mask)
    assert np.array_equal(np.asarray(join(leaves)), thetas[0])


# -- named scopes in the device programs -------------------------------------

@functools.lru_cache(maxsize=None)
def lowered_text(program: str) -> str:
    from kafka_ps_tpu.models import mlp
    from kafka_ps_tpu.models.task import LogRegTask, fit_delta
    from kafka_ps_tpu.parallel import bsp
    from kafka_ps_tpu.runtime import gang
    cfg = ModelConfig(num_features=16, num_classes=3, hidden_dim=8)
    task = mlp.MLPTask(cfg)
    theta = task.init_params()
    x = jnp.ones((2, 8, 16))
    y = jnp.zeros((2, 8), jnp.int32)
    mask = jnp.ones((2, 8))
    onehot = jax.nn.one_hot(y, cfg.num_rows, dtype=jnp.float32)
    if program == "mlp_local_update":
        # the solver as the BSP round and the gang run it: from one
        # shared flat theta, on the leaves, under a 2-worker vmap
        low = jax.jit(lambda t, xs, os, ms: jax.vmap(
            lambda xx, oo, mm: fit_delta(task, task.unflatten(t),
                                         xx, oo, mm))(xs, os, ms)
                      ).lower(theta, x, onehot, mask)
    elif program == "logreg_local_update":
        logreg = LogRegTask(cfg)
        low = jax.jit(logreg.local_update).lower(
            logreg.init_params(), x[0], y[0], mask[0])
    elif program == "bsp_step":
        low = bsp.make_bsp_step(cfg, 2, 1.0, task=task).lower(
            theta, x, y, mask)
    elif program == "bsp_scan":
        low = bsp.make_bsp_multi_step(cfg, 2, 1.0, 8, task=task).lower(
            theta, x, y, mask)
    elif program == "bsp_scan_mesh":
        from kafka_ps_tpu.parallel.mesh import worker_mesh
        low = bsp.make_bsp_multi_step(
            cfg, 2, 1.0, 8, mesh=worker_mesh(2), task=task).lower(
                theta, x, y, mask)
    elif program == "gang":
        fns = gang._gang_solver_fns("mlp", cfg)
        low = fns["update_eval_bcast"].lower(
            theta, tuple(x), tuple(y), tuple(mask), x[0], y[0])
    return low.as_text(debug_info=True)


FIT = ["kps.fit.grad", "kps.fit.param_step", "kps.fit.loss", "kps.fit.delta"]
BSP = FIT + ["kps.bsp.reduce", "kps.bsp.apply"]


@pytest.mark.parametrize("program,scope", [
    *[("mlp_local_update", s) for s in FIT],
    *[("logreg_local_update", s) for s in FIT],
    *[("bsp_step", s) for s in BSP],
    *[("bsp_scan", s) for s in BSP],
    *[("bsp_scan_mesh", s) for s in BSP],
    *[("gang", s) for s in FIT + ["kps.gang.fit", "kps.gang.eval",
                                  "kps.eval"]]])
def test_lowered_program_carries_the_scope(program, scope):
    assert scope in lowered_text(program)


# -- the flat key-space vector only at the programs' edges (PR 25) ------------

WORKERS, NUM_PARAMS = 2, 8 * 16 + 8 + 4 * 8 + 4     # lowered_text's sizes
FLAT = f"tensor<{NUM_PARAMS}xf32>"


def flat_concatenations(text: str) -> int:
    return len(re.findall(
        rf"stablehlo\.concatenate .*-> {FLAT}(?: loc\(.*\))?$", text, re.M))


def all_reduce_results(text: str) -> list[str]:
    """Result type of each all-reduce (its region's closing line)."""
    return [re.search(r"^\s*\}\) : \([^)]*\) -> (\S+)", part, re.M).group(1)
            for part in text.split('"stablehlo.all_reduce"')[1:]]


@pytest.mark.parametrize("program", [
    "mlp_local_update", "bsp_step", "bsp_scan", "bsp_scan_mesh", "gang"])
def test_no_program_builds_a_workers_by_params_array(program):
    """Inside a solver program the parameters are their leaves: under
    the worker vmap a flat carry is [workers, P], which a TPU tiles over
    (worker, key) and re-lays out every local step (PERF.md §6, PR 25)."""
    text = lowered_text(program)
    assert f"tensor<{WORKERS}x{NUM_PARAMS}x" not in text
    if program == "mlp_local_update":
        assert flat_concatenations(text) == 0        # leaves in, leaves out
    elif program == "gang":
        # the wire contract: still k flat deltas, each flattened after
        # the fan-out from its own row of every leaf
        signature = text[text.index("func.func public @main"):].split(
            "\n", 1)[0]
        assert signature.split(") -> (", 1)[1].count(FLAT) == WORKERS
        assert flat_concatenations(text) == WORKERS
    else:
        # one flat sum a round (the round's body is in the text once),
        # and under a mesh one all-reduce of it beside the scalar loss's
        assert flat_concatenations(text) == 1
        assert all_reduce_results(text) == (
            [FLAT, "tensor<f32>"] if program == "bsp_scan_mesh" else [])


def test_the_log_stacker_has_a_name():
    """`jit_log_stack` in a trace, not `jit__lambda`."""
    assert asynclog._stacker(2).__wrapped__.__name__ == "log_stack"
    text = asynclog._stacker(2).lower((jnp.float32(1), 0.0)).as_text()
    assert "jit_log_stack" in text

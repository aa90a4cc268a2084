"""The `glm4_moe_lite` family through the runtime, at its tiny size on
the CPU: the folded worker axis against the `vmap` (and the classifiers'
programs unchanged), token rows through the buffers and the device
slab, and the task through the CLI's own parser and drives.
tests/test_glm4_moe_lite.py holds the model against its reference."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.data.buffer import SlidingBuffer
from kafka_ps_tpu.models import glm4_moe_lite as glm
from kafka_ps_tpu.models.task import get_task
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "benchmark/families/glm4-moe-lite/tiny.model.json"


@pytest.fixture(scope="module")
def ps_cfg():
    return PSConfig(num_workers=3, task="glm4_moe_lite",
                    model=ModelConfig(num_max_iter=2,
                                      local_learning_rate=0.05,
                                      model_json=TINY),
                    buffer=BufferConfig(min_size=1, max_size=2))


@pytest.fixture(scope="module")
def task(ps_cfg):
    return get_task("glm4_moe_lite", ps_cfg.model)


def rows_of(task, n, seed=3):
    c = task.arch
    return np.random.default_rng(seed).integers(
        0, c.vocab_held, size=(n, c.row_width)).astype(np.int32)


# -- the worker axis: folded against the vmap --------------------------------

def _FoldedMLP(cfg):
    """The MLP told that its update does not batch: the folded programs
    then run it one worker at a time."""
    from kafka_ps_tpu.models.mlp import MLPTask

    class Folded(MLPTask):
        batches_workers = False
        counter_names = ("fits",)

        def fit_counted(self, leaves, x, encoded, mask):
            new, loss = self.fit(leaves, x, encoded, mask)
            return new, loss, jnp.ones((1,), jnp.int32)
    return Folded(cfg)


def _mlp_inputs(workers=4, cap=16, features=8, classes=3):
    cfg = ModelConfig(num_features=features, num_classes=classes,
                      hidden_dim=12, local_learning_rate=0.05)
    rng = np.random.default_rng(2)
    x = rng.standard_normal((workers, cap, features)).astype(np.float32)
    y = rng.integers(1, classes + 1, size=(workers, cap)).astype(np.int32)
    mask = (rng.random((workers, cap)) < 0.8).astype(np.float32)
    return cfg, x, y, mask


def test_the_folded_worker_axis_equals_the_vmap_on_the_mlp():
    cfg, x, y, mask = _mlp_inputs()
    batched = get_task("mlp", cfg)
    folded = _FoldedMLP(cfg)
    theta0 = batched.init_params()
    want, want_l = bsp.make_bsp_multi_step(cfg, 4, 0.25, 3, task=batched)(
        theta0, x, y, mask)
    leaves, got_l, counted = bsp.make_bsp_multi_step(
        cfg, 4, 0.25, 3, task=folded)(folded.unflatten(theta0), x, y, mask)
    # float32 round-off: the sum over the workers is taken in another
    # order (a running sum for a reduction)
    np.testing.assert_allclose(np.asarray(folded.flatten(leaves)),
                               np.asarray(want), rtol=2e-6, atol=2e-7)
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                               rtol=2e-6)
    assert int(counted[0]) == 3 * 4


def test_a_folded_task_has_no_program_over_a_mesh():
    cfg, *_ = _mlp_inputs()
    from kafka_ps_tpu.parallel.mesh import worker_mesh
    with pytest.raises(ValueError, match="no program over a mesh"):
        bsp.make_bsp_step(cfg, 4, 0.25, mesh=worker_mesh(4),
                          task=_FoldedMLP(cfg))


def _parent_multi_step(cfg, task, num_workers, server_lr, rounds):
    """`make_bsp_multi_step` as it stood before this family came (PR 26,
    parallel/bsp.py), written out: the builders one-hot the labels
    themselves and vmap `fit_delta` over the workers."""
    from functools import partial

    from kafka_ps_tpu.models.task import fit_delta

    def round_(theta, x, onehot, mask):
        leaves = task.unflatten(theta)
        deltas, losses = jax.vmap(
            lambda xx, oo, mm: fit_delta(task, leaves, xx, oo, mm)
        )(x, onehot, mask)
        with jax.named_scope("kps.bsp.reduce"):
            delta_sum = task.flatten(
                jax.tree.map(lambda d: d.sum(0), deltas))
            loss_sum = losses.sum()
        with jax.named_scope("kps.bsp.apply"):
            return theta + server_lr * delta_sum, loss_sum / num_workers

    def scanned(theta, x, y, mask, psum_axis):
        onehot = jax.nn.one_hot(y, cfg.num_rows, dtype=jnp.float32)
        return jax.lax.scan(lambda t, _: round_(t, x, onehot, mask),
                            theta, None, length=rounds)
    return jax.jit(partial(scanned, psum_axis=False))


@pytest.mark.parametrize("name", ["mlp", "logreg"])
def test_the_classifiers_compiled_program_is_unchanged(name):
    """Label encoding moved into the task and a folded path came beside
    the vmap: the program a classifier compiles to is, instruction for
    instruction, the one it was."""
    cfg, x, y, mask = _mlp_inputs()
    task = get_task(name, cfg)
    theta0 = task.init_params()
    now = bsp.make_bsp_multi_step(cfg, 4, 0.25, 8, task=task)
    was = _parent_multi_step(cfg, task, 4, 0.25, 8)
    text_now = now.lower(theta0, x, y, mask).as_text()
    text_was = was.lower(theta0, x, y, mask).as_text()
    assert text_now == text_was
    hlo_now = now.lower(theta0, x, y, mask).compile().as_text()
    hlo_was = was.lower(theta0, x, y, mask).compile().as_text()

    def instructions(text):
        """The instructions alone: no metadata, no table of the Python
        frames they were traced from."""
        return [ln.split(", metadata=")[0] for ln in text.splitlines()
                if " = " in ln and not ln.startswith(("HloModule",
                                                      "FileNames",
                                                      "FunctionNames"))]
    assert instructions(hlo_now) == instructions(hlo_was)


# -- token rows through the data path ----------------------------------------

def _buffer(cap=2, width=5):
    ticks = iter(range(0, 10**9, 1000))
    return SlidingBuffer(width, BufferConfig(min_size=1, max_size=cap),
                         clock_ms=lambda: float(next(ticks)),
                         dtype=np.int32)


def test_sliding_buffer_keeps_int32_rows_exact():
    buf = _buffer()
    buf.add(np.asarray([154879, 0, 19359, 7, 2**24 + 1], np.int32), 0)
    x, y, mask = buf.snapshot()
    assert x.dtype == np.int32 and y.dtype == np.int32
    assert x[0].tolist() == [154879, 0, 19359, 7, 2**24 + 1]
    assert mask.tolist() == [1.0, 0.0]


def test_sliding_buffer_evicts_the_oldest_token_row():
    buf = _buffer()
    for i in range(3):
        buf.add(np.full((5,), i + 1, np.int32), 0)
    x, _, mask = buf.snapshot()
    assert mask.tolist() == [1.0, 1.0]
    assert sorted(x[:, 0].tolist()) == [2, 3]      # row 1 went
    assert buf.num_tuples_seen == 3


def test_sliding_buffer_takes_a_token_row_from_the_csv_hop():
    """The CSV producer hands a row over as {column: value}."""
    buf = _buffer()
    buf.add({0: 12.0, 2: 154879.0, 4: 3.0}, 0)
    x, _, _ = buf.snapshot()
    assert x.dtype == np.int32 and x[0].tolist() == [12, 0, 154879, 0, 3]
    slots, xr, _, _ = buf.drain_dirty()
    assert xr.dtype == np.int32 and slots.tolist() == [0]


def test_sliding_buffer_state_round_trip_with_int32_rows():
    buf = _buffer()
    buf.add(np.arange(5, dtype=np.int32) + 100, 0)
    buf.add(np.arange(5, dtype=np.int32) + 200, 0)
    state = buf.state()
    assert state["x"].dtype == np.int32
    other = _buffer()
    other.restore_state(state)
    for a, b in zip(buf.snapshot(), other.snapshot()):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    # a float buffer is untouched by all this
    plain = SlidingBuffer(5, BufferConfig(min_size=1, max_size=2))
    assert plain.x.dtype == np.float32 and plain.dtype == np.float32


def test_the_device_slab_stores_token_rows_as_they_are():
    from kafka_ps_tpu.compress import slab
    store = slab.SlabStore("f32", 2, 5, row_dtype=np.int32)
    rows = np.asarray([[1, 2, 3, 4, 154879], [0, 0, 0, 0, 0]], np.int32)
    store.upload_full(rows, np.zeros(2, np.int32), np.asarray([1.0, 0.0]))
    store.apply_rows([1], np.asarray([[9, 8, 7, 6, 5]], np.int32), [0],
                     [1.0])
    x, _, mask = store.arrays()
    assert x.dtype == jnp.int32 and slab.decode_x(x).dtype == jnp.int32
    assert np.asarray(x).tolist() == [[1, 2, 3, 4, 154879], [9, 8, 7, 6, 5]]
    assert np.asarray(mask).tolist() == [1.0, 1.0]
    with pytest.raises(ValueError, match="stored as they are"):
        slab.SlabStore("bf16", 2, 5, row_dtype=np.int32)


# -- through the CLI's own parser and drives ---------------------------------

def _write_token_csvs(task, train_rows=24, test_rows=3):
    from kafka_ps_tpu.data.synth import write_csv
    rows = rows_of(task, train_rows + test_rows, seed=1)
    zeros = np.zeros((len(rows),), np.int32)
    write_csv("train.csv", rows[:train_rows], zeros[:train_rows])
    write_csv("test.csv", rows[train_rows:], zeros[train_rows:])


def _cli(*more):
    return ["-training", "train.csv", "-test", "test.csv", "--task",
            "glm4_moe_lite", "--model_json", TINY, "--num_workers", "2",
            "-min", "1", "-max", "2", "--local_learning_rate", "0.05",
            "-p", "1", "-l", *more]


SERVER_COLUMNS = ["timestamp", "partition", "vectorClock", "loss",
                  "fMeasure", "accuracy"]


@pytest.mark.parametrize("drive,iterations", [
    (("--fused", "--eval_every", "8"), 32),
    (("--fused",), 6),
    (("--mode", "serial"), 8),
    (("--mode", "serial", "--no-gang", "--no-eval-async"), 8)])
def test_the_task_runs_through_the_clis_drives(tmp_path, monkeypatch, task,
                                               drive, iterations):
    import pandas as pd

    from kafka_ps_tpu.cli import run as run_mod
    monkeypatch.chdir(tmp_path)
    _write_token_csvs(task)
    args = run_mod.build_parser().parse_args(
        _cli(*drive, "--max_iterations", str(iterations)))
    assert run_mod.run_with_args(args) == 0
    server = pd.read_csv("logs-server.csv", sep=";")
    worker = pd.read_csv("logs-worker.csv", sep=";")
    assert list(server.columns) == SERVER_COLUMNS
    assert list(worker.columns) == SERVER_COLUMNS + ["numTuplesSeen"]
    assert len(server) >= 1 and len(worker) >= iterations // 2
    assert np.isfinite(server[["loss", "fMeasure", "accuracy"]]
                       .to_numpy()).all()
    assert (server["loss"] > 0).all() and (worker["loss"] > 0).all()
    assert server["accuracy"].between(0, 1).all()


def test_the_cli_refuses_the_levers_the_task_cannot_hold_in_one_message():
    from kafka_ps_tpu.cli import run as run_mod
    args = run_mod.build_parser().parse_args(
        _cli("--compress", "int8", "--slab-dtype", "bf16",
             "--tier-hot-bytes", "4096"))
    with pytest.raises(SystemExit) as e:
        run_mod.cfg_from_args(args)
    said = str(e.value)
    assert said.startswith("--task glm4_moe_lite cannot run with ")
    for flag in ("--compress", "--slab-dtype", "--tier-hot-bytes"):
        assert flag + ":" in said
    # and the task without its file, or a file without the task
    bare = [a for a in _cli() if a not in ("--model_json", TINY)]
    with pytest.raises(SystemExit, match="needs --model_json"):
        run_mod.cfg_from_args(run_mod.build_parser().parse_args(bare))
    other = ["--task", "mlp", "--model_json", TINY]
    with pytest.raises(SystemExit, match="no file of its own"):
        run_mod.cfg_from_args(run_mod.build_parser().parse_args(other))


def test_a_relative_model_file_is_taken_from_the_repositorys_root(
        tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert not os.path.exists(TINY)
    c = glm.load_config(TINY)
    assert c.hidden_size == 64
    body = json.load(open(os.path.join(ROOT, TINY)))
    path = tmp_path / "model.json"
    path.write_text(json.dumps(dict(body, experts_held=9)))
    with pytest.raises(ValueError, match="expert_offset"):
        glm.load_config(str(path))


def test_the_fused_loop_keeps_the_flat_vector_at_the_calls_edges(task,
                                                                 ps_cfg):
    """A folded task is carried through `run_fused_bsp` as its leaves;
    the server's vector is the call's result, the counters are summed
    over the call, and a second call takes up where the first ended."""
    from kafka_ps_tpu.runtime.app import StreamingPSApp
    from kafka_ps_tpu.utils.trace import Tracer
    cfg = dataclasses.replace(ps_cfg, num_workers=2, eval_every=8)
    test_rows = rows_of(task, 2, seed=8)
    tracer = Tracer()
    app = StreamingPSApp(cfg, test_x=test_rows,
                         test_y=np.zeros(2, np.int32), tracer=tracer)
    for i, row in enumerate(rows_of(task, 4, seed=9)):
        app.data_sink(i % 2, row, 0)
    start = np.asarray(app.server.theta).copy()
    app.run_fused_bsp(max_server_iterations=16 * 2)
    first = np.asarray(app.server.theta).copy()
    assert app.server.iterations == 32 and np.any(first != start)
    counters = app.last_run["counters"]
    assert set(counters) == set(task.counter_names)
    assert counters["data.tokens"] == 32 * 2 * task.arch.sequence_length
    assert tracer.counters()["moe.assignments_here"] == counters[
        "moe.assignments_here"]
    app.run_fused_bsp(max_server_iterations=24 * 2)
    assert np.any(np.asarray(app.server.theta) != first)
    assert app.server.last_metrics is not None
    app.close_logs()


def _folded_app(task, ps_cfg, **more):
    from kafka_ps_tpu.runtime.app import StreamingPSApp
    cfg = dataclasses.replace(ps_cfg, num_workers=2, eval_every=8)
    app = StreamingPSApp(cfg, test_x=rows_of(task, 2, seed=8),
                         test_y=np.zeros(2, np.int32), **more)
    for i, row in enumerate(rows_of(task, 4, seed=9)):
        app.data_sink(i % 2, row, 0)
    return app


def test_a_checkpoint_inside_a_fused_call_is_of_one_clock(task, ps_cfg,
                                                          tmp_path):
    """A folded task's loop keeps the leaves on the device through the
    call; a checkpoint that falls due at a chunk's boundary holds the
    parameters OF THAT CLOCK beside its clocks and iterations, and a
    resume from it ends where the uninterrupted run ends."""
    from kafka_ps_tpu.utils import checkpoint as ckpt
    whole = _folded_app(task, ps_cfg)
    whole.server.checkpoint_path = str(tmp_path / "mid.npz")
    # 2 workers: a chunk is 16 iterations, so the only save of the
    # 24-clock call falls after its second chunk (32 >= 24, 48 - 32 < 24)
    whole.server.checkpoint_every = 24
    whole.run_fused_bsp(max_server_iterations=24 * 2)
    with np.load(whole.server.checkpoint_path) as z:
        saved = {k: z[k].copy() for k in ("theta", "clocks", "iterations")}
    assert int(saved["iterations"]) == 32
    assert saved["clocks"].tolist() == [16, 16]
    until16 = _folded_app(task, ps_cfg)
    until16.run_fused_bsp(max_server_iterations=16 * 2)
    np.testing.assert_array_equal(saved["theta"],
                                  np.asarray(until16.server.theta))
    resumed = _folded_app(task, ps_cfg)
    ckpt.restore(whole.server.checkpoint_path, resumed.server)
    resumed.run_fused_bsp(max_server_iterations=24 * 2)
    assert resumed.server.iterations == 48
    np.testing.assert_array_equal(np.asarray(resumed.server.theta),
                                  np.asarray(whole.server.theta))
    for app in (whole, until16, resumed):
        app.close_logs()


def test_a_snapshot_inside_a_fused_call_is_of_its_clock(task, ps_cfg):
    """With the serving plane attached, every chunk boundary publishes
    the parameters that hold every delta up to the clock it is stamped
    with, not the ones the call began with."""
    app = _folded_app(task, ps_cfg)
    app.enable_serving()
    published = []
    publish = app.server.serving.publish

    def recording(theta, clock, **kw):
        published.append((int(clock), np.asarray(theta).copy()))
        return publish(theta, clock, **kw)
    app.server.serving.publish = recording
    app.run_fused_bsp(max_server_iterations=16 * 2)
    assert [c for c, _ in published] == [8, 16, 16]
    until8 = _folded_app(task, ps_cfg)
    until8.run_fused_bsp(max_server_iterations=8 * 2)
    np.testing.assert_array_equal(published[0][1],
                                  np.asarray(until8.server.theta))
    np.testing.assert_array_equal(published[1][1],
                                  np.asarray(app.server.theta))
    app.close_serving()
    for a in (app, until8):
        a.close_logs()


# -- the benchmark's reader of the whole update's roofline share ------------

def _layer_metric(name):
    import importlib.util
    import sys
    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        "layer_metric_test_" + name,
        os.path.join(bench, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("starts,want", [
    ([0.5, 1.5, 2.5, 3.5], (1.0, 4)),     # once an update, 1 s apart
    ([0.5, 1.5], None)])                  # too few to tell a period
def test_the_update_period_is_read_from_the_marker_instruction(starts, want):
    """`lm_update_roofline_share` times an update by the instruction
    under `kps.fit.delta` that starts most often inside the solver
    programs' runs: operations outside those runs, under other scopes,
    or named by another executable's table do not count."""
    reader = _layer_metric("lm_update_roofline_share")
    ops = [("%fusion.7 = f32[8]{0} fusion(...)", s, s + 0.1) for s in starts]
    ops += [("%fusion.9 = f32[8]{0} fusion(...)", s + 0.2, s + 0.3)
            for s in starts for _ in range(2)]          # another scope
    ops += [("%fusion.7 = f32[8]{0} fusion(...)", 9.0, 9.1)]   # outside
    ops.sort(key=lambda o: o[1])
    tables = [{"fusion.7": "jit(scanned)/kps.fit.delta/sub",
               "fusion.9": "jit(scanned)/kps.fit.grad/dot"},
              {"fusion.1": "jit(scanned)/kps.fit.delta/sub"}]
    got = reader.marker_period(ops, [(0.0, 2.0), (2.0, 4.0)], tables,
                               "kps.fit.delta")
    assert got == want

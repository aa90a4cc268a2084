"""The `nemotron_h` family through the runtime, at its tiny size on the
CPU: the task through the CLI's own parser and drives (fused and
per-node, the gang with it), what both language-model tasks refuse, a
save inside a fused call and the resume, and the proof that an edit
to the shared frame (models/lm_common.py) changes nothing of what the
`glm4_moe_lite` family traces.  tests/test_nemotron_h.py holds the
model against its reference."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.models import glm4_moe_lite as glm
from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import nemotron_h as nh
from kafka_ps_tpu.models.task import get_task, task_class
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "benchmark/families/nemotron-h/tiny.model.json"
GLM_TINY = "benchmark/families/glm4-moe-lite/tiny.model.json"
MODEL_FILE = {"nemotron_h": TINY, "glm4_moe_lite": GLM_TINY}


@pytest.fixture(scope="module")
def ps_cfg():
    return PSConfig(num_workers=3, task="nemotron_h",
                    model=ModelConfig(num_max_iter=2,
                                      local_learning_rate=0.05,
                                      model_json=TINY),
                    buffer=BufferConfig(min_size=1, max_size=2))


@pytest.fixture(scope="module")
def task(ps_cfg):
    return get_task("nemotron_h", ps_cfg.model)


def rows_of(task, n, seed=3):
    return np.random.default_rng(seed).integers(
        0, task.arch.vocab_held, size=(n, task.row_width)).astype(np.int32)


# -- one frame, two families ---------------------------------------------------

def test_both_families_import_one_frame():
    """One expert layer, one head, one flat key space, one task frame:
    the two modules hold the shared module's own objects."""
    for module in (glm, nh):
        assert module.rms_norm is lm.rms_norm
        for shared in ("route", "live_rows_bound", "fit_counted",
                       "evaluate_leaves", "unflatten_leaves", "head_nll"):
            assert shared not in vars(module), (module.__name__, shared)
    for family in (glm.Glm4MoeLiteTask, nh.NemotronHTask):
        assert issubclass(family, lm.TokenRowsTask)
        for shared in ("fit_counted", "evaluate_leaves", "unflatten",
                       "flatten", "init_params", "encode_labels", "fit"):
            assert shared not in vars(family), (family, shared)
    assert nh.NemotronHTask.counter_names == lm.COUNTERS + ("ssm.chunks",)
    assert glm.Glm4MoeLiteTask.counter_names is lm.COUNTERS


def test_the_single_step_is_one_round_of_the_chunk(task, ps_cfg):
    w = ps_cfg.num_workers
    theta = task.init_params()
    x = np.stack([rows_of(task, 2, seed=20 + i) for i in range(w)])
    y, mask = np.zeros((w, 2), np.int32), np.ones((w, 2), np.float32)
    step = bsp.make_bsp_step(ps_cfg.model, w, ps_cfg.server_lr, task=task)
    chunk = bsp.make_bsp_multi_step(ps_cfg.model, w, ps_cfg.server_lr, 1,
                                    task=task)
    a, loss_a, _ = step(task.unflatten(theta), x, y, mask)
    b, loss_b, _ = chunk(task.unflatten(theta), x, y, mask)
    assert loss_a.shape == () and loss_b.shape == (1,)
    for name in a:
        assert np.array_equal(np.asarray(a[name]), np.asarray(b[name]))


# -- the other family's programs are the parent's ------------------------------

@pytest.fixture(scope="module")
def glm_stablehlo():
    """The StableHLO text of the three programs of the `glm4_moe_lite`
    family at its tiny size, as this tree traces them."""
    cfg = ModelConfig(num_max_iter=2, local_learning_rate=0.05,
                      model_json=GLM_TINY)
    task = get_task("glm4_moe_lite", cfg)
    leaves = jax.eval_shape(task.unflatten, jax.ShapeDtypeStruct(
        (task.num_params,), jnp.float32))
    w, cap = 3, 2

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)
    chunk = bsp.make_bsp_multi_step(cfg, w, 1.0 / w, 8, task=task)
    return {
        "fit_counted": jax.jit(task.fit_counted).lower(
            leaves, shaped((cap, task.row_width), jnp.int32), None,
            shaped((cap,), jnp.float32)).as_text(),
        "evaluate_leaves": jax.jit(task.evaluate_leaves).lower(
            leaves, shaped((3, task.row_width), jnp.int32), None).as_text(),
        "folded_chunk": chunk.lower(
            leaves, shaped((w, cap, task.row_width), jnp.int32),
            shaped((w, cap), jnp.int32),
            shaped((w, cap), jnp.float32)).as_text()}


@pytest.mark.parametrize("program", ["fit_counted", "evaluate_leaves",
                                     "folded_chunk"])
def test_glm4_moe_lites_stablehlo_is_the_parents(glm_stablehlo, program):
    """What the family shares with `nemotron_h` and `afmoe` lies in
    models/lm_common.py, and an edit there must leave what this family
    traces alone: the program is, character for character, the one
    tests/fixtures/glm4_tiny_stablehlo.json holds the digests of.  They
    are PR 40's own tree's — that PR made the over-the-bound branch of
    `routed_experts`' `cond` a `jax.checkpoint`, which changed every
    expert family's programs on purpose (all three fixtures were
    rewritten with it); from PR 38, which wrote this family's expert
    layers out and touched no shared line, they were that tree's, and
    until then commit e9a1946's, the one before the shared frame moved
    out of models/glm4_moe_lite.py."""
    stated = json.load(open(os.path.join(
        ROOT, "tests", "fixtures", "glm4_tiny_stablehlo.json")))
    if stated["jax"] != jax.__version__:
        pytest.skip(f"the digests were written under jax {stated['jax']}; "
                    f"this is {jax.__version__}, whose printer may differ")
    text = glm_stablehlo[program]
    assert "stablehlo." in text and len(text) > 50_000
    assert hashlib.sha256(text.encode()).hexdigest() \
        == stated["programs"][program]


# -- through the CLI's own parser and drives ---------------------------------

def _write_token_csvs(task, train_rows=24, test_rows=3):
    from kafka_ps_tpu.data.synth import write_csv
    rows = rows_of(task, train_rows + test_rows, seed=1)
    zeros = np.zeros((len(rows),), np.int32)
    write_csv("train.csv", rows[:train_rows], zeros[:train_rows])
    write_csv("test.csv", rows[train_rows:], zeros[train_rows:])


def _cli(*more, name="nemotron_h"):
    return ["-training", "train.csv", "-test", "test.csv", "--task", name,
            "--model_json", MODEL_FILE[name], "--num_workers", "2",
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


def test_the_per_node_gang_runs_members_of_the_task(task, ps_cfg):
    """`run_serial` with the gang on: one dispatch takes every ready
    member of the new task, one member at a time inside it
    (`over_members`), and the parameters move as without the gang."""
    from kafka_ps_tpu.runtime.app import StreamingPSApp
    from kafka_ps_tpu.utils.trace import Tracer

    def run(use_gang):
        cfg = dataclasses.replace(ps_cfg, num_workers=2, use_gang=use_gang)
        tracer = Tracer()
        app = StreamingPSApp(cfg, test_x=rows_of(task, 2, seed=8),
                             test_y=np.zeros(2, np.int32), tracer=tracer)
        for i, row in enumerate(rows_of(task, 4, seed=9)):
            app.data_sink(i % 2, row, 0)
        app.run_serial(max_server_iterations=6, pump=lambda: None)
        theta = np.asarray(app.server.theta).copy()
        app.close_logs()
        return theta, tracer.counters()
    with_gang, counted = run(True)
    without, counted_off = run(False)
    assert counted.get("gang.batched_dispatches", 0) > 0
    assert counted["dispatch.device"] < counted_off["dispatch.device"]
    np.testing.assert_allclose(with_gang, without, rtol=1e-5, atol=1e-7)
    assert np.any(with_gang != np.asarray(task.init_params()))


def _refusal(name):
    """What the CLI says to four levers at once, for `--task name`."""
    from kafka_ps_tpu.cli import run as run_mod
    args = run_mod.build_parser().parse_args(
        _cli("--compress", "int8", "--slab-dtype", "bf16",
             "--tier-hot-bytes", "4096", "--param_shards", "2", name=name))
    with pytest.raises(SystemExit) as e:
        run_mod.cfg_from_args(args)
    return str(e.value)


@pytest.mark.parametrize("name,other", [("nemotron_h", "glm4_moe_lite"),
                                        ("glm4_moe_lite", "nemotron_h")])
def test_both_language_model_tasks_refuse_the_same_levers(name, other):
    """What a task cannot run with follows from what its family says of
    itself — a file of its own, rows that are tokens, no program over a
    mesh — so both families refuse the same levers with the same words."""
    from kafka_ps_tpu.cli import run as run_mod
    family = task_class(name)
    assert family.model_file and not family.batches_workers
    assert family.row_dtype is np.int32
    said = _refusal(name)
    assert said.startswith(f"--task {name} cannot run with ")
    levers = {flag: why for _, what in run_mod.TASK_REFUSES
              for flag, (_, why) in what.items()}
    for flag in ("compress", "slab_dtype", "tier_hot_bytes", "param_shards"):
        assert f"--{flag.replace('_', '-')}: {levers[flag]}" in said
    assert said.replace(name, "X") == _refusal(other).replace(other, "X")
    # the task without its file, or a file without such a task
    bare = [a for a in _cli(name=name)
            if a not in ("--model_json", MODEL_FILE[name])]
    with pytest.raises(SystemExit, match=f"--task {name} needs --model_json"):
        run_mod.cfg_from_args(run_mod.build_parser().parse_args(bare))
    plain = ["--task", "mlp", "--model_json", MODEL_FILE[name]]
    with pytest.raises(SystemExit, match="no file of its own"):
        run_mod.cfg_from_args(run_mod.build_parser().parse_args(plain))


def test_the_classifiers_refuse_nothing_and_name_no_file():
    from kafka_ps_tpu.cli import run as run_mod
    for name in ("logreg", "mlp"):
        family = task_class(name)
        assert not family.model_file and family.batches_workers
        args = run_mod.build_parser().parse_args(
            ["--task", name, "--compress", "int8", "--slab-dtype", "bf16"])
        assert run_mod.cfg_from_args(args).task == name
    with pytest.raises(ValueError, match="unknown task"):
        task_class("no_such_family")


def test_a_relative_model_file_is_taken_from_the_repositorys_root(
        tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert not os.path.exists(TINY)
    c = nh.load_config(TINY)
    assert c.hidden_size == 64 and c.hybrid_override_pattern == "MEM*E"
    assert c.kinds("M") == 2 and c.kinds("E") == 2 and c.kinds("*") == 1


def _folded_app(task, ps_cfg, **more):
    from kafka_ps_tpu.runtime.app import StreamingPSApp
    cfg = dataclasses.replace(ps_cfg, num_workers=2, eval_every=8)
    app = StreamingPSApp(cfg, test_x=rows_of(task, 2, seed=8),
                         test_y=np.zeros(2, np.int32), **more)
    for i, row in enumerate(rows_of(task, 4, seed=9)):
        app.data_sink(i % 2, row, 0)
    return app


def test_the_fused_loop_sums_the_familys_counters_over_a_call(task, ps_cfg):
    from kafka_ps_tpu.utils.trace import Tracer
    tracer = Tracer()
    app = _folded_app(task, ps_cfg, tracer=tracer)
    start = np.asarray(app.server.theta).copy()
    app.run_fused_bsp(max_server_iterations=16 * 2)
    assert app.server.iterations == 32
    assert np.any(np.asarray(app.server.theta) != start)
    counters = app.last_run["counters"]
    assert set(counters) == set(task.counter_names)
    c = task.arch
    assert counters["data.tokens"] == 32 * 2 * c.sequence_length
    # 32 updates x (k + 1) passes x 2 rows x 4 chunks x 2 Mamba-2 blocks
    assert counters["ssm.chunks"] == 32 * 3 * 2 * c.chunks_a_row * 2
    assert tracer.counters()["ssm.chunks"] == counters["ssm.chunks"]
    assert app.server.last_metrics is not None
    app.close_logs()


def test_a_save_inside_a_fused_call_and_the_resume(task, ps_cfg, tmp_path):
    """A checkpoint that falls due at a chunk's boundary inside a fused
    call holds the parameters OF THAT CLOCK, and a resume from it ends
    where the uninterrupted run ends."""
    from kafka_ps_tpu.utils import checkpoint as ckpt
    whole = _folded_app(task, ps_cfg)
    whole.server.checkpoint_path = str(tmp_path / "mid.npz")
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

"""The `afmoe` family through the runtime, at its tiny size on the CPU:
the task through the CLI's own parser and drives (fused and per-node,
the gang with it), what the three language-model tasks refuse, a save
inside a fused call and the resume, and the proof that the blocked
core, RoPE and the gated expert in models/lm_common.py changed nothing
of what the other two families trace.  tests/test_afmoe.py holds the
model against its reference."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.models import afmoe
from kafka_ps_tpu.models import glm4_moe_lite as glm
from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import nemotron_h as nh
from kafka_ps_tpu.models.task import get_task, task_class
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL_FILE = {"afmoe": "benchmark/families/afmoe/tiny.model.json",
              "nemotron_h": "benchmark/families/nemotron-h/tiny.model.json",
              "glm4_moe_lite":
              "benchmark/families/glm4-moe-lite/tiny.model.json"}
# the GLM family's digests are held by tests/test_nemotron_h_runtime.py
FIXTURE = {"nemotron_h": "nemotron_tiny_stablehlo.json"}
TINY = MODEL_FILE["afmoe"]


@pytest.fixture(scope="module")
def ps_cfg():
    return PSConfig(num_workers=3, task="afmoe",
                    model=ModelConfig(num_max_iter=2,
                                      local_learning_rate=0.05,
                                      model_json=TINY),
                    buffer=BufferConfig(min_size=1, max_size=2))


@pytest.fixture(scope="module")
def task(ps_cfg):
    return get_task("afmoe", ps_cfg.model)


def rows_of(task, n, seed=3):
    return np.random.default_rng(seed).integers(
        0, task.arch.vocab_held, size=(n, task.row_width)).astype(np.int32)


# -- one frame, three families ---------------------------------------------------

def test_the_three_families_import_one_frame():
    """One attention core, one RoPE, one gated expert, one expert
    layer, one task frame: the modules hold the shared module's own
    objects, and none keeps a copy."""
    assert glm.rope is lm.rope
    assert afmoe.swiglu is lm.swiglu and glm.swiglu is lm.swiglu
    assert glm.swiglu_experts is lm.swiglu_experts
    # a head's norm and RoPE are the frame's one pass in the third
    # family (`lm.head_norm_rope`, PR 43), which keeps neither by name
    assert not {"rope", "rms_norm", "head_norm_rope"} & set(vars(afmoe))
    for module in (glm, nh):
        assert module.rms_norm is lm.rms_norm
    for module in (glm, nh, afmoe):
        for shared in ("route", "live_rows_bound", "fit_counted",
                       "evaluate_leaves", "head_nll", "blocked_attention",
                       "key_span"):
            assert shared not in vars(module), (module.__name__, shared)
    assert issubclass(afmoe.AfmoeTask, lm.TokenRowsTask)
    for shared in ("fit_counted", "evaluate_leaves", "unflatten", "flatten",
                   "init_params", "encode_labels", "fit"):
        assert shared not in vars(afmoe.AfmoeTask), shared
    assert afmoe.AfmoeTask.counter_names == lm.COUNTERS + (
        "attn.pairs_window", "attn.pairs_full", "attn.block_pairs",
        "attn.kernel_block_pairs", "attn.norm_rope_rows",
        "attn.norm_rope_kernel_rows")


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


# -- the other families' programs are the parent's --------------------------------

@pytest.fixture(scope="module")
def stablehlo():
    """{family: the StableHLO text of its three programs at its tiny
    size}, as this tree traces them."""
    def programs(name):
        cfg = ModelConfig(num_max_iter=2, local_learning_rate=0.05,
                          model_json=MODEL_FILE[name])
        task = get_task(name, cfg)
        leaves = jax.eval_shape(task.unflatten, jax.ShapeDtypeStruct(
            (task.num_params,), jnp.float32))
        w, cap = 3, 2
        shaped = jax.ShapeDtypeStruct
        chunk = bsp.make_bsp_multi_step(cfg, w, 1.0 / w, 8, task=task)
        return {
            "fit_counted": jax.jit(task.fit_counted).lower(
                leaves, shaped((cap, task.row_width), jnp.int32), None,
                shaped((cap,), jnp.float32)).as_text(),
            "evaluate_leaves": jax.jit(task.evaluate_leaves).lower(
                leaves, shaped((3, task.row_width), jnp.int32),
                None).as_text(),
            "folded_chunk": chunk.lower(
                leaves, shaped((w, cap, task.row_width), jnp.int32),
                shaped((w, cap), jnp.int32),
                shaped((w, cap), jnp.float32)).as_text()}
    return {name: programs(name) for name in FIXTURE}


@pytest.mark.parametrize("program", ["fit_counted", "evaluate_leaves",
                                     "folded_chunk"])
@pytest.mark.parametrize("name", sorted(FIXTURE))
def test_the_other_families_stablehlo_is_the_parents(stablehlo, name,
                                                     program):
    """RoPE and the gated expert moved to models/lm_common.py and the
    blocked core stands beside them: the programs the second family
    traces are, character for character, the ones the commit before
    traced (tests/fixtures/ holds the digests, written from the commit
    its `_what` names; the first family's are held by
    tests/test_nemotron_h_runtime.py).  The digests are PR 40's tree's
    since that PR changed the expert layer every family shares, on
    purpose (`routed_experts`' branch over the bound a
    `jax.checkpoint`); until then commit 1187fd0's, PR 32."""
    stated = json.load(open(os.path.join(ROOT, "tests", "fixtures",
                                         FIXTURE[name])))
    if stated["jax"] != jax.__version__:
        pytest.skip(f"the digests were written under jax {stated['jax']}; "
                    f"this is {jax.__version__}, whose printer may differ")
    text = stablehlo[name][program]
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


def _cli(*more, name="afmoe"):
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


@pytest.mark.parametrize("other", ["glm4_moe_lite", "nemotron_h"])
def test_the_three_language_model_tasks_refuse_the_same_levers(other):
    """What a task cannot run with follows from what its family says of
    itself — a file of its own, rows that are tokens, no program over a
    mesh — so the new family refuses the same levers with the same
    words as each of the other two."""
    from kafka_ps_tpu.cli import run as run_mod
    family = task_class("afmoe")
    assert family.model_file and not family.batches_workers
    assert family.row_dtype is np.int32
    said = _refusal("afmoe")
    assert said.startswith("--task afmoe cannot run with ")
    levers = {flag: why for _, what in run_mod.TASK_REFUSES
              for flag, (_, why) in what.items()}
    for flag in ("compress", "slab_dtype", "tier_hot_bytes", "param_shards"):
        assert f"--{flag.replace('_', '-')}: {levers[flag]}" in said
    assert said.replace("afmoe", "X") == _refusal(other).replace(other, "X")
    # the task without its file, or a file without such a task
    bare = [a for a in _cli() if a not in ("--model_json", TINY)]
    with pytest.raises(SystemExit, match="--task afmoe needs --model_json"):
        run_mod.cfg_from_args(run_mod.build_parser().parse_args(bare))
    plain = ["--task", "mlp", "--model_json", TINY]
    with pytest.raises(SystemExit, match="no file of its own"):
        run_mod.cfg_from_args(run_mod.build_parser().parse_args(plain))
    # one name more, and no new flag: the parser's other options are
    # the parent's
    parser = run_mod.build_parser()
    task_flag = next(a for a in parser._actions if a.dest == "task")
    assert task_flag.choices[:5] == ["logreg", "mlp", "glm4_moe_lite",
                                     "nemotron_h", "afmoe"]
    assert "afmoe" in next(a for a in parser._actions
                           if a.dest == "model_json").help


def test_a_relative_model_file_is_taken_from_the_repositorys_root(
        tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert not os.path.exists(TINY)
    c = afmoe.load_config(TINY)
    assert c.hidden_size == 64 and c.sliding_window == 8
    assert c.layers(afmoe.SLIDING) == 4 and c.layers(afmoe.FULL) == 1
    assert c.num_moe_layers == 4 and c.attention_block == 8
    assert (c.n_routed_experts, c.norm_topk_prob, c.routed_scaling_factor) \
        == (8, True, 2.826)


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
    # 32 updates x (k + 1) passes x 2 rows x the pairs of a row's pass,
    # in units of 1,024 pairs rounded down a pass
    window, full, blocks = afmoe.pair_counts(c)
    assert counters["attn.pairs_window"] == 32 * 3 * (2 * window // 1024)
    assert counters["attn.pairs_full"] == 32 * 3 * (2 * full // 1024)
    assert counters["attn.block_pairs"] == 32 * 3 * (2 * blocks // 1024)
    assert counters["attn.block_pairs"] > counters["attn.pairs_window"] > 0
    # through the CPU runtime the core is its plain tiles, whatever the
    # size: the kernel computed none of those blocks
    assert counters["attn.kernel_block_pairs"] == 0
    # q's and k's head rows through every layer, and no kernel either
    assert counters["attn.norm_rope_rows"] == 32 * 3 * (
        2 * c.sequence_length * c.num_hidden_layers
        * (c.num_attention_heads + c.num_key_value_heads) // 1024) > 0
    assert counters["attn.norm_rope_kernel_rows"] == 0
    assert tracer.counters()["attn.block_pairs"] \
        == counters["attn.block_pairs"]
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

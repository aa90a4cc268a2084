"""The `mellum` family through the runtime, at its tiny size on the CPU:
the task through `StreamingPSApp`'s fused BSP loop for 8 clocks against
the benchmark's reference, through the CLI's own parser and drives
(fused and per-node), what the five language-model tasks refuse, the
counters of a fused call (the placement's among them), and the proof
that a fifth family in models/lm_common.py's frame changed nothing of
what the fourth family traces (the first three families' digests are
held by tests/test_nemotron_h_runtime.py, tests/test_afmoe_runtime.py
and tests/test_ouro_runtime.py).  tests/test_mellum.py holds the model
against its reference."""

import dataclasses
import hashlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.models import afmoe
from kafka_ps_tpu.models import glm4_moe_lite as glm
from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import mellum
from kafka_ps_tpu.models import nemotron_h as nh
from kafka_ps_tpu.models import ouro
from kafka_ps_tpu.models.task import get_task, task_class
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL_FILE = {"mellum": "benchmark/families/mellum/tiny.model.json",
              "ouro": "benchmark/families/ouro/tiny.model.json",
              "afmoe": "benchmark/families/afmoe/tiny.model.json",
              "nemotron_h": "benchmark/families/nemotron-h/tiny.model.json",
              "glm4_moe_lite":
              "benchmark/families/glm4-moe-lite/tiny.model.json"}
FIXTURE = {"ouro": "ouro_tiny_stablehlo.json"}
TINY = MODEL_FILE["mellum"]


@pytest.fixture(scope="module")
def ps_cfg():
    return PSConfig(num_workers=3, task="mellum",
                    model=ModelConfig(num_max_iter=2,
                                      local_learning_rate=0.05,
                                      model_json=TINY),
                    buffer=BufferConfig(min_size=1, max_size=2))


@pytest.fixture(scope="module")
def task(ps_cfg):
    return get_task("mellum", ps_cfg.model)


def rows_of(task, n, seed=3):
    return np.random.default_rng(seed).integers(
        0, task.arch.vocab_held, size=(n, task.row_width)).astype(np.int32)


# -- one frame, five families ----------------------------------------------------

def test_the_five_families_import_one_frame():
    """One attention core, one expert layer, one task frame: the new
    module holds the shared module's own objects and keeps no copy; its
    router and its RoPE rules are its own, and the frame's stay the
    other families'."""
    assert mellum.sub is lm.sub
    for module in (glm, nh, afmoe, ouro, mellum):
        for shared in ("fit_counted", "evaluate_leaves", "routed_experts",
                       "blocked_attention", "key_span", "head_nll"):
            assert shared not in vars(module), (module.__name__, shared)
    assert mellum.route is not lm.route
    # its RoPE rules are tables (`rope_tables`) for the frame's one
    # pass over a head's norm and RoPE (`lm.head_norm_rope`, PR 43)
    assert not {"rope", "rms_norm", "head_norm_rope"} & set(vars(mellum))
    assert issubclass(mellum.MellumTask, lm.TokenRowsTask)
    for shared in ("evaluate_leaves", "unflatten", "flatten", "init_params",
                   "encode_labels", "fit"):
        assert shared not in vars(mellum.MellumTask), shared
    assert get_task("mellum",
                    ModelConfig(model_json=TINY)).slots_a_token == 2 * 4


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
def test_the_fourth_familys_stablehlo_is_the_parents(stablehlo, name,
                                                     program):
    """The fifth family brought its router and its RoPE rules in its own
    module and models/lm_common.py stayed as it was: the programs the
    fourth family traces are, character for character, the ones the
    commit before traced (tests/fixtures/ holds the digests, written
    from a checkout of the commit its `_what` names)."""
    stated = json.load(open(os.path.join(ROOT, "tests", "fixtures",
                                         FIXTURE[name])))
    if stated["jax"] != jax.__version__:
        pytest.skip(f"the digests were written under jax {stated['jax']}; "
                    f"this is {jax.__version__}, whose printer may differ")
    text = stablehlo[name][program]
    assert "stablehlo." in text and len(text) > 50_000
    assert hashlib.sha256(text.encode()).hexdigest() \
        == stated["programs"][program]


# -- through the app's fused loop against the reference -------------------------

def _reference():
    name = "mellum_family_test_reference"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            ROOT, "benchmark", "families", "mellum", "reference.py"))
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


def _folded_app(task, ps_cfg, **more):
    from kafka_ps_tpu.runtime.app import StreamingPSApp
    cfg = dataclasses.replace(ps_cfg, num_workers=2, eval_every=8)
    app = StreamingPSApp(cfg, test_x=rows_of(task, 2, seed=8),
                         test_y=np.zeros(2, np.int32), **more)
    for i, row in enumerate(rows_of(task, 4, seed=9)):
        app.data_sink(i % 2, row, 0)
    return app


def test_eight_fused_clocks_through_the_app_are_the_references(task, ps_cfg):
    """`StreamingPSApp.run_fused_bsp` — the entry, buffers, slab and
    folded programs the benchmark's cell drives — for 8 clocks from the
    stated start, against 8 reference rounds on what the buffers hold:
    parameters, the workers' losses a clock, and the server's
    evaluation row at clock 8."""
    ref = _reference()
    app = _folded_app(task, ps_cfg)
    cfg = dataclasses.replace(ps_cfg, num_workers=2)
    s = ref.shapes(cfg)
    theta0 = ref.init_params(s)
    assert np.array_equal(np.asarray(app.server.theta), theta0)
    slabs = [b.snapshot() for b in app.buffers]
    assert [int(m.sum()) for _, _, m in slabs] == [2, 2]
    want_t, want_l = ref.Reference(s).run(theta0, slabs, 8, keep_every=8)
    app.run_fused_bsp(max_server_iterations=8 * 2)
    assert app.server.iterations == 16
    got = np.asarray(app.server.theta)
    scale = 3 * 1e-5 * 8
    assert ref.param_gap(got, want_t[-1], theta0, s) <= scale
    assert np.max(np.abs(got - want_t[-1])) <= scale * np.max(
        np.abs(want_t[-1] - theta0))
    last = app.server.last_metrics
    want = ref.Reference(s).evaluate(want_t[-1], (rows_of(task, 2, seed=8),
                                                  None))
    assert float(last.loss) == pytest.approx(want["loss"], rel=scale)
    assert float(last.accuracy) == pytest.approx(want["accuracy"], abs=1e-6)
    assert want_l[-1] < want_l[0]               # and it learns
    app.close_logs()


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
    window, full, blocks = mellum.pair_counts(c)
    assert counters["attn.pairs_window"] == 32 * 3 * (2 * window // 1024)
    assert counters["attn.pairs_full"] == 32 * 3 * (2 * full // 1024)
    assert counters["attn.block_pairs"] == 32 * 3 * (2 * blocks // 1024)
    # every token chooses 2 of 8 experts in each of 4 layers, and a
    # quarter of the experts is held here
    slots = 32 * 3 * 2 * c.sequence_length * 2 * 4
    assert counters["moe.assignments_here"] \
        + counters["moe.assignments_away"] == slots
    assert 0.10 * slots < counters["moe.assignments_here"] < 0.45 * slots
    # the placement: 48 rows x 48 tokens a layer a pass under the
    # bound, 96 x 48 in a pass over it
    over = counters["moe.passes_over_bound"]
    under, beyond = mellum.place_pairs(2 * c.sequence_length, c)
    assert counters["moe.place_pairs"] == (
        32 * 3 * (4 * under // 1024) + over * ((beyond - under) // 1024))
    assert counters["moe.place_pairs"] > 0
    assert counters["moe.place_pairs_dense"] == counters["moe.place_pairs"]
    # through the CPU runtime the core is its plain tiles
    assert counters["attn.kernel_block_pairs"] == 0
    # q's and k's head rows through every layer, and no kernel either
    assert counters["attn.norm_rope_rows"] == 32 * 3 * (
        2 * c.sequence_length * c.num_hidden_layers
        * (c.num_attention_heads + c.num_key_value_heads) // 1024) > 0
    assert counters["attn.norm_rope_kernel_rows"] == 0
    assert tracer.counters()["moe.place_pairs"] == counters["moe.place_pairs"]
    assert app.server.last_metrics is not None
    app.close_logs()


# -- through the CLI's own parser and drives ---------------------------------

def _write_token_csvs(task, train_rows=24, test_rows=3):
    from kafka_ps_tpu.data.synth import write_csv
    rows = rows_of(task, train_rows + test_rows, seed=1)
    zeros = np.zeros((len(rows),), np.int32)
    write_csv("train.csv", rows[:train_rows], zeros[:train_rows])
    write_csv("test.csv", rows[train_rows:], zeros[train_rows:])


def _cli(*more, name="mellum"):
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


def _refusal(name):
    """What the CLI says to four levers at once, for `--task name`."""
    from kafka_ps_tpu.cli import run as run_mod
    args = run_mod.build_parser().parse_args(
        _cli("--compress", "int8", "--slab-dtype", "bf16",
             "--tier-hot-bytes", "4096", "--param_shards", "2", name=name))
    with pytest.raises(SystemExit) as e:
        run_mod.cfg_from_args(args)
    return str(e.value)


@pytest.mark.parametrize("other", ["glm4_moe_lite", "nemotron_h", "afmoe",
                                   "ouro"])
def test_the_five_language_model_tasks_refuse_the_same_levers(other):
    """What a task cannot run with follows from what its family says of
    itself — a file of its own, rows that are tokens, no program over a
    mesh — so the fifth family refuses the same levers with the same
    words as each of the other four."""
    from kafka_ps_tpu.cli import run as run_mod
    family = task_class("mellum")
    assert family.model_file and not family.batches_workers
    assert family.row_dtype is np.int32
    said = _refusal("mellum")
    assert said.startswith("--task mellum cannot run with ")
    assert said.replace("mellum", "X") == _refusal(other).replace(other, "X")
    # the task without its file, or a file without such a task
    bare = [a for a in _cli() if a not in ("--model_json", TINY)]
    with pytest.raises(SystemExit, match="--task mellum needs --model_json"):
        run_mod.cfg_from_args(run_mod.build_parser().parse_args(bare))
    plain = ["--task", "mlp", "--model_json", TINY]
    with pytest.raises(SystemExit, match="no file of its own"):
        run_mod.cfg_from_args(run_mod.build_parser().parse_args(plain))


def test_the_parser_has_the_parents_options_and_one_task_more():
    """No new flag or option: the CLI's parser has the 75 option
    strings the parent's has, and `--task` takes one name more, named
    in `--model_json`'s help."""
    from kafka_ps_tpu.cli import run as run_mod
    parser = run_mod.build_parser()
    options = [s for a in parser._actions for s in a.option_strings]
    assert len(options) == len(set(options)) == 75
    task_flag = next(a for a in parser._actions if a.dest == "task")
    assert task_flag.choices == ["logreg", "mlp", "glm4_moe_lite",
                                 "nemotron_h", "afmoe", "ouro", "mellum"]
    assert "mellum" in next(a for a in parser._actions
                            if a.dest == "model_json").help


def test_a_relative_model_file_is_taken_from_the_repositorys_root(
        tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert not os.path.exists(TINY)
    c = mellum.load_config(TINY)
    assert c.hidden_size == 64 and c.attention_block == 8
    assert c.layer_types == (mellum.SLIDING,) * 3 + (mellum.FULL,)
    assert c.rope(mellum.FULL)["rope_type"] == "yarn"
    assert c.rope(mellum.SLIDING) == {"rope_type": "default",
                                      "rope_theta": 500000}
    hash(c)                             # frozen and hashed, nested rules too

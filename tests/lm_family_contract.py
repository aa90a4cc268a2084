"""What every language-model family is held to through the runtime, at
its tiny size on the CPU, written once.  A family's file,
tests/test_<family>_runtime.py, states a `Family` record as `FAMILY`
and takes the cases (`from lm_family_contract import *`); pytest
collects no case from this module by itself.  tests/test_<family>.py
holds the model against its reference, tests/test_folded_frame.py what
the frame does for any task that does not batch its workers.

What the cases of a file need built — the task, the fused loop's jitted
programs — is built once a file: an app made by `folded_app` takes the
file's one set of programs (`StreamingPSApp._fused_programs`, which an
app otherwise fills for itself), so a case pays for running them, not
for compiling them again.  The CLI's drives build their own app, as a
user's run does."""

import dataclasses
import hashlib
import json
import os
import types
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models.task import get_task, task_class
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Family:
    """What differs from one family to the next."""
    name: str                   # `--task`
    module: types.ModuleType    # kafka_ps_tpu/models/<family>.py
    tiny: str                   # its tiny model file, from the root
    digests: str                # tests/fixtures/<this>: its traced programs
    # what `load_config(tiny)` must read: (arch) -> None, by assertions
    reads: Callable
    # what a fused call of 32 updates (2 workers, 2 rows each, k = 2)
    # must sum the family's own counters to: (task, counters) -> None
    counted: Callable
    counter_names: tuple        # the task's, in its order
    slots_a_token: int          # expert slots a token takes (0: none)
    # names of models/lm_common.py the module defines for itself, and
    # methods of `lm.TokenRowsTask` its task overrides
    own: tuple = ("load_config", "num_params")
    overrides: tuple = ()

    @property
    def ps_cfg(self) -> PSConfig:
        return PSConfig(num_workers=3, task=self.name,
                        model=ModelConfig(num_max_iter=2,
                                          local_learning_rate=0.05,
                                          model_json=self.tiny),
                        buffer=BufferConfig(min_size=1, max_size=2))


@pytest.fixture(scope="module")
def family(request):
    return request.module.FAMILY


@pytest.fixture(scope="module")
def ps_cfg(family):
    return family.ps_cfg


@pytest.fixture(scope="module")
def task(family, ps_cfg):
    return get_task(family.name, ps_cfg.model)


def rows_of(task, n, seed=3):
    return np.random.default_rng(seed).integers(
        0, task.arch.vocab_held, size=(n, task.row_width)).astype(np.int32)


@pytest.fixture(scope="module")
def fused_programs():
    """The file's one set of the fused loop's jitted programs."""
    return {}


@pytest.fixture
def folded_app(task, ps_cfg, fused_programs):
    """(tracer=None, **changes to the cfg) -> a `StreamingPSApp` of 2
    workers with 2 rows each in their buffers and an evaluation every 8
    clocks, on the file's one set of fused programs; the apps' logs are
    closed when the case is done."""
    from kafka_ps_tpu.runtime.app import StreamingPSApp
    apps = []

    def make(tracer=None, **changes):
        cfg = dataclasses.replace(ps_cfg, **{"num_workers": 2,
                                            "eval_every": 8, **changes})
        app = StreamingPSApp(cfg, test_x=rows_of(task, 2, seed=8),
                             test_y=np.zeros(2, np.int32), tracer=tracer)
        app._fused_programs = fused_programs
        for i, row in enumerate(rows_of(task, 4, seed=9)):
            app.data_sink(i % 2, row, 0)
        apps.append(app)
        return app
    yield make
    for app in apps:
        app.close_logs()


# -- one frame ---------------------------------------------------------------

# what the frame alone does: a family's module has no name for these
FRAME_ONLY = ("route", "routed_experts", "live_rows_bound", "fit_counted",
              "evaluate_leaves", "unflatten_leaves", "head_nll",
              "blocked_attention", "key_span", "head_norm_rope")
TASK_FRAME = ("fit_counted", "evaluate_leaves", "unflatten", "flatten",
              "init_params", "encode_labels", "fit")


def test_the_familys_module_holds_the_frames_objects_and_no_copy(family,
                                                                 task):
    """One attention core, one RoPE, one gated MLP, one expert layer,
    one head, one task frame: whatever the family's module calls by a
    name of models/lm_common.py IS that module's object, it has no name
    for what the frame alone does, and its task overrides nothing of
    `TokenRowsTask`'s — but for what its record states as its own."""
    module, cls = family.module, task_class(family.name)
    for name, held in vars(module).items():
        if not name.startswith("__") and hasattr(lm, name) \
                and name not in family.own:
            assert held is getattr(lm, name), name
    for name in set(FRAME_ONLY) - set(family.own):
        assert name not in vars(module), name
    for name in family.own:
        assert name in vars(module), name
    assert issubclass(cls, lm.TokenRowsTask) and type(task) is cls
    for name in TASK_FRAME:
        assert (name in vars(cls)) == (name in family.overrides), name
    assert cls.counter_names == family.counter_names
    assert task.slots_a_token == family.slots_a_token
    assert cls.model_file and not cls.batches_workers
    assert cls.row_dtype is np.int32


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


# -- the family's traced programs are the recorded ones -----------------------

def traced_programs(task, cfg: ModelConfig) -> dict:
    """{program: the StableHLO text} of the family's three programs at
    its tiny size (3 workers, 2 rows, a chunk of 8 clocks), as this
    tree traces them.  docs/TESTING.md says how a PR whose point is a
    change to a family's program writes its fixture anew."""
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
            leaves, shaped((3, task.row_width), jnp.int32), None).as_text(),
        "folded_chunk": chunk.lower(
            leaves, shaped((w, cap, task.row_width), jnp.int32),
            shaped((w, cap), jnp.int32),
            shaped((w, cap), jnp.float32)).as_text()}


@pytest.fixture(scope="module")
def stablehlo(task, ps_cfg):
    return traced_programs(task, ps_cfg.model)


@pytest.mark.parametrize("program", ["fit_counted", "evaluate_leaves",
                                     "folded_chunk"])
def test_the_familys_three_traced_programs_are_the_recorded_ones(
        family, stablehlo, program):
    """What the families share lies in models/lm_common.py, and an edit
    there — or a new family beside this one — must leave what this
    family traces alone: each program is, character for character, the
    one tests/fixtures/ holds the digest of.  The fixture's `_what`
    names the commit whose programs it records; a PR rewrites it only
    where a change to this family's program is its point."""
    stated = json.load(open(os.path.join(ROOT, "tests", "fixtures",
                                         family.digests)))
    if stated["jax"] != jax.__version__:
        pytest.skip(f"the digests were written under jax {stated['jax']}; "
                    f"this is {jax.__version__}, whose printer may differ")
    text = stablehlo[program]
    assert "stablehlo." in text and len(text) > 50_000
    assert hashlib.sha256(text.encode()).hexdigest() \
        == stated["programs"][program]


# -- through the CLI's own parser and drives ---------------------------------

def _write_token_csvs(task, train_rows=24, test_rows=2):
    """The CLI's files.  2 test rows, as `folded_app` and the gang's
    case hold: the per-node solver programs are kept a process
    (`runtime/worker.py` `_solver_fns`, `runtime/gang.py`
    `_gang_solver_fns`) and evaluate inside, so at one shape the serial
    drives and the gang's case compile them once between them."""
    from kafka_ps_tpu.data.synth import write_csv
    rows = rows_of(task, train_rows + test_rows, seed=1)
    zeros = np.zeros((len(rows),), np.int32)
    write_csv("train.csv", rows[:train_rows], zeros[:train_rows])
    write_csv("test.csv", rows[train_rows:], zeros[train_rows:])


def cli(family, *more):
    return ["-training", "train.csv", "-test", "test.csv", "--task",
            family.name, "--model_json", family.tiny, "--num_workers", "2",
            "-min", "1", "-max", "2", "--local_learning_rate", "0.05",
            "-p", "1", "-l", *more]


SERVER_COLUMNS = ["timestamp", "partition", "vectorClock", "loss",
                  "fMeasure", "accuracy"]


@pytest.mark.parametrize("drive,iterations", [
    (("--fused", "--eval_every", "8"), 32),
    (("--fused",), 6),
    (("--mode", "serial"), 8),
    (("--mode", "serial", "--no-gang", "--no-eval-async"), 8)])
def test_the_task_runs_through_the_clis_drives(tmp_path, monkeypatch, family,
                                               task, drive, iterations):
    import pandas as pd

    from kafka_ps_tpu.cli import run as run_mod
    monkeypatch.chdir(tmp_path)
    _write_token_csvs(task)
    args = run_mod.build_parser().parse_args(
        cli(family, *drive, "--max_iterations", str(iterations)))
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


def test_the_per_node_gang_runs_members_of_the_task(task, folded_app):
    """`run_serial` with the gang on: one dispatch takes every ready
    member of the task, one member at a time inside it
    (`over_members`), and the parameters move as without the gang."""
    from kafka_ps_tpu.utils.trace import Tracer

    def run(use_gang):
        tracer = Tracer()
        app = folded_app(tracer, use_gang=use_gang,
                         eval_every=PSConfig.eval_every)
        app.run_serial(max_server_iterations=6, pump=lambda: None)
        return np.asarray(app.server.theta).copy(), tracer.counters()
    with_gang, counted = run(True)
    without, counted_off = run(False)
    assert counted.get("gang.batched_dispatches", 0) > 0
    assert counted["dispatch.device"] < counted_off["dispatch.device"]
    np.testing.assert_allclose(with_gang, without, rtol=1e-5, atol=1e-7)
    assert np.any(with_gang != np.asarray(task.init_params()))


# the levers a language-model task refuses, in the order the CLI names
# them; the words are cli/run.py `TASK_REFUSES`'
REFUSED = ("compress", "tier_hot_bytes", "slab_dtype", "param_shards")


def test_the_task_refuses_the_levers_in_the_stated_words(family):
    """What a task cannot run with follows from what its family says of
    itself — a file of its own, rows that are tokens, no program over a
    mesh — never from its name: asked for four levers at once, every
    family's task answers with one message, the same but for its
    name."""
    from kafka_ps_tpu.cli import run as run_mod
    parse = run_mod.build_parser().parse_args
    why = {flag: why for _, levers in run_mod.TASK_REFUSES
           for flag, (_, why) in levers.items()}
    with pytest.raises(SystemExit) as e:
        run_mod.cfg_from_args(parse(cli(
            family, "--compress", "int8", "--slab-dtype", "bf16",
            "--tier-hot-bytes", "4096", "--param_shards", "2")))
    assert str(e.value) == f"--task {family.name} cannot run with " \
        + "; ".join(f"--{flag.replace('_', '-')}: {why[flag]}"
                    for flag in REFUSED)
    # the task without its file, or a file without such a task
    bare = [a for a in cli(family) if a not in ("--model_json", family.tiny)]
    with pytest.raises(SystemExit,
                       match=f"--task {family.name} needs --model_json"):
        run_mod.cfg_from_args(parse(bare))
    with pytest.raises(SystemExit, match="no file of its own"):
        run_mod.cfg_from_args(parse(["--task", "mlp", "--model_json",
                                     family.tiny]))
    assert family.name in next(a for a in run_mod.build_parser()._actions
                               if a.dest == "model_json").help


def test_a_relative_model_file_is_taken_from_the_repositorys_root(
        family, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert not os.path.exists(family.tiny)
    c = family.module.load_config(family.tiny)
    assert c.hidden_size == 64
    family.reads(c)
    hash(c)                             # frozen and hashed, nested rules too
    # and the cut the file states is held to the family's own sizes
    body = json.load(open(os.path.join(ROOT, family.tiny)))
    path = tmp_path / "model.json"
    path.write_text(json.dumps(dict(body, vocab_held=0)))
    with pytest.raises(ValueError, match="vocab_held"):
        family.module.load_config(str(path))
    if family.slots_a_token:
        path.write_text(json.dumps(dict(body, experts_held=9)))
        with pytest.raises(ValueError, match="expert_offset"):
            family.module.load_config(str(path))


# -- the fused loop ------------------------------------------------------------

def test_the_fused_loop_sums_the_familys_counters_over_a_call(
        family, task, folded_app):
    """A folded task is carried through `run_fused_bsp` as its leaves;
    the server's vector is the call's result and the family's counters
    are summed over the call's 32 updates, in the tracer as in
    `last_run`."""
    from kafka_ps_tpu.utils.trace import Tracer
    tracer = Tracer()
    app = folded_app(tracer)
    start = np.asarray(app.server.theta).copy()
    app.run_fused_bsp(max_server_iterations=16 * 2)
    assert app.server.iterations == 32
    assert np.any(np.asarray(app.server.theta) != start)
    counters = app.last_run["counters"]
    assert set(counters) == set(task.counter_names)
    assert counters["data.tokens"] == 32 * 2 * task.arch.sequence_length
    family.counted(task, counters)
    # through the CPU runtime every kernel's share is nothing
    for name in task.counter_names:
        if "kernel" in name:
            assert counters[name] == 0, name
    traced = tracer.counters()
    assert {name: traced[name] for name in counters} == counters
    assert app.server.last_metrics is not None


def test_a_save_inside_a_fused_call_and_the_resume(folded_app, tmp_path):
    """A folded task's loop keeps the leaves on the device through the
    call; a checkpoint that falls due at a chunk's boundary inside it
    holds the parameters OF THAT CLOCK beside its clocks and
    iterations, and a resume from it ends where the uninterrupted run
    ends."""
    from kafka_ps_tpu.utils import checkpoint as ckpt
    whole = folded_app()
    whole.server.checkpoint_path = str(tmp_path / "mid.npz")
    # 2 workers: a chunk is 16 iterations, so the only save of the
    # 24-clock call falls after its second chunk (32 >= 24, 48 - 32 < 24)
    whole.server.checkpoint_every = 24
    whole.run_fused_bsp(max_server_iterations=24 * 2)
    with np.load(whole.server.checkpoint_path) as z:
        saved = {k: z[k].copy() for k in ("theta", "clocks", "iterations")}
    assert int(saved["iterations"]) == 32
    assert saved["clocks"].tolist() == [16, 16]
    until16 = folded_app()
    until16.run_fused_bsp(max_server_iterations=16 * 2)
    np.testing.assert_array_equal(saved["theta"],
                                  np.asarray(until16.server.theta))
    resumed = folded_app()
    ckpt.restore(whole.server.checkpoint_path, resumed.server)
    resumed.run_fused_bsp(max_server_iterations=24 * 2)
    assert resumed.server.iterations == 48
    np.testing.assert_array_equal(np.asarray(resumed.server.theta),
                                  np.asarray(whole.server.theta))


# what a family's file takes by `import *`: the fixtures and every case,
# in the order they stand here (pytest runs a file's cases in that order)
__all__ = ["family", "ps_cfg", "task", "fused_programs", "folded_app",
           "stablehlo"] + [name for name in list(globals())
                           if name.startswith("test_")]

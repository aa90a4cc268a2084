"""The frame any task that does not batch its workers runs in, with no
language-model family in it: the folded worker axis against the `vmap`
(and the classifiers' programs as they were), token rows through the
buffers and the device slab, what the CLI's parser holds and what the
classifiers refuse, and the benchmark's reader of such a task's update
period.  tests/lm_family_contract.py holds what each family is held to
through this frame."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.data.buffer import SlidingBuffer
from kafka_ps_tpu.models.task import get_task, task_class, task_names
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def test_the_folded_chunk_is_the_round_written_plainly_bit_for_bit():
    """2 clocks of 4 workers through the folded chunk against the round
    as a Python loop: every worker fits from the clock's shared leaves,
    the sum starts at zero and takes `new - shared` in the workers'
    order, the apply adds `server_lr` times it.  To the bit: what ties
    the shared leaves inside the worker loop (a barrier) and where the
    sum's zero start ends up in the compiled program (PR 47) are no
    part of the mathematics."""
    cfg, x, y, mask = _mlp_inputs()
    task = _FoldedMLP(cfg)
    leaves0 = task.unflatten(task.init_params())
    encoded = task.encode_labels(y)
    fit = jax.jit(task.fit_counted)

    leaves, want_l = leaves0, []
    for _ in range(2):
        total = jax.tree.map(jnp.zeros_like, leaves)
        loss_sum = jnp.float32(0.0)
        for w in range(4):
            new, loss, _ = fit(leaves, x[w], encoded[w], mask[w])
            total = jax.tree.map(lambda t, n, o: t + (n - o), total, new,
                                 leaves)
            loss_sum = loss_sum + loss
        leaves = jax.tree.map(lambda a, d: a + jnp.float32(0.25) * d,
                              leaves, total)
        want_l.append(loss_sum / 4)

    got, got_l, counted = bsp.make_bsp_multi_step(
        cfg, 4, 0.25, 2, task=task)(jax.tree.map(jnp.copy, leaves0),
                                    x, y, mask)
    assert jax.tree.structure(got) == jax.tree.structure(leaves)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(leaves)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))
    assert int(counted[0]) == 2 * 4


def test_a_folded_task_has_no_program_over_a_mesh():
    cfg, *_ = _mlp_inputs()
    from kafka_ps_tpu.parallel.mesh import worker_mesh
    with pytest.raises(ValueError, match="no program over a mesh"):
        bsp.make_bsp_step(cfg, 4, 0.25, mesh=worker_mesh(4),
                          task=_FoldedMLP(cfg))


def _parent_multi_step(cfg, task, num_workers, server_lr, rounds):
    """`make_bsp_multi_step` as it stood before any task's label
    encoding was its own (PR 26, parallel/bsp.py), written out: the builders one-hot the labels
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


# -- the CLI's parser and the classifiers ------------------------------------

def test_the_parser_holds_75_option_strings_and_every_task_by_name():
    """A family comes as a name of `--task` and a file for
    `--model_json`, never as a flag: the CLI's parser has 75 option
    strings, `--task` takes the registry's names in the registry's
    order (models/task.py `task_names`), and `--model_json`'s help
    names every family that has a file of its own."""
    from kafka_ps_tpu.cli import run as run_mod
    parser = run_mod.build_parser()
    options = [s for a in parser._actions for s in a.option_strings]
    assert len(options) == len(set(options)) == 75
    task_flag = next(a for a in parser._actions if a.dest == "task")
    assert task_flag.choices == task_names() == [
        "logreg", "mlp", "glm4_moe_lite", "nemotron_h", "afmoe", "ouro",
        "mellum", "lfm2_moe", "granitemoehybrid"]
    said = next(a for a in parser._actions if a.dest == "model_json").help
    for name in task_names():
        assert (name in said) == bool(task_class(name).model_file), name


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

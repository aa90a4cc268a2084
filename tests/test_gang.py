"""Gang-scheduled dispatch (runtime/gang.py, docs/GANG_DISPATCH.md).

The contract under test is EQUIVALENCE, not approximation: coalescing
simultaneous gate releases into one batched device step must leave the
protocol's observable behavior bit-for-bit what the per-message path
produces — final theta, per-worker CSV rows (modulo timestamps), server
eval rows, message counts — while strictly reducing the number of
device dispatches.
"""

import numpy as np
import pytest

from kafka_ps_tpu.runtime.app import StreamingPSApp
from kafka_ps_tpu.utils.config import (BufferConfig, EVENTUAL, ModelConfig,
                                       PSConfig, StreamConfig)
from kafka_ps_tpu.utils.trace import Tracer


def gang_cfg(consistency=0, use_gang=True, num_workers=4, task="logreg",
             eval_every=1):
    return PSConfig(
        num_workers=num_workers,
        consistency_model=consistency,
        task=task,
        model=ModelConfig(num_features=8, num_classes=2,
                          local_learning_rate=0.5, hidden_dim=16),
        buffer=BufferConfig(min_size=8, max_size=32),
        stream=StreamConfig(time_per_event_ms=1.0),
        use_gang=use_gang,
        eval_every=eval_every,
    )


def make_dataset(n=256, f=8, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(1, 3, size=n).astype(np.int32)
    centers = np.array([[0.0] * f, [2.5] * f, [-2.5] * f], np.float32)
    x = (centers[y] + rng.normal(scale=0.5, size=(n, f))).astype(np.float32)
    return x, y


def build_app(cfg):
    x, y = make_dataset()
    logs = {"server": [], "worker": []}
    tracer = Tracer()
    app = StreamingPSApp(cfg, test_x=x, test_y=y,
                         server_log=logs["server"].append,
                         worker_log=logs["worker"].append,
                         tracer=tracer)
    for i in range(len(x)):
        w = i % cfg.num_workers
        app.data_sink(w, {j: float(v) for j, v in enumerate(x[i])
                          if v != 0}, int(y[i]))
    return app, logs, tracer


def strip_ts(rows):
    """Drop the leading timestamp field — the only row content allowed
    to differ between the gang and per-message paths."""
    return [r.split(";", 1)[1] for r in rows]


def run_serial_pair(consistency, **kw):
    out = {}
    for gang in (True, False):
        app, logs, tracer = build_app(
            gang_cfg(consistency, use_gang=gang, **kw))
        app.run_serial(max_server_iterations=40)
        out[gang] = (np.asarray(app.server.theta), logs,
                     tracer.counters())
    return out


# -- serial bitwise equivalence ----------------------------------------------


@pytest.mark.parametrize("consistency", [0, 3, EVENTUAL])
def test_serial_gang_bitwise_equivalent(consistency):
    res = run_serial_pair(consistency)
    theta_on, logs_on, _ = res[True]
    theta_off, logs_off, _ = res[False]
    assert theta_on.tobytes() == theta_off.tobytes()
    assert strip_ts(logs_on["worker"]) == strip_ts(logs_off["worker"])
    assert strip_ts(logs_on["server"]) == strip_ts(logs_off["server"])


@pytest.mark.parametrize("consistency", [0, 3, EVENTUAL])
def test_serial_gang_reduces_dispatches(consistency):
    res = run_serial_pair(consistency)
    disp_on = res[True][2].get("dispatch.device", 0)
    disp_off = res[False][2].get("dispatch.device", 0)
    assert disp_on < disp_off
    assert res[True][2].get("gang.batched_dispatches", 0) > 0
    assert res[True][2].get("server.gang_batched_applies", 0) > 0


@pytest.mark.parametrize("task", ["mlp"])
def test_serial_gang_bitwise_other_families(task):
    res = run_serial_pair(0, task=task)
    assert res[True][0].tobytes() == res[False][0].tobytes()
    assert strip_ts(res[True][1]["worker"]) == \
        strip_ts(res[False][1]["worker"])


def test_serial_gang_bitwise_off_eval_cadence():
    res = run_serial_pair(3, eval_every=4)
    assert res[True][0].tobytes() == res[False][0].tobytes()
    assert strip_ts(res[True][1]["worker"]) == \
        strip_ts(res[False][1]["worker"])
    assert strip_ts(res[True][1]["server"]) == \
        strip_ts(res[False][1]["server"])


# -- vmapped-vs-loop solver equivalence (the gang's core assumption) ---------


@pytest.mark.parametrize("task", ["logreg", "mlp"])
def test_vmapped_solver_matches_loop(task):
    """A stacked gang dispatch is the looped single dispatches, bitwise
    — for both model families."""
    import jax
    import jax.numpy as jnp

    from kafka_ps_tpu.models.task import get_task

    cfg = ModelConfig(num_features=8, num_classes=2,
                      local_learning_rate=0.5, hidden_dim=16)
    tsk = get_task(task, cfg)
    rng = np.random.default_rng(7)
    k, B = 3, 24
    thetas = jnp.asarray(rng.normal(size=(k, tsk.num_params))
                         .astype(np.float32) * 0.1)
    xs = jnp.asarray(rng.normal(size=(k, B, 8)).astype(np.float32))
    ys = jnp.asarray(rng.integers(1, 3, size=(k, B)).astype(np.int32))
    masks = jnp.asarray((rng.random((k, B)) < 0.8).astype(np.float32))

    ds, ls = jax.jit(jax.vmap(tsk.local_update))(thetas, xs, ys, masks)
    fn = jax.jit(tsk.local_update)
    singles = [fn(thetas[i], xs[i], ys[i], masks[i]) for i in range(k)]

    for i, (d1, l1) in enumerate(singles):
        assert np.asarray(d1).tobytes() == np.asarray(ds[i]).tobytes()
        assert np.asarray(l1, np.float32).tobytes() == \
            np.asarray(ls[i], np.float32).tobytes()


def test_vmapped_eval_matches_loop():
    import jax
    import jax.numpy as jnp

    from kafka_ps_tpu.models.task import get_task

    cfg = ModelConfig(num_features=8, num_classes=2,
                      local_learning_rate=0.5)
    tsk = get_task("logreg", cfg)
    x, y = make_dataset(64)
    rng = np.random.default_rng(3)
    thetas = jnp.asarray(rng.normal(size=(3, tsk.num_params))
                         .astype(np.float32) * 0.1)
    tx, ty = jnp.asarray(x), jnp.asarray(y)
    batched = jax.jit(jax.vmap(lambda t: tsk.evaluate(t, tx, ty)))(thetas)
    single = jax.jit(lambda t: tsk.evaluate(t, tx, ty))
    for i in range(3):
        m = single(thetas[i])
        for field in ("loss", "f1", "accuracy"):
            assert np.asarray(getattr(m, field), np.float32).tobytes() == \
                np.asarray(getattr(batched, field)[i], np.float32).tobytes()


# -- protocol plumbing -------------------------------------------------------


def test_gang_notices_emitted_and_transient():
    """The server advertises multi-member release sets on GANG_TOPIC;
    on a durable fabric the notices never reach the commit log (a
    replayed notice would promise messages whose delivery already
    happened)."""
    import os

    from kafka_ps_tpu.log.durable_fabric import DurableFabric
    from kafka_ps_tpu.runtime import fabric as fabric_mod

    cfg = gang_cfg(0)
    x, y = make_dataset()
    import tempfile
    root = tempfile.mkdtemp()
    tracer = Tracer()
    fab = DurableFabric(os.path.join(root, "log"), tracer=tracer)
    app = StreamingPSApp(cfg, test_x=x, test_y=y, tracer=tracer,
                         fabric=fab)
    for i in range(len(x)):
        app.data_sink(i % 4, {j: float(v) for j, v in enumerate(x[i])
                              if v != 0}, int(y[i]))
    app.run_serial(max_server_iterations=24)
    assert tracer.counters().get("send.gang", 0) > 0
    assert not any(t == fabric_mod.GANG_TOPIC
                   for t, _ in app.fabric.manager.partitions())
    app.fabric.close()


def test_socket_cfg_disables_gang():
    """Split mode has no gang-notice wire frame — its PSConfig must pin
    use_gang off regardless of CLI defaults."""
    import argparse

    from kafka_ps_tpu.cli.socket_mode import _make_cfg

    args = argparse.Namespace(
        num_workers=2, task="logreg", num_features=8, num_classes=2,
        local_iterations=2, local_learning_rate=0.5, hidden_dim=16)
    assert _make_cfg(args).use_gang is False


def test_no_gang_flag_restores_per_message_path():
    from kafka_ps_tpu.cli.run import build_parser

    args = build_parser().parse_args(
        ["--training_data_file_path", "x.csv",
         "--test_data_file_path", "y.csv", "--no-gang"])
    assert args.no_gang is True
    args2 = build_parser().parse_args(
        ["--training_data_file_path", "x.csv",
         "--test_data_file_path", "y.csv"])
    assert args2.no_gang is False


# -- threaded drive ----------------------------------------------------------


@pytest.mark.parametrize("consistency", [0, 3, EVENTUAL])
def test_threaded_gang_runs_and_learns(consistency):
    """Threaded coalescing is opportunistic (first-arrival), so the
    assertion is protocol health + learning, not bitwise equality."""
    app, logs, tracer = build_app(gang_cfg(consistency))
    app.run_threaded(max_server_iterations=40)
    assert app.server.iterations >= 40
    m = app.server.last_metrics
    assert m is not None and float(m.accuracy) > 0.9
    assert all(w.iterations > 0 for w in app.workers)
    assert logs["worker"] and all(len(r.split(";")) == 7
                                  for r in logs["worker"])


def test_threaded_gang_coalesces_sometimes():
    """Serial-like timing makes sequential release sets land together;
    at least SOME of them should coalesce even under thread scheduling
    noise (bootstrap alone guarantees one)."""
    app, _, tracer = build_app(gang_cfg(0))
    app.run_threaded(max_server_iterations=40)
    assert tracer.counters().get("gang.batched_dispatches", 0) >= 1

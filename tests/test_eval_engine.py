"""Async coalescing eval engine (kafka_ps_tpu/evaluation/engine.py).

The contract under test (docs/EVALUATION.md "Async evaluation"):

  * `--eval-async` is pure mechanism — theta AND the eval CSV rows
    (timestamps stripped) are BITWISE-identical to the fused path for
    all three consistency models, gang on or off, at any eval cadence,
    through the aggregation tier's summed composites, and through the
    N=2 sharded group's frontier eval;
  * coalescing is real: a backlog of k pending thetas evaluates as ONE
    batched dispatch whose per-row metrics equal standalone evals bit
    for bit, emitted in strict clock order;
  * `eval_lag_clocks` returns to 0 once training stops and the drain
    completes (the acceptance gauge).
"""

from __future__ import annotations

import numpy as np
import pytest

from kafka_ps_tpu.evaluation.engine import (EvalEngine, _MAX_COALESCE,
                                            coalesce_width_cap)
from kafka_ps_tpu.runtime import fabric as fabric_mod
from kafka_ps_tpu.runtime.app import StreamingPSApp
from kafka_ps_tpu.utils.config import EVENTUAL, ModelConfig
from tests.test_runtime import fill_buffers, make_dataset, small_cfg

import dataclasses


def _strip_ts(rows):
    return [";".join(r.split(";")[1:]) for r in rows]


def _run_app(consistency, *, eval_async, gang=True, eval_every=1,
             iters=24, drive="serial"):
    cfg = dataclasses.replace(small_cfg(consistency),
                              eval_async=eval_async, use_gang=gang,
                              eval_every=eval_every)
    x, y = make_dataset()
    rows: list = []
    app = StreamingPSApp(cfg, test_x=x, test_y=y,
                         server_log=rows.append,
                         worker_log=(lambda line: None))
    fill_buffers(app, x, y)
    if drive == "serial":
        app.run_serial(iters)
    else:
        app.run_threaded(iters)
    app.close_logs()
    return _strip_ts(rows), np.asarray(app.server.theta).tobytes(), app


# -- the A/B lever: bitwise across the eval plane --------------------------

@pytest.mark.parametrize("consistency", [0, 2, EVENTUAL])
@pytest.mark.parametrize("gang", [True, False])
def test_async_eval_bitwise_matches_fused(consistency, gang):
    fused_rows, fused_theta, _ = _run_app(consistency, eval_async=False,
                                          gang=gang)
    async_rows, async_theta, _ = _run_app(consistency, eval_async=True,
                                          gang=gang)
    assert fused_theta == async_theta
    assert fused_rows == async_rows
    assert len(fused_rows) > 0


def test_async_eval_under_threaded_drive():
    """Threaded drive is scheduling-nondeterministic ACROSS runs (two
    fused runs don't match each other either — arrival order varies),
    so the cross-run bitwise pin lives on the deterministic drives
    above and the socket leg (per-row bitwise is pinned engine-level
    by test_backlog_coalesces...).  Here the contract is intra-run:
    one row per eval clock in strict clock order, and the backlog
    drains to 0 when the drive loop's flush runs."""
    rows, _, app = _run_app(0, eval_async=True, drive="threaded")
    assert len(rows) > 0
    clocks = [int(r.split(";")[1]) for r in rows]
    assert clocks == sorted(clocks)
    assert len(set(clocks)) == len(clocks)
    assert app.eval_engine is not None
    assert app.eval_engine.lag_clocks == 0


@pytest.mark.parametrize("eval_every", [2, 3])
def test_async_eval_cadence_matches_fused(eval_every):
    """Off-cadence clocks must produce NO row and on-cadence clocks
    exactly one, under gang dispatch where eval positions become
    prefix requests."""
    fused_rows, fused_theta, _ = _run_app(0, eval_async=False,
                                          eval_every=eval_every)
    async_rows, async_theta, _ = _run_app(0, eval_async=True,
                                          eval_every=eval_every)
    assert fused_theta == async_theta
    assert fused_rows == async_rows
    clocks = [int(r.split(";")[1]) for r in async_rows]
    assert all(c % eval_every == 0 for c in clocks)
    assert clocks == sorted(clocks)


def test_lag_returns_to_zero_after_run():
    """Acceptance: eval_lag_clocks is 0 once training stops (the drive
    loop's flush_logs drains the engine)."""
    from kafka_ps_tpu.telemetry.registry import Telemetry
    cfg = dataclasses.replace(small_cfg(0), eval_async=True)
    x, y = make_dataset()
    tel = Telemetry()
    app = StreamingPSApp(cfg, test_x=x, test_y=y, telemetry=tel)
    fill_buffers(app, x, y)
    app.run_serial(24)
    assert app.eval_engine is not None
    assert app.eval_engine.lag_clocks == 0
    # the gauge agrees with the property
    assert app.eval_engine._m_lag.value == 0
    assert app.server.last_metrics is not None
    app.close_logs()


# -- aggregation tier: summed composites through the engine ----------------

def test_async_eval_bitwise_through_summed_composites():
    """_process_summed's eval split: a summed composite's eval clock
    must emit the same row async as fused (and feed model health —
    the parity fix riding this PR).  Pump mirrors test_agg's summed
    BSP harness."""
    from kafka_ps_tpu.agg import LocalAggregator
    from tests.test_agg import _deliver_weights

    def run(eval_async):
        cfg = dataclasses.replace(small_cfg(0), eval_async=eval_async,
                                  use_gang=False)
        x, y = make_dataset()
        rows: list = []
        app = StreamingPSApp(cfg, test_x=x, test_y=y,
                             server_log=rows.append,
                             worker_log=(lambda line: None))
        fill_buffers(app, x, y)
        agg = LocalAggregator(0, app.server.task.num_params, summed=True)
        app.server.start_training_loop()
        delivered: dict = {}
        while app.server.iterations < 16:
            _deliver_weights(app, delivered)
            while True:
                g = app.fabric.poll(fabric_mod.GRADIENTS_TOPIC, 0)
                if g is None:
                    break
                agg.offer(g)
            c = agg.combine()
            if c is not None:
                app.server.process(c)
        app.flush_logs()
        app.close_logs()
        return _strip_ts(rows), np.asarray(app.server.theta).tobytes()

    fused_rows, fused_theta = run(False)
    async_rows, async_theta = run(True)
    assert fused_theta == async_theta
    assert fused_rows == async_rows
    assert len(fused_rows) > 0


# -- sharded group: frontier eval through the engine -----------------------

def test_async_eval_bitwise_through_sharded_group():
    from kafka_ps_tpu.runtime.sharding import ShardedServerGroup
    from kafka_ps_tpu.runtime.worker import WorkerNode
    from kafka_ps_tpu.data.buffer import SlidingBuffer

    def run(eval_async):
        cfg = dataclasses.replace(small_cfg(0, num_workers=2),
                                  use_gang=False)
        x, y = make_dataset(n=128)
        rows: list = []
        fab = fabric_mod.Fabric()
        group = ShardedServerGroup(cfg, fab, 2, test_x=x, test_y=y,
                                   log=rows.append)
        if eval_async:
            assert group.enable_async_eval() is not None
        buffers = {w: SlidingBuffer(cfg.model.num_features, cfg.buffer)
                   for w in range(2)}
        workers = [WorkerNode(w, cfg, fab, buffers[w], x, y,
                              (lambda line: None))
                   for w in range(2)]
        for i in range(len(x)):
            buffers[i % 2].add(dict(enumerate(map(float, x[i]))),
                               int(y[i]))
        group.run_serial(workers, 16)
        group.close_eval()
        return (_strip_ts(rows),
                group.assembled_theta().tobytes())

    fused_rows, fused_theta = run(False)
    async_rows, async_theta = run(True)
    assert fused_theta == async_theta
    assert fused_rows == async_rows
    assert len(fused_rows) > 0


# -- the engine in isolation -----------------------------------------------

def _engine_fixture(n_test=32, **kw):
    from kafka_ps_tpu.models.task import get_task
    mcfg = ModelConfig(num_features=8, num_classes=2)
    task = get_task("logreg", mcfg)
    x, y = make_dataset(n=n_test, f=8)
    emitted: list = []
    eng = EvalEngine(task, x, y, lambda clock, m: emitted.append(
        (clock, float(m.loss), float(m.f1), float(m.accuracy))),
        start_thread=False, **kw)
    return task, x, y, eng, emitted


def test_backlog_coalesces_into_one_dispatch_in_clock_order():
    task, x, y, eng, emitted = _engine_fixture()
    rng = np.random.default_rng(1)
    thetas = [rng.normal(size=task.num_params).astype(np.float32)
              for _ in range(5)]
    for c, t in enumerate(thetas):
        eng.submit(t, c)
    assert eng.lag_clocks == 5    # clocks 0..4 pending, none evaluated
    assert eng.poll()             # ONE batched dispatch for the backlog
    assert not eng.poll()
    assert eng.stats()["dispatches"] == 1
    assert eng.stats()["widths"] == {"5": 1}
    assert [c for c, *_ in emitted] == [0, 1, 2, 3, 4]
    assert eng.lag_clocks == 0
    # each coalesced row equals a standalone eval to float32 tolerance:
    # the width-8 vmap program reduces the loss mean in another order
    # than the standalone one (1 ulp under jaxlib 0.9.0; widths 2 and 4
    # happen to match bitwise), F1/accuracy come from integer counts
    import jax.numpy as jnp
    for (c, loss, f1, acc), t in zip(emitted, thetas):
        m = task.evaluate(jnp.asarray(t), jnp.asarray(x), jnp.asarray(y))
        assert loss == pytest.approx(float(m.loss), rel=1e-6)
        assert (f1, acc) == (float(m.f1), float(m.accuracy))


def test_width_cap_bounds_single_dispatch():
    task, x, y, eng, emitted = _engine_fixture(max_width=4)
    rng = np.random.default_rng(2)
    for c in range(10):
        eng.submit(rng.normal(size=task.num_params).astype(np.float32), c)
    eng.drain()                  # start_thread=False: poll-until-empty
    s = eng.stats()
    assert s["dispatches"] == 3  # 4 + 4 + 2
    assert s["evals"] == 10
    assert max(int(w) for w in s["widths"]) <= 4
    assert [c for c, *_ in emitted] == list(range(10))


def test_threaded_engine_drains_and_reaps():
    from kafka_ps_tpu.models.task import get_task
    mcfg = ModelConfig(num_features=8, num_classes=2)
    task = get_task("logreg", mcfg)
    x, y = make_dataset(n=32, f=8)
    emitted: list = []
    eng = EvalEngine(task, x, y,
                     lambda clock, m: emitted.append(clock),
                     idle_exit=0.1)
    rng = np.random.default_rng(3)
    for c in range(6):
        eng.submit(rng.normal(size=task.num_params).astype(np.float32), c)
    eng.drain()
    assert emitted == list(range(6))
    assert eng.lag_clocks == 0
    eng.close()


def test_coalesce_width_cap_properties():
    # powers of two, >= 1, bounded by the hard ceiling
    assert coalesce_width_cap(100, 100, budget=8 * (100 + 100)) == 2
    assert coalesce_width_cap(100, 100, budget=1) == 1
    assert coalesce_width_cap(8, 8, budget=1 << 40) == _MAX_COALESCE
    w = coalesce_width_cap(6150, 11_000_000)
    assert w == 1                 # a huge test set forbids stacking
    for np_, nt in [(6150, 64), (530_000, 2048), (10, 10)]:
        w = coalesce_width_cap(np_, nt)
        assert w >= 1 and (w & (w - 1)) == 0 and w <= _MAX_COALESCE


# -- a failed dispatch fails the run (no exit 0 with rows missing) -----------

def test_dispatch_failure_is_kept_and_reraised():
    task, x, y, eng, emitted = _engine_fixture()
    theta = np.zeros(task.num_params, np.float32)

    def boom(batch):
        raise FloatingPointError("injected device failure")
    eng._dispatch = boom
    eng.submit(theta, 0)
    with pytest.raises(FloatingPointError):
        eng.poll()                       # the dispatching caller sees it
    for entry in (lambda: eng.submit(theta, 1), eng.drain, eng.close):
        with pytest.raises(RuntimeError,
                           match="eval engine dispatch failed") as info:
            entry()
        assert isinstance(info.value.__cause__, FloatingPointError)
    assert emitted == []


def test_cli_run_fails_on_an_eval_dispatch_error(tmp_path, monkeypatch):
    """The default path (threaded, --eval-async on) through the CLI: an
    eval dispatch that raises on the kps-eval thread must surface as a
    failed run, not a return code 0 with the popped clocks' rows
    silently missing."""
    from kafka_ps_tpu.cli import run as run_mod
    from kafka_ps_tpu.data.synth import generate, write_csv

    monkeypatch.chdir(tmp_path)
    x, y = generate(260, 16, 3, noise=1.0, sparsity=0.5, seed=0)
    write_csv("train.csv", x[:200], y[:200])
    write_csv("test.csv", x[200:], y[200:])
    real = EvalEngine._dispatch
    calls = []

    def flaky(self, batch):
        calls.append(len(batch))
        if len(calls) > 2:
            raise FloatingPointError("injected device failure")
        return real(self, batch)
    monkeypatch.setattr(EvalEngine, "_dispatch", flaky)
    with pytest.raises(RuntimeError, match="eval engine dispatch failed"):
        run_mod.main(["-training", "train.csv", "-test", "test.csv",
                      "--num_features", "16", "--num_classes", "3",
                      "--num_workers", "2", "-p", "1", "-l",
                      "--max_iterations", "400"])
    assert len(calls) == 3               # the engine stopped at the error

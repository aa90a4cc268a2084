"""CLI flag parity, CSV log sinks, checkpoint/resume, synthetic data,
and the multi-round fused step."""

import sys

import numpy as np
import pytest

from kafka_ps_tpu.cli import run as run_mod
from kafka_ps_tpu.data.synth import generate, write_csv
from kafka_ps_tpu.parallel import bsp, mesh as mesh_mod
from kafka_ps_tpu.runtime.app import StreamingPSApp
from kafka_ps_tpu.utils import checkpoint as ckpt
from kafka_ps_tpu.utils.config import ModelConfig
from kafka_ps_tpu.utils.csvlog import CsvLogSink, SERVER_HEADER, WORKER_HEADER

from tests.test_runtime import build_app, small_cfg


def test_parser_reference_flags_and_defaults():
    """Same flags/defaults as ServerAppRunner.java:19-26,59-63 and
    WorkerAppRunner.java:17-24,55-58."""
    args = run_mod.build_parser().parse_args([])
    assert args.training_data_file_path == "./data/train.csv"
    assert args.test_data_file_path == "./data/test.csv"
    assert args.consistency_model == 0
    assert args.producer_time_per_event == 200
    assert args.min_buffer_size == 128
    assert args.max_buffer_size == 1024
    assert args.buffer_size_coefficient == pytest.approx(0.3)
    assert not args.verbose and not args.remote and not args.logging
    assert args.num_workers == 4

    args = run_mod.build_parser().parse_args(
        ["-c", "-1", "-p", "50", "-min", "64", "-max", "256", "-bc", "0.5",
         "-training", "a.csv", "-test", "b.csv", "-v", "-r", "-l"])
    assert args.consistency_model == -1
    assert args.producer_time_per_event == 50
    assert (args.min_buffer_size, args.max_buffer_size) == (64, 256)
    assert args.buffer_size_coefficient == pytest.approx(0.5)
    assert args.training_data_file_path == "a.csv"
    assert args.verbose and args.remote and args.logging


def test_role_runner_flag_surfaces():
    """server runner: no worker flags; worker runner: no server flags
    (exact reference role split)."""
    sp = run_mod.build_parser(include_worker_flags=False)
    with pytest.raises(SystemExit):
        sp.parse_args(["-min", "1"])
    wp = run_mod.build_parser(include_server_flags=False)
    with pytest.raises(SystemExit):
        wp.parse_args(["-c", "0"])
    assert wp.parse_args(["-bc", "0.7"]).buffer_size_coefficient == \
        pytest.approx(0.7)


def test_csvlog_sink(tmp_path):
    p = tmp_path / "log.csv"
    sink = CsvLogSink(str(p), SERVER_HEADER)
    sink("1;2;3;4;5;6")
    sink.close()
    lines = p.read_text().splitlines()
    assert lines == [SERVER_HEADER, "1;2;3;4;5;6"]
    assert WORKER_HEADER.endswith(";numTuplesSeen")


def test_checkpoint_roundtrip(tmp_path):
    app, _, _ = build_app(0)
    app.run_serial(max_server_iterations=8)
    path = str(tmp_path / "ck.npz")
    ckpt.save(path, app.server)

    app2, _, _ = build_app(0)
    assert ckpt.maybe_restore(path, app2.server)
    np.testing.assert_array_equal(app2.server.theta, app.server.theta)
    assert app2.server.tracker.clocks == app.server.tracker.clocks
    assert app2.server.iterations == app.server.iterations
    # resumed app trains onward from the restored clocks without
    # protocol errors (the bootstrap broadcast re-issues current clocks)
    start_clock = min(app2.server.tracker.clocks)
    app2.run_serial(max_server_iterations=app2.server.iterations + 8)
    assert min(app2.server.tracker.clocks) > start_clock


def test_checkpoint_restore_mid_round(tmp_path):
    """Restoring a checkpoint whose clocks are mid-round (some replies
    withheld by the gate) must not trip the tracker sanitizer: withheld
    workers go back through the consistency gate, not the bootstrap
    broadcast."""
    app, _, _ = build_app(0)
    app.run_serial(max_server_iterations=6)   # 6 % 4 != 0 -> mid-round
    clocks = app.server.tracker.clocks
    assert max(clocks) != min(clocks)          # genuinely mid-round
    path = str(tmp_path / "mid.npz")
    ckpt.save(path, app.server)

    app2, _, _ = build_app(0)
    ckpt.maybe_restore(path, app2.server)
    app2.run_serial(max_server_iterations=app2.server.iterations + 12)
    spread = max(app2.server.tracker.clocks) - min(app2.server.tracker.clocks)
    assert spread <= 1


def test_checkpoint_every_zero_means_exit_only(tmp_path):
    app, _, _ = build_app(0)
    app.server.checkpoint_path = str(tmp_path / "never.npz")
    app.server.checkpoint_every = 0
    app.run_serial(max_server_iterations=8)    # must not raise / save
    import os
    assert not os.path.exists(app.server.checkpoint_path)


def test_fused_checkpoints_and_resumes(tmp_path):
    app, _, _ = build_app(0)
    app.server.checkpoint_path = str(tmp_path / "fused.npz")
    app.server.checkpoint_every = 8
    app.run_fused_bsp(max_server_iterations=16, log_metrics=False)
    z = np.load(app.server.checkpoint_path)
    assert int(z["iterations"]) >= 8
    # resume continues the clock forward
    app2, _, _ = build_app(0)
    ckpt.restore(str(tmp_path / "fused.npz"), app2.server)
    c0 = min(app2.server.tracker.clocks)
    app2.run_fused_bsp(max_server_iterations=app2.server.iterations + 8,
                       log_metrics=False)
    assert min(app2.server.tracker.clocks) > c0


def test_csvlog_append_mode(tmp_path):
    p = tmp_path / "log.csv"
    s1 = CsvLogSink(str(p), SERVER_HEADER)
    s1("row1")
    s1.close()
    s2 = CsvLogSink(str(p), SERVER_HEADER, append=True)
    s2("row2")
    s2.close()
    lines = p.read_text().splitlines()
    assert lines == [SERVER_HEADER, "row1", "row2"]  # one header, no loss


def test_checkpoint_shape_mismatch(tmp_path):
    app, _, _ = build_app(0)
    path = str(tmp_path / "ck.npz")
    ckpt.save(path, app.server)
    other = StreamingPSApp(small_cfg(0, num_workers=2))
    with pytest.raises(ValueError, match="worker count"):
        ckpt.restore(path, other.server)


def test_maybe_restore_missing(tmp_path):
    app, _, _ = build_app(0)
    assert not ckpt.maybe_restore(str(tmp_path / "nope.npz"), app.server)


def test_synth_dataset_shape_and_labels(tmp_path):
    x, y = generate(100, num_features=32, num_classes=5, seed=3)
    assert x.shape == (100, 32) and x.dtype == np.float32
    assert set(np.unique(y)) <= set(range(1, 6))
    assert (x == 0).mean() > 0.5  # sparse like hashed features
    p = tmp_path / "d.csv"
    write_csv(str(p), x, y)
    header = p.read_text().splitlines()[0]
    assert header.endswith(",Score")  # reference label column name
    xx, yy = run_mod.load_test_csv(str(p), 32)
    np.testing.assert_allclose(xx, x, atol=1e-4)
    np.testing.assert_array_equal(yy, y)


def test_load_test_csv_width_check(tmp_path):
    p = tmp_path / "bad.csv"
    p.write_text("a,b,c\n1,2,3\n")
    with pytest.raises(SystemExit, match="expected 5"):
        run_mod.load_test_csv(str(p), 4)


def test_multi_step_equals_repeated_single_step():
    cfg = ModelConfig(num_features=8, num_classes=2, local_learning_rate=0.3)
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    nw, cap = 4, 16
    x = jnp.asarray(rng.normal(size=(nw, cap, 8)).astype(np.float32))
    y = jnp.asarray(rng.integers(1, 3, size=(nw, cap)).astype(np.int32))
    mask = jnp.ones((nw, cap))
    theta0 = jnp.zeros(cfg.num_params)

    multi = bsp.make_bsp_multi_step(cfg, nw, 0.25, rounds=5)
    t_multi, losses = multi(theta0, x, y, mask)
    assert losses.shape == (5,)

    single = bsp.make_bsp_step(cfg, nw, 0.25)
    t = theta0
    for _ in range(5):
        t, _ = single(t, x, y, mask)
    np.testing.assert_allclose(np.asarray(t_multi), np.asarray(t), atol=1e-5)


def test_multi_step_mesh_matches_vmap():
    cfg = ModelConfig(num_features=8, num_classes=2, local_learning_rate=0.3)
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    nw, cap = 8, 16
    x = rng.normal(size=(nw, cap, 8)).astype(np.float32)
    y = rng.integers(1, 3, size=(nw, cap)).astype(np.int32)
    mask = np.ones((nw, cap), np.float32)
    theta0 = jnp.zeros(cfg.num_params)

    m = mesh_mod.worker_mesh()
    multi_mesh = bsp.make_bsp_multi_step(cfg, nw, 1 / nw, rounds=4, mesh=m)
    xs, ys, ms = bsp.shard_worker_batches(m, x, y, mask)
    t_mesh, _ = multi_mesh(theta0, xs, ys, ms)

    multi_vmap = bsp.make_bsp_multi_step(cfg, nw, 1 / nw, rounds=4)
    t_vmap, _ = multi_vmap(theta0, x, y, mask)
    np.testing.assert_allclose(np.asarray(t_mesh), np.asarray(t_vmap),
                               atol=2e-5)


def test_eval_every_skips_offcadence_evals(tmp_path, monkeypatch):
    """--eval_every N: workers/server compute test metrics only on every
    Nth clock; off-cadence worker rows carry the reference's -1
    placeholder.  The throughput/cadence trade-off knob of
    docs/EVALUATION.md."""
    import numpy as np

    from kafka_ps_tpu.cli import run as run_mod
    from kafka_ps_tpu.data.synth import write_csv, generate

    monkeypatch.chdir(tmp_path)
    x, y = generate(260, 16, 3, noise=1.0, sparsity=0.5, seed=0)
    write_csv("train.csv", x[:200], y[:200])
    write_csv("test.csv", x[200:], y[200:])
    args = run_mod.build_parser().parse_args(
        ["-training", "train.csv", "-test", "test.csv",
         "--num_features", "16", "--num_classes", "3",
         "--num_workers", "2", "-p", "1", "-l", "--mode", "serial",
         "--eval_every", "3", "--max_iterations", "16"])
    assert run_mod.run_with_args(args) == 0

    import pandas as pd
    w = pd.read_csv("logs-worker.csv", sep=";")
    on = w[w["vectorClock"] % 3 == 0]
    off = w[w["vectorClock"] % 3 != 0]
    assert len(on) and len(off)
    assert (on["fMeasure"] >= 0).all()
    assert (off["fMeasure"] == -1).all()
    s = pd.read_csv("logs-server.csv", sep=";")
    assert set(s["vectorClock"] % 3) == {0}


def test_cli_param_shards_range_sharded_run(tmp_path, monkeypatch):
    """--param_shards N drives the range-sharded 2-D mesh end-to-end
    from the public CLI contract (VERDICT r1: previously library-only).
    8 virtual devices -> workers 4 x params 2 mesh, 8 logical workers."""
    import pandas as pd

    from kafka_ps_tpu.cli import run as run_mod
    from kafka_ps_tpu.data.synth import generate, write_csv

    monkeypatch.chdir(tmp_path)
    x, y = generate(460, 16, 3, noise=1.0, sparsity=0.5, seed=0)
    write_csv("train.csv", x[:400], y[:400])
    write_csv("test.csv", x[400:], y[400:])
    args = run_mod.build_parser().parse_args(
        ["-training", "train.csv", "-test", "test.csv",
         "--num_features", "16", "--num_classes", "3",
         "--num_workers", "8", "-p", "1", "-l", "--fused",
         "--param_shards", "2", "--max_iterations", "40",
         "--local_learning_rate", "0.1"])
    assert run_mod.run_with_args(args) == 0

    s = pd.read_csv("logs-server.csv", sep=";")
    assert len(s) >= 5                       # 40 iters / 8 workers
    assert s["loss"].iloc[-1] < s["loss"].iloc[0]
    w = pd.read_csv("logs-worker.csv", sep=";")
    assert set(w["partition"]) == set(range(8))


def test_cli_param_shards_requires_fused():
    from kafka_ps_tpu.cli import run as run_mod
    args = run_mod.build_parser().parse_args(
        ["--param_shards", "2", "-test", "nonexistent.csv"])
    with __import__("pytest").raises(SystemExit, match="requires --fused"):
        run_mod.run_with_args(args)


def test_status_reporter_formats_and_rates():
    """utils/status.py: field rendering + the derived iters/s rate —
    the Control Center stand-in's line format (docs/EVALUATION.md)."""
    import io

    from kafka_ps_tpu.utils.status import StatusReporter

    samples = iter([{"iters": 0, "clocks": ["0:1", "1:1"],
                     "active": "2/2", "pending": {"gradients": 3}},
                    {"iters": 20, "clocks": ["0:6", "1:5"],
                     "active": "2/2", "pending": {"gradients": 0}}])
    ticks = iter([0.0, 2.0])
    out = io.StringIO()
    rep = StatusReporter(0.0, lambda: next(samples), out=out,
                         clock=lambda: next(ticks))
    rep.emit()
    rep.emit()
    lines = out.getvalue().splitlines()
    assert lines[0].startswith("[status] iters=0 clocks=0:1,1:1")
    assert "pending gradients=3" in lines[0]
    # 20 iters over 2 s -> +10.0/s on the second line
    assert "iters=20 (+10.0/s)" in lines[1]
    assert "active=2/2" in lines[1]


def test_status_reporter_derived_per_s_rates():
    """Any `*_per_s` key — top-level or one dict deep — is a cumulative
    count rendered as the rate since the previous line ("--" until a
    baseline exists): how the serving plane's QPS rides the heartbeat
    (docs/SERVING.md) without a schema change per counter."""
    import io

    from kafka_ps_tpu.utils.status import StatusReporter

    samples = iter([
        {"iters": 0, "predictions_per_s": 0,
         "serving": {"occ": 1.0, "rejections_per_s": 0}},
        {"iters": 10, "predictions_per_s": 300,
         "serving": {"occ": 3.5, "rejections_per_s": 4}},
        {"iters": 20, "predictions_per_s": 450,
         "serving": {"occ": 2.0, "rejections_per_s": 4}},
    ])
    ticks = iter([0.0, 2.0, 4.0])
    out = io.StringIO()
    rep = StatusReporter(0.0, lambda: next(samples), out=out,
                         clock=lambda: next(ticks))
    for _ in range(3):
        rep.emit()
    lines = out.getvalue().splitlines()
    # first line: no baseline yet for any derived key
    assert "predictions_per_s=--" in lines[0]
    assert "serving occ=1.0 rejections_per_s=--" in lines[0]
    # 300 predictions over 2 s; 4 rejections over the same window
    assert "predictions_per_s=150.0" in lines[1]
    assert "rejections_per_s=2.0" in lines[1]
    # each key rates against ITS OWN previous sample, not the first
    assert "predictions_per_s=75.0" in lines[2]
    assert "rejections_per_s=0.0" in lines[2]
    # non-rate fields pass through untouched
    assert "occ=3.5" in lines[1] and "occ=2.0" in lines[2]


def test_status_reporter_survives_source_errors():
    import io

    from kafka_ps_tpu.utils.status import StatusReporter

    out = io.StringIO()

    def bad_source():
        raise RuntimeError("torn down")

    rep = StatusReporter(0.0, bad_source, out=out)
    rep.emit()                       # must not raise
    assert "error=" in out.getvalue()


def test_threaded_run_emits_status_lines(capsys):
    """`--status_every` through the drive loop: the reporter thread
    samples a live run and stops cleanly with it."""
    app, logs, _ = build_app(0)
    app.run_threaded(max_server_iterations=40, status_every=0.05)
    err = capsys.readouterr().err
    status_lines = [ln for ln in err.splitlines()
                    if ln.startswith("[status]")]
    assert status_lines, err
    assert "clocks=" in status_lines[-1]
    assert "buffers=" in status_lines[-1]


def test_fused_chunking_keeps_per_clock_log_cadence(tmp_path, monkeypatch):
    """eval_every > 1 engages the multi-round chunk dispatch
    (StreamingPSApp.FUSED_CHUNK_ROUNDS): the worker log must still carry
    one row per worker per CLOCK (the per-node cadence,
    WorkerTrainingProcessor.java:85-92) — off-cadence rows with the
    reference's -1 placeholders, eval rows with shared metrics — and the
    combined logs must stay auditor-clean under the sequential
    contract."""
    import pandas as pd

    from kafka_ps_tpu.cli import run as run_mod
    from kafka_ps_tpu.data.synth import generate, write_csv
    from kafka_ps_tpu.evaluation import validate

    monkeypatch.chdir(tmp_path)
    x, y = generate(460, 16, 3, noise=1.0, sparsity=0.5, seed=0)
    write_csv("train.csv", x[:400], y[:400])
    write_csv("test.csv", x[400:], y[400:])
    args = run_mod.build_parser().parse_args(
        ["-training", "train.csv", "-test", "test.csv",
         "--num_features", "16", "--num_classes", "3",
         "--num_workers", "4", "-p", "1", "-l", "--fused",
         "--eval_every", "10", "--max_iterations", "160",
         "--local_learning_rate", "0.1"])
    assert run_mod.run_with_args(args) == 0

    w = pd.read_csv("logs-worker.csv", sep=";")
    s = pd.read_csv("logs-server.csv", sep=";")
    # 160 iterations / 4 workers = 40 clocks, EVERY clock logged
    for wk, g in w.groupby("partition"):
        assert g["vectorClock"].tolist() == list(range(1, 41))
    # off-cadence rows carry the reference's -1 placeholders; eval rows
    # carry real shared metrics
    off = w[w["vectorClock"] % 10 != 0]
    assert (off["fMeasure"] == -1).all() and (off["accuracy"] == -1).all()
    on = w[w["vectorClock"] % 10 == 0]
    assert (on["fMeasure"] > 0).all()
    assert (off["loss"] != -1).any()         # per-round losses are real
    # server evals exactly on cadence
    assert s["vectorClock"].tolist() == [10, 20, 30, 40]
    assert validate.validate_run(w, s, consistency_model=0) == []


def test_fused_chunking_range_sharded_mesh(tmp_path, monkeypatch):
    """The chunked dispatch also drives the range-sharded 2-D mesh
    (range_sharded.make_range_sharded_step(rounds=CHUNK)): same per-clock
    cadence and contract on the virtual 8-device mesh."""
    import pandas as pd

    from kafka_ps_tpu.cli import run as run_mod
    from kafka_ps_tpu.data.synth import generate, write_csv
    from kafka_ps_tpu.evaluation import validate

    monkeypatch.chdir(tmp_path)
    x, y = generate(460, 16, 3, noise=1.0, sparsity=0.5, seed=0)
    write_csv("train.csv", x[:400], y[:400])
    write_csv("test.csv", x[400:], y[400:])
    args = run_mod.build_parser().parse_args(
        ["-training", "train.csv", "-test", "test.csv",
         "--num_features", "16", "--num_classes", "3",
         "--num_workers", "8", "-p", "1", "-l", "--fused",
         "--param_shards", "2", "--eval_every", "8",
         "--max_iterations", "192", "--local_learning_rate", "0.1"])
    assert run_mod.run_with_args(args) == 0

    w = pd.read_csv("logs-worker.csv", sep=";")
    s = pd.read_csv("logs-server.csv", sep=";")
    for wk, g in w.groupby("partition"):
        assert g["vectorClock"].tolist() == list(range(1, 25))
    assert validate.validate_run(w, s, consistency_model=0) == []
    assert s["loss"].iloc[-1] < s["loss"].iloc[0]


def test_deferred_sink_keeps_a_fetch_failure_and_reraises(monkeypatch):
    """A device scalar that cannot be fetched fails the run: the drain
    thread keeps the error and submit/flush/close re-raise it — no row
    is written with a nan standing in for the value."""
    import time

    import jax.numpy as jnp
    import pytest

    from kafka_ps_tpu.utils import asynclog

    def device_lost(values):
        raise FloatingPointError("injected fetch failure")
    monkeypatch.setattr(asynclog, "_fetch_batched", device_lost)

    lines = []
    sink = asynclog.DeferredSink(lines.append, drain_interval=0.01)
    sink.submit("row;{}", jnp.float32(1.5))
    deadline = time.monotonic() + 10.0
    while sink._error is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert isinstance(sink._error, FloatingPointError)
    for entry in (lambda: sink.submit("row;{}", 2.0), sink.flush,
                  sink.close):
        with pytest.raises(RuntimeError, match="log drain failed"):
            entry()
    assert lines == []

    # flush() on the caller's thread raises the fetch error directly
    direct = asynclog.DeferredSink(lines.append, drain_interval=3600.0)
    direct.submit("row;{}", jnp.float32(2.5))
    with pytest.raises(FloatingPointError):
        direct.flush()
    direct.close()
    assert lines == []


# -- the deferred sink's backlog (utils/asynclog) -----------------------------

class _Future:
    """A device scalar's stand-in: ready when the test says so, and it
    remembers the threads that fetched it."""

    def __init__(self, value, ready=False, fails=False):
        import threading
        self.value, self.fails = value, fails
        self.fetched_on: list[str] = []
        self._ready = threading.Event()
        if ready:
            self._ready.set()

    def resolve(self):
        self._ready.set()

    def is_ready(self):
        return self._ready.is_set()

    def block_until_ready(self):
        assert self._ready.wait(30.0)
        return self

    def __array__(self, dtype=None, copy=None):
        import threading
        self.fetched_on.append(threading.current_thread().name)
        assert self._ready.wait(30.0)
        if self.fails:
            raise FloatingPointError("injected fetch failure")
        return np.asarray(self.value, np.float32)


def _recording_sink(max_pending, drain_interval=3600.0):
    """(sink, [(thread name, line)]): with the default interval only a
    backlog wakes the drain thread."""
    import threading

    from kafka_ps_tpu.utils import asynclog
    written = []
    sink = asynclog.DeferredSink(
        lambda line: written.append(
            (threading.current_thread().name, line)),
        max_pending=max_pending, drain_interval=drain_interval)
    return sink, written


def _in_thread(fn, name="submitter"):
    """Run fn on a named thread; .error holds what it raised."""
    import threading

    def run():
        try:
            fn()
        except BaseException as e:       # noqa: BLE001 — handed to the test
            t.error = e
    t = threading.Thread(target=run, name=name, daemon=True)
    t.error = None
    t.start()
    return t


def _wait_until(cond, seconds=10.0):
    import time
    deadline = time.monotonic() + seconds
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert cond()


def test_deferred_sink_backlog_waits_for_the_oldest_rows_only():
    """Over the bound with the oldest rows ready and the newest not:
    submit returns once the drain thread has taken the oldest, the
    newest still unready, and the submitting thread neither fetched nor
    wrote."""
    sink, written = _recording_sink(max_pending=4)
    old, new = _Future(1.5, ready=True), _Future(2.5)

    def submits():
        for i in range(3):
            sink.submit(f"old{i};{{}}", old)
        for i in range(2):                   # the fifth meets the bound
            sink.submit(f"new{i};{{}}", new)
    t = _in_thread(submits)
    t.join(10.0)
    assert not t.is_alive() and t.error is None
    assert not new.is_ready() and new.fetched_on == []
    assert sink.backlog_waits()[0] == 1 and sink.backlog_waits()[1] > 0
    _wait_until(lambda: len(written) == 3)
    assert written == [("kps-log-drain", f"old{i};1.5") for i in range(3)]
    assert old.fetched_on == ["kps-log-drain"]     # once for three rows
    new.resolve()
    sink.flush()
    assert [line for _, line in written[3:]] == ["new0;2.5", "new1;2.5"]
    sink.close()


def test_deferred_sink_backlog_waits_until_the_oldest_resolve():
    """With nothing ready the submitter waits; it returns when the
    oldest rows' value resolves, later rows still unready."""
    sink, written = _recording_sink(max_pending=4)
    first, later = _Future(0.25), _Future(0.75)

    def submits():
        for i in range(3):
            sink.submit(f"a{i};{{}}", first)
        for i in range(2):
            sink.submit(f"b{i};{{}}", later)
    t = _in_thread(submits)
    t.join(0.3)
    assert t.is_alive() and written == [] and first.fetched_on == []
    first.resolve()
    t.join(10.0)
    assert not t.is_alive() and t.error is None
    assert not later.is_ready() and "submitter" not in first.fetched_on
    _wait_until(lambda: len(written) == 3)
    later.resolve()
    sink.close()
    assert [line for _, line in written] == [
        "a0;0.25", "a1;0.25", "a2;0.25", "b0;0.75", "b1;0.75"]
    assert {name for name, _ in written[:3]} == {"kps-log-drain"}


@pytest.mark.parametrize("path", ["copy", "stack"])
def test_deferred_sink_fetches_each_distinct_value_once(monkeypatch, path):
    """N rows that share a value cost one fetched value, and the copies
    and the stacked transfer write the same lines."""
    import contextlib

    import jax.numpy as jnp

    from kafka_ps_tpu.utils import asynclog

    spans = []
    monkeypatch.setattr(
        asynclog.trace, "span",
        lambda name, **args: (spans.append((name, args)),
                              contextlib.nullcontext())[1])
    assert asynclog._fetch_path(asynclog._MAX_COPY) == "copy"
    assert asynclog._fetch_path(asynclog._MAX_COPY + 1) == "stack"
    monkeypatch.setattr(asynclog, "_MAX_COPY",
                        10 ** 6 if path == "copy" else 0)
    numbers = [0.1, 1.0 / 3.0, 1e-7, 12345.678, -1.0]
    shared = jnp.float32(numbers[0])
    values = [shared] * 40 + [jnp.float32(v) for v in numbers[1:]]
    lines = []
    sink = asynclog.DeferredSink(lines.append, drain_interval=3600.0)
    for i, v in enumerate(values):
        sink.submit(f"{i};{{}};{{}}", v, 7)
    sink.flush()
    sink.close()
    assert lines == [f"{i};{float(np.float32(v))};7.0"
                     for i, v in enumerate([numbers[0]] * 40 + numbers[1:])]
    fetches = [args for name, args in spans if name == "log.fetch"]
    assert fetches == [{"scalars": 44, "distinct": 5, "path": path}]

    # one value, many rows: one fetch of one value, on the copy path
    monkeypatch.undo()
    one = _Future(3.0, ready=True)
    sink, written = _recording_sink(max_pending=4096)
    for i in range(100):
        sink.submit("{};{}", one, one)
    sink.flush()
    sink.close()
    assert len(written) == 100 and len(one.fetched_on) == 1


def test_deferred_sink_three_producers_and_a_small_bound():
    """Every row written once, each producer's rows in its order, all of
    them by the time flush() returns."""
    sink, written = _recording_sink(max_pending=4, drain_interval=0.01)
    rows = 60

    def producer(k):
        def run():
            for i in range(rows):
                value = _Future(float(i), ready=True) if i % 3 else float(i)
                sink.submit(f"p{k};{i};{{}}", value)
        return run
    threads = [_in_thread(producer(k), name=f"producer-{k}")
               for k in range(3)]
    for t in threads:
        t.join(30.0)
        assert not t.is_alive() and t.error is None
    sink.flush()
    lines = [line for _, line in written]
    assert len(lines) == 3 * rows == len(set(lines))
    for k in range(3):
        assert [ln for ln in lines if ln.startswith(f"p{k};")] == [
            f"p{k};{i};{float(i)}" for i in range(rows)]
    assert not any(name.startswith("producer") for name, _ in written)
    assert sink.backlog_waits()[0] > 0
    sink.close()


def test_deferred_sink_fetch_failure_releases_a_waiting_submitter():
    """The drain thread's failure ends a backlog wait with the error;
    flush, close and the next submit raise it too; nothing is written."""
    sink, written = _recording_sink(max_pending=4)
    bad, never = _Future(1.0, ready=True, fails=True), _Future(2.0)

    def submits():
        sink.submit("bad;{}", bad)
        for i in range(5):       # over the bound even without `bad`
            sink.submit(f"r{i};{{}}", never)
    t = _in_thread(submits)
    t.join(10.0)
    assert not t.is_alive()
    assert isinstance(t.error, RuntimeError)
    assert isinstance(t.error.__cause__, FloatingPointError)
    assert not never.is_ready()
    for entry in (lambda: sink.submit("row;{}", 2.0), sink.flush,
                  sink.close):
        with pytest.raises(RuntimeError, match="log drain failed"):
            entry()
    assert written == []


def _fused_rows(max_pending):
    """A fused run of 3 chunks at eval_every 8 with the sinks' bound at
    `max_pending`: (server rows, worker rows) without their timestamps,
    and the app's last_run."""
    import dataclasses

    from tests.test_runtime import fill_buffers, make_dataset
    cfg = dataclasses.replace(small_cfg(0, num_workers=4), eval_every=8)
    x, y = make_dataset()
    logs = {"server": [], "worker": []}
    app = StreamingPSApp(cfg, test_x=x, test_y=y,
                         server_log=logs["server"].append,
                         worker_log=logs["worker"].append)
    for sink in app._log_sinks():
        sink._max_pending = max_pending
    fill_buffers(app, x, y)
    app.run_fused_bsp(max_server_iterations=24 * cfg.num_workers)
    app.close_logs()
    strip = lambda rows: [ln.split(";", 1)[1] for ln in rows]  # noqa: E731
    return strip(logs["server"]), strip(logs["worker"]), app.last_run


@pytest.fixture(scope="module")
def fused_rows_unbounded():
    return _fused_rows(10 ** 9)


@pytest.mark.parametrize("max_pending", [8, 4096])
def test_fused_run_rows_do_not_depend_on_the_sinks_bound(
        fused_rows_unbounded, max_pending):
    """The backlog wait changes when a row is written, never what it
    says or where it stands; the counter says whether it engaged."""
    server, worker, last = _fused_rows(max_pending)
    want_server, want_worker, unbounded = fused_rows_unbounded
    assert server == want_server and len(server) == 3
    assert worker == want_worker and len(worker) == 24 * 4
    assert unbounded["log_backlog_waits"] == 0
    assert unbounded["log_backlog_wait_s"] == 0.0
    if max_pending == 8:
        assert last["log_backlog_waits"] > 0
        assert last["log_backlog_wait_s"] > 0.0
    else:
        assert last["log_backlog_waits"] == 0

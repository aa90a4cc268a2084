"""System assembly + drive loops — the reference's apps/ layer
(BaseKafkaApp/ServerApp/WorkerApp topologies, BaseKafkaApp.java:23-87)
without the broker.

Wires: CSV stream producer → per-worker sliding buffers (the INPUT_DATA
hop), WorkerNodes ↔ ServerNode over the in-process fabric (the
WEIGHTS/GRADIENTS hops), with three drive modes:

  * `run_serial` — deterministic single-thread scheduler (the test
    harness the reference never built, SURVEY §4);
  * `run_threaded` — one thread per worker + server on the main thread,
    mirroring the reference's 4 stream threads (BaseKafkaApp.java:70);
    real wall-clock overlap for the async consistency models via JAX
    async dispatch;
  * `run_fused_bsp` — the TPU-native fast path for the sequential model:
    whole iterations as single jit'd shard_map steps (parallel/bsp.py),
    buffers re-slabbed between steps.
"""

from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np

from kafka_ps_tpu.data.buffer import SlidingBuffer
from kafka_ps_tpu.data.stream import CsvStreamProducer
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.runtime import fabric as fabric_mod
from kafka_ps_tpu.runtime.server import LogSink, ServerNode
from kafka_ps_tpu.runtime.worker import WorkerNode
from kafka_ps_tpu.telemetry import NULL_TELEMETRY
from kafka_ps_tpu.utils import asynclog, device
from kafka_ps_tpu.utils.asynclog import DeferredSink
from kafka_ps_tpu.utils.config import PSConfig, SEQUENTIAL
from kafka_ps_tpu.utils.trace import NULL_TRACER


# the slab-refresh part of `StreamingPSApp.last_run` before any refresh
NO_SLAB_REFRESH = {"slab_refreshes": 0, "slab_refresh_s": 0.0,
                   "slab_refresh_bytes": 0}
# and the part a folded task's fused call fills in, the seconds at its
# two ends (`_run_fused_loop`): on every other path there is no flat
# vector to bring up or down
NO_CALL_EDGES = {"theta_up_s": 0.0, "device_wait_s": 0.0,
                 "theta_down_s": 0.0}


class StreamingPSApp:
    """One process hosting the server + N logical workers, like the
    reference's single-JVM local deployment (SURVEY §4)."""

    def __init__(self, cfg: PSConfig,
                 test_x: np.ndarray | None = None,
                 test_y: np.ndarray | None = None,
                 server_log: LogSink | None = None,
                 worker_log: LogSink | None = None,
                 clock_ms=None,
                 tracer=None,
                 fabric=None,
                 telemetry=None):
        self.tracer = tracer or NULL_TRACER
        self.telemetry = telemetry or NULL_TELEMETRY
        # the start-up record's `app_init` phase (utils/device.py): the
        # whole assembly, and from here on the record's builds and
        # retroactive phases are this tracer's spans too
        device.attach(self.tracer)
        with device.setup_phase("app_init", self.tracer):
            self._assemble(cfg, test_x, test_y, server_log, worker_log,
                           clock_ms, fabric)

    def _assemble(self, cfg, test_x, test_y, server_log, worker_log,
                  clock_ms, fabric) -> None:
        """`__init__`'s work: the task and its flat vector, the test
        set placed, buffers, nodes, evaluation engine, sinks."""
        self.cfg = cfg
        # callers may supply a durable fabric (log/durable_fabric.py,
        # `--durable-log`); default stays the volatile in-memory one
        self.fabric = fabric or fabric_mod.Fabric(tracer=self.tracer)
        # what a row is (width, dtype) is the task's to say
        from kafka_ps_tpu.models.task import get_task
        task = get_task(cfg.task, cfg.model)
        self.buffers = [
            SlidingBuffer(task.row_width, cfg.buffer,
                          clock_ms=clock_ms, telemetry=self.telemetry,
                          worker=w, dtype=task.row_dtype)
            for w in range(cfg.num_workers)]
        # deferred sinks: the per-node hot path logs device futures
        # (loss/F1/accuracy) without blocking on them — flushed when
        # ready and force-flushed at drive-loop exit (utils/asynclog)
        server_log = DeferredSink(server_log or (lambda line: None))
        worker_log = DeferredSink(worker_log or (lambda line: None))
        self.server = ServerNode(cfg, self.fabric, test_x, test_y, server_log,
                                 tracer=self.tracer,
                                 telemetry=self.telemetry)
        if not task.batches_workers:
            # a folded task's flat vector lives on the host whenever no
            # drive call runs (`_run_fused_loop` has why), from the start
            self.server.theta = np.asarray(self.server.theta)
        self.workers = [
            WorkerNode(w, cfg, self.fabric, self.buffers[w], test_x, test_y,
                       worker_log, tracer=self.tracer,
                       telemetry=self.telemetry)
            for w in range(cfg.num_workers)]
        # compressed delta transport (kafka_ps_tpu/compress/): one shared
        # weights compressor on the server, one error-feedback residual
        # per worker.  {} when --compress none — everything above runs
        # untouched (messages carry no encoded payloads).
        self.compressors: dict[int, object] = {}
        if cfg.compress and cfg.compress != "none":
            from kafka_ps_tpu import compress
            codec = compress.get_codec(compress.parse_codec(cfg.compress),
                                       self.server.task.num_params)
            self.server.compressor = compress.WeightsCompressor(codec)
            for w in self.workers:
                w.compressor = compress.ErrorFeedback(codec)
                self.compressors[w.worker_id] = w.compressor
            # residuals are worker state: in-process runs fold them into
            # the server-side checkpoint next to the buffers
            self.server.checkpoint_residuals = self.compressors
        self._stop = threading.Event()
        # fused-program cache: re-entering run_fused_bsp (resume, the
        # benchmark's probe and window calls, alternating with other
        # drive modes) must reuse the SAME jit wrappers — a fresh jax.jit(shard_map(...)) re-traces
        # the whole multi-round program every call (hundreds of ms at
        # MLP-4096) even when the XLA compile cache hits
        self._fused_programs: dict = {}
        # the last drive call, written as run_fused_bsp / run_serial
        # return: path, seconds, the slab refreshes at the head of a
        # fused call (count, seconds, bytes), which a trace of the
        # steady state never holds, and how often and how long the call
        # waited on a log sink's backlog
        self.last_run: dict = {}
        self._reroute_counter = 0
        # durable resume: leading stream rows to drop because the log
        # already holds them (the CSV producer deterministically
        # re-produces the identical global row order, so "skip the
        # first N" is exactly-once re-ingestion; set by recover_durable)
        self._ingest_skip = 0
        self.worker_failures: list[tuple[int, BaseException | str]] = []
        # online serving plane (kafka_ps_tpu/serving/): built on demand
        # by enable_serving(); None keeps the app purely a trainer
        self.serving_engine = None
        # async coalescing eval engine (kafka_ps_tpu/evaluation/engine.py):
        # default-on when there is a test set — eval leaves the apply
        # critical path.  `--no-eval-async` keeps the fused programs.
        self.eval_engine = None
        if cfg.eval_async and test_x is not None:
            self.enable_async_eval()
        # rolling critical-path sampler, built lazily on first status()
        # heartbeat with telemetry on (telemetry/critpath.py)
        self._critpath = None
        # which solver programs the drive dispatches ("xla": the
        # per-node path's; run_fused_bsp sets "fused-bsp") — the
        # start-up line's field, printed in [status]
        self.solver = "xla"
        # Multi-host: the subset of logical workers this process hosts
        # (None = all).  Every host streams the same CSV with the same
        # global round-robin, keeping only its own workers' rows — the
        # per-broker-partition analogue (parallel/multihost.py).
        self.local_workers: set[int] | None = None

    # -- ingestion sink (the INPUT_DATA topic hop) -------------------------

    def data_sink(self, worker: int, features: dict[int, float],
                  label: int) -> None:
        if self._ingest_skip > 0:
            # durable resume: this row is already in the log (and, via
            # checkpoint + replay, in a buffer) — drop the re-produced
            # copy instead of ingesting it twice
            self._ingest_skip -= 1
            self.tracer.count("data.replay_skipped_rows")
            return
        status = self.server.tracker.tracker[worker]
        if not status.active:
            # partition reassignment: rows destined for an evicted worker
            # go round-robin to the survivors (the Kafka consumer-group
            # rebalance analogue, SURVEY §5).  Reroute BEFORE the local
            # filter: every host sees the same stream and membership, so
            # the deterministic counter picks the same survivor
            # everywhere and exactly one host keeps the row.
            active = self.server.tracker.active_workers
            worker = active[self._reroute_counter % len(active)]
            self._reroute_counter += 1
            self.tracer.count("data.rerouted_rows")
        if self.local_workers is not None and worker not in self.local_workers:
            return                  # another host's partition
        if getattr(self.fabric, "durable", False):
            # the INPUT_DATA hop: log the row under its FINAL key (post
            # reroute) and mark it consumed immediately — it is applied
            # to the buffer on the next line, so the ingest group's
            # offset is the count of buffered rows
            from kafka_ps_tpu.runtime.messages import LabeledData
            offset = self.fabric.persist(
                fabric_mod.INPUT_DATA_TOPIC, worker,
                LabeledData(features=features, label=label))
            self.fabric.mark_consumed(
                fabric_mod.INPUT_DATA_TOPIC, worker, offset)
        self.buffers[worker].add(features, label)

    def make_producer(self, csv_path: str, has_header: bool = True,
                      sleep=time.sleep) -> CsvStreamProducer:
        return CsvStreamProducer(
            csv_path, self.cfg.num_workers, self.data_sink,
            time_per_event_ms=self.cfg.stream.time_per_event_ms,
            prefill_per_worker=self.cfg.stream.prefill_per_worker,
            has_header=has_header, sleep=sleep)

    def wait_for_prefill(self, min_per_worker: int = 1,
                         timeout: float = 60.0) -> None:
        """The reference sleeps 20 s after starting the producer
        (ServerAppRunner.java:95); we wait on the actual invariant."""
        deadline = time.monotonic() + timeout
        waiting = [w for w in self.server.tracker.active_workers
                   if self.local_workers is None or w in self.local_workers]
        while any(self.buffers[w].count < min_per_worker for w in waiting):
            if time.monotonic() > deadline:
                raise TimeoutError("buffers not prefilled in time")
            time.sleep(0.01)

    def wait_for_stream_settle(self, producer,
                               timeout: float = 120.0) -> None:
        """Wait until the producer's unthrottled prefill burst is done
        (prefill rows sent, stream ended, or producer stopped) before
        training starts.  Training mid-burst races each iteration's
        buffer snapshot against the tail of the burst, making early
        windows timing-dependent — the reference avoided the same race
        with a blanket 20 s sleep (ServerAppRunner.java:95).  A paced
        stream slower than `timeout` just starts training (live tail
        ingestion is the steady state, only the burst is waited out)."""
        prefill = self.cfg.num_workers * self.cfg.stream.prefill_per_worker
        deadline = time.monotonic() + timeout
        while (producer.rows_sent < prefill
               and not producer.finished.is_set()
               and not producer.stopped.is_set()):
            if time.monotonic() > deadline:
                return
            time.sleep(0.005)

    # -- durable-log recovery (log/durable_fabric.py) ----------------------

    def recover_durable(self) -> dict[str, int]:
        """Crash recovery over a durable fabric, run once AFTER the
        checkpoint restore and BEFORE the producer starts:

          * re-enqueue the unconsumed WEIGHTS / GRADIENTS tail (the
            in-flight messages the dead process held);
          * replay the unconsumed INPUT_DATA tail into the restored
            buffers (rows ingested after the last checkpoint);
          * arm the re-ingestion skip so the restarted producer drops
            the rows the log already holds.

        The replay floor is the checkpoint's recorded offsets when the
        restore found any (`server.restored_log_offsets`), else the
        durably committed ones.  Returns replay counts per topic."""
        ckpt_offsets = self.server.restored_log_offsets
        counts = self.fabric.recover(ckpt_offsets)
        replayed_rows = 0
        total_logged = 0
        for topic, key in self.fabric.manager.partitions(
                fabric_mod.INPUT_DATA_TOPIC):
            total_logged += self.fabric.manager.get(topic, key).next_offset
            for offset, row in self.fabric.replay(topic, key, ckpt_offsets):
                self.buffers[key].add(row.features, row.label)
                self.fabric.mark_consumed(topic, key, offset)
                replayed_rows += 1
        self._ingest_skip = total_logged
        counts[fabric_mod.INPUT_DATA_TOPIC] = replayed_rows
        return counts

    # -- serving plane (kafka_ps_tpu/serving/, docs/SERVING.md) ------------

    def enable_serving(self):
        """Attach the online serving plane: a SnapshotRegistry on the
        server (publish at every gate release) plus a PredictionEngine
        batching reads against it.  Sized by cfg.serving.  Idempotent;
        returns the engine."""
        if self.serving_engine is not None:
            return self.serving_engine
        from kafka_ps_tpu.serving.engine import PredictionEngine
        from kafka_ps_tpu.serving.snapshot import SnapshotRegistry
        scfg = self.cfg.serving
        registry = SnapshotRegistry(capacity=scfg.ring_capacity)
        self.server.serving = registry
        self.serving_engine = PredictionEngine(
            self.server.task, registry,
            max_batch=scfg.max_batch,
            deadline_s=scfg.deadline_ms / 1000.0,
            queue_limit=scfg.queue_limit,
            shed_deadline_s=(scfg.shed_deadline_ms / 1000.0
                             if scfg.shed_deadline_ms else None),
            auto=scfg.auto,
            tracer=self.tracer, telemetry=self.telemetry)
        return self.serving_engine

    def close_serving(self) -> None:
        """Stop the engine's batcher thread (holds jit'd callables —
        must be joined before interpreter exit, docs/TESTING.md)."""
        if self.serving_engine is not None:
            self.serving_engine.close()

    # -- async eval plane (evaluation/engine.py, docs/EVALUATION.md) -------

    def enable_async_eval(self):
        """Attach the async coalescing eval engine to the server: eval-
        cadence applies submit (theta, clock) snapshots to its bounded
        queue instead of fusing the eval, and a dedicated thread
        coalesces pending snapshots into batched vmap dispatches,
        emitting CSV rows back through `server._emit_eval` in strict
        clock order (bitwise-identical to the fused path).  Idempotent;
        returns the engine (None without a test set)."""
        if self.eval_engine is not None:
            return self.eval_engine
        if self.server.test_x is None:
            return None
        from kafka_ps_tpu.evaluation.engine import EvalEngine
        self.eval_engine = self.server.attach_eval_engine(EvalEngine(
            self.server.task, self.server.test_x, self.server.test_y,
            self.server._emit_eval,
            telemetry=self.telemetry, tracer=self.tracer))
        return self.eval_engine

    def close_eval(self) -> None:
        """Drain pending evals and join the engine thread (holds jit'd
        callables — same interpreter-exit discipline as serving)."""
        if self.eval_engine is not None:
            self.eval_engine.close()

    # -- tiered residency (kafka_ps_tpu/store/, docs/TIERING.md) -----------

    def enable_tiering(self, cold_dir: str | None = None):
        """Attach a TieredParamStore to the server per cfg.tier and
        start its policy thread.  `cold_dir` hosts the cold partition
        (required when the warm tier is capped; under --durable-log the
        CLI passes `<log-dir>/param-cold`).  No-op when both caps are 0
        — theta stays fully resident.  Returns the store (or None)."""
        if not self.cfg.tier.enabled:
            return None
        if self.server.param_store is not None:
            return self.server.param_store
        from kafka_ps_tpu.runtime.messages import KeyRange
        from kafka_ps_tpu.store import ColdStore, TieredParamStore
        tcfg = self.cfg.tier
        cold = ColdStore.open(cold_dir) if cold_dir is not None else None
        store = TieredParamStore(
            np.asarray(self.server.theta),
            KeyRange(0, self.server.task.num_params),
            hot_bytes=tcfg.hot_bytes, warm_bytes=tcfg.warm_bytes,
            page_params=tcfg.page_params, cold=cold,
            telemetry=self.telemetry,
            rebalance_interval_s=tcfg.rebalance_interval_s)
        self.server.attach_param_store(store)
        store.start_policy_thread()
        return store

    def close_tiering(self) -> None:
        """Join the policy thread and close an owned cold log."""
        if self.server.param_store is not None:
            self.server.param_store.close()

    # -- membership --------------------------------------------------------

    def readmit_worker(self, worker_id: int) -> int:
        """Elastic scale-up through the app: rejoin the worker on the
        server AND reset its compile-grace baseline so the supervisor
        grants the first post-rejoin iteration the 10x jit grace."""
        clock = self.server.readmit_worker(worker_id)
        self.workers[worker_id].iterations_at_join = \
            self.workers[worker_id].iterations
        self.workers[worker_id].last_progress = time.monotonic()
        return clock

    # -- live observability (utils/status.py) ------------------------------

    def status(self) -> dict:
        """One sample of the runtime's pulse — rendered by StatusReporter
        as the periodic `[status]` stderr line (`--status_every`)."""
        tr = self.server.tracker
        active = tr.active_workers
        out = {
            "iters": self.server.iterations,
            "clocks": [f"{w}:{tr.tracker[w].vector_clock}"
                       for w in range(self.cfg.num_workers)],
            "active": f"{len(active)}/{self.cfg.num_workers}",
            "pending": {
                "weights": self.fabric.total_pending(
                    fabric_mod.WEIGHTS_TOPIC),
                "gradients": self.fabric.total_pending(
                    fabric_mod.GRADIENTS_TOPIC)},
            "buffers": [b.count for b in self.buffers],
            "solver": self.solver,
        }
        if self.eval_engine is not None:
            out["eval_lag"] = self.eval_engine.lag_clocks
        if self.serving_engine is not None:
            s = self.serving_engine.stats()
            # cumulative count under a *_per_s key: StatusReporter
            # renders the derived rate since the last heartbeat (QPS)
            out["predictions_per_s"] = s["requests"]
            out["serving"] = {
                "occ": s["occupancy"], "p50_ms": s["p50_ms"],
                "p99_ms": s["p99_ms"], "stale": s["rejections"]}
        if self.telemetry.enabled:
            # flattened registry heartbeat (counter totals + histogram
            # p50/n) rides the same [status] line as the runtime pulse
            out["metrics"] = self.telemetry.summary()
            # rolling critical path: per-heartbeat histogram deltas name
            # the segment dominating *this* window (telemetry/critpath)
            if self._critpath is None:
                from kafka_ps_tpu.telemetry.critpath import RollingCritpath
                self._critpath = RollingCritpath(self.telemetry)
            out["critpath"] = self._critpath.sample()
        if self.server.modelhealth.enabled:
            # model-health pulse (telemetry/modelhealth.py): update
            # norms, aggregate-direction cosine, drift verdict
            out["modelhealth"] = self.server.modelhealth.summary()
        return out

    def _start_status(self, status_every: float | None):
        from kafka_ps_tpu.utils.status import StatusReporter
        return StatusReporter(status_every or 0.0, self.status).start()

    # -- drive loops -------------------------------------------------------

    def flush_logs(self) -> None:
        """Force every deferred log line out (blocks on the device) —
        drive loops call this on exit so callers see complete logs.
        Pending async evals drain FIRST: their rows enter the server
        sink's queue before the sink itself is flushed."""
        with self.tracer.span("app.flush_logs"):
            if self.eval_engine is not None:
                self.eval_engine.drain()
            for sink in self._log_sinks():
                flush = getattr(sink, "flush", None)
                if flush is not None:
                    flush()

    def _log_sinks(self) -> list:
        """The server's sink and the workers' distinct ones."""
        return [self.server.log, *{id(w.log): w.log
                                   for w in self.workers}.values()]

    def _log_backlog(self) -> tuple[int, float]:
        """How often, and for how many seconds, a submitter has waited
        on a sink's backlog so far (utils/asynclog), over the sinks."""
        waits = [sink.backlog_waits() for sink in self._log_sinks()
                 if hasattr(sink, "backlog_waits")]
        return sum(n for n, _ in waits), sum(s for _, s in waits)

    def _record_run(self, path: str, t_call: float, backlog0, **more) -> None:
        """`last_run` of the drive call that began at `t_call` with the
        sinks' backlog counts at `backlog0`.  On every path it holds
        `path` ("serial" / "threaded" / "fused"), `seconds` (the whole
        call),
        `slab_refreshes` / `slab_refresh_s` / `slab_refresh_bytes`
        (NO_SLAB_REFRESH where nothing was uploaded), `theta_up_s` /
        `device_wait_s` / `theta_down_s` (a folded task's flat vector
        up where the call begins, the queued chunks finishing, the
        vector down where it ends; NO_CALL_EDGES elsewhere),
        `log_backlog_waits` / `log_backlog_wait_s`; and `counters`
        where the task counts (models/task.py `counter_names`)."""
        waits, wait_s = self._log_backlog()
        self.last_run = {"path": path,
                         "seconds": time.perf_counter() - t_call, **more,
                         "log_backlog_waits": waits - backlog0[0],
                         "log_backlog_wait_s": wait_s - backlog0[1]}
        # the start-up record keeps every call's start stamp and seconds,
        # and the process's first call whole (utils/device.py)
        device.record_call(self.last_run, self.telemetry)

    def close_logs(self) -> None:
        """Close the deferred sinks: joins their drain threads (which
        dispatch device fetches) and closes the wrapped file sinks.  The
        CLI calls this at exit so the process never finalizes with a
        live thread inside XLA (docs/TESTING.md)."""
        try:
            self.close_eval()     # re-raises a kept eval failure...
        finally:                  # ...after the sinks' threads are joined
            for sink in (self.server.log, *{id(w.log): w.log
                                            for w in self.workers}.values()):
                close = getattr(sink, "close", None)
                if close is not None:
                    close()

    def _make_gang(self):
        """The gang dispatcher for this run, or None when coalescing is
        off (`--no-gang`) — built lazily so non-gang runs never import
        runtime/gang.py."""
        if not self.cfg.use_gang:
            return None
        from kafka_ps_tpu.runtime.gang import GangDispatcher
        return GangDispatcher(self.workers, self.fabric, self.cfg,
                              tracer=self.tracer, telemetry=self.telemetry)

    def run_serial(self, max_server_iterations: int,
                   pump=None, status_every: float | None = None) -> None:
        """Deterministic scheduler: alternate weights delivery / gradient
        processing until the server has applied `max_server_iterations`
        gradient messages.  `pump()` (optional) feeds more stream rows
        between rounds.

        With gang dispatch on (the default) the schedule drains each
        release set whole: gang notices are claimed first (one batched
        worker dispatch per set), then stragglers run per-message, then
        the queued gradients are drained as one batch for the server's
        batched apply (runtime/server.process_batch).  `--no-gang` keeps
        the original strictly per-message alternation."""
        reporter = self._start_status(status_every)
        t_call, backlog0 = time.perf_counter(), self._log_backlog()
        stalled_rounds = 0
        gang = self._make_gang()
        try:
            self.server.start_training_loop()
            while self.server.iterations < max_server_iterations:
                with self.tracer.span("serial.round"):
                    progressed = self._serial_round(
                        gang, max_server_iterations)
                if pump is not None:
                    pump()
                # pump() can only add buffer rows, never fabric messages,
                # so a stretch of unprogressed rounds is a protocol
                # deadlock even with a pump attached.
                stalled_rounds = 0 if progressed else stalled_rounds + 1
                if stalled_rounds > (1000 if pump is not None else 0):
                    raise RuntimeError("deadlock: no deliverable messages")
        finally:
            reporter.stop()
            self.flush_logs()
        self._record_run("serial", t_call, backlog0, **NO_SLAB_REFRESH,
                         **NO_CALL_EDGES)

    def _serial_round(self, gang, max_server_iterations: int) -> bool:
        """One turn of the serial scheduler: weights out, gradients in.
        Whether any message moved."""
        progressed = False
        if gang is not None:
            with self.tracer.span("gang.drain"):
                progressed = gang.drain_serial()
        with self.tracer.span("serial.deliver"):
            for worker in self.workers:
                msg = self.fabric.poll(fabric_mod.WEIGHTS_TOPIC,
                                       worker.worker_id)
                if msg is not None:
                    worker.on_weights(msg)
                    progressed = True
        if gang is None:
            while self.server.iterations < max_server_iterations:
                g = self.fabric.poll(fabric_mod.GRADIENTS_TOPIC, 0)
                if g is None:
                    break
                self.server.process(g)
                progressed = True
            return progressed
        # drain the whole backlog, capped so a full batch cannot
        # overshoot the iteration budget (the benchmark's slices rely
        # on exact counts); drops (zombies/duplicates) under-fill a
        # round and the outer loop tops it up
        batch = []
        with self.tracer.span("serial.collect"):
            while (self.server.iterations + len(batch)
                   < max_server_iterations):
                g = self.fabric.poll(fabric_mod.GRADIENTS_TOPIC, 0)
                if g is None:
                    break
                batch.append(g)
        if len(batch) > 1:
            self.server.process_batch(batch)
        elif batch:
            self.server.process(batch[0])
        return progressed or bool(batch)

    def run_threaded(self, max_server_iterations: int,
                     poll_timeout: float = 0.1,
                     failure_policy: str = "halt",
                     heartbeat_timeout: float | None = None,
                     status_every: float | None = None) -> None:
        """One thread per worker (the reference's stream threads); server
        on the calling thread, doubling as the supervisor.

        Failure handling (the reference delegates this to Kafka
        consumer-group rebalancing + k8s restarts, SURVEY §5):
          * `failure_policy="halt"` — any worker exception stops the run
            and re-raises (the previous behavior, and the right default
            for tests);
          * `failure_policy="rebalance"` — a crashed worker (exception)
            or a hung worker (no completed iteration within
            `heartbeat_timeout` seconds despite pending weights
            messages) is evicted: the consistency gates stop waiting for
            it, its stream partition reroutes to the survivors
            (data_sink), and its in-flight gradients are dropped as
            zombies.  Training continues on the remaining workers.
        """
        if failure_policy not in ("halt", "rebalance"):
            raise ValueError(f"unknown failure_policy {failure_policy!r}")
        self._stop.clear()
        self.worker_failures = []    # this run's eviction record

        worker_errors: list[BaseException] = []
        failed_q: deque[tuple[int, BaseException]] = deque()
        gang = self._make_gang()
        if gang is not None:
            from kafka_ps_tpu.runtime.gang import GangMemberError
        else:
            GangMemberError = ()     # never raised without a gang

        def worker_loop(worker: WorkerNode):
            try:
                while not self._stop.is_set():
                    msg = self.fabric.poll_blocking(
                        fabric_mod.WEIGHTS_TOPIC, worker.worker_id,
                        timeout=poll_timeout)
                    if msg is not None:
                        if gang is not None:
                            # first arrival covered by a gang notice
                            # leads the set; otherwise runs solo
                            gang.offer(worker, msg)
                        else:
                            worker.on_weights(msg)
            except BaseException as e:   # surface worker death to the server
                # a gang member's failure surfaces on the LEADER's thread;
                # attribute it to the member, not the messenger
                wid = (e.worker_id if isinstance(e, GangMemberError)
                       else worker.worker_id)
                if failure_policy == "rebalance":
                    failed_q.append((wid, e))
                else:
                    worker_errors.append(e)
                    self._stop.set()

        threads = {w.worker_id: threading.Thread(
                       target=worker_loop, args=(w,), daemon=True,
                       name=f"worker-{w.worker_id}")
                   for w in self.workers}
        for t in threads.values():
            t.start()

        def evict(worker_id: int, reason) -> None:
            if not self.server.tracker.tracker[worker_id].active:
                return              # already evicted (e.g. heartbeat beat
                                    # the thread's own crash report)
            try:
                self.server.remove_worker(worker_id)
            except ValueError:      # last active worker: halt instead
                self._stop.set()
                worker_errors.append(
                    reason if isinstance(reason, BaseException)
                    else RuntimeError(f"worker {worker_id}: {reason}"))
                return
            self.worker_failures.append((worker_id, reason))

        def supervise() -> None:
            # crashed workers enqueue themselves before their thread
            # exits, so failed_q is the complete crash-detection channel
            while failed_q:
                w, err = failed_q.popleft()
                evict(w, err)
            if heartbeat_timeout is None:
                return
            now = time.monotonic()
            for w in list(self.server.tracker.active_workers):
                # Hung = owes a gradient (weights_message_sent) AND the
                # owed gradient is not already queued behind a slow
                # server AND no liveness signal within the timeout.
                # Staleness is measured from the LATEST of (worker's own
                # last progress, server's weights-send stamp) so time a
                # worker spent gate-blocked and idle doesn't count
                # against it.  A worker on its first iteration SINCE
                # (re)admission gets 10x grace: that call may pay jit
                # compilation (fresh start or a new code path after
                # rejoin).  heartbeat_timeout must still exceed the
                # worst-case steady-state single-iteration compute time.
                wk = self.workers[w]
                grace = (10.0 if wk.iterations == wk.iterations_at_join
                         else 1.0)
                baseline = max(self.workers[w].last_progress,
                               self.server.weights_sent_at[w])
                hung = (self.server.tracker.tracker[w].weights_message_sent
                        and not self.fabric.contains(
                            fabric_mod.GRADIENTS_TOPIC, 0,
                            lambda m, w=w: m.worker_id == w)
                        and now - baseline > heartbeat_timeout * grace)
                if hung:
                    evict(w, f"no heartbeat for {heartbeat_timeout}s")

        reporter = self._start_status(status_every)
        t_call, backlog0 = time.perf_counter(), self._log_backlog()
        try:
            self.server.start_training_loop()
            while self.server.iterations < max_server_iterations:
                if self._stop.is_set():
                    break
                g = self.fabric.poll_blocking(fabric_mod.GRADIENTS_TOPIC, 0,
                                              timeout=poll_timeout)
                if g is not None:
                    if gang is None:
                        self.server.process(g)
                    else:
                        # piggyback whatever else is already queued onto
                        # this wake-up: one batched apply instead of one
                        # apply per gradient (no waiting — only messages
                        # that have ALREADY arrived join the batch)
                        batch = [g]
                        while (self.server.iterations + len(batch)
                               < max_server_iterations):
                            g2 = self.fabric.poll(
                                fabric_mod.GRADIENTS_TOPIC, 0)
                            if g2 is None:
                                break
                            batch.append(g2)
                        if len(batch) > 1:
                            self.server.process_batch(batch)
                        else:
                            self.server.process(g)
                if failure_policy == "rebalance":
                    supervise()
        finally:
            reporter.stop()
            self._stop.set()
            # generous: an in-flight on_weights may be paying first-call
            # jit compilation on a loaded machine (the 5 s join of
            # rounds 2-4 could expire and leave the thread running)
            for t in threads.values():
                t.join(timeout=60.0)
            self.flush_logs()
        self._record_run("threaded", t_call, backlog0, **NO_SLAB_REFRESH,
                         **NO_CALL_EDGES)
        if worker_errors:
            raise RuntimeError("worker thread failed") from worker_errors[0]

    def run_fused_bsp(self, max_server_iterations: int, mesh=None,
                      log_metrics: bool = True,
                      status_every: float | None = None) -> None:
        """Sequential consistency as fused shard_map steps.  Each step is
        one full BSP iteration (all workers advance one clock).

        A 2-D mesh (workers x params axes, parallel/mesh.worker_param_mesh)
        selects the range-sharded server: parameters sharded over the
        params axis (the reference's latent KeyRange design,
        messages/KeyRange.java), all_gather pull / psum-slice push
        (parallel/range_sharded.py).  Single-process only.
        """
        import jax
        import jax.numpy as jnp

        from kafka_ps_tpu.parallel import range_sharded
        from kafka_ps_tpu.parallel.mesh import PARAM_AXIS

        if self.cfg.consistency_model != SEQUENTIAL:
            raise ValueError("fused path implements the sequential model only")
        range_mode = mesh is not None and PARAM_AXIS in mesh.shape
        self.solver = "fused-bsp"
        if range_mode and jax.process_count() > 1:
            raise ValueError(
                "range-sharded fused mode is single-process (the params "
                "axis would need a per-host theta-shard assembly)")
        # membership-aware: only active workers participate (a restored
        # checkpoint may carry evictions; their buffers are starved by
        # the data reroute and their tracker slots must stay frozen)
        active = self.server.tracker.active_workers
        task = self.server.task
        progs = self._fused_programs.setdefault(
            ("range" if range_mode else "bsp", len(active), mesh), {})
        if range_mode:
            if "step" not in progs:
                progs["step"] = range_sharded.make_range_sharded_step(
                    self.cfg.model, len(active), self.cfg.server_lr, mesh,
                    task=task)
            theta = range_sharded.shard_theta(
                mesh, jnp.asarray(self.server.theta), task)
        else:
            if "step" not in progs:
                progs["step"] = bsp.make_bsp_step(
                    self.cfg.model, len(active), self.cfg.server_lr,
                    mesh=mesh, task=task)
            # a folded task's loop cuts its leaves from the server's
            # vector itself, and holds no flat copy on the device
            theta = (jnp.asarray(self.server.theta)
                     if task.batches_workers else None)
        step = progs["step"]
        # under BSP all active clocks are uniform; resume from the
        # restored one
        clock = min(self.server.tracker.clocks[w] for w in active)
        # Multi-process job: this process hosts only the workers mapped
        # to its local mesh devices — it feeds their buffers and builds
        # the global arrays from its local slabs
        # (jax.make_array_from_process_local_data); the device program
        # is identical either way.
        multiproc = mesh is not None and jax.process_count() > 1
        if multiproc:
            from kafka_ps_tpu.parallel import multihost
            local_pos = multihost.local_worker_ids(len(active), mesh)
            feed = [active[i] for i in local_pos]
            # the data filter (set by the CLI before the producer
            # started) must match this derivation — a stale filter from
            # pre-restore membership starves buffers this process owns
            if (self.local_workers is not None
                    and set(feed) != set(self.local_workers)):
                raise RuntimeError(
                    f"local_workers {sorted(self.local_workers)} diverges "
                    f"from the mesh-derived feed set {sorted(feed)} — "
                    "membership changed after the data filter was set")
        else:
            feed = active
        # device-resident slab cache: between stream arrivals the loop
        # re-trains on identical buffers (the reference's steady state,
        # WorkerTrainingProcessor.java:63-97) — re-uploading ~16 MB of
        # unchanged slabs per iteration would make host->device transfer
        # the bottleneck.  num_tuples_seen strictly increases on every
        # insert, so it is the buffer content version.
        reporter = self._start_status(status_every)
        t_call, backlog0 = time.perf_counter(), self._log_backlog()
        try:
            refresh = self._run_fused_loop(
                max_server_iterations, mesh, log_metrics, range_mode,
                multiproc, step, theta, clock, active, feed, task, progs)
        finally:
            reporter.stop()
        # a folded task's counters, summed over the call's updates,
        # ride along under "counters" (models/task.py `counter_names`)
        self._record_run("fused", t_call, backlog0, **refresh)

    # rounds per fused chunk dispatch: several rounds share one
    # dispatch, few enough that stream arrivals are picked up promptly
    FUSED_CHUNK_ROUNDS = 8

    def _run_fused_loop(self, max_server_iterations, mesh, log_metrics,
                        range_mode, multiproc, step, theta, clock, active,
                        feed, task, progs) -> dict:
        """The fused drive loop; returns the call's slab-refresh counts
        and the seconds at its two ends (`StreamingPSApp.last_run`)."""
        import jax
        import jax.numpy as jnp

        from kafka_ps_tpu.parallel import range_sharded

        # Chunking: stretches with no eval boundary run CHUNK rounds as
        # ONE lax.scan dispatch (bsp.make_bsp_multi_step /
        # range_sharded.make_range_sharded_step(rounds=CHUNK)) instead
        # of one dispatch per round.  Eval cadences land exactly: a
        # chunk never crosses an eval clock, and eval_every=1
        # degenerates to the per-round path.
        CHUNK = self.FUSED_CHUNK_ROUNDS

        def get_multi_step():
            if "multi_step" not in progs:
                if range_mode:
                    progs["multi_step"] = \
                        range_sharded.make_range_sharded_step(
                            self.cfg.model, len(active),
                            self.cfg.server_lr, mesh, rounds=CHUNK,
                            task=task)
                else:
                    progs["multi_step"] = bsp.make_bsp_multi_step(
                        self.cfg.model, len(active), self.cfg.server_lr,
                        CHUNK, mesh=mesh, task=task)
            return progs["multi_step"]

        # A folded task (parallel/bsp.py) is carried through the call as
        # its leaves, donated from dispatch to dispatch: `theta` below is
        # then the leaves, cut from the flat vector here and joined
        # again where the loop ends, and the evaluation reads the leaves
        folded = not range_mode and not task.batches_workers
        evaluate = None
        refresh = {**NO_SLAB_REFRESH, **NO_CALL_EDGES}
        if folded:
            if "edges" not in progs:
                progs["edges"] = bsp.folded_edges(task)
            cut, join, evaluate = progs["edges"]
            # between calls the flat vector lives on the HOST: on the
            # device it would stand beside the leaves, the running sum,
            # a worker's working copy and its gradient as a fifth copy
            # of the parameters, which the chip cannot hold at the
            # published widths.  An upload where the call begins and a
            # download where it ends, once a call: for the three
            # language-model cells' 2.02 / 2.37 / 2.67 GB, 0.32 / 0.38 /
            # 0.44 s up (6.2 GB/s) and 0.58 / 0.72 / 0.81 s down (3.4
            # GB/s), 6.5-7.1% of a 13-19 s call (`last_run`'s
            # `theta_up_s` / `theta_down_s`; PERF.md section 5, PR 37).
            # The upload is waited for, so that the span and the counter
            # hold the copy and not its enqueueing; no chunk could start
            # before it.
            t_up = time.perf_counter()
            with self.tracer.span("fused.theta_up",
                                  bytes=4 * task.num_params):
                self.server.theta = np.asarray(self.server.theta)
                theta = jax.block_until_ready(cut(self.server.theta))
            refresh["theta_up_s"] = time.perf_counter() - t_up
        x = y = mask = None
        slab_versions: list[int] | None = None
        counted = []      # a folded task's counters, one array a dispatch
        while self.server.iterations < max_server_iterations:
            # one span a turn: the loop's own Python between its
            # children is this span's self time
            with self.tracer.span("fused.chunk"):
                versions = [self.buffers[w].num_tuples_seen for w in feed]
                # The version cache stays valid multi-process: the global
                # array build below (make_array_from_process_local_data)
                # is process-local — device_put of this host's shards
                # only, no cross-process rendezvous — so hosts may
                # disagree about re-uploading without hanging, and a host
                # whose buffers are unchanged reuses device slabs with
                # identical content.
                if versions != slab_versions:
                    t_refresh = time.perf_counter()
                    # x, y and the float32 validity mask of each slab
                    nbytes = sum(b.x.nbytes + b.y.nbytes + 4 * len(b.y)
                                 for b in (self.buffers[w] for w in feed))
                    with self.tracer.span("fused.slab_refresh",
                                          bytes=nbytes, workers=len(feed)):
                        # `slabs` stays referenced until the loop
                        # returns: dropped here, the host copies are
                        # freed at the top of the heap, glibc hands
                        # their pages back, and every call faults them
                        # in again (+1 s a call at 256 workers, PERF.md
                        # PR 24)
                        slabs = []
                        for w in feed:
                            sx, sy, sm = self.buffers[w].snapshot()
                            if sm.sum() == 0:
                                raise RuntimeError(
                                    "There is no data in the buffer of "
                                    f"worker {w}")
                            slabs.append((sx, sy, sm))
                        x = np.stack([s[0] for s in slabs])
                        y = np.stack([s[1] for s in slabs])
                        mask = np.stack([s[2] for s in slabs])
                        if multiproc:
                            from kafka_ps_tpu.parallel import multihost
                            x, y, mask = \
                                multihost.shard_worker_batches_global(
                                    mesh, x, y, mask)
                        elif range_mode:
                            x, y, mask = range_sharded.shard_worker_batches(
                                mesh, x, y, mask)
                        elif mesh is not None:
                            x, y, mask = bsp.shard_worker_batches(
                                mesh, x, y, mask)
                        else:
                            x, y, mask = (jnp.asarray(x), jnp.asarray(y),
                                          jnp.asarray(mask))
                    slab_versions = versions
                    refresh["slab_refreshes"] += 1
                    refresh["slab_refresh_bytes"] += nbytes
                    refresh["slab_refresh_s"] += (time.perf_counter()
                                                  - t_refresh)
                # rounds until the run cap / the next eval clock
                rounds_left = -(
                    (self.server.iterations - max_server_iterations)
                    // len(active))
                r = min(CHUNK, rounds_left)
                if log_metrics and self.server.test_x is not None:
                    r = min(r, self.cfg.eval_every
                            - (clock % self.cfg.eval_every))
                use_chunk = r == CHUNK
                if not use_chunk:
                    r = 1
                losses = None
                # the dispatch call: enqueue, plus any wait for a full
                # queue — no span reads a device value
                with self.tracer.span("bsp.step", clock=clock + 1,
                                      rounds=r):
                    # a folded task's programs return its counters
                    # third (parallel/bsp.py); they stay on the device
                    # until the loop returns
                    if use_chunk:
                        theta, losses, *more = get_multi_step()(
                            theta, x, y, mask)
                        mean_loss = losses[-1]
                    else:
                        theta, mean_loss, *more = step(theta, x, y, mask)
                    counted += more
                if multiproc and log_metrics:
                    # Multi-process runs with logging sync here: the
                    # psum makes every process's step k finish together
                    # on device, and blocking the hosts on it keeps
                    # their row timestamps aligned per clock — fully
                    # async hosts submit all their rows (and stamp them)
                    # way ahead of the device, and the auditor's
                    # cross-file timestamp-sorted spread becomes
                    # fiction.  Single-process runs keep pipelining.
                    mean_loss = float(mean_loss)
                self.tracer.count("bsp.steps")
                clock += r
                self.server.iterations += r * len(active)
                device.mark("first_update")
                with self.tracer.span("fused.publish"):
                    # theta is updated by replacement everywhere
                    # (runtime/server module doc), so the device array
                    # is stored directly — no per-step device->host copy
                    if range_mode:
                        self.server.theta = range_sharded.unshard_theta(
                            theta, task)
                    elif not folded:
                        self.server.theta = theta
                    elif (self.server.serving is not None
                          or self.server.checkpoint_due()):
                        # a reader is owed the parameters of THIS clock:
                        # a snapshot or a checkpoint holds theta, the
                        # clocks and the iterations of one moment, so
                        # the leaves are joined and brought to the host
                        # now (a 1 s stall at the published widths, paid
                        # only where someone reads)
                        self.server.theta = np.asarray(join(theta))
                    for w in active:
                        self.workers[w].iterations += r
                        self.server.tracker.tracker[w].vector_clock = clock
                        self.server.tracker.tracker[w] \
                            .weights_message_sent = True
                    # fused-path publication point: the chunk boundary
                    # is the gate release (all active workers advanced
                    # to `clock`)
                    self.server.publish_snapshot()
                    self.server.maybe_checkpoint()
                if log_metrics and self.server.test_x is not None:
                    self._log_fused_rounds(
                        theta, clock, r, losses, mean_loss, feed,
                        range_mode, multiproc, evaluate)
        if folded:
            # the flat vector, once a call unless a snapshot or a
            # checkpoint was owed at a chunk's boundary (above): until
            # here `server.theta` is the last of those, or the state
            # the call began with.  The wait for the queued chunks is
            # named apart from the copy: the download below waited for
            # the same, so this adds none
            t_wait = time.perf_counter()
            with self.tracer.span("fused.wait_device"):
                jax.block_until_ready((theta, counted))
            t_down = time.perf_counter()
            with self.tracer.span("fused.theta_down",
                                  bytes=4 * task.num_params):
                self.server.theta = np.asarray(join(theta))
                del theta
            refresh["device_wait_s"] = t_down - t_wait
            refresh["theta_down_s"] = time.perf_counter() - t_down
            self.server.publish_snapshot()
        self.flush_logs()    # deferred rows out before the loop returns
        if counted:
            totals = np.sum([np.asarray(c, np.int64) for c in counted],
                            axis=0)
            refresh["counters"] = {
                name: int(n) for name, n in zip(task.counter_names, totals)}
            for name, n in refresh["counters"].items():
                self.tracer.count(name, n)
        return refresh

    def _log_fused_rounds(self, theta, clock, r, losses, mean_loss, feed,
                          range_mode, multiproc, evaluate=None) -> None:
        """The server's eval row (on cadence) and every fed worker's
        row for each of the `r` rounds that ended on `clock`.
        `evaluate`: a folded task's evaluation from its leaves, which
        `theta` then is."""
        import jax
        import jax.numpy as jnp

        is_eval = clock % self.cfg.eval_every == 0
        m = None
        if is_eval:
            with self.tracer.span("fused.eval", clock=clock):
                # range mode: theta is the padded sharded vector;
                # eval on the reassembled flat layout (just stored)
                eval_theta = (jnp.asarray(self.server.theta)
                              if range_mode else theta)
                m = (evaluate or self.server.task.evaluate)(
                    eval_theta, self.server.test_x, self.server.test_y)
                self.server.last_metrics = m
        now = int(time.time() * 1000)
        with self.tracer.span("fused.log_rows", rows=r * len(feed)):
            # multi-process: the server line is process 0's alone
            # (identical replicated metrics; one writer per file).
            # Metric fields stay device futures (asynclog) so the
            # next chunk dispatches while the eval completes.
            if is_eval and (not multiproc or jax.process_index() == 0):
                asynclog.submit_or_write(
                    self.server.log, f"{now};-1;{clock};{{}};{{}};{{}}",
                    m.loss, m.f1, m.accuracy)
            # Worker log lines, same schema AND CADENCE as the
            # per-node path (WorkerTrainingProcessor.java:85-92):
            # one row per worker per CLOCK — off-cadence clocks log
            # the reference's -1 placeholders, eval clocks the
            # shared test metrics (identical across workers under
            # BSP — replicated weights).  Rows go out CLOCK-major
            # so a same-millisecond batch keeps the logged spread
            # within the BSP bound (the staleness auditor orders
            # ties by file order).  A chunk logs each of its r
            # rounds with that round's mean local loss.  Each
            # process logs only the workers it hosts (its sink path
            # is process-suffixed in multi-host mode, cli/run.py).
            # Log-schema caveat: numTuplesSeen is CHUNK-granular
            # here, not round-granular — all r rows of a chunk stamp
            # the buffer version sampled after the chunk dispatch,
            # because the per-round values no longer exist (the
            # rounds ran fused on device against one slab snapshot).
            # The per-node path stamps it per iteration; consumers
            # correlating loss against data volume should treat the
            # fused path's column as a step function with CHUNK-wide
            # treads.
            for i in range(r):
                ci = clock - r + 1 + i
                round_loss = (losses[i] if losses is not None
                              else mean_loss)
                ci_eval = is_eval and ci == clock
                f1 = m.f1 if ci_eval else -1.0
                acc = m.accuracy if ci_eval else -1.0
                for w in feed:
                    asynclog.submit_or_write(
                        self.workers[w].log,
                        f"{now};{w};{ci};{{}};{{}};{{}};"
                        f"{self.buffers[w].num_tuples_seen}",
                        round_loss, f1, acc)

    def stop(self) -> None:
        self._stop.set()

"""Split server/worker deployment over the socket transport — the
reference's ACTUAL process topology (one server JVM + worker JVMs
coupled through the broker, run.sh:10-18, kubernetes/*.yaml) for the
async consistency models.

    # host A — aggregator + consistency gate + stream producer
    python -m kafka_ps_tpu.cli.server_runner --listen 8477 \
        -c 10 -training train.csv -test test.csv --max_iterations 400 -l

    # host B (and C, ...) — the workers named by --worker_ids
    python -m kafka_ps_tpu.cli.worker_runner --connect hostA:8477 \
        --worker_ids 0,1,2,3 -test test.csv -l

WEIGHTS / GRADIENTS / INPUT_DATA cross the wire as binary serde frames
(runtime/net.py, runtime/serde.py) — ~24 KB per 6150-float model
message vs the reference's ~120 KB JSON.  The fused/BSP path scales via
jax.distributed instead (deploy/README.md); this mode exists so bounded
delay and eventual consistency have a real multi-host story too.
"""

from __future__ import annotations

import os
import queue
import sys
import threading
import time


from kafka_ps_tpu.analysis.lockgraph import OrderedLock
from kafka_ps_tpu.runtime import fabric as fabric_mod
from kafka_ps_tpu.runtime import net

def _make_cfg(args):
    from kafka_ps_tpu.cli.run import announce_device, apply_platform_env
    from kafka_ps_tpu.utils.config import (BufferConfig, ModelConfig,
                                           PSConfig, StreamConfig,
                                           TierConfig)
    apply_platform_env()
    if getattr(args, "eval_every", 1) < 1:
        raise SystemExit("--eval_every must be >= 1")
    if getattr(args, "tier_warm_bytes", 0) \
            and not getattr(args, "durable_log", None):
        raise SystemExit(
            "--tier-warm-bytes demotes pages to commit-log records; "
            "run with --durable-log DIR so the cold partition has a "
            "home (docs/TIERING.md)")
    cfg = PSConfig(
        num_workers=args.num_workers,
        consistency_model=getattr(args, "consistency_model", 0),
        task=args.task,
        model=ModelConfig(num_features=args.num_features,
                          num_classes=args.num_classes,
                          num_max_iter=args.local_iterations,
                          local_learning_rate=args.local_learning_rate,
                          hidden_dim=args.hidden_dim),
        buffer=BufferConfig(
            min_size=getattr(args, "min_buffer_size", 128),
            max_size=getattr(args, "max_buffer_size", 1024),
            coefficient=getattr(args, "buffer_size_coefficient", 0.3)),
        stream=StreamConfig(time_per_event_ms=getattr(
            args, "producer_time_per_event", 200)),
        eval_every=getattr(args, "eval_every", 1),
        eval_async=getattr(args, "eval_async", True),
        # the wire protocol has no gang-notice frame (runtime/serde.py),
        # and a notice crossing a socket could not promise anything
        # about remote queue contents anyway — split mode stays
        # per-message
        use_gang=False,
        compress=getattr(args, "compress", "none") or "none",
        tier=TierConfig(
            hot_bytes=getattr(args, "tier_hot_bytes", 0),
            warm_bytes=getattr(args, "tier_warm_bytes", 0),
            page_params=getattr(args, "tier_page_params", 1024)),
    )
    announce_device(cfg)
    return cfg


def _codec_spec(args):
    """Validate and parse --compress (host-side only, no jax import)."""
    from kafka_ps_tpu.compress import wire as cwire
    try:
        return cwire.parse_codec(getattr(args, "compress", "none") or "none")
    except ValueError as e:
        raise SystemExit(f"--compress: {e}") from None


def _attach_tier_store(server, cfg, key_range, cold_dir, telemetry):
    """Attach tiered hot/warm/cold residency per cfg.tier
    (kafka_ps_tpu/store/, docs/TIERING.md); no-op (None) when both caps
    are 0.  Called BEFORE the checkpoint restore so the restore can
    re-apply recorded residency.  Caller owns close() at teardown —
    after the final checkpoint save, which may still fault cold
    pages."""
    if not cfg.tier.enabled:
        return None
    import numpy as np

    from kafka_ps_tpu.store import ColdStore, TieredParamStore
    t = cfg.tier
    cold = ColdStore.open(cold_dir) if cold_dir is not None else None
    store = TieredParamStore(
        np.asarray(server.theta), key_range,
        hot_bytes=t.hot_bytes, warm_bytes=t.warm_bytes,
        page_params=t.page_params, cold=cold, telemetry=telemetry,
        rebalance_interval_s=t.rebalance_interval_s)
    server.attach_param_store(store)
    store.start_policy_thread()
    caps = {k: v for k, v in (("hot", t.hot_bytes),
                              ("warm", t.warm_bytes)) if v}
    print(f"tiered residency: caps {caps}, "
          f"{store.num_pages} pages of {t.page_params} keys",
          file=sys.stderr, flush=True)
    return store


def _make_telemetry(args):
    """Per-process observability handles (docs/OBSERVABILITY.md): each
    split-mode process owns its own Tracer (pid-stamped events — the
    merge CLI stitches the per-process dumps) and metrics registry."""
    from kafka_ps_tpu.telemetry import maybe_telemetry
    tracer = None
    if getattr(args, "trace", None):
        from kafka_ps_tpu.utils.trace import Tracer
        tracer = Tracer()
    # /varz serves this same registry, so a requested health plane
    # arms metrics even without a --metrics-file dump target
    telemetry = maybe_telemetry(
        tracer,
        want_metrics=bool(getattr(args, "metrics_file", None))
        or getattr(args, "health_port", None) is not None
        # the SLO plane judges registry families, so arming it arms them
        or getattr(args, "slo_serving_p99_ms", None) is not None
        or getattr(args, "slo_freshness_ms", None) is not None
        # model-health diagnostics are metric families first
        or getattr(args, "model_health", False))
    if getattr(args, "metrics_file", None) \
            and getattr(args, "metrics_every", 0.0) > 0:
        telemetry.start_dumper(args.metrics_file, args.metrics_every)
    return tracer, telemetry


def _make_ops(args, telemetry, *, role, shard=None, meta=None,
              modelhealth=None):
    """Flight recorder + watchdogs + health plane for one split-mode
    process (telemetry/health.py, docs/OBSERVABILITY.md).  Inert unless
    --flight-dir/--health-port, so every role wires it unconditionally;
    with --flight-dir the process also dumps its rings on SIGTERM/
    SIGABRT/fatal signals — the raw material of `python -m
    kafka_ps_tpu.telemetry postmortem`."""
    from kafka_ps_tpu.telemetry.health import OpsPlane
    from kafka_ps_tpu.telemetry.slo import plane_from_args
    return OpsPlane(flight_dir=getattr(args, "flight_dir", None),
                    health_port=getattr(args, "health_port", None),
                    telemetry=telemetry, role=role, shard=shard,
                    meta=meta,
                    profile=getattr(args, "profile", False),
                    slo_plane=plane_from_args(args, telemetry),
                    modelhealth=modelhealth)


def _make_modelhealth(args, telemetry, *, shard=None, num_features=None,
                      model="sequential", log_name=None):
    """Model-health plane for one split-mode process (--model-health,
    telemetry/modelhealth.py) plus its wall-clock-stamping drift-CSV
    sink — the monitor emits clock-free rows so telemetry/drift.py
    stays replay-pure (PS104); the stamp happens here, in CLI land.
    Returns (plane_or_None, sink_or_None); OpsPlane owns the plane's
    lifecycle, the caller closes the sink after ops.close()."""
    if not getattr(args, "model_health", False):
        return None, None
    from kafka_ps_tpu.telemetry.modelhealth import plane_from_args
    sink = None
    log = None
    if getattr(args, "logging", False) and log_name:
        from kafka_ps_tpu.utils.csvlog import CsvLogSink, DRIFT_HEADER
        sink = CsvLogSink(log_name, DRIFT_HEADER)
        log = (lambda rest:
               sink(f"{int(time.time() * 1000)};{rest}"))
    plane = plane_from_args(args, telemetry, shard=shard,
                            num_features=num_features, model=model,
                            log=log)
    return plane, sink


def _dump_telemetry(args, tracer, telemetry) -> None:
    """Exit-path flush for _make_telemetry (mirrors cli/run.py)."""
    if getattr(args, "metrics_file", None):
        telemetry.stop_dumper()
        telemetry.write_prometheus(args.metrics_file)
    if getattr(args, "trace", None) and tracer is not None:
        print(tracer.dump(args.trace), file=sys.stderr, flush=True)


class _BatchingSink:
    """Producer sink that coalesces stream rows into T_DATA_BATCH frames.

    Per-worker row buffers flush on size (one frame per `batch` rows) or
    age (`flush_aged`, called from the server main loop's poll tick, so
    a trickling stream never strands rows).  Delivery goes through
    ServerBridge.send_data_batch — one frame, one syscall, one receiver
    lock for the whole batch — and falls back to the per-row sink (which
    owns the reroute/eviction policy) whenever the batch path can't
    deliver.  Thread-safe: the producer thread adds while the main loop
    flushes; a size-flush racing an age-flush can reorder rows between
    frames, which the reroute path already permits (sliding-buffer
    ingest is order-insensitive beyond insertion ids).
    """

    def __init__(self, bridge, fallback, deliverable,
                 batch: int = 32, max_age: float = 0.05):
        self._bridge = bridge
        self._fallback = fallback      # per-row sink with reroute logic
        self._deliverable = deliverable
        self._batch = batch
        self._max_age = max_age
        self._rows: dict[int, list] = {}
        self._oldest: dict[int, float] = {}   # worker -> first-row time
        self._lock = OrderedLock("BatchingIngest.rows")

    def __call__(self, worker: int, features, label: int) -> None:
        with self._lock:
            rows = self._rows.setdefault(worker, [])
            if not rows:
                self._oldest[worker] = time.monotonic()
            rows.append((features, label))
            if len(rows) < self._batch:
                return
            del self._rows[worker]
            self._oldest.pop(worker, None)
        self._deliver(worker, rows)

    def flush_aged(self) -> None:
        """Flush every batch whose FIRST row has waited >= max_age."""
        now = time.monotonic()
        due = []
        with self._lock:
            for w, t0 in list(self._oldest.items()):
                if now - t0 >= self._max_age:
                    due.append((w, self._rows.pop(w)))
                    del self._oldest[w]
        for w, rows in due:
            self._deliver(w, rows)

    def flush_all(self) -> None:
        with self._lock:
            pending = [(w, self._rows.pop(w)) for w in list(self._rows)]
            self._oldest.clear()
        for w, rows in pending:
            self._deliver(w, rows)

    def _deliver(self, worker: int, rows) -> None:
        if self._deliverable(worker) and self._bridge.send_data_batch(
                worker, rows):
            return
        for features, label in rows:
            self._fallback(worker, features, label)


def run_server(args) -> int:
    """Server role: ServerNode + producer, all workers remote.

    Failure handling mirrors the in-process supervisor
    (runtime/app.py:run_threaded) across the wire — the reference gets
    the same from Kafka consumer-group rebalancing + k8s pod restarts
    (kubernetes/worker.yaml, SURVEY §5):
      * failure_policy=halt (default): a worker-connection loss stops
        the run with an error instead of deadlocking the gate;
      * failure_policy=rebalance: the dead connection's workers are
        evicted (gates stop waiting, their stream rows reroute to the
        survivors) and a reconnecting worker process is readmitted at
        the slowest active clock once its buffer holds data (READY).
    """
    from kafka_ps_tpu.cli.run import load_test_csv
    from kafka_ps_tpu.data.stream import CsvStreamProducer
    from kafka_ps_tpu.runtime.server import ServerNode
    from kafka_ps_tpu.utils.csvlog import (CsvLogSink, EVENTS_HEADER,
                                           NullLogSink, SERVER_HEADER)

    cfg = _make_cfg(args)
    codec_spec = _codec_spec(args)
    failure_policy = getattr(args, "failure_policy", "halt")
    hb_timeout = getattr(args, "heartbeat_timeout", None)
    test_x, test_y = load_test_csv(args.test_data_file_path,
                                   args.num_features)
    # a resumed run must CONTINUE the prior run's logs, not truncate
    # them (mirrors cli/run.py's make_app_from_args; post-run validation
    # audits the logs across the resume)
    checkpoint_path = getattr(args, "checkpoint", None)
    resuming = bool(checkpoint_path) and os.path.exists(checkpoint_path)
    log = CsvLogSink("./logs-server.csv" if args.logging else None,
                     SERVER_HEADER, append=resuming)
    # events persist incrementally — an end-of-run dump would lose the
    # auditor's eviction/readmission record on a crash
    events_log = (CsvLogSink("./logs-events.csv", EVENTS_HEADER,
                             append=resuming)
                  if args.logging else NullLogSink())
    # the logical-run id the bridge advertises (T_CONFIG): a resume
    # continues the checkpointed run, a fresh start mints a new one —
    # worker processes match their local state files against it
    run_id = None
    if resuming:
        from kafka_ps_tpu.utils import checkpoint as ckpt
        run_id = ckpt.peek_run_id(checkpoint_path)
    if run_id is None:
        run_id = time.time_ns()
    tracer, telemetry = _make_telemetry(args)
    bridge = net.ServerBridge(
        port=args.listen,
        heartbeat_interval=min(1.0, hb_timeout / 3) if hb_timeout else 1.0,
        heartbeat_timeout=hb_timeout,
        run_id=run_id,
        codec=codec_spec,
        tracer=tracer, telemetry=telemetry,
        shm=getattr(args, "serve_shm", False),
        coalesce=getattr(args, "wire_coalesce", True))
    print(f"listening on port {bridge.port}", file=sys.stderr, flush=True)
    from kafka_ps_tpu.utils.asynclog import DeferredSink
    fabric = bridge.wrap(fabric_mod.Fabric())
    server = ServerNode(cfg, fabric, test_x, test_y, DeferredSink(log),
                        tracer=tracer, telemetry=telemetry)
    # aggregation-tier hooks (kafka_ps_tpu/agg/, docs/AGGREGATION.md):
    # releases to workers behind an aggregator relay group into one
    # T_WEIGHTS_AGG frame per relay (no-op while no relay is connected)
    server.weights_group_send = bridge.send_weights_group
    if getattr(args, "bsp_order", False):
        # deterministic BSP apply order (worker-id per round) so an
        # aggregated run is bitwise-comparable to a direct socket run
        server.bsp_order = True
        print("bsp-order: buffering rounds for worker-id-ordered "
              "applies", file=sys.stderr, flush=True)
    if codec_spec.codec_id != net.CODEC_NONE:
        # weights leave this process quantize-dequantized so both sides
        # train against the SAME decoded theta; per-connection fallback
        # (a peer that negotiated NONE gets plain frames) lives in
        # ServerBridge._send
        from kafka_ps_tpu import compress
        codec = compress.get_codec(codec_spec, server.task.num_params)
        server.compressor = compress.WeightsCompressor(codec)
        print(f"compression: {codec_spec.name}", file=sys.stderr,
              flush=True)
    server.run_id = run_id
    server.membership_log = events_log   # before restore: it logs "resume"
    # async coalescing eval plane (evaluation/engine.py): default-on,
    # `--no-eval-async` restores the fused-eval apply programs
    eval_engine = None
    if cfg.eval_async and test_x is not None:
        from kafka_ps_tpu.evaluation.engine import EvalEngine
        eval_engine = server.attach_eval_engine(EvalEngine(
            server.task, server.test_x, server.test_y, server._emit_eval,
            telemetry=telemetry, tracer=tracer))

    from kafka_ps_tpu.log.durable_fabric import COLD_PARTITION_DIR
    from kafka_ps_tpu.runtime.messages import KeyRange
    tier_store = _attach_tier_store(
        server, cfg, KeyRange(0, server.task.num_params),
        cold_dir=(os.path.join(args.durable_log, COLD_PARTITION_DIR)
                  if getattr(args, "durable_log", None) else None),
        telemetry=telemetry)

    if checkpoint_path:
        from kafka_ps_tpu.utils import checkpoint as ckpt
        ckpt.maybe_restore(checkpoint_path, server)
        server.checkpoint_path = checkpoint_path
        server.checkpoint_every = getattr(args, "checkpoint_every", 50)
        if resuming:
            print(f"restored checkpoint at iteration {server.iterations}",
                  file=sys.stderr, flush=True)

    # online serving plane on the SAME port as the workers: predict-only
    # clients never HELLO, so the bridge routes them nothing but their
    # own T_PREDICTION replies (docs/SERVING.md)
    engine = None
    if getattr(args, "serve", False):
        from kafka_ps_tpu.serving.engine import PredictionEngine
        from kafka_ps_tpu.serving.snapshot import SnapshotRegistry
        registry = SnapshotRegistry(
            capacity=getattr(args, "serve_snapshots", 8))
        server.serving = registry
        shed_ms = getattr(args, "serve_shed_ms", 0.0)
        engine = PredictionEngine(
            server.task, registry,
            max_batch=getattr(args, "serve_batch", 16),
            deadline_s=getattr(args, "serve_deadline_ms", 2.0) / 1000.0,
            queue_limit=getattr(args, "serve_queue", 0),
            shed_deadline_s=shed_ms / 1000.0 if shed_ms else None,
            auto=getattr(args, "serve_auto", True),
            tracer=tracer, telemetry=telemetry)
        bridge.attach_serving(engine)
        server.publish_snapshot()    # cold start: restored/fresh theta
        # compile every bucket shape + calibrate the dispatch cost
        # model now, not in some client's p99 (docs/SERVING.md)
        engine.warmup()
        print(f"serving predictions on port {bridge.port}",
              file=sys.stderr, flush=True)

    # model-health plane (--model-health): the apply path feeds it;
    # the producer's row sink feeds its feature sketch below (in split
    # mode the buffers live in the worker processes, but every stream
    # row passes through HERE first)
    from kafka_ps_tpu.telemetry.registry import model_name
    modelhealth, drift_sink = _make_modelhealth(
        args, telemetry, num_features=cfg.model.num_features,
        model=model_name(cfg.consistency_model),
        log_name="./logs-drift.csv")
    if modelhealth is not None:
        server.attach_model_health(modelhealth)

    ops = _make_ops(args, telemetry, role="server",
                    modelhealth=modelhealth)
    ops.add_gate_watchdog(server)
    if eval_engine is not None:
        ops.add_eval_engine(eval_engine)   # /evalz detail row
    if engine is not None:
        ops.add_serving_watchdog(engine)
    ops.start()

    # membership events cross threads (bridge readers -> main loop):
    # ServerNode is single-threaded by design, so evictions/readmissions
    # are applied only between gradient polls
    events: "queue.Queue[tuple[str, object]]" = queue.Queue()
    bridge.on_disconnect = lambda ids: events.put(("disconnect", ids))
    bridge.on_ready = lambda w: events.put(("ready", w))

    workers = server.tracker.active_workers   # a checkpoint may carry evictions
    bridge.wait_for_connected(workers, timeout=args.connect_timeout)

    reroute = {"rr": 0, "dropped": 0}

    def sink(worker: int, features: dict[int, float], label: int) -> None:
        # Rows flow to whoever holds the worker's connection — including
        # (under rebalance) a reconnected-but-not-yet-readmitted process,
        # whose buffer must fill before READY triggers readmission.
        # Under halt an inactive worker can never be readmitted, so a
        # reconnected-evicted target (checkpoint carrying evictions)
        # would swallow its partition's rows forever — reroute instead.
        # A dead target reroutes round-robin to the survivors (the
        # partition reassignment of a consumer-group rebalance); with
        # nobody left the row is counted, not silently discarded.
        deliverable = (failure_policy == "rebalance"
                       or server.tracker.tracker[worker].active)
        if deliverable and bridge.send_data(worker, features, label):
            return
        active = server.tracker.active_workers
        for _ in range(len(active)):
            alt = active[reroute["rr"] % len(active)]
            reroute["rr"] += 1
            if alt != worker and bridge.send_data(alt, features, label):
                return
        reroute["dropped"] += 1

    batch_sink = _BatchingSink(
        bridge, sink,
        deliverable=lambda w: (failure_policy == "rebalance"
                               or server.tracker.tracker[w].active))
    row_sink = batch_sink
    if modelhealth is not None:
        def row_sink(worker: int, features, label: int) -> None:
            # sampled feature sketch (population-stability signal,
            # telemetry/drift.py) on the producer thread, before the
            # row fans out to whichever worker holds the connection
            modelhealth.drift.observe_row(features)
            batch_sink(worker, features, label)
    producer = CsvStreamProducer(
        args.training_data_file_path, cfg.num_workers, row_sink,
        time_per_event_ms=cfg.stream.time_per_event_ms,
        prefill_per_worker=cfg.stream.prefill_per_worker)
    producer.run_in_background()
    bridge.wait_for_workers(workers, timeout=args.connect_timeout)

    # one entry per worker that has announced READY this server
    # lifetime: a SECOND ready from a still-ACTIVE worker is a
    # restarted process (a member behind an aggregation relay — its
    # death never surfaces here as a disconnect) whose in-flight
    # weights assignment died with it
    seen_ready: set = set()

    def apply_events() -> None:
        while True:
            try:
                kind, val = events.get_nowait()
            except queue.Empty:
                return
            if kind == "disconnect":
                live = [w for w in val
                        if server.tracker.tracker[w].active]
                if not live:
                    continue
                if failure_policy == "halt":
                    raise RuntimeError(
                        f"worker connection lost for {sorted(live)} "
                        "(failure_policy=halt; use "
                        "--failure_policy rebalance to continue on "
                        "the survivors)")
                for w in live:
                    try:
                        server.remove_worker(w)
                    except ValueError:
                        raise RuntimeError(
                            "all worker connections lost") from None
                    print(f"evicted worker {w} (connection lost)",
                          file=sys.stderr, flush=True)
            elif kind == "ready":
                w = int(val)
                status = server.tracker.tracker[w]
                if (failure_policy == "rebalance"
                        and not status.active):
                    clock = server.readmit_worker(w)
                    seen_ready.add(w)
                    print(f"readmitted worker {w} at clock {clock}",
                          file=sys.stderr, flush=True)
                elif (w in seen_ready and status.active
                        and status.weights_message_sent):
                    # liveness reissue, mirroring ServerNode.
                    # _composite_member_live: the worker process
                    # restarted (durable state restored, so it READYs
                    # again immediately) while its round assignment was
                    # lost mid-flight — re-send the current weights so
                    # the stalled gate completes.  Idempotent for
                    # theta: a recompute yields a duplicate gradient
                    # the clock filter already drops.
                    server.send_weights(w, status.vector_clock)
                    print(f"reissued weights to restarted worker {w} "
                          f"at clock {status.vector_clock}",
                          file=sys.stderr, flush=True)
                else:
                    seen_ready.add(w)

    # live pulse (utils/status.py): iters/s, clocks, membership, queue
    # depth — the split-mode face of `--status_every`
    from kafka_ps_tpu.utils.status import StatusReporter

    rolling_critpath = None
    if telemetry.enabled:
        from kafka_ps_tpu.telemetry.critpath import RollingCritpath
        rolling_critpath = RollingCritpath(telemetry)

    def status() -> dict:
        tr = server.tracker
        active = tr.active_workers
        out = {
            "iters": server.iterations,
            "clocks": [f"{w}:{tr.tracker[w].vector_clock}"
                       for w in range(cfg.num_workers)],
            "active": f"{len(active)}/{cfg.num_workers}",
            "pending": {"gradients": fabric.total_pending(
                fabric_mod.GRADIENTS_TOPIC)},
            "rows_sent": producer.rows_sent,
        }
        if engine is not None:
            s = engine.stats()
            out["predictions_per_s"] = s["requests"]
            out["serving"] = {"occ": s["occupancy"],
                              "p50_ms": s["p50_ms"], "p99_ms": s["p99_ms"],
                              "stale": s["rejections"]}
        if telemetry.enabled:
            out["metrics"] = telemetry.summary()
        if rolling_critpath is not None:
            # per-heartbeat histogram deltas -> dominant-segment verdict
            # for this window (telemetry/critpath.py)
            out["critpath"] = rolling_critpath.sample()
        if modelhealth is not None:
            # model-health pulse: update norms, direction cosine,
            # drift verdict (telemetry/modelhealth.py)
            out["modelhealth"] = modelhealth.summary()
        return out

    reporter = StatusReporter(getattr(args, "status_every", 0.0) or 0.0,
                              status).start()

    server.start_training_loop()
    max_iters = args.max_iterations or sys.maxsize
    try:
        while server.iterations < max_iters:
            apply_events()
            batch_sink.flush_aged()   # age-bound the batched ingest path
            g = fabric.poll_blocking(fabric_mod.GRADIENTS_TOPIC, 0,
                                     timeout=0.2)
            if g is not None:
                server.process(g)
    except KeyboardInterrupt:
        # mirror cli/run.py: Ctrl-C is an orderly shutdown — the
        # finally block still checkpoints and flushes logs/events
        print("interrupted — shutting down", file=sys.stderr, flush=True)
    finally:
        reporter.stop()
        producer.stop()      # join the pump before teardown (SIGABRT
                             # discipline: no native-code daemon threads
                             # may outlive the main thread)
        batch_sink.flush_all()   # after the pump join: no concurrent adds
        bridge.close()       # workers see EOF and shut down; joins
                             # accept/heartbeat/reader threads
        if engine is not None:
            engine.close()   # after the bridge: no reader can submit now
        if eval_engine is not None:
            eval_engine.close()   # drains pending evals into server.log
        if checkpoint_path:
            from kafka_ps_tpu.utils import checkpoint as ckpt
            ckpt.save(checkpoint_path, server)
        if tier_store is not None:
            tier_store.close()   # after the save: it may fault cold pages
        if reroute["dropped"] or bridge.dropped_sends:
            print(f"dropped rows: {reroute['dropped']}, dropped sends: "
                  f"{bridge.dropped_sends}", file=sys.stderr, flush=True)
        server.log.close()           # joins drain thread + closes sink
        events_log.close()
        ops.close()                  # final flight dump + health down
        if drift_sink is not None:
            # after ops.close(): the plane's final drain may still
            # emit a verdict row
            drift_sink.close()
        _dump_telemetry(args, tracer, telemetry)
    return 0


def run_worker(args) -> int:
    """Worker role: the logical workers in --worker_ids, server remote.

    `--connect` with a comma-separated address list enters the
    range-sharded deployment (docs/SHARDING.md): one connection per
    shard-server process, gradient slices routed per shard, weights
    slices reassembled at a common clock.

    `--aggregate HOST:PORT` dials a per-host aggregator relay instead
    of the server (docs/AGGREGATION.md) and reuses the sharded path
    with one address: the relay speaks the server protocol downstream,
    and the router's redelivery cache is exactly the buffer-and-resend
    a SIGKILL'd relay needs (deltas it held die with it; the stale
    weights that follow reconnection trigger cache resends)."""
    if getattr(args, "aggregate", None):
        return _run_worker_sharded(args, [args.aggregate],
                                   aggregate=True)
    if "," in args.connect:
        return _run_worker_sharded(
            args, [a for a in args.connect.split(",") if a])
    from kafka_ps_tpu.cli.run import load_test_csv
    from kafka_ps_tpu.data.buffer import SlidingBuffer
    from kafka_ps_tpu.runtime.worker import WorkerNode
    from kafka_ps_tpu.utils.csvlog import CsvLogSink, WORKER_HEADER

    host, _, port = args.connect.rpartition(":")
    ids = [int(w) for w in args.worker_ids.split(",")]
    cfg = _make_cfg(args)
    test_x, test_y = load_test_csv(args.test_data_file_path,
                                   args.num_features)

    # connect FIRST: the handshake (net.T_CONFIG) carries the server's
    # logical-run id, which decides whether local state is valid below,
    # and the NEGOTIATED codec — compression runs at what the server
    # agreed to, not at what this process asked for (a mixed-version
    # server replies NONE and both sides ship plain frames)
    tracer, telemetry = _make_telemetry(args)
    bridge = net.WorkerBridge(
        host or "127.0.0.1", int(port), ids,
        heartbeat_timeout=getattr(args, "heartbeat_timeout", None),
        codec=_codec_spec(args),
        tracer=tracer, telemetry=telemetry,
        coalesce=getattr(args, "wire_coalesce", True))
    fabric = bridge.make_fabric()
    # per-process model-health plane (--model-health): each worker
    # process watches its OWN local training stream — eval rows from
    # _finish, sampled buffer arrivals into the feature sketch
    from kafka_ps_tpu.telemetry.registry import model_name
    modelhealth, drift_sink = _make_modelhealth(
        args, telemetry, num_features=cfg.model.num_features,
        model=model_name(cfg.consistency_model),
        log_name="./logs-drift-worker.csv")
    # death hooks armed before training: a SIGTERM'd worker leaves its
    # flight dump for the postmortem merge even mid-iteration
    ops = _make_ops(args, telemetry, role="worker",
                    modelhealth=modelhealth)
    ops.start()

    compressors = None
    if bridge.negotiated.codec_id != net.CODEC_NONE:
        from kafka_ps_tpu import compress
        from kafka_ps_tpu.models.task import get_task
        codec = compress.get_codec(
            bridge.negotiated, get_task(cfg.task, cfg.model).num_params)
        compressors = {w: compress.ErrorFeedback(codec) for w in ids}
        print(f"compression: {bridge.negotiated.name} (negotiated)",
              file=sys.stderr, flush=True)

    # worker-local durable state (utils/checkpoint.py): the per-process
    # analogue of the reference's changelog-backed store restore
    # (WorkerApp.java:40-42) — a worker process restarted WITHIN a run
    # recovers its training window instead of cold-starting an empty
    # buffer.  State written under a different run (the server started
    # fresh since) is stale: restoring it would seed this run with the
    # old run's rows and append to a log the server side truncated.
    state_path = None
    restoring = False
    if getattr(args, "checkpoint", None):
        from kafka_ps_tpu.utils import checkpoint as ckpt
        state_path = ckpt.worker_state_path(args.checkpoint, ids)
        stored = ckpt.peek_run_id(state_path)
        restoring = stored is not None and stored == bridge.server_run_id
        if not restoring and os.path.exists(state_path):
            print(f"discarding stale worker state {state_path} "
                  f"(run {stored} != server run {bridge.server_run_id})",
                  file=sys.stderr, flush=True)
            os.remove(state_path)
    # Log continuity is decided by RUN continuity, not by whether buffer
    # state restored (ADVICE r4): a worker SIGKILL'd before its first
    # state snapshot has no state file, but its pre-crash log rows still
    # belong to this logical run — truncating them would break the
    # cross-restart audit trail.  A sidecar marker records which run the
    # log belongs to.
    log_path = "./logs-worker.csv" if args.logging else None
    append_log = restoring
    if log_path is not None:
        marker = log_path + ".runid"
        try:
            with open(marker) as fh:
                append_log = append_log or (
                    int(fh.read().strip()) == bridge.server_run_id)
        except (OSError, ValueError):
            pass
        with open(marker, "w") as fh:
            fh.write(str(bridge.server_run_id))
    log = CsvLogSink(log_path, WORKER_HEADER, append=append_log)

    from kafka_ps_tpu.models.task import get_task
    task = get_task(cfg.task, cfg.model)     # a row's width and dtype
    buffers = {w: SlidingBuffer(task.row_width, cfg.buffer,
                                telemetry=telemetry, worker=w,
                                dtype=task.row_dtype)
               for w in ids}
    if restoring:
        from kafka_ps_tpu.utils import checkpoint as ckpt
        if ckpt.maybe_restore_worker(state_path, buffers,
                                     run_id=bridge.server_run_id,
                                     residuals=compressors):
            print("restored worker buffers: " + ", ".join(
                f"{w}:{buffers[w].count} rows (seen "
                f"{buffers[w].num_tuples_seen})" for w in ids),
                file=sys.stderr, flush=True)
    from kafka_ps_tpu.utils.asynclog import DeferredSink
    worker_log = DeferredSink(log)
    nodes = {w: WorkerNode(w, cfg, fabric, buffers[w], test_x, test_y,
                           worker_log, tracer=tracer, telemetry=telemetry)
             for w in ids}
    if compressors is not None:
        for w in ids:
            nodes[w].compressor = compressors[w]
    if modelhealth is not None:
        # all logical workers in this process share the one plane;
        # the reader thread's buffer inserts feed the feature sketch
        for w in ids:
            nodes[w].modelhealth = modelhealth
            buffers[w].attach_drift(modelhealth.drift)

    if state_path is not None:
        from kafka_ps_tpu.utils import checkpoint as ckpt
        state_stop = threading.Event()

        state_every = getattr(args, "state_every", 1.0)
        if state_every is None or state_every <= 0:
            raise SystemExit("--state_every must be > 0 (seconds between "
                             "durable buffer snapshots)")

        def state_saver():
            # the changelog analogue: snapshot on a cadence (the
            # --state_every flag) so a SIGKILL'd process loses at most
            # one interval of rows; skip idle intervals.  The
            # fingerprint covers insertions AND iteration counts: under
            # compression the error-feedback residuals advance on every
            # local iteration even when no new rows arrived, and a
            # snapshot that missed them would replay a biased stream
            # after a crash.
            last = None
            while not state_stop.wait(state_every):
                fp = (tuple(buffers[w].num_tuples_seen for w in ids),
                      tuple(nodes[w].iterations for w in ids))
                if fp != last:
                    ckpt.save_worker(state_path, buffers,
                                     run_id=bridge.server_run_id,
                                     residuals=compressors)
                    last = fp

        state_saver_thread = threading.Thread(
            target=state_saver, daemon=True, name="kps-worker-state")
        state_saver_thread.start()

    reader_thread = threading.Thread(target=bridge.run_reader,
                                     args=(buffers,), daemon=True,
                                     name="kps-worker-reader")
    reader_thread.start()

    # READY per worker once its buffer has data (the server gates the
    # training-loop bootstrap on this, net.ServerBridge.wait_for_workers)
    # — or `--ready-rows N` rows of it, when a test wants training to
    # start only after a deterministic ingestion prefix
    ready_stop = threading.Event()
    ready_rows = max(1, int(getattr(args, "ready_rows", 1) or 1))

    def announce_ready():
        pending = set(ids)
        while (pending and not bridge.disconnected.is_set()
               and not ready_stop.is_set()):
            for w in list(pending):
                if buffers[w].count >= ready_rows:
                    bridge.mark_ready(w)
                    pending.discard(w)
            time.sleep(0.01)

    ready_thread = threading.Thread(target=announce_ready, daemon=True,
                                    name="kps-worker-ready")
    ready_thread.start()

    stop = threading.Event()
    errors: list[BaseException] = []

    def worker_loop(node: WorkerNode):
        try:
            while not stop.is_set():
                msg = fabric.poll_blocking(fabric_mod.WEIGHTS_TOPIC,
                                           node.worker_id, timeout=0.1)
                if msg is not None:
                    node.on_weights(msg)
        except (ConnectionError, OSError):
            pass                      # server hung up mid-send
        except BaseException as e:    # pragma: no cover - diagnostics
            errors.append(e)
            stop.set()

    threads = [threading.Thread(target=worker_loop, args=(nodes[w],),
                                daemon=True, name=f"worker-{w}")
               for w in ids]
    for t in threads:
        t.start()
    bridge.disconnected.wait()        # run until the server closes
    stop.set()
    ready_stop.set()
    # Shutdown discipline (the round-4 SIGABRT root cause, docs/
    # TESTING.md): every thread that can touch JAX/XLA or numpy native
    # code MUST be joined before the interpreter finalizes — a daemon
    # thread killed inside C++ noexcept frames calls std::terminate.
    # A worker loop is bounded (poll timeout 0.1 s + one local update),
    # but the first post-load iteration can pay tens of seconds of jit
    # compilation on a loaded machine, so the joins are generous.
    leftover = []
    for t in threads:
        t.join(timeout=120.0)
        if t.is_alive():
            leftover.append(t.name)
    if state_path is not None:
        from kafka_ps_tpu.utils import checkpoint as ckpt
        state_stop.set()
        # join BEFORE the final save: two concurrent save_worker calls
        # share one tmp path and would corrupt the state file
        state_saver_thread.join(timeout=60.0)
        if state_saver_thread.is_alive():   # wedged in a stalled write
            print("warning: state saver still writing; skipping final "
                  "snapshot", file=sys.stderr, flush=True)
            leftover.append(state_saver_thread.name)
        else:
            ckpt.save_worker(state_path, buffers,   # final snapshot
                             run_id=bridge.server_run_id,
                             residuals=compressors)
    worker_log.close()    # joins the drain thread, flushes, closes log
    bridge.close()
    reader_thread.join(timeout=10.0)  # EOF/closed socket ends it
    ready_thread.join(timeout=10.0)
    for t in (reader_thread, ready_thread):
        if t.is_alive():
            leftover.append(t.name)
    # dump BEFORE the potential os._exit below — a wedged thread must
    # not cost the process its trace/metrics/flight files
    ops.close()
    if drift_sink is not None:
        drift_sink.close()
    _dump_telemetry(args, tracer, telemetry)
    rc = 0
    if errors:
        print(f"worker failed: {errors[0]!r}", file=sys.stderr, flush=True)
        rc = 1
    if leftover:
        # a thread survived its join and may be inside native code:
        # skip interpreter finalization entirely rather than risk the
        # teardown abort (this is a CLI process, nothing else to run)
        print(f"warning: threads still alive at exit: {leftover}; "
              "exiting without finalization", file=sys.stderr, flush=True)
        sys.stdout.flush()
        os._exit(rc)
    if errors:
        raise RuntimeError("worker failed") from errors[0]
    return 0


# -- range-sharded split deployment (docs/SHARDING.md) -----------------------

def run_server_shard(args) -> int:
    """One shard-server process of a `--shards N` split deployment:
    owns `ShardPlan.ranges[shard_id]` of theta with its own per-worker
    vector clocks, its own consistency gate (all three models evaluate
    per shard), its own per-shard checkpoint file
    (utils/checkpoint.shard_state_path) and — with `--durable-log DIR`
    — its own commit-log partition under `DIR/shard<I>of<N>`, so a
    SIGKILL'd shard recovers bitwise from checkpoint + log-tail replay
    while the other shards keep running (scripts/tier1.sh --shard).

    Shard 0 additionally hosts the stream producer (the data plane is
    unsharded — rows go to workers, not servers).  No shard hosts the
    server-side eval or the serving plane: each owns only a slice, and
    assembled-theta serving is the in-process ShardedServerGroup /
    FrontierCutPublisher story.  Worker-side gradient sparsification
    (`--compress topk:R` on the WORKER processes) is what shrinks the
    per-shard wire traffic; shard servers themselves run uncompressed
    weights slices.
    """
    from kafka_ps_tpu.models.task import get_task
    from kafka_ps_tpu.data.stream import CsvStreamProducer
    from kafka_ps_tpu.runtime.server import ServerNode
    from kafka_ps_tpu.runtime.sharding import ShardPlan
    from kafka_ps_tpu.utils import checkpoint as ckpt

    cfg = _make_cfg(args)
    num_shards, shard_id = args.shards, args.shard_id
    plan = ShardPlan(get_task(cfg.task, cfg.model).num_params, num_shards)
    key_range = plan.ranges[shard_id]
    if getattr(args, "serve", False):
        raise SystemExit(
            "--serve is unsharded-only in split mode: a shard process "
            "holds one theta slice; assembled-theta serving is the "
            "in-process ShardedServerGroup path (docs/SHARDING.md)")
    failure_policy = getattr(args, "failure_policy", "halt")
    hb_timeout = getattr(args, "heartbeat_timeout", None)

    checkpoint_path = None
    if getattr(args, "checkpoint", None):
        checkpoint_path = ckpt.shard_state_path(
            args.checkpoint, shard_id, num_shards)
    resuming = bool(checkpoint_path) and os.path.exists(checkpoint_path)
    run_id = ckpt.peek_run_id(checkpoint_path) if resuming else None
    if run_id is None:
        run_id = time.time_ns()

    tracer, telemetry = _make_telemetry(args)
    inner = fabric_mod.Fabric()
    if getattr(args, "durable_log", None):
        # one durable-log partition set per shard: gradients keyed 0
        # locally, rooted under a shard-suffixed directory so N shard
        # processes never share a segment file
        from kafka_ps_tpu.log import DurableFabric, LogConfig
        inner = DurableFabric(
            os.path.join(args.durable_log,
                         f"shard{shard_id}of{num_shards}"),
            LogConfig(fsync=getattr(args, "fsync", "interval")),
            tracer=tracer, telemetry=telemetry)
    bridge = net.ServerBridge(
        port=args.listen,
        heartbeat_interval=min(1.0, hb_timeout / 3) if hb_timeout else 1.0,
        heartbeat_timeout=hb_timeout,
        run_id=run_id, tracer=tracer, telemetry=telemetry,
        coalesce=getattr(args, "wire_coalesce", True))
    print(f"shard {shard_id}/{num_shards} range "
          f"[{key_range.start}, {key_range.end}) listening on port "
          f"{bridge.port}", file=sys.stderr, flush=True)
    fabric = bridge.wrap(inner)     # preserves DurableFabric's class
    server = ServerNode(cfg, fabric, None, None, None,
                        tracer=tracer, telemetry=telemetry,
                        key_range=key_range, shard_id=shard_id,
                        num_shards=num_shards)
    server.run_id = run_id
    tier_store = _attach_tier_store(
        server, cfg, key_range,
        cold_dir=(inner.cold_dir()      # under the shard-suffixed root
                  if getattr(inner, "durable", False) else None),
        telemetry=telemetry)
    if checkpoint_path:
        ckpt.maybe_restore(checkpoint_path, server)
        server.checkpoint_path = checkpoint_path
        server.checkpoint_every = getattr(args, "checkpoint_every", 50)
        if resuming:
            print(f"shard {shard_id}: restored checkpoint at iteration "
                  f"{server.iterations}", file=sys.stderr, flush=True)
    if getattr(inner, "durable", False):
        # crash recovery: re-enqueue the unconsumed gradient-slice tail
        # past the checkpoint's committed offsets; the tracker dedups
        # whatever the checkpoint already covers (at-least-once replay)
        counts = inner.recover(server.restored_log_offsets)
        if any(counts.values()):
            print(f"shard {shard_id}: durable-log replay {counts}",
                  file=sys.stderr, flush=True)

    # per-shard model-health plane: every metric family carries
    # shard=<I>, so fleet dashboards can tell WHICH slice went sour
    from kafka_ps_tpu.telemetry.registry import model_name
    modelhealth, drift_sink = _make_modelhealth(
        args, telemetry, shard=shard_id,
        num_features=cfg.model.num_features,
        model=model_name(cfg.consistency_model),
        log_name=f"./logs-drift-shard{shard_id}.csv")
    if modelhealth is not None:
        server.attach_model_health(modelhealth)

    # per-shard ops plane: the dump carries shard identity, so the
    # postmortem merge can tell WHICH gate in the fleet wedged
    ops = _make_ops(args, telemetry, role="server", shard=shard_id,
                    meta={"shards": list(range(num_shards))},
                    modelhealth=modelhealth)
    ops.add_gate_watchdog(server)
    if getattr(inner, "durable", False):
        ops.add_fsync_watchdog()
    ops.start()

    events: "queue.Queue[tuple[str, object]]" = queue.Queue()
    bridge.on_disconnect = lambda ids: events.put(("disconnect", ids))
    bridge.on_ready = lambda w: events.put(("ready", w))
    workers = server.tracker.active_workers
    bridge.wait_for_connected(workers, timeout=args.connect_timeout)

    producer = None
    batch_sink = None
    reroute = {"rr": 0, "dropped": 0}
    if shard_id == 0:
        # the data plane lives on shard 0 only — same sink/reroute
        # policy as the unsharded run_server
        def sink(worker: int, features: dict[int, float],
                 label: int) -> None:
            deliverable = (failure_policy == "rebalance"
                           or server.tracker.tracker[worker].active)
            if deliverable and bridge.send_data(worker, features, label):
                return
            active = server.tracker.active_workers
            for _ in range(len(active)):
                alt = active[reroute["rr"] % len(active)]
                reroute["rr"] += 1
                if alt != worker and bridge.send_data(alt, features,
                                                      label):
                    return
            reroute["dropped"] += 1

        batch_sink = _BatchingSink(
            bridge, sink,
            deliverable=lambda w: (failure_policy == "rebalance"
                                   or server.tracker.tracker[w].active))
        producer = CsvStreamProducer(
            args.training_data_file_path, cfg.num_workers, batch_sink,
            time_per_event_ms=cfg.stream.time_per_event_ms,
            prefill_per_worker=cfg.stream.prefill_per_worker)
        producer.run_in_background()
    bridge.wait_for_workers(workers, timeout=args.connect_timeout)

    def apply_events() -> None:
        while True:
            try:
                kind, val = events.get_nowait()
            except queue.Empty:
                return
            if kind == "disconnect":
                live = [w for w in val
                        if server.tracker.tracker[w].active]
                if not live:
                    continue
                if failure_policy == "halt":
                    raise RuntimeError(
                        f"shard {shard_id}: worker connection lost for "
                        f"{sorted(live)} (failure_policy=halt)")
                for w in live:
                    try:
                        server.remove_worker(w)
                    except ValueError:
                        raise RuntimeError(
                            "all worker connections lost") from None
            elif kind == "ready" and failure_policy == "rebalance":
                w = int(val)
                if not server.tracker.tracker[w].active:
                    server.readmit_worker(w)

    server.start_training_loop()
    max_iters = args.max_iterations or sys.maxsize
    try:
        while server.iterations < max_iters:
            apply_events()
            if batch_sink is not None:
                batch_sink.flush_aged()
            g = fabric.poll_blocking(fabric_mod.GRADIENTS_TOPIC, 0,
                                     timeout=0.2)
            if g is not None:
                server.process(g)
    except KeyboardInterrupt:
        print(f"shard {shard_id}: interrupted — shutting down",
              file=sys.stderr, flush=True)
    finally:
        if producer is not None:
            producer.stop()
        if batch_sink is not None:
            batch_sink.flush_all()
        bridge.close()
        if checkpoint_path:
            # commit point: checkpoint + committed log offsets describe
            # the same instant (ServerNode.save_checkpoint_now commits
            # a durable fabric's offsets after the save)
            server.save_checkpoint_now()
        if tier_store is not None:
            tier_store.close()   # after the save: it may fault cold pages
        if getattr(inner, "durable", False):
            inner.close()
        if reroute["dropped"] or bridge.dropped_sends:
            print(f"shard {shard_id}: dropped rows "
                  f"{reroute['dropped']}, dropped sends "
                  f"{bridge.dropped_sends}", file=sys.stderr, flush=True)
        ops.close()
        if drift_sink is not None:
            drift_sink.close()
        _dump_telemetry(args, tracer, telemetry)
    return 0


# -- hierarchical aggregation tier (kafka_ps_tpu/agg/) -----------------------

def run_aggregator(args) -> int:
    """Aggregator-relay role (docs/AGGREGATION.md): one per host,
    between that host's worker processes and the server.

        # the relay: HELLOs upstream as aggregator for workers 0-3,
        # listens for those worker processes downstream
        python -m kafka_ps_tpu.cli.agg_runner --connect hostA:8477 \\
            --listen 8478 --agg-id 0 --worker_ids 0,1,2,3

        # each member worker dials the RELAY, not the server
        python -m kafka_ps_tpu.cli.worker_runner --aggregate host:8478 \\
            --worker_ids 0 -test test.csv

    The server sees ONE connection, one composite gradient frame per
    (host, flush) and one grouped weights frame per release set —
    fan-in collapses from O(workers) to O(hosts).  The relay holds no
    durable protocol state (workers buffer-and-resend, the server gate
    deduplicates); with --compress it owns the error-feedback
    residuals, persisted via --checkpoint so a SIGKILL keeps the
    compressed path bitwise-pinned."""
    from kafka_ps_tpu.agg.relay import AggregatorRelay
    from kafka_ps_tpu.models.task import get_task

    connect = getattr(args, "connect", None)
    if not connect:
        raise SystemExit("aggregator role requires --connect HOST:PORT "
                         "(the upstream server)")
    host, _, port = connect.rpartition(":")
    ids = [int(w) for w in args.worker_ids.split(",")]
    cfg = _make_cfg(args)
    num_params = get_task(cfg.task, cfg.model).num_params
    tracer, telemetry = _make_telemetry(args)
    ops = _make_ops(args, telemetry, role="aggregator")
    ops.start()
    spec = _codec_spec(args)
    relay = AggregatorRelay(
        int(getattr(args, "agg_id", 0) or 0),
        host or "127.0.0.1", int(port), ids, num_params,
        listen_port=int(getattr(args, "listen", 0) or 0),
        codec_spec=spec if spec.codec_id != net.CODEC_NONE else None,
        summed=bool(getattr(args, "summed", False)),
        checkpoint_path=getattr(args, "checkpoint", None),
        flush_interval=float(getattr(args, "flush_interval", 0.002)
                             or 0.002),
        heartbeat_interval=1.0,
        heartbeat_timeout=getattr(args, "heartbeat_timeout", None),
        tracer=tracer, telemetry=telemetry,
        coalesce=getattr(args, "wire_coalesce", True))
    if relay.restored:
        print("restored aggregator error-feedback residuals",
              file=sys.stderr, flush=True)
    print(f"aggregator {relay.agg_id} listening on port {relay.port} "
          f"(members {','.join(map(str, ids))}, upstream {connect})",
          file=sys.stderr, flush=True)
    try:
        relay.run()               # until the server closes the run
    except KeyboardInterrupt:
        pass
    finally:
        relay.close()
        ops.close()
        _dump_telemetry(args, tracer, telemetry)
    return 0


class _AssemblerSink:
    """Per-bridge weights sink (net.WorkerBridge.set_weights_sink):
    feeds one shard's weights slices into the shared WeightsAssembler
    under a lock — N reader threads offer concurrently, and assembly
    state must mutate atomically per slice."""

    def __init__(self, shard_id: int, assembler, lock):
        self._shard_id = shard_id
        self._assembler = assembler
        self._lock = lock

    def send(self, topic: str, key: int, message) -> None:
        with self._lock:
            self._assembler.offer(self._shard_id, key, message)


def _run_worker_sharded(args, addrs: list[str],
                        aggregate: bool = False) -> int:
    """Worker role against a `--shards N` server fleet: one bridge per
    shard address (in shard-id order), a ShardRouter per logical worker
    splitting each delta into per-shard slices, and a WeightsAssembler
    reassembling per-shard weights slices into the one full-range
    message the WorkerNodes train on.

    A dead bridge is NOT fatal while any other shard is alive: the
    supervisor reconnects to the restarted shard process, and the
    router's redelivery cache resends the gradient slices the dead
    shard missed (bitwise — never recomputed).  The run ends when every
    shard has closed its connection (servers reached max iterations).

    `aggregate=True` (--aggregate, docs/AGGREGATION.md) points the one
    address at a per-host aggregator relay instead of a shard server.
    Same machinery, two differences: compression is delegated (raw f32
    to the relay, which owns the error-feedback residuals), and a
    reconnect resends the router's WHOLE cache — the relay is
    stateless, so unlike a checkpoint-restored shard nothing on the
    other side knows to ask for the deltas that died with it."""
    from kafka_ps_tpu.cli.run import load_test_csv
    from kafka_ps_tpu.data.buffer import SlidingBuffer
    from kafka_ps_tpu.models.task import get_task
    from kafka_ps_tpu.runtime.sharding import ShardPlan, ShardRouter, \
        WeightsAssembler
    from kafka_ps_tpu.runtime.worker import WorkerNode
    from kafka_ps_tpu.utils.csvlog import CsvLogSink, WORKER_HEADER

    ids = [int(w) for w in args.worker_ids.split(",")]
    cfg = _make_cfg(args)
    test_x, test_y = load_test_csv(args.test_data_file_path,
                                   args.num_features)
    num_params = get_task(cfg.task, cfg.model).num_params
    plan = ShardPlan(num_params, len(addrs))
    tracer, telemetry = _make_telemetry(args)
    # per-process model-health plane (--model-health): the sharded
    # worker watches its local training stream just like run_worker
    from kafka_ps_tpu.telemetry.registry import model_name
    modelhealth, drift_sink = _make_modelhealth(
        args, telemetry, num_features=cfg.model.num_features,
        model=model_name(cfg.consistency_model),
        log_name="./logs-drift-worker.csv")
    # meta names the FULL shard roster: the postmortem analyzer's
    # dead-shard detection is (known shards) - (shards that dumped),
    # and the worker's dump is what survives when a shard is SIGKILL'd
    ops = _make_ops(args, telemetry, role="worker",
                    meta={"shards": list(range(len(addrs)))},
                    modelhealth=modelhealth)
    ops.start()

    def connect(addr: str, timeout: float = 30.0):
        host, _, port = addr.rpartition(":")
        return net.WorkerBridge(host or "127.0.0.1", int(port), ids,
                                connect_timeout=timeout,
                                heartbeat_timeout=getattr(
                                    args, "heartbeat_timeout", None),
                                tracer=tracer, telemetry=telemetry,
                                coalesce=getattr(
                                    args, "wire_coalesce", True))

    slots: list = [connect(a) for a in addrs]

    fabric = fabric_mod.Fabric()        # local: assembled WEIGHTS only
    assemble_lock = OrderedLock("ShardedWorker.assemble")
    routers: dict[int, ShardRouter] = {}

    def resend_cb(shard_id: int, worker: int, clock: int) -> bool:
        router = routers.get(worker)
        return router.resend(shard_id, clock) if router else False

    assembler = WeightsAssembler(
        plan,
        deliver=lambda w, m: fabric.send(fabric_mod.WEIGHTS_TOPIC, w, m),
        resend=resend_cb)
    sinks = [_AssemblerSink(i, assembler, assemble_lock)
             for i in range(len(addrs))]
    for i, b in enumerate(slots):
        b.set_weights_sink(sinks[i])

    def safe_send(shard_id: int, message) -> None:
        # a slice to a crashed shard is dropped here and recovered by
        # the redelivery protocol once the shard is back (the router
        # cache holds it; the shard's stale weights slice triggers the
        # resend) — the worker must not die on a shard's crash
        try:
            slots[shard_id].send_gradients(0, message)
        except (ConnectionError, OSError):
            pass

    for w in ids:
        routers[w] = ShardRouter(plan, send=safe_send)

    compressors = None
    spec = _codec_spec(args)
    if spec.codec_id != net.CODEC_NONE:
        if aggregate:
            # the relay owns the error-feedback residuals and encodes
            # ONCE at the aggregator→server edge (agg/core.py);
            # encoding here too would quantize the signal twice
            print(f"compression: {spec.name} (delegated to aggregator)",
                  file=sys.stderr, flush=True)
        else:
            # no per-connection negotiation in the sharded fleet:
            # slices cross the wire DECODED (dense tid-1 / sparse
            # tid-6 frames), so --compress here is the local gradient
            # sparsifier — topk is what makes a delta touch few shards
            # (docs/SHARDING.md)
            from kafka_ps_tpu import compress
            codec = compress.get_codec(spec, num_params)
            compressors = {w: compress.ErrorFeedback(codec)
                           for w in ids}
            print(f"compression: {spec.name} (local sparsifier)",
                  file=sys.stderr, flush=True)

    from kafka_ps_tpu.models.task import get_task
    task = get_task(cfg.task, cfg.model)     # a row's width and dtype
    buffers = {w: SlidingBuffer(task.row_width, cfg.buffer,
                                telemetry=telemetry, worker=w,
                                dtype=task.row_dtype)
               for w in ids}

    # worker-local durable state, exactly as in run_worker: a member
    # process restarted WITHIN a run recovers its training window
    # instead of cold-starting an empty buffer.  Run continuity is
    # keyed on slots[0]'s advertised run id — one relay in aggregate
    # mode; in sharded mode shard 0 stands in for the fleet (per-shard
    # run ids are independent, so cross-restart state is best-effort
    # there).
    run_id = slots[0].server_run_id
    state_path = None
    restoring = False
    if getattr(args, "checkpoint", None):
        from kafka_ps_tpu.utils import checkpoint as ckpt
        state_path = ckpt.worker_state_path(args.checkpoint, ids)
        stored = ckpt.peek_run_id(state_path)
        restoring = stored is not None and stored == run_id
        if not restoring and os.path.exists(state_path):
            print(f"discarding stale worker state {state_path} "
                  f"(run {stored} != server run {run_id})",
                  file=sys.stderr, flush=True)
            os.remove(state_path)
    if restoring:
        from kafka_ps_tpu.utils import checkpoint as ckpt
        if ckpt.maybe_restore_worker(state_path, buffers, run_id=run_id,
                                     residuals=compressors):
            print("restored worker buffers: " + ", ".join(
                f"{w}:{buffers[w].count} rows (seen "
                f"{buffers[w].num_tuples_seen})" for w in ids),
                file=sys.stderr, flush=True)

    # log continuity decided by RUN continuity, not by whether state
    # restored (same rule as run_worker): pre-crash rows belong to this
    # logical run even when the crash beat the first state snapshot
    log_path = "./logs-worker.csv" if args.logging else None
    append_log = restoring
    if log_path is not None:
        marker = log_path + ".runid"
        try:
            with open(marker) as fh:
                append_log = append_log or (
                    int(fh.read().strip()) == run_id)
        except (OSError, ValueError):
            pass
        with open(marker, "w") as fh:
            fh.write(str(run_id))
    log = CsvLogSink(log_path, WORKER_HEADER, append=append_log)
    from kafka_ps_tpu.utils.asynclog import DeferredSink
    worker_log = DeferredSink(log)
    nodes = {w: WorkerNode(w, cfg, fabric, buffers[w], test_x, test_y,
                           worker_log, tracer=tracer, telemetry=telemetry)
             for w in ids}
    for w in ids:
        nodes[w].shard_router = routers[w]
        if compressors is not None:
            nodes[w].compressor = compressors[w]
        if modelhealth is not None:
            nodes[w].modelhealth = modelhealth
            buffers[w].attach_drift(modelhealth.drift)

    if state_path is not None:
        from kafka_ps_tpu.utils import checkpoint as ckpt
        state_stop = threading.Event()

        state_every = getattr(args, "state_every", 1.0)
        if state_every is None or state_every <= 0:
            raise SystemExit("--state_every must be > 0 (seconds between "
                             "durable buffer snapshots)")

        def state_saver():
            # snapshot on the --state_every cadence; the fingerprint
            # covers insertions and iterations (run_worker's rule)
            last = None
            while not state_stop.wait(state_every):
                fp = (tuple(buffers[w].num_tuples_seen for w in ids),
                      tuple(nodes[w].iterations for w in ids))
                if fp != last:
                    ckpt.save_worker(state_path, buffers, run_id=run_id,
                                     residuals=compressors)
                    last = fp

        state_saver_thread = threading.Thread(
            target=state_saver, daemon=True, name="kps-worker-state")
        state_saver_thread.start()

    reader_threads: list[threading.Thread] = []

    def start_reader(bridge) -> None:
        t = threading.Thread(target=bridge.run_reader, args=(buffers,),
                             daemon=True, name="kps-worker-reader")
        t.start()
        reader_threads.append(t)

    for b in slots:
        start_reader(b)

    stop = threading.Event()
    ready_rows = max(1, int(getattr(args, "ready_rows", 1) or 1))

    def announce_ready() -> None:
        pending = {(i, w) for i in range(len(slots)) for w in ids}
        while pending and not stop.is_set():
            for i, w in list(pending):
                if buffers[w].count >= ready_rows:
                    try:
                        slots[i].mark_ready(w)
                    except (ConnectionError, OSError):
                        continue
                    pending.discard((i, w))
            time.sleep(0.01)

    ready_thread = threading.Thread(target=announce_ready, daemon=True,
                                    name="kps-worker-ready")
    ready_thread.start()

    # A dead aggregator relay is indistinguishable from end-of-run to
    # its members by the socket alone: both drop their ONLY connection.
    # They are told apart explicitly — a cleanly-closing relay sends the
    # GOODBYE config first (net.GOODBYE_RUN_ID, agg/relay.py), a
    # SIGKILL'd one sends nothing, so its members hold the run open for
    # this grace window and resend their caches once the restarted relay
    # answers.  Sharded mode keeps the simple rule: the run ends when
    # every shard has closed (shard servers recover from their own
    # durable logs; nothing is lost by stopping).
    AGG_RECONNECT_GRACE = 30.0
    down_since = [None]

    def fleet_is_done() -> bool:
        if not all(s.disconnected.is_set() for s in slots):
            down_since[0] = None
            return False
        if not aggregate or any(s.run_over for s in slots):
            return True
        if down_since[0] is None:
            down_since[0] = time.monotonic()
        return time.monotonic() - down_since[0] > AGG_RECONNECT_GRACE

    def supervise() -> None:
        # reconnect crashed shards/relays; end the run when the whole
        # fleet is gone for good (normal completion: every shard closes
        # at max iterations, a relay forwards the goodbye)
        while not stop.is_set():
            if fleet_is_done():
                stop.set()
                return
            for i in range(len(slots)):
                if not slots[i].disconnected.is_set():
                    continue
                try:
                    nb = connect(addrs[i], timeout=3.0)
                except (ConnectionError, OSError):
                    continue        # shard still down; retry next sweep
                nb.set_weights_sink(sinks[i])
                start_reader(nb)
                slots[i] = nb
                for w in ids:
                    if buffers[w].count >= ready_rows:
                        try:
                            nb.mark_ready(w)
                        except (ConnectionError, OSError):
                            pass
                if aggregate:
                    # buffer-and-resend (docs/AGGREGATION.md): the
                    # relay is stateless, so deltas it held died with
                    # it and NOTHING on the restarted side will ask
                    # for them (a shard server replays its durable
                    # log; a relay cannot).  Resend the whole cached
                    # tail unprompted — the server deduplicates what
                    # it already applied and its duplicate-liveness
                    # rule re-issues any weights reply that was lost
                    # in flight.
                    for w in ids:
                        routers[w].resend(i, 0)
                print(("reconnected to aggregator" if aggregate else
                       f"reconnected to shard {i}") + f" ({addrs[i]})",
                      file=sys.stderr, flush=True)
            time.sleep(0.2)

    supervisor = threading.Thread(target=supervise, daemon=True,
                                  name="kps-worker-supervisor")
    supervisor.start()

    errors: list[BaseException] = []

    def worker_loop(node: WorkerNode) -> None:
        try:
            while not stop.is_set():
                msg = fabric.poll_blocking(fabric_mod.WEIGHTS_TOPIC,
                                           node.worker_id, timeout=0.1)
                if msg is not None:
                    node.on_weights(msg)
        except BaseException as e:    # pragma: no cover - diagnostics
            errors.append(e)
            stop.set()

    threads = [threading.Thread(target=worker_loop, args=(nodes[w],),
                                daemon=True, name=f"worker-{w}")
               for w in ids]
    for t in threads:
        t.start()
    stop.wait()                       # supervisor ends the run
    leftover = []
    for t in threads:
        t.join(timeout=120.0)
        if t.is_alive():
            leftover.append(t.name)
    if state_path is not None:
        from kafka_ps_tpu.utils import checkpoint as ckpt
        state_stop.set()
        # join BEFORE the final save: two concurrent save_worker calls
        # share one tmp path and would corrupt the state file
        state_saver_thread.join(timeout=60.0)
        if state_saver_thread.is_alive():
            print("warning: state saver still writing; skipping final "
                  "snapshot", file=sys.stderr, flush=True)
            leftover.append(state_saver_thread.name)
        else:
            ckpt.save_worker(state_path, buffers, run_id=run_id,
                             residuals=compressors)
    worker_log.close()
    for b in slots:
        b.close()
    supervisor.join(timeout=10.0)
    ready_thread.join(timeout=10.0)
    for t in reader_threads:
        t.join(timeout=10.0)
    for t in [supervisor, ready_thread, *reader_threads]:
        if t.is_alive():
            leftover.append(t.name)
    ops.close()                  # before any os._exit: the flight dump
    if drift_sink is not None:
        drift_sink.close()
    _dump_telemetry(args, tracer, telemetry)
    rc = 0
    if errors:
        print(f"worker failed: {errors[0]!r}", file=sys.stderr, flush=True)
        rc = 1
    if leftover:
        print(f"warning: threads still alive at exit: {leftover}; "
              "exiting without finalization", file=sys.stderr, flush=True)
        sys.stdout.flush()
        os._exit(rc)
    if errors:
        raise RuntimeError("worker failed") from errors[0]
    return 0


# -- log-following read replicas (docs/SERVING.md) ---------------------------

def run_replica(args) -> int:
    """Read-replica serving process: follow `--durable-log DIR` and
    answer T_PREDICT frames, never touching the training deployment.

    The replica tails the log strictly read-only (log/tail.py), so it
    can run against a LIVE training process's directory: read load
    scales by starting more of these, and training is provably
    unperturbed (scripts/tier1.sh --load asserts bitwise-identical
    theta with and without replica traffic).  For a `--shards N`
    deployment the replica assembles per-shard slices through
    FrontierCutPublisher and serves the full-range theta stamped with
    the frontier clock — the serving story the live sharded runtime
    itself does not offer (run_server_shard rejects --serve).
    """
    from kafka_ps_tpu.models.task import get_task
    from kafka_ps_tpu.serving.engine import PredictionEngine
    from kafka_ps_tpu.serving.replica import ReplicaFollower
    from kafka_ps_tpu.serving.snapshot import SnapshotRegistry

    root = getattr(args, "durable_log", None)
    if not root:
        raise SystemExit("--serve-replica requires --durable-log DIR "
                         "(the training deployment's commit log to "
                         "follow)")
    cfg = _make_cfg(args)
    tracer, telemetry = _make_telemetry(args)
    task = get_task(cfg.task, cfg.model)
    registry = SnapshotRegistry(
        capacity=getattr(args, "serve_snapshots", 8))
    follower = ReplicaFollower(root, registry, tracer=tracer)
    shed_ms = getattr(args, "serve_shed_ms", 0.0)
    engine = PredictionEngine(
        task, registry,
        max_batch=getattr(args, "serve_batch", 16),
        deadline_s=getattr(args, "serve_deadline_ms", 2.0) / 1000.0,
        queue_limit=getattr(args, "serve_queue", 0),
        shed_deadline_s=shed_ms / 1000.0 if shed_ms else None,
        auto=getattr(args, "serve_auto", True),
        tracer=tracer, telemetry=telemetry)
    follower.catch_up()              # cold start: serve what's logged
    ops = _make_ops(args, telemetry, role="replica")
    ops.add_replica_watchdog()
    ops.add_serving_watchdog(engine)
    ops.start()
    port = getattr(args, "serve_port", None)
    bridge = net.ServerBridge(port=0 if port is None else port,
                              run_id=time.time_ns(), tracer=tracer,
                              telemetry=telemetry,
                              shm=getattr(args, "serve_shm", False),
                              coalesce=getattr(args, "wire_coalesce",
                                               True))
    bridge.attach_serving(engine)
    follower.start()
    mode = (f"{follower.num_shards}-shard assembled"
            if follower.num_shards else "single-server")
    print(f"replica serving on port {bridge.port} "
          f"({mode} log {root}, clock {follower.clock})",
          file=sys.stderr, flush=True)
    if engine.warmup():
        print(f"replica warm at clock {follower.clock}",
              file=sys.stderr, flush=True)
    else:
        # started against an empty log: warm (compile buckets +
        # calibrate the dispatch cost model) the moment theta appears
        warmed = threading.Event()

        def _warm_on_first_publish(clock, _e=warmed):
            if not _e.is_set() and engine.warmup():
                _e.set()
                print(f"replica warm at clock {clock}",
                      file=sys.stderr, flush=True)

        follower.on_publish = _warm_on_first_publish
    try:
        # serve until killed — a replica has no natural end of run;
        # deployment manifests (deploy/k8s/replica.yaml) scale and
        # reap these processes
        duration = getattr(args, "replica_duration", None)
        if duration:
            time.sleep(float(duration))
        else:
            while True:
                time.sleep(3600.0)
    except KeyboardInterrupt:
        pass
    finally:
        follower.stop()
        engine.close()
        bridge.close()
        ops.close()
        _dump_telemetry(args, tracer, telemetry)
    return 0

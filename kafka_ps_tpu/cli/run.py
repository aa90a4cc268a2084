"""Canonical CLI — the whole streaming PS system in one process.

The reference splits server and worker into two JVMs because Kafka is
the transport (run.sh:10-18); on TPU one host process owns every device,
so this runner hosts producer + server + N logical workers together.
`cli/server_runner.py` and `cli/worker_runner.py` keep the reference's
per-role flag surfaces and delegate here.

Flags are the union of ServerAppRunner.java:19-26 and
WorkerAppRunner.java:17-24, same names and defaults; TPU-native extras
are prefixed with `--`-only long names.
"""

from __future__ import annotations

import argparse
import os
import sys



def build_parser(include_server_flags: bool = True,
                 include_worker_flags: bool = True,
                 prog: str = "kafka_ps_tpu") -> argparse.ArgumentParser:
    from kafka_ps_tpu.models.task import task_names
    p = argparse.ArgumentParser(
        prog=prog, description="TPU-native streaming parameter server")
    if include_server_flags:
        p.add_argument("-training", "--training_data_file_path",
                       default="./data/train.csv",
                       help="path to the training-data CSV "
                            "(BaseKafkaApp.java:35)")
        p.add_argument("-c", "--consistency_model", type=int, default=0,
                       help="0 sequential, k>0 bounded delay, -1 eventual")
        p.add_argument("-p", "--producer_time_per_event", type=int,
                       default=200, help="ms per produced event")
    if include_worker_flags:
        p.add_argument("-min", "--min_buffer_size", type=int, default=128)
        p.add_argument("-max", "--max_buffer_size", type=int, default=1024)
        p.add_argument("-bc", "--buffer_size_coefficient", type=float,
                       default=0.3)
    p.add_argument("-test", "--test_data_file_path",
                   default="./data/test.csv",
                   help="path to the test-data CSV (BaseKafkaApp.java:36)")
    p.add_argument("-v", "--verbose", action="store_true",
                   help="print the parameters that are used")
    p.add_argument("-r", "--remote", action="store_true",
                   help="distributed mode: join the multi-host job "
                        "(parallel/multihost.py; KPS_* env vars) and run "
                        "the fused BSP step over the global device mesh — "
                        "the reference's remote-Kafka-broker role "
                        "(ServerAppRunner.java:63)")
    p.add_argument("-l", "--logging", action="store_true",
                   help="write performance logs to ./logs-server.csv / "
                        "./logs-worker.csv instead of stdout")
    # TPU-native extras
    p.add_argument("--num_workers", type=int, default=4,
                   help="logical workers (reference hardcodes 4, "
                        "BaseKafkaApp.java:25)")
    p.add_argument("--num_features", type=int, default=1024)
    p.add_argument("--num_classes", type=int, default=5)
    p.add_argument("--task", choices=task_names(), default="logreg",
                   help="model family (models/task.py registry); logreg "
                        "is the reference's task")
    p.add_argument("--hidden_dim", type=int, default=128,
                   help="hidden width of the mlp task")
    p.add_argument("--model_json", default=None,
                   help="the model family's own configuration file "
                        "(a language-model family, --task "
                        # the two classifiers come first and have no file
                        + ", ".join(task_names()[2:]) + ": the published "
                        "keys and the cut held here, models/lm_common.py); a "
                        "relative path is taken from the repository's root")
    p.add_argument("--local_iterations", type=int, default=2,
                   help="k local solver steps per iteration "
                        "(numMaxIter, LogisticRegressionTaskSpark.java:35)")
    p.add_argument("--local_learning_rate", type=float, default=0.5)
    p.add_argument("--eval_every", type=int, default=1,
                   help="evaluate test metrics every Nth vector clock "
                        "(1 = the reference's every-iteration cadence, "
                        "LogisticRegressionTaskSpark.java:186; larger "
                        "values trade metric resolution for throughput "
                        "— eval dominates per-node wall-clock)")
    p.add_argument("--eval-async", dest="eval_async", action="store_true",
                   default=True,
                   help="async coalescing eval engine (default ON, "
                        "evaluation/engine.py): test-set evaluation "
                        "leaves the server's apply critical path — a "
                        "dedicated thread coalesces pending (theta, "
                        "clock) snapshots into batched vmap dispatches "
                        "and emits the SAME CSV rows in clock order "
                        "(equal to the fused path's to float32 "
                        "tolerance, docs/EVALUATION.md)")
    p.add_argument("--no-eval-async", dest="eval_async",
                   action="store_false",
                   help="fuse evaluation back into the apply dispatch "
                        "(the pre-engine behaviour; the bitwise "
                        "reference of tests/test_eval_engine.py)")
    p.add_argument("--max_iterations", type=int, default=0,
                   help="stop after this many server iterations "
                        "(0 = run until Ctrl-C, like the reference)")
    p.add_argument("--fused", action="store_true",
                   help="sequential model as fused shard_map steps "
                        "(TPU fast path)")
    p.add_argument("--param_shards", type=int, default=1,
                   help="with --fused: shard the parameter vector over "
                        "this many devices (2-D workers x params mesh — "
                        "the reference's latent KeyRange axis, "
                        "messages/KeyRange.java, parallel/range_sharded.py)")
    p.add_argument("--status_every", type=float, default=0.0,
                   metavar="SECONDS",
                   help="emit a [status] line to stderr every N seconds "
                        "(iters/s, per-worker clocks, membership, queue "
                        "depths, buffer fill) — the live-observability "
                        "stand-in for the reference's Confluent Control "
                        "Center UI (utils/status.py; 0 = off)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="write a Chrome trace-event JSON (spans + message "
                        "counters) on exit and print span stats — replaces "
                        "the reference's Confluent monitoring interceptors")
    p.add_argument("--metrics-file", dest="metrics_file", default=None,
                   metavar="PATH",
                   help="enable the metrics registry "
                        "(kafka_ps_tpu/telemetry/) and write a "
                        "Prometheus-style text dump of every counter/"
                        "gauge/histogram family to PATH at exit (and "
                        "every --metrics-every seconds); also folds a "
                        "flat metrics summary into each [status] line")
    p.add_argument("--metrics-every", dest="metrics_every", type=float,
                   default=0.0, metavar="SECONDS",
                   help="with --metrics-file: rewrite the dump every N "
                        "seconds (atomic replace; 0 = only at exit)")
    p.add_argument("--flight-dir", dest="flight_dir", default=None,
                   metavar="DIR",
                   help="enable the always-on flight recorder "
                        "(telemetry/flight.py, docs/OBSERVABILITY.md): "
                        "per-thread rings of structured events (gate "
                        "decisions, queue depths, frame sends, fsyncs, "
                        "snapshot publishes) dumped atomically to "
                        "DIR/flightdump-<pid>.json on SIGTERM/SIGABRT/"
                        "fatal signals, on watchdog trips, and at clean "
                        "exit; `python -m kafka_ps_tpu.telemetry "
                        "postmortem DIR` merges dumps across processes "
                        "and names the culprit")
    p.add_argument("--health-port", dest="health_port", type=int,
                   default=None, metavar="PORT",
                   help="serve the health/introspection plane on this "
                        "port (0 = ephemeral, printed to stderr): "
                        "/healthz watchdog-derived liveness/readiness "
                        "(the k8s probe target, deploy/k8s/*.yaml), "
                        "/varz Prometheus metrics snapshot, /flightz "
                        "recent flight-ring tail, /profilez collapsed "
                        "stacks when --profile is armed")
    p.add_argument("--profile", action="store_true",
                   help="arm the continuous sampling profiler "
                        "(telemetry/profiler.py, ~100 Hz stdlib stack "
                        "sampler, docs/OBSERVABILITY.md): collapsed-"
                        "stack text on /profilez (--health-port) and "
                        "the hottest stacks in every flight dump, so a "
                        "watchdog trip ships its own profile; under "
                        "2%% of a CPU-host run at PR 14, not measured "
                        "on the chip")
    p.add_argument("--slo-serving-p99-ms", dest="slo_serving_p99_ms",
                   type=float, default=None, metavar="MS",
                   help="arm the SLO plane (telemetry/slo.py) with a "
                        "serving-latency objective: 99%% of requests "
                        "answered within MS.  Burn rates over 5min/1h "
                        "windows export as slo_burn_rate gauges, ride "
                        "/healthz, and a sustained fast-window burn "
                        "trips a flight dump (serving availability is "
                        "always tracked once any --slo-* flag is set)")
    p.add_argument("--slo-freshness-ms", dest="slo_freshness_ms",
                   type=float, default=None, metavar="MS",
                   help="arm the SLO plane with a snapshot-freshness "
                        "objective: 99%% of served reads see a snapshot "
                        "younger than MS (snapshot_age_ms histogram; "
                        "same burn-rate windows and watchdog as "
                        "--slo-serving-p99-ms)")
    p.add_argument("--model-health", dest="model_health",
                   action="store_true",
                   help="arm the model-health plane (telemetry/"
                        "modelhealth.py, docs/OBSERVABILITY.md): per-"
                        "update delta norms + aggregate-direction "
                        "cosine + per-worker contribution accounting, "
                        "plus online drift detection over the streaming "
                        "eval metrics and sampled arrivals (telemetry/"
                        "drift.py).  Surfaces on /modelz, the [status] "
                        "heartbeat, and a latched DRIFT ships one "
                        "flight dump; under 2%% of a CPU-host run at "
                        "PR 15, not measured on the chip")
    p.add_argument("--drift-detector", dest="drift_detector",
                   choices=["ph", "adwin"], default="ph",
                   help="drift detector for --model-health: ph (Page-"
                        "Hinkley, directional mean-shift, the default) "
                        "or adwin (windowed adaptive cut, shift-"
                        "direction agnostic)")
    p.add_argument("--drift-threshold", dest="drift_threshold",
                   type=float, default=None, metavar="T",
                   help="detector trip threshold override (default: "
                        "the detector's own calibration; ph "
                        "statistic > T trips, adwin gap/bound > T)")
    p.add_argument("--device_trace", default=None, metavar="LOGDIR",
                   help="capture a jax.profiler device trace (TensorBoard "
                        "logdir) for the whole run")
    p.add_argument("--compress", default="none", metavar="CODEC",
                   help="compressed delta transport "
                        "(kafka_ps_tpu/compress/, docs/COMPRESSION.md): "
                        "none | bf16 | int8 | topk:<ratio>.  Applied "
                        "symmetrically — server->worker weights are "
                        "quantize-dequantized, worker->server deltas go "
                        "through per-worker error-feedback residuals.  "
                        "In socket mode both processes must name the "
                        "same codec (negotiated on HELLO; mismatches "
                        "fall back to none).  Incompatible with --fused")
    p.add_argument("--slab-dtype", dest="slab_dtype",
                   choices=["f32", "bf16", "int8"], default="f32",
                   help="storage precision of each worker's "
                        "device-resident training slab (compress/slab.py, "
                        "docs/PERFORMANCE.md): bf16 halves and int8 "
                        "(per-row max-abs scales) quarters the bytes the "
                        "training step streams from HBM; decode is fused "
                        "into the solver.  f32 is bitwise-identical to a "
                        "build without the flag.  Incompatible with "
                        "--fused (its BSP step keeps its own slab cache)")
    p.add_argument("--full-slab-upload", action="store_true",
                   dest="full_slab_upload",
                   help="disable incremental device-slab updates: "
                        "re-upload the whole slab whenever the buffer "
                        "changes instead of scattering only dirty rows "
                        "(the pre-PERFORMANCE.md behavior; the bitwise "
                        "reference of tests/test_slab.py)")
    p.add_argument("--tier-hot-bytes", dest="tier_hot_bytes", type=int,
                   default=0, metavar="BYTES",
                   help="tiered parameter residency (kafka_ps_tpu/store/, "
                        "docs/TIERING.md): cap the device-resident (hot) "
                        "tier of the server's parameter vector at BYTES; "
                        "overflow pages live in pinned host RAM (warm).  "
                        "0 = unbounded, today's fully-resident behavior.  "
                        "Capped runs stay bitwise-identical — they only "
                        "bound resident bytes.  Per process; split evenly "
                        "across in-process shards.  Incompatible with "
                        "--fused")
    p.add_argument("--tier-warm-bytes", dest="tier_warm_bytes", type=int,
                   default=0, metavar="BYTES",
                   help="cap the host-RAM (warm) tier at BYTES; overflow "
                        "pages demote to CRC-framed records in the commit "
                        "log and fault back in on demand — requires "
                        "--durable-log (the cold partition lives under "
                        "it).  0 = unbounded")
    p.add_argument("--tier-page-params", dest="tier_page_params", type=int,
                   default=1024, metavar="KEYS",
                   help="keys per residency page (the promotion/demotion "
                        "unit; must match across checkpoint resumes)")
    p.add_argument("--no-gang", action="store_true", dest="no_gang",
                   help="disable gang-scheduled dispatch: process every "
                        "gate release as its own device step instead of "
                        "coalescing simultaneous releases into one "
                        "batched step (runtime/gang.py, "
                        "docs/GANG_DISPATCH.md)")
    p.add_argument("--failure_policy", choices=["halt", "rebalance"],
                   default="halt",
                   help="threaded mode: evict crashed/hung workers and "
                        "continue on the survivors (rebalance), or stop "
                        "the run (halt)")
    p.add_argument("--heartbeat_timeout", type=float, default=None,
                   help="threaded+rebalance: seconds without worker "
                        "progress (with work pending) before eviction")
    p.add_argument("--mode", choices=["threaded", "serial"],
                   default="threaded")
    p.add_argument("--checkpoint", default=None,
                   help="path to save/restore parameters "
                        "(improvement over the reference's cold start)")
    p.add_argument("--checkpoint_every", type=int, default=50,
                   help="server iterations between checkpoint saves")
    p.add_argument("--durable-log", dest="durable_log", default=None,
                   metavar="DIR",
                   help="persist every WEIGHTS/GRADIENTS/INPUT_DATA "
                        "message to a segmented commit log under DIR "
                        "(kafka_ps_tpu/log/ — the reference's Kafka "
                        "broker durability); on restart the run replays "
                        "the unconsumed tail past the last checkpoint's "
                        "committed offsets (docs/DURABILITY.md)")
    p.add_argument("--fsync", choices=["none", "interval", "always"],
                   default="interval",
                   help="--durable-log fsync policy: page-cache only / "
                        "at most once per second / every append "
                        "(log/log.py)")
    # -- online serving plane (kafka_ps_tpu/serving/, docs/SERVING.md) --
    p.add_argument("--serve", action="store_true",
                   help="serve predictions while training: the server "
                        "publishes a weights snapshot at every "
                        "consistency-gate release and a micro-batching "
                        "engine answers staleness-bounded reads against "
                        "the newest one (never blocks training)")
    p.add_argument("--serve_port", type=int, default=None, metavar="PORT",
                   help="with --serve: also accept T_PREDICT frames on "
                        "this TCP port (0 = ephemeral; the bound port is "
                        "printed to stderr).  Omit for in-process-only "
                        "serving")
    p.add_argument("--serve_batch", type=int, default=16,
                   help="serving micro-batch size cap (one jit shape; "
                        "the gang-dispatch analogue for reads)")
    p.add_argument("--serve_deadline_ms", type=float, default=2.0,
                   help="max milliseconds a prediction waits for its "
                        "micro-batch to fill")
    p.add_argument("--serve_snapshots", type=int, default=8,
                   help="snapshot ring capacity (exact-clock audit reads)")
    p.add_argument("--serve-queue", dest="serve_queue", type=int, default=0,
                   metavar="N",
                   help="admission control: max outstanding admitted "
                        "requests PER MODEL before the engine sheds with "
                        "a typed Overloaded rejection (0 = unbounded, "
                        "the pre-admission-control behaviour)")
    p.add_argument("--serve-shed", dest="serve_shed_ms", type=float,
                   default=0.0, metavar="MS",
                   help="predictive shedding: reject a request whose "
                        "estimated queueing delay (EWMA batch service "
                        "time x queued batches) exceeds MS milliseconds "
                        "(0 = off)")
    p.add_argument("--serve-auto", dest="serve_auto", action="store_true",
                   default=True,
                   help="adaptive dispatch (default ON): the engine "
                        "learns per-model dispatch cost vs occupancy, "
                        "bypasses the batching queue below the measured "
                        "break-even, and sizes the batch window from the "
                        "live arrival rate (docs/SERVING.md, 'Dispatch "
                        "economics')")
    p.add_argument("--no-serve-auto", dest="serve_auto",
                   action="store_false",
                   help="disable adaptive dispatch: always micro-batch "
                        "with the full configured window (the pre-cost-"
                        "model behaviour)")
    p.add_argument("--serve-shm", dest="serve_shm", action="store_true",
                   help="offer co-located PredictClients a shared-memory "
                        "fast path (skips TCP framing); remote or legacy "
                        "clients fall back to sockets transparently")
    p.add_argument("--wire-coalesce", dest="wire_coalesce",
                   action="store_true", default=True,
                   help="frame coalescing on socket bridges (default ON): "
                        "sends queue behind a per-connection writer "
                        "thread that ships every queued frame in one "
                        "scatter-gather sendmsg; receives parse all "
                        "complete frames per recv_into chunk "
                        "(docs/WIRE.md)")
    p.add_argument("--no-wire-coalesce", dest="wire_coalesce",
                   action="store_false",
                   help="disable frame coalescing: one sendall per frame "
                        "under the connection lock (the pre-wire-engine "
                        "behaviour; byte stream is identical either way)")
    return p


def load_test_csv(path: str, num_features: int):
    """Test set: dense CSV with header, label in the last column
    (LogisticRegressionTaskSpark.java:77-92)."""
    from kafka_ps_tpu.data.stream import load_csv_dataset
    x, y = load_csv_dataset(path)
    if x.shape[1] != num_features:
        raise SystemExit(
            f"test CSV has {x.shape[1] + 1} columns, expected "
            f"{num_features + 1} (features + label)")
    return x, y


# the levers a task's rows or size cannot go through, by flag: what
# `args` holds when the lever is off, and why the task cannot hold it
_PAGES_A_CLASSIFIER = ("tiered residency pages a flat classifier theta "
                       "(kafka_ps_tpu/store/)")
_NO_MESH = ("its workers are folded one at a time on one device; it has "
            "no program over a mesh (parallel/bsp.py)")


def _token_rows(task) -> bool:
    import numpy as np
    return np.dtype(task.row_dtype).kind == "i"


# (what a task says of itself, models/task.py; the levers it then
# refuses: what `args` holds when the lever is off, and why)
TASK_REFUSES = (
    # a family with a file of its own
    (lambda task: task.model_file, {
        "compress": ("none", "the wire codecs were sized for deltas of "
                             "megabytes, and this family's delta does not "
                             "cross serde in one message"),
        "tier_hot_bytes": (0, _PAGES_A_CLASSIFIER),
        "tier_warm_bytes": (0, _PAGES_A_CLASSIFIER)}),
    # rows that are tokens
    (_token_rows, {
        "slab_dtype": ("f32", "its rows are int32 tokens, stored as they "
                              "are (compress/slab.py)")}),
    # no program over a mesh
    (lambda task: not task.batches_workers, {
        "remote": (False, _NO_MESH),
        "param_shards": (1, _NO_MESH)}))


def refuse_levers(args) -> None:
    """One message, with the reason, for every lever asked for that
    `--task` cannot hold — before any program is built.  What a task
    cannot hold follows from what its family says of itself, never from
    its name."""
    from kafka_ps_tpu.models.task import task_class
    task = task_class(args.task)
    asked = [(flag, why) for says, levers in TASK_REFUSES if says(task)
             for flag, (off, why) in levers.items()
             if (getattr(args, flag, off) or off) != off]
    if asked:
        raise SystemExit(
            f"--task {args.task} cannot run with "
            + "; ".join(f"--{flag.replace('_', '-')}: {why}"
                        for flag, why in asked))
    if task.model_file and not args.model_json:
        raise SystemExit(f"--task {args.task} needs --model_json FILE, "
                         "the family's own configuration")
    if args.model_json and not task.model_file:
        raise SystemExit(f"--model_json configures a family with a file of "
                         f"its own; --task {args.task} has no file of its "
                         "own")


def cfg_from_args(args):
    from kafka_ps_tpu.utils.config import (BufferConfig, ModelConfig,
                                           PSConfig, ServingConfig,
                                           StreamConfig, TierConfig)
    refuse_levers(args)
    return PSConfig(
        num_workers=args.num_workers,
        consistency_model=args.consistency_model,
        task=args.task,
        model=ModelConfig(num_features=args.num_features,
                          num_classes=args.num_classes,
                          num_max_iter=args.local_iterations,
                          local_learning_rate=args.local_learning_rate,
                          hidden_dim=args.hidden_dim,
                          model_json=args.model_json),
        buffer=BufferConfig(min_size=args.min_buffer_size,
                            max_size=args.max_buffer_size,
                            coefficient=args.buffer_size_coefficient),
        stream=StreamConfig(time_per_event_ms=args.producer_time_per_event),
        eval_every=getattr(args, "eval_every", 1),
        eval_async=getattr(args, "eval_async", True),
        use_gang=not getattr(args, "no_gang", False),
        compress=getattr(args, "compress", "none") or "none",
        slab_dtype=getattr(args, "slab_dtype", "f32") or "f32",
        slab_incremental=not getattr(args, "full_slab_upload", False),
        serving=ServingConfig(
            enabled=getattr(args, "serve", False),
            port=getattr(args, "serve_port", None),
            max_batch=getattr(args, "serve_batch", 16),
            deadline_ms=getattr(args, "serve_deadline_ms", 2.0),
            ring_capacity=getattr(args, "serve_snapshots", 8),
            queue_limit=getattr(args, "serve_queue", 0),
            shed_deadline_ms=getattr(args, "serve_shed_ms", 0.0),
            auto=getattr(args, "serve_auto", True),
            shm=getattr(args, "serve_shm", False)),
        tier=TierConfig(
            hot_bytes=getattr(args, "tier_hot_bytes", 0),
            warm_bytes=getattr(args, "tier_warm_bytes", 0),
            page_params=getattr(args, "tier_page_params", 1024)),
    )


def make_app_from_args(args, resuming: bool = False,
                       process_index: int = 0):
    """`process_index` > 0 (a non-coordinator host of a multi-process
    job) writes no server log and a process-suffixed worker log — one
    writer per file on a shared filesystem (deploy/README.md)."""
    from kafka_ps_tpu.runtime.app import StreamingPSApp
    from kafka_ps_tpu.utils.csvlog import (CsvLogSink, NullLogSink,
                                           SERVER_HEADER, WORKER_HEADER)

    cfg = cfg_from_args(args)
    # a row's width and dtype are the task's (token rows come as the
    # same CSV, one token a column and a label column that is ignored)
    from kafka_ps_tpu.models.task import get_task
    task = get_task(cfg.task, cfg.model)
    test_x, test_y = load_test_csv(args.test_data_file_path,
                                   task.row_width)
    test_x = test_x.astype(task.row_dtype)
    suffix = f".p{process_index}" if process_index else ""
    if process_index == 0:
        server_log = CsvLogSink(
            "./logs-server.csv" if args.logging else None,
            SERVER_HEADER, append=resuming)
    else:
        # a CsvLogSink(None) falls back to stdout (the reference's
        # default); non-coordinator processes must write NO server log
        server_log = NullLogSink()
    worker_log = CsvLogSink(
        f"./logs-worker{suffix}.csv" if args.logging else None,
        WORKER_HEADER, append=resuming)
    tracer = None
    if getattr(args, "trace", None):
        from kafka_ps_tpu.utils.trace import Tracer
        tracer = Tracer()
    from kafka_ps_tpu.telemetry import maybe_telemetry
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
    fabric = None
    if getattr(args, "durable_log", None):
        from kafka_ps_tpu.log import DurableFabric, LogConfig
        fabric = DurableFabric(
            args.durable_log,
            LogConfig(fsync=getattr(args, "fsync", "interval")),
            tracer=tracer, telemetry=telemetry)
    app = StreamingPSApp(cfg, test_x=test_x, test_y=test_y,
                         server_log=server_log, worker_log=worker_log,
                         tracer=tracer, fabric=fabric, telemetry=telemetry)
    return app, (server_log, worker_log)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return run_with_args(args)


def apply_platform_env() -> None:
    """Start-up hook shared by every CLI entry (this runner and the
    socket roles, cli/socket_mode.py), run before first backend use:
    KPS_PLATFORM pins the JAX platform for deployments that set the
    program's own variable (deploy/: =cpu for a broker-less smoke run
    or a CPU-mesh CI job — plain JAX_PLATFORMS works the same), the
    persistent compile cache is placed and the start-up record's
    listeners for `jax.monitoring`'s build events stand (utils/device.py:
    the record's `import` phase ends and its `backend` phase begins
    where this returns)."""
    from kafka_ps_tpu.utils import device
    platform = os.environ.get("KPS_PLATFORM")
    if platform:
        import jax
        jax.config.update("jax_platforms", platform)
    device.configure_compile_cache()
    device.env_ready()


def announce_device(cfg, fused: bool = False) -> None:
    """The ONE start-up line every entry point prints to stderr: where
    the process runs (platform, device_kind, count — first backend use
    happens here), the stack versions, the compile cache, and which
    solver programs the run dispatches: the per-node path's ("xla") or
    the fused BSP step's ("fused-bsp").  `cfg` is what every caller
    holds here (the roles, benchmark/run.py); nothing of it is printed."""
    from kafka_ps_tpu.utils import device
    print(device.startup_line(solver="fused-bsp" if fused else "xla"),
          file=sys.stderr, flush=True)


def run_with_args(args) -> int:
    apply_platform_env()
    if getattr(args, "eval_every", 1) < 1:
        raise SystemExit("--eval_every must be >= 1")
    if getattr(args, "param_shards", 1) > 1 and not args.fused:
        raise SystemExit("--param_shards requires --fused (the "
                         "range-sharded server is a fused-mesh mode)")
    if getattr(args, "serve_port", None) is not None \
            and not getattr(args, "serve", False):
        raise SystemExit("--serve_port requires --serve")
    if getattr(args, "slab_dtype", "f32") != "f32" and args.fused:
        # the fused BSP step (runtime/app.run_fused_bsp) keeps its own
        # whole-slab device cache outside the worker SlabStore path —
        # silently ignoring the dtype would misreport what ran
        raise SystemExit(
            "--slab-dtype applies to the per-node worker slab "
            "(compress/slab.py); the --fused BSP path keeps its own "
            "slab cache — drop one of the two flags")
    tier_hot = getattr(args, "tier_hot_bytes", 0)
    tier_warm = getattr(args, "tier_warm_bytes", 0)
    if tier_hot < 0 or tier_warm < 0:
        raise SystemExit("--tier-*-bytes caps must be >= 0")
    if (tier_hot or tier_warm) and args.fused:
        # the fused BSP step owns theta inside its shard_map program —
        # paged residency has no seam there; silently ignoring the caps
        # would misreport what ran
        raise SystemExit(
            "--tier-hot-bytes/--tier-warm-bytes apply to the per-node "
            "server (kafka_ps_tpu/store/); the --fused BSP path keeps "
            "theta inside its mesh program — drop one of the two flags")
    if tier_warm and not getattr(args, "durable_log", None):
        raise SystemExit(
            "--tier-warm-bytes demotes pages to commit-log records; "
            "run with --durable-log DIR so the cold partition has a "
            "home (docs/TIERING.md)")
    if getattr(args, "tier_page_params", 1024) < 1:
        raise SystemExit("--tier-page-params must be >= 1")
    compress = getattr(args, "compress", "none") or "none"
    if compress != "none":
        from kafka_ps_tpu.compress.wire import parse_codec
        try:
            parse_codec(compress)
        except ValueError as e:
            raise SystemExit(f"--compress: {e}") from None
        if args.fused:
            # the fused BSP step moves deltas through shard_map
            # collectives that never cross a serde boundary — there is
            # no wire to compress, and silently ignoring the flag would
            # misreport what ran
            raise SystemExit(
                "--compress applies to the message transport (per-node "
                "and socket modes); the --fused collectives never cross "
                "a serde boundary — drop one of the two flags")
    distributed = False
    if args.remote:
        from kafka_ps_tpu.parallel import multihost
        # join the job BEFORE building the app: process identity gates
        # the log sinks and checkpoint writer below
        distributed = multihost.initialize()
        if distributed and getattr(args, "durable_log", None):
            # the commit log is single-writer per partition; a
            # multi-host job would need per-host roots + a replicated
            # offsets store (ROADMAP)
            raise SystemExit(
                "--durable-log is single-process; a multi-host job "
                "must run without it (use --checkpoint for resume)")
        if distributed and not args.fused:
            # only the fused BSP step runs over the global mesh; the
            # host-orchestrated modes are single-host by design
            # (deploy/README.md)
            raise SystemExit(
                "-r joined a multi-host job but only --fused runs over "
                "the global mesh; add --fused (or run the async "
                "consistency modes single-host)")
        # unconfigured: behave like the reference's remote flag on a
        # local run — nothing to switch (ServerAppRunner.java:63)
    if args.verbose:
        print("\nUsed parameter:")
        for k, v in sorted(vars(args).items()):
            print(f"    {k}: {v}")
    announce_device(cfg_from_args(args), fused=args.fused)
    # the profiler's session opens before the app is built: the
    # start-up phase `kps.setup.app_init` and the first call's builds
    # then lie on the host plane of the same trace as the device's
    # operations (docs/OBSERVABILITY.md "One clock"); whatever way the
    # run ends, the session is stopped and its trace written
    from kafka_ps_tpu.utils.trace import device_trace
    with device_trace(args.device_trace):
        return _run_announced(args, distributed, tier_hot, tier_warm)


def _run_announced(args, distributed: bool, tier_hot: int,
                   tier_warm: int) -> int:
    """`run_with_args` from the `[device]` line on: the app built, the
    feed started, the drive call and the teardown."""
    process_index = 0
    if distributed:
        import jax
        process_index = jax.process_index()
    resuming = bool(args.checkpoint and os.path.exists(args.checkpoint))
    app, logs = make_app_from_args(args, resuming=resuming,
                                   process_index=process_index)

    # membership/resume events persist incrementally (one writer per
    # job): an end-of-run dump would lose the auditor's record on a
    # crash — the exact case the events segment elastic logs for
    from kafka_ps_tpu.utils.csvlog import (CsvLogSink as _Sink,
                                           NullLogSink as _Null,
                                           EVENTS_HEADER)
    events_log = (_Sink("./logs-events.csv", EVENTS_HEADER,
                        append=resuming)
                  if (args.logging and process_index == 0) else _Null())
    app.server.membership_log = events_log
    logs = [*logs, events_log]

    if tier_hot or tier_warm:
        # attach BEFORE the checkpoint restore below so the restore can
        # re-apply the recorded tier residency (utils/checkpoint.py)
        if distributed:
            raise SystemExit(
                "--tier-*-bytes is single-process (residency is a "
                "per-process resource; multi-host runs are --fused)")
        from kafka_ps_tpu.log.durable_fabric import COLD_PARTITION_DIR
        cold_dir = (os.path.join(args.durable_log, COLD_PARTITION_DIR)
                    if getattr(args, "durable_log", None) else None)
        app.enable_tiering(cold_dir)

    if args.checkpoint:
        from kafka_ps_tpu.utils import checkpoint as ckpt
        # single-process runs fold every worker's buffer into the
        # checkpoint (the durable training window); in a multi-host job
        # buffers are fed process-locally, so the coordinator's copies
        # of remote workers' buffers would be empty lies — skip them
        ckpt_buffers = app.buffers if not distributed else None
        restored = ckpt.maybe_restore(args.checkpoint, app.server,
                                      buffers=ckpt_buffers,
                                      residuals=app.compressors or None)
        if restored and args.verbose:
            print(f"    restored checkpoint at iteration "
                  f"{app.server.iterations}")
        if process_index == 0:   # one checkpoint writer per job
            app.server.checkpoint_path = args.checkpoint
            app.server.checkpoint_every = args.checkpoint_every
            app.server.checkpoint_buffers = ckpt_buffers

    if getattr(args, "durable_log", None):
        # replay the unconsumed tail past the restored checkpoint's
        # offsets (or the committed ones) BEFORE the producer starts:
        # recovery re-enqueues in-flight weights/gradients, refills the
        # buffers' post-checkpoint rows, and arms the re-ingestion skip
        counts = app.recover_durable()
        if args.verbose:
            print(f"    durable-log replay: {counts}")

    serve_bridge = None
    serve_engine = None
    if getattr(args, "serve", False):
        if distributed:
            raise SystemExit(
                "--serve is single-process: the serving plane reads the "
                "server's snapshot registry in-process (run a dedicated "
                "serving host against the checkpoint instead)")
        engine = serve_engine = app.enable_serving()
        # cold start (docs/SERVING.md): the restored (or fresh) theta is
        # servable before the first gate release...
        app.server.publish_snapshot()
        if getattr(args, "durable_log", None):
            # ...and when the durable log holds RELEASED weights strictly
            # ahead of the restored stable clock, publish those too —
            # readers immediately see everything the dead process had
            # already promised to some worker
            latest = app.fabric.latest_logged_weights()
            if (latest is not None
                    and latest.vector_clock > app.server.serving_clock()):
                app.server.publish_snapshot(latest.values,
                                            latest.vector_clock)
        if getattr(args, "serve_port", None) is not None:
            from kafka_ps_tpu.runtime import net
            serve_bridge = net.ServerBridge(port=args.serve_port,
                                            run_id=app.server.run_id,
                                            tracer=app.tracer,
                                            telemetry=app.telemetry,
                                            shm=getattr(args, "serve_shm",
                                                        False))
            serve_bridge.attach_serving(engine)
            print(f"serving on port {serve_bridge.port}",
                  file=sys.stderr, flush=True)

    # mesh + data-partition assignment come AFTER checkpoint restore: a
    # restored checkpoint can carry evictions, and both the divisibility
    # check and the local-worker filter must see the real membership
    mesh = None
    param_shards = getattr(args, "param_shards", 1)
    if param_shards > 1:
        if distributed:
            raise SystemExit("--param_shards is single-process (drop the "
                             "KPS_* multi-process env, or use plain -r)")
        import jax

        from kafka_ps_tpu.parallel import mesh as mesh_mod
        n_dev = len(jax.devices())
        if n_dev % param_shards != 0:
            raise SystemExit(
                f"--param_shards {param_shards} must divide the device "
                f"count {n_dev}")
        mesh = mesh_mod.worker_param_mesh(n_dev // param_shards,
                                          param_shards)
        active = app.server.tracker.active_workers
        if len(active) % mesh.devices.size != 0:
            raise SystemExit(
                f"{len(active)} active workers must be a multiple of "
                f"the {mesh.devices.size}-device mesh (workers shard "
                "over both mesh axes)")
    elif args.fused and args.remote:
        from kafka_ps_tpu.parallel import multihost
        mesh = multihost.global_worker_mesh()
        active = app.server.tracker.active_workers
        if len(active) % mesh.devices.size != 0:
            raise SystemExit(
                f"{len(active)} active workers must be a "
                f"multiple of the {mesh.devices.size}-device "
                f"mesh in --remote mode")
        if distributed:
            local_pos = multihost.local_worker_ids(len(active), mesh)
            app.local_workers = {active[i] for i in local_pos}

    # flight recorder + watchdogs + health plane (docs/OBSERVABILITY.md)
    # — wired unconditionally; inert unless --flight-dir/--health-port
    from kafka_ps_tpu.telemetry.health import OpsPlane
    from kafka_ps_tpu.telemetry.modelhealth import \
        plane_from_args as modelhealth_from_args
    from kafka_ps_tpu.telemetry.registry import model_name
    from kafka_ps_tpu.telemetry.slo import plane_from_args
    # model-health plane (--model-health): the server's apply path
    # feeds it, buffers feed its feature sketch, OpsPlane owns its
    # sampler thread + the armed drift watchdog.  The drift CSV sink
    # stamps wall-clock time HERE — the monitor emits clock-free rows
    # (PS104 keeps telemetry/drift.py replay-pure).
    drift_sink = None
    drift_log = None
    if getattr(args, "model_health", False) and getattr(args, "logging",
                                                        False):
        import time as _time
        from kafka_ps_tpu.utils.csvlog import DRIFT_HEADER
        drift_sink = _Sink("./logs-drift.csv", DRIFT_HEADER)
        drift_log = (lambda rest:
                     drift_sink(f"{int(_time.time() * 1000)};{rest}"))
    modelhealth = modelhealth_from_args(
        args, app.telemetry,
        num_features=app.cfg.model.num_features,
        model=model_name(app.cfg.consistency_model), log=drift_log)
    if modelhealth is not None:
        app.server.attach_model_health(modelhealth)
        for b in app.buffers:
            b.attach_drift(modelhealth.drift)
    ops = OpsPlane(flight_dir=getattr(args, "flight_dir", None),
                   health_port=getattr(args, "health_port", None),
                   telemetry=app.telemetry, role="run",
                   profile=getattr(args, "profile", False),
                   slo_plane=plane_from_args(args, app.telemetry),
                   modelhealth=modelhealth)
    ops.add_gate_watchdog(app.server)
    if getattr(args, "durable_log", None):
        ops.add_fsync_watchdog()
    if serve_engine is not None:
        ops.add_serving_watchdog(serve_engine)
    if app.eval_engine is not None:
        ops.add_eval_engine(app.eval_engine)   # /evalz detail row
    ops.start()

    metrics_file = getattr(args, "metrics_file", None)
    if metrics_file and getattr(args, "metrics_every", 0.0) > 0:
        # periodic Prometheus-style dump (atomic replace) so an external
        # scraper/tail can watch a long run; the exit path below writes
        # the final state either way
        app.telemetry.start_dumper(metrics_file, args.metrics_every)

    producer = app.make_producer(args.training_data_file_path)
    producer.run_in_background()
    app.wait_for_prefill(min_per_worker=1, timeout=120.0)
    app.wait_for_stream_settle(producer)

    max_iters = args.max_iterations or sys.maxsize
    try:
        status_every = getattr(args, "status_every", 0.0)
        if args.fused:
            app.run_fused_bsp(max_server_iterations=max_iters,
                              mesh=mesh, status_every=status_every)
        elif args.mode == "serial":
            app.run_serial(max_server_iterations=max_iters,
                           pump=lambda: None,
                           status_every=status_every)
        else:
            app.run_threaded(max_server_iterations=max_iters,
                             failure_policy=args.failure_policy,
                             heartbeat_timeout=args.heartbeat_timeout,
                             status_every=status_every)
    except KeyboardInterrupt:
        print("interrupted — shutting down", file=sys.stderr)
        app.stop()
    finally:
        # teardown discipline (docs/TESTING.md): join every thread that
        # can touch native code BEFORE interpreter finalization — the
        # producer sinks rows into numpy slabs and the deferred-log
        # drain threads dispatch device fetches
        producer.stop()
        # serving teardown: close the socket endpoint FIRST (stops new
        # requests), then the engine's batcher thread (holds jit'd
        # callables — joined before interpreter exit)
        if serve_bridge is not None:
            serve_bridge.close()
        app.close_serving()
        # ops plane after serving, before the logs: the final flight
        # dump still sees live telemetry and a coherent ring
        ops.close()
        if args.checkpoint and process_index == 0:
            # routed through the server so a durable fabric commits the
            # offsets this final snapshot covers (a commit point)
            app.server.save_checkpoint_now()
        # AFTER the final checkpoint: saving assembles theta, which may
        # fault cold pages and needs the cold log still open
        app.close_tiering()
        if getattr(args, "durable_log", None):
            app.fabric.close()
        app.close_logs()
        for log in logs:
            log.close()
        if drift_sink is not None:
            # after ops.close(): the plane's final drain may still emit
            # a verdict row
            drift_sink.close()
        if metrics_file:
            app.telemetry.stop_dumper()
            app.telemetry.write_prometheus(metrics_file)
        if args.trace:
            import json as _json
            print(app.tracer.dump(args.trace), file=sys.stderr)
            print(_json.dumps({"spans": app.tracer.span_stats(),
                               "counters": app.tracer.counters()},
                              indent=2), file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Configuration — the reference's three config tiers collapsed into dataclasses.

Mirrors the hardcoded constants and CLI defaults of the reference
(BaseKafkaApp.java:25-40, LogisticRegressionTaskSpark.java:32-35,
ServerAppRunner.java:19-26,59-63, WorkerAppRunner.java:17-24,55-58,
WorkerSamplingProcessor.java:21-23, ServerProcessor.java:36,44-49),
but everything the reference hardcodes is configurable here.
"""

from __future__ import annotations

import dataclasses

# Consistency-model constants (ServerProcessor.java:44-49):
#   sequential/BSP == 0, bounded-delay/SSP == k > 0, eventual/ASP == -1
#   (the reference's MAX_DELAY_INFINITY sentinel == the eventual model).
SEQUENTIAL = 0
EVENTUAL = -1


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """What every model family shares — the local solver (k steps, step
    size) and the shape of a classifier's rows — and where a family's
    own configuration is found.

    `num_features` / `num_classes` are the reference's LR task shape
    (LogisticRegressionTaskSpark.java:32-35): the parameter vector is
    flat with (num_classes + 1) * num_features coefficient keys
    followed by (num_classes + 1) intercept keys — 6*1024 + 6 = 6150
    by default.  One extra row because reference labels are
    1..num_classes and Spark sizes the model 0..max_label
    (LogisticRegressionTaskSpark.java:98-104,122-140).

    A family with more shape than that reads its own configuration
    from ONE file, `model_json` (`--model_json`; a relative path is
    taken from the repository's root): the published keys of the
    architecture and the cut this process holds
    (models/lm_common.py, the language-model families).  There is no
    flag per width; the row's width and dtype, the label encoding and
    whether the worker axis batches are the task's to say
    (models/task.py).
    """

    num_features: int = 1024
    num_classes: int = 5
    num_max_iter: int = 2       # k local solver steps per iteration
    local_learning_rate: float = 0.5  # step size of the local k-step solver
    # the mlp family's one width of its own; it predates model_json and
    # keeps its flag (`--hidden_dim`)
    hidden_dim: int = 128
    # a family's own configuration file (None: the family has none)
    model_json: str | None = None

    @property
    def num_rows(self) -> int:
        return self.num_classes + 1

    @property
    def num_params(self) -> int:
        return self.num_rows * self.num_features + self.num_rows


@dataclasses.dataclass(frozen=True)
class BufferConfig:
    """Dynamic sliding-buffer policy (WorkerAppRunner.java:55-58,
    WorkerSamplingProcessor.java:21-23,115-122)."""

    min_size: int = 128
    max_size: int = 1024
    coefficient: float = 0.3      # -bc: target = clamp(bc * events_per_min, min, max)
    arrival_window: int = 500     # inter-arrival-time window length


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Producer pacing (CsvProducer.java:73-83, ServerAppRunner.java:60)."""

    time_per_event_ms: float = 200.0   # -p: steady-state ms per event
    prefill_per_worker: int = 128      # first num_workers*128 rows unthrottled


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Online serving plane (kafka_ps_tpu/serving/, docs/SERVING.md):
    snapshot ring + micro-batching prediction engine.  `--serve` flag
    group in cli/run.py."""

    enabled: bool = False
    port: int | None = None       # socket endpoint; None = in-process only
    max_batch: int = 16           # micro-batch size cap (one jit shape)
    deadline_ms: float = 2.0      # max wait to fill a micro-batch
    ring_capacity: int = 8        # retained snapshots (at_clock reads)
    queue_limit: int = 0          # per-tenant admission budget; 0 = none
    shed_deadline_ms: float = 0.0  # predictive shed threshold; 0 = off
    auto: bool = True             # adaptive dispatch (costmodel.py)
    shm: bool = False             # offer same-host shared-memory path


@dataclasses.dataclass(frozen=True)
class TierConfig:
    """Tiered parameter residency (kafka_ps_tpu/store/,
    docs/TIERING.md): byte caps for the hot (device) and warm (host
    RAM) tiers; everything over the caps lives as commit-log records
    (cold).  `--tier-hot-bytes` / `--tier-warm-bytes` in cli/run.py.

    0 = unbounded — the fully-resident default, byte for byte today's
    behavior (no store is even constructed).  A warm cap needs a cold
    log to overflow into, so warm_bytes > 0 requires --durable-log (or
    a standalone cold directory).  Caps are PER PROCESS: a process
    hosting several in-process shards splits them evenly."""

    hot_bytes: int = 0
    warm_bytes: int = 0
    page_params: int = 1024        # keys per page (the residency unit)
    rebalance_interval_s: float = 0.05   # policy-thread cadence

    @property
    def enabled(self) -> bool:
        return self.hot_bytes > 0 or self.warm_bytes > 0


@dataclasses.dataclass(frozen=True)
class PSConfig:
    """Top-level parameter-server configuration (BaseKafkaApp.java:25,
    ServerProcessor.java:36,45-49)."""

    num_workers: int = 4
    consistency_model: int = SEQUENTIAL   # -c: 0 BSP, k>0 SSP, -1 ASP
    # model family, by its name in models/task.py's registry
    # (`task_names`); "logreg" is the reference's
    task: str = "logreg"
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    buffer: BufferConfig = dataclasses.field(default_factory=BufferConfig)
    stream: StreamConfig = dataclasses.field(default_factory=StreamConfig)
    # Server aggregation rate: 1/num_workers makes the BSP update the
    # average of worker deltas (ServerProcessor.java:36).
    learning_rate: float | None = None
    eval_every: int = 1   # server evaluates test metrics every iteration
    # Async coalescing eval engine (evaluation/engine.py,
    # docs/EVALUATION.md): take test-set evaluation off the server's
    # apply critical path — a dedicated thread coalesces pending
    # (theta, clock) snapshots into batched eval dispatches, emitting
    # results in strict clock order.  Default ON; `--no-eval-async` is
    # the A/B lever (same eval CSV rows to float32 tolerance).  The
    # fused-BSP drive loop ignores it (its eval is already
    # chunk-amortized, runtime/app._run_fused_loop).
    eval_async: bool = True
    seed: int = 0
    # Gang-scheduled dispatch (runtime/gang.py, docs/GANG_DISPATCH.md):
    # coalesce workers released by the consistency gate at the same
    # moment into one batched device step.  On by default for the
    # serial/threaded drive loops; `--no-gang` restores the per-message
    # path.  In-process fabrics only — socket mode forces it off (the
    # wire protocol has no gang notice frame).
    use_gang: bool = True
    # Compressed delta transport (kafka_ps_tpu/compress/,
    # docs/COMPRESSION.md): "none" | "bf16" | "int8" | "topk:<ratio>".
    # Applied symmetrically — server->worker weights are quantize-
    # dequantized, worker->server deltas go through per-worker
    # error-feedback residuals.  "none" is bitwise-identical to a build
    # without the feature.  Incompatible with the fused BSP path (its
    # collectives never cross a serde boundary).
    compress: str = "none"
    # Device-resident training slab (compress/slab.py,
    # docs/PERFORMANCE.md).  slab_dtype: "f32" | "bf16" | "int8" — the
    # storage precision of each worker's on-device slab; decode is
    # fused into the training step.  "f32" is bitwise-identical to a
    # build without the feature.  slab_incremental: scatter only dirty
    # buffer rows into the device slab instead of re-uploading the
    # whole slab on every arrival (full upload remains the fallback
    # for bootstrap, restore, and mass-delete churn).
    slab_dtype: str = "f32"
    slab_incremental: bool = True
    # Online serving plane (kafka_ps_tpu/serving/): disabled by default —
    # attaching it never perturbs training (snapshots alias the
    # immutable device theta), but the engine thread only exists when
    # asked for.
    serving: ServingConfig = dataclasses.field(default_factory=ServingConfig)
    # Tiered parameter residency (kafka_ps_tpu/store/): disabled (both
    # caps 0) keeps theta fully device-resident — bitwise-identical to
    # a build without the feature; capped runs stay bitwise-identical
    # too (the tier replay contract, docs/TIERING.md), they just bound
    # resident bytes.
    tier: TierConfig = dataclasses.field(default_factory=TierConfig)

    @property
    def server_lr(self) -> float:
        if self.learning_rate is not None:
            return self.learning_rate
        return 1.0 / self.num_workers

    @property
    def max_vector_clock_delay(self) -> int:
        """ServerProcessor.java:45-49: delay == consistency model value."""
        return self.consistency_model

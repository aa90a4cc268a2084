"""The parameter server — behavioral re-design of ServerProcessor
(processors/ServerProcessor.java:31-229).

State: the flat parameter vector (device-resident, updated by
REPLACEMENT — never mutated in place, so weights messages, evals and
checkpoints can all alias the immutable array), a MessageTracker, and
the consistency gate.  Aggregation: theta[range] += server_lr * delta
with server_lr defaulting to 1/num_workers, making the BSP update the
average of worker deltas (ServerProcessor.java:36,225-228).  Full-range
gradients (the per-node protocol) apply as one jit'd add with no host
synchronization; evaluation is an async dispatch whose results land in
the log when they resolve (utils/asynclog.DeferredSink) — the gate
never waits on an eval.

Consistency dispatch (ServerProcessor.java:95-134):
  * eventual (-1): answer only the sender, immediately;
  * sequential (0): when all gradients for clock t arrived, answer ALL
    workers with clock t+1;
  * bounded delay (k>0): answer every worker with an outstanding reply
    whose next clock is <= k ahead of the slowest worker.

Improvements over the reference (documented divergences):
  * gradient applied over the full half-open key range — the reference
    drops the last intercept via an inclusive/exclusive mismatch
    (SURVEY §3.5.1);
  * the server CSV line logs the real test loss instead of the
    hardcoded -1 (ServerProcessor.java:158-164) — same schema;
  * optional checkpointing (utils/checkpoint.py) instead of the
    reference's unconditional cold start (BaseKafkaApp.java:57).
"""

from __future__ import annotations

import time
from typing import Callable

import jax.numpy as jnp
import numpy as np

from kafka_ps_tpu.parallel.tracker import MessageTracker
from kafka_ps_tpu.runtime import fabric as fabric_mod
from kafka_ps_tpu.runtime.messages import (CompositeDelta, GangNotice,
                                           GradientMessage, KeyRange,
                                           WeightsMessage)
from kafka_ps_tpu.telemetry import (CLOCK_BUCKETS, NULL_TELEMETRY,
                                    model_name)
from kafka_ps_tpu.telemetry.flight import FLIGHT
from kafka_ps_tpu.telemetry.modelhealth import NULL_MODEL_HEALTH
from kafka_ps_tpu.utils import asynclog, device
from kafka_ps_tpu.utils.config import EVENTUAL, PSConfig
from kafka_ps_tpu.utils.trace import NULL_TRACER

LogSink = Callable[[str], None]


class ServerNode:
    """Central aggregator + consistency gate + online evaluator."""

    def __init__(self, cfg: PSConfig, fabric: fabric_mod.Fabric,
                 test_x: np.ndarray | None = None,
                 test_y: np.ndarray | None = None,
                 log: LogSink | None = None,
                 tracer=None, telemetry=None,
                 key_range: KeyRange | None = None,
                 shard_id: int = 0, num_shards: int = 1,
                 grad_key: int = 0):
        self.tracer = tracer or NULL_TRACER
        self.telemetry = telemetry or NULL_TELEMETRY
        self.cfg = cfg
        self.fabric = fabric
        self.tracker = MessageTracker(cfg.num_workers)
        # range sharding (runtime/sharding.py, docs/SHARDING.md): this
        # node owns `key_range` of the flat parameter vector — theta,
        # weights messages and the full-range fast path are all relative
        # to it.  The defaults (full range, shard 0 of 1, gradient key
        # 0) are byte-for-byte today's single server.
        self.shard_id = shard_id
        self.num_shards = num_shards
        self._grad_key = grad_key
        # consistency-model observability (docs/OBSERVABILITY.md): the
        # gate-wait and clock-lag distributions are what distinguish BSP
        # from bounded-delay from async at runtime.  Metric children are
        # pre-resolved here so the hot path never touches the registry's
        # family lock (null metrics when telemetry is off).  Sharded
        # servers label every family with their shard id; the unsharded
        # server keeps the historical label set.
        model = model_name(cfg.consistency_model)
        self._model = model          # span/critpath label, stable per node
        shard_labels = ({"shard": str(shard_id)} if num_shards > 1 else {})
        self._m_gate_wait = self.telemetry.histogram(
            "gate_wait_ms", model=model, **shard_labels)
        self._m_clock_lag = self.telemetry.histogram(
            "clock_lag", buckets=CLOCK_BUCKETS, model=model,
            **shard_labels)
        self._m_worker_lag = [
            self.telemetry.gauge("worker_clock_lag", worker=str(w),
                                 **shard_labels)
            for w in range(cfg.num_workers)]
        self._m_grads = [
            self.telemetry.counter("gradients_applied_total", worker=str(w),
                                   **shard_labels)
            for w in range(cfg.num_workers)]
        self._m_snapshots = self.telemetry.counter(
            "snapshots_published_total", **shard_labels)
        self._m_serving_clock = self.telemetry.gauge("serving_clock",
                                                     **shard_labels)
        # (perf_counter stamp, clock) of each worker's last un-answered
        # gradient: gate wait = release time - arrival time (host
        # scalars only); the clock rides along so the retroactive
        # gate.wait trace span can be matched to its delta flow
        # (telemetry/critpath.py keys segments on (worker, clock))
        self._grad_arrived: dict[int, tuple[float, int]] = {}
        # trace context of the gradient currently being processed — the
        # snapshot published by its release inherits it, extending the
        # delta.wire flow into the serving plane
        self._pending_trace = None
        from kafka_ps_tpu.models.task import get_task
        self.task = get_task(cfg.task, cfg.model)
        self._range = (key_range if key_range is not None
                       else KeyRange(0, self.task.num_params))
        # optional tiered residency (kafka_ps_tpu/store/, docs/
        # TIERING.md): None keeps theta a plain device array — today's
        # fully-resident behavior, byte for byte
        self.param_store = None
        # device-resident; updated by replacement only (see module doc).
        # A shard owns only its slice of the init vector (the slice of a
        # host ndarray is a view — same bits as the full init).
        if key_range is None:
            self.theta = jnp.asarray(self.task.init_params(),
                                     dtype=jnp.float32)
        else:
            self.theta = jnp.asarray(
                self.task.init_params()[key_range.start:key_range.end],
                dtype=jnp.float32)
        import jax
        self._apply_full = jax.jit(
            lambda t, d: t + self.cfg.server_lr * d)
        # sparse slice applies (SparseDeltaMessage, range sharding): one
        # jit'd scatter-add per padded bucket size — indices pad with 0
        # and values with 0.0, so duplicate pad entries add exact zeros
        self._sparse_apply_cache: dict = {}

        # apply + eval as ONE dispatch instead of two
        def _apply_eval(t, d, tx, ty):
            t2 = t + self.cfg.server_lr * d
            m = self.task.evaluate(t2, tx, ty)
            return t2, m
        self._apply_full_eval = jax.jit(_apply_eval)
        # Batched (gang) apply programs, keyed on the static shape of a
        # batch: (k, eval positions, prefix-theta positions).  Each is
        # ONE jit'd dispatch that chains the k per-message updates —
        # chained adds, NOT deltas.sum(0): float addition is not
        # associative, and the acceptance bar is bitwise equality with
        # k sequential _apply_full calls (docs/GANG_DISPATCH.md).
        self._gang_apply_cache: dict = {}
        self.test_x = jnp.asarray(test_x) if test_x is not None else None
        self.test_y = jnp.asarray(test_y) if test_y is not None else None
        self.log = log or (lambda line: None)
        self.iterations = 0          # total gradient messages applied
        self.last_metrics = None
        self._loop_started = False   # bootstrap broadcast done once
        # monotonic stamp of the last weights send per worker (heartbeat
        # baseline for the supervisor, runtime/app.py)
        self.weights_sent_at = [time.monotonic()] * cfg.num_workers
        # optional periodic checkpointing (utils/checkpoint.py)
        self.checkpoint_path: str | None = None
        self.checkpoint_every: int = 50   # <= 0: only save on exit
        self._last_checkpoint_iteration = 0
        # in-process runs fold the workers' buffers into the checkpoint
        # (durable training window); split mode leaves this None — each
        # worker process persists its own state file instead
        self.checkpoint_buffers = None
        # weights-side compression (compress.WeightsCompressor, set by
        # app/CLI wiring when --compress != none): every outgoing
        # WeightsMessage carries quantize-dequantized values + the
        # encoded parts; the master theta here stays full precision
        self.compressor = None
        # {worker: ErrorFeedback} for in-process runs — the residuals
        # ride the checkpoint next to the buffers (split mode persists
        # them in each worker process's state file instead)
        self.checkpoint_residuals = None
        # durable-log recovery (log/durable_fabric.py): the committed
        # offsets the restored checkpoint covers — replay starts there
        self.restored_log_offsets: dict[str, int] | None = None
        # logical-run identity: survives checkpoint resumes (restore
        # overwrites it), changes on every fresh start — worker-local
        # state files are only valid within the run that wrote them
        self.run_id = time.time_ns()
        # membership-change record (timestamp_ms, "evict"|"readmit"|
        # "resume", worker) — the audit trail the staleness auditor
        # segments elastic runs by (evaluation/validate.py epoch
        # checking).  `membership_log` (a CsvLogSink) persists each
        # event AS IT HAPPENS: an end-of-run write would lose the
        # record on a crash — the very scenario the events exist for
        self.membership_events: list[tuple[int, str, int]] = []
        self.membership_log = None
        # online serving plane (kafka_ps_tpu/serving/, docs/SERVING.md):
        # when a SnapshotRegistry is attached, every consistency-gate
        # release publishes the released theta for readers.  None (the
        # default) keeps publish_snapshot a no-op — training is
        # bitwise-identical with serving on or off.
        self.serving = None
        # model-health plane (telemetry/modelhealth.py): per-update
        # diagnostics + drift detection when --model-health armed it.
        # NULL by default — one attribute load on the hot path, and
        # theta stays bitwise-identical either way (the plane only
        # reads values the update already produced).
        self.modelhealth = NULL_MODEL_HEALTH
        # async eval plane (evaluation/engine.py, --eval-async): when an
        # EvalEngine is attached, eval-cadence applies shed the fused
        # eval — the apply dispatch keeps the non-eval shape and the
        # (theta, clock) pair is handed to the engine's queue instead
        # (O(1): theta is an immutable alias by the replacement-only
        # contract above).  None keeps the fused `_apply_full_eval`
        # path — the --no-eval-async A/B arm, bitwise-identical CSV.
        self.eval_engine = None
        # hierarchical aggregation (kafka_ps_tpu/agg/,
        # docs/AGGREGATION.md): stacked composites under BSP are
        # round-buffered here (clock -> {worker: delta}) and applied in
        # worker-id order once the round is complete, so the aggregated
        # path is bitwise-identical to a deterministically-ordered
        # direct run regardless of composite arrival order.
        # `bsp_order` extends the same ordering to DIRECT gradients
        # (the determinism knob the tier1 --agg A/B comparison runs
        # both arms under); `weights_group_send` is the socket bridge's
        # grouped-fanout hook — one T_WEIGHTS_AGG frame per aggregator
        # instead of one T_WEIGHTS per member.
        self._agg_pending: dict[int, dict[int, GradientMessage]] = {}
        self.bsp_order = False
        self.weights_group_send = None

    # -- tiered residency (kafka_ps_tpu/store/, docs/TIERING.md) -----------

    @property
    def theta(self):
        """The owned parameter slice.  A direct array when fully
        resident (today's behavior); assembled on demand from the
        tiered store when one is attached.  Either way the value is
        immutable-by-contract — readers may alias it, writers go
        through the setter (replacement only, see module doc)."""
        if self.param_store is not None:
            return self.param_store.assembled()
        return self._theta

    @theta.setter
    def theta(self, value):
        if self.param_store is not None:
            self.param_store.replace_all(value)
            return
        self._theta = value

    def attach_param_store(self, store) -> None:
        """Switch this node's slice to tiered hot/warm/cold residency.
        Seeds the store from the current theta (attach-any-time is
        safe: before or after a checkpoint restore); afterwards dense
        applies run per page and the configured byte caps bound what
        stays device/host resident while every computed bit stays
        identical (the tier replay contract, docs/TIERING.md)."""
        if (store.key_range.start != self._range.start
                or store.key_range.end != self._range.end):
            raise ValueError(
                f"store range [{store.key_range.start}, "
                f"{store.key_range.end}) != shard range "
                f"[{self._range.start}, {self._range.end})")
        # one-time seed at attach, not the hot path
        store.replace_all(np.asarray(self._theta))
        self.param_store = store
        self._theta = None           # the store owns the values now
        store.rebalance()            # settle residency under the caps

    def attach_model_health(self, plane) -> None:
        """Arm the model-health plane (telemetry/modelhealth.py): the
        apply path starts feeding it per-update diagnostics and eval
        metrics.  Detach by re-attaching NULL_MODEL_HEALTH."""
        self.modelhealth = plane

    def attach_eval_engine(self, engine):
        """Arm the async eval plane (evaluation/engine.py): eval-cadence
        applies stop fusing the eval and submit (theta, clock) to the
        engine instead; the engine calls `_emit_eval` back in strict
        clock order.  Returns the engine (attach-and-keep idiom)."""
        self.eval_engine = engine
        return engine

    def _emit_eval(self, clock: int, m) -> None:
        """The ONE eval emission point — every fused path and the async
        engine's thread funnel through here, so CSV rows, last_metrics
        and the model-health plane see one sequence regardless of the
        lever.  Schema: timestamp;partition;vectorClock;loss;fMeasure;
        accuracy (ServerAppRunner.java:81); partition=-1 like the
        reference, loss = real test loss (reference hardcodes -1).
        Metric fields may be device futures — asynclog defers the
        fetch; modelhealth's sampler floats its copies off-path."""
        self.last_metrics = m
        asynclog.submit_or_write(
            self.log,
            f"{int(time.time() * 1000)};-1;{clock};"
            "{};{};{}", m.loss, m.f1, m.accuracy)
        if self.modelhealth.enabled:
            self.modelhealth.observe_eval(m.loss, m.f1)

    # -- bootstrap (ServerProcessor.java:75-87) ----------------------------

    def start_training_loop(self) -> None:
        """Broadcast WeightsMessages to kick off the self-sustaining loop.

        Cold start: every worker is in the already-replied state (tracker
        bootstrap, MessageTracker.java:47-53) and gets clock 0, like the
        reference.  After a checkpoint restore: workers whose reply was
        delivered get their current clock re-sent (the in-flight message
        died with the crash); workers with a *withheld* reply go back
        through the consistency gate — only those currently eligible are
        re-issued, so restored runs respect the same staleness bounds.
        """
        if self._loop_started:
            # resuming a drive loop on a live system: the in-flight
            # messages are still in the fabric; re-broadcasting would
            # double-deliver and break the clock protocol
            return
        self._loop_started = True
        released: list[tuple[int, int]] = []
        for worker, status in enumerate(self.tracker.tracker):
            if not status.active:
                continue
            # Durable-log restart: the crash did NOT kill in-flight
            # messages — the replayed queue may already hold this
            # worker's reply (log/durable_fabric.recover).  Re-sending
            # it would double-deliver; the replayed copy is the send.
            if self.fabric.pending(fabric_mod.WEIGHTS_TOPIC, worker):
                if not status.weights_message_sent:
                    self.tracker.sent_message(worker, status.vector_clock)
                continue
            if status.weights_message_sent:
                self.fabric.send(fabric_mod.WEIGHTS_TOPIC, worker,
                                 self._weights_message(status.vector_clock))
                self.weights_sent_at[worker] = time.monotonic()
                released.append((worker, status.vector_clock))
        delay = self.cfg.max_vector_clock_delay
        if delay == EVENTUAL:
            # eventual answers immediately, so any surviving pending
            # reply is re-issued at once
            for worker, s in enumerate(self.tracker.tracker):
                if s.active and not s.weights_message_sent:
                    self.send_weights(worker, s.vector_clock)
                    released.append((worker, s.vector_clock))
        else:
            # sequential == bounded with delay 0: the tracker's own
            # sendable predicate (MessageTracker.java:69-79)
            released.extend(self._flush_gate(notify=False))
        # the bootstrap broadcast is one simultaneous release moment for
        # every consistency model — one notice covers all of it
        self._emit_gang_notice(sorted(released))
        # first snapshot: the weights the loop starts from (cold start or
        # checkpoint restore) are servable before any gradient arrives
        self.publish_snapshot()

    def _weights_message(self, vector_clock: int) -> WeightsMessage:
        if self.param_store is not None:
            # assembled() is a FRESH host vector per call — nothing else
            # aliases it, so no defensive copy is needed
            values = self.param_store.assembled()
        else:
            # device theta is immutable — safe to alias; a host-side
            # theta (checkpoint restore, partial-range splice) is copied
            # so a later in-place edit can't race an in-flight message
            # pscheck: disable=PS102 (host->host defensive copy, no device sync)
            values = (np.array(self.theta)
                      if isinstance(self.theta, np.ndarray) else self.theta)
        encoded = None
        if self.compressor is not None:
            # every worker trains on the decoded (quantize-dequantized)
            # copy — in-process consumers get it by reference, socket
            # peers decode the SAME parts to the same floats
            values, encoded = self.compressor.encode(values)
        return WeightsMessage(
            vector_clock=vector_clock,
            key_range=self._range,
            values=values, encoded=encoded)

    def send_weights(self, worker: int, clock: int) -> None:
        """The single weights-send site: dispatch + tracker bookkeeping +
        the sent-at stamp the supervisor's heartbeat measures from (time
        a worker spends gate-blocked and idle must not count against
        it)."""
        self.fabric.send(fabric_mod.WEIGHTS_TOPIC, worker,
                         self._weights_message(clock))
        self.weights_sent_at[worker] = time.monotonic()
        self.tracker.sent_message(worker, clock)
        self._observe_gate_release(worker)
        if FLIGHT.enabled:
            FLIGHT.record("gate.release", shard=self.shard_id,
                          worker=worker, clock=clock)
            FLIGHT.beat("gate")

    def _observe_gate_release(self, worker: int) -> None:
        """Gate-wait sample: how long this worker's gradient sat at the
        gate before its reply went out (BSP waits for the round, bounded
        delay waits for the slowest-within-k, eventual ~0).  Bootstrap
        and readmission sends have no arrival stamp and record
        nothing.

        Also emits the retroactive `gate.wait` trace span — the gate
        holds weights RELEASES, not applies (gradients apply on
        arrival), so the hold time only exists as a span once the
        release happens.  The tracer's default clock is the same
        perf_counter the arrival stamp used, so span_at gets two values
        on one epoch."""
        if not self.telemetry.enabled:
            return
        entry = self._grad_arrived.pop(worker, None)
        if entry is not None:
            arrived, clock = entry
            now = time.perf_counter()
            self._m_gate_wait.observe((now - arrived) * 1e3)
            self.tracer.span_at("gate.wait", arrived, now, worker=worker,
                                clock=clock, model=self._model,
                                shard=self.shard_id)

    def gate_waiting(self) -> int:
        """How many active workers are currently parked at the gate
        (gradient received, reply withheld) — the demand predicate the
        gate watchdog checks liveness against (telemetry/health.py).
        Host ints only; safe from any thread (racy reads see a
        consistent-enough count)."""
        return sum(1 for w in self.tracker.active_workers
                   if not self.tracker.tracker[w].weights_message_sent)

    # -- consistency gate (ServerProcessor.java:95-134) --------------------

    def workers_to_respond_to(self, received_vc: int,
                              sender: int) -> set[tuple[int, int]]:
        delay = self.cfg.max_vector_clock_delay
        if delay == EVENTUAL:
            return {(sender, received_vc + 1)}
        if delay == 0:
            if self.tracker.has_received_all_messages(received_vc):
                return {(w, received_vc + 1)
                        for w in self.tracker.active_workers}
            return set()
        return set(self.tracker.get_all_sendable_messages(delay))

    # -- membership: failure detection / elastic recovery ------------------
    # The reference delegates both to the platform (Kafka consumer-group
    # rebalancing + k8s pod restarts, SURVEY §5); here they are runtime
    # APIs driven by the supervisor in runtime/app.py.

    def record_membership_event(self, kind: str, worker: int) -> None:
        ev = (int(time.time() * 1000), kind, worker)
        self.membership_events.append(ev)
        if self.membership_log is not None:
            self.membership_log(f"{ev[0]};{kind};{worker}")

    def remove_worker(self, worker: int) -> None:
        """Evict a failed worker: every consistency gate stops waiting
        for its gradients, and any round it was blocking is released."""
        self.tracker.deactivate_worker(worker)
        self.record_membership_event("evict", worker)
        self.tracer.count("server.workers_removed")
        if self._agg_pending:
            # drop the evictee's buffered round members and re-check
            # completeness — an eviction must not strand a BSP round
            # the dead worker was the last missing member of
            for bucket in self._agg_pending.values():
                bucket.pop(worker, None)
            self._flush_agg_rounds()
        self._flush_gate()

    def readmit_worker(self, worker: int) -> int:
        """Elastic scale-up: rejoin at the slowest active clock with the
        current weights (the state-store-restore analogue)."""
        # drain any pre-eviction in-flight traffic: a stale gradient (or
        # stale queued weights) becoming "live" again would break the
        # clock protocol
        self.fabric.purge(fabric_mod.GRADIENTS_TOPIC, self._grad_key,
                          lambda m: getattr(m, "worker_id", None) == worker)
        self.fabric.purge(fabric_mod.WEIGHTS_TOPIC, worker, lambda m: True)
        clock = self.tracker.reactivate_worker(worker)
        self.record_membership_event("readmit", worker)
        self.tracer.count("server.workers_readmitted")
        self.send_weights(worker, clock)
        return clock

    def _flush_gate(self, notify: bool = True) -> list[tuple[int, int]]:
        """Send every reply the gate now permits (used after membership
        changes — a removal can unblock rounds the dead worker held up).
        Returns the release set; `notify=False` suppresses the gang
        notice so a caller folding several release sources into one
        simultaneous moment (start_training_loop) emits a single one."""
        delay = self.cfg.max_vector_clock_delay
        if delay == EVENTUAL:
            return []
        release = sorted(self.tracker.get_all_sendable_messages(
            max(delay, 0)))
        for worker, clock in release:
            self.send_weights(worker, clock)
        if notify:
            self._emit_gang_notice(release)
            if release:
                self.publish_snapshot()
        return release

    # -- gang dispatch (runtime/gang.py, docs/GANG_DISPATCH.md) ------------

    def _emit_gang_notice(self, release: list[tuple[int, int]]) -> None:
        """Publish a batched-weights notification for a multi-member
        release set, ALONGSIDE the per-worker messages (which remain the
        protocol — the notice is advisory and never serialized)."""
        if self.cfg.use_gang and len(release) > 1:
            self.fabric.send_transient(
                fabric_mod.GANG_TOPIC, 0, GangNotice(members=tuple(release)))
            self.tracer.count("server.gang_release_sets")

    def dispatch_release_set(self, release) -> None:
        """The consistency dispatch, as an explicit release set: sorted
        per-worker sends (worker-id order keeps serial scheduling
        deterministic) plus the gang notice when several workers were
        released at the same moment.

        When a `weights_group_send` hook is attached (the socket
        bridge's aggregator fan-out, net.ServerBridge), it gets first
        claim on the set: members it ships inside grouped frames come
        back as a handled set and receive bookkeeping only — the same
        tracker/stamp/metric sequence send_weights runs, minus the
        per-worker fabric send the grouped frame replaced."""
        release = sorted(release)
        handled = self._group_send(release, self._weights_message)
        for worker, clock in release:
            if worker in handled:
                self._mark_grouped_release(worker, clock)
            else:
                self.send_weights(worker, clock)
        self._emit_gang_notice(release)
        if release:
            self.publish_snapshot()

    def _group_send(self, release, builder) -> set:
        """Offer a sorted release set to the grouped-fanout hook.
        `builder(clock)` produces the WeightsMessage a grouped frame
        carries (one body per distinct clock; the hook re-uses it
        across members).  Returns the worker ids the hook shipped."""
        if self.weights_group_send is None or not release:
            return set()
        return self.weights_group_send(release, builder)

    def _mark_grouped_release(self, worker: int, clock: int) -> None:
        """Bookkeeping for a release whose bytes went out inside a
        grouped aggregator frame: everything send_weights does except
        the fabric send."""
        self.weights_sent_at[worker] = time.monotonic()
        self.tracker.sent_message(worker, clock)
        self._observe_gate_release(worker)
        if FLIGHT.enabled:
            FLIGHT.record("gate.release", shard=self.shard_id,
                          worker=worker, clock=clock, grouped=True)
            FLIGHT.beat("gate")

    # -- serving plane (kafka_ps_tpu/serving/, docs/SERVING.md) ------------

    def serving_clock(self) -> int:
        """The stable clock a snapshot is stamped with: the slowest
        ACTIVE worker's vector clock.  Every weights message released at
        or before this moment carries a clock >= it, so a reader holding
        a snapshot at clock c knows all workers have incorporated rounds
        < c — the read-side mirror of the bounded-delay invariant."""
        active = self.tracker.active_workers
        if not active:
            return 0
        return min(self.tracker.tracker[w].vector_clock for w in active)

    def publish_snapshot(self, theta=None, clock=None, trace=None) -> None:
        """Publish (theta, stable clock) to the attached snapshot
        registry; no-op when serving is off.  Called at every gate
        release — per-message, gang, fused — plus bootstrap/cold-start.
        O(1) host-side (the snapshot aliases the immutable device
        theta), so attaching a registry cannot perturb training.
        `trace` (default: the context of the gradient being processed)
        rides on the snapshot so the serving plane can close the
        delta.wire flow at first read."""
        registry = self.serving
        if registry is None:
            return
        if trace is None:
            trace = self._pending_trace
        clock = self.serving_clock() if clock is None else clock
        registry.publish(self.theta if theta is None else theta,
                         clock, trace=trace)
        if trace is not None:
            # the flow's publish step: critpath reads the snapshot-
            # publish moment off this event (the segment between apply
            # and the first serving read, telemetry/critpath.py)
            self.tracer.flow_step("delta.wire", trace, step="publish",
                                  clock=int(clock))
        self.tracer.count("serving.snapshots_published")
        if self.telemetry.enabled:
            self._m_snapshots.inc()
            self._m_serving_clock.set(clock)
        if FLIGHT.enabled:
            FLIGHT.record("snapshot.publish", shard=self.shard_id,
                          clock=int(clock))

    # -- the hot path (ServerProcessor.java:143-183) -----------------------

    def process(self, msg: GradientMessage) -> None:
        if isinstance(msg, CompositeDelta):
            self.process_composite(msg)
            return
        if (self.bsp_order and self.cfg.max_vector_clock_delay == 0
                and getattr(msg, "indices", None) is None
                and msg.key_range.start == self._range.start
                and msg.key_range.end == self._range.end):
            # deterministic BSP ordering (docs/AGGREGATION.md): direct
            # gradients join the same per-round buffer composites use,
            # so a direct run and an aggregated run apply every round
            # in identical worker-id order — the A/B determinism knob
            if self._buffer_round_member(msg):
                self._flush_agg_rounds()
            return
        if not self.tracker.tracker[msg.worker_id].active:
            # in-flight gradient from an evicted worker (zombie): drop it
            # rather than corrupt the vector-clock protocol
            self.tracer.count("server.zombie_gradients_dropped")
            return
        if self.tracker.is_duplicate(msg.worker_id, msg.vector_clock):
            # exactly-once under the durable log's at-least-once replay
            # (log/durable_fabric.py): a delta whose clock the tracker
            # already advanced past was applied before the crash (or is
            # a recomputation from a replayed weights message) — drop
            # it instead of double-stepping theta.  Clocks AHEAD of the
            # tracker still raise below (the protocol sanitizer).
            self.tracer.count("server.duplicate_gradients_dropped")
            return
        self.tracker.received_message(msg.worker_id, msg.vector_clock)
        self.tracer.count("server.gradients_applied")
        if self.telemetry.enabled:
            self._observe_arrival(msg.worker_id, msg.vector_clock)
        if FLIGHT.enabled:
            self._flight_arrival(msg.worker_id, msg.vector_clock)
        if self.modelhealth.enabled:
            # host arrays (socket path) compute inline; device arrays
            # are observed by reference and resolved off-path
            self.modelhealth.observe_update(msg.worker_id, msg.values)
        fid = getattr(msg, "trace", None)
        self._pending_trace = fid

        want_eval = (msg.worker_id == 0 and self.test_x is not None
                     and msg.vector_clock % self.cfg.eval_every == 0)
        # async lever: with an engine attached the apply keeps the
        # non-eval program shape and the eval is deferred to the
        # engine's queue after the dispatch
        defer_eval = want_eval and self.eval_engine is not None
        fused_eval = want_eval and not defer_eval
        m = None
        deferred_theta = None
        with self.tracer.span("server.apply", worker=msg.worker_id,
                              clock=msg.vector_clock,
                              shard=self.shard_id, model=self._model):
            r = msg.key_range
            if getattr(msg, "indices", None) is not None:
                # sparse delta slice (SparseDeltaMessage, range sharding):
                # O(nnz) scatter-add onto this shard's slice — an EMPTY
                # slice advanced the gate above and skips the device
                # dispatch entirely (the work-reduction sharded topk
                # scaling rides on, docs/SHARDING.md)
                self._apply_sparse(msg, fid)
            elif (r.start == self._range.start
                    and r.end == self._range.end):
                # per-node protocol: one async jit'd dispatch, no host
                # sync — eval iterations fuse the evaluation in (the
                # nested span keeps server.eval visible to --trace
                # consumers even though the dispatch is shared)
                if self.param_store is not None:
                    m, deferred_theta = self._apply_tiered(
                        msg.values, fused_eval, defer_eval,
                        msg.vector_clock)
                elif fused_eval:
                    with self.tracer.span("server.eval",
                                          clock=msg.vector_clock):
                        self.theta, m = self._apply_full_eval(
                            jnp.asarray(self.theta), msg.values,
                            self.test_x, self.test_y)
                else:
                    self.theta = self._apply_full(jnp.asarray(self.theta),
                                                  msg.values)
                self.tracer.count("dispatch.device")
                if fid is not None:
                    # step the delta flow: the wire arrow lands on the
                    # net.recv slice, this one on the apply slice
                    self.tracer.flow_step("delta.wire", fid,
                                          clock=msg.vector_clock)
            else:
                # sub-range splice, relative to this node's owned range
                lo = r.start - self._range.start
                hi = r.end - self._range.start
                if lo < 0 or hi > len(self._range):
                    raise ValueError(
                        f"gradient range [{r.start}, {r.end}) outside "
                        f"shard range [{self._range.start}, "
                        f"{self._range.end})")
                # pscheck: disable=PS102 (KeyRange splice is the documented host path)
                host = np.array(self.theta)
                # pscheck: disable=PS102 (KeyRange splice is the documented host path)
                host[lo:hi] += self.cfg.server_lr * np.asarray(msg.values)
                self.theta = host
            self.iterations += 1
            device.mark("first_update")

        if fused_eval:
            if m is None:            # partial-range splice path
                with self.tracer.span("server.eval", clock=msg.vector_clock):
                    m = self.task.evaluate(jnp.asarray(self.theta),
                                           self.test_x, self.test_y)
                    self.tracer.count("dispatch.device")
            self._emit_eval(msg.vector_clock, m)
        elif defer_eval:
            # immutable alias hand-off; the tiered path surfaces the
            # freshly-applied assembled vector so the engine never
            # re-assembles pages (and the splice path's theta is a
            # fresh host copy — also safe to alias)
            self.eval_engine.submit(
                self.theta if deferred_theta is None else deferred_theta,
                msg.vector_clock)

        self.dispatch_release_set(
            self.workers_to_respond_to(msg.vector_clock, msg.worker_id))
        self._pending_trace = None

        self.maybe_checkpoint()

    def _apply_tiered(self, delta, fused_eval: bool, defer_eval: bool,
                      clock: int):
        """Full-range dense apply against the tiered store.  Returns
        (metrics, deferred_theta) — at most one is non-None.

        Non-eval: per-page `t_p + lr * d_p` dispatches.  `_apply_full`
        is pointwise, so page-sliced applies produce bitwise-identical
        elements to the one full-slice apply — the tier bitwise
        contract (docs/TIERING.md).  Hot pages update device-to-device;
        warm/cold pages are materialized by the store (cold ones fault
        in from the log).

        Fused eval: assemble once and run the SAME fused
        `_apply_full_eval` program as the resident path, then scatter
        the result back — identical jaxpr on identical input bits, so
        the CSV metrics row matches the fully-resident run exactly.

        Deferred eval (--eval-async): the same assemble-once structure,
        but the apply keeps the non-eval program and the freshly-built
        t2 is returned for the engine's queue — an immutable device
        array the store's later page updates can never touch."""
        store = self.param_store
        if fused_eval:
            with self.tracer.span("server.eval", clock=clock):
                t2, m = self._apply_full_eval(
                    jnp.asarray(store.assembled()), delta,
                    self.test_x, self.test_y)
                store.replace_all(t2)
            return m, None
        if defer_eval:
            t2 = self._apply_full(jnp.asarray(store.assembled()), delta)
            store.replace_all(t2)
            return None, t2
        base = self._range.start
        for i, kr, value in store.pin_pages(self._range):
            lo, hi = kr.start - base, kr.end - base
            store.update_page(i, self._apply_full(jnp.asarray(value),
                                                  delta[lo:hi]))
        return None, None

    def _apply_sparse(self, msg, fid) -> None:
        """Apply a SparseDeltaMessage slice: theta[idx] += lr * vals as
        ONE jit'd scatter-add, compiled per padded bucket size (next
        power of two) so varying nnz across slices reuses a handful of
        programs.  Pad entries scatter an exact 0.0 onto index 0 —
        numerically exact (a padded slot may canonicalize -0.0; the
        sparse path carries no bitwise contract, docs/SHARDING.md).
        Empty slices skip the dispatch: the gate bookkeeping already
        ran, which is all an owning shard needs from a delta whose
        surviving top-k coordinates all live elsewhere."""
        k = len(msg.indices)
        if k == 0:
            self.tracer.count("dispatch.skipped_empty_slice")
        elif self.param_store is not None:
            self._apply_sparse_tiered(msg)
        else:
            bucket = 1 << max(3, int(k - 1).bit_length())
            idx = np.zeros((bucket,), dtype=np.int32)
            vals = np.zeros((bucket,), dtype=np.float32)
            idx[:k] = msg.indices
            vals[:k] = msg.values
            self.theta = self._sparse_apply_fn(bucket)(
                jnp.asarray(self.theta), idx, vals)
            self.tracer.count("dispatch.device")
        if fid is not None:
            # the arrow chain per delta SLICE: wire arrow lands on the
            # shard's net.recv, this step on its (possibly skipped) apply
            self.tracer.flow_step("delta.wire", fid,
                                  clock=msg.vector_clock,
                                  shard=self.shard_id)

    def _apply_sparse_tiered(self, msg) -> None:
        """Sparse scatter against the tiered store: group the slice's
        surviving indices by page (np.unique — sorted, deterministic)
        and run the bucketed scatter-add per touched page.  Pages the
        survivor set skips stay untouched — and therefore cool: this
        access skew is exactly what the heat policy feeds on
        (docs/TIERING.md)."""
        store = self.param_store
        # wire slices are host arrays; no device sync happens here
        idx = np.asarray(msg.indices, dtype=np.int64)
        vals = np.asarray(msg.values, dtype=np.float32)
        pages = idx // store.page_params
        for page in np.unique(pages):
            page = int(page)
            sel = pages == page
            local = (idx[sel] - page * store.page_params).astype(np.int32)
            n = len(local)
            bucket = 1 << max(3, int(n - 1).bit_length())
            bidx = np.zeros((bucket,), dtype=np.int32)
            bvals = np.zeros((bucket,), dtype=np.float32)
            bidx[:n] = local
            bvals[:n] = vals[sel]
            (_, _, value), = store.pin_pages(store.page_range(page))
            store.update_page(page, self._sparse_apply_fn(bucket)(
                jnp.asarray(value), bidx, bvals))
        self.tracer.count("dispatch.device")

    def _sparse_apply_fn(self, bucket: int):
        fn = self._sparse_apply_cache.get(bucket)
        if fn is None:
            import jax
            lr = self.cfg.server_lr

            def scatter(t, idx, vals):
                # pad entries are (0, 0.0) duplicates — scatter-add
                # tolerates duplicate indices, each contributing +0.0
                return t.at[idx].add(lr * vals)

            fn = jax.jit(scatter)
            self._sparse_apply_cache[bucket] = fn
        return fn

    def _flight_arrival(self, worker: int, clock: int) -> None:
        """Flight-recorder view of one gradient arrival: the full vector
        clock at gate-decision time (list index = worker id, evicted
        workers' clocks frozen where they stopped) plus this worker's
        lag — all host ints read off the tracker (no device values,
        PS106).  Kept to a flat int list: this runs per gradient (the
        armed recorder cost under 2% of server iters/s on the CPU dev
        host at PR 10; not measured on the chip)."""
        states = self.tracker.tracker
        clocks = [s.vector_clock for s in states]
        waiting = sum(1 for s in states
                      if s.active and not s.weights_message_sent)
        FLIGHT.record("gate.arrive", shard=self.shard_id, worker=worker,
                      clock=clock, lag=max(clocks) - clock,
                      waiting=waiting, clocks=clocks)
        FLIGHT.beat("gate")

    def _observe_arrival(self, worker: int, clock: int) -> None:
        """Per-gradient consistency observations, all host integers:
        arrival stamp (gate-wait baseline), this worker's clock lag
        behind the fastest active worker, and the applied-count."""
        self._grad_arrived[worker] = (time.perf_counter(), clock)
        self._m_grads[worker].inc()
        active = self.tracker.active_workers
        if active:
            fastest = max(self.tracker.tracker[w].vector_clock
                          for w in active)
            for w in active:
                lag = fastest - self.tracker.tracker[w].vector_clock
                self._m_worker_lag[w].set(lag)
            self._m_clock_lag.observe(
                fastest - self.tracker.tracker[worker].vector_clock)

    # -- hierarchical aggregation (kafka_ps_tpu/agg/, docs/AGGREGATION.md) --

    def process_composite(self, comp: CompositeDelta) -> None:
        """Apply one aggregator composite: the gate advances every
        member worker's clock from the composite's vector-clock map
        exactly as if the member deltas had arrived individually.

        Stacked composites expand into their per-member deltas: under
        BSP they enter the round buffer (worker-id-ordered applies,
        bitwise-pinned to the ordered direct path); under bounded
        delay/eventual they apply in member order via `process_batch`
        (itself bitwise-identical to per-message processing).  Summed
        composites apply as ONE pre-reduced add per host per clock —
        exact by linearity, not bitwise-pinned."""
        self.tracer.count("server.composites_received")
        if FLIGHT.enabled:
            FLIGHT.record("agg.composite", shard=self.shard_id,
                          agg=comp.agg_id, fan_in=comp.fan_in,
                          summed=comp.summed)
        if comp.summed:
            self._process_summed(comp)
            return
        resent: set = set()
        if self.cfg.max_vector_clock_delay == 0:
            buffered = False
            for d in comp.deltas:
                buffered |= self._buffer_round_member(d, resent)
            if buffered:
                self._flush_agg_rounds()
            return
        live = [d for d in comp.deltas
                if self._composite_member_live(d.worker_id,
                                               d.vector_clock, resent)]
        if live:
            self.process_batch(live)

    def _composite_member_live(self, worker: int, clock: int,
                               resent: set | None = None) -> bool:
        """Zombie/duplicate filter for one composite member, with the
        aggregator-restart liveness rule: a duplicate whose reply was
        already issued gets the current weights RE-sent — the original
        reply may have died inside the SIGKILL'd aggregator, and
        without a re-send the worker would wait forever (the worker
        side deduplicates redelivered weights, docs/COMPRESSION.md).
        `resent` bounds the re-send to once per worker per composite:
        a reconnecting worker's cache resend can land its whole tail of
        already-applied clocks inside one composite."""
        status = self.tracker.tracker[worker]
        if not status.active:
            self.tracer.count("server.zombie_gradients_dropped")
            return False
        if self.tracker.is_duplicate(worker, clock):
            self.tracer.count("server.duplicate_gradients_dropped")
            if status.weights_message_sent and (resent is None
                                                or worker not in resent):
                if resent is not None:
                    resent.add(worker)
                self.send_weights(worker, status.vector_clock)
            return False
        return True

    def _buffer_round_member(self, msg: GradientMessage,
                             resent: set | None = None) -> bool:
        """Queue one BSP-round member (from a composite expansion or a
        `bsp_order` direct gradient) for the ordered flush."""
        if not self._composite_member_live(msg.worker_id,
                                           msg.vector_clock, resent):
            return False
        bucket = self._agg_pending.setdefault(msg.vector_clock, {})
        if msg.worker_id in bucket:
            self.tracer.count("server.duplicate_gradients_dropped")
            return False
        bucket[msg.worker_id] = msg
        return True

    def _flush_agg_rounds(self) -> None:
        """Apply every complete buffered round, lowest clock first, in
        worker-id order — ONE process_batch per round, so evals land on
        the same prefix thetas and releases at the same moments as a
        worker-id-ordered serial direct run."""
        while self._agg_pending:
            clock = min(self._agg_pending)
            bucket = self._agg_pending[clock]
            expected = {w for w in self.tracker.active_workers
                        if self.tracker.tracker[w].vector_clock == clock}
            if not expected or not expected.issubset(bucket):
                return
            del self._agg_pending[clock]
            self.process_batch([bucket[w] for w in sorted(expected)])

    def _process_summed(self, comp: CompositeDelta) -> None:
        """One pre-reduced apply for a whole host's round contribution.
        All members must share one clock (the aggregator only sums a
        single-clock flush); a partially-duplicate composite is a
        protocol violation — the sum cannot be partially applied."""
        clocks = {c for _, c in comp.members}
        if len(clocks) != 1:
            raise ValueError(
                f"summed composite spans clocks {sorted(clocks)}")
        clock = next(iter(clocks))
        live, dup = [], []
        for worker, c in comp.members:
            if not self.tracker.tracker[worker].active:
                raise ValueError(
                    f"summed composite includes evicted worker {worker}")
            (dup if self.tracker.is_duplicate(worker, c)
             else live).append(worker)
        if not live:
            # whole-composite redelivery (aggregator restart): already
            # applied — re-issue any already-released replies that may
            # have died with the aggregator, drop the delta
            self.tracer.count("server.duplicate_gradients_dropped")
            for worker in dup:
                status = self.tracker.tracker[worker]
                if status.weights_message_sent:
                    self.send_weights(worker, status.vector_clock)
            return
        if dup:
            raise ValueError(
                f"summed composite partially applied: duplicates {dup} "
                f"alongside live members {live}")
        delta = comp.deltas[0]
        for worker in live:
            self.tracker.received_message(worker, clock)
            self.tracer.count("server.gradients_applied")
            if self.telemetry.enabled:
                self._observe_arrival(worker, clock)
            if FLIGHT.enabled:
                self._flight_arrival(worker, clock)
        fid = getattr(delta, "trace", None)
        self._pending_trace = fid
        want_eval = (0 in live and self.test_x is not None
                     and clock % self.cfg.eval_every == 0)
        defer_eval = want_eval and self.eval_engine is not None
        fused_eval = want_eval and not defer_eval
        m = None
        with self.tracer.span("server.apply", agg=comp.agg_id,
                              fan_in=len(live), clock=clock,
                              shard=self.shard_id, model=self._model):
            if fused_eval:
                with self.tracer.span("server.eval", clock=clock):
                    self.theta, m = self._apply_full_eval(
                        jnp.asarray(self.theta), delta.values,
                        self.test_x, self.test_y)
            else:
                self.theta = self._apply_full(jnp.asarray(self.theta),
                                              delta.values)
            self.tracer.count("dispatch.device")
            self.iterations += len(live)
            device.mark("first_update")
        if fused_eval:
            self._emit_eval(clock, m)
        elif defer_eval:
            # self.theta is replaced (never mutated) by later applies, so
            # handing the alias to the engine's queue is safe — the
            # snapshot-registry immutability contract (serving/snapshot.py)
            self.eval_engine.submit(self.theta, clock)
        release: set = set()
        for worker in live:
            release |= self.workers_to_respond_to(clock, worker)
        self.dispatch_release_set(release)
        self._pending_trace = None
        self.maybe_checkpoint()

    def process_batch(self, msgs: list[GradientMessage]) -> None:
        """Apply several queued gradients as ONE chained jit dispatch
        (gang dispatch, docs/GANG_DISPATCH.md) — bitwise-identical to
        calling `process` per message, cheaper by k-1 device round-trips.

        Per-message semantics are preserved exactly:
          * validation (zombie/duplicate drops) and the consistency gate
            run INCREMENTALLY per message, in queue order — the gate for
            message i sees the tracker state messages 0..i left behind,
            so release decisions match the per-message path;
          * gate bookkeeping (tracker.sent_message) happens at decision
            time, but the fabric sends are deferred until the batched
            apply yields each release's PREFIX theta — a mid-batch
            release observes theta after exactly the deltas the
            per-message path would have applied before it;
          * evals land at the same clocks, computed on the same prefix
            thetas, logged in the same row order;
          * the update itself is a chain of adds inside one jit —
            NOT deltas.sum(0), which is mathematically identical but
            not bitwise (float addition is non-associative).
        Checkpointing runs once at batch end (the crossing-based
        trigger still fires on schedule); cadence is not part of the
        bitwise contract.  Partial-range gradients (range sharding)
        fall back to per-message processing.
        """
        if self.param_store is not None:
            # the gang chain wants the whole slice in one device array;
            # with tiered residency attached, fall back to per-message
            # processing — bitwise-equivalent by the gang contract
            # itself (docs/GANG_DISPATCH.md, tests/test_gang.py), just
            # without the k-1 round-trip saving
            for m in msgs:
                self.process(m)
            return
        full = all(getattr(m, "indices", None) is None
                   and m.key_range.start == self._range.start
                   and m.key_range.end == self._range.end
                   for m in msgs)
        if not full:
            for m in msgs:
                self.process(m)
            return
        # duplicate detection must see the clock advancement the EARLIER
        # batch members will cause — a redelivered gradient can appear
        # twice in one recovered backlog (at-least-once replay), and the
        # per-message path would apply the first and drop the second.
        # Simulate the advancement here; the tracker itself moves below.
        live = []
        ahead: dict[int, int] = {}
        for m in msgs:
            if not self.tracker.tracker[m.worker_id].active:
                self.tracer.count("server.zombie_gradients_dropped")
                continue
            expected = ahead.get(
                m.worker_id, self.tracker.tracker[m.worker_id].vector_clock)
            if m.vector_clock < expected:
                self.tracer.count("server.duplicate_gradients_dropped")
                continue
            ahead[m.worker_id] = m.vector_clock + 1
            live.append(m)
        if len(live) < 2:
            for m in live:           # process() re-validates (no-op here)
                self.process(m)
            return

        k = len(live)
        defer_eval = self.eval_engine is not None
        eval_events: list[tuple[int, int]] = []   # (position, clock)
        release_events: list[tuple[int, list[tuple[int, int]]]] = []
        snap_clocks: dict[int, int] = {}
        for i, m in enumerate(live):
            self.tracker.received_message(m.worker_id, m.vector_clock)
            self.tracer.count("server.gradients_applied")
            if self.telemetry.enabled:
                self._observe_arrival(m.worker_id, m.vector_clock)
            if FLIGHT.enabled:
                self._flight_arrival(m.worker_id, m.vector_clock)
            if self.modelhealth.enabled:
                self.modelhealth.observe_update(m.worker_id, m.values)
            if (m.worker_id == 0 and self.test_x is not None
                    and m.vector_clock % self.cfg.eval_every == 0):
                eval_events.append((i, m.vector_clock))
            release = sorted(self.workers_to_respond_to(m.vector_clock,
                                                        m.worker_id))
            for w, c in release:
                self.tracker.sent_message(w, c)
            if release:
                release_events.append((i, release))
                if self.serving is not None:
                    # stable clock at gate-DECISION time: tracker state
                    # here matches the per-message path after message i
                    # (sent_message never moves clocks), so the published
                    # (theta_i, clock) sequence is bitwise-identical to
                    # processing the batch one message at a time
                    snap_clocks[i] = self.serving_clock()
        # releases at the last position see the final theta; earlier
        # ones need their prefix returned from the jit.  Deferred evals
        # turn their positions into prefix requests too — the engine
        # evaluates the SAME prefix theta the fused program would have,
        # it just does so off the apply path.
        prefix_need = {i for i, _ in release_events if i < k - 1}
        if defer_eval:
            prefix_need |= {i for i, _ in eval_events if i < k - 1}
            eval_positions: tuple = ()
        else:
            eval_positions = tuple(i for i, _ in eval_events)
        prefix_positions = tuple(sorted(prefix_need))
        fn = self._gang_apply_fn(k, eval_positions, prefix_positions)
        # same span name as the per-message path — one entry now covers
        # k chained applies (the `gang` arg distinguishes the two)
        with self.tracer.span("server.apply", gang=k,
                              workers=[m.worker_id for m in live],
                              model=self._model):
            final_theta, prefixes, metrics = fn(
                jnp.asarray(self.theta), self.test_x, self.test_y,
                *[m.values for m in live])
            self.iterations += k
            device.mark("first_update")
            for m in live:
                fid = getattr(m, "trace", None)
                if fid is not None:
                    self.tracer.flow_step("delta.wire", fid,
                                          clock=m.vector_clock)
        self.tracer.count("dispatch.device")
        self.tracer.count("server.gang_batched_applies")
        self.theta = final_theta
        prefix_theta = dict(zip(prefix_positions, prefixes))
        release_at = dict(release_events)
        eval_at = dict(eval_events)
        mi = 0
        batch_released: list[tuple[int, int]] = []
        for i, m in enumerate(live):
            if i in eval_at and not defer_eval:
                # the eval itself ran fused inside the batched apply;
                # this span marks where its results enter the protocol
                with self.tracer.span("server.eval",
                                      clock=m.vector_clock, fused=True):
                    met = metrics[mi]
                    mi += 1
                    self._emit_eval(m.vector_clock, met)
            elif i in eval_at:
                # deferred: hand the engine the prefix theta this clock
                # observed — the exact array the fused program would have
                # evaluated (final_theta for the last position)
                self.eval_engine.submit(
                    prefix_theta.get(i, final_theta), eval_at[i])
            rel = release_at.get(i)
            if rel:
                theta_i = prefix_theta.get(i, final_theta)
                handled = self._group_send(
                    rel, lambda clock: self._prepared_message(clock,
                                                              theta_i))
                for worker, clock in rel:
                    if worker in handled:
                        # gate bookkeeping (tracker.sent_message) ran at
                        # decision time above — stamp/metrics only here,
                        # matching _send_weights_prepared
                        self.weights_sent_at[worker] = time.monotonic()
                        self._observe_gate_release(worker)
                        if FLIGHT.enabled:
                            FLIGHT.record("gate.release",
                                          shard=self.shard_id,
                                          worker=worker, clock=clock,
                                          gang=True, grouped=True)
                            FLIGHT.beat("gate")
                    else:
                        self._send_weights_prepared(worker, clock,
                                                    theta_i)
                batch_released.extend(rel)
                if self.serving is not None:
                    # gang-path publication point: the prefix theta this
                    # release observed, at the clock captured when the
                    # gate opened — one snapshot per release event, same
                    # as the per-message path
                    self.publish_snapshot(theta_i, snap_clocks[i],
                                          trace=getattr(m, "trace", None))
        # ONE notice for everything this batch released: the release
        # events are simultaneous from the drive loop's point of view
        # (all sends above happened before any worker ran), and the gang
        # stacks per-member thetas, so mid-batch releases with prefix
        # thetas coalesce as well as end-of-batch ones.  This is what
        # lets the eventual model gang in steady state — its per-message
        # releases are all singletons.
        self._emit_gang_notice(sorted(batch_released))
        self.maybe_checkpoint()

    def _gang_apply_fn(self, k: int, eval_positions: tuple,
                       prefix_positions: tuple):
        """One jit'd program per batch shape: chain k updates, returning
        (final theta, prefix thetas at `prefix_positions`, metrics at
        `eval_positions`) — a single dispatch whatever the batch asks."""
        key = (k, eval_positions, prefix_positions)
        fn = self._gang_apply_cache.get(key)
        if fn is None:
            import jax
            lr = self.cfg.server_lr
            task = self.task
            eval_set = frozenset(eval_positions)
            prefix_set = frozenset(prefix_positions)

            def chain(t, tx, ty, *deltas):
                prefixes, metrics = [], []
                for i, d in enumerate(deltas):
                    t = t + lr * d
                    if i in prefix_set:
                        prefixes.append(t)
                    if i in eval_set:
                        metrics.append(task.evaluate(t, tx, ty))
                return t, prefixes, metrics

            fn = jax.jit(chain)
            self._gang_apply_cache[key] = fn
        return fn

    def _prepared_message(self, clock: int, theta) -> WeightsMessage:
        """WeightsMessage over an already-computed (prefix) theta —
        the builder the gang release path hands to grouped fan-out.
        Repeated calls on one theta array reuse the compressor's
        identity cache, so a multi-member release encodes once."""
        encoded = None
        if self.compressor is not None:
            # prefix thetas of one batch are distinct arrays, but a
            # multi-member release at the SAME position reuses the
            # compressor's identity cache
            theta, encoded = self.compressor.encode(theta)
        return WeightsMessage(vector_clock=clock, key_range=self._range,
                              values=theta, encoded=encoded)

    def _send_weights_prepared(self, worker: int, clock: int,
                               theta) -> None:
        """Fabric send for a release whose gate bookkeeping already ran
        (process_batch records tracker.sent_message at gate-decision
        time; the send waits for the batched apply to yield the prefix
        theta this release observes)."""
        self.fabric.send(fabric_mod.WEIGHTS_TOPIC, worker,
                         self._prepared_message(clock, theta))
        self.weights_sent_at[worker] = time.monotonic()
        self._observe_gate_release(worker)
        if FLIGHT.enabled:
            FLIGHT.record("gate.release", shard=self.shard_id,
                          worker=worker, clock=clock, gang=True)
            FLIGHT.beat("gate")

    def maybe_checkpoint(self) -> None:
        """Save once every `checkpoint_every` applied iterations —
        crossing-based so any iteration stride (1 in the message path,
        num_workers in the fused path) triggers on schedule."""
        if self.checkpoint_due():
            self.save_checkpoint_now()

    def checkpoint_due(self) -> bool:
        """Whether `maybe_checkpoint` would save now: a caller that
        keeps theta elsewhere between saves (a folded task's fused
        loop) stores it first."""
        return bool(self.checkpoint_path and self.checkpoint_every > 0
                    and (self.iterations - self._last_checkpoint_iteration
                         >= self.checkpoint_every))

    def save_checkpoint_now(self) -> None:
        """Write the checkpoint, and on a durable fabric
        (log/durable_fabric.py) make it a COMMIT POINT: snapshot the
        consumer offsets the state covers, store them inside the
        checkpoint (authoritative for replay), then durably commit them
        so retention can reap fully-consumed segments.  Order matters —
        offsets are only committed once the checkpoint that covers them
        is on disk, so a crash between the two steps replays extra
        records (at-least-once) instead of losing them."""
        if not self.checkpoint_path:
            return
        from kafka_ps_tpu.utils import checkpoint as ckpt
        offsets = (self.fabric.snapshot_offsets()
                   if getattr(self.fabric, "durable", False) else None)
        ckpt.save(self.checkpoint_path, self,
                  buffers=self.checkpoint_buffers, log_offsets=offsets,
                  residuals=self.checkpoint_residuals)
        if offsets is not None:
            self.fabric.commit(offsets)
        self._last_checkpoint_iteration = self.iterations

"""Worker compute node — behavioral re-design of WorkerTrainingProcessor
(processors/WorkerTrainingProcessor.java:24-138).

On each WeightsMessage: overwrite local parameters with the server's,
snapshot the worker's sliding buffer (a static-shape masked slab — no
per-row range scan), run the jit'd k-step local update on device, log
the worker CSV line, and send the delta back as a GradientMessage with
the same vector clock on the gather topic.

Device-resident hot path: the iteration performs
NO host synchronization — theta and the delta stay jax arrays end to
end (the in-process fabric carries device arrays; serde fetches only at
a socket boundary), the buffer slab is cached on device and re-uploaded
only when `num_tuples_seen` changes, and the log line's loss/F1/
accuracy are deferred futures (utils/asynclog.DeferredSink) so the
evaluation of iteration t overlaps the training of t+1 instead of
blocking it.

The reference's empty-buffer invariant (IllegalStateException,
WorkerTrainingProcessor.java:131-133) is preserved as RuntimeError.
"""

from __future__ import annotations

import functools
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from kafka_ps_tpu.compress import slab as slab_mod
from kafka_ps_tpu.data.buffer import SlidingBuffer
from kafka_ps_tpu.models.task import fit_slab, get_task
from kafka_ps_tpu.runtime import fabric as fabric_mod
from kafka_ps_tpu.runtime.messages import GradientMessage, KeyRange, WeightsMessage
from kafka_ps_tpu.telemetry import NULL_MODEL_HEALTH, NULL_TELEMETRY
from kafka_ps_tpu.utils import asynclog
from kafka_ps_tpu.utils.config import PSConfig
from kafka_ps_tpu.utils.trace import NULL_TRACER

LogSink = Callable[[str], None]


def fit_and_eval(task, leaves, x, y, mask, test_x, test_y):
    """One member's iteration on the parameters' leaves: the k-step
    solver, then the full-test-set evaluation of the post-fit model →
    (delta leaves, loss, f1, accuracy).  The single program below and
    the gang programs (runtime/gang.py, vmapped over the members) run
    this one function.  The evaluated model is `leaves + delta`, what
    the server holds after applying this delta alone — bitwise the
    `theta + delta` of the flat vectors; the fit's own result differs
    from it by one float32 rounding.  The two scopes split the
    program's device time into training and the members' evaluations
    (metadata only)."""
    with jax.named_scope("kps.gang.fit"):
        delta, loss = fit_slab(task, leaves, x, y, mask)
    with jax.named_scope("kps.gang.eval"):
        m = task.evaluate_leaves(jax.tree.map(jnp.add, leaves, delta),
                                 test_x, test_y)
    return delta, loss, m.f1, m.accuracy


@functools.lru_cache(maxsize=None)
def _solver_fns(task_name: str, cfg):
    """One compiled program per (task, cfg) — shared by every WorkerNode
    so N logical workers pay one trace/compile, not N.

    Returns (update, update_and_eval).  The fused variant runs the
    k-step local solver AND the full-test-set evaluation of theta+delta
    as ONE dispatch instead of three (update, theta+delta, evaluate).
    Metric semantics are unchanged — each worker still evaluates its
    own post-fit model, like the reference's in-iteration eval
    (LogisticRegressionTaskSpark.java:186).  Both take and return flat
    vectors (the wire contract) and work on the leaves between."""
    task = get_task(task_name, cfg)

    def update_and_eval(theta, x, y, mask, test_x, test_y):
        delta, *scalars = fit_and_eval(task, task.unflatten(theta),
                                       x, y, mask, test_x, test_y)
        return task.flatten(delta), *scalars

    return jax.jit(task.local_update), jax.jit(update_and_eval)


class WorkerNode:
    """One logical worker: private buffer + full model replica + jit'd
    local solver."""

    def __init__(self, worker_id: int, cfg: PSConfig, fabric: fabric_mod.Fabric,
                 buffer: SlidingBuffer,
                 test_x: np.ndarray | None = None,
                 test_y: np.ndarray | None = None,
                 log: LogSink | None = None,
                 tracer=None, telemetry=None):
        self.tracer = tracer or NULL_TRACER
        self.telemetry = telemetry or NULL_TELEMETRY
        # pre-resolved children: one leaf-lock inc / observe per
        # iteration when telemetry is on, nothing when off
        self._m_updates = self.telemetry.counter(
            "worker_updates_total", worker=str(worker_id))
        self._m_update_ms = self.telemetry.histogram(
            "worker_update_ms", worker=str(worker_id))
        # model-health plane (telemetry/modelhealth.py): in split mode
        # each worker process runs its own plane over its local
        # training rows — set by the CLI wiring when --model-health
        self.modelhealth = NULL_MODEL_HEALTH
        self.worker_id = worker_id
        self.cfg = cfg
        self.fabric = fabric
        self.buffer = buffer
        self.task = get_task(cfg.task, cfg.model)
        self.theta = np.zeros((self.task.num_params,), dtype=np.float32)
        self.test_x = jnp.asarray(test_x) if test_x is not None else None
        self.test_y = jnp.asarray(test_y) if test_y is not None else None
        self.log = log or (lambda line: None)
        # Device-resident slab (compress/slab.SlabStore,
        # docs/PERFORMANCE.md): the buffer slab lives on device in
        # cfg.slab_dtype storage, keyed by the buffer's mutation
        # counter.  Steady state uploads only the dirty rows
        # (O(changed rows) bytes) via a jit'd scatter; the full
        # re-upload remains the bootstrap/restore/mass-churn fallback.
        self._slab_version: int | None = None
        self._slab_store = slab_mod.SlabStore(
            cfg.slab_dtype, buffer.cfg.max_size, buffer.num_features,
            telemetry=self.telemetry, row_dtype=buffer.dtype)
        self.iterations = 0
        # iterations counted at (re)admission: the supervisor grants the
        # jit-compile grace to the first iteration *since joining*, not
        # just the process-lifetime first (runtime/app.py supervisor)
        self.iterations_at_join = 0
        # failure-detection heartbeat (read by the supervisor in
        # runtime/app.py): wall-clock of the last completed iteration
        self.last_progress = time.monotonic()
        # gradient-side compression (compress.ErrorFeedback, set by the
        # app/CLI wiring when --compress != none): each outgoing delta
        # is error-compensated, encoded and decoded on device; the
        # residual is part of this worker's checkpointable state
        self.compressor = None
        # (clock, GradientMessage) of the newest compressed send: crash
        # recovery redelivers weights clocks the worker already trained
        # on (the recovering gate re-releases what the replay also
        # re-enqueues).  Stateless workers just recompute and let the
        # server's clock filter drop the duplicate, but an EF residual
        # must advance exactly once per clock — duplicates resend this
        # cached message instead (_redelivered_weights)
        self._last_sent = None
        # range sharding (runtime/sharding.ShardRouter, set by the
        # group/CLI wiring when the server side runs N>1 shards): each
        # outgoing delta splits into per-shard slices pushed to the
        # owning shards instead of one full-range send.  None keeps the
        # unsharded send path — the N=1 protocol, bitwise today's.
        self.shard_router = None

    def _prepare(self, msg: WeightsMessage):
        """Pre-dispatch half of an iteration, shared by the single-
        dispatch path (on_weights) and the gang path (runtime/gang.py):
        heartbeat, theta overwrite, slab snapshot/version cache.
        Returns (theta, x, y, mask, num_tuples_seen, want_eval)."""
        # heartbeat: starting an iteration counts as liveness, so a slow
        # (e.g. first-compile) iteration is measured from its own start
        self.last_progress = time.monotonic()
        # Overwrite the local replica with the server's parameters
        # (WorkerTrainingProcessor.java:72).  Full-range messages (the
        # per-node protocol) replace the replica wholesale — a no-op
        # device_put when the in-process fabric delivered a device
        # array; partial KeyRanges take the host splice path.
        r = msg.key_range
        if r.start == 0 and r.end == self.task.num_params:
            self.theta = jnp.asarray(msg.values)
        else:
            # pscheck: disable=PS102 (KeyRange splice is the documented host path)
            host = np.array(self.theta)
            # pscheck: disable=PS102 (KeyRange splice is the documented host path)
            host[r.start:r.end] = np.asarray(msg.values)
            self.theta = host

        seen = self.buffer.num_tuples_seen
        if self.buffer.count == 0:
            # Empty-buffer invariant (WorkerTrainingProcessor.java:131-133).
            raise RuntimeError(
                f"There is no data in the buffer of worker {self.worker_id}")
        ver = self.buffer.version
        if ver != self._slab_version:
            store = self._slab_store
            if not (self.cfg.slab_incremental and store.ready):
                store.upload_full(*self.buffer.snapshot(clear_dirty=True))
            else:
                slots, xr, yr, mr = self.buffer.drain_dirty()
                if 2 * len(slots) >= store.capacity:
                    # mass churn (target-shrink delete storms, restore):
                    # one contiguous upload beats a near-full scatter
                    store.upload_full(
                        *self.buffer.snapshot(clear_dirty=True))
                elif len(slots):
                    store.apply_rows(slots, xr, yr, mr)
            self._slab_version = ver
        x, y, mask = self._slab_store.arrays()
        want_eval = (self.test_x is not None
                     and msg.vector_clock % self.cfg.eval_every == 0)
        return jnp.asarray(self.theta), x, y, mask, seen, want_eval

    def _finish(self, msg: WeightsMessage, seen: int,
                delta, loss, f1, acc) -> None:
        """Post-dispatch half, shared by both paths: the per-worker CSV
        row (fields stay device futures), the iteration count, and the
        per-worker GradientMessage — identical whether the solver ran
        solo or stacked inside a gang."""
        # schema: timestamp;partition;vectorClock;loss;fMeasure;accuracy;
        # numTuplesSeen (WorkerAppRunner.java:80,
        # WorkerTrainingProcessor.java:85-92)
        asynclog.submit_or_write(
            self.log,
            f"{int(time.time() * 1000)};{self.worker_id};"
            f"{msg.vector_clock};{{}};{{}};{{}};{seen}",
            loss, f1, acc)
        self.iterations += 1
        if self.modelhealth.enabled:
            # device futures observed by reference; the plane's sampler
            # thread floats them off the training path
            self.modelhealth.observe_eval(loss, f1)

        encoded = None
        if self.compressor is not None:
            # what the server applies is the DECODED delta (identical on
            # both sides of a socket); the quantization error stays here
            # as the residual folded into the next iteration's delta
            delta, encoded = self.compressor.step(delta)
        out = GradientMessage(
            vector_clock=msg.vector_clock,
            key_range=KeyRange(0, self.task.num_params),
            values=delta,
            encoded=encoded,
            worker_id=self.worker_id)
        if self.shard_router is not None:
            # split by key range and push each slice to its owning
            # shard (the router also caches the slices for shard-crash
            # redelivery, runtime/sharding.py)
            self.shard_router.route(out)
        else:
            self.fabric.send(fabric_mod.GRADIENTS_TOPIC, 0, out)
        if self.compressor is not None:
            self._last_sent = (msg.vector_clock, out)
        if self.telemetry.enabled:
            self._m_updates.inc()
        self.last_progress = time.monotonic()

    def _redelivered_weights(self, msg: WeightsMessage) -> bool:
        """True when `msg` is a weights clock this worker already
        trained on and the step must NOT run again.  Only compressed
        workers dedup: re-running a step would advance the
        error-feedback residual a second time for the same clock,
        which is exactly the bitwise-replay corruption crash recovery
        must avoid (tests/test_log_recovery.py).  The newest clock's
        cached gradient is resent so a gate waiting on this worker
        still completes (the server's clock filter drops it if the
        original got through); older clocks are stale and dropped."""
        if self.compressor is None:
            return False
        last = self._last_sent
        if last is None or msg.vector_clock > last[0]:
            return False
        if msg.vector_clock == last[0]:
            if self.shard_router is not None:
                self.shard_router.route(last[1])
            else:
                self.fabric.send(fabric_mod.GRADIENTS_TOPIC, 0, last[1])
        return True

    def on_weights(self, msg: WeightsMessage) -> None:
        if self._redelivered_weights(msg):
            return
        theta, x, y, mask, seen, want_eval = self._prepare(msg)

        # Post-fit test metrics, like the reference's per-iteration eval
        # inside calculateGradients (LogisticRegressionTaskSpark.java:186).
        # eval_every > 1 skips the full-test-set evaluation on
        # off-cadence clocks, logging the reference's own "-1 = not
        # computed" placeholder (ServerProcessor.java:158-164 uses it
        # for loss).  All numeric fields stay device futures — the line
        # is formatted when they resolve (utils/asynclog.DeferredSink).
        # Eval iterations fuse solver + evaluate into ONE dispatch
        # (_solver_fns).
        update_fn, update_eval_fn = _solver_fns(self.cfg.task,
                                                self.cfg.model)
        f1, acc = -1.0, -1.0
        t0 = time.perf_counter()
        with self.tracer.span("worker.local_update", worker=self.worker_id,
                              clock=msg.vector_clock):
            if want_eval:
                delta, loss, f1, acc = update_eval_fn(
                    theta, x, y, mask, self.test_x, self.test_y)
            else:
                delta, loss = update_fn(theta, x, y, mask)
        self.tracer.count("dispatch.device")
        if self.telemetry.enabled:
            # dispatch wall time, host clocks only — the async dispatch
            # is NOT synced for this (bitwise/latency non-perturbing)
            self._m_update_ms.observe((time.perf_counter() - t0) * 1e3)

        self._finish(msg, seen, delta, loss, f1, acc)

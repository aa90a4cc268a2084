"""Micro-batching prediction engine — gang dispatch for the read path.

Requests queue up; a single batcher thread coalesces them until either
`max_batch` rows are waiting or `deadline_s` has elapsed since the first
row arrived, then runs ONE jit'd forward pass over a padded fixed-shape
batch. The amortization argument is identical to training-side gang
dispatch (docs/GANG_DISPATCH.md): dispatch overhead is per-XLA-call, so
k requests per call cost ~1/k of the per-request dispatch tax.

Under load the engine protects itself instead of queueing to death
(docs/SERVING.md, "Operating at load"):

  * admission control — `queue_limit` bounds each tenant's outstanding
    admitted requests; `submit` on a full queue raises a typed
    `policy.OverloadedError` SYNCHRONOUSLY (the transport answers
    OVERLOADED immediately; nothing is parked behind work that cannot
    meet its deadline).  `shed_deadline_s` additionally sheds when the
    predicted queueing delay (backlog / batch capacity x the EWMA batch
    service time) exceeds the budget, even before the queue fills.
  * adaptive micro-batch sizing — dispatch shapes are power-of-two
    buckets of the live row count, capped at `max_batch`: light load
    pays a small batch's compute, heavy load grows the batch toward the
    cap instead of growing the dispatch count.  At most
    log2(max_batch)+1 compiles per model family (`TRACE_COUNTS`
    regression-tests that bound).

Batching itself is a measured decision, not a policy (`auto=True`,
docs/SERVING.md "Dispatch economics"): each tenant carries a
`DispatchCostModel` (serving/costmodel.py) fed by the same per-dispatch
timings that feed `LatencyRecorder`.  Below the learned break-even
occupancy, `submit` bypasses the queue entirely and serves the request
inline on the caller's thread — no window wait, no batcher handoff;
above it, the batcher's collect window is sized from the live arrival
rate instead of always sleeping the full deadline.  A cold or
uncalibrated engine keeps the batching path (the status quo);
`warmup()` calibrates, so warmed engines pick the right mode from the
first request.

Several model families serve from one engine: tenants register via
`add_model(model_id, task, registry)`, requests carry a model id (wire
trailer in runtime/net.py), and each tenant gets its own snapshot
registry and its own admission budget — a hot tenant sheds without
starving the others.

Each per-tenant micro-batch resolves that tenant's registry ONCE — all
its rows are answered from the same (theta, clock) pair, and each row's
read bound is checked against that snapshot (the registry only ever
serves its newest snapshot, so a bound the newest fails no snapshot
passes; see serving/policy.py).

jax imports are deferred to the first dispatch so thin clients can
import this module (for the Prediction type) without a backend.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, NamedTuple

import numpy as np

from kafka_ps_tpu.analysis.lockgraph import OrderedLock
from kafka_ps_tpu.serving import policy
from kafka_ps_tpu.serving.costmodel import DispatchCostModel
from kafka_ps_tpu.serving.snapshot import SnapshotRegistry
from kafka_ps_tpu.telemetry import NULL_TELEMETRY
from kafka_ps_tpu.telemetry.flight import FLIGHT
from kafka_ps_tpu.utils.trace import NULL_TRACER, LatencyRecorder


class Prediction(NamedTuple):
    label: int             # argmax class
    confidence: float      # softmax mass on the argmax class
    vector_clock: int      # clock of the snapshot that answered
    wall_time: float       # publication time of that snapshot


class _Request(NamedTuple):
    x: np.ndarray
    bound: policy.ReadBound | None
    callback: Callable     # called with Prediction or an Exception
    t0: float              # monotonic enqueue time (latency accounting)
    model_id: int          # tenant the request addresses


class _Tenant:
    """One served model family: its task, snapshot ring, compiled
    forward, dispatch cost model, and admission-budget bookkeeping."""

    __slots__ = ("model_id", "task", "registry", "predict", "depth",
                 "last_traced_seq", "cost", "compiled")

    def __init__(self, model_id: int, task, registry: SnapshotRegistry,
                 max_batch: int):
        self.model_id = model_id
        self.task = task
        self.registry = registry
        self.predict = None        # jit'd forward, built on first dispatch
        self.depth = 0             # admitted-but-unserved requests
        # seq of the last snapshot whose delta.wire flow was closed here:
        # the flow ends once, at the snapshot's FIRST serving read
        self.last_traced_seq = -1
        # dispatch economics (serving/costmodel.py): fed by warmup and
        # every live dispatch, read by submit's bypass decision
        self.cost = DispatchCostModel(max_batch)
        # bucket shapes this tenant's jit has seen: first-seen == one
        # XLA compile (jit caches one program per shape)
        self.compiled: set[int] = set()


_SENTINEL = object()

# Compile/dispatch-mode accounting for regression tests (the slab
# TRACE_COUNTS pattern): "compiles" counts first-seen (tenant, bucket)
# dispatch shapes — the test bound is at most one per bucket per model
# family across any batch-size sequence.
TRACE_COUNTS = {"compiles": 0, "batch": 0, "bypass": 0}


def _bucket(n: int, cap: int) -> int:
    """Smallest power of two >= n, capped — the adaptive dispatch shape."""
    b = 1
    while b < n and b < cap:
        b <<= 1
    return min(b, cap)


class PredictionEngine:
    """Deadline/size-capped micro-batcher over per-model snapshot rings
    with bounded admission and explicit load shedding."""

    def __init__(self, task, registry: SnapshotRegistry, *,
                 max_batch: int = 16, deadline_s: float = 0.002,
                 queue_limit: int = 0, shed_deadline_s: float | None = None,
                 adaptive: bool = True, auto: bool = True,
                 tracer=None, telemetry=None, now=time.time):
        self.max_batch = max(1, int(max_batch))
        self.deadline_s = max(0.0, float(deadline_s))
        # 0 = unbounded (the pre-admission-control behavior); > 0 bounds
        # EACH tenant's outstanding admitted requests
        self.queue_limit = max(0, int(queue_limit))
        self.shed_deadline_s = shed_deadline_s
        self.adaptive = adaptive
        # auto dispatch-mode selection: bypass the queue below the cost
        # model's break-even occupancy, size windows from the arrival
        # rate above it.  Decisions only engage once a tenant's model
        # is calibrated (warmup, or live samples covering both ends of
        # the batch-latency curve) — cold engines batch, as before.
        self.auto = bool(auto)
        self.tracer = tracer or NULL_TRACER
        self.telemetry = telemetry or NULL_TELEMETRY
        # pre-resolved metric children (null when telemetry is off):
        # observed per micro-batch, never per row, never on device data
        self._m_snapshot_age = self.telemetry.histogram("snapshot_age_ms")
        self._m_requests = self.telemetry.counter("serving_requests_total")
        self._m_rejections = self.telemetry.counter(
            "serving_rejections_total")
        self._m_queue_depth = self.telemetry.gauge("serving_queue_depth")
        self._m_sheds = self.telemetry.counter("serving_shed_total")
        self._m_batch_size = self.telemetry.histogram("serving_batch_size")
        # per-request wall latency as a bucketed histogram: the serving-
        # latency SLO (telemetry/slo.py) and the rolling critical path
        # need windowed bucket deltas, which the sliding-window
        # LatencyRecorder cannot provide
        self._m_latency = self.telemetry.histogram("serving_latency_ms")
        # dispatch-mode counter family: how often each dispatch path
        # won (the shm transport increments its own child in net.py)
        self._m_mode = {
            "batch": self.telemetry.counter("serving_dispatch_mode",
                                            mode="batch"),
            "bypass": self.telemetry.counter("serving_dispatch_mode",
                                             mode="bypass"),
        }
        self._now = now
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        # admission bookkeeping: depth counters must be exact (they gate
        # sheds), so they move under one leaf lock, never nested
        self._admission = OrderedLock("PredictionEngine.admission")
        # guarded-by: _admission (queue_depth/stats reads are lock-free gauge snapshots)
        self._depth = 0            # total admitted-but-unserved requests
        # inline bypass serves currently running on caller threads:
        # while one is in flight, new arrivals take the queue — that
        # overflow is how sustained concurrency shows up in the cost
        # model's demand signal and flips the engine back to batching
        self._bypassing = 0
        self._ewma_batch_s: float | None = None
        # guarded-by: _admission (add_model writes hold it; steady-state reads are GIL-atomic dict gets)
        self._tenants: dict[int, _Tenant] = {
            0: _Tenant(0, task, registry, self.max_batch)}
        self.latency = LatencyRecorder()
        # cumulative counters; status() exposes requests as a *_per_s key
        # guarded-by: _admission (stats reads are lock-free snapshots)
        self.requests = 0
        # guarded-by: _admission (stats reads are lock-free snapshots)
        self.batches = 0          # device dispatches (== jit calls)
        # guarded-by: _admission (stats reads are lock-free snapshots)
        self.batched_rows = 0     # rows that made it into a dispatch
        # guarded-by: _admission (stats reads are lock-free snapshots)
        self.rejections = 0       # staleness rejections
        self.sheds = 0            # admission-control sheds (typed)
        # guarded-by: _admission (stats reads are lock-free snapshots)
        self.bypasses = 0         # requests served on the fast path
        # guarded-by: _admission (stats reads are lock-free snapshots)
        self.errors = 0
        self._closed = False
        self._thread = threading.Thread(
            target=self._loop, name="kps-serve-batch", daemon=True)
        self._thread.start()

    # model-0 aliases — the single-tenant surface every existing caller
    # (runtime/app.py, cli/, tests) keeps using unchanged
    @property
    def task(self):
        return self._tenants[0].task

    @property
    def registry(self) -> SnapshotRegistry:
        return self._tenants[0].registry

    # -- multi-model surface -------------------------------------------------
    def add_model(self, model_id: int, task,
                  registry: SnapshotRegistry | None = None,
                  capacity: int = 8) -> SnapshotRegistry:
        """Register another served model family.  Returns its registry
        (created fresh when none is passed)."""
        model_id = int(model_id)
        with self._admission:
            if model_id in self._tenants:
                raise ValueError(f"model {model_id} already registered")
            reg = registry if registry is not None \
                else SnapshotRegistry(capacity=capacity)
            self._tenants[model_id] = _Tenant(model_id, task, reg,
                                              self.max_batch)
            return reg

    def model_ids(self) -> tuple[int, ...]:
        return tuple(sorted(self._tenants))

    def registry_for(self, model_id: int) -> SnapshotRegistry:
        return self._tenants[model_id].registry

    # -- request entry points ----------------------------------------------
    def submit(self, x, bound: policy.ReadBound | None = None,
               callback: Callable = lambda result: None, *,
               model_id: int = 0) -> None:
        """Async predict: callback fires on the batcher thread with a
        Prediction, or with the StalenessError/Exception that killed the
        request. Never blocks the caller; raises
        policy.OverloadedError synchronously when admission control
        sheds the request (reject fast — nothing is enqueued)."""
        if self._closed:
            raise RuntimeError("prediction engine is closed")
        tenant = self._tenants.get(model_id)
        if tenant is None:
            raise ValueError(f"unknown model id {model_id}")
        with self._admission:
            if self.queue_limit and tenant.depth >= self.queue_limit:
                self._shed(tenant, f"admission queue full "
                                   f"({tenant.depth}/{self.queue_limit})")
            if self.shed_deadline_s is not None \
                    and self._ewma_batch_s is not None:
                # predicted queueing delay: batches ahead of this row x
                # the EWMA batch service time — when that already blows
                # the deadline budget, queueing is a slower way to fail
                predicted = ((self._depth // self.max_batch + 1)
                             * self._ewma_batch_s)
                if predicted > self.shed_deadline_s:
                    self._shed(tenant,
                               f"predicted queueing delay "
                               f"{predicted * 1e3:.1f}ms > shed deadline "
                               f"{self.shed_deadline_s * 1e3:.1f}ms")
            tenant.depth += 1
            self._depth += 1
            tenant.cost.observe_arrival(time.monotonic())
            # bypass decision, made per request at admission: below the
            # learned engage threshold batching buys nothing — serve on
            # the caller's thread (no window wait, no batcher handoff).
            # Two inline lanes run concurrently with the batcher: the
            # jit'd forward is thread-safe and releases the GIL inside
            # XLA, so a second lane overlaps real compute while the
            # queue keeps the overflow; past two lanes the marginal
            # inline serve just adds scheduler contention, and overflow
            # through the queue is what feeds the demand estimate that
            # re-engages batching under sustained concurrency.
            bypass = (self.auto and self._bypassing < 2
                      and tenant.cost.bypass())
            if bypass:
                self._bypassing += 1
            if self.telemetry.enabled:
                self._m_queue_depth.set(self._depth)
        # pscheck: disable=PS102 (client boundary: coerces caller-supplied x)
        row = np.asarray(x, dtype=np.float32).reshape(-1)
        req = _Request(row, bound, callback, time.monotonic(), model_id)
        if bypass:
            try:
                self._serve([req], mode="bypass")
            finally:
                with self._admission:
                    self._bypassing -= 1
        else:
            self._q.put(req)

    def _shed(self, tenant: _Tenant, why: str):
        """Count + raise the typed rejection (admission lock held)."""
        self.sheds += 1
        self.tracer.count("serving.sheds")
        if self.telemetry.enabled:
            self._m_sheds.inc()
        raise policy.OverloadedError(
            f"request shed: {why}", queue_depth=tenant.depth,
            queue_limit=self.queue_limit or None, model_id=tenant.model_id)

    def predict(self, x, bound: policy.ReadBound | None = None, *,
                min_clock: int | None = None, max_age_s: float | None = None,
                model_id: int = 0, timeout: float = 30.0) -> Prediction:
        """Sync predict; raises StalenessError if the bound rejects and
        OverloadedError if admission control sheds."""
        if bound is None and (min_clock is not None or max_age_s is not None):
            bound = policy.ReadBound(min_clock=min_clock, max_age_s=max_age_s)
        done = threading.Event()
        box: list = []

        def _cb(result):
            box.append(result)
            done.set()

        self.submit(x, bound, _cb, model_id=model_id)
        if not done.wait(timeout):
            raise TimeoutError("prediction timed out")
        result = box[0]
        if isinstance(result, BaseException):
            raise result
        return result

    # -- batcher loop -------------------------------------------------------
    def _loop(self) -> None:
        while True:
            first = self._q.get()
            if first is _SENTINEL:
                return
            batch = [first]
            stop = False
            # instant drain: rows already queued joined while the last
            # window served — batching them costs no wait at all.  A
            # calibrated auto engine sizes the drain by regime: below
            # the engage threshold it serves ONE row per cycle (the
            # serial path — wake-ups stay staggered, the standing
            # backlog keeps the batcher hot, exactly the dynamics that
            # make an unbatched engine fast); once batching engages it
            # drains the backlog but LEAVES ONE ROW BEHIND, so the
            # batcher re-enters get() without parking on the futex and
            # client wake-ups overlap the next dispatch instead of
            # bursting behind a sleeping thread.  The leftover waits
            # exactly one dispatch, never a window.
            limit = self.max_batch
            if self.auto:
                cost = self._tenants[first.model_id].cost
                if cost.calibrated:
                    limit = 1 if cost.bypass() \
                        else min(limit, max(1, self._q.qsize()))
            while len(batch) < limit:
                try:
                    nxt = self._q.get_nowait()
                except queue.Empty:
                    break
                if nxt is _SENTINEL:
                    stop = True
                    break
                batch.append(nxt)
            # window sizing: a calibrated auto engine waits only as
            # long as the live arrival rate needs to fill the batch
            # (zero in the bypass regime); otherwise the configured
            # deadline, the pre-cost-model behavior.  The window opens
            # ONLY when the drain ran the queue dry — with a standing
            # backlog the batch already sized itself to the load, and
            # waiting on top of rows in hand just stalls the pipeline.
            if not stop and len(batch) < limit:
                deadline = time.monotonic() + self._window_s(first)
                while len(batch) < self.max_batch:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    try:
                        nxt = self._q.get(timeout=remaining)
                    except queue.Empty:
                        break
                    if nxt is _SENTINEL:
                        stop = True
                        break
                    batch.append(nxt)
            self._serve(batch)
            if stop:
                return

    def queue_depth(self) -> int:
        """Admitted-but-unserved requests right now (host int; the
        serving watchdog's demand predicate, telemetry/health.py)."""
        return self._depth

    def _window_s(self, first: _Request) -> float:
        tenant = self._tenants[first.model_id]
        if self.auto and tenant.cost.calibrated:
            return tenant.cost.window_s(1, self.deadline_s)
        return self.deadline_s

    def _serve(self, batch: list[_Request], mode: str = "batch") -> None:
        cost = self._tenants[batch[0].model_id].cost
        with self._admission:
            self.requests += len(batch)
            if mode == "bypass":
                self.bypasses += len(batch)
            for req in batch:
                self._tenants[req.model_id].depth -= 1
            self._depth -= len(batch)
            if self.telemetry.enabled:
                self._m_queue_depth.set(self._depth)
        TRACE_COUNTS[mode] += 1
        if FLIGHT.enabled:
            FLIGHT.record("serving.batch", n=len(batch),
                          depth=self._depth, mode=mode,
                          occupancy=round(cost.occupancy, 2),
                          break_even=round(cost.break_even, 2))
            FLIGHT.beat("serving")
        if self.telemetry.enabled:
            self._m_requests.inc(len(batch))
            self._m_mode[mode].inc()
        # what a full drain could have collected right now — the demand
        # sample the cost model sizes future windows against (None for
        # bypass serves, which never see the queue)
        avail = None
        if mode == "batch":
            avail = min(self.max_batch, len(batch) + self._q.qsize())
        # group by tenant, preserving arrival order within each group:
        # one collected window serves every model family present in it
        # (round-robin over model ids — no tenant waits an extra window)
        groups: dict[int, list[_Request]] = {}
        for req in batch:
            groups.setdefault(req.model_id, []).append(req)
        t_start = time.monotonic()
        for model_id in sorted(groups):
            self._serve_tenant(self._tenants[model_id],
                               groups[model_id], mode, avail)
        # EWMA of the window's service time feeds predictive shedding
        dt = time.monotonic() - t_start
        with self._admission:
            self._ewma_batch_s = dt if self._ewma_batch_s is None \
                else 0.2 * dt + 0.8 * self._ewma_batch_s

    def _serve_tenant(self, tenant: _Tenant, batch: list[_Request],
                      mode: str = "batch",
                      avail: int | None = None) -> None:
        # one snapshot resolution per tenant micro-batch: every row is
        # answered from the same hot-swapped (theta, clock) pair
        snap = tenant.registry.latest
        now = self._now()
        if self.telemetry.enabled and snap is not None:
            # read-side staleness: how old the answering snapshot is
            # at serve time (host floats; one sample per micro-batch)
            self._m_snapshot_age.observe(
                max(0.0, (now - snap.wall_time) * 1e3))
        live: list[_Request] = []
        for req in batch:
            try:
                policy.check(snap, req.bound, now)
            except policy.StalenessError as err:
                with self._admission:
                    self.rejections += 1
                self.tracer.count("serving.staleness_rejections")
                if self.telemetry.enabled:
                    self._m_rejections.inc()
                self._finish(req, err)
                continue
            live.append(req)
        if not live:
            return
        try:
            labels, confs = self._dispatch(tenant, snap, live, mode, avail)
        except Exception as err:  # noqa: BLE001 — fail the rows, not the loop
            with self._admission:
                self.errors += 1
            for req in live:
                self._finish(req, err)
            return
        with self._admission:
            # bypass serves run on caller threads, concurrent with the
            # batcher: dispatch counters move under the same leaf lock
            # as the depth bookkeeping
            self.batches += 1
            self.batched_rows += len(live)
        self.tracer.count("serving.batch_dispatches")
        if self.telemetry.enabled:
            self._m_batch_size.observe(len(live))
        for i, req in enumerate(live):
            # labels/confs are host arrays by here
            self._finish(req, Prediction(int(labels[i]), float(confs[i]),
                                         snap.vector_clock, snap.wall_time))

    def _dispatch(self, tenant: _Tenant, snap, live: list[_Request],
                  mode: str = "batch", avail: int | None = None):
        fn = self._predict_fn(tenant)
        # adaptive shape: a power-of-two bucket of the live count means
        # light load dispatches a small batch's compute while heavy load
        # grows toward max_batch — batch size, not dispatch count,
        # absorbs the offered rate (jit caches one program per bucket)
        rows = _bucket(len(live), self.max_batch) if self.adaptive \
            else self.max_batch
        self._note_shape(tenant, rows)
        t0 = time.monotonic()
        xs = np.zeros((rows, tenant.task.cfg.num_features),
                      dtype=np.float32)
        for i, req in enumerate(live):
            xs[i, :req.x.size] = req.x[:xs.shape[1]]
        with self.tracer.span("serving.predict", rows=len(live)):
            if snap.trace is not None and snap.seq > tenant.last_traced_seq:
                # close the delta.wire flow on this snapshot's FIRST
                # serving read: buffer -> solve -> wire -> apply ->
                # publish -> here, one connected arrow chain in Perfetto
                tenant.last_traced_seq = snap.seq
                self.tracer.flow_end("delta.wire", snap.trace,
                                     clock=snap.vector_clock)
            labels, confs = fn(snap.theta, xs)
            # block so latency samples measure real service time
            labels = np.asarray(labels)  # pscheck: disable=PS102 (deliberate latency-sample sync)
            confs = np.asarray(confs)  # pscheck: disable=PS102 (deliberate latency-sample sync)
        # the same sample that feeds LatencyRecorder/tracing calibrates
        # the cost model: assembly + device call + sync, one bucket
        tenant.cost.observe_dispatch(len(live), rows,
                                     time.monotonic() - t0,
                                     batched=(mode == "batch"),
                                     avail=avail)
        return labels, confs

    def _note_shape(self, tenant: _Tenant, rows: int) -> None:
        """First-seen dispatch shapes are XLA compiles (jit caches one
        program per shape) — the TRACE_COUNTS regression surface."""
        fresh = False
        with self._admission:
            if rows not in tenant.compiled:
                tenant.compiled.add(rows)
                fresh = True
        if fresh:
            TRACE_COUNTS["compiles"] += 1

    def _predict_fn(self, tenant: _Tenant):
        if tenant.predict is None:
            import jax
            import jax.numpy as jnp

            task = tenant.task

            def _forward(theta, x):
                lg = task.predict_logits(theta, x)
                probs = jax.nn.softmax(lg, axis=-1)
                return jnp.argmax(lg, axis=-1), jnp.max(probs, axis=-1)

            # double-checked under the admission lock: bypass serves
            # run on caller threads, so two first dispatches can race
            # here — exactly one jit (and its shape cache) must win
            with self._admission:
                if tenant.predict is None:
                    tenant.predict = jax.jit(_forward)  # pscheck: disable=PS101 (built once, cached on the tenant)
        return tenant.predict

    def warmup(self, model_id: int = 0) -> int:
        """Compile every adaptive bucket shape for a tenant against its
        current snapshot (no-op when none is published).  Call before
        measuring latency: a first-request XLA compile is orders of
        magnitude over the deadline and would land in some poor
        client's p99.  Each bucket is then timed with a SECOND,
        compile-free call to seed the dispatch cost model — a warmed
        engine is calibrated before its first request.  Returns the
        number of shapes compiled."""
        tenant = self._tenants[model_id]
        snap = tenant.registry.latest
        if snap is None:
            return 0
        fn = self._predict_fn(tenant)
        shapes = 0
        b = 1 if self.adaptive else self.max_batch
        while True:
            xs = np.zeros((b, tenant.task.cfg.num_features), np.float32)
            labels, _ = fn(snap.theta, xs)
            np.asarray(labels)          # sync: compile finished
            self._note_shape(tenant, b)
            t0 = time.monotonic()
            labels, _ = fn(snap.theta, xs)
            np.asarray(labels)          # sync: steady-state timing
            tenant.cost.seed(b, time.monotonic() - t0)
            shapes += 1
            if b >= self.max_batch:
                return shapes
            b <<= 1

    def _finish(self, req: _Request, result) -> None:
        elapsed = time.monotonic() - req.t0
        self.latency.record(elapsed)
        if self.telemetry.enabled:
            self._m_latency.observe(elapsed * 1e3)
        try:
            req.callback(result)
        except Exception:  # noqa: BLE001 — a bad callback must not stall serving
            self.tracer.count("serving.callback_errors")

    # -- ops surface --------------------------------------------------------
    def stats(self) -> dict:
        occupancy = (round(self.batched_rows / self.batches, 2)
                     if self.batches else 0.0)
        cost = self._tenants[0].cost
        out = {"requests": self.requests, "batches": self.batches,
               "occupancy": occupancy, "rejections": self.rejections,
               "sheds": self.sheds, "queue_depth": self._depth,
               "errors": self.errors, "bypasses": self.bypasses,
               # the regime the next lone request would be served in
               "mode": ("bypass" if self.auto and cost.bypass()
                        else "batch"),
               "break_even": round(cost.break_even, 2),
               "arrival_qps": round(cost.arrival_qps, 1)}
        out.update(self.latency.percentiles_ms(50, 99))
        return out

    def close(self, timeout: float = 30.0) -> None:
        """Stop the batcher thread. Must run before interpreter exit —
        the thread holds jit'd callables (native code)."""
        if self._closed:
            return
        self._closed = True
        self._q.put(_SENTINEL)
        self._thread.join(timeout)

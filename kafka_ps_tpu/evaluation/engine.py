"""Async coalescing eval engine — continuous test-set evaluation off
the apply critical path (docs/EVALUATION.md "Async evaluation").

The reference evaluates the full test set inside every server iteration
(ServerProcessor.java:153-165); our fused port kept that shape — eval
rides the apply dispatch (`ServerNode._apply_full_eval`), so each eval
re-reads the whole test set for a single theta.  What that costs on
the chip is not measured yet (PERF.md).

This engine is the serving plane's batching economics (Clipper-style,
serving/engine.py) applied to evaluation:

  * the server hands over `(theta, clock)` pairs with an O(1) append —
    thetas are immutable device aliases by the same contract that lets
    serving snapshots alias them (serving/snapshot.py module doc:
    ServerNode only ever REPLACES theta, never mutates it), so enqueue
    costs no copy and no host sync;
  * a dedicated `kps-eval` thread pops the whole backlog and evaluates
    k pending thetas as ONE batched dispatch — the vmap construction
    the gang solvers use (runtime/gang.py stacks thetas the same way).
    Each row's F1/accuracy equal a standalone eval of that theta; the
    loss agrees to float32 tolerance — XLA may reduce the loss mean of
    a wider vmap program in another order (1 ulp at width 8 under
    jaxlib 0.9.0 on the CPU; tests/test_eval_engine.py states it);
  * results are emitted in strict clock order whatever the coalescing
    did, through the SAME emission point the fused path uses
    (`ServerNode._emit_eval`): CSV rows, `last_metrics`, and
    `DriftMonitor.observe_eval` see the exact fused-path sequence.

Coalescing widths bucket to powers of two (pad by REPEATING the last
theta and discard the extra rows — vmap rows are independent, so
padding never changes the kept rows) and are capped by a byte budget
(`coalesce_width_cap`): chunking happens over
pending thetas, NEVER over the test set — splitting X_test would
change what the loss mean averages over, not just its rounding.

A dispatch that raises (a compile failure, a device error) is KEPT:
the engine stops, and the error re-raises to the next submitter, from
`drain()` and from `close()` — a run whose eval rows stopped coming
must not exit 0.

Crash story: the engine holds no durable state.  Pending-eval clocks
are exactly the eval-cadence clocks of gradients the durable log will
replay (log/durable_fabric.py) — a restarted server re-applies them
and re-submits the same (theta, clock) pairs, so no new checkpoint
state exists (tier1.sh --eval pins this under SIGKILL).

pscheck scope: PS102 (no host sync in submit/dispatch), PS104 (no wall
clock — timestamps belong to the emission callback, which lives in
runtime/server.py), PS106 (telemetry calls carry host ints only).
"""

from __future__ import annotations

import threading
from collections import deque

from kafka_ps_tpu.analysis.lockgraph import OrderedCondition
from kafka_ps_tpu.telemetry import NULL_TELEMETRY
from kafka_ps_tpu.telemetry.flight import FLIGHT
from kafka_ps_tpu.utils.trace import NULL_TRACER

# hard ceiling on a single batched dispatch, independent of the byte
# budget: beyond this the stacked matmul stops gaining and the jit
# program zoo grows for nothing
_MAX_COALESCE = 32

# coalesce-width histogram buckets (powers of two up to the ceiling)
WIDTH_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0)

# bytes one batched eval dispatch's stacked working set may take
_COALESCE_BYTE_BUDGET = 12 * 1024 * 1024


def coalesce_width_cap(num_params: int, n_test: int,
                       budget: int = _COALESCE_BYTE_BUDGET) -> int:
    """Widest power-of-two batch such that the stacked working set
    (k thetas + k per-example score rows against the resident test set)
    stays inside `budget` bytes.  The estimate charges
    one f32 per test row per lane — the score/prediction row the
    confusion-matrix build materializes (models/metrics.py) — plus the
    lane's theta; deliberately coarse, it only has to keep `n_test x k`
    from outgrowing the budget, not model fast memory exactly."""
    lane_bytes = 4 * (int(num_params) + int(n_test))
    cap = max(1, int(budget) // max(lane_bytes, 1))
    width = 1
    while width * 2 <= min(cap, _MAX_COALESCE):
        width *= 2
    return width


class EvalEngine:
    """Dedicated eval thread over a bounded (theta, clock) queue.

    `emit(clock, metrics)` is called on the engine thread in strict
    clock order — the caller owns row formatting, timestamps and
    downstream fan-out (ServerNode._emit_eval / the sharded group's
    row writer), so this module stays free of wall-clock reads.

    The thread is lazy and self-reaping (the DeferredSink discipline,
    utils/asynclog.py): started on first submit, exits after
    `idle_exit` seconds with nothing pending, restarted by the next
    submit — a process must never finalize with a live thread inside
    XLA (docs/TESTING.md).
    """

    def __init__(self, task, test_x, test_y, emit, *,
                 max_pending: int = 64, max_width: int | None = None,
                 telemetry=None, tracer=None,
                 start_thread: bool = True,
                 idle_exit: float = 10.0):
        import jax.numpy as jnp
        self._task = task
        self._tx = jnp.asarray(test_x)
        self._ty = jnp.asarray(test_y)
        self._emit = emit
        self._max_pending = int(max_pending)
        self._max_width = int(max_width) if max_width else \
            coalesce_width_cap(task.num_params, self._tx.shape[0])
        self._start_thread = start_thread
        self._idle_exit = idle_exit
        self.tracer = tracer or NULL_TRACER
        self.telemetry = telemetry or NULL_TELEMETRY
        self._m_lag = self.telemetry.gauge(
            "eval_lag_clocks",
            help_text="newest submitted eval clock minus newest "
                      "evaluated eval clock (async eval backlog)")
        self._m_width = self.telemetry.histogram(
            "eval_coalesce_width", buckets=WIDTH_BUCKETS,
            help_text="pending thetas coalesced per batched eval "
                      "dispatch")
        # pending (theta, clock) pairs + all engine state, one lock
        self._pending: deque = deque()
        self._cv = OrderedCondition("EvalEngine.pending")
        self._inflight = 0           # popped but not yet emitted
        # guarded-by: _cv — first dispatch failure; once set the engine
        # is dead and every entry point re-raises it
        self._error: BaseException | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # host-side counters for /evalz (telemetry/health.py)
        # guarded-by: _cv (submit writes hold the cv; reads are int snapshots)
        self._submitted_clock = -1
        # pscheck: disable=PS201 (dispatch-side monotonic clock; lag/stats reads tolerate a one-batch-stale snapshot)
        self._evaluated_clock = -1
        # pscheck: disable=PS201 (telemetry counter; racing poll drivers at worst undercount a stat)
        self._dispatches = 0
        # pscheck: disable=PS201 (telemetry counter; racing poll drivers at worst undercount a stat)
        self._evals = 0
        # pscheck: disable=PS201 (telemetry histogram; racing poll drivers at worst undercount a stat)
        self._width_counts: dict[int, int] = {}
        # pscheck: disable=PS201 (jit cache; a racing rebuild traces the same function - idempotent)
        self._programs: dict[int, object] = {}

    # -- producer side (the server's apply path) ---------------------------

    def submit(self, theta, clock: int) -> None:
        """O(1) hand-off of an immutable theta alias at an eval-cadence
        clock.  Never syncs the device and never formats — the whole
        point is that the apply path sheds eval entirely.  A backlog
        past `max_pending` makes the SUBMITTER wait for the engine to
        catch up (each queued theta pins a device array; the bound is
        the memory cap, and dropping is not an option — every clock
        owes a CSV row)."""
        self._raise_if_failed()
        clock = int(clock)
        with self.tracer.span("eval.submit", clock=clock):
            with self._cv:
                self._pending.append((theta, clock))
                self._submitted_clock = clock
                backlog = len(self._pending)
                self._cv.notify_all()
            if self.telemetry.enabled:
                self._m_lag.set(self._submitted_clock
                                - self._evaluated_clock)
            if self._start_thread:
                self._ensure_thread()
            if backlog > self._max_pending:
                self.drain()

    @property
    def lag_clocks(self) -> int:
        """Newest submitted eval clock minus newest evaluated one —
        0 when every released eval clock has been evaluated."""
        if self._submitted_clock < 0:
            return 0
        return self._submitted_clock - self._evaluated_clock

    # -- the kps-eval thread -----------------------------------------------

    def _ensure_thread(self) -> None:
        with self._cv:
            t = self._thread
            if t is None or not t.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name="kps-eval")
                self._thread.start()

    def _raise_if_failed(self) -> None:
        with self._cv:
            err = self._error
        if err is not None:
            raise RuntimeError("eval engine dispatch failed") from err

    def _loop(self) -> None:
        idle = 0.0
        tick = 0.25
        while not self._stop.is_set():
            with self._cv:
                if not self._pending:
                    self._cv.wait(timeout=tick)
            try:
                progressed = self.poll()
            except Exception:
                return      # kept in _error: submit/drain/close re-raise
            if not progressed:
                idle += tick
                if idle >= self._idle_exit:
                    with self._cv:
                        if self._thread is threading.current_thread():
                            self._thread = None
                    return
            else:
                idle = 0.0

    def poll(self) -> bool:
        """Pop up to one coalesced batch and dispatch it.  Returns
        whether anything was evaluated.  Runs on the engine thread in
        steady state; tests and close() call it directly for
        deterministic, caller-thread dispatch."""
        with self._cv:
            if not self._pending:
                return False
            batch = []
            while self._pending and len(batch) < self._max_width:
                batch.append(self._pending.popleft())
            self._inflight = len(batch)
        try:
            self._dispatch(batch)
        except BaseException as e:
            with self._cv:
                if self._error is None:
                    self._error = e
            raise
        finally:
            with self._cv:
                self._inflight = 0
                self._cv.notify_all()
        return True

    def _dispatch(self, batch) -> None:
        """ONE batched eval for the popped backlog, then emission in
        strict clock order.  Width buckets to the next power of two by
        repeating the last theta; the padded rows' outputs are
        discarded (vmap rows are independent — padding is
        bitwise-neutral for the kept rows)."""
        import jax.numpy as jnp
        k = len(batch)
        width = 1
        while width < k:
            width *= 2
        clock_lo, clock_hi = batch[0][1], batch[-1][1]
        with self.tracer.span("server.eval", clock=clock_hi,
                              coalesced=k):
            thetas = [jnp.asarray(t) for t, _ in batch]
            thetas.extend([thetas[-1]] * (width - k))
            mets = self._program(width)(self._tx, self._ty, *thetas)
        self._dispatches += 1
        self._evals += k
        self._width_counts[k] = self._width_counts.get(k, 0) + 1
        if self.telemetry.enabled:
            self._m_width.observe(k)
        if FLIGHT.enabled:
            FLIGHT.record("eval.dispatch", width=k,
                          clock_lo=clock_lo, clock_hi=clock_hi)
        for i, (_, clock) in enumerate(batch):
            self._emit(clock, mets[i])
            self._evaluated_clock = clock
        if self.telemetry.enabled:
            self._m_lag.set(max(
                0, self._submitted_clock - self._evaluated_clock))

    def _program(self, width: int):
        """Cached jit per coalesce width.  Width 1 is the standalone
        eval program; width k vmaps the SAME per-element program over
        stacked thetas (models/task.evaluate_batch) and unstacks the
        per-row metrics INSIDE the jit — fan-out costs no extra
        dispatches (the runtime/gang.py idiom).  The test set rides as
        arguments, exactly as the fused `_apply_full_eval` passes it."""
        fn = self._programs.get(width)
        if fn is None:
            import jax
            import jax.numpy as jnp
            task = self._task
            if width == 1:
                def single(tx, ty, theta):
                    return (task.evaluate(theta, tx, ty),)
                fn = jax.jit(single)
            else:
                def batched(tx, ty, *thetas):
                    met = task.evaluate_batch(jnp.stack(thetas), tx, ty)
                    return tuple(
                        type(met)(f1=met.f1[i], accuracy=met.accuracy[i],
                                  loss=met.loss[i])
                        for i in range(len(thetas)))
                fn = jax.jit(batched)
            self._programs[width] = fn
        return fn

    # -- lifecycle / introspection -----------------------------------------

    def drain(self, timeout: float = 120.0) -> None:
        """Block until every submitted clock has been dispatched AND
        emitted (rows handed to the log sink; device fetches may still
        be in flight — DeferredSink.flush owns those).  Drive loops
        call this at exit so `eval_lag_clocks` returns to 0 and the
        CSV is complete before sinks flush."""
        self._raise_if_failed()
        with self.tracer.span("eval.drain"):
            if self._start_thread:
                self._ensure_thread()
                with self._cv:
                    ok = self._cv.wait_for(
                        lambda: (not self._pending and self._inflight == 0)
                        or self._stop.is_set() or self._error is not None,
                        timeout=timeout)
                self._raise_if_failed()
                if not ok:               # pragma: no cover - watchdog
                    raise TimeoutError("eval engine drain timed out")
            else:
                while self.poll():
                    pass

    def close(self) -> None:
        """Drain, stop and join the kps-eval thread (it dispatches jit
        programs — must be joined before interpreter exit,
        docs/TESTING.md), then evaluate anything still pending inline.
        Re-raises a kept dispatch failure AFTER the join."""
        try:
            if self._start_thread and not self._stop.is_set():
                try:
                    self.drain()
                except TimeoutError:     # pragma: no cover - watchdog
                    pass
        finally:
            self._stop.set()
            with self._cv:
                self._cv.notify_all()
                t = self._thread
            if t is not None and t is not threading.current_thread():
                t.join(timeout=60.0)
        self._raise_if_failed()
        while self.poll():           # leftovers after a timed-out drain
            pass

    def stats(self) -> dict:
        """Host-side pulse for the /evalz health endpoint."""
        with self._cv:
            pending = len(self._pending) + self._inflight
        return {
            "pending": pending,
            "submitted_clock": self._submitted_clock,
            "evaluated_clock": self._evaluated_clock,
            "lag_clocks": self.lag_clocks,
            "dispatches": self._dispatches,
            "evals": self._evals,
            "max_width": self._max_width,
            "widths": {str(w): n for w, n in
                       sorted(self._width_counts.items())},
        }

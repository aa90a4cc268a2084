"""Deferred log formatting — the hot paths log device futures without
waiting for them.

The reference evaluates the full test set inside every iteration and
blocks on the result before logging (LogisticRegressionTaskSpark
.java:186, ServerProcessor.java:158-164).  On TPU the evaluation is an
async jit dispatch — the old loop blocked only because `float(metric)`
sat inside the f-string, a device->host sync per scalar.  A
DeferredSink keeps the LINE order of a plain sink while the numeric
fields stay device-resident futures.  Who does what:

  * the submitting thread (a drive loop dispatching device work) only
    appends — it never fetches and never formats.  Over `max_pending`
    it wakes the drain thread and waits, on a condition, until the
    drain thread has TAKEN enough of the oldest rows to bring the queue
    under the bound: it waits for the values of the oldest pending row,
    never for a newer one, so chunks dispatched since keep the device
    busy meanwhile.  The wait bounds the sink's memory and the host's
    lead over the device; a backlog blocks the submitter, never drops;
  * the drain thread (`kps-log-drain`) pops the longest ready prefix
    every `drain_interval`, and at once while a submitter waits (then
    blocking on the oldest row's values if nothing is ready yet),
    fetches each DISTINCT device value of the batch once (rows share
    them: a fused chunk's 2,048 rows hold 8-11) and formats;
  * the fetch moves the distinct values in the fewest transfers that
    do not queue behind dispatched work: up to `_MAX_COPY` of them as
    plain device->host copies, which wait for their own value only;
    more as stacked transfers of `_MAX_STACK` (one program a stack,
    which the device runs after everything already dispatched);
  * flush() writes everything pending, on the caller's thread, before
    it returns (drive loops call it on exit so callers always see
    complete logs).

FIFO is preserved per sink by a ticket turnstile: a batch takes its
ticket atomically with popping its entries (under the pending lock),
formats and fetches OUTSIDE any lock, and emits when the turnstile
reaches its ticket — so a CSV shared by several workers keeps the
arrival order the staleness auditor's tie-breaking relies on
(evaluation/validate.py sorts stably by timestamp, file order breaking
ms collisions), while one batch's device fetch never serializes
another's behind a held emit lock — they fetch concurrently and only
the cheap ordered sink writes queue up.

A fetch that fails (a deleted buffer, a device error) fails the run:
the drain thread keeps the error and stops, submit/flush/close
re-raise it, and a submitter waiting on the backlog is released with
it.  A row is never written with a made-up value.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import deque

from kafka_ps_tpu.analysis.lockgraph import OrderedCondition
from kafka_ps_tpu.utils import trace


@functools.lru_cache(maxsize=None)
def _stacker(n: int):
    """Jit'd scalar packer for a fixed batch size.  Eager `jnp.stack`
    would trigger a fresh trace/compile for every distinct batch length
    and an eager dispatch per op; bucketing lengths to powers of two
    keeps it to a handful of cached programs."""
    import jax
    import jax.numpy as jnp

    def log_stack(vs):
        return jnp.stack([jnp.asarray(v, jnp.float32) for v in vs])
    return jax.jit(log_stack)


# Stacker programs take one argument PER scalar, and XLA compile time
# is superlinear in argument count (measured on the 1-core reference
# box: 256 -> 0.7 s, 1024 -> 8 s, 4096 -> minutes).  Chunking bounds
# the largest program at 256 inputs; a large fetch costs ceil(N/256)
# transfers instead of one, but every program is compiled once and
# cached.
_MAX_STACK = 256

# Up to this many distinct values a batch are moved as plain
# device->host copies, more as stacked transfers.  One TPU v5e chip,
# PR 28's probe: with two 0.287 s programs queued on the device the
# stacker's fetch of 8 READY scalars returned after 572 ms (a program
# runs after everything dispatched before it), their copies after 0.8-
# 1.1 ms; on an idle device copies cost 0.44 ms + 0.073 ms a value
# (8: 0.76 ms, 64: 4.5 ms, 192: 14.6 ms) and a stack of up to 256 about
# 1.0-1.4 ms.  64 lies between the most a fused drive batches (11
# distinct values a chunk, three chunks: 33) and the least the per-node
# path does (192 a clock): there copies cost 3.5 ms more than a stack
# on the drain thread and still never wait for a dispatched program.
_MAX_COPY = 64


def _fetch_path(distinct: int) -> str:
    return "copy" if distinct <= _MAX_COPY else "stack"


def _fetch_batched(jax_vals: list) -> list[float]:
    """The float32 value of each device scalar, as a Python float: by
    copies or by stacked transfers (`_fetch_path`).  Either waits for
    scalars the device has not produced yet."""
    import numpy as np
    if _fetch_path(len(jax_vals)) == "copy":
        for v in jax_vals:
            start = getattr(v, "copy_to_host_async", None)
            if start is not None:    # all copies in flight, then read
                start()
        return [float(np.asarray(v).astype(np.float32)) for v in jax_vals]
    out: list[float] = []
    for start in range(0, len(jax_vals), _MAX_STACK):
        chunk = jax_vals[start:start + _MAX_STACK]
        n = 1
        while n < len(chunk):
            n *= 2
        padded = tuple(chunk) + (0.0,) * (n - len(chunk))
        flat = np.asarray(_stacker(n)(padded))
        out.extend(float(flat[i]) for i in range(len(chunk)))
    return out


def _is_jax(value) -> bool:
    return hasattr(value, "is_ready")


def _is_ready(value) -> bool:
    if not _is_jax(value):
        return True                  # plain python number
    try:
        return bool(value.is_ready())
    except Exception:                # deleted/donated buffer etc.
        return True


def _await(values) -> None:
    """Block until the device has produced `values` (one row's)."""
    for v in values:
        wait = getattr(v, "block_until_ready", None)
        if wait is not None:
            wait()


class DeferredSink:
    """Wraps a line sink; lines may carry unresolved device scalars.

    submit(template, *values): enqueue `template.format(*values)` where
    each value may be a jax scalar — fetched (batched, off-thread) when
    it resolves.  __call__(line): emit an already-formatted line (kept
    in FIFO with deferred entries).  flush(): force-emit everything.
    """

    def __init__(self, sink, max_pending: int = 4096,
                 drain_interval: float = 0.25,
                 idle_exit: float = 10.0):
        self._sink = sink
        self._pending: deque = deque()
        self._max_pending = max_pending
        self._interval = drain_interval
        self._idle_exit = idle_exit
        # guards _pending + tickets; its condition wakes the drain
        # thread (a backlog, close) and the submitters waiting on a
        # backlog (rows taken, the drain thread failed or exited)
        self._lock = OrderedCondition("DeferredSink.pending")
        # emission turnstile: tickets are taken under _lock, atomically
        # with popping the entries they cover, so ticket order == entry
        # order; emission happens strictly in ticket order but the
        # formatting (device fetches) between take and emit runs
        # unlocked and concurrent
        self._turn_cv = OrderedCondition("DeferredSink.turn")
        self._next_ticket = 0
        self._turn = 0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # guarded-by: _lock — the drain thread's failure, kept for the
        # producer side to re-raise
        self._error: BaseException | None = None
        # guarded-by: _lock — how often and how long a submitter waited
        # on a backlog
        self._backlog_waits = 0
        self._backlog_wait_s = 0.0

    # -- producer side -----------------------------------------------------

    def _raise_if_failed(self) -> None:
        """Caller holds _lock."""
        if self._error is not None:
            raise RuntimeError("log drain failed") from self._error

    def submit(self, template: str, *values) -> None:
        with self._lock:
            self._raise_if_failed()
            self._pending.append((template, values))
            n = len(self._pending)
            if self._thread is None and not self._stop.is_set():
                self._start_thread_locked()
        if n > self._max_pending:
            self._wait_for_room(n)

    def _wait_for_room(self, n: int) -> None:
        """A backlog blocks the submitter, never drops: wait until the
        drain thread has taken the queue's oldest rows down to the
        bound.  Nothing is fetched or written on this thread."""
        t0 = time.perf_counter()
        with trace.span("log.backlog_wait", pending=n,
                        waited_for=n - self._max_pending):
            with self._lock:
                if self._thread is threading.current_thread():
                    return                  # never wait on oneself
                self._lock.notify_all()     # the drain thread: now
                # close() writes what is pending: no wait once it began
                while (len(self._pending) > self._max_pending
                       and self._error is None and not self._stop.is_set()):
                    if self._thread is None:    # it exited: the next one
                        self._start_thread_locked()
                    self._lock.wait()
                self._backlog_waits += 1
                self._backlog_wait_s += time.perf_counter() - t0
                self._raise_if_failed()

    def backlog_waits(self) -> tuple[int, float]:
        """How often a submitter has waited on a backlog, and for how
        many seconds in all (StreamingPSApp.last_run holds a drive
        call's share of both, summed over the app's sinks)."""
        with self._lock:
            return self._backlog_waits, self._backlog_wait_s

    def __call__(self, line: str) -> None:
        with self._lock:
            if self._pending or self._thread is not None:
                self._pending.append((line, ()))
                return
            # pure-string sink right now: take a ticket so the write
            # lands AFTER any batch a drain/flush already popped (their
            # tickets are earlier) — the FIFO the auditor's tie-breaking
            # relies on, without re-checking under a second lock
            ticket = self._take_ticket_locked()
        self._emit_in_turn(ticket, (line,))

    # -- drain side --------------------------------------------------------

    def _start_thread_locked(self) -> None:
        """Caller holds _lock, which the new thread takes first: it
        cannot reach its exit (where it clears _thread) before this
        returns."""
        thread = threading.Thread(
            target=self._drain_loop, daemon=True, name="kps-log-drain")
        thread.start()
        self._thread = thread

    def _drain_loop(self) -> None:
        # Exits after _idle_exit seconds with nothing pending (restarted
        # by the next submit): a long-lived process (or a test suite
        # creating many sinks) must not accumulate forever-waking
        # threads — and a daemon thread that keeps dispatching device
        # fetches at interpreter exit dies inside XLA's C++ and aborts
        # the process (the round-4 SIGABRT, docs/TESTING.md).
        idle_since = time.monotonic()
        try:
            while True:
                with self._lock:
                    # sleep an interval, unless a backlog is there or
                    # arrives (its submitter notifies) or close() has
                    # begun (it sets _stop, then notifies: read here,
                    # under the lock, the two cannot be missed)
                    if (len(self._pending) <= self._max_pending
                            and not self._stop.is_set()):
                        self._lock.wait(timeout=self._interval)
                    if self._stop.is_set():
                        return
                    backlog = len(self._pending) > self._max_pending
                self._drain_ready(wait_oldest=backlog)
                with self._lock:
                    if self._pending:
                        idle_since = time.monotonic()
                    elif time.monotonic() - idle_since >= self._idle_exit:
                        return
        except Exception as e:
            with self._lock:         # kept: submit/flush/close re-raise
                self._error = e
        finally:
            # whoever waits on a backlog learns that this thread is gone
            # (and why), and starts the next one itself
            with self._lock:
                if self._thread is threading.current_thread():
                    self._thread = None
                self._lock.notify_all()

    def _take_ticket_locked(self) -> int:
        """Issue the next turnstile ticket; caller must hold _lock (the
        ticket must be atomic with the pop it covers).  EVERY ticket
        taken must reach _emit_in_turn, even on error — callers wrap the
        formatting in try/finally."""
        ticket = self._next_ticket
        self._next_ticket += 1
        return ticket

    def _emit_in_turn(self, ticket: int, lines) -> None:
        """Write `lines` to the sink when the turnstile reaches
        `ticket`; always advances the turn, so a failed batch cannot
        wedge every later emitter."""
        with self._turn_cv:
            self._turn_cv.wait_for(lambda: self._turn == ticket)
            try:
                for line in lines:
                    self._sink(line)
            finally:
                self._turn += 1
                self._turn_cv.notify_all()

    def _take_ready_locked(self) -> tuple[list, int | None]:
        """Pop the longest prefix whose values the device has produced,
        with its ticket, and tell the submitters waiting for room;
        caller holds _lock.  ([], None) with nothing ready."""
        ready = []
        while self._pending:
            _, values = self._pending[0]
            if not all(_is_ready(v) for v in values):
                break
            ready.append(self._pending.popleft())
        if not ready:
            return ready, None
        self._lock.notify_all()
        return ready, self._take_ticket_locked()

    def _drain_ready(self, wait_oldest: bool = False) -> None:
        """Write the ready prefix.  `wait_oldest`: a submitter waits on
        the backlog, so with nothing ready block for the OLDEST row's
        values (its chunk's rows are ready with it) — never for a newer
        row's, which a device still busy with later work has not
        reached."""
        with self._lock:
            ready, ticket = self._take_ready_locked()
            oldest = (self._pending[0][1]
                      if wait_oldest and not ready and self._pending else ())
        if oldest:
            _await(oldest)
            with self._lock:     # nothing, if a flush took them meanwhile
                ready, ticket = self._take_ready_locked()
        if not ready:
            return
        lines: list[str] = []
        with trace.span("log.drain", entries=len(ready)):
            try:
                lines = self._format_entries(ready)
            finally:
                self._emit_in_turn(ticket, lines)

    def _format_entries(self, entries) -> list[str]:
        """Format entries in order, fetching each DISTINCT device scalar
        they reference once.  Runs with NO lock held, so batches fetch
        concurrently.  A scalar that cannot be fetched raises — the
        caller's run fails instead of logging a guess."""
        refs = [v for _, values in entries for v in values if _is_jax(v)]
        distinct = list({id(v): v for v in refs}.values())
        fetched: dict[int, float] = {}
        if distinct:
            with trace.span("log.fetch", scalars=len(refs),
                            distinct=len(distinct),
                            path=_fetch_path(len(distinct))):
                fetched = dict(zip(map(id, distinct),
                                   _fetch_batched(distinct)))
        lines = []
        for template, values in entries:
            if values:
                template = template.format(*(
                    fetched[id(v)] if _is_jax(v) else float(v)
                    for v in values))
            lines.append(template)
        return lines

    def flush(self, reason: str = "explicit") -> None:
        """Write everything pending, on this thread, before returning.
        `reason` names the caller in the span: `explicit` (a drive
        loop's exit) or `close`."""
        with self._lock:
            self._raise_if_failed()
            entries = list(self._pending)
            self._pending.clear()
            self._lock.notify_all()      # room for waiting submitters
            # a ticket even when empty: flush doubles as an emission
            # barrier — by the time our turn has come and gone, every
            # batch popped before this point has been written
            ticket = self._take_ticket_locked()
        lines: list[str] = []
        with trace.span("log.flush", entries=len(entries), reason=reason):
            try:
                if entries:
                    lines = self._format_entries(entries)
            finally:
                self._emit_in_turn(ticket, lines)

    def close(self) -> None:
        self._stop.set()
        with self._lock:
            self._lock.notify_all()
            t = self._thread
        if t is not None and t is not threading.current_thread():
            # the drain thread may be mid device-fetch; a process must
            # never finalize while it is inside XLA (SIGABRT) — wait it
            # out (its work is bounded: one batched fetch)
            t.join(timeout=60.0)
        try:
            self.flush("close")
        finally:
            close = getattr(self._sink, "close", None)
            if close is not None:
                close()


def submit_or_write(log, template: str, *values) -> None:
    """Route a log line through a DeferredSink when the sink supports
    it, else format eagerly (plain sinks, test list-appenders)."""
    if hasattr(log, "submit"):
        log.submit(template, *values)
    else:
        log(template.format(*(float(v) for v in values)))

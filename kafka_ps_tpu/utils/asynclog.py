"""Deferred log formatting — the per-node hot path logs device futures
without waiting for them.

The reference evaluates the full test set inside every iteration and
blocks on the result before logging (LogisticRegressionTaskSpark
.java:186, ServerProcessor.java:158-164).  On TPU the evaluation is an
async jit dispatch — the old loop blocked only because `float(metric)`
sat inside the f-string, a device->host sync per scalar.  A
DeferredSink keeps the LINE order of a plain sink while the numeric
fields stay device-resident futures:

  * the training thread only appends — it never fetches;
  * a background drain thread periodically pops the longest ready
    prefix and moves ALL its scalars in ONE stacked device->host
    transfer (N lines cost one transfer, not 3N syncs), overlapping the
    fetch with further training;
  * flush() forces everything out in one batched fetch (drive loops
    call it on exit so callers always see complete logs).

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
the drain thread keeps the error and stops, and submit/flush/close
re-raise it.  A row is never written with a made-up value.
"""

from __future__ import annotations

import functools
import threading
from collections import deque

from kafka_ps_tpu.analysis.lockgraph import OrderedCondition, OrderedLock
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
# box: 256 -> 0.7 s, 1024 -> 8 s, 4096 -> minutes — a max_pending
# backlog flush used to wedge the training thread inside that compile).
# Chunking bounds the largest program at 256 inputs; a backlog fetch
# costs ceil(N/256) transfers instead of one, but every program is
# compiled once and cached.
_MAX_STACK = 256


def _fetch_batched(jax_vals: list) -> list[float]:
    """Chunked stacked device->host transfer for any number of
    scalars."""
    import numpy as np
    out: list[float] = []
    if not jax_vals:
        return out
    # the transfer waits for scalars the device has not produced yet
    with trace.span("log.fetch", scalars=len(jax_vals)):
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
        self._lock = OrderedLock("DeferredSink.pending")  # guards _pending + tickets
        # emission turnstile: tickets are taken under _lock, atomically
        # with popping the entries they cover, so ticket order == entry
        # order; emission happens strictly in ticket order but the
        # formatting (device fetches) between take and emit runs
        # unlocked and concurrent
        self._turn_cv = OrderedCondition("DeferredSink.turn")
        self._next_ticket = 0
        self._turn = 0
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # guarded-by: _lock — the drain thread's failure, kept for the
        # producer side to re-raise
        self._error: BaseException | None = None

    # -- producer side -----------------------------------------------------

    def _raise_if_failed(self) -> None:
        with self._lock:
            err = self._error
        if err is not None:
            raise RuntimeError("log drain failed") from err

    def submit(self, template: str, *values) -> None:
        self._raise_if_failed()
        with self._lock:
            self._pending.append((template, values))
            n = len(self._pending)
        self._ensure_thread()
        if n > self._max_pending:
            self.flush("backlog")    # pay one batched fetch

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

    def _ensure_thread(self) -> None:
        with self._lock:
            t = self._thread
            if t is None or not t.is_alive():
                self._thread = threading.Thread(
                    target=self._drain_loop, daemon=True,
                    name="kps-log-drain")
                self._thread.start()

    def _drain_loop(self) -> None:
        # Exits after _idle_exit seconds with nothing pending (restarted
        # by the next submit): a long-lived process (or a test suite
        # creating many sinks) must not accumulate forever-waking
        # threads — and a daemon thread that keeps dispatching device
        # fetches at interpreter exit dies inside XLA's C++ and aborts
        # the process (the round-4 SIGABRT, docs/TESTING.md).
        idle = 0.0
        while not self._stop.is_set():
            self._wake.wait(timeout=self._interval)
            self._wake.clear()
            try:
                self._drain_ready()
            except Exception as e:
                with self._lock:     # kept: submit/flush/close re-raise
                    self._error = e
                return
            with self._lock:
                if self._pending:
                    idle = 0.0
                    continue
                idle += self._interval
                if idle >= self._idle_exit:
                    if self._thread is threading.current_thread():
                        self._thread = None
                    return

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

    def _drain_ready(self) -> None:
        with self._lock:
            ready = []
            while self._pending:
                _, values = self._pending[0]
                if not all(_is_ready(v) for v in values):
                    break
                ready.append(self._pending.popleft())
            if not ready:
                return
            ticket = self._take_ticket_locked()
        lines: list[str] = []
        with trace.span("log.drain", entries=len(ready)):
            try:
                lines = self._format_entries(ready)
            finally:
                self._emit_in_turn(ticket, lines)

    def _format_entries(self, entries) -> list[str]:
        """Format entries in order, fetching every device scalar they
        reference in ONE stacked transfer.  Runs with NO lock held, so
        batches fetch concurrently.  A scalar that cannot be fetched
        raises — the caller's run fails instead of logging a guess."""
        jax_vals = [v for _, values in entries for v in values
                    if _is_jax(v)]
        fetched = dict(zip(map(id, jax_vals), _fetch_batched(jax_vals)))
        lines = []
        for template, values in entries:
            if values:
                template = template.format(*(
                    fetched[id(v)] if _is_jax(v) else float(v)
                    for v in values))
            lines.append(template)
        return lines

    def flush_ready(self) -> None:
        self._drain_ready()

    def flush(self, reason: str = "explicit") -> None:
        """`reason` names the caller in the span: `backlog` (submit met
        max_pending), `explicit` (a drive loop's exit), `close`."""
        self._raise_if_failed()
        with self._lock:
            entries = list(self._pending)
            self._pending.clear()
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
        self._wake.set()
        with self._lock:
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

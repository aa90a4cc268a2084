"""Tracing / profiling hooks — the reference's observability surface
(Confluent monitoring interceptors on every producer/consumer feeding
Control Center, BaseKafkaApp.java:73-78, dev/docker-compose.yaml:30-47)
rebuilt for the TPU runtime.

Three layers:
  * `Tracer` — host-side span + counter + flow-event recorder.  Spans
    export as Chrome trace-event JSON (load in chrome://tracing or
    Perfetto); counters are sampled over time as `ph: "C"` counter
    events, giving the per-topic message-flow timeline the Kafka
    interceptors provided; flow events (`ph: s/t/f`) connect a delta's
    lifecycle across threads AND processes (the wire trace context,
    runtime/net.py + docs/OBSERVABILITY.md).
  * `Tracer.span(...)` context manager — wrap any section; thread-safe,
    so the threaded runtime's per-worker threads can share one tracer.
    Every span, on NULL_TRACER too, is also a
    `jax.profiler.TraceAnnotation` named `kps.<name>`: while a profiler
    session runs, the program's spans lie on the host plane of the
    same `.xplane.pb` as the device operations, on the profiler's
    clock (docs/OBSERVABILITY.md "One clock").  Code with no tracer
    object calls the module-level `span(...)`, the same function.
  * `device_trace(...)` — jax.profiler wrapper capturing XLA/TPU traces
    (HLO timelines, per-op device time) to a TensorBoard logdir.

Disabled (the module-level NULL_TRACER, which runtime code takes as
`tracer or NULL_TRACER`) a counter or flow call returns at once, and a
span costs the annotation's inactive check: no lock, no list append,
no clock read.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import defaultdict, deque


class Tracer:
    """Span + counter + flow recorder with Chrome trace-event export.

    `pid` labels every event (defaults to the real process id — the
    merge CLI in kafka_ps_tpu/telemetry keys track groups off it);
    `counter_sample_s` throttles how often a hot counter emits a
    timeline sample (0 = every increment, for deterministic tests)."""

    def __init__(self, clock=time.perf_counter, pid: int | None = None,
                 counter_sample_s: float = 0.01):
        self._clock = clock
        self._t0 = clock()
        # wall-clock anchor for cross-process merging: perf_counter
        # epochs are process-private, so dump() records where this
        # tracer's zero sits on the shared wall clock
        self._wall0 = time.time()
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._counters: dict[str, int] = defaultdict(int)
        # sampled (ts_us, name, total) points -> ph:"C" events at dump
        self._counter_samples: list[tuple[float, str, int]] = []
        self._sample_every = counter_sample_s
        self._last_sample: dict[str, float] = {}
        self._flow_seq = 0
        self.pid = os.getpid() if pid is None else pid
        self.enabled = True

    # -- spans -------------------------------------------------------------
    def span(self, name: str, **args) -> "_Span":
        """Context manager round a section: a profiler annotation
        `kps.<name>` always, a Chrome event as well when this tracer is
        enabled.  `args` are host ints and literal strings."""
        return _Span(self, name, args)

    def span_at(self, name: str, start: float, end: float, **args) -> None:
        """Record a complete span from two clock values already taken
        (same clock as this tracer, time.perf_counter by default).
        For retroactive sections whose start predates the decision to
        record them — e.g. the consistency gate's hold time, known only
        at release (runtime/server.py:_observe_gate_release).  A Chrome
        event alone: a profiler annotation is opened and closed, it
        cannot be stamped afterwards."""
        if self.enabled:
            self._record(name, start, end, args)

    def span_at_wall(self, name: str, start: float, end: float,
                     **args) -> None:
        """`span_at` for two `time.time()` stamps (a `jax.monitoring`
        time span, a phase of the start-up record, utils/device.py):
        they are brought to this tracer's clock by the anchor `dump()`
        exports, so they may predate the tracer."""
        if self.enabled:
            shift = self._t0 - self._wall0
            self._record(name, start + shift, end + shift, args)

    def _record(self, name: str, start: float, end: float,
                args: dict) -> None:
        with self._lock:
            self._events.append({
                "name": name,
                "ph": "X",                      # complete event
                "ts": (start - self._t0) * 1e6,  # µs, trace convention
                "dur": max(0.0, end - start) * 1e6,
                "pid": self.pid,
                "tid": threading.get_ident() % 2 ** 31,
                "args": args,
            })

    # -- counters (message-flow view) --------------------------------------
    def count(self, name: str, n: int = 1) -> None:
        if not self.enabled:
            return
        now = self._clock()
        with self._lock:
            self._counters[name] += n
            # throttled timeline sample: Perfetto renders these as a
            # counter track (the satellite fix — totals alone never
            # appeared on the timeline)
            if now - self._last_sample.get(name, -1e18) >= self._sample_every:
                self._last_sample[name] = now
                self._counter_samples.append(
                    ((now - self._t0) * 1e6, name, self._counters[name]))

    # -- flow events (cross-thread / cross-process causality) --------------
    def new_flow_id(self) -> int:
        """Globally-unique flow id: pid in the high bits so ids from
        different processes never collide in a merged trace."""
        with self._lock:
            self._flow_seq += 1
            return ((self.pid & 0xFFFF) << 40) | self._flow_seq

    def flow(self, ph: str, name: str, flow_id: int, **args) -> None:
        """One flow event: ph 's' (start), 't' (step), 'f' (end).
        Emit from inside a span — viewers bind the arrow endpoints to
        the enclosing slice on this (pid, tid)."""
        if not self.enabled:
            return
        now = self._clock()
        ev = {"name": name, "cat": "flow", "ph": ph, "id": flow_id,
              "ts": (now - self._t0) * 1e6, "pid": self.pid,
              "tid": threading.get_ident() % 2 ** 31, "args": args}
        if ph == "f":
            ev["bp"] = "e"      # bind the arrowhead to the enclosing slice
        with self._lock:
            self._events.append(ev)

    def flow_start(self, name: str, flow_id: int, **args) -> None:
        self.flow("s", name, flow_id, **args)

    def flow_step(self, name: str, flow_id: int, **args) -> None:
        self.flow("t", name, flow_id, **args)

    def flow_end(self, name: str, flow_id: int, **args) -> None:
        self.flow("f", name, flow_id, **args)

    def clear(self) -> None:
        """Drop every recorded event and counter sample (the
        warmup-then-measure pattern: run until jit compiles settle,
        clear, then trace steady state).  Flow ids keep advancing, so
        post-clear events never collide with discarded ones; a flow
        whose start was discarded is simply unmatched downstream."""
        with self._lock:
            self._events.clear()
            self._counter_samples.clear()

    # -- export ------------------------------------------------------------
    def counters(self) -> dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def span_stats(self) -> dict[str, dict]:
        """Per-span-name count/total/mean milliseconds."""
        with self._lock:
            acc: dict[str, list[float]] = defaultdict(list)
            for e in self._events:
                acc[e["name"]].append(e["dur"] / 1e3)
        return {name: {"count": len(ds), "total_ms": round(sum(ds), 3),
                       "mean_ms": round(sum(ds) / len(ds), 3)}
                for name, ds in sorted(acc.items())}

    def dump(self, path: str) -> str:
        """Chrome trace-event JSON: {traceEvents: [...], counters: ...}.

        Counters land on the timeline as standard `ph: "C"` counter
        events (one per throttled sample plus a closing sample at dump
        time), so Perfetto draws them as counter tracks; the top-level
        "counters" totals stay for the programmatic consumers
        (span_stats callers, tests).  "wallClockT0" anchors this
        process's ts=0 on the shared wall clock for the merge CLI."""
        now_us = (self._clock() - self._t0) * 1e6
        with self._lock:
            events = list(self._events)
            tid = threading.get_ident() % 2 ** 31
            for ts_us, name, total in self._counter_samples:
                events.append({"name": name, "ph": "C", "ts": ts_us,
                               "pid": self.pid, "tid": tid,
                               "args": {"value": total}})
            for name, total in sorted(self._counters.items()):
                events.append({"name": name, "ph": "C", "ts": now_us,
                               "pid": self.pid, "tid": tid,
                               "args": {"value": total}})
            payload = {"traceEvents": events,
                       "counters": dict(self._counters),
                       "wallClockT0": self._wall0,
                       "pid": self.pid}
        with open(path, "w") as f:
            json.dump(payload, f)
        return path


# jax.profiler.TraceAnnotation, imported by the first span: modules that
# only count or time (log/, serving/loadgen.py) load without JAX
_TraceAnnotation = None


class _Span:
    """One `Tracer.span(...)`: the profiler annotation, and the Chrome
    event where the tracer records."""

    __slots__ = ("_tracer", "_name", "_args", "_note", "_start")

    def __init__(self, tracer: Tracer, name: str, args: dict):
        global _TraceAnnotation
        if _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation as _TraceAnnotation
        self._tracer, self._name, self._args = tracer, name, args
        self._note = _TraceAnnotation("kps." + name, **args)

    def __enter__(self) -> None:
        self._note.__enter__()
        tracer = self._tracer
        self._start = tracer._clock() if tracer.enabled else None

    def __exit__(self, *exc) -> None:
        if self._start is not None:
            tracer = self._tracer
            tracer._record(self._name, self._start, tracer._clock(),
                           self._args)
        self._note.__exit__(*exc)


class LatencyRecorder:
    """Sliding-window latency samples with percentile export — the
    serving plane's p50/p99 (seconds in, milliseconds out). Bounded so
    a long-lived server never grows; thread-safe so request callbacks
    and the status heartbeat can share one recorder."""

    def __init__(self, window: int = 4096):
        self._samples: "deque[float]" = deque(maxlen=max(1, window))
        self._lock = threading.Lock()
        self.count = 0

    def record(self, seconds: float) -> None:
        with self._lock:
            self._samples.append(seconds)
            self.count += 1

    def percentiles_ms(self, *ps: float) -> dict[str, float | None]:
        """{"p50_ms": ..., "p99_ms": ...}; None before any sample."""
        with self._lock:
            data = sorted(self._samples)
        out: dict[str, float | None] = {}
        for p in ps:
            key = f"p{p:g}_ms"
            if not data:
                out[key] = None
            else:
                idx = min(len(data) - 1, round(p / 100 * (len(data) - 1)))
                out[key] = round(data[idx] * 1e3, 3)
        return out


class _NullTracer(Tracer):
    """No-op tracer (observability off — the default, like running the
    reference without Control Center)."""

    def __init__(self):
        super().__init__()
        self.enabled = False


NULL_TRACER = _NullTracer()

# for code that holds no tracer object (utils/asynclog.py): the
# annotation alone
span = NULL_TRACER.span


@contextlib.contextmanager
def device_trace(logdir: str | None):
    """XLA/TPU device profiling via jax.profiler (per-op device time,
    HLO timeline, and the program's own `kps.*` spans on the host plane
    — view with TensorBoard).  None → no-op.  The Python call tracer is
    off: it hooks every call of the per-node loop and slows the host it
    is measuring; the spans are TraceMe events, which stay."""
    if logdir is None:
        yield
        return
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()

"""Streaming ingestion simulator — the reference's CsvProducer re-designed.

Reads a training CSV row by row, converts each row into a sparse sample
(zero features dropped, label = last column — CsvProducer.java:52-58),
assigns it round-robin to a logical worker (row_count % num_workers,
CsvProducer.java:61), and paces delivery: the first
num_workers * prefill_per_worker rows go unthrottled to pre-fill the
buffers, after which the producer sleeps 1 s every
(1000 / time_per_event_ms) rows (CsvProducer.java:73-83).

The Kafka INPUT_DATA topic hop disappears: the sink is a plain callable
(in-process fabric or directly the per-worker SlidingBuffer), which on
TPU means samples land in pinned host buffers awaiting the next
host→device slab transfer rather than a JSON round-trip through a broker.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Callable, Iterator

Sink = Callable[[int, dict[int, float], int], None]  # (worker, features, label)


def open_csv_rows(csv_path: str, has_header: bool = True,
                  num_features: int | None = None,
                  use_native: bool | None = None
                  ) -> tuple[str, Iterator[tuple[dict[int, float], int]]]:
    """(parser, rows): which parser serves this file — the native one's
    `status()` or "python" with the reason — and the iterator of
    (sparse_features, label) per CSV row, zero features dropped
    (CsvProducer.java:52-58).

    `use_native`: True forces the C++ parser (kafka_ps_tpu.native),
    False forces pure Python, None (default) auto-selects — the native
    path parses the whole file in one pass and replays rows; the Python
    path streams line by line."""
    parser = "python (forced)"
    if use_native is not False:
        from kafka_ps_tpu import native
        parser = native.status()
        parsed = None
        if native.is_available():
            try:
                parsed = native.parse_csv(csv_path, has_header=has_header)
            except RuntimeError as e:
                # the C parser is stricter (uniform width, no stray
                # whitespace); on auto-select fall through to Python
                if use_native:
                    raise
                parser = f"python (native parser refused the file: {e})"
        elif use_native:
            raise RuntimeError("native CSV parser requested but unavailable")
        if parsed is not None:
            if (num_features is not None and parsed.num_rows > 0
                    and parsed.num_features != num_features):
                raise ValueError(
                    f"rows have {parsed.num_features + 1} columns, "
                    f"expected {num_features + 1}")
            return parser, (parsed.row(i) for i in range(parsed.num_rows))
    return parser, _python_rows(csv_path, has_header, num_features)


def _python_rows(csv_path: str, has_header: bool,
                 num_features: int | None
                 ) -> Iterator[tuple[dict[int, float], int]]:
    with open(csv_path) as f:
        if has_header:
            f.readline()
        for line in f:
            line = line.strip()
            if not line:
                continue
            cols = line.split(",")
            if num_features is not None and len(cols) != num_features + 1:
                raise ValueError(
                    f"row has {len(cols)} columns, expected {num_features + 1}")
            feats = {i: float(v) for i, v in enumerate(cols[:-1])
                     if float(v) != 0.0}
            yield feats, int(float(cols[-1]))


def iter_csv_rows(csv_path: str, has_header: bool = True,
                  num_features: int | None = None,
                  use_native: bool | None = None
                  ) -> Iterator[tuple[dict[int, float], int]]:
    """The rows of `open_csv_rows`, for callers that do not report the
    parser.  Lazy like a generator: nothing is opened or parsed before
    the first row is asked for."""
    yield from open_csv_rows(csv_path, has_header, num_features,
                             use_native)[1]


class CsvStreamProducer:
    """Paced row pump: CSV → sink(worker, features, label)."""

    def __init__(self, csv_path: str, num_workers: int, sink: Sink,
                 time_per_event_ms: float = 200.0,
                 prefill_per_worker: int = 128,
                 has_header: bool = True,
                 num_features: int | None = None,
                 use_native: bool | None = None,
                 sleep: Callable[[float], None] = time.sleep):
        self.csv_path = csv_path
        self.num_workers = num_workers
        self.sink = sink
        self.time_per_event_ms = time_per_event_ms
        self.prefill_per_worker = prefill_per_worker
        self.has_header = has_header
        self.num_features = num_features
        # None = auto (native one-pass parse when available — O(file)
        # memory, faster); False = force the lazy line-by-line Python
        # path (constant memory, first row immediately)
        self.use_native = use_native
        # default pacing waits on the stop event, so stop() interrupts a
        # sleep instantly; an injected sleep (tests) is called directly
        self._sleep = sleep if sleep is not time.sleep else None
        # pscheck: disable=PS201 (producer-thread counter; read for end-of-run reporting after join)
        self.rows_sent = 0
        self.finished = threading.Event()
        self.stopped = threading.Event()
        self._thread: threading.Thread | None = None

    def run(self) -> None:
        prefill = self.num_workers * self.prefill_per_worker
        # 1 s sleep every this many rows (CsvProducer.java:75-78); a
        # time_per_event above 1000 ms degenerates to sleeping every row;
        # <= 0 means unthrottled (no pacing at all).
        rows_per_sleep = (max(1, int(1000 / self.time_per_event_ms))
                          if self.time_per_event_ms > 0 else 0)
        # the parser choice used to be silent; a run now says which one
        # fed it (stderr, beside the [device] start-up line)
        parser, rows = open_csv_rows(self.csv_path, self.has_header,
                                     self.num_features,
                                     use_native=self.use_native)
        print(f"[ingest] {self.csv_path}: csv parser = {parser}",
              file=sys.stderr, flush=True)
        for feats, label in rows:
            if self.stopped.is_set():
                break
            worker = self.rows_sent % self.num_workers
            self.sink(worker, feats, label)
            self.rows_sent += 1
            if (rows_per_sleep and self.rows_sent >= prefill
                    and self.rows_sent % rows_per_sleep == 0):
                if self._sleep is not None:
                    self._sleep(1.0)
                elif self.stopped.wait(1.0):
                    break
        self.finished.set()

    def run_in_background(self) -> threading.Thread:
        t = threading.Thread(target=self.run, daemon=True,
                             name="csv-stream-producer")
        self._thread = t
        t.start()
        return t

    def stop(self, join_timeout: float = 10.0) -> None:
        """Stop the pump and JOIN its thread: the drive loops call this
        on exit so the process never finalizes while the producer is
        mid-sink (a daemon thread dying inside native numpy/XLA code
        aborts the interpreter — the round-4 flake)."""
        self.stopped.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=join_timeout)


def load_csv_dataset(csv_path: str, has_header: bool = True
                     ) -> tuple["np.ndarray", "np.ndarray"]:
    """Whole CSV as dense (x, y) — label in the last column, the
    reference's file layout (CsvProducer.java:52-58, header column
    `Score`, LogisticRegressionTaskSpark.java:86-92)."""
    import numpy as np
    data = np.loadtxt(csv_path, delimiter=",",
                      skiprows=1 if has_header else 0)
    if data.ndim == 1:
        data = data[None, :]
    return data[:, :-1].astype(np.float32), data[:, -1].astype(np.int32)

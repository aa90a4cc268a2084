"""Dynamic sliding training-data buffer — one per logical worker.

Behavioral re-design of the reference's WorkerSamplingProcessor
(processors/WorkerSamplingProcessor.java:18-136).  The reference stores
sparse JSON rows in a Kafka Streams KV store keyed into a per-worker key
space; here each worker owns **fixed-capacity dense numpy arrays plus a
validity mask** — static shapes so the jit'd training step never
recompiles, and the device transfer is one contiguous slab instead of a
per-row range scan.

Policy preserved exactly:
  * inter-arrival times tracked over a 500-event window
    (WorkerSamplingProcessor.java:21-23,124-135);
  * target size = clamp(round(coefficient * events_per_minute), min, max)
    with events_per_minute = 60000 / mean_inter_arrival_ms, default mean
    1000 ms before any samples (WorkerSamplingProcessor.java:115-122);
  * insertion: below target → fill first empty slot; at target →
    overwrite oldest; above target (target shrank) → delete the n oldest
    then overwrite the next-oldest survivor
    (WorkerSamplingProcessor.java:79-112);
  * insertion IDs are buffer-relative: new ID = max ID currently in the
    buffer + 1 (0 when empty) (WorkerSamplingProcessor.java:74-77,110-111).

The reference's buffer-scan off-by-one (training scans one key into the
next worker's space, SURVEY §3.5.2) is intentionally NOT reproduced —
each buffer is a private object, so there is no adjacent key space to
leak into.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Callable

import numpy as np

from kafka_ps_tpu.analysis.lockgraph import OrderedLock
from kafka_ps_tpu.models.logreg import sparse_to_dense
from kafka_ps_tpu.utils.config import BufferConfig


def _default_clock_ms() -> float:
    return time.monotonic() * 1000.0


class SlidingBuffer:
    """Fixed-capacity masked ring buffer with a rate-adaptive target size."""

    def __init__(self, num_features: int, cfg: BufferConfig,
                 clock_ms: Callable[[], float] | None = None,
                 telemetry=None, worker: int | None = None,
                 dtype=np.float32):
        """`num_features` and `dtype` are the row's width and dtype, as
        the task states them (`MLTask.row_width` / `.row_dtype`):
        float32 features, or int32 tokens whose labels are the row
        itself."""
        self.cfg = cfg
        self.num_features = num_features
        self.dtype = np.dtype(dtype)
        if telemetry is None:
            from kafka_ps_tpu.telemetry import NULL_TELEMETRY
            telemetry = NULL_TELEMETRY
        self._telemetry = telemetry
        self._m_rows = telemetry.counter(
            "buffer_rows_ingested_total",
            worker="all" if worker is None else str(worker))
        cap = cfg.max_size
        self.x = np.zeros((cap, num_features), dtype=self.dtype)
        self.y = np.zeros((cap,), dtype=np.int32)
        # insertion_id[i] == 0 marks an empty slot (reference IDs start at 1).
        self.insertion_id = np.zeros((cap,), dtype=np.int64)
        self._clock_ms = clock_ms or _default_clock_ms
        self._inter_arrival_ms: deque[float] = deque(maxlen=cfg.arrival_window)
        self._last_arrival_ms: float | None = None
        # Slots whose (x, y, insertion_id) changed since the last
        # drain/clearing snapshot — the incremental device-slab path
        # (compress/slab.SlabStore.apply_rows) uploads only these.
        self._dirty: set[int] = set()
        # Monotonic mutation counter.  num_tuples_seen is NOT a valid
        # change detector (restore_state can rewind it; a mass-delete
        # with one insert moves it by 1 while touching many slots), so
        # the worker keys its device-slab cache off this instead.
        self._version = 0
        # add() and snapshot() are internally synchronized so the producer
        # thread and the training loop need no external locking.
        self._lock = OrderedLock("SlidingBuffer.state")
        # optional drift monitor (telemetry/drift.py): sampled arrivals
        # feed its per-feature Welford sketch.  None keeps ingest
        # byte-identical to today's path.
        self._drift = None

    def attach_drift(self, monitor) -> None:
        """Feed sampled arrivals to a DriftMonitor's feature sketch
        (population-stability scoring, --model-health)."""
        self._drift = monitor

    # -- rate tracking (WorkerSamplingProcessor.java:124-135) --------------

    def _record_arrival(self) -> None:
        now = self._clock_ms()
        if self._last_arrival_ms is not None:
            self._inter_arrival_ms.append(now - self._last_arrival_ms)
        self._last_arrival_ms = now

    def target_size(self) -> int:
        """clamp(round(coefficient * events_per_minute), min, max)."""
        if self._inter_arrival_ms:
            mean_ms = sum(self._inter_arrival_ms) / len(self._inter_arrival_ms)
        else:
            mean_ms = 1000.0
        if mean_ms <= 0:
            # burst arrivals within clock resolution: rate is effectively
            # infinite, clamp straight to the cap
            return self.cfg.max_size
        calculated = round(self.cfg.coefficient * 60000.0 / mean_ms)
        return max(self.cfg.min_size, min(self.cfg.max_size, int(calculated)))

    # -- insertion policy (WorkerSamplingProcessor.java:79-112) ------------

    def add(self, features, label: int) -> None:
        """Insert one sample, evicting per the dynamic-target policy."""
        with self._lock:
            self._add_locked(features, label)
        if self._telemetry.enabled:
            self._m_rows.inc()
        if self._drift is not None:
            # outside the buffer lock (lockgraph: never hold two);
            # observe_row itself samples every Nth arrival
            self._drift.observe_row(features)

    def add_many(self, rows) -> None:
        """Insert N (features, label) samples under ONE lock acquisition
        — the bulk half of the batched ingest path (net.T_DATA_BATCH,
        ServerBridge.send_data_batch).  Policy-identical to N add()
        calls: arrival recording and the dynamic-target eviction run
        per row, only the lock round-trips are amortized."""
        n = 0
        # rows may be a one-shot iterable: capture features while
        # inserting, sketch them after the lock is released (lockgraph:
        # never hold two)
        sampled = [] if self._drift is not None else None
        with self._lock:
            for features, label in rows:
                self._add_locked(features, label)
                n += 1
                if sampled is not None:
                    sampled.append(features)
        if n and self._telemetry.enabled:
            self._m_rows.inc(n)
        if sampled:
            for features in sampled:
                self._drift.observe_row(features)

    def _add_locked(self, features, label: int) -> None:
        self._record_arrival()
        target = self.target_size()

        filled = np.flatnonzero(self.insertion_id > 0)
        count = len(filled)
        new_id = int(self.insertion_id.max()) + 1 if count else 1

        if count < target:
            # fill the first empty slot
            slot = int(np.flatnonzero(self.insertion_id == 0)[0])
        elif count == target:
            # overwrite the oldest
            slot = int(filled[np.argmin(self.insertion_id[filled])])
        else:
            # target shrank: drop the n oldest, overwrite the next-oldest
            n = count - target
            oldest_first = filled[np.argsort(self.insertion_id[filled])]
            self.insertion_id[oldest_first[:n]] = 0
            self._dirty.update(int(s) for s in oldest_first[:n])
            slot = int(oldest_first[n])

        if isinstance(features, dict):
            row = sparse_to_dense([features], self.num_features)[0]
        else:
            row = np.asarray(features, dtype=self.dtype)
        self.x[slot] = row
        self.y[slot] = label
        self.insertion_id[slot] = new_id
        self._dirty.add(slot)
        self._version += 1

    # -- views for the training step ---------------------------------------

    @property
    def count(self) -> int:
        with self._lock:
            return int((self.insertion_id > 0).sum())

    @property
    def num_tuples_seen(self) -> int:
        """Worker log column: max insertion ID in the buffer
        (WorkerTrainingProcessor.java:80-92)."""
        with self._lock:
            return int(self.insertion_id.max())

    @property
    def version(self) -> int:
        """Monotonic mutation counter (bumps on every add/restore).
        Compare against a cached value to detect staleness — unlike
        num_tuples_seen this never aliases across restore_state."""
        with self._lock:
            return self._version

    @property
    def dirty_slots(self) -> list[int]:
        """Sorted slots touched since the last drain (non-clearing view,
        for tests/inspection; drain_dirty is the consuming call)."""
        with self._lock:
            return sorted(self._dirty)

    def drain_dirty(self):
        """(slots, x_rows, y_rows, mask_rows) for every slot touched
        since the last drain, then forget them — the delta the
        incremental device-slab path scatters instead of re-uploading
        the whole slab.  One lock acquisition, so the rows are a
        consistent cut: a slot deleted by a target shrink comes back
        with mask 0 and whatever stale x/y it holds (the mask is what
        the solver trusts, exactly as in snapshot())."""
        with self._lock:
            slots = np.asarray(sorted(self._dirty), dtype=np.int64)
            self._dirty.clear()
            mask = (self.insertion_id[slots] > 0).astype(np.float32)
            return slots, self.x[slots].copy(), self.y[slots].copy(), mask

    def snapshot(self, clear_dirty: bool = False
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(x, y, mask) — a consistent copy of the static-shape slab
        shipped to the device; safe to use without holding any lock.
        clear_dirty=True marks the copy as the new device baseline
        (a full upload subsumes any pending incremental delta)."""
        with self._lock:
            mask = (self.insertion_id > 0).astype(np.float32)
            if clear_dirty:
                self._dirty.clear()
            return self.x.copy(), self.y.copy(), mask

    # -- durability (utils/checkpoint.py) ----------------------------------

    def state(self) -> dict[str, np.ndarray]:
        """Serializable durable state: slab contents, insertion IDs, and
        the inter-arrival window behind the rate-adaptive target size —
        the changelog-backed state store the reference's workers restore
        from on reassignment (WorkerApp.java:40-42, Kafka Streams
        logged KV store)."""
        with self._lock:
            return {"x": self.x.copy(), "y": self.y.copy(),
                    "ids": self.insertion_id.copy(),
                    "arrivals": np.asarray(self._inter_arrival_ms,
                                           dtype=np.float64)}

    def restore_state(self, st) -> None:
        """Inverse of state().  The arrival CLOCK does not survive a
        restart (monotonic time is process-local), so the gap between
        the crash and the first post-restore arrival is not counted as
        an inter-arrival — only the restored window is."""
        if st["x"].shape != self.x.shape:
            raise ValueError(
                f"buffer state shape {st['x'].shape} != slab "
                f"{self.x.shape} (capacity/features changed?)")
        with self._lock:
            self.x[:] = st["x"]
            self.y[:] = st["y"]
            self.insertion_id[:] = st["ids"]
            self._inter_arrival_ms.clear()
            self._inter_arrival_ms.extend(float(v) for v in st["arrivals"])
            self._last_arrival_ms = None
            # every slot may differ from what a device slab holds
            self._dirty.update(range(self.x.shape[0]))
            self._version += 1

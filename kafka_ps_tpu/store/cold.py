"""Cold tier — parameter pages as CRC-framed records in a commit log.

The durable commit log (kafka_ps_tpu/log/) is already an
offset-indexed key-value store: `CommitLog.append` hands back a stable
offset and `CommitLog.read_at` (log/segment.py) is a CRC-verified
positioned point read through the sparse index.  The cold tier uses it
as exactly that — a demoted page is one appended record, a fault-in is
one point read — so cold parameters inherit the log's whole durability
story for free: torn tails are truncated on recovery, corruption is
detected (KeyError, never garbage floats), and retention never reaps a
partition no consumer group commits (log/manager.py), which is why a
`param-cold` topic under the durable-log root is safe.

Record payload: `<qqq>` header (page index, key start, key end) + raw
little-endian f32 bytes.  The header is verified on read — an offset
bookkeeping bug surfaces as a loud KeyError, not as silently wrong
parameters.

Append-only means demotions of the same page accumulate records; only
the offset the residency table holds is live, older records are
garbage the log's segment retention can reap once nothing references
them.  Checkpoint restore RE-demotes recorded-cold pages with fresh
appends (store/tiered.py `set_residency`), so a checkpoint never
depends on pre-checkpoint cold records.
"""

from __future__ import annotations

import struct

import numpy as np

from kafka_ps_tpu.log.log import CommitLog, LogConfig

_HDR = struct.Struct("<qqq")        # page index, key start, key end


class ColdStore:
    """Offset-addressed page storage over one CommitLog partition."""

    def __init__(self, log: CommitLog):
        self.log = log
        self._owned = False
        self.appends = 0
        self.reads = 0

    @classmethod
    def open(cls, directory: str, config: LogConfig | None = None
             ) -> "ColdStore":
        """Standalone cold partition (tests, runs without a
        durable fabric); `close()` then closes the log too."""
        store = cls(CommitLog(directory, config or LogConfig(fsync="none"),
                              name="param-cold"))
        store._owned = True
        return store

    def put(self, page: int, start: int, end: int,
            values: np.ndarray) -> int:
        """Append one page record; returns its log offset — the only
        handle the residency table needs to keep."""
        vals = np.ascontiguousarray(values, dtype=np.float32)
        if vals.shape != (end - start,):
            raise ValueError(
                f"page {page} [{start}, {end}) expects {end - start} "
                f"values, got shape {vals.shape}")
        self.appends += 1
        return self.log.append(_HDR.pack(page, start, end)
                               + vals.tobytes())

    def get(self, offset: int, page: int, start: int, end: int
            ) -> np.ndarray:
        """CRC-verified point read of the page record at `offset`;
        the stored header must match what the caller expects."""
        payload = self.log.read_at(offset)
        p, s, e = _HDR.unpack_from(payload, 0)
        if (p, s, e) != (page, start, end):
            raise KeyError(
                f"cold record at offset {offset} is page {p} "
                f"[{s}, {e}), wanted page {page} [{start}, {end})")
        self.reads += 1
        return np.frombuffer(payload, np.float32, count=e - s,
                             offset=_HDR.size).copy()

    def close(self) -> None:
        if self._owned:
            self.log.close()

"""Device-resident training-slab codec + store (docs/PERFORMANCE.md).

The worker's hot path streams its buffer slab ([cap, F] x + labels +
validity mask) through the solver every iteration.  Two memory walls,
two tools in this module:

* **Host->device bytes**: the slab used to be re-uploaded WHOLE
  whenever one row arrived (runtime/worker.py invalidated the device
  copy on any `num_tuples_seen` change — ~4-20 MB per arrival at
  reference shapes).  `SlabStore` keeps the slab device-resident and
  applies only the rows `SlidingBuffer` marked dirty, via a jit'd
  scatter whose changed-row count is padded to a power-of-two bucket —
  O(log cap) compiled shapes, O(changed rows) bytes moved.

* **HBM->VMEM bytes**: the solver re-reads the slab from HBM every
  step.  `--slab-dtype bf16|int8` stores the device slab reduced
  (encode fused into the scatter/upload program), and decode is fused
  into the training step (models/task.py `fit_slab` calls `decode_x`),
  halving or quartering the bytes every matmul streams.

This is the device-side refactor of the wire codec's quantizers
(compress/codecs.py): `quantize_rows`/`dequantize_rows` are the shared
int8 primitive — the wire codec applies them to the flat vector
reshaped to [nchunks, 256] chunks, the slab codec to [cap, F] with the
slab ROW as the chunk (a per-row scale broadcasts over a row's lanes,
where a mid-row chunk boundary would not).

Numerics contract: `--slab-dtype f32` is bitwise-identical to the
pre-slab-store behavior — encode/decode are identity (an f32->f32
astype leaves the jaxpr unchanged) and the scatter moves the same
float bits `SlidingBuffer.snapshot` would have uploaded.  bf16/int8
are lossy on x ONLY (labels and mask stay exact); eval-metric deltas
are bounded by the same tolerance as compressed transport
(tests/test_slab.py, docs/PERFORMANCE.md).

All programs are cached per slab dtype (`_slab_programs`, an lru_cache
factory like runtime/worker._solver_fns) and jit handles the
shape/bucket polymorphism — compile-once-per-(shape, dtype) is a
tested invariant (TRACE_COUNTS below, PS101-style regression test).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

SLAB_DTYPES = ("f32", "bf16", "int8")

# Trace counters, bumped INSIDE traced bodies (the pattern
# evaluation/ground_truth._fit_traces established): a counter that
# moves on a steady-state arrival means the hot path is re-tracing.
TRACE_COUNTS = {"full": 0, "apply": 0, "decode": 0}

# Changed-row counts are padded up to a power-of-two bucket (never
# below this) so N single-row arrivals reuse ONE compiled scatter.
MIN_BUCKET = 4


# -- shared int8 primitive (also used by compress/codecs._build_fns) ---------

def quantize_rows(r: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Max-abs int8 quantization over the last axis of a 2-D block:
    [n, c] f32 -> (q [n, c] int8, scale [n] f32).  The wire codec's
    chunks and the slab codec's rows are both just choices of `c`."""
    scale = jnp.max(jnp.abs(r), axis=-1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(r / safe[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_rows(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of quantize_rows (up to the quantization error)."""
    return q.astype(jnp.float32) * scale[..., None]


class QuantizedSlab(NamedTuple):
    """int8 slab storage: rows quantized with a per-row scale.  A
    NamedTuple is a jax pytree, so it flows through jit/vmap/tree-stack
    wherever a plain x array would (runtime/gang.py stacks members
    with a tree-map for exactly this reason)."""

    q: jax.Array       # [cap, F] int8
    scale: jax.Array   # [cap, 1] f32  (max|row| / 127)


def slab_batch_shape(x) -> tuple[int, int]:
    """(batch, num_features) of a slab in any storage dtype."""
    a = x.q if isinstance(x, QuantizedSlab) else x
    return a.shape[-2], a.shape[-1]


def decode_x(x) -> jax.Array:
    """Stored slab -> f32, fused into whatever program traces it
    (models/task.py `fit_slab`).  Identity for f32
    input — the astype leaves the traced jaxpr unchanged, which is the
    f32 bitwise contract."""
    TRACE_COUNTS["decode"] += 1
    if isinstance(x, QuantizedSlab):
        return x.q.astype(jnp.float32) * x.scale
    if jnp.issubdtype(x.dtype, jnp.integer):
        return x          # token rows are stored, and used, as they are
    return x.astype(jnp.float32)


def encode_x(dtype: str, x: jax.Array):
    """f32 rows -> stored form (traceable; fused into upload/scatter)."""
    if dtype == "bf16":
        return x.astype(jnp.bfloat16)
    if dtype == "int8":
        q, scale = quantize_rows(x)
        return QuantizedSlab(q=q, scale=scale[..., None])
    return x


@functools.lru_cache(maxsize=None)
def _slab_programs(dtype: str):
    """(full_upload, scatter_apply) jit'd programs for one slab dtype.
    jit's own cache keys the shape/bucket polymorphism, so the compile
    count is O(1) full + O(log cap) apply buckets per (cap, F)."""

    def full(x, y, mask):
        TRACE_COUNTS["full"] += 1
        return encode_x(dtype, x), y, mask

    def apply(sx, sy, sm, slots, xr, yr, mr):
        # slots padded with an out-of-range sentinel: mode="drop" makes
        # the padding rows no-ops, so every bucket size is one program
        TRACE_COUNTS["apply"] += 1
        enc = encode_x(dtype, xr)
        if dtype == "int8":
            sx = QuantizedSlab(
                q=sx.q.at[slots].set(enc.q, mode="drop"),
                scale=sx.scale.at[slots].set(enc.scale, mode="drop"))
        else:
            sx = sx.at[slots].set(enc, mode="drop")
        return (sx, sy.at[slots].set(yr, mode="drop"),
                sm.at[slots].set(mr, mode="drop"))

    return jax.jit(full), jax.jit(apply)


class SlabStore:
    """One worker's device-resident training slab.

    `upload_full` replaces the whole slab (bootstrap, restore,
    mass-delete fallback); `apply_rows` scatters a drained dirty set
    (SlidingBuffer.drain_dirty) into it.  `bytes_uploaded` counts the
    HOST bytes each path shipped (tests/test_slab.py holds the counts),
    so the ~cap/changed-rows upload reduction is a measured number, not
    an estimate."""

    def __init__(self, dtype: str, capacity: int, num_features: int,
                 telemetry=None, row_dtype=np.float32):
        if dtype not in SLAB_DTYPES:
            raise ValueError(
                f"slab dtype {dtype!r} not in {SLAB_DTYPES}")
        # what a row is made of on the host (the task's `row_dtype`):
        # float32 features take any storage dtype, integer token rows
        # only their own
        self.row_dtype = np.dtype(row_dtype)
        if self.row_dtype != np.float32 and dtype != "f32":
            raise ValueError(
                f"slab dtype {dtype!r} stores float32 rows; rows of "
                f"{self.row_dtype} are stored as they are")
        self.dtype = dtype
        self.capacity = capacity
        self.num_features = num_features
        self._x = None
        self._y = None
        self._mask = None
        self.bytes_uploaded = 0
        self.full_uploads = 0
        self.incremental_applies = 0
        self.rows_applied = 0
        # optional metrics mirror of bytes_uploaded (.nbytes of host
        # arrays — no device sync), labeled by upload path
        if telemetry is None:
            from kafka_ps_tpu.telemetry import NULL_TELEMETRY
            telemetry = NULL_TELEMETRY
        self._telemetry = telemetry
        self._m_full = telemetry.counter("slab_upload_bytes_total",
                                         path="full")
        self._m_rows = telemetry.counter("slab_upload_bytes_total",
                                         path="incremental")

    @property
    def ready(self) -> bool:
        return self._x is not None

    def upload_full(self, x, y, mask) -> None:
        """Host slab copy -> device store (encode fused in one jit)."""
        x = np.ascontiguousarray(x, dtype=self.row_dtype)
        y = np.ascontiguousarray(y, dtype=np.int32)
        mask = np.ascontiguousarray(mask, dtype=np.float32)
        self.bytes_uploaded += x.nbytes + y.nbytes + mask.nbytes
        self.full_uploads += 1
        if self._telemetry.enabled:
            self._m_full.inc(x.nbytes + y.nbytes + mask.nbytes)
        full, _ = _slab_programs(self.dtype)
        self._x, self._y, self._mask = full(x, y, mask)

    def apply_rows(self, slots, xr, yr, mr) -> None:
        """Scatter the changed rows into the device slab.  The row
        count is padded to a power-of-two bucket (sentinel slot ==
        capacity, dropped by the scatter) so arrival-count jitter
        never re-compiles."""
        n = int(len(slots))
        if n == 0:
            return
        if not self.ready:
            raise RuntimeError("apply_rows before the first upload_full")
        b = MIN_BUCKET
        while b < n:
            b *= 2
        pad = b - n
        slots_p = np.concatenate(
            [np.asarray(slots, np.int32),
             np.full((pad,), self.capacity, np.int32)])
        xr_p = np.concatenate(
            [np.asarray(xr, self.row_dtype),
             np.zeros((pad, self.num_features), self.row_dtype)])
        yr_p = np.concatenate(
            [np.asarray(yr, np.int32), np.zeros((pad,), np.int32)])
        mr_p = np.concatenate(
            [np.asarray(mr, np.float32), np.zeros((pad,), np.float32)])
        self.bytes_uploaded += (slots_p.nbytes + xr_p.nbytes
                                + yr_p.nbytes + mr_p.nbytes)
        self.incremental_applies += 1
        self.rows_applied += n
        if self._telemetry.enabled:
            self._m_rows.inc(slots_p.nbytes + xr_p.nbytes
                             + yr_p.nbytes + mr_p.nbytes)
        _, apply = _slab_programs(self.dtype)
        self._x, self._y, self._mask = apply(
            self._x, self._y, self._mask, slots_p, xr_p, yr_p, mr_p)

    def arrays(self):
        """(x, y, mask) device views — x in the storage dtype (plain
        f32/bf16 array or QuantizedSlab); decode happens inside the
        training step."""
        if not self.ready:
            raise RuntimeError("slab store read before first upload")
        return self._x, self._y, self._mask

    def device_bytes(self) -> int:
        """Bytes the solver streams from HBM per slab read — the
        quantity --slab-dtype shrinks (docs/PERFORMANCE.md)."""
        if not self.ready:
            return 0
        if isinstance(self._x, QuantizedSlab):
            xb = self._x.q.nbytes + self._x.scale.nbytes
        else:
            xb = self._x.nbytes
        return xb + self._y.nbytes + self._mask.nbytes


class ParamPageSlab:
    """Hot-tier device residency for the tiered parameter store
    (kafka_ps_tpu/store/, docs/TIERING.md): page index -> f32 device
    array, with the same measured-bytes discipline as SlabStore —
    `bytes_uploaded` counts actual host->device traffic and
    `device_bytes()` the resident HBM footprint: counters, not
    estimates.

    This is SlabStore's parameter-side sibling: per-PAGE residency of
    the server's theta slice instead of the worker's full training
    slab.  Values are immutable device arrays replaced wholesale (the
    theta replacement contract, runtime/server.py), so readers may
    hold a fetched reference without locking."""

    def __init__(self):
        self._pages: dict[int, jax.Array] = {}
        self.bytes_uploaded = 0
        self.uploads = 0

    def __contains__(self, page: int) -> bool:
        return page in self._pages

    def __len__(self) -> int:
        return len(self._pages)

    def put(self, page: int, values) -> jax.Array:
        """Install a page value; host arrays are uploaded (counted),
        device arrays (a jit apply's output) are stored as-is —
        the steady-state hot path moves zero host bytes."""
        if isinstance(values, np.ndarray):
            host = np.ascontiguousarray(values, dtype=np.float32)
            self.bytes_uploaded += host.nbytes
            self.uploads += 1
            values = jnp.asarray(host)
        self._pages[page] = values
        return values

    def get(self, page: int) -> jax.Array:
        return self._pages[page]

    def pop_host(self, page: int) -> np.ndarray:
        """Demotion fetch: device -> host, page leaves the slab."""
        return np.asarray(self._pages.pop(page), dtype=np.float32)

    def drop(self, page: int) -> None:
        self._pages.pop(page, None)

    def device_bytes(self) -> int:
        return sum(a.nbytes for a in self._pages.values())

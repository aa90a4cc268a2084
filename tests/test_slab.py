"""Device-resident incremental slab (compress/slab.py + the
SlidingBuffer dirty-slot tracking it consumes, docs/PERFORMANCE.md):
dirty-set semantics for every eviction branch, incremental-equals-full
slab content under randomized insertion, the compile-once trace-count
invariant, and the shared int8 primitive the wire codec now rides on.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.compress import slab as slab_mod
from kafka_ps_tpu.compress.slab import (SLAB_DTYPES, QuantizedSlab,
                                        SlabStore, decode_x,
                                        dequantize_rows, quantize_rows)
from kafka_ps_tpu.data.buffer import SlidingBuffer
from kafka_ps_tpu.utils.config import BufferConfig


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def advance(self, ms):
        self.t += ms

    def __call__(self):
        return self.t


def _buffer(min_size=2, max_size=8, coeff=0.3, window=500,
            num_features=4):
    clock = FakeClock()
    buf = SlidingBuffer(
        num_features=num_features,
        cfg=BufferConfig(min_size=min_size, max_size=max_size,
                         coefficient=coeff, arrival_window=window),
        clock_ms=clock)
    return buf, clock


def _add(buf, clock, label, dt_ms=1000.0, row=None):
    clock.advance(dt_ms)
    buf.add(row if row is not None else {0: float(label)}, label)


# -- dirty-slot tracking -----------------------------------------------------

def test_dirty_marks_fill_and_overwrite_oldest():
    buf, clock = _buffer(min_size=2, max_size=4)
    for i in range(4):                       # fill branch, slots 0..3
        _add(buf, clock, i + 1)
    assert buf.dirty_slots == [0, 1, 2, 3]

    slots, xr, yr, mask = buf.drain_dirty()
    assert slots.tolist() == [0, 1, 2, 3]
    assert mask.tolist() == [1.0, 1.0, 1.0, 1.0]
    np.testing.assert_array_equal(yr, [1, 2, 3, 4])
    assert buf.dirty_slots == []             # drain clears

    _add(buf, clock, 5)                      # overwrite-oldest → slot 0
    assert buf.dirty_slots == [0]
    assert buf.insertion_id[0] == 5


def test_dirty_marks_shrink_deleted_and_overwritten_slots():
    """Target-shrink mass-delete: the n deleted slots AND the
    overwritten next-oldest slot are all dirty; drained masks are 0 for
    the deleted ones (the solver trusts the mask, not the stale x)."""
    buf, clock = _buffer(min_size=2, max_size=8)
    for i in range(8):
        _add(buf, clock, i + 1, dt_ms=100.0)
    buf.drain_dirty()

    # mean inter-arrival jumps → target clamps to min_size=2; count(8) >
    # target(2): IDs 1..6 (slots 0..5) deleted, ID 7 (slot 6) overwritten
    _add(buf, clock, 9, dt_ms=100_000.0)
    assert buf.count == 2
    assert buf.dirty_slots == [0, 1, 2, 3, 4, 5, 6]

    slots, _, _, mask = buf.drain_dirty()
    assert slots.tolist() == [0, 1, 2, 3, 4, 5, 6]
    assert mask.tolist() == [0.0] * 6 + [1.0]   # slot 6 got the new row


def test_add_many_marks_all_touched_slots():
    buf, clock = _buffer(min_size=4, max_size=8)
    v0 = buf.version
    clock.advance(1000.0)
    buf.add_many([({0: 1.0}, 1), ({1: 2.0}, 2), ({2: 3.0}, 3)])
    assert buf.dirty_slots == [0, 1, 2]
    assert buf.version == v0 + 3             # one bump per row


def test_restore_state_marks_every_slot_dirty():
    buf, clock = _buffer(min_size=2, max_size=8)
    for i in range(3):
        _add(buf, clock, i + 1)
    st = buf.state()
    buf.drain_dirty()
    v_before = buf.version

    buf.restore_state(st)
    assert buf.dirty_slots == list(range(8))  # whole slab suspect
    assert buf.version == v_before + 1


def test_version_does_not_alias_across_restore():
    """num_tuples_seen rewinds on restore (it is a buffer-content max);
    version is a monotonic mutation counter, so the worker's device-slab
    cache keyed off version can never mistake a restored buffer for the
    pre-restore one."""
    buf, clock = _buffer(min_size=2, max_size=8)
    _add(buf, clock, 1)
    _add(buf, clock, 2)
    st = buf.state()
    seen_then, ver_then = buf.num_tuples_seen, buf.version

    _add(buf, clock, 3)
    buf.restore_state(st)
    assert buf.num_tuples_seen == seen_then      # aliases
    assert buf.version > ver_then                # does not


def test_snapshot_clear_dirty_sets_new_baseline():
    buf, clock = _buffer(min_size=2, max_size=8)
    _add(buf, clock, 1)
    assert buf.dirty_slots == [0]
    buf.snapshot(clear_dirty=True)               # full upload subsumes
    assert buf.dirty_slots == []
    buf.snapshot()                               # plain view: no effect
    _add(buf, clock, 2)
    assert buf.dirty_slots == [1]


# -- incremental device slab == from-scratch upload --------------------------

def _assert_stores_equal(inc: SlabStore, ref: SlabStore, dtype: str):
    ix, iy, im = inc.arrays()
    rx, ry, rm = ref.arrays()
    if dtype == "int8":
        assert isinstance(ix, QuantizedSlab)
        np.testing.assert_array_equal(np.asarray(ix.q), np.asarray(rx.q))
        np.testing.assert_array_equal(np.asarray(ix.scale),
                                      np.asarray(rx.scale))
    else:
        # exact for bf16 too (same per-element astype); BITWISE for f32
        assert np.asarray(ix).tobytes() == np.asarray(rx).tobytes()
    np.testing.assert_array_equal(np.asarray(iy), np.asarray(ry))
    np.testing.assert_array_equal(np.asarray(im), np.asarray(rm))


@pytest.mark.parametrize("dtype", SLAB_DTYPES)
def test_incremental_slab_matches_full_upload_randomized(dtype):
    """Randomized insertions through every eviction branch (slow/fast
    cadence flips the dynamic target around): scattering each drained
    dirty set must leave the device slab exactly equal to a from-scratch
    upload of the buffer — bitwise for f32."""
    rng = np.random.default_rng(7)
    buf, clock = _buffer(min_size=2, max_size=8, num_features=4)
    inc = SlabStore(dtype, 8, 4)
    inc.upload_full(*buf.snapshot(clear_dirty=True))

    for step in range(60):
        dt = float(rng.choice([100.0, 1000.0, 50_000.0],
                              p=[0.6, 0.3, 0.1]))
        row = rng.normal(scale=2.0, size=4).astype(np.float32)
        _add(buf, clock, int(rng.integers(0, 5)), dt_ms=dt, row=row)
        slots, xr, yr, mr = buf.drain_dirty()
        inc.apply_rows(slots, xr, yr, mr)

        ref = SlabStore(dtype, 8, 4)
        ref.upload_full(*buf.snapshot())
        _assert_stores_equal(inc, ref, dtype)

    assert inc.full_uploads == 1
    assert inc.incremental_applies == 60


def test_incremental_bytes_far_below_full_upload():
    """The whole point: per-arrival host->device traffic is O(changed
    rows), not O(capacity)."""
    cap, nf = 1024, 64
    store = SlabStore("f32", cap, nf)
    store.upload_full(np.zeros((cap, nf), np.float32),
                      np.zeros((cap,), np.int32),
                      np.zeros((cap,), np.float32))
    full_bytes = store.bytes_uploaded
    store.apply_rows(np.array([3]), np.zeros((1, nf), np.float32),
                     np.array([1], np.int32), np.array([1.0], np.float32))
    assert (store.bytes_uploaded - full_bytes) * 100 < full_bytes


# -- compile-once trace-count regression -------------------------------------

def test_apply_traces_once_per_bucket_not_per_arrival():
    """Steady-state single-row arrivals must NOT re-trace the scatter:
    row counts pad to power-of-two buckets, so counts 1..4 share one
    compiled program and count 5 costs exactly one more."""
    store = SlabStore("f32", 32, 8)
    store.upload_full(np.zeros((32, 8), np.float32),
                      np.zeros((32,), np.int32),
                      np.zeros((32,), np.float32))

    def apply_n(n):
        store.apply_rows(np.arange(n), np.ones((n, 8), np.float32),
                         np.ones((n,), np.int32),
                         np.ones((n,), np.float32))

    apply_n(1)                                   # warm the bucket-4 program
    warm = slab_mod.TRACE_COUNTS["apply"]
    for n in (1, 2, 3, 4, 1, 1, 1, 1, 1, 1):     # jitter inside the bucket
        apply_n(n)
    assert slab_mod.TRACE_COUNTS["apply"] == warm

    apply_n(5)                                   # next bucket: ONE new trace
    assert slab_mod.TRACE_COUNTS["apply"] == warm + 1
    apply_n(7)
    assert slab_mod.TRACE_COUNTS["apply"] == warm + 1


def test_full_upload_traces_once_per_shape():
    store = SlabStore("bf16", 16, 4)
    x = np.zeros((16, 4), np.float32)
    y = np.zeros((16,), np.int32)
    m = np.zeros((16,), np.float32)
    store.upload_full(x, y, m)
    warm = slab_mod.TRACE_COUNTS["full"]
    for _ in range(5):
        store.upload_full(x, y, m)
    assert slab_mod.TRACE_COUNTS["full"] == warm


def test_decode_fused_into_solver_traces_once():
    """decode_x is traced INSIDE the task's local_update — per-arrival
    solver dispatches at a steady (shape, dtype) must not re-trace it
    (the no-per-arrival-re-jit half of the PS101 story)."""
    from kafka_ps_tpu.models.task import LogRegTask
    from kafka_ps_tpu.utils.config import ModelConfig

    cfg = ModelConfig(num_features=4, num_classes=3)
    task = LogRegTask(cfg)
    theta = jnp.zeros((cfg.num_params,), jnp.float32)
    y = jnp.zeros((8,), jnp.int32)
    mask = jnp.ones((8,), jnp.float32)
    for stored in (jnp.zeros((8, 4), jnp.float32),
                   jnp.zeros((8, 4), jnp.bfloat16),
                   QuantizedSlab(q=jnp.zeros((8, 4), jnp.int8),
                                 scale=jnp.ones((8, 1), jnp.float32))):
        task.local_update(theta, stored, y, mask)  # warm
        warm = slab_mod.TRACE_COUNTS["decode"]
        for _ in range(10):
            LogRegTask(cfg).local_update(theta, stored, y, mask)
        assert slab_mod.TRACE_COUNTS["decode"] == warm


# -- the shared int8 primitive -----------------------------------------------

def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(3)
    r = jnp.asarray(rng.normal(scale=5.0, size=(16, 32)),
                    dtype=jnp.float32)
    q, scale = quantize_rows(r)
    back = dequantize_rows(q, scale)
    # max-abs scheme: per-row error ≤ half a quantization step
    step = np.asarray(scale)[:, None]
    assert (np.abs(np.asarray(back - r)) <= step / 2 + 1e-7).all()


def test_quantize_all_zero_row_is_exact():
    r = jnp.zeros((3, 8), jnp.float32)
    q, scale = quantize_rows(r)
    assert np.asarray(scale).tolist() == [0.0, 0.0, 0.0]
    np.testing.assert_array_equal(np.asarray(dequantize_rows(q, scale)),
                                  np.zeros((3, 8)))


def test_wire_codec_int8_matches_shared_primitive():
    """compress/codecs.py's int8 wire codec is now a reshape around
    quantize_rows/dequantize_rows — same values chunk-for-chunk, so the
    refactor is invisible to the EF/replay bitwise contract."""
    from kafka_ps_tpu.compress import wire
    from kafka_ps_tpu.compress.codecs import get_codec
    from kafka_ps_tpu.compress.wire import INT8_CHUNK

    n = 700                                      # pads to 3 chunks of 256
    rng = np.random.default_rng(11)
    v = rng.normal(scale=3.0, size=(n,)).astype(np.float32)
    codec = get_codec(wire.parse_codec("int8"), n)
    q, scale = codec.encode(v)

    nchunks = wire.int8_chunks(n)
    r = np.pad(v, (0, nchunks * INT8_CHUNK - n)).reshape(nchunks,
                                                         INT8_CHUNK)
    q_ref, scale_ref = quantize_rows(jnp.asarray(r))
    np.testing.assert_array_equal(np.asarray(q),
                                  np.asarray(q_ref).reshape(-1))
    np.testing.assert_array_equal(np.asarray(scale), np.asarray(scale_ref))
    np.testing.assert_array_equal(
        np.asarray(codec.decode(q, scale)),
        np.asarray(dequantize_rows(q_ref, scale_ref)).reshape(-1)[:n])


def test_decode_x_f32_identity_bits():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(4, 4)), dtype=jnp.float32)
    assert np.asarray(decode_x(x)).tobytes() == np.asarray(x).tobytes()


# -- worker-level: incremental slab is invisible to training -----------------

def test_worker_gradients_bitwise_equal_incremental_vs_full():
    """Two f32 workers fed identical arrivals — one scattering dirty
    rows into a resident slab, one re-uploading per change — must emit
    BITWISE-identical gradient messages (the tier1 --perf leg re-checks
    this end-to-end through the app runner)."""
    from kafka_ps_tpu.data.synth import generate
    from kafka_ps_tpu.runtime import fabric as fabric_mod
    from kafka_ps_tpu.runtime.messages import KeyRange, WeightsMessage
    from kafka_ps_tpu.runtime.worker import WorkerNode
    from kafka_ps_tpu.utils.config import (BufferConfig, ModelConfig,
                                           PSConfig)

    x, y = generate(24, 8, 3, seed=0)

    def run(incremental: bool) -> list[bytes]:
        cfg = PSConfig(
            num_workers=1, task="logreg",
            model=ModelConfig(num_features=8, num_classes=3),
            buffer=BufferConfig(min_size=4, max_size=16),
            slab_dtype="f32", slab_incremental=incremental)
        buf = SlidingBuffer(8, cfg.buffer)
        fab = fabric_mod.Fabric()
        node = WorkerNode(0, cfg, fab, buf)
        out, theta, i = [], jnp.zeros(node.task.num_params), 0
        for clock in range(4):
            for _ in range(6):                   # 6 arrivals per round
                buf.add(dict(enumerate(x[i])), int(y[i]))
                i += 1
            node.on_weights(WeightsMessage(
                vector_clock=clock,
                key_range=KeyRange(0, node.task.num_params),
                values=theta))
            g = fab.poll(fabric_mod.GRADIENTS_TOPIC, 0)
            out.append(np.asarray(g.values).tobytes())
        return out

    assert run(True) == run(False)

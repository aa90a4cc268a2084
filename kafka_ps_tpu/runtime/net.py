"""Socket transport — the cross-host hop for the ASYNC consistency
models (bounded delay / eventual), carrying the binary serde frames
(runtime/serde.py) over TCP.

This is the last Kafka property with no in-process counterpart: the
reference's server JVM and worker JVMs exchange WEIGHTS / GRADIENTS /
INPUT_DATA through the broker from different machines
(kubernetes/server.yaml + worker.yaml, broker kafka:9092).  The fused
BSP path scales out through jax.distributed collectives instead
(parallel/multihost.py) — but the async modes are host-orchestrated by
design, so their multi-host story is exactly this: a server process
(aggregator + consistency gate + producer) and worker processes
(buffers + local solvers), point-to-point sockets in place of topics.

Wire format, little-endian:
    frame  := <u32 length> <u8 topic> <i64 key> <payload>
    topic  := 1 WEIGHTS | 2 GRADIENTS | 3 INPUT_DATA | 4 HELLO | 5 READY
              | 6 PING | 7 PONG | 8 CONFIG | 9 PREDICT | 10 PREDICTION
              | 11 DATA_BATCH
    payload:= serde.to_bytes(message)   (HELLO: <i64 n> <i64 ids[n]>
                                                [<u8 codec_id> <f32 param>];
                                         READY/PING/PONG: empty;
                                         CONFIG: <f64 ping_interval_s>
                                                 <i64 run_id>
                                                 [<u8 codec_id> <f32 param>];
                                         DATA_BATCH: columnar <i64 -nrows>
                                         + packed index/value/label
                                         columns (serde.
                                         encode_labeled_rows); the
                                         legacy <i64 nrows> then per row
                                         <i32 len><serde bytes> layout
                                         is still accepted on receive;
                                         PREDICT / PREDICTION: see the
                                         encode_/decode_ helpers below)
`key` is the logical worker id (the Kafka record key, CsvProducer.java:61);
for PREDICT/PREDICTION it is the client's request id (echoed back).

Codec negotiation (docs/COMPRESSION.md): HELLO optionally carries the
worker's `--compress` codec; the server's CONFIG reply echoes the codec
the pair will actually use — the server's own codec when both sides
named the SAME one, `none` otherwise.  Both trailers are read with
unpack_from, so an old peer simply never sees them and the pair falls
back to uncompressed f32 frames — a `--compress none` fleet is
byte-identical to before this field existed.

Trace-context negotiation (docs/OBSERVABILITY.md) rides the same
pattern: one `<u8 offer>` byte AFTER the codec trailer on HELLO (the
worker offers 1 iff its tracer is on) and on CONFIG (the server answers
1 iff the offer arrived AND its own tracer is on).  When the pair
negotiates tracing ON, every WEIGHTS / GRADIENTS payload gains a
16-byte `<u64 flow_id> <u64 parent_span>` suffix after the serde bytes;
the receiver strips it before decoding and emits the matching Chrome
flow event, so a delta's worker -> server -> serving lifecycle renders
as one connected arrow chain in Perfetto after the merge CLI
(`python -m kafka_ps_tpu.telemetry merge`).  Old peers never offer and
never see a suffix — a legacy fleet stays byte-identical.

Range sharding (docs/SHARDING.md): a sharded deployment runs N of
these bridges — one per shard-server process — and every worker
process holds N WorkerBridge connections.  The frames themselves need
no new fields: the shard/range header rides INSIDE the serde payload
(every weights/gradient message carries a KeyRange; sparse slices are
tid-6 SparseDeltaMessage frames whose key_range names the owning
shard's span), so an unsharded peer speaks the same wire format.
GRADIENT sends go out per-bridge via `WorkerBridge.send_gradients`
(the ShardRouter's hook) and WEIGHTS slices land per-bridge into the
assembler via `set_weights_sink`.

Delivery properties preserved from the reference fabric: addressed
per-worker delivery, per-connection FIFO (TCP), asynchronous buffering
(the consistency gate never blocks on a send).  Cites:
ServerProcessor.java:172-182 (weights send), WorkerTrainingProcessor
.java:95-97 (gradient send, record key 0), CsvProducer.java:61-65.
"""

from __future__ import annotations

import dataclasses
import socket
import struct
import sys
import threading
import time

from kafka_ps_tpu.analysis.lockgraph import OrderedLock
from kafka_ps_tpu.compress.wire import NONE as CODEC_SPEC_NONE
from kafka_ps_tpu.compress.wire import CODEC_NONE, CodecSpec
from kafka_ps_tpu.runtime import fabric as fabric_mod
from kafka_ps_tpu.runtime import serde
# the wire engine (docs/WIRE.md): coalescing writer, buffered reader,
# scatter-gather send, and the shared frame header + force_close
from kafka_ps_tpu.runtime.wire import (_FRAME, FrameWriter, RecvBuffer,
                                       force_close, sendmsg_all)
from kafka_ps_tpu.telemetry import NULL_TELEMETRY
from kafka_ps_tpu.telemetry.flight import FLIGHT
from kafka_ps_tpu.utils.trace import NULL_TRACER

(T_WEIGHTS, T_GRADIENTS, T_DATA, T_HELLO, T_READY,
 T_PING, T_PONG, T_CONFIG, T_PREDICT, T_PREDICTION,
 T_DATA_BATCH, T_WEIGHTS_AGG) = range(1, 13)
# the full frame-topic table: data topics map to their fabric names,
# control/serving topics to wire-only names (test_net_framing.py keeps
# this exhaustive against the T_* constants)
TOPIC_NAMES = {T_WEIGHTS: fabric_mod.WEIGHTS_TOPIC,
               T_GRADIENTS: fabric_mod.GRADIENTS_TOPIC,
               T_DATA: fabric_mod.INPUT_DATA_TOPIC,
               T_HELLO: "hello", T_READY: "ready",
               T_PING: "ping", T_PONG: "pong", T_CONFIG: "config",
               T_PREDICT: "predict", T_PREDICTION: "prediction",
               T_DATA_BATCH: "input-data-batch",
               T_WEIGHTS_AGG: "weights-agg"}

# the optional codec trailer on HELLO and CONFIG (negotiation above)
_CODEC_TRAILER = struct.Struct("<Bf")
# the optional trace-offer/answer byte AFTER the codec trailer
_TRACE_TRAILER = struct.Struct("<B")
# the per-message trace context suffixed to WEIGHTS/GRADIENTS payloads
# when the pair negotiated tracing: <u64 flow_id> <u64 parent_span>
_TRACE_CTX = struct.Struct("<QQ")
# the optional shared-memory request byte AFTER the trace trailer on
# HELLO, and the matching offer AFTER the trace trailer on CONFIG:
# <u8 granted> <16s nonce> <64s NUL-padded segment name>.  Same
# append-and-length-check pattern as the codec/trace trailers: legacy
# peers on either side never see the bytes and stay on sockets
# (serving/shm.py, docs/SERVING.md "Dispatch economics")
_SHM_TRAILER = struct.Struct("<B")
_SHM_OFFER = struct.Struct("<B16s64s")
# the optional aggregator-role byte AFTER the shm trailer on HELLO
# (kafka_ps_tpu/agg/, docs/AGGREGATION.md): 1 marks the connection as
# a per-host aggregator relay.  Its registered ids are the MEMBER
# workers behind it (weights/data route through it), its disconnect
# does NOT evict them (the members are alive behind a restarting
# relay; they resend through the next one), and grouped fan-out may
# target it with ONE T_WEIGHTS_AGG frame per release.  Same
# append-and-length-check pattern as every other trailer: plain
# workers never send the byte and nothing changes for them.
_AGG_TRAILER = struct.Struct("<B")
# T_CONFIG re-sent mid-stream with this run id is a GOODBYE: the run is
# over and the peer is closing on purpose.  An aggregator relay sends
# it downstream before closing (agg/relay.py) so its member workers can
# tell a finished run from a crashed relay — the latter drops the
# members' ONLY connection exactly like end-of-run would, and without
# this marker they could not know to hold the run open and reconnect
# (cli/socket_mode._run_worker_sharded).  Real run ids are time_ns() or
# checkpointed positives; -1 can never collide.
GOODBYE_RUN_ID = -1
# T_WEIGHTS_AGG payload: <q n> then n x <q worker><q clock>, then ONE
# serde weights body shared by all members — the aggregator re-stamps
# the body's vector clock per member (serde._HEADER keeps the clock at
# byte offset 5 for plain AND compressed weights) and re-broadcasts,
# so a k-member release costs one upstream send instead of k.
_AGG_MEMBER = struct.Struct("<qq")

# -- serving-plane payloads (kafka_ps_tpu/serving/, docs/SERVING.md) -------
# PREDICT: the feature row plus the request's staleness bound; sentinel
# -1 encodes "unbounded" (clocks are non-negative, ages positive)
_PREDICT_HEADER = struct.Struct("<qdq")   # min_clock, max_age_s, n features
# PREDICTION: status + (label, confidence, snapshot clock, snapshot time)
_PREDICTION = struct.Struct("<Bqdqd")
PREDICT_OK, PREDICT_STALE, PREDICT_FAILED, PREDICT_OVERLOADED = 0, 1, 2, 3
# optional model-id trailer AFTER the feature row (multi-model serving,
# docs/SERVING.md) — same append-and-length-check pattern as the codec
# trailer, so frames from peers that never send it decode as model 0
_MODEL_TRAILER = struct.Struct("<q")


def encode_predict_request(x, min_clock: int | None = None,
                           max_age_s: float | None = None,
                           model_id: int = 0) -> bytes:
    import numpy as np
    row = np.asarray(x, dtype=np.float32).reshape(-1)
    return (_PREDICT_HEADER.pack(
        -1 if min_clock is None else int(min_clock),
        -1.0 if max_age_s is None else float(max_age_s),
        row.size) + row.tobytes()
        + _MODEL_TRAILER.pack(int(model_id)))


def decode_predict_request(payload: bytes):
    """(features, min_clock | None, max_age_s | None, model_id)."""
    import numpy as np
    min_clock, max_age_s, n = _PREDICT_HEADER.unpack_from(payload, 0)
    row = np.frombuffer(payload, dtype=np.float32, count=n,
                        offset=_PREDICT_HEADER.size)
    model_id = 0
    tail = _PREDICT_HEADER.size + row.nbytes
    if len(payload) >= tail + _MODEL_TRAILER.size:
        (model_id,) = _MODEL_TRAILER.unpack_from(payload, tail)
    return (row, None if min_clock < 0 else min_clock,
            None if max_age_s < 0 else max_age_s, model_id)


def encode_prediction(status: int, label: int = -1, confidence: float = 0.0,
                      vector_clock: int = -1, wall_time: float = 0.0) -> bytes:
    return _PREDICTION.pack(status, label, confidence, vector_clock,
                            wall_time)


def decode_prediction(payload: bytes):
    """(status, label, confidence, vector_clock, wall_time)."""
    return _PREDICTION.unpack_from(payload, 0)


def _encode_result(result) -> bytes:
    """Map a PredictionEngine callback argument — a Prediction, or the
    typed failure the engine passed instead — onto a wire PREDICTION
    payload.  Shared by the socket reply path and the shm serve loop so
    the two transports cannot drift on status semantics."""
    from kafka_ps_tpu.serving.policy import OverloadedError, StalenessError
    if isinstance(result, OverloadedError):
        return encode_prediction(PREDICT_OVERLOADED)
    if isinstance(result, StalenessError):
        return encode_prediction(PREDICT_STALE)
    if isinstance(result, BaseException):
        return encode_prediction(PREDICT_FAILED)
    return encode_prediction(PREDICT_OK, result.label, result.confidence,
                             result.vector_clock, result.wall_time)


def send_frame(sock: socket.socket, topic: int, key: int,
               payload: bytes = b"") -> None:
    """One frame, immediately (the non-queued fallback path).  Header
    and payload go out as a two-element scatter-gather send — a
    multi-KB weights payload is never copied just to prepend 13
    bytes."""
    header = _FRAME.pack(_FRAME.size - 4 + len(payload), topic, key)
    if len(payload):
        sendmsg_all(sock, (header, payload))
    else:
        sock.sendall(header)


def locked_send(sock: socket.socket, lock, topic: int, key: int,
                payload: bytes = b"") -> None:
    """Serialize one frame write onto `sock` under its dedicated write
    lock.  Interleaved frame bodies from concurrent senders would
    corrupt the stream, so the write lock's entire critical section IS
    the write — every bridge sends through here."""
    with lock:
        # pscheck: disable=PS105 (dedicated write lock: this send IS the critical section)
        send_frame(sock, topic, key, payload)


def recv_frame(sock: socket.socket) -> tuple[int, int, memoryview] | None:
    """(topic, key, payload) or None on a clean EOF.  The payload is a
    zero-copy memoryview into the received frame body — every decode
    site (np.frombuffer, struct.unpack_from, zlib, serde) reads
    bytes-likes, so slicing the 9-byte topic/key prefix no longer
    copies the multi-KB message payload."""
    head = _recv_exact(sock, 4)
    if head is None:
        return None
    (length,) = struct.unpack("<I", head)
    body = _recv_exact(sock, length)
    if body is None:
        raise ConnectionError("mid-frame EOF")
    topic, key = struct.unpack_from("<Bq", body, 0)
    return topic, key, memoryview(body)[9:]


def _read_codec_trailer(payload, offset: int) -> CodecSpec:
    """The optional <u8 codec_id> <f32 param> trailer of a HELLO or
    CONFIG payload; NONE when absent (old peer) or unintelligible
    (newer peer with codec ids we don't know)."""
    if len(payload) < offset + _CODEC_TRAILER.size:
        return CODEC_SPEC_NONE
    cid, param = _CODEC_TRAILER.unpack_from(payload, offset)
    try:
        return CodecSpec(cid, param)
    except ValueError:
        return CODEC_SPEC_NONE


def _read_trace_flag(payload, offset: int) -> bool:
    """The optional <u8> trace offer/answer after the codec trailer;
    False when absent (old peer)."""
    if len(payload) < offset + _TRACE_TRAILER.size:
        return False
    (flag,) = _TRACE_TRAILER.unpack_from(payload, offset)
    return bool(flag)


def _read_shm_flag(payload, offset: int) -> bool:
    """The optional <u8> shared-memory request after the trace trailer
    on HELLO; False when absent (old peer, or a client on sockets)."""
    if len(payload) < offset + _SHM_TRAILER.size:
        return False
    (flag,) = _SHM_TRAILER.unpack_from(payload, offset)
    return bool(flag)


def _read_agg_flag(payload, offset: int) -> bool:
    """The optional <u8> aggregator-role byte after the shm trailer on
    HELLO; False when absent (a plain worker, or any older peer)."""
    if len(payload) < offset + _AGG_TRAILER.size:
        return False
    (flag,) = _AGG_TRAILER.unpack_from(payload, offset)
    return bool(flag)


def _read_shm_offer(payload, offset: int) -> tuple[str, bytes] | None:
    """The optional shm offer after the trace trailer on CONFIG:
    (segment name, nonce), or None when absent (legacy server) or the
    server declined (granted byte 0 — shm off, or segment creation
    failed on its side)."""
    if len(payload) < offset + _SHM_OFFER.size:
        return None
    granted, nonce, name = _SHM_OFFER.unpack_from(payload, offset)
    if not granted:
        return None
    return name.rstrip(b"\0").decode("ascii", "replace"), nonce


def _frame_counters(telemetry):
    """Pre-resolved per-topic (sent, received) counter children plus the
    matching wire-byte counters, so the frame hot paths never hit the
    registry's family lock.  All-null children when telemetry is off."""
    sent = {t: (telemetry.counter("frames_sent", topic=name),
                telemetry.counter("wire_bytes_total", topic=name,
                                  direction="out"))
            for t, name in TOPIC_NAMES.items()}
    recv = {t: (telemetry.counter("frames_received", topic=name),
                telemetry.counter("wire_bytes_total", topic=name,
                                  direction="in"))
            for t, name in TOPIC_NAMES.items()}
    return sent, recv


def _recv_exact(sock: socket.socket, n: int) -> bytearray | bytes | None:
    """Exactly n bytes, or None on a clean EOF before the first byte.
    EOF after a partial read is a torn frame — a crashed peer, never an
    orderly shutdown — and raises so the caller treats it as a failure
    (the reference gets this for free from Kafka's record framing).
    Preallocated bytearray filled via recv_into — no quadratic
    `bytes += chunk` re-copy for payloads the kernel delivers in
    pieces.  Stays as the fallback read path for the handshake and the
    PredictClient (bridge readers use wire.RecvBuffer)."""
    if n == 0:
        return b""
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:])
        if r == 0:
            if got:
                raise ConnectionError(
                    f"mid-frame EOF ({got}/{n} bytes)")
            return None
        got += r
    return buf


class ServerBridge:
    """Server-process side: listens for worker processes, forwards
    WEIGHTS / INPUT_DATA to the connection owning each worker key, and
    delivers incoming GRADIENTS into the local fabric's gather queue.

    Install via `bridge.wrap(fabric)`: the returned fabric routes sends
    addressed to remote workers over their socket and leaves local
    behavior untouched (the Kafka-broker role, minus the broker).

    Failure detection (the consumer-group-rebalance analogue, SURVEY §5):
    a reader hitting EOF/reset purges the connection's worker ids and
    fires `on_disconnect(ids)`; a later HELLO re-registers them and
    fires `on_hello(ids)`; READY fires `on_ready(worker)` — the caller
    (cli/socket_mode.run_server) turns these into evictions and
    readmissions on the ServerNode.  With `heartbeat_interval` set the
    bridge PINGs every connection on that cadence and, when
    `heartbeat_timeout` is also set, force-closes connections silent for
    longer than it — half-open TCP (a worker host vanishing without a
    FIN) then surfaces as a normal disconnect instead of hanging the
    consistency gate forever.
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 heartbeat_interval: float | None = None,
                 heartbeat_timeout: float | None = None,
                 run_id: int = 0, codec: CodecSpec | None = None,
                 tracer=None, telemetry=None, shm: bool = False,
                 coalesce: bool = True):
        # `run_id` identifies the logical RUN (fresh server start, or
        # the run a checkpoint resume continues — utils/checkpoint.py
        # persists it).  Advertised in T_CONFIG so worker processes can
        # tell whether their local state file belongs to THIS run or is
        # a stale leftover from an earlier one (cli/socket_mode.py).
        self.run_id = run_id
        # `codec`: this server's `--compress` choice; per-connection
        # negotiation (docstring above) lands in `_codec_of`, and sends
        # to a none-negotiated peer strip the encoded payload in _send
        self.codec = codec if codec is not None else CODEC_SPEC_NONE
        # guarded-by: _lock (HELLO writes hold the state lock; send-path reads are GIL-atomic dict gets)
        self._codec_of: dict[socket.socket, CodecSpec] = {}
        self._tracer = tracer or NULL_TRACER
        self._telemetry = telemetry or NULL_TELEMETRY
        # per-connection trace negotiation (module docstring): True iff
        # the peer offered AND this side's tracer is on
        # guarded-by: _lock (HELLO writes hold the state lock; send-path reads are GIL-atomic)
        self._trace_of: dict[socket.socket, bool] = {}
        # pre-resolved metric children: one dict lookup + one leaf-lock
        # inc per frame on the hot path (null metrics when telemetry off)
        self._m_sent, self._m_recv = _frame_counters(self._telemetry)
        # bytes on the wire per frame topic, both directions, including
        # the 13-byte frame header (tests/test_net_framing.py reads this)
        self.wire_bytes: dict[int, int] = {}
        self._wire_lock = OrderedLock("ServerBridge.wire")
        self._listener = socket.create_server((host, port))
        self.port = self._listener.getsockname()[1]
        # guarded-by: _lock (registration holds the cv; routing reads are GIL-atomic dict gets)
        self._conn_of: dict[int, socket.socket] = {}   # worker -> conn
        self._ready: set[int] = set()
        self._lock = OrderedLock("ServerBridge.state", reentrant=True)
        self._cv = threading.Condition(self._lock)
        # pscheck: disable=PS201 (wrap publishes the fabric before any traffic can reference it - attach-before-serve)
        self._fabric: fabric_mod.Fabric | None = None
        self._stop = threading.Event()
        self._send_lock: dict[socket.socket, OrderedLock] = {}
        # `--wire-coalesce` (docs/WIRE.md): queue frames per connection
        # and ship them in scatter-gather batches from a dedicated
        # writer thread; off = the classic one-sendall-per-frame path
        self._coalesce = bool(coalesce)
        # guarded-by: _lock (accept-loop writes hold the state lock; send-path reads are GIL-atomic)
        self._writer_of: dict[socket.socket, FrameWriter] = {}
        # guarded-by: _lock (registered under the lock; the reader's per-frame store is GIL-atomic and the heartbeat tolerates an interval of staleness)
        self._last_recv: dict[socket.socket, float] = {}
        self.on_disconnect = None   # Callable[[list[int]], None]
        self.on_hello = None        # Callable[[list[int]], None]
        self.on_ready = None        # Callable[[int], None]
        # pscheck: disable=PS201 (attach_serving publishes the engine before predict frames can arrive)
        self._serving = None        # PredictionEngine (attach_serving)
        # same-host shared-memory fast path (serving/shm.py): offered
        # per connection on a HELLO that requests it, only when enabled
        # here AND a serving engine is attached
        self._shm_enabled = bool(shm)
        # connections whose HELLO carried the aggregator-role byte
        # (kafka_ps_tpu/agg/): weights to their member ids may group
        # into T_WEIGHTS_AGG frames, and their disconnects are relay
        # restarts, not member failures — on_disconnect is suppressed
        self._agg_conns: set[socket.socket] = set()
        # guarded-by: _lock (offer/teardown hold the state lock; reads are GIL-atomic)
        self._shm_of: dict[socket.socket, object] = {}
        self._shm_threads: list[threading.Thread] = []
        self._m_shm = self._telemetry.counter("serving_dispatch_mode",
                                              mode="shm")
        # pscheck: disable=PS201 (failure-path counter; a racing increment can only undercount telemetry)
        self.dropped_sends = 0      # frames lost to dead connections
        self._hb_interval = heartbeat_interval
        self._hb_timeout = heartbeat_timeout
        # guarded-by: _lock (accept loop swaps the list under the state lock; close() joins after the listener is down)
        self._reader_threads: list[threading.Thread] = []
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True, name="kps-net-accept")
        self._accept_thread.start()
        self._hb_thread = None
        if heartbeat_interval:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop, daemon=True,
                name="kps-net-heartbeat")
            self._hb_thread.start()

    # -- fabric integration ------------------------------------------------

    def wrap(self, fabric: fabric_mod.Fabric) -> fabric_mod.Fabric:
        bridge = self

        # subclass the wrapped fabric's OWN class, not the base Fabric:
        # wrapping a log.durable_fabric.DurableFabric must keep its
        # append-before-enqueue send and its recover/commit surface —
        # the sharded split deployment (--shards N --durable-log, one
        # log partition per shard process) relies on exactly that
        class BridgedFabric(type(fabric)):
            def send(self, topic, key, message):
                conn = bridge._conn_of.get(key) \
                    if topic == fabric_mod.WEIGHTS_TOPIC else None
                if conn is not None:
                    bridge._send(conn, T_WEIGHTS, key, message)
                else:
                    super().send(topic, key, message)

        out = object.__new__(BridgedFabric)
        # share ALL state with the original (queues, cond, tracer — and
        # any subclass state such as the durable log writer) so
        # pre-wrap queues and already-appended partitions stay visible
        out.__dict__ = fabric.__dict__
        self._fabric = out
        return out

    def attach_serving(self, engine) -> None:
        """Answer T_PREDICT frames from any connection through a
        serving.engine.PredictionEngine.  Requests are submitted async —
        the reader thread never blocks on a batch deadline — and the
        reply goes out from the engine's batcher thread.  A client need
        not HELLO: predict-only connections register no worker ids, so
        the weights/data routing never sees them."""
        self._serving = engine

    def send_data(self, worker: int, features: dict[int, float],
                  label: int) -> bool:
        """Forward one stream row to the process hosting `worker`.
        False if that worker is not (yet) connected or its connection
        just died — the caller reroutes or counts the row."""
        from kafka_ps_tpu.runtime.messages import LabeledData
        conn = self._conn_of.get(worker)
        if conn is None:
            return False
        return self._send(conn, T_DATA, worker, LabeledData(features, label))

    def send_data_batch(self, worker: int, rows) -> bool:
        """Forward N stream rows to the process hosting `worker` in ONE
        columnar frame: <i64 -nrows> discriminator + packed
        feature-index/value/label ndarray columns
        (serde.encode_labeled_rows) decoded straight into
        SlidingBuffer.add_many — no per-row serde header, length
        prefix, or dict rebuild on the encode side.  Receivers accept
        the legacy per-row <i32 len><serde blob> layout too (nrows >=
        0), so a mixed-version fleet interoperates.  `rows` is a
        sequence of (features, label); False exactly like send_data
        (the caller reroutes the rows)."""
        conn = self._conn_of.get(worker)
        if conn is None:
            return False
        return self._send_raw(conn, T_DATA_BATCH, worker,
                              serde.encode_labeled_rows(rows))

    def send_weights_group(self, release, builder) -> set:
        """Grouped weights fan-out for aggregator relays (the
        ServerNode.weights_group_send hook, docs/AGGREGATION.md): ship
        ONE T_WEIGHTS_AGG frame per relay covering every released
        member behind it — member (worker, clock) list + one weights
        body the relay re-stamps and re-broadcasts.  `builder(clock)`
        produces the WeightsMessage (called once per relay; repeated
        calls hit the server compressor's identity cache).  Returns the
        worker ids actually shipped — members on plain connections (or
        none at all) are left for the caller's per-worker path."""
        groups: dict[socket.socket, list] = {}
        for worker, clock in release:
            conn = self._conn_of.get(worker)
            if conn is not None and conn in self._agg_conns:
                groups.setdefault(conn, []).append((worker, clock))
        handled: set = set()
        for conn, members in groups.items():
            msg = builder(members[0][1])
            if (getattr(msg, "encoded", None) is not None
                    and self._codec_of.get(conn,
                                           CODEC_SPEC_NONE).codec_id
                    == CODEC_NONE):
                # same downgrade rule as _send: a none-negotiated relay
                # gets the decoded f32 body its members will train on
                msg = dataclasses.replace(msg, encoded=None)
            payload = b"".join(
                [struct.pack("<q", len(members))]
                + [_AGG_MEMBER.pack(w, c) for w, c in members]
                + [serde.to_bytes(msg)])
            if self._send_raw(conn, T_WEIGHTS_AGG, 0, payload):
                handled.update(w for w, _ in members)
        return handled

    def send_goodbye(self) -> None:
        """Announce end-of-run to every live connection (T_CONFIG with
        GOODBYE_RUN_ID) — the relay's last act before closing its
        downstream listener, so members stop instead of waiting out the
        crash-reconnect grace window.  Best-effort: a connection that
        dies mid-goodbye just pays the grace timeout."""
        payload = struct.pack("<dq", self._hb_interval or 0.0,
                              GOODBYE_RUN_ID)
        for conn in list(self._send_lock):
            self._send_raw(conn, T_CONFIG, 0, payload)

    def forward_frame(self, topic: int, worker: int,
                      payload: bytes) -> bool:
        """Raw pre-serialized frame send to the connection owning
        `worker` — the aggregator relay's downstream re-broadcast path
        (weights with a re-stamped clock, pass-through data rows): the
        bytes cross without a decode/encode cycle, so what the worker
        receives is bit-identical to what the server sent.  A weights
        frame to a trace-negotiated member gets a FRESH flow suffix —
        the member's reader strips 16 bytes unconditionally, and the
        upstream hop's suffix never crossed the relay."""
        conn = self._conn_of.get(worker)
        if conn is None:
            return False
        if topic == T_WEIGHTS and self._trace_of.get(conn):
            fid = self._tracer.new_flow_id()
            with self._tracer.span("net.send", topic="weights",
                                   worker=worker):
                self._tracer.flow_start("weights.wire", fid,
                                        worker=worker)
            payload += _TRACE_CTX.pack(fid, 0)
        return self._send_raw(conn, topic, worker, payload)

    def wait_for_connected(self, workers, timeout: float = 60.0) -> None:
        """Block until every worker id has a connection (HELLO seen) —
        before this the producer has nowhere to send their rows."""
        with self._cv:
            ok = self._cv.wait_for(
                lambda: all(w in self._conn_of for w in workers),
                timeout=timeout)
        if not ok:
            missing = [w for w in workers if w not in self._conn_of]
            raise TimeoutError(f"workers {missing} not connected in time")

    def wait_for_workers(self, workers, timeout: float = 60.0) -> None:
        """Block until every worker id has reported READY (its buffer
        holds data) — the actual invariant behind the reference's fixed
        20 s bootstrap sleep (ServerAppRunner.java:95)."""
        with self._cv:
            ok = self._cv.wait_for(
                lambda: all(w in self._ready for w in workers),
                timeout=timeout)
        if not ok:
            missing = [w for w in workers if w not in self._ready]
            raise TimeoutError(f"workers {missing} not ready in time")

    def close(self) -> None:
        self._stop.set()
        # shutdown BEFORE close: closing the fd does not wake a thread
        # blocked in accept() — the in-flight syscall pins the kernel
        # socket, leaving the port in LISTEN with no owner (a restart
        # on the same port then fails EADDRINUSE until process exit)
        try:
            self._listener.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._listener.close()
        except OSError:
            pass
        # join the accept loop FIRST: it may have accepted a connection
        # just before the listener closed, and no reader must be
        # spawned after the sweep below (a missed one would survive its
        # join and die inside native recv at interpreter exit)
        if self._accept_thread is not threading.current_thread():
            self._accept_thread.join(timeout=10.0)
        # flush-before-close: writers drain their queues first, so a
        # goodbye/CONFIG enqueued before close() reaches the wire in
        # order; only then are the sockets torn down
        for writer in list(self._writer_of.values()):
            writer.close(flush=True)
        # every live connection, including ones that never sent HELLO
        for conn in list(self._send_lock):
            force_close(conn)        # wakes the blocked reader thread
        # shm channels whose reader cleanup has not run yet: close (and
        # unlink — this side owns the segments) so no serve thread spins
        # on an unlinked mapping and /dev/shm is left clean
        for chan in list(self._shm_of.values()):
            chan.close()
        for t in list(self._shm_threads):
            if t is not threading.current_thread():
                t.join(timeout=10.0)
        # join everything before returning: readers hand GRADIENTS into
        # the fabric (device arrays) and the heartbeat waits at most one
        # interval — a thread left alive at interpreter exit can die
        # inside native code and abort the process
        for t in (*self._reader_threads, self._hb_thread):
            if t is not None and t is not threading.current_thread():
                t.join(timeout=10.0)

    # -- internals ---------------------------------------------------------

    def _send(self, conn, topic, key, message=None) -> bool:
        """False (never raises) when the connection is gone: the message
        is dropped, like a Kafka send to a dead consumer — the reader's
        disconnect cleanup drives the actual eviction, so a send from
        inside the consistency gate can't crash the server."""
        if (message is not None
                and getattr(message, "encoded", None) is not None
                and self._codec_of.get(conn,
                                       CODEC_SPEC_NONE).codec_id
                == CODEC_NONE):
            # this peer negotiated no compression (old version, or
            # `--compress none`): ship the decoded values as a plain f32
            # frame — they ARE the values every compressed peer decodes
            # to, so a mixed fleet stays consistent
            message = dataclasses.replace(message, encoded=None)
        payload = serde.to_bytes(message) if message is not None else b""
        if topic == T_WEIGHTS and self._trace_of.get(conn):
            # open the weights flow: arrow from this send slice to the
            # worker's matching net.recv (run_reader strips the suffix)
            fid = self._tracer.new_flow_id()
            with self._tracer.span("net.send", topic="weights", worker=key):
                self._tracer.flow_start("weights.wire", fid, worker=key)
            payload += _TRACE_CTX.pack(fid, 0)
        return self._send_raw(conn, topic, key, payload)

    def _send_raw(self, conn, topic, key, payload: bytes) -> bool:
        # `dropped_sends` is a data-loss diagnostic: a control frame
        # (PING/CONFIG) hitting a dying connection is not lost training
        # data, and neither is a prediction reply to a vanished client
        count = topic not in (T_PING, T_CONFIG, T_PREDICTION)
        writer = self._writer_of.get(conn)
        if writer is not None:
            # coalesced path: enqueue and return — the writer thread
            # ships batches in scatter-gather syscalls.  Wire-byte /
            # telemetry accounting happens HERE at enqueue time, so an
            # arm with coalescing on is number-for-number comparable to
            # one with it off (tests/test_net_framing.py).  PINGs are advisory:
            # regenerated next interval, so a full queue drops them
            # (typed counter) instead of blocking the heartbeat thread.
            if not writer.send(topic, key, payload,
                               advisory=topic == T_PING):
                self.dropped_sends += count
                if writer.dead:
                    force_close(conn)   # reader wakes -> cleanup/eviction
                return False
        else:
            lock = self._send_lock.get(conn)
            if lock is None:
                self.dropped_sends += count
                return False
            try:
                locked_send(conn, lock, topic, key, payload)
            except (ConnectionError, OSError):
                self.dropped_sends += count
                force_close(conn)   # wake the reader -> cleanup/eviction
                return False
        with self._wire_lock:
            self.wire_bytes[topic] = (self.wire_bytes.get(topic, 0)
                                      + _FRAME.size + len(payload))
        if self._telemetry.enabled:
            frames, nbytes = self._m_sent[topic]
            frames.inc()
            nbytes.inc(_FRAME.size + len(payload))
        if FLIGHT.enabled and topic in (T_WEIGHTS, T_GRADIENTS):
            # only the data-plane topics: a PING every few seconds
            # would evict the interesting events from a quiet ring
            FLIGHT.record("net.send", topic=TOPIC_NAMES[topic],
                          peer=key, bytes=len(payload))
        return True

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _addr = self._listener.accept()
            except OSError:
                return
            if self._stop.is_set():
                # raced close(): the listener accepted this connection
                # before it was torn down — it must not outlive close()
                force_close(conn)
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._cv:
                # per-connection registries are written under the state
                # lock; the heartbeat/send threads iterate them
                self._send_lock[conn] = OrderedLock("ServerBridge.send")
                if self._coalesce:
                    self._writer_of[conn] = FrameWriter(
                        conn, telemetry=self._telemetry)
                self._last_recv[conn] = time.monotonic()
            t = threading.Thread(target=self._reader, args=(conn,),
                                 daemon=True, name="kps-net-reader")
            t.start()
            # prune finished readers so worker churn over a long
            # rebalance run doesn't accumulate dead Thread objects
            with self._cv:
                self._reader_threads = [r for r in self._reader_threads
                                        if r.is_alive()] + [t]

    def _heartbeat_loop(self) -> None:
        while not self._stop.wait(self._hb_interval):
            now = time.monotonic()
            for conn in list(self._send_lock):
                silent = now - self._last_recv.get(conn, now)
                if (self._hb_timeout is not None
                        and silent > self._hb_timeout):
                    # half-open: no FIN will ever come; force the
                    # reader's recv to fail so cleanup runs
                    force_close(conn)
                    continue
                self._send(conn, T_PING, 0)

    def _reader(self, conn: socket.socket) -> None:
        # buffered receive (wire.RecvBuffer): one recv_into brings in
        # every frame the kernel has ready; payloads stay zero-copy
        # views into the buffer
        rbuf = RecvBuffer(conn)
        try:
            while not self._stop.is_set():
                frame = rbuf.recv_frame()
                if frame is None:
                    break
                self._last_recv[conn] = time.monotonic()
                topic, key, payload = frame
                with self._wire_lock:
                    self.wire_bytes[topic] = (
                        self.wire_bytes.get(topic, 0)
                        + _FRAME.size + len(payload))
                if self._telemetry.enabled:
                    frames, nbytes = self._m_recv[topic]
                    frames.inc()
                    nbytes.inc(_FRAME.size + len(payload))
                if topic == T_HELLO:
                    (n,) = struct.unpack_from("<q", payload, 0)
                    ids = struct.unpack_from(f"<{n}q", payload, 8)
                    # negotiation: use our codec iff the peer asked for
                    # the SAME one (old peers send no trailer -> NONE)
                    peer = _read_codec_trailer(payload, 8 + 8 * n)
                    negotiated = (self.codec if peer == self.codec
                                  else CODEC_SPEC_NONE)
                    # trace negotiation: ON iff the peer offered AND our
                    # tracer is on (old peers send no flag -> off)
                    trace_on = (_read_trace_flag(
                        payload, 8 + 8 * n + _CODEC_TRAILER.size)
                        and self._tracer.enabled)
                    with self._cv:
                        # negotiation results land under the state lock
                        # BEFORE T_CONFIG goes out: once the peer sees
                        # CONFIG it may talk coded frames, and the send
                        # paths read these dicts from other threads
                        self._codec_of[conn] = negotiated
                        self._trace_of[conn] = trace_on
                    # shm negotiation: the offer rides CONFIG only when
                    # the peer asked — worker handshakes stay
                    # byte-identical to every earlier version
                    if _read_agg_flag(payload, 8 + 8 * n
                                      + _CODEC_TRAILER.size
                                      + _TRACE_TRAILER.size
                                      + _SHM_TRAILER.size):
                        self._agg_conns.add(conn)
                    shm_tail = b""
                    if _read_shm_flag(payload, 8 + 8 * n
                                      + _CODEC_TRAILER.size
                                      + _TRACE_TRAILER.size):
                        chan = self._offer_shm(conn)
                        shm_tail = (_SHM_OFFER.pack(0, b"", b"")
                                    if chan is None else
                                    _SHM_OFFER.pack(
                                        1, chan.nonce,
                                        # pscheck: disable=PS103 (segment name is a fresh control string, not message parts)
                                        chan.name.encode("ascii")))
                    # T_CONFIG goes out BEFORE the ids are registered:
                    # once registered, the producer thread may race data
                    # rows onto this connection, and the worker-side
                    # handshake relies on T_CONFIG being the first
                    # non-PING frame (per-connection FIFO).  Payload:
                    # PING cadence (0.0 = no heartbeats; the worker must
                    # not time out at all) + the run id + the negotiated
                    # codec + the trace answer (old workers unpack_from
                    # past both trailers).
                    self._send_raw(conn, T_CONFIG, 0,
                                   struct.pack("<dq",
                                               self._hb_interval or 0.0,
                                               self.run_id)
                                   + _CODEC_TRAILER.pack(
                                       negotiated.codec_id,
                                       negotiated.param)
                                   + _TRACE_TRAILER.pack(int(trace_on))
                                   + shm_tail)
                    with self._cv:
                        for w in ids:
                            self._conn_of[w] = conn
                        self._cv.notify_all()
                    if FLIGHT.enabled:
                        FLIGHT.record("net.hello", workers=list(ids))
                    if self.on_hello is not None:
                        self.on_hello(list(ids))
                elif topic == T_READY:
                    with self._cv:
                        self._ready.add(key)
                        self._cv.notify_all()
                    if self.on_ready is not None:
                        self.on_ready(key)
                elif topic == T_PONG:
                    pass            # liveness already stamped above
                elif topic == T_GRADIENTS and self._fabric is not None:
                    fid = None
                    if self._trace_of.get(conn):
                        # strip the trace suffix BEFORE decoding —
                        # compressed frames hand their whole tail to
                        # unpack_parts, which must not see it
                        (fid, _parent) = _TRACE_CTX.unpack_from(
                            payload, len(payload) - _TRACE_CTX.size)
                        payload = payload[:len(payload) - _TRACE_CTX.size]
                    msg = serde.from_bytes(payload)
                    if FLIGHT.enabled:
                        FLIGHT.record(
                            "net.recv", topic="gradients",
                            worker=getattr(msg, "worker_id", key),
                            clock=getattr(msg, "vector_clock", -1))
                    if fid is not None:
                        with self._tracer.span("net.recv",
                                               topic="gradients"):
                            self._tracer.flow_step("delta.wire", fid)
                        # frozen dataclass: tests construct messages
                        # positionally, so the context rides as a
                        # dynamic attribute, not a schema field
                        object.__setattr__(msg, "trace", fid)
                    self._fabric.send(fabric_mod.GRADIENTS_TOPIC, 0, msg)
                elif topic == T_PREDICT:
                    self._handle_predict(conn, key, payload)
        except (ConnectionError, OSError):
            pass
        finally:
            self._cleanup_conn(conn)

    def _handle_predict(self, conn, key: int, payload: bytes) -> None:
        engine = self._serving
        if engine is None:
            # a predict frame on a training-only bridge: explicit
            # failure beats a silent hang on the client side
            self._send_raw(conn, T_PREDICTION, key,
                           encode_prediction(PREDICT_FAILED))
            return
        from kafka_ps_tpu.serving.policy import OverloadedError, ReadBound
        try:
            x, min_clock, max_age_s, model_id = \
                decode_predict_request(payload)
            bound = ReadBound(min_clock=min_clock, max_age_s=max_age_s)
        except Exception:  # noqa: BLE001 — malformed frame, not our crash
            self._send_raw(conn, T_PREDICTION, key,
                           encode_prediction(PREDICT_FAILED))
            return

        def reply(result, conn=conn, key=key):
            self._send_raw(conn, T_PREDICTION, key, _encode_result(result))

        try:
            engine.submit(x, bound, reply, model_id=model_id)
        except OverloadedError:
            # admission shed happens synchronously in submit — the fast
            # rejection the bounded queue exists for: the reader thread
            # answers immediately instead of parking work it cannot serve
            self._send_raw(conn, T_PREDICTION, key,
                           encode_prediction(PREDICT_OVERLOADED))
        except (ValueError, RuntimeError):
            # unknown model id, or engine already closed (shutdown race)
            self._send_raw(conn, T_PREDICTION, key,
                           encode_prediction(PREDICT_FAILED))

    def _offer_shm(self, conn):
        """Create a per-connection shm channel plus its serve thread;
        None (a declined offer, the client stays on sockets) when shm is
        disabled here, no serving engine is attached, or the segment
        cannot be created (e.g. /dev/shm exhausted)."""
        if not self._shm_enabled or self._serving is None:
            return None
        try:
            from kafka_ps_tpu.serving.shm import ShmChannel
            chan = ShmChannel.create()
        except Exception:  # noqa: BLE001 — degrade, never fail the HELLO
            return None
        t = threading.Thread(target=self._shm_serve, args=(chan,),
                             daemon=True, name="kps-shm-serve")
        with self._cv:
            self._shm_of[conn] = chan
            self._shm_threads.append(t)
        t.start()
        return chan

    def _shm_serve(self, chan) -> None:
        """Per-channel poll loop: pop the pending request, submit it to
        the engine async (same as the socket path — this thread never
        blocks on a batch window), publish the reply from the engine's
        callback.  Depth-1 protocol, so an unanswered seq backpressures
        exactly one client."""
        from kafka_ps_tpu.serving.policy import OverloadedError, ReadBound
        engine = self._serving
        while not self._stop.is_set() and not chan.closed:
            got = chan.serve_once()
            if got is None:
                time.sleep(0.0002)
                continue
            seq, raw = got
            try:
                x, min_clock, max_age_s, model_id = \
                    decode_predict_request(raw)
                bound = ReadBound(min_clock=min_clock, max_age_s=max_age_s)
            except Exception:  # noqa: BLE001 — malformed payload
                chan.respond(seq, encode_prediction(PREDICT_FAILED))
                continue

            def reply(result, seq=seq):
                chan.respond(seq, _encode_result(result))
                self._m_shm.inc()
                if FLIGHT.enabled:
                    FLIGHT.record("serving.batch", n=1, mode="shm")

            try:
                engine.submit(x, bound, reply, model_id=model_id)
            except OverloadedError:
                reply(OverloadedError("shed"))
            except (ValueError, RuntimeError) as err:
                reply(err)

    def _cleanup_conn(self, conn: socket.socket) -> None:
        """Purge a dead connection's registrations and surface the
        disconnect — without this the consistency gate waits forever for
        a dead worker's gradients (ADVICE r2 medium)."""
        try:
            conn.close()
        except OSError:
            pass
        writer = self._writer_of.pop(conn, None)
        if writer is not None:
            # the connection is dead — discard the queue, don't flush
            # (a writer mid-sendmsg fails on the closed fd and exits)
            writer.close(flush=False, timeout=2.0)
        with self._cv:
            ids = [w for w, c in self._conn_of.items() if c is conn]
            for w in ids:
                del self._conn_of[w]
                self._ready.discard(w)
            was_agg = conn in self._agg_conns
            self._agg_conns.discard(conn)
            self._send_lock.pop(conn, None)
            self._last_recv.pop(conn, None)
            self._codec_of.pop(conn, None)
            self._trace_of.pop(conn, None)
            chan = self._shm_of.pop(conn, None)
            self._cv.notify_all()
        if chan is not None:
            chan.close()    # wakes + ends the kps-shm-serve thread
        if FLIGHT.enabled and ids:
            FLIGHT.record("net.disconnect", workers=ids, agg=was_agg)
        if was_agg:
            # an aggregator relay died, not its member workers: the
            # members are alive behind it, buffering resends for the
            # restarted relay — evicting them would shrink the gate on
            # a transient.  Their registrations are purged above; a
            # re-HELLO from the restarted relay re-registers them.
            return
        if ids and not self._stop.is_set() and self.on_disconnect is not None:
            self.on_disconnect(ids)


class WorkerBridge:
    """Worker-process side: connects to the server, registers its
    logical worker ids, feeds received INPUT_DATA rows into the local
    buffers, delivers received WEIGHTS into the local fabric, and routes
    the workers' GRADIENTS sends back over the socket."""

    def __init__(self, host: str, port: int, worker_ids: list[int],
                 connect_timeout: float = 30.0,
                 heartbeat_timeout: float | None = None,
                 codec: CodecSpec | None = None,
                 tracer=None, telemetry=None,
                 aggregator: bool = False,
                 coalesce: bool = True):
        """`heartbeat_timeout`: seconds of total server silence before
        the connection is declared dead (only sensible when the server
        PINGs, i.e. it was built with a heartbeat_interval — otherwise a
        quiet-but-alive server would be misread as gone).
        `codec`: this worker process's `--compress` choice, offered on
        HELLO; `self.negotiated` holds what the server agreed to (NONE
        against an old or differently-configured server) — the caller
        builds its gradient compressors from THAT, not the flag.
        `tracer`: offering tracer — when it is on AND the server answers
        the offer, `self.trace_negotiated` goes True and WEIGHTS /
        GRADIENTS frames carry the 16-byte trace context.
        `aggregator`: HELLO as a per-host aggregation relay for
        `worker_ids` (the MEMBER workers behind it, docs/AGGREGATION
        .md): the server routes their weights/data through this
        connection, may group releases into T_WEIGHTS_AGG frames, and
        treats a disconnect as a relay restart instead of a member
        failure.
        `coalesce`: queue outgoing frames behind a wire.FrameWriter
        (scatter-gather batches from a dedicated writer thread,
        docs/WIRE.md); False is the classic locked-sendall-per-frame
        path (`--no-wire-coalesce`)."""
        self.worker_ids = list(worker_ids)
        self.aggregator = bool(aggregator)
        # relay hook (agg/relay.py): when set, run_reader hands raw
        # pass-through frames (data rows, per-worker weights, grouped
        # weights) to it BEFORE any decode; a True return consumes the
        # frame.  None keeps the classic worker-process dispatch.
        self.raw_forward = None
        self._heartbeat_timeout = heartbeat_timeout
        self.codec = codec if codec is not None else CODEC_SPEC_NONE
        self.negotiated = CODEC_SPEC_NONE
        self._tracer = tracer or NULL_TRACER
        self._telemetry = telemetry or NULL_TELEMETRY
        self.trace_negotiated = False
        self._m_sent, self._m_recv = _frame_counters(self._telemetry)
        self.wire_bytes: dict[int, int] = {}
        self._wire_lock = OrderedLock("WorkerBridge.wire")
        # retry: the server process may still be importing/binding when
        # this process is already up (both launched together, run.sh-style)
        deadline = time.monotonic() + connect_timeout
        while True:
            try:
                self._sock = socket.create_connection((host, port),
                                                      timeout=5.0)
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.2)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._send_lock = OrderedLock("WorkerBridge.send")
        self._stop = threading.Event()
        self.disconnected = threading.Event()
        # set by a mid-stream GOODBYE config: the run ended cleanly,
        # the EOF that follows is not a crash (read before
        # `disconnected` by the aggregated worker supervisor)
        # pscheck: disable=PS201 (monotonic bool set by the reader thread; pollers tolerate one stale read)
        self.run_over = False
        self.server_run_id: int | None = None
        payload = (struct.pack(f"<q{len(self.worker_ids)}q",
                               len(self.worker_ids), *self.worker_ids)
                   + _CODEC_TRAILER.pack(self.codec.codec_id,
                                         self.codec.param)
                   + _TRACE_TRAILER.pack(int(self._tracer.enabled)))
        if self.aggregator:
            # trailers are positional: the agg byte sits after the shm
            # slot, so an explicit not-requesting-shm byte fills it
            payload += _SHM_TRAILER.pack(0) + _AGG_TRAILER.pack(1)
        locked_send(self._sock, self._send_lock, T_HELLO, 0, payload)
        # synchronous handshake: the server replies T_CONFIG before it
        # registers our ids (net.ServerBridge._reader), so it is the
        # first non-PING frame on the wire — read it HERE, before any
        # reader thread exists, so callers know the server's run id and
        # ping cadence before deciding what local state to restore
        self._sock.settimeout(10.0)
        try:
            while True:
                frame = recv_frame(self._sock)
                if frame is None:
                    raise ConnectionError("server closed during handshake")
                topic, _key, pl = frame
                if topic == T_PING:
                    locked_send(self._sock, self._send_lock, T_PONG, 0)
                    continue
                if topic == T_CONFIG:
                    interval, run_id = struct.unpack_from("<dq", pl, 0)
                    self.server_run_id = int(run_id)
                    # a 16-byte CONFIG is an old server: no negotiation,
                    # stay uncompressed (the server can't decode tid 4/5)
                    self.negotiated = _read_codec_trailer(pl, 16)
                    # trace answer sits after the codec trailer; an old
                    # server never sends it -> tracing stays off-wire
                    self.trace_negotiated = _read_trace_flag(
                        pl, 16 + _CODEC_TRAILER.size)
                    break
                raise ConnectionError(
                    f"expected T_CONFIG during handshake, got topic {topic}")
        except socket.timeout as e:
            raise ConnectionError("no T_CONFIG from server") from e
        # steady state: the configured read timeout (a half-open server
        # link then surfaces as socket.timeout in the read loop —
        # TimeoutError is an OSError, same exit path as a reset), or
        # blocking forever when no timeout was requested; the advertised
        # cadence may floor or disable it
        self._sock.settimeout(heartbeat_timeout)
        self._apply_server_ping_interval(interval)
        # the coalescing writer starts AFTER the synchronous handshake:
        # HELLO went out on the locked path above, and nothing else can
        # have been enqueued yet, so per-connection frame order is
        # preserved across the switch
        self._writer = (FrameWriter(self._sock,
                                    telemetry=self._telemetry)
                        if coalesce else None)

    def _enqueue(self, topic: int, key: int, payload: bytes = b"",
                 advisory: bool = False) -> None:
        """Send one frame via the coalescing writer when enabled, the
        locked direct path otherwise.  A failed protocol enqueue (dead
        writer, or the backpressure deadline expired) raises
        ConnectionError — the exact failure surface locked_send has —
        so caller semantics are identical on both paths."""
        if self._writer is not None:
            if not self._writer.send(topic, key, payload,
                                     advisory=advisory) and not advisory:
                raise ConnectionError("wire writer closed")
            return
        locked_send(self._sock, self._send_lock, topic, key, payload)

    def send_gradients(self, key: int, message) -> None:
        """Serialize one gradient message (full-range, or a per-shard
        dense/sparse slice — serde handles both) and send it on THIS
        bridge's socket.  The make_fabric() path calls it for the
        single-connection deployment; a sharded worker process calls it
        directly as the ShardRouter's per-shard send hook, one bridge
        per shard (runtime/sharding.py, docs/SHARDING.md)."""
        payload = serde.to_bytes(message)
        if self.trace_negotiated:
            # open the delta flow: this send slice is the wire
            # segment's source; the server's net.recv is the first
            # step of the arrow chain.  Each shard slice gets its OWN
            # flow id — one Perfetto arrow chain per routed slice.
            fid = self._tracer.new_flow_id()
            with self._tracer.span(
                    "net.send", topic="gradients",
                    worker=getattr(message, "worker_id", key)):
                self._tracer.flow_start("delta.wire", fid)
            payload += _TRACE_CTX.pack(fid, 0)
        self._enqueue(T_GRADIENTS, key, payload)
        with self._wire_lock:
            self.wire_bytes[T_GRADIENTS] = (
                self.wire_bytes.get(T_GRADIENTS, 0)
                + _FRAME.size + len(payload))
        if self._telemetry.enabled:
            frames, nbytes = self._m_sent[T_GRADIENTS]
            frames.inc()
            nbytes.inc(_FRAME.size + len(payload))
        if FLIGHT.enabled:
            FLIGHT.record("net.send", topic="gradients",
                          worker=getattr(message, "worker_id", key),
                          clock=getattr(message, "vector_clock", -1),
                          bytes=len(payload))

    def send_payload(self, key: int, payload: bytes) -> None:
        """Ship one PRE-serialized gradient-topic frame — the relay's
        composite send (agg/relay.py), which serializes the composite
        exactly once for both the wire-bytes accounting and the send.
        The trace suffix is mandatory when negotiated: the server's
        reader strips 16 bytes from every T_GRADIENTS frame on a
        trace-negotiated connection, composite or not."""
        if self.trace_negotiated:
            fid = self._tracer.new_flow_id()
            with self._tracer.span("net.send", topic="gradients",
                                   worker=key):
                self._tracer.flow_start("delta.wire", fid)
            payload += _TRACE_CTX.pack(fid, 0)
        self._enqueue(T_GRADIENTS, key, payload)
        with self._wire_lock:
            self.wire_bytes[T_GRADIENTS] = (
                self.wire_bytes.get(T_GRADIENTS, 0)
                + _FRAME.size + len(payload))
        if self._telemetry.enabled:
            frames, nbytes = self._m_sent[T_GRADIENTS]
            frames.inc()
            nbytes.inc(_FRAME.size + len(payload))
        if FLIGHT.enabled:
            FLIGHT.record("net.send", topic="gradients", worker=key,
                          bytes=len(payload))

    def make_fabric(self) -> fabric_mod.Fabric:
        """Local fabric whose GRADIENTS sends cross the socket (the
        worker's view of the broker)."""
        bridge = self

        class BridgedFabric(fabric_mod.Fabric):
            def send(self, topic, key, message):
                if topic == fabric_mod.GRADIENTS_TOPIC:
                    bridge.send_gradients(key, message)
                else:
                    super().send(topic, key, message)

        # pscheck: disable=PS201 (make_fabric publishes before run_reader starts - the handshake orders it)
        self.fabric = BridgedFabric()
        return self.fabric

    def set_weights_sink(self, sink) -> None:
        """Deliver received WEIGHTS frames into `sink.send(topic, key,
        msg)` instead of a make_fabric() fabric.  A sharded worker
        process plugs a per-shard collector here so each bridge's
        weights SLICES feed runtime/sharding.WeightsAssembler.offer
        and only the reassembled full-range message reaches the
        workers' local fabric (docs/SHARDING.md)."""
        self.fabric = sink

    def _apply_server_ping_interval(self, interval: float) -> None:
        """React to the server's advertised PING cadence (T_CONFIG,
        consumed in the constructor handshake right after HELLO).  The
        worker's `heartbeat_timeout` and the
        server's ping interval are independent flags in different
        processes; a timeout below a few pings false-declares a healthy
        server dead and kills the whole worker process (ADVICE r3) — so
        the effective read timeout is floored at 3 pings, and disabled
        entirely when the server does not ping at all."""
        if self._heartbeat_timeout is None:
            return
        if interval <= 0.0:
            print(f"warning: server sends no heartbeats; ignoring "
                  f"heartbeat_timeout={self._heartbeat_timeout}s",
                  file=sys.stderr, flush=True)
            self._sock.settimeout(None)
            return
        floor = 3.0 * interval
        effective = self._heartbeat_timeout
        if effective < floor:
            print(f"warning: heartbeat_timeout={effective}s is under 3x "
                  f"the server ping interval ({interval}s); using "
                  f"{floor}s", file=sys.stderr, flush=True)
            effective = floor
        self._sock.settimeout(effective)

    def mark_ready(self, worker: int) -> None:
        self._enqueue(T_READY, worker)

    def run_reader(self, buffers: dict[int, object]) -> None:
        """Blocking read loop (call on a dedicated thread or the main
        thread): dispatches INPUT_DATA to `buffers[worker].add` (batched
        frames to `.add_many`) and WEIGHTS into the local fabric.
        Returns on EOF (server done)."""
        rbuf = RecvBuffer(self._sock)
        try:
            while not self._stop.is_set():
                frame = rbuf.recv_frame()
                if frame is None:
                    break
                topic, key, payload = frame
                with self._wire_lock:
                    self.wire_bytes[topic] = (
                        self.wire_bytes.get(topic, 0)
                        + _FRAME.size + len(payload))
                if self._telemetry.enabled:
                    frames, nbytes = self._m_recv[topic]
                    frames.inc()
                    nbytes.inc(_FRAME.size + len(payload))
                if topic == T_PING:
                    # a PONG is liveness, regenerated on the next PING:
                    # advisory — never blocks the reader on backpressure
                    self._enqueue(T_PONG, 0, advisory=True)
                    continue
                if topic == T_CONFIG:
                    # normally consumed by the constructor handshake;
                    # tolerate a re-sent config mid-stream (same <dq>
                    # decode — run id changes are not acted on, except
                    # the GOODBYE sentinel announcing a clean end-of-run
                    (interval, rid) = struct.unpack_from("<dq", payload, 0)
                    if rid == GOODBYE_RUN_ID:
                        self.run_over = True
                        continue
                    self._apply_server_ping_interval(interval)
                    continue
                fid = None
                if topic == T_WEIGHTS and self.trace_negotiated:
                    (fid, _parent) = _TRACE_CTX.unpack_from(
                        payload, len(payload) - _TRACE_CTX.size)
                    payload = payload[:len(payload) - _TRACE_CTX.size]
                if (self.raw_forward is not None
                        and topic in (T_DATA, T_DATA_BATCH,
                                      T_WEIGHTS, T_WEIGHTS_AGG)):
                    # aggregator relay: pass-through frames forward as
                    # raw bytes (no decode — a relay needs no jax, and
                    # the members receive bit-identical payloads).  The
                    # trace suffix stripped above belongs to the
                    # server→relay hop; forward_frame opens a fresh
                    # flow per member on the downstream re-broadcast.
                    if self.raw_forward(topic, key, bytes(payload)):
                        if fid is not None:
                            with self._tracer.span("net.recv",
                                                   topic="weights",
                                                   worker=key):
                                self._tracer.flow_end("weights.wire",
                                                      fid)
                        continue
                if topic == T_DATA_BATCH:
                    (nrows,) = struct.unpack_from("<q", payload, 0)
                    if nrows < 0:
                        # columnar layout (serde.encode_labeled_rows):
                        # packed ndarray columns, one decode per BATCH
                        buffers[key].add_many(
                            serde.decode_labeled_rows(payload))
                        continue
                    # legacy per-row layout from an older server
                    off = 8
                    rows = []
                    for _ in range(nrows):
                        # pscheck: disable=PS204 (legacy framing: old servers length-prefixed each row with an i32; the current encoder is columnar and never packs this)
                        (blen,) = struct.unpack_from("<i", payload, off)
                        off += 4
                        row = serde.from_bytes(payload[off:off + blen])
                        off += blen
                        rows.append((row.features, row.label))
                    buffers[key].add_many(rows)
                    continue
                msg = serde.from_bytes(payload)
                if topic == T_DATA:
                    buffers[key].add(msg.features, msg.label)
                elif topic == T_WEIGHTS:
                    if FLIGHT.enabled:
                        FLIGHT.record(
                            "net.weights_recv", worker=key,
                            clock=getattr(msg, "vector_clock", -1))
                    if fid is not None:
                        # close the weights flow on the receiving slice
                        with self._tracer.span("net.recv",
                                               topic="weights",
                                               worker=key):
                            self._tracer.flow_end("weights.wire", fid)
                        object.__setattr__(msg, "trace", fid)
                    self.fabric.send(fabric_mod.WEIGHTS_TOPIC, key, msg)
        except (ConnectionError, OSError):
            pass
        finally:
            self.disconnected.set()

    def close(self) -> None:
        self._stop.set()
        if self._writer is not None:
            # flush-before-close: queued frames (a final gradient, a
            # READY) reach the wire before the socket goes down
            self._writer.close(flush=True)
        try:
            self._sock.close()
        except OSError:
            pass


class PredictClient:
    """Remote prediction client for the serving plane (docs/SERVING.md).

    NOT a worker: it sends no HELLO, registers no worker ids, and so
    never receives weights or data frames — the connection carries only
    PREDICT/PREDICTION (plus the server's PINGs, answered here to stay
    alive under heartbeat-timeout enforcement).  Synchronous: one
    outstanding request per client; run several clients for concurrency.

    `reconnect=True` survives a dropped server connection the way the
    split deployment's worker processes do (cli/socket_mode supervise):
    on ConnectionError the client re-dials with exponential backoff up
    to `reconnect_timeout` seconds and replays the in-flight request on
    the fresh connection.  An OVERLOADED/STALE reply is a healthy
    connection — those never trigger a re-dial.
    """

    def __init__(self, host: str, port: int, timeout: float = 30.0, *,
                 reconnect: bool = False, reconnect_timeout: float = 10.0,
                 model_id: int = 0, shm: bool = False):
        self._host, self._port = host, port
        self._timeout = timeout
        self._reconnect = reconnect
        self._reconnect_timeout = reconnect_timeout
        self._model_id = int(model_id)
        self._send_lock = OrderedLock("PredictClient.send")
        self._req = 0
        self._closed = False
        self.reconnects = 0          # successful re-dials (ops/test surface)
        self._shm = bool(shm)
        self._chan = None            # ShmChannel once negotiated
        self._sock = self._dial()
        if self._shm:
            self._chan = self._negotiate_shm()

    def _dial(self) -> socket.socket:
        sock = socket.create_connection((self._host, self._port),
                                        timeout=5.0)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        sock.settimeout(self._timeout)
        return sock

    def _negotiate_shm(self):
        """Ask the server for a shared-memory channel: an empty-ids
        HELLO carrying the shm request trailer, answered by a CONFIG
        whose offer names the segment (docs/SERVING.md, "Dispatch
        economics").  ANY failure — legacy server (no offer bytes),
        declined offer, remote peer (the segment name does not exist on
        this host), nonce mismatch — returns None and the client stays
        on the socket it already holds.  Registering zero worker ids
        keeps this connection invisible to the weights/data routing,
        exactly like a plain predict-only connection."""
        try:
            locked_send(self._sock, self._send_lock, T_HELLO, 0,
                        struct.pack("<q", 0)
                        + _CODEC_TRAILER.pack(CODEC_SPEC_NONE.codec_id,
                                              CODEC_SPEC_NONE.param)
                        + _TRACE_TRAILER.pack(0)
                        + _SHM_TRAILER.pack(1))
            while True:
                frame = recv_frame(self._sock)
                if frame is None:
                    return None
                topic, _key, payload = frame
                if topic == T_PING:
                    locked_send(self._sock, self._send_lock, T_PONG, 0)
                    continue
                if topic != T_CONFIG:
                    continue
                offer = _read_shm_offer(
                    payload,
                    16 + _CODEC_TRAILER.size + _TRACE_TRAILER.size)
                if offer is None:
                    return None
                name, nonce = offer
                from kafka_ps_tpu.serving.shm import ShmChannel
                return ShmChannel.attach(name, nonce)
        except Exception:  # noqa: BLE001 — every failure means sockets
            return None

    def _drop_chan(self) -> None:
        chan, self._chan = self._chan, None
        if chan is not None:
            try:
                chan.close()
            except Exception:  # noqa: BLE001 — already torn down
                pass

    def _redial(self) -> None:
        """Replace the dead socket, backing off exponentially (0.05 s
        doubling to 1 s) until `reconnect_timeout` is spent."""
        try:
            force_close(self._sock)
        except OSError:
            pass
        deadline = time.monotonic() + self._reconnect_timeout
        backoff = 0.05
        while not self._closed:
            try:
                self._sock = self._dial()
                self.reconnects += 1
                if self._shm:
                    # the old segment died with the old server process;
                    # negotiate a fresh channel (or fall back) before
                    # the replayed request goes out
                    self._drop_chan()
                    self._chan = self._negotiate_shm()
                return
            except OSError as err:
                if time.monotonic() + backoff > deadline:
                    raise ConnectionError(
                        f"serving endpoint {self._host}:{self._port} did "
                        f"not come back within {self._reconnect_timeout}s"
                    ) from err
                time.sleep(backoff)
                backoff = min(backoff * 2, 1.0)
        raise ConnectionError("client closed during reconnect")

    def predict(self, x, min_clock: int | None = None,
                max_age_s: float | None = None,
                model_id: int | None = None):
        """(label, confidence, vector_clock, wall_time) namedtuple;
        raises serving.policy.StalenessError when the bound rejects and
        serving.policy.OverloadedError when the server shed the request
        (admission queue full — back off and retry)."""
        self._req += 1
        payload = encode_predict_request(
            x, min_clock, max_age_s,
            self._model_id if model_id is None else model_id)
        chan = self._chan
        if chan is not None:
            try:
                raw = chan.rpc(bytes(payload), timeout=self._timeout)
            except Exception:  # noqa: BLE001 — transport died mid-flight:
                # drop the channel and fall through to the socket below
                # (transparent degradation; OVERLOADED/STALE are healthy
                # REPLIES and raise from _decode_reply, not here)
                self._drop_chan()
            else:
                return self._decode_reply(raw, min_clock, max_age_s)
        while True:
            try:
                locked_send(self._sock, self._send_lock, T_PREDICT,
                            self._req, payload)
                return self._await_reply(min_clock, max_age_s)
            except (ConnectionError, OSError):
                if not self._reconnect or self._closed:
                    raise
                # fresh socket, no stale frames: replaying the same
                # request id is unambiguous (prediction is idempotent)
                self._redial()

    def _await_reply(self, min_clock, max_age_s):
        while True:
            frame = recv_frame(self._sock)
            if frame is None:
                raise ConnectionError(
                    "server closed before the prediction arrived")
            topic, key, payload = frame
            if topic == T_PING:
                locked_send(self._sock, self._send_lock, T_PONG, 0)
                continue
            if topic != T_PREDICTION or key != self._req:
                continue            # stray control frame (e.g. CONFIG)
            return self._decode_reply(payload, min_clock, max_age_s)

    def _decode_reply(self, payload, min_clock, max_age_s):
        """One PREDICTION payload (socket frame or shm response buffer)
        to the caller's result: Prediction, or the typed error."""
        status, label, conf, clock, wall = decode_prediction(payload)
        if status == PREDICT_STALE:
            from kafka_ps_tpu.serving.policy import StalenessError
            raise StalenessError(
                f"server rejected the read bound (min_clock="
                f"{min_clock}, max_age_s={max_age_s})",
                min_clock=min_clock, max_age_s=max_age_s)
        if status == PREDICT_OVERLOADED:
            from kafka_ps_tpu.serving.policy import OverloadedError
            raise OverloadedError(
                "server shed the request (admission queue full)")
        if status != PREDICT_OK:
            raise RuntimeError("prediction failed on the server")
        from kafka_ps_tpu.serving.engine import Prediction
        return Prediction(label, conf, clock, wall)

    @property
    def shm_active(self) -> bool:
        """True while predict() rides the shared-memory channel
        (ops/test surface — flips False on fallback)."""
        return self._chan is not None

    def close(self) -> None:
        self._closed = True
        self._drop_chan()
        force_close(self._sock)

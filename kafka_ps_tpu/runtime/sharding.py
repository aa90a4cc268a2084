"""Range-sharded multi-server runtime (docs/SHARDING.md).

The reference carries a KeyRange on every message but always runs ONE
server over the full range — the latent hook for partitioned parameters
(messages/KeyRange.java; Li et al., OSDI'14 §4.3 key-range server
groups).  This module promotes the single-process shard_map prototype
(parallel/range_sharded.py) into a real runtime:

  * `ShardPlan` — N contiguous, disjoint key ranges covering the flat
    parameter vector exactly (the LAST shard is clipped, so unlike the
    shard_map prototype no pad keys ever exist on the wire);
  * `ShardRouter` — worker-side delta splitter: one outgoing gradient
    becomes N slice messages, each pushed to the owning shard.  Dense
    deltas split into dense slices; topk-compressed deltas split into
    `SparseDeltaMessage`s routed by index range, so a sparse delta
    touches few shards (empty slices are still sent — every shard's
    consistency gate needs one message per (worker, clock));
  * `WeightsAssembler` — worker-side reassembly: per-shard weights
    slices at a common clock synthesize ONE full-range WeightsMessage.
    Slices at clocks the worker already trained on are redelivery
    (shard crash recovery) — the router resends its cached gradient
    slice to just that shard instead of re-running the step, which is
    what keeps per-shard durable-log recovery bitwise;
  * `ShardedServerGroup` — N ServerNodes, each owning one range slice
    of theta with its own per-worker vector clocks and its own gate
    (all three consistency models evaluate per shard).  N=1 constructs
    today's single full-range server through the SAME code path —
    bitwise-identical theta and CSV logs by construction.

Replay-critical determinism: the split/assemble order is fixed by
(shard id, worker id, clock) alone — pscheck enforces PS104 on this
module (no wall-clock, no RNG, no set iteration in the routing paths).
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable

import numpy as np

from kafka_ps_tpu.compress.wire import CODEC_TOPK
from kafka_ps_tpu.runtime import fabric as fabric_mod
from kafka_ps_tpu.runtime.messages import (GradientMessage, KeyRange,
                                           SparseDeltaMessage,
                                           WeightsMessage)
from kafka_ps_tpu.runtime.server import ServerNode
from kafka_ps_tpu.telemetry.flight import FLIGHT


class ShardPlan:
    """Static assignment of the flat key space [0, num_params) to
    `num_shards` contiguous half-open ranges.

    span = ceil(num_params / num_shards); shard i owns
    [i*span, min((i+1)*span, num_params)).  Every key has exactly one
    owner (`shard_of`), the ranges concatenate back to the full vector
    in shard-id order, and the last shard is CLIPPED — the runtime has
    no pad region (contrast parallel/range_sharded.py, whose shard_map
    prototype pads; see its pad-hygiene asserts)."""

    def __init__(self, num_params: int, num_shards: int):
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        if num_shards > num_params:
            raise ValueError(
                f"num_shards {num_shards} > num_params {num_params}")
        self.num_params = num_params
        self.num_shards = num_shards
        self.span = -(-num_params // num_shards)          # ceil division
        self.ranges: tuple[KeyRange, ...] = tuple(
            KeyRange(i * self.span, min((i + 1) * self.span, num_params))
            for i in range(num_shards))

    def shard_of(self, key: int) -> int:
        if not 0 <= key < self.num_params:
            raise ValueError(f"key {key} outside [0, {self.num_params})")
        return key // self.span

    def split_dense(self, msg: GradientMessage) -> list[GradientMessage]:
        """One dense slice per shard (full-range input only).  Slice i
        carries the owning shard's KeyRange and the matching contiguous
        values view; clock/worker/trace ride along unchanged."""
        values = np.asarray(msg.values)
        out = []
        for rng in self.ranges:
            s = GradientMessage(vector_clock=msg.vector_clock,
                                key_range=rng,
                                values=values[rng.start:rng.end],
                                worker_id=msg.worker_id)
            _copy_trace(msg, s)
            out.append(s)
        return out

    def split_sparse(self, msg: GradientMessage) -> list[SparseDeltaMessage]:
        """Route a topk-encoded delta by index range: shard i receives
        only the (index, value) pairs that land in its range, as LOCAL
        offsets.  Shards outside the survivor set get an EMPTY slice —
        their gate still needs the (worker, clock) message, but the
        apply is skipped (the work-reduction that makes sharded topk
        scale on one host; seen in a builder's run on the CPU dev host
        at PR 8, block `sharding_ab` of the deleted
        `git show 3337831:bench.py`, never measured on the chip)."""
        idx, vals = msg.encoded.parts
        idx = np.asarray(idx, dtype=np.int32)
        vals = np.asarray(vals, dtype=np.float32)
        order = np.argsort(idx, kind="stable")      # canonical wire form
        idx, vals = idx[order], vals[order]
        # one pass: searchsorted against the shard boundaries
        bounds = [r.start for r in self.ranges] + [self.num_params]
        cuts = np.searchsorted(idx, bounds)
        out = []
        for i, rng in enumerate(self.ranges):
            lo, hi = cuts[i], cuts[i + 1]
            s = SparseDeltaMessage(
                vector_clock=msg.vector_clock, key_range=rng,
                indices=idx[lo:hi] - rng.start, values=vals[lo:hi],
                worker_id=msg.worker_id)
            _copy_trace(msg, s)
            out.append(s)
        return out


def _copy_trace(src, dst) -> None:
    """Thread the delta.wire flow id onto a routed slice: each slice
    keeps the parent delta's trace context, so Perfetto renders one
    arrow chain per delta slice (send → wire → shard apply)."""
    fid = getattr(src, "trace", None)
    if fid is not None:
        object.__setattr__(dst, "trace", fid)


class ShardRouter:
    """Worker-side delta splitter + redelivery cache (one per worker).

    `send(shard_id, slice_msg)` is the transport: in-process it
    enqueues to (GRADIENTS_TOPIC, shard_id) on the shared fabric;
    socket mode sends on the shard's bridge.  The cache keeps the last
    `cache_clocks` clocks' slices so a recovering shard that redelivers
    an old weights slice gets the BITWISE-identical gradient slice
    resent (never recomputed — recomputation after the buffer moved on
    would diverge the shards)."""

    def __init__(self, plan: ShardPlan,
                 send: Callable[[int, object], None],
                 cache_clocks: int = 64):
        self.plan = plan
        self._send = send
        self._cache: OrderedDict[int, list] = OrderedDict()
        self._cache_clocks = cache_clocks

    def route(self, msg: GradientMessage) -> None:
        r = msg.key_range
        if r.start != 0 or r.end != self.plan.num_params:
            raise ValueError(
                f"router expects full-range deltas, got [{r.start}, {r.end})")
        enc = getattr(msg, "encoded", None)
        if enc is not None and enc.codec_id == CODEC_TOPK:
            slices = self.plan.split_sparse(msg)
        else:
            slices = self.plan.split_dense(msg)
        self._cache[msg.vector_clock] = slices
        while len(self._cache) > self._cache_clocks:
            self._cache.popitem(last=False)
        for shard_id, s in enumerate(slices):
            self._send(shard_id, s)

    def resend(self, shard_id: int, clock: int) -> bool:
        """Redeliver every cached slice for `shard_id` at clocks
        >= `clock` (ascending); True when anything was resent.  A
        recovering shard that redelivers weights at clock c is behind
        by every delta slice from c onward — resending the whole
        cached tail lets it catch up to the surviving shards in one
        pass, and its (worker, clock) duplicate filter drops whatever
        originally got through, so resending is always safe."""
        sent = False
        count = 0
        for c in sorted(self._cache):
            if c >= clock:
                self._send(shard_id, self._cache[c][shard_id])
                sent = True
                count += 1
        if FLIGHT.enabled:
            # host ints only, and the recorder stamps time internally —
            # the routing path itself stays wall-clock-free (PS104)
            FLIGHT.record("router.resend", shard=shard_id,
                          from_clock=clock, count=count)
        return sent


class WeightsAssembler:
    """Worker-side reassembly of per-shard weights slices.

    A worker's weight pull completes when every shard has released its
    slice at a COMMON clock; the assembled full-range WeightsMessage is
    then delivered exactly once per clock (deliver callback).  Stale
    slices (clock <= last delivered) are shard-recovery redelivery:
    `resend(shard, worker, clock)` asks the worker's router to repush
    its cached gradient slice so the lagging shard catches up."""

    def __init__(self, plan: ShardPlan,
                 deliver: Callable[[int, WeightsMessage], None],
                 resend: Callable[[int, int, int], bool] | None = None):
        self.plan = plan
        self._deliver = deliver
        self._resend = resend
        self._slices: dict[int, dict[int, WeightsMessage]] = {}
        self._delivered: dict[int, int] = {}

    def offer(self, shard_id: int, worker: int,
              msg: WeightsMessage) -> bool:
        """Feed one shard's slice; returns True when this completed an
        assembly and the full message was delivered."""
        if FLIGHT.enabled:
            # the per-shard weights ack trail postmortem's "last
            # (worker, clock) the dead shard served" is computed from
            FLIGHT.record("shard.weights", shard=shard_id, worker=worker,
                          clock=msg.vector_clock)
        last = self._delivered.get(worker, -1)
        if msg.vector_clock <= last:
            if self._resend is not None:
                self._resend(shard_id, worker, msg.vector_clock)
            return False
        held = self._slices.setdefault(worker, {})
        held[shard_id] = msg            # latest slice per shard wins
        if len(held) < self.plan.num_shards:
            return False
        clocks = [held[s].vector_clock
                  for s in range(self.plan.num_shards)]
        if min(clocks) != max(clocks):
            return False                # shards not yet at a common clock
        values = np.concatenate([
            # pscheck: disable=PS102 (host-side assembly; slices are host arrays)
            np.asarray(held[s].values)
            for s in range(self.plan.num_shards)])
        full = WeightsMessage(
            vector_clock=clocks[0],
            key_range=KeyRange(0, self.plan.num_params),
            values=values)
        _copy_trace(held[0], full)
        self._slices[worker] = {}
        self._delivered[worker] = clocks[0]
        self._deliver(worker, full)
        return True

    def drop(self, worker: int) -> None:
        """Forget partial state for a worker (eviction purge path)."""
        self._slices.pop(worker, None)


class _ShardWeightsFabric(fabric_mod.Fabric):
    """Send-side facade handed to each in-process shard ServerNode:
    weights slices feed the shared assembler (which synthesizes the
    full-range message into the real fabric), gang notices pass through
    from shard 0 only (all shards compute identical release sets in
    lockstep — N notices for one release moment would be noise), and
    everything else forwards to the inner fabric."""

    def __init__(self, inner: fabric_mod.Fabric, shard_id: int,
                 assembler: WeightsAssembler, forward_gang: bool):
        super().__init__()
        self._inner = inner
        self._shard_id = shard_id
        self._assembler = assembler
        self._forward_gang = forward_gang

    def send(self, topic: str, key: int, message) -> None:
        if topic == fabric_mod.WEIGHTS_TOPIC:
            self._assembler.offer(self._shard_id, key, message)
            return
        self._inner.send(topic, key, message)

    def send_transient(self, topic: str, key: int, message) -> None:
        if topic == fabric_mod.GANG_TOPIC and not self._forward_gang:
            return
        self._inner.send_transient(topic, key, message)

    def pending(self, topic: str, key: int = 0) -> int:
        if topic == fabric_mod.WEIGHTS_TOPIC:
            return 0        # slices never queue; assembly is immediate
        return self._inner.pending(topic, key)

    def purge(self, topic: str, key: int, pred) -> int:
        if topic == fabric_mod.WEIGHTS_TOPIC:
            self._assembler.drop(key)
            return 0
        return self._inner.purge(topic, key, pred)


class ShardedServerGroup:
    """N range-sharded ServerNodes behind one group facade.

    N=1 degenerates to today's single full-range server — same class,
    same constructor arguments, same fabric keys — so the unsharded
    bitwise contract (theta AND CSV rows, all three consistency models)
    holds by construction, pinned by tests/test_sharding.py.

    N>1: shard i owns plan.ranges[i], polls (GRADIENTS_TOPIC, i), and
    sends weights slices through the assembler.  Cross-shard consistent
    snapshots and the group-level eval both happen at the COMMON CLOCK
    FRONTIER (the min across shards of the per-shard stable clock):
    a cut is the vector of per-shard (theta_slice, clock) pairs taken
    when every shard has reached the frontier — concatenation is the
    servable/checkpointable full vector (docs/SHARDING.md)."""

    def __init__(self, cfg, fabric: fabric_mod.Fabric, num_shards: int,
                 test_x=None, test_y=None, log=None,
                 tracer=None, telemetry=None):
        from kafka_ps_tpu.models.task import get_task
        self.cfg = cfg
        self.fabric = fabric
        self.task = get_task(cfg.task, cfg.model)
        self.plan = ShardPlan(self.task.num_params, num_shards)
        self.test_x = test_x
        self.test_y = test_y
        self.log = log or (lambda line: None)
        self.routers: dict[int, ShardRouter] = {}
        self._eval_clock = -1
        self._cut_publisher = None
        self.eval_engine = None   # async eval plane (enable_async_eval)
        if num_shards == 1:
            node = ServerNode(cfg, fabric, test_x, test_y, log,
                              tracer=tracer, telemetry=telemetry)
            self.shards = [node]
            self.single: ServerNode | None = node
            self.assembler = None
            return
        self.single = None
        self.assembler = WeightsAssembler(
            self.plan,
            deliver=lambda w, m: fabric.send(
                fabric_mod.WEIGHTS_TOPIC, w, m),
            resend=self._resend_slice)
        self.shards = [
            ServerNode(cfg, _ShardWeightsFabric(fabric, i, self.assembler,
                                                forward_gang=(i == 0)),
                       None, None, None, tracer=tracer, telemetry=telemetry,
                       key_range=rng, shard_id=i, num_shards=num_shards,
                       grad_key=i)
            for i, rng in enumerate(self.plan.ranges)]

    # -- worker wiring -----------------------------------------------------

    def attach_workers(self, workers) -> None:
        """Give each worker a ShardRouter over this group's fabric keys.
        N=1 leaves workers untouched (the unsharded send path IS the
        N=1 protocol)."""
        if self.plan.num_shards == 1:
            return
        for w in workers:
            router = ShardRouter(
                self.plan,
                send=lambda sid, m: self.fabric.send(
                    fabric_mod.GRADIENTS_TOPIC, sid, m))
            w.shard_router = router
            self.routers[w.worker_id] = router

    def _resend_slice(self, shard_id: int, worker: int,
                      clock: int) -> bool:
        router = self.routers.get(worker)
        return router.resend(shard_id, clock) if router else False

    # -- group state -------------------------------------------------------

    @property
    def iterations(self) -> int:
        """Applied-message budget for drive loops: every (worker, clock)
        delta reaches EVERY shard (empty slices included), so the
        slowest shard's count is the number of fully-applied deltas."""
        return min(s.iterations for s in self.shards)

    def frontier_clock(self) -> int:
        """The common clock frontier: min across shards of the per-shard
        stable clock (serving_clock).  Every shard has incorporated all
        rounds below it — the cross-shard mirror of the single-server
        stable clock."""
        return min(s.serving_clock() for s in self.shards)

    def assembled_theta(self) -> np.ndarray:
        """Concatenate the per-shard theta slices in shard-id order.
        Host-side copy; the per-shard slices stay untouched."""
        return np.concatenate(
            [np.asarray(s.theta) for s in self.shards])

    def snapshot_cut(self) -> list[tuple]:
        """The consistent-cut vector: per-shard (theta reader, clock)
        in shard-id order, read at one drive-loop quiescent point.
        The slice is LAZY (a zero-arg callable): FrontierCutPublisher
        materializes only when the frontier actually advanced — with
        tiered residency attached (docs/TIERING.md), reading a slice
        assembles pages and faults cold ones, so the cuts that publish
        nothing must not touch the stores."""
        return [((lambda s=s: np.asarray(s.theta)), s.serving_clock())
                for s in self.shards]

    def attach_param_stores(self, make_store) -> None:
        """Tiered residency per shard (kafka_ps_tpu/store/): each shard
        gets its own TieredParamStore over its range — built by
        `make_store(shard)` so the caller decides per-shard budgets and
        cold partitions (residency is a per-process resource; the CLI
        splits a process's byte caps evenly across its in-process
        shards, docs/TIERING.md)."""
        for s in self.shards:
            s.attach_param_store(make_store(s))

    # -- serving / eval at the frontier ------------------------------------

    def attach_serving(self, registry) -> None:
        """Cross-shard serving: snapshots publish ASSEMBLED theta at the
        clock frontier (serving/snapshot.FrontierCutPublisher), never a
        torn mix of shard states.  N=1 attaches the registry directly —
        per-release publication, exactly the unsharded plane."""
        if self.single is not None:
            self.single.serving = registry
            return
        from kafka_ps_tpu.serving.snapshot import FrontierCutPublisher
        self._cut_publisher = FrontierCutPublisher(registry)

    def publish_frontier(self) -> None:
        """Publish a consistent cut if the frontier advanced.  Called by
        the drive loop between processing rounds (quiescent point: no
        shard is mid-apply)."""
        if self._cut_publisher is None:
            return
        self._cut_publisher.maybe_publish(self.snapshot_cut())

    def enable_async_eval(self, telemetry=None, tracer=None):
        """Attach the async coalescing eval plane (evaluation/engine.py).
        N=1 arms the inner ServerNode — exactly the unsharded lever.
        N>1 arms the GROUP's frontier eval: maybe_eval submits the
        assembled theta (already a fresh host copy — immutable by
        construction) instead of evaluating inline; the engine's thread
        emits the same CSV rows in frontier-clock order.  Idempotent;
        returns the engine (None without a test set)."""
        if self.eval_engine is not None:
            return self.eval_engine
        if self.single is not None:
            if self.single.test_x is None:
                return None
            from kafka_ps_tpu.evaluation.engine import EvalEngine
            self.eval_engine = self.single.attach_eval_engine(EvalEngine(
                self.single.task, self.single.test_x, self.single.test_y,
                self.single._emit_eval,
                telemetry=telemetry, tracer=tracer))
            return self.eval_engine
        if self.test_x is None:
            return None
        from kafka_ps_tpu.evaluation.engine import EvalEngine
        self.eval_engine = EvalEngine(
            self.task, self.test_x, self.test_y, self._emit_eval,
            telemetry=telemetry, tracer=tracer)
        return self.eval_engine

    def close_eval(self) -> None:
        """Drain pending evals and join the engine thread."""
        if self.eval_engine is not None:
            self.eval_engine.close()

    def _emit_eval(self, clock: int, m) -> None:
        """Group eval row writer — same schema as ServerNode._emit_eval
        (timestamp;partition;vectorClock;loss;fMeasure;accuracy); shared
        by the inline frontier eval and the async engine's thread."""
        import time
        from kafka_ps_tpu.utils import asynclog
        asynclog.submit_or_write(
            self.log,
            # pscheck: disable=PS104 (CSV wall-clock column, not replay state)
            f"{int(time.time() * 1000)};-1;{clock};"
            "{};{};{}", m.loss, m.f1, m.accuracy)

    def maybe_eval(self) -> None:
        """Group-level online eval: when the WORKER-0 frontier (min
        across shards of worker 0's clock) crosses the eval cadence,
        evaluate the assembled theta and emit the server CSV row —
        same schema as the single server.  Documented divergence at
        N>1: the eval observes the assembled theta at the frontier
        moment, not each shard's mid-round prefix (docs/SHARDING.md)."""
        if self.single is not None or self.test_x is None:
            return
        frontier0 = min(s.tracker.tracker[0].vector_clock
                        for s in self.shards)
        latest = frontier0 - (frontier0 % self.cfg.eval_every)
        if latest <= self._eval_clock or latest < 0:
            return
        self._eval_clock = latest
        if self.eval_engine is not None:
            # assembled_theta() is a fresh np.concatenate per call —
            # the engine's queue owns this copy outright
            self.eval_engine.submit(self.assembled_theta(), latest)
            return
        import jax.numpy as jnp
        m = self.task.evaluate(jnp.asarray(self.assembled_theta()),
                               jnp.asarray(self.test_x),
                               jnp.asarray(self.test_y))
        self._emit_eval(latest, m)

    # -- checkpointing -----------------------------------------------------

    def set_checkpoint(self, path: str, every: int = 50) -> None:
        """One checkpoint file per shard (utils/checkpoint.py
        shard_state_path): shard i saves its own slice + tracker +
        committed log offsets, independently recoverable — the
        per-shard durable-log partition's commit point."""
        from kafka_ps_tpu.utils import checkpoint as ckpt
        for i, s in enumerate(self.shards):
            s.checkpoint_path = ckpt.shard_state_path(
                path, i, self.plan.num_shards)
            s.checkpoint_every = every

    def maybe_restore(self) -> bool:
        from kafka_ps_tpu.utils import checkpoint as ckpt
        restored = False
        for s in self.shards:
            if s.checkpoint_path:
                restored |= ckpt.maybe_restore(s.checkpoint_path, s)
        return restored

    def save_checkpoint_now(self) -> None:
        for s in self.shards:
            s.save_checkpoint_now()

    # -- drive loop --------------------------------------------------------

    def start(self) -> None:
        for s in self.shards:
            s.start_training_loop()
        self.publish_frontier()

    def run_serial(self, workers, max_server_iterations: int,
                   pump=None) -> None:
        """Deterministic serial scheduler for the sharded group —
        mirrors app.run_serial's alternation (weights delivery, then
        gradient drain in shard-id order), without the gang claim (the
        gang path coalesces per shard server-side via process_batch;
        see run_serial_gang-less note in docs/SHARDING.md)."""
        self.attach_workers(workers)
        self.start()
        stalled = 0
        while self.iterations < max_server_iterations:
            progressed = False
            for worker in workers:
                msg = self.fabric.poll(fabric_mod.WEIGHTS_TOPIC,
                                       worker.worker_id)
                if msg is not None:
                    worker.on_weights(msg)
                    progressed = True
            for sid, shard in enumerate(self.shards):
                key = 0 if self.single is not None else sid
                while shard.iterations < max_server_iterations:
                    g = self.fabric.poll(fabric_mod.GRADIENTS_TOPIC, key)
                    if g is None:
                        break
                    shard.process(g)
                    progressed = True
            self.maybe_eval()
            self.publish_frontier()
            if pump is not None:
                pump()
            stalled = 0 if progressed else stalled + 1
            if stalled > (1000 if pump is not None else 0):
                raise RuntimeError(
                    "deadlock: no deliverable messages in sharded group")

"""Local delta pre-reduction — the aggregator's combine/expand engine.

One `LocalAggregator` lives on each host, between that host's workers
and the server(s).  Workers send it plain per-worker GradientMessages;
it combines everything pending into one `CompositeDelta` per flush and
forwards that upstream, then fans the returning weights back out.  The
server gate advances every member worker's clock from the composite's
(worker, clock) vector-clock map exactly as if the deltas had arrived
individually (runtime/server.py `process_composite`).

Two combine shapes (messages.CompositeDelta):

  * stacked (default) — members travel as their own per-worker deltas
    inside one frame.  The server applies them per-member in member
    order, so the aggregated path is BITWISE-identical to the direct
    path for all three consistency models (float addition is not
    associative; preserving the apply sequence, not just the sum, is
    what keeps the pin).
  * summed (`summed=True`) — members sharing ONE clock are pre-reduced
    into a single delta (exact by linearity for BSP): one server apply
    per host per clock.  Pending deltas that span clocks fall back to
    stacked for that flush, so mixed-progress moments never block.

Compression (`--compress`): workers ship raw f32 to their aggregator;
the aggregator owns the per-member error-feedback residuals
(compress/feedback.ErrorFeedback) and encodes at the aggregator→server
edge.  Because EF state is per worker stream and the encode sequence
per member is exactly what the worker itself would have produced, the
compressed aggregated path stays bitwise-pinned to the compressed
direct path in stacked mode.

Determinism: combine order, member order, and merge results are pure
functions of the offered messages (no wall clock, no hash-order
iteration) — the PS104 replay contract extends to this package.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict

import numpy as np

from kafka_ps_tpu.analysis.lockgraph import OrderedLock
from kafka_ps_tpu.runtime.messages import (CompositeDelta, GradientMessage,
                                           KeyRange, WeightsMessage)
from kafka_ps_tpu.telemetry import FLIGHT, NULL_TELEMETRY
from kafka_ps_tpu.utils.trace import NULL_TRACER

# composite fan-in distribution buckets (workers per composite)
FAN_IN_BUCKETS = (1, 2, 4, 8, 16, 32, 64)


def merge_composites(a: CompositeDelta, b: CompositeDelta) -> CompositeDelta:
    """Vector-clock merge of two STACKED composites: the union of their
    member maps, deduplicated by (worker, clock), sorted ascending.

    This is a semilattice join — associative, commutative, idempotent —
    because a redelivered (worker, clock) carries the identical delta
    (workers resend from their redelivery cache verbatim, never
    recompute), so "first writer wins" and "second writer wins" pick
    the same bytes.  tests/test_agg.py pins the algebra."""
    if a.summed or b.summed:
        raise ValueError("merge is defined on stacked composites only "
                         "(a summed composite has lost its members' "
                         "individual deltas)")
    by_member: dict[tuple[int, int], GradientMessage] = {}
    for comp in (a, b):
        for m, d in zip(comp.members, comp.deltas):
            by_member.setdefault(m, d)
    members = tuple(sorted(by_member))
    return CompositeDelta(agg_id=a.agg_id, members=members,
                          deltas=tuple(by_member[m] for m in members))


def split_composite(plan, composite: CompositeDelta) -> list[CompositeDelta]:
    """Range-sharding composition: run the shard split ONCE per
    composite instead of once per worker (docs/SHARDING.md).  Each
    member delta is sliced to every shard's key range; the result is
    one composite per shard carrying the full member map, so every
    shard's gate still sees one message per (host, clock)."""
    out = []
    for r in plan.ranges:
        deltas = []
        for d in composite.deltas:
            lo = r.start - d.key_range.start
            hi = r.end - d.key_range.start
            deltas.append(dataclasses.replace(
                d, key_range=KeyRange(r.start, r.end),
                values=d.values[lo:hi], encoded=None))
        out.append(CompositeDelta(agg_id=composite.agg_id,
                                  members=composite.members,
                                  deltas=tuple(deltas),
                                  summed=composite.summed))
    return out


class LocalAggregator:
    """Combine engine for one aggregator host.

    `offer()` is called from the per-worker reader threads; `combine()`
    from the forwarding loop.  Pending deltas are keyed (worker, clock)
    in arrival order with first-writer-wins dedup (a reconnecting
    worker's resend of an already-pending clock is dropped here; one
    that was already forwarded is deduplicated by the server gate)."""

    def __init__(self, agg_id: int, num_params: int, codec_spec=None,
                 summed: bool = False, telemetry=None, tracer=None):
        self.agg_id = agg_id
        self.num_params = num_params
        self.summed = summed
        self._spec = codec_spec          # compress/wire.CodecSpec or None
        self._ef = {}                    # worker id -> ErrorFeedback
        self._ef_clock = {}              # worker id -> last encoded clock
        self._ef_last = {}               # worker id -> last encoded msg
        self._pending: OrderedDict[tuple[int, int], GradientMessage] = \
            OrderedDict()
        self._lock = OrderedLock("agg.pending")
        self._telemetry = telemetry or NULL_TELEMETRY
        self._tracer = tracer or NULL_TRACER
        mode = "summed" if summed else "stacked"
        self._m_composites = self._telemetry.counter(
            "agg_composites_total", mode=mode)
        self._m_dropped_dups = self._telemetry.counter(
            "agg_duplicate_offers_total")
        self._m_fan_in = self._telemetry.histogram(
            "agg_fan_in", buckets=FAN_IN_BUCKETS)

    def _ef_for(self, worker: int):
        ef = self._ef.get(worker)
        if ef is None:
            from kafka_ps_tpu.compress.codecs import get_codec
            from kafka_ps_tpu.compress.feedback import ErrorFeedback
            ef = ErrorFeedback(get_codec(self._spec, self.num_params))
            self._ef[worker] = ef
        return ef

    # -- worker-facing side ------------------------------------------------

    def offer(self, msg: GradientMessage) -> bool:
        """Queue one worker delta for the next combine.  Returns False
        for a duplicate of a still-pending (worker, clock)."""
        key = (msg.worker_id, msg.vector_clock)
        with self._lock:
            if key in self._pending:
                self._m_dropped_dups.inc()
                return False
            self._pending[key] = msg
        return True

    @property
    def pending_count(self) -> int:
        with self._lock:
            return len(self._pending)

    # -- server-facing side ------------------------------------------------

    def combine(self) -> CompositeDelta | None:
        """Drain everything pending into one composite (None when
        idle).  Summed mode pre-reduces only when all pending members
        share one clock; otherwise this flush degrades to stacked so a
        mixed-progress moment (reconnect backlog, eventual consistency)
        never stalls or misorders anyone."""
        with self._lock:
            if not self._pending:
                return None
            drained = list(self._pending.items())
            self._pending.clear()
        drained.sort(key=lambda kv: kv[0])
        members = tuple(k for k, _ in drained)
        deltas = [d for _, d in drained]
        clocks = {c for _, c in members}
        summed = self.summed and len(clocks) == 1 and len(deltas) > 1
        if summed:
            total = deltas[0].values
            for d in deltas[1:]:         # ascending worker id: documented
                total = total + d.values  # exact by linearity, not bitwise
            base = GradientMessage(
                vector_clock=next(iter(clocks)),
                key_range=deltas[0].key_range, values=total,
                worker_id=members[0][0])
            deltas = [self._encode(base) if self._spec is not None
                      else base]
        elif self._spec is not None:
            kept_members, kept = [], []
            for m, d in zip(members, deltas):
                out = self._encode(d)
                if out is None:
                    # resend below the EF horizon: its original encode
                    # already rode a forwarded composite (ef_state
                    # persists only after the upstream send), so the
                    # server has it — re-advancing the residual here
                    # would desync every later encode
                    self._m_dropped_dups.inc()
                    continue
                kept_members.append(m)
                kept.append(out)
            if not kept:
                return None
            members, deltas = tuple(kept_members), kept
        composite = CompositeDelta(agg_id=self.agg_id, members=members,
                                   deltas=tuple(deltas), summed=summed)
        self._m_composites.inc()
        self._m_fan_in.observe(len(members))
        if FLIGHT.enabled:
            FLIGHT.record("agg.combine", agg=self.agg_id,
                          fan_in=len(members), summed=summed,
                          clock=members[-1][1])
        if self._tracer.enabled:
            for m, d in zip(members, composite.deltas):
                fid = getattr(d, "trace", None)
                if fid:
                    # continue the worker's delta.wire flow through the
                    # aggregator hop so critpath still stitches
                    # end-to-end
                    self._tracer.flow_step("delta.wire", fid,
                                           agg=self.agg_id, worker=m[0])
        return composite

    def _encode(self, msg: GradientMessage) -> GradientMessage | None:
        """Aggregator-owned error feedback at the upstream edge: the
        same compensate→encode→decode sequence the worker would have
        run on the direct path, keyed by the member's worker id.

        EF is a running residual, so each clock may advance it exactly
        once even when workers resend (reconnect replays the whole
        redelivery cache).  The clock horizon makes resends safe:
        a clock AT the horizon returns the cached encode verbatim
        (bitwise, the server deduplicates it), one BELOW it returns
        None (already forwarded — combine drops the member)."""
        w, c = msg.worker_id, msg.vector_clock
        last = self._ef_clock.get(w, -1)
        if c < last:
            return None
        if c == last:
            return self._ef_last[w]
        decoded, enc = self._ef_for(w).step(msg.values)
        out = dataclasses.replace(msg, values=decoded, encoded=enc)
        fid = getattr(msg, "trace", None)
        if fid:
            object.__setattr__(out, "trace", fid)
        self._ef_clock[w] = c
        self._ef_last[w] = out
        return out

    # -- weights fan-out (reverse direction) -------------------------------

    def expand(self, msg: WeightsMessage, members) -> list:
        """One server→aggregator weights send re-broadcast to every
        member: (worker, WeightsMessage-with-that-worker's-clock)
        pairs.  theta bytes are shared; only the clock stamp differs
        (eventual consistency advances members independently)."""
        out = []
        for worker, clock in members:
            m = (msg if msg.vector_clock == clock
                 else dataclasses.replace(msg, vector_clock=clock))
            out.append((worker, m))
        if FLIGHT.enabled:
            FLIGHT.record("agg.forward", agg=self.agg_id,
                          fan_out=len(out), clock=msg.vector_clock)
        return out

    # -- crash/restart seam ------------------------------------------------

    def reset(self) -> None:
        """Drop all pending state (the SIGKILL simulation seam of
        tests/test_agg.py): a real restart loses pending deltas AND
        EF residuals; workers re-send from their redelivery caches and
        the server gate deduplicates what had already been forwarded."""
        with self._lock:
            self._pending.clear()
        self._ef.clear()
        self._ef_clock.clear()
        self._ef_last.clear()

    def ef_state(self) -> dict[int, tuple[np.ndarray, int, bytes]]:
        """Snapshot the error-feedback plane for the relay checkpoint:
        worker -> (residual copy, last encoded clock, last encoded
        message as serde bytes).  Persisted AFTER each upstream send,
        so a restore's horizon only covers composites the server has:
        under `--compress` a SIGKILL'd aggregator would otherwise lose
        the residuals and break the bitwise pin on every later round."""
        from kafka_ps_tpu.runtime import serde
        out = {}
        for w, ef in self._ef.items():
            out[w] = (ef.state().copy(), self._ef_clock.get(w, -1),
                      serde.to_bytes(self._ef_last[w]))
        return out

    def ef_restore(self, state: dict) -> None:
        """Rehydrate `ef_state()` after a restart (agg/relay.py)."""
        from kafka_ps_tpu.runtime import serde
        for w, (residual, clock, last) in state.items():
            self._ef_for(int(w)).restore(np.asarray(residual))
            self._ef_clock[int(w)] = int(clock)
            self._ef_last[int(w)] = serde.from_bytes(last)


def direct_equivalent(composite: CompositeDelta) -> list[GradientMessage]:
    """The per-member message sequence this composite stands for, in
    member order — what the server's stacked expansion applies, and
    what tests compare against the direct path."""
    if composite.summed:
        raise ValueError("a summed composite has no per-member "
                         "equivalent (pre-reduced by linearity)")
    return list(composite.deltas)

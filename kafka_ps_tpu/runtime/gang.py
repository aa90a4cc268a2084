"""Gang-scheduled dispatch — coalesce simultaneous gate releases into
one batched XLA step on the per-node path (docs/GANG_DISPATCH.md).

The consistency gate routinely releases several workers at the same
moment: ALL of them under sequential (BSP), a subset under bounded
delay whenever the slowest worker catches up, every active worker at
bootstrap.  The per-message path pays one `update_and_eval` dispatch
per released worker; coalescing pays one per release set (the
dispatch count is exact and tested; what a dispatch costs on the chip
is not measured yet — PERF.md).  This is the classic parameter-server
batching lever (Li et al., OSDI'14); under bounded staleness the sets
that coalesce are exactly the SSP release sets of Ho et al. (NIPS'13).

A `GangDispatcher` claims a release set (advertised by the server's
advisory `GangNotice` on GANG_TOPIC alongside the per-worker messages),
runs `_prepare` on every member (each keeps its private buffer slab and
`num_tuples_seen` version), stacks the member slabs, and runs ONE
vmapped solver dispatch over the (k, …) batch — the parameters' leaves
go in once, unbatched, when the set shares one weights array
(sequential consistency: the server aliases the same device theta into
every member's message; the first local step reads that one set for
all members, models/task.py `local_steps`), stacked otherwise
(bounded/eventual sets with differing clocks).  The k flat deltas and
metric futures are fanned out INSIDE the jit (one dispatch, k buffers
out; `_gang_solver_fns` says what that costs on the device), then
`_finish` runs per member in worker-id order — the
same per-worker CSV rows and the same per-worker GradientMessages, in
the same order, as the per-message path.  Bitwise equivalence with the
per-message path is a tested invariant (tests/test_gang.py), not an
approximation: vmap runs the identical per-element program.

Threaded mode coalesces by first arrival: the thread that pops a
weights message covered by a notice becomes the gang leader and polls
the fabric for siblings already enqueued — no timer sleeps on the hot
path.  Members whose threads beat the leader to their own messages
simply run solo there; a gang is an optimization, never a barrier.

Range sharding (runtime/sharding.py): every shard's gate computes the
identical release sets in lockstep (same gradients, same clocks), so
only SHARD 0 forwards its GangNotice — N notices for one release
moment would be noise — and the worker-side claim fires once the
assembler has synthesized the full-range weights at the common clock.
Server-side, gang applies coalesce per shard (each shard's
process_batch chains its own slice applies); there is no cross-shard
barrier in the dispatch path.

Aggregation tier (kafka_ps_tpu/agg/, docs/AGGREGATION.md): a composite
release counts as its MEMBER SET, not as one event — when the gate
applies a CompositeDelta (or flushes a BSP round buffer) the released
workers it unblocks form a single release set and emit ONE GangNotice
covering every member, exactly as if the per-member deltas had arrived
back to back; `gang_members_total` therefore accounts fan-in
correctly under aggregation with no special casing here.  The relay's
grouped weights fan-out (T_WEIGHTS_AGG) is invisible to this module:
by the time a member worker polls its weights message the relay has
already expanded the group into per-worker frames with re-stamped
clocks, so notice claiming matches on (worker, clock) as always.
"""

from __future__ import annotations

import functools

from kafka_ps_tpu.analysis.lockgraph import OrderedLock
from kafka_ps_tpu.runtime import fabric as fabric_mod
from kafka_ps_tpu.runtime import worker as worker_mod
from kafka_ps_tpu.utils.trace import NULL_TRACER


class GangMemberError(RuntimeError):
    """A gang member failed inside another worker's thread — carries the
    member's id so the threaded supervisor evicts the right worker."""

    def __init__(self, worker_id: int, cause: BaseException):
        super().__init__(f"gang member {worker_id} failed: {cause!r}")
        self.worker_id = worker_id


@functools.lru_cache(maxsize=None)
def _gang_solver_fns(task_name: str, cfg):
    """Batched counterparts of worker._solver_fns, one compile per
    (task, cfg, member-count) — four jit'd entry points over TUPLES of
    per-member arrays (stacked inside the jit, so stacking costs no
    extra dispatch), each returning k flat deltas and k scalars of each
    kind (fanned out inside the jit, so fan-out costs no dispatch
    either):

      update_stacked(thetas, xs, ys, masks)
      update_bcast(theta, xs, ys, masks)            # shared theta
      update_eval_stacked(thetas, xs, ys, masks, test_x, test_y)
      update_eval_bcast(theta, xs, ys, masks, test_x, test_y)

    They vmap the SAME leaf-level function the single-dispatch path
    jits (worker.fit_and_eval; vmap preserves per-element semantics —
    the bitwise-equivalence test in tests/test_gang.py is the
    contract).  The flat vectors stop at the
    program's edges: a shared theta is unflattened once, member thetas
    one by one and their leaves stacked; the deltas leave as stacked
    leaves [k, …], and member i's flat delta is flattened from row i of
    every leaf.  What the fan-out costs on the device is that
    concatenation, 16.9 MB written a member at H=4096; cut out of a
    [k, P] array, whose TPU tiles interleave eight members, it cost 16%
    of the program (PERF.md §6, PR 25)."""
    import jax
    import jax.numpy as jnp

    from kafka_ps_tpu.models.task import fit_slab, get_task
    task = get_task(task_name, cfg)

    def unstack(a, k):
        return tuple(a[i] for i in range(k))

    def tstack(items):
        # componentwise stack: identical to jnp.stack for plain member
        # slabs, and stacks QuantizedSlab (int8 slab storage,
        # compress/slab.py) and parameter leaves field-by-field — vmap
        # then maps over the leading axis of every leaf, preserving
        # per-element semantics
        return jax.tree.map(lambda *leaves: jnp.stack(leaves), *items)

    def over_members(fn, in_axes):
        """`fn` over the members' axis: a `vmap`, or, for a task whose
        update does not batch (`task.batches_workers`), one member at a
        time."""
        if task.batches_workers:
            return jax.vmap(fn, in_axes=in_axes)

        def one_at_a_time(*args):
            def one(mapped):
                mapped = iter(mapped)
                return fn(*[a if axis is None else next(mapped)
                            for a, axis in zip(args, in_axes)])
            return jax.lax.map(one, tuple(
                a for a, axis in zip(args, in_axes) if axis is not None))
        return one_at_a_time

    def member_leaves(thetas, shared):
        """(leaves, their vmap axis): one set for a shared theta, else
        the members' own, stacked leaf by leaf."""
        if shared:
            return task.unflatten(thetas), None
        return tstack([task.unflatten(t) for t in thetas]), 0

    def fan_out(deltas, k):
        return tuple(task.flatten(jax.tree.map(lambda a: a[i], deltas))
                     for i in range(k))

    def update(thetas, shared, xs, ys, masks):
        k = len(xs)
        leaves, axis = member_leaves(thetas, shared)
        deltas, losses = over_members(
            functools.partial(fit_slab, task),
            (axis, 0, 0, 0))(
                leaves, tstack(xs), jnp.stack(ys), jnp.stack(masks))
        return fan_out(deltas, k), unstack(losses, k)

    def update_eval(thetas, shared, xs, ys, masks, test_x, test_y):
        k = len(xs)
        leaves, axis = member_leaves(thetas, shared)
        deltas, losses, f1s, accs = over_members(
            functools.partial(worker_mod.fit_and_eval, task),
            (axis, 0, 0, 0, None, None))(
                leaves, tstack(xs), jnp.stack(ys), jnp.stack(masks),
                test_x, test_y)
        return (fan_out(deltas, k), unstack(losses, k),
                unstack(f1s, k), unstack(accs, k))

    @jax.jit
    def update_stacked(thetas, xs, ys, masks):
        return update(thetas, False, xs, ys, masks)

    @jax.jit
    def update_bcast(theta, xs, ys, masks):
        return update(theta, True, xs, ys, masks)

    @jax.jit
    def update_eval_stacked(thetas, xs, ys, masks, test_x, test_y):
        return update_eval(thetas, False, xs, ys, masks, test_x, test_y)

    @jax.jit
    def update_eval_bcast(theta, xs, ys, masks, test_x, test_y):
        return update_eval(theta, True, xs, ys, masks, test_x, test_y)

    return {"update_stacked": update_stacked,
            "update_bcast": update_bcast,
            "update_eval_stacked": update_eval_stacked,
            "update_eval_bcast": update_eval_bcast}


def _gangable(worker) -> bool:
    """A worker whose `on_weights` has been overridden on the INSTANCE
    (test fault injectors, wrapper hooks) must keep the per-message
    entry point — the gang's `_prepare`/`_finish` split would silently
    bypass the wrapper.  Such workers are never claimed into a gang;
    their messages stay queued for the normal single-dispatch path."""
    return "on_weights" not in vars(worker)


class GangDispatcher:
    """Claims release sets and runs them as batched dispatches.

    Serial drive: `drain_serial()` pops each GangNotice, claims every
    member's weights message, and dispatches the whole set — fully
    deterministic.  Threaded drive: worker threads route messages
    through `offer()`; the first arrival covered by a notice leads the
    gang, claiming only siblings ALREADY enqueued (non-blocking polls,
    no sleeps — latecomers run solo on their own threads)."""

    def __init__(self, workers, fabric, cfg, tracer=None, telemetry=None):
        self.workers = {w.worker_id: w for w in workers}
        self.fabric = fabric
        self.cfg = cfg
        self.tracer = tracer or NULL_TRACER
        from kafka_ps_tpu.telemetry import NULL_TELEMETRY
        self.telemetry = telemetry or NULL_TELEMETRY
        self._m_dispatches = self.telemetry.counter("gang_dispatches_total")
        self._m_members = self.telemetry.counter("gang_members_total")
        self._offer_lock = OrderedLock("GangDispatcher.offer")
        # (worker_id, clock) -> the full member tuple of its notice
        self._notices: dict[tuple[int, int], tuple] = {}
        # error-feedback compression needs crash-recovery replay to
        # re-run the EXACT device programs the live run dispatched; a
        # recovery claim can merge releases the live run dispatched
        # separately (the restarted gate re-fires them inside one
        # batched apply), so compressed runs group members by clock —
        # one dispatch per release set — instead of letting a single
        # stacked program span clocks
        self._per_clock = bool(getattr(cfg, "compress", "none")
                               not in (None, "", "none"))

    # -- drive-loop entries ------------------------------------------------

    def drain_serial(self) -> bool:
        """Consume every queued gang notice, claiming each release set
        whole (the serial loop drains the set before dispatching).
        Returns True if any dispatch ran."""
        progressed = False
        while True:
            notice = self.fabric.poll(fabric_mod.GANG_TOPIC, 0)
            if notice is None:
                return progressed
            members = []
            for w, _ in notice.members:
                if not _gangable(self.workers[w]):
                    continue    # left queued for the per-message loop
                msg = self.fabric.poll(fabric_mod.WEIGHTS_TOPIC, w)
                if msg is None:
                    continue
                if self.workers[w]._redelivered_weights(msg):
                    continue    # recovery duplicate: cached resend only
                members.append((self.workers[w], msg))
            if not members:
                continue            # set already consumed elsewhere
            if len(members) == 1:
                members[0][0].on_weights(members[0][1])
            else:
                self.dispatch(members)
            progressed = True

    def offer(self, worker, msg) -> None:
        """Threaded entry: first-arrival leadership.  The calling thread
        pops the notice covering (worker, clock) — if there is one — and
        claims siblings' weights messages still sitting in the fabric.
        Members whose threads already popped their own message run solo
        there (their notice entry is dropped so they cannot re-claim a
        stale set).  All bookkeeping is non-blocking under one lock; the
        batched dispatch itself runs outside it."""
        if not _gangable(worker):
            worker.on_weights(msg)
            return
        if worker._redelivered_weights(msg):
            return              # recovery duplicate: cached resend only
        with self._offer_lock:
            self._refresh_notices()
            # entries superseded by this worker's own progress can never
            # match again — drop them so the map stays bounded
            for kc in [kc for kc in self._notices
                       if kc[0] == worker.worker_id
                       and kc[1] < msg.vector_clock]:
                del self._notices[kc]
            spec = self._notices.pop((worker.worker_id, msg.vector_clock),
                                     None)
            members = None
            if spec is not None:
                members = [(worker, msg)]
                for w, _ in spec:
                    if w == worker.worker_id:
                        continue
                    if not _gangable(self.workers[w]):
                        continue    # its own thread delivers per-message
                    sib = self.fabric.poll(fabric_mod.WEIGHTS_TOPIC, w)
                    if sib is None:
                        continue
                    if self.workers[w]._redelivered_weights(sib):
                        continue    # recovery duplicate: cached resend
                    members.append((self.workers[w], sib))
                for w, c in spec:   # claimed: latecomers run solo
                    self._notices.pop((w, c), None)
        if members is None or len(members) == 1:
            worker.on_weights(msg)
        else:
            self.dispatch(members)

    def _refresh_notices(self) -> None:
        while True:
            notice = self.fabric.poll(fabric_mod.GANG_TOPIC, 0)
            if notice is None:
                return
            for member in notice.members:
                self._notices[member] = notice.members

    # -- the batched step --------------------------------------------------

    def dispatch(self, members) -> None:
        """One batched device step for a claimed release set, preserving
        per-message semantics exactly: members sort by worker id (the
        serial per-message processing order), `_prepare`/`_finish` are
        the worker's own halves, and the solver runs the same
        per-element program vmapped.  Mixed eval cadence (bounded-delay
        sets span clocks) partitions into at most one eval and one
        non-eval dispatch; a partition of one keeps the single-dispatch
        path.  Partial-range messages (range sharding) cannot stack —
        the whole set degrades to per-message processing."""
        members = sorted(members, key=lambda wm: wm[0].worker_id)
        if any(m.key_range.start != 0
               or m.key_range.end != w.task.num_params
               for w, m in members):
            for w, m in members:
                w.on_weights(m)
            return

        failures: list[GangMemberError] = []
        prepared = []
        for w, m in members:
            try:
                prepared.append((w, m) + tuple(w._prepare(m)))
            except BaseException as e:   # the healthy members still run
                failures.append(GangMemberError(w.worker_id, e))
        results: dict[int, tuple] = {}
        if self._per_clock:
            grouped: dict[tuple, list] = {}
            for p in prepared:
                grouped.setdefault((p[7], p[1].vector_clock),
                                   []).append(p)
            for (with_eval, _), grp in grouped.items():
                self._dispatch_group(grp, with_eval, results)
        else:
            eval_grp = [p for p in prepared if p[7]]
            noeval_grp = [p for p in prepared if not p[7]]
            for grp, with_eval in ((eval_grp, True), (noeval_grp, False)):
                if grp:
                    self._dispatch_group(grp, with_eval, results)
        # _finish in member order: CSV rows and GradientMessages hit
        # their queues in exactly the per-message order
        for p in prepared:
            w, msg, _, _, _, _, seen, _ = p
            w._finish(msg, seen,
                      *results[(w.worker_id, msg.vector_clock)])
        if failures:
            raise failures[0]

    def _dispatch_group(self, grp, with_eval: bool, results: dict) -> None:
        k = len(grp)
        if k == 1:
            w, msg, theta, x, y, mask, _, _ = grp[0]
            update_fn, update_eval_fn = worker_mod._solver_fns(
                self.cfg.task, self.cfg.model)
            with self.tracer.span("worker.local_update",
                                  worker=w.worker_id,
                                  clock=msg.vector_clock):
                if with_eval:
                    delta, loss, f1, acc = update_eval_fn(
                        theta, x, y, mask, w.test_x, w.test_y)
                else:
                    delta, loss = update_fn(theta, x, y, mask)
                    f1 = acc = -1.0
            self.tracer.count("dispatch.device")
            results[(w.worker_id, msg.vector_clock)] = (delta, loss,
                                                        f1, acc)
            return

        thetas = [p[2] for p in grp]
        xs = tuple(p[3] for p in grp)
        ys = tuple(p[4] for p in grp)
        masks = tuple(p[5] for p in grp)
        # sequential release sets alias ONE server theta into every
        # member message (server._weights_message), so identity — not a
        # device-side compare — detects the broadcast case
        shared = all(t is thetas[0] for t in thetas)
        lead = grp[0][0]

        fns = _gang_solver_fns(self.cfg.task, self.cfg.model)
        # same span name as the per-message path — one entry now covers
        # k members (the `gang` arg distinguishes the two in traces)
        with self.tracer.span("worker.local_update", gang=k,
                              workers=[p[0].worker_id for p in grp]):
            if with_eval and shared:
                out = fns["update_eval_bcast"](
                    thetas[0], xs, ys, masks, lead.test_x, lead.test_y)
            elif with_eval:
                out = fns["update_eval_stacked"](
                    tuple(thetas), xs, ys, masks, lead.test_x, lead.test_y)
            elif shared:
                out = fns["update_bcast"](thetas[0], xs, ys, masks)
            else:
                out = fns["update_stacked"](tuple(thetas), xs, ys, masks)
        self.tracer.count("dispatch.device")
        self.tracer.count("gang.batched_dispatches")
        if self.telemetry.enabled:
            self._m_dispatches.inc()
            self._m_members.inc(k)
        if with_eval:
            deltas, losses, f1s, accs = out
        else:
            deltas, losses = out
            f1s = accs = (-1.0,) * k
        # keyed by (worker, clock): a recovery claim can hold TWO
        # messages for one worker (a merged notice spanning releases),
        # and each one's result must reach its own _finish
        for p, d, loss, f1, a in zip(grp, deltas, losses, f1s, accs):
            results[(p[0].worker_id, p[1].vector_clock)] = (d, loss, f1, a)

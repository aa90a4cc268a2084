"""Fused BSP training step — the sequential consistency model as a single
jit'd SPMD program over the device mesh.

This is the headline TPU-native design: the reference's per-iteration
round trip worker → GRADIENTS topic → server sum → WEIGHTS topic →
worker (JSON through a Kafka broker, ServerProcessor.java:143-183)
collapses into ONE compiled XLA step: each device runs the k-step local
solver on its buffer slab, deltas are averaged with `psum` over ICI, and
the replicated parameters advance in lockstep — the broadcast back is
free because the sharding is replicated.

Semantically identical to the message-driven sequential path
(runtime/server.py with consistency 0): theta' = theta + (1/N) * sum_i
delta_i, every worker always at the same clock.  Equivalence is tested
in tests/test_parallel.py.

When there are fewer devices than logical workers (e.g. one TPU chip
hosting 4 logical workers, like the reference's 4 stream threads in one
JVM — BaseKafkaApp.java:70), the worker axis falls back to a `vmap`
inside the device: same math, XLA parallelizes across the MXU.

A task whose update does not batch (`task.batches_workers` false: a
worker's own matrix products fill the chip, and W copies of its
parameters and gradients would not fit it) takes the folded programs
instead: the worker axis is a `lax.scan` over the slabs that adds each
worker's delta leaves into one running sum, and a scan chunk carries
the parameters as their leaves from round to round and from dispatch to
dispatch (donated) — the flat vector is cut into leaves where a drive
call begins and built again where it ends (`folded_edges`).  Those
programs also return the task's counters (`task.counter_names`),
summed over the dispatch's updates.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kafka_ps_tpu.models.task import default_task, fit_delta
from kafka_ps_tpu.parallel.mesh import WORKER_AXIS
from kafka_ps_tpu.utils.config import ModelConfig

# step(theta, x, y, mask) -> (theta', mean_loss), and for a folded
# task (leaves', mean_loss, counters) from its leaves
#   theta: [P] flat, replicated — what a vmapped program takes, carries
#   from round to round and returns; inside a round the parameters are
#   the task's leaves.  x: [N, cap, F]; y: [N, cap]; mask: [N, cap]
BspStep = Callable[..., tuple[jax.Array, jax.Array]]


def _make_round(task, num_workers: int, server_lr: float, psum_axis: bool):
    """One BSP clock on this device's slabs, shared by both builders.

    The shared theta is unflattened once, before the worker axis
    exists; the leaf-level fit is vmapped over the slabs; the leaf
    deltas are summed over the worker axis and only that sum is
    flattened — under a mesh, one `psum` of one flat [P] vector a clock.
    No array of shape [workers, num_params] is built: a TPU tiles one
    over (worker, key), and cutting W1 out of it and putting the
    gradient back were relayouts of every worker's parameters every
    local step (PERF.md §6, PR 25).  Nor is one of shape
    [workers, …leaf] built before a worker has a gradient: the leaves
    go into the `vmap` unbatched, the fit's first step reads them so
    (one product over every worker's rows; models/task.py
    `local_steps`), and a worker's first own copy of a leaf is what its
    first parameter step writes (PERF.md §6, PR 30)."""

    def round_(theta, x, onehot, mask):
        leaves = task.unflatten(theta)
        if psum_axis:
            # theta stays axis-invariant (the scan carry, the result):
            # a per-round copy of its leaves is cast device-varying for
            # the local math, so the scan carry inside `fit` has a
            # stable varying-axes type; psum below restores invariance
            leaves = jax.tree.map(
                lambda a: jax.lax.pcast(a, WORKER_AXIS, to="varying"),
                leaves)
        deltas, losses = jax.vmap(
            lambda xx, oo, mm: fit_delta(task, leaves, xx, oo, mm)
        )(x, onehot, mask)
        with jax.named_scope("kps.bsp.reduce"):
            delta_sum = task.flatten(
                jax.tree.map(lambda d: d.sum(0), deltas))
            loss_sum = losses.sum()
            if psum_axis:
                delta_sum = jax.lax.psum(delta_sum, WORKER_AXIS)
                loss_sum = jax.lax.psum(loss_sum, WORKER_AXIS)
        with jax.named_scope("kps.bsp.apply"):
            return theta + server_lr * delta_sum, loss_sum / num_workers

    return round_


def _make_folded_round(task, num_workers: int, server_lr: float):
    """One BSP clock on the parameters' leaves, one worker at a time:
    (leaves, x, encoded, mask) -> (leaves', mean loss, counters).
    Alive at once: the shared leaves, one worker's working copy and
    gradient, and the running sum of deltas.

    A barrier in the worker loop ties the shared leaves to a value that
    changes from worker to worker (see `worker`).  Until PR 47 that
    value was the running sum, and the sum paid for the ride: the
    compiler sinks the scan's zero start into the body as
    `select(i == 0, 0, total)`, a barrier fuses with nothing, and so
    every worker update read the whole sum and wrote it back (zeroed
    for the first worker) before `kps.fit.delta` read and wrote it
    again — 83 `broadcast_select_fusion`s and 2.365 GB of results in
    the GLM cell's chunk, 5.7 to 8.3 ms of every language-model update
    (PERF.md section 6, PR 47).  The sum now goes from the loop's state
    straight into its one consumer, and the select rides in that
    fusion."""

    def round_(leaves, x, encoded, mask):
        def worker(carry, slab):
            total, loss_sum, counted = carry
            # the shared leaves are tied to the running LOSS, a scalar
            # that changes from worker to worker: left loop-invariant,
            # every relayout of a weight for the first local step is
            # hoisted out of the loop and kept beside the leaves — two
            # more copies of the parameters at the published widths.
            # The running sum of deltas stays out of the barrier: what
            # goes through one is written to memory first
            with jax.named_scope("kps.bsp.carry"):
                shared, loss_sum = jax.lax.optimization_barrier(
                    (leaves, loss_sum))
            new, loss, counts = task.fit_counted(shared, *slab)
            with jax.named_scope("kps.fit.delta"):
                total = jax.tree.map(lambda t, n, o: t + (n - o),
                                     total, new, shared)
            return (total, loss_sum + loss, counted + counts), None

        # the loop's own time has a name of its own — what lies under
        # `kps.bsp.fold` and no scope of the solver's is the fold's:
        # the slabs sliced for a worker (benchmark/self_time.py).  The
        # running sum's zeros are no array: the compiler sinks them
        # into the body, where they are a select inside
        # `kps.fit.delta`'s fusion; `kps.bsp.carry` holds the barrier's
        # tuple elements and the copies of a few small leaves
        with jax.named_scope("kps.bsp.fold"):
            zero = (jax.tree.map(jnp.zeros_like, leaves), jnp.float32(0.0),
                    jnp.zeros((len(task.counter_names),), jnp.int32))
            (total, loss_sum, counted), _ = jax.lax.scan(
                worker, zero, (x, encoded, mask))
        with jax.named_scope("kps.bsp.apply"):
            return (jax.tree.map(lambda a, d: a + server_lr * d, leaves,
                                 total),
                    loss_sum / num_workers, counted)

    return round_


def _make_folded(task, num_workers: int, server_lr: float, rounds: int,
                 mesh, whole_chunk: bool) -> BspStep:
    """The folded step (`rounds` 1, one loss) or scan chunk (a loss a
    round): (leaves, x, y, mask) -> (leaves', loss(es), counters).
    The leaves are DONATED: a caller carries them from dispatch to
    dispatch and holds no other use of them (`folded_edges` has the way
    from and to the flat vector, and the evaluation from the leaves)."""
    if mesh is not None:
        raise ValueError(
            f"task {type(task).__name__} folds its workers one at a time "
            "on one device; it has no program over a mesh")
    round_ = _make_folded_round(task, num_workers, server_lr)

    def scanned(leaves, x, y, mask):    # the program's name: jit_scanned
        encoded = task.encode_labels(y)

        def clock(leaves, _):
            leaves, loss, counted = round_(leaves, x, encoded, mask)
            return leaves, (loss, counted)

        leaves, (losses, counted) = jax.lax.scan(clock, leaves, None,
                                                 length=rounds)
        return (leaves, losses if whole_chunk else losses[0],
                counted.sum(0))

    return jax.jit(scanned, donate_argnums=0)


def folded_edges(task):
    """The edges of a folded task's drive call, each a program of its
    own: (cut: flat theta -> leaves, join: leaves -> flat theta,
    evaluate: (leaves, test_x, test_y) -> Metrics).  The flat vector
    exists where a call begins and ends — the server, the wire and the
    checkpoint take it — and nowhere between: beside the leaves, the
    running sum, a worker's working copy and its gradient, a fifth and
    sixth copy of the parameters inside the chunk program would not fit
    the chip."""

    def cut(theta):
        with jax.named_scope("kps.bsp.flat"):
            return task.unflatten(theta)

    def join(leaves):
        with jax.named_scope("kps.bsp.flat"):
            return task.flatten(leaves)

    return jax.jit(cut), jax.jit(join), jax.jit(task.evaluate_leaves)


def _over_mesh(body, num_workers: int, mesh: Mesh) -> BspStep:
    if num_workers % mesh.devices.size != 0:
        raise ValueError(
            f"num_workers {num_workers} must be a multiple of mesh size "
            f"{mesh.devices.size}")
    # x: [N/d, cap, F] on each device; theta replicated
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(WORKER_AXIS), P(WORKER_AXIS), P(WORKER_AXIS)),
        out_specs=(P(), P())))


def make_bsp_step(cfg: ModelConfig, num_workers: int, server_lr: float,
                  mesh: Mesh | None = None, task=None) -> BspStep:
    """Build the fused one-iteration BSP step.

    With a mesh: `shard_map` over the worker axis, one (or more) logical
    workers per device, `psum` of the summed delta over ICI.  Without:
    pure vmap on the default device.
    """

    task = task or default_task(cfg)
    if not task.batches_workers:
        return _make_folded(task, num_workers, server_lr, 1, mesh,
                            whole_chunk=False)
    round_ = _make_round(task, num_workers, server_lr,
                         psum_axis=mesh is not None)

    def step(theta, x, y, mask):
        return round_(theta, x, task.encode_labels(y), mask)

    if mesh is None:
        return jax.jit(step)

    def shard_body(theta, x, y, mask):    # the program's name, see below
        return step(theta, x, y, mask)

    return _over_mesh(shard_body, num_workers, mesh)


def make_bsp_multi_step(cfg: ModelConfig, num_workers: int, server_lr: float,
                        rounds: int, mesh: Mesh | None = None,
                        task=None) -> BspStep:
    """`rounds` BSP iterations as ONE device program (lax.scan over the
    fused step) — a single dispatch executes an entire training stretch,
    eliminating per-iteration host latency entirely.  This is the
    steady-state inner loop between buffer refreshes: with no new stream
    arrivals the reference's loop re-trains on the same buffer
    (WorkerTrainingProcessor.java:63-97), which is exactly a scan."""

    task = task or default_task(cfg)
    if not task.batches_workers:
        return _make_folded(task, num_workers, server_lr, rounds, mesh,
                            whole_chunk=True)

    def scanned(theta, x, y, mask, psum_axis):
        round_ = _make_round(task, num_workers, server_lr, psum_axis)
        # labels are fixed across rounds: encoded once, above the scan
        onehot = task.encode_labels(y)
        return jax.lax.scan(lambda t, _: round_(t, x, onehot, mask),
                            theta, None, length=rounds)

    # The jitted callables keep their shapes: XLA names the programs
    # after them (`jit__unknown` for the partial, `jit_shard_body`), and
    # the benchmark finds the window's programs by those names
    # (benchmark/workloads/*.json `window_programs`, ROADMAP S0b).
    if mesh is None:
        return jax.jit(partial(scanned, psum_axis=False))

    def shard_body(theta, x, y, mask):
        return scanned(theta, x, y, mask, psum_axis=True)

    return _over_mesh(shard_body, num_workers, mesh)


def shard_worker_batches(mesh: Mesh, x, y, mask):
    """Place the stacked per-worker slabs [N, ...] sharded over the worker
    axis so host→device transfer happens once per device, not per worker."""
    return tuple(
        jax.device_put(a, NamedSharding(mesh, P(WORKER_AXIS)))
        for a in (x, y, mask))

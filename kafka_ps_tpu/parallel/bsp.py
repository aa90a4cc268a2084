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

# step(theta, x, y, mask) -> (theta', mean_loss)
#   theta: [P] flat, replicated — what the program takes, carries from
#   round to round and returns; inside a round the parameters are the
#   task's leaves.  x: [N, cap, F]; y: [N, cap]; mask: [N, cap]
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
    local step (PERF.md §6, PR 25)."""

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
    round_ = _make_round(task, num_workers, server_lr,
                         psum_axis=mesh is not None)

    def step(theta, x, y, mask):
        onehot = jax.nn.one_hot(y, cfg.num_rows, dtype=jnp.float32)
        return round_(theta, x, onehot, mask)

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

    def scanned(theta, x, y, mask, psum_axis):
        round_ = _make_round(task, num_workers, server_lr, psum_axis)
        # labels are fixed across rounds: one-hot once, above the scan
        onehot = jax.nn.one_hot(y, cfg.num_rows, dtype=jnp.float32)
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

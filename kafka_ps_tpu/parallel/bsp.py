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

from kafka_ps_tpu.parallel.mesh import WORKER_AXIS
from kafka_ps_tpu.utils.config import ModelConfig

# step(theta, x, y, mask) -> (theta', mean_loss)
#   theta: [P] replicated; x: [N, cap, F]; y: [N, cap]; mask: [N, cap]
BspStep = Callable[..., tuple[jax.Array, jax.Array]]


def _default_task(cfg: ModelConfig):
    from kafka_ps_tpu.models.task import default_task
    return default_task(cfg)


def _vmapped_local_updates(theta, x, y, mask, task):
    return jax.vmap(
        lambda xx, yy, mm: task.local_update(theta, xx, yy, mm)
    )(x, y, mask)


def _vmapped_local_updates_onehot(theta, x, onehot, mask, task):
    return jax.vmap(
        lambda xx, oo, mm: task.local_update_onehot(theta, xx, oo, mm)
    )(x, onehot, mask)


def make_bsp_step(cfg: ModelConfig, num_workers: int, server_lr: float,
                  mesh: Mesh | None = None, task=None) -> BspStep:
    """Build the fused one-iteration BSP step.

    With a mesh: `shard_map` over the worker axis, one (or more) logical
    workers per device, `psum` of deltas over ICI.  Without: pure vmap on
    the default device.
    """

    task = task or _default_task(cfg)

    def apply(theta, delta_sum, loss_sum):
        with jax.named_scope("kps.bsp.apply"):
            return theta + server_lr * delta_sum, loss_sum / num_workers

    if mesh is None:
        @jax.jit
        def step(theta, x, y, mask):
            deltas, losses = _vmapped_local_updates(theta, x, y, mask, task)
            with jax.named_scope("kps.bsp.reduce"):
                delta_sum, loss_sum = deltas.sum(0), losses.sum()
            return apply(theta, delta_sum, loss_sum)

        return step

    if num_workers % mesh.devices.size != 0:
        raise ValueError(
            f"num_workers {num_workers} must be a multiple of mesh size "
            f"{mesh.devices.size}")

    def shard_body(theta, x, y, mask):
        # x: [N/d, cap, F] on this device; theta replicated.  Cast theta
        # to device-varying so the scan carry inside local_update has a
        # stable varying-axes type (psum below restores invariance).
        theta_v = jax.lax.pcast(theta, WORKER_AXIS, to="varying")
        deltas, losses = _vmapped_local_updates(theta_v, x, y, mask, task)
        with jax.named_scope("kps.bsp.reduce"):
            delta_sum = jax.lax.psum(deltas.sum(0), WORKER_AXIS)
            loss_sum = jax.lax.psum(losses.sum(), WORKER_AXIS)
        return apply(theta, delta_sum, loss_sum)

    sharded = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), P(WORKER_AXIS), P(WORKER_AXIS), P(WORKER_AXIS)),
        out_specs=(P(), P()))
    return jax.jit(sharded)


def make_bsp_multi_step(cfg: ModelConfig, num_workers: int, server_lr: float,
                        rounds: int, mesh: Mesh | None = None,
                        task=None) -> BspStep:
    """`rounds` BSP iterations as ONE device program (lax.scan over the
    fused step) — a single dispatch executes an entire training stretch,
    eliminating per-iteration host latency entirely.  This is the
    steady-state inner loop between buffer refreshes: with no new stream
    arrivals the reference's loop re-trains on the same buffer
    (WorkerTrainingProcessor.java:63-97), which is exactly a scan."""

    task = task or _default_task(cfg)

    def round_body(theta, x, onehot, mask, psum_axis: bool):
        # The scan carry stays axis-invariant: pcast a per-round copy to
        # device-varying for the local math, psum the delta back to
        # invariance.
        theta_local = (jax.lax.pcast(theta, WORKER_AXIS, to="varying")
                       if psum_axis else theta)
        deltas, losses = _vmapped_local_updates_onehot(
            theta_local, x, onehot, mask, task)
        with jax.named_scope("kps.bsp.reduce"):
            delta_sum, loss_sum = deltas.sum(0), losses.sum()
            if psum_axis:
                delta_sum = jax.lax.psum(delta_sum, WORKER_AXIS)
                loss_sum = jax.lax.psum(loss_sum, WORKER_AXIS)
        with jax.named_scope("kps.bsp.apply"):
            return theta + server_lr * delta_sum, loss_sum / num_workers

    def scanned(theta, x, y, mask, psum_axis):
        # labels are fixed across rounds: one-hot once, above the scan
        onehot = jax.nn.one_hot(y, cfg.num_rows, dtype=jnp.float32)

        def body(t, _):
            t2, loss = round_body(t, x, onehot, mask, psum_axis)
            return t2, loss
        return jax.lax.scan(body, theta, None, length=rounds)

    if mesh is None:
        return jax.jit(partial(scanned, psum_axis=False))

    if num_workers % mesh.devices.size != 0:
        raise ValueError(
            f"num_workers {num_workers} must be a multiple of mesh size "
            f"{mesh.devices.size}")

    def shard_body(theta, x, y, mask):
        return scanned(theta, x, y, mask, psum_axis=True)

    sharded = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), P(WORKER_AXIS), P(WORKER_AXIS), P(WORKER_AXIS)),
        out_specs=(P(), P()))
    return jax.jit(sharded)


def shard_worker_batches(mesh: Mesh, x, y, mask):
    """Place the stacked per-worker slabs [N, ...] sharded over the worker
    axis so host→device transfer happens once per device, not per worker."""
    return tuple(
        jax.device_put(a, NamedSharding(mesh, P(WORKER_AXIS)))
        for a in (x, y, mask))

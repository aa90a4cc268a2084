"""Range-sharded parameter server — the reference's latent KeyRange axis
(messages/KeyRange.java, carried by every message but always full-range,
ServerProcessor.java:198-208) made real, the TPU way.

Classic parameter-server deployments shard the key space across server
nodes; the reference kept that hook but ran a single server
(README.md:115-119).  Here the parameter vector is sharded over a
`params` mesh axis while workers stay data-parallel over a `workers`
axis (a 2-D mesh, parallel/mesh.worker_param_mesh):

    theta shard [P/ps] per device column
      └─ all_gather over params axis  → full theta (the "weights pull")
      └─ k-step local update on this device's buffer slab — logical
         workers are sharded over BOTH mesh axes, so every device
         computes (no redundant work on the param columns)
      └─ delta: psum over the full mesh, then each device keeps its own
         key range (axis_index slice — the "gradient push" lands
         pre-sharded, like a classic PS server group)
      └─ theta_shard += server_lr * delta_shard

The collectives ride ICI; per-device parameter memory drops by the
param-shard factor (the scaling story for models far bigger than LR —
this is the ZeRO/weight-sharded-DP pattern expressed in shard_map).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kafka_ps_tpu.parallel.mesh import PARAM_AXIS, WORKER_AXIS
from kafka_ps_tpu.utils.config import ModelConfig


def padded_num_params(layout, num_param_shards: int) -> int:
    """theta length padded so every param shard is equal-size (static
    shapes; the pad keys are dead weight ignored by unflatten).

    `layout` is anything exposing `.num_params` — a ModelConfig (the
    logreg flat layout) or an MLTask (models/task.py)."""
    p = layout.num_params
    return p + (-p) % num_param_shards


def pad_theta(theta, layout, num_param_shards: int):
    return jnp.pad(jnp.asarray(theta),
                   (0, padded_num_params(layout, num_param_shards)
                    - layout.num_params))


def shard_theta(mesh: Mesh, theta, layout):
    """Place the (padded) parameter vector range-sharded over the params
    axis, replicated over the workers axis."""
    num_param_shards = mesh.shape[PARAM_AXIS]
    return jax.device_put(pad_theta(theta, layout, num_param_shards),
                          NamedSharding(mesh, P(PARAM_AXIS)))


def shard_worker_batches(mesh: Mesh, x, y, mask):
    """Worker slabs sharded over BOTH mesh axes — every device hosts
    num_workers / (worker_shards * param_shards) logical workers."""
    return tuple(
        jax.device_put(a, NamedSharding(mesh, P((WORKER_AXIS, PARAM_AXIS))))
        for a in (x, y, mask))


# step(theta_padded, x, y, mask) -> (theta_padded', mean_loss)
RangeShardedStep = Callable[..., tuple[jax.Array, jax.Array]]


def make_range_sharded_step(cfg: ModelConfig, num_workers: int,
                            server_lr: float, mesh: Mesh,
                            rounds: int = 1, task=None) -> RangeShardedStep:
    """Fused BSP step(s) with range-sharded parameters on a 2-D
    (workers × params) mesh.  `rounds > 1` scans whole iterations into
    one device program, like bsp.make_bsp_multi_step."""
    if WORKER_AXIS not in mesh.shape or PARAM_AXIS not in mesh.shape:
        raise ValueError(
            f"mesh must have axes ({WORKER_AXIS!r}, {PARAM_AXIS!r}), "
            f"got {dict(mesh.shape)}")
    num_devices = mesh.shape[WORKER_AXIS] * mesh.shape[PARAM_AXIS]
    if num_workers % num_devices != 0:
        raise ValueError(
            f"num_workers {num_workers} must be a multiple of the mesh "
            f"size {num_devices} (workers are sharded over both axes)")
    if task is None:
        from kafka_ps_tpu.models.task import default_task
        task = default_task(cfg)
    n_real = task.num_params
    param_shards = mesh.shape[PARAM_AXIS]
    n_pad = padded_num_params(task, param_shards)
    shard_len = n_pad // param_shards

    def local_update_padded(theta_full, xx, yy, mm):
        delta, loss = task.local_update(theta_full[:n_real], xx, yy, mm)
        return jnp.pad(delta, (0, n_pad - n_real)), loss

    def round_body(theta_shard, x, y, mask):
        # weights pull: reassemble the full replica from the server shards
        theta_full = jax.lax.all_gather(theta_shard, PARAM_AXIS, axis=0,
                                        tiled=True)
        theta_full = jax.lax.pcast(theta_full, WORKER_AXIS, to="varying")
        deltas, losses = jax.vmap(
            lambda xx, yy, mm: local_update_padded(theta_full, xx, yy, mm)
        )(x, y, mask)
        # gradient push: global sum, then each server shard keeps only
        # its own key range
        delta = jax.lax.psum(deltas.sum(0), (WORKER_AXIS, PARAM_AXIS))
        delta_shard = jax.lax.dynamic_slice(
            delta, (jax.lax.axis_index(PARAM_AXIS) * shard_len,),
            (shard_len,))
        loss_sum = jax.lax.psum(losses.sum(), (WORKER_AXIS, PARAM_AXIS))
        return (theta_shard + server_lr * delta_shard,
                loss_sum / num_workers)

    def shard_body(theta_shard, x, y, mask):
        def body(t, _):
            return round_body(t, x, y, mask)
        theta, losses = jax.lax.scan(body, theta_shard, None, length=rounds)
        # scalar loss for the single-round step (API parity with
        # bsp.make_bsp_step); per-round losses when scanning
        return theta, (losses[0] if rounds == 1 else losses)

    data_spec = P((WORKER_AXIS, PARAM_AXIS))
    sharded = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(PARAM_AXIS), data_spec, data_spec, data_spec),
        out_specs=(P(PARAM_AXIS), P()))
    return jax.jit(sharded)


def assert_pad_clean(theta_padded, layout) -> None:
    """Pad-hygiene invariant: the pad keys appended by `pad_theta` are
    DEAD — `local_update_padded` zero-pads every delta, so nothing may
    ever land there.  A nonzero pad region means a delta leaked past
    `num_params` (a kernel writing out of its logical range, or a theta
    padded from a wrong layout) and the real parameters adjacent to the
    boundary can no longer be trusted.  unshard_theta would silently
    drop the evidence; this check turns the leak into an error at the
    unshard boundary (regression: tests/test_range_sharded.py)."""
    n = layout.num_params
    pad = np.asarray(theta_padded[n:])
    if pad.size and np.any(pad != 0):
        bad = int(np.flatnonzero(pad)[0])
        raise ValueError(
            f"delta leaked into the shard pad region: key {n + bad} "
            f"(pad begins at {n}, padded length {len(theta_padded)}) "
            f"holds {float(pad[bad])!r}, expected 0")


def unshard_theta(theta_padded, layout) -> np.ndarray:
    """Back to the host-side flat layout (drops the shard padding).
    `layout` as in padded_num_params.  Returns a WRITABLE copy — the
    server's message path mutates theta in place (runtime/server.py),
    and an asarray view of a JAX array is read-only.  Asserts the pad
    region it drops is clean (assert_pad_clean) — dropping a nonzero
    pad would hide a range leak."""
    assert_pad_clean(theta_padded, layout)
    return np.array(theta_padded[:layout.num_params])

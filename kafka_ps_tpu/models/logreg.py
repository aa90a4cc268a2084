"""Multinomial logistic regression — the framework's flagship model family.

TPU-native re-design of the reference's ml/LogisticRegressionTaskSpark.java:
instead of wrapping a JVM solver (Spark MLlib LBFGS, reference :179-184), the
whole "k local solver iterations on the buffer → emit weight delta" contract
(reference :179-220) is one jit'd XLA program: k full-batch gradient
steps.  Dead-simple dense math that XLA fuses onto the MXU — the
batch matmul (cap × F) @ (F × C+1) is the hot op.

Parameter layout (LogisticRegressionTaskSpark.java:98-104,122-140): a flat
float32 vector of (C+1)*F coefficients (row-major, one row per class 0..C)
followed by (C+1) intercepts — 6150 keys for F=1024, C=5.  Labels are
1..num_classes; class row 0 exists but is never observed, exactly like the
Spark model sized 0..maxLabel.  The flat view is the PS key-value contract
(BaseMessage.java:29-32); `KeyRange` slices of it stay meaningful.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from kafka_ps_tpu.utils.config import ModelConfig


class LogRegParams(NamedTuple):
    """Dense views over the flat parameter vector."""

    weights: jax.Array   # (C+1, F) coefficient matrix
    intercept: jax.Array  # (C+1,)

    @property
    def flat(self) -> jax.Array:
        return jnp.concatenate([self.weights.reshape(-1), self.intercept])


def init_params(cfg: ModelConfig, dtype=jnp.float32) -> LogRegParams:
    """Zero-initialized, like the reference (LogisticRegressionTaskSpark.java:98-104
    — zero despite the method name 'random')."""
    return LogRegParams(
        weights=jnp.zeros((cfg.num_rows, cfg.num_features), dtype),
        intercept=jnp.zeros((cfg.num_rows,), dtype),
    )


def unflatten(theta: jax.Array, cfg: ModelConfig) -> LogRegParams:
    """Flat 6150-key vector → (W, b) views. Inverse of `LogRegParams.flat`."""
    n_coef = cfg.num_rows * cfg.num_features
    return LogRegParams(
        weights=theta[:n_coef].reshape(cfg.num_rows, cfg.num_features),
        intercept=theta[n_coef:],
    )


def logits(params: LogRegParams, x: jax.Array) -> jax.Array:
    """(B, F) @ (F, C+1) + b — the MXU hot op."""
    return x @ params.weights.T + params.intercept


def loss_fn(params: LogRegParams, x: jax.Array, y: jax.Array,
            mask: jax.Array) -> jax.Array:
    """Masked mean softmax cross-entropy.

    `mask` is the buffer validity mask (invalid slots contribute 0) — the
    static-shape answer to the reference's dynamically-sized buffer.
    Matches Spark's mean log-loss objective (objectiveHistory,
    LogisticRegressionTaskSpark.java:188-189).
    """
    lg = logits(params, x)
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None].astype(jnp.int32), axis=-1)[:, 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    return (nll * mask).sum() / denom


def grad_loss(theta: jax.Array, x: jax.Array, y: jax.Array, mask: jax.Array,
              cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """Closed-form (gradient, loss) of the masked softmax-CE objective at
    a flat theta, the gradient flat too — see `grad_loss_onehot`."""
    onehot = jax.nn.one_hot(y, cfg.num_rows, dtype=jnp.float32)
    grad, loss = grad_loss_onehot(unflatten(theta, cfg), x, onehot, mask)
    return grad.flat, loss


def grad_loss_onehot(params: LogRegParams, x: jax.Array, onehot: jax.Array,
                     mask: jax.Array) -> tuple[LogRegParams, jax.Array]:
    """Closed-form (gradient leaves, loss) with the label one-hot
    precomputed — callers running many solver steps on a fixed batch
    (lax.scan in `fit` and the fused multi-round BSP step) hoist the
    one-hot out of the loop.

    Written explicitly (G = (softmax − onehot)·mask/n; ∇W = Gᵀ·x — two
    MXU matmuls) rather than via `jax.grad` so the same code is safe
    inside `shard_map` bodies: under shard_map's replication rule, AD
    cotangents of replicated operands are auto-psum'd across the mesh,
    which would silently turn a per-worker gradient into the global sum
    (see tests/test_parallel.py::test_explicit_grad_matches_autodiff).
    """
    lg = logits(params, x)
    logp = jax.nn.log_softmax(lg, axis=-1)
    denom = jnp.maximum(mask.sum(), 1.0)
    nll = -(logp * onehot).sum(axis=-1)
    loss = (nll * mask).sum() / denom
    g = (jnp.exp(logp) - onehot) * (mask / denom)[:, None]   # [B, C+1]
    return LogRegParams(weights=g.T @ x, intercept=g.sum(axis=0)), loss


def fit(params: LogRegParams, x: jax.Array, onehot: jax.Array,
        mask: jax.Array, *, cfg: ModelConfig
        ) -> tuple[LogRegParams, jax.Array]:
    """cfg.num_max_iter local optimizer iterations on the buffer, on the
    leaves → (new leaves, loss at them).

    The reference's "gradient" is a k-step local-solver delta
    (newWeights − oldWeights after maxIter=2 LBFGS steps,
    LogisticRegressionTaskSpark.java:179-220) — local-SGD/FedAvg-style.
    We implement k full-batch gradient-descent steps in one fused XLA
    program, by the loop the classifier families share (models/task.py
    `local_steps`: the first step reads `params` as handed in, shared
    by every worker under a BSP `vmap`, and a worker's own copy of the
    leaves first exists as that step's result; the steps after it are a
    `lax.scan`); the capability
    ("k local solver steps, delta exchanged") is what is matched, not
    Spark's line-search trajectory (documented divergence, SURVEY §7).
    The `kps.fit.*` scopes are models/mlp.py's: metadata a device trace
    splits the time by."""
    # imported here: models/task.py imports this module for its default
    # family
    from kafka_ps_tpu.models.task import local_steps
    lr = cfg.local_learning_rate

    def step(p):
        with jax.named_scope("kps.fit.grad"):
            g, _ = grad_loss_onehot(p, x, onehot, mask)
        with jax.named_scope("kps.fit.param_step"):
            return jax.tree.map(lambda a, b: a - lr * b, p, g)

    def loss(p):
        with jax.named_scope("kps.fit.loss"):
            return grad_loss_onehot(p, x, onehot, mask)[1]

    return local_steps(step, loss, params, cfg.num_max_iter)


def sparse_to_dense(rows: list[dict[int, float]], num_features: int) -> np.ndarray:
    """Sparse feature maps (LabeledData.inputData, reference
    messages/LabeledData.java:14-28) → dense batch for the MXU."""
    out = np.zeros((len(rows), num_features), dtype=np.float32)
    for i, r in enumerate(rows):
        for k, v in r.items():
            out[i, int(k)] = v
    return out

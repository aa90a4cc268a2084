"""A second model family: one-hidden-layer MLP classifier.

Proves the PS runtime is model-agnostic (the reference hardwires its
single LR task, ml/LogisticRegressionTaskSpark.java — but its processor
layer only touches the task surface, so a faithful framework must
accept any task honoring the same contract, models/task.py): the
parameters' leaves `MLPParams`, a k-step local solver `fit` that
carries them, test metrics from them, and `unflatten` / `flatten`
between the leaves and the flat vector addressed by KeyRange keys.

Layout (flat, contiguous — the PS key space):
    W1 [H, F] | b1 [H] | W2 [C+1, H] | b2 [C+1]

The flat vector exists only where a program begins and ends.  Carried
through the local solver under the worker `vmap` it is `[workers, P]`,
which a TPU tiles over (worker, key): every step then cut W1 out of it
and re-laid it out as a matrix, and re-laid the gradient out to be
concatenated back — three 1 GB copies a step at 64 workers x H=4096,
a third of the solver's device time (PERF.md §6, PR 25).

A worker's own copy of the leaves comes into being as the result of its
first local step, not before it: `fit` takes that step from the leaves
as they were handed in, which every worker of a BSP clock shares, so
its forward is one product over all workers' rows and no
`[workers, H, F]` array is written for it to read (models/task.py
`local_steps`; PERF.md §6, PR 30).

Gradients come from `jax.grad`: safe here because every caller
(parallel/bsp.py, parallel/range_sharded.py) marks the parameters
device-varying with `pcast(..., to="varying")` before differentiating
inside shard_map, so no replicated cotangent psums are inserted (the
hazard logreg.grad_loss_onehot documents).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from kafka_ps_tpu.models import metrics as metrics_mod
from kafka_ps_tpu.models import task as task_mod
from kafka_ps_tpu.utils.config import ModelConfig


class MLPParams(NamedTuple):
    w1: jax.Array    # [H, F]
    b1: jax.Array    # [H]
    w2: jax.Array    # [C+1, H]
    b2: jax.Array    # [C+1]


def num_params(cfg: ModelConfig) -> int:
    h, f, c = cfg.hidden_dim, cfg.num_features, cfg.num_rows
    return h * f + h + c * h + c


def unflatten(theta: jax.Array, cfg: ModelConfig) -> MLPParams:
    h, f, c = cfg.hidden_dim, cfg.num_features, cfg.num_rows
    o1 = h * f
    o2 = o1 + h
    o3 = o2 + c * h
    return MLPParams(
        w1=theta[:o1].reshape(h, f),
        b1=theta[o1:o2],
        w2=theta[o2:o3].reshape(c, h),
        b2=theta[o3:])


def flatten(p: MLPParams) -> jax.Array:
    return jnp.concatenate([p.w1.reshape(-1), p.b1,
                            p.w2.reshape(-1), p.b2])


def logits(params: MLPParams, x: jax.Array) -> jax.Array:
    hidden = jax.nn.relu(x @ params.w1.T + params.b1)
    return hidden @ params.w2.T + params.b2


def loss_onehot(params: MLPParams, x, onehot, mask):
    logp = jax.nn.log_softmax(logits(params, x), axis=-1)
    nll = -(logp * onehot).sum(axis=-1)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


class MLPTask(task_mod.RowsWithClassLabel):
    """MLTask implementation (models/task.py protocol)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    @property
    def num_params(self) -> int:
        return num_params(self.cfg)

    def init_params(self) -> jax.Array:
        """He-initialized hidden layer (an all-zeros MLP has zero
        gradient); deterministic from cfg.  The reference zero-inits its
        LR (LogisticRegressionTaskSpark.java:98-104) — convexity makes
        that fine there, not here."""
        cfg = self.cfg
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        w1 = jax.random.normal(k1, (cfg.hidden_dim, cfg.num_features),
                               jnp.float32)
        w1 = w1 * jnp.sqrt(2.0 / cfg.num_features)
        w2 = jax.random.normal(k2, (cfg.num_rows, cfg.hidden_dim),
                               jnp.float32)
        w2 = w2 * jnp.sqrt(2.0 / cfg.hidden_dim)
        return flatten(MLPParams(
            w1=w1, b1=jnp.zeros(cfg.hidden_dim),
            w2=w2, b2=jnp.zeros(cfg.num_rows)))

    def unflatten(self, theta) -> MLPParams:
        return unflatten(theta, self.cfg)

    def flatten(self, leaves: MLPParams) -> jax.Array:
        return flatten(leaves)

    def fit(self, leaves, x, onehot, mask):
        return fit(leaves, x, onehot, mask, cfg=self.cfg)

    def evaluate_leaves(self, leaves, x_test, y_test) -> metrics_mod.Metrics:
        return evaluate_leaves(leaves, x_test, y_test, cfg=self.cfg)

    def logits(self, leaves, x):
        """(B, F) → (B, C+1) class scores."""
        return logits(leaves, x)


def fit(params: MLPParams, x, onehot, mask, *, cfg: ModelConfig):
    """cfg.num_max_iter full-batch steps on the leaves → (new leaves,
    loss at them), by the loop every classifier family shares
    (models/task.py `local_steps`): the first step reads `params` as
    handed in — shared by every worker under the fused round's `vmap`,
    so one product serves them all — and a worker's own copy of the
    leaves first exists as that step's result.  The `kps.fit.*` scopes
    name each part in the operations' metadata, so a device trace
    splits the solver's time by them (metadata only)."""
    lr = cfg.local_learning_rate
    grad = jax.grad(loss_onehot)

    def step(p):
        with jax.named_scope("kps.fit.grad"):
            g = grad(p, x, onehot, mask)
        with jax.named_scope("kps.fit.param_step"):
            return jax.tree.map(lambda a, b: a - lr * b, p, g)

    def loss(p):
        with jax.named_scope("kps.fit.loss"):
            return loss_onehot(p, x, onehot, mask)

    return task_mod.local_steps(step, loss, params, cfg.num_max_iter)


def evaluate_leaves(params: MLPParams, x_test, y_test, *, cfg: ModelConfig):
    with jax.named_scope("kps.eval"):
        lg = logits(params, x_test)
        preds = jnp.argmax(lg, axis=-1)
        onehot = jax.nn.one_hot(y_test, cfg.num_rows, dtype=jnp.float32)
        loss = loss_onehot(params, x_test, onehot,
                           jnp.ones(x_test.shape[0]))
        f1, acc = metrics_mod.weighted_f1_accuracy(preds, y_test,
                                                   cfg.num_rows)
        return metrics_mod.Metrics(f1=f1, accuracy=acc, loss=loss)

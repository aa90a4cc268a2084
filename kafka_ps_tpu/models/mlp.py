"""A second model family: one-hidden-layer MLP classifier.

Proves the PS runtime is model-agnostic (the reference hardwires its
single LR task, ml/LogisticRegressionTaskSpark.java — but its processor
layer only touches the task surface, so a faithful framework must
accept any task honoring the same contract): a flat parameter vector
addressed by KeyRange keys, a k-step local solver returning a delta,
and test metrics.

Layout (flat, contiguous — the PS key space):
    W1 [H, F] | b1 [H] | W2 [C+1, H] | b2 [C+1]

Gradients come from `jax.grad`: safe here because every caller
(parallel/bsp.py, parallel/range_sharded.py) marks theta device-varying
with `pcast(..., to="varying")` before differentiating inside shard_map, so no replicated
cotangent psums are inserted (the hazard logreg.grad_loss documents).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from kafka_ps_tpu.compress.slab import decode_x
from kafka_ps_tpu.models import metrics as metrics_mod
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


def _loss_onehot(theta, x, onehot, mask, cfg: ModelConfig):
    lg = logits(unflatten(theta, cfg), x)
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -(logp * onehot).sum(axis=-1)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


class MLPTask:
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

    def local_update_onehot(self, theta, x, onehot, mask):
        return _local_update_onehot(theta, x, onehot, mask, cfg=self.cfg)

    def local_update(self, theta, x, y, mask):
        # slab-storage decode (f32 identity) fuses into the jit below
        x = decode_x(x)
        onehot = jax.nn.one_hot(y, self.cfg.num_rows, dtype=jnp.float32)
        return self.local_update_onehot(theta, x, onehot, mask)

    def evaluate(self, theta, x_test, y_test) -> metrics_mod.Metrics:
        return _evaluate(theta, x_test, y_test, cfg=self.cfg)

    def evaluate_batch(self, thetas, x_test, y_test) -> metrics_mod.Metrics:
        """Stacked eval over (k, P) thetas — see LogRegTask.evaluate_batch
        (the async eval engine's coalesced dispatch)."""
        return jax.vmap(
            lambda t: self.evaluate(t, x_test, y_test))(thetas)

    def predict_logits(self, theta, x):
        """(B, F) → (B, C) class scores — the serving plane's forward
        pass (kafka_ps_tpu/serving/engine.py)."""
        return logits(unflatten(theta, self.cfg), x)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _local_update_onehot(theta, x, onehot, mask, *, cfg: ModelConfig):
    """Jitted like logreg.local_update so the per-node worker hot path
    runs one cached XLA program per iteration (re-jitting inside an
    enclosing jit — the fused BSP steps — is free: it inlines).  The
    `kps.fit.*` scopes name each part in the operations' metadata, so a
    device trace splits the solver's time by them (metadata only)."""
    lr = cfg.local_learning_rate
    grad = jax.grad(_loss_onehot)

    def step(t, _):
        with jax.named_scope("kps.fit.grad"):
            g = grad(t, x, onehot, mask, cfg)
        with jax.named_scope("kps.fit.param_step"):
            return t - lr * g, None

    theta_new, _ = jax.lax.scan(step, theta, None, length=cfg.num_max_iter)
    with jax.named_scope("kps.fit.loss"):
        final_loss = _loss_onehot(theta_new, x, onehot, mask, cfg)
    with jax.named_scope("kps.fit.delta"):
        return theta_new - theta, final_loss


@functools.partial(jax.jit, static_argnames=("cfg",))
def _evaluate(theta, x_test, y_test, *, cfg: ModelConfig):
    with jax.named_scope("kps.eval"):
        params = unflatten(theta, cfg)
        lg = logits(params, x_test)
        preds = jnp.argmax(lg, axis=-1)
        onehot = jax.nn.one_hot(y_test, cfg.num_rows, dtype=jnp.float32)
        loss = _loss_onehot(theta, x_test, onehot,
                            jnp.ones(x_test.shape[0]), cfg)
        f1, acc = metrics_mod.weighted_f1_accuracy(preds, y_test,
                                                   cfg.num_rows)
        return metrics_mod.Metrics(f1=f1, accuracy=acc, loss=loss)

"""Online test-set evaluation — jit'd weighted F1 + accuracy.

Replaces the reference's ml/Metrics.java (Spark
MulticlassClassificationEvaluator over (prediction, label) rows,
Metrics.java:15-24) and the per-iteration full-test-set predict
(LogisticRegressionTaskSpark.java:236-251).  Spark's "f1" metric is the
support-weighted mean of per-class F1; "accuracy" is plain accuracy — both
reproduced here from a confusion matrix built with one-hot matmuls so the
whole evaluation is a single fused XLA program on device.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from kafka_ps_tpu.models.logreg import logits, loss_fn
from kafka_ps_tpu.utils.config import ModelConfig


class Metrics(NamedTuple):
    f1: jax.Array        # support-weighted F1 (Spark evaluator default)
    accuracy: jax.Array
    loss: jax.Array      # mean CE on the test set


def confusion_matrix(preds: jax.Array, labels: jax.Array, n: int) -> jax.Array:
    """(n, n) counts[true, pred] via one-hot outer products (MXU-friendly)."""
    p = jax.nn.one_hot(preds, n, dtype=jnp.float32)
    t = jax.nn.one_hot(labels, n, dtype=jnp.float32)
    return t.T @ p


def weighted_f1_accuracy(preds: jax.Array, labels: jax.Array, n: int):
    cm = confusion_matrix(preds, labels, n)
    tp = jnp.diagonal(cm)
    support = cm.sum(axis=1)         # rows: true counts
    predicted = cm.sum(axis=0)       # cols: predicted counts
    precision = tp / jnp.maximum(predicted, 1.0)
    recall = tp / jnp.maximum(support, 1.0)
    f1 = 2 * precision * recall / jnp.maximum(precision + recall, 1e-12)
    weighted_f1 = (f1 * support).sum() / jnp.maximum(support.sum(), 1.0)
    accuracy = tp.sum() / jnp.maximum(support.sum(), 1.0)
    return weighted_f1, accuracy


def weighted_f1_accuracy_by_class(preds: jax.Array, labels: jax.Array, n: int):
    """`weighted_f1_accuracy` from per-class counts alone (true
    positives, support, predicted), for label spaces where an (n, n)
    confusion matrix is out of the question: a vocabulary."""
    ones = jnp.ones(labels.shape, jnp.float32)
    support = jnp.zeros((n,), jnp.float32).at[labels].add(ones)
    predicted = jnp.zeros((n,), jnp.float32).at[preds].add(ones)
    tp = jnp.zeros((n,), jnp.float32).at[labels].add(
        (preds == labels).astype(jnp.float32))
    precision = tp / jnp.maximum(predicted, 1.0)
    recall = tp / jnp.maximum(support, 1.0)
    f1 = 2 * precision * recall / jnp.maximum(precision + recall, 1e-12)
    total = jnp.maximum(support.sum(), 1.0)
    return (f1 * support).sum() / total, tp.sum() / total


def evaluate_leaves(params, x_test: jax.Array, y_test: jax.Array,
                    *, cfg: ModelConfig) -> Metrics:
    """Full-test-set metrics, same cadence as the reference (every server
    iteration on worker 0's update, ServerProcessor.java:153-165)."""
    with jax.named_scope("kps.eval"):
        preds = jnp.argmax(logits(params, x_test), axis=-1)
        loss = loss_fn(params, x_test, y_test, jnp.ones(x_test.shape[0]))
        f1, acc = weighted_f1_accuracy(preds, y_test, cfg.num_rows)
        return Metrics(f1=f1, accuracy=acc, loss=loss)

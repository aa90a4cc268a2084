"""Offline ground-truth oracle — port of
evaluation/python-ground-truth-algorithm.ipynb (cells 4-7).

The reference trains an offline model (datawig SimpleImputer) on the full
training CSV and compares it to the streaming system via sklearn's
classification_report (README.md:221-233: weighted F1 0.47 on
fine-food-reviews).  Here the oracle is the same multinomial LR the
streaming system trains, fitted full-batch to convergence with the jit'd
loss/grad from models/logreg — answering "is the distributed system
learning correctly" with the identical hypothesis class.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from kafka_ps_tpu.models import logreg
from kafka_ps_tpu.models.task import default_task
from kafka_ps_tpu.utils.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class GroundTruth:
    theta: np.ndarray
    f1: float
    accuracy: float
    loss: float
    report: str          # sklearn classification_report text


# Trace counter for the PS101 regression test (tests/test_evaluation.py):
# the body runs only when XLA traces, so repeated same-shape calls must
# leave it unchanged.
_fit_traces = 0


@functools.partial(jax.jit, static_argnames=("cfg", "steps"))
def _fit(theta0, x, y, mask, learning_rate, cfg, steps):
    global _fit_traces
    _fit_traces += 1

    def step(theta, _):
        g, _loss = logreg.grad_loss(theta, x, y, mask, cfg)
        return theta - learning_rate * g, None

    theta, _ = jax.lax.scan(step, theta0, None, length=steps)
    return theta


def train_offline(train_x: np.ndarray, train_y: np.ndarray,
                  cfg: ModelConfig, *, steps: int = 500,
                  learning_rate: float = 0.5) -> np.ndarray:
    """Full-batch gradient descent to (near-)convergence.  The whole
    optimization is one lax.scan under jit — a single XLA program.

    The program is the module-level `_fit` (cached by jit per shape and
    per static (cfg, steps)): the original closed over the data with a
    fresh `@jax.jit def fit` per call, which re-traced and re-compiled
    the whole scan on EVERY oracle evaluation — pscheck PS101."""
    x = jnp.asarray(train_x, jnp.float32)
    y = jnp.asarray(train_y, jnp.int32)
    mask = jnp.ones((x.shape[0],), jnp.float32)
    theta = _fit(jnp.zeros((cfg.num_params,), jnp.float32), x, y, mask,
                 learning_rate, cfg, steps)
    return np.asarray(jax.block_until_ready(theta))


def classification_report_text(theta: np.ndarray, test_x: np.ndarray,
                               test_y: np.ndarray, cfg: ModelConfig) -> str:
    from sklearn.metrics import classification_report
    params = logreg.unflatten(jnp.asarray(theta), cfg)
    preds = np.asarray(jnp.argmax(logreg.logits(params, jnp.asarray(
        test_x, jnp.float32)), axis=-1))
    return classification_report(test_y, preds, zero_division=0)


def compute(train_x: np.ndarray, train_y: np.ndarray,
            test_x: np.ndarray, test_y: np.ndarray,
            cfg: ModelConfig | None = None, *, steps: int = 500,
            learning_rate: float = 0.5) -> GroundTruth:
    cfg = cfg or ModelConfig()
    theta = train_offline(train_x, train_y, cfg, steps=steps,
                          learning_rate=learning_rate)
    m = default_task(cfg).evaluate(jnp.asarray(theta), jnp.asarray(test_x),
                                   jnp.asarray(test_y))
    return GroundTruth(
        theta=theta,
        f1=float(m.f1),
        accuracy=float(m.accuracy),
        loss=float(m.loss),
        report=classification_report_text(theta, test_x, test_y, cfg),
    )

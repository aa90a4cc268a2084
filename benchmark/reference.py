"""The plain reference: what one clock of the parameter server means,
in float32 `jax.numpy` at `highest` matmul precision, and the
comparison that decides `correct`.

Imports nothing from `kafka_ps_tpu` and takes nothing the program has
made except what is being judged (its parameter vectors and log rows).

Semantics (upstream: WorkerTrainingProcessor / ServerProcessor, BSP):
  * a worker runs k full-batch gradient-descent steps of size lr on the
    masked mean softmax cross-entropy of its slab, starting from the
    shared parameters; its delta is new - old and its logged loss is
    the loss at the new parameters;
  * the server adds (1/W) * sum of the W deltas; every worker is then
    one clock on;
  * evaluation is mean cross-entropy, support-weighted F1 and accuracy
    of argmax predictions on the test set.

Models: `logreg` (C1 x F weights | C1 intercepts, zero-initialised) and
`mlp` (H x F | H | C1 x H | C1, He-normal from PRNGKey(0), relu), both
as one flat float32 vector in that order.  C1 = num_classes + 1: labels
are 1..num_classes and row 0 is never observed.

The work runs one worker at a time, so the reference's own footprint
stays far below the program's and `memory_peak_bytes` stays the
program's.

This is the family that a configuration without a `family` key gets;
benchmark/run.py's docstring has the interface it calls.
"""

from __future__ import annotations

import dataclasses
import statistics

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"
# the column of the program's server log that each evaluated number is
# judged against (the header the program's CSV sink writes)
LOG_COLUMN = {"loss": "loss", "f1": "fMeasure", "accuracy": "accuracy"}


@dataclasses.dataclass(frozen=True)
class Shapes:
    task: str
    num_features: int
    num_classes: int
    hidden_dim: int
    local_iterations: int
    local_lr: float
    num_workers: int

    @property
    def c1(self) -> int:
        return self.num_classes + 1

    def leaves(self) -> list[tuple[str, tuple[int, ...]]]:
        f, h, c = self.num_features, self.hidden_dim, self.c1
        if self.task == "mlp":
            return [("w1", (h, f)), ("b1", (h,)), ("w2", (c, h)),
                    ("b2", (c,))]
        if self.task == "logreg":
            return [("weights", (c, f)), ("intercept", (c,))]
        raise KeyError(f"no reference for task {self.task!r}")

    @property
    def num_params(self) -> int:
        return sum(int(np.prod(s)) for _, s in self.leaves())


def shapes(cfg) -> Shapes:
    """The reference's view of the CLI's configuration."""
    return Shapes(
        task=cfg.task, num_features=cfg.model.num_features,
        num_classes=cfg.model.num_classes, hidden_dim=cfg.model.hidden_dim,
        local_iterations=cfg.model.num_max_iter,
        local_lr=cfg.model.local_learning_rate, num_workers=cfg.num_workers)


def split(theta, shapes: Shapes) -> dict:
    out, at = {}, 0
    for name, shape in shapes.leaves():
        n = int(np.prod(shape))
        out[name] = theta[at:at + n].reshape(shape)
        at += n
    return out


def init_params(shapes: Shapes):
    """The deployment's stated start: zeros for logreg; for the MLP
    He-normal hidden and output weights drawn from PRNGKey(0) split in
    two, zero biases."""
    if shapes.task == "logreg":
        return jnp.zeros((shapes.num_params,), jnp.float32)
    f, h, c = shapes.num_features, shapes.hidden_dim, shapes.c1
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    w1 = jax.random.normal(k1, (h, f), jnp.float32) * jnp.sqrt(2.0 / f)
    w2 = jax.random.normal(k2, (c, h), jnp.float32) * jnp.sqrt(2.0 / h)
    return jnp.concatenate([w1.reshape(-1), jnp.zeros((h,), jnp.float32),
                            w2.reshape(-1), jnp.zeros((c,), jnp.float32)])


def _logits(theta, x, shapes: Shapes):
    p = split(theta, shapes)
    if shapes.task == "mlp":
        hidden = jnp.maximum(x @ p["w1"].T + p["b1"], 0.0)
        return hidden @ p["w2"].T + p["b2"]
    return x @ p["weights"].T + p["intercept"]


def _loss(theta, x, y, mask, shapes: Shapes):
    logp = jax.nn.log_softmax(_logits(theta, x, shapes), axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def _local_update(theta, x, y, mask, shapes: Shapes):
    """k gradient steps -> (delta, loss at the new parameters)."""
    t = theta
    for _ in range(shapes.local_iterations):
        t = t - shapes.local_lr * jax.grad(_loss)(t, x, y, mask, shapes)
    return t - theta, _loss(t, x, y, mask, shapes)


def _held_in(dtype):
    if dtype is None:
        return lambda a: a
    return lambda a: a.astype(dtype).astype(jnp.float32)


class Reference:
    """Jitted once per cell; every call under `highest` precision.

    `theta_dtype` / `slab_dtype` exist for the CONTROL only
    (`CONTROLS`, benchmark/control.py): the same reference with the
    shared parameters, or the worker slabs, held in a lower precision
    between uses — the step a later PR would be tempted by.  All
    arithmetic stays float32 either way."""

    def __init__(self, shapes: Shapes, theta_dtype=None, slab_dtype=None):
        self.shapes = shapes
        self._store = _held_in(theta_dtype)
        self._slab = _held_in(slab_dtype)
        self._update = jax.jit(
            lambda th, x, y, m: _local_update(th, self._slab(x), y, m,
                                              shapes))
        self._evaluate = jax.jit(lambda th, x, y: _evaluate(th, x, y, shapes))

    def clock(self, theta, slabs):
        """One BSP clock over every worker's (x, y, mask) slab:
        (new theta, mean of the workers' losses)."""
        w = len(slabs)
        with jax.default_matmul_precision(PRECISION):
            total, losses = jnp.zeros_like(theta), []
            for x, y, mask in slabs:
                delta, loss = self._update(theta, jnp.asarray(x),
                                           jnp.asarray(y), jnp.asarray(mask))
                # wait for each worker: the dispatch queue would
                # otherwise run ahead and hold every worker's buffers
                total = jax.block_until_ready(total + delta)
                losses.append(loss)
            theta = self._store(theta + total / w)
        return theta, float(np.mean([float(v) for v in losses]))

    def run(self, theta0, slabs, clocks: int, keep_every: int = 1):
        """`clocks` BSP clocks from theta0: ([theta after every
        `keep_every`-th clock] as host arrays, [mean loss of each
        clock])."""
        theta = self._store(jnp.asarray(theta0, jnp.float32))
        thetas, losses = [], []
        for done in range(1, clocks + 1):
            theta, loss = self.clock(theta, slabs)
            if done % keep_every == 0:
                thetas.append(np.asarray(theta))
            losses.append(loss)
        return thetas, losses

    def evaluate(self, theta, test) -> dict:
        """The test set (rows, labels) under `theta`, by LOG_COLUMN's
        names."""
        with jax.default_matmul_precision(PRECISION):
            loss, f1, acc = self._evaluate(jnp.asarray(theta, jnp.float32),
                                           jnp.asarray(test[0]),
                                           jnp.asarray(test[1]))
        return {"loss": float(loss), "f1": float(f1), "accuracy": float(acc)}


def _evaluate(theta, x, y, shapes: Shapes):
    n = shapes.c1
    logits = _logits(theta, x, shapes)
    loss = _loss(theta, x, y, jnp.ones((x.shape[0],), jnp.float32), shapes)
    preds = jnp.argmax(logits, axis=-1)
    cm = jnp.zeros((n, n), jnp.float32).at[y, preds].add(1.0)
    tp = jnp.diagonal(cm)
    support, predicted = cm.sum(axis=1), cm.sum(axis=0)
    precision = tp / jnp.maximum(predicted, 1.0)
    recall = tp / jnp.maximum(support, 1.0)
    f1 = 2 * precision * recall / jnp.maximum(precision + recall, 1e-12)
    total = jnp.maximum(support.sum(), 1.0)
    return loss, (f1 * support).sum() / total, tp.sum() / total


# -- the comparison ----------------------------------------------------------


def param_gap(theta_prog, theta_ref, theta0, shapes: Shapes) -> float:
    """Worst leaf of | ||prog change|| - ||ref change|| | over the
    reference's norm of that leaf's change or of the median leaf's,
    whichever is larger (some leaves hardly move)."""
    dp = split(np.asarray(theta_prog, np.float64)
               - np.asarray(theta0, np.float64), shapes)
    dr = split(np.asarray(theta_ref, np.float64)
               - np.asarray(theta0, np.float64), shapes)
    ref_norms = {k: float(np.linalg.norm(v)) for k, v in dr.items()}
    floor = statistics.median(ref_norms.values())
    worst = 0.0
    for name, ref in ref_norms.items():
        gap = abs(float(np.linalg.norm(dp[name])) - ref)
        worst = max(worst, gap / max(ref, floor, 1e-30))
    return worst


# the controls of benchmark/control.py: Reference keywords by name.
#   theta_bf16  the shared parameters held in bfloat16 between clocks
#               (what halving the 16.9 MB broadcast and delta would do):
#               the control that has to come out as not correct;
#   slab_bf16   the worker slabs held in bfloat16 (`--slab-dtype bf16`):
#               recorded to show what the comparison can NOT see.  On
#               the chip the program's matrix products already round the
#               slab to bfloat16 (default precision), so against a
#               `highest` reference this control and the sound program
#               read alike.
CONTROLS = {"theta_bf16": {"theta_dtype": jnp.bfloat16},
            "slab_bf16": {"slab_dtype": jnp.bfloat16}}

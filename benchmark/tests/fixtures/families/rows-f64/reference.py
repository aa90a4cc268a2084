"""The fixture family's plain reference: softmax regression under BSP
in numpy float64, one worker at a time; imports nothing of the program.

Parameters: one flat vector, the [C, F] weights then the C intercepts,
C = num_classes + 1 (labels are 1..num_classes), zero at the start.  A
worker takes k full-batch gradient steps of size lr on the masked mean
cross-entropy of its slab; its delta is new - old, its loss the loss at
the new parameters; the server adds the mean of the deltas.  It
evaluates the mean cross-entropy and the share of test rows whose
largest logit is the label's (`hit_rate`, the log's `accuracy`).
"""

import dataclasses

import numpy as np

LOG_COLUMN = {"loss": "loss", "hit_rate": "accuracy"}
CONTROLS = {"theta_f16": {"theta_dtype": np.float16}}


@dataclasses.dataclass(frozen=True)
class Shapes:
    features: int
    rows: int            # class rows of the weights
    steps: int
    lr: float
    workers: int


def shapes(cfg):
    return Shapes(cfg.model.num_features, cfg.model.num_classes + 1,
                  cfg.model.num_max_iter, cfg.model.local_learning_rate,
                  cfg.num_workers)


def init_params(shapes):
    return np.zeros(shapes.rows * (shapes.features + 1), np.float32)


def _parts(theta, shapes):
    cut = shapes.rows * shapes.features
    return theta[:cut].reshape(shapes.rows, shapes.features), theta[cut:]


def _log_softmax(theta, x, shapes):
    w, b = _parts(theta, shapes)
    z = x @ w.T + b
    z = z - z.max(axis=1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=1, keepdims=True))


def _loss(theta, x, y, mask, shapes):
    picked = _log_softmax(theta, x, shapes)[np.arange(len(y)), y]
    return float(-(picked * mask).sum() / max(mask.sum(), 1.0))


def _grad(theta, x, y, mask, shapes):
    p = np.exp(_log_softmax(theta, x, shapes))
    p[np.arange(len(y)), y] -= 1.0
    p *= (mask / max(mask.sum(), 1.0))[:, None]
    return np.concatenate([(p.T @ x).reshape(-1), p.sum(axis=0)])


class Reference:
    def __init__(self, shapes, theta_dtype=None):
        self.shapes = shapes
        self._store = ((lambda t: t) if theta_dtype is None else
                       (lambda t: t.astype(theta_dtype).astype(np.float64)))

    def run(self, theta0, slabs, clocks, keep_every=1):
        s = self.shapes
        theta = self._store(np.asarray(theta0, np.float64))
        kept, losses = [], []
        for done in range(1, clocks + 1):
            total, seen = np.zeros_like(theta), []
            for x, y, mask in slabs:
                x, mask = np.asarray(x, np.float64), np.asarray(mask,
                                                                np.float64)
                t = theta
                for _ in range(s.steps):
                    t = t - s.lr * _grad(t, x, y, mask, s)
                total += t - theta
                seen.append(_loss(t, x, y, mask, s))
            theta = self._store(theta + total / len(slabs))
            losses.append(float(np.mean(seen)))
            if done % keep_every == 0:
                kept.append(theta.copy())
        return kept, losses

    def evaluate(self, theta, test):
        x, y = np.asarray(test[0], np.float64), np.asarray(test[1])
        theta = np.asarray(theta, np.float64)
        best = _log_softmax(theta, x, self.shapes).argmax(axis=1)
        return {"loss": _loss(theta, x, y, np.ones(len(y)), self.shapes),
                "hit_rate": float((best == y).mean())}


def param_gap(theta_prog, theta_ref, theta0, shapes):
    """Worst of the two leaves: the gap between the norms of the
    program's and the reference's change, over the reference's."""
    worst = 0.0
    start = np.asarray(theta0, np.float64)
    for prog, ref in zip(_parts(np.asarray(theta_prog, np.float64) - start,
                                shapes),
                         _parts(np.asarray(theta_ref, np.float64) - start,
                                shapes)):
        want = float(np.linalg.norm(ref))
        worst = max(worst, abs(float(np.linalg.norm(prog)) - want)
                    / max(want, 1e-30))
    return worst

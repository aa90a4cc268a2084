"""Model/metrics unit tests — parameter layout, loss/grad correctness vs
closed-form numpy, k-step local-update semantics, metric parity with sklearn
definitions (support-weighted F1, accuracy)."""

import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.models import logreg, metrics
from kafka_ps_tpu.models.task import LogRegTask
from kafka_ps_tpu.utils.config import ModelConfig

CFG = ModelConfig(num_features=16, num_classes=3, local_learning_rate=0.5)  # 4*16+4 = 68 params
CFG_LR01 = ModelConfig(num_features=16, num_classes=3, local_learning_rate=0.1)


def _rand_batch(n=32, cfg=CFG, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, cfg.num_features)).astype(np.float32)
    y = rng.integers(1, cfg.num_classes + 1, size=n).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def test_param_layout_6150():
    """Reference layout: (C+1)*F + (C+1) flat keys — 6150 for default shape
    (LogisticRegressionTaskSpark.java:98-104)."""
    cfg = ModelConfig()
    assert cfg.num_params == 6150
    p = logreg.init_params(cfg)
    assert p.flat.shape == (6150,)
    assert float(jnp.abs(p.flat).sum()) == 0.0  # zero-init like reference


def test_flatten_roundtrip():
    theta = jnp.arange(CFG.num_params, dtype=jnp.float32)
    p = logreg.unflatten(theta, CFG)
    assert p.weights.shape == (CFG.num_rows, CFG.num_features)
    np.testing.assert_array_equal(np.asarray(p.flat), np.asarray(theta))


def test_loss_matches_numpy():
    x, y = _rand_batch()
    rng = np.random.default_rng(1)
    theta = jnp.asarray(rng.normal(size=CFG.num_params).astype(np.float32))
    p = logreg.unflatten(theta, CFG)
    mask = jnp.ones(x.shape[0])
    got = float(logreg.loss_fn(p, x, y, mask))

    W = np.asarray(p.weights); b = np.asarray(p.intercept)
    lg = np.asarray(x) @ W.T + b
    lg -= lg.max(axis=1, keepdims=True)
    logp = lg - np.log(np.exp(lg).sum(axis=1, keepdims=True))
    want = -logp[np.arange(len(y)), np.asarray(y)].mean()
    assert got == pytest.approx(want, rel=1e-5)


def test_mask_excludes_rows():
    x, y = _rand_batch(8)
    theta = jnp.zeros(CFG.num_params)
    p = logreg.unflatten(theta, CFG)
    half = jnp.asarray([1, 1, 1, 1, 0, 0, 0, 0], jnp.float32)
    l_half = float(logreg.loss_fn(p, x, y, half))
    l_sub = float(logreg.loss_fn(p, x[:4], y[:4], jnp.ones(4)))
    assert l_half == pytest.approx(l_sub, rel=1e-6)


def test_local_update_is_delta_and_descends():
    """delta := new - old after k steps (LogisticRegressionTaskSpark.java:191-220),
    and applying it decreases the loss."""
    x, y = _rand_batch(64)
    mask = jnp.ones(64)
    theta = jnp.zeros(CFG.num_params)
    delta, loss = LogRegTask(CFG).local_update(theta, x, y, mask)
    assert delta.shape == theta.shape
    assert float(jnp.abs(delta).sum()) > 0
    l0 = float(logreg.loss_fn(logreg.unflatten(theta, CFG), x, y, mask))
    l1 = float(logreg.loss_fn(logreg.unflatten(theta + delta, CFG), x, y, mask))
    assert l1 < l0


def test_local_update_k_steps_composes():
    """k=2 from theta == one step, then one more step from the intermediate."""
    x, y = _rand_batch(16)
    mask = jnp.ones(16)
    theta = jnp.zeros(CFG.num_params)
    import dataclasses
    cfg2 = CFG_LR01
    cfg1 = dataclasses.replace(CFG_LR01, num_max_iter=1)
    d2, _ = LogRegTask(cfg2).local_update(theta, x, y, mask)
    d1, _ = LogRegTask(cfg1).local_update(theta, x, y, mask)
    d1b, _ = LogRegTask(cfg1).local_update(theta + d1, x, y, mask)
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d1 + d1b), atol=1e-5)


def test_weighted_f1_matches_sklearn_definition():
    rng = np.random.default_rng(2)
    y_true = rng.integers(0, 4, size=200)
    y_pred = rng.integers(0, 4, size=200)
    f1, acc = metrics.weighted_f1_accuracy(
        jnp.asarray(y_pred), jnp.asarray(y_true), 4)
    # hand-rolled support-weighted F1 (sklearn average='weighted')
    want_f1 = 0.0
    for c in range(4):
        tp = np.sum((y_true == c) & (y_pred == c))
        fp = np.sum((y_true != c) & (y_pred == c))
        fn = np.sum((y_true == c) & (y_pred != c))
        prec = tp / max(tp + fp, 1)
        rec = tp / max(tp + fn, 1)
        f1c = 2 * prec * rec / max(prec + rec, 1e-12)
        want_f1 += f1c * np.sum(y_true == c)
    want_f1 /= len(y_true)
    assert float(f1) == pytest.approx(want_f1, rel=1e-5)
    assert float(acc) == pytest.approx(np.mean(y_true == y_pred), rel=1e-6)


def test_evaluate_learns_separable_data():
    """End-to-end sanity: a few local updates reach high F1 on separable data."""
    cfg = ModelConfig(num_features=8, num_classes=2, local_learning_rate=0.5)
    rng = np.random.default_rng(3)
    n = 256
    y = rng.integers(1, 3, size=n).astype(np.int32)
    centers = np.array([[0.0] * 8, [3.0] * 8, [-3.0] * 8], np.float32)
    x = centers[y] + rng.normal(scale=0.3, size=(n, 8)).astype(np.float32)
    x, y = jnp.asarray(x), jnp.asarray(y)
    theta = jnp.zeros(cfg.num_params)
    for _ in range(20):
        d, _ = LogRegTask(cfg).local_update(theta, x, y, jnp.ones(n))
        theta = theta + d
    m = LogRegTask(cfg).evaluate(theta, x, y)
    assert float(m.accuracy) > 0.95
    assert float(m.f1) > 0.95


def test_sparse_to_dense():
    rows = [{0: 1.0, 3: 2.0}, {}, {7: -1.0}]
    d = logreg.sparse_to_dense(rows, 8)
    assert d.shape == (3, 8)
    assert d[0, 0] == 1.0 and d[0, 3] == 2.0 and d[2, 7] == -1.0
    assert d.sum() == 2.0

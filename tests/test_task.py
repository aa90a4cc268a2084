"""MLTask abstraction + the MLP model family: registry, parity of the
LogRegTask adapter with the direct logreg path, MLP learning end-to-end
through every runtime path (per-node, fused BSP, sharded mesh,
range-sharded)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.data.synth import generate
from kafka_ps_tpu.models import logreg, mlp
from kafka_ps_tpu.models.task import LogRegTask, fit_delta, get_task
from kafka_ps_tpu.parallel import bsp, mesh as mesh_mod, range_sharded
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig

CFG = ModelConfig(num_features=24, num_classes=3, hidden_dim=16)


def _data(n=96, cfg=CFG, seed=0):
    x, y = generate(n, cfg.num_features, cfg.num_classes, noise=0.6,
                    sparsity=0.3, seed=seed)
    return jnp.asarray(x), jnp.asarray(y), jnp.ones((n,), jnp.float32)


def test_registry_and_unknown_task():
    assert isinstance(get_task("logreg", CFG), LogRegTask)
    assert get_task("mlp", CFG).num_params == mlp.num_params(CFG)
    with pytest.raises(ValueError, match="unknown task"):
        get_task("transformer", CFG)


def test_logreg_task_matches_direct_path():
    """The task's flat update is models/logreg.py's own `fit`, called
    directly on the leaves."""
    task = get_task("logreg", CFG)
    x, y, mask = _data()
    theta = jnp.zeros(CFG.num_params)
    d_task, l_task = task.local_update(theta, x, y, mask)
    old = logreg.unflatten(theta, CFG)
    new, l_ref = jax.jit(logreg.fit, static_argnames="cfg")(
        old, x, jax.nn.one_hot(y, CFG.num_rows, dtype=jnp.float32), mask,
        cfg=CFG)
    d_ref = jax.tree.map(jnp.subtract, new, old).flat
    np.testing.assert_array_equal(np.asarray(d_task), np.asarray(d_ref))
    assert float(l_task) == float(l_ref)


def test_mlp_flatten_roundtrip():
    task = get_task("mlp", CFG)
    theta = task.init_params()
    assert theta.shape == (task.num_params,)
    p = mlp.unflatten(theta, CFG)
    np.testing.assert_array_equal(np.asarray(mlp.flatten(p)),
                                  np.asarray(theta))
    assert p.w1.shape == (CFG.hidden_dim, CFG.num_features)
    assert p.w2.shape == (CFG.num_rows, CFG.hidden_dim)


def test_mlp_grad_matches_autodiff_reference():
    """The MLP's scan-of-grad local update must decrease the loss and
    produce finite deltas (masked rows ignored)."""
    task = get_task("mlp", CFG)
    x, y, mask = _data()
    mask = mask.at[-10:].set(0.0)
    theta = task.init_params()
    onehot = jax.nn.one_hot(y, CFG.num_rows, dtype=jnp.float32)
    loss_before = mlp.loss_onehot(mlp.unflatten(theta, CFG), x, onehot, mask)
    delta, loss_after = task.local_update(theta, x, y, mask)
    assert np.isfinite(np.asarray(delta)).all()
    assert float(loss_after) < float(loss_before)


def test_mlp_learns_in_fused_bsp():
    task = get_task("mlp", CFG)
    nw, cap = 4, 16
    x, y = generate(nw * cap, CFG.num_features, CFG.num_classes,
                    noise=0.5, sparsity=0.3, seed=2)
    xb = jnp.asarray(x.reshape(nw, cap, -1))
    yb = jnp.asarray(y.reshape(nw, cap))
    mb = jnp.ones((nw, cap), jnp.float32)
    step = bsp.make_bsp_multi_step(CFG, nw, 1.0 / nw, rounds=80, task=task)
    theta, losses = step(task.init_params(), xb, yb, mb)
    assert float(losses[-1]) < float(losses[0])
    tx, ty, _ = _data(seed=3)
    m = task.evaluate(theta, tx, ty)
    # 64 train rows, 3 classes (chance = 0.33): well above chance on
    # held-out data is the "it learns" bar
    assert float(m.accuracy) > 0.55


def test_mlp_sharded_step_matches_unsharded():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    task = get_task("mlp", CFG)
    mesh = mesh_mod.worker_mesh(num_devices=4)
    nw, cap = 4, 16
    x, y = generate(nw * cap, CFG.num_features, CFG.num_classes, seed=4)
    xb = x.reshape(nw, cap, -1)
    yb = y.reshape(nw, cap)
    mb = np.ones((nw, cap), np.float32)
    theta0 = task.init_params()

    ref_step = bsp.make_bsp_step(CFG, nw, 0.25, task=task)
    t_ref, l_ref = ref_step(theta0, jnp.asarray(xb), jnp.asarray(yb),
                            jnp.asarray(mb))
    sh_step = bsp.make_bsp_step(CFG, nw, 0.25, mesh=mesh, task=task)
    xs, ys, ms = bsp.shard_worker_batches(mesh, xb, yb, mb)
    t_sh, l_sh = sh_step(theta0, xs, ys, ms)
    np.testing.assert_allclose(np.asarray(t_sh), np.asarray(t_ref),
                               rtol=1e-5, atol=1e-6)
    assert float(l_sh) == pytest.approx(float(l_ref), rel=1e-5)


def test_mlp_range_sharded_step():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    task = get_task("mlp", CFG)
    mesh = mesh_mod.worker_param_mesh(2, 2)
    nw, cap = 4, 16
    x, y = generate(nw * cap, CFG.num_features, CFG.num_classes, seed=5)
    xb = x.reshape(nw, cap, -1)
    yb = y.reshape(nw, cap)
    mb = np.ones((nw, cap), np.float32)

    theta0 = range_sharded.shard_theta(mesh, task.init_params(), task)
    step = range_sharded.make_range_sharded_step(CFG, nw, 0.25, mesh,
                                                 task=task)
    xs, ys, ms = range_sharded.shard_worker_batches(mesh, xb, yb, mb)
    t_sh, loss = step(theta0, xs, ys, ms)

    ref_step = bsp.make_bsp_step(CFG, nw, 0.25, task=task)
    t_ref, l_ref = ref_step(task.init_params(), jnp.asarray(xb),
                            jnp.asarray(yb), jnp.asarray(mb))
    np.testing.assert_allclose(range_sharded.unshard_theta(t_sh, task),
                               np.asarray(t_ref), rtol=1e-5, atol=1e-6)
    assert float(loss) == pytest.approx(float(l_ref), rel=1e-5)


def test_mlp_streaming_app_end_to_end():
    """The whole runtime (producer -> buffers -> per-node PS loop) on the
    mlp family, sequential consistency."""
    from kafka_ps_tpu.runtime.app import StreamingPSApp
    cfg = PSConfig(num_workers=2, task="mlp", model=CFG,
                   buffer=BufferConfig(min_size=4, max_size=16))
    x, y = generate(120, CFG.num_features, CFG.num_classes, noise=0.5,
                    sparsity=0.3, seed=6)
    app = StreamingPSApp(cfg, test_x=x[-24:], test_y=y[-24:])
    for i in range(64):
        app.data_sink(i % 2, {j: float(x[i, j])
                              for j in range(CFG.num_features)}, int(y[i]))
    app.run_serial(max_server_iterations=12, pump=lambda: None)
    assert app.server.iterations >= 12
    assert app.server.last_metrics is not None
    assert float(app.server.last_metrics.accuracy) > 0.5
    # theta is the MLP layout, not logreg's
    assert app.server.theta.shape == (mlp.num_params(CFG),)

# -- the leaf-level surface against the flat-carry solver it replaced (PR 25) --

def flat_carry_reference(family, cfg, theta, x, y, mask):
    """The solver as it stood before PR 25, written out plainly: the
    scan carries the FLAT vector, every step unflattens it, takes the
    gradient flat and steps `t - lr * g` on the flat vector; the final
    loss unflattens once more.  (A TPU re-laid the parameters out for
    each of those; the numbers are what must not change.)"""
    onehot = jax.nn.one_hot(y, cfg.num_rows, dtype=jnp.float32)
    lr = cfg.local_learning_rate

    if family == "mlp":
        def loss_of(t):
            p = mlp.unflatten(t, cfg)
            hidden = jax.nn.relu(x @ p.w1.T + p.b1)
            logp = jax.nn.log_softmax(hidden @ p.w2.T + p.b2, axis=-1)
            nll = -(logp * onehot).sum(axis=-1)
            return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)

        def grad_loss(t):
            return jax.grad(loss_of)(t), loss_of(t)
    else:
        def grad_loss(t):
            n_coef = cfg.num_rows * cfg.num_features
            w = t[:n_coef].reshape(cfg.num_rows, cfg.num_features)
            logp = jax.nn.log_softmax(x @ w.T + t[n_coef:], axis=-1)
            denom = jnp.maximum(mask.sum(), 1.0)
            loss = (-(logp * onehot).sum(axis=-1) * mask).sum() / denom
            g = (jnp.exp(logp) - onehot) * (mask / denom)[:, None]
            return jnp.concatenate([(g.T @ x).reshape(-1),
                                    g.sum(axis=0)]), loss

    @jax.jit
    def solve(t0):
        t, _ = jax.lax.scan(lambda t, _: (t - lr * grad_loss(t)[0], None),
                            t0, None, length=cfg.num_max_iter)
        return t - t0, grad_loss(t)[1]

    return solve(theta)


def _solver_case(family, k):
    cfg = ModelConfig(num_features=24, num_classes=3, hidden_dim=16,
                      num_max_iter=k, local_learning_rate=0.05)
    task = get_task(family, cfg)
    x, y, mask = _data(cfg=cfg, seed=10 + k)
    mask = mask.at[-10:].set(0.0)           # masked rows
    y = y.at[3].set(cfg.num_rows + 2)       # an out-of-range label
    # away from logreg's all-zero start, where rounding has no room
    theta = task.init_params() + 0.01 * jax.random.normal(
        jax.random.PRNGKey(k), (task.num_params,))
    return cfg, task, theta, x, y, mask


@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("family", ["mlp", "logreg"])
def test_local_update_matches_the_flat_carry_solver(family, k):
    """Same steps on the same numbers in another memory layout: on the
    CPU the new solver's delta and loss are BITWISE the flat-carry
    solver's (elementwise steps and differences do not care how the
    parameters are grouped, and the matrix products see the same
    operands), so the comparison is exact, not 1e-6."""
    cfg, task, theta, x, y, mask = _solver_case(family, k)
    d_ref, l_ref = flat_carry_reference(family, cfg, theta, x, y, mask)
    d_new, l_new = jax.jit(task.local_update)(theta, x, y, mask)
    assert np.isfinite(np.asarray(d_ref)).all() and np.asarray(d_ref).any()
    np.testing.assert_array_equal(np.asarray(d_new), np.asarray(d_ref))
    assert float(l_new) == float(l_ref)


# -- the k local steps, written once (models/task.py local_steps, PR 30) -------

WORKERS = 3


def _workers_case(family, k, stacked):
    """WORKERS slabs and the leaves they start from: one set shared by
    all, or each worker's own (stacked on a leading axis)."""
    cfg, task, theta, x, y, mask = _solver_case(family, k)
    slabs = [_data(cfg=cfg, seed=20 + w) for w in range(WORKERS)]
    xs = jnp.stack([s[0] for s in slabs])
    onehots = jnp.stack([task.encode_labels(s[1]) for s in slabs])
    masks = jnp.stack([mask] * WORKERS)
    if not stacked:
        return cfg, task, task.unflatten(theta), xs, onehots, masks
    own = [task.unflatten(theta + 0.01 * jax.random.normal(
        jax.random.PRNGKey(40 + w), theta.shape)) for w in range(WORKERS)]
    return (cfg, task, jax.tree.map(lambda *a: jnp.stack(a), *own),
            xs, onehots, masks)


def steps_written_out(family, cfg, leaves, x, onehot, mask):
    """k steps one after another, no loop construct and no worker axis
    → (delta leaves, loss at the last parameters)."""
    if family == "mlp":
        def grad_loss(p):
            return (jax.grad(mlp.loss_onehot)(p, x, onehot, mask),
                    mlp.loss_onehot(p, x, onehot, mask))
    else:
        def grad_loss(p):
            return logreg.grad_loss_onehot(p, x, onehot, mask)
    p = leaves
    for _ in range(cfg.num_max_iter):
        p = jax.tree.map(lambda a, g: a - cfg.local_learning_rate * g,
                         p, grad_loss(p)[0])
    return jax.tree.map(jnp.subtract, p, leaves), grad_loss(p)[1]


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["leaves-shared", "leaves-stacked"])
@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("family", ["mlp", "logreg"])
def test_local_steps_are_k_steps_written_out(family, k, stacked):
    """`fit_delta` under the worker `vmap` — leaves shared by every
    worker, as the fused round and the gang's bcast programs hand them,
    or each worker's own — against k hand-written steps a worker: the
    same deltas and the same loss, to the bit on the CPU (the first
    step's one product over all workers' rows gives each row what the
    worker's own product gives it)."""
    cfg, task, leaves, xs, onehots, masks = _workers_case(family, k, stacked)
    deltas, losses = jax.jit(jax.vmap(
        lambda l, x, o, m: fit_delta(task, l, x, o, m),
        in_axes=(0 if stacked else None, 0, 0, 0)))(
            leaves, xs, onehots, masks)
    for w in range(WORKERS):
        own = jax.tree.map(lambda a: a[w], leaves) if stacked else leaves
        d_ref, l_ref = jax.jit(
            lambda l, x, o, m: steps_written_out(family, cfg, l, x, o, m))(
                own, xs[w], onehots[w], masks[w])
        for got, ref in zip(jax.tree.leaves(deltas), jax.tree.leaves(d_ref)):
            assert np.asarray(ref).any()
            np.testing.assert_array_equal(np.asarray(got[w]),
                                          np.asarray(ref))
        assert float(losses[w]) == float(l_ref)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("family", ["mlp", "logreg"])
def test_first_local_step_reads_the_shared_leaves(family, k):
    """What the program is, read from its jaxpr: under a worker `vmap`
    of shared leaves the first product multiplies ALL workers' rows by
    the one shared matrix (no worker axis on the weights), and the
    steps after the first are one scan of k - 1 (none at k = 1)."""
    cfg, task, leaves, xs, onehots, masks = _workers_case(family, k, False)
    eqns = list(_equations(jax.make_jaxpr(jax.vmap(
        lambda x, o, m: fit_delta(task, leaves, x, o, m)))(
            xs, onehots, masks).jaxpr))
    first = next(e for e in eqns if e.primitive.name == "dot_general")
    assert sorted(len(v.aval.shape) for v in first.invars) == [2, 3]
    scans = [e for e in eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == ([k - 1] if k > 1 else [])


def test_a_local_update_takes_at_least_one_step():
    cfg, task, theta, x, y, mask = _solver_case("logreg", 0)
    with pytest.raises(ValueError, match="at least one step"):
        task.local_update(theta, x, y, mask)


@pytest.mark.parametrize("family", ["mlp", "logreg"])
def test_flatten_inverts_unflatten_through_the_protocol(family):
    task = get_task(family, CFG)
    theta = jax.random.normal(jax.random.PRNGKey(3), (task.num_params,))
    leaves = task.unflatten(theta)
    assert sum(a.size for a in jax.tree.leaves(leaves)) == task.num_params
    np.testing.assert_array_equal(np.asarray(task.flatten(leaves)),
                                  np.asarray(theta))


# -- the flat face, written once (models/task.py FlatFace) ---------------------

GLM_TINY = "benchmark/families/glm4-moe-lite/tiny.model.json"
ALL_FAMILIES = ["mlp", "logreg", "glm4_moe_lite"]


def _flat_case(family):
    """Every registered family at its tiny size: (task, theta, rows,
    labels in range, mask)."""
    if family != "glm4_moe_lite":
        cfg, task, theta, x, y, mask = _solver_case(family, 2)
        return task, theta, x, jnp.clip(y, 0, cfg.num_rows - 1), mask
    task = get_task(family, ModelConfig(
        num_max_iter=2, local_learning_rate=0.05, model_json=GLM_TINY))
    rng = np.random.default_rng(11)
    rows = jnp.asarray(rng.integers(
        0, task.arch.vocab_held, size=(3, task.row_width)), jnp.int32)
    theta = task.init_params() + 0.02 * jax.random.normal(
        jax.random.PRNGKey(2), (task.num_params,))
    # a token row carries its own labels; the label column is ignored
    return (task, theta, rows, jnp.zeros((3,), jnp.int32),
            jnp.asarray([1.0, 1.0, 0.0]))


@pytest.mark.parametrize("family", ALL_FAMILIES)
def test_flat_wrapper_is_flatten_of_the_leaf_level_fit(family):
    """One solver a family: `local_update` is unflatten, `fit`, the
    difference (`fit_delta`), flatten — bitwise."""
    task, theta, x, y, mask = _flat_case(family)
    leaves = task.unflatten(theta)
    want, loss = jax.jit(lambda *a: fit_delta(task, *a))(
        leaves, x, task.encode_labels(y), mask)
    delta, loss_flat = task.local_update(theta, x, y, mask)
    assert np.asarray(delta).any()
    np.testing.assert_array_equal(np.asarray(delta),
                                  np.asarray(task.flatten(want)))
    assert float(loss_flat) == float(loss)


@pytest.mark.parametrize("family", ALL_FAMILIES)
def test_flat_evaluate_is_evaluate_leaves(family):
    task, theta, x, y, _ = _flat_case(family)
    m_flat = task.evaluate(theta, x, y)
    m_leaf = jax.jit(task.evaluate_leaves)(task.unflatten(theta), x, y)
    assert [float(v) for v in m_flat] == [float(v) for v in m_leaf]


@pytest.mark.parametrize("family", ALL_FAMILIES)
def test_evaluate_batch_row_is_evaluate(family):
    """Row i of the stacked evaluation is `evaluate(thetas[i])`,
    bitwise — what lets the eval engine coalesce a backlog."""
    task, theta, x, y, _ = _flat_case(family)
    thetas = jnp.stack([theta, theta * 1.5, theta * 0.5])
    batch = task.evaluate_batch(thetas, x, y)
    for i in range(3):
        one = task.evaluate(thetas[i], x, y)
        assert [float(v[i]) for v in batch] == [float(v) for v in one]

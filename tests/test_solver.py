"""The one local update (models/task.py `fit_slab`, the programs of
runtime/worker.py `_solver_fns` and runtime/gang.py `_gang_solver_fns`)
held to what a second formulation of it used to be compared for: masked
and padded rows, labels out of range, slab storage forms, the gang
programs against the single one, and the widest model the benchmark
runs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.compress.slab import decode_x, encode_x
from kafka_ps_tpu.data.synth import generate
from kafka_ps_tpu.models.task import fit_slab, get_task
from kafka_ps_tpu.runtime.gang import _gang_solver_fns
from kafka_ps_tpu.runtime.worker import _solver_fns
from kafka_ps_tpu.utils.config import ModelConfig, PSConfig

CFG = ModelConfig(num_features=24, num_classes=3, hidden_dim=16,
                  num_max_iter=2, local_learning_rate=0.05)
FAMILIES = ["logreg", "mlp"]


def _slab(rows, cfg=CFG, seed=0):
    x, y = generate(rows, cfg.num_features, cfg.num_classes, noise=0.6,
                    sparsity=0.3, seed=seed)
    return jnp.asarray(x), jnp.asarray(y), jnp.ones((rows,), jnp.float32)


def _theta(task, seed=0):
    # away from logreg's all-zero start, where rounding has no room
    return task.init_params() + 0.01 * jax.random.normal(
        jax.random.PRNGKey(seed), (task.num_params,))


def _run(family, cfg, with_eval, theta, x, y, mask):
    """(delta, loss) through one of `_solver_fns`' two programs."""
    update, update_and_eval = _solver_fns(family, cfg)
    if not with_eval:
        return update(theta, x, y, mask)
    tx, ty, _ = _slab(32, cfg, seed=99)
    delta, loss, f1, acc = update_and_eval(theta, x, y, mask, tx, ty)
    assert np.isfinite(float(f1)) and np.isfinite(float(acc))
    return delta, loss


# -- masked and padded rows ----------------------------------------------------

@pytest.mark.parametrize("with_eval", [False, True])
@pytest.mark.parametrize("family", FAMILIES)
def test_all_rows_masked_is_a_zero_delta(family, with_eval):
    """A slab of invalid rows only (a buffer emptied by the target
    size): nothing divides by the zero row count, the delta is exactly
    zero and the loss finite."""
    x, y, _ = _slab(16)
    theta = _theta(get_task(family, CFG))
    delta, loss = _run(family, CFG, with_eval, theta, x, y,
                       jnp.zeros((16,), jnp.float32))
    assert not np.asarray(delta).any()
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("family,hidden", [("logreg", 16), ("mlp", 16),
                                           ("mlp", 20)])
def test_mask_zero_padding_rows_change_nothing(family, hidden):
    """13 rows, and the same 13 padded to the 16-row bucket with rows
    the mask leaves out (whatever they hold): the same delta and loss.
    Sums over another row count may round in another order, hence a
    tolerance — ten times under anything a counted padding row gives."""
    cfg = dataclasses.replace(CFG, hidden_dim=hidden)
    x, y, mask = _slab(13, cfg)
    junk_x, junk_y, _ = _slab(3, cfg, seed=5)
    theta = _theta(get_task(family, cfg))
    d13, l13 = _run(family, cfg, False, theta, x, y, mask)
    d16, l16 = _run(family, cfg, False, theta,
                    jnp.concatenate([x, 100.0 * junk_x]),
                    jnp.concatenate([y, junk_y]),
                    jnp.concatenate([mask, jnp.zeros((3,))]))
    assert np.asarray(d13).any()
    np.testing.assert_allclose(np.asarray(d16), np.asarray(d13),
                               rtol=1e-5, atol=1e-7)
    assert float(l16) == pytest.approx(float(l13), rel=1e-6)


# -- a label out of range, against numpy -----------------------------------------

def numpy_local_update(family, cfg, leaves, x, y, mask, row_weight):
    """k full-batch steps in float64 numpy → (delta leaves, loss).
    `row_weight(onehot)` is what multiplies a row's softmax in the
    gradient of the logits: 1 in logreg's closed form, the one-hot's
    own sum under `jax.grad` of the one-hot cross-entropy."""
    p = [np.asarray(a, np.float64) for a in leaves]
    start = [a.copy() for a in p]
    x, mask = np.asarray(x, np.float64), np.asarray(mask, np.float64)
    onehot = np.eye(cfg.num_rows)[np.clip(np.asarray(y), 0, cfg.num_rows - 1)]
    onehot[np.asarray(y) >= cfg.num_rows] = 0.0      # jax.nn.one_hot's row
    denom = max(mask.sum(), 1.0)

    def forward(p):
        if family == "logreg":
            w, b = p
            pre = hidden = None
            lg = x @ w.T + b
        else:
            w1, b1, w2, b2 = p
            pre = x @ w1.T + b1
            hidden = np.maximum(pre, 0.0)
            lg = hidden @ w2.T + b2
        lg = lg - lg.max(axis=1, keepdims=True)
        logp = lg - np.log(np.exp(lg).sum(axis=1, keepdims=True))
        return pre, hidden, logp

    for _ in range(cfg.num_max_iter):
        pre, hidden, logp = forward(p)
        g = ((np.exp(logp) * row_weight(onehot) - onehot)
             * (mask / denom)[:, None])
        if family == "logreg":
            grads = [g.T @ x, g.sum(axis=0)]
        else:
            back = (g @ p[2]) * (pre > 0)
            grads = [back.T @ x, back.sum(axis=0),
                     g.T @ hidden, g.sum(axis=0)]
        p = [a - cfg.local_learning_rate * b for a, b in zip(p, grads)]
    logp = forward(p)[2]
    loss = (-(logp * onehot).sum(axis=1) * mask).sum() / denom
    return [a - b for a, b in zip(p, start)], loss


KEEPS_SOFTMAX = {"logreg": True, "mlp": False}


@pytest.mark.parametrize("family", FAMILIES)
def test_out_of_range_label_against_numpy(family):
    """A label past the last class has an all-zero one-hot row.  Its
    loss term is zero in both families; logreg's closed-form gradient
    keeps the row's softmax term, the mlp's `jax.grad` gives the row no
    gradient at all.  The difference is deliberate and stays pinned:
    each family agrees with its own rule written out in numpy and not
    with the other's."""
    task = get_task(family, CFG)
    x, y, mask = _slab(16)
    y = y.at[3].set(CFG.num_classes + 7)
    theta = _theta(task)
    delta, loss = _solver_fns(family, CFG)[0](theta, x, y, mask)
    got = [np.asarray(a) for a in task.unflatten(delta)]

    def ones(onehot):
        return 1.0

    def own_sum(onehot):
        return onehot.sum(axis=1, keepdims=True)

    mine, other = ((ones, own_sum) if KEEPS_SOFTMAX[family]
                   else (own_sum, ones))
    want, want_loss = numpy_local_update(
        family, CFG, task.unflatten(theta), x, y, mask, mine)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-7)
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    wrong, _ = numpy_local_update(
        family, CFG, task.unflatten(theta), x, y, mask, other)
    assert max(np.abs(g - w).max() for g, w in zip(got, wrong)) > 1e-4


# -- slab storage ------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("family", FAMILIES)
def test_fit_slab_decodes_the_stored_slab(family, kind):
    """`--slab-dtype bf16|int8`: the solver on the slab as stored is,
    bitwise, the solver on `decode_x` of it — the decode is fused into
    the program, it is not another computation."""
    task = get_task(family, CFG)
    x, y, mask = _slab(24)
    mask = mask.at[-5:].set(0.0)
    stored = encode_x(kind, x)
    leaves = task.unflatten(_theta(task))
    solve = jax.jit(lambda *a: fit_slab(task, *a))
    d_stored, l_stored = solve(leaves, stored, y, mask)
    d_plain, l_plain = solve(leaves, decode_x(stored), y, mask)
    assert np.asarray(task.flatten(d_stored)).any()
    assert not np.array_equal(np.asarray(decode_x(stored)), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(task.flatten(d_stored)),
                                  np.asarray(task.flatten(d_plain)))
    assert float(l_stored) == float(l_plain)


# -- the gang programs are the single program ----------------------------------------

@pytest.mark.parametrize("entry", ["update_stacked", "update_bcast",
                                   "update_eval_stacked",
                                   "update_eval_bcast"])
@pytest.mark.parametrize("family", FAMILIES)
def test_gang_entry_point_is_the_single_program_per_member(family, entry):
    """Each of the four gang programs against `_solver_fns`' pair,
    member by member: deltas bitwise, scalars equal (k = 3)."""
    k = 3
    task = get_task(family, CFG)
    slabs = [_slab(24, seed=10 + i) for i in range(k)]
    xs, ys, masks = (tuple(s[i] for s in slabs) for i in range(3))
    masks = tuple(m.at[-(i + 1):].set(0.0) for i, m in enumerate(masks))
    shared = entry.endswith("bcast")
    thetas = [_theta(task, seed=0 if shared else i) for i in range(k)]
    tx, ty, _ = _slab(32, seed=99)
    test = (tx, ty) if "eval" in entry else ()
    out = _gang_solver_fns(family, CFG)[entry](
        thetas[0] if shared else tuple(thetas), xs, ys, masks, *test)
    single = _solver_fns(family, CFG)["eval" in entry]
    for i in range(k):
        want = single(thetas[i], xs[i], ys[i], masks[i], *test)
        assert np.asarray(want[0]).any()
        np.testing.assert_array_equal(np.asarray(out[0][i]),
                                      np.asarray(want[0]))
        assert [float(part[i]) for part in out[1:]] == \
            [float(v) for v in want[1:]]


# -- the width the benchmark runs ------------------------------------------------------

def test_the_solver_takes_the_cells_width():
    """1024 features x `hidden_dim` 4096, the `mlp-4096*` cells' model:
    the one solver has no width it refuses."""
    cfg = ModelConfig(num_features=1024, num_classes=5, hidden_dim=4096)
    task = get_task("mlp", cfg)
    x, y, mask = _slab(16, cfg)
    delta, loss = _solver_fns("mlp", cfg)[0](task.init_params(), x, y, mask)
    assert delta.shape == (4_222_982,) == (task.num_params,)
    assert np.isfinite(np.asarray(delta)).all() and np.asarray(delta).any()
    assert np.isfinite(float(loss))


# -- no second solver to ask for ---------------------------------------------------------

def test_the_option_is_gone(capsys):
    from kafka_ps_tpu.cli import run as run_mod
    with pytest.raises(SystemExit) as e:
        run_mod.main(["-training", "train.csv", "-test", "test.csv",
                      "--pallas"])
    assert e.value.code == 2
    assert "unrecognized arguments: --pallas" in capsys.readouterr().err
    with pytest.raises(TypeError, match="use_pallas"):
        PSConfig(use_pallas=True)

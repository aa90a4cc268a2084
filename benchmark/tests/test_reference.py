"""reference.py against a case worked by hand, and the comparison's
arithmetic."""

import math

from types import SimpleNamespace as NS

import numpy as np
import pytest

import reference
import run as harness


def test_two_local_steps_of_logistic_regression_by_hand():
    """One feature, one class (rows 0 and 1), one worker, one row
    x = [1], y = 1, zero start, lr 1, k = 2.
    step 1: p = [.5, .5]; grad W = b = [.5, -.5]; W = b = [-.5, .5]
    step 2: logits = [-1, 1]; p1 = 1/(1+e^-2); grad = [1-p1, p1-1]
            W = b = [-.5-(1-p1), .5+(1-p1)]
    loss at the new parameters: logits = [-2a, 2a], a = 1.5 - p1,
            loss = log(1 + e^(-4a))."""
    shapes = reference.Shapes("logreg", num_features=1, num_classes=1,
                              hidden_dim=0, local_iterations=2,
                              local_lr=1.0, num_workers=1)
    ref = reference.Reference(shapes)
    theta0 = np.asarray(reference.init_params(shapes))
    assert theta0.tolist() == [0.0, 0.0, 0.0, 0.0]
    slab = (np.array([[1.0]], np.float32), np.array([1], np.int32),
            np.array([1.0], np.float32))
    thetas, losses = ref.run(theta0, [slab], clocks=1)
    p1 = 1.0 / (1.0 + math.exp(-2.0))
    a = 1.5 - p1
    # flat layout: W row 0, W row 1, b row 0, b row 1; one worker, so
    # the server adds the whole delta
    assert thetas[0] == pytest.approx([-a, a, -a, a], rel=1e-6)
    assert losses[0] == pytest.approx(math.log1p(math.exp(-4 * a)), rel=1e-6)


def test_masked_rows_do_not_count_and_the_server_takes_the_mean():
    shapes = reference.Shapes("logreg", 1, 1, 0, 1, 1.0, 2)
    ref = reference.Reference(shapes)
    theta0 = np.zeros(4, np.float32)
    live = (np.array([[1.0], [5.0]], np.float32), np.array([1, 0], np.int32),
            np.array([1.0, 0.0], np.float32))
    idle = (np.array([[0.0], [0.0]], np.float32), np.array([1, 1], np.int32),
            np.array([1.0, 1.0], np.float32))
    thetas, _ = ref.run(theta0, [live, idle], clocks=1)
    # worker 0: one live row, delta W = [-.5, .5], b = [-.5, .5];
    # worker 1: x = 0, delta W = 0, b = [-.5, .5]; mean of the two
    assert thetas[0] == pytest.approx([-0.25, 0.25, -0.5, 0.5], rel=1e-6)


def test_evaluation_by_hand():
    shapes = reference.Shapes("logreg", 1, 2, 0, 1, 1.0, 1)
    ref = reference.Reference(shapes)
    # W = [0, 1, -1], b = 0: x > 0 predicts class 1, x < 0 class 2
    theta = np.array([0, 1, -1, 0, 0, 0], np.float32)
    x = np.array([[2.0], [3.0], [-1.0], [1.0]], np.float32)
    y = np.array([1, 1, 2, 2], np.int32)
    got = ref.evaluate(theta, (x, y))
    assert set(got) == set(reference.LOG_COLUMN)
    assert got["accuracy"] == pytest.approx(0.75)
    # class 1: precision 2/3, recall 1 -> F1 .8; class 2: precision 1,
    # recall 1/2 -> F1 2/3; equal support
    assert got["f1"] == pytest.approx((0.8 + 2 / 3) / 2, rel=1e-6)


def test_the_mlp_start_is_he_normal_and_laid_out_flat():
    shapes = reference.Shapes("mlp", 8, 3, 16, 2, 0.1, 1)
    theta = np.asarray(reference.init_params(shapes))
    leaves = reference.split(theta, shapes)
    assert theta.shape == (16 * 8 + 16 + 4 * 16 + 4,)
    assert [leaves[k].shape for k in ("w1", "b1", "w2", "b2")] == [
        (16, 8), (16,), (4, 16), (4,)]
    assert not leaves["b1"].any() and not leaves["b2"].any()
    assert leaves["w1"].std() == pytest.approx(math.sqrt(2 / 8), rel=0.3)


def test_leaf_norm_gap_takes_the_worst_leaf_against_the_median_floor():
    shapes = reference.Shapes("logreg", 2, 1, 0, 1, 1.0, 1)
    theta0 = np.zeros(6)
    ref = np.array([3.0, 4.0, 0.0, 0.0, 0.0, 1e-9])   # ||W|| 5, ||b|| 1e-9
    prog = np.array([3.0, 4.0, 0.0, 0.0, 0.0, 0.5])
    # the intercept leaf hardly moves: its gap is held against the
    # median leaf's norm (2.5), not its own
    assert reference.param_gap(prog, ref, theta0, shapes) == \
        pytest.approx(0.5 / 2.5, rel=1e-6)
    assert reference.param_gap(ref, ref, theta0, shapes) == 0.0


def test_bsp_spread_reads_the_log_in_file_order():
    rows = [(0, 0), (1, 0), (0, 1), (1, 1), (0, 2), (1, 2)]
    assert harness.bsp_spread(rows, 2) == 1
    assert harness.bsp_spread(rows + [(0, 3), (0, 4)], 2) == 2


def test_only_the_clocks_compared_are_kept():
    shapes = reference.Shapes("logreg", 1, 1, 0, 1, 1.0, 1)
    slab = (np.array([[1.0]], np.float32), np.array([1], np.int32),
            np.array([1.0], np.float32))
    theta0 = np.zeros(4, np.float32)
    every, losses = reference.Reference(shapes).run(theta0, [slab], 6)
    kept, same = reference.Reference(shapes).run(theta0, [slab], 6,
                                                 keep_every=3)
    assert len(every) == 6 and len(kept) == 2 and losses == same
    assert kept[0].tolist() == every[2].tolist()
    assert kept[1].tolist() == every[5].tolist()


def test_shapes_are_read_off_the_clis_configuration():
    model = NS(num_features=8, num_classes=3, hidden_dim=16, num_max_iter=2,
               local_learning_rate=0.1)
    got = reference.shapes(NS(task="mlp", model=model, num_workers=4))
    assert got == reference.Shapes("mlp", 8, 3, 16, 2, 0.1, 4)

"""The expert layer's two products with its 0/1 placement matrix as
kernels (models/placement_kernel.py) in Pallas's interpreter on the
CPU, against the products they stand for (`lm_common.routed_experts`'
`jnp.dot` with the matrix written out), and `routed_experts` itself
under `jax.grad` with the kernels made to take a tiny shape.

The kernels round their float32 operand as the chip's products do —
one bfloat16 piece at the default precision, a high and a low one at
`HIGH` — and the CPU's `jnp.dot` rounds nothing, so the product is
given the operand already rounded: placing, one term a row, then
agrees TO THE BIT, and a sum over a token's k rows within the roundings
of a sum whose order may differ."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import mellum, placement_kernel
from kafka_ps_tpu.models.task import get_task
from kafka_ps_tpu.utils.config import ModelConfig

TOKENS, HIDDEN, TILE, BLOCK = 64, 32, 16, 8
HIGH = jax.lax.Precision.HIGH
EPS = float(np.finfo(np.float32).eps)


def sorted_rows(sizes, rows: int, seed: int):
    """(`order[:rows] // k` of a sort whose held groups have `sizes`
    rows — ascending tokens inside a group, anything past the last —
    and the live rows)."""
    rng = np.random.default_rng(seed)
    tok = [np.sort(rng.choice(TOKENS, size=s, replace=False)) for s in sizes]
    tok = np.concatenate([*tok, rng.integers(0, TOKENS, size=rows)])[:rows]
    return jnp.asarray(tok, jnp.int32), min(sum(sizes), rows)


# name -> (the held groups' rows, the rows placed)
CASES = {
    "uneven_groups": ((37, 3, 12, 20), 128),
    "an_empty_group": ((25, 0, 30), 128),
    "nobody_here": ((0, 0, 0), 128),
    "n_here_on_a_tiles_edge": ((20, 28), 128),
    "a_tile_spans_three_groups": ((13, 4, 5, 30), 128),
    "over_the_bound": ((64, 50, 64, 60), 256),      # every slot placed
}


def written_out(tok, n_here):
    """`routed_experts`' matrix as the product path builds it."""
    live = (jnp.arange(tok.shape[0]) < n_here)[:, None]
    return jnp.where(live, jax.nn.one_hot(tok, TOKENS, dtype=jnp.bfloat16), 0)


def rounded(x, passes: int):
    """What the chip's product makes of a float32 operand beside a 0/1
    one: its bfloat16 rounding at the default precision (one pass), its
    high and low bfloat16 pieces at `HIGH` (two)."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    if passes == 1:
        return hi
    return hi + (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def kernel(x, plan, back: bool, passes: int):
    return placement_kernel.multiply(x, plan, back, passes, HIDDEN, True)


def case_of(name: str):
    sizes, rows = CASES[name]
    tok, n_here = sorted_rows(sizes, rows, seed=len(name))
    rng = np.random.default_rng(rows + n_here)
    h = jnp.asarray(rng.standard_normal((TOKENS, HIDDEN)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((rows, HIDDEN)), jnp.float32)
    return (tok, n_here, placement_kernel.plan(tok, n_here, TOKENS, TILE,
                                               BLOCK),
            written_out(tok, n_here), h, y)


@pytest.mark.parametrize("passes", [1, 2])
@pytest.mark.parametrize("name", sorted(CASES))
def test_placing_is_the_product_to_the_bit(name, passes):
    """`P · x` in one pass (placing) and in two (the add-back's
    transpose): a row has one term, so the kernel equals the product on
    the operand the chip's precision leaves, bit for bit; a dead row
    and a dead tile are zeros."""
    tok, n_here, plan, matrix, h, _ = case_of(name)
    got = np.asarray(kernel(h, plan, False, passes))
    want = np.asarray(jnp.dot(matrix, rounded(h, passes),
                              preferred_element_type=jnp.float32))
    assert np.array_equal(got, want)
    assert np.array_equal(
        got[:n_here], np.asarray(rounded(h, passes))[np.asarray(tok)[:n_here]])
    assert not got[n_here:].any()


@pytest.mark.parametrize("passes", [1, 2])
@pytest.mark.parametrize("name", sorted(CASES))
def test_adding_back_is_the_product_within_a_sums_rounding(name, passes):
    """`Pᵀ · x` in two passes (adding back) and in one (placing's
    transpose) against the product at that precision: a token's sum has
    at most k terms a piece, and only their order may differ.  NaN in
    the dead rows — what the grouped kernels leave past the last group —
    reaches nothing: the result is the clean rows' to the bit."""
    _, n_here, plan, matrix, _, y = case_of(name)
    live = (np.arange(y.shape[0]) < n_here)[:, None]
    got = np.asarray(kernel(jnp.where(live, y, jnp.nan), plan, True, passes))
    clean = jnp.where(live, y, 0.0)
    assert np.array_equal(got, np.asarray(kernel(clean, plan, True, passes)))
    want = np.asarray(jnp.dot(matrix.T, rounded(clean, passes),
                              precision=HIGH,
                              preferred_element_type=jnp.float32))
    room = 4 * EPS * np.asarray(jnp.dot(
        matrix.T.astype(jnp.float32), jnp.abs(clean), precision=HIGH))
    assert np.all(np.abs(got - want) <= room)
    assert np.isfinite(got).all() and (n_here == 0) == (not got.any())


@pytest.mark.parametrize("name", sorted(CASES))
def test_each_products_cotangent_is_the_other_product(name):
    """Under `jax.grad` placing hands back an add-back of its cotangent
    and the add-back a placing, each at the passes of the product it
    transposes; NaN in the dead rows of `d_xs` reaches no `d_h`."""
    _, n_here, plan, _, h, y = case_of(name)
    live = (np.arange(y.shape[0]) < n_here)[:, None]
    for passes in (1, 2):
        d_h = jax.grad(lambda h: jnp.sum(jnp.where(
            live, kernel(h, plan, False, passes) * y, 0.0)))(h)
        d_xs = jnp.where(live, y, jnp.nan)
        _, vjp = jax.vjp(lambda h: kernel(h, plan, False, passes), h)
        assert np.array_equal(np.asarray(vjp(d_xs)[0]), np.asarray(d_h))
        assert np.array_equal(np.asarray(d_h),
                              np.asarray(kernel(y, plan, True, passes)))
        d_y = jax.grad(lambda y: jnp.sum(
            kernel(y, plan, True, passes) * h))(y)
        assert np.array_equal(np.asarray(d_y),
                              np.asarray(kernel(h, plan, False, passes)))


@pytest.mark.parametrize("tile,block", [(16, 8), (8, 32), (32, 16)])
@pytest.mark.parametrize("name", sorted(CASES))
def test_the_plan_visits_the_pieces_that_hold_a_one(name, tile, block):
    """The plan's flags against a brute-force look at the dense matrix:
    a piece `[tile, block]` is visited iff it holds a one — the exact
    set, no range — and `pairs` is their elements in units of 1,024."""
    sizes, rows = CASES[name]
    tok, n_here = sorted_rows(sizes, rows, seed=len(name))
    plan = placement_kernel.plan(tok, n_here, TOKENS, tile, block)
    dense = np.asarray(written_out(tok, n_here), np.float32)
    holds = dense.reshape(rows // tile, tile, TOKENS // block, block).any(
        axis=(1, 3))
    assert np.array_equal(np.asarray(plan.visit) > 0, holds)
    assert int(plan.pairs) == holds.sum() * tile * block // 1024
    assert np.array_equal(np.asarray(plan.tok)[:n_here],
                          np.asarray(tok)[:n_here])
    assert (np.asarray(plan.tok)[n_here:] == -1).all()


@pytest.mark.parametrize("rows,tokens,hidden,taken", [
    (16384, 4096, 2304, True),      # mellum2-12b-ep4: the bound's rows
    (32768, 4096, 2304, True),      # and all its slots
    (4096, 4096, 2048, True),       # trinity-mini-ep16: the bound's rows
    (32768, 4096, 2048, True),      # and all its slots
    (4096, 1024, 2048, False),      # glm-4.7-flash-ep8: all its slots
    (1024, 1024, 2048, False),      # glm-4.7-flash-ep8
    (768, 1024, 2688, False),       # nemotron-3-nano-ep16
    (48, 48, 64, False),            # a tiny model file
    (16384 + 64, 4096, 2304, False),        # no whole tiles
    (16384, 4096 + 256, 2304, False),       # no whole blocks
    (16384, 4096, 2304 + 64, False),        # no whole lanes
])
def test_the_shape_says_which_placement_runs(rows, tokens, hidden, taken):
    assert placement_kernel.takes(rows, tokens, hidden) == taken
    if taken:
        chunk = placement_kernel.chunk_of(tokens, hidden)
        assert hidden % chunk == 0 and chunk % placement_kernel.LANES == 0
        assert 4 * tokens * chunk <= placement_kernel.RESIDENT_BYTES


# -- through `routed_experts` -------------------------------------------------

@pytest.fixture
def tiny_kernels(monkeypatch, the_tpus_branch):
    """The rule made to take a tiny shape, in tiles of 8 rows and
    blocks of 8 tokens and one chunk, the kernels in the interpreter
    (`the_tpus_branch`): the test's own steering, the program has no
    option that does this."""
    plan, multiply = placement_kernel.plan, placement_kernel.multiply
    monkeypatch.setattr(placement_kernel, "takes",
                        lambda rows, tokens, hidden: True)
    monkeypatch.setattr(placement_kernel, "plan",
                        functools.partial(plan, tile=8, block=8))
    monkeypatch.setattr(
        placement_kernel, "multiply",
        lambda x, plan_, back, passes: multiply(x, plan_, back, passes,
                                                x.shape[1]))


def _layer(over: bool):
    """`routed_experts` at the `mellum` family's tiny widths, three
    experts held, on 24 tokens: → (the function of (h, w, p), its
    arguments, the bound)."""
    c = dataclasses.replace(get_task("mellum", ModelConfig(
        model_json="benchmark/families/mellum/tiny.model.json")).arch,
        experts_held=3, expert_offset=0)
    t, k, held = 24, c.num_experts_per_tok, 3
    rng = np.random.default_rng(42)
    h = jnp.asarray(rng.standard_normal((t, c.hidden_size)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.2, 1.0, (t, k)), jnp.float32)
    inter = c.moe_intermediate_size
    p = {key: jnp.asarray(0.1 * rng.standard_normal(shape), jnp.float32)
         for key, shape in (("e_gate", (held, c.hidden_size, inter)),
                            ("e_up", (held, c.hidden_size, inter)),
                            ("e_down", (held, inter, c.hidden_size)))}
    # experts 3.. are held elsewhere; under the bound expert 1 is empty
    idx = jnp.asarray(np.tile([0, 1], (t, 1)) if over else
                      [[0, 2] if i % 3 == 0 else [2 + i % 2 * 3, 6]
                       for i in range(t)], jnp.int32)
    seen = jnp.asarray(rng.standard_normal((t, c.hidden_size)), jnp.float32)

    def layer(h, w, p):
        out, load = lm.routed_experts(h, idx, w, p, c, mellum._experts)
        return jnp.sum(out * seen), (out, load)
    return (jax.value_and_grad(layer, argnums=(0, 1, 2), has_aux=True),
            (h, w, p), idx, lm.live_rows_bound(t * k, c))


@pytest.mark.parametrize("over", [False, True], ids=["under", "over"])
def test_the_expert_layer_on_the_kernels_is_the_layer_on_the_products(
        over, request):
    """`routed_experts` with the kernels (in the interpreter, at a shape
    the rule is made to take) against the same call on the product
    path, value and the gradients in `h`, `w` and every expert matrix,
    under the bound and over it.  The kernels round as the chip does
    and the CPU's products do not, so they stand a bfloat16 rounding
    apart (7.8e-3 of the largest value at most); a missed piece stands
    at 1.  The count's fourth entry is the pieces that hold a one."""
    both, args, idx, bound = _layer(over)
    (_, (want, load)), g_want = both(*args)
    assert load.shape == (3,) and (int(load[0]) > bound) == over
    request.getfixturevalue("tiny_kernels")
    (_, (got, counted)), g_got = both(*args)
    assert counted.shape == (4,)
    assert np.array_equal(np.asarray(counted[:3]), np.asarray(load))
    for a, b in zip((got, *jax.tree.leaves(g_got)),
                    (want, *jax.tree.leaves(g_want))):
        assert np.isfinite(np.asarray(a)).all() and np.any(a)
        assert 0 < float(jnp.max(jnp.abs(a - b))) <= 7.8e-3 * float(
            jnp.max(jnp.abs(b)))
    # brute force: the sort, the matrix, its pieces of 8 x 8
    t, k = idx.shape
    key = np.where(np.asarray(idx) < 3, np.asarray(idx), 3).reshape(-1)
    order = np.argsort(key, kind="stable")
    rows = t * k if over else bound
    dense = np.zeros((rows, t), bool)
    n_here = int((key < 3).sum())
    live = np.arange(min(rows, n_here))
    dense[live, order[live] // k] = True
    pieces = dense.reshape(rows // 8, 8, t // 8, 8).any(axis=(1, 3)).sum()
    assert 0 < pieces < dense.size // 64
    assert int(counted[3]) == pieces * 64 // placement_kernel.PAIRS_UNIT


def test_elsewhere_than_a_tpu_the_taken_shape_runs_the_product(monkeypatch):
    """At a shape the rule takes the program branches on the platform:
    on the CPU both products are `jnp.dot` with the matrix written out,
    and the fourth count is the whole matrix."""
    both, args, _, bound = _layer(False)
    (_, (want, _)), g_want = both(*args)
    monkeypatch.setattr(placement_kernel, "takes",
                        lambda rows, tokens, hidden: True)
    monkeypatch.setattr(placement_kernel, "plan", functools.partial(
        placement_kernel.plan, tile=8, block=8))
    multiply = placement_kernel.multiply        # traced, never lowered
    monkeypatch.setattr(
        placement_kernel, "multiply",
        lambda x, plan, back, passes: multiply(x, plan, back, passes,
                                               x.shape[1]))
    monkeypatch.setattr(placement_kernel, "PAIRS_UNIT", 8)
    (_, (got, counted)), g_got = both(*args)
    assert int(counted[3]) == bound * 24 // 8
    for a, b in zip((got, *jax.tree.leaves(g_got)),
                    (want, *jax.tree.leaves(g_want))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_mellums_counters_read_the_layers_count(tiny_kernels):
    """`mellum`'s `fit_counted` with the kernels taking its tiny
    shape: `moe.place_pairs` is what the layers counted on the device —
    here under the whole matrix — and `moe.place_pairs_dense` the whole
    matrix, as before."""
    task = get_task("mellum", ModelConfig(
        model_json="benchmark/families/mellum/tiny.model.json",
        num_max_iter=1, local_learning_rate=0.01))
    rows = jnp.asarray(np.random.default_rng(0).integers(
        0, task.arch.vocab_held, (2, task.row_width)), jnp.int32)
    _, _, stats = task.fit_counted(
        task.unflatten(task.init_params()), rows, None, jnp.ones((2,)))
    counted = dict(zip(task.counter_names, np.asarray(stats).tolist()))
    assert len(task.counter_names) == stats.shape[0]
    over = counted["moe.passes_over_bound"]
    under, beyond = mellum.place_pairs(2 * task.arch.sequence_length,
                                       task.arch)
    assert counted["moe.place_pairs_dense"] == (
        2 * (4 * under // 1024) + over * ((beyond - under) // 1024))
    assert 0 <= counted["moe.place_pairs"] < counted["moe.place_pairs_dense"]

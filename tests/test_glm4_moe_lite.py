"""The `glm4_moe_lite` family (models/glm4_moe_lite.py) at its tiny size
on the CPU: against the benchmark's plain reference
(benchmark/families/glm4-moe-lite/reference.py) on seeded random
weights, the share of an expert layer against the uncut layer, the
folded worker axis against the `vmap`, token rows through the buffers,
and the task through the CLI's own drives.

Both sides run float32 at the CPU's full precision, so they agree to
round-off: 1e-5 relative is ten times the worst seen (a few 1e-6: sums
over 64..128 terms in another order), and far under anything a changed
formula would give."""

import dataclasses
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.models import glm4_moe_lite as glm
from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models.task import get_task
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(ROOT, "benchmark", "families", "glm4-moe-lite")
TINY = "benchmark/families/glm4-moe-lite/tiny.model.json"
RTOL = 1e-5


def family(part):
    """A file of the benchmark's family, as a module (the harness loads
    it the same way)."""
    name = "glm_family_test_" + part
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(FAMILY, part + ".py"))
    module = importlib.util.module_from_spec(spec)
    import sys
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def ref():
    return family("reference")


@pytest.fixture(scope="module")
def ps_cfg():
    return PSConfig(num_workers=3, task="glm4_moe_lite",
                    model=ModelConfig(num_max_iter=2,
                                      local_learning_rate=0.05,
                                      model_json=TINY),
                    buffer=BufferConfig(min_size=1, max_size=2))


@pytest.fixture(scope="module")
def task(ps_cfg):
    return get_task("glm4_moe_lite", ps_cfg.model)


@pytest.fixture(scope="module")
def theta(task):
    """Seeded random weights: the stated start, moved off it so that
    norm weights are not one and the selection bias is not zero."""
    rng = np.random.default_rng(7)
    start = np.asarray(task.init_params())
    return (start + 0.05 * rng.standard_normal(start.shape)).astype(
        np.float32)


def rows_of(task, n, seed=3):
    c = task.arch
    return np.random.default_rng(seed).integers(
        0, c.vocab_held, size=(n, c.row_width)).astype(np.int32)


def close(got, want, scale=None):
    got, want = np.asarray(got), np.asarray(want)
    scale = float(np.max(np.abs(want))) if scale is None else scale
    assert np.max(np.abs(got - want)) <= RTOL * max(scale, 1e-30), (
        float(np.max(np.abs(got - want))), scale)


def test_the_flat_layout_is_the_references(task, ref, ps_cfg):
    s = ref.shapes(ps_cfg)
    assert [(n, tuple(sh)) for n, sh in s.leaves()] == glm.leaf_specs(
        task.arch)
    assert s.num_params == task.num_params
    # and the stated start is the same to the last bit
    assert np.array_equal(np.asarray(task.init_params()),
                          ref.init_params(s))


# -- the program's list against the wire's: three expert layers ---------------

@pytest.fixture(scope="module")
def deep_cfg(tmp_path_factory):
    """The tiny size with THREE expert layers (the family's tiny file
    has one, and a stack of one layer shows nothing of the stack)."""
    body = json.load(open(os.path.join(ROOT, TINY)))
    body["num_hidden_layers"] = 4
    path = tmp_path_factory.mktemp("glm_deep") / "deep.model.json"
    path.write_text(json.dumps(body))
    return PSConfig(num_workers=2, task="glm4_moe_lite",
                    model=ModelConfig(num_max_iter=2,
                                      local_learning_rate=0.05,
                                      model_json=str(path)),
                    buffer=BufferConfig(min_size=1, max_size=2))


@pytest.fixture(scope="module")
def deep(deep_cfg):
    return get_task("glm4_moe_lite", deep_cfg.model)


def test_the_programs_list_covers_the_flat_vector_as_the_wires(deep, ref,
                                                               deep_cfg):
    """A stacked leaf of the wire is row-major, so the program's
    `moe.<l>.<name>` are its contiguous runs: cutting a flat vector
    into the program's leaves and joining them again gives the vector
    to the bit, and every leaf is the reference's view of the same
    vector (its layer of it, where the wire stacks)."""
    s = ref.shapes(deep_cfg)
    layers = deep.arch.num_moe_layers
    assert layers == 3 and s.num_params == deep.num_params
    assert lm.num_params(glm.leaf_specs(deep.arch)) == deep.num_params
    theta = np.random.default_rng(1).standard_normal(
        deep.num_params).astype(np.float32)
    leaves = deep.unflatten(jnp.asarray(theta))
    assert list(leaves) == [n for n, _ in glm.layer_specs(deep.arch)]
    assert np.array_equal(np.asarray(deep.flatten(leaves)), theta)
    seen = set()
    for name, wire in ref.split(theta, s).items():
        if not name.startswith("moe."):
            assert np.array_equal(np.asarray(leaves[name]), wire), name
            seen.add(name)
            continue
        assert wire.shape[0] == layers
        for at in range(layers):
            mine = f"moe.{at}.{name[4:]}"
            assert np.array_equal(np.asarray(leaves[mine]), wire[at]), mine
            seen.add(mine)
    assert seen == set(leaves)
    # and the stated start, drawn a stacked leaf at a time, is the
    # reference's to the last bit at this depth too
    assert np.array_equal(np.asarray(deep.init_params()), ref.init_params(s))


def _scanned_nll(leaves, rows, c):
    """The oracle: the trunk as one plain `lax.scan` over the expert
    layers' leaves stacked again (what the program ran before its
    layers were written out) -> (next-token losses, the layers' loads)."""
    stacked = {name: jnp.stack([leaves[f"moe.{at}.{name}"]
                                for at in range(c.num_moe_layers)])
               for name, _ in glm._moe_specs(c)}
    s = c.sequence_length
    x = leaves["embed"][rows[:, :s]]
    x = glm.dense_block(x, lm.sub(leaves, "dense."), c)
    x, loads = jax.lax.scan(lambda x, p: glm.moe_block(x, p, c), x, stacked)
    nll, _ = lm.head_nll(x, leaves["final_norm"], leaves["head"],
                         rows[:, 1:s + 1], c.rms_norm_eps)
    return nll, loads


def test_the_written_out_layers_are_a_scan_over_the_stack(deep):
    """`forward`, its gradient and the loads of the expert layers equal
    the scan over the re-stacked leaves, kept here as the oracle."""
    c = deep.arch
    rng = np.random.default_rng(7)
    start = np.asarray(deep.init_params())
    leaves = deep.unflatten(jnp.asarray(
        start + 0.05 * rng.standard_normal(start.shape).astype(np.float32)))
    rows = rows_of(deep, 2)

    def program(leaves):
        out = glm.forward(leaves, rows, c)
        return out["nll"].sum(), (out["nll"], out["loads"])

    def oracle(leaves):
        nll, loads = _scanned_nll(leaves, rows, c)
        return nll.sum(), (nll, loads)
    (_, (nll, loads)), grads = jax.value_and_grad(
        program, has_aux=True)(leaves)
    (_, (want_nll, want_loads)), want = jax.value_and_grad(
        oracle, has_aux=True)(leaves)
    close(nll, want_nll)
    # the module's block comes after the trunk's in `loads`
    assert loads.shape == (c.num_moe_layers + 1, 3)
    assert np.array_equal(np.asarray(loads[:c.num_moe_layers]),
                          np.asarray(want_loads))
    moved = 0
    for name in leaves:
        if name.endswith("router_bias") or name.startswith("mtp."):
            assert not np.any(grads[name]) and not np.any(want[name]), name
        else:
            close(grads[name], want[name])
            moved += bool(np.any(want[name]))
    assert moved == len(leaves) - c.num_moe_layers - sum(
        n.startswith("mtp.") for n in leaves)


def test_the_lowered_chunk_walks_no_stack_of_layers(deep, deep_cfg):
    """The folded chunk as it is lowered: under `kps.lm.layers` no loop
    (a `scan` lowers to a `while`) and no slice taken or written at a
    running index, and nowhere in the program a dynamic slice or update
    whose operand or result has a leaf's shape, the wire's stacked
    shapes among them."""
    c, w, cap = deep.arch, deep_cfg.num_workers, 2
    shaped = jax.ShapeDtypeStruct
    chunk = bsp.make_bsp_multi_step(deep_cfg.model, w, deep_cfg.server_lr, 8,
                                    task=deep)
    text = chunk.lower(
        jax.eval_shape(deep.unflatten,
                       shaped((deep.num_params,), jnp.float32)),
        shaped((w, cap, deep.row_width), jnp.int32),
        shaped((w, cap), jnp.int32),
        shaped((w, cap), jnp.float32)).as_text(debug_info=True)
    names = re.findall(r'loc\("([^"]*kps\.lm\.layers[^"]*)"', text)
    assert len(names) > 100             # the reader sees the scope
    assert any("/kps.mla" in n for n in names)
    walked = [n for n in names if re.search(
        r"kps\.lm\.layers.*/(scan|while|dynamic_slice|dynamic_update_slice)"
        r"\b", n)]
    assert walked == []
    # the fold over the workers and the scan over the clocks are loops,
    # and slice the slabs and write the losses: none touches a leaf
    matrices = {"x".join(map(str, sh)) + "xf32"
                for specs in (glm.leaf_specs(c), glm.layer_specs(c))
                for _, sh in specs if len(sh) >= 2}
    sliced = [line for line in text.splitlines()
              if re.search(r"stablehlo\.dynamic_(update_)?slice", line)]
    assert sliced and "stablehlo.while" in text
    assert not [line for line in sliced
                if set(re.findall(r"tensor<([\dx]+xf32)>", line)) & matrices]


def test_loss_and_gradients_agree_with_the_reference(task, ref, ps_cfg,
                                                     theta):
    s = ref.shapes(ps_cfg)
    rows, mask = rows_of(task, 2), jnp.asarray([1.0, 1.0])
    switches = ref.Reference(s).switches

    def program(t):
        return glm.loss_and_counts(task.unflatten(t), rows, mask,
                                   task.arch)[0]

    def reference(t):
        return ref._objective(ref.split(t, s), jnp.asarray(rows), mask, s,
                              switches)
    got, got_g = jax.value_and_grad(program)(jnp.asarray(theta))
    want, want_g = jax.value_and_grad(reference)(jnp.asarray(theta))
    close(got, want)
    for (name, _), g, w in zip(
            s.leaves(), ref.split(np.asarray(got_g), s).values(),
            ref.split(np.asarray(want_g), s).values()):
        if name.endswith("router_bias"):
            assert not np.any(g) and not np.any(w)     # it only selects
        else:
            close(g, w)


def test_a_masked_row_adds_nothing(task, theta):
    rows = rows_of(task, 2)
    leaves = task.unflatten(jnp.asarray(theta))
    both, _ = glm.loss_and_counts(leaves, rows, jnp.asarray([1.0, 0.0]),
                                  task.arch)
    alone, _ = glm.loss_and_counts(leaves, rows[:1], jnp.asarray([1.0]),
                                   task.arch)
    close(both, alone)


@pytest.mark.parametrize("rounds", [1, 2])
def test_bsp_rounds_agree_with_the_reference(task, ref, ps_cfg, theta,
                                             rounds):
    s = ref.shapes(ps_cfg)
    w = ps_cfg.num_workers
    x = np.stack([rows_of(task, 2, seed=10 + i) for i in range(w)])
    y = np.zeros((w, 2), np.int32)
    mask = np.ones((w, 2), np.float32)
    mask[1, 1] = 0.0                    # one worker's buffer half full
    slabs = [(x[i], y[i], mask[i]) for i in range(w)]
    want_t, want_l = ref.Reference(s).run(theta, slabs, rounds,
                                          keep_every=rounds)
    chunk = bsp.make_bsp_multi_step(ps_cfg.model, w, ps_cfg.server_lr,
                                    rounds, task=task)
    leaves, losses, counted = chunk(task.unflatten(jnp.asarray(theta)),
                                    x, y, mask)
    got = np.asarray(task.flatten(leaves))
    close(got - theta, want_t[-1] - theta)
    assert ref.param_gap(got, want_t[-1], theta, s) <= RTOL
    close(losses, want_l, scale=1.0)
    counted = dict(zip(task.counter_names, np.asarray(counted)))
    c = task.arch
    passes = rounds * w * (ps_cfg.model.num_max_iter + 1)
    blocks = c.num_moe_layers + c.num_nextn_predict_layers
    assert (counted["moe.assignments_here"] + counted["moe.assignments_away"]
            == passes * 2 * c.sequence_length * c.num_experts_per_tok
            * blocks)
    assert 0 <= counted["moe.passes_over_bound"] <= passes * blocks
    assert counted["data.tokens"] == rounds * 5 * c.sequence_length
    assert counted["data.pad_tokens"] == rounds * 1 * c.sequence_length


def test_the_single_step_is_one_round_of_the_chunk(task, ps_cfg, theta):
    w = ps_cfg.num_workers
    x = np.stack([rows_of(task, 2, seed=20 + i) for i in range(w)])
    y, mask = np.zeros((w, 2), np.int32), np.ones((w, 2), np.float32)
    step = bsp.make_bsp_step(ps_cfg.model, w, ps_cfg.server_lr, task=task)
    chunk = bsp.make_bsp_multi_step(ps_cfg.model, w, ps_cfg.server_lr, 1,
                                    task=task)
    a, loss_a, _ = step(task.unflatten(jnp.asarray(theta)), x, y, mask)
    b, loss_b, _ = chunk(task.unflatten(jnp.asarray(theta)), x, y, mask)
    assert loss_a.shape == () and loss_b.shape == (1,)
    for name in a:
        assert np.array_equal(np.asarray(a[name]), np.asarray(b[name]))


def test_logits_at_a_position_do_not_see_later_tokens(task, theta):
    """The prefix property of the causal latent attention (and of the
    per-token expert layers)."""
    c = task.arch
    leaves = task.unflatten(jnp.asarray(theta))
    row = rows_of(task, 1)
    cut = 6
    other = row.copy()
    other[:, cut + 1:] = (other[:, cut + 1:] + 17) % c.vocab_held
    a = glm.forward(leaves, row, c, with_logits=True)["logits"]
    b = glm.forward(leaves, other, c, with_logits=True)["logits"]
    close(a[:, :cut + 1], b[:, :cut + 1])
    assert np.max(np.abs(np.asarray(a[:, cut + 1:] - b[:, cut + 1:]))) > 1e-3


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_shares_of_an_expert_layer_sum_to_the_uncut_layer(task, ref,
                                                              ps_cfg, theta,
                                                              held):
    """Over the shares of one expert layer (8 experts: 8 shares of one,
    4 of two, ...), the routed parts summed and the shared expert
    counted once equal the reference's layer with every expert held."""
    c = task.arch
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.standard_normal((40, c.hidden_size)), jnp.float32)
    e, hd, i = c.n_routed_experts, c.hidden_size, c.moe_intermediate_size
    full = {"router": 0.5 * rng.standard_normal((hd, e)),
            "router_bias": 0.1 * rng.standard_normal((e,)),
            "e_gate": 0.1 * rng.standard_normal((e, hd, i)),
            "e_up": 0.1 * rng.standard_normal((e, hd, i)),
            "e_down": 0.1 * rng.standard_normal((e, i, hd)),
            "s_gate": 0.1 * rng.standard_normal((hd, i)),
            "s_up": 0.1 * rng.standard_normal((hd, i)),
            "s_down": 0.1 * rng.standard_normal((i, hd))}
    full = {k: jnp.asarray(v, jnp.float32) for k, v in full.items()}
    uncut = dataclasses.replace(ref.shapes(ps_cfg), experts_held=e,
                                expert_offset=0)
    want, _ = ref._experts(h, full, uncut, ref.Reference(uncut).switches)

    total = glm.swiglu(h, full["s_gate"], full["s_up"], full["s_down"])
    here = 0
    for offset in range(0, e, held):
        share = dataclasses.replace(c, experts_held=held,
                                    expert_offset=offset)
        p = dict(full, **{k: full[k][offset:offset + held]
                          for k in ("e_gate", "e_up", "e_down")})
        idx, w = lm.route(h, p["router"], p["router_bias"], share)
        part, load = lm.routed_experts(h, idx, w, p, share,
                                        glm.swiglu_experts)
        total = total + part
        here += int(load[0])
    assert here == 40 * c.num_experts_per_tok     # every choice, once
    close(total, want)


@pytest.mark.parametrize("favoured,here", [((0, 1), True), ((6, 7), False)])
def test_no_token_is_dropped_when_every_token_goes_one_way(task, favoured,
                                                           here):
    """A router forced to send every token to the same two experts:
    held here, every assignment is computed (the largest group is every
    token); held elsewhere, none is and nothing is added."""
    c = task.arch                                  # holds experts 0, 1
    rng = np.random.default_rng(5)
    t = 2 * c.sequence_length
    h = jnp.asarray(rng.standard_normal((t, c.hidden_size)), jnp.float32)
    p = {k: jnp.asarray(0.1 * rng.standard_normal(s), jnp.float32)
         for k, s in (("e_gate", (2, c.hidden_size, 32)),
                      ("e_up", (2, c.hidden_size, 32)),
                      ("e_down", (2, 32, c.hidden_size)))}
    bias = np.zeros((c.n_routed_experts,), np.float32)
    bias[list(favoured)] = 10.0
    router = jnp.asarray(rng.standard_normal(
        (c.hidden_size, c.n_routed_experts)), jnp.float32)
    idx, w = lm.route(h, router, jnp.asarray(bias), c)
    assert sorted(np.unique(np.asarray(idx))) == list(favoured)
    got, load = lm.routed_experts(h, idx, w, p, c,
                                        glm.swiglu_experts)
    if not here:
        assert np.asarray(load).tolist() == [0, 0, 0] and not np.any(got)
        return
    # every slot is live, over the bound: the pass places them all
    assert lm.live_rows_bound(t * 2, c) < t * 2
    assert np.asarray(load).tolist() == [t * 2, t, 1]
    # the weights follow the position an expert was chosen at
    w_of = jnp.zeros((t, 2)).at[jnp.arange(t)[:, None], idx].set(w)
    want = sum(w_of[:, e, None] * glm.swiglu(h, p["e_gate"][e],
                                             p["e_up"][e], p["e_down"][e])
               for e in range(2))
    close(got, want)


@pytest.mark.parametrize("hot", [-10.0, 0.0, 10.0])
def test_the_grouped_products_follow_any_routing(task, hot):
    """Few, some or all assignments routed to the experts held here —
    under the bound of rows the layer places, and over it, where it
    places every slot: the same sum as every held expert over its own
    tokens."""
    c = task.arch
    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.standard_normal((28, c.hidden_size)), jnp.float32)
    p = {k: jnp.asarray(0.1 * rng.standard_normal(s), jnp.float32)
         for k, s in (("e_gate", (2, c.hidden_size, 32)),
                      ("e_up", (2, c.hidden_size, 32)),
                      ("e_down", (2, 32, c.hidden_size)))}
    bias = jnp.asarray([hot, hot] + [0.0] * 6, jnp.float32)
    router = jnp.asarray(rng.standard_normal((c.hidden_size, 8)),
                         jnp.float32)
    idx, w = lm.route(h, router, bias, c)
    got, load = lm.routed_experts(h, idx, w, p, c,
                                        glm.swiglu_experts)
    w_of = np.zeros((28, 8), np.float32)
    np.put_along_axis(w_of, np.asarray(idx), np.asarray(w), axis=1)
    want = sum(w_of[:, e, None] * np.asarray(glm.swiglu(
        h, p["e_gate"][e], p["e_up"][e], p["e_down"][e]))
        for e in range(2))
    assert int(load[0]) == int((np.asarray(idx) < 2).sum())
    assert {-10.0: int(load[0]) < 14, 0.0: True,
            10.0: int(load[0]) == 56}[hot]
    assert lm.live_rows_bound(56, c) == 32
    assert int(load[2]) == (int(load[0]) > 32)
    assert int(load[2]) == {-10.0: 0, 0.0: int(load[2]), 10.0: 1}[hot]
    close(got, want)


def _leaving_nan(expert):
    """`expert` over a grouped product whose dx holds NaN in the rows
    past the last group: what the chip's untold kernel, which leaves
    those rows as it found them, hands back from a buffer that has held
    NaN."""
    def over_nan(xs, p, dot):
        @jax.custom_vjp
        def product(rows, matrices):
            return dot(rows, matrices)

        def forward(rows, matrices):
            return jax.vjp(dot, rows, matrices)

        def backward(back, dy):
            d_rows, d_matrices = back(dy)
            dead = jnp.arange(d_rows.shape[0]) >= dot.sizes.sum()
            return jnp.where(dead[:, None], jnp.nan, d_rows), d_matrices
        product.defvjp(forward, backward)
        product.sizes = dot.sizes
        return expert(xs, p, product)
    return over_nan


def test_rows_past_the_last_group_take_no_gradient(task):
    """The family's expert wraps its rows in `lm.live_rows_only`: a dx
    poisoned past the last group reaches no token and no matrix.  With
    the layers scanned over their stack the chip's buffers happened to
    hold finite numbers there; written out they hold NaN, and every
    parameter was NaN after the first clock (PERF.md section 6, PR 38)."""
    c = task.arch
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((32, c.hidden_size)), jnp.float32)
    p = {k: jnp.asarray(0.1 * rng.standard_normal(s), jnp.float32)
         for k, s in (("e_gate", (2, c.hidden_size, 32)),
                      ("e_up", (2, c.hidden_size, 32)),
                      ("e_down", (2, 32, c.hidden_size)))}
    idx, w = lm.route(h, jnp.asarray(rng.standard_normal(
        (c.hidden_size, c.n_routed_experts)), jnp.float32),
        jnp.zeros((c.n_routed_experts,)), c)

    def through(expert):
        def loss(h, p):
            out, load = lm.routed_experts(h, idx, w, p, c, expert)
            return jnp.sum(out ** 2), load
        return jax.grad(loss, argnums=(0, 1), has_aux=True)(h, p)
    (dh, dp), load = through(glm._experts)
    assert 0 < int(load[0]) < lm.live_rows_bound(64, c) == 32   # dead rows
    assert np.any(dh) and all(np.any(g) for g in dp.values())
    (got_h, got_p), _ = through(_leaving_nan(glm._experts))
    close(got_h, dh)
    for name in dp:
        close(got_p[name], dp[name])
    # the gated expert alone, as the family handed it over before
    (bare_h, _), _ = through(_leaving_nan(glm.swiglu_experts))
    assert np.isnan(np.asarray(bare_h)).all()


def test_evaluation_agrees_with_the_reference(task, ref, ps_cfg, theta):
    s = ref.shapes(ps_cfg)
    test_rows = rows_of(task, 3, seed=4)
    got = task.evaluate(jnp.asarray(theta), test_rows, None)
    want = ref.Reference(s).evaluate(theta, (test_rows, None))
    close(got.loss, want["loss"])
    close(got.f1, want["f1"], scale=1.0)
    close(got.accuracy, want["accuracy"], scale=1.0)


def test_weighted_f1_by_class_is_the_confusion_matrix_one():
    from kafka_ps_tpu.models import metrics
    rng = np.random.default_rng(0)
    preds = jnp.asarray(rng.integers(0, 7, size=200))
    labels = jnp.asarray(rng.integers(0, 7, size=200))
    a = metrics.weighted_f1_accuracy(preds, labels, 7)
    b = metrics.weighted_f1_accuracy_by_class(preds, labels, 7)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_each_control_of_the_reference_moves_the_result(ref, ps_cfg, task,
                                                        theta):
    """What the benchmark's controls stand for is seen by the numbers
    the cell compares, already at the tiny size."""
    s = ref.shapes(ps_cfg)
    slabs = [(rows_of(task, 1, seed=30), None, np.ones(1, np.float32))]
    s2 = dataclasses.replace(s, num_workers=1)
    want_t, want_l = ref.Reference(s2).run(theta, slabs, 1)
    for name, kwargs in ref.CONTROLS.items():
        got_t, got_l = ref.Reference(s2, **kwargs).run(theta, slabs, 1)
        gap = ref.param_gap(got_t[-1], want_t[-1], theta, s2)
        loss = max(abs(g - w) / w for g, w in zip(got_l, want_l))
        assert gap > 1e-3 or loss > 1e-3, (name, gap, loss)

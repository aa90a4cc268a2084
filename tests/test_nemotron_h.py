"""The `nemotron_h` family (models/nemotron_h.py) at its tiny size on the
CPU: against the benchmark's plain reference
(benchmark/families/nemotron-h/reference.py) on seeded random weights —
forward, loss, gradients and fused clocks — the chunked scan against the
token-by-token recurrence, the share of an expert layer against the
uncut layer, and each of the reference's controls.

Both sides run float32 at the CPU's full precision, so they agree to
round-off: 1e-5 relative is ten times the worst seen (a few 1e-6: sums
over 64..128 terms in another order), and far under anything a changed
formula would give."""

import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import nemotron_h as nh
from kafka_ps_tpu.models.task import get_task
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(ROOT, "benchmark", "families", "nemotron-h")
TINY = "benchmark/families/nemotron-h/tiny.model.json"
PUBLISHED = "benchmark/configs/nemotron-3-nano-ep16.model.json"
RTOL = 1e-5


def family(part):
    """A file of the benchmark's family, as a module (the harness loads
    it the same way)."""
    name = "nemotron_family_test_" + part
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(FAMILY, part + ".py"))
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


@pytest.fixture(scope="module")
def ref():
    return family("reference")


@pytest.fixture(scope="module")
def ps_cfg():
    return PSConfig(num_workers=3, task="nemotron_h",
                    model=ModelConfig(num_max_iter=2,
                                      local_learning_rate=0.05,
                                      model_json=TINY),
                    buffer=BufferConfig(min_size=1, max_size=2))


@pytest.fixture(scope="module")
def task(ps_cfg):
    return get_task("nemotron_h", ps_cfg.model)


@pytest.fixture(scope="module")
def theta(task):
    """Seeded random weights: the stated start, moved off it so that
    norm weights and D are not one and the selection bias is not zero."""
    rng = np.random.default_rng(7)
    start = np.asarray(task.init_params())
    return (start + 0.05 * rng.standard_normal(start.shape)).astype(
        np.float32)


def rows_of(task, n, seed=3):
    c = task.arch
    return np.random.default_rng(seed).integers(
        0, c.vocab_held, size=(n, task.row_width)).astype(np.int32)


def close(got, want, scale=None):
    got, want = np.asarray(got), np.asarray(want)
    scale = float(np.max(np.abs(want))) if scale is None else scale
    assert np.max(np.abs(got - want)) <= RTOL * max(scale, 1e-30), (
        float(np.max(np.abs(got - want))), scale)


def test_the_flat_layout_is_the_references(task, ref, ps_cfg):
    s = ref.shapes(ps_cfg)
    assert [(n, tuple(sh)) for n, sh in s.leaves()] == nh.leaf_specs(
        task.arch)
    assert s.num_params == task.num_params
    # and the stated start is the same to the last bit
    assert np.array_equal(np.asarray(task.init_params()),
                          ref.init_params(s))


def test_the_count_of_file_program_reference_and_costs_agree(ref):
    """At the published widths, from shapes alone: the configuration's
    `num_params`, the program's flat key space, the reference's and the
    benchmark's cost functions count the same 666,963,456."""
    stated = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "nemotron-3-nano-ep16.json")))
    assert stated["num_params"] == 666_963_456
    assert nh.num_params(nh.load_config(PUBLISHED)) == stated["num_params"]
    cfg = PSConfig(task="nemotron_h", model=ModelConfig(model_json=PUBLISHED))
    assert ref.shapes(cfg).num_params == stated["num_params"]
    costs = family("costs")
    assert costs.num_params(costs.model_file(cfg)) == stated["num_params"]
    by_kind = {k: lm.num_params(nh.block_specs(k, nh.load_config(PUBLISHED)))
               for k in "M*E"}
    assert by_kind == {"M": 38_744_896, "*": 23_399_040, "E": 100_125_440}


def test_the_initial_state_space_leaves_are_as_stated(task):
    c = task.arch
    leaves = {k: np.asarray(v) for k, v in nh.init_leaves(c).items()}
    a = np.exp(leaves["b0.A_log"])
    assert np.all((a >= 1.0) & (a <= 16.0))
    dt = np.log1p(np.exp(leaves["b0.dt_bias"]))        # softplus
    assert np.all((dt >= c.time_step_min * 0.999)
                  & (dt <= c.time_step_max * 1.001))
    assert np.all(leaves["b0.D"] == 1.0)
    assert np.all(leaves["b0.gate_norm"] == 1.0)
    assert np.all(leaves["b1.router_bias"] == 0.0)
    assert np.abs(leaves["b0.conv_w"]).max() <= 0.5
    assert 0.01 < leaves["b0.w_in"].std() < 0.03


def test_loss_and_gradients_agree_with_the_reference(task, ref, ps_cfg,
                                                     theta):
    s = ref.shapes(ps_cfg)
    rows, mask = rows_of(task, 2), jnp.asarray([1.0, 1.0])
    switches = ref.Reference(s).switches

    def program(t):
        return nh.loss_and_counts(task.unflatten(t), rows, mask,
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
            assert np.any(w), name                     # every leaf is used
            close(g, w)


def test_the_forward_pass_agrees_with_the_reference_row_by_row(task, ref,
                                                               ps_cfg,
                                                               theta):
    s = ref.shapes(ps_cfg)
    rows = rows_of(task, 2, seed=5)
    out = nh.forward(task.unflatten(jnp.asarray(theta)), rows, task.arch,
                     with_logits=True)
    for i, (nll, preds, choices) in enumerate(
            ref.Reference(s).forward_rows(theta, rows)):
        close(out["nll"][i], nll)
        assert np.array_equal(np.argmax(np.asarray(out["logits"][i]), -1),
                              preds)
        held = choices[..., s.expert_offset:s.expert_offset
                       + s.experts_held].sum()
        if i == 0:
            alone = nh.forward(task.unflatten(jnp.asarray(theta)),
                               rows[:1], task.arch)
            assert int(alone["loads"][:, 0].sum()) == int(held)


def test_a_masked_row_adds_nothing(task, theta):
    rows = rows_of(task, 2)
    leaves = task.unflatten(jnp.asarray(theta))
    both, _ = nh.loss_and_counts(leaves, rows, jnp.asarray([1.0, 0.0]),
                                 task.arch)
    alone, _ = nh.loss_and_counts(leaves, rows[:1], jnp.asarray([1.0]),
                                  task.arch)
    close(both, alone)


@pytest.mark.parametrize("rounds", [1, 2, 8])
def test_fused_clocks_agree_with_the_reference(task, ref, ps_cfg, theta,
                                               rounds):
    """The folded scan chunk of `rounds` clocks (8: the cell's chunk)
    against as many reference rounds on the same slabs."""
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
    # round-off grows with the clocks: each starts from the last one's
    scale = RTOL * rounds
    assert np.max(np.abs((got - theta) - (want_t[-1] - theta))) <= scale \
        * np.max(np.abs(want_t[-1] - theta))
    assert ref.param_gap(got, want_t[-1], theta, s) <= scale
    np.testing.assert_allclose(np.asarray(losses), want_l, rtol=scale)
    counted = dict(zip(task.counter_names, np.asarray(counted)))
    c = task.arch
    passes = rounds * w * (ps_cfg.model.num_max_iter + 1)
    assert task.counter_names[:len(lm.COUNTERS)] == lm.COUNTERS
    assert (counted["moe.assignments_here"] + counted["moe.assignments_away"]
            == passes * 2 * c.sequence_length * c.num_experts_per_tok
            * c.kinds("E"))
    assert 0 <= counted["moe.passes_over_bound"] <= passes * c.kinds("E")
    assert counted["data.tokens"] == rounds * 5 * c.sequence_length
    assert counted["data.pad_tokens"] == rounds * 1 * c.sequence_length
    # every row of a slab, masked or not, rides through every scan
    assert counted["ssm.chunks"] == passes * 2 * c.chunks_a_row \
        * c.kinds("M")


@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_the_chunked_scan_is_the_token_by_token_recurrence(ref, chunks):
    """`ssd_chunked` (intra-chunk products, chunk states, a scan over
    the chunks) against the definition, a step a token, at 1, 2 and 5
    chunks — and against the recurrence whose state is dropped at every
    chunk's start, which it must NOT equal once there are two chunks."""
    q, heads, p, groups, n = 8, 4, 6, 2, 5
    s = chunks * q
    rng = np.random.default_rng(chunks)
    x = rng.standard_normal((s, heads, p)).astype(np.float32)
    dt = np.log1p(np.exp(rng.standard_normal((s, heads)))).astype(np.float32)
    a = -np.exp(rng.uniform(0, 2.5, size=heads)).astype(np.float32)
    bm = rng.standard_normal((s, groups, n)).astype(np.float32)
    cm = rng.standard_normal((s, groups, n)).astype(np.float32)
    got = nh.ssd_chunked(x[None], dt[None], a, bm[None], cm[None], q)[0]
    per_head = lambda m: np.repeat(m, heads // groups, axis=1)
    want = ref._recurrence(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(a),
                           per_head(bm), per_head(cm), 0)
    close(got, want)
    broken = ref._recurrence(jnp.asarray(x), jnp.asarray(dt),
                             jnp.asarray(a), per_head(bm), per_head(cm), q)
    differs = float(np.max(np.abs(np.asarray(broken) - np.asarray(want))))
    assert (differs == 0.0) if chunks == 1 else (differs > 1e-2)
    # two rows at once are each row alone
    both = nh.ssd_chunked(np.stack([x, x[::-1]]), np.stack([dt, dt]), a,
                          np.stack([bm, bm]), np.stack([cm, cm]), q)
    close(both[0], want)


def test_the_convolution_is_causal_and_depthwise(ref):
    rng = np.random.default_rng(2)
    x = rng.standard_normal((9, 6)).astype(np.float32)
    w = rng.standard_normal((6, 4)).astype(np.float32)
    b = rng.standard_normal((6,)).astype(np.float32)
    got = np.asarray(nh.causal_conv(x[None], w, b)[0])
    close(got, ref._conv(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b)))
    # by hand: position 0 sees itself alone, position 3 its four taps
    np.testing.assert_allclose(got[0], x[0] * w[:, 3] + b, rtol=1e-6)
    np.testing.assert_allclose(
        got[3], sum(x[j] * w[:, j] for j in range(4)) + b, rtol=1e-5,
        atol=1e-6)


def test_the_gated_norm_gates_first_and_norms_inside_each_group():
    rng = np.random.default_rng(4)
    y = rng.standard_normal((1, 3, 8)).astype(np.float32)
    z = rng.standard_normal((1, 3, 8)).astype(np.float32)
    w = rng.standard_normal((8,)).astype(np.float32)
    got = np.asarray(nh.gated_group_norm(y, z, w, 2, 1e-5))
    v = y * (z / (1 + np.exp(-z)))
    want = np.concatenate(
        [v[..., g:g + 4] / np.sqrt((v[..., g:g + 4] ** 2).mean(
            -1, keepdims=True) + 1e-5) for g in (0, 4)], axis=-1) * w
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_a_row_that_is_not_a_whole_number_of_chunks_is_refused(tmp_path):
    body = json.load(open(os.path.join(ROOT, TINY)))
    path = tmp_path / "model.json"
    path.write_text(json.dumps(dict(body, sequence_length=18)))
    with pytest.raises(ValueError, match="whole number of scan chunks"):
        nh.load_config(str(path))
    path.write_text(json.dumps(dict(body, hybrid_override_pattern="MEM*")))
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nh.load_config(str(path))
    path.write_text(json.dumps(dict(body, experts_held=9)))
    with pytest.raises(ValueError, match="expert_offset"):
        nh.load_config(str(path))
    path.write_text(json.dumps(dict(body, model_type="glm4_moe_lite")))
    with pytest.raises(ValueError, match="is not nemotron_h"):
        nh.load_config(str(path))


def test_logits_at_a_position_do_not_see_later_tokens(task, theta):
    """The prefix property: the convolution, the scan (over a chunk's
    boundary too), attention and the per-token expert layers are causal."""
    c = task.arch
    leaves = task.unflatten(jnp.asarray(theta))
    row = rows_of(task, 1)
    cut = 5                             # inside the second chunk of 4
    other = row.copy()
    other[:, cut + 1:] = (other[:, cut + 1:] + 17) % c.vocab_held
    a = nh.forward(leaves, row, c, with_logits=True)["logits"]
    b = nh.forward(leaves, other, c, with_logits=True)["logits"]
    close(a[:, :cut + 1], b[:, :cut + 1])
    assert np.max(np.abs(np.asarray(a[:, cut + 1:] - b[:, cut + 1:]))) > 1e-3


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_shares_of_an_expert_layer_sum_to_the_uncut_layer(task, ref,
                                                              ps_cfg, held):
    """Over the shares of one expert layer (8 experts: 8 shares of one,
    4 of two, ...; 16 shares of 8 at the published widths), the routed
    parts summed and the shared expert counted once equal the
    reference's layer with every expert held."""
    c = task.arch
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.standard_normal((40, c.hidden_size)), jnp.float32)
    e, hd, i = c.n_routed_experts, c.hidden_size, c.moe_intermediate_size
    sh = c.moe_shared_expert_intermediate_size
    full = {"router": 0.5 * rng.standard_normal((hd, e)),
            "router_bias": 0.1 * rng.standard_normal((e,)),
            "e_up": 0.1 * rng.standard_normal((e, hd, i)),
            "e_down": 0.1 * rng.standard_normal((e, i, hd)),
            "s_up": 0.1 * rng.standard_normal((hd, sh)),
            "s_down": 0.1 * rng.standard_normal((sh, hd))}
    full = {k: jnp.asarray(v, jnp.float32) for k, v in full.items()}
    uncut = dataclasses.replace(ref.shapes(ps_cfg), experts_held=e,
                                expert_offset=0)
    want, _ = ref._experts(h, full, uncut, ref.Reference(uncut).switches)

    total = nh.relu2(h, full["s_up"], full["s_down"])
    here = 0
    for offset in range(0, e, held):
        share = dataclasses.replace(c, experts_held=held,
                                    expert_offset=offset)
        p = dict(full, **{k: full[k][offset:offset + held]
                          for k in ("e_up", "e_down")})
        idx, w = lm.route(h, p["router"], p["router_bias"], share)
        part, load = lm.routed_experts(h, idx, w, p, share,
                                        nh.relu2_experts)
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
         for k, s in (("e_up", (2, c.hidden_size, 32)),
                      ("e_down", (2, 32, c.hidden_size)))}
    bias = np.zeros((c.n_routed_experts,), np.float32)
    bias[list(favoured)] = 10.0
    router = jnp.asarray(rng.standard_normal(
        (c.hidden_size, c.n_routed_experts)), jnp.float32)
    idx, w = lm.route(h, router, jnp.asarray(bias), c)
    assert sorted(np.unique(np.asarray(idx))) == list(favoured)
    got, load = lm.routed_experts(h, idx, w, p, c,
                                        nh.relu2_experts)
    if not here:
        assert np.asarray(load).tolist() == [0, 0, 0] and not np.any(got)
        return
    # every slot is live, over the bound: the pass places them all
    assert lm.live_rows_bound(t * 2, c) < t * 2
    assert np.asarray(load).tolist() == [t * 2, t, 1]
    w_of = jnp.zeros((t, 2)).at[jnp.arange(t)[:, None], idx].set(w)
    want = sum(w_of[:, e, None] * nh.relu2(h, p["e_up"][e], p["e_down"][e])
               for e in range(2))
    close(got, want)


@pytest.mark.parametrize("shape,tiles", [
    # the cell's products, under the bound (768 rows) and over it (6,144)
    ((768, 2688, 1856), "256,896,640"), ((768, 1856, 2688), "256,640,896"),
    ((6144, 2688, 1856), "256,896,640"), ((6144, 1856, 2688), "256,640,896"),
    # rows that only 128 divides; a width with a prime count of 128s
    ((384, 2688, 1856), "128,896,640"), ((768, 2944, 1000), "256,768,512"),
    # the small shape the CPU test below runs
    ((128, 640, 384), "128,640,384"), ((512, 384, 640), "256,384,640"),
    # multiples of 512 (the GLM cell's), widths one block holds (the
    # tiny models'), rows no tile divides: the compiler's own choice
    ((1024, 2048, 1536), None), ((1024, 1536, 2048), None),
    ((4096, 2048, 1536), None), ((768, 64, 32), None), ((768, 32, 64), None),
    ((768, 512, 384), None), ((24, 640, 384), None), ((776, 2688, 1856), None),
])
def test_the_grouped_products_tiles_follow_the_shapes(shape, tiles):
    """`grouped_tiles`: a hint only where the kernel's own tiles are
    small, in whole 128s, no wider than the width padded to 128, the
    rows' tile dividing the rows, and the blocks of a grid step inside
    the budget (the transposes' accumulator counted)."""
    assert lm.grouped_tiles(*shape) == tiles
    if tiles is None:
        return
    (m, k, n), (tm, tk, tn) = shape, map(int, tiles.split(","))
    assert m % tm == 0 and tk % 128 == 0 and tn % 128 == 0
    assert tk < k + 128 and tn < n + 128
    assert lm.grouped_step_bytes(tm, tk, tn) <= lm.GROUPED_VMEM_BUDGET
    # the accumulator of the transposes is counted: 10.0 MB at the cell
    assert lm.grouped_step_bytes(256, 896, 640) == 10_027_008


@pytest.mark.parametrize("over", [False, True])
def test_a_hinted_grouped_product_is_the_dense_one(task, over):
    """At widths the rule hints (640 x 384) `routed_experts` lowers
    with the hint on its grouped products (the CPU takes it and ignores
    it) and equals the dense sum, forward and through `jax.grad`: a
    pass under `live_rows_bound` with an empty group, and one over it
    (every token to both held experts), which places every slot."""
    hd, inter, t = 640, 384, 128
    c = dataclasses.replace(task.arch, n_routed_experts=16, experts_held=2,
                            expert_offset=0, num_experts_per_tok=4)
    rng = np.random.default_rng(13)
    h = jnp.asarray(rng.standard_normal((t, hd)), jnp.float32)
    p = {k: jnp.asarray(0.05 * rng.standard_normal(s), jnp.float32)
         for k, s in (("e_up", (2, hd, inter)), ("e_down", (2, inter, hd)))}
    bias = np.zeros((16,), np.float32)
    bias[:2] = (10.0, 10.0) if over else (0.0, -10.0)
    idx, w = lm.route(h, jnp.asarray(rng.standard_normal((hd, 16)),
                                     jnp.float32), jnp.asarray(bias), c)
    seen = jnp.asarray(rng.standard_normal((t, hd)), jnp.float32)
    w_of = jnp.zeros((t, 16)).at[jnp.arange(t)[:, None], idx].set(w)

    def sparse(h, p):
        out, load = lm.routed_experts(h, idx, w, p, c, nh.relu2_experts)
        return jnp.sum(out * seen), (out, load)

    def dense(h, p):
        out = sum(w_of[:, e, None] * nh.relu2(h, p["e_up"][e], p["e_down"][e])
                  for e in range(2))
        return jnp.sum(out * seen), out

    bound = lm.live_rows_bound(t * 4, c)
    assert bound == 128 < t * 4
    text = jax.jit(sparse).lower(h, p).as_text()
    for rows, (k, n) in ((bound, (hd, inter)), (t * 4, (inter, hd))):
        assert f'ragged_dot_tiling = "{lm.grouped_tiles(rows, k, n)}"' in text
    (_, (got, load)), g_got = jax.value_and_grad(
        sparse, argnums=(0, 1), has_aux=True)(h, p)
    (_, want), g_want = jax.value_and_grad(
        dense, argnums=(0, 1), has_aux=True)(h, p)
    here, largest, went_over = np.asarray(load).tolist()
    assert went_over == over and (here > bound) == over
    assert over or largest == here          # the other group is empty
    close(got, want)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        close(a, b)


def test_a_told_products_dx_turns_its_tiles_round():
    """`told_grouped`: the product's lowering carries its own tiles
    only; its gradient's carries them (dW) and the same turned round
    (dx, whose contraction is the product's n and whose result its k),
    where `jax.grad` alone would hand dx the product's string."""
    rows, mats = jnp.ones((128, 640)), jnp.ones((2, 640, 384))
    sizes = jnp.asarray([30, 0], jnp.int32)
    own, round_ = ('ragged_dot_tiling = "128,640,384"',
                   'ragged_dot_tiling = "128,384,640"')

    def product(rows, mats):
        return lm.told_grouped(rows, mats, sizes, "128,640,384").sum()
    forward = jax.jit(product).lower(rows, mats).as_text()
    assert own in forward and round_ not in forward
    backward = jax.jit(jax.grad(product, argnums=(0, 1))).lower(
        rows, mats).as_text()
    assert own in backward and round_ in backward
    d_rows, d_mats = jax.grad(product, argnums=(0, 1))(rows, mats)
    want = jax.grad(lambda r, m: jax.lax.ragged_dot(r, m, sizes).sum(),
                    argnums=(0, 1))(rows, mats)
    close(d_rows, want[0])
    close(d_mats, want[1])


def test_evaluation_agrees_with_the_reference(task, ref, ps_cfg, theta):
    s = ref.shapes(ps_cfg)
    test_rows = rows_of(task, 3, seed=4)
    got = task.evaluate(jnp.asarray(theta), test_rows, None)
    want = ref.Reference(s).evaluate(theta, (test_rows, None))
    close(got.loss, want["loss"])
    close(got.f1, want["f1"], scale=1.0)
    close(got.accuracy, want["accuracy"], scale=1.0)


def _control_names():
    return ["theta_bf16", "top5", "no_shared", "relu_not_squared", "no_conv",
            "state_reset_each_chunk", "norm_before_gate", "no_D"]


def test_the_reference_has_the_controls_the_cell_names(ref):
    assert list(ref.CONTROLS) == _control_names()


@pytest.fixture(scope="module")
def one_clock(ref, ps_cfg, task, theta):
    """One worker, one row, one clock of the sound reference: what each
    control is set against."""
    s2 = dataclasses.replace(ref.shapes(ps_cfg), num_workers=1)
    slabs = [(rows_of(task, 1, seed=30), None, np.ones(1, np.float32))]
    return s2, slabs, ref.Reference(s2).run(theta, slabs, 1)


@pytest.mark.parametrize("name", _control_names())
def test_each_control_of_the_reference_moves_the_result(ref, theta,
                                                        one_clock, name):
    """What the benchmark's controls stand for is seen by the numbers
    the cell compares, already at the tiny size."""
    s2, slabs, (want_t, want_l) = one_clock
    got_t, got_l = ref.Reference(s2, **ref.CONTROLS[name]).run(
        theta, slabs, 1)
    gap = ref.param_gap(got_t[-1], want_t[-1], theta, s2)
    loss = max(abs(g - w) / w for g, w in zip(got_l, want_l))
    assert gap > 1e-3 or loss > 1e-3, (name, gap, loss)

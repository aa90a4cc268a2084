"""The `ouro` family (models/ouro.py) at its tiny size on the CPU:
against the benchmark's plain reference
(benchmark/families/ouro/reference.py) on seeded random weights —
forward, loss, gradients and fused clocks — THE LOOP against untied
copies of the weights set equal (values, and the gradient as the sum
over the copies), one step against the plain decoder, multi-head
attention through the blocked core at one query head a key/value head
against the masked `[S, S]` definition, and each of the reference's
controls.

Both sides run float32 at the CPU's full precision, so they agree to
round-off: 1e-5 relative is ten times the worst seen (sums in another
order), and far under anything a changed formula would give."""

import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.models import attention_kernel
from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import ouro
from kafka_ps_tpu.models.task import get_task
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(ROOT, "benchmark", "families", "ouro")
TINY = "benchmark/families/ouro/tiny.model.json"
PUBLISHED = "benchmark/configs/ouro-2.6b.model.json"
RTOL = 1e-5
CONTROL_NAMES = ["theta_bf16", "three_steps", "grad_last_use_only",
                 "no_norm_between_steps", "no_post_norms", "no_rope",
                 "heads_grouped"]


def family(part):
    """A file of the benchmark's family, as a module (the harness loads
    it the same way)."""
    name = "ouro_family_test_" + part
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
    return PSConfig(num_workers=3, task="ouro",
                    model=ModelConfig(num_max_iter=2,
                                      local_learning_rate=0.05,
                                      model_json=TINY),
                    buffer=BufferConfig(min_size=1, max_size=2))


@pytest.fixture(scope="module")
def task(ps_cfg):
    return get_task("ouro", ps_cfg.model)


@pytest.fixture(scope="module")
def theta(task):
    """Seeded random weights: the stated start, moved off it so that no
    norm weight is one."""
    rng = np.random.default_rng(7)
    start = np.asarray(task.init_params())
    return (start + 0.05 * rng.standard_normal(start.shape)).astype(
        np.float32)


def rows_of(task, n, seed=3):
    return np.random.default_rng(seed).integers(
        0, task.arch.vocab_held, size=(n, task.row_width)).astype(np.int32)


def close(got, want, scale=None):
    got, want = np.asarray(got), np.asarray(want)
    scale = float(np.max(np.abs(want))) if scale is None else scale
    assert np.max(np.abs(got - want)) <= RTOL * max(scale, 1e-30), (
        float(np.max(np.abs(got - want))), scale)


# -- the model against its reference --------------------------------------------

def test_the_flat_layout_is_the_references(task, ref, ps_cfg):
    s = ref.shapes(ps_cfg)
    assert [(n, tuple(sh)) for n, sh in s.leaves()] == ouro.leaf_specs(
        task.arch)
    assert s.num_params == task.num_params
    # a layer's leaves exist once, however often the layer is used
    assert sum(n.startswith("l0.") for n, _ in task.specs) == 11
    assert len(task.specs) == 3 + 11 * task.arch.num_hidden_layers
    # and the stated start is the same to the last bit
    assert np.array_equal(np.asarray(task.init_params()),
                          ref.init_params(s))
    leaves = ouro.init_leaves(task.arch)
    for name in ("l0.in_norm", "l2.post_mlp_norm", "final_norm"):
        assert np.all(np.asarray(leaves[name]) == 1.0)
    assert 0.01 < float(np.asarray(leaves["l0.wq"]).std()) < 0.03


def test_the_count_of_file_program_reference_and_costs_agree(ref):
    """At the published widths, from shapes alone: the configuration's
    `num_params`, the program's flat key space, the reference's and the
    benchmark's cost functions count the same 612,435,968 — the
    MODEL's, not four times its layers."""
    stated = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "ouro-2.6b.json")))
    assert stated["num_params"] == 612_435_968
    c = ouro.load_config(PUBLISHED)
    assert ouro.num_params(c) == stated["num_params"]
    cfg = PSConfig(task="ouro", model=ModelConfig(model_json=PUBLISHED))
    assert ref.shapes(cfg).num_params == stated["num_params"]
    costs = family("costs")
    m = costs.model_file(cfg)
    assert costs.num_params(m) == stated["num_params"]
    assert lm.num_params(ouro.layer_specs(c)) == 51_388_416
    # the published widths, heads and steps, untouched; the depth cut
    assert (c.hidden_size, c.intermediate_size, c.head_dim,
            c.num_attention_heads, c.num_key_value_heads, c.vocab_held,
            c.total_ut_steps) == (2048, 5632, 128, 16, 16, 49_152, 4)
    assert c.num_hidden_layers == 8 and c.layer_applications == 32
    assert c.attention_block == 512 and c.sequence_length == 1024
    # a layer APPLICATION counts: 26.0 TFLOP an update, compute-bound,
    # and a leaf's bytes once
    assert costs.layer_applications(m) == 32
    flops, bytes_ = costs.update_cost(m, 1, 2, 4)
    assert 25.9e12 < flops < 26.1e12 and bytes_ == 39.0 * 612_435_968
    one_step = costs.update_cost(dict(m, total_ut_steps=1), 1, 2, 4)[0]
    head = 7 * 1024 * 2.0 * 2048 * 49_152
    assert (flops - head) == pytest.approx(4 * (one_step - head))


def test_loss_and_gradients_agree_with_the_reference(task, ref, ps_cfg,
                                                     theta):
    s = ref.shapes(ps_cfg)
    rows, mask = rows_of(task, 2), jnp.asarray([1.0, 1.0])
    switches = ref.Reference(s).switches

    def program(t):
        return ouro.loss_and_counts(task.unflatten(t), rows, mask,
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
        assert np.any(w), name                     # every leaf is used
        close(g, w)


def test_the_forward_pass_agrees_with_the_reference_row_by_row(task, ref,
                                                               ps_cfg,
                                                               theta):
    s = ref.shapes(ps_cfg)
    rows = rows_of(task, 2, seed=5)
    out = ouro.forward(task.unflatten(jnp.asarray(theta)), rows, task.arch,
                       with_logits=True)
    for i, (nll, preds) in enumerate(
            ref.Reference(s).forward_rows(theta, rows)):
        close(out["nll"][i], nll)
        assert np.array_equal(np.argmax(np.asarray(out["logits"][i]), -1),
                              preds)


def test_a_masked_row_adds_nothing(task, theta):
    rows = rows_of(task, 2)
    leaves = task.unflatten(jnp.asarray(theta))
    both, counts = ouro.loss_and_counts(leaves, rows,
                                        jnp.asarray([1.0, 0.0]), task.arch)
    alone, _ = ouro.loss_and_counts(leaves, rows[:1], jnp.asarray([1.0]),
                                    task.arch)
    close(both, alone)
    # a dense family's count triple: no expert layer
    assert counts.shape == (3,) and not np.any(np.asarray(counts))


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
    scale = 3 * RTOL * rounds
    assert np.max(np.abs((got - theta) - (want_t[-1] - theta))) <= scale \
        * np.max(np.abs(want_t[-1] - theta))
    assert ref.param_gap(got, want_t[-1], theta, s) <= scale
    np.testing.assert_allclose(np.asarray(losses), want_l, rtol=scale)
    counted = dict(zip(task.counter_names, np.asarray(counted)))
    c = task.arch
    passes = rounds * w * (ps_cfg.model.num_max_iter + 1)
    assert task.counter_names[:len(lm.COUNTERS)] == lm.COUNTERS
    for name in lm.COUNTERS:            # no expert layer: the moe.* read 0
        if name.startswith("moe."):
            assert counted[name] == 0, name
    assert counted["data.tokens"] == rounds * 5 * c.sequence_length
    assert counted["data.pad_tokens"] == rounds * 1 * c.sequence_length
    # every row of a slab, masked or not, rides through every layer at
    # every step; the pair counters count in units of 1,024 pairs,
    # rounded down a pass
    full, blocks = ouro.pair_counts(c)
    assert (full, blocks) == (12 * 300, 12 * 384)
    assert counted["attn.pairs_window"] == 0
    assert counted["attn.pairs_full"] == passes * (2 * full // 1024)
    assert counted["attn.block_pairs"] == passes * (2 * blocks // 1024)
    assert counted["attn.kernel_block_pairs"] == 0      # head_dim 16
    # 3 layers x 4 steps, 2 rows, every pass
    assert counted["lm.layer_passes"] == passes * 2 * 12


def test_evaluation_agrees_with_the_reference(task, ref, ps_cfg, theta):
    s = ref.shapes(ps_cfg)
    test_rows = rows_of(task, 3, seed=4)
    got = task.evaluate(jnp.asarray(theta), test_rows, None)
    want = ref.Reference(s).evaluate(theta, (test_rows, None))
    close(got.loss, want["loss"])
    close(got.f1, want["f1"], scale=1.0)
    close(got.accuracy, want["accuracy"], scale=1.0)


def test_logits_at_a_position_do_not_see_later_tokens(task, theta):
    """The prefix property, over a tile's boundary and through every
    step of the loop."""
    c = task.arch
    leaves = task.unflatten(jnp.asarray(theta))
    row = rows_of(task, 1)
    cut = 9                             # inside the second tile of 8
    other = row.copy()
    other[:, cut + 1:] = (other[:, cut + 1:] + 17) % c.vocab_held
    a = ouro.forward(leaves, row, c, with_logits=True)["logits"]
    b = ouro.forward(leaves, other, c, with_logits=True)["logits"]
    close(a[:, :cut + 1], b[:, :cut + 1])
    assert np.max(np.abs(np.asarray(a[:, cut + 1:] - b[:, cut + 1:]))) > 1e-3


def test_a_model_file_the_family_cannot_run_is_refused(tmp_path):
    body = json.load(open(os.path.join(ROOT, TINY)))
    path = tmp_path / "model.json"
    for change, said in (
            ({"layer_types": body["layer_types"][:2]}, "layer_types"),
            ({"layer_types": ["sliding_attention"] * 3}, "layer_types"),
            ({"use_sliding_window": True}, "layer_types"),
            ({"total_ut_steps": 0}, "total_ut_steps"),
            ({"early_exit_threshold": 0.5}, "exit gate"),
            ({"tie_word_embeddings": True}, "untied head"),
            ({"num_key_value_heads": 3}, "divide over"),
            ({"vocab_held": 65}, "vocab_held"),
            ({"model_type": "afmoe"}, "is not ouro")):
        path.write_text(json.dumps(dict(body, **change)))
        with pytest.raises(ValueError, match=said):
            ouro.load_config(str(path))
    # a dense family states its vocabulary and no experts: the cut's
    # check asks it for none
    assert not hasattr(ouro.load_config(TINY), "experts_held")


# -- the loop --------------------------------------------------------------------

def untied(copies, shared, rows, c):
    """The forward pass through UNTIED weights: `copies[t]` holds step
    t's own layers and its own final norm, `shared` the embedding and
    the head; every application written out, nothing looped."""
    s = c.sequence_length
    x = shared["embed"][rows[:, :s]]
    for step in copies:
        for i in range(c.num_hidden_layers):
            x = ouro.layer(x, lm.sub(step, f"l{i}."), c)
        x = lm.rms_norm(x, step["final_norm"], c.rms_norm_eps)
    logits = x @ shared["head"]
    picked = jnp.take_along_axis(logits, rows[:, 1:s + 1, None], -1)[..., 0]
    return (jax.nn.logsumexp(logits, -1) - picked).sum()


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_the_loop_is_untied_copies_set_equal_and_its_gradient_their_sum(
        task, theta, steps):
    """The program's forward pass equals `steps` x layers applications
    through `steps` untied copies of the weights set equal, and a
    leaf's gradient equals the SUM of the copies' gradients: the
    gradient over the uses, formed by the loop's backward pass."""
    c = dataclasses.replace(task.arch, total_ut_steps=steps)
    leaves = task.unflatten(jnp.asarray(theta))
    rows = rows_of(task, 2, seed=21)
    looped = {k: v for k, v in leaves.items() if k not in ("embed", "head")}
    shared = {k: leaves[k] for k in ("embed", "head")}

    def program(looped, shared):
        return ouro.forward({**looped, **shared}, rows, c)["nll"].sum()
    got, (g_looped, g_shared) = jax.value_and_grad(program, (0, 1))(
        looped, shared)
    want, (g_copies, g_once) = jax.value_and_grad(
        lambda copies, shared: untied(copies, shared, rows, c), (0, 1))(
            [looped] * steps, shared)
    close(got, want)
    for name in shared:
        close(g_shared[name], g_once[name])
    for name in looped:
        parts = [np.asarray(g[name]) for g in g_copies]
        # every use moves the loss, the final norm at every step too
        assert all(np.any(p) for p in parts), name
        close(g_looped[name], sum(parts))
        if steps > 1:                   # and no one use is the gradient
            assert np.max(np.abs(np.asarray(g_looped[name]) - parts[-1])) \
                > 1e-3 * np.max(np.abs(parts[-1])), name


def test_one_step_is_the_plain_decoder(task, theta):
    """At `total_ut_steps` 1 the family is an ordinary decoder: the
    layers once, then the shared head with its final norm
    (`lm_common.head_nll`)."""
    c = dataclasses.replace(task.arch, total_ut_steps=1)
    leaves = task.unflatten(jnp.asarray(theta))
    rows = rows_of(task, 2, seed=22)
    s = c.sequence_length
    x = leaves["embed"][rows[:, :s]]
    for i in range(c.num_hidden_layers):
        x = ouro.layer(x, lm.sub(leaves, f"l{i}."), c)
    want, logits = lm.head_nll(x, leaves["final_norm"], leaves["head"],
                               rows[:, 1:s + 1], c.rms_norm_eps)
    got = ouro.forward(leaves, rows, c, with_logits=True)
    close(got["nll"], want)
    close(got["logits"], logits)
    # and four steps are not one
    four = ouro.forward(leaves, rows, task.arch)["nll"]
    assert np.max(np.abs(np.asarray(four - want))) > 1e-2


def test_the_final_norm_stands_between_the_steps(task, theta):
    """Scale `w_final`: with the norm at the end of every step, each
    later step starts from a scaled state, and the residual's weight
    against the normed halves changes — the last step's output is not
    the unscaled one times the factor, as it would be were the norm
    applied after the last step only."""
    c = task.arch
    leaves = task.unflatten(jnp.asarray(theta))
    x = jnp.asarray(np.random.default_rng(5).standard_normal(
        (1, c.sequence_length, c.hidden_size)), jnp.float32)
    scaled = dict(leaves, final_norm=3.0 * leaves["final_norm"])
    was = ouro.looped_layers(x, leaves, c)
    now = ouro.looped_layers(x, scaled, c)
    assert np.max(np.abs(np.asarray(now - 3.0 * was))) \
        > 1e-2 * np.max(np.abs(np.asarray(was)))
    one = dataclasses.replace(c, total_ut_steps=1)
    close(ouro.looped_layers(x, scaled, one),
          3.0 * ouro.looped_layers(x, leaves, one))


def test_the_loop_keeps_one_input_an_application_and_no_stack():
    """The program's text at the tiny size: the steps are one loop
    whose body holds each layer once (12 applications, 3 layers'
    products), no leaf is stacked over the steps, and what the
    backward pass keeps of the loop is `[steps, B, S, H]` inputs."""
    task = get_task("ouro", ModelConfig(num_max_iter=1, model_json=TINY))
    c = task.arch
    leaves = jax.eval_shape(task.unflatten, jax.ShapeDtypeStruct(
        (task.num_params,), jnp.float32))
    rows = jax.ShapeDtypeStruct((1, task.row_width), jnp.int32)
    text = jax.jit(jax.grad(lambda l, r: task.loss_and_counts(
        l, r, jnp.ones((1,)))[0])).lower(leaves, rows).as_text()
    assert "stablehlo.while" in text
    h, i, t = c.hidden_size, c.intermediate_size, c.total_ut_steps
    assert f"tensor<{t}x{h}x{i}xf32>" not in text       # no stacked leaf
    assert f"tensor<{t}x1x{c.sequence_length}x{h}xf32>" in text


# -- multi-head attention through the blocked core --------------------------------

def defined(q, k, v):
    """Attention as its definition, a head at a time: the whole [S, S]
    score matrix with the mask written as the inequality."""
    s, d = q.shape[1], q.shape[-1]
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / np.sqrt(d)
    probs = jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)


@pytest.mark.parametrize("block", [4, 8, 24])
def test_one_query_head_a_key_head_is_the_masked_definition(block):
    """`blocked_attention` at R = 1 (`q` `[B, S, heads, 1, D]`) against
    the definition, values and gradients."""
    rng = np.random.default_rng(block)
    q = jnp.asarray(rng.standard_normal((2, 24, 4, 1, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 24, 4, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 24, 4, 16)), jnp.float32)
    seen = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def blocked(q, k, v):
        out = lm.blocked_attention(q, k, v, window=None, block=block)
        return jnp.sum(out * seen), out

    def plain(q, k, v):
        out = defined(q, k, v)
        return jnp.sum(out * seen), out
    (_, got), g_got = jax.value_and_grad(blocked, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    (_, want), g_want = jax.value_and_grad(plain, argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
    close(got, want)
    for a, b in zip(g_got, g_want):
        close(a, b)


def test_the_layers_attention_is_the_references(task, ref, ps_cfg, theta):
    s = ref.shapes(ps_cfg)
    c = task.arch
    p = lm.sub(task.unflatten(jnp.asarray(theta)), "l1.")
    u = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, c.sequence_length, c.hidden_size)), jnp.float32)
    got = ouro.attention(u, p, c)
    for row in range(2):
        close(got[row], ref._attention(u[row], p, s,
                                       ref.Reference(s).switches))


def test_the_cells_core_is_the_kernels_at_one_query_head_a_key_head(
        tmp_path, request):
    """At a tiny size the kernel takes (`head_dim` 128, rows of 256 in
    one tile, 2 heads to 2 key/value heads): `attn.kernel_block_pairs`
    is 0 where the plain tiles ran — this platform — and
    `attn.block_pairs` with the TPU's branch taken, where the loss and
    the step are the plain path's to bfloat16's rounding."""
    body = json.load(open(os.path.join(ROOT, TINY)))
    body.update(head_dim=128, num_attention_heads=2, num_key_value_heads=2,
                sequence_length=256, num_hidden_layers=1,
                layer_types=[ouro.FULL], total_ut_steps=2)
    path = tmp_path / "kernel_legal.model.json"
    path.write_text(json.dumps(body))
    task = get_task("ouro", ModelConfig(
        num_max_iter=1, local_learning_rate=0.05, model_json=str(path)))
    c = task.arch
    assert attention_kernel.takes((2, 256, 2, 1, 128), c.attention_block)
    # and the cell's own shape: 16 heads of 128, a tile of 512
    assert attention_kernel.takes((1, 1024, 16, 1, 128), 512)
    leaves = task.unflatten(task.init_params())
    x, mask = rows_of(task, 2), jnp.ones((2,), jnp.float32)

    def fit():
        new, loss, counted = task.fit_counted(leaves, x, None, mask)
        return (np.asarray(task.flatten(new)), float(loss),
                dict(zip(task.counter_names, np.asarray(counted))))
    plain, plain_loss, counted = fit()
    assert counted["attn.kernel_block_pairs"] == 0
    blocks = 2 * (2 * ouro.pair_counts(c)[1] // ouro.PAIRS_UNIT)
    assert counted["attn.block_pairs"] == blocks > 0
    request.getfixturevalue("the_tpus_branch")
    new, loss, counted = fit()
    assert counted["attn.kernel_block_pairs"] \
        == counted["attn.block_pairs"] == blocks
    assert abs(loss - plain_loss) <= 1e-3 * plain_loss
    start = np.asarray(task.init_params())
    assert 0 < np.linalg.norm(new - plain) <= 0.02 * np.linalg.norm(
        plain - start)


def test_a_program_that_is_not_finite_has_no_gap_of_zero(ref, ps_cfg, theta):
    """`param_gap` of parameters that hold a nan is nan, which no limit
    admits (a largest-so-far comparison would skip it and read 0)."""
    s = ref.shapes(ps_cfg)
    moved = theta + np.float32(0.01)
    assert ref.param_gap(moved, moved, theta, s) == 0.0
    broken = moved.copy()
    broken[-5] = np.nan
    assert np.isnan(ref.param_gap(broken, moved, theta, s))


# -- the controls ----------------------------------------------------------------

def test_the_reference_has_the_controls_the_cell_names(ref):
    assert list(ref.CONTROLS) == CONTROL_NAMES


@pytest.fixture(scope="module")
def one_clock(ref, ps_cfg, task, theta):
    """One worker, one row, one clock of the sound reference: what each
    control is set against."""
    s2 = dataclasses.replace(ref.shapes(ps_cfg), num_workers=1)
    slabs = [(rows_of(task, 1, seed=30), None, np.ones(1, np.float32))]
    return s2, slabs, ref.Reference(s2).run(theta, slabs, 1)


@pytest.mark.parametrize("name", CONTROL_NAMES)
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


def test_the_gradient_of_one_use_is_not_the_sum(ref, ps_cfg, theta, task):
    """`grad_last_use_only`, the control a loop that forgets to sum
    stands for: the reference's gradient with the last step's input
    cut off is one use's, the embedding takes none, and it is not the
    program's."""
    s = ref.shapes(ps_cfg)
    rows, mask = jnp.asarray(rows_of(task, 1)), jnp.ones((1,))
    cut = ref.Reference(s, grad_last_use_only=True)
    g_cut = jax.grad(lambda p: ref._objective(p, rows, mask, s,
                                              cut.switches))(
        ref.split(jnp.asarray(theta), s))
    g_all = jax.grad(lambda t: ouro.loss_and_counts(
        task.unflatten(t), rows, mask, task.arch)[0])(jnp.asarray(theta))
    g_all = ref.split(np.asarray(g_all), s)
    assert not np.any(np.asarray(g_cut["embed"])) and np.any(g_all["embed"])
    close(g_cut["head"], g_all["head"])         # used once, after the cut
    for name in ("l0.wq", "l2.w_down", "final_norm"):
        assert np.max(np.abs(np.asarray(g_cut[name]) - g_all[name])) \
            > 1e-2 * np.max(np.abs(g_all[name])), name

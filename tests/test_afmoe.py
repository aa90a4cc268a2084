"""The `afmoe` family (models/afmoe.py) at its tiny size on the CPU:
against the benchmark's plain reference
(benchmark/families/afmoe/reference.py) on seeded random weights —
forward, loss, gradients and fused clocks — the blocked attention core
against the reference's masked `[S, S]` definition, RoPE in the sliding
layers and none in the full one, the share of an expert layer against
the uncut layer, and each of the reference's controls.

Both sides run float32 at the CPU's full precision, so they agree to
round-off: 1e-5 relative is ten times the worst seen (a few 1e-6: sums
in another order), and far under anything a changed formula would
give."""

import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.models import afmoe
from kafka_ps_tpu.models import attention_kernel
from kafka_ps_tpu.models import glm4_moe_lite
from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import nemotron_h
from kafka_ps_tpu.models.task import get_task
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(ROOT, "benchmark", "families", "afmoe")
TINY = "benchmark/families/afmoe/tiny.model.json"
PUBLISHED = "benchmark/configs/trinity-mini-ep16.model.json"
RTOL = 1e-5
CONTROL_NAMES = ["theta_bf16", "top7", "no_shared", "window_ignored",
                 "rope_on_full", "no_attn_gate", "no_qk_norm",
                 "no_embed_scale"]


def family(part):
    """A file of the benchmark's family, as a module (the harness loads
    it the same way)."""
    name = "afmoe_family_test_" + part
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
    return PSConfig(num_workers=3, task="afmoe",
                    model=ModelConfig(num_max_iter=2,
                                      local_learning_rate=0.05,
                                      model_json=TINY),
                    buffer=BufferConfig(min_size=1, max_size=2))


@pytest.fixture(scope="module")
def task(ps_cfg):
    return get_task("afmoe", ps_cfg.model)


@pytest.fixture(scope="module")
def theta(task):
    """Seeded random weights: the stated start, moved off it so that no
    norm weight is one and the selection bias is not zero."""
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
    assert [(n, tuple(sh)) for n, sh in s.leaves()] == afmoe.leaf_specs(
        task.arch)
    assert s.num_params == task.num_params
    # and the stated start is the same to the last bit
    assert np.array_equal(np.asarray(task.init_params()),
                          ref.init_params(s))
    leaves = afmoe.init_leaves(task.arch)
    for name in ("l0.in_norm", "l2.q_norm", "l3.post_mlp_norm",
                 "final_norm"):
        assert np.all(np.asarray(leaves[name]) == 1.0)
    assert not np.any(np.asarray(leaves["l1.router_bias"]))
    assert 0.01 < float(np.asarray(leaves["l0.wq"]).std()) < 0.03


def test_the_count_of_file_program_reference_and_costs_agree(ref):
    """At the published widths, from shapes alone: the configuration's
    `num_params`, the program's flat key space, the reference's and the
    benchmark's cost functions count the same 504,147,712."""
    stated = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "trinity-mini-ep16.json")))
    assert stated["num_params"] == 504_147_712
    c = afmoe.load_config(PUBLISHED)
    assert afmoe.num_params(c) == stated["num_params"]
    cfg = PSConfig(task="afmoe", model=ModelConfig(model_json=PUBLISHED))
    assert ref.shapes(cfg).num_params == stated["num_params"]
    costs = family("costs")
    assert costs.num_params(costs.model_file(cfg)) == stated["num_params"]
    by_kind = {dense: lm.num_params(afmoe.layer_specs(dense, c))
               for dense in (True, False)}
    assert by_kind == {True: 65_020_160, False: 84_156_800}
    # one whole period of the published pattern, behind one dense layer
    assert c.layer_types == (afmoe.SLIDING, afmoe.SLIDING, afmoe.FULL,
                             afmoe.SLIDING, afmoe.SLIDING)
    assert c.attention_block == 512 and c.sliding_window == 2048
    # and the same operations an update: 19.0 TFLOP, compute-bound
    flops, bytes_ = costs.update_cost(costs.model_file(cfg), 1, 2, 4)
    assert 18.9e12 < flops < 19.1e12 and bytes_ == 39.0 * 504_147_712


def test_loss_and_gradients_agree_with_the_reference(task, ref, ps_cfg,
                                                     theta):
    s = ref.shapes(ps_cfg)
    rows, mask = rows_of(task, 2), jnp.asarray([1.0, 1.0])
    switches = ref.Reference(s).switches

    def program(t):
        return afmoe.loss_and_counts(task.unflatten(t), rows, mask,
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
    out = afmoe.forward(task.unflatten(jnp.asarray(theta)), rows, task.arch,
                        with_logits=True)
    for i, (nll, preds, choices) in enumerate(
            ref.Reference(s).forward_rows(theta, rows)):
        close(out["nll"][i], nll)
        assert np.array_equal(np.argmax(np.asarray(out["logits"][i]), -1),
                              preds)
        held = choices[..., s.expert_offset:s.expert_offset
                       + s.experts_held].sum()
        if i == 0:
            alone = afmoe.forward(task.unflatten(jnp.asarray(theta)),
                                  rows[:1], task.arch)
            assert int(alone["loads"][:, 0].sum()) == int(held)


def test_a_masked_row_adds_nothing(task, theta):
    rows = rows_of(task, 2)
    leaves = task.unflatten(jnp.asarray(theta))
    both, _ = afmoe.loss_and_counts(leaves, rows, jnp.asarray([1.0, 0.0]),
                                    task.arch)
    alone, _ = afmoe.loss_and_counts(leaves, rows[:1], jnp.asarray([1.0]),
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
    # (seen: 1.1e-5 a clock at 8 clocks, the embedding's scale of
    # sqrt(hidden) and the four norms a layer carry it on)
    scale = 3 * RTOL * rounds
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
            * c.num_moe_layers)
    assert 0 <= counted["moe.passes_over_bound"] <= passes * c.num_moe_layers
    assert counted["data.tokens"] == rounds * 5 * c.sequence_length
    assert counted["data.pad_tokens"] == rounds * 1 * c.sequence_length
    # every row of a slab, masked or not, rides through every layer; the
    # pair counters count in units of 1,024 pairs, rounded down a pass
    window, full, blocks = afmoe.pair_counts(c)
    assert (window, full, blocks) == (4 * 164, 300, 4 * 320 + 384)
    for name, pairs in (("attn.pairs_window", window),
                        ("attn.pairs_full", full),
                        ("attn.block_pairs", blocks)):
        assert counted[name] == passes * (2 * pairs // afmoe.PAIRS_UNIT)
    assert counted["attn.kernel_block_pairs"] == 0      # head_dim 16
    # q's and k's head rows of both rows of a slab through every layer
    assert counted["attn.norm_rope_rows"] == passes * (
        2 * c.sequence_length * c.num_hidden_layers
        * (c.num_attention_heads + c.num_key_value_heads) // 1024) > 0
    assert counted["attn.norm_rope_kernel_rows"] == 0


def test_evaluation_agrees_with_the_reference(task, ref, ps_cfg, theta):
    s = ref.shapes(ps_cfg)
    test_rows = rows_of(task, 3, seed=4)
    got = task.evaluate(jnp.asarray(theta), test_rows, None)
    want = ref.Reference(s).evaluate(theta, (test_rows, None))
    close(got.loss, want["loss"])
    close(got.f1, want["f1"], scale=1.0)
    close(got.accuracy, want["accuracy"], scale=1.0)


def test_logits_at_a_position_do_not_see_later_tokens(task, theta):
    """The prefix property: both kinds of attention (over a tile's
    boundary too) and the per-token layers are causal."""
    c = task.arch
    leaves = task.unflatten(jnp.asarray(theta))
    row = rows_of(task, 1)
    cut = 9                             # inside the second tile of 8
    other = row.copy()
    other[:, cut + 1:] = (other[:, cut + 1:] + 17) % c.vocab_held
    a = afmoe.forward(leaves, row, c, with_logits=True)["logits"]
    b = afmoe.forward(leaves, other, c, with_logits=True)["logits"]
    close(a[:, :cut + 1], b[:, :cut + 1])
    assert np.max(np.abs(np.asarray(a[:, cut + 1:] - b[:, cut + 1:]))) > 1e-3


def test_a_model_file_the_family_cannot_run_is_refused(tmp_path):
    body = json.load(open(os.path.join(ROOT, TINY)))
    path = tmp_path / "model.json"
    for change, said in (
            ({"layer_types": body["layer_types"][:4]}, "layer_types"),
            ({"layer_types": ["chunked_attention"] * 5}, "layer_types"),
            ({"num_dense_layers": 5}, "leave an expert layer"),
            ({"num_shared_experts": 2}, "one shared expert"),
            ({"num_key_value_heads": 3}, "divide over"),
            ({"experts_held": 9}, "expert_offset"),
            ({"model_type": "nemotron_h"}, "is not afmoe")):
        path.write_text(json.dumps(dict(body, **change)))
        with pytest.raises(ValueError, match=said):
            afmoe.load_config(str(path))


# -- the band --------------------------------------------------------------------

def defined(q, k, v, window):
    """Attention as its definition, a head at a time: the whole [S, S]
    score matrix with the mask written as the two inequalities."""
    b, s, g, r, d = q.shape
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / np.sqrt(d)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)


@pytest.mark.parametrize("window,block", [
    (16, 8), (16, 4),           # a whole number of blocks
    (12, 8), (10, 4), (5, 8),   # and not: the band's edge cuts a block
    (None, 8), (None, 4)])      # a full layer
@pytest.mark.parametrize("windows", [1, 1.5, 3])
def test_the_blocked_core_is_the_masked_definition(windows, window, block):
    """`blocked_attention` against the definition, values and
    gradients, on rows of 1, 1.5 and 3 windows, with a window that is
    and is not a whole number of blocks, sliding and full."""
    s = int(windows * 16)
    rng = np.random.default_rng(s + block)
    q = jnp.asarray(rng.standard_normal((2, s, 2, 3, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, s, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, s, 2, 8)), jnp.float32)
    seen = jnp.asarray(rng.standard_normal((2, s, 2, 3, 8)), jnp.float32)

    def blocked(q, k, v):
        out = lm.blocked_attention(q, k, v, window=window, block=block)
        return jnp.sum(out * seen), out

    def plain(q, k, v):
        out = defined(q, k, v, window)
        return jnp.sum(out * seen), out
    (_, got), g_got = jax.value_and_grad(blocked, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    (_, want), g_want = jax.value_and_grad(plain, argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
    close(got, want)
    for a, b in zip(g_got, g_want):
        close(a, b)
    # what the counters count is what the mask holds
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    inside = (j <= i) & ((i - j < window) if window else True)
    assert lm.attention_pairs(s, window) == int(inside.sum())
    tiles = [lm.key_span(t, block, window) for t in range(s // block)]
    assert lm.attention_block_pairs(s, window, block) == sum(
        block * (hi - lo) for lo, hi in tiles)
    # and every pair of the mask lies in a block that is computed
    for t, (lo, hi) in enumerate(tiles):
        rows = inside[t * block:(t + 1) * block]
        assert not rows[:, :lo].any() and not rows[:, hi:].any()


def test_a_sliding_layer_skips_the_blocks_the_band_cannot_reach():
    """At the cell's size the tiles of 512 compute 1.25 times the pairs
    of the band (a core that computes every causal block reads 1.50,
    the whole square 2.67), and the program holds no `[S, S]` array a
    head: the widest tile of a sliding layer spans window + block keys."""
    s, w, block = 4096, 2048, 512
    pairs = lm.attention_pairs(s, w)
    assert pairs == 6_292_480 and lm.attention_pairs(s, None) == 8_390_656
    assert lm.attention_block_pairs(s, w, block) / pairs == pytest.approx(
        1.25, abs=1e-3)
    assert lm.attention_block_pairs(s, None, block) / pairs == \
        pytest.approx(1.50, abs=1e-3)
    spans = [lm.key_span(t, block, w) for t in range(s // block)]
    assert max(hi - lo for lo, hi in spans) == w + block
    assert spans[0] == (0, 512) and spans[-1] == (1536, 4096)
    q = jax.ShapeDtypeStruct((1, s, 4, 8, 128), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, s, 4, 128), jnp.float32)
    text = jax.jit(lambda q, k, v: lm.blocked_attention(
        q, k, v, window=w, block=block)).lower(q, kv, kv).as_text()
    assert f"x{w + block}xf32" in text
    assert f"x{s}x{s}x" not in text and f"x{3072}xf32" not in text
    with pytest.raises(ValueError, match="must divide"):
        lm.blocked_attention(jnp.zeros((1, 12, 1, 1, 4)),
                             jnp.zeros((1, 12, 1, 4)),
                             jnp.zeros((1, 12, 1, 4)), window=4, block=8)


def test_the_kernels_counter_is_the_blocks_where_the_kernel_ran(
        tmp_path, request):
    """`attn.kernel_block_pairs` through `fit_counted` at a tiny size
    the kernel takes (a sliding and a full layer, `head_dim` 128, rows
    of 256 in one tile): 0 where the plain tiles ran — this platform —
    and `attn.block_pairs` with the TPU's branch taken, where the loss
    and the step are the plain path's to bfloat16's rounding.  And
    `attn.norm_rope_kernel_rows` beside `attn.norm_rope_rows` the same
    way: the head norm and RoPE of q and k go through their kernel
    (models/norm_rope_kernel.py) at the same sizes, with tables in the
    sliding layer and without in the full one."""
    body = json.load(open(os.path.join(ROOT, TINY)))
    body.update(head_dim=128, num_attention_heads=2, num_key_value_heads=1,
                sequence_length=256, sliding_window=200, num_hidden_layers=2,
                layer_types=[afmoe.SLIDING, afmoe.FULL])
    path = tmp_path / "kernel_legal.model.json"
    path.write_text(json.dumps(body))
    task = get_task("afmoe", ModelConfig(
        num_max_iter=1, local_learning_rate=0.05, model_json=str(path)))
    c = task.arch
    assert attention_kernel.takes((2, 256, 1, 2, 128), c.attention_block)
    leaves = task.unflatten(task.init_params())
    x, mask = rows_of(task, 2), jnp.ones((2,), jnp.float32)

    def fit():
        new, loss, counted = task.fit_counted(leaves, x, None, mask)
        return (np.asarray(task.flatten(new)), float(loss),
                dict(zip(task.counter_names, np.asarray(counted))))
    plain, plain_loss, counted = fit()
    assert counted["attn.kernel_block_pairs"] == 0
    blocks = 2 * (2 * afmoe.pair_counts(c)[2] // afmoe.PAIRS_UNIT)
    assert counted["attn.block_pairs"] == blocks > 0
    normed = 2 * (2 * 256 * 2 * (2 + 1) // 1024)
    assert counted["attn.norm_rope_rows"] == normed > 0
    assert counted["attn.norm_rope_kernel_rows"] == 0
    request.getfixturevalue("the_tpus_branch")
    new, loss, counted = fit()
    assert counted["attn.kernel_block_pairs"] \
        == counted["attn.block_pairs"] == blocks
    assert counted["attn.norm_rope_kernel_rows"] \
        == counted["attn.norm_rope_rows"] == normed
    assert abs(loss - plain_loss) <= 1e-3 * plain_loss
    start = np.asarray(task.init_params())
    assert 0 < np.linalg.norm(new - plain) <= 0.02 * np.linalg.norm(
        plain - start)


def test_rope_is_in_the_sliding_layers_and_not_in_the_full_one(task, theta):
    """Move a token: the inputs at positions 2 and 5 change places.
    Positions 6 and 7 see both either way (the window is 8).  Without
    positions a query's output follows the SET of keys before it, so
    the full layer's is what it was, at every later position; with RoPE
    the scores follow the distance, so each sliding layer's changes."""
    c = task.arch
    leaves = task.unflatten(jnp.asarray(theta))
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.standard_normal((1, c.sequence_length,
                                         c.hidden_size)), jnp.float32)
    moved = u.at[:, 2].set(u[:, 5]).at[:, 5].set(u[:, 2])
    kinds = []
    for i, kind in enumerate(c.layer_types):
        p = lm.sub(leaves, f"l{i}.")
        was = afmoe.attention(u, p, c, kind)
        now = afmoe.attention(moved, p, c, kind)
        if kind == afmoe.FULL:
            close(now[:, 6:], was[:, 6:])
        else:
            assert float(jnp.max(jnp.abs(now[:, 6:8] - was[:, 6:8]))) > 1e-3
        kinds.append(kind == afmoe.FULL)
    assert kinds == [False, False, True, False, False]


# -- the expert layer's share ----------------------------------------------------

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
    e, hd, i = c.num_experts, c.hidden_size, c.moe_intermediate_size
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

    total = lm.swiglu(h, full["s_gate"], full["s_up"], full["s_down"])
    here = 0
    for offset in range(0, e, held):
        share = dataclasses.replace(c, experts_held=held,
                                    expert_offset=offset)
        p = dict(full, **{k: full[k][offset:offset + held]
                          for k in ("e_gate", "e_up", "e_down")})
        idx, w = lm.route(h, p["router"], p["router_bias"], share)
        part, load = lm.routed_experts(h, idx, w, p, share,
                                        lm.swiglu_experts)
        total = total + part
        here += int(load[0])
    assert here == 40 * c.num_experts_per_tok     # every choice, once
    close(total, want)


def test_rows_past_the_last_group_take_no_gradient(task):
    """`lm.live_rows_only`, which this family's expert wraps its rows
    in: the rows as they are forward; backward, whatever the cotangent
    holds past the last group — on the chip the untold kernel leaves
    NaN there — reaches nothing, and the live rows' passes whole."""
    sizes = jnp.asarray([3, 0, 2], jnp.int32)
    rows = jnp.arange(24.0).reshape(8, 3)
    ct = jnp.where(jnp.arange(8)[:, None] < 5, jnp.full((8, 3), 1.5), jnp.nan)
    out, back = jax.vjp(lambda r: lm.live_rows_only(r, sizes), rows)
    assert np.array_equal(np.asarray(out), np.asarray(rows))
    got = np.asarray(back(ct)[0])
    assert np.array_equal(got[:5], np.full((5, 3), 1.5)) and not got[5:].any()
    # through the layer: a cotangent poisoned past the last group leaves
    # the tokens' gradient finite, and equal to the unpoisoned one
    c = task.arch
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((32, c.hidden_size)), jnp.float32)
    p = {k: jnp.asarray(0.1 * rng.standard_normal(s), jnp.float32)
         for k, s in (("e_gate", (2, c.hidden_size, 32)),
                      ("e_up", (2, c.hidden_size, 32)),
                      ("e_down", (2, 32, c.hidden_size)))}
    idx, w = lm.route(h, jnp.asarray(rng.standard_normal(
        (c.hidden_size, c.num_experts)), jnp.float32),
        jnp.zeros((c.num_experts,)), c)

    def poisoned(xs, p, dot):
        live = (jnp.arange(xs.shape[0]) < dot.sizes.sum())[:, None]
        return jnp.where(live, afmoe._experts(xs, p, dot), jnp.nan)

    def through(expert):
        return jax.grad(lambda h: jnp.sum(lm.routed_experts(
            h, idx, w, p, c, expert)[0] ** 2))(h)
    sound = through(afmoe._experts)
    assert np.isfinite(np.asarray(sound)).all() and np.any(sound)
    close(through(poisoned), sound)
    close(sound, through(lm.swiglu_experts))   # the CPU leaves zeros there


def _gated(h, m, e):
    return (jax.nn.silu(h @ m["e_gate"][e]) * (h @ m["e_up"][e])) \
        @ m["e_down"][e]


def _relu2(h, m, e):
    return jnp.square(jax.nn.relu(h @ m["e_up"][e])) @ m["e_down"][e]


# the three expert families on the one expert layer, by task: (its tiny
# model file, what it hands `routed_experts`, expert `e` on every token)
EXPERT_FAMILIES = {
    "afmoe": (TINY, afmoe._experts, _gated),
    "glm4_moe_lite": ("benchmark/families/glm4-moe-lite/tiny.model.json",
                      glm4_moe_lite._experts, _gated),
    "nemotron_h": ("benchmark/families/nemotron-h/tiny.model.json",
                   nemotron_h.relu2_experts, _relu2)}


@pytest.mark.parametrize("over", [False, True], ids=["under", "over"])
@pytest.mark.parametrize("name", sorted(EXPERT_FAMILIES))
def test_either_branch_of_the_bound_is_the_dense_sum(name, over):
    """`routed_experts` at a family's tiny widths, three experts held:
    a pass UNDER `live_rows_bound` (a third of the tokens choose expert
    1, nobody 0 or 2: the bound's rows, most of them dead) and a pass
    forced OVER it (every token chooses 0 and 1, nobody 2: all T·K
    slots placed) — the branch that, since PR 40, keeps only its inputs
    and runs its forward once more backward.  Each equals the plain sum
    over the held experts of weight x expert(every token), written here
    with no `cond`, no sort and no placement — the value and the
    gradient in `h`, in `w` and in every expert matrix, all finite —
    and the load's third entry says which branch ran."""
    model_json, expert, one_expert = EXPERT_FAMILIES[name]
    c = dataclasses.replace(
        get_task(name, ModelConfig(model_json=model_json)).arch,
        experts_held=3, expert_offset=0)
    t, k, held = 24, c.num_experts_per_tok, 3
    assert (k, c.n_routed_experts) == (2, 8)
    bound = lm.live_rows_bound(t * k, c)
    assert bound == 40 < t * k
    rng = np.random.default_rng(40)
    h = jnp.asarray(rng.standard_normal((t, c.hidden_size)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.2, 1.0, (t, k)), jnp.float32)
    inter = c.moe_intermediate_size
    shapes = {"e_gate": (held, c.hidden_size, inter),
              "e_up": (held, c.hidden_size, inter),
              "e_down": (held, inter, c.hidden_size)}
    if one_expert is _relu2:
        del shapes["e_gate"]
    p = {key: jnp.asarray(0.1 * rng.standard_normal(shape), jnp.float32)
         for key, shape in shapes.items()}
    # experts 3.. are held elsewhere
    idx = jnp.asarray(np.tile([0, 1], (t, 1)) if over else
                      [[1, 4] if i % 3 == 0 else [5, 6] for i in range(t)],
                      jnp.int32)
    seen = jnp.asarray(rng.standard_normal((t, c.hidden_size)), jnp.float32)

    def sparse(h, w, p):
        out, load = lm.routed_experts(h, idx, w, p, c, expert)
        return jnp.sum(out * seen), (out, load)

    def dense(h, w, p):
        out = sum(jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)[:, None]
                  * one_expert(h, p, e) for e in range(held))
        return jnp.sum(out * seen), out

    (_, (got, load)), g_got = jax.jit(jax.value_and_grad(
        sparse, argnums=(0, 1, 2), has_aux=True))(h, w, p)
    (_, want), g_want = jax.value_and_grad(
        dense, argnums=(0, 1, 2), has_aux=True)(h, w, p)
    here, largest, went_over = np.asarray(load).tolist()
    assert (here, largest, went_over) == (
        (t * k, t, 1) if over else (t // 3, t // 3, 0))
    assert (here > bound) == over
    close(got, want)
    assert jax.tree.structure(g_got) == jax.tree.structure(g_want)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert np.isfinite(np.asarray(a)).all() and np.any(a)
        close(a, b)
    # the matrices of the expert nobody chose take no gradient at all
    for key in p:
        assert not np.any(g_got[2][key][2])
    # what crosses the `cond` for the backward pass: the branch under
    # the bound's residuals, `bound` rows each, and of the branch over
    # it its inputs only — nothing T·K rows or columns wide but the
    # sort's own `order` and `weight`
    traced = jax.make_jaxpr(jax.grad(lambda h: sparse(h, w, p)[0]))(h)
    forward, _ = [eqn for eqn in traced.jaxpr.eqns
                  if eqn.primitive.name == "cond"]
    kept = [v.aval.shape for v in forward.outvars]
    assert any(len(shape) == 2 and bound in shape for shape in kept)
    assert not [shape for shape in kept if len(shape) > 1 and t * k in shape]


def test_a_program_that_is_not_finite_has_no_gap_of_zero(ref, ps_cfg, theta):
    """`param_gap` of parameters that hold a nan is nan, which no limit
    admits (a largest-so-far comparison would skip it and read 0)."""
    s = ref.shapes(ps_cfg)
    moved = theta + np.float32(0.01)
    assert ref.param_gap(moved, moved, theta, s) == 0.0
    broken = moved.copy()
    broken[-5] = np.nan
    assert np.isnan(ref.param_gap(broken, moved, theta, s))


def test_the_cells_grouped_products_are_left_to_the_compilers_tiles():
    """`[rows, 2048] x [8, 2048, 1024]` and its transposes: widths that
    512 divides, so `grouped_tiles` says nothing, as at the GLM cell's —
    under the bound's 4,096 rows and over it at 32,768."""
    c = afmoe.load_config(PUBLISHED)
    slots = c.sequence_length * c.num_experts_per_tok
    assert slots == 32_768 and lm.live_rows_bound(slots, c) == 4096
    for rows in (4096, slots):
        assert lm.grouped_tiles(rows, 2048, 1024) is None
        assert lm.grouped_tiles(rows, 1024, 2048) is None


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

"""The `lfm2_moe` family (models/lfm2_moe.py) at its tiny size on the
CPU: against the benchmark's plain reference
(benchmark/families/lfm2-moe/reference.py) on seeded random weights —
forward, loss, gradients and fused clocks — the gated short convolution
against numbers worked by hand and causal, the tied leaf's gradient as
the reference's embedding part + head part, the router's weights and
what the selection bias moves, the share of an expert layer against the
uncut layer, the counters at heads of 64, and each of the reference's
controls.

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
from kafka_ps_tpu.models import lfm2_moe
from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import norm_rope_kernel
from kafka_ps_tpu.models import placement_kernel
from kafka_ps_tpu.models.task import get_task
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(ROOT, "benchmark", "families", "lfm2-moe")
TINY = "benchmark/families/lfm2-moe/tiny.model.json"
PUBLISHED = "benchmark/configs/lfm2-24b-a2b-ep8.model.json"
RTOL = 1e-5
CONTROL_NAMES = ["theta_bf16", "no_b_gate", "taps_reversed", "no_qk_norm",
                 "top3", "no_norm_topk", "softmax_router", "untied_head"]
CONV, FULL = lfm2_moe.CONV, lfm2_moe.FULL


def family(part):
    """A file of the benchmark's family, as a module (the harness loads
    it the same way)."""
    name = "lfm2_moe_family_test_" + part
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
    return PSConfig(num_workers=3, task="lfm2_moe",
                    model=ModelConfig(num_max_iter=2,
                                      local_learning_rate=0.05,
                                      model_json=TINY),
                    buffer=BufferConfig(min_size=1, max_size=2))


@pytest.fixture(scope="module")
def task(ps_cfg):
    return get_task("lfm2_moe", ps_cfg.model)


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
    assert [(n, tuple(sh)) for n, sh in s.leaves()] == lfm2_moe.leaf_specs(
        task.arch)
    assert s.num_params == task.num_params
    # ONE matrix at both ends: no head leaf, here or there
    names = [n for n, _ in s.leaves()]
    assert names[0] == "embed" and names[-1] == "final_norm"
    assert "head" not in names
    # and the stated start is the same to the last bit
    assert np.array_equal(np.asarray(task.init_params()),
                          ref.init_params(s))
    leaves = lfm2_moe.init_leaves(task.arch)
    for name in ("l0.operator_norm", "l1.q_norm", "l1.k_norm",
                 "l3.ffn_norm", "final_norm"):
        assert np.all(np.asarray(leaves[name]) == 1.0)
    assert not np.any(np.asarray(leaves["l1.router_bias"]))
    assert 0.01 < float(np.asarray(leaves["l0.w_in"]).std()) < 0.03
    # the taps start normal(0, 0.02) like the matrices
    assert 0.01 < float(np.asarray(leaves["l2.conv"]).std()) < 0.03
    # one frame: the family keeps no copy of what the frame gives
    assert issubclass(lfm2_moe.Lfm2MoeTask, lm.TokenRowsTask)
    for shared in ("routed_experts", "blocked_attention", "head_nll",
                   "evaluate_leaves", "swiglu_experts", "head_norm_rope"):
        assert shared not in vars(lfm2_moe), shared
    assert lfm2_moe.Lfm2MoeTask.counter_names == \
        afmoe.AfmoeTask.counter_names + ("conv.mix_rows",)
    assert lfm2_moe.PAIRS_UNIT == afmoe.PAIRS_UNIT


def test_the_count_of_file_program_reference_and_costs_agree(ref):
    """At the published widths, from shapes alone: the configuration's
    `num_params`, the program's flat key space, the reference's and the
    benchmark's cost functions count the same 469,285,248."""
    stated = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "lfm2-24b-a2b-ep8.json")))
    assert stated["num_params"] == 469_285_248
    c = lfm2_moe.load_config(PUBLISHED)
    assert lfm2_moe.num_params(c) == stated["num_params"]
    cfg = PSConfig(task="lfm2_moe", model=ModelConfig(model_json=PUBLISHED))
    assert ref.shapes(cfg).num_params == stated["num_params"]
    costs = family("costs")
    m = costs.model_file(cfg)
    assert costs.num_params(m) == stated["num_params"]
    by_kind = {(kind, dense): lm.num_params(lfm2_moe.layer_specs(kind, dense,
                                                                 c))
               for kind, dense in ((CONV, True), (FULL, False),
                                   (CONV, False))}
    assert by_kind == {(CONV, True): 89_139_200, (FULL, False): 86_118_592,
                       (CONV, False): 92_416_064}
    # the tied matrix once, and the final norm
    assert stated["num_params"] == (89_139_200 + 86_118_592
                                    + 3 * 92_416_064 + 8192 * 2048 + 2048)
    # the dense layer's conv, then one whole period of the published
    # pattern, 3 conv : 1 attention
    assert c.layer_types == (CONV, FULL, CONV, CONV, CONV)
    assert (c.num_dense_layers, c.num_moe_layers) == (1, 4)
    assert (c.head_dim, c.attention_block, c.conv_L_cache) == (64, 512, 3)
    assert (c.experts_held, c.num_experts, c.num_experts_per_tok) == (8, 64,
                                                                      4)
    # every number of the catalog's config the file carries as it is;
    # the cut's keys are the ones BENCHMARK.json lists as reduced
    model = json.load(open(os.path.join(ROOT, PUBLISHED)))
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "conv_L_cache", "conv_bias",
                "norm_eps", "norm_topk_prob", "use_expert_bias",
                "routed_scaling_factor", "rope_parameters",
                "max_position_embeddings", "model_type", "layer_types",
                "num_hidden_layers", "num_dense_layers"):
        assert stated[key] == model[key], key
    assert (model["hidden_size"], model["intermediate_size"],
            model["moe_intermediate_size"]) == (2048, 11776, 1536)
    assert stated["num_experts"] == model["experts_held"] == 8
    assert stated["vocab_size"] == model["vocab_held"] == 8192
    assert (model["num_experts"], model["vocab_size"]) == (64, 65536)
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(e for e in manifest["configs"]
                 if e["name"] == "lfm2-24b-a2b-ep8")
    assert entry["reduced"] == list(stated["reduced"]) == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    assert {key.split()[0] for key in stated["assumed"]} >= {
        "m1", "m2", "m3", "m4", "m5"}
    assert "no routed token is dropped" in stated["guarantees"]
    # the same operations an update: 389 MFLOP a token forward, 7 passes
    flops, bytes_ = costs.update_cost(m, 1, 2, 4)
    assert costs.forward_flops_per_token(m) == pytest.approx(389.0e6,
                                                             rel=1e-3)
    assert flops == pytest.approx(11.15e12, rel=1e-3)
    assert bytes_ == 39.0 * 469_285_248
    # of the matrix work a token passes, the conv layers' products are
    # over a third
    conv = 4 * 2.0 * costs.conv_params(m)
    assert 0.33 < conv / costs.forward_flops_per_token(m) < 0.37
    # the chain of an update's counted positions: 4 conv layers x 3
    # passes of 4,096 positions; two gradient passes of 11 arrays of
    # 2,048 float32 channels and a loss pass of 4
    mixed = 4 * 3 * 4096 // costs.ROWS_UNIT
    chain_flops, chain_bytes = costs.short_conv_mix(m, mixed, 1, 2)
    positions = 4 * 4096
    assert chain_bytes == 4.0 * 2048 * positions * (2 * 11 + 4) \
        + 4.0 * 2048 * 3 * 4 * (3 + 2)
    assert chain_flops == 7 * 2048 * positions * (2 * 3 + 1)
    assert chain_flops / 197e12 < chain_bytes / 819e9 / 100


def test_loss_and_gradients_agree_with_the_reference(task, ref, ps_cfg,
                                                     theta):
    s = ref.shapes(ps_cfg)
    rows, mask = rows_of(task, 2), jnp.asarray([1.0, 1.0])
    switches = ref.Reference(s).switches

    def program(t):
        return lfm2_moe.loss_and_counts(task.unflatten(t), rows, mask,
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


def test_the_tied_leafs_gradient_is_the_embedding_part_and_the_head_part(
        task, ref, ps_cfg, theta):
    """One leaf at both ends: its gradient in the program is the sum of
    what the reference's objective gives the matrix as the embedding (a
    scatter of rows: only rows of tokens that occur) and as the head (a
    dense product: every row), each taken with the other use held
    fixed."""
    s = ref.shapes(ps_cfg)
    rows, mask = rows_of(task, 2), jnp.asarray([1.0, 1.0])
    switches = ref.Reference(s).switches
    p = {n: jnp.asarray(v) for n, v in ref.split(theta, s).items()}

    def two_matrices(embed, head):
        q = dict(p, embed=embed, head=head)
        return ref._objective(q, jnp.asarray(rows), mask, s, switches)
    as_embed, as_head = jax.grad(two_matrices, argnums=(0, 1))(
        p["embed"], p["embed"].T)
    got = jax.grad(lambda t: lfm2_moe.loss_and_counts(
        task.unflatten(t), rows, mask, task.arch)[0])(jnp.asarray(theta))
    got = ref.split(np.asarray(got), s)["embed"]
    close(got, np.asarray(as_embed) + np.asarray(as_head).T)
    # the two parts are what they are said to be
    seen = np.zeros(s.vocab_held, bool)
    seen[rows[:, :s.sequence_length].reshape(-1)] = True
    assert not np.any(np.asarray(as_embed)[~seen]) and (~seen).any()
    assert np.all(np.any(np.asarray(as_head).T != 0, axis=1))
    assert np.max(np.abs(as_embed)) > 1e-4 and np.max(np.abs(as_head)) > 1e-4


def test_the_forward_pass_agrees_with_the_reference_row_by_row(task, ref,
                                                               ps_cfg,
                                                               theta):
    s = ref.shapes(ps_cfg)
    rows = rows_of(task, 2, seed=5)
    out = lfm2_moe.forward(task.unflatten(jnp.asarray(theta)), rows,
                           task.arch, with_logits=True)
    for i, (nll, preds, choices) in enumerate(
            ref.Reference(s).forward_rows(theta, rows)):
        close(out["nll"][i], nll)
        assert np.array_equal(np.argmax(np.asarray(out["logits"][i]), -1),
                              preds)
        assert choices.shape == (4, s.sequence_length, s.num_experts)
        held = choices[..., s.expert_offset:s.expert_offset
                       + s.experts_held].sum()
        if i == 0:
            alone = lfm2_moe.forward(task.unflatten(jnp.asarray(theta)),
                                     rows[:1], task.arch)
            assert int(alone["loads"][:, 0].sum()) == int(held)
    # logits too, against the reference's own (the tied matrix used twice)
    p = {n: jnp.asarray(v) for n, v in ref.split(theta, s).items()}
    _, logits, _ = ref._row(p, jnp.asarray(rows[0]), s,
                            ref.Reference(s).switches)
    close(out["logits"][0], logits)


def test_a_masked_row_adds_nothing(task, theta):
    rows = rows_of(task, 2)
    leaves = task.unflatten(jnp.asarray(theta))
    both, _ = lfm2_moe.loss_and_counts(leaves, rows, jnp.asarray([1.0, 0.0]),
                                       task.arch)
    alone, _ = lfm2_moe.loss_and_counts(leaves, rows[:1], jnp.asarray([1.0]),
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
    # at 24-token rows a pass's pairs and positions are under a unit of
    # 1,024: `test_the_counters_at_the_cells_size...` holds the counts
    for name in task.counter_names[len(lm.COUNTERS):]:
        assert counted[name] == 0, name


def test_evaluation_agrees_with_the_reference(task, ref, ps_cfg, theta):
    s = ref.shapes(ps_cfg)
    test_rows = rows_of(task, 3, seed=4)
    got = task.evaluate(jnp.asarray(theta), test_rows, None)
    want = ref.Reference(s).evaluate(theta, (test_rows, None))
    close(got.loss, want["loss"])
    close(got.f1, want["f1"], scale=1.0)
    close(got.accuracy, want["accuracy"], scale=1.0)


def test_logits_at_a_position_do_not_see_later_tokens(task, theta):
    """The prefix property: the convolutions, the attention (over a
    tile's boundary too) and the per-token layers are causal."""
    c = task.arch
    leaves = task.unflatten(jnp.asarray(theta))
    row = rows_of(task, 1)
    cut = 9                             # inside the second tile of 8
    other = row.copy()
    other[:, cut + 1:] = (other[:, cut + 1:] + 17) % c.vocab_held
    a = lfm2_moe.forward(leaves, row, c, with_logits=True)["logits"]
    b = lfm2_moe.forward(leaves, other, c, with_logits=True)["logits"]
    close(a[:, :cut + 1], b[:, :cut + 1])
    assert np.max(np.abs(np.asarray(a[:, cut + 1:] - b[:, cut + 1:]))) > 1e-3


def test_a_model_file_the_family_cannot_run_is_refused(tmp_path):
    body = json.load(open(os.path.join(ROOT, TINY)))
    path = tmp_path / "model.json"
    for change, said in (
            ({"layer_types": body["layer_types"][:4]}, "layer_types"),
            ({"layer_types": ["sliding_attention"] * 5}, "layer_types"),
            ({"num_dense_layers": 5}, "leave an expert layer"),
            ({"conv_bias": True}, "without bias"),
            ({"num_key_value_heads": 3}, "divide over"),
            ({"num_attention_heads": 3}, "divide over"),
            ({"rope_parameters": {"rope_type": "yarn", "rope_theta": 1e6}},
             "rope_type default"),
            ({"experts_held": 9}, "expert_offset"),
            ({"model_type": "afmoe"}, "is not lfm2_moe")):
        path.write_text(json.dumps(dict(body, **change)))
        with pytest.raises(ValueError, match=said):
            lfm2_moe.load_config(str(path))


# -- the gated short convolution -------------------------------------------------

def operator_config(hidden):
    """The tiny configuration at another width, for the operator alone."""
    body = json.load(open(os.path.join(ROOT, TINY)))
    fields = {f.name for f in dataclasses.fields(lfm2_moe.Lfm2MoeConfig)}
    return lfm2_moe.Lfm2MoeConfig(**dict(
        {k: v for k, v in body.items() if k in fields}, hidden_size=hidden,
        num_attention_heads=1, num_key_value_heads=1))


def test_the_convolution_is_the_numbers_worked_by_hand():
    """Two channels, five positions, three taps: `w[:, 2]` weighs the
    position itself, `w[:, 0]` the one two before it, and before the
    row's start there is nothing."""
    g = jnp.asarray([[[1.0, 10.0], [2.0, 20.0], [3.0, 30.0], [4.0, 40.0],
                      [5.0, 50.0]]])
    w = jnp.asarray([[100.0, 10.0, 1.0],       # channel 0
                     [0.5, 0.0, -1.0]])        # channel 1
    want = [[1.0, -10.0],                      # 1        | -10
            [12.0, -20.0],                     # 10 + 2   | -20
            [123.0, -25.0],                    # 100+20+3 | 5 - 30
            [234.0, -30.0],                    # 200+30+4 | 10 - 40
            [345.0, -35.0]]                    # 300+40+5 | 15 - 50
    assert np.array_equal(np.asarray(lfm2_moe.causal_conv(g, w))[0], want)
    # the whole operator with identity products: C * conv(B * z)
    c = operator_config(hidden=2)
    eye = jnp.eye(2)
    w_in = jnp.concatenate([2.0 * eye, 3.0 * eye, eye], axis=1)   # B, C, z
    p = {"w_in": w_in, "conv": w, "w_out": eye}
    u = g / jnp.asarray([1.0, 10.0])            # both channels 1..5
    got = lfm2_moe.short_conv(u, p, c)
    b_gate, c_gate, z = 2.0 * u, 3.0 * u, u
    assert np.allclose(np.asarray(got), np.asarray(
        c_gate * lfm2_moe.causal_conv(b_gate * z, w)))
    # position 2, channel 0: B z = 2 t^2 -> 100*2 + 10*8 + 1*18 = 298,
    # times C = 9
    assert float(got[0, 2, 0]) == pytest.approx(9.0 * 298.0)


def test_the_operator_is_causal_and_three_tokens_deep(task, theta):
    """A change at token t moves nothing before t, and nothing after
    t + 2 — through ONE operator; later layers carry it further."""
    c = task.arch
    p = lm.sub(task.unflatten(jnp.asarray(theta)), "l0.")
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.standard_normal((1, c.sequence_length,
                                         c.hidden_size)), jnp.float32)
    t = 7
    moved = u.at[:, t].add(1.0)
    was, now = lfm2_moe.short_conv(u, p, c), lfm2_moe.short_conv(moved, p, c)
    differs = np.asarray(jnp.max(jnp.abs(now - was), axis=-1))[0]
    assert not differs[:t].any() and not differs[t + 3:].any()
    assert (differs[t:t + 3] > 1e-6).all()
    # the reference's operator is the program's, and each of its two
    # controls is another
    ref = family("reference")
    s = ref.shapes(PSConfig(task="lfm2_moe",
                            model=ModelConfig(model_json=TINY)))
    sound = ref.Reference(s).switches
    host = {k: jnp.asarray(v) for k, v in p.items()}
    close(was[0], ref._short_conv(u[0], host, s, sound))
    for switch in ({"b_gate": False}, {"reversed_taps": True}):
        other = ref._short_conv(u[0], host, s, dict(sound, **switch))
        assert float(jnp.max(jnp.abs(other - was[0]))) > 1e-3


# -- the router ------------------------------------------------------------------

def test_the_routers_weights_and_what_the_bias_moves(task):
    """`w = s_top / (sum(s_top) + 1e-6)`, times the scale; the bias
    moves the CHOICE and never the weight of what is chosen."""
    c = task.arch
    rng = np.random.default_rng(12)
    h = jnp.asarray(rng.standard_normal((40, c.hidden_size)), jnp.float32)
    router = jnp.asarray(0.5 * rng.standard_normal(
        (c.hidden_size, c.num_experts)), jnp.float32)
    none = jnp.zeros((c.num_experts,))
    idx, w = lfm2_moe.route(h, router, none, c)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(
        h, router, precision=jax.lax.Precision.HIGHEST)))
    top = np.sort(s, axis=-1)[:, ::-1][:, :c.num_experts_per_tok]
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.sort(np.argsort(-s, -1)[:, :2], -1))
    np.testing.assert_allclose(
        np.sort(np.asarray(w), -1)[:, ::-1],
        top / (top.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # the floor is 1e-6, not `lm_common.route`'s 1e-20: where every
    # chosen score is tiny the weights no longer sum to one
    cold = jnp.full((c.num_experts,), -16.0)      # sigmoid(-16) = 1.1e-7
    tiny_idx, tiny_w = lfm2_moe.route(
        jnp.ones((1, c.num_experts)), jnp.diag(cold), none, c)
    assert float(tiny_w.sum()) == pytest.approx(2 * 1.125e-7 / (
        2 * 1.125e-7 + 1e-6), rel=1e-2)
    assert float(lm.route(jnp.ones((1, c.num_experts)), jnp.diag(cold), none,
                          c)[1].sum()) == pytest.approx(1.0)
    # a bias that lifts expert 5 over every score: every token chooses
    # it, and weighs it by its SCORE, the bias in no weight
    bias = none.at[5].set(2.0)
    b_idx, b_w = lfm2_moe.route(h, router, bias, c)
    assert np.all(np.any(np.asarray(b_idx) == 5, axis=-1))
    assert not np.all(np.any(np.asarray(idx) == 5, axis=-1))
    picked = np.take_along_axis(s, np.asarray(b_idx), -1)
    np.testing.assert_allclose(
        np.asarray(b_w), picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    # without `use_expert_bias` the leaf is read by nothing
    off = dataclasses.replace(c, use_expert_bias=False)
    o_idx, o_w = lfm2_moe.route(h, router, bias, off)
    assert np.array_equal(np.asarray(o_idx), np.asarray(idx))
    # `routed_scaling_factor` scales the weights, `norm_topk_prob` off
    # leaves the scores
    scaled = dataclasses.replace(c, routed_scaling_factor=2.5)
    close(lfm2_moe.route(h, router, none, scaled)[1], 2.5 * w)
    raw = dataclasses.replace(c, norm_topk_prob=False)
    close(np.sort(np.asarray(lfm2_moe.route(h, router, none, raw)[1]),
                  -1)[:, ::-1], top)


# -- the expert layer's share ----------------------------------------------------

@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_shares_of_an_expert_layer_sum_to_the_uncut_layer(task, ref,
                                                              ps_cfg, held):
    """Over the shares of one expert layer (8 experts: 8 shares of one,
    4 of two, ...; the eight shares `expert_offset` 0, 8, ... 56 of 8 at
    the published widths), the routed parts summed equal the
    reference's layer with every expert held: there is no shared
    expert, nothing is counted twice and nothing left out."""
    c = task.arch
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.standard_normal((40, c.hidden_size)), jnp.float32)
    e, hd, i = c.num_experts, c.hidden_size, c.moe_intermediate_size
    full = {"router": 0.5 * rng.standard_normal((hd, e)),
            "router_bias": 0.1 * rng.standard_normal((e,)),
            "e_gate": 0.1 * rng.standard_normal((e, hd, i)),
            "e_up": 0.1 * rng.standard_normal((e, hd, i)),
            "e_down": 0.1 * rng.standard_normal((e, i, hd))}
    full = {k: jnp.asarray(v, jnp.float32) for k, v in full.items()}
    uncut = dataclasses.replace(ref.shapes(ps_cfg), experts_held=e,
                                expert_offset=0)
    want, _ = ref._experts(h, full, uncut, ref.Reference(uncut).switches)

    total, here = 0.0, 0
    for offset in range(0, e, held):
        share = dataclasses.replace(c, experts_held=held,
                                    expert_offset=offset)
        p = dict(full, **{k: full[k][offset:offset + held]
                          for k in ("e_gate", "e_up", "e_down")})
        part, load = lfm2_moe.expert_layer(h[None], p, share)
        total = total + part[0]
        here += int(load[0])
    assert here == 40 * c.num_experts_per_tok     # every choice, once
    close(total, want)


def test_the_cells_expert_layer_is_trinitys_shape():
    """8 of 64 held, 4 a token, rows of 4,096: 16,384 slots a pass, the
    bound places 4,096 rows against 4,096 tokens — the third cell's
    0/1 matrix, which the placement's kernels take — and the grouped
    products `[rows, 2048] x [8, 2048, 1536]` are at widths 512
    divides, left to the compiler's tiles."""
    c = lfm2_moe.load_config(PUBLISHED)
    slots = c.sequence_length * c.num_experts_per_tok
    assert slots == 16_384 and lm.live_rows_bound(slots, c) == 4096
    for rows in (4096, slots):
        assert placement_kernel.takes(rows, 4096, c.hidden_size)
        assert lm.grouped_tiles(rows, 2048, 1536) is None
        assert lm.grouped_tiles(rows, 1536, 2048) is None


# -- the counters ----------------------------------------------------------------

def test_the_counters_at_the_cells_size_and_the_kernel_takes_heads_of_64(
        request):
    """A pass over a worker's slab at the published widths, from shapes
    alone: the full layer's triangle of 4,096 tokens, q's and k's head
    rows of the ONE attention layer, 16 units of 1,024 positions through
    the four conv layers' chains (48 an update, 1,536 a chunk of 32
    updates).  Heads of 64 channels ride the attention kernel two to a
    lane vector (PR 46: 8 KV heads, an even number), so with the TPU's
    branch taken `attn.kernel_block_pairs` is `attn.block_pairs`, and 0
    on the CPU; the norm-and-RoPE kernel still takes whole lanes only,
    and its counter reads 0 on both."""
    task = get_task("lfm2_moe", ModelConfig(model_json=PUBLISHED))
    c = task.arch
    q_shape = (1, c.sequence_length, c.num_key_value_heads,
               c.num_attention_heads // c.num_key_value_heads, c.head_dim)
    assert (q_shape, c.attention_block) == ((1, 4096, 8, 4, 64), 512)
    assert attention_kernel.takes(q_shape, c.attention_block)
    assert not norm_rope_kernel.takes((1, 4096, 32, 64))
    assert attention_kernel.takes(q_shape[:-1] + (128,), c.attention_block)
    slab = jax.ShapeDtypeStruct((1, task.row_width), jnp.int32)
    want = (0, 8_390_656 // 1024, 9_437_184 // 1024, 0,
            4096 * (32 + 8) // 1024, 0, 16)
    assert lfm2_moe.pair_counts(c) == (0, 8_390_656, 9_437_184)
    assert tuple(int(n) for n in task.own_counts(slab)) == want
    assert dict(zip(task.counter_names[len(lm.COUNTERS):], want)) == {
        "attn.pairs_window": 0, "attn.pairs_full": 8194,
        "attn.block_pairs": 9216, "attn.kernel_block_pairs": 0,
        "attn.norm_rope_rows": 160, "attn.norm_rope_kernel_rows": 0,
        "conv.mix_rows": 16}
    request.getfixturevalue("the_tpus_branch")
    on_the_chip = want[:3] + (9216,) + want[4:]
    assert tuple(int(n) for n in task.own_counts(slab)) == on_the_chip
    # int32 a dispatch: a chunk of 32 updates x 3 passes
    assert 32 * 3 * max(on_the_chip) < 2 ** 31


def test_the_counters_count_through_fit_counted_at_rows_of_a_unit(tmp_path):
    """`fit_counted` at a size whose pass fills whole units (rows of
    512 tokens, 2 a slab, 1 step): two passes of what `own_counts`
    gives one."""
    body = json.load(open(os.path.join(ROOT, TINY)))
    body.update(sequence_length=512)
    path = tmp_path / "longer.model.json"
    path.write_text(json.dumps(body))
    task = get_task("lfm2_moe", ModelConfig(
        num_max_iter=1, local_learning_rate=0.05, model_json=str(path)))
    c = task.arch
    x = rows_of(task, 2)
    _, loss, counted = task.fit_counted(task.unflatten(task.init_params()),
                                        x, None, jnp.ones((2,), jnp.float32))
    counted = dict(zip(task.counter_names, np.asarray(counted)))
    assert np.isfinite(float(loss))
    assert counted["conv.mix_rows"] == 2 * (2 * 512 * 4 // 1024) == 8
    assert counted["attn.pairs_full"] == 2 * (2 * 512 * 513 // 2 // 1024)
    assert counted["attn.block_pairs"] == 2 * (2 * 512 * 512 // 1024)
    assert counted["attn.norm_rope_rows"] == 2 * (2 * 512 * (4 + 2) // 1024)
    assert counted["attn.pairs_window"] == 0
    assert counted["attn.kernel_block_pairs"] == 0
    assert counted["attn.norm_rope_kernel_rows"] == 0
    assert c.layers(CONV) == 4 and c.layers(FULL) == 1


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
    assert got_t[-1].shape == want_t[-1].shape      # a second head is no part
    gap = ref.param_gap(got_t[-1], want_t[-1], theta, s2)
    loss = max(abs(g - w) / w for g, w in zip(got_l, want_l))
    assert gap > 1e-3 or loss > 1e-3, (name, gap, loss)

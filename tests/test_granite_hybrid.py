"""The `granitemoehybrid` family (models/granite_hybrid.py) at its tiny
size on the CPU: against the benchmark's plain reference
(benchmark/families/granite-hybrid/reference.py) on seeded random
weights — forward, loss, gradients and fused clocks — the chunked scan
at ONE group of B and C against the reference's token-by-token
recurrence, the four multipliers where the equations put them, the tied
leaf's gradient as the reference's embedding part + head part, the
slices of the tied matrix against the uncut vocabulary, the counters at
the cell's size, and each of the reference's controls.

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
from kafka_ps_tpu.models import granite_hybrid as gh
from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import nemotron_h
from kafka_ps_tpu.models import ssd_kernel
from kafka_ps_tpu.models.task import get_task
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(ROOT, "benchmark", "families", "granite-hybrid")
TINY = "benchmark/families/granite-hybrid/tiny.model.json"
PUBLISHED = "benchmark/configs/granite-4.0-h-micro-pp4.model.json"
TASK = "granitemoehybrid"
RTOL = 1e-5
CONTROL_NAMES = ["theta_bf16", "state_reset_each_chunk", "no_D",
                 "norm_before_gate", "taps_reversed", "residual_one",
                 "scores_sqrt", "no_embed_scale", "no_logits_scale",
                 "untied_head"]
MAMBA, ATTENTION = gh.MAMBA, gh.ATTENTION
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")


def family(part):
    """A file of the benchmark's family, as a module (the harness loads
    it the same way)."""
    name = "granite_hybrid_family_test_" + part
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
    return PSConfig(num_workers=3, task=TASK,
                    model=ModelConfig(num_max_iter=2,
                                      local_learning_rate=0.05,
                                      model_json=TINY),
                    buffer=BufferConfig(min_size=1, max_size=2))


@pytest.fixture(scope="module")
def task(ps_cfg):
    return get_task(TASK, ps_cfg.model)


@pytest.fixture(scope="module")
def theta(task):
    """Seeded random weights: the stated start, moved off it so that no
    norm weight and no `D` is one."""
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


def program_loss(task, theta, rows, mask, arch=None):
    return gh.loss_and_counts(task.unflatten(jnp.asarray(theta)), rows, mask,
                              arch or task.arch)[0]


# -- the model against its reference --------------------------------------------

def test_the_flat_layout_is_the_references(task, ref, ps_cfg):
    s = ref.shapes(ps_cfg)
    assert [(n, tuple(sh)) for n, sh in s.leaves()] == gh.leaf_specs(
        task.arch)
    assert s.num_params == task.num_params
    # ONE matrix at both ends: no head leaf, here or there
    names = [n for n, _ in s.leaves()]
    assert names[0] == "embed" and names[-1] == "final_norm"
    assert "head" not in names
    # and the stated start is the same to the last bit
    assert np.array_equal(np.asarray(task.init_params()),
                          ref.init_params(s))
    leaves = gh.init_leaves(task.arch)
    for name in ("l0.input_norm", "l0.gate_norm", "l0.D", "l5.post_norm",
                 "final_norm"):
        assert np.all(np.asarray(leaves[name]) == 1.0)
    assert 0.01 < float(np.asarray(leaves["l0.w_in"]).std()) < 0.03
    assert 0.01 < float(np.asarray(leaves["l5.wq"]).std()) < 0.03
    # (m1): the Mamba-2 start is `nemotron_h`'s
    taps = np.asarray(leaves["l1.conv_w"])
    assert -0.5 <= taps.min() < -0.4 and 0.4 < taps.max() <= 0.5
    a = np.exp(np.asarray(leaves["l1.A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    dt = np.log1p(np.exp(np.asarray(leaves["l1.dt_bias"])))    # softplus
    assert 0.9e-3 < dt.min() and dt.max() < 0.11
    # one frame and one mixer: the family keeps no copy of what the
    # frame gives, and its Mamba-2 mixer is `nemotron_h`'s own
    assert issubclass(gh.GraniteHybridTask, lm.TokenRowsTask)
    for shared in ("blocked_attention", "head_nll", "evaluate_leaves",
                   "head_norm_rope", "causal_conv", "ssd_chunked",
                   "gated_group_norm", "mamba2"):
        assert shared not in vars(gh), shared
    assert gh.nemotron_h is nemotron_h
    assert gh.GraniteHybridTask.counter_names == (
        nemotron_h.NemotronHTask.counter_names
        + afmoe.AfmoeTask.counter_names[len(lm.COUNTERS):] + ("mlp.rows",))
    assert gh.PAIRS_UNIT == afmoe.PAIRS_UNIT


def test_the_count_of_file_program_reference_and_costs_agree(ref):
    """At the published widths, from shapes alone: the configuration's
    `num_params`, the program's flat key space, the reference's and the
    benchmark's cost functions count the same 797,850,560."""
    stated = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "granite-4.0-h-micro-pp4.json")))
    assert stated["num_params"] == 797_850_560
    c = gh.load_config(PUBLISHED)
    assert gh.num_params(c) == stated["num_params"]
    cfg = PSConfig(task=TASK, model=ModelConfig(model_json=PUBLISHED))
    assert ref.shapes(cfg).num_params == stated["num_params"]
    costs = family("costs")
    m = costs.model_file(cfg)
    assert costs.num_params(m) == stated["num_params"]
    by_kind = {kind: lm.num_params(gh.layer_specs(kind, c))
               for kind in (MAMBA, ATTENTION)}
    assert by_kind == {MAMBA: 76_182_976, ATTENTION: 60_821_504}
    assert costs.mamba_params(m) + costs.mamba_small_params(m) == 25_847_232
    assert costs.attention_params(m) == 10_485_760
    assert costs.mlp_params(m) == 50_331_648
    # ten layers, the tied matrix once, and the final norm
    assert stated["num_params"] == (9 * 76_182_976 + 60_821_504
                                    + 25_088 * 2048 + 2048)
    # one whole period of the published pattern: 9 Mamba-2 : 1 attention
    assert c.layer_types == (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    assert (c.head_dim, c.attention_block, c.chunks_a_row,
            c.sequence_length) == (64, 512, 8, 2048)
    assert (c.mamba_inner, c.conv_dim, c.n_groups, c.chunk_size) == (
        4096, 4352, 1, 256)
    # every number of the catalog's config the file carries as it is;
    # the cut's keys are the ones BENCHMARK.json lists as reduced
    model = json.load(open(os.path.join(ROOT, PUBLISHED)))
    for key in ("hidden_size", "intermediate_size",
                "shared_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_local_experts",
                "num_experts_per_tok", "attention_bias",
                "attention_multiplier", "embedding_multiplier",
                "residual_multiplier", "logits_scaling",
                "position_embedding_type", "mamba_n_heads", "mamba_d_head",
                "mamba_expand", "mamba_d_state", "mamba_d_conv",
                "mamba_n_groups", "mamba_chunk_size", "mamba_conv_bias",
                "mamba_proj_bias", "rms_norm_eps", "hidden_act",
                "tie_word_embeddings", "max_position_embeddings",
                "model_type", "layer_types", "num_hidden_layers"):
        assert stated[key] == model[key], key
    assert (model["hidden_size"], model["shared_intermediate_size"],
            model["mamba_d_state"]) == (2048, 8192, 128)
    assert (model["attention_multiplier"], model["embedding_multiplier"],
            model["residual_multiplier"], model["logits_scaling"]) == (
        0.015625, 12, 0.22, 8)
    assert stated["vocab_size"] == model["vocab_held"] == 25_088
    assert model["vocab_size"] == 100_352 == 4 * model["vocab_held"]
    assert stated["published"]["num_hidden_layers"] == 40 == 4 * len(
        model["layer_types"])
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(e for e in manifest["configs"]
                 if e["name"] == "granite-4.0-h-micro-pp4")
    assert entry["reduced"] == list(stated["reduced"]) == [
        "num_hidden_layers", "layer_types", "vocab_size", "sequence_length"]
    assert {key.split()[0] for key in stated["assumed"]} >= {
        "m1", "m2", "m3", "m4"}
    nemotron = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "nemotron-3-nano-ep16.json")))
    assert stated["guarantees"] == nemotron["guarantees"][:4]
    # the same operations an update: 1.6 GFLOP a token forward, 7 passes
    flops, bytes_ = costs.update_cost(m, 1, 2, 4)
    forward = costs.forward_flops_per_token(m)
    assert forward == pytest.approx(1.6276e9, rel=1e-3)
    assert flops == 7 * 2048 * forward
    assert bytes_ == 39.0 * 797_850_560
    # of the matrix work a token passes the ten MLPs are 63%, the nine
    # mixers' projections 29%
    assert 0.61 < 10 * 2.0 * costs.mlp_params(m) / forward < 0.63
    assert 0.28 < 9 * 2.0 * costs.mamba_params(m) / forward < 0.30
    # the MLPs of an update's counted positions: 10 layers x 3 passes of
    # 2,048 positions; two gradient passes of three forwards' worth and
    # a loss pass of one
    counted = 10 * 3 * 2048 // costs.ROWS_UNIT
    mlp_flops, mlp_bytes = costs.dense_mlp(m, counted, 1, 2)
    assert mlp_flops == 2.0 * 3 * 2048 * 8192 * 10 * 2048 * (2 * 3 + 1)
    assert mlp_bytes == 4.0 * 2048 * 10 * 2048 * (2 * 5 + 2) \
        + 4.0 * 3 * 2048 * 8192 * 10 * (2 * 3 + 1)
    assert mlp_flops / 197e12 > 3 * mlp_bytes / 819e9       # the MXU's
    # the scan of an update's counted chunks, as `nemotron-h` counts it
    scan_flops, scan_bytes = costs.ssm_scan(cfg, 9 * 3 * 8)
    assert scan_flops == 9 * 7 * 2048 * 64 * (5.0 * 64 * 128 + 3 * 64)
    assert scan_bytes == 9 * 7 * 2048 * 4.0 * (2 * 4096 + 2 * 128 + 64)


def test_loss_and_gradients_agree_with_the_reference(task, ref, ps_cfg,
                                                     theta):
    s = ref.shapes(ps_cfg)
    rows, mask = rows_of(task, 2), jnp.asarray([1.0, 1.0])
    switches = ref.Reference(s).switches

    def reference(t):
        return ref._objective(ref.split(t, s), jnp.asarray(rows), mask, s,
                              switches)
    got, got_g = jax.value_and_grad(
        lambda t: program_loss(task, t, rows, mask))(jnp.asarray(theta))
    want, want_g = jax.value_and_grad(reference)(jnp.asarray(theta))
    close(got, want)
    for (name, _), g, w in zip(
            s.leaves(), ref.split(np.asarray(got_g), s).values(),
            ref.split(np.asarray(want_g), s).values()):
        assert np.any(w), name                         # every leaf is used
        close(g, w)


def test_the_tied_leafs_gradient_is_the_embedding_part_and_the_head_part(
        task, ref, ps_cfg, theta):
    """One leaf at both ends: its gradient in the program is the sum of
    what the reference's objective gives the matrix as the embedding (a
    scatter of rows: only rows of tokens that occur) and as the head (a
    dense product: every row), each under its scalar and each taken
    with the other use held fixed."""
    s = ref.shapes(ps_cfg)
    rows, mask = rows_of(task, 2, seed=6), jnp.asarray([1.0, 1.0])
    rows = rows % 11                    # some rows of E are never gathered
    switches = ref.Reference(s).switches
    p = {n: jnp.asarray(v) for n, v in ref.split(theta, s).items()}

    def two_matrices(embed, head):
        q = dict(p, embed=embed, head=head)
        return ref._objective(q, jnp.asarray(rows), mask, s, switches)
    as_embed, as_head = jax.grad(two_matrices, argnums=(0, 1))(
        p["embed"], p["embed"].T)
    got = jax.grad(lambda t: program_loss(task, t, rows, mask))(
        jnp.asarray(theta))
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
    out = gh.forward(task.unflatten(jnp.asarray(theta)), rows, task.arch,
                     with_logits=True)
    for i, (nll, preds) in enumerate(
            ref.Reference(s).forward_rows(theta, rows)):
        close(out["nll"][i], nll)
        assert np.array_equal(np.argmax(np.asarray(out["logits"][i]), -1),
                              preds)
    # logits too, against the reference's own (the tied matrix used twice)
    p = {n: jnp.asarray(v) for n, v in ref.split(theta, s).items()}
    _, logits = ref._row(p, jnp.asarray(rows[0]), s,
                         ref.Reference(s).switches)
    close(out["logits"][0], logits)


def test_a_masked_row_adds_nothing(task, theta):
    rows = rows_of(task, 2)
    both = program_loss(task, theta, rows, jnp.asarray([1.0, 0.0]))
    alone = program_loss(task, theta, rows[:1], jnp.asarray([1.0]))
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
    for name in lm.COUNTERS:
        if name.startswith("moe."):
            assert counted[name] == 0, name        # no expert layer
    assert counted["data.tokens"] == rounds * 5 * c.sequence_length
    assert counted["data.pad_tokens"] == rounds * 1 * c.sequence_length
    # 2 rows x 4 chunks of 8 x 9 Mamba-2 layers a pass
    assert counted["ssm.chunks"] == passes * 2 * 4 * 9
    # 2 rows of 32 tokens a pass: the attention layer's 1,056 pairs are
    # one unit of 1,024, its blocks' 2,048 two, and the 640 positions
    # through the ten MLPs under a unit, each rounded down once a pass
    # (`test_the_counters_at_the_cells_size...` holds the cell's counts)
    assert counted["attn.pairs_full"] == passes * 1
    assert counted["attn.block_pairs"] == passes * 2
    for name in ("attn.pairs_window", "attn.kernel_block_pairs",
                 "attn.norm_rope_rows", "attn.norm_rope_kernel_rows",
                 "mlp.rows"):
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
    """The prefix property: the convolutions, the scan (over a chunk's
    boundary too), the attention (over a tile's boundary) and the
    per-token layers are causal."""
    c = task.arch
    leaves = task.unflatten(jnp.asarray(theta))
    row = rows_of(task, 1)
    cut = 9                             # inside the second chunk of 8
    other = row.copy()
    other[:, cut + 1:] = (other[:, cut + 1:] + 5) % c.vocab_held
    a = gh.forward(leaves, row, c, with_logits=True)["logits"]
    b = gh.forward(leaves, other, c, with_logits=True)["logits"]
    close(a[:, :cut + 1], b[:, :cut + 1])
    assert np.max(np.abs(np.asarray(a[:, cut + 1:] - b[:, cut + 1:]))) > 1e-3


def test_a_model_file_the_family_cannot_run_is_refused(tmp_path):
    body = json.load(open(os.path.join(ROOT, TINY)))
    path = tmp_path / "model.json"
    for change, said in (
            ({"layer_types": body["layer_types"][:4]}, "layer_types"),
            ({"layer_types": ["full_attention"] * 10}, "layer_types"),
            ({"num_local_experts": 8}, "dense MLP in every layer"),
            ({"num_experts_per_tok": 2}, "dense MLP in every layer"),
            ({"position_embedding_type": "rope"}, "no positional encoding"),
            ({"tie_word_embeddings": False}, "tied head"),
            ({"mamba_conv_bias": False}, "convolution with bias"),
            ({"mamba_proj_bias": True}, "projections"),
            ({"mamba_expand": 3}, "mamba_expand"),
            ({"mamba_n_groups": 3}, "divide over mamba_n_groups"),
            ({"num_key_value_heads": 3}, "divide over"),
            ({"num_attention_heads": 3}, "divide over"),
            ({"sequence_length": 36}, "whole number of scan chunks"),
            ({"vocab_held": 65}, "vocab_held"),
            ({"model_type": "nemotron_h"}, "is not granitemoehybrid")):
        path.write_text(json.dumps(dict(body, **change)))
        with pytest.raises(ValueError, match=said):
            gh.load_config(str(path))


# -- the Mamba-2 mixer at one group ----------------------------------------------

def test_the_chunked_scan_is_the_recurrence_token_by_token(task, ref, ps_cfg,
                                                           theta):
    """`nemotron_h.ssd_chunked` at ONE group of B and C that all 8 heads
    read, in chunks of 8 over a row of 32 (the chunk does not equal the
    row: three hand-overs), against the reference's recurrence, a step a
    token; then the whole mixer, which IS `nemotron_h.mamba2`, against
    the reference's; and causal: a change at token t moves nothing
    before t and reaches the later chunks through the state."""
    c = task.arch
    s = ref.shapes(ps_cfg)
    assert (c.n_groups, c.chunk_size, c.sequence_length) == (1, 8, 32)
    rng = np.random.default_rng(21)
    n, heads, p_, st = 32, c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state
    x = jnp.asarray(rng.standard_normal((1, n, heads, p_)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (1, n, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 4.0, (heads,)), jnp.float32)
    bm = jnp.asarray(rng.standard_normal((1, n, 1, st)), jnp.float32)
    cm = jnp.asarray(rng.standard_normal((1, n, 1, st)), jnp.float32)
    got = nemotron_h.ssd_chunked(x, dt, a, bm, cm, c.chunk_size)
    want = ref._recurrence(x[0], dt[0], a, bm[0, :, 0], cm[0, :, 0], 0)
    close(got[0], want)
    # a state dropped at every chunk's start is another result
    dropped = ref._recurrence(x[0], dt[0], a, bm[0, :, 0], cm[0, :, 0], 8)
    assert float(jnp.max(jnp.abs(dropped - want))) > 1e-2
    close(dropped[:8], want[:8])
    # the whole mixer
    p = lm.sub(task.unflatten(jnp.asarray(theta)), "l0.")
    u = jnp.asarray(rng.standard_normal((1, n, c.hidden_size)), jnp.float32)
    sound = ref.Reference(s).switches
    was = nemotron_h.mamba2(u, p, c)
    close(was[0], ref._mamba(u[0], p, s, sound))
    for switch in ({"state_reset": True}, {"skip_D": False},
                   {"gate_first": False}, {"reversed_taps": True}):
        other = ref._mamba(u[0], p, s, dict(sound, **switch))
        assert float(jnp.max(jnp.abs(other - was[0]))) > 1e-4, switch
    t = 3
    now = nemotron_h.mamba2(u.at[:, t].add(1.0), p, c)
    differs = np.asarray(jnp.max(jnp.abs(now - was), axis=-1))[0]
    assert not differs[:t].any()
    # through the convolution's four taps at once, and through the
    # state into every later chunk
    assert (differs[t:t + 4] > 1e-5).all()
    assert all(differs[lo:lo + 8].max() > 1e-7 for lo in (8, 16, 24))


# -- the four multipliers --------------------------------------------------------

@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_moves_the_loss_and_sits_where_the_equations_put_it(
        task, ref, ps_cfg, theta, name):
    """Changed in the program and in the reference alike the two still
    agree — the scalar sits where the reference's equations put it —
    and the loss is another than at the published value."""
    s = ref.shapes(ps_cfg)
    rows, mask = rows_of(task, 2, seed=13), jnp.asarray([1.0, 1.0])
    switches = ref.Reference(s).switches
    published = float(program_loss(task, theta, rows, mask))
    # the scores of ONE tiny attention layer are small: their scalar
    # has to move far to show in the loss
    for factor in (64.0, 640.0) if name == "attention_multiplier" \
            else (0.5, 3.0):
        value = getattr(task.arch, name) * factor
        got = program_loss(task, theta, rows, mask,
                           dataclasses.replace(task.arch, **{name: value}))
        want = ref._objective(
            ref.split(jnp.asarray(theta), s), jnp.asarray(rows), mask,
            dataclasses.replace(s, **{name: value}), switches)
        close(got, want)
        assert abs(float(got) - published) > 1e-5 * published, (name, factor)


def test_the_residual_multiplier_is_on_both_branches_and_the_scores_scale_is_its_own(
        task, ref, ps_cfg, theta):
    """One layer worked by hand from the reference's parts: `a = x +
    0.22 Mix(N(x))`, `y = a + 0.22 MLP(N(a))` — the multiplier on the
    mixer's branch AND on the MLP's; and the scores are times
    `attention_multiplier`, which at heads of 16 channels is not
    `1 / sqrt(16)`."""
    c = task.arch
    s = ref.shapes(ps_cfg)
    sound = ref.Reference(s).switches
    leaves = task.unflatten(jnp.asarray(theta))
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.standard_normal((1, c.sequence_length,
                                         c.hidden_size)), jnp.float32)
    r = c.residual_multiplier
    assert r == 0.22
    for i, kind in ((0, MAMBA), (5, ATTENTION)):
        p = lm.sub(leaves, f"l{i}.")
        mix = ref._mamba if kind == MAMBA else ref._attention
        a = x[0] + r * mix(ref._norm(x[0], p["input_norm"], s.rms_norm_eps),
                           p, s, sound)
        y = a + r * ref._mlp(ref._norm(a, p["post_norm"], s.rms_norm_eps),
                             p, s)
        close(gh.layer(x, p, c, kind)[0], y)
        # on one branch only it is another layer
        half = a + ref._mlp(ref._norm(a, p["post_norm"], s.rms_norm_eps),
                            p, s)
        assert float(jnp.max(jnp.abs(half - y))) > 1e-2
    p = lm.sub(leaves, "l5.")
    u = ref._norm(x[0], p["input_norm"], s.rms_norm_eps)
    assert c.attention_multiplier == 1 / 64 != 1 / np.sqrt(c.head_dim)
    close(gh.attention(u[None], p, c)[0], ref._attention(u, p, s, sound))
    other = ref._attention(u, p, s, dict(sound, scores_sqrt=True))
    assert float(jnp.max(jnp.abs(
        other - ref._attention(u, p, s, sound)))) > 1e-4
    # no positional encoding: the last query sees a SET of keys — the
    # earlier tokens in another order give it the same output
    order = np.concatenate([np.random.default_rng(3).permutation(
        c.sequence_length - 1), [c.sequence_length - 1]])
    shuffled = gh.attention(u[order][None], p, c)[0]
    close(shuffled[-1], gh.attention(u[None], p, c)[0, -1])
    assert float(jnp.max(jnp.abs(shuffled[5] - gh.attention(
        u[None], p, c)[0, 5]))) > 1e-4


# -- the slices of the tied matrix -----------------------------------------------

def test_the_four_slices_logits_side_by_side_are_the_uncut_vocabularys(
        task, ref, ps_cfg, theta):
    """The tiny `E`'s 64 rows cut in four by the test itself (no key of
    the file says which slice: `vocab_held` holds the first rows, as in
    every family).  The uncut reference — all 64 rows held — gives a
    row's logits over the whole vocabulary; each slice's logits are the
    final norm's output times ITS rows of `E`, and set side by side the
    four are the uncut logits; the program's, over its slice, are the
    first block of them."""
    c = task.arch
    s = ref.shapes(ps_cfg)
    assert (c.vocab_size, c.vocab_held) == (64, 16)
    whole = dataclasses.replace(s, vocab_held=c.vocab_size)
    rng = np.random.default_rng(23)
    p = {n: jnp.asarray(v) for n, v in ref.split(theta, s).items()}
    full_e = jnp.concatenate([p["embed"], jnp.asarray(
        0.07 * rng.standard_normal((48, c.hidden_size)), jnp.float32)])
    row = jnp.asarray(rows_of(task, 1, seed=19)[0])   # ids of the first slice
    sound = ref.Reference(s).switches
    _, uncut = ref._row(dict(p, embed=full_e), row, whole, sound)
    assert uncut.shape == (c.sequence_length, 64)
    slices = []
    for j in range(4):
        rows_j = full_e[16 * j:16 * (j + 1)]
        # the first slice's rows embed the tokens; slice j's are the head
        _, logits = ref._row(dict(p, head=rows_j.T), row, s, sound)
        slices.append(logits)
    close(jnp.concatenate(slices, axis=-1), uncut)
    got = gh.forward(task.unflatten(jnp.asarray(theta)), row[None], c,
                     with_logits=True)["logits"][0]
    close(got, uncut[:, :16])
    assert float(jnp.max(jnp.abs(uncut[:, 16:]))) > 1e-2


# -- the counters ----------------------------------------------------------------

def test_the_counters_at_the_cells_size_and_the_kernel_takes_heads_of_64(
        request):
    """A pass over a worker's slab at the published widths, from shapes
    alone: 8 scan chunks of 256 a row through each of 9 Mamba-2 layers,
    the one attention layer's triangle of 2,048 tokens, 20 units of
    1,024 positions through the ten MLPs (60 an update, 1,920 a chunk of
    32 updates).  Heads of 64 channels ride the attention kernel two to
    a lane vector (8 KV heads, an even number), so with the TPU's
    branch taken `attn.kernel_block_pairs` is `attn.block_pairs`, and 0
    on the CPU; the scan's chunks of 256 under 64 heads of 64 channels
    are `ssd_kernel`'s the same way, `ssm.kernel_chunks` `ssm.chunks`
    there and 0 here; no head is normed or rotated, so the two
    norm-and-RoPE counters read 0 on both."""
    task = get_task(TASK, ModelConfig(model_json=PUBLISHED))
    c = task.arch
    q_shape = (1, c.sequence_length, c.num_key_value_heads,
               c.num_attention_heads // c.num_key_value_heads, c.head_dim)
    assert (q_shape, c.attention_block) == ((1, 2048, 8, 4, 64), 512)
    assert attention_kernel.takes(q_shape, c.attention_block)
    assert ssd_kernel.takes((1, 2048, 64, 64), c.n_groups, 128, 256)
    slab = jax.ShapeDtypeStruct((1, task.row_width), jnp.int32)
    assert gh.pair_counts(c) == (2_098_176, 2_621_440)
    want = {"ssm.chunks": 72, "ssm.kernel_chunks": 0,
            "attn.pairs_window": 0, "attn.pairs_full": 2049, "attn.block_pairs": 2560,
            "attn.kernel_block_pairs": 0, "attn.norm_rope_rows": 0,
            "attn.norm_rope_kernel_rows": 0, "mlp.rows": 20}
    names = task.counter_names[len(lm.COUNTERS):]
    assert dict(zip(names, (int(n) for n in task.own_counts(slab)))) == want
    request.getfixturevalue("the_tpus_branch")
    on_the_chip = dict(want, **{"attn.kernel_block_pairs": 2560,
                                "ssm.kernel_chunks": 72})
    assert dict(zip(names, (int(n) for n in task.own_counts(slab)))) \
        == on_the_chip
    # int32 a dispatch: a chunk of 32 updates x 3 passes
    assert 32 * 3 * max(on_the_chip.values()) < 2 ** 31
    assert 32 * 3 * on_the_chip["mlp.rows"] == 1920


def test_the_counters_count_through_fit_counted_at_rows_of_a_unit(tmp_path):
    """`fit_counted` at a size whose pass fills whole units (rows of
    128 tokens, 2 a slab, 1 step): two passes of what `own_counts`
    gives one."""
    body = json.load(open(os.path.join(ROOT, TINY)))
    body.update(sequence_length=128, mamba_chunk_size=16)
    path = tmp_path / "longer.model.json"
    path.write_text(json.dumps(body))
    task = get_task(TASK, ModelConfig(
        num_max_iter=1, local_learning_rate=0.05, model_json=str(path)))
    x = rows_of(task, 2)
    _, loss, counted = task.fit_counted(task.unflatten(task.init_params()),
                                        x, None, jnp.ones((2,), jnp.float32))
    counted = dict(zip(task.counter_names, np.asarray(counted)))
    assert np.isfinite(float(loss))
    assert counted["ssm.chunks"] == 2 * (2 * 8 * 9)
    assert counted["mlp.rows"] == 2 * (2 * 128 * 10 // 1024) == 4
    assert counted["attn.pairs_full"] == 2 * (2 * 128 * 129 // 2 // 1024)
    assert counted["attn.block_pairs"] == 2 * (2 * 128 * 128 // 1024)
    for name in ("attn.pairs_window", "attn.kernel_block_pairs",
                 "attn.norm_rope_rows", "attn.norm_rope_kernel_rows",
                 "ssm.kernel_chunks"):
        assert counted[name] == 0, name


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

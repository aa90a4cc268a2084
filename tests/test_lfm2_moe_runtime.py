"""The `lfm2_moe` family through the runtime: its record for the
contract every language-model family is held to
(tests/lm_family_contract.py).  tests/test_lfm2_moe.py holds the model
against its reference."""

from kafka_ps_tpu.models import lfm2_moe
from kafka_ps_tpu.models import lm_common as lm
from lm_family_contract import *  # noqa: F401,F403 — the contract's cases
from lm_family_contract import Family


def reads(c):
    assert c.layer_types == (lfm2_moe.CONV, lfm2_moe.FULL) \
        + (lfm2_moe.CONV,) * 3
    assert (c.layers(lfm2_moe.CONV), c.layers(lfm2_moe.FULL)) == (4, 1)
    assert (c.num_dense_layers, c.num_moe_layers) == (1, 4)
    assert (c.head_dim, c.attention_block, c.conv_L_cache) == (16, 8, 3)
    assert (c.n_routed_experts, c.norm_topk_prob, c.use_expert_bias,
            c.routed_scaling_factor, c.rope_theta) == (8, True, True, 1, 1e6)


def counted(task, counters):
    c = task.arch
    # every token chooses 2 of 8 experts in each of 4 expert layers, and
    # a quarter of the experts is held here
    slots = 32 * 3 * 2 * c.sequence_length * 2 * 4
    assert counters["moe.assignments_here"] \
        + counters["moe.assignments_away"] == slots
    assert 0.10 * slots < counters["moe.assignments_here"] < 0.45 * slots
    # 2 rows of 24 tokens a pass: the full layer's 600 pairs, its 240
    # head rows and the 192 positions through the conv layers' chains
    # are each under a unit of 1,024, rounded down once a pass
    # (tests/test_lfm2_moe.py holds the counts at sizes that fill units)
    window, full, blocks = lfm2_moe.pair_counts(c)
    assert (window, full, blocks) == (0, 300, 384)
    for name in task.counter_names[len(lm.COUNTERS):]:
        assert counters[name] == 0, name


FAMILY = Family(
    name="lfm2_moe", module=lfm2_moe,
    tiny="benchmark/families/lfm2-moe/tiny.model.json",
    digests="lfm2_moe_tiny_stablehlo.json", reads=reads, counted=counted,
    counter_names=lm.COUNTERS + (
        "attn.pairs_window", "attn.pairs_full", "attn.block_pairs",
        "attn.kernel_block_pairs", "attn.norm_rope_rows",
        "attn.norm_rope_kernel_rows", "conv.mix_rows"),
    slots_a_token=2 * 4,        # 2 of 8 experts in each of 4 expert layers
    # its router (the sum's floor 1e-6) and the expert layer that calls
    # it are its own
    own=("load_config", "num_params", "route", "expert_layer"))

"""The `afmoe` family through the runtime: its record for the contract
every language-model family is held to (tests/lm_family_contract.py).
tests/test_afmoe.py holds the model against its reference."""

from kafka_ps_tpu.models import afmoe
from kafka_ps_tpu.models import lm_common as lm
from lm_family_contract import *  # noqa: F401,F403 — the contract's cases
from lm_family_contract import Family


def reads(c):
    assert c.sliding_window == 8 and c.attention_block == 8
    assert c.layers(afmoe.SLIDING) == 4 and c.layers(afmoe.FULL) == 1
    assert c.num_moe_layers == 4
    assert (c.n_routed_experts, c.norm_topk_prob, c.routed_scaling_factor) \
        == (8, True, 2.826)


def counted(task, counters):
    c = task.arch
    # 32 updates x (k + 1) passes x 2 rows x the pairs of a row's pass,
    # in units of 1,024 pairs rounded down a pass
    window, full, blocks = afmoe.pair_counts(c)
    assert counters["attn.pairs_window"] == 32 * 3 * (2 * window // 1024)
    assert counters["attn.pairs_full"] == 32 * 3 * (2 * full // 1024)
    assert counters["attn.block_pairs"] == 32 * 3 * (2 * blocks // 1024)
    assert counters["attn.block_pairs"] > counters["attn.pairs_window"] > 0
    # q's and k's head rows through every layer
    assert counters["attn.norm_rope_rows"] == 32 * 3 * (
        2 * c.sequence_length * c.num_hidden_layers
        * (c.num_attention_heads + c.num_key_value_heads) // 1024) > 0


FAMILY = Family(
    name="afmoe", module=afmoe,
    tiny="benchmark/families/afmoe/tiny.model.json",
    digests="afmoe_tiny_stablehlo.json", reads=reads, counted=counted,
    counter_names=lm.COUNTERS + (
        "attn.pairs_window", "attn.pairs_full", "attn.block_pairs",
        "attn.kernel_block_pairs", "attn.norm_rope_rows",
        "attn.norm_rope_kernel_rows"),
    slots_a_token=2 * 4)        # 2 of 8 experts in each of 4 layers

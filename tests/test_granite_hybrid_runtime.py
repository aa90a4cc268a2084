"""The `granitemoehybrid` family through the runtime: its record for
the contract every language-model family is held to
(tests/lm_family_contract.py).  tests/test_granite_hybrid.py holds the
model against its reference."""

from kafka_ps_tpu.models import granite_hybrid as gh
from kafka_ps_tpu.models import lm_common as lm
from lm_family_contract import *  # noqa: F401,F403 — the contract's cases
from lm_family_contract import Family


def reads(c):
    assert c.layer_types == (gh.MAMBA,) * 5 + (gh.ATTENTION,) \
        + (gh.MAMBA,) * 4
    assert (c.layers(gh.MAMBA), c.layers(gh.ATTENTION)) == (9, 1)
    assert (c.head_dim, c.attention_block, c.chunks_a_row) == (16, 32, 4)
    assert (c.n_groups, c.mamba_inner, c.conv_dim) == (1, 128, 160)
    assert (c.embedding_multiplier, c.residual_multiplier,
            c.attention_multiplier, c.logits_scaling) == (12, 0.22,
                                                          0.015625, 8)


def counted(task, counters):
    # 32 updates x (k + 1) passes x 2 rows x 4 chunks x 9 Mamba-2 layers
    assert counters["ssm.chunks"] == 32 * 3 * 2 * task.arch.chunks_a_row * 9
    # 2 rows of 32 tokens a pass: the attention layer's 1,056 pairs fill
    # one unit of 1,024 a pass, its blocks two; the 640 positions through
    # the ten MLPs are under a unit, rounded down once a pass
    # (tests/test_granite_hybrid.py holds the counts at sizes that fill
    # units)
    assert gh.pair_counts(task.arch) == (528, 1024)
    assert counters["attn.pairs_full"] == 32 * 3 * 1
    assert counters["attn.block_pairs"] == 32 * 3 * 2
    # (the kernels are the chip's: a chunk of 16 tokens, on the CPU)
    for name in ("ssm.kernel_chunks", "attn.pairs_window",
                 "attn.kernel_block_pairs", "attn.norm_rope_rows",
                 "attn.norm_rope_kernel_rows", "mlp.rows"):
        assert counters[name] == 0, name
    for name in lm.COUNTERS:
        if name.startswith("moe."):
            assert counters[name] == 0, name


FAMILY = Family(
    name="granitemoehybrid", module=gh,
    tiny="benchmark/families/granite-hybrid/tiny.model.json",
    digests="granite_hybrid_tiny_stablehlo.json", reads=reads,
    counted=counted,
    counter_names=lm.COUNTERS + (
        "ssm.chunks", "ssm.kernel_chunks", "attn.pairs_window",
        "attn.pairs_full", "attn.block_pairs", "attn.kernel_block_pairs",
        "attn.norm_rope_rows", "attn.norm_rope_kernel_rows", "mlp.rows"),
    slots_a_token=0)            # no expert layer

"""The `ouro` family through the runtime: its record for the contract
every language-model family is held to (tests/lm_family_contract.py),
and what is this family's alone.  tests/test_ouro.py holds the model
against its reference."""

import dataclasses

import pytest

from kafka_ps_tpu.models import afmoe
from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import ouro
from lm_family_contract import *  # noqa: F401,F403 — the contract's cases
from lm_family_contract import Family


def reads(c):
    assert c.total_ut_steps == 4 and c.layer_applications == 12
    assert c.attention_block == 8
    assert c.layer_types == (ouro.FULL,) * 3


def counted(task, counters):
    c = task.arch
    # 32 updates x (k + 1) passes x 2 rows x the pairs of a row's pass
    # (12 layer applications), in units of 1,024 pairs rounded down a
    # pass
    full, blocks = ouro.pair_counts(c)
    assert counters["attn.pairs_window"] == 0
    assert counters["attn.pairs_full"] == 32 * 3 * (2 * full // 1024)
    assert counters["attn.block_pairs"] == 32 * 3 * (2 * blocks // 1024)
    assert counters["attn.block_pairs"] > counters["attn.pairs_full"] > 0
    # 3 layers x 4 steps x 3 passes = 36 a row an update
    assert counters["lm.layer_passes"] == 32 * 2 * 36
    # a dense family: it has no expert layer to count
    for name in task.counter_names:
        if name.startswith("moe."):
            assert counters[name] == 0, name


FAMILY = Family(
    name="ouro", module=ouro,
    tiny="benchmark/families/ouro/tiny.model.json",
    digests="ouro_tiny_stablehlo.json", reads=reads, counted=counted,
    counter_names=lm.COUNTERS + (
        "attn.pairs_window", "attn.pairs_full", "attn.block_pairs",
        "attn.kernel_block_pairs", "lm.layer_passes"),
    slots_a_token=0)                    # no expert layer


def test_the_cut_asks_a_family_for_its_experts_only_where_it_has_some():
    """`lm_common.validate_cut`: an expert family is held to its share;
    a dense family states its vocabulary and no dummy expert count."""
    dense = ouro.load_config(FAMILY.tiny)
    lm.validate_cut(dense)
    with pytest.raises(ValueError, match="vocab_held"):
        lm.validate_cut(dataclasses.replace(dense, vocab_held=0))
    sparse = afmoe.load_config("benchmark/families/afmoe/tiny.model.json")
    lm.validate_cut(sparse)
    for change in ({"experts_held": 9}, {"expert_offset": 7},
                   {"expert_offset": -1}):
        with pytest.raises(ValueError, match="expert_offset"):
            lm.validate_cut(dataclasses.replace(sparse, **change))
    lm.validate_cut(dataclasses.replace(sparse, expert_offset=6))
    assert ouro.PAIRS_UNIT == afmoe.PAIRS_UNIT

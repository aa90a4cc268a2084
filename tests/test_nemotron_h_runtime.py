"""The `nemotron_h` family through the runtime: its record for the
contract every language-model family is held to
(tests/lm_family_contract.py).  tests/test_nemotron_h.py holds the
model against its reference."""

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import nemotron_h as nh
from lm_family_contract import *  # noqa: F401,F403 — the contract's cases
from lm_family_contract import Family


def reads(c):
    assert c.hybrid_override_pattern == "MEM*E"
    assert c.kinds("M") == 2 and c.kinds("E") == 2 and c.kinds("*") == 1


def counted(task, counters):
    # 32 updates x (k + 1) passes x 2 rows x 4 chunks x 2 Mamba-2 blocks
    assert counters["ssm.chunks"] == 32 * 3 * 2 * task.arch.chunks_a_row * 2
    # the kernel is the chip's, and takes no chunk of 16 tokens
    assert counters["ssm.kernel_chunks"] == 0


FAMILY = Family(
    name="nemotron_h", module=nh,
    tiny="benchmark/families/nemotron-h/tiny.model.json",
    digests="nemotron_tiny_stablehlo.json", reads=reads, counted=counted,
    counter_names=lm.COUNTERS + ("ssm.chunks", "ssm.kernel_chunks"),
    slots_a_token=2 * 2)        # 2 experts in each of 2 expert blocks

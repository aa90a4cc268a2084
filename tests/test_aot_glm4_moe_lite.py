"""The `glm-4.7-flash-ep8.fused-bsp` cell's scan chunk, compiled once
for a described TPU v5e (tests/aot_described.py)."""

import re

import aot_described as described
from aot_described import aot, chunk, topo  # noqa: F401 — fixtures
from kafka_ps_tpu.models import lm_common as lm

CELL = ("glm4_moe_lite", "benchmark/configs/glm-4.7-flash-ep8.model.json")


def test_glm4_moe_lites_chunk_fits_the_chip_with_its_layers_written_out(
        aot, chunk):
    """591.3 M parameters held, 1 row of 1,024 tokens a worker.  The
    leaves are donated and the scratch stays under 8.97 GB: it reads
    8.156 GB since the fold's running sum stays out of the barrier (PR
    47: the limit is that reading and a tenth; the reading counts the
    sum's carried buffer twice, what is alive at once is the parent's
    7,793,843,712 bytes, tests/aot_described.py); 5.80 GB since the
    over-the-bound branch of the expert layer keeps its inputs only
    (PR 40); 6.37 GB since the expert layers are written out (PR 38);
    9.32 GB
    scanned over their stack; 16.49 GB with the local steps scanned and
    the shared leaves left loop-invariant in the fold over the workers,
    and 4.7 GB more with the flat vector cut into leaves without a
    barrier (PERF.md section 6, PR 27).  About 105 s."""
    described.leaves_are_donated_and_fit(chunk, 591_294_976, 8.97e9,
                                         7_793_843_712)
    # the expert layers are written out (PR 38): no array carries the
    # wire's leading layer axis — the scan over the stack copied a
    # layer's matrices out of `f32[4,8,2048,1536]` and wrote its
    # gradient back into one, 259 such lines and 31% of an update
    c = chunk.task.arch
    assert c.num_moe_layers == 4 and c.experts_held == 8
    assert "f32[8,2048,1536]" in chunk.text    # the reader sees a layer's
    assert not re.search(r"f32\[4,8,[\d,]*\]", chunk.text)
    # the grouped products are the chip's own kernel, not a dense
    # product, and at 2048 x 1536 it is told nothing: its own tiles
    calls = aot.ragged_dot_calls(chunk.text)
    assert calls and {tiles for _, tiles in calls} == {"512,512,512"}
    assert {lm.grouped_tiles(*shape) for shape, _ in calls} == {None}


def test_glm4_moe_lites_taken_branch_of_the_bound_writes_no_zeros(aot,
                                                                  chunk):
    described.taken_branch_writes_no_zeros_for_the_other(aot, chunk, 4096)


def test_glm4_moe_lites_barrier_ties_the_leaves_and_passes_nothing_else(
        aot, chunk):
    """The parent's chunk ran 83 selects of the running sum, 2.365 GB of
    results, under the barrier's scope; ten copies of small leaves
    (0.085 GB) stay, and no weight's relayout stands outside the worker
    loop, as in the parent's."""
    described.the_barrier_ties_the_leaves_and_passes_nothing_else(
        aot, chunk)


def test_glm4_moe_lites_placement_is_left_to_the_product(chunk):
    """1,024 rows under the bound x 1,024 tokens."""
    described.a_smaller_placement_is_left_to_the_product(chunk)


def test_glm4_moe_lites_norm_and_rope_are_the_plain_lines(chunk):
    """It rotates 64 of a head's channels and keeps `lm_common.rope`;
    3,329 lines of its chunk name the scope."""
    described.norm_and_rope_are_the_plain_lines(chunk, 3329)


def test_what_no_scope_names_is_under_a_tenth_of_glm4_moe_lites_bytes(
        chunk):
    """1.4% when written; 33% before the scan over its stacked layers
    had a name."""
    read = described.what_the_scopes_name(chunk)
    assert described.NAMED | described.NAMED_BY_EXPERTS <= read.named
    assert 0.0 < read.unnamed_share < 0.10, read.unnamed_share

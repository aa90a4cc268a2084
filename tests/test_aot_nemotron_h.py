"""The `nemotron-3-nano-ep16.fused-bsp` cell's scan chunk, compiled once
for a described TPU v5e (tests/aot_described.py)."""

import aot_described as described
from aot_described import aot, chunk, topo  # noqa: F401 — fixtures
from kafka_ps_tpu.models import lm_common as lm

CELL = ("nemotron_h", "benchmark/configs/nemotron-3-nano-ep16.model.json")


def test_nemotron_hs_chunk_fits_the_chip_and_walks_its_widths_in_told_tiles(
        aot, chunk):
    """667.0 M parameters held, 1 row a worker at the cell's own
    sequence length.  The leaves are donated and there is no second
    copy of the shared leaves: the scratch reads 9.03 GB at the cell's
    1,024 tokens (9.09 before PR 49, while the scan wrote its decays;
    7.55 while the fold's running sum went through the barrier, before
    PR 47: the reading counts the sum's carried buffer twice; 7.66
    before PR 40; 9.01 GB at 2,048, to the byte what the chip's backend
    reported, PR 31), what is alive at once is 8,805,378,048 bytes
    (8,839,067,136 before PR 49, tests/aot_described.py), a copy of the
    parameters is 2.67 GB, and the limit is the reading and a tenth.
    About 90 s."""
    described.leaves_are_donated_and_fit(chunk, 666_963_456, 9.94e9,
                                         8_805_378_048)
    # every grouped product — the two of an expert, their dx and dW,
    # under the bound's 768 rows and over it at 6,144 — runs the chip's
    # kernel in the tiles `grouped_tiles` states for the call's OWN
    # shape (dx: the product's turned round), none in the 128 x 128
    # blocks the compiler takes at 2688 and 1856 (1.09 ms a call for
    # 0.23-0.29, PERF.md section 5).  The hint is an undocumented
    # frontend attribute: a libtpu that stops honouring it fails here
    calls = aot.ragged_dot_calls(chunk.text)
    assert {shape for shape, _ in calls} == {
        (m, k, n) for m in (768, 6144)
        for k, n in ((2688, 1856), (1856, 2688))}
    assert all(tiles == lm.grouped_tiles(*shape) for shape, tiles in calls), \
        sorted(set(calls))
    assert not any(tiles.endswith(",128,128") for _, tiles in calls)
    # the chunked scan is in the program under its own scope, as the
    # kernels (eight groups of eight heads, chunks of 128), and no decay
    # `f32[1,8,128,128,8,8]` is written
    assert "kps.ssm.scan" in chunk.text and "kps.attn" in chunk.text
    described.the_scan_is_the_kernels_and_no_decay_is_written(
        chunk, mixers=4)


def test_nemotron_hs_taken_branch_of_the_bound_writes_no_zeros(aot, chunk):
    described.taken_branch_writes_no_zeros_for_the_other(aot, chunk, 6144)


def test_nemotron_hs_barrier_ties_the_leaves_and_passes_nothing_else(
        aot, chunk):
    """The parent's chunk ran 72 selects of the running sum, 2.668 GB of
    results, under the barrier's scope.  Twelve copies of a weight's
    shape stand in the entry computation, once a dispatch, as in the
    parent's."""
    described.the_barrier_ties_the_leaves_and_passes_nothing_else(
        aot, chunk, relayouts_outside=12)


def test_nemotron_hs_placement_is_left_to_the_product(chunk):
    """768 rows under the bound x 1,024 tokens."""
    described.a_smaller_placement_is_left_to_the_product(chunk)


def test_what_no_scope_names_is_under_a_tenth_of_nemotron_hs_bytes(chunk):
    """2.1% when written."""
    read = described.what_the_scopes_name(chunk)
    assert described.NAMED | described.NAMED_BY_EXPERTS <= read.named
    assert 0.0 < read.unnamed_share < 0.10, read.unnamed_share

"""The `mellum2-12b-ep4.fused-bsp` cell's scan chunk, compiled once for
a described TPU v5e (tests/aot_described.py)."""

import aot_described as described
from aot_described import aot, chunk, topo  # noqa: F401 — fixtures
from kafka_ps_tpu.models import lm_common as lm

CELL = ("mellum", "benchmark/configs/mellum2-12b-ep4.model.json")


def test_mellums_chunk_walks_its_widths_in_told_tiles(aot, chunk):
    """595.2 M parameters held, 16 of 64 experts, 1 row of 4,096 tokens
    a worker.  The leaves are donated and the scratch stays under what
    it read with the 0/1 matrices kept for the backward passes,
    8,976,765,440 bytes (8.98 + 2.38 GB of leaves = 11.36 GB, 19.1
    bytes a parameter, PR 41); 8,767,503,360 since the placement's
    kernels (PR 42), 8,677,585,920 since PR 43 — and 11,021,382,144
    since the fold's running sum stays out of the barrier (PR 47: the
    limit is that reading and a tenth; the reading counts the sum's
    carried buffer twice, what is alive at once is the parent's
    9,963,002,368 bytes, tests/aot_described.py).  A quarter of the
    experts held makes the expert layer's rows four times the `afmoe`
    cell's: the bound places 16,384 rows and a pass over it all 32,768.
    At 8,192-token rows the same chunk compiled to 12.79 GB of scratch
    + the 2.38 GB of leaves, 15.17 GB (compiled once by hand with
    scripts/aot_v5e_hlo.py, PR 41, not here).

    Every grouped product — the three of a SwiGLU expert, their dx and
    dW, under the bound's 16,384 rows and over it at 32,768 — runs the
    chip's kernel in the tiles `grouped_tiles` states for the call's
    OWN shape: hidden 2304 = 4.5 x 512 in 3 x 768 and the expert width
    896 = 7 x 128 whole, none in the 128 x 128 blocks the compiler
    takes at such widths (the rule PR 32 wrote for `nemotron_h`'s 2688
    / 1856).

    The attention core is the kernel in BOTH kinds of layer, at a
    window of TWO tiles (1,024 in tiles of 512): 5 forward calls a
    layer (2 gradient passes x (forward + recomputed) + the loss's) and
    2 backward, 3 sliding layers and 1 full, and no array of S x S
    elements a head anywhere.  About 90 s."""
    described.leaves_are_donated_and_fit(chunk, 595_154_176, 12.12e9,
                                         9_963_002_368)
    c = chunk.task.arch
    s, block = c.sequence_length, c.attention_block
    assert (s, block, c.sliding_window) == (4096, 512, 1024)
    slots = s * c.num_experts_per_tok
    bound = lm.live_rows_bound(slots, c)
    assert (bound, slots) == (16384, 32768)
    calls = aot.ragged_dot_calls(chunk.text)
    assert {shape for shape, _ in calls} == {
        (m, k, n) for m in (bound, slots)
        for k, n in ((2304, 896), (896, 2304))}
    assert all(tiles == lm.grouped_tiles(*shape) for shape, tiles in calls), \
        sorted(set(calls))
    assert {tiles for _, tiles in calls} == {"256,768,896", "256,896,768"}
    assert not any(tiles.endswith(",128,128") for _, tiles in calls)
    # the core's calls, by kernel and scope
    assert described.by_kernel_and_scope(
        described.mosaic_calls(chunk.text, "kps_attn_core_"),
        described.CORE_SCOPES) == {
        ("kps_attn_core_forward", "kps.attn.window"): 15,
        ("kps_attn_core_forward", "kps.attn.full"): 5,
        ("kps_attn_core_backward", "kps.attn.window"): 6,
        ("kps_attn_core_backward", "kps.attn.full"): 2}
    # (but q as its norm's kernel reads it, `[S, 32 x 128]`)
    q_wide = c.num_attention_heads * c.head_dim
    assert not described.square_of_scores(
        described.shapes_made(chunk.text), s,
        but=[(s, q_wide), (1, s, q_wide)])
    for scope in ("kps.attn.qkv", "kps.attn.norm_rope", "kps.attn.out",
                  "kps.moe.route", "kps.moe.sort", "kps.moe.place",
                  "kps.moe.expert_fn", "kps.moe.combine", "kps.lm.norm",
                  "kps.lm.embed", "kps.lm.head"):
        assert scope in chunk.text, scope
    for absent in ("kps.moe.shared", "kps.mlp", "kps.lm.layers"):
        assert absent not in chunk.text, absent


def test_mellums_taken_branch_of_the_bound_writes_no_zeros(aot, chunk):
    described.taken_branch_writes_no_zeros_for_the_other(aot, chunk, 32768)


def test_mellums_barrier_ties_the_leaves_and_passes_nothing_else(aot, chunk):
    """The parent's chunk ran 51 selects of the running sum, 2.381 GB of
    results, under the barrier's scope; no weight's relayout stands
    outside the worker loop, as in the parent's."""
    described.the_barrier_ties_the_leaves_and_passes_nothing_else(
        aot, chunk)


def test_mellums_placement_is_the_kernels_and_no_matrix(chunk):
    """16,384 rows under the bound x 4,096 tokens.  Each branch holds
    what the chunk held as products before the kernels (counted from
    its text, PR 42): 20 placing (4 expert layers x (2 gradient passes
    x (forward + recomputed) + the loss)) and 12 add-backs (the
    recomputed forward's is dead code), 40 and 24 in all."""
    described.a_large_placement_is_the_kernels_and_no_matrix(chunk, 40, 24)


def test_mellums_norm_and_rope_are_one_kernel_pass_that_rolls_the_lanes(
        chunk):
    """All 4 layers rotate, 3 sliding and 1 full.  20 top-level copies
    `f32[1,4096,32,64]` an update went with the plain lines, 326.9 ->
    286.4 Mcyc an update by XLA's estimate."""
    c = chunk.task.arch
    assert (c.layers("sliding_attention"), c.layers("full_attention")) \
        == (3, 1)
    described.norm_and_rope_are_one_kernel_pass(chunk, rotating=4, plain=0)


def test_what_no_scope_names_is_under_a_tenth_of_mellums_bytes(chunk):
    read = described.what_the_scopes_name(chunk)
    assert described.NAMED | described.NAMED_BY_EXPERTS <= read.named
    assert 0.0 < read.unnamed_share < 0.10, read.unnamed_share

"""The `trinity-mini-ep16.fused-bsp` cell's scan chunk, compiled once
for a described TPU v5e (tests/aot_described.py)."""

import math

import aot_described as described
from aot_described import aot, chunk, topo  # noqa: F401 — fixtures
from kafka_ps_tpu.models import lm_common as lm

CELL = ("afmoe", "benchmark/configs/trinity-mini-ep16.model.json")


def test_afmoes_chunk_holds_no_square_of_scores(aot, chunk):
    """504.1 M parameters held, 1 row of 4,096 tokens a worker.  The
    leaves are donated and the scratch stays under 9.55 GB: it reads
    8.682 GB since the fold's running sum stays out of the barrier (PR
    47: the limit is that reading and a tenth; the reading counts the
    sum's carried buffer twice, what is alive at once is under the
    parent's 8,308,171,776 bytes, tests/aot_described.py); 6.68 GB
    before, under ISSUE 40's 7.5 (6.75 with PR 42's placement kernels,
    which wrote the weighted rows the product had fused, 6.72 before
    them; 9.17 before PR 40; the plain tiles' 9,203,257,856 bytes before
    the attention kernel).

    The attention core is the kernel (models/attention_kernel.py, PR
    34): lowered for the chip, `blocked_attention` is Mosaic calls — a
    forward one a layer a pass, recomputed with the layer in a gradient
    pass, and a backward one — each under `kps.attn.window` or
    `kps.attn.full`.  No score array is in the program at all: with the
    plain tiles (PR 33) the largest were single tiles `[1, 4, 8, 512,
    L]`, L the tile's span of keys up to window + block = 2,560 in a
    sliding layer and 4,096 in the full one, computed three times
    forward; attention written plainly would hold `[1, 32, 4096,
    4096]`, 2.1 GB a layer a pass.  About 150 s."""
    described.leaves_are_donated_and_fit(chunk, 504_147_712, 9.55e9,
                                         8_308_171_776)
    c = chunk.task.arch
    s, block = c.sequence_length, c.attention_block
    assert (s, block, c.sliding_window) == (4096, 512, 2048)
    shapes = described.shapes_made(chunk.text)
    # (q as its projection writes it and its norm's kernel reads it,
    # `[S, 32 x 128]`, is as wide as the row is long: no scores)
    q_wide = c.num_attention_heads * c.head_dim
    assert not described.square_of_scores(
        shapes, s, but=[(s, q_wide), (1, s, q_wide)])
    # the core's calls, by kernel and scope: 2 gradient passes x
    # (forward + recomputed) + the loss's forward = 5 forward calls a
    # layer, 2 backward; 4 sliding layers and 1 full
    calls = described.mosaic_calls(chunk.text, "kps_attn_core_")
    assert described.by_kernel_and_scope(calls, described.CORE_SCOPES) == {
        ("kps_attn_core_forward", "kps.attn.window"): 20,
        ("kps_attn_core_forward", "kps.attn.full"): 5,
        ("kps_attn_core_backward", "kps.attn.window"): 8,
        ("kps_attn_core_backward", "kps.attn.full"): 2}
    assert chunk.text.count("kps_attn_core_") >= len(calls) == 35
    # and nothing makes a tile's scores: no float32 array of a tile's
    # 512 queries by a span of keys, under the core's scopes or anywhere
    assert not [sh for sh in shapes
                if len(sh) >= 2 and sh[-2] == block and sh[-1] >= block
                and sh[-1] % block == 0 and 8 in sh[:-2]]
    made = described.shapes_made("\n".join(
        line for line in chunk.text.splitlines()
        if any(scope in line for scope in described.CORE_SCOPES)), "f32")
    q_elements = s * c.num_attention_heads * c.head_dim
    assert made and max(math.prod(sh) for sh in made) <= q_elements, \
        sorted(made, key=math.prod)[-3:]
    # the grouped products at `[rows, 2048] x [8, 2048, 1024]`, under
    # the bound's 4,096 rows and over it at 32,768: the chip's own
    # kernel in the compiler's own tiles, as at `glm4_moe_lite`'s widths
    calls = aot.ragged_dot_calls(chunk.text)
    assert {shape for shape, _ in calls} == {
        (m, k, n) for m in (4096, 32768)
        for k, n in ((2048, 1024), (1024, 2048))}
    assert {tiles for _, tiles in calls} == {"512,512,512"}
    assert {lm.grouped_tiles(*shape) for shape, _ in calls} == {None}
    for scope in ("kps.attn.window", "kps.attn.full", "kps.attn.proj",
                  "kps.mlp", "kps.moe.experts"):
        assert scope in chunk.text, scope


def test_afmoes_taken_branch_of_the_bound_writes_no_zeros(aot, chunk):
    described.taken_branch_writes_no_zeros_for_the_other(aot, chunk, 32768)


def test_afmoes_barrier_ties_the_leaves_and_passes_nothing_else(aot, chunk):
    """The parent's chunk ran 93 selects of the running sum, 2.017 GB of
    results, under the barrier's scope; no weight's relayout stands
    outside the worker loop, as in the parent's."""
    described.the_barrier_ties_the_leaves_and_passes_nothing_else(
        aot, chunk)


def test_afmoes_placement_is_the_kernels_and_no_matrix(chunk):
    """4,096 rows under the bound x 4,096 tokens.  The layer norms the
    experts' sum and so needs it again backward: the recomputed
    forward's add-backs stay, 4 expert layers x (2 gradient passes x
    (forward + recomputed) + the loss) = 20 a branch of each, 40, and
    48 placing."""
    described.a_large_placement_is_the_kernels_and_no_matrix(chunk, 48, 40)


def test_afmoes_norm_and_rope_are_one_kernel_pass_that_rolls_the_lanes(
        chunk):
    """The 4 sliding layers rotate; the full one norms alone.  40
    top-level copies `f32[1,4096,32,64]` an update went with the plain
    lines, 340.3 -> 294.2 Mcyc an update by XLA's estimate."""
    c = chunk.task.arch
    assert (c.layers("sliding_attention"), c.layers("full_attention")) \
        == (4, 1)
    described.norm_and_rope_are_one_kernel_pass(chunk, rotating=4, plain=1)


def test_what_no_scope_names_is_under_a_tenth_of_afmoes_bytes(chunk):
    """2.2% when written."""
    read = described.what_the_scopes_name(chunk)
    assert described.NAMED | described.NAMED_BY_EXPERTS <= read.named
    assert 0.0 < read.unnamed_share < 0.10, read.unnamed_share

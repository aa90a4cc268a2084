"""The `lfm2-24b-a2b-ep8.fused-bsp` cell's scan chunk, compiled once
for a described TPU v5e (tests/aot_described.py)."""

import math
import re

import aot_described as described
from aot_described import aot, chunk, topo  # noqa: F401 — fixtures
from kafka_ps_tpu.models import lm_common as lm

CELL = ("lfm2_moe", "benchmark/configs/lfm2-24b-a2b-ep8.model.json")


def test_lfm2s_core_is_the_kernel_two_heads_to_a_lane_vector(aot, chunk):
    """469.3 M parameters held, 8 of 64 experts, 1 row of 4,096 tokens
    a worker.  The leaves are donated and the scratch is 7,698,161,664
    bytes since the fold's running sum stays out of the barrier (PR 47:
    the limit is that reading and a tenth; the reading counts the sum's
    carried buffer twice, what is alive at once is 7,366,776,320 bytes,
    1,536 over the parent's, tests/aot_described.py); 5,838,093,824
    bytes when written (5.84 + 1.88 GB of leaves = 7.72 GB, 16.4 bytes
    a parameter; 5,860,914,176 with the plain tiles, PR 45).  At
    8,192-token rows the plain-tiled chunk compiled to 7.47 GB of
    scratch + the 1.88 GB of leaves, 9.35 GB (compiled once by hand
    with scripts/aot_v5e_hlo.py, PR 45, not here): it fits.

    THE PIN PR 46 FLIPPED: heads of 64 channels are half a lane vector,
    and under an even number of KV heads `attention_kernel.takes` takes
    them two to a vector — q `[1, 4096, 8, 4, 64]` goes through the
    kernels as `[1, 4096, 4, 8, 128]`, the Trinity cell's shape — so,
    lowered for the chip, the ONE attention layer's core is Mosaic
    calls under `kps.attn.full`: a forward one a pass, recomputed with
    the layer in a gradient pass (2 x 2 + the loss's = 5), and a
    backward one a gradient pass (2).  That Mosaic lowers them at this
    shape is shown here without the chip.  No array of a tile's scores
    `[8, 4, 512, L]` is in the program (the plain tiles wrote them to
    memory, the largest 268 MB) and none of S x S elements a head; the
    largest array under the scope is a q, a cotangent or an output with
    its zero halves, `f32[1,4096,4,8,128]`, twice q's bytes.

    `norm_rope_kernel.takes` still takes whole lanes only: NO call of
    the norm-and-RoPE kernel is in the chunk, and the plain
    `rope(rms_norm(..))` cuts a 64-channel head at 32: the compiler
    lays q tokens-minor for it (`f32[1,4096,32,64]{1,3,2,0}`, PR 43's
    finding, a hundred times in the text).

    Every grouped product — the three of a SwiGLU expert, their dx and
    dW, under the bound's 4,096 rows and over it at 16,384 — runs the
    chip's kernel in the compiler's own tiles of 512: 2048 and 1536 are
    widths 512 divides, so `grouped_tiles` says nothing, as at the GLM
    and Trinity cells'.  About 115 s."""
    described.leaves_are_donated_and_fit(chunk, 469_285_248, 8.47e9,
                                         7_366_776_320)
    c = chunk.task.arch
    s, block = c.sequence_length, c.attention_block
    assert (s, block, c.head_dim) == (4096, 512, 64)
    assert (c.layers("conv"), c.layers("full_attention")) == (4, 1)
    calls = described.mosaic_calls(chunk.text, "kps_attn_core_")
    assert described.by_kernel_and_scope(calls, described.CORE_SCOPES) == {
        ("kps_attn_core_forward", "kps.attn.full"): 5,
        ("kps_attn_core_backward", "kps.attn.full"): 2}
    assert {made for _, made, *_ in calls} == {"f32[1,4096,4,8,128]"}
    assert "kps_norm_rope_" not in chunk.text
    shapes = described.shapes_made(chunk.text)
    assert not described.square_of_scores(shapes, s)
    # no tile's scores, of any span from one block to the whole row
    assert not [sh for sh in shapes
                if sh[-4:-1] == (8, 4, block) and sh[-1] % block == 0]
    made = described.shapes_made("\n".join(
        line for line in chunk.text.splitlines()
        if "kps.attn.full" in line), "f32")
    assert made and max(math.prod(sh) for sh in made) \
        == 2 * s * c.num_attention_heads * c.head_dim
    # q laid tokens-minor for the half-lane slices of the plain RoPE
    assert re.search(r"f32\[1,4096,32,64\]\{1,3,2,0", chunk.text)
    slots = s * c.num_experts_per_tok
    bound = lm.live_rows_bound(slots, c)
    assert (bound, slots) == (4096, 16384)
    calls = aot.ragged_dot_calls(chunk.text)
    assert {shape for shape, _ in calls} == {
        (m, k, n) for m in (bound, slots)
        for k, n in ((2048, 1536), (1536, 2048))}
    assert {tiles for _, tiles in calls} == {"512,512,512"}
    assert {lm.grouped_tiles(*shape) for shape, _ in calls} == {None}
    for scope in ("kps.ssm.proj", "kps.ssm.conv", "kps.attn.qkv",
                  "kps.attn.norm_rope", "kps.attn.out", "kps.attn.full",
                  "kps.mlp", "kps.moe.route", "kps.moe.sort",
                  "kps.moe.place", "kps.moe.expert_fn", "kps.moe.combine",
                  "kps.lm.norm", "kps.lm.embed", "kps.lm.head"):
        assert scope in chunk.text, scope
    for absent in ("kps.ssm.scan", "kps.ssm.norm", "kps.attn.window",
                   "kps.moe.shared", "kps.lm.layers"):
        assert absent not in chunk.text, absent


def test_lfm2s_taken_branch_of_the_bound_writes_no_zeros(aot, chunk):
    described.taken_branch_writes_no_zeros_for_the_other(aot, chunk, 16384)


def test_lfm2s_barrier_ties_the_leaves_and_passes_nothing_else(aot, chunk):
    """The parent's chunk ran 53 selects of the running sum, 1.877 GB of
    results, under the barrier's scope; one copy of a small leaf (8 MB)
    stays.  Four copies of a weight's shape stand in the entry
    computation, once a dispatch, as in the parent's."""
    described.the_barrier_ties_the_leaves_and_passes_nothing_else(
        aot, chunk, relayouts_outside=4)


def test_lfm2s_placement_is_the_kernels_and_no_matrix(chunk):
    """4,096 rows under the bound x 4,096 tokens, the Trinity cell's
    matrix.  Nothing norms the experts' sum, so the recomputed
    forward's add-backs are dead code, as in the Mellum2 cell's chunk:
    4 expert layers x (2 gradient passes x (forward + recomputed) + the
    loss) = 20 placing a branch, 40, and 12 add-backs, 24."""
    described.a_large_placement_is_the_kernels_and_no_matrix(chunk, 40, 24)


def test_lfm2s_norm_and_rope_are_the_plain_lines(chunk):
    """652 instructions under `kps.attn.norm_rope` when written, for
    ONE attention layer: slices, negations, concatenations and the
    copies the compiler makes for them (691 when the plain tiles read
    q and k, PR 45)."""
    described.norm_and_rope_are_the_plain_lines(chunk, 652)


def test_what_no_scope_names_is_under_a_tenth_of_lfm2s_bytes(chunk):
    """0.5% when written."""
    read = described.what_the_scopes_name(chunk)
    assert described.NAMED | described.NAMED_BY_EXPERTS | {
        "kps.ssm.conv", "kps.ssm.proj", "kps.attn.full", "kps.mlp"} \
        <= read.named
    assert 0.0 < read.unnamed_share < 0.10, read.unnamed_share

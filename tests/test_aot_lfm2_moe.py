"""The `lfm2-24b-a2b-ep8.fused-bsp` cell's scan chunk, compiled once
for a described TPU v5e (tests/aot_described.py)."""

import re

import aot_described as described
from aot_described import aot, chunk, topo  # noqa: F401 — fixtures
from kafka_ps_tpu.models import lm_common as lm

CELL = ("lfm2_moe", "benchmark/configs/lfm2-24b-a2b-ep8.model.json")


def test_lfm2s_chunk_runs_its_attention_off_both_kernels(aot, chunk):
    """469.3 M parameters held, 8 of 64 experts, 1 row of 4,096 tokens
    a worker.  The leaves are donated and the scratch is 5,860,914,176
    bytes when written (5.86 + 1.88 GB of leaves = 7.74 GB, 16.5 bytes
    a parameter, PR 45).  At 8,192-token rows the same chunk compiled
    to 7.47 GB of scratch + the 1.88 GB of leaves, 9.35 GB (compiled
    once by hand with scripts/aot_v5e_hlo.py, PR 45, not here): it
    fits, and what keeps such rows out of the cell is the plain core's
    time, not its bytes.

    THE PIN A LATER PR FLIPS: heads of 64 channels are half a lane
    vector, `attention_kernel.takes` and `norm_rope_kernel.takes` take
    whole lanes only, and so NO call of the attention kernel and NO
    call of the norm-and-RoPE kernel is in the chunk, lowered for the
    chip.  The core is `lm_common._attend_tiles`: a tile's scores `[8,
    4, 512, L]`, L the tile's span of keys from 512 to 4,096, are
    written to memory — the largest 268 MB — where the kernel forms
    them in VMEM; no array of S x S elements a head exists all the
    same.  And the plain `rope(rms_norm(..))` cuts a 64-channel head at
    32: the compiler lays q tokens-minor for it (`f32[1,4096,32,64]
    {1,3,2,0}`, PR 43's finding, a hundred times in the text).

    Every grouped product — the three of a SwiGLU expert, their dx and
    dW, under the bound's 4,096 rows and over it at 16,384 — runs the
    chip's kernel in the compiler's own tiles of 512: 2048 and 1536 are
    widths 512 divides, so `grouped_tiles` says nothing, as at the GLM
    and Trinity cells'.  About 115 s."""
    described.leaves_are_donated_and_fit(chunk, 469_285_248, 6.0e9)
    c = chunk.task.arch
    s, block = c.sequence_length, c.attention_block
    assert (s, block, c.head_dim) == (4096, 512, 64)
    assert (c.layers("conv"), c.layers("full_attention")) == (4, 1)
    assert "kps_attn_core_" not in chunk.text
    assert "kps_norm_rope_" not in chunk.text
    assert "tpu_custom_call" in chunk.text      # the reader sees kernels
    shapes = described.shapes_made(chunk.text)
    assert not described.square_of_scores(shapes, s)
    # a tile's scores, every span from one block to the whole row
    tiles = {sh[-1] for sh in shapes
             if sh[-4:-1] == (8, 4, block) and sh[-1] % block == 0}
    assert tiles == set(range(block, s + 1, block))
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


def test_lfm2s_placement_is_the_kernels_and_no_matrix(chunk):
    """4,096 rows under the bound x 4,096 tokens, the Trinity cell's
    matrix.  Nothing norms the experts' sum, so the recomputed
    forward's add-backs are dead code, as in the Mellum2 cell's chunk:
    4 expert layers x (2 gradient passes x (forward + recomputed) + the
    loss) = 20 placing a branch, 40, and 12 add-backs, 24."""
    described.a_large_placement_is_the_kernels_and_no_matrix(chunk, 40, 24)


def test_lfm2s_norm_and_rope_are_the_plain_lines(chunk):
    """691 instructions under `kps.attn.norm_rope` when written, for
    ONE attention layer: slices, negations, concatenations and the
    copies the compiler makes for them."""
    described.norm_and_rope_are_the_plain_lines(chunk, 691)


def test_what_no_scope_names_is_under_a_tenth_of_lfm2s_bytes(chunk):
    """0.5% when written."""
    read = described.what_the_scopes_name(chunk)
    assert described.NAMED | described.NAMED_BY_EXPERTS | {
        "kps.ssm.conv", "kps.ssm.proj", "kps.attn.full", "kps.mlp"} \
        <= read.named
    assert 0.0 < read.unnamed_share < 0.10, read.unnamed_share

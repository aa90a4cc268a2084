"""The `granite-4.0-h-micro-pp4.fused-bsp` cell's scan chunk, compiled
once for a described TPU v5e (tests/aot_described.py)."""

import aot_described as described
from aot_described import aot, chunk, topo  # noqa: F401 — fixtures

CELL = ("granitemoehybrid",
        "benchmark/configs/granite-4.0-h-micro-pp4.model.json")


def test_granites_chunk_fits_the_chip_at_2048_tokens_and_its_core_is_the_kernel(
        aot, chunk):
    """797.9 M parameters held — the largest state a fold has carried —
    1 row of 2,048 tokens a worker.  The leaves are donated and the
    scratch reads 10,575,263,744 bytes (10,829,666,304 before PR 49,
    while the scan wrote its decays): with the 3,191,402,240 bytes of
    leaves 13.77 GB, inside ISSUE 48's 15.2 GB rule (nine tenths of the
    chip's 16.9) at the ladder's FIRST step; what is alive at once is
    10,327,683,072 bytes (10,525,678,592 before); the limit is the
    reading and a tenth.
    (Compiled by hand with scripts/aot_v5e_hlo.py, PR 48, not here: at
    1,024-token rows 10.2908 GB of scratch, 13.48 GB with the leaves;
    at 1,024 tokens and an eighth of the vocabulary, 12,544 rows and
    772.2 M parameters, 9.9476 + 3.0887 = 13.04 GB; at 4,096-token
    rows 11.8090 GB, 15.00 GB with the leaves: the memory rule would
    hold there too, the time rule does not.)

    Nine of the ten layers are `nemotron_h.mamba2` at ONE group of B
    and C and chunks of 256: the chunked scan is in the program under
    `kps.ssm.scan` as `ssd_kernel`'s Mosaic
    calls (5 forward and 2 backward a mixer), and the decay tensor
    `f32[1,8,256,256,1,64]` (134 MB a layer pass, the largest array the
    scope made before PR 49) is nowhere
    (`the_scan_is_the_kernels_and_no_decay_is_written`).  Every
    layer's dense MLP stands under `kps.mlp`.

    The ONE attention layer has heads of 64 channels under 8 KV heads,
    an even number: `attention_kernel.takes` takes them two to a lane
    vector — q `[1, 2048, 8, 4, 64]` goes through the kernels as `[1,
    2048, 4, 8, 128]`, the LFM2 cell's pairing at half its row — so,
    lowered for the chip, the core is Mosaic calls under
    `kps.attn.full`: a forward one a pass, recomputed with the layer in
    a gradient pass (2 x 2 + the loss's = 5), and a backward one a
    gradient pass (2).  No array of S x S elements a head is in the
    program.  There is no head norm and no RoPE: NO call of the
    norm-and-RoPE kernel, and no instruction under `kps.attn.norm_rope`.
    About 80 s."""
    described.leaves_are_donated_and_fit(chunk, 797_850_560, 11.7e9,
                                         10_327_683_072, with_leaves=15.2e9)
    c = chunk.task.arch
    s, block = c.sequence_length, c.attention_block
    assert (s, block, c.head_dim, c.chunk_size, c.n_groups) == (
        2048, 512, 64, 256, 1)
    assert (c.layers("mamba"), c.layers("attention")) == (9, 1)
    calls = described.mosaic_calls(chunk.text, "kps_attn_core_")
    assert described.by_kernel_and_scope(calls, described.CORE_SCOPES) == {
        ("kps_attn_core_forward", "kps.attn.full"): 5,
        ("kps_attn_core_backward", "kps.attn.full"): 2}
    assert {made for _, made, *_ in calls} == {"f32[1,2048,4,8,128]"}
    assert "kps_norm_rope_" not in chunk.text
    assert "kps.attn.norm_rope" not in chunk.text
    # (the hidden size is 2,048 as the row is: a square projection and
    # a row of activations are no scores)
    shapes = described.shapes_made(chunk.text)
    assert not described.square_of_scores(shapes, s, but=[
        (2048, 2048), (2048, 2048, 1, 1, 1), (1, 2048, 2048)])
    # the scan's decay tensor, one group: [b, chunks, l, s, g, r]
    described.the_scan_is_the_kernels_and_no_decay_is_written(
        chunk, mixers=9)
    assert "ragged-dot" not in chunk.text and "kps.moe" not in chunk.text
    for scope in ("kps.ssm.proj", "kps.ssm.conv", "kps.ssm.scan",
                  "kps.ssm.norm", "kps.attn.qkv", "kps.attn.out",
                  "kps.attn.full", "kps.mlp", "kps.lm.norm", "kps.lm.embed",
                  "kps.lm.head"):
        assert scope in chunk.text, scope
    for absent in ("kps.attn.window", "kps.attn.proj", "kps.lm.layers"):
        assert absent not in chunk.text, absent


def test_granites_barrier_ties_the_leaves_and_passes_nothing_else(aot, chunk):
    """Nothing of a leaf's shape runs under the barrier's scope, and no
    weight-shaped relayout stands outside the worker loop."""
    described.the_barrier_ties_the_leaves_and_passes_nothing_else(
        aot, chunk, relayouts_outside=0)


def test_what_no_scope_names_is_under_a_tenth_of_granites_bytes(chunk):
    """The two x 0.22 and the residual adds lie under no scope of the
    model's: the compiler fuses them into their neighbours, and what is
    left unnamed is 1.2% of the result bytes when written."""
    read = described.what_the_scopes_name(chunk)
    assert described.NAMED | {"kps.ssm.scan", "kps.ssm.proj", "kps.ssm.conv",
                              "kps.ssm.norm", "kps.attn.full", "kps.mlp"} \
        <= read.named
    assert 0.0 < read.unnamed_share < 0.10, read.unnamed_share

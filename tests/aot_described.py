"""What the suite's compiles for a TPU v5e that is described, not
attached, share (scripts/aot_v5e_hlo.py: libtpu compiles from shapes
alone; nothing runs and no time is read).  tests/test_aot_<family>.py
states its cell as `CELL` and reads the ONE compile of that cell's scan
chunk its file makes (`chunk`); tests/test_aot_classifiers.py holds the
classifiers' programs.  A file a family, so that `--dist loadfile`
spreads the compiles and a new family or kernel edits one small file;
every file skips where libtpu offers no topology.  pytest collects
nothing from this module by itself."""

import collections
import importlib.util
import math
import os
import re
import sys
import types

import pytest

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import placement_kernel
from kafka_ps_tpu.models import ssd_kernel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the scopes every chunk names, and those only a family with an expert
# layer does
NAMED = {"kps.attn.qkv", "kps.attn.out", "kps.lm.norm", "kps.bsp.carry",
         "kps.bsp.fold"}
NAMED_BY_EXPERTS = {"kps.moe.sort", "kps.moe.place", "kps.moe.expert_fn",
                    "kps.moe.combine", "ragged-dot"}
CORE_SCOPES = ("kps.attn.window", "kps.attn.full")


@pytest.fixture(scope="module")
def aot():
    path = os.path.join(ROOT, "scripts", "aot_v5e_hlo.py")
    spec = importlib.util.spec_from_file_location("aot_v5e_hlo", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def topo(aot):
    try:
        return aot.describe_v5e()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


Chunk = collections.namedtuple("Chunk", "task compiled text scratch")


@pytest.fixture(scope="module")
def chunk(request, aot, topo):
    """The scan chunk of the file's `CELL` = (task, model file) at the
    published widths (4 workers folded one at a time, 1 row a worker, 8
    clocks), compiled once for the file → the task, the compiled
    program, its scheduled HLO text and its scratch in bytes."""
    task, compiled = aot.compile_folded_chunk(*request.module.CELL, topo)
    return Chunk(task, compiled, compiled.as_text(),
                 compiled.memory_analysis().temp_size_in_bytes)


# -- readers of a program's text ----------------------------------------------

def mosaic_calls(text, prefix):
    """The program's calls of the repo's own kernels whose name begins
    with `prefix` → [(kernel, the first array it makes, its number of
    operands, its `op_name`)], the `op_name` being what the benchmark's
    readers find a call's device time by."""
    return [(kernel, made, len(operands.split(", ")), op_name)
            for kernel, made, operands, op_name in re.findall(
                r"%%(%s\w+?)[.\d]* = \(?(\w+\[[\d,]*\]).*? custom-call\("
                r"(.*?)\), custom_call_target=\"tpu_custom_call\".*"
                r"op_name=\"([^\"]*)\"" % prefix, text)]


def by_kernel_and_scope(calls, scopes):
    """{(kernel, scope): calls}, each call under exactly one scope."""
    assert all(sum(scope in op_name for scope in scopes) == 1
               for *_, op_name in calls), calls
    return dict(collections.Counter(
        (kernel, scope) for kernel, *_, op_name in calls
        for scope in scopes if scope in op_name))


def shapes_made(text, dtype=r"\w+"):
    """The shape of every array an instruction of the program makes."""
    return {tuple(int(d) for d in dims.split(","))
            for dims in re.findall(r"= %s\[([\d,]+)\]" % dtype, text)}


def square_of_scores(shapes, s, but=()):
    """The shapes that could hold a row's scores whole: S twice among
    their axes, or S x S in one — `but` the ones stated as known."""
    return [sh for sh in shapes - set(but)
            if (len(sh) >= 2 and sh.count(s) >= 2) or s * s in sh]


# -- what several families' chunks are held to --------------------------------

def leaves_are_donated_and_fit(chunk, num_params, scratch, live,
                               with_leaves=15.0e9):
    """The leaves are donated (argument and result share their bytes:
    the flat vector is no argument and no result of the chunk), scratch
    + donated leaves stay under 15.0 GB of the chip's 16.9 (or under
    `with_leaves`, a reading seen to run the cell on the chip), and the
    scratch under the cell's own limit: PR 27's findings — leaves cut
    without a barrier, the local steps as a scan, the shared leaves
    loop-invariant in the fold over the workers — each cost one to two
    more copies of the parameters, which no cell's limit holds.

    `scratch` holds `temp_size_in_bytes`, what the harness prints as
    `memory_scratch_bytes`; `live` holds `peak_memory_in_bytes`, the
    compiler's own count of what is alive at once, arguments and all:
    the parent's to the byte, or to 1,536 of them, in every family
    where PR 47 raised `scratch`.  The two part where a buffer lives
    long: the first is libtpu's heap AND its fragmentation once more
    (PERF.md section 6, PR 47: a toy that fails to fit prints `HLO temp
    10.19G … 31.0% fragmentation (3.16G)` and reads 13.35 GiB here; the
    check that refuses a program takes the 10.19), and the fold's
    running sum keeps its carried buffer for the whole of a worker's
    update, where the parent rewrote it at the top (a pass over 2.4 GB
    a worker) and lent the buffer out in between: the heaps are as
    large (`glm4_moe_lite` 5,587,092,480 → 5,593,531,392 bytes, `ouro`
    9,033,253,888 → 9,026,962,432), the reading is one array of the
    parameters higher in every family."""
    assert chunk.task.num_params == num_params
    leaves = 4 * num_params
    memory = chunk.compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= leaves
    assert chunk.scratch + leaves < with_leaves, chunk.scratch
    assert chunk.scratch < scratch, chunk.scratch
    assert memory.peak_memory_in_bytes <= live, memory.peak_memory_in_bytes


def the_barrier_ties_the_leaves_and_passes_nothing_else(
        aot, chunk, relayouts_outside=0):
    """The barrier in the worker loop (parallel/bsp.py, under
    `kps.bsp.carry`) takes the shared leaves with the running loss.
    Until PR 47 it took them with the running SUM of deltas: the scan's
    zero start, sunk into the body as `select(i == 0, 0, total)`, could
    fuse with nothing across a barrier and was a pass of its own over
    every leaf of the sum a worker update — 83
    `broadcast_select_fusion`s and 2.365 GB of results in
    `glm4_moe_lite`'s chunk, 91 and 2.450 GB in `ouro`'s, 5.7 to 8.3 ms
    of an update on the chip.  So under that scope nothing the device
    runs has a leaf's shape but tuple elements and the `copy` of a few
    small leaves (ten and 0.085 GB in `glm4_moe_lite`'s, none in
    `ouro`'s), under a twentieth of the parameters' bytes.

    And what the barrier is for still holds: outside the worker loop no
    `convert`, `transpose` or `copy` makes an array of a weight's
    dimensions beyond the `relayouts_outside` the parent's chunk had —
    left loop-invariant, the leaves' relayouts for a worker's first
    step are hoisted out and kept beside them, two more copies of the
    parameters.  Counts from the text, never a time."""
    shapes = [shape for _, shape in chunk.task.specs]
    assert "kps.bsp.carry/optimization_barrier" in chunk.text
    passed = aot.under_the_carry(chunk.text, shapes)
    assert {opcode for _, opcode, _ in passed} <= {"copy"}, passed
    assert sum(size for *_, size in passed) < 4 * chunk.task.num_params / 20
    outside = aot.relayouts_outside_the_worker_loop(chunk.text, shapes)
    assert len(outside) == relayouts_outside, outside


def taken_branch_writes_no_zeros_for_the_other(aot, chunk, slots):
    """`routed_experts` places the sorted rows up to `live_rows_bound`
    or, in a pass that routes more here, all T·K slots, by a `cond` — 4
    expert layers x 7 passes an update, 28 `conditional`s.  Under
    `jax.grad` a `cond` returns one tuple of residuals for both
    branches and each branch fills the other's entries with zeros; the
    two place different row counts, so none is shared.  Since PR 40 the
    branch over the bound is a `jax.checkpoint` and keeps its inputs
    only, so in no computation that is branch 0 of a `conditional` —
    the pass under the bound, which every pass of the benchmark's cells
    takes (`moe.passes_over_bound` 0) — does a `broadcast` of a
    constant with T·K rows or columns stand alone.  Before: 96 of them
    and 15.57 GB an update in the `afmoe` cell's chunk (24.8 ms of its
    370 ms on the chip), 96 and 1.95 GB in `glm4_moe_lite`'s, 72 and
    2.21 GB in `nemotron_h`'s.  A count from the text, never a time."""
    c = chunk.task.arch
    assert c.sequence_length * c.num_experts_per_tok == slots
    assert lm.live_rows_bound(slots, c) < slots       # a `cond` is there
    assert chunk.text.count(" conditional(") == 28    # the reader sees them
    assert f"f32[{slots}," in chunk.text    # and the other branch's rows
    assert aot.zeros_in_taken_branches(chunk.text, slots) == []


def a_large_placement_is_the_kernels_and_no_matrix(chunk, placing,
                                                   adding_back):
    """Where `placement_kernel.takes` the expert layer's two products
    with the 0/1 matrix — under the bound and over it — they are Mosaic
    calls lowered for the chip and NO 0/1 array of rows x tokens
    elements is in the program (before PR 42: `bf16[16384,4096]`, 134
    MB a layer a pass and four kept for the backward passes, in the
    `mellum` cell's chunk, `bf16[4096,4096]` in `afmoe`'s).  The calls,
    by kernel and scope, over both branches of the bound: `placing`
    under `kps.moe.place` and `adding_back` under `kps.moe.combine`,
    and under `jax.grad` 8 a branch of each as the other's transpose,
    which keep the forward product's scope — so the two scopes go on
    holding what `moe_placement_self_share` and
    `moe_placement_roofline_share` read."""
    c = chunk.task.arch
    s = c.sequence_length
    slots = s * c.num_experts_per_tok
    bound = lm.live_rows_bound(slots, c)
    assert placement_kernel.takes(bound, s, c.hidden_size)
    assert placement_kernel.takes(slots, s, c.hidden_size)
    # the matrix was bfloat16 and its mask and one-hot compare `pred`,
    # made under the expert layer's scopes (a gate of `[4096 tokens,
    # 32 x 128]` is as large)
    made = re.compile(r"= (?:bf16|pred)\[(?:%s)\]" % "|".join(
        f"{a},{b}" for rows in (bound, slots)
        for a, b in ((rows, s), (s, rows))))
    assert not [line for line in chunk.text.splitlines()
                if "kps.moe" in line and made.search(line)]
    assert by_kernel_and_scope(mosaic_calls(chunk.text, "kps_moe_"),
                               ("kps.moe.place", "kps.moe.combine")) == {
        ("kps_moe_place", "kps.moe.place"): placing,
        ("kps_moe_add_back", "kps.moe.combine"): adding_back,
        ("kps_moe_add_back", "kps.moe.place"): 2 * 8,
        ("kps_moe_place", "kps.moe.combine"): 2 * 8}


def a_smaller_placement_is_left_to_the_product(chunk):
    """Where `placement_kernel.takes` the cell's 0/1 matrix not, no
    kernel of the placement is in the chunk and the matrix is."""
    c = chunk.task.arch
    s = c.sequence_length
    bound = lm.live_rows_bound(s * c.num_experts_per_tok, c)
    assert not placement_kernel.takes(bound, s, c.hidden_size)
    assert "kps_moe_" not in chunk.text
    assert f"bf16[{bound},{s}]" in chunk.text


def norm_and_rope_are_one_kernel_pass(chunk, rotating, plain):
    """q `[4096, 32 x 128]` and k `[4096, 4 x 128]` go through their
    head norm and RoPE as `lm_common.head_norm_rope`'s kernel
    (models/norm_rope_kernel.py, PR 43), Mosaic calls whose `op_name`
    lies under `kps.attn.norm_rope` — q's and k's a layer a pass: 2
    gradient passes x (forward + recomputed) + the loss's forward = 5
    forward passes and 2 backward, with the angles' two tables in the
    `rotating` layers (4 operands forward) and without in the `plain`
    ones, which norm alone (2).

    What the plain lines cost is gone with them.  A half of a 128-lane
    vector (`x[..., :64]`, `concatenate([-x2, x1])`) made the compiler
    hold q and k tokens-minor, `f32[1,4096,32,128]{1,3,2,0}`, and copy
    at every boundary that wants the channels in lanes: 15.5 of the
    scope's 39.0 Mcyc (XLA's own estimate) in the `afmoe` cell's chunk.
    And q's result leaves the kernel eight heads a tile, as the
    attention kernel reads it: no copy `f32[…,32,128]` stands between
    the two, where 25 stood between the projection and the plain norm.
    Counts from the text, never a time."""
    c = chunk.task.arch
    assert (c.sequence_length, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim) == (4096, 32, 4, 128)
    calls = mosaic_calls(chunk.text, "kps_norm_rope_")
    assert all("kps.attn.norm_rope" in op_name for *_, op_name in calls)
    forward, backward = "kps_norm_rope_forward", "kps_norm_rope_backward"
    q, k, q_flat = "f32[1,131072,128]", "f32[1,4096,512]", "f32[1,4096,4096]"
    want = {(forward, q, 4): 5 * rotating, (forward, k, 4): 5 * rotating,
            (backward, q_flat, 5): 2 * rotating,
            (backward, k, 5): 2 * rotating,
            (forward, q, 2): 5 * plain, (forward, k, 2): 5 * plain,
            (backward, q_flat, 3): 2 * plain, (backward, k, 3): 2 * plain}
    assert collections.Counter(call[:3] for call in calls) \
        == {key: n for key, n in want.items() if n}
    # nothing is laid tokens-minor, and no half of a head is copied
    assert not re.search(r"f32\[1,4096,32,128\]\{1,3,2,0", chunk.text)
    assert not re.search(r"f32\[1,4096,32,64\]", chunk.text)
    copies = [line for line in chunk.text.splitlines()
              if re.match(r"\s*(?:ROOT )?%copy[.\d]* = ", line)]
    assert copies                       # the reader sees the program's
    assert not [line for line in copies if re.search(
        r"kps\.attn\.norm_rope/(slice|neg|concatenate)\"", line)]
    assert not [line for line in copies if re.search(
        r"= f32\[(1,)?4096,32,128\]", line)]


def the_scan_is_the_kernels_and_no_decay_is_written(chunk, mixers):
    """Where `ssd_kernel.takes` the Mamba-2 mixers' shape, the chunked
    scan is Mosaic calls lowered for the chip, ALL under
    `kps.ssm.scan` — the scope `ssm_scan_roofline_share`,
    `ssm_share` and benchmark/self_time.py find the scan's device time
    by, forward, recomputed and backward — and none under another or no
    name: a forward one a mixer a pass, recomputed with the block in a
    gradient pass (2 x 2 + the loss's = 5), and a backward one a
    gradient pass (2), as the attention core's are counted.

    And what the plain lines wrote is gone with them: NO float32 array
    of chunks x Q x Q x heads elements — the decay `[b, chunks, l, s, g,
    r]`, its product with the scores, their cotangents — is made
    anywhere in the program, in any order of its axes, and nothing
    under the scope is as large (before PR 49 `f32[1,8,256,256,1,64]`,
    134 MB a layer a pass, was the largest array the Granite cell's
    scope made; `f32[1,8,128,128,8,8]`, 33.5 MB, the Nemotron cell's).
    Counts from the text, never a time."""
    c = chunk.task.arch
    chunks, q, heads = c.chunks_a_row, c.chunk_size, c.mamba_num_heads
    assert ssd_kernel.takes((1, c.sequence_length, heads, c.mamba_head_dim),
                            c.n_groups, c.ssm_state_size, q)
    calls = mosaic_calls(chunk.text, "kps_ssd_")
    assert by_kernel_and_scope(calls, ("kps.ssm.scan",)) == {
        ("kps_ssd_forward", "kps.ssm.scan"): 5 * mixers,
        ("kps_ssd_backward", "kps.ssm.scan"): 2 * mixers}
    # y `[S, heads x P]` forward; backward dx in its lanes of `[x | B | C]`
    assert {(kernel, made) for kernel, made, *_ in calls} == {
        ("kps_ssd_forward", f"f32[1,{c.sequence_length},{c.mamba_inner}]"),
        ("kps_ssd_backward", f"f32[1,{c.sequence_length},{c.conv_dim}]")}

    def axes(shape):
        return sorted(d for d in shape if d > 1)
    decays = [axes((chunks, q, q, heads)),
              axes((chunks, q, q, c.n_groups, heads // c.n_groups))]
    assert not [sh for sh in shapes_made(chunk.text, "f32")
                if axes(sh) in decays]
    under = shapes_made("\n".join(
        line for line in chunk.text.splitlines()
        if "kps.ssm.scan" in line), "f32")
    assert under and max(math.prod(sh) for sh in under) \
        < chunks * q * q * heads


def norm_and_rope_are_the_plain_lines(chunk, lines):
    """A family that keeps `lm_common.rope` has no kernel of the norm
    and RoPE in its chunk, and the program's instructions under
    `kps.attn.norm_rope` count `lines`."""
    assert "kps_norm_rope_" not in chunk.text
    assert sum("kps.attn.norm_rope" in line
               for line in chunk.text.splitlines()) == lines


def what_the_scopes_name(chunk):
    """Of the result bytes of the instructions the chip runs in the
    chunk, what lies under which scope of the model or of the parameter
    plane once the compiler's own operations are adopted by the scope
    they serve (benchmark/self_time.py, the table `--trace 1` prints):
    the module as parsed, the instructions the device runs, each one's
    (scope, …), the scopes named and the share of the bytes under none.
    A count from the program's text, not a time; a scope lost from the
    program, or a compiler that names its instructions otherwise, shows
    here before a chip run."""
    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import self_time
    module = self_time.parse_hlo(chunk.text)
    spec = self_time.table_spec()
    run = self_time.run_on_the_device(module)
    assert len(run) > 3000 and module["entry"] in module["computations"]
    scopes = self_time.adopted_scopes(module, spec)
    return types.SimpleNamespace(
        self_time=self_time, module=module, run=run, scopes=scopes,
        named={scope for scope, _ in scopes.values() if scope},
        unnamed_share=self_time.unnamed_byte_share(module, spec))

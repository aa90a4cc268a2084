"""The solver programs compiled for a TPU v5e that is described, not
attached (scripts/aot_v5e_hlo.py): the guard against the flat carry's
return.  libtpu compiles from shapes alone; nothing runs and no time is
read.  Every compile of the suite for the described chip lives in this
one file, behind a fixture (one process loads libtpu and keeps it), and
skips where libtpu offers no topology."""

import functools
import importlib.util
import json
import math
import os
import re
import sys

import pytest

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import placement_kernel

HIDDEN, WORKERS = 512, 8            # reduced: about 3 s a program
W1_BYTES = HIDDEN * 1024 * 4


@pytest.fixture(scope="module")
def aot():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "aot_v5e_hlo.py")
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


@pytest.fixture(scope="module")
def program_text(aot, topo):
    """A program of scripts/aot_v5e_hlo.py at the reduced widths,
    compiled once for the file → its scheduled HLO text."""
    @functools.cache
    def text(program):
        return aot.compile_program(program, topo, hidden=HIDDEN,
                                   workers=WORKERS).as_text()
    return text


CHUNKS = {
    "glm4_moe_lite": "benchmark/configs/glm-4.7-flash-ep8.model.json",
    "nemotron_h": "benchmark/configs/nemotron-3-nano-ep16.model.json",
    "afmoe": "benchmark/configs/trinity-mini-ep16.model.json",
    "ouro": "benchmark/configs/ouro-2.6b.model.json",
    "mellum": "benchmark/configs/mellum2-12b-ep4.model.json"}
# the scopes every chunk names, and those only a family with an expert
# layer does
NAMED = {"kps.attn.qkv", "kps.attn.out", "kps.lm.norm", "kps.bsp.carry",
         "kps.bsp.fold"}
# lines of the parent's chunks (f35376d) that name `kps.attn.norm_rope`
OURO_NORM_ROPE_LINES, GLM_NORM_ROPE_LINES = 2567, 3329
NAMED_BY_EXPERTS = {"kps.moe.sort", "kps.moe.place", "kps.moe.expert_fn",
                    "kps.moe.combine", "ragged-dot"}


@pytest.fixture(scope="module")
def folded_chunk(aot, topo):
    """A language-model cell's scan chunk at the published widths,
    compiled once for the file → (the task, the compiled program)."""
    @functools.cache
    def chunk(family, model_json=None):
        return aot.compile_folded_chunk(family, model_json or CHUNKS[family],
                                        topo)
    return chunk


@pytest.mark.parametrize("program", ["bsp_scan", "bsp_scan_mesh", "gang"])
def test_no_relayout_of_every_workers_parameters(aot, program_text, program):
    """Outside the fused computations no `copy` and no `slice` has a
    result as large as half of W1 times the workers on the chip (a bf16
    W1 of every worker).  With the flat vector carried through the local
    solver each program had three to five: W1 cut out of `[workers, P]`,
    re-laid out as a matrix, and its gradient re-laid out to be
    concatenated back (PERF.md §6, PR 25)."""
    text = program_text(program)
    assert "fusion(" in text            # the reader sees the program
    assert aot.big_relayouts(text, WORKERS * W1_BYTES // 2) == []


@pytest.mark.parametrize("program", ["bsp_scan", "bsp_scan_mesh", "gang"])
def test_no_worker_has_a_w1_before_its_first_gradient(aot, program_text,
                                                      program):
    """In the order the device runs the program, nothing makes a
    float32 `[workers, H, F]` array before the first matrix product of
    the first local step's gradient: that step reads the shared leaves,
    and a worker's first own W1 is what its parameter step writes
    (models/task.py `local_steps`, PR 30).  With all k steps a scan
    from the shared leaves, every program began a clock with a
    `broadcast` of W1 to every worker (`broadcast_in_dim` here, before
    the scan's `while`): 1.08 GB at the cells' size, and a scratch of
    3.9332 GB for `bsp_scan` against 2.8422 GB now (compiled here for
    the described chip at 64 workers x H=4096)."""
    assert aot.made_before(program_text(program), "f32",
                           (WORKERS, HIDDEN, 1024),
                           aot.FIRST_GRAD_PRODUCT) == []


def test_the_folded_chunk_at_the_published_widths_fits_the_chip(aot,
                                                                  folded_chunk):
    """The scan chunk of the benchmark's language-model cell (591.3 M
    parameters held, 4 workers folded one at a time, 1 row of 1,024
    tokens, 8 clocks), compiled for the described chip: the leaves are
    donated (argument and result share their bytes) and the program's
    scratch stays under 6.4 GB, so that it fits the chip's 16.9 GB
    beside nothing but its own leaves.  It reads 5.80 GB since the
    over-the-bound branch of the expert layer keeps its inputs only (PR
    40: the limit is that reading and a tenth); 6.37 GB since the
    expert layers are written out (PR 38); 9.32 GB scanned over their
    stack (10.21 GB at 2 rows when written, 11.39 GB there since the
    expert layer places its rows under a bound or all of them, both
    branches compiled);
    16.49 GB with the local steps scanned and the shared leaves left
    loop-invariant in the fold over the workers (the compiler then
    keeps a relayout of every weight beside the loop), and 4.7 GB more
    with the flat vector cut into leaves without a barrier (PERF.md
    section 6, PR 27).  About 105 s (50 s scanned)."""
    task, compiled = folded_chunk("glm4_moe_lite")
    assert task.num_params == 591_294_976
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * task.num_params
    assert memory.temp_size_in_bytes < 6.4e9, memory.temp_size_in_bytes
    # the expert layers are written out (PR 38): no array carries the
    # wire's leading layer axis — the scan over the stack copied a
    # layer's matrices out of `f32[4,8,2048,1536]` and wrote its
    # gradient back into one, 259 such lines and 31% of an update
    text = compiled.as_text()
    assert task.arch.num_moe_layers == 4 and task.arch.experts_held == 8
    assert "f32[8,2048,1536]" in text          # the reader sees a layer's
    assert not re.search(r"f32\[4,8,[\d,]*\]", text)
    # the grouped products are the chip's own kernel, not a dense
    # product, and at 2048 x 1536 it is told nothing: its own tiles
    calls = aot.ragged_dot_calls(text)
    assert calls and {tiles for _, tiles in calls} == {"512,512,512"}
    assert {lm.grouped_tiles(*shape) for shape, _ in calls} == {None}


def test_the_second_language_models_chunk_fits_the_chip_too(aot,
                                                              folded_chunk):
    """The scan chunk of `nemotron-3-nano-ep16.fused-bsp` (667.0 M
    parameters held, 4 workers folded one at a time, 1 row a worker at
    the cell's own sequence length, 8 clocks), compiled for the
    described chip — PR 27's four findings as assertions for this family
    too, which shares the frame they were made in.  The leaves are
    donated (the flat vector is no argument and no result of the chunk:
    argument and result share their bytes); scratch + donated leaves
    stay under 15.0 GB; and there is no second copy of the shared
    leaves: the scratch reads 7.66 GB at the cell's 1,024 tokens (15.5
    bytes a parameter with the leaves; 9.01 GB at 2,048, to the byte
    what the chip's backend reported, PR 31), and each of the other
    three findings —
    leaves cut without a barrier, the local steps as a scan, the shared
    leaves loop-invariant in the fold — cost one to two more copies of
    the parameters, 2.67 GB each, which 10.5 GB does not hold.  About
    90 s."""
    task, compiled = folded_chunk("nemotron_h")
    assert task.num_params == 666_963_456
    memory = compiled.memory_analysis()
    leaves = 4 * task.num_params
    assert memory.alias_size_in_bytes >= leaves
    assert memory.temp_size_in_bytes + leaves < 15.0e9, \
        memory.temp_size_in_bytes
    assert memory.temp_size_in_bytes < 10.5e9, memory.temp_size_in_bytes
    text = compiled.as_text()
    # every grouped product — the two of an expert, their dx and dW,
    # under the bound's 768 rows and over it at 6,144 — runs the chip's
    # kernel in the tiles `grouped_tiles` states for the call's OWN
    # shape (dx: the product's turned round), none in the 128 x 128
    # blocks the compiler takes at 2688 and 1856 (1.09 ms a call for
    # 0.23-0.29, PERF.md section 5).  The hint is an undocumented
    # frontend attribute: a libtpu that stops honouring it fails here
    calls = aot.ragged_dot_calls(text)
    assert {shape for shape, _ in calls} == {
        (m, k, n) for m in (768, 6144)
        for k, n in ((2688, 1856), (1856, 2688))}
    assert all(tiles == lm.grouped_tiles(*shape) for shape, tiles in calls), \
        sorted(set(calls))
    assert not any(tiles.endswith(",128,128") for _, tiles in calls)
    # the chunked scan is in the program under its own scope
    assert "kps.ssm.scan" in text and "kps.attn" in text


def test_the_third_language_models_chunk_holds_no_square_of_scores(
        aot, folded_chunk):
    """The scan chunk of `trinity-mini-ep16.fused-bsp` (504.1 M
    parameters held, 4 workers folded one at a time, 1 row of 4,096
    tokens a worker, 8 clocks), compiled for the described chip — PR
    27's four findings as assertions for this family too: the leaves
    are donated, scratch + donated leaves stay under 15.0 GB (6.72 +
    2.02 GB since PR 40, 9.17 + 2.02 before), and there is no second
    copy of the shared leaves (2.02 GB each, which 10.5 GB of scratch
    does not hold).

    And the attention core is the kernel (models/attention_kernel.py,
    PR 34): lowered for the chip, `blocked_attention` is Mosaic calls —
    a forward one a layer a pass, recomputed with the layer in a
    gradient pass, and a backward one — each a `custom-call` whose
    `op_name` lies under `kps.attn.window` or `kps.attn.full`, which is
    what the benchmark's readers find its device time by.  No score
    array is in the program at all: with the plain tiles (PR 33) the
    largest were single tiles `[1, 4, 8, 512, L]`, L the tile's span of
    keys up to window + block = 2,560 in a sliding layer and 4,096 in
    the full one, computed three times forward; the other two families'
    attention would hold `[1, 32, 4096, 4096]`, 2.1 GB a layer a pass.
    The scratch stays at or under the plain tiles' 9,203,257,856 bytes.
    About 150 s."""
    task, compiled = folded_chunk("afmoe")
    assert task.num_params == 504_147_712
    c = task.arch
    s, block = c.sequence_length, c.attention_block
    assert (s, block, c.sliding_window) == (4096, 512, 2048)
    memory = compiled.memory_analysis()
    leaves = 4 * task.num_params
    assert memory.alias_size_in_bytes >= leaves
    assert memory.temp_size_in_bytes + leaves < 15.0e9, \
        memory.temp_size_in_bytes
    assert memory.temp_size_in_bytes <= 9_203_257_856, \
        memory.temp_size_in_bytes
    text = compiled.as_text()
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"= \w+\[([\d,]+)\]", text)}
    # (q as its projection writes it and its norm's kernel reads it,
    # `[1, S, 32 x 128]`, is as wide as the row is long: no scores)
    q_flat = (1, s, c.num_attention_heads * c.head_dim)
    assert not [sh for sh in shapes - {q_flat}
                if len(sh) >= 3 and sh.count(s) >= 2]
    assert not [sh for sh in shapes if s * s in sh]
    # the core's calls, by kernel and scope: 2 gradient passes x
    # (forward + recomputed) + the loss's forward = 5 forward calls a
    # layer, 2 backward; 4 sliding layers and 1 full
    calls = re.findall(
        r"%(kps_attn_core_\w+?)[.\d]* = .* custom-call\(.*"
        r"custom_call_target=\"tpu_custom_call\".*op_name=\"([^\"]*)\"", text)
    scopes = ("kps.attn.window", "kps.attn.full")
    assert all(sum(scope in op_name for scope in scopes) == 1
               for _, op_name in calls), calls
    assert {(kernel, scope): sum(k == kernel and scope in op_name
                                 for k, op_name in calls)
            for kernel in ("kps_attn_core_forward", "kps_attn_core_backward")
            for scope in scopes} == {
        ("kps_attn_core_forward", "kps.attn.window"): 20,
        ("kps_attn_core_forward", "kps.attn.full"): 5,
        ("kps_attn_core_backward", "kps.attn.window"): 8,
        ("kps_attn_core_backward", "kps.attn.full"): 2}
    assert text.count("kps_attn_core_") >= len(calls) == 35
    # and nothing makes a tile's scores: no float32 array of a tile's
    # 512 queries by a span of keys, under the core's scopes or anywhere
    assert not [sh for sh in shapes
                if len(sh) >= 2 and sh[-2] == block and sh[-1] >= block
                and sh[-1] % block == 0 and 8 in sh[:-2]]
    under_core = [line for line in text.splitlines()
                  if any(scope in line for scope in scopes)]
    made = {tuple(int(d) for d in dims.split(","))
            for line in under_core
            for dims in re.findall(r"= f32\[([\d,]+)\]", line)}
    q_elements = s * c.num_attention_heads * c.head_dim
    assert made and max(math.prod(sh) for sh in made) <= q_elements, \
        sorted(made, key=math.prod)[-3:]
    # the grouped products at `[rows, 2048] x [8, 2048, 1024]`, under
    # the bound's 4,096 rows and over it at 32,768: the chip's own
    # kernel in the compiler's own tiles, as at the GLM family's widths
    calls = aot.ragged_dot_calls(text)
    assert {shape for shape, _ in calls} == {
        (m, k, n) for m in (4096, 32768)
        for k, n in ((2048, 1024), (1024, 2048))}
    assert {tiles for _, tiles in calls} == {"512,512,512"}
    assert {lm.grouped_tiles(*shape) for shape, _ in calls} == {None}
    for scope in ("kps.attn.window", "kps.attn.full", "kps.attn.proj",
                  "kps.mlp", "kps.moe.experts"):
        assert scope in text, scope


@pytest.mark.parametrize("family,slots,scratch", [
    ("afmoe", 32768, 7.5e9), ("glm4_moe_lite", 4096, 6.4e9),
    ("nemotron_h", 6144, 8.3e9), ("mellum", 32768, 9.9e9)])
def test_the_taken_branch_of_the_bound_writes_no_zeros_for_the_other(
        aot, folded_chunk, family, slots, scratch):
    """An expert family's chunk, compiled for the described chip (the
    fixture's own compile, no second one): `routed_experts` places the
    sorted rows up to `live_rows_bound` or, in a pass that routes more
    here, all T·K slots, by a `cond` — 4 expert layers x 7 passes an
    update, 28 `conditional`s.  Under `jax.grad` a `cond` returns one
    tuple of residuals for both branches and each branch fills the
    other's entries with zeros; the two place different row counts, so
    none is shared.  Since PR 40 the branch over the bound is a
    `jax.checkpoint` and keeps its inputs only, so in no computation
    that is branch 0 of a `conditional` — the pass under the bound,
    which every pass of the benchmark's cells takes
    (`moe.passes_over_bound` 0) — does a `broadcast` of a constant
    with T·K rows or columns stand alone.  Before: 96 of them, 15.57 GB
    an update in the third family's chunk (32,768 slots; 24.8 ms of its
    370 ms on the chip), 96 and 1.95 GB in the first's (4,096), 72 and
    2.21 GB in the second's (6,144).  The scratch fell with them:
    9,170,827,264 -> 6.72 GB, 6,369,835,008 -> 5.80 GB, 7.64 -> 7.55 GB
    (the limits: 7.5 GB as ISSUE 40 set it, the other two their reading
    and a tenth).  A count from the text, never a time."""
    task, compiled = folded_chunk(family)
    c = task.arch
    assert c.sequence_length * c.num_experts_per_tok == slots
    assert lm.live_rows_bound(slots, c) < slots       # a `cond` is there
    text = compiled.as_text()
    assert text.count(" conditional(") == 28          # the reader sees them
    assert f"f32[{slots}," in text      # and the other branch's rows
    assert aot.zeros_in_taken_branches(text, slots) == []
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < scratch, memory.temp_size_in_bytes


def test_the_fourth_language_models_chunk_holds_its_layers_once(
        aot, folded_chunk, tmp_path):
    """The scan chunk of `ouro-2.6b.fused-bsp` (612.4 M parameters held,
    8 layers run 4 times on one set of leaves, 4 workers folded one at
    a time, 1 row of 1,024 tokens a worker, 8 clocks), compiled for the
    described chip.  The leaves are donated and scratch + donated
    leaves stay under 15.0 GB: 10.60 + 2.45 = 13.05 GB when written,
    21.3 bytes a parameter, as a `lax.scan` over the steps with the
    layers written out in its body and the leaves closed over (73 s);
    with the 32 applications written out 8.34 + 2.45 GB in 184-220 s,
    over what the third family's chunk takes — why the loop is a scan.

    A layer's leaves are held ONCE: no array carries a leading axis of
    the steps over a leaf's shape (a stack of the leaves, or of their
    gradients), and what the loop keeps for the backward pass is one
    `[4, 1, 1024, 2048]` stack of inputs a layer.  What the loop costs
    beyond a once-through program — the same chunk at `total_ut_steps`
    1, 6.02 GB, which never holds its layers' gradient whole: each
    leaf's is consumed by its parameter step as it is made — is 4.58
    GB, 2.8 arrays of the layers' gradient (1.64 GB): the gradient
    itself, carried as the backward loop's state and summed over the
    uses there (no four gradients side by side), the loop-invariant
    leaves' bfloat16 roundings and relayouts that the compiler hoists
    out of the forward and of the backward `while` (`bf16[2048,5632]`
    in the loops' state, 0.82 GB each), and 24 more saved inputs.
    ISSUE 39 asked for at most ONE such array beyond; three is what
    holds, and PERF.md section 7 names the two the compiler adds.

    The attention core is the kernel at ONE query head a key/value
    head (`[1, 1024, 16, 1, 128]`, tiles of 512), under
    `kps.attn.full` inside `kps.lm.layers`: a loop's body holds each
    layer's call once, so 2 gradient passes x (forward + recomputed) +
    the loss's forward = 5 forward calls a layer and 2 backward.  No
    array of S x S elements a head is in the program.  About 75 s +
    60 s for the once-through chunk."""
    task, compiled = folded_chunk("ouro")
    assert task.num_params == 612_435_968
    c = task.arch
    s, block = c.sequence_length, c.attention_block
    assert (s, block, c.num_hidden_layers, c.total_ut_steps) == (1024, 512,
                                                                 8, 4)
    memory = compiled.memory_analysis()
    leaves = 4 * task.num_params
    layers = 4 * c.num_hidden_layers * lm.num_params(
        [(n, sh) for n, sh in task.specs if n.startswith("l0.")])
    assert layers == 8 * 51_388_416 * 4
    assert memory.alias_size_in_bytes >= leaves
    assert memory.temp_size_in_bytes + leaves < 15.0e9, \
        memory.temp_size_in_bytes
    # no further float32 copy of the layers' leaves fits under this
    assert memory.temp_size_in_bytes < 10.8e9, memory.temp_size_in_bytes
    text = compiled.as_text()
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"= \w+\[([\d,]+)\]", text)}
    assert not [sh for sh in shapes if len(sh) >= 2 and sh.count(s) >= 2]
    assert not [sh for sh in shapes if s * s in sh]
    # a leaf once: nothing stacks a leaf's shape over the steps, and
    # the inputs kept for the backward pass are stacked over them
    h, i = c.hidden_size, c.intermediate_size
    assert (h, i) in shapes and (i, h) in shapes and (h, h) in shapes
    assert not [sh for sh in shapes if len(sh) == 3 and sh[1:] in (
        (h, i), (i, h), (h, h))]
    assert (c.total_ut_steps, 1, s, h) in shapes
    # the core's calls, by kernel and scope
    calls = re.findall(
        r"%(kps_attn_core_\w+?)[.\d]* = \((f32\[[\d,]+\]).* custom-call\(.*"
        r"custom_call_target=\"tpu_custom_call\".*op_name=\"([^\"]*)\"", text)
    assert all("kps.lm.layers" in op_name and "kps.attn.full" in op_name
               and "kps.attn.window" not in op_name
               for _, _, op_name in calls), calls
    assert {made for _, made, _ in calls} == {"f32[1,1024,16,1,128]"}
    assert {kernel: sum(k == kernel for k, _, _ in calls)
            for kernel in ("kps_attn_core_forward",
                           "kps_attn_core_backward")} == {
        "kps_attn_core_forward": 5 * 8, "kps_attn_core_backward": 2 * 8}
    assert "ragged-dot" not in text and "kps.moe" not in text
    for scope in ("kps.lm.layers", "kps.attn.qkv", "kps.attn.norm_rope",
                  "kps.attn.out", "kps.mlp", "kps.lm.norm", "kps.lm.head"):
        assert scope in text, scope
    # against the once-through program of the same size
    body = json.load(open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), CHUNKS["ouro"])))
    once = tmp_path / "once_through.model.json"
    once.write_text(json.dumps(dict(body, total_ut_steps=1)))
    plain, plain_compiled = folded_chunk("ouro", str(once))
    assert plain.num_params == task.num_params
    beyond = (memory.temp_size_in_bytes
              - plain_compiled.memory_analysis().temp_size_in_bytes)
    assert 1 * layers < beyond < 3 * layers, beyond / layers


def test_the_fifth_language_models_chunk_walks_its_widths_in_told_tiles(
        aot, folded_chunk):
    """The scan chunk of `mellum2-12b-ep4.fused-bsp` (595.2 M parameters
    held, 16 of 64 experts, 4 workers folded one at a time, 1 row of
    4,096 tokens a worker, 8 clocks), compiled for the described chip.
    The leaves are donated and scratch + donated leaves stay under 15.0
    GB: 8,976,765,440 + 2,380,616,704 bytes when written (8.98 + 2.38 =
    11.36 GB, 19.1 bytes a parameter; the limit is that reading and a
    tenth).  A quarter of the experts held makes the expert layer's
    rows four times the third family's: the bound places 16,384 rows
    and a pass over it all 32,768 (until PR 42 through a
    `bf16[16384,4096]` 0/1 matrix, 134 MB, a layer a pass: the test
    below).  At 8,192-token rows the same chunk compiled to 12.79 GB
    of scratch + the 2.38 GB of leaves, 15.17 GB (compiled once by hand
    with scripts/aot_v5e_hlo.py, PR 41, not here: the 0/1 matrix was
    then `bf16[32768,8192]`, 537 MB, and its products 4 x the
    operations).

    Every grouped product — the three of a SwiGLU expert, their dx and
    dW, under the bound's 16,384 rows and over it at 32,768 — runs the
    chip's kernel in the tiles `grouped_tiles` states for the call's
    OWN shape: hidden 2304 = 4.5 x 512 in 3 x 768 and the expert width
    896 = 7 x 128 whole, none in the 128 x 128 blocks the compiler
    takes at such widths.  The rule PR 32 wrote for the second family's
    2688 / 1856 meets its second family here.

    The attention core is the kernel in BOTH kinds of layer, at a
    window of TWO tiles (1,024 in tiles of 512): 5 forward calls a
    layer (2 gradient passes x (forward + recomputed) + the loss's) and
    2 backward, 3 sliding layers and 1 full, and no array of S x S
    elements a head anywhere.  About 90 s."""
    task, compiled = folded_chunk("mellum")
    assert task.num_params == 595_154_176
    c = task.arch
    s, block = c.sequence_length, c.attention_block
    assert (s, block, c.sliding_window) == (4096, 512, 1024)
    memory = compiled.memory_analysis()
    leaves = 4 * task.num_params
    assert memory.alias_size_in_bytes >= leaves
    assert memory.temp_size_in_bytes + leaves < 15.0e9, \
        memory.temp_size_in_bytes
    assert memory.temp_size_in_bytes < 9.9e9, memory.temp_size_in_bytes
    text = compiled.as_text()
    slots = s * c.num_experts_per_tok
    bound = lm.live_rows_bound(slots, c)
    assert (bound, slots) == (16384, 32768)
    calls = aot.ragged_dot_calls(text)
    assert {shape for shape, _ in calls} == {
        (m, k, n) for m in (bound, slots)
        for k, n in ((2304, 896), (896, 2304))}
    assert all(tiles == lm.grouped_tiles(*shape) for shape, tiles in calls), \
        sorted(set(calls))
    assert {tiles for _, tiles in calls} == {"256,768,896", "256,896,768"}
    assert not any(tiles.endswith(",128,128") for _, tiles in calls)
    # the core's calls, by kernel and scope
    kernel_calls = re.findall(
        r"%(kps_attn_core_\w+?)[.\d]* = .* custom-call\(.*"
        r"custom_call_target=\"tpu_custom_call\".*op_name=\"([^\"]*)\"", text)
    scopes = ("kps.attn.window", "kps.attn.full")
    assert all(sum(scope in op_name for scope in scopes) == 1
               for _, op_name in kernel_calls), kernel_calls
    assert {(kernel, scope): sum(k == kernel and scope in op_name
                                 for k, op_name in kernel_calls)
            for kernel in ("kps_attn_core_forward", "kps_attn_core_backward")
            for scope in scopes} == {
        ("kps_attn_core_forward", "kps.attn.window"): 15,
        ("kps_attn_core_forward", "kps.attn.full"): 5,
        ("kps_attn_core_backward", "kps.attn.window"): 6,
        ("kps_attn_core_backward", "kps.attn.full"): 2}
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"= \w+\[([\d,]+)\]", text)}
    # (but q as its norm's kernel reads it, `[1, S, 32 x 128]`)
    q_flat = (1, s, c.num_attention_heads * c.head_dim)
    assert not [sh for sh in shapes - {q_flat}
                if len(sh) >= 3 and sh.count(s) >= 2]
    assert not [sh for sh in shapes if s * s in sh]
    for scope in ("kps.attn.qkv", "kps.attn.norm_rope", "kps.attn.out",
                  "kps.moe.route", "kps.moe.sort", "kps.moe.place",
                  "kps.moe.expert_fn", "kps.moe.combine", "kps.lm.norm",
                  "kps.lm.embed", "kps.lm.head"):
        assert scope in text, scope
    for absent in ("kps.moe.shared", "kps.mlp", "kps.lm.layers"):
        assert absent not in text, absent


@pytest.mark.parametrize("family,scratch,placing,adding_back", [
    ("mellum", 8_976_765_440, 40, 24), ("afmoe", 7.5e9, 48, 40)])
def test_a_large_placement_is_the_kernels_and_no_matrix(
        folded_chunk, family, scratch, placing, adding_back):
    """The fifth and the third family's chunks (the fixture's compiles,
    no second ones): at 16,384 and at 4,096 rows under the bound x
    4,096 tokens `placement_kernel.takes` the expert layer's two
    products with the 0/1 matrix, so lowered for the chip they are
    Mosaic calls and NO 0/1 array of rows x tokens elements is in the
    program any more — the parent held `bf16[16384,4096]` (134 MB a
    layer a pass, four kept for the backward passes) and
    `bf16[32768,4096]` in the branch over the bound, the third family
    `bf16[4096,4096]`.  The calls, by kernel and scope, over both
    branches of the bound: in the fifth family's each branch what the
    parent's chunk held as products (counted from its text, PR 42), 20
    placing (4 expert layers x (2 gradient passes x (forward +
    recomputed) + the loss)) and 12 add-backs (the recomputed
    forward's is dead code); in the third's, whose layer norms the
    experts' sum and so needs it again backward, the recomputed
    add-backs stay, 48 and 40 in all; and under `jax.grad` 8 a branch
    of each as the other's transpose, which keep the forward product's
    scope — so `kps.moe.place` and
    `kps.moe.combine` go on holding what `moe_placement_self_share`
    and `moe_placement_roofline_share` read.  The scratch fell by the
    kept matrices in the fifth family, 8,976,765,440 -> 8,767,503,360
    bytes when written (the limit: the parent's reading); the third's
    stands at 6.75 GB for 6.72 (its kept `bf16[4096,4096]` were 34 MB
    each, and the weighted rows the product fused are now written: the
    limit is ISSUE 40's 7.5 GB)."""
    task, compiled = folded_chunk(family)
    c = task.arch
    s = c.sequence_length
    slots = s * c.num_experts_per_tok
    bound = lm.live_rows_bound(slots, c)
    assert placement_kernel.takes(bound, s, c.hidden_size)
    assert placement_kernel.takes(slots, s, c.hidden_size)
    text = compiled.as_text()
    # the matrix was bfloat16 and its mask and one-hot compare `pred`,
    # made under the expert layer's scopes (the third family's
    # attention gate is `[4096 tokens, 32 x 128]` too)
    made = re.compile(r"= (?:bf16|pred)\[(?:%s)\]" % "|".join(
        f"{a},{b}" for rows in (bound, slots)
        for a, b in ((rows, s), (s, rows))))
    assert not [line for line in text.splitlines()
                if "kps.moe" in line and made.search(line)]
    calls = re.findall(
        r"%(kps_moe_\w+?)[.\d]* = .* custom-call\(.*"
        r"custom_call_target=\"tpu_custom_call\".*op_name=\"([^\"]*)\"", text)
    scopes = ("kps.moe.place", "kps.moe.combine")
    assert all(sum(scope in op_name for scope in scopes) == 1
               for _, op_name in calls), calls
    assert {(kernel, scope): sum(k == kernel and scope in op_name
                                 for k, op_name in calls)
            for kernel in ("kps_moe_place", "kps_moe_add_back")
            for scope in scopes} == {
        ("kps_moe_place", "kps.moe.place"): placing,
        ("kps_moe_add_back", "kps.moe.combine"): adding_back,
        ("kps_moe_add_back", "kps.moe.place"): 2 * 8,
        ("kps_moe_place", "kps.moe.combine"): 2 * 8}
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < scratch, memory.temp_size_in_bytes


@pytest.mark.parametrize("family,rotating,plain", [
    ("afmoe", 4, 1), ("mellum", 4, 0)])
def test_a_heads_norm_and_rope_are_one_kernel_pass_that_rolls_the_lanes(
        folded_chunk, family, rotating, plain):
    """The third and the fifth family's chunks (the fixture's compiles,
    no second ones): q `[4096, 32 x 128]` and k `[4096, 4 x 128]` go
    through their head norm and RoPE as `lm_common.head_norm_rope`'s
    kernel (models/norm_rope_kernel.py, PR 43), Mosaic calls whose
    `op_name` lies under `kps.attn.norm_rope`, which is what the
    benchmark's readers find their device time by — q's and k's a layer
    a pass: 2 gradient passes x (forward + recomputed) + the loss's
    forward = 5 forward passes and 2 backward, with the angles' two
    tables where the layer rotates (4 operands; the third family's
    full layer norms alone, 2).

    What the plain lines cost is gone with them.  A half of a 128-lane
    vector (`x[..., :64]`, `concatenate([-x2, x1])`) made the compiler
    hold q and k tokens-minor, `f32[1,4096,32,128]{1,3,2,0}`, and copy
    at every boundary that wants the channels in lanes: 40 (20 in the
    fifth family's chunk) top-level copies `f32[1,4096,32,64]{3,2,1,0}`
    an update named `…/kps.attn.norm_rope/slice` and as many `/neg`, 40
    under `/concatenate` — 15.5 of the scope's 39.0 Mcyc (XLA's own
    estimate) in the third family's chunk, 340.3 Mcyc an update in all
    and 294.2 now; the fifth's 326.9 -> 286.4.  And q's result leaves
    the kernel eight heads a tile, as the attention kernel reads it:
    no copy `f32[…,32,128]` stands between the two, where 25 stood
    between the projection and the plain norm (`f32[4096,32,128]
    {2,1,0}` under `kps.attn.qkv`).  Counts from the text, never a
    time."""
    task, compiled = folded_chunk(family)
    c = task.arch
    assert (c.sequence_length, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim) == (4096, 32, 4, 128)
    assert (c.layers("sliding_attention"), c.layers("full_attention")) \
        == (rotating - (family == "mellum"), 1)
    text = compiled.as_text()
    calls = re.findall(
        r"%(kps_norm_rope_\w+?)[.\d]* = (?:\()?f32\[([\d,]+)\].* custom-call\("
        r"(.*?)\), custom_call_target=\"tpu_custom_call\".*"
        r"op_name=\"([^\"]*)\"", text)
    assert all("kps.attn.norm_rope" in op_name for *_, op_name in calls)
    counted = {}
    for kernel, made, operands, _ in calls:
        key = (kernel, made, len(operands.split(", ")))
        counted[key] = counted.get(key, 0) + 1
    forward, backward = "kps_norm_rope_forward", "kps_norm_rope_backward"
    q, k, q_flat = "1,131072,128", "1,4096,512", "1,4096,4096"
    want = {(forward, q, 4): 5 * rotating, (forward, k, 4): 5 * rotating,
            (backward, q_flat, 5): 2 * rotating,
            (backward, k, 5): 2 * rotating,
            (forward, q, 2): 5 * plain, (forward, k, 2): 5 * plain,
            (backward, q_flat, 3): 2 * plain, (backward, k, 3): 2 * plain}
    assert counted == {key: n for key, n in want.items() if n}
    # nothing is laid tokens-minor, and no half of a head is copied
    assert not re.search(r"f32\[1,4096,32,128\]\{1,3,2,0", text)
    assert not re.search(r"f32\[1,4096,32,64\]", text)
    copies = [line for line in text.splitlines()
              if re.match(r"\s*(?:ROOT )?%copy[.\d]* = ", line)]
    assert copies                       # the reader sees the program's
    assert not [line for line in copies if re.search(
        r"kps\.attn\.norm_rope/(slice|neg|concatenate)\"", line)]
    assert not [line for line in copies if re.search(
        r"= f32\[(1,)?4096,32,128\]", line)]


@pytest.mark.parametrize("family,lines", [("ouro", OURO_NORM_ROPE_LINES),
                                          ("glm4_moe_lite",
                                           GLM_NORM_ROPE_LINES)])
def test_the_other_families_norm_and_rope_are_the_plain_lines(
        folded_chunk, family, lines):
    """The fourth family rotates without a head norm (and stands at 82%
    of its bytes), the first over 64 of a head's channels: both keep
    `lm_common.rope`, no kernel of PR 43 is in their chunks, and the
    instructions under `kps.attn.norm_rope` count what they counted in
    the parent's chunks (f35376d, compiled the same way)."""
    _, compiled = folded_chunk(family)
    text = compiled.as_text()
    assert "kps_norm_rope_" not in text
    assert sum("kps.attn.norm_rope" in line
               for line in text.splitlines()) == lines


@pytest.mark.parametrize("family", ["glm4_moe_lite", "nemotron_h"])
def test_a_smaller_placement_is_left_to_the_product(folded_chunk, family):
    """The chunks of the cells whose 0/1 matrix `placement_kernel.takes`
    not (1,024 x 1,024 and 768 x 1,024 under the bound): no kernel of
    the placement is in them, the matrix is, and the program is the
    parent's (the traced programs' digests:
    tests/fixtures/*_stablehlo.json)."""
    task, compiled = folded_chunk(family)
    c = task.arch
    s = c.sequence_length
    bound = lm.live_rows_bound(s * c.num_experts_per_tok, c)
    assert not placement_kernel.takes(bound, s, c.hidden_size)
    text = compiled.as_text()
    assert "kps_moe_" not in text and f"bf16[{bound},{s}]" in text


@pytest.mark.parametrize("family", sorted(CHUNKS))
def test_what_no_scope_names_is_under_a_tenth_of_a_chunks_bytes(
        folded_chunk, family):
    """Of the result bytes of the instructions the chip runs in a
    language-model cell's chunk, the share that lies under no scope of
    the model or of the parameter plane once the compiler's own
    operations are adopted by the scope they serve
    (benchmark/self_time.py, the table `--trace 1` prints): 1.4% / 2.1%
    / 2.2% when written, 33% in the first family before the scan over
    its stacked layers had a name.  In the fourth family's chunk, whose
    layers are a loop over the steps, what lies under `kps.lm.layers`
    ALONE is the loop's own and is NOT small: 24.5% of the result bytes
    (a loop's body counted once), nearly all ADOPTED — what the
    compiler does to a loop's invariants before it enters one: each
    leaf's bfloat16 rounding (`convert` `bf16[2048,5632]`), relayouts
    and copies of the weights, their prefetch in slices — where ISSUE
    39 hoped for under a tenth; held under three tenths here, so that
    a second such set shows.  A count from the program's text,
    not a time; a scope lost from the program, or a compiler that names
    its instructions otherwise, shows here before a chip run."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import self_time
    _, compiled = folded_chunk(family)
    module = self_time.parse_hlo(compiled.as_text())
    spec = self_time.table_spec()
    run = self_time.run_on_the_device(module)
    assert len(run) > 3000 and module["entry"] in module["computations"]
    scopes = self_time.adopted_scopes(module, spec)
    named = {scope for scope, _ in scopes.values() if scope}
    if family == "ouro":
        assert NAMED | {"kps.lm.layers", "kps.attn.full", "kps.mlp"} <= named
        assert not NAMED_BY_EXPERTS & named
        total = alone = 0
        for name in run:
            inst = module["instructions"][name]
            if inst["opcode"] in self_time.CONTAINERS \
                    or inst["opcode"].endswith("-start"):
                continue
            total += inst["bytes"]
            alone += inst["bytes"] * (scopes[name][0] == "kps.lm.layers")
        assert 0.10 < alone / total < 0.30, alone / total
    else:
        assert NAMED | NAMED_BY_EXPERTS <= named
    share = self_time.unnamed_byte_share(module, spec)
    assert 0.0 < share < 0.10, share

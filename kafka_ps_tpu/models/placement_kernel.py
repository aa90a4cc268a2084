"""The expert layer's two products with its 0/1 placement matrix as TPU
kernels (Pallas): what `lm_common.routed_experts` runs on the chip at
the shapes `takes` names, each the other's transpose behind one
`jax.custom_vjp`.

The matrix `P[r, t] = 1` iff sorted row `r` is live and holds token `t`
is `[rows, tokens]`, one one a live row, and the rows lie expert by
expert, in ascending token order inside a group.  The mathematics is
the product's — *placing* `P · x` (`[tokens, H]` → `[rows, H]`) and
*adding back* `Pᵀ · x` (`[rows, H]` → `[tokens, H]`), every one still
multiplied on the MXU — and what changes is that `P` is never written
to memory and that only its pieces that hold a one are multiplied: a
piece `[tile, block]` is built in VMEM from the tile's token ids (an
iota compare), and `plan` says which pieces hold a one at all.  The row
tiles past the last live row go whole; a live tile goes to the token
blocks its rows' ids fall in and to no other.

    grid (chunks of H, tiles of rows); the blocks of a tile: a loop
    inside the step, over the plan's flags

The side whose rows are the tokens (`x` when placing, the result when
adding back) stays in VMEM for a whole chunk of channels, so it is
read or written ONCE; the other side goes by a tile at a time, and a
dead tile of it is neither fetched (the index map repeats the last
live tile) nor multiplied.  A dead tile of the placed rows is written
as zeros; a dead ROW inside a live tile is masked before it is
multiplied, so whatever bits it holds (NaN: the grouped kernels leave
those rows as they found them) reach nothing.

Precision, pass for pass what the compiler makes of the plain products
(PERF.md section 5, PR 42: the parent's chunk compiled for a described
v5e): the operand comes in float32 and is rounded to bfloat16 in the
kernel, sums are float32.  `passes=1` is the default precision's one
pass (placing and its transpose): on the chip both equal the product
to the bit.  `passes=2` is `HIGH` on a 0/1 operand — the float32 side
as a high and a low bfloat16 piece, the 0/1 side has no low piece — for
adding back and its transpose; the pieces here are round-to-nearest,
the high one and then the rest, and the compiler cuts its own another
way: on the chip the two stand 6e-5 to 9e-5 apart on values up to 5,
the kernel 3.7e-5 from the sum in float64 and the product 7e-5 to 8e-5
(one bfloat16 pass stands 2e-2 from it).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# sorted rows a tile, tokens a block: the share of the matrix that is
# visited follows the TILE (a tile of a small group reaches every block
# of tokens), the MXU's filling the block (PERF.md section 6, PR 42)
ROW_TILE = 128
TOKEN_BLOCK = 512
# the resident side of a chunk of channels, float32: tokens x channels
# (4,096 tokens x 2,304 channels whole: fewer chunks read faster)
RESIDENT_BYTES = 40 * 1024 * 1024
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
# the pair counters' unit (models/mellum.py `moe.place_pairs`)
PAIRS_UNIT = 1024
# the smallest 0/1 matrix, rows x tokens, the kernels are given
MIN_MATRIX = 16 * 1024 * 1024


def chunk_of(tokens: int, hidden: int) -> int | None:
    """Channels a chunk: the most whole lanes that divide `hidden`
    with the resident side inside RESIDENT_BYTES."""
    for chunks in range(1, hidden // LANES + 1):
        width = hidden // chunks
        if (hidden % chunks == 0 and width % LANES == 0
                and 4 * tokens * width <= RESIDENT_BYTES):
            return width
    return None


def takes(rows: int, tokens: int, hidden: int) -> bool:
    """Whether the kernels take the products with a `[rows, tokens]`
    0/1 matrix over `hidden` channels: whole tiles, blocks and lanes, a
    chunk that fits VMEM, and a matrix of MIN_MATRIX elements or more.

    What set MIN_MATRIX (a TPU v5e, PERF.md section 6, PR 42; ms a call,
    product -> kernel, placing / its transpose / the add-back / its
    transpose): at 16,384 x 4,096 x 2,304 (the Mellum2 cell) 1.66 /
    2.01 / 3.34 / 3.27 -> 0.55 / 0.42 / 0.73 / 0.84 with 18% of the
    matrix visited, an update's 48 calls 115 -> 30 ms; at 4,096 x 4,096
    x 2,048 (the Trinity cell) 0.40 / 0.41 / 0.76 / 0.75 -> 0.29 / 0.26
    / 0.39 / 0.42 at 39% visited and 0.33 / 0.30 / 0.48 / 0.52 at 50%
    (even routing): 26 -> 16-19 ms an update.  End to end, on shared
    seeds: the Mellum2 cell 2.3037 / 2.2773 -> 2.9314 / 2.8772 updates
    a second, the Trinity cell 2.6608 / 2.6389 -> 2.7754 / 2.7756.  At
    1,024 x 1,024 and 768 x 1,024 (the GLM and Nemotron cells) the
    products are 2-4 ms of an update in all and were not tried: the
    product, and their programs the parent's."""
    return (rows % ROW_TILE == 0 and tokens % TOKEN_BLOCK == 0
            and chunk_of(tokens, hidden) is not None
            and rows * tokens >= MIN_MATRIX)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["tok", "visit", "n_here"],
                   meta_fields=["tokens"])
@dataclasses.dataclass(frozen=True)
class Plan:
    """What both kernels are told of the matrix: `tok` `[rows]` the
    token a live row holds and -1 on a dead one, `visit` `[tiles,
    blocks]` 1 where the piece holds a one, `n_here` the live rows,
    `tokens` the matrix's columns."""
    tok: jax.Array
    visit: jax.Array
    n_here: jax.Array
    tokens: int

    @property
    def tile(self) -> int:
        return self.tok.shape[0] // self.visit.shape[0]

    @property
    def block(self) -> int:
        return self.tokens // self.visit.shape[1]

    @property
    def pairs(self):
        """The elements of the pieces visited, in PAIRS_UNIT pairs."""
        return self.visit.sum() * (self.tile * self.block) // PAIRS_UNIT


def plan(tok, n_here, tokens: int, tile: int = ROW_TILE,
         block: int = TOKEN_BLOCK) -> Plan:
    """The plan of the matrix whose row `r` holds token `tok[r]` where
    `r < n_here` and nothing past it: plain `jax.numpy`, a compare of
    `rows x blocks` elements."""
    rows = tok.shape[0]
    tok = jnp.where(jnp.arange(rows) < n_here, tok, -1).astype(jnp.int32)
    at = (tok // block).reshape(rows // tile, tile, 1)      # -1: no block
    visit = (at == jnp.arange(tokens // block)).any(axis=1)
    return Plan(tok, visit.astype(jnp.int32),
                jnp.asarray(n_here, jnp.int32), tokens)


def _piece(tok, first: int, shape, along: int):
    """The 0/1 piece of `shape` whose tokens start at `first` and run
    along dimension `along`; `tok` broadcasts along the other."""
    at = first + jax.lax.broadcasted_iota(jnp.int32, shape, along)
    return (tok == at).astype(jnp.float32).astype(jnp.bfloat16)


def _pieces_of(x):
    """A float32 operand's high and low bfloat16 piece."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


# -- placing: [tokens, H] -> [rows, H] ----------------------------------------

def _place_kernel(visit_ref, n_ref, tok_ref, x_ref, o_ref, *pieces, block):
    tile = pl.program_id(1)
    rows, blocks = o_ref.shape[0], x_ref.shape[0] // block

    @pl.when(tile == 0)
    def _():
        # the chunk's tokens, rounded once for every tile that reads them
        for b in range(blocks):
            at = pl.ds(b * block, block)
            for ref, piece in zip(pieces, _pieces_of(x_ref[at, :])):
                ref[at, :] = piece

    o_ref[...] = jnp.zeros_like(o_ref)
    for b in range(blocks):
        @pl.when(visit_ref[tile * blocks + b] > 0)
        def _():
            at = pl.ds(b * block, block)
            p = _piece(tok_ref[...], b * block, (rows, block), 1)
            for ref in pieces:
                o_ref[...] += jnp.dot(p, ref[at, :],
                                      preferred_element_type=jnp.float32)


# -- adding back: [rows, H] -> [tokens, H] ------------------------------------

def _add_back_kernel(visit_ref, n_ref, tok_ref, x_ref, o_ref, *pieces, block):
    tile = pl.program_id(1)
    rows, blocks = x_ref.shape[0], o_ref.shape[0] // block
    live = n_ref[0] - tile * rows

    @pl.when(tile == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live > 0)
    def _():
        # a dead row's bits reach nothing: 0 x NaN is NaN on the MXU
        x = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < live,
            x_ref[...], 0.0)
        for ref, piece in zip(pieces, _pieces_of(x)):
            ref[...] = piece

        for b in range(blocks):
            @pl.when(visit_ref[tile * blocks + b] > 0)
            def _():
                at = pl.ds(b * block, block)
                p = _piece(tok_ref[...], b * block, (block, rows), 0)
                for ref in pieces:
                    o_ref[at, :] += jnp.dot(
                        p, ref[...], preferred_element_type=jnp.float32)


@functools.lru_cache(maxsize=None)
def _call(rows, tokens, hidden, tile, block, chunk, back, passes, interpret):
    """The placing (`back` False) or the adding-back kernel's call."""
    def by_tile(j, i, visit, n):
        return i, j

    def last_live(j, i, visit, n):
        return jnp.minimum(i, jnp.maximum(n[0] - 1, 0) // tile), j

    def whole(**how):
        return pl.BlockSpec((tokens, chunk), lambda j, i, visit, n: (0, j),
                            **how)

    if back:
        kernel, name, out_rows, out = (_add_back_kernel, "kps_moe_add_back",
                                       tokens, whole())
        tok = pl.BlockSpec((1, tile), lambda j, i, visit, n: (0, i))
        x, kept = pl.BlockSpec((tile, chunk), last_live), (tile, chunk)
    else:
        kernel, name, out_rows, out = (_place_kernel, "kps_moe_place", rows,
                                       pl.BlockSpec((tile, chunk), by_tile))
        tok = pl.BlockSpec((tile, 1), lambda j, i, visit, n: (i, 0))
        # read once a chunk: one buffer, none in flight beside it
        x, kept = whole(pipeline_mode=pl.Buffered(1)), (tokens, chunk)
    return pl.pallas_call(
        functools.partial(kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(hidden // chunk, rows // tile),
            in_specs=[tok, x], out_specs=out,
            scratch_shapes=[pltpu.VMEM(kept, jnp.bfloat16)] * passes),
        out_shape=jax.ShapeDtypeStruct((out_rows, hidden), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        # a quarter of the matrix visited: what the sorted rows leave
        cost_estimate=pl.CostEstimate(
            flops=passes * rows * tokens * hidden // 2, transcendentals=0,
            bytes_accessed=4 * (rows + tokens) * hidden),
        interpret=interpret, name=name)


def _product(x, plan_: Plan, back, passes, chunk, interpret):
    rows, tokens, hidden = plan_.tok.shape[0], plan_.tokens, x.shape[1]
    tok = plan_.tok.reshape((1, rows) if back else (rows, 1))
    return _call(rows, tokens, hidden, plan_.tile, plan_.block,
                 chunk or chunk_of(tokens, hidden), back, passes, interpret)(
        plan_.visit.reshape(-1), plan_.n_here.reshape(1), tok, x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def multiply(x, plan_: Plan, back: bool, passes: int, chunk=None,
             interpret: bool = False):
    """`P · x` (`back` False: `[tokens, H]` → `[rows, H]`) or `Pᵀ · x`
    (`back` True: `[rows, H]` → `[tokens, H]`) for the matrix `plan_`
    describes, float32 in and out, in `passes` bfloat16 passes; the
    cotangent is the other product at the same passes.  `chunk`: the
    channels a chunk (None: `chunk_of`); `interpret` runs the kernel in
    Pallas's interpreter (the CPU tests)."""
    return _product(x, plan_, back, passes, chunk, interpret)


def _multiply_fwd(x, plan_, back, passes, chunk, interpret):
    return _product(x, plan_, back, passes, chunk, interpret), plan_


def _multiply_bwd(back, passes, chunk, interpret, plan_, d_out):
    return _product(d_out, plan_, not back, passes, chunk, interpret), None


multiply.defvjp(_multiply_fwd, _multiply_bwd)

"""The chunked state-space scan as a TPU kernel (Pallas): what
`nemotron_h.ssd_chunked` runs on the chip at the shapes `takes` names,
forward and backward behind one `jax.custom_vjp`.

The mathematics is `nemotron_h.chunks_scanned`'s: with `cum` the running
sum of Δ·A inside a chunk of Q tokens, `xd = Δ·x` (formed here, a slab
of channels at a time: written as `jax.numpy` it is Δ broadcast to x's
shape, a copy and a product, three passes over arrays as large as x)
and `H` the state that enters the chunk, a head h of group g gives, a
chunk,

    y_l   = Σ_{s <= l} (C_l · B_s) exp(cum_l − cum_s) xd_s
            + exp(cum_l) H C_l  + D x_l
    H_end = exp(cum_end) H + Σ_s exp(cum_end − cum_s) xd_s ⊗ B_s

and what changes is where things live.  Written as `jax.numpy` the
decay `exp(cum_l − cum_s)` of every head is an array of chunks x Q x Q
x heads elements (134 MB a layer a pass at 2,048 tokens, chunks of 256
and 64 heads), made with the heads minor, multiplied by the broadcast
scores, re-laid with the heads batch-major for the product, and met
again in a gradient pass as its cotangent's; the chunks' own states,
the states handed on and the entering states' term are each a pass or
two more over arrays as large as x.  Here a head's decay and weighted
scores are formed in VMEM from a row and a column of `cum`, used, and
dropped — nothing of Q x Q elements reaches HBM, forward or backward —
and the state stays in VMEM while the grid walks a row's chunks: x is
read once and y written once.

    grid (B, blocks of heads, chunks)       the chunks in order, last

A block is at most `HEADS_A_BLOCK` heads of one group; a step forms its
group's `C Bᵀ` once for them.  The backward kernel walks the chunks
from the last to the first with the cotangent of the state in VMEM,
forms a head's decay and weighted scores once more, and makes dx, dΔ
(Δ·x's share: a head's sum of `d xd ⊙ x` over its channels), dB, dC and
d cum — the row sums less the column sums of `d_decay ⊙ decay`, and the
shares of the three exponentials `exp(cum_l)`, `exp(cum_end − cum_s)`
and `exp(cum_end)`.  Between the passes the custom_vjp keeps its inputs
and the state that entered each chunk (`[B, chunks, heads x P, N]`,
what the forward pass had in VMEM), float32; dB and dC leave a block of
heads each and the blocks of a group are summed outside.

Everything lies as the mixer's convolution wrote it, x, B and C side
by side in one array `[B, S, heads x P + 2 x groups x N]` (dx goes back
into such an array's lanes): a head's channels along the lanes, a block
of heads a block of lanes, a group's B or C a block of N lanes — no
slice, which would be a copy of x a pass; y and its cotangent `[B, S,
heads x P]`.  A head of 64 channels is half a lane vector: two
heads' channels are read as one slab of 128 lanes, each head's product
is taken with the whole slab (the chip's MXU is 128 wide: the other
half costs no pass) and its own half kept.  `cum` and Δ come as `[B,
chunks, heads, Q]`, a head's positions along the lanes: its row of a
chunk is a sublane broadcast, and its column is that broadcast
transposed — two heads' rows stacked give a slab's columns side by side
in one transpose.

Precision: inputs and outputs float32.  Each product's operands are
rounded to bfloat16 where the compiler rounds them in the plain path (a
float32 product at the chip's default precision is one bfloat16 pass)
and accumulated in float32; `cum`, its differences, the mask, `exp`,
the weighting, the state's update and the sums over a row or a column
are float32.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
HALF = LANES // 2
SUBLANES = 8
# heads a step of the grid, at most: whole sublanes of `cum`'s rows
HEADS_A_BLOCK = 32
# what a step may hold in VMEM, `_step_bytes`' count of the backward
# kernel's blocks (each twice: the pipeline's), the state and the few
# `[Q, Q]` arrays alive at once inside a head
VMEM_BUDGET_BYTES = 48 * 1024 * 1024
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
# slabs of channels (two heads of 64, one of more) a turn of a block's
# loop: straight-line code the scheduler may interleave
SLABS_A_TURN = 4

_NT = (((1,), (1,)), ((), ()))      # [m, d] x [n, d] -> [m, n]
_TN = (((0,), (0,)), ((), ()))      # [k, m] x [k, n] -> [m, n]


def _step_bytes(chunk: int, heads: int, p: int, state: int) -> int:
    """What VMEM_BUDGET_BYTES is held against."""
    return 4 * (6 * chunk * heads * p + 3 * heads * p * state
                + 8 * chunk * state + 8 * chunk * chunk)


def heads_a_block(heads_a_group: int, p: int, state: int, chunk: int) -> int:
    """The most heads of one group a step takes: whole sublanes, at
    most HEADS_A_BLOCK, a divisor of the group's, inside the VMEM
    budget; 0 where none is."""
    for heads in range(min(heads_a_group, HEADS_A_BLOCK), 0, -1):
        if (heads % SUBLANES == 0 and heads_a_group % heads == 0
                and _step_bytes(chunk, heads, p, state)
                <= VMEM_BUDGET_BYTES):
            return heads
    return 0


def takes(x_shape, groups: int, state: int, chunk: int) -> bool:
    """Whether the kernel takes `x` `[B, S, heads, P]` under `groups`
    groups of B and C of `state` channels in chunks of `chunk`: whole
    lanes of positions and of state, heads of 64 channels (two to a
    lane vector) or of whole lanes, a group's heads in whole sublanes
    (an odd number of heads never is), and a step inside its VMEM."""
    _, s, heads, p = x_shape
    if heads % groups or s % chunk:
        return False
    return (chunk % LANES == 0 and state % LANES == 0
            and (p == HALF or p % LANES == 0) and heads * p % state == 0
            and heads_a_block(heads // groups, p, state, chunk) > 0)


# -- what the two kernels share ------------------------------------------------

def _bf16(x):
    return x.astype(jnp.bfloat16)


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _tiles(q: int):
    """A chunk's targets a lane vector's worth at a time: (the tile's
    rows, the sources it sees at all — up to its own last)."""
    return [(slice(i * LANES, (i + 1) * LANES), (i + 1) * LANES)
            for i in range(q // LANES)]


def _wide(x, width: int):
    """`[.., LANES]`, every lane of a row alike, in `width` lanes."""
    return jnp.tile(x, (1, width // LANES))


def _column(row):
    """A head's `cum` over a chunk `[1, Q]` -> `[Q, LANES]`, position
    l's in every lane of row l."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, row.shape[1])))


def _decay(row, col, rows: slice, span: int):
    """A tile of targets' `exp(cum_l − cum_s)` over its `span` sources
    `[LANES, span]`: under the lower triangle, 0 above."""
    lower = (jax.lax.broadcasted_iota(jnp.int32, (LANES, span), 0)
             + rows.start
             >= jax.lax.broadcasted_iota(jnp.int32, (LANES, span), 1))
    seg = _wide(col[rows], span) - row[:, :span]
    return jnp.exp(jnp.where(lower, seg, -jnp.inf))


def _onto(total, part, axis: int):
    """The sum of two arrays that start together along `axis`, the
    shorter added onto the longer's first entries (`total` None: 0)."""
    if total is None:
        return part
    if part.shape[axis] > total.shape[axis]:
        total, part = part, total
    if part.shape[axis] == total.shape[axis]:
        return total + part
    head, rest = jnp.split(total, [part.shape[axis]], axis=axis)
    return jnp.concatenate([head + part, rest], axis=axis)


def _through(parts, state: int):
    """Each head's `exp(cum_end)` `[1, LANES]` over the rows of its own
    channels in a slab's state `[width, N]`."""
    if len(parts) == 1:
        return _wide(parts[0], state)
    row = jax.lax.broadcasted_iota(jnp.int32, (LANES, 1), 0)
    return _wide(jnp.where(row < HALF, parts[0], parts[1]), state)


def _own(x, k: int, per: int):
    """Of a slab of `per` heads' lanes, head k's own; zeros in the
    other's."""
    if per == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return jnp.where((lane < HALF) == (k == 0), x, 0.0)


def _columns(rows, width: int):
    """Each head's `[1, Q]` (a value a position) -> `[Q, width]`, a
    position's value in every lane of its head's own channels: of two
    heads the first's rows over the second's, transposed."""
    q = rows[0].shape[1]
    if len(rows) == 1:
        return _wide(_column(rows[0]), width)
    first = jax.lax.broadcasted_iota(jnp.int32, (LANES, 1), 0) < HALF
    return jnp.transpose(jnp.where(
        first, *(jnp.broadcast_to(row, (LANES, q)) for row in rows)))


def _side_by_side(parts):
    """A slab of each head's own lanes: the one head's as it is, of two
    the first's low half and the second's high half."""
    if len(parts) == 1:
        return parts[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return jnp.where(lane < HALF, parts[0], parts[1])


def _over_slabs(slabs: int, visit):
    """`visit(j)` for every slab of a block, SLABS_A_TURN to a turn of
    the loop: inside a turn the compiler overlaps one slab's products
    with the next one's decays, between turns nothing."""
    turn = max(t for t in range(1, SLABS_A_TURN + 1) if slabs % t == 0)

    def body(i, _):
        for u in range(turn):
            visit(i * turn + u)

    jax.lax.fori_loop(0, slabs // turn, body, None)


def _slabs(p: int):
    """(lanes of a slab, heads in it)."""
    width = max(p, LANES)
    return width, width // p


# -- forward -------------------------------------------------------------------

def _forward_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, skip_ref, y_ref,
                    *rest, p):
    """`rest`: the entering states' block where the backward pass will
    want them, then the scratch (the group's scores, the state)."""
    *entering_ref, s_ref, state_ref = rest
    q, heads = x_ref.shape[0], cum_ref.shape[0]
    n = state_ref.shape[1]
    width, per = _slabs(p)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    for kept in entering_ref:
        kept[...] = state_ref[...]
    bb, cb = _bf16(b_ref[...]), _bf16(c_ref[...])
    s_ref[...] = _dot(cb, bb, _NT)

    def slab(j):
        at = pl.ds(pl.multiple_of(j * width, width), width)
        x = x_ref[:, at]
        xs = x * _columns(
            [dt_ref[pl.ds(j * per + k, 1), :] for k in range(per)], width)
        xb = _bf16(xs)
        ys, ends, grown, through = [], [], [], []
        for k in range(per):
            row = cum_ref[pl.ds(j * per + k, 1), :]
            col = _column(row)
            ys.append(jnp.concatenate([
                _dot(_bf16(s_ref[rows, :span] * _decay(row, col, rows, span)),
                     xb[:span]) for rows, span in _tiles(q)], axis=0))
            end = col[q - 1:q]
            ends.append(jnp.exp(_wide(end, q) - row))
            grown.append(jnp.exp(row))
            through.append(jnp.exp(end))
        state = state_ref[at, :]
        entered = _dot(cb, _bf16(state), _NT)
        y_ref[:, at] = (_side_by_side(ys) + entered * _columns(grown, width)
                        + x * skip_ref[:, at])
        own = _dot(_bf16(xs * _columns(ends, width)), bb, _TN)
        state_ref[at, :] = _through(through, n) * state + own

    _over_slabs(heads // per, slab)


_Specs = collections.namedtuple(
    "_Specs", "grid heads channels by_head b c own states skip dskip")


def _specs(shape, groups: int, state: int, chunk: int, backward: bool):
    """What the two calls share: the grid, the heads a block, and the
    spec of a block's channels `[Q, heads x P]` (x, y, their
    cotangents), of its rows by head `[heads, Q]` (Δ, `cum`), of its
    group's B and of its C `[Q, N]` (lanes of the packed `[x | B | C]`,
    as x's are), of a block's own dB or dC `[Q, N]`, of its states
    `[heads x P, N]`, of its channels' D `[1, heads x P]` and of a
    chunk's sums for dD; `backward` walks the chunks from the last."""
    b, s, h, p = shape
    chunks = s // chunk
    heads = heads_a_block(h // groups, p, state, chunk)
    blocks_a_group = h // groups // heads

    def at(c):
        return chunks - 1 - c if backward else c

    return _Specs(
        (b, h // heads, chunks), heads,
        pl.BlockSpec((None, chunk, heads * p),
                     lambda b_, i, c: (b_, at(c), i)),
        pl.BlockSpec((None, None, heads, chunk),
                     lambda b_, i, c: (b_, at(c), i, 0)),
        *(pl.BlockSpec((None, chunk, state),
                       lambda b_, i, c, first=first: (
                           b_, at(c), first + i // blocks_a_group))
          for first in (h * p // state, h * p // state + groups)),
        pl.BlockSpec((None, chunk, state), lambda b_, i, c: (b_, at(c), i)),
        pl.BlockSpec((None, None, heads * p, state),
                     lambda b_, i, c: (b_, at(c), i, 0)),
        pl.BlockSpec((1, heads * p), lambda b_, i, c: (0, i)),
        pl.BlockSpec((None, None, 1, heads * p),
                     lambda b_, i, c: (b_, at(c), 0, i)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


@functools.lru_cache(maxsize=None)
def _forward_call(shape, groups, state, chunk, keep, interpret):
    """`keep`: the entering states are a second result."""
    b, s, h, p = shape
    at = _specs(shape, groups, state, chunk, False)
    heads = at.heads
    pairs = b * s * chunk * h
    kept = b * s // chunk * h * p * state
    return pl.pallas_call(
        functools.partial(_forward_kernel, p=p), grid=at.grid,
        in_specs=[at.channels, at.by_head, at.by_head, at.b, at.c, at.skip],
        out_specs=[at.channels] + [at.states] * keep,
        out_shape=[jax.ShapeDtypeStruct((b, s, h * p), jnp.float32)] + [
            jax.ShapeDtypeStruct((b, s // chunk, h * p, state),
                                 jnp.float32)] * keep,
        scratch_shapes=[pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((heads * p, state), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * pairs * p + 2 * b * s * (
                chunk * h // heads + 2 * h * p) * state,
            transcendentals=pairs,
            bytes_accessed=4 * (b * s * (2 * h * p + 2 * h + 2 * h // heads
                                         * state) + keep * kept)),
        interpret=interpret, name="kps_ssd_forward")


# -- backward ------------------------------------------------------------------

def _backward_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, skip_ref, dy_ref,
                     entering_ref, dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref,
                     dskip_ref, s_ref, ds_ref, col_ref, dtcol_ref, g_ref, *,
                     p):
    """`g_ref`: the cotangent of the state at this chunk's end;
    `col_ref`, `dtcol_ref`: d cum's and dΔ's sums over a row of the
    chunk, head h's in lane h."""
    q, heads = x_ref.shape[0], cum_ref.shape[0]
    n = g_ref.shape[1]
    width, per = _slabs(p)

    @pl.when(pl.program_id(2) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    bb, cb = _bf16(b_ref[...]), _bf16(c_ref[...])
    s_ref[...] = _dot(cb, bb, _NT)
    for ref in (ds_ref, db_ref, dc_ref, col_ref, dtcol_ref):
        ref[...] = jnp.zeros_like(ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1

    def slab(j):
        at = pl.ds(pl.multiple_of(j * width, width), width)
        x, dys = x_ref[:, at], dy_ref[:, at]
        dt = _columns(
            [dt_ref[pl.ds(j * per + k, 1), :] for k in range(per)], width)
        xs = x * dt
        xb = _bf16(xs)
        state, g = entering_ref[at, :], g_ref[at, :]
        sb, gb = _bf16(state), _bf16(g)
        dxd, ends, grown, through, sums = None, [], [], [], []
        for k in range(per):
            h = j * per + k
            row = cum_ref[pl.ds(h, 1), :]
            col = _column(row)
            dy = _bf16(_own(dys, k, per))
            over_l, over_s = None, []
            for rows, span in _tiles(q):
                decay = _decay(row, col, rows, span)
                scores = s_ref[rows, :span]
                dxd = _onto(dxd, _dot(_bf16(scores * decay), dy[rows], _TN),
                            0)
                d_scores = _dot(dy[rows], xb[:span], _NT) * decay
                ds_ref[rows, :span] += d_scores
                d_seg = d_scores * scores
                over_l = _onto(over_l, d_seg.sum(axis=0, keepdims=True), 1)
                over_s.append(d_seg.sum(axis=1, keepdims=True))
            dcum_ref[pl.ds(h, 1), :] = -over_l
            sums.append(jnp.concatenate(over_s, axis=0))
            end = col[q - 1:q]
            ends.append(jnp.exp(_wide(end, q) - row))
            grown.append(jnp.exp(row))
            through.append(jnp.exp(end))
        # the chunk's own share of its end state, (to_end · xd)ᵀ B
        to_end = _columns(ends, width)
        reaching = xs * to_end
        d_reaching = _dot(bb, gb, _NT)
        db_ref[...] += _dot(_bf16(reaching), gb)
        dxd = dxd + to_end * d_reaching
        dx_ref[:, at] = dxd * dt + dys * skip_ref[:, at]
        dskip_ref[:, at] = (dys * x).sum(axis=0, keepdims=True)
        d_dt = dxd * x
        d_to_end = d_reaching * reaching
        # the entering state's term, exp(cum_l) · H C_l
        entered = _dot(cb, sb, _NT)
        d_entered = _columns(grown, width) * dys
        dc_ref[...] += _dot(_bf16(d_entered), sb)
        d_grown = d_entered * entered
        # and the state handed on, exp(cum_end) · H
        handed = _through(through, n)
        g_ref[at, :] = handed * g + _dot(_bf16(d_entered), cb, _TN)
        d_through = handed * g * state
        # exp(cum_l): +1 to every l; exp(cum_end − cum_s): −1 to every
        # s, and their sum to the end, where exp(cum_end)'s goes too
        d_positions = d_grown - d_to_end
        d_ends = d_to_end.sum(axis=0, keepdims=True)
        for k in range(per):
            at_end = (_own(d_ends, k, per).sum(axis=1, keepdims=True)
                      + d_through[k * p:(k + 1) * p].sum(
                          axis=1, keepdims=True).sum(axis=0, keepdims=True))
            column = (sums[k] + _own(d_positions, k, per).sum(
                axis=1, keepdims=True) + jnp.where(last, at_end, 0.0))
            col_ref[...] = jnp.where(lane == j * per + k, column,
                                     col_ref[...])
            dtcol_ref[...] = jnp.where(
                lane == j * per + k,
                _own(d_dt, k, per).sum(axis=1, keepdims=True), dtcol_ref[...])

    _over_slabs(heads // per, slab)
    # head h's column sums lie in row h; its row sums in lane h
    dcum_ref[...] += jnp.transpose(col_ref[...])[:heads]
    ddt_ref[...] = jnp.transpose(dtcol_ref[...])[:heads]
    ds = _bf16(ds_ref[...])
    dc_ref[...] += _dot(ds, bb)
    db_ref[...] += _dot(ds, cb, _TN)


@functools.lru_cache(maxsize=None)
def _backward_call(shape, groups, state, chunk, interpret):
    b, s, h, p = shape
    at = _specs(shape, groups, state, chunk, True)
    heads, blocks = at.heads, h // at.heads
    return pl.pallas_call(
        functools.partial(_backward_kernel, p=p), grid=at.grid,
        in_specs=[at.channels, at.by_head, at.by_head, at.b, at.c, at.skip,
                  at.channels, at.states],
        out_specs=[at.channels, at.by_head, at.by_head, at.own, at.own,
                   at.dskip],
        out_shape=[jax.ShapeDtypeStruct(
                       (b, s, h * p + 2 * groups * state), jnp.float32),
                   jax.ShapeDtypeStruct((b, s // chunk, h, chunk),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((b, s // chunk, h, chunk),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((b, s, blocks * state), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, blocks * state), jnp.float32),
                   jax.ShapeDtypeStruct((b, s // chunk, 1, h * p),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, LANES), jnp.float32),
                        pltpu.VMEM((chunk, LANES), jnp.float32),
                        pltpu.VMEM((heads * p, state), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="kps_ssd_backward")


# -- the scan ------------------------------------------------------------------

def _laid(dt, cum, skip, p: int, chunk: int):
    """Δ, `cum` and D as the kernels read them."""
    b, s, h = dt.shape

    def by_head(m):
        return jnp.swapaxes(m.reshape(b, s // chunk, chunk, h), 2, 3)
    return by_head(dt), by_head(cum), jnp.repeat(skip, p)[None]


def _shape(packed, dt, groups: int, state: int):
    """x's `[B, S, heads, P]` inside `packed` `[B, S, heads x P + 2 x
    groups x N]`."""
    b, s, h = dt.shape
    return b, s, h, (packed.shape[-1] - 2 * groups * state) // h


def _forward(packed, dt, cum, skip, groups, state, chunk, keep, interpret):
    shape = _shape(packed, dt, groups, state)
    by_dt, by_cum, skips = _laid(dt, cum, skip, shape[-1], chunk)
    y, *entering = _forward_call(shape, groups, state, chunk, keep,
                                 interpret)(packed, by_dt, by_cum, packed,
                                            packed, skips)
    return (y.reshape(shape), *entering)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def scan(packed, dt, cum, skip, groups: int, state: int, chunk: int,
         interpret: bool = False):
    """`packed` = `[x | B | C]` `[B, S, heads x P + 2 x groups x N]` as
    the mixer's convolution writes them side by side (x's lanes are read
    a block of heads at a time and B's and C's a group at a time where
    they lie: a slice would be a copy of x a pass), `dt` = Δ `[B, S,
    heads]`, `cum` `[B, S, heads]` the running sum of Δ·A inside each
    chunk of `chunk` tokens, `skip` = D `[heads]` -> the recurrence's y
    over Δ·x from a zero state, plus D·x, `[B, S, heads, P]`, float32
    in and out.  (`cum` is an argument of its own: its cotangent is
    what Δ and A get through the running sum, beside Δ's own through
    Δ·x.)  `interpret` runs the kernels in Pallas's interpreter (the
    CPU tests)."""
    return _forward(packed, dt, cum, skip, groups, state, chunk, False,
                    interpret)[0]


def _scan_fwd(packed, dt, cum, skip, groups, state, chunk, interpret):
    y, entering = _forward(packed, dt, cum, skip, groups, state, chunk, True,
                           interpret)
    return y, (packed, dt, cum, skip, entering)


def _scan_bwd(groups, state, chunk, interpret, kept, dy):
    packed, dt, cum, skip, entering = kept
    b, s, h, p = shape = _shape(packed, dt, groups, state)
    by_dt, by_cum, skips = _laid(dt, cum, skip, p, chunk)
    d_packed, ddt, dcum, dbm, dcm, dskip = _backward_call(
        shape, groups, state, chunk, interpret)(
            packed, by_dt, by_cum, packed, packed, skips,
            dy.reshape(b, s, h * p), entering)
    # dx lies in its lanes of `d_packed`; a block of heads' dB and dC
    # came apart: a group's blocks are summed and laid beside it
    dbm, dcm = (d.reshape(b, s, groups, -1, state).sum(axis=3).reshape(
        b, s, groups * state) for d in (dbm, dcm))
    d_packed = jax.lax.dynamic_update_slice_in_dim(
        d_packed, jnp.concatenate([dbm, dcm], axis=-1), h * p, axis=2)
    ddt, dcum = (jnp.swapaxes(d, 2, 3).reshape(b, s, h) for d in (ddt, dcum))
    return d_packed, ddt, dcum, dskip.reshape(-1, h, p).sum(axis=(0, 2))


scan.defvjp(_scan_fwd, _scan_bwd)

"""The blocked attention core as a TPU kernel (Pallas): what
`lm_common.blocked_attention` runs on the chip at the shapes `takes`
names, forward and backward behind one `jax.custom_vjp`.

The algorithm is `lm_common.blocked_attention`'s — a tile of `block`
queries against the blocks of keys its band reaches (`key_span`) and no
other — and what changes is where a block's scores live: they are
formed ONCE, in VMEM, and never reach HBM.  The forward kernel carries
a running maximum and row sum over a tile's key blocks and writes the
output and each row's log-sum-exp; the backward kernel forms a block's
scores once more from q, k and the log-sum-exp and makes dq, dk and dv
from them: five products a block (scores, dP, dV, dK, dQ).

    grid (B, G, tiles of queries, key blocks of the widest span)

A tile's key blocks run from its span's first to its own; a step past
the tile's own block repeats that block's index (nothing is fetched)
and computes nothing, so an out-of-band block is neither fetched nor
multiplied.  The R query heads of a key/value head are stacked on the
rows (position-major, as `[.., S, G, R, D]` lies in memory), so a block
of k and v is read once for all of them.  Only the tile's own block
and the block the band's edge cuts are masked, by the same two
inequalities; the blocks between them lie inside the mask whole.

A head of 64 channels is half a lane vector, and two of them ride one:
under an even G, `q` `[B, S, G, R, 64]` goes through the same two kernels
as `[B, S, G / 2, 2R, 128]` (`_paired`: each row's own 64 channels in
its half of the lanes, zeros in the other) beside k and v read as `[B,
S, G / 2, 128]`, and of the output and dq each row's own half is kept
(`_own_halves`); dk and dv come out as they lie.  The chip's MXU is 128
wide, so a product over 64 channels fills half of it however it is laid
out and the zeros cost no pass; what the custom_vjp keeps between the
passes stays 64 wide.

Precision: q, k, v and the cotangent come in float32 and out, dq, dk,
dv leave in float32.  Each product's operands are rounded to bfloat16
where the compiler rounds them in the plain path (a float32 product at
the chip's default precision is one bfloat16 pass) and accumulated in
float32; the mask, the maxima, `exp`, the sums, the log-sum-exp and the
rescaling are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
HALF = LANES // 2
# what a masked score reads: finite, so that a row whose first block
# lies wholly outside the band has a maximum to subtract
MASKED = -0.7 * float(np.finfo(np.float32).max)
# rows (positions x query heads) of a tile whose scores are formed at a
# time, so that a block's chain of elementwise passes stays short (the
# chip hardly cares: 256 to 4,096 rows read within 4% of each other at
# the third language-model cell's shapes, PERF.md section 5)
CHUNK_ROWS = 1024
# k's and v's gradients of one key/value head stay in VMEM while its
# tiles go by: the row's tokens x head_dim they may hold
RESIDENT_ELEMENTS = 2 * 1024 * 1024
VMEM_LIMIT_BYTES = 96 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))      # [m, d] x [n, d] -> [m, n]
_TN = (((0,), (0,)), ((), ()))      # [k, m] x [k, n] -> [m, n]


def takes(q_shape, block: int) -> bool:
    """Whether the kernel takes `q` `[B, S, G, R, D]` in tiles of
    `block`: whole lanes of channels — or half a lane vector, 64, under
    an even G, whose heads ride two to a vector (`_paired`) — and of
    keys, and a row whose k and v gradients fit VMEM."""
    _, s, g, _, d = q_shape
    if d == HALF and g % 2 == 0:
        d = LANES
    return (d % LANES == 0 and block % LANES == 0 and s % block == 0
            and s * d <= RESIDENT_ELEMENTS)


# -- heads of half a lane vector, two to a vector --------------------------------

def _paired(x):
    """`[B, S, G, R, 64]` -> `[B, S, G / 2, 2R, 128]`: the query heads
    of two neighbouring key/value heads stacked on the rows, the first
    head's in the low half of the lanes and zeros in the high half, the
    second's the other way round.  Beside them k and v `[B, S, G, 64]`
    are `[B, S, G / 2, 128]` as they lie in memory, and a row's product
    with a pair's keys is its product with its own head's: the other
    half adds exact zeros."""
    b, s, g, r, d = x.shape
    x = x.reshape(b, s, g // 2, 2, r, d)
    zeros = jnp.zeros_like(x[:, :, :, 0])
    return jnp.concatenate([
        jnp.concatenate([x[:, :, :, 0], zeros], axis=-1),
        jnp.concatenate([zeros, x[:, :, :, 1]], axis=-1)], axis=3)


def _own_halves(x):
    """`[B, S, G / 2, 2R, 128]` -> `[B, S, G, R, 64]`: of each row of
    `_paired`'s layout the half of the lanes that is its own head's."""
    b, s, g, r, d = x.shape
    r, d = r // 2, d // 2
    return jnp.stack([x[:, :, :, :r, :d], x[:, :, :, r:, d:]],
                     axis=3).reshape(b, s, 2 * g, r, d)


def _first_block(tile, block: int, window: int | None):
    """`key_span`'s first block of a tile, on a tile index that is a
    Python number or a traced one."""
    if window is None:
        return 0 * tile
    earliest = tile * block - (window - 1)
    return earliest * (earliest > 0) // block


def _steps(tiles: int, block: int, window: int | None) -> int:
    """Key blocks of the widest span."""
    if window is None:
        return tiles
    return min(tiles, -(-(window - 1) // block) + 1)


def _chunk_positions(block: int, heads: int) -> int:
    """Positions of a tile taken at a time: the most whose rows (times
    `heads`) are whole lanes and at most CHUNK_ROWS, else the tile."""
    for pos in range(block, 0, -1):
        if (block % pos == 0 and pos * heads % LANES == 0
                and pos * heads <= CHUNK_ROWS):
            return pos
    return block


def _position(rows, heads: int):
    """Row `pos * heads + head` -> pos."""
    if heads & (heads - 1) == 0:
        return rows >> (heads.bit_length() - 1)
    return jax.lax.div(rows, jnp.int32(heads))


def _mask_needed(tile, kb, block: int, window: int | None):
    """The tile's own block (the diagonal cuts it) and, under a window,
    a block whose earliest key the tile's last query no longer sees."""
    needed = kb == tile
    if window is not None:
        needed |= (tile - kb + 1) * block > window
    return needed


def _seen(at_q, at_k, window: int | None):
    seen = at_k <= at_q
    if window is not None:
        seen &= at_q - at_k < window
    return seen


def _by_mask(tile, kb, block, window, visit):
    """`visit(masked)` for a block the tile reaches, compiled with and
    without the mask; nothing for a step past the tile's own block."""
    needed = _mask_needed(tile, kb, block, window)
    reached = kb <= tile

    @pl.when(reached & needed)
    def _():
        visit(True)

    @pl.when(reached & jnp.logical_not(needed))
    def _():
        visit(False)


# -- forward -------------------------------------------------------------------

def _forward_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                    acc_ref, *, window, block, heads, steps):
    tile, step = pl.program_id(2), pl.program_id(3)
    kb = _first_block(tile, block, window) + step
    d = q_ref.shape[-1]
    pos = _chunk_positions(block, heads)
    rows = pos * heads

    @pl.when(step == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def visit(masked: bool):
        k = k_ref[...].astype(jnp.bfloat16)
        v = v_ref[...].astype(jnp.bfloat16)
        for c in range(block // pos):
            q = q_ref[pl.ds(c * pos, pos)].reshape(rows, d)
            s = jax.lax.dot_general(q.astype(jnp.bfloat16), k, _NT,
                                    preferred_element_type=jnp.float32)
            if masked:
                at_q = tile * block + c * pos + _position(
                    jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), heads)
                at_k = kb * block + jax.lax.broadcasted_iota(
                    jnp.int32, (1, block), 1)
                s = jnp.where(_seen(at_q, at_k, window), s, MASKED)
            at = pl.ds(c * rows, rows)
            m_prev, l_prev = m_ref[at], l_ref[at]
            m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - jnp.tile(m_next, (1, block // LANES)))
            alpha = jnp.exp(m_prev - m_next)
            m_ref[at] = m_next
            l_ref[at] = alpha * l_prev + p.sum(axis=1, keepdims=True)
            acc_ref[at] = (
                acc_ref[at] * jnp.tile(alpha, (1, d // LANES))
                + jnp.dot(p.astype(jnp.bfloat16), v,
                          preferred_element_type=jnp.float32))

    _by_mask(tile, kb, block, window, visit)

    @pl.when(step == steps - 1)
    def _():
        l = l_ref[...]
        out = acc_ref[...] / jnp.tile(l, (1, d // LANES))
        o_ref[...] = out.reshape(o_ref.shape)
        # a row's log-sum-exp leaves along the lanes, as the backward
        # kernel reads it
        lse_ref[...] = jnp.transpose(m_ref[...] + jnp.log(l))[:1]


def _grid(shape, window, block):
    """What the two calls share: (the grid, the spec of a tile of
    queries `[block, R, D]`, of a block of keys `[block, D]`, of a
    tile's rows along the lanes `[1, block * R]`)."""
    b, s, g, r, d = shape
    tiles = s // block

    def at_tile(b_, g_, i, j):
        return b_, i, g_, 0, 0

    def at_keys(b_, g_, i, j):
        return b_, jnp.minimum(_first_block(i, block, window) + j, i), g_

    def at_rows(b_, g_, i, j):
        return b_, g_, 0, i

    return ((b, g, tiles, _steps(tiles, block, window)),
            pl.BlockSpec((None, block, None, r, d), at_tile),
            pl.BlockSpec((None, block, d), at_keys),
            pl.BlockSpec((None, None, 1, block * r), at_rows))


@functools.lru_cache(maxsize=None)
def _forward_call(shape, window, block, interpret):
    b, s, g, r, d = shape
    grid, tile, keys, rows = _grid(shape, window, block)
    # pairs inside the blocks visited, over every query head
    visited = b * g * r * block * block * sum(
        t + 1 - _first_block(t, block, window) for t in range(s // block))
    return pl.pallas_call(
        functools.partial(_forward_kernel, window=window, block=block,
                          heads=r, steps=grid[-1]),
        grid=grid, in_specs=[tile, keys, keys], out_specs=[tile, rows],
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, g, 1, s * r), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block * r, LANES), jnp.float32),
                        pltpu.VMEM((block * r, LANES), jnp.float32),
                        pltpu.VMEM((block * r, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=4 * d * visited, transcendentals=visited,
            bytes_accessed=4 * (2 * b * s * g * r * d + 2 * b * s * g * d)),
        interpret=interpret, name="kps_attn_core_forward")


def _forward(q, k, v, window, block, interpret):
    """(out as `q`, the rows' log-sum-exp as the kernels lay it)."""
    halves = q.shape[-1] == HALF
    if halves:
        q = _paired(q)
    b, s = q.shape[:2]
    out, lse = _forward_call(q.shape, window, block, interpret)(
        q, k.reshape(b, s, -1), v.reshape(b, s, -1))
    return (_own_halves(out) if halves else out), lse


# -- backward ------------------------------------------------------------------

def _backward_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, *, window, block, heads):
    tile, step = pl.program_id(2), pl.program_id(3)
    kb = _first_block(tile, block, window) + step
    d = q_ref.shape[-1]
    pos = _chunk_positions(block, heads)
    rows = pos * heads

    @pl.when((tile == 0) & (step == 0))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(step == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def visit(masked: bool):
        # keys along the sublanes, the tile's rows along the lanes: the
        # rows' log-sum-exp and delta are lane vectors
        k = k_ref[...].astype(jnp.bfloat16)
        v = v_ref[...].astype(jnp.bfloat16)
        dk = jnp.zeros((block, d), jnp.float32)
        dv = jnp.zeros((block, d), jnp.float32)
        for c in range(block // pos):
            here = pl.ds(c * pos, pos)
            q = q_ref[here].reshape(rows, d).astype(jnp.bfloat16)
            do = do_ref[here].reshape(rows, d).astype(jnp.bfloat16)
            at = pl.ds(c * rows, rows)
            s = jax.lax.dot_general(k, q, _NT,
                                    preferred_element_type=jnp.float32)
            if masked:
                at_q = tile * block + c * pos + _position(
                    jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1), heads)
                at_k = kb * block + jax.lax.broadcasted_iota(
                    jnp.int32, (block, 1), 0)
                s = jnp.where(_seen(at_q, at_k, window), s, MASKED)
            p = jnp.exp(s - lse_ref[:, at])
            dp = jax.lax.dot_general(v, do, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[:, at])).astype(jnp.bfloat16)
            dv += jnp.dot(p.astype(jnp.bfloat16), do,
                          preferred_element_type=jnp.float32)
            dk += jnp.dot(ds, q, preferred_element_type=jnp.float32)
            dq = jax.lax.dot_general(ds, k, _TN,
                                     preferred_element_type=jnp.float32)
            dq_ref[here] += dq.reshape(pos, heads, d)
        keys = pl.ds(pl.multiple_of(kb * block, block), block)
        dk_ref[keys, :] += dk
        dv_ref[keys, :] += dv

    _by_mask(tile, kb, block, window, visit)


@functools.lru_cache(maxsize=None)
def _backward_call(shape, window, block, interpret):
    b, s, g, r, d = shape
    grid, tile, keys, rows = _grid(shape, window, block)
    # a key/value head's whole dk and dv: resident while its tiles go by
    head = pl.BlockSpec((None, s, d), lambda b_, g_, i, j: (b_, 0, g_))
    return pl.pallas_call(
        functools.partial(_backward_kernel, window=window, block=block,
                          heads=r),
        grid=grid, in_specs=[tile, keys, keys, tile, rows, rows],
        out_specs=[tile, head, head],
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, s, g * d), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, g * d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="kps_attn_core_backward")


# -- the core ------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def attend(q, k, v, window: int | None, block: int, interpret: bool = False):
    """`lm_common.blocked_attention` on `q` ALREADY SCALED: `q` `[B, S,
    G, R, D]`, `k`, `v` `[B, S, G, D]` -> `[B, S, G, R, D]`, float32 in
    and out.  `interpret` runs the kernels in Pallas's interpreter (the
    CPU tests)."""
    return _forward(q, k, v, window, block, interpret)[0]


def _attend_fwd(q, k, v, window, block, interpret):
    out, lse = _forward(q, k, v, window, block, interpret)
    return out, (q, k, v, out, lse)


def _attend_bwd(window, block, interpret, kept, d_out):
    q, k, v, out, lse = kept
    # delta: a row's sum of dP x P, which is its d_out . out
    delta = (d_out * out).sum(-1)
    halves = q.shape[-1] == HALF
    if halves:
        q, d_out = _paired(q), _paired(d_out)
    b, s, g, r, _ = q.shape
    delta = jnp.moveaxis(delta.reshape(b, s, g, r), 1, 2).reshape(
        b, g, 1, s * r)
    dq, dk, dv = _backward_call(q.shape, window, block, interpret)(
        q, k.reshape(b, s, -1), v.reshape(b, s, -1), d_out, lse, delta)
    return ((_own_halves(dq) if halves else dq), dk.reshape(k.shape),
            dv.reshape(v.shape))


attend.defvjp(_attend_fwd, _attend_bwd)

"""A head's RMS norm and its rotate-half RoPE as ONE pass of a TPU
kernel (Pallas): what `lm_common.head_norm_rope` runs on the chip at
the shapes `takes` names, forward and backward behind one
`jax.custom_vjp`.

The mathematics is `lm_common.rope(lm_common.rms_norm(x, w, eps))`'s on
`x` `[B, S, heads, d]`, float32 at every step:

    y   = x * rsqrt(mean(x², d) + eps) * w
    out = (y * cos + rot(y) * sin) * scale        rot(y) = [-y2, y1]

and what changes is how a half is reached.  Written as two slices and a
concatenation, a half of a 128-lane vector is what the compiler lays
the whole of q tokens-minor for, and it pays for that layout at every
boundary that wants the channels in lanes (PERF.md section 6, PR 43).
Here a row of the projection's result `[S, heads x d]` stays as it
lies, each head's `d` channels along the lanes, and the rotation is a
lane roll by `d / 2` times a sign, `[-1] * (d/2) + [+1] * (d/2)`, which
the caller's tables carry (`signed`): one read of x, one write.  A roll
by half the lanes is its own inverse, so the backward pass rolls the
cotangent the same way.  Backward, from x and the cotangent alone (the
layer is recomputed anyway): dx, and the norm weight's gradient summed
into a block that stays in VMEM while the grid goes by.

    grid (B, tiles of positions); a tile's heads: a loop inside the step

Two layouts hold the channels in lanes, and they differ in what lies
along the sublanes.  The projection writes `[S, heads x d]`: eight
POSITIONS of one head a tile of memory.  The attention core reads q as
`[S, G, R, d]`: eight HEADS of one position a tile — other bytes, and
between the two the compiler puts a copy of q (and, before the
attention kernel, a pass of its own for the core's `1 / sqrt(d)`).  So
where the heads fill whole sublanes (`by_head`) the kernel reads x as
the projection wrote it and writes the result as the core reads it,
`[S x heads, d]`, a head's positions `heads` rows apart (a strided
store); the cotangent comes back in that layout and dx leaves in the
projection's.  `scale` is what the result is multiplied by on its way
out (the core's `1 / sqrt(d)` on q; 1 on k): the same pass.  Fewer
heads than a sublane tile (k: 4) stay `[S, heads x d]` on both sides,
which is how the core reads k.

`cos` and `sin` are `[S, d]` tables of a position's angles, read once a
tile for all its heads, or None where the layer norms and does not
rotate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# what a tile of positions may hold of one array, float32: the backward
# pass holds three of them (x, the cotangent, dx), each twice
TILE_BYTES = 2 * 1024 * 1024
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# the row counters' unit (`attn.norm_rope_rows`)
ROWS_UNIT = 1024


def takes(shape) -> bool:
    """Whether the kernel takes `x` `[B, S, heads, d]`: whole lanes of
    channels, whole sublanes of positions."""
    _, s, _, d = shape
    return d % LANES == 0 and s % SUBLANES == 0


def by_head(heads: int) -> bool:
    """Whether the result leaves as `[S x heads, d]`, eight heads of a
    position a tile of memory, as the attention core reads q: where the
    heads fill whole sublanes."""
    return heads % SUBLANES == 0


def laid(shape) -> tuple:
    """The shape the result of `x` `[B, S, heads, d]` leaves in, and
    its cotangent comes back in."""
    b, s, heads, d = shape
    return (b, s * heads, d) if by_head(heads) else (b, s, heads * d)


def tile_of(s: int, width: int) -> int:
    """Positions a tile: the most whole sublanes inside TILE_BYTES, the
    row's `s` at most; the last tile of a row may be partial."""
    return min(s, max(SUBLANES, TILE_BYTES // (4 * width)
                      // SUBLANES * SUBLANES))


def signed(sin):
    """`sin` with the rotation's sign: minus on the first half of the
    channels."""
    d = sin.shape[-1]
    return sin * jnp.where(jnp.arange(d) < d // 2, -1.0, 1.0)


def _normed(x, eps):
    """(x / rms(x), 1 / rms(x)) of `[rows, d]`."""
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r, r


def _head(ref, h: int, heads: int, d: int, by_head: bool = False):
    """Where head `h` of a tile lies in `ref`: its `d` lanes of every
    position's row, or (`by_head`) every `heads`-th row."""
    if by_head:
        return pl.ds(h, ref.shape[0] // heads, stride=heads), slice(None)
    return slice(None), pl.ds(h * d, d)


def _forward_kernel(x_ref, w_ref, *refs, heads, eps, scale, rotates,
                    by_head):
    o_ref = refs[-1]
    d = w_ref.shape[-1]
    w = w_ref[...]
    if rotates:
        cos, sin = refs[0][...], refs[1][...]
    for h in range(heads):
        y = _normed(x_ref[_head(x_ref, h, heads, d)], eps)[0] * w
        if rotates:
            y = y * cos + pltpu.roll(y, d // 2, 1) * sin
        o_ref[_head(o_ref, h, heads, d, by_head)] = (
            y if scale == 1.0 else y * scale)


def _backward_kernel(x_ref, dy_ref, w_ref, *refs, heads, eps, scale,
                     rotates, by_head, s):
    dx_ref, dw_ref = refs[-2:]
    tile, d = x_ref.shape[0], w_ref.shape[-1]
    w = w_ref[...]
    if rotates:
        cos, sin = refs[0][...], refs[1][...]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    # a partial last tile reads whatever lies past the row: its
    # positions reach nothing that is summed
    inside = (pl.program_id(1) * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, 1), 0) < s) if s % tile else None
    dw = jnp.zeros((SUBLANES, d), jnp.float32)
    for h in range(heads):
        at = _head(x_ref, h, heads, d)
        n, r = _normed(x_ref[at], eps)
        dy = dy_ref[_head(dy_ref, h, heads, d, by_head)]
        if scale != 1.0:
            dy = dy * scale
        if rotates:
            dy = dy * cos + pltpu.roll(dy * sin, d // 2, 1)
        dn = dy * w
        dx_ref[at] = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
        dwh = dy * n
        if inside is not None:
            dwh = jnp.where(inside, dwh, 0.0)
        dw += dwh.reshape(tile // SUBLANES, SUBLANES, d).sum(axis=0)
    dw_ref[...] += dw


@functools.lru_cache(maxsize=None)
def _call(shape, tile, eps, scale, rotates, backward, interpret):
    b, s, heads, d = shape
    width = heads * d

    def tiled(rows, lanes):
        return pl.BlockSpec((None, rows, lanes), lambda b_, i: (b_, i, 0))
    flat = tiled(tile, width)
    rows = tiled(tile * heads, d) if by_head(heads) else flat
    weight = pl.BlockSpec((1, d), lambda b_, i: (0, 0))
    tables = [pl.BlockSpec((tile, d), lambda b_, i: (i, 0))] * 2 * rotates
    elements = b * s * width
    if backward:
        kernel = functools.partial(_backward_kernel, s=s)
        in_specs = [flat, rows, weight] + tables
        out_specs = [flat, pl.BlockSpec((SUBLANES, d), lambda b_, i: (0, 0))]
        out_shape = [jax.ShapeDtypeStruct((b, s, width), jnp.float32),
                     jax.ShapeDtypeStruct((SUBLANES, d), jnp.float32)]
        semantics = ("arbitrary", "arbitrary")      # dw stays across both
    else:
        kernel = _forward_kernel
        in_specs = [flat, weight] + tables
        out_specs = rows
        out_shape = jax.ShapeDtypeStruct(laid(shape), jnp.float32)
        semantics = ("parallel", "parallel")
    return pl.pallas_call(
        functools.partial(kernel, heads=heads, eps=eps, scale=scale,
                          rotates=rotates, by_head=by_head(heads)),
        grid=(b, pl.cdiv(s, tile)), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=(20 if backward else 8) * elements,
            transcendentals=elements // d,
            bytes_accessed=4 * (3 if backward else 2) * elements),
        interpret=interpret,
        name="kps_norm_rope_" + ("backward" if backward else "forward"))


def _operands(x, w, cos, sin):
    """(the call's shape-dependent arguments, x flat, the weight and
    the tables as the kernels take them)."""
    b, s, heads, d = x.shape
    tables = () if cos is None else (cos, signed(sin))
    return ((x.shape, tile_of(s, heads * d)), x.reshape(b, s, heads * d),
            (w.reshape(1, d), *tables))


def _forward(x, w, cos, sin, eps, scale, interpret):
    sized, flat, rest = _operands(x, w, cos, sin)
    return _call(*sized, eps, scale, cos is not None, False, interpret)(
        flat, *rest).reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def norm_rope(x, w, cos, sin, eps: float, scale: float = 1.0,
              interpret: bool = False):
    """`rope(rms_norm(x, w, eps)) * scale` on `x` `[B, S, heads, d]`
    with the norm's weight `w` `[d]` and the angles' `cos`, `sin` `[S,
    d]` (both None: the norm alone), float32 in and out.  The tables
    take no gradient.  `interpret` runs the kernels in Pallas's
    interpreter (the CPU tests)."""
    return _forward(x, w, cos, sin, eps, scale, interpret)


def _norm_rope_fwd(x, w, cos, sin, eps, scale, interpret):
    return _forward(x, w, cos, sin, eps, scale, interpret), (x, w, cos, sin)


def _norm_rope_bwd(eps, scale, interpret, kept, d_out):
    x, w, cos, sin = kept
    sized, flat, rest = _operands(x, w, cos, sin)
    dx, dw = _call(*sized, eps, scale, cos is not None, True, interpret)(
        flat, d_out.reshape(laid(x.shape)), *rest)
    none = None if cos is None else jnp.zeros_like(cos)
    return dx.reshape(x.shape), dw.sum(axis=0), none, none


norm_rope.defvjp(_norm_rope_fwd, _norm_rope_bwd)

"""What the language-model families share (`glm4_moe_lite`, `nemotron_h`,
`afmoe`, `ouro`, `mellum`, `lfm2_moe`, `granitemoehybrid`): token rows,
RMSNorm, RoPE, the blocked attention core (on a TPU a kernel,
`models/attention_kernel.py`, where the shapes allow), the sliced head and
its loss, the router, the expert layer that knows its share, the gated
(SwiGLU) expert, the counters, the flat key space, the k-step solver with
its counts, the evaluation, and the task's frame.  A family brings its
own configuration, its leaves, its blocks and its expert's function;
nothing here tests a family's name.

Token rows are `int32[S + 2]`, whose labels are the row itself,
shifted.  A family's configuration is one JSON file (`--model_json`,
`ModelConfig.model_json`): the published keys and the cut —
`experts_held` / `expert_offset` (which of the `n_routed_experts` this
process holds), `vocab_held` (its slice of the vocabulary), the depth
and `sequence_length`.

The expert layer knows its share: it routes every token over ALL
`n_routed_experts`, computes what its own experts give for the tokens
routed to them plus the shared expert, and leaves out what the absent
experts would add — that partial result is what goes on to the next
layer.  No token is dropped and none is padded to a capacity: the
assignments are sorted by expert and run through `jax.lax.ragged_dot`,
which computes the rows of each held expert's group and no others.
The chip's kernel behind it is told which tiles to walk the held
experts' matrices in (`grouped_tiles`) where its own choice is small:
left to itself it walks a width that 512 does not divide, 2688 or
1856, in 128 x 128 blocks of 64 KB, a grid step each, and the call is
bound by the steps, not by the bytes (0.94-1.09 ms for 0.23-0.29).  The
shapes decide, and at widths that are multiples of 512 nothing is said.
Nothing stands in for the absent chips or their exchange.  What an
expert computes (`silu(gate) * up -> down`, `relu(up)**2 -> down`) is
the family's own function, handed in.

Leaves are a flat dict `{dotted name: array}`; a family's `leaf_specs`
fixes their order in the flat key space (the wire contract).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.xla_metadata import set_xla_metadata

from kafka_ps_tpu.models import (attention_kernel, norm_rope_kernel,
                                 placement_kernel)
from kafka_ps_tpu.models import metrics as metrics_mod
from kafka_ps_tpu.models import task as task_mod
from kafka_ps_tpu.utils.config import ModelConfig

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# what `fit_counted` returns beside the loss, in this order, before a
# family's own counters (Tracer.count names; runtime/app.py sums them
# over a drive call)
COUNTERS = ("moe.assignments_here", "moe.assignments_here_grad",
            "moe.assignments_away", "moe.expert_load_max",
            "data.tokens", "data.pad_tokens", "moe.passes_over_bound")


def resolve_model_json(path: str) -> str:
    """An absolute path as it is; a relative one from the repository's
    root (the benchmark's configurations carry a relative path and are
    run from any directory)."""
    return path if os.path.isabs(path) else os.path.join(REPO_ROOT, path)


@functools.lru_cache(maxsize=None)
def load_config(path: str, model_type: str, config_cls):
    """The family's configuration from its file: the dataclass's keys
    are read, the others (comments, published keys the program does not
    need) are left; a key without a default has to be there."""
    with open(resolve_model_json(path)) as fh:
        body = json.load(fh)
    if body.get("model_type") != model_type:
        raise ValueError(f"{path}: model_type {body.get('model_type')!r} is "
                         f"not {model_type}")
    fields = {f.name for f in dataclasses.fields(config_cls)}
    missing = [f.name for f in dataclasses.fields(config_cls)
               if f.default is dataclasses.MISSING and f.name not in body]
    if missing:
        raise ValueError(f"{path}: missing keys {missing}")
    c = config_cls(**{k: v for k, v in body.items() if k in fields})
    c.validate()
    return c


def validate_cut(c) -> None:
    """The cut a family states: which rows of the vocabulary are held
    here and, where it has an expert layer, which of the experts."""
    if hasattr(c, "experts_held") and not (
            0 <= c.expert_offset <= c.n_routed_experts - c.experts_held):
        raise ValueError("expert_offset + experts_held must lie inside "
                         "n_routed_experts")
    if not 0 < c.vocab_held <= c.vocab_size:
        raise ValueError("vocab_held must lie inside vocab_size")


# -- the flat key space --------------------------------------------------------

def num_params(specs) -> int:
    return sum(math.prod(s) for _, s in specs)


def unflatten(theta, specs) -> dict:
    """The leaves of a flat vector.  Each is cut out before it is
    shaped (the barrier): left to itself the compiler shapes the WHOLE
    vector as `[P / 64, 64]` to cut a router out of it, a padded 4.7 GB
    copy at the published widths."""
    out, at = {}, 0
    for name, shape in specs:
        n = math.prod(shape)
        out[name] = jax.lax.optimization_barrier(
            theta[at:at + n]).reshape(shape)
        at += n
    return out


def flatten(leaves: dict, specs) -> jax.Array:
    return jnp.concatenate([leaves[name].reshape(-1) for name, _ in specs])


def sub(leaves: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in leaves.items()
            if k.startswith(prefix)}


# -- the layers both families have -----------------------------------------------

def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def block_norm(x, w, eps: float):
    """`rms_norm` where a block norms its own input or output, under
    the named scope `kps.lm.norm`: outside every mixer's scope these
    would lie under `kps.fit.grad` alone."""
    with jax.named_scope("kps.lm.norm"):
        return rms_norm(x, w, eps)


def rope(x, theta: float):
    """Rotate-half RoPE over the whole last axis; positions run along
    axis -3 of `[..., S, heads, d]`."""
    d = x.shape[-1]
    s = x.shape[-3]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


# -- a head's norm and RoPE in one pass ------------------------------------------

def rope_angles(s: int, inv_freq, scale: float = 1.0):
    """(cos, sin) `[s, d]` float32 of positions 0..s-1 at the
    frequencies `inv_freq` `[d / 2]`, each half of the channels the
    same angles (rotate-half), cos and sin each times `scale`: the
    tables `rope` makes for itself, for `head_norm_rope`."""
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    return (cos, sin) if scale == 1.0 else (scale * cos, scale * sin)


def _norm_rope_plain(x, w, cos, sin, *, eps: float, scale: float):
    y = rms_norm(x, w, eps)
    if cos is not None:
        d = y.shape[-1]
        y1, y2 = y[..., :d // 2], y[..., d // 2:]
        y = (y * cos[:, None, :]
             + jnp.concatenate([-y2, y1], -1) * sin[:, None, :])
    return y if scale == 1.0 else y * scale


def head_norm_rope(x, w, eps: float, cos=None, sin=None, *,
                   scale: float = 1.0):
    """A projected q or k `[B, S, heads, d]` through its head-wise
    RMSNorm (weight `w` `[d]`) and rotate-half RoPE by the tables
    `cos`, `sin` `[S, d]` (`rope_angles`; both None: the layer norms
    and does not rotate), the result times `scale` (the attention
    core's `1 / sqrt(d)` where q goes on to `blocked_attention(...,
    scaled=True)`), float32 throughout.

    One computation, two ways to run it, and the input says which, as
    `blocked_attention` chooses its core.  On a TPU, at shapes the
    kernel takes (`norm_rope_kernel.takes`: whole lanes of channels,
    whole sublanes of positions), it is `norm_rope_kernel.norm_rope`:
    one pass over the rows as the projection wrote them, channels in
    lanes, the rotation a lane roll — x read once, the result written
    once, as the attention core reads it, and under `jax.grad` one
    more such pass from x and the cotangent.  Anywhere else — another
    platform, a `head_dim` of 16 — it is `rope(rms_norm(x, w, eps))` in
    plain `jax.numpy`, two slices and a concatenation, and the program
    is what it was before there was a kernel.  (Plain, a half of a 128-lane vector is what the
    chip's compiler lays q and k tokens-minor for: PERF.md section 6,
    PR 43.)  `rope` itself is as it was, for the families that rotate
    without a head norm or over fewer channels."""
    plain = functools.partial(_norm_rope_plain, eps=eps, scale=scale)
    if not norm_rope_kernel.takes(x.shape):
        return plain(x, w, cos, sin)
    return jax.lax.platform_dependent(
        x, w, cos, sin, default=plain,
        tpu=lambda x, w, cos, sin: norm_rope_kernel.norm_rope(
            x, w, cos, sin, eps, scale))


def norm_rope_counts(rows: int, c) -> tuple:
    """(`attn.norm_rope_rows`, `attn.norm_rope_kernel_rows`) of one
    pass over `rows` rows of a family whose every layer sends q and k
    through `head_norm_rope`: the head rows normed — positions x heads,
    q's and k's, over every layer — in units of
    `norm_rope_kernel.ROWS_UNIT`, and the same again where the kernel
    ran them, 0 where plain: chosen as the pass itself is chosen."""
    normed = (rows * c.sequence_length * c.num_hidden_layers
              * (c.num_attention_heads + c.num_key_value_heads)
              // norm_rope_kernel.ROWS_UNIT)
    if not norm_rope_kernel.takes((rows, c.sequence_length,
                                   c.num_attention_heads, c.head_dim)):
        return normed, 0
    return normed, normed * jax.lax.platform_dependent(
        tpu=lambda: 1, default=lambda: 0)


# -- the blocked attention core ------------------------------------------------

def key_span(tile: int, block: int, window: int | None) -> tuple[int, int]:
    """The keys `[lo, hi)` a tile of `block` queries is set against:
    up to the tile's own end (causal), and in a sliding layer from the
    whole block that holds the earliest key its first query sees
    (itself and the `window - 1` before it)."""
    first = tile * block
    lo = 0 if window is None else max(0, first - window + 1) // block * block
    return lo, first + block


def attention_pairs(s: int, window: int | None) -> int:
    """(query, key) pairs INSIDE the mask over one row of `s` tokens:
    query i sees key j iff j <= i, and under a window iff also
    i - j < window."""
    return sum(min(i + 1, window or s) for i in range(s))


def attention_block_pairs(s: int, window: int | None, block: int) -> int:
    """The pairs inside every block `blocked_attention` computes at
    all, over one row: each tile of queries times its `key_span`."""
    spans = (key_span(t, block, window) for t in range(s // block))
    return sum(block * (hi - lo) for lo, hi in spans)


def _attend_tile(q, k, v, first: int, lo: int, window: int | None):
    """One tile of queries `[B, T, G, R, D]` (already scaled), whose
    first sits at position `first`, against the keys and values `[B, L,
    G, D]` from position `lo` on: scores, mask, softmax, values.  The
    tile's whole span of keys is in hand, so the softmax is plain: no
    running maximum; every query sees at least its own key."""
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k)
    at_q = first + jnp.arange(q.shape[1])[:, None]
    at_k = lo + jnp.arange(k.shape[1])[None, :]
    seen = at_k <= at_q
    if window is not None:
        seen &= at_q - at_k < window
    scores = jnp.where(seen, scores, -jnp.inf)
    p = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p, v)
    return out / jnp.moveaxis(p.sum(axis=-1), 3, 1)[..., None]


def _attend_tiles(q, k, v, *, window: int | None, block: int):
    """The core in plain `jax.numpy` on `q` already scaled, the tiles
    written out since their spans differ in length: each tile's scores
    `[G, R, block, hi - lo]` are the largest array, and each tile is
    recomputed in the backward pass (`jax.checkpoint`, inside the
    block's own); what is kept of it is its slice of q, k and v."""
    tiles = []
    for t in range(q.shape[1] // block):
        lo, hi = key_span(t, block, window)
        tiles.append(jax.checkpoint(functools.partial(
            _attend_tile, first=t * block, lo=lo, window=window))(
                q[:, t * block:(t + 1) * block], k[:, lo:hi], v[:, lo:hi]))
    return jnp.concatenate(tiles, axis=1)


def blocked_attention(q, k, v, *, window: int | None, block: int,
                      scaled: bool = False):
    """Causal softmax attention over grouped-query heads, a tile of
    `block` queries at a time: `q` `[B, S, G, R, D]` (R query heads to
    each of the G key/value heads), `k`, `v` `[B, S, G, D]` -> `[B, S,
    G, R, D]`.  Query i sees key j iff `j <= i`, and under `window`
    iff also `i - j < window` (None: a full layer).  `scaled`: q comes
    already times `1 / sqrt(D)` (`head_norm_rope`'s `scale`: on the
    chip a pass of its own over q otherwise).

    Each tile is set against its own slice of keys (`key_span`) and no
    other: in a sliding layer the blocks the band cannot reach are
    never computed, stored or differentiated, so the work is S x
    (window + block) and not S x S, and no array of S x S elements a
    head exists anywhere; `block` must divide S.

    One algorithm, two ways to run it, and the input says which.  On a
    TPU, at shapes the kernel takes (`attention_kernel.takes`: a
    `head_dim` and a `block` of whole lanes, 128, or a `head_dim` of
    64 under an even G, two heads to a lane vector), the core is
    `attention_kernel.attend`: a block's scores are formed once, in
    VMEM, under a running maximum, and only the output and the rows'
    log-sum-exp are written.  Anywhere else — another platform, a
    `head_dim` of 8 — it is `_attend_tiles`, plain `jax.numpy`, and
    the program is what it was before there was a kernel."""
    s, d = q.shape[1], q.shape[-1]
    if s % block:
        raise ValueError(f"block {block} must divide the row's {s} tokens")
    if not scaled:
        q = q * (1.0 / math.sqrt(d))
    tiles = functools.partial(_attend_tiles, window=window, block=block)
    if not attention_kernel.takes(q.shape, block):
        return tiles(q, k, v)
    return jax.lax.platform_dependent(
        q, k, v, default=tiles, tpu=lambda q, k, v: attention_kernel.attend(
            q, k, v, window, block))


def kernel_attends(q_shape, block: int):
    """1 where `blocked_attention` runs `q_shape` as the kernel, 0 where
    as plain tiles: chosen as the core itself is chosen."""
    if not attention_kernel.takes(q_shape, block):
        return 0
    return jax.lax.platform_dependent(tpu=lambda: 1, default=lambda: 0)


# -- the expert layer ------------------------------------------------------------

def route(h, router, bias, c):
    """Every token over ALL experts → (chosen experts [T, K], their
    weights [T, K]).  float32 at `highest` precision, as the published
    gates compute it."""
    with jax.named_scope("kps.moe.route"):
        s = jax.nn.sigmoid(jnp.dot(h, router,
                                   precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(s + bias, c.num_experts_per_tok)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if c.norm_topk_prob:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return idx, w * c.routed_scaling_factor


def live_rows_bound(slots: int, c) -> int:
    """How many of a pass's `slots` (token, chosen expert) assignments
    the expert layer places without looking further: twice the even
    share of the experts held here, in whole tiles of 8 rows.  A pass
    that routes more here takes every slot instead (`routed_experts`)."""
    even = slots * c.experts_held / c.n_routed_experts
    return min(slots, 8 * math.ceil(2 * even / 8))


# what a grouped product's grid step may take of the kernel's 16 MB of
# scoped VMEM
GROUPED_VMEM_BUDGET = 12 * 2 ** 20


def grouped_step_bytes(tm: int, tk: int, tn: int) -> int:
    """What a grid step of the grouped-product kernel holds in VMEM
    under tiles `tm,tk,tn`: float32 rows `[tm, tk]`, matrix `[tk, tn]`
    and result `[tm, tn]`, each twice (one in flight), and an
    accumulator as large as the matrix block, which is what the
    product's dW carries under the same tiles."""
    return 4 * (2 * tm * tk + 3 * tk * tn + 2 * tm * tn)


def grouped_tiles(m: int, k: int, n: int) -> str | None:
    """The tiles `tm,tk,tn` in which the chip's grouped-product kernel
    should walk `[m, k] x [g, k, n]`, as its `ragged_dot_tiling` hint
    takes them, or None where it is told nothing.

    Left to itself the kernel walks a width that 512 divides in 512s
    and one like 2688 or 1856 in 128s (read from compiles for a
    described v5e), so a width that is a multiple of 512, or that one
    block of 512 holds, is well served and `None` leaves a product of
    such widths (and any whose rows no tile of 128 divides) to the
    compiler.  Any other width gets the fewest tiles no wider
    than a cap, evenly sized in whole 128s (2688 -> 3 x 896; 1856 ->
    3 x 640, the last one partial), the cap starting at 1024 and
    falling until a grid step (`grouped_step_bytes`) fits
    GROUPED_VMEM_BUDGET.  `tm` is the rows' own: 256, or 128 where 256
    does not divide `m`."""
    tm = next((t for t in (256, 128) if m % t == 0), None)
    if tm is None or all(d % 512 == 0 or d <= 512 for d in (k, n)):
        return None
    for cap in range(8, 0, -1):
        tk, tn = (128 * math.ceil(blocks / math.ceil(blocks / cap))
                  for blocks in (math.ceil(k / 128), math.ceil(n / 128)))
        if grouped_step_bytes(tm, tk, tn) <= GROUPED_VMEM_BUDGET:
            return f"{tm},{tk},{tn}"
    return None


# dW of a grouped product: the rows' and the result's gradient contracted
# over the ragged rows, a group at a time -> [g, k, n]
_GROUPED_DW = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def told_grouped(rows, matrices, sizes, tiles: str):
    """`jax.lax.ragged_dot` with the kernel told its `tiles`
    (`grouped_tiles`), and each of its transposes told its own.  Under
    `jax.grad` alone dx and dW inherit the product's string, and dx,
    whose contraction is the product's n and whose result its k, then
    walks 1856 in 896s and 2688 in 640s: 0.33 ms a call for 0.23 under
    the same tiles turned round (PERF.md, PR 32).

    Rows past the last group belong to no product.  The untold kernel
    happens to write finite numbers there; the told one leaves the row
    tiles no group reaches as it found them, NaN bits included.  The
    caller masks the product's rows by `where`; dx is masked here, so
    that a sum over the rows with weight 0 on the dead ones (the
    placement's transpose in `routed_experts`) stays finite.  The
    kernels themselves never read a dead row into a live result
    (chip_smoke.py feeds them NaN there)."""
    with set_xla_metadata(ragged_dot_tiling=tiles):
        return jax.lax.ragged_dot(rows, matrices, sizes)


def _zero_past_the_last_group(d_rows, sizes):
    live = (jnp.arange(d_rows.shape[0]) < sizes.sum())[:, None]
    return jnp.where(live, d_rows, 0.0)


def _told_grouped_fwd(rows, matrices, sizes, tiles):
    return told_grouped(rows, matrices, sizes, tiles), (rows, matrices, sizes)


def _told_grouped_bwd(tiles, kept, dy):
    rows, matrices, sizes = kept
    tm, tk, tn = tiles.split(",")
    with set_xla_metadata(ragged_dot_tiling=f"{tm},{tn},{tk}"):
        d_rows = jax.lax.ragged_dot(dy, jnp.swapaxes(matrices, 1, 2), sizes)
    d_rows = _zero_past_the_last_group(d_rows, sizes)
    with set_xla_metadata(ragged_dot_tiling=tiles):
        d_matrices = jax.lax.ragged_dot_general(rows, dy, sizes, _GROUPED_DW)
    return d_rows, d_matrices, None


told_grouped.defvjp(_told_grouped_fwd, _told_grouped_bwd)


@jax.custom_vjp
def live_rows_only(rows, sizes):
    """The sorted rows of a grouped product as they are; backward,
    the cotangent of the rows past the last group is cut off.  A family
    whose products are NOT told their tiles wraps its expert's input in
    this (`dot.sizes` are the groups): the untold kernel skips the row
    tiles no group reaches, what it leaves there is whatever the buffer
    held, and the placement's transpose in `routed_experts` sums dx
    over ALL rows, the dead ones with weight 0 — 0 x NaN (on the chip at
    `[4096, 2048] x [8, 2048, 1024]`: NaN from the first clock on a
    machine whose memory had held NaN, PERF.md PR 33).  `told_grouped`
    masks its own dx the same way."""
    return rows


def _live_rows_only_fwd(rows, sizes):
    return rows, sizes


def _live_rows_only_bwd(sizes, d_rows):
    return _zero_past_the_last_group(d_rows, sizes), None


live_rows_only.defvjp(_live_rows_only_fwd, _live_rows_only_bwd)


def routed_experts(h, idx, w, p: dict, c, expert):
    """The part of Σ w_e · expert_e(h) that the experts held here give
    → ([T, H], (assignments here, largest expert's load, 1 if the pass
    went over `live_rows_bound`; with the placement kernels a fourth:
    the pairs they multiplied)).  `expert(xs, p, dot)` is the family's
    own: what one expert computes on its rows, every product with the
    held experts' stacked matrices through `dot(rows, matrices)`
    (`dot.sizes`: the rows each held expert's group has).

    The (token, chosen expert) assignments are sorted by expert, absent
    experts last; the held experts' products run as grouped products
    over the sorted rows (`jax.lax.ragged_dot`: the chip's kernel
    computes the rows of each group and no others, so its work follows
    the routing and no assignment is dropped).  Each product tells the
    kernel the tiles `grouped_tiles` gives for its shape, and says
    nothing where that gives None: the same float32 operands, default
    precision and float32 sums either way — on the chip the told
    product, its dx and its dW equal the untold ones to the bit
    (chip_smoke.py).  Only the sorted rows up
    to `live_rows_bound` are placed and added back — the live ones come
    first — unless the pass counts more assignments here than that:
    then all T·K slots are, so none is ever dropped.  Tokens are placed
    into sorted order, and results added back, by products with a 0/1
    placement matrix rather than a gather and a scatter-add: a TPU
    scatter costs over a microsecond a row, a fifth of the update when
    it was written so.  Placing is exact at the default precision (one
    term a row, and the grouped product rounds its operand the same
    way); adding back runs at `HIGH`, which beside a 0/1 operand is two
    bfloat16 passes (the float32 side's high and low piece).

    One algorithm, two ways to run the two products, and the shape says
    which (`placement_kernel.takes(rows, tokens, hidden)` of the rows
    under the bound and of all T·K: one way for both branches).  Where
    it takes them, on a TPU, they are the kernels of
    `models/placement_kernel.py`: the matrix is never written to
    memory, a piece of it is built in VMEM from the rows' token ids
    and multiplied on the MXU, and a piece
    that holds no one — the tiles of dead rows whole, and most blocks
    of tokens of a tile inside one group — is neither fetched nor
    multiplied; the same passes, the same float32 sums, and under
    `jax.grad` each kernel is the other's transpose.  Anywhere else —
    another platform, a smaller matrix — it is `jnp.dot` with the
    matrix written out, and the program is what it was before there
    were kernels.  With the kernels the counts gain a fourth entry: the
    elements of the matrix that were multiplied, in units of
    `placement_kernel.PAIRS_UNIT` (elsewhere than a TPU: all of them).

    Named scopes inside `kps.moe.experts`, for the trace's readers
    (benchmark/self_time.py): `kps.moe.sort` (key, argsort, group
    sizes, the gathered weights), `kps.moe.place` (the live mask, the
    0/1 matrix or the kernels' plan, the placing product or kernel),
    `kps.moe.expert_fn` (the family's `expert` and the dead rows' mask;
    the chip's grouped-product calls inside it keep the `ragged-dot`
    name the compiler gives them), `kps.moe.combine` (the weighting and
    the add-back product or kernel) — the same in both branches of the
    bound's `cond`; a product's transpose under `jax.grad` keeps its
    scope, so `d_h` reads under `kps.moe.place` and the add-back's
    transpose under `kps.moe.combine`.  What a trace reads under
    `kps.moe.experts` ALONE: with the product, the add-back of the
    branch that ran (a branch's ROOT fusion takes the `cond`'s name,
    not its own scope's); with the kernels the add-back is a Mosaic
    call, which keeps `kps.moe.combine`, and what is left there is the
    `cond` itself and what the compiler moves across its edge.

    Under `jax.grad` the two branches differ in what they keep.  The
    branch under the bound — the one a pass takes unless more
    assignments land here than `live_rows_bound` — keeps its residuals
    as any traced code does and recomputes nothing.  The branch over
    the bound is a `jax.checkpoint`: it keeps its inputs (`h`, the
    matrices, `order`, `weight` — what the other branch holds anyway)
    and runs its forward once more in the backward pass.  A `cond`
    returns ONE tuple of residuals for both branches, each filling the
    other's entries with zeros, and the two place different row counts,
    so no entry is shared: with the rare branch's residuals crossing
    the `cond` the common one wrote all of them as zeros, T·K rows
    wide, on every gradient pass of every expert layer (24.8 ms of the
    third language-model cell's 370 ms update, PERF.md PR 40)."""
    with jax.named_scope("kps.moe.experts"):
        t, k = idx.shape
        held = c.experts_held
        with jax.named_scope("kps.moe.sort"):
            local = idx - c.expert_offset
            here = (local >= 0) & (local < held)
            # absent experts sort last, into a group that is never
            # computed
            key = jnp.where(here, local, held).reshape(-1)
            order = jnp.argsort(key, stable=True)
            sizes = (key[:, None] == jnp.arange(held)[None, :]).sum(
                axis=0, dtype=jnp.int32)
            n_here = sizes.sum()
            weight = jnp.where(here, w, 0.0).reshape(-1)[order]

        def grouped(rows, matrices):
            tiles = grouped_tiles(rows.shape[0], *matrices.shape[1:])
            if tiles is None:
                return jax.lax.ragged_dot(rows, matrices, sizes)
            return told_grouped(rows, matrices, sizes, tiles)
        grouped.sizes = sizes       # for a family's `live_rows_only`

        def placed(rows: int):
            """The sum from the first `rows` sorted assignments (where
            the kernels run: and the pairs of the 0/1 matrix that were
            multiplied, in units of PAIRS_UNIT)."""
            with jax.named_scope("kps.moe.place"):
                live = (jnp.arange(rows) < n_here)[:, None]
                if kernels:
                    plan = placement_kernel.plan(order[:rows] // k, n_here, t)
                    xs = _placing(h, plan)
                else:
                    place = jnp.where(live, jax.nn.one_hot(
                        order[:rows] // k, t, dtype=jnp.bfloat16), 0)
                    xs = jnp.dot(place, h, preferred_element_type=jnp.float32)
            with jax.named_scope("kps.moe.expert_fn"):
                # rows past the last group are never computed: whatever
                # the kernel leaves there must reach nothing
                y = jnp.where(live, expert(xs, p, grouped), 0.0)
            with jax.named_scope("kps.moe.combine"):
                if kernels:
                    return (_adding_back(y * weight[:rows, None], plan),
                            _pairs_multiplied(plan))
                return jnp.dot(place.T, y * weight[:rows, None],
                               precision=jax.lax.Precision.HIGH,
                               preferred_element_type=jnp.float32)

        bound = live_rows_bound(t * k, c)
        # one way for both branches: the kernels where they take each
        kernels = all(placement_kernel.takes(rows, t, h.shape[1])
                      for rows in (bound, t * k))
        went_over = n_here > bound
        out = (placed(t * k) if bound == t * k else
               jax.lax.cond(
                   went_over,
                   jax.checkpoint(functools.partial(placed, t * k)),
                   functools.partial(placed, bound)))
        counts = [n_here, sizes.max(), went_over.astype(jnp.int32)]
        if kernels:
            out, pairs = out
            counts.append(pairs)
        return out, jnp.stack(counts)


def _placement_matrix(plan):
    """`routed_experts`' 0/1 matrix `[rows, tokens]` written out: a
    dead row's token is -1, which is no column."""
    return jax.nn.one_hot(plan.tok, plan.tokens, dtype=jnp.bfloat16)


def _placing(h, plan):
    """`P · h`: on a TPU the kernel, elsewhere the product."""
    return jax.lax.platform_dependent(
        h, plan,
        tpu=lambda h, plan: placement_kernel.multiply(h, plan, False, 1),
        default=lambda h, plan: jnp.dot(
            _placement_matrix(plan), h, preferred_element_type=jnp.float32))


def _adding_back(yw, plan):
    """`Pᵀ · yw` at `HIGH`: on a TPU the kernel in its two passes,
    elsewhere the product."""
    return jax.lax.platform_dependent(
        yw, plan,
        tpu=lambda yw, plan: placement_kernel.multiply(yw, plan, True, 2),
        default=lambda yw, plan: jnp.dot(
            _placement_matrix(plan).T, yw, precision=jax.lax.Precision.HIGH,
            preferred_element_type=jnp.float32))


def _pairs_multiplied(plan):
    """The elements of the 0/1 matrix that `_placing` and `_adding_back`
    multiply, in units of PAIRS_UNIT: the pieces the kernels visit, the
    whole matrix where the product runs."""
    dense = plan.tok.shape[0] * plan.tokens // placement_kernel.PAIRS_UNIT
    return jax.lax.platform_dependent(
        plan, tpu=lambda plan: plan.pairs,
        default=lambda plan: jnp.int32(dense))


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def swiglu_experts(xs, p: dict, dot):
    """What `routed_experts` is handed by a family whose experts are
    gated: one held expert on its own rows, every expert at once; `dot`
    is the grouped product over the sorted assignments."""
    return dot(jax.nn.silu(dot(xs, p["e_gate"])) * dot(xs, p["e_up"]),
               p["e_down"])


def expert_layer(x, p: dict, c, expert, shared):
    """An expert layer's MLP on `[B, S, H]` (already normed) → (its
    output, (assignments here, largest load, went over the bound)).
    `expert` as `routed_experts` takes it; `shared(h, p)` is the shared
    expert on every token."""
    b, s, hd = x.shape
    h = x.reshape(b * s, hd)
    idx, w = route(h, p["router"], p["router_bias"], c)
    y, load = routed_experts(h, idx, w, p, c, expert)
    with jax.named_scope("kps.moe.shared"):
        y = y + shared(h, p)
    return y.reshape(b, s, hd), load


def head_nll(x, norm, head, targets, eps: float):
    """Final norm, the head over the held slice, and each position's
    negative log-likelihood of its target → ([B, S], logits)."""
    logits = rms_norm(x, norm, eps) @ head
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked, logits


# -- the solver and the evaluation -----------------------------------------------

def fit_counted(leaves: dict, rows, mask, *, loss_and_counts, lr: float,
                steps: int, sequence_length: int, slots_a_token: int,
                own_counts=(), beyond: int = 0):
    """`steps` full-batch SGD steps on a slab → (new leaves, the
    objective at them, the counters of the passes made: COUNTERS, then
    the family's `own_counts` of one pass, times the passes, then the
    first `beyond` of what its passes counted on the device beyond the
    three, summed — as many as the family names).
    `loss_and_counts(leaves, rows, mask)` → (objective, (assignments
    here, Σ largest load, expert layers over the bound, and whatever
    more the family's layers count: `routed_experts`' fourth)) of one
    pass; `slots_a_token`: (token, chosen expert) assignments a token
    makes in a pass, over every expert layer."""
    grad = jax.value_and_grad(loss_and_counts, has_aux=True)
    # the steps are written out, not scanned: a scan's carry starts as
    # a copy of the shared leaves and is kept beside each step's result,
    # two more copies of the parameters than the steps themselves need
    new, counts = leaves, []
    for _ in range(steps):
        with jax.named_scope("kps.fit.grad"):
            (_, counted), g = grad(new, rows, mask)
        with jax.named_scope("kps.fit.param_step"):
            new = jax.tree.map(lambda a, b: a - lr * b, new, g)
        counts.append(counted)
    counts = jnp.stack(counts)
    with jax.named_scope("kps.fit.loss"):
        loss, last = loss_and_counts(new, rows, mask)
    per_pass = rows.shape[0] * sequence_length * slots_a_token
    here_grad = counts[:, 0].sum()
    here = here_grad + last[0]
    rows_in = mask.sum().astype(jnp.int32)
    stats = jnp.stack([
        here, here_grad, (steps + 1) * per_pass - here,
        counts[:, 1].sum() + last[1],
        rows_in * sequence_length,
        (rows.shape[0] - rows_in) * sequence_length,
        counts[:, 2].sum() + last[2],
        *((steps + 1) * n for n in own_counts),
        *(counts[:, i].sum() + last[i]
          for i in range(3, min(last.shape[0], 3 + beyond)))]).astype(
              jnp.int32)
    return new, loss, stats


def evaluate_leaves(leaves: dict, test_rows, *, forward,
                    sequence_length: int, vocab_held: int):
    """Next-token prediction on held-out rows `[n, S + 2]`, one row at
    a time: mean cross-entropy, accuracy, and F1 weighted over the held
    vocabulary by per-class counts (no `[V, V]` matrix).
    `forward(leaves, rows, with_logits=True)` → {"nll", "logits"}."""
    with jax.named_scope("kps.eval"):
        s = sequence_length

        def one(row):
            out = forward(leaves, row[None], with_logits=True)
            return out["nll"][0].sum(), jnp.argmax(out["logits"][0], -1)
        nll, preds = jax.lax.map(one, test_rows)
        labels = test_rows[:, 1:s + 1].reshape(-1)
        f1, acc = metrics_mod.weighted_f1_accuracy_by_class(
            preds.reshape(-1), labels, vocab_held)
        return metrics_mod.Metrics(f1=f1, accuracy=acc,
                                   loss=nll.sum() / labels.shape[0])


# -- the task's frame ---------------------------------------------------------------

class TokenRowsTask(task_mod.FlatFace):
    """MLTask (models/task.py) over `ModelConfig.model_json`: what a
    language-model family's task is, whatever its blocks.  A family
    sets `model_type` and `config_cls` and writes `leaf_specs`,
    `init_leaves`, `forward`, `loss_and_counts` and `slots_a_token`
    (`own_counts` and `counter_names` where it counts more)."""

    batches_workers = False      # a worker's own products fill the MXU
    row_dtype = np.int32
    model_file = True            # the family's widths are a file's
    counter_names = COUNTERS
    model_type: str
    config_cls: type

    def __init__(self, cfg: ModelConfig):
        if not cfg.model_json:
            raise ValueError(f"--task {self.model_type} needs --model_json "
                             "FILE (the family's own configuration)")
        self.cfg = cfg
        self.arch = load_config(cfg.model_json, self.model_type,
                                self.config_cls)
        self.specs = tuple(self.leaf_specs())

    # what a family writes
    def leaf_specs(self) -> list[tuple[str, tuple[int, ...]]]:
        """(dotted name, shape) of every leaf, in flat-layout order."""
        raise NotImplementedError

    def init_leaves(self) -> dict:
        raise NotImplementedError

    def forward(self, leaves: dict, rows, *, with_logits=False) -> dict:
        raise NotImplementedError

    def loss_and_counts(self, leaves: dict, rows, mask):
        raise NotImplementedError

    @property
    def slots_a_token(self) -> int:
        raise NotImplementedError

    def own_counts(self, rows) -> tuple:
        """The family's own counters of ONE pass over `rows`, after
        COUNTERS in `counter_names`."""
        return ()

    # the frame
    @property
    def num_params(self) -> int:
        return num_params(self.specs)

    @property
    def row_width(self) -> int:
        return self.arch.sequence_length + 2

    def init_params(self) -> jax.Array:
        # a leaf at a time, then one concatenation: inside one program
        # the compiler folds init_std into the normal's own constants,
        # and the start would differ from the stated one by a rounding
        return _flatten(self.init_leaves(), specs=self.specs)

    def unflatten(self, theta) -> dict:
        return unflatten(theta, self.specs)

    def flatten(self, leaves: dict) -> jax.Array:
        return flatten(leaves, self.specs)

    def encode_labels(self, y):
        """A token row's labels are the row itself, shifted: the label
        column carries nothing."""
        return y

    def fit_counted(self, leaves, x, enc, mask):
        own = self.own_counts(x)
        return fit_counted(leaves, x, mask,
                           loss_and_counts=self.loss_and_counts,
                           lr=self.cfg.local_learning_rate,
                           steps=self.cfg.num_max_iter,
                           sequence_length=self.arch.sequence_length,
                           slots_a_token=self.slots_a_token,
                           own_counts=own,
                           beyond=len(self.counter_names) - len(COUNTERS)
                           - len(own))

    def fit(self, leaves, x, enc, mask):
        new, loss, _ = self.fit_counted(leaves, x, enc, mask)
        return new, loss

    def evaluate_leaves(self, leaves, x_test, y_test) -> metrics_mod.Metrics:
        return evaluate_leaves(leaves, x_test, forward=self.forward,
                               sequence_length=self.arch.sequence_length,
                               vocab_held=self.arch.vocab_held)

    def logits(self, leaves, x):
        """`[B, S + 2]` rows → `[B, vocab_held]` scores of the token
        after position S - 1."""
        return self.forward(leaves, x, with_logits=True)["logits"][:, -1]


@functools.partial(jax.jit, static_argnames=("specs",))
def _flatten(leaves: dict, *, specs):
    return flatten(leaves, specs)

"""Pallas TPU kernel: the fused k-step local update — the framework's
hot op (SURVEY §7.7).

One `pallas_call` holds the entire inner solver loop of a worker
iteration (the reference's `calculateGradients` = 2 LBFGS steps on the
buffer, LogisticRegressionTaskSpark.java:179-220; ours = k full-batch GD
steps, models/logreg.local_update) with all operands resident in VMEM:

    for _ in range(k):
        logits = x @ W.T + b            # MXU  [B,F]@[F,C8]
        g      = (softmax(logits) - onehot(y)) * mask / denom
        W     -= lr * g.T @ x           # MXU  [C8,B]@[B,F]
        b     -= lr * g.sum(0)
    loss = masked-CE(x, y; W, b)

No HBM round-trips between the k steps — the weights are the fori_loop
carry, resident on-chip across iterations.  The class axis is padded to
128 lanes (min f32 tile is 8×128); padded classes are −1e30-masked out
of the softmax so their rows never receive gradient.

Selection is by shape and storage, decided at trace time from what the
code can see (`select_program`): an f32 slab whose whole working set
fits the VMEM budget takes the resident kernel; oversize f32 slabs and
every bf16/int8 slab take the streaming kernel below; a gang release
set takes the batched grid kernel.  There is NO fallback to the XLA
solver: `--pallas` means a compiled Mosaic kernel ran, and a shape no
kernel admits (or a backend that is not a TPU) raises
`PallasUnavailable` naming the shape and the reason.  The Pallas
interpreter is for tests and the CPU smoke, asked for by name
(`interpret=True`; `PSConfig.use_pallas="interpret"`).

A second kernel family, `mlp_local_update`, fuses the one-hidden-layer
MLP's k-step solver the same way (forward + hand-derived backward as
one pallas_call, weights as the fori_loop carry — see the section
comment below).  `--pallas` dispatches by task family
(runtime/worker._solver_fns).

Chip record (TPU v5e, jax 0.9.0, PR 21): every variant here compiles
under Mosaic and agrees with the XLA solver at default matmul precision
(CHANGES.md PR 21 has the per-variant line).  Speed against the XLA
path: not measured on the chip — ROADMAP S4 decides which kernels stay.
The default path is XLA (`--pallas` opts in).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kafka_ps_tpu.compress.slab import QuantizedSlab
from kafka_ps_tpu.models import logreg
from kafka_ps_tpu.utils.config import ModelConfig

LANES = 128          # last-dim tile width; class axis padded up to this
# The selectors' estimate of one working set, each operand counted once.
_VMEM_BYTE_BUDGET = 12 * 1024 * 1024
# What Mosaic may actually allocate: the grid pipeline double-buffers
# every blocked operand (the constant-index weight blocks included), so
# a working set the selectors admit can need ~2x the budget plus
# compiler temporaries — past the 16 MiB Mosaic scopes by default.  The
# v5e core has 128 MiB of VMEM.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)

# Trace counters, bumped INSIDE traced bodies (the compress/slab.py
# TRACE_COUNTS pattern): which kernel program a caller's jit actually
# built.  Tests and chip_smoke.py read them to prove a kernel — and
# which one — ran where `--pallas` was asked for.
TRACE_COUNTS = {"resident": 0, "streaming": 0, "batched": 0}


class PallasUnavailable(ValueError):
    """No Pallas kernel can run this call; the message carries the
    shape and the reason.  Raised instead of training on the XLA
    solver under a `--pallas` label."""


def _require_backend(interpret: bool) -> None:
    if not interpret and jax.default_backend() != "tpu":
        raise PallasUnavailable(
            "compiled Mosaic kernels need a TPU backend, found "
            f"{jax.default_backend()!r} (the Pallas interpreter runs "
            "only where a test asks for it by name)")


def _slab_kind(x) -> str:
    """Storage form of a device slab (compress/slab.py): "f32", "bf16"
    or "int8" (QuantizedSlab).  Decided at trace time — one compiled
    program per storage form."""
    if isinstance(x, QuantizedSlab):
        return "int8"
    if x.dtype == jnp.bfloat16:
        return "bf16"
    return "f32"


_X_BYTES = {"f32": 4, "bf16": 2, "int8": 1}


def _slab_shape(x) -> tuple[int, int]:
    """(batch, num_features) of the trailing slab dims, any storage."""
    a = x.q if isinstance(x, QuantizedSlab) else x
    return a.shape[-2], a.shape[-1]


def _kernel(x_ref, y_ref, mask_ref, w0_ref, b0_ref,
            dw_ref, db_ref, loss_ref,
            *, k: int, lr: float, num_rows: int):
    x = x_ref[:]                       # [B, F]
    y = y_ref[:]                       # [B, 1] int32
    mask = mask_ref[:]                 # [B, 1] f32
    batch = x.shape[0]

    class_ids = jax.lax.broadcasted_iota(jnp.int32, (batch, LANES), 1)
    valid = (class_ids < num_rows).astype(jnp.float32)
    # mask the onehot with the valid-class predicate: an out-of-range
    # label (y >= num_rows) yields an all-zero row, so it contributes
    # zero loss — matching jax.nn.one_hot in models/logreg.grad_loss
    # (otherwise it would hit a -1e30-masked padded class and blow the
    # reported loss up to ~1e30)
    onehot = (class_ids == y).astype(jnp.float32) * valid  # [B, C8]
    neg_inf_pad = (1.0 - valid) * (-1e30)                  # kill padded classes
    denom = jnp.maximum(jnp.sum(mask), 1.0)

    def logp_of(w, b):
        logits = jax.lax.dot_general(
            x, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) + b + neg_inf_pad
        return jax.nn.log_softmax(logits, axis=-1)

    def body(_, carry):
        w, b = carry
        logp = logp_of(w, b)
        g = (jnp.exp(logp) - onehot) * (mask / denom)      # [B, C8]
        gw = jax.lax.dot_general(
            g, x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [C8, F]
        return w - lr * gw, b - lr * jnp.sum(g, axis=0, keepdims=True)

    w, b = jax.lax.fori_loop(0, k, body, (w0_ref[:], b0_ref[:]))

    logp = logp_of(w, b)
    nll = -jnp.sum(logp * onehot, axis=-1, keepdims=True)  # [B, 1]
    loss_ref[0, 0] = jnp.sum(nll * mask) / denom
    dw_ref[:] = w - w0_ref[:]
    db_ref[:] = b - b0_ref[:]


def _pad_batch(x, y, mask):
    """Pad the batch to a sublane multiple (min f32 tile is 8 rows);
    padded rows carry mask 0 so they contribute nothing."""
    pad_b = (-x.shape[0]) % 8
    if pad_b:
        x = jnp.pad(x, ((0, pad_b), (0, 0)))
        y = jnp.pad(y, ((0, pad_b),))
        mask = jnp.pad(mask, ((0, pad_b),))
    return x, y, mask


def fits_in_vmem(batch: int, num_features: int) -> bool:
    """Whole-problem VMEM residency estimate: x, the class-padded weight
    tensors (w0/dw + loop carry + gradient), and the [B, LANES]
    activations, all f32."""
    weight_like = 4 * LANES * num_features      # w0, dw, carry w, grad w
    act_like = 3 * batch * LANES                # onehot, logp, g
    total = batch * num_features + weight_like + act_like
    return total * 4 <= _VMEM_BYTE_BUDGET


def select_program(task_name: str, cfg: ModelConfig, batch: int,
                   kind: str) -> tuple[str, int | None]:
    """Which single-member kernel serves a [batch, F] slab stored as
    `kind`: ("resident", None) or ("streaming", tile_rows).  Raises
    PallasUnavailable when neither fits — the one place the shape
    rules live, shared by the kernels, the start-up line and
    [status]."""
    f = cfg.num_features
    if task_name == "mlp":
        shape = (f"batch={batch}, features={f}, "
                 f"hidden={cfg.hidden_dim}, slab={kind}")
        resident = kind == "f32" and mlp_fits_in_vmem(batch, f,
                                                      cfg.hidden_dim)
        tile = mlp_stream_tile(batch, f, cfg.hidden_dim, kind)
    else:
        shape = f"batch={batch}, features={f}, slab={kind}"
        resident = kind == "f32" and fits_in_vmem(batch, f)
        tile = stream_tile(batch, f, kind)
    if resident:
        return "resident", None
    if tile is not None:
        return "streaming", tile
    why = ("the streaming kernels need num_features to be a multiple of "
           f"{LANES}" if f % LANES else
           "the resident weight set plus one 32-row tile exceeds the "
           f"{_VMEM_BYTE_BUDGET >> 20} MB VMEM budget")
    raise PallasUnavailable(
        f"no pallas {task_name} kernel fits ({shape}): {why}")


def program_name(task_name: str, cfg: ModelConfig, batch: int, kind: str,
                 *, interpret: bool = False) -> str:
    """Host-side answer to "what will --pallas run here?" — backend
    check plus `select_program`; raises PallasUnavailable with the
    reason when the answer is "nothing"."""
    _require_backend(interpret)
    return select_program(task_name, cfg, batch, kind)[0]


def _select(task_name: str, cfg: ModelConfig, x,
            interpret: bool) -> tuple[str, int | None]:
    """The kernels' own entry check: backend, then `select_program` on
    the slab (or stacked member slabs) they were handed."""
    _require_backend(interpret)
    return select_program(task_name, cfg, _slab_shape(x)[0], _slab_kind(x))


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def local_update(theta: jax.Array, x: jax.Array, y: jax.Array,
                 mask: jax.Array, *, cfg: ModelConfig,
                 interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Drop-in replacement for models/logreg.local_update: k local solver
    steps on the buffer → (delta, loss at the updated parameters).

    `interpret=True` runs the kernel in the Pallas interpreter (CPU
    correctness tests).  Dispatch (`select_program`): an f32 slab that
    fits whole in VMEM takes this resident kernel; anything else that a
    streaming tile fits — oversize f32 slabs, bf16/int8 slab storage —
    takes the tiled double-buffered kernel below (`_stream_update`);
    when even one tile plus the weight set exceeds the budget, or the
    backend is not a TPU and interpret mode was not asked for, it
    raises PallasUnavailable.
    """
    program, tile = _select("logreg", cfg, x, interpret)
    num_features = _slab_shape(x)[1]
    if program == "streaming":
        return _stream_update(theta, x, y, mask, cfg=cfg, tile=tile,
                              interpret=interpret)
    TRACE_COUNTS["resident"] += 1

    params = logreg.unflatten(theta, cfg)
    w0 = jnp.zeros((LANES, num_features), jnp.float32
                   ).at[:cfg.num_rows].set(params.weights)
    b0 = jnp.zeros((1, LANES), jnp.float32
                   ).at[0, :cfg.num_rows].set(params.intercept)

    x, y, mask = _pad_batch(x, y, mask)

    kernel = functools.partial(_kernel, k=cfg.num_max_iter,
                               lr=cfg.local_learning_rate,
                               num_rows=cfg.num_rows)
    dw, db, loss = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((LANES, num_features), jnp.float32),
            jax.ShapeDtypeStruct((1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 5,
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(x.astype(jnp.float32),
      y.astype(jnp.int32).reshape(-1, 1),
      mask.astype(jnp.float32).reshape(-1, 1),
      w0, b0)

    delta = logreg.LogRegParams(weights=dw[:cfg.num_rows],
                                intercept=db[0, :cfg.num_rows]).flat
    return delta, loss[0, 0]


# -- MLP family (models/mlp.py): k-step fused update in VMEM -----------------
# Same design as the logreg kernel, one layer deeper: the whole
# forward + hand-derived backward of the one-hidden-layer net lives in
# a single pallas_call, weights as the fori_loop carry —
#     pre   = x @ W1.T + b1          # MXU [B,F]@[F,H8]
#     hid   = relu(pre)
#     logit = hid @ W2.T + b2        # MXU [B,H8]@[H8,C8]
#     g     = (softmax - onehot) * mask/denom
#     dW2   = g.T @ hid;  dh = (g @ W2) * (pre > 0)
#     dW1   = dh.T @ x;   db = column sums
# The hidden axis is padded to a lane multiple; padded units carry
# zero weights, pre = 0, and relu'(0) = 0 (matching jax.nn.relu's
# gradient), so they stay exactly zero through every step.


def mlp_fits_in_vmem(batch: int, num_features: int, hidden: int) -> bool:
    """Whole-problem VMEM residency: x, three W1-shaped tensors
    (initial/carry/grad), three [B,H8] activations (pre, hid, dh),
    three [B,LANES] class activations, plus the small W2-shaped set."""
    h8 = hidden + (-hidden) % LANES
    total = (batch * num_features          # x
             + 3 * h8 * num_features      # w1 triple
             + 3 * batch * h8             # pre, hid, dh
             + 3 * batch * LANES          # onehot, logp, g
             + 3 * LANES * h8)            # w2 triple
    return total * 4 <= _VMEM_BYTE_BUDGET


def _mlp_kernel(x_ref, y_ref, mask_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                dw1_ref, db1_ref, dw2_ref, db2_ref, loss_ref,
                *, k: int, lr: float, num_rows: int):
    x = x_ref[:]                       # [B, F]
    y = y_ref[:]                       # [B, 1] int32
    mask = mask_ref[:]                 # [B, 1] f32
    batch = x.shape[0]

    class_ids = jax.lax.broadcasted_iota(jnp.int32, (batch, LANES), 1)
    valid = (class_ids < num_rows).astype(jnp.float32)
    onehot = (class_ids == y).astype(jnp.float32) * valid
    neg_inf_pad = (1.0 - valid) * (-1e30)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    # Out-of-range labels: jax.nn.one_hot yields an all-zero row, and
    # jax.grad of the one-hot CE (models/mlp.loss_onehot — the XLA
    # path this kernel must match) then gives that row ZERO gradient.
    # The closed-form (softmax - onehot) does NOT (it leaves softmax),
    # so the row-validity factor kills it explicitly.  NOTE this
    # deliberately differs from the logreg kernel, whose XLA path uses
    # the closed form itself (logreg.grad_loss) and keeps the term.
    row_valid = jnp.sum(onehot, axis=-1, keepdims=True)     # [B, 1]

    def forward(w1, b1, w2, b2):
        pre = jax.lax.dot_general(
            x, w1, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) + b1        # [B, H8]
        hid = jnp.maximum(pre, 0.0)
        logits = jax.lax.dot_general(
            hid, w2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) + b2 + neg_inf_pad
        return pre, hid, jax.nn.log_softmax(logits, axis=-1)

    def body(_, carry):
        w1, b1, w2, b2 = carry
        pre, hid, logp = forward(w1, b1, w2, b2)
        g = (jnp.exp(logp) - onehot) * (mask * row_valid / denom)
        dw2 = jax.lax.dot_general(
            g, hid, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [C8, H8]
        db2 = jnp.sum(g, axis=0, keepdims=True)             # [1, C8]
        dh = jax.lax.dot_general(
            g, w2, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [B, H8]
        dh = dh * (pre > 0.0).astype(jnp.float32)           # relu'(0)=0
        dw1 = jax.lax.dot_general(
            dh, x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [H8, F]
        db1 = jnp.sum(dh, axis=0, keepdims=True)            # [1, H8]
        return (w1 - lr * dw1, b1 - lr * db1,
                w2 - lr * dw2, b2 - lr * db2)

    w1, b1, w2, b2 = jax.lax.fori_loop(
        0, k, body, (w1_ref[:], b1_ref[:], w2_ref[:], b2_ref[:]))

    _, _, logp = forward(w1, b1, w2, b2)
    nll = -jnp.sum(logp * onehot, axis=-1, keepdims=True)   # [B, 1]
    loss_ref[0, 0] = jnp.sum(nll * mask) / denom
    dw1_ref[:] = w1 - w1_ref[:]
    db1_ref[:] = b1 - b1_ref[:]
    dw2_ref[:] = w2 - w2_ref[:]
    db2_ref[:] = b2 - b2_ref[:]


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def mlp_local_update(theta: jax.Array, x: jax.Array, y: jax.Array,
                     mask: jax.Array, *, cfg: ModelConfig,
                     interpret: bool = False
                     ) -> tuple[jax.Array, jax.Array]:
    """Drop-in replacement for MLPTask.local_update (models/mlp.py):
    k full-batch GD steps on the buffer → (delta, loss at the updated
    parameters).  Dispatch rules match `local_update`: resident kernel
    for whole-VMEM f32 slabs, streaming kernel for oversize or
    reduced-precision slabs, PallasUnavailable otherwise."""
    from kafka_ps_tpu.models import mlp as mlp_mod

    program, tile = _select("mlp", cfg, x, interpret)
    num_features = _slab_shape(x)[1]
    hidden = cfg.hidden_dim
    if program == "streaming":
        return _mlp_stream_update(theta, x, y, mask, cfg=cfg, tile=tile,
                                  interpret=interpret)
    TRACE_COUNTS["resident"] += 1

    params = mlp_mod.unflatten(theta, cfg)
    h8 = hidden + (-hidden) % LANES
    w1 = jnp.zeros((h8, num_features), jnp.float32
                   ).at[:hidden].set(params.w1)
    b1 = jnp.zeros((1, h8), jnp.float32).at[0, :hidden].set(params.b1)
    w2 = jnp.zeros((LANES, h8), jnp.float32
                   ).at[:cfg.num_rows, :hidden].set(params.w2)
    b2 = jnp.zeros((1, LANES), jnp.float32
                   ).at[0, :cfg.num_rows].set(params.b2)

    x, y, mask = _pad_batch(x, y, mask)

    kernel = functools.partial(_mlp_kernel, k=cfg.num_max_iter,
                               lr=cfg.local_learning_rate,
                               num_rows=cfg.num_rows)
    dw1, db1, dw2, db2, loss = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((h8, num_features), jnp.float32),
            jax.ShapeDtypeStruct((1, h8), jnp.float32),
            jax.ShapeDtypeStruct((LANES, h8), jnp.float32),
            jax.ShapeDtypeStruct((1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 7,
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(x.astype(jnp.float32),
      y.astype(jnp.int32).reshape(-1, 1),
      mask.astype(jnp.float32).reshape(-1, 1),
      w1, b1, w2, b2)

    delta = mlp_mod.flatten(mlp_mod.MLPParams(
        w1=dw1[:hidden], b1=db1[0, :hidden],
        w2=dw2[:cfg.num_rows, :hidden], b2=db2[0, :cfg.num_rows]))
    return delta, loss[0, 0]


# -- streaming kernels: tiled, double-buffered VMEM (docs/PERFORMANCE.md) ----
# Slabs too large to sit whole in VMEM — and every reduced-precision
# slab (bf16/int8 storage, compress/slab.py) — stream through on-chip
# memory.  The grid is
# (k_solver_steps + 1, batch_tiles): the LAST axis iterates fastest, so
# each solver step walks every batch tile before the step index
# advances, and Pallas double-buffers the blocked x/y/mask specs (the
# next tile's DMA overlaps this tile's compute).  Weights live in VMEM
# scratch for the WHOLE call — per solver step the per-tile gradient
# contributions accumulate into scratch and apply once at the step's
# final tile; grid step (k, t) is the loss pass over the updated
# weights; outputs are written only at the very last grid step (the
# revisited-output accumulator pattern).  Reduced-precision decode
# happens per tile, in-kernel, right after the DMA — so the bytes that
# cross HBM->VMEM are the *stored* bytes (2 or ~1 per element), which
# is the whole point of --slab-dtype.
#
# Tile rows are multiples of 32 (the int8 min sublane tile; also
# satisfies bf16's 16 and f32's 8) and the feature axis must be a lane
# multiple; the chooser picks the largest tile whose working set fits
# the budget.  When even the weight set + one minimal tile can't fit,
# streaming is impossible and `select_program` raises.

_STREAM_TILES = (512, 256, 128, 64, 32)


def _stream_bytes(tile: int, num_features: int, kind: str) -> int:
    """Streaming working set: the resident weight set (w0 + carry +
    grad accumulator + dw output), double-buffered x/y/mask tiles in
    their STORED dtype (+ the int8 per-row scales), and the [tile,
    LANES] class activations."""
    weight_set = 4 * LANES * num_features * 4
    x_tile = num_features * _X_BYTES[kind] + (4 if kind == "int8" else 0)
    return (weight_set + 2 * tile * x_tile + 2 * tile * 8
            + 3 * tile * LANES * 4)


def stream_tile(batch: int, num_features: int, kind: str) -> int | None:
    """Largest usable batch-tile height, or None if streaming can't fit
    (weight set alone blows the budget) or the feature axis isn't a
    lane multiple (Mosaic tiling constraint)."""
    if num_features % LANES:
        return None
    bp = batch + (-batch) % 32
    for t in _STREAM_TILES:
        if (t <= max(bp, 32)
                and _stream_bytes(t, num_features, kind)
                <= _VMEM_BYTE_BUDGET):
            return t
    return None


def _pad_rows(x, y, mask, multiple: int):
    """Pad the batch axis to a tile multiple — padded rows carry mask 0
    (and, for QuantizedSlab, zero rows/scales), so they contribute
    nothing; handles every slab storage form."""
    batch = _slab_shape(x)[0]
    pad_b = (-batch) % multiple
    if not pad_b:
        return x, y, mask
    if isinstance(x, QuantizedSlab):
        x = QuantizedSlab(q=jnp.pad(x.q, ((0, pad_b), (0, 0))),
                          scale=jnp.pad(x.scale, ((0, pad_b), (0, 0))))
    else:
        x = jnp.pad(x, ((0, pad_b), (0, 0)))
    return x, jnp.pad(y, ((0, pad_b),)), jnp.pad(mask, ((0, pad_b),))


def _stream_core(x, y, mask, w0_ref, b0_ref, denom_ref,
                 dw_ref, db_ref, loss_ref,
                 w_scr, b_scr, gw_scr, gb_scr, loss_scr,
                 *, k: int, lr: float, num_rows: int, ntiles: int):
    """Grid-step body shared by the f32/bf16 and int8 wrappers; `x` is
    the already-decoded f32 tile."""
    s = pl.program_id(0)        # solver step; s == k is the loss pass
    t = pl.program_id(1)        # batch tile
    tile = x.shape[0]

    @pl.when(jnp.logical_and(s == 0, t == 0))
    def _init():
        w_scr[:] = w0_ref[:]
        b_scr[:] = b0_ref[:]

    @pl.when(t == 0)
    def _zero():
        gw_scr[:] = jnp.zeros(gw_scr.shape, jnp.float32)
        gb_scr[:] = jnp.zeros(gb_scr.shape, jnp.float32)
        loss_scr[0, 0] = 0.0

    class_ids = jax.lax.broadcasted_iota(jnp.int32, (tile, LANES), 1)
    valid = (class_ids < num_rows).astype(jnp.float32)
    onehot = (class_ids == y).astype(jnp.float32) * valid
    neg_inf_pad = (1.0 - valid) * (-1e30)
    denom = denom_ref[0, 0]

    logits = jax.lax.dot_general(
        x, w_scr[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) + b_scr[:] + neg_inf_pad
    logp = jax.nn.log_softmax(logits, axis=-1)

    @pl.when(s < k)
    def _grad():
        g = (jnp.exp(logp) - onehot) * (mask / denom)
        gw_scr[:] += jax.lax.dot_general(
            g, x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        gb_scr[:] += jnp.sum(g, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(s < k, t == ntiles - 1))
    def _apply():
        w_scr[:] = w_scr[:] - lr * gw_scr[:]
        b_scr[:] = b_scr[:] - lr * gb_scr[:]

    @pl.when(s == k)
    def _loss():
        nll = -jnp.sum(logp * onehot, axis=-1, keepdims=True)
        loss_scr[0, 0] += jnp.sum(nll * mask)

    @pl.when(jnp.logical_and(s == k, t == ntiles - 1))
    def _emit():
        dw_ref[:] = w_scr[:] - w0_ref[:]
        db_ref[:] = b_scr[:] - b0_ref[:]
        loss_ref[0, 0] = loss_scr[0, 0] / denom


def _stream_kernel(x_ref, y_ref, mask_ref, w0_ref, b0_ref, denom_ref,
                   dw_ref, db_ref, loss_ref,
                   w_scr, b_scr, gw_scr, gb_scr, loss_scr,
                   *, k, lr, num_rows, ntiles):
    _stream_core(x_ref[:].astype(jnp.float32), y_ref[:], mask_ref[:],
                 w0_ref, b0_ref, denom_ref, dw_ref, db_ref, loss_ref,
                 w_scr, b_scr, gw_scr, gb_scr, loss_scr,
                 k=k, lr=lr, num_rows=num_rows, ntiles=ntiles)


def _stream_kernel_q(q_ref, scale_ref, y_ref, mask_ref, w0_ref, b0_ref,
                     denom_ref, dw_ref, db_ref, loss_ref,
                     w_scr, b_scr, gw_scr, gb_scr, loss_scr,
                     *, k, lr, num_rows, ntiles):
    # per-row scales broadcast over the lane axis — decode costs one
    # VPU multiply per element, paid AFTER the 1-byte DMA
    x = q_ref[:].astype(jnp.float32) * scale_ref[:]
    _stream_core(x, y_ref[:], mask_ref[:],
                 w0_ref, b0_ref, denom_ref, dw_ref, db_ref, loss_ref,
                 w_scr, b_scr, gw_scr, gb_scr, loss_scr,
                 k=k, lr=lr, num_rows=num_rows, ntiles=ntiles)


def _stream_update(theta, x, y, mask, *, cfg: ModelConfig, tile: int,
                   interpret: bool):
    """Tiled logreg solver call — same contract as the resident kernel,
    any slab storage form."""
    TRACE_COUNTS["streaming"] += 1
    num_features = _slab_shape(x)[1]
    kind = _slab_kind(x)
    # denom over the UNPADDED mask (padding adds zeros — equal either
    # way; computed here once instead of per grid step)
    denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)),
                        1.0).reshape(1, 1)
    x, y, mask = _pad_rows(x, y, mask, tile)
    ntiles = _slab_shape(x)[0] // tile

    params = logreg.unflatten(theta, cfg)
    w0 = jnp.zeros((LANES, num_features), jnp.float32
                   ).at[:cfg.num_rows].set(params.weights)
    b0 = jnp.zeros((1, LANES), jnp.float32
                   ).at[0, :cfg.num_rows].set(params.intercept)

    def tmap(s, t):
        return (t, 0)

    def wmap(s, t):
        return (0, 0)

    def tspec(width):
        return pl.BlockSpec((tile, width), tmap, memory_space=pltpu.VMEM)

    y2 = y.astype(jnp.int32).reshape(-1, 1)
    m2 = mask.astype(jnp.float32).reshape(-1, 1)
    if kind == "int8":
        body, operands = _stream_kernel_q, (x.q, x.scale, y2, m2)
        in_specs = [tspec(num_features), tspec(1), tspec(1), tspec(1)]
    else:
        body, operands = _stream_kernel, (x, y2, m2)
        in_specs = [tspec(num_features), tspec(1), tspec(1)]
    in_specs += [
        pl.BlockSpec((LANES, num_features), wmap, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, LANES), wmap, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1), wmap, memory_space=pltpu.SMEM),
    ]

    kernel = functools.partial(body, k=cfg.num_max_iter,
                               lr=cfg.local_learning_rate,
                               num_rows=cfg.num_rows, ntiles=ntiles)
    # pscheck: disable=PS101 (traced only inside jit'd local_update, cached per (shape, dtype))
    dw, db, loss = pl.pallas_call(
        kernel,
        grid=(cfg.num_max_iter + 1, ntiles),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((LANES, num_features), wmap,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, LANES), wmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), wmap, memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((LANES, num_features), jnp.float32),
            jax.ShapeDtypeStruct((1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((LANES, num_features), jnp.float32),
            pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.VMEM((LANES, num_features), jnp.float32),
            pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.SMEM((1, 1), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*operands, w0, b0, denom)

    delta = logreg.LogRegParams(weights=dw[:cfg.num_rows],
                                intercept=db[0, :cfg.num_rows]).flat
    return delta, loss[0, 0]


def _mlp_stream_bytes(tile: int, num_features: int, h8: int,
                      kind: str) -> int:
    """MLP streaming working set: both weight sets resident ×4 (input,
    carry, grad accumulator, delta output), double-buffered tiles, and
    the per-tile hidden + class activations."""
    w1_set = 4 * h8 * num_features * 4
    w2_set = 4 * LANES * h8 * 4
    x_tile = num_features * _X_BYTES[kind] + (4 if kind == "int8" else 0)
    return (w1_set + w2_set + 2 * tile * x_tile + 2 * tile * 8
            + 3 * tile * h8 * 4 + 3 * tile * LANES * 4)


def mlp_stream_tile(batch: int, num_features: int, hidden: int,
                    kind: str) -> int | None:
    if num_features % LANES:
        return None
    h8 = hidden + (-hidden) % LANES
    bp = batch + (-batch) % 32
    for t in _STREAM_TILES:
        if (t <= max(bp, 32)
                and _mlp_stream_bytes(t, num_features, h8, kind)
                <= _VMEM_BYTE_BUDGET):
            return t
    return None


def _mlp_stream_core(x, y, mask,
                     w10_ref, b10_ref, w20_ref, b20_ref, denom_ref,
                     dw1_ref, db1_ref, dw2_ref, db2_ref, loss_ref,
                     w1_scr, b1_scr, w2_scr, b2_scr,
                     gw1_scr, gb1_scr, gw2_scr, gb2_scr, loss_scr,
                     *, k: int, lr: float, num_rows: int, ntiles: int):
    """MLP grid-step body: the _mlp_kernel math per tile, weight state
    and gradient accumulators in scratch across the grid (same
    row_valid factor — the XLA path it must match is jax.grad-based,
    see the note in _mlp_kernel)."""
    s = pl.program_id(0)
    t = pl.program_id(1)
    tile = x.shape[0]

    @pl.when(jnp.logical_and(s == 0, t == 0))
    def _init():
        w1_scr[:] = w10_ref[:]
        b1_scr[:] = b10_ref[:]
        w2_scr[:] = w20_ref[:]
        b2_scr[:] = b20_ref[:]

    @pl.when(t == 0)
    def _zero():
        gw1_scr[:] = jnp.zeros(gw1_scr.shape, jnp.float32)
        gb1_scr[:] = jnp.zeros(gb1_scr.shape, jnp.float32)
        gw2_scr[:] = jnp.zeros(gw2_scr.shape, jnp.float32)
        gb2_scr[:] = jnp.zeros(gb2_scr.shape, jnp.float32)
        loss_scr[0, 0] = 0.0

    class_ids = jax.lax.broadcasted_iota(jnp.int32, (tile, LANES), 1)
    valid = (class_ids < num_rows).astype(jnp.float32)
    onehot = (class_ids == y).astype(jnp.float32) * valid
    neg_inf_pad = (1.0 - valid) * (-1e30)
    row_valid = jnp.sum(onehot, axis=-1, keepdims=True)     # [T, 1]
    denom = denom_ref[0, 0]

    pre = jax.lax.dot_general(
        x, w1_scr[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) + b1_scr[:]     # [T, H8]
    hid = jnp.maximum(pre, 0.0)
    logits = jax.lax.dot_general(
        hid, w2_scr[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) + b2_scr[:] + neg_inf_pad
    logp = jax.nn.log_softmax(logits, axis=-1)

    @pl.when(s < k)
    def _grad():
        g = (jnp.exp(logp) - onehot) * (mask * row_valid / denom)
        gw2_scr[:] += jax.lax.dot_general(
            g, hid, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [C8, H8]
        gb2_scr[:] += jnp.sum(g, axis=0, keepdims=True)
        dh = jax.lax.dot_general(
            g, w2_scr[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [T, H8]
        dh = dh * (pre > 0.0).astype(jnp.float32)
        gw1_scr[:] += jax.lax.dot_general(
            dh, x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [H8, F]
        gb1_scr[:] += jnp.sum(dh, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(s < k, t == ntiles - 1))
    def _apply():
        w1_scr[:] = w1_scr[:] - lr * gw1_scr[:]
        b1_scr[:] = b1_scr[:] - lr * gb1_scr[:]
        w2_scr[:] = w2_scr[:] - lr * gw2_scr[:]
        b2_scr[:] = b2_scr[:] - lr * gb2_scr[:]

    @pl.when(s == k)
    def _loss():
        nll = -jnp.sum(logp * onehot, axis=-1, keepdims=True)
        loss_scr[0, 0] += jnp.sum(nll * mask)

    @pl.when(jnp.logical_and(s == k, t == ntiles - 1))
    def _emit():
        dw1_ref[:] = w1_scr[:] - w10_ref[:]
        db1_ref[:] = b1_scr[:] - b10_ref[:]
        dw2_ref[:] = w2_scr[:] - w20_ref[:]
        db2_ref[:] = b2_scr[:] - b20_ref[:]
        loss_ref[0, 0] = loss_scr[0, 0] / denom


def _mlp_stream_kernel(x_ref, y_ref, mask_ref, *rest, k, lr, num_rows,
                       ntiles):
    _mlp_stream_core(x_ref[:].astype(jnp.float32), y_ref[:], mask_ref[:],
                     *rest, k=k, lr=lr, num_rows=num_rows, ntiles=ntiles)


def _mlp_stream_kernel_q(q_ref, scale_ref, y_ref, mask_ref, *rest, k, lr,
                         num_rows, ntiles):
    x = q_ref[:].astype(jnp.float32) * scale_ref[:]
    _mlp_stream_core(x, y_ref[:], mask_ref[:], *rest,
                     k=k, lr=lr, num_rows=num_rows, ntiles=ntiles)


def _mlp_stream_update(theta, x, y, mask, *, cfg: ModelConfig, tile: int,
                       interpret: bool):
    from kafka_ps_tpu.models import mlp as mlp_mod

    TRACE_COUNTS["streaming"] += 1
    num_features = _slab_shape(x)[1]
    kind = _slab_kind(x)
    hidden = cfg.hidden_dim
    h8 = hidden + (-hidden) % LANES
    denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)),
                        1.0).reshape(1, 1)
    x, y, mask = _pad_rows(x, y, mask, tile)
    ntiles = _slab_shape(x)[0] // tile

    params = mlp_mod.unflatten(theta, cfg)
    w1 = jnp.zeros((h8, num_features), jnp.float32
                   ).at[:hidden].set(params.w1)
    b1 = jnp.zeros((1, h8), jnp.float32).at[0, :hidden].set(params.b1)
    w2 = jnp.zeros((LANES, h8), jnp.float32
                   ).at[:cfg.num_rows, :hidden].set(params.w2)
    b2 = jnp.zeros((1, LANES), jnp.float32
                   ).at[0, :cfg.num_rows].set(params.b2)

    def tmap(s, t):
        return (t, 0)

    def wmap(s, t):
        return (0, 0)

    def tspec(width):
        return pl.BlockSpec((tile, width), tmap, memory_space=pltpu.VMEM)

    def wspec(a, b):
        return pl.BlockSpec((a, b), wmap, memory_space=pltpu.VMEM)

    y2 = y.astype(jnp.int32).reshape(-1, 1)
    m2 = mask.astype(jnp.float32).reshape(-1, 1)
    if kind == "int8":
        body, operands = _mlp_stream_kernel_q, (x.q, x.scale, y2, m2)
        in_specs = [tspec(num_features), tspec(1), tspec(1), tspec(1)]
    else:
        body, operands = _mlp_stream_kernel, (x, y2, m2)
        in_specs = [tspec(num_features), tspec(1), tspec(1)]
    in_specs += [
        wspec(h8, num_features), wspec(1, h8),
        wspec(LANES, h8), wspec(1, LANES),
        pl.BlockSpec((1, 1), wmap, memory_space=pltpu.SMEM),
    ]

    kernel = functools.partial(body, k=cfg.num_max_iter,
                               lr=cfg.local_learning_rate,
                               num_rows=cfg.num_rows, ntiles=ntiles)
    # pscheck: disable=PS101 (traced only inside jit'd mlp_local_update, cached per (shape, dtype))
    dw1, db1, dw2, db2, loss = pl.pallas_call(
        kernel,
        grid=(cfg.num_max_iter + 1, ntiles),
        in_specs=in_specs,
        out_specs=(
            wspec(h8, num_features), wspec(1, h8),
            wspec(LANES, h8), wspec(1, LANES),
            pl.BlockSpec((1, 1), wmap, memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((h8, num_features), jnp.float32),
            jax.ShapeDtypeStruct((1, h8), jnp.float32),
            jax.ShapeDtypeStruct((LANES, h8), jnp.float32),
            jax.ShapeDtypeStruct((1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((h8, num_features), jnp.float32),
            pltpu.VMEM((1, h8), jnp.float32),
            pltpu.VMEM((LANES, h8), jnp.float32),
            pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.VMEM((h8, num_features), jnp.float32),
            pltpu.VMEM((1, h8), jnp.float32),
            pltpu.VMEM((LANES, h8), jnp.float32),
            pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.SMEM((1, 1), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*operands, w1, b1, w2, b2, denom)

    delta = mlp_mod.flatten(mlp_mod.MLPParams(
        w1=dw1[:hidden], b1=db1[0, :hidden],
        w2=dw2[:cfg.num_rows, :hidden], b2=db2[0, :cfg.num_rows]))
    return delta, loss[0, 0]


# -- batched (gang) entries: grid over the worker axis -----------------------
# One pallas_call runs a whole gang release set (runtime/gang.py): the
# grid's single axis walks the k gang members, each grid instance
# getting one member's (theta, slab) block via BlockSpecs whose leading
# `None` dimension squeezes the worker axis away — so the instance body
# IS the single-worker kernel, unchanged, and produces bit-identical
# per-member results by construction.  Versus k separate pallas_calls
# this costs one dispatch instead of k; the per-instance working set is
# one member's (double-buffered across grid steps, hence the raised
# compiler limit), so the same fits_in_vmem gates apply.  Member slabs
# the resident kernel does not admit (oversize f32, bf16/int8 storage)
# run the streaming kernel once per member INSIDE the same jit — still
# one dispatch, k kernel launches.


def _looped(single, thetas, xs, ys, masks):
    """k single-member kernel calls inside the caller's jit."""
    k = ys.shape[0]
    outs = [single(thetas[i], jax.tree.map(lambda a, i=i: a[i], xs),
                   ys[i], masks[i]) for i in range(k)]
    return (jnp.stack([d for d, _ in outs]),
            jnp.stack([loss for _, loss in outs]))


def _pad_batch_b(xs, ys, masks):
    """_pad_batch over stacked slabs: pad the BATCH axis (axis 1) of
    [k, B, ...] inputs to a sublane multiple; padded rows carry mask 0."""
    pad_b = (-xs.shape[1]) % 8
    if pad_b:
        xs = jnp.pad(xs, ((0, 0), (0, pad_b), (0, 0)))
        ys = jnp.pad(ys, ((0, 0), (0, pad_b)))
        masks = jnp.pad(masks, ((0, 0), (0, pad_b)))
    return xs, ys, masks


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def local_update_batched(thetas: jax.Array, xs: jax.Array, ys: jax.Array,
                         masks: jax.Array, *, cfg: ModelConfig,
                         interpret: bool = False
                         ) -> tuple[jax.Array, jax.Array]:
    """k independent logreg local updates as ONE device step:
    thetas [k, P], xs [k, B, F], ys [k, B], masks [k, B] →
    (deltas [k, P], losses [k]).  Row i equals
    local_update(thetas[i], xs[i], ys[i], masks[i]) — the grid
    instance runs the identical kernel body on the identical block.
    Selection is `select_program` on the per-member shape: resident →
    the grid kernel below; streaming (oversize or bf16/int8 member
    slabs, stacked componentwise by the gang's tree-stack) → the
    streaming kernel per member; neither → PallasUnavailable."""
    k = ys.shape[0]
    num_features = _slab_shape(xs)[1]
    program, tile = _select("logreg", cfg, xs, interpret)
    if program == "streaming":
        return _looped(
            functools.partial(_stream_update, cfg=cfg, tile=tile,
                              interpret=interpret),
            thetas, xs, ys, masks)
    TRACE_COUNTS["batched"] += 1

    def pack(theta):
        params = logreg.unflatten(theta, cfg)
        w0 = jnp.zeros((LANES, num_features), jnp.float32
                       ).at[:cfg.num_rows].set(params.weights)
        b0 = jnp.zeros((1, LANES), jnp.float32
                       ).at[0, :cfg.num_rows].set(params.intercept)
        return w0, b0

    w0s, b0s = jax.vmap(pack)(thetas)          # [k,LANES,F], [k,1,LANES]
    xs, ys, masks = _pad_batch_b(xs, ys, masks)
    batch_p = xs.shape[1]

    kernel = functools.partial(_kernel, k=cfg.num_max_iter,
                               lr=cfg.local_learning_rate,
                               num_rows=cfg.num_rows)

    def member(i):                 # BlockSpec: member i's block, worker
        return (i, 0, 0)           # axis squeezed by the None dimension

    dws, dbs, losses = pl.pallas_call(
        kernel,
        grid=(k,),
        in_specs=[
            pl.BlockSpec((None, batch_p, num_features), member,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, batch_p, 1), member,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, batch_p, 1), member,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, LANES, num_features), member,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, 1, LANES), member,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((None, LANES, num_features), member,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, 1, LANES), member,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, 1, 1), member,
                         memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((k, LANES, num_features), jnp.float32),
            jax.ShapeDtypeStruct((k, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((k, 1, 1), jnp.float32),
        ),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(xs.astype(jnp.float32),
      ys.astype(jnp.int32)[..., None],
      masks.astype(jnp.float32)[..., None],
      w0s, b0s)

    deltas = jax.vmap(
        lambda dw, db: logreg.LogRegParams(
            weights=dw[:cfg.num_rows],
            intercept=db[0, :cfg.num_rows]).flat)(dws, dbs)
    return deltas, losses[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def mlp_local_update_batched(thetas: jax.Array, xs: jax.Array,
                             ys: jax.Array, masks: jax.Array, *,
                             cfg: ModelConfig,
                             interpret: bool = False
                             ) -> tuple[jax.Array, jax.Array]:
    """k independent MLP local updates as ONE device step — the MLP
    counterpart of `local_update_batched`, same selection; row i equals
    mlp_local_update(thetas[i], ...)."""
    from kafka_ps_tpu.models import mlp as mlp_mod

    k = ys.shape[0]
    num_features = _slab_shape(xs)[1]
    hidden = cfg.hidden_dim
    program, tile = _select("mlp", cfg, xs, interpret)
    if program == "streaming":
        return _looped(
            functools.partial(_mlp_stream_update, cfg=cfg, tile=tile,
                              interpret=interpret),
            thetas, xs, ys, masks)
    TRACE_COUNTS["batched"] += 1

    h8 = hidden + (-hidden) % LANES

    def pack(theta):
        params = mlp_mod.unflatten(theta, cfg)
        w1 = jnp.zeros((h8, num_features), jnp.float32
                       ).at[:hidden].set(params.w1)
        b1 = jnp.zeros((1, h8), jnp.float32).at[0, :hidden].set(params.b1)
        w2 = jnp.zeros((LANES, h8), jnp.float32
                       ).at[:cfg.num_rows, :hidden].set(params.w2)
        b2 = jnp.zeros((1, LANES), jnp.float32
                       ).at[0, :cfg.num_rows].set(params.b2)
        return w1, b1, w2, b2

    w1s, b1s, w2s, b2s = jax.vmap(pack)(thetas)
    xs, ys, masks = _pad_batch_b(xs, ys, masks)
    batch_p = xs.shape[1]

    kernel = functools.partial(_mlp_kernel, k=cfg.num_max_iter,
                               lr=cfg.local_learning_rate,
                               num_rows=cfg.num_rows)

    def member(i):
        return (i, 0, 0)

    def vspec(a, b):
        return pl.BlockSpec((None, a, b), member, memory_space=pltpu.VMEM)

    dw1s, db1s, dw2s, db2s, losses = pl.pallas_call(
        kernel,
        grid=(k,),
        in_specs=[
            vspec(batch_p, num_features),
            vspec(batch_p, 1),
            vspec(batch_p, 1),
            vspec(h8, num_features),
            vspec(1, h8),
            vspec(LANES, h8),
            vspec(1, LANES),
        ],
        out_specs=(
            vspec(h8, num_features),
            vspec(1, h8),
            vspec(LANES, h8),
            vspec(1, LANES),
            pl.BlockSpec((None, 1, 1), member, memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((k, h8, num_features), jnp.float32),
            jax.ShapeDtypeStruct((k, 1, h8), jnp.float32),
            jax.ShapeDtypeStruct((k, LANES, h8), jnp.float32),
            jax.ShapeDtypeStruct((k, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((k, 1, 1), jnp.float32),
        ),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(xs.astype(jnp.float32),
      ys.astype(jnp.int32)[..., None],
      masks.astype(jnp.float32)[..., None],
      w1s, b1s, w2s, b2s)

    deltas = jax.vmap(
        lambda dw1, db1, dw2, db2: mlp_mod.flatten(mlp_mod.MLPParams(
            w1=dw1[:hidden], b1=db1[0, :hidden],
            w2=dw2[:cfg.num_rows, :hidden],
            b2=db2[0, :cfg.num_rows])))(dw1s, db1s, dw2s, db2s)
    return deltas, losses[:, 0, 0]

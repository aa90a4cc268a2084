"""Least work and bytes of one worker update of the `granite-hybrid`
family, from the model file alone.

`update(cfg)` and `evaluation(cfg, test)` are what the roofline readers
call (`run.family.costs`); `ssm_scan` is what `ssm_scan_roofline_share`
sets against the device time under `kps.ssm.scan`, `attention_core`
what `window_attention_roofline_share` sets against the device time
under `kps.attn.full`, `dense_mlp` what `dense_mlp_roofline_share` sets
against the self time under `kps.mlp`.  What is counted is the LEAST a
chip could do for the mathematics: matrix products at 2*m*n*k — a
Mamba-2 layer's two (`W_in` 2048 x 8512 and `W_out` 4096 x 2048), the
attention layer's four, every layer's MLP (`W_1` 2048 x 16384 and `W_2`
8192 x 2048), the head's (the embedding transposed: one matrix, one
product); the state-space layer as its RECURRENCE, a step a token (5 *
P * N + 3 * P operations a head: the decay, the outer product and its
sum into the state, the state times C, the skip term) — not the chunked
algorithm's products, which are more; the attention core for the
(query, key) pairs INSIDE the mask only — the triangle — at 4 *
head_dim operations a pair a query head (the score and the value
product); a backward pass twice its forward and nothing recomputed, so
one update of k steps and the forward-only loss is 3k + 1 forward
passes; the embedding gather, norms, softmax, softplus, the gates, the
convolution's bias and the four multipliers are left out (lower order).
Bytes are the parameter plane's, at the float32 the configuration
states, as benchmark/costs.py counts a classifier's weights: a step
reads every parameter for its forward and for its backward pass and
writes the new ones (12 bytes a parameter a step; a gradient that is
never written is the least); the loss reads them once (4); the running
sum of deltas is read and written (8); the apply, a clock, reads the
shared parameters and the sum and writes them (12, shared by the
workers).  Activations are left out (lower order at these sizes),
except in `ssm_scan` and `attention_core`, whose bytes ARE activations,
and in `dense_mlp`, which counts a layer's rows beside its matrices.
The table of peaks is benchmark/peaks.py's.
"""

from __future__ import annotations

import json
import os

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
MAMBA, ATTENTION = "mamba", "attention"
# the program's pair counters (`attn.pairs_full`, `attn.block_pairs`)
# count in units of 1,024 pairs and `mlp.rows` in units of 1,024
# positions: the device's counters are int32 a dispatch
# (models/granite_hybrid.py PAIRS_UNIT, ROWS_UNIT)
PAIRS_UNIT = 1024
ROWS_UNIT = 1024


def model_file(cfg) -> dict:
    path = cfg.model.model_json
    with open(path if os.path.isabs(path) else os.path.join(_ROOT, path)) \
            as fh:
        return json.load(fh)


def layers(m: dict, kind: str) -> int:
    return m["layer_types"].count(kind)


def head_dim(m: dict) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def _inner(m: dict) -> int:
    return m["mamba_n_heads"] * m["mamba_d_head"]


def _conv_dim(m: dict) -> int:
    return _inner(m) + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


def mamba_params(m: dict) -> int:
    """The two projections of one Mamba-2 mixer."""
    h = m["hidden_size"]
    return (h * (_inner(m) + _conv_dim(m) + m["mamba_n_heads"])
            + _inner(m) * h)


def mamba_small_params(m: dict) -> int:
    """conv weights and bias, dt_bias, A_log, D, the gated norm."""
    return (_conv_dim(m) * (m["mamba_d_conv"] + 1)
            + 3 * m["mamba_n_heads"] + _inner(m))


def attention_params(m: dict) -> int:
    """The four projections of one attention layer: q and o at heads *
    head_dim, k and v at kv heads * head_dim."""
    h, d = m["hidden_size"], head_dim(m)
    return h * d * (2 * m["num_attention_heads"]
                    + 2 * m["num_key_value_heads"])


def mlp_params(m: dict) -> int:
    """`W_1` (gate and up side by side) and `W_2` of one layer."""
    return 3 * m["hidden_size"] * m["shared_intermediate_size"]


def num_params(m: dict) -> int:
    """Every parameter held here, the small ones too (two norms a
    layer, a mixer's convolution, dt_bias, A_log, D and gated norm, the
    final norm): the count the configuration's file states.  The
    embedding and the head are ONE matrix, counted once."""
    h, v = m["hidden_size"], m["vocab_held"]
    return (v * h + h + m["num_hidden_layers"] * (2 * h + mlp_params(m))
            + layers(m, MAMBA) * (mamba_params(m) + mamba_small_params(m))
            + layers(m, ATTENTION) * attention_params(m))


def scan_flops_per_token(m: dict) -> float:
    """The recurrence itself, one Mamba-2 mixer, forward."""
    p, n = m["mamba_d_head"], m["mamba_d_state"]
    return m["mamba_n_heads"] * (5.0 * p * n + 3.0 * p)


def scan_bytes_per_token(m: dict) -> float:
    """x and y, B and C, Δ: float32, read or written once."""
    return 4.0 * (2 * _inner(m) + 2 * m["mamba_n_groups"] * m["mamba_d_state"]
                  + m["mamba_n_heads"])


def pairs_in_mask(s: int) -> int:
    """(query, key) pairs one row of `s` tokens has inside the mask:
    j <= i."""
    return s * (s + 1) // 2


def core_flops_per_pair(m: dict) -> float:
    """The score and the value product of one pair, every query head."""
    return 4.0 * head_dim(m) * m["num_attention_heads"]


def core_bytes_per_token(m: dict) -> float:
    """q and the output, k and v of one token of one layer: float32,
    read or written once."""
    return 4.0 * head_dim(m) * (2 * m["num_attention_heads"]
                                + 2 * m["num_key_value_heads"])


def forward_flops_per_token(m: dict) -> float:
    """One forward pass, a token."""
    h, v, s = m["hidden_size"], m["vocab_held"], m["sequence_length"]
    mamba = (2.0 * mamba_params(m) + 2.0 * m["mamba_d_conv"] * _conv_dim(m)
             + scan_flops_per_token(m))
    core = core_flops_per_pair(m) * pairs_in_mask(s) / s
    return (layers(m, MAMBA) * mamba
            + layers(m, ATTENTION) * (2.0 * attention_params(m) + core)
            + m["num_hidden_layers"] * 2.0 * mlp_params(m) + 2.0 * h * v)


def update_cost(m: dict, rows: int, k: int, workers: int
                ) -> tuple[float, float]:
    tokens = rows * m["sequence_length"]
    flops = (3 * k + 1) * tokens * forward_flops_per_token(m)
    bytes_ = (12.0 * k + 4 + 8 + 12.0 / workers) * num_params(m)
    return flops, bytes_


def ssm_scan(cfg, chunks: float) -> tuple[float, float]:
    """(operations, bytes) of the state-space recurrence for `chunks`
    counted scan chunks (the program's counter `ssm.chunks`: every pass
    of every Mamba-2 layer, the k gradient passes and the loss pass of
    an update alike).  A gradient pass is three forwards' worth, so a
    counted chunk weighs (3k + 1) / (k + 1) forward chunks."""
    m = model_file(cfg)
    k = cfg.model.num_max_iter
    forward_chunks = chunks * (3 * k + 1) / (k + 1)
    tokens = forward_chunks * m["mamba_chunk_size"]
    return (tokens * scan_flops_per_token(m),
            tokens * scan_bytes_per_token(m))


def attention_core(cfg, pairs_window: float, pairs_full: float
                   ) -> tuple[float, float]:
    """(operations, bytes) of the score and value products for the
    in-mask pairs the program COUNTED (its counters `attn.pairs_window`,
    0 in this family, and `attn.pairs_full`, in units of PAIRS_UNIT
    pairs: every pass of every attention layer, the k gradient passes
    and the loss pass of an update alike).  A gradient pass is three
    forwards' worth, so a counted pair weighs (3k + 1) / (k + 1) forward
    pairs.  Bytes: a layer's pass over a row reads q, k and v and writes
    the output once (`core_bytes_per_token`); how many such passes the
    counted pairs stand for follows from the pairs a row has inside the
    mask."""
    m = model_file(cfg)
    k = cfg.model.num_max_iter
    s = m["sequence_length"]
    pairs = (pairs_window + pairs_full) * PAIRS_UNIT * (3 * k + 1) / (k + 1)
    row_passes = pairs / pairs_in_mask(s)
    return (pairs * core_flops_per_pair(m),
            row_passes * s * core_bytes_per_token(m))


def dense_mlp(m: dict, mlp_rows: float, rows: int, k: int
              ) -> tuple[float, float]:
    """(operations, bytes) of the LEAST the SwiGLU's products take for
    the positions the program COUNTED (its counter `mlp.rows`, in units
    of ROWS_UNIT positions: every row of a slab through every layer's
    MLP in every pass, the k gradient passes and the loss pass of an
    update alike; `rows`: a slab's rows).

    A forward pass over a position is the three products, 2 * 3 * H * I
    operations; a gradient pass is that and the backward pass, twice
    that again (each product's dx and dW), and nothing recomputed (the
    program recomputes the layer: a fourth forward's worth a gradient
    pass, which the least leaves out).  So k of an update's k + 1
    counted passes weigh three forwards and one weighs one.  Bytes,
    float32 as the program holds them, each read or written once: a
    forward pass of a layer reads its rows `[positions, H]`, `W_1` and
    `W_2` and writes its result (the gate, the up and their product
    `[positions, I]` are never written); a gradient pass reads those
    once more with the cotangent and writes dx, dW_1 and dW_2.  The
    products are the MXU's: at 2,048 positions a layer pass is 206
    GFLOP beside 235 MB."""
    h, i = m["hidden_size"], m["shared_intermediate_size"]
    positions = mlp_rows * ROWS_UNIT
    grad, loss = positions * k / (k + 1), positions / (k + 1)
    per_layer_pass = rows * m["sequence_length"]
    grad_passes, loss_passes = grad / per_layer_pass, loss / per_layer_pass
    flops = 2.0 * 3 * h * i * (3 * grad + loss)
    bytes_ = (4.0 * h * (5 * grad + 2 * loss)
              + 4.0 * 3 * h * i * (3 * grad_passes + loss_passes))
    return flops, bytes_


def updates_counted(m: dict, cfg, counters: dict) -> float:
    """Worker updates behind the program's counters of a drive call:
    every update counts its slab's tokens once, empty slots as padding."""
    return ((counters["data.tokens"] + counters["data.pad_tokens"])
            / (cfg.buffer.max_size * m["sequence_length"]))


def update(cfg) -> tuple[float, float]:
    """(flops, bytes) of one worker update at the CLI's configuration."""
    return update_cost(model_file(cfg), cfg.buffer.max_size,
                       cfg.model.num_max_iter, cfg.num_workers)


def evaluation(cfg, test) -> tuple[float, float]:
    """(flops, bytes) of one evaluation of the held-out rows."""
    m = model_file(cfg)
    tokens = len(test[0]) * m["sequence_length"]
    return tokens * forward_flops_per_token(m), 4.0 * num_params(m)

"""Least work and bytes of one worker update of the `lfm2-moe` family,
from the model file alone.

`update(cfg)` and `evaluation(cfg, test)` are what the roofline readers
call (`run.family.costs`); `expert_products` is what
`moe_expert_roofline_share` sets against the device time under
`kps.moe.experts`, `attention_core` what `window_attention_roofline_share`
sets against the device time under `kps.attn.full`, `short_conv_mix`
what `short_conv_roofline_share` sets against the self time under
`kps.ssm.conv`.  What is counted is the LEAST a chip could do for the
mathematics: matrix products at 2*m*n*k — a conv layer's two (`W_in`
2048 x 6144 and `W_out` 2048 x 2048), an attention layer's four, the
dense MLP's three, the router's, the head's (the embedding transposed:
one matrix, one product); the attention core for the (query, key) pairs
INSIDE the mask only — the full layer's triangle — at 4 * head_dim
operations a pair a query head (the score and the value product); the
routed experts for the assignments routed HERE only (the expected share
`experts_held / num_experts` of tokens * experts-per-token in `update`,
the counted ones in `expert_products`); a backward pass twice its
forward and nothing recomputed, so one update of k steps and the
forward-only loss is 3k + 1 forward passes; the embedding gather,
norms, RoPE, softmax, the gates, the convolution's taps and the
router's top-k are left out of `update` (lower order: the chain of a
conv layer is 7 operations a channel a token beside its products'
16,384, and `short_conv_mix` counts it for its own reader only), and so
is the expert layer's placement: the mathematics asks for no product
with a 0/1 matrix.  Bytes are the parameter plane's, at the float32 the
configuration states, as benchmark/costs.py counts a classifier's
weights: a step reads every parameter for its forward and for its
backward pass and writes the new ones (12 bytes a parameter a step; a
gradient that is never written is the least); the loss reads them once
(4); the running sum of deltas is read and written (8); the apply, a
clock, reads the shared parameters and the sum and writes them (12,
shared by the workers).  Activations are left out (lower order at these
sizes), except in `attention_core` and `short_conv_mix`, whose bytes ARE
activations.  The table of peaks is benchmark/peaks.py's.
"""

from __future__ import annotations

import json
import os

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
CONV, FULL = "conv", "full_attention"
# the program's pair counters (`attn.pairs_window`, `attn.pairs_full`,
# `attn.block_pairs`) count in units of 1,024 pairs and `conv.mix_rows`
# in units of 1,024 positions: the device's counters are int32 a
# dispatch (models/lfm2_moe.py PAIRS_UNIT, ROWS_UNIT)
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


def conv_params(m: dict) -> int:
    """The two products of one conv layer's operator: `W_in` to three
    streams, `W_out` back."""
    h = m["hidden_size"]
    return h * 3 * h + h * h


def attention_params(m: dict) -> int:
    """The four projections of one attention layer: q and o at heads *
    head_dim, k and v at kv heads * head_dim."""
    h, d = m["hidden_size"], head_dim(m)
    return h * d * (2 * m["num_attention_heads"]
                    + 2 * m["num_key_value_heads"])


def dense_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_block_params(m: dict) -> int:
    """Matrices of one expert layer's MLP as held here: the router over
    all the experts, the held experts' three."""
    return (m["hidden_size"] * m["num_experts"]
            + m["experts_held"] * expert_params(m))


def expert_blocks(m: dict) -> int:
    """Expert layers a pass goes through."""
    return m["num_hidden_layers"] - m["num_dense_layers"]


def num_params(m: dict) -> int:
    """Every parameter held here, the small ones too (two norms a
    layer, a conv layer's taps, an attention layer's two head norms, the
    selection bias, the final norm): the count the configuration's file
    states.  The embedding and the head are ONE matrix, counted once."""
    h, v = m["hidden_size"], m["vocab_held"]
    return (v * h + h + m["num_hidden_layers"] * 2 * h
            + layers(m, CONV) * (conv_params(m) + h * m["conv_L_cache"])
            + layers(m, FULL) * (attention_params(m) + 2 * head_dim(m))
            + m["num_dense_layers"] * dense_params(m)
            + expert_blocks(m) * (expert_block_params(m) + m["num_experts"]))


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


def forward_flops_per_token(m: dict, routed_share: float | None = None
                            ) -> float:
    """One forward pass, a token.  `routed_share`: the share of a
    token's chosen experts that are held here (None: the expected one)."""
    h, v, s = m["hidden_size"], m["vocab_held"], m["sequence_length"]
    if routed_share is None:
        routed_share = m["experts_held"] / m["num_experts"]
    core = core_flops_per_pair(m) * layers(m, FULL) * pairs_in_mask(s) / s
    expert = (2.0 * h * m["num_experts"]
              + 2.0 * m["num_experts_per_tok"] * routed_share
              * expert_params(m))
    return (layers(m, CONV) * 2.0 * conv_params(m)
            + layers(m, FULL) * 2.0 * attention_params(m) + core
            + m["num_dense_layers"] * 2.0 * dense_params(m)
            + expert_blocks(m) * expert + 2.0 * h * v)


def update_cost(m: dict, rows: int, k: int, workers: int
                ) -> tuple[float, float]:
    tokens = rows * m["sequence_length"]
    flops = (3 * k + 1) * tokens * forward_flops_per_token(m)
    bytes_ = (12.0 * k + 4 + 8 + 12.0 / workers) * num_params(m)
    return flops, bytes_


def expert_products(m: dict, assignments_grad: float,
                    assignments_loss: float, layer_passes_grad: float,
                    layer_passes_loss: float) -> tuple[float, float]:
    """(operations, bytes) of the grouped products for counted
    assignments: each is three products of 2 * H * I; a gradient pass
    (forward and backward, nothing recomputed) is three forwards' worth.
    Bytes: the held experts' matrices, read once by a forward pass of an
    expert layer, and by a gradient pass read once more and their
    gradient written (`layer_passes_*`: how many times an expert layer
    was passed through)."""
    flops = 2.0 * expert_params(m) * (3 * assignments_grad
                                      + assignments_loss)
    held = 4.0 * m["experts_held"] * expert_params(m)
    return flops, held * (3 * layer_passes_grad + layer_passes_loss)


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


def short_conv_mix(m: dict, mix_rows: float, rows: int, k: int
                   ) -> tuple[float, float]:
    """(operations, bytes) of the LEAST the gate - filter - gate chain
    `C * conv(B * z)` takes for the positions the program COUNTED (its
    counter `conv.mix_rows`, in units of ROWS_UNIT positions: every row
    of a slab through every conv layer in every pass, the k gradient
    passes and the loss pass of an update alike; `rows`: a slab's rows).

    A forward pass over a position reads B, C and z and writes the
    mixed stream: four float32 arrays of `hidden_size` channels.  A
    gradient pass is that and the backward pass, which reads the three
    again and the cotangent and writes three gradients — seven more;
    nothing is recomputed (the program recomputes the layer: a fifth
    pass of four a gradient pass, which the least leaves out), and the
    filtered stream in between is never written.  So k of an update's
    k + 1 counted passes weigh 11 arrays and one weighs 4.  Beside
    them, a layer a pass: the taps `[hidden, L]` read, and in a gradient
    pass their gradient written.  Operations: a channel of a position
    takes 2L + 1 forward (the gate, L taps multiplied and added, the
    gate), a gradient pass three forwards' worth — a thousandth of what
    the bytes take, so the chain is the memory's."""
    h, taps = m["hidden_size"], m["conv_L_cache"]
    positions = mix_rows * ROWS_UNIT
    grad, loss = positions * k / (k + 1), positions / (k + 1)
    layer_passes = positions / (rows * m["sequence_length"])
    flops = (2 * taps + 1) * h * (3 * grad + loss)
    bytes_ = (4.0 * h * (11 * grad + 4 * loss)
              + 4.0 * h * taps * layer_passes * (1 + k / (k + 1)))
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

"""Least work and bytes of one worker update of the `mellum` family, from
the model file alone.

`update(cfg)` and `evaluation(cfg, test)` are what the roofline readers
call (`run.family.costs`); `expert_products` is what
`moe_expert_roofline_share` sets against the device time under
`kps.moe.experts`, `attention_core` what `window_attention_roofline_share`
sets against the device time under `kps.attn.window` and
`kps.attn.full`, `placement_products` what `moe_placement_roofline_share`
sets against the self time under `kps.moe.place`, `kps.moe.combine` and
`kps.moe.experts` alone.  What is counted is the LEAST a chip could do
for the mathematics: matrix products at 2*m*n*k; the attention core for
the (query, key) pairs INSIDE the mask only — a sliding layer's band, a
full layer's triangle — at 4 * head_dim operations a pair a query head
(the score and the value product); the routed experts for the
assignments routed HERE only (the expected share `experts_held /
num_experts` of tokens * experts-per-token in `update`, the counted
ones in `expert_products`); a backward pass twice its forward and
nothing recomputed, so one update of k steps and the forward-only loss
is 3k + 1 forward passes; the embedding gather, norms, RoPE, softmax,
the gates and the router's top-k are left out (lower order), and so is
the expert layer's placement: the mathematics asks for no product with
a 0/1 matrix (`placement_products` counts it for its own reader only).
Bytes are the parameter plane's, at the float32 the configuration
states, as benchmark/costs.py counts a classifier's weights: a step
reads every parameter for its forward and for its backward pass and
writes the new ones (12 bytes a parameter a step; a gradient that is
never written is the least); the loss reads them once (4); the running
sum of deltas is read and written (8); the apply, a clock, reads the
shared parameters and the sum and writes them (12, shared by the
workers).  Activations are left out (lower order at these sizes),
except in `attention_core` and `placement_products`, whose bytes ARE
activations.  The table of peaks is benchmark/peaks.py's.
"""

from __future__ import annotations

import json
import math
import os

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
SLIDING, FULL = "sliding_attention", "full_attention"
# the program's pair counters (`attn.pairs_window`, `attn.pairs_full`,
# `attn.block_pairs`, `moe.place_pairs`) count in units of 1,024 pairs:
# the device's counters are int32 a dispatch (models/mellum.py
# PAIRS_UNIT)
PAIRS_UNIT = 1024


def model_file(cfg) -> dict:
    path = cfg.model.model_json
    with open(path if os.path.isabs(path) else os.path.join(_ROOT, path)) \
            as fh:
        return json.load(fh)


def layers(m: dict, kind: str) -> int:
    return m["layer_types"].count(kind)


def attention_params(m: dict) -> int:
    """The four projections of one layer: q and o at heads * head_dim,
    k and v at kv heads * head_dim."""
    h, d = m["hidden_size"], m["head_dim"]
    return h * d * (2 * m["num_attention_heads"]
                    + 2 * m["num_key_value_heads"])


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_block_params(m: dict) -> int:
    """Matrices of one expert layer's MLP as held here: the router over
    all the experts, the held experts' three."""
    return (m["hidden_size"] * m["num_experts"]
            + m["experts_held"] * expert_params(m))


def expert_blocks(m: dict) -> int:
    """Expert layers a pass goes through: every layer."""
    return m["num_hidden_layers"]


def num_params(m: dict) -> int:
    """Every parameter held here, the small ones too (two norms and the
    two head norms a layer, the final norm): the count the
    configuration's file states."""
    h, v = m["hidden_size"], m["vocab_held"]
    small = 2 * h + 2 * m["head_dim"]
    return (2 * v * h + h + m["num_hidden_layers"]
            * (attention_params(m) + small + expert_block_params(m)))


def pairs_in_mask(s: int, window: int | None) -> int:
    """(query, key) pairs one row of `s` tokens has inside the mask:
    j <= i, and under a window also i - j < window."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def core_flops_per_pair(m: dict) -> float:
    """The score and the value product of one pair, every query head."""
    return 4.0 * m["head_dim"] * m["num_attention_heads"]


def core_bytes_per_token(m: dict) -> float:
    """q and the output, k and v of one token of one layer: float32,
    read or written once."""
    return 4.0 * m["head_dim"] * (2 * m["num_attention_heads"]
                                  + 2 * m["num_key_value_heads"])


def forward_flops_per_token(m: dict, routed_share: float | None = None
                            ) -> float:
    """One forward pass, a token.  `routed_share`: the share of a
    token's chosen experts that are held here (None: the expected one)."""
    h, v, s = m["hidden_size"], m["vocab_held"], m["sequence_length"]
    if routed_share is None:
        routed_share = m["experts_held"] / m["num_experts"]
    core = core_flops_per_pair(m) * (
        layers(m, SLIDING) * pairs_in_mask(s, m["sliding_window"])
        + layers(m, FULL) * pairs_in_mask(s, None)) / s
    expert = (2.0 * h * m["num_experts"]
              + 2.0 * m["num_experts_per_tok"] * routed_share
              * expert_params(m))
    return (m["num_hidden_layers"] * (2.0 * attention_params(m) + expert)
            + core + 2.0 * h * v)


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


def live_rows_bound(m: dict, tokens: int) -> int:
    """The rows an expert layer's pass over `tokens` tokens places
    under its bound, stated here apart from the program's
    `lm_common.live_rows_bound`: twice the even share of the experts
    held here, in whole tiles of 8 rows, and never more than the
    slots."""
    slots = tokens * m["num_experts_per_tok"]
    even = slots * m["experts_held"] / m["num_experts"]
    return min(slots, 8 * math.ceil(2 * even / 8))


def placement_products(m: dict, place_pairs: float, rows: int, k: int
                       ) -> tuple[float, float]:
    """(operations, bytes) of the LEAST the products with the expert
    layer's 0/1 matrix take for the (placed row, token) pairs the
    program COUNTED (its counter `moe.place_pairs`, in units of
    PAIRS_UNIT pairs: every expert layer of every pass, the k gradient
    passes and the loss pass of an update alike; `rows`: a slab's
    rows).  A forward pass of a layer is two products, the placing one
    `[R, T] x [T, H]` and the add-back `[T, R] x [R, H]`, each ONE pass
    of 2 * H operations a pair — the add-back runs at `HIGH`, three
    passes of the MXU, of which the least counts one; a gradient pass
    is those two and their two transposes (the 0/1 matrix takes no
    gradient), so a counted pair weighs (4k + 2) / (k + 1) products.
    Bytes, the operands once a product: the 0/1 matrix in bfloat16 (2 a
    pair), and the float32 rows in and out, (R + T) x H, R the rows
    under the bound."""
    h = m["hidden_size"]
    tokens = rows * m["sequence_length"]
    placed = live_rows_bound(m, tokens)
    products = place_pairs * PAIRS_UNIT * (4 * k + 2) / (k + 1)
    layer_products = products / (placed * tokens)
    return (products * 2.0 * h,
            products * 2.0 + layer_products * 4.0 * h * (placed + tokens))


def attention_core(cfg, pairs_window: float, pairs_full: float
                   ) -> tuple[float, float]:
    """(operations, bytes) of the score and value products for the
    in-mask pairs the program COUNTED (its counters `attn.pairs_window`
    and `attn.pairs_full`, in units of PAIRS_UNIT pairs: every pass of
    every layer of that kind, the k gradient passes and the loss pass
    of an update alike).  A gradient pass is three forwards' worth, so
    a counted pair weighs (3k + 1) / (k + 1) forward pairs.  Bytes: a
    layer's pass over a row reads q, k and v and writes the output once
    (`core_bytes_per_token`); how many such passes the counted pairs
    stand for follows from the pairs a row has inside each mask."""
    m = model_file(cfg)
    k = cfg.model.num_max_iter
    s = m["sequence_length"]
    weight = PAIRS_UNIT * (3 * k + 1) / (k + 1)
    window, full = pairs_window * weight, pairs_full * weight
    row_passes = (window / pairs_in_mask(s, m["sliding_window"])
                  + full / pairs_in_mask(s, None))
    return ((window + full) * core_flops_per_pair(m),
            row_passes * s * core_bytes_per_token(m))


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

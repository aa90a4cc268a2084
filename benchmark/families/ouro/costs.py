"""Least work and bytes of one worker update of the `ouro` family, from
the model file alone.

`update(cfg)` and `evaluation(cfg, test)` are what the roofline readers
call (`run.family.costs`); `attention_core` is what
`window_attention_roofline_share` sets against the device time under
`kps.attn.full`.  What is counted is the LEAST a chip could do for the
mathematics, and a layer APPLICATION counts, not a layer: a token
passes `num_hidden_layers * total_ut_steps` of them in a forward pass,
each with its own products, whatever the leaves they share.  Matrix
products at 2*m*n*k; the attention core for the (query, key) pairs
INSIDE the mask only — the triangle — at 4 * head_dim operations a pair
a query head (the score and the value product); a backward pass twice
its forward and nothing recomputed, so one update of k steps and the
forward-only loss is 3k + 1 forward passes; the embedding gather,
norms, RoPE, softmax and the gate of the MLP are left out (lower
order).  Bytes are the parameter plane's, at the float32 the
configuration states, and a leaf counts ONCE however often it is used
(the least reads it once a pass): a step reads every parameter for its
forward and for its backward pass and writes the new ones (12 bytes a
parameter a step; a gradient that is never written is the least); the
loss reads them once (4); the running sum of deltas is read and written
(8); the apply, a clock, reads the shared parameters and the sum and
writes them (12, shared by the workers).  Activations are left out
(lower order at these sizes), except in `attention_core`, whose bytes
ARE activations: q, k, v and the output, float32, read or written once
an application.  The table of peaks is benchmark/peaks.py's.
"""

from __future__ import annotations

import json
import os

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
# the program's pair counters (`attn.pairs_full`, `attn.block_pairs`)
# count in units of 1,024 pairs: the device's counters are int32 a
# dispatch (models/ouro.py PAIRS_UNIT)
PAIRS_UNIT = 1024


def model_file(cfg) -> dict:
    path = cfg.model.model_json
    with open(path if os.path.isabs(path) else os.path.join(_ROOT, path)) \
            as fh:
        return json.load(fh)


def layer_applications(m: dict) -> int:
    """Layers a token passes in one forward pass."""
    return m["num_hidden_layers"] * m["total_ut_steps"]


def attention_params(m: dict) -> int:
    """The four projections of one layer: q and o at heads * head_dim,
    k and v at kv heads * head_dim."""
    h, d = m["hidden_size"], m["head_dim"]
    return h * d * (2 * m["num_attention_heads"]
                    + 2 * m["num_key_value_heads"])


def mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def num_params(m: dict) -> int:
    """Every parameter held here, each layer's ONCE, the small ones too
    (four norms a layer, the final norm): the count the configuration's
    file states."""
    h, v = m["hidden_size"], m["vocab_held"]
    return (2 * v * h + h + m["num_hidden_layers"]
            * (attention_params(m) + mlp_params(m) + 4 * h))


def pairs_in_mask(s: int) -> int:
    """(query, key) pairs one row of `s` tokens has inside the mask:
    j <= i."""
    return s * (s + 1) // 2


def core_flops_per_pair(m: dict) -> float:
    """The score and the value product of one pair, every query head."""
    return 4.0 * m["head_dim"] * m["num_attention_heads"]


def core_bytes_per_token(m: dict) -> float:
    """q and the output, k and v of one token of one application:
    float32, read or written once."""
    return 4.0 * m["head_dim"] * (2 * m["num_attention_heads"]
                                  + 2 * m["num_key_value_heads"])


def forward_flops_per_token(m: dict) -> float:
    """One forward pass, a token: every layer application's products
    and core, and the head once."""
    s = m["sequence_length"]
    core = core_flops_per_pair(m) * pairs_in_mask(s) / s
    return (layer_applications(m)
            * (2.0 * (attention_params(m) + mlp_params(m)) + core)
            + 2.0 * m["hidden_size"] * m["vocab_held"])


def update_cost(m: dict, rows: int, k: int, workers: int
                ) -> tuple[float, float]:
    tokens = rows * m["sequence_length"]
    flops = (3 * k + 1) * tokens * forward_flops_per_token(m)
    bytes_ = (12.0 * k + 4 + 8 + 12.0 / workers) * num_params(m)
    return flops, bytes_


def attention_core(cfg, pairs_window: float, pairs_full: float
                   ) -> tuple[float, float]:
    """(operations, bytes) of the score and value products for the
    in-mask pairs the program COUNTED (its counter `attn.pairs_full`,
    in units of PAIRS_UNIT pairs: every pass of every layer application,
    the k gradient passes and the loss pass of an update alike;
    `attn.pairs_window` reads 0, no layer slides).  A gradient pass is
    three forwards' worth, so a counted pair weighs (3k + 1) / (k + 1)
    forward pairs.  Bytes: an application's pass over a row reads q, k
    and v and writes the output once (`core_bytes_per_token`); how many
    such passes the counted pairs stand for follows from the pairs a
    row has inside the mask."""
    m = model_file(cfg)
    k = cfg.model.num_max_iter
    s = m["sequence_length"]
    pairs = (pairs_window + pairs_full) * PAIRS_UNIT * (3 * k + 1) / (k + 1)
    return (pairs * core_flops_per_pair(m),
            pairs / pairs_in_mask(s) * s * core_bytes_per_token(m))


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

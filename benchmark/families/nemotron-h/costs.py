"""Least work and bytes of one worker update of the `nemotron-h` family,
from the model file alone.

`update(cfg)` and `evaluation(cfg, test)` are what the roofline readers
call (`run.family.costs`); `expert_products` is what
`moe_expert_roofline_share` sets against the device time under
`kps.moe.experts`, `ssm_scan` what `ssm_scan_roofline_share` sets
against the device time under `kps.ssm.scan`.  What is counted is the
LEAST a chip could do for the mathematics: matrix products at 2*m*n*k;
attention causal (a position attends to (S + 1) / 2 keys on average);
the routed experts for the assignments routed HERE only (the expected
share `experts_held / n_routed_experts` of tokens * experts-per-token
in `update`, the counted ones in `expert_products`); the state-space
layer as its RECURRENCE, a step a token (5 * P * N + 3 * P operations a
head: the decay, the outer product and its sum into the state, the
state times C, the skip term) — not the chunked algorithm's products,
which are more; a backward pass twice its forward and nothing
recomputed, so one update of k steps and the forward-only loss is
3k + 1 forward passes; the embedding gather, norms, softmax, softplus
and the router's top-k are left out (lower order).  Bytes are the
parameter plane's, at the float32 the configuration states, as
benchmark/costs.py counts a classifier's weights: a step reads every
parameter for its forward and for its backward pass and writes the new
ones (12 bytes a parameter a step; a gradient that is never written is
the least); the loss reads them once (4); the running sum of deltas is
read and written (8); the apply, a clock, reads the shared parameters
and the sum and writes them (12, shared by the workers).  Activations
are left out (lower order at these sizes), except in `ssm_scan`, whose
bytes ARE activations: what the recurrence reads (x, B, C, Δ) and
writes (y), in float32.  The table of peaks is benchmark/peaks.py's.
"""

from __future__ import annotations

import json
import os

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def model_file(cfg) -> dict:
    path = cfg.model.model_json
    with open(path if os.path.isabs(path) else os.path.join(_ROOT, path)) \
            as fh:
        return json.load(fh)


def blocks(m: dict, kind: str) -> int:
    return m["hybrid_override_pattern"].count(kind)


def _inner(m: dict) -> int:
    return m["mamba_num_heads"] * m["mamba_head_dim"]


def _conv_dim(m: dict) -> int:
    return _inner(m) + 2 * m["n_groups"] * m["ssm_state_size"]


def mamba_params(m: dict) -> int:
    """The two projections of one Mamba-2 block."""
    h = m["hidden_size"]
    return (h * (_inner(m) + _conv_dim(m) + m["mamba_num_heads"])
            + _inner(m) * h)


def mamba_small_params(m: dict) -> int:
    """conv weights and bias, dt_bias, A_log, D, the gated norm."""
    return (_conv_dim(m) * (m["conv_kernel"] + 1)
            + 3 * m["mamba_num_heads"] + _inner(m))


def attention_params(m: dict) -> int:
    h, d = m["hidden_size"], m["head_dim"]
    return 2 * h * d * (m["num_attention_heads"] + m["num_key_value_heads"])


def expert_params(m: dict) -> int:
    return 2 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m: dict) -> int:
    return (2 * m["hidden_size"] * m["n_shared_experts"]
            * m["moe_shared_expert_intermediate_size"])


def expert_block_params(m: dict) -> int:
    """Matrices of one expert layer as held here."""
    return (m["hidden_size"] * m["n_routed_experts"]
            + m["experts_held"] * expert_params(m) + shared_params(m))


def num_params(m: dict) -> int:
    """Every parameter held here, the small ones too: the count the
    configuration's file states."""
    h, v = m["hidden_size"], m["vocab_held"]
    return (2 * v * h + h
            + blocks(m, "M") * (mamba_params(m) + mamba_small_params(m) + h)
            + blocks(m, "*") * (attention_params(m) + h)
            + blocks(m, "E") * (expert_block_params(m)
                                + m["n_routed_experts"] + h))


def scan_flops_per_token(m: dict) -> float:
    """The recurrence itself, one Mamba-2 block, forward."""
    p, n = m["mamba_head_dim"], m["ssm_state_size"]
    return m["mamba_num_heads"] * (5.0 * p * n + 3.0 * p)


def scan_bytes_per_token(m: dict) -> float:
    """x and y, B and C, Δ: float32, read or written once."""
    return 4.0 * (2 * _inner(m) + 2 * m["n_groups"] * m["ssm_state_size"]
                  + m["mamba_num_heads"])


def attention_flops_per_token(m: dict) -> float:
    keys = (m["sequence_length"] + 1) / 2          # causal, on average
    return (2.0 * attention_params(m)
            + 2.0 * m["num_attention_heads"] * 2 * m["head_dim"] * keys)


def forward_flops_per_token(m: dict, routed_share: float | None = None
                            ) -> float:
    """One forward pass, a token.  `routed_share`: the share of a
    token's chosen experts that are held here (None: the expected one)."""
    h, v = m["hidden_size"], m["vocab_held"]
    if routed_share is None:
        routed_share = m["experts_held"] / m["n_routed_experts"]
    mamba = (2.0 * mamba_params(m)
             + 2.0 * m["conv_kernel"] * _conv_dim(m)
             + scan_flops_per_token(m))
    expert = (2.0 * h * m["n_routed_experts"] + 2.0 * shared_params(m)
              + 2.0 * m["num_experts_per_tok"] * routed_share
              * expert_params(m))
    return (blocks(m, "M") * mamba
            + blocks(m, "*") * attention_flops_per_token(m)
            + blocks(m, "E") * expert + 2.0 * h * v)


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
    assignments: each is two products of 2 * H * I (up, down; no gate);
    a gradient pass (forward and backward, nothing recomputed) is three
    forwards' worth.  Bytes: the held experts' matrices, read once by a
    forward pass of an expert layer, and by a gradient pass read once
    more and their gradient written (`layer_passes_*`: how many times
    an expert layer was passed through)."""
    flops = 2.0 * expert_params(m) * (3 * assignments_grad
                                      + assignments_loss)
    held = 4.0 * m["experts_held"] * expert_params(m)
    return flops, held * (3 * layer_passes_grad + layer_passes_loss)


def expert_blocks(m: dict) -> int:
    """Expert layers a pass goes through."""
    return blocks(m, "E")


def ssm_scan(cfg, chunks: float) -> tuple[float, float]:
    """(operations, bytes) of the state-space recurrence for `chunks`
    counted scan chunks (the program's counter `ssm.chunks`: every pass
    of every Mamba-2 block, the k gradient passes and the loss pass of
    an update alike).  A gradient pass is three forwards' worth, so a
    counted chunk weighs (3k + 1) / (k + 1) forward chunks."""
    m = model_file(cfg)
    k = cfg.model.num_max_iter
    forward_chunks = chunks * (3 * k + 1) / (k + 1)
    tokens = forward_chunks * m["chunk_size"]
    return (tokens * scan_flops_per_token(m),
            tokens * scan_bytes_per_token(m))


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

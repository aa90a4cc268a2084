"""Least work and bytes of one worker update of the `glm4-moe-lite`
family, from the model file alone.

`update(cfg)` and `evaluation(cfg, test)` are what the roofline readers
call (`run.family.costs`); `expert_products` is what
`moe_expert_roofline_share` sets against the device time under
`kps.moe.experts`.  What is counted is the LEAST a chip could do for the
mathematics: matrix products at 2*m*n*k; attention causal (a position
attends to (S + 1) / 2 keys on average); the routed experts for the
assignments routed HERE only (the expected share `experts_held /
n_routed_experts` of tokens * experts-per-token in `update`, the
counted ones in `expert_products`); a backward pass twice its forward
and nothing recomputed, so one update of k steps and the forward-only
loss is 3k + 1 forward passes; the embedding gather, norms, softmax and
the router's top-k are left out (lower order).  Bytes are the
parameter plane's, at the float32 the configuration states, as
benchmark/costs.py counts a classifier's weights: a step reads every
parameter for its forward and for its backward pass and writes the new
ones (12 bytes a parameter a step; a gradient that is never written is
the least); the loss reads them once (4); the running sum of deltas is
read and written (8); the apply, a clock, reads the shared parameters
and the sum and writes them (12, shared by the workers).  Activations
are left out (lower order at these sizes).  The table of peaks is
benchmark/peaks.py's.
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


def attention_params(m: dict) -> int:
    h, nh = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    return (h * m["q_lora_rank"] + m["q_lora_rank"] * nh * (dn + dr)
            + h * (m["kv_lora_rank"] + dr)
            + m["kv_lora_rank"] * nh * (dn + dv) + nh * dv * h)


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_block_params(m: dict) -> int:
    """Matrices of one expert layer as held here (norms left out)."""
    return (attention_params(m) + m["hidden_size"] * m["n_routed_experts"]
            + (m["experts_held"] + m["n_shared_experts"]) * expert_params(m))


def num_params(m: dict) -> int:
    h, v = m["hidden_size"], m["vocab_held"]
    blocks = expert_blocks(m)
    return (2 * v * h + attention_params(m) + 3 * h * m["intermediate_size"]
            + blocks * expert_block_params(m)
            + m["num_nextn_predict_layers"] * 2 * h * h)


def attention_flops_per_token(m: dict) -> float:
    nh = m["num_attention_heads"]
    keys = (m["sequence_length"] + 1) / 2          # causal, on average
    width = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
             + m["v_head_dim"])
    return 2.0 * attention_params(m) + 2.0 * nh * width * keys


def forward_flops_per_token(m: dict, routed_share: float | None = None
                            ) -> float:
    """One forward pass, a token.  `routed_share`: the share of a
    token's chosen experts that are held here (None: the expected one)."""
    h, v = m["hidden_size"], m["vocab_held"]
    if routed_share is None:
        routed_share = m["experts_held"] / m["n_routed_experts"]
    block = (attention_flops_per_token(m)
             + 2.0 * h * m["n_routed_experts"]
             + 2.0 * (m["n_shared_experts"]
                      + m["num_experts_per_tok"] * routed_share)
             * expert_params(m))
    blocks = expert_blocks(m)
    heads = 1 + m["num_nextn_predict_layers"]
    return (attention_flops_per_token(m)
            + 2.0 * 3 * h * m["intermediate_size"] + blocks * block
            + heads * 2.0 * h * v
            + m["num_nextn_predict_layers"] * 2.0 * 2 * h * h)


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


def expert_blocks(m: dict) -> int:
    """Expert layers a pass goes through (the MTP module's is one)."""
    return m["num_hidden_layers"] - 1 + m["num_nextn_predict_layers"]


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

"""A language-model family: `glm4_moe_lite` (GLM-4.7-Flash's published
shape, https://huggingface.co/zai-org/GLM-4.7-Flash config.json).

What it brings that the two classifier families do not: token rows
(`int32[S + 2]`, whose labels are the row itself, shifted), latent
attention (MLA), a leading dense SwiGLU layer, expert layers with a
sigmoid router, a selection bias, a shared expert and routed experts
run as grouped matrix products over the experts HELD HERE, a
multi-token-prediction (MTP) module, a sliced vocabulary, and a loss
that is the mean next-token cross-entropy.

Token rows, the norm, the router, the expert layer that knows its
share, the head and its loss, the flat key space, the solver with its
counters and the task's frame are `models/lm_common.py`'s, shared with
the other language-model family; what is here is this family's own:
its configuration, its leaves, latent attention, its SwiGLU experts
and the MTP module.

`leaf_specs` fixes the leaves' order in the flat key space (the wire
contract): there, and only there, the expert layers' leaves are stacked
on a leading layer axis (`moe.<name>`, `(layers,) + shape`).  The
program holds them a layer at a time (`layer_specs`: `moe.<l>.<name>`,
the stacked leaf's contiguous runs, so the flat vector is the same to
the element) and its blocks are written out, a layer's matrices operands
as they lie and its gradient a result of its own; every layer is
recomputed in the backward pass (`jax.checkpoint`), so one worker's
activations stay a few layers' worth.

Assumed, where the published config says nothing (each also noted in
the benchmark's reference): rotate-half RoPE over all rope dims; the
selection bias held fixed (no gradient reaches it: it only selects);
the MTP module in DeepSeek-V3's form — `eh_proj([RMSNorm(Emb(t_{i+1}))
‖ RMSNorm(h_i)])` with h_i taken before the final norm, one expert
block, its own final norm, the shared head — its loss added at weight
`mtp_loss_weight`; initialisation normal(0, `init_std`) from
`init_seed`, norms at one, the bias at zero.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models.lm_common import (rms_norm, rope, sub, swiglu,
                                            swiglu_experts)


@dataclasses.dataclass(frozen=True)
class Glm4Config:
    """The published keys (names as in config.json), then the cut, then
    what had to be assumed."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    first_k_dense_replace: int
    num_hidden_layers: int
    num_nextn_predict_layers: int
    rms_norm_eps: float
    rope_theta: float
    vocab_size: int
    # the cut
    experts_held: int
    expert_offset: int
    vocab_held: int
    sequence_length: int
    # assumed
    mtp_loss_weight: float = 0.3
    init_std: float = 0.02
    init_seed: int = 0

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        return self.sequence_length + 2

    def validate(self) -> None:
        if self.first_k_dense_replace != 1:
            raise ValueError("one leading dense layer is what this family "
                             "implements (first_k_dense_replace 1)")
        if self.num_moe_layers < 1:
            raise ValueError("num_hidden_layers must leave an expert layer")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers is 0 or 1")
        lm.validate_cut(self)
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")


def load_config(path: str) -> Glm4Config:
    return lm.load_config(path, "glm4_moe_lite", Glm4Config)


# -- the flat key space --------------------------------------------------------

def _attn_specs(c: Glm4Config) -> list[tuple[str, tuple[int, ...]]]:
    h, nh = c.hidden_size, c.num_attention_heads
    return [("in_norm", (h,)),
            ("wq_a", (h, c.q_lora_rank)), ("q_norm", (c.q_lora_rank,)),
            ("wq_b", (c.q_lora_rank, nh * c.qk_head_dim)),
            ("wkv_a", (h, c.kv_lora_rank + c.qk_rope_head_dim)),
            ("kv_norm", (c.kv_lora_rank,)),
            ("wkv_b", (c.kv_lora_rank,
                       nh * (c.qk_nope_head_dim + c.v_head_dim))),
            ("wo", (nh * c.v_head_dim, h)),
            ("post_norm", (h,))]


def _moe_specs(c: Glm4Config) -> list[tuple[str, tuple[int, ...]]]:
    h, i = c.hidden_size, c.moe_intermediate_size
    e, s = c.experts_held, c.n_shared_experts * c.moe_intermediate_size
    return _attn_specs(c) + [
        ("router", (h, c.n_routed_experts)),
        ("router_bias", (c.n_routed_experts,)),
        ("e_gate", (e, h, i)), ("e_up", (e, h, i)), ("e_down", (e, i, h)),
        ("s_gate", (h, s)), ("s_up", (h, s)), ("s_down", (s, h))]


def leaf_specs(c: Glm4Config) -> list[tuple[str, tuple[int, ...]]]:
    """(dotted name, shape) of every leaf of the WIRE, in flat-layout
    order.  The expert layers are stacked on a leading axis of
    `num_moe_layers`."""
    h, i, v = c.hidden_size, c.intermediate_size, c.vocab_held
    out = [("embed", (v, h))]
    out += [("dense." + n, s) for n, s in _attn_specs(c)]
    out += [("dense.w_gate", (h, i)), ("dense.w_up", (h, i)),
            ("dense.w_down", (i, h))]
    out += [("moe." + n, (c.num_moe_layers,) + s) for n, s in _moe_specs(c)]
    out += [("final_norm", (h,)), ("head", (h, v))]
    if c.num_nextn_predict_layers:
        out += [("mtp.enorm", (h,)), ("mtp.hnorm", (h,)),
                ("mtp.eh_proj", (2 * h, h))]
        out += [("mtp." + n, s) for n, s in _moe_specs(c)]
        out += [("mtp.final_norm", (h,))]
    return out


def _layer_names(name: str, c: Glm4Config) -> list[str] | None:
    """The program's names for the layers of a stacked leaf of the
    wire, in order; None for a leaf that is not stacked."""
    if not name.startswith("moe."):
        return None
    return [f"moe.{at}.{name[4:]}" for at in range(c.num_moe_layers)]


def layer_specs(c: Glm4Config) -> list[tuple[str, tuple[int, ...]]]:
    """(dotted name, shape) of every leaf as the PROGRAM holds it, in
    flat-layout order: `leaf_specs` with every stacked `moe.<name>` cut
    into its layers `moe.<l>.<name>`.  A stacked leaf is row-major, so
    its layers are contiguous runs of the flat vector, one after
    another: both lists cover the same elements in the same order."""
    out = []
    for name, shape in leaf_specs(c):
        layers = _layer_names(name, c)
        out += ([(name, shape)] if layers is None
                else [(layer, shape[1:]) for layer in layers])
    return out


def init_leaves(c: Glm4Config) -> dict:
    """normal(0, init_std) from `init_seed`, one key a leaf OF THE WIRE
    by its place in the layout (a stacked leaf is drawn whole and cut
    into its layers afterwards); norms one, the selection bias zero."""
    key = jax.random.PRNGKey(c.init_seed)
    out = {}
    for at, (name, shape) in enumerate(leaf_specs(c)):
        last = name.rsplit(".", 1)[-1]
        if last.endswith("norm"):
            leaf = jnp.ones(shape, jnp.float32)
        elif last == "router_bias":
            leaf = jnp.zeros(shape, jnp.float32)
        else:
            leaf = c.init_std * jax.random.normal(
                jax.random.fold_in(key, at), shape, jnp.float32)
        layers = _layer_names(name, c)
        out.update([(name, leaf)] if layers is None else zip(layers, leaf))
    return out


# -- the layers ------------------------------------------------------------------

def mla(x, p: dict, c: Glm4Config):
    """Latent attention on `[B, S, H]`, causal within a row."""
    with jax.named_scope("kps.mla"):
        b, s, _ = x.shape
        nh, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                          c.qk_rope_head_dim, c.v_head_dim)
        # the parts, in the order the program was written in before
        # they had names: `kps.attn.qkv` (the four projections to and
        # from the latents), `kps.attn.norm_rope` (the latents' norms
        # and RoPE), `kps.attn.out` (the output projection); the core
        # (scores, mask, softmax, values) is what is left under
        # `kps.mla` alone
        def project(x, w):
            with jax.named_scope("kps.attn.qkv"):
                return x @ p[w]

        def norm(x, w):
            with jax.named_scope("kps.attn.norm_rope"):
                return rms_norm(x, p[w], c.rms_norm_eps)

        def positions(x):
            with jax.named_scope("kps.attn.norm_rope"):
                return rope(x, c.rope_theta)

        h = lm.block_norm(x, p["in_norm"], c.rms_norm_eps)
        cq = norm(project(h, "wq_a"), "q_norm")
        q = project(cq, "wq_b").reshape(b, s, nh, dn + dr)
        kva = project(h, "wkv_a")
        ckv = norm(kva[..., :c.kv_lora_rank], "kv_norm")
        k_rope = positions(kva[..., None, c.kv_lora_rank:])
        kv = project(ckv, "wkv_b").reshape(b, s, nh, dn + dv)
        q_rope = positions(q[..., dn:])
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], kv[..., :dn])
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0]))
        scores = scores / math.sqrt(dn + dr)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., dn:])
        with jax.named_scope("kps.attn.out"):
            return out.reshape(b, s, nh * dv) @ p["wo"]


def _experts(xs, p: dict, dot):
    """What `lm_common.routed_experts` is handed: the gated expert on
    its own rows, which take no gradient past the last group.  The
    products at these widths are left to the compiler's tiles, and the
    untold kernel leaves those rows as it found them: with the layers
    written out the buffer it finds has held NaN (on the chip: every
    parameter NaN after clock 1), which the scan's never had."""
    return swiglu_experts(lm.live_rows_only(xs, dot.sizes), p, dot)


def _shared_expert(h, p: dict):
    return swiglu(h, p["s_gate"], p["s_up"], p["s_down"])


def moe(x, p: dict, c: Glm4Config):
    """Expert layer's MLP half on `[B, S, H]` (already normed) →
    (its output, (assignments here, largest load, went over the
    bound))."""
    return lm.expert_layer(x, p, c, _experts, _shared_expert)


def dense_block(x, p: dict, c: Glm4Config):
    x = x + mla(x, p, c)
    h = lm.block_norm(x, p["post_norm"], c.rms_norm_eps)
    return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"])


def moe_block(x, p: dict, c: Glm4Config):
    x = x + mla(x, p, c)
    y, load = moe(lm.block_norm(x, p["post_norm"], c.rms_norm_eps), p, c)
    return x + y, load


def _head_nll(x, norm, head, targets, c: Glm4Config):
    return lm.head_nll(x, norm, head, targets, c.rms_norm_eps)


def forward(leaves: dict, rows, c: Glm4Config, *, with_logits=False):
    """`rows` `[B, S + 2]` int32 → per-position losses and the routing
    counts: {"nll" [B, S] next-token, "mtp_nll" [B, S] or None,
    "loads" [blocks, 3], "logits" if asked}.  Every block is recomputed
    in the backward pass."""
    s = c.sequence_length
    tokens, t1, t2 = rows[:, :s], rows[:, 1:s + 1], rows[:, 2:s + 2]
    with jax.named_scope("kps.lm.embed"):
        x = leaves["embed"][tokens]
    x = jax.checkpoint(lambda x, p: dense_block(x, p, c))(
        x, sub(leaves, "dense."))
    # the expert layers written out: what lies under `kps.lm.layers`
    # and no scope inside a block is the residual adds
    with jax.named_scope("kps.lm.layers"):
        block = jax.checkpoint(lambda x, p: moe_block(x, p, c))
        loads = []
        for at in range(c.num_moe_layers):
            x, load = block(x, sub(leaves, f"moe.{at}."))
            loads.append(load)
        loads = jnp.stack(loads)
    with jax.named_scope("kps.lm.head"):
        nll, logits = jax.checkpoint(
            lambda x, n, hd, t: _head_nll(x, n, hd, t, c))(
                x, leaves["final_norm"], leaves["head"], t1)
    out = {"nll": nll, "mtp_nll": None, "loads": loads}
    if with_logits:
        out["logits"] = logits
    if c.num_nextn_predict_layers:
        with jax.named_scope("kps.lm.mtp"):
            m = sub(leaves, "mtp.")

            def mtp(x, emb_next, m, head):
                # [RMSNorm(Emb(t_{i+1})) ‖ RMSNorm(h_i)], h_i before the
                # final norm (assumed, module docstring)
                joined = jnp.concatenate(
                    [rms_norm(emb_next, m["enorm"], c.rms_norm_eps),
                     rms_norm(x, m["hnorm"], c.rms_norm_eps)], axis=-1)
                y, load = moe_block(joined @ m["eh_proj"], m, c)
                nll2, _ = _head_nll(y, m["final_norm"], head, t2, c)
                return nll2, load
            mtp_nll, load = jax.checkpoint(mtp)(
                x, leaves["embed"][t1], m, leaves["head"])
        out["mtp_nll"] = mtp_nll
        out["loads"] = jnp.concatenate([loads, load[None]], axis=0)
    return out


def loss_and_counts(leaves: dict, rows, mask, c: Glm4Config):
    """The training objective over the unmasked rows of a slab — mean
    next-token cross-entropy, plus `mtp_loss_weight` times the MTP
    module's — and (assignments here, Σ largest load, expert layers
    that went over `live_rows_bound`) of the pass."""
    out = forward(leaves, rows, c)
    per_row = out["nll"].sum(-1)
    if out["mtp_nll"] is not None:
        per_row = per_row + c.mtp_loss_weight * out["mtp_nll"].sum(-1)
    positions = jnp.maximum(mask.sum(), 1.0) * c.sequence_length
    return (per_row * mask).sum() / positions, out["loads"].sum(0)


def slots_a_token(c: Glm4Config) -> int:
    return c.num_experts_per_tok * (c.num_moe_layers
                                    + c.num_nextn_predict_layers)


# -- the task ----------------------------------------------------------------------

class Glm4MoeLiteTask(lm.TokenRowsTask):
    """`lm_common.TokenRowsTask` over this family's leaves and blocks."""

    model_type = "glm4_moe_lite"
    config_cls = Glm4Config

    def leaf_specs(self):
        """The program's list (the frame's `specs`, `unflatten`,
        `flatten`); the wire's is the module's `leaf_specs`."""
        return layer_specs(self.arch)

    def init_leaves(self) -> dict:
        return init_leaves(self.arch)

    def forward(self, leaves, rows, *, with_logits=False):
        return forward(leaves, rows, self.arch, with_logits=with_logits)

    def loss_and_counts(self, leaves, rows, mask):
        return loss_and_counts(leaves, rows, mask, self.arch)

    @property
    def slots_a_token(self) -> int:
        return slots_a_token(self.arch)

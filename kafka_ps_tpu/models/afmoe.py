"""A third language-model family: `afmoe` (Arcee Trinity-Mini's
published shape,
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json).

    x0 = E[tokens] * sqrt(hidden_size)            (mup_enabled)
    a  = x + N2(Attn(N1(x)))                      four norms a layer,
    y  = a + N4(MLP(N3(a)))                       each with its own weight

then a final RMSNorm and an untied head over the held slice of the
vocabulary; the loss is the mean next-token cross-entropy.  The layers
are of two kinds twice over: `layer_types` says which attend under a
sliding window and which over the whole row, `num_dense_layers` how
many leading layers have a dense SwiGLU MLP before the expert layers
begin.

Token rows, the norm, RoPE, the blocked attention core, the router, the
expert layer that knows its share and its gated expert, the head and its
loss, the flat key space, the solver with its counters and the task's
frame are `models/lm_common.py`'s, shared with `glm4_moe_lite` and
`nemotron_h`.  This family's own:

  * gated grouped-query attention with QK-norm: `q = u W_q` as `[S,
    heads, head_dim]`, `k`, `v` as `[S, kv heads, head_dim]`, `g = u
    W_g` as `[S, heads * head_dim]`; `q` and `k` each through an RMSNorm
    over the head's channels with a weight of its own; IN A SLIDING
    LAYER rotate-half RoPE over all the channels on q and k, IN A FULL
    LAYER no positional encoding; scores / sqrt(head_dim), query i sees
    key j iff `j <= i` and in a sliding layer also `i - j <
    sliding_window`; `out = (softmax(scores) v * sigmoid(g)) W_o`; no
    bias.  Scores, softmax and values are `lm_common.blocked_attention`,
    a tile of `attention_block` queries at a time, each against the keys
    its band reaches: a sliding layer costs S x window, not S x S;
  * a dense SwiGLU MLP in the leading layers; in the others a sigmoid
    router over all `num_experts` (top `num_experts_per_tok`, weights
    normalised by `route_norm`, times `route_scale`), SwiGLU experts and
    one shared expert of the same form.

Every layer is recomputed in the backward pass (`jax.checkpoint`); where
the attention core runs as plain tiles, each tile inside it once more,
and where it runs as the chip's kernel (`models/attention_kernel.py`: a
`head_dim` and a tile of whole lanes, on a TPU) the backward kernel
forms a block's scores once more itself.  The layers are written
out in their published order, each with leaves of its own
(`l<i>.<name>`): their kinds differ, so there is no stack to scan.

Assumed, where the published config says nothing (each also noted in the
benchmark's reference, from the published modelling code as known
without a network): it is the embedding's output that `mup_enabled`
scales, and nothing else; four norms a layer, two before and two after;
the output gate; the head-wise norms of q and k; no positional encoding
in a full layer; the selection bias held fixed at zero (no gradient
reaches it: it only selects); initialisation normal(0, `init_std`) from
`init_seed`, norms at one.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models.lm_common import sub, swiglu

SLIDING, FULL = "sliding_attention", "full_attention"
# the attention core's tile: 512 queries, or the largest tile under it
# that divides the row
ATTENTION_BLOCK = 512
# the device's counters are int32 a dispatch, and a chunk of 32 updates
# at 4,096 tokens covers 2.4e9 pairs in its sliding layers: the pair
# counters count in units of 1,024 pairs
PAIRS_UNIT = 1024


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The published keys (names as in config.json), then the cut, then
    what had to be assumed."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    layer_types: tuple
    sliding_window: int
    num_hidden_layers: int
    num_dense_layers: int
    num_experts: int
    num_shared_experts: int
    num_experts_per_tok: int
    route_norm: bool
    route_scale: float
    rms_norm_eps: float
    rope_theta: float
    mup_enabled: bool
    vocab_size: int
    # the cut
    experts_held: int
    expert_offset: int
    vocab_held: int
    sequence_length: int
    # assumed
    init_std: float = 0.02
    init_seed: int = 0

    def __post_init__(self):
        # a JSON list; the dataclass is frozen and hashed
        object.__setattr__(self, "layer_types", tuple(self.layer_types))

    # what `lm_common.route` and `routed_experts` read, under the names
    # the other families' configs publish them by
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def norm_topk_prob(self) -> bool:
        return self.route_norm

    @property
    def routed_scaling_factor(self) -> float:
        return self.route_scale

    @property
    def attention_block(self) -> int:
        return math.gcd(self.sequence_length, ATTENTION_BLOCK)

    def layers(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    def validate(self) -> None:
        kinds = self.layer_types
        if len(kinds) != self.num_hidden_layers or set(kinds) - {SLIDING,
                                                                 FULL}:
            raise ValueError(
                f"layer_types must name num_hidden_layers layers, each "
                f"{SLIDING} or {FULL}")
        if not 0 <= self.num_dense_layers < self.num_hidden_layers:
            raise ValueError("num_dense_layers must leave an expert layer")
        if self.num_shared_experts != 1:
            raise ValueError("one shared expert is what this family "
                             "implements (num_shared_experts 1)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must divide over "
                             "num_key_value_heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even")
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be at least 1")
        lm.validate_cut(self)


def load_config(path: str) -> AfmoeConfig:
    return lm.load_config(path, "afmoe", AfmoeConfig)


# -- the flat key space --------------------------------------------------------

def layer_specs(dense: bool, c: AfmoeConfig
                ) -> list[tuple[str, tuple[int, ...]]]:
    h, d = c.hidden_size, c.head_dim
    q, kv = c.num_attention_heads * d, c.num_key_value_heads * d
    out = [("in_norm", (h,)), ("wq", (h, q)), ("wk", (h, kv)),
           ("wv", (h, kv)), ("wg", (h, q)), ("q_norm", (d,)),
           ("k_norm", (d,)), ("wo", (q, h)), ("post_attn_norm", (h,)),
           ("pre_mlp_norm", (h,))]
    if dense:
        i = c.intermediate_size
        out += [("w_gate", (h, i)), ("w_up", (h, i)), ("w_down", (i, h))]
    else:
        i, e = c.moe_intermediate_size, c.experts_held
        s = c.num_shared_experts * i
        out += [("router", (h, c.num_experts)),
                ("router_bias", (c.num_experts,)),
                ("e_gate", (e, h, i)), ("e_up", (e, h, i)),
                ("e_down", (e, i, h)),
                ("s_gate", (h, s)), ("s_up", (h, s)), ("s_down", (s, h))]
    return out + [("post_mlp_norm", (h,))]


def leaf_specs(c: AfmoeConfig) -> list[tuple[str, tuple[int, ...]]]:
    """(dotted name, shape) of every leaf, in flat-layout order: the
    embedding, the layers in their published order (`l<i>.`), the final
    norm, the head."""
    out = [("embed", (c.vocab_held, c.hidden_size))]
    for i in range(c.num_hidden_layers):
        out += [(f"l{i}.{n}", s)
                for n, s in layer_specs(i < c.num_dense_layers, c)]
    return out + [("final_norm", (c.hidden_size,)),
                  ("head", (c.hidden_size, c.vocab_held))]


def num_params(c: AfmoeConfig) -> int:
    return lm.num_params(leaf_specs(c))


def init_leaves(c: AfmoeConfig) -> dict:
    """normal(0, init_std) from `init_seed`, one key a leaf by its
    place in the layout; norms one, the selection bias zero."""
    key = jax.random.PRNGKey(c.init_seed)
    out = {}
    for at, (name, shape) in enumerate(leaf_specs(c)):
        last = name.rsplit(".", 1)[-1]
        if last.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif last == "router_bias":
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = c.init_std * jax.random.normal(
                jax.random.fold_in(key, at), shape, jnp.float32)
    return out


# -- the layers ------------------------------------------------------------------

def attention(u, p: dict, c: AfmoeConfig, kind: str):
    """Gated grouped-query attention with QK-norm on `[B, S, H]`
    (already normed), causal within a row; `kind` says whether the layer
    slides (RoPE, the window) or is full (no positions, every earlier
    key)."""
    sliding = kind == SLIDING
    with jax.named_scope("kps.attn"):
        b, s, _ = u.shape
        nh, nkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        # the parts of `kps.attn.proj`: `kps.attn.qkv` (the q, k, v and
        # gate projections), `kps.attn.norm_rope` (a sliding layer's
        # tables, and each of q and k through its head norm and RoPE
        # in one pass, `lm.head_norm_rope`), `kps.attn.out` (the gate
        # applied and the output projection)
        def project(w, heads):
            with jax.named_scope("kps.attn.qkv"):
                return (u @ p[w]).reshape(b, s, heads, d)

        def norm_rope(x, w, scale=1.0):
            with jax.named_scope("kps.attn.norm_rope"):
                return lm.head_norm_rope(x, p[w], c.rms_norm_eps, *tables,
                                         scale=scale)

        with jax.named_scope("kps.attn.proj"):
            with jax.named_scope("kps.attn.norm_rope"):
                tables = lm.rope_angles(s, 1.0 / (c.rope_theta ** (jnp.arange(
                    0, d, 2, dtype=jnp.float32) / d))) if sliding else ()
            # the core's scale rides q's pass
            q = norm_rope(project("wq", nh), "q_norm", 1.0 / math.sqrt(d))
            k = norm_rope(project("wk", nkv), "k_norm")
            v = project("wv", nkv)
            with jax.named_scope("kps.attn.qkv"):
                gate = jax.nn.sigmoid(u @ p["wg"])
            # query head h reads key/value head h // (heads / kv heads)
            q = q.reshape(b, s, nkv, nh // nkv, d)
        with jax.named_scope("kps.attn.window" if sliding
                             else "kps.attn.full"):
            out = lm.blocked_attention(
                q, k, v, window=c.sliding_window if sliding else None,
                block=c.attention_block, scaled=True)
        with jax.named_scope("kps.attn.proj"), \
                jax.named_scope("kps.attn.out"):
            return (out.reshape(b, s, nh * d) * gate) @ p["wo"]


def _experts(xs, p: dict, dot):
    """What `lm_common.routed_experts` is handed: the gated expert on
    its own rows, which take no gradient past the last group (the
    products at these widths are left to the compiler's tiles, and the
    untold kernel leaves those rows as it found them)."""
    return lm.swiglu_experts(lm.live_rows_only(xs, dot.sizes), p, dot)


def _shared_expert(h, p: dict):
    return swiglu(h, p["s_gate"], p["s_up"], p["s_down"])


def layer(x, p: dict, c: AfmoeConfig, kind: str, dense: bool):
    """One layer on `[B, S, H]` -> (its output, an expert layer's counts
    or None)."""
    eps = c.rms_norm_eps
    a = x + lm.block_norm(
        attention(lm.block_norm(x, p["in_norm"], eps), p, c, kind),
        p["post_attn_norm"], eps)
    u = lm.block_norm(a, p["pre_mlp_norm"], eps)
    if dense:
        with jax.named_scope("kps.mlp"):
            y, load = swiglu(u, p["w_gate"], p["w_up"], p["w_down"]), None
    else:
        y, load = lm.expert_layer(u, p, c, _experts, _shared_expert)
    return a + lm.block_norm(y, p["post_mlp_norm"], eps), load


def forward(leaves: dict, rows, c: AfmoeConfig, *, with_logits=False):
    """`rows` `[B, S + 2]` int32 -> per-position losses and the routing
    counts: {"nll" [B, S] next-token, "loads" [expert layers, 3],
    "logits" if asked}.  Every layer is recomputed in the backward pass.
    (A row's last token is carried for another family's second head;
    nothing here reads it.)"""
    s = c.sequence_length
    tokens, t1 = rows[:, :s], rows[:, 1:s + 1]
    with jax.named_scope("kps.lm.embed"):
        x = leaves["embed"][tokens]
        if c.mup_enabled:
            x = x * math.sqrt(c.hidden_size)
    loads = []
    for i, kind in enumerate(c.layer_types):
        x, load = jax.checkpoint(
            lambda x, p, kind=kind, dense=i < c.num_dense_layers:
            layer(x, p, c, kind, dense))(x, sub(leaves, f"l{i}."))
        if load is not None:
            loads.append(load)
    with jax.named_scope("kps.lm.head"):
        nll, logits = jax.checkpoint(
            lambda x, n, hd, t: lm.head_nll(x, n, hd, t, c.rms_norm_eps))(
                x, leaves["final_norm"], leaves["head"], t1)
    out = {"nll": nll, "loads": jnp.stack(loads)}
    if with_logits:
        out["logits"] = logits
    return out


def loss_and_counts(leaves: dict, rows, mask, c: AfmoeConfig):
    """The training objective over the unmasked rows of a slab — mean
    next-token cross-entropy — and (assignments here, Σ largest load,
    expert layers that went over `live_rows_bound`) of the pass."""
    out = forward(leaves, rows, c)
    positions = jnp.maximum(mask.sum(), 1.0) * c.sequence_length
    return ((out["nll"].sum(-1) * mask).sum() / positions,
            out["loads"].sum(0))


def pair_counts(c: AfmoeConfig) -> tuple[int, int, int]:
    """(pairs inside the mask of the sliding layers, of the full layers,
    pairs inside every block the core computes) that one pass over one
    row covers, in pairs."""
    s, w, block = c.sequence_length, c.sliding_window, c.attention_block
    sliding, full = c.layers(SLIDING), c.layers(FULL)
    return (sliding * lm.attention_pairs(s, w),
            full * lm.attention_pairs(s, None),
            sliding * lm.attention_block_pairs(s, w, block)
            + full * lm.attention_block_pairs(s, None, block))


# -- the task ----------------------------------------------------------------------

class AfmoeTask(lm.TokenRowsTask):
    """`lm_common.TokenRowsTask` over this family's leaves and layers."""

    model_type = "afmoe"
    config_cls = AfmoeConfig
    counter_names = lm.COUNTERS + ("attn.pairs_window", "attn.pairs_full",
                                   "attn.block_pairs",
                                   "attn.kernel_block_pairs",
                                   "attn.norm_rope_rows",
                                   "attn.norm_rope_kernel_rows")

    def leaf_specs(self):
        return leaf_specs(self.arch)

    def init_leaves(self) -> dict:
        return init_leaves(self.arch)

    def forward(self, leaves, rows, *, with_logits=False):
        return forward(leaves, rows, self.arch, with_logits=with_logits)

    def loss_and_counts(self, leaves, rows, mask):
        return loss_and_counts(leaves, rows, mask, self.arch)

    @property
    def slots_a_token(self) -> int:
        return self.arch.num_experts_per_tok * self.arch.num_moe_layers

    def own_counts(self, rows) -> tuple:
        """`attn.pairs_window`, `attn.pairs_full`, `attn.block_pairs` of
        one pass, every row of the slab through every layer, in units of
        PAIRS_UNIT pairs (rounded down once a pass); and
        `attn.kernel_block_pairs`, the blocks' pairs again where the
        core ran them as the kernel, 0 where as plain tiles; then
        `norm_rope_counts`' two."""
        c = self.arch
        window, full, blocks = (rows.shape[0] * n // PAIRS_UNIT
                                for n in pair_counts(c))
        heads = c.num_attention_heads // c.num_key_value_heads
        return (window, full, blocks, blocks * lm.kernel_attends(
            (rows.shape[0], c.sequence_length, c.num_key_value_heads, heads,
             c.head_dim), c.attention_block),
                *lm.norm_rope_counts(rows.shape[0], c))

"""A sixth language-model family: `lfm2_moe` (Liquid AI LFM2-24B-A2B's
published shape,
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json).

    x0 = E[tokens]                                (no scale)
    a  = x + Op_kind(N_op(x))                     two RMSNorms a layer,
    y  = a + FF(N_ff(a))                          each with its own weight

then a final RMSNorm and the head, which is the embedding transposed —
ONE leaf `embed`, gathered at the bottom and multiplied at the top,
over the held slice of the vocabulary; the loss is the mean next-token
cross-entropy.  `layer_types` says which layers mix tokens by a gated
short convolution (`conv`) and which by attention (`full_attention`),
`num_dense_layers` how many leading layers have a dense SwiGLU MLP
before the expert layers begin.

Token rows, the norm, the blocked attention core, the head norm and
RoPE of q and k, the expert layer that knows its share and its gated
expert, the head's loss, the flat key space, the solver with its
counters and the task's frame are `models/lm_common.py`'s, shared with
the other five families.  This family's own:

  * the gated short convolution (`short_conv`): one product to three
    streams `[B | C | z] = u W_in` of `hidden_size` channels each, in
    that order; `g = B * z`; a causal depthwise filter of `conv_L_cache`
    taps over the row, `c_t = sum_j w[:, j] * g_(t - L + 1 + j)` with
    zeros before the row's start (`w[:, L - 1]` weighs the token
    itself), no bias (`conv_bias` false), no activation; `Op = (C * c)
    W_out`.  Linear in the row, no state carried, and between the two
    products no matrix work at all.  Its named scopes are the accepted
    ones of a state-space mixer, whose degenerate member a short FIR
    filter is: `kps.ssm` the operator, `kps.ssm.proj` the two products,
    `kps.ssm.conv` the chain gate - filter - gate (`kps.ssm.scan` and
    `kps.ssm.norm` read nothing in this family);
  * grouped-query attention with QK-norm and heads of `hidden_size /
    num_attention_heads` = 64 channels: `q = u W_q` as `[S, heads,
    64]`, `k`, `v` as `[S, kv heads, 64]`; `q` and `k` each through an
    RMSNorm over the head's channels with a weight of its own, then
    rotate-half RoPE over all the channels (`rope_parameters`: one
    theta, `default`); scores / sqrt(64), query i sees key j iff `j <=
    i`; `out = softmax(scores) v W_o`; no bias, no window, no gate.
    At 64 channels the core rides `attention_kernel` two heads to a
    lane vector (8 KV heads: four pairs); `norm_rope_kernel` takes
    whole lanes of 128 only, so the norm and RoPE run as the plain
    lines, on a TPU too;
  * a sigmoid router with a selection bias (`route`): scores over ALL
    `num_experts`, the `num_experts_per_tok` largest of score + bias,
    weights the scores at the chosen over (their sum + 1e-6), times
    `routed_scaling_factor`; `lm_common.route` computes the same but
    for the sum's floor (1e-20 there) and stays the other families'.
    No shared expert.  The routed part is `lm.routed_experts` as it
    stands.

Every layer is recomputed in the backward pass (`jax.checkpoint`); the
layers are written out in their published order, each with leaves of
its own (`l<i>.<name>`): their kinds differ, so there is no stack to
scan.

Assumed, where the published config says nothing (each also in the
benchmark's reference and the configuration's file): (m1) the head-wise
RMSNorm of q and k before RoPE — the public `lfm2_moe` attention has it
and no key switches it; (m2) the head is the embedding transposed — the
configuration class's default, and the catalog's `config` shows no key
against it; (m3) the selection bias is a leaf held at zero (no gradient
reaches it: it only selects; its update is a training recipe no key
defines); (m4) the 1e-6 in the renormalisation, from the public code;
(m5) no auxiliary router loss; initialisation normal(0, `init_std`)
from `init_seed` — the convolution's taps too: there is no `silu`
behind them to flatten a normal start, unlike `nemotron_h`'s — norms at
one.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models.lm_common import sub, swiglu

CONV, FULL = "conv", "full_attention"
# the attention core's tile: 512 queries, or the largest tile under it
# that divides the row
ATTENTION_BLOCK = 512
# the device's counters are int32 a dispatch: the pair counters count in
# units of 1,024 pairs and `conv.mix_rows` in units of 1,024 positions
# (a chunk of 32 updates at 4,096 tokens sends 1.6e6 positions through
# the four conv layers' chains: 1,536 units)
PAIRS_UNIT = 1024
ROWS_UNIT = 1024


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published keys (names as in config.json), then the cut, then
    what had to be assumed."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    layer_types: tuple
    num_hidden_layers: int
    num_dense_layers: int
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    use_expert_bias: bool
    routed_scaling_factor: float
    conv_L_cache: int
    conv_bias: bool
    norm_eps: float
    rope_parameters: tuple
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
        # a JSON list and a JSON object (as its sorted items); the
        # dataclass is frozen and hashed
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "rope_parameters", tuple(sorted(
            dict(self.rope_parameters).items())))

    # what `lm_common.routed_experts` and `norm_rope_counts` read, under
    # the names the other families' configs publish them by
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rope_theta(self) -> float:
        return float(dict(self.rope_parameters)["rope_theta"])

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
        if len(kinds) != self.num_hidden_layers or set(kinds) - {CONV, FULL}:
            raise ValueError(
                f"layer_types must name num_hidden_layers layers, each "
                f"{CONV} or {FULL}")
        if not 0 <= self.num_dense_layers < self.num_hidden_layers:
            raise ValueError("num_dense_layers must leave an expert layer")
        if self.conv_bias:
            raise ValueError("a convolution without bias is what this "
                             "family implements (conv_bias false)")
        if self.conv_L_cache < 1:
            raise ValueError("conv_L_cache must be at least 1")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide over "
                             "num_attention_heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must divide over "
                             "num_key_value_heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even")
        if dict(self.rope_parameters).get("rope_type") != "default":
            raise ValueError("rope_parameters must be of rope_type default")
        lm.validate_cut(self)


def load_config(path: str) -> Lfm2MoeConfig:
    return lm.load_config(path, "lfm2_moe", Lfm2MoeConfig)


# -- the flat key space --------------------------------------------------------

def layer_specs(kind: str, dense: bool, c: Lfm2MoeConfig
                ) -> list[tuple[str, tuple[int, ...]]]:
    h, d = c.hidden_size, c.head_dim
    out = [("operator_norm", (h,))]
    if kind == CONV:
        out += [("w_in", (h, 3 * h)), ("conv", (h, c.conv_L_cache)),
                ("w_out", (h, h))]
    else:
        q, kv = c.num_attention_heads * d, c.num_key_value_heads * d
        out += [("wq", (h, q)), ("wk", (h, kv)), ("wv", (h, kv)),
                ("q_norm", (d,)), ("k_norm", (d,)), ("wo", (q, h))]
    out += [("ffn_norm", (h,))]
    if dense:
        i = c.intermediate_size
        return out + [("w_gate", (h, i)), ("w_up", (h, i)),
                      ("w_down", (i, h))]
    i, e = c.moe_intermediate_size, c.experts_held
    return out + [("router", (h, c.num_experts)),
                  ("router_bias", (c.num_experts,)),
                  ("e_gate", (e, h, i)), ("e_up", (e, h, i)),
                  ("e_down", (e, i, h))]


def leaf_specs(c: Lfm2MoeConfig) -> list[tuple[str, tuple[int, ...]]]:
    """(dotted name, shape) of every leaf, in flat-layout order: the
    embedding (which is the head too), the layers in their published
    order (`l<i>.`), the final norm."""
    out = [("embed", (c.vocab_held, c.hidden_size))]
    for i, kind in enumerate(c.layer_types):
        out += [(f"l{i}.{n}", s)
                for n, s in layer_specs(kind, i < c.num_dense_layers, c)]
    return out + [("final_norm", (c.hidden_size,))]


def num_params(c: Lfm2MoeConfig) -> int:
    return lm.num_params(leaf_specs(c))


def init_leaves(c: Lfm2MoeConfig) -> dict:
    """normal(0, init_std) from `init_seed`, one key a leaf by its
    place in the layout, the convolution's taps too; norms one, the
    selection bias zero."""
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

def causal_conv(g, w):
    """Depthwise over `[B, S, C]` with `w` `[C, L]`: position t sees
    t - L + 1 .. t, zeros before the row's start; `w[:, L - 1]` weighs
    the position itself.  L shifted multiply-adds, no bias."""
    taps, s = w.shape[1], g.shape[1]
    padded = jnp.pad(g, ((0, 0), (taps - 1, 0), (0, 0)))
    out = padded[:, :s] * w[:, 0]
    for j in range(1, taps):
        out = out + padded[:, j:j + s] * w[:, j]
    return out


def short_conv(u, p: dict, c: Lfm2MoeConfig):
    """The gated short convolution on `[B, S, H]` (already normed):
    `(C * conv(B * z)) W_out` of `[B | C | z] = u W_in`."""
    h = c.hidden_size
    with jax.named_scope("kps.ssm"):
        with jax.named_scope("kps.ssm.proj"):
            bcz = u @ p["w_in"]
        with jax.named_scope("kps.ssm.conv"):
            gated = bcz[..., :h] * bcz[..., 2 * h:]
            mixed = bcz[..., h:2 * h] * causal_conv(gated, p["conv"])
        with jax.named_scope("kps.ssm.proj"):
            return mixed @ p["w_out"]


def attention(u, p: dict, c: Lfm2MoeConfig):
    """Grouped-query attention with QK-norm and RoPE on `[B, S, H]`
    (already normed), causal within a row, every earlier key."""
    with jax.named_scope("kps.attn"):
        b, s, _ = u.shape
        nh, nkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim

        def project(w, heads):
            with jax.named_scope("kps.attn.qkv"):
                return (u @ p[w]).reshape(b, s, heads, d)

        def norm_rope(x, w, scale=1.0):
            with jax.named_scope("kps.attn.norm_rope"):
                return lm.head_norm_rope(x, p[w], c.norm_eps, *tables,
                                         scale=scale)

        with jax.named_scope("kps.attn.proj"):
            with jax.named_scope("kps.attn.norm_rope"):
                tables = lm.rope_angles(s, 1.0 / (c.rope_theta ** (jnp.arange(
                    0, d, 2, dtype=jnp.float32) / d)))
            # the core's scale rides q's pass
            q = norm_rope(project("wq", nh), "q_norm", 1.0 / math.sqrt(d))
            k = norm_rope(project("wk", nkv), "k_norm")
            v = project("wv", nkv)
            # query head h reads key/value head h // (heads / kv heads)
            q = q.reshape(b, s, nkv, nh // nkv, d)
        with jax.named_scope("kps.attn.full"):
            out = lm.blocked_attention(q, k, v, window=None,
                                       block=c.attention_block, scaled=True)
        with jax.named_scope("kps.attn.proj"), \
                jax.named_scope("kps.attn.out"):
            return out.reshape(b, s, nh * d) @ p["wo"]


def route(h, router, bias, c: Lfm2MoeConfig):
    """Every token over ALL experts -> (chosen experts [T, K], their
    weights [T, K]): sigmoid scores, float32 at `highest` precision,
    the K largest of score + bias (the bias selects and weighs
    nothing), the chosen scores over (their sum + 1e-6) where
    `norm_topk_prob`, times `routed_scaling_factor`."""
    with jax.named_scope("kps.moe.route"):
        s = jax.nn.sigmoid(jnp.dot(h, router,
                                   precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(s + bias if c.use_expert_bias else s,
                               c.num_experts_per_tok)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if c.norm_topk_prob:
            w = w / (w.sum(-1, keepdims=True) + 1e-6)
        return idx, w * c.routed_scaling_factor


def _experts(xs, p: dict, dot):
    """What `lm_common.routed_experts` is handed: the gated expert on
    its own rows, which take no gradient past the last group (the
    products at these widths are left to the compiler's tiles, and the
    untold kernel leaves those rows as it found them)."""
    return lm.swiglu_experts(lm.live_rows_only(xs, dot.sizes), p, dot)


def expert_layer(u, p: dict, c: Lfm2MoeConfig):
    """The expert layer on `[B, S, H]` (already normed) -> (the held
    experts' part of its output, `lm_common.routed_experts`' counts)."""
    b, s, hd = u.shape
    h = u.reshape(b * s, hd)
    idx, w = route(h, p["router"], p["router_bias"], c)
    y, load = lm.routed_experts(h, idx, w, p, c, _experts)
    return y.reshape(b, s, hd), load


def layer(x, p: dict, c: Lfm2MoeConfig, kind: str, dense: bool):
    """One layer on `[B, S, H]` -> (its output, an expert layer's counts
    or None)."""
    u = lm.block_norm(x, p["operator_norm"], c.norm_eps)
    a = x + (short_conv(u, p, c) if kind == CONV else attention(u, p, c))
    u = lm.block_norm(a, p["ffn_norm"], c.norm_eps)
    if dense:
        with jax.named_scope("kps.mlp"):
            y, load = swiglu(u, p["w_gate"], p["w_up"], p["w_down"]), None
    else:
        y, load = expert_layer(u, p, c)
    return a + y, load


def forward(leaves: dict, rows, c: Lfm2MoeConfig, *, with_logits=False):
    """`rows` `[B, S + 2]` int32 -> per-position losses and the routing
    counts: {"nll" [B, S] next-token, "loads" [expert layers, 3 or 4:
    what `lm_common.routed_experts` counts], "logits" if asked}.  Every
    layer is recomputed in the backward pass.  (A row's last token is
    carried for another family's second head; nothing here reads it.)"""
    s = c.sequence_length
    tokens, t1 = rows[:, :s], rows[:, 1:s + 1]
    with jax.named_scope("kps.lm.embed"):
        x = leaves["embed"][tokens]
    loads = []
    for i, kind in enumerate(c.layer_types):
        x, load = jax.checkpoint(
            lambda x, p, kind=kind, dense=i < c.num_dense_layers:
            layer(x, p, c, kind, dense))(x, sub(leaves, f"l{i}."))
        if load is not None:
            loads.append(load)
    with jax.named_scope("kps.lm.head"):
        # the head is the embedding transposed: the one leaf's gradient
        # is the gather's scatter of rows plus this product's
        nll, logits = jax.checkpoint(
            lambda x, n, e, t: lm.head_nll(x, n, e.T, t, c.norm_eps))(
                x, leaves["final_norm"], leaves["embed"], t1)
    out = {"nll": nll, "loads": jnp.stack(loads)}
    if with_logits:
        out["logits"] = logits
    return out


def loss_and_counts(leaves: dict, rows, mask, c: Lfm2MoeConfig):
    """The training objective over the unmasked rows of a slab — mean
    next-token cross-entropy — and (assignments here, Σ largest load,
    expert layers that went over `live_rows_bound`) of the pass."""
    out = forward(leaves, rows, c)
    positions = jnp.maximum(mask.sum(), 1.0) * c.sequence_length
    return ((out["nll"].sum(-1) * mask).sum() / positions,
            out["loads"].sum(0))


def pair_counts(c: Lfm2MoeConfig) -> tuple[int, int, int]:
    """(pairs inside the mask of the sliding layers — this family has
    none —, of the full layers, pairs inside every block the core
    computes) that one pass over one row covers, in pairs."""
    s, full = c.sequence_length, c.layers(FULL)
    return (0, full * lm.attention_pairs(s, None),
            full * lm.attention_block_pairs(s, None, c.attention_block))


# -- the task ----------------------------------------------------------------------

class Lfm2MoeTask(lm.TokenRowsTask):
    """`lm_common.TokenRowsTask` over this family's leaves and layers."""

    model_type = "lfm2_moe"
    config_cls = Lfm2MoeConfig
    counter_names = lm.COUNTERS + ("attn.pairs_window", "attn.pairs_full",
                                   "attn.block_pairs",
                                   "attn.kernel_block_pairs",
                                   "attn.norm_rope_rows",
                                   "attn.norm_rope_kernel_rows",
                                   "conv.mix_rows")

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
        """`attn.pairs_window` (0: no layer slides), `attn.pairs_full`,
        `attn.block_pairs` and `attn.kernel_block_pairs` of one pass as
        the `afmoe` family counts them, `lm_common.norm_rope_counts`'
        two over the ATTENTION layers alone, and `conv.mix_rows`: the
        positions that go through a convolution chain, every row of the
        slab through every conv layer, in units of ROWS_UNIT — all
        rounded down once a pass."""
        c = self.arch
        window, full, blocks = (rows.shape[0] * n // PAIRS_UNIT
                                for n in pair_counts(c))
        heads = c.num_attention_heads // c.num_key_value_heads
        attending = dataclasses.replace(c, num_hidden_layers=c.layers(FULL))
        return (window, full, blocks, blocks * lm.kernel_attends(
            (rows.shape[0], c.sequence_length, c.num_key_value_heads, heads,
             c.head_dim), c.attention_block),
                *lm.norm_rope_counts(rows.shape[0], attending),
                rows.shape[0] * c.sequence_length * c.layers(CONV)
                // ROWS_UNIT)

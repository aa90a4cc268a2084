"""A fifth language-model family: `mellum` (JetBrains Mellum2-12B-A2.5B's
published shape,
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json).

    x0 = E[tokens]                                (no scale)
    a  = x + Attn_kind(N1(x))                     two RMSNorms a layer,
    y  = a + MoE(N2(a))                           each with its own weight

then a final RMSNorm and an untied head over the held slice of the
vocabulary; the loss is the mean next-token cross-entropy.  Every layer
is an expert layer (`mlp_layer_types` all `sparse`; the published
`intermediate_size` 7168 is read by no layer), and `layer_types` says
which attend under a sliding window and which over the whole row.

Token rows, the norm, the blocked attention core (on a TPU a kernel),
the expert layer that knows its share and its gated expert, the head and
its loss, the flat key space, the solver with its counters and the
task's frame are `models/lm_common.py`'s, shared with the other four
families.  This family's own:

  * grouped-query attention with QK-norm and no gate: `q = u W_q` as
    `[S, heads, head_dim]`, `k`, `v` as `[S, kv heads, head_dim]`; `q`
    and `k` each through an RMSNorm over the head's channels with a
    weight of its own; rotate-half RoPE over all the channels on q and k
    IN EVERY LAYER, by the rule `rope_parameters` gives the layer's
    kind (`rope_tables`): in a sliding layer one theta, in a full layer
    YaRN — each frequency a blend of itself and itself / `factor`, by a
    ramp between the channels that turn `beta_fast` and `beta_slow`
    times over `original_max_position_embeddings`, and cos and sin each
    times `attention_factor`; scores / sqrt(head_dim), query i sees key
    j iff `j <= i` and in a sliding layer also `i - j < sliding_window`;
    `out = softmax(scores) v W_o`; no bias;
  * a softmax router (`route`): probabilities over ALL `num_experts`,
    the `num_experts_per_tok` largest, renormalised over the chosen
    (`norm_topk_prob`); no bias, no scale, no shared expert, no dense
    layer.  The routed part is `lm.routed_experts` as it stands.

Every layer is recomputed in the backward pass (`jax.checkpoint`); the
layers are written out in their published order, each with leaves of
its own (`l<i>.<name>`).

Assumed, where the published config says nothing (each also in the
benchmark's reference and the configuration's file): (m1) the head-wise
RMSNorm of q and k before RoPE — the config has no key for it, and
neither has the Qwen3-MoE configuration class whose keys these are and
whose attention norms q and k so; (m2) the multi-token-prediction head
the model card mentions has no key in the config and is left out; (m3)
no auxiliary router loss; initialisation normal(0, `init_std`) from
`init_seed`, norms at one.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models.lm_common import sub

SLIDING, FULL = "sliding_attention", "full_attention"
# the attention core's tile: 512 queries, or the largest tile under it
# that divides the row
ATTENTION_BLOCK = 512
# the device's counters are int32 a dispatch: the pair counters, the
# attention core's and the placement's (`moe.place_pairs`), count in
# units of 1,024 pairs (a chunk of 32 updates at 4,096 tokens places
# 2.6e10 (row, token) pairs)
PAIRS_UNIT = 1024


def _frozen(value):
    """A JSON object as nested tuples of its sorted items: the
    dataclass is frozen and hashed."""
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    return value


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """The published keys (names as in config.json), then the cut, then
    what had to be assumed."""

    hidden_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    layer_types: tuple
    mlp_layer_types: tuple
    sliding_window: int
    num_hidden_layers: int
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    rms_norm_eps: float
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
        # JSON lists and objects; the dataclass is frozen and hashed
        for name in ("layer_types", "mlp_layer_types"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        object.__setattr__(self, "rope_parameters",
                           _frozen(self.rope_parameters))

    # what `lm_common.routed_experts` reads, under the name the other
    # families' configs publish it by
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def attention_block(self) -> int:
        return math.gcd(self.sequence_length, ATTENTION_BLOCK)

    def rope(self, kind: str) -> dict:
        """`rope_parameters` of one kind of layer."""
        return dict(dict(self.rope_parameters)[kind])

    def layers(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def validate(self) -> None:
        kinds = self.layer_types
        if len(kinds) != self.num_hidden_layers or set(kinds) - {SLIDING,
                                                                 FULL}:
            raise ValueError(
                f"layer_types must name num_hidden_layers layers, each "
                f"{SLIDING} or {FULL}")
        if self.mlp_layer_types != ("sparse",) * self.num_hidden_layers:
            raise ValueError("every layer's MLP is an expert layer "
                             "(mlp_layer_types all 'sparse') in what this "
                             "family implements")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must divide over "
                             "num_key_value_heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even")
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be at least 1")
        for kind in set(kinds):
            rule = dict(self.rope_parameters).get(kind)
            if rule is None or dict(rule).get("rope_type") not in (
                    "default", "yarn"):
                raise ValueError(f"rope_parameters[{kind!r}] must be of "
                                 "rope_type default or yarn")
        lm.validate_cut(self)


def load_config(path: str) -> MellumConfig:
    return lm.load_config(path, "mellum", MellumConfig)


# -- the flat key space --------------------------------------------------------

def layer_specs(c: MellumConfig) -> list[tuple[str, tuple[int, ...]]]:
    h, d = c.hidden_size, c.head_dim
    q, kv = c.num_attention_heads * d, c.num_key_value_heads * d
    i, e = c.moe_intermediate_size, c.experts_held
    return [("in_norm", (h,)), ("wq", (h, q)), ("wk", (h, kv)),
            ("wv", (h, kv)), ("q_norm", (d,)), ("k_norm", (d,)),
            ("wo", (q, h)), ("post_attn_norm", (h,)),
            ("router", (h, c.num_experts)),
            ("e_gate", (e, h, i)), ("e_up", (e, h, i)),
            ("e_down", (e, i, h))]


def leaf_specs(c: MellumConfig) -> list[tuple[str, tuple[int, ...]]]:
    """(dotted name, shape) of every leaf, in flat-layout order: the
    embedding, the layers in their published order (`l<i>.`), the final
    norm, the head."""
    out = [("embed", (c.vocab_held, c.hidden_size))]
    for i in range(c.num_hidden_layers):
        out += [(f"l{i}.{n}", s) for n, s in layer_specs(c)]
    return out + [("final_norm", (c.hidden_size,)),
                  ("head", (c.hidden_size, c.vocab_held))]


def num_params(c: MellumConfig) -> int:
    return lm.num_params(leaf_specs(c))


def init_leaves(c: MellumConfig) -> dict:
    """normal(0, init_std) from `init_seed`, one key a leaf by its
    place in the layout; norms one."""
    key = jax.random.PRNGKey(c.init_seed)
    out = {}
    for at, (name, shape) in enumerate(leaf_specs(c)):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = c.init_std * jax.random.normal(
                jax.random.fold_in(key, at), shape, jnp.float32)
    return out


# -- positions -----------------------------------------------------------------

def yarn_correction_dim(turns: float, dim: int, theta: float,
                        positions: int) -> float:
    """The (fractional) channel pair whose angle turns `turns` times
    over `positions` positions."""
    return dim * math.log(positions / (turns * 2 * math.pi)) / (
        2 * math.log(theta))


def rope_tables(rule: dict, dim: int) -> tuple[np.ndarray, float]:
    """(inv_freq `[dim / 2]` float32, what cos and sin are each
    multiplied by) of one kind of layer's `rope_parameters`.

    `default`: `inv_freq_i = theta^(-2i/dim)`, factor 1.  `yarn`: with
    `low = max(floor(d(beta_fast)), 0)`, `high = min(ceil(d(beta_slow)),
    dim - 1)` (`d`: `yarn_correction_dim` over
    `original_max_position_embeddings`; `truncate`, the default) and
    `ramp_i = clip((i - low) / (high - low), 0, 1)`, a frequency is `(1
    - ramp_i)` of itself and `ramp_i` of itself / `factor`: the fast
    channels turn as they did, the slow ones `factor` times slower; and
    `attention_factor` (0.1 ln factor + 1 where the config gives none)
    on cos and sin."""
    theta = float(rule["rope_theta"])
    pairs = np.arange(dim // 2, dtype=np.float64)
    inv = theta ** (-2.0 * pairs / dim)
    if rule["rope_type"] == "default":
        return inv.astype(np.float32), 1.0
    factor = float(rule["factor"])
    low, high = (yarn_correction_dim(
        rule.get(beta, default), dim, theta,
        rule["original_max_position_embeddings"])
                 for beta, default in (("beta_fast", 32), ("beta_slow", 1)))
    low, high = max(math.floor(low), 0), min(math.ceil(high), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((pairs - low) / (high - low), 0.0, 1.0)
    scale = rule.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return ((1.0 - ramp) * inv + ramp * inv / factor).astype(
        np.float32), float(scale)


# -- the layers ------------------------------------------------------------------

def attention(u, p: dict, c: MellumConfig, kind: str):
    """Grouped-query attention with QK-norm on `[B, S, H]` (already
    normed), causal within a row; `kind` says whether the layer slides
    (plain RoPE, the window) or is full (YaRN, every earlier key)."""
    sliding = kind == SLIDING
    with jax.named_scope("kps.attn"):
        b, s, _ = u.shape
        nh, nkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim

        def project(w, heads):
            with jax.named_scope("kps.attn.qkv"):
                return (u @ p[w]).reshape(b, s, heads, d)

        def norm_rope(x, w, scale=1.0):
            with jax.named_scope("kps.attn.norm_rope"):
                return lm.head_norm_rope(x, p[w], c.rms_norm_eps, *tables,
                                         scale=scale)

        with jax.named_scope("kps.attn.proj"):
            with jax.named_scope("kps.attn.norm_rope"):
                tables = lm.rope_angles(s, *rope_tables(c.rope(kind), d))
            # the core's scale rides q's pass
            q = norm_rope(project("wq", nh), "q_norm", 1.0 / math.sqrt(d))
            k = norm_rope(project("wk", nkv), "k_norm")
            v = project("wv", nkv)
            # query head h reads key/value head h // (heads / kv heads)
            q = q.reshape(b, s, nkv, nh // nkv, d)
        with jax.named_scope("kps.attn.window" if sliding
                             else "kps.attn.full"):
            out = lm.blocked_attention(
                q, k, v, window=c.sliding_window if sliding else None,
                block=c.attention_block, scaled=True)
        with jax.named_scope("kps.attn.proj"), \
                jax.named_scope("kps.attn.out"):
            return out.reshape(b, s, nh * d) @ p["wo"]


def route(h, router, c: MellumConfig):
    """Every token over ALL experts -> (chosen experts [T, K], their
    weights [T, K]): a softmax over all `num_experts`, float32 at
    `highest` precision, the K largest, renormalised over the chosen
    where `norm_topk_prob`."""
    with jax.named_scope("kps.moe.route"):
        prob = jax.nn.softmax(jnp.dot(
            h, router, precision=jax.lax.Precision.HIGHEST), axis=-1)
        w, idx = jax.lax.top_k(prob, c.num_experts_per_tok)
        if c.norm_topk_prob:
            w = w / w.sum(-1, keepdims=True)
        return idx, w


def _experts(xs, p: dict, dot):
    """What `lm_common.routed_experts` is handed: the gated expert on
    its own rows, which take no gradient past the last group (where a
    product is left to the compiler's tiles the untold kernel leaves
    those rows as it found them; `told_grouped` masks its own)."""
    return lm.swiglu_experts(lm.live_rows_only(xs, dot.sizes), p, dot)


def expert_layer(u, p: dict, c: MellumConfig):
    """The expert layer on `[B, S, H]` (already normed) -> (the held
    experts' part of its output, `lm_common.routed_experts`' counts)."""
    b, s, hd = u.shape
    h = u.reshape(b * s, hd)
    idx, w = route(h, p["router"], c)
    y, load = lm.routed_experts(h, idx, w, p, c, _experts)
    return y.reshape(b, s, hd), load


def layer(x, p: dict, c: MellumConfig, kind: str):
    """One layer on `[B, S, H]` -> (its output, the expert layer's
    counts)."""
    eps = c.rms_norm_eps
    a = x + attention(lm.block_norm(x, p["in_norm"], eps), p, c, kind)
    y, load = expert_layer(lm.block_norm(a, p["post_attn_norm"], eps), p, c)
    return a + y, load


def forward(leaves: dict, rows, c: MellumConfig, *, with_logits=False):
    """`rows` `[B, S + 2]` int32 -> per-position losses and the routing
    counts: {"nll" [B, S] next-token, "loads" [layers, 3 or 4: what
    `lm_common.routed_experts` counts], "logits" if asked}.  Every
    layer is recomputed in the backward pass.  (A row's last token is
    carried for another family's second head; nothing here reads it.)"""
    s = c.sequence_length
    tokens, t1 = rows[:, :s], rows[:, 1:s + 1]
    with jax.named_scope("kps.lm.embed"):
        x = leaves["embed"][tokens]
    loads = []
    for i, kind in enumerate(c.layer_types):
        x, load = jax.checkpoint(
            lambda x, p, kind=kind: layer(x, p, c, kind))(
                x, sub(leaves, f"l{i}."))
        loads.append(load)
    with jax.named_scope("kps.lm.head"):
        nll, logits = jax.checkpoint(
            lambda x, n, hd, t: lm.head_nll(x, n, hd, t, c.rms_norm_eps))(
                x, leaves["final_norm"], leaves["head"], t1)
    out = {"nll": nll, "loads": jnp.stack(loads)}
    if with_logits:
        out["logits"] = logits
    return out


def loss_and_counts(leaves: dict, rows, mask, c: MellumConfig):
    """The training objective over the unmasked rows of a slab — mean
    next-token cross-entropy — and (assignments here, Σ largest load,
    expert layers that went over `live_rows_bound`; where the placement
    kernels run, the pairs they multiplied) of the pass."""
    out = forward(leaves, rows, c)
    positions = jnp.maximum(mask.sum(), 1.0) * c.sequence_length
    return ((out["nll"].sum(-1) * mask).sum() / positions,
            out["loads"].sum(0))


def pair_counts(c: MellumConfig) -> tuple[int, int, int]:
    """(pairs inside the mask of the sliding layers, of the full layers,
    pairs inside every block the core computes) that one pass over one
    row covers, in pairs."""
    s, w, block = c.sequence_length, c.sliding_window, c.attention_block
    sliding, full = c.layers(SLIDING), c.layers(FULL)
    return (sliding * lm.attention_pairs(s, w),
            full * lm.attention_pairs(s, None),
            sliding * lm.attention_block_pairs(s, w, block)
            + full * lm.attention_block_pairs(s, None, block))


def place_pairs(tokens: int, c: MellumConfig) -> tuple[int, int]:
    """(placed row, token) pairs of ONE expert layer's pass over
    `tokens` tokens — the elements of `routed_experts`' 0/1 matrix —
    (under `live_rows_bound`, over it: all T·K slots)."""
    slots = tokens * c.num_experts_per_tok
    return lm.live_rows_bound(slots, c) * tokens, slots * tokens


# -- the task ----------------------------------------------------------------------

class MellumTask(lm.TokenRowsTask):
    """`lm_common.TokenRowsTask` over this family's leaves and layers."""

    model_type = "mellum"
    config_cls = MellumConfig
    counter_names = lm.COUNTERS + ("attn.pairs_window", "attn.pairs_full",
                                   "attn.block_pairs",
                                   "attn.kernel_block_pairs",
                                   "attn.norm_rope_rows",
                                   "attn.norm_rope_kernel_rows",
                                   "moe.place_pairs_dense",
                                   "moe.place_pairs")

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
        return self.arch.num_experts_per_tok * self.arch.num_hidden_layers

    def own_counts(self, rows) -> tuple:
        """`attn.pairs_window`, `attn.pairs_full`, `attn.block_pairs`
        and `attn.kernel_block_pairs` of one pass as the `afmoe` family
        counts them, `lm_common.norm_rope_counts`' two, and
        `moe.place_pairs_dense`: the (placed row,
        token) pairs of every expert layer of one pass with each layer
        under its bound (`fit_counted` adds what the passes over it
        placed more) — all in units of PAIRS_UNIT pairs, rounded down
        once a pass."""
        c = self.arch
        window, full, blocks = (rows.shape[0] * n // PAIRS_UNIT
                                for n in pair_counts(c))
        heads = c.num_attention_heads // c.num_key_value_heads
        under, _ = place_pairs(rows.shape[0] * c.sequence_length, c)
        return (window, full, blocks, blocks * lm.kernel_attends(
            (rows.shape[0], c.sequence_length, c.num_key_value_heads, heads,
             c.head_dim), c.attention_block),
                *lm.norm_rope_counts(rows.shape[0], c),
                c.num_hidden_layers * under // PAIRS_UNIT)

    def fit_counted(self, leaves, x, enc, mask):
        """The frame's, with the two counters of `routed_experts`' 0/1
        matrix made whole.  `moe.place_pairs_dense`, the whole matrix's
        elements (the last of `own_counts`): an expert layer's pass
        that went over `live_rows_bound` placed all T·K slots, and
        `moe.passes_over_bound` says how many did.  `moe.place_pairs`,
        the elements the program MULTIPLIED: where the placement
        kernels run (`placement_kernel.takes`) the layers counted them
        on the device, a pass's fourth count; where the product runs it
        multiplied the whole matrix."""
        new, loss, stats = super().fit_counted(leaves, x, enc, mask)
        under, over = place_pairs(x.shape[0] * self.arch.sequence_length,
                                  self.arch)
        went_over = stats[lm.COUNTERS.index("moe.passes_over_bound")]
        dense = self.counter_names.index("moe.place_pairs_dense")
        stats = stats.at[dense].add(
            went_over * ((over - under) // PAIRS_UNIT))
        if stats.shape[0] == len(self.counter_names):
            return new, loss, stats
        return new, loss, jnp.concatenate([stats, stats[dense:dense + 1]])

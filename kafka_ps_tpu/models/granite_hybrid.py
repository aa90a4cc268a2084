"""A seventh language-model family: `granitemoehybrid` (IBM
Granite-4.0-H-Micro's published shape,
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json).

    x0 = embedding_multiplier * E[tokens]
    a  = x + residual_multiplier * Mix_kind(N_in(x))     two RMSNorms a layer,
    y  = a + residual_multiplier * MLP(N_post(a))        each with its own weight
    logits = (N_f(x_L) E^T) / logits_scaling

the head being the embedding transposed — ONE leaf `embed`, gathered at
the bottom and multiplied at the top, over the held slice of the
vocabulary; the loss is the mean next-token cross-entropy.
`layer_types` says which layers mix tokens by a Mamba-2 state-space
mixer (`mamba`) and which by attention (`attention`); EVERY layer has a
dense SwiGLU MLP behind its mixer (`num_local_experts` 0: the block's
MLP is the `shared_mlp` alone, at `shared_intermediate_size`).

Token rows, the norm, the blocked attention core, the gated MLP, the
head's loss, the flat key space, the solver with its counters and the
task's frame are `models/lm_common.py`'s, shared with the other six
families; the Mamba-2 mixer is `models/nemotron_h.py`'s, whole
(`mamba2`: `[z | xBC | dt] = u W_in`, the causal 4-tap convolution with
bias and `silu`, `ssd_chunked`'s recurrence in chunks of
`mamba_chunk_size` — on a TPU, at the published 64 heads of 64 channels
and chunks of 256, `models/ssd_kernel.py`'s kernels: the `[256, 256]`
decays and weighted scores of a head and the state between the chunks
live in VMEM; anywhere else the einsums — the `D` term, the gate BEFORE
the norm, no projection bias) — imported, not copied, and read here at
ONE group of B and C that all the heads share.  This family's own:

  * a block of two branches, each added back times
    `residual_multiplier`, and the three other scalars: the embedding
    times `embedding_multiplier`, the scores times
    `attention_multiplier` (1/64 at heads of 64 channels, NOT
    1/sqrt(64)), the logits over `logits_scaling`;
  * grouped-query attention with NO positional encoding
    (`position_embedding_type` `nope`) and no head norm: `q = u W_q` as
    `[S, heads, 64]`, `k`, `v` as `[S, kv heads, 64]`, query i sees key
    j iff `j <= i`, `out = softmax(scores) v W_o`; q carries the scale
    into `lm.blocked_attention(..., scaled=True)`;
  * the MLP's first matrix holds gate and up side by side (`w1` `[H, 2
    I]`, gate first: the public code's `chunk(2)`).

Every layer is recomputed in the backward pass (`jax.checkpoint`); the
layers are written out in their published order, each with leaves of
its own (`l<i>.<name>`): their kinds differ, so there is no stack to
scan.

Assumed, where the published config says nothing (each also in the
benchmark's reference and the configuration's file): (m1) the Mamba-2
start is `nemotron_h`'s — `A_log = log(uniform[1, 16])`, `dt_bias` the
inverse softplus of log-uniform[`time_step_min`, `time_step_max`]
floored at `time_step_floor` (0.001, 0.1, 1e-4: the config has no
`time_step_*` key), `D` one, the convolution's weights and bias
uniform[-1/sqrt(k), 1/sqrt(k)]; (m2) no clamp on softplus(dt + dt_bias)
(`time_step_limit` (0, inf)); (m3) no RoPE anywhere (`rope_theta` is
unused); (m4) gate first in `w1`; initialisation normal(0, `init_std`)
from `init_seed`, norms at one.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import nemotron_h
from kafka_ps_tpu.models.lm_common import sub, swiglu

MAMBA, ATTENTION = "mamba", "attention"
# the attention core's tile: 512 queries, or the largest tile under it
# that divides the row
ATTENTION_BLOCK = 512
# the device's counters are int32 a dispatch: the pair counters count in
# units of 1,024 pairs, as `afmoe` counts them, and `mlp.rows` in units
# of 1,024 positions (a chunk of 32 updates at 2,048 tokens sends 1.97e6
# positions through the ten layers' MLPs: 1,920 units)
PAIRS_UNIT = 1024
ROWS_UNIT = 1024


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The published keys (names as in config.json), then the cut, then
    what had to be assumed."""

    hidden_size: int
    shared_intermediate_size: int
    num_local_experts: int
    num_experts_per_tok: int
    num_attention_heads: int
    num_key_value_heads: int
    layer_types: tuple
    num_hidden_layers: int
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    position_embedding_type: str
    mamba_n_heads: int
    mamba_d_head: int
    mamba_expand: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_n_groups: int
    mamba_chunk_size: int
    mamba_conv_bias: bool
    mamba_proj_bias: bool
    rms_norm_eps: float
    tie_word_embeddings: bool
    vocab_size: int
    # the cut (a dense family states its vocabulary and no experts)
    vocab_held: int
    sequence_length: int
    # assumed
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    init_std: float = 0.02
    init_seed: int = 0

    def __post_init__(self):
        # a JSON list; the dataclass is frozen and hashed
        object.__setattr__(self, "layer_types", tuple(self.layer_types))

    # what `nemotron_h.mamba2` reads, under the names that family's
    # config publishes them by
    mamba_num_heads = property(lambda self: self.mamba_n_heads)
    mamba_head_dim = property(lambda self: self.mamba_d_head)
    n_groups = property(lambda self: self.mamba_n_groups)
    ssm_state_size = property(lambda self: self.mamba_d_state)
    chunk_size = property(lambda self: self.mamba_chunk_size)
    layer_norm_epsilon = property(lambda self: self.rms_norm_eps)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def chunks_a_row(self) -> int:
        return self.sequence_length // self.mamba_chunk_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def attention_block(self) -> int:
        return math.gcd(self.sequence_length, ATTENTION_BLOCK)

    def layers(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def validate(self) -> None:
        kinds = self.layer_types
        if len(kinds) != self.num_hidden_layers \
                or set(kinds) - {MAMBA, ATTENTION}:
            raise ValueError(
                f"layer_types must name num_hidden_layers layers, each "
                f"{MAMBA} or {ATTENTION}")
        if self.num_local_experts or self.num_experts_per_tok:
            raise ValueError("a dense MLP in every layer is what this "
                             "family implements (num_local_experts 0)")
        if self.position_embedding_type != "nope":
            raise ValueError("no positional encoding is what this family "
                             "implements (position_embedding_type nope)")
        if not self.tie_word_embeddings:
            raise ValueError("a tied head is what this family implements "
                             "(tie_word_embeddings true)")
        if not self.mamba_conv_bias or self.mamba_proj_bias:
            raise ValueError("a convolution with bias and projections "
                             "without are what this family implements")
        if self.mamba_inner != self.mamba_expand * self.hidden_size:
            raise ValueError("mamba_n_heads * mamba_d_head must be "
                             "mamba_expand * hidden_size")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_heads must divide over mamba_n_groups")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide over "
                             "num_attention_heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must divide over "
                             "num_key_value_heads")
        if self.sequence_length % self.mamba_chunk_size:
            raise ValueError(
                f"sequence_length {self.sequence_length} must be a whole "
                f"number of scan chunks of mamba_chunk_size "
                f"{self.mamba_chunk_size}")
        lm.validate_cut(self)


def load_config(path: str) -> GraniteHybridConfig:
    return lm.load_config(path, "granitemoehybrid", GraniteHybridConfig)


# -- the flat key space --------------------------------------------------------

def layer_specs(kind: str, c: GraniteHybridConfig
                ) -> list[tuple[str, tuple[int, ...]]]:
    h, i = c.hidden_size, c.shared_intermediate_size
    out = [("input_norm", (h,))]
    if kind == MAMBA:
        out += [("w_in", (h, c.mamba_inner + c.conv_dim + c.mamba_n_heads)),
                ("conv_w", (c.conv_dim, c.mamba_d_conv)),
                ("conv_b", (c.conv_dim,)),
                ("dt_bias", (c.mamba_n_heads,)),
                ("A_log", (c.mamba_n_heads,)), ("D", (c.mamba_n_heads,)),
                ("gate_norm", (c.mamba_inner,)),
                ("w_out", (c.mamba_inner, h))]
    else:
        q = c.num_attention_heads * c.head_dim
        kv = c.num_key_value_heads * c.head_dim
        out += [("wq", (h, q)), ("wk", (h, kv)), ("wv", (h, kv)),
                ("wo", (q, h))]
    return out + [("post_norm", (h,)), ("w1", (h, 2 * i)), ("w2", (i, h))]


def leaf_specs(c: GraniteHybridConfig) -> list[tuple[str, tuple[int, ...]]]:
    """(dotted name, shape) of every leaf, in flat-layout order: the
    embedding (which is the head too), the layers in their published
    order (`l<i>.`), the final norm."""
    out = [("embed", (c.vocab_held, c.hidden_size))]
    for i, kind in enumerate(c.layer_types):
        out += [(f"l{i}.{n}", s) for n, s in layer_specs(kind, c)]
    return out + [("final_norm", (c.hidden_size,))]


def num_params(c: GraniteHybridConfig) -> int:
    return lm.num_params(leaf_specs(c))


def init_leaves(c: GraniteHybridConfig) -> dict:
    """One key a leaf by its place in the layout (module docstring has
    the distributions: (m1) is `nemotron_h.init_leaves`')."""
    key = jax.random.PRNGKey(c.init_seed)
    out = {}
    for at, (name, shape) in enumerate(leaf_specs(c)):
        k = jax.random.fold_in(key, at)
        last = name.rsplit(".", 1)[-1]
        if last.endswith("norm") or last == "D":
            out[name] = jnp.ones(shape, jnp.float32)
        elif last == "A_log":
            out[name] = jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0))
        elif last == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                         * (math.log(c.time_step_max)
                            - math.log(c.time_step_min))
                         + math.log(c.time_step_min))
            dt = jnp.maximum(dt, c.time_step_floor)
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif last in ("conv_w", "conv_b"):
            bound = 1.0 / math.sqrt(c.mamba_d_conv)
            out[name] = jax.random.uniform(k, shape, jnp.float32, -bound,
                                           bound)
        else:
            out[name] = c.init_std * jax.random.normal(k, shape, jnp.float32)
    return out


# -- the layers ------------------------------------------------------------------

def attention(u, p: dict, c: GraniteHybridConfig):
    """Grouped-query attention on `[B, S, H]` (already normed), causal
    within a row, every earlier key, no positional encoding; the scores'
    `attention_multiplier` rides q's projection."""
    with jax.named_scope("kps.attn"):
        b, s, _ = u.shape
        nh, nkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim

        def project(w, heads, scale=None):
            with jax.named_scope("kps.attn.qkv"):
                x = u @ p[w]
                return (x if scale is None else x * scale).reshape(
                    b, s, heads, d)

        # query head h reads key/value head h // (heads / kv heads)
        q = project("wq", nh, c.attention_multiplier).reshape(
            b, s, nkv, nh // nkv, d)
        k, v = project("wk", nkv), project("wv", nkv)
        with jax.named_scope("kps.attn.full"):
            out = lm.blocked_attention(q, k, v, window=None,
                                       block=c.attention_block, scaled=True)
        with jax.named_scope("kps.attn.out"):
            return out.reshape(b, s, nh * d) @ p["wo"]


def layer(x, p: dict, c: GraniteHybridConfig, kind: str):
    """One layer on `[B, S, H]`: the mixer and the MLP, each behind its
    norm and each added back times `residual_multiplier`."""
    eps, r = c.rms_norm_eps, c.residual_multiplier
    u = lm.block_norm(x, p["input_norm"], eps)
    a = x + r * (nemotron_h.mamba2(u, p, c) if kind == MAMBA
                 else attention(u, p, c))
    u = lm.block_norm(a, p["post_norm"], eps)
    with jax.named_scope("kps.mlp"):
        i = c.shared_intermediate_size
        y = swiglu(u, p["w1"][:, :i], p["w1"][:, i:], p["w2"])
    return a + r * y


def forward(leaves: dict, rows, c: GraniteHybridConfig, *,
            with_logits=False):
    """`rows` `[B, S + 2]` int32 -> {"nll" [B, S] next-token, "logits"
    if asked}.  Every layer is recomputed in the backward pass.  (A
    row's last token is carried for another family's second head;
    nothing here reads it.)"""
    s = c.sequence_length
    tokens, t1 = rows[:, :s], rows[:, 1:s + 1]
    with jax.named_scope("kps.lm.embed"):
        x = leaves["embed"][tokens] * c.embedding_multiplier
    for i, kind in enumerate(c.layer_types):
        x = jax.checkpoint(lambda x, p, kind=kind: layer(x, p, c, kind))(
            x, sub(leaves, f"l{i}."))
    with jax.named_scope("kps.lm.head"):
        # the head is the embedding transposed: the one leaf's gradient
        # is the gather's scatter of rows plus this product's.  The
        # logits' division rides the final norm's weight, 2,048 numbers
        # for the `[S, vocab_held]` logits: norm(x) (w / 8) E^T is
        # (norm(x) w E^T) / 8
        nll, logits = jax.checkpoint(
            lambda x, n, e, t: lm.head_nll(x, n / c.logits_scaling, e.T, t,
                                           c.rms_norm_eps))(
                x, leaves["final_norm"], leaves["embed"], t1)
    out = {"nll": nll}
    if with_logits:
        out["logits"] = logits
    return out


def loss_and_counts(leaves: dict, rows, mask, c: GraniteHybridConfig):
    """The training objective over the unmasked rows of a slab — mean
    next-token cross-entropy — and the expert layer's count triple,
    which a dense family leaves at zero."""
    out = forward(leaves, rows, c)
    positions = jnp.maximum(mask.sum(), 1.0) * c.sequence_length
    return ((out["nll"].sum(-1) * mask).sum() / positions,
            jnp.zeros((3,), jnp.int32))


def pair_counts(c: GraniteHybridConfig) -> tuple[int, int]:
    """(pairs inside the mask, pairs inside every block the core
    computes) that one pass over one row covers in the attention
    layers, in pairs."""
    s, attending = c.sequence_length, c.layers(ATTENTION)
    return (attending * lm.attention_pairs(s, None),
            attending * lm.attention_block_pairs(s, None, c.attention_block))


# -- the task ----------------------------------------------------------------------

class GraniteHybridTask(lm.TokenRowsTask):
    """`lm_common.TokenRowsTask` over this family's leaves and layers."""

    model_type = "granitemoehybrid"
    config_cls = GraniteHybridConfig
    slots_a_token = 0            # no expert layer: the `moe.*` read 0
    counter_names = lm.COUNTERS + ("ssm.chunks", "ssm.kernel_chunks",
                                   "attn.pairs_window",
                                   "attn.pairs_full", "attn.block_pairs",
                                   "attn.kernel_block_pairs",
                                   "attn.norm_rope_rows",
                                   "attn.norm_rope_kernel_rows", "mlp.rows")

    def leaf_specs(self):
        return leaf_specs(self.arch)

    def init_leaves(self) -> dict:
        return init_leaves(self.arch)

    def forward(self, leaves, rows, *, with_logits=False):
        return forward(leaves, rows, self.arch, with_logits=with_logits)

    def loss_and_counts(self, leaves, rows, mask):
        return loss_and_counts(leaves, rows, mask, self.arch)

    def own_counts(self, rows) -> tuple:
        """`ssm.chunks` as `nemotron_h` counts them (chunks scanned by
        one pass: every row of the slab through every Mamba-2 layer)
        and `ssm.kernel_chunks`, those the kernel scanned;
        `attn.pairs_window` (0: no layer slides), `attn.pairs_full`,
        `attn.block_pairs` and `attn.kernel_block_pairs` of one pass as
        `afmoe` counts them, in units of PAIRS_UNIT pairs;
        `attn.norm_rope_rows` and `attn.norm_rope_kernel_rows` 0 (no
        head is normed or rotated); and `mlp.rows`: the positions that
        go through a dense MLP, every row of the slab through every
        layer, in units of ROWS_UNIT — all rounded down once a pass."""
        c = self.arch
        full, blocks = (rows.shape[0] * n // PAIRS_UNIT
                        for n in pair_counts(c))
        heads = c.num_attention_heads // c.num_key_value_heads
        chunks = rows.shape[0] * c.chunks_a_row * c.layers(MAMBA)
        return (chunks, chunks * nemotron_h.kernel_chunks(rows.shape[0], c),
                0, full, blocks, blocks * lm.kernel_attends(
                    (rows.shape[0], c.sequence_length, c.num_key_value_heads,
                     heads, c.head_dim), c.attention_block),
                0, 0,
                rows.shape[0] * c.sequence_length * c.num_hidden_layers
                // ROWS_UNIT)

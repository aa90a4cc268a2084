"""A fourth language-model family: `ouro` (ByteDance Ouro-2.6B's
published shape,
https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json), a
dense decoder whose stack of layers is run `total_ut_steps` times on
ONE set of weights.

    h = E[tokens]
    for step in 1 .. total_ut_steps:              the SAME layers, the
        for layer in 1 .. num_hidden_layers:      SAME leaves
            a = h + N2(Attn(N1(h)))               four norms a layer,
            h = a + N4(MLP(N3(a)))                each with its own weight
        h = RMSNorm(h) * w_final                  the one final norm, at
                                                  the end of EVERY step

then an untied head over the whole vocabulary on the last step's output;
the loss is the mean next-token cross-entropy of the last step's logits.
Every layer is alike: multi-head attention (as many key/value heads as
query heads at the published widths: ONE query head a key/value head),
rotate-half RoPE over all the channels on q and k in every layer, the
positions the same at every step, causal over the whole row, no bias;
a SwiGLU MLP.

Token rows, the norm, RoPE, the blocked attention core (on a TPU the
kernel of `models/attention_kernel.py`), SwiGLU, the flat key space, the
solver with its counters and the task's frame are `models/lm_common.py`'s,
shared with `glm4_moe_lite`, `nemotron_h` and `afmoe`.  This family's
own is the loop.

A layer's leaves exist ONCE (`l<i>.<name>`): the flat vector, the wire's
key space and the delta are the model's, not `total_ut_steps` times it.
The steps are a `jax.lax.scan` whose body is the layers written out, the
leaves closed over: they are the loop's invariants, nothing is cut from
a stack, and in the backward pass a leaf's gradient is the loop's state
— ONE array a leaf, to which each step adds its use's part, so the sum
over the uses is formed on the device and no `total_ut_steps` gradients
wait side by side.  Every application of a layer is recomputed in the
backward pass (`jax.checkpoint`; a step's norm rides with its last
layer), so what is kept between the passes is one `[B, S, H]` input an
application.  The named scope `kps.lm.layers` lies round the loop: what
is under it ALONE is the loop's own cost — the residual adds, the state
handed from step to step, the gradient's sum over the uses.

Left out, and said: the exit gate (a `hidden_size -> 1` projection with
bias read after each step's norm, whose sigmoid gives each step a
probability of stopping there).  At `early_exit_threshold` 1 no step
stops early and the output is the last step's, which is what this
family computes and trains; the published training objective weighs
every step's loss by the gate's distribution.  The gate is in no leaf.

Assumed, where the published config says nothing (each also noted in the
benchmark's reference, from the published modelling code as known
without a network): the final norm applied at the end of every step;
four norms a layer, one before and one after each half; no bias; no
head-wise norm; initialisation normal(0, `init_std`) from `init_seed`,
norms at one.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models.lm_common import rope, sub, swiglu

FULL = "full_attention"
# the attention core's tile: 512 queries, or the largest tile under it
# that divides the row
ATTENTION_BLOCK = 512
# the pair counters count in units of 1,024 pairs, as `afmoe` counts
# them (the device's counters are int32 a dispatch)
PAIRS_UNIT = 1024


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """The published keys (names as in config.json), then the cut, then
    what had to be assumed."""

    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    layer_types: tuple
    num_hidden_layers: int
    total_ut_steps: int
    early_exit_threshold: float
    use_sliding_window: bool
    tie_word_embeddings: bool
    rms_norm_eps: float
    rope_theta: float
    vocab_size: int
    # the cut (a dense family states its vocabulary and no experts)
    vocab_held: int
    sequence_length: int
    # assumed
    init_std: float = 0.02
    init_seed: int = 0

    def __post_init__(self):
        # a JSON list; the dataclass is frozen and hashed
        object.__setattr__(self, "layer_types", tuple(self.layer_types))

    @property
    def attention_block(self) -> int:
        return math.gcd(self.sequence_length, ATTENTION_BLOCK)

    @property
    def layer_applications(self) -> int:
        """Layers a token passes in one forward pass."""
        return self.num_hidden_layers * self.total_ut_steps

    def validate(self) -> None:
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {FULL} or self.use_sliding_window):
            raise ValueError(
                f"layer_types must name num_hidden_layers layers, each "
                f"{FULL} (use_sliding_window false)")
        if self.total_ut_steps < 1:
            raise ValueError("total_ut_steps must be at least 1")
        if self.early_exit_threshold < 1:
            raise ValueError(
                "early_exit_threshold under 1 lets a step stop early: the "
                "exit gate is not what this family implements")
        if self.tie_word_embeddings:
            raise ValueError("an untied head is what this family "
                             "implements (tie_word_embeddings false)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must divide over "
                             "num_key_value_heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even")
        lm.validate_cut(self)


def load_config(path: str) -> OuroConfig:
    return lm.load_config(path, "ouro", OuroConfig)


# -- the flat key space --------------------------------------------------------

def layer_specs(c: OuroConfig) -> list[tuple[str, tuple[int, ...]]]:
    h, i, d = c.hidden_size, c.intermediate_size, c.head_dim
    q, kv = c.num_attention_heads * d, c.num_key_value_heads * d
    return [("in_norm", (h,)), ("wq", (h, q)), ("wk", (h, kv)),
            ("wv", (h, kv)), ("wo", (q, h)), ("post_attn_norm", (h,)),
            ("pre_mlp_norm", (h,)), ("w_gate", (h, i)), ("w_up", (h, i)),
            ("w_down", (i, h)), ("post_mlp_norm", (h,))]


def leaf_specs(c: OuroConfig) -> list[tuple[str, tuple[int, ...]]]:
    """(dotted name, shape) of every leaf, in flat-layout order: the
    embedding, the layers (`l<i>.`, each ONCE however often it is
    used), the final norm, the head."""
    out = [("embed", (c.vocab_held, c.hidden_size))]
    for i in range(c.num_hidden_layers):
        out += [(f"l{i}.{n}", s) for n, s in layer_specs(c)]
    return out + [("final_norm", (c.hidden_size,)),
                  ("head", (c.hidden_size, c.vocab_held))]


def num_params(c: OuroConfig) -> int:
    return lm.num_params(leaf_specs(c))


def init_leaves(c: OuroConfig) -> dict:
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


# -- the layers ------------------------------------------------------------------

def attention(u, p: dict, c: OuroConfig):
    """Multi-head attention on `[B, S, H]` (already normed), causal over
    the whole row, RoPE on q and k; the parts of `kps.attn.proj` under
    the names `afmoe` gives its own."""
    with jax.named_scope("kps.attn"):
        b, s, _ = u.shape
        nh, nkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim

        def project(w, heads):
            with jax.named_scope("kps.attn.qkv"):
                return (u @ p[w]).reshape(b, s, heads, d)

        with jax.named_scope("kps.attn.proj"):
            q, k, v = project("wq", nh), project("wk", nkv), project("wv", nkv)
            with jax.named_scope("kps.attn.norm_rope"):
                q, k = rope(q, c.rope_theta), rope(k, c.rope_theta)
                # query head h reads key/value head h // (heads / kv
                # heads): its own, at the published widths
                q = q.reshape(b, s, nkv, nh // nkv, d)
        with jax.named_scope("kps.attn.full"):
            out = lm.blocked_attention(q, k, v, window=None,
                                       block=c.attention_block)
        with jax.named_scope("kps.attn.proj"), \
                jax.named_scope("kps.attn.out"):
            return out.reshape(b, s, nh * d) @ p["wo"]


def layer(x, p: dict, c: OuroConfig):
    """One application of one layer on `[B, S, H]`."""
    eps = c.rms_norm_eps
    a = x + lm.block_norm(
        attention(lm.block_norm(x, p["in_norm"], eps), p, c),
        p["post_attn_norm"], eps)
    with jax.named_scope("kps.mlp"):
        y = swiglu(lm.block_norm(a, p["pre_mlp_norm"], eps), p["w_gate"],
                   p["w_up"], p["w_down"])
    return a + lm.block_norm(y, p["post_mlp_norm"], eps)


def looped_layers(x, leaves: dict, c: OuroConfig):
    """`total_ut_steps` times through the layers on the same leaves, the
    final norm at the end of every step.  The leaves are closed over:
    the loop's invariants forward, and backward each leaf's gradient is
    the loop's state, summed over the uses as the steps go by."""
    last = c.num_hidden_layers - 1

    def applied(x, p, w_final, ends_step: bool):
        y = layer(x, p, c)
        return lm.block_norm(y, w_final, c.rms_norm_eps) if ends_step else y

    def step(x, _):
        for i in range(c.num_hidden_layers):
            x = jax.checkpoint(applied, static_argnums=3)(
                x, sub(leaves, f"l{i}."), leaves["final_norm"], i == last)
        return x, None

    with jax.named_scope("kps.lm.layers"):
        return jax.lax.scan(step, x, None, length=c.total_ut_steps)[0]


def _nll(x, head, targets):
    """The head on the last step's output, which its step has normed
    already, and each position's negative log-likelihood of its target
    → ([B, S], logits)."""
    logits = x @ head
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked, logits


def forward(leaves: dict, rows, c: OuroConfig, *, with_logits=False):
    """`rows` `[B, S + 2]` int32 -> {"nll" [B, S] next-token, "logits"
    if asked}.  (A row's last token is carried for another family's
    second head; nothing here reads it.)"""
    s = c.sequence_length
    tokens, t1 = rows[:, :s], rows[:, 1:s + 1]
    with jax.named_scope("kps.lm.embed"):
        x = leaves["embed"][tokens]
    x = looped_layers(x, leaves, c)
    with jax.named_scope("kps.lm.head"):
        nll, logits = jax.checkpoint(_nll)(x, leaves["head"], t1)
    out = {"nll": nll}
    if with_logits:
        out["logits"] = logits
    return out


def loss_and_counts(leaves: dict, rows, mask, c: OuroConfig):
    """The training objective over the unmasked rows of a slab — mean
    next-token cross-entropy of the last step — and the expert layer's
    count triple, which a dense family leaves at zero."""
    out = forward(leaves, rows, c)
    positions = jnp.maximum(mask.sum(), 1.0) * c.sequence_length
    return ((out["nll"].sum(-1) * mask).sum() / positions,
            jnp.zeros((3,), jnp.int32))


def pair_counts(c: OuroConfig) -> tuple[int, int]:
    """(pairs inside the mask, pairs inside every block the core
    computes) that one pass over one row covers, every application of
    every layer, in pairs."""
    s = c.sequence_length
    return (c.layer_applications * lm.attention_pairs(s, None),
            c.layer_applications
            * lm.attention_block_pairs(s, None, c.attention_block))


# -- the task ----------------------------------------------------------------------

class OuroTask(lm.TokenRowsTask):
    """`lm_common.TokenRowsTask` over this family's leaves and loop."""

    model_type = "ouro"
    config_cls = OuroConfig
    slots_a_token = 0            # no expert layer: the `moe.*` read 0
    counter_names = lm.COUNTERS + ("attn.pairs_window", "attn.pairs_full",
                                   "attn.block_pairs",
                                   "attn.kernel_block_pairs",
                                   "lm.layer_passes")

    def leaf_specs(self):
        return leaf_specs(self.arch)

    def init_leaves(self) -> dict:
        return init_leaves(self.arch)

    def forward(self, leaves, rows, *, with_logits=False):
        return forward(leaves, rows, self.arch, with_logits=with_logits)

    def loss_and_counts(self, leaves, rows, mask):
        return loss_and_counts(leaves, rows, mask, self.arch)

    def own_counts(self, rows) -> tuple:
        """`attn.pairs_window` (0: no layer slides), `attn.pairs_full`,
        `attn.block_pairs` and `attn.kernel_block_pairs` of one pass as
        `afmoe` counts them, in units of PAIRS_UNIT pairs (rounded down
        once a pass); and `lm.layer_passes`, the layer APPLICATIONS of
        one pass: every row of the slab through every layer at every
        step."""
        c = self.arch
        full, blocks = (rows.shape[0] * n // PAIRS_UNIT
                        for n in pair_counts(c))
        heads = c.num_attention_heads // c.num_key_value_heads
        return (0, full, blocks, blocks * lm.kernel_attends(
            (rows.shape[0], c.sequence_length, c.num_key_value_heads, heads,
             c.head_dim), c.attention_block),
            rows.shape[0] * c.layer_applications)

"""A second language-model family: `nemotron_h` (NVIDIA-Nemotron-3-Nano-
30B-A3B's published shape,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
config.json).

Every block is ONE mixer alone behind its RMSNorm, `x ← x +
mixer(RMSNorm(x))`, of three kinds in the order `hybrid_override_pattern`
spells: `M` a Mamba-2 state-space mixer, `E` an expert layer, `*`
grouped-query attention.  Then a final RMSNorm and an untied head over
the held slice of the vocabulary; the loss is the mean next-token
cross-entropy.

Token rows, the norm, the router, the expert layer that knows its
share, the head and its loss, the flat key space, the solver with its
counters and the task's frame are `models/lm_common.py`'s, shared with
`glm4_moe_lite`.  This family's own:

  * the Mamba-2 mixer.  `[z | xBC | dt] = u W_in`; `xBC ←
    silu(conv(xBC))`, a causal depthwise convolution with bias, zeros
    before the row's start; `[x | B | C] = xBC`, `x` as `[S, heads,
    head_dim]`, `B`, `C` as `[S, n_groups, ssm_state_size]`, head `h`
    reads group `h // (heads / n_groups)`; `Δ = softplus(dt + dt_bias)`;
    `A_h = −exp(A_log_h)`; state `H_t,h = exp(Δ_t,h A_h) · H_t−1,h +
    Δ_t,h · x_t,h ⊗ B_t,g`, `y_t,h = H_t,h C_t,g + D_h x_t,h`; then
    the gated norm, gate FIRST: `RMSNorm_groups(y · silu(z)) · w`, the
    mean square taken inside each of the `n_groups` groups of channels;
    `out = y W_out`.  The recurrence is computed in chunks of
    `chunk_size` tokens (`ssd_chunked`): inside a chunk as products
    with the lower-triangular matrix of decays, each chunk's own
    contribution to its end state, and the state handed on from chunk
    to chunk — on a TPU, at chunks and a state of whole lanes, as
    `models/ssd_kernel.py`'s kernels (the decays and the state in
    VMEM), anywhere else as einsums and a `lax.scan` over the chunks;
    `sequence_length` must be a whole number of chunks;
  * attention: `num_attention_heads` query heads, `num_key_value_heads`
    key/value heads of `head_dim`, scores ÷ √head_dim, causal softmax,
    no bias and NO positional encoding (the published modelling code
    applies none; `rope_theta` is unused);
  * experts that are not gated: `relu(x W_up)² W_down`, the shared
    expert the same form at its own width.

Every block is recomputed in the backward pass (`jax.checkpoint`), the
chunked scan with it (its kernels' forward call once more).  The blocks
are written out in their published order, each with leaves of its own
(`b<i>.<name>`): the pattern is not periodic, so there is no stack to
scan.

Assumed, where the published config says nothing (each also noted in
the benchmark's reference): the selection bias held fixed at zero;
initialisation normal(0, `init_std`) from `init_seed`, norms and `D`
at one, `A_log = log(uniform[1, 16])`, `dt_bias` the inverse softplus
of log-uniform[`time_step_min`, `time_step_max`] floored at
`time_step_floor`, the convolution's weights and bias
uniform[−1/√k, 1/√k] (what the published code's framework gives a
depthwise convolution of kernel k left to itself);
`rescale_prenorm_residual` not applied.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import ssd_kernel
from kafka_ps_tpu.models.lm_common import rms_norm, sub  # noqa: F401


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The published keys (names as in config.json), then the cut, then
    what had to be assumed."""

    hidden_size: int
    hybrid_override_pattern: str
    num_hidden_layers: int
    layer_norm_epsilon: float
    vocab_size: int
    # Mamba-2
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    time_step_min: float
    time_step_max: float
    time_step_floor: float
    # attention
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    # the expert layer
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    # the cut
    experts_held: int
    expert_offset: int
    vocab_held: int
    sequence_length: int
    # assumed
    init_std: float = 0.02
    init_seed: int = 0

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def chunks_a_row(self) -> int:
        return self.sequence_length // self.chunk_size

    def kinds(self, kind: str) -> int:
        return self.hybrid_override_pattern.count(kind)

    def validate(self) -> None:
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers or set(pattern) - set("ME*"):
            raise ValueError("hybrid_override_pattern must spell "
                             "num_hidden_layers blocks of M, E and *")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is what this family "
                             "implements (n_shared_experts 1)")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("mamba_num_heads must divide over n_groups")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must divide over "
                             "num_key_value_heads")
        if self.sequence_length % self.chunk_size:
            raise ValueError(
                f"sequence_length {self.sequence_length} must be a whole "
                f"number of scan chunks of chunk_size {self.chunk_size}")
        lm.validate_cut(self)


def load_config(path: str) -> NemotronHConfig:
    return lm.load_config(path, "nemotron_h", NemotronHConfig)


# -- the flat key space --------------------------------------------------------

def block_specs(kind: str, c: NemotronHConfig
                ) -> list[tuple[str, tuple[int, ...]]]:
    h = c.hidden_size
    if kind == "M":
        return [("norm", (h,)),
                ("w_in", (h, c.mamba_inner + c.conv_dim + c.mamba_num_heads)),
                ("conv_w", (c.conv_dim, c.conv_kernel)),
                ("conv_b", (c.conv_dim,)),
                ("dt_bias", (c.mamba_num_heads,)),
                ("A_log", (c.mamba_num_heads,)), ("D", (c.mamba_num_heads,)),
                ("gate_norm", (c.mamba_inner,)),
                ("w_out", (c.mamba_inner, h))]
    if kind == "*":
        q = c.num_attention_heads * c.head_dim
        kv = c.num_key_value_heads * c.head_dim
        return [("norm", (h,)), ("wq", (h, q)), ("wk", (h, kv)),
                ("wv", (h, kv)), ("wo", (q, h))]
    i, e = c.moe_intermediate_size, c.experts_held
    s = c.n_shared_experts * c.moe_shared_expert_intermediate_size
    return [("norm", (h,)), ("router", (h, c.n_routed_experts)),
            ("router_bias", (c.n_routed_experts,)),
            ("e_up", (e, h, i)), ("e_down", (e, i, h)),
            ("s_up", (h, s)), ("s_down", (s, h))]


def leaf_specs(c: NemotronHConfig) -> list[tuple[str, tuple[int, ...]]]:
    """(dotted name, shape) of every leaf, in flat-layout order: the
    embedding, the blocks in their published order (`b<i>.`), the final
    norm, the head."""
    out = [("embed", (c.vocab_held, c.hidden_size))]
    for i, kind in enumerate(c.hybrid_override_pattern):
        out += [(f"b{i}.{n}", s) for n, s in block_specs(kind, c)]
    return out + [("final_norm", (c.hidden_size,)),
                  ("head", (c.hidden_size, c.vocab_held))]


def num_params(c: NemotronHConfig) -> int:
    return lm.num_params(leaf_specs(c))


def init_leaves(c: NemotronHConfig) -> dict:
    """One key a leaf by its place in the layout (module docstring has
    the distributions)."""
    key = jax.random.PRNGKey(c.init_seed)
    out = {}
    for at, (name, shape) in enumerate(leaf_specs(c)):
        k = jax.random.fold_in(key, at)
        last = name.rsplit(".", 1)[-1]
        if last.endswith("norm") or last == "D":
            out[name] = jnp.ones(shape, jnp.float32)
        elif last == "router_bias":
            out[name] = jnp.zeros(shape, jnp.float32)
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
            bound = 1.0 / math.sqrt(c.conv_kernel)
            out[name] = jax.random.uniform(k, shape, jnp.float32, -bound,
                                           bound)
        else:
            out[name] = c.init_std * jax.random.normal(k, shape, jnp.float32)
    return out


# -- the layers ------------------------------------------------------------------

def causal_conv(x, w, bias):
    """Depthwise over `[B, S, C]` with `w` `[C, k]`: position t sees
    t − k + 1 .. t, zeros before the row's start; `w[:, k − 1]` weighs
    the position itself."""
    k, s = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias
    for j in range(k):
        out = out + padded[:, j:j + s] * w[:, j]
    return out


def chunks_scanned(xd, cum, bm, cm):
    """The recurrence over a row's chunks in plain `jax.numpy`: `xd` =
    Δ·x `[b, chunks, Q, g, r, P]`, `cum` `[b, chunks, Q, g, r]`, `bm`,
    `cm` `[b, chunks, Q, g, N]` -> y `[b, chunks, Q, g, r, P]`.  The
    decay of every head, `[b, chunks, Q, Q, g, r]`, is the largest
    array it makes."""
    b, nc, chunk, g, r, p = xd.shape
    n = bm.shape[-1]
    # inside a chunk: target l, source s <= l
    seg = cum[:, :, :, None] - cum[:, :, None, :]      # [b, nc, l, s, g, r]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    scores = jnp.einsum("bclgn,bcsgn->bclsg", cm, bm)
    y = jnp.einsum("bclsgr,bcsgrp->bclgrp", scores[..., None] * decay, xd)
    # each chunk's own contribution to its end state
    to_end = jnp.exp(cum[:, :, -1:] - cum)
    own = jnp.einsum("bcsgn,bcsgrp->bcgrpn", bm, to_end[..., None] * xd)
    through = jnp.exp(cum[:, :, -1])                   # [b, nc, g, r]

    def hand_on(state, chunk_):
        own_c, through_c = chunk_
        return through_c[..., None, None] * state + own_c, state

    _, entering = jax.lax.scan(
        hand_on, jnp.zeros((b, g, r, p, n), xd.dtype),
        (own.swapaxes(0, 1), through.swapaxes(0, 1)))
    return y + jnp.einsum("bclgn,cbgrpn->bclgrp", cm, entering) \
        * jnp.exp(cum)[..., None]


def _chunks_scanned_kernel(packed, dt, cum, d, groups, state):
    """`chunks_scanned` over Δ·x, plus `d`·x, as `ssd_kernel.scan` runs
    it on x, B and C side by side: the product with Δ and the D term
    inside."""
    b, nc, chunk, g, r = cum.shape
    return ssd_kernel.scan(packed, dt, cum.reshape(b, nc * chunk, g * r), d,
                           groups, state, chunk)


def ssd_chunked(x, dt, a, bm, cm, chunk: int, d=None, packed=None):
    """The state-space recurrence `H_t = exp(Δ_t A) H_t−1 + Δ_t x_t ⊗
    B_t`, `y_t = H_t C_t` from a zero state, in chunks of `chunk`
    tokens.  `x` `[B, S, heads, P]`, `dt` `[B, S, heads]` (Δ, positive),
    `a` `[heads]` (negative), `bm`, `cm` `[B, S, groups, N]` → `[B, S,
    heads, P]`; with `d` `[heads]`, the mixer's D, `y_t + d · x_t`;
    `packed`: the array `[B, S, heads x P + 2 x groups x N]` that x,
    `bm` and `cm` are the three parts of, where the caller has it (the
    kernels read its lanes where they lie; without it the three are
    laid side by side for them).

    With `cum` the running sum of Δ·A inside a chunk: a token s reaches
    a later token l of its chunk with decay `exp(cum_l − cum_s)` (the
    lower-triangular products), reaches the chunk's end with
    `exp(cum_end − cum_s)` (the chunk's own state), and the state that
    enters a chunk reaches its token l with `exp(cum_l)`; the chunks'
    states are handed on in order, `H_end = exp(cum_end) H_enter +
    own`.

    One computation, two ways to run it, and the input says which, as
    `lm_common.blocked_attention` chooses its core.  On a TPU, at
    shapes the kernel takes (`ssd_kernel.takes`: chunks and state of
    whole lanes, heads of 64 channels in whole sublanes a group), it is
    `ssd_kernel.scan`: a head's `[Q, Q]` decay and weighted scores are
    formed in VMEM and never written, and the state stays there while
    the chunks go by.  Anywhere else — another platform, a chunk of 16
    — it is `chunks_scanned`, plain `jax.numpy` (einsums and a
    `lax.scan` over the chunks), and the program is what it was before
    there was a kernel.  The discretisation and the running sum are
    plain either way."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    nc, r = s // chunk, h // g
    taken = ssd_kernel.takes(x.shape, g, n, chunk)

    def scaled(x, dt):
        return (x * dt[..., None]).reshape(b, nc, chunk, g, r, p)

    def plain(x, xd, cum, bm, cm, d):
        y = chunks_scanned(xd, cum, bm, cm).reshape(b, s, h, p)
        return y if d is None else y + d[:, None] * x
    da = (dt * a).reshape(b, nc, chunk, g, r)
    # (the kernels form Δ·x themselves; handed to both ways it would be
    # differentiated, and its zero cotangent multiplied out, on a TPU too)
    xd = None if taken else scaled(x, dt)
    bm = bm.reshape(b, nc, chunk, g, n)
    cm = cm.reshape(b, nc, chunk, g, n)
    cum = jnp.cumsum(da, axis=2)                       # [b, nc, Q, g, r]
    if not taken:
        return plain(x, xd, cum, bm, cm, d)
    if packed is None:
        packed = jnp.concatenate(
            [m.reshape(b, s, -1) for m in (x, bm, cm)], axis=-1)

    def einsums(packed, dt, cum, d):
        x, bm, cm = (m.reshape(b, nc, chunk, g, -1) for m in jnp.split(
            packed, [h * p, h * p + g * n], axis=-1))
        x = x.reshape(b, s, h, p)
        return plain(x, scaled(x, dt), cum, bm, cm, d)
    return jax.lax.platform_dependent(
        packed, dt, cum, jnp.zeros((h,), x.dtype) if d is None else d,
        default=einsums, tpu=functools.partial(
            _chunks_scanned_kernel, groups=g, state=n))


def kernel_chunks(rows: int, c):
    """1 where `mamba2` under `c` scans `rows` rows' chunks as the
    kernel, 0 where plain: chosen as `ssd_chunked` chooses."""
    if not ssd_kernel.takes((rows, c.sequence_length, c.mamba_num_heads,
                             c.mamba_head_dim), c.n_groups,
                            c.ssm_state_size, c.chunk_size):
        return 0
    return jax.lax.platform_dependent(tpu=lambda: 1, default=lambda: 0)


def gated_group_norm(y, z, w, groups: int, eps: float):
    """The gate first, then RMSNorm inside each group of channels."""
    b, s, d = y.shape
    v = (y * jax.nn.silu(z)).reshape(b, s, groups, d // groups)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    return v.reshape(b, s, d) * w


def mamba2(u, p: dict, c: NemotronHConfig):
    """The Mamba-2 mixer on `[B, S, H]` (already normed)."""
    with jax.named_scope("kps.ssm"):
        b, s, _ = u.shape
        nh, hd, g, n = (c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                        c.ssm_state_size)
        inner = c.mamba_inner
        with jax.named_scope("kps.ssm.proj"):
            zxbcdt = u @ p["w_in"]
        z = zxbcdt[..., :inner]
        dt = zxbcdt[..., inner + c.conv_dim:]
        with jax.named_scope("kps.ssm.conv"):
            xbc = jax.nn.silu(causal_conv(
                zxbcdt[..., inner:inner + c.conv_dim], p["conv_w"],
                p["conv_b"]))
        with jax.named_scope("kps.ssm.scan"):
            x = xbc[..., :inner].reshape(b, s, nh, hd)
            bm = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
            cm = xbc[..., inner + g * n:].reshape(b, s, g, n)
            y = ssd_chunked(x, jax.nn.softplus(dt + p["dt_bias"]),
                            -jnp.exp(p["A_log"]), bm, cm, c.chunk_size,
                            p["D"], xbc).reshape(b, s, inner)
        with jax.named_scope("kps.ssm.norm"):
            y = gated_group_norm(y, z, p["gate_norm"], g,
                                 c.layer_norm_epsilon)
        with jax.named_scope("kps.ssm.proj"):
            return y @ p["w_out"]


def attention(u, p: dict, c: NemotronHConfig):
    """Grouped-query attention on `[B, S, H]` (already normed), causal
    within a row, no positional encoding."""
    with jax.named_scope("kps.attn"):
        b, s, _ = u.shape
        nkv, d = c.num_key_value_heads, c.head_dim
        r = c.num_attention_heads // nkv
        # `kps.attn.qkv` and `kps.attn.out` are the projections; the
        # core (scores, mask, softmax, values) is what is left under
        # `kps.attn` alone
        with jax.named_scope("kps.attn.qkv"):
            q = (u @ p["wq"]).reshape(b, s, nkv, r, d)
            k = (u @ p["wk"]).reshape(b, s, nkv, d)
            v = (u @ p["wv"]).reshape(b, s, nkv, d)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / math.sqrt(d)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
        with jax.named_scope("kps.attn.out"):
            return out.reshape(b, s, nkv * r * d) @ p["wo"]


def relu2(h, w_up, w_down):
    return jnp.square(jax.nn.relu(h @ w_up)) @ w_down


def relu2_experts(xs, p: dict, dot):
    """What `lm_common.routed_experts` is handed: one held expert on its
    own rows, every expert at once; `dot` is the grouped product over
    the sorted assignments."""
    return dot(jnp.square(jax.nn.relu(dot(xs, p["e_up"]))), p["e_down"])


def _shared_expert(h, p: dict):
    return relu2(h, p["s_up"], p["s_down"])


def block(kind: str, x, p: dict, c: NemotronHConfig):
    """`x + mixer(RMSNorm(x))` → (the block's output, an expert layer's
    counts or None)."""
    u = lm.block_norm(x, p["norm"], c.layer_norm_epsilon)
    if kind == "M":
        return x + mamba2(u, p, c), None
    if kind == "*":
        return x + attention(u, p, c), None
    y, load = lm.expert_layer(u, p, c, relu2_experts, _shared_expert)
    return x + y, load


def forward(leaves: dict, rows, c: NemotronHConfig, *, with_logits=False):
    """`rows` `[B, S + 2]` int32 → per-position losses and the routing
    counts: {"nll" [B, S] next-token, "loads" [expert layers, 3],
    "logits" if asked}.  Every block is recomputed in the backward
    pass.  (A row's last token is carried for the other family's
    second head; nothing here reads it.)"""
    s = c.sequence_length
    tokens, t1 = rows[:, :s], rows[:, 1:s + 1]
    with jax.named_scope("kps.lm.embed"):
        x = leaves["embed"][tokens]
    loads = []
    for i, kind in enumerate(c.hybrid_override_pattern):
        x, load = jax.checkpoint(
            lambda x, p, kind=kind: block(kind, x, p, c))(
                x, sub(leaves, f"b{i}."))
        if load is not None:
            loads.append(load)
    with jax.named_scope("kps.lm.head"):
        nll, logits = jax.checkpoint(
            lambda x, n, hd, t: lm.head_nll(x, n, hd, t,
                                            c.layer_norm_epsilon))(
                x, leaves["final_norm"], leaves["head"], t1)
    out = {"nll": nll, "loads": jnp.stack(loads)}
    if with_logits:
        out["logits"] = logits
    return out


def loss_and_counts(leaves: dict, rows, mask, c: NemotronHConfig):
    """The training objective over the unmasked rows of a slab — mean
    next-token cross-entropy — and (assignments here, Σ largest load,
    expert layers that went over `live_rows_bound`) of the pass."""
    out = forward(leaves, rows, c)
    positions = jnp.maximum(mask.sum(), 1.0) * c.sequence_length
    return ((out["nll"].sum(-1) * mask).sum() / positions,
            out["loads"].sum(0))


# -- the task ----------------------------------------------------------------------

class NemotronHTask(lm.TokenRowsTask):
    """`lm_common.TokenRowsTask` over this family's leaves and blocks."""

    model_type = "nemotron_h"
    config_cls = NemotronHConfig
    counter_names = lm.COUNTERS + ("ssm.chunks", "ssm.kernel_chunks")

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
        return self.arch.num_experts_per_tok * self.arch.kinds("E")

    def own_counts(self, rows) -> tuple:
        """`ssm.chunks`: chunks scanned by one pass, every row of the
        slab through every Mamba-2 block; `ssm.kernel_chunks`: those
        the kernel scanned (`kernel_chunks`)."""
        chunks = (rows.shape[0] * self.arch.chunks_a_row
                  * self.arch.kinds("M"))
        return chunks, chunks * kernel_chunks(rows.shape[0], self.arch)

"""A language-model family: `glm4_moe_lite` (GLM-4.7-Flash's published
shape, https://huggingface.co/zai-org/GLM-4.7-Flash config.json).

What it brings that the two classifier families do not: token rows
(`int32[S + 2]`, whose labels are the row itself, shifted), latent
attention (MLA), a leading dense SwiGLU layer, expert layers with a
sigmoid router, a selection bias, a shared expert and routed experts
run as grouped matrix products over the experts HELD HERE, a
multi-token-prediction (MTP) module, a sliced vocabulary, and a loss
that is the mean next-token cross-entropy.

The family's own configuration is one JSON file (`--model_json`,
`ModelConfig.model_json`): the published keys and the cut —
`experts_held` / `expert_offset` (which of the `n_routed_experts` this
process holds), `vocab_held` (its slice of the vocabulary),
`num_hidden_layers` and `sequence_length`.

The expert layer knows its share: it routes every token over ALL
`n_routed_experts`, computes what its own experts give for the tokens
routed to them plus the shared expert, and leaves out what the absent
experts would add — that partial result is what goes on to the next
layer.  No token is dropped and none is padded to a capacity: the
assignments are sorted by expert and run through `jax.lax.ragged_dot`,
which computes the rows of each held expert's group and no others.
Nothing stands in for the absent chips or their exchange.

Leaves are a flat dict `{dotted name: array}`; `leaf_specs` fixes their
order in the flat key space (the wire contract).  The expert layers'
leaves are stacked on a leading layer axis and scanned; every layer is
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
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from kafka_ps_tpu.models import metrics as metrics_mod
from kafka_ps_tpu.models import task as task_mod
from kafka_ps_tpu.utils.config import ModelConfig

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# what `fit_counted` returns beside the loss, in this order
# (Tracer.count names; runtime/app.py sums them over a drive call)
COUNTERS = ("moe.assignments_here", "moe.assignments_here_grad",
            "moe.assignments_away", "moe.expert_load_max",
            "data.tokens", "data.pad_tokens", "moe.passes_over_bound")


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
        if not (0 <= self.expert_offset and self.expert_offset
                + self.experts_held <= self.n_routed_experts):
            raise ValueError("expert_offset + experts_held must lie inside "
                             "n_routed_experts")
        if not 0 < self.vocab_held <= self.vocab_size:
            raise ValueError("vocab_held must lie inside vocab_size")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")


def resolve_model_json(path: str) -> str:
    """An absolute path as it is; a relative one from the repository's
    root (the benchmark's configurations carry a relative path and are
    run from any directory)."""
    return path if os.path.isabs(path) else os.path.join(REPO_ROOT, path)


@functools.lru_cache(maxsize=None)
def load_config(path: str) -> Glm4Config:
    with open(resolve_model_json(path)) as fh:
        body = json.load(fh)
    if body.get("model_type") != "glm4_moe_lite":
        raise ValueError(f"{path}: model_type {body.get('model_type')!r} is "
                         "not glm4_moe_lite")
    fields = {f.name for f in dataclasses.fields(Glm4Config)}
    missing = [f.name for f in dataclasses.fields(Glm4Config)
               if f.default is dataclasses.MISSING and f.name not in body]
    if missing:
        raise ValueError(f"{path}: missing keys {missing}")
    c = Glm4Config(**{k: v for k, v in body.items() if k in fields})
    c.validate()
    return c


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
    """(dotted name, shape) of every leaf, in flat-layout order.  The
    expert layers are stacked on a leading axis of `num_moe_layers`."""
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


def num_params(c: Glm4Config) -> int:
    return sum(math.prod(s) for _, s in leaf_specs(c))


def unflatten(theta, c: Glm4Config) -> dict:
    """The leaves of a flat vector.  Each is cut out before it is
    shaped (the barrier): left to itself the compiler shapes the WHOLE
    vector as `[P / 64, 64]` to cut a router out of it, a padded 4.7 GB
    copy at the published widths."""
    out, at = {}, 0
    for name, shape in leaf_specs(c):
        n = math.prod(shape)
        out[name] = jax.lax.optimization_barrier(
            theta[at:at + n]).reshape(shape)
        at += n
    return out


def flatten(leaves: dict, c: Glm4Config) -> jax.Array:
    return jnp.concatenate([leaves[name].reshape(-1)
                            for name, _ in leaf_specs(c)])


def sub(leaves: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in leaves.items()
            if k.startswith(prefix)}


def init_leaves(c: Glm4Config) -> dict:
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

def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x, theta: float):
    """Rotate-half RoPE over the whole last axis; positions run along
    axis -3 of `[..., S, heads, d]`."""
    d = x.shape[-1]
    s = x.shape[-3]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def mla(x, p: dict, c: Glm4Config):
    """Latent attention on `[B, S, H]`, causal within a row."""
    with jax.named_scope("kps.mla"):
        b, s, _ = x.shape
        nh, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                          c.qk_rope_head_dim, c.v_head_dim)
        h = rms_norm(x, p["in_norm"], c.rms_norm_eps)
        cq = rms_norm(h @ p["wq_a"], p["q_norm"], c.rms_norm_eps)
        q = (cq @ p["wq_b"]).reshape(b, s, nh, dn + dr)
        kva = h @ p["wkv_a"]
        ckv = rms_norm(kva[..., :c.kv_lora_rank], p["kv_norm"],
                       c.rms_norm_eps)
        k_rope = rope(kva[..., None, c.kv_lora_rank:], c.rope_theta)
        kv = (ckv @ p["wkv_b"]).reshape(b, s, nh, dn + dv)
        q_rope = rope(q[..., dn:], c.rope_theta)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], kv[..., :dn])
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0]))
        scores = scores / math.sqrt(dn + dr)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., dn:])
        return out.reshape(b, s, nh * dv) @ p["wo"]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def route(h, router, bias, c: Glm4Config):
    """Every token over ALL experts → (chosen experts [T, K], their
    weights [T, K]).  float32 at `highest` precision, as the published
    gate computes it."""
    with jax.named_scope("kps.moe.route"):
        s = jax.nn.sigmoid(jnp.dot(h, router,
                                   precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(s + bias, c.num_experts_per_tok)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if c.norm_topk_prob:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return idx, w * c.routed_scaling_factor


def live_rows_bound(slots: int, c: Glm4Config) -> int:
    """How many of a pass's `slots` (token, chosen expert) assignments
    the expert layer places without looking further: twice the even
    share of the experts held here, in whole tiles of 8 rows.  A pass
    that routes more here takes every slot instead (`routed_experts`)."""
    even = slots * c.experts_held / c.n_routed_experts
    return min(slots, 8 * math.ceil(2 * even / 8))


def routed_experts(h, idx, w, p: dict, c: Glm4Config):
    """The part of Σ w_e · SwiGLU_e(h) that the experts held here give
    → ([T, H], (assignments here, largest expert's load, 1 if the pass
    went over `live_rows_bound`)).

    The (token, chosen expert) assignments are sorted by expert, absent
    experts last; the held experts' products run as grouped products
    over the sorted rows (`jax.lax.ragged_dot`: the chip's kernel
    computes the rows of each group and no others, so its work follows
    the routing and no assignment is dropped).  Only the sorted rows up
    to `live_rows_bound` are placed and added back — the live ones come
    first — unless the pass counts more assignments here than that:
    then all T·K slots are, so none is ever dropped.  Tokens are placed
    into sorted order, and results added back, by products with a 0/1
    placement matrix rather than a gather and a scatter-add: a TPU
    scatter costs over a microsecond a row, a fifth of the update when
    it was written so.  Placing is exact at the default precision (one
    term a row, and the grouped product rounds its operand the same
    way); adding back runs at `HIGH`, which carries a float32 in three
    pieces."""
    with jax.named_scope("kps.moe.experts"):
        t, k = idx.shape
        held = c.experts_held
        local = idx - c.expert_offset
        here = (local >= 0) & (local < held)
        # absent experts sort last, into a group that is never computed
        key = jnp.where(here, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        sizes = (key[:, None] == jnp.arange(held)[None, :]).sum(
            axis=0, dtype=jnp.int32)
        n_here = sizes.sum()
        weight = jnp.where(here, w, 0.0).reshape(-1)[order]

        def placed(rows: int):
            """The sum from the first `rows` sorted assignments."""
            live = (jnp.arange(rows) < n_here)[:, None]
            place = jnp.where(live, jax.nn.one_hot(
                order[:rows] // k, t, dtype=jnp.bfloat16), 0)
            xs = jnp.dot(place, h, preferred_element_type=jnp.float32)
            act = (jax.nn.silu(jax.lax.ragged_dot(xs, p["e_gate"], sizes))
                   * jax.lax.ragged_dot(xs, p["e_up"], sizes))
            # rows past the last group are never computed: whatever
            # the kernel leaves there must reach nothing
            y = jnp.where(live, jax.lax.ragged_dot(act, p["e_down"], sizes),
                          0.0)
            return jnp.dot(place.T, y * weight[:rows, None],
                           precision=jax.lax.Precision.HIGH,
                           preferred_element_type=jnp.float32)

        bound = live_rows_bound(t * k, c)
        went_over = n_here > bound
        out = (placed(t * k) if bound == t * k else
               jax.lax.cond(went_over, functools.partial(placed, t * k),
                            functools.partial(placed, bound)))
        return out, jnp.stack([n_here, sizes.max(),
                               went_over.astype(jnp.int32)])


def moe(x, p: dict, c: Glm4Config):
    """Expert layer's MLP half on `[B, S, H]` (already normed) →
    (its output, (assignments here, largest load, went over the
    bound))."""
    b, s, hd = x.shape
    h = x.reshape(b * s, hd)
    idx, w = route(h, p["router"], p["router_bias"], c)
    y, load = routed_experts(h, idx, w, p, c)
    with jax.named_scope("kps.moe.shared"):
        y = y + swiglu(h, p["s_gate"], p["s_up"], p["s_down"])
    return y.reshape(b, s, hd), load


def dense_block(x, p: dict, c: Glm4Config):
    x = x + mla(x, p, c)
    h = rms_norm(x, p["post_norm"], c.rms_norm_eps)
    return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"])


def moe_block(x, p: dict, c: Glm4Config):
    x = x + mla(x, p, c)
    y, load = moe(rms_norm(x, p["post_norm"], c.rms_norm_eps), p, c)
    return x + y, load


def _head_nll(x, norm, head, targets, c: Glm4Config):
    """Final norm, the head over the held slice, and each position's
    negative log-likelihood of its target → ([B, S], logits)."""
    logits = rms_norm(x, norm, c.rms_norm_eps) @ head
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked, logits


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
    x, loads = jax.lax.scan(
        jax.checkpoint(lambda x, p: moe_block(x, p, c)), x,
        sub(leaves, "moe."))
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


def fit_counted(leaves: dict, rows, mask, *, c: Glm4Config, lr: float,
                steps: int):
    """`steps` full-batch SGD steps on a slab → (new leaves, the
    objective at them, COUNTERS of the passes made)."""
    grad = jax.value_and_grad(loss_and_counts, has_aux=True)
    # the steps are written out, not scanned: a scan's carry starts as
    # a copy of the shared leaves and is kept beside each step's result,
    # two more copies of the parameters than the steps themselves need
    new, counts = leaves, []
    for _ in range(steps):
        with jax.named_scope("kps.fit.grad"):
            (_, counted), g = grad(new, rows, mask, c)
        with jax.named_scope("kps.fit.param_step"):
            new = jax.tree.map(lambda a, b: a - lr * b, new, g)
        counts.append(counted)
    counts = jnp.stack(counts)
    with jax.named_scope("kps.fit.loss"):
        loss, last = loss_and_counts(new, rows, mask, c)
    blocks = c.num_moe_layers + c.num_nextn_predict_layers
    per_pass = rows.shape[0] * c.sequence_length * c.num_experts_per_tok \
        * blocks
    here_grad = counts[:, 0].sum()
    here = here_grad + last[0]
    rows_in = mask.sum().astype(jnp.int32)
    stats = jnp.stack([
        here, here_grad, (steps + 1) * per_pass - here,
        counts[:, 1].sum() + last[1],
        rows_in * c.sequence_length,
        (rows.shape[0] - rows_in) * c.sequence_length,
        counts[:, 2].sum() + last[2]]).astype(jnp.int32)
    return new, loss, stats


def evaluate_leaves(leaves: dict, test_rows, c: Glm4Config):
    """Next-token prediction on held-out rows `[n, S + 2]`, one row at
    a time: mean cross-entropy, accuracy, and F1 weighted over the held
    vocabulary by per-class counts (no `[V, V]` matrix)."""
    with jax.named_scope("kps.eval"):
        s = c.sequence_length

        def one(row):
            out = forward(leaves, row[None], c, with_logits=True)
            return out["nll"][0].sum(), jnp.argmax(out["logits"][0], -1)
        nll, preds = jax.lax.map(one, test_rows)
        labels = test_rows[:, 1:s + 1].reshape(-1)
        f1, acc = metrics_mod.weighted_f1_accuracy_by_class(
            preds.reshape(-1), labels, c.vocab_held)
        return metrics_mod.Metrics(f1=f1, accuracy=acc,
                                   loss=nll.sum() / labels.shape[0])


# -- the task ----------------------------------------------------------------------

class Glm4MoeLiteTask(task_mod.FlatFace):
    """MLTask (models/task.py) over `ModelConfig.model_json`."""

    batches_workers = False      # a worker's own products fill the MXU
    row_dtype = np.int32
    counter_names = COUNTERS

    def __init__(self, cfg: ModelConfig):
        if not cfg.model_json:
            raise ValueError("--task glm4_moe_lite needs --model_json FILE "
                             "(the family's own configuration)")
        self.cfg = cfg
        self.arch = load_config(cfg.model_json)

    @property
    def num_params(self) -> int:
        return num_params(self.arch)

    @property
    def row_width(self) -> int:
        return self.arch.row_width

    def init_params(self) -> jax.Array:
        # a leaf at a time, then one concatenation: inside one program
        # the compiler folds init_std into the normal's own constants,
        # and the start would differ from the stated one by a rounding
        return _flatten(init_leaves(self.arch), c=self.arch)

    def unflatten(self, theta) -> dict:
        return unflatten(theta, self.arch)

    def flatten(self, leaves: dict) -> jax.Array:
        return flatten(leaves, self.arch)

    def encode_labels(self, y):
        """A token row's labels are the row itself, shifted: the label
        column carries nothing."""
        return y

    def fit_counted(self, leaves, x, enc, mask):
        return fit_counted(leaves, x, mask, c=self.arch,
                           lr=self.cfg.local_learning_rate,
                           steps=self.cfg.num_max_iter)

    def fit(self, leaves, x, enc, mask):
        new, loss, _ = self.fit_counted(leaves, x, enc, mask)
        return new, loss

    def evaluate_leaves(self, leaves, x_test, y_test) -> metrics_mod.Metrics:
        return evaluate_leaves(leaves, x_test, self.arch)

    def logits(self, leaves, x):
        """`[B, S + 2]` rows → `[B, vocab_held]` scores of the token
        after position S - 1."""
        return forward(leaves, x, self.arch, with_logits=True)["logits"][:, -1]


@functools.partial(jax.jit, static_argnames=("c",))
def _flatten(leaves: dict, *, c: Glm4Config):
    return flatten(leaves, c)

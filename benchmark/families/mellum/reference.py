"""The plain reference of the `mellum` family: what one clock of the
parameter server means for JetBrains Mellum2-12B-A2.5B's published
shape (model_type mellum), in float32 `jax.numpy` at `highest` matmul
precision, one worker, one row, one query head and one held expert at a
time, and the comparison that decides `correct`.

Imports nothing from the program and takes nothing it has made except
what is being judged (its parameter vectors and log rows).  The model's
shape is read from the same file the program is pointed at
(`--model_json`), since that file IS the configuration.

Semantics (benchmark/reference.py has the parameter server's; the same
here): a worker runs k full-batch gradient-descent steps of size lr on
its slab from the shared parameters, its delta is new - old, its logged
loss the objective at the new parameters; the server adds (1/W) * the
sum of the deltas.  The objective of a slab of token rows `t[0..S+1]`
is the mean over unmasked rows and the S positions of the next-token
cross-entropy (position i predicts t[i+1]; t[S+1] is carried and read
by nothing).  Evaluation is the mean next-token cross-entropy, accuracy
and support-weighted F1 of argmax predictions over the held vocabulary
on the held-out rows.

The model.  (c) is what config.json of
JetBrains/Mellum2-12B-A2.5B-Instruct states; (m1)-(m3) are ASSUMED, the
config having no key for them, and stand under `assumed` in the
configuration's file in these words.

  frame      x0 = E[tokens], no scale; the layers; a final RMSNorm (eps
      1e-6 (c)); an untied head over the held slice (c
      tie_word_embeddings false); loss = mean next-token cross-entropy
      over the slice.  (m2) the multi-token-prediction head the model's
      card mentions has no key in the config and is left out.
  layer      two norms a layer, a = x + Attn(N1(x)), y = a + MoE(N2(a)),
      each N an RMSNorm with its own weight.  Every layer's MLP is an
      expert layer (c mlp_layer_types all sparse; intermediate_size
      7168 (c) is read by no layer).
  attention  u the normed input: q = u W_q as [S, heads, head_dim], k =
      u W_k, v = u W_v as [S, kv heads, head_dim] (c), no bias (c
      attention_bias false); (m1) q <- RMSNorm(q) * w_q, k <-
      RMSNorm(k) * w_k over the head's channels, before RoPE: the
      config has no key for it, and neither has the Qwen3-MoE
      configuration class, whose keys these are (norm_topk_prob,
      max_window_layers, use_sliding_window, moe_intermediate_size, an
      explicit head_dim) and whose attention norms q and k so;
      rotate-half RoPE over all the channels on q and k IN EVERY LAYER,
      by the rule rope_parameters (c) gives the layer's kind
      (`_frequencies`): in a sliding layer inv_freq_i = theta^(-2i/d);
      in a full layer YaRN (factor 16, original_max_position_embeddings
      8192, beta_fast 32, beta_slow 1, truncate): d(r) = dim * ln(8192 /
      (2 pi r)) / (2 ln theta), low = max(floor(d(32)), 0), high =
      min(ceil(d(1)), dim - 1), ramp_i = clip((i - low) / (high - low),
      0, 1), inv_freq_i = (1 - ramp_i) * theta^(-2i/d) + ramp_i *
      theta^(-2i/d) / 16, and cos and sin EACH times attention_factor
      (c); scores q.k / sqrt(head_dim), heads / kv heads query heads to
      a KV head (query head h reads KV head h // (heads / kv heads));
      query i sees key j iff j <= i, and in a sliding layer also i - j
      < sliding_window (c: the key itself and the window - 1 before
      it); softmax; out = (P v) W_o.  ATTENTION AS ITS DEFINITION
      (`_attention`): the whole [S, S] score matrix of a head with the
      mask written as those two inequalities, no blocks, no running
      maximum.
  expert layer  p = softmax(u W_r) over ALL num_experts (c); a token's
      experts are the num_experts_per_tok largest of p; weights p at
      the chosen, / their sum (c norm_topk_prob); no bias, no scale;
      expert e the SwiGLU form (c hidden_act silu) at
      moe_intermediate_size; no shared expert; y = the sum over the
      chosen experts THAT ARE HELD HERE (expert_offset .. +
      experts_held).  What the absent experts would add is left out,
      here as in the program: this chip's share of an expert-parallel
      group (the guide's cut).  (m3) no auxiliary router loss: its
      coefficient is not in the config.

Further assumed: initialisation normal(0, init_std) from the model
file's init_seed, norms at one; float32 parameters and plain SGD.

How it is computed: layers, rows, query heads and held experts are
loops; every layer, every row and every head is recomputed in the
backward pass (`jax.checkpoint`): it changes no value, it lets the
reference fit the chip beside its own four copies of the parameters.
The routing is dense and one-hot, no sort and no kernel: the routed
experts are computed an expert at a time over every token, under a
weight that is zero where the expert was not chosen (`_experts`) — the
plain form of the same sum, four times the routed work.

The flat layout (the wire contract, in this order): embed [V,H]; the
layers l<i>.{in_norm, wq, wk, wv, q_norm, k_norm, wo, post_attn_norm,
router [H,E], e_gate, e_up [held,H,I], e_down [held,I,H]}; final_norm;
head [H,V].  Weights multiply from the right (x @ W).

benchmark/run.py's docstring has the interface it calls.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"
LOG_COLUMN = {"loss": "loss", "f1": "fMeasure", "accuracy": "accuracy"}
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
SLIDING, FULL = "sliding_attention", "full_attention"
# the slabs of the last `Reference.run`, host arrays: `param_gap` counts
# on them the routing choices that differ between two parameter vectors
_LAST_SLABS: list = []


@dataclasses.dataclass(frozen=True)
class Shapes:
    hidden_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    layer_types: tuple
    sliding_window: int
    num_hidden_layers: int
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_sliding: tuple         # rope_parameters of a kind, sorted items
    rope_full: tuple
    experts_held: int
    expert_offset: int
    vocab_held: int
    sequence_length: int
    init_std: float
    init_seed: int
    local_iterations: int
    local_lr: float
    num_workers: int

    def layer(self) -> list[tuple[str, tuple[int, ...]]]:
        h, d = self.hidden_size, self.head_dim
        q, kv = self.num_attention_heads * d, self.num_key_value_heads * d
        i, e = self.moe_intermediate_size, self.experts_held
        return [("in_norm", (h,)), ("wq", (h, q)), ("wk", (h, kv)),
                ("wv", (h, kv)), ("q_norm", (d,)), ("k_norm", (d,)),
                ("wo", (q, h)), ("post_attn_norm", (h,)),
                ("router", (h, self.num_experts)),
                ("e_gate", (e, h, i)), ("e_up", (e, h, i)),
                ("e_down", (e, i, h))]

    def leaves(self) -> list[tuple[str, tuple[int, ...]]]:
        out = [("embed", (self.vocab_held, self.hidden_size))]
        for i in range(self.num_hidden_layers):
            out += [(f"l{i}.{n}", s) for n, s in self.layer()]
        return out + [("final_norm", (self.hidden_size,)),
                      ("head", (self.hidden_size, self.vocab_held))]

    @property
    def num_params(self) -> int:
        return sum(math.prod(s) for _, s in self.leaves())


def shapes(cfg) -> Shapes:
    """The reference's view of the CLI's configuration: the model file
    it names (a relative path from the repository's root) and the local
    solver's flags."""
    path = cfg.model.model_json
    with open(path if os.path.isabs(path) else os.path.join(_ROOT, path)) \
            as fh:
        body = json.load(fh)
    assert len(body["layer_types"]) == body["num_hidden_layers"]
    assert set(body["layer_types"]) <= {SLIDING, FULL}
    assert set(body["mlp_layer_types"]) == {"sparse"}, "every layer sparse"
    assert body["hidden_act"] == "silu" and not body["attention_bias"]
    assert not body["tie_word_embeddings"]
    rules = body["rope_parameters"]
    assert rules[SLIDING]["rope_type"] == "default"
    assert rules[FULL]["rope_type"] == "yarn"
    keys = {f.name for f in dataclasses.fields(Shapes)}
    body = dict(body, layer_types=tuple(body["layer_types"]),
                rope_sliding=tuple(sorted(rules[SLIDING].items())),
                rope_full=tuple(sorted(rules[FULL].items())))
    return Shapes(local_iterations=cfg.model.num_max_iter,
                  local_lr=cfg.model.local_learning_rate,
                  num_workers=cfg.num_workers,
                  **{k: v for k, v in body.items() if k in keys})


def split(theta, s: Shapes) -> dict:
    """{leaf name: its part of a flat vector, shaped} (views)."""
    out, at = {}, 0
    for name, shape in s.leaves():
        n = math.prod(shape)
        out[name] = theta[at:at + n].reshape(shape)
        at += n
    return out


def join(leaves: dict, s: Shapes) -> np.ndarray:
    """The flat host vector of device (or host) leaves."""
    return np.concatenate([np.asarray(leaves[name]).reshape(-1)
                           for name, _ in s.leaves()])


def init_params(s: Shapes) -> np.ndarray:
    """The deployment's stated start, as a host vector, every leaf from
    PRNGKey(init_seed) folded with its place in the layout: matrices
    normal(0, init_std), norms one."""
    key = jax.random.PRNGKey(s.init_seed)
    parts = []
    for at, (name, shape) in enumerate(s.leaves()):
        if name.endswith("norm"):
            leaf = np.ones(shape, np.float32)
        else:
            leaf = s.init_std * jax.random.normal(
                jax.random.fold_in(key, at), shape, jnp.float32)
        parts.append(np.asarray(leaf).reshape(-1))
    return np.concatenate(parts)


# -- the model -----------------------------------------------------------------

def _norm(x, w, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _frequencies(rule: dict, dim: int, yarn: bool):
    """(the dim / 2 angular frequencies, what cos and sin are each
    multiplied by) of one kind of layer's rope_parameters, as the
    module's head writes them out; `yarn` False is the control that
    leaves the blend and the factor out of a full layer."""
    theta = float(rule["rope_theta"])
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / dim)
    if rule["rope_type"] == "default" or not yarn:
        return plain, 1.0
    positions = rule["original_max_position_embeddings"]

    def d(turns):
        return dim * math.log(positions / (2 * math.pi * turns)) / (
            2 * math.log(theta))
    low = max(math.floor(d(rule["beta_fast"])), 0)
    high = min(math.ceil(d(rule["beta_slow"])), dim - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return ((1.0 - ramp) * plain + ramp * plain / rule["factor"],
            rule["attention_factor"])


def _rotate(x, freq, factor):
    """Rotate-half RoPE on `[S, heads, d]`: channel c < d/2 and channel
    c + d/2 turn together by the angle position * freq_c, cos and sin
    each times `factor`."""
    n, _, d = x.shape
    half = d // 2
    ang = jnp.arange(n, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = factor * jnp.cos(ang), factor * jnp.sin(ang)
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1)


def _attention(u, p, s: Shapes, sliding: bool, k: dict):
    """One row `[S, H]`, already normed; a query head at a time, each
    against the whole [S, S] score matrix of its key/value head."""
    n, nh, d = u.shape[0], s.num_attention_heads, s.head_dim
    per_kv = nh // s.num_key_value_heads
    q = (u @ p["wq"]).reshape(n, nh, d)
    key = (u @ p["wk"]).reshape(n, s.num_key_value_heads, d)
    val = (u @ p["wv"]).reshape(n, s.num_key_value_heads, d)
    q = _norm(q, p["q_norm"], s.rms_norm_eps)
    key = _norm(key, p["k_norm"], s.rms_norm_eps)
    freq, factor = _frequencies(
        dict(s.rope_sliding if sliding else s.rope_full), d, k["yarn"])
    q, key = _rotate(q, freq, factor), _rotate(key, freq, factor)
    i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    seen = j <= i
    if sliding and k["window"]:
        seen = seen & (i - j < s.sliding_window)

    def head(args):
        q_h, at = args
        k_h, v_h = key[:, at // per_kv], val[:, at // per_kv]
        scores = jnp.where(seen, (q_h @ k_h.T) / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v_h

    out = jax.lax.map(jax.checkpoint(head),
                      (q.transpose(1, 0, 2), jnp.arange(nh)))
    return out.transpose(1, 0, 2).reshape(n, nh * d) @ p["wo"]


def _swiglu(h, gate, up, down):
    return (_silu(h @ gate) * (h @ up)) @ down


def _chosen(h, p, s: Shapes, k: dict):
    """[T, E] weights of the chosen experts (0 elsewhere), and the 0/1
    choice itself: dense and one-hot."""
    logits = h @ p["router"]
    score = (jax.nn.softmax(logits, axis=-1) if k["softmax_router"]
             else jax.nn.sigmoid(logits))
    picked = jnp.argsort(-score, axis=-1)[:, :k["top_k"]]
    choice = jax.nn.one_hot(picked, s.num_experts,
                            dtype=jnp.float32).sum(axis=1)
    w = score * choice
    if s.norm_topk_prob and k["norm_topk"]:
        w = w / w.sum(axis=-1, keepdims=True)
    return w, choice


def _experts(h, p, s: Shapes, k: dict):
    """The held experts' part of the layer for the tokens `h` [T, H]: a
    loop over the held experts, each run over EVERY token and weighted
    by the token's weight for it, which is zero where it was not chosen
    — the plain form of the sum."""
    w, choice = _chosen(h, p, s, k)
    held = slice(s.expert_offset, s.expert_offset + s.experts_held)

    def expert(y, e):
        gate, up, down, weight = e
        return y + weight[:, None] * _swiglu(h, gate, up, down), None

    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(h),
                        (p["e_gate"], p["e_up"], p["e_down"], w[:, held].T))
    return y, choice


def _layer(x, p, s: Shapes, k: dict, kind: str):
    eps = s.rms_norm_eps
    a = x + _attention(_norm(x, p["in_norm"], eps), p, s, kind == SLIDING, k)
    y, choice = _experts(_norm(a, p["post_attn_norm"], eps), p, s, k)
    return a + y, choice


def _nll(x, norm, head, targets, s: Shapes):
    logits = _norm(x, norm, s.rms_norm_eps) @ head
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0], logits


def _sub(p: dict, prefix: str) -> dict:
    return {n[len(prefix):]: v for n, v in p.items() if n.startswith(prefix)}


def _row(p: dict, row, s: Shapes, k: dict):
    """One row of S + 2 tokens -> (next-token nll [S], logits [S, V],
    the expert layers' choices [layers, S, E]).  The layers are a loop
    in their published order; each is recomputed in the backward
    pass."""
    n = s.sequence_length
    x = p["embed"][row[:n]]
    choices = []
    for i, kind in enumerate(s.layer_types):
        x, choice = jax.checkpoint(
            lambda x, q, kind=kind: _layer(x, q, s, k, kind))(
                x, _sub(p, f"l{i}."))
        choices.append(choice)
    nll, logits = _nll(x, p["final_norm"], p["head"], row[1:n + 1], s)
    return nll, logits, jnp.stack(choices)


def _objective(p: dict, rows, mask, s: Shapes, k: dict):
    """Mean over the unmasked rows' positions, a row at a time, in the
    backward pass too."""
    def one(row):
        return _row(p, row, s, k)[0].sum()
    losses = jax.lax.map(jax.checkpoint(one), rows)
    return (losses * mask).sum() / (jnp.maximum(mask.sum(), 1.0)
                                    * s.sequence_length)


def _held_in(dtype):
    if dtype is None:
        return lambda a: a
    return lambda a: a.astype(dtype).astype(jnp.float32)


class Reference:
    """Jitted once per cell; every call under `highest` precision.  On
    the device at once: the shared parameters, the running sum of
    deltas, one worker's working copy and its gradient.  `run` returns
    host arrays and keeps nothing on the device.

    The keywords exist for the CONTROLS only (benchmark/control.py):
    the same reference with one thing a faster or a mistaken program
    would do."""

    def __init__(self, shapes: Shapes, theta_dtype=None, fewer_experts=0,
                 window=True, yarn=True, softmax_router=True,
                 norm_topk=True):
        s = self.shapes = shapes
        k = self.switches = {
            "top_k": s.num_experts_per_tok - fewer_experts,
            "window": window, "yarn": yarn,
            "softmax_router": softmax_router, "norm_topk": norm_topk}
        self._store = _held_in(theta_dtype)
        self._grad = jax.jit(jax.grad(
            lambda p, rows, mask: _objective(p, rows, mask, s, k)))
        self._loss = jax.jit(
            lambda p, rows, mask: _objective(p, rows, mask, s, k))
        self._row = jax.jit(lambda p, row: _row(p, row, s, k))
        self._step = jax.jit(
            lambda p, g: jax.tree.map(lambda a, b: a - s.local_lr * b, p, g),
            donate_argnums=1)
        self._add_delta = jax.jit(
            lambda total, new, old: jax.tree.map(
                lambda t, n, o: t + (n - o), total, new, old),
            donate_argnums=0)
        self._apply = jax.jit(
            lambda theta, total: jax.tree.map(
                lambda a, d: self._store(a + d / s.num_workers), theta,
                total),
            donate_argnums=0)

    def _device(self, theta) -> dict:
        return {n: self._store(jnp.asarray(v, jnp.float32))
                for n, v in split(np.asarray(theta), self.shapes).items()}

    def run(self, theta0, slabs, clocks: int, keep_every: int = 1):
        """`clocks` BSP clocks from theta0 over every worker's (rows, _,
        mask) slab: ([theta after every `keep_every`-th clock] as host
        vectors, [mean of the workers' losses, a clock])."""
        _LAST_SLABS[:] = [(np.asarray(x), np.asarray(m))
                          for x, _, m in slabs]
        thetas, losses, t0 = [], [], time.time()
        with jax.default_matmul_precision(PRECISION):
            theta = self._device(theta0)
            for done in range(1, clocks + 1):
                total = jax.tree.map(jnp.zeros_like, theta)
                of_clock = []
                for rows, mask in _LAST_SLABS:
                    rows, mask = jnp.asarray(rows), jnp.asarray(mask)
                    new = theta
                    for _ in range(self.shapes.local_iterations):
                        new = self._step(new, self._grad(new, rows, mask))
                    of_clock.append(self._loss(new, rows, mask))
                    # wait for each worker: the dispatch queue would
                    # otherwise hold every worker's buffers at once
                    total = jax.block_until_ready(
                        self._add_delta(total, new, theta))
                    del new
                theta = self._apply(theta, total)
                losses.append(float(np.mean([float(v) for v in of_clock])))
                if done % keep_every == 0:
                    thetas.append(join(theta, self.shapes))
                if done in (1, clocks):
                    print(f"[bench] reference: clock {done} done "
                          f"{time.time() - t0:.1f}s after its start",
                          flush=True)
        return thetas, losses

    def forward_rows(self, theta, rows):
        """Per row: (nll [S], logits argmax [S], choices [layers, S,
        E]), host arrays."""
        out = []
        with jax.default_matmul_precision(PRECISION):
            p = self._device(theta)
            for row in np.asarray(rows):
                nll, logits, choices = self._row(p, jnp.asarray(row))
                out.append((np.asarray(nll), np.asarray(jnp.argmax(logits,
                                                                   -1)),
                            np.asarray(choices)))
        return out

    def evaluate(self, theta, test) -> dict:
        """The held-out rows under `theta`, by LOG_COLUMN's names."""
        s = self.shapes
        rows = np.asarray(test[0])
        got = self.forward_rows(theta, rows)
        labels = rows[:, 1:s.sequence_length + 1].reshape(-1)
        preds = np.concatenate([g[1] for g in got])
        loss = float(np.concatenate([g[0] for g in got]).astype(
            np.float64).mean())
        v = s.vocab_held
        support = np.bincount(labels, minlength=v).astype(np.float64)
        predicted = np.bincount(preds, minlength=v).astype(np.float64)
        tp = np.bincount(labels[preds == labels], minlength=v).astype(
            np.float64)
        precision = tp / np.maximum(predicted, 1.0)
        recall = tp / np.maximum(support, 1.0)
        f1 = 2 * precision * recall / np.maximum(precision + recall, 1e-12)
        return {"loss": loss,
                "f1": float((f1 * support).sum() / support.sum()),
                "accuracy": float(tp.sum() / support.sum())}


# -- the comparison ------------------------------------------------------------

@functools.lru_cache(maxsize=2)
def _plain(s: Shapes) -> Reference:
    return Reference(s)


def routing_differs(theta_a, theta_b, s: Shapes) -> float | None:
    """The share of (token, layer) choices of the last run's first slab
    on which two parameter vectors pick another set of experts: a top-k
    choice is discrete, so a small difference in the parameters can
    flip it, and the flipped token then trains another expert."""
    if not _LAST_SLABS:
        return None
    ref = _plain(s)
    differ = total = 0
    for rows, mask in _LAST_SLABS[:1]:      # the first worker's slab
        live = rows[np.asarray(mask) > 0]
        a = ref.forward_rows(theta_a, live)
        b = ref.forward_rows(theta_b, live)
        for (_, _, ca), (_, _, cb) in zip(a, b):
            differ += int((np.abs(ca - cb).sum(axis=-1) > 0).sum())
            total += ca.shape[0] * ca.shape[1]
    return differ / max(total, 1)


def param_gap(theta_prog, theta_ref, theta0, s: Shapes) -> float:
    """Worst leaf of | ||prog change|| - ||ref change|| | over the
    reference's norm of that leaf's change or of the median leaf's,
    whichever is larger (some leaves hardly move); nan where a leaf of
    the program's is not finite.  A leaf at a time: three float64
    copies of the whole vector would not fit the host.  Beside it,
    printed: the share of routing choices on which the two parameter
    vectors differ."""
    prog, ref, start = (split(np.asarray(t), s)
                        for t in (theta_prog, theta_ref, theta0))
    norms = {}
    for name, _ in s.leaves():
        base = start[name].astype(np.float64)
        norms[name] = (float(np.linalg.norm(prog[name] - base)),
                       float(np.linalg.norm(ref[name] - base)))
    floor = statistics.median(r for _, r in norms.values())
    gaps = {name: abs(got - want) / max(want, floor, 1e-30)
            for name, (got, want) in norms.items()}
    # a leaf that is not finite is the worst there is: its gap is nan,
    # which no limit admits
    where = max(gaps, key=lambda name: (math.isnan(gaps[name]), gaps[name]))
    worst = gaps[where]
    share = routing_differs(theta_prog, theta_ref, s)
    print(f"[bench] reference: worst leaf {where!r} gap {worst!r}; routing "
          f"choices (token, layer) that differ between the two parameter "
          f"vectors: {share!r} of the first worker's slab", flush=True)
    return worst


# the controls of benchmark/control.py: Reference keywords by name, each
# what a faster or a mistaken program would compute, and each has to
# break at least one limit of the cell.
#   theta_bf16      the shared parameters held in bfloat16 between clocks
#                   (half the delta, half the parameter plane)
#   window_ignored  every layer full: the sliding layers' second
#                   inequality dropped (what a core that forgets the
#                   band computes)
#   plain_rope_on_full  the full layer under the sliding layers' rule:
#                   YaRN's blend of the frequencies and attention_factor
#                   on cos and sin left out
#   sigmoid_router  sigmoid scores for the softmax (the other expert
#                   families' router), the same top 8, renormalised
#   top7            one expert a token fewer than published (7 for 8)
#   no_norm_topk    the chosen experts' probabilities not renormalised
#                   (norm_topk_prob off)
CONTROLS = {"theta_bf16": {"theta_dtype": jnp.bfloat16},
            "window_ignored": {"window": False},
            "plain_rope_on_full": {"yarn": False},
            "sigmoid_router": {"softmax_router": False},
            "top7": {"fewer_experts": 1},
            "no_norm_topk": {"norm_topk": False}}

"""The plain reference of the `lfm2-moe` family: what one clock of the
parameter server means for Liquid AI LFM2-24B-A2B's published shape
(model_type lfm2_moe), in float32 `jax.numpy` at `highest` matmul
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

The model.  (c) is what config.json of LiquidAI/LFM2-24B-A2B states;
(m1)-(m5) are ASSUMED, the config having no key for them, and stand
under `assumed` in the configuration's file in these words.

  frame      x0 = E[tokens], no scale; the layers; a final RMSNorm (eps
      norm_eps 1e-5 (c)); logits = N_f(x_L) E^T over the held slice:
      (m2) the head is the embedding transposed, ONE matrix used twice —
      the lfm2_moe configuration class's default, and the catalog's
      config (which drops keys that say nothing of shape) shows no key
      against it; loss = mean next-token cross-entropy over the slice.
      (m5) no auxiliary router loss.
  layer      two norms a layer, a = x + Op(N_op(x)), y = a + FF(N_ff(a)),
      each N an RMSNorm with its own weight (operator_norm, ffn_norm).
      layer_types (c) says which Op: conv or full_attention.  FF is a
      dense SwiGLU MLP at intermediate_size in the leading
      num_dense_layers layers (c), an expert layer in the others.
  conv       u the normed input: [B | C | z] = u W_in, three streams of
      hidden_size channels IN THIS ORDER, no bias; g = B * z; a causal
      depthwise filter of conv_L_cache (c: 3) taps along the row, c_t =
      w[:,0] * g_(t-2) + w[:,1] * g_(t-1) + w[:,2] * g_t with g = 0
      before the row's start, no bias (c conv_bias false), no
      activation; Op = (C * c) W_out.  THE CONVOLUTION AS ITS
      DEFINITION (`_conv`): an explicit sum over the taps on a row
      padded with conv_L_cache - 1 zeros in front.
  attention  q = u W_q as [S, heads, 64], k = u W_k, v = u W_v as [S, kv
      heads, 64] (c: head size hidden_size / num_attention_heads), no
      bias; (m1) q <- RMSNorm(q) * w_qn, k <- RMSNorm(k) * w_kn over the
      head's 64 channels, eps norm_eps, before RoPE: the public lfm2_moe
      attention has it and no key switches it; rotate-half RoPE over
      all 64 channels, inv_freq_i = theta^(-2i/64), theta 1,000,000 (c
      rope_parameters, default), positions 0..S-1; scores q.k /
      sqrt(64), query head h reads KV head h // (heads / kv heads);
      query i sees key j iff j <= i; softmax; out = (P v) W_o.
      ATTENTION AS ITS DEFINITION (`_attention`): the whole [S, S] score
      matrix of a head with the mask written as that inequality, no
      blocks, no running maximum, a head at a time.
  dense MLP  (silu(h W_1) * (h W_3)) W_2 at intermediate_size (c); the
      leaves are named w_gate, w_up, w_down.
  expert layer  s = sigmoid(h W_r) over ALL num_experts (c); a token's
      experts are the num_experts_per_tok largest of s + b (c
      use_expert_bias; (m3) b is a leaf held at 0 that takes no
      gradient: its update is a training recipe no key defines);
      weights s at the chosen / (their sum + 1e-6) (c norm_topk_prob;
      (m4) the 1e-6 is the public code's, no key), times
      routed_scaling_factor (c: 1); expert e the SwiGLU form at
      moe_intermediate_size; no shared expert; y = the sum over the
      chosen experts THAT ARE HELD HERE (expert_offset .. +
      experts_held).  What the absent experts would add is left out,
      here as in the program: this chip's share of an expert-parallel
      group (the guide's cut).

Further assumed: initialisation normal(0, init_std) from the model
file's init_seed — the convolution's taps too (there is no silu behind
them to flatten a normal start, unlike the nemotron-h family's uniform
taps) — norms at one, the selection bias zero; float32 parameters and
plain SGD.

How it is computed: layers, rows, query heads and held experts are
loops; every layer, every row and every head is recomputed in the
backward pass (`jax.checkpoint`): it changes no value, it lets the
reference fit the chip beside its own four copies of the parameters.
The routing is dense and one-hot, no sort and no kernel: the routed
experts are computed an expert at a time over every token, under a
weight that is zero where the expert was not chosen (`_experts`) — the
plain form of the same sum, sixteen times the routed work at an eighth
held and 4 of 64 chosen.

The flat layout (the wire contract, in this order): embed [V,H]; the
layers l<i>.{operator_norm, then a conv layer's w_in [H,3H], conv
[H,L], w_out [H,H] or an attention layer's wq, wk, wv, q_norm, k_norm,
wo, then ffn_norm, then a dense layer's w_gate, w_up, w_down or an
expert layer's router [H,E], router_bias [E], e_gate, e_up [held,H,I],
e_down [held,I,H]}; final_norm.  There is no head leaf.  Weights
multiply from the right (x @ W).

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
CONV, FULL = "conv", "full_attention"
# the slabs of the last `Reference.run`, host arrays: `param_gap` counts
# on them the routing choices that differ between two parameter vectors
_LAST_SLABS: list = []


@dataclasses.dataclass(frozen=True)
class Shapes:
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
    norm_eps: float
    rope_theta: float
    experts_held: int
    expert_offset: int
    vocab_held: int
    sequence_length: int
    init_std: float
    init_seed: int
    local_iterations: int
    local_lr: float
    num_workers: int

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def layer(self, kind: str, dense: bool
              ) -> list[tuple[str, tuple[int, ...]]]:
        h, d = self.hidden_size, self.head_dim
        out = [("operator_norm", (h,))]
        if kind == CONV:
            out += [("w_in", (h, 3 * h)), ("conv", (h, self.conv_L_cache)),
                    ("w_out", (h, h))]
        else:
            q = self.num_attention_heads * d
            kv = self.num_key_value_heads * d
            out += [("wq", (h, q)), ("wk", (h, kv)), ("wv", (h, kv)),
                    ("q_norm", (d,)), ("k_norm", (d,)), ("wo", (q, h))]
        out += [("ffn_norm", (h,))]
        if dense:
            i = self.intermediate_size
            return out + [("w_gate", (h, i)), ("w_up", (h, i)),
                          ("w_down", (i, h))]
        i, e = self.moe_intermediate_size, self.experts_held
        return out + [("router", (h, self.num_experts)),
                      ("router_bias", (self.num_experts,)),
                      ("e_gate", (e, h, i)), ("e_up", (e, h, i)),
                      ("e_down", (e, i, h))]

    def leaves(self) -> list[tuple[str, tuple[int, ...]]]:
        out = [("embed", (self.vocab_held, self.hidden_size))]
        for i, kind in enumerate(self.layer_types):
            out += [(f"l{i}.{n}", s)
                    for n, s in self.layer(kind, i < self.num_dense_layers)]
        return out + [("final_norm", (self.hidden_size,))]

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
    assert set(body["layer_types"]) <= {CONV, FULL}
    assert not body["conv_bias"], "no bias on the convolution"
    rule = body["rope_parameters"]
    assert rule["rope_type"] == "default"
    keys = {f.name for f in dataclasses.fields(Shapes)}
    body = dict(body, layer_types=tuple(body["layer_types"]),
                rope_theta=float(rule["rope_theta"]))
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
    PRNGKey(init_seed) folded with its place in the layout: matrices and
    taps normal(0, init_std), norms one, the selection bias zero."""
    key = jax.random.PRNGKey(s.init_seed)
    parts = []
    for at, (name, shape) in enumerate(s.leaves()):
        last = name.rsplit(".", 1)[-1]
        if last.endswith("norm"):
            leaf = np.ones(shape, np.float32)
        elif last == "router_bias":
            leaf = np.zeros(shape, np.float32)
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


def _conv(g, w, reversed_taps: bool):
    """The causal depthwise filter on one row `[S, C]` with taps `w`
    `[C, L]`, as its definition: the row padded with L - 1 zeros in
    front, out_t = sum over j of w[:, j] * padded_(t + j), so that w[:,
    L - 1] weighs the token itself.  `reversed_taps` is the control that
    reads the taps the other way round (w[:, 0] on the token itself)."""
    n, taps = g.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, g.shape[1]), g.dtype), g])
    if reversed_taps:
        w = w[:, ::-1]
    return sum(padded[j:j + n] * w[:, j] for j in range(taps))


def _short_conv(u, p, s: Shapes, k: dict):
    """One row `[S, H]`, already normed."""
    h = s.hidden_size
    bcz = u @ p["w_in"]
    b_gate, c_gate, z = bcz[:, :h], bcz[:, h:2 * h], bcz[:, 2 * h:]
    g = b_gate * z if k["b_gate"] else z
    return (c_gate * _conv(g, p["conv"], k["reversed_taps"])) @ p["w_out"]


def _rotate(x, theta: float):
    """Rotate-half RoPE on `[S, heads, d]`: channel c < d/2 and channel
    c + d/2 turn together by the angle position * theta^(-2c/d)."""
    n, _, d = x.shape
    half = d // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None, None] * freq
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], axis=-1)


def _attention(u, p, s: Shapes, k: dict):
    """One row `[S, H]`, already normed; a query head at a time, each
    against the whole [S, S] score matrix of its key/value head."""
    n, nh, d = u.shape[0], s.num_attention_heads, s.head_dim
    per_kv = nh // s.num_key_value_heads
    q = (u @ p["wq"]).reshape(n, nh, d)
    key = (u @ p["wk"]).reshape(n, s.num_key_value_heads, d)
    val = (u @ p["wv"]).reshape(n, s.num_key_value_heads, d)
    if k["qk_norm"]:
        q = _norm(q, p["q_norm"], s.norm_eps)
        key = _norm(key, p["k_norm"], s.norm_eps)
    q, key = _rotate(q, s.rope_theta), _rotate(key, s.rope_theta)
    seen = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]

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
    ranked = score + p["router_bias"] if s.use_expert_bias else score
    picked = jnp.argsort(-ranked, axis=-1)[:, :k["top_k"]]
    choice = jax.nn.one_hot(picked, s.num_experts,
                            dtype=jnp.float32).sum(axis=1)
    w = score * choice
    if s.norm_topk_prob and k["norm_topk"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-6)
    return w * s.routed_scaling_factor, choice


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


def _layer(x, p, s: Shapes, k: dict, kind: str, dense: bool):
    u = _norm(x, p["operator_norm"], s.norm_eps)
    a = x + (_short_conv(u, p, s, k) if kind == CONV
             else _attention(u, p, s, k))
    u = _norm(a, p["ffn_norm"], s.norm_eps)
    if dense:
        return a + _swiglu(u, p["w_gate"], p["w_up"], p["w_down"]), None
    y, choice = _experts(u, p, s, k)
    return a + y, choice


def _nll(x, norm, head, targets, s: Shapes):
    logits = _norm(x, norm, s.norm_eps) @ head
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0], logits


def _sub(p: dict, prefix: str) -> dict:
    return {n[len(prefix):]: v for n, v in p.items() if n.startswith(prefix)}


def _row(p: dict, row, s: Shapes, k: dict):
    """One row of S + 2 tokens -> (next-token nll [S], logits [S, V],
    the expert layers' choices [expert layers, S, E]).  The layers are a
    loop in their published order; each is recomputed in the backward
    pass.  The head is the embedding transposed — the same array, used
    a second time — unless the control's own `head` leaf is there."""
    n = s.sequence_length
    x = p["embed"][row[:n]]
    choices = []
    for i, kind in enumerate(s.layer_types):
        x, choice = jax.checkpoint(
            lambda x, q, kind=kind, dense=i < s.num_dense_layers:
            _layer(x, q, s, k, kind, dense))(x, _sub(p, f"l{i}."))
        if choice is not None:
            choices.append(choice)
    head = p["head"] if "head" in p else p["embed"].T
    nll, logits = _nll(x, p["final_norm"], head, row[1:n + 1], s)
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
                 b_gate=True, reversed_taps=False, qk_norm=True,
                 norm_topk=True, softmax_router=False, untied_head=False):
        s = self.shapes = shapes
        k = self.switches = {
            "top_k": s.num_experts_per_tok - fewer_experts,
            "b_gate": b_gate, "reversed_taps": reversed_taps,
            "qk_norm": qk_norm, "norm_topk": norm_topk,
            "softmax_router": softmax_router}
        self.untied_head = untied_head
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
        s = self.shapes
        p = {n: self._store(jnp.asarray(v, jnp.float32))
             for n, v in split(np.asarray(theta), s).items()}
        if self.untied_head:
            # the control's second matrix: a head of its own, from the
            # key after the layout's last, trained beside the leaves and
            # no part of the flat vector that is compared
            p["head"] = self._store(s.init_std * jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(s.init_seed),
                                   len(s.leaves())),
                (s.hidden_size, s.vocab_held), jnp.float32))
        return p

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
        """Per row: (nll [S], logits argmax [S], choices [expert layers,
        S, E]), host arrays."""
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
    """The share of (token, expert layer) choices of the last run's
    first slab on which two parameter vectors pick another set of
    experts: a top-k choice is discrete, so a small difference in the
    parameters can flip it, and the flipped token then trains another
    expert."""
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
    whichever is larger (some leaves hardly move, the selection bias
    never); nan where a leaf of the program's is not finite.  A leaf at
    a time: three float64 copies of the whole vector would not fit the
    host.  Beside it, printed: the share of routing choices on which
    the two parameter vectors differ."""
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
          f"choices (token, expert layer) that differ between the two "
          f"parameter vectors: {share!r} of the first worker's slab",
          flush=True)
    return worst


# the controls of benchmark/control.py: Reference keywords by name, each
# what a faster or a mistaken program would compute, and each has to
# break at least one limit of the cell.
#   theta_bf16      the shared parameters held in bfloat16 between clocks
#                   (half the delta, half the parameter plane)
#   no_b_gate       the convolution's input gate left out: c = conv3(z)
#                   for conv3(B * z)
#   taps_reversed   the taps read the other way round: w[:, 0] on the
#                   token itself (a convolution for the published
#                   cross-correlation)
#   no_qk_norm      the head-wise norms of q and k left out (m1)
#   top3            one expert a token fewer than published (3 for 4)
#   no_norm_topk    the chosen experts' scores not renormalised
#                   (norm_topk_prob off)
#   softmax_router  softmax probabilities for the sigmoid scores (the
#                   mellum family's router), the same top 4 of score +
#                   bias, renormalised
#   untied_head     a head of its own (a second matrix, from another
#                   key) for the embedding transposed (m2)
CONTROLS = {"theta_bf16": {"theta_dtype": jnp.bfloat16},
            "no_b_gate": {"b_gate": False},
            "taps_reversed": {"reversed_taps": True},
            "no_qk_norm": {"qk_norm": False},
            "top3": {"fewer_experts": 1},
            "no_norm_topk": {"norm_topk": False},
            "softmax_router": {"softmax_router": True},
            "untied_head": {"untied_head": True}}
